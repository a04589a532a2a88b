"""Per-layer metrics of the state-space / attention family.

Counters (``sched.state.rows_gathered`` / ``rows_scattered`` /
``row_bytes``: what the state slab's movers carried, counted on the
host), span labels (``steps`` and ``seg`` on ``decode`` spans) and the
device trace, where the state kernel appears under its name
(``ssm_state_update``). Bytes and device time are paired by segment
(``readers.pairing``). A program without these counters or kernel, or a
byte model without ``mixer`` (another family, an older commit), gives
every reader here ``None``.
"""

from __future__ import annotations

from ..harness import ssm_bytes, stats
from . import pairing
from .counters import counter_delta


def ssm_hybrid_step_roofline(ctx, pattern):
    """The time the chip's memory would need for the bytes a decode step
    NEEDS (``harness.ssm_bytes``: every weight and the head once, the
    live rows' state read and written in every layer, the live
    positions' keys and values in every layer) over the device time the
    decode programs took, over the whole calls of the slice, each with
    its own segment's bytes. Memory-bound at these widths."""
    bm = ctx.bytes_model
    if "mixer" not in bm:
        return None
    return pairing.memory_roofline(
        ctx, pairing.paired(ctx, pattern),
        lambda s: s["steps"] * (bm["weights"]
                                + bm["state_per_row"] * len(s["live"])
                                + bm["kv_per_token"] * sum(s["live"])))


def ssm_state_update_roofline(ctx, op_pattern, module_pattern):
    """The state kernel alone: the bytes its live rows' matrices need
    (read and written once a layer a step) over the memory's rate, over
    the device time of the kernel's operations inside the whole calls of
    the slice, told by their SHORT name (``pairing.ops_inside``)."""
    if "mixer" not in ctx.bytes_model:
        return None
    pairs = pairing.paired(ctx, module_pattern)
    ops = pairing.ops_inside(ctx, pairs, op_pattern)
    if not ops:
        return None
    floor_s = 0.0
    for _, s in pairs:
        c = ssm_bytes.state_update(ctx.config, len(s["live"]))
        floor_s += s["steps"] * c["layers"] * max(
            c["bytes"] / ctx.peaks["hbm_bytes_per_s"],
            c["ops"] / ctx.peaks["bf16_flops"])
    return 100.0 * floor_s / (sum(e[2] for e in ops) / 1e9)


def slab_mb_moved_per_step(ctx):
    """Megabytes of row state the slab's movers carried (records
    gathered plus records scattered, times a record's bytes: the delta
    of the program's counters between the window's two reads) over the
    decode steps the window's segments ran (each segment's ``steps``
    once, from the ``decode`` spans of the requests it served): what
    continuous batching costs a model with a row state at a boundary."""
    moved = [counter_delta(ctx, f"sched.state.{k}")
             for k in ("rows_gathered", "rows_scattered")]
    row = ctx.counters_after.get("sched.state.row_bytes")
    if None in moved or not row:
        return None
    steps = {}
    for t in ctx.window_traces:
        for s in stats.find_spans(t["spans"], "decode"):
            labels = s.get("labels", {})
            if "seg" in labels:
                steps[labels["seg"]] = labels.get("steps", 0)
    if not sum(steps.values()):
        return None
    return sum(moved) * row / 1e6 / sum(steps.values())
