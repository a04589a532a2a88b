"""Per-layer metrics of the block-diffusion / sparse-expert family.

Counters (``sched.block.*``: what the rounds of the decode calls
counted: forwards, rounds, commits, positions fixed, forwards times the
rows that took part), span labels on
``decode`` spans (``rounds``, ``tokens``: the row's own yield; ``steps``:
the forwards the call ran, filled when the call is ready) and the device
trace. Bytes and device time are paired by segment
(``readers.pairing``). A program without these counters and labels
(another family, an older commit) gives every reader here ``None``.
"""

from __future__ import annotations

from ..harness import block_bytes, stats
from . import pairing
from .counters import counter_delta


def block_tokens_per_forward(ctx):
    """Positions the rounds fixed over the forwards they ran (denoise
    and commit), a row: ``tokens_fixed`` sums over the rows of a batch,
    so it is divided by the forwards times the rows that took part
    (``row_forwards``). What a pass over the weights yields a row: 4/3
    where every block takes two denoise forwards and a commit."""
    fixed = counter_delta(ctx, "sched.block.tokens_fixed")
    forwards = counter_delta(ctx, "sched.block.row_forwards")
    if fixed is None or not forwards:
        return None
    return fixed / forwards


def _calls(ctx):
    """``{seg: {"rounds", "forwards", "depths": [a live row's depth at
    mid-call, ...]}}`` of the window's decode calls of rounds."""
    calls = {}
    for t in ctx.window_traces:
        prompt = t.get("labels", {}).get("prompt_tokens")
        if prompt is None:
            continue
        emitted = 0
        for s in sorted(stats.find_spans(t["spans"], "decode"),
                        key=lambda s: s["start_ms"]):
            labels = s.get("labels", {})
            if "rounds" not in labels or "steps" not in labels:
                continue
            call = calls.setdefault(labels["seg"], {
                "rounds": labels["rounds"], "forwards": labels["steps"],
                "depths": []})
            call["depths"].append(prompt + emitted + labels["tokens"] / 2.0)
            emitted += labels["tokens"]
    return calls


def block_moe_step_roofline(ctx, pattern):
    """The time the chip's memory would need for the bytes the decode
    calls' forwards NEED (``harness.block_bytes``: the body a forward,
    the head a denoise forward, the held experts that were hit, the live
    rows' keys and values to their depth a forward) over the device time
    of the whole calls of the slice, each with its own segment's bytes:
    the share of the whole step. Memory-bound at these widths."""
    bm = ctx.bytes_model
    if "body" not in bm:
        return None
    calls = _calls(ctx)
    pairs = [(e, s) for e, s in pairing.routed(pairing.paired(ctx, pattern))
             if s["seg"] in calls]

    def need(s):
        c = calls[s["seg"]]
        return block_bytes.call_bytes(
            bm, c["forwards"], c["rounds"], s["experts_hit"],
            c["forwards"] * sum(c["depths"]))
    return pairing.memory_roofline(ctx, pairs, need)
