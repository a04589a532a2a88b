"""Per-layer metrics of the linear-attention / sparse-expert family.

Counters (``sched.state.*``: the state slab's slots beside the paged
pool; ``sched.moe.*`` as ``readers.latent_moe`` reads them), span labels
(``state_restored`` on ``prefill`` spans: the depth a state snapshot
gave a store walk, or 0) and the device trace, where the state kernel
appears under its name (``gdn_state_update``). Bytes and device time are
paired by segment, by ``seg`` and ``ready_ms`` as
``readers.latent_moe.paired`` pairs them, but with the two clocks lined
up from the data (``paired`` here): this cell's slice opens on an idle
device. A program without these counters, labels or kernel (another
family, an older commit) gives every reader here ``None``.
"""

from __future__ import annotations

import re
import types

from ..harness import gdn_bytes, stats, xtrace
from .device import _first
from .latent_moe import _inside, segments_ready_in_slice, whole

# a segment is stamped ready within this long of its call's end
_READY_SLACK_S = 0.05


def state_slab_peak_share(ctx):
    """State-slab slots in use (live rows and snapshots) at the window's
    fullest sample, over the slots there are. The samples and not the
    slab's lifetime ``state.peak``: set-up's widest round fills every
    slot, so the lifetime peak reads 100% in every run."""
    peak = max((s["sched.state.in_use"] for s in ctx.samples
                if "sched.state.in_use" in s), default=None)
    slots = ctx.counters_after.get("sched.state.slots")
    if peak is None or not slots:
        return None
    return 100.0 * peak / slots


def state_restore_share(ctx, prefix_tokens):
    """Of the window's prompts behind a shared prefix, those whose store
    walk restored a state snapshot (``state_restored > 0`` on their
    ``prefill`` span). A prompt is behind a shared prefix when its
    leading ``prefix_tokens`` tokens (the traffic file's) are also
    another answered request's: seeded token contents never collide
    otherwise."""
    heads, mine = {}, {}
    for r in ctx.rows:
        ids = (r.get("text") or "").split()
        if r["ok"] and len(ids) > prefix_tokens:
            mine[r["rid"]] = head = " ".join(ids[:prefix_tokens])
            heads[head] = heads.get(head, 0) + 1
    behind = restored = 0
    labelled = False
    for t in ctx.window_traces:
        spans = stats.find_spans(t["spans"], "prefill")
        if any("state_restored" in s.get("labels", {}) for s in spans):
            labelled = True
        if heads.get(mine.get(t.get("request_id")), 0) < 2:
            continue
        behind += 1
        restored += any(s.get("labels", {}).get("state_restored", 0) > 0
                        for s in spans)
    if not labelled or not behind:
        return None
    return 100.0 * restored / behind


def paired(ctx, dev, pattern):
    """``[(call, segment)]``: each whole call of the decode programs
    with the segment it ran, a segment being ready when its call ends.
    ``latent_moe.paired`` takes the device's first operation for the
    instant the profiler started; at this cell's rate the slice opens in
    a gap between two requests (the same gap in every run: a traffic
    file is one trace) and the device's first operation comes a second
    later. So the offset between the device's clock and the spans' is
    found here: the smallest at which EVERY decode call that ends inside
    the slice ends where a segment became ready (nothing is traced
    before the profiler starts, and whatever runs after that is traced,
    so the smallest is the true one even where segments follow each
    other evenly). No such offset pairs nothing."""
    calls = xtrace.matching(ctx.trace.modules.get(dev, []), pattern)
    kept = whole(ctx, calls)
    if not kept:
        return []
    first, last = xtrace.window_ns(ctx.trace)
    ends = [(e[1] + e[2]) / 1e9 for e in calls if e[1] + e[2] < last]
    segs = sorted(segments_ready_in_slice(types.SimpleNamespace(
        trace=None, trace_unix=ctx.trace_unix,
        window_traces=ctx.window_traces)).values(),
        key=lambda s: s["ready"])

    def seg_at(unix):
        return min(segs, key=lambda s: abs(s["ready"] - unix))

    for off in (s["ready"] - min(ends) for s in segs):
        if first / 1e9 + off >= ctx.trace_unix[0] - _READY_SLACK_S and all(
                abs(seg_at(e + off)["ready"] - e - off) < _READY_SLACK_S
                for e in ends):
            return [(e, seg_at((e[1] + e[2]) / 1e9 + off)) for e in kept]
    return []


def gdn_moe_step_roofline(ctx, pattern):
    """The time the chip's memory would need for the bytes a decode step
    NEEDS (``harness.gdn_bytes``: every non-expert weight and the head
    once, the experts that were hit, the live rows' state read and
    written, the live positions' keys and values) over the device time
    the decode programs took, over the whole calls of the slice, each
    with its own segment's bytes. Memory-bound at these widths."""
    dev = _first(ctx)
    bm = ctx.bytes_model
    if dev is None or "state_per_row" not in bm:
        return None
    pairs = [(e, s) for e, s in paired(ctx, dev, pattern)
             if s["experts_hit"] is not None]
    if not pairs:
        return None
    need = sum(s["steps"] * (bm["weights"]
                             + bm["state_per_row"] * len(s["live"])
                             + bm["kv_per_token"] * sum(s["live"]))
               + s["experts_hit"] * bm["expert"] for _, s in pairs)
    took_s = sum(e[2] for e, _ in pairs) / 1e9
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / took_s


def gdn_state_update_roofline(ctx, op_pattern, module_pattern):
    """The state kernel alone: the bytes its live rows' matrices need
    (read and written once a layer a step) over the memory's rate, over
    the device time of the kernel's operations inside the whole calls of
    the slice. Operations are told by their SHORT name
    (``xtrace.short_name``), not by a search of the event's HLO text,
    which names operands too."""
    dev = _first(ctx)
    if dev is None:
        return None
    pairs = paired(ctx, dev, module_pattern)
    rx = re.compile(op_pattern)
    ops = [e for e in _inside(ctx.trace.ops[dev], [e for e, _ in pairs])
           if not xtrace.is_container(e[0])
           and rx.search(xtrace.short_name(e[0]))]
    if not ops:
        return None
    floor_s = 0.0
    for _, s in pairs:
        c = gdn_bytes.state_update(ctx.config, len(s["live"]))
        floor_s += s["steps"] * c["layers"] * max(
            c["bytes"] / ctx.peaks["hbm_bytes_per_s"],
            c["ops"] / ctx.peaks["bf16_flops"])
    return 100.0 * floor_s / (sum(e[2] for e in ops) / 1e9)
