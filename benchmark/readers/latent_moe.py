"""Per-layer metrics of the latent-attention / sparse-expert family.

Counters (``sched.moe.*``: the routing sums the decode segments and the
prefills hand back beside their tokens), span labels (``experts_hit`` on
``decode`` spans, ``expert_load_max`` / ``expert_load_mean`` on
``prefill`` spans) and the device trace, where the kernel appears under
its name (``latent_decode_attention``) and the other operations under
their instruction and result shape (the program's named scopes,
``latent_attn``, ``moe_router``, ``moe_experts``, are in the HLO's
metadata, which the profiler's names leave out). A program without them
(another family, an older commit) gives every reader here ``None``.

Bytes and device time are paired by SEGMENT: a ``decode`` span carries
``seg`` (which spans are one segment) and ``ready_ms`` (when its result
existed on the device), and a segment counts for the traced slice when
it became ready inside it: those are the calls the slice timed, in the
same order. A segment's dispatch lies a segment's length or more before
(``PERF.md``, section 7): pairing by it takes other segments' bytes.
Only the calls the slice holds whole are timed (``whole``), each with
its own segment's bytes (``paired``): this family's slice is a second
long, three or four calls.
"""

from __future__ import annotations

import re

from ..harness import latent_bytes, stats, xtrace
from .counters import counter_delta
from .device import _first


def _share(ctx, top, bottom, scale=1.0):
    a, b = counter_delta(ctx, top), counter_delta(ctx, bottom)
    if a is None or not b:
        return None
    return 100.0 * a / (b * scale)


def experts_hit_share(ctx):
    """Held experts some row chose, over held experts x expert layers x
    decode steps: how much of the held experts' weights a step reads."""
    held = ctx.bytes_model.get("held")
    if not held:
        return None
    return _share(ctx, "sched.moe.experts_hit", "sched.moe.layer_forwards",
                  held)


def routed_here_share(ctx):
    """(row, choice) pairs of the decode steps that landed on held
    experts, over all of them: held / total if the router is even."""
    return _share(ctx, "sched.moe.pairs_here", "sched.moe.pairs_routed")


def expert_load_max_over_mean(ctx):
    """Tokens on the fullest held expert over tokens on the average one,
    a layer, over the window's prefills (sums of the span labels)."""
    top = bottom = 0.0
    for t in ctx.window_traces:
        for s in stats.find_spans(t["spans"], "prefill"):
            labels = s.get("labels", {})
            if "expert_load_max" in labels:
                top += labels["expert_load_max"]
                bottom += labels["expert_load_mean"]
    return top / bottom if bottom else None


def _inside(ops, calls):
    """The operations that start inside one of the calls (both sorted
    by nothing in particular; a slice holds a few dozen calls)."""
    spans = [(s, s + d) for _, s, d in calls]
    return [e for e in ops if any(a <= e[1] < b for a, b in spans)]


def whole(ctx, calls):
    """Of ``calls``, those the slice holds from their start to their
    end, in the device's order. The profiler keeps the piece of the
    call that is running when the slice opens or closes, and this
    family's slice is short (the profiler writes 0.1 ms an operation,
    PR 27): among three or four calls a piece counted as a call reads a
    share over 100%, and ``xtrace.whole_calls`` weighs a piece by its
    program's median, which two calls do not give. The first and the
    last call of the slice go with the pieces."""
    first, last = xtrace.window_ns(ctx.trace)
    return sorted((e for e in calls if e[1] > first and e[1] + e[2] < last),
                  key=lambda e: e[1])


def ops_ms_per_step(ctx, op_pattern, module_pattern):
    """Device time of the operations whose SHORT name (instruction,
    result shape, custom-call target: ``xtrace.short_name``) matches
    ``op_pattern``, inside the whole calls of the programs matching
    ``module_pattern``, over the decode steps those ran. The profiler's
    operation names carry no named scope, so a layer's operations are
    told by the widths only that layer has (a metric's data file lists
    them, from the configuration). Loops and branches are left out:
    they span the operations inside them."""
    dev = _first(ctx)
    if dev is None:
        return None
    calls = whole(ctx, xtrace.matching(ctx.trace.modules.get(dev, []),
                                       module_pattern))
    rx = re.compile(op_pattern)
    ops = [e for e in _inside(ctx.trace.ops[dev], calls)
           if not xtrace.is_container(e[0])
           and rx.search(xtrace.short_name(e[0]))]
    if not ops or not calls:
        return None
    return sum(e[2] for e in ops) / 1e6 / (len(calls) * ctx.seg_steps)


_START_SLACK_S = 0.25       # the profiler's start, before the first operation


def slice_unix(ctx):
    """The traced slice on the unix clock. ``trace_unix`` closes when the
    profiler has written the trace out, and at this family's two
    thousand operations a step that takes many times the slice (65 s
    for 1 s, PR 27): the device's own operations say how long it was."""
    lo, hi = ctx.trace_unix
    if ctx.trace is None or not ctx.trace.devices:
        return lo, hi
    first, last = xtrace.window_ns(ctx.trace)
    return lo, min(hi, lo + (last - first) / 1e9 + _START_SLACK_S)


def segments_ready_in_slice(ctx):
    """``{seg: {"steps", "live": [positions per row at mid-segment],
    "experts_hit", "ready"}}`` of the decode segments that became ready
    inside the traced slice."""
    lo, hi = slice_unix(ctx)
    segs = {}
    for t in ctx.window_traces:
        prompt = t.get("labels", {}).get("prompt_tokens")
        if prompt is None:
            continue
        emitted = 1
        for s in sorted(stats.find_spans(t["spans"], "decode"),
                        key=lambda s: s["start_ms"]):
            labels = s.get("labels", {})
            steps = labels.get("steps", 0)
            ready = labels.get("ready_ms")
            if ready is not None and "seg" in labels and \
                    lo <= t["started_unix"] + ready / 1e3 <= hi:
                seg = segs.setdefault(labels["seg"], {
                    "steps": steps, "live": [],
                    "experts_hit": labels.get("experts_hit"),
                    "ready": t["started_unix"] + ready / 1e3})
                seg["live"].append(prompt + emitted + steps / 2.0)
            emitted += steps
    return segs


def paired(ctx, dev, pattern):
    """``[(call, segment)]``: each whole call of the decode programs
    with the segment it ran. A segment is ready when its call ends, so
    the calls that end inside the slice and the segments ready inside it
    are the same, in the same order; the calls that are no whole ones
    (a piece at the slice's start, the first whole call) take their
    segments with them."""
    calls = xtrace.matching(ctx.trace.modules.get(dev, []), pattern)
    kept = whole(ctx, calls)
    if not kept:
        return []
    before = sum(1 for e in calls if e[1] + e[2] <= kept[0][1])
    segs = sorted(segments_ready_in_slice(ctx).values(),
                  key=lambda s: s["ready"])
    return list(zip(kept, segs[before:]))


def latent_moe_step_roofline(ctx, pattern):
    """The time the chip's memory would need for the bytes a decode step
    NEEDS (``harness.latent_bytes``: every non-expert weight and the
    head once, the experts that were hit, the live positions' vectors)
    over the device time the decode programs took, over the whole calls
    of the slice, each with its own segment's bytes. Memory-bound at
    these widths."""
    dev = _first(ctx)
    if dev is None:
        return None
    pairs = [(e, s) for e, s in paired(ctx, dev, pattern)
             if s["experts_hit"] is not None]
    if not pairs:
        return None
    bm = ctx.bytes_model
    need = sum(s["steps"] * (bm["weights"] + bm["kv_per_token"] * sum(s["live"]))
               + s["experts_hit"] * bm["expert"] for _, s in pairs)
    took_s = sum(e[2] for e, _ in pairs) / 1e9
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / took_s


def latent_decode_attention_roofline(ctx, op_pattern, module_pattern):
    """The kernel alone: the larger of bytes over the memory's rate and
    operations over the chip's rate, for the live positions of each
    whole call's segment (one call of the kernel a layer a step), over
    the device time of the kernel's operations inside those calls."""
    dev = _first(ctx)
    if dev is None:
        return None
    pairs = paired(ctx, dev, module_pattern)
    ops = xtrace.matching(_inside(ctx.trace.ops[dev], [e for e, _ in pairs]),
                          op_pattern)
    if not ops:
        return None
    layers = ctx.config["num_hidden_layers"]
    floor_s = 0.0
    for _, s in pairs:
        c = latent_bytes.decode_attention(ctx.config, sum(s["live"]))
        floor_s += s["steps"] * layers * max(
            c["bytes"] / ctx.peaks["hbm_bytes_per_s"],
            c["ops"] / ctx.peaks["bf16_flops"])
    return 100.0 * floor_s / (sum(e[2] for e in ops) / 1e9)
