"""Per-layer metrics read from the device trace of the traced slice."""

from __future__ import annotations

from ..harness import bytes as bytes_mod
from ..harness import stats, xtrace


def _first(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return ctx.trace.devices[0]


def module_ms_per_step(ctx, pattern, steps_per_call="seg_steps"):
    """Device time of the programs whose name matches ``pattern`` over
    the decode steps they ran (``seg_steps`` steps to a call)."""
    dev = _first(ctx)
    if dev is None:
        return None
    evs = xtrace.matching(ctx.trace.modules.get(dev, []), pattern)
    if not evs:
        return None
    steps = xtrace.whole_calls(evs) * (
        ctx.seg_steps if steps_per_call == "seg_steps"
        else int(steps_per_call))
    return sum(e[2] for e in evs) / 1e6 / steps


def module_ms_p50(ctx, pattern):
    """Median device time of one call of the programs matching ``pattern``."""
    dev = _first(ctx)
    if dev is None:
        return None
    evs = xtrace.matching(ctx.trace.modules.get(dev, []), pattern)
    if not evs:
        return None
    return stats.percentile([e[2] / 1e6 for e in evs], 50)


def op_ms_per_step(ctx, op_pattern, module_pattern):
    """Device time of the operations matching ``op_pattern`` (a kernel, by
    name) over the decode steps of the programs matching
    ``module_pattern``."""
    dev = _first(ctx)
    if dev is None:
        return None
    ops = xtrace.matching(ctx.trace.ops[dev], op_pattern)
    calls = xtrace.matching(ctx.trace.modules.get(dev, []), module_pattern)
    if not ops or not calls:
        return None
    return sum(e[2] for e in ops) / 1e6 / (
        xtrace.whole_calls(calls) * ctx.seg_steps)


def device_idle_share(ctx):
    if _first(ctx) is None:
        return None
    busy, window = xtrace.busy_and_window_s(ctx.trace)
    return 100.0 * (1.0 - busy / window)


def _segments(ctx):
    """Decode segments dispatched inside the traced slice, from the
    request span trees: ``{dispatch instant: [live positions per row at
    mid-segment], steps}``."""
    lo, hi = ctx.trace_unix
    segs = {}
    for t in ctx.window_traces:
        prompt = t.get("labels", {}).get("prompt_tokens")
        if prompt is None:
            continue
        emitted = 1
        for s in sorted(stats.find_spans(t["spans"], "decode"),
                        key=lambda s: s["start_ms"]):
            steps = s.get("labels", {}).get("steps", 0)
            at = t["started_unix"] + s["start_ms"] / 1e3
            if lo <= at <= hi:
                key = (round(at, 2), s["labels"].get("depth"))
                seg = segs.setdefault(key, {"steps": steps, "live": []})
                seg["live"].append(prompt + emitted + steps / 2.0)
            emitted += steps
    return list(segs.values())


def decode_step_roofline(ctx, pattern):
    """The time the chip's memory would need for the bytes a decode step
    NEEDS (``harness.bytes``: every weight once, the live cache positions
    of the rows decoding) over the device time the decode programs took.
    Memory-bound: at these batch widths the step's operations take far
    less than its bytes."""
    dev = _first(ctx)
    if dev is None:
        return None
    evs = xtrace.matching(ctx.trace.modules.get(dev, []), pattern)
    segs = _segments(ctx)
    if not evs or not segs:
        return None
    need = [s["steps"] * bytes_mod.step_bytes(ctx.bytes_model, sum(s["live"]))
            for s in segs]
    floor_s = (sum(need) / len(need)) / ctx.peaks["hbm_bytes_per_s"]
    took_s = sum(e[2] for e in evs) / xtrace.whole_calls(evs) / 1e9
    return 100.0 * floor_s / took_s
