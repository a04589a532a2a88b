"""Per-layer metrics read from the program's counters (scheduler, pool),
as deltas over the window or as the peak of samples taken through it."""

from __future__ import annotations

from ..harness import stats


def counter_delta(ctx, key):
    """How far one counter moved over the window."""
    if key not in ctx.counters_after or key not in ctx.counters_before:
        return None
    return ctx.counters_after[key] - ctx.counters_before[key]


def batch_occupancy(ctx):
    """Mean live rows per decode segment: every row records one decode
    span per segment it rode."""
    segments = counter_delta(ctx, "sched.segments")
    if not segments:
        return None
    spans = sum(len(stats.find_spans(t["spans"], "decode"))
                for t in ctx.window_traces)
    return spans / segments


def pool_peak_share(ctx):
    """Blocks in use at the fullest sample over blocks in the pool."""
    peak = max((s.get("pool.blocks_in_use", 0) for s in ctx.samples),
               default=None)
    total = ctx.counters_after.get("pool.blocks_total")
    if peak is None or not total:
        return None
    return 100.0 * peak / total
