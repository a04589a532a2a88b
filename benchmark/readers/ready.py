"""Per-layer metrics read from the ready instants and wait causes in the
server's span trees.

A ``prefill`` or ``decode`` span's window is the DISPATCH of its
programs; its ``ready_ms`` label is the instant their result existed on
the device, on the same clock (milliseconds since the request started).
A ``queue_wait`` span's ``closed_ms``, ``slot_ms``, ``pool_ms`` and
``boundary_ms`` labels split its duration by what held the head of the
queue. A program without these labels gives every reader here ``None``.
All readers take the whole window's span trees, not the traced slice.
"""

from __future__ import annotations

from ..harness import stats


def _ready(span):
    return span.get("labels", {}).get("ready_ms")


def _end(span):
    return span["start_ms"] + span["duration_ms"]


def _by_rid(ctx):
    return {t["request_id"]: t for t in ctx.window_traces}


def _instants(trace):
    """``(first prefill's ready, newest decode ready)`` of one request,
    ``None`` where a span or its ready instant is missing."""
    pre = stats.find_spans(trace["spans"], "prefill")
    dec = [_ready(s) for s in stats.find_spans(trace["spans"], "decode")]
    dec = [r for r in dec if r is not None]
    return (_ready(pre[0]) if pre else None), (max(dec) if dec else None)


def first_token_p95_ms(ctx, q=95.0):
    """Due instant to the instant the first token existed: generator
    lateness plus arrival to the first ``prefill`` span's ready instant;
    over all requests sent, a failed one counts as the worst."""
    traces = _by_rid(ctx)
    rows = []
    for r in ctx.rows:
        first = _instants(traces[r["rid"]])[0] if r["rid"] in traces else None
        rows.append(dict(r, first_token_ms=None if first is None
                         else r["lateness_ms"] + first))
    return stats.tail(rows, "first_token_ms", q)


def tpot_ready_p50_ms(ctx, q=50.0):
    """(newest ``decode`` ready - ``prefill`` ready) / (new tokens - 1)
    of each answered request: the gap a streaming client would see."""
    traces = _by_rid(ctx)
    vals = []
    for r in ctx.rows:
        n = r.get("new_tokens") or 0
        if not r["ok"] or n < 2 or r["rid"] not in traces:
            continue
        first, last = _instants(traces[r["rid"]])
        if first is not None and last is not None:
            vals.append((last - first) / (n - 1))
    return stats.percentile(vals, q)


def _segments(ctx):
    """``{seg: (batch, steps, dispatch lead ms)}`` and, for consecutive
    segments of one batch, ``{seg: ms between their ready instants}``:
    both differences are taken inside one request's tree, on one clock
    (consecutive segments of a batch share at least one row)."""
    segs, periods = {}, {}
    for t in ctx.window_traces:
        mine = sorted((s for s in stats.find_spans(t["spans"], "decode")
                       if _ready(s) is not None
                       and "seg" in s["labels"] and "batch" in s["labels"]),
                      key=lambda s: s["labels"]["seg"])
        for prev, s in zip([None] + mine, mine):
            lab = s["labels"]
            segs[lab["seg"]] = (lab["batch"], lab.get("steps"),
                                _ready(s) - _end(s))
            if prev is not None and lab["batch"] == prev["labels"]["batch"] \
                    and lab["seg"] == prev["labels"]["seg"] + 1:
                periods[lab["seg"]] = _ready(s) - _ready(prev)
    return segs, periods


def dispatch_lead_p95_ms(ctx, q=95.0):
    """How far the host runs ahead of the device: per segment, its ready
    instant less the end of its dispatch window."""
    segs, _ = _segments(ctx)
    return stats.percentile([lead for _, _, lead in segs.values()], q)


def segment_period_ms_per_step(ctx, q=50.0):
    """Ready instant to ready instant of consecutive segments of one
    batch, over the later segment's steps: the decode step plus what ran
    between the segments (joiners' chunks, pool movers, host gaps)."""
    segs, periods = _segments(ctx)
    return stats.percentile([ms / segs[k][1] for k, ms in periods.items()
                             if segs[k][1]], q)


def wait_closed_share(ctx):
    """Of the answered requests' queue waits, the share spent behind a
    batch that was closed to admission and had to drain."""
    traces = _by_rid(ctx)
    closed = waited = 0.0
    seen = False
    for r in ctx.rows:
        if not r["ok"] or r["rid"] not in traces:
            continue
        for s in stats.find_spans(traces[r["rid"]]["spans"], "queue_wait"):
            if "closed_ms" in s.get("labels", {}):
                seen = True
                closed += s["labels"]["closed_ms"]
                waited += s["duration_ms"]
    if not seen or not waited:
        return None
    return 100.0 * closed / waited
