"""Per-layer metrics read from the requests: the client's records joined
with the server's span trees (``harness.stats.join``)."""

from __future__ import annotations

from ..harness import stats


def queue_wait_p95_ms(ctx):
    """Arrival at the handler to the start of the request's prefill."""
    vals = [r["queue_ms"] for r in ctx.rows
            if r["ok"] and r.get("queue_ms") is not None]
    return stats.percentile(vals, 95)


def prefix_token_share(ctx):
    """Prompt tokens served from the prefix store over prompt tokens sent."""
    rows = [r for r in ctx.rows if r["ok"] and r.get("prompt_tokens")]
    sent = sum(r["prompt_tokens"] for r in rows)
    if not sent:
        return None
    return 100.0 * sum(r.get("reused_tokens", 0) for r in rows) / sent


def tail_ms(ctx, key, q=95.0):
    """The ``q``th percentile of one timing over all requests sent (a
    failed one counts as the worst): a tail that is reported beside the
    judged metrics because it does not repeat well enough to be one."""
    return stats.tail(ctx.rows, key, q)
