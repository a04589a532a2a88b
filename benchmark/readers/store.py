"""Per-layer metrics of the prefix store's walk, from its counters
(``prefix.*``: ``PrefixCachingEngine.stats()`` through
``harness/server.py:counters``). A program whose store does not count
its calls (an older commit) gives ``None``."""

from __future__ import annotations

from .counters import counter_delta


def store_tokens_per_call(ctx):
    """Prompt tokens the window's walks forwarded over the program
    calls that forwarded them (strides and tails alike): how wide the
    weight passes ran that stand in front of every live row."""
    tokens = counter_delta(ctx, "prefix.extend_tokens")
    calls = counter_delta(ctx, "prefix.extend_calls")
    if tokens is None or not calls:
        return None
    return tokens / calls
