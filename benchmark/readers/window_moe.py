"""Per-layer metrics of the sliding-window / sparse-expert family.

Counters (``sched.window.positions_held`` / ``positions_seen``: what the
records ALLOCATED to the live rows hold for the sliding-window layers,
bounded by the ring axis of the state slab's leaf, and the depths those
rows have reached, sampled with the pool's; ``sched.moe.*`` as
``readers.latent_moe`` reads them) and the device trace. Bytes and
device time are paired by segment as ``readers.gdn_moe.paired`` pairs
them (``gdn_moe.paired``, which takes ``latent_moe``'s ``whole`` and
``segments_ready_in_slice``: this cell's slice may open on an idle
device too). A program
without these counters (another family, an older commit) or a byte
model without ``window_row`` gives every reader here ``None``.
"""

from __future__ import annotations

from ..harness import window_bytes
from .device import _first
from .gdn_moe import paired


def swa_held_share(ctx):
    """Positions the sliding-window layers hold for the live rows over
    the positions those rows have reached, at the window's fullest
    sample (the one whose rows have reached the most): about ``window /
    depth`` when a window layer holds a window, 100 when it holds a
    depth (the program bounds ``held`` by the room of the leaf it
    allocated, not by the configuration's window)."""
    full = max((s for s in ctx.samples
                if s.get("sched.window.positions_seen")),
               key=lambda s: s["sched.window.positions_seen"], default=None)
    if full is None or "sched.window.positions_held" not in full:
        return None
    return (100.0 * full["sched.window.positions_held"]
            / full["sched.window.positions_seen"])


def swa_moe_step_roofline(ctx, pattern):
    """The time the chip's memory would need for the bytes a decode step
    NEEDS (``harness.window_bytes``: every non-expert weight and the
    head's slice once, the experts that were hit, the live positions'
    keys and values in the full layers, a window of each live row in the
    sliding ones) over the device time the decode programs took, over
    the whole calls of the slice, each with its own segment's bytes.
    Memory-bound at these widths."""
    dev = _first(ctx)
    bm = ctx.bytes_model
    if dev is None or "window_row" not in bm:
        return None
    pairs = [(e, s) for e, s in paired(ctx, dev, pattern)
             if s["experts_hit"] is not None]
    if not pairs:
        return None
    need = sum(s["steps"] * (bm["weights"]
                             + bm["kv_per_token"] * sum(s["live"])
                             + sum(window_bytes.window_per_row(bm, d)
                                   for d in s["live"]))
               + s["experts_hit"] * bm["expert"] for _, s in pairs)
    took_s = sum(e[2] for e, _ in pairs) / 1e9
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / took_s
