"""Attention with a lower bound: a layer that sees the last ``window``
positions, held in a per-row ring that does not grow; and query-blocked
causal attention for the layers that see everything.

A sliding-window layer allows query ``i`` to see key ``j`` iff ``j <= i``
and ``i - j < window`` (itself and the ``window - 1`` before it). What
such a layer keeps of a row is therefore the last ``window`` positions
and nothing else: a RING of ``window`` fused ``[K | V]`` rows a kv head,
position ``p`` of the row (its own count, left pad taken off) in slot
``p % window``. Keys are cached AFTER norm and rotary, so the order of
the slots does not matter to a softmax, and a slot's position follows
from the row's depth alone: the ring needs no index beside it, and it
means the same wherever the row's positions lie in a batch's cache (a
joiner's roll, a grown batch, a store's copy move it as they move any
row record).

- ``ring_decode_attention``: one position. The new row goes into its
  slot and the query reads the ``min(depth + 1, window)`` valid slots:
  ``window`` rows whatever the depth.
- ``ring_banded_attention``: a call of several positions (a seed's
  prefill, a store's stride, a ragged tail). Keys are the ring put back
  in order, then the call's own; a query block of ``window`` positions
  reads the ``2 window`` keys that can reach it, so a call of ``T``
  positions computes ``T x 2 window`` scores and not ``T x T``, whatever
  its length (a call under a window is one shorter block). The
  ring that comes back holds the last ``window`` valid positions;
  positions a left pad fills are written nowhere.
- ``blocked_causal_attention``: ``ops.attention.causal_attention`` over
  blocks of queries, for a full layer's call of thousands of positions,
  whose ``[heads, T, S]`` scores would not fit: one block's scores live
  at a time.

Scores and the softmax are float32 (``causal_attention``'s contract);
the ring carries the cache's type.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import NEG_INF, causal_attention

# Numerics contract (tools/graftcheck numerics pass): as ``ops.attention``.
PRECISION_CONTRACT = {
    "ring_decode_attention": {"regime": "carried", "exact": True,
                              "casts": ("f32", "carried")},
    "ring_banded_attention": {"regime": "carried", "exact": True,
                              "casts": ("f32", "carried")},
    "blocked_causal_attention": {"regime": "carried", "exact": True,
                                 "casts": ("f32", "carried")},
}

# float32 scores one block of queries may hold, in elements (512 MB)
SCORE_BUDGET = 1 << 27


def _attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
            allowed: jnp.ndarray) -> jnp.ndarray:
    """Grouped-query softmax attention under an explicit mask. q [B, H,
    Tq, hd]; k, v [B, Hkv, Tk, hd]; ``allowed`` [B, Tq, Tk]."""
    b, h, tq, hd = q.shape
    h_kv, tk = k.shape[1], k.shape[2]
    g = h // h_kv
    scores = jnp.einsum("bkgqd,bkud->bkgqu", q.reshape(b, h_kv, g, tq, hd),
                        k, preferred_element_type=jnp.float32)
    scores = scores * (1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)))
    scores = jnp.where(allowed[:, None, None], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqu,bkud->bkgqd", weights.astype(v.dtype), v)
    return out.reshape(b, h, tq, hd)


def _map_query_blocks(fn, q: jnp.ndarray, block: int) -> jnp.ndarray:
    """``fn(q_block [B, H, block, hd], start) -> [B, H, block, hd]``
    over the blocks of ``q`` [B, H, T, hd], one at a time; ``T`` is
    padded up to whole blocks (the padding's results are dropped)."""
    b, h, t, hd = q.shape
    nb = -(-t // block)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, nb * block - t), (0, 0)))
    qb = q.reshape(b, h, nb, block, hd).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(lambda x: fn(x[0], x[1] * block),
                      (qb, jnp.arange(nb, dtype=jnp.int32)))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, nb * block, hd)[:, :, :t]


def blocked_causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                             q_offset=0, kv_length=None,
                             k_valid_from: Optional[jnp.ndarray] = None,
                             ) -> jnp.ndarray:
    """``causal_attention`` with the queries taken a block at a time
    where all of them at once would hold more than ``SCORE_BUDGET``
    scores. A query's result does not depend on its block."""
    b, h, t, _ = q.shape
    fit = max(SCORE_BUDGET // (b * h * k.shape[2]), 8)
    block = 1 << (fit.bit_length() - 1)       # the power of two under it
    if t <= block:
        return causal_attention(q, k, v, q_offset, kv_length, k_valid_from)
    return _map_query_blocks(
        lambda qb, start: causal_attention(qb, k, v, q_offset + start,
                                           kv_length, k_valid_from),
        q, block)


def ring_decode_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                          v_new: jnp.ndarray, ring: jnp.ndarray,
                          depth: jnp.ndarray,
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One position over a window layer. q [B, H, 1, hd]; k_new, v_new
    [B, Hkv, 1, hd]; ``ring`` [B, Hkv, window, 2 hd] of ONE layer;
    ``depth`` [B]: the positions each row held before this one (its own
    count; below zero on a lane that is all pad, which then reads
    nothing it keeps). Returns ``(out [B, H, 1, hd], ring)``."""
    r, hd = ring.shape[2], k_new.shape[-1]
    slots = jnp.arange(r, dtype=jnp.int32)
    row = jnp.concatenate([k_new, v_new], axis=-1).astype(ring.dtype)
    mine = slots[None, :] == jnp.mod(depth, r)[:, None]            # [B, R]
    ring = jnp.where(mine[:, None, :, None], row, ring)
    allowed = slots[None, :] < jnp.minimum(depth + 1, r)[:, None]
    out = _attend(q, ring[..., :hd], ring[..., hd:], allowed[:, None, :])
    return out, ring


def ring_banded_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                          v_new: jnp.ndarray, ring: jnp.ndarray,
                          depth: jnp.ndarray,
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A call of ``T`` positions over a window layer. q [B, H, T, hd];
    k_new, v_new [B, Hkv, T, hd]; ``ring`` [B, Hkv, window, 2 hd] of ONE
    layer; ``depth`` [B]: the positions each row held before the call,
    ``-n`` where the call opens with ``n`` positions of left pad (they
    count as positions below zero: seen by nobody, written nowhere).
    Returns ``(out [B, H, T, hd], ring)``."""
    r, t, hd = ring.shape[2], k_new.shape[2], k_new.shape[-1]
    slots = jnp.arange(r, dtype=jnp.int32)
    # the ring in order: entry u is position depth - window + u
    back = jnp.mod(depth[:, None] + slots[None, :], r)             # [B, R]
    hist = jnp.take_along_axis(ring, back[:, None, :, None], axis=2)
    rows = jnp.concatenate([k_new, v_new], axis=-1).astype(ring.dtype)
    keys = jnp.concatenate([hist, rows], axis=2)                   # [.., R+T, ..]

    # blocks of a window, a call under a window one shorter block (one
    # block of t x (window + t) scores for a short call is no faster on
    # the chip: 381 against 396 us at 256 positions, 377 against 373 at
    # 512; my chip run, PR 37)
    block = min(t, r)
    nb = -(-t // block)
    keys = jnp.pad(keys, ((0, 0), (0, 0), (0, nb * block - t), (0, 0)))
    span = jnp.arange(r + block, dtype=jnp.int32)

    def one_block(qb, start):
        slab = jax.lax.dynamic_slice_in_dim(keys, start, r + block, axis=2)
        at = start + span                     # entry u of keys: depth - R + u
        mine = start + r + jnp.arange(block, dtype=jnp.int32)
        band = ((at[None, :] <= mine[:, None])
                & (mine[:, None] - at[None, :] < r))               # [bq, R+bq]
        real = depth[:, None] - r + at[None, :] >= 0               # [B, R+bq]
        return _attend(qb, slab[..., :hd], slab[..., hd:],
                       band[None] & real[:, None, :])

    out = (one_block(q, jnp.int32(0)) if nb == 1
           else _map_query_blocks(one_block, q, block))

    # slot s now holds the newest position <= last that lies in it, if
    # this call brought it (and it is no pad); else what it held
    last = depth + t - 1                                           # [B]
    newest = last[:, None] - jnp.mod(last[:, None] - slots[None, :], r)
    brought = newest >= jnp.maximum(depth, 0)[:, None]             # [B, R]
    src = jnp.clip(newest - depth[:, None], 0, t - 1)
    fresh = jnp.take_along_axis(rows, src[:, None, :, None], axis=2)
    ring = jnp.where(brought[:, None, :, None], fresh, ring)
    return out, ring
