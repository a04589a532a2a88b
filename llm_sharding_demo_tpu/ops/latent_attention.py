"""Latent (compressed-KV) attention in its two forms, and one position's.

The cache of a latent-attention layer holds ONE vector per position,
``[c_kv | k_pe]``: the normalised key/value latent (``rank`` wide) and
the rotary key part every head shares (``rope`` wide), in a row that
may be wider (zeros up to the chip's lane tile). Keys and values
of the heads are linear maps of the latent, ``k_nope = c_kv W_uk`` and
``v = c_kv W_uv`` (per head), so attention can run either way round:

- ``expanded``: make ``k_nope`` and ``v`` of every position and attend
  with ``nope + rope``-wide keys. Right when the keys are new anyway: a
  prefill into a fresh cache, which reads no cache at all.
- ``absorbed``: fold ``W_uk`` into the query (``q_lat = q_nope W_uk^T``,
  ``rank`` wide per head) and ``W_uv`` onto the output, and attend over
  the cached vectors themselves: every head reads the one shared
  ``rank + rope``-wide key whose first ``rank`` are also the value. Right
  against a cache: a decode step (or a continuation chunk) touches
  ``rank + rope`` values a position and never expands the past.

A single query position (a decode step) states the two folds of the
absorbed form as ONE matmul each against ``W_uk`` and ``W_uv`` as the
leaves lie (``_heads_in``, ``_heads_out``): stated per head, the chip's
compiler copies each 4 MB matrix into a head-major layout first, every
layer of every step, and runs the product on the vector unit.

All give the same result up to rounding. Reads of the cache are
bounded by the live depth inside the program: ``absorbed`` picks, by a
``lax.switch`` on the traced depth, the smallest window of
``READ_BUCKET`` times a power of two that covers it (or the whole
cache), so one compiled program serves every depth and a shallow row
does not stream the whole arena.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import NEG_INF, causal_attention

# Numerics contract (tools/graftcheck numerics pass): both forms carry
# the activation dtype through their matmuls and run scores and softmax
# in float32 (as ops.attention does); exact per regime.
PRECISION_CONTRACT = {
    "expanded": {"regime": "carried", "exact": True,
                 "casts": ("f32", "carried")},
    "absorbed": {"regime": "carried", "exact": True,
                 "casts": ("f32", "carried")},
    "write_latent": {"regime": "carried", "exact": True,
                     "casts": ("carried",)},
}

# the smallest read window; each switch branch doubles it
READ_BUCKET = 256
# a single position's folds run as one matmul over ``B x H`` rows up to
# here: every row passes every head's columns, H times the arithmetic,
# which the weights' stream hides while the rows are few (measured to
# 16 rows of 32 heads, PERF.md 6, PR 31) and no longer when they are many
ONE_MATMUL_ROWS = 512


def write_latent(cache: jnp.ndarray, entry: jnp.ndarray, layer_idx,
                 offset) -> jnp.ndarray:
    """``entry`` [B, S, width] into the stacked cache ``[L, B, 1, Smax,
    width]`` at ``(layer_idx, offset)``: an in-place column write on the
    loop-carried buffer (see ``ops.attention.write_kv_layer``)."""
    at = (layer_idx, 0, 0, offset, 0)      # unsigned: no wrap to compute
    return jax.lax.dynamic_update_slice(
        cache, entry[None, :, None].astype(cache.dtype),
        tuple(jnp.asarray(i).astype(jnp.uint32) for i in at))


def expanded(q_nope: jnp.ndarray, q_pe: jnp.ndarray, c_kv: jnp.ndarray,
             k_pe: jnp.ndarray, wuk: jnp.ndarray, wuv: jnp.ndarray,
             pad: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Causal attention over the tokens of this call alone (offset 0).

    q_nope [B, H, S, nope], q_pe [B, H, S, rope] (rotated), c_kv
    [B, S, rank] (normalised), k_pe [B, S, rope] (rotated), wuk/wuv
    [rank, H * nope|v]. Returns [B, H, S, v]."""
    b, h, s, _ = q_nope.shape
    k_nope = (c_kv @ wuk).reshape(b, s, h, -1).transpose(0, 2, 1, 3)
    v = (c_kv @ wuv).reshape(b, s, h, -1).transpose(0, 2, 1, 3)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], (b, h) + k_pe.shape[1:])],
        axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    return causal_attention(q, k, v, q_offset=0, k_valid_from=pad)


def _attend_window(q, layer, scale, rank, offset, pad):
    """q [B, H, S, width] over ``layer`` [B, W, width]: masked softmax
    in float32, values = the first ``rank`` of each cached vector."""
    s, w = q.shape[2], layer.shape[1]
    scores = jnp.einsum("bhsc,bkc->bhsk", q, layer,
                        preferred_element_type=jnp.float32) * scale
    q_pos = offset + jnp.arange(s)[:, None]
    k_pos = jnp.arange(w)[None, :]
    allowed = (k_pos <= q_pos)[None]
    if pad is not None:
        allowed = allowed & (k_pos >= pad[:, None, None])
    scores = jnp.where(allowed[:, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(layer.dtype)
    return jnp.einsum("bhsk,bkc->bhsc", p, layer[..., :rank])


def _own_block(shape):
    """[B, H, H', n] -> whether ``H == H'``, made where it is used."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            == jax.lax.broadcasted_iota(jnp.int32, shape, 2))


def _heads_in(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """``bhn,chn->bhc`` for one position: x [B, H, n], w [rank, H * n]
    as the leaf lies. ONE matmul against ``w`` whole, contracting its
    columns: row ``(b, h)`` holds its head's ``n`` values in block ``h``
    and zeros in the others, so every other head's columns add exact
    zeros. Stated per head (a batch dimension) the compiler copies the
    whole ``w`` into a head-major layout first, every layer, every
    step."""
    b, h, n = x.shape
    rows = jnp.where(_own_block((b, h, h, n)), x[:, :, None, :], 0)
    rows = rows.reshape(b * h, h * n)
    return jax.lax.dot_general(
        rows, w, (((1,), (1,)), ((), ()))).reshape(b, h, -1)


def _heads_out(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """``bhc,chv->bhv`` for one position: x [B, H, rank], w [rank,
    H * v] as the leaf lies. ONE matmul of the ``(b, h)`` rows against
    ``w`` whole, of which each head keeps its own block of columns (the
    sum below has one term). The barrier keeps the product whole: let
    through it, the compiler narrows it to each head's own block, which
    is the per-head form and its copy of ``w`` again."""
    b, h, rank = x.shape
    full = jax.lax.optimization_barrier(x.reshape(b * h, rank) @ w)
    full = full.reshape(b, h, h, -1)
    return jnp.sum(jnp.where(_own_block(full.shape), full, 0), axis=1)


def absorbed(q_nope: jnp.ndarray, q_pe: jnp.ndarray, cache: jnp.ndarray,
             layer_idx, offset, wuk: jnp.ndarray, wuv: jnp.ndarray,
             pad: Optional[jnp.ndarray] = None,
             decode_kernel: Optional[str] = None) -> jnp.ndarray:
    """Attention of ``S`` new queries at positions ``offset + arange(S)``
    over the cached vectors of layer ``layer_idx`` (the new tokens'
    entries already written). ``cache`` is the whole stacked ``[L, B, 1,
    Smax, rank + rope]`` buffer. Returns [B, H, S, v]. A single query
    position folds into and out of the latent by ``_heads_in`` and
    ``_heads_out`` and goes through the Pallas kernel (``ops.
    latent_decode``) when the engine resolved one (``decode_kernel``:
    ``"device"`` or ``"interpret"``); everything else through the
    einsums below."""
    b, h, s, nope = q_nope.shape
    rank = wuk.shape[0]
    smax, width = cache.shape[3], cache.shape[4]
    scale = 1.0 / math.sqrt(nope + q_pe.shape[-1])
    one = s == 1 and b * h <= ONE_MATMUL_ROWS
    wuv3 = wuv.reshape(rank, h, wuv.shape[1] // h)
    if one:
        q_lat = _heads_in(q_nope[:, :, 0], wuk)[:, :, None]
    else:
        q_lat = jnp.einsum("bhsn,chn->bhsc", q_nope,
                           wuk.reshape(rank, h, nope))
    # the cached rows may be wider than [c_kv | k_pe] (zeros up to the
    # lane tile): the query is zero there too
    q = jnp.concatenate([q_lat, q_pe], axis=-1)
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, width - q.shape[-1]),))
    if decode_kernel is not None and s == 1:
        from .latent_decode import latent_decode_attention
        o = latent_decode_attention(
            q[:, :, 0], cache, layer_idx, offset, scale, pad,
            interpret=decode_kernel == "interpret")
        o = o[..., :rank]
        return (_heads_out(o, wuv) if one
                else jnp.einsum("bhc,chv->bhv", o, wuv3))[:, :, None]

    def reader(window):
        def read(q):
            layer = jax.lax.dynamic_slice(
                cache, (layer_idx, 0, 0, 0, 0), (1, b, 1, window, width))
            return _attend_window(q, layer[0, :, 0], scale, rank, offset,
                                  pad)
        return read

    windows = [READ_BUCKET]
    while windows[-1] < smax:
        windows.append(windows[-1] * 2)
    windows[-1] = smax
    which = sum((offset + s > w).astype(jnp.int32) for w in windows[:-1])
    o_lat = jax.lax.switch(which, [reader(w) for w in windows], q)
    if one:
        return _heads_out(o_lat[:, :, 0], wuv)[:, :, None]
    return jnp.einsum("bhsc,chv->bhsv", o_lat, wuv3)
