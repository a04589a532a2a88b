"""Generation by diffusion over blocks: the mask, the choice, the transfer.

A block-diffusion model (SDAR) groups a row's positions in blocks of
``L``, counted from the row's own first token. Position ``i`` attends to
position ``j`` iff ``j // L <= i // L``: causal between blocks,
bidirectional inside one. An answer is made a block at a time: the block
starts as mask tokens, a DENOISE forward gives every position of the
block logits FOR THAT POSITION (no shift), every still-masked position
gets a candidate and a confidence, a transfer rule fixes some of them,
and when no mask is left a COMMIT forward runs the finished block once
more: its keys and values are what the cache keeps.

Three pieces live here, as pure functions the engine's round loop
(``runtime.engine``) and the family (``models.sdar_moe``) share:

- ``attend``: attention of ``T`` new positions (whole blocks, starting
  on a block boundary) over the cached positions before them, all
  visible, and over each other under the block mask. The cached part is
  READ BEFORE the new rows are written, so the write is not consumed by
  a matmul of the same layer and the compiler updates the cache in
  place (``ops.decode_attention`` says what happens otherwise). A
  round's forward (ONE block over a cache that holds something) is
  ``ops.block_decode``'s kernel where the engine resolved a decode
  kernel: it streams each row's span and not the layer's whole slice.
  The XLA form below is every other call's, and the oracle the kernel
  is tested against;
- ``choose``: greedy candidates and their confidences, in float32, the
  mask token's own logit left out of the choice;
- ``transfer``: which masked positions a forward fixes, by rule.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import block_decode
from .attention import NEG_INF, write_kv_layer_fused

# Numerics contract (tools/graftcheck numerics pass): scores, softmax
# and confidences in float32 whatever the regime; the weighted sum in
# the values' type, as ``ops.attention.causal_attention``.
PRECISION_CONTRACT = {
    "attend": {"regime": "carried", "exact": True, "casts": ("f32",)},
    "choose": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "transfer": {"regime": "f32", "exact": True, "casts": ("f32",)},
}

RULES = ("sequential", "low_confidence_static", "low_confidence_dynamic")

# what a call's rounds count beside its routing sums (the tail of the
# family's ``cache_counters``): forwards run (denoise and commit),
# rounds, commit forwards, positions fixed, those of them whose
# confidence passed the threshold, and forwards times the rows that
# took part in them (a lane without a request takes part in none)
COUNTERS = ("block_forwards", "block_rounds", "block_commits",
            "block_tokens_fixed", "block_fixed_over_threshold",
            "block_row_forwards")

# a block position that is still masked, in the ``[B, L]`` int32 block
# the rounds carry: a flag and no token id (a prompt may hold any id)
MASKED = -1


class Options(NamedTuple):
    """How a deployment generates (``serving.app`` sets them from the
    environment, the family's config holds the defaults)."""

    block_length: int
    denoising_steps: int
    confidence_threshold: float
    remasking: str
    mask_token_id: int


def block_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, length: int,
               pad: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``[.., Sq, Sk]`` bool: key ``k_pos`` is in the query's block or
    an earlier one, blocks counted from ``pad`` ([B], the row's first
    token) where given; a key under the pad is no key."""
    if pad is None:
        return (k_pos[None, :] // length) <= (q_pos[:, None] // length)
    q = (q_pos[None, :, None] - pad[:, None, None]) // length
    k = k_pos[None, None, :] - pad[:, None, None]
    return ((k // length) <= q) & (k >= 0)


def _scores(q, k, scale):
    """q [B, H, Sq, hd] x k [B, Hkv, Sk, hd] -> [B, H, Sq, Sk] float32,
    grouped queries riding their kv head (``causal_attention``)."""
    b, h, sq, hd = q.shape
    hkv = k.shape[1]
    s = jnp.einsum("bkgqd,bkud->bkgqu", q.reshape(b, hkv, h // hkv, sq, hd),
                   k, preferred_element_type=jnp.float32) * scale
    return s.reshape(b, h, sq, -1)


def _weighted(p, v):
    b, h, sq, sk = p.shape
    hkv = v.shape[1]
    o = jnp.einsum("bkgqu,bkud->bkgqd",
                   p.astype(v.dtype).reshape(b, hkv, h // hkv, sq, sk), v)
    return o.reshape(b, h, sq, v.shape[-1])


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, length: int,
           kv: Optional[jnp.ndarray] = None, layer_idx=None, offset=0,
           pad: Optional[jnp.ndarray] = None, fresh: bool = False,
           kernel: Optional[str] = None,
           ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """q [B, H, T, hd], k/v [B, Hkv, T, hd]: the new positions ``offset
    + arange(T)``. Without ``kv`` they attend to each other under the
    block mask. With ``kv`` (the fused ``[layers, B, Hkv, S, 2 hd]``
    cache) they also see every cached position of ``[pad, offset)``
    (``offset`` is a block boundary of every row, so all of them are
    earlier blocks), unless the cache is ``fresh`` and holds none; the
    new rows are then written at ``offset``. Returns ``(out, kv)``.

    ``kernel`` is the decode kernel the engine resolved (``"device"``,
    ``"interpret"`` or ``None``). Which calls take it follows from the
    shapes: ONE block (``T == length``) over a cache that is not fresh
    and has the kernel's geometry; a prefill or a stride of the store
    (``T > length``) reads the cache once a request, not once a forward,
    and keeps the form below."""
    t, hd = q.shape[2], q.shape[3]
    if (kernel is not None and kv is not None and not fresh and t == length
            and block_decode.eligible(kv.shape[3], hd)):
        return block_decode.block_decode_attention(
            q, k, v, kv, layer_idx, offset, pad,
            interpret=kernel == "interpret")
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, dtype=jnp.float32))
    pos = offset + jnp.arange(t)
    own = block_mask(pos, pos, length, pad)
    own = own[None, None] if pad is None else own[:, None]
    s_new = jnp.where(own, _scores(q, k, scale), NEG_INF)
    if kv is None or fresh:
        out = _weighted(jax.nn.softmax(s_new, axis=-1), v)
    else:
        layer = jax.lax.dynamic_index_in_dim(kv, layer_idx, axis=0,
                                             keepdims=False)
        held = jnp.arange(layer.shape[2])
        seen = held[None, :] < offset
        if pad is not None:
            seen = seen & (held[None, :] >= pad[:, None])
        s_old = jnp.where(seen[:, None, None, :],
                          _scores(q, layer[..., :hd], scale), NEG_INF)
        p = jax.nn.softmax(jnp.concatenate([s_old, s_new], axis=-1), axis=-1)
        split = layer.shape[2]
        out = (_weighted(p[..., :split], layer[..., hd:])
               + _weighted(p[..., split:], v))
    if kv is not None:
        kv = write_kv_layer_fused(kv, k, v, layer_idx, offset)
    return out, kv


def choose(logits: jnp.ndarray, mask_token_id: int,
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """logits [B, L, V] -> (candidates [B, L] int32, confidences [B, L]
    float32): the argmax over every token but the mask token, and its
    softmax probability over those tokens."""
    z = logits.astype(jnp.float32)
    z = z.at[..., mask_token_id].set(-jnp.inf)
    cand = jnp.argmax(z, axis=-1).astype(jnp.int32)
    top = jnp.max(z, axis=-1)
    conf = jnp.exp(top - jax.nn.logsumexp(z, axis=-1))
    return cand, conf


def floor_of(masked_at_start: jnp.ndarray, denoising_steps: int):
    """The fewest positions a forward fixes: ``ceil(masked at the
    block's start / denoising_steps)``, so that ``denoising_steps``
    forwards finish any block."""
    return -(-masked_at_start // denoising_steps)


def transfer(masked: jnp.ndarray, conf: jnp.ndarray, floor: jnp.ndarray,
             remasking: str, threshold: float,
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Which masked positions this forward fixes. ``masked`` [B, L]
    bool, ``conf`` [B, L] float32, ``floor`` [B] int32 -> ``(fix [B, L]
    bool, over [B, L] bool)``; ``over``: fixed because its confidence
    passed the threshold.

    - ``sequential``: the first ``floor`` masked positions, left to
      right;
    - ``low_confidence_static``: the ``floor`` most confident;
    - ``low_confidence_dynamic``: every masked position whose
      confidence is over ``threshold``, and at least ``floor``, the most
      confident first.

    Ties go to the earlier position. A row fixes ``min(floor, masked)``
    at least, so a row with nothing masked fixes nothing."""
    if remasking not in RULES:
        raise ValueError(f"remasking={remasking!r} not one of {RULES}")
    length = masked.shape[-1]
    idx = jnp.arange(length)
    if remasking == "sequential":
        key = jnp.where(masked, -idx.astype(jnp.float32), -jnp.inf)
    else:
        key = jnp.where(masked, conf, -jnp.inf)
    mine, other = key[:, :, None], key[:, None, :]
    # an entry's rank: how many come before it (``expert_ffn``'s way)
    rank = jnp.sum((other > mine) | ((other == mine)
                                     & (idx[None, None, :] < idx[None, :, None])),
                   axis=-1)
    fix = masked & (rank < floor[:, None])
    over = jnp.zeros_like(fix)
    if remasking == "low_confidence_dynamic":
        over = masked & (conf > threshold)
        fix = fix | over
    return fix, over
