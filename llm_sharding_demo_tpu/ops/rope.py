"""Rotary position embeddings (RoPE) — the LLaMA family's position scheme.

Unlike GPT-2's learned ``wpe`` table (which hard-caps context at
``n_positions`` rows — the reference's 1024-token ceiling, reference
server.py:57,80), RoPE is computed from the position index itself, so the
same weights serve any context length. This is what makes the llama
family this framework's genuine long-context path: nothing in the model
gathers from a position table.

Formulation matches HF ``LlamaRotaryEmbedding`` + ``apply_rotary_pos_emb``
(the "rotate half" convention, not interleaved):

    inv_freq_j = theta ** -(2j / hd)             j in [0, hd/2)
    emb        = concat([pos * inv_freq, pos * inv_freq])   # [.., S, hd]
    x'         = x * cos(emb) + rotate_half(x) * sin(emb)

Angles are computed in float32 regardless of activation dtype (bf16
angles at position ~8k would quantize to whole radians) and the rotation
is applied in float32 then cast back, mirroring HF's float32 cos/sin
buffers so the parity oracle stays exact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rope_angles(positions: jnp.ndarray, head_dim: int,
                theta: float = 10000.0) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Positions ``[...]`` (int) -> (cos, sin) each ``[..., head_dim]``."""
    j = jnp.arange(0, head_dim, 2, dtype=jnp.float32)
    inv_freq = theta ** (-j / head_dim)                      # [hd/2]
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)           # [..., hd]
    return jnp.cos(emb), jnp.sin(emb)


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray,
               sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate ``x`` [B, H, S, hd] by per-position angles.

    ``cos``/``sin`` are [S, hd] (uniform positions) or [B, S, hd]
    (per-row offsets for left-padded ragged batches); the head axis
    broadcasts.
    """
    if cos.ndim == 2:                        # [S, hd] -> [1, 1, S, hd]
        cos, sin = cos[None, None], sin[None, None]
    else:                                    # [B, S, hd] -> [B, 1, S, hd]
        cos, sin = cos[:, None], sin[:, None]
    x32 = x.astype(jnp.float32)
    out = x32 * cos + _rotate_half(x32) * sin
    return out.astype(x.dtype)


def apply_rope_leading(x: jnp.ndarray, cos: jnp.ndarray,
                       sin: jnp.ndarray) -> jnp.ndarray:
    """``apply_rope`` on the leading ``cos.shape[-1]`` dimensions of
    ``x`` [B, H, S, hd] alone (a config's ``partial_rotary_factor``):
    the rotate-half pairs lie inside that leading part, the rest of a
    head passes through unturned."""
    r = cos.shape[-1]
    if r == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate([apply_rope(x[..., :r], cos, sin), x[..., r:]],
                           axis=-1)


def pair_angles(positions: jnp.ndarray, head_dim: int,
                theta: float = 10000.0) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``rope_angles`` for the interleaved layout (a config's
    ``rope_interleave``), where components ``2i`` and ``2i+1`` are one
    pair turned by ``pos * inv_freq_i``: (cos, sin) each ``[...,
    head_dim]`` with the pair's angle on both of its lanes and the sign
    of the rotation folded into the sine (minus on the even lane), so
    that ``rotate_pairs`` is two multiplies and an add."""
    lane = jnp.arange(head_dim)
    inv_freq = theta ** (-(lane // 2 * 2).astype(jnp.float32) / head_dim)
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(freqs), jnp.sin(freqs) * jnp.where(lane % 2 == 0, -1.0, 1.0)


def rotate_pairs(x: jnp.ndarray, cos: jnp.ndarray,
                 sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate ``x`` [B, H, S, hd] pair by pair where the pairs lie:
    ``(x[2i], x[2i+1])`` by the angles of ``pair_angles`` (shaped as
    ``apply_rope`` takes them). No lane leaves its place: each takes its
    partner from the lane beside it (a matmul with the 0/1 matrix that
    swaps neighbours), so a query and a key rotated here
    keep the layout their weights give them, and their dot product is
    the one ``apply_rope`` gives on the half-split permutation of both.
    Float32 inside, like ``apply_rope``."""
    if cos.ndim == 2:                        # [S, hd] -> [1, 1, S, hd]
        cos, sin = cos[None, None], sin[None, None]
    else:                                    # [B, S, hd] -> [B, 1, S, hd]
        cos, sin = cos[:, None], sin[:, None]
    hd = x.shape[-1]
    lane = jnp.arange(hd)
    swap = (lane[:, None] == (lane ^ 1)[None, :]).astype(x.dtype)
    # one term a lane, so exact in any dtype at this precision; a lane
    # shuffle stated as slices or a roll costs the chip several copies
    partner = jnp.matmul(x, swap, precision=jax.lax.Precision.HIGHEST)
    out = (x.astype(jnp.float32) * cos
           + partner.astype(jnp.float32) * sin)
    return out.astype(x.dtype)
