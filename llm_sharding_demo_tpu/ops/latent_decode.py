"""Pallas decode attention over a latent cache: the absorbed form, streamed.

One decode step of latent attention (``ops.latent_attention.absorbed``
with a single query position) as a kernel: every head's query, already
folded into the latent space (``[q_nope W_uk^T | q_pe]``, ``width`` =
``rank + rope`` wide), attends over the row's cached ``[c_kv | k_pe]``
vectors, which are keys whole and values in their first ``rank``.

Like ``ops.decode_attention`` (the kernel of the two-plane caches) each
step takes one block of ``block_s`` positions carrying EVERY batch
row's slice, and reads follow the live depth with no program per depth.
Unlike it, the blocks come through the pipeline of a grid over the
cache's blocks: steps past the live depth name the last live block
again, which the pipeline does not fetch twice, and compute nothing.
(Rows are stored lane-aligned, ``models.latent_moe``'s ``cache_lanes``:
Mosaic refuses a hand-made DMA slice of a 576-wide row, and XLA keeps
arrays with such rows transposed.) The cache is read only (the new token's
vector is written by ``latent_attention.write_latent`` before the call,
an in-place column update of the loop-carried buffer), and there is one
"kv head": all ``H`` query heads ride the one stream, the matmuls are
``[H, width] x [block_s, width]^T`` and ``[H, block_s] x [block_s,
width]`` in the cache's dtype with float32 accumulation. The value
product runs over the full width and the caller keeps the first
``rank`` lanes: no sub-128-lane slice inside the kernel.

Numerics as ``ops.decode_attention``: online softmax in float32, so the
reduction order differs from the einsum path; equivalent, not byte
pinned (tests/test_latent_moe.py pins agreement within float32 noise in
interpret mode).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import BLOCK_S, NEG_INF, stream_block

# Numerics contract (tools/graftcheck numerics pass): scores and the
# accumulator are float32, the output is cast to the query's dtype; the
# online softmax makes it equivalent to, not byte-equal with, the XLA
# form (declared approximate under the engine's bf16 budget).
PRECISION_CONTRACT = {
    "latent_decode_attention": {"regime": "carried", "exact": False,
                                "oracle": "decode.bf16",
                                "casts": ("f32", "carried")},
}

KERNEL_NAME = "latent_decode_attention"
# the cache's dtype straight onto the MXU, float32 accumulation, whatever
# ``jax_default_matmul_precision`` says (Mosaic refuses "highest" on
# bfloat16 operands)
_MXU = jax.lax.Precision.DEFAULT


def eligible(max_seq: int) -> bool:
    """The cache is whole ``BLOCK_S`` blocks (the engine rounds it up
    when it wants this kernel)."""
    return max_seq % BLOCK_S == 0 and max_seq >= BLOCK_S


def _kernel(meta_ref,                      # SMEM [2] int32 (layer, offset)
            q_ref,                         # VMEM [B, H, width]
            vf_ref,                        # VMEM [B, 1, 1] int32 pad mask
            kv_ref,                        # VMEM [B, block_s, width]: block i
            out_ref,                       # VMEM [B, H, width]
            acc_ref, m_ref, l_ref,         # VMEM scratch, float32
            *, scale: float, block_s: int):
    i = pl.program_id(0)
    off = meta_ref[1]
    n_blk = (off + block_s) // block_s     # positions 0 .. off inclusive

    @pl.when(i == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(i < n_blk)
    def _():
        kvb = kv_ref[...]
        s = jax.lax.dot_general(q_ref[...], kvb,
                                (((2,), (2,)), ((0,), (0,))),
                                precision=_MXU,
                                preferred_element_type=jnp.float32) * scale
        pos = i * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_s), 2)
        ok = (pos <= off) & (pos >= vf_ref[...])           # [B, 1, BS]
        s = jnp.where(ok, s, NEG_INF)                      # [B, H, BS]
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=2, keepdims=True))
        corr = jnp.exp(m_ref[...] - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        pv = jax.lax.dot_general(p.astype(kvb.dtype), kvb,
                                 (((2,), (1,)), ((0,), (0,))),
                                 precision=_MXU,
                                 preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        # a row with no valid position at all (a ghost lane) gives zeros
        out_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _call(q, vf, cache, meta, *, scale: float, interpret: bool):
    _, b, _, smax, width = cache.shape
    h = q.shape[1]
    block_s = stream_block(b, width // 2, cache.dtype.itemsize)

    def block_of(i, meta):
        # steps past the live depth name the last live block again: the
        # pipeline fetches a block only when its index changes
        return (meta[0], 0, 0, jnp.minimum(i, meta[1] // block_s), 0)

    def whole(i, meta):
        return (0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(smax // block_s,),
        in_specs=[
            pl.BlockSpec((b, h, width), whole),                  # q
            pl.BlockSpec((b, 1, 1), whole),                      # vf
            pl.BlockSpec((None, b, None, block_s, width), block_of),
        ],
        out_specs=pl.BlockSpec((b, h, width), whole),
        scratch_shapes=[
            pltpu.VMEM((b, h, width), jnp.float32),              # acc
            pltpu.VMEM((b, h, 1), jnp.float32),                  # m
            pltpu.VMEM((b, h, 1), jnp.float32),                  # l
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_s=block_s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(meta, q, vf, cache)


def latent_decode_attention(q: jnp.ndarray, cache: jnp.ndarray, layer_idx,
                            offset, scale: float,
                            k_valid_from: Optional[jnp.ndarray] = None,
                            interpret: bool = False) -> jnp.ndarray:
    """q ``[B, H, width]`` (latent-space queries of ONE new position at
    ``offset``, whose own vector is already in the cache); ``cache`` the
    whole ``[L, B, 1, Smax, width]`` buffer. Returns ``[B, H, width]``:
    the softmax-weighted sum of the cached vectors over positions
    ``k_valid_from[b] .. offset``."""
    b = q.shape[0]
    if cache.ndim != 5 or cache.shape[2] != 1 or cache.shape[4] != q.shape[2]:
        raise ValueError(f"cache {cache.shape} is not a latent cache of "
                         f"width {q.shape[2]}")
    if k_valid_from is None:
        k_valid_from = jnp.zeros((b,), jnp.int32)
    vf = k_valid_from.astype(jnp.int32)[:, None, None]
    meta = jnp.asarray([layer_idx, offset], jnp.int32).reshape(2)
    return _call(q.astype(cache.dtype), vf, cache, meta, scale=scale,
                 interpret=interpret)
