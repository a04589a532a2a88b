"""Pallas flash-decode attention: single-token cached attention at HBM rate.

Why this kernel exists — measured on the bench chip (round 3):

- XLA will not update the KV cache in place when the freshly written
  buffer is consumed by a dot in the same loop iteration: every
  ``dynamic_update_slice`` + attend decode step materializes a copy of
  the touched cache buffers (~230 GB/s effective at GPT-2-124M bs=8,
  barrier/donation/unroll variants all measured worse). The reference
  never meets this problem — it has no cache at all (re-forwards the
  full sequence per token, reference server.py:169-181).
- The einsum decode attention reads the whole ``max_seq`` cache every
  step regardless of how many slots are valid.

The kernel operates on the FUSED cache layout
(``ops.attention.create_fused_cache``): one ``[L, B, Hkv, Smax, 2*hd]``
buffer whose rows are ``[K | V]`` on the lane axis. That layout is what
makes the kernel possible at GPT-2/llama head width (hd=64): Mosaic
requires 128-lane-aligned memref slices, which separate ``[..., hd]``
K/V buffers cannot provide — fused rows are exactly 128 lanes, one DMA
streams both halves, and the new token's write is a single full-row
copy. Per (batch row, kv head) grid cell:

- the new token's fused row is DMA'd into the cache IN PLACE
  (``input_output_aliases`` — the cache never copies);
- KV blocks stream HBM -> VMEM double-buffered, and both the block
  loop's bounds and each row's copies are *dynamic*: a row's reads track
  its own span ``[k_valid_from[b], offset)`` with no recompiles per
  depth, pad or live count. A block that lies wholly under a row's left
  pad is not read for that row, a lane whose span is empty (the iter
  scheduler gives a lane without a request one) reads nothing, and the
  loop starts at the first block some row needs (``first_block``; the
  XLA path reads the whole window whatever lives in it);
- online softmax over the blocks; the current token's contribution
  comes from the in-register ``k_new``/``v_new`` (its HBM write may
  still be in flight);
- grouped-query attention is native: ``H == G * Hkv`` query heads ride
  one kv head's stream (llama decodes without repeating K/V);
- the K-half/V-half lane routing is done with MXU-friendly constant
  projections (zero-padded queries for scores, a lane-selector matmul
  for the value half) — no sub-128-lane vector shuffles anywhere.

Numerics: scores/accumulator in float32, output cast to the query dtype.
The online-softmax reduction order differs from the XLA einsum+softmax,
so this path is *numerically equivalent* (same masked score set) but not
byte-pinned against the einsum path; greedy token streams are pinned
equal in tests on the oracle seeds. The exact-parity modes (fp32
BASELINE.json greedy) keep the XLA path.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BLOCK_S = 256          # cache positions per DMA block (and the unit the
                       # engine rounds its cache to)
_WRITE_ROWS = 8        # RMW window for the column write (HBM tile rows)
NEG_INF = -1e30        # f32 additive mask for scores


def eligible(max_seq: int, head_dim: int, q_len: int) -> bool:
    """Whether the kernel applies: single-token query, lane-aligned fused
    rows (2*hd a multiple of 128), cache allocated in whole blocks (the
    engine rounds its cache up to ``BLOCK_S`` multiples when it wants
    this kernel)."""
    return (q_len == 1 and (2 * head_dim) % 128 == 0
            and max_seq % BLOCK_S == 0 and max_seq >= BLOCK_S)


# What the block stream costs in VMEM, per cache position of a block and
# per (batch row, kv head): the double buffer (2 copies at the cache
# dtype) plus the loop body's f32 temporaries. The v5e compiler's own
# report put those at 4.2 f32 copies of a block (GPT-2 124M, bf16: 105 MB
# of temporaries beside a 25 MB block at B=16 / 256 positions, the same
# ratio at B=32 / 128) — and refused both, since a v5e core has 128 MiB.
_VMEM_BYTES = 128 * 1024 * 1024
_VMEM_HEADROOM = 12 * 1024 * 1024      # accumulators, masks, windows
_F32_TEMPORARIES = 4.25


def stream_block(bh: int, hd: int, itemsize: int) -> int:
    """Cache positions per streamed block: ``BLOCK_S``, halved until the
    stream fits a core's VMEM. Every result divides ``BLOCK_S``, so the
    cache is still whole blocks; GPT-2 124M keeps 256 through B=8 and
    streams 128 at B=16, 64 at B=32."""
    per_position = bh * 2 * hd * (2 * itemsize + 4 * _F32_TEMPORARIES)
    room = _VMEM_BYTES - _VMEM_HEADROOM
    bs = BLOCK_S
    while bs > _WRITE_ROWS and bs * per_position > room:
        bs //= 2
    return bs


def first_block(pad, off, block_s: int):
    """The first block of ``block_s`` positions that a row with the span
    ``[pad, off)`` streams: the block that holds ``pad``, and the block
    count ``ceil(off / block_s)`` (no block at all) where the span is
    empty. Row ``b`` reads exactly the blocks ``first_block(pad[b]) <= i
    < ceil(off / block_s)``, and the loop starts at the smallest of the
    rows' first blocks. Operators only: the kernel applies it to its SMEM
    scalars and the scheduler's counter to numpy arrays
    (``streamed_blocks``), so the two cannot drift apart."""
    n_blk = (off + block_s - 1) // block_s
    lo = pad // block_s
    return lo + (pad >= off) * (n_blk - lo)


def streamed_blocks(pads, off, block_s: int):
    """How many blocks each row's stream reads at offset ``off``: ``[B]``
    for ``pads`` ``[B]`` (and ``[B, T]`` for ``off`` ``[T]``, the steps
    of a call), zero for an empty span."""
    pads = np.asarray(pads)[(...,) + (None,) * np.ndim(off)]
    return (np.asarray(off) + block_s - 1) // block_s - first_block(
        pads, np.asarray(off), block_s)


def _kernel(meta_ref,                      # SMEM  [2] int32 (li, off)
            span_ref,                      # SMEM  [B] int32 pad per row
            q_ref, knew_ref, vnew_ref,     # VMEM (full arrays, [BH, ...])
            vf_ref,                        # VMEM [BH, 1, 1] int32 pad mask
            kv_in,                         # HBM fused cache (aliases out)
            out_ref, kv_out,               # VMEM out + aliased cache
            acc_ref, m_ref, l_ref,         # VMEM scratch
            kvbuf, winbuf, copy_sems, write_sem,
            *, batch: int, hkv: int, g: int, hd: int):
    """One grid cell, and per S-block one DMA a ROW that has a position
    of its span in the block (all of the row's kv heads: 1 MB at Mistral's
    geometry; issued and awaited in loops over the rows); the compute is
    batched over every (batch row, kv head), so the block loop runs one
    iteration a visited block. (Earlier shapes measured:
    a (b, h) grid ~2.6x slower and a flattened per-(b,h,block) loop ~1.9x
    slower — both drowned in per-iteration DMA/fence overhead at 64 KB
    blocks.) A row whose slice of a visited block was not fetched computes
    on zeros: the double buffer's lanes are cleared at entry wherever the
    row's first copy into them comes later (stale VMEM under ``p = 0``
    would be NaN if its bits are), and every score there is masked, so
    the block leaves the row's ``m``/``l``/``acc`` as they were."""
    li = meta_ref[0]
    off = meta_ref[1]
    bh = batch * hkv
    block_s = kvbuf.shape[3]               # see stream_block

    scale = 1.0 / (hd ** 0.5)

    # Lane-routing constants, built from iota (never materialized in HBM):
    # P_k [hd, 2hd] places a K-half query into fused lanes; P_v [2hd, hd]
    # extracts the V half of a fused accumulator. Both are used as dot
    # operands, so all lane movement happens on the MXU.
    row2 = jax.lax.broadcasted_iota(jnp.int32, (hd, 2 * hd), 0)
    col2 = jax.lax.broadcasted_iota(jnp.int32, (hd, 2 * hd), 1)
    p_k = (row2 == col2).astype(jnp.float32)               # [hd, 2hd]
    rowv = jax.lax.broadcasted_iota(jnp.int32, (2 * hd, hd), 0)
    colv = jax.lax.broadcasted_iota(jnp.int32, (2 * hd, hd), 1)
    p_v = (rowv == colv + hd).astype(jnp.float32)          # [2hd, hd]

    q = q_ref[...].astype(jnp.float32) * scale             # [BH, g, hd]
    q_ext = jax.lax.dot_general(q, p_k, (((2,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    vf_bh = vf_ref[...]                                    # [BH, 1, 1]

    # the stream's plan, from the rows' spans: row b copies blocks
    # [lo(b), n_blk), the loop visits [first, n_blk). Loops over the rows
    # (not unrolled: a program's size is what set-up pays for)
    n_blk = (off + block_s - 1) // block_s

    def lo(b):
        return first_block(span_ref[b], off, block_s)

    first = jax.lax.fori_loop(
        0, batch, lambda b, lowest: jnp.minimum(lowest, lo(b)), n_blk)

    def fetch(slot, i, b):
        return pltpu.make_async_copy(
            kv_in.at[li, b, :, pl.ds(i * block_s, block_s), :],
            kvbuf.at[slot, b], copy_sems.at[slot, b])

    def rows_of(ok, act):
        """``act(b)`` for every row ``b`` with ``ok(b)``."""
        def row(b, _):
            pl.when(ok(b))(functools.partial(act, b))
            return 0
        jax.lax.fori_loop(0, batch, row, 0)

    rows_of(lambda b: (first < n_blk) & (lo(b) == first),
            lambda b: fetch(jax.lax.rem(first, 2), first, b).start())
    # a lane of the double buffer is computed on before its row's first
    # copy into it lands wherever the row starts after the first (slot of
    # ``first``) or second visited block: those lanes, and no lane a copy
    # above or in the loop writes before it is read, are zeroed
    for k in range(2):
        def clear(b, k=k):
            kvbuf[jax.lax.rem(first + k, 2), b] = jnp.zeros(
                kvbuf.shape[2:], kvbuf.dtype)
        rows_of(lambda b, k=k: lo(b) > first + k, clear)
    # the column write's RMW window read starts NOW so its latency hides
    # behind the block stream (it reads pre-write state: rows < off are
    # never touched by this kernel until the final write below)
    base = (off // _WRITE_ROWS) * _WRITE_ROWS
    win_rd = pltpu.make_async_copy(
        kv_in.at[li, :, :, pl.ds(base, _WRITE_ROWS), :], winbuf, write_sem)
    win_rd.start()
    m_ref[...] = jnp.full((bh, g, 1), NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros((bh, g, 1), jnp.float32)
    acc_ref[...] = jnp.zeros((bh, g, 2 * hd), jnp.float32)

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        rows_of(lambda b: (i + 1 < n_blk) & (i + 1 >= lo(b)),
                lambda b: fetch(1 - slot, i + 1, b).start())
        rows_of(lambda b: i >= lo(b), lambda b: fetch(slot, i, b).wait())
        kvb = kvbuf[slot].astype(jnp.float32).reshape(bh, block_s, 2 * hd)
        # q_ext's V lanes are zero, so the 2hd contraction is q . K
        s = jax.lax.dot_general(q_ext, kvb, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        pos = i * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_s), 2)
        # strictly-prior positions stream from the cache; position ``off``
        # itself is the in-register self term (folded in at finalize)
        ok = (pos < off) & (pos >= vf_bh)                  # [BH, 1, BS]
        s = jnp.where(ok, s, NEG_INF)                      # [BH, g, BS]
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=2, keepdims=True))
        corr = jnp.exp(m_ref[...] - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)         # [BH, g, BS]
        pv = jax.lax.dot_general(p, kvb, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new
        return 0

    jax.lax.fori_loop(first, n_blk, body, 0)

    # fold the current token's self term in once, extract the V half on
    # the MXU, and emit every (b, h) at once
    k_new = knew_ref[...].astype(jnp.float32)              # [BH, 1, hd]
    v_new = vnew_ref[...].astype(jnp.float32)
    s_self = jax.lax.dot_general(q, k_new, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
    m_fin = jnp.maximum(m_ref[...], s_self)                # [BH, g, 1]
    corr_f = jnp.exp(m_ref[...] - m_fin)
    p_self = jnp.exp(s_self - m_fin)
    l_fin = l_ref[...] * corr_f + p_self
    acc_v = jax.lax.dot_general(acc_ref[...] * corr_f, p_v,
                                (((2,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    acc_v = acc_v + p_self * v_new                         # [BH, g, hd]
    out_ref[...] = (acc_v / l_fin).astype(out_ref.dtype)

    # in-place fused-row write for ALL (b, h) at once: read-modify-write
    # of one 8-row-aligned window per cache slice. The cache is aliased
    # in/out, so these windows are the ONLY mutation — untouched slots
    # never copy. (Single-row HBM writes are not DMA-able under bf16
    # tiling; the window's earlier rows are past positions and its later
    # rows future garbage, both preserved.) The read was issued at kernel
    # entry (win_rd) so only the final write's latency is exposed.
    win_rd.wait()
    kn2 = knew_ref[...].reshape(batch * hkv, hd).astype(jnp.float32)
    vn2 = vnew_ref[...].reshape(batch * hkv, hd).astype(jnp.float32)
    rows = (jax.lax.dot_general(kn2, p_k, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(vn2, p_v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32))
    rows = rows.reshape(batch, hkv, 1, 2 * hd).astype(winbuf.dtype)
    row_iota = jax.lax.broadcasted_iota(
        jnp.int32, (batch, hkv, _WRITE_ROWS, 2 * hd), 2)
    winbuf[...] = jnp.where(row_iota == off - base, rows, winbuf[...])
    wr = pltpu.make_async_copy(
        winbuf, kv_out.at[li, :, :, pl.ds(base, _WRITE_ROWS), :], write_sem)
    wr.start()
    wr.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(q4, k_new, v_new, pads, vf_bh, KV, meta, *, interpret: bool):
    L, B, Hkv, Smax, hd2 = KV.shape
    hd = hd2 // 2
    g = q4.shape[2]
    block_s = stream_block(B * Hkv, hd, KV.dtype.itemsize)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,             # meta, and the rows' pads
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # q [BH, g, hd]
            pl.BlockSpec(memory_space=pltpu.VMEM),  # k_new [BH, 1, hd]
            pl.BlockSpec(memory_space=pltpu.VMEM),  # v_new
            pl.BlockSpec(memory_space=pltpu.VMEM),  # vf [BH, 1, 1] int32
            pl.BlockSpec(memory_space=pltpu.HBM),   # fused KV (aliased out)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # out [B, Hkv, g, hd]
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        scratch_shapes=[
            pltpu.VMEM((B * Hkv, g, 2 * hd), jnp.float32),  # acc (fused)
            pltpu.VMEM((B * Hkv, g, 1), jnp.float32),       # m
            pltpu.VMEM((B * Hkv, g, 1), jnp.float32),       # l
            pltpu.VMEM((2, B, Hkv, block_s, 2 * hd), KV.dtype),  # dbl buf
            pltpu.VMEM((B, Hkv, _WRITE_ROWS, 2 * hd), KV.dtype),  # RMW win
            pltpu.SemaphoreType.DMA((2, B)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    kernel = functools.partial(_kernel, batch=B, hkv=Hkv, g=g, hd=hd)
    out, KV = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, g, hd), q4.dtype),
            jax.ShapeDtypeStruct(KV.shape, KV.dtype),
        ],
        # inputs (incl. the scalar operands): meta=0, pads=1, q=2,
        # k_new=3, v_new=4, vf=5, KV=6 -> outputs (out=0, KV=1)
        input_output_aliases={6: 1},
        # the double buffer alone is ~2*B*Hkv*block_s*2hd*2 bytes (12.6 MB
        # at GPT-2-124M bs=8) — past the default 16 MB scoped-vmem limit
        # once accumulators join; v5e has 128 MB of VMEM to give, and
        # stream_block keeps the stream inside it as the batch widens
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(meta, pads, q4.reshape(B * Hkv, g, hd),
      k_new.reshape(B * Hkv, 1, hd), v_new.reshape(B * Hkv, 1, hd),
      vf_bh, KV)
    return out, KV


def decode_attention(q: jnp.ndarray, k_new: jnp.ndarray, v_new: jnp.ndarray,
                     KV: jnp.ndarray, layer_idx, offset,
                     k_valid_from: Optional[jnp.ndarray] = None,
                     interpret: bool = False,
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token cached attention + in-place fused-cache update.

    q ``[B, H, 1, hd]``; k_new/v_new ``[B, Hkv, 1, hd]``; ``KV`` the full
    fused ``[L, B, Hkv, Smax, 2*hd]`` cache (returned updated; the update
    aliases the input — callers must treat the passed buffer as consumed,
    which the decode scan's carry semantics already do).
    ``layer_idx``/``offset`` are traced scalars; ``k_valid_from`` [B]
    masks each row's left-pad prefix like ``causal_attention``, and is
    where the row's stream starts: a row with ``k_valid_from >= offset``
    reads nothing and attends to its own token alone.
    """
    B, H, q_len, hd = q.shape
    L, _, Hkv, Smax, hd2 = KV.shape
    if q_len != 1:
        raise ValueError(f"decode kernel is single-token only, got S={q_len}")
    if hd2 != 2 * hd:
        raise ValueError(f"cache is not fused: lane dim {hd2} != 2*{hd}")
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    g = H // Hkv
    q4 = q.reshape(B, Hkv, g, hd)
    if k_valid_from is None:
        k_valid_from = jnp.zeros((B,), jnp.int32)
    # the pads twice: [B] scalars in SMEM, where the stream's copies are
    # decided, and pre-expanded to the [BH, 1, 1] layout the score mask
    # consumes in VMEM (building it from SMEM scalars in-kernel is
    # unsupported)
    pads = k_valid_from.astype(jnp.int32)
    vf_bh = jnp.repeat(pads, Hkv)[:, None, None]
    meta = jnp.asarray([layer_idx, offset], jnp.int32).reshape(2)
    out, KV = _call(q4, k_new, v_new, pads, vf_bh, KV, meta,
                    interpret=interpret)
    return out.reshape(B, H, 1, hd), KV
