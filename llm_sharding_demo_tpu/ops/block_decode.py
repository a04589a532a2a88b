"""Pallas decode attention for a block of positions: spans streamed, the
block's rows written in place.

One forward of a block-diffusion round (``ops.block_diffusion.attend``
with ``T == L`` over a cache that is not fresh) as a kernel, the way
``ops.decode_attention`` serves a single position: the ``L`` new
positions at ``offset`` (a block boundary of every row) see every cached
position of their row's span ``[pad[b], offset)`` and each other, and
their keys and values go into the fused cache at ``[offset, offset +
L)``. What the XLA form pays for whatever the rows hold (the layer's
whole ``[B, Hkv, S, 2 hd]`` slice read, a softmax over all ``S + L``
keys of every lane, an update the compiler schedules as a pass of its
own) follows the rows' spans here:

- a kv head's ``G x L`` query rows ride that head's stream as ONE tile
  (the single-position kernel folds ``G``; here ``G L``, 32 rows at
  SDAR's geometry);
- blocks of ``stream_block`` positions come HBM -> VMEM double-buffered,
  each row's copies under its own predicate: row ``b`` reads exactly the
  blocks ``first_block(pad[b], offset) <= i < ceil(offset / block)``,
  the loop starts at the least of the rows' first blocks, and a lane
  whose span is empty reads nothing. The block arithmetic is
  ``ops.decode_attention``'s own (imported, as the scheduler's counter
  imports it), so kernel, counter and the older kernel cannot drift;
- a row's arithmetic is its own: the block loop's body is a loop over
  the rows, and a row that has nothing in a block is skipped, its
  ``m``/``l``/``acc`` untouched. A row's result therefore does not
  depend on its batch-mates or on which lanes are empty, and the
  kernel's size does not grow with the batch (loops, not unrolled
  copies: a program's size is what set-up pays for);
- online softmax in float32 over the streamed blocks, then the block's
  own ``L`` keys from the in-register new rows, all visible (``T == L``:
  the block mask inside one block is all true), folded in last;
- the ``L`` new fused rows are written through a read-modify-write
  window of whole ``_WRITE_ROWS`` tiles (``input_output_aliases``: the
  cache never copies). ``L`` rows from any ``offset`` lie inside
  ``window_rows(L)`` rows from the tile boundary below ``offset``; the
  window is clamped to the cache's end, so one shape serves every
  ``offset``.

Numerics: the cache's dtype straight onto the MXU with float32
accumulation, scores, maxima, sums and the accumulator in float32, the
probabilities cast to the values' type for the weighted sum
(``block_diffusion.PRECISION_CONTRACT["attend"]``). The online softmax
orders the sum otherwise than ``jax.nn.softmax``: the same masked score
set, equal to rounding and not to the bit; the written rows ARE
``write_kv_layer_fused``'s, to the bit.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import (_WRITE_ROWS, NEG_INF, eligible as _one_position,
                               first_block, stream_block)

# Numerics contract (tools/graftcheck numerics pass): as the other decode
# kernels, equivalent to the XLA form and not byte-equal with it.
PRECISION_CONTRACT = {
    "block_decode_attention": {"regime": "carried", "exact": False,
                               "oracle": "decode.bf16",
                               "casts": ("f32", "carried")},
}

KERNEL_NAME = "block_decode_attention"
# the cache's dtype straight onto the MXU, float32 accumulation, whatever
# ``jax_default_matmul_precision`` says (``ops.latent_decode``)
_MXU = jax.lax.Precision.DEFAULT


def eligible(max_seq: int, head_dim: int) -> bool:
    """The single-position kernel's geometry (lane-aligned fused rows, a
    cache of whole blocks): the two kernels read one layout."""
    return _one_position(max_seq, head_dim, 1)


def window_rows(length: int) -> int:
    """Rows of the write's window: the whole ``_WRITE_ROWS`` tiles that
    ``length`` rows can touch from any offset (4 rows: two tiles)."""
    return _WRITE_ROWS * ((length + _WRITE_ROWS - 2) // _WRITE_ROWS + 1)


def _kernel(meta_ref,                      # SMEM [2] int32 (layer, offset)
            pad_ref,                       # SMEM [B] int32: a row's span
            q_ref,                         # VMEM [B, Hkv, G L, hd]
            new_ref,                       # VMEM [B, Hkv, L, 2 hd] fused
            kv_in,                         # HBM fused cache (aliases out)
            out_ref, kv_out,               # VMEM [B, Hkv, G L, hd]; HBM
            acc_ref, m_ref, l_ref,         # VMEM scratch, float32
            kvbuf, winbuf, copy_sems, write_sem,
            *, batch: int, hd: int, length: int):
    li = meta_ref[0]
    off = meta_ref[1]
    block_s = kvbuf.shape[3]               # see stream_block
    scale = 1.0 / (hd ** 0.5)
    n_blk = (off + block_s - 1) // block_s

    def lo(b):
        return first_block(pad_ref[b], off, block_s)

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
    # the write's window is read NOW, behind the stream: rows before
    # ``off`` are not touched by this kernel, rows from it on only by the
    # write at the end
    win = winbuf.shape[2]
    base = jnp.minimum((off // _WRITE_ROWS) * _WRITE_ROWS,
                       kv_in.shape[3] - win)
    win_rd = pltpu.make_async_copy(
        kv_in.at[li, :, :, pl.ds(base, win), :], winbuf, write_sem)
    win_rd.start()
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def fold(b, keys, values, ok=None):
        """Row ``b``'s online softmax takes ``n`` more positions: ``keys``
        and ``values`` ``[Hkv, n, hd]``, ``ok`` which of them it sees
        (at least one, or ``None``: all)."""
        s = jax.lax.dot_general(q_ref[b], keys, (((2,), (2,)), ((0,), (0,))),
                                precision=_MXU,
                                preferred_element_type=jnp.float32) * scale
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)                  # [Hkv, G L, n]
        m_old = m_ref[b]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=2, keepdims=True))
        corr = jnp.exp(m_old - m_new)
        # a masked score is NEG_INF under a finite maximum: exactly 0
        p = jnp.exp(s - m_new)
        pv = jax.lax.dot_general(p.astype(values.dtype), values,
                                 (((2,), (1,)), ((0,), (0,))),
                                 precision=_MXU,
                                 preferred_element_type=jnp.float32)
        l_ref[b] = l_ref[b] * corr + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[b] = acc_ref[b] * corr + pv
        m_ref[b] = m_new

    def body(i, _):
        slot = jax.lax.rem(i, 2)
        rows_of(lambda b: (i + 1 < n_blk) & (i + 1 >= lo(b)),
                lambda b: fetch(1 - slot, i + 1, b).start())
        pos = i * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_s), 2)

        def row(b):
            fetch(slot, i, b).wait()
            # ``attend``'s ``seen``: every cached position of the span
            fold(b, kvbuf[slot, b, :, :, pl.ds(0, hd)],
                 kvbuf[slot, b, :, :, pl.ds(hd, hd)],
                 (pos >= pad_ref[b]) & (pos < off))

        rows_of(lambda b: i >= lo(b), row)
        return 0

    jax.lax.fori_loop(first, n_blk, body, 0)

    # the L fused rows of every (row, kv head) at once, into the window
    # and back: the only mutation of the aliased cache. It flies while
    # the block's own term is folded in (which reads the new rows from
    # VMEM, not the cache)
    win_rd.wait()
    at = jax.lax.broadcasted_iota(jnp.int32, winbuf.shape, 2) - (off - base)
    rows = winbuf[...]
    for j in range(length):
        rows = jnp.where(at == j, new_ref[:, :, pl.ds(j, 1), :], rows)
    winbuf[...] = rows
    wr = pltpu.make_async_copy(
        winbuf, kv_out.at[li, :, :, pl.ds(base, win), :], write_sem)
    wr.start()

    def finish(b, _):
        # the block's own keys, all visible
        fold(b, new_ref[b, :, :, pl.ds(0, hd)], new_ref[b, :, :, pl.ds(hd, hd)])
        out_ref[b] = (acc_ref[b] / l_ref[b]).astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, batch, finish, 0)
    wr.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(q4, new, pads, kv, meta, *, interpret: bool):
    _, b, hkv, _, hd2 = kv.shape
    hd = hd2 // 2
    rows, length = q4.shape[2], new.shape[2]
    block_s = stream_block(b * hkv, hd, kv.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,             # meta, and the rows' pads
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # q [B, Hkv, G L, hd]
            pl.BlockSpec(memory_space=pltpu.VMEM),  # new [B, Hkv, L, 2 hd]
            pl.BlockSpec(memory_space=pltpu.HBM),   # fused cache (aliased)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # out [B, Hkv, G L, hd]
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, hkv, rows, hd), jnp.float32),         # acc
            pltpu.VMEM((b, hkv, rows, 1), jnp.float32),          # m
            pltpu.VMEM((b, hkv, rows, 1), jnp.float32),          # l
            pltpu.VMEM((2, b, hkv, block_s, hd2), kv.dtype),     # stream
            pltpu.VMEM((b, hkv, window_rows(length), hd2), kv.dtype),
            pltpu.SemaphoreType.DMA((2, b)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, batch=b, hd=hd, length=length),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q4.shape, q4.dtype),
            jax.ShapeDtypeStruct(kv.shape, kv.dtype),
        ],
        # inputs, the scalar operands among them: meta 0, pads 1, q 2,
        # new 3, kv 4 -> outputs (out 0, kv 1)
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name=KERNEL_NAME,
    )(meta, pads, q4, new, kv)


def block_decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           kv: jnp.ndarray, layer_idx, offset,
                           pad: Optional[jnp.ndarray] = None,
                           interpret: bool = False,
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q ``[B, H, L, hd]``, k/v ``[B, Hkv, L, hd]``: ONE block at
    ``offset``; ``kv`` the whole fused ``[layers, B, Hkv, S, 2 hd]``
    cache, returned with the block's rows written (the update aliases
    the input: the passed buffer is consumed). ``pad`` [B] is where each
    row's span starts; a row with ``pad >= offset`` reads nothing and
    its positions see each other alone."""
    b, h, length, hd = q.shape
    hkv, hd2 = kv.shape[2], kv.shape[4]
    if hd2 != 2 * hd:
        raise ValueError(f"cache is not fused: lane dim {hd2} != 2*{hd}")
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    # the rows ``write_kv_layer_fused`` writes, and the queries in the
    # type that meets them on the MXU
    new = jnp.concatenate([k, v], axis=-1).astype(kv.dtype)
    q4 = q.reshape(b, hkv, (h // hkv) * length, hd).astype(kv.dtype)
    pads = (jnp.zeros((b,), jnp.int32) if pad is None
            else pad.astype(jnp.int32))
    meta = jnp.asarray([layer_idx, offset], jnp.int32).reshape(2)
    out, kv = _call(q4, new, pads, kv, meta, interpret=interpret)
    return out.astype(q.dtype).reshape(b, h, length, hd), kv
