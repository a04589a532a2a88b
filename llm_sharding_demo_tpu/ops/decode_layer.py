"""Whole-stack decode megakernel: all L transformer layers in ONE launch.

Why (BASELINE.md int8 accounting / VERDICT r3 next #1): a bs=1 GPT-2
124M decode step issues ~100 kernel launches (12 flash-decode attention
+ ~7 int8 matmul kernels per layer), each with fixed dispatch/DMA-warmup
cost — ~0.1 ms/step of pure overhead that caps int8 at 1.40x over bf16
(bandwidth-ideal 1.8x) and leaves bf16 at ~62% of HBM peak. This kernel
runs the ENTIRE block stack — LN1, fused QKV projection, cached
attention with in-place fused-KV write, output projection, residual,
LN2, MLP (fc -> gelu -> proj), residual — for all L layers in one
``pallas_call``:

- grid ``(L,)``, sequential: each grid step is one layer. The stacked
  ``[L, ...]`` block weights (the model's native layout) arrive as
  BlockSpec-pipelined VMEM blocks — Pallas double-buffers layer l+1's
  weights behind layer l's compute, so the weight stream runs at HBM
  rate with no per-matmul launch cost.
- the hidden state rides a VMEM scratch that persists across grid steps
  (loaded from the input at l == 0, emitted at l == L-1) — it never
  touches HBM between layers.
- attention reuses the flash-decode design measured in
  ``ops.decode_attention`` (fused [K|V] 128-lane rows, one depth-bounded
  double-buffered block stream per layer, in-place 8-row-aligned RMW
  write, MXU lane-routing constants, online softmax) — the cache is
  aliased in/out so it never copies.
- weight-only int8: the quantized kernels stream as int8 VMEM blocks and
  dequantize in-register after the dot (``(x @ q) * scale``), the same
  scheme ``ops.quant._pallas_linear`` measured at ~int8-HBM rate —
  but without 7 separate launches per layer.

Embedding gather, ln_f and the LM head stay in XLA: the head matmul is
one large well-formed MXU op (~30% of the step's weight bytes) that XLA
already runs at bandwidth, and fusing it would force the vocab table
through this kernel's VMEM budget for nothing.

Numerics mirror the XLA path op-for-op (f32 LN statistics, activations
in the engine dtype, f32 softmax) but reduction orders differ
(online softmax, single-dot contractions), so this path is numerically
equivalent, not byte-pinned; greedy token streams are pinned equal in
tests on the oracle seeds — the same bar as ``decode_attention``.
The fp32 BASELINE parity mode never routes here.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import BLOCK_S, NEG_INF, _WRITE_ROWS, stream_block

_LANE = 128


# VMEM budget keeps the whole-stack fusion to decode-sized batches; the
# model falls back to the per-layer kernel above this (trace-time shape).
MAX_BATCH = 16


# Numerics contract (tools/graftcheck numerics pass): the megakernel's
# in-register precision discipline, declared. Every tile op upcasts to
# f32 (weights dequantize, LN stats, rope, online-softmax accumulators
# all f32 — preferred_element_type on every dot) and the value stream
# returns to the carried activation dtype exactly once per op. The
# kernels engage only for non-fp32 regimes and their online softmax is
# allclose-not-bitwise vs the XLA path, so the public entries are
# ``exact: False`` routed to graftnum's ``decode.bf16`` budget (int8
# engines additionally ride ops/quant.py's ``decode.int8`` entries).
PRECISION_CONTRACT = {
    "decode_layers": {"regime": "carried", "exact": False,
                      "oracle": "decode.bf16", "casts": ()},
    "decode_layers_llama": {"regime": "carried", "exact": False,
                            "oracle": "decode.bf16",
                            "casts": ("f32",)},  # rope cos/sin upcast
    "_ln": {"regime": "carried", "exact": True,
            "casts": ("f32", "carried")},
    "_rms": {"regime": "carried", "exact": True,
             "casts": ("f32", "carried")},
    "_gelu_new": {"regime": "carried", "exact": True, "casts": ()},
    "_matmul": {"regime": "carried", "exact": True, "accumulate": "f32",
                "casts": ("f32", "carried")},
    "_split_rows": {"regime": "f32", "exact": True, "accumulate": "f32",
                    "casts": ("f32",)},
    "_merge_rows": {"regime": "f32", "exact": True, "accumulate": "f32",
                    "casts": ("f32",)},
    "_rope_rows": {"regime": "f32", "exact": True, "accumulate": "f32",
                   "casts": ("f32",)},
    "_attention": {"regime": "f32", "exact": False,
                   "oracle": "decode.bf16", "accumulate": "f32",
                   "casts": ("f32", "carried")},
    "_kernel": {"regime": "carried", "exact": False,
                "oracle": "decode.bf16", "casts": ("f32", "carried")},
    "_llama_kernel": {"regime": "carried", "exact": False,
                      "oracle": "decode.bf16",
                      "casts": ("f32", "carried")},
}


def mega_requested(decode_kernel, seq_len: int) -> bool:
    """Shared dispatch predicate for every megakernel call site (model
    forwards and the stage runner)."""
    return (bool(decode_kernel) and decode_kernel.startswith("mega")
            and seq_len == 1)


def mega_downgrade(decode_kernel: str) -> str:
    """The per-layer-kernel mode a mega engine falls back to at trace
    time (batch past MAX_BATCH)."""
    return "interpret" if decode_kernel == "mega-interpret" else "device"
# Conservative VMEM ceiling for the eligibility estimate: the call sets
# vmem_limit_bytes=110MB; leave slack for accumulators/activations so
# "auto" never selects a megakernel Mosaic cannot allocate.
_VMEM_BUDGET = 90 * 1024 * 1024


def _vmem_fits(weight_elems_per_layer: int, hkv: int, hd: int,
               itemsize: int) -> bool:
    """The two big VMEM tenants: double-buffered layer weights (BlockSpec
    pipelining) and the double-buffered KV stream at the worst-case
    batch, at the engine's ACTUAL itemsize (fp32 engines reachable via
    the explicit 'mega' mode need twice bf16's budget)."""
    kv_stream = 2 * MAX_BATCH * hkv * BLOCK_S * 2 * hd * itemsize
    return 2 * weight_elems_per_layer * itemsize + kv_stream <= _VMEM_BUDGET


def eligible(config, max_seq: int, itemsize: int = 2) -> bool:
    """Whether the megakernel applies to this GPT-2 geometry: fused rows
    lane-aligned, cache in whole blocks, every matmul dim lane-aligned
    (real-model sizes are; toy test sizes fall back to the per-layer
    kernel), and the per-layer weights + KV stream fit the VMEM budget
    at the engine dtype's ``itemsize`` so no selection path picks an
    uncompilable kernel. Batch is a trace-time check (``MAX_BATCH``)."""
    d = config.n_embd
    return ((2 * config.head_dim) % _LANE == 0
            and max_seq % BLOCK_S == 0 and max_seq >= BLOCK_S
            and d % _LANE == 0
            and _vmem_fits(12 * d * d, config.n_head, config.head_dim,
                           itemsize))


def _ln(h, scale, bias, eps):
    """f32-stat LayerNorm on a [B, D] tile (mirrors ops.layers.layer_norm
    including the cast back to the activation dtype)."""
    x32 = h.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mean) ** 2, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(h.dtype)


def _gelu_new(x):
    # sqrt(2/pi) as a literal: Mosaic cannot legalize a scalar math.sqrt
    c = jnp.asarray(0.7978845608028654, dtype=x.dtype)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


def _split_rows(x, n_heads: int, hd: int):
    """[B, n_heads*hd] f32 -> [B*n_heads, hd]: the head split, without
    the lane-splitting vector reshape Mosaic rejects. Broadcast rows
    across a head axis (sublanes), zero out other heads' lanes, then
    collapse each head's lane group onto lanes [0, hd) with an
    iota-built projection on the MXU."""
    b, d = x.shape
    hm = (jax.lax.broadcasted_iota(jnp.int32, (n_heads, d), 1) // hd
          == jax.lax.broadcasted_iota(jnp.int32, (n_heads, d), 0)
          ).astype(jnp.float32)                        # [H, D] head mask
    c = (jax.lax.broadcasted_iota(jnp.int32, (d, hd), 0) % hd
         == jax.lax.broadcasted_iota(jnp.int32, (d, hd), 1)
         ).astype(jnp.float32)                         # [D, hd] collapse
    xb = jnp.broadcast_to(x[:, None, :], (b, n_heads, d)) * hm
    return jax.lax.dot_general(xb.reshape(b * n_heads, d), c,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _merge_rows(attn, b: int, n_heads: int, hd: int):
    """[B*n_heads, hd] f32 -> [B, n_heads*hd]: the head merge — expand
    each head's lanes back to its own lane group (MXU projection + head
    mask), then sum the head axis."""
    d = n_heads * hd
    cexp = (jax.lax.broadcasted_iota(jnp.int32, (hd, d), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (hd, d), 1) % hd
            ).astype(jnp.float32)                      # [hd, D] expand
    hm = (jax.lax.broadcasted_iota(jnp.int32, (n_heads, d), 1) // hd
          == jax.lax.broadcasted_iota(jnp.int32, (n_heads, d), 0)
          ).astype(jnp.float32)
    y = jax.lax.dot_general(attn, cexp, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return (y.reshape(b, n_heads, d) * hm).sum(axis=1)


def _matmul(x, w_ref, s_ref, b_ref, quantized: bool):
    """[B, in] @ (layer block of) [1, in, out] -> [B, out] in x.dtype.
    Quantized blocks dequantize in-register via the per-channel scale."""
    w = w_ref[0].astype(jnp.float32)
    y = jax.lax.dot_general(x.astype(jnp.float32), w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if quantized:
        y = y * s_ref[0, 0].astype(jnp.float32)
    if b_ref is not None:
        y = y + b_ref[0, 0].astype(jnp.float32)
    return y.astype(x.dtype)


def _attention(l, off, q, k_new, v_new, vf_ref, kv_hbm, kv_out,
               acc_ref, m_ref, l_ref, kvbuf, winbuf, copy_sems, write_sem,
               *, batch, hkv, g, hd):
    """Single-token cached attention for layer ``l`` against the fused
    [L, B, Hkv, S, 2hd] HBM cache — the ops.decode_attention design
    inlined (same DMA shape, same lane-routing constants, same
    online-softmax order), operating on in-register q/k/v from this
    layer's QKV projection. Returns [B*Hkv, g, hd] f32 and performs the
    in-place fused-row cache write.

    SYNC CONTRACT with ``ops.decode_attention._kernel``: this body is a
    deliberate inline of that kernel's loop (a ref-level shared helper
    would force re-verifying the proven per-layer kernel for zero
    behavior change — the inputs here are in-register values, there
    refs). Each kernel carries its OWN XLA-oracle exactness suite
    (tests/test_decode_attention.py, tests/test_decode_layer.py), so a
    behavior fix applied to one and not the other fails the stale
    side's tests; apply masking/finalize/write-window changes to BOTH."""
    bh = batch * hkv
    scale = 1.0 / (hd ** 0.5)
    block_s = kvbuf.shape[3]               # decode_attention.stream_block

    row2 = jax.lax.broadcasted_iota(jnp.int32, (hd, 2 * hd), 0)
    col2 = jax.lax.broadcasted_iota(jnp.int32, (hd, 2 * hd), 1)
    p_k = (row2 == col2).astype(jnp.float32)               # [hd, 2hd]
    rowv = jax.lax.broadcasted_iota(jnp.int32, (2 * hd, hd), 0)
    colv = jax.lax.broadcasted_iota(jnp.int32, (2 * hd, hd), 1)
    p_v = (rowv == colv + hd).astype(jnp.float32)          # [2hd, hd]

    qs = q * scale                                         # [BH, g, hd] f32
    q_ext = jax.lax.dot_general(qs, p_k, (((2,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    vf_bh = vf_ref[...]                                    # [BH, 1, 1]

    n_blk = jnp.maximum((off + block_s - 1) // block_s, 1)

    def fetch(slot, i):
        return pltpu.make_async_copy(
            kv_hbm.at[l, :, :, pl.ds(i * block_s, block_s), :],
            kvbuf.at[slot], copy_sems.at[slot])

    fetch(0, 0).start()
    base = (off // _WRITE_ROWS) * _WRITE_ROWS
    win_rd = pltpu.make_async_copy(
        kv_hbm.at[l, :, :, pl.ds(base, _WRITE_ROWS), :], winbuf, write_sem)
    win_rd.start()
    m_ref[...] = jnp.full((bh, g, 1), NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros((bh, g, 1), jnp.float32)
    acc_ref[...] = jnp.zeros((bh, g, 2 * hd), jnp.float32)

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blk)
        def _():
            fetch(1 - slot, i + 1).start()

        fetch(slot, i).wait()
        kvb = kvbuf[slot].astype(jnp.float32).reshape(bh, block_s, 2 * hd)
        s = jax.lax.dot_general(q_ext, kvb, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        pos = i * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_s), 2)
        ok = (pos < off) & (pos >= vf_bh)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=2, keepdims=True))
        corr = jnp.exp(m_ref[...] - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        pv = jax.lax.dot_general(p, kvb, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new
        return 0

    jax.lax.fori_loop(0, n_blk, body, 0)

    s_self = jax.lax.dot_general(qs, k_new, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
    m_fin = jnp.maximum(m_ref[...], s_self)
    corr_f = jnp.exp(m_ref[...] - m_fin)
    p_self = jnp.exp(s_self - m_fin)
    l_fin = l_ref[...] * corr_f + p_self
    acc_v = jax.lax.dot_general(acc_ref[...] * corr_f, p_v,
                                (((2,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    acc_v = acc_v + p_self * v_new                         # [BH, g, hd]
    out = acc_v / l_fin

    # in-place fused-row write (all (b, h) at once, 8-row RMW window)
    win_rd.wait()
    kn2 = k_new.reshape(bh, hd)
    vn2 = v_new.reshape(bh, hd)
    rows = (jax.lax.dot_general(kn2, p_k, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(vn2, p_v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32))
    rows = rows.reshape(batch, hkv, 1, 2 * hd).astype(winbuf.dtype)
    row_iota = jax.lax.broadcasted_iota(
        jnp.int32, (batch, hkv, _WRITE_ROWS, 2 * hd), 2)
    winbuf[...] = jnp.where(row_iota == off - base, rows, winbuf[...])
    wr = pltpu.make_async_copy(
        winbuf, kv_out.at[l, :, :, pl.ds(base, _WRITE_ROWS), :], write_sem)
    wr.start()
    wr.wait()
    return out


def _kernel(meta_ref,
            # per-layer weight blocks (BlockSpec-pipelined, leading 1)
            ln1_s, ln1_b, wqkv, sqkv, bqkv, wout, sout, bout,
            ln2_s, ln2_b, wfc, sfc, bfc, wproj, sproj, bproj,
            # whole-array operands
            h0_ref, vf_ref, kv_hbm,
            # outputs
            hout_ref, kv_out,
            # scratch
            h_ref, acc_ref, m_ref, l_ref, kvbuf, winbuf, copy_sems,
            write_sem,
            *, n_layer, batch, n_head, hkv, hd, eps, quantized):
    l = pl.program_id(0)
    off = meta_ref[0]

    @pl.when(l == 0)
    def _():
        h_ref[...] = h0_ref[...]

    h = h_ref[...]                                         # [B, D]
    d = h.shape[-1]
    g = n_head // hkv

    a = _ln(h, ln1_s[0, 0], ln1_b[0, 0], eps)
    qkv = _matmul(a, wqkv, sqkv, bqkv, quantized)          # [B, 3D]
    qkv32 = qkv.astype(jnp.float32)
    # head split via MXU lane routing (_split_rows): q rows group as
    # [B*Hkv, g, hd]; k/v as [B*Hkv, 1, hd] (sublane-only reshapes)
    q = _split_rows(qkv32[:, :d], n_head, hd).reshape(batch * hkv, g, hd)
    k_new = _split_rows(qkv32[:, d:2 * d], hkv, hd
                        ).reshape(batch * hkv, 1, hd)
    v_new = _split_rows(qkv32[:, 2 * d:], hkv, hd
                        ).reshape(batch * hkv, 1, hd)

    attn = _attention(l, off, q, k_new, v_new, vf_ref, kv_hbm, kv_out,
                      acc_ref, m_ref, l_ref, kvbuf, winbuf, copy_sems,
                      write_sem, batch=batch, hkv=hkv, g=g, hd=hd)
    attn = _merge_rows(attn.reshape(batch * n_head, hd), batch, n_head,
                       hd).astype(h.dtype)                 # [B, D]

    h = h + _matmul(attn, wout, sout, bout, quantized)
    m = _ln(h, ln2_s[0, 0], ln2_b[0, 0], eps)
    t = _gelu_new(_matmul(m, wfc, sfc, bfc, quantized))
    h = h + _matmul(t, wproj, sproj, bproj, quantized)
    h_ref[...] = h

    @pl.when(l == n_layer - 1)
    def _():
        hout_ref[...] = h


def _quant_pairs(kernels: list) -> Tuple[list, bool]:
    """Shared quantization plumbing for both families' part builders:
    kernel leaves -> ``[(w, scale), ...]`` plus the all-or-nothing
    quantized flag. A partially quantized tree would silently treat raw
    int8 codes as float weights (or drop a real scale) — refuse. Float
    trees get 1-lane dummy scales so both cases share one kernel
    signature (the static ``quantized`` flag means they are never
    read)."""
    from .quant import is_quantized

    pairs = [(leaf.q, leaf.scale) if is_quantized(leaf) else (leaf, None)
             for leaf in kernels]
    quantized = pairs[0][1] is not None
    if any((s is not None) != quantized for _, s in pairs):
        raise ValueError("mixed quantized/float block kernels")
    if not quantized:
        pairs = [(w, jnp.ones((w.shape[0], 1), jnp.float32))
                 for w, _ in pairs]
    return pairs, quantized


def _stack_vectors(parts: list) -> list:
    """Per-layer VECTORS ride as [L, 1, D]: Mosaic requires a block's
    last two dims to divide (8, 128) or equal the array's — a (1, D)
    block of an [L, D] array does neither, a (1, 1, D) block of
    [L, 1, D] matches exactly."""
    return [x[:, None, :] if x.ndim == 2 else x for x in parts]


def _weight_parts(blocks) -> Tuple[list, bool]:
    """Flatten the stacked GPT-2 block tree into the kernel's operand
    order; quantized kernels contribute (q, scale) pairs (dummy scales
    for float trees — see ``_quant_pairs``)."""
    a = blocks["attn"]
    mlp = blocks["mlp"]
    pairs, quantized = _quant_pairs(
        [a["c_attn"]["kernel"], a["c_proj"]["kernel"],
         mlp["c_fc"]["kernel"], mlp["c_proj"]["kernel"]])
    (wqkv, sqkv), (wout, sout), (wfc, sfc), (wproj, sproj) = pairs
    parts = [
        blocks["ln_1"]["scale"], blocks["ln_1"]["bias"],
        wqkv, sqkv, a["c_attn"]["bias"],
        wout, sout, a["c_proj"]["bias"],
        blocks["ln_2"]["scale"], blocks["ln_2"]["bias"],
        wfc, sfc, mlp["c_fc"]["bias"],
        wproj, sproj, mlp["c_proj"]["bias"],
    ]
    return _stack_vectors(parts), quantized


def _build_call(kernel, parts, vmem_operands, KV, meta, *, n_head,
                interpret):
    """Shared pallas_call plumbing for both family kernels: grid over
    layers with BlockSpec-pipelined stacked weights, whole-array VMEM
    operands (``vmem_operands[0]`` is the hidden state, whose shape and
    dtype define the output), the HBM-aliased fused cache, and the
    attention scratch set."""
    L, B, Hkv, _, hd2 = KV.shape
    hd = hd2 // 2
    h0 = vmem_operands[0]
    # VMEM the KV stream cannot have: the pipelined weight windows (two
    # layers' blocks resident at once) and _matmul's f32 working copy of
    # the largest weight, with the conversion's intermediate beside it
    # (the v5e compiler refused GPT-2 medium int8 at B=8 without this
    # term: its temporaries alone overran vmem_limit_bytes)
    sizes = [math.prod(x.shape[1:]) for x in parts]
    reserved = (2 * sum(n * x.dtype.itemsize for n, x in zip(sizes, parts))
                + 2 * 4 * max(sizes))
    block_s = stream_block(B * Hkv, hd, KV.dtype.itemsize, reserved=reserved)

    def layer_block(x):
        # one layer's block of a stacked [L, ...] tensor, pipelined
        # (index_map gets the scalar-prefetch ref as a trailing arg)
        return pl.BlockSpec((1,) + x.shape[1:],
                            lambda l, _meta, nd=x.ndim: (l,) + (0,) * (nd - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L,),
        in_specs=([layer_block(x) for x in parts]
                  + [pl.BlockSpec(memory_space=pltpu.VMEM)
                     for _ in vmem_operands]
                  + [pl.BlockSpec(memory_space=pltpu.HBM)]),  # KV (aliased)
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),            # h out
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        scratch_shapes=[
            pltpu.VMEM(h0.shape, h0.dtype),                   # h carry
            pltpu.VMEM((B * Hkv, n_head // Hkv, 2 * hd), jnp.float32),
            pltpu.VMEM((B * Hkv, n_head // Hkv, 1), jnp.float32),
            pltpu.VMEM((B * Hkv, n_head // Hkv, 1), jnp.float32),
            pltpu.VMEM((2, B, Hkv, block_s, 2 * hd), KV.dtype),
            pltpu.VMEM((B, Hkv, _WRITE_ROWS, 2 * hd), KV.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    n_in = 1 + len(parts) + len(vmem_operands) + 1
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(h0.shape, h0.dtype),
            jax.ShapeDtypeStruct(KV.shape, KV.dtype),
        ],
        input_output_aliases={n_in - 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=110 * 1024 * 1024),
        interpret=interpret,
    )(meta, *parts, *vmem_operands, KV)


@functools.partial(jax.jit,
                   static_argnames=("quantized", "n_head", "eps",
                                    "interpret"))
def _call(parts, h0, vf_bh, KV, meta, *, quantized, n_head, eps,
          interpret):
    L, B, Hkv, _, hd2 = KV.shape
    kernel = functools.partial(
        _kernel, n_layer=L, batch=B, n_head=n_head, hkv=Hkv, hd=hd2 // 2,
        eps=eps, quantized=quantized)
    return _build_call(kernel, parts, [h0, vf_bh], KV, meta,
                       n_head=n_head, interpret=interpret)


def llama_eligible(config, max_seq: int, itemsize: int = 2) -> bool:
    """Megakernel eligibility for the llama family: everything GPT-2
    needs, plus lane-aligned kv-projection and SwiGLU hidden dims."""
    d = config.n_embd
    kv = config.n_kv_head * config.head_dim
    per_layer = (2 * d * d + 2 * d * kv
                 + 3 * d * config.intermediate_size)
    return ((2 * config.head_dim) % _LANE == 0
            and max_seq % BLOCK_S == 0 and max_seq >= BLOCK_S
            and d % _LANE == 0 and kv % _LANE == 0
            and config.intermediate_size % _LANE == 0
            and _vmem_fits(per_layer, config.n_kv_head, config.head_dim,
                           itemsize))


def _rms(h, scale, eps):
    """f32-stat RMSNorm (mirrors ops.layers.rms_norm incl. the cast
    BEFORE the scale multiply — HF LlamaRMSNorm order)."""
    x32 = h.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return y.astype(h.dtype) * scale.astype(h.dtype)


def _rope_rows(x, cos_ref, sin_ref, batch: int, n_heads: int, hd: int):
    """Rotate [B*n_heads, hd] f32 rows by per-BATCH-row angles
    ([B, hd] f32 refs). rotate_half is an iota-built permutation on the
    MXU (a 32-lane shuffle Mosaic would reject as a vector op)."""
    half = hd // 2
    row = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
    # rotate_half(x)[j] = -x[j+half] (j < half) | x[j-half] (j >= half)
    r = (jnp.where(col < half, -1.0, 0.0) * (row == col + half)
         + jnp.where(col >= half, 1.0, 0.0) * (row + half == col)
         ).astype(jnp.float32)
    rot = jax.lax.dot_general(x, r, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)

    def widen(a):            # [B, hd] -> [B*n_heads, hd]
        return jnp.broadcast_to(a[:, None, :],
                                (batch, n_heads, hd)
                                ).reshape(batch * n_heads, hd)

    return x * widen(cos_ref[...]) + rot * widen(sin_ref[...])


def _llama_kernel(meta_ref,
                  ln_a, wq, sq, wk, sk, wv, sv, wo, so,
                  ln_m, wg, sg, wu, su, wd, sd,
                  h0_ref, vf_ref, cos_ref, sin_ref, kv_hbm,
                  hout_ref, kv_out,
                  h_ref, acc_ref, m_ref, l_ref, kvbuf, winbuf, copy_sems,
                  write_sem,
                  *, n_layer, batch, n_head, hkv, hd, eps, quantized):
    """llama-family sibling of ``_kernel``: RMSNorm, separate q/k/v
    projections, RoPE (in-kernel MXU rotate-half), GQA attention, and
    SwiGLU — same layer-grid / persistent-h / inlined-attention design."""
    l = pl.program_id(0)
    off = meta_ref[0]

    @pl.when(l == 0)
    def _():
        h_ref[...] = h0_ref[...]

    h = h_ref[...]
    g = n_head // hkv

    a = _rms(h, ln_a[0, 0], eps)
    q = _matmul(a, wq, sq, None, quantized).astype(jnp.float32)
    k = _matmul(a, wk, sk, None, quantized).astype(jnp.float32)
    v = _matmul(a, wv, sv, None, quantized).astype(jnp.float32)
    q_r = _split_rows(q, n_head, hd)                   # [B*H, hd]
    k_r = _split_rows(k, hkv, hd)                      # [B*Hkv, hd]
    q_r = _rope_rows(q_r, cos_ref, sin_ref, batch, n_head, hd)
    k_r = _rope_rows(k_r, cos_ref, sin_ref, batch, hkv, hd)
    q3 = q_r.reshape(batch * hkv, g, hd)
    k3 = k_r.reshape(batch * hkv, 1, hd)
    v3 = _split_rows(v, hkv, hd).reshape(batch * hkv, 1, hd)

    attn = _attention(l, off, q3, k3, v3, vf_ref, kv_hbm, kv_out,
                      acc_ref, m_ref, l_ref, kvbuf, winbuf, copy_sems,
                      write_sem, batch=batch, hkv=hkv, g=g, hd=hd)
    attn = _merge_rows(attn.reshape(batch * n_head, hd), batch, n_head,
                       hd).astype(h.dtype)

    h = h + _matmul(attn, wo, so, None, quantized)
    mm = _rms(h, ln_m[0, 0], eps)
    gate = _matmul(mm, wg, sg, None, quantized)
    up = _matmul(mm, wu, su, None, quantized)
    t = (gate * jax.lax.logistic(gate.astype(jnp.float32)
                                 ).astype(gate.dtype)) * up   # SwiGLU
    h = h + _matmul(t, wd, sd, None, quantized)
    h_ref[...] = h

    @pl.when(l == n_layer - 1)
    def _():
        hout_ref[...] = h


def _llama_weight_parts(blocks) -> Tuple[list, bool]:
    a = blocks["attn"]
    mlp = blocks["mlp"]
    pairs, quantized = _quant_pairs(
        [a["wq"]["kernel"], a["wk"]["kernel"], a["wv"]["kernel"],
         a["wo"]["kernel"], mlp["gate"]["kernel"], mlp["up"]["kernel"],
         mlp["down"]["kernel"]])
    (wq, sq), (wk, sk), (wv, sv), (wo, so), (wg, sg), (wu, su), (wd, sd) \
        = pairs
    parts = [
        blocks["ln_attn"]["scale"],
        wq, sq, wk, sk, wv, sv, wo, so,
        blocks["ln_mlp"]["scale"],
        wg, sg, wu, su, wd, sd,
    ]
    return _stack_vectors(parts), quantized


@functools.partial(jax.jit,
                   static_argnames=("quantized", "n_head", "eps",
                                    "interpret"))
def _llama_call(parts, h0, vf_bh, cos, sin, KV, meta, *, quantized,
                n_head, eps, interpret):
    L, B, Hkv, _, hd2 = KV.shape
    kernel = functools.partial(
        _llama_kernel, n_layer=L, batch=B, n_head=n_head, hkv=Hkv,
        hd=hd2 // 2, eps=eps, quantized=quantized)
    return _build_call(kernel, parts, [h0, vf_bh, cos, sin], KV, meta,
                       n_head=n_head, interpret=interpret)


def decode_layers_llama(blocks, h, KV, offset, cos, sin,
                        k_valid_from: Optional[jnp.ndarray] = None,
                        *, n_head: int, eps: float,
                        interpret: bool = False,
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """llama-family front end of the megakernel. ``cos``/``sin`` are the
    CURRENT position's per-batch-row rotary angles ``[B, hd]`` f32
    (computed by the caller — ops.rope convention)."""
    b, s, d = h.shape
    if s != 1:
        raise ValueError(f"megakernel is single-token only, got S={s}")
    L, _, hkv, _, _ = KV.shape
    parts, quantized = _llama_weight_parts(blocks)
    if k_valid_from is None:
        k_valid_from = jnp.zeros((b,), jnp.int32)
    vf_bh = jnp.repeat(k_valid_from.astype(jnp.int32), hkv)[:, None, None]
    meta = jnp.asarray([offset], jnp.int32).reshape(1)
    hout, KV = _llama_call(parts, h.reshape(b, d), vf_bh,
                           cos.astype(jnp.float32),
                           sin.astype(jnp.float32), KV, meta,
                           quantized=quantized, n_head=n_head, eps=eps,
                           interpret=interpret)
    return hout.reshape(b, 1, d), KV


def decode_layers(blocks, h, KV, offset,
                  k_valid_from: Optional[jnp.ndarray] = None,
                  *, n_head: int, eps: float,
                  interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Run the full GPT-2 block stack for ONE decode token in one launch.

    ``blocks``: the model's stacked ``[L, ...]`` block param tree (float
    or weight-only-int8); ``h`` ``[B, 1, D]`` the post-embedding hidden
    state; ``KV`` the fused ``[L, B, Hkv, Smax, 2*hd]`` cache (returned
    updated in place — aliased, the caller must treat the passed buffer
    as consumed); ``offset`` the current cache depth (traced scalar);
    ``k_valid_from`` ``[B]`` left-pad mask rows. Returns ``(h [B,1,D],
    KV)`` ready for ln_f + the LM head.
    """
    b, s, d = h.shape
    if s != 1:
        raise ValueError(f"megakernel is single-token only, got S={s}")
    L, _, hkv, _, _ = KV.shape
    parts, quantized = _weight_parts(blocks)
    if k_valid_from is None:
        k_valid_from = jnp.zeros((b,), jnp.int32)
    vf_bh = jnp.repeat(k_valid_from.astype(jnp.int32), hkv)[:, None, None]
    meta = jnp.asarray([offset], jnp.int32).reshape(1)
    hout, KV = _call(parts, h.reshape(b, d), vf_bh, KV, meta,
                     quantized=quantized, n_head=n_head, eps=eps,
                     interpret=interpret)
    return hout.reshape(b, 1, d), KV
