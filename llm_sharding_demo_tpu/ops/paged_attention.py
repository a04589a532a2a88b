"""Gather-based paged attention over a block-pool KV cache.

The contiguous decode stack allocates one ``[L, B, H, max_seq, hd]``
buffer pair per live batch row for the row's whole lifetime — a row
decoding at depth 40 in a 512-slot cache holds 512 slots of HBM, and a
parked or cached prefix state duplicates the entire allocation
(runtime.prefix_cache stored full prefill states per entry before the
pool existed). Helix Parallelism (PAPERS.md) makes the serving-side
observation this module acts on: at interactive batch sizes KV-cache
CAPACITY and placement bound concurrency, not FLOPs — so KV memory needs
a first-class manager with sub-row granularity.

This module is the ops layer of that manager (the allocator/runner live
in ``runtime.kv_pool``): attention and data movement over a POOLED cache,

- **pool**: one fixed ``[n_layer, num_blocks(+1), 2, n_kv_head,
  block_size, head_dim]`` buffer — per layer, ``[num_blocks, 2, Hkv,
  bs, hd]`` of KV blocks (k at index 0 of the pair axis, v at 1). The
  trailing ``+1`` block is the shared TRASH block: ghost rows and
  masked pad-prefix slots point at it, so every scatter target is a
  real block and no per-row liveness branching enters any program.
  The ``(2, n_kv_head, head_dim)`` of it is the family's cache entry
  (``models.cache_entry``): a family that caches ONE vector a position
  has a pool of ``[L, num_blocks+1, 1, 1, bs, width]``, moved by
  ``gather_rows`` / ``scatter_rows`` below (``copy_blocks`` serves
  both).
- **block tables**: ``[B, blocks_per_row]`` int32, TRACED operands —
  logical cache slot ``p`` of row ``b`` lives in pool block
  ``table[b, p // bs]`` at offset ``p % bs``. Tables never key
  programs: one compiled gather/scatter/attend serves every placement.
- **gather-based attention**: reads assemble the row's logical
  ``[Hkv, S, hd]`` view by gathering blocks (``jnp.take`` on the block
  axis). Static shapes throughout — the gathered view is always the
  full ``blocks_per_row * bs`` width, with causal/length masking doing
  what it already does for the contiguous cache (masked slots get
  exact-zero softmax weight in fp32, so trash-block garbage cannot
  perturb outputs — the same tolerance the left-pad and admission-roll
  machinery already relies on).

Two consumption patterns:

- ``paged_decode_attention``: the per-token path — single-token cached
  attention reading straight from the pool and writing the new K/V
  column into its block in place. The paged sibling of
  ``ops.attention.cached_attention_inplace`` (and the hook a Pallas
  paged kernel would slot into, the way ``ops.decode_attention``
  does for the contiguous fused cache:
  same HBM-resident pool ref, block-table-driven DMAs instead of
  ``jnp.take``). Byte-equal to the contiguous path — pinned by
  tests/test_paged_attention.py.
- ``gather_kv`` / ``scatter_kv``: the segment-granularity path the
  decode engines use (runtime.kv_pool): gather the pool-resident rows
  into a contiguous working cache, run the engine's existing
  (unchanged, byte-pinned) segment program, and scatter the updated
  rows back: paging without touching a single model program. The paged
  runner does so ONCE per compiled decode segment. The iteration
  scheduler, whose calls end at every retirement, keeps the working
  cache for the batch's life and scatters only the columns a call
  wrote (``column_span``): the whole-row forms then serve its seeds
  and joiners, and nothing gathers.

``scatter_kv`` writes with an UNROLLED ``dynamic_update_slice`` chain,
not ``.at[].set``: duplicate targets (every ghost/pad entry aliases the
one trash block) would make a scatter's result order-undefined, while
sequential updates are deterministic by construction — last write wins,
and only the trash block ever receives duplicates.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import causal_attention

# The default logical block width (cache slots per block) lives in
# utils.metrics.DEFAULT_KV_BLOCK_SIZE — shared with the block-gauge
# denomination so pooled and contiguous components report in the same
# unit by construction. 16 keeps block rows MXU-lane-friendly at hd=64
# (16*64 = 1024 lanes per [bs, hd] slice) while bounding per-row waste
# at an average bs/2 = 8 slots — against the contiguous allocator's
# max_seq - depth (hundreds).

# Static-analysis contract (tools/graftcheck): the jitted callables this
# module exposes, by holding name — the recompile-budget certifier
# (tools/graftcheck/recompile.py) enumerates these.
JIT_ENTRY_POINTS = ("paged_decode_attention",)


def pool_shape(n_layer: int, num_blocks: int, n_kv_head: int,
               block_size: int, head_dim: int,
               planes: int = 2) -> Tuple[int, ...]:
    """THE pool aval contract (one extra physical block: the trash
    block at index ``num_blocks``). graftcheck's paged contract family
    checks gather/scatter round-trips against this shape. ``planes``,
    ``n_kv_head`` and ``head_dim`` are the family's cache entry."""
    return (n_layer, num_blocks + 1, planes, n_kv_head, block_size,
            head_dim)


def blocks_per_row(max_seq: int, block_size: int) -> int:
    """Block-table width covering a ``max_seq``-slot logical row.
    ``max_seq`` must be a block multiple so the gathered contiguous
    view is EXACTLY the engine's cache width — the decode programs are
    then shared (and byte-identical) between paged and contiguous
    storage."""
    if max_seq % block_size:
        raise ValueError(
            f"max_seq={max_seq} is not a multiple of block_size="
            f"{block_size}; the gathered view must match the engine's "
            "cache width exactly")
    return max_seq // block_size


def gather_kv(pool: jnp.ndarray, tables: jnp.ndarray,
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Assemble contiguous per-row K/V views from the pool.

    pool ``[L, NBp, 2, H, bs, hd]``; tables ``[B, NBm]`` int32 (traced).
    Returns ``(k, v)`` each ``[L, B, H, NBm*bs, hd]`` — the engine's
    contiguous cache layout, byte-for-byte the scattered content (trash
    garbage lands only in slots the attention mask excludes).
    """
    b, nbm = tables.shape
    l, _, _, h, bs, hd = pool.shape
    g = jnp.take(pool, tables.reshape(-1), axis=1)  # [L, B*NBm, 2, H, bs, hd]
    g = g.reshape(l, b, nbm, 2, h, bs, hd)
    g = g.transpose(3, 0, 1, 4, 2, 5, 6)            # [2, L, B, H, NBm, bs, hd]
    kv = g.reshape(2, l, b, h, nbm * bs, hd)
    return kv[0], kv[1]


def scatter_kv(pool: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               tables: jnp.ndarray) -> jnp.ndarray:
    """Write contiguous per-row K/V back into their pool blocks.

    Inverse of ``gather_kv`` (k/v ``[L, B, H, NBm*bs, hd]``). The write
    chain is an unrolled per-(row, block) ``dynamic_update_slice`` —
    ``B * NBm`` updates of one block each — so duplicate targets (all
    ghost/pad entries alias the single trash block) resolve
    deterministically instead of hitting scatter's undefined-order
    semantics. Block indices are traced scalars: one compiled program
    per (B, NBm) shape, regardless of placement.
    """
    l, b, h, s, hd = k.shape
    nbm = tables.shape[1]
    bs = s // nbm
    kk = k.reshape(l, b, h, nbm, bs, hd)
    vv = v.reshape(l, b, h, nbm, bs, hd)
    # [B, NBm, L, 2, H, bs, hd]: one leading (row, block) index pair per
    # update
    src = jnp.stack([kk, vv], axis=0).transpose(2, 4, 1, 0, 3, 5, 6)
    for bi in range(b):
        for j in range(nbm):
            pool = jax.lax.dynamic_update_slice(
                pool, src[bi, j][:, None].astype(pool.dtype),
                (jnp.zeros((), jnp.int32), tables[bi, j],
                 jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                 jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)))
    return pool


def gather_rows(pool: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """``gather_kv`` for a one-plane pool ``[L, NBp, 1, H, bs, width]``
    (a latent cache: one head; a cache that keeps ``[K | V]`` in one
    fused row a kv head): a block is ``bs`` whole rows a head of the
    row-major cache, so the view ``[L, B, H, NBm*bs, width]`` is
    assembled by one block copy per table entry, in a loop, straight
    into the output. (The general form's take-then-transpose needs two
    more copies of the view as temporaries, which a chip full of weights
    does not have.)"""
    b, nbm = tables.shape
    l, _, _, h, bs, w = pool.shape
    flat = tables.reshape(-1)
    zero = jnp.zeros((), jnp.int32)

    def one(i, out):
        blk = jax.lax.dynamic_slice(
            pool, (zero, flat[i], zero, zero, zero, zero),
            (l, 1, 1, h, bs, w))
        return jax.lax.dynamic_update_slice(
            out, blk.reshape(l, 1, h, bs, w),
            (zero, i // nbm, zero, (i % nbm) * bs, zero))

    return jax.lax.fori_loop(
        0, b * nbm, one, jnp.zeros((l, b, h, nbm * bs, w), pool.dtype))


def scatter_rows(pool: jnp.ndarray, k: jnp.ndarray,
                 tables: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``gather_rows``: the same block copies the other way,
    in table order, so duplicate targets (the trash block) resolve as
    in ``scatter_kv``: the last write wins."""
    b, nbm = tables.shape
    l, _, _, h, bs, w = pool.shape
    flat = tables.reshape(-1)
    zero = jnp.zeros((), jnp.int32)

    def one(i, pool):
        blk = jax.lax.dynamic_slice(
            k, (zero, i // nbm, zero, (i % nbm) * bs, zero),
            (l, 1, h, bs, w))
        return jax.lax.dynamic_update_slice(
            pool, blk.reshape(l, 1, 1, h, bs, w).astype(pool.dtype),
            (zero, flat[i], zero, zero, zero, zero))

    return jax.lax.fori_loop(0, b * nbm, one, pool)


def span_blocks(seg_steps: int, block_size: int, nbm: int) -> int:
    """Table columns a decode call of at most ``seg_steps`` positions
    can write, wherever in a block it starts (the first position may be
    a block's last), capped at the table's width."""
    return min(nbm, (seg_steps + block_size - 2) // block_size + 1)


def column_span(tables: jnp.ndarray, col, span: int, *views):
    """Table columns ``[col, col + span)`` and the slots they hold of
    each contiguous view ``[L, B, H, NBm*bs, w]``: what the whole-row
    scatters above take, ``span`` blocks wide. ``col`` is a TRACED
    scalar (one program serves every depth), at most ``NBm - span``,
    and ``span`` is static."""
    bs = views[0].shape[-2] // tables.shape[1]
    return (jax.lax.dynamic_slice_in_dim(tables, col, span, axis=1),
            *(jax.lax.dynamic_slice_in_dim(x, col * bs, span * bs, axis=-2)
              for x in views))


def copy_blocks(pool: jnp.ndarray, src: jnp.ndarray,
                dst: jnp.ndarray) -> jnp.ndarray:
    """Copy whole blocks ``src[i] -> dst[i]`` (both ``[n]`` int32,
    traced) across every layer — THE copy-on-write primitive: a writer
    holding a shared (refcount > 1) block copies it here and retargets
    its table entry before the first write."""
    n = src.shape[0]
    zero = jnp.zeros((), jnp.int32)
    for i in range(n):
        blk = jax.lax.dynamic_slice(
            pool, (zero, src[i], zero, zero, zero, zero),
            (pool.shape[0], 1) + pool.shape[2:])
        pool = jax.lax.dynamic_update_slice(
            pool, blk, (zero, dst[i], zero, zero, zero, zero))
    return pool


def write_token_kv(pool: jnp.ndarray, k_new: jnp.ndarray,
                   v_new: jnp.ndarray, tables: jnp.ndarray,
                   layer_idx, offset) -> jnp.ndarray:
    """Write one token's K/V column into its pool block, one layer.

    k_new/v_new ``[B, H, 1, hd]``; logical slot ``offset`` (uniform
    traced scalar — the engines decode at uniform depth) of row ``b``
    lands in block ``tables[b, offset // bs]`` at slot ``offset % bs``.
    The paged sibling of ``ops.attention.write_kv_layer``.
    """
    b = k_new.shape[0]
    bs = pool.shape[4]
    blk_col = offset // bs
    slot = offset % bs
    zero = jnp.zeros((), jnp.int32)
    rows = jnp.stack([k_new[:, :, 0], v_new[:, :, 0]], axis=1)  # [B, 2, H, hd]
    for bi in range(b):
        # [1, 1, 2, H, 1, hd]: the pool-shaped update for one (layer,
        # block, slot) cell of one row
        piece = rows[bi][None, None, :, :, None].astype(pool.dtype)
        pool = jax.lax.dynamic_update_slice(
            pool, piece,
            (layer_idx, tables[bi, blk_col], zero, zero, slot, zero))
    return pool


def _paged_decode_attention_impl(q: jnp.ndarray, k_new: jnp.ndarray,
                                 v_new: jnp.ndarray, pool: jnp.ndarray,
                                 tables: jnp.ndarray, layer_idx, offset,
                                 k_valid_from: Optional[jnp.ndarray] = None,
                                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token cached attention straight off the pool: write the
    new column into its block, gather the layer's logical rows, attend.

    q ``[B, H, 1, hd]``; k_new/v_new ``[B, Hkv, 1, hd]``; returns
    ``(out [B, H, 1, hd], pool)``. Byte-equal to
    ``ops.attention.cached_attention_inplace`` on the contiguous cache
    — same masked score set, same contraction; the only difference is
    where the bytes live (pinned by tests/test_paged_attention.py).
    """
    pool = write_token_kv(pool, k_new, v_new, tables, layer_idx, offset)
    layer = jax.lax.dynamic_index_in_dim(pool, layer_idx, axis=0,
                                         keepdims=False)
    k, v = gather_kv(layer[None], tables)
    out = causal_attention(q, k[0], v[0], q_offset=offset,
                           kv_length=offset + 1, k_valid_from=k_valid_from)
    return out, pool


# The jitted per-token entry point (tables/indices traced: ONE program
# per shape set). No donation: callers that loop it (tests, a future
# model hook) manage their own pool rebinding; runtime.kv_pool's
# segment-path jits donate theirs.
paged_decode_attention = jax.jit(_paged_decode_attention_impl)
