"""Top-k routing (sigmoid or softmax scores) and a dropless grouped matmul
over held experts.

An expert layer here is TOLD which experts it holds: ``E`` consecutive
ids starting at ``first`` out of the router's ``n_total``. It routes
every token over all ``n_total``, computes the terms of the experts it
holds and leaves the others out, as one chip of an expert-parallel
deployment does (its exchange with the other chips is not run here and
nothing stands in for it).

Routing (``route``): scores ``s = sigmoid(x W_g)`` in float32; the ``k``
experts of a token are the top ``k`` of ``s + b`` (``b``: a selection
bias that moves the choice and not the weight); weights are
``s[chosen]``, normalised over the chosen and scaled. No capacity, no
dropped token: a token's choice depends on that token alone.
``route_softmax`` is the other published scoring: ``p = softmax(x W_g)``
over all ``n_total``, the ``k`` largest, weights ``p[chosen]``
normalised over the chosen; the same shorter form for a few tokens.

The grouped matmul (``held_experts_ffn``): the (token, choice) pairs
that landed on held experts are sorted by expert and cut into tiles of
``TILE`` rows, each tile of one expert; one loop runs over the tiles
that exist (a dynamic trip count), so an expert nobody chose is never
read and an expert ten tokens chose costs one tile. A tile gathers its
rows and adds its weighted outputs back by one-hot matmuls (exact: one
non-zero term a row). Tiles run in ascending expert order, so a token's
terms are summed in the same order whatever shares its batch, and every
tile is the same ``[TILE, d]`` shape: a token's result does not depend
on its batch mates (pinned bit-equal by tests/test_latent_moe.py).

A call of at most ``TILE`` pairs (a decode step: ``B x k``) takes the
same two functions through shorter forms: the top k by rank, without a
sort (``_top_k_by_rank``), and one tile an expert whose rows are all the
tokens, without sorting, gathering or scattering pairs
(``_one_tile_an_expert``). The same choice, the same weights, the same
``[TILE, d]`` tiles over the experts that were hit, in the same order.

The tiles themselves run in one of two ways, by what the engine resolved
for its decode kernels (``runtime.engine``: ``"device"`` on a TPU
outside float32, ``"interpret"`` for CPU tests, ``None`` under a mesh,
in the float32 regime and on a CPU) and handed down as ``kernel``:

- ``None``: the XLA loop, a trip a tile, which slices the tile's three
  matrices out of the stacks and multiplies by them as three fusions. A
  trip cannot start reading the next tile's matrices while it
  multiplies, and every fusion pays its own start: 20 us an expert of
  9.4 MB where its bytes take 11.5 (PERF.md 6, PR 51).
- otherwise ONE Pallas kernel, ``held_expert_tiles`` (``KERNEL_NAME``).
  Its scalar operands are the layer index and the hit list (the expert
  of each tile, in the order the tiles run); its grid is (tiles, chunks
  of ``f``), the first bound DYNAMIC: the number of tiles that exist, so
  a layer that hit one expert runs one step and a layer that hit none
  runs none. The weights stay the whole ``[L, E, d, f]`` / ``[L, E, f,
  d]`` stacks in HBM; the blocks' index maps pick ``(layer, expert,
  chunk)``, so the compiler's pipeline has the next step's blocks in
  flight while the present step multiplies and fetches nothing again
  that the step before had. Operands go onto the MXU in the carried
  dtype, products are summed in float32, the gate's SiLU and the
  product with ``up`` are float32, and the chunks' partial sums of the
  down-projection add in float32 in ascending chunk order: no lower
  than the loop's (which rounds each product to the carried dtype).
  Tile shape, tile order and chunk order follow the shapes and the hit
  list alone, so a token's result is still bit-equal whatever shares
  its batch. (A dynamic grid bound and one grid step with hand-made
  double-buffered copies were both measured and read within 1% of each
  other at every shape and hit count; the grid is a third of the code.)

How the kernel is fed, from the shapes alone:

- every call whose TOKENS fit a tile (``T <= TILE``; under the loop:
  whose pairs do) takes one tile an expert: all the tokens' rows once
  in VMEM, a step a chunk of ``chunk_of`` columns of ``f`` (about 4 MB
  of the three matrices: the first step's copy is the one nothing
  hides, so short steps; 256 columns at ``d`` 2048-2304, 128 at 6144),
  the result of expert ``e`` in slot ``e`` of ``[E, rows, d]`` float32;
  the weights and the float32 sum in ascending expert order stay XLA's;
- a longer call (a prefill, a stride) takes the general form where an
  expert's matrices fit the kernel's double buffer WHOLE
  (``fetched_whole``: 9.4, 6.3 and 14.2 MB do; consecutive tiles of one
  expert then share one fetch): its tiles are gathered ``GROUP`` at a
  time by the same one-hot matmuls, the kernel runs the group, and the
  same scatter adds them back tile by tile. Where they do not fit (75.5
  MB at ``d`` 6144, ``f`` 2048) each tile would stream its expert again
  in chunks, which read slower than the loop's fusions on the chip: the
  loop stays for those calls.
"""

from __future__ import annotations

import functools
import operator
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Numerics contract (tools/graftcheck numerics pass): routing runs in
# float32 whatever the regime (near-tied scores decide which experts a
# token sees); the expert matmuls carry the activation dtype and the
# weighted terms are summed in float32.
PRECISION_CONTRACT = {
    "route": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "route_softmax": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "held_experts_ffn": {"regime": "carried", "exact": True,
                         "casts": ("f32", "carried")},
    # the kernel: carried operands, float32 sums, a float32 result
    "held_expert_tiles": {"regime": "f32", "exact": True,
                          "accumulate": "f32", "casts": ("f32", "carried")},
}

TILE = 128
GROUP = 16             # tiles a call of the kernel takes, gathered
LANES = 128
KERNEL_NAME = "held_expert_tiles"
# of the kernel's 100 MiB of VMEM, what the double buffer of the three
# matrices may take; the rest is the tiles' rows, results and products
_WEIGHT_VMEM = 48 * 1024 * 1024
# what a step of the one-tile-an-expert form fetches: the first step's
# copy is the one nothing hides, so short steps; at 1.6-4.7 MB a step
# the stream still reads at 755 GB/s (PERF.md 6, PR 51)
_STEP_BYTES = 4 * 1024 * 1024
_HI = jax.lax.Precision.HIGHEST
# the kernel's products: the carried dtype straight onto the MXU, summed
# in float32, whatever ``jax_default_matmul_precision`` says (Mosaic
# refuses "highest" on bfloat16 operands)
_MXU = jax.lax.Precision.DEFAULT


def route(x: jnp.ndarray, wg: jnp.ndarray, bias: jnp.ndarray, top_k: int,
          scale: float, normalise: bool = True,
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [T, d], wg [d, n_total], bias [n_total] -> (ids [T, k] int32,
    weights [T, k] float32)."""
    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32),
                                  wg.astype(jnp.float32), precision=_HI))
    biased = s + bias.astype(jnp.float32)
    if x.shape[0] * top_k <= TILE:
        ids, w = _top_k_by_rank(biased, s, top_k)
    else:
        _, ids = jax.lax.top_k(biased, top_k)
        w = jnp.take_along_axis(s, ids, axis=-1)
    if normalise:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w * scale


def route_softmax(x: jnp.ndarray, wg: jnp.ndarray, top_k: int,
                  normalise: bool = True,
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [T, d], wg [d, n_total] -> (ids [T, k] int32, weights [T, k]
    float32): the ``k`` largest of ``softmax(x wg)`` and their
    probabilities, normalised over the chosen."""
    p = jax.nn.softmax(jnp.matmul(x.astype(jnp.float32),
                                  wg.astype(jnp.float32), precision=_HI),
                       axis=-1)
    if x.shape[0] * top_k <= TILE:
        ids, w = _top_k_by_rank(p, p, top_k)
    else:
        w, ids = jax.lax.top_k(p, top_k)
    if normalise:
        w = w / w.sum(-1, keepdims=True)
    return ids.astype(jnp.int32), w


def _top_k_by_rank(keys: jnp.ndarray, values: jnp.ndarray, k: int):
    """``lax.top_k``'s choice over ``keys`` [T, n] (descending, ties to
    the lower id) without sorting, for a few tokens: an entry's rank is
    how many entries come before it, all ``n x n`` comparisons at once,
    and slot ``r`` of a token takes the entry of rank ``r``. Returns
    ``(ids [T, k] int32, values[ids] [T, k])``, each a sum with one
    non-zero term. The chip sorts all ``n`` for a ``top_k``, 3 us a
    layer at ``n`` = 256 where this takes a fraction of one."""
    n = keys.shape[-1]
    idx = jnp.arange(n, dtype=jnp.int32)
    mine, other = keys[:, :, None], keys[:, None, :]
    before = (other > mine) | ((other == mine) & (idx[None, :] < idx[:, None]))
    rank = jnp.sum(before, axis=-1, dtype=jnp.int32)             # [T, n]
    slot = rank[:, None, :] == jnp.arange(k, dtype=jnp.int32)[:, None]
    return (jnp.sum(jnp.where(slot, idx, 0), axis=-1),
            jnp.sum(jnp.where(slot, values[:, None, :], 0.0), axis=-1))


def held_experts_ffn(x: jnp.ndarray, ids: jnp.ndarray, w: jnp.ndarray,
                     gate: jnp.ndarray, up: jnp.ndarray, down: jnp.ndarray,
                     layer_idx, first: int, kernel: Optional[str] = None,
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of ``sum_e w_e SwiGLU_e(x)``.

    x [T, d]; ids/w [T, k] from ``route``; gate/up ``[L, E, d, f]`` and
    down ``[L, E, f, d]``: the WHOLE stacks, indexed by ``(layer_idx,
    expert)`` inside the loop (or the kernel) so that only the experts
    that were chosen are read. ``kernel`` is what the engine resolved
    (``"device"``, ``"interpret"`` or ``None``: module docstring).
    Returns ``(y [T, d] in x's dtype, counts [E] int32)``: the pairs
    each held expert received."""
    t, k = ids.shape
    # the kernel's tiles cost what their matrices do, so under it one
    # tile an expert serves every call whose TOKENS fit a tile
    if t * k <= TILE or (kernel is not None and t <= TILE):
        return _one_tile_an_expert(x, ids, w, gate, up, down, layer_idx,
                                   first, kernel)
    n_held, d = gate.shape[1], x.shape[1]
    local = ids.reshape(-1) - first
    # pairs for experts held elsewhere sort behind every held one
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.zeros((n_held + 1,), jnp.int32).at[key].add(1)[:n_held]
    starts = jnp.cumsum(counts) - counts
    tiles = -(-counts // TILE)
    tile_ends = jnp.cumsum(tiles)
    flat_w = w.reshape(-1)
    tokens = jnp.arange(t, dtype=jnp.int32)

    def cut(i):
        """Tile ``i``: its expert, its rows' one-hot picks of the tokens
        ``[TILE, T]`` and their weights (0 past the expert's pairs)."""
        e = jnp.sum(tile_ends <= i).astype(jnp.int32)
        row0 = starts[e] + (i - (tile_ends[e] - tiles[e])) * TILE
        rows = row0 + jnp.arange(TILE, dtype=jnp.int32)
        valid = rows < starts[e] + counts[e]
        pair = order[jnp.minimum(rows, t * k - 1)]
        pick = ((pair // k)[:, None] == tokens[None, :]) & valid[:, None]
        return e, pick.astype(x.dtype), jnp.where(valid, flat_w[pair], 0.0)

    def add_back(acc, pick, y, weights):
        return acc + jnp.matmul(pick.astype(jnp.float32).T,
                                y * weights[:, None], precision=_HI)

    def one_tile(i, acc):
        e, pick, weights = cut(i)
        xs = jnp.matmul(pick, x, precision=_HI)
        return add_back(acc, pick, _expert_swiglu(xs, gate, up, down,
                                                  layer_idx, e), weights)

    acc = jnp.zeros((t, d), jnp.float32)
    if kernel is None or not fetched_whole(d, gate.shape[3],
                                           gate.dtype.itemsize):
        acc = jax.lax.fori_loop(0, tile_ends[-1], one_tile, acc)
        return acc.astype(x.dtype), counts

    # the kernel takes its tiles gathered, a GROUP at a call (a token
    # chooses an expert once: no expert has more than ceil(T / TILE))
    most = min(n_held * -(-t // TILE), t * k // TILE + n_held)
    group = min(GROUP, most)
    slots = jnp.arange(most + group, dtype=jnp.int32)
    # a slot past the last tile runs nothing; it names a held expert
    tile_expert = jnp.minimum(n_held - 1, jnp.sum(
        tile_ends[None, :] <= slots[:, None], axis=1, dtype=jnp.int32))

    def one_group(g, acc):
        base = g * group
        n = jnp.minimum(group, tile_ends[-1] - base)

        def gather(j, xs):
            _, pick, _ = cut(base + j)
            return jax.lax.dynamic_update_index_in_dim(
                xs, jnp.matmul(pick, x, precision=_HI), j, 0)

        xs = jax.lax.fori_loop(0, n, gather,
                               jnp.zeros((group, TILE, d), x.dtype))
        ys = held_expert_tiles(
            xs, jax.lax.dynamic_slice(tile_expert, (base,), (group,)), n,
            gate, up, down, layer_idx, interpret=kernel == "interpret")

        def scatter(j, acc):
            _, pick, weights = cut(base + j)
            y = jax.lax.dynamic_index_in_dim(ys, j, 0, keepdims=False)
            return add_back(acc, pick, y, weights)

        return jax.lax.fori_loop(0, n, scatter, acc)

    if most <= group:
        acc = one_group(0, acc)
    else:
        acc = jax.lax.fori_loop(0, -(-tile_ends[-1] // group), one_group, acc)
    return acc.astype(x.dtype), counts


def _unsigned(*index):
    """Start indices that cannot be negative, so that a slice does not
    spend three scalar operations on wrapping them."""
    return tuple(jnp.asarray(i).astype(jnp.uint32) for i in index)


def _expert_swiglu(xs, gate, up, down, layer_idx, e):
    """``SwiGLU_e(xs)`` in float32, the three matrices sliced out of the
    whole stacks at ``(layer_idx, e)``."""
    index = _unsigned(layer_idx, e, 0, 0)
    wg_ = jax.lax.dynamic_slice(gate, index, (1, 1) + gate.shape[2:])
    wu_ = jax.lax.dynamic_slice(up, index, (1, 1) + up.shape[2:])
    wd_ = jax.lax.dynamic_slice(down, index, (1, 1) + down.shape[2:])
    h = jax.nn.silu(xs @ wg_[0, 0]) * (xs @ wu_[0, 0])
    return (h @ wd_[0, 0]).astype(jnp.float32)


def _one_tile_an_expert(x, ids, w, gate, up, down, layer_idx, first,
                        kernel=None):
    """``held_experts_ffn`` for at most ``TILE`` pairs (a decode step:
    ``B x k``). A token chooses an expert at most once, so every held
    expert has one tile at most and its rows can be ALL the tokens, in
    their own order, each weighed by what it gave that expert (zero if
    it did not choose it): nothing is sorted, gathered or scattered.
    The tile is the same ``[TILE, d]`` as the general form's, the loop
    (or the kernel) still runs over the experts that were hit alone, in
    ascending order, and the terms are summed in float32 in that
    order."""
    t, k = ids.shape
    n_held = gate.shape[1]
    experts = jnp.arange(n_held, dtype=jnp.int32)
    chose = (ids - first)[:, :, None] == experts             # [T, k, E]
    counts = jnp.sum(chose, axis=(0, 1), dtype=jnp.int32)
    # one non-zero term a (token, expert): exact
    share = jnp.sum(jnp.where(chose, w[:, :, None], 0.0), axis=1).T  # [E, T]
    xs = jnp.pad(x, ((0, TILE - t), (0, 0)))

    if kernel is not None:
        # the experts that were hit, ascending, in the first slots: slot
        # ``i`` takes the hit expert that has ``i`` hit ones before it
        hit = counts > 0
        before = jnp.sum(hit[None, :] & (experts[None, :] < experts[:, None]),
                         axis=1, dtype=jnp.int32)
        hit_list = jnp.sum(
            jnp.where(hit[None, :] & (before[None, :] == experts[:, None]),
                      experts[None, :], 0), axis=1, dtype=jnp.int32)
        ys = held_expert_tiles(
            xs[None], hit_list, jnp.sum(hit, dtype=jnp.int32), gate, up,
            down, layer_idx, rows=t, interpret=kernel == "interpret")
        # an expert nobody chose left its slot as it was found
        terms = (jnp.where(hit[:, None, None], ys[:, :t], 0.0)
                 * share[:, :, None])
        acc = functools.reduce(operator.add, list(terms))
        return acc.astype(x.dtype), counts

    def hit_after(e):
        return jnp.min(jnp.where((counts > 0) & (experts > e), experts,
                                 n_held))

    def one_expert(carry):
        e, acc = carry
        y = _expert_swiglu(xs, gate, up, down, layer_idx, e)[:t]
        mine = jax.lax.dynamic_slice(share, _unsigned(e, 0), (1, t))[0]
        return hit_after(e), acc + y * mine[:, None]

    _, acc = jax.lax.while_loop(
        lambda carry: carry[0] < n_held, one_expert,
        (hit_after(-1), jnp.zeros(x.shape, jnp.float32)))
    return acc.astype(x.dtype), counts


# -- the tiles as one kernel ---------------------------------------------------


def fetched_whole(d: int, f: int, itemsize: int) -> bool:
    """Whether two experts' matrices fit ``_WEIGHT_VMEM`` (one
    multiplying, the next in flight): the general form takes the kernel
    where they do, since an expert's tiles then share ONE fetch. Where
    they do not (75 MB an expert at ``d`` 6144, ``f`` 2048) every tile
    streams its expert again in chunks, which read 5-11% SLOWER than
    the loop's three fusions on the chip (4.7-5.0 ms against 4.5 a
    layer of 32 tiles; PERF.md 6, PR 51): the loop stays for those."""
    return 2 * 3 * d * f * itemsize <= _WEIGHT_VMEM


def chunk_of(d: int, f: int, itemsize: int) -> int:
    """Columns of ``f`` a grid step of the one-tile-an-expert form
    carries: the most whole lanes that divide ``f`` and keep a step's
    three blocks within ``_STEP_BYTES`` (at least ``LANES``; all of
    ``f`` where it is not whole lanes). Read off the shapes and the
    dtype alone."""
    if f % LANES:
        return f
    fits = [q * LANES for q in range(1, f // LANES + 1)
            if f % (q * LANES) == 0
            and 3 * d * q * LANES * itemsize <= _STEP_BYTES]
    return max(fits, default=LANES)


def _tile_kernel(layer_ref, expert_ref, x_ref, gate_ref, up_ref, down_ref,
                 y_ref):
    """One grid step: a tile's rows against one chunk of its expert's
    three matrices. The chunks' partial sums of the down-projection add
    in float32 in ascending chunk order."""
    def mxu(a, b):
        return jnp.dot(a, b, precision=_MXU,
                       preferred_element_type=jnp.float32)

    xs = x_ref[0]
    h = (jax.nn.silu(mxu(xs, gate_ref[0, 0]))
         * mxu(xs, up_ref[0, 0])).astype(xs.dtype)
    part = mxu(h, down_ref[0, 0])[:y_ref.shape[1]]
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _():
        y_ref[0] = part

    @pl.when(chunk > 0)
    def _():
        y_ref[0] += part


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def held_expert_tiles(xs: jnp.ndarray, tile_expert: jnp.ndarray, n_tiles,
                      gate: jnp.ndarray, up: jnp.ndarray, down: jnp.ndarray,
                      layer_idx, *, rows: int = TILE,
                      interpret: bool = False) -> jnp.ndarray:
    """``SwiGLU_e`` of the first ``n_tiles`` tiles, ``e = tile_expert[i]``
    (held experts, ascending), as ONE kernel whose grid is ``n_tiles``
    (a dynamic bound: no tile, no step) by the chunks of ``f``: the
    pipeline has the next step's chunk of the three matrices in flight
    while the present one multiplies, and does not fetch again what the
    step before had (consecutive tiles of one expert whose matrices go
    whole).

    ``xs`` ``[G, TILE, d]``: tile ``i``'s rows; the result is ``[G,
    TILE, d]`` float32, slot ``i`` tile ``i``'s. Or ``[1, TILE, d]``:
    the rows of EVERY tile (one tile an expert); the result is ``[E,
    rows8, d]``, slot ``e`` the first ``rows`` rows (rounded up to 8) of
    expert ``e``'s tile. A slot no tile wrote holds whatever was there.
    gate/up/down: the whole stacks, as ``held_experts_ffn`` takes them,
    indexed by ``(layer_idx, e)`` in the copies' index maps."""
    _, _, d, f = gate.shape
    shared = xs.shape[0] == 1
    slots = gate.shape[1] if shared else xs.shape[0]
    rows = -(-rows // 8) * 8
    fc = chunk_of(d, f, gate.dtype.itemsize) if shared else f

    def tile(i, c, layer, expert):
        return (0 if shared else i, 0, 0)

    def slot(i, c, layer, expert):
        return (expert[i] if shared else i, 0, 0)

    def columns(i, c, layer, expert):
        return (layer[0], expert[i], 0, c)

    def rows_of_down(i, c, layer, expert):
        return (layer[0], expert[i], c, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # the layer, the tiles' experts
        grid=(n_tiles, f // fc),
        in_specs=[pl.BlockSpec((1, TILE, d), tile),
                  pl.BlockSpec((1, 1, d, fc), columns),
                  pl.BlockSpec((1, 1, d, fc), columns),
                  pl.BlockSpec((1, 1, fc, d), rows_of_down)],
        out_specs=pl.BlockSpec((1, rows, d), slot),
    )
    return pl.pallas_call(
        _tile_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # a tile's chunks revisit its result; tiles run in order
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name=KERNEL_NAME,
    )(jnp.asarray(layer_idx, jnp.int32).reshape(1),
      tile_expert.astype(jnp.int32), xs, gate, up, down)
