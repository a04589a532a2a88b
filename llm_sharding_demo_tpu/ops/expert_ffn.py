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
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# Numerics contract (tools/graftcheck numerics pass): routing runs in
# float32 whatever the regime (near-tied scores decide which experts a
# token sees); the expert matmuls carry the activation dtype and the
# weighted terms are summed in float32.
PRECISION_CONTRACT = {
    "route": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "route_softmax": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "held_experts_ffn": {"regime": "carried", "exact": True,
                         "casts": ("f32", "carried")},
}

TILE = 128
_HI = jax.lax.Precision.HIGHEST


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
                     layer_idx, first: int,
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of ``sum_e w_e SwiGLU_e(x)``.

    x [T, d]; ids/w [T, k] from ``route``; gate/up ``[L, E, d, f]`` and
    down ``[L, E, f, d]``: the WHOLE stacks, indexed by ``(layer_idx,
    expert)`` inside the loop so that only the experts that were chosen
    are read. Returns ``(y [T, d] in x's dtype, counts [E] int32)``:
    the pairs each held expert received."""
    t, k = ids.shape
    if t * k <= TILE:
        return _one_tile_an_expert(x, ids, w, gate, up, down, layer_idx,
                                   first)
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

    def one_tile(i, acc):
        e = jnp.sum(tile_ends <= i).astype(jnp.int32)
        row0 = starts[e] + (i - (tile_ends[e] - tiles[e])) * TILE
        rows = row0 + jnp.arange(TILE, dtype=jnp.int32)
        valid = rows < starts[e] + counts[e]
        pair = order[jnp.minimum(rows, t * k - 1)]
        pick = ((pair // k)[:, None] == tokens[None, :]) & valid[:, None]
        pick = pick.astype(x.dtype)                           # [TILE, T]
        xs = jnp.matmul(pick, x, precision=_HI)
        y = _expert_swiglu(xs, gate, up, down, layer_idx, e)
        y = y * jnp.where(valid, flat_w[pair], 0.0)[:, None]
        return acc + jnp.matmul(pick.astype(jnp.float32).T, y,
                                precision=_HI)

    acc = jax.lax.fori_loop(0, tile_ends[-1], one_tile,
                            jnp.zeros((t, d), jnp.float32))
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


def _one_tile_an_expert(x, ids, w, gate, up, down, layer_idx, first):
    """``held_experts_ffn`` for at most ``TILE`` pairs (a decode step:
    ``B x k``). A token chooses an expert at most once, so every held
    expert has one tile at most and its rows can be ALL the tokens, in
    their own order, each weighed by what it gave that expert (zero if
    it did not choose it): nothing is sorted, gathered or scattered.
    The tile is the same ``[TILE, d]`` as the general form's, the loop
    still runs over the experts that were hit alone, in ascending order,
    and the terms are summed in float32."""
    t, k = ids.shape
    n_held = gate.shape[1]
    experts = jnp.arange(n_held, dtype=jnp.int32)
    chose = (ids - first)[:, :, None] == experts             # [T, k, E]
    counts = jnp.sum(chose, axis=(0, 1), dtype=jnp.int32)
    # one non-zero term a (token, expert): exact
    share = jnp.sum(jnp.where(chose, w[:, :, None], 0.0), axis=1).T  # [E, T]
    xs = jnp.pad(x, ((0, TILE - t), (0, 0)))

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
