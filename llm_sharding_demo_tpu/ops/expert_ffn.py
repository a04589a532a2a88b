"""Sigmoid top-k routing and a dropless grouped matmul over held experts.

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
    _, ids = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if normalise:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w * scale


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
        index = (layer_idx, e, 0, 0)
        wg_ = jax.lax.dynamic_slice(gate, index, (1, 1) + gate.shape[2:])
        wu_ = jax.lax.dynamic_slice(up, index, (1, 1) + up.shape[2:])
        wd_ = jax.lax.dynamic_slice(down, index, (1, 1) + down.shape[2:])
        h = jax.nn.silu(xs @ wg_[0, 0]) * (xs @ wu_[0, 0])
        y = (h @ wd_[0, 0]).astype(jnp.float32)
        y = y * jnp.where(valid, flat_w[pair], 0.0)[:, None]
        return acc + jnp.matmul(pick.astype(jnp.float32).T, y,
                                precision=_HI)

    acc = jax.lax.fori_loop(0, tile_ends[-1], one_tile,
                            jnp.zeros((t, d), jnp.float32))
    return acc.astype(x.dtype), counts
