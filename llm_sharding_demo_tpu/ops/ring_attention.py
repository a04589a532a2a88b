"""Ring attention: causal self-attention over a sequence-sharded mesh axis.

Long-context support the reference structurally cannot have (it re-sends
the FULL growing sequence per token as JSON and is capped at GPT-2's 1024
learned positions — reference server.py:169-181, SURVEY.md §5
"Long-context": ABSENT). Here the sequence dimension is sharded across the
``sp`` mesh axis and attention runs blockwise:

- each device holds its local Q/K/V chunk; K/V chunks rotate around the
  ICI ring via ``lax.ppermute``, one hop per step, so every Q chunk sees
  every K/V chunk after ``sp`` steps without any device ever holding the
  full sequence — memory per device is O(S/sp), communication overlaps
  with the chunk's attention compute;
- numerically it is *online softmax* (the flash-attention recurrence):
  running max ``m``, normalizer ``l``, and un-normalized accumulator,
  renormalized as blocks arrive, all in float32 — bit-for-bit-tolerance
  identical to monolithic softmax attention;
- causality is enforced by *global* position masks computed from the ring
  step, so the same kernel covers diagonal (self) blocks, fully-visible
  past blocks, and fully-masked future blocks (the latter still cost a
  matmul — skipping them is a scheduling optimization, not a correctness
  need).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


# Placement contract (tools/graftcheck placement pass + utils/
# graftshard): Q/K/V enter and leave with the sequence dim sharded over
# ``sp``; the traced kernel must establish exactly that placement (the
# K/V ring rotation's ppermutes run over sp and nothing else).
PLACEMENT_CONTRACT = {
    "mesh_axes": ("sp",),
    "entry:ring_attention": "sp",
}

NEG_INF = -1e9


def _block_attend(q, k, v, q_pos, k_pos):
    """One Q-chunk × K/V-chunk partial attention, flash-style.

    q: [B, H, Sq, hd]; k/v: [B, H, Skv, hd]; q_pos/k_pos: global positions.
    Returns (un-normalized out [B,H,Sq,hd] fp32, row max m [B,H,Sq],
    row sum l [B,H,Sq]) for the online-softmax merge.
    """
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = (k_pos[None, :] <= q_pos[:, None])  # causal on global positions
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                           # [B,H,Sq]
    # rows with no visible keys: exp(NEG_INF - NEG_INF) would be 1 and
    # pollute l; clamp m to 0 there so exp(scores - 0) ~ 0.
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)                                # [B,H,Sq]
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out, m_safe, l


def _merge(acc, m, l, out_b, m_b, l_b):
    """Merge a new block into the running (acc, m, l) online-softmax state."""
    m_new = jnp.maximum(m, m_b)
    alpha = jnp.exp(m - m_new)      # rescale old accumulator
    beta = jnp.exp(m_b - m_new)     # rescale new block
    l_new = l * alpha + l_b * beta
    acc_new = acc * alpha[..., None] + out_b * beta[..., None]
    return acc_new, m_new, l_new


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh: Mesh, axis: str = "sp") -> jnp.ndarray:
    """Causal attention with Q/K/V sequence-sharded over ``axis``.

    q/k/v: [B, H, S, hd] *global* shapes, S divisible by the axis size;
    activations enter/leave with the S dim sharded over ``axis``. Returns
    [B, H, S, hd] in q's dtype.
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
    n = mesh.shape[axis]
    if q.shape[2] % n:
        raise ValueError(f"seq {q.shape[2]} not divisible by {axis}={n}")
    chunk = q.shape[2] // n

    def per_device(q_loc, k_loc, v_loc):
        # local views: [B, H, chunk, hd]
        idx = jax.lax.axis_index(axis)
        q_pos = idx * chunk + jnp.arange(chunk)

        # accumulators start as constants (axis-invariant) but the scan
        # carry becomes axis-varying after the first merge — cast up front
        # so the carry signature is stable; k/v enter already varying
        def vary(x):
            return jax.lax.pcast(x, axis, to="varying")

        init = (vary(jnp.zeros(q_loc.shape, jnp.float32)),
                vary(jnp.full(q_loc.shape[:3], NEG_INF, jnp.float32)),
                vary(jnp.zeros(q_loc.shape[:3], jnp.float32)),
                k_loc, v_loc)

        def step(carry, s):
            acc, m, l, k_blk, v_blk = carry
            # the K/V block on this device at ring step s started life on
            # device (idx - s) mod n
            src = jax.lax.rem(idx - s + n, n)
            k_pos = src * chunk + jnp.arange(chunk)
            out_b, m_b, l_b = _block_attend(q_loc, k_blk, v_blk, q_pos, k_pos)
            acc, m, l = _merge(acc, m, l, out_b, m_b, l_b)
            # rotate K/V forward around the ring (device i -> i+1)
            perm = [(i, (i + 1) % n) for i in range(n)]
            k_blk = jax.lax.ppermute(k_blk, axis, perm)
            v_blk = jax.lax.ppermute(v_blk, axis, perm)
            return (acc, m, l, k_blk, v_blk), None

        (acc, m, l, _, _), _ = jax.lax.scan(step, init, jnp.arange(n))
        # l==0 only for rows with no visible keys (impossible for causal
        # self-attention: position i always sees itself) — still, avoid /0
        l = jnp.maximum(l, 1e-20)
        return (acc / l[..., None]).astype(q_loc.dtype)

    spec = P(None, None, axis, None)
    return jax.shard_map(per_device, mesh=mesh,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         axis_names={axis})(q, k, v)
