"""The delta rule gated per key channel (Kimi Delta Attention).

``ops.gated_delta``'s rule with the decay a VECTOR over the key
dimensions of a head where that one's is a number a head. One position
does, for every head's float32 state ``S`` (``K`` by ``V``),

    S <- Diag(exp(g)) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q

with ``g <= 0`` in ``R^K`` (row ``c`` of ``S`` decays by ``exp(g_c)``),
``0 < beta < 1``, ``q`` and ``k`` unit length (``q`` also over
``sqrt(K)``). The state belongs to the row, the convolution in front of
the rule carries its tail, and masked positions get ``beta = 0`` and
``g = 0``, all as there; ``l2norm``, ``gates``, ``causal_conv``, the
triangular solve, the cut into chunks and the kernel's call are that
module's. Three forms of the same sums:

- ``recurrence``: the line above, position by position: the definition;
- ``chunked``: a call of several positions in chunks of ``CHUNK`` from
  the call's first. With ``G`` the running sum of ``g`` inside a chunk
  (a vector a position, falling): ``A_ij = sum_c k_ic k_jc e^{G_ic -
  G_jc}`` for ``i > j``, ``L = beta A``, ``T = (I + L)^-1``, ``[W | U]
  = T [beta (K e^G) | beta V]``; with the incoming ``S0``: ``V' = U - W
  S0``, ``O = (Q e^G) S0 + B V'`` where ``B_ij = sum_c q_ic k_jc
  e^{G_ic - G_jc}`` for ``i >= j``, ``S1 = Diag(e^{G_C}) S0 + (K
  e^{G_C - G})^T V'``. The decay sits INSIDE the sums of ``A`` and
  ``B``, so they are no matmul times a mask; the factoring ``(k_i
  e^{G_i}) . (k_j e^{-G_j})`` is one, and forms ``e^{-G}``: at a decay
  of 0.05 a position that is ``e^{190}`` inside one chunk, past
  float32. Here EVERY EXPONENT FORMED IS <= 0: a chunk is cut into
  sub-blocks of ``SUB`` positions; between a later sub-block and the
  positions before it the decay goes through the later one's FIRST
  position ``n``, ``e^{G_i - G_n} e^{G_n - G_j}`` with ``j < n <= i``
  (one matmul a sub-block; where the second factor underflows the
  product was smaller still), and inside a sub-block the ``[SUB, SUB,
  K]`` differences ``G_i - G_j``, ``i >= j``, are formed directly. A
  call that starts at a multiple of ``CHUNK`` computes the same sums
  whether a prompt is walked in one call or several;
- ``step_kernel``: one position as a Pallas kernel that streams a row's
  state through VMEM once, a block of heads at a time, the decay stood
  up as a column along the key axis beside ``q`` and ``k``: 2 x ``H K
  V`` x 4 bytes a row a layer and nothing else of size; for the rows
  that hold a request alone (``gated_delta``'s grid over the live
  lanes): another lane's state is left as it came in, its output zeros.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import gated_delta
from .gated_delta import CHUNK, in_chunks, stood_up, unit_lower_solve

# Numerics contract (tools/graftcheck numerics pass): float32 state and
# sums at full matmul precision whatever the regime, as the scalar rule.
PRECISION_CONTRACT = {
    "recurrence": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "chunked": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "step_kernel": {"regime": "f32", "exact": True, "casts": ("f32",)},
}

SUB = 16               # positions of a sub-block of a chunk
KERNEL_NAME = "kda_state_update"
_HI = jax.lax.Precision.HIGHEST


def recurrence(q, k, v, g, beta, state):
    """The rule position by position. ``q``, ``k``, ``g`` [B, H, T, K]
    (``q``, ``k`` already normalised), ``v`` [B, H, T, V], ``beta``
    [B, H, T], ``state`` [B, H, K, V]; all float32. Returns ``(o
    [B, H, T, V], state)``."""
    def one(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None]
        kv = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=_HI)
        d = b_t[..., None] * (v_t - kv)
        s = s + k_t[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=_HI)

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    state, o = jax.lax.scan(one, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 2), state


def _decayed_products(rows, k, big, sub: int):
    """``sum_c rows_ic k_jc e^{G_ic - G_jc}`` for ``i >= j`` (0 above
    the diagonal) with no exponent above 0 (module docstring). ``rows``
    [R, ..., C, K] (several left operands at once), ``k``, ``big``
    [..., C, K]. Returns [R, ..., C, C]."""
    c, dk = k.shape[-2:]
    m = c // sub

    def blocks(x):                     # [..., C, K] -> [..., m, sub, K]
        return x.reshape(x.shape[:-2] + (m, sub, dk))

    gb = blocks(big)
    first = gb[..., :1, :]             # G at a sub-block's first position
    # a later sub-block against every position before its first
    before = (jnp.arange(c)[None, :] < (jnp.arange(m) * sub)[:, None])
    upto = jnp.exp(jnp.where(before[..., None],
                             first - big[..., None, :, :], -jnp.inf))
    off = jnp.einsum("r...msk,...mjk->r...msj",
                     blocks(rows) * jnp.exp(gb - first),
                     k[..., None, :, :] * upto, precision=_HI)
    # inside a sub-block, the differences themselves
    i = jnp.arange(sub)
    within = jnp.exp(jnp.where((i[:, None] >= i[None, :])[..., None],
                               gb[..., :, None, :] - gb[..., None, :, :],
                               -jnp.inf))                # [..., m, s, s, K]
    diag = jnp.sum(blocks(rows)[..., :, None, :]
                   * (blocks(k)[..., None, :, :] * within), axis=-1)
    own = jnp.eye(m, dtype=diag.dtype)[:, None, :, None]
    full = off.reshape(off.shape[:-1] + (m, sub)) + diag[..., None, :] * own
    return full.reshape(full.shape[:-4] + (c, c))


def chunked(q, k, v, g, beta, state, chunk: int = CHUNK, sub: int = SUB):
    """The rule in chunks of ``chunk`` positions from the call's first
    (module docstring). Shapes as ``recurrence``; ``T`` is padded on the
    right to whole chunks with positions that change nothing."""
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    q, k, v, g, beta = in_chunks((q, k, v, g, beta), chunk)
    n = q.shape[0]
    big = jnp.cumsum(g, axis=-2)                             # G [.., C, K]
    a, qk = _decayed_products(jnp.stack([k, q]), k, big, sub)
    i = jnp.arange(chunk)
    lower = jnp.where(i[:, None] > i[None, :], a * beta[..., None], 0.0)
    rhs = jnp.concatenate([k * jnp.exp(big) * beta[..., None],
                           v * beta[..., None]], axis=-1)
    solved = unit_lower_solve(lower, rhs)
    w_, u_ = solved[..., :dk], solved[..., dk:]
    qg = q * jnp.exp(big)
    kg = k * jnp.exp(big[..., -1:, :] - big)
    g_end = jnp.exp(big[..., -1, :])                         # [.., K]

    def one(s, xs):
        w_c, u_c, qk_c, qg_c, kg_c, ge_c = xs
        vp = u_c - jnp.einsum("bhck,bhkv->bhcv", w_c, s, precision=_HI)
        o = (jnp.einsum("bhck,bhkv->bhcv", qg_c, s, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", qk_c, vp, precision=_HI))
        s = (s * ge_c[..., None]
             + jnp.einsum("bhck,bhcv->bhkv", kg_c, vp, precision=_HI))
        return s, o

    state, o = jax.lax.scan(one, state.astype(jnp.float32),
                            (w_, u_, qk, qg, kg, g_end))
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, dv)
    return o[:, :, :t], state


# -- one position, on the chip ------------------------------------------------


def _step_kernel(qkd_ref, vb_ref, s_ref, o_ref, s_out_ref):
    vb = vb_ref[...]                    # [hb, 2, V]   v, beta
    q_col, k_col, decay = stood_up(qkd_ref[...])   # [hb, 3, K] q, k, exp(g)
    v, beta = vb[:, 0:1], vb[:, 1:2]
    s = s_ref[...] * decay                                   # [hb, K, V]
    d = beta * (v - jnp.sum(s * k_col, axis=1, keepdims=True))
    s = s + k_col * d
    s_out_ref[...] = s
    o_ref[...] = jnp.sum(s * q_col, axis=1, keepdims=True)


def step_kernel(q, k, v, g, beta, states, layer_idx,
                interpret: bool = False, lanes=None):
    """One position of every LIVE row through the kernel. ``q``, ``k``,
    ``g`` [B, H, K], ``v`` [B, H, V], ``beta`` [B, H] (float32);
    ``states`` the WHOLE ``[layers, B, H, K, V]`` float32 stack, of
    which layer ``layer_idx`` is read and written in place (the input
    aliases the output: treat the passed buffer as consumed); ``lanes``
    the ``gated_delta.lane_order`` of the lanes that hold a request
    (``None``: every lane does). A lane that holds none is not streamed:
    its state stays bit for bit what came in and its row of ``o`` is
    zeros. Returns ``(o [B, H, V], states)``."""
    dv = v.shape[-1]
    qkd = jnp.stack([q, k, jnp.exp(g)], axis=2).astype(jnp.float32)
    vb = jnp.stack([v.astype(jnp.float32),
                    jnp.broadcast_to(beta[..., None], v.shape)], axis=2)
    if lanes is None:
        lanes = gated_delta.every_lane(q.shape[0])
    o, states = gated_delta._step_call(
        qkd, vb, states, layer_idx, lanes, interpret=interpret,
        kernel=_step_kernel, name=KERNEL_NAME)
    return o.reshape(o.shape[0], o.shape[1], dv), states


def step(q, k, v, g, beta, states, layer_idx,
         kernel: Optional[str] = None, lanes=None):
    """One position, by the kernel (``kernel``: ``"device"`` or
    ``"interpret"``; the live lanes of ``lanes`` alone, ``step_kernel``)
    or by the recurrence on the layer's slice, which computes every
    lane whatever ``lanes`` says."""
    if kernel is not None:
        return step_kernel(q, k, v, g, beta, states, layer_idx,
                           interpret=kernel == "interpret", lanes=lanes)
    s = jax.lax.dynamic_index_in_dim(states, layer_idx, 0, keepdims=False)
    o, s = recurrence(q[:, :, None], k[:, :, None], v[:, :, None],
                      g[:, :, None], beta[:, :, None], s)
    return o[:, :, 0], jax.lax.dynamic_update_index_in_dim(
        states, s.astype(states.dtype), layer_idx, 0)
