"""The gated delta rule: linear attention over a state a row carries.

A linear-attention layer keeps, for every value head of every row, one
matrix ``S`` (``K`` key dimensions by ``V`` value dimensions, float32)
in place of keys and values a position. One position does

    S <- exp(g) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q

with ``g <= 0`` (a decay) and ``0 < beta < 1`` (how much of the error
is written back), ``q`` and ``k`` unit length (``q`` also over
``sqrt(K)``). The state belongs to the ROW: it has no position axis, so
nothing here is paged, rolled or windowed; what a caller carries beside
it is the last ``width - 1`` inputs of the depthwise causal convolution
in front of the rule (``causal_conv``'s tail).

This module is the rule with ONE decay a head a position (``g`` a
scalar); ``ops.kda`` is the rule with one decay a key CHANNEL and takes
``l2norm``, ``gates``, ``causal_conv``, ``unit_lower_solve``, the cut
into chunks and the kernel's call from here. What is said below of ``L``'s
factoring (``e^{G_i - G_j}`` outside the dot product) and of every
exponent being <= 0 BY CONSTRUCTION holds for the scalar gate only: per
channel the decay sits inside the sum over channels, and the obvious
factoring forms ``e^{-G}``, which overflows (``ops.kda`` says what it
does instead). The recurrence, the solve, the state's carry through the
chunks and the masked positions are the same in both.

Three forms of the same sums:

- ``recurrence``: the line above, position by position (``lax.scan``):
  the definition, what the tests hold the others to, and the XLA path
  of a single position;
- ``chunked``: a call of several positions in chunks of ``CHUNK``,
  counted from the call's first position (the published
  ``torch_chunk_gated_delta_rule``). Inside a chunk, with ``G`` the
  running sum of ``g``: ``L_ij = beta_i (k_i . k_j) e^{G_i - G_j}`` for
  ``i > j``, ``T = (I + L)^-1``, ``[W | U] = T [beta e^G K | beta V]``
  in one matmul; with the incoming ``S0``: ``V' = U - W S0``, ``O = (Q
  e^G) S0 + ((Q K^T) e^{G_i - G_j} [i >= j]) V'``, ``S1 = e^{G_C} S0 +
  (K e^{G_C - G})^T V'``. Every exponent is <= 0. What does not depend
  on ``S0`` is computed for all chunks at once; one scan carries the
  state through them. ``T`` is built by ten whole-matrix matmuls for
  all chunks and heads together (``unit_lower_solve``), not by forward
  substitution, which the chip runs a row of the chunk at a time: on
  diagonal blocks of two rows ``T = I - L`` exactly, and two blocks
  ``T11``, ``T22`` with the ``L21`` between them make the block of twice
  the size, ``T21 = -T22 L21 T11``, five times over (as ``T <- T - T
  L' T`` with ``L'`` all the ``L21`` of a level; the levels are one
  scan over their masks, because unrolled they made every prefill and
  store program a tenth larger and a warm set-up 8% longer). The
  shorter product
  ``(I + N)(I + N^2)...(I + N^32)``, ``N = -L``, is NOT used: where
  keys are nearly parallel and ``beta`` is near 1, ``L`` is near 1
  under the whole diagonal and ``N^32`` holds binomials near 1e18, which
  cancel to NaN in float32; the merges never form a power of ``L`` and
  stay as close to the recurrence there as the serial solve does
  (``tests/test_gdn_moe.py``). A caller whose calls start at
  multiples of ``CHUNK`` (the prefix store's walk does, at its default
  chunk) therefore computes the same sums whether it walks a prompt in
  one call or in several: the grid is then absolute;
- ``step_kernel``: one position as a Pallas kernel that streams a row's
  state through VMEM once, a block of heads at a time (read, decay,
  correct, write back in place, read out): 2 x ``H K V`` x 4 bytes a row
  a layer and nothing else of size. It streams the rows that hold a
  request and no other (``lane_order``, ``lane_maps``, ``streamed``,
  which ``ops.kda`` and ``ops.ssd`` take from here): the live lanes'
  indices and their count are a scalar operand, the grid's rows read
  through it, and a lane without a request (an empty span:
  ``live_lanes``) is copied neither in nor out: its state stays bit for
  bit what came in, its output row is zeros.

Positions a caller masks (``valid`` false: the left pad of a prompt
bucket, the right pad up to a whole chunk) get ``beta = 0`` and ``g =
0``: they change nothing.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Numerics contract (tools/graftcheck numerics pass): the rule runs in
# float32 whatever the regime (the state is a running sum over the whole
# row; the published code carries it so), at full matmul precision; the
# convolution sums in float32 and hands on float32.
PRECISION_CONTRACT = {
    "recurrence": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "chunked": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "step_kernel": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "causal_conv": {"regime": "f32", "exact": True,
                    "casts": ("f32", "carried")},
}

CHUNK = 64
HEAD_BLOCK = 16        # value heads a grid step of the kernel streams
_HI = jax.lax.Precision.HIGHEST


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gates(a: jnp.ndarray, b: jnp.ndarray, a_log: jnp.ndarray,
          dt_bias: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``a``, ``b`` [..., H] -> ``(g, beta)`` float32: ``g = -exp(A_log)
    softplus(a + dt_bias)``, ``beta = sigmoid(b)``."""
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    return g, jax.nn.sigmoid(b.astype(jnp.float32))


def causal_conv(u: jnp.ndarray, tail: jnp.ndarray, w: jnp.ndarray,
                bias: Optional[jnp.ndarray] = None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal convolution with a carried tail, then SiLU.

    ``u`` [B, T, C] this call's inputs, ``tail`` [B, W-1, C] the inputs
    of the ``W - 1`` positions before it (zeros before position 0),
    ``w`` [C, W], ``bias`` [C] or ``None`` (nothing is added, and the
    program is what it was without the argument): ``c_t = silu(bias +
    sum_j w[:, j] u_{t - (W-1) + j})``.
    Returns ``(c [B, T, C] float32, the new tail)`` in ``tail``'s type."""
    t, width = u.shape[1], w.shape[1]
    full = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    w32 = w.astype(jnp.float32)
    c = sum(full[:, j:j + t].astype(jnp.float32) * w32[:, j]
            for j in range(width))
    if bias is not None:
        c = c + bias.astype(jnp.float32)
    return jax.nn.silu(c), full[:, t:].astype(tail.dtype)


def recurrence(q, k, v, g, beta, state):
    """The rule position by position. ``q``, ``k`` [B, H, T, K] (already
    normalised), ``v`` [B, H, T, V], ``g``, ``beta`` [B, H, T], ``state``
    [B, H, K, V]; all float32. Returns ``(o [B, H, T, V], state)``."""
    def one(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        kv = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=_HI)
        d = b_t[..., None] * (v_t - kv)
        s = s + k_t[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=_HI)

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    state, o = jax.lax.scan(one, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 2), state


def unit_lower_solve(lower: jnp.ndarray, rhs: jnp.ndarray) -> jnp.ndarray:
    """``(I + L)^-1 R`` for strictly lower ``L`` [..., C, C] and ``R``
    [..., C, N], the inverse built by whole-matrix matmuls (module
    docstring): exact on diagonal blocks of two rows, then blocks of
    twice the size from pairs of them until one block is left, the
    levels as one scan over their masks."""
    c = lower.shape[-1]

    def within(size):                  # [C, C]: inside a diagonal block
        return (np.arange(c)[:, None] // size) == (np.arange(c) // size)

    def mm(a, b):
        return jnp.matmul(a, b, precision=_HI)

    def merge(inv, pair):              # T21 = -T22 L21 T11, every pair
        below = jnp.where(pair, lower, 0.0)
        return inv - mm(mm(inv, below), inv), None

    sizes = [2 ** j for j in range(1, (c - 1).bit_length())]
    pairs = np.array([within(2 * s) & ~within(s) for s in sizes],
                     bool).reshape(-1, c, c)
    inv = jnp.eye(c, dtype=lower.dtype) - jnp.where(within(2), lower, 0.0)
    inv, _ = jax.lax.scan(merge, inv, pairs)
    return mm(inv, rhs)


def in_chunks(xs, chunk: int):
    """Each ``[B, H, T, ...]`` of ``xs`` padded on the right with zeros
    (positions that change nothing) to whole chunks and cut into them:
    ``[n, B, H, chunk, ...]``."""
    b, h, t = xs[0].shape[:3]
    n = -(-t // chunk)
    extra = n * chunk - t
    if extra:
        def fill(x):
            return jnp.pad(x, [(0, 0), (0, 0), (0, extra)]
                           + [(0, 0)] * (x.ndim - 3))
        xs = tuple(map(fill, xs))

    def cut(x):                        # [B,H,n*C,...] -> [n,B,H,C,...]
        return jnp.moveaxis(x.reshape((b, h, n, chunk) + x.shape[3:]), 2, 0)

    return tuple(map(cut, xs))


def chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The rule in chunks of ``chunk`` positions from the call's first
    (module docstring). Shapes as ``recurrence``; ``T`` is padded on the
    right to whole chunks with positions that change nothing."""
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    n = -(-t // chunk)
    q, k, v, g, beta = in_chunks((q, k, v, g, beta), chunk)
    big = jnp.cumsum(g, axis=-1)                               # G
    i = jnp.arange(chunk)
    seen = i[:, None] >= i[None, :]
    decay = jnp.exp(jnp.where(seen, big[..., :, None] - big[..., None, :],
                              -jnp.inf))
    kb = k * beta[..., None]
    lower = jnp.einsum("...ik,...jk->...ij", kb, k, precision=_HI) * decay
    lower = jnp.where(i[:, None] > i[None, :], lower, 0.0)
    rhs = jnp.concatenate([kb * jnp.exp(big)[..., None],
                           v * beta[..., None]], axis=-1)
    solved = unit_lower_solve(lower, rhs)
    w_, u_ = solved[..., :dk], solved[..., dk:]
    qk = jnp.einsum("...ik,...jk->...ij", q, k, precision=_HI) * decay
    qg = q * jnp.exp(big)[..., None]
    kg = k * jnp.exp(big[..., -1:] - big)[..., None]
    g_end = jnp.exp(big[..., -1])

    def one(s, xs):
        w_c, u_c, qk_c, qg_c, kg_c, ge_c = xs
        vp = u_c - jnp.einsum("bhck,bhkv->bhcv", w_c, s, precision=_HI)
        o = (jnp.einsum("bhck,bhkv->bhcv", qg_c, s, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", qk_c, vp, precision=_HI))
        s = (s * ge_c[..., None, None]
             + jnp.einsum("bhck,bhcv->bhkv", kg_c, vp, precision=_HI))
        return s, o

    state, o = jax.lax.scan(one, state.astype(jnp.float32),
                            (w_, u_, qk, qg, kg, g_end))
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, dv)
    return o[:, :, :t], state


# -- one position, on the chip ------------------------------------------------


def kernel_eligible(dk: int, dv: int, heads: int) -> bool:
    """Whether the compiled kernel takes these sizes: whole lane tiles
    of keys and values, heads in whole blocks. (Interpreted, any size.)"""
    hb = min(HEAD_BLOCK, heads)
    return dk % 128 == 0 and dv % 128 == 0 and heads % hb == 0


def stood_up(rows):
    """Inside a kernel: each of the ``n`` vectors of ``rows`` [hb, n, K],
    which lie along the lanes, stood up along the sublanes ([hb, K, 1]):
    the diagonal of its broadcast, summed over the lanes."""
    dk = rows.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    return [jnp.sum(jnp.where(eye, rows[:, i:i + 1], 0.0), axis=-1,
                    keepdims=True) for i in range(rows.shape[1])]


def lane_order(live: jnp.ndarray) -> jnp.ndarray:
    """``live`` [B] bool (the lanes that hold a request) -> what the
    kernels' grids walk, int32 ``[B + 1]``: the live lanes' indices in
    their order, then the others', then the live lanes' count. Three
    small sums (no sort): a model step works it out ONCE and hands it to
    every layer's kernel."""
    b = live.shape[0]
    ghost = jnp.logical_not(live)
    count = jnp.sum(live, dtype=jnp.int32)
    place = jnp.where(live, jnp.cumsum(live, dtype=jnp.int32) - 1,
                      count + jnp.cumsum(ghost, dtype=jnp.int32) - 1)
    lane = jnp.arange(b, dtype=jnp.int32)
    order = jnp.sum(jnp.where(place[None, :] == lane[:, None], lane, 0),
                    axis=1, dtype=jnp.int32)
    return jnp.concatenate([order, count[None]])


def live_lanes(pad: Optional[jnp.ndarray], offset, t: int,
               kernel: Optional[str]) -> Optional[jnp.ndarray]:
    """What a model step hands its state kernels: in a call of ONE
    position through the kernel with the rows' left pads, the
    ``lane_order`` of the rows whose span ``[pad, offset]`` is not
    empty. A lane without a request carries a pad that no depth reaches
    (``runtime.iterbatch._empty_span``); a row with a request has its
    pad under its depth. ``None`` (every lane) otherwise: no pads, the
    recurrence, several positions."""
    if pad is None or t != 1 or kernel is None:
        return None
    return lane_order(pad <= offset)


def every_lane(b: int) -> jnp.ndarray:
    """``lane_order`` of ``b`` live lanes: each in its place, ``b``."""
    return jnp.arange(b + 1, dtype=jnp.int32)


def lane_maps(b: int, blocks: int):
    """The two halves of a state kernel's index maps over a grid of
    ``(b rows, blocks of heads)`` with ``lanes`` (``lane_order``) in the
    scalar prefetch. ``held(i, j, lanes)``: the ``(lane, block)`` whose
    inputs and state step ``(i, j)`` holds, the ``i``-th LIVE lane's for
    the first ``count`` rows; every later step stays on the last block
    of the last live lane, which the step before it already holds, and a
    block whose indices repeat is copied neither in nor out again.
    ``own(i, j, lanes)``: the ``(lane, block)`` of the output row, each
    lane's once (a skipped lane's is written too: zeros)."""
    def held(i, j, lanes):
        count = lanes[b]
        last = jnp.maximum(count - 1, 0)
        return (lanes[jnp.minimum(i, last)],
                jnp.where(i < count, j, blocks - 1))

    def own(i, j, lanes):
        return lanes[i], j

    return held, own


def streamed(body, b: int):
    """``body`` (a family's update on its blocks' refs: inputs, the
    state, then the output row and the state out) as the kernel of a
    grid that ``lane_maps`` steers: run for the first ``count`` rows of
    the grid, the live lanes; a later step writes its lane's output row
    as zeros and touches nothing else. With NO live lane every step
    holds one and the same block, whose state goes out as it came in."""
    def kernel(li_ref, lanes_ref, *refs):
        del li_ref                      # used by the index maps
        s_ref, o_ref, s_out_ref = refs[-3:]
        count = lanes_ref[b]
        live = pl.program_id(0) < count
        pl.when(live)(functools.partial(body, *refs))

        @pl.when(jnp.logical_not(live))
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(count == 0)
        def _():
            s_out_ref[...] = s_ref[...]

    return kernel


# a skipped lane's steps revisit the block before them: the grid runs
# in order on one core
LANE_SEMANTICS = ("arbitrary", "arbitrary")


def _step_kernel(qk_ref, vdb_ref, s_ref, o_ref, s_out_ref):
    qk = qk_ref[...]                    # [hb, 2, K]   q, k
    vdb = vdb_ref[...]                  # [hb, 3, V]   v, exp(g), beta
    q_col, k_col = stood_up(qk)
    v, decay, beta = vdb[:, 0:1], vdb[:, 1:2], vdb[:, 2:3]
    s = s_ref[...] * decay                                   # [hb, K, V]
    d = beta * (v - jnp.sum(s * k_col, axis=1, keepdims=True))
    s = s + k_col * d
    s_out_ref[...] = s
    o_ref[...] = jnp.sum(s * q_col, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("kernel", "name", "interpret"))
def _step_call(qk, vdb, states, layer_idx, lanes, *, interpret: bool,
               kernel=_step_kernel, name: str = "gdn_state_update"):
    """``kernel`` over a grid of (row, block of heads): its vectors along
    the keys ``qk`` [B, H, n, K], those along the values ``vdb`` [B, H,
    m, V], and layer ``layer_idx`` of ``states`` streamed through VMEM
    and written back in place, for the live lanes of ``lanes``
    (``lane_order``) and no other (``lane_maps``, ``streamed``)."""
    _, b, h, dk, dv = states.shape
    hb = min(HEAD_BLOCK, h)
    held, own = lane_maps(b, h // hb)

    def vectors(i, j, li, ln):
        return (*held(i, j, ln), 0, 0)

    def state(i, j, li, ln):
        return (li[0], *held(i, j, ln), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hb),
        in_specs=[
            pl.BlockSpec((None, hb, qk.shape[2], dk), vectors),
            pl.BlockSpec((None, hb, vdb.shape[2], dv), vectors),
            pl.BlockSpec((None, None, hb, dk, dv), state),
        ],
        out_specs=[
            pl.BlockSpec((None, hb, 1, dv),
                         lambda i, j, li, ln: (*own(i, j, ln), 0, 0)),
            pl.BlockSpec((None, None, hb, dk, dv), state),
        ],
    )
    return pl.pallas_call(
        streamed(kernel, b),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, 1, dv), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # inputs with the scalar operands: li=0, lanes=1, qk=2, vdb=3,
        # states=4
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=LANE_SEMANTICS,
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name=name,
    )(jnp.asarray(layer_idx, jnp.int32).reshape(1), lanes, qk, vdb, states)


def step_kernel(q, k, v, g, beta, states, layer_idx,
                interpret: bool = False, lanes=None):
    """One position of every LIVE row through the kernel. ``q``, ``k``
    [B, H, K], ``v`` [B, H, V], ``g``, ``beta`` [B, H] (float32);
    ``states`` the WHOLE ``[layers, B, H, K, V]`` float32 stack, of
    which layer ``layer_idx`` is read and written in place (the input
    aliases the output: treat the passed buffer as consumed); ``lanes``
    the ``lane_order`` of the lanes that hold a request (``None``: every
    lane does). A lane that holds none is not streamed: its state stays
    bit for bit what came in and its row of ``o`` is zeros. Returns
    ``(o [B, H, V], states)``."""
    dv = v.shape[-1]
    qk = jnp.stack([q, k], axis=2).astype(jnp.float32)
    vdb = jnp.stack([v.astype(jnp.float32),
                     jnp.broadcast_to(jnp.exp(g)[..., None], v.shape),
                     jnp.broadcast_to(beta[..., None], v.shape)], axis=2)
    if lanes is None:
        lanes = every_lane(q.shape[0])
    o, states = _step_call(qk, vdb, states, layer_idx, lanes,
                           interpret=interpret)
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
