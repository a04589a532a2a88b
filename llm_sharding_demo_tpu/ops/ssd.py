"""The selective state-space rule (Mamba-2): a state a row carries.

A state-space mixer keeps, for every head of every row, one matrix ``S``
(``N`` state dimensions by ``P`` head dimensions, float32) in place of
keys and values a position. One position does

    S <- exp(dt A) S + B (dt x)^T;  y = S^T C

with ``dt > 0`` a head (``softplus`` of a projection), ``A < 0`` a head,
``x`` the head's ``P`` inputs, ``B`` and ``C`` the ``N``-vectors of the
head's GROUP (``heads / groups`` heads share them). Beside
``ops.gated_delta``'s rule this one subtracts nothing before it writes:
no ``S^T k`` correction, so a chunk has no triangle to invert. The
matrix is stored ``[N, P]``, the state dimension first (the published
code keeps ``[P, N]``: the same numbers transposed), so that the
read-out is a sum over sublanes and the kernel has the delta rule's
shape. The skip ``D x`` and everything around the rule (projection,
convolution, gate, norm) are the caller's; ``gated_delta.causal_conv``
is the convolution.

Three forms of the same sums:

- ``recurrence``: the line above, position by position (``lax.scan``):
  the definition, what the tests hold the others to, and the XLA path
  of a single position;
- ``chunked``: a call of several positions in chunks of ``chunk``
  counted from the call's first position (the SSD form, Dao & Gu 2024,
  arXiv:2405.21060). Inside a chunk, with ``a = dt A`` and ``G`` its
  running sum: ``Y_diag = ((C B^T) e^{G_i - G_j} [i >= j]) (dt x)``, the
  chunk's own state ``(B e^{G_C - G})^T (dt x)``, and with the incoming
  ``S0``: ``Y_off = (C S0) e^G``, ``S1 = e^{G_C} S0 +`` the chunk's own.
  Every exponent is <= 0. What does not depend on ``S0`` is computed
  for all chunks at once; one scan carries the state through them. A
  caller whose calls start at multiples of ``chunk`` (the prefix
  store's walk, whose chunk is a multiple of it) computes the same sums
  whether it walks a prompt in one call or in several: the grid is then
  absolute;
- ``step_kernel``: one position as a Pallas kernel that streams a row's
  state through VMEM once, a block of heads at a time (read, decay,
  rank-one write, write back in place, read out): 2 x ``H N P`` x 4
  bytes a row a layer and nothing else of size; for the rows that hold
  a request alone (``ops.gated_delta``'s grid over the live lanes):
  another lane's state is left as it came in, its output zeros.

Positions a caller masks (the left pad of a prompt bucket, the right pad
up to a whole chunk) get ``dt = 0``: they change nothing.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta import LANE_SEMANTICS, every_lane, lane_maps, streamed

# Numerics contract (tools/graftcheck numerics pass): the rule runs in
# float32 whatever the regime (the state is a running sum over the whole
# row), at full matmul precision; the gated norm takes float32 and hands
# on float32.
PRECISION_CONTRACT = {
    "recurrence": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "chunked": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "step_kernel": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "gated_group_norm": {"regime": "f32", "exact": True, "casts": ("f32",)},
}

HEAD_BLOCK = 16        # heads a grid step of the kernel streams
_HI = jax.lax.Precision.HIGHEST


def gated_group_norm(y: jnp.ndarray, z: jnp.ndarray, scale: jnp.ndarray,
                     groups: int, eps: float) -> jnp.ndarray:
    """``y silu(z)`` (the gate FIRST), then RMS norm over each of
    ``groups`` equal parts of the last axis, times ``scale`` over the
    whole of it. Float32 out."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = g.reshape(g.shape[:-1] + (groups, g.shape[-1] // groups))
    parts = parts * jax.lax.rsqrt(
        jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return parts.reshape(g.shape) * scale.astype(jnp.float32)


def recurrence(x, dt, a, bm, cm, state):
    """The rule position by position. ``x`` [B, T, H, P], ``dt``
    [B, T, H], ``a`` [H] (negative), ``bm``, ``cm`` [B, T, G, N],
    ``state`` [B, H, N, P]; all float32. Returns ``(y [B, T, H, P],
    state)``."""
    r = x.shape[2] // bm.shape[2]

    def one(s, xs):
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = jnp.repeat(b_t, r, axis=1), jnp.repeat(c_t, r, axis=1)
        s = (s * jnp.exp(dt_t * a)[..., None, None]
             + b_t[..., :, None] * (dt_t[..., None] * x_t)[..., None, :])
        return s, jnp.einsum("bhn,bhnp->bhp", c_t, s, precision=_HI)

    xs = tuple(jnp.moveaxis(v.astype(jnp.float32), 1, 0)
               for v in (x, dt, bm, cm))
    state, y = jax.lax.scan(one, state.astype(jnp.float32), xs)
    return jnp.moveaxis(y, 0, 1), state


def chunked(x, dt, a, bm, cm, state, chunk: int):
    """The rule in chunks of ``chunk`` positions from the call's first
    (module docstring). Shapes as ``recurrence``; ``T`` is padded on the
    right to whole chunks with positions that change nothing."""
    b, t, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    r = h // g
    nc = -(-t // chunk)
    extra = nc * chunk - t

    def heads_first(v, tail):          # ... -> [nc, B, G, (R,) C, (W)]
        v = v.astype(jnp.float32)
        if extra:
            v = jnp.pad(v, [(0, 0), (0, extra)] + [(0, 0)] * (v.ndim - 2))
        return v.reshape((b, nc, chunk, g) + tail)

    dt = heads_first(dt, (r,)).transpose(1, 0, 3, 4, 2)        # [nc,B,G,R,C]
    dtx = (heads_first(x, (r, p)).transpose(1, 0, 3, 4, 2, 5)
           * dt[..., None])                                    # [..,C,P]
    bm = heads_first(bm, (n,)).transpose(1, 0, 3, 2, 4)        # [nc,B,G,C,N]
    cm = heads_first(cm, (n,)).transpose(1, 0, 3, 2, 4)
    big = jnp.cumsum(
        dt * a.astype(jnp.float32).reshape(g, r, 1), axis=-1)  # G
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              big[..., :, None] - big[..., None, :],
                              -jnp.inf))                       # [..,R,Ci,Cj]
    cb = jnp.einsum("cbgin,cbgjn->cbgij", cm, bm, precision=_HI)
    y_diag = jnp.einsum("cbgrij,cbgrjp->cbgrip",
                        cb[:, :, :, None] * decay, dtx, precision=_HI)
    own = jnp.einsum("cbgjn,cbgrjp->cbgrnp", bm,
                     jnp.exp(big[..., -1:] - big)[..., None] * dtx,
                     precision=_HI)
    into = jnp.exp(big)                                        # e^{G_i}
    g_end = into[..., -1]                                      # [nc,B,G,R]

    def one(s, xs):
        cm_c, into_c, own_c, ge_c = xs
        y_off = jnp.einsum("bgin,bgrnp->bgrip", cm_c, s,
                           precision=_HI) * into_c[..., None]
        return s * ge_c[..., None, None] + own_c, y_off

    state, y_off = jax.lax.scan(
        one, state.astype(jnp.float32).reshape(b, g, r, n, p),
        (cm, into, own, g_end))
    y = (y_diag + y_off).transpose(1, 0, 4, 2, 3, 5)    # [B,nc,C,G,R,P]
    return (y.reshape(b, nc * chunk, h, p)[:, :t],
            state.reshape(b, h, n, p))


# -- one position, on the chip ------------------------------------------------


def _head_block(heads: int, groups: int) -> int:
    """Heads a grid step streams: ``HEAD_BLOCK`` or a group's heads if
    those are fewer (a block reads ONE group's ``B`` and ``C``)."""
    return min(HEAD_BLOCK, heads // groups)


def kernel_eligible(n: int, p: int, heads: int, groups: int,
                    compiled: bool = True) -> bool:
    """Whether the kernel takes these sizes: a group's heads in whole
    blocks and, ``compiled``, whole lane tiles of state and head
    dimensions (interpreted, any)."""
    whole = (heads // groups) % _head_block(heads, groups) == 0
    return whole and (not compiled or (n % 128 == 0 and p % 128 == 0))


def _step_kernel(bc_ref, xd_ref, s_ref, o_ref, s_out_ref):
    bc = bc_ref[...]                    # [2, N]       B, C of the group
    xd = xd_ref[...]                    # [hb, 2, P]   dt x, exp(dt A)
    n = bc.shape[-1]
    # a vector that lies along the lanes, stood up along the sublanes:
    # the diagonal of its broadcast, summed over the lanes
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))

    def column(row):                    # [1, N] -> [N, 1]
        return jnp.sum(jnp.where(eye, row, 0.0), axis=-1, keepdims=True)

    b_col, c_col = column(bc[0:1]), column(bc[1:2])
    dtx, decay = xd[:, 0:1], xd[:, 1:2]                      # [hb, 1, P]
    s = s_ref[...] * decay + b_col * dtx                     # [hb, N, P]
    s_out_ref[...] = s
    o_ref[...] = jnp.sum(s * c_col, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(bc, xd, states, layer_idx, lanes, *, interpret: bool):
    """``_step_kernel`` over a grid of (row, block of heads), for the
    live lanes of ``lanes`` (``gated_delta.lane_order``) and no other:
    the grid and the kernel around the update are ``gated_delta``'s
    (``lane_maps``, ``streamed``)."""
    _, b, h, n, p = states.shape
    groups = bc.shape[1]
    hb = _head_block(h, groups)
    per_group = h // groups // hb       # blocks a group's heads make
    held, own = lane_maps(b, h // hb)

    def group(i, j, li, ln):
        lane, block = held(i, j, ln)
        return lane, block // per_group, 0, 0

    def state(i, j, li, ln):
        return (li[0], *held(i, j, ln), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hb),
        in_specs=[
            pl.BlockSpec((None, None, 2, n), group),
            pl.BlockSpec((None, hb, 2, p),
                         lambda i, j, li, ln: (*held(i, j, ln), 0, 0)),
            pl.BlockSpec((None, None, hb, n, p), state),
        ],
        out_specs=[
            pl.BlockSpec((None, hb, 1, p),
                         lambda i, j, li, ln: (*own(i, j, ln), 0, 0)),
            pl.BlockSpec((None, None, hb, n, p), state),
        ],
    )
    return pl.pallas_call(
        streamed(_step_kernel, b),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, 1, p), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # inputs with the scalar operands: li=0, lanes=1, bc=2, xd=3,
        # states=4
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=LANE_SEMANTICS,
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="ssm_state_update",
    )(jnp.asarray(layer_idx, jnp.int32).reshape(1), lanes, bc, xd, states)


def step_kernel(x, dt, a, bm, cm, states, layer_idx,
                interpret: bool = False, lanes=None):
    """One position of every LIVE row through the kernel. ``x``
    [B, H, P], ``dt`` [B, H], ``a`` [H], ``bm``, ``cm`` [B, G, N]
    (float32); ``states`` the WHOLE ``[layers, B, H, N, P]`` float32
    stack, of which layer ``layer_idx`` is read and written in place
    (the input aliases the output: treat the passed buffer as consumed);
    ``lanes`` the ``gated_delta.lane_order`` of the lanes that hold a
    request (``None``: every lane does). A lane that holds none is not
    streamed: its state stays bit for bit what came in and its row of
    ``y`` is zeros. Returns ``(y [B, H, P], states)``."""
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    bc = jnp.stack([bm, cm], axis=2).astype(jnp.float32)
    xd = jnp.stack([dt[..., None] * x,
                    jnp.broadcast_to(jnp.exp(dt * a)[..., None], x.shape)],
                   axis=2)
    if lanes is None:
        lanes = every_lane(x.shape[0])
    y, states = _step_call(bc, xd, states, layer_idx, lanes,
                           interpret=interpret)
    return y.reshape(x.shape), states


def step(x, dt, a, bm, cm, states, layer_idx, kernel: Optional[str] = None,
         lanes=None):
    """One position, by the kernel (``kernel``: ``"device"`` or
    ``"interpret"``; the live lanes of ``lanes`` alone, ``step_kernel``)
    or by the recurrence on the layer's slice, which computes every
    lane whatever ``lanes`` says."""
    if kernel is not None:
        return step_kernel(x, dt, a, bm, cm, states, layer_idx,
                           interpret=kernel == "interpret", lanes=lanes)
    s = jax.lax.dynamic_index_in_dim(states, layer_idx, 0, keepdims=False)
    y, s = recurrence(x[:, None], dt[:, None], a, bm[:, None], cm[:, None], s)
    return y[:, 0], jax.lax.dynamic_update_index_in_dim(
        states, s.astype(states.dtype), layer_idx, 0)
