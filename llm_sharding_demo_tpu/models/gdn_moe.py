"""Gated-delta-rule linear attention, one softmax layer in four, sparse
experts in every layer.

The Qwen3-Next layer plan (the keys of its ``config.json``) as pure JAX,
with the family surface every runtime module dispatches on
(``init_params`` / ``forward`` / ``forward_with_cache`` / ``make_cache``):

- **Two kinds of mixer in periods** of ``full_attention_interval``:
  layer ``i`` is softmax attention iff ``(i + 1) % interval == 0``, the
  others are linear attention. ONE ``lax.scan`` runs over the periods
  (``n_layer / interval`` of them); its body is a period written out:
  ``interval - 1`` linear layers, then the softmax layer. The leaves are
  stacked accordingly: ``periods.gdn`` is a LIST of ``interval - 1``
  trees, one a place in the period, and ``periods.full`` one tree, every
  leaf ``[P, ...]``, so that what the scan slices out for an iteration
  is one layer's matrix, read once by its own matmul (stacked ``[P,
  interval - 1, ...]`` and indexed in the body, the compiler first
  copied every period's three matrices out: half of a decode step; my
  chip run, PR 35); the routed experts' stacks
  (``experts`` ``[n_layer, E, ...]``) stay outside the scan as loop
  constants, indexed by layer inside, so that only chosen experts are
  read. Both blocks are ``llama.pre_norm_block`` with this family's
  norm (``ops.layers.rms_norm_offset``: the ``(1 + w)`` form).
- **Linear attention** (``ops.gated_delta``): ``[q | k | v | z]`` and
  ``[b | a]`` from two projections, their columns stored in BLOCKS (all
  heads' ``q``, then ``k``, ``v``, ``z``; ``b`` then ``a``) where the
  published checkpoint interleaves them per key head: one fixed
  permutation, applied once when a checkpoint is loaded. Sliced per
  head out of an interleaved result, the chip's compiler wanted the
  weights the other way round and copied them, transposed, in every
  decode call: 2.2 GB of temporaries and as many bytes moved (the
  compiler's report for a v5e, PR 35); block slices are whole lane
  tiles and it copies nothing. A depthwise causal convolution of width 4 and
  SiLU over ``[q | k | v]``; unit-length ``q`` and ``k``; the gated
  delta rule over a float32 state ``[K, V]`` a value head (key head
  ``j`` serves value heads ``r j .. r j + r - 1``); per-head RMSNorm of
  the read-out times ``silu(z)``; the output projection. What a layer
  CACHES belongs to the row and not to a position: that state and the
  last 3 inputs of the convolution. ``row_state`` declares it; it rides
  in ``KVCache.state`` as ``(matrices [Lg, B, Hv, K, V] float32, tails
  [Lg, B, 3, C])``. A single position runs the recurrence (on a TPU the
  Pallas kernel that streams the state once), a call of several the
  chunked form.
- **Gated softmax attention**: queries and an output gate from one
  projection (``[q | gate]``, in blocks as above), grouped keys and values,
  per-head RMSNorm of queries and keys, rotary on the leading
  ``partial_rotary_factor`` of a head (rotate-half), causal softmax,
  ``sigmoid(gate)`` on the result. ONLY these layers cache positions
  (``cache_layers``, ``cache_entry``): ``n_layer / interval`` layers,
  ALWAYS in the fused ``[K | V]`` row the decode kernel reads
  (``ops.attention.create_fused_cache``), which the pool stores as one
  plane, so that the cache's second leaf is free for the routing
  counters. A decode step on a TPU
  goes through ``ops.decode_attention``, anything else through the
  masked einsum over the same buffer; both bound or mask their reads by
  the live depth (``bounds_own_reads``: the engine cuts no windows).
- **Experts** (``ops.expert_ffn``): ``softmax`` over ALL
  ``n_routed_total`` in float32, the ``n_experts_per_tok`` largest,
  normalised over the chosen; the layer computes the terms of the
  ``n_routed_experts`` consecutive ids from ``first_expert`` it HOLDS
  and leaves the others out; plus a shared expert scaled by
  ``sigmoid(x w_sg)``. With every expert held this is the whole model;
  with a thirty-second it is what one chip of a 32-way expert-parallel
  deployment computes before the exchange, which is not run here.
- **Window independent**: no capacity, no dropped token, and a row's
  state depends on that row's tokens alone.
- **A left pad changes nothing**: positions a prompt bucket pads get a
  zero input to the convolution, ``beta = 0`` and ``g = 0``, so state
  and tail after the pad are those of position 0, and the softmax
  layers mask them as the dense families do.

The counters in the cache's second leaf are ``models.latent_moe``'s, by
the same names. The multi-token-prediction module the published model
ships is no part of the next-token pass and is not built here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import expert_ffn, gated_delta
from ..ops.attention import (KVCache, cached_attention_fused,
                             causal_attention, merge_heads, split_heads)
from ..ops.layers import linear, rms_norm_offset
from ..ops.rope import apply_rope_leading
from . import stack
from .family import Family
from .latent_moe import CACHE_COUNTERS, INT8_REFUSED, _count, span_labels
from .llama import pre_norm_block, swiglu

Params = Dict[str, Any]

CONV_TAIL = 3                # carried inputs of a width-4 convolution


@dataclasses.dataclass(frozen=True)
class GDNMoEConfig:
    """Sizes under the published key names where the runtime does not
    need its own (``n_*`` as in ``LlamaConfig``)."""

    vocab_size: int = 151936
    n_positions: int = 262144
    n_embd: int = 2048
    n_layer: int = 48
    n_head: int = 16
    n_kv_head: int = 2
    head_dim: int = 256
    full_attention_interval: int = 4
    partial_rotary_factor: float = 0.25
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    n_routed_total: int = 512            # what the router scores
    n_routed_experts: int = 512          # held here ...
    first_expert: int = 0                # ... from this id
    n_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    attention_impl: str = "xla"

    @property
    def n_periods(self) -> int:
        return self.n_layer // self.full_attention_interval

    @property
    def n_linear(self) -> int:           # linear-attention layers
        return self.n_periods * (self.full_attention_interval - 1)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_channels(self) -> int:      # [q | k | v] of a linear layer
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    def __post_init__(self):
        if (self.full_attention_interval < 2
                or self.n_layer % self.full_attention_interval):
            raise ValueError(
                f"n_layer={self.n_layer} must be whole periods of "
                f"full_attention_interval={self.full_attention_interval}")
        if self.n_head % self.n_kv_head:
            raise ValueError("n_head must be a multiple of n_kv_head")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear_num_value_heads must be a multiple "
                             "of linear_num_key_heads")
        if self.linear_conv_kernel_dim != CONV_TAIL + 1:
            raise ValueError("the carried tail is built for a "
                             f"convolution of width {CONV_TAIL + 1}")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("partial_rotary_factor must leave an even "
                             "number of rotated dimensions")
        if self.first_expert < 0 or (self.first_expert + self.n_routed_experts
                                     > self.n_routed_total):
            raise ValueError(
                f"held experts [{self.first_expert}, {self.first_expert} + "
                f"{self.n_routed_experts}) lie outside the router's "
                f"{self.n_routed_total}")
        if self.n_experts_per_tok > self.n_routed_total:
            raise ValueError("n_experts_per_tok exceeds n_routed_total")
        if self.attention_impl != "xla":
            raise ValueError("this family runs attention_impl='xla'")


# Static-analysis/planner contract (tools/graftcheck/costmodel): see
# ``models.gpt2.SHARDING_DESCRIPTOR``. No mesh decoder runs this family;
# the lists name what such a split would have to divide.
SHARDING_DESCRIPTOR = {
    "column": ("periods.gdn.attn.in_qkvz", "periods.full.attn.wq",
               "periods.full.attn.wk", "periods.full.attn.wv"),
    "row": ("periods.gdn.attn.wo", "periods.full.attn.wo"),
    "expert": ("experts.gate", "experts.up", "experts.down"),
    "tp_divisors": ("n_head", "n_kv_head", "linear_num_key_heads"),
    "kvp_divisors": ("n_kv_head",),
    "ep_divisors": ("n_routed_total",),
}

# Numerics contract (tools/graftcheck numerics pass): the value stream
# carries the engine's dtype; routing, the softmax and the delta rule
# run in float32 inside their ops (declared there).
PRECISION_CONTRACT = {
    "forward": {"regime": "carried", "exact": True, "casts": ()},
    "forward_with_cache": {"regime": "carried", "exact": True, "casts": ()},
}

CONFIGS: Dict[str, GDNMoEConfig] = {
    # two periods in the published proportions, every expert held
    "gdn-moe-tiny": GDNMoEConfig(
        vocab_size=256, n_positions=512, n_embd=64, n_layer=8, n_head=4,
        n_kv_head=2, head_dim=32, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, n_routed_total=8,
        n_routed_experts=8, n_experts_per_tok=2),
}


def cache_entry(config: GDNMoEConfig) -> Tuple[int, int, int]:
    """(planes, heads, width) of one position in one CACHED layer, as
    stored: ONE plane of ``n_kv_head`` fused ``[K | V]`` rows. The pool
    then keeps whole fused rows in its blocks and moves them block by
    block (``ops.paged_attention.gather_rows``), where a two-plane pool
    splits and joins keys and values in every mover: 2.25 GB of
    temporaries at 16 rows, which this chip, full of weights and state,
    does not have (my chip run, PR 35)."""
    return (1, config.n_kv_head, 2 * config.head_dim)


def cache_layers(config: GDNMoEConfig) -> int:
    """How many layers cache positions: the softmax ones."""
    return config.n_periods


def row_state(config: GDNMoEConfig, dtype) -> Tuple[tuple, ...]:
    """What ONE row holds beside its positions, leaf by leaf of
    ``KVCache.state`` with the batch axis left out: ``(shape, dtype)``.
    The matrices in float32 as the published code carries them, the
    convolution tails in the served type."""
    c = config
    return (((c.n_linear, c.linear_num_value_heads, c.linear_key_head_dim,
              c.linear_value_head_dim), jnp.dtype(jnp.float32)),
            ((c.n_linear, CONV_TAIL, c.conv_channels), jnp.dtype(dtype)))


def decode_kernel_eligible(config: GDNMoEConfig, cache_seq: int) -> bool:
    """Whether a decode step can run its two Pallas kernels here: the
    two-plane kernel's geometry rule on the softmax layers, and whole
    lane tiles of state for the compiled delta-rule kernel."""
    from ..ops import decode_attention
    return (decode_attention.eligible(cache_seq, config.head_dim, 1)
            and (jax.default_backend() != "tpu"
                 or gated_delta.kernel_eligible(
                     config.linear_key_head_dim, config.linear_value_head_dim,
                     config.linear_num_value_heads)))


def init_params(config: GDNMoEConfig, key: jax.Array,
                dtype=jnp.float32) -> Params:
    """Random-init parameters in the layout of the module docstring,
    matmul weights under ``.../kernel`` as ``[in, out]``. Norm offsets
    are seeded non-zero so that ``1 + w`` and ``w`` differ; ``a_log``
    and ``dt_bias`` so that a position's decay ``exp(g)`` spans roughly
    0.5 to 0.999."""
    c = config
    d, f = c.n_embd, c.moe_intermediate_size
    hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
    p, nl = c.n_periods, c.full_attention_interval - 1
    keys = iter(jax.random.split(key, 128))

    def normal(shape, fan_in=None, std=None):
        std = std if std is not None else fan_in ** -0.5
        return (jax.random.normal(next(keys), shape) * std).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, minval=lo,
                                  maxval=hi).astype(dtype)

    def mlp(lead, width):
        return {"gate": {"kernel": normal(lead + (d, width), d)},
                "up": {"kernel": normal(lead + (d, width), d)},
                "down": {"kernel": normal(lead + (width, d), width)}}

    def common(lead):
        return {"ln_attn": {"scale": normal(lead + (d,), std=0.1)},
                "ln_mlp": {"scale": normal(lead + (d,), std=0.1)},
                "moe": {"router": {
                            "kernel": normal(lead + (d, c.n_routed_total), d)},
                        "shared": mlp(lead,
                                      c.shared_expert_intermediate_size),
                        "shared_gate": {"kernel": normal(lead + (d, 1), d)}}}

    fl = (p,)

    def linear_layers():
        return {**common(fl), "attn": {
            "in_qkvz": {"kernel": normal(
                fl + (d, 2 * hk * dk + 2 * hv * dv), d)},
            "in_ba": {"kernel": normal(fl + (d, 2 * hv), d)},
            "conv": {"weight": normal(
                fl + (c.conv_channels, c.linear_conv_kernel_dim),
                c.linear_conv_kernel_dim)},
            "a_log": uniform(fl + (hv,), -1.4, 0.7),
            "dt_bias": uniform(fl + (hv,), -4.0, -1.0),
            "norm": {"scale": 1.0 + normal(fl + (dv,), std=0.1)},
            "wo": {"kernel": normal(fl + (hv * dv, d), hv * dv)}}}

    return {
        "wte": normal((c.vocab_size, d), std=1.0),
        "periods": {
            "gdn": [linear_layers() for _ in range(nl)],
            "full": {**common(fl), "attn": {
                "wq": {"kernel": normal(
                    fl + (d, c.n_head * 2 * c.head_dim), d)},
                "wk": {"kernel": normal(
                    fl + (d, c.n_kv_head * c.head_dim), d)},
                "wv": {"kernel": normal(
                    fl + (d, c.n_kv_head * c.head_dim), d)},
                "q_norm": {"scale": normal(fl + (c.head_dim,), std=0.1)},
                "k_norm": {"scale": normal(fl + (c.head_dim,), std=0.1)},
                "wo": {"kernel": normal(
                    fl + (c.n_head * c.head_dim, d),
                    c.n_head * c.head_dim)}}},
        },
        "experts": mlp((c.n_layer, c.n_routed_experts), f),
        "ln_f": {"scale": normal((d,), std=0.1)},
        "lm_head": {"kernel": normal((d, c.vocab_size), d)},
    }


def _linear_attention(attn: Params, a: jnp.ndarray, config: GDNMoEConfig,
                      state, li, valid: Optional[jnp.ndarray],
                      kernel: Optional[str], lanes=None):
    """The linear-attention mixer: ``a`` [B, T, d] normed -> ``(out
    [B, T, d], state)``. ``state`` is ``(matrices, tails)`` of ALL the
    linear layers (or ``None``: no cache, zeros come in and nothing goes
    out), ``li`` this layer's index among them; ``valid`` [B, T] marks
    the positions that count (``None``: all); ``lanes`` says which rows
    a single position's kernel streams (``gated_delta.live_lanes``;
    ``None``: all)."""
    c = config
    b, t, _ = a.shape
    hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
    r = hv // hk
    with jax.named_scope("gdn_proj"):
        qkvz = linear(a, attn["in_qkvz"]["kernel"])   # [q | k | v | z]
        ba = linear(a, attn["in_ba"]["kernel"])       # [b | a]
        z = qkvz[..., 2 * hk * dk + hv * dv:].reshape(b, t, hv, dv)
        g, beta = gated_delta.gates(ba[..., hv:], ba[..., :hv],
                                    attn["a_log"], attn["dt_bias"])
    with jax.named_scope("gdn_conv"):
        u = qkvz[..., :2 * hk * dk + hv * dv]
        if valid is not None:
            u = jnp.where(valid[..., None], u, 0)
            g = jnp.where(valid[..., None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        if state is None:
            tail = jnp.zeros((b, CONV_TAIL, u.shape[-1]), u.dtype)
        else:
            tail = jax.lax.dynamic_index_in_dim(state[1], li, 0,
                                                keepdims=False)
        conv, tail = gated_delta.causal_conv(u, tail,
                                             attn["conv"]["weight"])
        q = gated_delta.l2norm(
            conv[..., :hk * dk].reshape(b, t, hk, dk)) * dk ** -0.5
        k = gated_delta.l2norm(
            conv[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk))
        v = conv[..., 2 * hk * dk:].reshape(b, t, hv, dv)
        if r > 1:
            q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    with jax.named_scope("gdn_state"):
        if t == 1 and state is not None:
            o, mats = gated_delta.step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                       beta[:, 0], state[0], li, kernel,
                                       lanes)
            o = o[:, None]                                   # [B, 1, Hv, V]
        else:
            s0 = (jnp.zeros((b, hv, dk, dv), jnp.float32) if state is None
                  else jax.lax.dynamic_index_in_dim(state[0], li, 0,
                                                    keepdims=False))
            o, s1 = gated_delta.chunked(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), g.transpose(0, 2, 1),
                beta.transpose(0, 2, 1), s0)
            o = o.transpose(0, 2, 1, 3)
            mats = (None if state is None else
                    jax.lax.dynamic_update_index_in_dim(
                        state[0], s1.astype(state[0].dtype), li, 0))
        if state is not None:
            state = (mats, jax.lax.dynamic_update_index_in_dim(
                state[1], tail, li, 0))
    with jax.named_scope("gdn_proj"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + c.rms_norm_eps)
        o = (o * attn["norm"]["scale"].astype(jnp.float32)
             * jax.nn.silu(z.astype(jnp.float32))).astype(a.dtype)
        return linear(o.reshape(b, t, hv * dv), attn["wo"]["kernel"]), state


def _gated_attention(attn: Params, a: jnp.ndarray, config: GDNMoEConfig,
                     cos, sin, kv: Optional[jnp.ndarray], li, offset,
                     pad: Optional[jnp.ndarray], kernel: Optional[str]):
    """The softmax mixer: ``a`` [B, T, d] normed -> ``(out, kv)`` over
    the fused ``[Lf, B, Hkv, S, 2 hd]`` cache of the softmax layers."""
    c = config
    b, t, _ = a.shape
    with jax.named_scope("gated_attn"):
        qg = linear(a, attn["wq"]["kernel"])                # [q | gate]
        q = split_heads(qg[..., :c.n_head * c.head_dim], c.n_head)
        gate = qg[..., c.n_head * c.head_dim:]
        k = split_heads(linear(a, attn["wk"]["kernel"]), c.n_kv_head)
        v = split_heads(linear(a, attn["wv"]["kernel"]), c.n_kv_head)
        q = rms_norm_offset(q, attn["q_norm"]["scale"], c.rms_norm_eps)
        k = rms_norm_offset(k, attn["k_norm"]["scale"], c.rms_norm_eps)
        q = apply_rope_leading(q, cos, sin)
        k = apply_rope_leading(k, cos, sin)
        if kv is None:
            o = causal_attention(q, k, v, q_offset=0, k_valid_from=pad)
        elif t == 1 and kernel is not None:
            from ..ops.decode_attention import decode_attention
            o, kv = decode_attention(q, k, v, kv, li, offset, pad,
                                     interpret=kernel == "interpret")
        else:
            o, kv = cached_attention_fused(q, k, v, kv, li, offset, pad)
        o = merge_heads(o) * jax.nn.sigmoid(
            gate.astype(jnp.float32)).astype(o.dtype)
        return linear(o, attn["wo"]["kernel"]), kv


def expert_layer(moe: Params, experts: Params, m: jnp.ndarray,
                 config: GDNMoEConfig, layer_idx,
                 kernel: Optional[str] = None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The feed-forward of every layer on ``m`` [B, T, d] normed: the
    held experts' weighted terms plus the gated shared expert. ``moe``
    holds this layer's router, shared expert and its gate, ``experts``
    the WHOLE ``[n_layer, E, ...]`` stacks, ``kernel`` what the engine
    resolved (``ops.expert_ffn``). Returns ``(out, counts
    [n_routed_experts])``."""
    c = config
    b, t, d = m.shape
    x = m.reshape(b * t, d)
    with jax.named_scope("moe_router"):
        ids, w = expert_ffn.route_softmax(
            x, moe["router"]["kernel"], c.n_experts_per_tok,
            c.norm_topk_prob)
    with jax.named_scope("moe_experts"):
        y, counts = expert_ffn.held_experts_ffn(
            x, ids, w, experts["gate"]["kernel"], experts["up"]["kernel"],
            experts["down"]["kernel"], layer_idx, c.first_expert, kernel)
    with jax.named_scope("moe_shared"):
        share = jax.nn.sigmoid(
            linear(x, moe["shared_gate"]["kernel"]).astype(jnp.float32))
        y = y + (swiglu(moe["shared"], x) * share).astype(y.dtype)
    return y.reshape(b, t, d), counts


def apply_blocks(params: Params, h: jnp.ndarray, config: GDNMoEConfig,
                 cos, sin, cache: Optional[KVCache] = None,
                 pad: Optional[jnp.ndarray] = None,
                 decode_kernel: Optional[str] = None,
                 ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """All the layers: one ``lax.scan`` over the periods, a period
    written out in its body. The cache's leaves (positions of the
    softmax layers, the rows' state) ride the carry."""
    c = config
    nl = c.full_attention_interval - 1
    t = h.shape[1]
    offset = 0 if cache is None else cache.length
    kv = None if cache is None else cache.k
    state = None if cache is None else cache.state
    counters = (jnp.zeros((len(CACHE_COUNTERS),), jnp.int32)
                if cache is None else cache.v)
    experts = params["experts"]
    valid = None
    if pad is not None and t > 1:
        valid = (offset + jnp.arange(t))[None, :] >= pad[:, None]
    lanes = gated_delta.live_lanes(pad, offset, t, decode_kernel)

    def period(carry, xs):
        h, kv, state = carry
        p, pi = xs
        seen = []

        def feed(moe, layer):
            def ffn(m):
                out, counts = expert_layer(moe, experts, m, c, layer,
                                           decode_kernel)
                seen.append(counts)
                return out
            return ffn

        for j, pj in enumerate(p["gdn"]):
            h, state = pre_norm_block(
                pj, h, c.rms_norm_eps,
                lambda a, pj=pj, j=j, state=state: _linear_attention(
                    pj["attn"], a, c, state, pi * nl + j, valid,
                    decode_kernel, lanes),
                feed(pj["moe"], pi * (nl + 1) + j), norm=rms_norm_offset)
        pf = p["full"]
        h, kv = pre_norm_block(
            pf, h, c.rms_norm_eps,
            lambda a: _gated_attention(pf["attn"], a, c, cos, sin, kv, pi,
                                       offset, pad, decode_kernel),
            feed(pf["moe"], pi * (nl + 1) + nl), norm=rms_norm_offset)
        return (h, kv, state), jnp.stack(seen)

    (h, kv, state), counts = jax.lax.scan(
        period, (h, kv, state),
        (params["periods"], jnp.arange(c.n_periods)))
    counters = _count(counters, counts.reshape(c.n_layer, -1),
                      h.shape[0] * t * c.n_experts_per_tok)
    if cache is None:
        return h, None
    new_len = cache.length + jnp.asarray(t, dtype=jnp.int32)
    return h, KVCache(kv, counters, new_len, state)


def forward(params: Params, input_ids: jnp.ndarray, config: GDNMoEConfig,
            remat: bool = False, mesh=None) -> jnp.ndarray:
    """Full no-cache forward: [B, S] -> [B, S, vocab] float32 logits
    (the chunked rule from a zero state; ``remat``/``mesh`` accepted for
    the family surface and unused: nothing trains or shards this family
    yet)."""
    return stack.forward(FAMILY, params, input_ids, config)


def forward_with_cache(params: Params, input_ids: jnp.ndarray,
                       config: GDNMoEConfig, cache: KVCache,
                       pad: Optional[jnp.ndarray] = None,
                       flash_prefill: bool = False,
                       decode_kernel: Optional[str] = None,
                       ) -> Tuple[jnp.ndarray, KVCache]:
    """Cached forward at ``cache.length``: a single position through
    the recurrence and the decode kernels where the engine resolved
    them, several through the chunked rule and the masked einsum.
    ``flash_prefill`` is accepted for the family surface and unused."""
    return stack.forward_with_cache(FAMILY, params, input_ids, config, cache,
                                    pad, flash_prefill, decode_kernel)


def make_cache(config: GDNMoEConfig, batch: int, max_seq: int,
               dtype=jnp.float32) -> KVCache:
    """The softmax layers' fused ``[P, B, Hkv, max_seq, 2 hd]`` rows,
    the zeroed counters, and the rows' zeroed state."""
    return stack.make_cache(FAMILY, config, batch, max_seq, dtype)


# What the linear-attention / sparse-expert families refuse, one
# sentence each (``models.kda_moe`` says the same): they serve through
# the single-device engine (solo, the iteration scheduler, the paged
# pool with its state slab, the prefix store) in float32 or bfloat16.
REFUSES = (
    ("spec_decode",
     "SPEC_DECODE: a rejected draft cannot be rewound out of "
     "{name}'s per-row state (it has no position axis) without "
     "a snapshot a verify; serve it without speculation"),
    ("kv_pool_dtype",
     "KV_POOL_DTYPE={value}: {name}'s pool is one "
     "plane with counters in its second leaf and its rows' state "
     "is float32 by contract; the quantized movers have not been "
     "fitted to it"),
    ("kv_host_blocks",
     "KV_HOST_BLOCKS: a demoted entry of {name} would need its "
     "state snapshot demoted with its blocks; the host tier "
     "moves blocks only"),
    ("multi_chip",
     "PP/TP/EP_DECODE: no multi-chip decoder stages or shards "
     "{name} (runs of unlike layers, a state slab beside "
     "the pool, experts indexed in place); it serves on one "
     "chip, told which experts it holds"),
    ("int8_weights", INT8_REFUSED))

FAMILY = Family(
    name="gdn_moe", config_class=GDNMoEConfig,
    frame=stack.Frame(apply_blocks, rotary_width=lambda c: c.rotary_dim,
                      norm=rms_norm_offset),
    cache_entry=cache_entry, cache_layers=cache_layers, row_state=row_state,
    cache_counters=CACHE_COUNTERS, span_labels=span_labels,
    bounds_own_reads=True,       # kernel and masked einsum bound their reads
    decode_kernel_eligible=decode_kernel_eligible,
    refuses=REFUSES)
