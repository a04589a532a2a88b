"""A state-space mixer and softmax attention side by side in every layer.

The Falcon-H1 layer plan (the keys of its ``config.json``; checked
against ``transformers``' ``modeling_falcon_h1.py``) as pure JAX, with
the family surface every runtime module dispatches on (``init_params`` /
``forward`` / ``forward_with_cache`` / ``make_cache``):

- **Every layer is alike** (period 1): ONE ``lax.scan`` over stacked
  ``[n_layer, ...]`` leaves. A layer norms its input once and hands it
  to BOTH mixers: ``h += ssm_out_multiplier Mixer(ssm_in_multiplier u)
  + attention_out_multiplier Attn(attention_in_multiplier u)``, then a
  SwiGLU whose gate and result carry ``mlp_multipliers``. Norms are the
  plain ``x rsqrt(mean x^2 + eps) w``.
- **The state-space mixer** (``ops.ssd``, Mamba-2): ``[z | x | B | C |
  dt]`` from one projection (columns in that order, as the checkpoint
  has them: the slices are whole lane tiles but the 32 of ``dt``), each
  range scaled by its ``ssm_multipliers`` entry; a depthwise causal
  convolution of width 4 WITH bias and SiLU over ``[x | B | C]``
  (``gated_delta.causal_conv``); ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; the selective rule over a float32 state ``[N, P]`` a
  head, ``B`` and ``C`` shared by the ``heads / groups`` heads of a
  group; the skip ``D x``; ``y silu(z)`` through an RMS norm over each
  GROUP's channels (the gate first: ``mamba_norm_before_gate`` false);
  the output projection. What it CACHES belongs to the row: that state
  and the last 3 inputs of the convolution (``row_state``), in
  ``KVCache.state`` as ``(matrices [L, B, H, N, P] float32, tails [L,
  B, 3, C])``. A single position runs the recurrence (on a TPU the
  Pallas kernel that streams the state once), a call of several the
  chunked form over ``mamba_chunk_size`` positions.
- **Attention**: grouped keys and values, no bias, no per-head norm,
  keys times ``key_multiplier``, rotary (rotate-half) over the whole
  head, causal softmax. It caches POSITIONS, in every layer
  (``cache_layers`` is all of them), ALWAYS in the fused ``[K | V]`` row
  the decode kernel reads, which the pool stores as one plane
  (``cache_entry``). A decode step on a TPU goes through
  ``ops.decode_attention``, anything else through the masked einsum over
  the same buffer; both bound or mask their reads by the live depth
  (``bounds_own_reads``).
- **The multipliers are applied at run time**, in float32 where the
  value is float32 anyway (the convolution's taps carry their ranges',
  the gate ``z``, ``dt``, the residual sums, the SwiGLU gate) and on the
  carried value elsewhere; none is folded into a stored weight, so the
  tree is the checkpoint's.
- **The head runs on the last position of a call of several** (the
  published ``num_logits_to_keep`` is 1): ``forward_with_cache`` returns
  ``[B, 1, vocab]`` there, which is what every caller takes
  (``logits[:, -1]``); all positions' logits of a prefill over a
  vocabulary this wide would be the largest array of the program.
  ``forward`` (no cache) returns every position's.
- **Window independent**, and **a left pad changes nothing**: positions
  a prompt bucket pads get a zero input to the convolution and ``dt =
  0``, so state and tail after the pad are those of position 0, and
  attention masks them as the dense families do.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import gated_delta, ssd
from ..ops.attention import (KVCache, cached_attention_fused,
                             causal_attention, merge_heads, split_heads)
from ..ops.layers import linear, rms_norm
from ..ops.rope import apply_rope
from . import stack
from .family import Family

Params = Dict[str, Any]

CONV_TAIL = 3                # carried inputs of a width-4 convolution


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig:
    """Sizes under the published key names where the runtime does not
    need its own (``n_*`` as in ``LlamaConfig``)."""

    vocab_size: int = 261120
    n_positions: int = 262144
    n_embd: int = 5120
    n_layer: int = 72
    n_head: int = 20
    n_kv_head: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)
    attention_impl: str = "xla"

    @property
    def conv_channels(self) -> int:      # [x | B | C]
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_width(self) -> int:      # [z | x | B | C | dt]
        return self.mamba_d_ssm + self.conv_channels + self.mamba_n_heads

    def __post_init__(self):
        # the published theta is an integer past 32 bits, and a JSON
        # list arrives as a list: a frozen config hashes
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        object.__setattr__(self, "ssm_multipliers",
                           tuple(float(m) for m in self.ssm_multipliers))
        object.__setattr__(self, "mlp_multipliers",
                           tuple(float(m) for m in self.mlp_multipliers))
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers holds 5 values ([z, x, B, C, "
                             "dt]) and mlp_multipliers 2 ([gate, down])")
        if self.n_head % self.n_kv_head:
            raise ValueError("n_head must be a multiple of n_kv_head")
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError("mamba_d_ssm must be mamba_n_heads x "
                             "mamba_d_head")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_heads must be a multiple of "
                             "mamba_n_groups")
        if self.mamba_d_conv != CONV_TAIL + 1:
            raise ValueError("the carried tail is built for a "
                             f"convolution of width {CONV_TAIL + 1}")
        if self.head_dim % 2:
            raise ValueError("rotary turns pairs: head_dim must be even")
        if self.attention_impl != "xla":
            raise ValueError("this family runs attention_impl='xla'")


# Static-analysis/planner contract (tools/graftcheck/costmodel): see
# ``models.gpt2.SHARDING_DESCRIPTOR``. No mesh decoder runs this family;
# the lists name what such a split would have to divide.
SHARDING_DESCRIPTOR = {
    "column": ("blocks.ssm.in_proj", "blocks.attn.wq", "blocks.attn.wk",
               "blocks.attn.wv", "blocks.mlp.gate", "blocks.mlp.up"),
    "row": ("blocks.ssm.out_proj", "blocks.attn.wo", "blocks.mlp.down"),
    "expert": (),
    "tp_divisors": ("n_head", "n_kv_head", "mamba_n_groups"),
    "kvp_divisors": ("n_kv_head",),
    "ep_divisors": (),
}

# Numerics contract (tools/graftcheck numerics pass): the value stream
# carries the engine's dtype; the softmax and the state-space rule run
# in float32 inside their ops (declared there).
PRECISION_CONTRACT = {
    "forward": {"regime": "carried", "exact": True, "casts": ()},
    "forward_with_cache": {"regime": "carried", "exact": True, "casts": ()},
}

CONFIGS: Dict[str, HybridSSMConfig] = {
    # the published proportions small: two groups, FIVE query heads a
    # key-value head (not a power of two), a vocabulary wider than d,
    # every multiplier away from 1
    "hybrid-ssm-tiny": HybridSSMConfig(
        vocab_size=320, n_positions=512, n_embd=64, n_layer=3, n_head=10,
        n_kv_head=2, head_dim=32, intermediate_size=96, mamba_d_ssm=64,
        mamba_n_heads=4, mamba_d_head=16, mamba_d_state=24,
        mamba_n_groups=2, mamba_chunk_size=32,
        embedding_multiplier=2.5, lm_head_multiplier=0.125,
        attention_in_multiplier=0.75, attention_out_multiplier=0.4,
        key_multiplier=0.3, ssm_in_multiplier=0.5, ssm_out_multiplier=0.6,
        ssm_multipliers=(0.7, 0.5, 0.35, 0.9, 0.6),
        mlp_multipliers=(0.45, 0.3)),
}


def cache_entry(config: HybridSSMConfig) -> Tuple[int, int, int]:
    """(planes, heads, width) of one position in one layer, as stored:
    ONE plane of ``n_kv_head`` fused ``[K | V]`` rows, as
    ``models.gdn_moe`` stores its own and for its reason: the pool then
    moves whole fused rows block by block, where a two-plane pool splits
    and joins keys and values in every mover, cache-sized temporaries
    this chip, full of weights and state, does not have."""
    return (1, config.n_kv_head, 2 * config.head_dim)


def row_state(config: HybridSSMConfig, dtype) -> Tuple[tuple, ...]:
    """What ONE row holds beside its positions, leaf by leaf of
    ``KVCache.state`` with the batch axis left out: ``(shape, dtype)``,
    for EVERY layer too. The matrices in float32 (``ops.ssd``'s
    contract), state dimension first; the convolution tails in the
    served type."""
    c = config
    return (((c.n_layer, c.mamba_n_heads, c.mamba_d_state, c.mamba_d_head),
             jnp.dtype(jnp.float32)),
            ((c.n_layer, CONV_TAIL, c.conv_channels), jnp.dtype(dtype)))


def decode_kernel_eligible(config: HybridSSMConfig, cache_seq: int) -> bool:
    """Whether a decode step can run its two Pallas kernels here: the
    two-plane kernel's geometry rule on attention, and whole lane tiles
    of state for the compiled state-space kernel."""
    from ..ops import decode_attention
    c = config
    return (decode_attention.eligible(cache_seq, c.head_dim, 1)
            and ssd.kernel_eligible(
                c.mamba_d_state, c.mamba_d_head, c.mamba_n_heads,
                c.mamba_n_groups, compiled=jax.default_backend() == "tpu"))


def init_params(config: HybridSSMConfig, key: jax.Array,
                dtype=jnp.float32) -> Params:
    """Random-init parameters, every block leaf stacked ``[n_layer,
    ...]``, matmul weights under ``.../kernel`` as ``[in, out]``. Norm
    scales are drawn away from 1 so that a dropped scale shows;
    ``a_log``, ``dt_bias`` and ``d`` as ``benchmark/reference/
    hybrid_ssm.py`` draws them."""
    c = config
    d, l, f = c.n_embd, c.n_layer, c.intermediate_size
    kv = c.n_kv_head * c.head_dim
    keys = iter(jax.random.split(key, 32))

    def normal(shape, fan_in=None, std=None, mean=0.0):
        std = std if std is not None else fan_in ** -0.5
        return (mean + jax.random.normal(next(keys), shape) * std
                ).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, minval=lo,
                                  maxval=hi).astype(dtype)

    def scale(shape):
        return {"scale": normal(shape, std=0.1, mean=1.0)}

    h = c.mamba_n_heads
    return {
        "wte": normal((c.vocab_size, d), std=1.0),
        "blocks": {
            "ln_attn": scale((l, d)),
            "ssm": {
                "in_proj": {"kernel": normal((l, d, c.in_proj_width), d)},
                "conv": {"weight": normal((l, c.conv_channels,
                                           c.mamba_d_conv), c.mamba_d_conv),
                         "bias": normal((l, c.conv_channels), std=0.1)},
                "dt_bias": uniform((l, h), -4.0, -1.0),
                "a_log": uniform((l, h), -1.4, 0.7),
                "d": normal((l, h), std=0.1, mean=1.0),
                "norm": scale((l, c.mamba_d_ssm)),
                "out_proj": {"kernel": normal((l, c.mamba_d_ssm, d),
                                              c.mamba_d_ssm)}},
            "attn": {
                "wq": {"kernel": normal((l, d, c.n_head * c.head_dim), d)},
                "wk": {"kernel": normal((l, d, kv), d)},
                "wv": {"kernel": normal((l, d, kv), d)},
                "wo": {"kernel": normal((l, c.n_head * c.head_dim, d),
                                        c.n_head * c.head_dim)}},
            "ln_mlp": scale((l, d)),
            "mlp": {"gate": {"kernel": normal((l, d, f), d)},
                    "up": {"kernel": normal((l, d, f), d)},
                    "down": {"kernel": normal((l, f, d), f)}},
        },
        "ln_f": scale((d,)),
        "lm_head": {"kernel": normal((d, c.vocab_size), d)},
    }


def _ssm_mixer(ssm: Params, s: jnp.ndarray, config: HybridSSMConfig,
               state, li, valid: Optional[jnp.ndarray],
               kernel: Optional[str], lanes=None):
    """The state-space mixer: ``s`` [B, T, d] (normed, times
    ``ssm_in_multiplier``) -> ``(out [B, T, d], state)``. ``state`` is
    ``(matrices, tails)`` of ALL the layers (or ``None``: no cache,
    zeros come in and nothing goes out), ``li`` this layer; ``valid``
    [B, T] marks the positions that count (``None``: all); ``lanes``
    says which rows a single position's kernel streams
    (``gated_delta.live_lanes``; ``None``: all)."""
    c = config
    b, t, _ = s.shape
    h, p, n, g = (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                  c.mamba_n_groups)
    dssm, gn = c.mamba_d_ssm, c.mamba_n_groups * c.mamba_d_state
    m_z, m_x, m_b, m_c, m_dt = c.ssm_multipliers
    with jax.named_scope("ssm_proj"):
        zxbcdt = linear(s, ssm["in_proj"]["kernel"])   # [z | x | B | C | dt]
        z = zxbcdt[..., :dssm].astype(jnp.float32) * m_z
        dt = jax.nn.softplus(
            zxbcdt[..., dssm + c.conv_channels:].astype(jnp.float32) * m_dt
            + ssm["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(ssm["a_log"].astype(jnp.float32))
    with jax.named_scope("ssm_conv"):
        u = zxbcdt[..., dssm:dssm + c.conv_channels]
        if valid is not None:
            u = jnp.where(valid[..., None], u, 0)
            dt = jnp.where(valid[..., None], dt, 0.0)
        if state is None:
            tail = jnp.zeros((b, CONV_TAIL, u.shape[-1]), u.dtype)
        else:
            tail = jax.lax.dynamic_index_in_dim(state[1], li, 0,
                                                keepdims=False)
        # a range's multiplier rides its channels' taps: the tail keeps
        # the projection's own values
        mup = np.repeat(np.asarray([m_x, m_b, m_c], np.float32),
                        [dssm, gn, gn])
        conv, tail = gated_delta.causal_conv(
            u, tail, ssm["conv"]["weight"].astype(jnp.float32)
            * mup[:, None], ssm["conv"]["bias"])
        x = conv[..., :dssm].reshape(b, t, h, p)
        bm = conv[..., dssm:dssm + gn].reshape(b, t, g, n)
        cm = conv[..., dssm + gn:].reshape(b, t, g, n)
    with jax.named_scope("ssm_state"):
        if t == 1 and state is not None:
            y, mats = ssd.step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                               state[0], li, kernel, lanes)
            y = y[:, None]                                   # [B, 1, H, P]
        else:
            s0 = (jnp.zeros((b, h, n, p), jnp.float32) if state is None
                  else jax.lax.dynamic_index_in_dim(state[0], li, 0,
                                                    keepdims=False))
            y, s1 = ssd.chunked(x, dt, a, bm, cm, s0, c.mamba_chunk_size)
            mats = (None if state is None else
                    jax.lax.dynamic_update_index_in_dim(
                        state[0], s1.astype(state[0].dtype), li, 0))
        if state is not None:
            state = (mats, jax.lax.dynamic_update_index_in_dim(
                state[1], tail, li, 0))
        y = y + ssm["d"].astype(jnp.float32)[:, None] * x
    with jax.named_scope("ssm_norm"):
        y = ssd.gated_group_norm(y.reshape(b, t, dssm), z,
                                 ssm["norm"]["scale"], g, c.rms_norm_eps)
    with jax.named_scope("ssm_proj"):
        return linear(y.astype(s.dtype), ssm["out_proj"]["kernel"]), state


def _attention(attn: Params, a: jnp.ndarray, config: HybridSSMConfig,
               cos, sin, kv: Optional[jnp.ndarray], li, offset,
               pad: Optional[jnp.ndarray], kernel: Optional[str]):
    """The softmax mixer: ``a`` [B, T, d] (normed, times
    ``attention_in_multiplier``) -> ``(out, kv)`` over the fused ``[L, B,
    Hkv, S, 2 hd]`` cache."""
    c = config
    t = a.shape[1]
    with jax.named_scope("hybrid_attn"):
        q = split_heads(linear(a, attn["wq"]["kernel"]), c.n_head)
        k = split_heads(linear(a, attn["wk"]["kernel"]), c.n_kv_head)
        k = k * jnp.asarray(c.key_multiplier, k.dtype)
        v = split_heads(linear(a, attn["wv"]["kernel"]), c.n_kv_head)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        if kv is None:
            o = causal_attention(q, k, v, q_offset=0, k_valid_from=pad)
        elif t == 1 and kernel is not None:
            from ..ops.decode_attention import decode_attention
            o, kv = decode_attention(q, k, v, kv, li, offset, pad,
                                     interpret=kernel == "interpret")
        else:
            o, kv = cached_attention_fused(q, k, v, kv, li, offset, pad)
        return linear(merge_heads(o), attn["wo"]["kernel"]), kv


def _ffn(mlp: Params, m: jnp.ndarray, config: HybridSSMConfig):
    """``down_multiplier W_down(W_up m silu(gate_multiplier W_gate m))``,
    float32 out (the caller adds it to the residual)."""
    gate_m, down_m = config.mlp_multipliers
    with jax.named_scope("dense_ffn"):
        gate = jax.nn.silu(
            linear(m, mlp["gate"]["kernel"]).astype(jnp.float32) * gate_m)
        y = linear(m, mlp["up"]["kernel"]) * gate.astype(m.dtype)
        return linear(y, mlp["down"]["kernel"]).astype(jnp.float32) * down_m


def apply_blocks(params: Params, h: jnp.ndarray, config: HybridSSMConfig,
                 cos, sin, cache: Optional[KVCache] = None,
                 pad: Optional[jnp.ndarray] = None,
                 decode_kernel: Optional[str] = None,
                 ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """All the layers: one ``lax.scan`` over them. The cache's leaves
    (every layer's positions, every layer's row state) ride the carry."""
    c = config
    t = h.shape[1]
    offset = 0 if cache is None else cache.length
    kv = None if cache is None else cache.k
    state = None if cache is None else cache.state
    valid = None
    if pad is not None and t > 1:
        valid = (offset + jnp.arange(t))[None, :] >= pad[:, None]
    lanes = gated_delta.live_lanes(pad, offset, t, decode_kernel)

    def layer(carry, xs):
        h, kv, state = carry
        p, li = xs
        u = rms_norm(h, p["ln_attn"]["scale"], c.rms_norm_eps)
        mixed, state = _ssm_mixer(
            p["ssm"], u * jnp.asarray(c.ssm_in_multiplier, u.dtype), c,
            state, li, valid, decode_kernel, lanes)
        seen, kv = _attention(
            p["attn"], u * jnp.asarray(c.attention_in_multiplier, u.dtype),
            c, cos, sin, kv, li, offset, pad, decode_kernel)
        h = (h.astype(jnp.float32)
             + mixed.astype(jnp.float32) * c.ssm_out_multiplier
             + seen.astype(jnp.float32) * c.attention_out_multiplier
             ).astype(h.dtype)
        m = rms_norm(h, p["ln_mlp"]["scale"], c.rms_norm_eps)
        h = (h.astype(jnp.float32) + _ffn(p["mlp"], m, c)).astype(h.dtype)
        return (h, kv, state), None

    (h, kv, state), _ = jax.lax.scan(
        layer, (h, kv, state), (params["blocks"], jnp.arange(c.n_layer)))
    if cache is None:
        return h, None
    new_len = cache.length + jnp.asarray(t, dtype=jnp.int32)
    return h, KVCache(kv, cache.v, new_len, state)


def forward(params: Params, input_ids: jnp.ndarray, config: HybridSSMConfig,
            remat: bool = False, mesh=None) -> jnp.ndarray:
    """Full no-cache forward: [B, S] -> [B, S, vocab] float32 logits
    (the chunked rule from a zero state; ``remat``/``mesh`` accepted for
    the family surface and unused: nothing trains or shards this family
    yet)."""
    return stack.forward(FAMILY, params, input_ids, config)


def forward_with_cache(params: Params, input_ids: jnp.ndarray,
                       config: HybridSSMConfig, cache: KVCache,
                       pad: Optional[jnp.ndarray] = None,
                       flash_prefill: bool = False,
                       decode_kernel: Optional[str] = None,
                       ) -> Tuple[jnp.ndarray, KVCache]:
    """Cached forward at ``cache.length``: a single position through
    the recurrence and the decode kernels where the engine resolved
    them, several through the chunked rule and the masked einsum.
    Returns the LAST position's logits ``[B, 1, vocab]`` whatever the
    call's length (module docstring). ``flash_prefill`` is accepted for
    the family surface and unused."""
    return stack.forward_with_cache(FAMILY, params, input_ids, config, cache,
                                    pad, flash_prefill, decode_kernel)


def make_cache(config: HybridSSMConfig, batch: int, max_seq: int,
               dtype=jnp.float32) -> KVCache:
    """Every layer's fused ``[L, B, Hkv, max_seq, 2 hd]`` rows, the
    fused layout's empty second leaf, and the rows' zeroed state."""
    return stack.make_cache(FAMILY, config, batch, max_seq, dtype)


# It serves through the single-device engine (solo, the iteration
# scheduler, the paged pool of every layer's positions with every
# layer's row state in the state slab, the prefix store) in float32,
# bfloat16 or with int8 weights; what it refuses, one sentence each.
FAMILY = Family(
    name="hybrid_ssm", config_class=HybridSSMConfig,
    frame=stack.Frame(
        apply_blocks, rotary_width=lambda c: c.head_dim,
        embedding_multiplier=lambda c: c.embedding_multiplier,
        logit_multiplier=lambda c: c.lm_head_multiplier,
        last_position_logits=True),
    cache_entry=cache_entry, row_state=row_state,
    bounds_own_reads=True,       # kernel and masked einsum bound their reads
    decode_kernel_eligible=decode_kernel_eligible,
    refuses=(
        ("spec_decode",
         "SPEC_DECODE: a rejected draft cannot be rewound out of "
         "{name}'s per-row state (it has no position axis) without "
         "a snapshot a verify; serve it without speculation"),
        ("kv_pool_dtype",
         "KV_POOL_DTYPE={value}: {name}'s pool holds "
         "fused [K | V] rows in one plane and its rows' state is "
         "float32 by contract; the quantized movers have not been "
         "fitted to either"),
        ("kv_host_blocks",
         "KV_HOST_BLOCKS: a demoted entry of {name} would need its "
         "state snapshot demoted with its blocks; the host tier "
         "moves blocks only"),
        ("multi_chip",
         "PP/TP/EP_DECODE: no multi-chip decoder stages or shards "
         "{name} (a state slab beside the pool in every layer, two "
         "state-space groups to divide); it serves on one chip")))
