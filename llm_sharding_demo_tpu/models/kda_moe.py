"""A delta rule gated per key channel in three layers of four, latent
attention without positions in the fourth, sparse experts behind both.

The Kimi-Linear layer plan (the keys of its ``config.json``; the rule
is Kimi Delta Attention, arXiv:2510.26692) as pure JAX, with the family
surface every runtime module dispatches on (``init_params`` /
``forward`` / ``forward_with_cache`` / ``make_cache``). Two things the
repo has, each changed, in ONE stack:

- **The layer kinds come from two published LISTS** (``kda_layers``,
  ``full_attn_layers``, 1-based), not from an interval, and the first
  ``first_k_dense`` layers have a dense SwiGLU where the others have
  experts. ``layer_plan`` covers the stack with REPEATS OF RUNS of
  layers so that the fewest layers are written out (the published 27,
  ``K`` a delta-rule layer, ``M`` a latent one, ``'`` a dense
  feed-forward: ``K'`` once, ``K K M K`` six times, ``K M`` once:
  seven layers' worth of program where the cut at every latent layer,
  ``K' K K M`` / ``K K K M`` x 5 / ``K K M``, is eleven; a program of
  this family is compiled a prompt width, a tail width and a batch
  width, some 150 a minute of traffic, and what is written out is what
  each costs). A group is ONE ``lax.scan`` over its repeats with the
  run written out in the body, and its leaves are stacked accordingly:
  ``groups[g]`` is a LIST of trees, one a place in the run, every leaf
  ``[repeats, ...]``, so that what the scan slices out for an iteration
  is one layer's matrix, read once by its own matmul (``models.gdn_moe``
  says what the other layout cost). A layer of either kind is one
  jitted function of its own leaves (``_layer``), so that a program
  traces each KIND once however often the plan writes it out. The routed experts' stacks
  (``experts`` ``[expert layers, E, ...]``) stay outside the scans as
  loop constants, indexed by layer inside, so that only chosen experts
  are read.
- **Delta-rule layers** (``ops.kda``): ``[q | k | v]`` from one
  projection and ``[f | g | b]``, the two low-rank gates' first halves
  and ``beta``'s logits, from another (the published checkpoint has a
  matrix each: fixed concatenations of columns, made once when a
  checkpoint is loaded; the blocks are whole lane tiles but ``b``, the
  last); a depthwise causal convolution of width 4 and SiLU over ``[q |
  k | v]``; unit-length ``q`` and ``k``; ``g = -exp(A_log) softplus(f
  W_fb + dt_bias)``, one decay a head a key CHANNEL; the rule over a
  float32 state ``[K, V]`` a head; per-head RMSNorm of the read-out
  times ``sigmoid(g W_gb)``; the output projection. What a layer CACHES
  belongs to the row: that state and the last 3 inputs of the
  convolution (``row_state``; ``KVCache.state`` is ``(matrices [Lk, B,
  H, K, V] float32, tails [Lk, B, 3, C])``). A single position runs the
  recurrence (on a TPU the Pallas kernel that streams the state once),
  a call of several the chunked form.
- **Latent layers**: ``models.latent_moe._attention`` with no query
  bottleneck (``q_lora_rank`` ``None``) and NO rotation
  (``mla_use_nope``): the model has no positional encoding anywhere,
  the delta-rule layers carry the order. ONLY these layers cache
  positions (``cache_layers``): one plane, one "head", ``[c_kv | k_r]``
  in a lane-aligned row (``cache_entry``), as that family's pool holds
  it; a prefill into a fresh cache attends in the expanded form
  (``fresh_prefill_flag``), everything else in the absorbed one, a
  decode step on a TPU through ``ops.latent_decode``'s kernel.
- **Experts**: ``models.latent_moe.expert_layer`` (sigmoid scores over
  ALL ``n_routed_total``, a selection bias, renormalised and scaled;
  the terms of the ``n_routed_experts`` ids from ``first_expert`` this
  chip HOLDS, plus a shared expert). The counters in the cache's second
  leaf are that family's, by the same names.
- **Window independent**, and **a left pad changes nothing**: padded
  positions get a zero input to the convolution, ``beta = 0`` and ``g =
  0``, so state and tail after the pad are those of position 0; the
  latent layers mask them.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import gated_delta, kda
from ..ops.attention import KVCache
from ..ops.layers import linear
from . import gdn_moe, stack
from .family import Family
from .latent_moe import (CACHE_COUNTERS, _attention, _count, expert_layer,
                         span_labels)
from .llama import pre_norm_block, swiglu

Params = Dict[str, Any]

CONV_TAIL = 3                # carried inputs of a width-4 convolution

KDA, MLA = "kda", "mla"


@dataclasses.dataclass(frozen=True)
class KDAMoEConfig:
    """Sizes under the published key names where the runtime does not
    need its own (``n_*`` as in ``LlamaConfig``); ``from_published``
    takes the nested ``linear_attn_config`` group as it is published."""

    vocab_size: int = 163840
    n_positions: int = 1048576
    n_embd: int = 2304
    n_layer: int = 27
    n_head: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15,
                                   17, 18, 19, 21, 22, 23, 25, 26)
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    linear_num_heads: int = 32
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    intermediate_size: int = 9216        # the leading dense layers
    moe_intermediate_size: int = 1024    # every expert, the shared one too
    first_k_dense: int = 1
    n_routed_total: int = 256            # what the router scores
    n_routed_experts: int = 256          # held here ...
    first_expert: int = 0                # ... from this id
    n_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    attention_impl: str = "xla"

    @classmethod
    def from_published(cls, linear_attn_config: dict, **sizes):
        la = linear_attn_config
        return cls(kda_layers=tuple(la["kda_layers"]),
                   full_attn_layers=tuple(la["full_attn_layers"]),
                   linear_num_heads=la["num_heads"],
                   linear_head_dim=la["head_dim"],
                   short_conv_kernel_size=la["short_conv_kernel_size"],
                   **sizes)

    @property
    def head_dim(self) -> int:           # of a latent layer's query/key
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:        # the values a position holds
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_lanes(self) -> int:        # ... in a lane-aligned row
        w = self.cache_width             # (``LatentMoEConfig.cache_lanes``)
        return w if w < 128 else -(-w // 128) * 128

    @property
    def gate_rank(self) -> int:          # the low-rank gates' width
        return self.linear_head_dim

    @property
    def conv_channels(self) -> int:      # [q | k | v] of a delta-rule layer
        return 3 * self.linear_num_heads * self.linear_head_dim

    @property
    def n_kda(self) -> int:
        return len(self.kda_layers)

    @property
    def n_mla(self) -> int:
        return len(self.full_attn_layers)

    def __post_init__(self):
        kda_l, mla_l = tuple(self.kda_layers), tuple(self.full_attn_layers)
        object.__setattr__(self, "kda_layers", kda_l)
        object.__setattr__(self, "full_attn_layers", mla_l)
        if sorted(kda_l + mla_l) != list(range(1, self.n_layer + 1)):
            raise ValueError(
                "kda_layers and full_attn_layers (1-based) must name every "
                f"one of the {self.n_layer} layers once")
        if not 0 < self.first_k_dense < self.n_layer:
            raise ValueError(
                f"first_k_dense={self.first_k_dense} must leave both kinds "
                f"of feed-forward among n_layer={self.n_layer}")
        if self.short_conv_kernel_size != CONV_TAIL + 1:
            raise ValueError("the carried tail is built for a "
                             f"convolution of width {CONV_TAIL + 1}")
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


@dataclasses.dataclass(frozen=True)
class Group:
    """``count`` repeats of one run of layers: ``kinds`` and ``dense`` a
    place in the run, and the index of the group's first layer among
    all layers, the delta-rule ones and the latent ones."""
    kinds: Tuple[str, ...]
    dense: Tuple[bool, ...]
    count: int
    first: int
    first_kda: int
    first_mla: int


@functools.lru_cache(maxsize=None)
def layer_plan(config: KDAMoEConfig) -> Tuple[Group, ...]:
    """The stack as repeats of runs, the cut that WRITES OUT the fewest
    layers (module docstring): ``best[i]`` is the cheapest cover of the
    layers from ``i`` on, a run of ``p`` layers repeated ``r`` times
    costing ``p``; of equal covers the one in fewer groups, of those the
    first met going by ``p`` and then ``r`` (the benchmark's reference
    lays the same tree out by the same rule, on its own)."""
    mla, n = set(config.full_attn_layers), config.n_layer
    make = [(MLA if layer + 1 in mla else KDA, layer < config.first_k_dense)
            for layer in range(n)]
    best = {n: (0, 0, ())}
    for i in range(n - 1, -1, -1):
        covers = []
        for p in range(1, n - i + 1):
            r = 1
            while make[i + r * p:i + (r + 1) * p] == make[i:i + p]:
                r += 1
            for reps in range(1, r + 1):
                cost, groups, rest = best[i + reps * p]
                covers.append((cost + p, groups + 1, ((i, p, reps),) + rest))
        best[i] = min(covers, key=lambda c: c[:2])   # the first of equals
    groups = []
    for first, p, reps in best[0][2]:
        kinds, dense = zip(*make[first:first + p])
        groups.append(Group(
            kinds, dense, reps, first,
            sum(k == KDA for k, _ in make[:first]),
            sum(k == MLA for k, _ in make[:first])))
    return tuple(groups)


# Static-analysis/planner contract (tools/graftcheck/costmodel): see
# ``models.gpt2.SHARDING_DESCRIPTOR``. No mesh decoder runs this family;
# the lists name what such a split would have to divide.
SHARDING_DESCRIPTOR = {
    "column": ("groups.attn.in_qkv", "groups.attn.wq", "groups.attn.wuk",
               "groups.attn.wuv"),
    "row": ("groups.attn.wo",),
    "expert": ("experts.gate", "experts.up", "experts.down"),
    "tp_divisors": ("n_head", "linear_num_heads"),
    "kvp_divisors": (),
    "ep_divisors": ("n_routed_total",),
}

# Numerics contract (tools/graftcheck numerics pass): the value stream
# carries the engine's dtype; routing, the softmax and the delta rule
# run in float32 inside their ops (declared there).
PRECISION_CONTRACT = {
    "forward": {"regime": "carried", "exact": True, "casts": ()},
    "forward_with_cache": {"regime": "carried", "exact": True, "casts": ()},
}

CONFIGS: Dict[str, KDAMoEConfig] = {
    # K' K M K K M K K M K M: the dense layer, a run K M K three times
    # (a scan of three), an irregular tail M; every expert held
    "kda-moe-tiny": KDAMoEConfig(
        vocab_size=256, n_positions=512, n_embd=64, n_layer=11, n_head=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, kda_layers=(1, 2, 4, 5, 7, 8, 10),
        full_attn_layers=(3, 6, 9, 11), linear_num_heads=4,
        linear_head_dim=16, intermediate_size=224, moe_intermediate_size=24,
        n_routed_total=16, n_routed_experts=16, n_experts_per_tok=4),
}


def cache_entry(config: KDAMoEConfig) -> Tuple[int, int, int]:
    """(planes, heads, width) of one position in one CACHED layer, as
    stored: one plane, one "head", ``cache_lanes`` wide, of which
    ``cache_width`` are ``[c_kv | k_r]`` and the rest zeros."""
    return (1, 1, config.cache_lanes)


def cache_layers(config: KDAMoEConfig) -> int:
    """How many layers cache positions: the latent ones."""
    return config.n_mla


def row_state(config: KDAMoEConfig, dtype) -> Tuple[tuple, ...]:
    """What ONE row holds beside its positions, leaf by leaf of
    ``KVCache.state`` with the batch axis left out: ``(shape, dtype)``.
    The matrices in float32 as the published code carries them, the
    convolution tails in the served type."""
    c = config
    return (((c.n_kda, c.linear_num_heads, c.linear_head_dim,
              c.linear_head_dim), jnp.dtype(jnp.float32)),
            ((c.n_kda, CONV_TAIL, c.conv_channels), jnp.dtype(dtype)))


def decode_kernel_eligible(config: KDAMoEConfig, cache_seq: int) -> bool:
    """Whether a decode step can run its two Pallas kernels here: whole
    blocks of cache for the latent one, whole lane tiles of state for
    the compiled delta-rule one."""
    from ..ops import latent_decode
    return (latent_decode.eligible(cache_seq)
            and (jax.default_backend() != "tpu"
                 or gated_delta.kernel_eligible(
                     config.linear_head_dim, config.linear_head_dim,
                     config.linear_num_heads)))


def prompt_bucket(config: KDAMoEConfig, length: int) -> int:
    """The width a lone prompt of ``length`` positions is left-padded to
    for its prefill (what the iteration scheduler asks a family that
    says so, in place of its multiples of 16): whole chunks of the rule
    up to eight of them, beyond that whole eighths of the power of two
    over it (640, 768, ... 1,024, 1,280, ...), under a quarter of pad
    past one chunk. A prefill program here is seven layers written out
    (``layer_plan``) and takes the compiler twice what a program of one
    scanned period does: a program every 16 positions is 33 of them for
    a minute of traffic, this ladder has 14 below 1,536. A left pad
    changes nothing (module docstring)."""
    step = max(kda.CHUNK, (1 << (length - 1).bit_length()) // 8)
    return -(-length // step) * step


def init_params(config: KDAMoEConfig, key: jax.Array,
                dtype=jnp.float32) -> Params:
    """Random-init parameters in the layout of the module docstring,
    matmul weights under ``.../kernel`` as ``[in, out]``. The selection
    bias is seeded non-zero so that choice and weight differ;
    ``a_log`` (a head) and ``dt_bias`` (a channel) so that a position's
    decay ``exp(g)`` spans roughly 0.5 to 0.999."""
    c = config
    d, f = c.n_embd, c.moe_intermediate_size
    h, hd = c.linear_num_heads, c.linear_head_dim
    r = c.gate_rank
    keys = iter(jax.random.split(key, 40 * c.n_layer + 16))

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

    def mixer(kind, n):
        if kind == MLA:
            return {
                "wq": {"kernel": normal((n, d, c.n_head * c.head_dim), d)},
                "wdkv": {"kernel": normal((n, d, c.cache_width), d)},
                "kv_norm": {"scale": jnp.ones((n, c.kv_lora_rank), dtype)},
                "wuk": {"kernel": normal(
                    (n, c.kv_lora_rank, c.n_head * c.qk_nope_head_dim),
                    c.kv_lora_rank)},
                "wuv": {"kernel": normal(
                    (n, c.kv_lora_rank, c.n_head * c.v_head_dim),
                    c.kv_lora_rank)},
                "wo": {"kernel": normal((n, c.n_head * c.v_head_dim, d),
                                        c.n_head * c.v_head_dim)}}
        return {
            "in_qkv": {"kernel": normal((n, d, 3 * h * hd), d)},
            "in_low": {"kernel": normal((n, d, 2 * r + h), d)},
            "conv": {"weight": normal(
                (n, c.conv_channels, c.short_conv_kernel_size),
                c.short_conv_kernel_size)},
            "f_b": {"kernel": normal((n, r, h * hd), r)},
            "g_b": {"kernel": normal((n, r, h * hd), r)},
            "a_log": uniform((n, h), -1.4, 0.7),
            "dt_bias": uniform((n, h * hd), -4.0, -1.0),
            "norm": {"scale": 1.0 + normal((n, hd), std=0.1)},
            "wo": {"kernel": normal((n, h * hd, d), h * hd)}}

    def layer(kind, dense, n):
        tree = {"ln_attn": {"scale": jnp.ones((n, d), dtype)},
                "ln_mlp": {"scale": jnp.ones((n, d), dtype)},
                "attn": mixer(kind, n)}
        if dense:
            tree["mlp"] = mlp((n,), c.intermediate_size)
        else:
            tree["moe"] = {
                "router": {
                    "kernel": normal((n, d, c.n_routed_total), d),
                    "bias": (jax.random.normal(
                        next(keys), (n, c.n_routed_total)) * 0.1
                    ).astype(jnp.float32)},
                "shared": mlp((n,), f * c.n_shared_experts)}
        return tree

    return {
        "wte": normal((c.vocab_size, d), std=1.0),
        "groups": [[layer(kind, dense, g.count)
                    for kind, dense in zip(g.kinds, g.dense)]
                   for g in layer_plan(c)],
        "experts": mlp((c.n_layer - c.first_k_dense, c.n_routed_experts), f),
        "ln_f": {"scale": jnp.ones((d,), dtype)},
        "lm_head": {"kernel": normal((d, c.vocab_size), d)},
    }


def _delta_attention(attn: Params, a: jnp.ndarray, config: KDAMoEConfig,
                     state, li, valid: Optional[jnp.ndarray],
                     kernel: Optional[str], lanes=None):
    """The delta-rule mixer: ``a`` [B, T, d] normed -> ``(out [B, T, d],
    state)``. ``state`` is ``(matrices, tails)`` of ALL the delta-rule
    layers (or ``None``: no cache, zeros come in and nothing goes out),
    ``li`` this layer's index among them; ``valid`` [B, T] marks the
    positions that count (``None``: all); ``lanes`` says which rows a
    single position's kernel streams (``gated_delta.live_lanes``;
    ``None``: all)."""
    c = config
    b, t, _ = a.shape
    h, hd, r = c.linear_num_heads, c.linear_head_dim, c.gate_rank
    with jax.named_scope("kda_proj"):
        u = linear(a, attn["in_qkv"]["kernel"])          # [q | k | v]
        low = linear(a, attn["in_low"]["kernel"])        # [f | g | b]
        f = linear(low[..., :r], attn["f_b"]["kernel"]).reshape(b, t, h, hd)
        gate = linear(low[..., r:2 * r], attn["g_b"]["kernel"])
        g, beta = gated_delta.gates(
            f, low[..., 2 * r:], attn["a_log"][:, None],
            attn["dt_bias"].reshape(h, hd))
    with jax.named_scope("kda_conv"):
        if valid is not None:
            u = jnp.where(valid[..., None], u, 0)
            g = jnp.where(valid[..., None, None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        if state is None:
            tail = jnp.zeros((b, CONV_TAIL, u.shape[-1]), u.dtype)
        else:
            tail = jax.lax.dynamic_index_in_dim(state[1], li, 0,
                                                keepdims=False)
        conv, tail = gated_delta.causal_conv(u, tail,
                                             attn["conv"]["weight"])
        q = gated_delta.l2norm(
            conv[..., :h * hd].reshape(b, t, h, hd)) * hd ** -0.5
        k = gated_delta.l2norm(
            conv[..., h * hd:2 * h * hd].reshape(b, t, h, hd))
        v = conv[..., 2 * h * hd:].reshape(b, t, h, hd)
    with jax.named_scope("kda_state"):
        if t == 1 and state is not None:
            o, mats = kda.step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                               beta[:, 0], state[0], li, kernel, lanes)
            o = o[:, None]                                   # [B, 1, H, V]
        else:
            s0 = (jnp.zeros((b, h, hd, hd), jnp.float32) if state is None
                  else jax.lax.dynamic_index_in_dim(state[0], li, 0,
                                                    keepdims=False))
            o, s1 = kda.chunked(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), g.transpose(0, 2, 1, 3),
                beta.transpose(0, 2, 1), s0)
            o = o.transpose(0, 2, 1, 3)
            mats = (None if state is None else
                    jax.lax.dynamic_update_index_in_dim(
                        state[0], s1.astype(state[0].dtype), li, 0))
        if state is not None:
            state = (mats, jax.lax.dynamic_update_index_in_dim(
                state[1], tail, li, 0))
    with jax.named_scope("kda_proj"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + c.rms_norm_eps)
        o = (o * attn["norm"]["scale"].astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32)).reshape(o.shape)
             ).astype(a.dtype)
        return linear(o.reshape(b, t, h * hd), attn["wo"]["kernel"]), state


@functools.partial(jax.jit,
                   static_argnames=("config", "kind", "fresh", "kernel"))
def _layer(p: Params, experts: Params, h, held, li, expert_layer_idx,
           offset, pad, valid, lanes, *, config: KDAMoEConfig, kind: str,
           fresh: bool, kernel: Optional[str]):
    """One layer of either kind on its own leaves ``p``: ``held`` is what
    its mixer carries (the rows' state for a delta-rule layer, the
    latent cache for a latent one), ``li`` its index among its kind.
    Returns ``(h, held, counts)``, ``counts`` ``None`` behind a dense
    feed-forward. Jitted, so that a program traces each kind once
    however often the plan writes it out."""
    seen = []

    def mixer(a):
        if kind == KDA:
            return _delta_attention(p["attn"], a, config, held, li, valid,
                                    kernel, lanes)
        return _attention(p["attn"], a, config, None, None, held, li,
                          offset, pad, fresh, kernel)

    def feed(m):
        if "mlp" in p:
            return swiglu(p["mlp"], m)
        out, counts = expert_layer(p["moe"], experts, m, config,
                                   expert_layer_idx, kernel)
        seen.append(counts)
        return out

    h, held = pre_norm_block(p, h, config.rms_norm_eps, mixer, feed)
    return h, held, (seen[0] if seen else None)


def apply_blocks(params: Params, h: jnp.ndarray, config: KDAMoEConfig,
                 cache: Optional[KVCache] = None,
                 pad: Optional[jnp.ndarray] = None, fresh: bool = False,
                 decode_kernel: Optional[str] = None,
                 ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """All the layers: one ``lax.scan`` a group of ``layer_plan`` over
    its repeats, the run written out in the body. The cache's leaves
    (the latent layers' positions, the rows' state) ride the carry."""
    c = config
    t = h.shape[1]
    offset = 0 if cache is None else cache.length
    latent = None if cache is None else cache.k
    state = None if cache is None else cache.state
    counters = (jnp.zeros((len(CACHE_COUNTERS),), jnp.int32)
                if cache is None else cache.v)
    experts = params["experts"]
    valid = None
    if pad is not None and t > 1:
        valid = (offset + jnp.arange(t))[None, :] >= pad[:, None]
    lanes = gated_delta.live_lanes(pad, offset, t, decode_kernel)

    def run(group: Group):
        def body(carry, xs):
            h, latent, state = carry
            places, rep = xs
            seen = []
            at = {kind: first + rep * group.kinds.count(kind)
                  for kind, first in ((KDA, group.first_kda),
                                      (MLA, group.first_mla))}
            held = {KDA: state, MLA: latent}
            for j, (p, kind) in enumerate(zip(places, group.kinds)):
                e = group.first + rep * len(places) + j - c.first_k_dense
                h, held[kind], counts = _layer(
                    p, experts, h, held[kind], at[kind], e, offset, pad,
                    valid, lanes, config=c, kind=kind, fresh=fresh,
                    kernel=decode_kernel)
                at[kind] = at[kind] + 1
                if counts is not None:
                    seen.append(counts)
            state, latent = held[KDA], held[MLA]
            # (a run of dense layers alone hands back no counts)
            return (h, latent, state), (
                jnp.stack(seen) if seen
                else jnp.zeros((0, c.n_routed_experts), jnp.int32))
        return body

    carry, counts = (h, latent, state), []
    for group, places in zip(layer_plan(c), params["groups"]):
        carry, seen = jax.lax.scan(run(group), carry,
                                   (places, jnp.arange(group.count)))
        counts.append(seen.reshape(-1, seen.shape[-1]))
    h, latent, state = carry
    counters = _count(counters, jnp.concatenate(counts),
                      h.shape[0] * t * c.n_experts_per_tok)
    if cache is None:
        return h, None
    new_len = cache.length + jnp.asarray(t, dtype=jnp.int32)
    return h, KVCache(latent, counters, new_len, state)


def forward(params: Params, input_ids: jnp.ndarray, config: KDAMoEConfig,
            remat: bool = False, mesh=None) -> jnp.ndarray:
    """Full no-cache forward: [B, S] -> [B, S, vocab] float32 logits
    (the chunked rule from a zero state, expanded attention;
    ``remat``/``mesh`` accepted for the family surface and unused:
    nothing trains or shards this family yet)."""
    return stack.forward(FAMILY, params, input_ids, config)


def forward_with_cache(params: Params, input_ids: jnp.ndarray,
                       config: KDAMoEConfig, cache: KVCache,
                       pad: Optional[jnp.ndarray] = None,
                       flash_prefill: bool = False,
                       decode_kernel: Optional[str] = None,
                       ) -> Tuple[jnp.ndarray, KVCache]:
    """Cached forward at ``cache.length``. With ``flash_prefill`` (the
    cache is fresh) the latent layers attend in the expanded form over
    this call's tokens alone; everything else reads the cache in the
    absorbed form. A single position goes through the recurrence and
    the two decode kernels where the engine resolved them, several
    through the chunked rule."""
    return stack.forward_with_cache(FAMILY, params, input_ids, config, cache,
                                    pad, flash_prefill, decode_kernel)


def make_cache(config: KDAMoEConfig, batch: int, max_seq: int,
               dtype=jnp.float32) -> KVCache:
    """The latent layers' ``[Lm, B, 1, max_seq, cache_lanes]`` rows, the
    zeroed counters, and the rows' zeroed state."""
    return stack.make_cache(FAMILY, config, batch, max_seq, dtype)


FAMILY = Family(
    name="kda_moe", config_class=KDAMoEConfig,
    frame=stack.Frame(apply_blocks),    # nothing is turned by position
    cache_entry=cache_entry, cache_layers=cache_layers, row_state=row_state,
    cache_counters=CACHE_COUNTERS, span_labels=span_labels,
    bounds_own_reads=True,       # absorbed attention bounds its reads by depth
    fresh_prefill_flag=True,     # wants to know a prefill's cache is fresh
    decode_kernel_eligible=decode_kernel_eligible,
    prompt_bucket=prompt_bucket,
    # per-row state without a position axis beside a one-plane pool, as
    # ``models.gdn_moe`` has it
    refuses=gdn_moe.REFUSES)
