"""Latent attention over one cached vector a position, then sparse experts.

The DeepSeek-V3 layer plan (the keys of its ``config.json``) as pure
JAX, with the family surface every runtime module dispatches on
(``init_params`` / ``forward`` / ``forward_with_cache`` / ``make_cache``):

- **Latent attention** (``ops.latent_attention``): queries through a
  ``q_lora_rank`` bottleneck with its own RMSNorm (``None``: one
  projection, ``wq``, and no bottleneck); keys and values
  through a ``kv_lora_rank`` latent, normalised, plus one rotary key of
  ``qk_rope_head_dim`` shared by all heads. THE CACHE HOLDS ``[c_kv |
  k_pe]`` AND NOTHING ELSE: one plane, one "head", ``kv_lora_rank +
  qk_rope_head_dim`` values a position a layer, stored in a row
  rounded up to the chip's lane tile (``cache_lanes``: 576 -> 640, the
  rest zeros; ``cache_entry``), which the paged pool, its movers and
  the prefix store take from here. A
  prefill into a fresh cache attends in the expanded form; anything
  that reads the cache (a decode step, a continuation chunk) in the
  absorbed form, whose reads are bounded by the live depth inside the
  program (``bounds_own_reads``: the engine cuts no windows for it); a
  decode step on a TPU runs it as a Pallas kernel (``ops.
  latent_decode``) that streams the row's vectors block by block. The
  rotary parts are turned pair by pair where they lie (``ops.rope.
  rotate_pairs``: the published layout is interleaved), queries and
  keys alike in every form, so ``k_pe`` is stored in the order the
  weights give it. ``mla_use_nope`` leaves every rotation out: the
  "rotary" dimensions are then plain dimensions of the one shared key
  (``models.kda_moe``, whose other layers carry the order).
- **A single position has its own forms** where a cheaper one exists,
  chosen by the shapes a call brings (a decode step is ``[B, 1]``):
  the folds into and out of the latent as one matmul each against the
  leaves as they lie (``ops.latent_attention``), the router's top k by
  rank and one tile an expert over all the rows (``ops.expert_ffn``).
  Same arithmetic, a third fewer device operations a layer: a decode
  step is forty layers of nine weight streams and a kernel, and what
  it paid beside them was launches and copies (PERF.md 6, PR 31).
- **Two kinds of layer in one stack**: ``first_k_dense`` leading layers
  with a dense SwiGLU, then expert layers: sigmoid-scored top-k routing
  over ALL ``n_routed_total`` experts with a selection bias, plus a
  shared expert every token takes. Each kind is one ``lax.scan`` over
  its own stacked leaves (the expert layers' counts come out of the
  scan stacked and are summed into the counters once a forward); both
  blocks are ``llama.pre_norm_block`` with another mixer and
  feed-forward, and the norm, SwiGLU, embedding and head are llama's.
- **The layer is told which experts it holds**: ``n_routed_experts``
  consecutive ids from ``first_expert`` of the ``n_routed_total`` the
  router scores. It computes its own experts' terms and leaves the
  others out (``ops.expert_ffn``); that partial result goes on to the
  next layer. With every expert held this is the whole model; with a
  sixteenth it is what one chip of a 16-way expert-parallel deployment
  computes before the exchange, which is not run here.
- **Window independent**: no capacity, no dropped token, so a row's
  result is the same whatever shares its batch or its chunk, and the
  iteration scheduler, the prefix store and joins apply.

The cache pytree is ``KVCache(k=latent [L, B, 1, Smax, lanes],
v=counters [len(CACHE_COUNTERS)] int32, length)``. The second leaf is
not values (a latent is its own value): like the fused layout's
placeholder it is one-dimensional, so every merger and mover passes it
through, and it carries the routing counters of the forwards the cache
has been through since it was made: a segment program zeroes it on
entry, so what comes back beside a segment's tokens is that segment's
sums.

The multi-token-prediction module some checkpoints of this plan ship
is no part of the next-token forward pass and is not built here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import expert_ffn, latent_attention
from ..ops.attention import KVCache
from ..ops.layers import linear, rms_norm
from ..ops.rope import pair_angles, rotate_pairs
from . import stack
from .family import Family
from .llama import pre_norm_block, swiglu

Params = Dict[str, Any]

# the grouped matmul (``ops.expert_ffn``) indexes plain stacks: what
# every family built on it says to INFERENCE_DTYPE=int8
INT8_REFUSED = ("INFERENCE_DTYPE=int8: {name} indexes its experts' plain "
                "weight stacks; it serves float32 or bfloat16")

# the cache's second leaf, in this order (all int32, summed over the
# expert layers of every forward since the leaf was zeroed)
CACHE_COUNTERS = ("experts_hit",     # distinct held experts some token chose
                  "pairs_here",      # (token, choice) pairs on held experts
                  "pairs_routed",    # all (token, choice) pairs
                  "load_max",        # pairs on the fullest held expert
                  "layer_forwards")  # expert-layer forwards counted


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    """Sizes under the published key names where the runtime does not
    need its own (``n_*`` as in ``LlamaConfig``)."""

    vocab_size: int = 129280
    n_positions: int = 131072
    n_embd: int = 2048
    n_layer: int = 40
    n_head: int = 32
    q_lora_rank: Optional[int] = 1536    # None: no query bottleneck
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 7168        # the leading dense layers
    moe_intermediate_size: int = 768     # every expert, the shared one too
    first_k_dense: int = 1
    n_routed_total: int = 256            # what the router scores
    n_routed_experts: int = 256          # held here ...
    first_expert: int = 0                # ... from this id
    n_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    mla_use_nope: bool = False           # True: nothing is rotated
    attention_impl: str = "xla"

    @property
    def head_dim(self) -> int:           # of a query/key; scores scale by it
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:        # the values a position holds
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_lanes(self) -> int:
        """The row a position is STORED in: ``cache_width`` rounded up
        to the chip's 128-lane tile (576 -> 640; widths under one tile,
        the test sizes, stay as they are), the rest zeros. A 576-wide
        bfloat16 row occupies 640 lanes on the chip whatever its shape
        says; left implicit, XLA stores such arrays transposed to save
        the padding and converts them, a cache-sized temporary each
        way, in every program that reads rows (measured: 4.7 GB for one
        gather of 16 rows, which does not fit beside the weights)."""
        w = self.cache_width
        return w if w < 128 else -(-w // 128) * 128

    def __post_init__(self):
        if not 0 < self.first_k_dense < self.n_layer:
            raise ValueError(
                f"first_k_dense={self.first_k_dense} must leave both kinds "
                f"of layer among n_layer={self.n_layer}")
        if self.first_expert < 0 or (self.first_expert + self.n_routed_experts
                                     > self.n_routed_total):
            raise ValueError(
                f"held experts [{self.first_expert}, {self.first_expert} + "
                f"{self.n_routed_experts}) lie outside the router's "
                f"{self.n_routed_total}")
        if self.n_experts_per_tok > self.n_routed_total:
            raise ValueError("n_experts_per_tok exceeds n_routed_total")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        if self.attention_impl != "xla":
            raise ValueError("latent attention runs attention_impl='xla'")


# Static-analysis/planner contract (tools/graftcheck/costmodel): see
# ``models.gpt2.SHARDING_DESCRIPTOR``. The held experts' stacks are the
# expert-parallel leaves; no mesh decoder runs this family yet, so the
# divisor lists name what such a split would have to divide.
SHARDING_DESCRIPTOR = {
    "column": ("blocks.attn.wuq", "blocks.attn.wuk", "blocks.attn.wuv",
               "blocks.moe.shared.gate", "blocks.moe.shared.up"),
    "row": ("blocks.attn.wo", "blocks.moe.shared.down"),
    "expert": ("blocks.moe.experts.gate", "blocks.moe.experts.up",
               "blocks.moe.experts.down"),
    "tp_divisors": ("n_head",),
    "kvp_divisors": (),
    "ep_divisors": ("n_routed_total",),
}

# Numerics contract (tools/graftcheck numerics pass): the value stream
# carries the engine's dtype; routing and softmax run in float32 inside
# their ops (declared there).
PRECISION_CONTRACT = {
    "forward": {"regime": "carried", "exact": True, "casts": ()},
    "forward_with_cache": {"regime": "carried", "exact": True, "casts": ()},
}

CONFIGS: Dict[str, LatentMoEConfig] = {
    # widths in the published proportions, every expert held
    "latent-moe-tiny": LatentMoEConfig(
        vocab_size=256, n_positions=512, n_embd=64, n_layer=4, n_head=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=224,
        moe_intermediate_size=24, first_k_dense=1, n_routed_total=16,
        n_routed_experts=16, n_experts_per_tok=4),
}


def span_labels(counters: Dict[str, int], config: LatentMoEConfig,
                prefill: bool) -> Dict[str, float]:
    """What a span says of the counters its program handed back: a
    decode segment its sums under their own names; a prefill the pairs
    on the fullest held expert and on the average one, a layer."""
    if not prefill:
        return {k: counters[k]
                for k in ("experts_hit", "pairs_here", "pairs_routed")}
    layers = max(counters["layer_forwards"], 1)
    return {"expert_load_max": round(counters["load_max"] / layers, 3),
            "expert_load_mean": round(
                counters["pairs_here"] / config.n_routed_experts / layers,
                3)}


def cache_entry(config: LatentMoEConfig) -> Tuple[int, int, int]:
    """(planes, heads, width) of one position in one layer's cache, as
    stored: one plane, one "head", ``cache_lanes`` wide, of which
    ``cache_width`` are ``[c_kv | k_pe]`` and the rest zeros."""
    return (1, 1, config.cache_lanes)


def decode_kernel_eligible(config: LatentMoEConfig, cache_seq: int) -> bool:
    """Whether the family's own Pallas decode kernel (``ops.
    latent_decode``) applies at this cache length: the engine asks this
    in place of the two-plane kernels' geometry rule, and keeps
    ``make_cache``'s layout either way."""
    from ..ops import latent_decode
    return latent_decode.eligible(cache_seq)


def init_params(config: LatentMoEConfig, key: jax.Array,
                dtype=jnp.float32) -> Params:
    """Random-init parameters: two stacks of block leaves (``dense``
    ``[first_k_dense, ...]`` and ``blocks`` ``[n_layer - first_k_dense,
    ...]``), matmul weights under ``.../kernel`` as ``[in, out]``; the
    experts' as ``[layers, experts, in, out]``. The selection bias is
    seeded non-zero so that choice and weight differ."""
    c = config
    d, h, f = c.n_embd, c.n_head, c.moe_intermediate_size
    n_dense, n_moe = c.first_k_dense, c.n_layer - c.first_k_dense
    keys = iter(jax.random.split(key, 32))

    def normal(shape, fan_in=None, std=None):
        std = std if std is not None else fan_in ** -0.5
        return (jax.random.normal(next(keys), shape) * std).astype(dtype)

    def queries(l):
        if c.q_lora_rank is None:
            return {"wq": {"kernel": normal((l, d, h * c.head_dim), d)}}
        return {
            "wdq": {"kernel": normal((l, d, c.q_lora_rank), d)},
            "q_norm": {"scale": jnp.ones((l, c.q_lora_rank), dtype)},
            "wuq": {"kernel": normal((l, c.q_lora_rank, h * c.head_dim),
                                     c.q_lora_rank)}}

    def attn(l):
        return {
            **queries(l),
            "wdkv": {"kernel": normal((l, d, c.cache_width), d)},
            "kv_norm": {"scale": jnp.ones((l, c.kv_lora_rank), dtype)},
            "wuk": {"kernel": normal(
                (l, c.kv_lora_rank, h * c.qk_nope_head_dim), c.kv_lora_rank)},
            "wuv": {"kernel": normal(
                (l, c.kv_lora_rank, h * c.v_head_dim), c.kv_lora_rank)},
            "wo": {"kernel": normal((l, h * c.v_head_dim, d),
                                    h * c.v_head_dim)},
        }

    def mlp(lead, width):
        return {"gate": {"kernel": normal(lead + (d, width), d)},
                "up": {"kernel": normal(lead + (d, width), d)},
                "down": {"kernel": normal(lead + (width, d), width)}}

    def norms(l):
        return {"ln_attn": {"scale": jnp.ones((l, d), dtype)},
                "ln_mlp": {"scale": jnp.ones((l, d), dtype)}}

    return {
        "wte": normal((c.vocab_size, d), std=1.0),
        "dense": {**norms(n_dense), "attn": attn(n_dense),
                  "mlp": mlp((n_dense,), c.intermediate_size)},
        "blocks": {
            **norms(n_moe), "attn": attn(n_moe),
            "moe": {
                "router": {
                    "kernel": normal((n_moe, d, c.n_routed_total), d),
                    "bias": (jax.random.normal(
                        next(keys), (n_moe, c.n_routed_total)) * 0.1
                    ).astype(jnp.float32)},
                "shared": mlp((n_moe,), f * c.n_shared_experts),
                "experts": mlp((n_moe, c.n_routed_experts), f),
            },
        },
        "ln_f": {"scale": jnp.ones((d,), dtype)},
        "lm_head": {"kernel": normal((d, c.vocab_size), d)},
    }


def _attention(attn: Params, a: jnp.ndarray, config: LatentMoEConfig,
               cos, sin, cache: Optional[jnp.ndarray], layer_idx, offset,
               pad: Optional[jnp.ndarray], fresh: bool,
               decode_kernel: Optional[str] = None):
    """The mixer: ``a`` [B, S, d] normed -> ``(out [B, S, d], cache)``.
    ``config`` is any family's that has this mixer's sizes under these
    names; with ``mla_use_nope`` the angles are not read."""
    c = config
    b, s, _ = a.shape
    with jax.named_scope("latent_attn"):
        if c.q_lora_rank is None:
            q = linear(a, attn["wq"]["kernel"])
        else:
            c_q = rms_norm(linear(a, attn["wdq"]["kernel"]),
                           attn["q_norm"]["scale"], c.rms_norm_eps)
            q = linear(c_q, attn["wuq"]["kernel"])
        q = q.reshape(b, s, c.n_head, c.head_dim).transpose(0, 2, 1, 3)
        q_nope, q_pe = (q[..., :c.qk_nope_head_dim],
                        q[..., c.qk_nope_head_dim:])
        if not c.mla_use_nope:
            q_pe = rotate_pairs(q_pe, cos, sin)
        down = linear(a, attn["wdkv"]["kernel"])
        c_kv = rms_norm(down[..., :c.kv_lora_rank],
                        attn["kv_norm"]["scale"], c.rms_norm_eps)
        k_pe = down[..., c.kv_lora_rank:]
        if not c.mla_use_nope:
            k_pe = rotate_pairs(k_pe[:, None], cos, sin)[:, 0]
        wuk, wuv = attn["wuk"]["kernel"], attn["wuv"]["kernel"]
        if cache is not None:
            # the row's lanes past ``cache_width`` are zeros since
            # ``make_cache`` (and the pool's planes) and stay so: this
            # is the only writer
            cache = latent_attention.write_latent(
                cache, jnp.concatenate([c_kv, k_pe], axis=-1), layer_idx,
                offset)
        if cache is None or fresh:
            o = latent_attention.expanded(q_nope, q_pe, c_kv, k_pe, wuk,
                                          wuv, pad)
        else:
            o = latent_attention.absorbed(q_nope, q_pe, cache, layer_idx,
                                          offset, wuk, wuv, pad,
                                          decode_kernel)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return linear(o, attn["wo"]["kernel"]), cache


def expert_layer(moe: Params, experts: Params, m: jnp.ndarray,
                 config: LatentMoEConfig, layer_idx,
                 kernel: Optional[str] = None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The expert feed-forward on ``m`` [B, S, d] normed: the held
    experts' weighted terms plus the shared expert. ``moe`` holds this
    layer's router and shared expert, ``experts`` the WHOLE ``[layers,
    experts, ...]`` stacks (indexed inside, so that only chosen experts
    are read), ``kernel`` what the engine resolved (the held experts'
    tiles as ``ops.expert_ffn``'s kernel, or its loop for ``None``).
    Returns ``(out, counts [n_routed_experts])``."""
    c = config
    b, s, d = m.shape
    x = m.reshape(b * s, d)
    with jax.named_scope("moe_router"):
        ids, w = expert_ffn.route(
            x, moe["router"]["kernel"], moe["router"]["bias"],
            c.n_experts_per_tok, c.routed_scaling_factor, c.norm_topk_prob)
    with jax.named_scope("moe_experts"):
        y, counts = expert_ffn.held_experts_ffn(
            x, ids, w, experts["gate"]["kernel"], experts["up"]["kernel"],
            experts["down"]["kernel"], layer_idx, c.first_expert, kernel)
    with jax.named_scope("moe_shared"):
        y = y + swiglu(moe["shared"], x)
    return y.reshape(b, s, d), counts


def _count(counters: jnp.ndarray, counts: jnp.ndarray, pairs: int):
    """``counts`` [layers, held]: what one forward's expert layers gave
    their held experts, added to ``CACHE_COUNTERS`` once a forward."""
    return counters + jnp.stack([
        jnp.sum(counts > 0), jnp.sum(counts),
        jnp.asarray(pairs * counts.shape[0]),
        jnp.sum(jnp.max(counts, axis=1)),
        jnp.asarray(counts.shape[0])]).astype(counters.dtype)


def apply_blocks(params: Params, h: jnp.ndarray, config: LatentMoEConfig,
                 cos, sin, cache: Optional[KVCache] = None,
                 pad: Optional[jnp.ndarray] = None, fresh: bool = False,
                 decode_kernel: Optional[str] = None,
                 ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Both stacks, each one ``lax.scan`` over its kind of layer. The
    cache (and its counters) ride the carry; the experts' stacks stay
    outside the scanned leaves, as loop constants."""
    c = config
    offset = 0 if cache is None else cache.length
    latent = None if cache is None else cache.k
    counters = (jnp.zeros((len(CACHE_COUNTERS),), jnp.int32)
                if cache is None else cache.v)
    moe_blocks = dict(params["blocks"])
    moe = dict(moe_blocks.pop("moe"))
    experts = moe.pop("experts")
    moe_blocks["moe"] = moe
    pairs = h.shape[0] * h.shape[1] * c.n_experts_per_tok

    def layer(carry, xs, first_layer, ffn):
        h, latent = carry
        p, li = xs
        seen = []

        def mixer(a):
            return _attention(p["attn"], a, c, cos, sin, latent,
                              li + first_layer, offset, pad, fresh,
                              decode_kernel)

        def feed(m):
            out, counts = ffn(p, m, li)
            seen.append(counts)
            return out

        h, latent = pre_norm_block(p, h, c.rms_norm_eps, mixer, feed)
        return (h, latent), seen[0]

    def dense_ffn(p, m, li):
        return swiglu(p["mlp"], m), None

    def expert_ffn_(p, m, li):
        return expert_layer(p["moe"], experts, m, c, li, decode_kernel)

    carry = (h, latent)
    for stack, first_layer, ffn in (
            (params["dense"], 0, dense_ffn),
            (moe_blocks, c.first_k_dense, expert_ffn_)):
        n = jax.tree_util.tree_leaves(stack)[0].shape[0]
        carry, counts = jax.lax.scan(
            lambda cr, xs, fl=first_layer, f=ffn: layer(cr, xs, fl, f),
            carry, (stack, jnp.arange(n)))
        if counts is not None:
            counters = _count(counters, counts, pairs)
    h, latent = carry
    if cache is None:
        return h, None
    new_len = cache.length + jnp.asarray(h.shape[1], dtype=jnp.int32)
    return h, KVCache(latent, counters, new_len)


def forward(params: Params, input_ids: jnp.ndarray, config: LatentMoEConfig,
            remat: bool = False, mesh=None) -> jnp.ndarray:
    """Full no-cache forward: [B, S] -> [B, S, vocab] float32 logits
    (expanded attention; ``remat``/``mesh`` accepted for the family
    surface and unused: nothing trains or shards this family yet)."""
    return stack.forward(FAMILY, params, input_ids, config)


def forward_with_cache(params: Params, input_ids: jnp.ndarray,
                       config: LatentMoEConfig, cache: KVCache,
                       pad: Optional[jnp.ndarray] = None,
                       flash_prefill: bool = False,
                       decode_kernel: Optional[str] = None,
                       ) -> Tuple[jnp.ndarray, KVCache]:
    """Cached forward at ``cache.length``. With ``flash_prefill`` (the
    cache is fresh; a left-pad prefix is masked either way) the
    expanded form attends over this call's tokens alone. Everything
    else attends over the cache in the absorbed form, a single position
    through the Pallas kernel where the engine resolved one."""
    return stack.forward_with_cache(FAMILY, params, input_ids, config, cache,
                                    pad, flash_prefill, decode_kernel)


def make_cache(config: LatentMoEConfig, batch: int, max_seq: int,
               dtype=jnp.float32) -> KVCache:
    """``[L, B, 1, max_seq, cache_lanes]`` latents (``kv_lora_rank +
    qk_rope_head_dim`` values a position, lane-aligned) and the zeroed
    counters."""
    return stack.make_cache(FAMILY, config, batch, max_seq, dtype)


# It serves through the single-device engine (solo, either batcher, the
# paged pool, the prefix store) in float32 or bfloat16; what it refuses,
# one sentence each, instead of a wrong answer further down.
FAMILY = Family(
    name="latent_moe", config_class=LatentMoEConfig,
    frame=stack.Frame(apply_blocks,
                      rotary_width=lambda c: c.qk_rope_head_dim,
                      angle_table=pair_angles),
    cache_entry=cache_entry,
    cache_counters=CACHE_COUNTERS, span_labels=span_labels,
    bounds_own_reads=True,       # absorbed attention bounds its reads by depth
    fresh_prefill_flag=True,     # wants to know a prefill's cache is fresh
    decode_kernel_eligible=decode_kernel_eligible,
    refuses=(
        ("kv_pool_dtype",
         "KV_POOL_DTYPE={value}: {name}'s pool holds one "
         "latent vector a position; the quantized movers scale per "
         "kv-head and have not been fitted to it"),
        ("kv_host_blocks",
         "KV_HOST_BLOCKS: the host tier has not been run over "
         "{name}'s one-plane pool"),
        ("spec_decode",
         "SPEC_DECODE: the verify loop's rewind leaves {name}'s "
         "routing counters and cached latents of rejected drafts "
         "untested; serve it without speculation"),
        ("multi_chip",
         "PP/TP/EP_DECODE: no multi-chip decoder stages or shards "
         "{name} (two stacks of unlike layers, experts indexed in "
         "place); it serves on one chip, told which experts it "
         "holds"),
        ("int8_weights", INT8_REFUSED)))
