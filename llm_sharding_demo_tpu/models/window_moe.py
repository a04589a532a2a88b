"""Sliding-window attention, one full-attention layer in four, a dense
feed-forward first and sparse experts after it.

The K-EXAONE layer plan (the keys of its ``config.json``, ``model_type``
``exaone_moe``) as pure JAX, with the family surface every runtime
module dispatches on (``init_params`` / ``forward`` /
``forward_with_cache`` / ``make_cache``):

- **Two kinds of attention in periods** of ``full_attention_interval``:
  layer ``i`` attends to everything before it iff ``(i + 1) % interval
  == 0``; the others see the last ``sliding_window`` positions (a query
  sees itself and the ``window - 1`` before it). Both project grouped
  queries, keys and values without biases and norm queries and keys per
  head (plain RMSNorm); the SLIDING layers turn them by rotary
  (rotate-half over the whole head, absolute positions), the full
  layers by nothing.
- **What a layer caches differs by kind.** A full layer caches every
  position, in the fused ``[K | V]`` row the decode kernel reads
  (``cache_layers``, ``cache_entry``: the pool holds THESE layers and no
  others). A sliding layer keeps, for each row, a RING of its last
  ``sliding_window`` positions (``ops.sliding_window``) and nothing
  else: ``row_state`` declares it, it rides in ``KVCache.state`` as one
  array ``[Lw, B, Hkv, window, 2 hd]`` (a live row's ring is its lane
  of the batch's working cache), and the state slab
  (``runtime.state_slab``) keeps it with a stored prefix. Whatever a row's depth, a sliding layer holds and reads
  ``window`` positions of it.
- **The first period differs from the others in its feed-forward**:
  the first ``first_k_dense`` layers have a dense SwiGLU, every later
  layer sparse experts (``models.latent_moe.expert_layer`` as it is:
  sigmoid scores over ALL ``n_routed_total``, a selection bias, the top
  ``n_experts_per_tok`` normalised and scaled, the terms of the experts
  HELD here, a shared expert every token takes). So the first period is
  written out on its own leaves (``head``: a list of ``interval``
  trees), and ONE ``lax.scan`` runs over the others, its body a period
  written out: ``interval - 1`` sliding layers, then the full one, the
  leaves a LIST of trees one a place in the period, every leaf ``[P -
  1, ...]`` (``models.gdn_moe`` has the reason). Neither kind of
  feed-forward has leaves where it is not used; the routed experts'
  stacks (``experts`` ``[expert layers, E, ...]``) stay outside the scan
  as loop constants, indexed by layer inside.
- **A call of thousands of positions** holds no ``[heads, T, T]``
  scores: the full layers attend a block of queries at a time, the
  sliding layers compute the band (``ops.sliding_window``).
- **Window independent**: no capacity, no dropped token, and a row's
  ring depends on that row's tokens alone. **A left pad changes
  nothing**: a ring slot is a row's OWN position modulo the window, the
  positions a bucket pads are written nowhere and seen by nobody.

The counters in the cache's second leaf are ``models.latent_moe``'s, by
the same names. The multi-token-prediction module the published model
ships is no part of the next-token pass and is not built here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import sliding_window
from ..ops.attention import (KVCache, merge_heads, split_heads,
                             write_kv_layer_fused)
from ..ops.layers import linear, rms_norm
from ..ops.rope import apply_rope
from . import stack
from .family import Family
from .latent_moe import (CACHE_COUNTERS, INT8_REFUSED, _count, expert_layer,
                         span_labels)
from .llama import pre_norm_block, swiglu

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    """Sizes under the published key names where the runtime does not
    need its own (``n_*`` as in ``LlamaConfig``)."""

    vocab_size: int = 153600
    n_positions: int = 262144
    n_embd: int = 6144
    n_layer: int = 48
    n_head: int = 64
    n_kv_head: int = 8
    head_dim: int = 128
    sliding_window: int = 128
    full_attention_interval: int = 4     # ``LLLG``
    intermediate_size: int = 18432       # the dense layers' width
    moe_intermediate_size: int = 2048
    n_shared_experts: int = 1
    first_k_dense: int = 1
    n_routed_total: int = 128            # what the router scores
    n_routed_experts: int = 128          # held here ...
    first_expert: int = 0                # ... from this id
    n_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    attention_impl: str = "xla"

    @property
    def n_periods(self) -> int:
        return self.n_layer // self.full_attention_interval

    @property
    def n_sliding(self) -> int:          # layers that hold a window
        return self.n_periods * (self.full_attention_interval - 1)

    def __post_init__(self):
        if (self.full_attention_interval < 2
                or self.n_layer % self.full_attention_interval):
            raise ValueError(
                f"n_layer={self.n_layer} must be whole periods of "
                f"full_attention_interval={self.full_attention_interval}")
        if self.n_head % self.n_kv_head:
            raise ValueError("n_head must be a multiple of n_kv_head")
        if self.sliding_window < 1 or self.head_dim % 2:
            raise ValueError("sliding_window must be >= 1 and head_dim even")
        if not 0 <= self.first_k_dense <= self.full_attention_interval:
            raise ValueError("the dense layers lie in the first period: "
                             f"first_k_dense={self.first_k_dense}")
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
    "column": ("periods.attn.wq", "periods.attn.wk", "periods.attn.wv"),
    "row": ("periods.attn.wo",),
    "expert": ("experts.gate", "experts.up", "experts.down"),
    "tp_divisors": ("n_head", "n_kv_head"),
    "kvp_divisors": ("n_kv_head",),
    "ep_divisors": ("n_routed_total",),
}

# Numerics contract (tools/graftcheck numerics pass): the value stream
# carries the engine's dtype; routing and the softmax run in float32
# inside their ops (declared there).
PRECISION_CONTRACT = {
    "forward": {"regime": "carried", "exact": True, "casts": ()},
    "forward_with_cache": {"regime": "carried", "exact": True, "casts": ()},
}

CONFIGS: Dict[str, WindowMoEConfig] = {
    # two periods in the published proportions, a dense first layer,
    # a quarter of the experts held
    "window-moe-tiny": WindowMoEConfig(
        vocab_size=256, n_positions=512, n_embd=64, n_layer=8, n_head=4,
        n_kv_head=2, head_dim=32, sliding_window=8, intermediate_size=96,
        moe_intermediate_size=32, n_routed_total=16, n_routed_experts=4,
        n_experts_per_tok=4),
}


def cache_entry(config: WindowMoEConfig) -> Tuple[int, int, int]:
    """(planes, heads, width) of one position in one CACHED layer, as
    stored: ONE plane of ``n_kv_head`` fused ``[K | V]`` rows
    (``models.gdn_moe.cache_entry`` has the reason)."""
    return (1, config.n_kv_head, 2 * config.head_dim)


def cache_layers(config: WindowMoEConfig) -> int:
    """How many layers cache every position: the full-attention ones."""
    return config.n_periods


def row_state(config: WindowMoEConfig, dtype) -> Tuple[tuple, ...]:
    """What ONE row holds beside its positions, leaf by leaf of
    ``KVCache.state`` with the batch axis left out: the sliding layers'
    rings, ``sliding_window`` fused rows a kv head a layer, in the
    served type."""
    c = config
    return (((c.n_sliding, c.n_kv_head, c.sliding_window, 2 * c.head_dim),
             jnp.dtype(dtype)),)


def decode_kernel_eligible(config: WindowMoEConfig, cache_seq: int) -> bool:
    """Whether a decode step's full layers can run the two-plane Pallas
    kernel here (the sliding layers read their ring in XLA)."""
    from ..ops import decode_attention
    return decode_attention.eligible(cache_seq, config.head_dim, 1)


def window_positions(state, depths) -> Tuple[int, int]:
    """``(held, seen)``: the positions the sliding layers hold for rows
    at ``depths``, and the positions those rows have reached, summed
    (what the scheduler samples as ``window.positions_*``). ``state``
    is where the rows' records live (a working cache's
    ``KVCache.state``): a row's room is the ring axis
    of what is ALLOCATED there, so records sized to a depth would read
    ``held == seen``."""
    room = state[0].shape[-2]
    depths = [max(int(d), 0) for d in depths]
    return (sum(min(d, room) for d in depths), sum(depths))


def prompt_bucket(config: WindowMoEConfig, length: int) -> int:
    """The width a lone prompt of ``length`` positions is left-padded to
    for its prefill (what the iteration scheduler asks a family that
    says so, in place of its multiples of 16): whole windows up to eight
    of them, beyond that whole eighths of the power of two over it
    (1,280, 1,536, ... 4,096, 5,120, ... 8,192), under a quarter of pad.
    This family's prompts run to thousands of positions: a prefill
    program every 16 positions is 512 programs below 8,192, which no
    set-up compiles; this ladder has 20, and a warm-up meets most. A
    left pad is written into no ring and seen by no query."""
    step = max(config.sliding_window, (1 << (length - 1).bit_length()) // 8)
    return -(-length // step) * step


def init_params(config: WindowMoEConfig, key: jax.Array,
                dtype=jnp.float32) -> Params:
    """Random-init parameters in the layout of the module docstring,
    matmul weights under ``.../kernel`` as ``[in, out]``. The selection
    bias is seeded non-zero so that choice and weight differ."""
    c = config
    d, f = c.n_embd, c.moe_intermediate_size
    n = c.full_attention_interval
    keys = iter(jax.random.split(key, 256))

    def normal(shape, fan_in=None, std=None):
        std = std if std is not None else fan_in ** -0.5
        return (jax.random.normal(next(keys), shape) * std).astype(dtype)

    def scale(shape):
        return {"scale": 1.0 + normal(shape, std=0.1)}

    def mlp(lead, width):
        return {"gate": {"kernel": normal(lead + (d, width), d)},
                "up": {"kernel": normal(lead + (d, width), d)},
                "down": {"kernel": normal(lead + (width, d), width)}}

    def layer(lead, dense):
        out = {"ln_attn": scale(lead + (d,)), "ln_mlp": scale(lead + (d,)),
               "attn": {
                   "wq": {"kernel": normal(
                       lead + (d, c.n_head * c.head_dim), d)},
                   "wk": {"kernel": normal(
                       lead + (d, c.n_kv_head * c.head_dim), d)},
                   "wv": {"kernel": normal(
                       lead + (d, c.n_kv_head * c.head_dim), d)},
                   "q_norm": scale(lead + (c.head_dim,)),
                   "k_norm": scale(lead + (c.head_dim,)),
                   "wo": {"kernel": normal(
                       lead + (c.n_head * c.head_dim, d),
                       c.n_head * c.head_dim)}}}
        if dense:
            out["mlp"] = mlp(lead, c.intermediate_size)
        else:
            out["moe"] = {
                "router": {"kernel": normal(lead + (d, c.n_routed_total), d),
                           "bias": normal(lead + (c.n_routed_total,),
                                          std=0.1).astype(jnp.float32)},
                "shared": mlp(lead, f * c.n_shared_experts)}
        return out

    return {
        "wte": normal((c.vocab_size, d), std=1.0),
        "head": [layer((), j < c.first_k_dense) for j in range(n)],
        "periods": [layer((c.n_periods - 1,), False) for _ in range(n)],
        "experts": mlp((c.n_layer - c.first_k_dense, c.n_routed_experts), f),
        "ln_f": scale((d,)),
        "lm_head": {"kernel": normal((d, c.vocab_size), d)},
    }


def _attention(attn: Params, a: jnp.ndarray, config: WindowMoEConfig,
               cos, sin, kept, li, offset, pad: Optional[jnp.ndarray],
               full: bool, fresh: bool, kernel: Optional[str]):
    """One layer's attention on ``a`` [B, T, d] normed -> ``(out [B, T,
    d], kept)``. ``kept`` is what this KIND of layer caches, for all the
    layers of the kind (``li`` this one's index among them): the fused
    ``[Lf, B, Hkv, S, 2 hd]`` positions of the full layers, the ``[Lw,
    B, Hkv, window, 2 hd]`` rings of the sliding ones; ``None``: no
    cache."""
    c = config
    b, t, _ = a.shape
    q = split_heads(linear(a, attn["wq"]["kernel"]), c.n_head)
    k = split_heads(linear(a, attn["wk"]["kernel"]), c.n_kv_head)
    v = split_heads(linear(a, attn["wv"]["kernel"]), c.n_kv_head)
    q = rms_norm(q, attn["q_norm"]["scale"], c.rms_norm_eps)
    k = rms_norm(k, attn["k_norm"]["scale"], c.rms_norm_eps)
    if full:
        if kept is None or fresh:
            # this call's tokens are all there is
            o = sliding_window.blocked_causal_attention(
                q, k, v, k_valid_from=pad)
            if kept is not None:
                kept = write_kv_layer_fused(kept, k, v, li, offset)
        elif t == 1 and kernel is not None:
            from ..ops.decode_attention import decode_attention
            o, kept = decode_attention(q, k, v, kept, li, offset, pad,
                                       interpret=kernel == "interpret")
        else:
            kept = write_kv_layer_fused(kept, k, v, li, offset)
            layer = jax.lax.dynamic_index_in_dim(kept, li, 0, keepdims=False)
            o = sliding_window.blocked_causal_attention(
                q, layer[..., :c.head_dim], layer[..., c.head_dim:],
                q_offset=offset, kv_length=offset + t, k_valid_from=pad)
    else:
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        # the positions each row held before this call, by its own count
        depth = jnp.broadcast_to(offset, (b,)).astype(jnp.int32)
        if pad is not None:
            depth = depth - pad.astype(jnp.int32)
        ring = (jnp.zeros((b, c.n_kv_head, c.sliding_window, 2 * c.head_dim),
                          k.dtype) if kept is None
                else jax.lax.dynamic_index_in_dim(kept, li, 0,
                                                  keepdims=False))
        attend = (sliding_window.ring_decode_attention
                  if t == 1 and kept is not None
                  else sliding_window.ring_banded_attention)
        o, ring = attend(q, k, v, ring, depth)
        if kept is not None:
            kept = jax.lax.dynamic_update_index_in_dim(kept, ring, li, 0)
    return linear(merge_heads(o), attn["wo"]["kernel"]), kept


def apply_blocks(params: Params, h: jnp.ndarray, config: WindowMoEConfig,
                 cos, sin, cache: Optional[KVCache] = None,
                 pad: Optional[jnp.ndarray] = None, fresh: bool = False,
                 decode_kernel: Optional[str] = None,
                 ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """All the layers: the first period on its own leaves, then one
    ``lax.scan`` over the others, a period written out in its body. The
    cache's leaves (positions of the full layers, the rows' rings) ride
    the carry."""
    c = config
    n = c.full_attention_interval
    offset = 0 if cache is None else cache.length
    kv = None if cache is None else cache.k
    rings = None if cache is None else cache.state[0]
    counters = (jnp.zeros((len(CACHE_COUNTERS),), jnp.int32)
                if cache is None else cache.v)
    experts = params["experts"]

    def period(h, kv, rings, places, pi):
        """Period ``pi`` on ``places``, its ``n`` layers' trees.
        Returns the expert layers' counts beside the carry."""
        seen = []
        for j, p in enumerate(places):
            full = j == n - 1
            layer = pi * n + j

            def mixer(a, p=p, full=full, kept=kv if full else rings):
                with jax.named_scope("full_attn" if full else "swa_attn"):
                    return _attention(
                        p["attn"], a, c, cos, sin, kept,
                        pi if full else pi * (n - 1) + j, offset, pad,
                        full, fresh, decode_kernel)

            def feed(m, p=p, layer=layer):
                if "mlp" in p:
                    with jax.named_scope("dense_ffn"):
                        return swiglu(p["mlp"], m)
                out, counts = expert_layer(p["moe"], experts, m, c,
                                           layer - c.first_k_dense,
                                           decode_kernel)
                seen.append(counts)
                return out

            h, kept = pre_norm_block(p, h, c.rms_norm_eps, mixer, feed)
            if full:
                kv = kept
            else:
                rings = kept
        return h, kv, rings, seen

    h, kv, rings, seen = period(h, kv, rings, params["head"], 0)
    counts = [jnp.stack(seen)] if seen else []
    if c.n_periods > 1:
        def body(carry, xs):
            h, kv, rings, seen = period(*carry, *xs)
            return (h, kv, rings), jnp.stack(seen)

        (h, kv, rings), rest = jax.lax.scan(
            body, (h, kv, rings),
            (params["periods"], jnp.arange(1, c.n_periods)))
        counts.append(rest.reshape(-1, rest.shape[-1]))
    counters = _count(counters, jnp.concatenate(counts),
                      h.shape[0] * h.shape[1] * c.n_experts_per_tok)
    if cache is None:
        return h, None
    new_len = cache.length + jnp.asarray(h.shape[1], dtype=jnp.int32)
    return h, KVCache(kv, counters, new_len, (rings,))


def forward(params: Params, input_ids: jnp.ndarray, config: WindowMoEConfig,
            remat: bool = False, mesh=None) -> jnp.ndarray:
    """Full no-cache forward: [B, S] -> [B, S, vocab] float32 logits
    (``remat``/``mesh`` accepted for the family surface and unused:
    nothing trains or shards this family yet)."""
    return stack.forward(FAMILY, params, input_ids, config)


def forward_with_cache(params: Params, input_ids: jnp.ndarray,
                       config: WindowMoEConfig, cache: KVCache,
                       pad: Optional[jnp.ndarray] = None,
                       flash_prefill: bool = False,
                       decode_kernel: Optional[str] = None,
                       ) -> Tuple[jnp.ndarray, KVCache]:
    """Cached forward at ``cache.length``. With ``flash_prefill`` (the
    cache is fresh; a left-pad prefix is masked either way) the full
    layers attend over this call's tokens alone. A single position runs
    the full layers through the decode kernel where the engine resolved
    one and the sliding layers over their ring; a call of several
    computes the band, and the full layers a block of queries at a
    time."""
    return stack.forward_with_cache(FAMILY, params, input_ids, config, cache,
                                    pad, flash_prefill, decode_kernel)


def make_cache(config: WindowMoEConfig, batch: int, max_seq: int,
               dtype=jnp.float32) -> KVCache:
    """The full layers' fused ``[P, B, Hkv, max_seq, 2 hd]`` rows, the
    zeroed counters, and the rows' zeroed rings."""
    return stack.make_cache(FAMILY, config, batch, max_seq, dtype)


# It serves through the single-device engine (solo, the iteration
# scheduler, the paged pool of its full layers with the window records
# in the state slab, the prefix store) in float32 or bfloat16; what it
# refuses, one sentence each.
FAMILY = Family(
    name="window_moe", config_class=WindowMoEConfig,
    frame=stack.Frame(apply_blocks, rotary_width=lambda c: c.head_dim),
    cache_entry=cache_entry, cache_layers=cache_layers, row_state=row_state,
    cache_counters=CACHE_COUNTERS, span_labels=span_labels,
    bounds_own_reads=True,       # kernel, ring and masked einsum bound reads
    fresh_prefill_flag=True,     # a fresh prefill attends its own tokens only
    decode_kernel_eligible=decode_kernel_eligible,
    prompt_bucket=prompt_bucket, window_positions=window_positions,
    refuses=(
        ("spec_decode",
         "SPEC_DECODE: a rejected draft cannot be taken back out of "
         "{name}'s window records (a ring has overwritten what the "
         "draft displaced); serve it without speculation"),
        ("kv_pool_dtype",
         "KV_POOL_DTYPE={value}: {name}'s pool is fused "
         "with counters in its second leaf and its window records "
         "carry the served type; the quantized movers have not been "
         "fitted to either"),
        ("kv_host_blocks",
         "KV_HOST_BLOCKS: a demoted entry of {name} would need its "
         "window records demoted with its blocks; the host tier "
         "moves blocks only"),
        ("multi_chip",
         "PP/TP/EP_DECODE: no multi-chip decoder stages or shards "
         "{name} (a first period unlike the others, a state slab "
         "beside the pool, experts indexed in place); it serves on "
         "one chip, told which experts it holds"),
        ("int8_weights", INT8_REFUSED)))
