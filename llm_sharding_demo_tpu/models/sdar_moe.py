"""Grouped-query attention under a block mask, sparse experts in every
layer: a model that generates by diffusion over blocks.

The SDAR layer plan (the keys of its ``config.json``, ``model_type``
``sdar_moe``) as pure JAX, with the family surface every runtime module
dispatches on (``init_params`` / ``forward`` / ``forward_with_cache`` /
``make_cache``). Every layer is the same:

```
a     = rms_norm(h, w_in)
q,k,v = a Wq, a Wk, a Wv             # grouped queries, no bias
q,k   = rope(rms_norm(q, w_qn)), rope(rms_norm(k, w_kn))   # per head
o     = softmax(q k^T / sqrt(hd) + M) v
h     = h + o Wo
m     = rms_norm(h, w_post)
h     = h + sum over the chosen experts HELD HERE of w_e SwiGLU_e(m)
```

- **The mask** ``M`` lets position ``i`` see position ``j`` iff ``j //
  L <= i // L`` (``L = block_length``, positions counted from the row's
  own first token): causal between blocks, bidirectional inside one
  (``ops.block_diffusion``). The prompt is prefilled under the same
  mask, and a forward of one block (``T == L``) gives logits for each of
  its positions, FOR that position (no shift): what the engine's rounds
  (``runtime.engine``) choose candidates from. A longer call hands back
  its last position's logits alone, which nobody reads: a prefill
  yields no token.
- **The experts**: a softmax router over ALL ``n_routed_total``, the
  top ``n_experts_per_tok`` renormalised over the chosen, no shared
  expert; the layer is told which ids it holds (``first_expert``,
  ``n_routed_experts``) and adds its own experts' part only
  (``ops.expert_ffn``), as one chip of an expert-parallel deployment
  does. What the absent experts would add is left out and nothing
  stands in for the exchange.
- **The cache** is one plane of fused ``[K | V]`` rows a kv head
  (``[n_layer, B, Hkv, S, 2 hd]``), the layout the paged pool moves
  block by block, with the counters in its second leaf: the routing
  sums of ``models.latent_moe`` and, behind them, what the rounds count
  (``ops.block_diffusion.COUNTERS``).

How it GENERATES is no part of the layer: ``FAMILY.block_options`` says
the block length, the denoising steps, the transfer rule and the mask
token, and the engine and the iteration scheduler run the rounds.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import block_decode, block_diffusion, expert_ffn
from ..ops.attention import KVCache, merge_heads, split_heads
from ..ops.layers import linear, rms_norm
from ..ops.rope import apply_rope
from . import latent_moe, stack
from .family import Family
from .llama import pre_norm_block

Params = Dict[str, Any]

CACHE_COUNTERS = latent_moe.CACHE_COUNTERS + block_diffusion.COUNTERS
_ROUTING = len(latent_moe.CACHE_COUNTERS)


@dataclasses.dataclass(frozen=True)
class SDARMoEConfig:
    """Sizes under the published key names where the runtime does not
    need its own (``n_*`` as in ``LlamaConfig``), and how the deployment
    generates (the last five; ``block_options``)."""

    vocab_size: int = 151936
    n_positions: int = 32768
    n_embd: int = 2048
    n_layer: int = 48
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    n_routed_total: int = 128            # what the router scores
    n_routed_experts: int = 128          # held here ...
    first_expert: int = 0                # ... from this id
    n_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    attention_impl: str = "xla"
    block_length: int = 4
    denoising_steps: int = 4
    confidence_threshold: float = 0.9
    remasking: str = "low_confidence_dynamic"
    mask_token_id: int = 151669

    def __post_init__(self):
        if self.n_head % self.n_kv_head:
            raise ValueError(f"n_head={self.n_head} not a multiple of "
                             f"n_kv_head={self.n_kv_head}")
        if not (0 <= self.first_expert
                and self.first_expert + self.n_routed_experts
                <= self.n_routed_total):
            raise ValueError(
                f"experts {self.first_expert}..+{self.n_routed_experts} "
                f"are not among the router's {self.n_routed_total}")
        if self.n_experts_per_tok > self.n_routed_total:
            raise ValueError("more experts a token than the router scores")
        if self.attention_impl != "xla":
            raise ValueError(f"attention_impl={self.attention_impl!r}: this "
                             "family has the masked einsum only")
        if self.block_length < 1 or self.denoising_steps < 1:
            raise ValueError("block_length and denoising_steps must be >= 1")
        if self.remasking not in block_diffusion.RULES:
            raise ValueError(f"remasking={self.remasking!r} not one of "
                             f"{block_diffusion.RULES}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id={self.mask_token_id} is no "
                             f"token of a vocabulary of {self.vocab_size}")


CONFIGS: Dict[str, SDARMoEConfig] = {
    "sdar-moe-tiny": SDARMoEConfig(
        vocab_size=256, n_positions=512, n_embd=64, n_layer=3, n_head=4,
        n_kv_head=2, head_dim=16, moe_intermediate_size=32,
        n_routed_total=8, n_routed_experts=4, first_expert=0,
        n_experts_per_tok=2, mask_token_id=255),
}


def block_options(config: SDARMoEConfig) -> block_diffusion.Options:
    return block_diffusion.Options(
        config.block_length, config.denoising_steps,
        config.confidence_threshold, config.remasking, config.mask_token_id)


def cache_entry(config: SDARMoEConfig) -> Tuple[int, int, int]:
    """ONE plane of ``n_kv_head`` fused ``[K | V]`` rows
    (``models.gdn_moe.cache_entry`` says why one plane)."""
    return (1, config.n_kv_head, 2 * config.head_dim)


def decode_kernel_eligible(config: SDARMoEConfig, cache_seq: int) -> bool:
    """The two-plane decode kernels' geometry rule: the cache in whole
    blocks of fused 128-lane rows. A round's forward (``L`` positions
    over the cached ones) is ``ops.block_decode``'s kernel under it;
    prefills and the store's strides keep the masked einsum of
    ``ops.block_diffusion.attend``."""
    return block_decode.eligible(cache_seq, config.head_dim)


def init_params(config: SDARMoEConfig, key: jax.Array,
                dtype=jnp.float32) -> Params:
    """Random-init parameters; stacked ``[n_layer, ...]`` leaves, the
    experts' ``[n_layer, held, ...]`` stacks beside the scanned ones."""
    c = config
    d, l, hd, f = c.n_embd, c.n_layer, c.head_dim, c.moe_intermediate_size
    held = c.n_routed_experts
    keys = jax.random.split(key, 10)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape) * fan_in ** -0.5).astype(dtype)

    return {
        "wte": (jax.random.normal(keys[0], (c.vocab_size, d))).astype(dtype),
        "blocks": {
            "ln_attn": {"scale": jnp.ones((l, d), dtype)},
            "attn": {
                "wq": {"kernel": normal(keys[1], (l, d, c.n_head * hd), d)},
                "wk": {"kernel": normal(keys[2], (l, d, c.n_kv_head * hd),
                                        d)},
                "wv": {"kernel": normal(keys[3], (l, d, c.n_kv_head * hd),
                                        d)},
                "wo": {"kernel": normal(keys[4], (l, c.n_head * hd, d),
                                        c.n_head * hd)},
                "q_norm": {"scale": jnp.ones((l, hd), dtype)},
                "k_norm": {"scale": jnp.ones((l, hd), dtype)},
            },
            "ln_mlp": {"scale": jnp.ones((l, d), dtype)},
            "moe": {"router": {"kernel": normal(
                keys[5], (l, d, c.n_routed_total), d)}},
        },
        "experts": {
            "gate": {"kernel": normal(keys[6], (l, held, d, f), d)},
            "up": {"kernel": normal(keys[7], (l, held, d, f), d)},
            "down": {"kernel": normal(keys[8], (l, held, f, d), f)},
        },
        "ln_f": {"scale": jnp.ones((d,), dtype)},
        "lm_head": {"kernel": normal(keys[9], (d, c.vocab_size), d)},
    }


def _attention(attn: Params, a: jnp.ndarray, config: SDARMoEConfig,
               cos, sin, kv: Optional[jnp.ndarray], li, offset,
               pad: Optional[jnp.ndarray], fresh: bool,
               kernel: Optional[str] = None):
    """``a`` [B, T, d] normed -> ``(out, kv)`` over the fused cache;
    ``kernel`` what the engine resolved (``block_diffusion.attend``)."""
    c = config
    with jax.named_scope("block_attn"):
        q = split_heads(linear(a, attn["wq"]["kernel"]), c.n_head)
        k = split_heads(linear(a, attn["wk"]["kernel"]), c.n_kv_head)
        v = split_heads(linear(a, attn["wv"]["kernel"]), c.n_kv_head)
        q = apply_rope(rms_norm(q, attn["q_norm"]["scale"], c.rms_norm_eps),
                       cos, sin)
        k = apply_rope(rms_norm(k, attn["k_norm"]["scale"], c.rms_norm_eps),
                       cos, sin)
        o, kv = block_diffusion.attend(q, k, v, c.block_length, kv, li,
                                       offset, pad, fresh, kernel)
        return linear(merge_heads(o), attn["wo"]["kernel"]), kv


def expert_layer(router: jnp.ndarray, experts: Params, m: jnp.ndarray,
                 config: SDARMoEConfig, layer_idx,
                 kernel: Optional[str] = None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The feed-forward on ``m`` [B, T, d] normed: the held experts'
    weighted terms and nothing else. ``experts`` holds the WHOLE
    ``[n_layer, held, ...]`` stacks (indexed inside, so that only chosen
    experts are read), ``kernel`` what the engine resolved
    (``ops.expert_ffn``). Returns ``(out, counts [held])``."""
    c = config
    b, t, d = m.shape
    x = m.reshape(b * t, d)
    with jax.named_scope("moe_router"):
        ids, w = expert_ffn.route_softmax(x, router, c.n_experts_per_tok,
                                          c.norm_topk_prob)
    with jax.named_scope("moe_experts"):
        y, counts = expert_ffn.held_experts_ffn(
            x, ids, w, experts["gate"]["kernel"], experts["up"]["kernel"],
            experts["down"]["kernel"], layer_idx, c.first_expert, kernel)
    return y.reshape(b, t, d), counts


def apply_blocks(params: Params, h: jnp.ndarray, config: SDARMoEConfig,
                 cos, sin, cache: Optional[KVCache] = None,
                 pad: Optional[jnp.ndarray] = None, fresh: bool = False,
                 decode_kernel: Optional[str] = None,
                 ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """All the layers, one ``lax.scan``; the cache rides the carry, the
    experts' stacks stay outside the scanned leaves, as loop constants.
    ``decode_kernel`` is what the engine resolved: under it the held
    experts' tiles run as ``ops.expert_ffn``'s kernel, and the attention
    of a forward of ONE block over the cached ones as
    ``ops.block_decode``'s (``block_diffusion.attend`` says which calls
    those are, from their shapes)."""
    c = config
    offset = 0 if cache is None else cache.length
    kv = None if cache is None else cache.k
    experts = params["experts"]

    def layer(carry, xs):
        h, kv = carry
        p, li = xs
        seen = []

        def ffn(m):
            out, counts = expert_layer(p["moe"]["router"]["kernel"], experts,
                                       m, c, li, decode_kernel)
            seen.append(counts)
            return out

        h, kv = pre_norm_block(
            p, h, c.rms_norm_eps,
            lambda a: _attention(p["attn"], a, c, cos, sin, kv, li, offset,
                                 pad, fresh, decode_kernel), ffn)
        return (h, kv), seen[0]

    (h, kv), counts = jax.lax.scan(
        layer, (h, kv), (params["blocks"], jnp.arange(c.n_layer)))
    if cache is None:
        return h, None
    routing = latent_moe._count(
        cache.v[:_ROUTING], counts,
        h.shape[0] * h.shape[1] * c.n_experts_per_tok)
    new_len = cache.length + jnp.asarray(h.shape[1], dtype=jnp.int32)
    return h, KVCache(kv, jnp.concatenate([routing, cache.v[_ROUTING:]]),
                      new_len)


def forward(params: Params, input_ids: jnp.ndarray, config: SDARMoEConfig,
            remat: bool = False, mesh=None) -> jnp.ndarray:
    """Full no-cache forward under the block mask: [B, S] -> [B, S,
    vocab] float32 logits, each position's FOR that position
    (``remat``/``mesh`` accepted for the family surface and unused)."""
    return stack.forward(FAMILY, params, input_ids, config)


def forward_with_cache(params: Params, input_ids: jnp.ndarray,
                       config: SDARMoEConfig, cache: KVCache,
                       pad: Optional[jnp.ndarray] = None,
                       flash_prefill: bool = False,
                       decode_kernel: Optional[str] = None,
                       ) -> Tuple[jnp.ndarray, KVCache]:
    """Cached forward of whole blocks at ``cache.length`` (a block
    boundary of every row). A call of ONE block hands back the logits of
    each of its positions (a round's forward); a longer one (a prefill,
    a stride of the prefix store) its last position's alone.
    ``flash_prefill`` is the engine's static word that the cache is
    fresh: nothing cached is read then; ``decode_kernel`` what it
    resolved (``"device"``, ``"interpret"`` or ``None``)."""
    c = config
    t = input_ids.shape[1]
    if decode_kernel not in (None, "device", "interpret"):
        raise ValueError(f"decode_kernel={decode_kernel!r}: this family "
                         "has the per-layer kernels only")
    if t % c.block_length:
        raise ValueError(f"a cached call forwards whole blocks of "
                         f"{c.block_length}, got {t} positions")
    h = stack.embed(params, input_ids)
    cos, sin = stack.angles(c.head_dim, c.rope_theta, t, cache.length, pad)
    h, cache = apply_blocks(params, h, c, cos, sin, cache, pad,
                            fresh=flash_prefill, decode_kernel=decode_kernel)
    if t != c.block_length:
        h = h[:, -1:]
    return stack.head(params, h, c.rms_norm_eps), cache


def make_cache(config: SDARMoEConfig, batch: int, max_seq: int,
               dtype=jnp.float32) -> KVCache:
    """The fused ``[n_layer, B, Hkv, max_seq, 2 hd]`` rows and the
    zeroed counters."""
    return stack.make_cache(FAMILY, config, batch, max_seq, dtype)


# What a family that generates by blocks refuses beside what every
# sparse-expert family here refuses: a draft-verify loop, a quantized
# pool and a host tier have not met a step that yields a block.
REFUSES = (
    ("spec_decode",
     "SPEC_DECODE: {name} generates by rounds of a whole block; a "
     "draft-verify loop over single tokens has no place in a round; "
     "serve it without speculation"),
    ("kv_pool_dtype",
     "KV_POOL_DTYPE={value}: {name}'s pool is one plane with counters "
     "in its second leaf, and a round reads the block it is writing; "
     "the quantized movers have not been fitted to it"),
    ("kv_host_blocks",
     "KV_HOST_BLOCKS: no deployment of {name} has needed the host "
     "tier yet and none has been checked against a demoted block "
     "boundary; serve it from the device pool"),
    ("multi_chip",
     "PP/TP/EP_DECODE: no multi-chip decoder stages or shards {name} "
     "(a round's forwards are one program's loop, experts indexed in "
     "place); it serves on one chip, told which experts it holds"),
    ("int8_weights", latent_moe.INT8_REFUSED))

FAMILY = Family(
    name="sdar_moe", config_class=SDARMoEConfig,
    frame=stack.Frame(apply_blocks, rotary_width=lambda c: c.head_dim),
    cache_entry=cache_entry,
    cache_counters=CACHE_COUNTERS, span_labels=latent_moe.span_labels,
    bounds_own_reads=True,       # the masked einsum bounds its own reads
    fresh_prefill_flag=True,
    decode_kernel_eligible=decode_kernel_eligible,
    block_options=block_options,
    refuses=REFUSES)
