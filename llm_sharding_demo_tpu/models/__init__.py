"""Model families and the family registry.

Every family module exposes the same pure-function surface —
``init_params`` / ``forward`` / ``forward_with_cache`` / ``make_cache``
over a stacked-block param pytree — so the runtime (decode engine,
speculative decoding, serving, quantization, checkpointing) dispatches on
the config object alone via ``family_module``.
"""

from __future__ import annotations


def family_module(config):
    """Config dataclass -> the model module implementing it.

    MoEConfig subclasses GPT2Config, so it is tested first; LlamaConfig is
    standalone. Plain GPT2Config is the only family the dense pipeline
    partitioner (parallel.partition) can stage.
    """
    from . import (gdn_moe, gpt2, hybrid_ssm, kda_moe, latent_moe, llama,
                   moe, window_moe)
    if isinstance(config, moe.MoEConfig):
        return moe
    if isinstance(config, hybrid_ssm.HybridSSMConfig):
        return hybrid_ssm
    if isinstance(config, window_moe.WindowMoEConfig):
        return window_moe
    if isinstance(config, gdn_moe.GDNMoEConfig):
        return gdn_moe
    if isinstance(config, kda_moe.KDAMoEConfig):
        return kda_moe
    if isinstance(config, latent_moe.LatentMoEConfig):
        return latent_moe
    if isinstance(config, llama.LlamaConfig):
        return llama
    if isinstance(config, gpt2.GPT2Config):
        return gpt2
    raise TypeError(f"unknown model config type {type(config).__name__}")


def cache_entry(config) -> tuple:
    """``(planes, heads, width)``: what ONE position holds in ONE
    CACHED layer, as the family declares it (``cache_layers`` says how
    many layers those are). The dense families keep two planes (keys,
    values) of ``n_kv_head x head_dim``; a family whose cache is
    something else (``latent_moe`` and ``kda_moe``: one plane of one
    latent vector) says so in its own ``cache_entry``. The paged pool, its movers, the
    prefix store and the byte accounting size themselves from this."""
    declared = getattr(family_module(config), "cache_entry", None)
    if declared is not None:
        return declared(config)
    return (2, getattr(config, "n_kv_head", config.n_head), config.head_dim)


def cache_layers(config) -> int:
    """How many of a model's layers cache positions: all of them unless
    the family says otherwise (``gdn_moe``: the softmax layers, one in
    ``full_attention_interval``; ``window_moe``: the full-attention
    layers, likewise; ``kda_moe``: the latent layers its published
    list names; their other layers hold ``row_state``;
    ``hybrid_ssm`` says all of them, and holds ``row_state`` in all of
    them too)."""
    declared = getattr(family_module(config), "cache_layers", None)
    return config.n_layer if declared is None else declared(config)


def row_state(config, dtype) -> tuple:
    """What one ROW holds beside its cached positions: ``(shape,
    dtype)`` of each leaf of ``KVCache.state``, batch axis left out, or
    ``()`` for the families whose every layer caches positions
    (``gdn_moe`` and ``kda_moe``: the linear-attention matrices and
    convolution tails;
    ``window_moe``: the sliding layers' rings of their last window of
    positions; ``hybrid_ssm``: every layer's state-space matrices and
    convolution tails, beside every layer's positions). The state slab
    (``runtime.state_slab.StateSlab``) sizes itself from this."""
    declared = getattr(family_module(config), "row_state", None)
    return () if declared is None else declared(config, dtype)


def is_partitionable(config) -> bool:
    """True when the reference's GPT-2 stage-shard WIRE topology applies
    to ``config`` (/forward + /forward_b compat endpoints, remote
    dispatch, shard-pod partial restore) — the wire-parity surface stays
    GPT-2-only by design."""
    from . import gpt2, moe
    return (isinstance(config, gpt2.GPT2Config)
            and not isinstance(config, moe.MoEConfig))


def is_stage_partitionable(config) -> bool:
    """True when ``parallel.partition`` can stage this family's tree —
    THE single staging predicate (engine and serving both consult it).
    Dense GPT-2 and llama stage; MoE's expert tree decodes unstaged."""
    from . import llama
    return is_partitionable(config) or isinstance(config, llama.LlamaConfig)


def is_window_independent(config) -> bool:
    """True when a token's routing/logits do not depend on which other
    tokens share its forward window — the property behind every
    byte-exactness contract that replays tokens in different window
    shapes (speculative verify windows, chunked prefill, prefix-cache
    continuations). MoE capacity-factor routing makes tokens compete for
    expert slots within a window, so it is window-DEPENDENT; the dense
    families are independent (``hybrid_ssm`` is one), and so are
    ``latent_moe``, ``gdn_moe``, ``kda_moe`` and ``window_moe``, whose
    routing has no capacity and drops no token."""
    from . import moe
    return not isinstance(config, moe.MoEConfig)
