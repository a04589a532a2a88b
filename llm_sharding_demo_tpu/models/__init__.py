"""Model families and the family registry.

Every family module exposes the same pure-function surface —
``init_params`` / ``forward`` / ``forward_with_cache`` / ``make_cache``
over a stacked-block param pytree — and states what else the rest of
the program may ask of it in ONE ``FAMILY = family.Family(...)``: its
cache's shape, what the engine and the scheduler need to know, the
serving options it refuses. ``REGISTRY`` finds that declaration from a
config object's type, so the runtime (decode engine, speculative
decoding, serving, quantization, checkpointing) dispatches on the config
alone. Adding a family is its module and one entry of ``FAMILIES``.
"""

from __future__ import annotations

from . import (gdn_moe, gpt2, hybrid_ssm, kda_moe, latent_moe, llama, moe,
               sdar_moe, window_moe)
from .family import Family

FAMILIES = (gpt2.FAMILY, moe.FAMILY, llama.FAMILY, latent_moe.FAMILY,
            gdn_moe.FAMILY, window_moe.FAMILY, hybrid_ssm.FAMILY,
            kda_moe.FAMILY, sdar_moe.FAMILY)
REGISTRY = {f.config_class: f for f in FAMILIES}


def family_of(config) -> Family:
    """Config dataclass -> its family's declaration: the most derived
    registered class of the config's type (``MoEConfig`` subclasses
    ``GPT2Config`` and is found first)."""
    for cls in type(config).__mro__:
        if cls in REGISTRY:
            return REGISTRY[cls]
    raise TypeError(f"unknown model config type {type(config).__name__}")


def family_named(name: str) -> Family:
    """The checkpoint tag (``Family.name``) -> the declaration."""
    for family in REGISTRY.values():
        if family.name == name:
            return family
    raise ValueError(f"unknown checkpoint model family {name!r}")


def family_module(config):
    """Config dataclass -> the model module implementing it."""
    return family_of(config).module


def cache_entry(config) -> tuple:
    """``(planes, heads, width)``: what ONE position holds in ONE
    CACHED layer, as the family declares it (``cache_layers`` says how
    many layers those are). The dense families keep two planes (keys,
    values) of ``n_kv_head x head_dim``; a family whose cache is
    something else (``latent_moe`` and ``kda_moe``: one plane of one
    latent vector) says so in its own ``cache_entry``. The paged pool, its movers, the
    prefix store and the byte accounting size themselves from this."""
    return family_of(config).cache_entry(config)


def cache_layers(config) -> int:
    """How many of a model's layers cache positions: all of them unless
    the family says otherwise (``gdn_moe``: the softmax layers, one in
    ``full_attention_interval``; ``window_moe``: the full-attention
    layers, likewise; ``kda_moe``: the latent layers its published
    list names; their other layers hold ``row_state``;
    ``hybrid_ssm`` says all of them, and holds ``row_state`` in all of
    them too)."""
    return family_of(config).cache_layers(config)


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
    return family_of(config).row_state(config, dtype)


def is_partitionable(config) -> bool:
    """True when the reference's GPT-2 stage-shard WIRE topology applies
    to ``config`` (/forward + /forward_b compat endpoints, remote
    dispatch, shard-pod partial restore) — the wire-parity surface stays
    GPT-2-only by design."""
    return family_of(config).wire_topology


def is_stage_partitionable(config) -> bool:
    """True when ``parallel.partition`` can stage this family's tree —
    THE single staging predicate (engine and serving both consult it).
    Dense GPT-2 and llama stage; MoE's expert tree decodes unstaged."""
    return family_of(config).stageable


def is_window_independent(config) -> bool:
    """True when a token's routing/logits do not depend on which other
    tokens share its forward window — the property behind every
    byte-exactness contract that replays tokens in different window
    shapes (speculative verify windows, chunked prefill, prefix-cache
    continuations). MoE capacity-factor routing makes tokens compete for
    expert slots within a window, so it is window-DEPENDENT; the dense
    families are independent (``hybrid_ssm`` is one), and so are
    ``latent_moe``, ``gdn_moe``, ``kda_moe``, ``window_moe`` and
    ``sdar_moe``, whose routing has no capacity and drops no token
    (``sdar_moe``'s windows are whole blocks: a position sees its
    block-mates, and every caller forwards whole blocks)."""
    return family_of(config).window_independent
