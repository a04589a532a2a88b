"""The frame the RMSNorm families share: embed, angles, blocks, head.

What differs between two families is their blocks (``apply_blocks``:
the mixers, the feed-forwards, how the layers are looped) and a handful
of constants around them, which a family states as the ``Frame`` of
its declaration (``models.family.Family.frame``). The
frame itself, the refusals at its door and the cache a family's
declaration (``models.family.Family``) describes are written here once.
Everything is decided while tracing, from the frame and the leaves'
types: a family without a multiplier multiplies by nothing.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from ..ops.attention import KVCache
from ..ops.layers import linear, rms_norm
from ..ops.quant import embed_rows, is_quantized
from ..ops.rope import rope_angles

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Frame:
    """How the shared frame runs one family's blocks."""

    # ``(params, h, config, cos, sin, cache=None, pad=None, *,
    # decode_kernel=None[, fresh=False]) -> (h, cache)``; without
    # ``rotary_width`` it takes no ``cos, sin``, and ``fresh`` is passed
    # to a family whose declaration sets ``fresh_prefill_flag``
    apply_blocks: Callable
    # ``config -> int``: the width the frame's angles turn, or ``None``
    # for a family that rotates nothing by position
    rotary_width: Optional[Callable[[Any], int]] = None
    # ``(positions, width, theta) -> (cos, sin)``
    angle_table: Callable = rope_angles
    norm: Callable = rms_norm            # the final norm (its epsilon is
    #                                      the config's ``rms_norm_eps``)
    # ``config -> float`` on the embeddings and on the logits
    embedding_multiplier: Optional[Callable[[Any], float]] = None
    logit_multiplier: Optional[Callable[[Any], float]] = None
    # a cached call of several positions hands back the LAST one's
    # logits alone, ``[B, 1, vocab]``
    last_position_logits: bool = False


def embed(params: Params, input_ids: jnp.ndarray) -> jnp.ndarray:
    wte = params["wte"]
    if is_quantized(wte):
        return embed_rows(wte, input_ids)
    return wte[input_ids]


def angles(rotary_width: int, theta: float, seq_len: int, offset,
           pad: Optional[jnp.ndarray], table: Callable = rope_angles):
    """(cos, sin) for positions ``offset + arange(S)`` (per-row shifted
    down by ``pad`` for left-padded ragged batches; pad columns clip to
    position 0 — masked as keys, never read as outputs)."""
    pos = offset + jnp.arange(seq_len)
    if pad is not None:
        pos = jnp.maximum(pos[None, :] - pad[:, None], 0)   # [B, S]
    return table(pos, rotary_width, theta)


def head(params: Params, h: jnp.ndarray, eps: float, norm: Callable = rms_norm,
         multiplier: Optional[float] = None) -> jnp.ndarray:
    """Final norm, then float32 logits off the untied head."""
    h = norm(h, params["ln_f"]["scale"], eps)
    kernel = params["lm_head"]["kernel"]
    if is_quantized(kernel):
        logits = linear(h, kernel).astype(jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, kernel,
                            preferred_element_type=jnp.float32)
    return logits if multiplier is None else logits * multiplier


def _start(frame: Frame, params: Params, input_ids, config, offset, pad):
    """Embeddings, and the angles where the family turns any."""
    h = embed(params, input_ids)
    if frame.embedding_multiplier is not None:
        h = (h.astype(jnp.float32) * frame.embedding_multiplier(config)
             ).astype(h.dtype)
    if frame.rotary_width is None:
        return h, ()
    return h, angles(frame.rotary_width(config), config.rope_theta,
                     input_ids.shape[1], offset, pad, frame.angle_table)


def _logits(frame: Frame, params: Params, h, config):
    return head(params, h, config.rms_norm_eps, frame.norm,
                None if frame.logit_multiplier is None
                else frame.logit_multiplier(config))


def forward(family, params: Params, input_ids: jnp.ndarray,
            config) -> jnp.ndarray:
    """Full no-cache forward: [B, S] -> [B, S, vocab] float32 logits."""
    frame = family.frame
    h, turned = _start(frame, params, input_ids, config, 0, None)
    h, _ = frame.apply_blocks(params, h, config, *turned)
    return _logits(frame, params, h, config)


def forward_with_cache(family, params: Params, input_ids: jnp.ndarray,
                       config, cache: KVCache, pad: Optional[jnp.ndarray],
                       flash_prefill: bool, decode_kernel: Optional[str],
                       ) -> Tuple[jnp.ndarray, KVCache]:
    """Cached forward at ``cache.length``. ``decode_kernel`` is what
    the engine resolved (``"device"`` or ``"interpret"``: a single
    position runs the family's Pallas kernels); ``flash_prefill`` is
    the engine's static word that the cache is fresh, handed on as
    ``fresh`` to a family that declares ``fresh_prefill_flag``."""
    if decode_kernel not in (None, "device", "interpret"):
        raise ValueError(f"decode_kernel={decode_kernel!r}: this family "
                         "has the per-layer kernels only")
    if cache.state is None and family.row_state(config, cache.k.dtype):
        raise ValueError("this family's cache carries the rows' state "
                         "(KVCache.state); it was dropped on the way here")
    frame = family.frame
    h, turned = _start(frame, params, input_ids, config, cache.length, pad)
    fresh = {"fresh": flash_prefill} if family.fresh_prefill_flag else {}
    h, cache = frame.apply_blocks(params, h, config, *turned, cache, pad,
                                  decode_kernel=decode_kernel, **fresh)
    if frame.last_position_logits:
        h = h[:, -1:]
    return _logits(frame, params, h, config), cache


def make_cache(family, config, batch: int, max_seq: int,
               dtype=jnp.float32) -> KVCache:
    """The contiguous cache of a one-plane family, from its declaration:
    ``[cache_layers, batch, heads, max_seq, width]`` positions, the
    zeroed counters (or the fused layout's empty second leaf), and
    ``row_state``'s leaves zeroed with the batch on axis 1: the three
    facts the paged pool and the state slab size themselves from."""
    if max_seq > config.n_positions:
        raise ValueError(
            f"max_seq={max_seq} exceeds n_positions={config.n_positions}")
    planes, heads, width = family.cache_entry(config)
    if planes != 1:
        raise ValueError(f"family {family.name!r} keeps {planes} planes; "
                         "this cache is the one-plane families'")
    names = family.cache_counters
    return KVCache(
        k=jnp.zeros((family.cache_layers(config), batch, heads, max_seq,
                     width), dtype),
        v=(jnp.zeros((len(names),), jnp.int32) if names
           else jnp.zeros((0,), dtype)),
        length=jnp.zeros((), jnp.int32),
        state=tuple(jnp.zeros(shape[:1] + (batch,) + shape[1:], dt)
                    for shape, dt in family.row_state(config, dtype))
        or None)
