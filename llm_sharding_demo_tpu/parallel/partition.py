"""N-stage pipeline partitioner over the GPT-2 parameter pytree.

This is the reference's ``split_gpt2_model`` capability (reference
server.py:51-105: ShardA = wte+wpe+blocks[:k], ShardB = blocks[k:]+ln_f+
lm_head) generalized to N contiguous stages, with the validation the
reference lacks: its shipped k8s config runs block 1 on *both* shards
(SPLIT_AT=2 on shard A, SPLIT_AT=1 on shard B — SURVEY.md §2.3.1). Here the
partition is computed once from a single source of truth and checked to be
disjoint and exhaustive before any stage exists.

TPU-native design notes:

- Stage parameters are *slices of the stacked-block pytree* (blocks carry a
  leading layer axis, models.gpt2), so a stage's blocks still run as one
  ``lax.scan`` and extraction is pure array slicing — no module surgery.
- The LM head is tied to ``wte``, so the last stage carries ``wte`` too
  (shared with stage 0 only when n_stages == 1). This is the memory-honest
  version of the reference, where every role holds the *full* model
  (server.py:108-110).
- ``stage_apply`` is a pure function of (stage params, hidden|ids) suitable
  for jit per device or for shard_map over a pipeline mesh axis.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..models.gpt2 import (GPT2Config, Params, apply_blocks, embed,
                           final_logits)
from ..ops.attention import KVCache


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One pipeline stage: blocks ``[start, end)`` of ``n_layer`` total."""

    index: int
    n_stages: int
    start: int
    end: int

    @property
    def is_first(self) -> bool:
        return self.index == 0

    @property
    def is_last(self) -> bool:
        return self.index == self.n_stages - 1

    @property
    def n_blocks(self) -> int:
        return self.end - self.start


def balanced_boundaries(n_layer: int, n_stages: int) -> List[int]:
    """Split points giving each stage ``n_layer // n_stages`` (±1) blocks.

    Returns the interior boundaries, e.g. 12 layers / 4 stages -> [3, 6, 9].
    Earlier stages get the remainder blocks (they also carry the embedding).
    """
    if not 1 <= n_stages <= n_layer:
        raise ValueError(f"n_stages={n_stages} must be in [1, n_layer={n_layer}]")
    base, rem = divmod(n_layer, n_stages)
    sizes = [base + (1 if i < rem else 0) for i in range(n_stages)]
    bounds, acc = [], 0
    for s in sizes[:-1]:
        acc += s
        bounds.append(acc)
    return bounds


def make_stage_specs(n_layer: int, boundaries: Sequence[int],
                     ) -> List[StageSpec]:
    """Interior boundaries -> validated StageSpecs.

    Raises if the partition is not strictly increasing, in range, or leaves
    any stage empty — i.e. it enforces disjoint + exhaustive block coverage,
    the guard SURVEY.md §4 item 2 calls for against the reference's shipped
    SPLIT_AT mismatch.
    """
    bounds = list(boundaries)
    cuts = [0] + bounds + [n_layer]
    for a, b in zip(cuts, cuts[1:]):
        if not a < b:
            raise ValueError(
                f"invalid partition {bounds!r} of {n_layer} layers: stage "
                f"[{a},{b}) is empty or out of order (partition must be "
                "disjoint and exhaustive)")
    n_stages = len(cuts) - 1
    return [StageSpec(index=i, n_stages=n_stages, start=cuts[i], end=cuts[i + 1])
            for i in range(n_stages)]


def validate_specs(specs: Sequence[StageSpec], n_layer: int) -> None:
    """Re-check an externally supplied stage list, in composition order.

    Enforces everything ``stage_apply`` relies on: stages tile
    ``[0, n_layer)`` *in list order* (no sorting — order is execution
    order), and ``index``/``n_stages`` are consistent so exactly the first
    stage embeds and exactly the last applies the LM head.
    """
    pos = 0
    for i, s in enumerate(specs):
        if s.index != i or s.n_stages != len(specs):
            raise ValueError(
                f"spec at position {i} has index={s.index}, "
                f"n_stages={s.n_stages}; expected index={i}, "
                f"n_stages={len(specs)} (is_first/is_last would misfire)")
        if s.start != pos or s.end <= s.start:
            raise ValueError(
                f"stages {[(t.start, t.end) for t in specs]} do not tile "
                f"[0,{n_layer}) in order: gap/overlap at block {pos}")
        pos = s.end
    if pos != n_layer:
        raise ValueError(f"stages cover [0,{pos}) but model has {n_layer} layers")


def _slice_blocks(blocks: Params, start: int, end: int) -> Params:
    return jax.tree_util.tree_map(lambda x: x[start:end], blocks)


def extract_stage_params(params: Params, spec: StageSpec) -> Params:
    """The parameter subset one stage actually needs (and nothing more).

    First stage: embeddings + its blocks. Last stage: its blocks + the
    final norm and head. Middle stages: blocks only. Contrast with the
    reference, where every pod loads and keeps the full model
    (server.py:40-42, 108-110).

    Family is detected structurally: the llama tree carries an untied
    ``lm_head`` (and no ``wpe``); the GPT-2/MoE tree ties its head to
    ``wte``.
    """
    out: Params = {"blocks": _slice_blocks(params["blocks"], spec.start, spec.end)}
    llama_tree = "lm_head" in params
    if spec.is_first:
        out["wte"] = params["wte"]
        if not llama_tree:
            out["wpe"] = params["wpe"]
    if spec.is_last:
        out["ln_f"] = params["ln_f"]
        if llama_tree:
            out["lm_head"] = params["lm_head"]
        else:
            out["wte_out"] = params["wte"]  # tied LM head
    return out


def partition_params(params: Params, specs: Sequence[StageSpec]) -> List[Params]:
    """All stages' parameter subsets: ``[extract_stage_params(p, s) for s]``."""
    return [extract_stage_params(params, s) for s in specs]


def stage_apply(stage_params: Params, spec: StageSpec, config: GPT2Config,
                x: jnp.ndarray, cache: Optional[KVCache] = None,
                pad: Optional[jnp.ndarray] = None,
                decode_kernel=None,
                ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Run one stage. First stage takes ``[B,S]`` ids, others ``[B,S,D]``
    hidden states; last stage returns ``[B,S,vocab]`` logits.

    This is the per-stage public contract the reference exposes as
    ``/forward`` (ids -> hidden, server.py:132-140) and ``/forward_b``
    (hidden -> logits, server.py:143-151), as a pure jittable function.
    ``cache`` holds only this stage's layers (leading axis ``spec.n_blocks``).

    The position offset is *derived*, never passed: ``cache.length`` when a
    cache is present, else 0. A caller-supplied offset could desynchronize
    the wpe gather from the attention mask / cache-write position, which
    both always come from the cache — so the knob deliberately doesn't
    exist. ``pad`` ([B] int32) is the ragged-batch left-pad vector (see
    models.gpt2.forward_with_cache): it shifts positions down per row and
    masks each row's pad prefix as keys.
    """
    from ..models.llama import LlamaConfig
    if isinstance(config, LlamaConfig):
        return _stage_apply_llama(stage_params, spec, config, x, cache, pad,
                                  decode_kernel)
    position_offset = cache.length if cache is not None else 0
    if pad is not None:
        position_offset = position_offset - pad[:, None]
    h = embed(stage_params, x, position_offset) if spec.is_first else x
    h, cache = apply_blocks(stage_params["blocks"], h, config, cache,
                            k_valid_from=pad, decode_kernel=decode_kernel)
    if spec.is_last:
        head_params = {"ln_f": stage_params["ln_f"], "wte": stage_params["wte_out"]}
        h = final_logits(head_params, h, config.layer_norm_epsilon)
    return h, cache


def _stage_apply_llama(stage_params: Params, spec: StageSpec, config,
                       x: jnp.ndarray, cache: Optional[KVCache],
                       pad: Optional[jnp.ndarray], decode_kernel=None,
                       ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """llama stage: RoPE angles derive from the stage cache's length (the
    same same-for-all-stages offset the dense path derives), embedding on
    the first stage, RMSNorm + untied head on the last."""
    from ..models import llama
    offset = cache.length if cache is not None else 0
    cos, sin = llama._angles(config, x.shape[1], offset, pad)
    h = llama._embed(stage_params, x) if spec.is_first else x
    h, cache = llama.apply_blocks(stage_params["blocks"], h, config, cos, sin,
                                  cache, k_valid_from=pad,
                                  decode_kernel=decode_kernel)
    if spec.is_last:
        h = llama._final(stage_params, h, config)
    return h, cache


def make_stage_cache(spec: StageSpec, config: GPT2Config, batch: int,
                     max_seq: int, dtype=jnp.float32) -> KVCache:
    """A KV cache sized for one stage's block count (kv-head width for
    GQA families — ``n_kv_head`` when the config defines it)."""
    if max_seq > config.n_positions:
        raise ValueError(
            f"max_seq={max_seq} exceeds n_positions={config.n_positions}")
    heads = getattr(config, "n_kv_head", config.n_head)
    return KVCache.create(spec.n_blocks, batch, heads, max_seq,
                          config.head_dim, dtype)


def stack_stage_params(params: Params, specs: Sequence[StageSpec]) -> Params:
    """Stage-major re-layout for single-jit pipelining over a mesh axis.

    Requires equal-size stages. Returns the block pytree reshaped from
    ``[n_layer, ...]`` to ``[n_stages, blocks_per_stage, ...]`` so a
    ``shard_map`` over the pipeline mesh axis gives each device its own
    ``[blocks_per_stage, ...]`` slice — the single-program SPMD form of the
    reference's multi-process topology.
    """
    sizes = {s.n_blocks for s in specs}
    if len(sizes) != 1:
        raise ValueError(
            f"stage-major stacking needs equal stage sizes, got "
            f"{[s.n_blocks for s in specs]}")
    per = sizes.pop()
    n_stages = len(specs)

    def reshape(x):
        return x.reshape((n_stages, per) + x.shape[1:])

    return jax.tree_util.tree_map(reshape, params["blocks"])


def stack_virtual_chunks(params: Params, n_stages: int,
                         n_virtual: int) -> Params:
    """Interleaved-1F1B re-layout: ``[L, ...]`` block leaves ->
    ``[n_stages, n_virtual, per_chunk, ...]`` with virtual chunk
    ``g = j * n_stages + d`` stored at ``[d, j]`` — device d owns every
    S-th chunk (the Megatron interleaved assignment), so one shard_map
    over the pp axis hands each device its ``[n_virtual, per_chunk,
    ...]`` slice. Requires ``L % (n_stages * n_virtual) == 0``.
    """
    def reshape(x):
        n_layer = x.shape[0]
        total = n_stages * n_virtual
        if n_layer % total:
            raise ValueError(
                f"interleaved stacking needs n_layer divisible by "
                f"pp * virtual_stages = {total}, got {n_layer}")
        per = n_layer // total
        # [L] in chunk-major order = [j, d, per]; devices want [d, j, per]
        return x.reshape((n_virtual, n_stages, per)
                         + x.shape[1:]).swapaxes(0, 1)

    return jax.tree_util.tree_map(reshape, params["blocks"])


def unstack_stage_params(stacked_blocks: Params) -> Params:
    """Inverse of ``stack_stage_params``: ``[S, per, ...]`` -> ``[L, ...]``."""
    def reshape(x):
        return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])

    return jax.tree_util.tree_map(reshape, stacked_blocks)


def stack_stage_params_padded(params: Params, specs: Sequence[StageSpec],
                              ) -> Tuple[Params, jnp.ndarray]:
    """Stage-major re-layout for ARBITRARY stage sizes.

    Stages are zero-padded to the largest stage's block count:
    ``[n_layer, ...]`` -> ``[n_stages, per_max, ...]`` plus a
    ``[n_stages, per_max]`` bool validity mask. Padding rows are all-zero
    parameters and are masked to identity inside the block scan
    (``models.gpt2.apply_blocks(valid=...)``), so the pipelined program
    matches the unpadded model exactly and padded params receive zero
    gradients (they stay zero under training; weight decay of zero is
    zero). This lifts the equal-stage restriction of
    ``stack_stage_params`` — e.g. 12 layers over 8 stages, or any uneven
    user-supplied BOUNDARIES.

    Cost: every stage *executes* ``per_max`` blocks, so a maximally uneven
    partition wastes ticks; balanced-but-uneven partitions (base+1 vs
    base) waste at most one block per stage.
    """
    per_max = max(s.n_blocks for s in specs)
    n_stages = len(specs)

    def pad_stack(x):
        rows = []
        for s in specs:
            piece = x[s.start:s.end]
            if s.n_blocks < per_max:
                pad_width = ((0, per_max - s.n_blocks),) + ((0, 0),) * (x.ndim - 1)
                piece = jnp.pad(piece, pad_width)
            rows.append(piece)
        return jnp.stack(rows)

    stacked = jax.tree_util.tree_map(pad_stack, params["blocks"])
    return stacked, stage_valid_mask(specs)


def stage_valid_mask(specs: Sequence[StageSpec]) -> jnp.ndarray:
    """[n_stages, per_max] bool: True where a stacked block row is a real
    layer, False where it is zero padding (see stack_stage_params_padded)."""
    per_max = max(s.n_blocks for s in specs)
    return jnp.asarray([[i < s.n_blocks for i in range(per_max)]
                        for s in specs])


def unstack_stage_params_padded(stacked_blocks: Params,
                                specs: Sequence[StageSpec]) -> Params:
    """Inverse of ``stack_stage_params_padded``: drop padding rows,
    concatenate the per-stage valid prefixes back to ``[n_layer, ...]``."""
    def merge(x):
        return jnp.concatenate([x[s.index, :s.n_blocks] for s in specs])

    return jax.tree_util.tree_map(merge, stacked_blocks)
