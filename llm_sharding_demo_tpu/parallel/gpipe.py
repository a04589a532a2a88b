"""GPipe-style pipeline-parallel block execution (shard_map + ppermute).

This is the *training-capable*, single-program form of pipeline
parallelism. Where ``parallel.pipeline.PipelineRunner`` mirrors the
reference's topology for serving (stage per device, host-driven handoff —
the TPU rebuild of reference server.py:169-181), this module runs all
stages inside ONE jitted SPMD program:

- transformer blocks are stacked stage-major ``[n_stages, per_stage, ...]``
  and sharded over the mesh's ``pp`` axis, so each device owns exactly its
  stage's weights;
- the classic GPipe schedule: the batch is split into M microbatches; at
  schedule tick t, stage i runs microbatch ``t - i``; activations hop to
  the next stage via ``lax.ppermute`` over the ICI ring. The pipeline
  "bubble" is the usual ``(S-1)/(M+S-1)`` fraction;
- reverse-mode AD differentiates straight through the schedule (the
  transpose of ``ppermute`` is the reverse ``ppermute``, of ``psum`` a
  broadcast), giving pipeline-parallel *training* for free — no hand-rolled
  backward schedule;
- the ``pp`` axis is the only *manual* axis: dp / tp / sp stay automatic
  (GSPMD), so the same step composes data, tensor, sequence, and pipeline
  parallelism on one mesh (see ``axis_names={pp_axis}`` on the shard_map).

The embedding and LM head run outside the shard_map under plain GSPMD:
with the tied head this keeps ``wte`` out of the manual program entirely
and lets XLA lay out the vocab matmul freely.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.gpt2 import GPT2Config, Params, apply_blocks

# Placement contract (tools/graftcheck placement pass + utils/
# graftshard): ``pp`` is the single MANUAL axis here — the compiled
# pipeline program's traced jaxpr must establish exactly that placement
# (blocks split stage-major over pp, activations replicated). tp/sp
# ride as automatic GSPMD axes inside the blocks and never appear as
# manual placement in the traced program.
PLACEMENT_CONTRACT = {
    "mesh_axes": ("pp", "tp", "sp"),
    "entry:_compiled_pipeline": "pp",
}


def microbatch(h: jnp.ndarray, n_microbatches: int) -> jnp.ndarray:
    """[B, ...] -> [M, B/M, ...]; validates divisibility."""
    b = h.shape[0]
    if b % n_microbatches:
        raise ValueError(
            f"batch {b} not divisible by n_microbatches={n_microbatches}")
    return h.reshape((n_microbatches, b // n_microbatches) + h.shape[1:])


def unmicrobatch(h: jnp.ndarray) -> jnp.ndarray:
    """[M, mb, ...] -> [M*mb, ...]."""
    return h.reshape((h.shape[0] * h.shape[1],) + h.shape[2:])


def gpipe_apply_blocks(stacked_blocks: Params, h_micro: jnp.ndarray,
                       config: GPT2Config, mesh: Mesh,
                       pp_axis: str = "pp", remat: bool = False,
                       valid: Optional[jnp.ndarray] = None,
                       ) -> jnp.ndarray:
    """Run stage-major stacked blocks over microbatched hidden states.

    ``stacked_blocks`` leaves: ``[n_stages, per_stage, ...]`` sharded
    ``P(pp_axis, ...)``; ``h_micro``: ``[M, mb, seq, D]`` replicated over
    ``pp`` (dp/sp sharding on mb/seq rides along as automatic axes).
    Returns ``[M, mb, seq, D]``.

    ``valid`` ([n_stages, per_stage] bool) marks real vs padding block
    rows for unequal stage sizes (``partition.stack_stage_params_padded``);
    padding rows run but are masked to identity. ``None`` means all rows
    are real (the equal-stage layout).

    Schedule: T = M + S - 1 ticks via ``lax.scan``. Stage 0 feeds
    microbatch t (clamped; overrun ticks recompute a stale microbatch whose
    output lands in an already-finalized slot — masked writes keep later
    real values authoritative). The last stage's finished microbatch
    ``t - (S-1)`` accumulates into the output buffer; a masked ``psum``
    replicates the final buffer across the pp axis so the caller's head/
    loss math is pp-invariant.
    """
    if pp_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {pp_axis!r} axis: {mesh.axis_names}")
    n_micro = h_micro.shape[0]
    fn = _compiled_pipeline(mesh, config, pp_axis, remat, n_micro,
                            valid is not None)
    if valid is None:
        return fn(stacked_blocks, h_micro)
    valid = jax.device_put(valid, NamedSharding(mesh, P(pp_axis)))
    return fn(stacked_blocks, valid, h_micro)


@functools.lru_cache(maxsize=64)
def _compiled_pipeline(mesh: Mesh, config: GPT2Config, pp_axis: str,
                       remat: bool, n_micro: int, has_valid: bool):
    """Build + jit the pipeline program once per (mesh, config, schedule).

    Cached on hashable keys because jit's own cache is keyed on function
    identity — rebuilding the shard_map closure per call would make every
    eager call re-trace AND re-XLA-compile the whole S-stage scan. The
    jit wrapper itself is required: EAGER shard_map hard-aborts (not
    raises) on the per-core lax.cond below in current JAX; under jit the
    same program compiles and runs correctly. Inside an outer jit (the
    train step) the inner jit is inlined for free.
    """
    n_stages = mesh.shape[pp_axis]
    n_ticks = n_micro + n_stages - 1
    # Family dispatch (static: config is in this function's cache key).
    # llama blocks need RoPE angles; positions are 0..S-1 for the whole
    # (no-cache) training forward, identical on every stage and tick.
    from ..models.llama import LlamaConfig
    is_llama = isinstance(config, LlamaConfig)

    def run_blocks(blocks_local, x, valid_row):
        if is_llama:
            from ..models import llama
            # same helper forward() uses: positions 0..S-1, no pad
            cos, sin = llama._angles(config, x.shape[1], 0, None)
            return llama.apply_blocks(blocks_local, x, config, cos, sin,
                                      remat=remat, valid=valid_row)[0]
        return apply_blocks(blocks_local, x, config, remat=remat,
                            valid=valid_row)[0]
    # Bubble ticks can skip the block FLOPs via a per-core lax.cond — but
    # only when the block computation contains no cross-device collectives:
    # tp/sp shard the matmuls/sequence and XLA's partitioner inserts
    # all-reduces inside the block, and collectives inside divergent
    # control flow abort. pp-only (±dp, which all-reduces grads outside
    # the blocks) is the common fast case; tp/sp meshes keep the
    # compute-and-mask schedule.
    skip_bubbles = all(mesh.shape.get(ax, 1) == 1 for ax in ("tp", "sp"))

    def per_stage(blocks_local: Params, valid_local,
                  h_all: jnp.ndarray) -> jnp.ndarray:
        # local view: [1, per_stage, ...] -> [per_stage, ...]
        blocks_local = jax.tree_util.tree_map(lambda x: x[0], blocks_local)
        valid_row = None if valid_local is None else valid_local[0]
        stage = jax.lax.axis_index(pp_axis)
        zeros_state = jnp.zeros(h_all.shape[1:], h_all.dtype)
        # mark the scan carry as pp-varying up front (it becomes varying
        # via ppermute/masked writes; the carry signature must agree)
        init = (jax.lax.pcast(zeros_state, pp_axis, to="varying"),
                jax.lax.pcast(jnp.zeros_like(h_all), pp_axis,
                              to="varying"))

        def tick(carry, t):
            state, outputs = carry
            feed = jax.lax.dynamic_index_in_dim(
                h_all, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
            x = jnp.where(stage == 0, feed, state)
            if skip_bubbles:
                # bubble ticks (stage i is idle before tick i and after
                # tick i + M - 1) skip the block FLOPs entirely: inside
                # shard_map this cond is real per-core control flow — each
                # TPU core has its own program counter, and the collective
                # (ppermute below) stays OUTSIDE the cond so every core
                # still joins it. With M microbatches on S stages this
                # recovers the (S-1)/(M+S-1) bubble fraction round 1
                # burned on recomputing stale microbatches.
                active = (t >= stage) & (t < stage + n_micro)
                y = jax.lax.cond(
                    active,
                    lambda x: run_blocks(blocks_local, x, valid_row),
                    lambda x: x,
                    x)
            else:
                y = run_blocks(blocks_local, x, valid_row)
            # hop to the next stage over the ICI ring; stage 0 receives
            # zeros (it is fed from h_all, never from a predecessor)
            incoming = jax.lax.ppermute(
                y, pp_axis, [(j, j + 1) for j in range(n_stages - 1)])
            done = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            written = jax.lax.dynamic_update_index_in_dim(
                outputs, y, done, axis=0)
            outputs = jnp.where(stage == n_stages - 1, written, outputs)
            return (incoming, outputs), None

        (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(n_ticks))
        # only the last stage holds real outputs; masked psum replicates
        outputs = jnp.where(stage == n_stages - 1, outputs, 0.0)
        return jax.lax.psum(outputs, pp_axis)

    if not has_valid:
        return jax.jit(jax.shard_map(
            lambda b, h: per_stage(b, None, h), mesh=mesh,
            in_specs=(P(pp_axis), P()), out_specs=P(),
            axis_names={pp_axis}))
    return jax.jit(jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(pp_axis), P(pp_axis), P()), out_specs=P(),
        axis_names={pp_axis}))


def stacked_block_pspecs(mesh: Mesh, pp_axis: str = "pp",
                         llama: bool = False, n_lead: int = 1) -> Params:
    """PartitionSpecs for stage-major stacked blocks: stage axis on ``pp``,
    plus the Megatron tp layout (shifted one axis right of
    ``spmd.param_pspecs`` / ``spmd.llama_param_pspecs`` because of the
    extra leading stage axis). ``n_lead=2`` covers the interleaved
    ``[S, v, per_chunk, ...]`` layout (an extra unsharded chunk axis)."""
    tp = "tp" if "tp" in mesh.axis_names else None

    def s(*tail):
        return P(pp_axis, *([None] * n_lead), *tail)

    if llama:
        return {
            "ln_attn": {"scale": s(None)},
            "attn": {
                "wq": {"kernel": s(None, tp)},
                "wk": {"kernel": s(None, tp)},
                "wv": {"kernel": s(None, tp)},
                "wo": {"kernel": s(tp, None)},
            },
            "ln_mlp": {"scale": s(None)},
            "mlp": {
                "gate": {"kernel": s(None, tp)},
                "up": {"kernel": s(None, tp)},
                "down": {"kernel": s(tp, None)},
            },
        }
    return {
        "ln_1": {"scale": s(None), "bias": s(None)},
        "attn": {
            "c_attn": {"kernel": s(None, tp), "bias": s(tp)},
            "c_proj": {"kernel": s(tp, None), "bias": s(None)},
        },
        "ln_2": {"scale": s(None), "bias": s(None)},
        "mlp": {
            "c_fc": {"kernel": s(None, tp), "bias": s(tp)},
            "c_proj": {"kernel": s(tp, None), "bias": s(None)},
        },
    }


def shard_stacked_blocks(stacked: Params, mesh: Mesh, pp_axis: str = "pp",
                         config=None, n_lead: int = 1) -> Params:
    """Place stage-major stacked blocks on the mesh; the family's pspec
    table is chosen from ``config`` (GPT-2 layout when None, for
    pre-llama callers)."""
    from ..models.llama import LlamaConfig
    specs = stacked_block_pspecs(mesh, pp_axis,
                                 llama=isinstance(config, LlamaConfig),
                                 n_lead=n_lead)
    return jax.tree_util.tree_map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        stacked, specs)
