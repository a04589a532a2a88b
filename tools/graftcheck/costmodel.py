"""graftplan: compile-free cost model + auto-sharding planner.

ROADMAP item 5 ("Learning to Shard" lite): the verifier machinery built
by PR 3/PR 5 — abstract eval of every partition plan against an
``AbstractMesh``, exact per-entry-point program counts through the
engine's own planners — refactored from a *gate* into a
*decision-maker*. For a model family x mesh x traffic mix, the planner
enumerates serving candidates (partition plan x stage split x batch
mode x max_batch x KV-pool geometry), gates each through the EXISTING
semantic verifier (invalid plans are rejected with the verifier's own
diagnostics and never scored), scores the survivors compile-free, and
emits a ranked table plus one chosen config that ``serving/app.py``
consumes via ``AUTO_PLAN=1``.

Cost model — the Helix Parallelism framing (PAPERS.md): at interactive
batch sizes DECODE is bound by *bytes moved* — weight and KV-cache HBM
streams plus inter-chip collective traffic — not FLOPs. Every term is
derived statically:

- **comm bytes** are read off traced jaxprs (``jax.make_jaxpr`` over
  ``AbstractMesh`` stand-ins — zero devices, zero compile): walk the
  program the topology would run, sum collective operand avals by the
  per-primitive formulas below, multiply by scan trip counts. The
  pipelined (pp) program is THE real ``PipelinedDecoder._pp_blocks``
  step (``semantic.build_ppdecode_programs``); tp/ep use declared
  stand-in programs carrying the documented Megatron / expert-dispatch
  collective schedules at real avals (GSPMD inserts the actual
  collectives at compile time, which a compile-free pass never sees —
  the stand-ins make the schedule explicit and walkable).
- **HBM footprint** from avals: params via ``jax.eval_shape`` over
  ``init_params`` divided by the derived sharding (``derive_pspecs``
  from each family's ``SHARDING_DESCRIPTOR`` — zero hand-written
  PartitionSpecs), KV state via the pool geometry math
  (``ops.paged_attention.pool_shape``) or the contiguous cache aval,
  peak activations as the largest single intermediate in the traced
  decode-step jaxpr. Exactness is pinned against real CPU buffer
  ``nbytes`` by tests/test_graftplan.py.
- **program counts** via the existing ``recompile.certify`` /
  ``certify_paged`` machinery (exact — certified equal to observed jit
  cache sizes — for admission-mode and solo-paged candidates; rows
  where the count is a static upper bound carry
  ``programs_exact: false``).

Collective byte formulas (TOTAL bytes crossing links, per execution of
the traced program; operand avals are the per-device view inside
``shard_map``):

- ``ppermute``:        operand_bytes x n_pairs (each pair ships one
                       per-device operand along one link)
- ``psum``/``pmax``/``pmin``: 2 x operand_bytes x (n - 1)
                       (bidirectional ring all-reduce)
- ``all_gather``:      operand_bytes x n x (n - 1) (every device
                       receives the other n-1 shards)
- ``reduce_scatter``:  operand_bytes x (n - 1)
- ``all_to_all``:      operand_bytes x (n - 1) (each device keeps 1/n
                       of its operand local)

Nested ``scan`` bodies multiply by the trip count; ``while`` bodies
count once (a static bound cannot know the trip count — documented);
``cond`` takes the max over branches.

Ranking: infeasible rows (HBM over budget) and verifier-rejected rows
never rank. Feasible rows sort by modeled decode cost per token
(weight-stream bytes per device amortized over the effective batch +
KV-stream bytes + paged gather/scatter amortization + ICI-weighted comm
bytes), tie-broken by fewer compiled programs, smaller HBM footprint,
then config simplicity (contiguous before paged, admission before iter,
smaller max_batch, fewer stages) — so on a single chip with
single-stream traffic the planner reproduces the hand-tuned serving
default by construction, and the choice only moves when the cost model
finds real bytes to save.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .core import Finding

_APP_PATH = "llm_sharding_demo_tpu/serving/app.py"

# relative cost of moving one byte over ICI vs streaming it from HBM
# (decode-step granularity; a single scalar keeps the model inspectable
# — the ranking rules in docs/ARCHITECTURE.md "Planning" discuss it)
ICI_BYTE_WEIGHT = 4.0
# the iteration scheduler's default segment width: paged decode pays one
# gather + one scatter of the row cache per segment
PAGED_SEG_STEPS = 32
DEFAULT_HBM_GB = 16.0


# -- traffic -----------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TrafficRow:
    """One request shape class in the traffic mix: ``count`` concurrent
    requests of ``prompt_len`` prompt tokens decoding ``max_new``."""

    prompt_len: int
    max_new: int
    count: int = 1

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


DEFAULT_TRAFFIC: Tuple[TrafficRow, ...] = (TrafficRow(16, 32, 1),)


def parse_traffic(spec: str) -> Tuple[TrafficRow, ...]:
    """``"16/32x8,64/16"`` -> 8 concurrent 16-prompt/32-new requests
    plus one 64-prompt/16-new request. Elements are
    ``prompt/new[xcount]``, comma-separated."""
    rows: List[TrafficRow] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        shape, _, cnt = part.partition("x")
        p, sep, n = shape.partition("/")
        try:
            row = TrafficRow(int(p), int(n) if sep else 0,
                             int(cnt) if cnt else 1)
        except ValueError as e:
            raise ValueError(
                f"bad traffic element {part!r}: want prompt/new[xcount], "
                f"e.g. 16/32x8") from e
        if row.prompt_len < 1 or row.max_new < 1 or row.count < 1:
            raise ValueError(
                f"bad traffic element {part!r}: prompt/new/count must "
                "all be >= 1")
        rows.append(row)
    if not rows:
        raise ValueError(f"traffic spec {spec!r} names no request shapes")
    return tuple(rows)


def concurrency(traffic: Sequence[TrafficRow]) -> int:
    return sum(r.count for r in traffic)


# -- derived sharding (zero hand-written PartitionSpecs) ---------------------


@functools.lru_cache(maxsize=64)
def param_avals(module, config):
    """Aval tree of the family's params. Memoized: one plan() run calls
    this per candidate (gating, sharding derivation, scoring) with the
    same (module, config) — configs are frozen dataclasses, so identity
    caching is sound, and callers never mutate the aval tree."""
    import jax
    return jax.eval_shape(lambda k: module.init_params(config, k),
                          jax.random.PRNGKey(0))


def derive_pspecs(module, config, mesh_axes: Dict[str, int]):
    """PartitionSpec tree derived from the family's
    ``SHARDING_DESCRIPTOR`` — architectural facts (which ops are
    Megatron column/row, which are expert-stacked), not hand-written
    specs. Pinned equal to the hand-tuned ``parallel.spmd`` layouts for
    all three families by tests/test_graftplan.py, which is what lets
    the planner onboard new families from their descriptors alone.

    Size-1 axes derive no sharding (replication already); ``config`` is
    unused by the tree shape but kept in the signature because the
    descriptor's divisor fields are validated against it by
    ``gate_candidate``."""
    from jax.sharding import PartitionSpec as P
    desc = getattr(module, "SHARDING_DESCRIPTOR", None)
    if desc is None:
        raise ValueError(
            f"{module.__name__} declares no SHARDING_DESCRIPTOR — the "
            "planner cannot derive a sharding for this family")
    tp = "tp" if mesh_axes.get("tp", 0) > 1 else None
    ep = "ep" if mesh_axes.get("ep", 0) > 1 else None
    avals = param_avals(module, config)

    def leaf_spec(path: str, rank: int):
        if not path.startswith("blocks."):
            return P()
        op, _, leaf = path.rpartition(".")
        entries = [None] * rank
        if ep and op in desc["expert"]:
            entries[1] = ep          # [L, E, ...]: the expert axis
        if tp and op in desc["column"]:
            entries[-1] = tp         # output dim (kernel AND bias)
        elif tp and op in desc["row"] and leaf == "kernel":
            entries[-2] = tp         # input dim; row bias replicates
        return P(*entries)

    def build(node, path: str):
        if isinstance(node, dict):
            return {k: build(v, f"{path}.{k}" if path else k)
                    for k, v in node.items()}
        return leaf_spec(path, len(node.shape))

    return build(avals, "")


def _leaf_items(tree, prefix: str = ""):
    if isinstance(tree, dict):
        for k in tree:
            yield from _leaf_items(tree[k], f"{prefix}.{k}" if prefix else k)
    else:
        yield prefix, tree


def tree_bytes(avals) -> int:
    return sum(int(np.prod(a.shape, dtype=np.int64))
               * np.dtype(a.dtype).itemsize
               for _, a in _leaf_items(avals))


def per_device_param_bytes(avals, pspecs, mesh_axes: Dict[str, int]) -> int:
    """One device's share of the param bytes under a derived spec tree
    (a leaf sharded over an axis holds 1/size of its bytes)."""
    specs = dict(_leaf_items(pspecs))
    total = 0
    for path, aval in _leaf_items(avals):
        nbytes = (int(np.prod(aval.shape, dtype=np.int64))
                  * np.dtype(aval.dtype).itemsize)
        shards = 1
        for entry in specs[path]:
            for axis in (entry if isinstance(entry, tuple) else (entry,)):
                if axis is not None:
                    shards *= mesh_axes.get(axis, 1)
        total += math.ceil(nbytes / shards)
    return total


# -- HBM footprint -----------------------------------------------------------


def kv_cache_bytes(config, batch: int, max_seq: int,
                   dtype_bytes: int = 4) -> int:
    """Contiguous KV state for ``batch`` rows: the
    ``[L, B, Hkv, max_seq, hd]`` k/v pair the engine allocates."""
    heads = getattr(config, "n_kv_head", config.n_head)
    return (2 * config.n_layer * batch * heads * max_seq
            * config.head_dim * dtype_bytes)


def kv_pool_bytes(config, num_blocks: int, block_size: int,
                  dtype_bytes: int = 4) -> int:
    """The paged pool's one fixed buffer — THE ``kv_pool`` geometry math
    (``ops.paged_attention.pool_shape``, trash block included), so the
    planner and the allocator can never disagree about pool bytes."""
    from llm_sharding_demo_tpu.ops.paged_attention import pool_shape
    heads = getattr(config, "n_kv_head", config.n_head)
    shape = pool_shape(config.n_layer, num_blocks, heads, block_size,
                       config.head_dim)
    return int(np.prod(shape, dtype=np.int64)) * dtype_bytes


@functools.lru_cache(maxsize=64)
def peak_activation_bytes(module, config, batch: int, max_seq: int) -> int:
    """Largest single intermediate in the traced decode-step jaxpr
    (``forward_with_cache`` at S=1 over the family's real cache aval) —
    the working-set spike on top of params + KV. Memoized (the full
    forward trace is the planner's most expensive step, and every
    candidate at the same effective batch shares it)."""
    import jax
    import jax.numpy as jnp
    pavals = param_avals(module, config)
    cache = jax.eval_shape(
        lambda: module.make_cache(config, batch, max_seq))
    ids = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, i, c: module.forward_with_cache(p, i, config, c))(
            pavals, ids, cache)

    peak = 0

    def walk(jxp):
        nonlocal peak
        from .semantic import _sub_jaxprs
        for eqn in jxp.eqns:
            out = sum(int(np.prod(v.aval.shape, dtype=np.int64))
                      * np.dtype(v.aval.dtype).itemsize
                      for v in eqn.outvars if hasattr(v, "aval"))
            peak = max(peak, out)
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(jaxpr.jaxpr)
    return peak


# -- comm bytes off traced jaxprs --------------------------------------------


def _axis_size(eqn, mesh_axes: Dict[str, int]) -> int:
    # reduction collectives (psum/pmax/pmin) carry ``axes``; the data
    # movers (ppermute/all_gather/all_to_all) carry ``axis_name``
    names = eqn.params.get("axis_name", eqn.params.get("axes", ()))
    if not isinstance(names, (tuple, list)):
        names = (names,)
    size = 1
    for n in names:
        size *= mesh_axes.get(n, 1)
    return size


def _operand_bytes(eqn) -> int:
    from jax.extend.core import Literal
    total = 0
    for v in eqn.invars:
        if isinstance(v, Literal) or not hasattr(v, "aval"):
            continue
        if not hasattr(v.aval, "shape"):
            continue
        total += (int(np.prod(v.aval.shape, dtype=np.int64))
                  * np.dtype(v.aval.dtype).itemsize)
    return total


def collective_bytes(jaxpr, mesh_axes: Dict[str, int]) -> int:
    """Total collective bytes one execution of ``jaxpr`` moves, by the
    per-primitive formulas in the module docstring. Recurses into
    sub-jaxprs; ``scan`` multiplies by trip count, ``cond`` takes the
    max branch, ``while`` counts one iteration."""
    from .semantic import COMM_PRIMITIVES

    def eqn_bytes(eqn) -> int:
        name = eqn.primitive.name
        if name not in COMM_PRIMITIVES:
            return 0
        n = _axis_size(eqn, mesh_axes)
        if n <= 1 and name != "ppermute":
            return 0
        b = _operand_bytes(eqn)
        if name == "ppermute":
            return b * len(eqn.params.get("perm", ()))
        if name in ("psum", "psum_invariant", "pmax", "pmin"):
            return 2 * b * (n - 1)
        if name == "all_gather":
            return b * n * (n - 1)
        if name in ("reduce_scatter", "all_to_all"):
            return b * (n - 1)
        return 0

    def walk(jxp) -> int:
        total = 0
        for eqn in jxp.eqns:
            total += eqn_bytes(eqn)
            name = eqn.primitive.name
            if name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                total += eqn.params["length"] * walk(body)
            elif name == "cond":
                total += max((walk(b.jaxpr)
                              for b in eqn.params["branches"]), default=0)
            elif name == "while":
                total += (walk(eqn.params["cond_jaxpr"].jaxpr)
                          + walk(eqn.params["body_jaxpr"].jaxpr))
            else:
                from .semantic import _sub_jaxprs
                for sub in _sub_jaxprs(eqn):
                    total += walk(sub)
        return total

    return walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)


def comm_bytes_program(fn, args, mesh_axes: Dict[str, int]) -> int:
    import jax
    return collective_bytes(jax.make_jaxpr(fn)(*args), mesh_axes)


# -- topology collective-schedule programs -----------------------------------


def pp_decode_step_program(n_stages: int, batch: int = 1,
                           module=None, config=None, mesh=None) -> tuple:
    """THE pp decode-step (fn, args) selection off
    ``semantic.build_ppdecode_programs`` — shared by the cost model's
    byte walk and bench.py's ICI calibration row (which compiles the
    same step on a concrete mesh), so the program being priced and the
    program being measured cannot drift apart."""
    from . import semantic
    rows = [r for r in semantic.build_ppdecode_programs(
        n_stages, batch=batch, module=module, config=config, mesh=mesh)
        if r[0].endswith("decode-step")]
    (_label, _scope, fn, args), = rows
    return fn, args


def pp_decode_comm_bytes(n_stages: int, batch: int = 1,
                         module=None, config=None) -> int:
    """Comm bytes of ONE pipelined decode token: the real
    ``PipelinedDecoder._pp_blocks`` step traced at S=1 (see
    ``semantic.build_ppdecode_programs`` — the same program the overlap
    lint walks). ``module``/``config`` are the model actually being
    scored (omitted: the registry gpt2 stand-in) — the handoff bytes
    scale with THAT model's hidden width, so pricing the stand-in
    would bias pp against tp/ep on any real config."""
    fn, args = pp_decode_step_program(n_stages, batch=batch,
                                      module=module, config=config)
    return comm_bytes_program(fn, args, {"pp": n_stages})


def tp_decode_comm_bytes(config, batch: int, tp: int) -> int:
    """Comm bytes of one tensor-parallel decode token: the Megatron
    collective schedule — per block, one psum of the [B, 1, D]
    activations after the row-parallel attention projection and one
    after the row-parallel MLP down projection — traced as a shard_map
    stand-in at real avals over an ``AbstractMesh`` and walked like any
    other program. (GSPMD inserts the real collectives at compile time;
    the stand-in declares the schedule the annotation provably
    produces.)"""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, PartitionSpec as P

    d = config.n_embd
    hidden = getattr(config, "intermediate_size", 4 * d)
    l = config.n_layer
    attn_sh = max(d // tp, 1)
    mlp_sh = max(hidden // tp, 1)
    mesh = AbstractMesh((tp,), ("tp",))

    def per_device(h, wcol_a, wrow_a, wcol_m, wrow_m):
        # weight args are already the per-device shards ([in, out/tp] /
        # [in/tp, out] per layer, stacked over L); h is replicated
        def body(carry, ws):
            h = carry
            ca, ra, cm, rm = ws
            a = jnp.einsum("bsd,df->bsf", h, ca)          # column partial
            h = h + jax.lax.psum(
                jnp.einsum("bsf,fd->bsd", a, ra), "tp")   # row + psum
            m = jnp.einsum("bsd,df->bsf", h, cm)
            h = h + jax.lax.psum(
                jnp.einsum("bsf,fd->bsd", m, rm), "tp")
            return h, None
        h, _ = jax.lax.scan(body, h, (wcol_a, wrow_a, wcol_m, wrow_m))
        return h

    rep = P()
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=(rep,) * 5,
                       out_specs=rep, axis_names={"tp"})
    h = jax.ShapeDtypeStruct((batch, 1, d), jnp.float32)
    args = (h,
            jax.ShapeDtypeStruct((l, d, attn_sh), jnp.float32),
            jax.ShapeDtypeStruct((l, attn_sh, d), jnp.float32),
            jax.ShapeDtypeStruct((l, d, mlp_sh), jnp.float32),
            jax.ShapeDtypeStruct((l, mlp_sh, d), jnp.float32))
    return comm_bytes_program(fn, args, {"tp": tp})


def kvp_decode_comm_bytes(config, batch: int, kvp: int) -> int:
    """Comm bytes of one decode token with the paged pool's kv-head
    plane sharded over ``kvp``: each device attends the (replicated)
    query against only its resident kv shard — a flash-style PARTIAL
    softmax (un-normalized o plus log-sum-exp per query head) — then
    the partials cross the kvp axis once per block (all_gather of
    ``o [B, Hq, hd]`` f32 + ``lse [B, Hq]`` f32) and combine with the
    usual max/exp renormalization. Traced as a shard_map stand-in at
    real avals over an ``AbstractMesh`` and walked like any other
    program (the tp/ep rationale: the stand-in declares the schedule
    the pool-plane sharding provably produces)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, PartitionSpec as P

    hq = config.n_head
    hd = config.head_dim
    mesh = AbstractMesh((kvp,), ("kvp",))

    def per_device(o_part, lse_part):
        def body(carry, _):
            o, lse = carry
            o_all = jax.lax.all_gather(o, "kvp")       # [kvp, B, Hq, hd]
            lse_all = jax.lax.all_gather(lse, "kvp")   # [kvp, B, Hq]
            m = jnp.max(lse_all, axis=0)
            w = jnp.exp(lse_all - m[None])
            norm = jnp.sum(w, axis=0)
            o = jnp.sum(o_all * w[..., None], axis=0) / norm[..., None]
            lse = m + jnp.log(norm)
            return (o, lse), None
        (o, _), _ = jax.lax.scan(body, (o_part, lse_part), None,
                                 length=config.n_layer)
        return o

    rep = P()
    # traced for its collectives only: the replicated stand-in operands
    # gather to the same value on every device, which varying-type
    # tracking cannot know, so it is off for this program
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=(rep, rep),
                       out_specs=rep, axis_names={"kvp"}, check_vma=False)
    o = jax.ShapeDtypeStruct((batch, hq, hd), jnp.float32)
    lse = jax.ShapeDtypeStruct((batch, hq), jnp.float32)
    return comm_bytes_program(fn, (o, lse), {"kvp": kvp})


def ep_decode_comm_bytes(config, batch: int, ep: int) -> int:
    """Comm bytes of one expert-parallel decode token: the expert
    dispatch/combine all-to-alls GSPMD derives from the expert-axis
    sharding — per block, the dispatched activations ``[E, B, C, D]``
    cross the ep axis twice. Traced as a shard_map stand-in (same
    rationale as the tp schedule)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from llm_sharding_demo_tpu.models.moe import expert_capacity

    e = config.n_experts
    d = config.n_embd
    cap = expert_capacity(config, 1)
    mesh = AbstractMesh((ep,), ("ep",))
    # per-device dispatched view, flattened so the exchanged axis is
    # exactly the ep axis: [ep, (E/ep)*B*C, D]
    rows = max(1, (e // ep) * batch * cap)

    def per_device(x):
        def body(carry, _):
            x = carry
            y = jax.lax.all_to_all(x, "ep", split_axis=0, concat_axis=0)
            x = jax.lax.all_to_all(y, "ep", split_axis=0, concat_axis=0)
            return x, None
        x, _ = jax.lax.scan(body, x, None, length=config.n_layer)
        return x

    fn = jax.shard_map(per_device, mesh=mesh, in_specs=(P("ep"),),
                       out_specs=P("ep"), axis_names={"ep"})
    x = jax.ShapeDtypeStruct((ep * ep, rows, d), jnp.float32)
    return comm_bytes_program(fn, (x,), {"ep": ep})


# -- candidates --------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One serving configuration the planner scores — exactly the knobs
    ``utils.config.ServingConfig`` exposes, so a chosen candidate maps
    1:1 onto env vars / an AUTO_PLAN override."""

    topology: str = "single"          # single | pp | tp | ep | kvp | kvp-tp
    boundaries: Tuple[int, ...] = ()  # pp stage split (interior bounds)
    batch_mode: str = "admission"
    max_batch: int = 1
    kv_pool_blocks: int = 0
    kv_block_size: int = 16

    @property
    def n_stages(self) -> int:
        return len(self.boundaries) + 1 if self.topology == "pp" else 1

    def label(self) -> str:
        parts = [self.topology]
        if self.topology == "pp":
            parts.append("b" + "+".join(str(b) for b in self.boundaries))
        parts.append(self.batch_mode)
        parts.append(f"mb{self.max_batch}")
        if self.kv_pool_blocks:
            parts.append(f"kv{self.kv_pool_blocks}x{self.kv_block_size}")
        return "/".join(parts)

    def serving_env(self) -> Dict[str, str]:
        """The env-var view of this candidate (the planner quickstart's
        copy-paste output; AUTO_PLAN applies the same mapping
        in-process)."""
        env = {
            "BATCH_MODE": self.batch_mode,
            "MAX_BATCH": str(self.max_batch),
            "PP_DECODE": "1" if self.topology == "pp" else "0",
            "TP_DECODE": "1" if self.topology in ("tp", "kvp-tp") else "0",
            "EP_DECODE": "1" if self.topology == "ep" else "0",
            "KVP_DECODE": "1" if self.topology in ("kvp", "kvp-tp")
                          else "0",
            "KV_POOL_BLOCKS": str(self.kv_pool_blocks),
            "KV_BLOCK_SIZE": str(self.kv_block_size),
        }
        if self.topology == "pp":
            env["BOUNDARIES"] = ",".join(str(b) for b in self.boundaries)
        return env


def enumerate_candidates(module, config, mesh_axes: Dict[str, int],
                         max_seq: int, max_batch_cap: int = 8,
                         kv_pool_blocks: int = 0,
                         kv_block_size: int = 16,
                         include_unsharded: bool = True,
                         ) -> List[Candidate]:
    """The candidate space: every topology the mesh and family admit x
    batch modes x batch widths x pool geometries. Composition legality
    is NOT decided here — ``gate_candidate`` rejects with diagnostics,
    so an illegal combination shows up as a rejected row rather than
    silently missing. ``include_unsharded=False`` drops the single
    rows (``plan_for_serving`` scores them once, on the no-mesh pass,
    instead of once per candidate mesh)."""
    from llm_sharding_demo_tpu.models import is_stage_partitionable
    from llm_sharding_demo_tpu.parallel import partition as Pt

    topos: List[Tuple[str, Tuple[int, ...]]] = (
        [("single", ())] if include_unsharded else [])
    if mesh_axes.get("pp", 0) > 1 and is_stage_partitionable(config) \
            and mesh_axes["pp"] <= config.n_layer:
        topos.append(("pp", tuple(Pt.balanced_boundaries(
            config.n_layer, mesh_axes["pp"]))))
    if mesh_axes.get("tp", 0) > 1 and not hasattr(config, "n_experts"):
        topos.append(("tp", ()))
    if mesh_axes.get("ep", 0) > 1 and hasattr(config, "n_experts"):
        topos.append(("ep", ()))

    widths = sorted({1, max(1, max_batch_cap)})
    out: List[Candidate] = []
    for topo, bounds in topos:
        for mb in widths:
            out.append(Candidate(topo, bounds, "admission", mb))
            if mb > 1 and topo == "single":
                out.append(Candidate(topo, bounds, "iter", mb))
            if kv_pool_blocks and topo == "single":
                mode = "iter" if mb > 1 else "admission"
                out.append(Candidate(topo, bounds, mode, mb,
                                     kv_pool_blocks, kv_block_size))
    # kvp: the paged pool's kv-head plane sharded over its own mesh axis
    # (multi-axis rows — kvp alone with replicated params, or kvp x tp
    # with the descriptor-derived param sharding on top). There is
    # nothing to shard without a pool, and the pool composes at
    # MAX_BATCH=1 admission outside the iter loop, so these rows carry
    # exactly that shape; divisibility/pspec legality is gate_candidate's
    # job as always (an indivisible kv-head count shows up as a rejected
    # row with diagnostics, not a missing one).
    if mesh_axes.get("kvp", 0) > 1 and kv_pool_blocks:
        out.append(Candidate("kvp", (), "admission", 1,
                             kv_pool_blocks, kv_block_size))
        if mesh_axes.get("tp", 0) > 1 and not hasattr(config, "n_experts"):
            out.append(Candidate("kvp-tp", (), "admission", 1,
                                 kv_pool_blocks, kv_block_size))
    return out


# -- gate: the existing semantic verifier ------------------------------------


def gate_candidate(module, config, cand: Candidate,
                   mesh_axes: Dict[str, int], max_seq: int,
                   ) -> Tuple[List[Finding], Optional[dict]]:
    """Every check the verifier already owns, plus the serving layer's
    own composition guards, run statically. Non-empty findings =
    rejected (never scored), with the same diagnostics ``python -m
    tools.graftcheck`` would print. Returns ``(findings, pspecs)`` —
    ``pspecs`` is the derived sharding tree for tp/ep candidates."""
    from . import semantic
    where = cand.label()
    findings: List[Finding] = []

    def guard(ok: bool, msg: str):
        if not ok:
            findings.append(Finding("plan-gate", _APP_PATH, 1, where, msg))

    # serving composition rules (mirrors serving/app.py's startup guards)
    guard(cand.batch_mode != "iter" or cand.max_batch > 1,
          "BATCH_MODE=iter requires MAX_BATCH > 1")
    from llm_sharding_demo_tpu.models import is_window_independent
    if cand.batch_mode == "iter" or cand.kv_pool_blocks:
        guard(is_window_independent(config),
              f"{type(config).__name__} is window-dependent (capacity "
              "routing); iter scheduling / paged KV serve dense families")
    if cand.kv_pool_blocks:
        guard(cand.topology in ("single", "kvp", "kvp-tp"),
              "KV_POOL_BLOCKS drives the paged engine's storage (single "
              "or kvp-sharded pool planes); PP/EP/TP_DECODE keep "
              "contiguous caches")
        guard(cand.max_batch == 1 or cand.batch_mode == "iter",
              "KV_POOL_BLOCKS batches through BATCH_MODE=iter")
        guard(max_seq % cand.kv_block_size == 0,
              f"MAX_SEQ={max_seq} must be a multiple of KV_BLOCK_SIZE="
              f"{cand.kv_block_size}")
    if cand.batch_mode == "iter":
        guard(cand.topology == "single",
              "BATCH_MODE=iter drives the single-device engine's segment "
              "loop; PP/EP/TP_DECODE use BATCH_MODE=admission")
    desc = getattr(module, "SHARDING_DESCRIPTOR", {})
    if cand.topology == "tp":
        tp = mesh_axes.get("tp", 1)
        for field in desc.get("tp_divisors", ()):
            v = getattr(config, field)
            guard(v % tp == 0,
                  f"TP_DECODE: {field}={v} not divisible by the "
                  f"{tp}-device tp axis (attention shards whole heads)")
    if cand.topology == "ep":
        ep = mesh_axes.get("ep", 1)
        for field in desc.get("ep_divisors", ()):
            v = getattr(config, field)
            guard(v % ep == 0,
                  f"EP_DECODE: {field}={v} not divisible by the "
                  f"{ep}-device ep axis")
    if cand.topology in ("kvp", "kvp-tp"):
        kvp = mesh_axes.get("kvp", 1)
        guard(cand.kv_pool_blocks > 0,
              "KVP_DECODE shards the paged pool's kv-head plane; it "
              "requires KV_POOL_BLOCKS")
        fields = desc.get("kvp_divisors")
        if fields is None:
            # a family that never declared which config field the
            # kvp axis divides is unreviewable, not implicitly legal
            guard(False,
                  f"KVP_DECODE: {type(config).__name__}'s family "
                  "declares no kvp_divisors in its SHARDING_DESCRIPTOR "
                  "— the pool-plane sharding is unreviewable")
        else:
            for field in fields:
                v = getattr(config, field)
                guard(v % kvp == 0,
                      f"KVP_DECODE: {field}={v} not divisible by the "
                      f"{kvp}-device kvp axis (pool planes shard whole "
                      "kv heads)")
        if cand.topology == "kvp-tp":
            tp = mesh_axes.get("tp", 1)
            for field in desc.get("tp_divisors", ()):
                v = getattr(config, field)
                guard(v % tp == 0,
                      f"TP_DECODE: {field}={v} not divisible by the "
                      f"{tp}-device tp axis (attention shards whole "
                      "heads)")
    if findings:
        return findings, None

    # semantic verifier gates
    pspecs = None
    if cand.topology == "pp":
        findings.extend(semantic.check_stage_contracts(
            module, config, cand.boundaries, max_seq=min(max_seq, 32),
            where=where))
        findings.extend(semantic.check_ring_program(cand.n_stages, where))
    if cand.topology in ("tp", "ep", "kvp-tp"):
        pspecs = derive_pspecs(module, config, mesh_axes)
        findings.extend(semantic.check_pspec_tree(
            pspecs, param_avals(module, config), mesh_axes, where))
    if cand.topology in ("kvp", "kvp-tp"):
        # the pool-plane spec itself through the SAME pspec validity
        # checks every hand-written spec goes through (placement.
        # check_pspec — the relocated single source of truth): the
        # [L, NB+1, 2, Hkv, bs, hd] planes shard whole kv heads (dim 3)
        # over kvp and nothing else
        from jax.sharding import PartitionSpec as P
        from .placement import check_pspec
        heads = getattr(config, "n_kv_head", config.n_head)
        plane = (config.n_layer, cand.kv_pool_blocks + 1, 2, heads,
                 cand.kv_block_size, config.head_dim)
        findings.extend(check_pspec(
            P(None, None, None, "kvp"), plane, mesh_axes,
            f"{where}:pool-plane"))
    if cand.kv_pool_blocks:
        heads = getattr(config, "n_kv_head", config.n_head)
        findings.extend(semantic.check_paged_contracts(
            n_layer=config.n_layer, num_blocks=cand.kv_pool_blocks,
            n_kv_head=heads, block_size=cand.kv_block_size,
            head_dim=config.head_dim, max_seq=max_seq,
            batches=(1, cand.max_batch), where=where))
    return findings, pspecs


# -- scoring -----------------------------------------------------------------


@dataclasses.dataclass
class PlanRow:
    candidate: Candidate
    ok: bool
    findings: List[Finding] = dataclasses.field(default_factory=list)
    comm_bytes_per_token: int = 0
    param_bytes_per_device: int = 0
    kv_bytes_per_device: int = 0
    act_bytes: int = 0
    hbm_bytes_per_device: int = 0
    programs: Dict[str, int] = dataclasses.field(default_factory=dict)
    programs_exact: bool = False
    cost_per_token: float = float("inf")
    note: str = ""

    @property
    def program_total(self) -> int:
        return sum(self.programs.values())

    def sort_key(self):
        c = self.candidate
        simplicity = (c.kv_pool_blocks > 0, c.batch_mode != "admission",
                      c.max_batch, c.n_stages, c.topology)
        return (not self.ok, self.cost_per_token, self.program_total,
                self.hbm_bytes_per_device, simplicity)

    def to_dict(self) -> dict:
        return {
            "config": dataclasses.asdict(self.candidate),
            "label": self.candidate.label(),
            "ok": self.ok,
            "cost_per_token": (None if math.isinf(self.cost_per_token)
                               else round(self.cost_per_token, 1)),
            "comm_bytes_per_token": self.comm_bytes_per_token,
            "param_bytes_per_device": self.param_bytes_per_device,
            "kv_bytes_per_device": self.kv_bytes_per_device,
            "peak_activation_bytes": self.act_bytes,
            "hbm_bytes_per_device": self.hbm_bytes_per_device,
            "programs": dict(self.programs),
            "program_total": self.program_total,
            "programs_exact": self.programs_exact,
            "serving_env": self.candidate.serving_env(),
            "note": self.note,
            "findings": [f.to_dict() for f in self.findings],
        }


def traffic_calls(traffic: Sequence[TrafficRow], max_batch: int):
    """The traffic mix as the ``GenerateCall`` rows the admission
    batcher would form: full ``max_batch``-wide rounds plus the
    remainder round per shape class."""
    from . import recompile as R
    greedy = R.greedy_sampling()
    calls = []
    for row in traffic:
        left = row.count
        while left > 0:
            b = min(left, max_batch)
            left -= b
            calls.append(R.GenerateCall(
                prompt_lens=(row.prompt_len,) * b, max_new=row.max_new,
                sampling=greedy))
    return calls


def count_programs(cand: Candidate, max_seq: int,
                   traffic: Sequence[TrafficRow],
                   ) -> Tuple[Dict[str, int], bool]:
    """Compiled-program population per entry point, via the EXISTING
    certifier machinery. Exact (== observed jit cache size, the
    recompile.certify guarantee) for admission-mode engine candidates
    and the solo paged runner. Iter-mode and pp rows are static
    ESTIMATES marked inexact: iter enumerates live widths
    1..max_batch (rows join/retire dynamically, each live width is a
    compiled program — paged-iter additionally merges the pool's
    gather/scatter movers per width, though admission-merge/CoW
    programs mint on demand and are not statically enumerable), and pp
    is keyed by the decoder's own (batch, prompt_len)/(batch, steps,
    sampling) structure but not yet pinned against a live multi-device
    cache."""
    from . import recompile as R
    desc = R.EngineDesc(max_seq=max_seq)
    if cand.batch_mode == "iter":
        pools: Dict[str, set] = {}
        for w in range(1, cand.max_batch + 1):
            wide = [TrafficRow(r.prompt_len, r.max_new, w) for r in traffic]
            for call in traffic_calls(wide, w):
                if cand.kv_pool_blocks:
                    paged = R.PagedDesc(max_seq=max_seq,
                                        block_size=cand.kv_block_size)
                    keysets = R.paged_runner_keys(desc, paged, call)
                else:
                    keysets = R.engine_call_keys(desc, call)
                for name, ks in keysets.items():
                    pools.setdefault(name, set()).update(ks)
        return {n: len(ks) for n, ks in pools.items()}, False
    calls = traffic_calls(traffic, cand.max_batch)
    if cand.kv_pool_blocks:
        paged = R.PagedDesc(max_seq=max_seq, block_size=cand.kv_block_size)
        # kvp rows shard the same paged movers: the population is the
        # certified single-device one, but not yet pinned against a
        # live kvp-mesh jit cache — estimate, like pp
        return (R.certify_paged(desc, paged, calls),
                cand.topology == "single")
    if cand.topology == "pp":
        keys_p, keys_d = set(), set()
        for call in calls:
            b = len(call.prompt_lens)
            keys_p.add((b, max(call.prompt_lens)))
            keys_d.add((b, call.max_new, call.sampling))
        return {"_prefill": len(keys_p), "_decode": len(keys_d)}, False
    return R.certify(desc, calls), True


def score_candidate(module, config, cand: Candidate,
                    mesh_axes: Dict[str, int], max_seq: int,
                    traffic: Sequence[TrafficRow], pspecs,
                    hbm_gb: float = DEFAULT_HBM_GB,
                    ici_byte_weight: Optional[float] = None) -> PlanRow:
    """Price one verifier-clean candidate. See the module docstring for
    the cost terms; everything here is avals and traced jaxprs.
    ``ici_byte_weight`` overrides the a-priori ``ICI_BYTE_WEIGHT`` —
    pass :func:`calibrate`'s measured value to score with this host's
    observed ICI cost instead of the model's guess."""
    row = PlanRow(candidate=cand, ok=True)
    conc = concurrency(traffic)
    eff_batch = max(1, min(cand.max_batch, conc))
    avals = param_avals(module, config)

    # params per device (pure kvp leaves params replicated — only the
    # pool planes shard; kvp-tp layers the descriptor-derived tp
    # sharding on top)
    if cand.topology in ("tp", "ep", "kvp-tp") and pspecs is not None:
        row.param_bytes_per_device = per_device_param_bytes(
            avals, pspecs, mesh_axes)
    elif cand.topology == "pp":
        from llm_sharding_demo_tpu.parallel import partition as Pt
        import jax
        specs = Pt.make_stage_specs(config.n_layer, cand.boundaries)
        stage_avals = jax.eval_shape(
            lambda p: Pt.partition_params(p, specs), avals)
        row.param_bytes_per_device = max(tree_bytes(s) for s in stage_avals)
    else:
        row.param_bytes_per_device = tree_bytes(avals)

    # KV state per device (the rows the config keeps resident)
    if cand.kv_pool_blocks:
        pool = kv_pool_bytes(config, cand.kv_pool_blocks,
                             cand.kv_block_size)
        kv_row = kv_cache_bytes(config, 1, max_seq)
        if cand.topology in ("kvp", "kvp-tp"):
            # pool planes shard whole kv heads over kvp: resident HBM
            # AND the per-token read stream both divide exactly (the
            # divisor gate already proved Hkv % kvp == 0)
            kvp = mesh_axes.get("kvp", 1)
            pool //= kvp
            kv_row //= kvp
        row.kv_bytes_per_device = pool
    else:
        kv_all = kv_cache_bytes(config, eff_batch, max_seq)
        if cand.topology == "pp":
            # a stage holds only its own layers' cache slice
            per = max((b - a) for a, b in zip(
                (0,) + cand.boundaries, cand.boundaries + (config.n_layer,)))
            kv_all = kv_all * per // config.n_layer
        elif cand.topology == "tp":
            tp = mesh_axes.get("tp", 1)
            heads = getattr(config, "n_kv_head", config.n_head)
            if heads % tp == 0:
                kv_all //= tp
        row.kv_bytes_per_device = kv_all
        kv_row = kv_all // eff_batch

    # comm per decode token
    if cand.topology == "pp":
        row.comm_bytes_per_token = pp_decode_comm_bytes(
            cand.n_stages, batch=eff_batch, module=module, config=config)
    elif cand.topology == "tp":
        row.comm_bytes_per_token = tp_decode_comm_bytes(
            config, eff_batch, mesh_axes["tp"])
    elif cand.topology == "ep":
        row.comm_bytes_per_token = ep_decode_comm_bytes(
            config, eff_batch, mesh_axes["ep"])
    elif cand.topology == "kvp":
        row.comm_bytes_per_token = kvp_decode_comm_bytes(
            config, eff_batch, mesh_axes["kvp"])
    elif cand.topology == "kvp-tp":
        # the two axes' schedules compose additively: per block the tp
        # psums AND the kvp partial-softmax gather both cross the ICI
        row.comm_bytes_per_token = (
            kvp_decode_comm_bytes(config, eff_batch, mesh_axes["kvp"])
            + tp_decode_comm_bytes(config, eff_batch, mesh_axes["tp"]))

    row.act_bytes = peak_activation_bytes(module, config, eff_batch,
                                          min(max_seq, 128))
    row.hbm_bytes_per_device = (row.param_bytes_per_device
                                + row.kv_bytes_per_device + row.act_bytes)
    budget = int(hbm_gb * (1 << 30))
    if row.hbm_bytes_per_device > budget:
        row.ok = False
        row.note = (f"infeasible: {row.hbm_bytes_per_device} bytes/device "
                    f"exceeds the {hbm_gb} GiB HBM budget")
        return row

    row.programs, row.programs_exact = count_programs(cand, max_seq, traffic)

    paged_overhead = (2 * kv_row / PAGED_SEG_STEPS
                      if cand.kv_pool_blocks else 0.0)
    weight_term = row.param_bytes_per_device / eff_batch
    ici_w = (ICI_BYTE_WEIGHT if ici_byte_weight is None
             else float(ici_byte_weight))
    row.cost_per_token = (weight_term + kv_row + paged_overhead
                          + ici_w * row.comm_bytes_per_token)
    return row


# -- the planner -------------------------------------------------------------


class CalibrationError(ValueError):
    """A calibration row is PRESENT in the journal but unparsable —
    malformed fields, non-numeric ratios, inconsistent byte splits.
    Distinct from a *skipped* row, which is an
    honest environment fact and calibrates nothing (``None``): a
    malformed measurement silently falling back to the a-priori weight
    is exactly how a broken journal writer would hide for rounds."""


def calibrate(journal) -> Optional[float]:
    """Measured ICI byte weight from a bench journal's
    ``ici_byte_weight_calibration`` row (the measurement half of the
    measure->model loop, ROADMAP item 5): the row journals the
    compiled executable's network bytes against the model's formula as
    ``measured_over_modeled``, and the weight the row was measured
    AGAINST as ``ici_byte_weight`` — the calibrated weight is their
    product, so a plan scored with it prices ICI traffic at what this
    host's XLA actually scheduled. Accepts a raw bench payload, a
    ``BENCH_rNN.json`` driver row (``parsed`` wrapper), or the config
    row itself; returns None when the journal carries no calibration
    row at all or a genuinely SKIPPED one (e.g. off-chip) — callers
    fall back to the a-priori ``ICI_BYTE_WEIGHT``. A row that is
    present but unparsable (malformed/partial fields) raises
    :class:`CalibrationError` instead: silently scoring with the
    a-priori weight would hide a broken journal writer forever."""
    doc = journal
    if isinstance(doc, dict) and "parsed" in doc:
        doc = doc.get("parsed")
    if not isinstance(doc, dict):
        return None
    row = None
    if doc.get("name") == "ici_byte_weight_calibration":
        row = doc
    else:
        for cfg in doc.get("configs") or ():
            if isinstance(cfg, dict) \
                    and cfg.get("name") == "ici_byte_weight_calibration":
                row = cfg
                break
    if row is None or row.get("skipped") or row.get("error"):
        return None
    ratio = row.get("measured_over_modeled")
    if not isinstance(ratio, (int, float)) or isinstance(ratio, bool) \
            or ratio <= 0:
        raise CalibrationError(
            "ici_byte_weight_calibration row is present but its "
            f"measured_over_modeled={ratio!r} is not a positive number "
            "— refusing to fall back silently on a malformed row "
            "(skipped rows calibrate nothing; malformed rows fail "
            "loudly)")
    base = row.get("ici_byte_weight")
    if base is None:
        base = ICI_BYTE_WEIGHT        # older rows omit the base weight
    elif not isinstance(base, (int, float)) or isinstance(base, bool) \
            or base <= 0:
        raise CalibrationError(
            "ici_byte_weight_calibration row is present but its "
            f"ici_byte_weight={base!r} is not a positive number — "
            "the row does not say what weight it was measured against")
    return float(base) * float(ratio)


def plan(module, config, mesh_axes: Dict[str, int], max_seq: int = 64,
         traffic: Optional[Sequence[TrafficRow]] = None,
         max_batch_cap: int = 8, kv_pool_blocks: int = 0,
         kv_block_size: int = 16, hbm_gb: float = DEFAULT_HBM_GB,
         include_unsharded: bool = True,
         ici_byte_weight: Optional[float] = None) -> dict:
    """The library API behind ``python -m tools.graftcheck plan``:
    enumerate -> gate -> score -> rank. Returns the JSON-able payload
    (schema: docs/ARCHITECTURE.md "Planning"); ``chosen`` is the
    top-ranked verifier-clean feasible row, or None when nothing
    survives. ``ici_byte_weight`` (see :func:`calibrate`) re-prices
    every candidate's ICI term with a measured weight."""
    traffic = tuple(traffic) if traffic else DEFAULT_TRAFFIC
    rows: List[PlanRow] = []
    for cand in enumerate_candidates(module, config, mesh_axes, max_seq,
                                     max_batch_cap, kv_pool_blocks,
                                     kv_block_size,
                                     include_unsharded=include_unsharded):
        findings, pspecs = gate_candidate(module, config, cand, mesh_axes,
                                          max_seq)
        if findings:
            rows.append(PlanRow(candidate=cand, ok=False, findings=findings,
                                note="rejected by the semantic verifier"))
            continue
        rows.append(score_candidate(module, config, cand, mesh_axes,
                                    max_seq, traffic, pspecs, hbm_gb,
                                    ici_byte_weight=ici_byte_weight))
    rows.sort(key=PlanRow.sort_key)
    chosen = next((r for r in rows if r.ok), None)
    return {
        "model": type(config).__name__,
        "mesh": dict(mesh_axes),
        "ici_byte_weight": (ICI_BYTE_WEIGHT if ici_byte_weight is None
                            else float(ici_byte_weight)),
        # provenance for the weight above: a payload scored with a
        # measured weight (startup calibration or a live grafttrend
        # refit) must be distinguishable from one priced a-priori —
        # two plan files can disagree on ranking for THIS reason alone
        "ici_byte_weight_source": ("a-priori" if ici_byte_weight is None
                                   else "provided"),
        "max_seq": max_seq,
        "traffic": [r.to_dict() for r in traffic],
        "plan": [r.to_dict() for r in rows],
        "chosen": chosen.to_dict() if chosen is not None else None,
        "rejected": sum(1 for r in rows if not r.ok),
    }


def plan_for_serving(config, n_devices: int, max_seq: int,
                     traffic: Optional[Sequence[TrafficRow]] = None,
                     max_batch_cap: int = 8, kv_pool_blocks: int = 0,
                     kv_block_size: int = 16,
                     hbm_gb: float = DEFAULT_HBM_GB) -> dict:
    """The AUTO_PLAN entry point: given the loaded model config and the
    pod's device count, search every single-axis mesh assignment of the
    devices (tp / ep / pp / unsharded) and return one merged payload
    whose ``chosen`` row is the global best."""
    from llm_sharding_demo_tpu.models import family_module
    module = family_module(config)
    meshes: List[Dict[str, int]] = [{}]
    if n_devices > 1:
        for axis in ("tp", "ep", "pp"):
            meshes.append({axis: n_devices})
    merged: Optional[dict] = None
    all_rows: List[dict] = []
    best: Optional[dict] = None
    for mesh_axes in meshes:
        # unsharded candidates score once (the no-mesh pass) — they are
        # mesh-independent, and re-scoring them per candidate mesh
        # would both waste startup tracing and duplicate table rows
        payload = plan(module, config, mesh_axes, max_seq=max_seq,
                       traffic=traffic, max_batch_cap=max_batch_cap,
                       kv_pool_blocks=kv_pool_blocks,
                       kv_block_size=kv_block_size, hbm_gb=hbm_gb,
                       include_unsharded=not mesh_axes)
        if merged is None:
            merged = payload
        for row in payload["plan"]:
            row = dict(row)
            row["mesh"] = dict(mesh_axes)
            all_rows.append(row)
        c = payload["chosen"]
        if c is not None:
            c = dict(c, mesh=dict(mesh_axes))
            if best is None or (c["cost_per_token"], c["program_total"]) < \
                    (best["cost_per_token"], best["program_total"]):
                best = c
    assert merged is not None
    all_rows.sort(key=lambda r: (not r["ok"],
                                 r["cost_per_token"] is None,
                                 r["cost_per_token"] or 0))
    merged["plan"] = all_rows
    merged["chosen"] = best
    merged["mesh"] = {"devices": n_devices}
    merged["rejected"] = sum(1 for r in all_rows if not r["ok"])
    return merged
