"""Pass 1: semantic contract verification by abstract evaluation.

Everything here runs under ``jax.eval_shape`` / ``jax.make_jaxpr`` on
CPU-mesh stand-ins (``jax.sharding.AbstractMesh``): shapes and dtypes
propagate through the REAL model/partition code, but no model program is
compiled and no device computes — the whole pass traces in well under a
second, so it runs on every test invocation.

Checks (each a function usable standalone on fixtures; ``run_semantic``
drives them over the registry):

- **Inter-stage contracts** (``check_stage_contracts``): for a family x
  partition plan, every stage's output aval must equal the next stage's
  input aval — ``[B, S]`` int32 into stage 0, the family hidden aval
  ``[B, S, D]`` (engine dtype) between stages (uneven/padded plans
  included), ``[B, S, vocab]`` out of the last — and each stage's cache
  must come back shape/dtype-identical (the decode scan carries it).
- **Partition plan validity** (``check_partition_plan``): overlapping /
  non-exhaustive / empty-stage plans are rejected with the partitioner's
  own diagnostic, surfaced as a finding.
- **Padded stacking round-trip** (``check_padded_stacking``): for uneven
  plans, ``unstack(stack(params))`` must reproduce the block avals
  exactly and the validity mask must count exactly ``n_layer`` true
  rows.
- **PartitionSpec validity** (``check_pspec_tree``): every spec leaf
  names only axes the mesh has, has rank <= array rank, uses no mesh
  axis twice, and shards only dims divisible by the axis size.
- **ppermute bijection** (``check_permutation`` /
  ``collect_ppermutes``): the stage-ring permutation must be a partial
  bijection over the axis (each source/destination at most once, all in
  range). ``collect_ppermutes`` extracts the pairs from a traced
  function's jaxpr (recursing into scan/while/cond/pjit/shard_map
  bodies), so the property is checked on what the program WILL run, not
  on what a docstring says.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

from .core import Finding

_PARTITION_PATH = "llm_sharding_demo_tpu/parallel/partition.py"
_PPDECODE_PATH = "llm_sharding_demo_tpu/parallel/ppdecode.py"
_SPMD_PATH = "llm_sharding_demo_tpu/parallel/spmd.py"


# -- partition plans ---------------------------------------------------------


def check_partition_plan(n_layer: int, boundaries: Sequence[int],
                         where: str = "plan") -> List[Finding]:
    """A plan must partition [0, n_layer) disjointly and exhaustively;
    the partitioner's ValueError is the precise diagnostic."""
    from llm_sharding_demo_tpu.parallel import partition as Pt
    try:
        Pt.make_stage_specs(n_layer, boundaries)
    except ValueError as e:
        return [Finding("stage-contract", _PARTITION_PATH, 1, where,
                        f"rejected partition plan: {e}")]
    return []


def check_spec_list(specs, n_layer: int, where: str = "specs",
                    ) -> List[Finding]:
    """``validate_specs`` as a finding source — overlapping stages,
    gaps, and index/n_stages inconsistencies in an externally built
    stage list."""
    from llm_sharding_demo_tpu.parallel import partition as Pt
    try:
        Pt.validate_specs(specs, n_layer)
    except ValueError as e:
        return [Finding("stage-contract", _PARTITION_PATH, 1, where,
                        f"rejected stage list: {e}")]
    return []


# -- inter-stage shape/dtype contracts ---------------------------------------


def _param_avals(module, config):
    import jax
    key = jax.random.PRNGKey(0)
    return jax.eval_shape(lambda k: module.init_params(config, k), key)


def check_stage_chain(stage_fns, first_in_aval, mid_aval, last_out_aval,
                      where: str) -> List[Finding]:
    """Generic chain checker: ``stage_fns[i]`` maps (x_aval) ->
    (out_aval, cache_delta_ok: bool). Used by the fixture tests with
    deliberately broken stages; ``check_stage_contracts`` builds the
    real stage closures and delegates here."""
    import jax
    findings: List[Finding] = []
    x = first_in_aval
    n = len(stage_fns)
    for i, fn in enumerate(stage_fns):
        try:
            out, cache_ok = fn(x)
        except Exception as e:  # noqa: BLE001 — a trace abort IS the finding
            findings.append(Finding(
                "stage-contract", _PARTITION_PATH, 1, where,
                f"stage {i} rejects its input aval "
                f"{tuple(x.shape)}/{x.dtype}: {type(e).__name__}: {e}"))
            return findings
        expect = last_out_aval if i == n - 1 else mid_aval
        if (tuple(out.shape) != tuple(expect.shape)
                or out.dtype != expect.dtype):
            findings.append(Finding(
                "stage-contract", _PARTITION_PATH, 1, where,
                f"stage {i} emits {tuple(out.shape)}/{out.dtype}, the "
                f"{'head contract' if i == n - 1 else 'next stage'} "
                f"expects {tuple(expect.shape)}/{expect.dtype}"))
        if not cache_ok:
            findings.append(Finding(
                "stage-contract", _PARTITION_PATH, 1, where,
                f"stage {i} returns a cache whose avals differ from its "
                "input cache (the decode scan carries it fixed-shape)"))
        x = out
    return findings


def check_stage_contracts(module, config, boundaries: Sequence[int],
                          batch: int = 2, seq: int = 6, max_seq: int = 32,
                          where: str = "", dtype=None) -> List[Finding]:
    """The registry-driven form: build the plan's stage closures over
    ``partition.stage_apply`` + per-stage caches and run the chain
    checker — all under eval_shape."""
    import jax
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.parallel import partition as Pt
    dtype = dtype or jnp.float32
    bad = check_partition_plan(config.n_layer, boundaries, where)
    if bad:
        return bad
    specs = Pt.make_stage_specs(config.n_layer, boundaries)
    params_aval = _param_avals(module, config)
    stage_avals = jax.eval_shape(
        lambda p: Pt.partition_params(p, specs), params_aval)

    def tree_avals_equal(a, b) -> bool:
        la = jax.tree_util.tree_leaves(a)
        lb = jax.tree_util.tree_leaves(b)
        return (len(la) == len(lb)
                and all(tuple(x.shape) == tuple(y.shape)
                        and x.dtype == y.dtype for x, y in zip(la, lb)))

    def make_fn(sp_aval, spec):
        cache_aval = jax.eval_shape(
            functools.partial(Pt.make_stage_cache, spec, config, batch,
                              max_seq, dtype))

        def fn(x_aval):
            out, cache_out = jax.eval_shape(
                lambda sp, x, c: Pt.stage_apply(sp, spec, config, x, c),
                sp_aval, x_aval, cache_aval)
            return out, tree_avals_equal(cache_aval, cache_out)

        return fn

    first_in = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    mid = jax.ShapeDtypeStruct((batch, seq, config.n_embd), dtype)
    last_out = jax.ShapeDtypeStruct((batch, seq, config.vocab_size),
                                    jnp.float32)
    fns = [make_fn(sp, spec) for sp, spec in zip(stage_avals, specs)]
    return check_stage_chain(fns, first_in, mid, last_out, where)


def check_padded_stacking(module, config, boundaries: Sequence[int],
                          where: str = "") -> List[Finding]:
    """Uneven-plan stacking: round-trip aval identity + mask row counts."""
    import jax
    import numpy as np

    from llm_sharding_demo_tpu.parallel import partition as Pt
    specs = Pt.make_stage_specs(config.n_layer, boundaries)
    params_aval = _param_avals(module, config)
    findings: List[Finding] = []

    rt = jax.eval_shape(
        lambda p: Pt.unstack_stage_params_padded(
            Pt.stack_stage_params_padded(p, specs)[0], specs), params_aval)
    orig = params_aval["blocks"]
    ra = jax.tree_util.tree_leaves(rt)
    oa = jax.tree_util.tree_leaves(orig)
    if (len(ra) != len(oa)
            or any(tuple(x.shape) != tuple(y.shape) or x.dtype != y.dtype
                   for x, y in zip(ra, oa))):
        findings.append(Finding(
            "stage-contract", _PARTITION_PATH, 1, where,
            "padded stack/unstack round-trip does not reproduce the "
            "block avals"))
    mask = np.asarray(Pt.stage_valid_mask(specs))
    per_max = max(s.n_blocks for s in specs)
    if mask.shape != (len(specs), per_max):
        findings.append(Finding(
            "stage-contract", _PARTITION_PATH, 1, where,
            f"validity mask shape {mask.shape}, want "
            f"{(len(specs), per_max)}"))
    elif int(mask.sum()) != config.n_layer:
        findings.append(Finding(
            "stage-contract", _PARTITION_PATH, 1, where,
            f"validity mask marks {int(mask.sum())} real layers, model "
            f"has {config.n_layer} — padded stages would execute the "
            "wrong layer set"))
    return findings


# -- PartitionSpec validity --------------------------------------------------


def check_pspec(spec, shape: Tuple[int, ...], mesh_axes: Dict[str, int],
                where: str) -> List[Finding]:
    """One spec against one array shape and a mesh's {axis: size}.

    Thin call-through: the axis-exists / rank-fits / axis-used-once /
    divisibility logic lives in the placement pass now (tools/
    graftcheck/placement.py — the single source of truth the planner's
    kvp gate also uses); the signature and the Finding shape (rule
    ``pspec`` against parallel/spmd.py) stay pinned here for the
    existing fixtures."""
    from .placement import check_pspec as _impl
    return _impl(spec, shape, mesh_axes, where)


def check_pspec_tree(specs_tree, aval_tree, mesh_axes: Dict[str, int],
                     where: str) -> List[Finding]:
    """Walk a pspec pytree against a matching aval pytree (dict-shaped,
    PartitionSpec leaves — the ``spmd.*_pspecs`` layout)."""
    import jax
    from jax.sharding import PartitionSpec
    findings: List[Finding] = []

    def walk(spec_node, aval_node, path: str):
        if isinstance(spec_node, PartitionSpec):
            leaves = jax.tree_util.tree_leaves(aval_node)
            if len(leaves) != 1:
                findings.append(Finding(
                    "pspec", _SPMD_PATH, 1, where,
                    f"{path}: one spec for {len(leaves)} arrays"))
                return
            findings.extend(check_pspec(
                spec_node, tuple(leaves[0].shape), mesh_axes,
                f"{where}/{path}"))
        elif isinstance(spec_node, dict):
            if not isinstance(aval_node, dict) or (
                    set(spec_node) != set(aval_node)):
                findings.append(Finding(
                    "pspec", _SPMD_PATH, 1, where,
                    f"{path}: spec tree keys {sorted(spec_node)} != "
                    f"param keys "
                    f"{sorted(aval_node) if isinstance(aval_node, dict) else type(aval_node).__name__}"))
                return
            for k in spec_node:
                walk(spec_node[k], aval_node[k], f"{path}.{k}" if path
                     else str(k))
        else:
            findings.append(Finding(
                "pspec", _SPMD_PATH, 1, where,
                f"{path}: unexpected spec node {type(spec_node).__name__}"))

    walk(specs_tree, aval_tree, "")
    return findings


# -- ppermute bijection ------------------------------------------------------


def check_permutation(pairs: Sequence[Tuple[int, int]], axis_size: int,
                      where: str) -> List[Finding]:
    """Partial-bijection check over a ``ppermute`` pair list: every
    source and every destination at most once, all indices in range.
    (A duplicate destination silently SUMS contributions on some
    backends and is undefined on others; a duplicate source double-sends
    — both are wiring bugs no runtime test at the wrong axis size would
    see.)"""
    problems: List[str] = []
    srcs: Dict[int, int] = {}
    dsts: Dict[int, int] = {}
    for i, (s, d) in enumerate(pairs):
        if not (0 <= s < axis_size) or not (0 <= d < axis_size):
            problems.append(
                f"pair {i} = ({s}, {d}) out of range for axis size "
                f"{axis_size}")
        if s in srcs:
            problems.append(
                f"source {s} appears in pairs {srcs[s]} and {i} — not a "
                "bijection (double-send)")
        srcs.setdefault(s, i)
        if d in dsts:
            problems.append(
                f"destination {d} appears in pairs {dsts[d]} and {i} — "
                "not a bijection (colliding receives)")
        dsts.setdefault(d, i)
    return [Finding("ppermute", _PPDECODE_PATH, 1, where, p)
            for p in problems]


def collect_ppermutes(fn, *avals) -> List[Tuple[tuple, tuple]]:
    """Trace ``fn`` (no compile, no execute) and return every
    ``ppermute`` equation's ``(axis_name, perm)`` — recursing into
    scan/while/cond/pjit/shard_map sub-jaxprs, so permutations inside
    compiled-loop bodies are found too."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*avals)
    found: List[Tuple[tuple, tuple]] = []

    def walk(jxp):
        for eqn in jxp.eqns:
            if eqn.primitive.name == "ppermute":
                found.append((tuple(eqn.params.get("axis_name", ())),
                              tuple(eqn.params.get("perm", ()))))
            for v in eqn.params.values():
                sub = getattr(v, "jaxpr", None)
                if sub is not None and hasattr(sub, "eqns"):
                    walk(sub)
                elif hasattr(v, "eqns"):
                    walk(v)
                elif isinstance(v, (tuple, list)):
                    for item in v:
                        sub = getattr(item, "jaxpr", None)
                        if sub is not None and hasattr(sub, "eqns"):
                            walk(sub)
                        elif hasattr(item, "eqns"):
                            walk(item)

    walk(jaxpr.jaxpr)
    return found


def check_ring_program(n_stages: int, where: str) -> List[Finding]:
    """Trace a shard_map stand-in that ppermutes with the REAL
    ``stage_ring_permutation`` over an AbstractMesh of ``n_stages``
    devices, extract the permutation from the jaxpr, and verify the
    bijection property — end-to-end through the same machinery a full
    program check would use, with zero devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from llm_sharding_demo_tpu.parallel.ppdecode import \
        stage_ring_permutation
    if n_stages < 2:
        # the declared helper must still behave (empty pair list)
        return check_permutation(stage_ring_permutation(n_stages),
                                 max(n_stages, 1), where)
    try:
        from jax import shard_map  # newer spelling
        smap = functools.partial(shard_map, axis_names={"pp"})
    except ImportError:
        from jax.experimental.shard_map import shard_map as smap
    mesh = AbstractMesh((n_stages,), ("pp",))

    def per_device(x):
        return jax.lax.ppermute(x, "pp", stage_ring_permutation(n_stages))

    fn = smap(per_device, mesh=mesh, in_specs=(P("pp"),), out_specs=P("pp"))
    aval = jax.ShapeDtypeStruct((n_stages, 4), jnp.float32)
    perms = collect_ppermutes(fn, aval)
    if not perms:
        return [Finding("ppermute", _PPDECODE_PATH, 1, where,
                        "traced ring program contains no ppermute — "
                        "extraction or wiring broke")]
    findings: List[Finding] = []
    for axis_name, perm in perms:
        findings.extend(check_permutation(perm, n_stages, where))
    return findings


# -- overlap lint (collectives vs compute) -----------------------------------

# comm primitives the overlap rule (and the cost model's byte walker,
# tools/graftcheck/costmodel.py) recognize in a traced jaxpr
# (``psum_invariant`` is what ``lax.psum`` traces to inside a
# ``shard_map`` that tracks varying types — the default)
COMM_PRIMITIVES = ("ppermute", "psum", "psum_invariant", "all_gather",
                   "all_to_all", "reduce_scatter", "pmax", "pmin")

# primitives that are pure data movement/bookkeeping — never the compute
# a transfer could overlap with
_TRIVIAL_PRIMITIVES = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "transpose",
    "convert_element_type", "slice", "concatenate", "iota", "select_n",
    "pad", "rev", "copy", "stop_gradient", "eq", "ne", "lt", "le", "gt",
    "ge", "add", "sub", "and", "or", "not", "pvary", "pcast",
    "axis_index", "squeeze_p",
})


def _sub_jaxprs(eqn):
    """Every sub-jaxpr a primitive's params carry (scan/while/cond/pjit/
    shard_map bodies), normalized to plain Jaxpr objects."""
    subs = []
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for item in items:
            inner = getattr(item, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                subs.append(inner)
            elif hasattr(item, "eqns"):
                subs.append(item)
    return subs


def check_overlap_jaxpr(jaxpr, where: str, path: str,
                        scope: str) -> List[Finding]:
    """Walk a traced jaxpr; inside every ``scan`` body, flag each
    collective that (a) feeds the scan's carry outputs and (b) consumes
    in-body compute — i.e. the transfer for step k sits strictly between
    step k's compute and step k+1's compute with nothing scheduled to
    hide it. That is the serial-handoff shape TokenWeave-style
    double-buffering (split the per-stage batch, overlap microbatch k's
    collective with k+1's compute) removes; a baselined finding is the
    declared decision NOT to overlap yet."""
    findings: List[Finding] = []

    def analyze_scan_body(body, num_carry: int, where_in: str):
        from jax.extend.core import Literal
        eqns = list(body.eqns)
        producer = {}
        for i, eqn in enumerate(eqns):
            for ov in eqn.outvars:
                producer[ov] = i
        # backward dependency closure per eqn (eqn indices it reads from)
        back: List[set] = []
        for i, eqn in enumerate(eqns):
            deps = set()
            for iv in eqn.invars:
                if isinstance(iv, Literal):
                    continue
                j = producer.get(iv)
                if j is not None:
                    deps.add(j)
                    deps |= back[j]
            back.append(deps)
        carry_outs = set(body.outvars[:num_carry])
        for i, eqn in enumerate(eqns):
            if eqn.primitive.name not in COMM_PRIMITIVES:
                continue
            # forward reach from this collective to the carry outputs
            reached = set(eqn.outvars)
            feeds_carry = bool(reached & carry_outs)
            for j in range(i + 1, len(eqns)):
                if i in back[j] or any(v in reached for v in eqns[j].invars):
                    back[j].add(i)
                    reached |= set(eqns[j].outvars)
            feeds_carry = feeds_carry or bool(reached & carry_outs)
            fed_by_compute = any(
                eqns[j].primitive.name not in _TRIVIAL_PRIMITIVES
                for j in back[i])
            if feeds_carry and fed_by_compute:
                findings.append(Finding(
                    "overlap", path, 1, scope,
                    f"{eqn.primitive.name} in {where_in} rides the scan "
                    "carry and consumes in-body compute: the transfer for "
                    "step k is strictly ordered between step k's and step "
                    "k+1's compute with no independent work to hide it "
                    "(double-buffer the microbatch to overlap, "
                    "TokenWeave-style)"))

    def walk(jxp):
        for eqn in jxp.eqns:
            if eqn.primitive.name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                analyze_scan_body(body, eqn.params["num_carry"],
                                  f"{where}: scan@{eqn.params.get('length')}")
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return findings


def build_ppdecode_programs(n_stages: int, batch: int = 1, seq: int = 8,
                            max_seq: int = 32, family: str = "gpt2",
                            module=None, config=None,
                            mesh=None) -> List[tuple]:
    """Trace the REAL ``PipelinedDecoder._pp_blocks`` step (the manual
    pipeline program both compiled phases run) over an ``AbstractMesh``
    stand-in — zero devices, zero compile. Returns ``(label, scope, fn,
    args)`` rows: one prefill-shaped step ([B, S, D] in) and one
    decode-shaped step ([B, 1, D] in). The overlap lint walks these; the
    cost model (costmodel.py) reads collective comm bytes off the same
    traced decode step, so what is linted and what is priced is the one
    program serving would run.

    ``module``/``config`` override the registry stand-in — the cost
    model passes the config actually being scored so the priced
    activations are that model's, not the tiny stand-in's; the overlap
    lint keeps the stand-ins (the property is shape-independent).
    ``mesh`` overrides the AbstractMesh stand-in with a CONCRETE mesh:
    bench.py's ICI calibration row compiles the returned decode step on
    real devices and compares the executable's measured comm bytes
    against the cost model's walk of the same jaxpr."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh

    from llm_sharding_demo_tpu.models.llama import LlamaConfig
    from llm_sharding_demo_tpu.parallel import partition as Pt
    from llm_sharding_demo_tpu.parallel.ppdecode import (
        GRAFTCHECK_DECODE_ENTRY_POINTS, PipelinedDecoder)
    from . import registry

    if module is None or config is None:
        fams = registry.families()
        module, config = fams["llama-tiny" if family == "llama"
                              else "gpt2-tiny"]
    if "_pp_blocks" not in GRAFTCHECK_DECODE_ENTRY_POINTS:
        raise ValueError(
            "ppdecode no longer declares _pp_blocks in "
            "GRAFTCHECK_DECODE_ENTRY_POINTS — update this builder to "
            "trace the declared entry points")
    bounds = Pt.balanced_boundaries(config.n_layer, n_stages)
    specs = Pt.make_stage_specs(config.n_layer, bounds)
    dec = PipelinedDecoder.__new__(PipelinedDecoder)
    dec.config = config
    dec.mesh = mesh if mesh is not None \
        else AbstractMesh((n_stages,), ("pp",))
    dec.max_seq = max_seq
    dec.pp_axis = "pp"
    dec.n_stages = n_stages
    dec.dtype = jnp.float32
    dec._llama = isinstance(config, LlamaConfig)
    if len({s.n_blocks for s in specs}) == 1:
        dec._valid = None
        dec.per_stage = specs[0].n_blocks
    else:
        dec._valid = Pt.stage_valid_mask(specs)
        dec.per_stage = max(s.n_blocks for s in specs)

    pavals = _param_avals(module, config)
    if dec._valid is None:
        blocks = jax.eval_shape(
            lambda p: Pt.stack_stage_params(p, specs), pavals)
    else:
        blocks = jax.eval_shape(
            lambda p: Pt.stack_stage_params_padded(p, specs)[0], pavals)
    heads = getattr(config, "n_kv_head", config.n_head)
    cache = jax.ShapeDtypeStruct(
        (n_stages, dec.per_stage, batch, heads, max_seq, config.head_dim),
        jnp.float32)
    length = jax.ShapeDtypeStruct((), jnp.int32)

    def step_fn(s: int):
        h = jax.ShapeDtypeStruct((batch, s, config.n_embd), jnp.float32)

        def fn(blocks, ck, cv, h, length):
            return dec._pp_blocks(blocks, ck, cv, h, length)

        return fn, (blocks, cache, cache, h, length)

    rows = []
    for label, s in (("prefill-step", seq), ("decode-step", 1)):
        fn, args = step_fn(s)
        rows.append((f"ppdecode/pp={n_stages}/{label}",
                     "PipelinedDecoder._pp_blocks", fn, args))
    return rows


def check_decode_overlap(n_stages: int, where: str) -> List[Finding]:
    """The registry-driven overlap pass: trace every declared pipelined
    decode program at this stage count and run the overlap rule on it."""
    import jax
    findings: List[Finding] = []
    for label, scope, fn, args in build_ppdecode_programs(n_stages):
        jaxpr = jax.make_jaxpr(fn)(*args)
        findings.extend(check_overlap_jaxpr(
            jaxpr, f"{where}/{label}", _PPDECODE_PATH, scope))
    return findings


# -- paged KV block-table contracts ------------------------------------------

_KV_POOL_PATH = "llm_sharding_demo_tpu/runtime/kv_pool.py"
_PAGED_OPS_PATH = "llm_sharding_demo_tpu/ops/paged_attention.py"


def check_paged_contracts(n_layer: int, num_blocks: int, n_kv_head: int,
                          block_size: int, head_dim: int, max_seq: int,
                          batches: Sequence[int] = (1, 2),
                          where: str = "") -> List[Finding]:
    """The paged block-table contract family, by abstract eval (no
    device, no compile):

    - the pool aval is the declared ``pool_shape`` (per layer
      ``[num_blocks, 2, n_kv_head, block_size, head_dim]`` + the trash
      block);
    - block tables are int32 and ``blocks_per_row * block_size ==
      max_seq`` (the gathered view must equal the engine's compiled
      cache width EXACTLY — any mismatch would silently mint new
      decode programs per width);
    - ``gather_kv`` emits the engine's contiguous cache aval and
      ``scatter_kv(gather_kv(...))`` round-trips the pool aval;
    - ``paged_decode_attention`` preserves the pool aval and emits the
      attention output aval ``[B, H, 1, hd]``.
    """
    import jax
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.ops import paged_attention as PA
    findings: List[Finding] = []
    try:
        nbm = PA.blocks_per_row(max_seq, block_size)
    except ValueError as e:
        return [Finding("paged-contract", _PAGED_OPS_PATH, 1, where,
                        f"rejected geometry: {e}")]
    pool_aval = jax.ShapeDtypeStruct(
        PA.pool_shape(n_layer, num_blocks, n_kv_head, block_size,
                      head_dim), jnp.float32)
    if pool_aval.shape[1:] != (num_blocks + 1, 2, n_kv_head, block_size,
                               head_dim):
        findings.append(Finding(
            "paged-contract", _PAGED_OPS_PATH, 1, where,
            f"pool aval {pool_aval.shape} breaks the per-layer "
            "[num_blocks+1, 2, n_kv_head, block_size, head_dim] "
            "contract"))
    for b in batches:
        tab = jax.ShapeDtypeStruct((b, nbm), jnp.int32)
        kv = jax.eval_shape(PA.gather_kv, pool_aval, tab)
        want = (n_layer, b, n_kv_head, max_seq, head_dim)
        for name, side in (("k", kv[0]), ("v", kv[1])):
            if tuple(side.shape) != want:
                findings.append(Finding(
                    "paged-contract", _PAGED_OPS_PATH, 1, where,
                    f"gather_kv {name} aval {tuple(side.shape)} != "
                    f"engine cache aval {want} at B={b} — the paged "
                    "path would not share the compiled decode "
                    "programs"))
        rt = jax.eval_shape(PA.scatter_kv, pool_aval, kv[0], kv[1], tab)
        if (tuple(rt.shape) != tuple(pool_aval.shape)
                or rt.dtype != pool_aval.dtype):
            findings.append(Finding(
                "paged-contract", _PAGED_OPS_PATH, 1, where,
                f"scatter(gather(pool)) aval {tuple(rt.shape)}/"
                f"{rt.dtype} does not round-trip the pool aval at "
                f"B={b}"))
        # float block tables must be REJECTED at trace time (a float
        # table would silently truncate placement)
        bad_tab = jax.ShapeDtypeStruct((b, nbm), jnp.float32)
        try:
            jax.eval_shape(PA.gather_kv, pool_aval, bad_tab)
            findings.append(Finding(
                "paged-contract", _PAGED_OPS_PATH, 1, where,
                "gather_kv accepted a float block table — tables must "
                "be int32"))
        except Exception:  # noqa: BLE001 — the rejection IS the contract
            pass
        h = n_kv_head * 2  # a GQA-grouped query head count
        q = jax.ShapeDtypeStruct((b, h, 1, head_dim), jnp.float32)
        knew = jax.ShapeDtypeStruct((b, n_kv_head, 1, head_dim),
                                    jnp.float32)
        out, pool_out = jax.eval_shape(
            lambda q, kn, vn, p, t: PA._paged_decode_attention_impl(
                q, kn, vn, p, t, jnp.int32(0), jnp.int32(4)),
            q, knew, knew, pool_aval, tab)
        if tuple(out.shape) != (b, h, 1, head_dim):
            findings.append(Finding(
                "paged-contract", _PAGED_OPS_PATH, 1, where,
                f"paged_decode_attention out aval {tuple(out.shape)} "
                f"!= {(b, h, 1, head_dim)}"))
        if tuple(pool_out.shape) != tuple(pool_aval.shape):
            findings.append(Finding(
                "paged-contract", _PAGED_OPS_PATH, 1, where,
                "paged_decode_attention does not preserve the pool "
                "aval"))
    return findings


# -- registry-driven pass ----------------------------------------------------


def run_semantic() -> Tuple[List[Finding], int]:
    """All registry contracts; -> (findings, checks_run)."""
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh

    from llm_sharding_demo_tpu.models import is_stage_partitionable
    from llm_sharding_demo_tpu.parallel import spmd
    from . import registry
    findings: List[Finding] = []
    checks = 0

    fams = registry.families()
    for fam_name, (module, config) in fams.items():
        if not is_stage_partitionable(config):
            continue
        for plan_name, bounds in registry.STAGE_PLANS:
            where = f"{fam_name}/{plan_name}"
            for dtype in (jnp.float32,):
                findings.extend(check_stage_contracts(
                    module, config, bounds, where=where, dtype=dtype))
                checks += 1
            from llm_sharding_demo_tpu.parallel import partition as Pt
            specs = Pt.make_stage_specs(config.n_layer, bounds)
            if len({s.n_blocks for s in specs}) > 1:
                findings.extend(check_padded_stacking(
                    module, config, bounds, where=where))
                checks += 1

    # PartitionSpec trees vs the mesh stand-ins they are meant for
    tp2, ep2_tp2 = registry.MESHES["tp2"], registry.MESHES["ep2-tp2"]
    mesh_tp = AbstractMesh(tuple(tp2.values()), tuple(tp2))
    mesh_ep = AbstractMesh(tuple(ep2_tp2.values()), tuple(ep2_tp2))
    gpt2_mod, gpt2_cfg = fams["gpt2-tiny"]
    llama_mod, llama_cfg = fams["llama-tiny"]
    moe_mod, moe_cfg = fams["moe-tiny"]
    findings.extend(check_pspec_tree(
        spmd.param_pspecs(mesh_tp), _param_avals(gpt2_mod, gpt2_cfg),
        registry.MESHES["tp2"], "gpt2-tiny/tp2"))
    findings.extend(check_pspec_tree(
        spmd.llama_param_pspecs(mesh_tp), _param_avals(llama_mod, llama_cfg),
        registry.MESHES["tp2"], "llama-tiny/tp2"))
    findings.extend(check_pspec_tree(
        spmd.moe_param_pspecs(mesh_ep), _param_avals(moe_mod, moe_cfg),
        registry.MESHES["ep2-tp2"], "moe-tiny/ep2-tp2"))
    checks += 3

    # engine tp divisibility contracts for the registered stand-ins
    tp = registry.MESHES["tp2"]["tp"]
    for name, cfg in (("gpt2-tiny", gpt2_cfg), ("llama-tiny", llama_cfg)):
        kv = getattr(cfg, "n_kv_head", cfg.n_head)
        if cfg.n_head % tp or (kv % tp and kv >= tp):
            findings.append(Finding(
                "pspec", _SPMD_PATH, 1, f"{name}/tp2",
                f"n_head={cfg.n_head}/n_kv_head={kv} not shardable over "
                f"tp={tp} whole heads"))
        checks += 1

    # ppermute ring bijection per registered stage-axis size
    for n in registry.RING_SIZES:
        findings.extend(check_ring_program(n, f"ring/pp={n}"))
        checks += 1

    # paged KV block-table contracts per registered pool geometry
    for label, kwargs in registry.PAGED_GEOMETRIES:
        findings.extend(check_paged_contracts(where=label, **kwargs))
        checks += 1

    # overlap lint over the declared pipelined-decode programs (ROADMAP
    # item 3 seed): the currently-serial ppdecode handoffs surface here
    # and stay baselined with justifications until double-buffering
    # lands — at which point the stale suppressions fail --strict
    for n in registry.OVERLAP_RING_SIZES:
        findings.extend(check_decode_overlap(n, f"overlap/pp={n}"))
        checks += 1

    return findings, checks
