"""graftcheck placement pass: declared placement contracts (compile-free).

The static half of **graftshard** (``llm_sharding_demo_tpu/utils/
graftshard.py`` is the dynamic half — the same static+dynamic split as
sanitize/locks/faults/slo/fleet/watch/timeline/memory/numerics). Every
sharded program in this repo places its tensors somewhere on the mesh;
until now WHERE was prose plus a handful of pspec-validity checks in
the semantic pass. Nothing verified that a declared placement is what
the lowered program actually establishes — exactly the hazard surface a
multi-axis KV-sharded pool (ROADMAP item 1, Helix-style per-tensor-class
axis choice) walks into. This pass makes placement a DECLARED contract:

One vocabulary, :data:`MESH_AXES` — every mesh axis any program in the
repo may name (``pp``/``tp``/``ep``/``dp``/``sp`` plus the new ``kvp``
KV-partition axis the planner enumerates). Every module whose programs
or long-lived buffers take a position on the mesh declares
``PLACEMENT_CONTRACT`` beside ``JIT_ENTRY_POINTS``::

    PLACEMENT_CONTRACT = {
        "mesh_axes": ("pp",),            # axes this module's programs
                                         # may establish placement over
        "holding:blocks": "pp",          # self.blocks sharded over pp
        "holding:shared": "replicated",  # explicitly replicated
        "entry:_pp_blocks": "pp",        # traced entry's placement axis
    }

``holding:<name>`` keys declare the placement class of a long-lived
buffer (a ``self.<name>`` attribute — the same names graftmem's
MEMORY_LEDGER tracks, which is how the dynamic auditor joins a live
``.sharding`` to its declaration); ``entry:<name>`` keys declare the
mesh axis a traced entry point's program establishes. Values are an
axis from the module's declared ``mesh_axes`` or the literal
``"replicated"``. ``models/`` modules declare through their existing
``SHARDING_DESCRIPTOR`` (validated here against the descriptor
vocabulary, now including ``kvp_divisors`` — the config fields a kvp
axis must divide).

Two analysis halves feed four rules:

- **AST half** (always on): contract shape/vocabulary validation, the
  holding/entry liveness checks, SHARDING_DESCRIPTOR vocabulary, the
  manual-collective trigger (a module CALLING ``lax.ppermute`` must
  declare a contract), string-literal collective axes against
  MESH_AXES, and the hot-path reshard scan over GRAFTCHECK_HOT_LOOPS
  scopes.
- **Jaxpr half** (skipped under ``--lint-only``): the semantic/numerics
  trace pattern — :func:`traced_placements` builds compile-free
  ``jax.make_jaxpr`` programs of the REAL entry points over
  ``AbstractMesh`` stand-ins and reads the placement they actually
  establish: shard_map in/out names, collective axis names, and
  sharding-constraint specs.

Rules (ids in brackets; suppressions ride the shared baseline):

- [placement-drift]        a malformed/stale PLACEMENT_CONTRACT or
                           SHARDING_DESCRIPTOR, a collective-issuing
                           module with no contract, or a traced entry
                           whose established placement disagrees with
                           its declaration (declares ``pp`` but the
                           program establishes none; declares
                           ``replicated`` but the program shards).
- [undeclared-collective]  a collective (psum/all_gather/ppermute/
                           all_to_all/...) over an axis outside
                           MESH_AXES, or outside the module's declared
                           ``mesh_axes`` — subsumes the axis half of
                           the ring-bijection check.
- [replicated-large-buffer] a shard_map operand above the byte
                           threshold entering fully replicated from a
                           module with no explicit ``"replicated"``
                           holding declaration — the accidental-pool-
                           replication trap a kvp-sharded pool must
                           fail loudly on.
- [hot-path-reshard]       a ``with_sharding_constraint`` / sharded
                           ``device_put`` inside a GRAFTCHECK_HOT_LOOPS
                           decode scope — an implicit per-token
                           resharding; baseline-suppressible with
                           justification like host-sync.

This module is also the single source of truth for PartitionSpec
validity (:func:`check_pspec` — axis-exists / rank-fits / axis-used-
once / divisibility), relocated from semantic.py; semantic keeps a thin
call-through so its fixtures stay pinned.

``--strict`` additionally fails a VACUOUS pass (a PLACEMENT_CONTRACT
resolving to zero live holdings/entries); ``cli.run --json`` carries
``placement_checks`` / ``placement_contracts`` / ``placement_vacuous``.
"""

from __future__ import annotations

import ast
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

from . import lint as L
from .core import Finding
from .locks import _module_assign
from .numerics import _const, _str_seq

PLACEMENT_RULE_IDS = ("placement-drift", "undeclared-collective",
                      "replicated-large-buffer", "hot-path-reshard")

# THE mesh-axis vocabulary: every axis any program in the repo may
# establish placement over. ``kvp`` is the KV-partition axis (Helix-
# style: the paged pool's kv-head dim sharded independently of tp) the
# planner enumerates; graftshard.MESH_AXES mirrors this — tests pin the
# two stay equal, like graftnum.REGIMES.
MESH_AXES = ("pp", "tp", "ep", "kvp", "dp", "sp")

REPLICATED = "replicated"

# replicated-large-buffer threshold: a fully replicated shard_map
# operand at/above this many bytes needs an explicit "replicated"
# holding declaration (the stand-in traces run far below it; a real
# pool plane is far above)
DEFAULT_REPLICATED_THRESHOLD = 1 << 20

_SPMD_PATH = "llm_sharding_demo_tpu/parallel/spmd.py"

# the descriptor vocabulary models/ declare placement through (the
# planner's derive_pspecs/gate_candidate read the same keys)
DESCRIPTOR_KEYS = ("column", "row", "expert",
                   "tp_divisors", "ep_divisors", "kvp_divisors")


# -- PartitionSpec validity (single source of truth; semantic.py keeps
# -- a thin call-through so its fixtures stay pinned) -------------------------


def check_pspec(spec, shape: Tuple[int, ...], mesh_axes: Dict[str, int],
                where: str) -> List[Finding]:
    """One spec against one array shape and a mesh's {axis: size}."""
    problems: List[str] = []
    entries = list(spec)
    if len(entries) > len(shape):
        problems.append(
            f"spec rank {len(entries)} exceeds array rank {len(shape)} "
            f"for shape {shape}")
        entries = entries[:len(shape)]
    used: Dict[str, int] = {}
    for dim, entry in enumerate(entries):
        axes = entry if isinstance(entry, tuple) else (entry,)
        factor = 1      # a dim sharded over SEVERAL axes splits by their
        for axis in axes:  # PRODUCT — per-axis checks alone would pass
            if axis is None:  # specs the real mesh rejects
                continue
            if axis not in mesh_axes:
                problems.append(
                    f"dim {dim} names mesh axis {axis!r}, mesh has "
                    f"{sorted(mesh_axes)}")
                continue
            if axis in used:
                problems.append(
                    f"mesh axis {axis!r} used on dims {used[axis]} and "
                    f"{dim} — an axis shards at most one dim")
            used[axis] = dim
            factor *= mesh_axes[axis]
        if factor > 1 and shape[dim] % factor:
            axes_str = "*".join(repr(a) for a in axes if a is not None)
            problems.append(
                f"dim {dim} of size {shape[dim]} not divisible by "
                f"mesh axis {axes_str}={factor}")
    return [Finding("pspec", _SPMD_PATH, 1, where, p) for p in problems]


# -- contract model ----------------------------------------------------------


class _Contract:
    """One parsed PLACEMENT_CONTRACT."""

    def __init__(self, line: int):
        self.line = line
        self.mesh_axes: Tuple[str, ...] = ()
        self.holdings: Dict[str, str] = {}   # name -> axis | "replicated"
        self.entries: Dict[str, str] = {}    # name -> axis | "replicated"

    def has_replicated_holding(self) -> bool:
        return any(v == REPLICATED for v in self.holdings.values())


def _str_dict_items(node: ast.AST) -> Optional[List[Tuple[str, ast.AST]]]:
    if not isinstance(node, ast.Dict):
        return None
    out = []
    for k, v in zip(node.keys, node.values):
        if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
            return None
        out.append((k.value, v))
    return out


def _parse_contract(mod: L.ModuleInfo,
                    findings: List[Finding]) -> Optional[_Contract]:
    """PLACEMENT_CONTRACT -> validated contract; malformed declarations
    land as placement-drift findings (the contract itself is the first
    thing held to the vocabulary). Returns None when the module
    declares nothing."""
    stmt = _module_assign(mod, "PLACEMENT_CONTRACT")
    if stmt is None:
        return None
    line = stmt.lineno
    c = _Contract(line)
    items = _str_dict_items(stmt.value)
    if items is None:
        findings.append(Finding(
            "placement-drift", mod.relpath, line, "<module>",
            "PLACEMENT_CONTRACT must be a dict literal keyed by "
            "'mesh_axes' / 'holding:<name>' / 'entry:<name>' (the "
            "placement pass reads it statically)"))
        return c
    fmap = dict(items)
    axes = _str_seq(fmap.get("mesh_axes", ast.Dict(keys=[], values=[])))
    if axes is None or not axes \
            or any(a not in MESH_AXES for a in axes):
        findings.append(Finding(
            "placement-drift", mod.relpath, line, "<module>",
            "PLACEMENT_CONTRACT must declare 'mesh_axes' as a non-empty "
            f"tuple/list literal of axes from {MESH_AXES} (the single "
            "placement vocabulary)"))
        return c
    c.mesh_axes = tuple(axes)
    ok_values = set(c.mesh_axes) | {REPLICATED}
    for key, vnode in items:
        if key == "mesh_axes":
            continue
        kind, sep, name = key.partition(":")
        if not sep or kind not in ("holding", "entry") or not name:
            findings.append(Finding(
                "placement-drift", mod.relpath, line, key,
                f"contract key {key!r} must be 'mesh_axes', "
                "'holding:<name>' or 'entry:<name>'"))
            continue
        value = _const(vnode)
        if value not in ok_values:
            findings.append(Finding(
                "placement-drift", mod.relpath, line, key,
                f"contract value for {key!r} is {value!r}; want "
                f"\"replicated\" or a declared mesh axis "
                f"{sorted(c.mesh_axes)}"))
            continue
        (c.holdings if kind == "holding" else c.entries)[name] = value
    return c


def _parse_descriptor(mod: L.ModuleInfo,
                      findings: List[Finding]) -> Optional[Dict[str, tuple]]:
    """models/ SHARDING_DESCRIPTOR -> {key: names}; malformed shapes
    are placement-drift findings (the planner's derive_pspecs and
    gate_candidate read the same literal)."""
    stmt = _module_assign(mod, "SHARDING_DESCRIPTOR")
    if stmt is None:
        return None
    line = stmt.lineno
    items = _str_dict_items(stmt.value)
    if items is None:
        findings.append(Finding(
            "placement-drift", mod.relpath, line, "<module>",
            "SHARDING_DESCRIPTOR must be a dict literal keyed by the "
            f"descriptor vocabulary {DESCRIPTOR_KEYS}"))
        return {}
    out: Dict[str, tuple] = {}
    for key, vnode in items:
        if key not in DESCRIPTOR_KEYS:
            findings.append(Finding(
                "placement-drift", mod.relpath, line, key,
                f"SHARDING_DESCRIPTOR key {key!r} is outside the "
                f"descriptor vocabulary {DESCRIPTOR_KEYS}"))
            continue
        names = _str_seq(vnode)
        if names is None:
            findings.append(Finding(
                "placement-drift", mod.relpath, line, key,
                f"SHARDING_DESCRIPTOR[{key!r}] must be a tuple/list "
                "literal of field-name strings"))
            continue
        out[key] = tuple(names)
    return out


def _holding_sites(mod: L.ModuleInfo) -> Dict[str, int]:
    """name -> first line of a ``self.<name> = ...`` assignment — the
    attributes a 'holding:' declaration can be live against (the same
    names graftmem's track() registers)."""
    out: Dict[str, int] = {}
    for node in ast.walk(mod.tree):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Attribute) \
                    and isinstance(t.value, ast.Name) \
                    and t.value.id == "self":
                out.setdefault(t.attr, t.lineno)
    return out


def _resolve_entry_fn(mod: L.ModuleInfo, name: str) -> Optional[ast.AST]:
    fn = mod.functions.get(name)
    if fn is not None:
        return fn
    hit = L._suffix_index(mod).get(name)
    return hit[1] if hit is not None else None


# -- AST half ----------------------------------------------------------------


_COLLECTIVE_CALL_NAMES = ("ppermute", "psum", "all_gather", "all_to_all",
                          "reduce_scatter", "pmax", "pmin")


def _collective_calls(mod: L.ModuleInfo) -> List[Tuple[int, str,
                                                       Optional[str]]]:
    """(line, primitive, axis-or-None) per ``lax.<collective>`` call in
    the module. The axis is resolved only when passed as a string
    literal (positionally arg 1 or via ``axis_name=``); a variable axis
    is None — checked by the traced half instead."""
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute)
                and f.attr in _COLLECTIVE_CALL_NAMES):
            continue
        axis = None
        if len(node.args) > 1:
            axis = _const(node.args[1])
        if axis is None:
            for kw in node.keywords:
                if kw.arg in ("axis_name", "axis"):
                    axis = _const(kw.value)
        out.append((node.lineno, f.attr,
                    axis if isinstance(axis, str) else None))
    return out


def _reshard_sites(fn: ast.AST) -> List[Tuple[int, str]]:
    """(line, spelling) per sharding transition in a hot-loop body:
    ``with_sharding_constraint`` always, ``device_put`` when it names a
    placement (second positional arg or device=/sharding= keyword)."""
    out = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not isinstance(f, ast.Attribute):
            continue
        if f.attr == "with_sharding_constraint":
            out.append((node.lineno, "with_sharding_constraint"))
        elif f.attr == "device_put" and (
                len(node.args) > 1
                or any(kw.arg in ("device", "sharding")
                       for kw in node.keywords)):
            out.append((node.lineno, "device_put"))
    return out


def _module_issues_collectives(mod: L.ModuleInfo) -> Optional[int]:
    """First line of a manual ``ppermute`` CALL — the signature of a
    hand-written ring program, the trigger that a module must declare
    PLACEMENT_CONTRACT (docstring mentions don't count; ``psum`` alone
    doesn't either — GSPMD-era helpers psum outside any placement
    story of their own)."""
    for line, prim, _axis in _collective_calls(mod):
        if prim == "ppermute":
            return line
    return None


# -- jaxpr half --------------------------------------------------------------


class TracedPlacement:
    """One production entry point traced at representative avals.

    ``build`` is called lazily (imports jax + the target module) and
    returns ``(fn, args)`` for ``jax.make_jaxpr(fn)(*args)``. The
    (relpath, entry) pair joins the trace to its declared
    ``entry:<name>`` contract row."""

    def __init__(self, relpath: str, entry: str,
                 build: Callable[[], tuple]):
        self.relpath = relpath
        self.entry = entry
        self.build = build


def traced_placements() -> List[TracedPlacement]:
    """The production trace table: the real pipelined decode step
    (``PipelinedDecoder._pp_blocks`` — the same program the overlap
    lint walks and the cost model prices), the gpipe training pipeline
    program, and the ring-attention kernel, each over an
    ``AbstractMesh`` stand-in. Kept beside the rules so adding a traced
    entry and its contract is one review."""
    PPDECODE = "llm_sharding_demo_tpu/parallel/ppdecode.py"
    GPIPE = "llm_sharding_demo_tpu/parallel/gpipe.py"
    RING = "llm_sharding_demo_tpu/ops/ring_attention.py"

    def _ppdecode():
        from . import semantic
        rows = [r for r in semantic.build_ppdecode_programs(2)
                if r[0].endswith("decode-step")]
        (_label, _scope, fn, args), = rows
        return fn, args

    def _gpipe():
        import jax
        import jax.numpy as jnp
        from jax.sharding import AbstractMesh

        from llm_sharding_demo_tpu.parallel import gpipe
        from llm_sharding_demo_tpu.parallel import partition as Pt
        from . import registry
        module, config = registry.families()["gpt2-tiny"]
        mesh = AbstractMesh((2,), ("pp",))
        specs = Pt.make_stage_specs(
            config.n_layer, Pt.balanced_boundaries(config.n_layer, 2))
        pavals = jax.eval_shape(
            lambda k: module.init_params(config, k), jax.random.PRNGKey(0))
        blocks = jax.eval_shape(
            lambda p: Pt.stack_stage_params(p, specs), pavals)
        fn = gpipe._compiled_pipeline(mesh, config, "pp", False, 2, False)
        h = jax.ShapeDtypeStruct((2, 1, 4, config.n_embd), jnp.float32)
        return fn, (blocks, h)

    def _ring():
        import jax
        import jax.numpy as jnp
        from jax.sharding import AbstractMesh

        from llm_sharding_demo_tpu.ops import ring_attention as RA
        mesh = AbstractMesh((2,), ("sp",))
        q = jax.ShapeDtypeStruct((1, 2, 4, 4), jnp.float32)
        return (lambda q, k, v: RA.ring_attention(q, k, v, mesh),
                (q, q, q))

    return [
        TracedPlacement(PPDECODE, "_pp_blocks", _ppdecode),
        TracedPlacement(GPIPE, "_compiled_pipeline", _gpipe),
        TracedPlacement(RING, "ring_attention", _ring),
    ]


def _spec_axes(spec) -> Set[str]:
    """Axis names a PartitionSpec (or shard_map names dict) mentions."""
    axes: Set[str] = set()
    for entry in spec:
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            if isinstance(a, str):
                axes.add(a)
    return axes


def _names_axes(names) -> Set[str]:
    """shard_map ``in_names``/``out_names`` dict ({dim: (axes,)}) ->
    axis-name set."""
    axes: Set[str] = set()
    if isinstance(names, dict):
        for v in names.values():
            for a in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(a, str):
                    axes.add(a)
    return axes


def _walk_eqns(jaxpr):
    from .semantic import _sub_jaxprs
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def analyze_program(closed) -> dict:
    """Read the placement a traced program actually establishes:

    - ``axes``: every mesh-axis name the program references (shard_map
      in/out names, collective axis params, sharding-constraint specs);
    - ``collectives``: deduped (primitive, axis) pairs;
    - ``replicated_in``: per shard_map eqn, the (shape, dtype, nbytes)
      of operands entering with NO axis names (fully replicated);
    - ``constraints``: sharding-constraint axis-name sets.
    """
    from .semantic import COMM_PRIMITIVES
    axes: Set[str] = set()
    collectives: Set[Tuple[str, str]] = set()
    replicated_in: List[Tuple[tuple, str, int]] = []
    constraints: List[Set[str]] = []
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    for eqn in _walk_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim == "shard_map":
            in_names = eqn.params.get("in_names",
                                      eqn.params.get("in_specs", ()))
            for var, names in zip(eqn.invars, in_names):
                got = (_names_axes(names) if isinstance(names, dict)
                       else _spec_axes(names))
                axes |= got
                aval = getattr(var, "aval", None)
                if not got and aval is not None \
                        and hasattr(aval, "shape"):
                    import numpy as np
                    nbytes = (int(np.prod(aval.shape, dtype=np.int64))
                              * np.dtype(aval.dtype).itemsize)
                    replicated_in.append((tuple(aval.shape),
                                          str(aval.dtype), nbytes))
            for names in eqn.params.get("out_names",
                                        eqn.params.get("out_specs", ())):
                axes |= (_names_axes(names) if isinstance(names, dict)
                         else _spec_axes(names))
        elif prim in COMM_PRIMITIVES:
            names = eqn.params.get("axis_name",
                                   eqn.params.get("axes", ()))
            if not isinstance(names, (tuple, list)):
                names = (names,)
            for a in names:
                if isinstance(a, str):
                    axes.add(a)
                    collectives.add((prim, a))
        elif prim == "sharding_constraint":
            spec = getattr(eqn.params.get("sharding"), "spec", None)
            if spec is not None:
                got = _spec_axes(spec)
                axes |= got
                constraints.append(got)
    return {"axes": axes, "collectives": collectives,
            "replicated_in": replicated_in, "constraints": constraints}


def _check_traced(entry: TracedPlacement, contract: _Contract,
                  want: str, line: int, threshold: int,
                  findings: List[Finding]) -> int:
    """Trace one entry and run the three jaxpr rules against its
    declared contract. Returns checks performed."""
    import jax

    fn, args = entry.build()
    closed = jax.make_jaxpr(fn)(*args)
    info = analyze_program(closed)
    checks = 0
    scope = entry.entry
    path = entry.relpath

    # undeclared-collective: every collective axis must be in the
    # global vocabulary AND the module's declared axes
    seen_axes: Set[str] = set()
    for prim, axis in sorted(info["collectives"]):
        if axis in seen_axes:
            continue
        seen_axes.add(axis)
        checks += 1
        if axis not in MESH_AXES:
            findings.append(Finding(
                "undeclared-collective", path, line, scope,
                f"traced {entry.entry}: {prim} over axis {axis!r}, "
                f"which is outside the MESH_AXES vocabulary "
                f"{MESH_AXES}"))
        elif axis not in contract.mesh_axes:
            findings.append(Finding(
                "undeclared-collective", path, line, scope,
                f"traced {entry.entry}: {prim} over axis {axis!r}, "
                "which this module's PLACEMENT_CONTRACT does not "
                f"declare (mesh_axes: {sorted(contract.mesh_axes)})"))

    # placement-drift: declared class vs established placement,
    # compared over the DECLARED vocabulary (off-vocabulary axes are
    # the undeclared-collective rule's story, not drift)
    checks += 1
    declared = set() if want == REPLICATED else {want}
    established = info["axes"] & set(contract.mesh_axes)
    extra = established - declared
    missing = declared - info["axes"]
    if extra:
        findings.append(Finding(
            "placement-drift", path, line, scope,
            f"traced {entry.entry} establishes placement over "
            f"{sorted(extra)} but its contract declares "
            f"{want!r} — the declaration and the lowered program "
            "disagree"))
    elif missing:
        findings.append(Finding(
            "placement-drift", path, line, scope,
            f"traced {entry.entry} declares placement over "
            f"{sorted(missing)} but the traced program establishes "
            "none of it — a stale declaration or a silently "
            "unsharded program"))

    # replicated-large-buffer: a big operand entering fully replicated
    # with no explicit "replicated" holding declaration anywhere in
    # the module (the accidental-pool-replication trap)
    for shape, dtype, nbytes in info["replicated_in"]:
        checks += 1
        if nbytes >= threshold and not contract.has_replicated_holding():
            findings.append(Finding(
                "replicated-large-buffer", path, line, scope,
                f"traced {entry.entry}: operand {shape}/{dtype} "
                f"({nbytes} bytes) enters the shard_map fully "
                "replicated and the module declares no explicit "
                "\"replicated\" holding — every device pays its full "
                "footprint (declare 'holding:<name>': \"replicated\" "
                "or shard it)"))
    return checks


# -- the pass ----------------------------------------------------------------


_SCOPE_PREFIXES = ("llm_sharding_demo_tpu/parallel/",
                   "llm_sharding_demo_tpu/ops/",
                   "llm_sharding_demo_tpu/runtime/",
                   "llm_sharding_demo_tpu/models/")


def run_placement(root: str, paths: Optional[List[str]] = None,
                  traced: Optional[Sequence[TracedPlacement]] = None,
                  trace: bool = True,
                  threshold: int = DEFAULT_REPLICATED_THRESHOLD,
                  ) -> Tuple[List[Finding], dict]:
    """The whole static pass -> (findings, summary). ``summary``
    carries ``placement_checks`` (contract/descriptor validations +
    liveness checks + hot-loop scans + traced-rule evaluations — the
    vacuity guard on the pass itself), ``placement_contracts``
    (per-module live declaration count) and ``vacuous`` (modules whose
    contract resolves to zero live holdings/entries — the strict
    driver fails these). ``paths`` / ``traced`` / ``threshold`` are
    injectable for rule fixtures; ``trace=False`` (lint-only mode)
    keeps the pass jax-free."""
    findings: List[Finding] = []
    checks = 0
    contracts: Dict[str, int] = {}
    vacuous: List[str] = []

    scan_paths = paths if paths is not None else L.iter_sources(root)
    mods: Dict[str, L.ModuleInfo] = {}
    for path in scan_paths:
        mod = L.index_module(path, root)
        if mod is not None:
            mods[mod.relpath] = mod

    contract_by_mod: Dict[str, _Contract] = {}
    for relpath, mod in sorted(mods.items()):
        in_scope = relpath.startswith(_SCOPE_PREFIXES) or paths is not None
        contract = _parse_contract(mod, findings)
        desc = _parse_descriptor(mod, findings)
        if contract is None and desc is None:
            if in_scope:
                coll_line = _module_issues_collectives(mod)
                if coll_line is not None:
                    checks += 1
                    findings.append(Finding(
                        "placement-drift", relpath, coll_line, "<module>",
                        "module issues manual collectives (ppermute) "
                        "but declares no PLACEMENT_CONTRACT — placement "
                        "must be declared, not implied (docs/"
                        "ARCHITECTURE.md 'Placement discipline')"))
            continue
        live = 0
        if contract is not None:
            checks += 1
            contract_by_mod[relpath] = contract
            holding_lines = _holding_sites(mod)
            for name in sorted(contract.holdings):
                checks += 1
                if name in holding_lines:
                    live += 1
                else:
                    findings.append(Finding(
                        "placement-drift", relpath, contract.line,
                        f"holding:{name}",
                        f"PLACEMENT_CONTRACT declares holding {name!r} "
                        "but the module assigns no such attribute "
                        "(stale declaration)"))
            for name in sorted(contract.entries):
                checks += 1
                if _resolve_entry_fn(mod, name) is not None:
                    live += 1
                else:
                    findings.append(Finding(
                        "placement-drift", relpath, contract.line,
                        f"entry:{name}",
                        f"PLACEMENT_CONTRACT declares entry {name!r} "
                        "but no such function exists in this module "
                        "(stale declaration)"))
            # string-literal collective axes against the vocabulary
            for cline, prim, axis in _collective_calls(mod):
                if axis is None:
                    continue
                checks += 1
                if axis not in MESH_AXES:
                    findings.append(Finding(
                        "undeclared-collective", relpath, cline,
                        "<module>",
                        f"{prim} over axis {axis!r}, which is outside "
                        f"the MESH_AXES vocabulary {MESH_AXES}"))
                elif axis not in contract.mesh_axes:
                    findings.append(Finding(
                        "undeclared-collective", relpath, cline,
                        "<module>",
                        f"{prim} over axis {axis!r}, which this "
                        "module's PLACEMENT_CONTRACT does not declare "
                        f"(mesh_axes: {sorted(contract.mesh_axes)})"))
        if desc is not None:
            checks += 1
            live += len(desc)
        if contract is not None or desc:
            contracts[relpath] = live
            if live == 0:
                vacuous.append(relpath)

    # hot-path-reshard: scan every declared decode hot loop
    for relpath, mod in sorted(mods.items()):
        for qual in sorted(mod.declared_hot_loops):
            name = qual.rsplit(".", 1)[-1]
            fn = _resolve_entry_fn(mod, name)
            if fn is None:
                continue  # the lint pass owns stale hot-loop findings
            checks += 1
            for rline, spelling in _reshard_sites(fn):
                findings.append(Finding(
                    "hot-path-reshard", relpath, rline, qual,
                    f"{spelling} inside decode hot loop {qual!r} — an "
                    "implicit per-token resharding (move placement to "
                    "setup, or baseline the decision with "
                    "justification)"))

    # jaxpr half
    if trace:
        for t in (traced if traced is not None else traced_placements()):
            contract = contract_by_mod.get(t.relpath)
            checks += 1
            if contract is None or t.entry not in contract.entries:
                findings.append(Finding(
                    "placement-drift", t.relpath, 1, t.entry,
                    f"traced entry point {t.entry!r} has no "
                    "PLACEMENT_CONTRACT 'entry:' row — its placement "
                    "is unreviewable"))
                continue
            mod = mods.get(t.relpath)
            fn_node = (_resolve_entry_fn(mod, t.entry)
                       if mod is not None else None)
            line = getattr(fn_node, "lineno", contract.line)
            checks += _check_traced(t, contract, contract.entries[t.entry],
                                    line, threshold, findings)

    summary = {
        "placement_checks": checks,
        "placement_contracts": contracts,
        "vacuous": sorted(vacuous),
    }
    return (sorted(findings, key=lambda f: (f.path, f.line, f.rule)),
            summary)
