"""graftsan Pass 1: donation-aliasing static analysis (compile-free).

PR 5 shipped a fix for a bug class the repo had no tooling to catch:
``np.asarray`` of a CPU jax array is a ZERO-COPY view of the device
buffer, and a later ``donate_argnums`` donation lets XLA rewrite that
memory in place under the view — the ``_SegOut`` token snapshots of
parked spec rows silently read rolled-over garbage. The hazard is
generic: every donation site in the runtime is a site where a host view
taken earlier (or a re-read after the call) dereferences freed storage.
This module is the static half of graftsan (the dynamic half — the
``GRAFTSAN=1`` pool sanitizer — lives in ``runtime.kv_pool``): an AST
pass over the production tree that makes donation a DECLARED contract
and walks call sites for the aliasing shapes that violate it.

In-file declarations (the registration annotations, same idiom as
``JIT_ENTRY_POINTS`` / ``GRAFTCHECK_HOT_LOOPS``):

- ``DONATED_ARGS``: dict literal ``{holding_name: (argnum, ...)}`` —
  every ``donate_argnums`` jit site in a ``runtime/`` module must be
  declared here (name AND exact indices), and every declaration must
  match a live site. The declarations double as the analyzer's
  resolution map: a call whose trailing name matches a declared
  donating callable is known to consume those argument positions.
- ``POOL_MOVER_SCOPES``: tuple of function qualnames in which invoking
  a pool data mover (``pool.gather`` / ``pool.scatter`` /
  ``pool.scatter_span`` / ``pool.scatter_row`` /
  ``pool.scatter_columns`` / ``pool.cow_copy``)
  is legal — the scopes that provably hold a live ``BlockAllocator``
  lease on every block id they move. A mover call outside a declared
  scope is a finding; the dynamic sanitizer enforces the same property
  at runtime per block id.

Rules (ids in brackets; suppressions ride the shared baseline):

- [undeclared-donation]  ``donate_argnums`` site in ``runtime/`` with
                         no matching ``DONATED_ARGS`` entry, an entry
                         whose indices disagree with the site, or a
                         stale declaration — mirror image of the
                         ``undeclared-jit`` rule.
- [donated-view]         a host view (``np.asarray`` / ``.view()`` /
                         ``jax.device_get`` / ``np.array(copy=False)``)
                         of a value that flows into a declared donated
                         argument without an owning copy. Covers the
                         historical ``_SegOut`` shape: a module-local
                         class whose ``__init__`` stores an argument
                         and later host-views it uncopied makes every
                         ``Cls(x)`` call a view of ``x``.
- [donated-reuse]        a donated buffer read again after the
                         donating call in the same scope (before any
                         rebinding) — the buffer no longer belongs to
                         the caller.
- [pool-lease]           pool mover invoked outside a declared
                         ``POOL_MOVER_SCOPES`` scope (or a stale scope
                         declaration).

The dataflow is deliberately scope-local and name-based (union-find
aliasing over plain assignments, per-function statement order, dotted
names treated as persistent state): precise enough to pin the shapes
that have actually bitten, conservative enough to stay quiet on the
production tree without suppressions.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding
from . import lint as L

SANITIZE_RULE_IDS = ("undeclared-donation", "donated-view",
                     "donated-reuse", "pool-lease")

# pool data movers (KVBlockPool's device-op surface) and the receiver
# names a consumer holds a pool under
_MOVER_NAMES = {"gather", "scatter", "scatter_span", "scatter_row",
                "scatter_columns", "cow_copy"}
_POOL_RECEIVERS = {"pool", "_pool"}


# -- declaration / site extraction -------------------------------------------


def _int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant)
                    and isinstance(elt.value, int)):
                return None
            out.append(elt.value)
        return tuple(out)
    return None


def declared_donations(mod: L.ModuleInfo,
                       ) -> Tuple[Optional[Dict[str, Tuple[int, ...]]], int]:
    """The module's ``DONATED_ARGS`` dict literal -> ({name: indices},
    decl line); (None, 0) when the module declares nothing."""
    for stmt in mod.tree.body:
        if not isinstance(stmt, ast.Assign):
            continue
        for tgt in stmt.targets:
            if isinstance(tgt, ast.Name) and tgt.id == "DONATED_ARGS":
                if not isinstance(stmt.value, ast.Dict):
                    return {}, stmt.lineno
                out: Dict[str, Tuple[int, ...]] = {}
                for k, v in zip(stmt.value.keys, stmt.value.values):
                    idxs = _int_tuple(v)
                    if (isinstance(k, ast.Constant)
                            and isinstance(k.value, str)
                            and idxs is not None):
                        out[k.value] = idxs
                return out, stmt.lineno
    return None, 0


def declared_pool_scopes(mod: L.ModuleInfo,
                         ) -> Tuple[Optional[Set[str]], int]:
    for stmt in mod.tree.body:
        if not isinstance(stmt, ast.Assign):
            continue
        for tgt in stmt.targets:
            if isinstance(tgt, ast.Name) and tgt.id == "POOL_MOVER_SCOPES":
                vals = L._string_tuple(stmt.value)
                return (vals if vals is not None else set()), stmt.lineno
    return None, 0


@dataclasses.dataclass
class DonationSite:
    line: int
    name: Optional[str]                 # holding attr/def name
    indices: Optional[Tuple[int, ...]]  # None: non-literal donate_argnums
    scope: str


def _parent_map(tree: ast.Module) -> Dict[int, ast.AST]:
    parents: Dict[int, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[id(child)] = node
    return parents


def _donate_kw(call: ast.Call) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            return kw.value
    return None


def _enclosing_scope(node: ast.AST, parents: Dict[int, ast.AST],
                     mod: L.ModuleInfo) -> str:
    cur = parents.get(id(node))
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return mod.qualname_of.get(cur, cur.name)
        cur = parents.get(id(cur))
    return "<module>"


def donation_sites(mod: L.ModuleInfo) -> List[DonationSite]:
    """Every ``jax.jit(..., donate_argnums=...)`` site (direct call or
    ``functools.partial(jax.jit, donate_argnums=...)`` decorator), with
    the holding name resolved through the nearest Assign target or
    decorated def — wrap- and comprehension-tolerant by construction."""
    parents = _parent_map(mod.tree)
    out: List[DonationSite] = []
    for node in ast.walk(mod.tree):
        call = L._jit_call(node)
        if call is None:
            continue
        kw = _donate_kw(call)
        if kw is None:
            continue
        # resolve the holding name: nearest enclosing Assign target, or
        # the def this call decorates
        name = None
        cur: ast.AST = call
        while True:
            parent = parents.get(id(cur))
            if parent is None:
                break
            if isinstance(parent, ast.Assign):
                tgt = parent.targets[0]
                if isinstance(tgt, ast.Attribute):
                    name = tgt.attr
                elif isinstance(tgt, ast.Name):
                    name = tgt.id
                break
            if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and cur in parent.decorator_list:
                name = parent.name
                break
            if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef, ast.Module)):
                break
            cur = parent
        out.append(DonationSite(
            line=call.lineno, name=name, indices=_int_tuple(kw),
            scope=_enclosing_scope(call, parents, mod)))
    return out


def rule_undeclared_donation(mod: L.ModuleInfo) -> List[Finding]:
    """runtime/ modules must declare every donation site in
    DONATED_ARGS (name + exact indices); modules that declare anywhere
    are held to the same consistency."""
    declared, decl_line = declared_donations(mod)
    sites = donation_sites(mod)
    enforce = "/runtime/" in "/" + mod.relpath or declared is not None
    if not enforce or (not sites and declared is None):
        return []
    declared = declared or {}
    out: List[Finding] = []
    site_names = set()
    for s in sites:
        if s.name is None:
            out.append(Finding(
                "undeclared-donation", mod.relpath, s.line, s.scope,
                "donate_argnums site not held by a nameable attribute — "
                "the donation-aliasing pass cannot resolve its callers; "
                "bind it and declare it in DONATED_ARGS"))
            continue
        site_names.add(s.name)
        if s.indices is None:
            out.append(Finding(
                "undeclared-donation", mod.relpath, s.line, s.scope,
                f"donation site {s.name!r} uses a non-literal "
                "donate_argnums — the analyzer (and the reader) cannot "
                "tell which buffers the call consumes"))
        elif s.name not in declared:
            out.append(Finding(
                "undeclared-donation", mod.relpath, s.line, s.scope,
                f"donation site {s.name!r} missing from this module's "
                "DONATED_ARGS declaration (the donation-aliasing pass "
                "resolves callers through declared names only)"))
        elif declared[s.name] != s.indices:
            out.append(Finding(
                "undeclared-donation", mod.relpath, s.line, s.scope,
                f"DONATED_ARGS declares {s.name!r} donating "
                f"{declared[s.name]} but the site donates {s.indices} — "
                "callers analyzed against the declaration would miss "
                "the real consumed buffers"))
    for name in sorted(set(declared) - site_names):
        out.append(Finding(
            "undeclared-donation", mod.relpath, decl_line or 1, "<module>",
            f"DONATED_ARGS declares {name!r} but no donate_argnums site "
            "binds it (stale declaration)"))
    return out


# -- pool mover lease scopes --------------------------------------------------


def _mover_calls(mod: L.ModuleInfo) -> List[Tuple[int, str, str]]:
    """(line, scope, 'recv.mover') for every pool-mover invocation:
    attribute call whose receiver's trailing name is a pool handle."""
    parents = _parent_map(mod.tree)
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr in _MOVER_NAMES):
            continue
        recv = f.value
        recv_name = None
        if isinstance(recv, ast.Name):
            recv_name = recv.id
        elif isinstance(recv, ast.Attribute):
            recv_name = recv.attr
        if recv_name not in _POOL_RECEIVERS:
            continue
        out.append((node.lineno,
                    _enclosing_scope(node, parents, mod),
                    f"{recv_name}.{f.attr}"))
    return out


def rule_pool_lease(mod: L.ModuleInfo) -> List[Finding]:
    declared, decl_line = declared_pool_scopes(mod)
    calls = _mover_calls(mod)
    if not calls and declared is None:
        return []
    declared = declared or set()
    out: List[Finding] = []
    hit: Set[str] = set()
    for line, scope, what in calls:
        if scope in declared:
            hit.add(scope)
        else:
            out.append(Finding(
                "pool-lease", mod.relpath, line, scope,
                f"pool mover {what}(...) invoked outside a declared "
                "POOL_MOVER_SCOPES lease scope — block ids moved here "
                "have no statically known live BlockAllocator lease "
                "(declare the scope, or route through one that is)"))
    for scope in sorted(declared - hit):
        out.append(Finding(
            "pool-lease", mod.relpath, decl_line or 1, "<module>",
            f"POOL_MOVER_SCOPES declares {scope!r} but it invokes no "
            "pool mover (stale declaration)"))
    return out


# -- donation dataflow (donated-view / donated-reuse) -------------------------


def _expr_key(node: ast.AST) -> Optional[str]:
    """Dotted-name key of an expression, peeling subscripts and
    value-preserving wrappers (``jax.block_until_ready``)."""
    while True:
        if isinstance(node, ast.Subscript):
            node = node.value
            continue
        if isinstance(node, ast.Call):
            f = node.func
            if (isinstance(f, ast.Attribute)
                    and f.attr == "block_until_ready"
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "jax" and node.args):
                node = node.args[0]
                continue
            return None
        break
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
        while isinstance(node, ast.Subscript):
            node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _kw_true(call: ast.Call, name: str) -> Optional[bool]:
    for kw in call.keywords:
        if kw.arg == name and isinstance(kw.value, ast.Constant):
            return bool(kw.value.value)
    return None


def _view_call(node: ast.Call, sinks: Dict[str, Set[int]],
               ) -> List[Tuple[ast.AST, str]]:
    """(viewed-expr, kind) pairs when ``node`` takes an uncopied host
    view of an argument."""
    f = node.func
    if isinstance(f, ast.Attribute):
        base = f.value.id if isinstance(f.value, ast.Name) else None
        if f.attr == "asarray" and base in ("np", "numpy") and node.args:
            return [(node.args[0], "np.asarray")]
        if (f.attr == "array" and base in ("np", "numpy") and node.args
                and _kw_true(node, "copy") is False):
            return [(node.args[0], "np.array(copy=False)")]
        if f.attr == "device_get" and base == "jax" and node.args:
            return [(node.args[0], "jax.device_get")]
        if f.attr == "view" and not node.keywords and len(node.args) <= 1:
            return [(f.value, ".view()")]
    elif isinstance(f, ast.Name) and f.id in sinks:
        return [(node.args[i], f"{f.id}(...)")
                for i in sinks[f.id] if i < len(node.args)]
    return []


def view_sink_classes(mod: L.ModuleInfo) -> Dict[str, Set[int]]:
    """Module-local classes whose ``__init__`` stores a positional arg
    into an attribute some method later host-views WITHOUT an owning
    copy — constructing one is then a view of that argument (the
    ``_SegOut`` bug shape)."""
    out: Dict[str, Set[int]] = {}
    for cls in mod.tree.body:
        if not isinstance(cls, ast.ClassDef):
            continue
        init = next((n for n in cls.body
                     if isinstance(n, ast.FunctionDef)
                     and n.name == "__init__"), None)
        if init is None:
            continue
        params = [a.arg for a in init.args.args[1:]]  # past self
        attr_of_param: Dict[str, int] = {}
        for stmt in init.body:
            if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Attribute)
                    and isinstance(stmt.targets[0].value, ast.Name)
                    and stmt.targets[0].value.id == "self"
                    and isinstance(stmt.value, ast.Name)
                    and stmt.value.id in params):
                attr_of_param[stmt.targets[0].attr] = \
                    params.index(stmt.value.id)
        if not attr_of_param:
            continue
        viewed: Set[str] = set()
        for node in ast.walk(cls):
            if isinstance(node, ast.Call):
                for expr, _kind in _view_call(node, {}):
                    key = _expr_key(expr)
                    if key and key.startswith("self."):
                        viewed.add(key[len("self."):].split(".")[0])
        idxs = {i for a, i in attr_of_param.items() if a in viewed}
        if idxs:
            out[cls.name] = idxs
    return out


class _Union:
    def __init__(self):
        self.parent: Dict[str, str] = {}

    def find(self, k: str) -> str:
        p = self.parent.setdefault(k, k)
        while p != self.parent.setdefault(p, p):
            p = self.parent[p]
        self.parent[k] = p
        return p

    def union(self, a: str, b: str) -> None:
        ra, rb = self.find(a), self.find(b)
        if ra != rb:
            self.parent[ra] = rb

    def members(self, root: str) -> List[str]:
        return [k for k in self.parent if self.find(k) == self.find(root)]


class _FlowScope:
    """Per-function donation dataflow: statements in textual order,
    donation/view/load/kill events keyed by union-find alias roots."""

    def __init__(self, mod: L.ModuleInfo, qual: str,
                 donating: Dict[str, Set[int]], sinks: Dict[str, Set[int]],
                 own_declared: Optional[Dict[str, Tuple[int, ...]]] = None):
        self.mod = mod
        self.qual = qual
        self.donating = donating
        self.sinks = sinks
        self.own_declared = own_declared or {}
        self.alias = _Union()
        self.viewed_live: Dict[str, List[Tuple[int, str]]] = {}
        self.viewed_all: Dict[str, List[Tuple[int, str]]] = {}
        self.donated_live: Dict[str, Tuple[int, str]] = {}
        self.donated_all: Dict[str, Tuple[int, str]] = {}
        self.local_donating: Dict[str, Set[int]] = {}  # IfExp aliases
        self.findings: List[Finding] = []
        self._reported: Set[Tuple[str, int]] = set()

    # -- event extraction --

    def _donation_indices(self, call: ast.Call) -> Optional[Set[int]]:
        f = call.func
        name = None
        if isinstance(f, ast.Attribute):
            name = f.attr
        elif isinstance(f, ast.Name):
            name = f.id
        if name is None:
            return None
        if name in self.local_donating:
            return self.local_donating[name]
        idxs = self.donating.get(name)
        if idxs is None:
            return None
        # collision guard: a plain def in THIS module shadowing a
        # donating name declared elsewhere (e.g. a method that happens
        # to share the trailing name) is not the donating callable
        if name in L._suffix_index(self.mod) \
                and name not in self.own_declared:
            return None
        return idxs

    def _emit(self, rule: str, line: int, msg: str) -> None:
        key = (rule, line)
        if key in self._reported:
            return
        self._reported.add(key)
        self.findings.append(Finding(rule, self.mod.relpath, line,
                                     self.qual, msg))

    # -- statement processing --

    def run(self, fn: ast.AST) -> List[Finding]:
        body = fn.body if not isinstance(fn, ast.Lambda) else [fn.body]
        self._walk_stmts(body)
        # persistent-state hazard, order-insensitive: a donated value
        # whose alias class contains dotted (attribute) state outlives
        # this invocation — the NEXT call's donation frees what this
        # call's view still references (the cross-segment _SegOut bug).
        for root, (dline, dkey) in self.donated_all.items():
            persistent = any("." in m for m in self.alias.members(root))
            if not persistent:
                continue
            for vline, kind in self.viewed_all.get(self.alias.find(root),
                                                  []):
                self._emit(
                    "donated-view", vline,
                    f"{kind} takes a zero-copy host view of a value "
                    f"aliased to persistent state that is donated in "
                    f"this scope ({dkey!r}, donated at line {dline}): a "
                    "later donating call rewrites the viewed memory in "
                    "place — take an owning copy (np.array(x, "
                    "copy=True) / x.copy())")
        return self.findings

    def _walk_stmts(self, stmts: Sequence[ast.stmt]) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # separate scopes
            self._process(stmt)
            for attr in ("body", "orelse", "finalbody"):
                self._walk_stmts(getattr(stmt, attr, []) or [])
            for h in getattr(stmt, "handlers", []) or []:
                self._walk_stmts(h.body)

    def _process(self, stmt: ast.stmt) -> None:
        views: List[Tuple[int, str, str]] = []      # (line, key, kind)
        donations: List[Tuple[int, str, str]] = []  # (line, key, repr)
        loads: List[Tuple[int, str]] = []
        copied: Set[int] = set()

        # IfExp donation alias: fn = self._a if c else self._b
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and isinstance(stmt.value, ast.IfExp)):
            idxs: Set[int] = set()
            for branch in (stmt.value.body, stmt.value.orelse):
                got = None
                if isinstance(branch, (ast.Attribute, ast.Name)):
                    trailing = (branch.attr if isinstance(
                        branch, ast.Attribute) else branch.id)
                    got = self.donating.get(trailing)
                if got:
                    idxs |= got
            if idxs:
                self.local_donating[stmt.targets[0].id] = idxs

        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            # owning-copy wrappers neutralize the inner view
            if isinstance(f, ast.Attribute) and f.attr == "copy" \
                    and isinstance(f.value, ast.Call):
                copied.add(id(f.value))
            if (isinstance(f, ast.Attribute) and f.attr == "array"
                    and isinstance(f.value, ast.Name)
                    and f.value.id in ("np", "numpy")
                    and _kw_true(node, "copy") is not False):
                for a in node.args:
                    if isinstance(a, ast.Call):
                        copied.add(id(a))
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                if id(node) not in copied:
                    for expr, kind in _view_call(node, self.sinks):
                        key = _expr_key(expr)
                        if key:
                            views.append((node.lineno, key, kind))
                idxs = self._donation_indices(node)
                if idxs:
                    for i in sorted(idxs):
                        if i < len(node.args):
                            key = _expr_key(node.args[i])
                            if key:
                                donations.append(
                                    (node.lineno, key,
                                     ast.unparse(node.func)))
            elif (isinstance(node, (ast.Name, ast.Attribute))
                  and isinstance(getattr(node, "ctx", None), ast.Load)):
                key = _expr_key(node)
                if key:
                    loads.append((node.lineno, key))

        donation_keys = {k for _, k, _ in donations}
        # donated-reuse: loads of a still-donated buffer (the donating
        # statement's own argument read is not a re-read)
        for line, key in loads:
            root = self.alias.find(key)
            if root in self.donated_live and key not in donation_keys:
                dline, dkey = self.donated_live[root]
                self._emit(
                    "donated-reuse", line,
                    f"{key!r} read after being donated at line {dline} "
                    f"({dkey!r}): the buffer was consumed by XLA and no "
                    "longer belongs to this scope — rebind the call's "
                    "output or copy before donating")
        # views: of an already-donated buffer (reuse-class), else record
        for line, key, kind in views:
            root = self.alias.find(key)
            if root in self.donated_live:
                dline, dkey = self.donated_live[root]
                self._emit(
                    "donated-view", line,
                    f"{kind} takes a host view of {key!r} AFTER its "
                    f"donation at line {dline}: the view reads storage "
                    "XLA already reclaimed")
            else:
                self.viewed_live.setdefault(root, []).append((line, kind))
                self.viewed_all.setdefault(root, []).append((line, kind))
        # donations: flag live earlier views, then mark
        for line, key, call_repr in donations:
            root = self.alias.find(key)
            for vline, kind in self.viewed_live.get(root, []):
                self._emit(
                    "donated-view", vline,
                    f"{kind} takes a zero-copy host view of {key!r} "
                    f"which is then donated at line {line} "
                    f"({call_repr}): the donation rewrites the viewed "
                    "memory in place — take an owning copy "
                    "(np.array(x, copy=True) / x.copy())")
            self.donated_live[root] = (line, key)
            self.donated_all[root] = (line, key)

        # stores: alias unions, then kills
        stores: List[str] = []
        if isinstance(stmt, ast.Assign):
            vkey = _expr_key(stmt.value)
            for tgt in stmt.targets:
                tkey = _expr_key(tgt)
                if tkey:
                    if vkey:
                        self.alias.union(tkey, vkey)
                    stores.append(tkey)
                elif isinstance(tgt, (ast.Tuple, ast.List)):
                    for elt in tgt.elts:
                        ekey = _expr_key(elt)
                        if ekey:
                            stores.append(ekey)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            tkey = _expr_key(stmt.target)
            if tkey:
                stores.append(tkey)
        elif isinstance(stmt, ast.For):
            tkey = _expr_key(stmt.target)
            if tkey:
                stores.append(tkey)
        for key in stores:
            root = self.alias.find(key)
            self.donated_live.pop(root, None)
            self.viewed_live.pop(root, None)


def _donating_map(mods: Sequence[L.ModuleInfo]) -> Dict[str, Set[int]]:
    """Union of every module's DONATED_ARGS declarations: trailing
    callable name -> consumed positional indices."""
    out: Dict[str, Set[int]] = {}
    for mod in mods:
        declared, _ = declared_donations(mod)
        for name, idxs in (declared or {}).items():
            out.setdefault(name, set()).update(idxs)
    return out


def rule_donation_flow(mod: L.ModuleInfo,
                       donating: Dict[str, Set[int]],
                       ) -> Tuple[List[Finding], int]:
    """-> (findings, functions flowed). The module's own DONATED_ARGS
    is resolved once here and shared by every scope (the collision
    guard consults it per call)."""
    sinks = view_sink_classes(mod)
    own_declared, _ = declared_donations(mod)
    findings: List[Finding] = []
    for qual, fn in sorted(mod.functions.items()):
        findings.extend(
            _FlowScope(mod, qual, donating, sinks,
                       own_declared=own_declared).run(fn))
    return findings, len(mod.functions)


# -- driver -------------------------------------------------------------------


def run_sanitize(root: str, paths: Optional[List[str]] = None,
                 ) -> Tuple[List[Finding], int]:
    """The whole static pass over the production surface (the lint's
    source set). -> (findings, checks_run) where ``checks_run`` counts
    real analysis units — donation sites validated, mover calls
    checked, and functions dataflowed — so a vacuity guard on the count
    actually proves the rules saw the tree (a file-count proxy would
    pass even with declaration parsing silently broken)."""
    mods: List[L.ModuleInfo] = []
    for path in (paths if paths is not None else L.iter_sources(root)):
        mod = L.index_module(path, root)
        if mod is not None:
            mods.append(mod)
    donating = _donating_map(mods)
    findings: List[Finding] = []
    checks = len(donating)           # resolvable donating callables
    for mod in mods:
        findings.extend(rule_undeclared_donation(mod))
        checks += len(donation_sites(mod))
        findings.extend(rule_pool_lease(mod))
        checks += len(_mover_calls(mod))
        flow, n_fns = rule_donation_flow(mod, donating)
        findings.extend(flow)
        checks += n_fns
    return (sorted(findings, key=lambda f: (f.path, f.line, f.rule)),
            checks)
