"""graftshard (placement discipline): static pass + dynamic auditor pins.

Three layers of claims:

1. **The repo passes its own placement pass, non-vacuously**: zero raw
   findings, >= 10 checks, live PLACEMENT_CONTRACT / SHARDING_DESCRIPTOR
   declarations for the pipeline modules, the models, and the paged
   pool — and the static/dynamic halves share ONE mesh-axis vocabulary
   (``placement.MESH_AXES == graftshard.MESH_AXES``, the
   graftnum.REGIMES sync pattern).
2. **Each rule has a seeded must-find fixture**: exactly one finding
   with file:line, for placement-drift (declared-vs-traced
   disagreement, both directions), undeclared-collective (AST literal
   and traced program), replicated-large-buffer (the accidental
   pool-plane-replication trap, plus its declared-"replicated" escape
   hatch), and hot-path-reshard.
3. **The dynamic auditor audits the declared**: armed via GRAFTSHARD=1,
   a live buffer whose placement disagrees with its owning module's
   PLACEMENT_CONTRACT raises GraftshardError with holding/component/
   declaration-site provenance at graftmem track/update time, and
   ``audit()``/``status()`` report it; disarmed, the hook is free.
"""

import os
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from llm_sharding_demo_tpu.utils import graftmem, graftshard

from tools.graftcheck import placement

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- 1. the repo is placement-clean and the vocabulary is synced -------------


def test_repo_placement_clean_and_nonvacuous():
    findings, summary = placement.run_placement(REPO)
    assert findings == [], [f.format() for f in findings]
    assert summary["placement_checks"] >= 10, "placement pass went vacuous"
    assert summary["vacuous"] == [], (
        "PLACEMENT_CONTRACT declarations resolving to nothing live: "
        f"{summary['vacuous']}")
    contracts = summary["placement_contracts"]
    for rel in ("llm_sharding_demo_tpu/parallel/ppdecode.py",
                "llm_sharding_demo_tpu/parallel/gpipe.py",
                "llm_sharding_demo_tpu/parallel/pipeline_1f1b.py",
                "llm_sharding_demo_tpu/ops/ring_attention.py",
                "llm_sharding_demo_tpu/runtime/kv_pool.py",
                "llm_sharding_demo_tpu/models/gpt2.py",
                "llm_sharding_demo_tpu/models/llama.py"):
        assert contracts.get(rel, 0) >= 1, (
            f"{rel}: no live placement declaration — the placement "
            "discipline stopped seeing this module's mesh position")


def test_mesh_axes_vocabulary_synced():
    """One vocabulary for both halves — the static pass and the live
    auditor can never disagree about which axes exist; ``kvp`` (the
    planner's KV-partition axis) is part of it."""
    assert placement.MESH_AXES == graftshard.MESH_AXES
    assert "kvp" in placement.MESH_AXES
    assert set(placement.PLACEMENT_RULE_IDS) == {
        "placement-drift", "undeclared-collective",
        "replicated-large-buffer", "hot-path-reshard"}


# -- 2. seeded must-find rule fixtures ---------------------------------------


def _fixture(tmp_path, relpath, source, **kw):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    kw.setdefault("traced", [])
    return placement.run_placement(str(tmp_path), paths=[str(p)], **kw)


def test_fixture_placement_drift_stale_declaration(tmp_path):
    """A contract declaring a holding no ``self.<name>`` assignment
    backs is exactly one placement-drift finding (stale declaration)."""
    findings, summary = _fixture(tmp_path, "parallel/stale.py", """\
        PLACEMENT_CONTRACT = {
            "mesh_axes": ("pp",),
            "holding:gone": "pp",
        }
        """)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "placement-drift"
    assert f.path == "parallel/stale.py" and f.line == 1
    assert f.scope == "holding:gone" and "stale" in f.message
    # zero live declarations -> the module is vacuous (strict fails)
    assert summary["vacuous"] == ["parallel/stale.py"]


def test_fixture_placement_drift_declared_but_not_established(tmp_path):
    """A traced entry DECLARING pp placement whose lowered program
    establishes none is exactly one placement-drift finding at the def
    line — the declaration must be true in the traced program."""
    p = tmp_path / "parallel" / "drift.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent("""\
        PLACEMENT_CONTRACT = {
            "mesh_axes": ("pp",),
            "entry:prog": "pp",
        }

        def prog(x):
            ...
        """))

    def prog(x):
        return x * 2.0

    traced = [placement.TracedPlacement("parallel/drift.py", "prog",
                                        lambda: (prog, (jnp.zeros(
                                            (2, 2), jnp.float32),)))]
    findings, _ = placement.run_placement(str(tmp_path), paths=[str(p)],
                                          traced=traced)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "placement-drift"
    assert f.path == "parallel/drift.py" and f.line == 6  # the def line
    assert f.scope == "prog" and "establishes none" in f.message


def test_fixture_placement_drift_replicated_but_sharded(tmp_path):
    """The other drift direction: an entry declared "replicated" whose
    traced program establishes tp placement is exactly one finding."""
    p = tmp_path / "parallel" / "rep.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent("""\
        PLACEMENT_CONTRACT = {
            "mesh_axes": ("tp",),
            "entry:prog": "replicated",
        }

        def prog(x):
            ...
        """))
    mesh = AbstractMesh((2,), ("tp",))

    def prog(x):
        return jax.shard_map(lambda v: v * 2.0, mesh=mesh,
                             in_specs=P("tp"), out_specs=P("tp"),
                             axis_names={"tp"})(x)

    traced = [placement.TracedPlacement("parallel/rep.py", "prog",
                                        lambda: (prog, (jnp.zeros(
                                            (2, 2), jnp.float32),)))]
    findings, _ = placement.run_placement(str(tmp_path), paths=[str(p)],
                                          traced=traced)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "placement-drift"
    assert f.path == "parallel/rep.py" and f.line == 6
    assert "['tp']" in f.message and "'replicated'" in f.message


def test_fixture_traced_entry_without_contract_row(tmp_path):
    """A traced production entry with no 'entry:' contract row is
    unreviewable — exactly one placement-drift finding."""
    p = tmp_path / "parallel" / "bare.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text("def prog(x):\n    ...\n")

    def prog(x):
        return x

    traced = [placement.TracedPlacement("parallel/bare.py", "prog",
                                        lambda: (prog, (jnp.zeros(
                                            (2,), jnp.float32),)))]
    findings, _ = placement.run_placement(str(tmp_path), paths=[str(p)],
                                          traced=traced)
    assert [f.rule for f in findings] == ["placement-drift"]
    assert "unreviewable" in findings[0].message


def test_fixture_undeclared_collective_ast(tmp_path):
    """A string-literal collective over an axis outside the module's
    declared mesh_axes is exactly one undeclared-collective finding at
    the call line (no tracing needed)."""
    findings, _ = _fixture(tmp_path, "ops/coll.py", """\
        import jax

        PLACEMENT_CONTRACT = {
            "mesh_axes": ("pp",),
            "entry:prog": "pp",
        }

        def prog(x):
            return jax.lax.psum(x, "tp")
        """)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "undeclared-collective"
    assert f.path == "ops/coll.py" and f.line == 9  # the psum call
    assert "'tp'" in f.message and "does not declare" in f.message


def test_fixture_undeclared_collective_traced(tmp_path):
    """A traced program whose collective crosses an axis the contract
    does not declare is exactly one undeclared-collective finding —
    the axis check reads the lowered jaxpr, not just literals."""
    p = tmp_path / "ops" / "tcoll.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent("""\
        PLACEMENT_CONTRACT = {
            "mesh_axes": ("pp",),
            "entry:prog": "replicated",
        }

        def prog(x):
            ...
        """))
    mesh = AbstractMesh((2,), ("tp",))

    def prog(x):
        return jax.shard_map(lambda v: jax.lax.psum(v, "tp"), mesh=mesh,
                             in_specs=P("tp"), out_specs=P(),
                             axis_names={"tp"})(x)

    traced = [placement.TracedPlacement("ops/tcoll.py", "prog",
                                        lambda: (prog, (jnp.zeros(
                                            (2, 2), jnp.float32),)))]
    findings, _ = placement.run_placement(str(tmp_path), paths=[str(p)],
                                          traced=traced)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "undeclared-collective"
    assert f.path == "ops/tcoll.py" and f.line == 6
    assert "psum" in f.message and "'tp'" in f.message


def _pool_trap_trace(tmp_path, relpath, source):
    """A kvp shard_map whose pool-plane operand enters fully
    replicated: in_specs (P(), P("kvp")) — the first operand (the
    'pool') carries no axis names."""
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    mesh = AbstractMesh((2,), ("kvp",))

    def lookup(pool, q):
        return jax.shard_map(lambda pl, v: v + jnp.sum(pl), mesh=mesh,
                             in_specs=(P(), P("kvp")),
                             out_specs=P("kvp"), axis_names={"kvp"})(pool, q)

    pool = jnp.zeros((2, 64, 4), jnp.float32)  # 2048 bytes, replicated
    q = jnp.zeros((2, 4), jnp.float32)
    traced = [placement.TracedPlacement(relpath, "lookup",
                                        lambda: (lookup, (pool, q)))]
    return placement.run_placement(str(tmp_path), paths=[str(p)],
                                   traced=traced, threshold=1024)


def test_fixture_replicated_pool_plane_trap(tmp_path):
    """The accidental-pool-replication trap: a pool-plane-sized operand
    entering the kvp shard_map fully replicated, from a module with no
    explicit "replicated" holding, is exactly one
    replicated-large-buffer finding."""
    findings, _ = _pool_trap_trace(tmp_path, "runtime/trap.py", """\
        PLACEMENT_CONTRACT = {
            "mesh_axes": ("kvp",),
            "entry:lookup": "kvp",
        }

        def lookup(pool, q):
            ...
        """)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "replicated-large-buffer"
    assert f.path == "runtime/trap.py" and f.line == 6
    assert "2048 bytes" in f.message and "replicated" in f.message


def test_fixture_replicated_declaration_is_the_escape_hatch(tmp_path):
    """The SAME program traces clean when the module explicitly
    declares the holding "replicated" — replication is legal, silent
    replication is not."""
    findings, _ = _pool_trap_trace(tmp_path, "runtime/ok.py", """\
        PLACEMENT_CONTRACT = {
            "mesh_axes": ("kvp",),
            "holding:pool": "replicated",
            "entry:lookup": "kvp",
        }

        class Store:
            def __init__(self):
                self.pool = None

        def lookup(pool, q):
            ...
        """)
    assert findings == [], [f.format() for f in findings]


def test_fixture_hot_path_reshard(tmp_path):
    """A with_sharding_constraint inside a declared decode hot loop is
    exactly one hot-path-reshard finding — an implicit per-token
    resharding."""
    findings, _ = _fixture(tmp_path, "runtime/hotpath.py", """\
        import jax

        GRAFTCHECK_HOT_LOOPS = ("step",)

        def step(x, s):
            return jax.lax.with_sharding_constraint(x, s)
        """)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "hot-path-reshard"
    assert f.path == "runtime/hotpath.py" and f.line == 6
    assert f.scope == "step"
    assert "with_sharding_constraint" in f.message


def test_fixture_malformed_contract_is_drift(tmp_path):
    """A contract naming an off-vocabulary axis is itself a
    placement-drift finding — the declaration is the first thing held
    to the vocabulary."""
    findings, _ = _fixture(tmp_path, "parallel/badaxes.py", """\
        PLACEMENT_CONTRACT = {
            "mesh_axes": ("warp",),
        }
        """)
    assert [f.rule for f in findings] == ["placement-drift"]
    assert "mesh_axes" in findings[0].message


# -- 3. the dynamic auditor (GRAFTSHARD=1) -----------------------------------


_FAKE_MOD = "graftshard_fixture_mod"


@pytest.fixture
def armed(monkeypatch, tmp_path):
    """Arm the auditor against a fake owning module whose
    PLACEMENT_CONTRACT declares holding 'buf' replicated (file on disk
    so violation provenance resolves to file:line)."""
    monkeypatch.setenv("GRAFTSHARD", "1")
    graftshard.clear()
    modfile = tmp_path / f"{_FAKE_MOD}.py"
    modfile.write_text(
        'PLACEMENT_CONTRACT = {"mesh_axes": ("pp",),\n'
        '                      "holding:buf": "replicated"}\n')
    mod = types.ModuleType(_FAKE_MOD)
    mod.PLACEMENT_CONTRACT = {"mesh_axes": ("pp",),
                              "holding:buf": "replicated"}
    mod.__file__ = str(modfile)
    monkeypatch.setitem(sys.modules, _FAKE_MOD, mod)
    yield str(modfile)
    graftshard.clear()


def _owner():
    class Owner:
        pass
    Owner.__module__ = _FAKE_MOD
    return Owner()


def _pp_placed(shape=(4, 4)):
    """A live buffer PLACED over the pp axis (1-device mesh — the check
    is spec-level, so this works on CPU)."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("pp",))
    return jax.device_put(jnp.zeros(shape, jnp.float32),
                          NamedSharding(mesh, P("pp")))


def test_auditor_clean_buffer_tracks_and_releases(armed):
    val = jnp.zeros((4, 4), jnp.float32)  # no named placement: satisfies
    handle = graftmem.track(_owner(), "buf", "pool_codes", val)
    st = graftshard.status()
    assert st["enabled"] is True
    assert st["checks"] >= 1 and st["violations"] == 0
    assert st["tracked"] == 1
    assert graftshard.audit() == []
    graftmem.release(handle)
    assert graftshard.status()["tracked"] == 0


def test_auditor_must_find_wrong_placement_at_track(armed):
    """The pinned must-find: a buffer placed over pp against a
    declared-"replicated" holding raises GraftshardError with full
    provenance, and audit() reports the still-live violation row."""
    val = _pp_placed()
    with pytest.raises(graftshard.GraftshardError) as ei:
        graftmem.track(_owner(), "buf", "pool_codes", val)
    e = ei.value
    assert e.holding == "buf" and e.component == "pool_codes"
    assert e.expected == "replicated" and e.found == ("pp",)
    assert e.where == f"{armed}:1"  # the PLACEMENT_CONTRACT line
    assert "contract at" in str(e)
    # the holding registered before the check: audit() sees it live
    rows = graftshard.audit()
    assert len(rows) == 1
    assert rows[0]["holding"] == "buf" and rows[0]["found"] == ["pp"]
    assert rows[0]["where"] == f"{armed}:1"
    assert graftshard.status()["violations"] >= 1


def test_auditor_rechecks_on_update(armed):
    """The donated-mover path: a holding tracked clean, then re-bound
    to a wrongly placed buffer at graftmem.update time, raises — the
    placement must survive every rebind."""
    handle = graftmem.track(_owner(), "buf", "pool_codes",
                            jnp.zeros((4, 4), jnp.float32))
    bad = _pp_placed()
    with pytest.raises(graftshard.GraftshardError):
        graftmem.update(handle, bad)
    graftmem.release(handle)


def test_auditor_disarmed_is_inert(monkeypatch):
    monkeypatch.delenv("GRAFTSHARD", raising=False)
    graftshard.clear()
    val = _pp_placed()
    handle = graftmem.track(_owner(), "buf", "pool_codes", val)  # no raise
    st = graftshard.status()
    assert st["enabled"] is False and st["tracked"] == 0
    graftmem.release(handle)


def test_auditor_ignores_undeclared_holdings(armed):
    """A holding the contract does not declare audits nothing —
    declaring is the static pass's discipline, auditing the declared
    is the dynamic half's."""
    val = _pp_placed()
    handle = graftmem.track(_owner(), "other", "pool_codes", val)
    assert graftshard.status()["tracked"] == 0
    graftmem.release(handle)
