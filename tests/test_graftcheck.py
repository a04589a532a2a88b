"""graftcheck in-suite driver (ISSUE 3 tentpole).

Three layers of pinning:

1. the REPO passes its own verifier — lint + semantic + recompile
   self-checks, wrap-tolerant, failing on any non-baselined finding;
2. deliberately broken fixtures (bad pspec, contract-mismatched stage,
   non-bijective ppermute, jit-in-handler, host-sync, undeclared jit,
   closure capture, time/metrics under jit) each produce a failing
   finding with file:line diagnostics;
3. the recompile-budget certifier's static bound EQUALS the observed
   jit cache sizes for the workloads PR 1's compile-space tests pin —
   no looser, no tighter.

The graftsan sanitize pass rides the same strict driver (a new
undeclared-donation or aliasing finding anywhere in the tree fails
``test_repo_passes_graftcheck``); its rule fixtures and the dynamic
sanitizer live in tests/test_graftsan.py.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine, SamplingConfig
from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine

from tools.graftcheck import cli, lint, recompile as R, sarif, semantic
from tools.graftcheck.core import (Finding, current_pr, load_baseline,
                                   split_findings, stale_audits)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = gpt2.GPT2Config(vocab_size=97, n_positions=128, n_embd=32,
                      n_layer=2, n_head=4)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(CFG, jax.random.PRNGKey(0))


# -- 1. the repo passes its own verifier -------------------------------------


def test_repo_passes_graftcheck():
    # strict: a stale baseline entry (dead suppression) fails the suite
    # too, not just the explicit stale_baseline assert below — CI
    # catches suppressions that outlive their findings
    payload = cli.run(root=REPO, strict=True)
    assert payload["strict"] is True
    assert payload["ok"], "\n".join(
        f"{f['path']}:{f['line']}: [{f['rule']}] {f['message']}"
        for f in payload["findings"]) or (
        "stale baseline entries under --strict: "
        f"{payload['stale_baseline']}")
    assert payload["stale_baseline"] == [], (
        "baseline entries whose findings are gone — delete the lines: "
        f"{payload['stale_baseline']}")
    assert payload["semantic_checks"] >= 20, "semantic pass went vacuous"
    assert payload["sanitize_checks"] >= 100, (
        "graftsan sanitize pass went vacuous — a new undeclared "
        "donation or aliasing finding anywhere in the tree fails this "
        "strict run (see tests/test_graftsan.py for the rule fixtures)")
    assert payload["locks_checks"] >= 100, (
        "graftlock locks pass went vacuous — a new unguarded-state / "
        "lock-order / atomic-check-act / blocking-under-lock finding "
        "anywhere in the tree fails this strict run (rule fixtures in "
        "tests/test_graftlock.py)")
    assert payload["locks_vacuous"] == [], (
        "lock-constructing modules with ZERO guarded regions — the "
        "concurrency contract stopped seeing their locking: "
        f"{payload['locks_vacuous']}")
    # every threaded module the locks pass tracks declares and USES its
    # contract (>= 1 with-region on a declared lock per module)
    regions = payload["locks_guarded_regions"]
    for rel in ("llm_sharding_demo_tpu/runtime/kv_pool.py",
                "llm_sharding_demo_tpu/runtime/iterbatch.py",
                "llm_sharding_demo_tpu/runtime/batcher.py",
                "llm_sharding_demo_tpu/runtime/prefix_cache.py",
                "llm_sharding_demo_tpu/runtime/spec_decode.py",
                "llm_sharding_demo_tpu/utils/metrics.py",
                "llm_sharding_demo_tpu/utils/tracing.py"):
        assert regions.get(rel, 0) >= 1, (
            f"{rel}: no guarded region — its GUARDED_STATE declaration "
            "no longer matches any `with <lock>` hold")
    assert payload["fault_checks"] >= 20, (
        "graftfault faults pass went vacuous — a new bare-blocking-call"
        " / unbounded-retry / deadline-drop / swallowed-fault finding "
        "anywhere in the tree fails this strict run (rule fixtures in "
        "tests/test_graftfault.py)")
    assert payload["fault_vacuous"] == [], (
        "boundary modules whose FAULT_POLICY covers none of their "
        f"blocking sites: {payload['fault_vacuous']}")
    # every boundary module declares a LIVE fault policy (>= 1 declared
    # entry matching a real blocking site)
    fpol = payload["fault_policies"]
    for rel in ("llm_sharding_demo_tpu/serving/app.py",
                "llm_sharding_demo_tpu/runtime/iterbatch.py",
                "llm_sharding_demo_tpu/runtime/batcher.py",
                "llm_sharding_demo_tpu/utils/subproc.py"):
        assert fpol.get(rel, 0) >= 1, (
            f"{rel}: no matched FAULT_POLICY entry — its fault "
            "contract no longer matches any blocking site")
    assert payload["scope_checks"] >= 10, (
        "graftscope static pass went vacuous — a new unprofiled jit "
        "entry point anywhere in the tree fails this strict run (rule "
        "fixtures in tests/test_graftscope.py)")
    assert payload["scope_vacuous"] == [], (
        "entry-point-declaring modules with ZERO graftscope-"
        "instrumented jit sites — device-time attribution went blind "
        f"there: {payload['scope_vacuous']}")
    # every runtime module with jit entry points has live profiled
    # dispatch sites (the PROFILED_SCOPES contract is not just declared)
    scoped = payload["scope_profiled_regions"]
    for rel in ("llm_sharding_demo_tpu/runtime/engine.py",
                "llm_sharding_demo_tpu/runtime/iterbatch.py",
                "llm_sharding_demo_tpu/runtime/spec_decode.py",
                "llm_sharding_demo_tpu/runtime/kv_pool.py",
                "llm_sharding_demo_tpu/runtime/batcher.py",
                "llm_sharding_demo_tpu/runtime/prefix_cache.py"):
        assert scoped.get(rel, 0) >= 1, (
            f"{rel}: no graftscope-instrumented jit site — its "
            "PROFILED_SCOPES declaration no longer matches any "
            "graftscope.instrument wrap")
    assert payload["slo_checks"] >= 10, (
        "graftload slo pass went vacuous — a new profile-without-slo / "
        "slo-without-source-metric finding anywhere in the tree fails "
        "this strict run (rule fixtures in tests/test_graftload.py)")
    assert payload["slo_vacuous"] == [], (
        "SLO_POLICY declarations matching no registered workload "
        f"profile: {payload['slo_vacuous']}")
    # the profile registry carries a LIVE policy per profile
    assert payload["slo_policies"].get(
        "llm_sharding_demo_tpu/loadgen/profiles.py", 0) >= 5, (
        "loadgen/profiles.py: the SLO_POLICY contract no longer "
        "matches the registered PROFILES")
    assert payload["fleet_checks"] >= 10, (
        "graftfleet fleet pass went vacuous — a new fleet-role / "
        "undeclared-replica-hop / handoff-provenance / "
        "affinity-key-drift finding anywhere in the tree fails this "
        "strict run (rule fixtures in tests/test_graftfleet.py)")
    assert payload["fleet_vacuous"] == [], (
        "fleet contract declarations matching nothing live: "
        f"{payload['fleet_vacuous']}")
    # the declared topology is LIVE: both hops dispatched, the router's
    # wire scope real, the adoption boundary enumerated, the affinity
    # key derived from the registry's own derivation
    fpol2 = payload["fleet_policies"]
    assert fpol2.get("llm_sharding_demo_tpu/fleet/topology.py", 0) >= 2, (
        "fleet/topology.py: HANDOFF_POLICY no longer matches the "
        "router's live _hop dispatches")
    assert fpol2.get("llm_sharding_demo_tpu/serving/router.py", 0) >= 1, (
        "serving/router.py: HOP_SCOPES no longer matches any replica "
        "wire call")
    assert fpol2.get(
        "llm_sharding_demo_tpu/runtime/prefix_cache.py", 0) >= 2, (
        "runtime/prefix_cache.py: HANDOFF_SCOPES no longer matches the "
        "registry surface (lookup_prefix/register_prefix sites moved)")
    assert fpol2.get("llm_sharding_demo_tpu/fleet/affinity.py", 0) >= 1, (
        "fleet/affinity.py: the affinity key is no longer derived from "
        "the declared AFFINITY_KEY_SOURCE")
    assert payload["watch_checks"] >= 10, (
        "graftwatch watch pass went vacuous — a new "
        "plan-signal-without-source / uncertified-plan-switch finding "
        "anywhere in the tree fails this strict run (rule fixtures in "
        "tests/test_graftwatch.py)")
    assert payload["watch_vacuous"] == [], (
        "watch contract declarations resolving to nothing live (the "
        "re-planner went blind or uncertified): "
        f"{payload['watch_vacuous']}")
    # every consumed signal resolves to a live emitted series
    assert payload["watch_signals"].get(
        "llm_sharding_demo_tpu/utils/graftwatch.py", 0) >= 10, (
        "utils/graftwatch.py: PLAN_SIGNALS no longer resolves the "
        "declared signal vocabulary to emitted METRIC_CATALOG series")
    assert payload["timeline_checks"] >= 10, (
        "grafttime timeline pass went vacuous — a new "
        "undeclared-timeline-event / timeline-event-not-emitted "
        "finding anywhere in the tree fails this strict run (rule "
        "fixtures in tests/test_grafttime.py)")
    assert payload["timeline_vacuous"] == [], (
        "TIMELINE_EVENTS declarations with no live emission (a "
        "timeline producer went dark): "
        f"{payload['timeline_vacuous']}")
    # the spine's producers each publish at least one live kind
    tl = payload["timeline_kinds"]
    for mod, floor in (("llm_sharding_demo_tpu/utils/tracing.py", 2),
                       ("llm_sharding_demo_tpu/utils/graftscope.py", 3),
                       ("llm_sharding_demo_tpu/runtime/iterbatch.py", 5),
                       ("llm_sharding_demo_tpu/utils/graftfault.py", 2),
                       ("llm_sharding_demo_tpu/utils/graftwatch.py", 2),
                       ("llm_sharding_demo_tpu/loadgen/driver.py", 1)):
        assert tl.get(mod, 0) >= floor, (
            f"{mod}: fewer than {floor} live timeline kind(s) — a "
            "declared producer stopped publishing")
    assert payload["trend_checks"] >= 15, (
        "grafttrend trend pass went vacuous — a new slo-without-watch "
        "/ watch-without-source / malformed-watch finding anywhere in "
        "the tree fails this strict run (rule fixtures in "
        "tests/test_grafttrend.py)")
    assert payload["trend_vacuous"] == [], (
        "WATCH_POLICY declarations covering zero SLO source series "
        "(the declared promises stopped being watched): "
        f"{payload['trend_vacuous']}")
    # every declared SLO promise keeps a live burn watch
    assert payload["trend_policies"].get(
        "llm_sharding_demo_tpu/utils/grafttrend.py", 0) >= 8, (
        "utils/grafttrend.py: WATCH_POLICY no longer resolves its "
        "declared watches against emitted series + declared budgets")
    assert payload["numerics_checks"] >= 10, (
        "graftnum numerics pass went vacuous — a new undeclared-cast / "
        "unstable-reduction / silent-downcast / approx-without-oracle "
        "finding anywhere in the tree fails this strict run (rule "
        "fixtures in tests/test_graftnum.py)")
    assert payload["numerics_vacuous"] == [], (
        "PRECISION_CONTRACT declarations resolving to zero live "
        f"entries: {payload['numerics_vacuous']}")
    # every low-precision module declares a LIVE precision contract
    npc = payload["numerics_contracts"]
    for rel in ("llm_sharding_demo_tpu/ops/quant.py",
                "llm_sharding_demo_tpu/ops/layers.py",
                "llm_sharding_demo_tpu/ops/kv_quant.py",
                "llm_sharding_demo_tpu/runtime/engine.py",
                "llm_sharding_demo_tpu/runtime/kv_pool.py",
                "llm_sharding_demo_tpu/models/moe.py"):
        assert npc.get(rel, 0) >= 1, (
            f"{rel}: no live PRECISION_CONTRACT entry — the numerics "
            "discipline stopped seeing its low-precision paths")
    assert payload["memory_checks"] >= 10, (
        "graftmem memory pass went vacuous — a new "
        "untracked-device-state / ledger-drift / "
        "unbounded-device-growth finding anywhere in the tree fails "
        "this strict run (rule fixtures in tests/test_graftmem.py)")
    assert payload["memory_vacuous"] == [], (
        "MEMORY_LEDGER declarations with no live graftmem.track site "
        "(a pool-holding module went unattributed): "
        f"{payload['memory_vacuous']}")
    # every module holding long-lived device state declares a LIVE ledger
    ml = payload["memory_ledgers"]
    for rel in ("llm_sharding_demo_tpu/runtime/kv_pool.py",
                "llm_sharding_demo_tpu/runtime/engine.py",
                "llm_sharding_demo_tpu/runtime/iterbatch.py",
                "llm_sharding_demo_tpu/runtime/spec_decode.py",
                "llm_sharding_demo_tpu/runtime/prefix_cache.py"):
        assert ml.get(rel, 0) >= 1, (
            f"{rel}: no live MEMORY_LEDGER holding — its device "
            "allocations stopped registering with the byte ledger")
    assert payload["tier_checks"] >= 10, (
        "grafttier tier pass went vacuous — a new "
        "undeclared-tier-movement / tier-ledger-gap / "
        "tier-event-drift finding anywhere in the tree fails this "
        "strict run (rule fixtures in tests/test_kv_tier.py)")
    assert payload["tier_vacuous"] == [], (
        "TIER_POLICY declarations with no live spill scope (the tier "
        f"boundary went dark): {payload['tier_vacuous']}")
    # the tier module's demote AND promote scopes both move blocks
    assert payload["tier_policies"].get(
        "llm_sharding_demo_tpu/runtime/kv_tier.py", 0) >= 2, (
        "runtime/kv_tier.py: SPILL_SCOPES no longer resolves both "
        "movement scopes against live demote/promote call sites")
    assert payload["placement_checks"] >= 10, (
        "graftshard placement pass went vacuous — a new placement-drift"
        " / undeclared-collective / replicated-large-buffer / "
        "hot-path-reshard finding anywhere in the tree fails this "
        "strict run (rule fixtures in tests/test_graftshard.py)")
    assert payload["placement_vacuous"] == [], (
        "PLACEMENT_CONTRACT declarations resolving to nothing live "
        "(placement discipline stopped seeing that module's mesh): "
        f"{payload['placement_vacuous']}")
    # the mesh-positioned modules each declare a LIVE placement contract
    pc = payload["placement_contracts"]
    for rel in ("llm_sharding_demo_tpu/parallel/ppdecode.py",
                "llm_sharding_demo_tpu/ops/ring_attention.py",
                "llm_sharding_demo_tpu/runtime/kv_pool.py",
                "llm_sharding_demo_tpu/models/llama.py"):
        assert pc.get(rel, 0) >= 1, (
            f"{rel}: no live PLACEMENT_CONTRACT/SHARDING_DESCRIPTOR "
            "declaration — its mesh position went undeclared")
    assert payload["stale_audits"] == [], (
        "baseline suppressions whose 'audited: PR<n>' tag lapsed — "
        f"re-verify and re-tag: {payload['stale_audits']}")
    # the full run reports every pass, each with its wall time
    assert payload["passes_run"] == list(cli.PASS_IDS)
    assert set(payload["pass_seconds"]) == set(cli.PASS_IDS)
    assert payload["suppressed"] >= 1, (
        "the documented sync points should be baselined findings — an "
        "empty suppression set means the host-sync rule stopped seeing "
        "them")
    for label, bounds in payload["recompile_bounds"].items():
        assert bounds, f"empty bound set for workload {label}"


def test_cli_module_entry_point_exits_zero():
    """Acceptance criterion: ``python -m tools.graftcheck`` exits 0 on
    the repo (run as a real subprocess from the repo root)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftcheck", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True


# -- 2. broken fixtures produce findings with file:line ----------------------


def _lint_fixture(tmp_path, relpath: str, source: str):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return lint.run_lint(str(tmp_path), paths=[str(p)],
                         with_metric_catalog=False)


def test_fixture_jit_in_handler(tmp_path):
    got = _lint_fixture(tmp_path, "serving/app.py", """\
        import jax

        def handler(req):
            fn = jax.jit(lambda x: x + 1)
            return fn(req)
        """)
    hits = [f for f in got if f.rule == "jit-in-handler"]
    assert len(hits) == 1
    assert hits[0].path == "serving/app.py" and hits[0].line == 4
    assert hits[0].scope == "handler"


def test_fixture_host_sync_in_hot_loop(tmp_path):
    got = _lint_fixture(tmp_path, "runtime/hot.py", """\
        import numpy as np

        GRAFTCHECK_HOT_LOOPS = ("Engine._advance",)

        class Engine:
            def _advance(self, state):
                n = state.counts.item()
                arr = np.asarray(state.tokens)
                return n, float(state.depth)
        """)
    hits = [f for f in got if f.rule == "host-sync"]
    assert [h.line for h in hits] == [7, 8, 9]
    assert all(h.scope == "Engine._advance" for h in hits)


def test_fixture_undeclared_and_stale_jit(tmp_path):
    got = _lint_fixture(tmp_path, "runtime/mod.py", """\
        import jax

        JIT_ENTRY_POINTS = ("_gone",)

        def _impl(x):
            return x

        _fast = jax.jit(_impl)
        """)
    msgs = [f.message for f in got if f.rule == "undeclared-jit"]
    assert len(msgs) == 2
    assert any("'_fast' missing from" in m for m in msgs)
    assert any("'_gone'" in m and "stale" in m for m in msgs)


def test_fixture_jit_closure_capture(tmp_path):
    got = _lint_fixture(tmp_path, "ops/build.py", """\
        import jax

        class Helper:
            scale = 2.0

        def build(scale):
            bad = jax.jit(lambda x: x * scale)
            good = jax.jit(lambda x, _s=scale: x * _s)
            ok = jax.jit(lambda x: x * Helper.scale)  # module-level class
            return bad, good, ok
        """)
    hits = [f for f in got if f.rule == "jit-closure"]
    assert len(hits) == 1 and "'scale'" in hits[0].message
    assert hits[0].line == 7


def test_fixture_time_and_metrics_in_jit(tmp_path):
    got = _lint_fixture(tmp_path, "runtime/jitted.py", """\
        import time

        import jax

        JIT_ENTRY_POINTS = ("f",)

        @jax.jit
        def f(x):
            REGISTRY.inc("steps_total")
            t = time.perf_counter()
            with timed("decode_seconds"):
                pass
            return x + t
        """)
    rules = sorted(f.rule for f in got)
    assert rules.count("time-in-jit") == 1
    assert rules.count("metrics-in-jit") == 2  # REGISTRY.inc + timed(...)
    by_rule = {f.rule: f for f in got}
    assert by_rule["time-in-jit"].line == 10


def test_fixture_lint_is_wrap_tolerant(tmp_path):
    """A call split across continuation lines is one ast.Call — the
    finding lands on the call line regardless of wrapping."""
    got = _lint_fixture(tmp_path, "runtime/hot.py", """\
        import numpy as np

        GRAFTCHECK_HOT_LOOPS = ("loop",)

        def loop(state):
            return np.asarray(
                state.tokens)
        """)
    hits = [f for f in got if f.rule == "host-sync"]
    assert len(hits) == 1 and hits[0].line == 6


def test_fixture_bad_pspec():
    from jax.sharding import PartitionSpec as P
    # unknown axis
    got = semantic.check_pspec(P("nope"), (8, 4), {"tp": 2}, "fix")
    assert len(got) == 1 and "names mesh axis 'nope'" in got[0].message
    # non-divisible sharded dim
    got = semantic.check_pspec(P("tp"), (7, 4), {"tp": 2}, "fix")
    assert len(got) == 1 and "not divisible" in got[0].message
    # rank overflow
    got = semantic.check_pspec(P(None, None, "tp"), (8, 4), {"tp": 2}, "fix")
    assert any("exceeds array rank" in f.message for f in got)
    # axis used twice
    got = semantic.check_pspec(P("tp", "tp"), (4, 4), {"tp": 2}, "fix")
    assert any("at most one dim" in f.message for f in got)
    # multi-axis sharding splits the dim by the PRODUCT of the axes:
    # per-axis divisibility alone would wrongly accept (2 % 2 == 0)
    got = semantic.check_pspec(P(("dp", "tp")), (2, 4),
                               {"dp": 2, "tp": 2}, "fix")
    assert len(got) == 1 and "'dp'*'tp'=4" in got[0].message
    assert semantic.check_pspec(P(("dp", "tp")), (4, 4),
                                {"dp": 2, "tp": 2}, "ok") == []
    # a valid spec is silent
    assert semantic.check_pspec(P(None, "tp"), (7, 4), {"tp": 2}, "ok") == []


def test_fixture_uneven_stage_nondivisible_sharded_dim():
    """The partition-plan edge case the verifier must catch: an uneven
    3-stage stacking sharded over a 2-wide pp axis — dim 0 (= n_stages)
    is not divisible by the mesh axis."""
    from jax.sharding import PartitionSpec as P
    got = semantic.check_pspec(P("pp"), (3, 2, 8, 8), {"pp": 2},
                               "uneven-1+2+1/pp2")
    assert len(got) == 1
    assert "dim 0 of size 3 not divisible by mesh axis 'pp'=2" \
        in got[0].message


def test_fixture_partition_plan_overlap_and_gap():
    from llm_sharding_demo_tpu.parallel.partition import StageSpec
    # overlapping / out-of-order boundaries -> empty stage
    got = semantic.check_partition_plan(4, [2, 2], "overlap")
    assert len(got) == 1 and "disjoint and exhaustive" in got[0].message
    # out-of-range boundary
    got = semantic.check_partition_plan(4, [5], "oob")
    assert len(got) == 1
    # non-exhaustive externally built stage list (covers [0, 3) of 4)
    specs = [StageSpec(index=0, n_stages=2, start=0, end=2),
             StageSpec(index=1, n_stages=2, start=2, end=3)]
    got = semantic.check_spec_list(specs, 4, "gap")
    assert len(got) == 1 and "cover [0,3)" in got[0].message
    # overlapping stage list
    specs = [StageSpec(index=0, n_stages=2, start=0, end=3),
             StageSpec(index=1, n_stages=2, start=2, end=4)]
    got = semantic.check_spec_list(specs, 4, "overlap2")
    assert len(got) == 1 and "gap/overlap" in got[0].message


def test_fixture_contract_mismatched_stage():
    mid = jax.ShapeDtypeStruct((2, 6, 8), jnp.float32)
    first_in = jax.ShapeDtypeStruct((2, 6), jnp.int32)
    last = jax.ShapeDtypeStruct((2, 6, 97), jnp.float32)

    def ok_stage(out_shape, dtype=jnp.float32):
        return lambda x: (jax.ShapeDtypeStruct(out_shape, dtype), True)

    # wrong hidden width out of stage 0
    got = semantic.check_stage_chain(
        [ok_stage((2, 6, 9)), ok_stage((2, 6, 97))],
        first_in, mid, last, "fixture")
    assert len(got) == 1 and "stage 0 emits (2, 6, 9)" in got[0].message
    # wrong inter-stage dtype
    got = semantic.check_stage_chain(
        [ok_stage((2, 6, 8), jnp.bfloat16), ok_stage((2, 6, 97))],
        first_in, mid, last, "fixture")
    assert len(got) == 1 and "bfloat16" in got[0].message
    # cache aval drift
    got = semantic.check_stage_chain(
        [lambda x: (mid, False), ok_stage((2, 6, 97))],
        first_in, mid, last, "fixture")
    assert len(got) == 1 and "cache" in got[0].message
    # clean chain is silent
    got = semantic.check_stage_chain(
        [ok_stage((2, 6, 8)), ok_stage((2, 6, 97))],
        first_in, mid, last, "fixture")
    assert got == []


def test_real_family_contracts_clean_and_bad_plan_caught():
    got = semantic.check_stage_contracts(gpt2, CFG, (1,), where="gpt2/2st")
    assert got == []
    got = semantic.check_stage_contracts(gpt2, CFG, (5,), where="gpt2/bad")
    assert len(got) == 1 and "rejected partition plan" in got[0].message


def test_fixture_nonbijective_ppermute():
    got = semantic.check_permutation([(0, 1), (0, 2)], 4, "fix")
    assert len(got) == 1 and "double-send" in got[0].message
    got = semantic.check_permutation([(0, 1), (2, 1)], 4, "fix")
    assert len(got) == 1 and "colliding receives" in got[0].message
    got = semantic.check_permutation([(0, 9)], 4, "fix")
    assert len(got) == 1 and "out of range" in got[0].message
    # the real ring is clean at every registered size
    from llm_sharding_demo_tpu.parallel.ppdecode import \
        stage_ring_permutation
    for n in (1, 2, 4, 8):
        assert semantic.check_permutation(
            stage_ring_permutation(n), n, "ring") == []


def test_ppermute_extraction_from_traced_program():
    """collect_ppermutes reads the permutation out of the JAXPR a
    shard_map program will actually run — including a deliberately
    non-bijective one, which the checker must then reject."""
    import functools
    from jax.sharding import AbstractMesh, PartitionSpec as P
    try:
        from jax import shard_map
        smap = functools.partial(shard_map, axis_names={"pp"})
    except ImportError:
        from jax.experimental.shard_map import shard_map as smap
    mesh = AbstractMesh((4,), ("pp",))

    def traced(perm):
        def per_device(x):
            return jax.lax.ppermute(x, "pp", perm)
        return smap(per_device, mesh=mesh, in_specs=(P("pp"),),
                    out_specs=P("pp"))

    aval = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    good = semantic.collect_ppermutes(traced([(0, 1), (1, 2), (2, 3)]), aval)
    assert len(good) == 1 and good[0][1] == ((0, 1), (1, 2), (2, 3))
    assert semantic.check_permutation(good[0][1], 4, "ok") == []
    bad = semantic.collect_ppermutes(traced([(0, 1), (2, 1)]), aval)
    assert len(bad) == 1
    assert semantic.check_permutation(bad[0][1], 4, "bad") != []
    # and the registry-driven ring check is clean end to end
    assert semantic.check_ring_program(4, "ring/pp=4") == []


# -- baseline workflow -------------------------------------------------------


def test_baseline_parse_suppress_and_stale(tmp_path):
    bl = tmp_path / "baseline.txt"
    bl.write_text(
        "# comment\n"
        "\n"
        "host-sync a/b.py::C.m the documented sync point\n"
        "host-sync a/b.py::C.gone fixed long ago\n")
    baseline = load_baseline(str(bl))
    assert baseline[("host-sync", "a/b.py", "C.m")].startswith("the doc")
    found = [Finding("host-sync", "a/b.py", 3, "C.m", "np.asarray"),
             Finding("host-sync", "a/b.py", 9, "C.m", "item()"),
             Finding("host-sync", "a/b.py", 4, "C.other", "float()")]
    active, suppressed, stale = split_findings(found, baseline)
    assert [f.scope for f in active] == ["C.other"]
    assert len(suppressed) == 2          # one entry covers the scope
    assert stale == {("host-sync", "a/b.py", "C.gone")}
    bl.write_text("host-sync missing-scope-separator why\n")
    with pytest.raises(ValueError, match="malformed baseline line"):
        load_baseline(str(bl))


def test_audit_tags_machine_checked(tmp_path):
    """Suppressions age: an entry with no ``audited: PR<n>`` tag, or
    one older than the last core.AUDIT_WINDOW PRs, is a stale-audit row
    (--strict fails on any); a fresh tag is clean."""
    (tmp_path / "CHANGES.md").write_text(
        "PR 9: something\nPR 17: something else\n")
    assert current_pr(str(tmp_path)) == 18
    bl = tmp_path / "baseline.txt"
    bl.write_text(
        "host-sync a/b.py::C.fresh documented (audited: PR17)\n"
        "host-sync a/b.py::C.old documented (audited: PR1)\n"
        "host-sync a/b.py::C.untagged documented but never re-verified\n")
    rows = stale_audits(str(bl), str(tmp_path))
    assert len(rows) == 2
    assert any("C.old" in r and "audited PR1" in r for r in rows)
    assert any("C.untagged" in r and "no 'audited: PR<n>' tag" in r
               for r in rows)
    assert not any("C.fresh" in r for r in rows)
    # a window-edge tag (current - window + 1) still passes
    from tools.graftcheck.core import AUDIT_WINDOW
    bl.write_text(
        f"host-sync a/b.py::C.edge ok (audited: PR{19 - AUDIT_WINDOW})\n")
    assert stale_audits(str(bl), str(tmp_path)) == []
    bl.write_text(
        f"host-sync a/b.py::C.edge ok (audited: PR{18 - AUDIT_WINDOW})\n")
    assert len(stale_audits(str(bl), str(tmp_path))) == 1
    # no CHANGES.md -> staleness can't be judged -> report nothing
    assert stale_audits(str(bl), str(tmp_path / "nowhere")) == []


def test_repo_baseline_audit_tags_fresh():
    """Every suppression in the repo's own baseline carries a
    fresh-enough audit tag (the strict driver fails otherwise)."""
    assert stale_audits() == [], (
        "re-verify these baseline suppressions and re-tag them "
        "'audited: PR<n>'")


def test_sarif_output_schema_pinned():
    """The --sarif emitter: SARIF 2.1.0, one run, driver graftcheck,
    rules collected from findings, file:line regions, and baseline-
    suppressed findings riding along marked externally suppressed
    (never dropped)."""
    payload = {
        "findings": [{"rule": "host-sync", "path": "a/b.py", "line": 7,
                      "scope": "C.m", "message": "np.asarray in loop"}],
        "suppressed_findings": [
            {"rule": "overlap", "path": "c/d.py", "line": 3,
             "scope": "C.n", "message": "documented",
             "justification": "by design (audited: PR18)"}],
    }
    doc = sarif.to_sarif(payload)
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
    assert len(doc["runs"]) == 1
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftcheck"
    assert [r["id"] for r in run["tool"]["driver"]["rules"]] == [
        "host-sync", "overlap"]  # sorted
    active, suppressed = run["results"]
    assert active["ruleId"] == "host-sync" and active["level"] == "error"
    loc = active["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "a/b.py"
    assert loc["region"]["startLine"] == 7
    assert "suppressions" not in active
    assert suppressed["level"] == "note"
    assert suppressed["suppressions"] == [{
        "kind": "external",
        "justification": "by design (audited: PR18)"}]


def test_sarif_cli_flag_emits_valid_document():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftcheck", "--lint-only",
         "--sarif"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    results = doc["runs"][0]["results"]
    # the documented baselined sync points ride along suppressed
    assert any(r.get("suppressions") for r in results)
    assert all(r["level"] == "note" for r in results
               if r.get("suppressions"))


def test_pass_selection_runs_subset_with_timings():
    """--passes runs exactly the selection; skipped passes report their
    schema defaults so journal consumers never branch on key presence;
    per-pass wall time rides in pass_seconds."""
    payload = cli.run(root=REPO, lint_only=True,
                      passes=("lint", "locks", "placement"))
    assert payload["passes_run"] == ["lint", "locks", "placement"]
    assert set(payload["pass_seconds"]) == {"lint", "locks", "placement"}
    assert all(t >= 0 for t in payload["pass_seconds"].values())
    assert payload["locks_checks"] >= 1
    assert payload["placement_checks"] >= 1
    # skipped passes: defaults, visibly dead
    assert payload["sanitize_checks"] == 0
    assert payload["numerics_checks"] == 0
    assert payload["numerics_contracts"] == {}


def test_pass_selection_rejects_unknown_and_strict_subsets():
    with pytest.raises(ValueError, match="unknown pass id"):
        cli.run(root=REPO, passes=("nope",))
    with pytest.raises(ValueError, match="strict requires the full"):
        cli.run(root=REPO, strict=True, passes=("locks",))
    # the CLI maps the refusal to exit code 2 (usage error, not finding)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftcheck", "--strict",
         "--passes", "locks"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert "strict requires the full pass set" in proc.stderr


# -- 3. recompile-budget certifier == observed cache sizes -------------------


def test_cert_equals_engine_cache_sizes(params):
    """The test_observability compile-space workload, certified: repeat
    solo generates mint nothing new, a new batch width mints exactly the
    certified programs — bound == _cache_size(), no looser, no tighter."""
    eng = DecodeEngine(params, CFG, max_seq=64)
    prompt = np.arange(1, 9, dtype=np.int32)
    eng.generate(prompt, max_new_tokens=4)
    eng.generate(prompt, max_new_tokens=4)
    eng.generate(np.tile(prompt, (2, 1)), max_new_tokens=4)

    desc = R.EngineDesc(max_seq=64)
    g = R.greedy_sampling()
    cert = R.certify(desc, [
        R.GenerateCall(prompt_lens=(8,), max_new=4, sampling=g),
        R.GenerateCall(prompt_lens=(8,), max_new=4, sampling=g),
        R.GenerateCall(prompt_lens=(8, 8), max_new=4, sampling=g),
    ])
    assert cert["_prefill"] == eng._prefill._cache_size() == 2
    assert cert["_decode_seg"] == eng._decode_seg._cache_size() == 2
    assert cert["_prefill_chunked"] == \
        eng._prefill_chunked._cache_size() == 0


def test_cert_equals_chunked_prefill_cache_sizes(params):
    eng = DecodeEngine(params, CFG, max_seq=128, prefill_chunk=16)
    rng = np.random.default_rng(3)
    eng.generate(rng.integers(0, CFG.vocab_size, size=(40,)),
                 max_new_tokens=8)
    desc = R.EngineDesc(max_seq=128, prefill_chunk=16)
    cert = R.certify(desc, [R.GenerateCall(prompt_lens=(40,), max_new=8,
                                           sampling=R.greedy_sampling())])
    assert cert["_prefill_chunked"] == \
        eng._prefill_chunked._cache_size() == 1
    assert cert["_prefill"] == eng._prefill._cache_size() == 0
    assert cert["_decode_seg"] == eng._decode_seg._cache_size() == 1


def test_cert_equals_spec_batched_loop_cache_sizes(params):
    """The PR 1 workload of test_spec_batched_compile_space_bounded,
    certified: acceptance patterns are traced values — ONE program per
    (width, max_new, policy), and the static bound equals the observed
    cache size at both workload points."""
    spec = SpecDecodeEngine(params, CFG, max_seq=128, draft_len=4)
    rng = np.random.default_rng(9)
    batches = [
        [np.asarray([5, 17, 3, 42] * 3, np.int32),
         rng.integers(0, CFG.vocab_size, size=(12,)).astype(np.int32)],
        [rng.integers(0, CFG.vocab_size, size=(7,)).astype(np.int32),
         np.asarray([2] * 9, np.int32)],
        [np.asarray([8, 3] * 5, np.int32),
         np.asarray([1, 2, 3] * 4, np.int32)],
    ]
    for b in batches:
        spec.generate(b, max_new_tokens=16)

    desc = R.EngineDesc(max_seq=128)
    sd = R.SpecDesc(draft_len=4)
    g = R.greedy_sampling()
    calls = [R.GenerateCall(prompt_lens=(12, 12), max_new=16, sampling=g),
             R.GenerateCall(prompt_lens=(7, 9), max_new=16, sampling=g),
             R.GenerateCall(prompt_lens=(10, 12), max_new=16, sampling=g)]
    cert = R.certify(desc, [], spec=sd, spec_calls=calls)
    assert cert["_loop_b"] == spec._loop_b._cache_size() == 1
    assert cert["_prefill"] == spec._eng._prefill._cache_size()

    spec.generate(batches[0], max_new_tokens=8)
    calls.append(R.GenerateCall(prompt_lens=(12, 12), max_new=8,
                                sampling=g))
    cert = R.certify(desc, [], spec=sd, spec_calls=calls)
    assert cert["_loop_b"] == spec._loop_b._cache_size() == 2
    assert cert["_prefill"] == spec._eng._prefill._cache_size()


def test_cert_equals_solo_spec_loop_cache_size(params):
    spec = SpecDecodeEngine(params, CFG, max_seq=128, draft_len=6)
    rng = np.random.default_rng(0)
    spec.generate(rng.integers(0, CFG.vocab_size, size=(9,)),
                  max_new_tokens=25)
    cert = R.certify(R.EngineDesc(max_seq=128), [],
                     spec=R.SpecDesc(draft_len=6),
                     spec_calls=[R.GenerateCall(prompt_lens=(9,),
                                                max_new=25,
                                                sampling=R.greedy_sampling())])
    assert cert["_loop"] == spec._loop._cache_size() == 1
    assert cert["_loop_b"] == spec._loop_b._cache_size() == 0


def test_cert_equals_iter_spec_segment_cache_size():
    """The PR 1 workload of test_spec_segment_compile_space_bounded
    (sequential solo spec requests through the iteration scheduler):
    one ``_seg_b`` program per (width, max_verify, policy)."""
    from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=256, n_embd=32,
                          n_layer=2, n_head=4)
    p = jax.tree.map(lambda x: x * 8.0,
                     gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    spec = SpecDecodeEngine(p, cfg, max_seq=200, draft_len=5)
    ib = IterBatchingEngine(spec.plain, max_batch=4, seg_steps=12,
                            max_wait_ms=50.0, spec=spec)
    rng = np.random.default_rng(34)
    prompts = [np.tile(np.asarray([5, 17, 3, 42], np.int32), 5),
               rng.integers(0, 211, size=(13,)),
               np.asarray([8] * 10, np.int32)]
    flagged = SamplingConfig(spec=True)
    for pr in prompts:
        ib.generate(pr, 30, sampling=flagged)
    keys = R.iter_spec_segment_keys(R.SpecDesc(draft_len=5), seg_steps=12,
                                    widths=[1], samplings=[flagged])
    assert len(keys) == spec._seg_b._cache_size() == 1


def test_planner_invariants_hold_and_catch_breakage(monkeypatch):
    desc = R.EngineDesc(max_seq=1024)
    call = R.GenerateCall(prompt_lens=(16,), max_new=700,
                          sampling=R.greedy_sampling())
    assert R.planner_invariants(desc, call) == []
    # a planner regression (steps dropped, shrinking window) is reported
    monkeypatch.setattr(DecodeEngine, "_segments",
                        lambda self, d, steps, bucket=None, quant=32:
                        [(steps - 5, 256), (1, 128)])
    problems = R.planner_invariants(desc, call)
    assert any("covers" in p for p in problems)
    assert any("shrink" in p for p in problems)
