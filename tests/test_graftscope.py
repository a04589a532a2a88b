"""graftscope device-time attribution (ISSUE 9 tentpole).

Four layers of pinning:

1. the attribution core: bounded rings (no growth under synthetic
   flood), transparent instrument wrappers, and the ``timed(sync=)``
   device-truth plumbing (the sync-mode pin itself lives in
   tests/test_observability.py beside the other tracing pins);
2. the JOIN: a real engine's observed dispatch rings equal the
   recompile certifier's program-key sets key-for-key, and
   ``tools/graftcheck scope``'s attribution run joins 1:1 on every
   exact workload;
3. the serving surface: ``GET /debug/profile`` serves live per-program
   timing + occupancy series under the threaded pooled-iterbatch app
   with GRAFTSAN=1 GRAFTSCHED=1, generation byte-equal to serial, and
   the declared overhead bound holds;
4. the gates: the ``unprofiled-entry-point`` rule fixtures each produce
   exactly the expected finding, and ``tools/bench_diff.py`` flags a
   seeded synthetic regression while passing the committed trajectory.
"""

import json
import os
import textwrap
import threading
import time

import jax
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.utils import graftsched, graftscope

from tools.graftcheck import lint, recompile as R, scope as scope_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = gpt2.GPT2Config(vocab_size=97, n_positions=128, n_embd=16,
                      n_layer=2, n_head=2)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(CFG, jax.random.PRNGKey(0))


# -- 1. the attribution core --------------------------------------------------


def test_rings_stay_bounded_under_flood():
    """The boundedness pin (ISSUE 9 satellite): 1k+ synthetic dispatch
    records and occupancy points never grow past the declared ring
    capacities — distinct program keys included (the key cap backstops
    a key-model bug)."""
    st = graftscope.ScopeState()
    for i in range(1000):
        st.record("fake._seg", (i,), 0.001)       # 1000 DISTINCT keys
        st.sample("queue_depth", i, scheduler="x")
    ring = st._rings["fake._seg"]
    assert len(ring["samples"]) == graftscope.RING_CAPACITY
    assert len(ring["programs"]) <= graftscope.KEY_CAPACITY + 1
    assert sum(v[0] for v in ring["programs"].values()) == 1000
    key = ("queue_depth", (("scheduler", "x"),))
    assert len(st._points[key]) == graftscope.SERIES_CAPACITY
    snap = st.snapshot(n=16)
    assert len(snap["dispatch"]["fake._seg"]["ring"]) == 16
    assert len(snap["series"]["queue_depth{scheduler=x}"]) == 16
    assert snap["dispatch"]["fake._seg"]["keys_truncated"] is True
    empty = st.snapshot(n=0)                  # ?n=0 really means none
    assert empty["dispatch"]["fake._seg"]["ring"] == []
    assert empty["series"]["queue_depth{scheduler=x}"] == []
    json.dumps(snap)  # JSON-able end to end


def test_instrument_wrapper_transparent_and_records():
    """The wrapper forwards results AND attributes (_cache_size — what
    CompileWatch and the recompile-budget tests read), records one ring
    sample per call keyed by key_fn, and short-circuits when disabled."""
    calls = []

    def fn(x, y=1):
        calls.append((x, y))
        return x + y
    fn._cache_size = lambda: 7

    wrapped = graftscope.instrument(fn, "test._fn",
                                    key_fn=lambda x, y=1: (x,))
    graftscope.clear()
    assert wrapped(2, y=3) == 5
    assert wrapped._cache_size() == 7            # attribute forwarding
    keys = graftscope.program_keys("test._fn")
    assert set(keys) == {(2,)} and keys[(2,)][0] == 1
    prev = graftscope.set_enabled(False)
    try:
        assert wrapped(4) == 5                   # still computes
        assert graftscope.program_keys("test._fn")[(2,)][0] == 1  # no new
    finally:
        graftscope.set_enabled(prev)


def test_dump_restore_roundtrip():
    st = graftscope.ScopeState()
    st.record("a._f", (1,), 0.5)
    saved = st.dump_state()
    st.record("a._f", (2,), 0.5)
    st.sample("queue_depth", 3)
    st.restore_state(saved)
    assert set(st.program_keys("a._f")) == {(1,)}
    assert st._points == {}


# -- 2. the join: observed rings == certified program keys --------------------


def test_engine_rings_join_certifier_keys(params):
    """THE tentpole invariant: a real engine's observed dispatch ring
    keys equal ``recompile.engine_call_keys``'s certified sets exactly
    — same key tuples, not just same counts — for prefill and every
    decode segment program."""
    eng = DecodeEngine(params, CFG, max_seq=64)
    graftscope.clear()
    eng.generate(np.full((1, 8), 5, dtype=np.int32), 12)
    eng.generate(np.full((2, 8), 7, dtype=np.int32), 12)
    desc = R.EngineDesc(max_seq=64)
    certified = {}
    for lens in ((8,), (8, 8)):
        for name, ks in R.engine_call_keys(
                desc, R.GenerateCall(prompt_lens=lens, max_new=12)).items():
            certified.setdefault(name, set()).update(ks)
    assert set(graftscope.program_keys("engine._prefill")) \
        == certified["_prefill"]
    assert set(graftscope.program_keys("engine._decode_seg")) \
        == certified["_decode_seg"]
    # and the observed program POPULATION matches the certified bound
    assert len(graftscope.program_keys("engine._decode_seg")) \
        == len(certified["_decode_seg"])


def test_attribution_run_joins_1to1():
    """``python -m tools.graftcheck scope``'s library body: every
    exact-marked workload joins measured rings against certified keys
    1:1, and the payload carries the measured-vs-modeled drift fields
    bench.py journals."""
    payload = scope_mod.run_attribution()
    assert payload["ok"] is True
    labels = [r["workload"] for r in payload["workloads"]]
    assert labels == ["solo-greedy", "batch2-greedy", "paged-solo"]
    for row in payload["workloads"]:
        assert row["joined_1to1"] is True
        for name, e in row["entry_points"].items():
            assert e["missing"] == [] and e["extra"] == [], (name, e)
        assert row["measured_decode_seconds_per_token"] > 0
        assert row["modeled_cost_bytes_per_token"] > 0
        assert row["implied_bytes_per_second"] > 0
    # the paged row joins the pool movers too
    paged = payload["workloads"][-1]
    assert {"_gather", "_scatter"} <= set(paged["entry_points"])
    json.dumps(payload, default=str)


# -- 3. overhead bound + serving surface --------------------------------------


def test_overhead_bound_pinned(params):
    """The declared bound (graftscope.OVERHEAD_FACTOR): a decode run
    with rings enabled stays within the factor of rings-disabled wall
    time. min-of-3 on both sides absorbs CPU scheduling noise; the
    per-dispatch cost is microseconds against millisecond dispatches."""
    eng = DecodeEngine(params, CFG, max_seq=64)
    prompt = np.full((1, 8), 5, dtype=np.int32)

    def run_once():
        eng.generate(prompt, 24)

    def best_of(n):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            run_once()
            best = min(best, time.perf_counter() - t0)
        return best

    run_once()                                   # warm-up: compiles
    prev = graftscope.set_enabled(False)
    try:
        disabled = best_of(3)
    finally:
        graftscope.set_enabled(prev)
    graftscope.set_enabled(True)
    enabled = best_of(3)
    assert enabled <= disabled * graftscope.OVERHEAD_FACTOR, (
        f"graftscope overhead {enabled / disabled:.2f}x exceeds the "
        f"declared {graftscope.OVERHEAD_FACTOR}x bound")


def _iter_pool_app(monkeypatch):
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.http import TestClient
    from llm_sharding_demo_tpu.serving.tokenizer import ByteTokenizer
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    monkeypatch.setenv("GRAFTSAN", "1")
    monkeypatch.setenv("GRAFTSCHED", "1")
    graftsched.clear()
    app_cfg = gpt2.GPT2Config(vocab_size=256, n_positions=64, n_embd=32,
                              n_layer=2, n_head=4)
    model = (app_cfg, gpt2.init_params(app_cfg, jax.random.PRNGKey(0)))
    cfg = ServingConfig(model_id="test", shard_role="coordinator",
                        max_seq=64, boundaries=(1,), max_batch=4,
                        batch_mode="iter", batch_wait_ms=10.0,
                        kv_pool_blocks=24, kv_block_size=8)
    return TestClient(create_app(cfg, model=model,
                                 tokenizer=ByteTokenizer()))


def test_debug_profile_live_under_threaded_generate(monkeypatch):
    """Acceptance criterion: /debug/profile serves live per-program
    timing + occupancy series under the threaded /generate integration
    test (GRAFTSAN=1 GRAFTSCHED=1), with byte-equal generation output;
    the payload's topology header matches /healthz (same _topology
    source) and every ring honors the ?n= bound."""
    client = _iter_pool_app(monkeypatch)
    graftscope.clear()
    bodies = [{"prompt": p, "max_new_tokens": 10, "mode": "greedy"}
              for p in ("Hello, world", "abcabcabc", "xyzw")]
    serial = []
    for b in bodies:
        r = client.post("/generate", json=b)
        assert r.status_code == 200, r.text
        serial.append(r.json()["generated"])

    results = [None] * len(bodies)

    def run(i):
        r = client.post("/generate", json=bodies[i])
        results[i] = (r.status_code, r.json())

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for i, (status, body) in enumerate(results):
        assert status == 200, body
        assert body["generated"] == serial[i]    # byte-equal to serial

    prof = client.get("/debug/profile?n=8")
    assert prof.status_code == 200
    payload = prof.json()
    assert set(payload) >= {"serving", "enabled", "sync", "truth",
                            "dispatch", "series"}
    assert payload["enabled"] is True
    # topology header matches /healthz (one _topology source for both)
    health = client.get("/healthz").json()
    for k, v in payload["serving"].items():
        assert health[k] == v, k
    # live per-program timing: the scheduler's dispatch scopes are hot
    dispatch = payload["dispatch"]
    assert "engine._prefill" in dispatch
    assert "engine._decode_seg" in dispatch
    # pooled segments: a call writes back the blocks it wrote (the
    # batch's working cache stays on the device; a gather shows only
    # where a batch grew: twice at most up to max_batch=4)
    assert "kv_pool._scatter_span" in dispatch
    assert dispatch.get("kv_pool._gather", {"calls": 0})["calls"] <= 2 * (
        health["iter_batch_stats"]["batches"])
    for scope_name, entry in dispatch.items():
        assert entry["calls"] >= 1, scope_name
        assert entry["programs"] >= 1
        assert len(entry["ring"]) <= 8           # the ?n= bound
    # occupancy series: the iter scheduler's decision-point samples
    assert any(k.startswith("batch_occupancy") for k in payload["series"])
    assert any(k.startswith("queue_depth") for k in payload["series"])
    assert any(k.startswith("kv_cache_blocks_in_use")
               for k in payload["series"])
    for pts in payload["series"].values():
        assert len(pts) <= 8
    # bad query -> 422, like /debug/requests
    assert client.get("/debug/profile?n=zap").status_code == 422
    graftsched.clear()


# -- 4a. the unprofiled-entry-point rule --------------------------------------


def _scope_fixture(tmp_path, relpath: str, source: str):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return scope_mod.run_scope_static(str(tmp_path), paths=[str(p)])


def test_rule_flags_unprofiled_entry_point(tmp_path):
    findings, summary = _scope_fixture(
        tmp_path, "llm_sharding_demo_tpu/runtime/fake.py", """\
        import jax
        JIT_ENTRY_POINTS = ("_f",)

        class E:
            def __init__(self):
                self._f = jax.jit(lambda x: x)
        """)
    assert [f.rule for f in findings] == ["unprofiled-entry-point"]
    assert findings[0].scope == "_f"             # baselinable per entry
    assert "PROFILED_SCOPES" in findings[0].message
    assert summary["vacuous"] == [
        "llm_sharding_demo_tpu/runtime/fake.py"]


def test_rule_flags_declared_but_unwrapped(tmp_path):
    findings, _ = _scope_fixture(
        tmp_path, "llm_sharding_demo_tpu/runtime/fake2.py", """\
        import jax
        JIT_ENTRY_POINTS = ("_f",)
        PROFILED_SCOPES = ("_f",)

        class E:
            def __init__(self):
                self._f = jax.jit(lambda x: x)
        """)
    assert [f.rule for f in findings] == ["unprofiled-entry-point"]
    assert "not wrapped in a graftscope.instrument" in findings[0].message


def test_rule_clean_when_wrapped_and_declared(tmp_path):
    findings, summary = _scope_fixture(
        tmp_path, "llm_sharding_demo_tpu/runtime/fake3.py", """\
        import jax
        from llm_sharding_demo_tpu.utils import graftscope
        JIT_ENTRY_POINTS = ("_f",)
        PROFILED_SCOPES = ("_f",)

        class E:
            def __init__(self):
                self._f = graftscope.instrument(
                    jax.jit(lambda x: x), "fake3._f")
        """)
    assert findings == []
    assert summary["profiled_regions"][
        "llm_sharding_demo_tpu/runtime/fake3.py"] == 1
    assert summary["vacuous"] == []


def test_rule_flags_stale_profiled_declaration(tmp_path):
    findings, _ = _scope_fixture(
        tmp_path, "llm_sharding_demo_tpu/runtime/fake4.py", """\
        import jax
        from llm_sharding_demo_tpu.utils import graftscope
        JIT_ENTRY_POINTS = ("_f",)
        PROFILED_SCOPES = ("_f", "_gone")

        class E:
            def __init__(self):
                self._f = graftscope.instrument(
                    jax.jit(lambda x: x), "fake4._f")
        """)
    assert [f.rule for f in findings] == ["unprofiled-entry-point"]
    assert findings[0].scope == "_gone"
    assert "stale declaration" in findings[0].message


def test_instrument_wrapper_transparent_to_undeclared_jit(tmp_path):
    """The lint indexer resolves the holding name THROUGH the wrapper:
    an instrument-wrapped, declared jit site produces no undeclared-jit
    finding (the wrapper must not break the PR 3 contract)."""
    p = tmp_path / "llm_sharding_demo_tpu/runtime/fake5.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent("""\
        import jax
        from llm_sharding_demo_tpu.utils import graftscope
        JIT_ENTRY_POINTS = ("_f",)
        PROFILED_SCOPES = ("_f",)

        class E:
            def __init__(self):
                self._f = graftscope.instrument(
                    jax.jit(lambda x, _s=3: x), "fake5._f")
        """))
    findings = lint.run_lint(str(tmp_path), paths=[str(p)],
                             with_metric_catalog=False)
    assert [f for f in findings if f.rule == "undeclared-jit"] == []


# -- 4b. bench_diff: the perf-regression gate ---------------------------------


def _bd():
    import importlib
    import sys
    tools = os.path.join(REPO, "tools")
    added = tools not in sys.path
    if added:
        sys.path.insert(0, tools)
    try:
        return importlib.import_module("bench_diff")
    finally:
        if added:
            sys.path.remove(tools)


def test_bench_diff_flags_seeded_regression(tmp_path):
    bd = _bd()
    (tmp_path / "hist_r01.json").write_text(json.dumps(
        {"n": 1, "parsed": {"value": 100.0, "configs": [
            {"name": "cfgA", "tokens_per_sec": 500.0,
             "p50_token_latency_ms": 2.0}]}}))
    (tmp_path / "cur.json").write_text(json.dumps(
        {"value": 120.0, "configs": [
            {"name": "cfgA", "tokens_per_sec": 200.0,   # -60%: regression
             "p50_token_latency_ms": 9.0}]}))           # +350%: regression
    rc = bd.main(["--current", str(tmp_path / "cur.json"),
                  "--history", str(tmp_path / "hist_*.json")])
    assert rc == 1
    verdict = bd.compare(
        bd.extract_metrics(json.loads((tmp_path / "cur.json").read_text())),
        bd.load_history([str(tmp_path / "hist_r01.json")]))
    assert sorted(verdict["regressions"]) == [
        "cfgA.p50_token_latency_ms", "cfgA.tokens_per_sec"]
    assert verdict["ok"] is False


def test_bench_diff_passes_improvements_and_noise(tmp_path):
    bd = _bd()
    (tmp_path / "hist_r01.json").write_text(json.dumps(
        {"n": 1, "parsed": {"value": 100.0, "configs": [
            {"name": "cfgA", "tokens_per_sec": 500.0,
             "transfer_rtt_ms": 80.0}]}}))
    (tmp_path / "cur.json").write_text(json.dumps(
        {"value": 140.0, "configs": [
            {"name": "cfgA", "tokens_per_sec": 450.0,   # -10%: noise, ok
             "transfer_rtt_ms": 200.0}]}))  # environment, never gated
    rc = bd.main(["--current", str(tmp_path / "cur.json"),
                  "--history", str(tmp_path / "hist_*.json")])
    assert rc == 0


def test_bench_diff_flags_config_that_started_erroring(tmp_path):
    """A config that produced gated numbers in the latest prior run and
    ERRORS now is the worst regression — it must gate, not become a
    silent gap in the join (review hardening). Skips (no chip)
    stay non-gating: environment, not a crash."""
    bd = _bd()
    hist = {"n": 1, "parsed": {"configs": [
        {"name": "cfgA", "tokens_per_sec": 500.0}]}}
    current = {"configs": [{"name": "cfgA", "error": "Boom: died"}]}
    verdict = bd.compare(
        bd.extract_metrics(current),
        [("r01", bd.extract_metrics(hist["parsed"]))],
        current_errors=bd.error_configs(current))
    assert verdict["regressions"] == ["cfgA"]
    assert verdict["ok"] is False
    # a SKIP is not an error: same shape, skipped row, no regression
    skipped = {"configs": [{"name": "cfgA", "skipped": "chip not attached"}]}
    verdict2 = bd.compare(
        bd.extract_metrics(skipped),
        [("r01", bd.extract_metrics(hist["parsed"]))],
        current_errors=bd.error_configs(skipped))
    assert verdict2["ok"] is True


def test_bench_diff_flattens_attribution_workloads():
    """The graftscope_attribution row's nested workload metrics enter
    the comparison (flattened), but host-dependent rates stay
    report-only — never gated across machines."""
    bd = _bd()
    payload = {"configs": [{"name": "graftscope_attribution",
                            "workloads": [{
                                "workload": "solo-greedy",
                                "implied_bytes_per_second": 2e6,
                                "measured_decode_seconds_per_token":
                                    0.02}]}]}
    cur = bd.extract_metrics(payload)
    assert cur["graftscope_attribution.solo-greedy."
               "implied_bytes_per_second"] == 2e6
    assert bd.classify("implied_bytes_per_second") is None
    assert bd.classify("measured_decode_seconds_per_token") is None


def test_bench_diff_skips_unparsed_rounds(tmp_path):
    """Rounds whose payload is null contribute nothing —
    the honest no-data case, not a vacuous pass of bad data."""
    bd = _bd()
    (tmp_path / "hist_r01.json").write_text(json.dumps(
        {"n": 1, "parsed": None}))
    (tmp_path / "hist_r02.json").write_text(json.dumps(
        {"n": 2, "parsed": {"skipped": "chip not attached", "configs": []}}))
    history = bd.load_history([str(tmp_path / "hist_r01.json"),
                               str(tmp_path / "hist_r02.json")])
    assert history == []


def test_bench_diff_cli_gates_a_trajectory(tmp_path):
    """The CLI wiring (ISSUE 9 acceptance) on a trajectory built here: a
    full matrix inside its thresholds exits 0, the same matrix with a
    halved throughput row exits 1. (The repo commits no full matrix any
    more: the driver's ledger takes over from ROADMAP S1.)"""
    bd = _bd()

    def payload(rate):
        return {"metric": "greedy_decode_throughput_gpt2_124m",
                "value": rate, "unit": "tokens/sec",
                "configs": [{"name": "cfg3_gpt2_124m_bs8",
                             "tokens_per_sec": 8 * rate}]}

    for n, rate in ((1, 700.0), (2, 720.0)):
        (tmp_path / f"BENCH_r0{n}.json").write_text(json.dumps(
            {"n": n, "parsed": payload(rate)}))
    history = str(tmp_path / "BENCH_r*.json")
    for rate, want in ((710.0, 0), (350.0, 1)):
        (tmp_path / "current.json").write_text(json.dumps(payload(rate)))
        assert bd.main(["--current", str(tmp_path / "current.json"),
                        "--history", history]) == want
