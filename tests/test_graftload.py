"""graftload in-suite driver (ISSUE 11 tentpole).

Four layers of pinning:

1. **replay identity**: the open-loop schedule is a pure function of
   ``(seed, profile, k)`` — byte-identical serializations per seed,
   and at width 1 (serial mode) two runs against fresh apps produce
   byte-identical per-request outputs;
2. **open vs closed loop**: at saturation the closed-loop comparison
   generator under-reports p99 (it throttles itself exactly when the
   system queues) — the reason the harness is open-loop by default;
3. **the slo static pass** (tools/graftcheck/slo.py): rule fixtures
   (profile-without-slo, slo-without-source-metric, stale/malformed/
   vacuous declarations) each produce findings with file:line, and the
   repo itself passes non-vacuously;
4. **the smoke acceptance run**: >= 2 profiles through the pooled
   iterbatch serving app under GRAFTSAN=1 GRAFTSCHED=1 GRAFTFAULT=1 —
   every outcome typed, conservation mid-run, zero sanitizer/race/leak
   findings.

Satellites pinned here too: /debug/requests?profile= triage filter,
the deadline_misses_total SLO source emission, bench_diff ungated
skip rows + --no-skips + goodput/slo_attainment classification, and
costmodel.calibrate's measured-ratio plan-score shift.
"""

import json
import threading
import time

import jax
import pytest

from llm_sharding_demo_tpu import loadgen
from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.utils import graftfault
from tools.graftload import build_demo_app

REPO = __import__("os").path.dirname(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__)))


@pytest.fixture(scope="module")
def demo():
    """One shared tiny pooled-iterbatch serving app (module-scoped:
    the jitted programs are the expensive part and every test here
    drives the same geometry)."""
    return build_demo_app(max_seq=128, max_batch=4,
                          recorder_capacity=512)


# -- 1. seeded replay identity ------------------------------------------------


def test_schedule_replay_byte_identical():
    """Same (seed, profile) -> byte-identical schedule; different seed
    -> a different one. Holds for EVERY registered profile."""
    for name, prof in loadgen.PROFILES.items():
        a = loadgen.schedule_bytes(prof, seed=7, n=32)
        b = loadgen.schedule_bytes(prof, seed=7, n=32)
        assert a == b, f"{name}: same seed must replay identically"
        assert a != loadgen.schedule_bytes(prof, seed=8, n=32), \
            f"{name}: different seed must differ"
    # and arrival k is pure in (seed, profile, k): field-for-field
    # equal to the schedule's row k (the FaultPlan preview contract)
    prof = loadgen.profile("bursty_chat")
    rows = loadgen.schedule(prof, seed=3, n=10)
    for k in (0, 4, 9):
        f = loadgen.arrival_fields(prof, 3, k)
        f.pop("gap")
        got = rows[k].to_dict()
        for key, v in f.items():
            assert got[key] == v


def test_schedule_shapes_match_profiles():
    """Profile structure lands in the generated arrivals: shared
    prefixes come from the declared pool (seed-independent), cache
    busting mints unique prefixes, abandonment flags carry the short
    walk-away budget, bursty arrivals clump."""
    chat = loadgen.profile("bursty_chat")
    rows = loadgen.schedule(chat, seed=1, n=40)
    prefixes = {loadgen.shared_prefix(chat, i)
                for i in range(chat.prefix_pool)}
    assert all(any(a.prompt.startswith(p) for p in prefixes)
               for a in rows)
    # seed-independent prefixes: another seed hits the same store keys
    rows2 = loadgen.schedule(chat, seed=2, n=40)
    assert {a.prompt[:chat.shared_prefix_len] for a in rows2} <= prefixes
    # bursty: a meaningful share of gaps are the intra-burst beat
    gaps = [round(b.t - a.t, 4) for a, b in zip(rows, rows[1:])]
    assert sum(1 for g in gaps if g <= 0.003) >= len(gaps) // 4
    # open-loop offsets are nondecreasing
    assert all(b.t >= a.t for a, b in zip(rows, rows[1:]))

    bust = loadgen.schedule(loadgen.profile("cache_buster"), seed=1, n=20)
    heads = [a.prompt.split("-")[:3] for a in bust]
    assert len({tuple(h) for h in heads}) == len(bust)

    ab = loadgen.schedule(loadgen.profile("abandonment"), seed=1, n=60)
    walk = [a for a in ab if a.abandoned]
    assert walk and all(
        a.deadline_ms == loadgen.profile("abandonment").abandon_after_ms
        for a in walk)
    assert all(a.deadline_ms == 60_000 for a in ab if not a.abandoned)


def test_width1_serial_replay_byte_identical_outputs(demo):
    """At width 1 the whole load run is deterministic end to end: two
    fresh apps (same init key), same (seed, profile) -> byte-identical
    per-request generated texts and statuses."""
    texts = []
    for _ in range(2):
        client, recorder, _reg = build_demo_app(max_seq=128, max_batch=4,
                                                recorder_capacity=64)
        rep = loadgen.run_load(client, loadgen.profile("agentic"),
                               seed=11, n=5, mode="serial",
                               recorder=recorder)
        assert rep["completed"] == 5, rep["error_codes"]
        texts.append([(o.status, o.generated) for o in rep["outcomes"]])
    assert texts[0] == texts[1]


# -- 2. open loop vs closed loop at saturation --------------------------------


def test_closed_loop_underreports_p99_at_saturation(demo):
    """THE reason the harness is open-loop: drive the same 12 requests
    (a) closed-loop at width 1 (the generator waits for the system —
    arrival pressure evaporates exactly when the system slows) and
    (b) open-loop at 50x the declared rate (arrivals keep their
    schedule; the backlog lands in the measured tail). The open-loop
    p99 must exceed the closed-loop p99 by a real factor — a
    closed-loop bench at saturation reports a healthy tail for a
    collapsing system."""
    client, recorder, _reg = demo
    prof = loadgen.profile("agentic")
    loadgen.run_load(client, prof, seed=9, n=2, mode="serial",
                     recorder=recorder)              # warm the programs
    closed = loadgen.run_load(client, prof, seed=5, n=12,
                              mode="closed", width=1,
                              recorder=recorder)
    opened = loadgen.run_load(client, prof, seed=5, n=12,
                              rate_scale=50.0, mode="open",
                              recorder=recorder)
    assert closed["completed"] == opened["completed"] == 12
    assert opened["p99_e2e_ms"] > 1.5 * closed["p99_e2e_ms"], (
        "open-loop tail must carry the queueing the closed loop hides",
        opened["p99_e2e_ms"], closed["p99_e2e_ms"])


# -- 3. the slo static pass ---------------------------------------------------


def _slo_fixture(tmp_path, source: str, **kw):
    import textwrap

    from tools.graftcheck import slo
    p = tmp_path / "loadgen" / "profiles.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    kw.setdefault("catalog", {"ttft_seconds": "histogram",
                              "generate_request_seconds": "histogram"})
    kw.setdefault("emitted", {"ttft_seconds",
                              "generate_request_seconds"})
    return slo.run_slo(str(tmp_path), paths=[str(p)], **kw)


def test_fixture_profile_without_slo_and_stale(tmp_path):
    findings, summary = _slo_fixture(tmp_path, """\
        PROFILES = {"a": 1, "b": 2}
        SLO_SOURCE_METRICS = {"ttft": "ttft_seconds"}
        SLO_POLICY = {
            "a": {"ttft": (1.0, 99)},
            "ghost": {"ttft": (1.0, 99)},
        }
        """)
    by_scope = {f.scope: f for f in findings}
    assert set(by_scope) == {"b", "ghost"}
    assert "no SLO_POLICY entry" in by_scope["b"].message
    assert "stale" in by_scope["ghost"].message
    assert all(f.rule == "profile-without-slo" for f in findings)
    assert all(f.path == "loadgen/profiles.py" and f.line >= 1
               for f in findings)
    assert summary["slo_policies"]["loadgen/profiles.py"] == 1


def test_fixture_profiles_module_without_policy(tmp_path):
    findings, _ = _slo_fixture(tmp_path, """\
        PROFILES = {"a": 1}
        """)
    assert len(findings) == 1
    assert findings[0].rule == "profile-without-slo"
    assert "declares no SLO_POLICY" in findings[0].message


def test_fixture_slo_without_source_metric(tmp_path):
    findings, _ = _slo_fixture(tmp_path, """\
        PROFILES = {"a": 1}
        SLO_SOURCE_METRICS = {"ttft": "ttft_seconds",
                              "e2e": "nonexistent_seconds",
                              "tpot": "generate_request_seconds"}
        SLO_POLICY = {"a": {"ttft": (1.0, 99),
                            "e2e": (2.0, 99),
                            "tpot": (0.5, 95),
                            "deadline_miss": (0.1, 100),
                            "bogus_metric": (1.0, 50)}}
        """, emitted={"ttft_seconds"})
    msgs = {f.message for f in findings
            if f.rule == "slo-without-source-metric"}
    assert len(msgs) == 4
    assert any("unknown SLO metric 'bogus_metric'" in m for m in msgs)
    assert any("'nonexistent_seconds', which is not in METRIC_CATALOG"
               in m for m in msgs)                      # e2e
    assert any("no request-path call site" in m for m in msgs)  # tpot
    assert any("no SLO_SOURCE_METRICS mapping" in m
               for m in msgs)                           # deadline_miss


def test_fixture_malformed_targets_and_vacuous(tmp_path):
    findings, summary = _slo_fixture(tmp_path, """\
        PROFILES = {"a": 1, "dead": 2}
        SLO_SOURCE_METRICS = {"ttft": "ttft_seconds"}
        SLO_POLICY = {"a": {"ttft": (0.0, 99)},
                      "dead": {}}
        """)
    rules = sorted(f.rule for f in findings)
    assert rules == ["profile-without-slo"] * 2
    assert any("positive target" in f.message for f in findings)
    assert any("non-empty dict literal" in f.message for f in findings)
    # zero entries matched a live profile with a VALID policy shape?
    # "a" still matches (the metric row is malformed, the entry is
    # live) — vacuity is about the registry join, not target hygiene
    assert summary["slo_policies"]["loadgen/profiles.py"] == 1
    # a policy matching NO live profile is vacuous (strict failure)
    findings2, summary2 = _slo_fixture(tmp_path, """\
        PROFILES = {"x": 1}
        SLO_SOURCE_METRICS = {"ttft": "ttft_seconds"}
        SLO_POLICY = {"gone": {"ttft": (1.0, 99)}}
        """)
    assert summary2["vacuous"] == ["loadgen/profiles.py"]
    # zero-tolerance deadline_miss (0.0, 100) is the strictest VALID
    # rate cap, not a malformed target; a zero latency target stays
    # malformed
    findings3, _ = _slo_fixture(tmp_path, """\
        PROFILES = {"a": 1}
        SLO_SOURCE_METRICS = {"deadline_miss": "deadline_misses_total"}
        SLO_POLICY = {"a": {"deadline_miss": (0.0, 100)}}
        """, catalog={"deadline_misses_total": "counter"},
        emitted={"deadline_misses_total"})
    assert findings3 == [], [f.format() for f in findings3]


def test_repo_slo_pass_clean_and_nonvacuous():
    from tools.graftcheck import slo
    findings, summary = slo.run_slo(REPO)
    assert findings == [], [f.format() for f in findings]
    assert summary["slo_checks"] >= 10
    assert summary["vacuous"] == []
    # every registered profile carries a live policy
    assert summary["slo_policies"][
        "llm_sharding_demo_tpu/loadgen/profiles.py"] \
        == len(loadgen.PROFILES)
    # the pass's vocabulary and the runtime's stay one thing
    assert tuple(slo.SLO_METRICS) == tuple(loadgen.SLO_METRICS)
    # every source mapping really resolves (the pass re-proves this
    # statically; this is the direct runtime-side pin)
    from llm_sharding_demo_tpu.utils.metrics import METRIC_CATALOG
    for metric, source in loadgen.SLO_SOURCE_METRICS.items():
        assert source in METRIC_CATALOG, (metric, source)


# -- 4. serving integration: profile triage + deadline-miss source -----------


def test_profile_label_rides_trace_and_debug_filter(demo):
    client, _recorder, _reg = demo
    for prof, prompt in (("alpha", "hello"), ("beta", "world"),
                         ("alpha", "again")):
        r = client.post("/generate",
                        json={"prompt": prompt, "max_new_tokens": 4,
                              "mode": "greedy"},
                        headers={"X-Workload-Profile": prof})
        assert r.status_code == 200, r.text
    dbg = client.get("/debug/requests?profile=alpha").json()
    assert dbg["profile"] == "alpha"
    assert len(dbg["requests"]) >= 2
    assert all(t["labels"]["profile"] == "alpha"
               for t in dbg["requests"])
    beta = client.get("/debug/requests?profile=beta").json()["requests"]
    assert len(beta) == 1 and beta[0]["labels"]["profile"] == "beta"
    assert client.get("/debug/requests?profile=nope").json()[
        "requests"] == []
    # an unsafe label charset is ignored, not echoed into labels
    r = client.post("/generate",
                    json={"prompt": "x", "max_new_tokens": 2,
                          "mode": "greedy"},
                    headers={"X-Workload-Profile": 'bad"label\n'})
    assert r.status_code == 200
    newest = client.get("/debug/requests?n=1").json()["requests"][0]
    assert "profile" not in newest.get("labels", {})


def test_deadline_miss_emits_slo_source_counter(demo):
    """The declared deadline_miss SLO source series really increments
    on the request path (what the slo pass statically verifies an
    emission site for)."""
    client, _recorder, reg = demo
    before = reg.snapshot().get("deadline_misses_total", 0)
    plan = graftfault.FaultPlan(seed=3, rate=1.0,
                                sites={"iterbatch.decode_seg"},
                                kinds={"decode_slow"})
    with graftfault.use(plan):
        r = client.post("/generate",
                        json={"prompt": "Hello, world",
                              "max_new_tokens": 10, "mode": "greedy"},
                        headers={"X-Deadline-Ms": "60"})
    assert r.status_code == 503 and r.json()["error"] == "deadline_exceeded"
    assert reg.snapshot()["deadline_misses_total"] == before + 1


# -- 5. the smoke acceptance run ----------------------------------------------


def test_smoke_two_profiles_under_all_three_harnesses(monkeypatch):
    """Acceptance: >= 2 profiles through the pooled iterbatch app
    under GRAFTSAN=1 GRAFTSCHED=1 GRAFTFAULT=1 (pinned seed) — every
    outcome a byte-delivered 200 or a typed 429/503, block
    conservation mid-run, zero sanitizer/race/leak findings, and the
    goodput/SLO reduction well-formed for both profiles."""
    from llm_sharding_demo_tpu.runtime import kv_pool
    from llm_sharding_demo_tpu.utils import graftsched
    monkeypatch.setenv("GRAFTSAN", "1")
    monkeypatch.setenv("GRAFTSCHED", "1")
    monkeypatch.setenv("GRAFTSCHED_SEED", "4")
    monkeypatch.setenv("GRAFTFAULT", "1")
    monkeypatch.setenv("GRAFTFAULT_SEED", "12")
    monkeypatch.setenv("GRAFTFAULT_RATE", "0.1")
    monkeypatch.setenv("GRAFTFAULT_SITES",
                       "iterbatch.decode_seg,iterbatch.admission_load")
    graftsched.clear()
    graftfault.reset()
    try:
        client, recorder, _reg = build_demo_app(
            max_seq=128, max_batch=4, recorder_capacity=128)
        # warm the compiled programs before the timed open-loop runs
        loadgen.run_load(client, loadgen.profile("agentic"), seed=1,
                         n=2, mode="serial", recorder=recorder)

        stop = threading.Event()
        health = []

        def watch():
            while not stop.is_set():
                health.append(client.get("/healthz"))
                time.sleep(0.05)

        watcher = threading.Thread(target=watch, daemon=True)
        watcher.start()
        reports = []
        try:
            for name in ("agentic", "bursty_chat"):
                reports.append(loadgen.run_load(
                    client, loadgen.profile(name), seed=6, n=8,
                    rate_scale=2.0, mode="open", recorder=recorder))
        finally:
            stop.set()
            watcher.join(timeout=10)

        for rep in reports:
            assert rep["offered"] == 8
            assert rep["errors"] == 0, rep["error_codes"]
            for o in rep["outcomes"]:
                assert o.status in (200, 429, 503), (o.status, o.code)
            # the reduction is complete: every declared SLO metric
            # scored, goodput bounded
            for metric in loadgen.SLO_POLICY[rep["profile"]]:
                assert metric in rep["slo"]
            assert 0.0 <= rep["goodput_fraction"] <= 1.0
            assert rep["slo_attainment"] is not None
        # occupancy rode the graftscope series during the run
        occ = loadgen.occupancy_summary()
        assert any(label.startswith("queue_depth") for label in occ)

        # conservation held at every mid-run health poll
        assert health, "watcher never sampled /healthz"
        for h in health:
            assert h.status_code == 200
            st = h.json()["kv_pool_stats"]
            assert st["blocks_in_use"] + st["blocks_free"] \
                == st["blocks_total"]
    finally:
        graftfault.reset()
    # zero race findings, no leaked blocks, clean quiesce
    kv_pool.graftsan_sweep(timeout=10.0)
    assert graftsched.findings() == [], \
        [f.format() for f in graftsched.findings()]


# -- 6. bench_diff satellites -------------------------------------------------


def _bd():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO, "tools", "bench_diff.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_diff_classifies_goodput_and_slo_higher_better():
    bd = _bd()
    assert bd.classify("goodput_fraction") == "higher"
    assert bd.classify("goodput_rps") == "higher"
    assert bd.classify("slo_attainment") == "higher"
    assert bd.classify("throughput_tokens_per_sec") == "higher"
    assert bd.classify("p99_e2e_ms") == "lower"
    assert bd.classify("deadline_misses") is None    # report-only
    # a goodput drop past the gate is a regression
    hist = [("r1", {"slo_attainment.agentic.goodput_fraction": 1.0})]
    verdict = bd.compare(
        {"slo_attainment.agentic.goodput_fraction": 0.5}, hist)
    assert verdict["ok"] is False
    assert verdict["regressions"] == [
        "slo_attainment.agentic.goodput_fraction"]


def test_bench_diff_ungated_skip_rows_and_no_skips(tmp_path):
    bd = _bd()
    payload = {"configs": [
        {"name": "graftload_pareto",
         "skipped": "open-loop load rates need the bench chip"},
        {"name": "cfg_ok", "tokens_per_sec": 100.0},
    ]}
    skips = bd.skipped_configs(payload)
    assert skips == {"graftload_pareto":
                     "open-loop load rates need the bench chip"}
    verdict = bd.compare(bd.extract_metrics(payload), [],
                         current_skips=skips)
    assert verdict["ungated_rows"] == [
        {"config": "graftload_pareto",
         "reason": "open-loop load rates need the bench chip"}]
    # a skip row never fails the default run...
    cur = tmp_path / "cur.json"
    cur.write_text(json.dumps(payload))
    assert bd.main(["--current", str(cur),
                    "--history", str(tmp_path / "none*.json")]) == 0
    # ...and ALWAYS fails --no-skips (CI notices the run was not gated)
    assert bd.main(["--current", str(cur),
                    "--history", str(tmp_path / "none*.json"),
                    "--no-skips"]) == 1
    # with no skip rows, --no-skips is a no-op
    clean = tmp_path / "clean.json"
    clean.write_text(json.dumps(
        {"configs": [{"name": "cfg_ok", "tokens_per_sec": 100.0}]}))
    assert bd.main(["--current", str(clean),
                    "--history", str(tmp_path / "none*.json"),
                    "--no-skips"]) == 0


def test_bench_journal_rows_flatten_for_gating():
    """The graftload journal shapes flatten into gated metrics through
    the same 'workloads' path graftscope_attribution uses — the rows
    are gateable the day they first land on-chip."""
    bd = _bd()
    payload = {"configs": [{
        "name": "graftload_pareto",
        "workloads": [{"workload": "agentic_x1",
                       "throughput_tokens_per_sec": 42.0,
                       "p99_e2e_ms": 120.0,
                       "goodput_fraction": 0.9}],
    }, {
        "name": "slo_attainment",
        "workloads": [{"workload": "agentic", "slo_attainment": 1.0,
                       "goodput_rps": 3.5}],
    }]}
    m = bd.extract_metrics(payload)
    assert m["graftload_pareto.agentic_x1.goodput_fraction"] == 0.9
    assert m["slo_attainment.agentic.slo_attainment"] == 1.0
    for name in ("graftload_pareto.agentic_x1.goodput_fraction",
                 "graftload_pareto.agentic_x1.throughput_tokens_per_sec",
                 "slo_attainment.agentic.slo_attainment",
                 "slo_attainment.agentic.goodput_rps"):
        assert bd.classify(name.rpartition(".")[2]) == "higher", name
    assert bd.classify(
        "graftload_pareto.agentic_x1.p99_e2e_ms"
        .rpartition(".")[2]) == "lower"


# -- 7. costmodel calibration (ROADMAP item 5 measurement half) ---------------


def test_plan_cli_calibrate_journal_flag(tmp_path, capsys):
    """The measure->model loop has a production consumer: ``python -m
    tools.graftcheck plan --calibrate-journal`` re-prices the ICI term
    with the journal's measured row (and an unusable journal falls
    back to the a-priori weight with a warning, not a crash)."""
    from tools.graftcheck import cli
    from tools.graftcheck import costmodel as CM
    journal = tmp_path / "BENCH_cal.json"
    journal.write_text(json.dumps({"configs": [
        {"name": "ici_byte_weight_calibration",
         "measured_over_modeled": 2.0, "ici_byte_weight": 4.0}]}))
    rc = cli.main(["plan", "--model", "gpt2-tiny", "--mesh", "1",
                   "--json", "--calibrate-journal", str(journal)])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["ici_byte_weight"] == pytest.approx(8.0)
    # skipped-row journal: warns, scores with the a-priori weight
    skipped = tmp_path / "BENCH_skip.json"
    skipped.write_text(json.dumps({"configs": [
        {"name": "ici_byte_weight_calibration", "skipped": "off-chip"}]}))
    rc = cli.main(["plan", "--model", "gpt2-tiny", "--mesh", "1",
                   "--json", "--calibrate-journal", str(skipped)])
    assert rc == 0
    cap = capsys.readouterr()
    assert json.loads(cap.out)["ici_byte_weight"] == CM.ICI_BYTE_WEIGHT
    assert "no usable" in cap.err


def test_calibrate_reads_journal_and_shifts_plan_score():
    from tools.graftcheck import costmodel as CM
    journal = {"parsed": {"configs": [
        {"name": "ici_byte_weight_calibration",
         "measured_over_modeled": 1.5, "ici_byte_weight": 4.0},
    ]}}
    w = CM.calibrate(journal)
    assert w == pytest.approx(4.0 * 1.5)
    # wrapper-free payloads and the bare row work too
    assert CM.calibrate(journal["parsed"]) == w
    assert CM.calibrate(journal["parsed"]["configs"][0]) == w
    # skipped / unusable rows calibrate nothing
    assert CM.calibrate({"configs": [
        {"name": "ici_byte_weight_calibration",
         "skipped": "chip not attached"}]}) is None
    assert CM.calibrate({"configs": []}) is None

    # golden: a calibrated pp plan score shifts by EXACTLY the
    # measured ratio applied to the ICI term — (w' - w) x comm bytes
    cfg = gpt2.GPT2Config(vocab_size=97, n_positions=128, n_embd=32,
                          n_layer=2, n_head=4)
    cand = CM.Candidate(topology="pp", boundaries=(1,))
    traffic = (CM.TrafficRow(16, 16, 1),)
    base = CM.score_candidate(gpt2, cfg, cand, {"pp": 2}, 64, traffic,
                              None)
    cal = CM.score_candidate(gpt2, cfg, cand, {"pp": 2}, 64, traffic,
                             None, ici_byte_weight=w)
    assert base.ok and cal.ok
    assert base.comm_bytes_per_token > 0
    assert cal.cost_per_token - base.cost_per_token == pytest.approx(
        (w - CM.ICI_BYTE_WEIGHT) * base.comm_bytes_per_token)
    # and the ranking entry point threads the weight end to end
    payload = CM.plan(gpt2, cfg, {"pp": 2}, max_seq=64, traffic=traffic,
                      include_unsharded=False, ici_byte_weight=w)
    assert payload["ici_byte_weight"] == w
    row = next(r for r in payload["plan"]
               if r["ok"] and r["label"] == cand.label())
    assert row["cost_per_token"] == pytest.approx(cal.cost_per_token)


# -- 8. goodput accounting: sheds are not misses ------------------------------


def test_summarize_splits_sheds_misses_and_walkaways():
    """Pure-reduction pin: typed 429/503 sheds, deadline misses, and
    scheduled walk-aways land in DIFFERENT buckets, and goodput only
    charges broken promises."""
    prof = loadgen.profile("abandonment")
    O = loadgen.Outcome
    outcomes = [
        O(k=0, request_id="a", status=200, latency_s=1.0, new_tokens=8),
        O(k=1, request_id="b", status=200, latency_s=70.0,
          new_tokens=8),                         # completed PAST e2e SLO
        O(k=2, request_id="c", status=429, code="kv_pool_saturated"),
        O(k=3, request_id="d", status=503, code="circuit_open"),
        O(k=4, request_id="e", status=503, code="deadline_exceeded"),
        O(k=5, request_id="f", status=503, code="deadline_exceeded",
          abandoned=True),                       # scheduled walk-away
    ]
    rep = loadgen.summarize(prof, outcomes, wall_s=10.0)
    assert rep["completed"] == 2
    assert rep["shed_429"] == 1
    assert rep["shed_503"] == 1                  # circuit_open only
    assert rep["deadline_misses"] == 1           # the non-abandoned one
    assert rep["abandoned"] == 1
    assert rep["errors"] == 0
    # demanded = 6 - 1 walk-away = 5; only request "a" was in budget
    assert rep["goodput"] == 1
    assert rep["goodput_fraction"] == pytest.approx(1 / 5)
    # miss fraction = 1/5 > the declared 0.05 cap -> not attained
    assert rep["slo"]["deadline_miss"]["observed_miss_fraction"] \
        == pytest.approx(0.2)
    assert rep["slo"]["deadline_miss"]["attained"] is False
    assert rep["slo"]["e2e"]["attained"] is False   # p99 of [1, 70] > 60


def test_cli_preview_is_replay_identical(tmp_path):
    """python -m tools.graftload --preview prints the pure schedule —
    two invocations, identical bytes (the CLI-level replay pin)."""
    import subprocess
    import sys
    outs = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.graftload", "--profiles",
             "agentic", "--seed", "5", "--preview", "6", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        outs.append(proc.stdout)
    assert outs[0] == outs[1]
    rows = json.loads(outs[0])["agentic"]
    assert [r["k"] for r in rows] == list(range(6))
