"""graftwatch in-suite driver (ISSUE 13 tentpole).

Five layers of pinning:

1. **the pure decision core**: the windowed traffic-mix estimate is an
   order-independent reduction; ``decide_plan`` is a pure function with
   declared hysteresis (install past the margin, switch back on equal
   score + simpler); the switch-event journal of a ``PlanSwitcher``
   driven by a deterministic admission choreography is byte-identical
   across fresh instances — the FaultPlan/GRAFTSCHED replay contract;
2. **calibration**: ``fit_cost_weights`` recovers the per-primitive
   byte weights from journaled ``graftscope_attribution`` drift rows
   (hand-built goldens), and ``costmodel.calibrate`` distinguishes all
   three journal shapes — absent/skipped (None), valid (weight),
   present-but-unparsable (typed ``CalibrationError``);
3. **the acceptance run**: a seeded graftload mix flip (serial ->
   open burst -> serial) against the AUTO_PLAN_CONTINUOUS app under
   GRAFTSAN=1 GRAFTSCHED=1 — >= 1 live switch each way, per-request
   outputs byte-equal to the SAME schedule replayed against each
   static plan, replaying the whole mix again mints ZERO new compiled
   programs across further live switches (jit cache sizes asserted),
   observed program counts inside the pre-certified bounds, pool
   conservation + clean sanitizer sweep (no pool state leaks across a
   switch);
4. **the watch static pass** (tools/graftcheck/watch.py): rule
   fixtures (plan-signal-without-source, uncertified-plan-switch,
   stale/malformed/vacuous declarations) each produce findings with
   file:line, and the repo itself passes non-vacuously;
5. **satellites**: router prefill-hop fanout ordered by the watcher's
   per-replica queue-depth estimate (seeded two-prefill-replica pin),
   ``hop_breaker_open`` transition samples surfaced in
   ``/debug/profile``'s window-independent ``series_totals``, and the
   plan CLI's typed refusal of a malformed calibration journal.
"""

import dataclasses
import json
import os
import textwrap

import pytest

from llm_sharding_demo_tpu import loadgen
from llm_sharding_demo_tpu.utils import graftfault, graftscope, graftwatch
from llm_sharding_demo_tpu.utils.metrics import (METRIC_CATALOG,
                                                 MetricsRegistry)
from tools.graftcheck import costmodel as CM
from tools.graftcheck import watch
from tools.graftload import build_demo_app

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _demo_config(max_seq=64):
    from llm_sharding_demo_tpu.fleet.harness import demo_model
    cfg, _params = demo_model(max_seq)
    return cfg


# -- 1. the pure decision core ------------------------------------------------


def test_watcher_estimate_is_order_independent_and_windowed():
    obs = [(8, 4, 0), (24, 8, 2), (12, 6, 1), (16, 4, 0), (10, 8, 3)]
    estimates = []
    for perm in (obs, obs[::-1], obs[2:] + obs[:2]):
        w = graftwatch.TelemetryWatcher(window=16,
                                        registry=MetricsRegistry())
        for p, n, pend in perm:
            w.observe(p, n, pend)
        estimates.append(w.estimate())
    assert estimates[0] == estimates[1] == estimates[2]
    assert estimates[0].requests == 5
    assert estimates[0].concurrency == 1 + 3
    # the window is a ring: old observations age out of the estimate
    w = graftwatch.TelemetryWatcher(window=4, registry=MetricsRegistry())
    for _ in range(10):
        w.observe(100, 10, 9)
    for _ in range(4):
        w.observe(8, 4, 0)
    est = w.estimate()
    assert est.requests == 4 and est.concurrency == 1
    assert est.prompt_p50 == 8
    assert w.admitted() == 14
    # the empty watcher estimates the single-stream default
    assert graftwatch.TelemetryWatcher(
        registry=MetricsRegistry()).estimate() == \
        graftwatch.TrafficEstimate()


def _synthetic_costs():
    mk = lambda label, mode, mb, param, kv: graftwatch.PlanCost(
        label=label, batch_mode=mode, max_batch=mb, param_bytes=param,
        kv_bytes_per_row=kv, paged_overhead=0.0)
    return {"solo": mk("solo", "admission", 1, 1000, 100),
            "batched": mk("batched", "iter", 4, 1000, 100)}


def test_decide_plan_pure_with_declared_hysteresis():
    costs = _synthetic_costs()
    w = graftwatch.CostWeights(ici_byte_weight=4.0)
    one = graftwatch.TrafficEstimate(requests=8, concurrency=1)
    burst = graftwatch.TrafficEstimate(requests=8, concurrency=4)
    # single stream: scores equal, the simpler plan is the decision
    dec, scores = graftwatch.decide_plan(one, costs, w, "solo")
    assert dec == "solo" and scores["solo"] == scores["batched"]
    # concurrency amortizes the weight stream: batched wins PAST the
    # margin (250/1100 << 0.9) and the switch installs
    dec, scores = graftwatch.decide_plan(burst, costs, w, "solo")
    assert dec == "batched"
    assert scores["batched"] < 0.9 * scores["solo"]
    # hysteresis: a sub-margin win does NOT flap the plan
    tight = {"solo": dataclasses.replace(costs["solo"], param_bytes=100,
                                         kv_bytes_per_row=1000),
             "batched": dataclasses.replace(costs["batched"],
                                            param_bytes=100,
                                            kv_bytes_per_row=1000)}
    dec, scores = graftwatch.decide_plan(
        graftwatch.TrafficEstimate(requests=8, concurrency=2),
        tight, w, "solo")
    assert scores["batched"] < scores["solo"]          # it IS better...
    assert scores["batched"] > 0.9 * scores["solo"]    # ...but in-margin
    assert dec == "solo"
    # the traffic-drained switch-back: equal score, strictly simpler
    dec, _ = graftwatch.decide_plan(one, costs, w, "batched")
    assert dec == "solo"
    # pure: same inputs, same outputs, every time
    assert graftwatch.decide_plan(burst, costs, w, "solo") \
        == graftwatch.decide_plan(burst, costs, w, "solo")


def _build_switcher(wave=8, window=16):
    reg = MetricsRegistry()
    watcher = graftwatch.TelemetryWatcher(window=window, registry=reg)
    costs = _synthetic_costs()
    certified = {lb: {"programs": {"_prefill": 1}, "program_total": 1,
                      "programs_exact": lb == "solo"}
                 for lb in costs}
    plans = {lb: object() for lb in costs}
    return graftwatch.PlanSwitcher(
        plans, costs, certified, watcher,
        weights=graftwatch.CostWeights(ici_byte_weight=4.0),
        wave=wave, registry=reg)


def test_switch_events_replay_byte_identical():
    """The journaled wave evaluations are a pure function of the
    admission choreography: two fresh switchers driven by the same
    deterministic sequence produce byte-identical event journals
    (minus the wall-clock context field) — the FaultPlan/GRAFTSCHED
    replay-identity contract the acceptance criterion names."""
    sched = loadgen.schedule(loadgen.profile("agentic"), seed=7, n=16)
    journals = []
    for _ in range(2):
        sw = _build_switcher()
        assert sw.health_view()["active"] == "solo"   # simplest start
        # phase A: 16 serial admissions (release immediately)
        for a in sched:
            sw.admit(len(a.prompt.encode("utf-8")), a.max_new)
            sw.release()
        # phase B: a burst — 8 admissions held in flight, then drained
        for a in sched[:8]:
            sw.admit(len(a.prompt.encode("utf-8")), a.max_new)
        for _ in range(8):
            sw.release()
        # phase C: traffic drains back to single-stream
        for a in sched:
            sw.admit(len(a.prompt.encode("utf-8")), a.max_new)
            sw.release()
        journals.append(json.dumps(sw.events(strip_time=True),
                                   sort_keys=True))
        flips = [(e["from"], e["to"]) for e in sw.events()
                 if e["switched"]]
        assert ("solo", "batched") in flips
        assert ("batched", "solo") in flips
    assert journals[0] == journals[1]


def test_plan_switcher_typed_uncertified_errors():
    reg = MetricsRegistry()
    watcher = graftwatch.TelemetryWatcher(registry=reg)
    costs = _synthetic_costs()
    certified = {lb: {"programs": {}} for lb in costs}
    plans = {lb: object() for lb in costs}
    # a plan without a certified entry is a typed construction error
    with pytest.raises(graftwatch.UncertifiedPlanError,
                       match="priced AND certified"):
        graftwatch.PlanSwitcher(plans, costs, {"solo": {}}, watcher,
                                registry=reg)
    # a label outside the declared PLAN_SET can never be switchable
    rogue = {"solo": object(), "rogue": object()}
    rcosts = {"solo": costs["solo"],
              "rogue": dataclasses.replace(costs["batched"],
                                           label="rogue")}
    with pytest.raises(graftwatch.UncertifiedPlanError,
                       match="PLAN_SET"):
        graftwatch.PlanSwitcher(rogue, rcosts,
                                {lb: {} for lb in rogue}, watcher,
                                registry=reg)
    # an uncertified initial plan is refused, not silently installed
    with pytest.raises(graftwatch.UncertifiedPlanError, match="initial"):
        graftwatch.PlanSwitcher(plans, costs, certified, watcher,
                                initial="ghost", registry=reg)
    # and the declared provenance map rejects unknown signals
    with pytest.raises(KeyError, match="unknown plan signal"):
        graftwatch.signal_series("ghost_signal")


def test_certify_plan_set_proves_program_costs():
    """Every switchable plan's compiled-program cost comes from THE
    recompile certifier: the solo row is exact, the iter row is the
    documented static bound, and both carry their candidate."""
    cfg = _demo_config()
    cert = graftwatch.certify_plan_set(cfg, max_seq=64, max_batch=3,
                                       pool_blocks=12, block_size=16,
                                       traffic="16/8")
    assert set(cert) == set(graftwatch.PLAN_SET)
    assert cert["solo"]["programs_exact"] is True
    assert cert["batched"]["programs_exact"] is False
    for row in cert.values():
        assert row["program_total"] == sum(row["programs"].values())
        assert row["program_total"] > 0
    # the iter bound dominates the solo one (widths 1..max_batch)
    assert cert["batched"]["program_total"] \
        >= cert["solo"]["program_total"]


# -- 2. calibration -----------------------------------------------------------


def _attribution_journal(workloads):
    return {"configs": [{"name": "graftscope_attribution",
                         "workloads": workloads}]}


def test_fit_cost_weights_golden_fit():
    # two consistent HBM-only rows: the 1-D projection is exact
    j = _attribution_journal([
        {"workload": "solo", "measured_decode_seconds_per_token": 2e-3,
         "modeled_cost_bytes_per_token": 1e6,
         "modeled_comm_bytes_per_token": 0,
         "entry_points": {"engine._decode_seg": {"seconds_total": 1.5},
                          "kv_pool._gather": {"seconds_total": 0.5}}},
        {"workload": "batch2", "measured_decode_seconds_per_token": 4e-3,
         "modeled_cost_bytes_per_token": 2e6,
         "modeled_comm_bytes_per_token": 0},
    ])
    w = graftwatch.fit_cost_weights(j)
    assert w.hbm_seconds_per_byte == pytest.approx(2e-9)
    assert w.rows_used == 2
    assert w.source == "graftscope_attribution"
    assert w.ici_byte_weight is None      # nothing moved ICI bytes
    assert dict(w.per_scope_seconds) == {"engine._decode_seg": 1.5,
                                         "kv_pool._gather": 0.5}
    # a row that moves ICI bytes identifies the RELATIVE weight: the
    # modeled total priced comm at the a-priori 4.0, the measured
    # seconds were generated at w_h=2e-9, w_ici_s=8e-9 -> ratio 4.0
    j2 = _attribution_journal([
        {"workload": "solo", "measured_decode_seconds_per_token": 2e-3,
         "modeled_cost_bytes_per_token": 1e6,
         "modeled_comm_bytes_per_token": 0},
        {"workload": "pp2", "measured_decode_seconds_per_token": 4e-3,
         "modeled_cost_bytes_per_token": 1.6e6 + 4.0 * 1e5,
         "modeled_comm_bytes_per_token": 1e5},
    ])
    w2 = graftwatch.fit_cost_weights(j2)
    assert w2.hbm_seconds_per_byte == pytest.approx(2e-9)
    assert w2.ici_byte_weight == pytest.approx(4.0)


def test_fit_cost_weights_skipped_and_fallback_shapes():
    # no journal / no row: the a-priori weights, honestly labeled
    assert graftwatch.fit_cost_weights({}).source == "a-priori"
    assert graftwatch.fit_cost_weights(
        {"configs": []}).rows_used == 0
    # a skipped row calibrates nothing (environment fact, not an error)
    skipped = {"configs": [{"name": "graftscope_attribution",
                            "skipped": "chip not attached"}]}
    assert graftwatch.fit_cost_weights(skipped).source == "a-priori"
    # honestly-unmeasured workloads are skipped, not fatal
    j = _attribution_journal([
        {"workload": "w", "measured_decode_seconds_per_token": None,
         "modeled_cost_bytes_per_token": 1e6}])
    assert graftwatch.fit_cost_weights(j).rows_used == 0
    # the ici calibration row still resolves through the same journal
    both = {"configs": [
        {"name": "ici_byte_weight_calibration",
         "measured_over_modeled": 2.0, "ici_byte_weight": 4.0}]}
    w = graftwatch.fit_cost_weights(both)
    assert w.ici_byte_weight == pytest.approx(8.0)
    assert w.source == "ici-row-only"


def test_fit_cost_weights_typed_errors_on_unparsable_rows():
    for bad in (
        # workloads is not a list
        {"configs": [{"name": "graftscope_attribution",
                      "workloads": "oops"}]},
        # a workload row is not an object
        _attribution_journal(["oops"]),
        # measured present but non-positive
        _attribution_journal([
            {"workload": "w", "measured_decode_seconds_per_token": -1.0,
             "modeled_cost_bytes_per_token": 1e6}]),
        # measured present, modeled missing
        _attribution_journal([
            {"workload": "w",
             "measured_decode_seconds_per_token": 1e-3}]),
        # bool masquerading as a number
        _attribution_journal([
            {"workload": "w", "measured_decode_seconds_per_token": True,
             "modeled_cost_bytes_per_token": 1e6}]),
        # inconsistent byte split: comm-priced term exceeds the total
        _attribution_journal([
            {"workload": "w", "measured_decode_seconds_per_token": 1e-3,
             "modeled_cost_bytes_per_token": 1e3,
             "modeled_comm_bytes_per_token": 1e6}]),
    ):
        with pytest.raises(CM.CalibrationError):
            graftwatch.fit_cost_weights(bad)


def test_calibrate_three_journal_shapes():
    """The satellite contract: None for absent AND genuinely skipped
    rows, the measured weight for valid rows, a typed CalibrationError
    for present-but-unparsable rows — never a silent a-priori
    fallback on a malformed measurement."""
    # shape 1: absent / skipped -> None
    assert CM.calibrate({}) is None
    assert CM.calibrate({"configs": []}) is None
    assert CM.calibrate({"configs": [
        {"name": "ici_byte_weight_calibration",
         "skipped": "off-chip"}]}) is None
    assert CM.calibrate({"configs": [
        {"name": "ici_byte_weight_calibration",
         "error": "IndexError: ..."}]}) is None
    # shape 2: valid -> base x ratio (older rows omit the base weight)
    row = {"name": "ici_byte_weight_calibration",
           "measured_over_modeled": 2.0, "ici_byte_weight": 3.0}
    assert CM.calibrate({"configs": [row]}) == pytest.approx(6.0)
    assert CM.calibrate({"parsed": {"configs": [row]}}) \
        == pytest.approx(6.0)
    assert CM.calibrate(row) == pytest.approx(6.0)
    legacy = {"name": "ici_byte_weight_calibration",
              "measured_over_modeled": 2.0}
    assert CM.calibrate(legacy) == pytest.approx(2.0 * CM.ICI_BYTE_WEIGHT)
    # shape 3: present but unparsable -> typed diagnostic
    for field, value in (("measured_over_modeled", "2.0"),
                         ("measured_over_modeled", 0),
                         ("measured_over_modeled", True),
                         ("ici_byte_weight", -1.0),
                         ("ici_byte_weight", "4")):
        bad = {"name": "ici_byte_weight_calibration",
               "measured_over_modeled": 2.0, "ici_byte_weight": 4.0}
        bad[field] = value
        with pytest.raises(CM.CalibrationError, match=field):
            CM.calibrate({"configs": [bad]})


def test_plan_cli_refuses_malformed_calibration_journal(tmp_path,
                                                        capsys):
    """``plan --calibrate-journal`` with a present-but-unparsable row
    exits 2 with the typed diagnostic — distinct from the skipped-row
    warning path (pinned in tests/test_graftload.py)."""
    from tools.graftcheck import cli
    journal = tmp_path / "BENCH_bad.json"
    journal.write_text(json.dumps({"configs": [
        {"name": "ici_byte_weight_calibration",
         "measured_over_modeled": "not-a-number"}]}))
    rc = cli.main(["plan", "--model", "gpt2-tiny", "--mesh", "1",
                   "--json", "--calibrate-journal", str(journal)])
    assert rc == 2
    assert "calibrate:" in capsys.readouterr().err


# -- 3. the acceptance run ----------------------------------------------------


_ENTRY_POINTS = ("_prefill", "_prefill_chunked", "_decode_seg",
                 "_gather", "_scatter", "_scatter_row", "_copy")


def _observed_caches(switcher):
    solo = switcher.plans["solo"]
    eng, pool = solo.engine, solo.pool
    return {
        "_prefill": eng._prefill._cache_size(),
        "_prefill_chunked": eng._prefill_chunked._cache_size(),
        "_decode_seg": eng._decode_seg._cache_size(),
        "_gather": pool._gather._cache_size(),
        "_scatter": pool._scatter._cache_size(),
        "_scatter_row": pool._scatter_row._cache_size(),
        "_copy": pool._copy._cache_size(),
    }


def test_continuous_plan_switch_exactness(monkeypatch):
    """THE acceptance run: a seeded graftload mix flip (serial ->
    60x open burst -> serial, agentic profile) against the
    AUTO_PLAN_CONTINUOUS app under GRAFTSAN=1 GRAFTSCHED=1.

    Pinned: >= 1 live switch each direction; every request a
    byte-delivered 200, byte-equal across phases AND to the same
    schedule replayed against each STATIC plan (solo paged admission /
    pooled iter); replaying the whole mix again switches again while
    minting ZERO new compiled programs (jit cache sizes asserted —
    "a plan switch causes zero recompiles beyond the certified set");
    observed program counts stay inside the pre-certified bounds for
    the statically enumerable entry points; pool conservation at
    /healthz, clean graftsan sweep, zero graftsched findings (no pool
    state leaks across a switch)."""
    from llm_sharding_demo_tpu.runtime import kv_pool
    from llm_sharding_demo_tpu.utils import graftsched
    monkeypatch.setenv("GRAFTSAN", "1")
    monkeypatch.setenv("GRAFTSCHED", "1")
    monkeypatch.setenv("GRAFTSCHED_SEED", "5")
    graftsched.clear()

    SEED, N = 7, 10
    prof = loadgen.profile("agentic")
    sched = loadgen.schedule(prof, SEED, N)
    # certify the plan set against the schedule's OWN traffic classes
    # (byte-level prompt lengths — the demo app's ByteTokenizer), so
    # the certified bounds cover the whole run
    classes = sorted({(len(a.prompt.encode("utf-8")), a.max_new)
                      for a in sched})
    traffic = ",".join(f"{p}/{n}" for p, n in classes)

    client, recorder, reg = build_demo_app(
        max_seq=64, max_batch=3, recorder_capacity=256,
        continuous=True, auto_plan_traffic=traffic)
    sw = client.app.plan_switcher
    assert sw is not None
    assert set(sw.certified) == set(graftwatch.PLAN_SET)
    assert sw.health_view()["active"] == "solo"

    def run(mode, rate=1.0):
        rep = loadgen.run_load(client, prof, seed=SEED, n=N, mode=mode,
                               rate_scale=rate, recorder=recorder)
        assert rep["completed"] == N, rep["error_codes"]
        return [(o.status, o.generated) for o in rep["outcomes"]]

    p1 = run("serial")                  # single-stream: stays solo
    p2 = run("open", rate=60.0)         # the burst: flips to batched
    flips = [(e["from"], e["to"]) for e in sw.events() if e["switched"]]
    assert flips[:1] == [("solo", "batched")], sw.events()
    p3 = run("serial")                  # drains back toward solo
    caches = _observed_caches(sw)

    # the full mix again: MORE live switches, ZERO new programs
    p4 = run("serial")
    p5 = run("open", rate=60.0)
    p6 = run("serial")
    flips = [(e["from"], e["to"]) for e in sw.events() if e["switched"]]
    assert flips.count(("solo", "batched")) >= 2
    assert ("batched", "solo") in flips
    assert _observed_caches(sw) == caches, (
        "a live plan switch minted compiled programs beyond the "
        "certified set", caches, _observed_caches(sw))
    # switch accounting reached the registry (labeled, bounded set)
    switch_total = sum(v for k, v in reg.snapshot().items()
                       if k.startswith("plan_switches_total"))
    assert switch_total == sw.health_view()["switches"] == len(flips)
    assert switch_total >= 3

    # greedy decode is byte-equal across every phase and plan
    assert p1 == p2 == p3 == p4 == p5 == p6

    # ... and byte-equal to the SAME schedule against each STATIC plan
    for static_batch in (1, 3):         # solo paged / pooled iter
        c2, r2, _ = build_demo_app(max_seq=64, max_batch=static_batch,
                                   recorder_capacity=64)
        assert c2.app.plan_switcher is None
        rep = loadgen.run_load(c2, prof, seed=SEED, n=N, mode="serial",
                               recorder=r2)
        assert [(o.status, o.generated) for o in rep["outcomes"]] \
            == p1, f"static max_batch={static_batch} diverged"

    # observed program counts stay inside the certified bounds for the
    # statically enumerable entry points (the on-demand admission/CoW
    # movers are documented as not statically enumerable)
    for entry in ("_prefill", "_prefill_chunked", "_decode_seg",
                  "_gather", "_scatter"):
        bound = sum(sw.certified[p]["programs"].get(entry, 0)
                    for p in sw.certified)
        assert caches[entry] <= bound, (entry, caches[entry], bound)

    # plan switches ride the shared occupancy timeline
    occ = loadgen.occupancy_summary()
    assert any(label.startswith("auto_plan_active") for label in occ)

    # /healthz reports the LIVE plan + conservation; no state leaked
    h = client.get("/healthz").json()
    assert h["auto_plan"]["mode"] == "continuous"
    assert h["auto_plan"]["active"] == sw.health_view()["active"]
    assert h["auto_plan"]["switches"] == switch_total
    st = h["kv_pool_stats"]
    assert st["blocks_in_use"] + st["blocks_free"] == st["blocks_total"]
    kv_pool.graftsan_sweep(timeout=10.0)
    assert graftsched.findings() == [], \
        [f.format() for f in graftsched.findings()]


def test_expired_deadline_releases_inflight(monkeypatch):
    """Regression pin (review): an exception between the switcher's
    admission and the generate call — the deadline pre-check is the
    routine one under the abandonment profile — must still release the
    watcher's in-flight estimate. A leaked counter inflates
    TrafficEstimate.concurrency permanently and biases every later
    plan decision toward the batched plan."""
    client, _rec, _reg = build_demo_app(max_seq=64, max_batch=3,
                                        continuous=True,
                                        auto_plan_traffic="16/8")
    sw = client.app.plan_switcher
    base_admitted = sw.watcher.admitted()
    monkeypatch.setattr(graftfault.Deadline, "expired",
                        lambda self: True)
    for i in range(3):
        r = client.post("/generate",
                        json={"prompt": f"doomed request {i}",
                              "max_new_tokens": 4, "mode": "greedy"},
                        headers={"X-Deadline-Ms": "5"})
        assert r.status_code == 503
        assert r.json()["error"] == "deadline_exceeded"
    # the doomed requests WERE admitted (the pre-check fires after
    # admission — this pin is non-vacuous)...
    assert sw.watcher.admitted() == base_admitted + 3
    # ...and every admission was released on the failure path
    with sw._lock:
        assert sw._inflight == 0
    monkeypatch.undo()
    # the estimate is not poisoned: a healthy request still admits,
    # serves, and observes pending == 0
    r = client.post("/generate", json={"prompt": "healthy again",
                                       "max_new_tokens": 4,
                                       "mode": "greedy"})
    assert r.status_code == 200
    est = sw.watcher.estimate()
    assert est.concurrency == 1, est


def test_debug_plan_payload_shape():
    """GET /debug/plan serves the whole decision state; off continuous
    mode the payload still answers with mode off (monitoring can tell
    WHY there is no switch history instead of reading a 404)."""
    client, _rec, _reg = build_demo_app(max_seq=64, max_batch=3,
                                        continuous=True,
                                        auto_plan_traffic="16/8")
    r = client.post("/generate", json={"prompt": "debug plan shape",
                                       "max_new_tokens": 4,
                                       "mode": "greedy"})
    assert r.status_code == 200
    p = client.get("/debug/plan?n=4").json()
    assert p["mode"] == "continuous"
    assert p["active"] in graftwatch.PLAN_SET
    assert set(p["signals"]) == set(graftwatch.SIGNALS)
    assert set(p["signal_values"]) == set(graftwatch.SIGNALS)
    for sig, val in p["signal_values"].items():
        assert val["series"] == graftwatch.PLAN_SIGNALS[sig]
        assert val["kind"] in ("gauge", "counter")
    assert p["calibrated_weights"]["ici_byte_weight"] \
        == CM.ICI_BYTE_WEIGHT                  # a-priori, pre-resolved
    labels = {row["label"] for row in p["plans"]}
    assert labels == set(graftwatch.PLAN_SET)
    for row in p["plans"]:
        assert row["certified"]["program_total"] > 0
        assert row["score_bytes_per_token"] > 0
        assert row["active"] == (row["label"] == p["active"])
    assert p["admitted"] == 1 and isinstance(p["events"], list)
    assert p["serving"]["auto_plan"]["mode"] == "continuous"
    assert client.get("/debug/plan?n=bogus").status_code == 422
    # off continuous mode: a typed "off" payload, not a 404
    c2, _r2, _g2 = build_demo_app(max_seq=64, max_batch=1)
    off = c2.get("/debug/plan").json()
    assert off["mode"] == "off" and off["auto_plan"] is None


def test_config_guards_continuous_composition():
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    base = dict(model_id="m", shard_role="coordinator", max_seq=64,
                boundaries=(1,), max_batch=3, batch_mode="iter",
                kv_pool_blocks=12, kv_block_size=16)
    ServingConfig(**base, auto_plan_continuous=True)   # valid
    with pytest.raises(ValueError, match="AUTO_PLAN_CONTINUOUS"):
        ServingConfig(**{**base, "max_batch": 1,
                         "batch_mode": "admission"},
                      auto_plan_continuous=True)
    with pytest.raises(ValueError, match="compile spaces"):
        ServingConfig(**base, auto_plan_continuous=True, spec_decode=3)
    with pytest.raises(ValueError, match="AUTO_PLAN_JOURNAL"):
        ServingConfig(**base, auto_plan_journal="BENCH.json")


# -- 4. the watch static pass -------------------------------------------------


def _watch_fixture(tmp_path, source: str, **kw):
    p = tmp_path / "utils" / "graftwatch.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    kw.setdefault("catalog", {"queue_depth": "gauge",
                              "emitted_series": "counter",
                              "silent_series": "counter"})
    kw.setdefault("emitted", {"queue_depth", "emitted_series"})
    return watch.run_watch(str(tmp_path), paths=[str(p)], **kw)


def test_fixture_signal_rules(tmp_path):
    findings, summary = _watch_fixture(tmp_path, """\
        SIGNALS = ("queue_depth", "pool", "silent", "unmapped")
        PLAN_SIGNALS = {
            "queue_depth": "queue_depth",
            "pool": "nonexistent_series",
            "silent": "silent_series",
            "stale_one": "queue_depth",
            "unmapped": 42,
        }
        """)
    assert all(f.rule == "plan-signal-without-source" for f in findings)
    by_scope = {f.scope: f.message for f in findings}
    assert "not in METRIC_CATALOG" in by_scope["pool"]
    assert "no production call site emits" in by_scope["silent"]
    assert "stale declaration" in by_scope["stale_one"]
    assert "string literal" in by_scope["unmapped"]
    assert set(by_scope) == {"pool", "silent", "stale_one", "unmapped"}
    assert all(f.path == "utils/graftwatch.py" and f.line >= 1
               for f in findings)
    # one signal fully resolved -> the pass is not vacuous
    assert summary["watch_signals"]["utils/graftwatch.py"] == 1
    assert summary["vacuous"] == []


def test_fixture_missing_mapping_and_malformed_declarations(tmp_path):
    findings, summary = _watch_fixture(tmp_path, """\
        SIGNALS = ("queue_depth", "ghost")
        PLAN_SIGNALS = {"queue_depth": "queue_depth"}
        """)
    assert len(findings) == 1
    assert findings[0].scope == "ghost"
    assert "no PLAN_SIGNALS mapping" in findings[0].message
    # a non-literal PLAN_SIGNALS is itself the finding, and the module
    # counts as vacuous (nothing resolved)
    findings2, summary2 = _watch_fixture(tmp_path, """\
        SIGNALS = ("queue_depth",)
        PLAN_SIGNALS = dict(queue_depth="queue_depth")
        """)
    assert any("dict literal" in f.message for f in findings2)
    assert summary2["vacuous"] == ["utils/graftwatch.py"]
    findings3, _ = _watch_fixture(tmp_path, """\
        SIGNALS = (1, 2)
        PLAN_SIGNALS = {"queue_depth": "queue_depth"}
        """)
    assert any("tuple/list literal of string" in f.message
               for f in findings3)


def test_fixture_uncertified_plan_switch(tmp_path):
    findings, summary = _watch_fixture(tmp_path, """\
        PLAN_SET = ("a", "b", "orphan")
        PLAN_BUILDERS = ("build", "missing_fn")

        def build(engine):
            plans = {"a": 1, "b": 2, "rogue": 3}
            payload = {"programs": 4, "program_total": 5}
            return plans, payload

        def run(sw):
            sw.switch_to("zz")
            sw.switch_to("a")
        """)
    assert all(f.rule == "uncertified-plan-switch" for f in findings)
    msgs = [f.message for f in findings]
    assert any("no such function exists" in m for m in msgs)   # missing_fn
    assert any("constructs plan label 'rogue'" in m for m in msgs)
    assert any("'orphan' but no PLAN_BUILDERS function constructs"
               in m for m in msgs)
    assert any("switch target 'zz' is outside" in m for m in msgs)
    # the in-set literal and the payload dict produce NO findings
    assert not any(f.scope == "a" for f in findings)
    assert not any("'programs'" in m for m in msgs)
    assert len(findings) == 4, msgs


def test_fixture_plan_set_shape_and_vacuity(tmp_path):
    findings, summary = _watch_fixture(tmp_path, """\
        PLAN_SET = ()
        """)
    assert any("non-empty tuple/list literal" in f.message
               for f in findings)
    assert summary["vacuous"] == ["utils/graftwatch.py"]
    findings2, _ = _watch_fixture(tmp_path, """\
        PLAN_SET = ("a",)

        def build():
            return {"a": 1}
        """)
    assert any("must declare PLAN_BUILDERS" in f.message
               for f in findings2)


def test_repo_watch_pass_clean_and_nonvacuous():
    findings, summary = watch.run_watch(REPO)
    assert findings == [], [f.format() for f in findings]
    assert summary["watch_checks"] >= 10
    assert summary["vacuous"] == []
    # every declared signal resolves to a live emitted series
    assert summary["watch_signals"][
        "llm_sharding_demo_tpu/utils/graftwatch.py"] \
        == len(graftwatch.SIGNALS)
    # the pass's vocabulary and the runtime's stay one thing
    assert tuple(watch.WATCH_SIGNALS) == tuple(graftwatch.SIGNALS)
    # the runtime-side mirror of what the pass proves statically
    for signal, series in graftwatch.PLAN_SIGNALS.items():
        assert series in METRIC_CATALOG, (signal, series)
    assert set(graftwatch.PLAN_SIGNALS) == set(graftwatch.SIGNALS)


# -- 5. satellites ------------------------------------------------------------


def test_order_by_queue_depth_is_stable_and_pure():
    names = ["p2", "p0", "p1"]
    # no load: the caller's deterministic (ring-walk) order survives
    assert graftwatch.order_by_queue_depth(names, {}) == names
    # a backed-up replica demotes past its peers; ties keep ring order
    assert graftwatch.order_by_queue_depth(names, {"p2": 3}) \
        == ["p0", "p1", "p2"]
    assert graftwatch.order_by_queue_depth(names, {"p2": 3, "p0": 3}) \
        == ["p1", "p2", "p0"]
    # unknown names count as idle, and the function is pure
    for _ in range(3):
        assert graftwatch.order_by_queue_depth(
            names, {"p0": 1, "ghost": 9}) == ["p2", "p1", "p0"]


def test_prefill_fanout_by_queue_depth_two_replicas():
    """Satellite (graftfleet follow-on b): prefill hops schedule by
    the router's per-replica queue-depth estimate instead of raw ring
    order — a seeded two-prefill-replica fleet routes every warm
    around the backed-up replica, and drains back to the ring's
    deterministic spread when the depth clears."""
    import random

    from llm_sharding_demo_tpu.fleet import build_fleet
    f = build_fleet(n_decode=1, n_prefill=2)
    router = f.app.router
    # seeded, replay-identical probe prompts with DISTINCT content
    # keys (first chunks differ), so the idle ring walk spreads them
    rng = random.Random("graftwatch/fanout/3")
    prompts = [f"user{rng.randrange(1 << 16):05d}: spread probe "
               "prompt, long enough to key!" for _ in range(8)]

    def hop_targets(tag):
        targets = []
        for i, prompt in enumerate(prompts):
            rid = f"fanout-{tag}-{i:02d}"
            r = f.client.post("/generate",
                              json={"prompt": prompt,
                                    "max_new_tokens": 2,
                                    "mode": "greedy"},
                              headers={"X-Request-ID": rid})
            assert r.status_code == 200, r.text
            tree = [t for t in f.client.get("/debug/requests?n=32")
                    .json()["requests"] if t["request_id"] == rid][0]
            targets += [s["labels"]["target"] for s in tree["spans"]
                        if s["name"] == "prefill_hop"]
        return targets

    # idle fleet: the prefill ring's warm spread reaches BOTH replicas
    spread = hop_targets("idle")
    assert set(spread) == {"prefill0", "prefill1"}, spread
    # the deterministic pin: order == the pure sort of the ring walk
    # by the router's own in-flight counters
    order = router.prefill_order(b"any-key-at-all")
    assert [p.name for p in order] == graftwatch.order_by_queue_depth(
        [p.name for p in order], router.inflight())
    # back up prefill0: every hop reorders around it
    for _ in range(3):
        router._note_start("prefill0")
    try:
        assert set(hop_targets("backed")) == {"prefill1"}
        assert [p.name for p in router.prefill_order(b"k")][0] \
            == "prefill1"
    finally:
        for _ in range(3):
            router._note_done("prefill0")
    # drained: the ring spread returns
    assert set(hop_targets("drained")) == {"prefill0", "prefill1"}


def test_breaker_series_surfaces_in_profile_snapshot_totals():
    """Satellite: hop_breaker_open samples fire only on HopPolicy
    TRANSITIONS, so a windowed /debug/profile view can miss the (old)
    opening sample while the breaker is still open — the
    window-independent ``series_totals`` block carries every series'
    point count and current value regardless of ``?n=``."""
    policy = graftfault.HopPolicy(attempts=1, timeout_s=1.0,
                                  base_backoff_s=0.001,
                                  max_backoff_s=0.002,
                                  breaker_threshold=2,
                                  breaker_cooldown_s=60.0)

    def boom(_timeout_s):
        raise graftfault.TransientFault("test.hop", "reset",
                                        "injected (test)")

    with pytest.raises(graftfault.TransientFault):
        policy.call(boom, shard="s0")
    # the threshold-crossing failure IS the open transition
    with pytest.raises(graftfault.CircuitOpenError):
        policy.call(boom, shard="s0")
    assert policy.breaker_state("s0") == "open"
    # age the transition out of the windowed view with newer samples
    for i in range(4):
        graftscope.sample("queue_depth", float(i), scheduler="t")
    snap = graftscope.snapshot(n=2)
    label = "hop_breaker_open{target=s0}"
    assert label in snap["series_totals"]
    tot = snap["series_totals"][label]
    assert tot["last"] == 1.0 and tot["max"] == 1.0
    assert tot["points"] >= 1
    # the zero-window snapshot (totals-only mode) still carries it
    empty = graftscope.snapshot(n=0)
    assert empty["series"][label] == []
    assert empty["series_totals"][label]["last"] == 1.0
    # a probe close is a transition too: last flips to 0.0
    policy._breakers["s0"].opened_at = -1e9     # force cooldown expiry
    policy.call(lambda t: "ok", shard="s0")
    assert policy.breaker_state("s0") == "closed"
    assert graftscope.snapshot(n=0)["series_totals"][label]["last"] \
        == 0.0


def test_bench_diff_classifies_plan_switch_metrics():
    """Satellite (CI/tooling): the journaled ``plan_switch`` row's
    invariant metric — compiled programs minted beyond the
    pre-certified set — is gated LOWER-better by bench_diff (the
    pinned value is zero, so any upward drift is a certified-envelope
    leak), while the goodput flanks ride the existing higher-better
    classification."""
    import importlib.util as _ilu
    spec = _ilu.spec_from_file_location(
        "bench_diff", os.path.join(REPO, "tools", "bench_diff.py"))
    bd = _ilu.module_from_spec(spec)
    spec.loader.exec_module(bd)
    assert bd.classify("recompiles_beyond_certified") == "lower"
    assert bd.classify("goodput_fraction_before") == "higher"
    assert bd.classify("goodput_fraction_after") == "higher"
    assert bd.classify("throughput_tokens_per_sec_after") == "higher"
    assert bd.classify("p99_e2e_ms_after") == "lower"
    # report-only context fields stay ungated
    assert bd.classify("switches") is None
    assert bd.classify("certified_program_total") is None
