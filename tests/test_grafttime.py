"""grafttime: the unified causal timeline (bus + export + static pass).

What is pinned here:

1. **bus mechanics**: bounded ring under a 10k-event flood, ambient
   correlation (correlate / request trace / replica), replay
   projection, rebase, and the pinned overhead bound (bus-armed vs
   bus-off decode wall, min-of-3 — the graftscope pattern).
2. **THE acceptance run** (ISSUE 14): one request through the pooled
   iterbatch app under GRAFTSAN=1 GRAFTSCHED=1 GRAFTFAULT=1 with a
   seeded transient decode fault -> a single ``/debug/timeline?rid=``
   stream carrying, in causal order on one clock: arrival, admission,
   dispatch begin/end with certifier program keys, the fault
   injection, the park + byte-identical resume, the park-budget
   breaker state, and the final span close — and its Chrome-trace
   export is schema-valid.
3. **replay determinism**: under GRAFTSCHED=1 with a pinned seed, two
   fresh apps driven by the same serial loadgen schedule produce
   byte-identical per-rid event streams modulo the declared wall-clock
   fields (``grafttime.replay_view`` — the FaultPlan/GRAFTSCHED
   contract), and the export round-trips ``json.loads`` schema-valid.
4. **serving surfaces**: /debug index pinned equal to the /healthz
   topology block; /debug/timeline filters (?rid/?since/?kinds/?n)
   incl. typed 422s; black-box dumps on typed Unavailable (+ the
   $GRAFTTIME_DIR file form); the export CLI.
5. **the static timeline pass**: rule fixtures (undeclared kind,
   off-vocabulary kind, missing required field, stale declaration,
   vacuous module) each exactly one finding with file:line, plus the
   repo-clean/non-vacuous pin.
6. **bench_diff satellites**: ``no_skips_ok`` in the verdict (the
   journaled loud form of --no-skips) and the timeline_overhead row's
   metric classifications.
"""

import json
import os

import jax
import numpy as np
import pytest

from llm_sharding_demo_tpu import loadgen
from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.utils import (graftfault, graftsched,
                                         grafttime, tracing)
from tools.graftcheck import timeline as tl_pass
from tools.graftload import build_demo_app

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- 1. bus mechanics ---------------------------------------------------------


def test_vocabulary_and_field_schema_sync():
    """Every kind with required fields is in the vocabulary, the
    replay-exempt kinds are real kinds, and sample_event covers the
    whole vocabulary schema-complete."""
    assert set(grafttime.KIND_FIELDS) <= set(grafttime.EVENT_KINDS)
    assert set(grafttime.REPLAY_EXEMPT_KINDS) <= set(grafttime.EVENT_KINDS)
    for kind in grafttime.EVENT_KINDS:
        ev = grafttime.sample_event(kind)
        assert ev["kind"] == kind
        for f in grafttime.KIND_FIELDS.get(kind, ()):
            assert f in ev, (kind, f)
    with pytest.raises(KeyError):
        grafttime.sample_event("nope")


def test_bus_bounded_under_flood():
    """10k-event flood: the ring never grows past capacity and the
    drop accounting is honest (a ring, not a log)."""
    grafttime.clear()
    n = 10_000
    for i in range(n):
        grafttime.emit("occupancy", name="queue_depth",
                       value=float(i & 3))
    snap = grafttime.snapshot()
    assert len(snap["events"]) == grafttime.BUS.capacity
    assert snap["emitted_total"] == n
    assert snap["dropped"] == n - grafttime.BUS.capacity
    # newest events won; ts nondecreasing in stream order
    ts = [e["ts"] for e in snap["events"]]
    assert ts == sorted(ts)


def test_since_seq_cursor_incremental_poll_and_wraparound():
    """Satellite (ISSUE 19): ``?since_seq=`` turns /debug/timeline
    into an incremental poll — the payload echoes a ``cursor`` (the
    newest emission sequence) and feeding it back returns only the
    events emitted after it. Pinned through ring wraparound: seq keeps
    climbing while old events rotate out, the increment never
    re-delivers, and events that rotated away between polls surface as
    a rising ``dropped`` count, never as silent gaps presented as
    complete streams."""
    grafttime.clear()
    for i in range(3):
        grafttime.emit("occupancy", name="queue_depth", value=float(i))
    first = grafttime.snapshot()
    assert first["cursor"] == 3
    assert first["since_seq"] is None
    # the increment: only events past the cursor come back
    grafttime.emit("admission", rid="inc-1")
    inc = grafttime.snapshot(since_seq=first["cursor"])
    assert [e["kind"] for e in inc["events"]] == ["admission"]
    assert inc["since_seq"] == first["cursor"]
    assert inc["cursor"] == 4
    # an empty increment is honestly empty, cursor unchanged
    again = grafttime.snapshot(since_seq=inc["cursor"])
    assert again["events"] == [] and again["cursor"] == inc["cursor"]
    # wraparound: flood past RING_CAPACITY from the cursor; seq stays
    # monotonic, the ring holds only the newest capacity events, and
    # the dropped counter carries the honest gap
    cursor = inc["cursor"]
    flood = grafttime.BUS.capacity + 50
    for i in range(flood):
        grafttime.emit("occupancy", name="queue_depth",
                       value=float(i & 1))
    wrap = grafttime.snapshot(since_seq=cursor)
    assert wrap["cursor"] == cursor + flood
    assert len(wrap["events"]) == grafttime.BUS.capacity
    seqs = [e["seq"] for e in wrap["events"]]
    assert min(seqs) > cursor                  # nothing re-delivered
    assert seqs == sorted(seqs)
    assert wrap["dropped"] == wrap["emitted_total"] \
        - grafttime.BUS.capacity
    # the oldest held seq shows exactly what rotated away
    assert min(seqs) == wrap["cursor"] - grafttime.BUS.capacity + 1
    # a cursor in the future of the stream returns nothing (a consumer
    # that over-advanced fails empty, not wrong)
    assert grafttime.snapshot(since_seq=10 ** 9)["events"] == []


def test_correlate_and_ambient_resolution():
    grafttime.clear()
    # explicit rid wins
    grafttime.emit("admission", rid="r-a")
    # correlate: one rid -> rid field, many -> rids field
    with grafttime.correlate(["r-b"]):
        grafttime.emit("fault_inject", site="s", fault="k")
    with grafttime.correlate(["r-c", "r-d", None]):
        grafttime.emit("fault_inject", site="s", fault="k")
    # ambient request trace supplies the rid when nothing else does
    with tracing.use_trace(tracing.RequestTrace("r-e")):
        grafttime.emit("eviction", blocks=1)
    with grafttime.use_replica("decode0"):
        grafttime.emit("breaker", state="open")
    evs = grafttime.events()
    by_kind = {}
    for e in evs:
        by_kind.setdefault(e["kind"], []).append(e)
    assert by_kind["admission"][0]["rid"] == "r-a"
    assert by_kind["fault_inject"][0]["rid"] == "r-b"
    assert by_kind["fault_inject"][1]["rids"] == ["r-c", "r-d"]
    assert by_kind["eviction"][0]["rid"] == "r-e"
    assert by_kind["breaker"][0]["replica"] == "decode0"
    # rid filter matches both the scalar and the membership form
    assert [e["kind"] for e in grafttime.events(rid="r-c")] \
        == ["fault_inject"]
    assert [e["kind"] for e in grafttime.events(rid="r-b")] \
        == ["fault_inject"]


def test_replay_view_projection():
    evs = [
        {"kind": "arrival", "rid": "r1", "ts": 1.0, "seq": 1, "tid": 9,
         "k": 0},
        {"kind": "lock_acquire", "rid": "r1", "ts": 1.5, "seq": 2,
         "tid": 9, "name": "x", "wait_ms": 0.1},
        {"kind": "occupancy", "rid": "r1", "ts": 1.6, "seq": 3,
         "tid": 9, "name": "queue_depth", "value": 1.0},
        {"kind": "span_close", "rids": ["r1", "r2"], "ts": 2.0,
         "seq": 4, "tid": 9, "name": "prefill", "dur_ms": 3.0},
        {"kind": "eviction", "ts": 2.5, "seq": 5, "tid": 9, "blocks": 1},
    ]
    view = grafttime.replay_view(evs)
    # schedule-observation kinds and uncorrelated events dropped,
    # wall-clock fields stripped, shared events fan out per rid
    assert sorted(view) == ["r1", "r2"]
    assert view["r1"] == [
        {"kind": "arrival", "rid": "r1", "k": 0},
        {"kind": "span_close", "rids": ["r1", "r2"], "name": "prefill"},
    ]
    assert view["r2"] == [
        {"kind": "span_close", "rids": ["r1", "r2"], "name": "prefill"},
    ]


def test_rebase_shifts_onto_caller_clock():
    evs = [{"kind": "arrival", "ts": 10.0, "rid": "r"},
           {"kind": "span_close", "ts": 12.5, "rid": "r", "name": "x"}]
    shifted = grafttime.rebase(evs, 100.0)
    assert [e["ts"] for e in shifted] == [110.0, 112.5]
    assert [e["ts"] for e in evs] == [10.0, 12.5]   # input untouched


def test_export_chrome_every_kind_schema_valid():
    evs = [grafttime.sample_event(k) for k in grafttime.EVENT_KINDS]
    payload = grafttime.export_chrome(evs, meta={"note": "t"})
    assert grafttime.validate_chrome(payload) == []
    # round-trips as real JSON
    back = json.loads(json.dumps(payload))
    assert len(back["traceEvents"]) == len(evs)
    phases = {te["ph"] for te in back["traceEvents"]}
    assert "X" in phases and "C" in phases and "i" in phases
    # window kinds carry their measured duration
    spans = [te for te in back["traceEvents"] if te["ph"] == "X"]
    assert all(te["dur"] >= 0 and te["ts"] >= 0 for te in spans)
    # validator actually rejects garbage
    assert grafttime.validate_chrome({"traceEvents": [{}]}) != []
    assert grafttime.validate_chrome([]) != []


def test_export_cli_round_trip(tmp_path):
    from tools import grafttime as cli
    src = tmp_path / "stream.json"
    out = tmp_path / "trace.json"
    src.write_text(json.dumps(
        {"events": [grafttime.sample_event("span_close"),
                    grafttime.sample_event("arrival")]}))
    assert cli.main(["export", "--input", str(src),
                     "--output", str(out)]) == 0
    trace = json.loads(out.read_text())
    assert grafttime.validate_chrome(trace) == []
    assert trace["otherData"]["producer"] == "grafttime"
    # bare-list input shape
    src.write_text(json.dumps([grafttime.sample_event("park")]))
    assert cli.main(["export", "--input", str(src),
                     "--output", str(out)]) == 0
    # unreadable / unrecognized input: typed refusal, exit 2
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert cli.main(["export", "--input", str(bad)]) == 2
    src.write_text(json.dumps({"nope": 1}))
    assert cli.main(["export", "--input", str(src)]) == 2


TINY = gpt2.GPT2Config(vocab_size=97, n_positions=64, n_embd=16,
                       n_layer=2, n_head=2)


def test_overhead_bound_pinned():
    """The declared bound (grafttime.OVERHEAD_FACTOR): a decode run
    with the bus armed (all producers live) stays within the factor of
    bus-off wall time. min-of-3 on both sides absorbs CPU scheduling
    noise — the per-event cost is a plain-lock deque append against
    millisecond dispatches."""
    import time

    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    params = gpt2.init_params(TINY, jax.random.PRNGKey(0))
    eng = DecodeEngine(params, TINY, max_seq=64)
    prompt = np.full((1, 8), 5, dtype=np.int32)

    def best_of(n):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            eng.generate(prompt, 24)
            best = min(best, time.perf_counter() - t0)
        return best

    eng.generate(prompt, 24)                     # warm-up: compiles
    prev = grafttime.set_enabled(False)
    try:
        disabled = best_of(3)
    finally:
        grafttime.set_enabled(prev)
    grafttime.set_enabled(True)
    enabled = best_of(3)
    assert enabled <= disabled * grafttime.OVERHEAD_FACTOR, (
        f"grafttime overhead {enabled / disabled:.2f}x exceeds the "
        f"declared {grafttime.OVERHEAD_FACTOR}x bound")


# -- 2. serving surfaces ------------------------------------------------------


@pytest.fixture(scope="module")
def demo():
    """One shared tiny pooled-iterbatch serving app (module-scoped:
    the jitted programs are the expensive part)."""
    return build_demo_app(max_seq=128, max_batch=4,
                          recorder_capacity=128)


def test_debug_index_pinned_to_healthz_topology(demo):
    """Satellite: GET /debug lists every debug surface with a
    description, under the SAME topology header as /healthz."""
    client, _rec, _reg = demo
    idx = client.get("/debug")
    assert idx.status_code == 200
    body = idx.json()
    assert sorted(body["surfaces"]) == [
        "/debug/memory", "/debug/plan", "/debug/profile",
        "/debug/requests", "/debug/timeline", "/debug/trend"]
    for surface, desc in body["surfaces"].items():
        assert isinstance(desc, str) and desc
        assert client.get(surface).status_code == 200, surface
    hz = client.get("/healthz").json()
    # the index's serving block IS the /healthz topology block
    for k, v in body["serving"].items():
        assert hz[k] == v, k
    # and it is the full topology dict, not a subset hand-copy
    assert {"role", "model", "n_stages", "batch_mode", "max_batch",
            "kv_pool_blocks", "fleet_role"} <= set(body["serving"])


def test_debug_timeline_filters_and_422s(demo):
    client, _rec, _reg = demo
    grafttime.clear()
    rid = "tl-filter-1"
    r = client.post("/generate", json={"prompt": "Hi there",
                                       "max_new_tokens": 3,
                                       "mode": "greedy"},
                    headers={"X-Request-ID": rid})
    assert r.status_code == 200
    full = client.get("/debug/timeline").json()
    assert full["enabled"] is True
    assert full["clock"]["epoch_unix"] > 0
    assert set(full["kinds"]) == set(grafttime.EVENT_KINDS)
    stream = client.get(f"/debug/timeline?rid={rid}").json()["events"]
    assert stream, "rid stream empty"
    assert all(e.get("rid") == rid or rid in e.get("rids", ())
               for e in stream)
    kinds = [e["kind"] for e in stream]
    assert "span_close" in kinds and "admission" in kinds
    # replica label rode the request-scoped events
    assert any(e.get("replica") == "solo" for e in stream)
    # kinds filter
    only = client.get(
        f"/debug/timeline?rid={rid}&kinds=admission").json()["events"]
    assert only and all(e["kind"] == "admission" for e in only)
    # since: nothing is newer than the bus's own now
    now = grafttime.now_ms()
    assert client.get(
        f"/debug/timeline?since={now}").json()["events"] == []
    # n caps to the newest n; n=0 means NONE, not all (the graftscope
    # window convention)
    assert len(client.get(
        "/debug/timeline?n=3").json()["events"]) == 3
    assert client.get("/debug/timeline?n=0").json()["events"] == []
    # since_seq: the echoed cursor feeds the next incremental poll
    # (the ?since= ts filter would skip a backdated late emission;
    # the seq cursor cannot)
    head = client.get("/debug/timeline").json()
    assert head["cursor"] == head["emitted_total"]
    grafttime.emit("occupancy", name="queue_depth", value=1.0)
    inc = client.get(
        f"/debug/timeline?since_seq={head['cursor']}").json()
    assert [e["kind"] for e in inc["events"]] == ["occupancy"]
    assert inc["since_seq"] == head["cursor"]
    # typed 422s
    assert client.get("/debug/timeline?since=abc").status_code == 422
    assert client.get("/debug/timeline?n=abc").status_code == 422
    r = client.get("/debug/timeline?since_seq=abc")
    assert r.status_code == 422
    assert "cursor" in r.json()["detail"]
    bad = client.get("/debug/timeline?kinds=admission,bogus")
    assert bad.status_code == 422
    assert "bogus" in bad.json()["detail"]


def test_blackbox_dump_on_typed_unavailable(demo, tmp_path,
                                            monkeypatch):
    """A typed Unavailable surfacing at the serving boundary journals
    the ring (bounded in-process dump + the $GRAFTTIME_DIR file)."""
    client, _rec, _reg = demo
    monkeypatch.setenv("GRAFTTIME_DIR", str(tmp_path))
    grafttime.clear()
    grafttime.clear_blackbox()
    rid = "tl-bb-1"
    r = client.post("/generate", json={"prompt": "Hello doomed",
                                       "max_new_tokens": 3,
                                       "mode": "greedy"},
                    headers={"X-Request-ID": rid,
                             "X-Deadline-Ms": "1"})
    assert r.status_code == 503
    assert r.json()["error"] == "deadline_exceeded"
    dumps = grafttime.blackbox_dumps()
    assert len(dumps) == 1
    assert dumps[0]["reason"] == "deadline_exceeded"
    assert dumps[0]["rid"] == rid
    assert any(e.get("rid") == rid for e in dumps[0]["events"])
    files = sorted(tmp_path.glob("grafttime_blackbox_*.json"))
    assert len(files) == 1
    on_disk = json.loads(files[0].read_text())
    assert on_disk["reason"] == "deadline_exceeded"
    # the dump exports as a valid Chrome trace (the CLI input contract)
    payload = grafttime.export_chrome(on_disk["events"])
    assert grafttime.validate_chrome(payload) == []


def test_router_timeline_joins_replicas_on_one_clock():
    """The fleet form: one request through the router shows router AND
    replica events in a single ?rid= stream (shared process bus = one
    clock by construction; clock_alignment says so), with replica
    labels distinguishing the hops."""
    from llm_sharding_demo_tpu.fleet.harness import build_fleet
    fleet = build_fleet(n_decode=2, n_prefill=1, max_batch=2)
    grafttime.clear()
    rid = "tl-fleet-1"
    r = fleet.client.post("/generate",
                          json={"prompt": "Hello fleet timeline!",
                                "max_new_tokens": 3, "mode": "greedy"},
                          headers={"X-Request-ID": rid})
    assert r.status_code == 200
    body = fleet.client.get(f"/debug/timeline?rid={rid}").json()
    assert body["clock_alignment"] == {"mode": "shared-process-clock",
                                       "offset_ms": 0.0}
    assert body["serving"]["role"] == "router"
    stream = body["events"]
    replicas = {e.get("replica") for e in stream} - {None}
    # the router labeled its own spans; at least one replica served
    assert "router" in replicas
    assert any(lbl.startswith(("decode", "prefill"))
               for lbl in replicas), replicas
    # SCHEDULER-side events carry the replica too: the iter worker
    # thread pins its app's label (handler contextvars don't propagate
    # to a thread started at construction)
    adm = [e for e in stream if e["kind"] == "admission"]
    assert adm and all(a.get("replica", "").startswith("decode")
                       for a in adm), adm
    ts = [e["ts"] for e in stream]
    assert ts == sorted(ts)
    # the router's debug index lists its own two surfaces
    idx = fleet.client.get("/debug").json()
    assert sorted(idx["surfaces"]) == ["/debug/requests",
                                       "/debug/timeline"]


# -- 3. THE acceptance run ----------------------------------------------------


def _ordered(kinds_seq, *wanted):
    """Index of each wanted kind's FIRST occurrence; asserts strictly
    increasing (causal order in the stream)."""
    idxs = []
    for w in wanted:
        assert w in kinds_seq, f"kind {w!r} missing from stream"
        idxs.append(kinds_seq.index(w))
    assert idxs == sorted(idxs), list(zip(wanted, idxs))
    return idxs


def test_acceptance_causal_stream_with_seeded_fault(monkeypatch):
    """ISSUE 14 acceptance: one request through the pooled-iter app
    under GRAFTSAN=1 GRAFTSCHED=1 GRAFTFAULT=1 with exactly one seeded
    transient decode fault. The ?rid= stream shows the whole causal
    story on one clock — and the resumed stream is byte-identical to
    an unfaulted run of the same schedule."""
    from llm_sharding_demo_tpu.runtime import kv_pool
    monkeypatch.setenv("GRAFTSAN", "1")
    monkeypatch.setenv("GRAFTSCHED", "1")
    monkeypatch.setenv("GRAFTSCHED_SEED", "3")
    monkeypatch.setenv("GRAFTFAULT", "1")
    graftsched.clear()
    graftfault.reset()
    prof = loadgen.profile("agentic")
    try:
        # unfaulted reference: same schedule, fresh app
        client0, rec0, _ = build_demo_app(max_seq=128, max_batch=2,
                                          recorder_capacity=16)
        ref = loadgen.run_load(client0, prof, seed=21, n=1,
                               mode="serial", recorder=rec0)
        assert ref["completed"] == 1

        client, rec, _reg = build_demo_app(max_seq=128, max_batch=2,
                                           recorder_capacity=16)
        grafttime.clear()
        plan = graftfault.FaultPlan(seed=7, rate=1.0, max_injections=1,
                                    sites={"iterbatch.decode_seg"},
                                    kinds={"decode_transient"})
        with graftfault.use(plan):
            rep = loadgen.run_load(client, prof, seed=21, n=1,
                                   mode="serial", recorder=rec)
        assert len(plan.injections) == 1, "the seeded fault never fired"
        assert rep["completed"] == 1, rep["error_codes"]
        # byte-identical resume: the faulted run's output equals the
        # unfaulted reference's
        assert [o.generated for o in rep["outcomes"]] \
            == [o.generated for o in ref["outcomes"]]

        rid = rep["outcomes"][0].request_id
        stream = client.get(
            f"/debug/timeline?rid={rid}").json()["events"]
        kinds = [e["kind"] for e in stream]
        # ONE clock, causal order: arrival -> admission -> dispatch ->
        # fault -> breaker state -> park -> resume -> final span close
        _ordered(kinds, "arrival", "admission", "dispatch_begin",
                 "fault_inject", "breaker", "park", "resume")
        assert kinds and kinds[0] == "arrival"
        # the final span close is the whole-request window
        closes = [e for e in stream if e["kind"] == "span_close"]
        assert closes and closes[-1]["name"] == "request"
        assert kinds.index("resume") < len(kinds) - 1 - kinds[::-1] \
            .index("span_close")
        # ts nondecreasing across the stream (one clock)
        ts = [e["ts"] for e in stream]
        assert ts == sorted(ts)
        # dispatch events carry the certifier's program keys for both
        # the prefill and the segment decode programs
        ends = [e for e in stream if e["kind"] == "dispatch_end"]
        assert any("._prefill" in e["scope"] and e["key"]
                   for e in ends), ends
        assert any("._decode_seg" in e["scope"] and e["key"]
                   for e in ends), ends
        # the fault injection names its site + provenance
        fi = next(e for e in stream if e["kind"] == "fault_inject")
        assert fi["site"] == "iterbatch.decode_seg"
        assert fi["fault"] == "decode_transient"
        # park carries the fault reason; breaker is the row's
        # park-budget state, still closed (budget absorbed it)
        pk = next(e for e in stream if e["kind"] == "park")
        assert pk["reason"] == "fault" and pk["rid"] == rid
        br = next(e for e in stream if e["kind"] == "breaker")
        assert br["state"] == "closed"
        assert br["scope"] == "iterbatch.fault_park_budget"
        assert br["used"] == 1
        # the Chrome-trace export of THIS stream is schema-valid and
        # round-trips json.loads
        payload = grafttime.export_chrome(stream)
        assert grafttime.validate_chrome(payload) == []
        json.loads(json.dumps(payload))
    finally:
        graftfault.reset()
    kv_pool.graftsan_sweep(timeout=10.0)
    assert graftsched.findings() == [], \
        [f.format() for f in graftsched.findings()]


# -- 4. replay determinism ----------------------------------------------------


def test_two_runs_byte_identical_replay_view(monkeypatch):
    """Under GRAFTSCHED=1 with a pinned seed, the same serial loadgen
    schedule on two fresh apps produces byte-identical per-rid event
    streams modulo the declared wall-clock fields and
    schedule-observation kinds (grafttime.replay_view — the
    FaultPlan/GRAFTSCHED replay contract)."""
    monkeypatch.setenv("GRAFTSCHED", "1")
    monkeypatch.setenv("GRAFTSCHED_SEED", "5")
    views = []
    exports = []
    for _ in range(2):
        graftsched.clear()
        client, rec, _reg = build_demo_app(max_seq=128, max_batch=4,
                                           recorder_capacity=32)
        grafttime.clear()
        rep = loadgen.run_load(client, loadgen.profile("agentic"),
                               seed=13, n=3, mode="serial",
                               recorder=rec)
        assert rep["completed"] == 3, rep["error_codes"]
        evs = grafttime.events()
        views.append(json.dumps(grafttime.replay_view(evs),
                                sort_keys=True))
        exports.append(grafttime.export_chrome(evs))
    assert views[0] == views[1]
    # and the export round-trips json.loads schema-valid
    for payload in exports:
        assert grafttime.validate_chrome(payload) == []
        json.loads(json.dumps(payload))


# -- 5. the static timeline pass ----------------------------------------------

VOCAB = {"arrival": "x", "park": "x", "occupancy": "x"}
FIELDS = {"arrival": ("rid",), "park": ("rid", "reason"),
          "occupancy": ("name", "value")}


def _run_fixture(tmp_path, source):
    p = tmp_path / "fixture_mod.py"
    p.write_text(source)
    return tl_pass.run_timeline(str(tmp_path), paths=[str(p)],
                                vocabulary=VOCAB, kind_fields=FIELDS,
                                check_export=False)


def test_fixture_emit_without_declaration(tmp_path):
    findings, summary = _run_fixture(tmp_path, """\
from llm_sharding_demo_tpu.utils import grafttime

def fire(rid):
    grafttime.emit("arrival", rid=rid)
""")
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "undeclared-timeline-event"
    assert "declares no TIMELINE_EVENTS" in f.message
    assert f.line == 4 and f.scope == "fire"


def test_fixture_off_vocabulary_and_undeclared_kind(tmp_path):
    findings, _ = _run_fixture(tmp_path, """\
from llm_sharding_demo_tpu.utils import grafttime

TIMELINE_EVENTS = {"arrival": "fire"}

def fire(rid):
    grafttime.emit("arrival", rid=rid)
    grafttime.emit("warp_drive", rid=rid)       # off-vocabulary
    grafttime.emit("park", rid=rid, reason="x")  # undeclared here
    grafttime.emit("arr" + "ival", rid=rid)      # computed kind
""")
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 3
    assert any("outside the fixed vocabulary" in m for m in msgs)
    assert any("not declared in this module's TIMELINE_EVENTS" in m
               for m in msgs)
    assert any("must be a string literal" in m for m in msgs)


def test_fixture_missing_required_field(tmp_path):
    findings, _ = _run_fixture(tmp_path, """\
from llm_sharding_demo_tpu.utils import grafttime

TIMELINE_EVENTS = {"park": "fire"}

def fire(rid):
    grafttime.emit("park", rid=rid)   # reason not spelled
""")
    assert len(findings) == 1
    assert "does not spell required field(s) ['reason']" \
        in findings[0].message


def test_fixture_stale_declaration_and_vacuous(tmp_path):
    findings, summary = _run_fixture(tmp_path, """\
from llm_sharding_demo_tpu.utils import grafttime

TIMELINE_EVENTS = {"arrival": "fire", "bogus_kind": "nowhere"}
""")
    rules = sorted(f.rule for f in findings)
    assert rules == ["timeline-event-not-emitted",
                     "timeline-event-not-emitted"]
    msgs = sorted(f.message for f in findings)
    assert any("no grafttime.emit site in this module publishes it"
               in m for m in msgs)
    assert any("outside the fixed vocabulary" in m for m in msgs)
    # nothing declared is live -> the module is vacuous
    assert summary["vacuous"] == ["fixture_mod.py"]
    assert summary["timeline_kinds"]["fixture_mod.py"] == 0


def test_fixture_malformed_declaration(tmp_path):
    findings, _ = _run_fixture(tmp_path, """\
from llm_sharding_demo_tpu.utils import grafttime

KINDS = ("arrival",)
TIMELINE_EVENTS = {k: "dyn" for k in KINDS}

def fire(rid):
    grafttime.emit("arrival", rid=rid)
""")
    assert any("must be a dict literal" in f.message for f in findings)


def test_repo_timeline_pass_clean_and_nonvacuous():
    """The real tree: zero findings, no vacuous producer, the declared
    producer set live (mirrors the strict in-suite driver's floor)."""
    findings, summary = tl_pass.run_timeline(REPO)
    assert findings == [], [f.format() for f in findings]
    assert summary["vacuous"] == []
    assert summary["timeline_checks"] >= 10
    live = summary["timeline_kinds"]
    assert live.get("llm_sharding_demo_tpu/runtime/iterbatch.py", 0) >= 5
    assert live.get("llm_sharding_demo_tpu/utils/tracing.py", 0) >= 2
    # export validity is part of the pass's check budget: every
    # vocabulary kind contributed a check
    assert summary["timeline_checks"] >= len(grafttime.EVENT_KINDS)


# -- 6. bench_diff satellites -------------------------------------------------


def _bench_diff():
    import sys
    tools = os.path.join(REPO, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import bench_diff
    return bench_diff


def test_bench_diff_no_skips_ok_journaled_form():
    """Satellite: the --no-skips verdict rides the payload as
    ``no_skips_ok`` — a run without its chip (skip-with-reason rows) is
    loud in the journaled bench_diff row, not only behind the
    opt-in flag."""
    bd = _bench_diff()
    hist = [("r01", {"a.tokens_per_sec": 10.0})]
    clean = bd.compare({"a.tokens_per_sec": 10.0}, hist)
    assert clean["ok"] is True and clean["no_skips_ok"] is True
    skipped = bd.compare({"a.tokens_per_sec": 10.0}, hist,
                         current_skips={"cfg14_paged": "chip not attached"})
    assert skipped["ok"] is True           # skips alone never gate...
    assert skipped["no_skips_ok"] is False  # ...but they are LOUD
    assert skipped["ungated_rows"] == [
        {"config": "cfg14_paged", "reason": "chip not attached"}]
    # a regression turns both off
    regressed = bd.compare({"a.tokens_per_sec": 1.0}, hist)
    assert regressed["ok"] is False and regressed["no_skips_ok"] is False


def test_bench_diff_timeline_overhead_classifications():
    """The timeline_overhead row's gated fields: emit throughput
    regresses downward, the bus-armed wall ratio upward."""
    bd = _bench_diff()
    assert bd.classify("events_per_sec") == "higher"
    assert bd.classify("overhead_factor") == "lower"
    hist = [("r01", {"timeline_overhead.events_per_sec": 1000.0,
                     "timeline_overhead.overhead_factor": 1.0})]
    v = bd.compare({"timeline_overhead.events_per_sec": 100.0,
                    "timeline_overhead.overhead_factor": 2.0}, hist)
    assert sorted(v["regressions"]) == [
        "timeline_overhead.events_per_sec",
        "timeline_overhead.overhead_factor"]
