"""The readers of ready instants and wait causes (benchmark/readers/ready.py):
known answers on hand-made span trees, nothing from a program without the
labels, found by name through the loader, and all five in the line of a
traced rehearsal."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark.harness.spec import REPO, ROOT, Spec
from benchmark.readers import ready

NEW = {"first_token_p95_ms": ("ms", "Scheduler"),
       "tpot_ready_p50_ms": ("ms", "Scheduler"),
       "dispatch_lead_p95_ms": ("ms", "Engine"),
       "segment_period_ms_per_step": ("ms", "Engine"),
       "wait_closed_share": ("%", "Scheduler")}
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture")


def _span(name, start, dur, **labels):
    return {"name": name, "start_ms": start, "duration_ms": dur,
            "labels": labels}


def _decode(start, seg, batch, ready_ms, steps=32):
    return _span("decode", start, 1.0, seg=seg, batch=batch, steps=steps,
                 ready_ms=ready_ms)


def _ctx(with_labels=True):
    """Three answered requests and one that failed. ``a`` seeds batch 0
    and rides segments 0-2; ``b`` joins it for segments 1-2 (its clock
    starts 100 ms after ``a``'s); ``c`` waits behind the closed batch
    and seeds batch 1 with segment 3."""
    a = [_span("queue_wait", 0, 10, closed_ms=0.0, slot_ms=0.0, pool_ms=0.0,
               boundary_ms=10.0),
         _span("prefill", 10, 2, kind="seed", ready_ms=60.0),
         _decode(12, 0, 0, 400.0), _decode(14, 1, 0, 784.0),
         _decode(16, 2, 0, 1104.0)]
    b = [_span("queue_wait", 0, 30, closed_ms=0.0, slot_ms=10.0, pool_ms=0.0,
               boundary_ms=20.0),
         _span("prefill", 30, 5, prefix=True, live=1, ready_ms=450.0),
         _decode(-86, 1, 0, 684.0), _decode(-84, 2, 0, 1004.0)]
    c = [_span("queue_wait", 0, 1000, closed_ms=900.0, slot_ms=0.0,
               pool_ms=0.0, boundary_ms=100.0),
         _span("prefill", 1000, 3, kind="seed", ready_ms=1010.0),
         _decode(1003, 3, 1, 1303.0, steps=16)]
    trees = {"a": a, "b": b, "c": c}
    if not with_labels:
        for spans in trees.values():
            for s in spans:
                s["labels"] = {k: v for k, v in s["labels"].items()
                               if k in ("kind", "prefix", "steps")}
    traces = [{"request_id": rid, "started_unix": 0.0, "duration_ms": 2000.0,
               "spans": spans} for rid, spans in trees.items()]
    rows = [{"rid": "a", "ok": True, "lateness_ms": 1.0, "new_tokens": 97},
            {"rid": "b", "ok": True, "lateness_ms": 2.0, "new_tokens": 2},
            {"rid": "c", "ok": True, "lateness_ms": 0.5, "new_tokens": 1},
            {"rid": "d", "ok": False, "lateness_ms": 0.0}]
    return types.SimpleNamespace(rows=rows, window_traces=traces)


def test_first_token_counts_from_the_due_instant_and_a_failure_as_the_worst():
    # a 61, b 452, c 1010.5, d failed -> 1010.5: the worst twice
    assert ready.first_token_p95_ms(_ctx()) == 1010.5
    assert ready.first_token_p95_ms(_ctx(), q=50.0) == 452.0
    assert ready.first_token_p95_ms(_ctx(), q=25.0) == 61.0


def test_tpot_ready_is_last_decode_ready_less_prefill_ready_over_the_gaps():
    # a: (1104 - 60) / 96 = 10.875; b: (1004 - 450) / 1 = 554; c: one token
    assert ready.tpot_ready_p50_ms(_ctx()) == 10.875
    assert ready.tpot_ready_p50_ms(_ctx(), q=100.0) == 554.0


def test_dispatch_lead_is_per_distinct_segment():
    # seg 0: 400 - 13 = 387; seg 1: 769 on either clock; seg 2: 1087;
    # seg 3: 299 -> four values, not six
    ctx = _ctx()
    assert ready.dispatch_lead_p95_ms(ctx) == 1087.0
    assert ready.dispatch_lead_p95_ms(ctx, q=25.0) == 299.0
    assert ready.dispatch_lead_p95_ms(ctx, q=50.0) == 387.0


def test_segment_period_pairs_consecutive_segments_of_one_batch_only():
    # batch 0: seg 1 (784 - 400) / 32 = 12, seg 2 (1104 - 784) / 32 = 10
    # (b's clock gives the same 320 / 32); seg 3 is batch 1's first
    ctx = _ctx()
    assert ready.segment_period_ms_per_step(ctx) == 10.0
    assert ready.segment_period_ms_per_step(ctx, q=100.0) == 12.0


def test_wait_closed_share_is_over_answered_requests_waits():
    assert ready.wait_closed_share(_ctx()) == pytest.approx(
        100.0 * 900.0 / 1040.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_labels_gives_none(name):
    ctx = _ctx(with_labels=False)
    assert getattr(ready, name)(ctx) is None
    assert getattr(ready, name)(types.SimpleNamespace(
        rows=[], window_traces=[])) is None


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of the CPU fixture with the five metrics added as new
    files and entries, and a cell whose answers outlast one segment (so
    a batch has consecutive segments to take a period from)."""
    tmp = tmp_path_factory.mktemp("ready") / "fixture"
    shutil.copytree(FIXTURE, tmp)
    before = {p: p.read_bytes() for p in tmp.rglob("*.json")
              if p.name != "BENCHMARK.json"}
    doc = json.loads((tmp / "BENCHMARK.json").read_text())
    for name, (unit, layer) in NEW.items():
        shutil.copy(os.path.join(ROOT, "layer_metrics", f"{name}.json"),
                    tmp / "bench" / "layer_metrics")
        doc["per_layer"].append({"name": name, "unit": unit,
                                 "better": "lower", "source": "program_span",
                                 "layer": layer, "moves": "tpot_p50_ms"})
    traffic = json.loads((tmp / "bench" / "traffic" / "tiny.json").read_text())
    traffic["output"] = {"median": 48, "sigma": 0.2, "min": 40, "max": 64}
    (tmp / "bench" / "traffic" / "tiny-long.json").write_text(
        json.dumps(traffic))
    shutil.copy(tmp / "bench" / "cells" / "tiny-llama.tiny.json",
                tmp / "bench" / "cells" / "tiny-llama.tiny-long.json")
    doc["workloads"].append(dict(doc["workloads"][0],
                                 name="tiny-llama.tiny-long",
                                 traffic="tiny-long"))
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp, before


def test_the_loader_finds_each_new_metric_by_name(grown):
    tmp, before = grown
    spec = Spec(str(tmp / "BENCHMARK.json"))
    listed = [m["name"] for m in spec.metrics("per_layer",
                                              "tiny-llama.tiny-long")]
    assert listed[-5:] == list(NEW)
    ctx = _ctx()
    for name in NEW:
        assert spec.reader(name)(ctx) == getattr(ready, name)(ctx)
    assert all(p.read_bytes() == b for p, b in before.items())
    # and the repo's own entries name the same readers, for both cells
    own = Spec()
    for name, (unit, layer) in NEW.items():
        (entry,) = [m for m in own.doc["per_layer"] if m["name"] == name]
        assert (entry["unit"], entry["layer"], entry["moves"]) == \
            (unit, layer, "tpot_p50_ms")
        assert entry["workloads"] == [w["name"] for w in own.doc["workloads"]]
        assert own.reader(name)(ctx) == getattr(ready, name)(ctx)


def test_a_traced_rehearsal_prints_all_five_with_values(grown):
    tmp, _ = grown
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "tiny-llama.tiny-long", "--seconds", "2", "--benchmark-json",
         str(tmp / "BENCHMARK.json"), "--seed", str(2**31 + 7), "--trace",
         "1", "--rehearse"], cwd=REPO, capture_output=True, text=True,
        timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"     # never a device metric
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(got), sorted(got)
    every = json.loads(next(l for l in lines if l.startswith(
        "end to end in this traced run")).split(": ", 1)[1])
    # a ready instant is never before the end of the dispatch it covers,
    # and the server's finish never before the last ready instant
    assert got["first_token_p95_ms"] >= every["ttft_p95_ms"]
    assert 0 < got["tpot_ready_p50_ms"] <= every["tpot_p50_ms"]
    assert got["segment_period_ms_per_step"] > 0
    assert got["dispatch_lead_p95_ms"] >= 0
    assert 0 <= got["wait_closed_share"] <= 100
