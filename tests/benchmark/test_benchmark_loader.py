"""A configuration, a traffic mix, a cell and a per-layer metric added as
new files and entries are found by name: no file that is there is edited."""

import json
import os
import re
import shutil
import sys
import types

import pytest

from benchmark.harness.spec import REPO, ROOT, Spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture
def grown(tmp_path):
    """A copy of the benchmark with one of each kind added beside it."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    root = tmp_path / "benchmark"
    for sub in ("configs", "traffic", "cells", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, sub), root / sub)
    shutil.copy(os.path.join(ROOT, "peaks.json"), root)
    before = {p: p.read_bytes() for p in root.rglob("*.json")}
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())

    config = json.loads((root / "configs" / "mistral-7b-l16.json").read_text())
    config["num_hidden_layers"] = 24
    (root / "configs" / "new-model.json").write_text(json.dumps(config))
    (root / "traffic" / "new-mix.json").write_text(json.dumps({
        "arrival": "poisson", "base_seed": 3,
        "prompt": {"median": 100, "sigma": 0.1, "min": 64, "max": 128},
        "output": {"median": 8, "sigma": 0.1, "min": 4, "max": 16}}))
    (root / "cells" / "new-model.new-mix.json").write_text(
        json.dumps({"rate_rps": 3.5}))
    (root / "layer_metrics" / "new_metric.json").write_text(json.dumps(
        {"reader": "new_reader_module:read", "params": {"scale": 2}}))
    (tmp_path / "new_reader_module.py").write_text(
        "def read(ctx, scale):\n    return ctx.value * scale\n")
    doc["configs"].append({"name": "new-model", "source": "x",
                           "file": "benchmark/configs/new-model.json",
                           "reduced": ["num_hidden_layers"], "why": "x"})
    doc["workloads"].append({"name": "new-model.new-mix", "config": "new-model",
                             "traffic": "new-mix", "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "new_metric", "unit": "ms",
                             "better": "lower", "source": "program_span",
                             "layer": "Engine", "moves": "tpot_p95_ms",
                             "workloads": ["new-model.new-mix"]})
    doc["end_to_end"] = [dict(m, workloads=m["workloads"] + ["new-model.new-mix"])
                         if m["name"] == "tpot_p95_ms" else m
                         for m in doc["end_to_end"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    sys.path.insert(0, str(tmp_path))
    yield tmp_path, before
    sys.path.remove(str(tmp_path))
    sys.modules.pop("new_reader_module", None)


def test_new_files_are_found_by_name(grown):
    tmp_path, before = grown
    spec = Spec(str(tmp_path / "BENCHMARK.json"))
    entry = spec.workload("new-model.new-mix")
    assert spec.config(entry["config"])["num_hidden_layers"] == 24
    assert spec.traffic(entry["traffic"])["name"] == "new-mix"
    assert spec.cell("new-model.new-mix")["rate_rps"] == 3.5
    assert spec.reader("new_metric")(types.SimpleNamespace(value=21)) == 42
    assert [m["name"] for m in spec.metrics("per_layer", "new-model.new-mix")] \
        == ["new_metric"]
    assert {m["name"] for m in spec.metrics("end_to_end", "new-model.new-mix")} \
        == {"tpot_p95_ms", "setup_s"}
    # and nothing that was there changed
    assert all(p.read_bytes() == b for p, b in before.items())
    from benchmark.harness import traffic
    arrivals = traffic.schedule(spec.traffic("new-mix"), 1, 3.5, 4, 50257)
    assert len(arrivals) == 14
    assert all(64 <= len(a.prompt_ids) <= 128 for a in arrivals)


def test_unknown_names_are_errors_not_defaults():
    spec = Spec()
    with pytest.raises(KeyError):
        spec.workload("no-such-cell")
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_benchmark_json_keeps_the_contracts_shape():
    doc = Spec().doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e and 1 <= doc["run_seconds"] <= 51
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in (
            "host_clock", "device_trace")
    cells = {w["name"] for w in doc["workloads"]}
    for w in doc["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in doc["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        moved = next(e for e in doc["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])


@pytest.mark.parametrize("cell", [w["name"] for w in Spec().doc["workloads"]])
def test_every_cell_has_its_files_and_readers(cell):
    spec = Spec()
    entry = spec.workload(cell)
    config = spec.config(entry["config"])
    assert spec.traffic(entry["traffic"])["prompt"]["max"] + \
        spec.traffic(entry["traffic"])["output"]["max"] <= \
        int(config["serving_env"]["MAX_SEQ"])
    assert spec.cell(cell)["rate_rps"] > 0
    from benchmark.harness.spec import resolve
    assert callable(resolve(config["warmup"]))
    assert callable(resolve(config["check"]["procedure"]))
    assert set(config["check"]["limits"]) == {"deficit_mean", "deficit_max"}
    layer = spec.metrics("per_layer", cell)
    assert layer and all(callable(spec.reader(m["name"])) for m in layer)
    assert len(spec.metrics("end_to_end", cell)) >= 2
    listed = next(c for c in spec.doc["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == config["reduced"]
