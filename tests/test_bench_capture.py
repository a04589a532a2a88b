"""What is left of the bench driver's contract after ISSUE 21: one
process, a TPU or nothing (non-zero exit, no result line), an error for a
device whose peak is unknown — and the per-config /metrics deltas.
"""

import os
import subprocess
import sys

import pytest

import bench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_without_a_tpu_exits_nonzero_and_prints_no_result():
    """No CPU fallback, no ``value: null`` line with rc 0: a measurement
    path that finds no chip fails."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                        "--quick"], env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout[-400:]
    assert "no TPU" in r.stderr


def test_unknown_device_kind_is_an_error(monkeypatch):
    """The peaks table holds the devices it knows; anything else raises
    (under the CPU-pinned suite the device kind is ``cpu``)."""
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        bench._peak_bf16_flops()


def test_bench_has_no_parent_probe_child_or_journal():
    """One process for each chip: the scaffolding that ran the bench in
    a child of a probing parent is gone, and stays gone."""
    for name in ("_parent_main", "_probe_backend", "_run_child",
                 "_journal_row", "_CHILD_SENTINEL", "_PROGRESS_ENV"):
        assert not hasattr(bench, name), name


def test_metrics_delta_counters_and_gauges():
    """Per-config /metrics deltas: counters as after-before, gauges at
    final value, unchanged series and _avg noise dropped."""
    before = {"spec_verify_steps_total": 10.0,
              "prefix_cache_hits_total": 2.0,
              "ttft_seconds{mode=greedy}_count": 5,
              "ttft_seconds{mode=greedy}_avg": 0.01,
              "queue_depth{scheduler=iter}": 3.0}
    after = {"spec_verify_steps_total": 25.0,          # counter: delta
             "prefix_cache_hits_total": 2.0,           # unchanged: drop
             "ttft_seconds{mode=greedy}_count": 9,
             "ttft_seconds{mode=greedy}_avg": 0.02,    # _avg: drop
             "queue_depth{scheduler=iter}": 1.0,       # gauge: final
             "compile_events_total{phase=decode}": 4}  # new series
    d = bench._metrics_delta(before, after)
    assert d == {"spec_verify_steps_total": 15.0,
                 "ttft_seconds{mode=greedy}_count": 4,
                 "queue_depth{scheduler=iter}": 1.0,
                 "compile_events_total{phase=decode}": 4}


def test_metrics_delta_stays_off_the_compact_line():
    """The delta rides the matrix rows but stays off the compact driver
    line (_COMPACT_DROP)."""
    assert "metrics_delta" in bench._COMPACT_DROP
    from llm_sharding_demo_tpu.utils.metrics import REGISTRY
    before = REGISTRY.snapshot()
    REGISTRY.inc("generate_requests_total", mode="greedy")
    assert bench._metrics_delta(before, REGISTRY.snapshot()) == {
        "generate_requests_total{mode=greedy}": 1.0}
