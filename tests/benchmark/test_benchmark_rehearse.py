"""The whole command, end to end, at a tiny configuration on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.spec import REPO

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixture", "BENCHMARK.json")


def run(*extra, env=None):
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "tiny-llama.tiny", "--seconds", "2", "--benchmark-json", FIXTURE,
         *extra], cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "3",
             **(env or {})})


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_one_result_line_with_the_contracts_keys(trace):
    p = run("--seed", str(2**31 + 99), "--trace", str(trace), "--rehearse",
            "--control", str(1 - trace))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 12
    assert result["device"]["platform"] == "cpu"     # never a device metric
    names = set(result["metrics"])
    if trace:
        assert "queue_wait_p95_ms" in names and "setup_s" not in names
        assert "device_idle_share" not in names       # no device plane: left out
    else:
        assert names == {"tpot_p50_ms", "ttft_p95_ms", "latency_p95_ms",
                         "out_tokens_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in result["metrics"].values())
    assert any(l.startswith("check: deficit_mean") and "limit" in l
               for l in lines)
    assert any("programs compiled inside it" in l for l in lines)
    # the controls' readings are printed only when asked for, by hand
    assert any(l.startswith("control: {") for l in lines) is (not trace)


def test_without_a_tpu_there_is_no_result():
    p = run("--seed", "1", "--trace", "0")
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
    assert "no CPU fallback" in p.stderr
