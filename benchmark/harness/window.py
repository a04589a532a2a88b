"""One measured window: the generator child, the counters, the traced slice."""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from . import spec as spec_mod
from . import stats, traffic as traffic_mod


@dataclasses.dataclass
class Window:
    rows: List[dict]                       # one per request sent
    seconds: float
    counters_before: Dict[str, float]
    counters_after: Dict[str, float]
    samples: List[Dict[str, float]]
    window_traces: List[dict]              # server span trees of the rows
    trace_dir: Optional[str] = None
    trace_unix: Optional[tuple] = None     # (start, stop) of the traced slice
    go_perf: float = 0.0                   # perf_counter when the window opened
    drain_s: float = 0.0
    arrivals: list = dataclasses.field(default_factory=list)


def _plan(arrivals, port: int, tag: str, timeout_s: float) -> dict:
    return {"host": "127.0.0.1", "port": port, "timeout_s": timeout_s + 60,
            "drain_s": timeout_s,
            "senders": min(max(len(arrivals), 1), 768),
            "arrivals": [{"k": a.k, "t": a.t, "rid": f"{tag}-{a.k}",
                          "prompt": a.prompt, "max_new": a.max_new,
                          "n_prompt": len(a.prompt_ids)}
                         for a in arrivals]}


def run(served, traffic: dict, seed: int, rate_rps: float, seconds: float,
        tmp: str, tag: str, vocab: int, trace_slice: Optional[tuple] = None,
        sample_s: Optional[float] = None, timeout_s: float = 120.0) -> Window:
    """Offer ``rate_rps`` for ``seconds`` from a child process and wait
    up to ``timeout_s`` after the window for answers still out.
    ``trace_slice=(start_s, length_s)`` runs the profiler over that part
    of the window."""
    arrivals = traffic_mod.schedule(traffic, seed, rate_rps, seconds, vocab)
    plan_path = os.path.join(tmp, f"{tag}.plan.json")
    out_path = os.path.join(tmp, f"{tag}.out.json")
    with open(plan_path, "w", encoding="utf-8") as f:
        json.dump(_plan(arrivals, served.port, tag, timeout_s), f)
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    child = subprocess.Popen(
        [sys.executable, "-m", "benchmark.harness.loadgen", plan_path,
         out_path], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        cwd=spec_mod.REPO, env=env, text=True)
    samples: List[Dict[str, float]] = []
    stop = threading.Event()
    win = Window([], seconds, {}, {}, samples, [], arrivals=arrivals)
    try:
        if child.stdout.readline().strip() != "READY":
            raise RuntimeError("the load generator did not come up")
        win.counters_before = served.counters()
        child.stdin.write("GO\n")
        child.stdin.flush()
        win.go_perf = time.perf_counter()
        helpers = []
        if sample_s:
            def sample():
                while not stop.wait(sample_s):
                    samples.append(served.counters())
            helpers.append(threading.Thread(target=sample, daemon=True))
        if trace_slice:
            win.trace_dir = os.path.join(tmp, f"{tag}.trace")

            def traced():
                import jax
                if stop.wait(trace_slice[0]):
                    return
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                t0 = time.time()
                jax.profiler.start_trace(win.trace_dir, profiler_options=opts)
                stop.wait(trace_slice[1])
                jax.profiler.stop_trace()
                win.trace_unix = (t0, time.time())
            helpers.append(threading.Thread(target=traced, daemon=True))
        for h in helpers:
            h.start()
        rc = child.wait(timeout=seconds + timeout_s + 30)
        win.drain_s = time.perf_counter() - win.go_perf - seconds
        stop.set()
        for h in helpers:
            h.join(60)
        if rc != 0:
            raise RuntimeError(f"the load generator exited with {rc}")
    finally:
        stop.set()
        if child.poll() is None:
            child.kill()
            child.wait()
        for p in (child.stdin, child.stdout):
            if p:
                p.close()
    win.counters_after = served.counters()
    with open(out_path, encoding="utf-8") as f:
        client = json.load(f)
    traces = served.traces()
    win.rows = stats.join(client, traces)
    win.window_traces = [traces[c["rid"]] for c in client if c["rid"] in traces]
    return win
