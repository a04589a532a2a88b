"""Find a cell's knee, once, when the cell is defined.

``python -m benchmark.sweep --workload <name> --rates 2,4,6,8 --seconds 20``:
one process and one set-up; each rate is offered for ``--seconds`` with
the cell's traffic, and the line printed for it says whether the system
kept up: how long the backlog took to drain after the window closed, the
tails, the tokens per second completed inside the window. The knee is
the highest rate whose backlog does not grow: its drain stays near one
request's own latency and its tails near those of the rate below. The
cell file records the sweep and the rate chosen (``0.8 x knee`` for a
cell judged by tails, above the knee for one judged by tokens per second).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--drain", type=float, default=30.0,
                    help="seconds to wait after a window; a backlog still "
                         "there ends the sweep")
    ap.add_argument("--log", default=None, help="append each line here too")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--benchmark-json", default=None)
    args = ap.parse_args(argv)
    from . import run as run_mod
    from .harness import server, stats, traffic as traffic_mod, window
    from .harness.spec import REPO, Spec, resolve

    spec = Spec(args.benchmark_json)
    entry = spec.workload(args.workload)
    config = spec.config(entry["config"])
    traffic = spec.traffic(entry["traffic"])
    run_mod.device_or_exit(spec, entry["chips"], args.rehearse)
    server.configure_jax(REPO)
    compiles = server.CompileCounter()
    tmp = tempfile.mkdtemp(prefix="benchmark-sweep-")
    served = server.Served(config, args.seed, None)
    vocab = served.model_config.vocab_size
    try:
        rates = [float(r) for r in args.rates.split(",")]
        sizes = traffic_mod.sizes(
            traffic, traffic_mod.count(max(rates), args.seconds))
        resolve(config["warmup"])(served, traffic, sizes, vocab)
        window.run(served, traffic, -1 - args.seed, rates[0], 6.0, tmp,
                   "warm", vocab)
        for i, rate in enumerate(rates):
            mark = compiles.mark()
            win = window.run(served, traffic, args.seed + i, rate,
                             args.seconds, tmp, f"r{i}", vocab,
                             timeout_s=args.drain)
            e2e = stats.end_to_end(win.rows, args.seconds)
            segments = win.counters_after.get("sched.segments", 0) - \
                win.counters_before.get("sched.segments", 0)
            line = json.dumps({
                "rate_rps": rate, "sent": len(win.rows),
                "failed": sum(not r["ok"] for r in win.rows),
                "drain_s": round(win.drain_s, 2),
                "latency_p50_ms": stats.summary(win.rows, "latency_ms")["p50"],
                **{k: round(v, 2) for k, v in e2e.items()},
                "segments": segments,
                "compiled_in_window": compiles.since(mark)[0],
                "memory_peak_bytes": served.memory_peak_bytes()})
            print(line, flush=True)
            if args.log:
                os.makedirs(os.path.dirname(args.log) or ".", exist_ok=True)
                with open(args.log, "a", encoding="utf-8") as f:
                    f.write(line + "\n")
            if any(r["unfinished"] for r in win.rows):
                print(f"stopping: the backlog of {rate} req/s did not drain "
                      f"in {args.drain:g}s", flush=True)
                break
    finally:
        served.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
