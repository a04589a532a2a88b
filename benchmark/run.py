"""One run of one cell.

``python -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: brings the system up on the chip this process is given,
warms every program the cell's window can meet, measures one window,
checks the tokens that window served against the plain reference, and
prints as its last line one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device`` and, traced, ``breakdown``.

There is no CPU fallback: without a TPU of a kind in ``peaks.json``, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result. ``--rehearse`` (CPU, tiny sizes, the tests) runs the same code
and prints a line that says ``"platform": "cpu"``.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()          # set-up counts from here

import argparse                    # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402
import tempfile                    # noqa: E402
import types                       # noqa: E402


def _args(argv):
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow the CPU (tests and rehearsals only)")
    ap.add_argument("--benchmark-json", default=None,
                    help="another BENCHMARK.json (tests)")
    ap.add_argument("--describe-trace", default=None,
                    help="write what the trace holds to this file")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="by hand, when a limit of `correct` is set: also "
                         "print the controls' readings (PERF.md, section 2)")
    return ap.parse_args(argv)


def device_or_exit(spec, chips: int, rehearse: bool):
    import jax
    devs = jax.local_devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if not rehearse:
        if platform != "tpu":
            sys.exit(f"benchmark: JAX found platform {platform!r}, not a "
                     "TPU; there is no CPU fallback")
        if len(devs) < chips:
            sys.exit(f"benchmark: the cell needs {chips} chips, JAX sees "
                     f"{len(devs)}")
    peaks = spec.peaks(kind) if platform == "tpu" else None
    return {"platform": platform, "kind": kind, "count": chips}, peaks


def main(argv=None) -> int:
    args = _args(argv)
    from .harness import server, stats, traffic as traffic_mod, window, xtrace
    from .harness.spec import REPO, Spec, resolve

    spec = Spec(args.benchmark_json)
    cell_entry = spec.workload(args.workload)
    config = spec.config(cell_entry["config"])
    traffic = spec.traffic(cell_entry["traffic"])
    cell = spec.cell(args.workload)
    device, peaks = device_or_exit(spec, cell_entry["chips"], args.rehearse)
    cache_dir = server.configure_jax(REPO)
    compiles = server.CompileCounter()
    tmp = tempfile.mkdtemp(prefix="benchmark-")
    spans = os.path.join(spec.root, "layer_metrics", "host_spans.json") \
        if args.trace else None
    served = server.Served(config, args.seed, spans)
    vocab = served.model_config.vocab_size
    try:
        kernel = getattr(served.engine, "_decode_kernel", None)
        print(f"up: weights {served.init_s:.1f}s, create_app+serve "
              f"{served.create_s:.1f}s; decode kernel {kernel!r}; "
              f"compile cache {cache_dir}", flush=True)
        if not args.rehearse and kernel not in ("mega", "device"):
            sys.exit(f"benchmark: the engine resolved decode kernel "
                     f"{kernel!r}, not a compiled Pallas kernel")

        # -- warm -----------------------------------------------------------
        sizes = traffic_mod.sizes(
            traffic, traffic_mod.count(cell["rate_rps"], args.seconds))
        warmed = resolve(config["warmup"])(served, traffic, sizes, vocab)
        # the head of the window's own trace, with other token contents
        w = window.run(served, traffic, -1 - args.seed, cell["rate_rps"],
                       cell.get("warmup_s", 6.0), tmp, "warm", vocab)
        print("warm: " + json.dumps({k: round(v, 1) for k, v in
                                     warmed.items()})
              + f", replay of {len(w.rows)} requests "
              f"{w.seconds + w.drain_s:.1f}s "
              f"({sum(not r['ok'] for r in w.rows)} failed)", flush=True)
        built = compiles.mark()
        named = len(compiles.names)
        print(f"set-up built or loaded {built[0]} programs, "
              f"{built[1]:.1f}s inside XLA's compile, {built[2]} cache hits",
              flush=True)

        # -- measure --------------------------------------------------------
        setup_s = time.perf_counter() - _T0
        slice_ = None
        if args.trace:
            length = min(cell.get("trace_seconds", 3.0), args.seconds / 2)
            slice_ = (args.seconds * 0.4, length)
        win = window.run(served, traffic, args.seed, cell["rate_rps"],
                         args.seconds, tmp, "run", vocab, trace_slice=slice_,
                         sample_s=0.1 if args.trace else None,
                         timeout_s=cell.get("drain_s", 120.0))
        in_window = compiles.since(built)
        rows = win.rows
        # above its knee a cell ends with a backlog by design: a request
        # still out at the drain limit has not failed, it has not finished
        unfinished = sum(r["unfinished"] for r in rows) \
            if cell.get("above_knee") else 0
        failed = sum(not r["ok"] for r in rows) - unfinished
        print(f"window: {len(rows)} requests offered at "
              f"{cell['rate_rps']} req/s over {args.seconds:g}s, {failed} "
              f"failed, {unfinished} left unfinished, waited "
              f"{win.drain_s:.1f}s after it closed; "
              f"{in_window[0]} programs compiled inside it "
              f"{compiles.names[named:][:8]}", flush=True)
        late = stats.summary(rows, "lateness_ms")
        print(f"generator lateness ms: p50 {late['p50']:.3f} p95 "
              f"{late['p95']:.3f} max {late['max']:.3f}")
        for key in ("ttft_ms", "tpot_ms", "latency_ms"):
            s = stats.summary(rows, key)
            if s["n"]:
                print(f"{key}: n {s['n']} p50 {s['p50']:.3f} p95 "
                      f"{s['p95']:.3f} max {s['max']:.3f}")
        for r in [r for r in rows if not r["ok"] and not r["unfinished"]][:5]:
            print(f"failed: {r['rid']} status {r['status']} {r['error']}")
        print("counters over the window: " + json.dumps(
            {k: v - win.counters_before.get(k, 0)
             for k, v in sorted(win.counters_after.items())
             if v != win.counters_before.get(k, 0)}))

        every = stats.end_to_end(rows, args.seconds)
        every["setup_s"] = setup_s
        # the system's own peak: read before the reference computes
        device["memory_peak_bytes"] = served.memory_peak_bytes()

        # -- correct --------------------------------------------------------
        t0 = time.perf_counter()
        lim = config["check"]
        got = resolve(lim["procedure"])(served, win.arrivals, rows,
                                        control=bool(args.control))
        correct = all(got["readings"][k] <= v
                      for k, v in lim["limits"].items())
        print("check: " + ", ".join(
            f"{k} {got['readings'][k]:.6g} (limit {v})"
            for k, v in lim["limits"].items())
            + f"; {got['tokens']} tokens of {got['requests']} requests, "
            f"{time.perf_counter() - t0:.1f}s", flush=True)
        if args.control:
            print("control: " + json.dumps(got.get("control")), flush=True)
        result = {"correct": bool(correct), "attempted": len(rows),
                  "failed": failed}
        if not args.trace:
            wanted = spec.metrics("end_to_end", args.workload)
            result["metrics"] = {m["name"]: {"value": every[m["name"]],
                                             "unit": m["unit"]}
                                 for m in wanted if m["name"] in every}
        else:
            trace = None
            if win.trace_dir and win.trace_unix:
                trace = xtrace.load(xtrace.newest_xplane(win.trace_dir),
                                    served.host_span_names)
                if args.describe_trace:
                    os.makedirs(os.path.dirname(args.describe_trace) or ".",
                                exist_ok=True)
                    with open(args.describe_trace, "w") as f:
                        json.dump(xtrace.describe(trace), f, indent=1)
                if trace.devices:
                    busy, span = xtrace.busy_and_window_s(trace)
                    device["busy_s"], device["window_s"] = busy, span
                    result["breakdown"] = xtrace.breakdown(trace)
            ctx = types.SimpleNamespace(
                rows=rows, window_traces=win.window_traces,
                counters_before=win.counters_before,
                counters_after=win.counters_after, samples=win.samples,
                trace=trace, trace_unix=win.trace_unix, seconds=args.seconds,
                seg_steps=getattr(served.scheduler, "seg_steps", 1),
                config=config, peaks=peaks,
                bytes_model=resolve(config["bytes_model"])(config))
            result["metrics"] = {}
            for m in spec.metrics("per_layer", args.workload):
                v = spec.reader(m["name"])(ctx)
                if v is not None:
                    result["metrics"][m["name"]] = {"value": v,
                                                    "unit": m["unit"]}
            print("end to end in this traced run (not the judged one): "
                  + json.dumps(every))
        result["device"] = device
        print(json.dumps(result), flush=True)
    finally:
        served.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the scheduler's daemon threads can abort the interpreter's own
    # teardown (a known artifact of the program); every child has been
    # waited for and every file closed by now
    os._exit(rc)
