"""bench_diff: perf-regression gating over the bench trajectory.

The driver has journaled a ``BENCH_rNN.json`` row per round since round
1 — and nothing ever read them back: a throughput regression would land
in the trajectory and sit there unflagged. This tool closes that loop:

    python tools/bench_diff.py --current BENCH_full.json
                               [--history 'BENCH_r*.json']
                               [--threshold 0.25] [--json]

- **current** is a bench payload (the ``bench.py`` full-matrix artifact:
  headline ``metric``/``value`` plus per-config rows);
- **history** is the committed trajectory (``BENCH_rNN.json`` driver
  rows, each wrapping a ``parsed`` payload; rounds whose payload is
  null/skipped contribute nothing);
- every numeric metric the two sides share is classified by name
  (throughput-like: higher is better; latency-like: lower is better;
  unclassifiable names are reported but never gated) and compared
  against the LATEST prior value with a relative threshold. A gated
  metric moving past its threshold in the bad direction is a
  regression: nonzero exit, wired into the in-suite driver
  (tests/test_graftscope.py) so a committed artifact that regresses the
  trajectory fails CI rather than aging silently.

The default threshold is deliberately loose (25%): round-to-round noise
is real; the gate exists for
step-function regressions (a donated-buffer copy re-appearing, a
compile storm, a scheduler serialization), not single-digit drift —
the drift story is the journaled rows themselves.

bench.py journals the verdict as the ``bench_diff`` config row beside
``graftcheck_static_analysis``, so every committed matrix carries its
own comparison against the trajectory that preceded it.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

DEFAULT_THRESHOLD = 0.25

# per-metric threshold overrides (relative). Fixed per-request costs
# dominate the headline's sub-second workload — keep its gate loose.
THRESHOLDS: Dict[str, float] = {
    "headline.value": 0.35,
}

# name-suffix/substring classification: which direction is "worse".
_HIGHER_BETTER = ("tokens_per_sec", "tokens_per_second", "speedup",
                  "vs_baseline", "mfu", "cache_speedup",
                  "accepted_tokens_per_verify", "success_rate",
                  # timeline_overhead row (grafttime): a slower event
                  # bus regresses DOWNWARD in emit throughput
                  "events_per_sec",
                  # graftload rows: goodput-under-SLO and declared-SLO
                  # attainment regress DOWNWARD (fewer requests inside
                  # their declared budgets)
                  "goodput", "slo_attainment",
                  # graftfleet rows: a regressing router scatters warm
                  # prefixes (affinity hit rate drops) and an emptier
                  # batch at the same offered load means admission or
                  # scheduling got worse, not better
                  "affinity_hit_rate", "batch_occupancy",
                  # numerics_oracle row (graftnum): greedy argmax
                  # agreement of an approximate path with its f32
                  # sibling regresses DOWNWARD (checked before the
                  # lower-better list so the metric never falls through
                  # to a latency-ish suffix match)
                  "top1_agreement",
                  # kv_quant_capacity row (quantized KV blocks): rows
                  # admitted before the first preemption, the int8/f32
                  # admitted-row ratio at equal pool bytes, and the
                  # prefix-store depth all regress DOWNWARD — fewer
                  # resident rows per HBM byte
                  "before_first_preemption", "capacity_ratio",
                  "prefix_store_depth",
                  # tiered_kv_depth row (grafttier): the ledger-measured
                  # host/device depth ratio and the replayed-epoch
                  # prefix/promoted hit rates all regress DOWNWARD —
                  # less prefix state resident per device byte, or a
                  # tier that stopped answering affinity hits (the
                  # promote-stall side is the _ms suffix, lower-better)
                  "depth_ratio", "prefix_hit_rate", "promoted_hit_rate",
                  # trend_detection row (grafttrend): the seeded burst
                  # is pinned, so a reducer that stops tripping on it
                  # went blind — detection regresses DOWNWARD
                  "burst_detected")
_LOWER_BETTER = ("_ms", "latency", "step_ms", "prefill_ms",
                 # traffic_mix occupancy join: deeper queues at the
                 # same offered rate = the serving stack fell behind
                 "queue_depth",
                 # plan_switch row (graftwatch): compiled programs
                 # minted past the pre-certified plan set — the pinned
                 # invariant is ZERO, so any upward drift is a
                 # certified-envelope leak, the worst kind of
                 # regression a live re-planner can have
                 "recompile",
                 # timeline_overhead row (grafttime): the bus-armed vs
                 # bus-off wall ratio drifting up means the always-on
                 # timeline started taxing the decode path
                 "overhead_factor",
                 # numerics_oracle row (graftnum): per-position logit
                 # MSE of an approximate path vs its f32 sibling —
                 # upward drift means the quantizer/bf16 discipline
                 # lost precision (also caught by the "_ms" suffix,
                 # but the explicit name documents the intent)
                 "logit_mse",
                 # hbm_attribution row (graftmem): |measured/modeled - 1|
                 # byte drift between the live ledger and the cost
                 # model's aval arithmetic — f32 configs pin at exactly
                 # 0.0 and the int8 pool's designed savings is constant
                 # for fixed geometry, so ANY upward movement means the
                 # ledger lost an allocation or the model lost a term
                 "drift",
                 # trend_detection row (grafttrend): alerts fired
                 # during the QUIET serial phases of the pinned mix —
                 # a watch that pages on healthy traffic is worse than
                 # no watch at all
                 "false_positive")
# environment properties, not code performance: the dispatch RTT, the
# reference CPU's own rate, and the attribution run's host-dependent
# byte rates vary by machine/route — comparing them across rounds would
# gate the weather, not the code (they still ride the rows report-only)
_NOT_GATED = ("transfer_rtt", "rtt_bound", "ref_cpu", "baseline_cpu",
              "implied_bytes_per_second", "seconds_per_token")


def classify(field: str) -> Optional[str]:
    """'higher' | 'lower' | None (not gated). ``headline.value`` is the
    round's tokens/sec headline — always gated higher-better."""
    f = field.lower()
    if any(s in f for s in _NOT_GATED):
        return None
    if f in ("value", "headline.value"):
        return "higher"
    if any(s in f for s in _HIGHER_BETTER):
        return "higher"
    if any(s in f for s in _LOWER_BETTER):
        return "lower"
    return None


def extract_metrics(payload: dict) -> Dict[str, float]:
    """Flatten a bench payload into ``{"cfg.field": value}`` numeric
    rows plus the headline ``headline.value``. Skips error/skip rows
    and non-scalar fields."""
    out: Dict[str, float] = {}
    if not isinstance(payload, dict):
        return out
    for field, v in payload.items():
        # top-level numeric fields are the round's headline block
        # (value, vs_baseline, latency context); early rounds carried
        # their whole matrix there, so flattening them keeps the oldest
        # trajectory comparable
        if field in ("configs", "n", "batch"):
            continue
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[f"headline.{field}"] = float(v)
    for cfg in payload.get("configs") or ():
        if not isinstance(cfg, dict):
            continue
        name = cfg.get("name")
        if not name or cfg.get("error") or cfg.get("skipped"):
            continue
        for field, val in cfg.items():
            if field in ("name", "note", "metrics_delta"):
                continue
            if isinstance(val, (int, float)) and not isinstance(val, bool):
                out[f"{name}.{field}"] = float(val)
            elif field == "workloads" and isinstance(val, list):
                # nested per-workload rows (the graftscope_attribution
                # journal shape): flatten so the drift trajectory is
                # comparable across rounds (host-dependent rates stay
                # report-only via _NOT_GATED)
                for row in val:
                    if not isinstance(row, dict):
                        continue
                    wl = row.get("workload")
                    for f2, v2 in row.items():
                        if wl and isinstance(v2, (int, float)) \
                                and not isinstance(v2, bool):
                            out[f"{name}.{wl}.{f2}"] = float(v2)
    return out


def skipped_configs(payload: dict) -> Dict[str, str]:
    """Config names whose row was SKIPPED with a reason. These rows
    contribute no gated metrics — which
    used to be silent: a trajectory where every on-chip row skips
    still exited 0 and read as "gated". ``compare`` now reports them
    as ``ungated_rows`` with their reasons, and ``--no-skips`` turns
    any of them into a nonzero exit so CI does not green-light an
    ungated run."""
    out: Dict[str, str] = {}
    for cfg in (payload or {}).get("configs") or ():
        if isinstance(cfg, dict) and cfg.get("name") \
                and cfg.get("skipped"):
            out[cfg["name"]] = str(cfg["skipped"])
    return out


def error_configs(payload: dict) -> set:
    """Config names whose row ERRORED — what ``compare`` uses to turn a
    config that stopped producing numbers into a finding instead of a
    silent gap. Skip rows (``skipped``) are
    deliberately excluded: a skip is environment, not a crash, and the
    trajectory is honestly full of them."""
    out = set()
    for cfg in (payload or {}).get("configs") or ():
        if isinstance(cfg, dict) and cfg.get("name") and cfg.get("error"):
            out.add(cfg["name"])
    return out


def load_history(paths: List[str]) -> List[Tuple[str, Dict[str, float]]]:
    """[(label, metrics)] oldest-first. Driver rows wrap the payload in
    ``parsed`` (null when the round's output didn't parse — those rows
    contribute nothing, honestly)."""
    rows: List[Tuple[int, str, Dict[str, float]]] = []
    for i, path in enumerate(sorted(paths)):
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        wrapped = isinstance(doc, dict) and "parsed" in doc
        payload = doc.get("parsed") if wrapped else doc
        metrics = extract_metrics(payload or {})
        # only driver rows carry a round number; a raw payload's "n"
        # would be some unrelated field (e.g. a token count) and
        # sorting on it would misorder the trajectory — raw files keep
        # their sorted-glob position
        n = doc.get("n", i) if wrapped else i
        if metrics:
            rows.append((int(n), os.path.basename(path), metrics))
    rows.sort()
    return [(label, m) for _, label, m in rows]


def compare(current: Dict[str, float],
            history: List[Tuple[str, Dict[str, float]]],
            threshold: float = DEFAULT_THRESHOLD,
            current_errors: Optional[set] = None,
            current_skips: Optional[Dict[str, str]] = None) -> dict:
    """Join current metrics against the latest prior value per metric.
    Returns the JSON-able verdict payload; ``ok`` is False iff any
    gated metric regressed past its threshold — or a config that
    produced gated numbers in the latest prior run now ERRORS
    (``current_errors``): a config dying outright is the worst
    regression, not a silent gap in the join."""
    rows: List[dict] = []
    regressions: List[str] = []
    for name in sorted(current_errors or ()):
        prior_fields = sorted(
            m for label, metrics in history[-1:] for m in metrics
            if m.startswith(name + ".")
            and classify(m.rpartition(".")[2]) is not None)
        if prior_fields:
            rows.append({"metric": name, "status": "regression",
                         "error": "config errored this run; its gated "
                                  f"metrics vanished: {prior_fields}"})
            regressions.append(name)
    for metric in sorted(current):
        prior = prior_run = None
        for label, metrics in reversed(history):
            if metrics.get(metric) is not None:
                prior, prior_run = metrics[metric], label
                break
        if prior is None:
            rows.append({"metric": metric, "current": current[metric],
                         "status": "no-prior"})
            continue
        direction = classify(metric.rpartition(".")[2] or metric)
        thr = THRESHOLDS.get(metric, threshold)
        delta = (current[metric] - prior) / abs(prior) if prior else 0.0
        row = {"metric": metric, "current": current[metric],
               "prior": prior, "prior_run": prior_run,
               "delta_pct": round(delta * 100, 2)}
        if direction is None:
            row["status"] = "not-gated"
        elif (direction == "higher" and delta < -thr) \
                or (direction == "lower" and delta > thr):
            row["status"] = "regression"
            row["threshold_pct"] = round(thr * 100, 1)
            regressions.append(metric)
        else:
            row["status"] = "ok"
        rows.append(row)
    return {
        "ok": not regressions,
        "threshold": threshold,
        "compared": sum(1 for r in rows if r["status"] in
                        ("ok", "regression")),
        "regressions": regressions,
        # skip-with-reason rows: environment-honest but UNGATED — they
        # never fail the default run, but they must not vanish either
        # (--no-skips promotes their presence to a nonzero exit)
        "ungated_rows": [{"config": name, "reason": reason}
                         for name, reason in
                         sorted((current_skips or {}).items())],
        # the --no-skips verdict as DATA: ok AND nothing ungated — the
        # journaled bench_diff row carries it, so a run whose rows
        # skipped is loud in the row payload itself, not only behind
        # the opt-in CLI flag
        "no_skips_ok": (not regressions) and not (current_skips or {}),
        "history_runs": [label for label, _ in history],
        "rows": rows,
    }


def main(argv: Optional[List[str]] = None) -> int:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(
        prog="python tools/bench_diff.py",
        description="flag perf regressions against the committed "
                    "BENCH_* trajectory (exit 1 on regression)")
    ap.add_argument("--current", required=True,
                    help="bench payload to gate (a full matrix written "
                    "by bench.py)")
    ap.add_argument("--history",
                    default=os.path.join(here, "BENCH_r*.json"),
                    help="glob of prior trajectory rows")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="relative regression threshold (default 0.25)")
    ap.add_argument("--no-skips", action="store_true",
                    help="exit nonzero when any config row was skipped "
                    "with a reason (ungated_rows) — CI mode: an ungated "
                    "run must not read as a gated one")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    try:
        with open(args.current, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench_diff: cannot read --current {args.current}: {e}",
              file=sys.stderr)
        return 2
    payload = doc.get("parsed") if isinstance(doc, dict) \
        and "parsed" in doc else doc
    current = extract_metrics(payload or {})
    history = load_history(glob.glob(args.history))
    verdict = compare(current, history, threshold=args.threshold,
                      current_errors=error_configs(payload or {}),
                      current_skips=skipped_configs(payload or {}))

    if args.json:
        print(json.dumps(verdict, indent=2))
    else:
        for r in verdict["rows"]:
            if r["status"] != "regression":
                continue
            if "error" in r:
                print(f"REGRESSION {r['metric']}: {r['error']}")
            else:
                print(f"REGRESSION {r['metric']}: {r['prior']} "
                      f"({r['prior_run']}) -> {r['current']} "
                      f"({r['delta_pct']}% past the "
                      f"{r['threshold_pct']}% gate)")
        for row in verdict["ungated_rows"]:
            print(f"UNGATED {row['config']}: skipped — {row['reason']}")
        print(f"bench_diff: {verdict['compared']} metric(s) compared "
              f"against {len(verdict['history_runs'])} prior run(s), "
              f"{len(verdict['regressions'])} regression(s), "
              f"{len(verdict['ungated_rows'])} ungated skip row(s)")
    if args.no_skips:
        return 0 if verdict["no_skips_ok"] else 1
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
