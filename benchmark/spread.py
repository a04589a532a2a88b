"""Spread of repeated runs, as the contract measures it.

``python -m benchmark.spread <set-1 logs> -- <set-2 logs>``: reads the
last line of each run's log, and prints for each metric each set's
median and spread (the distance between the first and third quartile of
``statistics.quantiles(values, n=4)`` over the median) and the wider of
the two: about a fifth of the bound to set.
"""

from __future__ import annotations

import json
import statistics
import sys

from .harness.stats import spread


def last_line(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        lines = [l for l in f.read().splitlines() if l.startswith("{")]
    return json.loads(lines[-1])


def main(argv) -> int:
    sets, cur = [], []
    for a in argv[1:]:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    runs = [[last_line(p) for p in s] for s in sets if s]
    bad = [r for s in runs for r in s if not r["correct"] or r["failed"]]
    print(f"{sum(len(s) for s in runs)} runs, {len(bad)} not correct or "
          "with failed requests")
    for name in runs[0][0]["metrics"]:
        row = []
        for s in runs:
            vals = [r["metrics"][name]["value"] for r in s]
            # a side's first run compiles: its set-up is recorded apart
            use = vals[1:] if name == "setup_s" else vals
            row.append((statistics.median(use), spread(use), vals))
        widest = max(r[1] for r in row)
        print(f"{name}: widest spread {widest:.4f}; " + "; ".join(
            f"set {i + 1} median {m:.6g} spread {sp:.4f}"
            for i, (m, sp, _) in enumerate(row)))
        for i, (_, _, vals) in enumerate(row):
            print(f"   set {i + 1}: " + " ".join(f"{v:.6g}" for v in vals))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
