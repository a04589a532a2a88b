"""From client records and server span trees to end-to-end numbers.

Every timing counts from the instant the request was DUE, not from the
instant the generator got round to sending it: ``lateness = sent - due``
is added to what the server measured from arrival, so a stall makes the
requests behind it slower, as it does for users, and only durations
cross the process boundary (no clock is shared).
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (the smallest value with at least ``q``
    percent of the sample at or below it)."""
    vs = sorted(values)
    if not vs:
        return None
    rank = max(math.ceil(q / 100.0 * len(vs)), 1)
    return vs[min(rank, len(vs)) - 1]


def spread(values: List[float]) -> float:
    """Interquartile distance over the median (the contract's spread)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def find_spans(spans: List[dict], name: str) -> List[dict]:
    out = []
    for s in spans:
        if s["name"] == name:
            out.append(s)
        out.extend(find_spans(s.get("spans", []), name))
    return out


def join(client: List[dict], traces: Dict[str, dict]) -> List[dict]:
    """One row per request sent: the client's record joined with the
    server's span tree by request id. Durations in milliseconds."""
    rows = []
    for c in client:
        row = dict(c)
        row["lateness_ms"] = (c["sent"] - c["due"]) * 1e3
        row["ok"] = c["status"] == 200 and c.get("error") is None
        row["unfinished"] = c.get("error") == "unfinished at the drain limit"
        row["latency_ms"] = ((c["done"] - c["due"]) * 1e3
                             if c.get("done") is not None else None)
        # tokens actually returned, counted from the answer's text
        row["returned_tokens"] = max(
            len((c.get("text") or "").split()) - c["n_prompt"], 0)
        tr = traces.get(c["rid"])
        if row["ok"] and tr is not None and "error" not in tr.get("labels", {}):
            lab = tr.get("labels", {})
            pre = find_spans(tr["spans"], "prefill")
            row["server_ms"] = tr["duration_ms"]
            row["new_tokens"] = lab.get("new_tokens")
            if pre:
                first = pre[0]
                ttft_srv = first["start_ms"] + first["duration_ms"]
                row["queue_ms"] = first["start_ms"]
                row["ttft_ms"] = row["lateness_ms"] + ttft_srv
                n = row["new_tokens"] or 0
                if n > 1:
                    row["tpot_ms"] = (tr["duration_ms"] - ttft_srv) / (n - 1)
            row["reused_tokens"] = sum(
                s.get("labels", {}).get("reused_tokens", 0) for s in pre)
            row["prompt_tokens"] = lab.get("prompt_tokens")
        rows.append(row)
    return rows


def tail(rows: List[dict], key: str, q: float = 95.0) -> Optional[float]:
    """The ``q``th percentile of ``key`` over ALL requests sent: one that
    failed, or has no such timing, counts as the worst."""
    vals = [r.get(key) if r["ok"] else None for r in rows]
    if not vals:
        return None
    seen = [v for v in vals if v is not None]
    if not seen:
        return None
    worst = max(seen)
    return percentile([worst if v is None else v for v in vals], q)


def summary(rows: List[dict], key: str) -> dict:
    vals = [r[key] for r in rows if r["ok"] and r.get(key) is not None]
    return {"n": len(vals),
            "p50": percentile(vals, 50), "p95": percentile(vals, 95),
            "max": max(vals) if vals else None}


def end_to_end(rows: List[dict], seconds: float) -> Dict[str, float]:
    """Every end-to-end number the rows can give; the cell's entry in
    ``BENCHMARK.json`` says which of them it reports."""
    out = {}
    for name, key, q in (("ttft_p95_ms", "ttft_ms", 95.0),
                         ("tpot_p95_ms", "tpot_ms", 95.0),
                         ("tpot_p50_ms", "tpot_ms", 50.0),
                         ("latency_p95_ms", "latency_ms", 95.0)):
        v = tail(rows, key, q)
        if v is not None:
            out[name] = v
    inside = [r for r in rows if r["ok"] and r.get("done") is not None
              and r["done"] <= seconds]
    out["out_tokens_per_s"] = sum(r["returned_tokens"]
                                  for r in inside) / seconds
    return out
