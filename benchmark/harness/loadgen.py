"""The load generator: a child process that never imports JAX.

The server process holds the chip and most of a core; the generator
gets its own interpreter. It reads a schedule file, answers ``READY``,
waits for ``GO`` on its standard input (the instant the window opens in
both processes), then sends each request over loopback HTTP when it is
due, from a small pool of sender threads, and writes one record per
request: when it was due, when it was sent, when the answer was
complete (all as offsets from ``GO``), the status and the text. It waits
``drain_s`` after the last arrival for answers still out; one that has
not come by then is recorded as unfinished.

Run as ``python -m benchmark.harness.loadgen <schedule.json> <out.json>``.
"""

from __future__ import annotations

import http.client
import json
import queue
import sys
import threading
import time
from typing import List


def _send(host: str, port: int, item: dict, t0: float, timeout: float) -> dict:
    rec = {"k": item["k"], "rid": item["rid"], "due": item["t"],
           "max_new": item["max_new"], "n_prompt": item["n_prompt"],
           "status": None, "error": None, "done": None, "text": None}
    body = json.dumps({"prompt": item["prompt"], "mode": "greedy",
                       "max_new_tokens": item["max_new"]}).encode()
    rec["sent"] = time.perf_counter() - t0
    try:
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            conn.request("POST", "/generate", body=body, headers={
                "Content-Type": "application/json",
                "X-Request-ID": item["rid"]})
            resp = conn.getresponse()
            payload = resp.read()
            rec["done"] = time.perf_counter() - t0
            rec["status"] = resp.status
        finally:
            conn.close()
        doc = json.loads(payload)
        if "generated" in doc:
            rec["text"] = doc["generated"]
        else:
            rec["error"] = json.dumps(doc)[:300]
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    return rec


def run(plan: dict) -> List[dict]:
    items = plan["arrivals"]
    host, port = plan["host"], plan["port"]
    todo: "queue.Queue" = queue.Queue()
    out, lock = [], threading.Lock()

    def worker():
        while True:
            item = todo.get()
            if item is None:
                return
            rec = _send(host, port, item, t0, plan["timeout_s"])
            with lock:
                out.append(rec)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(plan["senders"])]
    for t in threads:
        t.start()
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        raise SystemExit("loadgen: expected GO")
    t0 = time.perf_counter()
    for item in items:
        wait = item["t"] - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        todo.put(item)
    for _ in threads:
        todo.put(None)
    deadline = time.monotonic() + plan["drain_s"]
    for t in threads:
        t.join(max(deadline - time.monotonic(), 0.01))
    with lock:
        seen = {r["k"] for r in out}
        for item in items:            # a sender that never came back
            if item["k"] not in seen:
                out.append({"k": item["k"], "rid": item["rid"],
                            "due": item["t"], "sent": item["t"],
                            "max_new": item["max_new"],
                            "n_prompt": item["n_prompt"], "status": None,
                            "error": "unfinished at the drain limit",
                            "done": None, "text": None})
        return sorted(out, key=lambda r: r["k"])


def main(argv: List[str]) -> int:
    with open(argv[1], encoding="utf-8") as f:
        plan = json.load(f)
    records = run(plan)
    with open(argv[2], "w", encoding="utf-8") as f:
        json.dump(records, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
