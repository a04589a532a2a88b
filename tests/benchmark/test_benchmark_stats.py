"""Percentiles and timing from the due instant, with a late generator."""

import pytest

from benchmark.harness import stats


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 50, 5),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 95, 10),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90, 9),
    (list(range(1, 101)), 95, 95),
    ([7], 95, 7),
    ([3, 1, 2], 0, 1),
])
def test_nearest_rank_percentile(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 95) is None


def test_spread_is_the_contracts():
    import statistics
    v = [10.0, 10.2, 10.4, 10.1, 9.9, 10.3]
    q = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q[2] - q[0]) / statistics.median(v))


def _trace(rid, queue_ms, prefill_ms, total_ms, new, reused=0, prompt=64):
    labels = {"prefix_hit": bool(reused)}
    if reused:
        labels["reused_tokens"] = reused
    return {"request_id": rid, "duration_ms": total_ms, "started_unix": 0.0,
            "labels": {"new_tokens": new, "prompt_tokens": prompt},
            "spans": [{"name": "tokenize", "start_ms": 0.0, "duration_ms": 0.1},
                      {"name": "queue_wait", "start_ms": 0.1,
                       "duration_ms": queue_ms - 0.1},
                      {"name": "prefill", "start_ms": queue_ms,
                       "duration_ms": prefill_ms, "labels": labels},
                      {"name": "decode", "start_ms": queue_ms + prefill_ms,
                       "duration_ms": 1.0, "labels": {"steps": 32}}]}


def _client(rid, due, sent, done, status=200, max_new=11, error=None,
            returned=None):
    """An answer of 3 prompt tokens and ``returned`` (or ``max_new``) more."""
    n = 3 + (max_new if returned is None else returned)
    return {"k": 0, "rid": rid, "due": due, "sent": sent, "done": done,
            "status": status, "error": error, "max_new": max_new,
            "n_prompt": 3, "text": " ".join(["1"] * n) if done else None}


def test_a_late_generator_is_charged_to_the_request():
    # due at 1.000 s, sent 40 ms late; the server saw 30 ms of queue, a
    # 20 ms prefill and 150 ms in all for 11 tokens; answered at 1.200 s
    rows = stats.join([_client("a", 1.0, 1.04, 1.2)],
                      {"a": _trace("a", 30.0, 20.0, 150.0, 11)})
    r = rows[0]
    assert r["lateness_ms"] == pytest.approx(40.0)
    assert r["ttft_ms"] == pytest.approx(40.0 + 30.0 + 20.0)
    assert r["tpot_ms"] == pytest.approx((150.0 - 50.0) / 10)
    assert r["latency_ms"] == pytest.approx(200.0)
    assert r["queue_ms"] == pytest.approx(30.0)
    every = stats.end_to_end(rows, 10.0)
    assert every["tpot_p50_ms"] == every["tpot_p95_ms"] == pytest.approx(10.0)


def test_a_failed_request_counts_as_the_worst():
    client = [_client(f"r{i}", 0.0, 0.0, 0.1) for i in range(19)]
    client.append(_client("bad", 0.0, 0.0, None, status=503, error="x"))
    traces = {f"r{i}": _trace(f"r{i}", 1.0, 9.0 + i, 100.0, 11)
              for i in range(19)}
    rows = stats.join(client, traces)
    # 20 requests: the 95th percentile is the 19th value; the failure is
    # ranked with the worst seen (28 ms), so the 19th is the slowest success
    assert stats.tail(rows, "ttft_ms") == pytest.approx(10.0 + 18)
    ok_only = stats.percentile([r["ttft_ms"] for r in rows if r["ok"]], 95)
    assert ok_only == pytest.approx(10.0 + 18)
    client += [_client(f"bad{i}", 0.0, 0.0, None, status=503, error="x")
               for i in range(3)]
    assert stats.tail(stats.join(client, traces), "ttft_ms") == \
        pytest.approx(28.0)


def test_tokens_per_second_counts_completions_inside_the_window():
    client = [_client("a", 0.0, 0.0, 4.0, max_new=100),
              _client("b", 1.0, 1.0, 9.9, max_new=50),
              _client("c", 2.0, 2.0, 10.5, max_new=70)]   # after the window
    rows = stats.join(client, {})
    assert stats.end_to_end(rows, 10.0)["out_tokens_per_s"] == 15.0


def test_tokens_per_second_counts_the_tokens_returned_not_those_asked_for():
    rows = stats.join([_client("a", 0.0, 0.0, 4.0, max_new=100, returned=40)],
                      {})
    assert rows[0]["returned_tokens"] == 40
    assert stats.end_to_end(rows, 10.0)["out_tokens_per_s"] == 4.0


def test_prefix_share_and_queue_wait_readers():
    import types
    from benchmark.readers import requests
    rows = stats.join(
        [_client("a", 0.0, 0.0, 0.2), _client("b", 0.0, 0.01, 0.3)],
        {"a": _trace("a", 1.0, 5.0, 190.0, 11, reused=0, prompt=192),
         "b": _trace("b", 1.0, 5.0, 280.0, 11, reused=128, prompt=192)})
    ctx = types.SimpleNamespace(rows=rows)
    assert requests.prefix_token_share(ctx) == pytest.approx(100 * 128 / 384)
    assert requests.queue_wait_p95_ms(ctx) == pytest.approx(1.0)
