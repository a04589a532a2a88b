"""Ready instants and wait causes (utils.tracing.ReadyWaiter, the iter
scheduler's span trees, the serving metrics derived from them).

A ``prefill`` or ``decode`` span of the iteration scheduler covers a
DISPATCH; ``ready_ms`` is when the result existed. These tests hold the
stamping to its contract: every handed-over span is stamped before its
trace is kept, the scheduler thread never waits for it, all rows of a
segment share one instant, and the ``queue_wait`` span says what the
head of the queue waited for.
"""

import threading
import time

import jax
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.utils import tracing

WAIT_LABELS = ("closed_ms", "slot_ms", "pool_ms", "boundary_ms")


def _engine(max_seq=200):
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=256, n_embd=32,
                          n_layer=2, n_head=4)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    return DecodeEngine(params, cfg, max_seq=max_seq)


@pytest.fixture(scope="module")
def sched():
    return IterBatchingEngine(_engine(), max_batch=4, seg_steps=8,
                              max_wait_ms=0.0)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 211, size=(n,))


def _drive(ib, jobs, timeout=300):
    """``jobs``: ``(prompt, max_new, trigger, kwargs)``; a trigger is
    polled until true (event-driven arrivals). Returns each request's
    live ``RequestTrace``, settled the way the flight recorder would."""
    traces = [tracing.RequestTrace(f"r{i}") for i in range(len(jobs))]

    def run(tr, p, n, trigger, kw):
        deadline = time.monotonic() + 120
        while trigger is not None and not trigger() \
                and time.monotonic() < deadline:
            time.sleep(0.001)
        with tracing.use_trace(tr):
            ib.generate(p, n, **kw)
        tr.finish()

    threads = [threading.Thread(target=run, args=(tr, *job))
               for tr, job in zip(traces, jobs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
        assert not t.is_alive()
    return traces


def _after(ib, base, k):
    return lambda: ib.stats()["segments"] >= base + k


def _joined_pair(ib):
    base = ib.stats()["segments"]
    return _drive(ib, [(_prompt(5), 120, None, {}),
                       (_prompt(9, 1), 20, _after(ib, base, 1), {})])


def test_prefill_and_every_decode_span_is_ready_after_its_window(sched):
    for tr in _joined_pair(sched):
        d = tr.to_dict()
        spans = [s for s in d["spans"] if s["name"] in ("prefill", "decode")]
        assert {s["name"] for s in spans} == {"prefill", "decode"}
        for s in spans:
            assert s["labels"]["ready_ms"] >= \
                s["start_ms"] + s["duration_ms"] - 0.002, s


def test_rows_of_one_segment_share_seg_and_one_ready_instant(sched):
    a, b = _joined_pair(sched)
    by_seg_a = {s.labels["seg"]: s for s in a.find_all("decode")}
    shared = [(by_seg_a[s.labels["seg"]], s) for s in b.find_all("decode")
              if s.labels["seg"] in by_seg_a]
    assert shared, "the second request never rode a segment with the first"
    for sa, sb in shared:
        assert sa.ready == sb.ready and (sa.t0, sa.t1) == (sb.t0, sb.t1)
        assert sa.labels["batch"] == sb.labels["batch"]


def test_seg_rises_by_one_per_segment_and_batch_per_seeded_batch(sched):
    before = sched.stats()
    (a,) = _drive(sched, [(_prompt(5), 30, None, {})])
    (b,) = _drive(sched, [(_prompt(6, 2), 10, None, {})])
    segs = [s.labels["seg"] for t in (a, b) for s in t.find_all("decode")]
    assert segs == list(range(before["segments"],
                              before["segments"] + len(segs)))
    assert {s.labels["batch"] for s in a.find_all("decode")} == \
        {before["batches"]}
    assert {s.labels["batch"] for s in b.find_all("decode")} == \
        {before["batches"] + 1}


class _Held:
    """An array whose readiness the test decides."""

    def __init__(self):
        self.go = threading.Event()

    def block_until_ready(self):
        assert self.go.wait(60)
        return self


def test_a_trace_is_not_recorded_before_its_handover_is_stamped():
    waiter, rec = tracing.ReadyWaiter(), tracing.FlightRecorder()
    tr = tracing.RequestTrace("held")
    span = tr.add_span("prefill", tr.t0, tr.t0 + 0.001)
    held = _Held()
    waiter.hand(held, [(tr, span)])
    keeper = threading.Thread(target=rec.record, args=(tr,))
    keeper.start()
    time.sleep(0.1)
    assert keeper.is_alive() and len(rec) == 0 and span.ready is None
    released = time.perf_counter()
    held.go.set()
    keeper.join(30)
    assert not keeper.is_alive() and len(rec) == 1
    assert span.ready >= released
    got = rec.find("held")["spans"][0]["labels"]["ready_ms"]
    assert got == round((span.ready - tr.t0) * 1e3, 3)


def test_the_scheduler_does_not_wait_while_the_waiter_does(sched):
    """The process-wide waiter stuck on a held array: segments still
    dispatch, the request still finishes, and its own thread stamps."""
    held = _Held()
    tracing.READY.hand(held, [])
    try:
        before = sched.stats()["segments"]
        (tr,) = _drive(sched, [(_prompt(5), 20, None, {})], timeout=120)
        assert sched.stats()["segments"] - before >= 2
        assert not held.go.is_set()
        tr.settle()
        for s in tr.find_all("prefill") + tr.find_all("decode"):
            assert s.ready is not None and s.ready >= s.t1
    finally:
        held.go.set()


def test_waiter_stamps_in_fifo_order_and_runs_then_once_each():
    waiter = tracing.ReadyWaiter()
    tr = tracing.RequestTrace("fifo")
    first, second = _Held(), _Held()
    seen = []
    s1 = tr.add_span("decode", tr.t0, tr.t0)
    s2 = tr.add_span("decode", tr.t0, tr.t0)
    waiter.hand(first, [(tr, s1)], then=lambda at: seen.append(("a", at)))
    waiter.hand(second, [(tr, s2)], then=lambda at: seen.append(("b", at)))
    second.go.set()                    # the device never finishes out of
    time.sleep(0.05)                   # order; the waiter does not look
    assert s2.ready is None and not seen
    first.go.set()
    deadline = time.monotonic() + 30
    while len(seen) < 2 and time.monotonic() < deadline:
        time.sleep(0.001)
    assert [k for k, _ in seen] == ["a", "b"]
    assert seen[0][1] == s1.ready <= s2.ready == seen[1][1]


def _wait_span(tr):
    (w,) = tr.find_all("queue_wait")
    assert abs(sum(w.labels[k] for k in WAIT_LABELS)
               - w.duration * 1e3) < 1.0, w.labels
    return w


def test_a_prompt_longer_than_the_live_depth_waits_closed(sched):
    before = sched.stats()
    # 150 tokens: the first row is still live when a loaded machine gets
    # round to the second request's thread
    a, b = _drive(sched, [
        (_prompt(5), 150, None, {}),
        (_prompt(70, 3), 4, _after(sched, before["segments"], 1), {})])
    after = sched.stats()
    assert after["closes_depth"] == before["closes_depth"] + 1
    assert after["closes_policy"] == before["closes_policy"]
    assert after["batches_closed"] == before["batches_closed"] + 1
    wa, wb = _wait_span(a), _wait_span(b)
    assert wb.labels["closed_ms"] > 0 and wa.labels["closed_ms"] == 0
    assert wb.labels["slot_ms"] == wb.labels["pool_ms"] == 0
    # it seeded the next batch: nothing live to hold up
    assert "live" not in b.find("prefill").labels


def test_a_sampling_mismatch_closes_by_policy(sched):
    from llm_sharding_demo_tpu.runtime.engine import SamplingConfig
    before = sched.stats()
    sample = {"sampling": SamplingConfig(mode="sample", temperature=0.7,
                                         top_k=20),
              "key": jax.random.PRNGKey(3)}
    # a first row long enough (19 calls) that the second finds it live
    # on a loaded machine too: the host no longer runs calls ahead of
    # the device, so a short batch is over in the time it computes
    _, b = _drive(sched, [
        (_prompt(5), 150, None, {}),
        (_prompt(6, 6), 4, _after(sched, before["segments"], 1), sample)])
    after = sched.stats()
    assert after["closes_policy"] == before["closes_policy"] + 1
    assert after["closes_depth"] == before["closes_depth"]
    assert after["batches_closed"] == before["batches_closed"] + 1
    assert _wait_span(b).labels["closed_ms"] > 0


def test_a_full_batch_waits_for_a_slot():
    ib = IterBatchingEngine(_engine(), max_batch=1, seg_steps=8,
                            max_wait_ms=0.0)
    # 150 tokens, as above: the first row is still live when the second
    # request's thread gets its turn on a loaded machine
    a, b = _drive(ib, [(_prompt(5), 150, None, {}),
                       (_prompt(6, 4), 4, _after(ib, 0, 1), {})])
    _wait_span(a)
    wb = _wait_span(b)
    assert wb.labels["slot_ms"] > 0 and ib.stats()["defers_slot"] >= 1
    assert wb.labels["closed_ms"] == wb.labels["pool_ms"] == 0
    assert ib.stats()["batches_closed"] == 0


def test_a_two_block_pool_waits_for_pool_room():
    from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
    eng = _engine(max_seq=64)
    pool = KVBlockPool.for_engine(eng, num_blocks=2, block_size=32,
                                  watermark=1.0)
    ib = IterBatchingEngine(eng, max_batch=4, seg_steps=8, max_wait_ms=0.0,
                            pool=pool)
    # the first row grows into both blocks; the joiner's one block is not
    # there until it retires
    a, b = _drive(ib, [(_prompt(5), 44, None, {}),
                       (_prompt(6, 5), 4, _after(ib, 0, 3), {})])
    _wait_span(a)
    wb = _wait_span(b)
    assert wb.labels["pool_ms"] > 0 and ib.stats()["defers_pool"] >= 1
    assert wb.labels["closed_ms"] == wb.labels["slot_ms"] == 0
    assert pool.allocator.stats().blocks_in_use == 0


def test_a_joiner_says_how_many_rows_it_held_up_and_step_ms_is_gone(sched):
    a, b = _joined_pair(sched)
    assert b.find("prefill").labels["live"] == 1
    assert "live" not in a.find("prefill").labels
    for s in a.find_all("decode") + b.find_all("decode"):
        assert "step_ms" not in s.labels and 1 <= s.labels["steps"] <= 8
    # a call ends where a row's budget ends: 120 and 20 tokens are 119
    # and 19 steps paid, no more
    assert [sum(s.labels["steps"] for s in tr.find_all("decode"))
            for tr in (a, b)] == [119, 19]


def test_spec_segments_carry_their_sync_as_the_ready_instant():
    from llm_sharding_demo_tpu.runtime.engine import SamplingConfig
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine
    eng = _engine()
    spec = SpecDecodeEngine(eng.params, eng.config, max_seq=200, draft_len=3)
    ib = IterBatchingEngine(spec.plain, max_batch=2, seg_steps=8,
                            max_wait_ms=0.0, spec=spec)
    (tr,) = _drive(ib, [(_prompt(12), 24, None,
                         {"sampling": SamplingConfig(spec=True)})])
    decodes = tr.find_all("decode")
    assert len(decodes) >= 2 and tr.find("prefill").ready is not None
    assert [s.labels["seg"] for s in decodes] == list(range(len(decodes)))
    for s in decodes:
        assert s.labels["spec"] and s.t0 <= s.ready <= s.t1


def test_ready_instants_survive_the_cross_replica_stitch():
    tr = tracing.RequestTrace("replica")
    tr.add_span("prefill", tr.t0 + 0.010, tr.t0 + 0.011,
                ready=tr.t0 + 0.050, kind="seed")
    payload = tr.finish().to_dict()
    assert payload["spans"][0]["labels"] == {"kind": "seed",
                                             "ready_ms": 50.0}
    host = tracing.RequestTrace("router")
    hop = host.graft("decode_hop", payload, host.t0 + 1.0, host.t0 + 2.0)
    (child,) = hop.children
    assert child.labels == {"kind": "seed"}
    assert child.ready == pytest.approx(host.t0 + 1.050)
    out = host.finish().to_dict()["spans"][0]["spans"][0]
    assert out["labels"]["ready_ms"] == pytest.approx(1050.0, abs=0.01)


def _served(registry, recorder):
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.http import TestClient
    from llm_sharding_demo_tpu.serving.tokenizer import ByteTokenizer
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    cfg = gpt2.GPT2Config(vocab_size=256, n_positions=256, n_embd=32,
                          n_layer=2, n_head=4)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(4)))
    return TestClient(create_app(
        ServingConfig(model_id="t", max_seq=200, max_batch=4,
                      batch_mode="iter", batch_wait_ms=0.0),
        model=(cfg, params), tokenizer=ByteTokenizer(), registry=registry,
        recorder=recorder))


def test_served_request_shows_ready_and_wait_labels_and_honest_metrics():
    from llm_sharding_demo_tpu.utils.metrics import MetricsRegistry
    reg, rec = MetricsRegistry(), tracing.FlightRecorder()
    client = _served(reg, rec)
    body = {"prompt": "Hello there", "max_new_tokens": 120, "mode": "greedy"}
    client.post("/generate", json=body)               # compiles
    before = reg.snapshot()
    client.post("/generate", json=body, headers={"X-Request-ID": "warm"})
    after = reg.snapshot()
    tree = next(t for t in client.get("/debug/requests?n=4").json()["requests"]
                if t["request_id"] == "warm")
    by_name = {}
    for s in tree["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    (wait,), (pre,) = by_name["queue_wait"], by_name["prefill"]
    assert set(WAIT_LABELS) <= set(wait["labels"])
    assert pre["labels"]["ready_ms"] >= pre["start_ms"] + pre["duration_ms"]
    assert all("ready_ms" in s["labels"] for s in by_name["decode"])
    # ttft ends where the first token existed, not where it was enqueued
    assert tree["labels"]["ttft_ms"] == pre["labels"]["ready_ms"]

    def delta(key):
        return after[key] - before.get(key, 0)
    assert delta("tpot_seconds{mode=greedy}_count") == 1
    tpot = delta("tpot_seconds{mode=greedy}_sum")
    wall_per_token = tree["duration_ms"] / 1e3 / 119
    assert wall_per_token / 10 <= tpot <= wall_per_token
    last = max(s["labels"]["ready_ms"] for s in by_name["decode"])
    assert tpot == pytest.approx(
        (last - pre["labels"]["ready_ms"]) / 1e3 / 119, abs=1e-5)
    assert delta("ttft_seconds{mode=greedy}_sum") == pytest.approx(
        pre["labels"]["ready_ms"] / 1e3, abs=1e-5)


def _drained():
    """Every handover made so far has been stamped and observed."""
    done = threading.Event()
    tracing.READY.hand(None, [], then=lambda at: done.set())
    assert done.wait(60)


def test_decode_step_seconds_is_the_period_between_ready_instants():
    from llm_sharding_demo_tpu.utils.metrics import REGISTRY
    ib = IterBatchingEngine(_engine(), max_batch=2, seg_steps=8,
                            max_wait_ms=0.0)
    ib.generate(_prompt(5), 20)                        # compiles
    key = "decode_step_seconds{component=iter}"
    _drained()                     # the waiter observes, after the fact
    before = REGISTRY.snapshot()
    (tr,) = _drive(ib, [(_prompt(5), 33, None, {})])
    decodes = tr.find_all("decode")
    _drained()
    after = REGISTRY.snapshot()
    assert after[key + "_count"] - before.get(key + "_count", 0) == \
        len(decodes) == 4
    # seed's first token to the last segment, ready to ready, over 8 steps
    want = (decodes[-1].ready - tr.find("prefill").ready) / 8
    assert after[key + "_sum"] - before.get(key + "_sum", 0) == \
        pytest.approx(want, abs=1e-5)
