"""The iter scheduler's thread accounts for its own time
(``utils.tracing.StateLog``, kept by ``runtime.iterbatch``): at every
instant it is in exactly one of ``idle``, ``hold``, ``seed``, ``admit``,
``advance``, ``other``, and the states' seconds sum to its lifetime.

Nothing here sleeps or polls: the log's arithmetic runs on a scripted
clock, and where the real scheduler is driven, the test waits on events
the scheduler's own thread sets (a spy round a method records the state
the thread is in when it gets there; the bare ``queue.get`` is the idle
park)."""

import collections
import re
import threading
import time

import jax
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.runtime import iterbatch
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine, SamplingConfig
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.utils import tracing
from llm_sharding_demo_tpu.utils.metrics import REGISTRY

STATES = ("idle", "hold", "seed", "admit", "advance", "other")
SPANS = {"sched.idle", "sched.hold_lead", "sched.seed", "sched.admit",
         "sched.segment_dispatch"}
SEG = 8
WAIT = 120                         # no event here takes a second


@pytest.fixture(scope="module")
def engine():
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=256, n_embd=32,
                          n_layer=2, n_head=4)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    return DecodeEngine(params, cfg, max_seq=200)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 211, size=(n,))


class Driven:
    """A scheduler with its worker's parks and method entries observable:
    ``parked`` is set when the worker reaches the idle park; ``seen``
    holds ``(method, open state, idle seconds so far)`` for every entry
    into a spied method, recorded on the worker's own thread; ``gate``
    maps a method to an event its next entry waits for."""

    def __init__(self, engine, spy=(), **kw):
        self.it = it = IterBatchingEngine(engine, max_batch=4,
                                          seg_steps=SEG, max_wait_ms=0.0,
                                          **kw)
        self.parked = threading.Event()
        self.queued = threading.Event()
        self.seen, self.gate = [], {}
        get, put = it._queue.get, it._queue.put

        def spied_get(*a, **k):
            if not a and not k:        # the bare get is the idle park
                self.parked.set()
            return get(*a, **k)

        def spied_put(req):
            put(req)
            self.queued.set()
        it._queue.get, it._queue.put = spied_get, spied_put
        self.spied = tuple(spy)
        for name in spy:
            setattr(it, name, self._spied(name, getattr(it, name)))

    def _spied(self, name, inner):
        def outer(*a, **k):
            self.seen.append((name, self.state(),
                              self.it.states.totals()["idle"]))
            gate = self.gate.pop(name, None)
            if gate is not None:
                assert gate.wait(WAIT)
            return inner(*a, **k)
        return outer

    def state(self):
        return self.it.states.intervals(1)[-1][0]

    def generate(self, *a, **k):
        """One request, then the worker back at its idle park."""
        self.parked.clear()
        out = self.it.generate(*a, **k)
        assert self.parked.wait(WAIT)
        return out

    def together(self, first, second, at="_advance"):
        """``first`` seeds a batch; ``second`` is queued while the worker
        stands at its next entry into ``at``, so it joins at the
        boundary after it. Returns both results."""
        assert at in self.spied
        self.parked.clear()
        self.queued.clear()
        self.gate[at] = both = threading.Event()
        got = [None, None]

        def go(i, job):
            got[i] = self.it.generate(*job[0], **job[1])

        a = threading.Thread(target=go, args=(0, first))
        a.start()
        assert self.queued.wait(WAIT)
        self.queued.clear()
        # the worker holds at ``at`` (or will) until the joiner is queued
        b = threading.Thread(target=go, args=(1, second))
        b.start()
        assert self.queued.wait(WAIT)
        both.set()
        for t in (a, b):
            t.join(WAIT)
            assert not t.is_alive()
        assert self.parked.wait(WAIT)
        return got


def _contiguous(intervals):
    return all(a[2] == b[1] for a, b in zip(intervals, intervals[1:]))


def _scripted(states=STATES, initial="other", **kw):
    now = [100.0]
    log = tracing.StateLog(states, initial, clock=lambda: now[0], **kw)
    return log, now


# -- the log's own arithmetic, on a scripted clock -----------------------------

def test_totals_count_the_open_state_up_to_the_read():
    log, now = _scripted()
    now[0] = 101.5
    assert log.enter("idle") == ("other", 1.5)
    now[0] = 104.0
    assert log.totals() == {**dict.fromkeys(STATES, 0.0), "other": 1.5,
                            "idle": 2.5}
    now[0] = 104.25
    # a delta between two reads is exact whatever state they fall in
    assert log.totals()["idle"] == 2.75
    assert sum(log.totals().values()) == now[0] - log.t_start


def test_the_ring_is_bounded_and_neither_overlaps_nor_leaves_a_hole():
    log, now = _scripted(capacity=8)
    for k in range(100):
        now[0] += 0.125
        log.enter(STATES[k % len(STATES)])
    now[0] += 1.0
    kept = log.intervals()
    assert len(kept) == 8 + 1              # the ring and the open state
    assert _contiguous(kept) and kept[-1][2] == now[0]
    assert all(t1 > t0 for _, t0, t1 in kept)
    assert [s for s, _, _ in log.intervals(3)] == [s for s, _, _ in kept[-3:]]
    # the totals keep what the ring let go
    assert sum(log.totals().values()) == pytest.approx(now[0] - log.t_start)


def test_staying_changes_nothing_and_an_unknown_state_is_refused():
    log, now = _scripted()
    now[0] = 101.0
    assert log.enter("other") == ("other", 0.0)
    assert len(log.intervals()) == 1
    with pytest.raises(ValueError, match="unknown state"):
        log.enter("asleep")
    with pytest.raises(ValueError, match="unknown state"):
        tracing.StateLog(STATES, "asleep")


def test_a_state_is_an_annotation_entered_and_left_by_enter(monkeypatch):
    opened, closed = [], []

    class Span:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            closed.append(self.name)

    monkeypatch.setattr(tracing, "annotate", Span)
    log, now = _scripted(annotations=iterbatch._STATE_SPANS)
    for state in ("idle", "other", "seed", "admit", "advance", "hold",
                  "other"):
        now[0] += 1.0
        log.enter(state)
    # ``other`` carries none; every other state's is left as the next
    # state is entered
    assert opened == ["sched.idle", "sched.seed", "sched.admit",
                      "sched.segment_dispatch", "sched.hold_lead"]
    assert closed == opened
    assert set(iterbatch._STATE_SPANS.values()) == SPANS
    assert set(iterbatch._STATE_SPANS) == set(STATES) - {"other"}


def test_the_wait_behind_the_device_lands_in_hold_and_nowhere_else(engine):
    """``_hold_lead`` on a scripted clock: two calls in flight, the
    older takes a quarter of a second to exist."""
    it = IterBatchingEngine(engine, max_batch=4, seg_steps=SEG)
    log, now = _scripted(annotations=iterbatch._STATE_SPANS, label="t")
    it.states = log

    class Tokens:
        def __init__(self, takes):
            self.takes = takes

        def block_until_ready(self):
            now[0] += self.takes
            return self

    it._in_flight = collections.deque([Tokens(0.25), Tokens(9.0)])
    now[0] += 0.5
    it._hold_lead()
    assert len(it._in_flight) == 1
    assert log.totals() == {**dict.fromkeys(STATES, 0.0), "other": 0.5,
                            "hold": 0.25}
    it._hold_lead()                        # one in flight: no wait, no state
    assert [s for s, _, _ in log.intervals()] == ["other", "hold", "other"]
    assert it.stats()["t_hold_s"] == 0.25


# -- the scheduler's thread ----------------------------------------------------

def test_the_six_states_sum_to_the_threads_lifetime(engine):
    d = Driven(engine)
    d.generate(_prompt(7), 30)
    before = time.perf_counter()
    stats = d.it.stats()
    after = time.perf_counter()
    took = {s: stats[f"t_{s}_s"] for s in STATES}
    assert all(isinstance(v, float) and v >= 0.0 for v in took.values())
    born = d.it.states.t_start
    assert before - born - 1e-3 <= sum(took.values()) <= after - born + 1e-3
    # ... and the ring tiles it from the thread's first instant to now
    kept = d.it.states.intervals()
    assert kept[0][1] == born and _contiguous(kept)
    assert took["seed"] > 0 and took["advance"] > 0 and took["idle"] > 0


def test_idle_grows_while_nothing_is_queued(engine):
    d = Driven(engine)
    d.generate(_prompt(7), 4)
    assert d.state() == "idle"
    a, b = d.it.states.totals(), d.it.states.totals()
    assert b["idle"] > a["idle"]
    assert {s: b[s] - a[s] for s in STATES if s != "idle"} == \
        dict.fromkeys([s for s in STATES if s != "idle"], 0.0)


def test_idle_stands_still_while_a_batch_lives(engine):
    d = Driven(engine, spy=("_seed_batch", "_advance"))
    d.generate(_prompt(7), 4 * SEG)
    calls = [x for x in d.seen if x[0] == "_advance"]
    assert len(calls) >= 4
    # the idle seconds the worker saw at the seed are those it saw at
    # every later call of the batch
    assert {idle for _, _, idle in d.seen} == {d.seen[0][2]}
    assert d.it.states.totals()["idle"] > d.seen[0][2] > 0


def test_a_seed_a_join_and_a_cut_call_each_end_in_the_state_they_should(
        engine):
    d = Driven(engine, spy=("_seed_batch", "_admit_one", "_advance",
                            "_retire_finished"))
    first, second = d.together(((_prompt(7), 5 * SEG), {}),
                               ((_prompt(5, 1), SEG + 3), {}))
    assert first.new_tokens == 5 * SEG and second.new_tokens == SEG + 3
    stats = d.it.stats()
    assert stats["joins"] == 1 and stats["segments_cut"] >= 1
    # inside each method the thread is in that method's state ...
    inside = collections.defaultdict(set)
    for name, state, _ in d.seen:
        inside[name].add(state)
    assert inside["_seed_batch"] == {"seed"}
    assert inside["_admit_one"] == {"admit"}
    assert inside["_advance"] == {"advance"}
    assert inside["_retire_finished"] <= {"seed", "admit", "advance"}
    # ... and each ends where it should: a seed in ``other``, an
    # admission in the call's ``advance``, a call (cut or whole) in
    # ``other``; the batch's life is one sentence of that grammar
    said = " ".join(s for s, _, _ in d.it.states.intervals())
    batch = r"seed other(?: (?:hold other )?(?:admit )?advance other)+"
    assert re.fullmatch(rf"other idle other {batch} idle", said), said
    assert " admit advance " in said and " hold other " in said


def test_a_seed_that_fails_leaves_the_thread_accounted_for(engine,
                                                           monkeypatch):
    d = Driven(engine)

    def broken(seed):
        raise RuntimeError("no prefill today")
    d.it._seed_batch = broken
    d.parked.clear()
    with pytest.raises(RuntimeError, match="no prefill today"):
        d.it.generate(_prompt(7), 4)
    assert d.parked.wait(WAIT)
    said = [s for s, _, _ in d.it.states.intervals()]
    assert said == ["other", "idle", "other", "seed", "other", "idle"]
    assert _contiguous(d.it.states.intervals())


@pytest.mark.parametrize("sampling,key", [
    (SamplingConfig(mode="greedy"), None),
    (SamplingConfig(mode="sample", temperature=0.7, top_k=30), 11),
], ids=["greedy", "seeded"])
def test_the_tokens_of_a_seeded_batch_are_the_solo_engines(engine, sampling,
                                                           key):
    """The log records; it dispatches nothing and waits for nothing: a
    batch's rows are their solo streams byte for byte, as at the parent."""
    d = Driven(engine, spy=("_advance",))
    jobs = [(_prompt(7), 3 * SEG + 5), (_prompt(12, 2), SEG - 1)]
    kw = [{"sampling": sampling,
           **({} if key is None else {"key": jax.random.PRNGKey(key + i)})}
          for i in range(2)]
    got = d.together((jobs[0], kw[0]), (jobs[1], kw[1]))
    for (prompt, new), k, res in zip(jobs, kw, got):
        solo = engine.generate(prompt[None, :], new, **k).tokens[0]
        assert np.asarray(res.tokens[0], np.int32).tobytes() == \
            np.asarray(solo, np.int32).tobytes()


def test_the_process_hands_out_the_log_under_its_replicas_label(engine):
    it = IterBatchingEngine(engine, max_batch=2, replica="decode-7")
    assert it.states in tracing.state_logs()
    assert it.states.label == "decode-7"
    starts = [log.t_start for log in tracing.state_logs()]
    assert starts == sorted(starts)


def test_metrics_carry_the_seconds_by_state(engine):
    def seconds(state):
        return REGISTRY.snapshot().get(
            f"iter_scheduler_state_seconds_total{{state={state}}}", 0.0)
    before = {s: seconds(s) for s in STATES}
    d = Driven(engine)
    d.generate(_prompt(7), 3 * SEG)
    took = d.it.states.totals()
    # every closed interval was added as it closed; the open idle is not
    for s in ("seed", "admit", "advance", "hold", "other"):
        assert seconds(s) - before[s] == pytest.approx(took[s], abs=1e-6)
    assert 'iter_scheduler_state_seconds_total{state="advance"}' in \
        REGISTRY.prometheus()


def test_debug_requests_carries_the_scheduler_and_metrics_the_counter():
    from llm_sharding_demo_tpu.fleet import harness
    client, _rec, _reg = harness.build_single(max_seq=128, max_batch=2)
    r = client.post("/generate", json={"prompt": "Hi, ",
                                       "max_new_tokens": 4,
                                       "mode": "greedy"})
    assert r.status_code == 200
    body = client.get("/debug/requests?n=3").json()
    sched = body["scheduler"]
    assert set(sched["seconds"]) == set(STATES)
    assert sched["seconds"]["seed"] > 0
    assert 1 <= len(sched["intervals"]) <= 3
    assert set(sched["intervals"][-1]) == {"state", "start_unix",
                                           "duration_ms"}
    assert abs(sched["started_unix"] - time.time()) < 3600
    # the admission batcher keeps no such log: no key
    solo, _, _ = harness.build_single(max_seq=128, max_batch=1)
    assert "scheduler" not in solo.get("/debug/requests").json()


def test_a_profile_without_the_harness_carries_the_states_on_one_line(
        engine, tmp_path):
    """``tracing.trace(dir)`` round a few requests, no wrapper: the five
    names lie on the scheduler thread's line of the host plane."""
    from jax.profiler import ProfileData
    d = Driven(engine, spy=("_advance",))
    d.generate(_prompt(7), 4)              # every program warm
    with tracing.trace(str(tmp_path)):
        d.together(((_prompt(7), 4 * SEG), {}), ((_prompt(5, 1), SEG), {}))
        d.generate(_prompt(9, 3), 3)
    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert found
    lines = {}
    for plane in ProfileData.from_file(str(found[-1])).planes:
        for line in plane.lines:
            names = {e.name for e in line.events} & SPANS
            if names:
                lines[(plane.name, line.name)] = names
    assert list(lines.values()) == [SPANS], lines
    assert next(iter(lines))[0].startswith("/host:")
