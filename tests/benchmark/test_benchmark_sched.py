"""The scheduler's state log through its three readers
(``readers/sched.py``): the two counter metrics from deltas of
``sched.t_<state>_s``, and the device's idle time cut by the log's
``idle`` intervals, on synthetic device operations, paired calls and a
real ``StateLog`` driven by a scripted clock. A case lays out what the
DEVICE ran and what the SCHEDULER was doing on the unix clock; ``build``
puts the first on a device clock with an origin of its own and the
second on a host clock behind an offset."""

import json
import os
import time
import types

import jax
import numpy as np
import pytest

from benchmark.harness import server, xtrace
from benchmark.harness.spec import REPO, Spec
from benchmark.readers import device, sched
from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.utils import tracing

T0 = 1000.0                    # every request's ``started_unix``
ORIGIN = 7e12                  # the device clock at unix ``T0``
STATES = ("idle", "hold", "seed", "admit", "advance", "other")
CELLS = ["mistral-7b-l16.chat", "mistral-7b-l16.chat-b",
         "joyai-llm-flash-ep16.assist", "qwen3-next-80b-ep32.threads",
         "k-exaone-236b-ep8.shortlong"]
METRICS = {
    "sched_idle_share": ("%", "higher", "program_counter", "Scheduler"),
    "sched_host_ms_per_call": ("ms", "lower", "program_counter",
                               "Scheduler"),
    "device_idle_with_work_share": ("%", "lower", "program_span", "Device"),
}
# six decode calls of 0.3 s (s after ``T0``): the slice opens with the
# first and closes with the last, so four are whole; between them three
# gaps in which the device has nothing to run, the first of them ended
# by a joiner's prefill
CALLS = [1.00, 1.30, 1.90, 2.25, 2.65, 2.95]
CALL_S = 0.3
PREFILL = (1.80, 0.10)
GAPS = [(1.60, 1.80), (2.20, 2.25), (2.55, 2.65)]
WINDOW_S = 3.25 - 1.00


def _counters(before, after):
    return types.SimpleNamespace(counters_before=before,
                                 counters_after=after)


def _seconds(**took):
    return {f"sched.t_{s}_s": took.get(s, 0.0) for s in STATES}


def _log(transitions, host_offset, capacity=65536):
    """A ``StateLog`` whose thread was in ``other`` from unix ``T0`` and
    entered ``state`` at each ``(s after T0, state)``, on a host clock
    that reads ``unix - host_offset``."""
    now = [T0 - host_offset]
    log = tracing.StateLog(STATES, "other", capacity=capacity,
                           clock=lambda: now[0])
    log.unix_offset = host_offset
    for at, state in transitions:
        now[0] = T0 + at - host_offset
        log.enter(state)
    now[0] = T0 + 50.0 - host_offset       # the instant of the read
    return log


def build(monkeypatch, transitions, host_offset=0.0, lag_ms=0.0, **log_kw):
    """The context a traced run's readers get, and the log behind the
    program's process-wide handle."""
    mods, ops, spans = [], [], []
    for k, at in enumerate(CALLS):
        mods.append(("jit__decode_seg_impl(1)", ORIGIN + at * 1e9,
                     CALL_S * 1e9))
        ops.append((f"%fusion.{k} = bf16[1,4096]{{1,0}} fusion(%p)",
                    ORIGIN + at * 1e9, CALL_S * 1e9))
        spans.append({"name": "decode", "start_ms": at * 1e3 - 300.0,
                      "duration_ms": 1.0,
                      "labels": {"seg": k, "steps": 30, "batch": 0,
                                 "width": 1,
                                 "ready_ms": (at + CALL_S) * 1e3 + lag_ms}})
    mods.append(("jit__prefill_impl(2)", ORIGIN + PREFILL[0] * 1e9,
                 PREFILL[1] * 1e9))
    ops.append(("%fusion.9 = bf16[256,4096]{1,0} fusion(%p)",
                ORIGIN + PREFILL[0] * 1e9, PREFILL[1] * 1e9))
    log = _log(transitions, host_offset, **log_kw)
    monkeypatch.setattr(tracing, "state_logs", lambda: [log])
    return types.SimpleNamespace(
        trace=xtrace.Trace({"d": mods}, {"d": ops}, [], {}),
        window_traces=[{"request_id": "a", "started_unix": T0,
                        "labels": {"prompt_tokens": 100}, "spans": spans}],
        trace_unix=(T0 + 0.95, T0 + 40.0))


# what the scheduler was doing: idle through the first gap but for the
# 20 ms in which it seeds the arrival that ends it; dispatching late
# through the second; held behind the device, then dispatching, through
# the third
BUSY = [(0.5, "advance"), (1.35, "idle"), (1.78, "seed"), (1.81, "other"),
        (2.15, "advance"), (2.26, "other"), (2.50, "hold"),
        (2.60, "advance"), (2.66, "other"), (3.00, "idle")]


# -- the two counter metrics ---------------------------------------------------

def test_idle_share_and_host_ms_a_call_from_deltas():
    before = {**_seconds(idle=100.0, hold=2.0, seed=1.0, admit=0.5,
                         advance=3.0, other=0.25), "sched.segments": 400}
    after = {**_seconds(idle=112.0, hold=32.0, seed=2.0, admit=1.5,
                        advance=6.5, other=0.75), "sched.segments": 600}
    ctx = _counters(before, after)
    # 12 s idle of the 48 s between the two reads
    assert sched.sched_idle_share(ctx) == pytest.approx(25.0)
    # seed 1 + admit 1 + advance 3.5 + other 0.5 over 200 calls
    assert sched.sched_host_ms_per_call(ctx) == pytest.approx(30.0)


@pytest.mark.parametrize("before,after", [
    ({}, {}),
    # the parent commit: a scheduler that counts calls and no seconds
    ({"sched.segments": 4}, {"sched.segments": 9}),
    # one of the six missing
    ({k: 0.0 for k in list(_seconds())[1:]},
     {k: 1.0 for k in list(_seconds())[1:]}),
], ids=["no-counters", "parent", "five-of-six"])
def test_a_program_without_the_counters_gives_none(before, after):
    ctx = _counters(before, after)
    assert sched.sched_idle_share(ctx) is None
    assert sched.sched_host_ms_per_call(ctx) is None


def test_a_window_without_a_call_has_no_host_time_a_call():
    ctx = _counters({**_seconds(), "sched.segments": 7},
                    {**_seconds(idle=51.0), "sched.segments": 7})
    assert sched.sched_idle_share(ctx) == pytest.approx(100.0)
    assert sched.sched_host_ms_per_call(ctx) is None


def test_the_harness_hands_the_schedulers_seconds_to_the_readers():
    """``Served.counters`` flattens ``stats()`` under ``sched.``: the six
    seconds are floats there, and their deltas between two reads are the
    time between the reads."""
    cfg = gpt2.GPT2Config(vocab_size=127, n_positions=128, n_embd=32,
                          n_layer=1, n_head=2)
    eng = DecodeEngine(gpt2.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                       max_seq=96)
    it = IterBatchingEngine(eng, max_batch=2, seg_steps=4)
    served = types.SimpleNamespace(scheduler=it, pool=None)
    t0 = time.perf_counter()
    before = server.Served.counters(served)
    t1 = time.perf_counter()
    it.generate(np.arange(1, 10, dtype=np.int32), 14)
    t2 = time.perf_counter()
    after = server.Served.counters(served)
    t3 = time.perf_counter()
    took = [after[k] - before[k] for k in _seconds()]
    assert all(isinstance(after[k], float) for k in _seconds())
    assert t2 - t1 <= sum(took) <= t3 - t0
    ctx = _counters(before, after)
    assert after["sched.segments"] - before["sched.segments"] == 4
    assert 0.0 <= sched.sched_idle_share(ctx) < 100.0
    host = sum(after[f"sched.t_{s}_s"] - before[f"sched.t_{s}_s"]
               for s in ("seed", "admit", "advance", "other"))
    assert sched.sched_host_ms_per_call(ctx) == pytest.approx(1e3 * host / 4)


# -- the device's idle time, cut by the log ------------------------------------

def test_a_gap_inside_idle_is_no_request_and_one_that_straddles_is_split(
        monkeypatch):
    # only the first gap's scheduler: idle 1.35-1.78, then the seed
    ctx = build(monkeypatch, [(0.5, "advance"), (1.35, "idle"),
                              (1.78, "seed"), (1.81, "advance")])
    with_work, no_request, window = sched.idle_split(ctx, "decode_seg")
    assert window == pytest.approx(WINDOW_S * 1e9)
    # 1.60-1.78 lies inside ``idle``; 1.78-1.80 is the seed's
    assert no_request == pytest.approx(0.18e9)
    assert with_work == pytest.approx((0.02 + 0.05 + 0.10) * 1e9)


def test_a_gap_inside_hold_or_advance_is_with_work(monkeypatch):
    ctx = build(monkeypatch, [(0.5, "advance"), (2.50, "hold"),
                              (2.60, "advance"), (2.66, "other")])
    with_work, no_request, _ = sched.idle_split(ctx, "decode_seg")
    assert no_request == 0.0
    assert with_work == pytest.approx(sum(b - a for a, b in GAPS) * 1e9)
    assert sched.device_idle_with_work_share(ctx, "decode_seg") == \
        pytest.approx(100 * 0.35 / WINDOW_S)


def test_the_two_parts_sum_to_device_idle_share(monkeypatch):
    ctx = build(monkeypatch, BUSY)
    with_work, no_request, window = sched.idle_split(ctx, "decode_seg")
    assert 100 * (with_work + no_request) / window == \
        pytest.approx(device.device_idle_share(ctx))
    assert sched.device_idle_with_work_share(ctx, "decode_seg") == \
        pytest.approx(100 * (0.02 + 0.05 + 0.10) / WINDOW_S)
    assert sched.device_idle_with_work_share(ctx, "decode_seg") <= \
        device.device_idle_share(ctx)


@pytest.mark.parametrize("host_offset", [0.0, 777.25, -431999.5])
def test_the_intervals_lie_behind_a_clock_offset(monkeypatch, host_offset):
    """The host's clock, the wall clock and the device's each have an
    origin; the reading does not move with any of them."""
    ctx = build(monkeypatch, BUSY, host_offset=host_offset)
    assert sched.device_idle_with_work_share(ctx, "decode_seg") == \
        pytest.approx(100 * 0.17 / WINDOW_S, rel=1e-4)


def test_a_ready_stamp_that_trails_its_call_moves_an_edge_by_that_much(
        monkeypatch):
    """The offset between the clocks is a segment's ready instant less
    its call's end: a stamp 0.8 ms late (the chip's are within 0.92)
    puts the log 0.8 ms early on the device's clock, so the ``idle``
    that the seed ends covers that much less of its gap."""
    ctx = build(monkeypatch, BUSY, lag_ms=0.8)
    got = sched.device_idle_with_work_share(ctx, "decode_seg")
    assert got == pytest.approx(100 * (0.17 + 0.0008) / WINDOW_S, rel=1e-4)


def test_the_busiest_log_is_the_one_that_served(monkeypatch):
    ctx = build(monkeypatch, BUSY)
    served, = tracing.state_logs()
    quiet = _log([(0.1, "idle")], 0.0)
    monkeypatch.setattr(tracing, "state_logs", lambda: [quiet, served])
    assert sched.scheduler_log() is served
    assert sched.device_idle_with_work_share(ctx, "decode_seg") == \
        pytest.approx(100 * 0.17 / WINDOW_S)


@pytest.mark.parametrize("case", ["parent", "no-log", "unpaired",
                                  "ring-too-short"])
def test_where_the_log_cannot_be_read_there_is_no_number(monkeypatch, case):
    kw = {"capacity": 4} if case == "ring-too-short" else {}
    ctx = build(monkeypatch, BUSY, **kw)
    if case == "parent":
        # a program whose tracing module hands out no logs
        monkeypatch.delattr(tracing, "state_logs")
    elif case == "no-log":
        monkeypatch.setattr(tracing, "state_logs", lambda: [])
    elif case == "unpaired":
        ctx.window_traces = []
    else:
        # four closed intervals back from the read: the ring starts at
        # 2.26, inside the slice
        assert tracing.state_logs()[0].intervals()[0][1] == T0 + 2.26
    assert sched.idle_split(ctx, "decode_seg") is None
    assert sched.device_idle_with_work_share(ctx, "decode_seg") is None


# -- declared, and found by name -----------------------------------------------

@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_entry_is_found_by_name_for_the_five_cells(name, monkeypatch):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entry, = [m for m in doc["per_layer"] if m["name"] == name]
    unit, better, source, layer = METRICS[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "tpot_p50_ms", "workloads": CELLS}
    spec = Spec()
    for cell in CELLS:
        assert name in [m["name"] for m in spec.metrics("per_layer", cell)]
    # its file resolves to a reader that reads a context
    read = spec.reader(name)
    ctx = build(monkeypatch, BUSY)
    ctx.counters_before = {**_seconds(), "sched.segments": 0}
    ctx.counters_after = {**_seconds(idle=10.0, advance=2.0),
                          "sched.segments": 100}
    assert read(ctx) == pytest.approx(
        {"sched_idle_share": 100 * 10.0 / 12.0,
         "sched_host_ms_per_call": 20.0,
         "device_idle_with_work_share": 100 * 0.17 / WINDOW_S}[name])
