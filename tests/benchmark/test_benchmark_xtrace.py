"""The reduction from a profiler trace to busy time, per-program and
per-kernel time, idle gaps and what the host was doing in them."""

import types

import pytest

from benchmark.harness import xtrace
from benchmark.readers import device

MS = 1e6   # nanoseconds

# one device, 100 ms: two decode segments of 32 steps, one prefill, idle
# in between. Operations overlap their program; a kernel runs inside it.
MODULES = [("jit__decode_seg_impl(1)", 0 * MS, 40 * MS),
           ("jit__prefill_impl(7)", 50 * MS, 10 * MS),
           ("jit__decode_seg_impl(1)", 70 * MS, 30 * MS)]
OPS = [("fusion.1", 0 * MS, 25 * MS), ("_kernel.3", 20 * MS, 20 * MS),
       ("fusion.9", 50 * MS, 10 * MS),
       ("fusion.1", 70 * MS, 18 * MS), ("_kernel.3", 88 * MS, 12 * MS)]
HOST = [("sched.admit", 41 * MS, 8 * MS), ("sched.retire", 60 * MS, 2 * MS),
        ("sched.segment_dispatch", 62 * MS, 8 * MS)]


def trace():
    return xtrace.Trace({"/device:TPU:0": MODULES}, {"/device:TPU:0": OPS},
                        HOST, {})


def test_busy_is_the_union_not_the_sum():
    assert xtrace.merged(OPS) == [(0, 40 * MS), (50 * MS, 60 * MS),
                                  (70 * MS, 100 * MS)]
    assert xtrace.busy_ns(OPS) == 80 * MS
    busy, window = xtrace.busy_and_window_s(trace())
    assert busy == pytest.approx(0.080) and window == pytest.approx(0.100)


def test_gaps_go_to_the_host_span_that_covers_most_of_them():
    gaps = xtrace.idle_gaps(OPS)
    assert gaps == [(40 * MS, 50 * MS), (60 * MS, 70 * MS)]
    assert xtrace.attribute(gaps[0], HOST) == "sched.admit"
    assert xtrace.attribute(gaps[1], HOST) == "sched.segment_dispatch"
    assert xtrace.attribute((200 * MS, 210 * MS), HOST) == "no host span"


def test_breakdown_names_operations_and_gaps():
    b = xtrace.breakdown(trace())
    assert b["device_ops"][0] == ["fusion", pytest.approx(0.053)]
    assert ["_kernel", pytest.approx(0.032)] in b["device_ops"]
    assert b["idle_gaps"] == [["sched.admit", pytest.approx(0.010)],
                              ["sched.segment_dispatch", pytest.approx(0.010)]]


def ctx(**kw):
    base = dict(trace=trace(), seg_steps=32, trace_unix=(100.0, 100.1))
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_per_program_and_per_kernel_readers():
    c = ctx()
    assert device.module_ms_per_step(c, "decode_seg") == \
        pytest.approx(70.0 / 64)
    assert device.module_ms_p50(c, "prefill_impl|jit__run") == \
        pytest.approx(10.0)
    assert device.op_ms_per_step(c, "_kernel", "decode_seg") == \
        pytest.approx(32.0 / 64)
    assert device.device_idle_share(c) == pytest.approx(20.0)
    assert device.module_ms_per_step(c, "no_such_program") is None
    assert device.device_idle_share(ctx(trace=None)) is None


def test_a_call_cut_by_the_edge_of_the_slice_counts_as_its_share():
    ms = 1e6
    whole = [("jit__decode_seg_impl(1)", i * 400 * ms, 312 * ms)
             for i in range(3)]
    cut = [("jit__decode_seg_impl(1)", 1200 * ms, 100 * ms)]
    other = [("jit__decode_seg_impl(2)", 1300 * ms, 288 * ms)]
    assert xtrace.whole_calls(whole) == pytest.approx(3.0)
    assert xtrace.whole_calls(whole + cut + other) == \
        pytest.approx(3.0 + 100 / 312 + 1.0)
    t = trace()
    t.modules[t.devices[0]] = whole + cut
    c = ctx(trace=t)
    # 9.75 ms a step, not (3 x 312 + 100) / (4 x 32) = 8.09
    assert device.module_ms_per_step(c, "decode_seg") == \
        pytest.approx(312 / 32)


def test_roofline_share_from_needed_bytes():
    # two rows decode together in both segments: prompts of 100 and 200
    # tokens; the first segment starts with one token emitted
    def req(prompt):
        return {"started_unix": 100.0, "labels": {"prompt_tokens": prompt},
                "spans": [{"name": "decode", "start_ms": 0.0,
                           "duration_ms": 1.0,
                           "labels": {"steps": 32, "depth": 232}},
                          {"name": "decode", "start_ms": 70.0,
                           "duration_ms": 1.0,
                           "labels": {"steps": 32, "depth": 264}}]}
    c = ctx(window_traces=[req(100), req(200)],
            bytes_model={"weights": 1_000_000, "kv_per_token": 1000},
            peaks={"hbm_bytes_per_s": 1e9})
    live1 = (100 + 1 + 16) + (200 + 1 + 16)
    live2 = live1 + 64
    need = 32 * (1_000_000 + 1000 * (live1 + live2) / 2)
    floor_s = need / 1e9
    assert device.decode_step_roofline(c, "decode_seg") == \
        pytest.approx(100 * floor_s / 0.035)


def test_a_recorded_trace_loads(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("sched.admit"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    t = xtrace.load(xtrace.newest_xplane(str(tmp_path)), ["sched.admit"])
    assert [e[0] for e in t.host] == ["sched.admit"] and t.host[0][2] > 0
    assert t.devices == []               # the CPU has no device plane
    with pytest.raises(ValueError):
        xtrace.window_ns(t)
