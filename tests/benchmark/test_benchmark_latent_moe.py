"""The latent-attention / sparse-expert configuration in the benchmark:
found by name in a copy of the fixture, run whole at a tiny size on the
CPU, and its readers on traces with and without what they read."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark.harness import latent_bytes
from benchmark.harness.spec import REPO, ROOT, Spec, resolve
from benchmark.readers import latent_moe as readers

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tiny-latent.tiny"
METRICS = ("experts_hit_share", "routed_here_share",
           "expert_load_max_over_mean", "latent_attn_ms_per_step",
           "expert_ffn_ms_per_step", "latent_moe_step_roofline",
           "latent_decode_attention_roofline")


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """The fixture benchmark, copied, with the new configuration at a
    tiny size, its cell and its per-layer metrics added beside it."""
    tmp = tmp_path_factory.mktemp("latent")
    shutil.copytree(os.path.join(HERE, "fixture", "bench"), tmp / "bench")
    doc = json.load(open(os.path.join(HERE, "fixture", "BENCHMARK.json")))
    real = json.load(open(os.path.join(ROOT, "configs",
                                       "joyai-llm-flash-ep16.json")))
    tiny = dict(real, hidden_size=64, vocab_size=512, num_attention_heads=4,
                q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, qk_head_dim=24,
                intermediate_size=224, moe_intermediate_size=24,
                num_hidden_layers=3, n_routed_experts=4,
                published_n_routed_experts=16, first_expert=4,
                num_experts_per_tok=4, max_position_embeddings=512)
    tiny["serving_env"] = dict(real["serving_env"], MAX_BATCH="4",
                               MAX_SEQ="256", KV_POOL_BLOCKS="96",
                               PREFIX_CACHE="4", PREFIX_CHUNK="16")
    tiny["check"] = dict(real["check"], requests=8, limits={
        "deficit_mean": 0.02, "deficit_max": 0.5})
    (tmp / "bench" / "configs" / "tiny-latent.json").write_text(
        json.dumps(tiny))
    shutil.copy(tmp / "bench" / "cells" / "tiny-llama.tiny.json",
                tmp / "bench" / "cells" / f"{CELL}.json")
    for name in METRICS:
        shutil.copy(os.path.join(ROOT, "layer_metrics", f"{name}.json"),
                    tmp / "bench" / "layer_metrics")
    doc["configs"].append({"name": "tiny-latent", "source": "none",
                           "file": "bench/configs/tiny-latent.json",
                           "reduced": ["n_routed_experts"], "why": "x"})
    doc["workloads"].append({"name": CELL, "config": "tiny-latent",
                             "traffic": "tiny", "chips": 1, "why": "x"})
    for name in METRICS:
        doc["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "Kernels and model step",
            "moves": "tpot_p50_ms", "workloads": [CELL]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return str(tmp / "BENCHMARK.json")


def test_found_by_name_and_its_parts_resolve(grown):
    spec = Spec(grown)
    config = spec.config(spec.workload(CELL)["config"])
    assert config["n_routed_experts"] == 4
    reference = resolve(config["reference"])
    assert callable(reference.init) and callable(reference.logits)
    bm = resolve(config["bytes_model"])(config)
    assert bm["kv_per_token"] == 3 * (32 + 8) * 2 and bm["held"] == 4
    assert {m["name"] for m in spec.metrics("per_layer", CELL)} >= set(METRICS)
    assert all(callable(spec.reader(n)) for n in METRICS)


def test_the_published_configuration_counts_what_the_issue_counted():
    spec = Spec()
    config = spec.config("joyai-llm-flash-ep16")
    row = [json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"JoyAI-LLM-Flash"' in l] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    for published in row:
        changed = {k for k, v in published["config"].items()
                   if config.get(k) != v}
        assert changed == {"n_routed_experts"} == set(config["reduced"])
    assert config["published_n_routed_experts"] == 256
    assert config["num_hidden_layers"] == 40 and config["vocab_size"] == 129280
    bm = latent_bytes.latent_moe(config)
    assert bm["kv_per_token"] == 46080
    assert 3.10e9 < bm["weights"] < 3.16e9       # ISSUE 27: 3.13 GB a step
    assert bm["expert"] == 3 * 2048 * 768 * 2 and bm["expert_layers"] == 39
    held = bm["expert_layers"] * bm["held"] * bm["expert"]
    embedding = 129280 * 2048 * 2
    assert 9.5e9 < bm["weights"] + held + embedding < 9.6e9    # 9.55 GB


@pytest.mark.parametrize("trace", [0, 1])
def test_the_whole_command_at_a_tiny_size(grown, trace):
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seconds", "2", "--benchmark-json", grown, "--seed",
         str(2**31 + 5), "--trace", str(trace), "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    names = set(result["metrics"])
    if trace:
        # counters and span labels are read; no device plane on the CPU
        assert {"experts_hit_share", "routed_here_share",
                "expert_load_max_over_mean"} <= names
        assert not {"latent_moe_step_roofline", "latent_attn_ms_per_step",
                    "latent_decode_attention_roofline"} & names
        assert 0 < result["metrics"]["experts_hit_share"]["value"] <= 100
        assert 0 < result["metrics"]["routed_here_share"]["value"] < 100
        assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1
    else:
        assert {"tpot_p50_ms", "setup_s"} <= names


# -- the readers on synthetic traces -------------------------------------------

def _ctx(**kw):
    base = dict(trace=None, trace_unix=(1000.0, 1003.0), window_traces=[],
                counters_before={}, counters_after={}, seg_steps=32,
                config=Spec().config("joyai-llm-flash-ep16"),
                peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})
    base.update(kw)
    ctx = types.SimpleNamespace(**base)
    ctx.bytes_model = latent_bytes.latent_moe(ctx.config)
    return ctx


def test_readers_find_nothing_in_a_program_without_the_spans():
    """The parent commit's program: no ``moe.*`` counters, no routing
    labels, no named scopes, no kernel. Nothing raises."""
    ops = [("%fusion.1 = bf16[8,14336]{1,0} fusion(%p)", 10.0, 5.0)]
    mods = [("jit__decode_seg_impl(123)", 0.0, 100.0)]
    trace = types.SimpleNamespace(devices=["d"], modules={"d": mods},
                                  ops={"d": ops})
    old = [{"request_id": "a", "started_unix": 1000.0,
            "labels": {"prompt_tokens": 100},
            "spans": [{"name": "prefill", "start_ms": 0, "duration_ms": 5,
                       "labels": {"ready_ms": 9.0}},
                      {"name": "decode", "start_ms": 10, "duration_ms": 1,
                       "labels": {"seg": 3, "steps": 32, "ready_ms": 400.0}}]}]
    ctx = _ctx(trace=trace, window_traces=old,
               counters_before={"sched.segments": 1},
               counters_after={"sched.segments": 9})
    spec = Spec()
    for name in METRICS:
        assert spec.reader(name)(ctx) is None, name
    assert all(spec.reader(n)(_ctx()) is None for n in METRICS)


def test_readers_read_the_right_numbers_from_a_synthetic_trace():
    ctx = _ctx(counters_before={"sched.moe.experts_hit": 100,
                                "sched.moe.layer_forwards": 1000,
                                "sched.moe.pairs_here": 10,
                                "sched.moe.pairs_routed": 100},
               counters_after={"sched.moe.experts_hit": 100 + 39 * 32 * 2,
                               "sched.moe.layer_forwards": 1000 + 39 * 32,
                               "sched.moe.pairs_here": 10 + 50,
                               "sched.moe.pairs_routed": 100 + 800},
               window_traces=[{"request_id": "a", "spans": [
                   {"name": "prefill", "start_ms": 0, "duration_ms": 1,
                    "labels": {"expert_load_max": 9.0,
                               "expert_load_mean": 4.0}},
                   {"name": "prefill", "start_ms": 2, "duration_ms": 1,
                    "labels": {"expert_load_max": 3.0,
                               "expert_load_mean": 2.0}}]}])
    assert readers.experts_hit_share(ctx) == pytest.approx(100 * 2 / 16)
    assert readers.routed_here_share(ctx) == pytest.approx(6.25)
    assert readers.expert_load_max_over_mean(ctx) == pytest.approx(2.0)


def test_step_roofline_pairs_bytes_and_time_by_seg_and_ready():
    """The slice opens inside segment 6's call and closes inside segment
    8's: the profiler keeps both pieces. Segment 7's call is the one
    whole call, and only its bytes and its time count: segment 6 became
    ready inside the slice, but its call is a piece; segment 8 was
    dispatched inside the slice and became ready after it. The kernel's
    and the scopes' times come from operations inside the whole decode
    call only, loops left out."""
    def request(rid, prompt, spans):
        return {"request_id": rid, "started_unix": 990.0,
                "labels": {"prompt_tokens": prompt}, "spans": spans}

    def decode(seg, start_ms, ready_ms, hit, steps=32):
        return {"name": "decode", "start_ms": start_ms, "duration_ms": 2.0,
                "labels": {"seg": seg, "steps": steps, "ready_ms": ready_ms,
                           "experts_hit": hit}}

    traces = [
        request("a", 1000, [decode(6, 9000.0, 10050.0, 5000),     # a piece
                            decode(7, 9500.0, 10400.0, 2000),     # whole
                            decode(8, 10500.0, 13500.0, 9000)]),  # out
        request("b", 500, [decode(7, 9500.0, 10400.0, 2000),
                           decode(8, 10500.0, 13500.0, 9000)]),
        request("c", 2000, [decode(8, 10500.0, 13500.0, 9000)])]
    step_ns = 6e6                                   # 6 ms a step
    at = 1e8                                        # the whole call's start
    mods = [("jit__decode_seg_impl(1)", 0.0, 5e7),
            ("jit__decode_seg_impl(1)", at, 32 * step_ns),
            ("jit__prefill_impl(2)", at + 32 * step_ns, 5e6),
            ("jit__decode_seg_impl(1)", 2.8e9, 1e8)]
    name = ('%latent_decode_attention.3 = bf16[4,32,640]{2,1,0} '
            'custom-call(%a, %b), custom_call_target="tpu_custom_call"')
    scoped = "%fusion.12 = bf16[4,1536]{1,0} fusion(%p), kind=kLoop"
    expert = "%fusion.40 = bf16[128,768]{1,0} fusion(%p), kind=kOutput"
    other = "%fusion.41 = bf16[4,1,2048]{2,1,0} fusion(%p), kind=kLoop"
    loop = "%while.3 = (s32[], bf16[128,768]) while(%t), body=%b"
    ops = [(name, 0.0, 4e7), (expert, 4e7, 1e7),    # the first piece's
           (name, at + 10.0, 32 * 40 * 2e4), (scoped, at + 20.0, 32 * 1e5),
           (expert, at + 30.0, 32 * 3e5), (loop, at + 25.0, 32 * 4e5),
           (other, at + 40.0, 32 * 5e5),
           (scoped, at + 32 * step_ns + 1.0, 7e7),  # a prefill's: left out
           (name, 2.8e9, 1e8)]                      # the last piece's
    trace = types.SimpleNamespace(devices=["d"], modules={"d": mods},
                                  ops={"d": ops})
    # the profiler wrote the trace out for 82 s after the slice closed,
    # and ``trace_unix`` ends there: the device's operations bound it
    late = _ctx(trace=trace, window_traces=traces,
                trace_unix=(1000.0, 1085.0))
    assert readers.slice_unix(late) == pytest.approx((1000.0, 1003.15))
    assert set(readers.segments_ready_in_slice(late)) == {6, 7}
    ctx = _ctx(trace=trace, window_traces=traces)
    assert [e[1] for e in readers.whole(ctx, mods)] \
        == [at, at + 32 * step_ns]
    (call, seg), = readers.paired(ctx, "d", "decode_seg")
    assert call[1] == at and seg["experts_hit"] == 2000
    live = (1000 + 1 + 32 + 16) + (500 + 1 + 16)
    assert sorted(seg["live"]) == [517.0, 1049.0]
    assert readers.latent_moe_step_roofline(late, "decode_seg") \
        == readers.latent_moe_step_roofline(ctx, "decode_seg")
    bm = ctx.bytes_model
    need = 32 * (bm["weights"] + 46080 * live) + 2000 * bm["expert"]
    want = 100 * (need / 819e9) / (32 * step_ns / 1e9)
    got = readers.latent_moe_step_roofline(ctx, "decode_seg")
    assert got == pytest.approx(want) and 0 < got < 100
    kernel = readers.latent_decode_attention_roofline(
        ctx, "latent_decode_attention", "decode_seg")
    floor = 32 * 40 * max(live * 576 * 2 / 819e9,
                          4 * 32 * 576 * live / 197e12)
    assert kernel == pytest.approx(100 * floor / (32 * 40 * 2e4 / 1e9))
    spec = Spec()
    assert spec.reader("latent_attn_ms_per_step")(ctx) \
        == pytest.approx((32 * 40 * 2e4 + 32 * 1e5) / 1e6 / 32)
    assert spec.reader("expert_ffn_ms_per_step")(ctx) \
        == pytest.approx(32 * 3e5 / 1e6 / 32)
