"""The per-channel delta-rule / latent-attention / sparse-expert
configuration in the benchmark: found by name in a copy of the fixture,
run whole at a tiny size on the CPU, its byte model against a count of
the leaves, and its readers on traces with and without what they read."""

import json
import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

from benchmark.harness import kda_bytes, traffic as traffic_mod
from benchmark.harness.spec import REPO, ROOT, Spec, resolve
from benchmark.readers import kda_moe as readers

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "kimi-linear-48b-ep16"
REAL_CELL = f"{NAME}.longanswer"
CELL = "tiny-kda.tiny"
OURS = ("kda_latent_step_roofline", "kda_state_update_roofline",
        "kda_ms_per_step")
METRICS = ("experts_hit_share", "routed_here_share",
           "expert_load_max_over_mean", "state_slab_peak_share") + OURS
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_of(real):
    tiny = dict(real, hidden_size=64, vocab_size=512, num_attention_heads=4,
                num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, intermediate_size=224,
                moe_intermediate_size=24, num_hidden_layers=7,
                num_experts=4, published_num_experts=8, first_expert=2,
                num_experts_per_token=2, model_max_length=512,
                linear_attn_config=dict(
                    real["linear_attn_config"], head_dim=16, num_heads=4,
                    kda_layers=[1, 2, 4, 5, 6], full_attn_layers=[3, 7]))
    # served in float32: at a width of 64 bfloat16's own noise (0.04-0.07
    # and 1.1-1.4 over 61 tokens, three seeds on the CPU) is the int8
    # control's (0.03-0.14 and 0.8-1.7), so limits between them would
    # hold nothing; float32 against the float32 reference reads 1e-4,
    # and another request's logits 2.6-3.7
    tiny["serving_env"] = dict(real["serving_env"], MAX_BATCH="4",
                               MAX_SEQ="256", KV_POOL_BLOCKS="96",
                               PREFIX_CACHE="4", PREFIX_CHUNK="16",
                               INFERENCE_DTYPE="float32")
    tiny["check"] = dict(real["check"], requests=8, limits={
        "deficit_mean": 0.005, "deficit_max": 0.5})
    return tiny


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """The fixture benchmark, copied, with the new configuration at a
    tiny size (seven layers: a run ``K' K M`` and a run ``K K K M``, 4 of
    8 experts held), its cell and its per-layer metrics added beside
    it."""
    tmp = tmp_path_factory.mktemp("kda")
    shutil.copytree(os.path.join(HERE, "fixture", "bench"), tmp / "bench")
    doc = json.load(open(os.path.join(HERE, "fixture", "BENCHMARK.json")))
    real = json.load(open(os.path.join(ROOT, "configs", f"{NAME}.json")))
    (tmp / "bench" / "configs" / "tiny-kda.json").write_text(
        json.dumps(tiny_of(real)))
    shutil.copy(tmp / "bench" / "cells" / "tiny-llama.tiny.json",
                tmp / "bench" / "cells" / f"{CELL}.json")
    for name in METRICS:
        shutil.copy(os.path.join(ROOT, "layer_metrics", f"{name}.json"),
                    tmp / "bench" / "layer_metrics" / f"{name}.json")
    doc["configs"].append({"name": "tiny-kda", "source": "none",
                           "file": "bench/configs/tiny-kda.json",
                           "reduced": ["num_experts"], "why": "x"})
    doc["workloads"].append({"name": CELL, "config": "tiny-kda",
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
    assert config["num_experts"] == 4
    reference = resolve(config["reference"])
    assert callable(reference.init) and callable(reference.logits)
    bm = resolve(config["bytes_model"])(config)
    assert bm["kv_per_token"] == 2 * (32 + 8) * 2 and bm["held"] == 4
    assert bm["expert_layers"] == 6
    assert bm["state_per_row"] == 5 * 2 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    assert {m["name"] for m in spec.metrics("per_layer", CELL)} >= set(METRICS)
    assert all(callable(spec.reader(n)) for n in METRICS)


def test_the_real_cell_its_traffic_and_its_metrics_are_found_by_name():
    spec = Spec()
    entry = spec.workload(REAL_CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NAME, "longanswer", 1)
    traffic = spec.traffic("longanswer")
    assert traffic["arrival"] == "poisson"
    assert traffic["shared_prefix"] == {"count": 2, "tokens": 256,
                                        "share": 0.5}
    assert traffic["prompt"] == {"median": 320, "sigma": 0.8, "min": 64,
                                 "max": 1536}
    assert traffic["output"] == {"median": 640, "sigma": 0.4, "min": 256,
                                 "max": 1024}
    bases = {spec.traffic(w["traffic"])["base_seed"]
             for w in spec.doc["workloads"] if w["name"] != REAL_CELL}
    assert traffic["base_seed"] not in bases           # a trace of its own
    cell = spec.cell(REAL_CELL)
    assert cell["rate_rps"] > 0 and cell["warmup_s"] == 8.0
    assert [line["rate_rps"] for line in cell["sweep"]] == [
        0.5, 1.0, 1.5, 2.0, 3.0]
    judged = {m["name"] for m in spec.metrics("end_to_end", REAL_CELL)}
    assert judged == {"tpot_p50_ms", "setup_s"}
    reported = {m["name"] for m in spec.metrics("per_layer", REAL_CELL)}
    assert set(METRICS) | {
        "latency_p95_ms", "queue_wait_p95_ms", "ttft_p95_ms",
        "first_token_p95_ms", "tpot_ready_p50_ms", "dispatch_lead_p95_ms",
        "segment_period_ms_per_step", "wait_closed_share",
        "tpot_p95_unjudged_ms", "batch_occupancy", "prefix_token_share",
        "pool_peak_share", "decode_step_ms",
        "device_idle_share"} <= reported
    # other families' device metrics, whose patterns, layer counts or
    # prefix lengths are theirs, the ones a test pins to its cells, and
    # ``prefill_ms_p50``: a slice of 1 s of this cell need hold no
    # prefill (at 1.5 req/s one held none), and a listed metric has to
    # be on every traced line
    assert not {"decode_step_roofline", "latent_moe_step_roofline",
                "prefill_ms_p50",
                "latent_decode_attention_roofline", "expert_ffn_ms_per_step",
                "gdn_moe_step_roofline", "gdn_state_update_roofline",
                "gdn_ms_per_step", "state_restore_share",
                "sched_idle_share", "store_tokens_per_call"} & reported
    assert all(callable(spec.reader(n)) for n in reported)
    # the three of this PR, found by NAME and once (a later PR appends
    # behind them and may list a cell of its own beside this one)
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert all(names.count(n) == 1 for n in OURS)
    for m in spec.doc["per_layer"]:
        if m["name"] in OURS:
            assert REAL_CELL in m["workloads"]
            assert m["moves"] == "tpot_p50_ms" and m["unit"] in ("%", "ms")
            assert m["source"] == "device_trace"
    # the traffic: half behind a prefix (256 and at least one token more),
    # answers of 256 to 1,024, every prompt inside the cache with its answer
    sizes = traffic_mod.sizes(traffic, 2000)
    assert 0.45 < sum(s[2] >= 0 for s in sizes) / len(sizes) < 0.55
    assert min(p for p, _, _ in sizes) == 64
    assert all(p >= 257 for p, _, pid in sizes if pid >= 0)
    assert {min(n for _, n, _ in sizes), max(n for _, n, _ in sizes)} == {
        256, 1024}
    assert 600 < sorted(n for _, n, _ in sizes)[1000] < 680
    max_seq = int(spec.config(NAME)["serving_env"]["MAX_SEQ"])
    assert max(p + n for p, n, _ in sizes) <= max_seq == 2560


def test_the_published_configuration_counts_what_the_issue_counted():
    config = Spec().config(NAME)
    row = [json.loads(l) for l in open(CATALOG)
           if '"Kimi-Linear-48B-A3B-Instruct"' in l] \
        if os.path.exists(CATALOG) else []
    for published in row:
        changed = {k for k, v in published["config"].items()
                   if config.get(k) != v}
        assert changed == {"num_experts"} == set(config["reduced"])
        assert config["source"] == published["source_url"]
    assert config["published_num_experts"] == 256
    assert config["num_experts"] == 16 and config["first_expert"] == 0
    assert config["num_hidden_layers"] == 27
    assert config["vocab_size"] == 163840
    la = config["linear_attn_config"]
    assert len(la["kda_layers"]) == 20 and len(la["full_attn_layers"]) == 7
    assert config["serving_env"]["MAX_SEQ"] == "2560"
    assert config["check"]["requests"] <= 32
    for key in ("deployment", "not_served", "assumed"):
        assert config[key]
    bm = kda_bytes.kda_moe(config)
    assert bm["kv_per_token"] == 7 * 576 * 2                    # 8.1 KB
    assert bm["expert"] == 3 * 2304 * 1024 * 2 and bm["held"] == 16
    assert bm["expert_layers"] == 26
    # ISSUE 46: 2,012M parameters outside the routed experts, of which
    # the embedding (377.5M) is no part of a step: 3.27 GB
    assert 3.26e9 < bm["weights"] < 3.28e9
    # 20 layers x 2 (in and out) x (32 x 128 x 128 float32 + 3 x 12288 bf16)
    assert bm["state_per_row"] == 20 * 2 * (32 * 128 * 128 * 4
                                            + 3 * 12288 * 2)
    held = bm["expert_layers"] * bm["held"] * bm["expert"]
    embedding = 163840 * 2304 * 2
    assert 9.90e9 < bm["weights"] + held + embedding < 9.93e9    # 9.91 GB
    kernel = kda_bytes.state_update(config, 2)
    assert kernel["bytes"] == 2 * 2 * 32 * 128 * 128 * 4
    assert kernel["layers"] == 20


def test_the_byte_model_is_a_count_of_the_leaves():
    """Every leaf the reference's ``init`` makes at the published sizes
    (shapes alone), but the embedding and the routed experts, is what a
    step reads: the byte model counts exactly those."""
    config = Spec().config(NAME)
    shapes = jax.eval_shape(
        lambda: resolve(config["reference"]).init(config, 0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = routed = 0
    for path, leaf in flat:
        names = [getattr(p, "key", None) for p in path]
        size = leaf.size * leaf.dtype.itemsize
        if names[0] == "experts":
            routed += size
        elif names[0] != "wte":
            total += size
    bm = kda_bytes.kda_moe(config)
    assert bm["weights"] == total
    assert bm["expert_layers"] * bm["held"] * bm["expert"] == routed


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
                "expert_load_max_over_mean", "state_slab_peak_share"} <= names
        assert not set(OURS) & names
        assert 0 < result["metrics"]["state_slab_peak_share"]["value"] <= 100
        assert 0 < result["metrics"]["routed_here_share"]["value"] < 100
    else:
        assert {"tpot_p50_ms", "setup_s"} <= names


# -- the readers on synthetic traces -------------------------------------------

def _ctx(**kw):
    base = dict(trace=None, trace_unix=(1000.0, 1003.0), window_traces=[],
                rows=[], counters_before={}, counters_after={}, samples=[],
                config=Spec().config(NAME),
                peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})
    base.update(kw)
    ctx = types.SimpleNamespace(**base)
    ctx.bytes_model = kw.get("bytes_model") or kda_bytes.kda_moe(ctx.config)
    return ctx


def test_readers_find_nothing_in_a_program_without_the_kernel():
    """The parent commit's program, or another family's cell: no kernel
    of this name, a configuration without the nested group. Nothing
    raises."""
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
    rows = [{"rid": "a", "ok": True, "text": "1 2 3 4"}]
    other = Spec().config("qwen3-next-80b-ep32")
    ctx = _ctx(trace=trace, window_traces=old, rows=rows, config=other,
               counters_after={"sched.segments": 9},
               bytes_model={"weights": 1, "kv_per_token": 1})
    spec = Spec()
    for name in OURS:
        assert spec.reader(name)(ctx) is None, name
        assert spec.reader(name)(_ctx()) is None, name


def test_step_and_kernel_rooflines_pair_bytes_and_time_by_segment():
    """One whole decode call between two pieces: its segment's bytes
    (weights, two live rows' state in and out, their positions' latents,
    the experts hit) over its time; the kernel by its short name, inside
    that call only; the delta-rule mixers' operations by what the
    metric's file names."""
    def request(rid, prompt, spans):
        return {"request_id": rid, "started_unix": 990.0,
                "labels": {"prompt_tokens": prompt}, "spans": spans}

    def decode(seg, start_ms, ready_ms, hit, steps=32):
        return {"name": "decode", "start_ms": start_ms, "duration_ms": 2.0,
                "labels": {"seg": seg, "steps": steps, "ready_ms": ready_ms,
                           "experts_hit": hit}}

    traces = [
        request("a", 1000, [decode(6, 9000.0, 10050.0, 5000),
                            decode(7, 9500.0, 10359.0, 2000),
                            decode(8, 10500.0, 13500.0, 9000)]),
        request("b", 500, [decode(7, 9500.0, 10359.0, 2000),
                           decode(8, 10500.0, 13500.0, 9000)])]
    step_ns = 8e6
    at = 1e8
    mods = [("jit__decode_seg_impl(1)", 0.0, 5e7),
            ("jit__decode_seg_impl(1)", at, 32 * step_ns),
            ("jit__decode_seg_impl(1)", 2.8e9, 1e8)]
    kernel = ('%kda_state_update.3 = (f32[2,32,1,128]{3,2,1,0}, '
              'f32[20,2,32,128,128]{4,3,2,1,0}) custom-call(%a, %b), '
              'custom_call_target="tpu_custom_call"')
    # names the kernel as an OPERAND: a search of the whole text would
    # count it, the short name does not
    after = "%fusion.9 = bf16[2,1,2304]{2,1,0} fusion(%kda_state_update.3)"
    proj = "%fusion.12 = bf16[2,1,12288]{2,1,0} fusion(%p), kind=kOutput"
    ops = [(kernel, 0.0, 4e7),
           (kernel, at + 10.0, 32 * 20 * 1e4), (after, at + 20.0, 32 * 2e4),
           (proj, at + 30.0, 32 * 3e5), (kernel, 2.8e9, 1e8)]
    trace = types.SimpleNamespace(devices=["d"], modules={"d": mods},
                                  ops={"d": ops})
    ctx = _ctx(trace=trace, window_traces=traces)
    bm = ctx.bytes_model
    live = (1000 + 1 + 32 + 16) + (500 + 1 + 16)
    need = 32 * (bm["weights"] + 2 * bm["state_per_row"]
                 + bm["kv_per_token"] * live) + 2000 * bm["expert"]
    got = readers.kda_latent_step_roofline(ctx, "decode_seg")
    assert got == pytest.approx(100 * (need / 819e9) / (32 * step_ns / 1e9))
    assert 0 < got < 100
    got = readers.kda_state_update_roofline(ctx, "kda_state_update",
                                            "decode_seg")
    floor = 32 * 20 * (2 * 2 * 32 * 128 * 128 * 4 / 819e9)
    assert got == pytest.approx(100 * floor / (32 * 20 * 1e4 / 1e9))
    per_step = Spec().reader("kda_ms_per_step")(ctx)
    assert per_step == pytest.approx(
        (32 * 20 * 1e4 + 32 * 3e5) / 1e6 / 32)
