"""The sliding-window / sparse-expert configuration in the benchmark:
found by name in a copy of the fixture, run whole at a tiny size on the
CPU, its byte model against a count of the leaves, and its readers on
traces with and without what they read."""

import json
import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

from benchmark.harness import traffic as traffic_mod, window_bytes
from benchmark.harness.spec import REPO, ROOT, Spec, resolve
from benchmark.readers import window_moe as readers

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "k-exaone-236b-ep8"
REAL_CELL = f"{NAME}.shortlong"
CELL = "tiny-window.tiny"
OURS = ("swa_moe_step_roofline", "swa_attn_ms_per_step", "swa_held_share",
        "swa_restore_share")
METRICS = ("experts_hit_share", "routed_here_share",
           "expert_load_max_over_mean", "state_slab_peak_share") + OURS
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}
CUT_LISTS = {"layer_types", "mlp_layer_types", "sliding_windows"}


def tiny_of(real):
    tiny = dict(real, hidden_size=64, vocab_size=512, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, sliding_window=8,
                intermediate_size=96, moe_intermediate_size=32,
                num_hidden_layers=8, num_experts=4, published_num_experts=16,
                first_expert=4, num_experts_per_tok=4,
                max_position_embeddings=512)
    tiny["serving_env"] = dict(real["serving_env"], MAX_BATCH="4",
                               MAX_SEQ="256", KV_POOL_BLOCKS="96",
                               PREFIX_CACHE="4", PREFIX_CHUNK="16")
    tiny["check"] = dict(real["check"], requests=8, limits={
        "deficit_mean": 0.02, "deficit_max": 0.5})
    return tiny


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """The fixture benchmark, copied, with the new configuration at a
    tiny size (two periods, a window of 8, 4 of 16 experts held), its
    cell and its per-layer metrics added beside it."""
    tmp = tmp_path_factory.mktemp("window")
    shutil.copytree(os.path.join(HERE, "fixture", "bench"), tmp / "bench")
    doc = json.load(open(os.path.join(HERE, "fixture", "BENCHMARK.json")))
    real = json.load(open(os.path.join(ROOT, "configs", f"{NAME}.json")))
    (tmp / "bench" / "configs" / "tiny-window.json").write_text(
        json.dumps(tiny_of(real)))
    shutil.copy(tmp / "bench" / "cells" / "tiny-llama.tiny.json",
                tmp / "bench" / "cells" / f"{CELL}.json")
    for name in METRICS:
        shutil.copy(os.path.join(ROOT, "layer_metrics", f"{name}.json"),
                    tmp / "bench" / "layer_metrics" / f"{name}.json")
    doc["configs"].append({"name": "tiny-window", "source": "none",
                           "file": "bench/configs/tiny-window.json",
                           "reduced": sorted(REDUCED), "why": "x"})
    doc["workloads"].append({"name": CELL, "config": "tiny-window",
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
    # two full layers' keys and values a position; six windows a row
    assert bm["kv_per_token"] == 2 * 2 * 2 * 32 * 2 and bm["held"] == 4
    assert bm["window_row"] == 6 * 2 * 2 * 32 * 2 and bm["window"] == 8
    assert bm["expert_layers"] == 7
    assert {m["name"] for m in spec.metrics("per_layer", CELL)} >= set(METRICS)
    assert all(callable(spec.reader(n)) for n in METRICS)


def test_the_real_cell_its_traffic_and_its_metrics_are_found_by_name():
    spec = Spec()
    entry = spec.workload(REAL_CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NAME, "shortlong", 1)
    traffic = spec.traffic("shortlong")
    assert traffic["arrival"] == "poisson"
    assert traffic["shared_prefix"] == {"count": 2, "tokens": 384,
                                        "share": 0.5}
    assert traffic["prompt"] == {"median": 1024, "sigma": 1.1, "min": 128,
                                 "max": 8192}
    assert traffic["output"] == {"median": 160, "sigma": 0.7, "min": 32,
                                 "max": 512}
    assert traffic["base_seed"] == 20261001
    cell = spec.cell(REAL_CELL)
    assert cell["rate_rps"] == pytest.approx(0.8 * cell["knee_rps"])
    assert {row["rate_rps"] for row in cell["sweep"]} >= {
        0.25, 0.5, 0.75, 1.0}
    judged = {m["name"] for m in spec.metrics("end_to_end", REAL_CELL)}
    assert judged == {"tpot_p50_ms", "setup_s"}
    reported = {m["name"] for m in spec.metrics("per_layer", REAL_CELL)}
    assert {"experts_hit_share", "routed_here_share",
            "expert_load_max_over_mean", "prefix_token_share",
            "pool_peak_share", "batch_occupancy", "tpot_p95_unjudged_ms",
            "decode_step_ms", "device_idle_share",
            "decode_attn_ms_per_step", "state_slab_peak_share"} <= reported
    # the traced slice holds an admission at this rate (the cell file),
    # so the prefill's device time is reported
    assert "prefill_ms_p50" in reported
    # other families' metrics, the store's pinned one (its test holds it
    # to three cells) and the restore share whose file counts prefixes
    # of 1,024 tokens: swa_restore_share is that reader at this
    # traffic's 384
    assert not {"decode_step_roofline", "latent_moe_step_roofline",
                "gdn_moe_step_roofline", "gdn_ms_per_step",
                "store_tokens_per_call", "state_restore_share"} & reported
    with open(os.path.join(ROOT, "layer_metrics",
                           "swa_restore_share.json")) as f:
        assert json.load(f)["params"] == {
            "prefix_tokens": traffic["shared_prefix"]["tokens"]}
    assert all(callable(spec.reader(n)) for n in reported)
    # the family's own metrics were APPENDED: they follow the entry that
    # was the last at the parent, in this order. (Not pinned as the
    # last of the list: the next PR appends behind them, and a pin of
    # that kind is what fails in test_benchmark_store.py:38 since PR 35
    # and in test_benchmark_gdn_moe.py:128 since this PR.)
    names = [m["name"] for m in spec.doc["per_layer"]]
    at = names.index("state_restore_share")
    assert names[at + 1:at + 1 + len(OURS)] == list(OURS)
    for m in spec.doc["per_layer"][at + 1:at + 1 + len(OURS)]:
        assert m["workloads"] == [REAL_CELL] and m["moves"] == "tpot_p50_ms"
        assert m["unit"] in ("%", "ms")
    # the traffic: a tenth of the prompts under 256 before a prefix
    # lifts half of those to 385, a tenth over 4,096, half behind a
    # prefix, every prompt inside the cache with its answer
    sizes = traffic_mod.sizes(traffic, 2000)
    assert 0.4 < sum(s[2] >= 0 for s in sizes) / len(sizes) < 0.6
    assert 0.06 < sum(p > 4096 for p, _, _ in sizes) / len(sizes) < 0.14
    assert 0.02 < sum(p < 256 for p, _, _ in sizes) / len(sizes) < 0.1
    assert min(p for p, _, _ in sizes) == 128
    assert max(p + n for p, n, _ in sizes) <= int(
        spec.config(NAME)["serving_env"]["MAX_SEQ"])


def test_the_published_configuration_counts_what_the_issue_counted():
    config = Spec().config(NAME)
    row = [json.loads(l) for l in open(CATALOG)
           if '"K-EXAONE-236B-A23B"' in l] \
        if os.path.exists(CATALOG) else []
    for published in row:
        changed = {k for k, v in published["config"].items()
                   if config.get(k) != v}
        assert changed == REDUCED | CUT_LISTS
        assert set(config["reduced"]) == REDUCED
        assert config["source"] == published["source_url"]
        for k in CUT_LISTS:                  # cut with the depth, no more
            assert config[k] == published["config"][k][:8]
        assert config["rope_theta"] == \
            published["config"]["rope_parameters"]["rope_theta"]
    assert (config["published_num_hidden_layers"],
            config["published_num_experts"],
            config["published_vocab_size"]) == (48, 128, 153600)
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (8, 16, 19200)
    assert config["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 2
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 7
    assert {"norm_placement", "rope", "weights", "first_expert",
            "torch_dtype"} <= set(config["assumed"])
    assert "multi-token-prediction" in config["not_served"]
    assert "8-way" in config["deployment"]
    bm = window_bytes.window_moe(config)
    assert bm["kv_per_token"] == 2 * 2 * 8 * 128 * 2             # 8,192 B
    assert bm["window_row"] == 6 * 2 * 8 * 128 * 2               # 24,576 B
    assert bm["expert"] == 3 * 6144 * 2048 * 2 and bm["held"] == 16
    # ISSUE 37: 3.27 GB of non-expert weights and head a step
    assert 3.26e9 < bm["weights"] < 3.28e9
    held = bm["expert_layers"] * bm["held"] * bm["expert"]
    embedding = 19200 * 6144 * 2
    assert 11.95e9 < bm["weights"] + held + embedding < 11.97e9  # 11.96 GB
    # what a window layer reads of a row stops growing at the window
    assert window_bytes.window_per_row(bm, 100) == 100 * 24576
    assert window_bytes.window_per_row(bm, 128) == \
        window_bytes.window_per_row(bm, 8000) == 128 * 24576


def test_the_byte_model_is_a_count_of_the_leaves():
    """Every leaf the reference's ``init`` makes at the cut's sizes
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
    bm = window_bytes.window_moe(config)
    assert bm["weights"] == total
    assert bm["expert_layers"] * bm["held"] * bm["expert"] == routed


@pytest.mark.parametrize("trace", [0, 1])
def test_the_whole_command_at_a_tiny_size(grown, trace):
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seconds", "2", "--benchmark-json", grown, "--seed",
         str(2**31 + 7), "--trace", str(trace), "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    names = set(result["metrics"])
    if trace:
        # counters and span labels are read; no device plane on the CPU
        assert {"experts_hit_share", "routed_here_share",
                "expert_load_max_over_mean", "state_slab_peak_share",
                "swa_held_share"} <= names
        assert not {"swa_moe_step_roofline", "swa_attn_ms_per_step"} & names
        assert 0 < result["metrics"]["swa_held_share"]["value"] <= 100
        assert 0 < result["metrics"]["routed_here_share"]["value"] < 100
    else:
        assert {"tpot_p50_ms", "setup_s"} <= names


# -- the readers on synthetic traces -------------------------------------------

def _ctx(**kw):
    base = dict(trace=None, trace_unix=(1000.0, 1003.0), window_traces=[],
                rows=[], counters_before={}, counters_after={}, samples=[],
                seg_steps=32,
                config=Spec().config(NAME),
                peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})
    base.update(kw)
    ctx = types.SimpleNamespace(**base)
    ctx.bytes_model = kw.get("bytes_model") or \
        window_bytes.window_moe(ctx.config)
    return ctx


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent commit's program, or another family's cell: no
    ``window.*`` counters, a byte model without ``window_row``, no
    operation with a ring's shape. Nothing raises."""
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
               samples=[{"sched.state.in_use": 3}, {"pool.blocks_in_use": 9}],
               counters_after={"sched.segments": 9},
               bytes_model={"weights": 1, "kv_per_token": 1})
    spec = Spec()
    for name in OURS:
        assert spec.reader(name)(ctx) is None, name
        assert spec.reader(name)(_ctx()) is None, name


def test_the_held_share_is_read_at_the_fullest_sample():
    samples = [{"sched.window.positions_held": 128,
                "sched.window.positions_seen": 400},
               {"sched.window.positions_held": 384,
                "sched.window.positions_seen": 9000},
               {"sched.window.positions_held": 0,
                "sched.window.positions_seen": 0},
               {"sched.state.in_use": 2}]
    assert readers.swa_held_share(_ctx(samples=samples)) == pytest.approx(
        100 * 384 / 9000)
    # a layer that held a depth would read 100
    assert readers.swa_held_share(_ctx(samples=[
        {"sched.window.positions_held": 700,
         "sched.window.positions_seen": 700}])) == pytest.approx(100.0)
    assert readers.swa_held_share(_ctx(samples=[
        {"sched.window.positions_seen": 0,
         "sched.window.positions_held": 0}])) is None


def test_step_roofline_and_window_time_pair_bytes_and_time_by_segment():
    """One whole decode call between two pieces: its segment's bytes
    (weights, the live positions in the full layers, a WINDOW of each
    live row in the sliding ones, the experts hit) over its time; the
    sliding layers' attention by the shapes only a ring has, inside that
    call only."""
    def request(rid, prompt, spans):
        return {"request_id": rid, "started_unix": 990.0,
                "labels": {"prompt_tokens": prompt}, "spans": spans}

    def decode(seg, start_ms, ready_ms, hit, steps=32):
        return {"name": "decode", "start_ms": start_ms, "duration_ms": 2.0,
                "labels": {"seg": seg, "steps": steps, "ready_ms": ready_ms,
                           "experts_hit": hit}}

    traces = [
        request("a", 3000, [decode(6, 9000.0, 10050.0, 5000),
                            decode(7, 9500.0, 10400.0, 200),
                            decode(8, 10500.0, 13500.0, 9000)]),
        request("b", 60, [decode(7, 9500.0, 10400.0, 200),
                          decode(8, 10500.0, 13500.0, 9000)])]
    step_ns = 8e6
    at = 1e8
    mods = [("jit__decode_seg_impl(1)", 0.0, 5e7),
            ("jit__decode_seg_impl(1)", at, 32 * step_ns),
            ("jit__decode_seg_impl(1)", 2.8e9, 1e8)]
    ring = ("%select_fusion.3 = bf16[2,8,128,256]{3,2,1,0} "
            "fusion(%iota, %p), kind=kLoop")
    scores = "%fusion.9 = f32[2,8,8,128]{3,2,1,0} fusion(%q, %ring)"
    # names a ring as an OPERAND: a search of the whole text would count
    # it, the short name does not
    after = ("%fusion.12 = bf16[2,1,8192]{2,1,0} "
             "fusion(%select_fusion.3 bf16[2,8,128,256])")
    other = "%fusion.41 = bf16[2,1,6144]{2,1,0} fusion(%p), kind=kLoop"
    # the full layers' kernel: at width 1 its result has the scores' shape
    kernel = ('%_call.25 = (bf16[8,8,128]{2,1,0}, bf16[2,1,8,8704,256]) '
              'custom-call(%q, %kv), custom_call_target="tpu_custom_call"')
    ops = [(ring, 0.0, 4e7),
           (ring, at + 10.0, 32 * 6 * 1e4), (scores, at + 20.0, 32 * 6 * 2e4),
           (after, at + 30.0, 32 * 3e5), (other, at + 40.0, 32 * 5e5),
           (kernel, at + 50.0, 32 * 2 * 1e4),
           (ring, 2.8e9, 1e8)]
    trace = types.SimpleNamespace(devices=["d"], modules={"d": mods},
                                  ops={"d": ops})
    ctx = _ctx(trace=trace, window_traces=traces)
    bm = ctx.bytes_model
    deep, shallow = 3000 + 1 + 32 + 16, 60 + 1 + 16
    need = 32 * (bm["weights"] + bm["kv_per_token"] * (deep + shallow)
                 + bm["window_row"] * (128 + shallow)) + 200 * bm["expert"]
    got = readers.swa_moe_step_roofline(ctx, "decode_seg")
    assert got == pytest.approx(100 * (need / 819e9) / (32 * step_ns / 1e9))
    assert 0 < got < 100
    per_step = Spec().reader("swa_attn_ms_per_step")(ctx)
    assert per_step == pytest.approx(
        (32 * 6 * 1e4 + 32 * 6 * 2e4) / 1e6 / 32)
