"""The linear-attention / sparse-expert configuration in the benchmark:
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

from benchmark.harness import gdn_bytes, traffic as traffic_mod
from benchmark.harness.spec import REPO, ROOT, Spec, resolve
from benchmark.readers import gdn_moe as readers

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "qwen3-next-80b-ep32"
CELL = "tiny-gdn.tiny"
METRICS = ("experts_hit_share", "routed_here_share",
           "expert_load_max_over_mean", "gdn_moe_step_roofline",
           "gdn_state_update_roofline", "gdn_ms_per_step",
           "state_slab_peak_share", "state_restore_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_of(real):
    tiny = dict(real, hidden_size=64, vocab_size=512, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, linear_num_key_heads=2,
                linear_num_value_heads=4, linear_key_head_dim=16,
                linear_value_head_dim=16, moe_intermediate_size=32,
                shared_expert_intermediate_size=32, num_hidden_layers=8,
                num_experts=4, published_num_experts=8, first_expert=2,
                num_experts_per_tok=2, max_position_embeddings=512)
    tiny["serving_env"] = dict(real["serving_env"], MAX_BATCH="4",
                               MAX_SEQ="256", KV_POOL_BLOCKS="96",
                               PREFIX_CACHE="4", PREFIX_CHUNK="16")
    tiny["check"] = dict(real["check"], requests=8, limits={
        "deficit_mean": 0.02, "deficit_max": 0.5})
    return tiny


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """The fixture benchmark, copied, with the new configuration at a
    tiny size (two periods, 4 of 8 experts held), its cell and its
    per-layer metrics added beside it."""
    tmp = tmp_path_factory.mktemp("gdn")
    shutil.copytree(os.path.join(HERE, "fixture", "bench"), tmp / "bench")
    doc = json.load(open(os.path.join(HERE, "fixture", "BENCHMARK.json")))
    real = json.load(open(os.path.join(ROOT, "configs", f"{NAME}.json")))
    (tmp / "bench" / "configs" / "tiny-gdn.json").write_text(
        json.dumps(tiny_of(real)))
    shutil.copy(tmp / "bench" / "cells" / "tiny-llama.tiny.json",
                tmp / "bench" / "cells" / f"{CELL}.json")
    for name in METRICS:
        doc_m = json.load(open(os.path.join(ROOT, "layer_metrics",
                                            f"{name}.json")))
        if name == "state_restore_share":
            # the fixture's traffic shares prefixes of its own length
            fixture = json.load(open(tmp / "bench" / "traffic" / "tiny.json"))
            doc_m["params"]["prefix_tokens"] = \
                fixture["shared_prefix"]["tokens"]
        (tmp / "bench" / "layer_metrics" / f"{name}.json").write_text(
            json.dumps(doc_m))
    doc["configs"].append({"name": "tiny-gdn", "source": "none",
                           "file": "bench/configs/tiny-gdn.json",
                           "reduced": ["num_experts"], "why": "x"})
    doc["workloads"].append({"name": CELL, "config": "tiny-gdn",
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
    assert bm["kv_per_token"] == 2 * 2 * 2 * 32 * 2 and bm["held"] == 4
    assert bm["expert_layers"] == 8
    assert {m["name"] for m in spec.metrics("per_layer", CELL)} >= set(METRICS)
    assert all(callable(spec.reader(n)) for n in METRICS)


def test_the_real_cell_its_traffic_and_its_metrics_are_found_by_name():
    spec = Spec()
    cell = f"{NAME}.threads"
    entry = spec.workload(cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NAME, "threads", 1)
    traffic = spec.traffic("threads")
    assert traffic["shared_prefix"] == {"count": 2, "tokens": 1024,
                                        "share": 0.7}
    assert traffic["prompt"] == {"median": 1280, "sigma": 0.5, "min": 256,
                                 "max": 2560}
    assert traffic["output"]["median"] == 192
    assert traffic["base_seed"] == 20260930
    assert spec.cell(cell)["rate_rps"] > 0
    judged = {m["name"] for m in spec.metrics("end_to_end", cell)}
    assert judged == {"tpot_p50_ms", "setup_s"}
    reported = {m["name"] for m in spec.metrics("per_layer", cell)}
    assert {"experts_hit_share", "routed_here_share",
            "expert_load_max_over_mean", "prefix_token_share",
            "pool_peak_share", "batch_occupancy",
            "tpot_p95_unjudged_ms"} <= reported
    assert not {"decode_step_roofline", "latent_moe_step_roofline",
                "latent_attn_ms_per_step", "expert_ffn_ms_per_step",
                "decode_attn_ms_per_step"} & reported
    assert all(callable(spec.reader(n)) for n in reported)
    # the family's own metrics are the LAST entries of BENCHMARK.json
    # (a PR appends; tests/benchmark/test_benchmark_store.py:38 pinned
    # ``store_tokens_per_call`` there and to three cells, so that line
    # fails from this PR on and this cell does not join that metric);
    # the cell is on the engine's and the device's lists, whose readers
    # find its programs
    ours = set(METRICS[3:])
    assert {m["name"] for m in spec.doc["per_layer"][-len(ours):]} == ours
    assert ours | {"decode_step_ms", "prefill_ms_p50",
                   "device_idle_share"} <= reported
    for m in spec.doc["per_layer"]:
        if m["name"] in ours:
            assert m["workloads"] == [cell] and m["moves"] == "tpot_p50_ms"
    # a third of the prompts behind a prefix are the prefix and at most
    # a chunk more: the requests that register an entry at that depth
    sizes = traffic_mod.sizes(traffic, 400)
    shared = [s for s in sizes if s[2] >= 0]
    assert 0.6 < len(shared) / len(sizes) < 0.8
    assert 0.2 < sum(p <= 1024 + 64 for p, _, _ in shared) / len(shared) < 0.5


def test_the_published_configuration_counts_what_the_issue_counted():
    config = Spec().config(NAME)
    row = [json.loads(l) for l in open(CATALOG)
           if '"Qwen3-Next-80B-A3B-Instruct"' in l] \
        if os.path.exists(CATALOG) else []
    for published in row:
        changed = {k for k, v in published["config"].items()
                   if config.get(k) != v}
        assert changed == {"num_experts"} == set(config["reduced"])
        assert config["source"] == published["source_url"]
    assert config["published_num_experts"] == 512
    assert config["num_hidden_layers"] == 48
    assert config["vocab_size"] == 151936
    bm = gdn_bytes.gdn_moe(config)
    assert bm["kv_per_token"] == 12 * 2 * 2 * 256 * 2           # 24.6 KB
    assert bm["expert"] == 3 * 2048 * 512 * 2 and bm["held"] == 16
    # ISSUE 35: non-expert weights 3.49 GB + the head 0.62 GB a step
    assert 4.09e9 < bm["weights"] < 4.13e9
    # 36 layers x 2 (in and out) x (32 x 128 x 128 float32 + 3 x 8192 bf16)
    assert bm["state_per_row"] == 36 * 2 * (32 * 128 * 128 * 4
                                            + 3 * 8192 * 2)
    held = bm["expert_layers"] * bm["held"] * bm["expert"]
    embedding = 151936 * 2048 * 2
    assert 9.5e9 < bm["weights"] + held + embedding < 9.62e9    # 9.56 GB
    kernel = gdn_bytes.state_update(config, 2)
    assert kernel["bytes"] == 2 * 2 * 32 * 128 * 128 * 4
    assert kernel["layers"] == 36


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
    bm = gdn_bytes.gdn_moe(config)
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
        assert not {"gdn_moe_step_roofline", "gdn_state_update_roofline",
                    "gdn_ms_per_step"} & names
        assert 0 < result["metrics"]["state_slab_peak_share"]["value"] <= 100
        assert 0 < result["metrics"]["routed_here_share"]["value"] < 100
        if "state_restore_share" in names:
            assert 0 <= result["metrics"]["state_restore_share"]["value"] \
                <= 100
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
    ctx.bytes_model = kw.get("bytes_model") or gdn_bytes.gdn_moe(ctx.config)
    return ctx


def test_readers_find_nothing_in_a_program_without_the_spans():
    """The parent commit's program, or another family's cell: no
    ``state.*`` counters, no ``state_restored`` label, no kernel, a byte
    model without ``state_per_row``. Nothing raises."""
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
    ctx = _ctx(trace=trace, window_traces=old, rows=rows,
               counters_after={"sched.segments": 9},
               bytes_model={"weights": 1, "kv_per_token": 1})
    spec = Spec()
    for name in ("gdn_moe_step_roofline", "gdn_state_update_roofline",
                 "gdn_ms_per_step", "state_slab_peak_share",
                 "state_restore_share"):
        assert spec.reader(name)(ctx) is None, name
        assert spec.reader(name)(_ctx()) is None, name


def test_slab_and_restore_shares_from_counters_and_labels():
    head = " ".join(map(str, range(4)))

    def request(rid, restored):
        return {"request_id": rid, "spans": [
            {"name": "prefill", "start_ms": 0, "duration_ms": 1,
             "labels": {"state_restored": restored}}]}

    rows = [{"rid": "a", "ok": True, "text": head + " 9 9"},
            {"rid": "b", "ok": True, "text": head + " 8 8 8"},
            {"rid": "c", "ok": True, "text": head + " 7"},
            {"rid": "d", "ok": True, "text": "5 5 5 5 5 5"},   # its own
            {"rid": "e", "ok": False, "text": None}]
    traces = [request("a", 0), request("b", 4), request("c", 4),
              request("d", 0)]
    ctx = _ctx(rows=rows, window_traces=traces,
               samples=[{"sched.state.in_use": 3}, {"sched.state.in_use": 6},
                        {"sched.state.in_use": 4}],
               counters_after={"sched.state.peak": 24,    # set-up's, unread
                               "sched.state.slots": 24})
    assert readers.state_slab_peak_share(ctx) == pytest.approx(25.0)
    # a, b and c share a head of four tokens; two of them restored
    assert readers.state_restore_share(ctx, 4) == pytest.approx(200 / 3)


def test_calls_and_segments_pair_in_a_slice_that_opens_on_an_idle_device():
    """The cell's slice opens in a gap between two requests: the first
    device operation (a joiner's walk) comes 1.1 s after the profiler
    started, on a clock of the device's own, and the trace is written
    out 42 s later. Two whole decode calls and a piece; segments follow
    each other evenly, so a later offset would fit as well: the
    smallest one is taken. ``latent_moe.paired`` finds no segment here."""
    from benchmark.readers import latent_moe

    def decode(seg, ready_ms, hit):
        return {"name": "decode", "start_ms": ready_ms - 300.0,
                "duration_ms": 2.0,
                "labels": {"seg": seg, "steps": 32, "ready_ms": ready_ms,
                           "experts_hit": hit}}

    step_ns, origin = 8e6, 5e12
    call = 32 * step_ns
    # unix 1001.1 is the device's ``origin``; ready 3 ms after a call ends
    ready = [1100.0 + 120.0 + (k + 1) * call / 1e6 + 3.0 for k in range(5)]
    traces = [{"request_id": "a", "started_unix": 1000.0,
               "labels": {"prompt_tokens": 1025},
               "spans": [decode(4, -500.0, 1)]          # before the slice
               + [decode(7 + k, ready[k], 1000 * (k + 1)) for k in range(5)]}]
    mods = [("jit__extend_impl(2)", origin, 1e8),
            ("jit__decode_seg_impl(1)", origin + 1.2e8, call),
            ("jit__decode_seg_impl(1)", origin + 1.2e8 + call, call),
            ("jit__decode_seg_impl(1)", origin + 1.2e8 + 2 * call, 1e8)]
    ops = [("%fusion.1 = bf16[256,2048]{1,0} fusion(%p)", origin, 1e8),
           ("%fusion.2 = bf16[1,1,2048]{2,1,0} fusion(%p)",
            origin + 1.2e8 + 2 * call, 1e8)]
    trace = types.SimpleNamespace(devices=["d"], modules={"d": mods},
                                  ops={"d": ops})
    ctx = _ctx(trace=trace, window_traces=traces,
               trace_unix=(1000.0, 1045.0))
    pairs = readers.paired(ctx, "d", "decode_seg")
    assert [(e[1], s["experts_hit"]) for e, s in pairs] == [
        (origin + 1.2e8, 1000), (origin + 1.2e8 + call, 2000)]
    assert latent_moe.paired(ctx, "d", "decode_seg") == []
    bm = ctx.bytes_model
    live = (1025 + 33 + 16) + (1025 + 65 + 16)
    need = 32 * (2 * (bm["weights"] + bm["state_per_row"])
                 + bm["kv_per_token"] * live) + 3000 * bm["expert"]
    assert readers.gdn_moe_step_roofline(ctx, "decode_seg") == pytest.approx(
        100 * (need / 819e9) / (2 * call / 1e9))
    # ready instants that no offset lines up with both calls' ends: nothing
    for span in traces[0]["spans"]:
        if span["labels"]["seg"] in (8, 10):
            span["labels"]["ready_ms"] += 120.0
    assert readers.paired(ctx, "d", "decode_seg") == []


def test_step_and_kernel_rooflines_pair_bytes_and_time_by_segment():
    """One whole decode call between two pieces (as in the latent
    family's test): its segment's bytes (weights, two live rows' state in
    and out, their positions, the experts hit) over its time; the kernel
    by its short name, inside that call only."""
    def request(rid, prompt, spans):
        return {"request_id": rid, "started_unix": 990.0,
                "labels": {"prompt_tokens": prompt}, "spans": spans}

    def decode(seg, start_ms, ready_ms, hit, steps=32):
        return {"name": "decode", "start_ms": start_ms, "duration_ms": 2.0,
                "labels": {"seg": seg, "steps": steps, "ready_ms": ready_ms,
                           "experts_hit": hit}}

    traces = [
        request("a", 1000, [decode(6, 9000.0, 10050.0, 5000),
                            decode(7, 9500.0, 10400.0, 2000),
                            decode(8, 10500.0, 13500.0, 9000)]),
        request("b", 500, [decode(7, 9500.0, 10400.0, 2000),
                           decode(8, 10500.0, 13500.0, 9000)])]
    step_ns = 8e6
    at = 1e8
    mods = [("jit__decode_seg_impl(1)", 0.0, 5e7),
            ("jit__decode_seg_impl(1)", at, 32 * step_ns),
            ("jit__decode_seg_impl(1)", 2.8e9, 1e8)]
    kernel = ('%gdn_state_update.3 = (f32[2,32,1,128]{3,2,1,0}, '
              'f32[36,2,32,128,128]{4,3,2,1,0}) custom-call(%a, %b), '
              'custom_call_target="tpu_custom_call"')
    # names the kernel as an OPERAND: a search of the whole text would
    # count it, the short name does not
    after = "%fusion.9 = f32[2,32,128]{2,1,0} fusion(%gdn_state_update.3)"
    proj = "%fusion.12 = bf16[2,1,12288]{2,1,0} fusion(%p), kind=kOutput"
    other = "%fusion.41 = bf16[2,1,2048]{2,1,0} fusion(%p), kind=kLoop"
    ops = [(kernel, 0.0, 4e7),
           (kernel, at + 10.0, 32 * 36 * 1e4), (after, at + 20.0, 32 * 2e4),
           (proj, at + 30.0, 32 * 3e5), (other, at + 40.0, 32 * 5e5),
           (kernel, 2.8e9, 1e8)]
    trace = types.SimpleNamespace(devices=["d"], modules={"d": mods},
                                  ops={"d": ops})
    ctx = _ctx(trace=trace, window_traces=traces)
    bm = ctx.bytes_model
    live = (1000 + 1 + 32 + 16) + (500 + 1 + 16)
    need = 32 * (bm["weights"] + 2 * bm["state_per_row"]
                 + bm["kv_per_token"] * live) + 2000 * bm["expert"]
    got = readers.gdn_moe_step_roofline(ctx, "decode_seg")
    assert got == pytest.approx(100 * (need / 819e9) / (32 * step_ns / 1e9))
    assert 0 < got < 100
    got = readers.gdn_state_update_roofline(ctx, "gdn_state_update",
                                            "decode_seg")
    floor = 32 * 36 * (2 * 2 * 32 * 128 * 128 * 4 / 819e9)
    assert got == pytest.approx(100 * floor / (32 * 36 * 1e4 / 1e9))
    per_step = Spec().reader("gdn_ms_per_step")(ctx)
    assert per_step == pytest.approx(
        (32 * 36 * 1e4 + 32 * 2e4 + 32 * 3e5) / 1e6 / 32)
