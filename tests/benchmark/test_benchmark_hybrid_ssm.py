"""The state-space / attention configuration in the benchmark: found by
name in a copy of the fixture, run whole at a tiny size on the CPU, its
byte model against the issue's arithmetic and a count of the leaves,
and its readers on traces with and without what they read.

The guide's "shares add up" test does not apply: no share of a layer is
cut (no head, group, feed-forward column or vocabulary row): the
configuration's one reduction is depth, six whole layers of 72."""

import json
import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

from benchmark.harness import ssm_bytes, traffic as traffic_mod
from benchmark.harness.spec import REPO, ROOT, Spec, resolve
from benchmark.readers import hybrid_ssm as readers

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "falcon-h1-34b-l6"
REAL_CELL = f"{NAME}.burstchat"
CELL = "tiny-hybrid.tiny"
METRICS = ("ssm_hybrid_step_roofline", "ssm_state_update_roofline",
           "ssm_ms_per_step", "ssm_restore_share", "slab_mb_moved_per_step",
           "state_slab_peak_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_of(real):
    tiny = dict(real, hidden_size=64, vocab_size=512, num_attention_heads=10,
                num_key_value_heads=2, head_dim=32, intermediate_size=96,
                mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
                mamba_d_state=24, mamba_n_groups=2, mamba_chunk_size=16,
                num_hidden_layers=3, max_position_embeddings=512)
    tiny["serving_env"] = dict(real["serving_env"], MAX_BATCH="4",
                               MAX_SEQ="256", KV_POOL_BLOCKS="96",
                               PREFIX_CACHE="4", PREFIX_CHUNK="16")
    tiny["check"] = dict(real["check"], requests=8, limits={
        "deficit_mean": 0.02, "deficit_max": 0.5})
    return tiny


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """The fixture benchmark, copied, with the new configuration at a
    tiny size (three layers, the published multipliers), its cell and
    its per-layer metrics added beside it."""
    tmp = tmp_path_factory.mktemp("hybrid")
    shutil.copytree(os.path.join(HERE, "fixture", "bench"), tmp / "bench")
    doc = json.load(open(os.path.join(HERE, "fixture", "BENCHMARK.json")))
    real = json.load(open(os.path.join(ROOT, "configs", f"{NAME}.json")))
    (tmp / "bench" / "configs" / "tiny-hybrid.json").write_text(
        json.dumps(tiny_of(real)))
    shutil.copy(tmp / "bench" / "cells" / "tiny-llama.tiny.json",
                tmp / "bench" / "cells" / f"{CELL}.json")
    for name in METRICS:
        doc_m = json.load(open(os.path.join(ROOT, "layer_metrics",
                                            f"{name}.json")))
        if name == "ssm_restore_share":
            # the fixture's traffic shares prefixes of its own length
            fixture = json.load(open(tmp / "bench" / "traffic" / "tiny.json"))
            doc_m["params"]["prefix_tokens"] = \
                fixture["shared_prefix"]["tokens"]
        (tmp / "bench" / "layer_metrics" / f"{name}.json").write_text(
            json.dumps(doc_m))
    doc["configs"].append({"name": "tiny-hybrid", "source": "none",
                           "file": "bench/configs/tiny-hybrid.json",
                           "reduced": ["num_hidden_layers"], "why": "x"})
    doc["workloads"].append({"name": CELL, "config": "tiny-hybrid",
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
    assert config["num_hidden_layers"] == 3
    reference = resolve(config["reference"])
    assert callable(reference.init) and callable(reference.logits)
    bm = resolve(config["bytes_model"])(config)
    assert bm["kv_per_token"] == 3 * 2 * 2 * 32 * 2
    assert bm["state_per_row"] == 3 * 2 * (4 * 16 * 24 * 4 + 3 * 160 * 2)
    assert {m["name"] for m in spec.metrics("per_layer", CELL)} >= set(METRICS)
    assert all(callable(spec.reader(n)) for n in METRICS)
    # the family's class takes the file's keys, lists and the theta that
    # is an integer past 32 bits among them
    from benchmark.harness import server
    model = server.family_config(config)
    assert model.ssm_multipliers == tuple(config["ssm_multipliers"])
    assert model.mlp_multipliers == tuple(config["mlp_multipliers"])
    assert model.rope_theta == 1e11 and hash(model) is not None


def test_the_real_cell_its_traffic_and_its_metrics_are_found_by_name():
    spec = Spec()
    entry = spec.workload(REAL_CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NAME, "burstchat", 1)
    # ISSUE 42's table, to the letter
    traffic = spec.traffic("burstchat")
    assert (traffic["arrival"], traffic["burst_mean"],
            traffic["burst_gap_s"]) == ("bursts", 4, 0.02)
    assert traffic["prompt"] == {"median": 128, "sigma": 0.8, "min": 16,
                                 "max": 768}
    assert traffic["output"] == {"median": 64, "sigma": 0.6, "min": 16,
                                 "max": 256}
    assert traffic["shared_prefix"] == {"count": 2, "tokens": 128,
                                        "share": 0.5}
    assert traffic["base_seed"] == 20261002
    cell = spec.cell(REAL_CELL)
    assert cell["rate_rps"] == pytest.approx(0.8 * cell["knee_rps"])
    assert [line["rate_rps"] for line in cell["sweep"]][:6] == [
        1.0, 2.0, 3.0, 4.0, 5.0, 6.0][:len(cell["sweep"])]
    judged = {m["name"] for m in spec.metrics("end_to_end", REAL_CELL)}
    assert judged == {"tpot_p50_ms", "setup_s"}
    reported = {m["name"] for m in spec.metrics("per_layer", REAL_CELL)}
    ours = set(METRICS[:5])
    assert ours | {"decode_step_ms", "prefill_ms_p50", "device_idle_share",
                   "state_slab_peak_share",
                   "prefix_token_share", "pool_peak_share",
                   "batch_occupancy", "tpot_p95_unjudged_ms"} <= reported
    # the dense family's byte model and the test-pinned store and
    # scheduler metrics stay with their cells; `decode_attn_ms_per_step`
    # sums every `tpu_custom_call`, which here is the state kernel too
    # (as in `threads`); no other family's metric reads this cell
    assert not {"decode_step_roofline", "store_tokens_per_call",
                "decode_attn_ms_per_step", "sched_idle_share",
                "sched_host_ms_per_call", "device_idle_with_work_share",
                "gdn_moe_step_roofline", "gdn_ms_per_step",
                "state_restore_share", "swa_restore_share",
                "experts_hit_share", "latent_moe_step_roofline"} & reported
    assert all(callable(spec.reader(n)) for n in reported)
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert all(names.count(n) == 1 for n in ours)
    for m in spec.doc["per_layer"]:
        if m["name"] in ours:
            assert REAL_CELL in m["workloads"]
            assert m["moves"] == "tpot_p50_ms"
    # the window at the cell's rate: 120 requests or more, half of them
    # behind a prefix, prompts up to the clip, every burst inside 0.1 s
    n = traffic_mod.count(cell["rate_rps"], 51)
    sizes = traffic_mod.sizes(traffic, n)
    assert n >= 120 and max(p for p, _, _ in sizes) <= 768
    assert 0.4 < sum(pid >= 0 for *_, pid in sizes) / n < 0.6
    gaps = traffic_mod.gaps(traffic, n, 51.0)
    inside = sum(g == 0.02 for g in gaps)
    assert 0.65 < inside / n < 0.85          # bursts of four on average


def test_the_published_configuration_counts_what_the_issue_counted():
    config = Spec().config(NAME)
    row = [json.loads(l) for l in open(CATALOG)
           if '"Falcon-H1-34B-Instruct"' in l] \
        if os.path.exists(CATALOG) else []
    for published in row:
        changed = {k for k, v in published["config"].items()
                   if config.get(k) != v}
        assert changed == {"num_hidden_layers"} == set(config["reduced"])
        assert config["source"] == published["source_url"]
    assert config["num_hidden_layers"] == 6
    assert config["vocab_size"] == 261120
    assert (config["mamba_d_ssm"], config["mamba_d_state"],
            config["mamba_n_groups"]) == (4096, 256, 2)
    bm = ssm_bytes.hybrid_ssm(config)
    # ISSUE 42: a layer is 430,120,032 parameters, 860.2 MB
    assert bm["layer"] == 430_120_032 * 2
    # in_proj 5,120 x 9,248, convolution 25,600, 96 + 4,096, out_proj
    assert bm["mixer"] == (47_349_760 + 25_600 + 96 + 4_096
                           + 20_971_520) * 2
    # six layers + the head (+ the final norm): 7,835 MB a step
    assert bm["weights"] == (6 * 430_120_032 + 261120 * 5120 + 5120) * 2
    assert 7.835e9 < bm["weights"] < 7.836e9
    assert bm["kv_per_token"] == 6 * 2 * 4 * 128 * 2 == 12288
    # a record is 25.35 MB; read and written a step: 50.7 MB a live row
    assert bm["state_per_row"] == 2 * 6 * (32 * 128 * 256 * 4
                                           + 3 * 5120 * 2)
    assert 25.34e6 < bm["state_per_row"] / 2 < 25.36e6
    embedding = 261120 * 5120 * 2
    assert 10.50e9 < bm["weights"] + embedding < 10.52e9        # 10.51 GB
    kernel = ssm_bytes.state_update(config, 2)
    assert kernel["bytes"] == 2 * 2 * 4_194_304 and kernel["layers"] == 6
    assert set(config["assumed"]) >= {"state_types", "weights",
                                      "multipliers", "column_order"}
    assert "12" in config["deployment"] and config["check"]["requests"] == 32


def test_the_byte_model_is_a_count_of_the_leaves():
    """Every leaf the reference's ``init`` makes at the published sizes
    (shapes alone), but the embedding, is what a step reads: the byte
    model counts exactly those, and the family's own ``init_params``
    makes the same tree."""
    config = Spec().config(NAME)
    shapes = jax.eval_shape(
        lambda: resolve(config["reference"]).init(config, 0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = sum(leaf.size * leaf.dtype.itemsize for path, leaf in flat
                if getattr(path[0], "key", None) != "wte")
    assert ssm_bytes.hybrid_ssm(config)["weights"] == total
    from benchmark.harness import server
    from llm_sharding_demo_tpu.models import hybrid_ssm
    own = jax.eval_shape(lambda: hybrid_ssm.init_params(
        server.family_config(config), jax.random.PRNGKey(0), "bfloat16"))
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), own)
            == jax.tree.map(lambda x: (x.shape, x.dtype), shapes))


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
        assert {"slab_mb_moved_per_step", "state_slab_peak_share"} <= names
        assert not {"ssm_hybrid_step_roofline", "ssm_state_update_roofline",
                    "ssm_ms_per_step"} & names
        assert 0 < result["metrics"]["state_slab_peak_share"]["value"] <= 100
        assert result["metrics"]["slab_mb_moved_per_step"]["value"] > 0
        if "ssm_restore_share" in names:
            assert 0 <= result["metrics"]["ssm_restore_share"]["value"] <= 100
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
    ctx.bytes_model = kw.get("bytes_model") or ssm_bytes.hybrid_ssm(ctx.config)
    return ctx


def test_readers_find_nothing_in_a_program_without_the_spans():
    """The parent commit's program, or another family's cell: no
    ``state.rows_*`` counters, no kernel, a byte model without
    ``mixer``. Nothing raises."""
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
               counters_before={"sched.segments": 1},
               counters_after={"sched.segments": 9, "sched.state.slots": 24},
               bytes_model={"weights": 1, "kv_per_token": 1,
                            "state_per_row": 1})
    spec = Spec()
    for name in METRICS[:5]:
        assert spec.reader(name)(ctx) is None, name
        assert spec.reader(name)(_ctx()) is None, name


def test_slab_megabytes_a_step_and_the_restore_share():
    head = " ".join(map(str, range(4)))

    def request(rid, restored, decodes):
        return {"request_id": rid, "spans": [
            {"name": "prefill", "start_ms": 0, "duration_ms": 1,
             "labels": {"state_restored": restored}}] + [
            {"name": "decode", "start_ms": 1, "duration_ms": 1,
             "labels": {"seg": seg, "steps": steps}}
            for seg, steps in decodes]}

    rows = [{"rid": "a", "ok": True, "text": head + " 9 9"},
            {"rid": "b", "ok": True, "text": head + " 8 8 8"},
            {"rid": "c", "ok": True, "text": "5 5 5 5 5 5"}]
    # segments 1, 2 and 3 ran 32, 8 and 20 steps; rows share them
    traces = [request("a", 0, [(1, 32), (2, 8)]),
              request("b", 4, [(2, 8), (3, 20)]),
              request("c", 0, [(3, 20)])]
    ctx = _ctx(rows=rows, window_traces=traces,
               counters_before={"sched.state.rows_gathered": 100,
                                "sched.state.rows_scattered": 90,
                                "sched.state.row_bytes": 25_350_144},
               counters_after={"sched.state.rows_gathered": 112,
                               "sched.state.rows_scattered": 98,
                               "sched.state.row_bytes": 25_350_144})
    assert readers.slab_mb_moved_per_step(ctx) == pytest.approx(
        (12 + 8) * 25.350144 / 60)
    # a and b share a head of four tokens; b restored. The metric's own
    # file asks for the traffic's 128, which these toy prompts lack
    from benchmark.readers import gdn_moe
    assert gdn_moe.state_restore_share(ctx, 4) == pytest.approx(50.0)
    assert Spec().reader("ssm_restore_share")(ctx) is None


def test_step_and_kernel_rooflines_pair_bytes_and_time_by_segment():
    """One whole decode call between two pieces: its segment's bytes
    (weights, two live rows' state in and out in six layers, their
    positions in six layers) over its time; the kernel by its short
    name, inside that call only; the mixer's operations by shape."""
    def request(rid, prompt, spans):
        return {"request_id": rid, "started_unix": 990.0,
                "labels": {"prompt_tokens": prompt}, "spans": spans}

    def decode(seg, start_ms, ready_ms, steps=32):
        return {"name": "decode", "start_ms": start_ms, "duration_ms": 2.0,
                "labels": {"seg": seg, "steps": steps, "ready_ms": ready_ms}}

    # segment 7 is stamped ready 3 ms after its call ends (990 s + 10,487
    # ms = 1000 s + 100 ms + 32 x 12 ms + 3 ms)
    traces = [
        request("a", 300, [decode(6, 9000.0, 10050.0),
                           decode(7, 9500.0, 10487.0),
                           decode(8, 10500.0, 13500.0)]),
        request("b", 100, [decode(7, 9500.0, 10487.0),
                           decode(8, 10500.0, 13500.0)])]
    step_ns = 12e6
    at = 1e8
    mods = [("jit__decode_seg_impl(1)", 0.0, 5e7),
            ("jit__decode_seg_impl(1)", at, 32 * step_ns),
            ("jit__decode_seg_impl(1)", 2.8e9, 1e8)]
    kernel = ('%ssm_state_update.3 = (f32[2,32,1,128]{3,2,1,0}, '
              'f32[6,2,32,256,128]{4,3,2,1,0}) custom-call(%a, %b), '
              'custom_call_target="tpu_custom_call"')
    # names the kernel as an OPERAND: a search of the whole text would
    # count it, the short name does not
    after = "%fusion.9 = f32[2,32,128]{2,1,0} fusion(%ssm_state_update.3)"
    proj = "%fusion.12 = bf16[2,1,9248]{2,1,0} fusion(%p), kind=kOutput"
    other = "%fusion.41 = bf16[2,1,5120]{2,1,0} fusion(%p), kind=kLoop"
    ffn = "%fusion.42 = bf16[2,1,21504]{2,1,0} fusion(%p), kind=kLoop"
    ops = [(kernel, 0.0, 4e7),
           (kernel, at + 10.0, 32 * 6 * 3e4), (after, at + 20.0, 32 * 2e4),
           (proj, at + 30.0, 32 * 3e5), (other, at + 40.0, 32 * 5e5),
           (ffn, at + 50.0, 32 * 9e5), (kernel, 2.8e9, 1e8)]
    trace = types.SimpleNamespace(devices=["d"], modules={"d": mods},
                                  ops={"d": ops})
    ctx = _ctx(trace=trace, window_traces=traces)
    bm = ctx.bytes_model
    live = (300 + 1 + 32 + 16) + (100 + 1 + 16)
    need = 32 * (bm["weights"] + 2 * bm["state_per_row"]
                 + bm["kv_per_token"] * live)
    got = readers.ssm_hybrid_step_roofline(ctx, "decode_seg")
    assert got == pytest.approx(100 * (need / 819e9) / (32 * step_ns / 1e9))
    assert 75 < got < 100
    got = readers.ssm_state_update_roofline(ctx, "ssm_state_update",
                                            "decode_seg")
    floor = 32 * 6 * (2 * 2 * 32 * 256 * 128 * 4 / 819e9)
    assert got == pytest.approx(100 * floor / (32 * 6 * 3e4 / 1e9))
    assert 0 < got < 100
    per_step = Spec().reader("ssm_ms_per_step")(ctx)
    assert per_step == pytest.approx(
        (32 * 6 * 3e4 + 32 * 2e4 + 32 * 3e5) / 1e6 / 32)
    # another family's byte model: nothing
    ctx.bytes_model = {"weights": 1, "kv_per_token": 1, "state_per_row": 1}
    assert readers.ssm_hybrid_step_roofline(ctx, "decode_seg") is None
    assert readers.ssm_state_update_roofline(ctx, "ssm_state_update",
                                             "decode_seg") is None
