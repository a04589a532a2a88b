"""The block-diffusion / sparse-expert configuration in the benchmark:
found by name in a copy of the fixture, run whole at a tiny size on the
CPU, its byte model against hand counts, its readers on traces with and
without what they read, and its check on built examples."""

import json
import os
import shutil
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import block_bytes, check_blocks
from benchmark.harness.spec import REPO, ROOT, Spec, resolve
from benchmark.readers import sdar_moe as readers
from benchmark.reference import sdar_moe as ref_mod

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "sdar-30b-a3b-ep8"
REAL_CELL = f"{NAME}.blockgen"
CELL = "tiny-sdar.tiny"
OURS = ("block_tokens_per_forward", "block_moe_step_roofline",
        "block_expert_tiles_ms_per_forward",
        "block_cache_slice_ms_per_forward")
METRICS = ("experts_hit_share", "routed_here_share",
           "expert_load_max_over_mean") + OURS
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_of(real):
    tiny = dict(real, hidden_size=64, vocab_size=512, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
                num_hidden_layers=3, num_experts=4, published_num_experts=8,
                first_expert=2, num_experts_per_tok=2,
                max_position_embeddings=512, mask_token_id=511)
    # served in float32, as the other tiny configurations are: at a width
    # of 64 bfloat16's own noise reads like the int8 control's
    tiny["serving_env"] = dict(real["serving_env"], MAX_BATCH="4",
                               MAX_SEQ="256", KV_POOL_BLOCKS="96",
                               PREFIX_CACHE="4", PREFIX_CHUNK="16",
                               INFERENCE_DTYPE="float32")
    tiny["check"] = dict(real["check"], requests=8, limits={
        "deficit_mean": 0.005, "deficit_max": 0.5}, own_limits={
        "noise_over_int8": 0.5, "own_over_other_row": 1.0,
        "choice_deficit_mean": 0.02, "choice_deficit_max": 0.1})
    return tiny


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """The fixture benchmark, copied, with the new configuration at a
    tiny size (three layers, 4 of 8 experts held), its cell and its
    per-layer metrics added beside it."""
    tmp = tmp_path_factory.mktemp("sdar")
    shutil.copytree(os.path.join(HERE, "fixture", "bench"), tmp / "bench")
    doc = json.load(open(os.path.join(HERE, "fixture", "BENCHMARK.json")))
    real = json.load(open(os.path.join(ROOT, "configs", f"{NAME}.json")))
    (tmp / "bench" / "configs" / "tiny-sdar.json").write_text(
        json.dumps(tiny_of(real)))
    shutil.copy(tmp / "bench" / "cells" / "tiny-llama.tiny.json",
                tmp / "bench" / "cells" / f"{CELL}.json")
    for name in METRICS:
        shutil.copy(os.path.join(ROOT, "layer_metrics", f"{name}.json"),
                    tmp / "bench" / "layer_metrics" / f"{name}.json")
    doc["configs"].append({"name": "tiny-sdar", "source": "none",
                           "file": "bench/configs/tiny-sdar.json",
                           "reduced": ["num_experts"], "why": "x"})
    doc["workloads"].append({"name": CELL, "config": "tiny-sdar",
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
    assert callable(reference.init) and callable(reference.generate)
    assert resolve(config["check"]["procedure"]) is check_blocks.served_blocks
    bm = resolve(config["bytes_model"])(config)
    assert bm["kv_per_token"] == 3 * 2 * 2 * 16 * 2 and bm["held"] == 4
    assert bm["expert_layers"] == 3
    assert {m["name"] for m in spec.metrics("per_layer", CELL)} >= set(METRICS)
    assert all(callable(spec.reader(n)) for n in METRICS)


def test_the_real_cell_its_traffic_and_its_metrics_are_found_by_name():
    spec = Spec()
    cell = spec.workload(REAL_CELL)
    assert cell["chips"] == 1 and cell["config"] == NAME
    traffic = spec.traffic(cell["traffic"])
    assert traffic["prompt"] == {"median": 192, "sigma": 0.8, "min": 32,
                                 "max": 1024}
    assert traffic["shared_prefix"] == {"count": 2, "tokens": 256,
                                        "share": 0.5}
    assert spec.cell(REAL_CELL)["rate_rps"] > 0
    mine = {m["name"] for m in spec.metrics("per_layer", REAL_CELL)}
    assert set(OURS) <= mine and "decode_step_ms" in mine
    assert "tpot_p50_ms" in {m["name"] for m in
                             spec.metrics("end_to_end", REAL_CELL)}
    for m in spec.doc["per_layer"]:
        if m["name"] in OURS:
            assert m["workloads"] == [REAL_CELL]
    # every prompt residue of a block takes part, and budgets end inside
    # blocks
    from benchmark.harness import traffic as traffic_mod
    sizes = traffic_mod.sizes(traffic, 40)
    assert {p % 4 for p, _, _ in sizes} == {0, 1, 2, 3}
    assert any((p + n) % 4 for p, n, _ in sizes)


def test_the_published_configuration_is_the_catalogs_but_for_the_experts():
    config = Spec().config(NAME)
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "SDAR-30B-A3B-Chat")
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == {"num_experts"} == set(config["reduced"])
    assert config["published_num_experts"] == row["config"]["num_experts"]
    for word in ("block_length", "mask_token_id", "denoising_steps",
                 "remasking"):
        assert word in config["assumed"], word
    family = resolve(config["family"])(**{
        k: config[v] for k, v in config["family_kwargs"].items()})
    assert (family.n_routed_total, family.n_routed_experts) == (128, 16)
    assert ref_mod.options(config) == {
        "block_length": 4, "denoising_steps": 2, "confidence_threshold": 0.9,
        "remasking": "low_confidence_dynamic", "mask_token_id": 151669}


def test_the_byte_model_against_hand_counts():
    """ISSUE 50's arithmetic: 19.1M parameters a layer outside the
    experts, 4.72M an expert, 98,304 B a cached position, 0.62 GB of
    head; and the leaves the reference makes."""
    config = Spec().config(NAME)
    bm = block_bytes.sdar_moe(config)
    assert bm["expert"] == 3 * 2048 * 768 * 2 == 9_437_184
    assert bm["kv_per_token"] == 2 * 4 * 128 * 2 * 48 == 98_304
    assert bm["head"] == 2048 * 151936 * 2
    layer = (2048 * 4096 * 2 + 2048 * 512 * 2 + 2048 * 128 + 2 * 128
             + 2 * 2048)
    assert 19.1e6 < layer < 19.2e6
    assert bm["body"] == (48 * layer + 2048) * 2
    assert bm["weights"] == bm["body"] + bm["head"]
    shapes = jax.eval_shape(lambda: ref_mod.sdar_moe.init(config, 0))
    total = sum(int(np.prod(x.shape)) * 2 for x in jax.tree.leaves(shapes))
    embedding = 151936 * 2048 * 2
    assert total == (bm["weights"] + embedding
                     + 48 * 16 * bm["expert"])
    assert 10.3e9 < total < 10.4e9
    # a call of 8 rounds, 24 forwards, 8 rows at depth 700
    need = block_bytes.call_bytes(bm, 24, 8, 24 * 48 * 14, 24 * 8 * 700)
    assert need == (24 * bm["body"] + 16 * bm["head"]
                    + 24 * 48 * 14 * bm["expert"]
                    + 24 * 8 * 700 * bm["kv_per_token"])


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
    assert result["correct"] is True and result["failed"] == 0, p.stdout[-2000:]
    names = set(result["metrics"])
    if trace:
        # counters and span labels are read; no device plane on the CPU
        assert {"experts_hit_share", "routed_here_share",
                "expert_load_max_over_mean",
                "block_tokens_per_forward"} <= names
        assert "block_moe_step_roofline" not in names
        # 4 positions a block over one or two denoise forwards and a
        # commit (at this width some confidences pass the threshold),
        # fewer where a prompt's tail fills part of a first block
        assert 1.0 < result["metrics"]["block_tokens_per_forward"][
            "value"] <= 2.0
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
    ctx.bytes_model = kw.get("bytes_model") or block_bytes.sdar_moe(
        ctx.config)
    return ctx


def test_readers_find_nothing_in_a_program_without_rounds():
    """The parent commit's program, or another family's cell: no
    ``sched.block.*`` counter, no ``rounds`` on a span. Nothing raises."""
    mods = [("jit__decode_seg_impl(123)", 0.0, 100.0)]
    trace = types.SimpleNamespace(devices=["d"], modules={"d": mods},
                                  ops={"d": []})
    old = [{"request_id": "a", "started_unix": 1000.0,
            "labels": {"prompt_tokens": 100},
            "spans": [{"name": "decode", "start_ms": 10, "duration_ms": 1,
                       "labels": {"seg": 3, "steps": 32, "ready_ms": 400.0,
                                  "experts_hit": 7}}]}]
    other = Spec().config("qwen3-next-80b-ep32")
    ctx = _ctx(trace=trace, window_traces=old, config=other,
               counters_after={"sched.segments": 9},
               counters_before={"sched.segments": 1},
               bytes_model={"weights": 1, "kv_per_token": 1, "expert": 1})
    spec = Spec()
    # the two readers this PR wrote; the other two metrics are data for
    # an accepted reader (``readers/device.py:op_ms_per_step``)
    for name in OURS[:2]:
        assert spec.reader(name)(ctx) is None, name
        assert spec.reader(name)(_ctx()) is None, name
    for name in OURS[2:]:
        assert spec.reader(name)(_ctx()) is None, name


def test_tokens_per_forward_is_a_quotient_of_the_windows_deltas():
    ctx = _ctx(counters_before={"sched.block.tokens_fixed": 100,
                                "sched.block.row_forwards": 60,
                                "sched.block.forwards": 30},
               counters_after={"sched.block.tokens_fixed": 500,
                               "sched.block.row_forwards": 360,
                               "sched.block.forwards": 130})
    assert readers.block_tokens_per_forward(ctx) == pytest.approx(4 / 3)


def test_the_step_roofline_pairs_bytes_and_time_by_segment():
    """One whole decode call between two pieces: its own segment's
    forwards, rounds, experts and live depths over its own time."""
    def request(rid, prompt, spans):
        return {"request_id": rid, "started_unix": 990.0,
                "labels": {"prompt_tokens": prompt}, "spans": spans}

    def decode(seg, start_ms, ready_ms, hit, rounds, tokens):
        return {"name": "decode", "start_ms": start_ms, "duration_ms": 2.0,
                "labels": {"seg": seg, "steps": 3 * rounds, "rounds": rounds,
                           "tokens": tokens, "ready_ms": ready_ms,
                           "experts_hit": hit}}

    traces = [
        request("a", 1000, [decode(6, 9000.0, 10050.0, 5000, 8, 31),
                            decode(7, 9500.0, 10364.0, 2000, 8, 32),
                            decode(8, 10500.0, 13500.0, 9000, 8, 32)]),
        request("b", 500, [decode(7, 9500.0, 10364.0, 2000, 8, 30),
                           decode(8, 10500.0, 13500.0, 9000, 8, 32)])]
    forward_ns = 11e6
    at = 1e8
    mods = [("jit__decode_seg_impl(1)", 0.0, 5e7),
            ("jit__decode_seg_impl(1)", at, 24 * forward_ns),
            ("jit__decode_seg_impl(1)", 2.8e9, 1e8)]
    op = "%fusion.1 = bf16[32,2048]{1,0} fusion(%p)"
    trace = types.SimpleNamespace(
        devices=["d"], modules={"d": mods},
        ops={"d": [(op, 0.0, 4e7), (op, at + 10.0, 1e4), (op, 2.8e9, 1e8)]})
    ctx = _ctx(trace=trace, window_traces=traces)
    bm = ctx.bytes_model
    depths = (1000 + 31 + 16) + (500 + 15)
    need = (24 * bm["body"] + 16 * bm["head"] + 2000 * bm["expert"]
            + 24 * depths * bm["kv_per_token"])
    got = readers.block_moe_step_roofline(ctx, "decode_seg")
    assert got == pytest.approx(
        100 * (need / 819e9) / (24 * forward_ns / 1e9))
    assert 0 < got < 100


# what the traced slice's operations are called (my chip run, PR 50)
TILE_UP, TILE_DOWN = "fusion bf16[128,768]", "fusion bf16[128,2048]"
CACHE_SLICE = "dynamic-slice_bitcast_fusion bf16[8,4,2048,256]"
HEAD = "fusion f32[8,4,151936]"


@pytest.mark.parametrize("metric, takes, leaves", [
    ("block_expert_tiles_ms_per_forward", (TILE_UP, TILE_DOWN),
     (CACHE_SLICE, HEAD, "fusion f32[8,4]")),
    ("block_cache_slice_ms_per_forward",
     (CACHE_SLICE, CACHE_SLICE.replace("[8,", "[2,")),
     (TILE_UP, TILE_DOWN, HEAD)),
    # why the cell is not on the accepted expert metric's list: its
    # second pattern takes this family's fused [K | V] rows of 256 too
    ("expert_ffn_ms_per_step", (TILE_UP, TILE_DOWN, CACHE_SLICE), (HEAD,))])
def test_the_operation_patterns_take_what_they_name(metric, takes, leaves):
    import re
    params = json.load(open(os.path.join(
        ROOT, "layer_metrics", f"{metric}.json")))["params"]
    assert params["module_pattern"] == "decode_seg"
    for name in takes:
        assert re.search(params["op_pattern"], name), name
    for name in leaves:
        assert not re.search(params["op_pattern"], name), name


# -- the check on built examples -------------------------------------------

SIZES = {"hidden_size": 64, "vocab_size": 256, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16,
         "moe_intermediate_size": 32, "num_experts": 4,
         "published_num_experts": 8, "first_expert": 0,
         "num_experts_per_tok": 2, "norm_topk_prob": True,
         "num_hidden_layers": 3, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
         "block_length": 4, "denoising_steps": 2,
         "confidence_threshold": 0.9,
         "remasking": "low_confidence_dynamic", "mask_token_id": 255}


@pytest.fixture(scope="module")
def built():
    """Four requests answered by the reference's own loop."""
    ref = ref_mod.sdar_moe
    params = ref.init(SIZES, 7, jnp.float32)
    rng = np.random.RandomState(0)
    triples = []
    for p, n in ((8, 16), (9, 15), (11, 14), (13, 23)):
        prompt = rng.randint(0, 255, p).tolist()
        g = ref.generate(params, SIZES, prompt, n)
        triples.append((prompt, g["tokens"], g["fixed_at"]))
    return ref, params, triples


def test_one_pass_gives_every_denoise_forwards_logits(built):
    """The layout's logits at a (forward, position) are the loop's."""
    ref, params, triples = built
    prompt, new, fixed_at = triples[0]      # a prompt of whole blocks
    o = ref_mod.options(SIZES)
    g = ref.generate(params, SIZES, prompt, len(new))
    ids, pos, seen, at, scored = ref_mod.denoise_layout(prompt, new,
                                                        fixed_at, o)
    assert scored == len(new) - (len(prompt) + len(new)) % 4
    lo = len(prompt) - len(prompt) % 4
    # the second block's two forwards
    for f, seen_by_loop in ((0, g["logits"][2]), (1, g["logits"][3])):
        out = [at[f] + (lo + 4 - lo) + i for i in range(4)]
        z = np.asarray(ref.forward(params, SIZES, ids, pos, seen, out))
        np.testing.assert_allclose(z, seen_by_loop, atol=2e-4)


CHECK = {"limits": {"deficit_mean": 0.005, "deficit_max": 0.5},
         "own_limits": {"noise_over_int8": 0.5, "own_over_other_row": 1.0,
                        "choice_deficit_mean": 0.02,
                        "choice_deficit_max": 0.1}}


def test_a_right_answer_scores_near_nothing(built):
    ref, params, triples = built
    got = check_blocks.score(ref, params, dict(SIZES, check=CHECK), triples,
                             control=True)
    assert got["tokens"] == sum(
        len(n) - (len(p) + len(n)) % 4 for p, n, _ in triples)
    readings = got["readings"]
    assert set(readings) == set(check_blocks._READINGS)
    assert readings["deficit_max"] < 1e-3
    assert readings["choice_deficit_max"] < 1e-3
    assert readings["choice_deficit_mean"] < 1e-3
    assert readings["own_over_other_row"] == 0.0
    assert readings["noise_over_int8"] < 1e-2
    assert check_blocks.over(readings, CHECK) == []


@pytest.mark.parametrize("control, by", [
    ("int8", "noise_over_int8"), ("turned", "choice_deficit_mean")])
def test_a_control_comes_out_not_correct_by_the_same_comparison(
        built, control, by):
    """Each control's readings go through ``over``, the comparison that
    decides ``correct``, and the control says which limits it broke."""
    ref, params, triples = built
    got = check_blocks.score(ref, params, dict(SIZES, check=CHECK), triples,
                             control=True)["control"][control]
    assert by in got["over"]
    assert got["over"] == check_blocks.over(got, CHECK)
    if control == "int8":
        assert got["noise_over_int8"] == 1.0
    else:
        assert got["choice_deficit_mean"] > 0.05
        assert got["choice_deficit_max"] > 0.1


@pytest.fixture(scope="module")
def alike():
    """Three requests behind ONE shared prefix and one behind none."""
    ref = ref_mod.sdar_moe
    params = ref.init(SIZES, 7, jnp.float32)
    rng = np.random.RandomState(1)
    prefix = rng.randint(0, 255, 64).tolist()
    triples = []
    for tail, n in ((8, 16), (11, 16), (5, 15), (0, 16)):
        prompt = (prefix if tail else []) + rng.randint(
            0, 255, tail or 21).tolist()
        g = ref.generate(params, SIZES, prompt, n)
        triples.append((prompt, g["tokens"], g["fixed_at"]))
    return ref, params, triples


def test_every_wrong_row_is_caught_behind_one_prefix_too(alike):
    """Each request's tokens fit its own prompt better than its
    partner's do, and a pair swapped reads the reciprocal: every pair
    counts, those behind one prefix too."""
    ref, params, triples = alike
    assert check_blocks._partners(triples) == [1, 2, 0, 0]
    got = check_blocks.score(ref, params, dict(SIZES, check=CHECK), triples,
                             control=True)
    assert got["readings"]["own_over_other_row"] < 0.01
    wrong = got["control"]["wrong_row"]
    assert wrong["pairs"] == wrong["told"] == wrong["caught"] == 4
    assert wrong["one_prefix"]["pairs"] == wrong["one_prefix"]["caught"] == 3
    assert wrong["own_over_other_row"] > 100
    assert len(got["control"]["pairs"]) == 4


def test_two_answers_that_rounding_could_have_made_are_not_told_apart(alike):
    """A partner whose answer fits this prompt as closely as the prompt's
    own (here: the same answer) is no evidence either way: the pair does
    not count, and the control says so instead of reading a chance."""
    ref, params, triples = alike
    twice = [triples[0], triples[0]]
    got = check_blocks.score(ref, params, dict(SIZES, check=CHECK), twice,
                             control=True)
    assert got["readings"]["own_over_other_row"] == 0.0
    wrong = got["control"]["wrong_row"]
    assert (wrong["pairs"], wrong["told"], wrong["caught"]) == (2, 0, 0)
    assert wrong["own_over_other_row"] is None


def test_rows_served_each_others_tokens_come_out_not_correct(alike):
    """The fault itself, not its control: two requests behind one
    prefix served each other's answers read over the limit of 1."""
    ref, params, triples = alike
    (p0, t0, f0), (p1, t1, f1) = triples[:2]
    swapped = [(p0, t1, f0), (p1, t0, f1)] + triples[2:]
    got = check_blocks.score(ref, params, dict(SIZES, check=CHECK),
                             swapped)["readings"]
    assert got["own_over_other_row"] > 1.0
    assert "own_over_other_row" in check_blocks.over(got, CHECK)


def test_over_names_what_lies_over_its_limit_and_takes_nan_for_over():
    assert check_blocks.over({"deficit_mean": 0.004, "deficit_max": 0.6,
                              "noise_over_int8": float("nan"),
                              "choice_deficit_max": 0.1}, CHECK) == [
        "noise_over_int8", "deficit_max"]
    assert check_blocks.over({"unlimited": 9.0}, CHECK) == []


def test_a_shifted_fixed_at_or_another_rows_tokens_break_the_readings(built):
    ref, params, triples = built
    o = ref_mod.options(SIZES)
    prompt, new, fixed_at = triples[0]
    shifted = list(fixed_at)
    shifted[shifted.index(0)] = 1      # said to be fixed a forward later
    # one entry shifted is no schedule the rule could have made: the
    # floor is 2 a forward (``served_blocks`` then reads infinite)
    assert not check_blocks.valid_schedule(len(prompt), shifted, o)
    assert check_blocks.valid_schedule(len(prompt), fixed_at, o)
    assert not check_blocks.valid_schedule(len(prompt), [2] * len(new), o)
    # a schedule the rule COULD have made and did not (every block's
    # forwards turned round): the positions said to be fixed first are
    # not the reference's most confident, and tokens said to be chosen
    # at forward 0 were chosen with two of their block in sight
    turned = [1 - f for f in fixed_at]
    assert check_blocks.valid_schedule(len(prompt), turned, o)
    sound = check_blocks.score(ref, params, SIZES, [triples[0]])["readings"]
    late = check_blocks.score(ref, params, SIZES,
                              [(prompt, new, turned)])["readings"]
    assert late["choice_deficit_max"] > sound["choice_deficit_max"] + 0.1
    assert late["choice_deficit_mean"] > sound["choice_deficit_mean"] + 0.05
    assert late["deficit_max"] > sound["deficit_max"] + 0.1
    other = check_blocks.score(
        ref, params, SIZES,
        [(triples[1][0], new[:15], triples[1][2])])["readings"]
    assert other["deficit_max"] > 1.0


def test_a_malformed_answer_or_schedule_reads_infinite(built):
    ref, params, triples = built
    prompt, new, fixed_at = triples[0]
    config = dict(SIZES, check={"requests": 4})
    arrivals = [types.SimpleNamespace(prompt_ids=tuple(prompt))]

    def served(spans):
        return types.SimpleNamespace(
            config=config, reference=ref, params=params,
            traces=lambda: {"r": {"spans": spans}})

    def decode(start, fixed):
        return {"name": "decode", "start_ms": start, "duration_ms": 1.0,
                "labels": {"fixed_at": fixed}}

    row = {"k": 0, "rid": "r", "ok": True, "max_new": len(new),
           "text": " ".join(map(str, prompt + new))}
    good = [decode(5.0, fixed_at[8:]), decode(1.0, fixed_at[:8])]
    got = check_blocks.served_blocks(served(good), arrivals, [row])
    assert got["readings"]["deficit_max"] < 1e-3 and got["requests"] == 1
    assert got["over"] == [] and "as_read" not in got
    # a schedule the rule could have made and did not: its choice
    # reading passes its own limit, the result names it, keeps what was
    # read and says not correct through the two readings run.py compares
    config["check"]["own_limits"] = {"choice_deficit_mean": 0.02}
    turned = [decode(1.0, [1 - f for f in fixed_at])]
    got = check_blocks.served_blocks(served(turned), arrivals, [row])
    assert got["over"] == ["choice_deficit_mean"]
    assert got["readings"]["choice_deficit_mean"] > 0.02
    assert got["readings"]["deficit_max"] == float("inf")
    assert got["as_read"]["deficit_max"] < float("inf")
    got = check_blocks.served_blocks(served(good), arrivals, [row])
    assert got["readings"]["deficit_max"] < 1e-3 and got["over"] == []
    for spans, r in ((good[:1], row), (good, dict(row, max_new=99)),
                     ([decode(1.0, [3] * len(new))], row)):
        got = check_blocks.served_blocks(served(spans), arrivals, [r])
        assert got["readings"]["deficit_mean"] == float("inf")
