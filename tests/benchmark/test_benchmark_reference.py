"""The plain reference against the program's own models at a tiny size,
and the control against the limit it has to break."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, server
from benchmark.reference import dense

HERE = os.path.dirname(os.path.abspath(__file__))
TINY_LLAMA = json.load(open(os.path.join(
    HERE, "fixture", "bench", "configs", "tiny-llama.json")))
TINY_GPT2 = {
    "n_embd": 64, "n_head": 4, "n_layer": 2, "n_positions": 128,
    "vocab_size": 384, "layer_norm_epsilon": 1e-05,
    "family": "llm_sharding_demo_tpu.models.gpt2:GPT2Config",
    "family_kwargs": {"vocab_size": "vocab_size", "n_positions": "n_positions",
                      "n_embd": "n_embd", "n_layer": "n_layer",
                      "n_head": "n_head",
                      "layer_norm_epsilon": "layer_norm_epsilon"}}

CASES = [("llama", dense.llama, TINY_LLAMA,
          "llm_sharding_demo_tpu.models.llama"),
         ("gpt2", dense.gpt2, TINY_GPT2, "llm_sharding_demo_tpu.models.gpt2")]


@pytest.mark.parametrize("name,ref,sizes,module", CASES,
                         ids=[c[0] for c in CASES])
def test_reference_agrees_with_the_programs_forward(name, ref, sizes, module):
    import importlib
    model = importlib.import_module(module)
    cfg = server.family_config(sizes)
    params = ref.init(sizes, 2**31 + 7, jnp.float32)
    ids = np.random.default_rng(0).integers(0, sizes["vocab_size"], 24)
    want = np.asarray(ref.logits(params, sizes, ids, list(range(24))))
    got = np.asarray(model.forward(params, jnp.asarray(ids[None]), cfg))[0]
    centred = want - want.mean(-1, keepdims=True)
    assert (((got - want) ** 2).sum() / (centred ** 2).sum()) ** 0.5 < 1e-4
    assert want.std() > 0.1 and len(set(want.argmax(-1))) > 6


@pytest.mark.parametrize("name,ref,sizes,module", CASES,
                         ids=[c[0] for c in CASES])
def test_weights_have_the_programs_tree_and_the_served_type(
        name, ref, sizes, module):
    import importlib
    model = importlib.import_module(module)
    cfg = server.family_config(sizes)
    mine = ref.init(sizes, 1)
    theirs = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), mine) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), theirs)
    again = ref.init(sizes, 1)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(mine), jax.tree.leaves(again)))
    other = ref.init(sizes, 2)
    assert not bool((mine["wte"] == other["wte"]).all())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_controls_come_out_as_not_correct(seed):
    """At a size a test can hold, with the engine in float32 as the sound
    run: the reference with int8 weights choosing the tokens breaks the
    limit on ``deficit_mean`` that the engine keeps, and one row's tokens
    scored against another row's logits break the limit on
    ``deficit_max``. (At this width bfloat16 and int8 read alike; the
    real configuration's limits come from chip readings: PERF.md.)"""
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    sizes = TINY_LLAMA
    cfg = server.family_config(sizes)
    params = dense.llama.init(sizes, seed)
    engine = DecodeEngine(params, cfg, max_seq=256, dtype="float32")
    rng = np.random.default_rng(seed)
    pairs = []
    for n in (16, 40, 24, 56, 33, 48):
        prompt = [int(x) for x in rng.integers(0, sizes["vocab_size"], n)]
        out = np.asarray(engine.generate(np.asarray([prompt]),
                                         max_new_tokens=48).tokens)[0]
        pairs.append((prompt, [int(t) for t in out[-48:]]))
    got = check.score(dense.llama, params, sizes, pairs, control=True)
    assert got["tokens"] == 6 * 48
    assert got["readings"]["deficit_mean"] <= 2e-5 \
        < got["control"]["deficit_mean_int8"]
    assert got["readings"]["deficit_max"] <= 0.5 \
        < got["control"]["deficit_max_wrong_row"]


def test_ragged_sequences_share_padded_programs_and_read_the_same():
    sizes = TINY_LLAMA
    params = dense.llama.init(sizes, 5, jnp.float32)
    seq = [int(x) for x in np.random.default_rng(1).integers(0, 512, 70)]
    padded = check.reference_logits(dense.llama, params, sizes, seq, 60)
    plain = np.asarray(dense.llama.logits(params, sizes, seq,
                                          list(range(60, 70))))
    assert padded.shape == plain.shape == (10, 512)
    np.testing.assert_allclose(padded, plain, rtol=0, atol=1e-5)


def test_an_answer_that_is_not_prompt_plus_the_tokens_asked_for_is_infinite():
    import types
    from benchmark.harness.traffic import Arrival
    served = types.SimpleNamespace(config={"check": {"requests": 4}},
                                   reference=None, params=None)
    arrivals = [Arrival(k=0, t=0.0, prompt_ids=(5, 6, 7), max_new=4,
                        prefix_id=-1)]
    rows = [{"k": 0, "ok": True, "text": "5 6 7 1 2", "max_new": 4}]
    got = check.served_tokens(served, arrivals, rows)
    assert got["readings"]["deficit_max"] == float("inf")
    assert check.sample([{"ok": False, "text": "1"}] + rows, 4) == rows


def test_deficit_is_zero_for_the_references_own_choice():
    z = np.asarray([[0.0, 3.0, 1.0], [2.0, 0.0, 0.0]], np.float32)
    d = check.deficits(z, [1, 1])
    assert d[0] == 0.0 and d[1] == pytest.approx(2.0 / z[1].std())
