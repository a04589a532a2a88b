"""The seam between a model family and the rest of the program.

A family is one declaration (``models.family.Family``, stated once in
its module as ``FAMILY``) and, for the RMSNorm families, one frame
(``models.stack``). These tests pin what each of the eight declarations
says to what its module-level hooks said before there was a type
(literal tables: a drift shows), what each served family refuses and in
which words, and that a family NO file of ``runtime/`` or ``serving/``
has heard of is served from one entry of the registry.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_demo_tpu import models
from llm_sharding_demo_tpu.models import (gdn_moe, gpt2, hybrid_ssm, kda_moe,
                                          latent_moe, llama, moe, sdar_moe,
                                          stack, window_moe)
from llm_sharding_demo_tpu.models.family import REFUSABLE, Family
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.utils.config import ServingConfig

COUNTERS = ("experts_hit", "pairs_here", "pairs_routed", "load_max",
            "layer_forwards")
F32, BF16, I32 = "float32", "bfloat16", "int32"
TINY = {
    "gpt2": gpt2.CONFIGS["tiny-gpt2"],
    "moe": moe.MoEConfig(vocab_size=101, n_positions=32, n_embd=16,
                         n_layer=2, n_head=2, n_experts=4, expert_top_k=2),
    "llama": llama.CONFIGS["llama-tiny"],
    "latent_moe": latent_moe.CONFIGS["latent-moe-tiny"],
    "gdn_moe": gdn_moe.CONFIGS["gdn-moe-tiny"],
    "window_moe": window_moe.CONFIGS["window-moe-tiny"],
    "hybrid_ssm": hybrid_ssm.CONFIGS["hybrid-ssm-tiny"],
    "kda_moe": kda_moe.CONFIGS["kda-moe-tiny"],
    "sdar_moe": sdar_moe.CONFIGS["sdar-moe-tiny"],
}
MODULES = {"gpt2": gpt2, "moe": moe, "llama": llama,
           "latent_moe": latent_moe, "gdn_moe": gdn_moe,
           "window_moe": window_moe, "hybrid_ssm": hybrid_ssm,
           "kda_moe": kda_moe, "sdar_moe": sdar_moe}

# what each served family says to each option it refuses: the parent's
# sentences (PR 48's ``serving/app.py``), word for word, before the
# closing " (refused for this family)"
REFUSED = {
    ("latent_moe", "kv_pool_dtype"):
        "KV_POOL_DTYPE=int8: LatentMoEConfig's pool holds one latent "
        "vector a position; the quantized movers scale per kv-head and "
        "have not been fitted to it",
    ("latent_moe", "kv_host_blocks"):
        "KV_HOST_BLOCKS: the host tier has not been run over "
        "LatentMoEConfig's one-plane pool",
    ("latent_moe", "spec_decode"):
        "SPEC_DECODE: the verify loop's rewind leaves LatentMoEConfig's"
        " routing counters and cached latents of rejected drafts "
        "untested; serve it without speculation",
    ("latent_moe", "multi_chip"):
        "PP/TP/EP_DECODE: no multi-chip decoder stages or shards "
        "LatentMoEConfig (two stacks of unlike layers, experts indexed "
        "in place); it serves on one chip, told which experts it holds",
    ("latent_moe", "int8_weights"):
        "INFERENCE_DTYPE=int8: LatentMoEConfig indexes its experts' "
        "plain weight stacks; it serves float32 or bfloat16",
    ("gdn_moe", "kv_pool_dtype"):
        "KV_POOL_DTYPE=int8: GDNMoEConfig's pool is one plane with "
        "counters in its second leaf and its rows' state is float32 by "
        "contract; the quantized movers have not been fitted to it",
    ("gdn_moe", "kv_host_blocks"):
        "KV_HOST_BLOCKS: a demoted entry of GDNMoEConfig would need its"
        " state snapshot demoted with its blocks; the host tier moves "
        "blocks only",
    ("gdn_moe", "spec_decode"):
        "SPEC_DECODE: a rejected draft cannot be rewound out of "
        "GDNMoEConfig's per-row state (it has no position axis) without"
        " a snapshot a verify; serve it without speculation",
    ("gdn_moe", "multi_chip"):
        "PP/TP/EP_DECODE: no multi-chip decoder stages or shards "
        "GDNMoEConfig (runs of unlike layers, a state slab beside the "
        "pool, experts indexed in place); it serves on one chip, told "
        "which experts it holds",
    ("gdn_moe", "int8_weights"):
        "INFERENCE_DTYPE=int8: GDNMoEConfig indexes its experts' plain "
        "weight stacks; it serves float32 or bfloat16",
    ("window_moe", "kv_pool_dtype"):
        "KV_POOL_DTYPE=int8: WindowMoEConfig's pool is fused with "
        "counters in its second leaf and its window records carry the "
        "served type; the quantized movers have not been fitted to "
        "either",
    ("window_moe", "kv_host_blocks"):
        "KV_HOST_BLOCKS: a demoted entry of WindowMoEConfig would need "
        "its window records demoted with its blocks; the host tier "
        "moves blocks only",
    ("window_moe", "spec_decode"):
        "SPEC_DECODE: a rejected draft cannot be taken back out of "
        "WindowMoEConfig's window records (a ring has overwritten what "
        "the draft displaced); serve it without speculation",
    ("window_moe", "multi_chip"):
        "PP/TP/EP_DECODE: no multi-chip decoder stages or shards "
        "WindowMoEConfig (a first period unlike the others, a state "
        "slab beside the pool, experts indexed in place); it serves on "
        "one chip, told which experts it holds",
    ("window_moe", "int8_weights"):
        "INFERENCE_DTYPE=int8: WindowMoEConfig indexes its experts' "
        "plain weight stacks; it serves float32 or bfloat16",
    ("hybrid_ssm", "kv_pool_dtype"):
        "KV_POOL_DTYPE=int8: HybridSSMConfig's pool holds fused [K | V]"
        " rows in one plane and its rows' state is float32 by contract;"
        " the quantized movers have not been fitted to either",
    ("hybrid_ssm", "kv_host_blocks"):
        "KV_HOST_BLOCKS: a demoted entry of HybridSSMConfig would need "
        "its state snapshot demoted with its blocks; the host tier "
        "moves blocks only",
    ("hybrid_ssm", "spec_decode"):
        "SPEC_DECODE: a rejected draft cannot be rewound out of "
        "HybridSSMConfig's per-row state (it has no position axis) "
        "without a snapshot a verify; serve it without speculation",
    ("hybrid_ssm", "multi_chip"):
        "PP/TP/EP_DECODE: no multi-chip decoder stages or shards "
        "HybridSSMConfig (a state slab beside the pool in every layer, "
        "two state-space groups to divide); it serves on one chip",
    ("kda_moe", "kv_pool_dtype"):
        "KV_POOL_DTYPE=int8: KDAMoEConfig's pool is one plane with "
        "counters in its second leaf and its rows' state is float32 by "
        "contract; the quantized movers have not been fitted to it",
    ("kda_moe", "kv_host_blocks"):
        "KV_HOST_BLOCKS: a demoted entry of KDAMoEConfig would need its"
        " state snapshot demoted with its blocks; the host tier moves "
        "blocks only",
    ("kda_moe", "spec_decode"):
        "SPEC_DECODE: a rejected draft cannot be rewound out of "
        "KDAMoEConfig's per-row state (it has no position axis) without"
        " a snapshot a verify; serve it without speculation",
    ("kda_moe", "multi_chip"):
        "PP/TP/EP_DECODE: no multi-chip decoder stages or shards "
        "KDAMoEConfig (runs of unlike layers, a state slab beside the "
        "pool, experts indexed in place); it serves on one chip, told "
        "which experts it holds",
    ("kda_moe", "int8_weights"):
        "INFERENCE_DTYPE=int8: KDAMoEConfig indexes its experts' plain "
        "weight stacks; it serves float32 or bfloat16",
    # (new with the family, PR 50: a step that yields a block)
    ("sdar_moe", "spec_decode"):
        "SPEC_DECODE: SDARMoEConfig generates by rounds of a whole block; "
        "a draft-verify loop over single tokens has no place in a round; "
        "serve it without speculation",
    ("sdar_moe", "kv_pool_dtype"):
        "KV_POOL_DTYPE=int8: SDARMoEConfig's pool is one plane with "
        "counters in its second leaf, and a round reads the block it is "
        "writing; the quantized movers have not been fitted to it",
    ("sdar_moe", "kv_host_blocks"):
        "KV_HOST_BLOCKS: no deployment of SDARMoEConfig has needed the "
        "host tier yet and none has been checked against a demoted block "
        "boundary; serve it from the device pool",
    ("sdar_moe", "multi_chip"):
        "PP/TP/EP_DECODE: no multi-chip decoder stages or shards "
        "SDARMoEConfig (a round's forwards are one program's loop, experts "
        "indexed in place); it serves on one chip, told which experts it "
        "holds",
    ("sdar_moe", "int8_weights"):
        "INFERENCE_DTYPE=int8: SDARMoEConfig indexes its experts' plain "
        "weight stacks; it serves float32 or bfloat16",
}


# What the module-level hooks of each family said on the parent (PR 48),
# field by field of the declaration; ``cache`` is the family's own
# ``make_cache(config, 3, 32, bfloat16)`` there, leaf for leaf.
def says(entry, layers, cache, state=(), counters=(), bounds=False,
         fresh=False, own_kernel=False, bucket=False, window=False,
         wire=False, stageable=False, independent=True, refuses=()):
    return dict(entry=entry, layers=layers, cache=cache, state=state,
                counters=counters, bounds=bounds, fresh=fresh,
                own_kernel=own_kernel, bucket=bucket, window=window,
                wire=wire, stageable=stageable, independent=independent,
                refuses=refuses)


STATEFUL = ("spec_decode", "kv_pool_dtype", "kv_host_blocks", "multi_chip")
SAID = {
    "gpt2": says((2, 2, 1), 2, [((2, 3, 2, 32, 1), BF16)] * 2 + [((), I32)],
                 wire=True, stageable=True),
    "moe": says((2, 2, 8), 2, [((2, 3, 2, 32, 8), BF16)] * 2 + [((), I32)],
                independent=False),
    "llama": says((2, 2, 8), 2,
                  [((2, 3, 2, 32, 8), BF16)] * 2 + [((), I32)],
                  stageable=True),
    "latent_moe": says(
        (1, 1, 40), 4, [((4, 3, 1, 32, 40), BF16), ((5,), I32), ((), I32)],
        counters=COUNTERS, bounds=True, fresh=True, own_kernel=True,
        refuses=("kv_pool_dtype", "kv_host_blocks", "spec_decode",
                 "multi_chip", "int8_weights")),
    "gdn_moe": says(
        (1, 2, 64), 2,
        [((2, 3, 2, 32, 64), BF16), ((5,), I32), ((), I32),
         ((6, 3, 4, 16, 16), F32), ((6, 3, 3, 128), BF16)],
        state=(((6, 4, 16, 16), F32), ((6, 3, 128), BF16)),
        counters=COUNTERS, bounds=True, own_kernel=True,
        refuses=STATEFUL + ("int8_weights",)),
    "window_moe": says(
        (1, 2, 64), 2,
        [((2, 3, 2, 32, 64), BF16), ((5,), I32), ((), I32),
         ((6, 3, 2, 8, 64), BF16)],
        state=(((6, 2, 8, 64), BF16),),
        counters=COUNTERS, bounds=True, fresh=True, own_kernel=True,
        bucket=True, window=True, refuses=STATEFUL + ("int8_weights",)),
    "hybrid_ssm": says(
        (1, 2, 64), 3,
        [((3, 3, 2, 32, 64), BF16), ((0,), BF16), ((), I32),
         ((3, 3, 4, 24, 16), F32), ((3, 3, 3, 160), BF16)],
        state=(((3, 4, 24, 16), F32), ((3, 3, 160), BF16)),
        bounds=True, own_kernel=True, refuses=STATEFUL),
    "kda_moe": says(
        (1, 1, 40), 4,
        [((4, 3, 1, 32, 40), BF16), ((5,), I32), ((), I32),
         ((7, 3, 4, 16, 16), F32), ((7, 3, 3, 192), BF16)],
        state=(((7, 4, 16, 16), F32), ((7, 3, 192), BF16)),
        counters=COUNTERS, bounds=True, fresh=True, own_kernel=True,
        bucket=True, refuses=STATEFUL + ("int8_weights",)),
    # (new with the family, PR 50; behind the routing counters, what its
    # rounds count)
    "sdar_moe": says(
        (1, 2, 32), 3, [((3, 3, 2, 32, 32), BF16), ((11,), I32), ((), I32)],
        counters=COUNTERS + ("block_forwards", "block_rounds",
                             "block_commits", "block_tokens_fixed",
                             "block_fixed_over_threshold",
                             "block_row_forwards"),
        bounds=True, fresh=True, own_kernel=True,
        refuses=STATEFUL + ("int8_weights",)),
}


def _leaves(tree):
    return [(x.shape, str(x.dtype)) for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("name", sorted(SAID))
def test_the_declaration_says_what_the_hooks_said(name):
    config, said, module = TINY[name], SAID[name], MODULES[name]
    family = models.family_of(config)
    assert family is module.FAMILY and family.name == name
    assert family.config_class is type(config)
    assert models.family_module(config) is module is family.module
    assert models.family_named(name) is family
    assert models.cache_entry(config) == said["entry"]
    assert models.cache_layers(config) == said["layers"]
    assert [(s, str(jnp.dtype(d)))
            for s, d in models.row_state(config, jnp.bfloat16)] == [
        (s, d) for s, d in said["state"]]
    assert family.cache_counters == said["counters"]
    assert (family.span_labels is not None) == bool(said["counters"])
    assert family.bounds_own_reads is said["bounds"]
    assert family.fresh_prefill_flag is said["fresh"]
    assert (family.decode_kernel_eligible is not None) is said["own_kernel"]
    assert (family.prompt_bucket is not None) is said["bucket"]
    assert (family.window_positions is not None) is said["window"]
    assert models.is_partitionable(config) is said["wire"]
    assert models.is_stage_partitionable(config) is said["stageable"]
    assert models.is_window_independent(config) is said["independent"]
    assert tuple(o for o, _ in family.refuses) == said["refuses"]
    assert (family.refusal("int8_weights", config) is None) == (
        "int8_weights" not in said["refuses"])
    # the cache the module hands out, and the cache the declaration
    # describes: layers x batch x heads x positions x width, a plane each
    made = jax.eval_shape(lambda: module.make_cache(config, 3, 32,
                                                    jnp.bfloat16))
    assert _leaves(made) == said["cache"]
    planes, heads, width = said["entry"]
    assert said["cache"][0] == ((said["layers"], 3, heads, 32, width), BF16)
    if planes == 1:
        built = jax.eval_shape(lambda: stack.make_cache(
            family, config, 3, 32, jnp.bfloat16))
        assert _leaves(built) == said["cache"]
        assert jax.tree.structure(built) == jax.tree.structure(made)
    else:
        with pytest.raises(ValueError, match="planes"):
            stack.make_cache(family, config, 3, 32)


def test_a_declaration_that_is_wrong_fails_where_it_is_stated():
    with pytest.raises(TypeError, match="bounds_own_read"):
        Family(name="x", config_class=int, module=llama,
               bounds_own_read=True)           # misspelt: no silent default
    with pytest.raises(ValueError, match="not among"):
        Family(name="x", config_class=int, module=llama,
               refuses=(("speculation", "no"),))
    with pytest.raises(TypeError, match="make_cache"):
        Family(name="x", config_class=int,
               module=types.SimpleNamespace(init_params=len, forward=len,
                                            forward_with_cache=len))
    with pytest.raises(TypeError, match="unknown model config type"):
        models.family_of(object())
    with pytest.raises(ValueError, match="unknown checkpoint model family"):
        models.family_named("gpt3")


def _app_config(**extra):
    base = dict(model_id="test", max_seq=64, batch_mode="iter",
                max_batch=2, kv_pool_blocks=16)
    return ServingConfig(**{**base, **extra})


# how a deployment asks for each refusable option
ASKS = {
    "kv_pool_dtype": dict(kv_pool_dtype="int8"),
    "kv_host_blocks": dict(kv_host_blocks=8),
    "spec_decode": dict(spec_decode=2),
    "multi_chip": dict(batch_mode="admission", max_batch=1,
                       kv_pool_blocks=0, tp_decode=True),
    "int8_weights": dict(inference_dtype="int8"),
}
assert tuple(ASKS) == REFUSABLE
SERVED = [("hybrid_ssm", "int8_weights")]   # its weights are plain matmuls


@pytest.fixture(scope="module")
def tiny_params():
    made = {}

    def get(name):
        if name not in made:
            made[name] = MODULES[name].init_params(TINY[name],
                                                   jax.random.PRNGKey(0))
        return made[name]
    return get


@pytest.mark.parametrize("name,option", sorted(REFUSED) + SERVED)
def test_what_the_family_refuses(tiny_params, name, option):
    """Every (family, option) pair the server refused on the parent, in
    the parent's words; a pair a family does NOT refuse is served."""
    from llm_sharding_demo_tpu.serving.app import create_app
    model = (TINY[name], tiny_params(name))
    if (name, option) in SERVED:
        assert MODULES[name].FAMILY.refusal(option, TINY[name]) is None
        app = create_app(_app_config(**ASKS[option]), model=model)
        status, health, _ = app.handle("GET", "/healthz", b"", {})
        assert status == 200 and health["inference_dtype"] == "int8"
        return
    with pytest.raises(ValueError) as e:
        create_app(_app_config(**ASKS[option]), model=model)
    assert str(e.value) == REFUSED[name, option] + " (refused for this family)"
    if option == "int8_weights":
        # one decision, one sentence: the engine raises the declaration's
        with pytest.raises(NotImplementedError) as e:
            DecodeEngine(model[1], model[0], max_seq=64, dtype="int8")
        assert str(e.value) == REFUSED[name, option]


# -- a family nothing outside this file has heard of ----------------------

@dataclasses.dataclass(frozen=True)
class ToyConfig:
    """Llama's sizes under a class of its own: no ``isinstance`` on a
    family's config anywhere can find it."""
    vocab_size: int = 256
    n_positions: int = 128
    n_embd: int = 32
    n_layer: int = 2
    n_head: int = 4
    n_kv_head: int = 2
    intermediate_size: int = 64
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    attention_impl: str = "xla"

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


def _toy_blocks(params, h, config, cos, sin, cache=None, pad=None,
                decode_kernel=None):
    return llama.apply_blocks(params["blocks"], h, config, cos, sin, cache,
                              k_valid_from=pad, decode_kernel=decode_kernel)


TOY = Family(
    name="toy", config_class=ToyConfig,
    module=types.SimpleNamespace(
        init_params=llama.init_params,
        forward=lambda params, ids, config, remat=False, mesh=None:
            stack.forward(TOY, params, ids, config),
        forward_with_cache=lambda params, ids, config, cache, pad=None,
            flash_prefill=False, decode_kernel=None:
            stack.forward_with_cache(TOY, params, ids, config, cache, pad,
                                     flash_prefill, decode_kernel),
        make_cache=llama.make_cache),
    frame=stack.Frame(_toy_blocks, rotary_width=lambda c: c.head_dim),
    refuses=(("spec_decode",
              "SPEC_DECODE: {name} is a toy and drafts nothing"),))


@pytest.fixture
def toy(monkeypatch):
    """The claim "one line of the registry", as a fixture."""
    monkeypatch.setitem(models.REGISTRY, ToyConfig, TOY)
    config = ToyConfig()
    return config, llama.init_params(config, jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def as_llama():
    """The same weights served as llama: what the toy must emit."""
    config = llama.LlamaConfig(
        **{f.name: getattr(ToyConfig(), f.name)
           for f in dataclasses.fields(ToyConfig)})
    params = llama.init_params(config, jax.random.PRNGKey(3))
    prompt = np.arange(5, 16) % 256
    return prompt, np.asarray(DecodeEngine(params, config, max_seq=64)
                              .generate(prompt, 12).tokens)


def test_a_toy_family_decodes_through_the_engine(toy, as_llama):
    config, params = toy
    prompt, want = as_llama
    eng = DecodeEngine(params, config, max_seq=64)
    assert eng.family is TOY and eng._model is TOY.module
    assert np.array_equal(np.asarray(eng.generate(prompt, 12).tokens), want)
    models.REGISTRY.pop(ToyConfig)      # without its one entry: unknown
    with pytest.raises(TypeError, match="unknown model config type"):
        DecodeEngine(params, config, max_seq=64)


def test_a_toy_family_decodes_through_the_iteration_scheduler(toy, as_llama):
    from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
    from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
    config, params = toy
    prompt, want = as_llama
    eng = DecodeEngine(params, config, max_seq=64)
    pool = KVBlockPool.for_engine(eng, 16, block_size=16)
    it = IterBatchingEngine(eng, max_batch=2, seg_steps=4, pool=pool)
    got = it.generate(prompt, 12)
    assert np.array_equal(np.asarray(got.tokens).reshape(-1),
                          want.reshape(-1))


def test_a_toy_family_is_refused_in_its_own_words(toy):
    from llm_sharding_demo_tpu.serving.app import create_app
    with pytest.raises(ValueError) as e:
        create_app(_app_config(spec_decode=2), model=toy)
    assert str(e.value) == ("SPEC_DECODE: ToyConfig is a toy and drafts "
                            "nothing (refused for this family)")
    app = create_app(_app_config(), model=toy)
    status, payload, _ = app.handle(
        "POST", "/generate",
        b'{"prompt": "a b c", "max_new_tokens": 4, "mode": "greedy"}', {})
    assert status == 200 and payload["generated"]
