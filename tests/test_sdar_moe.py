"""The block-diffusion / sparse-expert family against its plain reference.

Small sizes in the published proportions (three layers, d 64, 4 query
heads over 2 key-value heads of 16, 8 experts of width 32, top 2, 4 of
them held, blocks of 4), seeded random weights from the REFERENCE's
``init`` (the tree the benchmark hands the program), float32 on the CPU.

Tolerance: ``TOL`` = 5e-5 on logits whose spread is about 1, the budget
the other families' tests use. Both sides are float32 at ``highest``;
they differ in the order of their sums (the program attends to the
cached positions and the block's own in two parts under one softmax, the
reference under one mask). A cache kept in bfloat16 moves the logits by
1e-2 and more, so the bound would catch it
(``test_a_bfloat16_cache_would_fail`` shows it does). Generation is
compared on TOKENS and on ``fixed_at`` (for each token, the denoise
forward of its round that fixed it) against the reference's own loop,
every forward of which is a whole pass with no cache.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar_moe as ref_mod
from llm_sharding_demo_tpu.models import sdar_moe
from llm_sharding_demo_tpu.ops import block_diffusion as BD
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine


@pytest.fixture(autouse=True, scope="module")
def highest():
    """Both sides at ``highest``, in this file alone (a test process
    runs other files too)."""
    with jax.default_matmul_precision("highest"):
        yield


TOL = 5e-5
REF = ref_mod.sdar_moe
SIZES = {"hidden_size": 64, "vocab_size": 256, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16,
         "moe_intermediate_size": 32, "num_experts": 4,
         "published_num_experts": 8, "first_expert": 2,
         "num_experts_per_tok": 2, "norm_topk_prob": True,
         "num_hidden_layers": 3, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
         "block_length": 4, "denoising_steps": 2,
         "confidence_threshold": 0.9,
         "remasking": "low_confidence_dynamic", "mask_token_id": 255}


def config_of(sizes, **over):
    return dataclasses.replace(
        sdar_moe.CONFIGS["sdar-moe-tiny"],
        first_expert=sizes["first_expert"],
        n_routed_experts=sizes["num_experts"],
        n_routed_total=sizes["published_num_experts"],
        denoising_steps=sizes["denoising_steps"],
        remasking=sizes["remasking"],
        confidence_threshold=sizes["confidence_threshold"], **over)


@pytest.fixture(scope="module")
def whole():
    return SIZES, config_of(SIZES), REF.init(SIZES, 7, jnp.float32)


def prompt_of(n, seed=0):
    return np.random.RandomState(seed).randint(0, 255, n).tolist()


# -- the layer and the mask ------------------------------------------------

def test_the_block_mask_is_causal_between_blocks_and_open_inside_one():
    seen = np.asarray(BD.block_mask(jnp.arange(8), jnp.arange(8), 4))
    assert seen[:4, :4].all() and seen[4:, :].all() and not seen[:4, 4:].any()
    # counted from the row's own first token: a pad of 2 moves the grid
    padded = np.asarray(BD.block_mask(jnp.arange(8), jnp.arange(8), 4,
                                      jnp.asarray([2])))[0]
    assert padded[2:6, 2:6].all() and not padded[2:6, 6:].any()
    assert not padded[:, :2].any() and padded[6:, 2:].all()
    assert (np.asarray(ref_mod.block_seen(8, 4)) == seen).all()


@pytest.mark.parametrize("n", [8, 12, 40])
def test_the_forward_is_the_references(whole, n):
    sizes, cfg, params = whole
    ids = prompt_of(n, seed=n)
    ref = np.asarray(REF.logits(params, sizes, ids, list(range(n))))
    got = np.asarray(sdar_moe.forward(params, jnp.asarray([ids]), cfg))[0]
    assert np.abs(got - ref).max() < TOL
    assert ref.std() > 0.5


def _through_the_cache(params, cfg, ids, keep):
    """``ids[:keep]`` prefilled in one call and a stride of the store,
    then the next block with its last two positions masked."""
    fwd = jax.jit(lambda p, i, c, fresh: sdar_moe.forward_with_cache(
        p, i, cfg, c, flash_prefill=fresh), static_argnums=3)
    _, cache = fwd(params, jnp.asarray([ids[:16]]),
                   sdar_moe.make_cache(cfg, 1, 64), True)
    last, cache = fwd(params, jnp.asarray([ids[16:keep]]), cache, False)
    return fwd, last, cache


def test_a_denoise_forward_through_the_cache_is_the_references(whole):
    sizes, cfg, params = whole
    ids = prompt_of(28, seed=3)
    block = ids[24:26] + [255, 255]
    ref = np.asarray(REF.logits(params, sizes, ids[:24] + block,
                                list(range(23, 28))))
    fwd, last, cache = _through_the_cache(params, cfg, ids, 24)
    # a call of several blocks hands back its last position's logits
    assert last.shape == (1, 1, 256)
    assert np.abs(np.asarray(last[0, 0]) - ref[0]).max() < TOL
    got, after = fwd(params, jnp.asarray([block]), cache, False)
    assert got.shape == (1, 4, 256) and int(after.length) == 28
    assert np.abs(np.asarray(got[0]) - ref[1:]).max() < TOL
    counters = dict(zip(sdar_moe.CACHE_COUNTERS, np.asarray(after.v)))
    assert counters["pairs_routed"] == 28 * 2 * 3
    assert counters["layer_forwards"] == 3 * 3
    assert 0 < counters["pairs_here"] < counters["pairs_routed"]
    assert counters["block_forwards"] == 0      # the engine's to count


def test_a_bfloat16_cache_would_fail(whole):
    """The tolerance is tight enough: the same denoise forward over a
    cache rounded to bfloat16 parts from the reference by far more than
    ``TOL``."""
    sizes, cfg, params = whole
    ids = prompt_of(28, seed=3)
    block = ids[24:26] + [255, 255]
    ref = np.asarray(REF.logits(params, sizes, ids[:24] + block,
                                list(range(24, 28))))
    fwd, _, cache = _through_the_cache(params, cfg, ids, 24)
    rounded = cache._replace(
        k=cache.k.astype(jnp.bfloat16).astype(jnp.float32))
    got, _ = fwd(params, jnp.asarray([block]), rounded, False)
    assert np.abs(np.asarray(got[0]) - ref).max() > 20 * TOL


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the expert parts of the shares that hold
    ids 0-1, 2-3, 4-5, 6-7 add up to what a layer holding all 8 gives."""
    uncut = dict(SIZES, num_experts=8, first_expert=0)
    params = REF.init(uncut, 5, jnp.float32)
    m = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
    router = params["blocks"]["moe"]["router"]["kernel"][1]
    whole_, counts = sdar_moe.expert_layer(
        router, params["experts"], m, config_of(uncut), 1)
    parts = 0
    for first in range(0, 8, 2):
        held = jax.tree.map(lambda x: x[:, first:first + 2],
                            params["experts"])
        share = dict(uncut, num_experts=2, first_expert=first)
        part, c = sdar_moe.expert_layer(router, held, m, config_of(share), 1)
        parts = parts + part
        assert (np.asarray(c) == np.asarray(counts)[first:first + 2]).all()
    assert int(counts.sum()) == 2 * 8 * 2
    assert np.abs(np.asarray(whole_) - np.asarray(parts)).max() < 1e-5
    assert np.abs(np.asarray(whole_)).max() > 0.1


# -- the transfer rule -----------------------------------------------------

def test_the_transfer_rules_are_the_references():
    rng = np.random.RandomState(0)
    for trial in range(40):
        z = rng.randn(4, 256) * rng.choice([1.0, 6.0])
        masked = rng.rand(4) < 0.7
        if not masked.any():
            continue
        steps = int(rng.choice([1, 2, 4]))
        floor = -(-int(masked.sum()) // steps)
        cand, conf = BD.choose(jnp.asarray(z[None], jnp.float32), 255)
        for rule in BD.RULES:
            o = {"remasking": rule, "confidence_threshold": 0.5,
                 "mask_token_id": 255}
            want = ref_mod.choose_and_transfer(z, masked, floor, o)
            fix, over = BD.transfer(jnp.asarray(masked[None]), conf,
                                    jnp.asarray([floor]), rule, 0.5)
            got = {int(i): int(cand[0, i])
                   for i in np.flatnonzero(np.asarray(fix[0]))}
            assert got == want, (trial, rule)
            assert not (np.asarray(over[0]) & ~np.asarray(fix[0])).any()
    # the mask token is no candidate
    z = np.zeros((1, 1, 256), np.float32)
    z[..., 255] = 9.0
    z[..., 7] = 1.0
    cand, conf = BD.choose(jnp.asarray(z), 255)
    assert int(cand[0, 0]) == 7 and 0 < float(conf[0, 0]) < 1
    with pytest.raises(ValueError, match="remasking"):
        BD.transfer(jnp.ones((1, 4), bool), jnp.ones((1, 4)),
                    jnp.asarray([1]), "random", 0.9)


# -- generation ------------------------------------------------------------

def _same_as_the_loop(engine, params, sizes, prompt, n):
    want = REF.generate(params, sizes, prompt, n)
    got = engine.generate([prompt], n)
    assert got.tokens[0, len(prompt):].tolist() == want["tokens"]
    assert got.fixed_at[0].tolist() == want["fixed_at"]
    # the rounds ran the loop's denoise forwards and a commit a block
    blocks = -(-(len(prompt) % 4 + n) // 4)
    assert got.decode_steps == want["forwards"] + blocks
    return want


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("rest", [0, 1, 3])
def test_rounds_through_the_cache_are_the_published_loop(rest, steps):
    """A prompt that ends ``rest`` positions past a block boundary, a
    budget that is no multiple of a block."""
    sizes = dict(SIZES, denoising_steps=steps)
    params = REF.init(sizes, 11, jnp.float32)
    engine = DecodeEngine(params, config_of(sizes), max_seq=64)
    want = _same_as_the_loop(engine, params, sizes,
                             prompt_of(12 + rest, seed=rest), 10)
    # the floor: ceil(masked at the block's start / steps) a forward
    assert max(want["fixed_at"]) <= steps - 1


@pytest.mark.parametrize("rule", BD.RULES)
def test_each_transfer_rule_is_the_published_loops(rule):
    sizes = dict(SIZES, remasking=rule, denoising_steps=2)
    params = REF.init(sizes, 13, jnp.float32)
    engine = DecodeEngine(params, config_of(sizes), max_seq=64)
    want = _same_as_the_loop(engine, params, sizes, prompt_of(9, seed=5), 11)
    if rule == "sequential":
        first = [f for f in want["fixed_at"][3:7]]
        assert first == [0, 0, 1, 1]


# fused rows of 128 lanes and a cache of whole blocks: the geometry the
# decode kernels take (``sdar_moe.decode_kernel_eligible``)
KERNEL_SIZES = dict(SIZES, head_dim=64)


@pytest.mark.parametrize("rest,rows", [(0, 1), (1, 1), (3, 3)],
                         ids=["on-a-boundary", "one-past", "a-batch-of-three"])
def test_rounds_under_the_decode_kernels_are_the_published_loop(rest, rows):
    """``decode_kernel="interpret"``: every round's forwards take
    ``ops.block_decode``'s kernel (and the experts' tiles theirs), the
    prefill keeps the XLA form; rows of different lengths ride one
    batch, so the kernel meets pads, and each row is still the loop's."""
    sizes = dict(KERNEL_SIZES, denoising_steps=2)
    params = REF.init(sizes, 11, jnp.float32)
    engine = DecodeEngine(params, config_of(sizes, head_dim=64), max_seq=200,
                          decode_kernel="interpret")
    assert engine._decode_kernel == "interpret" and engine._cache_seq == 256
    prompts = [prompt_of(12 + rest + 8 * i, seed=rest + i)
               for i in range(rows)]
    together = engine.generate(prompts, 10)
    for i, prompt in enumerate(prompts):
        want = REF.generate(params, sizes, prompt, 10)
        assert together.row_tokens(i)[len(prompt):].tolist() == want["tokens"]
        assert together.fixed_at[i].tolist() == want["fixed_at"]


def sharpened(params, by=6.0):
    """A head that is sure of itself: some confidences pass 0.9."""
    head = params["lm_head"]["kernel"] * by
    return dict(params, lm_head={"kernel": head})


def test_the_thresholds_side_rows_finish_in_different_forwards():
    """Weights under which some confidences pass the threshold: rows of
    one batch finish their blocks in different forwards, and each still
    equals its solo run and the published loop."""
    sizes = dict(SIZES, denoising_steps=4, confidence_threshold=0.9)
    params = sharpened(REF.init(sizes, 17, jnp.float32))
    engine = DecodeEngine(params, config_of(sizes), max_seq=96)
    prompts = [prompt_of(8, seed=1), prompt_of(13, seed=2),
               prompt_of(18, seed=3)]
    together = engine.generate(prompts, 16)
    forwards = []
    for i, prompt in enumerate(prompts):
        want = _same_as_the_loop(engine, params, sizes, prompt, 16)
        row = together.row_tokens(i)
        assert row[len(prompt):].tolist() == want["tokens"]
        assert together.fixed_at[i].tolist() == want["fixed_at"]
        forwards.append(want["forwards"])
    assert len(set(forwards)) > 1, forwards
    # the threshold fixed more than the floor somewhere, and less than
    # everything: 4 positions took between 1 and 4 forwards
    spread = {max(together.fixed_at[i][j:j + 4]) for i in range(3)
              for j in range(0, 16, 4)}
    assert len(spread) > 1


def test_a_walk_of_the_store_prefills_under_the_block_mask(whole):
    """Chunked, and behind a stored prefix: strides of a 16-token chunk
    and a ragged tail of whole blocks, a miss and then a hit."""
    sizes, cfg, params = whole
    engine = DecodeEngine(params, cfg, max_seq=128)
    store = PrefixCachingEngine(engine, capacity=2, chunk=16)
    ids = prompt_of(60, seed=8)
    ref = np.asarray(REF.logits(params, sizes, ids, [59]))
    for hit in (False, True):
        logits, cache, n = store.prefill_state(np.asarray(ids))
        assert n == 60 and int(cache.length) == 60
        assert np.abs(np.asarray(logits[0]) - ref[0]).max() < TOL
        assert store.stats()["hits"] == int(hit)


def test_what_the_solo_engine_refuses(whole):
    sizes, cfg, params = whole
    engine = DecodeEngine(params, cfg, max_seq=64)
    with pytest.raises(ValueError, match="one whole block"):
        engine.generate([prompt_of(3)], 4)
    with pytest.raises(ValueError, match="max_seq"):
        engine.generate([prompt_of(40)], 40)
    with pytest.raises(NotImplementedError, match="prefill_chunk"):
        DecodeEngine(params, cfg, max_seq=64, prefill_chunk=16)
    with pytest.raises(ValueError, match="whole blocks"):
        sdar_moe.forward_with_cache(params, jnp.zeros((1, 6), jnp.int32),
                                    cfg, sdar_moe.make_cache(cfg, 1, 64))
