"""The per-channel delta-rule / latent-attention / sparse-expert family
against its plain reference.

Small sizes in the published proportions (eleven layers from the two
lists, ``K' K M K K M K K M K M``: the dense first layer, a run ``K M
K`` three times (a scan of three) and an irregular tail ``M``; d 64, 4 delta-rule heads of
16, 4 latent heads of 16 + 8 over a latent of 32, 16 experts of width
24, top 4, a shared one), seeded random weights from the REFERENCE's
``init`` (the tree the benchmark hands the program), float32 on the CPU.

Tolerance: ``TOL`` = 5e-5 on logits whose spread is about 1. Both sides
are float32 at ``highest``; they differ in the order of their sums (the
program's chunked rule against the reference's recurrence, the absorbed
form or an online softmax against the reference's expanded blocks),
which leaves a few ulps a layer: 1.1e-5 measured over eleven layers and
150 positions. A bfloat16 state (or anything else computed in bfloat16)
moves the logits by 1e-2 and more, so the bound would catch it
(``test_a_bfloat16_state_would_fail`` shows it does).
"""

import dataclasses
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.kda_moe import kda_moe as REF
from llm_sharding_demo_tpu.models import (cache_entry, cache_layers,
                                          family_module,
                                          is_window_independent, kda_moe,
                                          latent_moe, llama, row_state)
from llm_sharding_demo_tpu.ops import gated_delta, kda
from llm_sharding_demo_tpu.ops.rope import pair_angles
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool, PagedKVRunner
from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine
from llm_sharding_demo_tpu.utils import tracing

TOL = 5e-5
SIZES = dict(
    hidden_size=64, vocab_size=256, num_attention_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    q_lora_rank=None, mla_use_nope=True, intermediate_size=224,
    moe_intermediate_size=24, first_k_dense_replace=1, num_experts=16,
    published_num_experts=16, first_expert=0, num_experts_per_token=4,
    num_shared_experts=1, routed_scaling_factor=2.446, moe_renormalize=True,
    rms_norm_eps=1e-5, num_hidden_layers=11, model_max_length=512,
    linear_attn_config=dict(
        kda_layers=[1, 2, 4, 5, 7, 8, 10], full_attn_layers=[3, 6, 9, 11],
        head_dim=16, num_heads=4, short_conv_kernel_size=4))

# the configuration file's ``family_kwargs``, so that the tests build
# the config the way the benchmark does
with open("benchmark/configs/kimi-linear-48b-ep16.json") as _f:
    KWARGS = json.load(_f)["family_kwargs"]


def config_of(s):
    sizes = dict(s, attention_impl="xla")
    return kda_moe.KDAMoEConfig.from_published(
        **{k: sizes[v] for k, v in KWARGS.items()})


@pytest.fixture(scope="module")
def whole():
    return SIZES, config_of(SIZES), REF.init(SIZES, 7, jnp.float32)


def share_of(sizes, params, first, count):
    """One chip's share: ``count`` held experts from id ``first``."""
    s = dict(sizes, num_experts=count, first_expert=first)
    p = dict(params)
    p["experts"] = jax.tree.map(lambda x: x[:, first:first + count],
                                params["experts"])
    return s, config_of(s), p


def reference_logits(params, sizes, ids):
    return np.asarray(REF.logits(params, sizes, list(ids),
                                 list(range(len(ids)))))


def rule_inputs(seed, b, h, t, dk, dv, decay=0.7):
    """``g`` uniform in ``[-decay, 0]`` a channel a position."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (gated_delta.l2norm(jax.random.normal(k[0], (b, h, t, dk)))
            / dk ** 0.5,
            gated_delta.l2norm(jax.random.normal(k[1], (b, h, t, dk))),
            jax.random.normal(k[2], (b, h, t, dv)),
            -jax.random.uniform(k[3], (b, h, t, dk)) * decay,
            jax.nn.sigmoid(jax.random.normal(k[4], (b, h, t))),
            jax.random.normal(k[5], (b, h, dk, dv)))


def recurrence64(q, k, v, g, beta, s):
    """``kda.recurrence`` in float64 on the host: what float32 errors of
    a few 1e-7 are measured against."""
    q, k, v, g, beta, s = (np.asarray(x, np.float64)
                           for x in (q, k, v, g, beta, s))
    o = np.empty(v.shape)
    for t in range(q.shape[2]):
        s = s * np.exp(g[:, :, t])[..., None]
        d = beta[:, :, t, None] * (
            v[:, :, t] - np.einsum("bhk,bhkv->bhv", k[:, :, t], s))
        s = s + k[:, :, t, :, None] * d[..., None, :]
        o[:, :, t] = np.einsum("bhk,bhkv->bhv", q[:, :, t], s)
    return o, s


def test_family_is_registered_and_declares_what_it_caches(whole):
    _, cfg, _ = whole
    assert family_module(cfg) is kda_moe
    assert is_window_independent(cfg)
    # four of the eleven layers cache positions, seven hold a row's state
    assert cache_layers(cfg) == 4 and cfg.n_kda == 7
    assert cache_entry(cfg) == (1, 1, 40)     # one latent row, [c | k_r]
    (mat, mat_t), (tail, tail_t) = row_state(cfg, jnp.bfloat16)
    assert mat == (7, 4, 16, 16) and mat_t == jnp.float32
    assert tail == (7, 3, 3 * 4 * 16) and tail_t == jnp.bfloat16
    cache = kda_moe.make_cache(cfg, 3, 64)
    assert cache.k.shape == (4, 3, 1, 64, 40) and cache.v.shape == (5,)
    assert [x.shape for x in cache.state] == [(7, 3, 4, 16, 16),
                                              (7, 3, 3, 192)]
    # the tiny preset is this plan; the published widths give one plane
    # of 640 lanes in 7 layers and 43.4 MB of state a row
    assert kda_moe.CONFIGS["kda-moe-tiny"] == cfg
    full = kda_moe.KDAMoEConfig()
    assert cache_entry(full) == (1, 1, 640) and cache_layers(full) == 7
    assert [s for s, _ in row_state(full, jnp.bfloat16)] == [
        (20, 32, 128, 128), (20, 3, 12288)]
    # a lone prompt's prefill: whole chunks, then eighths of a power of 2
    assert [kda_moe.prompt_bucket(full, n) for n in (
        1, 64, 65, 500, 513, 1024, 1025, 1536)] == [
        64, 64, 128, 512, 640, 1024, 1280, 1536]
    assert len({kda_moe.prompt_bucket(full, n)
                for n in range(64, 1537)}) == 14


def test_the_plan_is_read_from_the_two_lists(whole):
    _, cfg, params = whole
    plan = kda_moe.layer_plan(cfg)
    # K' K M K K M K K M K M: the dense layer, K M K three times, M
    assert [(g.kinds, g.dense, g.count, g.first, g.first_kda, g.first_mla)
            for g in plan] == [
        (("kda",), (True,), 1, 0, 0, 0),
        (("kda", "mla", "kda"), (False, False, False), 3, 1, 1, 0),
        (("mla",), (False,), 1, 10, 7, 3)]
    # the published 27: K', K K M K six times, the tail K M: seven
    # layers written out where a cut at every latent layer writes eleven
    full = kda_moe.layer_plan(kda_moe.KDAMoEConfig())
    assert [("".join(k[0] for k in g.kinds), g.count, g.first, g.first_kda,
             g.first_mla) for g in full] == [
        ("k", 1, 0, 0, 0), ("kkmk", 6, 1, 1, 0), ("km", 1, 25, 19, 6)]
    assert sum(len(g.kinds) for g in full) == 7
    assert sum(len(g.kinds) * g.count for g in full) == 27
    # an interval is a plan too: one run, repeated
    assert [(g.kinds, g.count) for g in kda_moe.layer_plan(
        dataclasses.replace(cfg, n_layer=8, first_k_dense=4,
                            kda_layers=(1, 3, 5, 7),
                            full_attn_layers=(2, 4, 6, 8)))] == [
        (("kda", "mla"), 2), (("kda", "mla"), 2)]
    # a group is a list of trees, one a place, every leaf [repeats, ...]
    assert [[jax.tree.leaves(p)[0].shape[0] for p in g]
            for g in params["groups"]] == [[1], [3, 3, 3], [1]]
    assert "mlp" in params["groups"][0][0] and \
        all("moe" in p for g in params["groups"][1:] for p in g)
    # and the program's own init makes the reference's tree
    mine = kda_moe.init_params(cfg, jax.random.PRNGKey(0))
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), mine)
            == jax.tree.map(lambda x: (x.shape, x.dtype), params))
    with pytest.raises(ValueError):                      # a layer unnamed
        dataclasses.replace(cfg, full_attn_layers=(3, 6, 9))


@pytest.mark.parametrize("held", [(0, 16), (4, 8)], ids=["all", "share"])
def test_prefill_then_decode_through_the_cache_agrees(whole, held):
    """Prefill 140 tokens (chunks of 64 and a ragged one) then decode
    10 through the cache, against the reference's ONE full pass, on
    logits; the routing counters count every pair of the ten expert
    layers."""
    sizes, cfg, params = whole
    sizes, cfg, params = share_of(sizes, params, *held)
    ids = np.random.RandomState(0).randint(0, 256, (2, 150))
    ref = np.stack([reference_logits(params, sizes, row) for row in ids])
    cache = kda_moe.make_cache(cfg, 2, 256)
    fwd = jax.jit(lambda p, i, c, fresh: kda_moe.forward_with_cache(
        p, i, cfg, c, flash_prefill=fresh), static_argnums=3)
    got, cache = fwd(params, jnp.asarray(ids[:, :140]), cache, True)
    assert np.abs(np.asarray(got) - ref[:, :140]).max() < TOL
    for t in range(140, 150):
        one, cache = fwd(params, jnp.asarray(ids[:, t:t + 1]), cache, False)
        assert np.abs(np.asarray(one[:, 0]) - ref[:, t]).max() < TOL, t
    counters = dict(zip(kda_moe.CACHE_COUNTERS, np.asarray(cache.v)))
    assert counters["pairs_routed"] == 2 * 150 * 4 * 10
    assert counters["layer_forwards"] == 10 * 11
    if held == (0, 16):
        assert counters["pairs_here"] == counters["pairs_routed"]
    else:
        assert 0 < counters["pairs_here"] < counters["pairs_routed"]
    assert int(cache.length) == 150
    full = np.asarray(kda_moe.forward(params, jnp.asarray(ids), cfg))
    assert np.abs(full - ref).max() < TOL


def test_a_continuation_chunk_reads_the_cache_and_the_state(whole):
    """What a store walk does: 128 tokens into a fresh cache, then a
    chunk of 22 in the absorbed form from the carried state, equal the
    reference's one pass."""
    sizes, cfg, params = whole
    ids = np.random.RandomState(4).randint(0, 256, (1, 150))
    ref = reference_logits(params, sizes, ids[0])
    fwd = jax.jit(lambda p, i, c, fresh: kda_moe.forward_with_cache(
        p, i, cfg, c, flash_prefill=fresh), static_argnums=3)
    _, cache = fwd(params, jnp.asarray(ids[:, :128]),
                   kda_moe.make_cache(cfg, 1, 256), True)
    got, cache = fwd(params, jnp.asarray(ids[:, 128:]), cache, False)
    assert np.abs(np.asarray(got[0]) - ref[128:]).max() < TOL


def test_a_bfloat16_state_would_fail(whole):
    """The tolerance is tight enough: the same prefill with the state
    rounded to bfloat16 between two calls parts from the reference by
    far more than ``TOL``."""
    sizes, cfg, params = whole
    ids = np.random.RandomState(0).randint(0, 256, (1, 150))
    ref = reference_logits(params, sizes, ids[0])
    fwd = jax.jit(lambda p, i, c, fresh: kda_moe.forward_with_cache(
        p, i, cfg, c, flash_prefill=fresh), static_argnums=3)
    _, cache = fwd(params, jnp.asarray(ids[:, :128]),
                   kda_moe.make_cache(cfg, 1, 256), True)
    mats, tails = cache.state
    rounded = cache._replace(state=(
        mats.astype(jnp.bfloat16).astype(jnp.float32), tails))
    got, _ = fwd(params, jnp.asarray(ids[:, 128:]), rounded, False)
    assert np.abs(np.asarray(got[0]) - ref[128:]).max() > 20 * TOL


@pytest.mark.parametrize("t", [1, 37, 64, 65, 150, 256])
@pytest.mark.parametrize("draw,dk,dv", [
    ("ordinary", 16, 24), ("ordinary", 128, 128), ("strong", 16, 24),
    ("strong", 128, 128), ("close-keys", 128, 128)],
    ids=["small", "published", "small-strong-decay",
         "published-strong-decay", "published-close-keys"])
def test_the_chunked_rule_is_the_recurrence(draw, dk, dv, t):
    """With an incoming state and lengths that are not whole chunks, at
    the tests' head sizes and the published ones. ``strong``: decays of
    up to ``e^-6`` a position, so that ``e^{-G}`` over one chunk of 64
    (up to ``e^{384}``) is past float32 (``e^{88.7}``): the form that
    factors the decay out of the dot product gives NaN there (shown),
    this one no exponent above 0. ``close-keys``: keys within a few
    degrees, ``beta`` 0.98 and hardly any decay, the draw hardest on the
    inversion, held to the float64 recurrence."""
    if draw == "close-keys":
        q, k, v, g, beta, s0 = rule_inputs(t, 2, 4, t, dk, dv)
        ks = jax.random.split(jax.random.PRNGKey(t + 1000), 2)
        k = gated_delta.l2norm(jax.random.normal(ks[0], (2, 4, 1, dk))
                               + 0.05 * jax.random.normal(ks[1], k.shape))
        hard = (q, k, v, jnp.full_like(g, -0.005),
                jnp.full_like(beta, 0.98), s0)
        want = recurrence64(*hard)
        got = jax.jit(kda.chunked)(*hard)
        for w, a in zip(want, got):
            assert np.abs(w - np.asarray(a)).max() < 6e-6
        return
    x = rule_inputs(t, 2, 4, t, dk, dv, 6.0 if draw == "strong" else 0.7)
    o1, s1 = kda.recurrence(*x)
    o2, s2 = jax.jit(kda.chunked)(*x)
    # the running sum G reaches -190 on the strong draw, where float32
    # resolves 1.5e-5: every e^{G_i - G_j} carries that relative error
    # (7e-6 measured on states of size 1.6), which the recurrence, that
    # never forms G, does not; at the model's decays G stays above -45
    tol = 2e-5 if draw == "strong" else 4e-6
    assert np.abs(np.asarray(o1 - o2)).max() < tol
    assert np.abs(np.asarray(s1 - s2)).max() < tol
    if draw == "strong" and t >= 64:
        big = jnp.cumsum(x[3][:, :, :64], axis=2)
        with np.errstate(over="ignore"):
            assert not np.isfinite(np.asarray(jnp.exp(-big))).all()


@pytest.mark.parametrize("t", [1, 64, 150])
def test_a_decay_constant_over_the_channels_is_the_scalar_rule(t):
    """With one decay a head the per-channel forms are the scalar
    module's: recurrence, chunked and the interpreted kernel."""
    q, k, v, g, beta, s0 = rule_inputs(t + 7, 2, 4, t, 16, 24)
    g1 = g[..., 0]
    gk = jnp.broadcast_to(g1[..., None], g.shape)
    for mine, theirs in ((kda.recurrence, gated_delta.recurrence),
                         (kda.chunked, gated_delta.chunked)):
        o1, s1 = jax.jit(theirs)(q, k, v, g1, beta, s0)
        o2, s2 = jax.jit(mine)(q, k, v, gk, beta, s0)
        assert np.abs(np.asarray(o1 - o2)).max() < 2e-6
        assert np.abs(np.asarray(s1 - s2)).max() < 2e-6
    states = jnp.stack([s0, s0 + 1.0])
    one = (q[:, :, 0], k[:, :, 0], v[:, :, 0])
    o1, s1 = gated_delta.step(*one, g1[:, :, 0], beta[:, :, 0], states, 1,
                              "interpret")
    o2, s2 = kda.step(*one, gk[:, :, 0], beta[:, :, 0], states, 1,
                      "interpret")
    assert np.abs(np.asarray(o1 - o2)).max() < 1e-6
    assert np.abs(np.asarray(s1 - s2)).max() < 1e-6


def test_a_walk_in_several_calls_is_the_walk_in_one():
    """Calls that start at multiples of the chunk compute the same sums
    as one call: the grid is then absolute (what makes a store hit equal
    to a cold prefill)."""
    q, k, v, g, beta, s0 = rule_inputs(3, 1, 4, 229, 16, 16)
    run = jax.jit(kda.chunked)
    o, s = run(q, k, v, g, beta, s0)
    outs, state = [], s0
    for lo, hi in ((0, 128), (128, 192), (192, 229)):
        part, state = run(q[:, :, lo:hi], k[:, :, lo:hi], v[:, :, lo:hi],
                          g[:, :, lo:hi], beta[:, :, lo:hi], state)
        outs.append(part)
    assert np.array_equal(np.asarray(jnp.concatenate(outs, axis=2)),
                          np.asarray(o))
    assert np.array_equal(np.asarray(state), np.asarray(s))


@pytest.mark.parametrize("decay", [0.7, 6.0], ids=["ordinary", "strong"])
def test_the_kernel_is_the_recurrence(decay):
    """The Pallas state update, interpreted: one position of every row,
    layer 1 of 3 rewritten in place and the others untouched."""
    q, k, v, g, beta, _ = rule_inputs(5, 3, 4, 1, 16, 24, decay)
    states = jax.random.normal(jax.random.PRNGKey(9), (3, 3, 4, 16, 24))
    args = (q[:, :, 0], k[:, :, 0], v[:, :, 0], g[:, :, 0], beta[:, :, 0])
    o1, s1 = kda.step(*args, states, 1, None)
    o2, s2 = kda.step(*args, states, 1, "interpret")
    assert np.abs(np.asarray(o1 - o2)).max() < 1e-6
    assert np.abs(np.asarray(s1 - s2)).max() < 1e-6
    assert np.array_equal(np.asarray(s2[0]), np.asarray(states[0]))
    assert np.array_equal(np.asarray(s2[2]), np.asarray(states[2]))
    assert not np.array_equal(np.asarray(s2[1]), np.asarray(states[1]))


# which lanes hold a request, by name, at any number of rows: the live
# lanes' kernel cases (ISSUE 47)
LIVE = {"all-live": lambda b: [True] * b,
        "one-live": lambda b: [i == b // 2 for i in range(b)],
        "lane-0-ghost": lambda b: [i > 0 for i in range(b)],
        "last-lane-ghost": lambda b: [i < b - 1 for i in range(b)],
        "alternating": lambda b: [i % 2 == 0 for i in range(b)],
        "none-live": lambda b: [False] * b}


@pytest.mark.parametrize("rows", [2, 4])
@pytest.mark.parametrize("pattern", sorted(LIVE))
def test_the_kernel_streams_the_live_lanes(rows, pattern):
    """Over two blocks of heads: a live lane's output and state are the
    recurrence's; a lane without a request is not streamed: its state,
    in every layer of the stack, is bit for bit what came in, its output
    row exactly zero."""
    live = np.asarray(LIVE[pattern](rows))
    q, k, v, g, beta, _ = rule_inputs(5, rows, 32, 1, 16, 24)
    states = jax.random.normal(jax.random.PRNGKey(9), (3, rows, 32, 16, 24))
    args = (q[:, :, 0], k[:, :, 0], v[:, :, 0], g[:, :, 0], beta[:, :, 0])
    o1, s1 = map(np.asarray, kda.step(*args, states, 1, None))
    o2, s2 = map(np.asarray, kda.step(
        *args, states, 1, "interpret",
        gated_delta.lane_order(jnp.asarray(live))))
    assert np.abs(o1[live] - o2[live]).max(initial=0) < 1e-6
    assert np.abs(s1[:, live] - s2[:, live]).max(initial=0) < 1e-6
    assert np.array_equal(s2[:, ~live], np.asarray(states)[:, ~live])
    assert np.array_equal(s2[[0, 2]], np.asarray(states)[[0, 2]])
    assert np.all(o2[~live] == 0) and np.all(np.isfinite(o2))
    if live.any():
        assert not np.array_equal(s2[1, live], np.asarray(states)[1, live])


def test_no_lanes_given_is_every_lane_live():
    q, k, v, g, beta, _ = rule_inputs(5, 4, 32, 1, 16, 24)
    states = jax.random.normal(jax.random.PRNGKey(9), (3, 4, 32, 16, 24))
    args = (q[:, :, 0], k[:, :, 0], v[:, :, 0], g[:, :, 0], beta[:, :, 0])
    o1, s1 = kda.step(*args, states, 1, "interpret")
    o2, s2 = kda.step(*args, states, 1, "interpret",
                      gated_delta.lane_order(jnp.ones((4,), bool)))
    assert np.array_equal(np.asarray(o1), np.asarray(o2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))


def test_a_left_padded_bucket_is_the_unpadded_prompt(whole):
    """Row 1 of a bucket of 140 is a prompt of 118 behind 22 pad
    positions: its logits and its state are the unpadded prompt's (the
    pad feeds zeros into the convolution and leaves the state alone, the
    latent layers mask it; the chunk grid shifts by the pad, hence
    allclose and not equal)."""
    _, cfg, params = whole
    ids = np.random.RandomState(1).randint(0, 256, (2, 140))
    padded = ids.copy()
    padded[1, :22] = 0
    fwd = jax.jit(lambda p, i, c, pad: kda_moe.forward_with_cache(
        p, i, cfg, c, pad, flash_prefill=True))
    got, cache = fwd(params, jnp.asarray(padded),
                     kda_moe.make_cache(cfg, 2, 256), jnp.asarray([0, 22]))
    alone, solo = fwd(params, jnp.asarray(ids[1:, 22:]),
                      kda_moe.make_cache(cfg, 1, 256), None)
    assert np.abs(np.asarray(got[1, 22:] - alone[0])).max() < TOL
    for a, b in zip(cache.state, solo.state):
        assert np.abs(np.asarray(a[:, 1] - b[:, 0])).max() < 1e-5
    # the first layer's tail is the last three inputs, pad or no pad
    assert np.array_equal(np.asarray(cache.state[1][0, 1]),
                          np.asarray(solo.state[1][0, 0]))


def test_the_shares_add_up_to_the_uncut_layer(whole):
    """What each of 16 chips gives for a layer (its one held expert's
    terms), the shared expert counted once, adds up to the uncut
    reference layer."""
    sizes, cfg, params = whole
    m = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64))
    layer = 3                     # the fourth expert layer: layer 5, a K
    moe = jax.tree.map(lambda x: x[1], params["groups"][1][0]["moe"])
    uncut = np.asarray(REF._experts(
        moe, jax.tree.map(lambda x: x[layer], params["experts"]),
        m[0], sizes, None))
    shared = np.asarray(llama.swiglu(moe["shared"], m.reshape(-1, 64)))
    total = np.zeros_like(uncut)
    for first in range(16):
        _, share_cfg, share_params = share_of(sizes, params, first, 1)
        out, counts = kda_moe.expert_layer(moe, share_params["experts"], m,
                                           share_cfg, layer)
        assert counts.shape == (1,)
        total += np.asarray(out[0]) - shared
    assert np.abs(total + shared - uncut).max() < 1e-5


def test_the_latent_mixer_is_the_other_familys_with_two_conditions(whole):
    """``latent_moe._attention`` under this family's config: no query
    bottleneck and no rotation; with angles of zero the rotating
    family's arithmetic is the same, so the condition drops work and
    changes no sum."""
    _, cfg, params = whole
    assert kda_moe._attention is latent_moe._attention
    attn = jax.tree.map(lambda x: x[0], params["groups"][1][1]["attn"])
    assert set(attn) == {"wq", "wdkv", "kv_norm", "wuk", "wuv", "wo"}
    a = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 64))
    out, _ = latent_moe._attention(attn, a, cfg, None, None, None, 0, 0,
                                   None, True)
    turning = dataclasses.replace(cfg, mla_use_nope=False)
    cos, sin = pair_angles(jnp.zeros((9,)), 8)            # no turn at all
    same, _ = latent_moe._attention(attn, a, turning, cos, sin, None, 0, 0,
                                    None, True)
    assert np.abs(np.asarray(out - same)).max() < 1e-6


def test_the_pool_holds_the_latent_layers_and_the_slab_the_rest(whole):
    _, cfg, params = whole
    eng = DecodeEngine(params, cfg, max_seq=256)
    assert eng._decode_kernel is None and eng.cache_counters
    pool = KVBlockPool.for_engine(eng, 32, block_size=16, state_slots=5)
    # 4 cached layers of 11, one plane of one latent row, the counters
    # beside them
    assert pool.data.shape == (4, 33, 1, 1, 16, 40) and pool.planes == 1
    assert pool.slab.slots == 5
    assert pool.slab.bytes_per_slot == 7 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    cache = pool.gather(np.full((1, pool.nbm), pool.trash, np.int32), 0)
    assert cache.k.shape == (4, 1, 1, 256, 40) and cache.v.shape == (5,)
    assert cache.state is None          # rows' state is the slab's
    with pytest.raises(ValueError, match="state_slots"):
        KVBlockPool.for_engine(eng, 32, block_size=16)


def test_a_lane_without_a_request_is_not_streamed_by_the_step(whole):
    """Three rows after their prompts, one position through
    ``forward_with_cache``: with lane 2's pad at the cache's length (a
    lane without a request, ``iterbatch._empty_span``) the interpreted
    kernels give the live rows the XLA path's logits, as they do with
    every lane live; the empty lane's state goes out as it came in and
    its logits are finite."""
    _, cfg, params = whole
    ids = jnp.asarray(np.random.RandomState(4).randint(0, 256, (3, 41)))
    fwd = jax.jit(lambda p, i, c, pad, kernel: kda_moe.forward_with_cache(
        p, i, cfg, c, pad, decode_kernel=kernel),
        static_argnames=("kernel",))
    _, cache = fwd(params, ids[:, :40], kda_moe.make_cache(cfg, 3, 256),
                   jnp.asarray([0, 5, 0]), None)
    pad = jnp.asarray([0, 5, 256])
    want, _ = fwd(params, ids[:, 40:], cache, pad, None)
    got, after = fwd(params, ids[:, 40:], cache, pad, "interpret")
    assert np.abs(np.asarray(got[:2] - want[:2])).max() < TOL
    assert np.all(np.isfinite(np.asarray(got)))
    before = cache.state[0]
    assert np.array_equal(np.asarray(after.state[0][:, 2]),
                          np.asarray(before[:, 2]))
    assert not np.array_equal(np.asarray(after.state[0][:, :2]),
                              np.asarray(before[:, :2]))


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_solo_and_paged_streams_are_the_references_choice(whole, kernel):
    """The solo engine and the solo paged runner (which carries the
    row's state itself) serve one stream; teacher-forced through the
    reference every served token is its choice or within noise of it.
    ``interpret`` runs BOTH Pallas kernels of a decode step."""
    sizes, cfg, params = whole
    eng = DecodeEngine(params, cfg, max_seq=256, decode_kernel=kernel)
    assert eng._decode_kernel == (None if kernel == "xla" else kernel)
    prompt = np.random.RandomState(2).randint(0, 256, (70,))
    got = eng.generate(prompt, 24).tokens[0]
    pool = KVBlockPool.for_engine(eng, 32, block_size=16, state_slots=2)
    paged = PagedKVRunner(eng, pool).generate(prompt, 24).tokens[0]
    assert np.array_equal(got, paged)
    ref = reference_logits(params, sizes, got[:-1])[len(prompt) - 1:]
    served = got[len(prompt):]
    assert np.all(ref.max(-1) - ref[np.arange(len(served)), served] < TOL)


@pytest.mark.parametrize("kernel,pooled", [("xla", False), ("xla", True),
                                           ("interpret", True)])
def test_rows_that_join_and_retire_serve_their_solo_streams(whole, kernel,
                                                            pooled):
    """Rows joining a live batch (their state merged into a lane
    with no roll), growing it, and retiring, through
    ``IterBatchingEngine`` with and without the pool, the slab and the
    store: every stream equals its solo run; the spans carry the routing
    counters and the state labels, ``stats()`` the slab's. A hit
    restores a snapshot AND shares latent blocks."""
    sizes, cfg, params = whole
    eng = DecodeEngine(params, cfg, max_seq=256, decode_kernel=kernel)
    pool = prefix = None
    if pooled:
        pool = KVBlockPool.for_engine(eng, 96, block_size=16,
                                      state_slots=3)
        prefix = PrefixCachingEngine(eng, capacity=3, chunk=64, pool=pool)
    it = IterBatchingEngine(eng, max_batch=4, seg_steps=8, prefix=prefix,
                            pool=pool)
    rs = np.random.RandomState(6)
    shared = rs.randint(0, 256, (64,))
    prompts = [rs.randint(0, 256, (150,)),  # the deepest first: the rest join
               np.concatenate([shared, rs.randint(0, 256, (7,))]),
               np.concatenate([shared, rs.randint(0, 256, (30,))]),
               rs.randint(0, 256, (11,))]
    news = [48, 12, 9, 14]
    got = {}

    def go(i):
        tr = tracing.RequestTrace(f"r{i}")
        with tracing.use_trace(tr):
            got[i] = (it.generate(prompts[i], news[i]), tr)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(4)]
    seg, started = eng._decode_seg, []

    def first_segment_waits_for_the_joiners(*a, **kw):
        out = seg(*a, **kw)
        if not started:
            started.append(1)
            for t in threads[1:]:
                t.start()
                time.sleep(0.02)
            deadline = time.monotonic() + 120
            while it._queue.qsize() < 3 and time.monotonic() < deadline:
                time.sleep(0.001)
        return out

    eng._decode_seg = first_segment_waits_for_the_joiners
    threads[0].start()
    for t in threads:
        t.join(timeout=600)
    st = it.stats()
    assert st["joins"] >= 3 and st["grows"] >= 1
    solo = DecodeEngine(params, cfg, max_seq=256)
    for i in range(4):
        want = solo.generate(prompts[i], news[i]).tokens
        res, tr = got[i]
        assert np.array_equal(res.tokens, want), i
        tr.settle()
        dec = [s for s in tr.spans if s.name == "decode"]
        assert dec and all({"experts_hit", "pairs_here", "pairs_routed"}
                           <= set(s.labels) for s in dec)
    steps = {s.labels["seg"]: s.labels["steps"] for _, tr in got.values()
             for s in tr.spans if s.name == "decode"}
    assert len(steps) == st["segments"] and max(steps.values()) == 8
    assert st["moe.layer_forwards"] == 10 * sum(steps.values())
    if pooled:
        pre = [s for _, tr in got.values() for s in tr.spans
               if s.name == "prefill" and "state_restored" in s.labels]
        # the first prompt behind the shared 64 took a snapshot at that
        # depth, the second restored it (and registered nothing new)
        assert sorted(s.labels["state_restored"] for s in pre)[-1] == 64
        assert sum(s.labels["state_snapshots"] for s in pre) == 1
        assert prefix.stats()["hits"] >= 1
        assert st["state.slots"] == 7 and st["state.restores"] >= 1
        # the batch has ended: what is held is the store's snapshot
        assert st["state.in_use"] == st["state.snapshots"] == 1
        assert 4 <= st["state.peak"] <= 7
        # no call moved a record: a restore out of its slot, a snapshot
        # into its slot and a joiner's record into its lane are all
        assert st["state_calls_resident"] == st["segments"]
        assert st["state.rows_gathered"] == st["state.restores"]
        assert st["state.rows_scattered"] == 1 + st["joins"]
        assert pool.slab.slots == 3 and pool.slab.stats()["state.peak"] <= 2
        assert pool.allocator.stats().blocks_in_use == \
            pool.allocator.stats().blocks_evictable
    else:
        assert "state.slots" not in st


def test_a_store_hit_is_a_cold_prefill(whole):
    """A snapshot restored at depth 128 and extended gives the logits
    and the state of the cold walk BIT FOR BIT (the chunk grid is
    absolute), the latent blocks shared."""
    _, cfg, params = whole
    eng = DecodeEngine(params, cfg, max_seq=256)
    pool = KVBlockPool.for_engine(eng, 64, block_size=16, state_slots=4)
    store = PrefixCachingEngine(eng, capacity=2, chunk=64, pool=pool)
    rs = np.random.RandomState(8)
    shared = rs.randint(0, 256, (128,))
    first = np.concatenate([shared, rs.randint(0, 256, (5,))])
    second = np.concatenate([shared, rs.randint(0, 256, (40,))])
    store.prefill_state(first)                   # registers depth 128
    slab = pool.slab
    assert slab.stats()["state.snapshots"] == 1
    hit_logits, hit_cache, _ = store.prefill_state(second)
    assert store.stats()["hits"] == 1 and slab.stats()["state.restores"] == 1
    cold = PrefixCachingEngine(eng, capacity=2, chunk=64)
    cold_logits, cold_cache, _ = cold.prefill_state(second)
    assert np.array_equal(np.asarray(hit_logits), np.asarray(cold_logits))
    for a, b in zip(hit_cache.state, cold_cache.state):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_what_the_engines_refuse():
    # (what the SERVER refuses for every family: tests/test_family.py)
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine
    cfg = kda_moe.CONFIGS["kda-moe-tiny"]
    params = kda_moe.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="int8"):
        DecodeEngine(params, cfg, max_seq=64, dtype="int8")
    with pytest.raises(NotImplementedError, match="rewound"):
        SpecDecodeEngine(params, cfg, max_seq=64, draft_len=2)
    with pytest.raises(ValueError, match="dropped on the way"):
        kda_moe.forward_with_cache(
            params, jnp.zeros((1, 1), jnp.int32), cfg,
            kda_moe.make_cache(cfg, 1, 64)._replace(state=None))


def test_served_over_http_with_pool_store_and_slab():
    """The normal path: ``create_app`` -> ``POST /generate`` under
    ``BATCH_MODE=iter`` with the pool, its state slab and the prefix
    store; /healthz's scheduler block carries the slab's counters."""
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    cfg = kda_moe.CONFIGS["kda-moe-tiny"]
    params = kda_moe.init_params(cfg, jax.random.PRNGKey(0))
    app = create_app(ServingConfig(
        model_id="test", max_seq=128, batch_mode="iter", max_batch=2,
        kv_pool_blocks=32, kv_block_size=16, prefix_cache=2),
        model=(cfg, params))
    body = json.dumps({"prompt": "a b c d e f g h", "max_new_tokens": 6,
                       "mode": "greedy"}).encode()
    status, payload, _ = app.handle("POST", "/generate", body, {})
    assert status == 200 and payload["generated"]
    st = app.runner.stats()
    assert st["state.slots"] == 2 + 2 and st["state.in_use"] == 0
    assert st["state.peak"] >= 1
