"""The linear-attention / sparse-expert family against its plain reference.

Small sizes in the published proportions (two periods of three
gated-delta-rule layers and one gated softmax layer, d 64, 4 / 2 heads
of 32 with rotary on the leading 8, 2 key and 4 value heads of 16, 8
experts of width 32, top 2, a gated shared one), seeded random weights
from the REFERENCE's ``init`` (the tree the benchmark hands the
program), float32 on the CPU.

Tolerance: ``TOL`` = 5e-5 on logits whose spread is about 1. Both sides
are float32 at ``highest``; they differ in the order of their sums (the
program's chunked delta rule against the reference's recurrence, a
masked einsum or an online softmax against the reference's blocks),
which leaves a few ulps a layer: 1.1e-5 measured over eight layers and
150 positions. Computing anything in bfloat16 moves the logits by 1e-2
and more, so the bound would catch it.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.gdn_moe import gdn_moe as REF
from llm_sharding_demo_tpu.models import (cache_entry, cache_layers,
                                          family_module, gdn_moe,
                                          is_window_independent, llama,
                                          row_state)
from llm_sharding_demo_tpu.ops import expert_ffn, gated_delta
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool, PagedKVRunner
from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine
from llm_sharding_demo_tpu.runtime.state_slab import StateSlab
from llm_sharding_demo_tpu.utils import graftnum, tracing

TOL = 5e-5
SIZES = dict(
    hidden_size=64, vocab_size=256, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    num_experts=8, published_num_experts=8, first_expert=0,
    num_experts_per_tok=2, norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_theta=10000000, partial_rotary_factor=0.25,
    full_attention_interval=4, num_hidden_layers=8,
    max_position_embeddings=512)


def config_of(s):
    return gdn_moe.GDNMoEConfig(
        vocab_size=s["vocab_size"], n_positions=s["max_position_embeddings"],
        n_embd=s["hidden_size"], n_layer=s["num_hidden_layers"],
        n_head=s["num_attention_heads"], n_kv_head=s["num_key_value_heads"],
        head_dim=s["head_dim"],
        full_attention_interval=s["full_attention_interval"],
        partial_rotary_factor=s["partial_rotary_factor"],
        linear_num_key_heads=s["linear_num_key_heads"],
        linear_num_value_heads=s["linear_num_value_heads"],
        linear_key_head_dim=s["linear_key_head_dim"],
        linear_value_head_dim=s["linear_value_head_dim"],
        linear_conv_kernel_dim=s["linear_conv_kernel_dim"],
        moe_intermediate_size=s["moe_intermediate_size"],
        shared_expert_intermediate_size=s["shared_expert_intermediate_size"],
        n_routed_total=s["published_num_experts"],
        n_routed_experts=s["num_experts"], first_expert=s["first_expert"],
        n_experts_per_tok=s["num_experts_per_tok"],
        norm_topk_prob=s["norm_topk_prob"], rms_norm_eps=s["rms_norm_eps"],
        rope_theta=s["rope_theta"])


@pytest.fixture(scope="module")
def whole():
    params = REF.init(SIZES, 7, jnp.float32)
    return SIZES, config_of(SIZES), params


@pytest.fixture(scope="module")
def wide():
    """The same plan with heads of 64, which the two-plane decode
    kernel's geometry rule takes (2 x 64 lanes), for the interpreted
    kernels."""
    sizes = dict(SIZES, head_dim=64)
    return sizes, config_of(sizes), REF.init(sizes, 11, jnp.float32)


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


def rule_inputs(seed, b, h, t, dk, dv):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (gated_delta.l2norm(jax.random.normal(k[0], (b, h, t, dk)))
            / dk ** 0.5,
            gated_delta.l2norm(jax.random.normal(k[1], (b, h, t, dk))),
            jax.random.normal(k[2], (b, h, t, dv)),
            -jax.random.uniform(k[3], (b, h, t)) * 0.7,
            jax.nn.sigmoid(jax.random.normal(k[4], (b, h, t))),
            jax.random.normal(k[5], (b, h, dk, dv)))


def test_family_is_registered_and_declares_what_it_caches(whole):
    _, cfg, _ = whole
    assert family_module(cfg) is gdn_moe
    assert is_window_independent(cfg)
    # two of the eight layers cache positions, six hold a row's state
    assert cache_layers(cfg) == 2 and cfg.n_linear == 6
    assert cache_entry(cfg) == (1, 2, 64)     # fused [K | V] rows
    (mat, mat_t), (tail, tail_t) = row_state(cfg, jnp.bfloat16)
    assert mat == (6, 4, 16, 16) and mat_t == jnp.float32
    assert tail == (6, 3, 2 * 2 * 16 + 4 * 16) and tail_t == jnp.bfloat16
    cache = gdn_moe.make_cache(cfg, 3, 64)
    assert cache.k.shape == (2, 3, 2, 64, 64) and cache.v.shape == (5,)
    assert [x.shape for x in cache.state] == [(6, 3, 4, 16, 16),
                                              (6, 3, 3, 128)]
    # every other family: all layers cache, no row state
    dense = llama.CONFIGS["llama-tiny"]
    assert cache_layers(dense) == dense.n_layer
    assert row_state(dense, jnp.float32) == ()


@pytest.mark.parametrize("held", [(0, 8), (2, 4)], ids=["all", "share"])
def test_prefill_then_decode_through_the_cache_agrees(whole, held):
    """Prefill 140 tokens (chunks of 64 and a ragged one) then decode
    10 through the cache, against the reference's ONE full pass, on
    logits; the routing counters count every pair."""
    sizes, cfg, params = whole
    sizes, cfg, params = share_of(sizes, params, *held)
    ids = np.random.RandomState(0).randint(0, 256, (2, 150))
    ref = np.stack([reference_logits(params, sizes, row) for row in ids])
    cache = gdn_moe.make_cache(cfg, 2, 256)
    fwd = jax.jit(lambda p, i, c: gdn_moe.forward_with_cache(p, i, cfg, c))
    got, cache = fwd(params, jnp.asarray(ids[:, :140]), cache)
    assert np.abs(np.asarray(got) - ref[:, :140]).max() < TOL
    for t in range(140, 150):
        one, cache = fwd(params, jnp.asarray(ids[:, t:t + 1]), cache)
        assert np.abs(np.asarray(one[:, 0]) - ref[:, t]).max() < TOL, t
    counters = dict(zip(gdn_moe.CACHE_COUNTERS, np.asarray(cache.v)))
    assert counters["pairs_routed"] == 2 * 150 * 2 * 8
    assert counters["layer_forwards"] == 8 * 11
    if held == (0, 8):
        assert counters["pairs_here"] == counters["pairs_routed"]
    else:
        assert 0 < counters["pairs_here"] < counters["pairs_routed"]
    assert int(cache.length) == 150
    full = np.asarray(gdn_moe.forward(params, jnp.asarray(ids), cfg))
    assert np.abs(full - ref).max() < TOL


def close_keys(seed, b, h, t, dk, dv):
    """The draw on which a chunk's triangle is hardest to invert: keys
    within a few degrees of each other, nearly all of the error written
    back and hardly any decay, so ``L`` is close to 0.98 everywhere
    under the diagonal (its powers hold binomials near 1e18)."""
    q, k, v, g, beta, s0 = rule_inputs(seed, b, h, t, dk, dv)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1000), 2)
    k = gated_delta.l2norm(jax.random.normal(ks[0], (b, h, 1, dk))
                           + 0.05 * jax.random.normal(ks[1], k.shape))
    return q, k, v, jnp.full_like(g, -0.005), jnp.full_like(beta, 0.98), s0


def recurrence64(q, k, v, g, beta, s):
    """``gated_delta.recurrence`` in float64 on the host: what float32
    errors of a few 1e-7 are measured against."""
    q, k, v, g, beta, s = (np.asarray(x, np.float64)
                           for x in (q, k, v, g, beta, s))
    o = np.empty(v.shape)
    for t in range(q.shape[2]):
        s = s * np.exp(g[:, :, t])[..., None, None]
        d = beta[:, :, t, None] * (
            v[:, :, t] - np.einsum("bhk,bhkv->bhv", k[:, :, t], s))
        s = s + k[:, :, t, :, None] * d[..., None, :]
        o[:, :, t] = np.einsum("bhk,bhkv->bhv", q[:, :, t], s)
    return o, s


def serial_solve(lower, rhs):
    """What ``chunked`` called before it inverted by matmuls: forward
    substitution, a chunk's rows one after the other. The yardstick."""
    return jax.lax.linalg.triangular_solve(
        lower + jnp.eye(lower.shape[-1], dtype=lower.dtype), rhs,
        left_side=True, lower=True, unit_diagonal=True)


@pytest.mark.parametrize("t", [1, 37, 64, 65, 150, 256, 1088])
@pytest.mark.parametrize("draw,dk,dv", [
    ("ordinary", 16, 24), ("ordinary", 128, 128), ("close-keys", 128, 128)],
    ids=["small", "published", "published-close-keys"])
def test_the_chunked_rule_is_the_recurrence(monkeypatch, draw, dk, dv, t):
    """With an incoming state and lengths that are not whole chunks, at
    the tests' head sizes and the published ones; and on the draw that
    is hardest on the inversion, no further from the float64 recurrence
    than 1.5 times what the serial solve in its place is."""
    if draw == "ordinary":
        q, k, v, g, beta, s0 = rule_inputs(t, 2, 4, t, dk, dv)
        o1, s1 = gated_delta.recurrence(q, k, v, g, beta, s0)
        o2, s2 = jax.jit(gated_delta.chunked)(q, k, v, g, beta, s0)
        assert np.abs(np.asarray(o1 - o2)).max() < 2e-6
        assert np.abs(np.asarray(s1 - s2)).max() < 2e-6
        return
    hard = close_keys(t, 2, 4, t, dk, dv)
    want = recurrence64(*hard)
    got = jax.jit(gated_delta.chunked)(*hard)
    monkeypatch.setattr(gated_delta, "unit_lower_solve", serial_solve)
    yard = jax.jit(lambda *x: gated_delta.chunked(*x))(*hard)
    for w, a, b in zip(want, got, yard):
        mine, solves = np.abs(w - np.asarray(a)).max(), np.abs(
            w - np.asarray(b)).max()
        assert solves < 4e-6 and mine <= 1.5 * solves, (mine, solves)


def test_a_walk_in_several_calls_is_the_walk_in_one():
    """Calls that start at multiples of the chunk compute the same sums
    as one call: the grid is then absolute (what makes a store hit equal
    to a cold prefill)."""
    q, k, v, g, beta, s0 = rule_inputs(3, 1, 4, 229, 16, 16)
    run = jax.jit(gated_delta.chunked)
    o, s = run(q, k, v, g, beta, s0)
    outs, state = [], s0
    for lo, hi in ((0, 128), (128, 192), (192, 229)):
        part, state = run(q[:, :, lo:hi], k[:, :, lo:hi], v[:, :, lo:hi],
                          g[:, :, lo:hi], beta[:, :, lo:hi], state)
        outs.append(part)
    assert np.array_equal(np.asarray(jnp.concatenate(outs, axis=2)),
                          np.asarray(o))
    assert np.array_equal(np.asarray(state), np.asarray(s))


def test_the_kernel_is_the_recurrence():
    """The Pallas state update, interpreted: one position of every row,
    layer 1 of 3 rewritten in place and the others untouched."""
    q, k, v, g, beta, _ = rule_inputs(5, 3, 4, 1, 16, 24)
    states = jax.random.normal(jax.random.PRNGKey(9), (3, 3, 4, 16, 24))
    args = (q[:, :, 0], k[:, :, 0], v[:, :, 0], g[:, :, 0], beta[:, :, 0])
    o1, s1 = gated_delta.step(*args, states, 1, None)
    o2, s2 = gated_delta.step(*args, states, 1, "interpret")
    assert np.abs(np.asarray(o1 - o2)).max() < 1e-6
    assert np.abs(np.asarray(s1 - s2)).max() < 1e-6
    assert np.array_equal(np.asarray(s2[0]), np.asarray(states[0]))
    assert np.array_equal(np.asarray(s2[2]), np.asarray(states[2]))
    assert not np.array_equal(np.asarray(s2[1]), np.asarray(states[1]))
    assert gated_delta.kernel_eligible(128, 128, 32)
    assert not gated_delta.kernel_eligible(16, 24, 4)


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
    o1, s1 = map(np.asarray, gated_delta.step(*args, states, 1, None))
    o2, s2 = map(np.asarray, gated_delta.step(
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
    every = gated_delta.lane_order(jnp.ones((4,), bool))
    assert np.array_equal(np.asarray(every),
                          np.asarray(gated_delta.every_lane(4)))
    o1, s1 = gated_delta.step(*args, states, 1, "interpret")
    o2, s2 = gated_delta.step(*args, states, 1, "interpret", every)
    assert np.array_equal(np.asarray(o1), np.asarray(o2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))


@pytest.mark.parametrize("live", [(True, False, True), (False, True, True),
                                  (False, False, False)])
def test_the_live_lanes_come_first_in_their_order(live):
    got = np.asarray(gated_delta.lane_order(jnp.asarray(live)))
    alive = [i for i, x in enumerate(live) if x]
    assert list(got[:len(alive)]) == alive and got[-1] == len(alive)
    assert sorted(got[:-1]) == [0, 1, 2]
    # a step learns the lanes from the pads: a pad no depth reaches
    pad = jnp.asarray([0 if x else 256 for x in live])
    assert np.array_equal(
        np.asarray(gated_delta.live_lanes(pad, 40, 1, "interpret")), got)
    assert gated_delta.live_lanes(pad, 40, 1, None) is None
    assert gated_delta.live_lanes(pad, 40, 2, "interpret") is None
    assert gated_delta.live_lanes(None, 40, 1, "interpret") is None


def test_the_convolution_carries_its_tail():
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    zeros = jnp.zeros((2, 3, 6))
    whole_c, whole_tail = gated_delta.causal_conv(u, zeros, w)
    c1, tail = gated_delta.causal_conv(u[:, :4], zeros, w)
    c2, tail = gated_delta.causal_conv(u[:, 4:5], tail, w)   # one position
    c3, tail = gated_delta.causal_conv(u[:, 5:], tail, w)
    assert np.array_equal(np.asarray(jnp.concatenate([c1, c2, c3], 1)),
                          np.asarray(whole_c))
    assert np.array_equal(np.asarray(tail), np.asarray(whole_tail))
    assert np.array_equal(np.asarray(whole_tail), np.asarray(u[:, -3:]))


def test_a_left_padded_bucket_is_the_unpadded_prompt(whole):
    """Row 1 of a bucket of 140 is a prompt of 118 behind 22 pad
    positions: its logits and its state are the unpadded prompt's (the
    pad feeds zeros into the convolution and leaves the state alone; the
    chunk grid shifts by the pad, hence allclose and not equal)."""
    _, cfg, params = whole
    ids = np.random.RandomState(1).randint(0, 256, (2, 140))
    padded = ids.copy()
    padded[1, :22] = 0
    fwd = jax.jit(lambda p, i, c, pad: gdn_moe.forward_with_cache(
        p, i, cfg, c, pad))
    got, cache = fwd(params, jnp.asarray(padded),
                     gdn_moe.make_cache(cfg, 2, 256), jnp.asarray([0, 22]))
    alone, solo = fwd(params, jnp.asarray(ids[1:, 22:]),
                      gdn_moe.make_cache(cfg, 1, 256), None)
    assert np.abs(np.asarray(got[1, 22:] - alone[0])).max() < TOL
    for a, b in zip(cache.state, solo.state):
        assert np.abs(np.asarray(a[:, 1] - b[:, 0])).max() < 1e-5
    # the first layer's tail is the last three inputs, pad or no pad
    assert np.array_equal(np.asarray(cache.state[1][0, 1]),
                          np.asarray(solo.state[1][0, 0]))


def test_the_shares_add_up_to_the_uncut_layer(whole):
    """What every share of a layer gives (its held experts' terms), the
    shared expert counted once, adds up to the uncut reference layer."""
    sizes, cfg, params = whole
    m = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64))
    layer = 5                                    # a linear layer's
    moe = jax.tree.map(lambda x: x[1], params["periods"]["gdn"][1]["moe"])
    uncut = np.asarray(REF._experts(
        moe, jax.tree.map(lambda x: x[layer], params["experts"]),
        m[0], sizes, None))
    total = np.zeros_like(uncut)
    shared_once = None
    for first in (0, 2, 4, 6):
        _, share_cfg, share_params = share_of(sizes, params, first, 2)
        out, counts = gdn_moe.expert_layer(moe, share_params["experts"], m,
                                           share_cfg, layer)
        assert counts.shape == (2,)
        # the routed part alone: take the gated shared expert off
        x = m.reshape(-1, 64)
        shared = np.asarray(
            jax.nn.sigmoid(x @ moe["shared_gate"]["kernel"])
            * llama.swiglu(moe["shared"], x))
        shared_once = shared
        total += np.asarray(out[0]) - shared
    assert np.abs(total + shared_once - uncut).max() < 1e-5


@pytest.mark.parametrize("rows", [3, 80], ids=["by-rank", "by-sort"])
def test_softmax_routing_picks_and_weighs(rows):
    """Both forms of the choice (a few tokens by rank, many by a sort):
    the k largest probabilities of the softmax over ALL experts,
    normalised over the chosen."""
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, 16))
    wg = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
    ids, w = expert_ffn.route_softmax(x, wg, 4)
    p = np.asarray(jax.nn.softmax(
        jnp.matmul(x, wg, precision="highest"), axis=-1), np.float64)
    want = np.argsort(-p, axis=-1, kind="stable")[:, :4]
    assert np.array_equal(np.asarray(ids), want)
    chosen = np.take_along_axis(p, want, axis=-1)
    assert np.abs(np.asarray(w) - chosen / chosen.sum(-1, keepdims=True)
                  ).max() < 1e-6
    _, raw = expert_ffn.route_softmax(x, wg, 4, normalise=False)
    assert np.abs(np.asarray(raw) - chosen).max() < 1e-6


def test_the_slab_keeps_snapshots_one_record_a_move():
    leaves = (((2, 3, 4), jnp.float32), ((2, 5), jnp.bfloat16))
    slab = StateSlab(leaves, 3)
    assert [x.shape for x in slab.data] == [(2, 3, 3, 4), (2, 3, 5)]
    state = tuple(jnp.arange(2 * 2 * np.prod(s[1:]), dtype=jnp.float32
                             ).reshape(s[:1] + (2,) + s[1:]).astype(t)
                  for s, t in leaves)
    rows = [tuple(x[:, i:i + 1] for x in state) for i in range(2)]
    a, b, c = slab.alloc(), slab.alloc(), slab.alloc()
    assert slab.alloc() is None and slab.stats()["state.peak"] == 3
    slab.snapshot(b"one", b, rows[1])
    slab.snapshot(b"key", c, rows[0])
    assert slab.stats()["state.snapshots"] == 2
    for key, want in ((b"key", rows[0]), (b"one", rows[1])):
        got = slab.restore(key)
        assert all(x.dtype == y.dtype and x.shape == y.shape
                   and np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(got, want))
    # a key taken again moves to the new slot and frees the old one
    slab.snapshot(b"key", a, rows[1])
    assert np.array_equal(np.asarray(slab.restore(b"key")[0]),
                          np.asarray(rows[1][0]))
    assert slab.restore(b"other") is None
    slab.drop([b"key", b"never"])
    st = slab.stats()
    assert (st["state.evictions"], st["state.restores"],
            st["state.in_use"], st["state.snapshots"]) == (1, 3, 1, 1)
    assert (st["state.rows_gathered"], st["state.rows_scattered"]) == (3, 3)
    with pytest.raises(ValueError):
        slab.free(a)


def test_the_pool_holds_the_softmax_layers_and_the_slab_the_rest(whole):
    _, cfg, params = whole
    eng = DecodeEngine(params, cfg, max_seq=256)
    assert eng._decode_kernel is None and eng.cache_counters
    pool = KVBlockPool.for_engine(eng, 32, block_size=16, state_slots=5)
    # 2 cached layers of 8, one plane of fused [K | V] rows, the
    # counters beside them
    assert pool.data.shape == (2, 33, 1, 2, 16, 64) and pool.planes == 1
    assert pool.slab.slots == 5
    assert pool.slab.bytes_per_slot == 6 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    cache = pool.gather(np.full((1, pool.nbm), pool.trash, np.int32), 0)
    assert cache.k.shape == (2, 1, 2, 256, 64) and cache.v.shape == (5,)
    assert cache.state is None          # rows' state is the slab's
    with pytest.raises(NotImplementedError, match="one-plane"):
        KVBlockPool.for_engine(eng, 32, block_size=16, block_dtype="int8")
    with pytest.raises(ValueError, match="state_slots"):
        KVBlockPool.for_engine(eng, 32, block_size=16)
    with pytest.raises(ValueError, match="state_slots"):
        IterBatchingEngine(eng, pool=KVBlockPool(
            2, 32, 2, 16, 64, 256, planes=1,
            aux=jax.ShapeDtypeStruct((5,), jnp.int32)))
    # a dense family's pool is what it was: every layer, no slab
    dense = llama.CONFIGS["llama-tiny"]
    deng = DecodeEngine(llama.init_params(dense, jax.random.PRNGKey(0)),
                        dense, max_seq=64)
    dpool = KVBlockPool.for_engine(deng, 8, block_size=16, state_slots=5)
    assert dpool.data.shape[0] == dense.n_layer and dpool.slab is None
    assert not dpool.fused


def test_a_lane_without_a_request_is_not_streamed_by_the_step(wide):
    """Three rows after their prompts, one position through
    ``forward_with_cache``: with lane 2's pad at the cache's length (a
    lane without a request, ``iterbatch._empty_span``) the interpreted
    kernels give the live rows the XLA path's logits, as they do with
    every lane live; the empty lane's state goes out as it came in and
    its logits are finite."""
    _, cfg, params = wide
    ids = jnp.asarray(np.random.RandomState(4).randint(0, 256, (3, 41)))
    fwd = jax.jit(lambda p, i, c, pad, kernel: gdn_moe.forward_with_cache(
        p, i, cfg, c, pad, decode_kernel=kernel),
        static_argnames=("kernel",))
    _, cache = fwd(params, ids[:, :40], gdn_moe.make_cache(cfg, 3, 256),
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
def test_solo_and_paged_streams_are_the_references_choice(wide, kernel):
    """The solo engine and the solo paged runner (which carries the
    row's state itself) serve one stream; teacher-forced through the
    reference every served token is its choice or within noise of it."""
    sizes, cfg, params = wide
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
def test_rows_that_join_and_retire_serve_their_solo_streams(wide, kernel,
                                                            pooled):
    """Rows joining a live batch (their state merged into a lane
    with no roll), growing it, and retiring, through
    ``IterBatchingEngine`` with and without the pool, the slab and the
    store: every stream equals its solo run; the spans carry the routing
    counters and the state labels, ``stats()`` the slab's."""
    sizes, cfg, params = wide
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
    # a call runs to the first live row's budget, eight steps at most:
    # its sums are those of the steps it ran
    steps = {s.labels["seg"]: s.labels["steps"] for _, tr in got.values()
             for s in tr.spans if s.name == "decode"}
    assert len(steps) == st["segments"] and max(steps.values()) == 8
    assert st["moe.layer_forwards"] == 8 * sum(steps.values())
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


def test_a_store_hit_is_a_cold_prefill_and_eviction_frees_the_slot(whole):
    """A snapshot restored at depth 128 and extended gives the logits
    and the state of the cold walk BIT FOR BIT (the chunk grid is
    absolute); an evicted entry hands its slab slot back."""
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
    # the non-pool store keeps the state inside its copied entries
    again, again_cache, _ = cold.prefill_state(second)
    assert cold.stats()["hits"] == 1
    assert np.array_equal(np.asarray(again), np.asarray(cold_logits))
    # a third and fourth prompt: the capacity trim evicts, slots return
    for seed in (1, 2):
        store.prefill_state(np.random.RandomState(seed).randint(
            0, 256, (70,)))
    st = slab.stats()
    assert pool.allocator.prefix_len() == 2 == st["state.snapshots"]
    assert st["state.evictions"] >= 1 and st["state.in_use"] == 2
    pool.allocator.evict_lru()
    pool.allocator.evict_lru()
    assert slab.stats()["state.in_use"] == 0
    assert pool.allocator.stats().blocks_in_use == 0


def test_a_preempted_row_resumes_inside_the_declared_tolerance(whole):
    """Two long rows oversubscribe a tiny pool: the younger is parked
    (blocks AND state slot freed) and resumed by recompute, its state
    rebuilt through the chunked rule. Not byte for byte the
    uninterrupted row's (``graftnum.EQUIVALENCE_BUDGETS``): every served
    token is the reference's choice or within the budget of it."""
    sizes, cfg, params = whole
    budget = graftnum.EQUIVALENCE_BUDGETS["resume.row_state"]["logit_abs"]
    eng = DecodeEngine(params, cfg, max_seq=200)
    pool = KVBlockPool.for_engine(eng, num_blocks=25, block_size=8,
                                  watermark=1.0, state_slots=4)
    it = IterBatchingEngine(eng, max_batch=4, seg_steps=8,
                            max_wait_ms=300.0, pool=pool)
    rs = np.random.RandomState(42)
    prompts = [rs.randint(0, 256, (5,)), rs.randint(0, 256, (8,))]
    news = [96, 110]
    got = {}

    def go(i):
        got[i] = it.generate(prompts[i], news[i]).tokens[0]

    threads = [threading.Thread(target=go, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    st = it.stats()
    assert st["preemptions"] >= 1 and st["resumes"] >= 1
    assert st["parked"] == 0 and st["state.in_use"] == 0
    assert pool.allocator.stats().blocks_in_use == 0
    for i in range(2):
        seq = got[i]
        ref = reference_logits(params, sizes, seq[:-1])[len(prompts[i]) - 1:]
        served = seq[len(prompts[i]):]
        assert len(served) == news[i]
        assert np.all(ref.max(-1) - ref[np.arange(len(served)), served]
                      < budget), i


def test_what_the_engines_refuse():
    # (what the SERVER refuses for every family: tests/test_family.py)
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine
    cfg = gdn_moe.CONFIGS["gdn-moe-tiny"]
    params = gdn_moe.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="int8"):
        DecodeEngine(params, cfg, max_seq=64, dtype="int8")
    with pytest.raises(NotImplementedError, match="rewound"):
        SpecDecodeEngine(params, cfg, max_seq=64, draft_len=2)
    with pytest.raises(ValueError, match="dropped on the way"):
        gdn_moe.forward_with_cache(
            params, jnp.zeros((1, 1), jnp.int32), cfg,
            gdn_moe.make_cache(cfg, 1, 64)._replace(state=None))


def test_served_over_http_with_pool_store_and_slab():
    """The normal path: ``create_app`` -> ``POST /generate`` under
    ``BATCH_MODE=iter`` with the pool, its state slab and the prefix
    store; /healthz's scheduler block carries the slab's counters."""
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    cfg = gdn_moe.CONFIGS["gdn-moe-tiny"]
    params = gdn_moe.init_params(cfg, jax.random.PRNGKey(0))
    app = create_app(ServingConfig(
        model_id="test", max_seq=128, batch_mode="iter", max_batch=2,
        kv_pool_blocks=32, kv_block_size=16, prefix_cache=2),
        model=(cfg, params))
    import json
    body = json.dumps({"prompt": "a b c d e f g h", "max_new_tokens": 6,
                       "mode": "greedy"}).encode()
    status, payload, _ = app.handle("POST", "/generate", body, {})
    assert status == 200 and payload["generated"]
    st = app.runner.stats()
    assert st["state.slots"] == 2 + 2 and st["state.in_use"] == 0
    assert st["state.peak"] >= 1
