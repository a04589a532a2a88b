"""The state-space / attention family against its plain reference.

Small sizes in the published proportions (three layers that are all
alike, d 64, 10 / 2 heads of 32: FIVE query heads a key-value head; a
Mamba-2 mixer of 4 heads of 16 in 2 groups over a state of 24, chunks of
32; SwiGLU 96; a vocabulary of 320, wider than d; every multiplier away
from 1), seeded random weights from the REFERENCE's ``init`` (the tree
the benchmark hands the program), float32 on the CPU.

Tolerance: ``TOL`` = 2e-6 on logits whose spread is about 0.12 (the head
multiplier is an eighth). Both sides are float32 at ``highest``; they
differ in the order of their sums (the program's chunked rule against
the reference's recurrence, a masked einsum or an online softmax against
the reference's blocks), which leaves a few ulps a layer: 2.4e-7
measured over three layers and 150 positions. A state or a projection in
bfloat16 moves the logits by 1.9e-5 and more, and a multiplier set to 1 by
1.6e-3 at the least (``test_each_multiplier_is_applied``), so the bound
catches either.

The guide's "shares add up" test does not apply: no share is cut, the
configuration's one reduction is depth.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.hybrid_ssm import hybrid_ssm as REF
from llm_sharding_demo_tpu.models import (cache_entry, cache_layers,
                                          family_module, gdn_moe,
                                          hybrid_ssm, is_window_independent,
                                          row_state)
from llm_sharding_demo_tpu.ops import decode_attention, gated_delta, ssd
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool, PagedKVRunner
from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine
from llm_sharding_demo_tpu.utils import graftnum, tracing

TOL = 2e-6
SCALARS = ("embedding_multiplier", "lm_head_multiplier",
           "attention_in_multiplier", "attention_out_multiplier",
           "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")
SIZES = dict(
    hidden_size=64, vocab_size=320, num_attention_heads=10,
    num_key_value_heads=2, head_dim=32, intermediate_size=96,
    mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=24,
    mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=32,
    rms_norm_eps=1e-5, rope_theta=100000000000, num_hidden_layers=3,
    max_position_embeddings=512,
    embedding_multiplier=2.5, lm_head_multiplier=0.125,
    attention_in_multiplier=0.75, attention_out_multiplier=0.4,
    key_multiplier=0.3, ssm_in_multiplier=0.5, ssm_out_multiplier=0.6,
    ssm_multipliers=[0.7, 0.5, 0.35, 0.9, 0.6],
    mlp_multipliers=[0.45, 0.3])


def config_of(s):
    return hybrid_ssm.HybridSSMConfig(
        vocab_size=s["vocab_size"], n_positions=s["max_position_embeddings"],
        n_embd=s["hidden_size"], n_layer=s["num_hidden_layers"],
        n_head=s["num_attention_heads"], n_kv_head=s["num_key_value_heads"],
        head_dim=s["head_dim"], intermediate_size=s["intermediate_size"],
        mamba_d_ssm=s["mamba_d_ssm"], mamba_n_heads=s["mamba_n_heads"],
        mamba_d_head=s["mamba_d_head"], mamba_d_state=s["mamba_d_state"],
        mamba_n_groups=s["mamba_n_groups"], mamba_d_conv=s["mamba_d_conv"],
        mamba_chunk_size=s["mamba_chunk_size"],
        rms_norm_eps=s["rms_norm_eps"], rope_theta=s["rope_theta"],
        ssm_multipliers=s["ssm_multipliers"],
        mlp_multipliers=s["mlp_multipliers"],
        **{k: s[k] for k in SCALARS})


@pytest.fixture(scope="module")
def whole():
    return SIZES, config_of(SIZES), REF.init(SIZES, 7, jnp.float32)


@pytest.fixture(scope="module")
def wide():
    """The same plan with heads of 64, which the two-plane decode
    kernel's geometry rule takes (2 x 64 lanes), for the interpreted
    kernels; still five query heads a key-value head."""
    sizes = dict(SIZES, head_dim=64)
    return sizes, config_of(sizes), REF.init(sizes, 11, jnp.float32)


def reference_logits(params, sizes, ids):
    return np.asarray(REF.logits(params, sizes, list(ids),
                                 list(range(len(ids)))))


def rule_inputs(seed, b, t, h, g, p, n, decay="ordinary"):
    """``decay``: how fast a state forgets: ``ordinary`` draws, ``near-1``
    (``dt A`` about -1e-4) or ``near-0`` (about -40: a state gone within
    one position)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, h)))
    a = -jnp.exp(jax.random.uniform(k[2], (h,), minval=-1.4, maxval=0.7))
    if decay == "near-1":
        dt, a = dt * 1e-3, a * 0.1
    elif decay == "near-0":
        dt, a = dt + 8.0, a - 4.0
    return (jax.random.normal(k[0], (b, t, h, p)), dt, a,
            jax.random.normal(k[3], (b, t, g, n)),
            jax.random.normal(k[4], (b, t, g, n)),
            jax.random.normal(k[5], (b, h, n, p)))


def test_family_is_registered_and_declares_pool_and_slab_for_every_layer(
        whole):
    _, cfg, _ = whole
    assert family_module(cfg) is hybrid_ssm
    assert is_window_independent(cfg)
    # every one of the three layers caches positions AND holds a row's
    # state: fused [K | V] rows in one plane, matrices and tails
    assert cache_layers(cfg) == cfg.n_layer == 3
    assert cache_entry(cfg) == (1, 2, 64)
    (mat, mat_t), (tail, tail_t) = row_state(cfg, jnp.bfloat16)
    assert mat == (3, 4, 24, 16) and mat_t == jnp.float32
    assert tail == (3, 3, 64 + 2 * 2 * 24) and tail_t == jnp.bfloat16
    cache = hybrid_ssm.make_cache(cfg, 3, 64)
    assert cache.k.shape == (3, 3, 2, 64, 64) and cache.v.shape == (0,)
    assert [x.shape for x in cache.state] == [(3, 3, 4, 24, 16),
                                              (3, 3, 3, 160)]
    assert cfg.in_proj_width == 64 + 160 + 4
    # the published sizes: the arithmetic of the configuration's file
    full = hybrid_ssm.HybridSSMConfig()
    assert full.in_proj_width == 9248 and full.conv_channels == 5120
    (mat, _), (tail, _) = row_state(dataclasses.replace(full, n_layer=6),
                                    jnp.bfloat16)
    assert mat == (6, 32, 256, 128) and tail == (6, 3, 5120)
    assert hybrid_ssm.CONFIGS["hybrid-ssm-tiny"] == cfg


def test_prefill_then_decode_through_the_cache_agrees(whole):
    """Prefill 140 tokens (chunks of 32 and a ragged one) then decode
    10 through the cache, against the reference's ONE full pass, on
    LOGITS; the no-cache pass gives every position's."""
    sizes, cfg, params = whole
    ids = np.random.RandomState(0).randint(0, 320, (2, 150))
    ref = np.stack([reference_logits(params, sizes, row) for row in ids])
    cache = hybrid_ssm.make_cache(cfg, 2, 256)
    fwd = jax.jit(lambda p, i, c: hybrid_ssm.forward_with_cache(p, i, cfg, c))
    got, cache = fwd(params, jnp.asarray(ids[:, :140]), cache)
    assert np.abs(np.asarray(got[:, 0]) - ref[:, 139]).max() < TOL
    for t in range(140, 150):
        one, cache = fwd(params, jnp.asarray(ids[:, t:t + 1]), cache)
        assert np.abs(np.asarray(one[:, 0]) - ref[:, t]).max() < TOL, t
    assert int(cache.length) == 150
    full = np.asarray(hybrid_ssm.forward(params, jnp.asarray(ids), cfg))
    assert np.abs(full - ref).max() < TOL
    # what the bound is for: a state carried in bfloat16 is far outside
    low = hybrid_ssm.make_cache(cfg, 2, 256)
    got, low = fwd(params, jnp.asarray(ids[:, :140]), low)
    low = low._replace(state=(low.state[0].astype(jnp.bfloat16).astype(
        jnp.float32), low.state[1]))
    one, _ = fwd(params, jnp.asarray(ids[:, 140:141]), low)
    assert np.abs(np.asarray(one[:, 0]) - ref[:, 140]).max() > 5 * TOL


def test_a_call_of_several_returns_the_last_positions_logits_only(whole):
    """The head runs on the last position of a multi-position call: one
    row of logits comes back, equal to the full pass's last row, and the
    engine's prefill takes it."""
    sizes, cfg, params = whole
    ids = np.random.RandomState(3).randint(0, 320, (1, 70))
    got, _ = hybrid_ssm.forward_with_cache(
        params, jnp.asarray(ids), cfg, hybrid_ssm.make_cache(cfg, 1, 128))
    assert got.shape == (1, 1, 320)
    full = np.asarray(hybrid_ssm.forward(params, jnp.asarray(ids), cfg))
    assert np.abs(np.asarray(got[0, 0]) - full[0, -1]).max() < TOL
    eng = DecodeEngine(params, cfg, max_seq=128)
    last, _ = eng._prefill(eng._run_params(), jnp.asarray(ids), None)
    assert last.shape == (1, 320)
    assert np.abs(np.asarray(last[0]) - full[0, -1]).max() < TOL


MULTIPLIERS = ([(k, None) for k in SCALARS]
               + [("ssm_multipliers", i) for i in range(5)]
               + [("mlp_multipliers", i) for i in range(2)])


@pytest.mark.parametrize("key,index", MULTIPLIERS,
                         ids=[k if i is None else f"{k}-{i}"
                              for k, i in MULTIPLIERS])
def test_each_multiplier_is_applied(whole, key, index):
    """Fourteen scalars under nine keys: set to 1 in the REFERENCE, each
    one moves the logits far outside ``TOL`` of the program's, which
    applies it; with it restored they agree."""
    sizes, cfg, params = whole
    ids = np.random.RandomState(4).randint(0, 320, (60,))
    got = np.asarray(hybrid_ssm.forward(params, jnp.asarray(ids[None]),
                                        cfg))[0]
    assert np.abs(got - reference_logits(params, sizes, ids)).max() < TOL
    if index is None:
        changed = dict(sizes, **{key: 1.0})
    else:
        values = list(sizes[key])
        values[index] = 1.0
        changed = dict(sizes, **{key: values})
    moved = np.abs(got - reference_logits(params, changed, ids)).max()
    assert moved > 100 * TOL, (key, index, moved)


@pytest.mark.parametrize("t", [1, 37, 128, 129, 300, 1088])
@pytest.mark.parametrize("decay,h,g,p,n,chunk", [
    ("ordinary", 4, 2, 16, 24, 32), ("ordinary", 32, 2, 128, 256, 128),
    ("near-1", 4, 2, 16, 24, 32), ("near-0", 4, 2, 16, 24, 32)],
    ids=["small", "published", "decay-near-1", "decay-near-0"])
def test_the_chunked_rule_is_the_recurrence(decay, h, g, p, n, chunk, t):
    """With an incoming state and lengths that are not whole chunks, at
    the tests' head sizes and the published ones, and where a state
    hardly forgets or forgets within a position: relative to the
    largest read-out (hundreds: nothing is normalised here; float32 sums
    of up to 1,088 terms in another order leave 2.6e-6 of it)."""
    b = 1 if h == 32 else 2
    args = rule_inputs(t, b, t, h, g, p, n, decay)
    y1, s1 = jax.jit(ssd.recurrence)(*args)
    y2, s2 = jax.jit(lambda *x: ssd.chunked(*x, chunk))(*args)
    assert np.all(np.isfinite(np.asarray(y2)))
    assert np.abs(np.asarray(y1 - y2)).max() < 5e-6 * max(
        1.0, float(jnp.abs(y1).max()))
    assert np.abs(np.asarray(s1 - s2)).max() < 5e-6 * max(
        1.0, float(jnp.abs(s1).max()))


def test_a_walk_in_several_calls_is_the_walk_in_one():
    """Calls that start at multiples of the chunk compute the same sums
    as one call: the grid is then absolute (what makes a store hit equal
    to a cold prefill)."""
    x, dt, a, bm, cm, s0 = rule_inputs(3, 1, 229, 4, 2, 16, 24)
    run = jax.jit(lambda *v: ssd.chunked(*v, 32))
    y, s = run(x, dt, a, bm, cm, s0)
    outs, state = [], s0
    for lo, hi in ((0, 128), (128, 192), (192, 229)):
        part, state = run(x[:, lo:hi], dt[:, lo:hi], a, bm[:, lo:hi],
                          cm[:, lo:hi], state)
        outs.append(part)
    assert np.array_equal(np.asarray(jnp.concatenate(outs, axis=1)),
                          np.asarray(y))
    assert np.array_equal(np.asarray(state), np.asarray(s))


def test_the_kernel_is_the_recurrence():
    """The Pallas state update, interpreted: one position of every row,
    layer 1 of 3 rewritten in place and the others untouched; a block of
    heads reads its OWN group's B and C."""
    x, dt, a, bm, cm, _ = rule_inputs(5, 3, 1, 4, 2, 16, 24)
    states = jax.random.normal(jax.random.PRNGKey(9), (3, 3, 4, 24, 16))
    args = (x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
    y1, s1 = ssd.step(*args, states, 1, None)
    y2, s2 = ssd.step(*args, states, 1, "interpret")
    assert np.abs(np.asarray(y1 - y2)).max() < 1e-5
    assert np.abs(np.asarray(s1 - s2)).max() < 1e-6
    assert np.array_equal(np.asarray(s2[0]), np.asarray(states[0]))
    assert np.array_equal(np.asarray(s2[2]), np.asarray(states[2]))
    assert not np.array_equal(np.asarray(s2[1]), np.asarray(states[1]))
    # the two groups differ: swapping them changes the answer
    y3, _ = ssd.step(args[0], args[1], a, bm[:, 0, ::-1], cm[:, 0, ::-1],
                     states, 1, "interpret")
    assert np.abs(np.asarray(y3 - y2)).max() > 1e-2
    assert ssd.kernel_eligible(256, 128, 32, 2)
    assert not ssd.kernel_eligible(24, 16, 4, 2)
    assert ssd.kernel_eligible(24, 16, 4, 2, compiled=False)


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
    """Over four blocks of heads, two a group: a live lane's output and
    state are the recurrence's; a lane without a request is not
    streamed: its state, in every layer of the stack, is bit for bit
    what came in, its output row exactly zero."""
    live = np.asarray(LIVE[pattern](rows))
    x, dt, a, bm, cm, _ = rule_inputs(5, rows, 1, 64, 2, 16, 24)
    states = jax.random.normal(jax.random.PRNGKey(9), (3, rows, 64, 24, 16))
    args = (x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
    y1, s1 = map(np.asarray, ssd.step(*args, states, 1, None))
    y2, s2 = map(np.asarray, ssd.step(
        *args, states, 1, "interpret",
        gated_delta.lane_order(jnp.asarray(live))))
    assert np.abs(y1[live] - y2[live]).max(initial=0) < 1e-5
    assert np.abs(s1[:, live] - s2[:, live]).max(initial=0) < 1e-6
    assert np.array_equal(s2[:, ~live], np.asarray(states)[:, ~live])
    assert np.array_equal(s2[[0, 2]], np.asarray(states)[[0, 2]])
    assert np.all(y2[~live] == 0) and np.all(np.isfinite(y2))
    if live.any():
        assert not np.array_equal(s2[1, live], np.asarray(states)[1, live])


def test_no_lanes_given_is_every_lane_live():
    x, dt, a, bm, cm, _ = rule_inputs(5, 4, 1, 64, 2, 16, 24)
    states = jax.random.normal(jax.random.PRNGKey(9), (3, 4, 64, 24, 16))
    args = (x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
    y1, s1 = ssd.step(*args, states, 1, "interpret")
    y2, s2 = ssd.step(*args, states, 1, "interpret",
                      gated_delta.lane_order(jnp.ones((4,), bool)))
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))


def test_the_gated_group_norm_gates_first_and_norms_by_group():
    y = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 64))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 64))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (64,))
    got = np.asarray(ssd.gated_group_norm(y, z, w, 2, 1e-5))
    g = np.asarray(y * jax.nn.silu(z), np.float64).reshape(2, 5, 2, 32)
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)
            ).reshape(2, 5, 64) * np.asarray(w, np.float64)
    assert np.abs(got - want).max() < 1e-5
    one_group = np.asarray(ssd.gated_group_norm(y, z, w, 1, 1e-5))
    assert np.abs(one_group - want).max() > 1e-2


@pytest.mark.parametrize("bias", [False, True], ids=["no-bias", "bias"])
def test_the_convolution_carries_its_tail(bias):
    """With and without the bias; without it the result is bit for bit
    what ``gdn_moe``'s call (which passes none) always got."""
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    b = jax.random.normal(jax.random.PRNGKey(2), (6,)) if bias else None
    zeros = jnp.zeros((2, 3, 6))
    whole_c, whole_tail = gated_delta.causal_conv(u, zeros, w, b)
    c1, tail = gated_delta.causal_conv(u[:, :4], zeros, w, b)
    c2, tail = gated_delta.causal_conv(u[:, 4:5], tail, w, b)  # one position
    c3, tail = gated_delta.causal_conv(u[:, 5:], tail, w, b)
    assert np.array_equal(np.asarray(jnp.concatenate([c1, c2, c3], 1)),
                          np.asarray(whole_c))
    assert np.array_equal(np.asarray(tail), np.asarray(whole_tail))
    assert np.array_equal(np.asarray(whole_tail), np.asarray(u[:, -3:]))
    full = jnp.concatenate([zeros, u], axis=1)
    plain = sum(full[:, j:j + 10] * w[:, j] for j in range(4))
    if bias:
        assert np.abs(np.asarray(
            whole_c - jax.nn.silu(plain + b))).max() < 1e-6
        assert np.abs(np.asarray(whole_c - jax.nn.silu(plain))).max() > 1e-2
    else:
        assert np.array_equal(np.asarray(whole_c),
                              np.asarray(jax.nn.silu(plain)))
        # and the program text of a call without the argument is the
        # text of a call that passes None
        old = jax.jit(lambda u, t, w: gated_delta.causal_conv(u, t, w))
        new = jax.jit(lambda u, t, w: gated_delta.causal_conv(u, t, w, None))
        assert (old.lower(u, zeros, w).as_text()
                == new.lower(u, zeros, w).as_text())


def test_the_linear_attention_family_is_bit_for_bit_what_it_was(monkeypatch):
    """``gdn_moe`` passes no bias: its cached forward lowers to the SAME
    program text as with the convolution's body as it stood before it
    took the argument (written out here)."""
    def conv_before(u, tail, w):
        t, width = u.shape[1], w.shape[1]
        full = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
        w32 = w.astype(jnp.float32)
        c = sum(full[:, j:j + t].astype(jnp.float32) * w32[:, j]
                for j in range(width))
        return jax.nn.silu(c), full[:, t:].astype(tail.dtype)

    cfg = gdn_moe.CONFIGS["gdn-moe-tiny"]
    params = gdn_moe.init_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 40)))
    cache = gdn_moe.make_cache(cfg, 2, 64)

    def text():
        return jax.jit(lambda p, i, c: gdn_moe.forward_with_cache(
            p, i, cfg, c)).lower(params, ids, cache).as_text()

    now = text()
    monkeypatch.setattr(gated_delta, "causal_conv", conv_before)
    assert text() == now


def test_a_left_padded_bucket_is_the_unpadded_prompt(whole):
    """Row 1 of a bucket of 140 is a prompt of 118 behind 22 pad
    positions: its logits and its state are the unpadded prompt's (the
    pad feeds zeros into the convolution and leaves the state alone; the
    chunk grid shifts by the pad, hence allclose and not equal)."""
    _, cfg, params = whole
    ids = np.random.RandomState(1).randint(0, 320, (2, 140))
    padded = ids.copy()
    padded[1, :22] = 0
    fwd = jax.jit(lambda p, i, c, pad: hybrid_ssm.forward_with_cache(
        p, i, cfg, c, pad))
    got, cache = fwd(params, jnp.asarray(padded),
                     hybrid_ssm.make_cache(cfg, 2, 256), jnp.asarray([0, 22]))
    alone, solo = fwd(params, jnp.asarray(ids[1:, 22:]),
                      hybrid_ssm.make_cache(cfg, 1, 256), None)
    assert np.abs(np.asarray(got[1] - alone[0])).max() < TOL
    for a, b in zip(cache.state, solo.state):
        assert np.abs(np.asarray(a[:, 1] - b[:, 0])).max() < 1e-5
    # the first layer's tail is the last three inputs, pad or no pad
    assert np.array_equal(np.asarray(cache.state[1][0, 1]),
                          np.asarray(solo.state[1][0, 0]))


def test_five_query_heads_a_key_value_head_through_the_decode_kernel():
    """The two-plane decode kernel at a group of five (the cells have 4
    and 8): interpreted, against the masked einsum over the same fused
    buffer, with a left pad and at a depth past one block."""
    from llm_sharding_demo_tpu.ops.attention import cached_attention_fused
    b, hkv, g, hd, depth = 2, 2, 5, 64, 300
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    kv = jax.random.normal(k[0], (2, b, hkv, 512, 2 * hd))
    q = jax.random.normal(k[1], (b, hkv * g, 1, hd))
    k_new = jax.random.normal(k[2], (b, hkv, 1, hd))
    v_new = jax.random.normal(k[3], (b, hkv, 1, hd))
    pad = jnp.asarray([0, 17], jnp.int32)
    assert decode_attention.eligible(512, hd, 1)
    want, kv1 = cached_attention_fused(q, k_new, v_new, kv, 1, depth, pad)
    got, kv2 = decode_attention.decode_attention(
        q, k_new, v_new, kv, 1, depth, pad, interpret=True)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert np.array_equal(np.asarray(kv1), np.asarray(kv2))


def test_the_pool_and_the_slab_both_hold_every_layer(whole):
    _, cfg, params = whole
    eng = DecodeEngine(params, cfg, max_seq=256)
    assert eng._decode_kernel is None and not eng.cache_counters
    pool = KVBlockPool.for_engine(eng, 32, block_size=16, state_slots=5)
    # 3 cached layers of 3, one plane of fused [K | V] rows
    assert pool.data.shape == (3, 33, 1, 2, 16, 64) and pool.planes == 1
    assert pool.slab.slots == 5
    assert pool.slab.data[0].shape == (3, 5, 4, 24, 16)
    assert pool.slab.bytes_per_slot == 3 * (4 * 24 * 16 * 4 + 3 * 160 * 4)
    cache = pool.gather(np.full((1, pool.nbm), pool.trash, np.int32), 0)
    assert cache.k.shape == (3, 1, 2, 256, 64) and cache.v.shape == (0,)
    assert cache.state is None          # rows' state is the slab's
    with pytest.raises(NotImplementedError, match="one-plane"):
        KVBlockPool.for_engine(eng, 32, block_size=16, block_dtype="int8")
    with pytest.raises(ValueError, match="state_slots"):
        KVBlockPool.for_engine(eng, 32, block_size=16)


def test_a_lane_without_a_request_is_not_streamed_by_the_step(wide):
    """Three rows after their prompts, one position through
    ``forward_with_cache``: with lane 2's pad at the cache's length (a
    lane without a request, ``iterbatch._empty_span``) the interpreted
    kernels give the live rows the XLA path's logits, as they do with
    every lane live; the empty lane's state goes out as it came in and
    its logits are finite."""
    _, cfg, params = wide
    ids = jnp.asarray(np.random.RandomState(4).randint(0, 256, (3, 41)))
    fwd = jax.jit(lambda p, i, c, pad, kernel: hybrid_ssm.forward_with_cache(
        p, i, cfg, c, pad, decode_kernel=kernel),
        static_argnames=("kernel",))
    _, cache = fwd(params, ids[:, :40], hybrid_ssm.make_cache(cfg, 3, 256),
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
    prompt = np.random.RandomState(2).randint(0, 320, (70,))
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
    store: every stream equals its solo run; the spans carry the state
    labels, ``stats()`` the slab's counters, the movers' among them."""
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
    shared = rs.randint(0, 320, (64,))
    prompts = [rs.randint(0, 320, (150,)),  # the deepest first: the rest join
               np.concatenate([shared, rs.randint(0, 320, (7,))]),
               np.concatenate([shared, rs.randint(0, 320, (30,))]),
               rs.randint(0, 320, (11,))]
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
    if pooled:
        pre = [s for _, tr in got.values() for s in tr.spans
               if s.name == "prefill" and "state_restored" in s.labels]
        # the first prompt behind the shared 64 took a snapshot at that
        # depth, the second restored it (and registered nothing new):
        # blocks AND state of the same three layers
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
        assert st["state.row_bytes"] == pool.slab.bytes_per_slot
        kv = pool.stats()
        assert kv["layers"] == 3 and kv["entry_width"] == 1 * 2 * 128
    else:
        assert "state.slots" not in st


def test_a_store_hit_is_a_cold_prefill_and_eviction_frees_the_slot(whole):
    """A snapshot restored at depth 128 and extended gives the logits
    and the state of the cold walk BIT FOR BIT (the chunk grid is
    absolute: the store's 64 is two of the rule's 32), from blocks and a
    record of the SAME three layers; an evicted entry hands its slab
    slot back."""
    _, cfg, params = whole
    eng = DecodeEngine(params, cfg, max_seq=256)
    pool = KVBlockPool.for_engine(eng, 64, block_size=16, state_slots=4)
    store = PrefixCachingEngine(eng, capacity=2, chunk=64, pool=pool)
    rs = np.random.RandomState(8)
    shared = rs.randint(0, 320, (128,))
    first = np.concatenate([shared, rs.randint(0, 320, (5,))])
    second = np.concatenate([shared, rs.randint(0, 320, (40,))])
    store.prefill_state(first)                   # registers depth 128
    slab = pool.slab
    assert slab.stats()["state.snapshots"] == 1
    hit_logits, hit_cache, _ = store.prefill_state(second)
    assert store.stats()["hits"] == 1 and slab.stats()["state.restores"] == 1
    cold = PrefixCachingEngine(eng, capacity=2, chunk=64)
    cold_logits, cold_cache, _ = cold.prefill_state(second)
    assert np.array_equal(np.asarray(hit_logits), np.asarray(cold_logits))
    for a, b in zip(hit_cache.state, cold_cache.state):
        assert a.shape[0] == cfg.n_layer
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(hit_cache.k[:, :, :, :168]),
                          np.asarray(cold_cache.k[:, :, :, :168]))
    # the non-pool store keeps the state inside its copied entries
    again, again_cache, _ = cold.prefill_state(second)
    assert cold.stats()["hits"] == 1
    assert np.array_equal(np.asarray(again), np.asarray(cold_logits))
    # a third and fourth prompt: the capacity trim evicts, slots return
    for seed in (1, 2):
        store.prefill_state(np.random.RandomState(seed).randint(
            0, 320, (70,)))
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
    prompts = [rs.randint(0, 320, (5,)), rs.randint(0, 320, (8,))]
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


def test_int8_weights_serve_the_family():
    """Weight-only int8 is fitted (every matmul goes through ``linear``,
    the head included), so it is not among the refusals: the stream
    stays within quantisation noise of the float32 one's logits."""
    cfg = hybrid_ssm.CONFIGS["hybrid-ssm-tiny"]
    params = hybrid_ssm.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.RandomState(0).randint(0, 320, (20,))
    low = DecodeEngine(params, cfg, max_seq=64, dtype="int8")
    out = low.generate(prompt, 6).tokens[0]
    assert out.shape == (26,)
    ref = np.asarray(hybrid_ssm.forward(params, jnp.asarray(out[None, :-1]),
                                        cfg))[0, len(prompt) - 1:]
    served = out[len(prompt):]
    deficit = (ref.max(-1) - ref[np.arange(6), served]) / ref.std(-1)
    assert deficit.max() < 1.0


def test_what_the_engines_refuse():
    # (what the SERVER refuses for every family: tests/test_family.py)
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine
    cfg = hybrid_ssm.CONFIGS["hybrid-ssm-tiny"]
    params = hybrid_ssm.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="rewound"):
        SpecDecodeEngine(params, cfg, max_seq=64, draft_len=2)
    with pytest.raises(ValueError, match="dropped on the way"):
        hybrid_ssm.forward_with_cache(
            params, jnp.zeros((1, 1), jnp.int32), cfg,
            hybrid_ssm.make_cache(cfg, 1, 64)._replace(state=None))
    with pytest.raises(ValueError, match="5 values"):
        dataclasses.replace(cfg, ssm_multipliers=(1.0, 1.0))


def test_served_over_http_with_pool_store_and_slab():
    """The normal path: ``create_app`` -> ``POST /generate`` under
    ``BATCH_MODE=iter`` with the pool, its state slab and the prefix
    store, the model found by its preset's name; /healthz's scheduler
    block carries the slab's counters."""
    from llm_sharding_demo_tpu.serving import loader
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    cfg = loader._fallback_configs()["hybrid-ssm-tiny"]
    assert cfg is hybrid_ssm.CONFIGS["hybrid-ssm-tiny"]
    params = hybrid_ssm.init_params(cfg, jax.random.PRNGKey(0))
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
    # a lone row seeds its batch: no record is moved for it
    assert st["state.rows_gathered"] == 0 and st["state.row_bytes"] > 0
    assert st["state_calls_resident"] == st["segments"] >= 1
