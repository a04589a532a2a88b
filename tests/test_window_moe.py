"""The sliding-window / sparse-expert family against its plain reference.

Small sizes in the published proportions (two periods of three
sliding-window layers and one full-attention layer, a window of 8, d 64,
4 / 2 heads of 32, a dense first layer of width 96, then 16 experts of
width 32 of which 4 are held, top 4, a shared one), seeded random
weights from the REFERENCE's ``init`` (the tree the benchmark hands the
program), float32 on the CPU.

Tolerance: ``TOL`` = 5e-5 on logits whose spread is about 1. Both sides
are float32 at ``highest``; they differ in the order of their sums (a
ring read in slot order or a band in blocks against the reference's
masked blocks), which leaves a few ulps a layer: 4e-6 measured over
eight layers. The same program in bfloat16 moves the logits by 1e-2 and
more (a flipped expert choice by tenths), and a test holds it to FAIL
the bound.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.window_moe import window_moe as REF
from llm_sharding_demo_tpu.models import (cache_entry, cache_layers,
                                          family_module,
                                          is_window_independent, llama,
                                          row_state, window_moe)
from llm_sharding_demo_tpu.ops import sliding_window
from llm_sharding_demo_tpu.ops.attention import causal_attention
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool, PagedKVRunner
from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine
from llm_sharding_demo_tpu.utils import graftnum, tracing

TOL = 5e-5
W = 8
SIZES = dict(
    hidden_size=64, vocab_size=256, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, sliding_window=W,
    full_attention_interval=4, intermediate_size=96,
    moe_intermediate_size=32, num_shared_experts=1,
    first_k_dense_replace=1, num_experts=16, published_num_experts=16,
    first_expert=0, num_experts_per_tok=4, routed_scaling_factor=2.5,
    norm_topk_prob=True, rms_norm_eps=1e-5, rope_theta=1000000,
    num_hidden_layers=8, max_position_embeddings=512,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2)


def config_of(s):
    return window_moe.WindowMoEConfig(
        vocab_size=s["vocab_size"], n_positions=s["max_position_embeddings"],
        n_embd=s["hidden_size"], n_layer=s["num_hidden_layers"],
        n_head=s["num_attention_heads"], n_kv_head=s["num_key_value_heads"],
        head_dim=s["head_dim"], sliding_window=s["sliding_window"],
        full_attention_interval=s["full_attention_interval"],
        intermediate_size=s["intermediate_size"],
        moe_intermediate_size=s["moe_intermediate_size"],
        n_shared_experts=s["num_shared_experts"],
        first_k_dense=s["first_k_dense_replace"],
        n_routed_total=s["published_num_experts"],
        n_routed_experts=s["num_experts"], first_expert=s["first_expert"],
        n_experts_per_tok=s["num_experts_per_tok"],
        routed_scaling_factor=s["routed_scaling_factor"],
        norm_topk_prob=s["norm_topk_prob"], rms_norm_eps=s["rms_norm_eps"],
        rope_theta=s["rope_theta"])


@pytest.fixture(scope="module")
def whole():
    params = REF.init(SIZES, 7, jnp.float32)
    return SIZES, config_of(SIZES), params


@pytest.fixture(scope="module")
def wide():
    """The same plan with heads of 64, which the two-plane decode
    kernel's geometry rule takes (2 x 64 lanes), a quarter of the
    experts held: for the interpreted kernel and the runtime."""
    sizes = dict(SIZES, head_dim=64)
    params = REF.init(sizes, 11, jnp.float32)
    return share_of(sizes, params, 4, 4)


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


def masked_attention(q, k, v, window):
    """The band written as a mask over all pairs: the yardstick."""
    t = q.shape[2]
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    g = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, g, axis=1), np.repeat(v, g, axis=1)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = np.where((j <= i) & (i - j < window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)


def test_family_is_registered_and_declares_what_it_caches(whole):
    _, cfg, _ = whole
    assert family_module(cfg) is window_moe
    assert is_window_independent(cfg)
    # two of the eight layers cache positions, six hold a window
    assert cache_layers(cfg) == 2 and cfg.n_sliding == 6
    assert cache_entry(cfg) == (1, 2, 64)     # fused [K | V] rows
    ((ring, ring_t),) = row_state(cfg, jnp.bfloat16)
    assert ring == (6, 2, W, 64) and ring_t == jnp.bfloat16
    cache = window_moe.make_cache(cfg, 3, 64)
    assert cache.k.shape == (2, 3, 2, 64, 64) and cache.v.shape == (5,)
    assert [x.shape for x in cache.state] == [(6, 3, 2, W, 64)]
    # the dense layer has no router and the expert layers no dense mlp
    params = window_moe.init_params(cfg, jax.random.PRNGKey(0))
    assert "mlp" in params["head"][0] and "moe" not in params["head"][0]
    assert all("moe" in p and "mlp" not in p
               for p in params["head"][1:] + params["periods"])
    assert params["experts"]["gate"]["kernel"].shape[:2] == (7, 16)
    want = jax.tree.map(jnp.shape, REF.init(SIZES, 1, jnp.float32))
    assert jax.tree.map(jnp.shape, params) == want


@pytest.mark.parametrize("held", [(0, 16), (4, 4)], ids=["all", "share"])
def test_prefill_then_decode_through_the_cache_agrees(whole, held):
    """Prefill 50 tokens (six windows and a ragged rest) then decode 10
    through the cache, against the reference's ONE full pass, on logits;
    the routing counters count every pair; bfloat16 fails the bound."""
    sizes, cfg, params = whole
    sizes, cfg, params = share_of(sizes, params, *held)
    ids = np.random.RandomState(0).randint(0, 256, (2, 60))
    ref = np.stack([reference_logits(params, sizes, row) for row in ids])
    cache = window_moe.make_cache(cfg, 2, 64)
    fwd = jax.jit(lambda p, i, c: window_moe.forward_with_cache(p, i, cfg, c))
    got, cache = fwd(params, jnp.asarray(ids[:, :50]), cache)
    assert np.abs(np.asarray(got) - ref[:, :50]).max() < TOL
    for t in range(50, 60):
        one, cache = fwd(params, jnp.asarray(ids[:, t:t + 1]), cache)
        assert np.abs(np.asarray(one[:, 0]) - ref[:, t]).max() < TOL, t
    counters = dict(zip(window_moe.CACHE_COUNTERS, np.asarray(cache.v)))
    assert counters["pairs_routed"] == 2 * 60 * 4 * 7
    assert counters["layer_forwards"] == 7 * 11
    if held == (0, 16):
        assert counters["pairs_here"] == counters["pairs_routed"]
    else:
        assert 0 < counters["pairs_here"] < counters["pairs_routed"]
    assert int(cache.length) == 60
    full = np.asarray(window_moe.forward(params, jnp.asarray(ids), cfg))
    assert np.abs(full - ref).max() < TOL
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    rough = np.asarray(window_moe.forward(low, jnp.asarray(ids), cfg))
    assert np.abs(rough - ref).max() > 100 * TOL


@pytest.mark.parametrize("start", [W - 2, W, 2 * W - 1],
                         ids=["below", "at", "wrapped"])
def test_decode_on_both_sides_of_the_window_and_past_a_wrap(whole, start):
    """One position at a time from depth ``start`` to ``2 W + 3``: the
    window fills (valid slots below ``W``), is exactly full, and the
    ring wraps and overwrites what left the window."""
    sizes, cfg, params = whole
    ids = np.random.RandomState(3).randint(0, 256, (1, 2 * W + 4))
    ref = reference_logits(params, sizes, ids[0])
    fwd = jax.jit(lambda p, i, c: window_moe.forward_with_cache(p, i, cfg, c))
    _, cache = fwd(params, jnp.asarray(ids[:, :start]),
                   window_moe.make_cache(cfg, 1, 64))
    for t in range(start, 2 * W + 4):
        one, cache = fwd(params, jnp.asarray(ids[:, t:t + 1]), cache)
        assert np.abs(np.asarray(one[0, 0]) - ref[t]).max() < TOL, t


@pytest.mark.parametrize("t,depth", [(1, 0), (5, 3), (W, W - 1), (13, 21),
                                     (4 * W, 5), (4 * W + 1, 0), (75, 19)])
def test_a_call_computes_the_band_and_keeps_the_last_window(t, depth):
    """``ring_banded_attention`` over ``t`` new positions behind
    ``depth`` old ones (one block up to four windows, blocks of a window
    beyond), two rows of which one opens with a left pad: the band
    written as a mask over all pairs, and the ring that comes back holds
    the last ``W`` positions where ``position % W`` says."""
    rs = np.random.RandomState(t * 31 + depth)
    total = depth + t
    q = rs.randn(2, 4, total, 16).astype(np.float32)
    k = rs.randn(2, 2, total, 16).astype(np.float32)
    v = rs.randn(2, 2, total, 16).astype(np.float32)
    want = masked_attention(q, k, v, W)
    rows = np.concatenate([k, v], axis=-1)
    ring = np.zeros((2, 2, W, 32), np.float32)
    for p in range(depth):
        ring[:, :, p % W] = rows[:1, :, p]       # both rows hold row 0's
    pad = 3 if depth == 0 and t > 3 else 0  # row 1 opens with a pad
    depths = jnp.asarray([depth, depth - pad], jnp.int32)
    new = [np.concatenate([np.zeros_like(x[:1, :, :pad]),
                           x[:1, :, depth:total - pad]], axis=2)
           for x in (q, k, v)]
    args = [jnp.asarray(np.concatenate([x[:1, :, depth:], y]))
            for x, y in zip((q, k, v), new)]
    out, kept = sliding_window.ring_banded_attention(
        *args, jnp.asarray(ring), depths)
    assert np.abs(np.asarray(out[0]) - want[0, :, depth:]).max() < 1e-5
    assert np.abs(np.asarray(out[1, :, pad:])
                  - want[0, :, depth:total - pad]).max() < 1e-5
    for row, last in ((0, total), (1, total - pad)):
        for p in range(max(last - W, 0), last):
            assert np.array_equal(np.asarray(kept[row, :, p % W]),
                                  rows[0, :, p]), (row, p)
    if total - pad < W:                    # slots never reached stay zero
        assert not np.asarray(kept[1, :, total - pad:]).any()


def test_query_blocks_do_not_change_a_query(monkeypatch):
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(2, 4, 70, 16).astype(np.float32))
    k = jnp.asarray(rs.randn(2, 2, 96, 16).astype(np.float32))
    v = jnp.asarray(rs.randn(2, 2, 96, 16).astype(np.float32))
    pad = jnp.asarray([0, 4])
    want = causal_attention(q, k, v, 20, 90, pad)
    assert sliding_window.blocked_causal_attention(
        q, k, v, 20, 90, pad) is not None
    monkeypatch.setattr(sliding_window, "SCORE_BUDGET", 2 * 4 * 96 * 16)
    got = sliding_window.blocked_causal_attention(q, k, v, 20, 90, pad)
    assert np.abs(np.asarray(got - want)).max() < 1e-6


def test_a_walk_in_several_calls_is_the_walk_in_one(whole):
    """A prompt forwarded in one call, and in calls of 19, 1, 33 and 7
    (blocks, a single position, a straddle): the same logits within
    float32 noise, the same ring slot for slot."""
    _, cfg, params = whole
    ids = jnp.asarray(np.random.RandomState(4).randint(0, 256, (1, 60)))
    fwd = jax.jit(lambda p, i, c: window_moe.forward_with_cache(p, i, cfg, c))
    one, whole_cache = fwd(params, ids, window_moe.make_cache(cfg, 1, 64))
    cache, parts, at = window_moe.make_cache(cfg, 1, 64), [], 0
    for n in (19, 1, 33, 7):
        got, cache = fwd(params, ids[:, at:at + n], cache)
        parts.append(got)
        at += n
    assert np.abs(np.asarray(jnp.concatenate(parts, 1) - one)).max() < TOL
    assert np.abs(np.asarray(cache.state[0]
                             - whole_cache.state[0])).max() < 1e-5
    assert np.abs(np.asarray(cache.k - whole_cache.k)).max() < 1e-5


def test_a_left_padded_bucket_is_the_unpadded_prompt(whole):
    """Row 1 of a bucket of 48 is a prompt of 37 behind 11 pad
    positions: its logits and its ring are the unpadded prompt's (a slot
    is the row's own position modulo the window; the pad writes
    nothing), at the prefill and at the steps after it."""
    _, cfg, params = whole
    ids = np.random.RandomState(1).randint(0, 256, (2, 52))
    padded = ids.copy()
    padded[1, :11] = 0
    fwd = jax.jit(lambda p, i, c, pad, fresh: window_moe.forward_with_cache(
        p, i, cfg, c, pad, flash_prefill=fresh), static_argnums=4)
    pad = jnp.asarray([0, 11])
    got, cache = fwd(params, jnp.asarray(padded[:, :48]),
                     window_moe.make_cache(cfg, 2, 64), pad, True)
    alone, solo = fwd(params, jnp.asarray(ids[1:, 11:48]),
                      window_moe.make_cache(cfg, 1, 64), None, True)
    assert np.abs(np.asarray(got[1, 11:] - alone[0])).max() < TOL
    assert np.abs(np.asarray(cache.state[0][:, 1]
                             - solo.state[0][:, 0])).max() < 1e-5
    for t in range(48, 52):
        got, cache = fwd(params, jnp.asarray(padded[:, t:t + 1]), cache,
                         pad, False)
        alone, solo = fwd(params, jnp.asarray(ids[1:, t:t + 1]), solo, None,
                          False)
        assert np.abs(np.asarray(got[1] - alone[0])).max() < TOL
    # a short prompt behind a long pad: slots it never reached are zero
    short = np.zeros((1, 16), np.int64)
    short[0, 13:] = ids[0, :3]
    _, cache = fwd(params, jnp.asarray(short),
                   window_moe.make_cache(cfg, 1, 64), jnp.asarray([13]), True)
    ring = np.asarray(cache.state[0])
    assert ring[:, :, :, :3].any() and not ring[:, :, :, 3:].any()


def test_the_shares_add_up_to_the_uncut_layer_and_logits(whole):
    """What every share of a layer gives (its held experts' terms), the
    shared expert counted once, adds up to the uncut reference layer;
    the vocabulary's eight slices concatenate to the uncut logits."""
    sizes, cfg, params = whole
    m = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64))
    layer = 5                                    # 0-based, of the model
    moe = jax.tree.map(lambda x: x[0], params["periods"][1]["moe"])
    mine = jax.tree.map(lambda x: x[layer - 1], params["experts"])
    uncut = np.asarray(REF._experts(moe, mine, m[0], sizes, None))
    shared = np.asarray(llama.swiglu(moe["shared"], m.reshape(-1, 64)))
    total = np.zeros_like(uncut)
    for first in range(0, 16, 2):                # eight shares of two
        _, share_cfg, share_params = share_of(sizes, params, first, 2)
        out, counts = window_moe.expert_layer(
            moe, share_params["experts"], m, share_cfg, layer - 1)
        assert counts.shape == (2,)
        total += np.asarray(out[0]) - shared
    assert np.abs(total + shared - uncut).max() < 1e-5
    # ids of the first slice (the rows a share's embedding holds)
    ids = np.random.RandomState(9).randint(0, 32, (24,))
    ref = reference_logits(params, sizes, ids)
    head = params["lm_head"]["kernel"]
    slices = [np.asarray(REF.logits(
        dict(params, lm_head={"kernel": head[:, lo:lo + 32]}), sizes,
        list(ids), list(range(24)))) for lo in range(0, 256, 32)]
    assert np.abs(np.concatenate(slices, -1) - ref).max() < 1e-5
    first = dict(params, wte=params["wte"][:32],
                 lm_head={"kernel": head[:, :32]})
    cut = config_of(dict(sizes, vocab_size=32))
    got = np.asarray(window_moe.forward(first, jnp.asarray(ids[None]), cut))
    assert np.abs(got[0] - ref[:, :32]).max() < TOL


def test_the_pool_holds_the_full_layers_and_the_slab_the_windows(whole):
    _, cfg, params = whole
    eng = DecodeEngine(params, cfg, max_seq=256)
    assert eng._decode_kernel is None and eng.cache_counters
    pool = KVBlockPool.for_engine(eng, 32, block_size=16, state_slots=5)
    # 2 cached layers of 8, one plane of fused [K | V] rows
    assert pool.data.shape == (2, 33, 1, 2, 16, 64) and pool.planes == 1
    assert pool.stats()["layers"] == 2 and pool.stats()["entry_width"] == 128
    # a slot is six layers' windows and no more, whatever MAX_SEQ
    assert pool.slab.bytes_per_slot == 6 * 2 * W * 64 * 4
    cache = pool.gather(np.full((1, pool.nbm), pool.trash, np.int32), 0)
    assert cache.k.shape == (2, 1, 2, 256, 64) and cache.state is None
    with pytest.raises(ValueError, match="state_slots"):
        KVBlockPool.for_engine(eng, 32, block_size=16)
    dense = llama.CONFIGS["llama-tiny"]
    deng = DecodeEngine(llama.init_params(dense, jax.random.PRNGKey(0)),
                        dense, max_seq=64)
    assert KVBlockPool.for_engine(deng, 8, block_size=16).stats()[
        "layers"] == dense.n_layer


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_solo_and_paged_streams_are_the_references_choice(wide, kernel):
    """The solo engine and the solo paged runner (which carries the
    row's rings itself) serve one stream, well past the window;
    teacher-forced through the reference every served token is its
    choice or within noise of it."""
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
    """Rows joining a live batch (their rings merged with no roll, or
    into a slab slot), growing it, and retiring, through
    ``IterBatchingEngine`` with and without the pool, the slab and the
    store: every stream equals its solo run; the spans carry the routing
    counters and the state labels, ``stats()`` the slab's and the
    windows'."""
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
    got, sampled = {}, []

    def go(i):
        tr = tracing.RequestTrace(f"r{i}")
        with tracing.use_trace(tr):
            got[i] = (it.generate(prompts[i], news[i]), tr)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(4)]
    seg, started = eng._decode_seg, []

    def first_segment_waits_for_the_joiners(*a, **kw):
        out = seg(*a, **kw)
        sampled.append(it.stats())
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
    assert st["moe.layer_forwards"] == 7 * sum(steps.values())
    # the first row alone at depth 150: six layers hold 8 of them each
    assert sampled[0]["window.positions_held"] == W
    assert sampled[0]["window.positions_seen"] == 150
    assert max(s["window.positions_held"] for s in sampled) <= 4 * W
    assert st["window.positions_held"] == st["window.positions_seen"] == 0
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


def test_a_rows_window_bytes_do_not_grow_with_depth(whole):
    """What the sliding layers hold of a row at depth ``3 W`` is what
    they hold at depth ``W``: the counters, the slab's slot, the working
    cache's leaf and the bytes a step reads."""
    _, cfg, params = whole
    fwd = jax.jit(lambda p, i, c: window_moe.forward_with_cache(p, i, cfg, c))
    ids = jnp.asarray(np.random.RandomState(5).randint(0, 256, (1, 3 * W)))
    _, at_w = fwd(params, ids[:, :W], window_moe.make_cache(cfg, 1, 64))
    _, at_3w = fwd(params, ids, window_moe.make_cache(cfg, 1, 64))
    assert at_w.state[0].shape == at_3w.state[0].shape == (6, 1, 2, W, 64)
    # the counters read the room of what is allocated, not the config:
    # records sized to a depth would read held == seen
    assert window_moe.window_positions(at_w.state, [W]) == (W, W)
    assert window_moe.window_positions(at_3w.state, [3 * W]) == (W, 3 * W)
    assert window_moe.window_positions(at_3w.state, [3, 3 * W, -2]) == (
        3 + W, 3 + 3 * W)
    deep = (jax.ShapeDtypeStruct((6, 1, 2, 64, 64), jnp.float32),)
    assert window_moe.window_positions(deep, [3 * W, 70]) == (
        3 * W + 64, 3 * W + 70)
    for max_seq in (64, 512):
        eng = DecodeEngine(params, cfg, max_seq=max_seq)
        pool = KVBlockPool.for_engine(eng, 40, block_size=16, state_slots=1)
        assert pool.slab.bytes_per_slot == 6 * 2 * W * 64 * 4
        assert window_moe.window_positions(pool.slab.data, [3 * W]) == (
            W, 3 * W)


@pytest.mark.parametrize("length,width", [
    (1, 128), (128, 128), (385, 512), (1024, 1024), (1025, 1280),
    (2995, 3072), (4097, 5120), (6556, 7168), (8192, 8192)])
def test_a_lone_prompts_width_is_a_rung_of_the_familys_ladder(length, width):
    """Whole windows up to eight, then eighths of the power of two over
    the prompt: under a quarter of pad."""
    cfg = config_of(dict(SIZES, sliding_window=128))
    assert window_moe.prompt_bucket(cfg, length) == width
    assert length <= width < max(1.25 * length, length + 128)


def test_the_scheduler_buckets_a_lone_prompt_as_the_family_says(whole):
    """20 prefill programs below 8,192 positions at the published
    window, where multiples of 16 are 512; the scheduler asks the family,
    and a family that says nothing keeps its multiples of 16."""
    _, cfg, params = whole
    served = config_of(dict(SIZES, sliding_window=128))
    assert len({window_moe.prompt_bucket(served, n)
                for n in range(1, 8193)}) == 20
    it = IterBatchingEngine(DecodeEngine(params, cfg, max_seq=64))
    assert it._bucketed(33) == 40 and it._bucketed(7) == W
    lcfg = llama.LlamaConfig(vocab_size=64, n_positions=64, n_embd=32,
                             n_layer=1, n_head=2, n_kv_head=2)
    other = IterBatchingEngine(DecodeEngine(
        llama.init_params(lcfg, jax.random.PRNGKey(0)), lcfg, max_seq=64))
    assert other._bucketed(33) == 48


def test_a_store_hit_is_a_cold_walk_and_eviction_frees_the_record(whole):
    """A record restored at depth 128 (sixteen windows) and extended
    gives the logits, the positions and the rings of the cold walk BIT
    FOR BIT; an evicted entry hands its slab slot back."""
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
    assert np.array_equal(np.asarray(hit_cache.state[0]),
                          np.asarray(cold_cache.state[0]))
    assert np.array_equal(np.asarray(hit_cache.k[..., :168, :]),
                          np.asarray(cold_cache.k[..., :168, :]))
    # the non-pool store keeps the rings inside its copied entries
    again, _, _ = cold.prefill_state(second)
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
    (blocks AND slab slot freed) and resumed by recompute, its rings
    rebuilt through the banded call. Not byte for byte the uninterrupted
    row's (``graftnum.EQUIVALENCE_BUDGETS``): every served token is the
    reference's choice or within the budget of it."""
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


# (what the SERVER refuses for every family: tests/test_family.py)
def test_what_the_engines_refuse():
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine
    cfg = window_moe.CONFIGS["window-moe-tiny"]
    params = window_moe.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="int8"):
        DecodeEngine(params, cfg, max_seq=64, dtype="int8")
    with pytest.raises(NotImplementedError, match="beside"):
        SpecDecodeEngine(params, cfg, max_seq=64, draft_len=2)
    with pytest.raises(ValueError, match="dropped on the way"):
        window_moe.forward_with_cache(
            params, jnp.zeros((1, 1), jnp.int32), cfg,
            window_moe.make_cache(cfg, 1, 64)._replace(state=None))
    with pytest.raises(ValueError, match="whole periods"):
        window_moe.WindowMoEConfig(n_layer=6)
    with pytest.raises(ValueError, match="first period"):
        window_moe.WindowMoEConfig(first_k_dense=5)


def test_served_over_http_with_pool_store_and_slab(monkeypatch):
    """The normal path: ``from_env()`` -> ``create_app`` -> ``POST
    /generate`` under ``BATCH_MODE=iter`` with the pool, its state slab
    and the prefix store, the family found by ``MODEL_ID``; /healthz's
    blocks carry the slab's counters and the pool's layers."""
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.loader import _fallback_configs
    from llm_sharding_demo_tpu.utils.config import from_env
    for k, v in dict(MODEL_ID="window-moe-tiny", MAX_SEQ="128",
                     BATCH_MODE="iter", MAX_BATCH="2", KV_POOL_BLOCKS="32",
                     KV_BLOCK_SIZE="16", PREFIX_CACHE="2").items():
        monkeypatch.setenv(k, v)
    cfg = _fallback_configs()["window-moe-tiny"]
    assert cfg is window_moe.CONFIGS["window-moe-tiny"]
    params = window_moe.init_params(cfg, jax.random.PRNGKey(0))
    app = create_app(from_env(), model=(cfg, params))
    body = json.dumps({"prompt": "a b c d e f g h i j k l m",
                       "max_new_tokens": 12, "mode": "greedy"}).encode()
    status, payload, _ = app.handle("POST", "/generate", body, {})
    assert status == 200 and payload["generated"]
    st = app.runner.stats()
    assert st["state.slots"] == 2 + 2 and st["state.in_use"] == 0
    assert st["state.peak"] >= 1 and "window.positions_held" in st
    status, health, _ = app.handle("GET", "/healthz", b"", {})
    assert health["kv_pool_stats"]["layers"] == 2
