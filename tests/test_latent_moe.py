"""The latent-attention / sparse-expert family against its plain reference.

Small sizes in the published proportions (d 64, 4 heads of 16 | 8 | 16,
ranks 48 and 32, 16 experts of width 24, top 4, 1 dense + 3 expert
layers), seeded random weights from the REFERENCE's ``init`` (the tree
the benchmark hands the program), float32 on the CPU.

Tolerance: ``TOL`` = 2e-5 on logits whose spread is about 1. Both sides
are float32 at ``highest``; they differ in the order of their sums (the
absorbed form contracts over the latent, the reference over the
expanded heads; the kernel's softmax is online), which leaves a few
ulps a layer, 3e-6 measured. Computing anything in bfloat16 moves the
logits by 1e-2 and more, so the bound would catch it.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.latent_moe import latent_moe as REF
from llm_sharding_demo_tpu.models import (cache_entry, family_module,
                                          is_window_independent, latent_moe)
from llm_sharding_demo_tpu.models import llama
from llm_sharding_demo_tpu.ops import expert_ffn
from llm_sharding_demo_tpu.runtime import kv_pool
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine
from llm_sharding_demo_tpu.utils import tracing

TOL = 2e-5
SIZES = dict(
    hidden_size=64, vocab_size=256, num_attention_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    moe_intermediate_size=24, intermediate_size=224, n_routed_experts=16,
    published_n_routed_experts=16, first_expert=0, first_k_dense_replace=1,
    num_hidden_layers=4, num_experts_per_tok=4, n_shared_experts=1,
    norm_topk_prob=True, routed_scaling_factor=2.5, rms_norm_eps=1e-6,
    rope_theta=32000000, max_position_embeddings=512)


def config_of(sizes):
    return latent_moe.LatentMoEConfig(
        vocab_size=sizes["vocab_size"],
        n_positions=sizes["max_position_embeddings"],
        n_embd=sizes["hidden_size"], n_layer=sizes["num_hidden_layers"],
        n_head=sizes["num_attention_heads"],
        q_lora_rank=sizes["q_lora_rank"], kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        first_k_dense=sizes["first_k_dense_replace"],
        n_routed_total=sizes["published_n_routed_experts"],
        n_routed_experts=sizes["n_routed_experts"],
        first_expert=sizes["first_expert"],
        n_experts_per_tok=sizes["num_experts_per_tok"],
        rope_theta=float(sizes["rope_theta"]))


@pytest.fixture(scope="module")
def whole():
    """Every expert held: ``(sizes, config, params)``."""
    return SIZES, config_of(SIZES), REF.init(SIZES, 2**31 + 7, jnp.float32)


def share_of(sizes, params, first, count):
    """The same weights with ``count`` experts from ``first`` held."""
    cut = dict(sizes, n_routed_experts=count, first_expert=first)
    p = jax.tree.map(lambda x: x, params)
    moe = dict(p["blocks"]["moe"])
    moe["experts"] = jax.tree.map(lambda x: x[:, first:first + count],
                                  moe["experts"])
    p["blocks"] = dict(p["blocks"], moe=moe)
    return cut, config_of(cut), p


def reference_logits(params, sizes, ids):
    return np.asarray(REF.logits(params, sizes, ids, list(range(len(ids)))))


def test_family_is_registered_and_declares_its_cache(whole):
    _, cfg, _ = whole
    assert family_module(cfg) is latent_moe
    assert is_window_independent(cfg)
    assert cache_entry(cfg) == (1, 1, 32 + 8)
    assert cache_entry(llama.CONFIGS["llama-tiny"]) == (2, 2, 8)


@pytest.mark.parametrize("held,rank", [((0, 16), 32), ((4, 4), 32),
                                       ((0, 16), 128)],
                         ids=["every-expert", "a-quarter", "padded-rows"])
def test_prefill_then_decode_through_the_latent_cache_agrees(whole, held,
                                                             rank):
    """Expanded prefill, then absorbed decode steps one token at a time,
    against the reference's one full pass; a continuation chunk (the
    prefix store's form) too. With a quarter of the experts held both
    sides leave the same terms out. With a latent of 128 the 136 values
    a position holds are stored in rows of 256 lanes (as the published
    576 are in 640), zeros beyond."""
    if rank == 32:
        sizes, cfg, params = share_of(whole[0], whole[2], *held)
    else:
        sizes = dict(SIZES, kv_lora_rank=rank)
        cfg, params = config_of(sizes), REF.init(sizes, 5, jnp.float32)
        assert (cfg.cache_width, cfg.cache_lanes) == (136, 256)
        assert (latent_moe.LatentMoEConfig().cache_width,
                latent_moe.LatentMoEConfig().cache_lanes) == (576, 640)
    ids = np.random.RandomState(0).randint(0, 256, (40,))
    ref = reference_logits(params, sizes, ids)
    with jax.default_matmul_precision("highest"):
        cache = latent_moe.make_cache(cfg, 1, 64)
        got, cache = latent_moe.forward_with_cache(
            params, jnp.asarray(ids[None, :24]), cfg, cache,
            flash_prefill=True)
        rows = [np.asarray(got[0])]
        for t in range(24, 40):
            got, cache = latent_moe.forward_with_cache(
                params, jnp.asarray(ids[None, t:t + 1]), cfg, cache)
            rows.append(np.asarray(got[0]))
        assert np.abs(np.concatenate(rows) - ref).max() < TOL
        assert cache.k.shape == (4, 1, 1, 64, cfg.cache_lanes)
        assert cfg.cache_width == rank + 8            # [c_kv | k_pe] only
        assert not np.asarray(cache.k[..., cfg.cache_width:]).any()
        cache = latent_moe.make_cache(cfg, 1, 64)
        a, cache = latent_moe.forward_with_cache(
            params, jnp.asarray(ids[None, :16]), cfg, cache)
        b, cache = latent_moe.forward_with_cache(
            params, jnp.asarray(ids[None, 16:]), cfg, cache)
        assert np.abs(np.concatenate([a[0], b[0]]) - ref).max() < TOL
        full = latent_moe.forward(params, jnp.asarray(ids[None]), cfg)
        assert np.abs(np.asarray(full[0]) - ref).max() < TOL


def test_the_shares_add_up_to_the_uncut_layer(whole):
    """Over every share of the experts (four chips of four), the routed
    parts add up, with the shared expert counted once, to what the uncut
    reference gives for the whole layer."""
    sizes, cfg, params = whole
    s = dict(sizes)
    x = jnp.asarray(np.random.RandomState(1).randn(1, 24, 64), jnp.float32)
    blocks = params["blocks"]
    layer = jax.tree.map(lambda a: a[1], blocks["moe"])
    uncut = np.asarray(REF._experts(layer, x[0], s, None))
    shared = np.asarray(latent_moe.swiglu(layer["shared"], x))[0]
    total = np.zeros_like(uncut)
    with jax.default_matmul_precision("highest"):
        for first in range(0, 16, 4):
            _, c, p = share_of(sizes, params, first, 4)
            moe = dict(p["blocks"]["moe"])
            experts = moe.pop("experts")
            out, counts = latent_moe.expert_layer(
                jax.tree.map(lambda a: a[1], moe), experts, x, c, 1)
            total += np.asarray(out[0]) - shared
            assert int(counts.sum()) > 0
    assert np.abs(total + shared - uncut).max() < TOL


def test_routing_bias_chooses_and_scores_weigh():
    """The bias moves the CHOICE and never the weight; weights are the
    chosen scores normalised and scaled by 2.5."""
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(9, 64), jnp.float32)
    wg = jnp.asarray(rs.randn(64, 16) * 0.125, jnp.float32)
    bias = jnp.zeros((16,)).at[5].set(10.0).at[0].set(-10.0)
    ids, w = expert_ffn.route(x, wg, bias, 4, 2.5)
    scores = np.asarray(jax.nn.sigmoid(x @ wg))
    ids, w = np.asarray(ids), np.asarray(w)
    assert (ids == 5).any(axis=1).all() and not (ids == 0).any()
    assert not np.array_equal(
        np.sort(ids), np.sort(np.argsort(-scores, axis=1)[:, :4]))
    picked = np.take_along_axis(scores, ids, axis=1)
    np.testing.assert_allclose(
        w, picked / picked.sum(1, keepdims=True) * 2.5, rtol=1e-6)
    np.testing.assert_allclose(w.sum(1), 2.5, rtol=1e-6)
    _, raw = expert_ffn.route(x, wg, bias, 4, 1.0, normalise=False)
    np.testing.assert_allclose(np.asarray(raw), picked, rtol=1e-6)


@pytest.mark.parametrize("kernel", [None, "interpret"],
                         ids=["loop", "kernel"])
@pytest.mark.parametrize("b,s", [(4, 40), (8, 1), (16, 1)],
                         ids=["general", "one-tile-an-expert", "widest-step"])
def test_a_tokens_output_is_bit_equal_alone_in_a_batch_and_in_a_chunk(
        whole, b, s, kernel):
    """Window independence: no capacity, no dropped token, tiles of one
    shape summed in expert order. Bit-equal wherever the token sits and
    whatever shares its forward: alone among zeros, among other mates,
    as part of a chunk laid elsewhere. (The forwards compared have one
    shape, so the CPU's matmuls are the same programs; across shapes a
    row's dense matmuls already differ in the last bit on this backend,
    with or without experts, so those are held to 1e-5.) Through the
    general grouped matmul (160 tokens) and through the form for at most
    ``TILE`` pairs that a decode step takes (8 and 16 rows of one
    position), whose routed part is one ``[TILE, d]`` program at every
    width: THAT is bit-equal across widths too. With the tiles as the
    loop and as the one kernel (``ops.expert_ffn.held_expert_tiles``,
    interpreted), whose tile shape, tile order and chunk order follow
    the shapes and the hit list alone."""
    _, cfg, params = whole
    moe = dict(jax.tree.map(lambda a: a, params["blocks"]["moe"]))
    experts = moe.pop("experts")
    layer = jax.tree.map(lambda a: a[2], moe)
    rs = np.random.RandomState(3)
    t = b * s
    assert (t * cfg.n_experts_per_tok <= expert_ffn.TILE) == (s == 1)
    x = rs.randn(t, 64).astype(np.float32)

    def run(tokens, shape=(b, s)):
        out, counts = latent_moe.expert_layer(
            layer, experts, jnp.asarray(tokens.reshape(*shape, 64)), cfg, 2,
            kernel)
        assert int(counts.sum()) == len(tokens) * 4              # none dropped
        return np.asarray(out).reshape(len(tokens), 64)

    batch = run(x)
    alone = np.zeros_like(x)
    alone[t - 3] = x[1]
    assert np.array_equal(run(alone)[t - 3], batch[1])
    n = max(t // 10, 3)                                          # a chunk
    mates = rs.randn(t, 64).astype(np.float32)
    mates[1:1 + n] = x[t - n - 1:t - 1]
    assert np.array_equal(run(mates)[1:1 + n], batch[t - n - 1:t - 1])
    assert np.array_equal(run(x[::-1])[::-1], batch)
    np.testing.assert_allclose(run(x[1:2], (1, 1)), batch[1:2], atol=1e-5)
    np.testing.assert_allclose(run(x[2:2 + n], (1, n)), batch[2:2 + n],
                               atol=1e-5)
    if s == 1:
        ids, w = expert_ffn.route(
            jnp.asarray(x), layer["router"]["kernel"],
            layer["router"]["bias"], 4, cfg.routed_scaling_factor)

        def routed(rows):
            y, _ = expert_ffn.held_experts_ffn(
                jnp.asarray(x[rows]), ids[rows], w[rows],
                experts["gate"]["kernel"], experts["up"]["kernel"],
                experts["down"]["kernel"], 2, 0, kernel)
            return np.asarray(y)

        every = routed(slice(0, t))
        for rows in (slice(1, 2), slice(2, 6), slice(0, t // 2)):
            assert np.array_equal(routed(rows), every[rows])


@pytest.mark.parametrize("rows", [1, 16])
def test_a_decode_steps_routing_is_the_general_forms(whole, monkeypatch,
                                                     rows):
    """The forms for at most ``TILE`` pairs (top k by rank, one tile an
    expert over all the rows) against the general ones (``lax.top_k``,
    pairs sorted into tiles) on the same rows: ids, weights, the counts
    and the five counters byte-equal; the routed sum within float32
    noise (the general form's tiles hold other rows)."""
    _, cfg, params = whole
    moe = dict(jax.tree.map(lambda a: a, params["blocks"]["moe"]))
    experts = moe.pop("experts")
    layer = jax.tree.map(lambda a: a[1], moe)
    x = jnp.asarray(np.random.RandomState(31).randn(rows, 1, 64), jnp.float32)
    # ties, which both must give to the lower id
    tied = jnp.asarray(np.round(np.random.RandomState(32).randn(rows, 16), 1),
                       jnp.float32)

    def both():
        ids, w = expert_ffn.route(
            x[:, 0], layer["router"]["kernel"], layer["router"]["bias"], 4,
            cfg.routed_scaling_factor)
        out, counts = latent_moe.expert_layer(layer, experts, x, cfg, 1)
        counters = latent_moe._count(jnp.zeros((5,), jnp.int32),
                                     counts[None], rows * 4)
        _, cache = latent_moe.forward_with_cache(
            params, jnp.arange(rows)[:, None], cfg,
            latent_moe.make_cache(cfg, rows, 8))
        pick = expert_ffn.route(tied, jnp.eye(16), jnp.zeros((16,)), 4, 1.0)
        return [np.asarray(a) for a in
                (ids, w, counts, counters, cache.v, *pick, out)]

    with jax.default_matmul_precision("highest"):
        few = both()
        # no call has few enough pairs for the forms a decode step takes
        monkeypatch.setattr(expert_ffn, "TILE", 1)
        general = both()
    for a, g in zip(few[:-1], general[:-1]):
        assert np.array_equal(a, g)
    assert few[2].sum() == rows * 4 and few[4][4] == 3
    assert np.abs(few[-1] - general[-1]).max() < TOL


@pytest.mark.parametrize("kernel", [None, "interpret"],
                         ids=["xla", "interpret"])
def test_one_position_alone_and_as_the_last_of_two(whole, kernel):
    """The single-position form of the attention front (the folds into
    and out of the latent as one matmul each against the leaves as they
    lie) against the einsum form: position 20 run alone and as the last
    token of a two-token call writes the same cache row and gives the
    same logits within float32 noise."""
    _, cfg, params = whole
    ids = jnp.asarray(np.random.RandomState(33).randint(0, 256, (1, 21)))
    with jax.default_matmul_precision("highest"):
        def prefilled(n):
            _, cache = latent_moe.forward_with_cache(
                params, ids[:, :n], cfg, latent_moe.make_cache(cfg, 1, 256),
                flash_prefill=True)
            return cache
        one, c1 = latent_moe.forward_with_cache(
            params, ids[:, 20:], cfg, prefilled(20), decode_kernel=kernel)
        two, c2 = latent_moe.forward_with_cache(
            params, ids[:, 19:], cfg, prefilled(19))
    assert np.abs(np.asarray(one[0, -1]) - np.asarray(two[0, -1])).max() < TOL
    assert np.abs(np.asarray(c1.k) - np.asarray(c2.k)).max() < TOL
    assert int(c1.length) == int(c2.length) == 21


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_three_writers_one_cache_agree_with_the_whole_forward(whole, kernel):
    """One convention for the rotary lanes across every writer and
    reader of the cache: a fresh prefill (expanded form) writes 24
    positions, the prefix store's ``_extend`` (einsum absorbed form) 16
    more, then eight decode steps (single-position form) one each; every
    logit agrees with ``forward`` over the whole sequence."""
    _, cfg, params = whole
    ids = jnp.asarray(np.random.RandomState(34).randint(0, 256, (1, 48)))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(latent_moe.forward(params, ids, cfg))[0]
        eng = DecodeEngine(params, cfg, max_seq=256, decode_kernel=kernel)
        store = PrefixCachingEngine(eng, capacity=2, chunk=8)
        last, cache = eng._prefill_impl(eng.params, ids[:, :24], None)
        got = [np.asarray(last)]
        chunk, cache = store._extend(eng.params, cache, ids[:, 24:40])
        got.append(np.asarray(chunk[0]))
        for t in range(40, 48):
            step, cache = eng._forward_cached(eng.params, ids[:, t:t + 1],
                                              cache, None)
            got.append(np.asarray(step[0]))
    assert np.abs(np.concatenate(got) - ref[23:]).max() < TOL
    assert not np.asarray(cache.k[..., cfg.cache_width:]).any()


def test_pool_blocks_hold_the_familys_entry_and_llama_is_unchanged(whole):
    _, cfg, params = whole
    eng = DecodeEngine(params, cfg, max_seq=64)
    pool = KVBlockPool.for_engine(eng, 12, block_size=16)
    assert pool.data.shape == (4, 13, 1, 1, 16, 40)
    st = pool.stats()
    assert st["entry_width"] == 40
    assert st["bytes_per_block"] == 4 * 16 * 40 * 4 == kv_pool.bytes_per_block(
        4, 1, 16, 40, jnp.float32, planes=1)
    with pytest.raises(NotImplementedError):
        KVBlockPool.for_engine(eng, 12, block_size=16, block_dtype="int8")
    lcfg = llama.CONFIGS["llama-tiny"]
    leng = DecodeEngine(llama.init_params(lcfg, jax.random.PRNGKey(0)), lcfg,
                        max_seq=64)
    lpool = KVBlockPool.for_engine(leng, 12, block_size=16)
    assert lpool.data.shape == (2, 13, 2, 2, 16, 8)      # as before this family
    assert lpool.stats()["bytes_per_block"] == 2 * 2 * 2 * 16 * 8 * 4 \
        == kv_pool.bytes_per_block(2, 2, 16, 8, jnp.float32)
    assert lpool.stats()["entry_width"] == 2 * 2 * 8
    k = jnp.asarray(np.random.RandomState(4).randn(2, 1, 2, 64, 8), jnp.float32)
    table = np.asarray([[0, 1, 2, 3]], np.int32)
    lpool.scatter(kv_pool.KVCache(k, -k, jnp.asarray(64)), table)
    back = lpool.gather(table, 64)
    assert np.array_equal(back.k, k) and np.array_equal(back.v, -k)


def test_the_kernel_agrees_with_the_einsum_form(whole):
    """``decode_kernel="interpret"``: the Pallas kernel, interpreted,
    serves the same greedy stream and keeps the family's cache."""
    _, cfg, params = whole
    prompt = np.random.RandomState(5).randint(0, 256, (21,))
    plain = DecodeEngine(params, cfg, max_seq=256)
    kern = DecodeEngine(params, cfg, max_seq=256, decode_kernel="interpret")
    assert plain._decode_kernel is None and kern._decode_kernel == "interpret"
    assert kern._fresh_cache(2).k.shape == (4, 2, 1, 256, 40)
    assert np.array_equal(plain.generate(prompt, 12).tokens,
                          kern.generate(prompt, 12).tokens)
    with pytest.raises(ValueError):
        DecodeEngine(params, cfg, max_seq=256, decode_kernel="mega-interpret")


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_iter_batcher_pool_and_prefix_store_serve_the_solo_streams(
        whole, kernel):
    """Rows joining and retiring and a shared-prefix hit, through
    ``IterBatchingEngine`` + pool + prefix store: every stream equals its
    solo run, whose logits the reference confirms; decode spans carry the
    segment's routing counters, prefill spans the expert load, and
    ``stats()`` the same sums."""
    sizes, cfg, params = whole
    eng = DecodeEngine(params, cfg, max_seq=256, decode_kernel=kernel)
    pool = KVBlockPool.for_engine(eng, 96, block_size=16)
    prefix = PrefixCachingEngine(eng, capacity=4, chunk=16, pool=pool)
    it = IterBatchingEngine(eng, max_batch=4, seg_steps=8, prefix=prefix,
                            pool=pool)
    rs = np.random.RandomState(6)
    shared = rs.randint(0, 256, (32,))
    prompts = [rs.randint(0, 256, (48,)),   # the deepest first: the rest join
               np.concatenate([shared, rs.randint(0, 256, (7,))]),
               np.concatenate([shared, rs.randint(0, 256, (3,))]),
               rs.randint(0, 256, (11,))]
    news = [40, 12, 9, 14]
    got = {}

    def go(i):
        tr = tracing.RequestTrace(f"r{i}")
        with tracing.use_trace(tr):
            got[i] = (it.generate(prompts[i], news[i]), tr)

    threads = []
    for i in range(4):
        t = threading.Thread(target=go, args=(i,))
        t.start()
        threads.append(t)
        time.sleep(0.6 if i == 0 else 0.05)
    for t in threads:
        t.join()
    st = it.stats()
    assert st["joins"] >= 1 and prefix.stats()["hits"] >= 1
    solo = DecodeEngine(params, cfg, max_seq=256)
    for i in range(4):
        want = solo.generate(prompts[i], news[i]).tokens
        res, tr = got[i]
        assert np.array_equal(res.tokens, want), i
        tr.settle()
        dec = [s for s in tr.spans if s.name == "decode"]
        pre = [s for s in tr.spans if s.name == "prefill"]
        assert dec and all(
            {"seg", "experts_hit", "pairs_here", "pairs_routed"}
            <= set(s.labels) for s in dec)
        assert all(s.labels["pairs_here"] == s.labels["pairs_routed"] ==
                   s.labels["steps"] * s.labels["width"] * 4 * 3 for s in dec)
        assert pre and all(s.labels["expert_load_max"]
                           >= s.labels["expert_load_mean"] > 0 for s in pre)
    assert st["moe.pairs_routed"] == st["moe.pairs_here"] > 0
    # a call runs to the first live row's budget, eight steps at most:
    # its sums are those of the steps it ran
    steps = {s.labels["seg"]: s.labels["steps"] for _, tr in got.values()
             for s in tr.spans if s.name == "decode"}
    assert len(steps) == st["segments"] and max(steps.values()) == 8
    assert st["moe.layer_forwards"] == 3 * sum(steps.values())
    assert 0 < st["moe.experts_hit"] <= 16 * st["moe.layer_forwards"]
    assert st["moe.prefill_layer_forwards"] > 0
    # the longest stream, teacher-forced through the reference: each
    # served token is the reference's own choice or within noise of it
    seq = got[0][0].tokens[0]
    ref = reference_logits(params, sizes, seq[:-1])[len(prompts[0]) - 1:]
    served = seq[len(prompts[0]):]
    assert np.all(ref.max(-1) - ref[np.arange(len(served)), served] < TOL)


def test_a_joiner_on_all_three_strides_serves_the_solo_stream(whole):
    """A joiner seven whole chunks long walks the pool-backed store in
    strides of 4, 2 and 1 chunks (64, 32 and 16 tokens through the
    router and the grouped matmul at once) beside a live row: its stream
    is the solo engine's, and each served token is the float32
    reference's own choice or within noise of it."""
    sizes, cfg, params = whole
    eng = DecodeEngine(params, cfg, max_seq=256)
    pool = KVBlockPool.for_engine(eng, 96, block_size=16)
    prefix = PrefixCachingEngine(eng, capacity=4, chunk=16, pool=pool)
    it = IterBatchingEngine(eng, max_batch=4, seg_steps=8, prefix=prefix,
                            pool=pool)
    rs = np.random.RandomState(28)
    prompts = [rs.randint(0, 256, (124,)),  # seeds: deep enough to admit
               rs.randint(0, 256, (119,))]  # 7 chunks of 16 + a tail of 7
    news = [48, 12]
    got = {}

    def go(i):
        got[i] = it.generate(prompts[i], news[i])

    threads = [threading.Thread(target=go, args=(i,)) for i in range(2)]
    # Row 0's first decode call does not return to the scheduler before
    # the joiner is in its queue, so the boundary after it admits the
    # joiner whatever the machine's load: row 0's remaining segments are
    # compiled and quick, and a thread started beside them can lose.
    seg, h0 = eng._decode_seg, {}

    def first_segment_waits_for_the_joiner(*a, **kw):
        out = seg(*a, **kw)
        if not h0:
            h0.update(prefix.stats())
            threads[1].start()
            deadline = time.monotonic() + 120
            while it._queue.qsize() < 1 and time.monotonic() < deadline:
                time.sleep(0.001)
        return out

    eng._decode_seg = first_segment_waits_for_the_joiner
    threads[0].start()
    for t in threads:
        t.join(timeout=300)
    assert it.stats()["joins"] >= 1
    h1 = prefix.stats()
    assert h1["extend_calls"] - h0["extend_calls"] == 4
    assert h1["extend_tokens"] - h0["extend_tokens"] == 119
    solo = DecodeEngine(params, cfg, max_seq=256)
    for i in range(2):
        want = solo.generate(prompts[i], news[i]).tokens
        assert np.array_equal(got[i].tokens, want), i
    seq = got[1].tokens[0]
    ref = reference_logits(params, sizes, seq[:-1])[len(prompts[1]) - 1:]
    served = seq[len(prompts[1]):]
    assert np.all(ref.max(-1) - ref[np.arange(len(served)), served] < TOL)


def test_what_the_engine_refuses():
    # (what the SERVER refuses for every family: tests/test_family.py)
    cfg = latent_moe.CONFIGS["latent-moe-tiny"]
    params = latent_moe.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="int8"):
        DecodeEngine(params, cfg, max_seq=64, dtype="int8")
