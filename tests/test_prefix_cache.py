"""Prefix-cache tests: byte-exact equivalence with the plain engine
across cold/hit/partial-hit/extension patterns, LRU eviction, stored-
entry immutability under donation, and the serving knob.
"""

import jax
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine, SamplingConfig
from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine

CFG = gpt2.GPT2Config(vocab_size=127, n_positions=256, n_embd=32,
                      n_layer=2, n_head=4)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def plain(params):
    return DecodeEngine(params, CFG, max_seq=192)


def make_prompt(rng, system, n_user):
    return np.concatenate([system, rng.integers(0, CFG.vocab_size,
                                                size=(n_user,))]).astype(np.int32)


def test_hit_paths_token_exact(params, plain):
    """Cold miss, exact re-use, and deeper extension all match the plain
    engine byte-for-byte, while the cache actually hits."""
    pce = PrefixCachingEngine(DecodeEngine(params, CFG, max_seq=192),
                              capacity=4, chunk=16)
    rng = np.random.default_rng(0)
    system = (np.arange(40, dtype=np.int32) * 11) % CFG.vocab_size

    for i, n_user in enumerate((7, 12, 30, 3)):
        prompt = make_prompt(rng, system, n_user)
        want = plain.generate(prompt, max_new_tokens=10)
        got = pce.generate(prompt, max_new_tokens=10)
        np.testing.assert_array_equal(got.tokens, want.tokens)
    s = pce.stats()
    assert s["misses"] >= 1 and s["hits"] >= 2, s
    # the 40-token shared system prefix = 2 full 16-chunks cached
    assert s["entries"] >= 1


def test_stored_entries_survive_donation(params, plain):
    """The decode scan donates its cache; a second identical request must
    still hit and still be correct (stored buffers were copied, not
    consumed)."""
    pce = PrefixCachingEngine(DecodeEngine(params, CFG, max_seq=192),
                              capacity=2, chunk=8)
    prompt = (np.arange(30, dtype=np.int32) * 7) % CFG.vocab_size
    want = plain.generate(prompt, max_new_tokens=8)
    a = pce.generate(prompt, max_new_tokens=8)
    b = pce.generate(prompt, max_new_tokens=8)  # full-depth hit
    c = pce.generate(prompt, max_new_tokens=8)
    np.testing.assert_array_equal(a.tokens, want.tokens)
    np.testing.assert_array_equal(b.tokens, want.tokens)
    np.testing.assert_array_equal(c.tokens, want.tokens)
    assert pce.stats()["hits"] >= 2


def test_lru_eviction(params):
    pce = PrefixCachingEngine(DecodeEngine(params, CFG, max_seq=192),
                              capacity=2, chunk=8)
    rng = np.random.default_rng(1)
    for seed in range(4):  # 4 distinct prefixes, capacity 2
        prompt = rng.integers(0, CFG.vocab_size, size=(20,)).astype(np.int32)
        pce.generate(prompt, max_new_tokens=3)
    assert pce.stats()["entries"] == 2


def test_sampled_and_staged(params, plain):
    """Seeded sampling through the prefix path matches the plain engine
    (same key consumption); staged engines work too."""
    pce = PrefixCachingEngine(
        DecodeEngine(params, CFG, max_seq=192, boundaries=[1]),
        capacity=2, chunk=8)
    prompt = (np.arange(21, dtype=np.int32) * 5) % CFG.vocab_size
    s = SamplingConfig(mode="sample", temperature=0.7, top_k=10)
    want = plain.generate(prompt, 8, sampling=s, key=jax.random.PRNGKey(5))
    cold = pce.generate(prompt, 8, sampling=s, key=jax.random.PRNGKey(5))
    warm = pce.generate(prompt, 8, sampling=s, key=jax.random.PRNGKey(5))
    np.testing.assert_array_equal(cold.tokens, want.tokens)
    np.testing.assert_array_equal(warm.tokens, want.tokens)


def test_guards(params):
    eng = DecodeEngine(params, CFG, max_seq=64)
    with pytest.raises(ValueError, match="capacity"):
        PrefixCachingEngine(eng, capacity=0)
    pce = PrefixCachingEngine(eng, capacity=1, chunk=8)
    two = np.stack([np.arange(9, dtype=np.int32)] * 2)
    with pytest.raises(ValueError, match="single-stream"):
        pce.generate(two, 4)


def test_serving_prefix_cache_knob(params):
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.http import TestClient
    from llm_sharding_demo_tpu.serving.tokenizer import ByteTokenizer
    from llm_sharding_demo_tpu.utils.config import ServingConfig

    cfg = ServingConfig(model_id="t", max_seq=64, prefix_cache=2)
    client = TestClient(create_app(cfg, model=(CFG, params),
                                   tokenizer=ByteTokenizer()))
    assert client.get("/healthz").json()["prefix_cache"] == 2
    body = {"prompt": "The same system preamble here. Q1", "max_new_tokens": 5,
            "mode": "greedy"}
    r1 = client.post("/generate", json=body)
    r2 = client.post("/generate", json=body)
    assert r1.status_code == 200 and r1.json() == r2.json()
    # round 3: PREFIX_CACHE + MAX_BATCH composes (batcher-level per-row
    # store prefills); the healthz stats surface through the batcher
    combo = TestClient(create_app(
        ServingConfig(model_id="t", max_seq=64, prefix_cache=2, max_batch=4),
        model=(CFG, params), tokenizer=ByteTokenizer()))
    c1 = combo.post("/generate", json=body)
    assert c1.status_code == 200 and c1.json() == r1.json()
    assert "prefix_cache_stats" in combo.get("/healthz").json()
    # the triple composes now (ISSUE 1): spec rounds bypass the store
    # (batched verify loop), plain solo rounds keep the prefix path —
    # output identical either way
    triple = TestClient(create_app(
        ServingConfig(model_id="t", max_seq=64, prefix_cache=2,
                      max_batch=4, spec_decode=4),
        model=(CFG, params), tokenizer=ByteTokenizer()))
    t1 = triple.post("/generate", json=body)
    assert t1.status_code == 200 and t1.json() == r1.json()
    with pytest.raises(ValueError, match="local decode path"):
        create_app(ServingConfig(model_id="t", prefix_cache=2,
                                 shard_role="a"),
                   model=(CFG, params), tokenizer=ByteTokenizer())


def test_prefix_cache_composes_with_speculation(params, plain):
    """Spec verify loop decoding off the prefix-built cache: greedy
    streams byte-equal to the plain engine across cold/hit requests, and
    BOTH subsystems actually engage (cache hits AND verify acceptance)."""
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine

    spec = SpecDecodeEngine(params, CFG, max_seq=192, draft_len=5)
    pce = PrefixCachingEngine(spec.plain, capacity=2, chunk=16, spec=spec)

    system = np.asarray([4, 9] * 20, dtype=np.int32)  # repetitive: spec food
    for n_user in (6, 11, 3):
        prompt = np.concatenate(
            [system, np.asarray([4, 9] * n_user, dtype=np.int32)])
        want = plain.generate(prompt, max_new_tokens=15)
        got = pce.generate(prompt, max_new_tokens=15)
        np.testing.assert_array_equal(got.tokens, want.tokens)
        assert got.verify_steps is not None and got.verify_steps < 14
    assert pce.stats()["hits"] >= 1
    assert spec.stats()["requests"] == 3


def test_prefix_cache_spec_mismatched_engine_rejected(params):
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine

    other = DecodeEngine(params, CFG, max_seq=192)
    spec = SpecDecodeEngine(params, CFG, max_seq=192)
    with pytest.raises(ValueError, match="same DecodeEngine"):
        PrefixCachingEngine(other, capacity=2, spec=spec)


def test_serving_prefix_plus_spec(params):
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.http import TestClient
    from llm_sharding_demo_tpu.serving.tokenizer import ByteTokenizer
    from llm_sharding_demo_tpu.utils.config import ServingConfig

    # prefill_chunk=8 doubles as the prefix-cache chunk width; the
    # default 64 would leave this short prompt with no full chunk to
    # cache (a documented no-op, visible via the stats asserted below)
    both = TestClient(create_app(
        ServingConfig(model_id="t", max_seq=96, prefix_cache=2,
                      spec_decode=4, prefill_chunk=8),
        model=(CFG, params), tokenizer=ByteTokenizer()))
    plain = TestClient(create_app(
        ServingConfig(model_id="t", max_seq=96),
        model=(CFG, params), tokenizer=ByteTokenizer()))
    body = {"prompt": "Hi, Hi, Hi, Hi, Hi, ", "max_new_tokens": 10,
            "mode": "greedy"}
    assert both.post("/generate", json=body).json() == \
        plain.post("/generate", json=body).json()
    both.post("/generate", json=body)  # second: prefix hit + spec
    h = both.get("/healthz").json()
    assert h["prefix_cache_stats"]["hits"] >= 1
    assert h["spec_decode_stats"]["requests"] >= 1


def test_prefix_composes_with_batching_mixed_hit_miss():
    """PREFIX_CACHE x MAX_BATCH (VERDICT r2 next #8): per-row store
    prefills (each row hitting at its own depth, or missing) merge into
    one batched decode. Every row must equal its solo-engine stream
    token-for-token — hit rows, miss rows, and dummy padding rows."""
    import jax
    import numpy as np
    from llm_sharding_demo_tpu.models import gpt2
    from llm_sharding_demo_tpu.runtime.batcher import BatchingEngine
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine

    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=256, n_embd=32,
                          n_layer=2, n_head=2)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    engine = DecodeEngine(params, cfg, max_seq=200)
    prefix = PrefixCachingEngine(engine, capacity=4, chunk=8)
    batcher = BatchingEngine(engine, max_batch=4, max_wait_ms=40.0,
                             prefix=prefix)

    rng = np.random.default_rng(0)
    shared = list(rng.integers(0, cfg.vocab_size, size=24))   # 3 chunks
    p_hit1 = shared + [5, 6]
    p_hit2 = shared + [9]
    p_miss = list(rng.integers(0, cfg.vocab_size, size=11))

    solo = DecodeEngine(params, cfg, max_seq=200)
    want = {tuple(p): list(solo.generate(np.asarray([p]), 10).tokens[0])
            for p in (p_hit1, p_hit2, p_miss)}

    # seed the store with the shared prefix
    prefix.generate(np.asarray(shared + [1]), 2)
    assert prefix.stats()["entries"] >= 1

    import threading
    results = {}

    def worker(p):
        results[tuple(p)] = list(
            batcher.generate(np.asarray(p), 10).tokens[0])

    threads = [threading.Thread(target=worker, args=(p,))
               for p in (p_hit1, p_hit2, p_miss)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for p, got in results.items():
        assert got == want[p], (list(p)[:4], got[-5:], want[p][-5:])
    st = prefix.stats()
    assert st["hits"] >= 2          # the two shared-prefix rows hit
    assert batcher.rows_served == 3


# -- the walk's stride ladder -------------------------------------------------

STRIDE_CHUNK = 8


def _ladder_widths(chunks_left):
    """The reference decomposition: 4, 2, 1 whole chunks, largest first."""
    out = []
    for s in (4, 2, 1):
        while chunks_left >= s:
            out.append(s * STRIDE_CHUNK)
            chunks_left -= s
    return out


def _recording(pce):
    """Record the ids width of every continuation-program call."""
    widths = []

    def wrap(fn):
        def call(params, cache, ids):
            widths.append(int(ids.shape[1]))
            return fn(params, cache, ids)
        return call

    pce._extend = wrap(pce._extend)
    pce._extend_keep = wrap(pce._extend_keep)
    return widths


def _prefill_labels(pce, prompt):
    from llm_sharding_demo_tpu.utils import tracing
    tr = tracing.RequestTrace("walk")
    with tracing.use_trace(tr):
        pce.prefill_state(prompt)
    return tr.find("prefill").labels


@pytest.mark.parametrize("pooled", [False, True], ids=["store", "pool"])
@pytest.mark.parametrize("hit_chunks", [0, 2], ids=["miss", "hit"])
@pytest.mark.parametrize("chunks_left", range(1, 10))
def test_strided_walk_exact_and_on_the_ladder(params, plain, chunks_left,
                                              hit_chunks, pooled):
    """A walk of ``chunks_left`` whole chunks past the hit depth runs on
    the ladder's widths alone, gives the plain engine's greedy stream,
    leaves the store with the chunk-by-chunk walk's one key (the deepest
    whole chunk), and counts its calls and tokens."""
    from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
    eng = DecodeEngine(params, CFG, max_seq=192)
    pool = (KVBlockPool.for_engine(eng, num_blocks=64, block_size=8)
            if pooled else None)
    pce = PrefixCachingEngine(eng, capacity=4, chunk=STRIDE_CHUNK, pool=pool)
    rng = np.random.default_rng(100 * chunks_left + 10 * hit_chunks + pooled)
    m_total = hit_chunks + chunks_left
    tail = 1 + chunks_left % 5
    prompt = rng.integers(
        0, CFG.vocab_size,
        size=(m_total * STRIDE_CHUNK + tail,)).astype(np.int32)
    if hit_chunks:
        seed = np.concatenate([prompt[:hit_chunks * STRIDE_CHUNK],
                               [3]]).astype(np.int32)
        pce.generate(seed, 2)
    before = pce.stats()
    widths = _recording(pce)

    got = pce.generate(prompt, max_new_tokens=8)
    np.testing.assert_array_equal(
        got.tokens, plain.generate(prompt, max_new_tokens=8).tokens)
    assert widths == _ladder_widths(chunks_left) + [tail]

    after = pce.stats()
    assert after["extend_calls"] - before["extend_calls"] == len(widths)
    assert (after["extend_tokens"] - before["extend_tokens"]
            == chunks_left * STRIDE_CHUNK + tail == sum(widths))
    assert after["hits"] - before["hits"] == (1 if hit_chunks else 0)
    assert after["entries"] == (2 if hit_chunks else 1)
    key = pce._key(prompt, m_total, STRIDE_CHUNK)
    assert (pool.allocator.has_prefix(key) if pooled
            else key in pce._store)

    # the next request behind the same whole chunks reuses all of them
    # in one tail call, as it did behind the chunk-by-chunk walk
    del widths[:]
    follow = np.concatenate([prompt[:m_total * STRIDE_CHUNK],
                             [5, 6, 7]]).astype(np.int32)
    labels = _prefill_labels(pce, follow)
    assert labels["reused_tokens"] == m_total * STRIDE_CHUNK
    assert labels["extend_calls"] == 1 and widths == [3]
