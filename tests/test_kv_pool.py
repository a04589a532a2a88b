"""Paged KV-cache memory subsystem (runtime.kv_pool).

Three layers of claims, each pinned:

- **BlockAllocator** (host-only): ref counts, all-or-nothing
  allocation, prefix-registry structural sharing, LRU eviction of
  zero-ref prefix blocks, watermark admission.
- **PagedKVRunner**: paged decode is BYTE-EQUAL to the contiguous
  engine (greedy and seeded sample, solo and ragged batch, EOS-armed)
  because it runs the engine's OWN compiled programs on gathered
  views; with the pool-backed prefix store, a hit REFERENCES store
  blocks (copy-on-write at the frontier) instead of copying the
  prefill state.
- **Recompute-on-resume** (the iterbatch preemption mechanism, pinned
  here at engine level where the environment's batched-sampled
  limitations don't apply — see tests/test_iterbatch.py for the
  scheduler-level scenarios): re-prefilling prompt + already-emitted
  tokens and continuing the row's own step-key chain reproduces the
  un-preempted stream byte-identically, greedy AND seeded sample.

Plus the serving admission surface (429 + Retry-After, /healthz pool
stats), the pool-derived block gauges, the retired-metric lint, and
the recompile-budget certification of the paged entry points.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.runtime.engine import (DecodeEngine,
                                                  SamplingConfig,
                                                  _split_keys, _step_keys)
from llm_sharding_demo_tpu.runtime.kv_pool import (BlockAllocator,
                                                   KVBlockPool,
                                                   PagedKVRunner,
                                                   PoolExhausted)
from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine

BS = 8


@pytest.fixture(scope="module")
def setup():
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=256, n_embd=32,
                          n_layer=2, n_head=4)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params, DecodeEngine(params, cfg, max_seq=64)


# -- BlockAllocator ----------------------------------------------------------


def test_allocator_alloc_free_refcount():
    a = BlockAllocator(num_blocks=8, block_size=BS)
    ids = a.alloc(3)
    assert len(set(ids)) == 3
    st = a.stats()
    assert (st.blocks_in_use, st.blocks_free) == (3, 5)
    a.ref(ids[:1])
    a.free(ids)                       # ids[0] survives at ref 1
    assert a.stats().blocks_in_use == 1
    a.free(ids[:1])
    assert a.stats().blocks_in_use == 0
    with pytest.raises(ValueError):
        a.free(ids[:1])               # double free
    with pytest.raises(ValueError):
        a.ref([ids[0]])               # ref of unallocated


def test_allocator_all_or_nothing_and_exhaustion():
    a = BlockAllocator(num_blocks=4, block_size=BS)
    first = a.alloc(3)
    with pytest.raises(PoolExhausted):
        a.alloc(2)                    # nothing taken on failure
    assert a.stats().blocks_free == 1
    last = a.alloc(1)
    assert last
    # release everything: under GRAFTSAN=1 the suite's teardown sweep
    # reports still-held caller refs as leaks (with provenance)
    a.free(first)
    a.free(last)


def test_allocator_prefix_sharing_and_lru_eviction():
    a = BlockAllocator(num_blocks=8, block_size=BS)
    ids1 = a.alloc(2)
    a.register_prefix(b"p1", ids1)
    a.free(ids1)                      # only the entry's refs remain
    st = a.stats()
    assert st.blocks_evictable == 2 and st.prefix_entries == 1
    # a deeper entry shares p1's blocks structurally
    ids2 = a.alloc(2)
    a.register_prefix(b"p2", list(ids1) + ids2)
    a.free(ids2)
    assert a.stats().blocks_in_use == 4     # 2 shared + 2 new, no copies
    # lookup refs for the caller and refreshes recency
    got = a.lookup_prefix(b"p1")
    assert got == tuple(ids1)
    assert a.refcount(ids1[0]) == 3   # p1 + p2 + caller
    a.free(got)
    # exhaustion evicts LRU-first (p2: registered later but p1 was
    # looked up last). Evicting p2 frees only ids2 — ids1 stays alive
    # through p1's refs (shared blocks survive their entry's eviction).
    ids6 = a.alloc(6)
    st = a.stats()
    assert st.prefix_entries == 1 and st.evictions == 1
    assert st.blocks_in_use == 8 and st.blocks_free == 0
    assert a.refcount(ids1[0]) == 1   # p1 only
    # deeper pressure evicts p1 too
    with pytest.raises(PoolExhausted):
        a.alloc(3)                    # even evicting p1 yields only 2
    assert a.stats().evictions == 2 and a.stats().prefix_entries == 0
    a.free(ids6)                      # GRAFTSAN teardown-sweep hygiene


def test_allocator_watermark_admission():
    a = BlockAllocator(num_blocks=10, block_size=BS, watermark=0.8)
    assert a.can_admit(8)
    assert not a.can_admit(9)         # past the watermark reserve
    ids = a.alloc(9)                  # alloc itself MAY use the reserve
    assert not a.can_admit(1)
    a.free(ids)
    assert a.can_admit(8)
    assert a.blocks_for(17) == 3


# -- PagedKVRunner: paged == contiguous --------------------------------------


def test_paged_runner_byte_equal_greedy_and_eos(setup):
    cfg, params, eng = setup
    pool = KVBlockPool.for_engine(eng, num_blocks=24, block_size=BS)
    runner = PagedKVRunner(eng, pool)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 211, size=(7,)).astype(np.int32)
    want = eng.generate(prompt[None, :], 20)
    got = runner.generate(prompt[None, :], 20)
    np.testing.assert_array_equal(got.tokens, want.tokens)
    assert pool.allocator.stats().blocks_in_use == 0   # all freed
    # EOS-armed: same truncated prefix
    eos = int(want.tokens[0, -1])
    want_e = eng.generate(prompt[None, :], 40, eos_id=eos)
    got_e = runner.generate(prompt[None, :], 40, eos_id=eos)
    np.testing.assert_array_equal(got_e.tokens, want_e.tokens)
    assert got_e.new_tokens == want_e.new_tokens


def test_paged_runner_byte_equal_sampled_ragged_batch(setup):
    cfg, params, eng = setup
    pool = KVBlockPool.for_engine(eng, num_blocks=24, block_size=BS)
    runner = PagedKVRunner(eng, pool)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 211, size=(5,)),
               rng.integers(0, 211, size=(9,))]
    keys = jnp.stack([jax.random.PRNGKey(1), jax.random.PRNGKey(2)])
    s = SamplingConfig(mode="sample", temperature=0.7, top_k=17)
    want = eng.generate(prompts, 16, sampling=s, key=keys)
    got = runner.generate(prompts, 16, sampling=s, key=keys)
    np.testing.assert_array_equal(got.tokens, want.tokens)
    np.testing.assert_array_equal(got.pad, want.pad)


def test_paged_runner_emits_pool_gauges(setup):
    cfg, params, eng = setup
    from llm_sharding_demo_tpu.utils.metrics import REGISTRY
    pool = KVBlockPool.for_engine(eng, num_blocks=24, block_size=BS)
    runner = PagedKVRunner(eng, pool)
    rng = np.random.default_rng(5)
    runner.generate(rng.integers(0, 211, size=(6,))[None, :], 8)
    snap = REGISTRY.snapshot()
    # pool-backed gauges carry the storage regime label (f32 here: the
    # full-precision pool inherits the engine dtype) plus the per-block
    # HBM cost — see tests/test_kv_quant.py for the quantized labels
    key = "{block_dtype=f32,component=paged}"
    assert snap["kv_cache_blocks_total" + key] == 24
    assert ("kv_cache_blocks_in_use" + key) in snap
    assert snap["kv_pool_bytes_per_block" + key] == pool._bytes_per_block


# -- prefix store on the pool ------------------------------------------------


def test_pool_backed_prefix_store_byte_equal_and_shares_blocks(setup):
    cfg, params, eng = setup
    pool = KVBlockPool.for_engine(eng, num_blocks=40, block_size=BS)
    # chunk NOT a block multiple: the shared frontier block must CoW
    pref = PrefixCachingEngine(eng, capacity=4, chunk=20, pool=pool)
    runner = PagedKVRunner(eng, pool, prefix=pref)
    rng = np.random.default_rng(6)
    long = rng.integers(0, 211, size=(30,)).astype(np.int32)
    want = eng.generate(long[None, :], 12).tokens
    got1 = runner.generate(long[None, :], 12).tokens     # miss + insert
    got2 = runner.generate(long[None, :], 12).tokens     # hit, shares
    np.testing.assert_array_equal(got1, want)
    np.testing.assert_array_equal(got2, want)
    st = pool.allocator.stats()
    # the store's entry is the only resident state, and the hit run
    # exercised copy-on-write on the unaligned frontier block
    assert st.prefix_entries == 1
    assert st.cow_copies >= 1
    assert st.blocks_in_use == st.blocks_evictable == 3  # ceil(20/8)
    # the plain pool-backed prefix engine is byte-equal too
    np.testing.assert_array_equal(pref.generate(long[None, :], 12).tokens,
                                  want)
    assert pref.stats()["hits"] >= 2 and pref.stats()["pooled"]


def test_pool_prefix_entries_share_structurally_and_evict_lru(setup):
    cfg, params, eng = setup
    pool = KVBlockPool.for_engine(eng, num_blocks=8, block_size=BS)
    pref = PrefixCachingEngine(eng, capacity=8, chunk=16, pool=pool)
    rng = np.random.default_rng(7)
    base = rng.integers(0, 211, size=(17,)).astype(np.int32)
    pref.generate(base[None, :], 4)              # entry at depth 16
    deep = np.concatenate([base[:16],
                           rng.integers(0, 211, size=(18,))]).astype(
                               np.int32)
    pref.generate(deep[None, :], 4)              # entry at depth 32
    st = pool.allocator.stats()
    assert st.prefix_entries == 2
    # depth-16 entry: 2 blocks; depth-32 entry SHARES them + 2 new —
    # the old store would have held two full max_seq cache copies
    assert st.blocks_in_use == 4
    # pool pressure LRU-evicts entries instead of failing the request
    big = rng.integers(0, 211, size=(60,)).astype(np.int32)
    got = pref.generate(big[None, :], 4).tokens
    np.testing.assert_array_equal(got, eng.generate(big[None, :], 4).tokens)
    assert pool.allocator.stats().evictions >= 1


def test_prefill_shared_refs_deepest_entry(setup):
    cfg, params, eng = setup
    pool = KVBlockPool.for_engine(eng, num_blocks=24, block_size=BS)
    pref = PrefixCachingEngine(eng, capacity=4, chunk=16, pool=pool)
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, 211, size=(20,)).astype(np.int32)
    logits, cache, ids, depth = pref.prefill_shared(prompt)
    # the walk just inserted the depth-16 entry; the caller holds refs
    assert depth == 16 and len(ids) == 2
    assert all(pool.allocator.refcount(b) == 2 for b in ids)
    pool.allocator.free(ids)
    assert logits.shape == (1, cfg.vocab_size)


# -- recompute-on-resume exactness (the preemption mechanism) ----------------


def test_recompute_resume_byte_identical_greedy_and_sampled(setup):
    """THE preemption/resume exactness argument, at engine level: after
    k emitted tokens, re-prefill prompt + emitted[:-1], carry
    emitted[-1] as the live token, and continue the SAME decode-key
    chain at step offset k-1 — the continuation equals the
    un-preempted stream byte-for-byte (prefill-recomputed KV ==
    incrementally-decoded KV; split(k, n)[i] is prefix-stable)."""
    cfg, params, eng = setup
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 211, size=(7,)).astype(np.int32)
    N, k = 20, 6
    key = jax.random.PRNGKey(42)
    s = SamplingConfig(mode="sample", temperature=0.8, top_k=12)
    for sampling, kw in ((SamplingConfig(), {}), (s, {"key": key})):
        toks = eng.generate(prompt[None, :], N, sampling=sampling,
                            **kw).tokens[0]
        emitted = toks[len(prompt):len(prompt) + k]
        ext = np.concatenate([prompt, emitted[:-1]]).astype(np.int32)
        _, dk = _split_keys(kw.get("key", jax.random.PRNGKey(0)))
        logits, cache = eng._prefill(eng._run_params(),
                                     jnp.asarray(ext[None, :]), None)
        token = jnp.asarray([emitted[-1]], jnp.int32)
        sk = _step_keys(dk, N - 1)
        used = k - 1
        parts = [np.asarray(token)[:, None]]
        for n, w in eng._segments(len(ext), N - k + 1):
            out, cache = eng._decode_seg(
                eng._run_params(), token, cache, None,
                sk[used:used + n], sampling=sampling, window=w)
            token = out[:, -1]
            parts.append(np.asarray(out))
            used += n
        got = np.concatenate(parts, axis=1)[0]
        np.testing.assert_array_equal(got, toks[len(prompt) + k - 1:])


# -- recompile budget: certified == observed ---------------------------------


def test_paged_cert_equals_observed_cache_sizes(setup):
    """The paged workloads' certified program bounds equal the REAL
    pool/engine jit cache sizes — no looser, no tighter (the graftcheck
    acceptance bar for the new entry points)."""
    import tools.graftcheck.recompile as R
    from tools.graftcheck import registry as REG
    cfg, params, _ = setup
    eng = DecodeEngine(params, cfg, max_seq=64)   # fresh program caches
    pool = KVBlockPool.for_engine(eng, num_blocks=24, block_size=8)
    runner = PagedKVRunner(eng, pool)
    rng = np.random.default_rng(10)
    for label, desc, paged, calls in REG.paged_workloads():
        assert desc.max_seq == eng.max_seq
        assert paged.block_size == pool.block_size
        for call in calls:
            prompts = [rng.integers(0, 211, size=(n,))
                       for n in call.prompt_lens]
            runner.generate(prompts if len(prompts) > 1
                            else prompts[0][None, :], call.max_new)
    cert = {}
    for label, desc, paged, calls in REG.paged_workloads():
        for name, n in R.certify_paged(desc, paged, calls).items():
            cert[name] = max(cert.get(name, 0), n)
    # pool data movers: one gather + one scatter program per width
    merged = {}
    for label, desc, paged, calls in REG.paged_workloads():
        for call in calls:
            for name, ks in R.paged_runner_keys(desc, paged,
                                                call).items():
                merged.setdefault(name, set()).update(ks)
    assert len(merged["_gather"]) == pool._gather._cache_size()
    assert len(merged["_scatter"]) == pool._scatter._cache_size()
    assert len(merged["_scatter_row"]) == \
        pool._scatter_row._cache_size() == 0
    assert len(merged["_copy"]) == pool._copy._cache_size() == 0
    assert len(merged["_prefill"]) == eng._prefill._cache_size()
    assert len(merged["_decode_seg"]) == eng._decode_seg._cache_size()


# -- retired-metric lint -----------------------------------------------------


def test_retired_metric_rule_fails_revived_names(tmp_path):
    from tools.graftcheck.metric_catalog import find_violations
    src = tmp_path / "m.py"
    src.write_text("from llm_sharding_demo_tpu.utils.metrics import "
                   "REGISTRY\n"
                   'REGISTRY.gauge("kv_cache_slots_in_use", 1)\n')
    bad = find_violations([str(src)])
    assert len(bad) == 1
    assert "retired" in bad[0][3]
    assert "kv_cache_blocks_in_use" in bad[0][3]


def test_catalog_has_block_gauges_not_retired_names():
    from llm_sharding_demo_tpu.utils.metrics import (METRIC_CATALOG,
                                                     RETIRED_METRICS)
    assert METRIC_CATALOG["kv_cache_blocks_in_use"] == "gauge"
    assert METRIC_CATALOG["kv_cache_blocks_total"] == "gauge"
    assert "kv_cache_slots_in_use" in RETIRED_METRICS
    assert not set(METRIC_CATALOG) & set(RETIRED_METRICS)


# -- serving admission (429 + Retry-After) -----------------------------------


def _serving_model():
    config = gpt2.GPT2Config(vocab_size=256, n_positions=64, n_embd=32,
                             n_layer=2, n_head=4)
    return config, gpt2.init_params(config, jax.random.PRNGKey(0))


def test_serving_healthz_reports_pool_and_generates(setup):
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.http import TestClient
    from llm_sharding_demo_tpu.serving.tokenizer import ByteTokenizer
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    cfg = ServingConfig(model_id="test", shard_role="coordinator",
                        max_seq=64, boundaries=(1,), kv_pool_blocks=16,
                        kv_block_size=8)
    client = TestClient(create_app(cfg, model=_serving_model(),
                                   tokenizer=ByteTokenizer()))
    h = client.get("/healthz").json()
    assert h["kv_pool_blocks"] == 16 and h["kv_block_size"] == 8
    assert h["kv_pool_stats"]["blocks_total"] == 16
    r = client.post("/generate", json={"prompt": "hi",
                                       "max_new_tokens": 6,
                                       "mode": "greedy"})
    assert r.status_code == 200 and "generated" in r.json()


def test_serving_sheds_429_with_retry_after_under_pool_pressure(
        setup, monkeypatch):
    """Sustained pool exhaustion answers 429 + Retry-After instead of
    queueing unboundedly; the shed is counted and flight-recorded."""
    from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.http import TestClient
    from llm_sharding_demo_tpu.serving.tokenizer import ByteTokenizer
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    from llm_sharding_demo_tpu.utils.metrics import MetricsRegistry
    reg = MetricsRegistry()
    cfg = ServingConfig(model_id="test", shard_role="coordinator",
                        max_seq=64, boundaries=(1,), kv_pool_blocks=16,
                        kv_block_size=8, max_batch=2, batch_mode="iter")
    client = TestClient(create_app(cfg, model=_serving_model(),
                                   tokenizer=ByteTokenizer(),
                                   registry=reg))
    monkeypatch.setattr(IterBatchingEngine, "admission_load",
                        lambda self, p, n: (False, 3.0))
    r = client.post("/generate", json={"prompt": "hello",
                                       "max_new_tokens": 6,
                                       "mode": "greedy"})
    assert r.status_code == 429
    assert r.headers.get("Retry-After") == "3"
    assert r.json()["error"] == "kv_pool_saturated"
    assert r.headers.get("X-Request-ID")
    snap = reg.snapshot()
    assert snap["kv_pool_admission_rejections_total"] == 1


def test_iterbatch_admission_load_sheds_on_saturation(setup):
    """The 429 decision itself, deterministic: pool watermark refuses
    the footprint AND the waiting line is at its limit."""
    cfg, params, eng = setup
    pool = KVBlockPool.for_engine(eng, num_blocks=8, block_size=8,
                                  watermark=0.5)
    from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
    ib = IterBatchingEngine(eng, max_batch=2, max_wait_ms=1.0,
                            pool=pool, queue_limit=0)
    ok, retry = ib.admission_load(40, 8)     # 5 blocks > 0.5 * 8
    assert not ok and retry >= 1.0
    ok, _ = ib.admission_load(8, 8)          # 1 block fits the watermark
    assert ok


def test_movers_take_a_private_copy_of_their_tables():
    """The scheduler rewrites its block tables in place between
    dispatches. A device array made straight from such a buffer aliases
    it on the CPU backend when the buffer is 64-byte aligned, so an
    asynchronously dispatched mover could read the REWRITTEN table —
    another row's blocks (the fault behind the pooled tests that failed
    under load and passed alone). Pinned on a buffer aligned on
    purpose."""
    raw = np.zeros(16 * 64 + 16, np.int32)
    skip = (-raw.ctypes.data % 64) // 4
    tables = raw[skip:skip + 16 * 64].reshape(16, 64)
    assert tables.ctypes.data % 64 == 0
    tables[:] = 3
    on_device = KVBlockPool._device_tables(tables)
    tables[:] = 9                      # the scheduler moves on
    np.testing.assert_array_equal(np.asarray(on_device), 3)


def test_pool_serves_an_engine_with_a_pallas_decode_kernel():
    """What ``decode_kernel="auto"`` resolves to on a TPU outside fp32:
    the engine's caches are FUSED ([K|V] rows), and the pool's movers
    convert at the block boundary — paged greedy decode equals the
    kernel engine's own contiguous decode, solo and as a ragged batch,
    and every block comes back."""
    cfg = gpt2.GPT2Config(vocab_size=512, n_positions=256, n_embd=128,
                          n_layer=2, n_head=2)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    eng = DecodeEngine(params, cfg, max_seq=256, dtype="bfloat16",
                       decode_kernel="interpret")
    assert eng._decode_kernel == "interpret"
    pool = KVBlockPool.for_engine(eng, num_blocks=48, block_size=16)
    assert pool.fused
    runner = PagedKVRunner(eng, pool)
    rng = np.random.default_rng(5)
    solo = rng.integers(0, 512, size=(1, 21))
    np.testing.assert_array_equal(runner.generate(solo, 12).tokens,
                                  eng.generate(solo, 12).tokens)
    ragged = [list(rng.integers(0, 512, size=(9,))),
              list(rng.integers(0, 512, size=(17,)))]
    np.testing.assert_array_equal(runner.generate(ragged, 10).tokens,
                                  eng.generate(ragged, 10).tokens)
    assert pool.allocator.stats().blocks_in_use == 0
