"""Quantized KV block storage (ops.kv_quant + the pool's ``_q`` mover
family) — ISSUE 16.

Four layers of claims, each pinned:

- **Block codecs** (ops.kv_quant): absmax roundtrip error is bounded by
  the regime's step size per (block, k|v, head) scale group; all-zero
  blocks round-trip exactly; the scale aval contract matches the pool
  shape's trailing trash block.
- **Full-precision pools are untouched**: a pool built without
  ``block_dtype`` has no ``_q`` movers, no scales array, and the paged
  runner stays BYTE-EQUAL to the contiguous engine (f32 and bf16) —
  the quant movers existing in the codebase must not cost the
  byte-equality pins anything.
- **Quantized pools work end to end**: deterministic replay, preempt/
  park/resume under the iteration scheduler WITH the sanitizer armed,
  prefix-store CoW sharing, recompile certification (``_q`` keys equal
  observed jit cache sizes), stats/gauges carrying the storage regime,
  and the kv.int8 tolerance-oracle path measuring a real (not skipped)
  row, replay-identical across runs.
- **The knobs fail loudly**: full-precision spellings and typos are
  typed errors at pool construction and at ServingConfig parse;
  ``fp8`` stays out of the ENGINE regime vocabulary.

Quantized preemption/resume is TOLERANCE-equivalent (kv.int8 budget),
not byte-identical — requantization after recompute can differ in the
last code — so the scheduler scenario here asserts the machinery
(preempted, resumed, completed, all blocks freed, no GraftsanError),
not stream equality. See tests/test_iterbatch.py for the byte-equality
scenarios on full-precision pools.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.ops import kv_quant as KVQ
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine, SamplingConfig
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.runtime.kv_pool import (KVBlockPool,
                                                   PagedKVRunner,
                                                   bytes_per_block)
from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine
from llm_sharding_demo_tpu.utils.graftnum import (GraftnumError,
                                                  engine_regime_of,
                                                  oracle_rows)

BS = 8


@pytest.fixture(scope="module")
def setup():
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=256, n_embd=32,
                          n_layer=2, n_head=4)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params, DecodeEngine(params, cfg, max_seq=64)


# -- block codecs ------------------------------------------------------------


def test_int8_roundtrip_error_bounded_per_scale_group():
    rng = np.random.default_rng(0)
    blk = jnp.asarray(rng.normal(size=(3, 2, 16, 8)).astype(np.float32))
    codes, scales = KVQ.quantize_blocks_int8(blk)
    assert codes.dtype == jnp.int8 and codes.shape == blk.shape
    assert scales.dtype == jnp.float32 and scales.shape == blk.shape[:-2]
    back = np.asarray(KVQ.dequantize_blocks(codes, scales, jnp.float32))
    # absmax scaling: |err| <= scale/2 + float slop, per (.., bs, hd) group
    absmax = np.abs(np.asarray(blk)).max(axis=(-2, -1))
    err = np.abs(back - np.asarray(blk)).max(axis=(-2, -1))
    np.testing.assert_array_less(err, absmax / 127.0 * 0.501 + 1e-7)


def test_fp8_roundtrip_error_bounded():
    if not KVQ.fp8_supported():
        pytest.skip("backend lacks float8_e4m3fn storage")
    rng = np.random.default_rng(1)
    blk = jnp.asarray(rng.normal(size=(2, 2, 16, 8)).astype(np.float32))
    codes, scales = KVQ.quantize_blocks_fp8(blk)
    assert codes.dtype == jnp.float8_e4m3fn and codes.shape == blk.shape
    back = np.asarray(KVQ.dequantize_blocks(codes, scales, jnp.float32))
    # e4m3 carries ~3 mantissa bits: relative step 2^-3 on the
    # absmax-normalized content is a generous elementwise bound
    absmax = np.abs(np.asarray(blk)).max(axis=(-2, -1), keepdims=True)
    err = np.abs(back - np.asarray(blk))
    assert np.all(err < absmax * 0.07 + 1e-7)


def test_zero_blocks_roundtrip_exactly_and_scale_shapes_match_pool():
    zero = jnp.zeros((2, 2, 8, 4), jnp.float32)
    codes, scales = KVQ.quantize_blocks_int8(zero)
    assert not np.asarray(codes).any()
    np.testing.assert_array_equal(
        np.asarray(KVQ.dequantize_blocks(codes, scales, jnp.float32)), 0.0)
    # the scale aval carries the pool's trailing trash block
    from llm_sharding_demo_tpu.ops import paged_attention as PA
    pool_shape = PA.pool_shape(2, 24, 4, BS, 8)
    assert KVQ.scales_shape(2, 24, 4) == (2, 25, 2, 4)
    assert KVQ.scales_shape(2, 24, 4)[:2] == pool_shape[:2]


# -- full-precision pools: untouched by the feature --------------------------


def test_full_precision_pool_has_no_quant_movers(setup):
    cfg, params, eng = setup
    pool = KVBlockPool.for_engine(eng, num_blocks=24, block_size=BS)
    assert pool.block_dtype is None and pool.scales is None
    assert hasattr(pool, "_gather") and not hasattr(pool, "_gather_q")
    assert pool.block_regime == "f32"
    q = KVBlockPool.for_engine(eng, num_blocks=24, block_size=BS,
                               block_dtype="int8")
    assert q.block_dtype == "int8" and q.scales is not None
    assert hasattr(q, "_gather_q") and not hasattr(q, "_gather")


def test_full_precision_byte_equality_survives_f32_and_bf16(setup):
    """The no-regression pin: with the quant mover family present in
    the module, full-precision pools (f32 AND bf16 engines) stay
    byte-equal to contiguous decode — greedy and seeded sample."""
    cfg, params, _ = setup
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 211, size=(7,)).astype(np.int32)
    s = SamplingConfig(mode="sample", temperature=0.7, top_k=17)
    key = jax.random.PRNGKey(5)
    for dtype in (jnp.float32, jnp.bfloat16):
        eng = DecodeEngine(params, cfg, max_seq=64, dtype=dtype)
        pool = KVBlockPool.for_engine(eng, num_blocks=24, block_size=BS)
        runner = PagedKVRunner(eng, pool)
        want = eng.generate(prompt[None, :], 16)
        got = runner.generate(prompt[None, :], 16)
        np.testing.assert_array_equal(got.tokens, want.tokens)
        want_s = eng.generate(prompt[None, :], 16, sampling=s, key=key)
        got_s = runner.generate(prompt[None, :], 16, sampling=s, key=key)
        np.testing.assert_array_equal(got_s.tokens, want_s.tokens)
        assert pool.allocator.stats().blocks_in_use == 0


# -- quantized pools end to end ----------------------------------------------


def test_quantized_runner_completes_and_replays_identically(setup):
    """Content-only requantization: every scatter recomputes scales
    from the content, so two identical runs over the same pool are
    byte-equal to each other (determinism — the tolerance argument vs
    full precision lives in the kv.int8 oracle, not here)."""
    cfg, params, eng = setup
    pool = KVBlockPool.for_engine(eng, num_blocks=24, block_size=BS,
                                  block_dtype="int8")
    runner = PagedKVRunner(eng, pool)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 211, size=(5,)),
               rng.integers(0, 211, size=(9,))]
    keys = jnp.stack([jax.random.PRNGKey(1), jax.random.PRNGKey(2)])
    s = SamplingConfig(mode="sample", temperature=0.7, top_k=17)
    a = runner.generate(prompts, 16, sampling=s, key=keys)
    b = runner.generate(prompts, 16, sampling=s, key=keys)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    np.testing.assert_array_equal(a.pad, b.pad)
    assert a.tokens.shape[1] >= 16
    assert pool.allocator.stats().blocks_in_use == 0


def test_kv_int8_oracle_row_is_real_and_replay_identical():
    """The strict-driver wiring bar: the kv.int8 path runs a REAL
    measurement (not a skip row) inside its declared budget — the
    oracle raises on breach, so the row existing IS the pass — and the
    report is replay-identical across a full engine/pool REBUILD (the
    k-th workload is a pure function of (seed, path, k), so the
    bench-consumer row and an independently-built probe agree byte for
    byte)."""
    rows = oracle_rows(seed=0, max_seq=16)
    by_path = {r["path"]: r for r in rows}
    row = by_path["kv.int8"]
    assert "skipped" not in row
    assert row["seed"] == 0 and row["n_positions"] > 0
    # fp8 is declared either way: measured where the backend supports
    # the storage dtype, an explicit skip-with-reason row where not
    fp8 = by_path["kv.fp8"]
    if KVQ.fp8_supported():
        assert "skipped" not in fp8 and fp8["n_positions"] > 0
    else:
        assert fp8["skipped"]
    # replay: rebuild ONLY the kv.int8 probe (fresh engine, fresh pool,
    # fresh jit caches) and compare twice against a fresh exact engine
    from llm_sharding_demo_tpu.fleet.harness import demo_model
    from llm_sharding_demo_tpu.utils.graftnum import (ToleranceOracle,
                                                      _QuantizedKVProbe)
    from llm_sharding_demo_tpu.utils.metrics import DEFAULT_KV_BLOCK_SIZE
    cfg, params = demo_model(16)
    exact = DecodeEngine(params, cfg, max_seq=16)
    pool = KVBlockPool.for_engine(
        exact, num_blocks=2 * (exact._cache_seq // DEFAULT_KV_BLOCK_SIZE),
        block_dtype="int8")
    probe = _QuantizedKVProbe(exact, pool)
    r1 = ToleranceOracle(0).compare("kv.int8", probe, exact)
    r2 = ToleranceOracle(0).compare("kv.int8", probe, exact)
    assert r1 == r2
    assert {k: v for k, v in r1.items() if k != "positions"} == row


def test_quantized_cert_equals_observed_cache_sizes(setup):
    """certify_paged with ``quantized=True`` bounds the ``_q`` mover
    programs exactly — same key structure as the plain family (storage
    dtype never keys programs), observed on a REAL int8 pool."""
    import tools.graftcheck.recompile as R
    from tools.graftcheck import registry as REG
    cfg, params, _ = setup
    eng = DecodeEngine(params, cfg, max_seq=64)   # fresh program caches
    pool = KVBlockPool.for_engine(eng, num_blocks=24, block_size=8,
                                  block_dtype="int8")
    runner = PagedKVRunner(eng, pool)
    rng = np.random.default_rng(10)
    for label, desc, paged, calls in REG.paged_workloads():
        for call in calls:
            prompts = [rng.integers(0, 211, size=(n,))
                       for n in call.prompt_lens]
            runner.generate(prompts if len(prompts) > 1
                            else prompts[0][None, :], call.max_new)
    merged = {}
    for label, desc, paged, calls in REG.paged_workloads():
        pq = dataclasses.replace(paged, quantized=True)
        for call in calls:
            for name, ks in R.paged_runner_keys(desc, pq, call).items():
                merged.setdefault(name, set()).update(ks)
        cert = R.certify_paged(desc, pq, calls)
        assert "_gather_q" in cert and "_gather" not in cert
    assert len(merged["_gather_q"]) == pool._gather_q._cache_size()
    assert len(merged["_scatter_q"]) == pool._scatter_q._cache_size()
    assert len(merged["_scatter_row_q"]) == \
        pool._scatter_row_q._cache_size() == 0
    assert len(merged["_copy_q"]) == pool._copy_q._cache_size() == 0
    assert len(merged["_prefill"]) == eng._prefill._cache_size()
    assert len(merged["_decode_seg"]) == eng._decode_seg._cache_size()


def test_quantized_pool_preempts_and_resumes_under_graftsan():
    """The scheduler machinery on int8 storage WITH the sanitizer
    armed: a deliberately tiny quantized pool oversubscribes, the
    younger row parks and resumes by recompute, both rows complete,
    every block returns, and no GraftsanError fires (the poisoner runs
    the ``_q`` copy mover). Streams are NOT pinned byte-equal to solo:
    resume-by-recompute under quantized storage is tolerance-equivalent
    (kv.int8), not byte-identical — see the module docstring."""
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=256, n_embd=32,
                          n_layer=2, n_head=4)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    eng = DecodeEngine(params, cfg, max_seq=104)
    pool = KVBlockPool.for_engine(eng, num_blocks=13, block_size=8,
                                  watermark=1.0, sanitize=True,
                                  block_dtype="int8")
    ib = IterBatchingEngine(eng, max_batch=4, seg_steps=8,
                            max_wait_ms=300.0, pool=pool)
    rng = np.random.default_rng(42)
    pA = rng.integers(0, 211, size=(5,))
    pB = rng.integers(0, 211, size=(8,))
    res = [None, None]

    def run(i, p, n):
        res[i] = ib.generate(p, n)

    threads = [threading.Thread(target=run, args=(0, pA, 48)),
               threading.Thread(target=run, args=(1, pB, 60))]
    for t in threads:
        t.start()
        time.sleep(0.01)
    for t in threads:
        t.join(timeout=300)
    st = ib.stats()
    assert res[0] is not None and res[1] is not None
    assert res[0].tokens.shape[1] == len(pA) + 48
    assert res[1].tokens.shape[1] == len(pB) + 60
    assert st["preemptions"] >= 1 and st["resumes"] >= 1
    assert st["parked"] == 0
    assert pool.allocator.stats().blocks_in_use == 0


def test_quantized_prefix_store_shares_blocks_with_cow(setup):
    """Prefix sharing on int8 storage: the hit path references store
    blocks (CoW at the unaligned frontier) and replays identically.
    The MISS run is not pinned equal to the HIT runs: the frontier
    block's scale covers different resident content in the store copy
    vs the private full row — that drift is the declared kv.int8
    budget, not a bug."""
    cfg, params, eng = setup
    pool = KVBlockPool.for_engine(eng, num_blocks=40, block_size=BS,
                                  block_dtype="int8")
    pref = PrefixCachingEngine(eng, capacity=4, chunk=20, pool=pool)
    runner = PagedKVRunner(eng, pool, prefix=pref)
    rng = np.random.default_rng(6)
    long = rng.integers(0, 211, size=(30,)).astype(np.int32)
    got1 = runner.generate(long[None, :], 12).tokens     # miss + insert
    got2 = runner.generate(long[None, :], 12).tokens     # hit, shares
    got3 = runner.generate(long[None, :], 12).tokens     # hit again
    assert got1.shape == got2.shape == got3.shape
    np.testing.assert_array_equal(got2, got3)            # hits replay
    st = pool.allocator.stats()
    assert st.prefix_entries == 1
    assert st.cow_copies >= 1
    assert st.blocks_in_use == st.blocks_evictable == 3  # ceil(20/8)
    assert pref.stats()["hits"] >= 2 and pref.stats()["pooled"]


# -- stats, gauges, capacity arithmetic --------------------------------------


def test_quantized_stats_gauges_and_capacity_ratio(setup):
    cfg, params, eng = setup
    from llm_sharding_demo_tpu.utils.metrics import REGISTRY
    pool = KVBlockPool.for_engine(eng, num_blocks=24, block_size=BS,
                                  block_dtype="int8")
    runner = PagedKVRunner(eng, pool)
    rng = np.random.default_rng(7)
    runner.generate(rng.integers(0, 211, size=(6,))[None, :], 8)
    st = pool.stats()
    assert st["block_dtype"] == "int8"
    assert st["bytes_per_block"] == pool._bytes_per_block
    snap = REGISTRY.snapshot()
    key = "{block_dtype=int8,component=paged}"
    assert snap["kv_cache_blocks_total" + key] == 24
    assert ("kv_cache_blocks_in_use" + key) in snap
    assert snap["kv_pool_bytes_per_block" + key] == pool._bytes_per_block
    # the module-level planner arithmetic matches the built pool, and
    # int8 storage buys >= 2x blocks at equal HBM (the tentpole claim;
    # the scale overhead is one f32 per (layer, k|v, head) per block)
    heads = getattr(cfg, "n_kv_head", cfg.n_head)
    full = bytes_per_block(cfg.n_layer, heads, BS, cfg.head_dim,
                           dtype=jnp.float32)
    narrow = bytes_per_block(cfg.n_layer, heads, BS, cfg.head_dim,
                             dtype=jnp.float32, block_dtype="int8")
    assert narrow == pool._bytes_per_block
    assert full >= 2 * narrow


# -- the knobs fail loudly ---------------------------------------------------


def test_pool_rejects_full_precision_and_undeclared_block_dtypes(setup):
    cfg, params, eng = setup
    with pytest.raises(ValueError, match="full-precision"):
        KVBlockPool.for_engine(eng, num_blocks=24, block_size=BS,
                               block_dtype="f32")
    with pytest.raises(ValueError, match="full-precision"):
        KVBlockPool.for_engine(eng, num_blocks=24, block_size=BS,
                               block_dtype="bfloat16")
    with pytest.raises(GraftnumError, match="regime"):
        KVBlockPool.for_engine(eng, num_blocks=24, block_size=BS,
                               block_dtype="int4")


def test_serving_config_kv_pool_dtype_validation():
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    ok = ServingConfig(kv_pool_dtype="int8", kv_pool_blocks=24,
                       kv_block_size=8, max_seq=64)
    assert ok.kv_pool_dtype == "int8"
    # the knob without a pool would be silently ignored — loud instead
    with pytest.raises(ValueError, match="KV_POOL_DTYPE"):
        ServingConfig(kv_pool_dtype="int8")
    # typos fail through THE regime vocabulary, not a KeyError
    with pytest.raises(ValueError, match="KV_POOL_DTYPE"):
        ServingConfig(kv_pool_dtype="int4", kv_pool_blocks=24,
                      kv_block_size=8, max_seq=64)
    # full-precision spellings point at the pool's existing behavior
    with pytest.raises(ValueError, match="KV_POOL_DTYPE"):
        ServingConfig(kv_pool_dtype="bfloat16", kv_pool_blocks=24,
                      kv_block_size=8, max_seq=64)
    # continuous re-planning certifies the full-precision movers only
    with pytest.raises(ValueError, match="KV_POOL_DTYPE"):
        ServingConfig(kv_pool_dtype="int8", kv_pool_blocks=24,
                      kv_block_size=8, max_seq=64, max_batch=4,
                      batch_mode="iter", auto_plan_continuous=True)


def test_fp8_stays_out_of_engine_regime_vocabulary():
    assert engine_regime_of("bfloat16") == "bf16"
    with pytest.raises(GraftnumError, match="ENGINE regime"):
        engine_regime_of("fp8")


def test_quantized_pool_is_gathered_for_every_call(setup):
    """A quantized pool has no column-range mover (a block's scale is
    of its whole content) and the scheduler keeps no working cache
    beside it: every decode call gathers the batch through the blocks'
    scales and scatters it back whole, and the counters say so."""
    cfg, params, eng = setup
    pool = KVBlockPool.for_engine(eng, num_blocks=16, block_size=BS,
                                  block_dtype="int8")
    assert not hasattr(pool, "_scatter_span")
    ib = IterBatchingEngine(eng, max_batch=2, seg_steps=8, pool=pool)
    res = ib.generate(np.arange(5) + 3, 20)
    assert res.new_tokens == 20
    st = ib.stats()
    assert st["segments"] == st["cache_gathers"] == 3
    assert st["calls_resident"] == 0
    assert st["blocks_written_back"] == 3 * pool.nbm
    assert pool._gather_q._cache_size() == 1
