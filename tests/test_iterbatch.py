"""Iteration-level continuous batching (runtime.iterbatch).

Correctness bar (same as the admission batcher, per row): whatever a
request joined mid-flight, however segments were scheduled, its tokens
equal a solo engine run — greedy via row-independent attention +
left-pad masking, seeded sampling via per-row keys at the row's own
step offsets. Plus the scheduling claims themselves: a request arriving
mid-decode joins the LIVE batch (within one segment) instead of waiting
it out, and an early-EOS row frees its slot before the batch ends.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine, SamplingConfig
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine


def _setup(max_seq=200, **kw):
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=256, n_embd=32,
                          n_layer=2, n_head=4)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    engine = DecodeEngine(params, cfg, max_seq=max_seq, **kw)
    return cfg, params, engine


@pytest.fixture(scope="module")
def setup():
    cfg, params, engine = _setup()
    return engine, IterBatchingEngine(engine, max_batch=4, seg_steps=8,
                                      max_wait_ms=50.0)


def _staggered(ib, jobs):
    """jobs: list of (prompt, steps, trigger, kwargs). ``trigger`` is a
    fixed delay in seconds, or a callable polled until it returns True
    (event-driven arrival — immune to how fast the warm compilation
    cache makes the first batch finish). Returns results in job order."""
    res = [None] * len(jobs)

    def run(i, p, n, trigger, kw):
        if callable(trigger):
            deadline = time.monotonic() + 120
            while not trigger() and time.monotonic() < deadline:
                time.sleep(0.001)
        else:
            time.sleep(trigger)
        res[i] = ib.generate(p, n, **kw)

    threads = [threading.Thread(target=run, args=(i, p, n, d, kw))
               for i, (p, n, d, kw) in enumerate(jobs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    return res


def _after_segments(ib, base, k):
    """Trigger: the scheduler has run ``k`` more segments than ``base``
    — i.e. the head batch is live and mid-decode RIGHT NOW."""
    return lambda: ib.stats()["segments"] >= base + k


def test_mid_decode_join_is_exact_and_within_one_segment(setup):
    """The VERDICT r3 #2 'done' bar: a request arriving mid-decode
    starts within one segment (joins the live batch) and its tokens
    equal a solo run."""
    engine, ib = setup
    rng = np.random.default_rng(1)
    pA = rng.integers(0, 211, size=(5,))
    pB = rng.integers(0, 211, size=(9,))
    wantA = engine.generate(pA[None, :], 96).tokens[0]
    wantB = engine.generate(pB[None, :], 40).tokens[0]
    before = ib.stats()
    # B arrives once A's decode is demonstrably mid-flight (event-driven:
    # a fixed sleep breaks when the warm compile cache makes A fast)
    resA, resB = _staggered(ib, [
        (pA, 96, 0.0, {}),
        (pB, 40, _after_segments(ib, before["segments"], 1), {})])
    after = ib.stats()
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    np.testing.assert_array_equal(resB.tokens[0], wantB)
    # B joined A's live batch (a join, not a second batch)
    assert after["joins"] - before["joins"] >= 1
    assert after["batches"] - before["batches"] == 1


def test_many_staggered_greedy_all_exact(setup):
    engine, ib = setup
    rng = np.random.default_rng(2)
    jobs = []
    want = []
    for i, (n_prompt, steps, delay) in enumerate(
            [(4, 50, 0.0), (7, 30, 0.2), (11, 40, 0.5), (6, 20, 0.9),
             (9, 25, 1.2)]):
        p = rng.integers(0, 211, size=(n_prompt,))
        jobs.append((p, steps, delay, {}))
        want.append(engine.generate(p[None, :], steps).tokens[0])
    res = _staggered(ib, jobs)
    for i, (r, w) in enumerate(zip(res, want)):
        assert r is not None, f"request {i} never completed"
        np.testing.assert_array_equal(r.tokens[0], w, err_msg=f"req {i}")


def test_sampled_joiner_stream_byte_equal_solo(setup):
    """A sample-mode row joining mid-decode consumes its own per-step
    keys at its own offsets — byte-equal to the solo run."""
    engine, ib = setup
    rng = np.random.default_rng(3)
    pA = rng.integers(0, 211, size=(5,))
    pB = rng.integers(0, 211, size=(8,))
    s = SamplingConfig(mode="sample", temperature=0.7, top_k=30)
    kA, kB = jax.random.PRNGKey(11), jax.random.PRNGKey(12)
    wantA = engine.generate(pA[None, :], 96, sampling=s, key=kA).tokens[0]
    wantB = engine.generate(pB[None, :], 30, sampling=s, key=kB).tokens[0]
    before = ib.stats()
    resA, resB = _staggered(ib, [
        (pA, 96, 0.0, dict(sampling=s, key=kA)),
        (pB, 30, _after_segments(ib, before["segments"], 1),
         dict(sampling=s, key=kB))])
    after = ib.stats()
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    np.testing.assert_array_equal(resB.tokens[0], wantB)
    assert after["joins"] - before["joins"] >= 1


def test_eos_row_retires_early_and_frees_slot(setup):
    """An early-EOS row stops at a segment boundary (truncated, exact
    prefix) instead of decoding to the end of the batch."""
    engine, ib = setup
    rng = np.random.default_rng(4)
    pA = rng.integers(0, 211, size=(5,))
    pB = rng.integers(0, 211, size=(6,))
    wantA = engine.generate(pA[None, :], 80).tokens[0]
    plainB = engine.generate(pB[None, :], 80).tokens[0]
    eosB = int(plainB[6 + 3])  # B's 4th new token
    before = ib.stats()
    resA, resB = _staggered(ib, [
        (pA, 80, 0.0, {}), (pB, 80, 0.1, dict(eos_id=eosB))])
    after = ib.stats()
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    # B: exact prefix through its EOS, then stopped
    nB = resB.new_tokens
    assert nB < 80
    np.testing.assert_array_equal(resB.tokens[0], plainB[:6 + nB])
    assert int(resB.tokens[0, -1]) == eosB
    assert after["eos_retires"] - before["eos_retires"] >= 1


def test_long_prompt_late_joiner_waits_until_depth_allows(setup):
    """A joiner whose prompt exceeds the current depth cannot merge yet
    (its content would need future slots); it must still complete
    exactly — either joining later or seeding the next batch."""
    engine, ib = setup
    rng = np.random.default_rng(5)
    pA = rng.integers(0, 211, size=(4,))       # depth starts at 16
    pB = rng.integers(0, 211, size=(60,))      # > current depth at arrival
    wantA = engine.generate(pA[None, :], 70).tokens[0]
    wantB = engine.generate(pB[None, :], 20).tokens[0]
    resA, resB = _staggered(ib, [
        (pA, 70, 0.0, {}), (pB, 20, 0.5, {})])
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    np.testing.assert_array_equal(resB.tokens[0], wantB)


def test_policy_switch_drains_then_seeds_new_batch(setup):
    """A sample arrival during a greedy batch closes admission (FIFO)
    and seeds the next batch; both finish exact."""
    engine, ib = setup
    rng = np.random.default_rng(6)
    pG = rng.integers(0, 211, size=(5,))
    pS = rng.integers(0, 211, size=(7,))
    s = SamplingConfig(mode="sample", temperature=0.9, top_k=15)
    k = jax.random.PRNGKey(44)
    wantG = engine.generate(pG[None, :], 40).tokens[0]
    wantS = engine.generate(pS[None, :], 20, sampling=s, key=k).tokens[0]
    resG, resS = _staggered(ib, [
        (pG, 40, 0.0, {}), (pS, 20, 0.5, dict(sampling=s, key=k))])
    np.testing.assert_array_equal(resG.tokens[0], wantG)
    np.testing.assert_array_equal(resS.tokens[0], wantS)


def test_composes_with_decode_kernel_fused_cache():
    """Kernel-mode engines (fused [K|V] cache, interpret on CPU) admit
    and retire through the same roll/merge — streams stay exact."""
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=1024, n_embd=64,
                          n_layer=2, n_head=1)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(7)))
    engine = DecodeEngine(params, cfg, max_seq=300,
                          decode_kernel="interpret")
    ib = IterBatchingEngine(engine, max_batch=2, seg_steps=8,
                            max_wait_ms=30.0)
    rng = np.random.default_rng(8)
    pA = rng.integers(0, 211, size=(5,))
    pB = rng.integers(0, 211, size=(7,))
    wantA = engine.generate(pA[None, :], 40).tokens[0]
    wantB = engine.generate(pB[None, :], 24).tokens[0]
    resA, resB = _staggered(ib, [(pA, 40, 0.0, {}), (pB, 24, 0.6, {})])
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    np.testing.assert_array_equal(resB.tokens[0], wantB)


def test_composes_with_staged_engine():
    cfg, params, _ = _setup()
    engine = DecodeEngine(params, cfg, max_seq=200, boundaries=[1])
    ib = IterBatchingEngine(engine, max_batch=2, seg_steps=8,
                            max_wait_ms=30.0)
    rng = np.random.default_rng(9)
    pA = rng.integers(0, 211, size=(5,))
    pB = rng.integers(0, 211, size=(6,))
    wantA = engine.generate(pA[None, :], 96).tokens[0]
    wantB = engine.generate(pB[None, :], 20).tokens[0]
    # B arrives while A is demonstrably mid-flight (not after a fixed
    # delay, which a warm compile cache turns into "after A is done"):
    # the batch of one GROWS around the staged engine's list of caches,
    # which the grow once took for one cache (`'list' object has no
    # attribute 'state'`, a 500 under load)
    before = ib.stats()
    resA, resB = _staggered(ib, [
        (pA, 96, 0.0, {}),
        (pB, 20, _after_segments(ib, before["segments"], 1), {})])
    after = ib.stats()
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    np.testing.assert_array_equal(resB.tokens[0], wantB)
    assert after["joins"] - before["joins"] >= 1
    assert after["grows"] - before["grows"] >= 1


def _spec_setup(max_seq=200, draft_len=5, seg_steps=12, max_batch=4):
    """A speculative engine + iteration scheduler sharing ONE plain
    engine (the composition's wiring contract: spec.plain IS the
    scheduler's engine)."""
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=256, n_embd=32,
                          n_layer=2, n_head=4)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    spec = SpecDecodeEngine(params, cfg, max_seq=max_seq,
                            draft_len=draft_len)
    ib = IterBatchingEngine(spec.plain, max_batch=max_batch,
                            seg_steps=seg_steps, max_wait_ms=50.0,
                            spec=spec)
    return spec, ib


@pytest.fixture(scope="module")
def spec_setup():
    return _spec_setup()


SPEC = SamplingConfig(spec=True)


def test_spec_segments_mid_flight_join_exact(spec_setup):
    """THE tentpole bar (ISSUE 1): speculative decoding composes with
    continuous batching — a spec request arriving mid-decode joins the
    LIVE speculating batch at a segment boundary, and every row is
    byte-equal to its solo ``SpecDecodeEngine.generate`` run, whatever
    per-row acceptance the draft-verify segments produced."""
    spec, ib = spec_setup
    rng = np.random.default_rng(31)
    pA = np.tile(np.asarray([5, 17, 3, 42], np.int32), 6)  # accepts drafts
    pB = rng.integers(0, 211, size=(9,))                   # mostly rejects
    wantA = spec.generate(pA, 96).tokens[0]
    wantB = spec.generate(pB, 40).tokens[0]
    before = ib.stats()
    resA, resB = _staggered(ib, [
        (pA, 96, 0.0, dict(sampling=SPEC)),
        (pB, 40, _after_segments(ib, before["segments"], 1),
         dict(sampling=SPEC))])
    after = ib.stats()
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    np.testing.assert_array_equal(resB.tokens[0], wantB)
    # B joined A's live speculating batch; segments were draft-verify
    assert after["joins"] - before["joins"] >= 1
    assert after["batches"] - before["batches"] == 1
    assert after["spec_segments"] - before["spec_segments"] >= 2


def test_spec_sampled_rows_byte_equal_solo_across_segments(spec_setup):
    """Seeded sample-mode speculation under the scheduler: per-row
    verify key chains resume across segment boundaries, so a row's
    stream is byte-equal to its uninterrupted solo run (not merely
    same-distribution) — the joiner starting its chain at its own
    step 0."""
    spec, ib = spec_setup
    s = SamplingConfig(mode="sample", temperature=0.7, top_k=30, spec=True)
    pA = np.tile(np.asarray([7, 3], np.int32), 8)
    pB = np.tile(np.asarray([9, 2, 11], np.int32), 4)
    kA, kB = jax.random.PRNGKey(61), jax.random.PRNGKey(62)
    wantA = spec.generate(pA, 60, sampling=s, key=kA).tokens[0]
    wantB = spec.generate(pB, 24, sampling=s, key=kB).tokens[0]
    before = ib.stats()
    resA, resB = _staggered(ib, [
        (pA, 60, 0.0, dict(sampling=s, key=kA)),
        (pB, 24, _after_segments(ib, before["segments"], 1),
         dict(sampling=s, key=kB))])
    after = ib.stats()
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    np.testing.assert_array_equal(resB.tokens[0], wantB)
    assert after["spec_segments"] - before["spec_segments"] >= 1


def test_spec_and_plain_batches_stay_separate(spec_setup):
    """The ``spec`` flag is part of policy equality: a plain arrival
    during a spec batch seeds its OWN batch (FIFO preserved) instead of
    joining — and both finish exact."""
    spec, ib = spec_setup
    rng = np.random.default_rng(33)
    pS = np.tile(np.asarray([4, 19], np.int32), 6)
    pP = rng.integers(0, 211, size=(7,))
    wantS = spec.generate(pS, 60).tokens[0]
    wantP = spec.plain.generate(pP[None, :], 20).tokens[0]
    before = ib.stats()
    resS, resP = _staggered(ib, [
        (pS, 60, 0.0, dict(sampling=SPEC)),
        (pP, 20, _after_segments(ib, before["segments"], 1), {})])
    after = ib.stats()
    np.testing.assert_array_equal(resS.tokens[0], wantS)
    np.testing.assert_array_equal(resP.tokens[0], wantP)
    assert after["batches"] - before["batches"] == 2


def test_spec_segment_compile_space_bounded(spec_setup):
    """Acceptance criterion (ISSUE 1): the spec verify/rewind segment
    program set stays FINITE under varying per-row acceptance — one
    program per (batch width, max_verify, policy), acceptance counts
    and budgets being traced values. Several requests with wildly
    different acceptance profiles at width 1 must share ONE program."""
    spec, ib = _spec_setup()
    rng = np.random.default_rng(34)
    prompts = [np.tile(np.asarray([5, 17, 3, 42], np.int32), 5),
               rng.integers(0, 211, size=(13,)),
               np.asarray([8] * 10, np.int32)]
    for p in prompts:
        ib.generate(p, 30, sampling=SPEC)
    widths = 1   # sequential solo requests all ran at right-sized width 1
    assert spec._seg_b._cache_size() == widths, (
        f"{spec._seg_b._cache_size()} spec-segment programs for "
        f"{widths} (width, policy) combo(s) — a shape is being minted "
        "per acceptance pattern")


def test_prefix_cache_admission_prefill_exact():
    """Satellite (ISSUE 1): iterbatch admission prefills through the
    prefix store — a joiner whose prompt shares a cached prefix
    forwards only its suffix, hits the store, and its stream is
    byte-equal to the solo run."""
    from llm_sharding_demo_tpu.runtime.prefix_cache import (
        PrefixCachingEngine)
    cfg, params, engine = _setup()
    prefix = PrefixCachingEngine(engine, capacity=4, chunk=16)
    ib = IterBatchingEngine(engine, max_batch=4, seg_steps=8,
                            max_wait_ms=50.0, prefix=prefix)
    rng = np.random.default_rng(35)
    shared = rng.integers(0, 211, size=(40,))
    # warm the store (2 chunks of 16 cached; public admission-prefill API)
    prefix.prefill_state(shared)
    h0 = prefix.stats()
    pA = rng.integers(0, 211, size=(45,))   # seeds: depth 48 >= len(shared)
    pB = shared                             # joiner: warm-prefix admission
    wantA = engine.generate(pA[None, :], 60).tokens[0]
    wantB = engine.generate(pB[None, :], 30).tokens[0]
    before = ib.stats()
    resA, resB = _staggered(ib, [
        (pA, 60, 0.0, {}),
        (pB, 30, _after_segments(ib, before["segments"], 1), {})])
    after = ib.stats()
    h1 = prefix.stats()
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    np.testing.assert_array_equal(resB.tokens[0], wantB)
    assert after["joins"] - before["joins"] >= 1
    assert h1["hits"] > h0["hits"], (
        "the joiner's admission prefill never consulted the prefix store")


def test_spec_validation_gates():
    """Spec-flagged requests the verify loop cannot serve exactly are
    refused on the CALLER thread with their own numbers; miswired
    engines are refused at construction."""
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine
    spec, ib = _spec_setup(max_seq=64, draft_len=4)
    with pytest.raises(ValueError, match="speculative engine"):
        IterBatchingEngine(spec.plain, max_batch=2).generate(
            np.arange(8, dtype=np.int32), 4, sampling=SPEC)
    with pytest.raises(ValueError, match="shorter than ngram"):
        ib.generate(np.asarray([5], np.int32), 4, sampling=SPEC)
    with pytest.raises(ValueError, match="headroom"):
        ib.generate(np.arange(8, dtype=np.int32), 64 - 8, sampling=SPEC)
    # spec engine must wrap the SAME DecodeEngine instance
    cfg, params, other = _setup()
    with pytest.raises(ValueError, match="same DecodeEngine"):
        IterBatchingEngine(other, max_batch=2,
                           spec=_spec_setup(max_seq=64)[0])
    with pytest.raises(ValueError, match="same engine"):
        from llm_sharding_demo_tpu.runtime.prefix_cache import (
            PrefixCachingEngine)
        IterBatchingEngine(other, max_batch=2,
                           prefix=PrefixCachingEngine(_setup()[2]))


def test_validation_gates():
    from llm_sharding_demo_tpu.models import moe
    cfg, params, engine = _setup()
    # keyless sample refused on the caller thread
    ib = IterBatchingEngine(engine, max_batch=2)
    with pytest.raises(ValueError, match="PRNG key"):
        ib.generate(np.asarray([5, 6]), 4,
                    sampling=SamplingConfig(mode="sample"))
    with pytest.raises(ValueError, match="max_seq"):
        ib.generate(np.arange(190), 90)
    # MoE routing is not window-independent
    mcfg = moe.MoEConfig(vocab_size=97, n_positions=64, n_embd=16,
                         n_layer=2, n_head=2, n_experts=4, expert_top_k=2)
    meng = DecodeEngine(moe.init_params(mcfg, jax.random.PRNGKey(0)),
                        mcfg, max_seq=48)
    with pytest.raises(NotImplementedError, match="window-independent"):
        IterBatchingEngine(meng, max_batch=2)
    # chunked-prefill engines use the admission batcher
    ceng = DecodeEngine(params, cfg, max_seq=200, prefill_chunk=8)
    with pytest.raises(NotImplementedError, match="prefill_chunk"):
        IterBatchingEngine(ceng, max_batch=2)


def test_serving_batch_mode_iter():
    """BATCH_MODE=iter serves concurrent /generate requests through the
    iteration scheduler; outputs match the admission-mode app, healthz
    reports the scheduler stats, misconfigurations refuse."""
    import json
    import threading as th
    import urllib.error
    import urllib.request

    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.http import TestClient, serve
    from llm_sharding_demo_tpu.serving.tokenizer import ByteTokenizer
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    from tests.test_convert_and_failure import _free_port

    cfg = gpt2.GPT2Config(vocab_size=256, n_positions=64, n_embd=16,
                          n_layer=2, n_head=2)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(4)))
    model = (cfg, params)
    ref = TestClient(create_app(
        ServingConfig(model_id="t", max_seq=48, max_batch=4),
        model=model, tokenizer=ByteTokenizer()))
    port = _free_port()
    app = create_app(
        ServingConfig(model_id="t", max_seq=48, max_batch=4,
                      batch_mode="iter", batch_wait_ms=25.0),
        model=model, tokenizer=ByteTokenizer())
    server = serve(app, host="127.0.0.1", port=port, block=False)
    try:
        prompts = ["Hi", "Hello there", "abc", "xyzw"]
        want = {p: ref.post("/generate", json={
            "prompt": p, "max_new_tokens": 6, "mode": "greedy"}
        ).json()["generated"] for p in prompts}
        results = {}

        def post(p):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                json.dumps({"prompt": p, "max_new_tokens": 6,
                            "mode": "greedy"}).encode(),
                {"content-type": "application/json"})
            try:
                results[p] = json.loads(urllib.request.urlopen(
                    req, timeout=300).read())["generated"]
            except urllib.error.HTTPError as e:
                # a thread that dies leaves ``results`` short and says
                # nothing: keep what the server said
                results[p] = f"{e.code}: {e.read()[:300]!r}"

        threads = [th.Thread(target=post, args=(p,)) for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert results == want
        h = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30).read())
        assert h["batch_mode"] == "iter"
        assert h["iter_batch_stats"]["rows"] >= 4
    finally:
        server.shutdown()

    import pytest as _pytest
    from llm_sharding_demo_tpu.utils.config import ServingConfig as SC
    with _pytest.raises(ValueError, match="MAX_BATCH"):
        create_app(SC(model_id="t", max_seq=48, batch_mode="iter"),
                   model=model, tokenizer=ByteTokenizer())
    # PREFIX_CACHE now COMPOSES with iter mode (store-backed admission
    # prefills, ISSUE 1 satellite) — it must construct, while chunked
    # prefill still refuses loudly (different program structure)
    create_app(SC(model_id="t", max_seq=48, batch_mode="iter",
                  max_batch=4, prefix_cache=2),
               model=model, tokenizer=ByteTokenizer())
    with _pytest.raises(ValueError, match="admission"):
        create_app(SC(model_id="t", max_seq=48, batch_mode="iter",
                      max_batch=4, prefill_chunk=8),
                   model=model, tokenizer=ByteTokenizer())


def test_two_incompatible_arrivals_none_dropped(setup):
    """Regression (round-4 review): a request parked as the FIFO head
    must never be overwritten when a SECOND incompatible request
    arrives — both must complete."""
    engine, ib = setup
    rng = np.random.default_rng(13)
    pG = rng.integers(0, 211, size=(5,))
    pS1 = rng.integers(0, 211, size=(6,))
    pS2 = rng.integers(0, 211, size=(7,))
    s1 = SamplingConfig(mode="sample", temperature=0.7, top_k=20)
    s2 = SamplingConfig(mode="sample", temperature=0.9, top_k=10)
    k1, k2 = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
    wantG = engine.generate(pG[None, :], 60).tokens[0]
    want1 = engine.generate(pS1[None, :], 10, sampling=s1, key=k1).tokens[0]
    want2 = engine.generate(pS2[None, :], 10, sampling=s2, key=k2).tokens[0]
    resG, res1, res2 = _staggered(ib, [
        (pG, 60, 0.0, {}),
        (pS1, 10, 0.4, dict(sampling=s1, key=k1)),
        (pS2, 10, 0.6, dict(sampling=s2, key=k2))])
    assert resG is not None and res1 is not None and res2 is not None
    np.testing.assert_array_equal(resG.tokens[0], wantG)
    np.testing.assert_array_equal(res1.tokens[0], want1)
    np.testing.assert_array_equal(res2.tokens[0], want2)


def test_seed_failure_delivers_error_to_all_gathered_peers():
    """ADVICE r4 medium: a prefill failure during seeding must error-out
    EVERY gathered request — a peer whose done is never set blocks its
    caller forever (serving calls generate() with no timeout)."""
    _, _, engine = _setup()

    def boom(*a, **kw):
        raise RuntimeError("synthetic prefill OOM")

    engine._prefill = boom
    ib = IterBatchingEngine(engine, max_batch=4, seg_steps=8,
                            max_wait_ms=400.0)
    rng = np.random.default_rng(0)
    jobs = [(rng.integers(0, 211, size=(5,)), 8, 0.0, {}),
            (rng.integers(0, 211, size=(6,)), 8, 0.05, {}),
            (rng.integers(0, 211, size=(7,)), 8, 0.1, {})]
    errs = [None] * len(jobs)

    def run(i, p, n, delay, kw):
        time.sleep(delay)
        try:
            ib.generate(p, n, **kw)
        except Exception as e:  # noqa: BLE001
            errs[i] = e

    threads = [threading.Thread(target=run, args=(i, *j))
               for i, j in enumerate(jobs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    for i, e in enumerate(errs):
        assert isinstance(e, RuntimeError), (i, e)
        assert "synthetic prefill OOM" in str(e)


def test_admit_failure_delivers_error_to_popped_request():
    """ADVICE r4 medium, second path: _admit_one raising after the
    request left the queue but before it entered state.slots must error
    that request, not strand it."""
    _, _, engine = _setup()
    ib = IterBatchingEngine(engine, max_batch=4, seg_steps=8,
                            max_wait_ms=10.0)

    orig = IterBatchingEngine._admit_one

    def boom(self, state, req, slot, resume=None, reserved=None):
        raise RuntimeError("synthetic admit failure")

    IterBatchingEngine._admit_one = boom
    try:
        rng = np.random.default_rng(1)
        jobs = [(rng.integers(0, 211, size=(5,)), 120, 0.0, {}),
                (rng.integers(0, 211, size=(6,)), 8,
                 _after_segments(ib, ib.stats()["segments"], 1), {})]
        out = [None] * 2

        def run(i, p, n, trigger, kw):
            if callable(trigger):
                deadline = time.monotonic() + 120
                while not trigger() and time.monotonic() < deadline:
                    time.sleep(0.001)
            else:
                time.sleep(trigger)
            try:
                out[i] = ("ok", ib.generate(p, n, **kw))
            except Exception as e:  # noqa: BLE001
                out[i] = ("err", e)

        threads = [threading.Thread(target=run, args=(i, *j))
                   for i, j in enumerate(jobs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert out[0] is not None and out[1] is not None, out
        # the joiner hit the synthetic failure; nobody blocked forever
        kinds = {k for k, _ in out}
        assert "err" in kinds
        for k, v in out:
            if k == "err":
                assert "synthetic admit failure" in str(v)
    finally:
        IterBatchingEngine._admit_one = orig


def test_timeout_cancels_request_and_frees_slot():
    """ADVICE r4 low: generate(timeout=...) must CANCEL the request —
    the scheduler skips it at dequeue / frees its live slot — so
    repeated timeouts cannot accumulate dead decode work, and the
    scheduler stays healthy for later requests."""
    _, _, engine = _setup()
    ib = IterBatchingEngine(engine, max_batch=2, seg_steps=8,
                            max_wait_ms=5.0)
    rng = np.random.default_rng(2)
    p1 = rng.integers(0, 211, size=(5,))
    with pytest.raises(TimeoutError):
        ib.generate(p1, 120, timeout=1e-4)
    # the cancelled row frees at the next segment boundary; a fresh
    # request afterwards is served normally and promptly
    p2 = rng.integers(0, 211, size=(6,))
    res = ib.generate(p2, 8, timeout=120.0)
    assert res.new_tokens == 8
    # the timed-out request must not be counted as served
    assert ib.stats()["rows"] == 1


def test_right_sized_width_grows_on_join():
    """ADVICE r4: a lone request runs at width 1 (no ghost-row FLOPs —
    zero grows, zero joins); a mid-decode arrival grows the live batch
    instead of waiting, and both streams stay exact."""
    _, _, engine = _setup()
    ib = IterBatchingEngine(engine, max_batch=4, seg_steps=8,
                            max_wait_ms=5.0)
    rng = np.random.default_rng(21)
    p1 = rng.integers(0, 211, size=(5,))
    want1 = engine.generate(p1[None, :], 24).tokens[0]
    res1 = ib.generate(p1, 24)
    np.testing.assert_array_equal(res1.tokens[0], want1)
    solo = ib.stats()
    assert solo["grows"] == 0 and solo["joins"] == 0

    pA = rng.integers(0, 211, size=(5,))
    pB = rng.integers(0, 211, size=(7,))
    wantA = engine.generate(pA[None, :], 96).tokens[0]
    wantB = engine.generate(pB[None, :], 30).tokens[0]
    resA, resB = _staggered(ib, [
        (pA, 96, 0.0, {}),
        (pB, 30, _after_segments(ib, solo["segments"], 1), {})])
    after = ib.stats()
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    np.testing.assert_array_equal(resB.tokens[0], wantB)
    assert after["joins"] - solo["joins"] >= 1     # joined the live batch
    assert after["grows"] - solo["grows"] >= 1     # ...by growing width


# -- paged KV pool: paged segments, preemption, resume (ISSUE 5) -------------
#
# The pool-backed scheduler runs the SAME compiled segment programs on
# gathered views, so paged state is byte-equal to contiguous state by
# construction; these tests pin that end to end, plus the admission/
# preemption/resume machinery. Sampled byte-equality is pinned where
# this container's environment supports it: width-1 paged-vs-contiguous
# here, and the engine-level recompute-resume mechanism in
# tests/test_kv_pool.py (width>=2 sampled-vs-solo is a PRE-EXISTING
# environment failure — see test_sampled_joiner_stream_byte_equal_solo
# and test_batcher's batched-sample test, failing at the seed).


def _pool_setup(max_seq=200, num_blocks=25, block_size=8, watermark=1.0,
                **kw):
    from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
    cfg, params, engine = _setup(max_seq=max_seq)
    pool = KVBlockPool.for_engine(engine, num_blocks=num_blocks,
                                  block_size=block_size,
                                  watermark=watermark)
    ib = IterBatchingEngine(engine, max_batch=4, seg_steps=8,
                            max_wait_ms=kw.pop("max_wait_ms", 300.0),
                            pool=pool, **kw)
    return engine, pool, ib


def test_pool_paged_rows_byte_equal_solo_greedy_with_join():
    """Paged storage under the scheduler: staggered greedy arrivals
    (mid-flight join included) equal their solo runs, and every block
    returns to the pool at retirement."""
    engine, pool, ib = _pool_setup(num_blocks=64, max_wait_ms=50.0)
    rng = np.random.default_rng(41)
    pA = rng.integers(0, 211, size=(5,))
    pB = rng.integers(0, 211, size=(9,))
    wantA = engine.generate(pA[None, :], 96).tokens[0]
    wantB = engine.generate(pB[None, :], 40).tokens[0]
    before = ib.stats()
    resA, resB = _staggered(ib, [
        (pA, 96, 0.0, {}),
        (pB, 40, _after_segments(ib, before["segments"], 1), {})])
    after = ib.stats()
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    np.testing.assert_array_equal(resB.tokens[0], wantB)
    assert after["joins"] - before["joins"] >= 1
    assert after["preemptions"] == 0            # pool was big enough
    assert pool.allocator.stats().blocks_in_use == 0


def test_pool_preempts_lowest_priority_and_resumes_byte_identical():
    """THE preemption bar: two long rows oversubscribe a deliberately
    tiny pool; growth exhausts it mid-decode, the YOUNGER row is
    parked (its blocks freed) and later resumed by recompute — both
    final streams equal their un-preempted solo runs exactly."""
    engine, pool, ib = _pool_setup()     # 25 blocks = 1 full row
    rng = np.random.default_rng(42)
    pA = rng.integers(0, 211, size=(5,))
    pB = rng.integers(0, 211, size=(8,))
    wantA = engine.generate(pA[None, :], 96).tokens[0]
    wantB = engine.generate(pB[None, :], 110).tokens[0]
    resA, resB = _staggered(ib, [(pA, 96, 0.0, {}), (pB, 110, 0.0, {})])
    st = ib.stats()
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    np.testing.assert_array_equal(resB.tokens[0], wantB)
    assert st["preemptions"] >= 1 and st["resumes"] >= 1
    assert st["parked"] == 0
    assert pool.allocator.stats().blocks_in_use == 0


def test_midflight_join_during_preemption_is_exact():
    """A request arriving WHILE a row is parked still joins the live
    batch (the parked row resumes later, oldest-first) — all three
    streams byte-equal solo, and the preempted row's trace carries the
    pressure labels the flight recorder surfaces."""
    from llm_sharding_demo_tpu.utils import tracing
    engine, pool, ib = _pool_setup()
    rng = np.random.default_rng(43)
    pA = rng.integers(0, 211, size=(5,))
    pB = rng.integers(0, 211, size=(8,))
    pC = rng.integers(0, 211, size=(6,))
    wantA = engine.generate(pA[None, :], 96).tokens[0]
    wantB = engine.generate(pB[None, :], 110).tokens[0]
    wantC = engine.generate(pC[None, :], 16).tokens[0]
    traceB = tracing.RequestTrace("req-b", mode="greedy")

    def run_b():
        with tracing.use_trace(traceB):
            return ib.generate(pB, 110, timeout=300)

    resB_box = [None]

    def run_b_thread():
        resB_box[0] = run_b()

    import threading as _th
    tB = _th.Thread(target=run_b_thread)
    resA, resC = [None], [None]

    def run_a():
        resA[0] = ib.generate(pA, 96, timeout=300)

    def run_c():
        deadline = time.monotonic() + 120
        while ib.stats()["preemptions"] < 1 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert ib.stats()["preemptions"] >= 1, "preemption never happened"
        resC[0] = ib.generate(pC, 16, timeout=300)

    tA = _th.Thread(target=run_a)
    tC = _th.Thread(target=run_c)
    tA.start(); tB.start(); tC.start()
    for t in (tA, tB, tC):
        t.join(timeout=300)
    st = ib.stats()
    np.testing.assert_array_equal(resA[0].tokens[0], wantA)
    np.testing.assert_array_equal(resB_box[0].tokens[0], wantB)
    np.testing.assert_array_equal(resC[0].tokens[0], wantC)
    assert st["preemptions"] >= 1 and st["resumes"] >= 1
    # the preempted row's trace explains the pressure-induced latency:
    # a "preempted" span plus the preempted label (B was the youngest
    # of the two long rows, so it was the victim)
    assert traceB.labels.get("preempted", 0) >= 1
    assert any(s.name == "preempted" for s in traceB.find_all("preempted"))
    decode_spans = traceB.find_all("decode")
    assert decode_spans and all("blocks" in s.labels
                                for s in decode_spans)
    assert pool.allocator.stats().blocks_in_use == 0


def test_pool_sampled_width1_paged_equals_contiguous():
    """Paged vs contiguous byte-equality for seeded sampling under the
    scheduler, at the width this environment's sampled oracle supports
    (width-1; the width>=2 sampled-vs-solo gap is a pre-existing env
    failure — the paged path reproduces the contiguous scheduler's
    stream EXACTLY either way)."""
    engine, pool, ib_pool = _pool_setup(max_wait_ms=5.0)
    ib_plain = IterBatchingEngine(engine, max_batch=4, seg_steps=8,
                                  max_wait_ms=5.0)
    rng = np.random.default_rng(44)
    p = rng.integers(0, 211, size=(5,))
    s = SamplingConfig(mode="sample", temperature=0.7, top_k=30)
    key = jax.random.PRNGKey(11)
    want = ib_plain.generate(p, 96, sampling=s, key=key,
                             timeout=300).tokens[0]
    got = ib_pool.generate(p, 96, sampling=s, key=key,
                           timeout=300).tokens[0]
    np.testing.assert_array_equal(got, want)
    assert pool.allocator.stats().blocks_in_use == 0


def test_spec_pool_segments_byte_equal_solo_greedy():
    """Speculative draft-verify segments on paged storage: the spec
    segment's full-row roll hands off through the pool's whole-row
    scatter (spec_decode.SEG_REWRITES_FULL_CACHE), and streams stay
    byte-equal to solo SpecDecodeEngine runs."""
    from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=256, n_embd=32,
                          n_layer=2, n_head=4)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    spec = SpecDecodeEngine(params, cfg, max_seq=200, draft_len=5)
    pool = KVBlockPool.for_engine(spec.plain, num_blocks=32, block_size=8,
                                  watermark=1.0)
    ib = IterBatchingEngine(spec.plain, max_batch=4, seg_steps=12,
                            max_wait_ms=50.0, spec=spec, pool=pool)
    pA = np.tile(np.asarray([5, 17, 3, 42], np.int32), 6)  # draft-friendly
    want = spec.generate(pA, 96).tokens[0]
    res = ib.generate(pA, 96, sampling=SamplingConfig(spec=True),
                      timeout=300)
    np.testing.assert_array_equal(res.tokens[0], want)
    assert ib.stats()["spec_segments"] >= 2
    assert pool.allocator.stats().blocks_in_use == 0


def test_spec_rows_preempt_and_resume_byte_identical():
    """Preemption composes with speculation: spec rows park with their
    verify-state snapshot (emitted stream from the token buffer) and
    resume by recompute through the SEED path (extended ids rebuild the
    buffer lane; the chain key snapshot restores sampled chains) —
    streams stay byte-equal to solo SpecDecodeEngine runs across many
    park/resume cycles."""
    from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=256, n_embd=32,
                          n_layer=2, n_head=4)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    spec = SpecDecodeEngine(params, cfg, max_seq=200, draft_len=5)
    pA = np.tile(np.asarray([5, 17, 3, 42], np.int32), 6)
    pB = np.tile(np.asarray([9, 4, 33, 8], np.int32), 6)
    wantA = spec.generate(pA, 90).tokens[0]
    wantB = spec.generate(pB, 90).tokens[0]
    pool = KVBlockPool.for_engine(spec.plain, num_blocks=25, block_size=8,
                                  watermark=1.0)
    ib = IterBatchingEngine(spec.plain, max_batch=4, seg_steps=12,
                            max_wait_ms=300.0, spec=spec, pool=pool)
    res = [None, None]

    def run(i, p):
        res[i] = ib.generate(p, 90, sampling=SamplingConfig(spec=True),
                             timeout=300)

    ts = [threading.Thread(target=run, args=(0, pA)),
          threading.Thread(target=run, args=(1, pB))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=400)
    st = ib.stats()
    np.testing.assert_array_equal(res[0].tokens[0], wantA)
    np.testing.assert_array_equal(res[1].tokens[0], wantB)
    assert st["preemptions"] >= 1 and st["resumes"] >= 1
    assert pool.allocator.stats().blocks_in_use == 0


def test_joiner_walks_all_three_strides_through_the_pooled_store():
    """A joiner seven whole chunks long (strides of 4, 2 and 1, then
    its tail) joins a live batch through the pool-backed store, llama
    widths (grouped-query heads): both streams equal the solo engine's,
    and the store's counters show four calls, not eight."""
    from llm_sharding_demo_tpu.models import llama
    from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
    from llm_sharding_demo_tpu.runtime.prefix_cache import (
        PrefixCachingEngine)
    cfg = llama.LlamaConfig(vocab_size=211, n_positions=256, n_embd=64,
                            n_layer=2, n_head=4, n_kv_head=2,
                            intermediate_size=96)
    params = jax.tree.map(lambda x: x * 4.0,
                          llama.init_params(cfg, jax.random.PRNGKey(3)))
    engine = DecodeEngine(params, cfg, max_seq=200)
    pool = KVBlockPool.for_engine(engine, num_blocks=96, block_size=8)
    prefix = PrefixCachingEngine(engine, capacity=4, chunk=8, pool=pool)
    ib = IterBatchingEngine(engine, max_batch=4, seg_steps=8,
                            max_wait_ms=50.0, prefix=prefix, pool=pool)
    rng = np.random.default_rng(28)
    pA = rng.integers(0, 211, size=(64,))   # seeds: deep enough to admit pB
    pB = rng.integers(0, 211, size=(61,))   # 7 chunks of 8 + a tail of 5
    wantA = engine.generate(pA[None, :], 60).tokens[0]
    wantB = engine.generate(pB[None, :], 20).tokens[0]
    before, h0 = ib.stats(), prefix.stats()
    resA, resB = _staggered(ib, [
        (pA, 60, 0.0, {}),
        (pB, 20, _after_segments(ib, before["segments"], 1), {})])
    h1 = prefix.stats()
    np.testing.assert_array_equal(resA.tokens[0], wantA)
    np.testing.assert_array_equal(resB.tokens[0], wantB)
    assert ib.stats()["joins"] - before["joins"] >= 1
    assert h1["extend_calls"] - h0["extend_calls"] == 4
    assert h1["extend_tokens"] - h0["extend_tokens"] == 61
