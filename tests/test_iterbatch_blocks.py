"""A step that yields a block: the iter scheduler (runtime.iterbatch)
over a family that generates by rounds (``Family.block_options``).

A decode call is ROUNDS; a round yields a whole block of ``L`` positions
a row whatever the weights, so ``emitted``, ``depth`` and the blocks
written back move by whole blocks and the host still knows a call's
yield a call ahead; only the forwards are data, and they come back on
the ``decode`` span when the call is ready (``steps``, ``fixed_at``).
And every family WITHOUT ``block_options`` lowers its prefill and decode
programs to the text the parent commit lowers them to.

Tiny sizes on the CPU, float32, weights from the reference's ``init``;
every row is compared with the reference's own loop (whole passes, no
cache, no batch).
"""

import dataclasses
import hashlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar_moe as ref_mod
from llm_sharding_demo_tpu.models import latent_moe, llama, sdar_moe
from llm_sharding_demo_tpu.ops.decode_attention import BLOCK_S
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine, SamplingConfig
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine
from llm_sharding_demo_tpu.utils import tracing


@pytest.fixture(autouse=True, scope="module")
def highest():
    """Both sides at ``highest``, in this file alone (a test process
    runs other files too)."""
    with jax.default_matmul_precision("highest"):
        yield


REF = ref_mod.sdar_moe
SIZES = {"hidden_size": 64, "vocab_size": 256, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16,
         "moe_intermediate_size": 32, "num_experts": 4,
         "published_num_experts": 8, "first_expert": 0,
         "num_experts_per_tok": 2, "norm_topk_prob": True,
         "num_hidden_layers": 3, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
         "block_length": 4, "denoising_steps": 2,
         "confidence_threshold": 0.9,
         "remasking": "low_confidence_dynamic", "mask_token_id": 255}


@pytest.fixture(scope="module")
def served():
    """Engine, pool, store and scheduler as ``serving.app`` builds them,
    every boundary of the scheduler recorded."""
    cfg = dataclasses.replace(sdar_moe.CONFIGS["sdar-moe-tiny"],
                              denoising_steps=2)
    params = REF.init(SIZES, 7, jnp.float32)
    engine = DecodeEngine(params, cfg, max_seq=128)
    pool = KVBlockPool.for_engine(engine, num_blocks=64, block_size=16)
    store = PrefixCachingEngine(engine, capacity=4, chunk=16, pool=pool)
    ib = IterBatchingEngine(engine, max_batch=4, seg_steps=8, max_wait_ms=0,
                            prefix=store, pool=pool)
    seen, inner = [], ib._advance

    def advance(state):
        before = (state.depth, [None if s is None else (s.pad, s.emitted)
                                for s in state.slots])
        out = inner(state)
        seen.append(before + (state.depth,))
        return out
    ib._advance = advance
    return types_ns(ib=ib, params=params, seen=seen, pool=pool)


def types_ns(**kw):
    import types
    return types.SimpleNamespace(**kw)


def prompt_of(n, seed):
    return np.random.RandomState(seed).randint(0, 255, n).tolist()


def ask(ib, jobs, gap_s=0.25):
    """``(prompt, n)`` jobs a moment apart, each under a trace of its
    own: ``[(GenerateResult, its decode spans' labels)]``."""
    out = [None] * len(jobs)

    def go(i):
        trace = tracing.RequestTrace(f"r{i}")
        with tracing.use_trace(trace):
            got = ib.generate(np.asarray(jobs[i][0]), jobs[i][1])
        trace.settle()
        out[i] = (got, [s.labels for s in trace.find_all("decode")],
                  [s.labels for s in trace.find_all("prefill")])

    threads = []
    for i in range(len(jobs)):
        threads.append(threading.Thread(target=go, args=(i,)))
        threads[-1].start()
        time.sleep(gap_s)
    for t in threads:
        t.join()
    return out


def test_a_joiner_meets_a_batch_mid_answer_and_both_equal_their_solo_runs(
        served):
    """Seeds and joiners, prompts on and off a block boundary, budgets
    inside a block, two prompts behind one stored prefix: every row's
    tokens and schedule are the published loop's, and what the spans and
    the counters say adds up."""
    ib, params = served.ib, served.params
    shared = prompt_of(32, seed=99)
    jobs = [(prompt_of(8, 1), 30), (prompt_of(9, 2), 7),
            (prompt_of(11, 3), 6), (prompt_of(13, 4), 10),
            (shared + prompt_of(3, 5), 6), (shared + prompt_of(5, 6), 6)]
    before = ib.stats()
    got = ask(ib, jobs)
    after = ib.stats()
    joined = 0
    for (prompt, n), (res, decodes, prefills) in zip(jobs, got):
        want = REF.generate(params, SIZES, prompt, n)
        assert res.tokens[0, len(prompt):].tolist() == want["tokens"]
        assert res.fixed_at[0].tolist() == want["fixed_at"]
        assert res.new_tokens == n
        # the spans: a call's rounds, the row's own yield, the forwards
        # the call ran, and for each token the forward that fixed it
        assert sum(d["tokens"] for d in decodes) == n
        assert sum((d["fixed_at"] for d in decodes), []) == want["fixed_at"]
        for d in decodes:
            # the floor's yield: two denoise forwards and a commit a
            # round, one and a commit where a prompt left one position
            assert 2 * d["rounds"] <= d["steps"] <= 3 * d["rounds"]
            assert len(d["fixed_at"]) == d["tokens"] <= 4 * d["rounds"]
        # a first round's block holds what the prompt left over
        assert decodes[0]["tokens"] == min(
            4 * decodes[0]["rounds"] - len(prompt) % 4, n)
        joined += prefills[0].get("kind") != "seed"
    assert joined >= 1
    moved = {k: after[k] - before[k] for k in after
             if isinstance(after[k], (int, float)) and after[k] != before[k]}
    assert (2 * moved["block.rounds"] < moved["block.forwards"]
            <= 3 * moved["block.rounds"])
    assert moved["block.commits"] == moved["block.rounds"]
    # a row's yield a forward: 4 / 3 at the floor, less where a prompt
    # fills part of a first block or a budget ends inside a last one
    assert 1.0 < (moved["block.tokens_fixed"]
                  / moved["block.row_forwards"]) <= 4 / 3
    assert moved["rows"] == len(jobs) and moved["joins"] == joined
    # every boundary: depth and pads are whole blocks, a call moves the
    # depth by whole blocks, and a row's grid meets the batch's depth
    rounds = 0
    for depth, slots, depth_after in served.seen:
        assert depth % 4 == 0 and (depth_after - depth) % 4 == 0
        assert 4 <= depth_after - depth <= 8
        rounds += (depth_after - depth) // 4
        for s in slots:
            assert s is None or (depth - s[0]) % 4 == 0
    assert rounds == moved["block.rounds"]
    # blocks written back: the columns a call's positions span, a live row
    assert moved["blocks_written_back"] > 0
    assert moved["calls_resident"] == moved["segments"]
    # nothing of the pool is left behind
    assert served.pool.stats()["blocks_in_use"] <= 4 * 4


def test_a_budget_that_ends_inside_a_block_retires_with_the_right_count(
        served):
    ib, params = served.ib, served.params
    for n in (1, 2, 5):
        prompt = prompt_of(10, seed=20 + n)
        (res, decodes, _), = ask(ib, [(prompt, n)])
        want = REF.generate(params, SIZES, prompt, n)
        assert res.tokens.shape == (1, 10 + n)
        assert res.tokens[0, 10:].tolist() == want["tokens"]
        assert sum(d["tokens"] for d in decodes) == n
        assert sum(d["rounds"] for d in decodes) == -(-(2 + n) // 4)


# -- under the decode kernels: a round's forwards stream the rows' spans ----

KERNEL_SIZES = dict(SIZES, head_dim=64)    # fused rows of 128 lanes


def test_a_joiner_under_the_decode_kernels_still_equals_its_solo_run():
    """``decode_kernel="interpret"``: every forward of a round is
    ``ops.block_decode``'s kernel a layer. A joiner meets a batch
    mid-answer (another pad, a grown width, empty lanes), every row is
    the published loop's, and the stream counter has counted the call's
    FORWARDS: the cache is one streamed block, so a live row streams one
    block a forward (the device's own ``block_row_forwards``) and the
    rectangle is the width's."""
    cfg = dataclasses.replace(sdar_moe.CONFIGS["sdar-moe-tiny"],
                              denoising_steps=2, head_dim=64)
    params = REF.init(KERNEL_SIZES, 7, jnp.float32)
    engine = DecodeEngine(params, cfg, max_seq=256,
                          decode_kernel="interpret")
    assert engine._decode_kernel == "interpret"
    pool = KVBlockPool.for_engine(engine, num_blocks=64, block_size=16)
    ib = IterBatchingEngine(engine, max_batch=4, seg_steps=8, max_wait_ms=0,
                            pool=pool)
    widths, inner = [], ib._advance

    def advance(state):
        widths.append(len(state.slots))
        return inner(state)
    ib._advance = advance
    jobs = [(prompt_of(8, 1), 40), (prompt_of(13, 2), 9),
            (prompt_of(22, 3), 6)]
    got = ask(ib, jobs, gap_s=1.0)
    for (prompt, n), (res, decodes, prefills) in zip(jobs, got):
        want = REF.generate(params, KERNEL_SIZES, prompt, n)
        assert res.tokens[0, len(prompt):].tolist() == want["tokens"]
        assert res.fixed_at[0].tolist() == want["fixed_at"]
    assert sum(p[0].get("kind") != "seed" for _, _, p in got) >= 1
    st = ib.stats()
    assert st["attn_positions_streamed"] == BLOCK_S * st["block.row_forwards"]
    assert 0 < st["attn_positions_streamed"] < st["attn_positions_rect"]
    assert st["attn_positions_rect"] % BLOCK_S == 0
    # every call's forwards times its width: between the narrowest and
    # the widest width over all forwards
    assert (min(widths) * st["block.forwards"]
            <= st["attn_positions_rect"] // BLOCK_S
            <= max(widths) * st["block.forwards"])
    assert max(widths) > 1


@pytest.mark.parametrize("depth,rounds,forwards,mine,whole", [
    # depth 508: both rounds (508, 512) stream two blocks of 256; the
    # row padded to 300 starts in the second
    (508, 2, 6, 6 * (2 + 1), 6 * 4 * 2),
    # depth 512: the second round, at 516, streams a third block
    (512, 2, 6, 3 * (2 + 1) + 3 * (3 + 2), 3 * 4 * 2 + 3 * 4 * 3),
    # a lone round, both rows inside the first block
    (40, 1, 3, 3 * (1 + 1), 3 * 4 * 1)],
    ids=["inside-a-block", "across-a-blocks-edge", "one-round"])
def test_the_stream_counter_counts_a_calls_forwards(served, depth, rounds,
                                                    forwards, mine, whole):
    """Two rows of known pads in a width of four: streamed and
    rectangle to the digit, from the forwards the call reports; the
    empty lanes add to the rectangle alone."""
    ib = served.ib
    state = types_ns(slots=[types_ns(pad=0), None,
                            types_ns(pad=300 if depth > 300 else 8), None])
    before = ib.stats()
    count = ib._count_stream(state, depth, rounds)
    assert ib.stats()["attn_positions_rect"] == before["attn_positions_rect"]
    count(forwards)
    after = ib.stats()
    assert (after["attn_positions_streamed"]
            - before["attn_positions_streamed"]) == BLOCK_S * mine
    assert (after["attn_positions_rect"]
            - before["attn_positions_rect"]) == BLOCK_S * whole
    assert after["state_lanes_compiled"] == before["state_lanes_compiled"]


def test_what_the_scheduler_refuses(served):
    ib = served.ib
    with pytest.raises(ValueError, match="at least one block"):
        ib.generate(np.asarray([1, 2, 3]), 4)
    with pytest.raises(ValueError, match="chooses greedily"):
        ib.generate(np.asarray(prompt_of(8, 0)), 4,
                    sampling=SamplingConfig(mode="sample"),
                    key=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="exceeds max_seq"):
        ib.generate(np.asarray(prompt_of(100, 0)), 29)
    with pytest.raises(ValueError, match="whole blocks"):
        IterBatchingEngine(ib.engine, max_batch=2, seg_steps=6)


# -- every family whose step yields a token runs the programs it ran -------

# sha256 of the lowered text of the two programs at these shapes on the
# PARENT commit (0019d8d; ``PYTHONPATH=<parent checkout>``, this file's
# ``lowered`` function)
PARENT = {
    ("llama", "prefill"):
        "f6a33422293f656a1183f8193cc8553b3728a80591e62ff7b94b51a36aa4ff2b",
    ("llama", "decode"):
        "8b1641311b688a9ec2a70c0907e385c1b1c4a03973633893ebfd6288e3334465",
    ("latent_moe", "prefill"):
        "947f8b07f6c69061f966e498068f56096de2ebb1fd1d44bf7c9545cca7549b83",
    ("latent_moe", "decode"):
        "6f06244ba8e3ef00914d063db5047892f51d69d79aa64280cb9cf6a4c8355148",
}
FAMILIES = {"llama": (llama, llama.CONFIGS["llama-tiny"]),
            "latent_moe": (latent_moe,
                           latent_moe.CONFIGS["latent-moe-tiny"])}


def lowered(name, program):
    with jax.default_matmul_precision(None):
        return _lowered(name, program)


def _lowered(name, program):
    module, cfg = FAMILIES[name]
    shapes = jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    eng = DecodeEngine(params, cfg, max_seq=64, decode_kernel="xla")
    assert eng.block is None
    if program == "prefill":
        return jax.jit(eng._prefill_impl).lower(
            eng.params, jnp.zeros((2, 16), jnp.int32),
            jnp.zeros((2,), jnp.int32)).as_text()
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         jax.eval_shape(lambda: eng._fresh_cache(2)))
    return jax.jit(eng._decode_seg_impl,
                   static_argnames=("sampling", "window")).lower(
        eng.params, jnp.zeros((2,), jnp.int32), cache,
        jnp.zeros((2,), jnp.int32), jnp.zeros((8, 2, 2), jnp.uint32),
        np.int32(3), sampling=SamplingConfig(), window=None).as_text()


@pytest.mark.parametrize("name,program", sorted(PARENT))
def test_a_family_without_blocks_lowers_to_the_parents_program(name, program):
    text = lowered(name, program)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT[name, program]


def test_a_scheduler_without_blocks_counts_steps_as_it_did():
    """The scheduler's arithmetic at a unit of one token: a call's steps
    on its span, no ``rounds``, the first token from the prefill."""
    cfg = llama.CONFIGS["llama-tiny"]
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ib = IterBatchingEngine(DecodeEngine(params, cfg, max_seq=64),
                            max_batch=2, seg_steps=8, max_wait_ms=0)
    (res, decodes, _), = ask(ib, [(prompt_of(9, 1), 12)])
    assert res.new_tokens == 12 and res.fixed_at is None
    assert [d["steps"] for d in decodes] == [8, 3]
    assert all("rounds" not in d and "fixed_at" not in d for d in decodes)
    assert not any(k.startswith("block.") for k in ib.stats())
