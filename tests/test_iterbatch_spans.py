"""A lane's span in the iter scheduler (runtime.iterbatch): what the
decode kernel streams is each row's own ``[pad, depth)``, so a lane WITH
a request keeps its pad to the digit and a lane WITHOUT one (a ghost of
the seed's width or of a grow, a retired or parked row's) carries an
EMPTY span, a pad no depth reaches. And the counter that says how often
that spares a read: ``attn_positions_streamed`` over
``attn_positions_rect``; for a family whose rows hold a state in the
slab, ``state_lanes_streamed`` over ``state_lanes_compiled``.

Tiny sizes on the CPU. The state is read on the worker's own thread, at
every entry into ``_advance`` (a boundary: whatever seeded, grew, joined
or retired since the last call has happened).
"""

import threading
import time

import jax
import numpy as np

from llm_sharding_demo_tpu.models import gpt2, hybrid_ssm
from llm_sharding_demo_tpu.ops.decode_attention import BLOCK_S
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool


def _engine(max_seq, n_embd=32, n_head=4, **kw):
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=1024, n_embd=n_embd,
                          n_layer=2, n_head=n_head)
    params = jax.tree.map(lambda x: x * 8.0,
                          gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    return DecodeEngine(params, cfg, max_seq=max_seq, **kw)


def _watched(engine, **kw):
    """A scheduler whose every boundary is recorded: ``(depth, pad_j on
    the host, each lane's slot pad or None, each lane's prompt length)``."""
    ib = IterBatchingEngine(engine, max_batch=4, seg_steps=8,
                            max_wait_ms=5.0, **kw)
    seen, inner = [], ib._advance

    def advance(state):
        seen.append((state.depth, np.asarray(state.pad_j).tolist(),
                     [None if s is None else s.pad for s in state.slots],
                     [None if s is None else s.plen for s in state.slots]))
        return inner(state)
    ib._advance = advance
    return ib, seen


def _staggered(ib, jobs):
    """jobs: (prompt, steps, trigger); a trigger is polled until true."""
    res = [None] * len(jobs)

    def run(i, p, n, trigger):
        deadline = time.monotonic() + 120
        while not trigger() and time.monotonic() < deadline:
            time.sleep(0.001)
        res[i] = ib.generate(p, n)
    threads = [threading.Thread(target=run, args=(i, *job))
               for i, job in enumerate(jobs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    return res


def _check_lanes(ib, seen):
    """At every boundary: a lane with a request holds its exact pad
    (``depth`` at its admission less its prompt), one without the empty
    span; both kinds were met."""
    kinds = set()
    joined_at = {}
    for depth, pad_j, slot_pads, plens in seen:
        for i, (on_device, pad, plen) in enumerate(
                zip(pad_j, slot_pads, plens)):
            if pad is None:
                assert on_device == ib._no_span >= ib.engine.max_seq
                kinds.add("empty")
            else:
                assert on_device == pad < depth
                # the pad a row was admitted with never moves
                assert joined_at.setdefault((i, plen), pad) == pad
                kinds.add("live")
    assert kinds == {"empty", "live"}


def test_ghost_and_retired_lanes_carry_an_empty_span():
    """A grow's ghost lanes, a retired row's lane, and a later joiner
    into that lane: empty, empty, its exact pad; the streams are the
    solo runs'."""
    engine = _engine(200)
    ib, seen = _watched(engine)
    rng = np.random.default_rng(4)
    pA, pB, pC = (rng.integers(0, 211, size=(n,)) for n in (20, 5, 7))
    want = [engine.generate(p[None, :], n).tokens[0]
            for p, n in ((pA, 150), (pB, 10), (pC, 12))]
    base = ib.stats()

    def segments(k):
        return lambda: ib.stats()["segments"] >= base["segments"] + k
    rows = lambda: ib.stats()["rows"] - base["rows"]   # noqa: E731
    got = _staggered(ib, [(pA, 150, lambda: True), (pB, 10, segments(1)),
                          (pC, 12, lambda: rows() >= 1)])
    for r, w in zip(got, want):
        np.testing.assert_array_equal(r.tokens[0], w)
    after = ib.stats()
    assert after["grows"] - base["grows"] >= 1
    assert after["joins"] - base["joins"] == 2
    _check_lanes(ib, seen)
    # B's lane, empty after its retirement, took C at C's own pad
    lane_b = next(i for d, pj, sp, pl in seen
                  for i, n in enumerate(pl) if n == len(pB))
    wide = [b for b in seen if len(b[1]) > lane_b]
    c = [(pj[lane_b], sp[lane_b]) for d, pj, sp, pl in wide
         if pl[lane_b] == len(pC)]
    assert c and all(on_dev == pad for on_dev, pad in c)
    between = [pj[lane_b] for d, pj, sp, pl in wide if pl[lane_b] is None]
    assert between and set(between) == {ib._no_span}


def test_a_seeds_ghost_lanes_and_a_pooled_batch():
    """Three requests seed a batch of width 4: the fourth lane is a
    ghost from the start. Pooled, so a grow's lanes come from a gather
    and a parked row's lane empties the same way."""
    engine = _engine(200)
    pool = KVBlockPool.for_engine(engine, num_blocks=60, block_size=8)
    ib, seen = _watched(engine, pool=pool)
    ib.max_wait_ms = 300.0              # gather all three into the seed
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 211, size=(n,)) for n in (9, 5, 12)]
    want = [engine.generate(p[None, :], 20).tokens[0] for p in prompts]
    got = _staggered(ib, [(p, 20, lambda: True) for p in prompts])
    for r, w in zip(got, want):
        np.testing.assert_array_equal(r.tokens[0], w)
    assert ib.stats()["batches"] == 1 and len(seen[0][1]) == 4
    _check_lanes(ib, seen)


def test_stream_counters_one_long_row_and_three_short():
    """One row 600 positions deep and three joiners of a few positions,
    through the kernel itself (interpreted): the joiners' spans start in
    the long row's third block and the grow's lanes are empty, so well
    under half the rectangle is streamed; the streams are the solo XLA
    engine's."""
    xla = _engine(900, n_embd=64, n_head=1, decode_kernel="xla")
    engine = _engine(900, n_embd=64, n_head=1, decode_kernel="interpret")
    assert engine._decode_kernel == "interpret"
    ib, seen = _watched(engine)
    rng = np.random.default_rng(6)
    long = rng.integers(0, 211, size=(600,))
    short = [rng.integers(0, 211, size=(n,)) for n in (5, 9, 6)]
    want = [xla.generate(long[None, :], 56).tokens[0]] + [
        xla.generate(p[None, :], 10).tokens[0] for p in short]
    base = ib.stats()
    assert base["attn_positions_streamed"] == base["attn_positions_rect"] == 0
    joined = lambda: ib.stats()["segments"] >= 1      # noqa: E731
    got = _staggered(ib, [(long, 56, lambda: True)]
                     + [(p, 10, joined) for p in short])
    for r, w in zip(got, want):
        np.testing.assert_array_equal(r.tokens[0], w)
    _check_lanes(ib, seen)
    st = ib.stats()
    assert st["joins"] == 3 and st["grows"] == 2
    streamed, rect = st["attn_positions_streamed"], st["attn_positions_rect"]
    assert isinstance(streamed, int) and isinstance(rect, int)
    assert 0 < streamed < 0.5 * rect
    assert streamed % BLOCK_S == 0 and rect % BLOCK_S == 0
    # at least the long row's own span, every step it decoded
    assert streamed >= 55 * 3 * BLOCK_S


def test_a_lone_row_without_pad_streams_its_rectangle():
    engine = _engine(200)
    ib, seen = _watched(engine)
    p = np.random.default_rng(7).integers(0, 211, size=(16,))
    ib.generate(p, 20)
    assert [pads for _, pads, _, _ in seen] == [[0]] * len(seen)
    st = ib.stats()
    assert st["attn_positions_streamed"] == st["attn_positions_rect"] \
        == 19 * BLOCK_S


def test_state_lane_counters_after_two_rows_retire():
    """A slab batch of width 4 (the state-space family, every layer a
    row's state): while all four rows live the state kernels stream
    every compiled lane; over the calls after two rows have retired,
    half of them. The streams are the solo runs'."""
    cfg = hybrid_ssm.CONFIGS["hybrid-ssm-tiny"]
    engine = DecodeEngine(
        hybrid_ssm.init_params(cfg, jax.random.PRNGKey(0)), cfg, max_seq=128)
    pool = KVBlockPool.for_engine(engine, num_blocks=40, block_size=8,
                                  state_slots=1)
    ib = IterBatchingEngine(engine, max_batch=4, seg_steps=8,
                            max_wait_ms=300.0, pool=pool)
    marks, inner = [], ib._advance

    def advance(state):
        marks.append((sum(s is not None for s in state.slots),
                      len(state.slots), ib.stats()))
        return inner(state)
    ib._advance = advance
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 320, size=(n,)) for n in (9, 5, 12, 7)]
    news = [10, 40, 10, 40]
    want = [engine.generate(p[None, :], n).tokens[0]
            for p, n in zip(prompts, news)]
    got = _staggered(ib, [(p, n, lambda: True)
                          for p, n in zip(prompts, news)])
    for r, w in zip(got, want):
        np.testing.assert_array_equal(r.tokens[0], w)
    assert ib.stats()["batches"] == 1
    assert {width for _, width, _ in marks} == {4}
    assert [live for live, _, _ in marks][0] == 4
    full = next(st for live, _, st in marks if live == 2)
    assert full["state_lanes_streamed"] == full["state_lanes_compiled"] > 0
    end = ib.stats()
    streamed = end["state_lanes_streamed"] - full["state_lanes_streamed"]
    compiled = end["state_lanes_compiled"] - full["state_lanes_compiled"]
    assert isinstance(streamed, int) and compiled == 4 * 30
    assert streamed / compiled == 0.5


def test_a_pooled_batch_without_a_slab_counts_no_state_lanes():
    engine = _engine(200)
    pool = KVBlockPool.for_engine(engine, num_blocks=60, block_size=8)
    assert pool.slab is None
    ib = IterBatchingEngine(engine, max_batch=4, seg_steps=8,
                            max_wait_ms=5.0, pool=pool)
    ib.generate(np.random.default_rng(9).integers(0, 211, size=(16,)), 20)
    st = ib.stats()
    assert st["attn_positions_rect"] > 0
    assert st["state_lanes_streamed"] == st["state_lanes_compiled"] == 0
