"""A decode call ends where the first live row's budget ends
(runtime.iterbatch ``_advance``), its step count an operand of the one
program a width has (runtime.engine ``_decode_seg_impl``).

Tiny sizes on the CPU, float32, over the four families the cells serve
(``llama``, ``latent_moe``, ``gdn_moe``, ``window_moe``: each with its
own cache pytree in the counted loop's carry). What is held: a row cut
short is its solo stream byte for byte, a row pays exactly the steps
between its tokens, a call's length mints no program, the counted form
at a whole call is the scan form bit for bit, and the routing counters
a cut call hands back are those of its steps.

And what a pooled batch keeps between its calls: its working cache
stays on the device from seed to end, no call gathers, the write-back
behind a call rewrites the blocks that call wrote and no other, at
every boundary the pool holds what the resident cache holds, and the
one gather left is a grow's.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import (gdn_moe, latent_moe, llama,
                                          window_moe)
from llm_sharding_demo_tpu.ops.attention import KVCache
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine, SamplingConfig
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
from llm_sharding_demo_tpu.utils import graftfault, tracing

FAMILIES = {"llama": (llama, "llama-tiny"),
            "latent_moe": (latent_moe, "latent-moe-tiny"),
            "gdn_moe": (gdn_moe, "gdn-moe-tiny"),
            "window_moe": (window_moe, "window-moe-tiny")}
# expert layers a forward counts (``layer_forwards`` a step), where the
# family's cache carries routing counters
EXPERT_LAYERS = {"latent_moe": 3, "gdn_moe": 8, "window_moe": 7}
SEG = 32
MAX_SEQ = 256
GREEDY = SamplingConfig(mode="greedy")
SAMPLED = SamplingConfig(mode="sample", temperature=0.7, top_k=30)


@pytest.fixture(scope="module")
def engines():
    """family -> its engine over seeded weights, built once."""
    built = {}

    def get(family):
        if family not in built:
            module, name = FAMILIES[family]
            cfg = module.CONFIGS[name]
            # weights wide enough that greedy streams vary
            params = jax.tree.map(
                lambda x: x * 4.0,
                module.init_params(cfg, jax.random.PRNGKey(5)))
            built[family] = DecodeEngine(params, cfg, max_seq=MAX_SEQ)
        return built[family]
    return get


def _scheduler(eng, pooled, **kw):
    pool = (KVBlockPool.for_engine(eng, 96, block_size=16, state_slots=1)
            if pooled else None)
    return IterBatchingEngine(eng, max_batch=4, pool=pool, **kw)


def _together(it, jobs):
    """Every job's ``(result, trace)``, all sent at once."""
    got = [None] * len(jobs)

    def go(i, prompt, new, kw):
        tr = tracing.RequestTrace(f"r{i}")
        with tracing.use_trace(tr):
            got[i] = (it.generate(prompt, new, **kw), tr)

    threads = [threading.Thread(target=go, args=(i, *job))
               for i, job in enumerate(jobs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    return got


def _decode_spans(tr):
    tr.settle()
    return [s for s in tr.spans if s.name == "decode"]


@pytest.mark.parametrize("sampling", [GREEDY, SAMPLED],
                         ids=["greedy-pooled", "sampled"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_rows_of_differing_budgets_serve_their_solo_streams(engines, family,
                                                            sampling):
    """Budgets 5, 40 and 70 in one batch: each row is answered at its
    last token with its solo stream, paid exactly the steps between its
    tokens, and the counters say so."""
    eng = engines(family)
    greedy = sampling.mode == "greedy"
    it = _scheduler(eng, pooled=greedy, max_wait_ms=300.0)
    assert it.seg_steps == SEG
    rs = np.random.RandomState(3)
    budgets = (5, 40, 70)
    jobs = [(rs.randint(0, 256, (n,)), new,
             {} if greedy else dict(sampling=sampling,
                                    key=jax.random.PRNGKey(20 + new)))
            for n, new in zip((9, 30, 17), budgets)]
    got = _together(it, jobs)
    st = it.stats()
    calls = {}
    for (prompt, new, kw), (res, tr) in zip(jobs, got):
        want = eng.generate(prompt[None, :], new, **kw).tokens[0]
        assert np.array_equal(res.tokens[0], want), new
        assert res.new_tokens == new
        spans = _decode_spans(tr)
        assert sum(s.labels["steps"] for s in spans) == new - 1
        calls.update({s.labels["seg"]: s.labels["steps"] for s in spans})
    # a call is cut wherever a budget ended it before SEG steps (no row
    # is near the cache's end here), and only there
    assert st["segments"] == len(calls)
    assert st["segments_cut"] == sum(n < SEG for n in calls.values()) >= 2
    assert st["steps_paid"] == st["gaps_answered"] == sum(budgets) - 3
    if eng.cache_counters:
        # every call's sums are those of the steps it ran
        assert st["moe.layer_forwards"] == \
            EXPERT_LAYERS[family] * sum(calls.values())


def _prefilled(eng, batch=2, length=12):
    ids = jnp.asarray(np.random.RandomState(8).randint(0, 256,
                                                       (batch, length)))
    logits, cache = eng._prefill(eng._run_params(), ids, None)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


def _call(eng, n=None, sampling=GREEDY):
    """One decode call of ``SEG`` keys from a fresh prefill: the scan
    form, or the counted form told to run ``n`` steps."""
    token, cache = _prefilled(eng)
    keys = jnp.stack([jax.random.split(jax.random.PRNGKey(r), SEG)
                      for r in (1, 2)], axis=1)          # [SEG, B, 2]
    steps = () if n is None else (np.int32(n),)
    return eng._decode_seg(eng._run_params(), token, cache, None, keys,
                           *steps, sampling=sampling, window=None)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_length_of_a_width_is_one_program(engines, family):
    """Lengths 1..32 of one width run ONE compiled decode program, each
    the scan form's first ``n`` tokens, the last of them handed back;
    the routing counters of a cut call are those of its steps."""
    eng = engines(family)
    whole, _ = _call(eng)
    whole = np.asarray(whole)
    before = eng._decode_seg._cache_size()
    for n in range(1, SEG + 1):
        out, cache, last = _call(eng, n)
        assert np.array_equal(np.asarray(out)[:, :n], whole[:, :n]), n
        assert np.array_equal(np.asarray(last), whole[:, n - 1]), n
        if eng.cache_counters:
            got = dict(zip(eng.cache_counters, np.asarray(cache.v)))
            assert got["layer_forwards"] == EXPERT_LAYERS[family] * n
    assert eng._decode_seg._cache_size() == before + 1


@pytest.mark.parametrize("sampling", [GREEDY, SAMPLED],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_counted_form_at_a_whole_call_is_the_scan_form(engines, family,
                                                           sampling):
    """``n = seg_steps``: tokens and every leaf of the cache bit for
    bit, so a caller that passes no count keeps what it had."""
    eng = engines(family)
    out, cache = _call(eng, sampling=sampling)
    got, counted, last = _call(eng, SEG, sampling=sampling)
    assert np.array_equal(np.asarray(got), np.asarray(out))
    assert np.array_equal(np.asarray(last), np.asarray(out)[:, -1])
    for a, b in zip(jax.tree.leaves(counted), jax.tree.leaves(cache)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_an_armed_row_beside_a_budget_row_retires_at_its_eos(engines,
                                                             family):
    """A budget known ahead cuts the call; an armed ``eos_id`` ends its
    row only once its tokens are read, at a call's end, as it did."""
    eng = engines(family)
    it = _scheduler(eng, pooled=False, max_wait_ms=300.0)
    rs = np.random.RandomState(4)
    budget, armed = rs.randint(0, 256, (14,)), rs.randint(0, 256, (11,))
    plain = eng.generate(armed[None, :], 60).tokens[0][len(armed):]
    at = 20
    # an id the armed row's stream first shows at its 21st token
    while plain[at] in plain[:at]:
        at += 1
    (res_b, _), (res_a, tr) = _together(it, [
        (budget, 7, {}), (armed, 60, dict(eos_id=int(plain[at])))])
    assert np.array_equal(
        res_b.tokens[0], eng.generate(budget[None, :], 7).tokens[0])
    assert res_a.new_tokens == at + 1
    assert np.array_equal(res_a.tokens[0][len(armed):], plain[:at + 1])
    st = it.stats()
    assert st["eos_retires"] == 1 and st["segments_cut"] >= 1
    # the armed row paid its calls to their ends: past its EOS, never
    # past its budget
    paid = sum(s.labels["steps"] for s in _decode_spans(tr))
    assert at <= paid <= 59
    assert st["steps_paid"] == 6 + paid and st["gaps_answered"] == 6 + at


def test_the_host_stays_one_call_ahead_of_the_device():
    """``_hold_lead`` waits, oldest first, for every call in flight but
    the newest, and for nothing when one or none is; the thread is in
    ``hold`` for the wait and for nothing else."""
    import collections
    import types
    waited, entered = [], []

    class Tokens:
        def __init__(self, no):
            self.no = no

        def block_until_ready(self):
            waited.append(self.no)
            return self

    sched = types.SimpleNamespace(
        _in_flight=collections.deque(Tokens(i) for i in range(3)),
        _enter=lambda state: entered.append((state, list(waited))))
    IterBatchingEngine._hold_lead(sched)
    assert waited == [0, 1] and [t.no for t in sched._in_flight] == [2]
    assert entered == [("hold", []), ("other", [0, 1])]
    IterBatchingEngine._hold_lead(sched)
    assert waited == [0, 1] and len(sched._in_flight) == 1
    sched._in_flight.clear()
    IterBatchingEngine._hold_lead(sched)
    assert waited == [0, 1] and len(entered) == 2


# -- the resident working cache of a pooled batch ----------------------------

POISON = 7.0


def _mismatches(it, state, when):
    """Where the pool parts from the resident cache: a live row's
    gathered table against its resident row over ``[pad, depth)``, and
    a free or ghost lane's table against the trash block."""
    pool, bad = it.pool, []
    if state.cache is None:
        return [f"{when}: the batch holds no resident cache"]
    pad = np.asarray(state.pad_j)
    for i, s in enumerate(state.slots):
        if s is None:
            if (state.tables[i] != pool.trash).any():
                bad.append(f"{when}: free lane {i} holds blocks")
            continue
        got = pool.gather(state.tables[i:i + 1], state.depth)
        for leaf in ("k", "v"):
            want = getattr(state.cache, leaf)
            if getattr(want, "ndim", 0) <= 1:
                continue        # a one-plane cache's counters
            a = np.asarray(getattr(got, leaf))[:, 0, :, pad[i]:state.depth]
            b = np.asarray(want)[:, i, :, pad[i]:state.depth]
            if not np.array_equal(a, b):
                bad.append(f"{when}: row {i} {leaf} at depth {state.depth}")
    return bad


def _watch(it):
    """Check the pool round every decode call and every write-back of
    ``it``, on its own thread; what was found wrong, and what each
    write-back should have counted."""
    pool, bad, wrote = it.pool, [], []
    advance, span = it._advance, pool.scatter_span

    def watched_span(cache, tables, col, n_cols):
        before = np.asarray(pool.data)
        span(cache, tables, col, n_cols)
        after = np.asarray(pool.data)
        lo = min(col, pool.nbm - n_cols)
        may = set(tables[:, lo:lo + n_cols].ravel()) | {pool.trash}
        did = {b for b in range(after.shape[1])
               if not np.array_equal(before[:, b], after[:, b])}
        if not did <= may:
            bad.append(f"write-back at column {col} changed {did - may}")
        wrote.append([int((tables != pool.trash).any(axis=1).sum()), col])

    def watched_advance(state):
        bad.extend(_mismatches(it, state, "before a call"))
        d, calls = state.depth, len(wrote)
        advance(state)
        if len(wrote) > calls:
            # live rows x the columns that hold positions [d, depth)
            live, col = wrote[-1]
            wrote[-1] = live * ((state.depth - 1) // pool.block_size
                                - col + 1)
            assert col == d // pool.block_size
        bad.extend(_mismatches(it, state, "after a call"))

    pool.scatter_span, it._advance = watched_span, watched_advance
    return bad, wrote


def _after_calls(it, n):
    """Sleep until ``it`` has run ``n`` more decode calls."""
    import time
    want = it.stats()["segments"] + n
    deadline = time.monotonic() + 300
    while it.stats()["segments"] < want and time.monotonic() < deadline:
        time.sleep(0.01)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_pool_mirrors_the_resident_cache(engines, family):
    """Seed, join and grow, a retirement at a cut call, a preemption
    with its resume, and a fault park, on a pool of ONE row's blocks
    filled with poison: every row is its solo stream, no call gathers
    (a grow does, once), at every boundary the pool holds each live row
    as the resident cache does, and a write-back changes the written
    columns' blocks and the trash block, nothing else."""
    eng = engines(family)
    pool = KVBlockPool.for_engine(eng, MAX_SEQ // 16, block_size=16,
                                  state_slots=1, watermark=1.0)
    pool.data = jnp.full_like(pool.data, POISON)
    it = IterBatchingEngine(eng, max_batch=4, pool=pool, max_wait_ms=300.0)
    bad, wrote = _watch(it)
    rs = np.random.RandomState(6)
    # two rows seed together and outgrow the pool (the younger parks,
    # and is resumed by recompute when the elder has gone); a third
    # joins them a call later (the batch grows) and retires four steps
    # on, cutting that call short
    jobs = [(rs.randint(0, 256, (n,)), new)
            for n, new in ((9, 150), (30, 120), (17, 5))]
    got = [None] * 4

    def go(i, prompt, new):
        got[i] = it.generate(prompt, new, timeout=600)

    threads = [threading.Thread(target=go, args=(i, *jobs[i]))
               for i in range(2)]
    for t in threads:
        t.start()
    _after_calls(it, 1)
    threads.append(threading.Thread(target=go, args=(2, *jobs[2])))
    threads[-1].start()
    for t in threads:
        t.join(timeout=600)
    # a transient fault two calls into a fourth row: parked, resumed
    last = (rs.randint(0, 256, (12,)), 200)
    jobs.append(last)
    t = threading.Thread(target=go, args=(3, *last))
    t.start()
    _after_calls(it, 2)
    with graftfault.use(graftfault.FaultPlan(
            seed=7, rate=1.0, max_injections=1,
            sites={"iterbatch.decode_seg"}, kinds={"decode_transient"})):
        t.join(timeout=600)
    graftfault.reset()
    for (prompt, new), res in zip(jobs, got):
        want = eng.generate(prompt[None, :], new).tokens[0]
        assert np.array_equal(res.tokens[0], want), new
    assert not bad, bad[:5]
    st = it.stats()
    assert st["joins"] >= 1 and st["grows"] >= 1 and st["segments_cut"] >= 1
    assert st["preemptions"] >= 1 and st["fault_parks"] == 1
    assert st["resumes"] >= 2
    assert st["calls_resident"] == st["segments"] == len(wrote)
    assert st["cache_gathers"] == st["grows"]
    assert st["blocks_written_back"] == sum(wrote)
    assert pool.allocator.stats().blocks_in_use == 0
    if pool.slab is not None:
        # the rows' state rode in the working cache through all of it:
        # no call moved a record, a joiner's went into its lane (the
        # resumes that joined a live batch too), none is held now
        assert st["state_calls_resident"] == st["segments"]
        assert st["state.rows_gathered"] == 0 == st["state.in_use"]
        assert 1 <= st["state.rows_scattered"] <= (
            st["joins"] + st["resumes"])
        assert st["state.slots"] == 1 + 4 and 2 <= st["state.peak"] <= 4
    # ONE write-back program a width (1, 2 and 4), whatever the depth
    assert pool._scatter_span._cache_size() <= 3
    # a grow's gather at widths 2 and 4, and this test's own reads
    assert pool._gather._cache_size() <= 3


def _span_case(family, engines):
    """A pool, a two-row working cache of random content, and tables
    that give row 0 blocks of its own and leave row 1 a ghost."""
    rs = np.random.RandomState(2)
    if family == "fused":
        # the layout of the Pallas decode kernels: [K | V] rows
        pool = KVBlockPool(2, 40, 2, 16, 8, MAX_SEQ, fused=True)
        k = jnp.asarray(rs.randn(2, 2, 2, MAX_SEQ, 16), jnp.float32)
        cache = KVCache(k=k, v=jnp.zeros((0,), jnp.float32),
                        length=jnp.zeros((), jnp.int32))
    else:
        eng = engines(family)
        pool = KVBlockPool.for_engine(eng, 40, block_size=16, state_slots=1)
        cache = jax.tree.map(
            lambda x: (jnp.asarray(rs.randn(*x.shape), x.dtype)
                       if x.ndim > 1 else x), _prefilled(eng)[1])
    tables = np.full((2, pool.nbm), pool.trash, np.int32)
    tables[0] = rs.permutation(40)[:pool.nbm]
    return pool, cache, tables


@pytest.mark.parametrize("family", sorted(FAMILIES) + ["fused"])
def test_a_write_back_rewrites_its_columns_and_no_other(engines, family):
    """``scatter_span`` at every first column, the clamped ones past the
    table's end among them: ONE program, the span's blocks of a live row
    hold the cache's content, the ghost row reaches the trash block
    only, every other block keeps its poison."""
    pool, cache, tables = _span_case(family, engines)
    span = 3
    for col in range(pool.nbm + 1):
        pool.data = jnp.full_like(pool.data, POISON)
        pool.scatter_span(cache, tables, col, span)
        lo = min(col, pool.nbm - span)
        data = np.asarray(pool.data)
        kept = np.ones(data.shape[1], bool)
        kept[tables[0, lo:lo + span]] = kept[pool.trash] = False
        assert (data[:, kept] == POISON).all(), col
        got = pool.gather(tables[:1], 0)
        for leaf in ("k", "v"):
            want = getattr(cache, leaf)
            if getattr(want, "ndim", 0) > 1:
                at = slice(lo * 16, (lo + span) * 16)
                assert np.array_equal(
                    np.asarray(getattr(got, leaf))[:, 0, :, at],
                    np.asarray(want)[:, 0, :, at]), (col, leaf)
    assert pool._scatter_span._cache_size() == 1


def test_span_blocks_cover_a_call_wherever_it_starts():
    """The fixed span holds every block a call of up to ``seg_steps``
    positions touches, from any offset in a block, and is as narrow as
    the worst start allows."""
    from llm_sharding_demo_tpu.ops.paged_attention import span_blocks
    for seg, bs in ((32, 16), (8, 8), (1, 16), (16, 16), (33, 16), (5, 64)):
        span = span_blocks(seg, bs, 1 << 20)
        touched = [(d + n - 1) // bs - d // bs + 1
                   for d in range(3 * bs) for n in range(1, seg + 1)]
        assert max(touched) == span, (seg, bs)
    assert span_blocks(32, 16, 2) == 2
