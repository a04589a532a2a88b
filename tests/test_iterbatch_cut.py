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
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import (gdn_moe, latent_moe, llama,
                                          window_moe)
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine, SamplingConfig
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
from llm_sharding_demo_tpu.utils import tracing

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
    pool = (KVBlockPool.for_engine(eng, 96, block_size=16, state_slots=4)
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
