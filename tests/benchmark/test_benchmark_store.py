"""``store_tokens_per_call``: found by name, read from the store's
counters as the harness flattens them, left out (not failing) where the
program does not count its walk's calls; and set-up still meets every
width the window's walks can ask for."""

import json
import os
import types

import jax
import numpy as np
import pytest

from benchmark.harness import server, traffic
from benchmark.harness.spec import REPO, Spec
from benchmark.readers import store
from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.runtime import prefix_cache
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine

METRIC = "store_tokens_per_call"
CELLS = ["mistral-7b-l16.chat", "mistral-7b-l16.chat-b",
         "joyai-llm-flash-ep16.assist"]


def _ctx(before, after):
    return types.SimpleNamespace(counters_before=before, counters_after=after)


def test_the_metric_is_declared_for_the_three_cells_and_found_by_name():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entry, = [m for m in doc["per_layer"] if m["name"] == METRIC]
    assert entry == {"name": METRIC, "unit": "tokens", "better": "higher",
                     "source": "program_counter",
                     "layer": "KV pool / prefix store",
                     "moves": "tpot_p50_ms", "workloads": CELLS}
    assert doc["per_layer"][-1] is entry            # appended, last
    read = Spec().reader(METRIC)
    ctx = _ctx({"prefix.extend_calls": 10, "prefix.extend_tokens": 600},
               {"prefix.extend_calls": 14, "prefix.extend_tokens": 1373})
    assert read(ctx) == pytest.approx(773 / 4)


@pytest.mark.parametrize("before,after", [
    ({}, {}),
    # the parent commit's store: hits and misses, no calls counted
    ({"prefix.hits": 1, "prefix.misses": 2},
     {"prefix.hits": 4, "prefix.misses": 9}),
    # a window in which nobody walked the store
    ({"prefix.extend_calls": 7, "prefix.extend_tokens": 400},
     {"prefix.extend_calls": 7, "prefix.extend_tokens": 400}),
], ids=["no-counters", "parent", "quiet"])
def test_a_window_without_the_counters_gives_none(before, after):
    assert store.store_tokens_per_call(_ctx(before, after)) is None


def test_the_harness_hands_the_stores_counters_to_the_reader():
    """``Served.counters`` flattens ``stats()`` under ``prefix.``: a
    miss walk of 7 chunks and a tail is 4 calls, and the next request
    behind the same chunks is one more."""
    cfg = gpt2.GPT2Config(vocab_size=127, n_positions=128, n_embd=32,
                          n_layer=1, n_head=2)
    eng = DecodeEngine(gpt2.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                       max_seq=96)
    pce = prefix_cache.PrefixCachingEngine(eng, capacity=2, chunk=8)
    served = types.SimpleNamespace(
        scheduler=types.SimpleNamespace(prefix=pce), pool=None)
    before = server.Served.counters(served)
    prompt = (np.arange(59, dtype=np.int32) * 7) % cfg.vocab_size
    pce.prefill_state(prompt)
    pce.prefill_state(np.concatenate([prompt[:56], [1, 2]]))
    after = server.Served.counters(served)
    assert after["prefix.extend_calls"] - before["prefix.extend_calls"] == 5
    assert store.store_tokens_per_call(_ctx(before, after)) \
        == pytest.approx((59 + 2) / 5)


@pytest.mark.parametrize("cell", CELLS)
def test_setup_meets_every_stride_width_a_window_walk_can_take(cell):
    """Set-up sends every prompt length of the window's trace as a
    joiner with contents of its own (``warm_iter``): a walk from depth
    0. A stride's program is keyed by its width alone, so once those
    walks have met every width of the ladder, a window walk from any
    hit depth compiles nothing."""
    spec = Spec()
    entry = spec.workload(cell)
    mix = spec.traffic(entry["traffic"])
    n = traffic.count(spec.cell(cell)["rate_rps"], 51)
    chunk = 64                      # serving/app.py's default alignment
    met = {s for plen, _, _ in traffic.sizes(mix, n)
           for s in prefix_cache._strides((plen - 1) // chunk)}
    assert met == set(prefix_cache.STRIDE_LADDER)
