"""A live row's state is a lane of its batch's working cache; the state
slab holds the prefix store's snapshots and nothing else. So a batch
that fills every lane takes no slot from the store: a snapshot taken
before it is restored into it and is still there after it, in each of
the four families whose rows hold a state beside their positions.
"""

import threading
import time

import jax
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import (gdn_moe, hybrid_ssm, kda_moe,
                                          window_moe)
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine
from llm_sharding_demo_tpu.utils import tracing

FAMILIES = {"gdn_moe": (gdn_moe, "gdn-moe-tiny"),
            "hybrid_ssm": (hybrid_ssm, "hybrid-ssm-tiny"),
            "kda_moe": (kda_moe, "kda-moe-tiny"),
            "window_moe": (window_moe, "window-moe-tiny")}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_snapshot_survives_a_batch_that_fills_every_lane(family):
    module, name = FAMILIES[family]
    cfg = module.CONFIGS[name]
    params = jax.tree.map(
        lambda x: x * 4 if x.ndim > 1 else x,
        module.init_params(cfg, jax.random.PRNGKey(5)))
    eng = DecodeEngine(params, cfg, max_seq=256)
    # ONE slot: the store's one entry. The batch's two lanes are its own.
    pool = KVBlockPool.for_engine(eng, 96, block_size=16, state_slots=1)
    store = PrefixCachingEngine(eng, capacity=1, chunk=64, pool=pool)
    it = IterBatchingEngine(eng, max_batch=2, seg_steps=8, prefix=store,
                            pool=pool)
    rs = np.random.RandomState(3)
    shared = rs.randint(0, 256, (64,))
    store.prefill_state(np.concatenate([shared, rs.randint(0, 256, (5,))]))
    assert it.stats()["state.in_use"] == it.stats()["state.snapshots"] == 1
    # the deepest first, then one behind the stored prefix (it restores
    # the snapshot into the second lane), then one that finds no lane
    # and waits for the first to go
    jobs = [(rs.randint(0, 256, (150,)), 40),
            (np.concatenate([shared, rs.randint(0, 256, (7,))]), 30),
            (rs.randint(0, 256, (11,)), 6)]
    got = {}

    def go(i):
        tr = tracing.RequestTrace(f"r{i}")
        with tracing.use_trace(tr):
            got[i] = (it.generate(*jobs[i], timeout=600), tr)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(3)]
    seg, started, held = eng._decode_seg, [], []

    def first_call_waits_for_the_others(*a, **kw):
        out = seg(*a, **kw)
        if not started:
            started.append(1)
            for t in threads[1:]:
                t.start()
                time.sleep(0.02)
            deadline = time.monotonic() + 120
            while it._queue.qsize() < 2 and time.monotonic() < deadline:
                time.sleep(0.001)
        held.append(it.stats()["state.in_use"])
        return out

    eng._decode_seg = first_call_waits_for_the_others
    threads[0].start()
    for t in threads:
        t.join(timeout=600)
    solo = DecodeEngine(params, cfg, max_seq=256)
    for i, (prompt, new) in enumerate(jobs):
        assert np.array_equal(got[i][0].tokens,
                              solo.generate(prompt, new).tokens), i
    st = it.stats()
    restored = [s.labels["state_restored"] for _, tr in got.values()
                for s in tr.spans
                if s.name == "prefill" and "state_restored" in s.labels]
    assert max(restored) == 64 and st["state.restores"] == 1
    # both lanes held a row with the snapshot beside them, and nobody
    # was turned away or evicted for a record's room
    assert max(held) == 3 == st["state.peak"] and st["state.slots"] == 3
    assert st["joins"] == 2 and st["defers_slot"] >= 1
    assert st["defers_pool"] == 0 == st["state.evictions"]
    assert st["state.in_use"] == st["state.snapshots"] == 1
    assert st["state_calls_resident"] == st["segments"]
    assert st["state.rows_gathered"] == 1
    assert st["state.rows_scattered"] == 1 + st["joins"]
    assert pool.slab.stats()["state.peak"] == 1
    assert [x.shape[1] for x in pool.slab.data] == [1] * len(pool.slab.data)
