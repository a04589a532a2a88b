"""Test bootstrap: force an 8-device virtual CPU mesh.

The TPU-native analog of "multi-node tests without real nodes" (SURVEY.md §4
item 4): tests exercise 2- and 4-stage pipelines and dp/tp meshes on forced
host devices; the identical code runs unmodified on a real TPU slice.

Ordering matters: XLA_FLAGS must be set before the first backend use, and
the platform is pinned through jax.config so the suite stays on the CPU
whatever JAX_PLATFORMS says.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)
# the persistent-cache AOT loader logs multi-KB machine-feature diffs at
# ERROR level on every cache hit; they are informational here and drown
# real test output
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Parity oracles compare fp32 logits against torch; on CPU this is the
# default, and on any accelerator 'highest' keeps matmuls out of bf16.
jax.config.update("jax_default_matmul_precision", "highest")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent compilation cache (suite wall-time): many tests build
# per-instance engines whose jitted programs lower to IDENTICAL HLO — the
# persistent cache dedupes those compiles across modules within one run,
# and repeat runs start warm (measured 3x on the heavier decode files).
# Keyed by jaxlib version internally, so a stale dir is ignored, never
# wrong. Its place is utils.compile_cache's rule: JAX_COMPILATION_CACHE_DIR
# where set, else <checkout>/.jax_cache.
from llm_sharding_demo_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)


def pytest_sessionstart(session):
    n = len(jax.devices())
    assert n == 8, f"expected 8 forced host devices, got {n}"


import pytest  # noqa: E402

# Two-tier suite (ADVICE item 7): ``-m quick`` runs the core serving
# exactness oracles — the engine/scheduler/speculation/prefix/batching
# token-exactness contracts every runtime change must hold — in well
# under 10 minutes cold. The full (unmarked) invocation is the tier-1
# gate and still runs everything; marking is centralized HERE (by
# module) so test files don't each carry boilerplate and the tier
# membership is one reviewable list.
_QUICK_MODULES = {
    "test_engine",          # decode engine: streams, EOS, sampling
    "test_batcher",         # admission batching per-row exactness
    "test_iterbatch",       # continuous batching + spec/prefix segments
    "test_spec_decode",     # speculation: solo + batched verify loops
    "test_prefix_cache",    # cross-request KV reuse byte-exactness
    "test_kv_pool",         # paged KV pool: paged ≡ contiguous, CoW,
                            # preempt/resume recompute exactness
    "test_kv_tier",         # grafttier host spill: demote/promote
                            # byte-identity, ledgers, tier pass
    "test_paged_attention", # block gather/scatter + paged attention ops
    "test_chunked_prefill", # chunked ≡ monolithic prefill
    "test_subproc",         # watchdog attribution (bench/CI harness)
    "test_tokenizer",       # offline BPE round-trips
    "test_graftcheck",      # static contract verifier + lint (whole-repo)
    "test_graftplan",       # cost model goldens + planner rankings
    "test_graftsan",        # donation-aliasing pass + pool sanitizer
    "test_graftlock",       # lock-discipline pass + GRAFTSCHED harness
    "test_graftfault",      # fault contracts + seeded injection + deadlines
    "test_graftscope",      # device-time attribution + bench_diff gate
    "test_graftload",       # open-loop load harness + declared SLOs
    "test_graftfleet",      # disaggregated fleet: router, handoff, pass
    "test_graftwatch",      # continuous re-planning: watcher, switcher
    "test_grafttime",       # unified causal timeline: bus, export, pass
    "test_graftnum",        # numerics discipline: contracts + oracle
    "test_graftmem",        # HBM ledger: attribution, reconcile, pass
    "test_grafttrend",      # trend watches: reducer, refit, pass
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "quick: core exactness oracles (fast tier; "
                   "run with -m quick, full suite runs unmarked)")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 gate (-m 'not slow')")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__.rpartition(".")[2] in _QUICK_MODULES:
            item.add_marker(pytest.mark.quick)


@pytest.fixture(autouse=True)
def _metrics_isolation():
    """Snapshot/restore the process-global metrics REGISTRY, flight
    RECORDER, and graftscope attribution rings around every test:
    modules bind these at import, so they cannot be swapped per-test —
    but their STATE can, which is what metric/ring assertions need (one
    test's generate calls must not inflate another's counters or
    dispatch rings). ``create_app`` additionally accepts an injected
    registry/recorder for tests that want full isolation."""
    from llm_sharding_demo_tpu.utils import (graftmem, graftscope,
                                             grafttime, grafttrend,
                                             metrics, tracing)
    state = metrics.REGISTRY.dump_state()
    scope_state = graftscope.dump_state()
    scope_flags = (graftscope.enabled(), graftscope.sync_enabled())
    time_state = grafttime.dump_state()
    time_enabled = grafttime.enabled()
    blackbox_saved = grafttime.blackbox_dumps()
    mem_state = graftmem.dump_state()
    trend_state = grafttrend.dump_state()
    with tracing.RECORDER._lock:
        saved = list(tracing.RECORDER._traces)
    yield
    metrics.REGISTRY.restore_state(state)
    graftscope.restore_state(scope_state)
    graftscope.set_enabled(scope_flags[0])
    graftscope.set_sync(scope_flags[1])
    grafttime.restore_state(time_state)
    grafttime.set_enabled(time_enabled)
    graftmem.restore_state(mem_state)
    grafttrend.restore_state(trend_state)
    grafttime.clear_blackbox()
    with grafttime._DUMPS_LOCK:
        grafttime._DUMPS.extend(blackbox_saved)
    with tracing.RECORDER._lock:
        tracing.RECORDER._traces.clear()
        tracing.RECORDER._traces.extend(saved)


@pytest.fixture(autouse=True)
def _graftlock_thread_and_lock_hygiene():
    """Concurrency hygiene after every test (the graftlock satellite):
    no instrumented lock may still be held (a scheduler that unwound
    without releasing would deadlock the NEXT test, not this one — fail
    here, with the lock name), and no new non-daemon thread may outlive
    the test (scheduler workers are daemons by design; a non-daemon
    leak hangs interpreter shutdown). Lingering non-daemon threads get
    a short grace poll before being declared leaked."""
    import threading
    import time as _time
    before = {t for t in threading.enumerate() if not t.daemon}
    yield
    from llm_sharding_demo_tpu.utils import graftsched
    # grace poll: a scheduler worker's trailing beat (gauge refresh
    # after the last delivery) may hold a lock for a moment
    deadline = _time.monotonic() + 2.0
    while graftsched.held_locks() and _time.monotonic() < deadline:
        _time.sleep(0.01)
    held = graftsched.held_locks()
    assert not held, (
        f"instrumented locks still held after the test: {held} — a "
        "code path released its thread without releasing its lock")
    while True:
        leaked = [t for t in threading.enumerate()
                  if not t.daemon and t.is_alive() and t not in before]
        if not leaked or _time.monotonic() > deadline:
            break
        _time.sleep(0.05)
    assert not leaked, (
        f"non-daemon threads leaked by the test: {leaked} — join them "
        "or mark them daemon")


@pytest.fixture(autouse=True)
def _graftsan_teardown_sweep():
    """Under ``GRAFTSAN=1`` (the sanitizer tier — the whole quick tier
    must run clean under it), every test ends with a leak sweep: any
    live sanitizing BlockAllocator still holding caller refs beyond its
    prefix entries fails the test with per-block grant provenance.
    Block release can trail request delivery by a scheduler beat, so
    the sweep polls briefly before declaring a leak."""
    yield
    if os.environ.get("GRAFTSAN", "") not in ("", "0"):
        from llm_sharding_demo_tpu.runtime import kv_pool
        kv_pool.graftsan_sweep(timeout=5.0)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Bound in-process XLA state: the full suite compiles hundreds of
    CPU programs in one interpreter, and past ~the-whole-suite volume
    XLA:CPU segfaulted inside a later compile (reproduced twice at ~99%
    in jax compiler.py backend_compile_and_load). Dropping executables
    between modules keeps the live-program population at
    one-module-scale; the persistent compilation cache makes any
    cross-module recompiles cheap loads."""
    yield
    jax.clear_caches()


class DescribedChip:
    """One chip of a described ``v5e:2x2`` (section 2 of the
    on-chip-measurement guide): the TPU compiler builds for it from
    shapes alone, and refuses what interpret mode lets through."""

    HBM_BYTES = 16e9

    def __init__(self, sharding):
        self.sharding = sharding

    def shape(self, shape, dtype=jax.numpy.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=self.sharding)

    def placed(self, tree):
        """Shapes (from ``jax.eval_shape``) pinned to the chip."""
        return jax.tree.map(lambda x: self.shape(x.shape, x.dtype), tree)

    def check(self, compiled):
        """A Mosaic kernel inside, and a program that fits the chip."""
        assert "tpu_custom_call" in compiled.as_text(), \
            "no Mosaic kernel inside"
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        assert total < self.HBM_BYTES, \
            f"{total / 1e9:.1f} GB does not fit a v5e chip"
        return mem

    def compile(self, fn, *args):
        return self.check(
            jax.jit(fn).lower(*self.placed(args)).compile())


@pytest.fixture(scope="module")
def one_chip():
    """The described chip for the ``test_tpu_compile*`` files. Described
    inside a fixture, never at import: the call loads the TPU's library
    into the worker that runs the file (the tier-1 command sets
    ``ALLOW_MULTIPLE_LIBTPU_LOAD`` for the second such worker)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it undescribed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache but cannot be
    # read back without a chip; keep the cache out of these tests
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield DescribedChip(jax.sharding.SingleDeviceSharding(topo.devices[0]))
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
