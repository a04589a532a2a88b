"""In-process metrics: counters, gauges + latency histograms, Prometheus-exposable.

The reference's observability is one startup print and uvicorn access
logs (reference server.py:27, Dockerfile:19; SURVEY.md §5 "Metrics":
ABSENT — the optional k8s metrics-server only sees pod CPU/mem). This
registry backs the serving layer's /metrics endpoint and the decode
engine's per-request timings.

Thread-safe (the stdlib HTTP server is one-thread-per-request). Export
format is Prometheus text exposition, so a scrape config pointed at the
pod Just Works; ``snapshot()`` returns the same data as a dict for tests
and /healthz embedding.

``METRIC_CATALOG`` is the single inventory of every metric name this
codebase may emit, with its instrument kind. ``tools/check_metrics.py``
(run in the test suite) greps the ``REGISTRY.inc/observe/gauge`` call
sites against it, so a typo'd name cannot silently fork a time series.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Tuple

from . import graftsched

# Lock-discipline contract (tools/graftcheck locks pass): every series
# map and the compile-watch cursor live under the owning instance's
# ``_lock``; both classes are called from arbitrary handler/scheduler
# threads.
GUARDED_STATE = {"_counters": "_lock", "_gauges": "_lock",
                 "_histograms": "_lock", "_seen": "_lock"}
LOCK_ORDER = ("_lock",)

# latency buckets (seconds): 1ms .. 60s, roughly log-spaced
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


# name -> instrument kind ("counter" | "histogram" | "gauge"). THE metric
# inventory: every literal name passed to REGISTRY.inc/observe/gauge must
# appear here with the matching kind (tools/check_metrics.py enforces it),
# and docs/ARCHITECTURE.md's observability section points here instead of
# duplicating the list.
METRIC_CATALOG: Dict[str, str] = {
    # serving surface (serving/app.py)
    "generate_requests_total": "counter",
    "generated_tokens_total": "counter",
    "upstream_failures_total": "counter",
    "generate_request_seconds": "histogram",
    # request-phase latency split (derived from the request trace), per
    # mode. ttft_seconds: arrival to the instant the first token EXISTED
    # (the first prefill span's ready instant; the end of its window
    # where nobody stamped one). tpot_seconds: (last decode span's ready
    # instant - prefill ready) / (decoded - 1); without ready instants
    # the decode spans' wall time over the same gaps
    "ttft_seconds": "histogram",
    "tpot_seconds": "histogram",
    # graftscope device-time attribution (utils/graftscope.py):
    # per-dispatch wall clock of every PROFILED_SCOPES jit entry point,
    # labeled scope="module._entry" — serving-thread enqueue windows by
    # default, device truth under GRAFTSCOPE_SYNC=1 (see graftscope's
    # truth model); and the per-decode-step time each decode front end
    # derives from its own timing window, labeled by component
    # (component="engine": device-inclusive, the final fetch syncs;
    # component="iter": consecutive READY instants of one batch over the
    # later segment's steps, so the step plus what ran between the two
    # segments; "iter_spec": the same over its verify steps, a batch's
    # first segment counted from its own dispatch)
    "dispatch_seconds": "histogram",
    "decode_step_seconds": "histogram",
    # admission batcher (runtime/batcher.py)
    "decode_batches_total": "counter",
    "batched_requests_total": "counter",
    "batched_rows_padded_total": "counter",
    # iteration-level scheduler (runtime/iterbatch.py)
    "iter_batches_total": "counter",
    "iter_joins_total": "counter",
    "iter_segments_total": "counter",
    "iter_spec_segments_total": "counter",
    "iter_grows_total": "counter",
    "iter_eos_retires_total": "counter",
    "iter_segments_cut_total": "counter",
    # pooled batches: decode calls run on the batch's resident working
    # cache with no gather, whole gathers of a live batch (one where it
    # grows; one in front of every call on a quantized pool), and
    # blocks of live rows the write-backs behind the calls rewrote
    "iter_calls_resident_total": "counter",
    "iter_cache_gathers_total": "counter",
    "kv_pool_blocks_written_back_total": "counter",
    # cache positions the decode kernel's stream reads for the live rows'
    # own spans, the positions of the rectangles width x depth, and the
    # running quotient (iterbatch._count_stream)
    "iter_attn_positions_streamed_total": "counter",
    "iter_attn_positions_rect_total": "counter",
    "iter_attn_stream_share": "gauge",
    # lanes the state kernels stream a step (live rows) and lanes of the
    # compiled width, for batches whose rows hold a state
    "iter_state_lanes_streamed_total": "counter",
    "iter_state_lanes_compiled_total": "counter",
    "iter_steps_paid_total": "counter",
    "iter_gaps_answered_total": "counter",
    "iter_rows_total": "counter",
    # seconds of the scheduler thread's life by what it was doing,
    # labeled state (idle / hold / seed / admit / advance / other;
    # iterbatch._STATES), added as each state closes: an idle device is
    # "no request" (idle) or the host's (the rest)
    "iter_scheduler_state_seconds_total": "counter",
    # speculation (runtime/spec_decode.py)
    "spec_verify_steps_total": "counter",
    "spec_emitted_tokens_total": "counter",
    # prefix cache (runtime/prefix_cache.py)
    "prefix_cache_hits_total": "counter",
    "prefix_cache_misses_total": "counter",
    "prefix_cache_reused_tokens_total": "counter",
    # compile events: one increment per NEW jitted program entering a
    # tracked cache (engine prefill/decode, spec loops/segments) — a
    # compile storm is visible as a burst here, distinguishable from
    # steady-state latency
    "compile_events_total": "counter",
    # paged KV pool (runtime/kv_pool.py)
    "kv_pool_evictions_total": "counter",   # LRU prefix-entry evictions
    "kv_pool_cow_copies_total": "counter",  # copy-on-write block copies
    "kv_pool_preemptions_total": "counter",  # rows parked under pressure
    "kv_pool_resumes_total": "counter",     # parked rows recomputed back in
    # serving admission control: /generate requests turned away with
    # 429 + Retry-After because the KV pool could not host them
    "kv_pool_admission_rejections_total": "counter",
    # fault tolerance (graftfault): shard-hop retries through the typed
    # HopPolicy, labeled stage (shard role) x low-cardinality failure
    # reason (timeout/connection/http_error/error); and transient
    # decode faults the iter scheduler absorbed by parking the live
    # rows through the recompute-resume path
    "shard_hop_retries_total": "counter",
    "iter_fault_parks_total": "counter",
    # SLO deadline misses (graftload / loadgen SLO_SOURCE_METRICS):
    # accepted requests that exhausted their X-Deadline-Ms budget and
    # died typed (503 deadline_exceeded) — the source series behind
    # every declared ``deadline_miss`` SLO target, and deliberately
    # NOT a shed counter (sheds refuse work; this broke a promise).
    # Counts EVERY budget death: the server cannot see caller intent,
    # so deliberate walk-aways (the loadgen abandonment profile's
    # short budgets) increment it too — the load harness nets those
    # out CLIENT-side when scoring deadline_miss SLOs
    # (loadgen.driver.summarize), so alert thresholds on this raw
    # series must budget for expected abandonment traffic.
    "deadline_misses_total": "counter",
    # live-state gauges
    "queue_depth": "gauge",                 # waiting requests per scheduler
    # per-TARGET circuit-breaker state (graftfault HopPolicy): 1 while
    # that downstream's breaker is OPEN, 0 when a probe closes it. The
    # target label names the breaker's downstream — a stage shard on
    # the coordinator, a replica name on the fleet router (N
    # downstreams, one breaker and one labeled series each). Emitted
    # as a REGISTRY gauge AND sampled into the graftscope occupancy
    # series on transitions, so a graftload run sees breaker flaps on
    # the same timeline as queue depth.
    "hop_breaker_open": "gauge",
    # graftfleet router (serving/router.py): request routing per
    # target/role, affinity accounting (ring-owner routes vs fallback
    # placements), typed per-replica sheds encountered walking the
    # candidate list (whether fallback absorbed them or the shed was
    # surfaced), and prefill hops that degraded to a cold decode-side
    # prefill
    "fleet_requests_total": "counter",
    "fleet_affinity_hits_total": "counter",
    "fleet_affinity_fallbacks_total": "counter",
    "fleet_sheds_total": "counter",
    "fleet_prefill_degraded_total": "counter",
    "batch_occupancy": "gauge",             # live rows / compiled width
    "iter_live_rows": "gauge",              # live iterbatch rows
    # KV memory in BLOCK denomination, labeled by the writer component
    # (component="pool"/"paged"/"iter": exact allocator numbers;
    # component="engine"/"batcher": the contiguous arena expressed in
    # equivalent blocks via kv_block_gauges) — one unit across the
    # whole serving surface, so "how full is KV memory" is one query.
    # Replaces the retired per-component kv_cache_slots_in_use series
    # (see RETIRED_METRICS).
    # Pool-backed components additionally label the pair with
    # block_dtype (the storage regime: f32/bf16 full-precision, or
    # int8/fp8 quantized — runtime.kv_pool) so a capacity query can
    # group by what a block IS, and publish the per-block HBM cost:
    # quantized pools fit 2-4x the blocks in the same bytes, and the
    # gauge pair alone would misread that as "more memory".
    "kv_cache_blocks_in_use": "gauge",
    "kv_cache_blocks_total": "gauge",
    "kv_pool_bytes_per_block": "gauge",
    # host-RAM KV spill tier (runtime/kv_tier.py — grafttier): demotions
    # move a cold zero-ref prefix entry's raw blocks (codes + scales for
    # quantized pools) to bounded host buffers instead of evicting to
    # oblivion; promotions device_put them back on an affinity hit. The
    # gauge pair is the host tier's block occupancy in the SAME block
    # denomination as the device pair above (host blocks hold the same
    # bytes a device block does), so prefix-store depth across tiers is
    # one query.
    "tier_demotions_total": "counter",
    "tier_promotions_total": "counter",
    "kv_host_blocks_in_use": "gauge",
    "kv_host_blocks_total": "gauge",
    "jit_program_cache_size": "gauge",      # compiled programs per component
    "spec_acceptance_rate": "gauge",        # emitted tokens per verify
    # continuous planning (utils/graftwatch.py): one increment per live
    # plan switch (labeled from/to — the certified set is tiny, so the
    # label space is bounded by construction), and a per-plan 0/1 gauge
    # naming the ACTIVE plan. The gauge doubles as a graftscope
    # occupancy series, so a graftload run sees plan switches on the
    # same timeline as queue depth and pool blocks.
    "plan_switches_total": "counter",
    "auto_plan_active": "gauge",
    # declared HBM ledger (utils/graftmem.py): live registered device
    # bytes, labeled component= from the MEMORY_COMPONENTS vocabulary
    # (params / pool_codes / pool_scales / engine_cache / spec_buffers
    # / prefix_store, plus the "total" grand sum). The gauge doubles
    # as a graftscope occupancy series, so residency trajectories sit
    # beside queue depth and pool blocks; /debug/memory serves the
    # full per-holding table.
    "hbm_bytes": "gauge",
    # trend & drift watch (utils/grafttrend.py): one increment per
    # WATCH_POLICY trip, labeled watch x severity (both drawn from the
    # declared policy, so the label space is bounded by construction);
    # and the live-refit output — the ICI byte weight currently
    # threaded into plan scoring (a-priori costmodel.ICI_BYTE_WEIGHT
    # until the first grafttrend.refit, the fitted value after). The
    # gauge doubles as a graftscope occupancy series, so weight moves
    # sit on the same timeline as queue depth and plan switches.
    "trend_alerts_total": "counter",
    "costmodel_byte_weight": "gauge",
}

# Metric names that USED to exist and were replaced: a call site (or a
# catalog entry) reviving one of these fails the graftcheck
# metric-catalog rule with the replacement spelled out — dashboards
# migrated once and must not silently fork back to the dead series.
RETIRED_METRICS: Dict[str, str] = {
    "kv_cache_slots_in_use":
        "kv_cache_blocks_in_use / kv_cache_blocks_total (block "
        "denomination, same component labels)",
}

# Block width used to express contiguous (non-pooled) KV arenas in the
# pool's block denomination — and runtime.kv_pool's default physical
# block size, so the two denominations agree by default.
DEFAULT_KV_BLOCK_SIZE = 16


def kv_block_gauges(component: str, used_slots: int, total_slots: int,
                    block_size: int = DEFAULT_KV_BLOCK_SIZE,
                    registry: "MetricsRegistry" = None) -> None:
    """Set the ``kv_cache_blocks_*`` gauge pair for a component that
    manages contiguous slot arenas (solo engine, admission batcher,
    non-pooled iterbatch): slots are converted to equivalent blocks
    (ceil). Pool-backed components bypass this and publish the
    allocator's exact numbers (``KVBlockPool.note_gauges``)."""
    reg = registry or REGISTRY
    reg.gauge("kv_cache_blocks_in_use",
              -(-int(used_slots) // block_size) if used_slots > 0 else 0,
              component=component)
    reg.gauge("kv_cache_blocks_total",
              -(-int(total_slots) // block_size) if total_slots > 0 else 0,
              component=component)


class MetricsRegistry:
    def __init__(self):
        self._lock = graftsched.lock("metrics.MetricsRegistry._lock")
        self._counters: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float] = {}
        self._gauges: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float] = {}
        self._histograms: Dict[Tuple[str, Tuple[Tuple[str, str], ...]],
                               List] = {}

    @staticmethod
    def _key(name: str, labels: Dict[str, str]):
        return name, tuple(sorted(labels.items()))

    def inc(self, name: str, value: float = 1.0, **labels) -> None:
        key = self._key(name, labels)
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + value

    def gauge(self, name: str, value: float, **labels) -> None:
        """Set a gauge to its current value (last write wins)."""
        key = self._key(name, labels)
        with self._lock:
            self._gauges[key] = float(value)

    def observe(self, name: str, seconds: float, **labels) -> None:
        key = self._key(name, labels)
        with self._lock:
            if key not in self._histograms:
                self._histograms[key] = [
                    [0] * (len(DEFAULT_BUCKETS) + 1), 0.0, 0]
            counts, _, _ = self._histograms[key]
            counts[bisect.bisect_left(DEFAULT_BUCKETS, seconds)] += 1
            self._histograms[key][1] += seconds
            self._histograms[key][2] += 1

    # -- test isolation (tests/conftest.py) ----------------------------------

    def dump_state(self) -> tuple:
        """Deep snapshot of all series — the conftest isolation fixture
        pairs this with ``restore_state`` so one test's metric writes
        cannot leak into another's assertions on the process-global
        ``REGISTRY``."""
        with self._lock:
            return (dict(self._counters), dict(self._gauges),
                    {k: [list(v[0]), v[1], v[2]]
                     for k, v in self._histograms.items()})

    def restore_state(self, state: tuple) -> None:
        counters, gauges, histograms = state
        with self._lock:
            self._counters = dict(counters)
            self._gauges = dict(gauges)
            self._histograms = {k: [list(v[0]), v[1], v[2]]
                                for k, v in histograms.items()}

    def histogram_buckets(self) -> Dict[str, tuple]:
        """``{name{k=v,...}: (bucket_counts, sum, count)}`` — the raw
        per-label-set bucket counts behind each histogram (bucket ``i``
        spans ``(DEFAULT_BUCKETS[i-1], DEFAULT_BUCKETS[i]]``, plus the
        +Inf overflow slot). ``snapshot()`` deliberately flattens
        histograms to count/sum/avg; the grafttrend burn-rate poller
        needs the bucket resolution to count observations past a
        declared SLO target without storing per-sample values."""
        with self._lock:
            return {_fmt_name(name, labels): (list(counts), total, n)
                    for (name, labels), (counts, total, n)
                    in self._histograms.items()}

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            out: Dict[str, object] = {}
            for (name, labels), v in self._counters.items():
                out[_fmt_name(name, labels)] = v
            for (name, labels), v in self._gauges.items():
                out[_fmt_name(name, labels)] = v
            for (name, labels), (counts, total, n) in self._histograms.items():
                base = _fmt_name(name, labels)
                out[base + "_count"] = n
                out[base + "_sum"] = round(total, 6)
                if n:
                    out[base + "_avg"] = round(total / n, 6)
            return out

    def prometheus(self) -> str:
        """Prometheus text exposition format.

        One ``# TYPE`` line per metric *name* with all label sets grouped
        under it — duplicate TYPE lines for a name make the scraper drop
        the whole page.
        """
        lines: List[str] = []
        with self._lock:
            seen_type: set = set()
            for (name, labels), v in sorted(self._counters.items()):
                if name not in seen_type:
                    seen_type.add(name)
                    lines.append(f"# TYPE {name} counter")
                lines.append(f"{name}{_prom_labels(labels)} {v}")
            for (name, labels), v in sorted(self._gauges.items()):
                if name not in seen_type:
                    seen_type.add(name)
                    lines.append(f"# TYPE {name} gauge")
                lines.append(f"{name}{_prom_labels(labels)} {v}")
            for (name, labels), (counts, total, n) in sorted(
                    self._histograms.items()):
                if name not in seen_type:
                    seen_type.add(name)
                    lines.append(f"# TYPE {name} histogram")
                acc = 0
                for bound, c in zip(DEFAULT_BUCKETS, counts):
                    acc += c
                    lines.append(
                        f'{name}_bucket{_prom_labels(labels, le=bound)} {acc}')
                acc += counts[-1]
                lines.append(
                    f'{name}_bucket{_prom_labels(labels, le="+Inf")} {acc}')
                lines.append(f"{name}_sum{_prom_labels(labels)} {total}")
                lines.append(f"{name}_count{_prom_labels(labels)} {n}")
        return "\n".join(lines) + "\n"


class CompileWatch:
    """Turns jitted-program cache growth into ``compile_events_total``.

    Wraps one ``jax.jit`` result; ``check()`` (called after invocations,
    off the hot device path) diffs ``_cache_size()`` against the last
    observed value and increments the counter by exactly the number of
    NEW compiled programs, labeled with ``phase`` — so a compile storm
    (e.g. unbucketed shapes minting a program per request) is visible as
    a counter burst, distinguishable from steady-state latency.
    """

    def __init__(self, phase: str, fn):
        self.phase = phase
        self._fn = fn
        self._seen = 0
        # solo-mode engines are called straight from server handler
        # threads — an unsynchronized read-modify-write of _seen would
        # let two concurrent checks double-count the same new program
        self._lock = graftsched.lock("metrics.CompileWatch._lock")

    def seen(self) -> int:
        """Programs observed so far (locked read — gauge derivations in
        engine/spec_decode run on handler threads concurrent with
        ``check``)."""
        with self._lock:
            return self._seen

    def check(self, registry: "MetricsRegistry" = None) -> int:
        size_of = getattr(self._fn, "_cache_size", None)
        if size_of is None:  # non-jit stub (tests)
            return 0
        size = size_of()
        with self._lock:
            new = size - self._seen
            if new > 0:
                self._seen = size
        if new > 0:
            (registry or REGISTRY).inc("compile_events_total", value=new,
                                       phase=self.phase)
        return max(new, 0)


def _fmt_name(name: str, labels) -> str:
    if not labels:
        return name
    return name + "{" + ",".join(f"{k}={v}" for k, v in labels) + "}"


def _escape_label_value(v) -> str:
    """Escape a label value per the Prometheus text-format spec:
    backslash, double-quote, and line-feed must be escaped, or the
    exposition line is invalid and the scraper drops the WHOLE page."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _prom_labels(labels, le=None) -> str:
    items = list(labels)
    if le is not None:
        items = items + [("le", le)]
    if not items:
        return ""
    return "{" + ",".join(
        f'{k}="{_escape_label_value(v)}"' for k, v in items) + "}"


# process-wide default registry (what serving.app uses)
REGISTRY = MetricsRegistry()
