"""graftmem: declared HBM ledger — live byte attribution + drift watch.

The spine could attribute device *time* (utils.graftscope) and causal
*order* (utils.grafttime) but not device *memory*: the cost model's
``hbm_bytes_per_device`` prediction (tools/graftcheck/costmodel.py) was
checked once in a golden test and never reconciled against the running
process. This module closes the byte gap the same way graftscope closed
the time gap — a declared contract, a live ledger, and a drift watch:

- **the ledger**: every long-lived device allocation registers with
  component provenance via ``track(owner, holding, component, value)``
  (model params, pool code/scale planes, contiguous caches, spec-decode
  buffers, prefix-store holdings — the :data:`MEMORY_COMPONENTS`
  vocabulary). Bytes are measured from the ACTUAL jax buffers
  (``leaf.nbytes`` over the registered pytree), never re-derived from
  shape arithmetic, so the ledger is the measured side of every
  measured-vs-modeled comparison. ``update`` re-measures a rebound
  holding; ``release`` retires it; a ``weakref.finalize`` on the owner
  retires anything a GC'd owner left behind.
- **the declared contract**: each runtime/ module lists
  ``MEMORY_LEDGER = {holding: component}`` beside JIT_ENTRY_POINTS;
  ``tools/graftcheck/memory.py`` statically verifies every persistent
  device-array attribute is declared, every declaration is live, and
  container accumulation of device arrays has a declared bound.
- **the drift watch**: ``reconcile(plan_row)`` confronts the cost
  model's ``param_bytes_per_device`` / pool-footprint predictions with
  the ledger's live bytes per component and reports the ratio —
  graftscope's measured-vs-modeled pattern, applied to bytes. bench.py
  journals it (``hbm_attribution``), bench_diff gates drift
  lower-better.

Every mutation samples the per-component total into graftscope's
occupancy rings (gauge ``hbm_bytes{component}``), publishes the same
gauge to /metrics, and lands a ``mem_alloc``/``mem_free`` byte-delta
event on the grafttime bus — so residency trajectories sit on the same
clock as the admissions, evictions, and plan switches that moved them.
``GET /debug/memory`` (serving/app.py) serves ``snapshot()``.

Conservation (the blocks_in_use+blocks_free==blocks_total discipline):
``snapshot()["conserved"]`` cross-checks the per-entry table against
the independently maintained running component/grand totals — /healthz
turns a disagreement into a 500, because a ledger that cannot account
for its own bytes must not report capacity.

``GRAFTMEM=0`` disables recording entirely (``track`` returns the null
handle 0; ``update``/``release`` on it are no-ops).
"""

from __future__ import annotations

import os
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

from . import graftsched, grafttime

# Lock-discipline contract (tools/graftcheck locks pass): the entry
# table and the running totals are written by engine/scheduler threads
# and read by /debug/memory and /healthz handlers concurrently — all
# under the ledger instance's ``_lock``. Bus/gauge emission happens
# OUTSIDE the hold (the apparatus stays off its own critical section).
GUARDED_STATE = {"_entries": "_lock", "_component_totals": "_lock",
                 "_total": "_lock", "_peaks": "_lock"}
LOCK_ORDER = ("_lock",)

# Timeline contract (tools/graftcheck timeline pass): every byte delta
# lands on the unified causal stream — an OOM-shaped residency climb is
# only diagnosable when it sits on the same clock as the admissions and
# evictions that drove it.
TIMELINE_EVENTS = {
    "mem_alloc": "MemoryLedger._emit",
    "mem_free": "MemoryLedger._emit",
}

# THE component vocabulary (tools/graftcheck/memory.py rejects a
# MEMORY_LEDGER declaration whose component falls outside it — a new
# residency class is a reviewed vocabulary change, not an ad-hoc
# string). Keep in sync with the ARCHITECTURE.md component table.
MEMORY_COMPONENTS = {
    "params":       "model parameter tree (placed or host-staged)",
    "pool_codes":   "paged KV pool block-storage plane (KVBlockPool"
                    ".data — full-precision or quantized codes)",
    "pool_scales":  "quantized pool per-block f32 scales plane "
                    "(KVBlockPool.scales)",
    "pool_latent":  "paged pool of a one-plane (latent) cache: one "
                    "vector a position a layer (KVBlockPool.data)",
    "state_slab":   "per-row state records beside the paged pool: the "
                    "prefix store's snapshots (StateSlab.data)",
    "engine_cache": "contiguous KV caches and in-flight decode "
                    "working views (engine / iterbatch batch state)",
    "spec_buffers": "speculative-decode device token buffers",
    "prefix_store": "prefix-cache store holdings (non-pool mode "
                    "deep-copied cache pytrees)",
    "host_spill":   "grafttier host-RAM spill store (demoted prefix "
                    "entries' raw block codes + scales as numpy)",
}

# snapshot() holdings-table bound: hottest entries first, truncation
# marked (the graftscope keys-table discipline — a silent cap would
# read as "everything shown" exactly when a leak mints too many)
HOLDINGS_CAPACITY = 64

_enabled = [os.environ.get("GRAFTMEM", "1") != "0"]


def enabled() -> bool:
    return _enabled[0]


def set_enabled(value: bool) -> bool:
    """Toggle recording (returns the previous value). Tests use this
    for disabled-path coverage; production leaves it on."""
    prev = _enabled[0]
    _enabled[0] = bool(value)
    return prev


def measure(value: Any) -> Tuple[int, Dict[str, int]]:
    """Total live bytes and per-device attribution for one holding:
    the sum of ``leaf.nbytes`` over the pytree's array leaves — the
    buffers jax actually committed, never shape arithmetic. Per-device
    attribution comes from each leaf's ``addressable_shards`` when the
    runtime exposes them (a sharded leaf attributes each shard's bytes
    to its device); leaves without shard info attribute their full
    ``nbytes`` to ``"unsharded"``."""
    import jax  # deferred: the ledger must import before any backend

    total = 0
    devices: Dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(value):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None:
            continue
        total += int(nbytes)
        shards = getattr(leaf, "addressable_shards", None)
        attributed = False
        if shards:
            try:
                for sh in shards:
                    data = getattr(sh, "data", None)
                    sb = getattr(data, "nbytes", None)
                    if sb is None:
                        continue
                    dev = str(getattr(sh, "device", "unsharded"))
                    devices[dev] = devices.get(dev, 0) + int(sb)
                    attributed = True
            except Exception:  # noqa: BLE001 — attribution is
                attributed = False  # best-effort; totals are not
        if not attributed:
            devices["unsharded"] = devices.get("unsharded", 0) + int(nbytes)
    return total, devices


class MemoryLedger:
    """The process-wide byte ledger: a handle-keyed entry table (one
    entry per tracked holding instance — concurrent generates on one
    engine each hold their own working-cache entry without collision)
    plus independently maintained running per-component and grand
    totals (the redundancy IS the conservation check)."""

    def __init__(self):
        self._lock = graftsched.lock("graftmem.MemoryLedger._lock")
        # handle -> {"owner_id", "owner", "holding", "component",
        #            "bytes", "devices"}
        self._entries: Dict[int, dict] = {}
        # running totals, maintained incrementally on every mutation —
        # deliberately NOT derived from the entry table, so snapshot()
        # can cross-check the two bookkeeping paths (conservation)
        self._component_totals: Dict[str, int] = {}
        self._total = 0
        # component -> [peak_bytes, t_ms_at_peak]; "" keys the grand
        # total's peak
        self._peaks: Dict[str, list] = {}
        self._next_handle = 1
        self.t0 = time.perf_counter()

    # -- recording -----------------------------------------------------------

    def _now_ms(self) -> float:
        return (time.perf_counter() - self.t0) * 1e3

    def _emit(self, component: str, delta: int, comp_total: int,
              total: int) -> None:
        # outside the ledger lock by construction (callers compute the
        # deltas under the hold, then emit). The graftscope sample
        # mirrors onto grafttime as ``occupancy`` itself; the byte
        # delta additionally lands as its own mem_* event so replay
        # and Perfetto see allocation CAUSALITY, not just the series.
        from . import graftscope
        from .metrics import REGISTRY
        if delta >= 0:
            grafttime.emit("mem_alloc", component=component,
                           bytes=int(delta), total=int(comp_total))
        else:
            grafttime.emit("mem_free", component=component,
                           bytes=-int(delta), total=int(comp_total))
        graftscope.sample("hbm_bytes", float(comp_total),
                          component=component)
        REGISTRY.gauge("hbm_bytes", float(comp_total),
                       component=component)
        REGISTRY.gauge("hbm_bytes", float(total), component="total")

    def track(self, owner: Any, holding: str, component: str,
              value: Any) -> int:
        """Register one long-lived device holding; returns the entry's
        handle (0 when disabled). ``component`` must be in
        :data:`MEMORY_COMPONENTS` (the static pass verifies call sites;
        the runtime check catches dynamic drift). The owner is held
        weakly — a GC'd owner's entries auto-release."""
        if not _enabled[0]:
            return 0
        if component not in MEMORY_COMPONENTS:
            raise ValueError(
                f"component {component!r} outside the graftmem "
                f"vocabulary {sorted(MEMORY_COMPONENTS)}")
        nbytes, devices = measure(value)
        with self._lock:
            handle = self._next_handle
            self._next_handle += 1
            self._entries[handle] = {
                "owner_id": id(owner),
                "owner": type(owner).__name__,
                "holding": holding,
                "component": component,
                "bytes": nbytes,
                "devices": devices,
            }
            comp_total = self._component_totals.get(component, 0) + nbytes
            self._component_totals[component] = comp_total
            self._total += nbytes
            total = self._total
            self._note_peaks_locked(component, comp_total, total)
        try:
            weakref.finalize(owner, self.release, handle)
        except TypeError:
            pass  # non-weakref-able owner: explicit release only
        self._emit(component, nbytes, comp_total, total)
        return handle

    def update(self, handle: int, value: Any) -> None:
        """Re-measure a rebound holding (pool buffer through a donated
        mover, batch cache through grow/admit) against the same entry."""
        if not _enabled[0] or not handle:
            return
        nbytes, devices = measure(value)
        with self._lock:
            entry = self._entries.get(handle)
            if entry is None:
                return
            delta = nbytes - entry["bytes"]
            entry["bytes"] = nbytes
            entry["devices"] = devices
            component = entry["component"]
            comp_total = self._component_totals.get(component, 0) + delta
            self._component_totals[component] = comp_total
            self._total += delta
            total = self._total
            self._note_peaks_locked(component, comp_total, total)
        if delta:
            self._emit(component, delta, comp_total, total)

    def release(self, handle: int) -> None:
        """Retire one holding (idempotent — the weakref finalizer and
        an explicit release may both fire)."""
        if not handle:
            return
        with self._lock:
            entry = self._entries.pop(handle, None)
            if entry is None:
                return
            nbytes = entry["bytes"]
            component = entry["component"]
            comp_total = self._component_totals.get(component, 0) - nbytes
            self._component_totals[component] = comp_total
            self._total -= nbytes
            total = self._total
        if nbytes:
            self._emit(component, -nbytes, comp_total, total)

    def _note_peaks_locked(self, component: str, comp_total: int,
                           total: int) -> None:
        now = self._now_ms()
        peak = self._peaks.get(component)
        if peak is None or comp_total > peak[0]:
            self._peaks[component] = [comp_total, round(now, 3)]
        gpeak = self._peaks.get("")
        if gpeak is None or total > gpeak[0]:
            self._peaks[""] = [total, round(now, 3)]

    # -- reading -------------------------------------------------------------

    def component_bytes(self) -> Dict[str, int]:
        """Per-component live bytes, derived from the entry table (the
        bookkeeping path conservation checks AGAINST the running
        totals)."""
        with self._lock:
            out: Dict[str, int] = {}
            for entry in self._entries.values():
                c = entry["component"]
                out[c] = out.get(c, 0) + entry["bytes"]
            return out

    def total_bytes(self) -> int:
        with self._lock:
            return int(self._total)

    def peak_bytes(self) -> int:
        with self._lock:
            peak = self._peaks.get("")
            return peak[0] if peak else 0

    def holding_bytes(self, owner: Any, holding: str) -> int:
        """Live bytes of one owner's named holding (sum over its
        entries) — what /healthz derives ``pool_bytes`` from, so pool
        byte reporting has exactly ONE bookkeeping path."""
        oid = id(owner)
        with self._lock:
            return sum(e["bytes"] for e in self._entries.values()
                       if e["owner_id"] == oid
                       and e["holding"] == holding)

    def snapshot(self) -> dict:
        """Bounded JSON view (the /debug/memory payload body): the
        per-component table with peaks, per-device attribution, the
        hottest holdings, and the conservation verdict."""
        with self._lock:
            derived: Dict[str, int] = {}
            devices: Dict[str, int] = {}
            holdings: List[dict] = []
            for entry in self._entries.values():
                c = entry["component"]
                derived[c] = derived.get(c, 0) + entry["bytes"]
                for dev, b in entry["devices"].items():
                    devices[dev] = devices.get(dev, 0) + b
                holdings.append({
                    "component": c,
                    "holding": entry["holding"],
                    "owner": entry["owner"],
                    "bytes": entry["bytes"],
                })
            running = {c: b for c, b in self._component_totals.items()
                       if b or derived.get(c)}
            total = self._total
            entries_n = len(self._entries)
            peaks = {(c or "total"): {"bytes": p[0], "t_ms": p[1]}
                     for c, p in self._peaks.items()}
        conserved = (derived == running
                     and sum(running.values()) == total)
        holdings.sort(key=lambda h: h["bytes"], reverse=True)
        components = {
            c: {"bytes": running.get(c, 0),
                "entries": sum(1 for h in holdings
                               if h["component"] == c),
                "peak_bytes": peaks.get(c, {}).get("bytes", 0)}
            for c in sorted(set(running) | set(derived))}
        out = {
            "enabled": enabled(),
            # the honesty header (the utils.tracing contract): what
            # these numbers are and are not
            "truth": ("bytes are live jax buffer nbytes summed over "
                      "REGISTERED holdings (the MEMORY_LEDGER "
                      "contract) — transient activations and XLA "
                      "scratch are not ledger entries; per-device "
                      "attribution uses addressable_shards where the "
                      "runtime exposes them"),
            "components": components,
            "total_bytes": total,
            "peak_bytes": peaks.get("total", {}).get("bytes", 0),
            "peaks": peaks,
            "devices": devices,
            "entries": entries_n,
            "holdings": holdings[:HOLDINGS_CAPACITY],
            "conserved": conserved,
        }
        if len(holdings) > HOLDINGS_CAPACITY:
            out["holdings_truncated"] = True
        return out

    def reconcile(self, plan_row) -> dict:
        """Drift between the cost model's predicted footprint and the
        ledger's live bytes (graftscope's measured-vs-modeled pattern,
        applied to bytes). ``plan_row`` is a ``costmodel.PlanRow`` or
        its ``to_dict()`` — predicted ``param_bytes_per_device`` and
        ``kv_bytes_per_device`` compare against the ledger's ``params``
        and ``pool_codes``+``pool_scales`` components. Ratios are
        measured/predicted; on a single-device process the ledger total
        IS per-device, which is what the CPU exactness pins exercise.
        A quantized pool drifts BELOW the f32-aval prediction by
        design — reconcile reports it, the capacity bench journals it."""
        row = (plan_row.to_dict() if hasattr(plan_row, "to_dict")
               else dict(plan_row))
        comp = self.component_bytes()
        measured_params = comp.get("params", 0)
        measured_pool = (comp.get("pool_codes", 0)
                         + comp.get("pool_scales", 0)
                         + comp.get("pool_latent", 0))
        measured_cache = comp.get("engine_cache", 0)

        def _cmp(measured: int, predicted) -> dict:
            predicted = int(predicted or 0)
            out = {"measured_bytes": measured,
                   "predicted_bytes": predicted}
            if predicted > 0:
                ratio = measured / predicted
                out["ratio"] = round(ratio, 6)
                out["drift"] = round(abs(ratio - 1.0), 6)
            return out

        components = {
            "params": _cmp(measured_params,
                           row.get("param_bytes_per_device")),
            "kv": _cmp(measured_pool or measured_cache,
                       row.get("kv_bytes_per_device")),
        }
        total_measured = self.total_bytes()
        out = {
            "plan": row.get("label"),
            "components": components,
            "total": _cmp(total_measured,
                          row.get("hbm_bytes_per_device")),
            "ledger": comp,
        }
        drifts = [c["drift"] for c in components.values()
                  if "drift" in c]
        if drifts:
            out["max_component_drift"] = max(drifts)
        return out

    # -- test isolation (tests/conftest.py) ----------------------------------

    def dump_state(self) -> tuple:
        with self._lock:
            return (dict(self._entries),
                    dict(self._component_totals),
                    self._total,
                    {k: list(v) for k, v in self._peaks.items()},
                    self._next_handle, self.t0)

    def restore_state(self, state: tuple) -> None:
        entries, totals, total, peaks, next_handle, t0 = state
        with self._lock:
            self._entries = dict(entries)
            self._component_totals = dict(totals)
            self._total = total
            self._peaks = {k: list(v) for k, v in peaks.items()}
            # never rewind the handle counter: entries registered after
            # the dump vanish here, but their owners' finalizers may
            # still fire release(handle) later — a rewound counter would
            # hand the same id to a NEW entry and the stale finalizer
            # would free it (handles stay process-unique instead)
            self._next_handle = max(self._next_handle, next_handle)
            self.t0 = t0

    def clear(self) -> None:
        # _next_handle deliberately NOT rewound (see restore_state):
        # finalizers of owners created before the clear may still fire
        # release(handle), and a reused id would free the wrong entry
        with self._lock:
            self._entries = {}
            self._component_totals = {}
            self._total = 0
            self._peaks = {}
            self.t0 = time.perf_counter()


# process-wide default ledger (what the runtime modules and serving app
# register against; tests snapshot/restore it via the conftest fixture)
STATE = MemoryLedger()


# -- module-level conveniences (the call-site API the static pass scans) ------


def track(owner: Any, holding: str, component: str, value: Any) -> int:
    handle = STATE.track(owner, holding, component, value)
    # ledger registration is the one moment the VALUE itself is in hand,
    # so the live placement auditor (utils/graftshard, GRAFTSHARD=1)
    # piggybacks here; unarmed it is a single env-var check
    from . import graftshard
    graftshard.observe_track(owner, holding, component, value, handle)
    return handle


def update(handle: int, value: Any) -> None:
    STATE.update(handle, value)
    from . import graftshard
    graftshard.observe_update(handle, value)


def release(handle: int) -> None:
    STATE.release(handle)
    from . import graftshard
    graftshard.observe_release(handle)


def holding_bytes(owner: Any, holding: str) -> int:
    return STATE.holding_bytes(owner, holding)


def component_bytes() -> Dict[str, int]:
    return STATE.component_bytes()


def total_bytes() -> int:
    return STATE.total_bytes()


def peak_bytes() -> int:
    return STATE.peak_bytes()


def snapshot() -> dict:
    return STATE.snapshot()


def reconcile(plan_row) -> dict:
    return STATE.reconcile(plan_row)


def dump_state() -> tuple:
    return STATE.dump_state()


def restore_state(state: tuple) -> None:
    STATE.restore_state(state)


def clear() -> None:
    STATE.clear()
