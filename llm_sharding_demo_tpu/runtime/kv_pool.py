"""Paged KV-cache memory subsystem: block pool, CoW sharing, admission.

After PRs 1-3 the binding serving constraint is KV memory, not
scheduling: every decode row owns a contiguous ``max_seq`` cache for its
whole lifetime, the prefix store duplicated entire prefill states per
entry, and nothing sheds load under pressure — the reference, of course,
has no KV state at all (it re-forwards the full sequence per token,
reference server.py:169-181). This module is the first-class manager:

- ``BlockAllocator`` — host-side, device-free accounting: ref-counted
  blocks, a content-keyed prefix registry whose entries share blocks
  structurally (entry for chunks [0, m) references the same physical
  blocks as the deeper entry for [0, m+k) — the duplication the old
  store paid is gone), LRU eviction of zero-ref prefix blocks, and
  watermark admission (``can_admit`` holds back a growth reserve so
  live batches can deepen without instantly preempting).
- ``KVBlockPool`` — the device pool (one
  ``[L, num_blocks+1, 2, Hkv, block_size, hd]`` buffer; per layer the
  ``[num_blocks, 2, n_kv_head, block_size, head_dim]`` block array,
  plus the shared trash block) + the jitted gather/scatter/copy
  programs over it (``ops.paged_attention``) and the pool-derived
  ``kv_cache_blocks_*`` gauges.
- ``PagedKVRunner`` — solo/batched paged decode over an unmodified
  ``DecodeEngine``: prefill with THE engine's program, scatter the
  state into blocks, then per decode segment gather -> run the
  engine's OWN ``_decode_seg`` -> scatter back. The compiled model
  programs are untouched and shared with contiguous serving, so paged
  decode is byte-equal by construction (greedy and seeded sample,
  pinned). With a pool-backed ``PrefixCachingEngine`` attached, a
  prefix hit REFERENCES the store's blocks in the row's table instead
  of copying the prefill state — live decode and the prefix store
  share one physical copy, with the partially-filled frontier block
  copy-on-write'd before the row's first write into it.

Quantized block storage (``block_dtype="int8"`` / ``"fp8"``, the
serving ``KV_POOL_DTYPE`` knob): the pool stores narrow codes plus one
f32 absmax scale per (layer, block, k|v, kv-head) — ``ops.kv_quant`` —
with quantize-on-scatter / dequant-on-gather movers (``_gather_q`` /
``_scatter_q`` / ``_scatter_row_q`` / ``_copy_q``, the ``_q`` jit
family). At int8 that is ~4x the f32 pool's rows-per-byte at equal HBM:
the allocator contract (refcounts, CoW, prefix sharing, GRAFTSAN
provenance) is untouched — quantization changes block CONTENTS only —
while capacity-per-byte scales with the narrow dtype. The path is
``exact: False`` under the ``kv.int8``/``kv.fp8`` tolerance budgets
(utils.graftnum); full-precision pools construct ONLY the plain mover
family, so every paged≡contiguous byte-equality pin is structurally
confined to them.

Preemption (the admission story's other half) lives in
``runtime.iterbatch``: under pool exhaustion the scheduler parks the
lowest-priority row, frees its blocks, and later resumes it by
RECOMPUTE — re-prefilling prompt + already-emitted tokens and
continuing the row's own per-step PRNG chain, which reproduces the
un-preempted stream byte-identically (prefix-stable key splits +
prefill/incremental KV equality, pinned by tests). ``serving.app``
turns sustained exhaustion into 429 + Retry-After instead of queueing
unboundedly.

Block lifecycle (docs/ARCHITECTURE.md has the full diagram)::

    free -> allocated (ref=1, private)
         -> shared    (ref>1: live table refs and/or prefix entries)
         -> evictable (ref held only by prefix entries, LRU-ordered)
         -> free      (last ref dropped / entry evicted)

Writers never mutate a shared block: extension into a shared frontier
block goes through ``cow_copy`` (allocate, copy, retarget the table
entry, deref the original).
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time
import weakref
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kv_quant as KVQ
from ..ops import paged_attention as PA
from ..ops.attention import KVCache
from ..utils import graftfault, graftmem, graftsched, graftscope, \
    grafttime, tracing
from ..utils.metrics import DEFAULT_KV_BLOCK_SIZE, REGISTRY, CompileWatch
from .engine import (DecodeEngine, GenerateResult, SamplingConfig,
                     _eos_capped_segments, _split_keys, _step_keys,
                     prepare_generate, select_token)

# Static-analysis contract (tools/graftcheck): every ``jax.jit`` site in
# this module, by holding attribute — enumerated by the recompile-budget
# certifier; an undeclared site is a lint finding. ``_poison`` is the
# sanitizer's free-block poisoner (GRAFTSAN=1 only — see GraftsanError).
# The ``_q`` names are the quantized-pool mover family (constructed
# instead of — never alongside — the plain family when ``block_dtype``
# is set); ``_poison_q`` is its GRAFTSAN-only poisoner.
JIT_ENTRY_POINTS = ("_gather", "_scatter", "_scatter_span", "_scatter_row",
                    "_copy", "_poison", "_gather_q", "_scatter_q",
                    "_scatter_row_q", "_copy_q", "_poison_q")

# Observability contract (tools/graftcheck scope pass + utils/graftscope):
# every serving-path mover's dispatch is timed into the graftscope ring,
# keyed (batch, table width) — the certifier's paged_runner_keys model.
# ``_poison``/``_poison_q`` are deliberately NOT profiled: they are the
# GRAFTSAN-only free-block poisoners, sanitizer hooks off every serving
# path — baselined in tools/graftcheck/baseline.txt with that
# justification.
PROFILED_SCOPES = ("_gather", "_scatter", "_scatter_span", "_scatter_row",
                   "_copy", "_gather_q", "_scatter_q", "_scatter_row_q",
                   "_copy_q")

# Timeline contract (tools/graftcheck timeline pass): the allocator's
# LRU evictions land on the unified causal stream (utils/grafttime) —
# an eviction storm is only diagnosable when it sits on the same clock
# as the admissions/preemptions that provoked it. (Admission events are
# the SCHEDULERS' story — iterbatch emits them with the rid; the
# allocator's view is the block economy.)
TIMELINE_EVENTS = {
    "eviction": "BlockAllocator._evict_lru_locked",
}

# HBM-ledger contract (tools/graftcheck memory pass + utils/graftmem):
# the pool's two long-lived device planes, by graftmem component. The
# block-storage plane holds full-precision blocks OR quantized codes
# (one buffer either way — ``pool_codes`` names the plane, the
# ``block_dtype`` stats field names what a block IS); the f32 scales
# plane exists only for quantized pools. Sizes are CONSTANT across the
# donated movers (every rebind is shape-identical), so registration at
# construction is the whole lifecycle — /healthz derives ``pool_bytes``
# from these entries, never from shape arithmetic.
MEMORY_LEDGER = {
    "data": "pool_codes",
    "scales": "pool_scales",
    # a one-plane pool (a latent cache: one vector a position) is the
    # same buffer under its own holding and component, so a byte table
    # tells the two kinds of pool apart
    "latent": "pool_latent",
}

# Placement contract (tools/graftcheck placement pass + utils/
# graftshard): the pool's two device planes are EXPLICITLY replicated
# today — the single-device paged engine owns the whole block table.
# ``kvp`` is the declared partition axis a mesh-sharded pool will
# split the kv-head dim over (ROADMAP item 1; the planner already
# enumerates and prices kvp candidates against this vocabulary) — the
# builder that lands it flips these holdings to "kvp" and the dynamic
# auditor (GRAFTSHARD=1) starts requiring that placement on the live
# buffers at track()/update() time.
PLACEMENT_CONTRACT = {
    "mesh_axes": ("kvp",),
    "holding:data": "replicated",
    "holding:scales": "replicated",
}


# graftscope program-key derivations (the certifier's model: gather/
# scatter key by (batch, table width) — block ids and placement are
# traced operands and never key programs)

def _gather_scope_key(pool, tables):
    return (int(tables.shape[0]), int(tables.shape[1]))


def _scatter_scope_key(pool, k, v, tables):
    return (int(tables.shape[0]), int(tables.shape[1]))


def _scatter_span_scope_key(pool, k, v, tables, col, span):
    # the first column is traced: a width's calls share one program
    return (int(tables.shape[0]), int(tables.shape[1]), int(span))


def _scatter_row_scope_key(pool, k, v, table_row, roll):
    return (int(k.shape[-2]), int(table_row.shape[0]))


def _copy_scope_key(pool, src, dst):
    return (int(src.shape[0]),)


# quantized-family keys: same (batch, table width) model — the scale
# array rides along as a second carried operand and never keys programs
# beyond the shapes the data already keys

def _gather_q_scope_key(data, scales, tables):
    return (int(tables.shape[0]), int(tables.shape[1]))


def _scatter_q_scope_key(data, scales, k, v, tables):
    return (int(tables.shape[0]), int(tables.shape[1]))


def _scatter_row_q_scope_key(data, scales, k, v, table_row, roll):
    return (int(k.shape[-2]), int(table_row.shape[0]))


def _copy_q_scope_key(data, scales, src, dst):
    return (int(src.shape[0]),)

# Donation contract (tools/graftcheck sanitize pass): the pool movers
# all consume the pool buffer itself (arg 0) — ``self.data`` is re-bound
# from every call's output under ``_dev_lock``, and nothing may hold a
# host view of it. The quantized movers additionally consume the scale
# array (arg 1): ``self.scales`` is re-bound in the same statement, so
# (data, scales) stay one atomic device state.
DONATED_ARGS = {"_scatter": (0,), "_scatter_span": (0,),
                "_scatter_row": (0,), "_copy": (0,),
                "_poison": (0,), "_scatter_q": (0, 1),
                "_scatter_row_q": (0, 1), "_copy_q": (0, 1),
                "_poison_q": (0, 1)}

# Pool-mover lease scopes (tools/graftcheck sanitize pass): the paged
# runner's two mover sites — every block id they move is a live
# allocation of this generate (owned/shared row ids) or the trash block.
POOL_MOVER_SCOPES = ("PagedKVRunner._prefill_tables",
                     "PagedKVRunner._decode")

# Tier-movement contract (tools/graftcheck tier pass): the ONLY scope
# here allowed to invoke tier movement is the pressure hook wired by
# attach_tier — the allocator calls it OUTSIDE ``_lock``, and every
# other demotion/promotion site lives in kv_tier/prefix_cache behind
# their own SPILL_SCOPES declarations.
SPILL_SCOPES = ("KVBlockPool.attach_tier",)

# Lock-discipline contract (tools/graftcheck locks pass): every shared
# mutable attribute, by guarding lock. The allocator's accounting
# (free list, refcounts, prefix registry, sanitizer provenance,
# counters) lives under its reentrant ``_lock``; the device pool buffer
# is rebound only under ``_dev_lock``. ``*_locked``-suffix helpers run
# with the caller's hold by convention.
GUARDED_STATE = {
    "_free": "_lock", "_ref": "_lock", "_prefix": "_lock",
    "_prefix_ref": "_lock", "_san_*": "_lock",
    "evictions": "_lock", "cow_copies": "_lock", "_dropped": "_lock",
    "data": "_dev_lock", "scales": "_dev_lock",
}

# Numerics contract (tools/graftcheck numerics pass): the quantized
# mover family is ``exact: False`` — it routes to the seeded ``kv.*``
# tolerance budgets in utils/graftnum.py TOLERANCE_POLICY. The entries
# name the per-instance nested impls (the lint resolver indexes nested
# defs by qualname suffix). All four are ``carried``: the narrowing/
# widening casts live in ops.kv_quant's own contracted quantizers —
# these impls carry (data, scales) through and pick the regime's
# quantizer at construction. ``kv.int8`` is the representative oracle
# path for the regime-shared programs (gather/copy compile once per
# shape for either storage dtype); the fp8-specific budget routes
# through ops.kv_quant's ``scatter_kv_fp8``/``quantize_blocks_fp8``.
PRECISION_CONTRACT = {
    "_gather_q_impl": {"regime": "carried", "exact": False,
                       "oracle": "kv.int8", "casts": ("carried",)},
    "_scatter_q_impl": {"regime": "carried", "exact": False,
                        "oracle": "kv.int8", "casts": ("carried",)},
    "_scatter_row_q_impl": {"regime": "carried", "exact": False,
                            "oracle": "kv.int8", "casts": ("carried",)},
    "_copy_q_impl": {"regime": "carried", "exact": True, "casts": ()},
}

# Permitted acquisition order: device ops validate tables against live
# allocator state, so ``_dev_lock`` may hold across an ``_lock``
# acquisition — never the reverse (``_notify_freed`` fires the poison
# hook OUTSIDE ``_lock`` precisely to keep this order acyclic).
LOCK_ORDER = ("_dev_lock", "_lock")

# Locks whose documented job is serializing DEVICE work: jit dispatch /
# device sync under them is the design (the pool buffer is donated
# through every scatter; the solo runner runs one generation at a
# time), not a blocking-under-lock finding.
DEVICE_LOCKS = ("_dev_lock", "_gen_lock")

# gauge/stats label spelling for full-precision storage, keyed by numpy
# dtype name — the quantized regimes label with their graftnum tokens
# directly, so the ``block_dtype`` label space is exactly the regime
# vocabulary
_REGIME_LABELS = {"float32": "f32", "bfloat16": "bf16",
                  "float16": "f16", "float64": "f64"}


def bytes_per_block(n_layer: int, n_kv_head: int, block_size: int,
                    head_dim: int, dtype=jnp.float32,
                    block_dtype: Optional[str] = None,
                    planes: int = 2) -> int:
    """HBM bytes one physical block costs, scales included: the unit
    the capacity bench (`kv_quant_capacity`) uses to size an int8 and
    an f32 pool to the SAME byte budget, and the number the
    ``kv_pool_bytes_per_block`` gauge publishes. Quantized blocks pay
    ``2 * n_kv_head`` f32 scales per layer on top of the narrow codes
    (1/(block_size*head_dim) of the data — negligible, but counted).
    ``planes``/``n_kv_head``/``head_dim`` are the family's cache entry
    (``models.cache_entry``): a one-plane latent pool's block holds
    ``block_size x width`` values a layer."""
    slots = n_layer * planes * n_kv_head * block_size * head_dim
    if block_dtype is None:
        return slots * np.dtype(dtype).itemsize
    storage = KVQ.STORAGE_DTYPES[block_dtype]
    scale_bytes = n_layer * 2 * n_kv_head * np.dtype(np.float32).itemsize
    return slots * np.dtype(storage).itemsize + scale_bytes


class PoolExhausted(RuntimeError):
    """No allocation possible even after evicting every zero-ref prefix
    entry. Schedulers catch this and preempt; serving turns sustained
    exhaustion into 429."""


class GraftsanError(RuntimeError, ValueError):
    """A memory-safety invariant violation caught by the graftsan
    dynamic sanitizer (``GRAFTSAN=1``): double-free, use-after-free
    gather/scatter, CoW write to a shared block, refcount-conservation
    drift, or a leak at teardown. Messages carry the offending block id
    and the provenance (call sites) of the grants/frees involved.

    Also a ``ValueError``: the sanitizer UPGRADES the allocator's plain
    double-free ValueError with provenance, and callers (and tests)
    catching the documented ValueError contract must keep working when
    the sanitizer is armed."""


def _graftsan_enabled() -> bool:
    return os.environ.get("GRAFTSAN", "") not in ("", "0")


def _call_site(skip_file: str = __file__) -> str:
    """``file.py:line (func)`` of the nearest caller frame outside this
    module — the provenance unit the sanitizer records per grant/free."""
    f = sys._getframe(1)
    while f is not None and f.f_code.co_filename == skip_file:
        f = f.f_back
    if f is None:
        return "<unknown>"
    return (f"{os.path.basename(f.f_code.co_filename)}:{f.f_lineno} "
            f"({f.f_code.co_name})")


# live sanitizing allocators, for suite-level teardown sweeps
# (``graftsan_sweep`` — the conftest hook under GRAFTSAN=1)
_SAN_ALLOCATORS: "weakref.WeakSet[BlockAllocator]" = weakref.WeakSet()


def graftsan_sweep(timeout: float = 2.0) -> None:
    """Assert every live sanitizing allocator is quiesced (no leaked
    caller refs): the teardown hook the suite runs after each test
    under ``GRAFTSAN=1``. Raises ``GraftsanError`` listing each leaked
    block with its grant-site provenance."""
    for alloc in list(_SAN_ALLOCATORS):
        alloc.graftsan_assert_quiesced(timeout=timeout)


@dataclasses.dataclass(frozen=True)
class PoolStats:
    blocks_total: int
    blocks_free: int
    blocks_in_use: int      # any ref (live rows and/or prefix entries)
    blocks_evictable: int   # in_use blocks whose refs are ALL prefix refs
    prefix_entries: int
    evictions: int
    cow_copies: int

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class BlockAllocator:
    """Host-side ref-counted block accounting. Pure bookkeeping — no
    device arrays — so every policy (refcounts, CoW, LRU, watermarks)
    is unit-testable without a pool.

    ``watermark`` bounds ADMISSION, not allocation: ``can_admit(n)``
    refuses while ``n`` would push referenced blocks past
    ``watermark * num_blocks``, keeping the remainder free as growth
    headroom for already-admitted rows (so preemption stays the
    exception, not the steady state). ``alloc`` itself may use the
    reserve — that is what it is for.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 watermark: float = 0.9,
                 sanitize: Optional[bool] = None):
        if num_blocks < 1:
            raise ValueError(f"num_blocks={num_blocks} must be >= 1")
        if block_size < 1:
            raise ValueError(f"block_size={block_size} must be >= 1")
        if not 0.0 < watermark <= 1.0:
            raise ValueError(f"watermark={watermark} must be in (0, 1]")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.watermark = watermark
        self._lock = graftsched.rlock("kv_pool.BlockAllocator._lock")
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._ref: Dict[int, int] = {}
        # content-key -> tuple(block ids); insertion order IS the LRU
        # order (lookups move_to_end). Each entry holds one ref per id,
        # tracked separately in _prefix_ref so "evictable" is decidable.
        self._prefix: "OrderedDict[bytes, Tuple[int, ...]]" = OrderedDict()
        self._prefix_ref: Dict[int, int] = {}
        self.evictions = 0
        self.cow_copies = 0
        # graftsan dynamic sanitizer (GRAFTSAN=1, or explicit flag):
        # per-block grant-site provenance, refcount-conservation asserts
        # at every boundary, freed-block poisoning (via _on_free — the
        # owning pool wires its trash-copy writer in), and leak reports
        # at teardown (graftsan_report / graftsan_assert_quiesced).
        self.sanitize = (_graftsan_enabled() if sanitize is None
                         else sanitize)
        self._san_owner: Dict[int, List[str]] = {}   # grant sites, LIFO
        self._san_freed: Dict[int, str] = {}         # last freeing site
        self._san_grants = 0
        self._san_drops = 0
        self._on_free: Optional[Callable[[List[int]], None]] = None
        # grafttier demotion hook (runtime/kv_tier.py, wired by
        # KVBlockPool.attach_tier): called OUTSIDE ``_lock`` when
        # allocation pressure would otherwise LRU-evict prefix entries;
        # returns True when it moved one entry down a tier. None means
        # no tier — plain eviction is the only relief valve.
        self._tier_demote: Optional[Callable[[], bool]] = None
        # state-slab hook (runtime/state_slab.py, wired by
        # KVBlockPool.attach_slab): the content keys of prefix entries
        # dropped since the last flush (eviction, the capacity trim,
        # pool pressure, a demotion), handed over OUTSIDE ``_lock`` by
        # ``_notify_freed`` so that each entry's state snapshot goes
        # with its blocks. None: nothing is recorded.
        self._on_prefix_drop: Optional[Callable[[List[bytes]], None]] = None
        self._dropped: List[bytes] = []
        if self.sanitize:
            _SAN_ALLOCATORS.add(self)

    # -- sanitizer bookkeeping (all under self._lock) ------------------------

    def _san_grant_locked(self, b: int, site: str) -> None:
        self._san_grants += 1
        self._san_owner.setdefault(b, []).append(site)
        self._san_freed.pop(b, None)

    def _san_drop_locked(self, b: int, site: str,
                         fully_freed: bool) -> None:
        self._san_drops += 1
        owners = self._san_owner.get(b)
        if owners:
            owners.pop()
        if fully_freed:
            self._san_owner.pop(b, None)
            self._san_freed[b] = site

    def _san_check_locked(self, boundary: str) -> None:
        """Refcount conservation at a boundary: free + referenced ==
        total, grants - drops == live refs, prefix refs bounded by
        total refs. A violation is an accounting bug — raise with the
        numbers, not a silent drift."""
        free_n, ref_n = len(self._free), len(self._ref)
        if free_n + ref_n != self.num_blocks:
            raise GraftsanError(
                f"[{boundary}] block conservation broken: {free_n} free "
                f"+ {ref_n} referenced != {self.num_blocks} total")
        live = sum(self._ref.values())
        if self._san_grants - self._san_drops != live:
            raise GraftsanError(
                f"[{boundary}] refcount conservation broken: "
                f"{self._san_grants} grants - {self._san_drops} drops "
                f"!= {live} live refs")
        for b, pr in self._prefix_ref.items():
            if pr > self._ref.get(b, 0):
                raise GraftsanError(
                    f"[{boundary}] block {b} holds {pr} prefix refs but "
                    f"only {self._ref.get(b, 0)} total refs")

    def freed_provenance(self, block: int) -> Optional[str]:
        """The site that last freed ``block`` (sanitizer mode), if it is
        currently free because of an explicit free/eviction."""
        with self._lock:
            return self._san_freed.get(block)

    def graftsan_report(self) -> List[dict]:
        """Leak report: blocks whose refcount exceeds their prefix-entry
        refs once all client work has retired — every such ref was
        granted to a caller that never released it. Each row carries
        the live grant-site provenance."""
        with self._lock:
            out = []
            for b in sorted(self._ref):
                extra = self._ref[b] - self._prefix_ref.get(b, 0)
                if extra > 0:
                    out.append({
                        "block": b,
                        "leaked_refs": extra,
                        "prefix_refs": self._prefix_ref.get(b, 0),
                        "grant_sites": list(self._san_owner.get(b, [])),
                    })
            return out

    def graftsan_assert_quiesced(self, timeout: float = 2.0) -> None:
        """Poll until no caller refs remain beyond prefix entries (block
        release can trail request delivery by a scheduler beat), then
        raise ``GraftsanError`` with provenance if leaks persist."""
        deadline = time.monotonic() + timeout
        leaks = self.graftsan_report()
        while leaks and time.monotonic() < deadline:
            time.sleep(0.01)
            leaks = self.graftsan_report()
        if leaks:
            lines = "; ".join(
                f"block {r['block']}: {r['leaked_refs']} leaked ref(s), "
                f"granted at {r['grant_sites']}" for r in leaks)
            raise GraftsanError(
                f"pool teardown leak: {len(leaks)} block(s) still hold "
                f"caller refs — {lines}")
        with self._lock:
            if self.sanitize:
                self._san_check_locked("teardown")

    # -- sizing --------------------------------------------------------------

    def blocks_for(self, n_slots: int) -> int:
        return max(0, -(-n_slots // self.block_size))

    # -- allocation ----------------------------------------------------------

    def _evictable_blocks_locked(self) -> int:
        return sum(1 for b, r in self._ref.items()
                   if r > 0 and r == self._prefix_ref.get(b, 0))

    def available(self) -> int:
        """Blocks obtainable right now: free + freeable-by-eviction."""
        with self._lock:
            return len(self._free) + self._evictable_blocks_locked()

    def _can_admit_locked(self, n_blocks: int) -> bool:
        """THE admission predicate (availability + watermark), under the
        caller's ``_lock`` hold — shared by the advisory ``can_admit``
        (the serving 429 gate) and the atomic ``admit_alloc`` grant, so
        the two can never drift."""
        if n_blocks > len(self._free) + self._evictable_blocks_locked():
            return False
        live = len(self._ref) - self._evictable_blocks_locked()
        return live + n_blocks <= self.watermark * self.num_blocks

    def can_admit(self, n_blocks: int) -> bool:
        """Watermark admission: would granting ``n_blocks`` keep
        referenced blocks at or under the watermark (after evicting
        prefix entries as needed)? ADVISORY — the answer can be stale
        by the time a caller acts on it; grants go through
        ``admit_alloc``, which re-evaluates under one hold."""
        with self._lock:
            if self.sanitize:
                self._san_check_locked("admission")
            return self._can_admit_locked(n_blocks)

    def _notify_freed(self, freed: List[int]) -> None:
        """Fire the sanitizer's poison hook for fully-freed blocks —
        OUTSIDE ``self._lock`` (the pool's writer takes ``_dev_lock``,
        and gather/scatter validation reads allocator state under it;
        firing inside would invert the lock order)."""
        if freed and self._on_free is not None:
            self._on_free(freed)
        if self._on_prefix_drop is not None:
            with self._lock:
                keys, self._dropped = self._dropped, []
            if keys:
                self._on_prefix_drop(keys)

    def _note_dropped_locked(self, key: bytes) -> None:
        if self._on_prefix_drop is not None:
            self._dropped.append(key)

    def _alloc_locked(self, n: int, site: str) -> Tuple[List[int],
                                                        List[int]]:
        """Grant ``n`` blocks at ref=1 under the caller's ``_lock``
        hold, LRU-evicting as needed -> (granted, eviction-freed)."""
        evict_freed: List[int] = []
        while len(self._free) < n and self._prefix:
            evict_freed.extend(self._evict_lru_locked())
        if len(self._free) < n:
            raise PoolExhausted(
                f"need {n} blocks, {len(self._free)} free and no "
                f"evictable prefix entries ({len(self._ref)} blocks "
                "referenced)")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        if self.sanitize:
            for b in out:
                self._san_grant_locked(b, site)
            self._san_check_locked("alloc")
        # eviction-freed blocks this alloc immediately re-took are
        # live again — only the remainder gets poisoned
        return out, [b for b in evict_freed if b not in self._ref]

    def _demote_pressure(self, n: int) -> None:
        """Best-effort demotion pre-pass, OUTSIDE ``_lock``: while
        satisfying ``n`` would force LRU eviction and a tier is
        attached, ask it to demote the LRU prefix entry to host RAM
        instead. The hook does device reads (``spill_blocks`` under
        ``_dev_lock``), so it cannot run under ``_lock`` — this is a
        pre-pass by construction, and ``_alloc_locked``'s plain
        eviction remains the in-lock fallback when the tier refuses
        (budget exhausted, entry too large, or a concurrent race).
        Each successful demotion removes one registry entry, so the
        loop terminates."""
        hook = self._tier_demote
        if hook is None:
            return
        while True:
            with self._lock:
                pressed = len(self._free) < n and bool(self._prefix)
            if not pressed or not hook():
                return

    def alloc(self, n: int) -> List[int]:
        """Allocate ``n`` blocks at ref=1, LRU-evicting zero-ref prefix
        entries as needed (demoting them to the attached grafttier host
        tier first, when one is wired). All-or-nothing: raises
        ``PoolExhausted`` without taking anything when ``n`` cannot be
        satisfied."""
        if n == 0:
            return []
        self._demote_pressure(n)
        with self._lock:
            site = _call_site() if self.sanitize else ""
            out, evict_freed = self._alloc_locked(n, site)
        self._notify_freed(evict_freed)
        return out

    def admit_alloc(self, n: int) -> Optional[List[int]]:
        """ATOMIC watermark admission + grant: ``can_admit`` and the
        allocation run under ONE ``_lock`` hold, so no concurrent
        allocator user can slip between the check and the grant (the
        check-then-act window the two-step form leaves open turns a
        deferrable admission into a ``PoolExhausted`` request failure —
        or, raced the other way, an over-watermark grant). Returns the
        granted ids, or None when the watermark (or availability)
        refuses — the caller defers, exactly like a ``can_admit``
        False."""
        if n == 0:
            return []
        # seeded pool-exhaustion spike (graftfault): the grant refuses
        # exactly as a genuinely full pool would — the caller's
        # deferral/preemption machinery absorbs it, deterministically
        # replayable under a pinned seed
        if graftfault.inject("kv_pool.admit_alloc", "pool_spike"):
            return None
        self._demote_pressure(n)
        evict_freed: List[int] = []
        with self._lock:
            if self.sanitize:
                self._san_check_locked("admission")
            if not self._can_admit_locked(n):
                return None
            site = _call_site() if self.sanitize else ""
            out, evict_freed = self._alloc_locked(n, site)
        self._notify_freed(evict_freed)
        return out

    def note_cow(self) -> None:
        """Count one copy-on-write block copy (under ``_lock``: pools
        are shared across front ends, and an unguarded ``+= 1`` from
        two concurrent CoW paths loses updates)."""
        with self._lock:
            self.cow_copies += 1

    def ref(self, ids) -> None:
        with self._lock:
            site = _call_site() if self.sanitize else ""
            for b in ids:
                if b not in self._ref:
                    raise ValueError(f"ref of unallocated block {b}")
                self._ref[b] += 1
                if self.sanitize:
                    self._san_grant_locked(b, site)
            if self.sanitize:
                self._san_check_locked("ref")

    def free(self, ids) -> None:
        """Drop one ref per id; zero-ref blocks return to the free
        list (idempotence is the caller's problem — double-frees raise;
        the sanitizer upgrades them to ``GraftsanError`` with the
        original freeing site's provenance)."""
        freed: List[int] = []
        with self._lock:
            site = _call_site() if self.sanitize else ""
            for b in ids:
                r = self._ref.get(b)
                if r is None:
                    if self.sanitize:
                        prior = self._san_freed.get(b)
                        raise GraftsanError(
                            f"double-free of block {b} at {site}: "
                            + (f"previously freed at {prior}" if prior
                               else "block was never allocated"))
                    raise ValueError(f"free of unallocated block {b}")
                if r == 1:
                    del self._ref[b]
                    self._free.append(b)
                    freed.append(b)
                    if self.sanitize:
                        self._san_drop_locked(b, site, fully_freed=True)
                else:
                    self._ref[b] = r - 1
                    if self.sanitize:
                        self._san_drop_locked(b, site, fully_freed=False)
            if self.sanitize:
                self._san_check_locked("free")
        self._notify_freed(freed)

    def refcount(self, block: int) -> int:
        with self._lock:
            return self._ref.get(block, 0)

    # -- prefix registry -----------------------------------------------------

    def register_prefix(self, key: bytes, ids) -> None:
        """Register ``ids`` as the cached state for content ``key``.
        The entry takes its OWN ref on every block (the caller keeps
        any refs it holds); re-registering an existing key is a no-op
        beyond an LRU touch."""
        with self._lock:
            if key in self._prefix:
                self._prefix.move_to_end(key)
                return
            ids = tuple(ids)
            site = f"prefix:{_call_site()}" if self.sanitize else ""
            for b in ids:
                if b not in self._ref:
                    raise ValueError(
                        f"register_prefix of unallocated block {b}")
                self._ref[b] += 1
                self._prefix_ref[b] = self._prefix_ref.get(b, 0) + 1
                if self.sanitize:
                    self._san_grant_locked(b, site)
            self._prefix[key] = ids
            if self.sanitize:
                self._san_check_locked("register_prefix")

    def lookup_prefix(self, key: bytes) -> Optional[Tuple[int, ...]]:
        """Hit -> the entry's block ids with one caller ref added per
        block (release with ``free``); miss -> None. Hits refresh LRU
        recency."""
        with self._lock:
            ids = self._prefix.get(key)
            if ids is None:
                return None
            self._prefix.move_to_end(key)
            site = _call_site() if self.sanitize else ""
            for b in ids:
                self._ref[b] += 1
                if self.sanitize:
                    self._san_grant_locked(b, site)
            if self.sanitize:
                self._san_check_locked("lookup_prefix")
            return ids

    def has_prefix(self, key: bytes) -> bool:
        with self._lock:
            return key in self._prefix

    def drop_prefix(self, key: bytes) -> bool:
        freed: List[int] = []
        with self._lock:
            ids = self._prefix.pop(key, None)
            if ids is None:
                return False
            self._note_dropped_locked(key)
            freed = self._deref_prefix_locked(ids)
            if self.sanitize:
                self._san_check_locked("drop_prefix")
        self._notify_freed(freed)
        return True

    def prefix_len(self) -> int:
        with self._lock:
            return len(self._prefix)

    # -- grafttier demotion surgery (runtime/kv_tier.py) ---------------------

    def lease_lru_prefix(self) -> Optional[Tuple[bytes, Tuple[int, ...]]]:
        """Peek the LRU prefix entry and take one caller ref per block
        WITHOUT refreshing recency — the tier's demote lease. The refs
        keep the blocks alive (and their contents immutable: registry
        blocks are shared, so the CoW trap guards them) while the tier
        copies them to host OUTSIDE this lock; release with ``free``
        after ``demote_pop_prefix``. None when the registry is empty."""
        with self._lock:
            if not self._prefix:
                return None
            key = next(iter(self._prefix))
            ids = self._prefix[key]
            site = f"tier:{_call_site()}" if self.sanitize else ""
            for b in ids:
                self._ref[b] += 1
                if self.sanitize:
                    self._san_grant_locked(b, site)
            if self.sanitize:
                self._san_check_locked("tier_lease")
            return key, ids

    def demote_pop_prefix(self, key: bytes, expect_ids) -> bool:
        """Drop the registry entry for ``key`` as a DEMOTION: the tier
        captured the blocks' bytes and now owns the entry's cold copy,
        so this is a tier move, not an eviction-to-oblivion (neither
        ``evictions`` nor the eviction event fires — the tier emits
        ``tier_demote`` once the host entry is installed). Returns
        False without touching anything when the entry vanished or was
        re-registered with different blocks since the lease (the tier
        discards its stale host copy)."""
        expect = tuple(expect_ids)
        freed: List[int] = []
        with self._lock:
            if self._prefix.get(key) != expect:
                return False
            del self._prefix[key]
            self._note_dropped_locked(key)
            freed = self._deref_prefix_locked(expect)
            if self.sanitize:
                self._san_check_locked("tier_demote")
        self._notify_freed(freed)
        return True

    def _deref_prefix_locked(self, ids) -> List[int]:
        freed: List[int] = []
        site = _call_site() if self.sanitize else ""
        for b in ids:
            self._prefix_ref[b] -= 1
            if self._prefix_ref[b] == 0:
                del self._prefix_ref[b]
            if self._ref[b] == 1:
                del self._ref[b]
                self._free.append(b)
                freed.append(b)
                if self.sanitize:
                    self._san_drop_locked(b, site, fully_freed=True)
            else:
                self._ref[b] -= 1
                if self.sanitize:
                    self._san_drop_locked(b, site, fully_freed=False)
        return freed

    def _evict_lru_locked(self) -> List[int]:
        key, ids = self._prefix.popitem(last=False)
        self._note_dropped_locked(key)
        freed = self._deref_prefix_locked(ids)
        self.evictions += 1
        REGISTRY.inc("kv_pool_evictions_total")
        # one bounded ring append under the hold (the _sample_breaker
        # precedent): the eviction joins the causal timeline at the
        # instant the block economy changed
        grafttime.emit("eviction", blocks=len(ids), freed=len(freed),
                       prefix_entries=len(self._prefix))
        if self.sanitize:
            self._san_check_locked("eviction")
        return freed

    def evict_lru(self) -> None:
        freed: List[int] = []
        with self._lock:
            if self._prefix:
                freed = self._evict_lru_locked()
        self._notify_freed(freed)

    # -- stats ---------------------------------------------------------------

    def stats(self) -> PoolStats:
        with self._lock:
            ev = self._evictable_blocks_locked()
            return PoolStats(
                blocks_total=self.num_blocks,
                blocks_free=len(self._free),
                blocks_in_use=len(self._ref),
                blocks_evictable=ev,
                prefix_entries=len(self._prefix),
                evictions=self.evictions,
                cow_copies=self.cow_copies)


class KVBlockPool:
    """The device block pool + its allocator + its compiled programs.

    One buffer ``[L, num_blocks+1, 2, Hkv, bs, hd]`` (index
    ``num_blocks`` is the shared trash block — see
    ``ops.paged_attention``). All device mutation goes through the
    jitted programs here, serialized by ``_dev_lock`` (the pool buffer
    is donated through every scatter, and concurrent front ends — a
    solo runner, the prefix store, the iteration scheduler — may share
    one pool).
    """

    def __init__(self, n_layer: int, num_blocks: int, n_kv_head: int,
                 block_size: int, head_dim: int, max_seq: int,
                 dtype=jnp.float32, watermark: float = 0.9,
                 sanitize: Optional[bool] = None,
                 block_dtype: Optional[str] = None,
                 fused: bool = False, planes: int = 2, aux=None):
        """``planes``, ``n_kv_head`` and ``head_dim`` are the family's
        cache entry (``models.cache_entry``). A ONE-plane pool serves a
        cache whose first leaf is the only storage (``models.
        latent_moe``: one latent vector a position; ``models.gdn_moe``:
        keys and values in one fused row a kv head); ``aux`` is the
        shape and dtype of that cache's second, one-dimensional leaf,
        made anew (zeroed) by every gather and dropped by every scatter.

        ``fused``: the engine's caches use the FUSED layout of the
        Pallas decode kernels (``ops.attention.create_fused_cache`` —
        one ``[L, B, H, S, 2*hd]`` buffer of ``[K | V]`` rows plus an
        empty placeholder). Block storage is the same either way; the
        movers join K and V on gather and split them on scatter inside
        their own programs, so every front end hands the engine's
        compiled programs the layout they were built for."""
        self.nbm = PA.blocks_per_row(max_seq, block_size)
        self.fused = fused
        self.planes = planes
        self.entry_width = planes * n_kv_head * head_dim
        self.layers = n_layer
        if planes not in (1, 2) or (planes == 1 and (fused or block_dtype)):
            raise NotImplementedError(
                f"a pool of {planes} plane(s) with fused={fused}, "
                f"block_dtype={block_dtype!r}: one-plane (latent) pools "
                "store full-precision blocks for the unfused layout only")
        if num_blocks < self.nbm:
            raise ValueError(
                f"num_blocks={num_blocks} cannot hold even one full "
                f"row ({self.nbm} blocks at max_seq={max_seq}, "
                f"block_size={block_size}) — nothing could ever decode "
                "to budget")
        self.block_size = block_size
        self.max_seq = max_seq
        self.trash = num_blocks
        self.dtype = dtype
        # quantized block storage (opt-in): validate the knob through
        # THE regime vocabulary (a typo fails with graftnum's
        # regime-vocabulary error, not a KeyError), then reject
        # full-precision spellings — those pools already store blocks
        # in the engine dtype, and routing them here would silently
        # trade their byte-equality pins for a tolerance budget.
        self.block_dtype: Optional[str] = None
        if block_dtype:
            from ..utils.graftnum import regime_of
            regime = regime_of(block_dtype)
            if regime not in KVQ.STORAGE_DTYPES:
                raise ValueError(
                    f"block_dtype={block_dtype!r} is the full-precision "
                    f"regime {regime!r} — the pool already stores blocks "
                    "in the engine dtype there; quantized storage takes "
                    f"one of {sorted(KVQ.STORAGE_DTYPES)}")
            if regime == "fp8" and not KVQ.fp8_supported():
                raise ValueError(
                    "block_dtype='fp8' requires float8_e4m3fn support "
                    "on this backend (ops.kv_quant.fp8_supported() is "
                    "False) — use 'int8' here")
            self.block_dtype = regime
        self.block_regime = self.block_dtype or _REGIME_LABELS.get(
            np.dtype(dtype).name, np.dtype(dtype).name)
        self.allocator = BlockAllocator(num_blocks, block_size,
                                        watermark=watermark,
                                        sanitize=sanitize)
        shape = PA.pool_shape(n_layer, num_blocks, n_kv_head, block_size,
                              head_dim, planes)
        if self.block_dtype is not None:
            self.data = jnp.zeros(shape,
                                  dtype=KVQ.STORAGE_DTYPES[self.block_dtype])
            self.scales = jnp.zeros(
                KVQ.scales_shape(n_layer, num_blocks, n_kv_head),
                dtype=jnp.float32)
        else:
            self.data = jnp.zeros(shape, dtype=dtype)
            self.scales = None
        self._bytes_per_block = self.data.nbytes // shape[1] + (
            0 if self.scales is None
            else self.scales.nbytes // shape[1])
        self._dev_lock = graftsched.rlock("kv_pool.KVBlockPool._dev_lock")
        if planes == 1:
            graftmem.track(self, "latent", "pool_latent", self.data)
        else:
            graftmem.track(self, "data", "pool_codes", self.data)
        if self.scales is not None:
            graftmem.track(self, "scales", "pool_scales", self.scales)
        # grafttier host spill tier (runtime/kv_tier.py), attached via
        # attach_tier — None means cold prefix entries LRU-evict to
        # oblivion exactly as before
        self.tier = None
        # the per-row state slab (runtime/state_slab.py) of a family
        # whose rows hold a state beside their positions, attached via
        # attach_slab — None for every other family
        self.slab = None

        # per-instance defs (not the module-level ops directly): each
        # pool owns its jitted-program caches, so ``_cache_size()`` is
        # THIS pool's program count — the recompile-budget certifier
        # pins it per workload, which a function-identity-shared cache
        # would smear across instances. A pool constructs exactly ONE
        # mover family: plain (full precision, below) or ``_q``
        # (quantized, _init_quantized_movers) — never both, so the
        # full-precision jit population is bit-identical to a build
        # without this feature and the byte-equality pins stay pinned
        # to precisely the programs they always covered.

        def join(k, v):
            # block movers' separate (K, V) -> the engine's cache leaves
            if not fused:
                return k, v
            return (jnp.concatenate([k, v], axis=-1),
                    jnp.zeros((0,), dtype=k.dtype))

        def split(k, v):
            # the engine's cache leaves -> separate (K, V)
            return (k[..., :head_dim], k[..., head_dim:]) if fused else (k, v)

        self._join, self._split = join, split
        if self.block_dtype is not None:
            self._compile_watches = self._init_quantized_movers()
            return

        def _gather_impl(pool, tables):
            if planes == 1:
                return (PA.gather_rows(pool, tables),
                        jnp.zeros(aux.shape, aux.dtype))
            return join(*PA.gather_kv(pool, tables))

        def _scatter_impl(pool, k, v, tables):
            if planes == 1:
                return PA.scatter_rows(pool, k, tables)
            return PA.scatter_kv(pool, *split(k, v), tables)

        def _scatter_span_impl(pool, k, v, tables, col, span):
            # a decode call's write-back: of a working cache that stays
            # with its caller, only the ``span`` table columns from
            # ``col`` on (ops.paged_attention.column_span), B x span
            # block updates whatever the table's width
            if planes == 1:
                tw, kw = PA.column_span(tables, col, span, k)
                return PA.scatter_rows(pool, kw, tw)
            if fused:
                tw, kw = PA.column_span(tables, col, span, k)
                return PA.scatter_kv(pool, *split(kw, v), tw)
            tw, kw, vw = PA.column_span(tables, col, span, k, v)
            return PA.scatter_kv(pool, kw, vw, tw)

        def _scatter_one_rolled(pool, k, v, table_row, roll):
            # admission merge: roll a solo-prefilled row's K/V content
            # along the slot axis (engine left-pad convention — wrap
            # garbage lands in masked pad slots), then scatter the full
            # row. roll/table are traced: one program per solo shape.
            k, v = split(k, v)
            k = jnp.roll(k, roll, axis=-2)
            if planes == 1:
                return PA.scatter_rows(pool, k, table_row[None])
            v = jnp.roll(v, roll, axis=-2)
            return PA.scatter_kv(pool, k, v, table_row[None])

        def _copy_impl(pool, src, dst):
            return PA.copy_blocks(pool, src, dst)

        self._gather = graftscope.instrument(
            jax.jit(_gather_impl), "kv_pool._gather",
            key_fn=_gather_scope_key)
        self._scatter = graftscope.instrument(
            jax.jit(_scatter_impl, donate_argnums=(0,)),
            "kv_pool._scatter", key_fn=_scatter_scope_key)
        self._scatter_span = graftscope.instrument(
            jax.jit(_scatter_span_impl, donate_argnums=(0,),
                    static_argnums=(5,)),
            "kv_pool._scatter_span", key_fn=_scatter_span_scope_key)
        self._scatter_row = graftscope.instrument(
            jax.jit(_scatter_one_rolled, donate_argnums=(0,)),
            "kv_pool._scatter_row", key_fn=_scatter_row_scope_key)
        self._copy = graftscope.instrument(
            jax.jit(_copy_impl, donate_argnums=(0,)),
            "kv_pool._copy", key_fn=_copy_scope_key)
        watches = [
            CompileWatch("kv_pool", self._gather),
            CompileWatch("kv_pool", self._scatter),
            CompileWatch("kv_pool", self._scatter_span),
            CompileWatch("kv_pool", self._scatter_row),
            CompileWatch("kv_pool", self._copy)]
        if self.allocator.sanitize:
            # graftsan free-block poisoner: rewrite each freed block
            # THROUGH the trash-block write path (the same copy mover
            # CoW uses, one block per dispatch so the program shape is
            # the existing [1]-id copy — no new compiled programs under
            # GRAFTSAN beyond this instance's own jit). The content
            # becomes trash-block garbage on device; the authoritative
            # use-after-free TRAP is the host-side table validation in
            # gather/scatter, which raises with the freeing site's
            # provenance.
            def _poison_impl(pool, src, dst):
                return PA.copy_blocks(pool, src, dst)

            self._poison = jax.jit(_poison_impl, donate_argnums=(0,))
            self.allocator._on_free = self._graftsan_poison
            watches.append(CompileWatch("kv_pool", self._poison))
        self._compile_watches = tuple(watches)

    def _init_quantized_movers(self) -> tuple:
        """Construct the ``_q`` jit family for a quantized pool: the
        same four movers, carrying (data, scales) as one donated pair.
        The regime's quantizer is bound at construction (ops.kv_quant),
        so the traced programs contain no regime branching; the gather
        dequantizes into the ENGINE dtype — downstream decode programs
        see exactly the avals the full-precision gather produces and
        stay shared with contiguous serving."""
        out_dtype = self.dtype
        scatter_fn = (KVQ.scatter_kv_int8 if self.block_dtype == "int8"
                      else KVQ.scatter_kv_fp8)

        join, split = self._join, self._split

        def _gather_q_impl(data, scales, tables):
            return join(*KVQ.gather_kv_q(data, scales, tables, out_dtype))

        def _scatter_q_impl(data, scales, k, v, tables):
            return scatter_fn(data, scales, *split(k, v), tables)

        def _scatter_row_q_impl(data, scales, k, v, table_row, roll):
            # admission merge, quantized: same roll-then-scatter as the
            # plain family; the full row re-quantizes on the way in.
            k, v = split(k, v)
            k = jnp.roll(k, roll, axis=-2)
            v = jnp.roll(v, roll, axis=-2)
            return scatter_fn(data, scales, k, v, table_row[None])

        def _copy_q_impl(data, scales, src, dst):
            return KVQ.copy_blocks_q(data, scales, src, dst)

        self._gather_q = graftscope.instrument(
            jax.jit(_gather_q_impl), "kv_pool._gather_q",
            key_fn=_gather_q_scope_key)
        self._scatter_q = graftscope.instrument(
            jax.jit(_scatter_q_impl, donate_argnums=(0, 1)),
            "kv_pool._scatter_q", key_fn=_scatter_q_scope_key)
        self._scatter_row_q = graftscope.instrument(
            jax.jit(_scatter_row_q_impl, donate_argnums=(0, 1)),
            "kv_pool._scatter_row_q", key_fn=_scatter_row_q_scope_key)
        self._copy_q = graftscope.instrument(
            jax.jit(_copy_q_impl, donate_argnums=(0, 1)),
            "kv_pool._copy_q", key_fn=_copy_q_scope_key)
        watches = [
            CompileWatch("kv_pool", self._gather_q),
            CompileWatch("kv_pool", self._scatter_q),
            CompileWatch("kv_pool", self._scatter_row_q),
            CompileWatch("kv_pool", self._copy_q)]
        if self.allocator.sanitize:
            # quantized poisoner: trash-copy through copy_blocks_q so
            # the block's SCALE is poisoned along with its codes — a
            # use-after-free gather of a poisoned block dequantizes to
            # trash-block garbage, never to stale real content.
            def _poison_q_impl(data, scales, src, dst):
                return KVQ.copy_blocks_q(data, scales, src, dst)

            self._poison_q = jax.jit(_poison_q_impl, donate_argnums=(0, 1))
            self.allocator._on_free = self._graftsan_poison
            watches.append(CompileWatch("kv_pool", self._poison_q))
        return tuple(watches)

    # -- graftsan (GRAFTSAN=1) -----------------------------------------------

    def _graftsan_poison(self, ids: List[int]) -> None:
        """``BlockAllocator._on_free`` hook: poison each fully-freed
        block by copying the trash block over it (fired outside the
        allocator lock — see ``_notify_freed``)."""
        trash = jnp.asarray([self.trash], jnp.int32)
        with self._dev_lock:
            for b in ids:
                if self.allocator.refcount(b) > 0:
                    continue  # re-allocated between free and poison
                dst = jnp.asarray([b], jnp.int32)
                if self.block_dtype is not None:
                    self.data, self.scales = self._poison_q(
                        self.data, self.scales, trash, dst)
                else:
                    self.data = self._poison(self.data, trash, dst)

    def _graftsan_check_tables(self, tables, op: str,
                               write: bool = False) -> None:
        """Use-after-free trap: every table id a mover touches must be
        the trash block or a live (refcount >= 1) allocation. A freed
        id raises with the freeing site's provenance; a never-allocated
        id is an uninitialized-placement bug. Writes (``write=True``)
        additionally trap on SHARED blocks (refcount > 1): the module
        contract is that writers never mutate a shared block — extension
        into a shared frontier goes through ``cow_copy`` first."""
        alloc = self.allocator
        for b in {int(x) for x in np.asarray(tables).reshape(-1)}:
            if b == self.trash:
                continue
            if not 0 <= b < alloc.num_blocks:
                raise GraftsanError(
                    f"{op} touches out-of-range block id {b} "
                    f"(pool has {alloc.num_blocks} blocks)")
            refs = alloc.refcount(b)
            if refs == 0:
                site = alloc.freed_provenance(b)
                raise GraftsanError(
                    f"use-after-free: {op} touches poisoned block {b}"
                    + (f", freed at {site}" if site
                       else ", which was never allocated"))
            if write and refs > 1:
                with alloc._lock:
                    sites = list(alloc._san_owner.get(b, []))
                raise GraftsanError(
                    f"CoW violation: {op} writes shared block {b} "
                    f"(refcount {refs}, granted at {sites}) without a "
                    "private copy — shared blocks are immutable; "
                    "cow_copy before the first write")

    @classmethod
    def for_engine(cls, engine: DecodeEngine, num_blocks: int,
                   block_size: int = DEFAULT_KV_BLOCK_SIZE,
                   watermark: float = 0.9,
                   sanitize: Optional[bool] = None,
                   block_dtype: Optional[str] = None,
                   state_slots: int = 0) -> "KVBlockPool":
        """Build a pool matching an engine's cache geometry: as many
        layers as the family says cache positions (``models.
        cache_layers``: not every layer of every model does), each of
        the family's ``cache_entry``; and, for a family whose rows hold
        a state beside their positions (``models.row_state``), a state
        slab of ``state_slots`` records beside it (the prefix store's
        snapshots: ``PREFIX_CACHE`` as served). The paged
        path drives the engine's OWN compiled programs on gathered
        views, so the engine must be the unstaged single-device one:
        no stage partitioning (per-stage cache lists), no mesh. With a
        Pallas decode kernel resolved (what ``decode_kernel="auto"``
        does on a TPU outside fp32) the engine's caches are FUSED and
        the pool's movers convert at the block boundary (``fused``)."""
        if engine.specs is not None:
            raise NotImplementedError(
                "KV pool paging covers the unstaged engine; staged "
                "per-stage cache lists page in a later PR")
        if engine._mesh is not None:
            raise NotImplementedError(
                "KV pool paging is single-device; mesh decode (tp/ep) "
                "keeps contiguous caches")
        from ..models import cache_entry, cache_layers, row_state
        cfg = engine.config
        planes, heads, width = cache_entry(cfg)
        aux = (jax.eval_shape(lambda: engine._fresh_cache(1)).v
               if planes == 1 else None)
        leaves = row_state(cfg, engine.dtype)
        pool = cls(cache_layers(cfg), num_blocks, heads, block_size,
                   width, engine._cache_seq, dtype=engine.dtype,
                   watermark=watermark, sanitize=sanitize,
                   block_dtype=block_dtype,
                   fused=(engine._decode_kernel is not None
                          and planes == 2),
                   planes=planes, aux=aux)
        if leaves:
            if state_slots < 1:
                raise ValueError(
                    f"{type(cfg).__name__}'s rows hold a state beside "
                    "their positions: give the pool state_slots (a slot "
                    "a stored prefix's snapshot)")
            from .state_slab import StateSlab
            pool.attach_slab(StateSlab(leaves, state_slots))
        return pool

    # -- device ops (all under _dev_lock) ------------------------------------

    @staticmethod
    def _device_tables(tables) -> jnp.ndarray:
        """Block tables for a mover, from a PRIVATE host copy. Callers
        keep their tables as host arrays and rewrite them in place
        between dispatches (the scheduler's ``state.tables``). A device
        array made straight from such a buffer may alias it (the CPU
        backend, when the buffer is suitably aligned) or copy it only
        when the transfer runs, after this call has returned — either
        way an asynchronously dispatched mover could read the
        REWRITTEN table and gather another row's blocks. Nobody holds
        the copy made here, so nobody can rewrite it."""
        return jnp.asarray(np.array(tables, dtype=np.int32))

    def gather(self, tables: np.ndarray, length: int) -> KVCache:
        """Contiguous working cache for the tabled rows: a FRESH buffer
        that is the caller's from here on (to donate to a decode call,
        or to keep). ``length`` is the logical depth the caller tracks
        host-side. The iteration scheduler keeps its working cache on
        the device from a batch's seed to its end and calls this for a
        live batch of a full-precision pool only where the batch grows;
        what it returns there is that cache over each row's ``[pad,
        depth)``, at the new width."""
        with self._dev_lock:
            if self.allocator.sanitize:
                self._graftsan_check_tables(tables, "gather")
            tj = self._device_tables(tables)
            if self.block_dtype is not None:
                k, v = self._gather_q(self.data, self.scales, tj)
            else:
                k, v = self._gather(self.data, tj)
        return KVCache(k=k, v=v, length=jnp.asarray(length, jnp.int32))

    def scatter(self, cache: KVCache, tables: np.ndarray) -> None:
        with self._dev_lock:
            if self.allocator.sanitize:
                self._graftsan_check_tables(tables, "scatter", write=True)
            tj = self._device_tables(tables)
            if self.block_dtype is not None:
                self.data, self.scales = self._scatter_q(
                    self.data, self.scales, cache.k, cache.v, tj)
            else:
                self.data = self._scatter(self.data, cache.k, cache.v, tj)

    def scatter_span(self, cache: KVCache, tables: np.ndarray,
                     col: int, span: int) -> None:
        """Write back table columns ``[col, col + span)`` of a
        full-width working cache the caller keeps: the blocks a decode
        call wrote. ``col`` is an operand and ``span`` is fixed by the
        caller (``ops.paged_attention.span_blocks``), so a batch width
        has ONE program whatever the depth; a span that would pass the
        last column ends on it instead, rewriting a block or two with
        the cache's own content. Full-precision pools only: a quantized
        block's scale is of its whole content, so that pool's callers
        stay on ``gather`` / ``scatter``."""
        col = min(col, self.nbm - span)
        with self._dev_lock:
            if self.allocator.sanitize:
                self._graftsan_check_tables(tables[:, col:col + span],
                                            "scatter_span", write=True)
            self.data = self._scatter_span(
                self.data, cache.k, cache.v, self._device_tables(tables),
                np.int32(col), span)

    def scatter_columns(self, cache: KVCache, tables: np.ndarray,
                        nb_lo: int) -> None:
        """Scatter only table columns ``[nb_lo, NBm)`` of a full-width
        contiguous cache — THE column-offset convention for writing a
        privately-owned tail behind a shared (immutable) prefix, used
        by both the prefix store's insert and the paged runner's
        shared-prefix placement. One program per nb_lo value
        (``scatter_kv`` derives the block size from the view widths) —
        bounded by the store's chunk grid."""
        bs = self.block_size
        sub = KVCache(k=cache.k[..., nb_lo * bs:, :],
                      v=(cache.v if self.fused or self.planes == 1
                         else cache.v[..., nb_lo * bs:, :]),
                      length=cache.length)   # rows' state: not the pool's
        self.scatter(sub, tables[:, nb_lo:])

    def scatter_row(self, cache: KVCache, table_row: np.ndarray,
                    roll: int) -> None:
        """Merge one solo-prefilled row (content at ``[sp - plen, sp)``)
        into its blocks at logical ``[d - plen, d)`` (``roll = d - sp``,
        the iterbatch admission move)."""
        with self._dev_lock:
            if self.allocator.sanitize:
                self._graftsan_check_tables(table_row, "scatter_row", write=True)
            row_j = self._device_tables(table_row)
            roll_j = jnp.asarray(roll, jnp.int32)
            if self.block_dtype is not None:
                self.data, self.scales = self._scatter_row_q(
                    self.data, self.scales, cache.k, cache.v, row_j,
                    roll_j)
            else:
                self.data = self._scatter_row(
                    self.data, cache.k, cache.v, row_j, roll_j)

    def attach_slab(self, slab) -> None:
        """Wire a state slab (runtime/state_slab.py) beside this pool:
        a prefix entry dropped from the registry, however (LRU eviction,
        the capacity trim, pool pressure), takes its state snapshot
        with it."""
        self.slab = slab
        self.allocator._on_prefix_drop = slab.drop

    def attach_tier(self, tier) -> None:
        """Wire a grafttier host tier (runtime/kv_tier.py) below this
        pool: allocation pressure demotes cold prefix entries into it
        (``BlockAllocator._demote_pressure``) instead of evicting them
        to oblivion, and the prefix store promotes demoted entries back
        on an affinity hit."""
        self.tier = tier
        self.allocator._tier_demote = lambda: tier.demote_lru(self)

    def spill_blocks(self, ids) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Host copies of the RAW storage planes for ``ids`` — the
        tier's demote reader: ``[L, n, 2, Hkv, bs, hd]`` codes plus the
        ``[L, n, 2, Hkv]`` f32 scales for quantized pools (None for
        full-precision pools). Codes spill AS codes, never dequantized
        f32 — a quantized spill moves the narrow bytes (~4x fewer at
        int8) and a demote/promote round trip is bit-exact at the code
        level for every storage regime (no re-quantization drift)."""
        idx = np.asarray(ids, dtype=np.int32)
        with self._dev_lock:
            if self.allocator.sanitize:
                self._graftsan_check_tables(idx, "spill_blocks")
            codes = np.asarray(self.data[:, jnp.asarray(idx)])
            scales = (None if self.scales is None
                      else np.asarray(self.scales[:, jnp.asarray(idx)]))
        return codes, scales

    def fill_blocks(self, ids, codes: np.ndarray,
                    scales: Optional[np.ndarray] = None) -> None:
        """Write spilled raw blocks back into freshly-allocated ids —
        the tier's promote writer: the host copy returns through
        ``jax.device_put`` into the SAME plane slots a scatter would
        fill, byte-identical to the content ``spill_blocks`` captured.
        The target blocks must be privately owned (the promote path
        allocates them at ref=1 before registering the prefix entry) —
        under GRAFTSAN a shared target trips the CoW write trap."""
        idx_np = np.asarray(ids, dtype=np.int32)
        with self._dev_lock:
            if self.allocator.sanitize:
                self._graftsan_check_tables(idx_np, "fill_blocks",
                                            write=True)
            idx = jnp.asarray(idx_np)
            self.data = self.data.at[:, idx].set(
                jax.device_put(codes).astype(self.data.dtype))
            if self.scales is not None:
                self.scales = self.scales.at[:, idx].set(
                    jax.device_put(scales).astype(self.scales.dtype))

    def cow_copy(self, src: int) -> int:
        """Copy-on-write: allocate a private block, copy ``src`` into
        it, and return the new id. The caller retargets its table entry
        and drops its own ref on ``src``."""
        if self.allocator.sanitize:
            self._graftsan_check_tables(np.asarray([src]), "cow_copy")
        dst = self.allocator.alloc(1)[0]
        with self._dev_lock:
            src_j = jnp.asarray([src], jnp.int32)
            dst_j = jnp.asarray([dst], jnp.int32)
            if self.block_dtype is not None:
                self.data, self.scales = self._copy_q(
                    self.data, self.scales, src_j, dst_j)
            else:
                self.data = self._copy(self.data, src_j, dst_j)
        # locked counter bump (locks-pass finding: pools are shared
        # across front ends — the prefix store's insert and a paged
        # runner can CoW concurrently, and a bare += here loses updates)
        self.allocator.note_cow()
        REGISTRY.inc("kv_pool_cow_copies_total")
        return dst

    # -- observability -------------------------------------------------------

    def note_compiles(self) -> None:
        for w in self._compile_watches:
            w.check()

    def note_gauges(self, component: str = "pool") -> None:
        st = self.allocator.stats()
        in_use = st.blocks_in_use - st.blocks_evictable
        # the block-count gauges carry the storage regime as a label so
        # a capacity dashboard can translate blocks to bytes (and tell
        # a quantized pool's 2x block count from a provisioning change)
        REGISTRY.gauge("kv_cache_blocks_in_use", in_use,
                       component=component,
                       block_dtype=self.block_regime)
        REGISTRY.gauge("kv_cache_blocks_total", st.blocks_total,
                       component=component,
                       block_dtype=self.block_regime)
        REGISTRY.gauge("kv_pool_bytes_per_block", self._bytes_per_block,
                       component=component,
                       block_dtype=self.block_regime)
        # graftscope occupancy time series: blocks-in-use over time at
        # the pool's own accounting points, served at /debug/profile
        graftscope.sample("kv_cache_blocks_in_use", in_use,
                          component=component,
                          block_dtype=self.block_regime)
        if self.tier is not None:
            self.tier.note_gauges(component=component)

    def stats(self) -> dict:
        out = {**self.allocator.stats().as_dict(),
               "block_size": self.block_size,
               "blocks_per_row": self.nbm,
               "block_dtype": self.block_regime,
               "bytes_per_block": self._bytes_per_block,
               # values one position holds in one layer, as the family
               # declares them: 2 x n_kv_head x head_dim, or one latent
               "entry_width": self.entry_width,
               # the layers that cache every position (models.
               # cache_layers: not every layer of every model does)
               "layers": self.layers,
               "graftsan": self.allocator.sanitize}
        if self.tier is not None:
            out["tier"] = self.tier.stats()
        return out


class PagedKVRunner:
    """Solo/batched paged generate: the engine's compiled programs on
    pool-backed storage (same calling convention as
    ``DecodeEngine.generate``; byte-equal output, pinned by
    tests/test_kv_pool.py).

    With ``prefix`` (a pool-backed ``PrefixCachingEngine`` wrapping the
    SAME engine and pool), a prompt whose prefix is stored prefills
    only its suffix AND shares the store's physical blocks in its own
    table — the full-depth duplication the old store paid per entry is
    gone; only the partially-filled frontier block is copy-on-write'd
    (the row will write into it).
    """

    def __init__(self, engine: DecodeEngine, pool: KVBlockPool,
                 prefix=None):
        if pool.max_seq != engine._cache_seq:
            raise ValueError(
                f"pool rows span {pool.max_seq} slots, engine cache is "
                f"{engine._cache_seq} — gathered views must match the "
                "compiled programs' cache width exactly")
        if engine.prefill_chunk:
            raise NotImplementedError(
                "PagedKVRunner prefills monolithically (one scatter per "
                "admission); build the engine without prefill_chunk")
        if prefix is not None:
            if prefix.plain is not engine:
                raise ValueError("prefix must wrap the same DecodeEngine")
            if getattr(prefix, "_pool", None) is not pool:
                raise ValueError(
                    "prefix store must be backed by the same pool "
                    "(pass pool= to PrefixCachingEngine) — block "
                    "sharing is the point")
        self.engine = engine
        self.pool = pool
        self.prefix = prefix
        self._row_state = None     # under _gen_lock, one generation's
        # one generation at a time: the pool buffer is donated through
        # every scatter, and the allocator's alloc/free pairs must not
        # interleave between concurrent generates. A declared DEVICE
        # lock (it serializes whole device generations by design).
        self._gen_lock = graftsched.lock("kv_pool.PagedKVRunner._gen_lock",
                                         timeout=600.0)

    def generate(self, prompt_ids, max_new_tokens: int,
                 sampling: SamplingConfig = SamplingConfig(),
                 key: Optional[jax.Array] = None,
                 pad: Optional[np.ndarray] = None,
                 eos_id: Optional[int] = None) -> GenerateResult:
        eng = self.engine
        ids, batch, prompt_len, key, pad = prepare_generate(
            prompt_ids, max_new_tokens, eng.max_seq, sampling, key, pad=pad)
        alloc = self.pool.allocator
        with self._gen_lock:
            t0 = time.perf_counter()
            prefill_key, decode_key = _split_keys(key)
            run_params = eng._run_params()
            # tables rows cover the full logical row; entries past the
            # owned/shared range are trash (masked garbage)
            logits, tables, owned, shared = self._prefill_tables(
                ids, batch, prompt_len, max_new_tokens, pad, run_params)
            first = select_token(logits, sampling, prefill_key)
            first.block_until_ready()
            t1 = time.perf_counter()
            tracing.record("prefill", t0, t1, batch=batch,
                           prompt_len=prompt_len, paged=True)
            self.pool.note_gauges(component="paged")
            # columns below every row's shared-prefix floor hold
            # IMMUTABLE registry blocks: decode never writes them, so
            # the per-segment scatter narrows to the owned tail — same
            # program key as the prefill placement's narrowed scatter,
            # and the graftsan CoW trap stays precise (a write to a
            # shared block is always a bug, never segment round-trip).
            nb_lo = min((len(s) for s in shared), default=0)
            try:
                return self._decode(run_params, ids, pad, first, tables,
                                    decode_key, max_new_tokens, sampling,
                                    prompt_len, t1 - t0, eos_id, nb_lo)
            finally:
                for row_ids in owned:
                    alloc.free(row_ids)
                for row_ids in shared:
                    alloc.free(row_ids)
                self.pool.note_gauges(component="paged")

    # -- prefill + placement -------------------------------------------------

    def _prefill_tables(self, ids, batch, prompt_len, max_new, pad,
                        run_params):
        """Prefill (through the prefix store when attached), allocate
        each row's blocks, scatter the state. Returns
        ``(last_logits [B, V], tables [B, NBm], owned_ids per row,
        shared_ids per row)``."""
        eng = self.engine
        pool = self.pool
        alloc = pool.allocator
        bs = pool.block_size
        nbm = pool.nbm
        need = alloc.blocks_for(prompt_len + max_new)
        tables = np.full((batch, nbm), pool.trash, dtype=np.int32)
        owned: List[List[int]] = []
        shared: List[List[int]] = []

        use_store = (self.prefix is not None and batch == 1
                     and not pad.any())
        frontier: List[int] = []
        try:
            if use_store:
                logits, cache, keep_ids, hit_depth = \
                    self.prefix.prefill_shared(ids[0])
                # shared full blocks stay shared; a partially-filled
                # frontier block is CoW'd (this row writes into it)
                n_full = hit_depth // bs
                row_shared = list(keep_ids[:n_full])
                shared.append(row_shared)
                row_owned: List[int] = []
                owned.append(row_owned)
                frontier = list(keep_ids[n_full:])
                while frontier:
                    row_owned.append(pool.cow_copy(frontier[0]))
                    alloc.free([frontier.pop(0)])
                row_owned.extend(alloc.alloc(need - n_full - len(row_owned)))
                tables[0, :n_full] = row_shared
                tables[0, n_full:need] = row_owned
                # scatter ONLY the privately owned tail: shared prefix
                # blocks already hold these bytes (the walk gathered
                # from them) and registry blocks are immutable by
                # contract
                pool.scatter_columns(cache, tables, n_full)
            else:
                ids_j = jnp.asarray(ids, dtype=jnp.int32)
                pad_j = jnp.asarray(pad) if pad.any() else None
                logits, cache = eng._prefill(run_params, ids_j, pad_j)
                for b in range(batch):
                    row = alloc.alloc(need)
                    tables[b, :need] = row
                    owned.append(row)
                    shared.append([])
                pool.scatter(cache, tables)
        except BaseException:
            # all-or-nothing: a mid-placement failure (e.g. exhaustion
            # after the CoW copy) must not leak the refs taken so far
            for row_ids in owned:
                alloc.free(row_ids)
            for row_ids in shared:
                alloc.free(row_ids)
            alloc.free(frontier)
            raise
        # what the rows hold beside their positions (``KVCache.state``,
        # or None) has no blocks to go to: this generation carries it
        self._row_state = cache.state
        return logits, tables, owned, shared

    # -- decode --------------------------------------------------------------

    def _decode(self, run_params, ids, pad, first, tables, decode_key,
                max_new_tokens, sampling, prompt_len, prefill_seconds,
                eos_id, nb_lo: int = 0) -> GenerateResult:
        eng = self.engine
        pad_j = jnp.asarray(pad) if pad.any() else None
        t1 = time.perf_counter()
        steps = max_new_tokens
        parts = [np.asarray(first)[:, None]]
        token = first
        segs = eng._segments(prompt_len, steps)
        done = None
        if eos_id is not None:
            segs = _eos_capped_segments(segs)
            done = parts[0][:, 0] == eos_id
        depth = prompt_len
        if steps > 1 and not (done is not None and done.all()):
            step_keys = _step_keys(decode_key, steps - 1)
            used = 0
            state = self._row_state
            for n, window in segs:
                working = self.pool.gather(tables, depth)._replace(
                    state=state)
                out, working = eng._decode_seg(
                    run_params, token, working, pad_j,
                    step_keys[used:used + n], sampling=sampling,
                    window=window)
                state = working.state
                self.pool.scatter_columns(working, tables, nb_lo)
                token = out[:, -1]
                parts.append(np.asarray(out))
                depth += n
                used += n
                if done is not None:
                    done |= (parts[-1] == eos_id).any(axis=1)
                    if done.all():
                        break
        new = np.concatenate(parts, axis=1)
        t2 = time.perf_counter()
        tracing.record("decode", t1, t2, batch=new.shape[0],
                       steps=new.shape[1], paged=True,
                       blocks_held=int(
                           (tables != self.pool.trash).sum()))
        eng._note_compiles()
        self.pool.note_compiles()
        tokens = np.concatenate([ids, new], axis=1)
        return GenerateResult(tokens=tokens, prompt_len=prompt_len,
                              prefill_seconds=prefill_seconds,
                              decode_seconds=t2 - t1,
                              new_tokens=new.shape[1],
                              decode_steps=new.shape[1] - 1,
                              pad=pad if pad.any() else None)
