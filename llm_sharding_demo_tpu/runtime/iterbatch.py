"""Iteration-level continuous batching: join/retire at segment boundaries.

``runtime.batcher`` batches at ADMISSION: it groups waiting requests,
runs one bucketed decode to completion, and only then looks at the queue
again — a request arriving mid-decode waits out the whole batch
(VERDICT r3 weak #3). This module schedules at ITERATION level, the
vLLM-style upgrade: the decode runs as fixed-size compiled segments, and
between segments the scheduler

- **admits** queued requests into free batch slots (solo bucketed
  prefill, then the row's K/V merges into the live cache at the current
  depth — the same roll-and-mask move the prefix batcher uses), and
- **retires** rows that finished (their ``max_new_tokens`` reached, or
  their ``eos_id`` emitted — early-EOS rows free their slot instead of
  decoding dead tokens to the end of the batch).

The segment loop dispatches asynchronously: segments queue back-to-back
on the device with NO host sync unless a decision is needed (a retiring
row's tokens are fetched for delivery; EOS-armed rows force a fetch per
segment). The device never idles waiting for the host on the fast path.

Exactness is the same bar as the admission batcher, per row:

- greedy rows equal their solo engine runs token-for-token (row-
  independent attention + left-pad masking — a joined row's cache
  content at slots ``[d - plen, d)`` with ``pad = d - plen`` is exactly
  a solo run's, shifted);
- seeded sample rows are byte-equal to solo runs: per-row keys with the
  row's OWN step offsets (``split(dk, n)[t]`` is prefix-stable, so a
  row joining at depth d still consumes key ``t`` at its step ``t``).

Batches are policy-pure (one SamplingConfig per live batch, like the
admission batcher); an incompatible arrival closes admission and seeds
the next batch, preserving FIFO. MoE is refused: its routing is not
window-independent (``models.is_window_independent``), so a row's
tokens could depend on batch composition.

Batches are RIGHT-SIZED (ADVICE r4): a batch compiles at the smallest
power-of-two width that fits its seed and grows on demand when an
arrival finds no free slot — a lone request decodes at width 1 instead
of paying ``max_batch`` x ghost-row FLOPs. Ghost rows (width minus live
rows) replicate a real row; per-row independence keeps them inert. A
lane WITHOUT a request (a ghost, a retired or parked row's) carries an
EMPTY span: its entry of ``pad_j`` is the cache's length, a pad no depth
reaches, so the decode kernel, which streams each row's own ``[pad,
depth)``, reads nothing for it (``_empty_span``), and the state kernels
of a family whose rows hold a state stream the other lanes alone; a lane
WITH a request keeps its pad to the digit. A family that generates by
blocks streams spans too: every forward of a round is
``ops.block_decode``'s kernel a layer, over the same ``[pad, depth)``.
``attn_positions_streamed`` over ``attn_positions_rect`` (``stats()``)
says what share of the rectangle width x depth the live rows' spans are
(a forward of a round counted like a step), and
``state_lanes_streamed`` over ``state_lanes_compiled`` what share of
the compiled lanes the state kernels stream.

Compiled-program inventory (bounded): the engine's prefill programs
(prompt-bucketed: multiples of ``prompt_bucket``, or the ladder a family
of long prompts declares), ONE decode-segment program per (window bucket,
sampling, power-of-two batch width up to ``max_batch``) whatever a
call's length (the step count is an operand of the program: a call cut
at a row's budget or at the cache's end runs the same one), one admit
program per width, and one tiny grow program per adjacent width pair.

Speculative segments (``spec=``): a batch whose policy carries the
``SamplingConfig.spec`` flag advances through the speculative engine's
draft-verify SEGMENT program (runtime.spec_decode.``_seg_b``) instead of
the single-token segment scan: each segment runs up to
``seg_steps // (draft_len + 1)`` verify forwards, every row accepting
its own ``k_i in [0, draft_len]`` drafts per verify with a per-row
cache rewind (uniform-depth re-sync — rows stay mergeable, so admission
and retirement keep working mid-speculation). Per-row emission within a
segment is ragged, so a spec segment costs ONE host sync (fetching
per-row counts + the new depth) — the price of data-dependent progress,
same class as EOS-armed batches. Exactness bar unchanged: every row —
seeded sample rows included — is byte-equal to its solo
``SpecDecodeEngine.generate`` run (per-row key chains resume across
segments; joiners start their chain at their own step 0). Spec batches
admit only rows speculation is exact for (prompt >= ngram, draft_len
slots of headroom); the ``spec`` flag is part of policy equality, so a
spec arrival during a plain batch (or vice versa) closes admission and
seeds the next batch — the same FIFO-preserving policy-change handling
as any sampling change. One spec-segment program per (width, policy):
acceptance counts are traced, never program keys.

Prefix-cache composition (``prefix=``): admissions prefill through the
prefix store (``PrefixCachingEngine.prefill_state``) — a joiner whose
prompt shares a cached prefix forwards only its suffix before merging
into the live batch at the current depth. Exact (store replay is
byte-identical to a cold prefill) and compile-bounded by the store's
chunk programs.

Paged KV composition (``pool=``, runtime.kv_pool): rows' KV state is
accounted in ref-counted pool BLOCKS; fully-padded table positions point
at the shared trash block, so a short row costs
``ceil(content/block_size)`` blocks, not ``max_seq`` slots. The live
batch keeps its contiguous working cache on the device from its seed to
its end, as the un-pooled scheduler does (``state.cache``,
``_admit_cache``), and runs the UNCHANGED segment program on it (same
program keys, byte-identical tokens); the pool is WRITTEN while the
batch lives: the seed's prefill and a joiner's row whole, and behind
every decode call only the table columns that call wrote
(``KVBlockPool.scatter_span``: two or three blocks a row where the
table has ``max_seq / block_size``). So at every boundary a gather of
a live row's table equals the resident row over ``[pad, depth)``: the
pool stays truthful for whoever reads blocks (the prefix store, a tier,
a kernel that reads through the tables). The batch itself reads it
once a GROW: the wider cache is gathered from the pool, so that two
widths of it never stand side by side (``_grow``). Two kinds of batch keep a whole gather in front of every call and a
whole scatter behind it: one on a QUANTIZED pool (its served tokens
depend on every position being read back through its block's scale),
and a speculative one (its segment rolls whole rows). What a row holds
beside its positions (``models.row_state``) is a lane of the same
working cache from the seed or the join to the row's end: the state
slab beside the pool keeps the prefix store's snapshots and no live
row's record, and no call moves one. The pool is also the ADMISSION
authority:

- admission of a policy-compatible request defers (without closing the
  batch) while the allocator's watermark says its blocks don't fit —
  and ``serving.app`` turns sustained refusal into 429 + Retry-After;
- when live rows GROW past a block boundary and allocation fails even
  after LRU-evicting prefix entries, the scheduler PREEMPTS the
  lowest-priority row (latest admission order): fetch its emitted
  tokens, free its blocks, park it. Parked rows resume — oldest first,
  before any queued request — by RECOMPUTE: re-prefill prompt +
  already-emitted tokens (one bucketed solo prefill, exactly the
  admission move) and continue the row's own per-step PRNG chain.
  Byte-identical to the un-preempted stream (prefix-stable key splits;
  prefill-recomputed KV equals incrementally-decoded KV — pinned by
  tests for greedy and seeded sample, plain and spec batches).

Every admission/watermark/preemption quantity above is denominated in
BLOCKS (``allocator.blocks_for``), never bytes — so a quantized pool
(``block_dtype`` set: narrow storage, smaller bytes-per-block) raises
the admissible row count at a fixed HBM budget purely by being built
with more blocks, with zero scheduler branches. Under quantized storage
the resume-by-recompute stream is equivalent within the declared
``kv.int8``/``kv.fp8`` tolerance budgets rather than byte-identical
(rescattering recomputes content scales — see runtime.kv_pool); the
full-precision pool keeps every byte-equality pin above.
"""

from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import paged_attention as PA
from ..ops.attention import KVCache
from ..ops.decode_attention import BLOCK_S, streamed_blocks
from ..utils import graftfault, graftmem, graftsched, graftscope, \
    grafttime, tracing
from ..utils.metrics import REGISTRY, kv_block_gauges
from .batcher import _round_up
from .engine import (DecodeEngine, GenerateResult, SamplingConfig,
                     select_token)


# Static-analysis contract (tools/graftcheck): every ``jax.jit`` site in
# this module, by holding name — enumerated by the recompile-budget
# certifier; an undeclared site is a lint finding.
JIT_ENTRY_POINTS = ("_admit_cache",)

# Observability contract (tools/graftcheck scope pass + utils/graftscope):
# the admission-merge program's dispatches are timed into the graftscope
# ring (graftscope.instrument at the jit site below).
PROFILED_SCOPES = ("_admit_cache",)

# Donation contract (tools/graftcheck sanitize pass): ``_admit_cache``
# consumes the live batch cache (arg 0) — callers re-bind
# ``state.cache`` from its output, never the donated input.
DONATED_ARGS = {"_admit_cache": (0,)}

# Pool-mover lease scopes (tools/graftcheck sanitize pass): the only
# functions allowed to invoke pool gather/scatter movers — each holds a
# live BlockAllocator lease on every block id it moves (table entries
# are this batch's ``_Slot.blk_ids`` allocations or the trash block).
POOL_MOVER_SCOPES = ("IterBatchingEngine._init_tables",
                     "IterBatchingEngine._place_admitted",
                     "IterBatchingEngine._grow",
                     "IterBatchingEngine._advance",
                     "IterBatchingEngine._advance_spec")

# Decode hot-loop scopes (tools/graftcheck host-sync rule): the segment
# dispatch loop is the zero-sync fast path; the spec variant's syncs are
# the documented per-segment price and are baselined. The one wait a
# plain batch's loop makes is BETWEEN dispatches and on purpose
# (``_hold_lead``: the host stays one call ahead of the device, no
# more); it fetches nothing.
GRAFTCHECK_HOT_LOOPS = ("IterBatchingEngine._advance",
                        "IterBatchingEngine._advance_spec")

# Fault contract (tools/graftcheck faults pass): the scheduler's two
# blocking boundaries. The caller's ``done.wait`` derives its budget
# from the request deadline (and cancellation frees the row's blocks at
# the next segment boundary); the worker's bare ``_queue.get`` is the
# idle park — deadlines are checked at every dequeue, so a stale
# request is failed typed instead of decoded for nobody.
FAULT_POLICY = {
    "done.wait": ("request", "none",
                  "cancel + free blocks at the next segment boundary"),
    "_queue.get": ("unbounded", "none",
                   "idle worker; deadline checked at dequeue"),
}

# Transient decode faults (graftfault.TransientFault — injected engine
# exceptions, and the class real transient device failures map to) park
# the live rows through the PR 5 recompute-resume path; a row that
# keeps faulting past this many parks fails typed instead of cycling
# forever.
FAULT_PARK_BUDGET = 3

# Timeline contract (tools/graftcheck timeline pass): the scheduler's
# lifecycle decisions land on the unified causal stream
# (utils/grafttime), rid-correlated — admission (seed/join), park
# (with its reason), preemption victim choice, recompute-resume, and
# the per-row fault-park-budget breaker state. Shared batched
# dispatches carry the live rid set via ``grafttime.correlate`` around
# the segment/seed dispatch regions (the fanout-span analog).
TIMELINE_EVENTS = {
    "admission": "_seed_batch / _admit_one_inner",
    "park": "_park_slot",
    "preempt": "_preempt_lowest",
    "resume": "_seed_batch / _admit_one_inner",
    "breaker": "_fault_park_all (per-row park-budget state)",
}

# HBM-ledger contract (tools/graftcheck memory pass + utils/graftmem):
# the live batch's long-lived device holdings, by graftmem component —
# both live on ``_BatchState`` (handle-keyed per batch). ``cache`` is
# the contiguous working cache (registered at seed, re-measured at
# grow/admit rebinds, released when the batch tears down, or at the
# seed where a quantized pool or a speculative batch gives it up to the
# pool); ``buf`` is the spec verify token buffer (spec batches only).
# The pool's block storage is the POOL's ledger entry
# (runtime/kv_pool.py): a resident working cache beside it is a second
# copy of the live rows and is counted as one.
MEMORY_LEDGER = {
    "cache": "engine_cache",
    "buf": "spec_buffers",
}

# Lock-discipline contract (tools/graftcheck locks pass): the scheduler
# counters AND the cross-thread scheduling state (``_parked`` parked
# rows, ``_pending`` held queue head) live under ``_stats_lock`` —
# serving threads read them through ``admission_load``/``stats`` while
# the worker mutates them, which is exactly the lost-update/stale-read
# window the pass exists to flag (the worker routes every touch through
# the tiny *_locked-discipline helpers below). ``_np`` is the lazily
# materialized host copy ``_SegOut`` guards with its own ``_lock``.
GUARDED_STATE = {
    "batches_run": "_stats_lock", "rows_served": "_stats_lock",
    "joins": "_stats_lock", "segments_run": "_stats_lock",
    "spec_segments_run": "_stats_lock", "eos_retires": "_stats_lock",
    "segments_cut": "_stats_lock", "steps_paid": "_stats_lock",
    "gaps_answered": "_stats_lock",
    "calls_resident": "_stats_lock", "cache_gathers": "_stats_lock",
    "blocks_written_back": "_stats_lock",
    "attn_positions_streamed": "_stats_lock",
    "attn_positions_rect": "_stats_lock",
    "state_lanes_streamed": "_stats_lock",
    "state_lanes_compiled": "_stats_lock",
    "state_calls_resident": "_stats_lock", "_state_joined": "_stats_lock",
    "_state_rows": "_stats_lock", "_state_peak": "_stats_lock",
    "grows": "_stats_lock", "preemptions": "_stats_lock",
    "resumes": "_stats_lock", "fault_parks": "_stats_lock",
    "batches_closed": "_stats_lock", "_turned": "_stats_lock",
    "_moe": "_stats_lock", "_rounds": "_stats_lock",
    "_window": "_stats_lock",
    "_parked": "_stats_lock",
    "_pending": "_stats_lock",
    "_np": "_lock",
}

# ``_stats_lock`` holds are leaf-scoped (list/counter ops only) and the
# _SegOut fetch lock never nests inside them; the declared order keeps
# it that way.
LOCK_ORDER = ("_stats_lock", "_lock")


# Why the head of the queue stays where it is (the ``queue_wait`` span's
# ``<reason>_ms`` labels): the live batch is closed to admission and has
# to drain; the batch is full at ``max_batch``; the pool has no room;
# and everything else — the scheduler is inside a dispatch, an admission
# of someone ahead, or ``_seed``'s wait.
_WAIT_REASONS = ("closed", "slot", "pool", "boundary")


# What the scheduler thread is doing, one state at every instant of its
# life (``tracing.StateLog``; ``IterBatchingEngine.states``): ``idle``
# in ``_loop``'s wait for a request with nothing parked, pending or
# live; ``hold`` in ``_hold_lead``'s wait for the device; ``seed``,
# ``admit`` and ``advance`` in ``_seed``, ``_admit`` and ``_advance``
# with everything under them; ``other`` for what is left (the loops' own
# lines, a batch's teardown). ``stats()`` gives their seconds as
# ``t_<state>_s``. On the profiler's clock each is a span under the name
# the benchmark's wrapper has used for that function, so a gap's name
# reads the same with the wrapper or without; ``other`` carries none.
_STATES = ("idle", "hold", "seed", "admit", "advance", "other")
_STATE_SPANS = {"idle": "sched.idle", "hold": "sched.hold_lead",
                "seed": "sched.seed", "admit": "sched.admit",
                "advance": "sched.segment_dispatch"}


def _rid_of(req) -> Optional[str]:
    """The request's timeline correlator (its trace's X-Request-ID);
    None for untraced engine-level calls."""
    return getattr(req.trace, "request_id", None)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclasses.dataclass
class _Req:
    prompt: np.ndarray
    max_new_tokens: int
    sampling: SamplingConfig
    key: Optional[jax.Array]
    eos_id: Optional[int]
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    payload: Optional[tuple] = None   # (_Slot, eos_at) — caller assembles
    error: Optional[Exception] = None
    # Set by generate() on timeout: the caller is gone, so the scheduler
    # drops the request at dequeue and frees its slot at the next
    # retirement pass instead of decoding dead tokens for nobody.
    cancelled: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    # per-request deadline budget (graftfault.Deadline): checked at
    # every dequeue and segment boundary — a past-deadline request/row
    # is failed typed and its blocks freed, never decoded for nobody
    deadline: Optional[graftfault.Deadline] = None
    # request-trace propagation (caller's ambient RequestTrace): the
    # scheduler stamps queue wait, the admission prefill, and every
    # decode segment the row rode into it
    trace: Optional[object] = None
    t_submit: float = 0.0

    def fail(self, e: Exception) -> None:
        """Deliver an error exactly once (idempotent across the several
        except paths that may observe the same request)."""
        if not self.done.is_set():
            self.error = e
            self.done.set()


class _SegOut:
    """One segment's [B, n] token output, fetched to host at most once
    (several retiring rows may share it; caller threads race the fetch,
    hence the lock). The device->host copy starts ASYNC at construction
    so it overlaps later segments — by delivery time it is usually
    already resident."""

    def __init__(self, arr):
        self.arr = arr
        self._np = None
        self._lock = graftsched.lock("iterbatch._SegOut._lock")
        try:
            arr.copy_to_host_async()
        except AttributeError:  # non-jax array (tests)
            pass

    @property
    def np(self) -> np.ndarray:
        with self._lock:
            if self._np is None:
                # OWNING copy, not np.asarray: on the CPU backend
                # np.asarray returns a ZERO-COPY view of the device
                # buffer, and once the next segment DONATES the array
                # XLA may rewrite that memory in place under the view —
                # the snapshot would silently shift (observed as
                # rolled-buffer corruption in parked spec rows)
                self._np = np.array(self.arr, copy=True)
            return self._np

    def row(self, row: int, plane: int = 0) -> np.ndarray:
        """One row's columns: its tokens, or (``plane`` 1, a call of
        rounds: ``engine._decode_rounds``) the forward that fixed each."""
        a = self.np[row]
        return a if a.ndim == 1 else a[plane]


@dataclasses.dataclass
class _Slot:
    req: _Req
    plen: int
    row: int                      # this slot's batch row index (fixed)
    first_ref: Optional["_SegOut"]  # holds the first generated token ...
    first_idx: int                # ... at this index (None for resumed
                                  # rows: resumed_prefix replaces it)
    dk: Optional[jax.Array]       # per-row decode key (sample mode)
    emitted: int = 1              # tokens generated so far (incl. first)
    # (_SegOut, lo, n): the row's n tokens of that call, from column lo
    segs: List = dataclasses.field(default_factory=list)
    # generation by blocks: positions of the row's NEXT block that its
    # prompt already fills (its first round yields that many tokens
    # fewer; 0 from then on, and for every other family)
    given: int = 0
    # admission order: THE preemption priority (higher = admitted later
    # = preempted first). Monotonic across the scheduler's lifetime.
    order: int = 0
    # the row's left pad in the batch's cache, as ``pad_j`` holds it on
    # the device (a plain batch never moves it): its span is
    # ``[pad, depth)``, which the decode kernel streams and
    # ``attn_positions_streamed`` counts
    pad: int = 0
    # pool mode: this row's block ids at table columns
    # [blk_lo, blk_lo + len(blk_ids)) — everything outside points at
    # the trash block
    blk_lo: int = 0
    blk_ids: List[int] = dataclasses.field(default_factory=list)
    # tokens emitted before a preemption (host copy); delivery prepends
    # them in place of first_ref
    resumed_prefix: Optional[np.ndarray] = None
    # Spec-mode delivery state: the latest segment's [B, buflen] token
    # buffer (prompt + everything emitted, per row, left-aligned at the
    # row's pad) and this row's pad at that moment — _row_tokens reads
    # the stream straight out of it, no per-segment part list needed.
    spec_buf: Optional["_SegOut"] = None
    spec_pad: int = 0
    # transient-fault parks this row has already absorbed (graftfault):
    # past FAULT_PARK_BUDGET the row fails typed instead of re-parking
    fault_budget_used: int = 0
    t0: float = 0.0
    done_t: float = 0.0


def _admit_cache_impl(cache, solo, slot, roll):
    """Merge a solo-prefilled row into batch slot ``slot``: the row's
    K/V content rolls from solo slots ``[sp - plen, sp)`` to the batch's
    ``[d - plen, d)`` (``roll = d - sp``; wrap garbage lands in the
    masked pad prefix or in not-yet-written slots that decode overwrites
    before reading). ``slot``/``roll`` are traced scalars — one compiled
    program serves every admission. Handles plain, fused (placeholder
    ``v``), and staged (list) cache forms."""
    def one(c: KVCache, s: KVCache) -> KVCache:
        k = jax.lax.dynamic_update_slice_in_dim(
            c.k, jnp.roll(s.k, roll, axis=-2), slot, axis=1)
        if getattr(c.v, "ndim", 0) <= 1:      # fused cache: v placeholder
            v = c.v
        else:
            v = jax.lax.dynamic_update_slice_in_dim(
                c.v, jnp.roll(s.v, roll, axis=-2), slot, axis=1)
        # what a row holds beside its positions has no slot axis to
        # roll: the joiner's goes into its batch row as it is
        state = (None if c.state is None else tuple(
            jax.lax.dynamic_update_slice_in_dim(x, y.astype(x.dtype), slot,
                                                axis=1)
            for x, y in zip(c.state, s.state)))
        return KVCache(k=k, v=v, length=c.length, state=state)

    if isinstance(cache, list):
        return [one(c, s) for c, s in zip(cache, solo)]
    return one(cache, solo)


def _admit_cache_scope_key(cache, solo, slot, roll):
    """Program key: (batch width, cache width, solo width) — slot/roll
    are traced and never key programs."""
    c = cache[0] if isinstance(cache, list) else cache
    s = solo[0] if isinstance(solo, list) else solo
    return (int(c.k.shape[1]), int(c.k.shape[-2]), int(s.k.shape[-2]))


_admit_cache = graftscope.instrument(
    jax.jit(_admit_cache_impl, donate_argnums=(0,)),
    "iterbatch._admit_cache", key_fn=_admit_cache_scope_key)


def _widen_state(state, rows: int):
    """``rows`` more lanes behind a working cache's row state (``None``
    where the family has none). The new lanes are EMPTY: a lane without
    a request has an empty span, and the state kernels copy its record
    neither in nor out (``_empty_span``); what else reads it computes
    on zeros, for nobody."""
    if state is None:
        return None
    return tuple(jnp.pad(x, [(0, 0), (0, rows)] + [(0, 0)] * (x.ndim - 2))
                 for x in state)


@dataclasses.dataclass
class _Parked:
    """A preempted row between its park and its resume: everything the
    recompute path needs to reproduce the stream byte-identically."""

    req: _Req
    plen: int
    emitted: int                  # tokens generated before the park
    tokens: np.ndarray            # those tokens, fetched to host
    order: int                    # original admission order (priority)
    t0: float                     # original admission wall-clock
    preempt_t: float = 0.0
    spec_key: Optional[np.ndarray] = None  # verify key chain (spec rows)
    fault_budget_used: int = 0    # transient-fault parks absorbed so far


class _BatchState:
    """The live batch between segments (worker-thread-only state)."""

    def __init__(self, sampling, token, cache, pad_j, depth):
        self.sampling = sampling
        self.token = token            # [B] device
        self.cache = cache            # the working cache, resident from
                                      # seed to end; None where the pool
                                      # is gathered for every call
                                      # (``_init_tables``)
        self.pad_j = pad_j            # [B] device int32
        self.depth = depth            # uniform cache depth (host int)
        self.tables: Optional[np.ndarray] = None   # [B, NBm] (pool mode)
        self.slots: List[Optional[_Slot]] = []
        self.closed = False           # True: no more admissions (FIFO)
        self.batch = 0                # ``batches_run`` it was seeded under
        # the newest handover of this batch (tracing.READY.hand): the
        # seed's first-token array, then each plain segment's output
        self.ready = None
        # speculative batches only: device token buffer [B, buflen]
        # (prompt + emitted per row, content ending at depth + 1) and
        # the per-row verify key chains [B, 2] (sample mode)
        self.spec_mode = False
        self.buf = None
        self.keys = None
        self.spec_ready: Optional[float] = None   # its newest segment's sync
        # HBM ledger handles (utils/graftmem): released by _run_batch
        # at batch teardown (the owner finalizer backstops any path
        # that drops the state without reaching it)
        self.mem_cache = (graftmem.track(self, "cache", "engine_cache",
                                         cache)
                          if cache is not None else 0)
        self.mem_buf = 0

    def active(self):
        return any(s is not None for s in self.slots)


class IterBatchingEngine:
    """Thread-safe iteration-level batching front end over a
    ``DecodeEngine`` (same calling convention as ``BatchingEngine``).

    ``seg_steps`` is the LONGEST decode call: a call ends where the
    first live row's budget ends (or the cache does) and after
    ``seg_steps`` steps otherwise, and admissions and retirements happen
    at every call's end, so a row is answered at its last token.
    Smaller = lower join latency, more scheduler work; larger = better
    dispatch pipelining. A request's worst-case join delay is one call.
    """

    def __init__(self, engine: DecodeEngine, max_batch: int = 8,
                 seg_steps: int = 32, max_wait_ms: float = 2.0,
                 prompt_bucket: int = 16, spec=None, prefix=None,
                 pool=None, queue_limit: Optional[int] = None,
                 replica: Optional[str] = None):
        """``spec`` (optional ``SpecDecodeEngine`` wrapping THIS engine)
        enables speculative segments: batches whose policy carries
        ``SamplingConfig.spec`` advance by draft-verify forwards instead
        of single-token steps (see module docstring). ``prefix``
        (optional ``PrefixCachingEngine`` wrapping THIS engine) routes
        admission prefills through the prefix store, so a joiner with a
        warm prefix forwards only its suffix.

        ``pool`` (optional ``runtime.kv_pool.KVBlockPool`` matching THIS
        engine's cache geometry) turns on paged KV storage, watermark
        admission, and preemption/resume (module docstring).
        ``queue_limit`` feeds ``admission_load`` (the serving 429
        decision): with the pool unable to host a request AND at least
        this many requests already waiting/parked, serving sheds load
        instead of queueing unboundedly. Defaults to ``max_batch``.

        ``replica`` labels the worker thread's timeline events
        (grafttime's replica correlator): the serving handler's
        ambient label is a contextvar on ITS thread, so without this
        the scheduler-side events (admission/park/resume/dispatch)
        would carry no replica in a fleet's unified stream."""
        from ..models import is_window_independent
        if not is_window_independent(engine.config):
            raise NotImplementedError(
                "iteration-level batching requires window-independent "
                "routing (a joined MoE row's tokens could depend on "
                "batch composition); MoE serves via the admission "
                "batcher")
        if engine.prefill_chunk:
            raise NotImplementedError(
                "iteration-level batching prefills admissions solo at "
                "bucketed lengths; it does not compose with "
                "prefill_chunk (use the admission batcher)")
        if engine._mesh is not None:
            raise NotImplementedError(
                "iteration-level batching drives the single-device "
                "engine; mesh decode (tp/ep) uses the admission batcher")
        if spec is not None and spec.plain is not engine:
            raise ValueError("spec must wrap the same DecodeEngine (shared "
                             "weights/programs), got a different instance")
        if prefix is not None and prefix.plain is not engine:
            raise ValueError("prefix must wrap the same engine instance")
        if pool is not None and pool.max_seq != engine._cache_seq:
            raise ValueError(
                f"pool rows span {pool.max_seq} slots, engine cache is "
                f"{engine._cache_seq}; gathered segments must match the "
                "compiled programs' cache width")
        # a family that generates by ROUNDS over blocks of ``_unit``
        # positions (engine.block; ops.block_diffusion): a call is
        # rounds, a row yields a whole block a round, and every depth,
        # pad and stored chunk is whole blocks. 1: a step, a token.
        self._blocks = engine.block is not None
        self._unit = engine.block.block_length if self._blocks else 1
        if self._blocks:
            if spec is not None:
                raise NotImplementedError(
                    "a family that generates by blocks does not speculate")
            for what, n in (("seg_steps", seg_steps),
                            ("prompt_bucket", prompt_bucket),
                            ("the prefix store's chunk",
                             prefix.chunk if prefix is not None else 0),
                            ("the pool's block_size",
                             pool.block_size if pool is not None else 0)):
                if n % self._unit:
                    raise ValueError(
                        f"{what}={n} is not whole blocks of {self._unit}: "
                        "a depth, a pad or a stored chunk would end "
                        "inside a block")
        self.engine = engine
        self.spec = spec
        self.prefix = prefix
        self.pool = pool
        # the state slab beside the pool (runtime.state_slab), for a
        # family whose rows hold a state beside their positions: the
        # store's snapshots; a live row's record is its lane of the
        # batch's working cache
        self._slab = getattr(pool, "slab", None)
        from ..models import row_state
        if (pool is not None and self._slab is None
                and row_state(engine.config, engine.dtype)):
            raise ValueError(
                f"{type(engine.config).__name__}'s rows hold a state "
                "beside their positions: build the pool with "
                "KVBlockPool.for_engine(..., state_slots=)")
        self.queue_limit = max_batch if queue_limit is None else queue_limit
        self.replica = replica
        self.max_batch = max_batch
        self.seg_steps = seg_steps
        self.max_wait_s = max_wait_ms / 1e3
        self.prompt_bucket = prompt_bucket
        self._queue: "queue.Queue[_Req]" = queue.Queue()
        self._pending: Optional[_Req] = None
        self._parked: List[_Parked] = []   # preempted rows, oldest first
        self._order = 0                    # admission-order counter
        #                                    (worker-thread-only)
        self._stats_lock = graftsched.lock(
            "iterbatch.IterBatchingEngine._stats_lock")
        self.batches_run = 0
        self.rows_served = 0
        self.joins = 0                # admissions into a LIVE batch
        self.segments_run = 0
        self.spec_segments_run = 0    # draft-verify segments (spec mode)
        self.eos_retires = 0
        # calls that ran fewer than seg_steps steps because a row's
        # budget ended there; the decode steps the rows delivered were
        # live for, and the gaps between the tokens they were answered
        # with (steps paid per gap: 1.0 when every row ends by budget)
        self.segments_cut = 0
        self.steps_paid = 0
        self.gaps_answered = 0
        # pooled batches: decode calls that ran on the resident cache
        # with no gather in front of them, whole gathers of a live
        # batch (one a grow; one a call where the pool keeps the cache),
        # and blocks of live rows the write-backs behind the calls
        # rewrote
        self.calls_resident = 0
        self.cache_gathers = 0
        self.blocks_written_back = 0
        # batches whose rows hold a state: decode calls that ran on the
        # resident row state (every one), the records joiners' merges
        # wrote into a lane, and the records the live batch holds now
        # and held at most together with the slab's snapshots
        self.state_calls_resident = 0
        self._state_joined = 0
        self._state_rows = 0
        self._state_peak = 0
        # cache positions the decode kernel's stream reads for the live
        # rows of the plain calls (each row's own span ``[pad, depth)`` in
        # whole blocks, summed over a call's steps), and the positions of
        # the rectangle width x depth a stream of whole batches reads:
        # how often spans spare the kernel a read (``_count_stream``)
        self.attn_positions_streamed = 0
        self.attn_positions_rect = 0
        # lanes the state kernels stream (the live rows') and lanes of
        # the compiled width, a step, for the plain calls of batches
        # whose rows hold a state in the slab
        self.state_lanes_streamed = 0
        self.state_lanes_compiled = 0
        # what a lane WITHOUT a request holds in ``pad_j``: a pad no
        # depth reaches, so that its span is empty and the kernel reads
        # nothing for it (``_vacate``)
        self._no_span = int(engine._cache_seq)
        # table columns one call's positions can span (pool mode)
        self._span = (None if pool is None else PA.span_blocks(
            seg_steps, pool.block_size, pool.nbm))
        self.grows = 0                # width upgrades of a live batch
        self.preemptions = 0          # rows parked under pool pressure
        self.resumes = 0              # parked rows recomputed back in
        self.fault_parks = 0          # transient-fault park events
        self.batches_closed = 0       # batches that ended closed
        # how often _admit turned the head away, by rule: closed for a
        # prompt longer than the live depth or a generation past the
        # cache / for a sampling mismatch; deferred for a slot / for
        # pool room
        self._turned = dict.fromkeys(
            ("closes_depth", "closes_policy", "defers_slot",
             "defers_pool"), 0)
        # routing sums of a family whose cache carries counters
        # (engine.cache_counters; models.latent_moe): what the decode
        # segments and the prefills handed back, added up as each
        # becomes ready. Empty for the dense families.
        routing = [k for k in engine.cache_counters
                   if not k.startswith("block_")]
        self._moe = dict.fromkeys(
            [f"moe.{k}" for k in routing]
            + [f"moe.prefill_{k}" for k in routing], 0)
        # what the rounds of a family that generates by blocks counted
        # (``block_*`` among the cache's counters;
        # ``ops.block_diffusion.COUNTERS`` says what each is)
        self._rounds = dict.fromkeys(
            [f"block.{k[6:]}" for k in engine.cache_counters
             if k.startswith("block_")], 0)
        # a family whose sliding-window layers hold a window of a row
        # and not its depth (``window_positions``; models.window_moe):
        # what the records allocated for the live rows hold and the
        # depths those rows have reached, as of the last scheduling
        # decision
        self._window: dict = {}
        self._window_positions = engine.family.window_positions
        # a family that serves prompts of thousands of positions says
        # how coarsely a lone prompt is bucketed (``prompt_bucket``;
        # models.window_moe); the others take multiples of the argument
        self._family_bucket = engine.family.prompt_bucket
        # (instant, reason) transitions of what holds the head of the
        # queue (worker-thread-only): every admitted request's wait is
        # cut by it. 4096 transitions span minutes of boundaries; a wait
        # older than the list counts as "boundary"
        self._wait_log: "collections.deque" = collections.deque(maxlen=4096)
        # token arrays of the plain decode calls dispatched and not yet
        # known to have run, oldest first, whichever batch they served
        # (worker-thread-only; ``_hold_lead``)
        self._in_flight: "collections.deque" = collections.deque()
        # the worker's own time by state (``_STATES``), from its start
        self.states = tracing.StateLog(_STATES, "other", _STATE_SPANS,
                                       label=replica)
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._worker.start()

    # -- caller side ---------------------------------------------------------

    def generate(self, prompt_ids, max_new_tokens: int,
                 sampling: SamplingConfig = SamplingConfig(),
                 key: Optional[jax.Array] = None,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = None,
                 deadline: Optional[graftfault.Deadline] = None,
                 ) -> GenerateResult:
        prompt = np.asarray(prompt_ids, dtype=np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("prompt must be non-empty")
        # (a family that generates by blocks writes whole blocks: what
        # the prompt leaves over and the answer, rounded up to one)
        rest = len(prompt) % self._unit
        if (len(prompt) - rest + _round_up(max_new_tokens + rest, self._unit)
                > self.engine.max_seq):
            raise ValueError(
                f"prompt_len={len(prompt)} + max_new_tokens="
                f"{max_new_tokens} exceeds max_seq={self.engine.max_seq}")
        if self._blocks and (len(prompt) < self._unit
                             or sampling.mode != "greedy"):
            raise ValueError(
                f"generation by blocks of {self._unit} takes a prompt of "
                "at least one block and chooses greedily")
        if sampling.mode != "greedy" and key is None:
            raise ValueError(
                "sample-mode requests must carry a per-request PRNG key")
        if sampling.spec:
            # caller-thread eligibility: a spec-flagged request the
            # verify loop cannot serve exactly must be refused HERE with
            # its own numbers, not discovered mid-batch (rule defined
            # once, on the engine)
            if self.spec is None:
                raise ValueError(
                    "sampling.spec requested but this scheduler has no "
                    "speculative engine attached (pass spec= at "
                    "construction)")
            self.spec.check_request(len(prompt), max_new_tokens)
        if deadline is not None:
            deadline.raise_if_expired("iter-batched generate")
        req = _Req(prompt=prompt, max_new_tokens=max_new_tokens,
                   sampling=sampling, key=key, eos_id=eos_id,
                   deadline=deadline,
                   trace=tracing.current_trace(),
                   t_submit=time.perf_counter())
        self._queue.put(req)
        REGISTRY.gauge("queue_depth", self._queue.qsize(),
                       scheduler="iter")
        # the caller's wait derives from the remaining deadline budget:
        # HTTP wait is the first leg the budget bounds end-to-end
        wait = timeout
        if deadline is not None:
            rem = deadline.remaining()
            wait = rem if wait is None else min(wait, rem)
        if not req.done.wait(wait):
            # Cancel, don't just abandon: the scheduler skips cancelled
            # requests at dequeue and retires a cancelled live row at the
            # next segment boundary, so repeated timeouts cannot
            # accumulate dead decode work (ADVICE r4).
            req.cancelled.set()
            if deadline is not None and deadline.expired():
                raise graftfault.DeadlineExceeded(
                    "iter-batched generate: deadline budget exhausted; "
                    "in-flight work is cancelled at the next segment "
                    "boundary and its blocks freed")
            raise TimeoutError("iter-batched generate timed out")
        if req.error is not None:
            raise req.error
        # token assembly (the device->host fetches) happens HERE, on the
        # caller's thread: the scheduler thread only marks rows done, so
        # it never blocks on a transfer and keeps dispatching segments.
        # The async copies started at segment creation usually make this
        # a no-wait read.
        s, eos_at = req.payload
        new = self._row_tokens(s)
        if req.trace is not None:
            # the fetch above waited for the same arrays: stamp whatever
            # ready instant the waiter has not got round to
            req.trace.settle()
        if eos_at is not None:
            new = new[:eos_at + 1]
        tokens = np.concatenate([req.prompt, new])[None, :]
        # Timing caveat: the scheduler never syncs per phase, so
        # decode_seconds here is the row's WALL time from admission to
        # retirement (prefill + shared segments + scheduling), not a
        # pure decode window — an honest end-to-end number, but do not
        # read tokens_per_second as a device decode rate.
        fixed_at = None
        if self._blocks and s.resumed_prefix is None:
            fixed_at = np.concatenate(
                [seg.row(s.row, 1)[lo:lo + n] for seg, lo, n in s.segs]
            )[None, :len(new)]
        return GenerateResult(
            tokens=tokens, prompt_len=s.plen,
            prefill_seconds=0.0, decode_seconds=s.done_t - s.t0,
            new_tokens=len(new), decode_steps=len(new) - 1,
            fixed_at=fixed_at)

    def stats(self) -> dict:
        with self._stats_lock:
            out = {"batches": self.batches_run, "rows": self.rows_served,
                   "joins": self.joins, "segments": self.segments_run,
                   "spec_segments": self.spec_segments_run,
                   "eos_retires": self.eos_retires,
                   "segments_cut": self.segments_cut,
                   "steps_paid": self.steps_paid,
                   "gaps_answered": self.gaps_answered,
                   "calls_resident": self.calls_resident,
                   "cache_gathers": self.cache_gathers,
                   "blocks_written_back": self.blocks_written_back,
                   "attn_positions_streamed": self.attn_positions_streamed,
                   "attn_positions_rect": self.attn_positions_rect,
                   "state_lanes_streamed": self.state_lanes_streamed,
                   "state_lanes_compiled": self.state_lanes_compiled,
                   "grows": self.grows,
                   "preemptions": self.preemptions,
                   "resumes": self.resumes,
                   "fault_parks": self.fault_parks,
                   "batches_closed": self.batches_closed,
                   **self._turned, **self._moe, **self._rounds,
                   **self._window,
                   "parked": len(self._parked)}
            resident, joined, rows, peak = (
                self.state_calls_resident, self._state_joined,
                self._state_rows, self._state_peak)
        if self._slab is not None:
            # the records the device has room for and those it holds:
            # the widest working state's lanes beside the slab's slots
            st = self._slab.stats()
            st["state.slots"] += self.max_batch
            st["state.in_use"] += rows
            st["state.peak"] = max(st["state.peak"], peak)
            st["state.rows_scattered"] += joined
            out.update(st, state_calls_resident=resident)
        out.update({f"t_{k}_s": v for k, v in self.states.totals().items()})
        return out

    def admission_load(self, prompt_len: int,
                       max_new_tokens: int) -> Tuple[bool, float]:
        """The serving 429 decision: can this request reasonably be
        queued, or is the pool saturated AND the queue already at its
        limit (sustained overload — shed with Retry-After)? Always
        admits without a pool (the pre-pool unbounded-queue behavior)."""
        if self.pool is None:
            return True, 0.0
        # admission footprint (the prefill's blocks) — growth past it is
        # the preemption machinery's business, not the 429 gate's.
        # ``can_admit`` here is ADVISORY (load shedding): the worker's
        # actual grant goes through the atomic ``admit_alloc`` path, so
        # a stale answer costs one queue beat, never a request failure.
        need = self.pool.allocator.blocks_for(prompt_len)
        with self._stats_lock:
            waiting = (self._queue.qsize() + len(self._parked)
                       + (1 if self._pending is not None else 0))
        # seeded pool-exhaustion spike (graftfault): the 429 gate sheds
        # exactly as it would under a real capacity storm, so the shed
        # path (Retry-After plausibility, rejection counter, allocator
        # conservation) is testable deterministically
        spike = graftfault.inject("iterbatch.admission_load",
                                  "pool_spike")
        if spike is None and (self.pool.allocator.can_admit(need)
                              or waiting < self.queue_limit):
            return True, 0.0
        # crude but honest: each max_batch-wide wave of waiters needs
        # roughly one batch lifetime to drain
        return False, float(1 + waiting // max(self.max_batch, 1))

    # -- worker side ---------------------------------------------------------

    # The worker owns ``_parked``/``_pending`` mutation, but serving
    # threads read both (``admission_load``, ``stats``) — so EVERY touch
    # goes through these leaf-locked helpers (the locks-pass
    # unguarded-state contract; before this discipline, ``stats`` read
    # ``_parked`` under ``_stats_lock`` while the worker mutated it with
    # no lock at all — guarded in one place and bare in another).

    def _peek_parked(self) -> Optional[_Parked]:
        with self._stats_lock:
            return self._parked[0] if self._parked else None

    def _pop_parked(self) -> Optional[_Parked]:
        with self._stats_lock:
            return self._parked.pop(0) if self._parked else None

    def _park(self, parked: _Parked) -> None:
        # oldest-first resume order (sorted by admission order)
        with self._stats_lock:
            self._parked.append(parked)
            self._parked.sort(key=lambda p: p.order)

    def _take_pending(self) -> Optional[_Req]:
        with self._stats_lock:
            req, self._pending = self._pending, None
            return req

    def _get_pending(self) -> Optional[_Req]:
        with self._stats_lock:
            return self._pending

    def _set_pending(self, req: Optional[_Req]) -> None:
        with self._stats_lock:
            self._pending = req

    def _enter(self, state: str) -> None:
        """From now on the scheduler thread is in ``state``."""
        was, took = self.states.enter(state)
        if took:
            REGISTRY.inc("iter_scheduler_state_seconds_total", value=took,
                         state=was)

    def _mark(self, reason: str) -> None:
        """From now on the head of the queue waits for ``reason``."""
        if not self._wait_log or self._wait_log[-1][1] != reason:
            self._wait_log.append((time.perf_counter(), reason))

    def _turn_away(self, reason: str, counter: str) -> None:
        self._mark(reason)
        with self._stats_lock:
            self._turned[counter] += 1

    def _wait_labels(self, t0: float, t1: float) -> dict:
        """The wait ``[t0, t1)`` cut by the transitions: milliseconds
        under each reason, summing to the wait."""
        took = dict.fromkeys(_WAIT_REASONS, 0.0)
        end = t1
        for at, reason in reversed(self._wait_log):
            if at >= end:
                continue
            took[reason] += end - max(at, t0)
            end = max(at, t0)
            if at <= t0:
                break
        took["boundary"] += end - t0    # older than the oldest transition
        return {f"{r}_ms": round(v * 1e3, 3) for r, v in took.items()}

    def _req_dead(self, req: _Req) -> bool:
        """Cancelled OR past its deadline — either way nobody wants the
        work. A past-deadline request is failed typed here (once: the
        caller usually raised at its own wait expiry already, and marked
        the request cancelled) and marked cancelled so every later
        checkpoint skips it. The flight recorder gets the same
        ``deadline_exceeded`` span as on the mid-decode path
        (``_retire_finished``): on a loaded host a short budget runs out
        before the row is admitted."""
        if req.deadline is not None and req.deadline.expired():
            if req.trace is not None and not req.done.is_set():
                t = time.perf_counter()
                req.trace.add_span("deadline_exceeded", t, t,
                                   scheduler="iter", emitted=0)
            req.fail(graftfault.DeadlineExceeded(
                "deadline budget exhausted before the scheduler could "
                "run this request"))
            req.cancelled.set()
            return True
        return req.cancelled.is_set()

    def _loop(self):
        if self.replica is not None:
            # the worker thread's OWN context: every timeline event it
            # emits carries this app's replica label (the handler
            # thread's ambient label does not propagate here)
            grafttime.set_thread_replica(self.replica)
        while True:
            # parked rows outrank every queued request (they were
            # admitted first — FIFO priority): with any parked, the next
            # batch seeds from the parked head instead of the queue
            head = self._pop_parked()
            if head is not None:
                if self._req_dead(head.req):
                    continue
            else:
                head = self._take_pending()
                if head is None:
                    # nothing parked, pending or live: there is no request
                    self._enter("idle")
                    head = self._queue.get()
                    self._enter("other")
                if self._req_dead(head):
                    continue
            try:
                self._run_batch(head)
            except Exception as e:  # noqa: BLE001 — delivered per-request
                (head.req if isinstance(head, _Parked) else head).fail(e)

    def _compatible(self, state: _BatchState, ent) -> bool:
        """Can this entry (a fresh ``_Req`` or a ``_Parked`` resume)
        join the live batch right now? ONE predicate for both — a
        policy constraint added here gates resumes and fresh arrivals
        identically. Policy must match (the ``spec`` flag included — a
        spec arrival never joins a plain batch or vice versa), the
        tokens its prefill forwards must fit the current depth (content
        at ``[d - plen', d)``), and its remaining generation must fit
        the cache — with ``draft_len`` extra slots of verify-write
        headroom when the batch speculates. Pool room is checked
        SEPARATELY (``_reserve_blocks`` / ``admit_alloc``): a policy
        mismatch closes admission, missing pool room only defers it."""
        reserve = self.spec.draft_len if state.spec_mode else 0
        return (self._ent_req(ent).sampling == state.sampling
                and len(self._ent_ids(ent)) <= state.depth
                and state.depth + self._ent_need(ent) + reserve
                <= self.engine.max_seq)

    def _run_batch(self, head: _Req):
        self._hold_lead()
        self._enter("seed")
        try:
            state = self._seed(head)
        finally:
            self._enter("other")
        try:
            while state.active():
                self._hold_lead()
                if not state.closed:
                    self._enter("admit")
                    self._admit(state)
                self._enter("advance")
                try:
                    # the segment dispatch serves every live row: its
                    # instrumented dispatches (and any fault injected
                    # inside) carry the live rid set on the timeline
                    with grafttime.correlate(
                            [_rid_of(s.req) for s in state.slots
                             if s is not None]):
                        self._advance(state)
                except graftfault.TransientFault as e:
                    # degraded mode: a transient decode fault parks
                    # every live row through the PR 5 recompute-resume
                    # path — resumed streams are byte-identical; a row
                    # past its park budget fails typed (503) instead of
                    # cycling forever
                    self._fault_park_all(state, e)
                self._enter("other")
        except Exception as e:  # noqa: BLE001
            for i, s in enumerate(state.slots):
                if s is not None:
                    s.req.fail(e)
                    # an aborted batch must hand its pool blocks back —
                    # the normal retire/cancel/preempt release paths
                    # never run for these slots, and leaked refs would
                    # shrink the pool permanently
                    self._release_blocks(state, i)
            raise
        finally:
            self._enter("other")
            self._mark("boundary")
            if state.closed:
                with self._stats_lock:
                    self.batches_closed += 1
            # batch teardown: its device holdings leave the HBM ledger
            # (an idle scheduler must not keep reporting the last
            # batch's cache/buffer bytes)
            graftmem.release(state.mem_cache)
            graftmem.release(state.mem_buf)
            self._note_state_rows(None)

    def _note_state_rows(self, more: Optional[int]) -> None:
        """``more`` records more (fewer) in the live batch's working
        cache, one a live row; ``None``: the batch has ended and holds
        none. With the slab's snapshots, what the device holds of row
        state (``stats()``'s ``state.in_use`` and ``state.peak``)."""
        if self._slab is None:
            return
        snapshots = self._slab.stats()["state.in_use"]
        with self._stats_lock:
            self._state_rows = (0 if more is None
                                else self._state_rows + more)
            self._state_peak = max(self._state_peak,
                                   self._state_rows + snapshots)

    def _hold_lead(self) -> None:
        """Wait until at most ONE decode call is in flight: the one the
        device runs. The next is then dispatched behind it, so the
        device is never left without work, and what a boundary decides
        (who retires, who is admitted, whether the batch ends and the
        next arrival seeds another) is decided a call ahead of the
        device and no further. Without it the bound
        is the runtime's own cap on programs in flight, which counts
        programs and not their time: calls cut short at a row's budget
        let the host run five calls (1.6 s) ahead at widths 1 and 2, a
        joiner's prefill queued behind all of them (PERF.md 6, PR 39).
        Fetches nothing: the wait is for the tokens to exist."""
        if len(self._in_flight) > 1:
            self._enter("hold")
            while len(self._in_flight) > 1:
                jax.block_until_ready(self._in_flight.popleft())
            self._enter("other")

    # -- seeding -------------------------------------------------------------

    @staticmethod
    def _ent_req(e) -> _Req:
        return e.req if isinstance(e, _Parked) else e

    def _ent_ids(self, e) -> np.ndarray:
        """The tokens a seed/admission prefill forwards for this entry:
        the prompt, or — resuming a parked row — prompt + all emitted
        tokens but the last (the last is the live, not-yet-forwarded
        token the segment loop carries). Generation by blocks: the
        WHOLE BLOCKS of everything known (the prompt and what a parked
        row emitted); the rest is given to the row's first round
        (``_ent_block``)."""
        if self._blocks:
            known = self._ent_known(e)
            return known[:len(known) - len(known) % self._unit]
        if isinstance(e, _Parked):
            return np.concatenate([e.req.prompt, e.tokens[:-1]])
        return e.prompt

    @staticmethod
    def _ent_known(e) -> np.ndarray:
        if isinstance(e, _Parked):
            return np.concatenate([e.req.prompt, e.tokens])
        return e.prompt

    def _ent_block(self, e) -> np.ndarray:
        """Generation by blocks: the entry's block as its first round
        finds it, ``[L]``: what is known past its whole blocks, then
        masked positions."""
        from ..ops.block_diffusion import MASKED
        known = self._ent_known(e)
        block = np.full((self._unit,), MASKED, np.int32)
        given = len(known) % self._unit
        block[:given] = known[len(known) - given:]
        return block

    def _ent_need(self, e) -> int:
        """Cache slots the entry still needs past its prefill."""
        left = self._ent_req(e).max_new_tokens - (
            e.emitted if isinstance(e, _Parked) else 0)
        if self._blocks:
            return _round_up(left + len(self._ent_known(e)) % self._unit,
                             self._unit)
        return left + 1 if isinstance(e, _Parked) else left

    def _seed(self, head) -> _BatchState:
        """Start a batch: gather same-policy parked rows first (they
        outrank every queued request), then up-to-``max_wait`` queued
        peers that fit. Any failure past the gathering point (e.g. a
        prefill OOM) is delivered to EVERY gathered request, not just
        the head — a gathered peer with ``done`` never set would block
        its caller forever (ADVICE r4 medium)."""
        seed = [head]
        sampling = self._ent_req(head).sampling
        while len(seed) < self.max_batch:
            nxt = self._peek_parked()
            if nxt is None:
                break
            if self._req_dead(nxt.req):
                self._pop_parked()
                continue
            if (nxt.req.sampling == sampling
                    and self._fits(seed + [nxt])):
                seed.append(self._pop_parked())
            else:
                break  # stays parked; reconsidered at admission/next seed
        deadline = time.monotonic() + self.max_wait_s
        while len(seed) < self.max_batch:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                nxt = self._queue.get(timeout=remaining)
            except queue.Empty:
                break
            if self._req_dead(nxt):
                continue
            if nxt.sampling == sampling and self._fits(seed + [nxt]):
                seed.append(nxt)
            else:
                # incompatible arrival: parked as the FIFO head — _admit
                # reconsiders it first (it may fit once the batch is
                # live) and otherwise it seeds the next batch
                self._set_pending(nxt)
                break
        try:
            return self._seed_batch(seed)
        except Exception as e:  # noqa: BLE001
            for r in seed:
                self._ent_req(r).fail(e)
            raise

    def _seed_batch(self, seed: List) -> _BatchState:
        eng = self.engine
        sampling = self._ent_req(seed[0]).sampling
        spec_mode = sampling.spec
        s_max = self._seed_smax(seed)
        rows = [self._ent_ids(e) for e in seed]

        # Right-size the compiled width (ADVICE r4: a lone request must
        # not pay max_batch x prefill/decode FLOPs for ghost rows): the
        # batch runs at the next power of two that fits the seed, and
        # _admit grows it on demand. Width set = {1, 2, 4, ..,
        # max_batch} — a bounded extra-program inventory.
        b = min(_next_pow2(len(seed)), self.max_batch)
        # timeline: the admission/resume DECISION happens here, at
        # gather time — before the seed prefill dispatch it causes
        for e in seed:
            r = self._ent_req(e)
            if isinstance(e, _Parked):
                grafttime.emit("resume", rid=_rid_of(r),
                               emitted=e.emitted, mode="seed", width=b)
            else:
                grafttime.emit("admission", rid=_rid_of(r), mode="seed",
                               width=b, prompt_len=len(r.prompt))
        ids = np.zeros((b, s_max), dtype=np.int32)
        pad = np.zeros((b,), dtype=np.int32)
        for i in range(b):
            row = rows[min(i, len(seed) - 1)]  # free slots replicate last
            ids[i, s_max - len(row):] = row
            pad[i] = s_max - len(row)
        ids_j = jnp.asarray(ids)
        pad_j = jnp.asarray(pad)

        t0 = time.monotonic()
        sp0 = time.perf_counter()
        run_params = eng._run_params()
        # the shared seed prefill serves every gathered request: its
        # instrumented dispatches carry the whole rid set (grafttime)
        with grafttime.correlate([_rid_of(self._ent_req(e))
                                  for e in seed]):
            last_logits, cache = eng._prefill(run_params, ids_j, pad_j)
        if self._blocks:
            # the prefill yields no token: a row's first comes from its
            # first round, which starts from what its prompt leaves over
            # (a lane without a request has nothing masked)
            dks = None
            first = jnp.asarray(np.stack(
                [self._ent_block(e) for e in seed]
                + [np.zeros((self._unit,), np.int32)] * (b - len(seed))))
        else:
            first, pks, dks = self._first_tokens(
                last_logits, sampling,
                [self._ent_req(e).key for e in seed], b)
        # Resumed rows: the "first" token is the parked row's last
        # emitted token — KNOWN, never re-selected (greedy would
        # reproduce it from the recomputed logits; a sampled row's draw
        # came from an earlier step key, so the override is what makes
        # the resumed stream byte-identical).
        for i, e in enumerate(seed):
            if isinstance(e, _Parked) and not self._blocks:
                first = first.at[i].set(int(e.tokens[-1]))
        sp1 = time.perf_counter()
        covered = []                  # (trace, its prefill span)
        for e in seed:
            r = self._ent_req(e)
            if r.trace is not None:
                if isinstance(e, _Parked):
                    r.trace.add_span("preempted", e.preempt_t, sp0,
                                     scheduler="iter")
                    pre = r.trace.add_span(
                        "prefill", sp0, sp1, kind="resume", width=b,
                        emitted=e.emitted)
                else:
                    r.trace.add_span("queue_wait", r.t_submit, sp0,
                                     scheduler="iter",
                                     **self._wait_labels(r.t_submit, sp0))
                    pre = r.trace.add_span(
                        "prefill", sp0, sp1, kind="seed", width=b,
                        prompt_len=len(r.prompt))
                covered.append((r.trace, pre))

        if not spec_mode:
            # the prefill's ghost lanes (``_empty_span``); a new array,
            # the prefill's own pads may still alias ``pad``
            pad_j = jnp.asarray(np.where(np.arange(b) < len(seed), pad,
                                         np.int32(self._no_span)))
        state = _BatchState(sampling, first, cache, pad_j, s_max)
        # the span's window is the dispatch; the shared first-token
        # array says when the prefill had run
        state.ready = tracing.READY.hand(
            last_logits if self._blocks else first, covered,
            counters=self._routing_counters(cache, True))
        if spec_mode:
            # verify-loop entry state (spec_decode._seg_b invariant): the
            # token buffer holds prompt + the unforwarded first token per
            # row, content at [pad_b, depth + 1); the per-row key chains
            # are the dks the solo loop would carry (split(key)[1]) —
            # except resumed rows, whose chains advanced with every
            # verify step and resume from the parked snapshot.
            buf = jnp.zeros((b, eng.max_seq + self.spec.draft_len + 1),
                            jnp.int32)
            buf = jax.lax.dynamic_update_slice(buf, ids_j, (0, 0))
            buf = jax.lax.dynamic_update_slice(buf, first[:, None],
                                               (0, s_max))
            state.spec_mode = True
            state.buf = buf
            state.mem_buf = graftmem.track(state, "buf", "spec_buffers",
                                           buf)
            keys = (dks if dks is not None
                    else jnp.zeros((b, 2), jnp.uint32))
            for i, e in enumerate(seed):
                if isinstance(e, _Parked) and e.spec_key is not None:
                    keys = keys.at[i].set(jnp.asarray(e.spec_key))
            state.keys = keys
        # one shared [B] fetch (a prefill by blocks yields no token)
        first_ref = None if self._blocks else _SegOut(first)
        state.slots = [None] * b
        n_res = 0
        for i, e in enumerate(seed):
            r = self._ent_req(e)
            if isinstance(e, _Parked):
                n_res += 1
                state.slots[i] = _Slot(
                    req=r, plen=e.plen, row=i, pad=int(pad[i]),
                    first_ref=None,
                    first_idx=0, dk=None if dks is None else dks[i],
                    emitted=e.emitted, resumed_prefix=e.tokens,
                    order=e.order, t0=e.t0,
                    fault_budget_used=e.fault_budget_used)
            else:
                self._order += 1
                state.slots[i] = _Slot(req=r, plen=len(r.prompt), row=i,
                                       pad=int(pad[i]),
                                       first_ref=first_ref, first_idx=i,
                                       dk=None if dks is None else dks[i],
                                       emitted=0 if self._blocks else 1,
                                       order=self._order, t0=t0)
            state.slots[i].given = self._given(e)
        if self.pool is not None:
            self._init_tables(state)
        self._note_state_rows(len(seed))
        with self._stats_lock:
            state.batch = self.batches_run
            self.batches_run += 1
            self.resumes += n_res
        REGISTRY.inc("iter_batches_total")
        if n_res:
            REGISTRY.inc("kv_pool_resumes_total", value=n_res)
        self.engine._note_compiles()
        self._retire_finished(state)      # max_new_tokens == 1 rows
        self._set_gauges(state)
        return state

    def _given(self, e) -> int:
        """Positions of the entry's next block that are known already
        (generation by blocks; 0 otherwise)."""
        return len(self._ent_known(e)) % self._unit if self._blocks else 0

    def _fits(self, ents: List) -> bool:
        s_max = self._seed_smax(ents)
        ok = all(s_max + self._ent_need(e) + self._reserve(ents[0])
                 <= self.engine.max_seq
                 and len(self._ent_ids(e)) <= s_max for e in ents)
        if ok and self.pool is not None:
            # CURRENT footprint only (blocks covering the seed depth):
            # admission deliberately OVERSUBSCRIBES future growth — that
            # is what preemption is for; a worst-case check here would
            # forbid exactly the concurrency the pool exists to raise
            alloc = self.pool.allocator
            need = sum(
                alloc.blocks_for(s_max)
                - (s_max - len(self._ent_ids(e))) // self.pool.block_size
                for e in ents)
            ok = need <= alloc.available()
        return ok

    def _reserve(self, ent) -> int:
        """Cache slots held back beyond the generation: speculative
        batches need ``draft_len`` of verify-write headroom past the
        deepest content slot (the spec engine's own guard, applied to
        the batch's shared shape)."""
        return (self.spec.draft_len
                if self._ent_req(ent).sampling.spec else 0)

    def _bucketed(self, length: int) -> int:
        """The width a lone prefill of ``length`` positions compiles at."""
        if self._family_bucket is not None:
            return self._family_bucket(self.engine.config, length)
        return _round_up(length, self.prompt_bucket)

    def _seed_smax(self, ents: List) -> int:
        raw = max(len(self._ent_ids(e)) for e in ents)
        need = max(self._ent_need(e) for e in ents)
        # (whole blocks, where the family generates by blocks)
        return min(self._bucketed(raw),
                   (self.engine.max_seq - need - self._reserve(ents[0]))
                   // self._unit * self._unit)

    def _first_tokens(self, last_logits, sampling, keys, b):
        """First-token selection + per-row (prefill, decode) key split.
        Free slots get zero keys (their draws are dropped)."""
        if sampling.mode == "greedy":
            first = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
            return first, None, None
        ks = [jnp.asarray(k) for k in keys]
        ks += [jnp.zeros_like(ks[0])] * (b - len(ks))
        stack = jnp.stack(ks)                       # [b, 2]
        pair = jax.vmap(jax.random.split)(stack)    # [b, 2, 2]
        pks, dks = pair[:, 0], pair[:, 1]
        first = select_token(last_logits, sampling, pks)
        return first, pks, dks

    # -- admission -----------------------------------------------------------

    def _reserve_blocks(self, state: _BatchState, ent):
        """ATOMIC pool admission for one would-be row's CURRENT
        footprint — blocks covering its content at the live depth
        (pad-prefix blocks are free, they point at trash). Growth past
        this is deliberately oversubscribed: preemption handles it.

        The watermark check and the grant run under ONE allocator lock
        hold (``BlockAllocator.admit_alloc``): the old two-step
        ``can_admit`` -> later ``alloc`` left a window where a
        concurrent pool user (the prefix store's insert, a solo paged
        runner sharing the pool) could take the checked blocks, turning
        a deferrable admission into a ``PoolExhausted`` request failure
        — or, raced the other way, an over-watermark grant (the
        graftsched check-then-act fixture pins both shapes). Returns
        ``(p_lo, granted ids)`` or None to defer (blocks free up as
        rows retire). A row's state takes no room of its own: its place
        is its lane (``_slot_possible``)."""
        if self.pool is None:
            return 0, []
        alloc = self.pool.allocator
        plen_eff = len(self._ent_ids(ent))
        p_lo = (state.depth - plen_eff) // self.pool.block_size
        p_hi = -(-state.depth // self.pool.block_size)
        ids = alloc.admit_alloc(p_hi - p_lo)
        return None if ids is None else (p_lo, ids)

    def _free_reserved(self, reserved) -> None:
        """Hand back what ``_reserve_blocks`` granted."""
        if self.pool is not None and reserved is not None:
            self.pool.allocator.free(reserved[1])

    def _admit(self, state: _BatchState):
        """Drain parked rows (oldest first — they outrank the queue),
        then compatible queued requests, into free slots. Strict FIFO:
        an incompatible head closes admission for this batch and seeds
        the next one — EXCEPT a head that is policy-compatible but
        lacks pool room, which stays waiting without closing (blocks
        free up as rows retire; closing would thrash batches under
        memory pressure). A request parked in ``_pending`` (by
        ``_seed`` or a previous round) is ALWAYS the queue's head — it
        is reconsidered first and never overwritten, so no request can
        be dropped. When the right-sized batch has no free slot but is
        narrower than ``max_batch``, the live batch GROWS to the next
        power of two (ghost rows replicate row 0; per-row exactness
        makes them inert) instead of turning the arrival away."""
        while True:
            ent = self._peek_parked()
            if ent is None:
                break
            if self._req_dead(ent.req):
                self._pop_parked()
                continue
            if not self._compatible(state, ent):
                # the parked head must not be overtaken by younger
                # queued requests: a policy mismatch closes admission
                # (it seeds the next batch); a depth/headroom mismatch
                # just waits for the next batch to seed from it
                if ent.req.sampling != state.sampling:
                    state.closed = True
                    self._turn_away("closed", "closes_policy")
                else:
                    self._mark("boundary")
                return
            if not self._slot_possible(state):
                self._turn_away("slot", "defers_slot")
                return  # full batch: retried at the next boundary
            reserved = self._reserve_blocks(state, ent)
            if reserved is None:
                self._turn_away("pool", "defers_pool")
                return  # blocks free up as rows retire; stays parked
            slot = self._free_slot(state)
            if slot is None:
                self._free_reserved(reserved)
                self._turn_away("slot", "defers_slot")
                return
            ent = self._pop_parked()
            try:
                self._admit_one(state, ent.req, slot, resume=ent,
                                reserved=reserved)
            except Exception as e:  # noqa: BLE001
                ent.req.fail(e)
                raise
        while True:
            req = self._get_pending()
            if req is None:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    self._mark("boundary")
                    return
                self._set_pending(req)
            if self._req_dead(req):
                self._set_pending(None)
                continue
            if not self._compatible(state, req):
                state.closed = True  # req stays parked as the FIFO head
                self._turn_away(
                    "closed", "closes_policy"
                    if req.sampling != state.sampling else "closes_depth")
                return
            if not self._slot_possible(state):
                self._turn_away("slot", "defers_slot")
                return  # full batch: req stays the head
            reserved = self._reserve_blocks(state, req)
            if reserved is None:
                self._turn_away("pool", "defers_pool")
                return  # req stays the head; retried as rows retire
            slot = self._free_slot(state)
            if slot is None:
                self._free_reserved(reserved)
                self._turn_away("slot", "defers_slot")
                return
            self._set_pending(None)
            try:
                self._admit_one(state, req, slot, reserved=reserved)
            except Exception as e:  # noqa: BLE001 — the popped request is
                req.fail(e)        # not in state.slots yet; without this
                raise              # its caller would block forever

    def _slot_possible(self, state: _BatchState) -> bool:
        """Could an admission find (or grow into) a slot right now?
        Checked BEFORE reserving pool blocks: ``admit_alloc`` may evict
        zero-ref prefix entries to satisfy a grant, and reserving for a
        full, ungrowable batch would thrash the prefix cache for a
        grant that is immediately handed back."""
        return (any(s is None for s in state.slots)
                or len(state.slots) < self.max_batch)

    def _free_slot(self, state: _BatchState) -> Optional[int]:
        free = [i for i, s in enumerate(state.slots) if s is None]
        if not free:
            if len(state.slots) >= self.max_batch:
                return None  # full: retried at the next boundary
            self._grow(state)
            free = [i for i, s in enumerate(state.slots) if s is None]
        return free[0]


    def _grow(self, state: _BatchState):
        """Widen the live batch to the next power of two: pad token /
        cache along the batch axis by replicating row 0 (any live
        content is valid ghost material — rows are independent), and
        pad_j with the empty span of a lane without a request.
        One tiny concat program per (width, cache-shape) pair, from the
        same bounded width set as the decode programs.

        A pooled batch's resident cache is not widened but let go and
        GATHERED again at the new width: the pool holds every live row
        as the cache does (a grow happens at a boundary), and the two
        widths then never stand on the device side by side (widening
        16 rows of ``chat`` by concatenation held 4.3 GB where the
        cache is 2.15: PERF.md 6, PR 41). The one gather a live batch
        still makes: four in a batch's life at most. What rows hold
        beside their positions is in no pool and is widened in either
        kind of batch, by empty lanes (``_widen_state``): for those
        leaves alone a pooled batch's two widths stand side by side
        while the grow runs."""
        old = len(state.slots)
        new = min(_next_pow2(old + 1), self.max_batch)
        pad_rows = new - old

        def rep(x, axis):
            return jnp.concatenate(
                [x, jnp.repeat(jax.lax.slice_in_dim(x, 0, 1, axis=axis),
                               pad_rows, axis=axis)], axis=axis)

        def grow_cache(c):
            def one(kc: KVCache) -> KVCache:
                v = kc.v if getattr(kc.v, "ndim", 0) <= 1 else rep(kc.v, 1)
                return KVCache(k=rep(kc.k, 1), v=v, length=kc.length,
                               state=_widen_state(kc.state, pad_rows))
            if isinstance(c, list):
                return [one(x) for x in c]
            return one(c)

        state.token = rep(state.token, 0)
        # a ghost lane's span is empty (``_empty_span``); a speculative
        # batch's keeps row 0's pad
        state.pad_j = (rep(state.pad_j, 0) if state.spec_mode
                       else jnp.concatenate(
                           [state.pad_j, jnp.full((pad_rows,), self._no_span,
                                                  jnp.int32)]))
        resident = state.cache is not None
        if resident and state.tables is None:
            state.cache = grow_cache(state.cache)
        elif resident:
            # (one KVCache: a staged engine's list of them has no pool)
            row_state = state.cache.state
            state.cache = None
        if state.tables is not None:
            # a ghost lane reads the trash block here and its
            # write-back lands there, like a retired row's stale lane
            state.tables = np.concatenate(
                [state.tables,
                 np.full((pad_rows, self.pool.nbm), self.pool.trash,
                         dtype=np.int32)], axis=0)
            if resident:
                state.cache = self.pool.gather(
                    state.tables, state.depth)._replace(
                        state=_widen_state(row_state, pad_rows))
                with self._stats_lock:
                    self.cache_gathers += 1
                REGISTRY.inc("iter_cache_gathers_total")
        if resident:
            graftmem.update(state.mem_cache, state.cache)
        if state.spec_mode:
            # ghost rows clone row 0's buffer/key lane; their zero
            # budgets keep them inert through every verify (n_emit = 0)
            state.buf = rep(state.buf, 0)
            graftmem.update(state.mem_buf, state.buf)
            state.keys = rep(state.keys, 0)
        state.slots = state.slots + [None] * pad_rows
        with self._stats_lock:
            self.grows += 1
        REGISTRY.inc("iter_grows_total")

    def _admit_one(self, state: _BatchState, req: _Req, slot: int,
                   resume: Optional[_Parked] = None,
                   reserved: Optional[Tuple[int, List[int]]] = None):
        """``reserved`` (pool mode) is the row's atomically pre-granted
        block reservation from ``_reserve_blocks`` — this function owns
        it: consumed by ``_place_admitted`` on success, freed on ANY
        failure in between (a prefill OOM must not leak the grant)."""
        try:
            return self._admit_one_inner(state, req, slot, resume,
                                         reserved)
        except BaseException:
            if self.pool is not None and reserved is not None:
                self._free_reserved(reserved)
                if state.tables is not None:
                    state.tables[slot, :] = self.pool.trash
            raise

    def _admit_one_inner(self, state: _BatchState, req: _Req, slot: int,
                         resume: Optional[_Parked],
                         reserved: Optional[Tuple[int, List[int]]]):
        eng = self.engine
        ent = resume if resume is not None else req
        stream = self._ent_ids(ent)
        plen_eff = len(stream)            # tokens the prefill forwards
        # timeline: the join/resume DECISION happens here — before the
        # admit prefill dispatch it causes
        if resume is not None:
            grafttime.emit("resume", rid=_rid_of(req),
                           emitted=resume.emitted, mode="join",
                           depth=state.depth)
        else:
            grafttime.emit("admission", rid=_rid_of(req), mode="join",
                           depth=state.depth, prompt_len=plen_eff)
        plen = resume.plen if resume is not None else plen_eff
        t0 = resume.t0 if resume is not None else time.monotonic()
        p0 = time.perf_counter()
        # whoever waits behind this one waits for its admission from
        # here on, not for what held it
        self._mark("boundary")
        live = sum(s is not None for s in state.slots)
        if req.trace is not None:
            if resume is not None:
                req.trace.add_span("preempted", resume.preempt_t, p0,
                                   scheduler="iter")
            else:
                req.trace.add_span("queue_wait", req.t_submit, p0,
                                   scheduler="iter",
                                   **self._wait_labels(req.t_submit, p0))
        pre = None
        if self.prefix is not None and resume is None:
            # admission prefill through the prefix store: a joiner whose
            # prompt shares a cached prefix forwards only its suffix (and
            # warms the store for the next one). The store's cache is
            # right-aligned — content at [0, plen), no pad — so the merge
            # roll below uses sp = plen. Byte-exact: store replay equals
            # a cold prefill (pinned by tests/test_prefix_cache.py).
            # prefill_state records this row's prefill span (with prefix
            # hit/miss annotations) into the ambient trace.
            with tracing.use_trace(req.trace):
                logits, solo, sp = self.prefix.prefill_state(stream)
            if req.trace is not None:
                pre = req.trace.find_all("prefill")[-1]
                pre.labels["live"] = live
        else:
            sp = min(self._bucketed(plen_eff), state.depth)
            if sp < plen_eff:  # bucket would overshoot current depth:
                sp = plen_eff  # exact length (rare; one extra program)
            ids = np.zeros((1, sp), dtype=np.int32)
            ids[0, sp - plen_eff:] = stream
            with grafttime.correlate([_rid_of(req)]):
                logits, solo = eng._prefill(
                    eng._run_params(), jnp.asarray(ids),
                    jnp.asarray([sp - plen_eff], jnp.int32))
            if req.trace is not None:
                pre = req.trace.add_span(
                    "prefill", p0, time.perf_counter(),
                    kind="resume" if resume is not None else "admit",
                    depth=state.depth, prompt_len=plen_eff, live=live)
        sampling = state.sampling
        if self._blocks:
            first, dk = jnp.asarray(self._ent_block(ent)), None
        elif sampling.mode == "greedy":
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
            dk = None
        else:
            pk, dk = jax.random.split(jnp.asarray(req.key))
            first = select_token(logits, sampling, pk[None, :])[0]
        if resume is not None and not self._blocks:
            # the live token is the parked row's last emitted one —
            # known, never re-selected (see _seed_batch)
            first = jnp.asarray(int(resume.tokens[-1]), jnp.int32)
        if pre is not None:
            tracing.READY.hand(logits if self._blocks else first,
                               [(req.trace, pre)],
                               counters=self._routing_counters(solo, True))
        if self.pool is not None:
            blk_lo, blk_ids = self._place_admitted(
                state, slot, solo, state.depth - sp, reserved)
        if state.cache is not None:
            state.cache = _admit_cache(
                state.cache, solo, jnp.asarray(slot, jnp.int32),
                jnp.asarray(state.depth - sp, jnp.int32))
            graftmem.update(state.mem_cache, state.cache)
        state.pad_j = state.pad_j.at[slot].set(state.depth - plen_eff)
        state.token = state.token.at[slot].set(first)
        if state.spec_mode:
            # splice the joiner's stream into its buffer lane: forwarded
            # tokens at [depth - plen_eff, depth), live token at depth —
            # the verify invariant every live row already satisfies.
            # Host-built row + traced-offset writes: no program minted
            # per depth.
            rowbuf = np.zeros((state.buf.shape[1],), np.int32)
            rowbuf[state.depth - plen_eff:state.depth] = stream
            row_j = jax.lax.dynamic_update_slice(
                jnp.asarray(rowbuf), first[None],
                (jnp.asarray(state.depth, jnp.int32),))
            state.buf = state.buf.at[slot].set(row_j)
            if sampling.mode != "greedy":
                # the row's verify key chain starts at its own
                # split(key)[1] (a fresh joiner) or resumes the parked
                # snapshot (the chain advanced with every verify step)
                chain = (jnp.asarray(resume.spec_key)
                         if resume is not None and resume.spec_key
                         is not None else dk)
                state.keys = state.keys.at[slot].set(chain)
        self._order += 1
        state.slots[slot] = _Slot(
            req=req, plen=plen, row=slot, pad=state.depth - plen_eff,
            first_ref=(None if resume is not None or self._blocks
                       else _SegOut(first[None])),
            first_idx=0, dk=dk, t0=t0, given=self._given(ent),
            emitted=(resume.emitted if resume is not None
                     else 0 if self._blocks else 1),
            resumed_prefix=resume.tokens if resume is not None else None,
            order=resume.order if resume is not None else self._order,
            fault_budget_used=(resume.fault_budget_used
                               if resume is not None else 0))
        if self.pool is not None:
            state.slots[slot].blk_lo = blk_lo
            state.slots[slot].blk_ids = blk_ids
        with self._stats_lock:
            if resume is not None:
                self.resumes += 1
            else:
                self.joins += 1
            # the merge above wrote the joiner's record into its lane
            self._state_joined += self._slab is not None
        self._note_state_rows(1)
        if resume is not None:
            REGISTRY.inc("kv_pool_resumes_total")
        else:
            REGISTRY.inc("iter_joins_total")
        if req.max_new_tokens <= state.slots[slot].emitted:
            self._retire_finished(state)

    # -- paged storage (pool mode) -------------------------------------------

    def _init_tables(self, state: _BatchState) -> None:
        """Seed-time placement: allocate each live row's content blocks
        (pad-prefix positions stay on trash) and scatter the seed
        prefill into them, whole. The contiguous cache STAYS with the
        batch as its resident working cache, with what its rows hold
        beside their positions (which is in no pool); from here on the
        pool is written behind every call and read only where the batch
        grows (``_grow``). A quantized pool and a speculative batch give
        the cache up here and gather it anew for every call: no family
        with a row state is served by either (``serving.app``)."""
        bs = self.pool.block_size
        state.tables = np.full((len(state.slots), self.pool.nbm),
                               self.pool.trash, dtype=np.int32)
        p_hi = -(-state.depth // bs)
        pad_np = np.asarray(state.pad_j)
        try:
            for i, s in enumerate(state.slots):
                if s is None:
                    continue
                p_lo = int(pad_np[i]) // bs
                s.blk_lo = p_lo
                s.blk_ids = self.pool.allocator.alloc(p_hi - p_lo)
                state.tables[i, p_lo:p_hi] = s.blk_ids
            self.pool.scatter(state.cache, state.tables)
        except BaseException:
            # all-or-nothing: rows placed before the failure must not
            # leak their refs (the seed delivers the error to every
            # request; nothing will ever retire these slots)
            for i in range(len(state.slots)):
                self._release_blocks(state, i)
            raise
        if self.pool.block_dtype is not None or state.spec_mode:
            assert self._slab is None, "a row state has no pool to go to"
            state.cache = None
            # the pool alone holds the KV bytes (its own ledger entry)
            graftmem.release(state.mem_cache)
            state.mem_cache = 0

    def _place_admitted(self, state: _BatchState, slot: int,
                        solo, roll: int,
                        reserved: Tuple[int, List[int]]):
        """Admission-time placement of one solo-prefilled row into its
        PRE-RESERVED content blocks (the atomic ``_reserve_blocks``
        grant — allocation no longer happens here, so the watermark
        check and the grant cannot be split by a concurrent pool user)
        and scatter of the rolled row (the paged form of
        ``_admit_cache``'s roll merge, which the caller makes as well
        where the batch has a resident cache: the two then hold the
        same row; the row's state goes into its lane by that merge
        alone). ``_admit_one`` owns freeing the reservation on failure;
        this only resets the table row."""
        p_lo, ids = reserved
        try:
            state.tables[slot, :] = self.pool.trash
            state.tables[slot, p_lo:p_lo + len(ids)] = ids
            self.pool.scatter_row(solo, state.tables[slot], roll)
        except BaseException:
            state.tables[slot, :] = self.pool.trash
            raise
        return p_lo, ids

    def _vacate(self, state: _BatchState, i: int) -> None:
        """Lane ``i`` holds no request from here: its blocks go back and
        its span is EMPTY (``_empty_span``)."""
        self._release_blocks(state, i)
        state.slots[i] = None
        self._note_state_rows(-1)
        self._empty_span(state, i)

    def _empty_span(self, state: _BatchState, i: int) -> None:
        """A lane without a request (retired, parked, a ghost of the
        seed's width) gets a pad that no depth reaches: the decode kernel
        streams a row's span ``[pad, depth)`` and can tell such a lane
        from a live one by nothing else, so with its row's stale pad it
        would go on reading the row's blocks. The state kernels tell it
        by the same pad (``ops.gated_delta.live_lanes``): they copy its
        state neither in nor out and give zeros for it. Everything else
        of the lane still computes (rows are independent; its output is
        its own token's value, its positions clip at 0). A speculative
        batch keeps its lanes' pads: its segment rolls and rewrites
        every lane's pad, and runs the XLA attention, which reads the
        window whatever the pads say."""
        if not state.spec_mode:
            state.pad_j = state.pad_j.at[i].set(self._no_span)

    def _release_blocks(self, state: _BatchState, i: int) -> None:
        s = state.slots[i]
        if self.pool is None or s is None or not s.blk_ids:
            return
        self.pool.allocator.free(s.blk_ids)
        s.blk_ids = []
        if state.tables is not None:
            state.tables[i, :] = self.pool.trash

    def _ensure_blocks(self, state: _BatchState, new_depth: int) -> None:
        """Pre-segment growth: every live row must own blocks covering
        depth ``new_depth - 1``'s writes. Walked oldest-first so that
        when allocation fails — even after the allocator LRU-evicted
        every zero-ref prefix entry — the rows preempted to make room
        are the youngest (lowest priority)."""
        from .kv_pool import PoolExhausted
        p_hi = -(-new_depth // self.pool.block_size)
        for s in sorted((s for s in state.slots if s is not None),
                        key=lambda s: s.order):
            if state.slots[s.row] is not s:
                continue  # preempted by an earlier iteration
            while True:
                missing = p_hi - (s.blk_lo + len(s.blk_ids))
                if missing <= 0:
                    break
                try:
                    ids = self.pool.allocator.alloc(missing)
                except PoolExhausted:
                    if not self._preempt_lowest(state):
                        raise  # nothing left to preempt: cannot happen
                        # while the pool holds >= blocks_per_row blocks
                    if state.slots[s.row] is not s:
                        break  # this row WAS the youngest: it parked
                    continue
                col = s.blk_lo + len(s.blk_ids)
                state.tables[s.row, col:p_hi] = ids
                s.blk_ids.extend(ids)

    def _extend_blocks_down(self, state: _BatchState,
                            pad_np: np.ndarray) -> None:
        """Spec-mode low growth: a re-sync roll that shrank a row's pad
        moved real content into columns below ``blk_lo`` — own them
        before the full-row scatter (preempting younger rows if the
        allocator cannot stretch)."""
        from .kv_pool import PoolExhausted
        bs = self.pool.block_size
        for s in sorted((s for s in state.slots if s is not None),
                        key=lambda s: s.order):
            if state.slots[s.row] is not s:
                continue
            new_lo = int(pad_np[s.row]) // bs
            while new_lo < s.blk_lo:
                try:
                    ids = self.pool.allocator.alloc(s.blk_lo - new_lo)
                except PoolExhausted:
                    if not self._preempt_lowest(state):
                        raise
                    if state.slots[s.row] is not s:
                        break
                    continue
                state.tables[s.row, new_lo:s.blk_lo] = ids
                s.blk_ids = ids + s.blk_ids
                s.blk_lo = new_lo

    def _park_slot(self, state: _BatchState, s: _Slot,
                   fault_budget_used: int = 0,
                   reason: str = "preempt") -> None:
        """Park one live row for recompute-resume: fetch its emitted
        tokens (host sync — parking is the slow path by design), free
        its blocks, queue it oldest-first. Shared by pool-pressure
        preemption (``reason="preempt"``) and transient-fault recovery
        (``reason="fault"``) — both replay the row byte-identically
        through the same resume machinery."""
        tokens = np.asarray(self._row_tokens(s), dtype=np.int32)
        spec_key = None
        if state.spec_mode and state.sampling.mode != "greedy":
            spec_key = np.asarray(state.keys[s.row])
        parked = _Parked(req=s.req, plen=s.plen,
                         emitted=min(s.emitted, s.req.max_new_tokens),
                         tokens=tokens, order=s.order, t0=s.t0,
                         preempt_t=time.perf_counter(),
                         spec_key=spec_key,
                         fault_budget_used=fault_budget_used)
        self._vacate(state, s.row)
        self._park(parked)
        grafttime.emit("park", rid=_rid_of(s.req), reason=reason,
                       emitted=parked.emitted)

    def _preempt_lowest(self, state: _BatchState) -> bool:
        """Park the lowest-priority live row (latest admission order).
        The victim set is EVERY live row, including the one whose
        growth triggered the call — priority alone decides (the growth
        loops detect their own row parking and stop)."""
        live = [s for s in state.slots if s is not None]
        if not live:
            return False
        victim = max(live, key=lambda s: s.order)
        grafttime.emit("preempt", rid=_rid_of(victim.req),
                       order=victim.order)
        self._park_slot(state, victim,
                        fault_budget_used=victim.fault_budget_used)
        if victim.req.trace is not None:
            victim.req.trace.labels["preempted"] = (
                victim.req.trace.labels.get("preempted", 0) + 1)
        with self._stats_lock:
            self.preemptions += 1
        REGISTRY.inc("kv_pool_preemptions_total")
        return True

    def _fault_park_all(self, state: _BatchState,
                        fault: Exception) -> None:
        """Transient-fault recovery (graftfault): park EVERY live row —
        the failed segment never appended its output, so each row's
        park snapshot is exactly its pre-segment state and the
        recompute-resume replay is byte-identical. A row past its
        FAULT_PARK_BUDGET fails typed (503 Retry-After upstream)
        instead of cycling park/resume forever."""
        for i, s in enumerate(state.slots):
            if s is None:
                continue
            if s.fault_budget_used + 1 > FAULT_PARK_BUDGET:
                if s.req.trace is not None:
                    t = time.perf_counter()
                    s.req.trace.add_span("fault_budget_exhausted", t, t,
                                         scheduler="iter",
                                         parks=s.fault_budget_used)
                # the row's park-budget breaker OPENS: no more recovery
                # attempts — the degraded-mode decision, on the timeline
                grafttime.emit("breaker", state="open",
                               rid=_rid_of(s.req),
                               scope="iterbatch.fault_park_budget",
                               used=s.fault_budget_used,
                               budget=FAULT_PARK_BUDGET)
                s.req.fail(graftfault.FaultBudgetError(
                    f"row exhausted its transient-fault park budget "
                    f"({FAULT_PARK_BUDGET}); last fault: {fault}"))
                self._vacate(state, i)
                continue
            if s.req.trace is not None:
                s.req.trace.labels["fault_parks"] = (
                    s.req.trace.labels.get("fault_parks", 0) + 1)
            # budget still absorbs this fault: the breaker stays CLOSED
            # with its remaining headroom recorded
            grafttime.emit("breaker", state="closed",
                           rid=_rid_of(s.req),
                           scope="iterbatch.fault_park_budget",
                           used=s.fault_budget_used + 1,
                           budget=FAULT_PARK_BUDGET)
            self._park_slot(state, s, reason="fault",
                            fault_budget_used=s.fault_budget_used + 1)
        with self._stats_lock:
            self.fault_parks += 1
        REGISTRY.inc("iter_fault_parks_total")

    # -- the segment step ----------------------------------------------------

    def _routing_counters(self, cache, prefill: bool):
        """The ``counters`` of a ready handover (``tracing.READY.hand``)
        for a cache that carries routing counters, or ``None``: a copy
        of its counter leaf (the cache itself is donated onward) and the
        function that, once the numbers exist, adds them to ``stats()``
        and has the family name them as span labels."""
        names = self.engine.cache_counters
        if not names:
            return None
        family, config = self.engine.family, self.engine.config

        def label(values):
            got = dict(zip(names, values))
            with self._stats_lock:
                for k, v in got.items():
                    if k.startswith("block_"):
                        self._rounds[f"block.{k[6:]}"] += v
                    else:
                        self._moe[f"moe.prefill_{k}" if prefill
                                  else f"moe.{k}"] += v
            return family.span_labels(got, config, prefill)
        return jnp.copy(cache.v), label

    def _set_gauges(self, state: _BatchState) -> None:
        """Live-state gauges, refreshed at every scheduling decision
        point (seed, segment boundary): what the batch looks like NOW."""
        live = sum(1 for s in state.slots if s is not None)
        width = len(state.slots)
        occupancy = round(live / max(width, 1), 4)
        depth = self._queue.qsize()
        REGISTRY.gauge("iter_live_rows", live)
        REGISTRY.gauge("batch_occupancy", occupancy, scheduler="iter")
        if self.pool is not None:
            # exact allocator numbers (live rows + prefix entries)
            self.pool.note_gauges(component="iter")
        else:
            kv_block_gauges("iter", state.depth * live,
                            width * self.engine._cache_seq)
        REGISTRY.gauge("queue_depth", depth, scheduler="iter")
        if self._window_positions is not None:
            # a live row's cache holds its prompt and all it emitted but
            # the token in flight
            held, seen = self._window_positions(
                state.cache.state,
                [s.plen + s.emitted - 1 for s in state.slots
                 if s is not None])
            with self._stats_lock:
                self._window = {"window.positions_held": held,
                                "window.positions_seen": seen}
        # graftscope occupancy time series: the trajectory behind the
        # instantaneous gauges above, served at /debug/profile
        graftscope.sample("iter_live_rows", live)
        graftscope.sample("batch_occupancy", occupancy, scheduler="iter")
        graftscope.sample("queue_depth", depth, scheduler="iter")

    def _advance(self, state: _BatchState):
        # Seeded mid-decode engine faults (graftfault), fired BEFORE any
        # state mutation so a transient park snapshots exactly the
        # pre-segment state: transient -> park/resume (byte-identical),
        # permanent -> the batch fails typed with partial traces
        # flight-recorded, slow -> a deterministic stall (what drives
        # the deadline-exceeded fixtures).
        kind = graftfault.inject("iterbatch.decode_seg",
                                 "decode_transient", "decode_permanent",
                                 "decode_slow")
        if kind == "decode_slow":
            time.sleep(0.05)
        elif kind == "decode_transient":
            raise graftfault.TransientFault(
                "iterbatch.decode_seg", kind,
                "graftfault: injected transient decode fault")
        elif kind == "decode_permanent":
            raise graftfault.PermanentFault(
                "iterbatch.decode_seg", kind,
                "graftfault: injected permanent engine fault")
        if state.spec_mode:
            return self._advance_spec(state)
        eng = self.engine
        d = state.depth
        # the call ends where the first live row's budget ends (known
        # on the host a call ahead of the device, with no fetch): that
        # row is answered at its last token and its slot and blocks
        # come back at this call's end
        # (a family that generates by blocks: ``n`` ROUNDS, each a
        # whole block of ``unit`` positions a row, which the host knows
        # a call ahead just the same; only the forwards are data)
        unit = self._unit
        longest = min(self.seg_steps, eng.max_seq - d) // unit
        n = min(longest, *(-(-(s.req.max_new_tokens - s.emitted + s.given)
                             // unit)
                           for s in state.slots if s is not None))
        assert n >= 1, "active rows past max_seq or budget (admission bug)"
        span = n * unit                      # positions the call writes
        window = eng._decode_window(d + span)   # shared bucket policy
        pooled = self.pool is not None
        if pooled:
            # grow every live row's block range to cover this segment's
            # writes — THE preemption point (youngest row parks when
            # even LRU eviction cannot free enough blocks)
            self._ensure_blocks(state, d + span)
            if not state.active():
                return  # everyone preempted (single-row pool squeeze)
        # the batch's own working cache, its rows' state in it; a batch
        # that gave it up to the pool at its seed gathers the whole of
        # it (``_init_tables``)
        resident = state.cache is not None
        cache = state.cache if resident else self.pool.gather(state.tables, d)
        # keys for ``seg_steps`` steps whatever ``n`` (a row's keys are
        # prefix-stable) and ``n`` an operand: one program a width
        step_keys = self._segment_keys(state, self.seg_steps)
        t0 = time.perf_counter()
        out, cache, state.token = eng._decode_seg(
            eng._run_params(), state.token, cache, state.pad_j,
            step_keys, np.int32(n), sampling=state.sampling, window=window)
        routing = self._routing_counters(cache, False)
        if pooled:
            bs = self.pool.block_size
            if resident:
                # only the columns that hold positions [d, d + span)
                self.pool.scatter_span(cache, state.tables, d // bs,
                                       self._span)
                wrote = (d + span - 1) // bs - d // bs + 1
            else:
                self.pool.scatter(cache, state.tables)
                wrote = self.pool.nbm
            self.pool.note_compiles()
            if self._slab is not None:
                self._slab.note_compiles()   # the store's two movers
        if resident:
            state.cache = cache
        # the decode kernel's stream: a step is a forward; how many a
        # call of rounds ran is the device's to say (below)
        count_stream = self._count_stream(state, d, n)
        if not self._blocks:
            count_stream(n)
        state.depth = d + span
        self._in_flight.append(out)
        seg = _SegOut(out)
        t1 = time.perf_counter()
        eng._note_compiles()
        cut = n < longest
        live = sum(s is not None for s in state.slots)
        with self._stats_lock:
            seg_no = self.segments_run
            self.segments_run += 1
            self.segments_cut += cut
            if pooled:
                self.calls_resident += resident
                self.cache_gathers += not resident
                self.blocks_written_back += live * wrote
                self.state_calls_resident += self._slab is not None
        REGISTRY.inc("iter_segments_total")
        if cut:
            REGISTRY.inc("iter_segments_cut_total")
        if pooled:
            REGISTRY.inc("iter_calls_resident_total" if resident
                         else "iter_cache_gathers_total")
            REGISTRY.inc("kv_pool_blocks_written_back_total",
                         value=live * wrote)
        covered, took = [], []
        for s in state.slots:
            if s is not None:
                # the row's own yield: a first round's block holds
                # ``given`` positions of the prompt, a last one may reach
                # past the budget
                lo, s.given = s.given, 0
                take = min(span - lo, s.req.max_new_tokens - s.emitted)
                s.segs.append((seg, lo, take))
                s.emitted += take
                if s.req.trace is not None:
                    # the window is the DISPATCH (segments queue
                    # asynchronously on the device — the serving-thread
                    # view); the waiter stamps when the segment had run.
                    # A call of rounds says its rounds and the row's
                    # tokens now; ``steps`` (the FORWARDS it ran) and
                    # ``fixed_at`` are the device's to say (below)
                    covered.append((s.req.trace, s.req.trace.add_span(
                        "decode", t0, t1, seg=seg_no, batch=state.batch,
                        **({"rounds": n, "tokens": take} if self._blocks
                           else {"steps": n}),
                        width=len(state.slots), depth=state.depth,
                        **({"blocks": len(s.blk_ids)} if pooled else {}))))
                    took.append((s.row, lo, take))
        if self._blocks:
            counted = routing[1]
            ran = eng.cache_counters.index("block_forwards")

            def forwards_and_fixes(values):
                # every row's span: the forwards the call ran (a pass
                # over the weights each: what ``steps`` means to every
                # reader) and, for each token the row got from it, the
                # forward inside its round that fixed it
                count_stream(values[ran])
                said = dict(counted(values), steps=values[ran])
                return [dict(said, fixed_at=seg.row(row, 1)[lo:lo + take]
                             .tolist()) for row, lo, take in took]
            routing = (routing[0], forwards_and_fixes)
        prev = state.ready

        def observe(at):
            # per-decode-step time, DEVICE view: consecutive ready
            # instants of one batch over the later segment's steps —
            # the step itself plus whatever ran between the segments
            # (joiners' prefills, pool movers, host gaps)
            if prev.at is not None:
                REGISTRY.observe("decode_step_seconds", (at - prev.at) / n,
                                 component="iter")
        state.ready = tracing.READY.hand(out, covered, then=observe,
                                         counters=routing)
        self._retire_finished(state)
        self._set_gauges(state)

    def _count_stream(self, state: _BatchState, d: int, n: int):
        """What the decode kernel's stream reads in a call of ``n`` steps
        from depth ``d``, reckoned on the host with the kernel's own
        arithmetic (``ops.decode_attention.streamed_blocks``) in blocks
        of ``BLOCK_S`` positions (a batch wide enough to stream finer
        blocks reads a little under it): every live row's span, and the
        rectangle width x depth that a stream of whole batches read. The
        quotient is the share of the rectangle that spans still read:
        near 1 for a lone row without pad, the lower the more lanes are
        empty or pad. Beside it, for a batch whose rows hold a state in
        the slab: the lanes the state kernels stream a step (the live
        rows', ``ops.gated_delta.live_lanes``) and the lanes of the
        compiled width.

        Returns the function that counts it, given the FORWARDS the call
        ran: ``n`` for a call of steps (a step is a forward, each a
        position deeper). A call of ROUNDS runs its forwards
        (``ops.block_decode``'s kernel a layer of each) at its rounds'
        depths ``d, d + unit, ...`` and says how many when it is ready;
        they are counted spread evenly over the rounds (rounds of one
        call differ by a block at most, where one starts on a block's
        edge). The rows' pads are taken NOW: a lane may be vacated
        before the call is ready."""
        offs = d + self._unit * np.arange(n)
        streamed = int(streamed_blocks(
            [s.pad for s in state.slots if s is not None], offs,
            BLOCK_S).sum())
        rect = len(state.slots) * int(
            streamed_blocks([0], offs, BLOCK_S).sum())
        live = sum(s is not None for s in state.slots)
        width = len(state.slots) if self._slab is not None else 0

        def count(forwards: int) -> None:
            mine = BLOCK_S * (streamed * forwards // n)
            whole = BLOCK_S * (rect * forwards // n)
            lanes, compiled = forwards * live, forwards * width
            with self._stats_lock:
                self.attn_positions_streamed += mine
                self.attn_positions_rect += whole
                if compiled:
                    self.state_lanes_streamed += lanes
                    self.state_lanes_compiled += compiled
                share = self.attn_positions_streamed / max(
                    self.attn_positions_rect, 1)
            REGISTRY.inc("iter_attn_positions_streamed_total", value=mine)
            REGISTRY.inc("iter_attn_positions_rect_total", value=whole)
            REGISTRY.gauge("iter_attn_stream_share", round(share, 4))
            if compiled:
                REGISTRY.inc("iter_state_lanes_streamed_total", value=lanes)
                REGISTRY.inc("iter_state_lanes_compiled_total",
                             value=compiled)
        return count

    def _advance_spec(self, state: _BatchState):
        """One draft-verify SEGMENT (spec batches): up to
        ``seg_steps // (draft_len + 1)`` verify forwards — the same
        device-work quantum as ``seg_steps`` single-token steps — with
        per-row acceptance, rewind, and uniform-depth re-sync all inside
        ONE compiled program (spec_decode._seg_b). Each row's emission
        is capped at its own remaining budget, so a short row never
        over-decodes and ghost rows (budget 0) stay inert.

        Costs ONE host sync per segment: the scheduler must read the
        per-row emission counts, the new per-row pads, and the new
        uniform depth to retire/admit (the price of data-dependent
        progress — same class as EOS-armed batches); the token buffer's
        device->host copy rides the same window and MUST materialize
        here, before the next segment donates the buffer."""
        eng = self.engine
        K = self.spec.draft_len
        max_verify = max(1, self.seg_steps // (K + 1))
        pooled = self.pool is not None
        if pooled:
            # verify headroom: writes reach depth + K within a verify,
            # and the segment can emit up to max_verify * (K + 1) new
            # tokens — cover the worst case before dispatch (preempting
            # youngest rows if the allocator cannot stretch)
            worst = min(state.depth + max_verify * (K + 1) + K,
                        eng.max_seq)
            self._ensure_blocks(state, worst)
            if not state.active():
                return
            in_cache = self.pool.gather(state.tables, state.depth)
        else:
            in_cache = state.cache
        # budgets AFTER any preemption above: a row parked at this
        # boundary must enter the segment as an inert ghost (budget 0),
        # not keep drafting into the trash block
        b = len(state.slots)
        budgets = np.zeros((b,), np.int32)
        for i, s in enumerate(state.slots):
            if s is not None:
                budgets[i] = max(s.req.max_new_tokens - s.emitted, 0)
        t0 = time.perf_counter()
        # the spec flag is routing metadata: normalize it out of the
        # static sampling arg so the segment program is shared with (and
        # byte-identical to) the solo spec engine's acceptance math
        sampling = dataclasses.replace(state.sampling, spec=False)
        buf, total, cache, pad, emitted, steps, keys = self.spec._seg_b(
            eng._run_params(), state.buf, in_cache,
            jnp.asarray(state.depth + 1, jnp.int32), state.pad_j,
            state.keys, jnp.asarray(budgets),
            max_verify=max_verify, sampling=sampling)
        state.buf = buf
        state.pad_j, state.keys = pad, keys
        seg = _SegOut(buf)
        emitted_np = np.asarray(emitted)          # THE per-segment sync
        ready = time.perf_counter()               # ... so it has run
        pad_np = np.asarray(pad)
        steps_i = int(steps)
        state.depth = int(total) - 1
        # slot progress updates FIRST: a preemption triggered by the
        # pool handoff below must park a POST-segment-consistent
        # snapshot (emitted, buffer, key chain all advanced together)
        for s in state.slots:
            if s is not None:
                s.emitted += int(emitted_np[s.row])
                s.spec_buf = seg
                s.spec_pad = int(pad_np[s.row])
        if pooled:
            # The spec segment's per-row rewind/re-sync ROLLS whole
            # cache rows (spec_decode._roll_cache_rows — a permutation
            # of every slot, not an append), so (a) a row's content can
            # extend DOWNWARD into what used to be pad — any table
            # column the roll made live must own a real block before
            # the handoff, or the scatter would drop content into the
            # trash block — and (b) the handoff must rewrite the full
            # row, never just the new columns. The declared contract
            # keeps the two modules honest.
            from .spec_decode import SEG_REWRITES_FULL_CACHE
            assert SEG_REWRITES_FULL_CACHE, (
                "spec segments no longer rewrite whole cache rows; the "
                "pool handoff can narrow to the new columns")
            self._extend_blocks_down(state, pad_np)
            self.pool.scatter(cache, state.tables)
            self.pool.note_compiles()
        else:
            state.cache = cache
        _ = seg.np  # materialize: the next segment donates ``buf``
        with self._stats_lock:
            seg_no = self.segments_run
            self.segments_run += 1
            self.spec_segments_run += 1
        # acceptance stats flow through the spec engine's one accounting
        # path (counters + /healthz stats + the acceptance-rate gauge),
        # so solo-spec and spec x iterbatch modes cannot diverge;
        # requests are counted at retirement (_deliver), hence 0 here
        self.spec._update_stats(0, int(emitted_np.sum()), steps_i)
        REGISTRY.inc("iter_segments_total")
        REGISTRY.inc("iter_spec_segments_total")
        self.spec._note_compiles()
        t1 = time.perf_counter()
        # per-VERIFY-step time (a spec segment's scheduling quantum),
        # between consecutive ready instants of the batch; its first
        # segment counts from its own dispatch (the window holds the
        # segment's one documented host sync)
        since = t0 if state.spec_ready is None else state.spec_ready
        state.spec_ready = ready
        REGISTRY.observe("decode_step_seconds",
                         (ready - since) / max(steps_i, 1),
                         component="iter_spec")
        for s in state.slots:
            if s is not None and s.req.trace is not None:
                # the sync above is this segment's ready instant: it
                # lies INSIDE the window, which closes after the pool
                # handoff
                s.req.trace.add_span(
                    "decode", t0, t1, ready=ready, seg=seg_no,
                    batch=state.batch, spec=True,
                    verify_steps=steps_i,
                    emitted=int(emitted_np[s.row]),
                    width=len(state.slots), depth=state.depth,
                    **({"blocks": len(s.blk_ids)} if pooled else {}))
        self._retire_finished(state)
        self._set_gauges(state)

    def _segment_keys(self, state: _BatchState, n: int):
        """[n, B, 2] per-step keys. Sample rows consume THEIR OWN step
        indices (emitted-1 ... emitted-1+n of split(dk, .) — prefix-
        stable, so a late joiner's stream matches its solo run); greedy
        segments pass zeros (the program's key operand is never read).
        A call may start at any step of a row, so the split runs to the
        next multiple of ``n`` and the start is an operand of the slice:
        the tiny programs here stay one a multiple, not one a step."""
        b = len(state.slots)
        if state.sampling.mode == "greedy":
            return jnp.zeros((n, b, 2), jnp.uint32)
        cols = []
        for s in state.slots:
            if s is None or s.dk is None:
                cols.append(jnp.zeros((n, 2), jnp.uint32))
            else:
                t0 = s.emitted - 1
                cols.append(jax.lax.dynamic_slice_in_dim(
                    jax.random.split(s.dk, -(-(t0 + n) // n) * n),
                    np.int32(t0), n))
        return jnp.stack(cols, axis=1)              # [n, B, 2]

    # -- retirement ----------------------------------------------------------

    def _retire_finished(self, state: _BatchState):
        eos_armed = any(s is not None and s.req.eos_id is not None
                        for s in state.slots)
        for i, s in enumerate(state.slots):
            if s is None:
                continue
            if (s.req.deadline is not None and s.req.deadline.expired()
                    and not s.req.done.is_set()):
                # Past-deadline row: cancelled at THIS segment boundary
                # with its blocks freed (GRAFTSAN conservation holds
                # through it) and a typed failure delivered — the
                # deadline budget is honored mid-decode, not only at
                # admission.
                if s.req.trace is not None:
                    t = time.perf_counter()
                    s.req.trace.add_span("deadline_exceeded", t, t,
                                         scheduler="iter",
                                         emitted=s.emitted)
                s.req.fail(graftfault.DeadlineExceeded(
                    "deadline budget exhausted mid-decode; row "
                    "cancelled at the segment boundary"))
                s.req.cancelled.set()
                self._vacate(state, i)
                continue
            if s.req.cancelled.is_set():
                # Caller timed out and left: free the slot instead of
                # decoding dead tokens for nobody. Nothing is delivered
                # (the payload has no reader). The flight recorder gets
                # an ``abandoned`` span at the moment the blocks come
                # back, so the reclamation is observable, not implicit.
                if s.req.trace is not None:
                    t = time.perf_counter()
                    s.req.trace.add_span("abandoned", t, t,
                                         scheduler="iter",
                                         emitted=s.emitted)
                self._vacate(state, i)
                continue
            done = s.emitted >= s.req.max_new_tokens
            eos_at = None
            if s.req.eos_id is not None and (done or eos_armed):
                # EOS scan forces the segment fetch; only armed batches
                # pay this per-segment sync
                toks = self._row_tokens(s)
                hits = np.flatnonzero(toks == s.req.eos_id)
                if hits.size:
                    eos_at = int(hits[0])
                    done = True
            if done:
                self._deliver(state, i, s, eos_at)

    def _row_tokens(self, s: _Slot) -> np.ndarray:
        if s.spec_buf is not None:
            # spec rows: the buffer IS the stream — prompt at
            # [pad, pad + plen), everything emitted right after it
            # (resumed rows included: the resume splice rebuilt the
            # lane with the full emitted stream in place)
            row = s.spec_buf.np[s.row]
            start = s.spec_pad + s.plen
            n = min(s.emitted, s.req.max_new_tokens)
            return row[start:start + n]
        if s.resumed_prefix is not None:
            # a resumed row's pre-preemption tokens were fetched at the
            # park; segments since the resume append after them
            parts = [s.resumed_prefix]
        elif s.first_ref is not None:
            parts = [s.first_ref.np[s.first_idx:s.first_idx + 1]]
        else:
            # generation by blocks: a prefill yields no token
            parts = [np.zeros((0,), np.int32)]
        parts += [seg.row(s.row)[lo:lo + n] for seg, lo, n in s.segs]
        return np.concatenate(parts)[:s.req.max_new_tokens]

    def _deliver(self, state: _BatchState, i: int, s: _Slot, eos_at):
        """Retire the slot and hand the row to its caller. No fetch
        happens here — the caller's thread assembles the tokens (see
        ``generate``), so the scheduler keeps dispatching."""
        if eos_at is not None and eos_at + 1 < s.req.max_new_tokens:
            with self._stats_lock:
                self.eos_retires += 1
            REGISTRY.inc("iter_eos_retires_total")
        s.done_t = time.monotonic()
        s.req.payload = (s, eos_at)
        gaps = (min(s.emitted, s.req.max_new_tokens) - 1 if eos_at is None
                else eos_at)
        # counted before the caller is told: whoever reads ``stats()``
        # behind its answer finds the row in them
        with self._stats_lock:
            self.rows_served += 1
            self.steps_paid += s.emitted - 1
            self.gaps_answered += gaps
        s.req.done.set()
        self._vacate(state, i)
        REGISTRY.inc("iter_steps_paid_total", value=s.emitted - 1)
        REGISTRY.inc("iter_gaps_answered_total", value=gaps)
        if state.spec_mode:
            with self.spec._stats_lock:
                self.spec._requests += 1
        REGISTRY.inc("iter_rows_total")
