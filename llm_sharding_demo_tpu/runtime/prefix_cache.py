"""Prefix caching: cross-request KV reuse for shared prompt prefixes.

Serving workloads repeat prompt prefixes constantly (system prompts,
few-shot preambles, chat history). The reference re-forwards every token
of every request (reference server.py:169-181); the plain engine prefills
each request from scratch. This front end caches KV states at chunk
boundaries and, on a prefix hit, prefills only the suffix.

Design — right-aligned chunking, unlike the engine's left-padded
``prefill_chunk``:

- The prompt is split from the LEFT edge into ``chunk``-wide pieces plus
  a ragged tail. Positions are true absolute positions (no pad), so a
  prefix's KV state is identical no matter what follows it — exactly the
  property left-alignment destroys (its pad width depends on total
  length) and the reason this module does its own chunking.
- The walk from the hit depth to the deepest whole chunk takes strides
  of whole chunks from a fixed ladder (``STRIDE_LADDER``: 4, 2, 1),
  largest first: every program call streams every weight once, so 12
  chunks left are 3 calls, not 12. The stride is a function of the
  chunks left alone; ``chunk`` stays the store's alignment (keys, hit
  depths, shared blocks).
- Compile count stays bounded: one program per ladder width (3) + at
  most ``chunk - 1`` tail widths, regardless of prompt length diversity.
- Cache entries are keyed by the token *content* of the first ``m``
  chunks and stored in LRU order. A lookup walks from the longest
  possible prefix down, so a request reuses the deepest cached state
  available, then extends it stride by stride.
- Exactness: a hit replays the same ``forward_with_cache`` math the cold
  path runs, on a device-side COPY of the stored buffers (the decode
  scan donates its cache input, and stored entries must survive), so
  greedy streams are byte-identical with the cache on or off — pinned by
  tests/test_prefix_cache.py.

Single-stream by design (per-row cache depths would need per-row offsets,
like speculation); ``runtime.batcher`` remains the batched-throughput
path. Thread-safe: ThreadingHTTPServer handles requests concurrently and
the store + donation-sensitive programs are serialized by a lock.

What a hit saves is prefill COMPUTE and HBM traffic (a 3092-token prompt
with a 3072-token cached prefix forwards 148 tokens instead of 3092 —
~20x less device work, at the same dispatch count as the plain
prefill). Whether that shows as lower request latency or only as freed
device time depends on what bounds the prefill: not measured on the
chip under the driver.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import graftmem, graftsched, graftscope, tracing
from ..utils.metrics import REGISTRY
from .engine import (DecodeEngine, GenerateResult, SamplingConfig,
                     prepare_generate, select_token)

# Static-analysis contract (tools/graftcheck): every ``jax.jit`` site in
# this module, by holding attribute — an undeclared site is a lint
# finding (a compiled-program population the recompile budget would
# silently miss).
JIT_ENTRY_POINTS = ("_extend", "_extend_keep")

# Observability contract (tools/graftcheck scope pass + utils/graftscope):
# both continuation programs' dispatches are timed into the graftscope
# ring (graftscope.instrument at the jit sites), keyed by operand shape
# — the ids width IS the program key (one program per stride/tail width).
PROFILED_SCOPES = ("_extend", "_extend_keep")


def _extend_scope_key(params, cache, ids):
    return (int(ids.shape[0]), int(ids.shape[1]))


# Whole chunks one walk call forwards, largest first. A call streams
# every weight whatever it carries, and a v5e bf16 pass carries about
# 256 tokens before compute overtakes the bytes; each entry is one more
# program per donation variant, so the ladder is short.
STRIDE_LADDER = (4, 2, 1)


def _strides(chunks: int):
    """``chunks`` whole chunks as ladder strides, largest first."""
    for s in STRIDE_LADDER:
        while chunks >= s:
            yield s
            chunks -= s

# Donation contract (tools/graftcheck sanitize pass): ``_extend``
# consumes its cache input (arg 1 — fresh caches and intermediate walk
# states); ``_extend_keep`` deliberately does NOT (stored entries must
# survive their first replay) and so declares nothing.
DONATED_ARGS = {"_extend": (1,)}

# Pool-mover lease scopes (tools/graftcheck sanitize pass): the store's
# two pool touchpoints — both move only block ids they hold refs on
# (the lookup's caller refs / the insert's fresh allocation).
POOL_MOVER_SCOPES = ("PrefixCachingEngine._gather_entry",
                     "PrefixCachingEngine._insert_pool")

# Registry handoff scopes (tools/graftcheck fleet pass): the ONLY
# functions allowed to touch the allocator's content-keyed registry
# surface (``lookup_prefix`` / ``register_prefix``) — the prefill ->
# decode block-handoff boundary. ``_lookup`` takes the adopter-side
# caller refs (a decode row referencing a prefill replica's blocks),
# ``_insert_pool`` registers the producer side (the registry takes its
# own refs). Enumerating the boundary here is what lets graftsan's
# per-block grant provenance be read as HANDOFF provenance: every
# cross-replica block lease traces to one of these two declared sites.
HANDOFF_SCOPES = ("PrefixCachingEngine._lookup",
                  "PrefixCachingEngine._insert_pool")

# Tier-movement contract (tools/graftcheck tier pass): the store's two
# grafttier touch points — the depth walk promotes a demoted entry on
# an affinity hit, and the capacity trim demotes the device LRU before
# falling back to plain eviction.
SPILL_SCOPES = ("PrefixCachingEngine._lookup",
                "PrefixCachingEngine._insert_pool")

# HBM-ledger contract (tools/graftcheck memory pass + utils/graftmem):
# the store's deep-copied cache pytrees (non-pool mode) are the
# module's long-lived device holdings — one handle-keyed ledger entry
# per stored prefix, registered at insert and released at LRU eviction.
# Pool-mode entries are block-id tuples (host ints, refs on the pool's
# own ledgered plane), so nothing registers and nothing double-counts.
MEMORY_LEDGER = {
    "_store": "prefix_store",
}

# Growth-bound contract (tools/graftcheck unbounded-device-growth
# rule): the store accumulates device arrays but is bounded — at most
# ``capacity`` entries, LRU ``popitem(last=False)`` eviction at insert.
MEMORY_BOUNDS = {
    "_store": "capacity entries; LRU popitem(last=False) at insert",
}

# Lock-discipline contract (tools/graftcheck locks pass): the store and
# its hit/miss counters live under ``_store_lock`` only — ``stats()``
# (the /healthz read) must never wait out an in-flight generation's
# seconds of device time behind the big lock.
GUARDED_STATE = {"_store": "_store_lock", "hits": "_store_lock",
                 "misses": "_store_lock", "_mem_handles": "_store_lock",
                 "extend_calls": "_store_lock",
                 "extend_tokens": "_store_lock"}

# The device lock is always the OUTER of the pair (generate/prefill
# take ``_lock``, then the walk touches the store under
# ``_store_lock``); an opposite-order path would deadlock a /healthz
# reader against an in-flight generation.
LOCK_ORDER = ("_lock", "_store_lock")

# ``_lock`` serializes the donation-sensitive extend/decode programs —
# one generation at a time is the module's documented design, so device
# dispatch under it is not a blocking-under-lock finding.
DEVICE_LOCKS = ("_lock",)


class PrefixCachingEngine:
    """Wraps a ``DecodeEngine`` with a chunk-aligned KV prefix cache.

    ``capacity`` bounds resident entries (each is a full
    ``[L, 1, H, max_seq, hd]`` KV buffer pair in the engine dtype — size
    the capacity to HBM). ``chunk`` is the alignment width: prefixes are
    cached at multiples of it, and it bounds the compile count of the
    incremental prefill programs.
    """

    def __init__(self, engine: DecodeEngine, capacity: int = 4,
                 chunk: int = 64, spec=None, pool=None):
        """``spec`` (optional ``SpecDecodeEngine`` wrapping THIS
        ``engine``) composes speculation with prefix reuse: the prefix
        path builds the cache, the verify loop decodes off it. Requests
        speculation can't serve (short prompts, no draft headroom) fall
        back to the plain decode scan.

        ``pool`` (optional ``runtime.kv_pool.KVBlockPool`` matching THIS
        engine's cache geometry) re-homes the store into the shared
        block pool: entries hold ref-counted BLOCK IDS instead of full
        ``[L, 1, H, max_seq, hd]`` buffer copies, so (a) an entry costs
        ``ceil(depth / block_size)`` blocks, not a whole ``max_seq``
        allocation, (b) entries that extend each other SHARE their
        common chunks' physical blocks structurally (the entry for
        chunks [0, m) and the deeper [0, m+k) entry reference the same
        blocks — the old store stored both in full), (c) eviction is
        the allocator's LRU over zero-ref prefix blocks (pool pressure
        evicts cold entries even below ``capacity``), and (d) live
        paged decode rows can reference entry blocks directly
        (``prefill_shared`` — zero-copy reuse, the partially-filled
        frontier block CoW'd by the consumer). Byte-exactness is
        unchanged: a hit gathers the entry into a fresh contiguous
        buffer and replays the same extend programs."""
        from ..models import is_window_independent
        if not is_window_independent(engine.config):
            # same routing-semantics gate as speculation and chunked
            # prefill (see models.is_window_independent): a chunked
            # continuation off a cached prefix must route identically to
            # the monolithic prefill for byte-exactness to hold
            raise NotImplementedError(
                "prefix caching replays the prompt in chunk windows; MoE "
                "capacity-factor routing is window-dependent, so the "
                "cached path would not be token-exact — serve MoE with "
                "the plain engine")
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        if spec is not None and spec.plain is not engine:
            raise ValueError("spec must wrap the same DecodeEngine (shared "
                             "weights/programs), got a different instance")
        if pool is not None and pool.max_seq != engine._cache_seq:
            raise ValueError(
                f"pool rows span {pool.max_seq} slots, engine cache is "
                f"{engine._cache_seq}; gathered entries must match the "
                "extend programs' cache width")
        self._eng = engine
        self._spec = spec
        self._pool = pool
        self.capacity = capacity
        self.chunk = chunk
        self._store: "OrderedDict[Tuple[int, ...], object]" = OrderedDict()
        # store key -> graftmem handle for the entry's device bytes
        # (non-pool mode; empty under a pool)
        self._mem_handles: dict = {}
        # Two locks: ``_lock`` serializes device work (the donation-
        # sensitive extend/decode programs run one generation at a time),
        # while ``_store_lock`` guards only the store and counters — so
        # ``stats()`` (the /healthz read) never waits out an in-flight
        # generation's seconds of device time behind the big lock.
        self._lock = graftsched.lock("prefix_cache.PrefixCachingEngine._lock",
                                     timeout=600.0)
        self._store_lock = graftsched.lock(
            "prefix_cache.PrefixCachingEngine._store_lock")
        self.hits = 0
        self.misses = 0
        # program calls the walks made (strides and tails) and the
        # tokens they forwarded: tokens a call is how wide the walk's
        # weight passes ran
        self.extend_calls = 0
        self.extend_tokens = 0
        # One continuation program per ids width (the ladder's stride
        # widths plus the ragged tail widths < chunk): forward `ids` at
        # cache.length.
        # Two donation variants: ``_extend`` consumes its cache input
        # (fresh caches and intermediate states), while ``_extend_keep``
        # leaves it intact — used for the FIRST step off a stored entry,
        # so the "copy the stored buffers" happens INSIDE the program
        # (XLA's copy-on-update of a non-donated input) instead of as a
        # separate host-dispatched copy — folding the copy keeps a
        # full-depth hit at the same dispatch count as a plain prefill.
        def _run(params, cache, ids):
            return engine._forward_cached(params, ids, cache, None)

        self._extend = graftscope.instrument(
            jax.jit(_run, donate_argnums=(1,)), "prefix_cache._extend",
            key_fn=_extend_scope_key)
        self._extend_keep = graftscope.instrument(
            jax.jit(_run), "prefix_cache._extend_keep",
            key_fn=_extend_scope_key)

    @property
    def plain(self) -> DecodeEngine:
        return self._eng

    @staticmethod
    def _key(prompt: np.ndarray, m_chunks: int, chunk: int) -> bytes:
        """Exact, cheap store key: the raw int32 bytes of the first
        ``m_chunks`` chunks (no per-token Python boxing — lookups on
        long prompts walk many candidate depths under the lock)."""
        return np.ascontiguousarray(
            prompt[:m_chunks * chunk], dtype=np.int32).tobytes()

    def _lookup(self, prompt: np.ndarray) -> Tuple[int, Optional[object]]:
        """Longest cached prefix of ``prompt`` -> (n_chunks_hit, entry).
        Non-pool entries are stored cache pytrees; pool entries are
        block-id tuples with one caller ref added per block (release
        with ``allocator.free``)."""
        m_max = (len(prompt) - 1) // self.chunk  # leave >=1 token to forward
        if self._pool is not None:
            tier = self._pool.tier
            for m in range(m_max, 0, -1):
                key = self._key(prompt, m, self.chunk)
                ids = self._pool.allocator.lookup_prefix(key)
                if ids is None and tier is not None and tier.has(key):
                    # demoted entry (grafttier): promote its blocks back
                    # into the pool ahead of admission. The entry kept
                    # its content key through the round trip, so the
                    # zero-copy reference semantics downstream
                    # (prefill_shared re-walking this very key) hold
                    # unchanged; a refused promote (pool full even
                    # after demoting) just walks on to shallower depths.
                    ids = tier.promote(self._pool, key)
                if ids is not None:
                    return m, ids
            return 0, None
        with self._store_lock:
            for m in range(m_max, 0, -1):
                key = self._key(prompt, m, self.chunk)
                entry = self._store.get(key)
                if entry is not None:
                    self._store.move_to_end(key)
                    return m, entry
        return 0, None

    def _gather_entry(self, ids, depth: int):
        """Pool mode: assemble an entry's blocks into a fresh
        contiguous full-width cache (trash-padded past the entry, where
        every slot is masked anyway) — byte-equal to the stored state,
        and safely donatable by the extend/decode programs."""
        import numpy as _np
        table = _np.full((1, self._pool.nbm), self._pool.trash,
                         dtype=_np.int32)
        table[0, :len(ids)] = ids
        return self._pool.gather(table, depth)

    def _insert_pool(self, prompt: np.ndarray, m_total: int, cache,
                     hit_ids, m_hit: int) -> bool:
        """Pool-mode insert: the new entry SHARES the hit entry's full
        blocks and allocates fresh ones only for the new chunks (the
        frontier region is re-scattered from the walk cache into a
        fresh block — registry blocks stay immutable). A full pool
        skips the insert instead of failing the request.

        With a state slab beside the pool the entry is its blocks AND a
        snapshot of the row's state at this boundary (``cache.state``):
        blocks are shared with a shallower entry, a state cannot be, so
        the entry's cost is a slab slot on top of its new blocks, made
        free by evicting the oldest entry where snapshots hold them
        all. Returns whether an entry was made."""
        from .kv_pool import PoolExhausted
        alloc = self._pool.allocator
        slab = self._pool.slab
        key = self._key(prompt, m_total, self.chunk)
        if alloc.has_prefix(key):
            return False
        slot = None
        if slab is not None:
            slot = slab.alloc()
            while slot is None and alloc.prefix_len():
                alloc.evict_lru()       # its snapshot comes back
                slot = slab.alloc()
            if slot is None:
                return False
        bs = self._pool.block_size
        nb_new = alloc.blocks_for(m_total * self.chunk)
        n_share = (m_hit * self.chunk) // bs if hit_ids else 0
        share = list(hit_ids[:n_share]) if hit_ids else []
        try:
            fresh = alloc.alloc(nb_new - n_share)
        except PoolExhausted:
            if slab is not None:
                slab.free(slot)
            return False
        try:
            table = np.full((1, self._pool.nbm), self._pool.trash,
                            dtype=np.int32)
            table[0, :n_share] = share
            table[0, n_share:nb_new] = fresh
            self._pool.scatter_columns(cache, table, n_share)
            alloc.register_prefix(key, share + fresh)
            if slab is not None:
                slab.snapshot(key, slot, cache.state)
                slot = None
        finally:
            alloc.free(fresh)  # entry refs (if registered) keep them;
            # on a scatter/register failure this is the leak guard
            if slot is not None:
                slab.free(slot)
        while alloc.prefix_len() > self.capacity:
            # capacity trim prefers the tier ladder: demote the LRU
            # entry to host RAM when a grafttier is attached, and only
            # evict to oblivion when there is no tier (or it refused —
            # budget exhausted / race)
            tier = self._pool.tier
            if tier is None or not tier.demote_lru(self._pool):
                alloc.evict_lru()
        return True

    def _insert(self, prompt: np.ndarray, m_chunks: int, cache) -> None:
        """Store a COPY of ``cache`` as the state after ``m_chunks`` full
        chunks of ``prompt`` (no-op if present)."""
        if m_chunks < 1:
            return
        key = self._key(prompt, m_chunks, self.chunk)
        with self._store_lock:
            if key in self._store:
                self._store.move_to_end(key)
                return
            entry = jax.tree.map(jnp.copy, cache)
            self._store[key] = entry
            self._mem_handles[key] = graftmem.track(
                self, "_store", "prefix_store", entry)
            while len(self._store) > self.capacity:
                old, _ = self._store.popitem(last=False)
                graftmem.release(self._mem_handles.pop(old, 0))

    def _prefill_walk(self, prompt: np.ndarray, prompt_len: int):
        """Store-aware chunk-aligned prefill of one prompt row: returns
        ``(last_logits [1, V], cache)``. Caller holds ``self._lock``.

        The returned cache is always a fresh program output (the tail
        step runs unconditionally and the first step off a stored entry
        copies inside the program, ``_extend_keep``), so downstream
        decode may donate it."""
        run_params = self._eng._run_params()
        m_hit, entry = self._lookup(prompt)
        hit_ids = None
        slab = self._pool.slab if self._pool is not None else None
        restored = None
        if entry is not None and slab is not None:
            # the entry is its blocks AND its state snapshot: one that
            # lost the snapshot between the lookup and here is no hit
            restored = slab.restore(self._key(prompt, m_hit, self.chunk))
            if restored is None:
                self._pool.allocator.free(entry)
                m_hit, entry = 0, None
        if entry is not None:
            with self._store_lock:
                self.hits += 1
            REGISTRY.inc("prefix_cache_hits_total")
            REGISTRY.inc("prefix_cache_reused_tokens_total",
                         value=m_hit * self.chunk)
            # mark the enclosing prefill span (request trace) so a
            # flight-recorder timeline shows hit depth, not just speed
            tracing.annotate_span(prefix_hit=True,
                                  reused_tokens=m_hit * self.chunk)
            if self._pool is not None:
                hit_ids = entry                 # ref'd block ids
                try:
                    cache = self._gather_entry(hit_ids,
                                               m_hit * self.chunk)
                except BaseException:
                    self._pool.allocator.free(hit_ids)
                    raise
                if restored is not None:
                    cache = cache._replace(state=restored)
            else:
                cache = entry
                if self._eng.cache_counters:
                    # the stored state's counters are those of the
                    # request that made it; this one counts its own
                    cache = cache._replace(v=jnp.zeros_like(cache.v))
        else:
            with self._store_lock:
                self.misses += 1
            REGISTRY.inc("prefix_cache_misses_total")
            tracing.annotate_span(prefix_hit=False)
            cache = self._eng._fresh_cache(1)

        # extend in ladder strides (one program a width), snapshotting
        # the deepest full-chunk state for the store before the ragged
        # tail consumes the buffers. The first step off a stored
        # entry must not donate it (see _extend_keep) — unless the
        # entry came from the pool, where the gather already produced
        # a fresh buffer.
        m_total = (prompt_len - 1) // self.chunk
        from_store = entry is not None and self._pool is None

        def step(cache, ids):
            nonlocal from_store
            fn = self._extend_keep if from_store else self._extend
            from_store = False
            return fn(run_params, cache, ids)

        strides = list(_strides(m_total - m_hit))
        made = False
        try:
            m = m_hit
            for s in strides:
                _, cache = step(cache, jnp.asarray(
                    prompt[None, m * self.chunk:(m + s) * self.chunk]))
                m += s
            if m_total > m_hit:
                if self._pool is not None:
                    made = self._insert_pool(prompt, m_total, cache,
                                             hit_ids, m_hit)
                else:
                    self._insert(prompt, m_total, cache)
                    made = True
        finally:
            # the caller refs taken by the pool lookup must not outlive
            # the walk even when an extend step raises — a phantom ref
            # would pin the entry's blocks past its own eviction
            if hit_ids is not None:
                self._pool.allocator.free(hit_ids)
        tail = jnp.asarray(prompt[None, m_total * self.chunk:])
        logits, cache = step(cache, tail)
        calls = len(strides) + 1                # the tail's
        with self._store_lock:
            self.extend_calls += calls
            self.extend_tokens += prompt_len - m_hit * self.chunk
        tracing.annotate_span(extend_calls=calls)
        if getattr(cache, "state", None) is not None:
            # the depth a state snapshot gave this walk (0: none) and
            # the snapshots it took (an entry of this family is one)
            tracing.annotate_span(
                state_restored=m_hit * self.chunk if entry is not None
                else 0, state_snapshots=int(made))
        return logits, cache

    def prefill_state(self, prompt: np.ndarray):
        """Public single-row prefill for the batching front end
        (runtime.batcher): ``(last_logits [1, V], cache, prompt_len)``
        with the store consulted/updated. The caller owns the returned
        cache (safe to donate)."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        with self._lock:
            with tracing.span("prefill", prefix=True,
                              prompt_len=len(prompt)):
                logits, cache = self._prefill_walk(prompt, len(prompt))
        return logits[:, -1], cache, len(prompt)

    def prefill_shared(self, prompt: np.ndarray):
        """Paged-runner entry (pool mode only): walk the store, then
        return ``(last_logits [1, V], cache, shared_ids, hit_depth)``
        where ``shared_ids`` are the block ids of the DEEPEST entry now
        covering the prompt (including one the walk just inserted),
        with one caller ref per block — the runner references them in
        its own table instead of duplicating the prefill state, and
        releases them at retirement."""
        if self._pool is None:
            raise ValueError("prefill_shared requires a pool-backed "
                             "store (pass pool= at construction)")
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        with self._lock:
            with tracing.span("prefill", prefix=True,
                              prompt_len=len(prompt)):
                logits, cache = self._prefill_walk(prompt, len(prompt))
            m, ids = self._lookup(prompt)
        return (logits[:, -1], cache, list(ids or ()),
                m * self.chunk)

    def generate(self, prompt_ids, max_new_tokens: int,
                 sampling: SamplingConfig = SamplingConfig(),
                 key: Optional[jax.Array] = None) -> GenerateResult:
        ids, batch, prompt_len, key, pad = prepare_generate(
            prompt_ids, max_new_tokens, self._eng.max_seq, sampling, key,
            allow_ragged=False)
        if batch != 1:
            raise ValueError("prefix caching is single-stream (batch=1); "
                             "batched throughput goes through "
                             "DecodeEngine / runtime.batcher")
        prompt = ids[0]
        run_params = self._eng._run_params()

        with self._lock:
            t0 = time.perf_counter()
            with tracing.span("prefill", prefix=True,
                              prompt_len=prompt_len):
                logits, cache = self._prefill_walk(prompt, prompt_len)

                prefill_key, decode_key = jax.random.split(key)
                first = select_token(logits[:, -1], sampling, prefill_key)
                first.block_until_ready()
            prefill_seconds = time.perf_counter() - t0

            spec = self._spec
            if spec is not None and spec.eligible(prompt_len,
                                                  max_new_tokens):
                # the prefix path's cache is right-aligned (no pad, true
                # positions, length == prompt_len) — exactly the state the
                # verify loop expects; it donates the cache, which is
                # always a fresh _extend output here (stored entries were
                # snapshotted by copy)
                result = spec.run_loop(
                    run_params, prompt, first, cache, prompt_len,
                    decode_key, max_new_tokens, sampling,
                    prefill_seconds=prefill_seconds)
            else:
                result = self._eng._decode_and_pack(
                    run_params, ids, pad, None, first, cache, decode_key,
                    max_new_tokens, sampling, prompt_len, prefill_seconds)
        return result

    def stats(self) -> dict:
        with self._store_lock:
            entries = (self._pool.allocator.prefix_len()
                       if self._pool is not None else len(self._store))
            out = {"entries": entries, "hits": self.hits,
                   "misses": self.misses, "capacity": self.capacity,
                   "chunk": self.chunk, "extend_calls": self.extend_calls,
                   "extend_tokens": self.extend_tokens}
            if self._pool is not None:
                out["pooled"] = True
            return out
