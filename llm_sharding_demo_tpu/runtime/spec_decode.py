"""Speculative decoding: prompt-lookup drafts + one-program greedy verify.

Latency optimization with **no reference counterpart** (the reference
forwards one token per two HTTP round-trips, reference server.py:169-181;
this module emits up to ``draft_len + 1`` tokens per forward). Greedy
speculative decoding is *provably token-exact*: a draft token is kept only
when it equals the model's own argmax at that position, so the emitted
stream is byte-identical to plain greedy decode — the parity test pins
this (tests/test_spec_decode.py). Sample mode is *distribution-exact* via
rejection sampling against the point-mass draft (see ``_loop_impl``),
reproducing the reference's temperature/top-k sampler distribution
(reference server.py:187-205) token for token — pinned by a pmf test.

Why it pays on TPU: single-stream decode is HBM-bandwidth-bound — every
step streams all weights to produce ONE token's worth of MXU work. A
verify step forwards ``K+1`` tokens through the same weights for the same
weight traffic, so each accepted draft is a nearly-free token. With
prompt-lookup drafting (Saxena's "prompt lookup decoding" /
assisted-generation n-gram variant) the draft model is the sequence
itself — no second network:

- **draft**: find the most recent previous occurrence of the last
  ``ngram`` tokens in the sequence so far; propose the ``draft_len``
  tokens that followed it (natural text and greedy GPT-2 output are both
  highly repetitive, so acceptance is high exactly when decode is long);
- **verify**: one cached forward of ``[t_last, d_1..d_K]`` at the current
  cache offset (ops.attention.cached_attention already supports S>1
  writes at a dynamic offset); accept the longest prefix where
  ``d_j == argmax(logits_{j-1})``, emit one bonus token from the first
  mismatch position;
- **rewind**: the KV written for rejected drafts is logically dropped by
  resetting ``KVCache.length`` (a traced scalar) — the stale slots sit
  beyond the valid length, are masked out of attention by ``kv_length``,
  and are physically overwritten by the next verify step's write at the
  rewound offset.

The whole generation after prefill is ONE compiled program: a
``lax.while_loop`` whose body is draft-match (vectorized n-gram scan, no
host work) + verify forward + buffer/cache bookkeeping.

Batched speculation (the spec x batching composition): rows accept
*different* draft counts per verify, which would naively need per-row
cache write offsets — impossible under one ``dynamic_update_slice``. The
batched loop keeps every row at ONE uniform cache depth instead
(the iterbatch trick, inverted): row i's content occupies slots
``[pad_i, total)`` with per-row left-pad slack, every verify forwards
``[t_last_i, drafts_i]`` for all rows at the shared offset, and after
per-row acceptance the batch RE-SYNCS — each row's cache/buffer rolls by
a signed per-row shift so all rows end at the new uniform depth
``max_i(content_len_i)``, the slack landing in the masked pad prefix.
The roll is a pure permutation (values bitwise intact, positions =
slot - pad_i unchanged), so each row's stream is byte-equal to its solo
single-stream spec run — greedy AND seeded sample (per-row key chains
advance one split per verify, exactly like the solo loop). Acceptance
counts are traced values inside one ``lax.while_loop`` program: the
compiled-program set stays one loop per (batch width, policy), never one
per acceptance pattern. The minimal uniform depth also preserves the
single-stream headroom bound: writes never pass
``max_i(plen_i + max_new) + draft_len <= max_seq``.

``seg_verify`` exposes the same body as a bounded SEGMENT program
(per-row budgets, at most ``max_verify`` verifies) so the iteration-level
scheduler (runtime.iterbatch) can run speculative segments on a live
batch — rows join/retire between segments without draining the batch.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.gpt2 import GPT2Config, Params
from ..ops.attention import KVCache
from ..utils import graftmem, graftsched, graftscope, tracing
from ..utils.metrics import REGISTRY, CompileWatch
from .engine import (DecodeEngine, GenerateResult, SamplingConfig,
                     prepare_generate, sampler_pmf, select_token)


# Static-analysis contract (tools/graftcheck): every ``jax.jit`` site in
# this module, by holding attribute — enumerated by the recompile-budget
# certifier; an undeclared site is a lint finding.
JIT_ENTRY_POINTS = ("_loop", "_loop_b", "_seg_b")

# Observability contract (tools/graftcheck scope pass + utils/graftscope):
# every declared jit entry point's dispatch is timed into the graftscope
# ring (graftscope.instrument at the jit site), keyed in the certifier's
# program-key model (recompile.spec_call_keys / iter_spec_segment_keys).
PROFILED_SCOPES = ("_loop", "_loop_b", "_seg_b")


# graftscope program-key derivations (the certifier's model: _loop ->
# (max_new, sampling, pad present); _loop_b -> (b, max_new, sampling);
# _seg_b -> (width, max_verify, sampling) — acceptance counts and
# budgets are traced and never key programs)

def _loop_scope_key(params, first_token, cache, buf, total, key, pad, *,
                    max_new, sampling):
    return (max_new, sampling, pad is not None)


def _loop_b_scope_key(params, first, cache, buf, total, keys, pad, *,
                      max_new, sampling):
    return (int(first.shape[0]), max_new, sampling)


def _seg_b_scope_key(params, buf, cache, total, pad, keys, budgets, *,
                     max_verify, sampling):
    return (int(buf.shape[0]), max_verify, sampling)

# Donation contract (tools/graftcheck sanitize pass): consumed
# positional arguments per entry point. ``_loop``/``_loop_b`` donate
# the prefill cache (and the batched token buffer); ``_seg_b`` donates
# the segment's token buffer and working cache — the iteration
# scheduler must re-bind both from the call's outputs every segment.
DONATED_ARGS = {"_loop": (2,), "_loop_b": (2, 3), "_seg_b": (1, 2)}

# Lock-discipline contract (tools/graftcheck locks pass): the
# acceptance accounting ThreadingHTTPServer callers and the iteration
# scheduler both bump lives under ``_stats_lock`` — including the
# cross-module ``spec._requests`` retirement count in
# runtime/iterbatch.py, which this declaration holds to the same lock.
GUARDED_STATE = {"_requests": "_stats_lock", "_verifies": "_stats_lock",
                 "_emitted": "_stats_lock"}
LOCK_ORDER = ("_stats_lock",)

# Block-handoff contract for pool-backed schedulers (see
# ``_seg_b_impl``): True means a spec segment may rewrite ANY slot of a
# row's cache (the re-sync roll), so paged storage must scatter whole
# rows back, never just the newly decoded columns.
SEG_REWRITES_FULL_CACHE = True

# HBM-ledger contract (tools/graftcheck memory pass + utils/graftmem):
# the verify loop's device token buffer ``[.., max_seq + draft_len + 1]``
# — live from allocation to the post-loop numpy fetch (solo and batched
# paths each register their own handle-keyed entry; the iteration
# scheduler's per-batch spec buffer registers in runtime/iterbatch.py).
MEMORY_LEDGER = {
    "buf": "spec_buffers",
}


class SpecDecodeEngine:
    """Speculative decode engine (single stream; greedy + sample modes).

    Composes a ``DecodeEngine`` for parameter preparation (dtype cast /
    int8 quantization / model-family dispatch) and its jitted prefill;
    replaces the token-by-token decode scan with the verify loop above.

    ``draft_len`` (K) is the speculation depth: each verify forward costs
    one (K+1)-token step and emits 1..K+1 tokens. ``ngram`` is the match
    width for prompt lookup (2 is the standard sweet spot: long enough to
    avoid noise matches, short enough to fire often).
    """

    def __init__(self, params: Params, config: GPT2Config, max_seq: int,
                 dtype=jnp.float32, draft_len: int = 6, ngram: int = 2,
                 prefill_chunk: Optional[int] = None):
        from ..models import is_window_independent, row_state
        if row_state(config, dtype):
            raise NotImplementedError(
                f"{type(config).__name__}'s rows hold a state beside "
                "their positions: a rejected draft is rewound out of a "
                "cache by position, and a state has none to rewind to "
                "without a snapshot a verify; it decodes without "
                "speculation")
        if not is_window_independent(config):
            # Not an implementation gap — a semantic one: a (K+1)-token
            # verify forward must route identically to the plain engine's
            # single-token steps for the token-exactness guarantee to
            # hold (see models.is_window_independent).
            raise NotImplementedError(
                "speculative decoding requires window-independent token "
                "routing; MoE capacity-factor routing makes multi-token "
                "verify windows route differently than single-token "
                "decode steps — serve MoE with the plain engine")
        if draft_len < 1:
            raise ValueError("draft_len must be >= 1")
        if ngram < 1:
            raise ValueError("ngram must be >= 1")
        self.draft_len = draft_len
        self.ngram = ngram
        # The engine owns params/cache sizing (and chunked prefill); its
        # overflow guard also covers ours (we re-check with draft headroom
        # in generate()). decode_kernel is pinned to "xla" on BOTH sides:
        # the verify windows are multi-token (fused-XLA numerics), so a
        # kernel-decoding plain engine would break the token-exactness
        # contract between the spec stream and the plain fallback stream
        # on argmax near-ties.
        self._eng = DecodeEngine(params, config, max_seq, dtype=dtype,
                                 prefill_chunk=prefill_chunk,
                                 decode_kernel="xla")
        self.config = config
        self.max_seq = max_seq
        self._stats_lock = graftsched.lock(
            "spec_decode.SpecDecodeEngine._stats_lock")
        self._requests = 0
        self._verifies = 0
        self._emitted = 0
        self._loop = graftscope.instrument(
            jax.jit(self._loop_impl,
                    static_argnames=("max_new", "sampling"),
                    donate_argnums=(2,)),
            "spec_decode._loop", key_fn=_loop_scope_key)
        # Batched variants (one program per batch width + policy, never
        # per acceptance pattern): the full-generation loop and the
        # bounded segment program the iteration scheduler drives.
        self._loop_b = graftscope.instrument(
            jax.jit(self._loop_b_impl,
                    static_argnames=("max_new", "sampling"),
                    donate_argnums=(2, 3)),
            "spec_decode._loop_b", key_fn=_loop_b_scope_key)
        self._seg_b = graftscope.instrument(
            jax.jit(self._seg_b_impl,
                    static_argnames=("max_verify", "sampling"),
                    donate_argnums=(1, 2)),
            "spec_decode._seg_b", key_fn=_seg_b_scope_key)
        # compile-event accounting (one increment per NEW (width, policy)
        # program — see utils.metrics.CompileWatch); the iteration
        # scheduler checks the segment watch after its dispatches
        self._compile_watches = (CompileWatch("spec_loop", self._loop),
                                 CompileWatch("spec_loop", self._loop_b),
                                 CompileWatch("spec_seg", self._seg_b))

    def _note_compiles(self) -> None:
        self._eng._note_compiles()   # the shared prefill programs
        for w in self._compile_watches:
            w.check()
        REGISTRY.gauge("jit_program_cache_size",
                       sum(w.seen() for w in self._compile_watches),
                       component="spec")

    def _update_stats(self, n_req: int, n_tok: int, steps: int) -> None:
        """Shared acceptance accounting: cumulative /healthz stats,
        counters, and the live acceptance-rate gauge."""
        with self._stats_lock:
            self._requests += n_req
            self._verifies += steps
            self._emitted += n_tok
            rate = self._emitted / max(self._verifies, 1)
        REGISTRY.inc("spec_verify_steps_total", value=steps)
        REGISTRY.inc("spec_emitted_tokens_total", value=n_tok)
        REGISTRY.gauge("spec_acceptance_rate", round(rate, 4))

    @property
    def plain(self) -> DecodeEngine:
        """The wrapped plain engine (shared weights/compilations) — the
        serving layer routes ineligible requests here."""
        return self._eng

    def check_request(self, prompt_len: int, max_new_tokens: int) -> None:
        """Raising form of the speculation-eligibility predicate, THE
        single definition of the rule: the batching front ends
        (runtime.batcher, runtime.iterbatch) call it on the caller
        thread so a spec-flagged request the verify loop cannot serve
        exactly is refused with its own numbers, never discovered
        mid-batch — and a future change to the rule (e.g. an alignment
        reserve) cannot silently diverge between front ends."""
        if prompt_len < self.ngram:
            raise ValueError(
                f"prompt_len={prompt_len} shorter than ngram={self.ngram}")
        total = prompt_len + max_new_tokens + self.draft_len
        if total > self.max_seq:
            raise ValueError(
                f"prompt_len={prompt_len} + max_new_tokens="
                f"{max_new_tokens} + draft_len={self.draft_len} "
                f"exceeds max_seq={self.max_seq}; verify writes need "
                "draft_len slots of headroom")

    def eligible(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Boolean form of ``check_request``: prompt long enough for an
        n-gram and ``draft_len`` slots of cache headroom for verify
        writes. The serving router and the prefix-cache front end both
        consult this (a request that fails it decodes plain)."""
        try:
            self.check_request(prompt_len, max_new_tokens)
            return True
        except ValueError:
            return False

    def stats(self) -> dict:
        """Cumulative speculation effectiveness (served at /healthz)."""
        with self._stats_lock:
            return {"requests": self._requests,
                    "verify_steps": self._verifies,
                    "emitted_tokens": self._emitted,
                    "draft_len": self.draft_len,
                    "tokens_per_verify": round(self._emitted
                                               / max(self._verifies, 1), 2)}

    # -- shared verify-step pieces (solo loop + batched loop/segment) --------

    def _draft_row(self, buf, low, total, t_last):
        """Propose K tokens for ONE row via most-recent n-gram match over
        ``buf[low:total)`` (``low`` excludes the left-pad prefix — pad
        garbage must never become draft material). THE draft definition:
        the solo loop calls it with scalars, the batched paths vmap it
        with per-row ``low``/``t_last`` — same ops, so a batched row's
        drafts are bitwise its solo run's."""
        K, ngram = self.draft_len, self.ngram
        buflen = buf.shape[0]
        j_arr = jnp.arange(buflen, dtype=jnp.int32)
        last = jax.lax.dynamic_slice(buf, (total - ngram,), (ngram,))
        match = jnp.ones((buflen,), dtype=bool)
        for t in range(ngram):
            match = match & (jnp.roll(buf, -t) == last[t])
        # exclude the current occurrence itself, anything past it,
        # and the left-pad prefix
        match = match & (j_arr < total - ngram) & (j_arr >= low)
        cand = jnp.where(match, j_arr, -1)
        best = cand.max()
        found = best >= 0
        start = jnp.where(found, best + ngram, 0)
        got = jax.lax.dynamic_slice(buf, (start,), (K,))
        # fallback: repeat the last token (catches token-loop output)
        return jnp.where(found, got, jnp.full((K,), t_last, jnp.int32))

    def _accept_patch(self, logits, drafts, step_key,
                      sampling: SamplingConfig):
        """[K+1, V] verify logits -> (n_accept, patch_tokens [K+1]).

        ``patch_tokens[j]`` is meaningful for ``j <= n_accept``:
        accepted drafts then the bonus token. One row's acceptance —
        shared verbatim between the solo loop and the vmapped batched
        paths (vmapped per-row RNG draws consume the same bits a solo
        call with that row's key would — the select_token per-row-key
        contract)."""
        K = self.draft_len
        if sampling.mode == "greedy":
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            hits = (drafts == greedy[:K]).astype(jnp.int32)
            # greedy[j] is the token after x[j]; the bonus at the first
            # mismatch position is greedy itself, so patch == greedy
            return jnp.cumprod(hits).sum(), greedy
        # THE sampler distribution (engine.sampler_pmf: temperature +
        # top-k + optional nucleus) — shared with select_token so
        # acceptance probabilities and the plain sampler cannot drift
        probs, top_idx = sampler_pmf(logits, sampling)   # [K+1, k]
        k_acc, k_res = jax.random.split(step_key)
        in_topk = top_idx[:K] == drafts[:, None]         # [K, k]
        p_d = (probs[:K] * in_topk).sum(-1)              # [K]
        u = jax.random.uniform(k_acc, (K,))
        n_accept = jnp.cumprod((u < p_d).astype(jnp.int32)).sum()
        # bonus from row n_accept: the residual when a rejection
        # happened there, the plain pmf when every draft was accepted
        row_p, row_i = probs[n_accept], top_idx[n_accept]
        d_rej = drafts[jnp.minimum(n_accept, K - 1)]
        zero_d = (n_accept < K) & (row_i == d_rej)
        resid = jnp.where(zero_d, 0.0, row_p)
        choice = jax.random.categorical(k_res, jnp.log(resid))
        bonus = row_i[choice].astype(jnp.int32)
        dr_ext = jnp.concatenate([drafts, jnp.zeros((1,), jnp.int32)])
        return n_accept, jnp.where(jnp.arange(K + 1) < n_accept,
                                   dr_ext, bonus)

    # -- compiled verify loop ------------------------------------------------

    def _loop_impl(self, params, first_token, cache, buf, total, key, pad, *,
                   max_new: int, sampling: SamplingConfig):
        """(buf, total, cache) after prefill -> (buf, verify_steps).

        ``pad`` is ``None`` or a ``[1]`` int32 array: the left-pad prefix
        the chunk-aligned prefill placed in ``buf``/cache slots ``[0,
        pad)`` — masked as attention keys and excluded from the n-gram
        draft search (chunk padding must never become draft material).

        Invariant at loop entry: ``buf[:total]`` holds prompt + emitted
        tokens, ``cache.length == total - 1`` (the last emitted token has
        not been forwarded yet), ``emitted`` counts new tokens so far.

        Greedy acceptance compares drafts against the model argmax —
        token-exact by construction. Sample mode is *distribution-exact*
        rejection sampling against the point-mass draft: draft ``d_j`` is
        accepted with probability ``p_j(d_j)`` under the reference
        sampler's temperature/top-k pmf; the first rejection's bonus token
        is drawn from the residual ``p_j`` with ``d_j`` zeroed and
        renormalized (for a point-mass proposal the Leviathan residual
        ``max(0, p - q)/Z`` reduces to exactly that), and a fully-accepted
        window draws the bonus from ``p_K`` unmodified. Each emitted token
        is therefore distributed exactly as the plain sampler's — only the
        RNG consumption pattern differs, so seeded streams differ while
        the distribution does not (pinned by the pmf test)."""
        K = self.draft_len

        low = jnp.int32(0) if pad is None else pad[0]

        def body(carry):
            buf, total, cache, emitted, steps, key = carry
            key, step_key = jax.random.split(key)
            t_last = buf[total - 1]
            drafts = self._draft_row(buf, low, total, t_last)
            x = jnp.concatenate([t_last[None], drafts])[None, :]  # [1, K+1]
            logits, cache = self._eng._forward_cached(params, x, cache, pad)
            n_accept, patch_tokens = self._accept_patch(logits[0], drafts,
                                                        step_key, sampling)
            n_emit = jnp.minimum(n_accept + 1, max_new - emitted)
            # splice the emitted tokens into buf at `total`
            old = jax.lax.dynamic_slice(buf, (total,), (K + 1,))
            patch = jnp.where(jnp.arange(K + 1) < n_emit, patch_tokens, old)
            buf = jax.lax.dynamic_update_slice(buf, patch, (total,))
            # rewind: forwarded-and-kept = t_last + the accepted prefix;
            # slots beyond are stale and masked by kv_length until the
            # next verify overwrites them at the rewound offset
            cache = cache._replace(
                length=(total - 1 + n_emit).astype(jnp.int32))
            return (buf, total + n_emit, cache, emitted + n_emit,
                    steps + 1, key)

        def cond(carry):
            return carry[3] < max_new

        first = first_token.reshape(()).astype(jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, first[None], (total,))
        carry = (buf, total + 1, cache, jnp.int32(1), jnp.int32(0), key)
        buf, _, cache, _, steps, _ = jax.lax.while_loop(cond, body, carry)
        return buf, steps, cache

    # -- batched verify loop -------------------------------------------------

    @staticmethod
    def _roll_cache_rows(cache, shifts):
        """Per-row signed roll of every cache buffer along the slot axis
        (``out[.., b, .., j, :] = in[.., b, .., j - shifts[b], :]``, mod
        buffer size) — the batched rewind/re-sync permutation. A pure
        gather: values stay bitwise intact, and since a row's positions
        are ``slot - pad`` with pad shifted by the same amount, the
        row's math is untouched. Handles plain, fused (placeholder
        ``v``), and staged (list) cache forms. ``shifts`` is traced —
        one compiled gather serves every acceptance pattern."""
        def g(x):
            if getattr(x, "ndim", 0) <= 1:
                return x                           # fused placeholder v
            s = x.shape[-2]
            idx = (jnp.arange(s)[None, :] - shifts[:, None]) % s  # [B, S]
            shape = (1, idx.shape[0]) + (1,) * (x.ndim - 4) + (s, 1)
            return jnp.take_along_axis(x, idx.reshape(shape), axis=-2)

        def one(c: KVCache) -> KVCache:
            return KVCache(k=g(c.k), v=g(c.v), length=c.length)

        if isinstance(cache, list):
            return [one(c) for c in cache]
        return one(cache)

    def _step_b(self, params, sampling: SamplingConfig, budgets, carry):
        """One batched verify step + per-row rewind/re-sync: the body of
        both batched programs (full loop and iterbatch segment).

        Carry: ``(buf [B, buflen], total, cache, pad [B], emitted [B],
        steps, keys [B, 2])``. Invariant (the solo loop's, per row at
        ONE uniform depth): row b's content is ``buf[b, pad_b:total]``,
        ``cache.length == total - 1`` with slots ``[pad_b, total - 1)``
        valid for row b, and the last emitted token is unforwarded.

        ``budgets`` [B] cap each row's TOTAL emission (ghost/finished
        rows run n_emit = 0 and just carry garbage nobody reads); the
        cap is the same ``min(n_accept + 1, remaining)`` the solo loop
        applies at max_new, so a capped row's stream is byte-equal to a
        solo run with that budget. After acceptance the batch re-syncs
        at the MINIMAL uniform depth ``max_b(content_len_b)`` — pads
        absorb the per-row slack, so depth never exceeds the longest
        row's content and verify writes keep the single-stream headroom
        bound (``max(plen + budget) + draft_len``)."""
        buf, total, cache, pad, emitted, steps, keys = carry
        K = self.draft_len
        b, buflen = buf.shape
        if sampling.mode == "greedy":
            step_keys = keys                       # program never reads them
        else:
            pair = jax.vmap(jax.random.split)(keys)        # [B, 2, 2]
            keys, step_keys = pair[:, 0], pair[:, 1]
        t_last = buf[:, total - 1]                         # [B]
        drafts = jax.vmap(
            lambda bf, lo, tl: self._draft_row(bf, lo, total, tl))(
                buf, pad, t_last)                          # [B, K]
        x = jnp.concatenate([t_last[:, None], drafts], axis=1)  # [B, K+1]
        logits, cache = self._eng._forward_cached(params, x, cache, pad)
        n_accept, patch = jax.vmap(
            lambda lg, dr, sk: self._accept_patch(lg, dr, sk, sampling))(
                logits, drafts, step_keys)
        n_emit = jnp.clip(n_accept + 1, 0, budgets - emitted)     # [B]
        old = jax.lax.dynamic_slice(buf, (0, total), (b, K + 1))
        write = jnp.where(jnp.arange(K + 1)[None, :] < n_emit[:, None],
                          patch, old)
        buf = jax.lax.dynamic_update_slice(buf, write, (0, total))
        # rewind + re-sync: row b keeps n_emit_b of the K+1 verify slots
        # (t_last + accepted prefix — the solo loop's length formula),
        # then every row rolls by a signed per-row shift so content ends
        # at the new uniform depth; the slack lands in the masked pad
        # prefix and stale verify slots sit beyond the new length until
        # the next verify overwrites them.
        content = (total - pad) + n_emit                   # [B] new lens
        new_total = content.max()
        new_pad = new_total - content                      # [B] >= 0
        shifts = new_pad - pad                             # signed
        bidx = (jnp.arange(buflen)[None, :] - shifts[:, None]) % buflen
        buf = jnp.take_along_axis(buf, bidx, axis=1)
        cache = self._roll_cache_rows(cache, shifts)
        new_len = (new_total - 1).astype(jnp.int32)
        if isinstance(cache, list):
            cache = [c._replace(length=new_len) for c in cache]
        else:
            cache = cache._replace(length=new_len)
        return (buf, new_total, cache, new_pad, emitted + n_emit,
                steps + 1, keys)

    def _loop_b_impl(self, params, first, cache, buf, total, keys, pad, *,
                     max_new: int, sampling: SamplingConfig):
        """Batched full-generation loop: ``(buf, pad, total, steps,
        cache)`` after prefill -> completion. Entry state mirrors the
        solo loop per row: ``buf[b, pad_b:total]`` holds row b's prompt,
        ``cache.length == total`` from prefill, ``first`` [B] are the
        prefill-selected tokens (appended here, making ``cache.length ==
        total' - 1``). Runs until EVERY row emitted ``max_new``; rows
        that finish early keep verifying as ghosts (n_emit = 0, content
        frozen) — harmless by row independence."""
        b = buf.shape[0]
        first = first.reshape((b,)).astype(jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, first[:, None], (0, total))
        budgets = jnp.full((b,), max_new, jnp.int32)
        carry = (buf, total + 1, cache, pad,
                 jnp.ones((b,), jnp.int32), jnp.int32(0), keys)

        def cond(c):
            return jnp.any(c[4] < max_new)

        def body(c):
            return self._step_b(params, sampling, budgets, c)

        buf, total, cache, pad, _, steps, _ = jax.lax.while_loop(
            cond, body, carry)
        return buf, pad, total, steps, cache

    def _seg_b_impl(self, params, buf, cache, total, pad, keys, budgets, *,
                    max_verify: int, sampling: SamplingConfig):
        """Bounded draft-verify SEGMENT over a live batch (the
        iteration-level scheduler's spec segment type): up to
        ``max_verify`` verify steps, stopping early when every row's
        remaining ``budgets`` [B] are spent. Returns ``(buf, total,
        cache, pad, emitted [B], steps, keys)`` — the same carry it
        takes, so segments resume exactly where the last one stopped
        (per-row key chains included: a row's verify sequence across
        segments is identical to its uninterrupted solo run).

        Paged-KV block handoff contract (runtime.kv_pool x
        runtime.iterbatch): the per-row rewind/re-sync inside
        ``_step_b`` ROLLS entire cache rows (``_roll_cache_rows`` — a
        permutation of every slot, not an append at the frontier), so a
        pool-backed scheduler must scatter the FULL row back into its
        blocks after each spec segment; a new-columns-only handoff
        would silently keep pre-roll bytes for the untouched blocks.
        ``SEG_REWRITES_FULL_CACHE`` declares this; iterbatch asserts it
        before choosing its scatter range."""
        b = buf.shape[0]
        carry = (buf, total, cache, pad,
                 jnp.zeros((b,), jnp.int32), jnp.int32(0), keys)

        def cond(c):
            return (c[5] < max_verify) & jnp.any(c[4] < budgets)

        def body(c):
            return self._step_b(params, sampling, budgets, c)

        return jax.lax.while_loop(cond, body, carry)

    # -- public API ----------------------------------------------------------

    def generate(self, prompt_ids, max_new_tokens: int,
                 sampling: SamplingConfig = SamplingConfig(),
                 key: Optional[jax.Array] = None,
                 pad: Optional[np.ndarray] = None,
                 delivered: Optional[tuple] = None) -> GenerateResult:
        """Speculative generate: per row token-exact vs
        ``DecodeEngine.generate`` in greedy mode, distribution-exact
        (rejection sampling, see ``_loop_impl``) in sample mode.

        Accepts ``[S]`` / ``[1, S]`` single streams (the original loop,
        byte-for-byte unchanged), ``[B, S]`` batches, and ragged prompt
        lists (left-padded); ``pad`` lets pre-padded callers
        (runtime.batcher) declare their left-pad prefixes, exactly like
        the plain engine. Batched rows are byte-equal to their solo
        spec runs: greedy by construction, seeded sampling via per-row
        key chains (``key`` must then be a ``[B, 2]`` per-row stack —
        each row's stream is a function of its own key only).

        ``delivered`` (optional ``(requests, tokens)``) overrides the
        acceptance-stats accounting for bucketing front ends
        (runtime.batcher): a bucketed round decodes dummy rows and
        over-decodes short requests to the shared step count, but
        /healthz's ``tokens_per_verify`` must count what callers were
        actually served, or the admission and iteration schedulers
        would report incompatible numbers for the same metric.
        """
        # the spec flag is routing metadata for the batching front ends;
        # normalize it away so flagged and unflagged requests share the
        # same compiled programs (and identical token streams)
        sampling = dataclasses.replace(sampling, spec=False)
        ids, batch, prompt_len, key, pad = prepare_generate(
            prompt_ids, max_new_tokens, self.max_seq, sampling, key,
            allow_ragged=True, pad=pad)
        min_plen = prompt_len - (int(pad.max()) if pad.any() else 0)
        if min_plen < self.ngram:
            raise ValueError(
                f"prompt_len={min_plen} shorter than ngram={self.ngram}")
        # Verify steps write up to draft_len tokens past the final length,
        # so the cache/position headroom check is stricter than the
        # engine's prompt+new <= max_seq guard. (The batched loop's
        # uniform depth never exceeds the longest row's content length —
        # see _step_b — so the single-stream bound covers batches too.)
        total_max = prompt_len + max_new_tokens + self.draft_len
        if total_max > self.max_seq:
            raise ValueError(
                f"prompt_len + max_new_tokens + draft_len = {total_max} "
                f"exceeds max_seq={self.max_seq}; verify writes need "
                "draft_len slots of headroom")
        if (batch > 1 and sampling.mode != "greedy"
                and getattr(key, "ndim", 1) != 2):
            raise ValueError(
                "batched sample-mode speculation needs a [B, 2] per-row "
                "key stack (one key per row — the engine._split_keys "
                "contract; a single joint key cannot be byte-equal to "
                "per-row solo runs)")

        # Chunk-align through the inner engine's shared helper; reserve
        # covers upcoming tokens AND the verify write headroom.
        ids, pad, prompt_len, chunk = self._eng._align_chunks(
            ids, pad, prompt_len, reserve=max_new_tokens + self.draft_len)

        ids_j = jnp.asarray(ids, dtype=jnp.int32)
        pad_j = jnp.asarray(pad) if pad.any() else None
        run_params = self._eng._run_params()

        t0 = time.perf_counter()
        if batch == 1:
            if getattr(key, "ndim", 1) == 2:
                key = key[0]     # a 1-row per-row stack == the solo key
            prefill_key, loop_key = jax.random.split(key)
        elif sampling.mode == "greedy":
            prefill_key = key                    # never consumed by greedy
            loop_key = jnp.zeros((batch, 2), jnp.uint32)
        else:
            pair = jax.vmap(jax.random.split)(key)       # [B, 2, 2]
            prefill_key, loop_key = pair[:, 0], pair[:, 1]
        if chunk:
            n_chunks = ids_j.shape[1] // chunk
            chunks = ids_j.reshape(batch, n_chunks, chunk).transpose(1, 0, 2)
            last_logits, cache = self._eng._prefill_chunked(
                run_params, chunks,
                pad_j if pad_j is not None
                else jnp.zeros((batch,), jnp.int32))
        else:
            last_logits, cache = self._eng._prefill(run_params, ids_j, pad_j)
        first = select_token(last_logits, sampling, prefill_key)
        first.block_until_ready()
        t1 = time.perf_counter()
        tracing.record("prefill", t0, t1, batch=batch,
                       prompt_len=prompt_len, chunked=bool(chunk))

        if batch == 1:
            return self.run_loop(run_params, ids_j[0], first, cache,
                                 prompt_len, loop_key, max_new_tokens,
                                 sampling, prefill_seconds=t1 - t0,
                                 pad=pad if pad.any() else None,
                                 delivered=delivered)
        return self._run_loop_batched(run_params, ids_j, first, cache,
                                      prompt_len, loop_key, pad,
                                      max_new_tokens, sampling,
                                      prefill_seconds=t1 - t0,
                                      delivered=delivered)

    def _run_loop_batched(self, run_params, ids_j, first, cache,
                          prompt_len: int, loop_keys, pad,
                          max_new_tokens: int, sampling: SamplingConfig,
                          prefill_seconds: float = 0.0,
                          delivered: Optional[tuple] = None
                          ) -> GenerateResult:
        """Run the batched verify loop off a prepared batched prefill
        state and assemble the result. ``pad`` [B] numpy is each row's
        left-pad prefix (bucket pad and/or ragged left_pad). The loop's
        re-syncs keep the batch at the MINIMAL uniform depth, so a pad
        shared by every row is slid out: the final pad is
        ``pad_b - min(pad)`` (row content still exactly
        ``prompt + max_new`` tokens) — the RETURNED pads are the ones
        reported for output stripping, never the input ones."""
        batch = ids_j.shape[0]
        t1 = time.perf_counter()
        buf = jnp.zeros((batch, self.max_seq + self.draft_len + 1),
                        jnp.int32)
        mem_h = graftmem.track(self, "buf", "spec_buffers", buf)
        buf = jax.lax.dynamic_update_slice(buf, ids_j, (0, 0))
        buf, pad_out, total, steps, _ = self._loop_b(
            run_params, first, cache, buf, jnp.int32(prompt_len),
            loop_keys, jnp.asarray(pad, dtype=jnp.int32),
            max_new=max_new_tokens, sampling=sampling)
        buf = np.asarray(jax.block_until_ready(buf))
        graftmem.release(mem_h)  # device buffer fetched; entry retires
        pad_np = np.asarray(pad_out).astype(np.int32)
        total_i = int(total)
        t2 = time.perf_counter()

        steps_i = int(steps)
        n_req, n_tok = (delivered if delivered is not None
                        else (batch, batch * max_new_tokens))
        self._update_stats(n_req, n_tok, steps_i)
        tracing.record("decode", t1, t2, spec=True, batch=batch,
                       verify_steps=steps_i,
                       emitted=batch * max_new_tokens)
        self._note_compiles()

        tokens = buf[:, :total_i]
        return GenerateResult(tokens=tokens, prompt_len=prompt_len,
                              prefill_seconds=prefill_seconds,
                              decode_seconds=t2 - t1,
                              new_tokens=max_new_tokens,
                              decode_steps=max_new_tokens - 1,
                              verify_steps=steps_i,
                              pad=pad_np if pad_np.any() else None)

    def run_loop(self, run_params, prompt_row, first, cache,
                 prompt_len: int, loop_key, max_new_tokens: int,
                 sampling: SamplingConfig,
                 prefill_seconds: float = 0.0,
                 pad=None,
                 delivered: Optional[tuple] = None) -> GenerateResult:
        """Run the compiled verify loop off a prepared prefill state and
        assemble the result — shared by ``generate`` and the prefix-cache
        front end (runtime.prefix_cache), which produces (first, cache)
        its own way. Donates ``cache``; updates speculation stats.

        ``pad`` ([1] numpy, optional) is the single source of the
        left-pad prefix: the loop's device-side mask derives from it, and
        the result reports it for output stripping — one value, no way to
        desync the two uses.

        ``delivered`` is the same served-(requests, tokens) stats
        override ``generate`` documents: a bucketing front end's SOLO
        spec round lands here (batch == 1), and its over-decode past the
        request's own max_new_tokens is shape tax exactly like the
        batched path's — without the override /healthz would count the
        bucketed step total."""
        # front ends (prefix cache, batchers) may pass a spec-flagged
        # policy through; the flag is routing metadata — normalize so
        # flagged and plain calls share one compiled loop per policy
        sampling = dataclasses.replace(sampling, spec=False)
        pad_j = jnp.asarray(pad) if pad is not None and pad.any() else None
        t1 = time.perf_counter()
        buf = jnp.zeros((self.max_seq + self.draft_len + 1,), jnp.int32)
        mem_h = graftmem.track(self, "buf", "spec_buffers", buf)
        buf = jax.lax.dynamic_update_slice(
            buf, jnp.asarray(prompt_row, dtype=jnp.int32), (0,))
        buf, steps, _ = self._loop(run_params, first[0], cache, buf,
                                   jnp.int32(prompt_len), loop_key, pad_j,
                                   max_new=max_new_tokens, sampling=sampling)
        buf = np.asarray(jax.block_until_ready(buf))
        graftmem.release(mem_h)  # device buffer fetched; entry retires
        t2 = time.perf_counter()

        steps_i = int(steps)
        n_req, n_tok = (delivered if delivered is not None
                        else (1, max_new_tokens))
        self._update_stats(n_req, n_tok, steps_i)
        tracing.record("decode", t1, t2, spec=True, batch=1,
                       verify_steps=steps_i, emitted=max_new_tokens)
        self._note_compiles()

        tokens = buf[None, :prompt_len + max_new_tokens]
        return GenerateResult(tokens=tokens, prompt_len=prompt_len,
                              prefill_seconds=prefill_seconds,
                              decode_seconds=t2 - t1,
                              new_tokens=max_new_tokens,
                              decode_steps=max_new_tokens - 1,
                              verify_steps=steps_i, pad=pad)
