"""Recompile-budget certifier: static program-space bounds per workload.

XLA compiles one program per (static shapes, static args) key, so the
compiled-program population of a serving config is a *function* of the
request stream's shape set — the runtime tests observe it after the fact
via ``jit._cache_size()`` (PR 1's compile-space asserts). This module
computes the same numbers STATICALLY: for each declared jit entry point
(``JIT_ENTRY_POINTS`` in the runtime modules, enforced by the
``undeclared-jit`` lint rule) it derives the program key a call mints,
by running the engine's REAL host-side planning code — never a
re-implementation that could drift:

- ``DecodeEngine._align_chunks`` / ``_segments`` /
  ``_eos_capped_segments`` run against a stand-in carrying only the
  fields they read (``prefill_chunk``, ``max_seq``, ``_decode_kernel``,
  ``WINDOW_BUCKET``), so the certified segment plan IS the executed one;
- static-argument identity uses the live ``SamplingConfig`` equality
  (the jit static-arg hash), with the spec engine's documented
  ``spec=False`` normalization applied where the runtime applies it.

Certified == observed is the acceptance bar: tests/test_graftcheck.py
replays the PR 1 compile-space workloads on real tiny engines and
asserts the bound equals every ``_cache_size()`` exactly — no looser,
no tighter. (One documented exception: an ``eos``-armed call may exit
early, executing a PREFIX of its certified segments — the bound is
then an upper bound, still exact when generation runs to budget.)

Program-key model per entry point:

- ``_prefill``          (batch, padded prompt_len, pad operand present)
- ``_prefill_chunked``  (batch, n_chunks)
- ``_decode_seg``       (batch, segment len, window, sampling,
                         key form [one|per-row], pad operand present);
                        a COUNTED call (the ``steps`` operand present:
                        the iteration scheduler's) keys on the longest
                        length its key buffer allows, ``"<=n"``, never
                        on the length it runs: a call cut at a row's
                        budget or at the cache's end mints no program
- ``_loop``   [spec]    (max_new, normalized sampling, pad present)
- ``_loop_b`` [spec]    (batch, max_new, normalized sampling)
- ``_seg_b``  [spec]    (width, max_verify, normalized sampling)
"""

from __future__ import annotations

import dataclasses
import types
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class EngineDesc:
    """The DecodeEngine fields that shape its program space."""

    max_seq: int
    prefill_chunk: Optional[int] = None
    kernel: bool = False          # a Pallas decode kernel is active
    window_bucket: Optional[int] = None   # None -> engine default


@dataclasses.dataclass(frozen=True)
class SpecDesc:
    draft_len: int
    ngram: int = 2


@dataclasses.dataclass(frozen=True)
class PagedDesc:
    """The KVBlockPool fields that shape its program space (the pool's
    block COUNT never keys programs — tables are traced).

    ``quantized``: the pool stores narrow blocks (``block_dtype`` set),
    so its movers are the ``_gather_q``/``_scatter_q`` family — same
    key structure (tables traced, scales ride the same program), the
    plain movers' bound drops to zero. The STORAGE dtype itself never
    keys programs either: int8 vs fp8 pools mint the same key set.
    """

    max_seq: int
    block_size: int
    quantized: bool = False

    @property
    def nbm(self) -> int:
        return self.max_seq // self.block_size


@dataclasses.dataclass(frozen=True)
class GenerateCall:
    """One ``generate()`` invocation, by shape."""

    prompt_lens: Tuple[int, ...]          # one entry per row
    max_new: int
    sampling: object = None               # SamplingConfig; None -> greedy
    per_row_keys: bool = False            # [B, 2] key stack passed
    explicit_pad: Optional[Tuple[int, ...]] = None
    eos: bool = False


def greedy_sampling():
    from llm_sharding_demo_tpu.runtime.engine import SamplingConfig
    return SamplingConfig()


def _planner(desc: EngineDesc):
    """Stand-in carrying exactly the fields the engine's host-side
    planning methods read — the methods themselves are borrowed from
    ``DecodeEngine`` unbound, so the certified plan is computed by THE
    production planning code."""
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    return types.SimpleNamespace(
        prefill_chunk=desc.prefill_chunk,
        max_seq=desc.max_seq,
        _decode_kernel="device" if desc.kernel else None,
        WINDOW_BUCKET=desc.window_bucket or DecodeEngine.WINDOW_BUCKET)


def _prepare(call: GenerateCall):
    """Mirror ``prepare_generate``'s shape outcome: [B, S] left-padded
    ids + per-row pad vector."""
    import numpy as np
    lens = call.prompt_lens
    b, s = len(lens), max(lens)
    if call.explicit_pad is not None:
        pad = np.asarray(call.explicit_pad, dtype=np.int32)
    else:
        pad = np.asarray([s - l for l in lens], dtype=np.int32)
    return np.zeros((b, s), dtype=np.int32), pad, b, s


def _sampling(call: GenerateCall):
    return call.sampling if call.sampling is not None else greedy_sampling()


def engine_call_keys(desc: EngineDesc, call: GenerateCall) -> Dict[str, set]:
    """Program keys one ``DecodeEngine.generate`` call touches."""
    from llm_sharding_demo_tpu.runtime.engine import (DecodeEngine,
                                                      _eos_capped_segments)
    ns = _planner(desc)
    ids, pad, b, s = _prepare(call)
    ids, pad, plen, chunk = DecodeEngine._align_chunks(
        ns, ids, pad, s, reserve=call.max_new)
    pad_any = bool(pad.any())
    keys: Dict[str, set] = {"_prefill": set(), "_prefill_chunked": set(),
                            "_decode_seg": set()}
    if chunk:
        keys["_prefill_chunked"].add((b, ids.shape[1] // chunk))
    else:
        keys["_prefill"].add((b, plen, pad_any))
    if call.max_new > 1:
        segs = DecodeEngine._segments(ns, plen, call.max_new)
        if call.eos:
            segs = _eos_capped_segments(segs)
        key_form = "per-row" if call.per_row_keys else "one"
        for n, window in segs:
            keys["_decode_seg"].add(
                (b, n, window, _sampling(call), key_form, pad_any))
    return keys


def spec_call_keys(desc: EngineDesc, spec: SpecDesc,
                   call: GenerateCall) -> Dict[str, set]:
    """Program keys one ``SpecDecodeEngine.generate`` call touches
    (prefill shared with the wrapped plain engine; the verify loop
    replaces the decode scan)."""
    import dataclasses as dc

    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    ns = _planner(desc)
    ids, pad, b, s = _prepare(call)
    ids, pad, plen, chunk = DecodeEngine._align_chunks(
        ns, ids, pad, s, reserve=call.max_new + spec.draft_len)
    pad_any = bool(pad.any())
    norm = dc.replace(_sampling(call), spec=False)
    keys: Dict[str, set] = {"_prefill": set(), "_prefill_chunked": set(),
                            "_loop": set(), "_loop_b": set()}
    if chunk:
        keys["_prefill_chunked"].add((b, ids.shape[1] // chunk))
    else:
        keys["_prefill"].add((b, plen, pad_any))
    if b == 1:
        keys["_loop"].add((call.max_new, norm, pad_any))
    else:
        keys["_loop_b"].add((b, call.max_new, norm))
    return keys


def paged_runner_keys(desc: EngineDesc, paged: PagedDesc,
                      call: GenerateCall) -> Dict[str, set]:
    """Program keys one ``PagedKVRunner.generate`` call touches: the
    engine's own prefill/decode keys (the paged path runs THE same
    compiled programs on gathered views — that identity is the
    byte-equality argument) plus the pool's data movers:

    - ``_gather``/``_scatter``: one program per (batch, table width) —
      tables and block ids are traced operands, so PLACEMENT never
      keys anything;
    - ``_scatter`` additionally mints one program per shared-prefix
      column offset (the narrower owned-tail view after a store hit —
      placement AND the decode loop's per-segment write-back both use
      it: shared registry blocks are immutable, so decode scatters only
      the owned columns); plain runs stay on the full-width key;
    - ``_scatter_row``/``_copy``: admission/CoW movers — unused by a
      plain generate (the iteration scheduler and prefix sharing mint
      them), so their bound here is zero;
    - a QUANTIZED pool (``paged.quantized``) runs the ``_q`` mover
      family instead — identical key structure (the scales array rides
      the same program; tables stay traced), with the plain movers
      bounded at zero.
    """
    keys = engine_call_keys(desc, call)
    b = len(call.prompt_lens)
    gather = "_gather_q" if paged.quantized else "_gather"
    scatter = "_scatter_q" if paged.quantized else "_scatter"
    row = "_scatter_row_q" if paged.quantized else "_scatter_row"
    copy = "_copy_q" if paged.quantized else "_copy"
    keys[gather] = ({(b, paged.nbm)} if call.max_new > 1 else set())
    keys[scatter] = {(b, paged.nbm)}
    keys[row] = set()
    keys[copy] = set()
    return keys


def certify_paged(desc: EngineDesc, paged: PagedDesc,
                  calls: Sequence[GenerateCall]) -> Dict[str, int]:
    """Distinct-program bound per entry point for a paged workload."""
    pools: Dict[str, set] = {}
    for call in calls:
        for name, ks in paged_runner_keys(desc, paged, call).items():
            pools.setdefault(name, set()).update(ks)
    return {name: len(ks) for name, ks in pools.items()}


def iter_spec_segment_keys(spec: SpecDesc, seg_steps: int,
                           widths: Iterable[int],
                           samplings: Iterable[object]) -> set:
    """``_seg_b`` program keys the iteration scheduler mints: one per
    (compiled width, max_verify, normalized policy) — acceptance counts
    and budgets are traced values and never key programs
    (runtime.iterbatch module docstring)."""
    import dataclasses as dc
    max_verify = max(1, seg_steps // (spec.draft_len + 1))
    return {(w, max_verify, dc.replace(s, spec=False))
            for w in widths for s in samplings}


def certify(desc: EngineDesc, calls: Sequence[GenerateCall],
            spec: Optional[SpecDesc] = None,
            spec_calls: Sequence[GenerateCall] = (),
            ) -> Dict[str, int]:
    """Distinct-program bound per entry point for a workload: the union
    of every call's key set. ``calls`` go through the plain engine,
    ``spec_calls`` through a speculative engine sharing the same
    ``desc`` (prefill programs pool, exactly as the runtime shares
    them)."""
    pools: Dict[str, set] = {}

    def merge(keysets: Dict[str, set]):
        for name, ks in keysets.items():
            pools.setdefault(name, set()).update(ks)

    for call in calls:
        merge(engine_call_keys(desc, call))
    for call in spec_calls:
        if spec is None:
            raise ValueError("spec_calls passed without a SpecDesc")
        merge(spec_call_keys(desc, spec, call))
    return {name: len(ks) for name, ks in pools.items()}


def planner_invariants(desc: EngineDesc, call: GenerateCall) -> List[str]:
    """Static sanity of the segment plan itself (CLI self-check): step
    conservation and window monotonicity/bounds. A violation means the
    planner would mint programs the budget math cannot describe."""
    from llm_sharding_demo_tpu.runtime.engine import (DecodeEngine,
                                                      _eos_capped_segments)
    ns = _planner(desc)
    ids, pad, b, s = _prepare(call)
    # validate the plan the engine would EXECUTE: segments derive from
    # the chunk-aligned prompt length, exactly as in engine_call_keys
    _, _, plen, _ = DecodeEngine._align_chunks(
        ns, ids, pad, s, reserve=call.max_new)
    problems: List[str] = []
    if call.max_new <= 1:
        return problems
    segs = DecodeEngine._segments(ns, plen, call.max_new)
    if call.eos:
        segs = _eos_capped_segments(segs)
    total = sum(n for n, _ in segs)
    if total != call.max_new - 1:
        problems.append(
            f"segment plan covers {total} steps, want {call.max_new - 1} "
            f"(prompt_len={s}, max_new={call.max_new})")
    last_w = 0
    for n, w in segs:
        if n < 1:
            problems.append(f"empty segment in plan {segs}")
        if w is not None:
            if w > desc.max_seq:
                problems.append(f"window {w} exceeds max_seq={desc.max_seq}")
            if w < last_w:
                problems.append(f"windows shrink in plan {segs}")
            last_w = w
    return problems
