"""graftnum: the tolerance oracle for APPROXIMATE compute paths.

The repo's exactness discipline is byte-equality: every exact path
(paged ≡ contiguous, chunked ≡ monolithic, spec ≡ plain, fleet ≡
single) is pinned token-for-token. Approximate paths — weight-only int8
(``ops.quant``) and bf16 decode — deliberately break that contract, and
until now their quality claims lived in prose ("logits stay f32",
"within quantization error") that nothing measured on a pinned seed.
This module is the dynamic half of **graftnum** (the static half is
``tools/graftcheck/numerics.py``, the same split as graftsan/graftlock/
graftfault): a seeded, replay-identical oracle that runs an approximate
engine against its f32/exact sibling and holds the divergence to a
DECLARED budget.

Declarations (read statically by the numerics pass):

- ``REGIMES``: the dtype-regime vocabulary. ``DecodeEngine(dtype=...)``
  validates against it via :func:`regime_of` — an off-vocabulary dtype
  is a typed :class:`GraftnumError` at construction, not a silent
  ``astype`` to something no contract covers.
- ``TOLERANCE_POLICY``: ``{path: {"logit_mse": cap,
  "top1_agreement": floor}}`` — the declared quality budget per
  approximate path. Every ``PRECISION_CONTRACT`` entry with
  ``exact: False`` must name one of these paths (rule
  ``approx-without-oracle``), so an approximate path without a measured
  budget cannot ship.

Oracle methodology (:class:`ToleranceOracle`):

- Workloads are seeded and replay-identical: the k-th prompt for a path
  is a pure function of ``(seed, path, k)`` via
  ``random.Random(f"{seed}/{path}/{k}")`` — the FaultPlan/GRAFTSCHED/
  loadgen contract, so a breach reproduces from its report.
- Comparison is TEACHER-FORCED along the exact engine's greedy
  trajectory: at each step both engines score the SAME prefix (prompt +
  the exact stream's tokens), so per-position logit MSE and greedy
  top-1 agreement are position-aligned instead of measuring the chaos
  of diverged contexts (one flipped argmax rewrites all later context —
  stream distance measures conditioning, not quantization quality).
- Logits come from each engine's OWN compiled prefill entry point
  (``_prefill``), i.e. the production quantized/bf16 compute path, not
  a re-implementation.
- A breach raises a typed :class:`GraftnumError` carrying per-position
  provenance (prompt index, step, per-position MSE, both argmaxes), so
  the failing position is debuggable, not just the aggregate.

Consumers: the int8 weight-only path (``decode.int8``), bf16-vs-f32
decode (``decode.bf16``), and the quantized KV pool (``kv.int8`` /
``kv.fp8``): per-block narrow KV storage (runtime.kv_pool
``block_dtype``, ops.kv_quant) measured by this same oracle through
:class:`_QuantizedKVProbe` — the production pool movers
(quantize-on-scatter, dequant-on-gather) inserted into the exact
engine's own compiled forward, so the measured divergence is exactly
one pool round-trip per scored position.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional, Sequence, Tuple

# The dtype-regime vocabulary. tools/graftcheck/numerics.py mirrors
# this as NUM_REGIMES (tests pin the two stay equal, like the slo
# pass's SLO_METRICS). ``fp8`` is a KV-block STORAGE regime only
# (runtime.kv_pool ``block_dtype`` / serving ``KV_POOL_DTYPE``);
# engines admit the first three via :func:`engine_regime_of`.
REGIMES = ("f32", "bf16", "int8", "fp8")

# Accepted spellings per regime (engine callers pass jnp dtypes, numpy
# dtypes, or serving-config strings; all collapse to one regime token).
# Both fp8 interchange formats collapse to one regime: the contract is
# about the quantize/dequantize boundary, and kv-block storage uses
# e4m3fn (ops.kv_quant.STORAGE_DTYPES — mantissa over exponent for
# absmax-normalized block content).
_REGIME_ALIASES = {
    "float32": "f32", "f32": "f32",
    "bfloat16": "bf16", "bf16": "bf16",
    "int8": "int8",
    "fp8": "fp8", "float8_e4m3fn": "fp8", "float8_e5m2": "fp8",
}

# Declared quality budgets per approximate path — the oracle's gate and
# the approx-without-oracle rule's registry. ``logit_mse`` is a CAP on
# the mean per-position MSE over the vocab (f32 logits, teacher-forced
# positions); ``top1_agreement`` is a FLOOR on the fraction of positions
# whose greedy argmax matches the exact path. Bounds carry ~100x
# headroom over values measured on the pinned bench seed (seed 0, demo
# model: 3.0e-7 bf16 / 1.7e-6 int8, agreement 1.0 both) so the gate
# catches step-function regressions (a lost f32 accumulator, a scale
# folded on the wrong axis — those move MSE by orders of magnitude),
# never round-off drift across hosts/BLAS builds.
TOLERANCE_POLICY = {
    # weight-only int8 decode (ops.quant) vs the f32 parity engine
    "decode.int8": {"logit_mse": 2e-4, "top1_agreement": 0.90},
    # bf16 decode (matmul operand rounding only; LN stats/softmax/
    # logits stay f32) vs the f32 parity engine
    "decode.bf16": {"logit_mse": 5e-5, "top1_agreement": 0.95},
    # quantized KV blocks (runtime.kv_pool block_dtype, ops.kv_quant):
    # the exact engine's own forward with one pool scatter/gather
    # round-trip on the KV cache per scored position
    # (_QuantizedKVProbe). Measured on seed 0 (demo model): 1.5e-8
    # int8 / 3.0e-7 fp8-e4m3fn, agreement 1.0 both — same ~100x
    # headroom convention as the decode paths. (int8 is TIGHTER than
    # fp8 here: 127 uniform levels beat e4m3's 3-bit mantissa on
    # absmax-normalized block content.)
    "kv.int8": {"logit_mse": 2e-6, "top1_agreement": 0.90},
    "kv.fp8": {"logit_mse": 3e-5, "top1_agreement": 0.90},
}

# Equivalence budgets the oracle above does not measure: paths whose two
# sides are BOTH the exact engine at one precision, but which sum in
# another order, so that what other families pin byte for byte holds
# here to a bound. Pinned by the named tests, not by ``oracle_rows``.
EQUIVALENCE_BUDGETS = {
    # a preempted row of a family with per-row state (models.gdn_moe):
    # resume-by-recompute rebuilds the linear-attention state through
    # the CHUNKED delta rule over prompt + emitted tokens (and on a
    # bucket's grid, not the uninterrupted prompt's), where the
    # uninterrupted row reached it through the one-position RECURRENCE:
    # the same sums in another order. In float32 the resumed row's
    # logits lie within ``logit_abs`` of the uninterrupted row's, for
    # logits whose spread is about 1 (measured 4e-6 on the test sizes;
    # bfloat16 arithmetic anywhere moves them by 1e-2). Every served
    # token stays the float32 reference's choice or within that noise
    # of it (tests/test_gdn_moe.py). The dense families and latent_moe
    # keep their byte pins: their recomputed cache entries are
    # per-position projections, equal bit for bit. models.window_moe's
    # window records take the same budget: a resumed row's rings are
    # rebuilt by the banded call (blocks of a window) where the
    # uninterrupted row stepped over its ring, and every layer above
    # the first sees that difference (tests/test_window_moe.py).
    "resume.row_state": {"logit_abs": 5e-5},
}


class GraftnumError(Exception):
    """Typed numerics-contract violation.

    Raised by :func:`regime_of` on an off-vocabulary dtype and by
    :class:`ToleranceOracle` on a tolerance breach; a breach carries
    ``path`` / ``metric`` / ``limit`` / ``observed`` plus ``positions``
    — the per-position provenance rows (prompt index, step, per-position
    logit MSE, exact vs approx argmax) sorted worst-first.
    """

    def __init__(self, message: str, path: Optional[str] = None,
                 metric: Optional[str] = None,
                 limit: Optional[float] = None,
                 observed: Optional[float] = None,
                 positions: Sequence[dict] = ()):
        super().__init__(message)
        self.path = path
        self.metric = metric
        self.limit = limit
        self.observed = observed
        self.positions = tuple(positions)


def regime_of(dtype) -> str:
    """Collapse a dtype spelling to its declared regime token.

    Accepts the declared regimes in any spelling (``jnp.float32`` /
    ``"bfloat16"`` / ``"int8"`` / ``"fp8"`` / numpy dtypes); anything
    else — ``"float16"``, a typo — raises a typed
    :class:`GraftnumError` instead of flowing into ``astype`` and
    silently running a precision nothing declared.
    """
    name = dtype if isinstance(dtype, str) else None
    if name is None:
        try:
            import jax.numpy as jnp
            name = jnp.dtype(dtype).name
        except TypeError:
            name = repr(dtype)
    regime = _REGIME_ALIASES.get(name)
    if regime is None:
        raise GraftnumError(
            f"dtype {dtype!r} is outside the declared regime vocabulary "
            f"{REGIMES} (spellings: float32/bfloat16/int8/fp8 and their "
            "jnp dtypes). Low-precision regimes are a declared contract "
            "(PRECISION_CONTRACT + TOLERANCE_POLICY, see "
            "docs/ARCHITECTURE.md 'Numerics discipline'); an undeclared "
            "dtype has no cast boundaries and no tolerance budget.")
    return regime


def engine_regime_of(dtype) -> str:
    """:func:`regime_of`, restricted to ENGINE compute regimes.

    ``fp8`` is in the declared vocabulary as a KV-block STORAGE regime
    (``runtime.kv_pool`` ``block_dtype`` / serving ``KV_POOL_DTYPE``) —
    no engine forward runs fp8 activations or weights, so an engine
    constructor passing it gets the same typed regime-vocabulary error
    an undeclared dtype would, pointing at the knob that does exist.
    """
    regime = regime_of(dtype)
    if regime == "fp8":
        raise GraftnumError(
            f"dtype {dtype!r} is outside the ENGINE regime vocabulary "
            f"{REGIMES[:-1]}: 'fp8' is a KV-block storage regime — set "
            "it per pool (KVBlockPool(block_dtype='fp8') / the serving "
            "KV_POOL_DTYPE knob), not as an engine compute dtype.")
    return regime


def _seeded_prompt(seed: int, path: str, k: int, vocab: int,
                   length: int) -> List[int]:
    """The k-th workload prompt: a pure function of (seed, path, k) —
    replay-identical like FaultPlan firings and loadgen arrivals."""
    rng = random.Random(f"{seed}/{path}/{k}")
    return [rng.randrange(vocab) for _ in range(length)]


class ToleranceOracle:
    """Seeded approximate-vs-exact comparison against declared budgets.

    One oracle instance fixes the workload schedule (``seed``,
    ``n_prompts``, ``prompt_len``, ``steps``); :meth:`compare` runs one
    approximate engine against its exact sibling and returns the
    JSON-able report (byte-identical across fresh runs with the same
    seed — pinned by tests), raising :class:`GraftnumError` with
    per-position provenance when the path's declared policy is
    breached. ``policy`` is injectable for fixtures; the default is the
    declared :data:`TOLERANCE_POLICY`.
    """

    def __init__(self, seed: int, policy: Optional[Dict] = None,
                 n_prompts: int = 3, prompt_len: int = 5, steps: int = 6):
        self.seed = seed
        self.policy = TOLERANCE_POLICY if policy is None else policy
        self.n_prompts = n_prompts
        self.prompt_len = prompt_len
        self.steps = steps

    def workloads(self, path: str, vocab: int) -> List[List[int]]:
        return [_seeded_prompt(self.seed, path, k, vocab, self.prompt_len)
                for k in range(self.n_prompts)]

    @staticmethod
    def _last_logits(engine, ids):
        """[1, S] ids -> [V] f32 last-position logits through the
        engine's OWN compiled prefill (the production quantized/bf16
        compute path — never a re-implementation)."""
        import jax.numpy as jnp
        import numpy as np
        logits, _cache = engine._prefill(engine._run_params(),
                                         jnp.asarray(ids, jnp.int32), None)
        return np.asarray(logits, dtype=np.float32)[0]

    def compare(self, path: str, approx_engine, exact_engine) -> dict:
        """Run ``path``'s seeded workloads through both engines and gate
        the divergence against the declared policy. Returns the report;
        raises :class:`GraftnumError` on breach."""
        import numpy as np

        if path not in self.policy:
            raise GraftnumError(
                f"approximate path {path!r} has no TOLERANCE_POLICY "
                f"entry (declared paths: {sorted(self.policy)}) — an "
                "approximate path without a declared budget cannot be "
                "gated", path=path)
        policy = self.policy[path]
        vocab = exact_engine.config.vocab_size
        positions: List[dict] = []
        for k, prompt in enumerate(self.workloads(path, vocab)):
            arr = np.asarray([prompt], dtype=np.int32)
            # teacher forcing: the exact engine's greedy stream is the
            # shared trajectory both sides score position-by-position
            forced = exact_engine.generate(arr, self.steps).tokens[
                0, len(prompt):].tolist()
            for t in range(self.steps):
                ids = [prompt + forced[:t]]
                le = self._last_logits(exact_engine, ids)
                la = self._last_logits(approx_engine, ids)
                mse = float(np.mean((la - le) ** 2))
                e_top, a_top = int(le.argmax()), int(la.argmax())
                positions.append({
                    "prompt": k, "step": t,
                    "logit_mse": round(mse, 12),
                    "exact_top1": e_top, "approx_top1": a_top,
                    "agree": e_top == a_top,
                })
        mse_mean = float(np.mean([p["logit_mse"] for p in positions]))
        agreement = float(np.mean([p["agree"] for p in positions]))
        report = {
            "path": path,
            "seed": self.seed,
            "n_prompts": self.n_prompts,
            "prompt_len": self.prompt_len,
            "steps": self.steps,
            "n_positions": len(positions),
            "logit_mse": round(mse_mean, 12),
            "top1_agreement": round(agreement, 6),
            "policy": dict(policy),
            "positions": positions,
        }
        if mse_mean > policy["logit_mse"]:
            worst = sorted(positions, key=lambda p: -p["logit_mse"])[:5]
            raise GraftnumError(
                f"path {path!r}: logit_mse {mse_mean:.3e} exceeds the "
                f"declared cap {policy['logit_mse']:.3e} (seed "
                f"{self.seed}; worst positions {worst})",
                path=path, metric="logit_mse",
                limit=policy["logit_mse"], observed=mse_mean,
                positions=worst)
        if agreement < policy["top1_agreement"]:
            worst = [p for p in positions if not p["agree"]][:5]
            raise GraftnumError(
                f"path {path!r}: top1_agreement {agreement:.4f} below "
                f"the declared floor {policy['top1_agreement']:.4f} "
                f"(seed {self.seed}; disagreeing positions {worst})",
                path=path, metric="top1_agreement",
                limit=policy["top1_agreement"], observed=agreement,
                positions=worst)
        return report


# Lease contract (tools/graftcheck sanitize pass): the probe's
# ``_prefill`` is the one scope here that moves pool blocks, and it
# brackets its movers with its own alloc/free (try/finally) — the
# lease is held for exactly the round-trip being measured.
POOL_MOVER_SCOPES = ("_QuantizedKVProbe._prefill",)


class _QuantizedKVProbe:
    """An "approximate engine" whose ONLY approximation is the
    quantized KV pool: the exact engine's own compiled programs, with
    the KV cache routed through the pool's production quantize-on-
    scatter / dequant-on-gather movers between prefilling the history
    and scoring the last position. The oracle's ``_last_logits`` call
    therefore measures exactly one pool round-trip of KV error per
    position — model weights, activations, and every other program are
    the exact engine's, so a budget breach localizes to the movers.

    Duck-types the slice of the engine surface the oracle touches:
    ``config``, ``_run_params``, ``_prefill``.
    """

    def __init__(self, engine, pool):
        if pool.block_dtype is None:
            raise GraftnumError(
                "probe pool stores full-precision blocks — the probe "
                "would measure a byte-identity, not a quantized path; "
                "construct the pool with block_dtype set")
        self.engine = engine
        self.pool = pool
        self.config = engine.config

    def _run_params(self):
        return self.engine._run_params()

    def _prefill(self, params, ids, pad):
        """[1, S] ids -> ([1, V] last-position logits, cache): prefill
        the first S-1 tokens exactly, round-trip that cache through the
        quantized pool (scatter = quantize, gather = dequantize), then
        score token S with the exact engine's cached forward on the
        dequantized working view."""
        import numpy as np

        eng, pool = self.engine, self.pool
        hist = int(ids.shape[1]) - 1
        _logits, cache = eng._prefill(params, ids[:, :-1], pad)
        row = pool.allocator.alloc(pool.nbm)
        tables = np.asarray([row], np.int32)
        try:
            pool.scatter(cache, tables)
            working = pool.gather(tables, hist)
            logits, working = eng._forward_cached(params, ids[:, -1:],
                                                  working, pad)
        finally:
            pool.allocator.free(row)
        return logits[:, -1], working


def oracle_rows(seed: int = 0, max_seq: int = 64) -> List[dict]:
    """The bench/CI consumer: run every declared TOLERANCE_POLICY path
    on the pinned demo model (fleet.harness.demo_model — the same
    geometry every harness serves) and return one compact report row
    per path (positions dropped; the oracle raises on breach, so a row
    existing means the path is inside its declared budget). A path
    whose backend prerequisite is missing (fp8 storage on an old chip)
    yields a ``{"skipped": reason}`` row — present, so the journal
    shows the gap, but unmeasured."""
    import jax.numpy as jnp

    from ..fleet.harness import demo_model
    from ..ops import kv_quant
    from ..runtime.engine import DecodeEngine
    from ..runtime.kv_pool import KVBlockPool
    from .metrics import DEFAULT_KV_BLOCK_SIZE

    cfg, params = demo_model(max_seq)
    exact = DecodeEngine(params, cfg, max_seq=max_seq)

    def kv_probe(block_dtype):
        # twice the one-row block count: headroom is irrelevant to the
        # oracle (one row at a time), this just keeps the allocator's
        # watermark out of the way
        pool = KVBlockPool.for_engine(
            exact, num_blocks=2 * (exact._cache_seq // DEFAULT_KV_BLOCK_SIZE),
            block_dtype=block_dtype)
        return _QuantizedKVProbe(exact, pool)

    engines = {
        "decode.int8": DecodeEngine(params, cfg, max_seq=max_seq,
                                    dtype="int8"),
        "decode.bf16": DecodeEngine(params, cfg, max_seq=max_seq,
                                    dtype=jnp.bfloat16),
        "kv.int8": kv_probe("int8"),
        "kv.fp8": (kv_probe("fp8") if kv_quant.fp8_supported()
                   else "backend lacks float8_e4m3fn storage "
                        "(ops.kv_quant.fp8_supported() is False)"),
    }
    oracle = ToleranceOracle(seed)
    rows = []
    for path in sorted(TOLERANCE_POLICY):
        if path not in engines:
            # a declared budget with no measuring engine here is a
            # WIRING gap, not a tolerance breach — keep the two
            # distinguishable in the bench journal (the row's error
            # names the unmapped path instead of a bare KeyError)
            raise GraftnumError(
                f"TOLERANCE_POLICY declares {path!r} but oracle_rows "
                f"builds no engine for it (covered: {sorted(engines)})"
                " — wire the new path's approximate engine in before "
                "declaring its budget", path=path)
        if isinstance(engines[path], str):
            rows.append({"path": path, "seed": seed,
                         "skipped": engines[path]})
            continue
        report = oracle.compare(path, engines[path], exact)
        rows.append({k: v for k, v in report.items() if k != "positions"})
    return rows
