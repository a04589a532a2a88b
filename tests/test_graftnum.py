"""graftnum in-suite driver (ISSUE 15 tentpole).

Three layers of pinning, mirroring the graftsan/graftlock/graftfault
drivers:

1. the REPO passes its own numerics pass — every ops/ and runtime/
   module with low-precision arithmetic declares a live
   PRECISION_CONTRACT, zero findings, non-vacuous (the strict floor
   rides tests/test_graftcheck.py);
2. deliberately broken fixtures produce EXACTLY one finding per rule
   with file:line provenance (undeclared-cast AST + traced-jaxpr forms,
   unstable-reduction, silent-downcast, approx-without-oracle);
3. the seeded tolerance oracle: int8-vs-f32 and bf16-vs-f32 goldens on
   a pinned seed, byte-identical reports across two fresh runs, and a
   breach fixture raising typed GraftnumError with per-position
   provenance.

Satellites pinned here: DecodeEngine's typed regime validation, the
serving INFERENCE_DTYPE guard, and bench_diff's numerics-metric
classification (top1_agreement higher-better, logit_mse lower-better).
"""

import json
import os
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
from llm_sharding_demo_tpu.utils import graftnum
from llm_sharding_demo_tpu.utils.graftnum import (GraftnumError,
                                                  ToleranceOracle,
                                                  regime_of)

from tools.graftcheck import numerics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = gpt2.GPT2Config(vocab_size=211, n_positions=64, n_embd=32,
                      n_layer=2, n_head=4)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(CFG, jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def exact_engine(params):
    return DecodeEngine(params, CFG, max_seq=32)


# -- 1. the repo passes its own numerics pass --------------------------------


def test_repo_numerics_clean_and_nonvacuous():
    findings, summary = numerics.run_numerics(REPO)
    assert findings == [], "\n".join(f.format() for f in findings)
    # acceptance floor (ISSUE 15): >= 10 checks, >= 3 modules with live
    # PRECISION_CONTRACTs — the pass must not be vacuous
    assert summary["numerics_checks"] >= 10
    live = {m for m, n in summary["numerics_contracts"].items() if n >= 1}
    assert len(live) >= 3, summary["numerics_contracts"]
    for rel in ("llm_sharding_demo_tpu/ops/quant.py",
                "llm_sharding_demo_tpu/ops/layers.py",
                "llm_sharding_demo_tpu/runtime/engine.py"):
        assert summary["numerics_contracts"].get(rel, 0) >= 1, (
            f"{rel}: PRECISION_CONTRACT resolves to no live entries")
    assert summary["vacuous"] == []


def test_regime_vocabulary_sync():
    """The pass's regime vocabulary mirrors graftnum's (the SLO_METRICS
    / WATCH_SIGNALS pattern: one declared vocabulary, pinned equal)."""
    assert numerics.NUM_REGIMES == graftnum.REGIMES
    assert set(numerics.ORACLE_METRICS) == \
        {"logit_mse", "top1_agreement"}
    # every declared budget speaks exactly the oracle's metrics
    for path, spec in graftnum.TOLERANCE_POLICY.items():
        assert set(spec) == set(numerics.ORACLE_METRICS), path


# -- 2. rule fixtures: exactly one finding each, with file:line --------------


def _fixture(tmp_path, relpath: str, source: str, **kw):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    kw.setdefault("policy", {})
    kw.setdefault("traced", [])
    return numerics.run_numerics(str(tmp_path), paths=[str(p)], **kw)


def test_fixture_undeclared_cast_ast(tmp_path):
    """An .astype to a dtype outside the entry's declared boundaries is
    exactly one undeclared-cast finding at the cast line."""
    findings, _ = _fixture(tmp_path, "ops/fix.py", """\
        import jax.numpy as jnp

        PRECISION_CONTRACT = {
            "f": {"regime": "carried", "exact": True, "casts": ("f32",)},
        }

        def f(x):
            y = x.astype(jnp.float32)
            return y.astype(jnp.float16)
        """)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "undeclared-cast"
    assert f.path == "ops/fix.py" and f.line == 9
    assert f.scope == "f" and "'f16'" in f.message


def test_fixture_low_precision_module_without_contract(tmp_path):
    """A runtime/ module touching sub-f32 dtypes with no
    PRECISION_CONTRACT at all is a finding (the trigger that forced
    quant.py/engine.py to declare)."""
    findings, _ = _fixture(tmp_path, "runtime/fix.py", """\
        import jax.numpy as jnp

        def prep(params):
            return params.astype(jnp.bfloat16)
        """)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "undeclared-cast" and f.scope == "<module>"
    assert "no PRECISION_CONTRACT" in f.message and f.line == 4


def test_fixture_name_bound_dtype_string_cannot_evade_trigger(tmp_path):
    """The trigger sees EXACT low-precision string constants anywhere —
    a name-bound spelling (`KV_DTYPE = "int8"` + astype(KV_DTYPE)) is
    caught, while prose docstrings mentioning int8 are not (exact
    equality, never substring)."""
    findings, _ = _fixture(tmp_path, "ops/kvq.py", """\
        '''A module whose docstring talks about int8 at length.'''

        KV_DTYPE = "int8"

        def quantize(cache):
            return cache.astype(KV_DTYPE)
        """)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "undeclared-cast" and f.scope == "<module>"
    assert f.line == 3  # the name-bound constant, not the docstring


def test_fixture_stale_contract_entry(tmp_path):
    findings, summary = _fixture(tmp_path, "ops/fix.py", """\
        PRECISION_CONTRACT = {
            "gone": {"regime": "f32", "exact": True, "casts": ()},
        }
        """)
    assert [f.rule for f in findings] == ["undeclared-cast"]
    assert "stale" in findings[0].message
    # a contract resolving to zero live entries is vacuous (strict fails)
    assert summary["vacuous"] == ["ops/fix.py"]


def test_fixture_unstable_reduction(tmp_path):
    """A traced dot_general over bf16 avals without f32 accumulation is
    exactly one unstable-reduction finding, even though the entry
    DECLARES the f32 discipline — the declaration must be true in the
    traced program."""
    p = tmp_path / "ops" / "red.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent("""\
        PRECISION_CONTRACT = {
            "bad_dot": {"regime": "carried", "exact": True,
                        "accumulate": "f32", "casts": ()},
        }

        def bad_dot(a, b):
            ...
        """))

    def bad_dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))

    traced = [numerics.TracedEntry("ops/red.py", "bad_dot", lambda: (
        bad_dot, (jnp.zeros((2, 8), jnp.bfloat16),
                  jnp.zeros((8, 4), jnp.bfloat16))))]
    findings, _ = numerics.run_numerics(str(tmp_path), paths=[str(p)],
                                        traced=traced, policy={})
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "unstable-reduction"
    assert f.path == "ops/red.py" and f.line == 6  # the def line
    assert "dot_general" in f.message and "bfloat16" in f.message


def test_fixture_unstable_reduction_sees_fp8(tmp_path):
    """fp8 avals are LOW precision to the traced rules (width 8), not
    unknown-defaulting-to-32: a float8 dot without f32 accumulation is
    a finding — the quantized-KV landing pad cannot trace clean by
    being off the width map."""
    p = tmp_path / "ops" / "red8.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent("""\
        PRECISION_CONTRACT = {
            "fp8_dot": {"regime": "carried", "exact": True,
                        "accumulate": "f32", "casts": ()},
        }

        def fp8_dot(a, b):
            ...
        """))

    def fp8_dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))

    traced = [numerics.TracedEntry("ops/red8.py", "fp8_dot", lambda: (
        fp8_dot, (jnp.zeros((2, 8), jnp.float8_e4m3fn),
                  jnp.zeros((8, 4), jnp.float8_e4m3fn))))]
    findings, _ = numerics.run_numerics(str(tmp_path), paths=[str(p)],
                                        traced=traced, policy={})
    assert [f.rule for f in findings] == ["unstable-reduction"]
    assert "float8_e4m3fn" in findings[0].message


def test_fixture_silent_downcast(tmp_path):
    """A traced entry narrowing f32 -> bf16 at its output boundary,
    with the interior cast SANCTIONED, is exactly one silent-downcast
    finding: the regime declaration covers the boundary, not just the
    body."""
    p = tmp_path / "ops" / "down.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent("""\
        PRECISION_CONTRACT = {
            "narrow": {"regime": "f32", "exact": True,
                       "casts": ("bf16",)},
        }

        def narrow(x):
            ...
        """))

    def narrow(x):
        return (x * 2).astype(jnp.bfloat16)

    traced = [numerics.TracedEntry("ops/down.py", "narrow", lambda: (
        narrow, (jnp.zeros((2, 8), jnp.float32),)))]
    findings, _ = numerics.run_numerics(str(tmp_path), paths=[str(p)],
                                        traced=traced, policy={})
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "silent-downcast"
    assert f.path == "ops/down.py" and f.line == 6
    assert "bfloat16" in f.message and "'f32'" in f.message


def test_fixture_approx_without_oracle(tmp_path):
    findings, _ = _fixture(tmp_path, "ops/apx.py", """\
        PRECISION_CONTRACT = {
            "q": {"regime": "int8", "exact": False, "casts": ()},
        }

        def q(x):
            return x
        """)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "approx-without-oracle"
    assert f.path == "ops/apx.py" and f.line == 1 and f.scope == "q"
    assert "exact: False" in f.message


def test_fixture_exact_pin_claiming_approx_path(tmp_path):
    """The other direction of the rule: a byte-equality (exact: True)
    declaration must not claim a tolerance-gated path."""
    findings, _ = _fixture(tmp_path, "ops/apx.py", """\
        PRECISION_CONTRACT = {
            "q": {"regime": "f32", "exact": True, "casts": (),
                  "oracle": "decode.int8"},
        }

        def q(x):
            return x
        """, policy={"decode.int8": {"logit_mse": 1.0,
                                     "top1_agreement": 0.5}})
    msgs = [f for f in findings if f.rule == "approx-without-oracle"]
    # the exact/oracle contradiction plus the now-unreferenced policy
    # path (no approx entry routes to it) — both are real findings
    assert len(msgs) == 2
    assert any("must not claim" in f.message and f.scope == "q"
               for f in msgs)
    assert any("no PRECISION_CONTRACT entry maps to it" in f.message
               for f in msgs)


def test_fixture_unknown_oracle_path_and_malformed_regime(tmp_path):
    findings, _ = _fixture(tmp_path, "ops/apx.py", """\
        PRECISION_CONTRACT = {
            "q": {"regime": "int8", "exact": False, "casts": (),
                  "oracle": "decode.fp8"},
            "r": {"regime": "tf32", "exact": True, "casts": ()},
        }

        def q(x):
            return x

        def r(x):
            return x
        """)
    rules = sorted(f.rule for f in findings)
    assert rules == ["approx-without-oracle", "undeclared-cast"]
    by_rule = {f.rule: f for f in findings}
    assert "'decode.fp8'" in by_rule["approx-without-oracle"].message
    assert "'tf32'" in by_rule["undeclared-cast"].message


# -- 3. the tolerance oracle -------------------------------------------------


def _int8_engine(params):
    return DecodeEngine(params, CFG, max_seq=32, dtype="int8")


def test_oracle_int8_golden_replay_identical(params, exact_engine):
    """THE acceptance golden: the seeded int8-vs-f32 report is inside
    the declared budget and byte-identical across two FRESH oracle +
    engine instances (the FaultPlan/GRAFTSCHED replay contract)."""
    reports = []
    for _ in range(2):
        oracle = ToleranceOracle(seed=7)
        report = oracle.compare("decode.int8", _int8_engine(params),
                                DecodeEngine(params, CFG, max_seq=32))
        reports.append(report)
    assert json.dumps(reports[0], sort_keys=True) == \
        json.dumps(reports[1], sort_keys=True)
    r = reports[0]
    assert r["seed"] == 7 and r["path"] == "decode.int8"
    assert r["n_positions"] == len(r["positions"]) > 0
    assert 0.0 <= r["top1_agreement"] <= 1.0
    assert r["logit_mse"] >= 0.0
    assert r["logit_mse"] <= \
        graftnum.TOLERANCE_POLICY["decode.int8"]["logit_mse"]
    # per-position provenance rows are complete
    for p in r["positions"]:
        assert set(p) == {"prompt", "step", "logit_mse", "exact_top1",
                          "approx_top1", "agree"}


def test_oracle_bf16_within_policy(params, exact_engine):
    report = ToleranceOracle(seed=7).compare(
        "decode.bf16",
        DecodeEngine(params, CFG, max_seq=32, dtype=jnp.bfloat16),
        exact_engine)
    assert report["top1_agreement"] >= \
        graftnum.TOLERANCE_POLICY["decode.bf16"]["top1_agreement"]


def test_oracle_workloads_are_pure_functions_of_seed_path_k():
    a = ToleranceOracle(seed=3).workloads("decode.int8", vocab=97)
    b = ToleranceOracle(seed=3).workloads("decode.int8", vocab=97)
    c = ToleranceOracle(seed=4).workloads("decode.int8", vocab=97)
    d = ToleranceOracle(seed=3).workloads("decode.bf16", vocab=97)
    assert a == b            # replay-identical
    assert a != c            # seed changes the schedule
    assert a != d            # path changes the schedule
    assert all(0 <= t < 97 for row in a for t in row)


def test_oracle_breach_raises_typed_provenance(params, exact_engine):
    """An impossibly tight injected budget breaches: typed
    GraftnumError carrying path/metric/limit/observed and per-position
    provenance rows (worst-first)."""
    oracle = ToleranceOracle(
        seed=7, policy={"decode.int8": {"logit_mse": 1e-30,
                                        "top1_agreement": 1.0}})
    with pytest.raises(GraftnumError) as ei:
        oracle.compare("decode.int8", _int8_engine(params), exact_engine)
    e = ei.value
    assert e.path == "decode.int8" and e.metric == "logit_mse"
    assert e.limit == 1e-30 and e.observed > e.limit
    assert len(e.positions) > 0
    p = e.positions[0]
    assert {"prompt", "step", "logit_mse"} <= set(p)
    # worst-first ordering
    mses = [q["logit_mse"] for q in e.positions]
    assert mses == sorted(mses, reverse=True)


def test_oracle_unknown_path_is_typed(params, exact_engine):
    with pytest.raises(GraftnumError) as ei:
        ToleranceOracle(seed=0).compare("decode.fp8",
                                        exact_engine, exact_engine)
    assert "TOLERANCE_POLICY" in str(ei.value)


# -- satellites --------------------------------------------------------------


def test_engine_dtype_regime_vocabulary(params):
    """DecodeEngine(dtype=...) validates against the DECLARED regime
    vocabulary with a typed error — arbitrary strings and undeclared
    dtypes no longer flow into astype."""
    for dtype, regime in ((jnp.float32, "f32"), ("float32", "f32"),
                          (jnp.bfloat16, "bf16"), ("bfloat16", "bf16"),
                          ("int8", "int8"), (jnp.int8, "int8")):
        assert regime_of(dtype) == regime
    eng = DecodeEngine(params, CFG, max_seq=32, dtype="bfloat16")
    assert eng.regime == "bf16"
    for bad in ("float16", "fp8", "bogus", jnp.float64, object()):
        with pytest.raises(GraftnumError) as ei:
            DecodeEngine(params, CFG, max_seq=32, dtype=bad)
        assert "regime vocabulary" in str(ei.value)


def test_parallel_runners_share_the_regime_gate(params):
    """The sibling engine constructors in parallel/ flow through the
    SAME graftnum.regime_of mechanism — an off-vocabulary dtype is a
    typed reject there too, not a silent astype."""
    from llm_sharding_demo_tpu.parallel.pipeline import PipelineRunner
    with pytest.raises(GraftnumError, match="regime vocabulary"):
        PipelineRunner(params, CFG, boundaries=[1], max_seq=32,
                       dtype="float16")
    # int8 keeps its own targeted refusal (quantize, don't truncate),
    # which fires AFTER the vocabulary gate
    with pytest.raises(ValueError, match="quantization"):
        PipelineRunner(params, CFG, boundaries=[1], max_seq=32,
                       dtype="int8")
    from llm_sharding_demo_tpu.parallel.ppdecode import PipelinedDecoder
    from llm_sharding_demo_tpu.parallel.spmd import make_mesh
    mesh = make_mesh({"pp": 2}, jax.devices()[:2])
    with pytest.raises(GraftnumError, match="regime vocabulary"):
        PipelinedDecoder(params, CFG, mesh, max_seq=32, dtype="float16")


def test_oracle_rows_unmapped_policy_path_is_typed(monkeypatch):
    """A declared budget with no measuring engine is a typed WIRING
    error naming the path — distinguishable from a tolerance breach in
    the bench journal (never a bare KeyError)."""
    monkeypatch.setattr(
        graftnum, "TOLERANCE_POLICY",
        {"kv.int4": {"logit_mse": 1e-3, "top1_agreement": 0.9}})
    with pytest.raises(GraftnumError, match="wire the new path"):
        graftnum.oracle_rows(seed=0, max_seq=32)


def test_serving_inference_dtype_guard_pinned():
    """The serving config guard rejects off-vocabulary INFERENCE_DTYPE
    at parse time — the fleet never boots into an undeclared regime."""
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    with pytest.raises(ValueError, match="INFERENCE_DTYPE"):
        ServingConfig(inference_dtype="fp8")
    with pytest.raises(ValueError, match="INFERENCE_DTYPE"):
        ServingConfig(inference_dtype="float16")
    # the accepted vocabulary is exactly the declared regimes' spellings
    for ok in ("float32", "bfloat16", "int8"):
        assert ServingConfig(inference_dtype=ok).inference_dtype == ok


def test_bench_diff_classifies_oracle_metrics():
    """Classification pinned (ISSUE 15 satellite): agreement gates
    higher-better, MSE lower-better — flattened per-path names
    included."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import bench_diff
    finally:
        sys.path.remove(os.path.join(REPO, "tools"))
    assert bench_diff.classify("top1_agreement") == "higher"
    assert bench_diff.classify("int8_top1_agreement") == "higher"
    assert bench_diff.classify("bf16_top1_agreement") == "higher"
    assert bench_diff.classify("logit_mse") == "lower"
    assert bench_diff.classify("int8_logit_mse") == "lower"
    assert bench_diff.classify("bf16_logit_mse") == "lower"


def test_quant_matmul_bf16_accumulates_f32():
    """Regression pin for the real finding the pass surfaced: the XLA
    fallback now accumulates bf16-activation dots in f32 (one final
    rounding) instead of rounding at bf16 through the dot AND the scale
    multiply. The result must match the f32-reference computation after
    a single bf16 rounding, and the f32 path stays byte-identical."""
    from llm_sharding_demo_tpu.ops import quant
    rng = np.random.default_rng(12)
    w = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    qleaf = quant.quantize_array(w, jnp.bfloat16)
    x32 = jnp.asarray(rng.normal(size=(3, 64)).astype(np.float32))
    x16 = x32.astype(jnp.bfloat16)
    got = quant.quant_matmul(x16, qleaf)
    assert got.dtype == jnp.bfloat16
    want = (jax.lax.dot_general(
        x16.astype(jnp.float32), qleaf.q.astype(jnp.float32),
        (((1,), (0,)), ((), ())))
        * qleaf.scale.astype(jnp.float32)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # f32 activations: the fix is a bit-for-bit no-op
    qleaf32 = quant.quantize_array(w, jnp.float32)
    a = quant.quant_matmul(x32, qleaf32)
    b = x32 @ quant.dequantize_array(qleaf32, jnp.float32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-5, rtol=1e-5)


def test_oracle_rows_bench_consumer():
    """The bench row's library entry point: one compact row per
    declared policy path, positions dropped, inside budget (it raises
    otherwise)."""
    rows = graftnum.oracle_rows(seed=0, max_seq=32)
    assert [r["path"] for r in rows] == sorted(graftnum.TOLERANCE_POLICY)
    for r in rows:
        assert "positions" not in r
        assert r["seed"] == 0
        if "skipped" in r:
            # backend-prerequisite skip (fp8 storage): a reasoned row,
            # never a silent hole in the journal
            assert r["skipped"]
            continue
        assert r["n_positions"] > 0
