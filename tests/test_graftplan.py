"""graftplan (tools/graftcheck/costmodel): cost model + planner pins.

Four layers of claims:

1. **Derived sharding == hand-tuned sharding**: ``derive_pspecs`` from
   each family's ``SHARDING_DESCRIPTOR`` reproduces the hand-written
   ``parallel.spmd`` PartitionSpec trees exactly, for all three
   families — the planner's "zero hand-written PartitionSpecs" claim.
2. **Golden cost numbers, pinned exactly**: collective comm bytes for
   the REAL ppermute stage-ring program at known widths/stage counts
   (hand arithmetic in the comments), and HBM footprint numbers equal
   to the ``nbytes`` of the actual CPU buffers (params, contiguous KV,
   the paged pool) — not approximately, exactly.
3. **Program counts certified == observed**: every exact-marked scored
   plan row's program count equals the real engine/pool jit cache
   sizes after replaying the traffic (the recompile.certify guarantee,
   extended to planner rows).
4. **Planner rankings**: GPT-2 on one device with single-stream
   traffic reproduces the hand-tuned serving default as the top plan;
   llama (GQA) on a tp mesh and MoE on an ep mesh get verifier-clean
   sharded plans; illegal compositions are rejected with diagnostics
   (never scored); AUTO_PLAN=1 resolves and reports through serving.
"""

import json

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from llm_sharding_demo_tpu.models import gpt2, llama, moe
from llm_sharding_demo_tpu.parallel import spmd
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine

from tools.graftcheck import cli, costmodel as CM, registry, semantic
from tools.graftcheck import recompile as R

GPT2_CFG = registry.planner_families()["gpt2-tiny"][1]
LLAMA_CFG = registry.planner_families()["llama-gqa"][1]
MOE_CFG = registry.planner_families()["moe-tiny"][1]


def _spec_items(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _spec_items(tree[k], f"{prefix}.{k}" if prefix else k)
    else:
        yield prefix, tree


def _assert_spec_trees_equal(derived, hand):
    d, h = dict(_spec_items(derived)), dict(_spec_items(hand))
    assert set(d) == set(h)
    for path in d:
        # compare normalized to tuples with trailing Nones stripped:
        # P(None, 'tp') and P(None, 'tp', None) shard identically
        def norm(spec):
            t = tuple(spec)
            while t and t[-1] is None:
                t = t[:-1]
            return t
        assert norm(d[path]) == norm(h[path]), (
            f"{path}: derived {d[path]} != hand-written {h[path]}")


# -- 1. derived sharding == hand-tuned spmd layouts --------------------------


def test_derived_pspecs_match_hand_written_gpt2():
    # build the hand-written tree against a tp-present mesh name set;
    # derive against {"tp": 2} (sizes only gate divisibility, and the
    # hand-written layout shards by axis PRESENCE)
    hand = spmd.param_pspecs(
        type("M", (), {"axis_names": ("tp",)})())
    derived = CM.derive_pspecs(gpt2, GPT2_CFG, {"tp": 2})
    _assert_spec_trees_equal(derived, hand)


def test_derived_pspecs_match_hand_written_llama():
    hand = spmd.llama_param_pspecs(
        type("M", (), {"axis_names": ("tp",)})())
    derived = CM.derive_pspecs(llama, LLAMA_CFG, {"tp": 2})
    _assert_spec_trees_equal(derived, hand)


def test_derived_pspecs_match_hand_written_moe():
    hand = spmd.moe_param_pspecs(
        type("M", (), {"axis_names": ("ep", "tp")})())
    derived = CM.derive_pspecs(moe, MOE_CFG, {"ep": 2, "tp": 2})
    _assert_spec_trees_equal(derived, hand)


def test_derived_pspecs_are_verifier_clean():
    for module, config, axes in (
            (gpt2, GPT2_CFG, {"tp": 2}),
            (llama, LLAMA_CFG, {"tp": 2}),
            (moe, MOE_CFG, {"ep": 2, "tp": 2})):
        specs = CM.derive_pspecs(module, config, axes)
        got = semantic.check_pspec_tree(
            specs, CM.param_avals(module, config), axes, "derived")
        assert got == [], [f.message for f in got]


def test_descriptor_missing_is_an_error():
    class NoDesc:
        __name__ = "nodesc"
    with pytest.raises(ValueError, match="SHARDING_DESCRIPTOR"):
        CM.derive_pspecs(NoDesc, GPT2_CFG, {"tp": 2})


# -- 2a. golden comm bytes (exact, hand-computed) ----------------------------


def test_ppermute_ring_comm_bytes_golden():
    """Comm bytes of the REAL PipelinedDecoder decode step (gpt2-tiny
    registry stand-in: D=8, fp32), by the documented formulas.

    pp=2, B=1: hidden aval [1, 1, 8] fp32 = 32 bytes.
      - tick scan runs 2 ticks; the ring has 1 pair -> ppermute moves
        32 x 1 = 32 bytes/tick, 64 total;
      - the final psum of the [1, 1, 8] output: 2 x 32 x (2-1) = 64.
      => 128 bytes per decoded token.
    """
    assert CM.pp_decode_comm_bytes(2, batch=1) == 128


def test_ppermute_ring_comm_bytes_golden_wider():
    """pp=4, B=2: hidden aval [2, 1, 8] fp32 = 64 bytes.
      - 4 ticks x 3 ring pairs x 64 bytes = 768;
      - final psum: 2 x 64 x (4-1) = 384.
      => 1152 bytes per decoded token."""
    assert CM.pp_decode_comm_bytes(4, batch=2) == 1152


def test_tp_megatron_comm_bytes_golden():
    """llama-gqa (D=16, L=4) over tp=2, B=1: each block psums the
    [1, 1, 16] fp32 activations twice (attention row projection + MLP
    down projection): 2 psums x 4 layers x (2 x 64 x (2-1)) = 1024."""
    assert CM.tp_decode_comm_bytes(LLAMA_CFG, 1, 2) == 1024


def test_kvp_partial_softmax_comm_bytes_golden():
    """llama-gqa (Hq=4, hd=4, L=4) over kvp=2, B=1: each device attends
    against its resident kv shard, then the partial-softmax combine
    crosses the kvp axis once per block — an all_gather of the
    un-normalized ``o [1, 4, 4]`` fp32 (64 B) plus the per-head
    log-sum-exp ``lse [1, 4]`` fp32 (16 B). all_gather over an n-wide
    axis moves b x n x (n-1) bytes: (64 + 16) x 2 x 1 = 160 B/layer,
    x 4 layers => 640 bytes per decoded token."""
    assert CM.kvp_decode_comm_bytes(LLAMA_CFG, 1, 2) == 640


def test_kvp_tp_comm_bytes_compose_additively():
    """kvp x tp: the tp psums and the kvp gathers both cross the ICI —
    640 (kvp partial-softmax combine) + 1024 (the two Megatron psums
    per block, pinned above) = 1664."""
    assert (CM.kvp_decode_comm_bytes(LLAMA_CFG, 1, 2)
            + CM.tp_decode_comm_bytes(LLAMA_CFG, 1, 2)) == 1664


def test_collective_walker_handles_scan_trip_counts():
    """A hand-built program: psum of a [4] fp32 (16 bytes) inside a
    3-trip scan over a 2-wide axis -> 3 x (2 x 16 x 1) = 96 bytes."""
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((2,), ("tp",))

    def per_device(x):
        def body(c, _):
            return jax.lax.psum(c, "tp") * 0 + c, None
        y, _ = jax.lax.scan(body, x, None, length=3)
        return y

    fn = jax.shard_map(per_device, mesh=mesh, in_specs=(P(),), out_specs=P(),
                       axis_names={"tp"})
    aval = jax.ShapeDtypeStruct((4,), jnp.float32)
    assert CM.comm_bytes_program(fn, (aval,), {"tp": 2}) == 96


# -- 2b. HBM footprint == actual CPU buffer nbytes (exact) -------------------


def test_param_bytes_equal_real_buffer_nbytes():
    params = gpt2.init_params(GPT2_CFG, jax.random.PRNGKey(0))
    real = sum(np.asarray(x).nbytes
               for x in jax.tree_util.tree_leaves(params))
    assert CM.tree_bytes(CM.param_avals(gpt2, GPT2_CFG)) == real


def test_contiguous_kv_bytes_equal_real_cache_nbytes():
    cache = gpt2.make_cache(GPT2_CFG, batch=3, max_seq=32)
    real = np.asarray(cache.k).nbytes + np.asarray(cache.v).nbytes
    assert CM.kv_cache_bytes(GPT2_CFG, 3, 32) == real
    # and the GQA family (kv-head-width cache)
    lcache = llama.make_cache(LLAMA_CFG, batch=2, max_seq=64)
    lreal = np.asarray(lcache.k).nbytes + np.asarray(lcache.v).nbytes
    assert CM.kv_cache_bytes(LLAMA_CFG, 2, 64) == lreal


def test_pool_bytes_equal_real_pool_nbytes():
    from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
    pool = KVBlockPool(GPT2_CFG.n_layer, 16, GPT2_CFG.n_head, 8,
                       GPT2_CFG.head_dim, max_seq=64)
    assert CM.kv_pool_bytes(GPT2_CFG, 16, 8) == np.asarray(pool.data).nbytes


def test_kvp_pool_bytes_per_device_is_exact_half():
    """The kvp row's HBM claim against the REAL pool buffer: the
    llama-gqa paged pool's kv-head plane sharded over kvp=2 puts
    exactly ``pool.data.nbytes // 2`` on each device — whole kv heads,
    no remainder (n_kv_head=2 divides)."""
    from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
    pool = KVBlockPool(LLAMA_CFG.n_layer, 16, LLAMA_CFG.n_kv_head, 16,
                       LLAMA_CFG.head_dim, max_seq=64)
    total = CM.kv_pool_bytes(LLAMA_CFG, 16, 16)
    assert total == np.asarray(pool.data).nbytes
    assert total % 2 == 0
    payload = CM.plan(llama, LLAMA_CFG, {"kvp": 2}, max_seq=64,
                      kv_pool_blocks=16, kv_block_size=16)
    kvp_rows = [r for r in payload["plan"]
                if r["config"]["topology"] == "kvp"]
    assert kvp_rows and all(r["ok"] for r in kvp_rows)
    assert kvp_rows[0]["kv_bytes_per_device"] == total // 2


def test_sharded_param_bytes_split_by_axis_size():
    avals = CM.param_avals(llama, LLAMA_CFG)
    total = CM.tree_bytes(avals)
    specs = CM.derive_pspecs(llama, LLAMA_CFG, {"tp": 2})
    per_dev = CM.per_device_param_bytes(avals, specs, {"tp": 2})
    # strictly less than replicated, more than total/2 (embeddings,
    # norms, and the untied head stay replicated)
    assert total / 2 < per_dev < total


# -- 3. program counts: certified == observed --------------------------------


TRAFFIC = (CM.TrafficRow(8, 4, 1), CM.TrafficRow(8, 4, 2))


def _fresh_engine(max_seq=64):
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=256, n_embd=32,
                          n_layer=2, n_head=4)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_every_exact_plan_row_program_count_equals_observed():
    """Acceptance pin: for the GPT-2 workloads, every scored plan row
    marked programs_exact has its count certified EQUAL to the observed
    jit cache sizes after replaying that row's traffic on a real
    engine (paged rows replay on a real pool-backed runner)."""
    from llm_sharding_demo_tpu.runtime.kv_pool import (KVBlockPool,
                                                       PagedKVRunner)
    cfg, params = _fresh_engine()
    payload = CM.plan(gpt2, cfg, {}, max_seq=64, traffic=TRAFFIC,
                      max_batch_cap=2, kv_pool_blocks=16, kv_block_size=8)
    rows = [r for r in payload["plan"] if r["ok"] and r["programs_exact"]]
    assert rows, "no exact rows scored"
    rng = np.random.default_rng(7)
    for row in rows:
        c = row["config"]
        eng = DecodeEngine(params, cfg, max_seq=64)
        runner = eng
        pool = None
        if c["kv_pool_blocks"]:
            pool = KVBlockPool.for_engine(eng, num_blocks=c["kv_pool_blocks"],
                                          block_size=c["kv_block_size"])
            runner = PagedKVRunner(eng, pool)
        for call in CM.traffic_calls(TRAFFIC, c["max_batch"]):
            prompts = np.stack([rng.integers(0, 211, size=(n,))
                                for n in call.prompt_lens])
            runner.generate(prompts if len(call.prompt_lens) > 1
                            else prompts[0], call.max_new)
        observed = {
            "_prefill": eng._prefill._cache_size(),
            "_prefill_chunked": eng._prefill_chunked._cache_size(),
            "_decode_seg": eng._decode_seg._cache_size(),
        }
        if pool is not None:
            observed.update({
                "_gather": pool._gather._cache_size(),
                "_scatter": pool._scatter._cache_size(),
                "_scatter_row": pool._scatter_row._cache_size(),
                "_copy": pool._copy._cache_size(),
            })
        assert row["programs"] == observed, (
            f"{row['label']}: certified {row['programs']} != observed "
            f"{observed}")


# -- 4. planner rankings -----------------------------------------------------


def test_gpt2_single_device_reproduces_hand_tuned_default():
    """The acceptance criterion: GPT-2 on the default 1-axis mesh (one
    device, no sharding axes) with single-stream traffic ranks the
    hand-tuned serving default first — admission mode, MAX_BATCH=1, no
    paged pool, no sharded topology (exactly ServingConfig's
    defaults)."""
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    payload = CM.plan(gpt2, GPT2_CFG, {}, max_seq=64,
                      max_batch_cap=8, kv_pool_blocks=16)
    chosen = payload["chosen"]
    assert chosen is not None
    dflt = ServingConfig()
    assert chosen["config"]["topology"] == "single"
    assert chosen["config"]["batch_mode"] == dflt.batch_mode
    assert chosen["config"]["max_batch"] == dflt.max_batch == 1
    assert chosen["config"]["kv_pool_blocks"] == dflt.kv_pool_blocks == 0
    env = chosen["serving_env"]
    assert (env["PP_DECODE"], env["TP_DECODE"], env["EP_DECODE"]) == \
        ("0", "0", "0")


def test_gpt2_batched_traffic_chooses_batching():
    """Under 8-way concurrent traffic the weight stream amortizes over
    the batch, so a batched candidate must outrank MAX_BATCH=1."""
    payload = CM.plan(gpt2, GPT2_CFG, {}, max_seq=64,
                      traffic=CM.parse_traffic("8/8x8"), max_batch_cap=8)
    assert payload["chosen"]["config"]["max_batch"] == 8


def test_llama_gqa_tp_mesh_gets_verifier_clean_sharded_plan():
    """Acceptance: a valid, verifier-clean plan for the llama GQA
    family on a tp mesh with zero hand-written PartitionSpecs — the tp
    candidate derives its sharding from the descriptor and survives
    every gate; with single-stream traffic the halved per-device
    weight stream beats the replicated engine."""
    payload = CM.plan(llama, LLAMA_CFG, {"tp": 2}, max_seq=64)
    chosen = payload["chosen"]
    assert chosen["config"]["topology"] == "tp"
    assert chosen["findings"] == []
    tp_rows = [r for r in payload["plan"]
               if r["config"]["topology"] == "tp"]
    assert tp_rows and all(r["ok"] for r in tp_rows)


def test_moe_ep_mesh_gets_verifier_clean_expert_plan():
    payload = CM.plan(moe, MOE_CFG, {"ep": 2}, max_seq=64)
    chosen = payload["chosen"]
    assert chosen["config"]["topology"] == "ep"
    assert chosen["findings"] == []
    assert chosen["comm_bytes_per_token"] > 0  # the all-to-alls priced


def test_gqa_head_ratio_gates_indivisible_tp():
    """The GQA head-ratio descriptor at work: the families() llama
    stand-in has n_kv_head=1, which a 2-wide tp axis cannot divide —
    the tp candidate must be REJECTED with the engine's own guard
    language, never scored."""
    _, tiny = registry.families()["llama-tiny"]
    payload = CM.plan(llama, tiny, {"tp": 2}, max_seq=64)
    tp_rows = [r for r in payload["plan"]
               if r["config"]["topology"] == "tp"]
    assert tp_rows and all(not r["ok"] for r in tp_rows)
    assert any("n_kv_head=1" in f["message"]
               for r in tp_rows for f in r["findings"])
    # the single-device fallback still serves
    assert payload["chosen"]["config"]["topology"] == "single"


def test_kvp_tp_multi_axis_plan_verifier_gated_with_goldens():
    """Acceptance: on a 4-device kvp=2 x tp=2 mesh with a paged pool,
    the planner enumerates verifier-gated multi-axis rows and prices
    them at the pinned goldens — kvp alone at 640 comm bytes/token
    (partial-softmax combine), kvp x tp at 1664 (additive schedules),
    both with the pool plane exactly halved per device."""
    payload = CM.plan(llama, LLAMA_CFG, {"kvp": 2, "tp": 2}, max_seq=64,
                      kv_pool_blocks=16, kv_block_size=16)
    rows = {r["config"]["topology"]: r for r in payload["plan"]
            if r["config"]["topology"] in ("kvp", "kvp-tp")}
    assert set(rows) == {"kvp", "kvp-tp"}
    half_pool = CM.kv_pool_bytes(LLAMA_CFG, 16, 16) // 2
    for topo, comm in (("kvp", 640), ("kvp-tp", 1664)):
        row = rows[topo]
        assert row["ok"], row["findings"]
        assert row["findings"] == []
        assert row["comm_bytes_per_token"] == comm
        assert row["kv_bytes_per_device"] == half_pool
        assert row["serving_env"]["KVP_DECODE"] == "1"
        assert row["serving_env"]["KV_POOL_BLOCKS"] == "16"
    assert rows["kvp-tp"]["serving_env"]["TP_DECODE"] == "1"
    assert rows["kvp"]["serving_env"]["TP_DECODE"] == "0"
    # kvp x tp additionally shards the params: strictly less HBM than
    # the kvp-only row's replicated weights
    assert (rows["kvp-tp"]["param_bytes_per_device"]
            < rows["kvp"]["param_bytes_per_device"])
    assert payload["chosen"] is not None


def test_kvp_indivisible_kv_heads_rejected_with_diagnostics():
    """The families() llama stand-in has n_kv_head=1, which a 2-wide
    kvp axis cannot split into whole kv heads — the kvp candidate must
    be REJECTED with the divisibility diagnostic, never scored."""
    _, tiny = registry.families()["llama-tiny"]
    payload = CM.plan(llama, tiny, {"kvp": 2}, max_seq=64,
                      kv_pool_blocks=16, kv_block_size=16)
    kvp_rows = [r for r in payload["plan"]
                if r["config"]["topology"] == "kvp"]
    assert kvp_rows and all(not r["ok"] for r in kvp_rows)
    assert any("n_kv_head=1 not divisible" in f["message"]
               and "kvp" in f["message"]
               for r in kvp_rows for f in r["findings"])
    assert all(r["cost_per_token"] is None for r in kvp_rows)


def test_kvp_without_descriptor_fields_rejected():
    """A family whose SHARDING_DESCRIPTOR declares no kvp_divisors is
    unreviewable for pool-plane sharding — the kvp row is rejected
    with that diagnostic (moe also rejects the pool itself: window-
    dependent attention)."""
    payload = CM.plan(moe, MOE_CFG, {"kvp": 2}, max_seq=64,
                      kv_pool_blocks=16, kv_block_size=16)
    kvp_rows = [r for r in payload["plan"]
                if r["config"]["topology"] == "kvp"]
    assert kvp_rows and all(not r["ok"] for r in kvp_rows)
    assert any("kvp_divisors" in f["message"]
               for r in kvp_rows for f in r["findings"])


def test_kvp_requires_a_pool():
    """No paged pool, no kvp rows: the axis shards the pool's kv-head
    plane, so a poolless mesh enumerates none."""
    payload = CM.plan(llama, LLAMA_CFG, {"kvp": 2}, max_seq=64)
    assert [r for r in payload["plan"]
            if r["config"]["topology"] == "kvp"] == []


def test_illegal_compositions_rejected_never_scored():
    payload = CM.plan(moe, MOE_CFG, {}, max_seq=64, max_batch_cap=4,
                      kv_pool_blocks=16)
    for row in payload["plan"]:
        c = row["config"]
        if c["batch_mode"] == "iter" or c["kv_pool_blocks"]:
            # MoE is window-dependent: iter scheduling and paged KV
            # must be rejected by the gate with a diagnostic
            assert not row["ok"]
            assert row["findings"], row
            assert row["cost_per_token"] is None


def test_infeasible_hbm_budget_rejects_with_note():
    payload = CM.plan(gpt2, GPT2_CFG, {}, max_seq=64,
                      hbm_gb=1e-6)  # ~1 KiB budget: nothing fits
    assert payload["chosen"] is None
    assert all("infeasible" in r["note"] for r in payload["plan"])


def test_traffic_parsing():
    rows = CM.parse_traffic("16/32x8, 64/16")
    assert rows == (CM.TrafficRow(16, 32, 8), CM.TrafficRow(64, 16, 1))
    with pytest.raises(ValueError, match="prompt/new"):
        CM.parse_traffic("16x8")
    with pytest.raises(ValueError, match=">= 1"):
        CM.parse_traffic("0/4")
    with pytest.raises(ValueError, match="no request shapes"):
        CM.parse_traffic(" , ")


# -- overlap lint fixtures ---------------------------------------------------


def test_overlap_rule_flags_carry_collective_fed_by_compute():
    """A scan whose body computes, then ppermutes the result into the
    carry — the serial-handoff shape — must produce a finding; a scan
    that only forwards an input through a collective (no in-body
    compute upstream) must not."""
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((2,), ("pp",))

    def serial(x, w):
        def body(c, _):
            y = jnp.tanh(c @ w)                       # in-body compute
            c = jax.lax.ppermute(y, "pp", [(0, 1)])   # rides the carry
            return c, None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    def forwarding(x):
        def body(c, _):
            c = jax.lax.ppermute(c, "pp", [(0, 1)])   # pure transport
            return c, None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    aval = jax.ShapeDtypeStruct((2, 4, 4), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    fn = jax.shard_map(serial, mesh=mesh, in_specs=(P("pp"), P()),
                       out_specs=P("pp"), axis_names={"pp"})
    jaxpr = jax.make_jaxpr(fn)(aval, w)
    got = semantic.check_overlap_jaxpr(jaxpr, "fix", "p.py", "serial")
    assert len(got) == 1 and got[0].rule == "overlap"
    assert "strictly ordered" in got[0].message

    fn2 = jax.shard_map(forwarding, mesh=mesh, in_specs=(P("pp"),),
                        out_specs=P("pp"), axis_names={"pp"})
    jaxpr2 = jax.make_jaxpr(fn2)(aval)
    assert semantic.check_overlap_jaxpr(jaxpr2, "fix", "p.py", "fwd") == []


def test_real_ppdecode_serial_handoffs_are_found_and_baselined():
    """The declared decode entry points produce overlap findings (the
    handoffs ARE serial today) and every one of them is suppressed by
    the baseline — so the day double-buffering lands, the suppression
    goes stale and --strict fails until it is deleted."""
    from tools.graftcheck.core import load_baseline, split_findings
    found = []
    for n in registry.OVERLAP_RING_SIZES:
        found.extend(semantic.check_decode_overlap(n, f"overlap/pp={n}"))
    assert found, "ppdecode handoffs no longer flagged — did "\
        "double-buffering land? then delete the baseline entry"
    active, suppressed, _ = split_findings(found, load_baseline())
    assert active == [] and len(suppressed) == len(found)


# -- AUTO_PLAN serving integration -------------------------------------------


@pytest.fixture(scope="module")
def served_model():
    cfg = gpt2.GPT2Config(vocab_size=257, n_positions=128, n_embd=8,
                          n_layer=2, n_head=2)
    return cfg, gpt2.init_params(cfg, jax.random.PRNGKey(0))


def test_auto_plan_resolves_and_reports(served_model):
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.http import TestClient
    from llm_sharding_demo_tpu.serving.tokenizer import ByteTokenizer
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    cfg, params = served_model
    client = TestClient(create_app(
        ServingConfig(model_id="t", max_seq=64, auto_plan=True),
        model=(cfg, params), tokenizer=ByteTokenizer()))
    h = client.get("/healthz").json()
    # one device, single-stream default traffic: the planner reproduces
    # the hand-tuned default and says so on /healthz
    assert h["auto_plan"]["chosen"] == "single/admission/mb1"
    # candidate counts depend on the host's visible device count (the
    # suite exposes several virtual CPU devices, so sharded candidates
    # enumerate — and get gated); the CHOICE must not
    assert h["auto_plan"]["candidates"] >= 1
    assert h["max_batch"] == 1 and h["batch_mode"] == "admission"
    assert h["kv_pool_blocks"] == 0
    # the flight-recorder header shares the topology dict (including
    # the auto_plan row) by construction
    d = client.get("/debug/requests").json()
    assert d["serving"]["auto_plan"] == h["auto_plan"]
    r = client.post("/generate", json={"prompt": "Hi",
                                       "max_new_tokens": 4,
                                       "mode": "greedy"})
    assert "generated" in r.json()


def test_auto_plan_traffic_env_drives_batching(served_model):
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.http import TestClient
    from llm_sharding_demo_tpu.serving.tokenizer import ByteTokenizer
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    cfg, params = served_model
    client = TestClient(create_app(
        ServingConfig(model_id="t", max_seq=64, auto_plan=True,
                      max_batch=8, auto_plan_traffic="8/8x8"),
        model=(cfg, params), tokenizer=ByteTokenizer()))
    h = client.get("/healthz").json()
    assert h["max_batch"] == 8
    assert h["auto_plan"]["chosen"].endswith("mb8")


def test_auto_plan_rejected_off_coordinator(served_model):
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.tokenizer import ByteTokenizer
    from llm_sharding_demo_tpu.utils.config import ServingConfig
    cfg, params = served_model
    with pytest.raises(ValueError, match="AUTO_PLAN"):
        create_app(ServingConfig(model_id="t", shard_role="a",
                                 auto_plan=True),
                   model=(cfg, params), tokenizer=ByteTokenizer())


# -- --json schema (satellite: documented payload shape) ---------------------


def test_verifier_json_schema_shape():
    """The graftcheck --json payload schema (docs/ARCHITECTURE.md
    "Static analysis"): keys and types, pinned. lint-only keeps this
    fast; the full-run payload has the same shape (test_graftcheck pins
    the full run's semantics)."""
    payload = cli.run(lint_only=True)
    assert set(payload) == {"ok", "strict", "findings", "suppressed",
                            "suppressed_findings",
                            "stale_baseline", "stale_audits",
                            "passes_run", "pass_seconds",
                            "semantic_checks",
                            "sanitize_checks", "locks_checks",
                            "locks_guarded_regions", "locks_vacuous",
                            "fault_checks", "fault_policies",
                            "fault_vacuous",
                            "scope_checks", "scope_profiled_regions",
                            "scope_vacuous", "slo_checks",
                            "slo_policies", "slo_vacuous",
                            "fleet_checks", "fleet_policies",
                            "fleet_vacuous",
                            "watch_checks", "watch_signals",
                            "watch_vacuous",
                            "timeline_checks", "timeline_kinds",
                            "timeline_vacuous",
                            "numerics_checks", "numerics_contracts",
                            "numerics_vacuous",
                            "memory_checks", "memory_ledgers",
                            "memory_vacuous",
                            "tier_checks", "tier_policies",
                            "tier_vacuous",
                            "trend_checks", "trend_policies",
                            "trend_vacuous",
                            "placement_checks", "placement_contracts",
                            "placement_vacuous",
                            "recompile_bounds"}
    assert isinstance(payload["ok"], bool)
    assert isinstance(payload["sanitize_checks"], int)
    assert isinstance(payload["locks_checks"], int)
    assert isinstance(payload["fault_checks"], int)
    assert isinstance(payload["fault_policies"], dict)
    assert isinstance(payload["fault_vacuous"], list)
    assert isinstance(payload["locks_guarded_regions"], dict)
    assert isinstance(payload["locks_vacuous"], list)
    assert isinstance(payload["scope_checks"], int)
    assert isinstance(payload["scope_profiled_regions"], dict)
    assert isinstance(payload["scope_vacuous"], list)
    assert isinstance(payload["slo_checks"], int)
    assert isinstance(payload["slo_policies"], dict)
    assert isinstance(payload["slo_vacuous"], list)
    assert isinstance(payload["fleet_checks"], int)
    assert isinstance(payload["fleet_policies"], dict)
    assert isinstance(payload["fleet_vacuous"], list)
    assert isinstance(payload["watch_checks"], int)
    assert isinstance(payload["watch_signals"], dict)
    assert isinstance(payload["watch_vacuous"], list)
    assert isinstance(payload["timeline_checks"], int)
    assert isinstance(payload["numerics_checks"], int)
    assert isinstance(payload["numerics_contracts"], dict)
    assert isinstance(payload["numerics_vacuous"], list)
    assert isinstance(payload["timeline_kinds"], dict)
    assert isinstance(payload["timeline_vacuous"], list)
    assert isinstance(payload["memory_checks"], int)
    assert isinstance(payload["memory_ledgers"], dict)
    assert isinstance(payload["memory_vacuous"], list)
    assert isinstance(payload["tier_checks"], int)
    assert isinstance(payload["tier_policies"], dict)
    assert isinstance(payload["tier_vacuous"], list)
    assert isinstance(payload["placement_checks"], int)
    assert isinstance(payload["placement_contracts"], dict)
    assert isinstance(payload["placement_vacuous"], list)
    assert isinstance(payload["trend_checks"], int)
    assert isinstance(payload["trend_policies"], dict)
    assert isinstance(payload["trend_vacuous"], list)
    assert isinstance(payload["stale_audits"], list)
    assert isinstance(payload["passes_run"], list)
    assert isinstance(payload["pass_seconds"], dict)
    assert set(payload["pass_seconds"]) == set(payload["passes_run"])
    assert isinstance(payload["suppressed_findings"], list)
    assert isinstance(payload["strict"], bool)
    assert isinstance(payload["findings"], list)
    assert isinstance(payload["suppressed"], int)
    assert isinstance(payload["stale_baseline"], list)
    assert isinstance(payload["recompile_bounds"], dict)
    json.dumps(payload)  # JSON-able end to end


def test_plan_json_schema_shape():
    """The plan payload schema (docs/ARCHITECTURE.md "Planning"):
    top-level keys, per-row keys, and the chosen row's env mapping."""
    payload = CM.plan(gpt2, GPT2_CFG, {}, max_seq=64)
    assert set(payload) == {"model", "mesh", "ici_byte_weight",
                            "ici_byte_weight_source",
                            "max_seq", "traffic", "plan", "chosen",
                            "rejected"}
    assert payload["ici_byte_weight"] == CM.ICI_BYTE_WEIGHT
    assert payload["ici_byte_weight_source"] == "a-priori"
    row_keys = {"config", "label", "ok", "cost_per_token",
                "comm_bytes_per_token", "param_bytes_per_device",
                "kv_bytes_per_device", "peak_activation_bytes",
                "hbm_bytes_per_device", "programs", "program_total",
                "programs_exact", "serving_env", "note", "findings"}
    for row in payload["plan"]:
        assert set(row) == row_keys
        assert set(row["config"]) == {"topology", "boundaries",
                                      "batch_mode", "max_batch",
                                      "kv_pool_blocks", "kv_block_size"}
    assert payload["chosen"]["serving_env"].keys() >= {
        "BATCH_MODE", "MAX_BATCH", "PP_DECODE", "TP_DECODE", "EP_DECODE",
        "KVP_DECODE", "KV_POOL_BLOCKS", "KV_BLOCK_SIZE"}
    json.dumps(payload, default=str)


# -- --strict stale-suppression hygiene --------------------------------------


def test_strict_fails_on_stale_baseline(tmp_path):
    """A baseline line whose finding no longer exists is report-only by
    default and a hard failure under --strict — the hygiene that keeps
    dead suppressions from hiding future regressions."""
    import os
    real = open(os.path.join(os.path.dirname(cli.__file__),
                             "baseline.txt")).read()
    bl = tmp_path / "baseline.txt"
    bl.write_text(real + "\nhost-sync a/gone.py::Dead.scope "
                         "fixed long ago\n")
    payload = cli.run(lint_only=True, baseline_path=str(bl), strict=True)
    assert payload["findings"] == []          # nothing newly active
    assert any("a/gone.py" in s for s in payload["stale_baseline"])
    assert payload["ok"] is False             # strict: stale = failure
    relaxed = cli.run(lint_only=True, baseline_path=str(bl), strict=False)
    assert relaxed["ok"] is True              # report-only by default
