"""HTTP serving surface — wire-compatible with the reference.

Routes, schemas, role guards, and response shapes mirror reference
server.py:116-210 exactly:

- ``POST /forward``    {"input_ids": [int]}        -> {"hidden_states": [[[f]]]}
- ``POST /forward_b``  {"hidden_states": [[[f]]]}  -> {"logits": [[[f]]]}
- ``POST /generate``   {"prompt", "max_new_tokens"} -> {"generated": str}
- role guards return HTTP 200 with ``{"error": "This instance is not
  ..."}`` — preserved verbatim for wire parity even though it's a
  reference quirk (SURVEY.md §2.3.5: its coordinator's raise_for_status
  never fires on misrouting);

plus what the reference lacks:

- ``GET /healthz`` readiness/liveness (SURVEY.md §5 "Failure detection":
  the reference ships no probes, so k8s cannot tell a wedged pod from a
  healthy one);
- N-stage local dispatch: the common-case pod owns its TPU devices and
  runs the whole pipeline on-device (``parallel.pipeline``); ``DISPATCH=
  remote`` reproduces the reference's three-pod HTTP topology for
  drop-in k8s compatibility (coordinator POSTs to shard services per
  token, reference server.py:169-181);
- request-level decode controls: the reference hard-codes
  temperature=0.6/top_k=40 sampling (server.py:187-205); here that is the
  default, with optional ``mode="greedy"`` (BASELINE.json's parity mode)
  and an explicit ``seed`` for reproducibility.
"""

from __future__ import annotations

import logging
import re
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from pydantic import BaseModel

from ..models import gpt2
from ..parallel import partition as P_
from ..parallel.pipeline import PipelineRunner
from ..runtime.engine import REF_TEMPERATURE, REF_TOP_K, SamplingConfig
from ..utils import graftfault, graftmem, graftshard, grafttime, \
    grafttrend, tracing
from ..utils.config import ServingConfig, from_env
from ..utils.metrics import REGISTRY
from ..utils.tracing import timed
from . import loader
from .http import JSONApp
from .tokenizer import get_tokenizer

log = logging.getLogger(__name__)

# Lock-discipline contract (tools/graftcheck locks pass): this module
# runs on ThreadingHTTPServer handler threads but owns NO locks — every
# shared object a handler touches (runner/pool/registry/recorder)
# guards its own state (see those modules' GUARDED_STATE). Declared
# empty so a lock added here must declare what it protects.
GUARDED_STATE = {}
LOCK_ORDER = ()

# Fault contract (tools/graftcheck faults pass): the coordinator's one
# blocking boundary is the remote-dispatch shard hop. Its per-attempt
# timeout derives from the request's remaining deadline budget
# (X-Deadline-Ms) capped by the HopPolicy's per-attempt budget; retries
# ride the typed policy (capped exponential backoff + jitter, per-shard
# circuit breaker); failure degrades to a typed 502 (upstream) or 503 +
# Retry-After (breaker open / deadline exhausted) — never an opaque 500.
FAULT_POLICY = {
    "requests.post": ("request", "hop-policy",
                      "typed 502/503 + Retry-After, per-shard breaker"),
}


class UpstreamError(Exception):
    """A shard hop failed (connection, HTTP error, or error body)."""

    def __init__(self, shard: str, url: str, detail: str):
        super().__init__(f"shard {shard} at {url}: {detail}")
        self.shard = shard
        self.url = url
        self.detail = detail


class InputIDs(BaseModel):
    input_ids: List[int]


class PrefillReq(BaseModel):
    """graftfleet /prefill body: just the prompt — the prefill replica
    fills shared pool blocks; block ids never cross the wire."""

    prompt: str


class HiddenStates(BaseModel):
    hidden_states: list  # nested [batch, seq, hidden]


class GenerateReq(BaseModel):
    prompt: str
    max_new_tokens: int = 20
    # extensions beyond the reference schema (defaults reproduce its
    # behavior: temperature-0.6/top-k-40 sampling)
    mode: str = "sample"
    temperature: float = REF_TEMPERATURE
    top_k: int = REF_TOP_K
    # nucleus sampling within the top-k survivors; 1.0 = off (pure
    # reference math)
    top_p: float = 1.0
    # stop early (truncate) at the tokenizer's EOS token, or at an
    # explicit ``eos_token_id``. Off by default: the reference always
    # emits exactly max_new_tokens (server.py:169), so parity mode does
    # too.
    stop_at_eos: bool = False
    eos_token_id: Optional[int] = None
    # Seed reproducibility contract: the same (prompt, params, seed) on
    # the SAME server configuration replays the same stream. Across
    # configurations the stream may legitimately differ while the
    # distribution does not: SPEC_DECODE>0 routes sample-mode requests
    # through the rejection-sampled speculative engine, whose RNG
    # consumption pattern differs from the plain scan's (and from the
    # reference's unseeded torch sampler, SURVEY.md §7(d)). Don't key
    # golden outputs on seeds across serving-config changes.
    seed: Optional[int] = None


# -- request-identity / deadline header parsing (shared by /generate,
# -- /prefill, and the fleet router — ONE charset and ONE budget bound,
# -- so a future widening cannot land in one copy and miss the others)

_RID_RE = re.compile(r"[A-Za-z0-9._:-]{1,128}")
_PROFILE_RE = re.compile(r"[A-Za-z0-9._:-]{1,64}")
DEADLINE_MS_ERROR = ("X-Deadline-Ms must be an integer millisecond "
                     "budget in [1, 86400000]")


def parse_request_identity(headers: dict) -> Tuple[str, Optional[str]]:
    """(rid, profile_label): honor a caller's X-Request-ID, mint one
    otherwise; both values restricted to a safe charset — they are
    interpolated into log lines, echoed as headers, and query-matched
    verbatim (the same injection class _escape_label_value fixes for
    /metrics)."""
    raw_rid = (headers.get("x-request-id") or "").strip()
    rid = (raw_rid if _RID_RE.fullmatch(raw_rid)
           else tracing.new_request_id())
    raw_prof = (headers.get("x-workload-profile") or "").strip()
    return rid, (raw_prof if _PROFILE_RE.fullmatch(raw_prof) else None)


def parse_deadline_header(headers: dict):
    """X-Deadline-Ms -> (deadline, dl_ms, error): (None, None, None)
    when absent, (None, None, msg) on a malformed/out-of-range value
    (callers answer 400 — this header is an extension, so
    status-checking clients get the honest signal; parity only binds
    the reference's own fields)."""
    raw_dl = (headers.get("x-deadline-ms") or "").strip()
    if not raw_dl:
        return None, None, None
    try:
        dl_ms = int(raw_dl)
    except ValueError:
        dl_ms = 0
    if not 1 <= dl_ms <= 86_400_000:
        return None, None, DEADLINE_MS_ERROR
    return graftfault.Deadline.from_ms(dl_ms), dl_ms, None


def create_app(cfg: Optional[ServingConfig] = None,
               model=None, tokenizer=None,
               registry=None, recorder=None, kv_pool=None,
               replica: Optional[str] = None) -> JSONApp:
    """Build the app. ``model=(config, params)`` / ``tokenizer`` injectable
    for tests; by default resolved via ``serving.loader`` / HF-or-byte
    tokenizer. ``registry`` (utils.metrics.MetricsRegistry) and
    ``recorder`` (utils.tracing.FlightRecorder) are likewise injectable —
    tests can assert the app-level series/traces without touching the
    process-global defaults. ``kv_pool`` (a ``runtime.kv_pool.
    KVBlockPool`` matching this app's engine geometry) makes this
    replica serve off a SHARED pool instead of building its own — the
    graftfleet process-local form, where prefill and decode replicas
    hand blocks off through one allocator's content-keyed registry.
    ``replica`` labels this app's request-scoped timeline events
    (grafttime's replica correlator — the fleet harness passes the
    replica name); defaults to the fleet role, or "solo"."""
    cfg = cfg or from_env()
    replica_label = replica or cfg.fleet_role or "solo"
    reg = registry if registry is not None else REGISTRY
    rec = recorder if recorder is not None else tracing.RECORDER
    # Trend & drift watch (utils/grafttrend): one reducer per app,
    # folded over THIS app's registry — the poll-on-read loop (every
    # GET /debug/trend taps the producers and evaluates the declared
    # WATCH_POLICY), plus the wave-boundary tap when continuous
    # planning attaches it below.
    trend_reducer = grafttrend.TrendReducer(registry=reg)
    # multi-host glue sits HERE, where every entry path converges (CLI,
    # `serving.app:app` lazy attribute, tests) — it must run before the
    # first backend use, i.e. before the model loads. No-op when the
    # COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID contract is unset.
    from ..parallel.distributed import maybe_initialize
    maybe_initialize()
    # Role-aware loading: shard pods with a checkpoint partial-restore only
    # their stage's layers (utils.checkpoint.load_stage_params); a
    # remote-dispatch coordinator reads config only. ``params`` is None in
    # those cases and ``stage_only`` holds a shard role's subset.
    if model is not None:
        config, params = model
        stage_only = None
    else:
        config, params, stage_only = loader.resolve_for_role(cfg)
    tokenizer = tokenizer or get_tokenizer(cfg.model_id,
                                           checkpoint_dir=cfg.checkpoint_dir)

    # AUTO_PLAN (tools/graftcheck/costmodel): resolve the decode
    # topology/batching/KV knobs at startup from the compile-free cost
    # model — every candidate is gated through the graftcheck semantic
    # verifier before scoring, so a plan this block installs is exactly
    # as validated as a hand-written one (the guards below still run on
    # the resolved values). The chosen plan is logged and reported
    # under /healthz "auto_plan".
    auto_plan_info = None
    if cfg.auto_plan:
        if not (cfg.shard_role == "coordinator" and cfg.dispatch == "local"):
            raise ValueError("AUTO_PLAN applies to the coordinator's local "
                             "decode path only")
        try:
            from tools.graftcheck import costmodel as _cm
        except ImportError as e:
            raise ValueError(
                "AUTO_PLAN=1 needs the repo's tools/ package importable "
                "(run from the repo checkout root)") from e
        plan_traffic = (_cm.parse_traffic(cfg.auto_plan_traffic)
                        if cfg.auto_plan_traffic else None)
        payload = _cm.plan_for_serving(
            config, len(jax.devices()), max_seq=cfg.max_seq,
            traffic=plan_traffic, max_batch_cap=max(cfg.max_batch, 1),
            kv_pool_blocks=cfg.kv_pool_blocks,
            kv_block_size=cfg.kv_block_size)
        chosen = payload["chosen"]
        if chosen is None:
            raise ValueError(
                "AUTO_PLAN: no candidate serving config survived the "
                "graftcheck verifier for this model/mesh/traffic")
        import dataclasses as _dc
        c = chosen["config"]
        cfg = _dc.replace(
            cfg,
            batch_mode=c["batch_mode"], max_batch=c["max_batch"],
            kv_pool_blocks=c["kv_pool_blocks"],
            kv_block_size=c["kv_block_size"],
            pp_decode=c["topology"] == "pp",
            tp_decode=c["topology"] == "tp",
            ep_decode=c["topology"] == "ep",
            boundaries=(tuple(c["boundaries"]) if c["topology"] == "pp"
                        else cfg.boundaries))
        auto_plan_info = {
            "chosen": chosen["label"],
            "mesh": chosen.get("mesh", {}),
            "cost_per_token": chosen["cost_per_token"],
            "comm_bytes_per_token": chosen["comm_bytes_per_token"],
            "hbm_bytes_per_device": chosen["hbm_bytes_per_device"],
            "programs_exact": chosen["programs_exact"],
            "candidates": len(payload["plan"]),
            "rejected": payload["rejected"],
        }
        log.info('{"event": "auto_plan", "chosen": "%s", '
                 '"cost_per_token": %s, "candidates": %d, "rejected": %d}',
                 chosen["label"], chosen["cost_per_token"],
                 len(payload["plan"]), payload["rejected"])

    n_layer = config.n_layer
    for b in cfg.boundaries:
        if not 1 <= b <= n_layer - 1:
            raise ValueError(
                f"boundary {b} out of range for n_layer={n_layer}")

    # Build only what this role serves (the reference loads the full model
    # into every pod regardless of role, server.py:108-110 — the exact
    # memory waste this gate avoids):
    # - coordinator + local dispatch: the N-stage pipeline for /generate;
    # - roles a/b: their half of the two-stage compat view for /forward +
    #   /forward_b — the reference's ShardA/ShardB contract
    #   (server.py:51-105) regardless of how many stages /generate uses;
    # - coordinator + remote dispatch: nothing (shards hold the weights).
    from ..models import (family_of, is_partitionable,
                          is_stage_partitionable)
    # Two distinct notions: ``partitionable`` is the reference's GPT-2
    # WIRE topology (/forward + /forward_b relay, remote dispatch) —
    # GPT-2-only by design; ``stageable`` is whether the decode engine
    # can stage the family at all (GPT-2 and llama; MoE decodes
    # unstaged).
    partitionable = is_partitionable(config)
    stageable = is_stage_partitionable(config)
    if not partitionable and cfg.dispatch == "remote":
        # the remote topology relays hidden states between stage shards
        # (/forward -> /forward_b), which non-GPT-2 pods decline —
        # /generate would die on a KeyError mid-relay; fail at startup
        raise ValueError(
            "DISPATCH=remote requires the dense GPT-2 stage-shard "
            f"topology; {type(config).__name__} models serve with "
            "DISPATCH=local")
    if cfg.inference_dtype != "float32" and not (
            cfg.shard_role == "coordinator" and cfg.dispatch == "local"):
        # only the local decode runner implements the fast dtypes; a
        # silently-ignored knob with /healthz still reporting it would
        # tell monitoring the fleet is quantized when it is not
        raise ValueError(
            f"INFERENCE_DTYPE={cfg.inference_dtype} applies to the "
            "coordinator's local decode path only; shard/remote roles "
            "serve the fp32 parity endpoints")
    if cfg.spec_decode > 0 and not (
            cfg.shard_role == "coordinator" and cfg.dispatch == "local"):
        raise ValueError(
            f"SPEC_DECODE={cfg.spec_decode} applies to the coordinator's "
            "local decode path only")
    if cfg.prefix_cache > 0:
        if not (cfg.shard_role == "coordinator" and cfg.dispatch == "local"):
            raise ValueError(
                f"PREFIX_CACHE={cfg.prefix_cache} applies to the "
                "coordinator's local decode path only")
        # prefix+batching composes (per-row store prefills merged into
        # one batched decode in admission mode, store-backed admission
        # prefills in iter mode), and prefix+speculation composes
        # single-stream AND batched (spec-flagged rounds/batches decode
        # through the batched verify loop).

    def _refuse(refused):
        """``(asked for, why not)`` pairs of what a family refuses: the
        first one asked for is raised, one message each."""
        for on, why in refused:
            if on:
                raise ValueError(f"{why} (refused for this family)")

    # what the family's declaration (models.family.Family.refuses) says
    # it does not serve, refused here, at start-up, instead of a wrong
    # answer further down
    asked = {"kv_pool_dtype": cfg.kv_pool_dtype,
             "kv_host_blocks": cfg.kv_host_blocks > 0,
             "spec_decode": cfg.spec_decode > 0,
             "multi_chip": cfg.pp_decode or cfg.tp_decode or cfg.ep_decode,
             "int8_weights": cfg.inference_dtype == "int8"}
    family = family_of(config)
    _refuse((asked[option], family.refusal(option, config, asked[option]))
            for option, _ in family.refuses)
    if cfg.ep_decode:
        if not (cfg.shard_role == "coordinator" and cfg.dispatch == "local"):
            raise ValueError("EP_DECODE applies to the coordinator's local "
                             "decode path only")
        if not hasattr(config, "n_experts"):
            raise ValueError(
                f"EP_DECODE shards MoE expert weights; "
                f"{type(config).__name__} models have no expert axis")
        if cfg.pp_decode or cfg.spec_decode > 0 or cfg.prefix_cache > 0:
            raise ValueError(
                "EP_DECODE composes with MAX_BATCH only; PP_DECODE, "
                "SPEC_DECODE, and PREFIX_CACHE own other decode programs "
                "(and MoE prefills monolithically — no PREFILL_CHUNK)")
        ep_size = min(len(jax.devices()), config.n_experts)
        if config.n_experts % ep_size:
            raise ValueError(
                f"EP_DECODE: n_experts={config.n_experts} not divisible "
                f"by the {ep_size}-device ep axis")
    if cfg.kv_pool_blocks > 0:
        if not (cfg.shard_role == "coordinator" and cfg.dispatch == "local"):
            raise ValueError("KV_POOL_BLOCKS applies to the coordinator's "
                             "local decode path only")
        if cfg.pp_decode or cfg.ep_decode or cfg.tp_decode:
            raise ValueError(
                "KV_POOL_BLOCKS drives the single-device engine's paged "
                "storage; PP/EP/TP_DECODE keep contiguous caches")
        if cfg.prefill_chunk > 0:
            raise ValueError(
                "KV_POOL_BLOCKS prefills monolithically (one block "
                "scatter per admission); PREFILL_CHUNK owns another "
                "prefill program structure")
        if cfg.spec_decode > 0 and cfg.batch_mode != "iter":
            raise ValueError(
                "KV_POOL_BLOCKS composes with SPEC_DECODE through "
                "BATCH_MODE=iter (paged draft-verify segments); the "
                "solo paged runner decodes one token per forward")
        if cfg.max_batch > 1 and cfg.batch_mode != "iter":
            raise ValueError(
                "KV_POOL_BLOCKS batches through BATCH_MODE=iter "
                "(watermark admission + preemption live at segment "
                "boundaries); the admission batcher keeps contiguous "
                "round caches")
        from ..models import is_window_independent as _wi
        if not _wi(config):
            raise ValueError(
                "KV_POOL_BLOCKS requires window-independent routing "
                f"(dense families); {type(config).__name__} serves "
                "unpaged")
    if cfg.batch_mode == "iter":
        if cfg.max_batch <= 1:
            raise ValueError("BATCH_MODE=iter requires MAX_BATCH > 1 "
                             "(iteration-level scheduling is a batching "
                             "policy)")
        if not (cfg.shard_role == "coordinator" and cfg.dispatch == "local"):
            raise ValueError("BATCH_MODE=iter applies to the coordinator's "
                             "local decode path only")
        if (cfg.prefill_chunk > 0 or cfg.pp_decode
                or cfg.ep_decode or cfg.tp_decode):
            # SPEC_DECODE composes (draft-verify segments) and
            # PREFIX_CACHE composes (store-backed admission prefills);
            # chunked prefill and the mesh/pipeline decoders still own
            # other program structures
            raise ValueError(
                "BATCH_MODE=iter drives the single-device engine's "
                "segment loop; PREFILL_CHUNK/PP/EP/TP_DECODE use "
                "BATCH_MODE=admission")
        from ..models import is_window_independent
        if not is_window_independent(config):
            raise ValueError(
                "BATCH_MODE=iter requires window-independent routing "
                f"(dense families); {type(config).__name__} batches via "
                "BATCH_MODE=admission")
    if cfg.tp_decode:
        if not (cfg.shard_role == "coordinator" and cfg.dispatch == "local"):
            raise ValueError("TP_DECODE applies to the coordinator's local "
                             "decode path only")
        if hasattr(config, "n_experts"):
            raise ValueError(
                "TP_DECODE shards dense-family projections; MoE models "
                "shard their expert axis via EP_DECODE instead")
        if (cfg.pp_decode or cfg.ep_decode or cfg.spec_decode > 0
                or cfg.prefix_cache > 0):
            raise ValueError(
                "TP_DECODE composes with MAX_BATCH and PREFILL_CHUNK "
                "only; PP_DECODE/EP_DECODE/SPEC_DECODE/PREFIX_CACHE own "
                "other decode programs")
        if cfg.inference_dtype == "int8":
            raise ValueError(
                "TP_DECODE runs fp32/bf16 (the int8 streaming matmuls "
                "are unpartitioned Pallas kernels GSPMD cannot split)")
        tp_size = len(jax.devices())
        kv_heads = getattr(config, "n_kv_head", config.n_head)
        if config.n_head % tp_size or kv_heads % tp_size:
            raise ValueError(
                f"TP_DECODE: this pod's {tp_size} devices must divide "
                f"n_head={config.n_head} and n_kv_head={kv_heads} "
                "(attention shards over whole heads)")
    if cfg.pp_decode:
        if not (cfg.shard_role == "coordinator" and cfg.dispatch == "local"):
            raise ValueError("PP_DECODE applies to the coordinator's local "
                             "decode path only")
        if not stageable:
            raise ValueError(
                f"PP_DECODE requires a stage-partitionable family; "
                f"{type(config).__name__} models decode unstaged")
        if cfg.spec_decode > 0 or cfg.prefix_cache > 0 or cfg.prefill_chunk > 0:
            # round 3 lifted the rest of the round-2 exclusivity wall:
            # int8 stage weights and (ragged) batching now compose with
            # the ppermute program (parallel.ppdecode); speculation,
            # prefix caching, and chunked prefill still own the
            # single-device engine's prefill/decode program structure
            raise ValueError(
                "PP_DECODE composes with MAX_BATCH>1 and "
                "INFERENCE_DTYPE=int8; SPEC_DECODE, PREFIX_CACHE, and "
                "PREFILL_CHUNK own the single-device engine's programs")
        n_stages_cfg = len(cfg.boundaries) + 1
        if len(jax.devices()) < n_stages_cfg:
            raise ValueError(
                f"PP_DECODE needs >= {n_stages_cfg} devices (one per "
                f"stage); this pod sees {len(jax.devices())}")
    runner = None
    spec_runner = None
    prefix_runner = None   # closure target for /prefill's role guard
    switcher = None        # graftwatch continuous-mode plan switcher
    # ``kv_pool`` is the (optional) injected shared pool; non-pooled
    # configurations must not carry one (validated below), and only the
    # coordinator's local decode path can host it at all
    if kv_pool is not None and not (cfg.shard_role == "coordinator"
                                    and cfg.dispatch == "local"):
        raise ValueError("kv_pool injection applies to the "
                         "coordinator's local decode path only")
    # What /healthz reports as n_stages: the decode topology actually
    # serving /generate, not just the configured partition — a monitoring
    # read of "3 stages" while an unstaged engine answers requests is the
    # same silent-knob misreport the INFERENCE_DTYPE guard above refuses.
    decode_stages = len(cfg.boundaries) + 1
    if cfg.shard_role == "coordinator" and cfg.dispatch == "local":
        # the validated dtype name passes straight through: astype/zeros
        # accept dtype strings and the engine branches on "int8" itself
        dtype = cfg.inference_dtype
        # chunked prefill bounds compile count per prompt length; 0 -> off
        pchunk = cfg.prefill_chunk or None
        if cfg.auto_plan_continuous:
            # Continuous re-planning (utils/graftwatch, the dynamic
            # half of the graftcheck watch pass): ONE engine and ONE
            # block pool back a PRE-CERTIFIED switchable plan set —
            # the solo paged runner and the pooled iteration
            # scheduler, both built HERE, at startup. The switcher
            # only ever re-routes admissions between these front ends
            # (it can never construct a runner), which is the whole
            # "a plan switch causes zero recompiles beyond the
            # certified set" invariant; the certified program cost of
            # each plan is proven through recompile.certify machinery
            # in graftwatch.certify_plan_set and served at
            # GET /debug/plan. Composition exclusions live in
            # utils.config (__post_init__).
            from ..models import is_window_independent as _wi_c
            if not _wi_c(config):
                raise ValueError(
                    "AUTO_PLAN_CONTINUOUS requires window-independent "
                    f"routing (dense families); {type(config).__name__} "
                    "serves hand-tuned")
            try:
                from ..utils import graftwatch
                import tools.graftcheck  # noqa: F401 — certifier dep
            except ImportError as e:
                raise ValueError(
                    "AUTO_PLAN_CONTINUOUS needs the repo's tools/ "
                    "package importable (run from the repo checkout "
                    "root) — the plan set is certified through "
                    "tools/graftcheck") from e
            from ..runtime.engine import DecodeEngine
            engine = DecodeEngine(params, config, max_seq=cfg.max_seq,
                                  dtype=dtype)
            decode_stages = 1
            if kv_pool is not None:
                if kv_pool.max_seq != engine._cache_seq:
                    raise ValueError(
                        f"injected kv_pool spans {kv_pool.max_seq} "
                        f"slots, engine cache is {engine._cache_seq} — "
                        "shared-pool replicas must agree on geometry")
                if kv_pool.block_dtype is not None:
                    # config already refuses KV_POOL_DTYPE under
                    # continuous mode; an injected pool must not smuggle
                    # quantized movers past the certified plan set
                    raise ValueError(
                        f"injected kv_pool stores {kv_pool.block_regime} "
                        "blocks — AUTO_PLAN_CONTINUOUS certifies the "
                        "full-precision mover programs only")
            else:
                from ..runtime.kv_pool import KVBlockPool
                kv_pool = KVBlockPool.for_engine(
                    engine, num_blocks=cfg.kv_pool_blocks,
                    block_size=cfg.kv_block_size)
            weights = graftwatch.CostWeights.apriori()
            if cfg.auto_plan_journal:
                # telemetry-calibrated byte weights: the journaled
                # graftscope_attribution drift rows (and the ICI
                # calibration row) re-price the live scoring with this
                # host's measured rates. A malformed journal raises the
                # typed CalibrationError at startup — never a silent
                # fall-back to the a-priori weights.
                import json as _json
                with open(cfg.auto_plan_journal, encoding="utf-8") as f:
                    weights = graftwatch.fit_cost_weights(_json.load(f))
            plans, plan_cost_map, certified = graftwatch.build_plan_set(
                engine, kv_pool, config, max_seq=cfg.max_seq,
                max_batch=cfg.max_batch,
                traffic=cfg.auto_plan_traffic or None,
                batch_wait_ms=cfg.batch_wait_ms)
            watcher = graftwatch.TelemetryWatcher(registry=reg)
            switcher = graftwatch.PlanSwitcher(
                plans, plan_cost_map, certified, watcher,
                weights=weights, registry=reg)
            # between waves the switcher polls the trend reducer and
            # sizes the declared SIZING_POLICY knobs from its windowed
            # occupancy estimate (zero-recompile, byte-equal — see
            # graftwatch.attach_trend)
            switcher.attach_trend(trend_reducer)
            log.info('{"event": "auto_plan_continuous", "plans": %s, '
                     '"active": "%s", "weights": "%s"}',
                     sorted(plans), switcher.health_view()["active"],
                     weights.source)
        elif cfg.spec_decode > 0:
            # prompt-lookup speculation (runtime.spec_decode):
            # single-stream requests emit up to draft_len+1 tokens per
            # forward — token-exact for greedy, distribution-exact for
            # sample mode; requests that don't fit speculation's guards
            # fall through to the wrapped plain engine (same weights).
            # The spec engine decodes unstaged (one program, one device
            # group) — reflected in decode_stages below.
            from ..runtime.spec_decode import SpecDecodeEngine
            spec_runner = SpecDecodeEngine(params, config,
                                           max_seq=cfg.max_seq, dtype=dtype,
                                           draft_len=cfg.spec_decode,
                                           prefill_chunk=pchunk)
            runner = spec_runner.plain
            decode_stages = 1
        elif not stageable:
            # MoE's expert tree isn't stage-partitionable; the whole
            # model decodes as one program on the pod's devices
            # (models.family_module dispatch in the engine). EP_DECODE
            # shards the expert stack over an ep mesh axis spanning the
            # pod's devices (validated above).
            from ..runtime.engine import DecodeEngine
            mesh = None
            if cfg.ep_decode:
                from ..parallel.spmd import make_mesh
                ep_size = min(len(jax.devices()), config.n_experts)
                mesh = make_mesh({"ep": ep_size}, jax.devices()[:ep_size])
            runner = DecodeEngine(params, config, max_seq=cfg.max_seq,
                                  dtype=dtype, prefill_chunk=pchunk,
                                  mesh=mesh)
            decode_stages = 1  # unstaged (no dense partition)
        elif cfg.pp_decode:
            # one stage per device, activations hop the ICI ring inside
            # a single compiled program per phase (parallel.ppdecode) —
            # the TPU-native endgame of the reference's per-token HTTP
            # topology. Composes with int8 stage weights, uneven
            # BOUNDARIES (padded stacking), and MAX_BATCH>1 (the batcher
            # wraps below; ragged rows ride per-row pad masks).
            from ..parallel.ppdecode import PipelinedDecoder
            from ..parallel.spmd import make_mesh
            n_st = len(cfg.boundaries) + 1
            mesh = make_mesh({"pp": n_st}, jax.devices()[:n_st])
            runner = PipelinedDecoder(params, config, mesh,
                                      max_seq=cfg.max_seq, dtype=dtype,
                                      boundaries=list(cfg.boundaries))
        elif cfg.tp_decode:
            # tensor-parallel single-stream decode: Megatron column/row
            # projections + head-sharded KV cache over a tp mesh spanning
            # the pod's devices (runtime.engine._place_tp_params);
            # composes with MAX_BATCH (the batcher wraps below) and
            # PREFILL_CHUNK. Divisibility validated above.
            from ..parallel.spmd import make_mesh
            from ..runtime.engine import DecodeEngine
            mesh = make_mesh({"tp": len(jax.devices())}, jax.devices())
            runner = DecodeEngine(params, config, max_seq=cfg.max_seq,
                                  dtype=dtype, prefill_chunk=pchunk,
                                  mesh=mesh)
            decode_stages = 1  # unstaged (tensor axis, not stage axis)
        elif (cfg.max_batch > 1 or cfg.inference_dtype == "int8" or pchunk
              or cfg.prefix_cache > 0 or cfg.kv_pool_blocks > 0):
            # Continuous batching multiplexes concurrent requests onto
            # shared ragged batched decodes (runtime.batcher), riding the
            # staged DecodeEngine (single program per phase, ragged +
            # int8 + chunked-prefill support); int8, PREFILL_CHUNK, and
            # PREFIX_CACHE also need the engine (the per-device
            # PipelineRunner casts float dtypes but neither quantizes,
            # chunks its prefill, nor holds reusable KV state).
            # The PipelineRunner stays the plain single-stream path.
            from ..runtime.engine import DecodeEngine
            if cfg.kv_pool_blocks > 0:
                # paged KV storage gathers/scatters whole-model cache
                # rows, so the engine runs unstaged (per-stage cache
                # lists page in a later PR)
                runner = DecodeEngine(params, config, max_seq=cfg.max_seq,
                                      dtype=dtype)
                decode_stages = 1
            else:
                runner = DecodeEngine(params, config, max_seq=cfg.max_seq,
                                      boundaries=list(cfg.boundaries),
                                      dtype=dtype, prefill_chunk=pchunk)
        else:
            runner = PipelineRunner(params, config, list(cfg.boundaries),
                                    max_seq=cfg.max_seq, dtype=dtype)
        if switcher is not None:
            # continuous mode built its engine, pool, and certified
            # plan set above; admissions route through the switcher
            pass
        elif cfg.kv_pool_blocks > 0:
            # the paged KV block pool (runtime.kv_pool): one ref-counted
            # block store shared by the prefix store and whichever
            # decode front end serves /generate. An INJECTED pool
            # (graftfleet) is shared across replica apps — prefill
            # replicas fill its registry, decode replicas adopt the
            # blocks zero-copy; geometry is validated against this
            # app's engine below (PagedKVRunner / PrefixCachingEngine
            # constructors), same as an owned pool.
            if kv_pool is not None:
                eng_ = (spec_runner.plain if spec_runner is not None
                        else runner)
                if kv_pool.max_seq != eng_._cache_seq:
                    raise ValueError(
                        f"injected kv_pool spans {kv_pool.max_seq} "
                        f"slots, engine cache is {eng_._cache_seq} — "
                        "shared-pool replicas must agree on geometry")
                # storage regime is geometry too: a decode replica
                # gathering f32 views from a pool a prefill replica
                # filled as int8 (or vice versa) would be a silent
                # cross-replica numerics mismatch
                from ..utils.graftnum import regime_of as _regime_of
                want = (_regime_of(cfg.kv_pool_dtype)
                        if cfg.kv_pool_dtype else None)
                if kv_pool.block_dtype != want:
                    raise ValueError(
                        f"injected kv_pool stores {kv_pool.block_regime} "
                        f"blocks, KV_POOL_DTYPE={cfg.kv_pool_dtype!r} — "
                        "shared-pool replicas must agree on block "
                        "storage")
            else:
                from ..runtime.kv_pool import KVBlockPool
                kv_pool = KVBlockPool.for_engine(
                    spec_runner.plain if spec_runner is not None
                    else runner,
                    num_blocks=cfg.kv_pool_blocks,
                    block_size=cfg.kv_block_size,
                    block_dtype=cfg.kv_pool_dtype or None,
                    # a family whose rows hold a state beside their
                    # positions: a slab slot a stored prefix's snapshot
                    # (a live row's record is a lane of its batch's
                    # working cache; one where there is no store, for
                    # the slab says the family has a row state; ignored
                    # by every other family)
                    state_slots=max(cfg.prefix_cache, 1))
        elif kv_pool is not None:
            raise ValueError("kv_pool injected but KV_POOL_BLOCKS=0 — "
                             "a silently unused pool would misreport "
                             "the serving composition")
        if (kv_pool is not None and cfg.kv_host_blocks > 0
                and kv_pool.tier is None):
            # grafttier host spill tier (runtime.kv_tier): cold prefix
            # entries demote to bounded host RAM instead of LRU-evicting
            # to oblivion, and promote back on an affinity hit. An
            # injected pool may arrive with its tier already attached
            # (graftfleet replicas share the pool AND its tier).
            from ..runtime.kv_tier import HostKVTier
            kv_pool.attach_tier(HostKVTier(cfg.kv_host_blocks))
        prefix_runner = None
        if cfg.prefix_cache > 0:
            # cross-request KV reuse (runtime.prefix_cache): wraps the
            # plain single-stream engine built above; with SPEC_DECODE
            # also on, the verify loop decodes off the prefix-built
            # cache. With a KV pool, store entries hold ref-counted
            # block ids (structural sharing + LRU under pool pressure)
            # instead of full cache copies.
            from ..runtime.prefix_cache import PrefixCachingEngine
            prefix_runner = PrefixCachingEngine(
                runner, capacity=cfg.prefix_cache,
                chunk=cfg.prefix_chunk or cfg.prefill_chunk or 64,
                spec=spec_runner, pool=kv_pool)
            runner = prefix_runner
        if switcher is not None:
            pass   # the plan set IS the batching decision, per wave
        elif cfg.max_batch > 1:
            base = (prefix_runner.plain if prefix_runner is not None
                    else runner)
            if cfg.batch_mode == "iter":
                # iteration-level scheduling: requests join the live
                # batch at the next decode segment; early-EOS rows free
                # their slot (runtime.iterbatch; exclusions validated
                # above, so ``base`` here is always a DecodeEngine).
                # SPEC_DECODE batches advance by draft-verify segments;
                # PREFIX_CACHE backs admission prefills with the store;
                # KV_POOL_BLOCKS pages row state with watermark
                # admission and preemption/resume.
                from ..runtime.iterbatch import IterBatchingEngine
                runner = IterBatchingEngine(base,
                                            max_batch=cfg.max_batch,
                                            max_wait_ms=cfg.batch_wait_ms,
                                            spec=spec_runner,
                                            prefix=prefix_runner,
                                            pool=kv_pool,
                                            replica=replica_label)
            else:
                from ..runtime.batcher import BatchingEngine
                runner = BatchingEngine(base, max_batch=cfg.max_batch,
                                        max_wait_ms=cfg.batch_wait_ms,
                                        prefix=prefix_runner,
                                        spec=spec_runner)
        elif kv_pool is not None:
            # solo paged decode: the engine's own programs on
            # pool-backed storage; a prefix hit REFERENCES store blocks
            # instead of copying the prefill state
            from ..runtime.kv_pool import PagedKVRunner
            runner = PagedKVRunner(
                prefix_runner.plain if prefix_runner is not None
                else runner, kv_pool, prefix=prefix_runner)
    if not partitionable:
        compat_specs = compat_params = None
    else:
        compat_specs = P_.make_stage_specs(n_layer, [cfg.split_at])
        compat_params = {
            role: ((stage_only if stage_only is not None
                    else P_.extract_stage_params(params, compat_specs[i]))
                   if cfg.shard_role == role else None)
            for i, role in enumerate(("a", "b"))
        }

    app = JSONApp(title="llm-sharding-demo-tpu", version="0.1.0")

    @app.get("/metrics")
    def metrics():
        # Prometheus text exposition (the reference has no metrics at all,
        # SURVEY.md §5): request counters, gauges + latency histograms.
        return reg.prometheus()

    def _topology() -> dict:
        """The decode topology/composition ACTUALLY serving /generate —
        the single source for /healthz and the flight-recorder header
        (/debug/requests), so the two can never disagree."""
        topo = {
            "role": cfg.shard_role,
            "model": cfg.model_id,
            "n_stages": decode_stages,
            "dispatch": cfg.dispatch,
            "max_batch": cfg.max_batch,
            "batch_mode": cfg.batch_mode,
            "inference_dtype": cfg.inference_dtype,
            "spec_decode": cfg.spec_decode,
            "prefill_chunk": cfg.prefill_chunk,
            "prefix_cache": cfg.prefix_cache,
            "pp_decode": cfg.pp_decode,
            "ep_decode": cfg.ep_decode,
            "tp_decode": cfg.tp_decode,
            "kv_pool_blocks": cfg.kv_pool_blocks,
            "kv_block_size": cfg.kv_block_size,
            "kv_pool_dtype": cfg.kv_pool_dtype,
            "kv_host_blocks": cfg.kv_host_blocks,
            # graftfleet (llm_sharding_demo_tpu/fleet): this replica's
            # declared role and the prefix-store alignment width the
            # router's affinity keys must match
            "fleet_role": cfg.fleet_role,
            "prefix_chunk": cfg.prefix_chunk,
        }
        if switcher is not None:
            # continuous mode (graftwatch): auto_plan is LIVE, not
            # startup-only — the current plan, switch count, and wave
            # config, merged over any startup-planner row
            topo["auto_plan"] = {**(auto_plan_info or {}),
                                 **switcher.health_view()}
        elif auto_plan_info is not None:
            # how the knobs above were resolved (AUTO_PLAN=1): the
            # planner's chosen row, so monitoring can tell a planned
            # topology from a hand-tuned one
            topo["auto_plan"] = auto_plan_info
        return topo

    @app.get("/healthz")
    def healthz():
        live = {}
        from ..runtime.iterbatch import IterBatchingEngine as _IB
        if switcher is not None:
            # continuous mode: the pooled scheduler's stats stay
            # visible whichever plan is active (its worker lives for
            # the process; "active" rides the auto_plan block)
            for _r in switcher.plans.values():
                if isinstance(_r, _IB):
                    live["iter_batch_stats"] = _r.stats()
        elif isinstance(runner, _IB):
            # iteration-level scheduler: joins/segments/eos-retires
            # (spec_segments counts draft-verify segments when
            # SPEC_DECODE composes)
            live["iter_batch_stats"] = runner.stats()
            if runner.prefix is not None:
                live["prefix_cache_stats"] = runner.prefix.stats()
        else:
            # prefix cache: live hit/miss/entries — directly, or through
            # the batcher when PREFIX_CACHE composes with MAX_BATCH>1
            prefix_src = getattr(runner, "prefix", None)
            if prefix_src is None and hasattr(runner, "stats"):
                prefix_src = runner
            if prefix_src is not None and hasattr(prefix_src, "stats"):
                live["prefix_cache_stats"] = prefix_src.stats()
        if spec_runner is not None:  # speculation: live acceptance stats
            live["spec_decode_stats"] = spec_runner.stats()
        if kv_pool is not None:  # paged KV memory: allocator truth
            st = kv_pool.stats()
            # Pool-stats conservation invariant (graftsan satellite):
            # every block is free or referenced, never both or neither.
            # Drift here means the allocator's accounting broke — turn
            # it into a 500 (the handler's uncaught-exception path)
            # instead of serving a silently wrong gauge.
            if st["blocks_in_use"] + st["blocks_free"] != st["blocks_total"]:
                raise AssertionError(
                    "kv_pool_stats conservation violated: "
                    f"{st['blocks_in_use']} in_use + {st['blocks_free']} "
                    f"free != {st['blocks_total']} total")
            # HBM bytes of the pool's device planes, from the graftmem
            # ledger (codes + quantized scales) — NEVER re-derived from
            # shape arithmetic here, so byte reporting has exactly one
            # bookkeeping path (the blocks-conservation discipline,
            # applied to bytes)
            st["pool_bytes"] = (
                graftmem.holding_bytes(kv_pool, "data")
                + graftmem.holding_bytes(kv_pool, "scales")
                + graftmem.holding_bytes(kv_pool, "latent"))
            if kv_pool.tier is not None:
                # Per-tier conservation (the grafttier analog of the
                # block assert above): entries, occupancy, and the
                # movement ledger must agree, and the tier block's
                # measured host_bytes is the graftmem host_spill
                # component's own bookkeeping (holding_bytes) — drift
                # turns the health check red, not a silently wrong
                # capacity report.
                kv_pool.tier.graftsan_check("healthz")
            live["kv_pool_stats"] = st
        # Byte-conservation invariant (the blocks_in_use + blocks_free
        # == blocks_total pattern, applied to the HBM ledger): the
        # per-entry table must agree with the running component/grand
        # totals. Drift means the ledger's accounting broke — 500, not
        # a silently wrong /debug/memory.
        mem = graftmem.snapshot()
        if graftmem.enabled() and not mem["conserved"]:
            raise AssertionError(
                "graftmem byte conservation violated: component sum "
                f"{mem['components']} disagrees with ledger total "
                f"{mem['total_bytes']}")
        # Live placement auditor (utils/graftshard, GRAFTSHARD=1):
        # armed/checks/violations/tracked, so operators can see whether
        # placement discipline is being enforced — and a violation that
        # slipped past the raise path (audit-only drift) turns the
        # health check red instead of hiding in a log.
        shard_status = graftshard.status()
        if shard_status["enabled"]:
            shard_status["audit"] = graftshard.audit()
            if shard_status["audit"]:
                raise AssertionError(
                    "graftshard placement contract violated: "
                    f"{shard_status['audit']}")
        return {
            **live,
            "status": "ok",
            "graftshard": shard_status,
            # trend-watch state (utils/grafttrend): declared watch
            # count, evaluation count, and any LATCHED trips — a page
            # that fired is visible on the health probe, not only on
            # the debug surface
            "trend": trend_reducer.health_view(),
            **_topology(),
            "devices": [str(d) for d in jax.devices()],
        }

    @app.get("/debug/requests")
    def debug_requests(query: dict):
        """Flight recorder: JSON span timelines of the last N completed
        /generate requests (bounded ring — see utils.tracing.
        FlightRecorder). ``?n=K`` caps the rows returned, ``?slowest=1``
        orders by duration instead of recency — the view that answers
        "where did that slow request's time go" without a profiler —
        and ``?errors=1`` keeps only failed requests (error-labeled
        traces: timeouts, shed 429s, typed 503s, upstream failures),
        the fault-triage view graftfault's degraded paths feed.
        ``?profile=<label>`` keeps only requests carrying that
        X-Workload-Profile label — the view that triages ONE graftload
        workload profile's slow/failed requests out of a mixed run
        (composes with ``errors``/``slowest``). Under the iter
        scheduler the payload also carries ``scheduler``: the scheduler
        thread's seconds by state and its newest intervals
        (``utils.tracing.StateLog``), the time in which there was no
        request or the host held one up, which no request's tree
        holds."""
        return tracing.debug_requests_payload(
            rec, query, _topology(),
            scheduler=getattr(runner, "states", None))

    @app.get("/debug/profile")
    def debug_profile(query: dict):
        """graftscope attribution view (utils/graftscope): bounded
        per-program dispatch-timing rings for every PROFILED_SCOPES jit
        entry point plus the occupancy time series (pool blocks in use,
        batch occupancy, queue depth). ``?n=K`` caps ring samples and
        series points per entry. Honesty header rides the payload: the
        dispatch numbers are serving-thread enqueue windows unless sync
        mode is armed (never in serving) — device-level truth is the
        profiler trace's job, exactly as utils/tracing documents."""
        try:
            n = int(query.get("n", "32"))
        except ValueError:
            return 422, {"detail": "n must be an integer"}
        from ..utils import graftscope
        return {
            "serving": _topology(),
            **graftscope.snapshot(n=n),
        }

    @app.get("/debug/plan")
    def debug_plan(query: dict):
        """Continuous-planning decision state (utils/graftwatch): the
        active plan, per-plan scores under the live windowed estimate,
        calibrated byte weights, each plan's certified program cost,
        the bounded switch-event journal (``?n=K`` caps events), and
        the declared PLAN_SIGNALS provenance map with live signal
        values. Off continuous mode the payload still answers (mode
        "startup"/"off") so monitoring can tell WHY there is no switch
        history instead of reading a 404."""
        if switcher is None:
            return {
                "serving": _topology(),
                "mode": "startup" if auto_plan_info is not None
                else "off",
                "auto_plan": auto_plan_info,
            }
        try:
            n = int(query.get("n", "16"))
        except ValueError:
            return 422, {"detail": "n must be an integer"}
        return {"serving": _topology(), **switcher.describe(n=n)}

    @app.get("/debug/memory")
    def debug_memory():
        """graftmem HBM ledger view (utils/graftmem): the per-component
        live-byte table with peaks and per-device attribution, the
        hottest registered holdings, the conservation verdict, and —
        when a pool serves — the pool geometry with its ledger-derived
        ``pool_bytes``. Bytes are live jax buffer nbytes over
        REGISTERED holdings (the MEMORY_LEDGER contract; the payload's
        honesty header spells what is and is not counted). Same
        topology header as /healthz (pinned equal by tests)."""
        body = {
            "serving": _topology(),
            **graftmem.snapshot(),
        }
        if kv_pool is not None:
            st = kv_pool.stats()
            st["pool_bytes"] = (
                graftmem.holding_bytes(kv_pool, "data")
                + graftmem.holding_bytes(kv_pool, "scales")
                + graftmem.holding_bytes(kv_pool, "latent"))
            body["pool"] = st
        return body

    @app.get("/debug")
    def debug_index():
        """The debug-surface index: every /debug/* endpoint with a
        one-line description, under the SAME topology header as
        /healthz (pinned equal by tests) — operators stop guessing
        URLs and stop wondering which composition a surface reflects."""
        return {
            "serving": _topology(),
            "surfaces": {
                "/debug/requests": (
                    "flight recorder: span trees of the last N "
                    "requests (?n, ?slowest=1, ?errors=1, ?profile=)"),
                "/debug/profile": (
                    "graftscope attribution: per-program dispatch "
                    "rings + occupancy time series (?n)"),
                "/debug/plan": (
                    "graftwatch continuous-planning decision state: "
                    "active plan, scores, switch journal (?n)"),
                "/debug/timeline": (
                    "grafttime unified causal event stream, one clock "
                    "over spans/dispatches/faults/plan switches "
                    "(?rid=, ?since=, ?since_seq=, ?kinds=, ?n=)"),
                "/debug/trend": (
                    "grafttrend watch state: declared WATCH_POLICY "
                    "verdicts, windowed series reductions, alert "
                    "journal, refit history (?eval=0 reads without "
                    "polling/evaluating)"),
                "/debug/memory": (
                    "graftmem HBM ledger: per-component live bytes, "
                    "peaks, per-device attribution, pool geometry, "
                    "byte-conservation verdict"),
            },
        }

    @app.get("/debug/timeline")
    def debug_timeline(query: dict):
        """The unified causal timeline (utils/grafttime): every
        producer's typed events on one monotonic clock. ``?rid=``
        keeps one request's causal stream (shared batched phases
        included — they carry the rid set), ``?since=`` is an
        exclusive ms lower bound on the bus clock, ``?kinds=`` a
        comma-separated vocabulary filter, ``?n=`` caps to the newest
        n. Export the payload with ``python -m tools.grafttime
        export`` for chrome://tracing / Perfetto."""
        return grafttime.debug_timeline_payload(query, _topology())

    @app.get("/debug/trend")
    def debug_trend(query: dict):
        """Trend & drift watch state (utils/grafttrend): per-watch
        verdicts against the declared WATCH_POLICY, windowed series
        reductions (rate, p50/p99 sketch), the bounded alert journal,
        and the refit history. The default GET is the poll-on-read
        loop: it taps the live producers (registry histogram buckets,
        counters, gauges) and EVALUATES the watches — scraping this
        surface is the alerting cadence (trips latch, so repeated
        scrapes of a sustained burn alert once). ``?eval=0`` reads
        the current state without polling or evaluating."""
        if query.get("eval", "1") != "0":
            trend_reducer.poll()
            trend_reducer.evaluate()
        return {"serving": _topology(), **trend_reducer.describe()}

    @app.post("/prefill")
    def prefill(req: PrefillReq, headers: dict):
        # thin wrapper: the replica label rides every timeline event
        # this request emits (grafttime's ambient replica correlator)
        with grafttime.use_replica(replica_label):
            return _prefill(req, headers)

    def _prefill(req: PrefillReq, headers: dict):
        """graftfleet prefill-replica endpoint: run the prompt's
        chunk-aligned prefill and FILL shared pool blocks — the walk
        lands every full-chunk prefix state in the pool's content-keyed
        registry (``register_prefix``, the registry holding its own
        refs), where decode replicas adopt it zero-copy via
        ``prefill_shared``. Nothing but the prompt crosses the hop and
        nothing but block ids change hands afterward: transfer is
        block handoff, never a tensor copy (fleet/topology.py
        HANDOFF_POLICY documents the lifetime rule). Typed sheds ride
        the same paths as /generate: pool saturation answers 429 +
        Retry-After, an exhausted X-Deadline-Ms budget 503."""
        rid, _profile = parse_request_identity(headers)
        hdrs = {"X-Request-ID": rid}

        def out(body, status=200):
            return status, body, hdrs

        if cfg.fleet_role != "prefill":
            return out({"error": "This instance is not a fleet "
                                 "prefill replica."}, status=400)
        # the FLEET_ROLE guard in utils.config makes this unreachable
        # (prefill requires the pool-backed store); belt and braces for
        # injected-model tests that bypass from_env
        if prefix_runner is None or kv_pool is None:
            return out({"error": "prefill replicas need the pool-backed "
                                 "prefix store (KV_POOL_BLOCKS + "
                                 "PREFIX_CACHE)"}, status=400)
        deadline, _dl_ms, dl_err = parse_deadline_header(headers)
        if dl_err:
            return out({"error": dl_err}, status=400)
        trace = tracing.RequestTrace(rid, fleet="prefill")

        def reject(msg: str):
            # a proper 400, flight-recorded: /prefill is a new
            # non-parity endpoint, and the router keys its degraded-
            # warm accounting on the status code — a 200-with-error
            # body would count as a successful warm
            trace.labels.update(error=msg)
            rec.record(trace)
            return out({"error": msg}, status=400)

        with trace.span("tokenize"):
            prompt_ids = tokenizer.encode(req.prompt)
        if not prompt_ids:
            return reject("prompt tokenized to zero tokens")
        if len(prompt_ids) >= cfg.max_seq:
            return reject(f"prompt ({len(prompt_ids)} tokens) leaves "
                          f"no forward room under max_seq "
                          f"({cfg.max_seq})")
        chunk = prefix_runner.chunk
        m_total = (len(prompt_ids) - 1) // chunk
        alloc = kv_pool.allocator
        # admission: a registry fill the pool cannot host is SHED, not
        # queued — the 429 + Retry-After discipline every fleet hop
        # shares (the walk itself also degrades gracefully on a full
        # pool, skipping the insert; this gate sheds before paying the
        # prefill compute)
        need = alloc.blocks_for(m_total * chunk)
        if need:
            # registered prefixes SHARE blocks (_insert_pool): a warm
            # repeat fill allocates nothing, and a partial hit only the
            # new chunks' blocks — gate on that marginal need, or warm
            # prefills (the replica's whole point) get shed whenever
            # the pool is busy. has_prefix takes no leases: this walk
            # is the same key ladder _lookup descends, refs deferred to
            # the walk itself.
            arr = np.asarray(prompt_ids, dtype=np.int32)
            key_of = prefix_runner._key
            if alloc.has_prefix(key_of(arr, m_total, chunk)):
                need = 0
            else:
                for m in range(m_total - 1, 0, -1):
                    if alloc.has_prefix(key_of(arr, m, chunk)):
                        need -= (m * chunk) // kv_pool.block_size
                        break
        if need > 0 and alloc.available() < need:
            reg.inc("kv_pool_admission_rejections_total")
            hdrs["Retry-After"] = "1"
            trace.labels.update(error="kv_pool_saturated")
            rec.record(trace)
            return out({"error": "kv_pool_saturated",
                        "detail": "pool cannot host this prefix fill; "
                                  "retry after the indicated backoff"},
                       status=429)
        try:
            if deadline is not None:
                deadline.raise_if_expired("prefill")
            with tracing.use_trace(trace):
                _logits, _cache, shared_ids, depth = \
                    prefix_runner.prefill_shared(
                        np.asarray(prompt_ids, dtype=np.int32))
            # the walk's caller refs are released immediately: the
            # REGISTRY holds the entry's own refs, and this endpoint
            # hands off ids by content key, never by lease
            alloc.free(shared_ids)
        except graftfault.Unavailable as e:
            hdrs["Retry-After"] = str(max(1, int(round(e.retry_after))))
            if e.code == "deadline_exceeded":
                reg.inc("deadline_misses_total")
            trace.labels.update(error=e.code)
            rec.record(trace)
            # post-mortem black box (grafttime): the events that led
            # to the typed failure, journaled before the ring rotates
            grafttime.blackbox(e.code, rid=rid)
            return out({"error": e.code, "detail": str(e)}, status=503)
        except Exception as e:  # noqa: BLE001 — flight-record + echo id
            trace.labels.update(error=f"{type(e).__name__}: {e}")
            rec.record(trace)
            from ..runtime.kv_pool import GraftsanError
            if isinstance(e, GraftsanError):
                grafttime.blackbox(f"graftsan:{type(e).__name__}",
                                   rid=rid)
            return out({"detail": f"{type(e).__name__}: {e}"}, status=500)
        trace.labels.update(registered_tokens=depth)
        rec.record(trace)
        return out({"registered_tokens": depth,
                    "prefix_entries": alloc.prefix_len(),
                    "chunk": chunk})

    @app.post("/forward")
    def forward_a(req: InputIDs):
        if cfg.shard_role != "a":
            return {"error": "This instance is not shard A."}
        if not partitionable:
            return {"error": "stage endpoints serve dense GPT-2 only; "
                             f"{type(config).__name__} models generate "
                             "via /generate"}
        ids = jnp.asarray([req.input_ids], dtype=jnp.int32)
        hidden, _ = P_.stage_apply(compat_params["a"], compat_specs[0],
                                   config, ids)
        return {"hidden_states": np.asarray(hidden).tolist()}

    @app.post("/forward_b")
    def forward_b(req: HiddenStates):
        if cfg.shard_role != "b":
            return {"error": "This instance is not shard B."}
        if not partitionable:
            return {"error": "stage endpoints serve dense GPT-2 only; "
                             f"{type(config).__name__} models generate "
                             "via /generate"}
        hidden = jnp.asarray(np.asarray(req.hidden_states, dtype=np.float32))
        logits, _ = P_.stage_apply(compat_params["b"], compat_specs[1],
                                   config, hidden)
        return {"logits": np.asarray(logits).tolist()}

    def _generate_local(req: GenerateReq, prompt_ids: List[int],
                        eos_id: Optional[int] = None,
                        deadline: Optional[graftfault.Deadline] = None,
                        ) -> List[int]:
        sampling = (SamplingConfig(mode="greedy") if req.mode == "greedy"
                    else SamplingConfig(mode="sample",
                                        temperature=req.temperature,
                                        top_k=req.top_k,
                                        top_p=req.top_p))
        seed = req.seed if req.seed is not None else int(
            np.random.default_rng().integers(2 ** 31))
        # Speculation serves only the requests it is exact and safe for:
        # prompt at least ngram long and draft_len slots of cache headroom
        # left (greedy is token-exact, sample distribution-exact via
        # rejection sampling). Everything else uses the plain engine —
        # same weights, just one token per forward. With PREFIX_CACHE on
        # (solo), the prefix engine IS the entry point and applies the
        # same spec eligibility internally (runtime.prefix_cache).
        # Behind a batching front end (MAX_BATCH>1), routing is the
        # ``SamplingConfig.spec`` flag: flagged requests gather into
        # spec-only rounds/batches (policy equality keeps FIFO) and
        # decode through the batched verify loop.
        eng = runner
        plan_release = None
        if switcher is not None:
            # continuous mode: ONE admission observation per request,
            # wave-boundary re-planning inside admit(), and the plan
            # that serves THIS request returned — in-flight requests
            # keep the runner they were admitted to across a switch
            # (both front ends share every compiled program and the
            # one block pool, so nothing leaks and nothing recompiles)
            eng, plan_label = switcher.admit(len(prompt_ids),
                                             req.max_new_tokens)
            plan_release = switcher.release
            tr = tracing.current_trace()
            if tr is not None:
                tr.labels.update(plan=plan_label)
        # the try/finally opens HERE, not at the generate call: anything
        # below can raise (the deadline pre-check especially — expired
        # budgets are routine under the abandonment profile), and a
        # skipped release would leak the watcher's in-flight estimate
        # permanently, biasing every later plan decision wide
        try:
            import dataclasses as _dc

            from ..runtime.batcher import BatchingEngine as _BE
            from ..runtime.engine import DecodeEngine as _DE
            from ..runtime.iterbatch import IterBatchingEngine as _IB
            eligible = (spec_runner is not None
                        and spec_runner.eligible(len(prompt_ids),
                                                 req.max_new_tokens))
            if eligible and isinstance(runner, (_BE, _IB)):
                sampling = _dc.replace(sampling, spec=True)
            elif eligible and cfg.prefix_cache == 0:
                eng = spec_runner
            from ..runtime.kv_pool import PagedKVRunner as _PR
            kw = {}
            if eos_id is not None and isinstance(eng, (_DE, _IB, _PR)):
                # segment-boundary early exit: stop_at_eos requests stop
                # paying device time for dead tokens past the stop
                # (tokens emitted are the exact prefix of the uncapped
                # stream; the iter scheduler additionally frees the
                # row's slot). Other runners (spec/prefix/admission-
                # batcher/pipeline) keep the host-side truncation below
                # — same wire result.
                kw["eos_id"] = eos_id
            if deadline is not None:
                # the deadline budget is honored END-TO-END on the iter
                # scheduler (queue wait, segment-boundary cancellation
                # with blocks freed) and per-hop on remote dispatch;
                # other runners at least refuse work the budget cannot
                # cover
                deadline.raise_if_expired("generate")
                if isinstance(eng, _IB):
                    kw["deadline"] = deadline
            result = eng.generate(np.asarray(prompt_ids),
                                  max_new_tokens=req.max_new_tokens,
                                  sampling=sampling,
                                  key=jax.random.PRNGKey(seed), **kw)
        finally:
            if plan_release is not None:
                plan_release()   # the watcher's in-flight estimate
        # row_tokens strips any left pad the engine introduced (chunked
        # prefill alignment); plain runs return the row unchanged
        return [int(t) for t in result.row_tokens(0)]

    # One hop discipline for every coordinator->shard POST
    # (utils/graftfault.HopPolicy): capped exponential backoff + seeded
    # jitter between attempts, a per-request retry budget, and a
    # per-shard circuit breaker — a dead shard fails fast with a typed
    # 503 + Retry-After instead of stacking 30s timeouts. Each retry is
    # counted into shard_hop_retries_total{stage,reason}. UpstreamError
    # (an error BODY from a live shard — misroute, missing key) is
    # fatal: repetition does not fix routing.
    hop_policy = graftfault.HopPolicy(
        attempts=3, timeout_s=30.0, base_backoff_s=0.25,
        max_backoff_s=2.0, breaker_threshold=5, breaker_cooldown_s=5.0,
        fatal=(UpstreamError,), registry=reg,
        on_retry=lambda shard, reason: reg.inc(
            "shard_hop_retries_total", stage=shard, reason=reason))

    def _relay(shard: str, url: str, payload: dict, key: str,
               deadline: Optional[graftfault.Deadline] = None):
        """One shard hop through the typed HopPolicy.

        Failure modes the reference leaves raw (SURVEY.md §2.3.5: its
        role-guard 200s make raise_for_status useless and a misroute
        dies as a KeyError): connection errors/timeouts (retried under
        the policy's capped backoff, per-attempt timeout derived from
        the remaining deadline budget), HTTP errors, and
        200-with-``{"error"}`` bodies. Transport failures surface as
        UpstreamError -> a typed 502; an open breaker or an exhausted
        deadline surfaces as graftfault.Unavailable -> a typed 503 +
        Retry-After. Seeded fault injection (GRAFTFAULT) lands HERE,
        before the wire call, so the whole retry/breaker path replays
        deterministically.
        """
        import requests

        def attempt(timeout_s: float):
            kind = graftfault.inject("serving.shard_hop", "reset",
                                     "timeout", "http_503", "slow")
            if kind == "reset":
                raise requests.exceptions.ConnectionError(
                    "graftfault: injected connection reset")
            if kind == "timeout":
                raise requests.exceptions.Timeout(
                    "graftfault: injected hop timeout")
            if kind == "http_503":
                raise requests.exceptions.HTTPError(
                    "graftfault: injected shard 503")
            if kind == "slow":
                import time as _time
                _time.sleep(min(0.05, timeout_s))
            resp = requests.post(url, json=payload, timeout=timeout_s)
            resp.raise_for_status()
            body = resp.json()
            if key not in body:
                raise UpstreamError(
                    shard, url,
                    str(body.get("error", f"response missing {key!r}")))
            return body[key]

        try:
            return hop_policy.call(attempt, shard=shard,
                                   deadline=deadline)
        except (UpstreamError, graftfault.Unavailable):
            raise
        except requests.exceptions.RequestException as e:
            raise UpstreamError(shard, url, f"{type(e).__name__}: {e}")

    def _generate_remote(req: GenerateReq, prompt_ids: List[int],
                         eos_id: Optional[int] = None,
                         deadline: Optional[graftfault.Deadline] = None,
                         ) -> List[int]:
        """Reference-topology decode: per token, POST the full sequence to
        shard A, relay hidden states to shard B, sample host-side
        (reference server.py:169-206). O(n²) and JSON-lossy by design —
        it exists for wire-level drop-in compatibility, not speed.

        Sampling goes through ``engine.sampler_pmf`` — THE sampler
        definition — with a host-side ``rng.choice`` draw (seed contract:
        one numpy draw per token, as before). Unlike the fixed-length
        device scan, this Python loop CAN stop at EOS, saving the
        remaining per-token HTTP round trips."""
        from ..runtime.engine import sampler_pmf
        ids = list(prompt_ids)
        rng = np.random.default_rng(req.seed)
        sampling = (None if req.mode == "greedy" else
                    SamplingConfig(mode="sample",
                                   temperature=req.temperature,
                                   top_k=req.top_k, top_p=req.top_p))
        for _ in range(req.max_new_tokens):
            hidden = _relay("a", f"{cfg.shard_a_url}/forward",
                            {"input_ids": ids}, "hidden_states",
                            deadline=deadline)
            logits = np.asarray(_relay(
                "b", f"{cfg.shard_b_url}/forward_b",
                {"hidden_states": hidden}, "logits",
                deadline=deadline))[0, -1]
            if req.mode == "greedy":
                ids.append(int(np.argmax(logits)))
            else:
                probs, top_idx = sampler_pmf(jnp.asarray(logits), sampling)
                probs = np.asarray(probs, dtype=np.float64)
                ids.append(int(rng.choice(np.asarray(top_idx),
                                          p=probs / probs.sum())))
            if eos_id is not None and ids[-1] == eos_id:
                break
        return ids

    @app.post("/generate")
    def generate(req: GenerateReq, headers: dict):
        # thin wrapper: the replica label rides every timeline event
        # this request emits (grafttime's ambient replica correlator)
        with grafttime.use_replica(replica_label):
            return _generate(req, headers)

    def _generate(req: GenerateReq, headers: dict):
        # Request identity: every response (errors included) echoes the
        # X-Request-ID as a response header — the BODY stays wire-parity
        # with the reference ({"generated": ...}, server.py:210). The
        # X-Workload-Profile label (graftload) lets the flight recorder
        # filter per traffic shape (/debug/requests?profile=...).
        rid, profile_label = parse_request_identity(headers)
        hdrs = {"X-Request-ID": rid}

        def out(body, status=200):
            return status, body, hdrs

        if cfg.shard_role != "coordinator":
            return out({"error": "This instance is not coordinator."})
        if req.max_new_tokens < 1:
            return out({"error": "max_new_tokens must be >= 1"})
        # Per-request deadline budget (graftfault): ``X-Deadline-Ms``
        # caps the caller's total wait — HTTP wait, queue wait, shard
        # hop timeouts, and in-flight decode all derive from the
        # remaining budget; a row past its deadline is cancelled at the
        # next segment boundary with its blocks freed, and the caller
        # gets a typed 503 + Retry-After instead of a hung connection.
        deadline, dl_ms, dl_err = parse_deadline_header(headers)
        if dl_err:
            return out({"error": dl_err}, status=400)
        trace = tracing.RequestTrace(rid, mode=req.mode,
                                     dispatch=cfg.dispatch)
        if profile_label is not None:
            trace.labels.update(profile=profile_label)
        if deadline is not None:
            trace.labels.update(deadline_ms=dl_ms)
        with trace.span("tokenize"):
            prompt_ids = tokenizer.encode(req.prompt)
        if not prompt_ids:
            return out({"error": "prompt tokenized to zero tokens"})
        if len(prompt_ids) + req.max_new_tokens > cfg.max_seq:
            return out({"error": f"prompt ({len(prompt_ids)} tokens) + "
                        f"max_new_tokens ({req.max_new_tokens}) exceeds "
                        f"max_seq ({cfg.max_seq})"})
        if req.mode not in ("sample", "greedy"):
            return out({"error": f"unknown mode {req.mode!r}"})
        if req.mode == "sample":
            if req.temperature <= 0:
                return out({"error": "temperature must be > 0"})
            if not 1 <= req.top_k <= config.vocab_size:
                return out(
                    {"error": f"top_k must be in [1, {config.vocab_size}]"})
            if not 0.0 < req.top_p <= 1.0:
                return out({"error": "top_p must be in (0, 1]"})
        eos_id = None
        if req.stop_at_eos or req.eos_token_id is not None:
            eos_id = (req.eos_token_id if req.eos_token_id is not None
                      else getattr(tokenizer, "eos_token_id", None))
            if eos_id is None:
                return out({"error": "stop_at_eos requested but the "
                            "tokenizer has no eos_token_id; pass "
                            "eos_token_id explicitly"})
            if not 0 <= eos_id < config.vocab_size:
                return out(
                    {"error": f"eos_token_id {eos_id} out of vocab range"})
        if kv_pool is not None and cfg.dispatch == "local":
            # Admission control (runtime.kv_pool): a request the KV
            # pool cannot host — with the waiting line already at its
            # limit — is SHED with 429 + Retry-After instead of queued
            # unboundedly (the pre-pool behavior let the queue grow
            # without bound under sustained overload, trading it for
            # timeout storms). The iter scheduler owns the policy;
            # the solo paged runner rejects only what the pool could
            # never host right now.
            from ..runtime.iterbatch import IterBatchingEngine as _IB2
            # continuous mode gates against the ACTIVE plan (advisory,
            # like every admission answer here: the worker's actual
            # grant is the atomic admit_alloc path, so a wave switch
            # between this gate and dispatch costs one queue beat,
            # never a wrong failure)
            gate_runner = runner if switcher is None else switcher.peek()
            if isinstance(gate_runner, _IB2):
                ok, retry = gate_runner.admission_load(
                    len(prompt_ids), req.max_new_tokens)
            else:
                need = kv_pool.allocator.blocks_for(
                    len(prompt_ids) + req.max_new_tokens)
                # seeded pool-exhaustion spike (graftfault): the solo
                # paged runner's 429 gate sheds exactly as a full pool
                # would — the fleet router's per-replica shed/fallback
                # math is testable deterministically (the pooled iter
                # scheduler has the same site in admission_load)
                spike = graftfault.inject("serving.admission",
                                          "pool_spike")
                ok = (spike is None
                      and kv_pool.allocator.available() >= need)
                retry = 1.0
            if not ok:
                reg.inc("kv_pool_admission_rejections_total")
                hdrs["Retry-After"] = str(max(1, int(round(retry))))
                trace.labels.update(error="kv_pool_saturated")
                rec.record(trace)
                return out({"error": "kv_pool_saturated",
                            "detail": "KV memory pool cannot admit this "
                                      "request; retry after the "
                                      "indicated backoff"}, status=429)
        # The ambient trace rides the generation: solo runners record
        # prefill/decode spans directly; the batch schedulers capture it
        # onto their queue entry and stamp queue wait + shared phases
        # from the worker side (runtime.batcher / runtime.iterbatch).
        try:
            with timed("generate_request_seconds", registry=reg,
                       mode=req.mode, dispatch=cfg.dispatch):
                if cfg.dispatch == "remote":
                    try:
                        with tracing.use_trace(trace):
                            ids = _generate_remote(req, prompt_ids,
                                                   eos_id=eos_id,
                                                   deadline=deadline)
                    except UpstreamError as e:
                        # typed upstream failure (the reference propagates
                        # a raw exception -> opaque 500, server.py:173-180)
                        log.warning("upstream failure: %s", e)
                        reg.inc("upstream_failures_total", shard=e.shard)
                        trace.labels.update(error="upstream_failure",
                                            shard=e.shard)
                        rec.record(trace)
                        return out({"error": "upstream_failure",
                                    "shard": e.shard, "upstream": e.url,
                                    "detail": e.detail}, status=502)
                else:
                    with tracing.use_trace(trace):
                        ids = _generate_local(req, prompt_ids,
                                              eos_id=eos_id,
                                              deadline=deadline)
            # the response-assembly tail (EOS truncation, detokenize,
            # latency derivation) stays INSIDE the try: a decode error
            # surfacing there must still flight-record and echo the id
            finish_reason = "length"
            # tokens actually DECODED — captured before the host-side
            # EOS truncation below, so TPOT divides decode wall time by
            # the steps the device really ran, not the kept prefix (an
            # early EOS would otherwise inflate TPOT ~budget/kept-fold)
            n_decoded = len(ids) - len(prompt_ids)
            if eos_id is not None:
                # truncate at the first EOS among the NEW tokens (the
                # decode scan is fixed-length on device; stopping is a
                # host-side truncation, the standard serving semantics)
                new = ids[len(prompt_ids):]
                if eos_id in new:
                    ids = ids[:len(prompt_ids) + new.index(eos_id)]
                    finish_reason = "stop"
            n_new = len(ids) - len(prompt_ids)
            reg.inc("generate_requests_total", mode=req.mode)
            reg.inc("generated_tokens_total", value=n_new)
            log.info('{"event": "generate", "mode": "%s", '
                     '"request_id": "%s", "prompt_tokens": %d, '
                     '"new_tokens": %d, "finish_reason": "%s"}', req.mode,
                     rid, len(prompt_ids), n_new, finish_reason)
            with trace.span("detokenize"):
                try:
                    text = tokenizer.decode(ids, skip_special_tokens=True)
                except TypeError:  # ByteTokenizer takes no HF kwargs
                    text = tokenizer.decode(ids)
            trace.finish()
            # Latency split derived from the span tree. TTFT counts from
            # request arrival (queue wait included — what the caller
            # experiences) to the instant the first token EXISTED: the
            # prefill span's ready instant, or, where the span's own
            # window already waited for the device, its end; runners
            # without span instrumentation (PipelineRunner, remote
            # dispatch) fall back to the whole request. TPOT is the
            # time from there to the last decode span's ready instant
            # over the inter-token steps actually decoded; without
            # ready instants, the decode spans' wall time (spans that
            # waited for the device) over the same steps.
            pre = trace.find("prefill")
            pre_ready = None if pre is None else pre.ready
            if pre is None:
                ttft = trace.duration
            else:
                ttft = (pre.t1 if pre_ready is None else pre_ready) \
                    - trace.t0
            reg.observe("ttft_seconds", ttft, mode=req.mode)
            if n_decoded > 1:
                decode_spans = trace.find_all("decode")
                readies = [s.ready for s in decode_spans
                           if s.ready is not None]
                if readies and pre_ready is not None:
                    decode_wall = max(max(readies) - pre_ready, 0.0)
                elif decode_spans:
                    decode_wall = sum(s.duration for s in decode_spans)
                else:
                    decode_wall = max(trace.duration - ttft, 0.0)
                reg.observe("tpot_seconds", decode_wall / (n_decoded - 1),
                            mode=req.mode)
            trace.labels.update(prompt_tokens=len(prompt_ids),
                                new_tokens=n_new,
                                finish_reason=finish_reason,
                                ttft_ms=round(ttft * 1e3, 3))
            rec.record(trace)
        except graftfault.Unavailable as e:
            # typed degraded-mode unavailability (graftfault): deadline
            # budget exhausted, per-shard breaker open, transient-fault
            # park budget exhausted, or a permanent engine fault — 503 +
            # Retry-After with the partial span tree flight-recorded and
            # the X-Request-ID echoed, never an opaque 500
            hdrs["Retry-After"] = str(max(1, int(round(e.retry_after))))
            if e.code == "deadline_exceeded":
                # the SLO deadline_miss source series (loadgen
                # SLO_SOURCE_METRICS; the graftcheck slo pass verifies
                # this emission exists): accepted work that died on its
                # budget — distinct from the shed counters above
                reg.inc("deadline_misses_total")
            trace.labels.update(error=e.code)
            rec.record(trace)
            # post-mortem black box (grafttime): a typed Unavailable is
            # exactly the moment the causal stream must outlive the
            # ring — journal it (bounded; $GRAFTTIME_DIR adds a file)
            grafttime.blackbox(e.code, rid=rid)
            return out({"error": e.code, "detail": str(e)}, status=503)
        except Exception as e:  # noqa: BLE001 — a failed (e.g. timed-out)
            # generation is exactly the request the flight recorder must
            # keep, and the caller still needs its X-Request-ID echo;
            # body shape matches http.py's uncaught-500 {"detail": ...}
            trace.labels.update(error=f"{type(e).__name__}: {e}")
            rec.record(trace)
            from ..runtime.kv_pool import GraftsanError
            if isinstance(e, GraftsanError):
                # a sanitizer trap firing on the serving path is THE
                # black-box case: provenance + the event stream that
                # led to it, journaled at the instant it surfaced
                grafttime.blackbox(f"graftsan:{type(e).__name__}",
                                   rid=rid)
            return out({"detail": f"{type(e).__name__}: {e}"}, status=500)
        body = {"generated": text}
        if eos_id is not None:
            # extension field, absent in parity mode so the reference's
            # wire shape ({"generated": ...}, server.py:210) is untouched
            body["finish_reason"] = finish_reason
        return out(body)

    # continuous mode's decision state, exposed for the in-suite pins
    # (tests reach the certified plan set and the event journal through
    # the app object; the wire surface is GET /debug/plan)
    app.plan_switcher = switcher
    app.trend_reducer = trend_reducer
    # the object answering /generate (None on shard/remote roles): the
    # on-chip smoke reads the resolved decode kernel and the arrays'
    # placement from it — no wire surface reports either
    app.runner = runner
    return app


# Lazy module attribute so `from ...serving.app import app` builds the
# env-configured app on first access (the reference builds its app at
# import, server.py:129), while importing create_app for tests stays free.
# Cached: repeated access must not re-load the model.
def __getattr__(name: str):
    if name == "app":
        globals()["app"] = create_app()
        return globals()["app"]
    raise AttributeError(name)
