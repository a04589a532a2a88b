"""Decode engine: jitted prefill + scanned token loop, on-device sampling.

TPU-native replacement for the reference's coordinator decode loop
(reference server.py:154-210), which per token: re-POSTs the *entire*
sequence over HTTP to shard A, relays hidden states to shard B, pulls fp32
logits back to the host as JSON, and samples in numpy/torch
(server.py:169-206). Here the whole generation is two compiled programs:

- ``prefill``: one forward over the prompt, filling the KV cache;
- ``decode``: a single ``lax.scan`` over ``max_new_tokens`` whose body is
  the cached single-token step + on-device token selection. No
  host↔device traffic inside the loop, no re-forwarding (the KV cache is
  the fix for the reference's O(n²) loop — BASELINE.json config 5).

Token selection modes mirror the reference:

- ``greedy``: argmax — BASELINE.json's parity mode.
- ``sample``: temperature + top-k multinomial, the reference's hard-coded
  temperature=0.6 / top_k=40 sampler (server.py:187-206) — but with an
  explicit PRNG key instead of torch's unseeded global state (SURVEY.md
  §2.3.4: cross-framework RNG parity is impossible; we mirror the
  distribution math).

Batching is a leading batch dim; unequal-length prompts left-pad into a
rectangle with per-row position offsets and key masks (``left_pad`` /
``prepare_generate`` — the reference hardcodes batch=1, server.py:137),
and ``runtime.batcher`` multiplexes concurrent serving requests onto
these batched decodes.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.gpt2 import GPT2Config, Params
from ..ops.attention import KVCache
from ..utils import graftmem, graftscope, tracing
from ..utils.metrics import REGISTRY, CompileWatch, kv_block_gauges

# Reference sampler constants (server.py:188, 191).
REF_TEMPERATURE = 0.6
REF_TOP_K = 40

# EOS-armed decodes check for stop every this many steps (a multiple of
# the segment planner's quantum, so capping mints no new programs).
EOS_SEGMENT = 32


# Static-analysis contract (tools/graftcheck): every ``jax.jit`` call
# site in this module must appear here, named by the attribute/function
# holding the jitted callable — the recompile-budget certifier
# enumerates these, and an undeclared jit site is a lint finding (a
# compiled-program population the budget would silently miss).
# ``_decode_seg`` holds two forms of one body: without a ``steps``
# operand a ``lax.scan`` whose length (``len(step_keys)``) is part of
# the program's key; with it a counted loop whose length is an operand
# and NOT a key — one program serves every length up to
# ``len(step_keys)`` (the iteration scheduler's calls).
JIT_ENTRY_POINTS = ("_prefill", "_prefill_chunked", "_decode_seg")

# Observability contract (tools/graftcheck scope pass + utils/graftscope):
# every declared jit entry point whose dispatch is timed into the
# graftscope ring — wrapped in ``graftscope.instrument`` at the jit
# site, with a key_fn deriving the SAME program key the recompile
# certifier models, so measured rings join certified populations 1:1.
# An entry point neither listed here nor baselined with a justification
# is an ``unprofiled-entry-point`` finding.
PROFILED_SCOPES = ("_prefill", "_prefill_chunked", "_decode_seg")

# Donation contract (tools/graftcheck sanitize pass): the positional
# arguments each jitted entry point CONSUMES (donate_argnums). Callers
# must not re-read a donated buffer after the call, and any host view
# (np.asarray of a CPU jax array is zero-copy) of a value that flows
# into a donated slot must take an owning copy first — the
# donation-aliasing rules resolve call sites through this declaration.
DONATED_ARGS = {"_decode_seg": (2,)}

# Decode hot-loop scopes (tools/graftcheck host-sync rule): functions
# whose loop bodies sit between compiled decode dispatches, where an
# accidental ``.item()``/``np.asarray``/``float()`` on a device value
# stalls the dispatch pipeline. Intentional syncs are baselined in
# tools/graftcheck/baseline.txt with a justification.
GRAFTCHECK_HOT_LOOPS = ("DecodeEngine._decode_and_pack",)

# HBM-ledger contract (tools/graftcheck memory pass + utils/graftmem):
# the engine's long-lived device holdings, by graftmem component.
# ``params`` is the finalized weight tree — placed, quantized, or the
# staged slices (whichever copy the compiled programs actually read;
# registered once, AFTER mesh placement / stage partitioning settles
# which). ``cache`` is the contiguous decode working view: one ledger
# entry per in-flight ``_decode_and_pack`` (handle-keyed, so concurrent
# generates on one engine attribute independently), released where the
# last segment's output drops its alias on the donated prefill cache.
MEMORY_LEDGER = {
    "params": "params",
    "cache": "engine_cache",
}

# Numerics contract (tools/graftcheck numerics pass — the static half
# of graftnum): the engine's value-stream discipline. The compiled
# entry points carry the construction regime end to end (``carried``:
# params/cache/activations share ``self.dtype``, validated against
# graftnum.REGIMES in ``__init__`` with a typed error), and token
# selection runs f32 regardless of regime (``sampler_pmf`` upcasts the
# logits once — the "softmax and logits stay f32" half of the bf16/
# int8 prose, now traced). All entries exact: the f32 regime is the
# byte-pinned parity mode; approximate REGIMES are declared at their
# source modules (ops/quant.py -> decode.int8, ops/latent_decode.py
# -> decode.bf16) and measured by graftnum's oracle at the engine level.
PRECISION_CONTRACT = {
    "_prefill_impl": {"regime": "carried", "exact": True, "casts": ()},
    "_prefill_chunked_impl": {"regime": "carried", "exact": True,
                              "casts": ()},
    "_decode_seg_impl": {"regime": "carried", "exact": True,
                         "casts": ()},
    "sampler_pmf": {"regime": "f32", "exact": True, "casts": ("f32",)},
    "select_token": {"regime": "f32", "exact": True, "casts": ()},
}


# EOS check-cap doubling ceiling: checks land at 32, 64, 128, 256, 256...
# steps, so a long armed decode pays O(log) + steps/256 syncs instead of
# steps/32. Each check is a host sync that drains the dispatch pipeline
# (ADVICE r4: fixed 32-step checks can cost more than the dead tokens
# they save); the doubling schedule keeps the early checks
# (most exits are early) while bounding the sync tax on long tails at
# <1/256 steps. Worst-case overshoot past the EOS grows with the same
# schedule and stays ≤ the current check interval.
_EOS_CAP_MAX = 256


def _eos_capped_segments(segs: list) -> list:
    """Subdivide planner segments for EOS checking with doubling caps.
    Chunk sizes are planner quanta or powers of two between EOS_SEGMENT
    and ``_EOS_CAP_MAX`` — a bounded compiled-program set."""
    out = []
    cap = EOS_SEGMENT
    for n, w in segs:
        while n > 0:
            take = min(cap, n)
            out.append((take, w))
            n -= take
            cap = min(cap * 2, _EOS_CAP_MAX)
    return out


# graftscope program-key derivations — one per profiled entry point,
# reading the ACTUAL call operands in the exact model
# tools/graftcheck/recompile.py certifies (engine_call_keys), so the
# measured dispatch rings and the certified program populations join
# key-for-key (pinned by tests/test_graftscope.py).

def _prefill_scope_key(params, ids, pad):
    return (int(ids.shape[0]), int(ids.shape[1]), pad is not None)


def _prefill_chunked_scope_key(params, chunks, pad):
    return (int(chunks.shape[1]), int(chunks.shape[0]))


def _decode_seg_scope_key(params, token, cache, pad, step_keys, steps=None,
                          *, sampling, window):
    # a counted call's length is an operand and no key: its program is
    # keyed by the longest call it serves
    n = int(step_keys.shape[0])
    return (int(token.shape[0]), n if steps is None else f"<={n}", window,
            sampling,
            "per-row" if getattr(step_keys, "ndim", 2) == 3 else "one",
            pad is not None)


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Token-selection policy for one generate call.

    ``top_p`` (nucleus sampling, an extension beyond the reference's
    fixed top-k) further restricts the top-k survivors to the smallest
    prefix whose cumulative probability reaches ``top_p``; 1.0 disables
    it, reproducing the reference's math exactly.
    """

    mode: str = "greedy"  # "greedy" | "sample"
    temperature: float = REF_TEMPERATURE
    top_k: int = REF_TOP_K
    top_p: float = 1.0
    # Speculative-decoding routing flag: a spec-enabled policy batches
    # only with itself (SamplingConfig equality drives batch grouping,
    # so the existing FIFO policy-change handling applies unchanged) and
    # the batching front ends (runtime.batcher, runtime.iterbatch) route
    # such batches through the speculative engine. Pure routing
    # metadata: it never changes the sampler math — the spec engine
    # normalizes it away before compiling, so greedy stays token-exact
    # and sample keeps the same distribution.
    spec: bool = False

    def __post_init__(self):
        if self.mode not in ("greedy", "sample"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.mode == "sample":
            if self.temperature <= 0:
                raise ValueError("temperature must be > 0 for sampling")
            if self.top_k < 1:
                raise ValueError("top_k must be >= 1")
            if not 0.0 < self.top_p <= 1.0:
                raise ValueError("top_p must be in (0, 1]")


def sampler_pmf(logits: jnp.ndarray, sampling: SamplingConfig,
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[..., vocab] logits -> ``(probs, idx)`` each ``[..., k]``: the
    sampler's distribution over the top-k survivors, descending.

    THE single definition of the sampling distribution — ``select_token``
    draws from it and speculative decoding's rejection sampler accepts
    against it, so the two paths cannot drift apart. Temperature + top-k
    mirror the reference (server.py:187-205); ``top_p`` then zeroes
    survivors outside the smallest prefix with cumulative mass >= top_p
    (the first survivor always stays) and renormalizes.
    """
    scaled = logits.astype(jnp.float32) / sampling.temperature
    top_vals, top_idx = jax.lax.top_k(scaled, sampling.top_k)
    probs = jax.nn.softmax(top_vals, axis=-1)          # descending
    if sampling.top_p < 1.0:
        cum_before = jnp.cumsum(probs, axis=-1) - probs
        keep = cum_before < sampling.top_p             # keeps index 0 always
        probs = jnp.where(keep, probs, 0.0)
        probs = probs / probs.sum(axis=-1, keepdims=True)
    return probs, top_idx


def select_token(logits: jnp.ndarray, sampling: SamplingConfig,
                 key: Optional[jax.Array]) -> jnp.ndarray:
    """[B, vocab] last-position logits -> [B] int32 next tokens, on device.

    Greedy is plain argmax. Sample mode draws from ``sampler_pmf`` — the
    reference's temperature/top-k math (server.py:187-205) plus optional
    nucleus filtering — as one fused device computation (categorical over
    the k survivors, mapped back through the top-k indices).

    ``key`` is either ONE key (a single joint draw over the batch — the
    single-stream form) or a ``[B, 2]`` stack of per-row keys (one
    independent draw per row, so a row's stream depends only on its own
    key — the basis of batched seeded sampling, ``runtime.batcher``).
    At B=1 the two forms draw identical bits (the categorical's gumbel
    bits depend on the element count, not the leading shape), so a solo
    run and a one-row per-row run are byte-equal — pinned in tests.
    """
    if sampling.mode == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    probs, top_idx = sampler_pmf(logits, sampling)
    if key.ndim == 2:                                  # [B, 2] per-row keys
        choice = jax.vmap(
            lambda k, p: jax.random.categorical(k, jnp.log(p)))(key, probs)
    else:
        choice = jax.random.categorical(key, jnp.log(probs), axis=-1)  # [B]
    return jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)


def _split_keys(key: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(prefill_key, decode_key) from either key form: a single key
    splits once; a ``[B, 2]`` per-row stack splits per row (each row's
    derivation identical to a solo run's — the byte-equality basis of
    batched seeded sampling)."""
    if key.ndim == 2:
        pair = jax.vmap(jax.random.split)(key)         # [B, 2, 2]
        return pair[:, 0], pair[:, 1]
    return tuple(jax.random.split(key))


def _step_keys(decode_key: jax.Array, n: int) -> jax.Array:
    """Per-decode-step keys: ``[n, 2]`` for a single key, ``[n, B, 2]``
    for a per-row stack (the scan consumes axis 0 either way). Splits are
    prefix-stable (``split(k, n)[i]`` is independent of ``n``), so a
    row's stream does not change when the batcher's steps bucket
    over-decodes past its own max_new_tokens."""
    if decode_key.ndim == 2:
        return jax.vmap(
            lambda k: jax.random.split(k, n))(decode_key).transpose(1, 0, 2)
    return jax.random.split(decode_key, n)


def left_pad(prompts, pad_id: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Ragged prompt list -> (ids [B, S_max] left-padded, pad [B]).

    Left-padding (not right-) is the TPU-shaped choice: every row's last
    prompt token lands in the same column, so prefill sampling reads one
    column, decode cache writes use one uniform ``dynamic_update_slice``
    offset for the whole batch, and no per-row scatter is ever needed. The
    pad prefix is excluded via per-row position offsets and the
    ``k_valid_from`` attention mask (ops.attention.causal_attention).
    """
    rows = [np.asarray(p, dtype=np.int32).reshape(-1) for p in prompts]
    if any(len(r) < 1 for r in rows):
        raise ValueError("every prompt must be non-empty")
    s_max = max(len(r) for r in rows)
    ids = np.full((len(rows), s_max), pad_id, dtype=np.int32)
    pad = np.zeros((len(rows),), dtype=np.int32)
    for i, r in enumerate(rows):
        ids[i, s_max - len(r):] = r
        pad[i] = s_max - len(r)
    return ids, pad


def prepare_generate(prompt_ids, max_new_tokens: int, max_seq: int,
                     sampling: SamplingConfig, key: Optional[jax.Array],
                     allow_ragged: bool = True,
                     pad: Optional[np.ndarray] = None,
                     ) -> Tuple[np.ndarray, int, int, jax.Array, np.ndarray]:
    """Shared validation/normalization for every ``generate`` front end
    (single-device engine and pipeline runner).

    Returns ``(ids [B,S], batch, prompt_len, key, pad [B])``. Ragged input
    (a list of unequal-length sequences) is left-padded; ``pad[b]`` is row
    b's pad-prefix length (all zeros for rectangular input). Callers that
    pre-pad themselves (``runtime.batcher`` buckets shapes) pass their own
    ``pad`` vector with rectangular ids. The overflow check is the static
    guard against silent KV-cache clamping: past ``max_seq``,
    ``dynamic_update_slice`` would clamp the write offset and corrupt
    generation without an error (see ops.attention.cached_attention).
    """
    if pad is not None:
        ids = np.asarray(prompt_ids)
        if ids.ndim != 2 or len(pad) != ids.shape[0]:
            raise ValueError("explicit pad requires [B, S] ids with one "
                             "pad entry per row")
        pad = np.asarray(pad, dtype=np.int32)
    elif (isinstance(prompt_ids, (list, tuple)) and prompt_ids
            and not np.isscalar(prompt_ids[0])
            and len({len(np.asarray(p).reshape(-1)) for p in prompt_ids}) > 1):
        if not allow_ragged:
            # Central guard: a ragged batch reaching a rectangular-only
            # front end would decode wrong tokens silently (one uniform
            # cache-write offset per batch), so refuse here, once.
            raise NotImplementedError(
                "this generate front end requires equal-length prompts; "
                "ragged batches go through runtime.engine.DecodeEngine")
        ids, pad = left_pad(prompt_ids)
    else:
        ids = np.asarray(prompt_ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        pad = np.zeros((ids.shape[0],), dtype=np.int32)
    batch, prompt_len = ids.shape
    if prompt_len < 1:
        raise ValueError("prompt must be non-empty")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    total = prompt_len + max_new_tokens
    if total > max_seq:
        raise ValueError(
            f"prompt_len={prompt_len} + max_new_tokens={max_new_tokens} "
            f"= {total} exceeds max_seq={max_seq}; cache writes would "
            "silently clamp")
    if sampling.mode == "sample" and key is None:
        raise ValueError("sample mode requires an explicit PRNG key")
    if key is None:
        key = jax.random.PRNGKey(0)  # unused by greedy; fixed for shape
    elif getattr(key, "ndim", 1) == 2 and key.shape[0] != batch:
        raise ValueError(
            f"per-row key stack has {key.shape[0]} rows for a "
            f"batch of {batch}")
    return ids, batch, prompt_len, key, pad


@dataclasses.dataclass
class GenerateResult:
    """Tokens plus the timing the bench harness reports (BASELINE.md metric).

    ``decode_seconds`` times exactly ``decode_steps`` cached single-token
    forwards (= ``new_tokens - 1``: the first new token comes from the
    prefill logits, so its selection is inside the prefill window). The
    throughput/latency properties divide by ``decode_steps``, not
    ``new_tokens`` — dividing by ``new_tokens`` would overstate throughput
    by N/(N-1) and explode at N=1.
    """

    tokens: np.ndarray           # [B, prompt_len + new_tokens]
    prompt_len: int
    prefill_seconds: float
    decode_seconds: float
    new_tokens: int
    decode_steps: int
    pad: Optional[np.ndarray] = None  # [B] left-pad prefix lengths (ragged)
    # Speculative decode only (runtime.spec_decode): number of verify
    # forwards actually run; zero acceptance costs new_tokens - 1 verifies
    # (the first token comes from prefill), fewer means drafts landed.
    verify_steps: Optional[int] = None
    # A family that generates by blocks only (``Family.block_options``):
    # for each new token the index, inside its round, of the denoise
    # forward that fixed it, ``[B, new_tokens]``; ``decode_steps`` is
    # then the forwards the rounds ran (denoise and commit).
    fixed_at: Optional[np.ndarray] = None

    def row_tokens(self, i: int) -> np.ndarray:
        """Row i's tokens with its left-pad prefix stripped."""
        start = int(self.pad[i]) if self.pad is not None else 0
        return self.tokens[i, start:]

    @property
    def tokens_per_second(self) -> float:
        """Steady-state decode throughput (tokens/s across the batch)."""
        if self.decode_steps == 0:
            return float("nan")  # a 1-token generate has no decode window
        batch = self.tokens.shape[0]
        return self.decode_steps * batch / self.decode_seconds

    @property
    def per_token_latency(self) -> float:
        if self.decode_steps == 0:
            return float("nan")
        return self.decode_seconds / self.decode_steps


def _place_ep_params(params: Params, config, mesh, ep_axis: str) -> Params:
    """Expert-parallel placement: stacked expert leaves ``[L, E, ...]``
    shard over ``ep`` on their E axis (int8 ``QuantizedTensor`` codes and
    scales in lockstep), everything else replicates. Validates the
    mesh/family contract — see the ``DecodeEngine(mesh=...)`` docs."""
    if ep_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {ep_axis!r} axis: {mesh.axis_names}")
    ep = mesh.shape[ep_axis]
    if config.n_experts % ep:
        raise ValueError(
            f"n_experts={config.n_experts} not divisible by ep={ep}")
    from jax.sharding import NamedSharding, PartitionSpec as P_

    def place(path, leaf):
        names = [getattr(p, "key", p) for p in path]
        if "experts" in names:
            ndim = leaf.q.ndim if hasattr(leaf, "q") else leaf.ndim
            spec = P_(None, ep_axis, *([None] * (ndim - 2)))
        else:
            spec = P_()
        return jax.tree.map(
            lambda x: jax.device_put(
                x, NamedSharding(mesh, P_(*spec[:x.ndim]))), leaf)

    return jax.tree_util.tree_map_with_path(
        place, params, is_leaf=lambda x: hasattr(x, "q") or hasattr(x, "ndim"))


def _place_tp_params(params: Params, config, mesh) -> Params:
    """Megatron tensor-parallel placement for dense-family decode: QKV/up
    projections column-sharded, attention-out/down row-sharded over the
    ``tp`` mesh axis (the family's ``parallel.spmd`` pspecs), embeddings
    and norms replicated. GSPMD derives the two per-block all-reduces;
    the KV cache shards over the head axis (``DecodeEngine._fresh_cache``)
    so each chip attends only its own heads. This is the one classic
    inference-parallelism axis the reference lacks entirely — its only
    split is between layers (reference server.py:63-64)."""
    from jax.sharding import NamedSharding

    from ..models import family_of

    # the spmd pspec helpers key on the literal axis name "tp"
    if "tp" not in mesh.axis_names:
        raise ValueError(f"mesh has no 'tp' axis: {mesh.axis_names}")
    tp = mesh.shape["tp"]
    kv_heads = getattr(config, "n_kv_head", config.n_head)
    if config.n_head % tp or kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide n_head={config.n_head} and "
            f"n_kv_head={kv_heads}: the KV cache and attention shard "
            "over whole heads")
    specs = family_of(config).param_pspecs(mesh)

    def place(spec, leaf):
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(place, specs, params)


class DecodeEngine:
    """Single-model decode engine (pipeline-parallel variant in
    ``parallel.pipeline``): owns jitted prefill/decode programs keyed by
    static shapes, so repeated ``generate`` calls reuse compilations.

    ``boundaries`` switches on *staged* mode: params are partitioned into
    N validated pipeline stages (parallel.partition) and the compiled
    programs compose ``stage_apply`` over them — the whole multi-stage
    decode is still ONE program per phase (a single dispatch for the entire
    token scan), unlike the host-driven ``PipelineRunner`` which pays
    n_stages dispatches + transfers per token. On one chip this is the
    honest "N-shard" configuration (stage partitioning real, placement
    colocated); the multi-device single-program form lives in
    ``parallel.ppdecode`` (shard_map + ppermute over a pp mesh axis).
    """

    def __init__(self, params: Params, config: GPT2Config, max_seq: int,
                 dtype=jnp.float32, boundaries=None,
                 prefill_chunk: Optional[int] = None,
                 decode_kernel: str = "auto",
                 mesh=None, ep_axis: str = "ep"):
        """``dtype`` is the inference compute dtype: float params are cast
        once here and the KV cache allocates in it. bfloat16 halves weight
        and cache HBM traffic (the decode bottleneck — each token streams
        every weight once); LN statistics, softmax, and the final logits
        stay float32 (ops.layers.layer_norm, ops.attention, final_logits),
        so bf16 degrades only the matmul operand precision. float32 remains
        the greedy-parity mode BASELINE.json specifies.

        ``dtype="int8"`` selects weight-only int8: matmul kernels and the
        embedding/head table stored int8 with per-channel scales
        (ops.quant), activations and KV cache in bfloat16 — halves weight
        HBM traffic again over bf16. Tokens may diverge from the bf16
        stream within quantization error; fp32/bf16 remain the parity
        modes.

        ``prefill_chunk=C`` bounds the compile count under XLA's
        static-shape rule: a monolithic prefill compiles one program PER
        PROMPT LENGTH (a first-compile stall — tens of seconds on TPU —
        every time serving sees a new length), while chunked prefill
        left-pads the prompt to a multiple of ``C`` and scans one C-wide
        cached forward over the chunks, so the compiled-program space is
        the ~``max_seq/C`` distinct chunk COUNTS (each sharing the single
        scanned body) instead of every length. Numerically identical to
        monolithic prefill: the chunk padding rides the ragged-batch
        machinery (per-row position offsets + ``k_valid_from`` masking),
        token streams are byte-equal."""
        if max_seq > config.n_positions:
            raise ValueError(
                f"max_seq={max_seq} exceeds n_positions={config.n_positions}")
        from ..models import is_window_independent
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be >= 1")
            if not is_window_independent(config):
                # chunked prefill replays the prompt in C-token windows;
                # window-dependent routing (MoE) would route them
                # differently than the monolithic prefill, breaking the
                # byte-exactness contract. Refuse before any weight work.
                raise NotImplementedError(
                    "prefill_chunk requires window-independent routing; "
                    "MoE models prefill monolithically")
        # dtype is validated against the DECLARED engine regime
        # vocabulary (graftnum.REGIMES minus fp8 — that one is a
        # KV-block storage regime, kv_pool block_dtype) with a typed
        # error: an off-vocabulary dtype ("float16", a typo) used to
        # flow straight into astype and run a precision no
        # PRECISION_CONTRACT covers and no TOLERANCE_POLICY budgets.
        from ..utils.graftnum import engine_regime_of
        self.regime = engine_regime_of(dtype)
        quantize = self.regime == "int8"
        from ..models import family_of, is_stage_partitionable
        self.family = family_of(config)
        why_not = quantize and self.family.refusal("int8_weights", config)
        if why_not:
            raise NotImplementedError(why_not)
        if quantize and mesh is not None and not hasattr(config, "n_experts"):
            # refuse BEFORE any weight work (quantizing a real checkpoint
            # takes seconds — same convention as the prefill_chunk guard)
            raise NotImplementedError(
                "int8 does not compose with tp decode: the int8 "
                "streaming matmuls are unpartitioned Pallas kernels "
                "GSPMD cannot split; tp decode runs fp32/bf16")
        if quantize:
            dtype = jnp.bfloat16  # activation/KV-cache dtype under int8
            from ..ops.quant import quantize_params
            # quantize straight from the checkpoint dtype: a bf16 pre-cast
            # would truncate mantissas BEFORE rounding to int8 codes
            # (double rounding), wasting quantization accuracy for nothing
            self.params = quantize_params(params, dtype)
        else:
            self.params = jax.tree.map(
                lambda x: x.astype(dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        self.config = config
        self.max_seq = max_seq
        self.dtype = dtype
        # Mesh decode — the family picks the parallelism axis:
        #
        # - MoE + mesh("ep"): expert-parallel inference. Stacked expert
        #   kernels/biases shard over their E axis and everything else
        #   replicates — each chip holds (and streams) E/ep experts'
        #   weights, and GSPMD derives the dispatch/combine collectives
        #   from the dense formulation (the routed-gather fast path is
        #   disabled under a mesh: a jnp.take over the sharded E axis
        #   would make XLA all-gather the full expert stack, exactly the
        #   traffic ep-sharding exists to avoid).
        # - dense (GPT-2 / llama) + mesh("tp"): tensor-parallel decode.
        #   Megatron column/row-sharded projections (_place_tp_params),
        #   KV cache sharded over heads, GSPMD-derived per-block
        #   all-reduces — single-stream latency scaling across chips.
        self._mesh = mesh
        self._mesh_mode: Optional[str] = None
        if mesh is not None:
            if boundaries is not None:
                raise ValueError("mesh decode (ep/tp) and stage "
                                 "partitioning are mutually exclusive")
            if hasattr(config, "n_experts"):
                self._mesh_mode = "ep"
                self.params = _place_ep_params(self.params, config, mesh,
                                               ep_axis)
            else:
                # (int8 x tp already refused above, before weight work)
                self._mesh_mode = "tp"
                self.params = _place_tp_params(self.params, config, mesh)
        # Model dispatch: any family whose module exposes the
        # (forward_with_cache, make_cache) pair can be decoded
        # (models.family_of). Stage partitioning covers the dense
        # families (GPT-2 and llama — parallel.partition dispatches
        # structurally); MoE's expert tree decodes unstaged.
        self._model = self.family.module
        if boundaries is not None and not is_stage_partitionable(config):
            raise NotImplementedError(
                "pipeline stage partitioning (boundaries) covers the "
                f"dense GPT-2 and llama param trees; "
                f"{type(config).__name__} models decode unstaged")
        if boundaries is None:
            self.specs = None
            self.stage_params = None
        else:
            from ..parallel import partition as P
            self.specs = P.make_stage_specs(config.n_layer, boundaries)
            self.stage_params = P.partition_params(self.params, self.specs)
            # The compiled programs only ever see the staged copy; dropping
            # the monolithic pytree keeps one set of weights resident, not
            # two (the slices are new buffers).
            self.params = None
        # the weight tree is now FINAL (quantized/placed/staged) — this
        # is the copy the compiled programs read, so it is the copy the
        # HBM ledger attributes (graftmem measures live buffer nbytes,
        # so a quantized tree registers its quantized footprint)
        graftmem.track(self, "params", "params",
                       self.params if self.params is not None
                       else self.stage_params)
        self.prefill_chunk = prefill_chunk
        # Decode-attention dispatch (``decode_kernel``), four modes over
        # one Pallas path, the per-layer flash-decode kernel (in-place
        # cache write + depth-adaptive block reads; ops.decode_attention,
        # or the family's own under ``decode_kernel_eligible``):
        #   "auto"      the served default: the kernel on a TPU outside
        #               float32, else XLA; quietly XLA (with the exact
        #               ``max_seq`` allocation) on an ineligible geometry
        #               or under a mesh
        #   "xla"       the einsum path, the byte-pinned parity oracle
        #   "layer"     the compiled kernel asked for by name: refuses
        #               where "auto" would fall back
        #   "interpret" the same kernel interpreted, for CPU tests;
        #               refuses like "layer"
        # The kernel needs the cache allocated in whole blocks, so the
        # PHYSICAL cache rounds up to a BLOCK_S multiple (capped at
        # n_positions).
        from ..ops import decode_attention as _DA
        _KERNEL_MODES = ("auto", "xla", "layer", "interpret")
        if decode_kernel not in _KERNEL_MODES:
            raise ValueError(
                f"decode_kernel={decode_kernel!r} not one of {_KERNEL_MODES}")
        self._cache_seq = max_seq
        self._decode_kernel: Optional[str] = None
        if self.family.bounds_own_reads:
            # the family's attention bounds its cache reads by the live
            # depth inside the program (as the decode kernels' block
            # loops do): a window bucket as wide as the cache means the
            # engine cuts no windows for it
            self.WINDOW_BUCKET = max_seq
        # names of the counters a family's cache carries in its second,
        # one-dimensional leaf (models.latent_moe), or ()
        self.cache_counters = self.family.cache_counters
        # how a family that generates by ROUNDS over blocks does so
        # (ops.block_diffusion.Options), or None: a step yields a token
        self.block = (None if self.family.block_options is None
                      else self.family.block_options(config))
        if self.block is not None and (mesh is not None
                                       or boundaries is not None
                                       or prefill_chunk):
            raise NotImplementedError(
                f"{type(config).__name__} generates by rounds over blocks "
                "on the single-device engine; no mesh, stages or "
                "prefill_chunk")
        # "auto" engages only outside the f32 regime, however the dtype
        # was spelled (fp32 is BASELINE.json's byte-pinned greedy-parity
        # mode; the kernel's online softmax is allclose-not-bitwise vs
        # the einsum path) and only without a mesh (the kernel's manual
        # DMAs don't compose with GSPMD partitioning — "auto" quietly
        # resolves to XLA there, while the EXPLICIT kernel request
        # refuses rather than silently running something else).
        explicit_kernel = decode_kernel in ("layer", "interpret")
        # a family with a cache of its own (models.cache_entry) brings
        # its own kernel and geometry rule and keeps its own cache
        # layout under it
        own_rule = self.family.decode_kernel_eligible
        if mesh is not None and explicit_kernel:
            raise ValueError(
                f"decode_kernel={decode_kernel!r} does not compose with a "
                "mesh (the Pallas decode kernels are unpartitioned); use "
                "'auto' or 'xla'")
        want = mesh is None and (
            explicit_kernel
            or (decode_kernel == "auto"
                and jax.default_backend() == "tpu"
                and self.regime != "f32"))
        if want:
            rounded = min(-(-max_seq // _DA.BLOCK_S) * _DA.BLOCK_S,
                          config.n_positions)
            if (own_rule(config, rounded) if own_rule is not None
                    else _DA.eligible(rounded, config.head_dim, 1)):
                self._cache_seq = rounded
                self._decode_kernel = ("interpret"
                                       if decode_kernel == "interpret"
                                       else "device")
            elif explicit_kernel:
                # An EXPLICIT kernel request must never silently run
                # something else (mirrors the mesh refusal above): a
                # config slip would otherwise stop exercising the kernel
                # in tests that forget to assert _decode_kernel. Only
                # "auto" may quietly resolve to XLA.
                raise ValueError(
                    f"decode_kernel={decode_kernel!r} requested but the "
                    f"geometry is ineligible (head_dim={config.head_dim}, "
                    f"cache={rounded}): needs 2*head_dim % 128 == 0 and a "
                    f"whole-{_DA.BLOCK_S}-block cache; use 'auto' or 'xla'")
        # Prefill allocates its cache *inside* the program (zeros are free
        # under XLA and the layout matches the decode program exactly);
        # decode donates the prefill-produced cache so the two
        # [L, B, H, max_seq, hd] buffers update in place instead of
        # doubling.
        # each jit site rides a graftscope dispatch timer (PROFILED_SCOPES
        # contract): per-call wall clock into the bounded attribution
        # ring, keyed by the certifier's program-key model
        self._prefill = graftscope.instrument(
            jax.jit(self._prefill_impl), "engine._prefill",
            key_fn=_prefill_scope_key)
        self._prefill_chunked = graftscope.instrument(
            jax.jit(self._prefill_chunked_impl), "engine._prefill_chunked",
            key_fn=_prefill_chunked_scope_key)
        # static args: the sampling policy and the attention window (both
        # change the traced program; the step count rides the step_keys
        # shape, or the ``steps`` operand where a caller passes one).
        self._decode_seg = graftscope.instrument(
            jax.jit(self._decode_seg_impl, donate_argnums=(2,),
                    static_argnames=("sampling", "window")),
            "engine._decode_seg", key_fn=_decode_seg_scope_key)
        # compile-event accounting (utils.metrics.CompileWatch): every NEW
        # program entering these caches increments compile_events_total
        # with a phase label — checked after invocations, off the hot
        # device path, so compile storms are observable as counter bursts.
        self._compile_watches = (CompileWatch("prefill", self._prefill),
                                 CompileWatch("prefill",
                                              self._prefill_chunked),
                                 CompileWatch("decode", self._decode_seg))

    def _note_compiles(self) -> None:
        """Diff the jitted program caches into ``compile_events_total``
        and refresh the program-count gauge. Called after generate phases
        (and by the iteration scheduler after its segment dispatches)."""
        for w in self._compile_watches:
            w.check()
        # w.seen() (locked read): CompileWatch._seen is declared guarded
        # state, and solo engines are driven straight from concurrent
        # server handler threads
        REGISTRY.gauge("jit_program_cache_size",
                       sum(w.seen() for w in self._compile_watches),
                       component="engine")

    # -- compiled programs ---------------------------------------------------

    def _fresh_cache(self, batch: int):
        # allocation size may exceed the semantic ``max_seq`` bound: the
        # decode kernel wants whole BLOCK_S blocks (see __init__). Kernel
        # mode allocates the FUSED layout (K|V interleaved rows — see
        # ops.attention.create_fused_cache) the kernel's aligned DMAs
        # require; the XLA mode keeps the family's separate buffers.
        heads = getattr(self.config, "n_kv_head", self.config.n_head)
        if (self._decode_kernel is not None
                and self.family.decode_kernel_eligible is None):
            from ..ops.attention import create_fused_cache
            if self.specs is None:
                return create_fused_cache(self.config.n_layer, batch, heads,
                                          self._cache_seq,
                                          self.config.head_dim, self.dtype)
            return [create_fused_cache(s.n_blocks, batch, heads,
                                       self._cache_seq, self.config.head_dim,
                                       self.dtype) for s in self.specs]
        if self.specs is None:
            cache = self._model.make_cache(self.config, batch,
                                           self._cache_seq, self.dtype)
            if self._mesh_mode == "tp":
                # [L, B, H, S, hd] buffers shard over the HEAD axis: each
                # chip's attention reads/writes only its own heads' cache
                # slots — no cross-chip KV traffic, only the two
                # GSPMD-inserted per-block all-reduces touch ICI
                from jax.sharding import NamedSharding, PartitionSpec as P_
                sh = NamedSharding(self._mesh, P_(None, None, "tp"))
                cache = KVCache(
                    k=jax.lax.with_sharding_constraint(cache.k, sh),
                    v=jax.lax.with_sharding_constraint(cache.v, sh),
                    length=cache.length)
            return cache
        from ..parallel import partition as P
        return [P.make_stage_cache(s, self.config, batch, self._cache_seq,
                                   self.dtype) for s in self.specs]

    def _forward_cached(self, params, x, cache, pad, flash_prefill=False):
        """One cached forward — plain (fused model) or staged composition.

        ``flash_prefill`` is the static fresh-cache-prefill flag (see
        ``_prefill_impl``); the staged path ignores it (stage prefills
        are short at current scales). Single-token calls route through
        the flash-decode kernel when enabled (``decode_kernel``); the
        model gates on query length, so prefill and the speculative
        multi-token verify forwards stay on the XLA path.
        """
        if self.specs is None:
            kw = {}
            if self._mesh_mode == "ep":
                kw["routed_mlp"] = False  # MoE only (validated in __init__)
            return self._model.forward_with_cache(
                params, x, self.config, cache, pad,
                flash_prefill=flash_prefill,
                decode_kernel=self._decode_kernel, **kw)
        from ..parallel import partition as P
        new_caches = []
        for sp, spec, c in zip(params, self.specs, cache):
            x, c = P.stage_apply(sp, spec, self.config, x, c, pad,
                                 decode_kernel=self._decode_kernel)
            new_caches.append(c)
        return x, new_caches

    def _run_params(self):
        return self.stage_params if self.specs is not None else self.params

    def _prefill_impl(self, params: Params, ids: jnp.ndarray,
                      pad: Optional[jnp.ndarray],
                      ) -> Tuple[jnp.ndarray, KVCache]:
        cache = self._fresh_cache(ids.shape[0])
        # Fresh-cache prefill at offset 0 with no pad mask is plain causal
        # attention — route it through the Pallas flash kernel when the
        # config asks for it (attention_impl="pallas"): no O(S^2) score
        # materialization at long context. All conditions are static at
        # trace time; flash_eligible keeps ragged user lengths the kernel
        # cannot tile (it would fall back to one full-S VMEM block) on
        # the XLA path.
        from ..ops.flash_attention import flash_eligible, flash_profitable
        # _mesh gate: the Mosaic flash kernel is unpartitioned — under a
        # tp/ep mesh GSPMD cannot split it, so mesh decode keeps the XLA
        # prefill (same rule as the decode kernel and int8 matmuls)
        flash = (self.config.attention_impl == "pallas" and pad is None
                 and ids.shape[1] > 1 and self.specs is None
                 and self._mesh is None
                 and flash_eligible(ids.shape[1])
                 and flash_profitable(ids.shape[1]))
        # a family with its own fresh-cache attention form (latent:
        # expanded, reading no cache; it masks pad itself) takes the
        # same static word
        flash = flash or self.family.fresh_prefill_flag
        logits, cache = self._forward_cached(params, ids, cache, pad,
                                             flash_prefill=flash)
        return logits[:, -1], cache

    def _prefill_chunked_impl(self, params: Params, chunks: jnp.ndarray,
                              pad: jnp.ndarray,
                              ) -> Tuple[jnp.ndarray, KVCache]:
        """``chunks`` [n, B, C] (left-pad-aligned); ``pad`` [B] includes
        the alignment pad. One C-wide cached forward scanned over the
        chunk axis — the compiled body is shared by every chunk, so the
        program space is per chunk COUNT, not per prompt length."""
        cache = self._fresh_cache(chunks.shape[1])

        def body(cache, chunk):
            logits, cache = self._forward_cached(params, chunk, cache, pad)
            return cache, logits[:, -1]

        cache, last = jax.lax.scan(body, cache, chunks)
        return last[-1], cache

    def _align_chunks(self, ids: np.ndarray, pad: np.ndarray,
                      prompt_len: int, reserve: int):
        """Left-pad ``ids`` to a multiple of ``prefill_chunk`` when chunked
        prefill applies. Returns ``(ids, pad, prompt_len, chunk_or_None)``;
        ``chunk=None`` means use the monolithic prefill (chunking off,
        prompt fits in one chunk, or no cache headroom for the alignment
        pad given ``reserve`` upcoming tokens). Correctness never depends
        on which path is taken."""
        chunk = self.prefill_chunk
        if not chunk or prompt_len <= chunk:
            return ids, pad, prompt_len, None
        n_chunks = -(-prompt_len // chunk)
        if n_chunks * chunk + reserve > self.max_seq:
            return ids, pad, prompt_len, None
        extra = n_chunks * chunk - prompt_len
        if extra:
            ids = np.concatenate(
                [np.zeros((ids.shape[0], extra), np.int32), ids], axis=1)
            pad = pad + extra
        return ids, pad, n_chunks * chunk, chunk

    # -- windowed decode segments --------------------------------------------
    #
    # The decode scan's attention reads the whole [*, max_seq, *] cache
    # every step even when only `depth` slots are valid: a 528-slot cache
    # decoded from depth 16 streams 33x the useful KV bytes on step one.
    # Splitting the scan into segments with STATIC, growing windows (the
    # next power-of-two bucket over the segment's deepest slot) keeps
    # every shape static under jit while the attention read tracks actual
    # depth. Byte-exact: slots >= depth are masked out either way, and the
    # per-step PRNG keys are split once for the whole decode, so sampled
    # streams are identical to the unsegmented program's.

    def _slice_cache(self, cache, window: int):
        def cut(c: KVCache) -> KVCache:
            return c._replace(k=c.k[..., :window, :],
                              v=c.v[..., :window, :])
        return [cut(c) for c in cache] if isinstance(cache, list) else cut(cache)

    def _merge_window(self, full, sub):
        def merge(f: KVCache, s: KVCache) -> KVCache:
            zeros = (0,) * f.k.ndim
            return s._replace(
                k=jax.lax.dynamic_update_slice(f.k, s.k, zeros),
                v=jax.lax.dynamic_update_slice(f.v, s.v, zeros))
        if isinstance(full, list):
            return [merge(f, s) for f, s in zip(full, sub)]
        return merge(full, sub)

    # windowed-decode bucket policy, shared with runtime.iterbatch
    WINDOW_BUCKET = 128

    def _decode_window(self, deepest: int) -> Optional[int]:
        """The attention window for a segment whose deepest cache slot is
        ``deepest``: the smallest power-of-two multiple of
        ``WINDOW_BUCKET`` covering it, or ``None`` for the full-cache
        program (window would reach ``max_seq``, or the flash-decode
        kernel is active — its block loop already depth-bounds reads).
        THE single definition of the bucket policy; ``_segments`` and the
        iteration-level scheduler both derive windows from it."""
        if self._decode_kernel is not None:
            return None
        w = self.WINDOW_BUCKET
        while w < deepest:
            w *= 2
        return None if w >= self.max_seq else w

    def _segments(self, start_depth: int, steps: int,
                  bucket: Optional[int] = None, quant: int = 32) -> list:
        """Split ``steps - 1`` decode forwards into ``(n_forwards, window)``
        segments. The forward at cache depth ``d`` needs ``window >= d+1``;
        windows are power-of-two multiples of ``bucket``. Once the window
        reaches ``max_seq`` the remainder runs as ``(n, None)`` — the plain
        full-cache program, shared by every generate (no slice/merge).

        Compile-space note: intermediate segment lengths are quantized
        DOWN to multiples of ``quant`` (a depth within ``quant`` of a
        window edge skips straight to the next window), so the program
        set is bounded by {multiples of quant} x {log windows} no matter
        how many distinct prompt depths serving sees — unbatched traffic
        with arbitrary prompt lengths compiles the same handful of
        bodies. Only the FINAL segment's length is request-keyed
        (= remaining steps), exactly like the pre-windowing steps-keyed
        scheme, and the batcher's ``steps_bucket`` already quantizes that.

        With the flash-decode kernel active, segmentation is pointless:
        the kernel's block loop already bounds its reads by the live
        depth (a dynamic trip count — no recompiles), so the whole decode
        runs as one full-cache program."""
        if self._decode_kernel is not None:
            return [(steps - 1, None)]
        bucket = bucket or self.WINDOW_BUCKET
        total = steps - 1
        segs = []
        d = start_depth
        while total > 0:
            w = bucket
            while w < d + 1:
                w *= 2
            if w - d < quant and w < self.max_seq:
                w *= 2  # too close to the edge: a sub-quant segment
                        # would mint a new program for little read saving
            if w >= self.max_seq:
                segs.append((total, None))
                break
            room = w - d
            if room >= total:
                segs.append((total, w))
                break
            n = (room // quant) * quant
            segs.append((n, w))
            d += n
            total -= n
        return segs

    def _decode_seg_impl(self, params: Params, token: jnp.ndarray,
                         cache, pad: Optional[jnp.ndarray],
                         step_keys: jax.Array,
                         steps: Optional[jnp.ndarray] = None, *,
                         sampling: SamplingConfig,
                         window: Optional[int]):
        """Forward cached single-token steps from ``token``; attention
        reads only the first ``window`` cache slots (sliced out
        statically; the updated slice merges back into the donated full
        buffer on exit).

        Without ``steps`` the call runs ``len(step_keys)`` steps as one
        ``lax.scan`` and returns ``(tokens [B, n], cache)``: the form of
        a caller that knows its length ahead. With ``steps``, an int32
        scalar OPERAND, the same body runs ``steps`` times (at most
        ``len(step_keys)``) and the call returns ``(tokens [B,
        len(step_keys)], cache, last [B])``: columns from ``steps`` on
        are never written and never read, ``last`` is the token of the
        last step run. The length is then no part of the program's key:
        one program serves every length up to ``len(step_keys)``.

        A family that generates by blocks runs ROUNDS in place of steps
        (``_decode_rounds``): ``token`` is then each row's block ``[B,
        L]``, ``steps`` the rounds, and the tokens come back with the
        forward that fixed each."""
        if self.block is not None:
            return self._decode_rounds(params, token, cache, pad,
                                       step_keys.shape[0], steps, sampling)
        sub = self._slice_cache(cache, window) if window else cache
        if self.cache_counters:
            # what comes back beside this segment's tokens is this
            # segment's sums, however long the cache has lived
            sub = sub._replace(v=jnp.zeros_like(sub.v))

        def body(carry, step_key):
            token, c = carry
            logits, c = self._forward_cached(params, token[:, None], c, pad)
            nxt = select_token(logits[:, -1], sampling, step_key)
            return (nxt, c), nxt

        if steps is None:
            (_, sub), out = jax.lax.scan(body, (token, sub), step_keys)
            out = out.T                                  # [n, B] -> [B, n]
        else:
            def counted(i, carry):
                token, c, out = carry
                (nxt, c), _ = body((token, c), step_keys[i])
                # an unsigned row: not tested for being negative, as a
                # scan's is not
                return nxt, c, jax.lax.dynamic_update_index_in_dim(
                    out, nxt, i.astype(jnp.uint32), 0)

            token, sub, out = jax.lax.fori_loop(
                0, steps, counted,
                (token, sub, jnp.zeros((step_keys.shape[0], token.shape[0]),
                                       jnp.int32)))
            out = out.T
        cache = self._merge_window(cache, sub) if window else sub
        return (out, cache) if steps is None else (out, cache, token)

    def _decode_rounds(self, params: Params, block: jnp.ndarray, cache,
                       pad: Optional[jnp.ndarray], room: int,
                       rounds: jnp.ndarray, sampling: SamplingConfig):
        """``rounds`` ROUNDS of generation by blocks from the batch's
        depth ``cache.length`` (a block boundary of every row).

        ``block`` [B, L] int32 is each row's block as the first round
        finds it: token ids where a position is given (the tail of a
        prompt that does not end on a boundary), ``MASKED`` elsewhere;
        every later round starts all masked. A round runs DENOISE
        forwards of the block's ``L`` positions against the cache, each
        writing the block's keys and values at ``[d, d + L)`` and
        leaving the depth where it was, choosing a candidate and a
        confidence for every masked position and fixing some by the
        transfer rule, until no LIVE row has a masked position (a lane
        without a request, ``pad >= d``, has none to begin with); then
        one COMMIT forward on the finished block, whose keys and values
        the cache keeps, and the depth moves by ``L``. Every row commits
        in the same forward: the batch keeps one depth, and a round
        yields ``L`` positions a row whatever the weights. Only the
        FORWARDS are data.

        Returns ``(out [B, 2, room], cache, next block [B, L])``:
        ``out[:, 0]`` the positions' tokens (given ones included, at
        their places), ``out[:, 1]`` for each the index inside its round
        of the forward that fixed it (-1: given); columns from ``rounds
        x L`` on are never written. The cache's counter leaf holds this
        call's sums: the routing of every forward and, behind it, what
        the rounds counted (``ops.block_diffusion.COUNTERS``)."""
        from ..ops import block_diffusion as BD
        if sampling.mode != "greedy":
            raise NotImplementedError(
                "generation by blocks chooses candidates greedily; "
                "sampled candidates are not implemented")
        opt = self.block
        length = opt.block_length
        b = block.shape[0]
        at = self.cache_counters.index(BD.COUNTERS[0])
        live = (jnp.ones((b,), bool) if pad is None
                else pad < cache.length)
        sub = cache._replace(v=jnp.zeros_like(cache.v))

        def one_round(i, carry):
            blk, c, out, tally = carry
            blk = jnp.where(live[:, None], blk, 0)
            masked0 = jnp.sum(blk == BD.MASKED, axis=-1, dtype=jnp.int32)
            floor = BD.floor_of(masked0, opt.denoising_steps)
            depth = c.length

            def denoise(state):
                blk, fixed_at, c, f, over_n = state
                masked = blk == BD.MASKED
                logits, c = self._forward_cached(
                    params, jnp.where(masked, opt.mask_token_id, blk), c,
                    pad)
                cand, conf = BD.choose(logits, opt.mask_token_id)
                fix, over = BD.transfer(masked, conf, floor, opt.remasking,
                                        opt.confidence_threshold)
                return (jnp.where(fix, cand, blk),
                        jnp.where(fix, f, fixed_at),
                        c._replace(length=depth), f + 1,
                        over_n + jnp.sum(over, dtype=jnp.int32))

            blk, fixed_at, c, forwards, over_n = jax.lax.while_loop(
                lambda state: jnp.any(state[0] == BD.MASKED), denoise,
                (blk, jnp.full_like(blk, -1), c, jnp.int32(0),
                 jnp.int32(0)))
            # the commit: its logits are read by nobody, so the compiled
            # program runs no head for it
            _, c = self._forward_cached(params, blk, c, pad)
            out = jax.lax.dynamic_update_slice(
                out, jnp.stack([blk, fixed_at], axis=1),
                (jnp.uint32(0), jnp.uint32(0),
                 (i * length).astype(jnp.uint32)))
            tally = tally + jnp.stack([
                forwards + 1, jnp.int32(1), jnp.int32(1), jnp.sum(masked0),
                over_n, (forwards + 1) * jnp.sum(live, dtype=jnp.int32)])
            return jnp.full_like(blk, BD.MASKED), c, out, tally

        block, sub, out, tally = jax.lax.fori_loop(
            0, rounds, one_round,
            (block, sub, jnp.zeros((b, 2, room), jnp.int32),
             jnp.zeros((len(BD.COUNTERS),), jnp.int32)))
        sub = sub._replace(v=sub.v.at[at:at + len(BD.COUNTERS)].add(tally))
        return out, sub, block

    def _generate_blocks(self, prompt_ids, max_new_tokens: int,
                         sampling: SamplingConfig) -> GenerateResult:
        """``generate`` for a family that generates by blocks: each
        row's whole blocks are prefilled, the rest of its prompt is
        given to the first round, and as many rounds run as the longest
        answer needs. Rows may differ in length (their pads are whole
        blocks, so every row's block grid meets the batch's depth)."""
        from ..ops import block_diffusion as BD
        length = self.block.block_length
        rows = ([np.asarray(prompt_ids, np.int32)]
                if np.ndim(prompt_ids[0]) == 0 else
                [np.asarray(r, np.int32).reshape(-1) for r in prompt_ids])
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        given = [len(r) % length for r in rows]
        kept = [len(r) - g for r, g in zip(rows, given)]
        if min(kept) < length:
            raise ValueError(f"a prompt needs at least one whole block of "
                             f"{length} tokens")
        depth = max(kept)
        rounds = -(-(max(given) + max_new_tokens) // length)
        if depth + rounds * length > self.max_seq:
            raise ValueError(
                f"prompt blocks {depth} + {rounds} rounds of {length} "
                f"exceed max_seq={self.max_seq}; cache writes would "
                "silently clamp")
        ids = np.zeros((len(rows), depth), np.int32)
        block = np.full((len(rows), length), BD.MASKED, np.int32)
        for i, (r, k, g) in enumerate(zip(rows, kept, given)):
            ids[i, depth - k:] = r[:k]
            block[i, :g] = r[k:]
        pad = np.asarray([depth - k for k in kept], np.int32)
        pad_j = jnp.asarray(pad) if pad.any() else None
        t0 = time.perf_counter()
        run_params = self._run_params()
        _, cache = self._prefill(run_params, jnp.asarray(ids), pad_j)
        jax.block_until_ready(cache.k)
        t1 = time.perf_counter()
        tracing.record("prefill", t0, t1, batch=len(rows), prompt_len=depth,
                       chunked=False)
        out, cache, _ = self._decode_seg(
            run_params, jnp.asarray(block), cache, pad_j,
            jnp.zeros((rounds * length, 2), jnp.uint32), np.int32(rounds),
            sampling=sampling, window=None)
        counters = dict(zip(self.cache_counters, np.asarray(cache.v)))
        del cache
        out = np.asarray(out)
        t2 = time.perf_counter()
        forwards = int(counters["block_forwards"])
        tracing.record("decode", t1, t2, batch=len(rows), steps=forwards,
                       rounds=rounds, tokens=max_new_tokens)
        self._note_compiles()
        new = np.stack([out[i, :, g:g + max_new_tokens]
                        for i, g in enumerate(given)])
        prompts, lead = left_pad(rows)
        return GenerateResult(
            tokens=np.concatenate([prompts, new[:, 0]], axis=1),
            prompt_len=prompts.shape[1], prefill_seconds=t1 - t0,
            decode_seconds=t2 - t1, new_tokens=max_new_tokens,
            decode_steps=forwards, pad=lead if lead.any() else None,
            fixed_at=new[:, 1])

    # -- public API ----------------------------------------------------------

    def generate(self, prompt_ids, max_new_tokens: int,
                 sampling: SamplingConfig = SamplingConfig(),
                 key: Optional[jax.Array] = None,
                 pad: Optional[np.ndarray] = None,
                 eos_id: Optional[int] = None) -> GenerateResult:
        """[B, S] (or [S]) prompt ids -> GenerateResult with [B, S+N] tokens.

        Validation (including the static cache-overflow guard) is shared
        with the pipeline runner via ``prepare_generate``. ``pad`` lets
        pre-padded callers (runtime.batcher) declare their left-pad
        prefixes explicitly.

        ``eos_id`` arms on-device-work early exit: the decode runs in
        chunks with DOUBLING caps (``EOS_SEGMENT`` = 32, then 64, 128,
        up to ``_EOS_CAP_MAX`` = 256 steps) and stops at the first
        boundary where EVERY row has emitted ``eos_id`` — the emitted
        tokens are the byte-exact prefix of the uncapped stream (same
        programs, same prefix-stable per-step keys), but dead tokens
        past the last row's EOS stop costing device time. Each armed
        chunk costs one host sync (the unarmed path keeps its zero-sync
        dispatch pipeline); the doubling schedule bounds that tax on
        long generations while keeping early exits fine-grained —
        worst-case overshoot past the EOS equals the current chunk size
        (up to 256 steps late in a long decode). Serving arms it only
        for ``stop_at_eos`` requests. May return fewer than
        ``max_new_tokens`` tokens (``GenerateResult.new_tokens``).
        """
        if self.block is not None:
            if pad is not None or eos_id is not None:
                raise NotImplementedError(
                    "generation by blocks takes ragged prompts as a list "
                    "and stops at max_new_tokens")
            return self._generate_blocks(prompt_ids, max_new_tokens, sampling)
        ids, batch, prompt_len, key, pad = prepare_generate(
            prompt_ids, max_new_tokens, self.max_seq, sampling, key, pad=pad)

        ids, pad, prompt_len, chunk = self._align_chunks(
            ids, pad, prompt_len, reserve=max_new_tokens)

        ids_j = jnp.asarray(ids, dtype=jnp.int32)
        # Rectangular batches keep pad=None: the compiled programs then skip
        # the per-row mask entirely (same numerics, no [B,Sq,Skv] mask
        # materialization) and stay byte-identical to the pre-ragged path.
        pad_j = jnp.asarray(pad) if pad.any() else None

        t0 = time.perf_counter()
        prefill_key, decode_key = _split_keys(key)
        run_params = self._run_params()
        if chunk:
            n_chunks = ids_j.shape[1] // chunk
            chunks = ids_j.reshape(batch, n_chunks, chunk).transpose(1, 0, 2)
            last_logits, cache = self._prefill_chunked(
                run_params, chunks,
                pad_j if pad_j is not None
                else jnp.zeros((batch,), jnp.int32))
        else:
            last_logits, cache = self._prefill(run_params, ids_j, pad_j)
        first = select_token(last_logits, sampling, prefill_key)
        first.block_until_ready()
        t1 = time.perf_counter()
        tracing.record("prefill", t0, t1, batch=batch,
                       prompt_len=prompt_len, chunked=bool(chunk))
        # KV reservation in the pool's block denomination (see
        # utils.metrics.kv_block_gauges): the contiguous arena this
        # generate holds, vs its allocated capacity
        kv_block_gauges("engine", batch * (prompt_len + max_new_tokens),
                        batch * self._cache_seq)
        return self._decode_and_pack(run_params, ids, pad, pad_j, first,
                                     cache, decode_key, max_new_tokens,
                                     sampling, prompt_len, t1 - t0,
                                     eos_id=eos_id)

    def _decode_and_pack(self, run_params, ids, pad, pad_j, first, cache,
                         decode_key, max_new_tokens: int,
                         sampling: SamplingConfig, prompt_len: int,
                         prefill_seconds: float,
                         eos_id: Optional[int] = None) -> GenerateResult:
        """Run the compiled decode scan off a prepared (first token, cache)
        state and assemble the GenerateResult — shared by ``generate`` and
        the prefix-cache front end (runtime.prefix_cache), which prepares
        the prefill state its own way. Donates ``cache``.

        The decode runs as windowed segments (see ``_segments``): each
        segment is one compiled scan whose attention reads only the
        current power-of-two depth bucket of the cache, so shallow steps
        stop paying for the full ``max_seq`` read. Exact, and the same
        program count as before for short generations.

        ``eos_id`` (see ``generate``) subdivides segments with DOUBLING
        caps (32, 64, ... ``_EOS_CAP_MAX``) and fetches each chunk's
        tokens; the loop exits at the first boundary where every row has
        emitted the id. Early exits keep their fine granularity while a
        long armed tail pays logarithmically few syncs (ADVICE r4:
        fixed 32-step checks can cost more than the dead tokens they
        save). Program set stays bounded: chunk sizes
        are powers of two or planner quanta."""
        t1 = time.perf_counter()
        # working-view ledger entry: the contiguous cache is live for
        # exactly this generation (handle-keyed — concurrent generates
        # each hold their own entry); released at the ``del`` below.
        # Segment rebinds are donated and shape-identical, so one
        # registration covers the whole decode.
        mem_h = graftmem.track(self, "cache", "engine_cache", cache)
        steps = max_new_tokens
        parts = [first[:, None]]
        token = first
        segs = self._segments(prompt_len, steps)
        done = None
        if eos_id is not None:
            segs = _eos_capped_segments(segs)
            done = np.asarray(first) == eos_id
        if steps > 1 and not (done is not None and done.all()):
            step_keys = _step_keys(decode_key, steps - 1)
            used = 0
            for n, window in segs:
                out, cache = self._decode_seg(
                    run_params, token, cache, pad_j,
                    step_keys[used:used + n], sampling=sampling,
                    window=window)
                token = out[:, -1]
                parts.append(out)
                used += n
                if done is not None:
                    done |= (np.asarray(out) == eos_id).any(axis=1)
                    if done.all():
                        break
        del cache  # last segment's output aliases the donated prefill cache
        graftmem.release(mem_h)
        new = np.asarray(jax.block_until_ready(jnp.concatenate(parts, axis=1)))
        t2 = time.perf_counter()
        steps_run = new.shape[1] - 1
        tracing.record("decode", t1, t2, batch=new.shape[0],
                       steps=new.shape[1], segments=len(segs),
                       step_ms=round((t2 - t1) / max(steps_run, 1) * 1e3, 3))
        if steps_run > 0:
            # per-decode-step time, DEVICE-inclusive: this window closes
            # after the block_until_ready fetch above, so it covers real
            # execution — unlike the scheduler-side dispatch windows
            # (see utils.metrics METRIC_CATALOG's truth note)
            REGISTRY.observe("decode_step_seconds", (t2 - t1) / steps_run,
                             component="engine")
        self._note_compiles()
        # generation done: its cache reservation is released (an idle
        # server must not keep reporting the last request's blocks)
        kv_block_gauges("engine", 0, new.shape[0] * self._cache_seq)

        tokens = np.concatenate([ids, new], axis=1)
        return GenerateResult(tokens=tokens, prompt_len=prompt_len,
                              prefill_seconds=prefill_seconds,
                              decode_seconds=t2 - t1,
                              new_tokens=new.shape[1],
                              decode_steps=new.shape[1] - 1,
                              pad=pad if pad.any() else None)
