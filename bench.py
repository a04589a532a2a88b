"""Benchmark harness: the full BASELINE.json measurement matrix.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "configs"}.
The top-level metric is the headline number (GPT-2 124M single-stream greedy
decode, bf16, on the visible TPU chip); ``configs`` carries every
BASELINE.md row so the matrix has measured values instead of TBDs:

  cfg1  tiny-gpt2, 2-shard pipeline, 20 new tokens (the notebook workload)
  cfg2  GPT-2 124M, 2-shard (6+6) + single-chip engine, single prompt
  cfg3  GPT-2 124M, batch=8 (the reference can only run bs=1 sequentially,
        server.py:137 — its baseline is 8x one stream)
  cfg4  GPT-2 medium, 4-shard pipeline (round-robin on this 1 chip: the
        bench environment exposes a single TPU; stage handoffs still run,
        labeled honestly in the row)
  cfg5  KV-cache incremental decode vs O(n^2) full re-forward per token —
        both measured on THIS framework on-chip, plus the reference's own
        O(n^2) torch CPU loop for scale

Baseline denominators re-measure the reference's decode algorithm
in-process on CPU: a torch GPT-2 re-forwarding the FULL growing sequence
per token with no KV cache (reference server.py:169-181), greedy. No
HTTP/JSON hops are charged to it, so every vs_baseline here is
conservative — the deployed reference is slower than its denominator.

Both sides use random-init weights of the same architecture (no HF hub in
this image; throughput is weight-independent). fp32 engine rows exist
because fp32 is the BASELINE.json greedy-parity mode; bf16 rows are the
TPU-native fast path (fp32 LN/softmax/logits, bf16 weights + KV).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

# Fault contract (tools/graftcheck faults pass): the matrix child runs
# under a configured hard timeout; a timeout becomes the row's error
# field, never a hung bench.
FAULT_POLICY = {
    "subprocess.run": ("config", "none",
                       "row records an error on child timeout"),
}

# Timeline contract (tools/graftcheck timeline pass): the
# timeline_overhead row's emit-throughput micro-bench publishes
# occupancy points onto the bus it is measuring.
TIMELINE_EVENTS = {
    "occupancy": "cfg_timeline_overhead micro-bench",
}

PROMPT_LEN = 16
# Two-point decode windows: a generate() call pays fixed costs beside its
# tokens (prompt up, tokens down, dispatch — measured and reported as
# transfer_rtt_ms). Timing one window charges them to the tokens; the
# marginal cost between two windows cancels them, giving the steady-state
# per-token cost.
STEPS_A = 64
STEPS_B = 512


def measure_reference_cpu(config, prompt_len: int, new_tokens: int) -> float:
    """tokens/sec of the reference's O(n^2) CPU decode loop (torch)."""
    import torch
    from transformers import GPT2Config as HFConfig, GPT2LMHeadModel

    torch.manual_seed(0)
    model = GPT2LMHeadModel(HFConfig(
        vocab_size=config.vocab_size, n_positions=config.n_positions,
        n_embd=config.n_embd, n_layer=config.n_layer, n_head=config.n_head))
    model.eval()
    ids = list(np.random.default_rng(0).integers(
        0, config.vocab_size, size=(prompt_len,)))
    # warmup one forward (thread pools, allocator)
    with torch.no_grad():
        model(torch.tensor([ids]))
    t0 = time.perf_counter()
    for _ in range(new_tokens):
        with torch.no_grad():
            logits = model(torch.tensor([ids])).logits[0, -1]
        ids.append(int(torch.argmax(logits)))  # greedy parity mode
    dt = time.perf_counter() - t0
    return new_tokens / dt


def _fetch(out) -> None:
    """Close a timing window by pulling one scalar to the host: the
    fetch cannot return before the work that produces it has run. Every
    timing window in this file ends with one (the engine/pipeline
    ``generate`` paths already do, via ``np.asarray`` of the token
    output).
    """
    import jax

    leaf = jax.tree_util.tree_leaves(out)[0]
    idx = (0,) * getattr(leaf, "ndim", 0)
    # slice ON DEVICE before transferring: the window times the work,
    # not the copy of a full array
    np.asarray(leaf[idx] if idx else leaf)


def measure_single_program_e2e(config, prompt_len: int,
                               new_tokens: int) -> dict:
    """The entire generate — prefill + scanned greedy decode — as ONE
    compiled program closed by ONE host fetch: the minimum-sync form of
    the notebook workload (VERDICT r3 next #8). Its wall time is the
    floor one round trip sets; anything above it is device/compile
    work."""
    import jax
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.models import gpt2

    params = gpt2.init_params(config, jax.random.PRNGKey(0))
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(
            0, config.vocab_size, size=(1, prompt_len)), jnp.int32)

    @jax.jit
    def full_generate(params, ids):
        cache = gpt2.make_cache(config, 1, prompt_len + new_tokens + 4,
                                jnp.float32)
        logits, cache = gpt2.forward_with_cache(params, ids, config, cache)
        first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

        def body(carry, _):
            tok, cache = carry
            lg, cache = gpt2.forward_with_cache(params, tok[:, None],
                                                config, cache)
            nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
            return (nxt, cache), nxt

        (_, _), rest = jax.lax.scan(body, (first, cache), None,
                                    length=new_tokens - 1)
        return jnp.concatenate([first, rest[:, 0]])

    _fetch(full_generate(params, prompt))          # compile
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        _fetch(full_generate(params, prompt))
        best = min(best, time.perf_counter() - t0)
    return {"e2e_seconds": best, "tokens_per_sec": new_tokens / best}


def measure_dispatch_rtt() -> float:
    """Fixed per-sync overhead, ms: one host->device->host round trip.

    A generate() call pays it a couple of times (prompt up, tokens
    down). This fixed cost is what the two-point marginal timing
    cancels."""
    import jax.numpy as jnp

    def roundtrip():
        x = jnp.asarray(np.zeros((1, 256), np.int32))
        _fetch(x + 1)  # +1 defeats any host-side short-circuit

    roundtrip()  # warmup
    t0 = time.perf_counter()
    n = 5
    for _ in range(n):
        roundtrip()
    return (time.perf_counter() - t0) / n * 1e3


def marginal_seconds(time_window, n1: int, n2: int, reps: int = 5):
    """THE timing harness, used by every config.

    ``time_window(n)`` must run one dependency-chained compiled program of
    size ``n`` closed by a host fetch (see ``_fetch``) and return its wall
    seconds. Two window sizes, min-of-``reps`` each, marginal cost
    ``(t2-t1)/(n2-n1)`` — the fixed per-window cost cancels.
    Returns None when the marginal is non-positive (signal below the
    barrier jitter) rather than reporting nonsense.
    """
    time_window(n1), time_window(n2)               # compile + warm
    t1 = min(time_window(n1) for _ in range(reps))
    t2 = min(time_window(n2) for _ in range(reps))
    m = (t2 - t1) / (n2 - n1)
    return m if m > 0 else None


def _two_point(runner, prompt, s_a: int = STEPS_A, s_b: int = STEPS_B) -> dict:
    """Steady-state decode cost for a ``generate``-style runner."""
    last = {}

    def time_window(n):
        result = runner.generate(prompt, n)
        last[n] = result
        return result.decode_seconds

    marginal = marginal_seconds(time_window, s_a, s_b)
    rb = last[s_b]
    degraded = marginal is None
    if degraded:  # below timer resolution: fall back to the e2e rate
        marginal = rb.decode_seconds / rb.decode_steps
    batch = prompt.shape[0]
    out = {
        "tokens_per_sec": batch / marginal,
        "p50_token_latency_ms": marginal * 1e3,
        "e2e_tokens_per_sec": rb.tokens_per_second,
        "prefill_ms": rb.prefill_seconds * 1e3,
    }
    if degraded:
        out["degraded_timing"] = True
    if rb.verify_steps is not None:  # speculative runner: acceptance stats
        out["verify_steps"] = rb.verify_steps
        out["accepted_tokens_per_verify"] = round(
            rb.new_tokens / rb.verify_steps, 2)
    return out


def measure_engine(config, prompt_len: int, batch: int,
                   dtype_name: str = "float32", s_b: int = STEPS_B,
                   decode_kernel: str = "auto") -> dict:
    """Single-device engine: jitted prefill + scanned KV-cache decode.

    ``dtype_name="int8"`` is the weight-only quantized fast path
    (ops.quant): int8 kernels/embedding, bf16 activations + KV cache.
    ``decode_kernel`` is ``DecodeEngine``'s; "auto" is the production
    dispatch."""
    import jax
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.models import family_module
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine

    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
             "int8": "int8"}[dtype_name]
    mod = family_module(config)  # gpt2 or llama geometry, same harness
    params = mod.init_params(config, jax.random.PRNGKey(0))
    engine = DecodeEngine(params, config, max_seq=prompt_len + s_b,
                          dtype=dtype, decode_kernel=decode_kernel)
    prompt = np.random.default_rng(0).integers(
        0, config.vocab_size, size=(batch, prompt_len))
    return _two_point(engine, prompt, s_b=s_b)


def measure_pipeline(config, n_stages: int, prompt_len: int,
                     batch: int = 1, dtype_name: str = "float32",
                     two_point: bool = True, new_tokens: int = STEPS_A,
                     ) -> dict:
    """N-shard pipelined decode as a single compiled program per phase.

    With >= n_stages real devices this is the shard_map + ppermute decoder
    (one program, stage weights resident per chip, ICI hops). On the 1-chip
    bench environment it falls back to the staged DecodeEngine: the SAME
    validated stage partition (parallel.partition), composed in one
    program on the one chip — labeled in the row. The host-driven
    PipelineRunner is deliberately not timed here: it dispatches from
    the host once per token and stage."""
    import jax
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.models import family_module
    from llm_sharding_demo_tpu.parallel.ppdecode import PipelinedDecoder
    from llm_sharding_demo_tpu.parallel.spmd import make_mesh
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine

    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    params = family_module(config).init_params(config, jax.random.PRNGKey(0))
    per = config.n_layer // n_stages
    boundaries = [per * i for i in range(1, n_stages)]
    max_seq = prompt_len + (STEPS_B if two_point else new_tokens)
    n_real = len(jax.devices())
    if n_real >= n_stages:
        mesh = make_mesh({"pp": n_stages}, jax.devices()[:n_stages])
        runner = PipelinedDecoder(params, config, mesh, max_seq=max_seq,
                                  dtype=dtype)
        placement = f"ppermute over {n_stages} devices"
    else:
        runner = DecodeEngine(params, config, max_seq=max_seq, dtype=dtype,
                              boundaries=boundaries)
        placement = f"{n_stages} stages fused on {n_real} chip(s)"
    prompt = np.random.default_rng(0).integers(
        0, config.vocab_size, size=(batch, prompt_len))
    if two_point:
        out = _two_point(runner, prompt)
    else:  # fixed workload (cfg1's mandated 20 tokens): e2e, RTT included
        runner.generate(prompt, new_tokens)        # warmup
        result = runner.generate(prompt, new_tokens)
        out = {
            "tokens_per_sec": result.tokens_per_second,
            "p50_token_latency_ms": result.per_token_latency * 1e3,
        }
    out["placement"] = placement
    return out


def measure_moe(prompt_len: int, batch: int = 1,
                dtype_name: str = "bfloat16", config=None) -> dict:
    """MoE decode: GPT-2-124M geometry with the MLP swapped for 8 experts
    (top-2, ~7x the MLP weights). Exercises the second model family's
    cached decode path end-to-end on-chip."""
    import jax
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.models import moe
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine

    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
             "int8": "int8"}[dtype_name]
    if config is None:
        config = moe.MoEConfig(vocab_size=50257, n_positions=1024, n_embd=768,
                               n_layer=12, n_head=12, n_experts=8,
                               expert_top_k=2)
    params = moe.init_params(config, jax.random.PRNGKey(0))
    engine = DecodeEngine(params, config, max_seq=prompt_len + STEPS_B,
                          dtype=dtype)
    prompt = np.random.default_rng(0).integers(
        0, config.vocab_size, size=(batch, prompt_len))
    return _two_point(engine, prompt)


def measure_spec_decode(config, prompt_len: int,
                        dtype_name: str = "bfloat16", draft_len: int = 6,
                        s_b: int = STEPS_B) -> dict:
    """Prompt-lookup speculative decode vs the plain engine, same weights.

    Greedy speculation is token-exact (runtime.spec_decode), so this is a
    pure latency measurement: tokens/sec of the verify-loop program vs the
    one-token-per-forward scan, plus the realized acceptance (tokens per
    verify forward). Greedy decode from a random prompt settles into a
    repetition loop — the favorable case for lookup drafting; the row
    reports acceptance so the speedup can be read in context (worst case,
    zero acceptance, speculation degrades toward the K+1-token forward
    cost per token)."""
    import jax
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.models import family_module
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine

    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
             "int8": "int8"}[dtype_name]
    params = family_module(config).init_params(config, jax.random.PRNGKey(0))
    max_seq = min(prompt_len + s_b + draft_len, config.n_positions)
    spec = SpecDecodeEngine(params, config, max_seq=max_seq, dtype=dtype,
                            draft_len=draft_len)
    plain = DecodeEngine(params, config, max_seq=max_seq, dtype=dtype)
    prompt = np.random.default_rng(0).integers(
        0, config.vocab_size, size=(1, prompt_len))

    spec_out = _two_point(spec, prompt, s_b=s_b)      # shared harness:
    plain_out = _two_point(plain, prompt, s_b=s_b)    # degraded fallback etc.
    out = {
        "spec_tokens_per_sec": spec_out["tokens_per_sec"],
        "plain_tokens_per_sec": plain_out["tokens_per_sec"],
        "verify_steps": spec_out["verify_steps"],
        "accepted_tokens_per_verify": spec_out["accepted_tokens_per_verify"],
        "draft_len": draft_len,
        "speedup": round(
            spec_out["tokens_per_sec"] / plain_out["tokens_per_sec"], 2),
    }
    if spec_out.get("degraded_timing") or plain_out.get("degraded_timing"):
        out["degraded_timing"] = True
    return out


def measure_flash_attention(seq_lens=(1024, 2048, 4096), iters: int = 0,
                            ) -> list:
    """Pallas flash kernel vs the XLA einsum attention, fwd and fwd+bwd.

    GPT-2 124M head geometry (H=12, hd=64), bf16 inputs, per-S speedups.
    TPU only (the bench refuses to start without one; rows carry the
    backend name). ``iters=0`` picks a per-S window sized so the marginal
    signal clears the per-window jitter; a marginal that still comes out
    non-positive is reported as null (below resolution), never as a
    negative "speedup".
    """
    import jax
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.ops.attention import causal_attention
    from llm_sharding_demo_tpu.ops.flash_attention import flash_attention

    rows = []
    for s in seq_lens:
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 12, s, 64),
                                     dtype=jnp.bfloat16) for i in range(3))

        def flash_fwd(q, k, v):
            return flash_attention(q, k, v)

        def _chain_grads(fwd, q, k, v):
            # all three grads feed the carry (else XLA DCEs the dk/dv
            # kernels); normalized so 100+ chained steps stay finite
            dq, dk, dv = jax.grad(
                lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)
            acc = (dq + dk + dv).astype(jnp.float32)
            return (acc / jnp.maximum(jnp.max(jnp.abs(acc)), 1e-3)
                    ).astype(q.dtype)

        def flash_step(q, k, v):
            return _chain_grads(flash_fwd, q, k, v)

        def xla_step(q, k, v):
            return _chain_grads(causal_attention, q, k, v)

        def time_it(op, n_iters):
            # N dependency-chained invocations inside ONE program (the
            # output feeds the next call's q), closed by a host fetch:
            # dataflow chaining serializes the invocations inside the
            # window (see _fetch).
            compiled = {}

            def make(n):
                if n not in compiled:
                    @jax.jit
                    def run(q, k, v):
                        return jax.lax.fori_loop(
                            0, n, lambda i, acc: op(acc, k, v), q)
                    compiled[n] = run
                return compiled[n]

            def time_window(n):
                fn = make(n)
                t0 = time.perf_counter()
                _fetch(fn(q, k, v))
                return time.perf_counter() - t0

            m = marginal_seconds(time_window, n_iters, 5 * n_iters)
            return None if m is None else m * 1e3

        # window sized inversely to the O(S^2) op cost so the marginal
        # signal stays well above barrier jitter at every S
        n = iters or max(25, int(400 * (1024 / s) ** 2))
        t_flash, t_xla = time_it(flash_fwd, n), time_it(causal_attention, n)
        tb_flash, tb_xla = time_it(flash_step, n), time_it(xla_step, n)

        def rnd(x):
            return None if x is None else round(x, 3)

        def ratio(a, b):
            return None if (a is None or b is None) else round(a / b, 2)

        from llm_sharding_demo_tpu.ops.flash_attention import flash_profitable
        auto = "pallas" if flash_profitable(s) else "xla"
        rows.append({
            "seq_len": s,
            "fwd_flash_ms": rnd(t_flash),
            "fwd_xla_ms": rnd(t_xla),
            "fwd_speedup": ratio(t_xla, t_flash),
            "fwdbwd_flash_ms": rnd(tb_flash),
            "fwdbwd_xla_ms": rnd(tb_xla),
            "fwdbwd_speedup": ratio(tb_xla, tb_flash),
            # what attention_impl="pallas" actually runs at this length:
            # dispatch-by-measured-crossover (ops.flash_attention.
            # flash_profitable), so the effective speedup is
            # max(1.0, kernel speedup) — the kernel never regresses
            "auto_dispatch": auto,
            "backend": jax.default_backend(),
        })
    return rows


def measure_uncached_jax(config, prompt_len: int, new_tokens: int,
                         dtype_name: str = "bfloat16",
                         n1: int = STEPS_A):
    """Our model WITHOUT the KV cache: re-forward the full fixed-length
    sequence per token (one compile; the reference's O(n^2) algorithm at
    constant shape). Denominator for cfg5's cache-speedup ratio. The
    per-token host dispatches pipeline asynchronously — comparable with
    the cached steady-state."""
    import jax
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.models import gpt2

    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    params = gpt2.init_params(config, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    total = prompt_len + new_tokens

    def step(ids, t):
        logits = gpt2.forward(params, ids, config)          # [1, total, V]
        nxt = jnp.argmax(jax.lax.dynamic_slice(
            logits, (0, t - 1, 0), (1, 1, config.vocab_size))).astype(jnp.int32)
        return jax.lax.dynamic_update_slice(ids, nxt[None, None], (0, t))

    def make(n_tokens: int):
        # the whole n-token O(n^2) decode as ONE chained program — each
        # step's ids feed the next, so device time is dataflow-serialized
        # and the closing host fetch (_fetch) bounds it honestly
        @jax.jit
        def run(ids):
            return jax.lax.fori_loop(
                prompt_len, prompt_len + n_tokens,
                lambda t, ids: step(ids, t), ids)
        return run

    ids0 = np.zeros((1, total), dtype=np.int32)
    ids0[0, :prompt_len] = np.random.default_rng(0).integers(
        0, config.vocab_size, size=(prompt_len,))
    ids0 = jnp.asarray(ids0)
    compiled = {}

    def time_window(n) -> float:
        if n not in compiled:
            compiled[n] = make(n)
        t0 = time.perf_counter()
        _fetch(compiled[n](ids0))
        return time.perf_counter() - t0

    # marginal rate over tokens [n1, new_tokens) — ``n1`` defaults to the
    # SAME small window the cached engine's two-point marginal starts at,
    # so cfg5's cached/uncached rates cover identical token ranges (the
    # uncached path is O(n^2): a deeper-only window would understate its
    # rate and overstate the cache speedup). None when below resolution.
    m = marginal_seconds(time_window, n1, new_tokens)
    return None if m is None else 1.0 / m


FULL_MATRIX_FILE = "BENCH_full.json"
_COMPACT_DROP = ("note", "traceback_tail", "metrics_delta")


def _metrics_delta(before: dict, after: dict, limit: int = 60) -> dict:
    """Changed series between two ``REGISTRY.snapshot()`` calls, per
    bench config row: counters/histograms as deltas, gauges at their
    final value. Journaled alongside each row's timing so acceptance
    rates, cache hits, and compile events per config become part of the
    perf trajectory instead of being lost when the process exits. Kept
    out of the compact driver line (``_COMPACT_DROP``) — the full
    matrix file and the progress journal carry it."""
    from llm_sharding_demo_tpu.utils.metrics import METRIC_CATALOG
    changed = {}
    for k, v in sorted(after.items()):
        if not isinstance(v, (int, float)) or k.endswith("_avg"):
            continue
        base = k.split("{", 1)[0]
        if METRIC_CATALOG.get(base) == "gauge":
            if before.get(k) != v:
                changed[k] = v
        else:
            d = v - before.get(k, 0)
            if d:
                changed[k] = round(d, 6)
    if len(changed) <= limit:  # exactly-limit rows must not claim truncation
        return changed
    out = dict(list(changed.items())[:limit])
    out["truncated"] = True
    return out


def emit(payload: dict, write_file: bool = True) -> None:
    """Write the FULL annotated matrix to ``FULL_MATRIX_FILE`` and print a
    COMPACT single JSON line for the driver's tail capture.

    Round 2 lost half its measurement matrix: the one output line (nine
    configs with long prose notes) outgrew the driver's tail window and
    BENCH_r02.json recorded ``parsed: null``. The driver contract is one
    parseable line; the prose belongs in the matrix file.
    ``write_file=False`` (--quick smoke runs) keeps a full run's matrix
    from being clobbered by a one-config smoke payload.
    """
    import os
    if write_file:
        here = os.path.dirname(os.path.abspath(__file__))
        full_path = os.path.join(here, FULL_MATRIX_FILE)
        try:
            with open(full_path, "w") as f:
                json.dump(payload, f, indent=2)
                f.write("\n")
        except OSError:
            pass  # read-only checkout: the compact line still reports
        try:
            # BASELINE.md's measured table is RENDERED from this artifact
            # (VERDICT r4 weak #7: regenerate, don't accrete)
            sys_path_added = False
            import sys as _sys
            tools = os.path.join(here, "tools")
            if tools not in _sys.path:
                _sys.path.insert(0, tools)
                sys_path_added = True
            import render_baseline
            render_baseline.update_file(os.path.join(here, "BASELINE.md"),
                                        payload)
            if sys_path_added:
                _sys.path.remove(tools)
        except Exception:  # noqa: BLE001 — rendering must never cost the
            pass           # artifact its JSON line

    def compact_cfg(cfg: dict) -> dict:
        out = {}
        for k, v in cfg.items():
            if k in _COMPACT_DROP:
                continue
            if isinstance(v, str) and len(v) > 80:
                v = v[:77] + "..."
            out[k] = v
        return out

    compact = {k: v for k, v in payload.items() if k != "configs"}
    compact["configs"] = [compact_cfg(c) for c in payload.get("configs", [])]
    if write_file:
        compact["full_matrix_file"] = FULL_MATRIX_FILE
    print(json.dumps(compact))


def measure_iterbatch(config, dtype="bfloat16", n_requests: int = 12,
                      max_batch: int = 4, steps: int = 192,
                      prompt_len: int = 60, stagger_s: float = 0.04,
                      seg_steps: int = 64) -> dict:
    """Staggered-arrival serving throughput: the admission batcher
    (rounds run to completion) vs the iteration-level scheduler
    (requests join the live batch at segment boundaries) on the same
    weights and workload. Arrivals are staggered so most requests land
    MID-decode — the case admission-level batching serializes.

    Wall-clock aggregate includes every host sync either scheduler pays
    (an end-to-end number, not a device-only one). All requests share one
    shape, so each scheduler compiles a bounded handful of programs.
    """
    import threading as _th

    import jax
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.models import gpt2
    from llm_sharding_demo_tpu.runtime.batcher import BatchingEngine
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine

    params = gpt2.init_params(config, jax.random.PRNGKey(0),
                              dtype=jnp.float32)
    # cache headroom beyond one generation: a mid-decode joiner needs
    # depth + its steps to fit, so without headroom nothing ever joins
    # (the uniform-depth design spends d - plen slots on a late joiner)
    bucketed = (prompt_len + 15) // 16 * 16
    max_seq = min(config.n_positions, bucketed + 4 * steps)
    engine = DecodeEngine(params, config, max_seq=max_seq, dtype=dtype)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, config.vocab_size, size=(prompt_len,))

    def drive(sched) -> float:
        done = [None] * n_requests

        def run(i):
            time.sleep(i * stagger_s)
            done[i] = sched.generate(prompt, steps)

        t0 = time.perf_counter()
        threads = [_th.Thread(target=run, args=(i,))
                   for i in range(n_requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        assert all(r is not None for r in done)
        return n_requests * steps / dt

    results = {}
    for name, make in (
            ("admission", lambda: BatchingEngine(
                engine, max_batch=max_batch, max_wait_ms=5.0)),
            ("iter", lambda: IterBatchingEngine(
                engine, max_batch=max_batch, seg_steps=seg_steps,
                max_wait_ms=5.0))):
        sched = make()
        drive(sched)                 # warmup: compiles + caches programs
        before = sched.stats() if name == "iter" else None
        results[name] = drive(sched)
        if name == "iter":
            after = sched.stats()    # delta = the measured drive only
            results["iter_stats"] = {
                k: after[k] - before[k] for k in after}
    return {
        "admission_tokens_per_sec": round(results["admission"], 1),
        "iter_tokens_per_sec": round(results["iter"], 1),
        "iter_vs_admission": round(results["iter"] / results["admission"],
                                   2),
        "n_requests": n_requests, "max_batch": max_batch, "steps": steps,
        "stagger_ms": round(stagger_s * 1e3, 1),
        "seg_steps": seg_steps,
        "iter_joins": results["iter_stats"]["joins"],
        "iter_segments": results["iter_stats"]["segments"],
    }


def measure_paged_kv(config, dtype="bfloat16", steps: int = 192,
                     prompt_len: int = 60, block_size: int = 16,
                     max_batch: int = 8) -> dict:
    """Paged vs contiguous decode (ISSUE 5): (a) solo decode rate
    through the PagedKVRunner (the engine's own programs + one
    gather/scatter round trip per segment) vs the plain engine — the
    paging tax; (b) max concurrent iterbatch rows before the first
    preemption on a deliberately small pool — the capacity the block
    granularity buys over per-row max_seq arenas.

    Needs the bench chip: CPU rates for the gather/scatter overhead
    would mislead (the tax is HBM traffic, not host arithmetic).
    """
    import threading as _th

    import jax
    import jax.numpy as jnp


    from llm_sharding_demo_tpu.models import gpt2
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
    from llm_sharding_demo_tpu.runtime.kv_pool import (KVBlockPool,
                                                       PagedKVRunner)

    params = gpt2.init_params(config, jax.random.PRNGKey(0),
                              dtype=jnp.float32)
    bucketed = (prompt_len + 15) // 16 * 16
    max_seq = min(config.n_positions,
                  -(-(bucketed + 2 * steps) // block_size) * block_size)
    engine = DecodeEngine(params, config, max_seq=max_seq, dtype=dtype)
    nbm = max_seq // block_size
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, config.vocab_size, size=(prompt_len,))

    # (a) solo paged vs contiguous decode rate
    pool = KVBlockPool.for_engine(engine, num_blocks=2 * nbm,
                                  block_size=block_size)
    runner = PagedKVRunner(engine, pool)
    engine.generate(prompt[None, :], steps)          # warmup/compile
    runner.generate(prompt[None, :], steps)
    t0 = time.perf_counter()
    contiguous = engine.generate(prompt[None, :], steps)
    t1 = time.perf_counter()
    runner.generate(prompt[None, :], steps)
    t2 = time.perf_counter()
    contig_rate = steps / (t1 - t0)
    paged_rate = steps / (t2 - t1)

    # (b) concurrency before first preemption: a pool of 2 full rows'
    # worth of blocks, rows that each need ~1/2 row — block granularity
    # admits ~4 before pressure; the contiguous allocator would cap at
    # pool_bytes / max_seq_row = 2
    small = KVBlockPool.for_engine(engine, num_blocks=2 * nbm,
                                   block_size=block_size, watermark=1.0)
    ib = IterBatchingEngine(engine, max_batch=max_batch, seg_steps=64,
                            max_wait_ms=200.0, pool=small)
    admitted = 0
    threads = []

    def run_one():
        ib.generate(prompt, steps, timeout=600)

    for i in range(max_batch):
        if ib.stats()["preemptions"] > 0:
            break
        threads.append(_th.Thread(target=run_one))
        threads[-1].start()
        admitted += 1
        time.sleep(0.2)
    for t in threads:
        t.join()
    st = ib.stats()
    return {
        "contiguous_tokens_per_sec": round(contig_rate, 1),
        "paged_tokens_per_sec": round(paged_rate, 1),
        "paging_tax": round(1 - paged_rate / contig_rate, 3),
        "block_size": block_size, "max_seq": max_seq,
        "pool_blocks": 2 * nbm,
        "rows_admitted_before_first_preemption": admitted,
        "contiguous_rows_that_pool_could_hold": 2,
        "preemptions": st["preemptions"], "resumes": st["resumes"],
    }


def measure_kv_quant_capacity(config, steps: int = 192,
                              prompt_len: int = 60, block_size: int = 16,
                              max_batch: int = 12) -> dict:
    """Quantized-vs-f32 KV capacity at EQUAL pool bytes (ISSUE 16): two
    pools sized to the same HBM budget — the f32 pool's byte footprint,
    with the int8 pool taking however many narrow blocks fit in those
    bytes (``kv_pool.bytes_per_block`` arithmetic, scales included) —
    driven through the iteration scheduler until the first preemption.
    The admitted-row ratio IS the effective-capacity claim: admission is
    denominated in blocks, so narrow storage converts to concurrency
    with zero scheduler changes. Also journals each pool's prefix-store
    depth (whole aligned prompts the allocator can hold resident — the
    same blocks_for arithmetic the prefix store's LRU lives under).
    The kv.int8 accuracy side of the trade rides the numerics_oracle
    row (kv_int8_logit_mse / kv_int8_top1_agreement), gated by
    bench_diff alongside this row's capacity metrics.

    Needs the bench chip: the 2-4x is HBM bytes; host-RAM pools would
    journal a vacuous ratio.
    """
    import threading as _th

    import jax
    import jax.numpy as jnp


    from llm_sharding_demo_tpu.models import gpt2
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
    from llm_sharding_demo_tpu.runtime.kv_pool import (KVBlockPool,
                                                       bytes_per_block)

    params = gpt2.init_params(config, jax.random.PRNGKey(0),
                              dtype=jnp.float32)
    bucketed = (prompt_len + 15) // 16 * 16
    max_seq = min(config.n_positions,
                  -(-(bucketed + 2 * steps) // block_size) * block_size)
    # f32 engine: the full-precision pool inherits 4-byte blocks, so the
    # equal-byte comparison is the paper-claim shape (int8 vs f32)
    engine = DecodeEngine(params, config, max_seq=max_seq, dtype="float32")
    nbm = max_seq // block_size
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, config.vocab_size, size=(prompt_len,))

    full_bpb = bytes_per_block(config.n_layer, config.n_head, block_size,
                               config.head_dim, dtype=jnp.float32)
    int8_bpb = bytes_per_block(config.n_layer, config.n_head, block_size,
                               config.head_dim, dtype=jnp.float32,
                               block_dtype="int8")
    full_blocks = 2 * nbm                   # two full rows' worth
    budget = full_blocks * full_bpb
    int8_blocks = budget // int8_bpb

    def rows_before_preemption(pool):
        ib = IterBatchingEngine(engine, max_batch=max_batch, seg_steps=64,
                                max_wait_ms=200.0, pool=pool)
        admitted = 0
        threads = []

        def run_one():
            ib.generate(prompt, steps, timeout=600)

        for _ in range(max_batch):
            if ib.stats()["preemptions"] > 0:
                break
            threads.append(_th.Thread(target=run_one))
            threads[-1].start()
            admitted += 1
            time.sleep(0.2)
        for t in threads:
            t.join()
        # whole aligned prompts resident at once = the prefix store's
        # depth bound on this pool (its entries hold these same blocks)
        depth = (pool.allocator.num_blocks
                 // pool.allocator.blocks_for(bucketed))
        return admitted, depth, ib.stats()

    f32_pool = KVBlockPool.for_engine(engine, num_blocks=full_blocks,
                                      block_size=block_size, watermark=1.0)
    f32_rows, f32_depth, f32_st = rows_before_preemption(f32_pool)
    q_pool = KVBlockPool.for_engine(engine, num_blocks=int(int8_blocks),
                                    block_size=block_size, watermark=1.0,
                                    block_dtype="int8")
    q_rows, q_depth, q_st = rows_before_preemption(q_pool)
    return {
        "pool_bytes": int(budget),
        "f32_bytes_per_block": int(full_bpb),
        "int8_bytes_per_block": int(int8_bpb),
        "f32_pool_blocks": int(full_blocks),
        "int8_pool_blocks": int(int8_blocks),
        "f32_before_first_preemption": f32_rows,
        "int8_before_first_preemption": q_rows,
        "capacity_ratio": round(q_rows / max(f32_rows, 1), 2),
        "f32_prefix_store_depth": f32_depth,
        "int8_prefix_store_depth": q_depth,
        "f32_preemptions": f32_st["preemptions"],
        "int8_preemptions": q_st["preemptions"],
    }


def measure_tiered_kv_depth(n_requests: int = 56, prefix_depth: int = 24,
                            seed: int = 5, max_new: int = 8,
                            block_size: int = 8,
                            device_blocks: int = 16) -> dict:
    """grafttier capacity row (ISSUE 20): a bursty_chat-derived prefix
    population (the loadgen ``prefix_depth`` knob) driven through a
    deliberately small device pool with a host-RAM spill tier attached
    (``runtime.kv_tier``), twice over the SAME seeded schedule. The
    cold epoch inserts every arrival's full-depth prefix entry and the
    store's capacity trim demotes them to the host tier; the warm
    epoch replays the identical arrivals, so every lookup lands on a
    demoted entry and promotes it back — the affinity-hit path.

    The capacity claim is LEDGER-MEASURED, never shape arithmetic:
    ``depth_ratio`` divides the host tier's resident bytes (graftmem
    ``host_spill`` holding, the same single bookkeeping path
    /debug/memory serves) by the device pool's plane bytes (codes +
    scales holdings) at the cold epoch's end — the >= 10x prefix-store
    depth the tier buys over the device pool alone. The warm epoch
    contributes the serving-side rates: prefix/promoted hit rates and
    goodput (higher-better), mean promote stall (lower-better), all
    gated by tools/bench_diff.py.

    Runs on any backend: the depth claim is byte accounting and the
    rates are within-row (one epoch vs its own wall), not chip rates.
    """
    import dataclasses as _dc

    import jax

    from llm_sharding_demo_tpu.loadgen.profiles import PROFILES
    from llm_sharding_demo_tpu.loadgen.schedule import schedule
    from llm_sharding_demo_tpu.models import gpt2
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    from llm_sharding_demo_tpu.runtime.kv_pool import (KVBlockPool,
                                                       PagedKVRunner)
    from llm_sharding_demo_tpu.runtime.kv_tier import HostKVTier
    from llm_sharding_demo_tpu.runtime.prefix_cache import \
        PrefixCachingEngine
    from llm_sharding_demo_tpu.utils import graftmem

    # byte-vocab micro model: arrival prompt STRINGS encode directly to
    # token ids, so the driven prefixes are exactly the profile's
    # deterministic shared_prefix population
    config = gpt2.GPT2Config(vocab_size=256, n_positions=128, n_embd=32,
                             n_layer=2, n_head=4)
    params = gpt2.init_params(config, jax.random.PRNGKey(0))
    engine = DecodeEngine(params, config, max_seq=96)
    pool = KVBlockPool.for_engine(engine, num_blocks=device_blocks,
                                  block_size=block_size)
    host_blocks = 16 * device_blocks
    pool.attach_tier(HostKVTier(host_blocks))
    # capacity=2 keeps at most two entries device-resident — every
    # further insert demotes through the tier ladder, which is the
    # whole point of the row
    pref = PrefixCachingEngine(engine, capacity=2, chunk=block_size,
                               pool=pool)
    runner = PagedKVRunner(engine, pool, prefix=pref)

    prof = _dc.replace(PROFILES["bursty_chat"], prefix_depth=prefix_depth)
    arrivals = schedule(prof, seed, n_requests)
    prompts = [np.frombuffer(a.prompt.encode("utf-8"),
                             dtype=np.uint8).astype(np.int32)[:80]
               for a in arrivals]

    def epoch() -> float:
        t0 = time.perf_counter()
        for p in prompts:
            runner.generate(p, max_new)
        return time.perf_counter() - t0

    cold_s = epoch()                       # insert + demote (and XLA
    #                                        compiles — warm excludes)
    pool_bytes = (graftmem.holding_bytes(pool, "data")
                  + graftmem.holding_bytes(pool, "scales"))
    cold_tier = pool.tier.stats()
    cold_store = pref.stats()
    warm_s = epoch()                       # replay: promote on hit
    warm_tier = pool.tier.stats()
    warm_store = pref.stats()
    hits = warm_store["hits"] - cold_store["hits"]
    promoted = warm_tier["promotions"] - cold_tier["promotions"]
    stall_ms = (warm_tier["promote_ms_total"]
                - cold_tier["promote_ms_total"])
    return {
        "requests_per_epoch": n_requests,
        "prefix_depth": prefix_depth,
        "seed": seed,
        "device_pool_bytes": int(pool_bytes),
        "host_bytes_resident": int(cold_tier["host_bytes"]),
        "host_blocks_in_use": cold_tier["host_blocks_in_use"],
        "host_blocks_total": host_blocks,
        "depth_ratio": round(cold_tier["host_bytes"]
                             / max(pool_bytes, 1), 2),
        "demotions": warm_tier["demotions"],
        "discards": warm_tier["discards"],
        "prefix_hit_rate": round(hits / max(n_requests, 1), 3),
        "promoted_hit_rate": round(promoted / max(n_requests, 1), 3),
        "goodput_rps": round(n_requests / max(warm_s, 1e-9), 2),
        "promote_stall_ms": round(stall_ms / max(promoted, 1), 3),
        "cold_epoch_s": round(cold_s, 3),
        "warm_epoch_s": round(warm_s, 3),
    }


def measure_concurrent_load(config, dtype="bfloat16", width: int = 6,
                            steps: int = 96, prompt_len: int = 48,
                            block_size: int = 16) -> dict:
    """Concurrent-load latency + lock-contention row (ISSUE 8): ``width``
    (>= 4) simultaneous clients through the pooled iteration scheduler,
    with every declared lock constructed as an instrumented graftsched
    ``TracedLock`` in accounting-only mode (``GRAFTSCHED=trace``: wait
    totals, no schedule perturbation). Journals per-request p50/p99
    latency AND the per-lock contention totals — so a change that makes
    the host-side scheduler serialize on a blocked lock (exactly the
    stall TokenWeave-style overlap cannot absorb, ROADMAP item 3) shows
    up in the same trajectory as the latencies it causes.

    Needs the bench chip: CPU decode rates make queueing, not locking,
    the bottleneck, and the contention split would mislead.
    """
    import threading as _th

    import jax


    from llm_sharding_demo_tpu.models import gpt2
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
    from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
    from llm_sharding_demo_tpu.utils import graftsched

    from llm_sharding_demo_tpu.utils import metrics as _metrics
    from llm_sharding_demo_tpu.utils import tracing as _tracing

    prior = os.environ.get("GRAFTSCHED")
    os.environ["GRAFTSCHED"] = "trace"    # accounting only, no yields
    # the module-singleton registry/recorder locks were constructed at
    # import time (before the env was armed) — re-wrap them so their
    # contention is measured too. Safe here: this row runs before its
    # own threads start, and prior rows' worker threads are idle in
    # queue.get (no REGISTRY call in flight).
    reg_lock, rec_lock = _metrics.REGISTRY._lock, _tracing.RECORDER._lock
    _metrics.REGISTRY._lock = graftsched.lock(
        "metrics.MetricsRegistry._lock")
    _tracing.RECORDER._lock = graftsched.lock(
        "tracing.FlightRecorder._lock")
    try:
        graftsched.clear()
        params = gpt2.init_params(config, jax.random.PRNGKey(0))
        bucketed = (prompt_len + 15) // 16 * 16
        max_seq = min(config.n_positions, bucketed + 2 * steps)
        engine = DecodeEngine(params, config, max_seq=max_seq,
                              dtype=dtype)
        nbm = -(-max_seq // block_size)
        pool = KVBlockPool.for_engine(engine, num_blocks=width * nbm,
                                      block_size=block_size)
        ib = IterBatchingEngine(engine, max_batch=width, seg_steps=32,
                                max_wait_ms=20.0, pool=pool)
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, config.vocab_size, size=(prompt_len,))
        ib.generate(prompt, steps, timeout=600)       # warmup/compile

        lat = [0.0] * width

        def run_one(i):
            t0 = time.perf_counter()
            ib.generate(prompt, steps, timeout=600)
            lat[i] = time.perf_counter() - t0

        graftsched.clear()                # contention for the run only
        threads = [_th.Thread(target=run_one, args=(i,))
                   for i in range(width)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        cont = graftsched.contention()
        return {
            "width": width,
            "steps_per_request": steps,
            "p50_request_latency_ms": round(
                float(np.percentile(lat, 50)) * 1e3, 1),
            "p99_request_latency_ms": round(
                float(np.percentile(lat, 99)) * 1e3, 1),
            "aggregate_tokens_per_sec": round(width * steps / wall, 1),
            "lock_contention": cont,
            "lock_wait_total_ms": round(
                sum(v["wait_seconds"] for v in cont.values()) * 1e3, 2),
            "findings": [f.format() for f in graftsched.findings()],
        }
    finally:
        _metrics.REGISTRY._lock = reg_lock
        _tracing.RECORDER._lock = rec_lock
        if prior is None:
            os.environ.pop("GRAFTSCHED", None)
        else:
            os.environ["GRAFTSCHED"] = prior


def measure_fault_recovery(config, dtype="bfloat16", width: int = 6,
                           steps: int = 96, prompt_len: int = 48,
                           block_size: int = 16, fault_rate: float = 0.10,
                           fault_seed: int = 10) -> dict:
    """Degraded-mode serving cost row (ISSUE 10, graftfault): ``width``
    concurrent clients through the pooled iteration scheduler with a
    PINNED seeded fault plan injecting transient decode faults at
    ``fault_rate`` per segment — every faulted segment parks the live
    rows through the recompute-resume path and replays them
    byte-identically. Journals p50/p99 request latency, the success
    rate, and the park/resume counts, so the price of fault recovery
    rides the same trajectory (tools/bench_diff.py gates success_rate
    higher-better and the latencies lower-better) as the fast path.

    Needs the bench chip for the same reason concurrent_load does: CPU
    decode rates make queueing, not recovery, the bottleneck.
    """
    import threading as _th

    import jax


    from llm_sharding_demo_tpu.models import gpt2
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
    from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
    from llm_sharding_demo_tpu.utils import graftfault

    params = gpt2.init_params(config, jax.random.PRNGKey(0))
    bucketed = (prompt_len + 15) // 16 * 16
    max_seq = min(config.n_positions, bucketed + 2 * steps)
    engine = DecodeEngine(params, config, max_seq=max_seq, dtype=dtype)
    nbm = -(-max_seq // block_size)
    pool = KVBlockPool.for_engine(engine, num_blocks=width * nbm,
                                  block_size=block_size)
    ib = IterBatchingEngine(engine, max_batch=width, seg_steps=32,
                            max_wait_ms=20.0, pool=pool)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, config.vocab_size, size=(prompt_len,))
    ib.generate(prompt, steps, timeout=600)       # warmup/compile

    lat = [0.0] * width
    ok = [False] * width

    def run_one(i):
        t0 = time.perf_counter()
        try:
            ib.generate(prompt, steps, timeout=600)
            ok[i] = True
        except Exception:  # noqa: BLE001 — failure IS the measurement
            pass
        lat[i] = time.perf_counter() - t0

    plan = graftfault.FaultPlan(seed=fault_seed, rate=fault_rate,
                                sites={"iterbatch.decode_seg"},
                                kinds={"decode_transient"})
    base = ib.stats()
    with graftfault.use(plan):
        threads = [_th.Thread(target=run_one, args=(i,))
                   for i in range(width)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
    st = ib.stats()
    return {
        "width": width,
        "steps_per_request": steps,
        "fault_rate": fault_rate,
        "fault_seed": fault_seed,
        "injected_faults": len(plan.injections),
        "fault_parks": st["fault_parks"] - base["fault_parks"],
        "resumes": st["resumes"] - base["resumes"],
        "success_rate": round(sum(ok) / width, 4),
        "p50_request_latency_ms": round(
            float(np.percentile(lat, 50)) * 1e3, 1),
        "p99_request_latency_ms": round(
            float(np.percentile(lat, 99)) * 1e3, 1),
        "aggregate_tokens_per_sec": round(width * steps / wall, 1),
    }


def measure_graftload(profiles=("bursty_chat", "agentic"), seed: int = 0,
                      n_requests: int = 16,
                      rate_scales=(1.0, 2.0)) -> dict:
    """graftload rows (ISSUE 11): the seeded open-loop scenario harness
    driven against the in-process pooled-iterbatch serving app —
    ``rate_scales`` sweeps each profile's declared arrival rate, so
    every (profile, rate) pair contributes one throughput-vs-p99
    Pareto point, and the base rate contributes the per-profile
    goodput/SLO-attainment row (typed 429/503 sheds counted separately
    from SLO misses). The schedule is a pure function of (seed,
    profile, k) — this row replays identically run to run.

    Needs the bench chip: on CPU the decode itself dominates and the
    Pareto front would measure the host, not the serving stack.
    """
    import jax


    from llm_sharding_demo_tpu import loadgen
    from llm_sharding_demo_tpu.utils import graftscope
    from tools.graftload import build_demo_app

    client, recorder, _registry = build_demo_app(
        max_seq=256, max_batch=4,
        recorder_capacity=max(64, 2 * n_requests * len(profiles)
                              * len(rate_scales)))
    # warmup/compile pass (serial, tiny): the open-loop tails must
    # measure serving, not first-touch XLA compiles
    loadgen.run_load(client, loadgen.profile(profiles[0]),
                     seed=seed + 1, n=2, mode="serial",
                     recorder=recorder)
    # window the journaled occupancy to the sweep itself — the
    # graftscope rings are process-global and earlier bench configs
    # (concurrent_load, fault_recovery) sampled the same series
    occ_since = graftscope.now_ms()
    pareto, slo_rows, reports = [], [], []
    for name in profiles:
        prof = loadgen.profile(name)
        for scale in rate_scales:
            rep = loadgen.run_load(client, prof, seed=seed,
                                   n=n_requests, rate_scale=scale,
                                   mode="open", recorder=recorder)
            reports.append(rep)
            row = loadgen.pareto_row(rep)
            row["workload"] = f"{name}_x{scale:g}".replace(".", "p")
            pareto.append(row)
            if scale == rate_scales[0]:
                srow = loadgen.slo_row(rep)
                srow["workload"] = name
                slo_rows.append(srow)
    return {
        "seed": seed,
        "requests_per_run": n_requests,
        "pareto": pareto,
        "slo_rows": slo_rows,
        # the measured TRAFFIC-MIX signal (ISSUE 12 satellite, the
        # ROADMAP item-5/6 follow-on AUTO_PLAN continuous mode needs):
        # demand + goodput-under-SLO + induced occupancy per
        # (profile, rate) — loadgen.traffic_mix_row over the same runs
        "traffic_mix": loadgen.traffic_mix_row(reports)["workloads"],
        "occupancy": loadgen.occupancy_summary(since_ms=occ_since),
    }


def measure_fleet_scaling(seed: int = 0, n_requests: int = 16) -> dict:
    """graftfleet scaling row (ISSUE 12): the disaggregated fleet —
    router + 1 prefill replica + N decode replicas over ONE shared
    pool — driven by the bursty_chat profile at 1 vs 2 decode
    replicas. The deep-shared-prefix workload is the fleet's favorable
    case (the prefill replica warms the content-keyed registry once,
    affinity routing keeps adoptions local), so this row is the
    replica-scaling signal: throughput/goodput per decode-replica
    count plus the router's affinity hit rate and typed-shed split.

    Needs the bench chip: on CPU the decode itself dominates and a
    second replica would measure host contention, not serving scale.
    """
    import jax


    from llm_sharding_demo_tpu import loadgen
    from llm_sharding_demo_tpu.fleet import build_fleet

    prof = loadgen.profile("bursty_chat")
    rows = []
    for n_decode in (1, 2):
        f = build_fleet(n_decode=n_decode, n_prefill=1,
                        max_seq=256, kv_pool_blocks=0,
                        recorder_capacity=max(64, 2 * n_requests))
        # warmup/compile pass so the open-loop tails measure serving
        loadgen.run_load(f.client, prof, seed=seed + 1, n=2,
                         mode="serial", recorder=f.recorder)
        # affinity_stats is cumulative — snapshot after warmup so the
        # journaled (gated) rates cover only the measured run
        base = f.app.router.affinity_stats()
        rep = loadgen.run_load(f.client, prof, seed=seed,
                               n=n_requests, rate_scale=2.0,
                               mode="open", recorder=f.recorder)
        stats = {k: v - base[k]
                 for k, v in f.app.router.affinity_stats().items()}
        routed = stats["hits"] + stats["fallbacks"]
        rows.append({
            "workload": f"decode_x{n_decode}",
            "decode_replicas": n_decode,
            "offered_rps": rep["offered_rps"],
            "completed": rep["completed"],
            "throughput_tokens_per_sec":
                rep["throughput_tokens_per_sec"],
            "goodput_rps": rep["goodput_rps"],
            "goodput_fraction": rep["goodput_fraction"],
            "p99_e2e_ms": rep["p99_e2e_ms"],
            "shed_429": rep["shed_429"],
            "shed_503": rep["shed_503"],
            "affinity_hit_rate": round(stats["hits"] / routed, 4)
            if routed else 0.0,
            "replica_sheds": stats["sheds"],
        })
    return {"seed": seed, "requests_per_run": n_requests,
            "workloads": rows}


def measure_plan_switch(seed: int = 7, n_requests: int = 10) -> dict:
    """graftwatch live re-planning row (ISSUE 13): the seeded mix flip
    (serial single-stream -> open burst -> serial again, agentic
    profile) against the AUTO_PLAN_CONTINUOUS app — the bench-grade
    twin of tests/test_graftwatch.py's acceptance run. Journals the
    live switch count, goodput/throughput before (solo plan, serial
    phase) and after (batched plan, burst phase) the switch, and the
    pinned invariant as a number: compiled programs minted by replaying
    the whole mix across further live switches — ZERO beyond the
    pre-certified set, gated lower-better by bench_diff so any upward
    drift reads as a certified-envelope leak, not noise.

    Needs the bench chip: on CPU the decode itself dominates and the
    open-loop burst would measure the host, not the switch.
    """
    import jax


    from llm_sharding_demo_tpu import loadgen
    from tools.graftload import build_demo_app

    prof = loadgen.profile("agentic")
    sched = loadgen.schedule(prof, seed, n_requests)
    # certify the plan set against the schedule's OWN traffic classes
    # (byte-level prompt lengths — the demo app's ByteTokenizer), so
    # the certified bounds cover the whole measured run
    classes = sorted({(len(a.prompt.encode("utf-8")), a.max_new)
                      for a in sched})
    traffic = ",".join(f"{p}/{n}" for p, n in classes)
    client, recorder, _reg = build_demo_app(
        max_seq=256, max_batch=4, recorder_capacity=max(64, 8 * n_requests),
        continuous=True, auto_plan_traffic=traffic)
    sw = client.app.plan_switcher

    def caches():
        solo = sw.plans["solo"]
        eng, pool = solo.engine, solo.pool
        return sum(fn._cache_size() for fn in (
            eng._prefill, eng._prefill_chunked, eng._decode_seg,
            pool._gather, pool._scatter, pool._scatter_row, pool._copy))

    def run(mode, rate=1.0):
        return loadgen.run_load(client, prof, seed=seed, n=n_requests,
                                mode=mode, rate_scale=rate,
                                recorder=recorder)

    # warmup/compile pass so phase goodput measures serving, not
    # first-touch XLA compiles
    loadgen.run_load(client, prof, seed=seed + 1, n=2, mode="serial",
                     recorder=recorder)
    before = run("serial")            # single-stream: stays solo
    burst = run("open", rate=60.0)    # the burst: flips to batched
    run("serial")                     # drains back toward solo
    programs_after_mix = caches()
    # the full mix again: more live switches, zero new programs is the
    # journaled invariant
    run("serial")
    after = run("open", rate=60.0)
    run("serial")
    recompiles = caches() - programs_after_mix
    hv = sw.health_view()
    return {
        "seed": seed,
        "requests_per_run": n_requests,
        "switches": hv["switches"],
        "switch_flips": [f'{e["from"]}->{e["to"]}'
                         for e in sw.events() if e["switched"]],
        "active_plan": hv["active"],
        "certified_program_total": sum(
            sw.certified[p]["program_total"] for p in sw.certified),
        # THE invariant, as a gated metric (lower-better, expect 0)
        "recompiles_beyond_certified": recompiles,
        "goodput_fraction_before": before["goodput_fraction"],
        "goodput_fraction_after": after["goodput_fraction"],
        "throughput_tokens_per_sec_before":
            before["throughput_tokens_per_sec"],
        "throughput_tokens_per_sec_after":
            after["throughput_tokens_per_sec"],
        "p99_e2e_ms_before": before["p99_e2e_ms"],
        "p99_e2e_ms_burst": burst["p99_e2e_ms"],
        "p99_e2e_ms_after": after["p99_e2e_ms"],
    }


def measure_spec_iterbatch(config, dtype="bfloat16", n_requests: int = 8,
                           max_batch: int = 4, steps: int = 160,
                           prompt_len: int = 64, stagger_s: float = 0.04,
                           seg_steps: int = 64, draft_len: int = 6) -> dict:
    """Speculation x continuous batching — the composition this repo's
    two strongest serving optimizations could not reach before: the SAME
    staggered multi-request workload through (a) the plain iteration
    scheduler (one token per forward per row) and (b) the iteration
    scheduler running draft-verify segments (runtime.spec_decode._seg_b,
    per-row acceptance + uniform-depth re-sync).

    The workload is REPETITIVE (periodic prompt), the favorable case for
    prompt-lookup drafting — exactly the serving profile (templated
    outputs, code, few-shot continuations) the composition targets; the
    acceptance column contextualizes the speedup the way cfg8 does for
    the solo case. Exactness is pinned by tests (every spec row
    byte-equal to its solo speculative run); this row measures the
    aggregate tokens/sec the composition buys."""
    import threading as _th

    import jax
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.models import gpt2
    from llm_sharding_demo_tpu.runtime.engine import SamplingConfig
    from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine

    params = gpt2.init_params(config, jax.random.PRNGKey(0),
                              dtype=jnp.float32)
    bucketed = (prompt_len + 15) // 16 * 16
    max_seq = min(config.n_positions,
                  bucketed + 4 * steps + draft_len)
    spec = SpecDecodeEngine(params, config, max_seq=max_seq, dtype=dtype,
                            draft_len=draft_len)
    engine = spec.plain
    # periodic prompt: greedy continuation loops, so lookup drafts land
    period = np.asarray([11, 29, 3, 47, 5, 17, 23, 2], dtype=np.int32)
    prompt = np.tile(period, prompt_len // len(period) + 1)[:prompt_len]

    def drive(ib, sampling) -> float:
        done = [None] * n_requests

        def run(i):
            time.sleep(i * stagger_s)
            done[i] = ib.generate(prompt, steps, sampling=sampling)

        t0 = time.perf_counter()
        threads = [_th.Thread(target=run, args=(i,))
                   for i in range(n_requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        assert all(r is not None for r in done)
        return n_requests * steps / dt

    results = {}
    for name, sampling in (("plain", SamplingConfig()),
                           ("spec", SamplingConfig(spec=True))):
        ib = IterBatchingEngine(engine, max_batch=max_batch,
                                seg_steps=seg_steps, max_wait_ms=5.0,
                                spec=spec)
        drive(ib, sampling)          # warmup: compiles + caches programs
        before = (spec.stats(), ib.stats())
        results[name] = drive(ib, sampling)
        if name == "spec":
            s_after, ib_after = spec.stats(), ib.stats()
            verifies = s_after["verify_steps"] - before[0]["verify_steps"]
            emitted = (s_after["emitted_tokens"]
                       - before[0]["emitted_tokens"])
            results["accept"] = round(emitted / max(verifies, 1), 2)
            results["spec_segments"] = (ib_after["spec_segments"]
                                        - before[1]["spec_segments"])
            results["joins"] = ib_after["joins"] - before[1]["joins"]
    return {
        "iter_tokens_per_sec": round(results["plain"], 1),
        "spec_iter_tokens_per_sec": round(results["spec"], 1),
        "spec_vs_plain_iter": round(results["spec"] / results["plain"], 2),
        "accepted_tokens_per_verify": results["accept"],
        "draft_len": draft_len, "n_requests": n_requests,
        "max_batch": max_batch, "steps": steps,
        "seg_steps": seg_steps, "spec_segments": results["spec_segments"],
        "joins": results["joins"],
        "stagger_ms": round(stagger_s * 1e3, 1),
    }


def measure_training(config, batch: int = 8, seq: int = 512,
                     dtype_name: str = "bfloat16") -> dict:
    """Single-chip jitted train step (fwd + bwd + AdamW, remat): tokens/s
    and achieved MFU. The training subsystem had correctness tests but no
    measured perf before round 3 (VERDICT r2 missing #3).

    MFU convention: model FLOPs = 6 * n_params per token (fwd 2N + bwd
    4N; attention FLOPs and the remat recompute are excluded, the
    standard accounting), against the attached device kind's bf16 peak
    (emitted as ``peak_flops``; MFU is omitted when the peak is unknown,
    e.g. on the CPU fallback).
    """
    import jax
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.models import gpt2
    from llm_sharding_demo_tpu.training import train

    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    params = gpt2.init_params(config, jax.random.PRNGKey(0), dtype=dtype)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    step = train.TrainStep(config, train.adamw(1e-3), remat=True)
    p, opt = step.init(params)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, config.vocab_size, size=(batch, seq + 1)), jnp.int32)

    def make(n):
        @jax.jit
        def run(p, opt, ids):
            def body(i, carry):
                p, opt, _ = carry
                return step._step(p, opt, ids)
            return jax.lax.fori_loop(0, n, body,
                                     (p, opt, jnp.zeros((), jnp.float32)))
        return run

    compiled = {}

    def time_window(n):
        if n not in compiled:
            compiled[n] = make(n)
        t0 = time.perf_counter()
        _, _, loss = compiled[n](p, opt, ids)
        _fetch(loss)
        return time.perf_counter() - t0

    m = marginal_seconds(time_window, 2, 8, reps=3)
    if m is None:
        return {"error": "marginal below timer resolution"}
    tokens_per_sec = batch * seq / m
    out = {
        "tokens_per_sec": round(tokens_per_sec, 1),
        "step_ms": round(m * 1e3, 2),
        "batch": batch, "seq": seq, "n_params": n_params,
    }
    peak = _peak_bf16_flops()
    out["peak_flops"] = peak
    out["mfu"] = round(tokens_per_sec * 6 * n_params / peak, 4)
    return out


def _peak_bf16_flops() -> float:
    """Dense bf16 peak for the attached device kind. A device that is
    not in the table is an error, not a default."""
    import jax
    kind = jax.devices()[0].device_kind.lower()
    for tag, peak in (("v5 lite", 197e12), ("v5e", 197e12),
                      ("v5p", 459e12), ("v5", 459e12),
                      ("v6 lite", 918e12), ("v6e", 918e12),
                      ("v4", 275e12)):
        if tag in kind:
            return peak
    raise ValueError(f"no peak FLOP/s known for device_kind {kind!r}; add "
                     "it to _peak_bf16_flops with its source")


def measure_gpipe_overhead() -> dict:
    """Pipeline schedules (GPipe and 1F1B, pp4 x dp2) vs pure dp8, same
    model and global batch, on an 8-device virtual CPU mesh (the only
    multi-device environment the bench has): the ratios are the
    schedules' overheads — the numbers behind parallel.gpipe's
    bubble-skip claim and parallel.pipeline_1f1b's schedule upgrade.
    Absolute CPU times are meaningless; only the ratios are reported.
    1F1B runs M=8 microbatches (its bounded stash is what makes large M
    affordable — the schedule's whole point); GPipe keeps its M=4 row
    for continuity with earlier rounds."""
    import json as _json
    import subprocess
    import sys

    code = r"""
import os, time, json
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.parallel import spmd
from llm_sharding_demo_tpu.training import train

cfg = gpt2.GPT2Config(vocab_size=2048, n_positions=256, n_embd=256,
                      n_layer=8, n_head=8)
params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
ids = jnp.asarray(np.random.default_rng(0).integers(
    0, cfg.vocab_size, size=(8, 129)), jnp.int32)

def time_steps(step, p, opt, batch, n=3):
    p, opt, loss = step(p, opt, batch); jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(n):
        p, opt, loss = step(p, opt, batch)
    jax.block_until_ready(loss)
    return (time.perf_counter() - t0) / n

dp_mesh = spmd.make_mesh({"dp": 8}, jax.devices())
dp = train.TrainStep(cfg, train.adamw(1e-3), mesh=dp_mesh)
pdp, odp = dp.init(params)
t_dp = time_steps(dp, pdp, odp, dp.shard_batch(ids))

gp_mesh = spmd.make_mesh({"dp": 2, "pp": 4}, jax.devices())
gp = train.GPipeTrainStep(cfg, train.adamw(1e-3), gp_mesh, n_microbatches=4)
pgp, ogp = gp.init(params)
t_gp = time_steps(gp, pgp, ogp, gp.shard_batch(ids))

fb = train.GPipeTrainStep(cfg, train.adamw(1e-3), gp_mesh, n_microbatches=8,
                          schedule="1f1b")
pfb, ofb = fb.init(params)
t_fb = time_steps(fb, pfb, ofb, fb.shard_batch(ids))

iv = train.GPipeTrainStep(cfg, train.adamw(1e-3), gp_mesh, n_microbatches=8,
                          schedule="1f1b", virtual_stages=2)
piv, oiv = iv.init(params)
t_iv = time_steps(iv, piv, oiv, iv.shard_batch(ids))
print(json.dumps({"dp8_step_s": round(t_dp, 4),
                  "pp4dp2_step_s": round(t_gp, 4),
                  "gpipe_vs_dp": round(t_gp / t_dp, 2),
                  "pp4dp2_1f1b_step_s": round(t_fb, 4),
                  "1f1b_vs_dp": round(t_fb / t_dp, 2),
                  "1f1b_vs_gpipe": round(t_fb / t_gp, 2),
                  "pp4dp2_1f1b_v2_step_s": round(t_iv, 4),
                  "1f1b_interleaved_v2_vs_dp": round(t_iv / t_dp, 2)}))
"""
    import os
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1200,
                         cwd=os.path.dirname(os.path.abspath(__file__)))
    if out.returncode != 0:
        return {"error": out.stderr.strip()[-300:]}
    return _json.loads(out.stdout.strip().splitlines()[-1])


_HEADLINE_METRIC = "greedy_decode_throughput_gpt2_124m"
_QUICK_METRIC = "greedy_decode_throughput_tiny"


def require_tpu():
    """The bench measures a TPU and nothing else: without one it exits
    non-zero and prints no result line. One process holds the chip."""
    import sys

    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform {device.platform!r}); "
                 "there is no CPU fallback")
    return device


def main() -> None:
    import os
    import sys

    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true",
                        help="cfg1 only (tiny model) for a fast smoke run")
    args = parser.parse_args()

    device = require_tpu()
    import jax

    from llm_sharding_demo_tpu.utils import compile_cache
    print(f"bench: {device.device_kind} x {len(jax.devices())}; compile "
          f"cache at {compile_cache.configure()}", file=sys.stderr)

    from llm_sharding_demo_tpu.models import gpt2

    tiny, g124, gmed = (gpt2.CONFIGS[k]
                        for k in ("tiny-gpt2", "gpt2", "gpt2-medium"))
    configs = []
    try:
        rtt_ms = measure_dispatch_rtt()
    except Exception as e:  # noqa: BLE001 — a dead rtt probe must not
        rtt_ms = None       # void the artifact; rtt-dependent rows error
        row = {"name": "dispatch_rtt",          # individually via safe()
               "error": f"{type(e).__name__}: {e}"}
        configs.append(row)

    # cfg1: tiny-gpt2, 2-shard, 20 tokens — the notebook workload, timed
    # e2e as mandated. With ~2 round trips of rtt_ms in a sub-second
    # workload, this row is bound by them by construction; the
    # steady-state row shows what the chip itself does.
    def cfg1():
        ref_tiny = measure_reference_cpu(tiny, 4, 20)
        pipe_tiny = measure_pipeline(tiny, 2, 4, two_point=False,
                                     new_tokens=20)
        fused = measure_single_program_e2e(tiny, 4, 20)
        if rtt_ms is None:  # rtt probe died: keep the real measurements,
            return {        # just drop the rtt-derived context fields
                "tokens_per_sec": round(pipe_tiny["tokens_per_sec"], 2),
                "single_program_tokens_per_sec": round(
                    fused["tokens_per_sec"], 1),
                "ref_cpu_tokens_per_sec": round(ref_tiny, 2),
                "vs_baseline": round(
                    pipe_tiny["tokens_per_sec"] / ref_tiny, 2),
                "single_program_vs_baseline": round(
                    fused["tokens_per_sec"] / ref_tiny, 2),
                "transfer_rtt_ms": None,
                "note": "rtt probe failed; see dispatch_rtt error row",
            }
        rtt_bound = 20 / (rtt_ms / 1e3)
        return {
            "tokens_per_sec": round(pipe_tiny["tokens_per_sec"], 2),
            "single_program_tokens_per_sec": round(
                fused["tokens_per_sec"], 1),
            "ref_cpu_tokens_per_sec": round(ref_tiny, 2),
            "vs_baseline": round(pipe_tiny["tokens_per_sec"] / ref_tiny, 2),
            "single_program_vs_baseline": round(
                fused["tokens_per_sec"] / ref_tiny, 2),
            "transfer_rtt_ms": round(rtt_ms, 1),
            "rtt_bound_tokens_per_sec": round(rtt_bound, 1),
            "note": "2-stage single-program pipeline, "
                    + pipe_tiny["placement"]
                    + "; single_program_* = the whole 20-token workload as "
                      "ONE compiled program closed by ONE fetch (prefill + "
                      "scanned decode), bounded by one round trip: "
                      f"20 tok / {rtt_ms:.0f} ms = {rtt_bound:.0f} tok/s. "
                      "See cfg2 for steady-state chip rates",
        }

    # Each config runs isolated: one failing measurement must not cost the
    # round its whole BENCH artifact — the failed row records the error
    # and the rest of the matrix still reports.
    def safe(name: str, fn) -> None:
        import traceback

        from llm_sharding_demo_tpu.utils.metrics import REGISTRY
        before = REGISTRY.snapshot()
        try:
            row = {"name": name, **fn()}
        except Exception as e:  # noqa: BLE001 — report, don't die
            row = {"name": name, "error": f"{type(e).__name__}: {e}",
                   "traceback_tail":
                       traceback.format_exc().strip()[-600:]}
        delta = _metrics_delta(before, REGISTRY.snapshot())
        if delta:
            row["metrics_delta"] = delta
        configs.append(row)

    def cfg_graftcheck():
        """Static-analysis journal row (ISSUE 3): the graftcheck --json
        payload rides the perf matrix, so contract drift (new lint
        findings, changed recompile bounds, stale baseline entries)
        lands in the same trajectory as the timings. Cheap (a few
        seconds of AST walking + abstract eval, no device work)
        and journaled FIRST — before any chip-bound row — so a
        timeout-cut run still records it."""
        import sys as _sys
        here = os.path.dirname(os.path.abspath(__file__))
        added = here not in _sys.path
        if added:
            _sys.path.insert(0, here)
        try:
            from tools.graftcheck import cli as _gc
            payload = _gc.run(root=here)
        finally:
            if added:
                try:
                    _sys.path.remove(here)
                except ValueError:
                    pass
        return {
            "ok": payload["ok"],
            "active_findings": len(payload["findings"]),
            # full finding rows only when something is wrong — the OK
            # case stays one compact journal line
            **({"findings": payload["findings"]}
               if payload["findings"] else {}),
            "suppressed": payload["suppressed"],
            "stale_baseline": payload["stale_baseline"],
            "semantic_checks": payload["semantic_checks"],
            "sanitize_checks": payload["sanitize_checks"],
            "locks_checks": payload["locks_checks"],
            "locks_vacuous": payload["locks_vacuous"],
            "slo_checks": payload["slo_checks"],
            "slo_vacuous": payload["slo_vacuous"],
            "numerics_checks": payload["numerics_checks"],
            "numerics_vacuous": payload["numerics_vacuous"],
            "memory_checks": payload["memory_checks"],
            "memory_ledgers": payload["memory_ledgers"],
            "memory_vacuous": payload["memory_vacuous"],
            "recompile_bounds": payload["recompile_bounds"],
        }

    def cfg_graftplan():
        """Chosen-plan journal row (ISSUE 6): the auto-sharding
        planner's pick for the bench model on this host's devices rides
        the perf matrix next to graftcheck_static_analysis, so a cost-
        model change that flips the chosen serving config shows up in
        the same trajectory as the timings it would cause. Compile-free
        (abstract eval only), no device work."""
        import sys as _sys
        here = os.path.dirname(os.path.abspath(__file__))
        added = here not in _sys.path
        if added:
            _sys.path.insert(0, here)
        try:
            import jax as _jax

            from tools.graftcheck import costmodel as _cm, registry as _reg
            module, config = _reg.planner_families()["gpt2-tiny"]
            payload = _cm.plan_for_serving(
                config, len(_jax.devices()), max_seq=64,
                traffic=_cm.parse_traffic("16/32x8"), max_batch_cap=8,
                kv_pool_blocks=32)
        finally:
            if added:
                try:
                    _sys.path.remove(here)
                except ValueError:
                    pass
        chosen = payload["chosen"]
        return {
            "devices": len(_jax.devices()),
            "traffic": "16/32x8",
            "chosen": chosen,
            "candidates": len(payload["plan"]),
            "rejected": payload["rejected"],
        }

    def cfg_ici_calibration():
        """ICI_BYTE_WEIGHT calibration row (ROADMAP item 5 follow-on):
        measured-vs-modeled comm bytes for the pp=2 ppdecode ring. The
        cost model walks collective bytes off the traced decode step
        (tools/graftcheck/costmodel.py, ICI_BYTE_WEIGHT = relative cost
        of an ICI byte vs an HBM byte); this row compiles THE SAME step
        on the real 2-device pp mesh and journals what the executable's
        own cost analysis reports for the transfer, so a drift between
        the model's byte formula and what XLA actually schedules lands
        in the perf trajectory. Runs only on a host with >= 2 chips.
        """
        import jax as _jax

        from tools.graftcheck import costmodel as _cm

        from llm_sharding_demo_tpu.models import gpt2 as _g
        from llm_sharding_demo_tpu.parallel.spmd import make_mesh
        modeled = _cm.pp_decode_comm_bytes(2, batch=1, module=_g,
                                           config=tiny)
        mesh = make_mesh({"pp": 2}, _jax.devices()[:2])
        fn, args = _cm.pp_decode_step_program(2, batch=1, module=_g,
                                              config=tiny, mesh=mesh)
        compiled = _jax.jit(fn).lower(*args).compile()
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        measured = None
        measured_key = None
        for key, val in sorted((analysis or {}).items()):
            if "network" in key.lower():
                measured = (measured or 0.0) + float(val)
                measured_key = key if measured_key is None \
                    else f"{measured_key}+{key}"
        hlo_permutes = compiled.as_text().count("collective-permute")
        row = {
            "modeled_comm_bytes_per_token": modeled,
            "measured_comm_bytes_per_token": measured,
            "measured_source": measured_key or "cost_analysis had no "
                                               "network counters",
            "hlo_collective_permutes": hlo_permutes,
            "ici_byte_weight": _cm.ICI_BYTE_WEIGHT,
            "note": "pp=2 ppdecode ring decode step; ratio calibrates "
                    "the planner's ICI byte weight against the "
                    "compiled executable",
        }
        if measured and modeled:
            row["measured_over_modeled"] = round(measured / modeled, 3)
        return row

    def cfg_graftscope_attribution():
        """Measured-vs-modeled attribution row (ISSUE 9): replay the
        canonical workloads on tiny real engines with device-true
        dispatch timing (graftscope sync mode) and join the observed
        program rings against the recompile certifier's key sets —
        exact rows must join 1:1 — plus the implied byte rate against
        the cost model's per-token prediction. Compile-cheap, CPU-safe,
        no device dependency; the drift trajectory rides the journal."""
        import sys as _sys
        here = os.path.dirname(os.path.abspath(__file__))
        added = here not in _sys.path
        if added:
            _sys.path.insert(0, here)
        try:
            from tools.graftcheck import scope as _scope
            payload = _scope.run_attribution()
        finally:
            if added:
                try:
                    _sys.path.remove(here)
                except ValueError:
                    pass
        return {
            "ok": payload["ok"],
            "workloads": [
                {k: v for k, v in row.items() if k != "entry_points"}
                for row in payload["workloads"]],
            "note": payload["note"],
        }

    def cfg_numerics_oracle():
        """graftnum tolerance-oracle row (ISSUE 15): every declared
        TOLERANCE_POLICY path (int8 weight-only, bf16 decode, quantized
        KV blocks) measured against the f32 parity engine on the PINNED
        seed — per-path logit MSE (lower-better) and greedy top-1
        agreement (higher-better), gated by tools/bench_diff.py so a
        quantizer or mixed-precision regression lands in the trajectory
        as a numerics drift, not a mystery token flip. Seeded and
        replay-identical (tests/test_graftnum.py pins byte-identical
        reports across fresh runs); CPU-safe —
        the oracle RAISES on a declared-budget breach, so this row
        erroring is itself the signal."""
        from llm_sharding_demo_tpu.utils import graftnum

        rows = graftnum.oracle_rows(seed=0)
        flat = {"seed": 0, "paths": len(rows)}
        for r in rows:
            # flatten per-path metrics so bench_diff gates them:
            # decode_int8_logit_mse / decode_int8_top1_agreement / ...
            # — the FULL path keys the row, so two policy paths sharing
            # a suffix (decode.int8 vs kv.int8) can never silently
            # shadow each other's gated metrics
            tag = r["path"].replace(".", "_")
            if "skipped" in r:
                # backend-prerequisite skip (fp8 storage on an old
                # chip): journal WHY, so the gated set shrinking is a
                # recorded fact, never a silent hole in the trajectory
                flat[f"{tag}_skipped"] = r["skipped"]
                continue
            flat[f"{tag}_logit_mse"] = r["logit_mse"]
            flat[f"{tag}_top1_agreement"] = r["top1_agreement"]
            flat[f"{tag}_positions"] = r["n_positions"]
        return flat

    safe("graftcheck_static_analysis", cfg_graftcheck)
    safe("graftcheck_chosen_plan", cfg_graftplan)
    safe("numerics_oracle", cfg_numerics_oracle)
    safe("graftscope_attribution", cfg_graftscope_attribution)
    if len(jax.devices()) >= 2:  # a real pp=2 ring needs two chips
        safe("ici_byte_weight_calibration", cfg_ici_calibration)
    safe("cfg1_tiny_gpt2_2shard_20tok", cfg1)

    if args.quick:
        row = next((c for c in configs
                    if c["name"] == "cfg1_tiny_gpt2_2shard_20tok"), {})
        emit({
            "metric": _QUICK_METRIC,
            "value": row.get("tokens_per_sec"),
            "unit": "tokens/sec",
            "vs_baseline": row.get("vs_baseline"),
            "configs": configs,
        }, write_file=False)
        return

    # Shared 124M baseline: the reference O(n^2) loop, 20 tokens. Guarded
    # like the config rows: if the CPU denominator itself fails, TPU rows
    # still report absolute rates with vs_baseline = null.
    try:
        ref_124 = measure_reference_cpu(g124, PROMPT_LEN, 20)
    except Exception as e:  # noqa: BLE001
        configs.append({"name": "ref_cpu_gpt2_124m",
                        "error": f"{type(e).__name__}: {e}"})
        ref_124 = None

    def vs_ref(x):
        return None if ref_124 is None else round(x / ref_124, 2)

    def ref_cpu():
        return None if ref_124 is None else round(ref_124, 2)

    def cfg2():
        # 124M single stream — 2-shard pipeline AND the fused single-chip
        # engine (fp32 parity mode + bf16 fast path).
        pipe_124 = measure_pipeline(g124, 2, PROMPT_LEN, 1, "bfloat16")
        eng_f32 = measure_engine(g124, PROMPT_LEN, 1, "float32")
        eng_bf16 = measure_engine(g124, PROMPT_LEN, 1, "bfloat16")
        eng_int8 = measure_engine(g124, PROMPT_LEN, 1, "int8")
        return {
            "tokens_per_sec": round(pipe_124["tokens_per_sec"], 2),
            "engine_fp32_tokens_per_sec": round(eng_f32["tokens_per_sec"], 2),
            "engine_bf16_tokens_per_sec": round(eng_bf16["tokens_per_sec"], 2),
            "engine_int8_tokens_per_sec": round(eng_int8["tokens_per_sec"], 2),
            "p50_token_latency_ms": round(eng_bf16["p50_token_latency_ms"], 3),
            "e2e_tokens_per_sec": round(eng_bf16["e2e_tokens_per_sec"], 2),
            "ref_cpu_tokens_per_sec": ref_cpu(),
            "vs_baseline": vs_ref(pipe_124["tokens_per_sec"]),
            "engine_bf16_vs_baseline": vs_ref(eng_bf16["tokens_per_sec"]),
            "engine_int8_vs_baseline": vs_ref(eng_int8["tokens_per_sec"]),
            "note": "steady-state (marginal) decode rates; 2-stage bf16 "
                    "pipeline, " + pipe_124["placement"]
                    + "; engine rows are the unstaged single-chip path "
                      "(fp32 = parity mode, bf16 = fast, int8 = weight-only "
                      "quantized fast path)",
        }

    def cfg3():
        # 124M batch=8. Reference baseline: 8 sequential bs=1 streams ==
        # the same tokens/sec (server.py:137 hardcodes batch 1).
        b8_f32 = measure_engine(g124, PROMPT_LEN, 8, "float32")
        b8_bf16 = measure_engine(g124, PROMPT_LEN, 8, "bfloat16")
        return {
            "tokens_per_sec": round(b8_bf16["tokens_per_sec"], 2),
            "engine_fp32_tokens_per_sec": round(b8_f32["tokens_per_sec"], 2),
            "ref_cpu_tokens_per_sec": ref_cpu(),
            "vs_baseline": vs_ref(b8_bf16["tokens_per_sec"]),
            "note": "aggregate steady-state tokens/sec over 8 rows; "
                    "reference can only run them sequentially at its bs=1 "
                    "rate",
        }

    def cfg4():
        ref_med = measure_reference_cpu(gmed, PROMPT_LEN, 10)
        pipe_med = measure_pipeline(gmed, 4, PROMPT_LEN, 1, "bfloat16")
        return {
            "tokens_per_sec": round(pipe_med["tokens_per_sec"], 2),
            "ref_cpu_tokens_per_sec": round(ref_med, 2),
            "vs_baseline": round(pipe_med["tokens_per_sec"] / ref_med, 2),
            "placement": pipe_med["placement"],
            "note": "steady-state bf16 4-stage pipeline; baseline is the "
                    "reference algorithm on gpt2-medium",
        }

    def cfg5():
        # KV cache vs O(n^2) — both on this framework, same chip. Long
        # window (most of the position table): at short sequences a fast
        # chip hides the O(n^2) compute behind weight streaming.
        long_steps = g124.n_positions - PROMPT_LEN - 16
        uncached = measure_uncached_jax(g124, PROMPT_LEN, long_steps)
        cached_long = measure_engine(g124, PROMPT_LEN, 1, "bfloat16",
                                     s_b=long_steps)
        return {
            "tokens_per_sec": round(cached_long["tokens_per_sec"], 2),
            "uncached_jax_tokens_per_sec":
                None if uncached is None else round(uncached, 2),
            "cache_speedup":
                None if uncached is None else round(
                    cached_long["tokens_per_sec"] / uncached, 2),
            "ref_cpu_tokens_per_sec": ref_cpu(),
            "vs_baseline": vs_ref(cached_long["tokens_per_sec"]),
            "note": "uncached = full fixed-length re-forward per token "
                    "on-chip (the reference's algorithm, server.py:169-181)"
                    f", bf16, marginal over tokens [{STEPS_A}, {long_steps})"
                    " for BOTH cached and uncached",
        }

    def cfg6():
        # MoE decode — second model family; the reference is dense-only
        # (SURVEY.md §2.2 "EP: not applicable"), anchor is the dense loop.
        moe_bf16 = measure_moe(PROMPT_LEN, 1, "bfloat16")
        moe_int8 = measure_moe(PROMPT_LEN, 1, "int8")
        return {
            "tokens_per_sec": round(moe_bf16["tokens_per_sec"], 2),
            "int8_tokens_per_sec": round(moe_int8["tokens_per_sec"], 2),
            "p50_token_latency_ms": round(moe_bf16["p50_token_latency_ms"], 3),
            "ref_cpu_tokens_per_sec": ref_cpu(),
            "vs_baseline": vs_ref(moe_bf16["tokens_per_sec"]),
            "note": "GPT-2 124M geometry, dense MLP -> 8 experts top-2 "
                    "(~7x MLP weights); steady-state bf16 cached decode, "
                    "plus the weight-only int8 row; reference has no MoE — "
                    "anchor is the dense 124M CPU loop",
        }

    def cfg8():
        sd = measure_spec_decode(g124, PROMPT_LEN, "bfloat16")
        row = {
            "tokens_per_sec": round(sd["spec_tokens_per_sec"], 2),
            "plain_tokens_per_sec": round(sd["plain_tokens_per_sec"], 2),
            "speedup_vs_plain": sd["speedup"],
            "accepted_tokens_per_verify": sd["accepted_tokens_per_verify"],
            "draft_len": sd["draft_len"],
            "ref_cpu_tokens_per_sec": ref_cpu(),
            "vs_baseline": vs_ref(sd["spec_tokens_per_sec"]),
            "note": "prompt-lookup speculation (runtime.spec_decode), bf16, "
                    "greedy token-exact; acceptance column shows how "
                    "repetitive this workload's greedy continuation was",
        }
        if sd.get("degraded_timing"):
            row["degraded_timing"] = True
        return row

    def cfg9():
        # llama family — RoPE + GQA (kv=4: 3x smaller KV cache) + SwiGLU.
        # The long-context column decodes at ~3k depth, past GPT-2's
        # 1024-learned-position ceiling (server.py:57).
        from llm_sharding_demo_tpu.models import llama as llama_mod
        lcfg = llama_mod.CONFIGS["llama-124m"]
        ll_bf16 = measure_engine(lcfg, PROMPT_LEN, 1, "bfloat16")
        ll_int8 = measure_engine(lcfg, PROMPT_LEN, 1, "int8")
        ll_long = measure_engine(lcfg, 3072, 1, "bfloat16")
        return {
            "tokens_per_sec": round(ll_bf16["tokens_per_sec"], 2),
            "int8_tokens_per_sec": round(ll_int8["tokens_per_sec"], 2),
            "long_context_tokens_per_sec": round(ll_long["tokens_per_sec"], 2),
            "long_context_prefill_ms": round(ll_long["prefill_ms"], 1),
            "p50_token_latency_ms": round(ll_bf16["p50_token_latency_ms"], 3),
            "ref_cpu_tokens_per_sec": ref_cpu(),
            "vs_baseline": vs_ref(ll_bf16["tokens_per_sec"]),
            "note": "llama family (RMSNorm/RoPE/SwiGLU/GQA kv=4), bf16 + "
                    "weight-only int8 steady-state decode; long-context "
                    "column = 3072-token prompt, decode at ~3-3.5k depth — "
                    "beyond the reference's 1024-position ceiling; anchor "
                    "is the dense 124M CPU loop",
        }

    def cfg7():
        return {
            "rows": measure_flash_attention(),
            "note": "Pallas K-blocked online-softmax kernel vs XLA einsum "
                    "attention, GPT-2 head geometry, bf16; fwd and fwd+bwd; "
                    "auto_dispatch = what attention_impl='pallas' actually "
                    "runs (measured-crossover dispatch, never < 1.0x XLA)",
        }

    def cfg10():
        tr = measure_training(g124)
        gp = measure_gpipe_overhead()
        return {
            **{k: v for k, v in tr.items()},
            "gpipe_cpu_mesh": gp,
            "note": "single-chip jitted train step (fwd+bwd+AdamW, remat), "
                    "GPT-2 124M bf16; MFU = 6N-per-token model FLOPs vs "
                    "the emitted peak_flops (device-kind bf16 peak; "
                    "omitted when unknown); gpipe_cpu_mesh = pp4xdp2 "
                    "pipeline schedules (GPipe M=4, 1F1B M=8) vs pure dp8 "
                    "step-time ratios on the 8-device virtual CPU mesh "
                    "(schedule overhead; CPU absolute times are not chip "
                    "numbers)",
        }

    def cfg11():
        return {
            **measure_iterbatch(g124),
            "note": "staggered arrivals (requests land mid-decode), GPT-2 "
                    "124M bf16, aggregate tokens/sec from first submit to "
                    "last completion incl. all host syncs; admission = "
                    "runtime.batcher rounds, iter = runtime.iterbatch "
                    "segment-boundary join/retire",
        }

    def cfg13():
        return {
            **measure_spec_iterbatch(g124),
            "note": "speculation x continuous batching (the previously "
                    "mutually-exclusive pair): staggered arrivals on a "
                    "REPETITIVE workload, GPT-2 124M bf16, aggregate "
                    "tokens/sec; spec_iter = draft-verify segments with "
                    "per-row acceptance (runtime.spec_decode._seg_b under "
                    "runtime.iterbatch), iter = plain single-token "
                    "segments on the same scheduler and weights; "
                    "acceptance column contextualizes the speedup (cfg8 "
                    "is the solo analog)",
        }

    def cfg14():
        return {
            **measure_paged_kv(g124),
            "note": "paged KV pool (runtime.kv_pool): solo decode "
                    "through PagedKVRunner (engine programs + one "
                    "gather/scatter per segment) vs the contiguous "
                    "engine = the paging tax; rows-before-preemption "
                    "on a 2-full-rows pool shows the concurrency "
                    "block granularity buys (contiguous arenas cap at "
                    "2 rows for the same bytes); skip-with-reason off "
                    "the bench chip",
        }

    def cfg_kv_quant_capacity():
        return {
            **measure_kv_quant_capacity(g124),
            "note": "quantized KV blocks (runtime.kv_pool block_dtype="
                    "'int8' + ops.kv_quant): rows admitted before the "
                    "first preemption and prefix-store depth, int8 vs "
                    "f32 pools at EQUAL HBM bytes (scales included) — "
                    "the effective-capacity half of the trade; the "
                    "accuracy half is the numerics_oracle row's "
                    "kv_int8_* metrics; skip-with-reason off the bench "
                    "chip",
        }

    def cfg_concurrent_load():
        return {
            **measure_concurrent_load(g124),
            "note": "width >= 4 concurrent clients through the pooled "
                    "iteration scheduler with graftsched-instrumented "
                    "locks (GRAFTSCHED=trace): p50/p99 request latency "
                    "+ per-lock wait totals — a scheduler serializing "
                    "on a blocked lock lands here before it lands in "
                    "the throughput rows; skip-with-reason off the "
                    "bench chip",
        }

    safe("cfg2_gpt2_124m_2shard_single_prompt", cfg2)
    safe("cfg3_gpt2_124m_bs8", cfg3)
    safe("cfg11_iterbatch_staggered_arrivals", cfg11)
    def cfg_fault_recovery():
        return {
            **measure_fault_recovery(g124),
            "note": "width 6 concurrent clients under a pinned 10% "
                    "transient-decode-fault seed (graftfault): p50/p99 "
                    "latency, success rate, and park/resume counts — "
                    "the price of byte-identical fault recovery rides "
                    "the gated trajectory; skip-with-reason off the "
                    "bench chip",
        }

    # graftload (ISSUE 11): ONE shared open-loop load run feeds both
    # journal rows — the Pareto sweep and the per-profile SLO
    # attainment — so the two can never disagree about what was driven
    _graftload_memo = {}

    def _graftload_result():
        if not _graftload_memo:
            try:
                _graftload_memo["result"] = measure_graftload()
            except Exception as e:  # noqa: BLE001 — both rows report it
                _graftload_memo["error"] = e
        if "error" in _graftload_memo:
            raise _graftload_memo["error"]
        return _graftload_memo["result"]

    def cfg_graftload_pareto():
        r = _graftload_result()
        return {
            "seed": r["seed"],
            "requests_per_run": r["requests_per_run"],
            "workloads": r["pareto"],
            "occupancy": r["occupancy"],
            "note": "seeded open-loop arrivals (replay-identical per "
                    "(seed, profile, k)) against the pooled iterbatch "
                    "app; one Pareto point per (profile, rate_scale) — "
                    "throughput/goodput gated higher-better, tails "
                    "lower-better by bench_diff",
        }

    def cfg_slo_attainment():
        r = _graftload_result()
        return {
            "seed": r["seed"],
            "workloads": r["slo_rows"],
            "note": "declared SLO_POLICY attainment per profile at the "
                    "base arrival rate: observed percentile vs target "
                    "per metric, goodput-under-SLO with typed 429/503 "
                    "sheds counted separately from SLO misses",
        }

    def cfg_traffic_mix():
        """The measured traffic-mix signal (ISSUE 12 satellite): one
        row per (profile, rate_scale) joining offered demand, goodput
        under the declared SLOs, and the occupancy the mix induced —
        the tuple AUTO_PLAN's continuous mode watches to decide the
        measured optimum flipped (ROADMAP item-5/6 follow-on)."""
        r = _graftload_result()
        return {
            "seed": r["seed"],
            "workloads": r["traffic_mix"],
            "note": "per-(profile, rate) demand/goodput/occupancy join "
                    "from the shared graftload run; goodput and "
                    "throughput gated higher-better, queue depth "
                    "lower-better by bench_diff",
        }

    def cfg_fleet_scaling():
        """graftfleet replica scaling (ISSUE 12): bursty_chat through
        the shared-pool fleet at 1 vs 2 decode replicas — throughput/
        goodput per replica count, router affinity hit rate, typed-shed
        split; skip-with-reason off the bench chip."""
        return measure_fleet_scaling()

    def cfg_plan_switch():
        """graftwatch live re-planning (ISSUE 13): seeded mix flip
        against the AUTO_PLAN_CONTINUOUS app — switch count, goodput/
        throughput before vs after the switch, and recompiles beyond
        the pre-certified plan set (the pinned ZERO, gated lower-better
        so a certified-envelope leak fails the trajectory); skip-with-
        reason off the bench chip."""
        return measure_plan_switch()

    def cfg_tiered_kv_depth():
        return {
            **measure_tiered_kv_depth(),
            "note": "grafttier host-RAM spill (runtime.kv_tier): a "
                    "bursty_chat-derived prefix population (loadgen "
                    "prefix_depth knob) through a small device pool + "
                    "host tier, replayed over the same seeded schedule "
                    "— ledger-measured prefix-store depth vs device "
                    "pool bytes (the >= 10x claim) plus warm-epoch "
                    "prefix/promoted hit rates and goodput (higher-"
                    "better) and promote stall (lower-better); runs on "
                    "any backend (byte accounting, not chip rates)",
        }

    safe("cfg14_paged_kv_vs_contiguous", cfg14)
    safe("kv_quant_capacity", cfg_kv_quant_capacity)
    safe("tiered_kv_depth", cfg_tiered_kv_depth)
    safe("concurrent_load", cfg_concurrent_load)
    safe("fault_recovery", cfg_fault_recovery)
    safe("graftload_pareto", cfg_graftload_pareto)
    safe("slo_attainment", cfg_slo_attainment)
    safe("traffic_mix", cfg_traffic_mix)
    safe("fleet_scaling", cfg_fleet_scaling)
    safe("plan_switch", cfg_plan_switch)
    safe("cfg4_gpt2_medium_4shard", cfg4)
    safe("cfg5_kv_cache_vs_on2", cfg5)
    safe("cfg6_moe_8e_top2_124m_geometry", cfg6)
    safe("cfg8_speculative_decode_124m", cfg8)
    safe("cfg13_spec_iterbatch_staggered", cfg13)
    safe("cfg9_llama_124m_gqa", cfg9)
    safe("cfg7_flash_attention_vs_xla", cfg7)
    safe("cfg10_training_gpt2_124m", cfg10)

    def cfg_timeline_overhead():
        """grafttime event-bus cost row (ISSUE 14): emit throughput
        into the bounded ring (events/sec) plus the bus-armed vs
        bus-off wall ratio on a tiny decode workload — min-of-3 each
        side, mirroring graftscope's pinned OVERHEAD_FACTOR pattern
        (tests/test_grafttime.py pins the bound; this row journals the
        trajectory bench_diff gates: events_per_sec higher-better,
        overhead_factor lower-better). CPU-safe."""
        import time as _time

        from llm_sharding_demo_tpu.fleet.harness import demo_model
        from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
        from llm_sharding_demo_tpu.utils import grafttime

        n = 20_000
        # force the bus ON for the throughput half: with GRAFTTIME=0 in
        # the environment the emits would time the disabled early
        # return and journal an inflated (and later "regressing")
        # events_per_sec
        prev = grafttime.set_enabled(True)
        try:
            t0 = _time.perf_counter()
            for i in range(n):
                grafttime.emit("occupancy", name="queue_depth",
                               value=float(i & 7))
            eps = n / (_time.perf_counter() - t0)
        finally:
            grafttime.set_enabled(prev)

        cfg_model, params = demo_model(64)
        eng = DecodeEngine(params, cfg_model, max_seq=64)
        prompt = np.full((1, 8), 5, dtype=np.int32)
        eng.generate(prompt, 16)          # warm-up: compiles

        def best_of(k: int) -> float:
            best = float("inf")
            for _ in range(k):
                t = _time.perf_counter()
                eng.generate(prompt, 16)
                best = min(best, _time.perf_counter() - t)
            return best

        prev = grafttime.set_enabled(False)
        try:
            off = best_of(3)
        finally:
            grafttime.set_enabled(prev)
        grafttime.set_enabled(True)
        try:
            on = best_of(3)
        finally:
            grafttime.set_enabled(prev)
        return {
            "events_per_sec": round(eps, 1),
            "overhead_factor": round(on / off, 4),
            "overhead_bound": grafttime.OVERHEAD_FACTOR,
            "ring_capacity": grafttime.BUS.capacity,
            "within_bound": bool(on <= off * grafttime.OVERHEAD_FACTOR),
        }

    safe("timeline_overhead", cfg_timeline_overhead)

    def cfg_hbm_attribution():
        """graftmem measured-vs-modeled byte row (ISSUE 17): the live
        ledger's per-component bytes against the cost model's aval
        arithmetic for the SAME objects — a solo f32 engine's params
        (tree_bytes over param_avals), an f32 paged pool and an int8
        paged pool (kv_pool_bytes, the allocator's own geometry math) —
        plus the ledger peak during a pooled iterbatch run. The *_drift
        fields are |measured/predicted - 1| and gate lower-better in
        bench_diff: f32 drifts are exactly 0.0 by construction (the
        tests/test_graftmem.py exactness pins, journaled), and the int8
        pool's drift below the f32-aval prediction is the quantizer's
        designed savings — CONSTANT for fixed geometry, so any movement
        means the ledger or the model changed. CPU-safe."""
        import sys as _sys

        import jax

        from llm_sharding_demo_tpu.fleet.harness import demo_model
        from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
        from llm_sharding_demo_tpu.runtime.iterbatch import IterBatchingEngine
        from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
        from llm_sharding_demo_tpu.utils import graftmem

        if not graftmem.enabled():
            raise RuntimeError("GRAFTMEM=0 in the environment — the "
                               "ledger registers nothing to attribute")

        here = os.path.dirname(os.path.abspath(__file__))
        added = here not in _sys.path
        if added:
            _sys.path.insert(0, here)
        try:
            from tools.graftcheck import costmodel as _cm
        finally:
            if added:
                try:
                    _sys.path.remove(here)
                except ValueError:
                    pass
        from llm_sharding_demo_tpu.models import gpt2 as _gpt2

        cfg_model, params = demo_model(64)
        eng = DecodeEngine(params, cfg_model, max_seq=64, dtype="float32")
        f32_pool = KVBlockPool.for_engine(eng, num_blocks=16, block_size=16)
        q_pool = KVBlockPool.for_engine(eng, num_blocks=16, block_size=16,
                                        block_dtype="int8")

        # predictions from aval arithmetic only — no live buffer reads
        pred_params = _cm.tree_bytes(_cm.param_avals(_gpt2, cfg_model))
        pred_pool = _cm.kv_pool_bytes(cfg_model, 16, 16)

        def drift(measured: int, predicted: int) -> float:
            return round(abs(measured / predicted - 1.0), 6)

        m_params = graftmem.holding_bytes(eng, "params")
        m_f32 = (graftmem.holding_bytes(f32_pool, "data")
                 + graftmem.holding_bytes(f32_pool, "scales"))
        m_int8 = (graftmem.holding_bytes(q_pool, "data")
                  + graftmem.holding_bytes(q_pool, "scales"))

        # peak during a pooled iterbatch run: the working cache +
        # spec-free decode path registers/releases through the ledger
        ib = IterBatchingEngine(eng, max_batch=2, seg_steps=8,
                                max_wait_ms=10.0, pool=f32_pool)
        rng = np.random.default_rng(17)
        prompt = rng.integers(0, cfg_model.vocab_size, size=(12,))
        ib.generate(prompt, 8, timeout=120)
        snap = graftmem.snapshot()
        return {
            "params_measured_bytes": int(m_params),
            "params_predicted_bytes": int(pred_params),
            "params_drift": drift(m_params, pred_params),
            "pool_f32_measured_bytes": int(m_f32),
            "pool_f32_predicted_bytes": int(pred_pool),
            "pool_f32_drift": drift(m_f32, pred_pool),
            "pool_int8_measured_bytes": int(m_int8),
            # the int8 pool against the f32-aval prediction: the drift
            # IS the designed savings (codes narrow 4x, scales ride on
            # top) — constant for fixed geometry, gated lower-better
            "pool_int8_drift": drift(m_int8, pred_pool),
            "peak_bytes": int(snap["peak_bytes"]),
            "engine_cache_peak_bytes": int(
                snap["peaks"].get("engine_cache", {}).get("bytes", 0)),
            "ledger": {c: int(b)
                       for c, b in graftmem.component_bytes().items()},
            "conserved": bool(snap["conserved"]),
        }

    safe("hbm_attribution", cfg_hbm_attribution)

    def cfg_trend_detection():
        """grafttrend seeded detection row (ISSUE 19): the plan-switch
        traffic mix (serial -> open burst -> serial, agentic profile)
        against the AUTO_PLAN_CONTINUOUS app with a dedicated
        TrendReducer polling the live registry between phases —
        journals whether the seeded burst tripped a declared watch
        (burst_detected, gated higher-better: a reducer that stops
        seeing its pinned burst went blind) and the alerts fired
        during the QUIET serial phases (false_positives, gated
        lower-better: a watch that pages on healthy traffic is worse
        than no watch). Seed-pinned arrivals make both trajectories,
        not noise.

        Needs the bench chip: on CPU the decode dominates and the
        open burst saturates the host, so the quiet phases would trip
        latency watches on machine noise, not traffic shape.
        """
        import jax


        from llm_sharding_demo_tpu import loadgen
        from llm_sharding_demo_tpu.utils import grafttrend
        from tools.graftload import build_demo_app

        seed, n_requests = 7, 10
        prof = loadgen.profile("agentic")
        sched = loadgen.schedule(prof, seed, n_requests)
        classes = sorted({(len(a.prompt.encode("utf-8")), a.max_new)
                          for a in sched})
        traffic = ",".join(f"{p}/{n}" for p, n in classes)
        client, recorder, reg = build_demo_app(
            max_seq=256, max_batch=4,
            recorder_capacity=max(64, 8 * n_requests),
            continuous=True, auto_plan_traffic=traffic)
        red = grafttrend.TrendReducer(registry=reg, blackbox=False)

        def run_phase(mode, rate=1.0):
            rep = loadgen.run_load(client, prof, seed=seed,
                                   n=n_requests, mode=mode,
                                   rate_scale=rate, recorder=recorder,
                                   trend=red)
            return rep["trend"]["alerts_fired"]

        red.poll()                    # seed histogram/counter cursors
        quiet1 = run_phase("serial")          # quiet: stays solo
        burst_alerts = run_phase("open", rate=60.0)  # the seeded burst
        quiet2 = run_phase("serial")          # drain: quiet again
        false_pos = quiet1 + quiet2
        return {
            "seed": seed,
            "requests_per_run": n_requests,
            "watches_declared": len(grafttrend.WATCH_POLICY),
            "burst_detected": int(burst_alerts > 0),
            "burst_alerts": burst_alerts,
            "false_positives": false_pos,
            "tripped": sorted({a["watch"] for a in red.alerts()}),
        }

    safe("trend_detection", cfg_trend_detection)

    def cfg_bench_diff():
        """Perf-regression verdict (ISSUE 9, tools/bench_diff.py): THIS
        run's rows so far compared against the committed BENCH_r*.json
        trajectory with per-metric thresholds — a step-function
        regression lands in the journal as its own row instead of aging
        silently in the trajectory. Runs after every measurement row so
        the verdict covers the whole matrix."""
        import glob as _glob
        import sys as _sys
        here = os.path.dirname(os.path.abspath(__file__))
        tools = os.path.join(here, "tools")
        added = tools not in _sys.path
        if added:
            _sys.path.insert(0, tools)
        try:
            import bench_diff as _bd
        finally:
            if added:
                try:
                    _sys.path.remove(tools)
                except ValueError:
                    pass
        current = _bd.extract_metrics({"configs": configs})
        history = _bd.load_history(
            _glob.glob(os.path.join(here, "BENCH_r*.json")))
        verdict = _bd.compare(
            current, history,
            current_errors=_bd.error_configs({"configs": configs}),
            current_skips=_bd.skipped_configs({"configs": configs}))
        return {
            "ok": verdict["ok"],
            "compared": verdict["compared"],
            "regressions": verdict["regressions"],
            # skip-with-reason rows that contributed no gated metrics
            # this run — visible in the verdict instead of vanishing
            # (tools/bench_diff.py --no-skips turns these into a
            # nonzero exit for CI)
            "ungated_rows": verdict["ungated_rows"],
            # the --no-skips verdict as journaled DATA: false whenever
            # any row skipped — the blind spot is loud in the row
            # itself, not only behind the opt-in flag
            "no_skips_ok": verdict["no_skips_ok"],
            "history_runs": verdict["history_runs"],
            # full per-metric rows only when something regressed — the
            # OK case stays one compact journal line
            **({"rows": [r for r in verdict["rows"]
                         if r["status"] == "regression"]}
               if verdict["regressions"] else {}),
        }

    safe("bench_diff", cfg_bench_diff)

    by_name = {c["name"]: c for c in configs}
    head = by_name.get("cfg2_gpt2_124m_2shard_single_prompt", {})
    batched = by_name.get("cfg3_gpt2_124m_bs8", {})
    emit({
        "metric": _HEADLINE_METRIC,
        "value": head.get("engine_bf16_tokens_per_sec"),
        "unit": "tokens/sec",
        "vs_baseline": head.get("engine_bf16_vs_baseline"),
        "dtype": "bfloat16",
        "fp32_tokens_per_sec": head.get("engine_fp32_tokens_per_sec"),
        # THE serving metric (aggregate batched decode) alongside the
        # round-1-compatible single-stream headline
        "batched_bs8_tokens_per_sec": batched.get("tokens_per_sec"),
        "transfer_rtt_ms": None if rtt_ms is None else round(rtt_ms, 1),
        "configs": configs,
    })


if __name__ == "__main__":
    main()
