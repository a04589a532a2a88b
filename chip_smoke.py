#!/usr/bin/env python3
"""The quickest proof that the serving stack still starts on the chip.

``python chip_smoke.py`` (one TPU chip, one process): builds the app the
way ``python -m llm_sharding_demo_tpu.serving`` does (``from_env()`` ->
``create_app`` -> ``serve``) for GPT-2 124M at full width and depth —
bf16, iteration-level batching, paged KV pool, prefix store — answers
requests over HTTP, and checks what comes out by the repo's own means:
batched rows against their solo runs, the Pallas decode path against the
XLA path inside the ``decode.bf16`` budget of ``utils/graftnum.py``, pool
blocks back to zero, no compilations once warm.

``python chip_smoke.py --chips 4`` (run by hand on a four-chip host) runs
ONLY the path that exists across chips: GPT-2 medium split into four
stages (``PP_DECODE=1``, one ``shard_map`` + ``ppermute`` program) served
the same way, against the unstaged single-device engine on the same
weights.

Weights are the loader's seeded random init (no network, no checkpoint).
There is no CPU fallback: without a TPU the script exits non-zero and
prints no result line. Any failed check raises; the last line of a good
run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import logging
import math
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import requests

# what the one-chip phase serves: the stack ROADMAP.md describes, not the
# bare defaults. The pool holds eight full 1024-token rows (8 x 64
# blocks) plus room for the prefix store's entries.
SERVING_ENV = {
    "MODEL_ID": "gpt2",
    "MAX_SEQ": "1024",
    "INFERENCE_DTYPE": "bfloat16",
    "BATCH_MODE": "iter",
    "MAX_BATCH": "8",
    "KV_POOL_BLOCKS": "640",
    "KV_BLOCK_SIZE": "16",
    "PREFIX_CACHE": "8",
}

# the path across chips (BASELINE.json: "4-shard pipeline across 4 TPU
# chips, GPT-2 medium"); float32 is the mode tests/test_ppdecode.py pins
# token-exact against the unstaged engine
PIPELINE_ENV = {
    "MODEL_ID": "gpt2-medium",
    "MAX_SEQ": "1024",
    "BOUNDARIES": "6,12,18",
    "PP_DECODE": "1",
    "INFERENCE_DTYPE": "float32",
}

# DecodeEngine._decode_kernel values that are compiled Pallas kernels:
# the per-layer flash-decode kernel. None is the XLA einsum path;
# "interpret" is the same kernel under the Pallas interpreter.
COMPILED_DECODE_KERNELS = ("device",)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Every program XLA builds or loads in this process, from JAX's own
    monitoring events (eager-op programs included — stricter than the
    engine's per-jit-site CompileWatch)."""

    def __init__(self):
        import jax.monitoring as mon
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == _COMPILE_EVENT:
            self.programs += 1
            self.seconds += seconds

    def _on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self):
        return (self.programs, self.seconds, self.cache_hits)

    def since(self, mark):
        return (self.programs - mark[0], self.seconds - mark[1],
                self.cache_hits - mark[2])


def timed(counter, name, fn, repeats=2):
    """Run ``fn`` ``repeats`` times; print wall seconds of the first call
    (which compiles) and of the later calls, with the programs XLA built
    in each. Returns the last result."""
    rows = []
    for _ in range(repeats):
        mark, t0 = counter.mark(), time.perf_counter()
        out = fn()
        rows.append((time.perf_counter() - t0, *counter.since(mark)))
    first = rows[0]
    later = rows[1:] or [(float("nan"), 0, 0.0, 0)]
    print(f"phase {name}: first_call_s={first[0]:.3f} "
          f"(programs={first[1]} compile_s={first[2]:.3f} "
          f"cache_hits={first[3]}) "
          f"later_call_s={min(r[0] for r in later):.3f} "
          f"(programs={sum(r[1] for r in later)})", flush=True)
    return out


class TokenTap:
    """The wire answers with text, and the byte-level fallback tokenizer
    renders every id past 255 as U+FFFD — so token ids are read where the
    handler gets them: a recording wrapper around the app's runner, keyed
    by the X-Request-ID riding the handler thread's ambient trace."""

    def __init__(self, runner):
        from llm_sharding_demo_tpu.utils import tracing
        self._lock = threading.Lock()
        self._by_rid = {}
        inner = runner.generate

        def generate(prompt_ids, *args, **kwargs):
            result = inner(prompt_ids, *args, **kwargs)
            n_prompt = len(list(prompt_ids))
            with self._lock:
                self._by_rid[tracing.current_trace().request_id] = [
                    int(t) for t in result.row_tokens(0)[n_prompt:]]
            return result

        runner.generate = generate

    def pop(self, rid):
        with self._lock:
            return self._by_rid.pop(rid)


class Client:
    """``client.py``'s request shape over real sockets, plus the request
    id the tap is keyed by."""

    def __init__(self, url, tap):
        self.url = url
        self.tap = tap
        self._n = 0
        self._lock = threading.Lock()

    def generate(self, prompt, max_new_tokens, mode="greedy", seed=None):
        """POST /generate; returns the new token ids. Asserts a 200 with
        the requested token count."""
        with self._lock:
            self._n += 1
            rid = f"smoke-{self._n}"
        body = {"prompt": prompt, "max_new_tokens": max_new_tokens,
                "mode": mode}
        if seed is not None:
            body["seed"] = seed
        resp = requests.post(f"{self.url}/generate", json=body,
                             headers={"X-Request-ID": rid}, timeout=900)
        assert resp.status_code == 200, (resp.status_code, resp.text[:500])
        payload = resp.json()
        assert "generated" in payload, payload
        tokens = self.tap.pop(rid)
        assert len(tokens) == max_new_tokens, (
            f"{rid}: asked for {max_new_tokens} tokens, got {len(tokens)}")
        return tokens

    def get(self, path):
        resp = requests.get(f"{self.url}{path}", timeout=60)
        assert resp.status_code == 200, (path, resp.status_code,
                                         resp.text[:500])
        return resp


class _Warnings(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.text, self.at = [], []

    def emit(self, record):
        self.text.append(record.getMessage())
        self.at.append(record.created)


def start_server(env):
    """The path ``python -m llm_sharding_demo_tpu.serving`` takes, with
    the server on a daemon thread of this process. Asserts the offline
    resolution this script's checks are written for: the loader's seeded
    random init and the byte-level tokenizer (one token per byte)."""
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.http import serve
    from llm_sharding_demo_tpu.utils.config import from_env

    os.environ.update(env)
    warned = _Warnings()
    log = logging.getLogger("llm_sharding_demo_tpu.serving")
    log.addHandler(warned)
    t0, wall0 = time.perf_counter(), time.time()
    try:
        app = create_app(from_env())
    finally:
        log.removeHandler(warned)
    server = serve(app, host="127.0.0.1", port=0, block=False)
    url = f"http://127.0.0.1:{server.server_address[1]}"
    print(f"serving {env['MODEL_ID']} at {url} "
          f"(create_app+serve {time.perf_counter() - t0:.1f}s; its "
          f"warnings came at {[round(t - wall0, 1) for t in warned.at]} s: "
          "imports and the failed hub look-up for the weights, the seeded "
          "init, then the look-up for the tokenizer)", flush=True)
    assert any("RANDOM-INIT" in w for w in warned.text), (
        "the loader found real weights; this smoke expects none")
    assert any("byte-level fallback" in w for w in warned.text), (
        "a real tokenizer loaded; this smoke sizes prompts in bytes")
    print("weights: the loader's seeded random init (PRNGKey(0)) — no "
          "checkpoint, no HF cache, no network; tokenizer: byte-level "
          "fallback")
    return app, server, url


def decode_engine(runner):
    """The DecodeEngine under the serving front ends."""
    eng = getattr(runner, "engine", runner)
    return getattr(eng, "plain", eng)


def check_decode_kernel(engine):
    kernel = engine._decode_kernel
    print(f"decode kernel resolved by the engine: {kernel!r} "
          f"(cache {engine._cache_seq} slots, dtype {engine.dtype})")
    assert kernel in COMPILED_DECODE_KERNELS, (
        f"the engine resolved decode kernel {kernel!r}: not a compiled "
        f"Pallas kernel {COMPILED_DECODE_KERNELS} (None is the XLA path)")


def report_prefill_attention(engine, seq):
    """Which attention branch the engine's prefill takes at ``seq`` tokens
    — the same static gate ``DecodeEngine._prefill_impl`` evaluates."""
    from llm_sharding_demo_tpu.ops.flash_attention import (
        FLASH_MIN_SEQ, flash_eligible, flash_profitable)
    impl = engine.config.attention_impl
    flash = (impl == "pallas" and flash_eligible(seq)
             and flash_profitable(seq))
    print(f"prefill attention at {seq} tokens: "
          f"{'pallas flash kernel' if flash else 'xla einsum'} "
          f"(attention_impl={impl!r}; the flash kernel is dispatched from "
          f"{FLASH_MIN_SEQ} tokens, past this model's "
          f"{engine.config.n_positions} positions)")


def _text(n, salt):
    """``n`` ASCII bytes (= n byte-level tokens), distinct per salt."""
    words = ("shard", "stage", "token", "cache", "block", "batch", "chip",
             "mesh", "layer", "head")
    out, i = [], salt
    while sum(len(w) + 1 for w in out) < n:
        out.append(words[i % len(words)] + str(i * 7 % 10))
        i += 3
    return " ".join(out)[:n].ljust(n, ".")


def concurrent_workload(head_new=512):
    """Eight greedy requests of unequal prompt length. The first (longest
    prompt, longest generation) seeds a live batch; the other seven are
    sent together once it decodes, so they join mid-flight. Two of them
    share a 192-token prefix (three chunks of the prefix store)."""
    shared = _text(192, 1)
    rows = [(_text(300, 2), head_new)]
    rows += [(_text(n, 10 + i), new) for i, (n, new) in enumerate(
        [(24, 32), (57, 40), (96, 48), (131, 56), (180, 64)])]
    rows += [(shared + _text(20, 30), 36), (shared + _text(45, 40), 44)]
    return rows


def run_concurrent(client, rows):
    """One round of the workload above; returns each row's new tokens."""
    def stat(name):
        return client.get("/healthz").json()["iter_batch_stats"][name]

    before = stat("segments")
    with ThreadPoolExecutor(max_workers=len(rows)) as pool:
        head = pool.submit(client.generate, *rows[0])
        deadline = time.monotonic() + 600
        while stat("segments") == before and not head.done():
            assert time.monotonic() < deadline, "head row never decoded"
            time.sleep(0.002)
        rest = [pool.submit(client.generate, *row) for row in rows[1:]]
        return [f.result() for f in [head] + rest]


def logit_margin(engine, prompt_ids, tokens, step):
    """Top-2 logit margin at ``step`` of a greedy stream, through the
    engine's own compiled prefill over prompt + the tokens before it."""
    import jax.numpy as jnp
    import numpy as np
    ids = jnp.asarray([list(prompt_ids) + list(tokens[:step])], jnp.int32)
    logits, _cache = engine._prefill(engine._run_params(), ids, None)
    top = np.sort(np.asarray(logits, np.float32)[0])[-2:]
    return float(top[1] - top[0])


def compare_rows(engine, prompts, batched, solo, label):
    """Byte-equal is the repo's bar (what the CPU tests pin). On the chip
    in bf16 two batch widths are two programs and random-init weights
    have near ties, so a row may part from its solo run — but only where
    the top-2 logits are closer than the ``decode.bf16`` budget lets two
    programs disagree. Each program's logits lie within sqrt(logit_mse)
    (RMS) of the oracle's, so the margin between two tokens, seen by two
    programs, differs by 2 * sqrt(logit_mse) RMS (four such errors);
    three of those deviations is the bound. (One deviation, 0.0141, is
    too tight for any path whose matmuls are XLA's: at width 8 and at
    width 1 they round the bf16 residual stream differently, and the XLA
    path itself parts at a margin of 0.0196 on this chip: PERF.md 6,
    PR 29.)"""
    from llm_sharding_demo_tpu.utils.graftnum import TOLERANCE_POLICY
    near_tie = 6 * math.sqrt(TOLERANCE_POLICY["decode.bf16"]["logit_mse"])
    equal = 0
    for i, (prompt_ids, a, b) in enumerate(zip(prompts, batched, solo)):
        if a == b:
            equal += 1
            continue
        step = next(t for t, (x, y) in enumerate(zip(a, b)) if x != y)
        margin = logit_margin(engine, prompt_ids, b, step)
        print(f"{label} row {i}: differs from its solo run at step {step} "
              f"(batched {a[step]}, solo {b[step]}); top-2 logit margin "
              f"there {margin:.5f} (near-tie bound {near_tie:.5f})")
        assert margin <= near_tie, (
            f"{label} row {i} differs at step {step} where the top-2 "
            f"margin {margin:.5f} is outside the decode.bf16 budget "
            f"({near_tie:.5f}): not a near tie")
    print(f"{label}: {equal}/{len(solo)} rows byte-equal to their solo "
          "runs, the rest differ only at a near tie")


def request_phase(client, app, counter, head_new=512, max_rounds=6):
    """Phases 3 and 4 of ISSUE 21: solo greedy, seeded sample, eight
    concurrent rows that join mid-flight, then /healthz and /metrics."""
    engine = decode_engine(app.runner)
    check_decode_kernel(engine)
    report_prefill_attention(engine, engine.max_seq)

    timed(counter, "greedy", lambda: client.generate(
        "Hi, I am a shard of a language model and", 32))
    a = timed(counter, "sample", lambda: client.generate(
        "Once upon a time there was a chip.", 32, mode="sample", seed=7))
    b = client.generate("Once upon a time there was a chip.", 32,
                        mode="sample", seed=7)
    assert a == b, "a seeded sample request is not reproducible"

    rows = concurrent_workload(head_new)
    solo = timed(counter, "solo-rows", lambda: [
        client.generate(*row) for row in rows])

    joins0 = client.get("/healthz").json()["iter_batch_stats"]["joins"]
    batched = None
    for rnd in range(max_rounds):
        mark, t0 = counter.mark(), time.perf_counter()
        batched = run_concurrent(client, rows)
        programs, compile_s, hits = counter.since(mark)
        print(f"phase concurrent round {rnd}: "
              f"wall_s={time.perf_counter() - t0:.3f} programs={programs} "
              f"compile_s={compile_s:.3f} cache_hits={hits}", flush=True)
        if programs == 0:
            break
    print(f"compilations after warm-up: {programs} "
          f"(concurrent round {rnd}, after {rnd} warm-up rounds)")
    assert programs == 0, (
        f"still compiling after {max_rounds} rounds of the same workload")

    health = client.get("/healthz").json()
    print("healthz:", json.dumps({k: health[k] for k in (
        "model", "n_stages", "batch_mode", "max_batch", "inference_dtype",
        "iter_batch_stats", "prefix_cache_stats", "kv_pool_stats")}))
    assert health["batch_mode"] == "iter" and health["max_batch"] > 1
    stats = health["iter_batch_stats"]
    assert stats["joins"] > joins0, "no row joined a live batch"
    pool = health["kv_pool_stats"]
    held = pool["blocks_in_use"] - pool["blocks_evictable"]
    assert held == 0, (
        f"{held} pool blocks still held by rows after every request "
        f"finished: {pool}")
    assert health["prefix_cache_stats"]["hits"] >= 1, "no prefix-store hit"
    metrics = client.get("/metrics").text
    for series in ("generated_tokens_total", "iter_joins_total",
                   "prefix_cache_hits_total", "compile_events_total"):
        assert series in metrics, f"/metrics lacks {series}"

    prompts = [list(p.encode()) for p, _ in rows]
    compare_rows(engine, prompts, batched, solo, "concurrent")
    return engine


def stepwise_logits(engine, prompt_ids, forced, step):
    """[len(forced) + 1, V] f32 logits: prefill, then one single-token
    cached forward (``step``, from ``decode_step``) per forced token."""
    import jax.numpy as jnp
    import numpy as np
    params = engine._run_params()
    logits, cache = engine._prefill(
        params, jnp.asarray([prompt_ids], jnp.int32), None)
    out = [np.asarray(logits, np.float32)[0]]
    for tok in forced:
        logits, cache = step(params, jnp.asarray([tok], jnp.int32), cache)
        out.append(np.asarray(logits, np.float32)[0, -1])
    return np.stack(out)


def decode_step(engine):
    """One cached single-token forward, jitted: ``_forward_cached`` is the
    body of the engine's decode segment, so this is the decode path
    itself, with its logits kept."""
    import jax
    return jax.jit(
        lambda p, tok, cache: engine._forward_cached(
            p, tok[:, None], cache, None), donate_argnums=(2,))


def kernel_vs_xla(engine, counter, steps=48):
    """Point 5: the kernel path against the XLA path on this device —
    same weights, same prompt, prefill then ``steps`` decode forwards,
    teacher-forced by the XLA engine's greedy stream (the oracle's own
    scheme, utils/graftnum.ToleranceOracle) and gated by the budget that
    file declares for ``decode.bf16``."""
    import numpy as np
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    from llm_sharding_demo_tpu.utils.graftnum import TOLERANCE_POLICY
    policy = TOLERANCE_POLICY["decode.bf16"]
    xla = DecodeEngine(engine.params, engine.config, max_seq=engine.max_seq,
                       dtype=engine.dtype, decode_kernel="xla")
    assert xla._decode_kernel is None
    prompt = list(_text(64, 5).encode())
    forced = [int(t) for t in
              xla.generate(np.asarray([prompt]), steps).tokens[0, 64:]]
    ref, got = (
        timed(counter, name, lambda e=e, step=decode_step(e):
              stepwise_logits(e, prompt, forced[:-1], step))
        for name, e in (("xla-path", xla),
                        (f"kernel-path[{engine._decode_kernel}]", engine)))
    assert np.isfinite(got).all() and got.shape == ref.shape == (
        steps, engine.config.vocab_size), (got.shape, ref.shape)
    # position 0 is the prefill both engines share; the decode steps are
    # where the kernel runs
    mse = float(np.mean((got[1:] - ref[1:]) ** 2))
    agree = float(np.mean(got[1:].argmax(-1) == ref[1:].argmax(-1)))
    print(f"kernel vs xla over {steps - 1} decode steps: logit_mse="
          f"{mse:.3e} (budget {policy['logit_mse']:.1e}) top1_agreement="
          f"{agree:.4f} (floor {policy['top1_agreement']})")
    assert mse <= policy["logit_mse"], "kernel path outside decode.bf16 mse"
    assert agree >= policy["top1_agreement"], (
        "kernel path outside decode.bf16 top-1 agreement")


def one_chip(counter):
    app, server, url = start_server(SERVING_ENV)
    try:
        client = Client(url, TokenTap(app.runner))
        engine = request_phase(client, app, counter)
        assert engine.config.n_layer == 12 and engine.config.n_embd == 768
        kernel_vs_xla(engine, counter)
    finally:
        server.shutdown()
        server.server_close()


def stage_devices(arr, n_stages):
    """The device holding each stage's slice of a stage-major array,
    read from the array's own sharding."""
    by_stage = {}
    for shard in arr.addressable_shards:
        start = shard.index[0].start or 0
        assert shard.data.shape[0] == 1, (
            f"a shard holds {shard.data.shape[0]} stages: {arr.sharding}")
        by_stage[start] = shard.device
    assert sorted(by_stage) == list(range(n_stages)), by_stage
    return [by_stage[i] for i in range(n_stages)]


def check_pipeline(app, client, counter, prompts, new_tokens=32):
    """The --chips 4 phase: requests through the staged server, placement
    read from the arrays, tokens against the unstaged engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine
    from llm_sharding_demo_tpu.serving import loader
    from llm_sharding_demo_tpu.utils.config import from_env

    dec = app.runner
    n = dec.n_stages
    health = client.get("/healthz").json()
    assert health["n_stages"] == n == 4 and health["pp_decode"], health
    assert len(health["devices"]) >= n, health["devices"]

    placed = None
    for leaf in jax.tree.leaves(dec.blocks):
        devs = stage_devices(leaf, n)
        assert len(set(devs)) == n, f"stages share a device: {devs}"
        assert placed in (None, devs), (placed, devs)
        placed = devs
    _logits, ck, cv = dec._prefill(dec.shared, dec.blocks,
                                   jnp.zeros((1, 8), jnp.int32), None)
    for kv in (ck, cv):
        assert stage_devices(kv, n) == placed, (stage_devices(kv, n), placed)
    print(f"stage placement: blocks and KV of stages 0..{n - 1} on "
          f"{[str(d) for d in placed]}; /healthz n_stages={n}")

    staged = [timed(counter, f"pipeline-generate[{len(p)} bytes]",
                    lambda p=p: client.generate(p, new_tokens))
              for p in prompts]

    # the unstaged engine on one device, same seeded weights
    cfg = from_env()
    config, params = loader.resolve_model(cfg)
    eng = DecodeEngine(params, config, max_seq=cfg.max_seq,
                       dtype=cfg.inference_dtype)
    solo = [[int(t) for t in eng.generate(
        np.asarray([list(p.encode())]), new_tokens).tokens[0, -new_tokens:]]
        for p in prompts]
    compare_rows(eng, [list(p.encode()) for p in prompts], staged, solo,
                 "pipeline")


def four_chips(counter):
    app, server, url = start_server(PIPELINE_ENV)
    try:
        client = Client(url, TokenTap(app.runner))
        check_pipeline(app, client, counter,
                       [_text(n, 50 + n) for n in (16, 48, 120)])
    finally:
        server.shutdown()
        server.server_close()


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the four-stage pipelined path")
    args = parser.parse_args()

    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{device.platform!r}); there is no CPU fallback")
    if len(jax.devices()) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs that many "
                 f"devices, JAX found {len(jax.devices())}")

    from importlib import metadata

    import jaxlib
    from llm_sharding_demo_tpu.utils import compile_cache
    print(f"device: {device.device_kind} x {len(jax.devices())} "
          f"(platform {device.platform}); jax {jax.__version__}, jaxlib "
          f"{jaxlib.__version__}, libtpu {metadata.version('libtpu')}; "
          f"compile cache: {compile_cache.configure()}", flush=True)

    counter = CompileCounter()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(counter)
    print(f"total: {time.perf_counter() - t0:.1f}s, {counter.programs} "
          f"programs built or loaded ({counter.cache_hits} from the "
          f"compile cache), {counter.seconds:.1f}s inside XLA's compile")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
