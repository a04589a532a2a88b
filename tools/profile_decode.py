"""Decode-step profiling probes for the bench chip.

These probes produced the round-3 findings (see ops/decode_attention.py
and the git log):

1. XLA will NOT update a KV cache in place when the freshly written
   buffer feeds a dot in the same loop iteration — every
   ``dynamic_update_slice``+attend decode step materializes a copy of
   the touched buffers (~200-230 GB/s effective vs ~515 GB/s for
   read-only streaming). Donation, ``optimization_barrier``, full
   unrolling, and separate per-layer buffers all measured the same or
   worse.
2. Attention reads over scan **xs** stream at ~515 GB/s; the decode
   kernel's fused-KV DMA blocks reach further still.
3. The LM-head matvec at bs=8 runs at ~800 GB/s — HBM roofline; the
   head was never the batched-decode bottleneck.

Methodology (see also bench.py): every timing window is ONE
dependency-chained compiled program closed by a host fetch, and rates
are two-point marginals so the fixed per-window cost cancels.

Usage: python tools/profile_decode.py [--probe engine|attention|head]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# THE timing harness lives in bench.py (incl. the non-positive-marginal
# guard for windows drowned by barrier jitter) — reuse, don't re-derive
from bench import _fetch, marginal_seconds


def marginal(window, n1: int, n2: int, reps: int = 3) -> float:
    m = marginal_seconds(window, n1, n2, reps=reps)
    if m is None:
        raise RuntimeError("marginal below the timer's resolution "
                           "(t2 <= t1); enlarge the windows")
    return m


def probe_engine() -> None:
    """Full decode steps via the real engine (the known-good harness):
    kernel vs XLA path at the cfg3 shape."""
    import bench
    from llm_sharding_demo_tpu.models import gpt2

    for bs in (1, 8):
        out = bench.measure_engine(gpt2.CONFIGS["gpt2"], 16, bs,
                                   "bfloat16", s_b=512)
        ms = out["p50_token_latency_ms"]
        print(f"engine bs={bs}: {ms:.3f} ms/step "
              f"({out['tokens_per_sec']:.0f} tok/s)", flush=True)


def probe_attention() -> None:
    """Isolated cached-attention read patterns at the cfg3 shape —
    reproduces finding 1/2 above."""
    L, B, H, S, hd = 12, 8, 12, 528, 64
    key = jax.random.PRNGKey(0)
    K = jax.random.normal(key, (L, B, H, S, hd), jnp.bfloat16)
    V = jax.random.normal(key, (L, B, H, S, hd), jnp.bfloat16)
    q0 = jax.random.normal(key, (B, H, hd), jnp.bfloat16)
    kn = jax.random.normal(key, (B, H, 1, hd), jnp.bfloat16)
    nbytes = L * B * H * S * hd * 2 * 2

    def attend(h, k, v):
        s = jnp.einsum("bhd,bhkd->bhk", h, k,
                       preferred_element_type=jnp.float32)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return h + jnp.einsum("bhk,bhkd->bhd", w, v) * 1e-3

    def stream_step(q, K, V):            # read-only: scan xs streaming
        def body(h, kv):
            k, v = kv
            return attend(h, k, v), None
        h, _ = jax.lax.scan(body, q, (K, V))
        return h, K, V

    def carry_step(q, K, V):             # write-then-read on the carry
        def body(c, li):
            h, K, V = c
            K = jax.lax.dynamic_update_slice(
                K, kn[None] + h[:, :, None, :] * 0, (li, 0, 0, 100, 0))
            V = jax.lax.dynamic_update_slice(V, kn[None], (li, 0, 0, 100, 0))
            k = jax.lax.dynamic_index_in_dim(K, li, 0, keepdims=False)
            v = jax.lax.dynamic_index_in_dim(V, li, 0, keepdims=False)
            return (attend(h, k, v), K, V), None
        (h, K, V), _ = jax.lax.scan(body, (q, K, V), jnp.arange(L))
        return h, K, V

    for name, step in (("stream (read-only)", stream_step),
                       ("carry (write+read)", carry_step)):
        def run_n(n, step=step):
            @jax.jit
            def run(q, K, V):
                def body(c, _):
                    return step(*c), None
                (q, K, V), _ = jax.lax.scan(body, (q, K, V), None, length=n)
                return q
            return run

        compiled = {}

        def window(n):
            if n not in compiled:
                compiled[n] = run_n(n)
            t0 = time.perf_counter()
            _fetch(compiled[n](q0, K, V))
            return time.perf_counter() - t0

        ms = marginal(window, 8, 32) * 1e3
        print(f"attention {name}: {ms:.3f} ms/step, "
              f"{nbytes / (ms / 1e3) / 1e9:.0f} GB/s", flush=True)


def probe_head() -> None:
    """LM-head matvec at bs=8 (finding 3)."""
    w = jax.random.normal(jax.random.PRNGKey(0), (768, 50257), jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 768), jnp.bfloat16)
    compiled = {}

    def run_n(n):
        @jax.jit
        def run(x):
            def body(c, _):
                y = jnp.einsum("bd,dv->bv", c, w,
                               preferred_element_type=jnp.float32)
                return c + (y[:, :768] * 1e-6).astype(c.dtype), None
            c, _ = jax.lax.scan(body, x, None, length=n)
            return c
        return run

    def window(n):
        if n not in compiled:
            compiled[n] = run_n(n)
        t0 = time.perf_counter()
        _fetch(compiled[n](x))
        return time.perf_counter() - t0

    ms = marginal(window, 16, 64) * 1e3
    nbytes = 768 * 50257 * 2
    print(f"head matvec bs=8: {ms:.3f} ms/step, "
          f"{nbytes / (ms / 1e3) / 1e9:.0f} GB/s")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--probe", default="engine",
                    choices=("engine", "attention", "head"))
    args = ap.parse_args()
    {"engine": probe_engine, "attention": probe_attention,
     "head": probe_head}[args.probe]()
