"""``chip_smoke.py`` off the chip: what can be pinned without one.

- no CPU fallback: under the forced CPU platform the script exits non-zero
  and its last line is not the ok line;
- the request phase's helpers, driven in-process against tiny-gpt2 on the
  CPU — same server path, same checks — with the one check that needs a
  chip (the decode kernel is a compiled Pallas one) steered from here, by
  patching the module's constant, not by an option of the program;
- the compile-cache rule the script, the server, the bench and this suite
  share.
"""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from llm_sharding_demo_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_exits_nonzero_without_a_tpu_and_prints_no_ok_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout, r.stdout[-400:]
    assert "no TPU" in r.stderr


@pytest.fixture(scope="module")
def served():
    """tiny-gpt2 behind the smoke's serving configuration (bf16, iter
    batcher, pool, prefix store), over real sockets."""
    env = dict(chip_smoke.SERVING_ENV, MODEL_ID="sshleifer/tiny-gpt2")
    saved = {k: os.environ.get(k) for k in env}
    app, server, url = chip_smoke.start_server(env)
    try:
        yield app, chip_smoke.Client(url, chip_smoke.TokenTap(app.runner))
    finally:
        server.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_kernel_check_refuses_the_xla_path(served):
    """On the CPU ``decode_kernel="auto"`` resolves to XLA — exactly what
    the smoke must never accept on the chip."""
    app, _client = served
    engine = chip_smoke.decode_engine(app.runner)
    assert engine._decode_kernel is None
    with pytest.raises(AssertionError, match="not a compiled Pallas kernel"):
        chip_smoke.check_decode_kernel(engine)


def test_request_phase_passes_on_cpu(served, monkeypatch):
    """Greedy, seeded sample, eight rows solo and concurrent with
    mid-flight joins, /healthz and /metrics, rows against their solo
    runs, then the decode-path comparison (XLA against itself here)."""
    app, client = served
    monkeypatch.setattr(chip_smoke, "COMPILED_DECODE_KERNELS", (None,))
    counter = chip_smoke.CompileCounter()
    engine = chip_smoke.request_phase(client, app, counter, head_new=160)
    chip_smoke.kernel_vs_xla(engine, counter, steps=8)


@pytest.mark.parametrize("placed", ["/some/dir", None])
def test_compile_cache_rule(monkeypatch, placed):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code (JAX
    reads the variable itself). Unset: <checkout>/.jax_cache."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if placed:
        monkeypatch.setenv(compile_cache.ENV_VAR, placed)
        assert compile_cache.configure() == placed
        assert updates == []
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.configure() == want
        assert updates == [("jax_compilation_cache_dir", want)]
