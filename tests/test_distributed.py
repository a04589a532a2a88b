"""Multi-host bootstrap glue (parallel.distributed).

A real multi-host run needs multiple hosts; what IS testable in one
process: the env contract (no-op / partial-config error), the
single-process jax.distributed service round trip (initialize with
num_processes=1 starts and joins a real coordination service), the
global-mesh builder, and the host-local -> global batch path feeding an
actual sharded computation.
"""

import os
import socket

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from llm_sharding_demo_tpu.parallel import distributed, spmd


def test_single_process_is_noop(monkeypatch):
    for var in ("COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    assert distributed.maybe_initialize() is False


def test_partial_config_rejected(monkeypatch):
    monkeypatch.setenv("COORDINATOR_ADDRESS", "localhost:9999")
    monkeypatch.delenv("NUM_PROCESSES", raising=False)
    monkeypatch.delenv("PROCESS_ID", raising=False)
    with pytest.raises(ValueError, match="partial multi-host config"):
        distributed.maybe_initialize()


def test_global_mesh_and_host_batch():
    mesh = distributed.global_mesh({"dp": 2, "tp": 4})
    assert mesh.shape == {"dp": 2, "tp": 4}
    batch = np.arange(4 * 3, dtype=np.int32).reshape(4, 3)
    arr = distributed.shard_host_batch(batch, mesh, axis="dp")
    assert arr.shape == (4, 3)
    assert arr.sharding.spec == P("dp")
    # feeds real sharded compute
    total = jax.jit(jnp.sum)(arr)
    assert int(total) == batch.sum()


def test_global_mesh_size_mismatch():
    with pytest.raises(ValueError, match="needs 16 devices"):
        distributed.global_mesh({"dp": 4, "tp": 4})


def test_single_process_service_roundtrip():
    """initialize(num_processes=1) joins a REAL coordination service and
    the global runtime still computes — the exact code path multi-host
    pods take, minus the extra peers. Runs in a clean subprocess because
    jax.distributed.initialize must precede ANY backend use and this
    process's backend is already up (conftest)."""
    import subprocess
    import sys

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    code = f"""
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp, numpy as np
from llm_sharding_demo_tpu.parallel import distributed
assert distributed.maybe_initialize(
    coordinator_address="127.0.0.1:{port}",
    num_processes=1, process_id=0) is True
assert jax.process_count() == 1
assert distributed.maybe_initialize() is True  # idempotent
mesh = distributed.global_mesh({{"dp": 8}})
arr = distributed.shard_host_batch(np.ones((8, 2), np.float32), mesh, "dp")
assert float(jax.jit(jnp.sum)(arr)) == 16.0
print("roundtrip-ok")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "roundtrip-ok" in out.stdout
