"""Compile a cell's programs at real shapes for a described ``v5e:2x2``.

Run by hand, here, before chip time is spent: ``python -m
benchmark.rehearse --workload <name>``. No chip is attached: the TPU
compiler builds the engine's prefill program (longest prompt of the
cell's traffic), the prefix store's chunk program and the decode-segment
program (``MAX_BATCH`` rows, the per-layer Pallas decode kernel) from
shapes alone, and ``memory_analysis()`` of each is printed beside the
bytes the weights and the pool will hold. What the compiler refuses here
(a kernel over its VMEM, a program over the device's memory) costs no
chip time. A compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


class _Abstract:
    """A leaf that is only a shape: enough for ``DecodeEngine.__init__``
    (which casts and sizes its parameters) without a device to hold them."""

    def __init__(self, shape, dtype, sharding):
        import jax
        self.sds = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        self.shape, self.dtype = shape, self.sds.dtype

    def astype(self, _dtype):
        return self

    @property
    def nbytes(self):
        import math
        return math.prod(self.shape) * self.dtype.itemsize


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.rehearse")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--benchmark-json", default=None)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from .harness import server
    from .harness.spec import Spec, resolve
    from llm_sharding_demo_tpu.runtime.engine import (DecodeEngine,
                                                      SamplingConfig)

    spec = Spec(args.benchmark_json)
    entry = spec.workload(args.workload)
    if entry["chips"] != 1:
        sys.exit("benchmark.rehearse: one-chip cells only (a cell across "
                 "chips brings its own scratch script for the mesh)")
    config = spec.config(entry["config"])
    traffic = spec.traffic(entry["traffic"])
    env = config["serving_env"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    model_config = server.family_config(config)
    reference = resolve(config["reference"])
    shapes = jax.eval_shape(lambda: reference.init(config, 0))
    abstract = jax.tree.map(lambda s: _Abstract(s.shape, s.dtype, chip), shapes)
    engine = DecodeEngine(abstract, model_config, max_seq=int(env["MAX_SEQ"]),
                          dtype=env["INFERENCE_DTYPE"], decode_kernel="layer")
    params = jax.tree.map(lambda a: a.sds, engine.params,
                          is_leaf=lambda x: isinstance(x, _Abstract))
    weights = sum(a.nbytes for a in jax.tree.leaves(
        abstract, is_leaf=lambda x: isinstance(x, _Abstract)))
    bm = resolve(config["bytes_model"])(config)
    pool = int(env["KV_POOL_BLOCKS"]) * int(env["KV_BLOCK_SIZE"]) * bm["kv_per_token"]
    print(f"weights {weights / 1e9:.2f} GB, pool {pool / 1e9:.2f} GB, decode "
          f"kernel {engine._decode_kernel!r}")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def report(name, lowered):
        m = lowered.compile().memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.2f} GB", flush=True)

    longest = traffic["prompt"]["max"]
    report(f"prefill 1x{longest}", jax.jit(engine._prefill_impl).lower(
        params, sds((1, longest), jnp.int32), sds((1,), jnp.int32)))
    width = int(env["MAX_BATCH"])
    cache = jax.eval_shape(lambda: engine._fresh_cache(width))
    cache = jax.tree.map(lambda s: sds(s.shape, s.dtype), cache)
    steps = jax.eval_shape(lambda: jnp.zeros((32, width, 2), jnp.uint32))
    report(f"decode segment {width} rows x 32 steps", jax.jit(
        engine._decode_seg_impl, static_argnames=("sampling", "window")).lower(
        params, sds((width,), jnp.int32), cache, sds((width,), jnp.int32),
        sds(steps.shape, steps.dtype), sampling=SamplingConfig(mode="greedy"),
        window=None))
    return 0


if __name__ == "__main__":
    sys.exit(main())
