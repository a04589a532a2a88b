"""The main path's Pallas kernels, compiled at real widths for a TPU v5e.

No chip is attached here: the TPU compiler is installed and compiles for a
DESCRIBED ``v5e:2x2`` device (section 2 of the on-chip-measurement guide),
which refuses what interpret mode lets through — a slice off the tiling,
a kernel past its fast-memory limit, a program past the device's 16 GB.
A compile that passes is not a chip run; ``chip_smoke.py`` is.

All of these tests live in this one file, and the topology is described
inside a module-scoped fixture: only one process may load the TPU's
library, so the call must not run while any module is imported, and a
second file's fixture would skip in silence on another xdist worker.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from llm_sharding_demo_tpu.models import gpt2, llama
from llm_sharding_demo_tpu.ops import decode_layer, quant
from llm_sharding_demo_tpu.ops.decode_attention import decode_attention
from llm_sharding_demo_tpu.ops.flash_attention import flash_attention

SMAX = 1024
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it undescribed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache but cannot be
    # read back without a chip; keep the cache out of these tests
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _placed(tree, sharding):
    """Shapes (from ``jax.eval_shape``) pinned to the described chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, one_chip, *args):
    compiled = jax.jit(fn).lower(*_placed(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel inside"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16e9, f"{total / 1e9:.1f} GB does not fit a v5e chip"
    return compiled


def _shape(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _blocks(family, config, dtype):
    """The stacked block tree the megakernel takes, as shapes: float
    (``dtype`` bf16) or weight-only int8 (``dtype`` "int8")."""
    def build():
        params = family.init_params(config, jax.random.PRNGKey(0))
        if dtype == "int8":
            return quant.quantize_params(params, BF16)["blocks"]
        return jax.tree.map(lambda x: x.astype(BF16), params["blocks"])
    return jax.eval_shape(build)


@pytest.mark.parametrize("batch", [1, 8, 32])
def test_decode_attention_compiles(one_chip, batch):
    cfg = gpt2.CONFIGS["gpt2"]
    h, hd = cfg.n_head, cfg.head_dim
    _compile(
        lambda q, k, v, kv: decode_attention(q, k, v, kv, 3, 517),
        one_chip,
        _shape((batch, h, 1, hd)), _shape((batch, h, 1, hd)),
        _shape((batch, h, 1, hd)),
        _shape((cfg.n_layer, batch, h, SMAX, 2 * hd)))


@pytest.mark.parametrize("batch,lanes", [(1, 640), (16, 640)])
def test_latent_decode_attention_compiles(one_chip, batch, lanes):
    """The absorbed decode step over a latent cache at the published
    widths: 32 heads, rows of 640 lanes (576 values), a 3,072-slot cache
    of 40 layers. (At 576 lanes Mosaic refused a hand-made DMA slice.)"""
    from llm_sharding_demo_tpu.ops.latent_decode import (
        latent_decode_attention)
    _compile(lambda q, cache, pad, li, off: latent_decode_attention(
        q, cache, li, off, 192 ** -0.5, pad), one_chip,
        _shape((batch, 32, lanes)), _shape((40, batch, 1, 3072, lanes)),
        _shape((batch,), jnp.int32), _shape((), jnp.int32),
        _shape((), jnp.int32))


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("batch", [1, 8, decode_layer.MAX_BATCH])
@pytest.mark.parametrize("name", ["gpt2", "gpt2-medium"])
def test_gpt2_megakernel_compiles(one_chip, name, batch, dtype):
    cfg = gpt2.CONFIGS[name]
    assert decode_layer.eligible(cfg, SMAX, 2)
    _compile(
        lambda blocks, h, kv: decode_layer.decode_layers(
            blocks, h, kv, 517, n_head=cfg.n_head,
            eps=cfg.layer_norm_epsilon),
        one_chip, _blocks(gpt2, cfg, dtype), _shape((batch, 1, cfg.n_embd)),
        _shape((cfg.n_layer, batch, cfg.n_head, SMAX, 2 * cfg.head_dim)))


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("batch", [1, 8, decode_layer.MAX_BATCH])
def test_llama_megakernel_compiles(one_chip, batch, dtype):
    cfg = llama.CONFIGS["llama-124m"]
    assert decode_layer.llama_eligible(cfg, SMAX, 2)
    _compile(
        lambda blocks, h, kv, cos, sin: decode_layer.decode_layers_llama(
            blocks, h, kv, 517, cos, sin, n_head=cfg.n_head,
            eps=cfg.rms_norm_eps),
        one_chip, _blocks(llama, cfg, dtype), _shape((batch, 1, cfg.n_embd)),
        _shape((cfg.n_layer, batch, cfg.n_kv_head, SMAX, 2 * cfg.head_dim)),
        _shape((batch, cfg.head_dim), jnp.float32),
        _shape((batch, cfg.head_dim), jnp.float32))


@pytest.mark.parametrize("rows", [1, 8])
def test_int8_linear_and_head_compile(one_chip, rows):
    """ops/quant's streaming int8 matmuls at d=768: the MLP up-projection
    and the LM head over the padded vocab."""
    cfg = gpt2.CONFIGS["gpt2"]
    d, v_pad = cfg.n_embd, quant._round_up_vocab(cfg.vocab_size)
    # pallas_eligible minus its "is the backend a TPU" clause
    assert quant.pallas_eligible(d, 4 * d, rows, force_pallas=True)
    assert d % 128 == 0 and v_pad % 128 == 0
    _compile(quant._pallas_linear, one_chip, _shape((rows, d)),
             _shape((d, 4 * d), jnp.int8), _shape((4 * d,), jnp.float32))
    _compile(quant._pallas_head, one_chip, _shape((rows, d)),
             _shape((v_pad, d), jnp.int8))


@pytest.mark.parametrize("seq", [1024, 2048])
def test_flash_attention_forward_and_backward_compile(one_chip, seq):
    qkv = [_shape((1, 12, seq, 64))] * 3
    _compile(flash_attention, one_chip, *qkv)
    _compile(jax.grad(lambda q, k, v: flash_attention(q, k, v).astype(
        jnp.float32).sum(), argnums=(0, 1, 2)), one_chip, *qkv)


def test_engine_decode_segment_compiles_with_megakernel(one_chip):
    """One whole program of the serving path: the engine's decode segment
    for GPT-2 124M at batch 8 with ``decode_kernel="mega"`` given
    explicitly (the explicit mode does not ask ``jax.default_backend()``,
    so the engine takes its TPU branch here), from ``jax.eval_shape``
    parameters."""

    from llm_sharding_demo_tpu.runtime.engine import (DecodeEngine,
                                                      SamplingConfig)
    cfg = gpt2.CONFIGS["gpt2"]
    # tiny real arrays for the constructor, the 124M shapes for the
    # program: the jitted segment takes the parameters as an argument
    eng = DecodeEngine(
        gpt2.init_params(gpt2.GPT2Config(
            vocab_size=64, n_positions=SMAX, n_embd=768, n_layer=1,
            n_head=12), jax.random.PRNGKey(0)),
        cfg, max_seq=SMAX, dtype=BF16, decode_kernel="mega")
    assert eng._decode_kernel == "mega"
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(BF16),
        gpt2.init_params(cfg, jax.random.PRNGKey(0))))
    batch = 8
    cache = jax.eval_shape(lambda: eng._fresh_cache(batch))
    args = _placed((params, _shape((batch,), jnp.int32), cache,
                    _shape((batch,), jnp.int32),
                    _shape((32, 2), jnp.uint32)), one_chip)
    compiled = jax.jit(
        eng._decode_seg_impl, donate_argnums=(2,),
        static_argnames=("sampling", "window")).lower(
            *args, sampling=SamplingConfig(mode="greedy"),
            window=None).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 16e9
