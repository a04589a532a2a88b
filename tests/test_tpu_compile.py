"""The main path's Pallas kernels, compiled at real widths for a TPU v5e.

No chip is attached here: the TPU compiler is installed and compiles for a
DESCRIBED ``v5e:2x2`` device (section 2 of the on-chip-measurement guide),
which refuses what interpret mode lets through — a slice off the tiling,
a kernel past its fast-memory limit, a program past the device's 16 GB.
A compile that passes is not a chip run; ``chip_smoke.py`` is.

The described chip is ``tests/conftest.py``'s ``one_chip``; the engine's
whole programs are compiled in ``tests/test_tpu_compile_engine.py``, a
file (and so, under ``--dist loadfile``, a worker) of their own.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.ops import decode_attention as decode_attention_mod
from llm_sharding_demo_tpu.ops import quant
from llm_sharding_demo_tpu.ops.decode_attention import (decode_attention,
                                                        stream_block)
from llm_sharding_demo_tpu.ops.flash_attention import flash_attention



# (query heads, kv heads, head width, layers, cache depth): GPT-2 124M,
# and the geometry ``mistral-7b-l16``'s cells run (32 query heads over 8
# of 128, a 2,048-slot cache), at the widths the scheduler compiles
@pytest.mark.parametrize("geometry,batch", [
    *[pytest.param((12, 12, 64, 12, 1024), b, id=f"gpt2-124m-{b}")
      for b in (1, 8, 32)],
    *[pytest.param((32, 8, 128, 16, 2048), b, id=f"mistral-7b-l16-{b}")
      for b in (1, 8, 16)]])
def test_decode_attention_compiles(one_chip, geometry, batch):
    h, hkv, hd, layers, depth = geometry
    shape = one_chip.shape
    one_chip.compile(
        lambda q, k, v, kv: decode_attention(q, k, v, kv, 3, 517),
        shape((batch, h, 1, hd)), shape((batch, hkv, 1, hd)),
        shape((batch, hkv, 1, hd)),
        shape((layers, batch, hkv, depth, 2 * hd)))


# the same kernel with the rows' pads, as the iter scheduler calls it
# (the spans its copies follow: a second scalar operand, a copy and a
# semaphore a row): ``mistral-7b-l16`` at the two widest batches its
# cells run, ``falcon-h1-34b-l6`` (20 query heads over 4) at its widest,
# ``qwen3-next-80b-ep32``'s softmax layers (16 over 2 of 256) at 4
@pytest.mark.parametrize("geometry,batch", [
    *[pytest.param((32, 8, 128, 16, 2048), b, id=f"mistral-7b-l16-{b}")
      for b in (8, 16)],
    pytest.param((20, 4, 128, 6, 1024), 16, id="falcon-h1-34b-l6-16"),
    pytest.param((16, 2, 256, 12, 3072), 4, id="qwen3-next-80b-ep32-4")])
def test_decode_attention_with_spans_compiles(one_chip, geometry, batch):
    h, hkv, hd, layers, depth = geometry
    shape = one_chip.shape
    compiled = jax.jit(
        lambda q, k, v, kv, li, off, pad: decode_attention(
            q, k, v, kv, li, off, pad)).lower(
        shape((batch, h, 1, hd)), shape((batch, hkv, 1, hd)),
        shape((batch, hkv, 1, hd)),
        shape((layers, batch, hkv, depth, 2 * hd)),
        shape((), jnp.int32), shape((), jnp.int32),
        shape((batch,), jnp.int32)).compile()
    one_chip.check(compiled)
    # the VMEM the compiler held the kernel to (the program's own report
    # of its scoped memory: a kernel past it does not compile) lies
    # inside the room ``stream_block`` reckons with, and so does the
    # stream it sized: the double buffer and the body's temporaries
    room = (decode_attention_mod._VMEM_BYTES
            - decode_attention_mod._VMEM_HEADROOM)
    held = max(int(n) for n in re.findall(
        r'"memory_space":"1","offset":"0","size":"(\d+)"',
        compiled.as_text()))
    assert 0 < held <= room
    block = stream_block(batch * hkv, hd, 2)
    assert block * batch * hkv * 2 * hd * (
        2 * 2 + 4 * decode_attention_mod._F32_TEMPORARIES) <= room


# a block's four positions over the same two-plane cache
# (``ops.block_decode``): SDAR-30B-A3B's geometry (32 query heads over 4
# of 128, 48 layers, a 2,048-slot cache) at its narrowest and widest
# width, where the halves of a fused row are whole lane tiles; and rows
# of 128 lanes (GPT-2's 64-wide heads), where the kernel cuts the
# halves inside a tile, which only this compiler can refuse
@pytest.mark.parametrize("geometry,batch", [
    *[pytest.param((32, 4, 128, 48, 2048), b, id=f"sdar-30b-a3b-ep8-{b}")
      for b in (1, 8)],
    pytest.param((12, 12, 64, 12, 1024), 4, id="heads-of-64-4")])
def test_block_decode_attention_compiles(one_chip, geometry, batch):
    from llm_sharding_demo_tpu.ops.block_decode import block_decode_attention
    h, hkv, hd, layers, depth = geometry
    shape = one_chip.shape
    compiled = jax.jit(
        lambda q, k, v, kv, li, off, pad: block_decode_attention(
            q, k, v, kv, li, off, pad), donate_argnums=(3,)).lower(
        shape((batch, h, 4, hd)), shape((batch, hkv, 4, hd)),
        shape((batch, hkv, 4, hd)),
        shape((layers, batch, hkv, depth, 2 * hd)),
        shape((), jnp.int32), shape((), jnp.int32),
        shape((batch,), jnp.int32)).compile()
    mem = one_chip.check(compiled)
    # the cache goes in and comes out as one buffer, and nothing else
    # is held on the device beside the operands
    assert mem.alias_size_in_bytes == layers * batch * hkv * depth * 2 * hd * 2
    assert mem.temp_size_in_bytes < 1e6


@pytest.mark.parametrize("batch,lanes", [(1, 640), (16, 640)])
def test_latent_decode_attention_compiles(one_chip, batch, lanes):
    """The absorbed decode step over a latent cache at the published
    widths: 32 heads, rows of 640 lanes (576 values), a 3,072-slot cache
    of 40 layers. (At 576 lanes Mosaic refused a hand-made DMA slice.)"""
    from llm_sharding_demo_tpu.ops.latent_decode import (
        latent_decode_attention)
    shape = one_chip.shape
    one_chip.compile(lambda q, cache, pad, li, off: latent_decode_attention(
        q, cache, li, off, 192 ** -0.5, pad),
        shape((batch, 32, lanes)), shape((40, batch, 1, 3072, lanes)),
        shape((batch,), jnp.int32), shape((), jnp.int32),
        shape((), jnp.int32))


@pytest.mark.parametrize("rows", [1, 8])
def test_int8_linear_and_head_compile(one_chip, rows):
    """ops/quant's streaming int8 matmuls at d=768: the MLP up-projection
    and the LM head over the padded vocab."""
    cfg = gpt2.CONFIGS["gpt2"]
    d, v_pad = cfg.n_embd, quant._round_up_vocab(cfg.vocab_size)
    # pallas_eligible minus its "is the backend a TPU" clause
    assert quant.pallas_eligible(d, 4 * d, rows, force_pallas=True)
    assert d % 128 == 0 and v_pad % 128 == 0
    shape = one_chip.shape
    one_chip.compile(quant._pallas_linear, shape((rows, d)),
                     shape((d, 4 * d), jnp.int8),
                     shape((4 * d,), jnp.float32))
    one_chip.compile(quant._pallas_head, shape((rows, d)),
                     shape((v_pad, d), jnp.int8))


@pytest.mark.parametrize("seq", [1024, 2048])
def test_flash_attention_forward_and_backward_compile(one_chip, seq):
    qkv = [one_chip.shape((1, 12, seq, 64))] * 3
    one_chip.compile(flash_attention, *qkv)
    one_chip.compile(jax.grad(lambda q, k, v: flash_attention(
        q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)), *qkv)
