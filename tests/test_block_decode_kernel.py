"""A block's attention as one kernel (``ops.block_decode``), interpreted
on the CPU, against the XLA form of ``ops.block_diffusion.attend``.

The kernel's numbers are ``attend``'s to rounding (the online softmax
orders its sum otherwise), its written rows ``write_kv_layer_fused``'s
to the bit, a row's result its own whatever rides beside it, and which
calls take it follows from their shapes alone. What the chip's compiler
makes of it is ``tests/test_tpu_compile_engine.py``'s.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import sdar_moe
from llm_sharding_demo_tpu.ops import block_decode
from llm_sharding_demo_tpu.ops import block_diffusion as BD
from llm_sharding_demo_tpu.ops.attention import write_kv_layer_fused
from llm_sharding_demo_tpu.ops.decode_attention import BLOCK_S

L = 4
S = 2 * BLOCK_S            # two streamed blocks
LAYERS, LAYER = 2, 1
# (query heads, kv heads, head width): the test family's geometry (fused
# rows of 128 lanes, so the halves are cut inside a lane tile) and
# SDAR-30B-A3B's
SMALL, SDAR = (4, 2, 64), (32, 4, 128)

# name -> (offset, each row's pad or None): ``offset`` less a pad is
# whole blocks of L, as the engine keeps it
CASES = {
    "a-lone-row-without-pad": (40, None),
    "rows-of-different-pads": (300, [0, 40, 280]),
    "a-lane-with-an-empty-span": (300, [8, S, 260]),
    "a-span-that-starts-inside-a-streamed-block": (420, [300, 100]),
    "offset-on-a-write-tile": (296, [0, 256]),
    "offset-in-the-middle-of-a-write-tile": (300, [0, 256]),
    "rows-across-two-write-tiles": (302, [2, 270]),
    "offset-on-a-streamed-blocks-edge": (BLOCK_S, [0, 128]),
    "the-last-block-of-the-cache": (S - L, [0, 500]),
}
TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def operands(geometry, batch, dtype, seed=0):
    h, hkv, hd = geometry
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (batch, h, L, hd), dtype)
    k = jax.random.normal(keys[1], (batch, hkv, L, hd), dtype)
    v = jax.random.normal(keys[2], (batch, hkv, L, hd), dtype)
    kv = jax.random.normal(keys[3], (LAYERS, batch, hkv, S, 2 * hd), dtype)
    return q, k, v, kv


def bits(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("geometry,case", [
    *[(SMALL, c) for c in CASES],
    (SDAR, "rows-of-different-pads"), (SDAR, "a-lane-with-an-empty-span"),
    (SDAR, "rows-across-two-write-tiles")],
    ids=lambda x: x if isinstance(x, str) else "gqa-%d-%d-%d" % x)
def test_the_kernel_is_attends_xla_form(geometry, case, dtype):
    offset, pads = CASES[case]
    batch = 1 if pads is None else len(pads)
    q, k, v, kv = operands(geometry, batch, dtype)
    pad = None if pads is None else jnp.asarray(pads, jnp.int32)
    want, _ = jax.jit(lambda *a: BD.attend(*a, L, kv, LAYER, offset, pad))(
        q, k, v)
    written = write_kv_layer_fused(kv, k, v, LAYER, offset)
    got, cache = BD.attend(q, k, v, L, kv, LAYER, offset, pad,
                           kernel="interpret")
    assert got.dtype == want.dtype and got.shape == want.shape
    # a lane without a request is read by nobody: it has to be finite
    live = np.ones(batch, bool) if pads is None else np.asarray(pads) < offset
    assert np.isfinite(bits(got)).all()
    assert np.abs(bits(got) - bits(want))[live].max() < TOLERANCE[dtype]
    assert 0.05 < np.abs(bits(want))[live].mean()
    # the cache after the call, every lane's rows: to the bit
    assert (bits(cache) == bits(written)).all()
    assert not (bits(cache) == bits(kv)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("pads,row", [
    ([0, 40, 280], 1), ([S, 40, S], 1), ([40, 280, 4, S], 0), ([300, 40], 1)],
    ids=["between-two-rows", "between-empty-lanes", "first-of-four",
         "after-a-later-starter"])
def test_a_rows_result_is_its_own(pads, row, dtype):
    """The row with the span ``[40, 300)`` alone, and in a batch: its
    output and its written rows are the same bits whatever its
    batch-mates hold and whichever lanes are empty."""
    offset = 300
    q, k, v, kv = operands(SMALL, len(pads), dtype, seed=3)

    def only(x, axis=0):
        return jax.lax.slice_in_dim(x, row, row + 1, axis=axis)

    alone, alone_kv = block_decode.block_decode_attention(
        only(q), only(k), only(v), only(kv, 1), LAYER, offset,
        jnp.asarray([40], jnp.int32), interpret=True)
    batch, batch_kv = block_decode.block_decode_attention(
        q, k, v, kv, LAYER, offset, jnp.asarray(pads, jnp.int32),
        interpret=True)
    assert (bits(only(batch)) == bits(alone)).all()
    assert (bits(only(batch_kv, 1)) == bits(alone_kv)).all()


def test_an_empty_lane_reads_nothing_of_the_cache():
    """A lane whose span is empty sees its own block alone: its output
    is the same whatever the cache holds under it."""
    q, k, v, kv = operands(SMALL, 2, jnp.float32)
    pad = jnp.asarray([S, 0], jnp.int32)
    one, _ = block_decode.block_decode_attention(
        q, k, v, kv, LAYER, 300, pad, interpret=True)
    other, _ = block_decode.block_decode_attention(
        q, k, v, kv.at[:, 0].set(7.0), LAYER, 300, pad, interpret=True)
    assert (bits(one) == bits(other)).all()
    want, _ = BD.attend(q[:1], k[:1], v[:1], L)
    assert np.abs(bits(one[:1]) - bits(want)).max() < 1e-5


def test_the_window_of_a_write_is_whole_tiles():
    assert block_decode.window_rows(4) == 16
    assert block_decode.window_rows(1) == 8
    assert block_decode.window_rows(2) == 16
    assert block_decode.window_rows(10) == 24


def test_the_kernel_refuses_a_cache_that_is_not_fused():
    q, k, v, kv = operands(SMALL, 1, jnp.float32)
    with pytest.raises(ValueError, match="not fused"):
        block_decode.block_decode_attention(q, k, v, kv[..., :64], 0, 8,
                                            interpret=True)


# -- which calls take it: from the shapes and what the engine resolved ----

def _lowered_for_the_chip(t, fresh, kernel, seq=BLOCK_S, head_dim=64):
    """The family's cached forward lowered FOR a TPU (no chip and no
    compile: the text names its Mosaic kernels)."""
    cfg = dataclasses.replace(sdar_moe.CONFIGS["sdar-moe-tiny"],
                              head_dim=head_dim)
    shapes = jax.eval_shape(
        lambda: sdar_moe.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: sdar_moe.make_cache(cfg, 2, seq))
    return jax.jit(
        lambda p, i, c, pad: sdar_moe.forward_with_cache(
            p, i, cfg, c, pad, flash_prefill=fresh, decode_kernel=kernel)
    ).trace(shapes, jax.ShapeDtypeStruct((2, t), jnp.int32), cache,
            jax.ShapeDtypeStruct((2,), jnp.int32)
            ).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("t,fresh,kernel,seq,head_dim,takes_it", [
    (L, False, "device", BLOCK_S, 64, True),
    (L, True, "device", BLOCK_S, 64, False),
    (L, False, None, BLOCK_S, 64, False),
    (2 * L, False, "device", BLOCK_S, 64, False),
    (L, False, "device", BLOCK_S + 64, 64, False),
    (L, False, "device", BLOCK_S, 16, False)],
    ids=["one-block", "a-fresh-cache", "no-kernel-resolved", "two-blocks",
         "a-cache-of-no-whole-blocks", "rows-narrower-than-the-lanes"])
def test_what_runs_follows_the_shapes(t, fresh, kernel, seq, head_dim,
                                      takes_it):
    text = _lowered_for_the_chip(t, fresh, kernel, seq, head_dim)
    # the layers are one scan: one call in its body, or none
    assert text.count(f'kernel_name = "{block_decode.KERNEL_NAME}"') == int(
        takes_it), text.count(block_decode.KERNEL_NAME)
    # the XLA form's whole-layer slice of the cache goes with it
    heads = sdar_moe.CONFIGS["sdar-moe-tiny"].n_kv_head
    slice_of_a_layer = f"-> tensor<1x2x{heads}x{seq}x{2 * head_dim}x"
    assert (slice_of_a_layer in text) == (not takes_it and not fresh)
