"""The held experts' tiles as one Pallas kernel (``ops.expert_ffn.
held_expert_tiles``), interpreted on the CPU, against the XLA loop it
stands in for.

The five expert cells' ``(d, f)`` at an eighth (the proportions that
decide the chunk and the form: ``k-exaone``'s expert is the one that
does not fit the double buffer whole), sixteen held experts of 32 or 64,
float32 so that the two sides differ by the order of their sums alone.
The kernel's budgets are cut by the same 64 (and doubled for float32's
4 bytes), so that every rule fires at test size as it does at the
cells'.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_demo_tpu.ops import expert_ffn

# (d, f) of benchmark/configs/*.json
CELLS = {
    "joyai-llm-flash-ep16": (2048, 768),
    "qwen3-next-80b-ep32": (2048, 512),
    "k-exaone-236b-ep8": (6144, 2048),
    "kimi-linear-48b-ep16": (2304, 1024),
    "sdar-30b-a3b-ep8": (2048, 768),
}
CUT = 8
HELD, LAYERS, TOP_K = 16, 2, 4
# tokens of a call: one tile an expert (pairs fit a tile; tokens do,
# pairs do not: the kernel's form still) and the general form
FORMS = {"pairs-fit-a-tile": 8, "tokens-fit-a-tile": 40, "general": 160}


@pytest.fixture(scope="module", autouse=True)
def budgets_at_test_size():
    patch = pytest.MonkeyPatch()
    patch.setattr(expert_ffn, "_WEIGHT_VMEM",
                  2 * expert_ffn._WEIGHT_VMEM // CUT ** 2)
    patch.setattr(expert_ffn, "_STEP_BYTES",
                  2 * expert_ffn._STEP_BYTES // CUT ** 2)
    yield patch
    patch.undo()
    _ffn.clear_cache()
    expert_ffn.held_expert_tiles.clear_cache()


# traced once a (shapes, first, kernel): the budgets above are read
# while tracing
_ffn = jax.jit(expert_ffn.held_experts_ffn,
               static_argnames=("first", "kernel"))


def _stacks(cell, dtype=jnp.float32, seed=0):
    d, f = (n // CUT for n in CELLS[cell])
    rs = np.random.RandomState(seed)

    def normal(shape, fan_in):
        return jnp.asarray(rs.randn(*shape) / np.sqrt(fan_in), dtype)
    return (normal((LAYERS, HELD, d, f), d), normal((LAYERS, HELD, d, f), d),
            normal((LAYERS, HELD, f, d), f))


def _choices(case, t, rs):
    """``(ids [t, TOP_K], first)``: which experts the tokens chose and
    the first one this chip holds."""
    if case == "none-hit":                    # all held elsewhere
        return HELD + np.stack([rs.permutation(HELD)[:TOP_K]
                                for _ in range(t)]), 0
    if case == "one-hit":
        ids = HELD + np.stack([rs.permutation(HELD)[:TOP_K]
                               for _ in range(t)])
        ids[::3, 1] = 5
        return ids, 0
    if case == "all-hit":
        ids = np.stack([(r + np.arange(TOP_K) * 4) % HELD for r in range(t)])
        return ids, 0
    if case == "one-crowded":                 # every token on expert 3
        ids = np.stack([rs.permutation(HELD)[:TOP_K] for _ in range(t)])
        ids[ids == 3] = 9
        ids[:, 2] = 3
        return ids, 0
    assert case == "some-elsewhere"           # held: 16 .. 31 of 64
    return np.stack([rs.permutation(4 * HELD)[:TOP_K]
                     for _ in range(t)]), HELD


def _both(cell, form, case, dtype=jnp.float32):
    t = FORMS[form]
    rs = np.random.RandomState(len(case) + t)
    gate, up, down = _stacks(cell, dtype)
    ids, first = _choices(case, t, rs)
    x = jnp.asarray(rs.randn(t, gate.shape[2]), dtype)
    w = jnp.asarray(rs.rand(t, TOP_K), jnp.float32)
    args = (x, jnp.asarray(ids, jnp.int32), w, gate, up, down, 1)
    return (_ffn(*args, first=first, kernel=None),
            _ffn(*args, first=first, kernel="interpret"),
            np.asarray(ids) - first)


@pytest.mark.parametrize("case", ["none-hit", "one-hit", "all-hit",
                                  "one-crowded", "some-elsewhere"])
@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_kernel_agrees_with_the_loop(cell, form, case):
    (loop, loop_counts), (kernel, counts), local = _both(cell, form, case)
    held = local[(local >= 0) & (local < HELD)]
    want = np.bincount(held, minlength=HELD)
    assert np.array_equal(counts, want) and np.array_equal(loop_counts, want)
    if case == "none-hit":
        assert not np.asarray(kernel).any() and not want.any()
    if case == "one-crowded":
        assert want[3] == FORMS[form]         # two tiles of it at 160
    scale = max(float(jnp.max(jnp.abs(loop))), 1.0)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(loop),
                               atol=1e-5 * scale, rtol=0)


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("cell", ["sdar-30b-a3b-ep8", "k-exaone-236b-ep8"])
def test_in_bfloat16_the_two_differ_by_its_rounding(cell, form):
    """The loop rounds each product to bfloat16 where the kernel keeps
    float32 up to the down-projection's operand: a few units of 2**-8
    of the result's size, and no more."""
    (loop, _), (kernel, _), _ = _both(cell, form, "all-hit", jnp.bfloat16)
    assert kernel.dtype == loop.dtype == jnp.bfloat16
    loop, kernel = (np.asarray(a, np.float32) for a in (loop, kernel))
    assert np.max(np.abs(kernel - loop)) <= 4 * 2.0 ** -8 * np.max(
        np.abs(loop))


def test_what_runs_follows_the_shapes(budgets_at_test_size, monkeypatch):
    """The chunk of ``f`` and the form, at the cells' own sizes: read
    off ``gate.shape`` and the dtype, no family's name."""
    for name in ("_WEIGHT_VMEM", "_STEP_BYTES"):     # the module's own
        monkeypatch.setattr(expert_ffn, name,
                            getattr(expert_ffn, name) * CUT ** 2 // 2)
    chunks = {cell: expert_ffn.chunk_of(d, f, 2)
              for cell, (d, f) in CELLS.items()}
    assert chunks == {"joyai-llm-flash-ep16": 256, "qwen3-next-80b-ep32": 256,
                      "k-exaone-236b-ep8": 128, "kimi-linear-48b-ep16": 256,
                      "sdar-30b-a3b-ep8": 256}
    for cell, (d, f) in CELLS.items():
        assert f % chunks[cell] == 0
        assert 2 * 3 * d * chunks[cell] * 2 <= expert_ffn._WEIGHT_VMEM
    assert {cell for cell, (d, f) in CELLS.items()
            if not expert_ffn.fetched_whole(d, f, 2)} == {"k-exaone-236b-ep8"}
    assert expert_ffn.chunk_of(64, 24, 4) == 24       # not whole lanes


def test_the_general_form_keeps_the_loop_for_an_expert_too_large():
    """``k-exaone``'s expert does not fit the double buffer whole: its
    prefills keep the loop (no kernel in the traced program), its decode
    steps take the kernel in chunks of ``f``."""
    gate, up, down = _stacks("k-exaone-236b-ep8")
    d, f = gate.shape[2:]
    assert not expert_ffn.fetched_whole(d, f, 4)
    assert expert_ffn.chunk_of(d, f, 4) < f

    def traced(t):
        return str(jax.make_jaxpr(
            lambda x, ids, w: expert_ffn.held_experts_ffn(
                x, ids, w, gate, up, down, 0, 0, kernel="interpret"))(
                    jnp.zeros((t, d)), jnp.zeros((t, TOP_K), jnp.int32),
                    jnp.zeros((t, TOP_K))))
    assert "pallas_call" not in traced(FORMS["general"])
    assert "pallas_call" in traced(FORMS["tokens-fit-a-tile"])
    small = _stacks("sdar-30b-a3b-ep8")
    assert expert_ffn.fetched_whole(*small[0].shape[2:], 4)
