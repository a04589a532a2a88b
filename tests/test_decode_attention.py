"""Flash-decode kernel oracle: the Pallas kernel (interpret mode) must
agree with the fused XLA cached attention op-for-op, and a kernel-mode
engine must reproduce the XLA engine's greedy streams token-for-token.

The kernel is the TPU fast path for single-token decode
(ops.decode_attention); byte-level logit parity is NOT claimed (online
softmax reorders the reduction), so the oracle here is (a) tight allclose
at op level and (b) exact greedy-token equality at engine level on the
oracle seeds — mirroring how the int8 fast path is pinned.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import gpt2, llama
from llm_sharding_demo_tpu.ops.attention import (cached_attention_fused,
                                                 create_fused_cache,
                                                 is_fused_cache)
from llm_sharding_demo_tpu.ops.decode_attention import (BLOCK_S, _call,
                                                        decode_attention,
                                                        eligible, first_block,
                                                        stream_block,
                                                        streamed_blocks)
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype)


# eight rows whose spans start in different blocks of 256 positions
RAGGED = [0, 10, 260, 520, 699, 300, 0, 512]


def _operands(off, vf, hkv, L=3, H=4, hd=64):
    """A batch as wide as ``vf`` (two rows without one) over a cache of
    whole blocks that holds ``off``, written up to ``off``."""
    B = 2 if vf is None else len(vf)
    S = BLOCK_S * (off // BLOCK_S + 1) if off >= 2 * BLOCK_S else 512
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    KV = _rand(ks[0], (L, B, hkv, S, 2 * hd))
    KV = KV.at[..., off:, :].set(0)   # slots >= off unwritten (zeros)
    q = _rand(ks[1], (B, H, 1, hd))
    kn = _rand(ks[2], (B, hkv, 1, hd))
    vn = _rand(ks[3], (B, hkv, 1, hd))
    return KV, q, kn, vn, None if vf is None else jnp.asarray(vf, jnp.int32)


def _under_pad(KV, vf, value):
    """``KV`` with ``value`` in every streamed block that lies wholly
    under its row's pad, all layers and heads."""
    bs = stream_block(KV.shape[1] * KV.shape[2], KV.shape[-1] // 2,
                      KV.dtype.itemsize)
    for b, pad in enumerate(vf):
        KV = KV.at[:, b, :, :min(pad // bs * bs, KV.shape[3])].set(value)
    return KV


@pytest.mark.parametrize("off,vf", [
    (37, None),                       # single partial block
    (255, [0, 5]),                    # block boundary - 1, ragged mask
    (256, None),                      # exactly one full block
    (509, [100, 0]),                  # deep, ragged
    (700, RAGGED),                    # a first block a row
    (700, [260, 300, 520, 256, 699, 511, 512, 600]),   # none in block 0
    (700, [0, 10, 700, 520, 699, 300, 0, 512]),        # one empty span
    (700, [1024, 10, 900, 520, 1024, 300, 700, 512]),  # several
    (300, [512, 300]),                # every span empty: no block visited
    (768, RAGGED),                    # on a block boundary
    (767, RAGGED),                    # and one short of it
])
@pytest.mark.parametrize("hkv", [2, 4])   # GQA (g=2) and MHA (g=1)
def test_kernel_matches_fused_xla(off, vf, hkv):
    KV, q, kn, vn, vf_j = _operands(off, vf, hkv)
    # a lane past ``off`` masks its own token too in the XLA form; the
    # kernel's self term stands, so the lane's output is its new value
    dead = np.zeros(q.shape[0], bool) if vf is None else np.asarray(vf) > off
    g = q.shape[1] // hkv
    for li in (0, KV.shape[0] - 1):
        ref, KV1 = cached_attention_fused(q, kn, vn, KV, li, off, vf_j)
        out, KV2 = decode_attention(q, kn, vn, KV, li, off, vf_j,
                                    interpret=True)
        ref = jnp.where(dead[:, None, None, None],
                        jnp.repeat(vn, g, axis=1), ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)
        # the in-place column write must be byte-identical to the XLA
        # write (values pass through untouched)
        assert jnp.array_equal(KV1, KV2)


@pytest.mark.parametrize("off,vf", [
    (700, RAGGED), (700, [1024, 10, 900, 520, 1024, 300, 700, 512]),
    (768, [256, 767, 512, 300, 768, 0, 511, 513])])
def test_kernel_reads_no_block_under_a_rows_pad(off, vf):
    """NaN in every block that lies wholly under a row's pad (a lane with
    an empty span: every block) and the clean cache's result all the
    same: those blocks are not consumed. (The interpreter's scratch
    starts as NaN too: what a row computes on where it fetched nothing
    is the cleared buffer.)"""
    KV, q, kn, vn, vf_j = _operands(off, vf, hkv=2)
    bad = _under_pad(KV, vf, jnp.nan)
    assert bool(jnp.isnan(bad).any())
    for li in (0, KV.shape[0] - 1):
        want, _ = decode_attention(q, kn, vn, KV, li, off, vf_j,
                                   interpret=True)
        got, KV2 = decode_attention(q, kn, vn, bad, li, off, vf_j,
                                    interpret=True)
        assert jnp.array_equal(want, got)
        _, KV1 = cached_attention_fused(q, kn, vn, bad, li, off, vf_j)
        np.testing.assert_array_equal(np.asarray(KV1), np.asarray(KV2))


@pytest.mark.parametrize("off,vf", [
    (700, RAGGED), (700, [260, 300, 520, 256, 699, 511, 512, 600]),
    (767, [1024, 10, 900, 520, 1024, 300, 700, 512])])
def test_skipped_blocks_change_no_bit(off, vf):
    """The rows' outputs against the stream of the WHOLE rectangle (what
    the kernel read before spans reached it): the same call with all-zero
    pads for the stream and the rows' pads for the score mask, on a cache
    whose pad region is zero. A block that was not read is one whose
    every score was masked: bit for bit the same."""
    hkv = 2
    KV, q, kn, vn, vf_j = _operands(off, vf, hkv)
    KV = _under_pad(KV, vf, 0.0)
    B, H, _, hd = q.shape
    args = (q.reshape(B, hkv, H // hkv, hd), kn, vn)
    mask = jnp.repeat(vf_j, hkv)[:, None, None]
    meta = jnp.asarray([1, off], jnp.int32)
    spans, _ = _call(*args, vf_j, mask, KV, meta, interpret=True)
    whole, _ = _call(*args, jnp.zeros_like(vf_j), mask, KV, meta,
                     interpret=True)
    assert jnp.array_equal(spans, whole)


def test_first_block_and_streamed_blocks_count_the_fetches():
    """Which (row, block) pairs the stream fetches, as the kernel and the
    scheduler's counter both reckon it."""
    # a lane with an empty span: no block; [520, 700) in 256-blocks: one
    assert list(streamed_blocks([700, 1024, 520, 0, 699, 256], 700, 256)
                ) == [0, 0, 1, 3, 1, 2]
    assert first_block(520, 700, 256) == 2
    assert first_block(700, 700, 256) == 3 == first_block(2048, 700, 256)
    # the loop's first block is the smallest of the rows' first blocks
    assert min(first_block(np.asarray([520, 300, 2048]), 700, 256)) == 1
    # on a block boundary and one past it; nothing at depth 0
    assert list(streamed_blocks([0, 255, 256, 511], 512, 256)) == [2, 2, 1, 1]
    assert list(streamed_blocks([0, 255, 256, 511], 513, 256)) == [3, 3, 2, 2]
    assert list(streamed_blocks([0, 5], 0, 256)) == [0, 0]
    # a call's steps at once: [rows, steps]
    np.testing.assert_array_equal(
        streamed_blocks([0, 300, 2048], np.arange(510, 514), 256),
        [[2, 2, 2, 3], [1, 1, 1, 2], [0, 0, 0, 0]])
    # narrower blocks (a wide batch's stream)
    assert list(streamed_blocks([0, 130, 700], 700, 128)) == [6, 5, 0]


def test_engine_kernel_greedy_stream_matches_xla_gpt2():
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=1024, n_embd=64,
                          n_layer=2, n_head=1)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(1))
    p = np.asarray([[5, 9, 2, 77, 30]])
    xla = DecodeEngine(params, cfg, max_seq=300, decode_kernel="xla")
    ker = DecodeEngine(params, cfg, max_seq=300, decode_kernel="interpret")
    assert ker._decode_kernel == "interpret"      # eligibility engaged
    assert is_fused_cache(ker._fresh_cache(1))
    a = xla.generate(p, 40)
    b = ker.generate(p, 40)
    assert list(a.tokens[0]) == list(b.tokens[0])
    # ragged batch through the kernel's per-row pad mask
    ar = xla.generate([[5, 9, 2, 77, 30], [42, 3]], 24)
    br = ker.generate([[5, 9, 2, 77, 30], [42, 3]], 24)
    assert np.array_equal(ar.tokens, br.tokens)


def test_engine_kernel_greedy_stream_matches_xla_llama_gqa():
    cfg = llama.LlamaConfig(vocab_size=211, n_positions=1024, n_embd=128,
                            n_layer=2, n_head=2, n_kv_head=1,
                            intermediate_size=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    p = np.asarray([[5, 9, 2, 77, 30]])
    a = DecodeEngine(params, cfg, max_seq=300,
                     decode_kernel="xla").generate(p, 40)
    b = DecodeEngine(params, cfg, max_seq=300,
                     decode_kernel="interpret").generate(p, 40)
    assert list(a.tokens[0]) == list(b.tokens[0])


def test_kernel_mode_composes_with_spec_and_chunked_prefill():
    """Multi-token steps (chunked prefill, speculative verify windows) on
    a fused cache take the fused XLA path; streams must stay exact."""
    from llm_sharding_demo_tpu.runtime.spec_decode import SpecDecodeEngine
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=1024, n_embd=64,
                          n_layer=2, n_head=1)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(3))
    prompt = np.asarray([[7, 7, 3, 7, 7, 3, 7, 7]])
    plain = DecodeEngine(params, cfg, max_seq=300, decode_kernel="xla")
    want = list(plain.generate(prompt, 30).tokens[0])

    chunked = DecodeEngine(params, cfg, max_seq=300, prefill_chunk=4,
                           decode_kernel="interpret")
    got = chunked.generate(prompt, 30)
    assert list(got.row_tokens(0)) == want

    spec = SpecDecodeEngine(params, cfg, max_seq=300, draft_len=4)
    assert spec._eng._decode_kernel is None  # spec pins xla on both sides
    sp = spec.generate(prompt, 30)
    assert list(sp.tokens[0]) == want


@pytest.mark.parametrize("family", ["gpt2", "llama-gqa"])
def test_staged_engine_with_kernel_matches_xla(family):
    """DecodeEngine(boundaries=...) + the decode kernel: per-stage fused
    caches, kernel invoked per stage (``parallel.partition``'s two stage
    runners) — streams match the XLA engine, solo and ragged."""
    if family == "gpt2":
        cfg = gpt2.GPT2Config(vocab_size=211, n_positions=1024, n_embd=64,
                              n_layer=4, n_head=1)
        params = gpt2.init_params(cfg, jax.random.PRNGKey(5))
    else:
        cfg = llama.LlamaConfig(vocab_size=211, n_positions=1024,
                                n_embd=128, n_layer=4, n_head=2,
                                n_kv_head=1, intermediate_size=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(6))
    xla = DecodeEngine(params, cfg, max_seq=300, decode_kernel="xla")
    staged = DecodeEngine(params, cfg, max_seq=300, boundaries=[1, 3],
                          decode_kernel="interpret")
    assert staged._decode_kernel == "interpret"
    assert is_fused_cache(staged._fresh_cache(1)[0])
    for prompts, n in (([[5, 9, 2, 77, 30]], 24), ([[5, 9, 2], [42]], 16)):
        assert np.array_equal(xla.generate(prompts, n).tokens,
                              staged.generate(prompts, n).tokens)


def test_eligibility_gates():
    assert eligible(BLOCK_S, 64, 1)
    assert not eligible(BLOCK_S, 64, 2)        # multi-token query
    assert not eligible(BLOCK_S - 1, 64, 1)    # unaligned cache
    assert not eligible(BLOCK_S, 8, 1)         # tiny head dim
    # an EXPLICIT kernel request on ineligible geometry must refuse
    # loudly (silent fallback is reserved for "auto" — a config slip
    # would otherwise stop exercising the kernel unnoticed)
    cfg = gpt2.CONFIGS["tiny-gpt2"]            # hd == 1
    with pytest.raises(ValueError, match="ineligible"):
        DecodeEngine(gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                     cfg, max_seq=64, decode_kernel="interpret")
    # "auto" on the same geometry quietly keeps the XLA engine
    eng = DecodeEngine(gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                       cfg, max_seq=64, decode_kernel="auto")
    assert eng._decode_kernel is None
    assert not is_fused_cache(eng._fresh_cache(1))


def test_fp32_parity_mode_never_takes_the_kernel(monkeypatch):
    """BASELINE.json's fp32 greedy-parity mode must stay on the
    byte-pinned XLA path even on a TPU backend where "auto" would
    otherwise engage the (allclose-not-bitwise) kernel."""
    import llm_sharding_demo_tpu.runtime.engine as eng_mod
    monkeypatch.setattr(eng_mod.jax, "default_backend", lambda: "tpu")
    cfg = gpt2.GPT2Config(vocab_size=97, n_positions=1024, n_embd=64,
                          n_layer=2, n_head=1)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    fp32 = DecodeEngine(params, cfg, max_seq=300, dtype=jnp.float32)
    assert fp32._decode_kernel is None          # parity mode -> XLA
    bf16 = DecodeEngine(params, cfg, max_seq=300, dtype=jnp.bfloat16)
    assert bf16._decode_kernel == "device"      # fast path -> kernel


# -- the one place the choice lives: DecodeEngine.__init__ --------------------

def _gpt2(n_embd=64, n_head=1):
    cfg = gpt2.GPT2Config(vocab_size=97, n_positions=1024, n_embd=n_embd,
                          n_layer=1, n_head=n_head)
    return cfg, gpt2.init_params(cfg, jax.random.PRNGKey(0))


def _latent():
    from llm_sharding_demo_tpu.models import latent_moe
    cfg = latent_moe.LatentMoEConfig(
        vocab_size=97, n_positions=512, n_embd=32, n_layer=2, n_head=2,
        q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=32,
        moe_intermediate_size=8, n_routed_total=4, n_routed_experts=4,
        n_experts_per_tok=2)
    return cfg, latent_moe.init_params(cfg, jax.random.PRNGKey(0))


def _tp_mesh():
    from llm_sharding_demo_tpu.parallel.spmd import make_mesh
    return make_mesh({"tp": 2}, jax.devices()[:2])


# (mode, model, dtype, backend, mesh) -> (_decode_kernel, _cache_seq) at
# max_seq 300, or the refusal's words. The dtype is spelled as the server
# passes it, a string.
RESOLUTION = {
    "auto-float32-on-a-tpu": (
        ("auto", _gpt2, "float32", "tpu", None), (None, 300)),
    "auto-bfloat16-off-the-tpu": (
        ("auto", _gpt2, "bfloat16", "cpu", None), (None, 300)),
    "xla-bfloat16-on-a-tpu": (
        ("xla", _gpt2, "bfloat16", "tpu", None), (None, 300)),
    "layer-eligible": (
        ("layer", _gpt2, "bfloat16", "cpu", None), ("device", 2 * BLOCK_S)),
    "interpret": (
        ("interpret", _gpt2, "float32", "cpu", None),
        ("interpret", 2 * BLOCK_S)),
    "layer-narrow-heads": (
        ("layer", lambda: _gpt2(n_embd=32, n_head=2), "bfloat16", "cpu",
         None), "geometry is ineligible"),
    "layer-under-a-mesh": (
        ("layer", lambda: _gpt2(n_head=2), "bfloat16", "cpu", _tp_mesh),
        "does not compose with a mesh"),
    "layer-latent-family": (
        ("layer", _latent, "bfloat16", "cpu", None), ("device", 2 * BLOCK_S)),
}


@pytest.mark.parametrize("case", list(RESOLUTION))
def test_decode_kernel_resolution(monkeypatch, case):
    """The four modes, what each resolves to, and what the explicit ones
    refuse: ``_decode_kernel`` is ``None`` (XLA, the cache as long as
    asked), ``"device"`` or ``"interpret"`` (the cache in whole blocks)."""
    import llm_sharding_demo_tpu.runtime.engine as eng_mod
    (mode, model, dtype, backend, mesh), want = RESOLUTION[case]
    monkeypatch.setattr(eng_mod.jax, "default_backend", lambda: backend)
    cfg, params = model()

    def build():
        return DecodeEngine(params, cfg, max_seq=300, dtype=dtype,
                            decode_kernel=mode,
                            mesh=mesh() if mesh else None)
    if isinstance(want, str):
        with pytest.raises(ValueError, match=want):
            build()
        return
    eng = build()
    assert (eng._decode_kernel, eng._cache_seq) == want
    cache = eng._fresh_cache(1)
    if model is _latent:
        # the family's own rule (its head width fails the two-plane
        # one), and its own cache layout under the kernel
        assert not eligible(eng._cache_seq, cfg.head_dim, 1)
        assert not is_fused_cache(cache)
        assert cache.k.shape == (cfg.n_layer, 1, 1, 2 * BLOCK_S,
                                 cfg.cache_lanes)
    else:
        assert is_fused_cache(cache) == (want[0] is not None)


@pytest.mark.parametrize("name", ["mega", "mega-interpret",
                                  "layer-interpret"])
def test_removed_kernel_modes_are_unknown_names(name):
    """Names of modes that no longer exist are refused like any unknown
    name, with the modes there are."""
    cfg, params = _gpt2()
    with pytest.raises(ValueError) as e:
        DecodeEngine(params, cfg, max_seq=300, decode_kernel=name)
    assert repr(name) in str(e.value)
    assert "('auto', 'xla', 'layer', 'interpret')" in str(e.value)


# -- what the kernel path owes every composition, on "interpret" --------------

def _scaled(n_embd=128, n_head=2):
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=1024, n_embd=n_embd,
                          n_layer=2, n_head=n_head)
    return cfg, jax.tree.map(lambda x: x * 4.0,
                             gpt2.init_params(cfg, jax.random.PRNGKey(1)))


def test_int8_weights_logits_allclose_across_xla_and_kernel():
    """Weight-only int8 under the kernel: a decode step's logits agree
    with the XLA path's (token equality across paths is not promised
    for int8; the engine's documented contract)."""
    cfg, params = _scaled()
    p = jnp.asarray([[5, 9, 2, 77, 30]])
    logits = {}
    for mode in ("xla", "interpret"):
        eng = DecodeEngine(params, cfg, max_seq=300, dtype="int8",
                           decode_kernel=mode)
        _, cache = eng._prefill(eng._run_params(), p, None)
        step, _ = eng._forward_cached(
            eng._run_params(), jnp.asarray([[100]], jnp.int32), cache, None)
        logits[mode] = np.asarray(step[0, -1], np.float32)
    np.testing.assert_allclose(logits["interpret"], logits["xla"],
                               rtol=0.08, atol=0.35)


def test_kernel_mode_samples_the_xla_stream_after_chunked_prefill():
    """Seeded sampling rides the same per-row keys whichever path
    computes the logits, a chunked prefill and a 1-token prompt (whose
    prefill is itself a kernel step, at depth 0) included."""
    from llm_sharding_demo_tpu.runtime.engine import SamplingConfig
    cfg, params = _scaled()
    prompt = np.arange(23).reshape(1, 23) % cfg.vocab_size
    s = SamplingConfig(mode="sample", temperature=0.7, top_k=30)
    k = jax.random.PRNGKey(5)
    xla = DecodeEngine(params, cfg, max_seq=300, decode_kernel="xla")
    ker = DecodeEngine(params, cfg, max_seq=300, prefill_chunk=8,
                       decode_kernel="interpret")
    assert ker._decode_kernel == "interpret"
    for p in (prompt, np.asarray([[7]])):
        want = xla.generate(p, 16, sampling=s, key=k)
        got = ker.generate(p, 16, sampling=s, key=k)
        assert list(want.tokens[0]) == list(got.row_tokens(0))
