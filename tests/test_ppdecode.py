"""Single-program pipelined decode (parallel.ppdecode) vs the engine and
the host-driven runner: token-exact across stage counts, plus the staged
single-program DecodeEngine mode (boundaries=...)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.parallel.pipeline import PipelineRunner
from llm_sharding_demo_tpu.parallel.ppdecode import PipelinedDecoder
from llm_sharding_demo_tpu.parallel.spmd import make_mesh
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine, SamplingConfig


@pytest.fixture(scope="module")
def model():
    cfg = gpt2.GPT2Config(vocab_size=211, n_positions=96, n_embd=64,
                          n_layer=4, n_head=4)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def want(model):
    cfg, params = model
    engine = DecodeEngine(params, cfg, max_seq=64)
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, size=(2, 7))
    return prompt, engine.generate(prompt, 12).tokens


@pytest.mark.parametrize("n_stages", [1, 2, 4])
def test_ppdecode_matches_engine(model, want, n_stages):
    cfg, params = model
    prompt, expected = want
    mesh = make_mesh({"pp": n_stages}, jax.devices()[:n_stages])
    dec = PipelinedDecoder(params, cfg, mesh, max_seq=64)
    np.testing.assert_array_equal(dec.generate(prompt, 12).tokens, expected)


def test_ppdecode_matches_host_driven_runner(model, want):
    """The single-program path ≡ the stage-per-device runner (VERDICT #9:
    same tokens, one dispatch per generate instead of N per token)."""
    cfg, params = model
    prompt, expected = want
    runner = PipelineRunner(params, cfg, [2], max_seq=64,
                            devices=jax.devices()[:2])
    np.testing.assert_array_equal(runner.generate(prompt, 12).tokens, expected)


def test_ppdecode_records_prefill_and_decode_spans_with_ready(model, want):
    """The pipelined path waits for ``first`` and for the tokens, so its
    spans carry their own ends as ready instants: serving's TTFT ends at
    the first token instead of falling back to the whole request."""
    from llm_sharding_demo_tpu.utils import tracing
    cfg, params = model
    prompt, expected = want
    dec = PipelinedDecoder(params, cfg, make_mesh({"pp": 2}, jax.devices()[:2]),
                           max_seq=64)
    tr = tracing.RequestTrace("pp")
    with tracing.use_trace(tr):
        np.testing.assert_array_equal(dec.generate(prompt, 12).tokens,
                                      expected)
    pre, dcd = tr.find("prefill"), tr.find("decode")
    assert pre.t0 < pre.t1 == pre.ready == dcd.t0 < dcd.t1 == dcd.ready
    assert pre.labels == {"batch": 2, "prompt_len": 7, "stages": 2}
    assert dcd.labels == {"batch": 2, "steps": 11, "stages": 2}
    spans = tr.finish().to_dict()["spans"]
    assert [s["name"] for s in spans] == ["prefill", "decode"]
    assert all(s["labels"]["ready_ms"] == pytest.approx(
        s["start_ms"] + s["duration_ms"], abs=0.002) for s in spans)


def test_ppdecode_sampling_deterministic(model):
    cfg, params = model
    mesh = make_mesh({"pp": 2}, jax.devices()[:2])
    dec = PipelinedDecoder(params, cfg, mesh, max_seq=64)
    s = SamplingConfig(mode="sample", temperature=0.6, top_k=40)
    prompt = np.asarray([3, 14, 15])
    a = dec.generate(prompt, 6, sampling=s, key=jax.random.PRNGKey(7))
    b = dec.generate(prompt, 6, sampling=s, key=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_ppdecode_ragged_batch_matches_engine(model):
    """Round-3 composition: ragged left-padded batches decode through the
    ppermute program with per-row pad masks — token-exact vs the
    single-device engine row for row."""
    cfg, params = model
    mesh = make_mesh({"pp": 2}, jax.devices()[:2])
    dec = PipelinedDecoder(params, cfg, mesh, max_seq=64)
    eng = DecodeEngine(params, cfg, max_seq=64)
    ragged = [[5, 6, 7], [1, 2, 3, 4, 5]]
    a = eng.generate(ragged, 8)
    b = dec.generate(ragged, 8)
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_ppdecode_uneven_stages_match_engine(model, want):
    """3 stages over 4 layers: zero-padded stage-major stacking with
    identity masking (partition.stack_stage_params_padded) — the uneven
    partition decodes token-exact."""
    cfg, params = model
    prompt, expected = want
    mesh3 = make_mesh({"pp": 3}, jax.devices()[:3])
    dec = PipelinedDecoder(params, cfg, mesh3, max_seq=64)
    assert dec._valid is not None       # really took the padded path
    np.testing.assert_array_equal(dec.generate(prompt, 12).tokens, expected)


def test_ppdecode_int8_matches_int8_engine(model):
    """Weight-only int8 stage weights: the ppermute program quantizes via
    ops.quant exactly like the engine, so the two int8 streams agree."""
    cfg, params = model
    prompt = np.random.default_rng(3).integers(0, cfg.vocab_size, size=(2, 7))
    mesh = make_mesh({"pp": 2}, jax.devices()[:2])
    dec = PipelinedDecoder(params, cfg, mesh, max_seq=64, dtype="int8")
    eng = DecodeEngine(params, cfg, max_seq=64, dtype="int8",
                       decode_kernel="xla")
    a = eng.generate(prompt, 10)
    b = dec.generate(prompt, 10)
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_staged_engine_matches_plain(model, want):
    """DecodeEngine(boundaries=...) — the fused-staged single-chip mode the
    bench uses for its N-shard-on-1-chip rows — is token-exact, including
    ragged batches."""
    cfg, params = model
    prompt, expected = want
    staged = DecodeEngine(params, cfg, max_seq=64, boundaries=[1, 3])
    np.testing.assert_array_equal(staged.generate(prompt, 12).tokens, expected)
    plain = DecodeEngine(params, cfg, max_seq=64)
    ragged = [[5, 6, 7], [1, 2, 3, 4, 5]]
    a = plain.generate(ragged, 6)
    b = staged.generate(ragged, 6)
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_llama_pipelined_decoder_matches_engine():
    """The shard_map+ppermute decoder covers llama: token-exact vs the
    single-device engine on a 4-stage pp mesh (GQA cache at kv width
    sharded per stage)."""
    import jax
    import numpy as np

    from llm_sharding_demo_tpu.models import llama
    from llm_sharding_demo_tpu.parallel.ppdecode import PipelinedDecoder
    from llm_sharding_demo_tpu.parallel.spmd import make_mesh
    from llm_sharding_demo_tpu.runtime.engine import DecodeEngine

    config = llama.LlamaConfig(vocab_size=97, n_positions=64, n_embd=32,
                               n_layer=4, n_head=4, n_kv_head=2,
                               intermediate_size=48)
    params = llama.init_params(config, jax.random.PRNGKey(0))
    mesh = make_mesh({"pp": 4}, jax.devices()[:4])
    dec = PipelinedDecoder(params, config, mesh, max_seq=48)
    eng = DecodeEngine(params, config, max_seq=48)
    prompt = (np.arange(9, dtype=np.int32) * 11) % config.vocab_size
    want = eng.generate(prompt, max_new_tokens=10)
    got = dec.generate(prompt, max_new_tokens=10)
    np.testing.assert_array_equal(got.tokens, want.tokens)


def test_serving_pp_decode_knob():
    """PP_DECODE=1 serves /generate through the shard_map+ppermute decoder
    (one stage per device on the 8-device test mesh), byte-equal to the
    default runner; misconfigurations refuse at startup."""
    import jax
    import numpy as np
    import pytest

    from llm_sharding_demo_tpu.models import gpt2
    from llm_sharding_demo_tpu.serving.app import create_app
    from llm_sharding_demo_tpu.serving.http import TestClient
    from llm_sharding_demo_tpu.serving.tokenizer import ByteTokenizer
    from llm_sharding_demo_tpu.utils.config import ServingConfig

    config = gpt2.GPT2Config(vocab_size=256, n_positions=64, n_embd=32,
                             n_layer=4, n_head=4)
    params = gpt2.init_params(config, jax.random.PRNGKey(0))
    body = {"prompt": "Hi, ", "max_new_tokens": 6, "mode": "greedy"}

    pp = TestClient(create_app(
        ServingConfig(model_id="t", max_seq=64, boundaries=(2,),
                      pp_decode=True),
        model=(config, params), tokenizer=ByteTokenizer()))
    assert pp.get("/healthz").json()["pp_decode"] is True
    plain = TestClient(create_app(
        ServingConfig(model_id="t", max_seq=64, boundaries=(2,)),
        model=(config, params), tokenizer=ByteTokenizer()))
    assert pp.post("/generate", json=body).json() == \
        plain.post("/generate", json=body).json()

    # round 3: uneven boundaries serve (padded stacking) ...
    uneven = TestClient(create_app(
        ServingConfig(model_id="t", max_seq=64, boundaries=(1,),
                      pp_decode=True),
        model=(config, params), tokenizer=ByteTokenizer()))
    assert uneven.post("/generate", json=body).json() == \
        plain.post("/generate", json=body).json()
    # ... as do int8 + batched pp decode (the composed production shape)
    combo = TestClient(create_app(
        ServingConfig(model_id="t", max_seq=64, boundaries=(2,),
                      pp_decode=True, max_batch=4,
                      inference_dtype="int8"),
        model=(config, params), tokenizer=ByteTokenizer()))
    int8_plain = TestClient(create_app(
        ServingConfig(model_id="t", max_seq=64, boundaries=(2,),
                      inference_dtype="int8"),
        model=(config, params), tokenizer=ByteTokenizer()))
    assert combo.post("/generate", json=body).json() == \
        int8_plain.post("/generate", json=body).json()
    # speculation/prefix/chunked prefill still own the engine's programs
    with pytest.raises(ValueError, match="own the single-device"):
        create_app(ServingConfig(model_id="t", pp_decode=True,
                                 spec_decode=4, boundaries=(2,)),
                   model=(config, params), tokenizer=ByteTokenizer())
