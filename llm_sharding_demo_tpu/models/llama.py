"""LLaMA-family model (RMSNorm + RoPE + SwiGLU + GQA) as pure JAX.

Second dense model family, beyond reference parity (the reference serves
GPT-2 only, reference server.py:41). Same pure-pytree design and public
surface as ``models.gpt2`` — ``init_params`` / ``forward`` /
``forward_with_cache`` / ``make_cache`` over stacked ``[n_layer, ...]``
block leaves scanned by ``lax.scan`` — so the decode engine, speculative
decoding, serving, quantization, and checkpointing all work via the
family's declaration (``FAMILY`` below; ``models.family``) without
knowing the architecture. Differences from GPT-2 that matter here:

- **RoPE instead of a learned position table** (``ops.rope``): positions
  are computed, not gathered, so context length is bounded only by cache
  memory — this family is the framework's genuine long-context path
  (GPT-2 hard-stops at 1024 learned positions, the reference's ceiling).
- **Grouped-query attention**: ``n_kv_head <= n_head``; the KV cache is
  allocated at kv-head width (``ops.attention`` handles grouped q/kv
  natively), shrinking decode's cache traffic by ``n_head/n_kv_head``.
- **RMSNorm** (no biases anywhere) and **SwiGLU** MLP
  (``down(silu(gate(x)) * up(x))``).
- **Untied LM head** (HF ``LlamaForCausalLM`` default).

Numerics mirror HF ``modeling_llama`` (fp32 norm statistics, fp32 rotary
angles, fp32 logits) so the logit-parity oracle
(tests/test_llama.py) pins conversion + forward exactly.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import (KVCache, cached_attention_inplace,
                             causal_attention, merge_heads, split_heads,
                             write_kv_layer)
from ..ops.layers import linear, rms_norm
from ..ops.rope import apply_rope
from . import stack
from .family import Family
from .stack import embed as _embed  # parallel/ and training/ call it so

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Architecture hyperparameters (mirrors the HF ``LlamaConfig`` fields
    we use; ``n_*`` naming kept consistent with ``GPT2Config``)."""

    vocab_size: int = 32000
    n_positions: int = 4096          # cache/serving bound, NOT a table size
    n_embd: int = 768                # hidden_size
    n_layer: int = 12
    n_head: int = 12
    n_kv_head: int = 12              # < n_head => grouped-query attention
    intermediate_size: int = 2048
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # "xla" | "pallas" | "ring" — same contract as GPT2Config. pallas/ring
    # run on full-width K/V (GQA heads repeated first); the no-repeat
    # grouped path is the default xla einsum.
    attention_impl: str = "xla"

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    def __post_init__(self):
        if self.n_embd % self.n_head != 0:
            raise ValueError(
                f"n_embd={self.n_embd} not divisible by n_head={self.n_head}")
        if self.n_head % self.n_kv_head != 0:
            raise ValueError(f"n_head={self.n_head} not a multiple of "
                             f"n_kv_head={self.n_kv_head}")
        if self.attention_impl not in ("xla", "pallas", "ring"):
            raise ValueError(
                f"attention_impl={self.attention_impl!r} not xla|pallas|ring")


# Static-analysis/planner contract (tools/graftcheck/costmodel): the
# family's sharding facts — see ``models.gpt2.SHARDING_DESCRIPTOR`` for
# the schema. The GQA head-ratio lives in ``tp_divisors``: a tensor axis
# must divide BOTH head counts (attention shards whole q heads AND whole
# kv heads; a tp that splits a kv group would replicate cache writes),
# which is exactly the engine's own TP_DECODE guard. The derived
# PartitionSpec tree is pinned equal to ``spmd.llama_param_pspecs`` by
# tests/test_graftplan.py.
SHARDING_DESCRIPTOR = {
    "column": ("blocks.attn.wq", "blocks.attn.wk", "blocks.attn.wv",
               "blocks.mlp.gate", "blocks.mlp.up"),
    "row": ("blocks.attn.wo", "blocks.mlp.down"),
    "expert": (),
    "tp_divisors": ("n_head", "n_kv_head"),
    # kvp (KV-partition, Helix-style) shards the PAGED POOL's kv-head
    # dim only — query heads replicate, so unlike tp the GQA ratio does
    # not constrain it; only the kv head count must divide
    "kvp_divisors": ("n_kv_head",),
    "ep_divisors": (),
}


# "llama-124m" is the GPT-2-124M-comparable geometry used by the bench;
# "llama-tiny" a test/smoke size. Both use GQA (n_kv_head < n_head) so the
# family's distinguishing feature is always exercised.
CONFIGS: Dict[str, LlamaConfig] = {
    "llama-tiny": LlamaConfig(vocab_size=256, n_positions=512, n_embd=32,
                              n_layer=2, n_head=4, n_kv_head=2,
                              intermediate_size=64),
    "llama-124m": LlamaConfig(vocab_size=32000, n_positions=4096, n_embd=768,
                              n_layer=12, n_head=12, n_kv_head=4,
                              intermediate_size=2048),
}


def init_params(config: LlamaConfig, key: jax.Array,
                dtype=jnp.float32) -> Params:
    """Random-init parameters; stacked ``[n_layer, ...]`` block leaves.

    All matmul weights live under ``.../kernel`` in the ``[in, out]``
    layout so ``ops.quant.quantize_params`` and the serving int8 path
    apply unchanged.
    """
    d, l = config.n_embd, config.n_layer
    hd, i = config.head_dim, config.intermediate_size
    kv = config.n_kv_head * hd
    std = 0.02
    keys = jax.random.split(key, 9)

    def normal(k, shape):
        return (jax.random.normal(k, shape) * std).astype(dtype)

    return {
        "wte": normal(keys[0], (config.vocab_size, d)),
        "blocks": {
            "ln_attn": {"scale": jnp.ones((l, d), dtype)},
            "attn": {
                "wq": {"kernel": normal(keys[1], (l, d, d))},
                "wk": {"kernel": normal(keys[2], (l, d, kv))},
                "wv": {"kernel": normal(keys[3], (l, d, kv))},
                "wo": {"kernel": normal(keys[4], (l, d, d))},
            },
            "ln_mlp": {"scale": jnp.ones((l, d), dtype)},
            "mlp": {
                "gate": {"kernel": normal(keys[5], (l, d, i))},
                "up": {"kernel": normal(keys[6], (l, d, i))},
                "down": {"kernel": normal(keys[7], (l, i, d))},
            },
        },
        "ln_f": {"scale": jnp.ones((d,), dtype)},
        "lm_head": {"kernel": normal(keys[8], (d, config.vocab_size))},
    }


def swiglu(mlp: Params, m: jnp.ndarray) -> jnp.ndarray:
    """``down(silu(gate(m)) * up(m))`` over ``{gate, up, down}`` kernels."""
    return linear(jax.nn.silu(linear(m, mlp["gate"]["kernel"]))
                  * linear(m, mlp["up"]["kernel"]), mlp["down"]["kernel"])


def pre_norm_block(block_params: Params, h: jnp.ndarray, eps: float,
                   mixer, ffn, norm=rms_norm):
    """One pre-norm residual block assembled from its two halves:
    ``h += mixer(norm(h))`` then ``h += ffn(norm(h))``. ``mixer(a)``
    returns ``(out, state)`` (its updated cache, whatever form that
    takes), ``ffn(m)`` returns ``out``; both read their own weights from
    a closure. Every RMSNorm family's block is this with another mixer
    or feed-forward (``models.latent_moe`` runs two kinds of layer in
    one stack through it; ``models.gdn_moe`` brings its own ``norm``,
    the ``(1 + w)`` form). Returns ``(h, state)``."""
    out, state = mixer(norm(h, block_params["ln_attn"]["scale"], eps))
    h = h + out
    return h + ffn(norm(h, block_params["ln_mlp"]["scale"], eps)), state


def _block(block_params: Params, h: jnp.ndarray, config: LlamaConfig,
           cos: jnp.ndarray, sin: jnp.ndarray,
           cache_k: Optional[jnp.ndarray], cache_v: Optional[jnp.ndarray],
           offset, k_valid_from: Optional[jnp.ndarray] = None,
           mesh=None, flash_prefill: bool = False, layer_idx=None,
           decode_kernel: Optional[str] = None,
           ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray],
                      Optional[jnp.ndarray]]:
    """One pre-norm llama block; optionally reads/writes the KV cache.

    ``cache_k``/``cache_v`` are the FULL stacked ``[L, B, Hkv, max_seq,
    hd]`` buffers with ``layer_idx`` selecting this block's slice — the
    in-place carry pattern (see ``ops.attention.write_kv_layer``)."""
    attn = block_params["attn"]

    def mixer(a):
        q = split_heads(linear(a, attn["wq"]["kernel"]), config.n_head)
        k = split_heads(linear(a, attn["wk"]["kernel"]), config.n_kv_head)
        v = split_heads(linear(a, attn["wv"]["kernel"]), config.n_kv_head)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if cache_k is None:
            impl = config.attention_impl

            def repeat_kv(k, v):
                # the pallas/ring kernels want equal q/kv head counts; repeat
                # (HF repeat_kv ordering) — a training-path materialization,
                # the cached decode path below never repeats, and neither do
                # the XLA fallbacks (grouped einsum handles GQA natively)
                g = config.n_head // config.n_kv_head
                return ((jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1))
                        if g > 1 else (k, v))

            if impl == "pallas":
                from ..ops.flash_attention import (flash_attention,
                                                   flash_profitable)
                if flash_profitable(q.shape[2]):
                    kf, vf = repeat_kv(k, v)
                    attn_out = flash_attention(
                        q, kf, vf, interpret=jax.default_backend() != "tpu")
                else:
                    # below the measured crossover the XLA einsum wins
                    attn_out = causal_attention(q, k, v, q_offset=offset,
                                                k_valid_from=k_valid_from)
            elif impl == "ring":
                from ..ops.ring_attention import ring_attention
                if mesh is None:
                    raise ValueError("attention_impl='ring' needs a mesh with "
                                     "an 'sp' axis: pass forward(..., mesh=mesh)")
                if k_valid_from is not None:
                    raise NotImplementedError(
                        "ring attention does not support ragged batches")
                kf, vf = repeat_kv(k, v)
                attn_out = ring_attention(q, kf, vf, mesh, axis="sp")
            else:
                attn_out = causal_attention(q, k, v, q_offset=offset,
                                            k_valid_from=k_valid_from)
            new_ck = new_cv = None
        elif decode_kernel is not None:
            # FUSED cache mode (ops.attention.create_fused_cache): cache_k is
            # the fused [L, B, Hkv, Smax, 2*hd] buffer, cache_v a placeholder
            from ..ops.attention import (cached_attention_fused,
                                         write_kv_layer_fused)
            if flash_prefill:
                from ..ops.flash_attention import flash_attention
                new_ck = write_kv_layer_fused(cache_k, k, v, layer_idx, offset)
                g = config.n_head // config.n_kv_head
                kf = jnp.repeat(k, g, axis=1) if g > 1 else k
                vf = jnp.repeat(v, g, axis=1) if g > 1 else v
                attn_out = flash_attention(
                    q, kf, vf, interpret=jax.default_backend() != "tpu")
            elif q.shape[2] == 1:
                # GQA-native flash-decode kernel: g = n_head/n_kv_head query
                # heads ride each kv head's block stream, K/V never repeat
                from ..ops.decode_attention import decode_attention
                attn_out, new_ck = decode_attention(
                    q, k, v, cache_k, layer_idx, offset, k_valid_from,
                    interpret=decode_kernel == "interpret")
            else:
                attn_out, new_ck = cached_attention_fused(
                    q, k, v, cache_k, layer_idx, offset, k_valid_from)
            new_cv = cache_v
        elif flash_prefill:
            # fresh-cache prefill (offset 0, no pad): cached attention is
            # plain causal attention over the new K/V — write the cache at
            # kv-head width, run the flash kernel on repeated heads (the
            # kernel wants equal q/kv head counts; a one-off prefill
            # materialization, decode still reads the narrow cache)
            from ..ops.flash_attention import flash_attention
            new_ck, new_cv = write_kv_layer(cache_k, cache_v, k, v, layer_idx,
                                            offset)
            g = config.n_head // config.n_kv_head
            kf = jnp.repeat(k, g, axis=1) if g > 1 else k
            vf = jnp.repeat(v, g, axis=1) if g > 1 else v
            attn_out = flash_attention(
                q, kf, vf, interpret=jax.default_backend() != "tpu")
        else:
            attn_out, new_ck, new_cv = cached_attention_inplace(
                q, k, v, cache_k, cache_v, layer_idx, offset, k_valid_from)
        return (linear(merge_heads(attn_out), attn["wo"]["kernel"]),
                (new_ck, new_cv))

    h, (new_ck, new_cv) = pre_norm_block(
        block_params, h, config.rms_norm_eps, mixer,
        lambda m: swiglu(block_params["mlp"], m))
    return h, new_ck, new_cv


def _angles(config: LlamaConfig, seq_len: int, offset,
            pad: Optional[jnp.ndarray]):
    return stack.angles(config.head_dim, config.rope_theta, seq_len, offset,
                        pad)


def _final(params: Params, h: jnp.ndarray, config: LlamaConfig) -> jnp.ndarray:
    return stack.head(params, h, config.rms_norm_eps)


def apply_blocks(blocks: Params, h: jnp.ndarray, config: LlamaConfig,
                 cos: jnp.ndarray, sin: jnp.ndarray,
                 cache: Optional[KVCache] = None, remat: bool = False,
                 k_valid_from: Optional[jnp.ndarray] = None, mesh=None,
                 flash_prefill: bool = False,
                 valid: Optional[jnp.ndarray] = None,
                 decode_kernel: Optional[str] = None,
                 ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Run a stack of llama blocks (leading layer axis) via ``lax.scan`` —
    the llama sibling of ``gpt2.apply_blocks``, factored out so the
    pipeline partitioner (parallel.partition) and the GPipe schedule
    (parallel.gpipe) can run a STAGE's block slice.

    ``valid`` ([L] bool, no-cache path only) masks padding layers to
    identity — the uneven-pipeline-stage mechanism, exactly as in
    ``gpt2.apply_blocks``."""
    if cache is None:
        if valid is None:
            def body(carry, layer_params):
                out, _, _ = _block(layer_params, carry, config, cos, sin,
                                   None, None, 0, k_valid_from=k_valid_from,
                                   mesh=mesh)
                return out, None
        else:
            blocks = (blocks, valid)

            def body(carry, xs):
                layer_params, valid_l = xs
                out, _, _ = _block(layer_params, carry, config, cos, sin,
                                   None, None, 0, k_valid_from=k_valid_from,
                                   mesh=mesh)
                return jnp.where(valid_l, out, carry), None

        if remat:
            body = jax.checkpoint(body)
        h, _ = jax.lax.scan(body, h, blocks)
        return h, None

    offset = cache.length
    n_blocks = jax.tree_util.tree_leaves(blocks)[0].shape[0]

    # Cache rides the CARRY (in-place column updates), not xs/ys — see
    # ops.attention.write_kv_layer for the memory-behavior rationale.
    # ``valid`` masks padding layers to identity, as in gpt2.apply_blocks
    # (their cache slices take garbage writes no real layer ever reads).
    def body(carry, xs):
        h, K, V = carry
        if valid is None:
            layer_params, li = xs
        else:
            layer_params, li, valid_l = xs
        out, K, V = _block(layer_params, h, config, cos, sin, K, V, offset,
                           k_valid_from=k_valid_from,
                           flash_prefill=flash_prefill, layer_idx=li,
                           decode_kernel=decode_kernel)
        if valid is not None:
            out = jnp.where(valid_l, out, h)
        return (out, K, V), None

    xs = ((blocks, jnp.arange(n_blocks)) if valid is None
          else (blocks, jnp.arange(n_blocks), valid))
    (h, new_k, new_v), _ = jax.lax.scan(body, (h, cache.k, cache.v), xs)
    new_len = cache.length + jnp.asarray(h.shape[1], dtype=jnp.int32)
    return h, KVCache(new_k, new_v, new_len)


def forward(params: Params, input_ids: jnp.ndarray, config: LlamaConfig,
            remat: bool = False, mesh=None) -> jnp.ndarray:
    """Full no-cache forward: [B, S] -> [B, S, vocab] float32 logits."""
    h = _embed(params, input_ids)
    cos, sin = _angles(config, input_ids.shape[1], 0, None)
    h, _ = apply_blocks(params["blocks"], h, config, cos, sin,
                        remat=remat, mesh=mesh)
    return _final(params, h, config)


def forward_with_cache(params: Params, input_ids: jnp.ndarray,
                       config: LlamaConfig, cache: KVCache,
                       pad: Optional[jnp.ndarray] = None,
                       flash_prefill: bool = False,
                       decode_kernel: Optional[str] = None,
                       ) -> Tuple[jnp.ndarray, KVCache]:
    """Cached forward (prefill when cache.length==0, decode otherwise).

    Same contract as ``gpt2.forward_with_cache`` — multi-token steps at a
    dynamic offset work, which is what speculative decoding's verify
    forward relies on.
    """
    h = _embed(params, input_ids)
    offset = cache.length
    cos, sin = _angles(config, input_ids.shape[1], offset, pad)
    # structural guard (mirrors gpt2): the flash branch has no pad mask,
    # so ragged batches always take the masked cached-attention path
    flash_prefill = flash_prefill and pad is None
    h, cache = apply_blocks(params["blocks"], h, config, cos, sin, cache,
                            k_valid_from=pad, flash_prefill=flash_prefill,
                            decode_kernel=decode_kernel)
    return _final(params, h, config), cache


def make_cache(config: LlamaConfig, batch: int, max_seq: int,
               dtype=jnp.float32) -> KVCache:
    """KV cache at kv-head width ([L, B, n_kv_head, max_seq, hd]).

    ``n_positions`` bounds ``max_seq`` as a config contract (cache sizing /
    serving limit), not a table size — raise it in the config and longer
    contexts work with the same weights (RoPE).
    """
    if max_seq > config.n_positions:
        raise ValueError(
            f"max_seq={max_seq} exceeds n_positions={config.n_positions} "
            "(the configured serving/cache bound)")
    return KVCache.create(config.n_layer, batch, config.n_kv_head, max_seq,
                          config.head_dim, dtype)


def _tp_pspecs(mesh):
    from ..parallel import spmd
    return spmd.llama_param_pspecs(mesh)


FAMILY = Family(name="llama", config_class=LlamaConfig, stageable=True,
                param_pspecs=_tp_pspecs)
