"""GPT-2 as pure JAX functions over a parameter pytree.

TPU-native re-design of the model layer the reference gets from HuggingFace
(``AutoModelForCausalLM.from_pretrained`` at reference server.py:41, torch
modules ``wte/wpe/drop/h/ln_f/lm_head`` wired into two shards at
server.py:56-60). Differences by design, not translation:

- Parameters are a plain pytree (nested dicts of ``jnp`` arrays). All
  transformer blocks are *stacked on a leading layer axis*, so applying a
  stage's blocks is one ``lax.scan`` — a single compiled loop body reused
  across layers — instead of the reference's Python ``for block in
  self.blocks`` (server.py:84-85, 99-100).
- The LM head is weight-tied to ``wte`` (as in GPT-2 proper): logits are
  ``h @ wte.T``. No separate lm_head tensor exists, which also fixes the
  reference quirk of every role holding full weights (server.py:108-110).
- Kernels use the ``[in, out]`` layout matching HF ``Conv1D`` storage so the
  checkpoint converter (``models.hf_convert``) is copy-only.
- Everything is shape-static and jit-friendly; positions derive from an
  integer offset rather than re-materialized ``arange(0, seq_len)`` per call
  (the reference recomputes positions from zero every token,
  server.py:80, because it has no cache).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import (KVCache, cached_attention_inplace,
                             causal_attention, merge_heads, split_heads,
                             write_kv_layer)
from ..ops.layers import gelu_new, layer_norm, linear
from .family import Family

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    """Architecture hyperparameters (mirrors HF ``GPT2Config`` fields we use)."""

    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    layer_norm_epsilon: float = 1e-5
    # "xla": fused einsum attention (default). "pallas": Mosaic flash
    # kernel (ops.flash_attention). "ring": sequence-parallel ring
    # attention over the mesh's "sp" axis (ops.ring_attention) — the
    # long-context path; requires a mesh passed to ``forward``. All three
    # apply to the no-cache forward (training / compat endpoints).
    # Cached single-token decode has its own dispatch, independent of
    # this knob: the engine's ``decode_kernel`` routes it through the
    # Pallas flash-decode kernel (ops.decode_attention) on TPU, or the
    # fused XLA path in the byte-pinned parity modes.
    attention_impl: str = "xla"

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    def __post_init__(self):
        if self.n_embd % self.n_head != 0:
            raise ValueError(
                f"n_embd={self.n_embd} not divisible by n_head={self.n_head}")
        if self.attention_impl not in ("xla", "pallas", "ring"):
            raise ValueError(
                f"attention_impl={self.attention_impl!r} not xla|pallas|ring")


# Static-analysis/planner contract (tools/graftcheck/costmodel): how this
# family's stacked param tree shards, as architectural facts rather than
# hand-written PartitionSpecs. ``column``/``row`` name the ops (kernel +
# optional bias siblings) that are Megatron column-/row-parallel over a
# ``tp`` axis; ``expert`` names ops stacked on an expert axis (dim 1 of
# the block leaf, after the layer axis) shardable over ``ep``;
# ``tp_divisors``/``ep_divisors`` name config fields the corresponding
# mesh axis size must divide for the plan to be runnable (the engine's
# own guards). ``costmodel.derive_pspecs`` turns this into the full
# PartitionSpec tree — pinned equal to the hand-tuned ``spmd``
# layouts by tests/test_graftplan.py.
SHARDING_DESCRIPTOR = {
    "column": ("blocks.attn.c_attn", "blocks.mlp.c_fc"),
    "row": ("blocks.attn.c_proj", "blocks.mlp.c_proj"),
    "expert": (),
    "tp_divisors": ("n_head",),
    "ep_divisors": (),
    # MHA: kv heads == n_head, so a kvp (KV-partition) axis shards the
    # same head count tp does (tools/graftcheck placement/costmodel)
    "kvp_divisors": ("n_head",),
}


# Named configs for the BASELINE.json measurement matrix. "tiny-gpt2" matches
# sshleifer/tiny-gpt2 (the reference's default MODEL_ID, server.py:20);
# "gpt2" is GPT-2 124M; "gpt2-medium" the 355M config (4-stage target).
CONFIGS: Dict[str, GPT2Config] = {
    "tiny-gpt2": GPT2Config(vocab_size=50257, n_positions=1024, n_embd=2,
                            n_layer=2, n_head=2),
    "gpt2": GPT2Config(vocab_size=50257, n_positions=1024, n_embd=768,
                       n_layer=12, n_head=12),
    "gpt2-medium": GPT2Config(vocab_size=50257, n_positions=1024, n_embd=1024,
                              n_layer=24, n_head=16),
}


def init_params(config: GPT2Config, key: jax.Array,
                dtype=jnp.float32) -> Params:
    """Random-init parameters (normal(0.02) weights, zero biases, unit LN).

    Block tensors carry a leading ``n_layer`` axis (see module docstring).
    """
    k_wte, k_wpe, k_blocks = jax.random.split(key, 3)
    d, l = config.n_embd, config.n_layer
    std = 0.02

    def normal(k, shape):
        return (jax.random.normal(k, shape) * std).astype(dtype)

    bkeys = jax.random.split(k_blocks, 4)
    params: Params = {
        "wte": normal(k_wte, (config.vocab_size, d)),
        "wpe": normal(k_wpe, (config.n_positions, d)),
        "blocks": {
            "ln_1": {"scale": jnp.ones((l, d), dtype), "bias": jnp.zeros((l, d), dtype)},
            "attn": {
                "c_attn": {"kernel": normal(bkeys[0], (l, d, 3 * d)),
                           "bias": jnp.zeros((l, 3 * d), dtype)},
                "c_proj": {"kernel": normal(bkeys[1], (l, d, d)),
                           "bias": jnp.zeros((l, d), dtype)},
            },
            "ln_2": {"scale": jnp.ones((l, d), dtype), "bias": jnp.zeros((l, d), dtype)},
            "mlp": {
                "c_fc": {"kernel": normal(bkeys[2], (l, d, 4 * d)),
                         "bias": jnp.zeros((l, 4 * d), dtype)},
                "c_proj": {"kernel": normal(bkeys[3], (l, 4 * d, d)),
                           "bias": jnp.zeros((l, d), dtype)},
            },
        },
        "ln_f": {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
    }
    return params


# ---------------------------------------------------------------------------
# Forward pieces. Split into embed / blocks / final so the pipeline
# partitioner (parallel.partition) can hand each stage exactly the pieces the
# reference gives its shards: A = wte+wpe+blocks[:k] (server.py:68-86),
# B = blocks[k:]+ln_f+lm_head (server.py:90-103) — generalized to N stages.
# ---------------------------------------------------------------------------

def embed(params: Params, input_ids: jnp.ndarray,
          position_offset: jnp.ndarray | int = 0) -> jnp.ndarray:
    """Token + position embeddings. [B, S] int32 -> [B, S, D].

    ``position_offset`` is the absolute position of the first token (nonzero
    during incremental decode). The reference always uses offset 0 because it
    re-forwards the full sequence (server.py:80). A ``[B, 1]`` offset gives
    per-row positions for left-padded ragged batches (pad columns clip to
    position 0; their outputs are never read — attention masks them as keys
    and sampling reads only the final, real column).
    """
    seq_len = input_ids.shape[-1]
    positions = jnp.maximum(position_offset + jnp.arange(seq_len), 0)
    wte = params["wte"]
    from ..ops.quant import is_quantized
    if is_quantized(wte):  # weight-only int8 table (ops.quant)
        from ..ops.quant import embed_rows
        return embed_rows(wte, input_ids) + params["wpe"][positions]
    return wte[input_ids] + params["wpe"][positions]


def _block(block_params: Params, h: jnp.ndarray, n_head: int, eps: float,
           cache_k: Optional[jnp.ndarray], cache_v: Optional[jnp.ndarray],
           offset, attn_impl: str = "xla",
           k_valid_from: Optional[jnp.ndarray] = None, mesh=None,
           mlp_fn=None, flash_prefill: bool = False, layer_idx=None,
           decode_kernel: Optional[str] = None,
           ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """One pre-LN transformer block; optionally reads/writes the KV cache.

    ``cache_k``/``cache_v`` (when given) are the FULL stacked
    ``[L, B, H, max_seq, hd]`` buffers and ``layer_idx`` selects this
    block's slice: the write is an in-place token-column
    ``dynamic_update_slice`` on the loop-carried cache (see
    ``ops.attention.write_kv_layer`` for why slice-per-layer re-stacking
    was a full cache copy per decode step). Returns the updated stacks.

    ``mlp_fn(block_params, m) -> mlp_out`` swaps the dense MLP for another
    feed-forward (``models.moe`` passes its routed expert MLP here), so the
    attention half — the part every family shares — exists exactly once.

    ``flash_prefill`` (static) routes the CACHED path's attention through
    the Pallas flash kernel. Callers may set it only for a fresh-cache
    prefill (offset 0, no pad, S == full window): there the cached
    attention is exactly plain causal attention over the new K/V, so the
    cache write and the attention decouple — the kernel never touches the
    cache buffers and the O(S^2) score materialization disappears at
    long context (the engine derives the flag, runtime.engine._prefill).
    """
    a = layer_norm(h, block_params["ln_1"]["scale"], block_params["ln_1"]["bias"], eps)
    qkv = linear(a, block_params["attn"]["c_attn"]["kernel"],
                 block_params["attn"]["c_attn"]["bias"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q, k, v = (split_heads(x, n_head) for x in (q, k, v))
    if cache_k is None:
        if attn_impl == "pallas":
            from ..ops.flash_attention import (flash_attention,
                                               flash_profitable)
            if flash_profitable(q.shape[2]):
                attn_out = flash_attention(
                    q, k, v, interpret=jax.default_backend() != "tpu")
            else:
                # below the measured crossover the XLA einsum wins —
                # "pallas" means "kernel where it pays", never a regression
                attn_out = causal_attention(q, k, v, q_offset=offset,
                                            k_valid_from=k_valid_from)
        elif attn_impl == "ring":
            from ..ops.ring_attention import ring_attention  # lazy import
            if mesh is None:
                raise ValueError(
                    "attention_impl='ring' needs a mesh with an 'sp' axis: "
                    "pass forward(..., mesh=mesh) (or TrainStep(mesh=...))")
            if k_valid_from is not None:
                raise NotImplementedError(
                    "ring attention does not support ragged (left-padded) "
                    "batches")
            attn_out = ring_attention(q, k, v, mesh, axis="sp")
        else:
            attn_out = causal_attention(q, k, v, q_offset=offset,
                                        k_valid_from=k_valid_from)
        new_ck = new_cv = None
    elif decode_kernel:
        # FUSED cache mode (see ops.attention.create_fused_cache):
        # ``cache_k`` is the [L, B, H, Smax, 2*hd] fused buffer and
        # ``cache_v`` an empty placeholder riding the pytree.
        from ..ops.attention import (cached_attention_fused,
                                     write_kv_layer_fused)
        if flash_prefill:
            from ..ops.flash_attention import flash_attention
            new_ck = write_kv_layer_fused(cache_k, k, v, layer_idx, offset)
            attn_out = flash_attention(
                q, k, v, interpret=jax.default_backend() != "tpu")
        elif q.shape[2] == 1:
            # single-token step -> the Pallas flash-decode kernel: fused
            # row written in place inside the kernel, KV blocks streamed
            # with a depth-adaptive trip count (ops.decode_attention —
            # the XLA path measures ~3x slower at batched-decode shapes)
            from ..ops.decode_attention import decode_attention
            attn_out, new_ck = decode_attention(
                q, k, v, cache_k, layer_idx, offset, k_valid_from,
                interpret=decode_kernel == "interpret")
        else:
            attn_out, new_ck = cached_attention_fused(
                q, k, v, cache_k, layer_idx, offset, k_valid_from)
        new_cv = cache_v
    elif flash_prefill:
        from ..ops.flash_attention import flash_attention  # lazy import
        new_ck, new_cv = write_kv_layer(cache_k, cache_v, k, v, layer_idx,
                                        offset)
        attn_out = flash_attention(
            q, k, v, interpret=jax.default_backend() != "tpu")
    else:
        attn_out, new_ck, new_cv = cached_attention_inplace(
            q, k, v, cache_k, cache_v, layer_idx, offset, k_valid_from)
    attn_out = linear(merge_heads(attn_out),
                      block_params["attn"]["c_proj"]["kernel"],
                      block_params["attn"]["c_proj"]["bias"])
    h = h + attn_out
    m = layer_norm(h, block_params["ln_2"]["scale"], block_params["ln_2"]["bias"], eps)
    if mlp_fn is None:
        m = linear(gelu_new(linear(m, block_params["mlp"]["c_fc"]["kernel"],
                                   block_params["mlp"]["c_fc"]["bias"])),
                   block_params["mlp"]["c_proj"]["kernel"],
                   block_params["mlp"]["c_proj"]["bias"])
    else:
        m = mlp_fn(block_params, m)
    return h + m, new_ck, new_cv


def apply_blocks(blocks: Params, h: jnp.ndarray, config: GPT2Config,
                 cache: Optional[KVCache] = None, remat: bool = False,
                 k_valid_from: Optional[jnp.ndarray] = None, mesh=None,
                 valid: Optional[jnp.ndarray] = None,
                 flash_prefill: bool = False,
                 decode_kernel: Optional[str] = None,
                 ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Run a stack of blocks (leading layer axis) via ``lax.scan``.

    ``blocks`` leaves are ``[L, ...]``; ``cache`` (if given) carries matching
    ``[L, B, H, max_seq, hd]`` buffers. One compiled body serves every layer —
    the TPU-shaped replacement for the reference's per-module Python loop
    (server.py:84-85, 99-100).

    ``remat=True`` checkpoints each block under reverse-mode AD: the
    backward pass recomputes block activations instead of storing all
    ``L`` of them — the standard HBM-for-FLOPs trade for training.

    ``valid`` ([L] bool, no-cache path only) masks padding layers to
    identity — the mechanism behind unequal pipeline stages, where stage
    blocks are zero-padded to a common count (``parallel.partition.
    stack_stage_params_padded``). A masked layer contributes nothing to
    the output, so its (zero) parameters also receive exactly zero
    gradient and stay zero under training.
    """
    eps = config.layer_norm_epsilon
    n_head = config.n_head

    if cache is None:
        if valid is None:
            def body(carry, layer_params):
                out, _, _ = _block(layer_params, carry, n_head, eps, None,
                                   None, 0, config.attention_impl,
                                   k_valid_from, mesh)
                return out, None
        else:
            blocks = (blocks, valid)

            def body(carry, xs):
                layer_params, valid_l = xs
                out, _, _ = _block(layer_params, carry, n_head, eps, None,
                                   None, 0, config.attention_impl,
                                   k_valid_from, mesh)
                return jnp.where(valid_l, out, carry), None

        if remat:
            body = jax.checkpoint(body)
        h, _ = jax.lax.scan(body, h, blocks)
        return h, None

    offset = cache.length
    n_blocks = jax.tree_util.tree_leaves(blocks)[0].shape[0]

    # Cache rides the CARRY (in-place column updates), not xs/ys — see
    # ops.attention.write_kv_layer for the memory-behavior rationale.
    # ``valid`` masks padding layers to identity (uneven pipeline stages,
    # parallel.partition.stack_stage_params_padded): a padded layer's
    # output is discarded and its cache slice — written with garbage
    # derived from zero params — is never read by any real layer.
    def body(carry, xs):
        h, K, V = carry
        if valid is None:
            layer_params, li = xs
        else:
            layer_params, li, valid_l = xs
        out, K, V = _block(layer_params, h, n_head, eps, K, V,
                           offset, k_valid_from=k_valid_from,
                           flash_prefill=flash_prefill, layer_idx=li,
                           decode_kernel=decode_kernel)
        if valid is not None:
            out = jnp.where(valid_l, out, h)
        return (out, K, V), None

    xs = ((blocks, jnp.arange(n_blocks)) if valid is None
          else (blocks, jnp.arange(n_blocks), valid))
    (h, new_k, new_v), _ = jax.lax.scan(body, (h, cache.k, cache.v), xs)
    new_len = cache.length + jnp.asarray(h.shape[1], dtype=jnp.int32)
    return h, KVCache(k=new_k, v=new_v, length=new_len)


def final_logits(params: Params, h: jnp.ndarray, eps: float) -> jnp.ndarray:
    """ln_f followed by the tied LM head (logits = h @ wte.T).

    Equivalent of the reference's ShardB tail (ln_f -> lm_head,
    server.py:101-102); tying to ``wte`` matches GPT-2's actual weight
    sharing, which HF also applies. Logits accumulate in float32 even under
    bfloat16 weights/activations so argmax/sampling see full-precision
    scores (bf16 logits would quantize ~3 decimal digits and break greedy
    tie behavior).
    """
    h = layer_norm(h, params["ln_f"]["scale"], params["ln_f"]["bias"], eps)
    from ..ops.quant import is_quantized
    if is_quantized(params["wte"]):  # int8 table: fold scale into h
        from ..ops.quant import head_logits
        return head_logits(h, params["wte"])
    return jnp.einsum("bsd,vd->bsv", h, params["wte"],
                      preferred_element_type=jnp.float32)


def forward(params: Params, input_ids: jnp.ndarray,
            config: GPT2Config, remat: bool = False, mesh=None) -> jnp.ndarray:
    """Full no-cache forward: [B, S] -> [B, S, vocab] logits.

    The parity oracle against HF GPT-2 (SURVEY.md §4 item 1) and the compat
    ``/forward`` + ``/forward_b`` composition both go through here.
    ``remat`` is for the training path (see ``apply_blocks``); ``mesh`` is
    required when ``config.attention_impl == "ring"`` (the sequence-
    parallel long-context path shards attention over the mesh's sp axis).
    """
    h = embed(params, input_ids, 0)
    h, _ = apply_blocks(params["blocks"], h, config, remat=remat, mesh=mesh)
    return final_logits(params, h, config.layer_norm_epsilon)


def forward_with_cache(params: Params, input_ids: jnp.ndarray,
                       config: GPT2Config, cache: KVCache,
                       pad: Optional[jnp.ndarray] = None,
                       flash_prefill: bool = False,
                       decode_kernel: Optional[str] = None,
                       ) -> Tuple[jnp.ndarray, KVCache]:
    """Cached forward (prefill when cache.length==0, decode step otherwise).

    Returns full-sequence logits and the updated cache. The decode engine
    (runtime.engine) jits this once for prefill shapes and once for the
    single-token step.

    ``pad`` ([B] int32, optional) enables ragged batches of left-padded
    prompts: row b's first ``pad[b]`` cache slots are pad tokens, so its
    positions shift down by ``pad[b]`` and those slots are masked as keys.
    Cache indices stay uniform across rows (the point of left-padding: one
    ``dynamic_update_slice`` serves the whole batch).
    """
    if pad is None:
        h = embed(params, input_ids, cache.length)
        h, cache = apply_blocks(params["blocks"], h, config, cache,
                                flash_prefill=flash_prefill,
                                decode_kernel=decode_kernel)
    else:
        h = embed(params, input_ids, cache.length - pad[:, None])
        h, cache = apply_blocks(params["blocks"], h, config, cache,
                                k_valid_from=pad,
                                decode_kernel=decode_kernel)
    return final_logits(params, h, config.layer_norm_epsilon), cache


def make_cache(config: GPT2Config, batch: int, max_seq: int,
               dtype=jnp.float32) -> KVCache:
    """Allocate a fixed-size KV cache.

    ``max_seq`` is bounded by ``n_positions``: past the learned position
    table, ``wpe`` gathers and cache writes would silently clamp (XLA
    out-of-bounds semantics) and corrupt generation instead of erroring.
    """
    if max_seq > config.n_positions:
        raise ValueError(
            f"max_seq={max_seq} exceeds n_positions={config.n_positions}; "
            "decode past the position table would silently clamp")
    return KVCache.create(config.n_layer, batch, config.n_head, max_seq,
                          config.head_dim, dtype)


FAMILY = Family(name="gpt2", config_class=GPT2Config, wire_topology=True,
                stageable=True)
