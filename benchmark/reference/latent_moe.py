"""The plain reference of the latent-attention / sparse-expert family.

DeepSeek-V3's layer plan (DeepSeek-AI 2024, arXiv:2412.19437; the keys
are those of its ``config.json``, which ``JoyAI-LLM-Flash`` publishes
unchanged) in straight ``jax.numpy``, float32, ``precision="highest"``:
no kernel, no cache, no batching, one sequence at a time, independent
of ``llm_sharding_demo_tpu/models``. Per layer, with ``x = RMSNorm(h)``:

- attention, the EXPANDED form only: ``c_q = RMSNorm(x W_dq)``,
  ``[q_nope | q_pe] = c_q W_uq`` per head; ``[c_kv | k_pe] = x W_dkv``,
  ``c_kv = RMSNorm(c_kv)``, ``k_pe`` shared by all heads; RoPE on the
  pairs ``(2i, 2i+1)`` of ``q_pe`` and ``k_pe`` (``rope_interleave``);
  ``k_nope = c_kv W_uk``, ``v = c_kv W_uv`` per head; scores ``(q_nope .
  k_nope + q_pe . k_pe) / sqrt(nope + rope)``, causal softmax, ``P v``,
  heads concatenated through ``W_o``;
- feed-forward: SwiGLU in the leading ``first_k_dense_replace`` layers;
  after them ``s = sigmoid(x W_g)``, the ``num_experts_per_tok`` experts
  with the largest ``s + b``, weights ``s[chosen] / (sum + 1e-20) *
  routed_scaling_factor``, ``sum_e w_e SwiGLU_e(x)`` over the experts
  HELD (the configuration's ``n_routed_experts`` ids from
  ``first_expert`` of the ``published_n_routed_experts`` the router
  scores; the others' terms are left out, as in the program) plus the
  shared expert;
- ``h += attention``, ``h += feed-forward``; final RMSNorm; untied head.

Departures from the published model, each a note here and nowhere
hidden: weights are seeded random normals (std ``fan_in ** -0.5``, the
embedding 1.0, the selection bias 0.1: ``assumed`` in the configuration
file), made on the device in the tree layout the program's family takes
(``W_ukv`` as its two column blocks ``wuk`` and ``wuv``); the rotation
is written on complex pairs, which is the published permute-then-
rotate-half up to one fixed permutation of ``q_pe`` and ``k_pe`` alike;
``n_group = topk_group = 1`` makes group-limited routing the identity
and it is not written out; the multi-token-prediction module
(``num_nextn_predict_layers``) is no part of the next-token pass and has
no weights here; attention runs over blocks of ``_Q_BLOCK`` queries so
that a 3k-token float32 pass fits beside the served model (the same
sums, fewer at a time), and a sequence is right-padded to the
configuration's ``MAX_SEQ`` so that ragged requests share one program.
``weights="int8"`` is ``dense._mm``'s control: every matrix, the
router's too, rounded to int8 codes a column.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .dense import (F32, HI, _Reference, _freeze, _key, _layer, _mm,
                    _normal, _stack)

_Q_BLOCK = 512


def _rms(x, scale, eps):
    return (x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
            * scale.astype(F32))


def _rope_pairs(x, theta):
    """x [..., S, rope]: rotate each pair ``(x[2i], x[2i+1])`` of
    position ``p`` by ``p * theta ** (-2i / rope)``."""
    s, r = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv            # [S, r/2]
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) \
        * jax.lax.complex(jnp.cos(ang), jnp.sin(ang))
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def _swiglu(x, mlp, weights):
    return _mm(jax.nn.silu(_mm(x, mlp["gate"]["kernel"], weights))
               * _mm(x, mlp["up"]["kernel"], weights),
               mlp["down"]["kernel"], weights)


class LatentMoE(_Reference):

    def init(self, sizes, seed, dtype=jnp.bfloat16):
        return self._init(_freeze(sizes), _key(seed), jnp.dtype(dtype))

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(0, 2))
    def _init(sizes, key, dtype):
        s = dict(sizes)
        d, v, h = s["hidden_size"], s["vocab_size"], s["num_attention_heads"]
        rq, rkv = s["q_lora_rank"], s["kv_lora_rank"]
        nope, rope, vd = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                          s["v_head_dim"])
        f, held = s["moe_intermediate_size"], s["n_routed_experts"]
        total = s["published_n_routed_experts"]
        n_dense = s["first_k_dense_replace"]
        ke, kh, kd, kb = jax.random.split(key, 4)

        def w(k, a, b):
            return {"kernel": _normal(k, (a, b), a ** -0.5, dtype)}

        def ones(n):
            return {"scale": jnp.ones((n,), dtype)}

        def mlp(ks, width):
            return {"gate": w(ks[0], d, width), "up": w(ks[1], d, width),
                    "down": w(ks[2], width, d)}

        def common(ks):
            return {"ln_attn": ones(d), "ln_mlp": ones(d), "attn": {
                "wdq": w(ks[0], d, rq), "q_norm": ones(rq),
                "wuq": w(ks[1], rq, h * (nope + rope)),
                "wdkv": w(ks[2], d, rkv + rope), "kv_norm": ones(rkv),
                "wuk": w(ks[3], rkv, h * nope), "wuv": w(ks[4], rkv, h * vd),
                "wo": w(ks[5], h * vd, d)}}

        def dense(k):
            ks = jax.random.split(k, 9)
            return {**common(ks), "mlp": mlp(ks[6:], s["intermediate_size"])}

        def expert(k):
            ks = jax.random.split(k, 14)
            each = jax.vmap(lambda kk: mlp(jax.random.split(kk, 3), f))(
                jax.random.split(ks[13], held))
            return {**common(ks), "moe": {
                "router": {"kernel": w(ks[6], d, total)["kernel"],
                           "bias": _normal(ks[7], (total,), 0.1, dtype)},
                "shared": mlp(ks[8:11], f * s["n_shared_experts"]),
                "experts": each}}

        return {"wte": _normal(ke, (v, d), 1.0, dtype),
                "dense": _stack(n_dense, kd, dense),
                "blocks": _stack(s["num_hidden_layers"] - n_dense, kb,
                                 expert),
                "ln_f": ones(d),
                "lm_head": w(kh, d, v)}

    def logits(self, params, sizes, ids, positions, weights=None):
        # right-padded to the serving bound (attention is causal: what
        # follows a position changes nothing at it), so that a window's
        # ragged requests share ONE program a layer kind: building a
        # program a length took longer than the passes themselves
        bound = int(sizes.get("serving_env", {}).get("MAX_SEQ", len(ids)))
        ids = list(ids) + [0] * max(bound - len(ids), 0)
        ids = jnp.asarray(ids, jnp.int32)
        frozen = _freeze(sizes)
        h = params["wte"][ids].astype(F32)
        for l in range(sizes["first_k_dense_replace"]):
            h = self._block(params["dense"], l, h, weights=weights,
                            sizes=frozen, dense=True)
        for l in range(sizes["num_hidden_layers"]
                       - sizes["first_k_dense_replace"]):
            h = self._block(params["blocks"], l, h, weights=weights,
                            sizes=frozen, dense=False)
        return self._head(params, h[jnp.asarray(positions)],
                          weights=weights, sizes=frozen)

    @staticmethod
    def _attention(b, x, s, weights):
        a = b["attn"]
        n, h = x.shape[0], s["num_attention_heads"]
        nope, rope = s["qk_nope_head_dim"], s["qk_rope_head_dim"]
        eps, theta = s["rms_norm_eps"], s["rope_theta"]
        c_q = _rms(_mm(x, a["wdq"]["kernel"], weights),
                   a["q_norm"]["scale"], eps)
        q = _mm(c_q, a["wuq"]["kernel"], weights).reshape(
            n, h, nope + rope).transpose(1, 0, 2)            # [H, S, 192]
        q_pe = _rope_pairs(q[..., nope:], theta)
        down = _mm(x, a["wdkv"]["kernel"], weights)
        rank = s["kv_lora_rank"]
        c_kv = _rms(down[:, :rank], a["kv_norm"]["scale"], eps)
        k_pe = _rope_pairs(down[:, rank:], theta)            # [S, rope]
        k_nope = _mm(c_kv, a["wuk"]["kernel"], weights).reshape(
            n, h, nope).transpose(1, 0, 2)
        v = _mm(c_kv, a["wuv"]["kernel"], weights).reshape(
            n, h, -1).transpose(1, 0, 2)
        outs = []
        for lo in range(0, n, _Q_BLOCK):
            hi = min(lo + _Q_BLOCK, n)
            sc = (jnp.einsum("hqd,hkd->hqk", q[:, lo:hi, :nope],
                             k_nope[:, :hi], precision=HI)
                  + jnp.einsum("hqd,kd->hqk", q_pe[:, lo:hi], k_pe[:hi],
                               precision=HI)) / math.sqrt(nope + rope)
            seen = (jnp.arange(hi)[None, :]
                    <= jnp.arange(lo, hi)[:, None])
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,hkd->hqd", p, v[:, :hi],
                                   precision=HI))
        o = jnp.concatenate(outs, axis=1).transpose(1, 0, 2).reshape(n, -1)
        return _mm(o, a["wo"]["kernel"], weights)

    @staticmethod
    def _experts(moe, x, s, weights):
        """The held experts' weighted terms plus the shared expert."""
        k, first = s["num_experts_per_tok"], s.get("first_expert", 0)
        score = jax.nn.sigmoid(_mm(x, moe["router"]["kernel"], weights))
        _, chosen = jax.lax.top_k(
            score + moe["router"]["bias"].astype(F32), k)
        w = jnp.take_along_axis(score, chosen, axis=-1)
        if s["norm_topk_prob"]:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        w = w * s["routed_scaling_factor"]

        def one(y, xs):
            expert, e = xs
            w_e = jnp.where(chosen == first + e, w, 0.0).sum(-1)  # [S]
            return y + w_e[:, None] * _swiglu(x, expert, weights), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
            moe["experts"], jnp.arange(s["n_routed_experts"])))
        return y + _swiglu(x, moe["shared"], weights)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes",
                                                 "dense"))
    def _block(blocks, l, h, *, weights, sizes, dense):
        s = dict(sizes)
        b, eps = _layer(blocks, l), s["rms_norm_eps"]
        h = h + LatentMoE._attention(
            b, _rms(h, b["ln_attn"]["scale"], eps), s, weights)
        m = _rms(h, b["ln_mlp"]["scale"], eps)
        if dense:
            return h + _swiglu(m, b["mlp"], weights)
        return h + LatentMoE._experts(b["moe"], m, s, weights)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes"))
    def _head(params, h, *, weights, sizes):
        h = _rms(h, params["ln_f"]["scale"], dict(sizes)["rms_norm_eps"])
        return _mm(h, params["lm_head"]["kernel"], weights)


latent_moe = LatentMoE()
