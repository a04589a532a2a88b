"""The plain reference: GPT-2 and the llama family's forward pass.

Straight ``jax.numpy`` in float32 at ``precision="highest"``, one layer
at a time, with no kernel, no cache and no batching, written from the
published equations and independent of ``llm_sharding_demo_tpu/models``.
It also makes the weights: from the seed, on the device, in the type
they are served in, in the tree layout the program's families take
through ``create_app(model=(config, params))``. The program is handed
those arrays; the reference reads the same arrays upcast, so nothing the
program computed enters the comparison.

A reference is one object with ``init(sizes, seed, dtype)`` and
``logits(params, sizes, ids, positions, weights=None)``; a
configuration file names it by dotted path, so a new architecture
brings its own.

Departures from the published models: weights are seeded random
normals (std ``fan_in ** -0.5`` for matrices, so activations keep their
scale through the depth and the largest logit varies), not a checkpoint.
``weights="int8"`` rounds every matrix to int8 codes with one scale per
output channel before use: the control of ``tests`` and ``PERF.md``,
the nearest precision below the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _key(seed: int):
    k = jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(k, int(seed) >> 31)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def _stack(n_layer, key, one_layer):
    """``one_layer(key) -> tree`` for every layer, stacked on a leading
    axis, one layer's temporaries live at a time."""
    def body(_, k):
        return None, one_layer(k)
    return jax.lax.scan(body, None, jax.random.split(key, n_layer))[1]


def _mm(x, w, weights):
    w = w.astype(F32)
    if weights == "int8":
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        w = jnp.clip(jnp.round(w / scale), -127, 127) * scale
    elif weights is not None:
        raise ValueError(f"weights={weights!r}: None or 'int8'")
    return jnp.matmul(x, w, precision=HI)


def _attend(q, k, v):
    """Causal softmax attention; q [H, S, hd], k/v [Hkv, S, hd]."""
    g = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k, precision=HI) / math.sqrt(q.shape[-1])
    n = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v,
                      precision=HI)


def _heads(x, n):          # [S, n*hd] -> [n, S, hd]
    return x.reshape(x.shape[0], n, -1).transpose(1, 0, 2)


def _merge(x):             # [n, S, hd] -> [S, n*hd]
    return x.transpose(1, 0, 2).reshape(x.shape[1], -1)


def _layer(tree, l):
    return jax.tree.map(lambda x: x[l], tree)


class _Reference:
    depth_key = "num_hidden_layers"

    def logits(self, params, sizes, ids, positions, weights=None):
        """Float32 logits ``[len(positions), vocab]`` of one sequence
        ``ids [S]`` at the given positions."""
        ids = jnp.asarray(ids, jnp.int32)
        h = self._embed(params, ids)
        for l in range(sizes[self.depth_key]):
            h = self._block(params["blocks"], l, h, weights=weights,
                            sizes=_freeze(sizes))
        return self._head(params, h[jnp.asarray(positions)],
                          weights=weights, sizes=_freeze(sizes))


def _freeze(sizes):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float))))


class GPT2(_Reference):
    """Radford et al. 2019: learned positions, pre-LayerNorm blocks with
    biases, fused QKV, tanh GELU, head tied to the embedding."""

    depth_key = "n_layer"

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(0, 2))
    def _init(sizes, key, dtype):
        s = dict(sizes)
        d, v, p = s["n_embd"], s["vocab_size"], s["n_positions"]
        ke, kp, kb = jax.random.split(key, 3)

        def one(k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            zeros, ones = (lambda n: jnp.zeros((n,), dtype)), (
                lambda n: jnp.ones((n,), dtype))
            return {
                "ln_1": {"scale": ones(d), "bias": zeros(d)},
                "attn": {"c_attn": {"kernel": _normal(k1, (d, 3 * d), d ** -0.5, dtype),
                                    "bias": zeros(3 * d)},
                         "c_proj": {"kernel": _normal(k2, (d, d), d ** -0.5, dtype),
                                    "bias": zeros(d)}},
                "ln_2": {"scale": ones(d), "bias": zeros(d)},
                "mlp": {"c_fc": {"kernel": _normal(k3, (d, 4 * d), d ** -0.5, dtype),
                                 "bias": zeros(4 * d)},
                        "c_proj": {"kernel": _normal(k4, (4 * d, d), (4 * d) ** -0.5, dtype),
                                   "bias": zeros(d)}},
            }
        return {"wte": _normal(ke, (v, d), 0.05, dtype),
                "wpe": _normal(kp, (p, d), 0.05, dtype),
                "blocks": _stack(s["n_layer"], kb, one),
                "ln_f": {"scale": jnp.ones((d,), dtype),
                         "bias": jnp.zeros((d,), dtype)}}

    def init(self, sizes, seed, dtype=jnp.bfloat16):
        return self._init(_freeze(sizes), _key(seed), jnp.dtype(dtype))

    @staticmethod
    def _ln(x, p, eps):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return ((x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(F32)
                + p["bias"].astype(F32))

    @staticmethod
    @jax.jit
    def _embed(params, ids):
        return (params["wte"][ids].astype(F32)
                + params["wpe"][jnp.arange(ids.shape[0])].astype(F32))

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes"))
    def _block(blocks, l, h, *, weights, sizes):
        s = dict(sizes)
        b, nh, eps = _layer(blocks, l), s["n_head"], s["layer_norm_epsilon"]
        a = GPT2._ln(h, b["ln_1"], eps)
        qkv = _mm(a, b["attn"]["c_attn"]["kernel"], weights) \
            + b["attn"]["c_attn"]["bias"].astype(F32)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        o = _merge(_attend(_heads(q, nh), _heads(k, nh), _heads(v, nh)))
        h = h + _mm(o, b["attn"]["c_proj"]["kernel"], weights) \
            + b["attn"]["c_proj"]["bias"].astype(F32)
        m = GPT2._ln(h, b["ln_2"], eps)
        m = _mm(m, b["mlp"]["c_fc"]["kernel"], weights) \
            + b["mlp"]["c_fc"]["bias"].astype(F32)
        m = 0.5 * m * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (m + 0.044715 * m ** 3)))
        return h + _mm(m, b["mlp"]["c_proj"]["kernel"], weights) \
            + b["mlp"]["c_proj"]["bias"].astype(F32)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes"))
    def _head(params, h, *, weights, sizes):
        h = GPT2._ln(h, params["ln_f"], dict(sizes)["layer_norm_epsilon"])
        return _mm(h, params["wte"].T, weights)


class Llama(_Reference):
    """Touvron et al. 2023 / Jiang et al. 2023 (Mistral 7B): RMSNorm,
    rotary positions (rotate-half), grouped-query attention, SwiGLU,
    no biases, untied head."""

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(0, 2))
    def _init(sizes, key, dtype):
        s = dict(sizes)
        d, v, i = s["hidden_size"], s["vocab_size"], s["intermediate_size"]
        kv = s["num_key_value_heads"] * s["head_dim"]
        ke, kh, kb = jax.random.split(key, 3)

        def one(k):
            ks = jax.random.split(k, 7)
            w = lambda kk, a, b: {"kernel": _normal(kk, (a, b), a ** -0.5, dtype)}
            return {"ln_attn": {"scale": jnp.ones((d,), dtype)},
                    "attn": {"wq": w(ks[0], d, d), "wk": w(ks[1], d, kv),
                             "wv": w(ks[2], d, kv), "wo": w(ks[3], d, d)},
                    "ln_mlp": {"scale": jnp.ones((d,), dtype)},
                    "mlp": {"gate": w(ks[4], d, i), "up": w(ks[5], d, i),
                            "down": w(ks[6], i, d)}}
        return {"wte": _normal(ke, (v, d), 1.0, dtype),
                "blocks": _stack(s["num_hidden_layers"], kb, one),
                "ln_f": {"scale": jnp.ones((d,), dtype)},
                "lm_head": {"kernel": _normal(kh, (d, v), d ** -0.5, dtype)}}

    def init(self, sizes, seed, dtype=jnp.bfloat16):
        return self._init(_freeze(sizes), _key(seed), jnp.dtype(dtype))

    @staticmethod
    def _rms(x, p, eps):
        return (x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
                * p["scale"].astype(F32))

    @staticmethod
    def _rope(x, theta):        # [H, S, hd], positions 0..S-1
        hd = x.shape[-1]
        inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
        ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
        ang = jnp.concatenate([ang, ang], -1)
        rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
        return x * jnp.cos(ang) + rot * jnp.sin(ang)

    @staticmethod
    @jax.jit
    def _embed(params, ids):
        return params["wte"][ids].astype(F32)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes"))
    def _block(blocks, l, h, *, weights, sizes):
        s = dict(sizes)
        b, eps, theta = _layer(blocks, l), s["rms_norm_eps"], s["rope_theta"]
        nh, nkv = s["num_attention_heads"], s["num_key_value_heads"]
        a = Llama._rms(h, b["ln_attn"], eps)
        q = Llama._rope(_heads(_mm(a, b["attn"]["wq"]["kernel"], weights), nh), theta)
        k = Llama._rope(_heads(_mm(a, b["attn"]["wk"]["kernel"], weights), nkv), theta)
        v = _heads(_mm(a, b["attn"]["wv"]["kernel"], weights), nkv)
        h = h + _mm(_merge(_attend(q, k, v)), b["attn"]["wo"]["kernel"], weights)
        m = Llama._rms(h, b["ln_mlp"], eps)
        m = jax.nn.silu(_mm(m, b["mlp"]["gate"]["kernel"], weights)) \
            * _mm(m, b["mlp"]["up"]["kernel"], weights)
        return h + _mm(m, b["mlp"]["down"]["kernel"], weights)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes"))
    def _head(params, h, *, weights, sizes):
        h = Llama._rms(h, params["ln_f"], dict(sizes)["rms_norm_eps"])
        return _mm(h, params["lm_head"]["kernel"], weights)


gpt2 = GPT2()
llama = Llama()
