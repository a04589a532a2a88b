"""The plain reference of the linear-attention / sparse-expert family.

Qwen3-Next's layer plan (the keys of the ``config.json`` of
``Qwen/Qwen3-Next-80B-A3B-Instruct``; the gated delta rule is Yang et
al. 2024, arXiv:2412.06464) in straight ``jax.numpy``, float32,
``precision="highest"``: no kernel, no cache, no batching, one sequence
at a time, independent of ``llm_sharding_demo_tpu/models``. With ``norm(x)
= x rsqrt(mean x^2 + eps) (1 + w)`` and ``x = norm1(h)``:

- layer ``i`` (from 0) is **softmax attention** iff ``(i + 1) %
  full_attention_interval == 0``: per head ``[q | gate] = x W_q``, ``k =
  x W_k``, ``v = x W_v``; ``q``, ``k`` through ``norm`` over the head;
  rotary (rotate-half) on the leading ``partial_rotary_factor`` of a
  head; causal softmax at ``head_dim ** -0.5``, ``num_attention_heads /
  num_key_value_heads`` query heads a key-value head; ``(attn
  sigmoid(gate)) W_o``;
- every other layer is **linear attention**: ``[q | k | v | z] = x
  W_qkvz`` and ``[b | a] = x W_ba``; ``c_t = silu(sum_j w_conv[:, j]
  u_{t-3+j})`` over ``u = [q | k | v]``, zeros before position 0;
  ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)``; ``q`` and ``k`` to unit length (``q`` also over
  ``sqrt(K)``), key head ``j`` serving value heads ``r j .. r j + r - 1``;
  per value head, from ``S = 0``, POSITION BY POSITION (``lax.scan``;
  the chunked form is the program's, not the reference's): ``S <- e^g S``,
  ``d = beta (v - S^T k)``, ``S <- S + k d^T``, ``o = S^T q``; ``y =
  o rsqrt(mean o^2 + eps) w_n silu(z)`` per head; ``y W_o``;
- every layer's feed-forward, on ``m = norm2(h)``: ``p = softmax(m
  W_r)`` over all ``published_num_experts``, the ``num_experts_per_tok``
  largest, ``w = p[chosen] / sum p[chosen]``; ``sum_e w_e SwiGLU_e(m)``
  over the experts HELD (the configuration's ``num_experts`` ids from
  ``first_expert``; the others' terms are left out, as in the program)
  plus ``sigmoid(m w_sg) SwiGLU_s(m)``;
- ``h += mixer``, ``h += feed-forward``; final ``norm``; untied head.

Departures from the published model, each a note here and nowhere
hidden: weights are seeded random normals (std ``fan_in ** -0.5``, the
embedding 1.0, the norms' offsets ``w`` 0.1, ``w_n`` 1 + 0.1 N, ``A_log``
uniform in [-1.4, 0.7] and ``dt_bias`` in [-4, -1] so that a position's
decay ``exp(g)`` spans roughly 0.5 to 0.999: ``assumed`` in the
configuration file), made on the device in the tree layout the program's
family takes (the linear layers as a list of ``interval - 1`` trees, one
a place in the period, the softmax layers as one tree, every leaf
``[periods, ...]``; the routed experts' ``[layers, held, ...]``); the columns of ``W_qkvz``, ``W_ba`` and ``W_q`` are stored
in blocks (all heads' ``q``, then all heads' ``k``, ``v``, ``z``; ``b``
then ``a``; ``q`` then ``gate``) where the published checkpoint
interleaves them per head: one fixed permutation of the columns, which
a checkpoint would be permuted by once at loading and which changes no
result (the program's reason is in ``models/gdn_moe.py``); the
multi-token-prediction module the published model ships is no part of
the next-token pass and has no weights here;
attention runs over blocks of ``_Q_BLOCK`` queries so that a 3k-token
float32 pass fits beside the served model (the same sums, fewer at a
time), and a sequence is right-padded to the configuration's ``MAX_SEQ``
so that ragged requests share one program (every layer is causal: what
follows a position changes nothing at it).
``weights="int8"`` is ``dense._mm``'s control: every matrix, the
router's and the shared expert's gate too, rounded to int8 codes a
column.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .dense import F32, HI, _Reference, _freeze, _key, _mm, _normal, _stack

_Q_BLOCK = 512


def _norm(x, w, eps):
    return (x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
            * (1.0 + w.astype(F32)))


def _rope_leading(x, rotary, theta):
    """x [..., S, hd]: rotate-half on the leading ``rotary`` dimensions
    by position, the rest unturned."""
    s = x.shape[-2]
    inv = theta ** (-jnp.arange(0, rotary, 2, dtype=F32) / rotary)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv            # [S, r/2]
    ang = jnp.concatenate([ang, ang], axis=-1)
    lead, rest = x[..., :rotary], x[..., rotary:]
    half = rotary // 2
    turned = jnp.concatenate([-lead[..., half:], lead[..., :half]], axis=-1)
    return jnp.concatenate(
        [lead * jnp.cos(ang) + turned * jnp.sin(ang), rest], axis=-1)


def _swiglu(x, mlp, weights):
    return _mm(jax.nn.silu(_mm(x, mlp["gate"]["kernel"], weights))
               * _mm(x, mlp["up"]["kernel"], weights),
               mlp["down"]["kernel"], weights)


class GDNMoE(_Reference):

    def init(self, sizes, seed, dtype=jnp.bfloat16):
        return self._init(_freeze(sizes), _key(seed), jnp.dtype(dtype))

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(0, 2))
    def _init(sizes, key, dtype):
        s = dict(sizes)
        d, v = s["hidden_size"], s["vocab_size"]
        h, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
        hk, hv = s["linear_num_key_heads"], s["linear_num_value_heads"]
        dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
        width = s["linear_conv_kernel_dim"]
        f = s["moe_intermediate_size"]
        fs = s["shared_expert_intermediate_size"]
        held, total = s["num_experts"], s["published_num_experts"]
        interval = s["full_attention_interval"]
        periods = s["num_hidden_layers"] // interval
        ke, kh, kg, kf, kx, kn = jax.random.split(key, 6)

        def w(k, a, b):
            return {"kernel": _normal(k, (a, b), a ** -0.5, dtype)}

        def offset(k, n):
            return {"scale": _normal(k, (n,), 0.1, dtype)}

        def mlp(ks, wide):
            return {"gate": w(ks[0], d, wide), "up": w(ks[1], d, wide),
                    "down": w(ks[2], wide, d)}

        def common(ks):
            return {"ln_attn": offset(ks[0], d), "ln_mlp": offset(ks[1], d),
                    "moe": {"router": w(ks[2], d, total),
                            "shared": mlp(ks[3:6], fs),
                            "shared_gate": w(ks[6], d, 1)}}

        def uniform(k, n, lo, hi):
            return jax.random.uniform(k, (n,), F32, lo, hi).astype(dtype)

        def linear(k):
            ks = jax.random.split(k, 14)
            return {**common(ks), "attn": {
                "in_qkvz": w(ks[7], d, 2 * hk * dk + 2 * hv * dv),
                "in_ba": w(ks[8], d, 2 * hv),
                "conv": {"weight": _normal(
                    ks[9], (2 * hk * dk + hv * dv, width), width ** -0.5,
                    dtype)},
                "a_log": uniform(ks[10], hv, -1.4, 0.7),
                "dt_bias": uniform(ks[11], hv, -4.0, -1.0),
                "norm": {"scale": (1.0 + _normal(ks[12], (dv,), 0.1, F32)
                                   ).astype(dtype)},
                "wo": w(ks[13], hv * dv, d)}}

        def full(k):
            ks = jax.random.split(k, 13)
            return {**common(ks), "attn": {
                "wq": w(ks[7], d, h * 2 * hd), "wk": w(ks[8], d, hkv * hd),
                "wv": w(ks[9], d, hkv * hd), "q_norm": offset(ks[10], hd),
                "k_norm": offset(ks[11], hd), "wo": w(ks[12], h * hd, d)}}

        def experts(k):
            return jax.vmap(lambda kk: mlp(jax.random.split(kk, 3), f))(
                jax.random.split(k, held))

        return {"wte": _normal(ke, (v, d), 1.0, dtype),
                "periods": {
                    "gdn": [_stack(periods, k, linear) for k in
                            jax.random.split(kg, interval - 1)],
                    "full": _stack(periods, kf, full)},
                "experts": _stack(s["num_hidden_layers"], kx, experts),
                "ln_f": offset(kn, d),
                "lm_head": w(kh, d, v)}

    def logits(self, params, sizes, ids, positions, weights=None):
        bound = int(sizes.get("serving_env", {}).get("MAX_SEQ", len(ids)))
        ids = list(ids) + [0] * max(bound - len(ids), 0)
        ids = jnp.asarray(ids, jnp.int32)
        frozen = _freeze(sizes)
        interval = sizes["full_attention_interval"]
        h = params["wte"][ids].astype(F32)
        for l in range(sizes["num_hidden_layers"]):
            p, j = divmod(l, interval)
            if j == interval - 1:
                h = self._block(params["periods"]["full"], (p,),
                                params["experts"], l, h, weights=weights,
                                sizes=frozen, full=True)
            else:
                h = self._block(params["periods"]["gdn"][j], (p,),
                                params["experts"], l, h, weights=weights,
                                sizes=frozen, full=False)
        return self._head(params, h[jnp.asarray(positions)],
                          weights=weights, sizes=frozen)

    @staticmethod
    def _linear_attention(a, x, s, weights):
        n = x.shape[0]
        hk, hv = s["linear_num_key_heads"], s["linear_num_value_heads"]
        dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
        r, eps = hv // hk, s["rms_norm_eps"]
        qkvz = _mm(x, a["in_qkvz"]["kernel"], weights)       # [q|k|v|z]
        ba = _mm(x, a["in_ba"]["kernel"], weights)           # [b|a]
        channels = 2 * hk * dk + hv * dv
        u, z = qkvz[:, :channels], qkvz[:, channels:].reshape(n, hv, dv)
        beta = jax.nn.sigmoid(ba[:, :hv])
        g = -jnp.exp(a["a_log"].astype(F32)) * jax.nn.softplus(
            ba[:, hv:] + a["dt_bias"].astype(F32))
        wc = a["conv"]["weight"].astype(F32)                   # [C, width]
        width = wc.shape[1]
        padded = jnp.concatenate(
            [jnp.zeros((width - 1, u.shape[1]), F32), u], axis=0)
        c = jax.nn.silu(sum(padded[j:j + n] * wc[:, j]
                            for j in range(width)))

        def unit(y):
            return y * jax.lax.rsqrt((y * y).sum(-1, keepdims=True) + 1e-6)

        q = unit(c[:, :hk * dk].reshape(n, hk, dk)) / math.sqrt(dk)
        k = unit(c[:, hk * dk:2 * hk * dk].reshape(n, hk, dk))
        v = c[:, 2 * hk * dk:].reshape(n, hv, dv)
        q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)

        def one(state, xs):
            q_t, k_t, v_t, g_t, b_t = xs                     # [Hv, ...]
            state = state * jnp.exp(g_t)[:, None, None]
            kv = jnp.einsum("hk,hkv->hv", k_t, state, precision=HI)
            delta = b_t[:, None] * (v_t - kv)
            state = state + k_t[:, :, None] * delta[:, None, :]
            return state, jnp.einsum("hk,hkv->hv", q_t, state, precision=HI)

        _, o = jax.lax.scan(one, jnp.zeros((hv, dk, dv), F32),
                            (q, k, v, g, beta))
        o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)
        y = o * a["norm"]["scale"].astype(F32) * jax.nn.silu(z)
        return _mm(y.reshape(n, hv * dv), a["wo"]["kernel"], weights)

    @staticmethod
    def _softmax_attention(a, x, s, weights):
        n = x.shape[0]
        h, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
        eps, theta = s["rms_norm_eps"], s["rope_theta"]
        rotary = int(hd * s["partial_rotary_factor"])
        qg = _mm(x, a["wq"]["kernel"], weights)              # [q|gate]
        q = qg[:, :h * hd].reshape(n, h, hd).transpose(1, 0, 2)
        gate = qg[:, h * hd:]
        k = _mm(x, a["wk"]["kernel"], weights).reshape(
            n, hkv, hd).transpose(1, 0, 2)
        v = _mm(x, a["wv"]["kernel"], weights).reshape(
            n, hkv, hd).transpose(1, 0, 2)
        q = _rope_leading(_norm(q, a["q_norm"]["scale"], eps), rotary, theta)
        k = _rope_leading(_norm(k, a["k_norm"]["scale"], eps), rotary, theta)
        k, v = (jnp.repeat(k, h // hkv, axis=0),
                jnp.repeat(v, h // hkv, axis=0))
        outs = []
        for lo in range(0, n, _Q_BLOCK):
            hi = min(lo + _Q_BLOCK, n)
            sc = jnp.einsum("hqd,hkd->hqk", q[:, lo:hi], k[:, :hi],
                            precision=HI) / math.sqrt(hd)
            seen = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,hkd->hqd", p, v[:, :hi],
                                   precision=HI))
        o = jnp.concatenate(outs, axis=1).transpose(1, 0, 2).reshape(n, -1)
        return _mm(o * jax.nn.sigmoid(gate), a["wo"]["kernel"], weights)

    @staticmethod
    def _experts(moe, experts, x, s, weights):
        """The held experts' weighted terms plus the gated shared one."""
        k, first = s["num_experts_per_tok"], s.get("first_expert", 0)
        p = jax.nn.softmax(_mm(x, moe["router"]["kernel"], weights), axis=-1)
        w, chosen = jax.lax.top_k(p, k)
        if s["norm_topk_prob"]:
            w = w / w.sum(-1, keepdims=True)

        def one(y, xs):
            expert, e = xs
            w_e = jnp.where(chosen == first + e, w, 0.0).sum(-1)  # [S]
            return y + w_e[:, None] * _swiglu(x, expert, weights), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                            (experts, jnp.arange(s["num_experts"])))
        share = jax.nn.sigmoid(_mm(x, moe["shared_gate"]["kernel"], weights))
        return y + share * _swiglu(x, moe["shared"], weights)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes", "full"))
    def _block(stack, at, experts, l, h, *, weights, sizes, full):
        s = dict(sizes)
        b = jax.tree.map(lambda x: x[at], stack)
        mine = jax.tree.map(lambda x: x[l], experts)
        eps = s["rms_norm_eps"]
        x = _norm(h, b["ln_attn"]["scale"], eps)
        mixer = (GDNMoE._softmax_attention if full
                 else GDNMoE._linear_attention)
        h = h + mixer(b["attn"], x, s, weights)
        m = _norm(h, b["ln_mlp"]["scale"], eps)
        return h + GDNMoE._experts(b["moe"], mine, m, s, weights)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes"))
    def _head(params, h, *, weights, sizes):
        h = _norm(h, params["ln_f"]["scale"], dict(sizes)["rms_norm_eps"])
        return _mm(h, params["lm_head"]["kernel"], weights)


gdn_moe = GDNMoE()
