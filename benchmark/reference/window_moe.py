"""The plain reference of the sliding-window / sparse-expert family.

K-EXAONE's layer plan (the keys of the ``config.json`` of
``LGAI-EXAONE/K-EXAONE-236B-A23B``, ``model_type`` ``exaone_moe``) in
straight ``jax.numpy``, float32, ``precision="highest"``: no kernel, no
cache, no ring, no batching, one sequence at a time, independent of
``llm_sharding_demo_tpu/models``. With ``norm(x; w) = x rsqrt(mean x^2 +
eps) w`` and ``x = norm(h; w1)``:

- **attention**, every layer: ``q = x W_q``, ``k = x W_k``, ``v = x
  W_v`` without biases; per head ``q <- norm(q; w_qn)``, ``k <- norm(k;
  w_kn)``; on a SLIDING layer (``layer_types[l] ==
  "sliding_attention"``: every layer but each ``full_attention_interval``
  -th) rotary on ``q`` and ``k`` (rotate-half over the whole head,
  ``rope_theta``, absolute positions), on a FULL layer none; scores ``q_i
  . k_j / sqrt(head_dim)``, allowed iff ``j <= i`` and (the layer is
  full or ``i - j < sliding_window``): the banded mask is written as a
  mask; softmax; ``num_attention_heads / num_key_value_heads`` query
  heads a key-value head; ``concat(heads) W_o``;
- **feed-forward** on ``m = norm(h; w2)``: the first
  ``first_k_dense_replace`` layers ``SwiGLU(m) = (silu(m W_g) * (m W_u))
  W_d`` at ``intermediate_size``; every later layer ``s = sigmoid(m
  W_r)`` over all ``published_num_experts``, the ``num_experts_per_tok``
  largest of ``s + b``, ``w = routed_scaling_factor s[chosen] / sum
  s[chosen]``; ``sum_e w_e SwiGLU_e(m)`` over the experts HELD (the
  configuration's ``num_experts`` ids from ``first_expert``; the others'
  terms are left out, as in the program) plus ``SwiGLU_shared(m)``;
- ``h += attention``, ``h += feed-forward``; final ``norm``; untied head.

Departures from the published model, each a note here and nowhere
hidden: weights are seeded random normals (std ``fan_in ** -0.5``, the
embedding 1.0, norm weights ``1 + 0.1 N``, the selection bias 0.1:
``assumed`` in the configuration file), made on the device in the tree
layout the program's family takes (the first period's layers as a list
of trees, ``head``; the other periods' as a list of trees one a place in
the period, every leaf ``[periods - 1, ...]``; the routed experts'
``[expert layers, held, ...]``); the two norms of a block norm the
sub-layer's INPUT and rotary turns the sliding layers alone (the
catalog's ``config`` has a key for neither: ``assumed``); the
multi-token-prediction module the published model ships is no part of
the next-token pass and has no weights here; attention runs over blocks
of ``_Q_BLOCK`` queries and the feed-forward over blocks of ``_ROWS``
positions so that an 8,704-position float32 pass fits beside the served
model (the same sums, fewer at a time), and a sequence is right-padded
to the next of a few lengths (2,048, its double, then the
configuration's ``MAX_SEQ``) and the positions asked for to whole
``_POSITIONS`` so that ragged requests share programs (every layer is
causal: what follows a position changes nothing at it). A run of the
cell compiles these programs anew, and the driver gives a whole run
1,200 s: nine layer programs and one head, not the fifteen and eight
of a finer ladder.
``weights="int8"`` is ``dense._mm``'s control: every matrix, the
router's too, rounded to int8 codes a column.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .dense import F32, HI, _Reference, _freeze, _key, _mm, _normal, _stack

_Q_BLOCK = 256
_ROWS = 512
_POSITIONS = 512


def _norm(x, w, eps):
    return (x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
            * w.astype(F32))


def _rope(x, theta):
    """x [..., S, hd]: rotate-half over the whole head by position."""
    s, hd = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv            # [S, hd/2]
    ang = jnp.concatenate([ang, ang], axis=-1)
    turned = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def _swiglu(x, mlp, weights):
    return _mm(jax.nn.silu(_mm(x, mlp["gate"]["kernel"], weights))
               * _mm(x, mlp["up"]["kernel"], weights),
               mlp["down"]["kernel"], weights)


def _in_rows(fn, x):
    """``fn`` over blocks of ``_ROWS`` positions of ``x`` [S, d]."""
    n = x.shape[0]
    if n <= _ROWS or n % _ROWS:
        return fn(x)
    return jax.lax.map(fn, x.reshape(n // _ROWS, _ROWS, -1)).reshape(n, -1)


class WindowMoE(_Reference):

    def init(self, sizes, seed, dtype=jnp.bfloat16):
        return self._init(_freeze(sizes), _key(seed), jnp.dtype(dtype))

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(0, 2))
    def _init(sizes, key, dtype):
        s = dict(sizes)
        d, v = s["hidden_size"], s["vocab_size"]
        h, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
        f, held = s["moe_intermediate_size"], s["num_experts"]
        total = s["published_num_experts"]
        interval = s["full_attention_interval"]
        n_dense = s["first_k_dense_replace"]
        periods = s["num_hidden_layers"] // interval
        ke, kh, k0, kp, kx, kn = jax.random.split(key, 6)

        def w(k, a, b):
            return {"kernel": _normal(k, (a, b), a ** -0.5, dtype)}

        def scale(k, n):
            return {"scale": (1.0 + _normal(k, (n,), 0.1, F32)
                              ).astype(dtype)}

        def mlp(ks, wide):
            return {"gate": w(ks[0], d, wide), "up": w(ks[1], d, wide),
                    "down": w(ks[2], wide, d)}

        def layer(k, dense):
            ks = jax.random.split(k, 13)
            out = {"ln_attn": scale(ks[0], d), "ln_mlp": scale(ks[1], d),
                   "attn": {"wq": w(ks[2], d, h * hd),
                            "wk": w(ks[3], d, hkv * hd),
                            "wv": w(ks[4], d, hkv * hd),
                            "q_norm": scale(ks[5], hd),
                            "k_norm": scale(ks[6], hd),
                            "wo": w(ks[7], h * hd, d)}}
            if dense:
                out["mlp"] = mlp(ks[8:11], s["intermediate_size"])
            else:
                out["moe"] = {
                    "router": {"kernel": w(ks[8], d, total)["kernel"],
                               "bias": _normal(ks[9], (total,), 0.1, F32)},
                    "shared": mlp(ks[10:13], f * s["num_shared_experts"])}
            return out

        def experts(k):
            return jax.vmap(lambda kk: mlp(jax.random.split(kk, 3), f))(
                jax.random.split(k, held))

        return {"wte": _normal(ke, (v, d), 1.0, dtype),
                "head": [layer(k, j < n_dense) for j, k in
                         enumerate(jax.random.split(k0, interval))],
                "periods": [_stack(periods - 1, k,
                                   lambda kk: layer(kk, False))
                            for k in jax.random.split(kp, interval)],
                "experts": _stack(s["num_hidden_layers"] - n_dense, kx,
                                  experts),
                "ln_f": scale(kn, d),
                "lm_head": w(kh, d, v)}

    def logits(self, params, sizes, ids, positions, weights=None):
        top = int(sizes.get("serving_env", {}).get("MAX_SEQ", len(ids)))
        bound = 2048
        while bound < len(ids):
            bound *= 2
        if 2 * bound > top:
            bound = top
        bound = max(bound, len(ids))
        ids = jnp.asarray(list(ids) + [0] * (bound - len(ids)), jnp.int32)
        asked = len(positions)
        positions = list(positions) + [positions[-1]] * (-asked % _POSITIONS)
        frozen = _freeze(sizes)
        interval = sizes["full_attention_interval"]
        n_dense = sizes["first_k_dense_replace"]
        h = params["wte"][ids].astype(F32)
        for l in range(sizes["num_hidden_layers"]):
            p, j = divmod(l, interval)
            b = (params["head"][j] if p == 0 else
                 jax.tree.map(lambda x: x[p - 1], params["periods"][j]))
            mine = (None if l < n_dense else jax.tree.map(
                lambda x: x[l - n_dense], params["experts"]))
            h = self._block(b, mine, h, weights=weights, sizes=frozen,
                            full=sizes["layer_types"][l] == "full_attention")
        return self._head(params, h[jnp.asarray(positions)],
                          weights=weights, sizes=frozen)[:asked]

    @staticmethod
    def _attention(a, x, s, weights, full):
        n = x.shape[0]
        h, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
        eps, window = s["rms_norm_eps"], s["sliding_window"]
        q = _mm(x, a["wq"]["kernel"], weights).reshape(
            n, h, hd).transpose(1, 0, 2)
        k = _mm(x, a["wk"]["kernel"], weights).reshape(
            n, hkv, hd).transpose(1, 0, 2)
        v = _mm(x, a["wv"]["kernel"], weights).reshape(
            n, hkv, hd).transpose(1, 0, 2)
        q = _norm(q, a["q_norm"]["scale"], eps)
        k = _norm(k, a["k_norm"]["scale"], eps)
        if not full:
            q, k = _rope(q, s["rope_theta"]), _rope(k, s["rope_theta"])
        # blocks of queries one after another, every block the same
        # shapes (a loop written out is a program that grows with the
        # length: 34 blocks at 8,704 positions compiled for minutes):
        # a full layer's block reads every key and masks what follows
        # it, a sliding layer's the ``window + block`` keys that can
        # reach it, out of keys padded in front by a window
        block = min(_Q_BLOCK, n)
        nb = -(-n // block)
        front = 0 if full else window
        reach = nb * block if full else window + block
        q = jnp.pad(q, ((0, 0), (0, nb * block - n), (0, 0)))
        k = jnp.pad(k, ((0, 0), (front, nb * block - n), (0, 0)))
        v = jnp.pad(v, ((0, 0), (front, nb * block - n), (0, 0)))
        q = q.reshape(hkv, h // hkv, nb, block, hd).transpose(2, 0, 1, 3, 4)

        def one(xs):
            qb, lo = xs
            first = 0 if full else lo            # in the padded keys
            kb = jax.lax.dynamic_slice_in_dim(k, first, reach, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, first, reach, axis=1)
            sc = jnp.einsum("kgqd,kud->kgqu", qb, kb,
                            precision=HI) / math.sqrt(hd)
            i = lo + jnp.arange(block)[:, None]
            j = first - front + jnp.arange(reach)[None, :]
            seen = (j <= i) & (j >= 0)
            if not full:
                seen = seen & (i - j < window)
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
            return jnp.einsum("kgqu,kud->kgqd", p, vb, precision=HI)

        o = jax.lax.map(one, (q, jnp.arange(nb) * block))
        o = o.transpose(1, 2, 0, 3, 4).reshape(h, nb * block, hd)[:, :n]
        o = o.transpose(1, 0, 2).reshape(n, -1)
        return _mm(o, a["wo"]["kernel"], weights)

    @staticmethod
    def _experts(moe, experts, x, s, weights):
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

        y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                            (experts, jnp.arange(s["num_experts"])))
        return y + _swiglu(x, moe["shared"], weights)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes", "full"))
    def _block(b, experts, h, *, weights, sizes, full):
        s = dict(sizes)
        eps = s["rms_norm_eps"]
        h = h + WindowMoE._attention(
            b["attn"], _norm(h, b["ln_attn"]["scale"], eps), s, weights,
            full)
        m = _norm(h, b["ln_mlp"]["scale"], eps)
        if "mlp" in b:
            return h + _in_rows(lambda r: _swiglu(r, b["mlp"], weights), m)
        return h + _in_rows(lambda r: WindowMoE._experts(
            b["moe"], experts, r, s, weights), m)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes"))
    def _head(params, h, *, weights, sizes):
        h = _norm(h, params["ln_f"]["scale"], dict(sizes)["rms_norm_eps"])
        return _mm(h, params["lm_head"]["kernel"], weights)


window_moe = WindowMoE()
