"""The plain reference of the state-space / attention family.

Falcon-H1's layer plan (the keys of the ``config.json`` of
``tiiuae/Falcon-H1-34B-Instruct``, checked line by line against
``transformers``' ``modeling_falcon_h1.py``; the selective state-space
rule is Mamba-2's, Dao & Gu 2024, arXiv:2405.21060) in straight
``jax.numpy``, float32, ``precision="highest"``: no kernel, no cache, no
batching, one sequence at a time, independent of
``llm_sharding_demo_tpu/models``. With ``norm(x) = x rsqrt(mean x^2 +
eps) w`` and every multiplier a key of the configuration:

- ``h0 = embed[id] embedding_multiplier``;
- a layer: ``u = norm_in(h)``; ``h += ssm_out_multiplier
  Mixer(ssm_in_multiplier u) + attention_out_multiplier
  Attn(attention_in_multiplier u)``; ``m = norm_ff(h)``; ``h +=
  down_multiplier W_down(W_up m silu(gate_multiplier W_gate m))`` with
  ``[gate_multiplier, down_multiplier] = mlp_multipliers``;
- ``Attn(a)``: ``q = a W_q``, ``k = key_multiplier (a W_k)``, ``v = a
  W_v``, no bias, no per-head norm; rotary (rotate-half) over the whole
  head at ``rope_theta``, no scaling; causal softmax at ``head_dim **
  -0.5``, ``num_attention_heads / num_key_value_heads`` query heads a
  key-value head; ``W_o``;
- ``Mixer(s)``: ``p = (s W_in) mup``, ``mup`` being ``ssm_multipliers[0
  .. 4]`` over the column ranges of ``[z | x | B | C | dt]`` (``d_ssm``,
  ``d_ssm``, ``groups x d_state`` twice, ``heads``); ``[x | B | C]``
  through the depthwise causal convolution of ``mamba_d_conv`` taps WITH
  bias, then SiLU, zeros before position 0; ``dt = softplus(dt +
  dt_bias)`` (no clamp: the published limits are 0 and infinity), ``A =
  -exp(A_log)``, one of each a head; per head ``j`` (``B``, ``C`` of
  group ``j // (heads / groups)``), from ``S = 0``, POSITION BY POSITION
  (``lax.scan``; the chunked form is the program's, not the
  reference's): ``S <- exp(dt A_j) S + dt x B^T`` (``S`` is ``d_head x
  d_state``), ``y = S C + D_j x``; ``y <- y silu(z)`` (the gate FIRST:
  ``mamba_norm_before_gate`` false), RMS norm over each group's channels
  with a scale over all of them; ``W_out``, no bias;
- final ``norm``; ``logits = lm_head_multiplier (h W_head)``, untied.

Departures from the published model, each a note here and nowhere
hidden: weights are seeded random normals (std ``fan_in ** -0.5``, the
embedding 1.0, the convolution's bias 0.1; norm scales and ``D`` 1 + 0.1
N so that a dropped scale shows; ``A_log`` uniform in [-1.4, 0.7] and
``dt_bias`` in [-4, -1], one of each a head: ``assumed`` in the
configuration file), made on the device in the tree layout the
program's family takes (every block leaf ``[layers, ...]``; the two
vocabulary tables in slices, so that no float32 copy of a table is ever
whole); the state here is float32 (the published cache carries it in the
model's type); attention runs over blocks of ``_Q_BLOCK`` queries and
the head over slices of the vocabulary so that a 1,024-position float32
pass fits beside the served model (the same sums, fewer at a time), and
a sequence is right-padded to the configuration's ``MAX_SEQ`` so that
ragged requests share one program (every layer is causal: what follows a
position changes nothing at it). ``weights="int8"`` is ``dense._mm``'s
control: every matrix rounded to int8 codes a column.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .dense import F32, HI, _Reference, _key, _mm, _normal, _stack

_Q_BLOCK = 512
_SLICES = 16          # at most this many slices of a vocabulary table


def _freeze(sizes):
    """Numbers and lists of numbers (the multipliers), hashable."""
    out = []
    for k, v in sizes.items():
        if isinstance(v, (list, tuple)) and all(
                isinstance(x, (int, float)) for x in v):
            out.append((k, tuple(v)))
        elif isinstance(v, (int, float)):
            out.append((k, v))
    return tuple(sorted(out))


def _slices(n: int) -> int:
    return max(s for s in range(1, _SLICES + 1) if n % s == 0)


def _norm(x, w, eps):
    return (x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
            * w.astype(F32))


def _rope(x, theta):
    """x [..., S, hd]: rotate-half over the whole head by position."""
    s, hd = x.shape[-2], x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv            # [S, hd/2]
    ang = jnp.concatenate([ang, ang], axis=-1)
    turned = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


class HybridSSM(_Reference):

    def init(self, sizes, seed, dtype=jnp.bfloat16):
        return self._init(_freeze(sizes), _key(seed), jnp.dtype(dtype))

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(0, 2))
    def _init(sizes, key, dtype):
        s = dict(sizes)
        d, v, f = s["hidden_size"], s["vocab_size"], s["intermediate_size"]
        h, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
        dssm, heads = s["mamba_d_ssm"], s["mamba_n_heads"]
        channels = dssm + 2 * s["mamba_n_groups"] * s["mamba_d_state"]
        width = s["mamba_d_conv"]
        ke, kh, kb, kn = jax.random.split(key, 4)

        def w(k, a, b):
            return {"kernel": _normal(k, (a, b), a ** -0.5, dtype)}

        def scale(k, n):
            return {"scale": (1.0 + _normal(k, (n,), 0.1, F32)).astype(dtype)}

        def uniform(k, n, lo, hi):
            return jax.random.uniform(k, (n,), F32, lo, hi).astype(dtype)

        def one(k):
            ks = jax.random.split(k, 17)
            return {
                "ln_attn": scale(ks[0], d),
                "ssm": {
                    "in_proj": w(ks[1], d, dssm + channels + heads),
                    "conv": {"weight": _normal(ks[2], (channels, width),
                                               width ** -0.5, dtype),
                             "bias": _normal(ks[3], (channels,), 0.1,
                                             dtype)},
                    "dt_bias": uniform(ks[4], heads, -4.0, -1.0),
                    "a_log": uniform(ks[5], heads, -1.4, 0.7),
                    "d": scale(ks[6], heads)["scale"],
                    "norm": scale(ks[7], dssm),
                    "out_proj": w(ks[8], dssm, d)},
                "attn": {"wq": w(ks[9], d, h * hd),
                         "wk": w(ks[10], d, hkv * hd),
                         "wv": w(ks[11], d, hkv * hd),
                         "wo": w(ks[12], h * hd, d)},
                "ln_mlp": scale(ks[13], d),
                "mlp": {"gate": w(ks[14], d, f), "up": w(ks[15], d, f),
                        "down": w(ks[16], f, d)}}

        nv, nd = _slices(v), _slices(d)
        wte = _stack(nv, ke, lambda k: _normal(k, (v // nv, d), 1.0, dtype))
        head = _stack(nd, kh, lambda k: _normal(k, (d // nd, v), d ** -0.5,
                                                dtype))
        return {"wte": wte.reshape(v, d),
                "blocks": _stack(s["num_hidden_layers"], kb, one),
                "ln_f": scale(kn, d),
                "lm_head": {"kernel": head.reshape(d, v)}}

    def logits(self, params, sizes, ids, positions, weights=None):
        bound = int(sizes.get("serving_env", {}).get("MAX_SEQ", len(ids)))
        ids = list(ids) + [0] * max(bound - len(ids), 0)
        ids = jnp.asarray(ids, jnp.int32)
        frozen = _freeze(sizes)
        h = self._embed(params, ids, sizes=frozen)
        for l in range(sizes["num_hidden_layers"]):
            h = self._block(params["blocks"], l, h, weights=weights,
                            sizes=frozen)
        return self._head(params, h[jnp.asarray(positions)],
                          weights=weights, sizes=frozen)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("sizes",))
    def _embed(params, ids, *, sizes):
        return params["wte"][ids].astype(F32) * dict(sizes)[
            "embedding_multiplier"]

    @staticmethod
    def _mixer(m, x, s, weights):
        n = x.shape[0]
        heads, p, ds, g = (s["mamba_n_heads"], s["mamba_d_head"],
                           s["mamba_d_state"], s["mamba_n_groups"])
        dssm, gn = s["mamba_d_ssm"], g * ds
        mz, mx, mb, mc, mdt = s["ssm_multipliers"]
        proj = _mm(x, m["in_proj"]["kernel"], weights)   # [z|x|B|C|dt]
        z = proj[:, :dssm] * mz
        u = jnp.concatenate([proj[:, dssm:2 * dssm] * mx,
                             proj[:, 2 * dssm:2 * dssm + gn] * mb,
                             proj[:, 2 * dssm + gn:2 * dssm + 2 * gn] * mc],
                            axis=-1)
        dt = jax.nn.softplus(proj[:, 2 * dssm + 2 * gn:] * mdt
                             + m["dt_bias"].astype(F32))     # [S, heads]
        wc = m["conv"]["weight"].astype(F32)                 # [C, width]
        width = wc.shape[1]
        padded = jnp.concatenate(
            [jnp.zeros((width - 1, u.shape[1]), F32), u], axis=0)
        c = jax.nn.silu(sum(padded[j:j + n] * wc[:, j] for j in range(width))
                        + m["conv"]["bias"].astype(F32))
        xs = c[:, :dssm].reshape(n, heads, p)
        bm = jnp.repeat(c[:, dssm:dssm + gn].reshape(n, g, ds),
                        heads // g, axis=1)                  # [S, heads, N]
        cm = jnp.repeat(c[:, dssm + gn:].reshape(n, g, ds), heads // g,
                        axis=1)
        a = -jnp.exp(m["a_log"].astype(F32))                 # [heads]

        def one(state, step):                                # [heads, P, N]
            x_t, dt_t, b_t, c_t = step
            state = (state * jnp.exp(dt_t * a)[:, None, None]
                     + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return state, jnp.einsum("hpn,hn->hp", state, c_t, precision=HI)

        _, y = jax.lax.scan(one, jnp.zeros((heads, p, ds), F32),
                            (xs, dt, bm, cm))
        y = (y + m["d"].astype(F32)[:, None] * xs).reshape(n, dssm)
        y = (y * jax.nn.silu(z)).reshape(n, g, dssm // g)
        y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True)
                              + s["rms_norm_eps"])
        y = y.reshape(n, dssm) * m["norm"]["scale"].astype(F32)
        return _mm(y, m["out_proj"]["kernel"], weights)

    @staticmethod
    def _attention(a, x, s, weights):
        n = x.shape[0]
        h, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
        theta = s["rope_theta"]

        def heads(y, count):
            return y.reshape(n, count, hd).transpose(1, 0, 2)

        q = _rope(heads(_mm(x, a["wq"]["kernel"], weights), h), theta)
        k = _rope(heads(_mm(x, a["wk"]["kernel"], weights)
                        * s["key_multiplier"], hkv), theta)
        v = heads(_mm(x, a["wv"]["kernel"], weights), hkv)
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
        return _mm(o, a["wo"]["kernel"], weights)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes"))
    def _block(blocks, l, h, *, weights, sizes):
        s = dict(sizes)
        b = jax.tree.map(lambda x: x[l], blocks)
        eps = s["rms_norm_eps"]
        gate_m, down_m = s["mlp_multipliers"]
        u = _norm(h, b["ln_attn"]["scale"], eps)
        h = (h + s["ssm_out_multiplier"] * HybridSSM._mixer(
                 b["ssm"], u * s["ssm_in_multiplier"], s, weights)
             + s["attention_out_multiplier"] * HybridSSM._attention(
                 b["attn"], u * s["attention_in_multiplier"], s, weights))
        m = _norm(h, b["ln_mlp"]["scale"], eps)
        mlp = b["mlp"]
        y = (_mm(m, mlp["up"]["kernel"], weights) * jax.nn.silu(
            _mm(m, mlp["gate"]["kernel"], weights) * gate_m))
        return h + down_m * _mm(y, mlp["down"]["kernel"], weights)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes"))
    def _head(params, h, *, weights, sizes):
        s = dict(sizes)
        h = _norm(h, params["ln_f"]["scale"], s["rms_norm_eps"])
        kernel = params["lm_head"]["kernel"]
        v = kernel.shape[1]
        count = _slices(v)

        def part(_, j):
            cols = jax.lax.dynamic_slice_in_dim(kernel, j * (v // count),
                                                v // count, axis=1)
            return None, _mm(h, cols, weights)

        _, parts = jax.lax.scan(part, None, jnp.arange(count))
        return (jnp.moveaxis(parts, 0, 1).reshape(h.shape[0], v)
                * s["lm_head_multiplier"])


hybrid_ssm = HybridSSM()
