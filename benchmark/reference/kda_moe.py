"""The plain reference of the per-channel delta-rule / latent-attention
/ sparse-expert family.

Kimi-Linear's layer plan (the keys of the ``config.json`` of
``moonshotai/Kimi-Linear-48B-A3B-Instruct``; the rule is Kimi Delta
Attention, Kimi Team 2025, arXiv:2510.26692) in straight ``jax.numpy``,
float32, ``precision="highest"``: no kernel, no cache, no batching, one
sequence at a time, independent of ``llm_sharding_demo_tpu/models``.
With ``norm(x) = x rsqrt(mean x^2 + eps) w``, ``x = norm1(h)`` and ``d``
the hidden size:

- layer ``l`` (from 1) is a **delta-rule layer** iff ``l`` is in
  ``linear_attn_config.kda_layers`` (``H`` heads of ``K = V =
  head_dim``): ``q~ = x W_q``, ``k~ = x W_k``, ``v~ = x W_v``; ``c_t =
  silu(sum_j w_conv[:, j] u_{t-3+j})`` over ``u = [q~ | k~ | v~]``,
  zeros before position 0; per head ``q = c_q / |c_q| / sqrt(K)``, ``k
  = c_k / |c_k|``, ``v = c_v``; ``g = -exp(A_log[h]) softplus((x W_fa)
  W_fb + dt_bias)`` in ``R^{H x K}`` (one decay a key CHANNEL), ``beta
  = sigmoid(x W_b)`` in ``R^H``; per head, from ``S = 0``, POSITION BY
  POSITION (``lax.scan``; the chunked form is the program's, not the
  reference's): ``S <- Diag(e^g) S``, ``d = beta (v - S^T k)``, ``S <-
  S + k d^T``, ``o = S^T q``; ``y = o rsqrt(mean o^2 + eps) w_n
  sigmoid((x W_ga) W_gb)`` per head; ``y W_o``;
- every other layer (``full_attn_layers``) is **latent attention with
  no positions**: ``[q_n | q_r] = x W_q`` per head (``q_lora_rank``
  null: no bottleneck); ``[c | k_r] = x W_dkv``, ``c <- norm(c)``;
  ``k_n = c W_uk``, ``v = c W_uv`` per head, ``k_r`` shared by all
  heads; NOTHING is rotated (``mla_use_nope``); scores ``(q_n . k_n +
  q_r . k_r) / sqrt(nope + rope)``, causal softmax, ``P v``, heads
  concatenated through ``W_o``;
- feed-forward on ``m = norm2(h)``: SwiGLU of ``intermediate_size`` in
  the leading ``first_k_dense_replace`` layers; after them ``s =
  sigmoid(m W_r)`` over all ``published_num_experts``, the
  ``num_experts_per_token`` largest of ``s + b`` (``num_expert_group``
  1: group-limited choice is the identity and not written out), ``w =
  s[chosen] / (sum + 1e-20) * routed_scaling_factor``
  (``moe_renormalize``), ``sum_e w_e SwiGLU_e(m)`` over the experts
  HELD (the configuration's ``num_experts`` ids from ``first_expert``;
  the others' terms are left out, as in the program) plus the shared
  expert;
- ``h += mixer``, ``h += feed-forward``; final ``norm``; untied head.

Departures from the published model, each a note here and nowhere
hidden: weights are seeded random normals (std ``fan_in ** -0.5``, the
embedding 1.0, the norms' scales 1, ``w_n`` 1 + 0.1 N, the selection
bias 0.1, ``A_log`` uniform in [-1.4, 0.7] a head and ``dt_bias`` in
[-4, -1] a channel so that a channel's decay ``exp(g)`` a position
spans roughly 0.5 to 0.999: ``assumed`` in the configuration file),
made on the device in the tree layout the program's family takes:
the stack is covered by repeats of runs of layers so that the fewest
layers are written out (the published 27: the dense first layer, six
times delta, delta, latent, delta, then delta, latent), a group a list
of trees, one a place in the run, every leaf ``[repeats, ...]``
(``_plan`` below works it out from the two published lists, on its
own); the routed experts ``[expert layers, held, ...]``. ``W_q``, ``W_k``, ``W_v`` of a delta-rule layer are
stored side by side as ``in_qkv`` (and their three convolutions as
one), ``W_fa``, ``W_ga`` and ``W_b`` as ``in_low``, and the latent
layers' ``W_ukv`` as its two column blocks: fixed concatenations and
cuts of columns, which a checkpoint would go through once at loading
and which change no result. The low-rank gates' width (128 =
``linear_attn_config.head_dim``) and the absence of any bias on a
projection are not keys of the published config (``assumed``).
Attention runs over blocks of ``_Q_BLOCK`` queries (``lax.map``) so
that a float32 pass fits beside the served model (the same sums, fewer
at a time), and a sequence is right-padded to the next multiple of
``_PAD`` positions (at most the configuration's ``MAX_SEQ``: five
lengths) so that ragged requests share programs (every layer is causal:
what follows a position changes nothing at it; the rule walks a
sequence position by position, 20 layers of it, so a pass costs what
its padded length is, and a window's sequences are a third to a half of
``MAX_SEQ``).
``weights="int8"`` is ``dense._mm``'s control: every matrix, the
router's and the gates' too, rounded to int8 codes a column.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .dense import F32, HI, _Reference, _freeze, _key, _mm, _normal, _stack

_Q_BLOCK = 512
_PAD = 512


def _norm(x, w, eps):
    return (x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
            * w.astype(F32))


def _swiglu(x, mlp, weights):
    return _mm(jax.nn.silu(_mm(x, mlp["gate"]["kernel"], weights))
               * _mm(x, mlp["up"]["kernel"], weights),
               mlp["down"]["kernel"], weights)


def _plan(sizes):
    """The groups of the program's parameter tree: ``[(make, repeats)]``
    with ``make`` the ``(latent?, dense?)`` of a run's layers. The tree
    covers the stack with repeats of runs so that the fewest layers are
    written out, of equal covers the one in fewer groups, of those the
    first met going by a run's width and then its repeats; the same
    rule, worked out here on its own."""
    full = set(sizes["linear_attn_config"]["full_attn_layers"])
    n = sizes["num_hidden_layers"]
    layers = [(l + 1 in full, l < sizes["first_k_dense_replace"])
              for l in range(n)]
    cheapest = {n: (0, 0, [])}
    for i in reversed(range(n)):
        found = []
        for width in range(1, n - i + 1):
            run, repeats = layers[i:i + width], 1
            while layers[i + repeats * width:
                         i + (repeats + 1) * width] == run:
                repeats += 1
            for r in range(1, repeats + 1):
                cost, groups, rest = cheapest[i + r * width]
                found.append((cost + width, groups + 1,
                              [(i, tuple(run), r)] + rest))
        cheapest[i] = min(found, key=lambda f: f[:2])  # the first of equals
    return [(make, repeats) for _, make, repeats in cheapest[0][2]]


def _flat(sizes):
    """The numbers a jitted block needs, the nested group's among them."""
    la = sizes["linear_attn_config"]
    return _freeze(dict(sizes, linear_heads=la["num_heads"],
                        linear_head_dim=la["head_dim"],
                        conv_width=la["short_conv_kernel_size"]))


class KDAMoE(_Reference):

    def init(self, sizes, seed, dtype=jnp.bfloat16):
        groups = tuple(_plan(sizes))
        return self._init(_flat(sizes), groups, _key(seed), jnp.dtype(dtype))

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(0, 1, 3))
    def _init(sizes, groups, key, dtype):
        s = dict(sizes)
        d, v, h = s["hidden_size"], s["vocab_size"], s["num_attention_heads"]
        rkv = s["kv_lora_rank"]
        nope, rope, vd = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                          s["v_head_dim"])
        hl, hd, width = s["linear_heads"], s["linear_head_dim"], \
            s["conv_width"]
        rank = hd                       # the gates' low-rank width
        f, held = s["moe_intermediate_size"], s["num_experts"]
        total = s["published_num_experts"]
        n_dense = s["first_k_dense_replace"]
        ke, kh, kg, kx = jax.random.split(key, 4)

        def w(k, a, b):
            return {"kernel": _normal(k, (a, b), a ** -0.5, dtype)}

        def ones(n):
            return {"scale": jnp.ones((n,), dtype)}

        def mlp(ks, wide):
            return {"gate": w(ks[0], d, wide), "up": w(ks[1], d, wide),
                    "down": w(ks[2], wide, d)}

        def uniform(k, n, lo, hi):
            return jax.random.uniform(k, (n,), F32, lo, hi).astype(dtype)

        def delta(ks):
            return {
                "in_qkv": w(ks[0], d, 3 * hl * hd),
                "in_low": w(ks[1], d, 2 * rank + hl),
                "conv": {"weight": _normal(ks[2], (3 * hl * hd, width),
                                           width ** -0.5, dtype)},
                "f_b": w(ks[3], rank, hl * hd), "g_b": w(ks[4], rank, hl * hd),
                "a_log": uniform(ks[5], hl, -1.4, 0.7),
                "dt_bias": uniform(ks[6], hl * hd, -4.0, -1.0),
                "norm": {"scale": (1.0 + _normal(ks[7], (hd,), 0.1, F32)
                                   ).astype(dtype)},
                "wo": w(ks[8], hl * hd, d)}

        def latent(ks):
            return {"wq": w(ks[0], d, h * (nope + rope)),
                    "wdkv": w(ks[1], d, rkv + rope), "kv_norm": ones(rkv),
                    "wuk": w(ks[2], rkv, h * nope),
                    "wuv": w(ks[3], rkv, h * vd), "wo": w(ks[4], h * vd, d)}

        def layer(is_latent, dense):
            def one(k):
                ks = jax.random.split(k, 14)
                tree = {"ln_attn": ones(d), "ln_mlp": ones(d),
                        "attn": latent(ks) if is_latent else delta(ks)}
                if dense:
                    tree["mlp"] = mlp(ks[9:12], s["intermediate_size"])
                else:
                    tree["moe"] = {
                        "router": {"kernel": w(ks[9], d, total)["kernel"],
                                   "bias": _normal(ks[10], (total,), 0.1,
                                                   dtype)},
                        "shared": mlp(ks[11:14],
                                      f * s["num_shared_experts"])}
                return tree
            return one

        def experts(k):
            return jax.vmap(lambda kk: mlp(jax.random.split(kk, 3), f))(
                jax.random.split(k, held))

        gkeys = jax.random.split(kg, len(groups))
        return {"wte": _normal(ke, (v, d), 1.0, dtype),
                "groups": [
                    [_stack(runs, k, layer(*place)) for k, place in
                     zip(jax.random.split(gk, len(make)), make)]
                    for gk, (make, runs) in zip(gkeys, groups)],
                "experts": _stack(s["num_hidden_layers"] - n_dense, kx,
                                  experts),
                "ln_f": ones(d),
                "lm_head": w(kh, d, v)}

    def logits(self, params, sizes, ids, positions, weights=None):
        bound = int(sizes.get("serving_env", {}).get("MAX_SEQ", len(ids)))
        bound = min(-(-len(ids) // _PAD) * _PAD, max(bound, len(ids)))
        ids = list(ids) + [0] * (bound - len(ids))
        ids = jnp.asarray(ids, jnp.int32)
        frozen = _flat(sizes)
        n_dense = sizes["first_k_dense_replace"]
        h = params["wte"][ids].astype(F32)
        l = 0
        for places, (make, runs) in zip(params["groups"], _plan(sizes)):
            for run in range(runs):
                for stack, (is_latent, dense) in zip(places, make):
                    h = self._block(stack, run, params["experts"],
                                    max(l - n_dense, 0), h, weights=weights,
                                    sizes=frozen, latent=is_latent,
                                    dense=dense)
                    l += 1
        return self._head(params, h[jnp.asarray(positions)],
                          weights=weights, sizes=frozen)

    @staticmethod
    def _delta_attention(a, x, s, weights):
        n = x.shape[0]
        h, hd = s["linear_heads"], s["linear_head_dim"]
        rank, eps = hd, s["rms_norm_eps"]
        u = _mm(x, a["in_qkv"]["kernel"], weights)            # [q|k|v]
        low = _mm(x, a["in_low"]["kernel"], weights)          # [fa|ga|b]
        f = _mm(low[:, :rank], a["f_b"]["kernel"], weights).reshape(n, h, hd)
        gate = _mm(low[:, rank:2 * rank], a["g_b"]["kernel"],
                   weights).reshape(n, h, hd)
        beta = jax.nn.sigmoid(low[:, 2 * rank:])              # [S, H]
        g = -jnp.exp(a["a_log"].astype(F32))[:, None] * jax.nn.softplus(
            f + a["dt_bias"].astype(F32).reshape(h, hd))      # [S, H, K]
        wc = a["conv"]["weight"].astype(F32)                  # [C, width]
        width = wc.shape[1]
        padded = jnp.concatenate(
            [jnp.zeros((width - 1, u.shape[1]), F32), u], axis=0)
        c = jax.nn.silu(sum(padded[j:j + n] * wc[:, j]
                            for j in range(width)))

        def unit(y):
            return y * jax.lax.rsqrt((y * y).sum(-1, keepdims=True) + 1e-6)

        q = unit(c[:, :h * hd].reshape(n, h, hd)) / math.sqrt(hd)
        k = unit(c[:, h * hd:2 * h * hd].reshape(n, h, hd))
        v = c[:, 2 * h * hd:].reshape(n, h, hd)

        def one(state, xs):
            q_t, k_t, v_t, g_t, b_t = xs                      # [H, ...]
            state = state * jnp.exp(g_t)[:, :, None]
            kv = jnp.einsum("hk,hkv->hv", k_t, state, precision=HI)
            delta = b_t[:, None] * (v_t - kv)
            state = state + k_t[:, :, None] * delta[:, None, :]
            return state, jnp.einsum("hk,hkv->hv", q_t, state, precision=HI)

        _, o = jax.lax.scan(one, jnp.zeros((h, hd, hd), F32),
                            (q, k, v, g, beta))
        o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)
        y = o * a["norm"]["scale"].astype(F32) * jax.nn.sigmoid(gate)
        return _mm(y.reshape(n, h * hd), a["wo"]["kernel"], weights)

    @staticmethod
    def _latent_attention(a, x, s, weights):
        n, h = x.shape[0], s["num_attention_heads"]
        nope, rope = s["qk_nope_head_dim"], s["qk_rope_head_dim"]
        rank, eps = s["kv_lora_rank"], s["rms_norm_eps"]
        q = _mm(x, a["wq"]["kernel"], weights).reshape(
            n, h, nope + rope).transpose(1, 0, 2)             # [H, S, 192]
        down = _mm(x, a["wdkv"]["kernel"], weights)
        c_kv = _norm(down[:, :rank], a["kv_norm"]["scale"], eps)
        k_r = down[:, rank:]                                  # [S, rope]
        k_n = _mm(c_kv, a["wuk"]["kernel"], weights).reshape(
            n, h, nope).transpose(1, 0, 2)
        v = _mm(c_kv, a["wuv"]["kernel"], weights).reshape(
            n, h, -1).transpose(1, 0, 2)
        block = min(_Q_BLOCK, n)
        nb = -(-n // block)
        q = jnp.pad(q, ((0, 0), (0, nb * block - n), (0, 0)))
        q = q.reshape(h, nb, block, nope + rope).transpose(1, 0, 2, 3)

        def one(xs):
            qb, lo = xs
            sc = (jnp.einsum("hqd,hkd->hqk", qb[..., :nope], k_n,
                             precision=HI)
                  + jnp.einsum("hqd,kd->hqk", qb[..., nope:], k_r,
                               precision=HI)) / math.sqrt(nope + rope)
            seen = jnp.arange(n)[None, :] <= lo + jnp.arange(block)[:, None]
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,hkd->hqd", p, v, precision=HI)

        o = jax.lax.map(one, (q, jnp.arange(nb) * block))     # [nb,H,blk,v]
        o = o.transpose(0, 2, 1, 3).reshape(nb * block, -1)[:n]
        return _mm(o, a["wo"]["kernel"], weights)

    @staticmethod
    def _experts(moe, experts, x, s, weights):
        """The held experts' weighted terms plus the shared expert."""
        k, first = s["num_experts_per_token"], s.get("first_expert", 0)
        score = jax.nn.sigmoid(_mm(x, moe["router"]["kernel"], weights))
        _, chosen = jax.lax.top_k(
            score + moe["router"]["bias"].astype(F32), k)
        w = jnp.take_along_axis(score, chosen, axis=-1)
        if s["moe_renormalize"]:
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
    @functools.partial(jax.jit, static_argnames=("weights", "sizes",
                                                 "latent", "dense"))
    def _block(stack, run, experts, el, h, *, weights, sizes, latent, dense):
        s = dict(sizes)
        b = jax.tree.map(lambda x: x[run], stack)
        eps = s["rms_norm_eps"]
        x = _norm(h, b["ln_attn"]["scale"], eps)
        mixer = (KDAMoE._latent_attention if latent
                 else KDAMoE._delta_attention)
        h = h + mixer(b["attn"], x, s, weights)
        m = _norm(h, b["ln_mlp"]["scale"], eps)
        if dense:
            return h + _swiglu(m, b["mlp"], weights)
        mine = jax.tree.map(lambda x: x[el], experts)
        return h + KDAMoE._experts(b["moe"], mine, m, s, weights)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes"))
    def _head(params, h, *, weights, sizes):
        h = _norm(h, params["ln_f"]["scale"], dict(sizes)["rms_norm_eps"])
        return _mm(h, params["lm_head"]["kernel"], weights)


kda_moe = KDAMoE()
