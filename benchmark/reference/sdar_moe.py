"""The plain reference of the block-diffusion / sparse-expert family.

SDAR's layer plan (the keys of the ``config.json`` of
``JetLM/SDAR-30B-A3B-Chat``, ``model_type`` ``sdar_moe``) and its
generation loop in straight ``jax.numpy``, float32,
``precision="highest"``: no kernel, no cache, no batching, one sequence
at a time, independent of ``llm_sharding_demo_tpu/models``. With ``norm(x,
w) = x rsqrt(mean x^2 + eps) w``, every layer:

```
a     = norm(h, w_in)
q,k,v = a Wq, a Wk, a Wv             # 32 / 4 / 4 heads of 128, no bias
q,k   = rope(norm(q, w_qn)), rope(norm(k, w_kn))   # per head, rotate-half
o     = softmax(q k^T / sqrt(hd) + M) v ,  M[i,j] = 0 if seen[i,j] else -inf
h     = h + o Wo
m     = norm(h, w_post)
p     = softmax(m Wg) over all published_num_experts; the
        num_experts_per_tok largest, w = p[chosen] / sum p[chosen]
h     = h + sum over the chosen experts HELD of w_e (silu(m G_e) * (m U_e)) D_e
logits = norm(h_last, w_f) W_head
```

``seen`` is a matrix the caller gives (``forward``): ``logits`` builds
the published one, ``j // L <= i // L`` over one sequence (causal between
blocks of ``L``, bidirectional inside one), and ``denoise_layout`` the
one under which ONE pass gives every denoise forward's logits of a whole
request (a noisy copy of a block sees itself and the clean blocks before
it: the block-diagonal plus offset block-causal mask block diffusion is
trained under).

``generate`` is the published loop: a block starts as mask tokens
(positions the prompt's last partial block fills are given); a forward
gives each position logits FOR that position; every still-masked
position gets the argmax and its softmax probability as a confidence;
``low_confidence_dynamic`` fixes every masked position over the
threshold and at least ``ceil(masked at the block's start /
denoising_steps)``, the most confident first (``low_confidence_static``:
that many and no more; ``sequential``: that many, left to right); when
no mask is left the next block starts.

Departures from the published code, each a note here and nowhere hidden:
weights are seeded random normals (std ``fan_in ** -0.5``, the embedding
1.0, norm scales ``1 + 0.1 N``), with two departures that a model whose
every masked position enters as THE SAME token needs: the mask token's
own embedding has std 0.1 (a hundredth of the others' variance, so that a
masked position's state is what attention brings it), and the per-head
norms of queries and keys are ``_QK_SCALE (1 + 0.1 N)``. The rule for
that scale is read on this reference and its own int8 control, not on a
program: the largest quarter step at which the reference is still
WELL-CONDITIONED, its int8-weights control parting from its own float32
choice by a ``deficit_mean`` under a hundredth of a logit's spread (the
order of the other expert families' int8 controls). On the chip at full
size that control reads 0.004-0.008 at 1.5, 0.04-0.065 at 1.75 and
0.57-0.74 at 2: the sharper attention is, the more of every rounding 48
layers of it hand on, and at 2 the reference is chaotic (at a width of
256 and 32 layers, float32 arithmetic with nothing but each layer's
input rounded to bfloat16 parts from the unrounded pass by 0.002 / 0.006
/ 0.13 at 1.5 / 1.75 / 2; int8 weights by 0.009 / 0.066 / 0.73), so no
bfloat16 program can be held to it. Under 1.5 answers stop depending on
their prompts: at 1 attention over hundreds of random keys is a
near-uniform mean and every answer is one token repeated, the same for
every prompt; at 1.25 answers behind one shared prefix agree in 95% of
their tokens. How far a prompt's own part moves its answer and how far a
rounding does are ONE property of seeded weights, the sharpness of
attention, so no scale buys the first without the second
(``PERF.md``, sections 2 and 6). All made on the device in the
tree layout the program's family takes; only the experts HELD (``num_experts`` ids
from ``first_expert``) add their terms, as in the program; every forward
of ``generate`` is a whole pass over prompt + answer so far, so the
published loop's last forward over a finished block (which only fills
the cache) has nothing to do here and is not run; candidates are greedy;
the mask token's own logit is left out of the choice and of the
confidence's softmax (a trained model gives it none); logits are for
the position itself (no shift); the noise schedule is training's and is
not used. Attention runs over blocks of ``_Q_BLOCK`` queries so that a
float32 pass of a few thousand positions fits beside the served model
(the same sums, fewer at a time). ``weights="int8"`` is ``dense._mm``'s
control: every matrix, the router's too, rounded to int8 codes a column.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .dense import F32, HI, _freeze, _key, _mm, _normal, _stack

_Q_BLOCK = 512
# the per-head norms' scales of queries and keys are drawn around this
# (a score's spread is then a little over 2 where unit scales give 1):
# the largest quarter step at which the reference's int8 control stays
# under a hundredth of a logit's spread; and the mask token's embedding
# with this spread where every other token's has 1: see the module's
# note on seeded weights
_QK_SCALE = 1.5
_MASK_EMBED_STD = 0.1
RULES = ("sequential", "low_confidence_static", "low_confidence_dynamic")


def _norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, pos, theta):
    """x [H, S, hd] turned by rotate-half at positions ``pos`` [S]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rot * sin


def _swiglu(x, mlp, weights):
    return _mm(jax.nn.silu(_mm(x, mlp["gate"]["kernel"], weights))
               * _mm(x, mlp["up"]["kernel"], weights),
               mlp["down"]["kernel"], weights)


def options(sizes: dict) -> dict:
    """How the configuration generates: its own keys."""
    return {"block_length": int(sizes["block_length"]),
            "denoising_steps": int(sizes["denoising_steps"]),
            "confidence_threshold": float(sizes["confidence_threshold"]),
            "remasking": str(sizes["remasking"]),
            "mask_token_id": int(sizes["mask_token_id"])}


def block_seen(n: int, length: int) -> np.ndarray:
    """The published mask over one sequence of ``n`` positions."""
    i = np.arange(n)
    return (i[None, :] // length) <= (i[:, None] // length)


class SDARMoE:

    def init(self, sizes, seed, dtype=jnp.bfloat16):
        return self._init(_freeze(sizes), _key(seed), jnp.dtype(dtype))

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(0, 2))
    def _init(sizes, key, dtype):
        s = dict(sizes)
        d, v = s["hidden_size"], s["vocab_size"]
        h, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
        f = s["moe_intermediate_size"]
        held, total = s["num_experts"], s["published_num_experts"]
        ke, kh, kb, kx, kn = jax.random.split(key, 5)

        def w(k, a, b):
            return {"kernel": _normal(k, (a, b), a ** -0.5, dtype)}

        def scale(k, n, around=1.0):
            return {"scale": (around * (1.0 + _normal(k, (n,), 0.1, F32))
                              ).astype(dtype)}

        def layer(k):
            ks = jax.random.split(k, 9)
            return {"ln_attn": scale(ks[0], d), "ln_mlp": scale(ks[1], d),
                    "attn": {"wq": w(ks[2], d, h * hd),
                             "wk": w(ks[3], d, hkv * hd),
                             "wv": w(ks[4], d, hkv * hd),
                             "wo": w(ks[5], h * hd, d),
                             "q_norm": scale(ks[6], hd, _QK_SCALE),
                             "k_norm": scale(ks[7], hd, _QK_SCALE)},
                    "moe": {"router": w(ks[8], d, total)}}

        def experts(k):
            def one(kk):
                ks = jax.random.split(kk, 3)
                return {"gate": w(ks[0], d, f), "up": w(ks[1], d, f),
                        "down": w(ks[2], f, d)}
            return jax.vmap(one)(jax.random.split(k, held))

        rows = jnp.where(jnp.arange(v) == s["mask_token_id"],
                         _MASK_EMBED_STD, 1.0)[:, None]
        return {"wte": (jax.random.normal(ke, (v, d), F32) * rows
                        ).astype(dtype),
                "blocks": _stack(s["num_hidden_layers"], kb, layer),
                "experts": _stack(s["num_hidden_layers"], kx, experts),
                "ln_f": scale(kn, d),
                "lm_head": w(kh, d, v)}

    # -- one pass --------------------------------------------------------

    def forward(self, params, sizes, ids, pos, seen, out, weights=None):
        """Float32 logits ``[len(out), vocab]`` of the tokens ``ids``
        [S] standing at positions ``pos`` [S], token ``i`` attending to
        token ``j`` iff ``seen[i, j]`` ([S, S] bool; every row sees
        something), at the token indices ``out``."""
        ids = jnp.asarray(ids, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        seen = jnp.asarray(seen, bool)
        frozen = _freeze(sizes)
        h = params["wte"][ids].astype(F32)
        for l in range(sizes["num_hidden_layers"]):
            h = self._block(params["blocks"], params["experts"], l, h, pos,
                            seen, weights=weights, sizes=frozen)
        return self._head(params, h[jnp.asarray(out)], weights=weights,
                          sizes=frozen)

    def logits(self, params, sizes, ids, positions, weights=None):
        """Logits of one sequence under the published mask, at
        ``positions``; a masked position holds the mask token's id. The
        sequence is padded on the right to whole runs of 64 (blocks are
        causal between them: what follows a block changes nothing in
        it), so ragged lengths share programs."""
        length = options(sizes)["block_length"]
        n = -(-len(ids) // 64) * 64
        ids = list(ids) + [0] * (n - len(ids))
        return self.forward(params, sizes, ids, np.arange(n),
                            block_seen(n, length), positions, weights)

    @staticmethod
    def _attention(a, x, pos, seen, s, weights):
        n = x.shape[0]
        h, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
        eps, theta = s["rms_norm_eps"], s["rope_theta"]

        def heads(y, count):
            return y.reshape(n, count, hd).transpose(1, 0, 2)

        q = heads(_mm(x, a["wq"]["kernel"], weights), h)
        k = heads(_mm(x, a["wk"]["kernel"], weights), hkv)
        v = heads(_mm(x, a["wv"]["kernel"], weights), hkv)
        q = _rope(_norm(q, a["q_norm"]["scale"], eps), pos, theta)
        k = _rope(_norm(k, a["k_norm"]["scale"], eps), pos, theta)
        k, v = (jnp.repeat(k, h // hkv, axis=0),
                jnp.repeat(v, h // hkv, axis=0))
        outs = []
        for lo in range(0, n, _Q_BLOCK):
            hi = min(lo + _Q_BLOCK, n)
            sc = jnp.einsum("hqd,hkd->hqk", q[:, lo:hi], k,
                            precision=HI) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(seen[lo:hi], sc, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,hkd->hqd", p, v, precision=HI))
        o = jnp.concatenate(outs, axis=1).transpose(1, 0, 2).reshape(n, -1)
        return _mm(o, a["wo"]["kernel"], weights)

    @staticmethod
    def _experts(router, experts, x, s, weights):
        """The held experts' weighted terms, and nothing else."""
        k, first = s["num_experts_per_tok"], s.get("first_expert", 0)
        p = jax.nn.softmax(_mm(x, router["kernel"], weights), axis=-1)
        w, chosen = jax.lax.top_k(p, k)
        if s["norm_topk_prob"]:
            w = w / w.sum(-1, keepdims=True)

        def one(y, xs):
            expert, e = xs
            w_e = jnp.where(chosen == first + e, w, 0.0).sum(-1)  # [S]
            return y + w_e[:, None] * _swiglu(x, expert, weights), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                            (experts, jnp.arange(s["num_experts"])))
        return y

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes"))
    def _block(blocks, experts, l, h, pos, seen, *, weights, sizes):
        s = dict(sizes)
        b = jax.tree.map(lambda x: x[l], blocks)
        mine = jax.tree.map(lambda x: x[l], experts)
        eps = s["rms_norm_eps"]
        x = _norm(h, b["ln_attn"]["scale"], eps)
        h = h + SDARMoE._attention(b["attn"], x, pos, seen, s, weights)
        m = _norm(h, b["ln_mlp"]["scale"], eps)
        return h + SDARMoE._experts(b["moe"]["router"], mine, m, s, weights)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("weights", "sizes"))
    def _head(params, h, *, weights, sizes):
        h = _norm(h, params["ln_f"]["scale"], dict(sizes)["rms_norm_eps"])
        return _mm(h, params["lm_head"]["kernel"], weights)

    # -- the published loop ----------------------------------------------

    def generate(self, params, sizes, prompt, n, opts=None, weights=None):
        """``n`` tokens after ``prompt``: ``{"tokens": [n], "fixed_at":
        [n] (the forward inside its block's round that fixed each),
        "forwards": denoise forwards run, "logits": one ``[L, vocab]``
        array a denoise forward, in order}``."""
        o = dict(options(sizes), **(opts or {}))
        length, steps = o["block_length"], o["denoising_steps"]
        mask_id = o["mask_token_id"]
        p = len(prompt)
        end = -(-(p + n) // length) * length
        x = list(prompt) + [mask_id] * (end - p)
        masked = np.arange(end) >= p
        fixed_at = np.full(end, -1)
        seen_logits = []
        for lo in range(p - p % length, end, length):
            hi = lo + length
            floor = -(-int(masked[lo:hi].sum()) // steps)
            f = 0
            while masked[lo:hi].any():
                z = np.asarray(self.logits(params, sizes, x[:hi],
                                           list(range(lo, hi)), weights),
                               np.float64)
                seen_logits.append(z.astype(np.float32))
                fix = choose_and_transfer(z, masked[lo:hi], floor, o)
                for i, token in fix.items():
                    x[lo + i], masked[lo + i] = token, False
                    fixed_at[lo + i] = f
                f += 1
        return {"tokens": [int(t) for t in x[p:p + n]],
                "fixed_at": [int(t) for t in fixed_at[p:p + n]],
                "forwards": len(seen_logits), "logits": seen_logits}


def confidences(z: np.ndarray, mask_id: int):
    """logits [L, V] -> (candidates [L], confidences [L]): the argmax
    over every token but the mask token, and its softmax probability
    over those tokens."""
    z = np.array(z, np.float64)
    z[:, mask_id] = -np.inf
    cand = z.argmax(-1)
    top = z.max(-1)
    return cand, 1.0 / np.exp(z - top[:, None]).sum(-1)


def choose_and_transfer(z, masked, floor: int, o: dict) -> dict:
    """One forward's transfer: ``{position in block: token}`` of the
    masked positions it fixes (ties go to the earlier position)."""
    cand, conf = confidences(z, o["mask_token_id"])
    open_ = [i for i in range(len(masked)) if masked[i]]
    if o["remasking"] == "sequential":
        order = open_
    elif o["remasking"] in RULES:
        order = sorted(open_, key=lambda i: (-conf[i], i))
    else:
        raise ValueError(f"remasking={o['remasking']!r} not one of {RULES}")
    take = set(order[:floor])
    if o["remasking"] == "low_confidence_dynamic":
        take |= {i for i in open_ if conf[i] > o["confidence_threshold"]}
    return {i: int(cand[i]) for i in sorted(take)}


def denoise_layout(prompt, answer, fixed_at, o: dict):
    """ONE pass that gives every denoise forward's logits of a request.

    The tokens are ``[prompt + answer | copy 0 | copy 1 | ...]``: copy
    ``f`` holds the answer's blocks (each from its first position, the
    tail of the prompt included where the prompt ends inside a block) as
    they stood BEFORE denoise forward ``f`` of their round: given
    positions and positions fixed by an earlier forward hold their
    token, the others the mask token. A token of the clean part sees
    the clean tokens of its own and earlier blocks; a token of copy
    ``f`` sees copy ``f``'s tokens of its own block and the clean tokens
    of earlier blocks. An answer that ends inside a block is cut to its
    whole blocks: what the loop put behind the budget is in no answer,
    and the block's other positions saw it. Returns ``(ids, pos, seen,
    at, scored)``: ``at[f]`` the token index of copy ``f``'s first
    position (the position ``len(prompt) - len(prompt) % L``),
    ``scored`` how many of the answer's tokens the layout holds."""
    length, mask_id = o["block_length"], o["mask_token_id"]
    p = len(prompt)
    scored = len(answer) - (p + len(answer)) % length
    clean = list(prompt) + list(answer[:scored])
    lo, end = p - p % length, len(clean)
    when = np.full(end, -1)
    when[p:] = fixed_at[:scored]
    copies = int(max(when[p:], default=-1)) + 1
    ids, pos = list(clean), list(range(end))
    copy_of = [-1] * end
    at = []
    for f in range(copies):
        at.append(len(ids))
        for i in range(lo, end):
            ids.append(clean[i] if when[i] < f else mask_id)
            pos.append(i)
            copy_of.append(f)
    block_of, copy_of = np.asarray(pos) // length, np.asarray(copy_of)
    earlier = block_of[None, :] < block_of[:, None]
    same = block_of[None, :] == block_of[:, None]
    seen = ((copy_of[None, :] == -1) & earlier) | (
        same & (copy_of[None, :] == copy_of[:, None]))
    return ids, pos, seen, at, scored


sdar_moe = SDARMoE()
