"""Bytes and operations of the per-channel delta-rule / latent-attention
/ sparse-expert family.

``kda_moe`` is the family's ``bytes_model`` (as ``harness.bytes`` has one
per dense family): what ONE decode step has to read whatever the routing
decides: every weight outside the routed experts once (the 20 delta-rule
mixers' projections, convolutions, gates and norms, the 7 latent mixers',
the dense first layer, every expert layer's router, selection bias and
shared expert, the norms, the head), one cached vector of ``kv_lora_rank
+ qk_rope_head_dim`` values a live position in the 7 layers that cache
positions, and, a live ROW, the delta-rule layers' state read and
written (``state_per_row``: the float32 matrices and the convolution
tails, each once in and once out: a step rewrites them whole). A true
lower bound: the routed experts come on top, by what the program's
counters say was chosen (``expert`` bytes apiece).

``state_update`` counts the new Pallas kernel (``ops.kda``): the bytes
and operations one call needs for the rows that are live.
"""

from __future__ import annotations


def _linear(sizes: dict) -> dict:
    return sizes["linear_attn_config"]


def kda_moe(sizes: dict, itemsize: int = 2) -> dict:
    d, v, h = sizes["hidden_size"], sizes["vocab_size"], \
        sizes["num_attention_heads"]
    rkv = sizes["kv_lora_rank"]
    nope, rope, vd = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], \
        sizes["v_head_dim"]
    la = _linear(sizes)
    hl, hd, width = la["num_heads"], la["head_dim"], \
        la["short_conv_kernel_size"]
    rank = hd                                # the gates' low-rank width
    n_kda, n_mla = len(la["kda_layers"]), len(la["full_attn_layers"])
    layers, n_dense = sizes["num_hidden_layers"], \
        sizes["first_k_dense_replace"]
    f, total = sizes["moe_intermediate_size"], sizes["published_num_experts"]
    channels = 3 * hl * hd
    delta = (d * channels + d * (2 * rank + hl) + channels * width
             + 2 * rank * hl * hd + hl + hl * hd + hd + hl * hd * d)
    latent = (d * h * (nope + rope) + d * (rkv + rope) + rkv
              + rkv * h * nope + rkv * h * vd + h * vd * d)
    dense = 3 * d * sizes["intermediate_size"]
    shared = 3 * d * f * sizes["num_shared_experts"]
    router = d * total + total                               # + the bias
    weights = (n_kda * delta + n_mla * latent + layers * 2 * d
               + n_dense * dense + (layers - n_dense) * (shared + router)
               + d * v + d)
    return {"weights": weights * itemsize,
            "kv_per_token": n_mla * (rkv + rope) * itemsize,
            "expert": 3 * d * f * itemsize,
            "expert_layers": layers - n_dense,
            "held": sizes["num_experts"],
            "state_per_row": n_kda * 2 * (
                hl * hd * hd * 4 + (width - 1) * channels * itemsize)}


def state_update(sizes: dict, live_rows: float) -> dict:
    """One call of the state kernel (one delta-rule layer, one step) with
    ``live_rows`` rows decoding: every head's float32 matrix read and
    written once; seven operations an element (the decay, two
    multiply-adds for ``S^T k`` and ``S^T q``, one for ``k d^T``)."""
    la = _linear(sizes)
    elements = la["num_heads"] * la["head_dim"] ** 2 * live_rows
    return {"bytes": 2 * 4 * elements, "ops": 7 * elements,
            "layers": len(la["kda_layers"])}
