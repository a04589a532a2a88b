"""Bytes and operations of the latent-attention / sparse-expert family.

``latent_moe`` is the family's ``bytes_model`` (as ``harness.bytes`` has
one per dense family): what ONE decode step has to read whatever the
routing decides: every weight outside the routed experts once (the
latent-attention projections, the dense layers, each expert layer's
router and shared expert, the norms, the head) and one cached vector of
``kv_lora_rank + qk_rope_head_dim`` values a layer for every live
position. A true lower bound: the routed experts come on top, by what
the program's counters say was chosen (``expert`` bytes apiece).

``decode_attention`` counts the new Pallas kernel
(``ops.latent_decode``): the bytes and operations one call needs for
the positions that are live.
"""

from __future__ import annotations


def latent_moe(sizes: dict, itemsize: int = 2) -> dict:
    d, v, h = sizes["hidden_size"], sizes["vocab_size"], \
        sizes["num_attention_heads"]
    rq, rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope, vd = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], \
        sizes["v_head_dim"]
    f, total = sizes["moe_intermediate_size"], \
        sizes["published_n_routed_experts"]
    layers, n_dense = sizes["num_hidden_layers"], \
        sizes["first_k_dense_replace"]
    attention = (d * rq + rq + rq * h * (nope + rope) + d * (rkv + rope)
                 + rkv + rkv * h * nope + rkv * h * vd + h * vd * d
                 + 2 * d)                                    # + 2 norms
    dense = 3 * d * sizes["intermediate_size"]
    shared = 3 * d * f * sizes["n_shared_experts"]
    router = d * total + total                               # + the bias
    weights = (layers * attention + n_dense * dense
               + (layers - n_dense) * (shared + router) + d * v + d)
    return {"weights": weights * itemsize,
            "kv_per_token": layers * (rkv + rope) * itemsize,
            "expert": 3 * d * f * itemsize,
            "expert_layers": layers - n_dense,
            "held": sizes["n_routed_experts"]}


def decode_attention(sizes: dict, live_positions: float,
                     itemsize: int = 2) -> dict:
    """One call of ``latent_decode_attention`` (one layer, one step) with
    ``live_positions`` cached positions summed over the rows decoding:
    every head's query dotted with, and its weights applied to, each
    live vector."""
    width = sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]
    heads = sizes["num_attention_heads"]
    return {"bytes": live_positions * width * itemsize,
            "ops": 2 * 2 * heads * width * live_positions}
