"""Bytes and operations of the linear-attention / sparse-expert family.

``gdn_moe`` is the family's ``bytes_model`` (as ``harness.bytes`` has one
per dense family): what ONE decode step has to read whatever the routing
decides: every weight outside the routed experts once (the 36 linear
mixers' projections, convolutions, gates and norms, the 12 softmax
mixers', every layer's router, shared expert and its gate, the norms,
the head), the keys and values of every live position in the 12 layers
that cache positions, and, a live ROW, the linear layers' state read and
written (``state_per_row``: the float32 matrices and the convolution
tails, each once in and once out: a step rewrites them whole). A true
lower bound: the routed experts come on top, by what the program's
counters say was chosen (``expert`` bytes apiece).

``state_update`` counts the new Pallas kernel (``ops.gated_delta``): the
bytes and operations one call needs for the rows that are live.
"""

from __future__ import annotations


def _linear_layers(sizes: dict) -> int:
    interval = sizes["full_attention_interval"]
    return sizes["num_hidden_layers"] // interval * (interval - 1)


def gdn_moe(sizes: dict, itemsize: int = 2) -> dict:
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    h, hkv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    width = sizes["linear_conv_kernel_dim"]
    layers, n_linear = sizes["num_hidden_layers"], _linear_layers(sizes)
    n_full = layers - n_linear
    channels = 2 * hk * dk + hv * dv
    linear = (d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv
              + channels * width + 2 * hv + dv + hv * dv * d)
    full = (d * h * 2 * hd + 2 * d * hkv * hd + 2 * hd + h * hd * d)
    common = (d * sizes["published_num_experts"]             # the router
              + 3 * d * sizes["shared_expert_intermediate_size"] + d
              + 2 * d)                                       # + 2 norms
    weights = (n_linear * linear + n_full * full + layers * common
               + d * v + d)
    return {"weights": weights * itemsize,
            "kv_per_token": n_full * 2 * hkv * hd * itemsize,
            "expert": 3 * d * sizes["moe_intermediate_size"] * itemsize,
            "expert_layers": layers,
            "held": sizes["num_experts"],
            "state_per_row": n_linear * 2 * (
                hv * dk * dv * 4 + (width - 1) * channels * itemsize)}


def state_update(sizes: dict, live_rows: float) -> dict:
    """One call of the state kernel (one linear layer, one step) with
    ``live_rows`` rows decoding: every value head's float32 matrix read
    and written once; seven operations an element (the decay, two
    multiply-adds for ``S^T k`` and ``S^T q``, one for ``k d^T``)."""
    elements = (sizes["linear_num_value_heads"] * sizes["linear_key_head_dim"]
                * sizes["linear_value_head_dim"]) * live_rows
    return {"bytes": 2 * 4 * elements, "ops": 7 * elements,
            "layers": _linear_layers(sizes)}
