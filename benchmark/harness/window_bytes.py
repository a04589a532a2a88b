"""Bytes of the sliding-window / sparse-expert family.

``window_moe`` is the family's ``bytes_model`` (as ``harness.bytes`` has
one per dense family): what ONE decode step has to read whatever the
routing decides: every weight outside the routed experts once (every
layer's attention projections and its two per-head norms, the dense
layers' feed-forward, each expert layer's router with its selection bias
and its shared expert, the norms, the slice of the head this chip
holds), the keys and values of every live position in the layers that
cache positions (``kv_per_token``: the FULL-attention layers alone), and,
a live ROW, what the sliding-window layers hold of it: ``window_row``
bytes a position for ``min(depth, window)`` positions, whatever the
depth (``window_per_row``). A true lower bound: the routed experts come
on top, by what the program's counters say was chosen (``expert`` bytes
apiece).

No kernel is new in this family (the full layers run ``ops.
decode_attention`` as it is, the sliding layers read their ring in XLA),
so no kernel's operations are counted here.
"""

from __future__ import annotations


def _sliding_layers(sizes: dict) -> int:
    interval = sizes["full_attention_interval"]
    return sizes["num_hidden_layers"] // interval * (interval - 1)


def window_moe(sizes: dict, itemsize: int = 2) -> dict:
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    h, hkv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    layers, n_dense = (sizes["num_hidden_layers"],
                       sizes["first_k_dense_replace"])
    total = sizes["published_num_experts"]
    n_sliding = _sliding_layers(sizes)
    attention = (d * h * hd + 2 * d * hkv * hd + 2 * hd + h * hd * d
                 + 2 * d)                                    # + 2 norms
    dense = 3 * d * sizes["intermediate_size"]
    shared = (3 * d * sizes["moe_intermediate_size"]
              * sizes["num_shared_experts"])
    router = d * total
    weights = (layers * attention + n_dense * dense
               + (layers - n_dense) * (shared + router) + d * v + d)
    return {"weights": weights * itemsize
            + (layers - n_dense) * total * 4,                # float32 biases
            "kv_per_token": (layers - n_sliding) * 2 * hkv * hd * itemsize,
            "expert": 3 * d * sizes["moe_intermediate_size"] * itemsize,
            "expert_layers": layers - n_dense,
            "held": sizes["num_experts"],
            "window": sizes["sliding_window"],
            "window_row": n_sliding * 2 * hkv * hd * itemsize}


def window_per_row(model: dict, depth: float) -> float:
    """Bytes the sliding layers read of ONE row at ``depth``."""
    return model["window_row"] * min(depth, model["window"])
