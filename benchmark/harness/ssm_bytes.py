"""Bytes and operations of the state-space / attention family.

``hybrid_ssm`` is the family's ``bytes_model`` (as ``harness.bytes`` has
one per dense family): what ONE decode step has to read: every weight
once (each layer's state-space mixer: projection in, convolution taps
and bias, ``dt_bias``, ``A_log``, ``D``, the gated norm's scale,
projection out; its attention; its SwiGLU; two norms; the final norm and
the head: the embedding is gathered a row a token and left out), the
keys and values of every live position in every layer, and, a live ROW,
every layer's state read and written (``state_per_row``: the float32
matrices and the convolution tails, each once in and once out: a step
rewrites them whole). A true lower bound, computed from the
configuration's sizes.

``state_update`` counts the new Pallas kernel (``ops.ssd``): the bytes
and operations one call needs for the rows that are live.
"""

from __future__ import annotations


def _mixer(sizes: dict) -> int:
    """Parameters of one layer's state-space mixer."""
    d, dssm, heads = (sizes["hidden_size"], sizes["mamba_d_ssm"],
                      sizes["mamba_n_heads"])
    channels = dssm + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return (d * (dssm + channels + heads)            # [z | x | B | C | dt]
            + channels * sizes["mamba_d_conv"] + channels
            + 3 * heads + dssm + dssm * d)


def hybrid_ssm(sizes: dict, itemsize: int = 2) -> dict:
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    h, hkv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    layers = sizes["num_hidden_layers"]
    channels = (sizes["mamba_d_ssm"]
                + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"])
    attention = 2 * d * h * hd + 2 * d * hkv * hd
    layer = (_mixer(sizes) + attention
             + 3 * d * sizes["intermediate_size"] + 2 * d)
    return {"weights": (layers * layer + d * v + d) * itemsize,
            "layer": layer * itemsize,
            "mixer": _mixer(sizes) * itemsize,
            "kv_per_token": layers * 2 * hkv * hd * itemsize,
            "state_per_row": layers * 2 * (
                sizes["mamba_n_heads"] * sizes["mamba_d_head"]
                * sizes["mamba_d_state"] * 4
                + (sizes["mamba_d_conv"] - 1) * channels * itemsize)}


def state_update(sizes: dict, live_rows: float) -> dict:
    """One call of the state kernel (one layer, one step) with
    ``live_rows`` rows decoding: every head's float32 matrix read and
    written once; five operations an element (the decay, a multiply-add
    for ``B (dt x)^T``, one for ``S^T C``)."""
    elements = (sizes["mamba_n_heads"] * sizes["mamba_d_head"]
                * sizes["mamba_d_state"]) * live_rows
    return {"bytes": 2 * 4 * elements, "ops": 5 * elements,
            "layers": sizes["num_hidden_layers"]}
