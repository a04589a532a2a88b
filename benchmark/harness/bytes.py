"""The bytes a decode step NEEDS, from the configuration's sizes alone.

A decode step of a batch has to read every block weight and the output
head once (whatever the batch width) and the keys and values of every
live position of every row it decodes. That is a lower bound on what
the program moves (it may also read padding, write the cache, move
activations), so a share of the roofline computed from it cannot pass
100% while the count is right.

One function per family, named by a configuration file's
``bytes_model``; each returns ``{"weights": bytes per step,
"kv_per_token": bytes per live position}`` for the served element size.
"""

from __future__ import annotations


def llama(sizes: dict, itemsize: int = 2) -> dict:
    d, i, v = sizes["hidden_size"], sizes["intermediate_size"], sizes["vocab_size"]
    layers = sizes["num_hidden_layers"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    per_layer = d * d + 2 * d * kv + d * d + 3 * d * i + 2 * d   # + 2 norms
    head = d * v + d                                             # + final norm
    return {"weights": (layers * per_layer + head) * itemsize,
            "kv_per_token": layers * 2 * kv * itemsize}


def gpt2(sizes: dict, itemsize: int = 2) -> dict:
    d, v, layers = sizes["n_embd"], sizes["vocab_size"], sizes["n_layer"]
    per_layer = (3 * d * d + 3 * d) + (d * d + d) + (4 * d * d + 4 * d) \
        + (4 * d * d + d) + 4 * d                                # + 2 LayerNorms
    head = d * v + 2 * d                                         # tied wte + ln_f
    return {"weights": (layers * per_layer + head) * itemsize,
            "kv_per_token": layers * 2 * d * itemsize}


def step_bytes(model: dict, live_positions: float) -> float:
    """Bytes one decode step needs with ``live_positions`` cached
    positions summed over the rows decoding."""
    return model["weights"] + model["kv_per_token"] * live_positions
