"""Bytes of the block-diffusion / sparse-expert family.

``sdar_moe`` is the family's ``bytes_model`` (as ``harness.bytes`` has
one per dense family): what ONE forward of a round has to read whatever
the routing decides. A forward is four positions a row (a block), and
there are two kinds: a DENOISE forward reads every weight outside the
routed experts (``body``: the 48 mixers' projections and per-head norms,
every layer's router, the norms) and the head (``head``: candidates need
logits); the COMMIT forward that closes a round reads the body alone
(nobody reads its logits: the compiled program runs no head for it).
Both read the keys and values of every live position (``kv_per_token``).
A true lower bound: the routed experts come on top, by what the
program's counters say was chosen (``expert`` bytes apiece, ``held`` of
them a layer at most). ``weights`` is body + head, what the family-blind
readers mean by it.
"""

from __future__ import annotations


def sdar_moe(sizes: dict, itemsize: int = 2) -> dict:
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    h, hkv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    layers = sizes["num_hidden_layers"]
    mixer = d * h * hd + 2 * d * hkv * hd + h * hd * d + 2 * hd
    common = d * sizes["published_num_experts"] + 2 * d   # router, 2 norms
    body = layers * (mixer + common) + d                  # + final norm
    head = d * v
    return {"weights": (body + head) * itemsize,
            "body": body * itemsize,
            "head": head * itemsize,
            "kv_per_token": layers * 2 * hkv * hd * itemsize,
            "expert": 3 * d * sizes["moe_intermediate_size"] * itemsize,
            "expert_layers": layers,
            "held": sizes["num_experts"]}


def call_bytes(bm: dict, forwards: int, rounds: int, experts_hit: int,
               positions: float) -> float:
    """What a decode call of ``rounds`` rounds and ``forwards`` forwards
    (one commit a round, the others denoise) needs: the body a forward,
    the head a denoise forward, the distinct held experts its positions
    chose (summed over layers and forwards by the program's counter),
    and ``positions`` cached positions read (live rows' depths, summed
    over the forwards)."""
    return (forwards * bm["body"] + (forwards - rounds) * bm["head"]
            + experts_hit * bm["expert"] + positions * bm["kv_per_token"])
