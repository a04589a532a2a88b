"""Per-layer metrics of the per-channel delta-rule / latent-attention /
sparse-expert family.

The family's counters and span labels are the ones ``readers.latent_moe``
(``sched.moe.*``, ``experts_hit``, ``expert_load_*``) and
``readers.gdn_moe`` (``sched.state.*``, ``state_restored``) read, and
those readers serve its cell unchanged; what is here is the device
trace's side, where the state kernel appears under its own name
(``kda_state_update``). Bytes and device time are paired by segment
(``readers.pairing``). A program without the kernel (another family, an
older commit) gives every reader here ``None``.
"""

from __future__ import annotations

from ..harness import kda_bytes
from . import gdn_moe, pairing


def kda_latent_step_roofline(ctx, pattern):
    """The time the chip's memory would need for the bytes a decode step
    NEEDS (``harness.kda_bytes``: every non-expert weight and the head
    once, the experts that were hit, the live rows' state read and
    written, the live positions' latent vectors) over the device time
    the decode programs took, over the whole calls of the slice, each
    with its own segment's bytes: the sum ``gdn_moe_step_roofline``
    makes, over this family's byte model."""
    if "linear_attn_config" not in ctx.config:
        return None
    return gdn_moe.gdn_moe_step_roofline(ctx, pattern)


def kda_state_update_roofline(ctx, op_pattern, module_pattern):
    """The state kernel alone: the bytes its live rows' matrices need
    (read and written once a layer a step) over the memory's rate, over
    the device time of the kernel's operations inside the whole calls of
    the slice, told by their SHORT name (``pairing.ops_inside``)."""
    pairs = pairing.paired(ctx, module_pattern)
    ops = pairing.ops_inside(ctx, pairs, op_pattern)
    if not ops:
        return None
    floor_s = 0.0
    for _, s in pairs:
        c = kda_bytes.state_update(ctx.config, len(s["live"]))
        floor_s += s["steps"] * c["layers"] * max(
            c["bytes"] / ctx.peaks["hbm_bytes_per_s"],
            c["ops"] / ctx.peaks["bf16_flops"])
    return 100.0 * floor_s / (sum(e[2] for e in ops) / 1e9)
