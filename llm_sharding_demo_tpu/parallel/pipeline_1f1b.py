"""1F1B pipeline-parallel training: one-forward-one-backward schedule.

``parallel.gpipe`` differentiates THROUGH the forward schedule: AD
transposes the forward scan into a full backward scan, so the program is
all-forwards-then-all-backwards — every stage must stash activations for
all M microbatches, and the schedule runs 2(M + S - 1) ticks. This
module hand-schedules the backward instead (the Megatron/PipeDream-style
upgrade the reference's layer-split serving topology never needed,
reference server.py:51-64 — its pipeline never trains):

- lockstep ticks ``t = 0 .. M + 2S - 3``; at tick t, stage s runs the
  FORWARD of microbatch ``t - s`` and the BACKWARD of microbatch
  ``t - (2S - 2 - s)`` (when in range). The last stage's backward of a
  microbatch starts in the SAME tick as its forward — the defining 1F1B
  interleaving — so cotangents chase activations down the pipe with
  ``S - 1`` ticks of lag instead of ``M + S - 1``.
- each stage stashes only its IN-FLIGHT microbatch inputs: at most
  ``min(M, 2S - 1)`` live entries (vs M for GPipe) — activation memory
  is bounded by pipeline depth, not schedule length, which is what lets
  M grow (and the bubble fraction (S-1)/(M+S-1) shrink) without memory
  blowing up.
- the backward recomputes the stage forward under ``jax.vjp``
  (activation rematerialization — the same trade GPipe's ``remat=True``
  path makes), so stash entries are single activations, not whole
  residual stacks.
- embedding and LM head/loss run INSIDE the program (stage 0 / last
  stage): the last stage needs per-microbatch loss cotangents the tick
  the microbatch arrives. Their grads accumulate locally and psum over
  ``pp`` at the end. GPT-2's tied head contributes to ``wte`` from both
  ends; the accumulation handles that naturally.
- like gpipe, only ``pp`` is a manual axis: dp/tp ride as automatic
  GSPMD axes (grad reductions over dp are inserted by the partitioner).

Returns (loss, grads) directly — there is no outer ``jax.grad``; the
train step applies the optimizer to the returned grads.  Losses match
``gpipe_lm_loss`` to reduction-order tolerance (same math, different
summation schedule); the dryrun ``check`` tolerance covers it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.gpt2 import GPT2Config, Params
from .gpipe import microbatch

# Placement contract (tools/graftcheck placement pass + utils/
# graftshard): same manual-axis story as gpipe — ``pp`` is the only
# manual axis in the 1F1B program (dp grad reductions are GSPMD-
# inserted and never manual placement); the schedule's backward trace
# is too heavy for the compile-free traced half, so this contract is
# checked by the AST half (liveness + literal collective axes) only.
PLACEMENT_CONTRACT = {
    "mesh_axes": ("pp", "tp", "dp"),
    "entry:_compiled_1f1b": "pp",
}


def one_f_one_b_loss_and_grads(params: Params, ids: jnp.ndarray,
                               config: GPT2Config, mesh: Mesh,
                               n_microbatches: int,
                               valid: Optional[jnp.ndarray] = None,
                               pp_axis: str = "pp",
                               virtual_stages: int = 1):
    """LM loss + grads with blocks run under the 1F1B schedule.

    ``params`` uses the gpipe layout (``GPipeTrainStep.init``): family
    embed/head leaves replicated + ``stacked_blocks`` stage-major over
    ``pp`` (``[S, per, ...]`` for ``virtual_stages=1``, the interleaved
    ``[S, v, per_chunk, ...]`` layout otherwise). ``ids`` [B, S]; B must
    divide by ``n_microbatches``. Returns ``(loss, grads)`` with
    ``grads`` shaped exactly like ``params``.

    ``virtual_stages=v > 1`` selects INTERLEAVED 1F1B (Megatron-style):
    each device owns every S-th chunk of layers, so a microbatch makes v
    ring trips and the warm-up/drain bubble shrinks from ``(S-1)/M``
    fractions toward ``(S-1)/(vM)`` at the cost of v x ppermute volume
    and a v x wider stash.  CAVEAT: the bubble win needs the per-core
    ``lax.cond`` skip, which tp/sp meshes disable (collectives inside
    blocks); there the masked path computes every chunk every tick and
    interleaving only ADDS ticks (M + 2vS - 2 full-work ticks) — keep
    ``virtual_stages=1`` on tp/sp meshes.
    """
    if pp_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {pp_axis!r} axis: {mesh.axis_names}")
    if virtual_stages > 1 and valid is not None:
        raise NotImplementedError(
            "interleaved 1F1B requires equal chunks (n_layer divisible "
            "by pp * virtual_stages); uneven boundaries are a "
            "virtual_stages=1 feature")
    ids_m = microbatch(jnp.asarray(ids, jnp.int32), n_microbatches)
    fn = _compiled_1f1b(mesh, config, pp_axis, n_microbatches,
                        valid is not None, virtual_stages)
    if valid is None:
        return fn(params, ids_m)
    valid = jax.device_put(valid, NamedSharding(mesh, P(pp_axis)))
    return fn(params, valid, ids_m)


@functools.lru_cache(maxsize=64)
def _compiled_1f1b(mesh: Mesh, config: GPT2Config, pp_axis: str,
                   n_micro: int, has_valid: bool, n_virtual: int = 1):
    """Build + jit the 1F1B program once per (mesh, config, schedule).

    Same caching rationale as ``gpipe._compiled_pipeline``: jit keys on
    function identity, and eager shard_map aborts on per-core control
    flow — the jit wrapper is required, and inlines for free inside the
    train step's outer jit.
    """
    n_stages = mesh.shape[pp_axis]
    vs_total = n_virtual * n_stages     # virtual pipeline depth
    n_ticks = n_micro + 2 * vs_total - 2
    # stash depth (per chunk): in-flight microbatches at virtual stage
    # vs are those with vs + m <= t < m + 2(VS-1) - vs + 1, at most
    # 2(VS-1-vs)+1 <= 2VS-1; one extra trash slot absorbs writes on
    # inactive ticks (cheaper than a predicated full-buffer select).
    k_stash = min(n_micro, 2 * vs_total - 1)

    from ..models.llama import LlamaConfig
    is_llama = isinstance(config, LlamaConfig)
    eps = getattr(config, "layer_norm_epsilon", None)

    def run_blocks(blocks_local, x, valid_row):
        if is_llama:
            from ..models import llama
            cos, sin = llama._angles(config, x.shape[1], 0, None)
            return llama.apply_blocks(blocks_local, x, config, cos, sin,
                                      valid=valid_row)[0]
        from ..models.gpt2 import apply_blocks
        return apply_blocks(blocks_local, x, config, valid=valid_row)[0]

    def embed_fwd(emb, ids_in):
        if is_llama:
            return emb["wte"][ids_in]
        s_in = ids_in.shape[-1]
        return emb["wte"][ids_in] + emb["wpe"][:s_in]

    def embed_bwd(emb, ids_in, dx):
        """Transpose of embed_fwd: gather -> scatter-add, (+ wpe row
        sums for GPT-2)."""
        g = {"wte": jnp.zeros_like(emb["wte"]).at[ids_in].add(
            dx.astype(emb["wte"].dtype))}
        if not is_llama:
            s_in = ids_in.shape[-1]
            g["wpe"] = jnp.zeros_like(emb["wpe"]).at[:s_in].add(
                dx.sum(axis=0).astype(emb["wpe"].dtype))
        return g

    def head_loss(head, y, tgt):
        """Per-microbatch MEAN next-token CE through ln_f + head."""
        if is_llama:
            from ..models import llama
            logits = llama._final(head, y, config)
        else:
            from ..models.gpt2 import final_logits
            logits = final_logits(head, y, eps)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), tgt)
        return jnp.mean(ce)

    # Collectives may not sit inside divergent per-core control flow;
    # blocks contain GSPMD-inserted all-reduces when tp/sp are real, so
    # the bubble/role conds only compile on pp(+dp) meshes — otherwise
    # every stage computes and the selects keep the math right.
    can_cond = all(mesh.shape.get(ax, 1) == 1 for ax in ("tp", "sp"))

    emb_keys = ("wte",) if is_llama else ("wte", "wpe")
    head_keys = ("ln_f", "lm_head") if is_llama else ("ln_f", "wte")

    def per_stage(blocks_local, valid_local, emb, head, ids_m):
        # local layout: [1, v, per_chunk, ...] -> per-chunk trees; chunk
        # j on device d is virtual stage j*S + d (interleaved; v=1 is
        # the flat schedule)
        blocks_local = jax.tree_util.tree_map(lambda x: x[0], blocks_local)
        chunks = [jax.tree_util.tree_map(lambda x, j=j: x[j], blocks_local)
                  for j in range(n_virtual)]
        valid_rows = (None if valid_local is None
                      else [valid_local[0][j] for j in range(n_virtual)])
        stage = jax.lax.axis_index(pp_axis)
        is_first = stage == 0
        is_last = stage == n_stages - 1

        mb, s_tot = ids_m.shape[1], ids_m.shape[2]
        s_in = s_tot - 1
        d = config.n_embd
        act = jnp.zeros((mb, s_in, d), jnp.float32)

        def vary(tree):
            # the scan carry becomes pp-varying via ppermute/role masks;
            # its signature must say so up front (same move as gpipe).
            # Leaves derived from pp-sharded INPUTS (zeros_like the local
            # block slice) are already varying — pcast rejects the no-op.
            def f(a):
                try:
                    return jax.lax.pcast(a, pp_axis, to="varying")
                except ValueError:
                    return a
            return jax.tree_util.tree_map(f, tree)

        # CRITICAL: differentiate wrt a pp-VARYING copy of the head
        # params. AD wrt a pp-invariant value inside the manual region
        # transposes the implicit invariant->varying broadcast into a
        # psum over pp — a hidden collective that (a) aborts inside
        # lax.cond branches and (b) sums every stage's (mostly garbage)
        # head grads in the masked path before the role mask applies.
        # With a varying head, grads stay per-stage; the single explicit
        # psum at the end does the cross-stage reduction once.
        head_v = vary(head)

        def head_grads_of(y, tgt):
            (loss_m, (dhead, dy)) = jax.value_and_grad(
                head_loss, argnums=(0, 1))(head_v, y, tgt)
            return loss_m, dhead, dy

        zero_gb = [jax.tree_util.tree_map(jnp.zeros_like, c)
                   for c in chunks]
        zero_gh = jax.tree_util.tree_map(jnp.zeros_like, head_v)
        zero_ge = jax.tree_util.tree_map(jnp.zeros_like, emb)

        init = vary(dict(
            fwd_in=[act] * n_virtual,
            bwd_in=[act] * n_virtual,
            stash=[jnp.zeros((k_stash + 1, mb, s_in, d), jnp.float32)
                   for _ in range(n_virtual)],
            gb=zero_gb,
            gh=zero_gh,
            ge=zero_ge,
            loss=jnp.float32(0.0),
        ))

        # v=1 keeps OPEN chains (no wrap edges): the wrapped payloads are
        # always discarded there (embed/dy_last overrides), so the two
        # wrap transfers per tick would be pure dead traffic. v>1 needs
        # the full ring — the wrap carries chunk j to chunk j+1.
        if n_virtual == 1:
            fwd_ring = [(i, i + 1) for i in range(n_stages - 1)]
            bwd_ring = [(i, i - 1) for i in range(1, n_stages)]
        else:
            fwd_ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            bwd_ring = [(i, (i - 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            stash = list(carry["stash"])
            gb = list(carry["gb"])
            gh, ge, loss_acc = carry["gh"], carry["ge"], carry["loss"]
            ys, dxs = [], []

            for j in range(n_virtual):
                bl_j, valid_j = chunks[j], (None if valid_rows is None
                                            else valid_rows[j])

                def fwd_of(x, bl_j=bl_j, valid_j=valid_j):
                    return run_blocks(bl_j, x, valid_j)

                def bwd_of(x, dy, bl_j=bl_j, valid_j=valid_j):
                    _, vjp = jax.vjp(
                        lambda bl, xx: run_blocks(bl, xx, valid_j),
                        bl_j, x)
                    return vjp(dy)

                vs = j * n_stages + stage          # virtual stage index
                m_f = t - vs                       # forward microbatch
                m_b = t - (2 * (vs_total - 1) - vs)  # backward microbatch
                act_f = (m_f >= 0) & (m_f < n_micro)
                act_b = (m_b >= 0) & (m_b < n_micro)
                mf_c = jnp.clip(m_f, 0, n_micro - 1)
                mb_c = jnp.clip(m_b, 0, n_micro - 1)
                ids_f = jax.lax.dynamic_index_in_dim(ids_m, mf_c, 0,
                                                     keepdims=False)
                ids_b = jax.lax.dynamic_index_in_dim(ids_m, mb_c, 0,
                                                     keepdims=False)

                # ---- forward slot ---------------------------------------
                x = carry["fwd_in"][j]
                if j == 0:  # only virtual stage 0 embeds fresh input
                    x = jnp.where(is_first,
                                  embed_fwd(emb, ids_f[:, :-1]), x)
                if can_cond:
                    y = jax.lax.cond(act_f, fwd_of, lambda x: x, x)
                else:
                    y = fwd_of(x)
                # stash this chunk's input; inactive ticks hit the trash
                # slot
                slot = jnp.where(act_f, mf_c % k_stash, k_stash)
                stash[j] = jax.lax.dynamic_update_index_in_dim(
                    stash[j], x, slot, axis=0)

                # final virtual stage: per-microbatch loss + cotangent,
                # SAME tick
                if j == n_virtual - 1:
                    last_work = is_last & act_f
                    if can_cond:
                        loss_m, dhead, dy_last = jax.lax.cond(
                            last_work,
                            lambda y, tgt: head_grads_of(y, tgt),
                            lambda y, tgt: (vary(jnp.float32(0.0)),
                                            zero_gh, jnp.zeros_like(y)),
                            y, ids_f[:, 1:])
                    else:
                        loss_m, dhead, dy_last = head_grads_of(
                            y, ids_f[:, 1:])
                        loss_m = jnp.where(last_work, loss_m, 0.0)
                        dhead = jax.tree_util.tree_map(
                            lambda g: jnp.where(last_work, g, 0.0), dhead)
                        dy_last = jnp.where(last_work, dy_last, 0.0)
                    loss_acc = loss_acc + loss_m
                    gh = jax.tree_util.tree_map(jnp.add, gh, dhead)

                # ---- backward slot --------------------------------------
                xb = jax.lax.dynamic_index_in_dim(
                    stash[j], mb_c % k_stash, 0, keepdims=False)
                dy = carry["bwd_in"][j]
                if j == n_virtual - 1:
                    dy = jnp.where(is_last, dy_last, dy)
                if can_cond:
                    dbl, dx = jax.lax.cond(
                        act_b, bwd_of,
                        lambda x, dy, j=j: vary((zero_gb[j],
                                                 jnp.zeros_like(x))),
                        xb, dy)
                else:
                    dbl, dx = bwd_of(xb, dy)
                    dbl = jax.tree_util.tree_map(
                        lambda g: jnp.where(act_b, g, 0.0), dbl)
                    dx = jnp.where(act_b, dx, 0.0)
                gb[j] = jax.tree_util.tree_map(jnp.add, gb[j], dbl)

                # virtual stage 0 pushes its input cotangent into the
                # embedding grads
                if j == 0:
                    first_work = is_first & act_b
                    if can_cond:
                        demb = jax.lax.cond(
                            first_work,
                            lambda ids_in, dx: vary(
                                embed_bwd(emb, ids_in, dx)),
                            lambda ids_in, dx: vary(zero_ge),
                            ids_b[:, :-1], dx)
                    else:
                        demb = embed_bwd(emb, ids_b[:, :-1], dx)
                        demb = jax.tree_util.tree_map(
                            lambda g: jnp.where(first_work, g, 0.0), demb)
                    ge = jax.tree_util.tree_map(jnp.add, ge, demb)

                ys.append(y)
                dxs.append(dx)

            # ---- ship activations down, cotangents up -------------------
            # Full rings (wrap included): chunk j's output feeds virtual
            # stage j*S+d+1 — device d+1's chunk j, except the wrap from
            # device S-1 to device 0's chunk j+1, handled by the roll
            # below. Device 0's chunk-0 slot receives the discarded
            # VS-1 wrap (embed overrides it at use time); mirrored for
            # cotangents, where the head cotangent overrides the last
            # device's chunk v-1 slot.
            recv_f = [jax.lax.ppermute(y, pp_axis, fwd_ring) for y in ys]
            recv_b = [jax.lax.ppermute(dx, pp_axis, bwd_ring)
                      for dx in dxs]
            fwd_in = [jnp.where(is_first, recv_f[(j - 1) % n_virtual],
                                recv_f[j]) for j in range(n_virtual)]
            bwd_in = [jnp.where(is_last, recv_b[(j + 1) % n_virtual],
                                recv_b[j]) for j in range(n_virtual)]

            carry = dict(fwd_in=fwd_in, bwd_in=bwd_in, stash=stash,
                         gb=gb, gh=gh, ge=ge, loss=loss_acc)
            return carry, None

        final, _ = jax.lax.scan(tick, init, jnp.arange(n_ticks))

        inv_m = 1.0 / n_micro
        loss = jax.lax.psum(final["loss"] * inv_m, pp_axis)
        # [v][per_chunk, ...] trees -> one [1, v, per_chunk, ...] tree
        # (leading axis restored for the P(pp) out_spec)
        gb = jax.tree_util.tree_map(
            lambda *gs: (jnp.stack(gs) * inv_m)[None], *final["gb"])
        gh = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g * inv_m, pp_axis), final["gh"])
        ge = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g * inv_m, pp_axis), final["ge"])
        return loss, gb, gh, ge

    def wrapped(params, valid, ids_m):
        emb = {k: params[k] for k in emb_keys}
        head = {k: params[k] for k in head_keys}
        blocks = params["stacked_blocks"]
        if n_virtual == 1:
            # legacy flat layout [S, per, ...] <-> internal [S, 1, per,
            # ...]; grads are squeezed back so the tree matches params
            blocks = jax.tree_util.tree_map(lambda x: x[:, None], blocks)
            if valid is not None:
                valid = valid[:, None]
        run = jax.shard_map(
            per_stage if has_valid else
            (lambda b, e, h, i: per_stage(b, None, e, h, i)),
            mesh=mesh,
            in_specs=((P(pp_axis), P(pp_axis), P(), P(), P())
                      if has_valid else (P(pp_axis), P(), P(), P())),
            out_specs=(P(), P(pp_axis), P(), P()),
            axis_names={pp_axis})
        args = ((blocks, valid, emb, head, ids_m) if has_valid
                else (blocks, emb, head, ids_m))
        loss, gb, gh, ge = run(*args)
        if n_virtual == 1:
            gb = jax.tree_util.tree_map(lambda x: x[:, 0], gb)
        grads = {"stacked_blocks": gb}
        for k in emb_keys:
            grads[k] = ge[k]
        for k in head_keys:
            # GPT-2's tied head: wte grad = embed side + head side
            grads[k] = (grads[k] + gh[k]) if k in grads else gh[k]
        return loss, grads

    if has_valid:
        return jax.jit(wrapped)
    return jax.jit(lambda params, ids_m: wrapped(params, None, ids_m))
