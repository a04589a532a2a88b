"""Checkpoint save/restore (Orbax) — the subsystem the reference lacks.

The reference re-downloads full HF weights into every pod at import time
and never saves anything (reference server.py:40-42; SURVEY.md §5
"Checkpoint / resume": ABSENT). Here conversion is one explicit step
(``models.hf_convert`` or the ``tools/convert_hf.py`` CLI) and serving/
training restore from an Orbax checkpoint directory — so pods need no hub
access and each pipeline stage can load only its own parameter subset
(``load_stage_params``).

Layout on disk::

    <dir>/config.json          # the config's fields + its "family" tag
    <dir>/params/              # Orbax PyTreeCheckpointer payload

In memory the block stack is ``[n_layer, ...]`` leaves (the ``lax.scan``
layout, models.gpt2.apply_blocks); on disk each layer is its own subtree
(``blocks/{i}/...``) so a pipeline-stage restore reads ONLY its layers'
bytes from storage (``load_stage_params`` — Orbax partial restore via
``transforms={}``). Round-1 review flagged the old stacked layout for
pulling the whole model through host RAM per stage pod; per-layer
storage is what makes the partial read possible at all, since Orbax
can skip whole arrays but not slice inside one. Pre-existing stacked
checkpoints still load (structural detection + full-read fallback).

Training state (params + optimizer + step counter) uses the same
mechanism under ``<dir>/train_state``.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp

from ..models import family_named, family_of
from ..models.gpt2 import GPT2Config, Params
from ..parallel import partition as P_

CONFIG_FILE = "config.json"
PARAMS_DIR = "params"
TRAIN_DIR = "train_state"


def _split_blocks(blocks: Params) -> dict:
    """Stacked ``[L, ...]`` block leaves -> ``{"0": layer_tree, ...}``."""
    n_layer = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    return {str(i): jax.tree.map(lambda x: np.asarray(x[i]), blocks)
            for i in range(n_layer)}


def _stack_blocks(per_layer: dict) -> Params:
    """``{"0": layer_tree, ...}`` -> stacked ``[L, ...]`` leaves.

    Copies layer by layer into preallocated output and drops each source
    layer as it lands, so peak host RAM is ~1x the stack plus the not-yet-
    copied layers — not the 2x of a naive ``np.stack`` over a list that
    keeps every source alive until the end.
    """
    keys = sorted(per_layer, key=int)
    n = len(keys)

    def _alloc(x):
        out = np.empty((n,) + np.shape(x), np.asarray(x).dtype)
        out[0] = x
        return out

    out = jax.tree.map(_alloc, per_layer[keys[0]])
    per_layer[keys[0]] = None
    for i, k in enumerate(keys[1:], start=1):
        jax.tree.map(lambda dst, src, i=i: dst.__setitem__(i, src),
                     out, per_layer[k])
        per_layer[k] = None  # free the source layer's arrays promptly
    return out


def _is_per_layer(blocks) -> bool:
    """Structural layout detection: per-layer checkpoints key blocks by
    layer index ("0", "1", ...); the legacy stacked layout keys them by
    module name ("attn", "ln_1", ...)."""
    return (isinstance(blocks, dict) and bool(blocks)
            and all(k.isdigit() for k in blocks))


def save(directory: str, params: Params, config: GPT2Config) -> None:
    """Write config + params (per-layer block layout — see module doc).
    Overwrites an existing checkpoint."""
    directory = os.path.abspath(directory)
    os.makedirs(directory, exist_ok=True)
    # ``dataclasses.asdict`` flattens every family to a plain dict; the
    # family's tag beside the fields says which config class they are
    payload = {"family": family_of(config).name,
               **dataclasses.asdict(config)}
    with open(os.path.join(directory, CONFIG_FILE), "w") as f:
        json.dump(payload, f, indent=2)
    # one stack under ``blocks`` goes to disk a layer a subtree (what a
    # stage restore reads); a family that lays its layers out otherwise
    # (periods, groups) is written as it stands
    on_disk = dict(params)
    if "blocks" in params:
        on_disk["blocks"] = _split_blocks(params["blocks"])
    ckptr = ocp.PyTreeCheckpointer()
    ckptr.save(os.path.join(directory, PARAMS_DIR), on_disk, force=True)


def load_config(directory: str) -> GPT2Config:
    with open(os.path.join(os.path.abspath(directory), CONFIG_FILE)) as f:
        fields = json.load(f)
    family = fields.pop("family", "gpt2")  # pre-tag checkpoints are dense
    # JSON has no tuples: a list among the fields was one
    fields = {k: _tuples(v) for k, v in fields.items()}
    return family_named(family).config_class(**fields)


def _tuples(value):
    if isinstance(value, list):
        return tuple(_tuples(v) for v in value)
    return value


def load(directory: str) -> Tuple[GPT2Config, Params]:
    """Restore (config, params); restacks per-layer blocks into the
    in-memory ``[L, ...]`` scan layout. Legacy stacked checkpoints pass
    through unchanged."""
    directory = os.path.abspath(directory)
    config = load_config(directory)
    ckptr = ocp.PyTreeCheckpointer()
    params = ckptr.restore(os.path.join(directory, PARAMS_DIR))
    if _is_per_layer(params.get("blocks")):
        params = dict(params)
        params["blocks"] = _stack_blocks(params["blocks"])
    return config, params


def save_train_state(directory: str, params: Params, opt_state: Any,
                     step: int) -> None:
    """Mid-training snapshot: params + optimizer moments + step counter.

    A crashed/preempted training job resumes bit-exactly — Adam moments
    and the schedule position (optax's counter inside ``opt_state``) are
    part of the trajectory, so restarting from params alone would change
    every subsequent update. ``step`` is caller bookkeeping (data/loop
    position), saved alongside but not consulted by the optimizer. Lives
    under ``<dir>/train_state`` beside the serving layout.
    """
    directory = os.path.abspath(directory)
    os.makedirs(directory, exist_ok=True)
    payload = {"params": params, "opt_state": opt_state,
               "step": jax.numpy.asarray(step)}
    ocp.PyTreeCheckpointer().save(
        os.path.join(directory, TRAIN_DIR), payload, force=True)


def load_train_state(directory: str, params_template: Params,
                     opt_state_template: Any) -> Tuple[Params, Any, int]:
    """Restore a ``save_train_state`` snapshot as ``(params, opt_state,
    step)``.

    Orbax serializes pytree STRUCTURE loosely (optax states are nested
    NamedTuples that round-trip as plain containers), so callers pass
    templates — typically a fresh ``TrainStep.init(...)`` result — and
    the restore maps leaves back onto the exact optimizer-state classes.
    ``restore_args`` built from the templates make leaves restore
    directly into the RESUMING job's shardings; without them orbax reads
    device layouts from the checkpoint file, which it itself flags as
    unsafe when the resumed pod's topology differs from the saver's —
    the exact preemption-resume case this function exists for.

    ``step`` is loop/data-position bookkeeping for the caller; the LR
    schedule's own position is optax state inside ``opt_state`` and
    restores with it regardless of this value.
    """
    directory = os.path.abspath(directory)
    template = {"params": params_template, "opt_state": opt_state_template,
                "step": jax.numpy.asarray(0)}
    restored = ocp.PyTreeCheckpointer().restore(
        os.path.join(directory, TRAIN_DIR), item=template,
        restore_args=ocp.checkpoint_utils.construct_restore_args(template))
    return restored["params"], restored["opt_state"], int(restored["step"])


def load_stage_params(directory: str, spec: P_.StageSpec,
                      ) -> Tuple[GPT2Config, Params]:
    """Restore only one pipeline stage's parameter subset — a TRUE partial
    read: Orbax fetches just the stage's layer subtrees (plus embeddings
    for the first stage / ln_f + the tied head table for the last), so
    neither device nor host memory ever holds the rest of the model. This
    is the storage-level fix for the reference quirk of every role holding
    the full model (server.py:108-110).

    Legacy stacked-layout checkpoints can't be read partially (one
    ``[L, ...]`` array per leaf on disk); those fall back to full restore
    + slice, as before.
    """
    directory = os.path.abspath(directory)
    path = os.path.join(directory, PARAMS_DIR)
    ckptr = ocp.PyTreeCheckpointer()
    disk_tree = ckptr.metadata(path).item_metadata.tree
    if not _is_per_layer(disk_tree.get("blocks")):
        config, params = load(directory)
        return config, P_.extract_stage_params(params, spec)
    config = load_config(directory)

    # Family detected structurally, mirroring extract_stage_params: the
    # llama tree carries an untied ``lm_head`` (and no ``wpe``); the
    # GPT-2/MoE tree ties its head to ``wte``.
    llama_tree = "lm_head" in disk_tree
    item: dict = {"blocks": {str(i): disk_tree["blocks"][str(i)]
                             for i in range(spec.start, spec.end)}}
    if spec.is_first:
        item["wte"] = disk_tree["wte"]
        if not llama_tree:
            item["wpe"] = disk_tree["wpe"]
    if spec.is_last:
        item["ln_f"] = disk_tree["ln_f"]
        if llama_tree:
            item["lm_head"] = disk_tree["lm_head"]
        else:
            item.setdefault("wte", disk_tree["wte"])  # tied LM head table
    # metadata leaves are placeholders; restore_type=np.ndarray reads each
    # array as host numpy (shape/dtype from disk) without consulting the
    # saver's sharding file — a stage pod's topology never matches the
    # saver's anyway. transforms={} limits the read to exactly the keys
    # present in ``item``.
    restore_args = jax.tree.map(
        lambda _: ocp.RestoreArgs(restore_type=np.ndarray), item)
    got = ckptr.restore(path, item=item, transforms={},
                        restore_args=restore_args)
    out: Params = {"blocks": _stack_blocks(got["blocks"])}
    if spec.is_first:
        out["wte"] = got["wte"]
        if not llama_tree:
            out["wpe"] = got["wpe"]
    if spec.is_last:
        out["ln_f"] = got["ln_f"]
        if llama_tree:
            out["lm_head"] = got["lm_head"]
        else:
            out["wte_out"] = got["wte"]
    return config, out
