"""Model resolution for serving: checkpoint -> HF cache/hub -> random init.

Replaces the reference's import-time ``AutoModelForCausalLM.from_pretrained``
in every pod (reference server.py:40-42) with an explicit resolution order:

1. ``CHECKPOINT_DIR`` set → Orbax restore (no hub, no torch, the
   production path);
2. the HF model is loadable (cached or hub reachable) → convert through
   ``models.hf_convert`` (torch imported only here, never on the TPU
   serving path);
3. otherwise → random init from the named architecture (keeps the service
   and its wire contract alive in air-gapped test environments; logged
   loudly since generations are untrained noise — which is also true of
   the reference's default tiny-gpt2, README.md:135).
"""

from __future__ import annotations

import logging
from typing import Optional, Tuple

import jax

from ..models import gpt2
from ..models.gpt2 import GPT2Config, Params
from ..utils import checkpoint as ckpt
from ..utils.config import ServingConfig

log = logging.getLogger(__name__)


def resolve_for_role(cfg: ServingConfig,
                     ) -> Tuple[GPT2Config, Optional[Params],
                                Optional[Params]]:
    """Role-aware resolution: ``(config, full_params, stage_params)`` —
    load only what this role actually serves (the reference loads the full
    model into every pod regardless of role, server.py:40-42, 108-110).

    - shard ``a``/``b`` with a dense checkpoint: TRUE partial restore of
      just that role's two-stage compat subset (``ckpt.load_stage_params``
      reads only those layers' bytes) → ``(config, None, stage)``;
    - coordinator with ``DISPATCH=remote`` and a checkpoint: the weights
      live in the shard pods; only the config is read →
      ``(config, None, None)``;
    - everything else (coordinator+local, or no checkpoint — the HF/
      random-init fallbacks produce a full tree anyway) →
      ``(config, params, None)``.
    """
    if cfg.checkpoint_dir:
        if cfg.shard_role in ("a", "b"):
            config = ckpt.load_config(cfg.checkpoint_dir)
            from ..models import is_partitionable
            if not is_partitionable(config):
                # MoE/llama stage endpoints decline every request
                # (app.py), so such a shard pod needs no weights — config
                # only
                return config, None, None
            from ..parallel import partition as P_
            specs = P_.make_stage_specs(config.n_layer, [cfg.split_at])
            idx = 0 if cfg.shard_role == "a" else 1
            log.info("partial-restoring stage %s (blocks [%d, %d)) "
                     "from %s", cfg.shard_role, specs[idx].start,
                     specs[idx].end, cfg.checkpoint_dir)
            _, stage = ckpt.load_stage_params(cfg.checkpoint_dir, specs[idx])
            return config, None, stage
        elif cfg.shard_role == "coordinator" and cfg.dispatch == "remote":
            log.info("remote-dispatch coordinator: config only from %s",
                     cfg.checkpoint_dir)
            return ckpt.load_config(cfg.checkpoint_dir), None, None
    config, params = resolve_model(cfg)
    return config, params, None


def hub_reachable(timeout: float = 1.0) -> bool:
    """Fast offline detection: can we even resolve the HF hub host?

    Without this, air-gapped startups sit through huggingface_hub's
    5-retry backoff (~30 s) before falling back. An unresolvable host is
    a definitive "offline"; resolvable-but-down still goes the slow path.
    """
    import os
    import socket
    prior = socket.getdefaulttimeout()
    try:
        socket.setdefaulttimeout(timeout)
        socket.getaddrinfo("huggingface.co", 443)
        return True
    except OSError:
        # Belt and braces: transformers' adapter(PEFT) probe ignores
        # local_files_only in some versions, so force hub-offline mode
        # process-wide once we know the hub is unreachable.
        os.environ["HF_HUB_OFFLINE"] = "1"
        return False
    finally:
        socket.setdefaulttimeout(prior)

def _fallback_configs():
    # HF model ids / family names -> architecture configs for the
    # random-init fallback (lazy so importing loader stays light).
    from ..models import hybrid_ssm, llama, window_moe
    return {
        "sshleifer/tiny-gpt2": gpt2.CONFIGS["tiny-gpt2"],
        "gpt2": gpt2.CONFIGS["gpt2"],
        "gpt2-medium": gpt2.CONFIGS["gpt2-medium"],
        "llama-tiny": llama.CONFIGS["llama-tiny"],
        "llama-124m": llama.CONFIGS["llama-124m"],
        "window-moe-tiny": window_moe.CONFIGS["window-moe-tiny"],
        "hybrid-ssm-tiny": hybrid_ssm.CONFIGS["hybrid-ssm-tiny"],
    }


def resolve_model(cfg: ServingConfig) -> Tuple[GPT2Config, Params]:
    if cfg.checkpoint_dir:
        log.info("loading checkpoint from %s", cfg.checkpoint_dir)
        return ckpt.load(cfg.checkpoint_dir)

    try:
        # reachability check FIRST: it sets HF_HUB_OFFLINE before
        # huggingface_hub snapshots the env at import time
        offline = not hub_reachable()
        from transformers import AutoModelForCausalLM

        from ..models.hf_convert import (llama_params_from_hf_model,
                                         params_from_hf_model)
        model = AutoModelForCausalLM.from_pretrained(
            cfg.model_id, local_files_only=offline)
        model.eval()
        log.info("converted HF model %s", cfg.model_id)
        if getattr(model.config, "model_type", "gpt2") == "llama":
            return llama_params_from_hf_model(model)
        return params_from_hf_model(model)
    except Exception as e:  # hub unreachable / not cached / not convertible
        fallbacks = _fallback_configs()
        if cfg.model_id not in fallbacks:
            raise RuntimeError(
                f"cannot load {cfg.model_id!r}: no checkpoint dir, HF load "
                f"failed ({e}), and no fallback architecture is registered"
            ) from e
        config = fallbacks[cfg.model_id]
        log.warning(
            "HF load of %s failed (%s); using RANDOM-INIT %s weights — "
            "output will be untrained noise", cfg.model_id, e, config)
        from ..models import family_module
        return config, family_module(config).init_params(
            config, jax.random.PRNGKey(0))
