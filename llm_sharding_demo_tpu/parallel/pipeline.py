"""Multi-device pipeline runtime: one stage per device, ICI handoff.

This is the runtime form of the reference's deployment topology — shard A
pod → coordinator relay → shard B pod over JSON/HTTP (reference
server.py:169-181) — rebuilt the TPU way: every stage's parameters and KV
cache live resident on their own device; the hidden-state hop between
stages is a direct device-to-device transfer (ICI on a real slice),
scheduled by XLA when stage i+1's jitted program consumes stage i's output.
The coordinator relay disappears entirely: nothing returns to the host
between stages except the final logits' sampled token.

Contrast of the per-token critical path:

  reference: tokenize → HTTP POST full sequence → torch fwd A → JSON
             encode [1,S,D] floats → HTTP relay → torch fwd B → JSON
             logits → numpy sampling           (2 HTTP round trips/token)
  here:      device0 embed+blocks → ICI xfer [B,1,D] → device1 blocks+head
             → on-device argmax → [B] int32 to host   (one tiny D2H/token)

The stage-per-device form keeps each stage's weights off every other chip
(the reference loads the full model in all three pods, server.py:108-110).
For the single-jit SPMD form used by training and microbatched inference,
see ``parallel.spmd`` (shard_map + ppermute over a pipeline mesh axis).
"""

from __future__ import annotations

import logging
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.gpt2 import GPT2Config, Params
from ..ops.attention import KVCache
from ..runtime.engine import (GenerateResult, SamplingConfig,
                              prepare_generate, select_token)
from . import partition as P

# Donation contract (tools/graftcheck sanitize pass): every per-stage
# jit in ``_stage_fns`` consumes its cache argument (arg 2) — callers
# always continue with the RETURNED caches (see ``forward``'s docstring).
DONATED_ARGS = {"_stage_fns": (2,)}


class PipelineRunner:
    """N pipeline stages resident on N devices of a 1×N mesh.

    ``devices=None`` uses ``jax.devices()[:n_stages]``; with fewer physical
    devices than stages, stages wrap round-robin (useful on the single
    benchmark chip and matching the "roles on one box" degenerate case).
    """

    def __init__(self, params: Params, config: GPT2Config,
                 boundaries: Sequence[int], max_seq: int,
                 devices: Optional[Sequence[jax.Device]] = None,
                 dtype=jnp.float32):
        if max_seq > config.n_positions:
            raise ValueError(
                f"max_seq={max_seq} exceeds n_positions={config.n_positions}")
        self.config = config
        self.max_seq = max_seq
        self.dtype = dtype
        # declared-vocabulary gate first (typed reject of float16/fp8/
        # typos — the same graftnum.engine_regime_of mechanism
        # DecodeEngine uses; fp8 is a KV-block storage regime, not an
        # engine compute dtype), THEN the targeted int8 refusal (this
        # runner casts, and an astype to int8 would truncate floats,
        # not quantize)
        from ..utils.graftnum import engine_regime_of
        engine_regime_of(dtype)
        from ..ops.quant import reject_raw_int8
        reject_raw_int8(dtype)
        # inference compute dtype applies to the WEIGHTS too (the decode
        # bottleneck is streaming them), exactly as DecodeEngine casts —
        # dtype only sizing the KV cache would silently leave fp32
        # matmuls behind a bf16 label.
        params = jax.tree.map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        # make_stage_specs already enforces disjoint+exhaustive coverage;
        # validate_specs exists for externally supplied spec lists.
        self.specs = P.make_stage_specs(config.n_layer, boundaries)

        avail = list(devices) if devices is not None else jax.devices()
        self.devices = [avail[i % len(avail)] for i in range(len(self.specs))]
        if len(avail) < len(self.specs):
            # the default serving path on one chip (SPLIT_AT's two stages)
            # lands here by design; it must not pass for a split across
            # chips, so say where the stages went
            logging.getLogger(__name__).warning(
                "%d stages on %d device(s): stages share devices "
                "round-robin (%s) — a stage-per-chip pipeline needs "
                "PP_DECODE=1 and one device per stage",
                len(self.specs), len(avail),
                [str(d) for d in self.devices])

        # Each stage's param subset moves to its device once, at
        # construction — weights never transfer again (the reference
        # re-sends activations as JSON per token; weights it duplicates
        # everywhere).
        self.stage_params: List[Params] = [
            jax.device_put(sp, dev)
            for sp, dev in zip(P.partition_params(params, self.specs),
                               self.devices)
        ]
        # One jitted program per stage; placement follows the committed
        # stage params (and the explicitly transferred input, see
        # ``forward``). Donating the cache argument lets XLA update the KV
        # buffers in place.
        self._stage_fns = [
            jax.jit(lambda sp, x, cache, _spec=spec: P.stage_apply(
                sp, _spec, self.config, x, cache),
                    donate_argnums=(2,))
            for spec in self.specs
        ]

    @property
    def n_stages(self) -> int:
        return len(self.specs)

    def init_caches(self, batch: int) -> List[KVCache]:
        """Per-stage KV caches, each allocated on its stage's device."""
        return [
            jax.device_put(
                P.make_stage_cache(spec, self.config, batch, self.max_seq,
                                   self.dtype), dev)
            for spec, dev in zip(self.specs, self.devices)
        ]

    def forward(self, x: jnp.ndarray, caches: Optional[List[KVCache]] = None,
                ) -> Tuple[jnp.ndarray, Optional[List[KVCache]]]:
        """Run ids (or hidden states) through all stages in order.

        Returns final-stage output ([B,S,vocab] logits) and updated caches.
        The inter-stage transfer happens implicitly: stage i+1's jit
        consumes stage i's on-device output — on a multi-chip slice that is
        an ICI copy, never a host bounce.

        **Donation**: the supplied ``caches`` buffers are donated to XLA
        (updated in place on TPU) and must not be reused after this call —
        always continue with the *returned* caches, as ``generate`` does.
        """
        new_caches: Optional[List[KVCache]] = [] if caches is not None else None
        for i, fn in enumerate(self._stage_fns):
            cache_in = caches[i] if caches is not None else None
            # The inter-stage hop: move the activation to stage i's device
            # (ICI device-to-device on a slice; async, overlaps with the
            # previous stage's tail). This is the reference's HTTP relay
            # (server.py:172-181) reduced to one hardware copy.
            x = jax.device_put(x, self.devices[i])
            x, cache_out = fn(self.stage_params[i], x, cache_in)
            if new_caches is not None:
                new_caches.append(cache_out)
        return x, new_caches

    def generate(self, prompt_ids, max_new_tokens: int,
                 sampling: SamplingConfig = SamplingConfig(),
                 key: Optional[jax.Array] = None) -> GenerateResult:
        """Pipelined generate: prefill once, then cached per-token steps.

        The token loop is host-driven (each token must traverse all stages
        sequentially — inherent to inference pipelining), but every step
        moves only a [B,1,D] hidden slice between devices and a [B] token
        to the host. Validation (including the static cache-overflow
        guard) is shared with the single-device engine via
        ``runtime.engine.prepare_generate``.
        """
        ids, batch, prompt_len, key, _ = prepare_generate(
            prompt_ids, max_new_tokens, self.max_seq, sampling, key,
            allow_ragged=False)

        caches = self.init_caches(batch)
        ids_j = jnp.asarray(ids, dtype=jnp.int32)

        t0 = time.perf_counter()
        logits, caches = self.forward(ids_j, caches)
        step_key, key = jax.random.split(key)
        token = select_token(logits[:, -1], sampling, step_key)
        token.block_until_ready()
        t1 = time.perf_counter()

        out = [token]
        for _ in range(max_new_tokens - 1):
            logits, caches = self.forward(token[:, None], caches)
            step_key, key = jax.random.split(key)
            token = select_token(logits[:, -1], sampling, step_key)
            out.append(token)
        new = np.stack([np.asarray(t) for t in jax.block_until_ready(out)], axis=1)
        t2 = time.perf_counter()

        tokens = np.concatenate([ids, new], axis=1)
        return GenerateResult(tokens=tokens, prompt_len=prompt_len,
                              prefill_seconds=t1 - t0, decode_seconds=t2 - t1,
                              new_tokens=max_new_tokens,
                              decode_steps=max_new_tokens - 1)
