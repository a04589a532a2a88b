"""Elementwise / normalization primitives for the GPT-2 compute path.

These are the TPU-native equivalents of the torch submodules the reference
wires into its shards (ln_1/ln_2/ln_f LayerNorms and the MLP GELU inside
each ``block`` at reference server.py:84-85, 99-102). They are pure
functions so XLA can fuse them into the surrounding matmuls — there is no
module state and no dropout path (dropout is inert in the reference too:
``model.eval()`` at server.py:42,109-110 makes its ``drop`` a no-op).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .quant import QuantizedTensor, quant_matmul

# Numerics contract (tools/graftcheck numerics pass): these primitives
# ARE the repo's mixed-precision discipline — statistics in f32, value
# stream in the carried activation dtype. The traced-jaxpr half of the
# pass verifies the declaration at bf16 avals: the f32 upcast and the
# cast back to the input dtype are the only sanctioned boundaries, and
# the output never narrows below the carried dtype. All exact: the
# bf16 REGIME is approximate (gated by graftnum's decode.bf16 budget at
# the engine level), but these functions are deterministic and
# byte-stable per regime.
PRECISION_CONTRACT = {
    "layer_norm": {"regime": "carried", "exact": True,
                   "casts": ("f32", "carried")},
    "rms_norm": {"regime": "carried", "exact": True,
                 "casts": ("f32", "carried")},
    "rms_norm_offset": {"regime": "carried", "exact": True,
                        "casts": ("f32", "carried")},
    "gelu_new": {"regime": "carried", "exact": True, "casts": ()},
    "linear": {"regime": "carried", "exact": True, "casts": ()},
}


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    """LayerNorm over the trailing (feature) axis.

    Statistics are computed in float32 regardless of the activation dtype so
    bfloat16 compute on TPU does not lose precision in the variance, then the
    result is cast back to the input dtype.
    """
    orig_dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) / jnp.sqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(orig_dtype)


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray,
             eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm over the trailing axis (the LLaMA-family normalizer).

    Statistics in float32 (like ``layer_norm``); the scale multiply happens
    AFTER casting back to the activation dtype, matching HF
    ``LlamaRMSNorm.forward`` exactly so the llama logit-parity oracle
    stays tight under bf16.
    """
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * scale.astype(x.dtype)


def rms_norm_offset(x: jnp.ndarray, w: jnp.ndarray,
                    eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm in the ``(1 + w)`` form (weights stored as an offset from
    one, zero at initialisation): statistics AND the scale in float32,
    one cast at the end, as the published ``Qwen3NextRMSNorm`` does:
    ``1 + w`` in bfloat16 would round the scale to eight bits."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def gelu_new(x: jnp.ndarray) -> jnp.ndarray:
    """GPT-2's tanh-approximated GELU (HF ``gelu_new``).

    0.5 * x * (1 + tanh(sqrt(2/pi) * (x + 0.044715 * x^3)))

    Matching the exact approximation matters for the logit-parity oracle
    tests (SURVEY.md §4 item 1) — ``jax.nn.gelu(approximate=True)`` uses the
    same formula, but we spell it out so the contract is explicit.
    """
    c = jnp.sqrt(jnp.asarray(2.0 / jnp.pi, dtype=x.dtype))
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * jnp.power(x, 3))))


def linear(x: jnp.ndarray, kernel, bias: jnp.ndarray | None = None
           ) -> jnp.ndarray:
    """Affine map with an ``[in, out]`` kernel.

    The kernel layout deliberately matches HF GPT-2's ``Conv1D`` storage
    (weight is ``[in_features, out_features]``, the transpose of
    ``nn.Linear``) so checkpoint conversion is a direct copy — this is the
    Conv1D layout trap called out in SURVEY.md §5 "Checkpoint / resume".

    ``kernel`` may be a weight-only-int8 ``QuantizedTensor`` (see
    ``ops.quant``) — the int8 decode path flows through here without the
    model code knowing.
    """
    if isinstance(kernel, QuantizedTensor):
        y = quant_matmul(x, kernel)
    else:
        y = x @ kernel
    if bias is not None:
        y = y + bias
    return y
