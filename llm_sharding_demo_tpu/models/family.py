"""What a model family declares of itself, as one typed value.

Every family module states ONE ``FAMILY = Family(...)``; ``models.
REGISTRY`` finds it from a config's type, and the engine, the iteration
scheduler, the server and the checkpoint format read its fields. A field
that is left out takes the default written here (the dense families'
answer); a field that is misspelt fails where the module is imported.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Callable, Optional, Tuple

# the serving options a family may refuse, each with its own sentence
# (``Family.refuses``); ``serving.app`` says which of them a deployment
# asked for
REFUSABLE = ("kv_pool_dtype",    # KV_POOL_DTYPE: a quantized pool
             "kv_host_blocks",   # KV_HOST_BLOCKS: the host tier
             "spec_decode",      # SPEC_DECODE: speculation
             "multi_chip",       # PP_DECODE / TP_DECODE / EP_DECODE
             "int8_weights")     # INFERENCE_DTYPE=int8


def _two_planes(config) -> Tuple[int, int, int]:
    return (2, getattr(config, "n_kv_head", config.n_head), config.head_dim)


def _every_layer(config) -> int:
    return config.n_layer


def _no_row_state(config, dtype) -> tuple:
    return ()


def _gpt2_pspecs(mesh):
    from ..parallel import spmd
    return spmd.param_pspecs(mesh)


@dataclasses.dataclass(frozen=True)
class Family:
    """One model family, as the rest of the program may ask of it."""

    # -- identity ---------------------------------------------------------
    name: str               # the checkpoint's ``family`` tag
    config_class: type      # what ``models.REGISTRY`` is keyed by
    # ``init_params`` / ``forward`` / ``forward_with_cache`` /
    # ``make_cache`` over the family's parameter tree; left out, the
    # module that defines ``config_class``
    module: Any = None

    # -- the cache --------------------------------------------------------
    # ``config -> (planes, heads, width)``: what ONE position holds in
    # ONE cached layer. The default is two planes (keys, values) of
    # ``n_kv_head x head_dim``
    cache_entry: Callable[[Any], Tuple[int, int, int]] = _two_planes
    # ``config -> int``: how many layers cache positions (default: all)
    cache_layers: Callable[[Any], int] = _every_layer
    # ``(config, dtype) -> ((shape, dtype), ...)``: the leaves of
    # ``KVCache.state`` a ROW holds beside its positions, batch axis
    # left out (default: none)
    row_state: Callable[[Any, Any], tuple] = _no_row_state
    # names of the int32 counters the cache's second, one-dimensional
    # leaf carries, and ``(counters, config, prefill) -> labels``: what
    # a span says of the counters its program handed back
    cache_counters: Tuple[str, ...] = ()
    span_labels: Optional[Callable[[dict, Any, bool], dict]] = None

    # -- what the engine asks ---------------------------------------------
    # attention bounds its cache reads by the live depth inside the
    # program: the engine cuts no windows
    bounds_own_reads: bool = False
    # a prefill into a fresh cache has a form of its own and takes the
    # engine's static ``flash_prefill`` word for "the cache is fresh"
    fresh_prefill_flag: bool = False
    # ``(config, cache_seq) -> bool``: the family brings its own Pallas
    # decode kernel(s), geometry rule and cache layout under them;
    # ``None``: the two-plane kernel's rule and the fused layout
    decode_kernel_eligible: Optional[Callable[[Any, int], bool]] = None
    # ``mesh -> PartitionSpec tree`` for tensor-parallel decode
    param_pspecs: Callable[[Any], Any] = _gpt2_pspecs
    # how ``models.stack``'s shared frame runs the family's blocks (a
    # ``stack.Frame``), or ``None`` for a family that brings its own
    frame: Any = None

    # -- what the iteration scheduler asks --------------------------------
    # ``(config, length) -> width`` a lone prompt is left-padded to, in
    # place of the scheduler's multiples of 16
    prompt_bucket: Optional[Callable[[Any, int], int]] = None
    # ``(state, depths) -> (held, seen)``: positions the rows' window
    # records hold, and positions those rows have reached
    window_positions: Optional[Callable[[Any, Any], Tuple[int, int]]] = None
    # ``config -> ops.block_diffusion.Options``: the family generates by
    # ROUNDS over blocks of ``block_length`` positions (a round is some
    # denoise forwards and a commit, and yields a whole block a row);
    # ``None``: a step yields one token a row
    block_options: Optional[Callable[[Any], Any]] = None

    # -- topology and exactness -------------------------------------------
    # the reference's GPT-2 stage-shard WIRE topology applies
    # (/forward + /forward_b, remote dispatch, shard-pod restore)
    wire_topology: bool = False
    # ``parallel.partition`` can stage this family's tree
    stageable: bool = False
    # a token's routing and logits do not depend on which other tokens
    # share its forward window
    window_independent: bool = True

    # -- what it refuses --------------------------------------------------
    # ``(option of REFUSABLE, sentence)`` in the order they are checked;
    # ``{name}`` is the config class's name, ``{value}`` what was asked
    refuses: Tuple[Tuple[str, str], ...] = ()

    def __post_init__(self):
        if self.module is None:
            object.__setattr__(self, "module",
                               sys.modules[self.config_class.__module__])
        unknown = [o for o, _ in self.refuses if o not in REFUSABLE]
        if unknown:
            raise ValueError(f"family {self.name!r} refuses {unknown}: not "
                             f"among {REFUSABLE}")
        for word in ("init_params", "forward", "forward_with_cache",
                     "make_cache"):
            if not callable(getattr(self.module, word, None)):
                raise TypeError(f"family {self.name!r}: its module has no "
                                f"{word}")

    def refusal(self, option: str, config, value=None) -> Optional[str]:
        """The family's sentence for ``option``, or ``None`` where it
        serves it."""
        for refused, why in self.refuses:
            if refused == option:
                return why.format(name=type(config).__name__, value=value)
        return None
