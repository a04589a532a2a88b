"""The serving path's whole programs, compiled for a TPU v5e on the
decode kernel the cells run (``decode_kernel="layer"`` -> ``"device"``).

The twin of ``tests/test_tpu_compile.py`` one level up: not a kernel
alone but the engine's decode segment and the prefix store's ``_extend``
around it, from shapes, for the described chip (``tests/conftest.py``'s
``one_chip``). The benchmark's configurations keep their published
widths, read from ``benchmark/configs/*.json`` the way the harness reads
them; only the depth is cut, for compile time. A compile that passes is
not a chip run.
"""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import server
from benchmark.harness.spec import Spec, resolve
from benchmark.rehearse import _Abstract
from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.ops import block_decode, expert_ffn, gated_delta
from llm_sharding_demo_tpu.runtime.engine import DecodeEngine, SamplingConfig
from llm_sharding_demo_tpu.runtime.prefix_cache import PrefixCachingEngine

# the depth cut: two layers of mistral-7b-l16; one dense and one expert
# layer (the 16 experts this chip holds) of joyai-llm-flash-ep16; one
# period of qwen3-next-80b-ep32 (three linear-attention layers and the
# softmax one: its depth comes in whole periods); the first period of
# k-exaone-236b-ep8 (the dense layer and two expert layers with a
# window each, then the full-attention one: whole periods too)
LAYERS = 2
DEPTH = {"qwen3-next-80b-ep32": 4, "k-exaone-236b-ep8": 4}
GDN = "qwen3-next-80b-ep32"
SWA = "k-exaone-236b-ep8"
# the decode widths the iteration scheduler compiles (PERF.md 5)
WIDTHS = (1, 2, 4, 8, 16)
SEG_STEPS = 32
# GB a decode segment may hold beside its arguments and results (the
# compiler's own report here: 0.0004, 0.069 and 0.042 at 16 rows; the
# window family's 0.50 a period from two rows on is the compiler's own
# copies of the four query projections, which it keeps in fast memory
# over a call's 32 steps: 0.01 at one row)
TEMPORARIES = {"gpt2-124m": 0.01, "mistral-7b-l16": 0.1,
               "joyai-llm-flash-ep16": 0.06, GDN: 0.25, SWA: 0.6}
# the same for the store's widest stride, 256 ids (0.068 and 0.113: the
# figure PR 28's builder read by hand, PERF.md 6) and for a seed's
# longest prompt (0.83 at 1,536 ids and 1.70 at 2,560). The window
# family's cache is 8,704 positions deep and its longest prompt 8,192:
# a stride's 0.30 is one block of 128 queries' float32 scores over the
# cache in a full layer (0.29), a prefill's 1.68 the dense layer's
# [8192, 18432] products, every position's logits (0.63) and a block of
# 256 queries' scores (0.54): [64, 8192, 8192] float32 would be 17 GB
EXTEND_TEMPORARIES = {"mistral-7b-l16": 0.1, "joyai-llm-flash-ep16": 0.15,
                      GDN: 0.3, SWA: 0.35}
PREFILL_TEMPORARIES = {"mistral-7b-l16": 1.0, "joyai-llm-flash-ep16": 2.0,
                       GDN: 2.0, SWA: 2.0}


# Device operations in ONE iteration of a layer loop of the compiled
# decode segment at the cells' whole depth (the loop's body): every
# instruction the compiler schedules there but the ones below, which
# move or name data without a launch. A budget is the
# number reached, not looser, so that a change which grows a layer
# back shows here; Mistral's is pinned, so that a shared function's
# change that reaches it shows whichever way it goes.
# Before the single-position forms (ISSUE 31) the expert layer stood at
# 103 + 24 a tile at width 1 and 88, 96, 96, 92 + 24 at widths 2 to 16;
# with them, and the held experts' tiles a loop over the experts that
# were hit, at (67, 61, 65, 65, 60) + 7 a hit expert. Since ISSUE 51 the
# tiles are ONE ``held_expert_tiles`` kernel a layer and no loop: the
# second number is how many such kernels a layer holds, and the first is
# no looser than the loop's layer with ONE expert hit (74, 68, 72, 72,
# 67): the hit list and the weighted sum around the kernel cost 6, 2,
# -2, -2 and 2 operations a LAYER where the loop cost 7 a hit expert.
NOT_OPERATIONS = {"parameter", "tuple", "get-tuple-element", "bitcast",
                  "reshape", "constant", "copy-done"}
LAYER_OPERATIONS = {
    ("mistral-7b-l16", 8): (33, None),
    ("joyai-llm-flash-ep16", 1): (73, 1),
    ("joyai-llm-flash-ep16", 2): (63, 1),
    ("joyai-llm-flash-ep16", 4): (63, 1),
    ("joyai-llm-flash-ep16", 8): (63, 1),
    ("joyai-llm-flash-ep16", 16): (62, 1),
}
# what the single-position forms took out of a latent layer and must
# not come back: the up-projections' copy into a head-major layout and
# any sort (the router's top 8 of 256, the 8 pairs by expert)
GONE = (r"= bf16\[64,8,32,128\]\S* copy\(", r" sort\(")
# what the chunked delta rule's inversion by matmuls took out of a walk
# and a prefill: ``lax.linalg.triangular_solve``, which this compiler
# spells as the custom call ``InvertDiagBlocksLowerTriangular`` (a loop
# over a chunk's 64 rows inside it) under the operation's name
SERIAL_SOLVE = (r"InvertDiagBlocks", r"triangular[-_]solve")


def _engine(chip, shapes, model_config, max_seq, dtype):
    """A ``decode_kernel="layer"`` engine over shapes alone, with the
    parameter tree its programs take."""
    def is_leaf(x):
        return isinstance(x, _Abstract)
    abstract = jax.tree.map(
        lambda s: _Abstract(s.shape, s.dtype, chip.sharding), shapes)
    eng = DecodeEngine(abstract, model_config, max_seq=max_seq, dtype=dtype,
                       decode_kernel="layer")
    assert eng._decode_kernel == "device"
    return eng, jax.tree.map(lambda a: a.sds, eng.params, is_leaf=is_leaf)


def _built(chip, name, layers=LAYERS):
    if name == "gpt2-124m":
        cfg = gpt2.CONFIGS["gpt2"]
        shapes = jax.eval_shape(
            lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0)))
        return _engine(chip, shapes, cfg, 1024, jnp.bfloat16)
    config = Spec().config(name)
    config = dict(config, num_hidden_layers=(DEPTH.get(name, layers)
                                             if layers else
                                             config["num_hidden_layers"]))
    env = config["serving_env"]
    shapes = jax.eval_shape(
        lambda: resolve(config["reference"]).init(config, 0))
    return _engine(chip, shapes, server.family_config(config),
                   int(env["MAX_SEQ"]), env["INFERENCE_DTYPE"])


@pytest.fixture(scope="module")
def built(one_chip):
    """(name, layers) -> (engine, parameter shapes), each built once;
    ``layers=None`` is the configuration's whole depth."""
    cache = {}

    def get(name, layers=LAYERS):
        if (name, layers) not in cache:
            cache[name, layers] = _built(one_chip, name, layers)
        return cache[name, layers]
    return get


def _decode_segment(chip, eng, params, batch, counted=True):
    """The program a decode call of the scheduler runs, compiled, and
    the cache it donates: the COUNTED form (the call's length an int32
    operand, at most ``SEG_STEPS``), or the ``lax.scan`` of ``SEG_STEPS``
    steps that a caller with no count keeps."""
    lowered, cache = _lowered_segment(chip, eng, params, batch, counted)
    return lowered.compile(), cache


def _lowered_segment(chip, eng, params, batch, counted=True):
    shape = chip.shape
    cache = chip.placed(jax.eval_shape(lambda: eng._fresh_cache(batch)))
    steps = (shape((), jnp.int32),) if counted else ()
    # a family that generates by blocks takes each row's block and runs
    # ROUNDS (``engine._decode_rounds``)
    token = (batch,) if eng.block is None else (batch,
                                                eng.block.block_length)
    return jax.jit(
        eng._decode_seg_impl, donate_argnums=(2,),
        static_argnames=("sampling", "window")).lower(
            params, shape(token, jnp.int32), cache,
            shape((batch,), jnp.int32),
            shape((SEG_STEPS, batch, 2), jnp.uint32), *steps,
            sampling=SamplingConfig(mode="greedy"), window=None), cache


def _computations(text):
    """``{name: its instructions}`` of a compiled module's text."""
    lines, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            name = head.group(1)
            lines[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None and " = " in line:
            lines[name].append(line)
    return lines


def _loops(text):
    """``{body: (computation that holds the loop, its instructions)}``
    of every ``while`` in a compiled module's text."""
    lines, found = _computations(text), {}
    for holder, held in lines.items():
        for line in held:
            loop = re.search(r" while\(.*body=%?([\w.\-]+)", line)
            if loop:
                found[loop.group(1)] = holder, lines[loop.group(1)]
    return found


def _expert_kernels(lines):
    """The ``held_expert_tiles`` kernels among a computation's
    instructions (ISSUE 51: one an expert layer)."""
    found = [x for x in lines
             if re.search(rf"%{expert_ffn.KERNEL_NAME}[.\d]* = ", x)]
    assert all("tpu_custom_call" in x for x in found), found
    return found


def _trip_bounds(text):
    """The whole-number constants that the conditions of a compiled
    module's ``while`` loops compare with: a counted loop's trips (the
    chunk scan of a 2,560-id prefill reads 40)."""
    lines = _computations(text)
    return {int(n)
            for cond in re.findall(r" while\(.*?condition=%?([\w.\-]+)", text)
            for n in re.findall(r"constant\((\d+)\)", "\n".join(lines[cond]))}


def _operations(lines):
    found = []
    for line in lines:
        op = re.search(r" = .*?(?:^|[\s)])([a-z][\w\-]*)\(",
                       line.split(", metadata=")[0])
        if op.group(1) not in NOT_OPERATIONS:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("name,batch", [
    ("gpt2-124m", 8),
    *[("mistral-7b-l16", b) for b in WIDTHS],
    *[("joyai-llm-flash-ep16", b) for b in WIDTHS],
    *[(GDN, b) for b in WIDTHS],
    *[(SWA, b) for b in WIDTHS]])
def test_engine_decode_segment_compiles(one_chip, built, name, batch):
    """The program a decode call of the scheduler runs: up to
    ``SEG_STEPS`` greedy steps over ``batch`` rows on the engine's own
    cache, which it donates."""
    eng, params = built(name)
    compiled, cache = _decode_segment(one_chip, eng, params, batch)
    mem = one_chip.check(compiled)
    # the cache is updated in place: what the program holds beside its
    # arguments is activations and logits, never a second cache
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < TEMPORARIES[name] * 1e9, (
        f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries")


def _held(mem):
    """Bytes a program holds while it runs: arguments, results that are
    no argument's buffer, and temporaries."""
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


@pytest.mark.parametrize("name", ["mistral-7b-l16", "joyai-llm-flash-ep16",
                                  GDN, SWA])
def test_the_counted_segment_holds_no_more_than_the_scan(one_chip, built,
                                                         name):
    """At the cells' whole depth and 16 rows: the counted loop carries
    the donated cache in place as the scan does. What it holds beside
    the scan's bytes (the compiler's own report here): 16,896 B of
    arguments in every family (the keys it indexes, ``SEG_STEPS`` x 16
    pairs, which a greedy scan is never handed, and the count), 512 B
    of results (the last token), its ``[SEG_STEPS, 16]`` token buffer
    among the temporaries, which otherwise moved by -225,792 to +64,000
    B (the linear-attention family's). 128 KiB holds that; a second
    copy of one cache plane would be a hundred megabytes and more."""
    eng, params = built(name, None)
    counted, cache = _decode_segment(one_chip, eng, params, 16)
    scan, _ = _decode_segment(one_chip, eng, params, 16, counted=False)
    got, want = counted.memory_analysis(), scan.memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    assert got.alias_size_in_bytes >= cache_bytes
    assert got.temp_size_in_bytes <= want.temp_size_in_bytes + 2**17, (
        got.temp_size_in_bytes, want.temp_size_in_bytes)
    assert _held(got) <= _held(want) + 2**17, (_held(got), _held(want))


def _write_back(chip, built, name, batch):
    """The program behind a decode call of a pooled batch, compiled at
    the cell's pool (``KV_POOL_BLOCKS`` blocks, the configuration's
    whole depth) for ``batch`` rows: its memory report, its text and
    the pool's bytes. The pool object itself is a two-layer one of one
    row's blocks: its mover is compiled from shapes."""
    from llm_sharding_demo_tpu.models import cache_entry, cache_layers
    from llm_sharding_demo_tpu.ops import paged_attention as PA
    from llm_sharding_demo_tpu.runtime.kv_pool import KVBlockPool
    eng, _ = built(name)
    config = Spec().config(name)
    cfg, env = server.family_config(config), config["serving_env"]
    bs = int(env["KV_BLOCK_SIZE"])
    pool = KVBlockPool.for_engine(eng, eng._cache_seq // bs, block_size=bs,
                                  state_slots=2)
    planes, heads, width = cache_entry(cfg)
    layers = cache_layers(cfg)
    data = chip.shape(PA.pool_shape(layers, int(env["KV_POOL_BLOCKS"]),
                                    heads, bs, width, planes))
    cache = jax.eval_shape(lambda: eng._fresh_cache(batch))
    k = chip.shape((layers,) + cache.k.shape[1:])
    v = chip.shape(cache.v.shape if cache.v.ndim <= 1
                   else (layers,) + cache.v.shape[1:], cache.v.dtype)
    span = PA.span_blocks(SEG_STEPS, bs, pool.nbm)
    assert span == 3
    compiled = pool._scatter_span.lower(
        data, k, v, chip.shape((batch, pool.nbm), jnp.int32),
        chip.shape((), jnp.int32), span).compile()
    return (compiled.memory_analysis(), compiled.as_text(),
            data.size * data.dtype.itemsize)


@pytest.mark.parametrize("batch", WIDTHS)
@pytest.mark.parametrize("name", ["mistral-7b-l16", "joyai-llm-flash-ep16"])
def test_the_write_back_holds_three_updates_a_row(one_chip, built, name,
                                                  batch):
    """``KVBlockPool.scatter_span`` at the two-plane pool of the
    ``chat`` cells and at the one-plane pool of ``assist``: the first
    column is an operand (one program a width, whatever a call's depth
    and length), ``batch`` x 3 block updates (unrolled, or the trips of
    one loop) where the whole-row scatter has ``batch`` x 128 or 192,
    the pool updated in place and nothing of its size beside it (the
    compiler's own report here: 1.7 MB of temporaries at 16 rows of the
    2.69 GB pool, 0.13 MB at the latent one's 2.10 GB)."""
    mem, text, pool_bytes = _write_back(one_chip, built, name, batch)
    entry = text[text.index("\nENTRY "):].split("\n", 2)[1]
    assert " s32[]" in entry, entry     # the first column, traced
    updates = len(re.findall(r" dynamic-update-slice\(", text))
    trips = _trip_bounds(text)
    assert (updates, trips) in ((batch * 3, set()), (1, {batch * 3})), (
        updates, trips)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 2**23, mem.temp_size_in_bytes


@pytest.mark.parametrize("name,batch", sorted(LAYER_OPERATIONS))
def test_decode_segment_layer_operations(one_chip, built, name, batch):
    """The census of ISSUE 31 (PERF.md 3): the decode segment at the
    cell's WHOLE depth (at two layers the compiler unrolls the layer
    loops into the step's body and there is nothing to count), its
    steps' loop holding the layer loop and that, for the latent family,
    ONE kernel over the experts that were hit and no loop (ISSUE 51)."""
    eng, params = built(name, None)
    compiled, _ = _decode_segment(one_chip, eng, params, batch)
    loops = _loops(compiled.as_text())
    steps = [b for b, (holder, _) in loops.items() if holder not in loops]
    assert len(steps) == 1, sorted(loops)
    layers = [b for b, (holder, _) in loops.items() if holder == steps[0]]
    assert len(layers) == 1, sorted(loops)
    layer = _operations(loops[layers[0]][1])
    inside = [b for b, (holder, _) in loops.items() if holder == layers[0]]
    want_layer, want_kernels = LAYER_OPERATIONS[name, batch]
    listed = "\n".join([f"{len(layer)} operations in a layer:"] + layer)
    assert not inside, (inside, listed)    # no loop over experts, or any
    if want_kernels is None:        # pinned, not a budget
        assert len(layer) == want_layer, listed
        return
    assert len(layer) <= want_layer, listed
    assert len(_expert_kernels(loops[layers[0]][1])) == want_kernels, listed
    # at 512 rows the fold's own [512, 4096] product has W_uv's shape
    own_product = batch * eng.config.n_head == 512
    for gone in (GONE[1:] if own_product else GONE):
        back = [x for x in layer if re.search(gone, x)]
        assert not back, back


# The same census for the linear-attention / expert family, whose layer
# loop runs over PERIODS: one iteration is three linear-attention layers
# (two projections, the convolution, the state kernel, the gated norm,
# the output projection) and one gated softmax layer, each with its
# router, shared expert and, since ISSUE 51, ONE kernel over the held
# experts that were hit: (operations in a period, such kernels in it).
# With a loop over those experts a layer the numbers reached were (297,
# 7 a hit expert) and (314, 29) (ISSUE 35): at 16 rows the 160 (row,
# choice) pairs are more than a tile, so routing takes its general form
# (a sort) and the loop took its own (29 operations a tile); the kernel
# takes one tile an expert wherever the TOKENS fit a tile, so the
# experts' sort, gathers and scatters are gone there too. The numbers
# reached, under the loop's period ALONE (289 and 277 against 297 and
# 314, before its 7 and 29 operations a hit expert).
PERIOD_OPERATIONS = {
    (GDN, 1): (289, 4),
    (GDN, 16): (277, 4),
}
# The window / expert family at its whole depth is two periods: the
# first on its own leaves in the step's body, the second the one
# iteration of its loop over periods, which the compiler unrolls into
# the step's body too. So a STEP is counted: (operations in a step,
# ``held_expert_tiles`` kernels in it), the numbers reached. Eight
# layers, of which six read a ring (a select, two dots and a softmax in
# XLA) and two run the two-plane decode kernel; seven expert layers.
# With a loop over the hit experts a layer they were (612, 7 a hit
# expert) and (547, 7) (ISSUEs 37, 39: the program whose steps are
# counted by an operand): 549 is under the loop's step alone, 578 under
# the loop's step with one expert hit a layer (547 + 7 x 7 = 596).
STEP_OPERATIONS = {
    (SWA, 1): (549, 7),
    (SWA, 16): (578, 7),
}


@pytest.mark.parametrize("name,batch", sorted(PERIOD_OPERATIONS))
def test_decode_segment_period_operations(one_chip, built, name, batch):
    eng, params = built(name, None)
    compiled, _ = _decode_segment(one_chip, eng, params, batch)
    loops = _loops(compiled.as_text())
    steps = [b for b, (holder, _) in loops.items() if holder not in loops]
    assert len(steps) == 1, sorted(loops)
    periods = [b for b, (holder, _) in loops.items() if holder == steps[0]]
    assert len(periods) == 1, sorted(loops)
    period = _operations(loops[periods[0]][1])
    want_period, want_kernels = PERIOD_OPERATIONS[name, batch]
    experts = _expert_kernels(loops[periods[0]][1])
    kernels = [x for x in period if "custom-call" in x]
    # three state kernels and one two-plane decode kernel a period,
    # and the held experts' tiles of each of its four layers
    assert len(experts) == want_kernels, experts
    assert len(kernels) == 4 + want_kernels, kernels
    assert len(period) <= want_period, "\n".join(
        [f"{len(period)} operations in a period:"] + period)
    # no loop over the experts that were hit, or any other, in a period
    assert not [b for b, (holder, _) in loops.items()
                if holder == periods[0]], sorted(loops)
    # the router's top 10 of 512 by a sort past a tile of pairs; the
    # experts sort nothing at either width
    sorts = [x for x in period if re.search(r" sort\(", x)]
    assert len(sorts) == (4 if batch > 12 else 0), sorts


@pytest.mark.parametrize("name,batch", sorted(STEP_OPERATIONS))
def test_decode_segment_step_operations(one_chip, built, name, batch):
    eng, params = built(name, None)
    compiled, _ = _decode_segment(one_chip, eng, params, batch)
    loops = _loops(compiled.as_text())
    steps = [b for b, (holder, _) in loops.items() if holder not in loops]
    assert len(steps) == 1, sorted(loops)
    step = _operations(loops[steps[0]][1])
    want_step, want_kernels = STEP_OPERATIONS[name, batch]
    experts = _expert_kernels(loops[steps[0]][1])
    kernels = [x for x in loops[steps[0]][1] if "tpu_custom_call" in x]
    # one two-plane decode kernel a full-attention layer, none for a
    # ring, and the held experts' tiles of each expert layer
    assert len(experts) == want_kernels, experts
    assert len(kernels) == eng.config.n_periods + want_kernels, kernels
    assert len(step) <= want_step, "\n".join(
        [f"{len(step)} operations in a step:"] + step)
    # no loop over the experts that were hit, or any other, in a step
    assert not [b for b, (holder, _) in loops.items()
                if holder == steps[0]], sorted(loops)
    assert not [x for x in step if re.search(r" sort\(", x)]


@pytest.fixture(scope="module")
def walks(one_chip, built):
    """(name, ids) -> the store's compiled ``_extend``, each once."""
    @functools.cache
    def get(name, ids):
        eng, params = built(name)
        store = PrefixCachingEngine(eng, capacity=8, chunk=64)
        row = one_chip.placed(jax.eval_shape(lambda: eng._fresh_cache(1)))
        return store._extend.lower(
            params, row, one_chip.shape((1, ids), jnp.int32)).compile()
    return get


@pytest.fixture(scope="module")
def prefills(one_chip, built):
    """workload -> (its configuration's name, the compiled prefill of
    the longest prompt its traffic draws), each once."""
    @functools.cache
    def get(workload):
        spec = Spec()
        entry = spec.workload(workload)
        longest = spec.traffic(entry["traffic"])["prompt"]["max"]
        eng, params = built(entry["config"])
        return entry["config"], jax.jit(eng._prefill_impl).lower(
            params, one_chip.shape((1, longest), jnp.int32),
            one_chip.shape((1,), jnp.int32)).compile()
    return get


@pytest.mark.parametrize("ids", [64, 128, 256])
@pytest.mark.parametrize("name", ["mistral-7b-l16", "joyai-llm-flash-ep16",
                                  GDN, SWA])
def test_prefix_store_extend_compiles(walks, name, ids):
    """The store's ``_extend`` at the strides a walk takes (one, two and
    four 64-token chunks; PERF.md 6, PR 28): a multi-token step over the
    kernel engine's cache, which for the latent family is the expanded
    attention form and the grouped matmul over the held experts."""
    mem = walks(name, ids).memory_analysis()
    assert mem.temp_size_in_bytes < EXTEND_TEMPORARIES[name] * 1e9, (
        f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries")


@pytest.mark.parametrize("workload", ["mistral-7b-l16.chat",
                                      "joyai-llm-flash-ep16.assist",
                                      GDN + ".threads",
                                      SWA + ".shortlong"])
def test_engine_prefill_compiles_at_the_longest_prompt(prefills, workload):
    """A seed's whole prompt in one call, at the longest the cell's
    traffic draws (what ``benchmark/rehearse.py`` compiles by hand)."""
    name, compiled = prefills(workload)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < PREFILL_TEMPORARIES[name] * 1e9, (
        f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries")


@pytest.mark.parametrize("ids", [64, 128, 256, "longest prompt"])
def test_the_chunked_rule_holds_no_serial_solve(walks, prefills, ids):
    """A walk's strides and a seed's prefill of the linear-attention
    family invert a chunk's triangle by matmuls (ISSUE 36): no serial
    solve and no loop over a chunk's rows."""
    compiled = (prefills(GDN + ".threads")[1] if isinstance(ids, str)
                else walks(GDN, ids))
    text = compiled.as_text()
    for gone in SERIAL_SOLVE:
        assert not re.search(gone, text), gone
    assert gated_delta.CHUNK not in _trip_bounds(text)


# -- the state-space / attention family (ISSUE 42) ----------------------------
#
# Its six layers are all alike, so the layer loop of its decode segment
# runs over LAYERS and one iteration is counted, as for Mistral: a
# state-space mixer (one projection in, the convolution, the state
# kernel, the gated norm, one projection out) and attention (three
# projections, rotary, the two-plane decode kernel at FIVE query heads a
# key-value head, one projection out) side by side, then the SwiGLU.
# The numbers reached (59 operations a layer at one row, 65 at 16);
# two kernel calls a layer, twelve a step.
SSM = "falcon-h1-34b-l6"
SSM_LAYER_OPERATIONS = {1: 59, 16: 65}
# GB beside arguments and results (the compiler's report here): a
# decode segment 0.575 at one row and 0.764 from two on, which is NOT
# activations: the compiler copies three stacked weights once a call,
# ahead of the steps' loop (the mixer's projection [6, 5120, 9248],
# 0.57 GB, whose width is no whole number of lane tiles, and the query
# and key projections the other way round, 0.19 GB; PERF.md 7); the
# store's strides 0.002-0.006; a seed's longest prompt, 768 ids, 0.065.
# EVERY position's logits of that prefill would be [768, 261120] float32
# = 0.80 GB on their own: the family's calls of several positions run
# the head on the last one
SSM_TEMPORARIES, SSM_EXTEND_TEMPORARIES, SSM_PREFILL_TEMPORARIES = 0.8, 0.02, 0.1


@pytest.mark.parametrize("batch", WIDTHS)
def test_hybrid_ssm_decode_segment_compiles(one_chip, built, batch):
    eng, params = built(SSM, None)
    compiled, cache = _decode_segment(one_chip, eng, params, batch)
    mem = one_chip.check(compiled)
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    # positions AND row state of all six layers are updated in place
    assert [x.shape[0] for x in jax.tree.leaves(cache)
            if x.ndim > 1] == [6, 6, 6]
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < SSM_TEMPORARIES * 1e9, (
        f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries")


@pytest.mark.parametrize("batch", sorted(SSM_LAYER_OPERATIONS))
def test_hybrid_ssm_decode_segment_layer_operations(one_chip, built, batch):
    eng, params = built(SSM, None)
    compiled, _ = _decode_segment(one_chip, eng, params, batch)
    loops = _loops(compiled.as_text())
    steps = [b for b, (holder, _) in loops.items() if holder not in loops]
    assert len(steps) == 1, sorted(loops)
    layers = [b for b, (holder, _) in loops.items() if holder == steps[0]]
    assert len(layers) == 1, sorted(loops)
    assert eng.config.n_layer in _trip_bounds(compiled.as_text())
    layer = _operations(loops[layers[0]][1])
    listed = "\n".join([f"{len(layer)} operations in a layer:"] + layer)
    kernels = [x for x in loops[layers[0]][1] if "tpu_custom_call" in x]
    # the state kernel and the two-plane decode kernel, once a layer
    assert len(kernels) == 2, kernels
    assert sum("ssm_state_update" in x for x in kernels) == 1, kernels
    assert len(layer) <= SSM_LAYER_OPERATIONS[batch], listed
    assert not [x for x in layer if re.search(r" sort\(", x)]


@pytest.mark.parametrize("ids", [64, 128, 256])
def test_hybrid_ssm_prefix_store_extend_compiles(walks, ids):
    mem = walks(SSM, ids).memory_analysis()
    assert mem.temp_size_in_bytes < SSM_EXTEND_TEMPORARIES * 1e9, (
        f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries")


def test_hybrid_ssm_prefill_holds_one_positions_logits(prefills):
    """A seed's longest prompt (768 ids) in one call: the program holds
    the LAST position's logits and no ``[768, 261120]`` array of any
    type, and its temporaries stay under what that array alone would
    take."""
    name, compiled = prefills(SSM + ".burstchat")
    assert name == SSM
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < SSM_PREFILL_TEMPORARIES * 1e9, (
        f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries")
    text = compiled.as_text()
    assert not re.search(r"\[(1,)?768,261120\]", text)
    assert re.search(r"f32\[1,(1,)?261120\]", text)
    assert 768 * 261120 * 4 > SSM_PREFILL_TEMPORARIES * 1e9


# A live row's record is a lane of the batch's working cache (ISSUE
# 48): what still MOVES a record is a joiner's merge into its lane, a
# grow's widening of the state leaves, and the store's two movers at
# ONE record. At this family's leaves ([6, B, 32, 256, 128] float32 and
# [6, B, 3, 5120] bfloat16, 25.35 MB a row) the mover that went, a
# ``jnp.take`` of 2-16 slots out of a 25-slot slab, held 0.63-0.92 GB
# beside its arguments: the compiler copied the whole slab in two halves
# of the 256-wide axis first. The compiler's report here for what stays:
# no temporaries in any of the four (the merge updates the cache's
# planes and state in place at this family's, qwen3-next's and
# kimi-linear's leaves alike).
ROW_STATE_TEMPORARIES = 0.05


def _row_state_bytes(tree):
    return sum(x.size * x.dtype.itemsize for x in tree)


def test_a_joiner_merges_into_its_lane_in_place(one_chip, built):
    from llm_sharding_demo_tpu.runtime.iterbatch import _admit_cache_impl
    eng, _ = built(SSM, None)
    cache = one_chip.placed(jax.eval_shape(lambda: eng._fresh_cache(16)))
    solo = one_chip.placed(jax.eval_shape(lambda: eng._fresh_cache(1)))
    assert [x.shape[1:] for x in cache.state] == [(16, 32, 256, 128),
                                                  (16, 3, 5120)]
    mem = jax.jit(_admit_cache_impl, donate_argnums=(0,)).lower(
        cache, solo, one_chip.shape((), jnp.int32),
        one_chip.shape((), jnp.int32)).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= _row_state_bytes(cache.state)
    assert mem.temp_size_in_bytes < ROW_STATE_TEMPORARIES * 1e9, (
        f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries")


def test_a_grow_holds_the_two_widths_of_the_row_state_and_no_more(
        one_chip, built):
    from llm_sharding_demo_tpu.runtime.iterbatch import _widen_state
    eng, _ = built(SSM, None)
    narrow = one_chip.placed(
        jax.eval_shape(lambda: eng._fresh_cache(8)).state)
    mem = jax.jit(lambda state: _widen_state(state, 8)).lower(
        narrow).compile().memory_analysis()
    # the wider leaves (and the tuple that names them) are all it makes
    assert 0 <= mem.output_size_in_bytes - 2 * _row_state_bytes(narrow) < 2**12
    assert mem.temp_size_in_bytes < ROW_STATE_TEMPORARIES * 1e9, (
        f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries")


def test_the_slabs_movers_carry_one_record(one_chip, built):
    """A restore's copy out of the store's eight slots and a snapshot's
    write into one: a slice and an in-place update, the slot an operand."""
    from llm_sharding_demo_tpu.models import row_state
    from llm_sharding_demo_tpu.runtime import state_slab
    eng, _ = built(SSM, None)
    slots = int(Spec().config(SSM)["serving_env"]["PREFIX_CACHE"])
    data = tuple(one_chip.shape(s[:1] + (slots,) + s[1:], t)
                 for s, t in row_state(eng.config, eng.dtype))
    row = tuple(one_chip.shape(x.shape[:1] + (1,) + x.shape[2:], x.dtype)
                for x in data)
    slot = one_chip.shape((), jnp.int32)
    gather = jax.jit(state_slab._gather_state_impl).lower(
        data, slot).compile().memory_analysis()
    assert gather.temp_size_in_bytes < 2**20, gather.temp_size_in_bytes
    scatter = jax.jit(state_slab._scatter_state_impl,
                      donate_argnums=(0,)).lower(
        data, row, slot).compile().memory_analysis()
    assert scatter.alias_size_in_bytes >= _row_state_bytes(data)
    assert scatter.temp_size_in_bytes < 2**20, scatter.temp_size_in_bytes


# -- the per-channel delta-rule / latent family (ISSUE 46) ---------------------
#
# Its depth comes from two published lists and cannot be cut: the whole
# 27 layers are compiled. A decode step is three groups (models.kda_moe's
# ``layer_plan``): the dense first layer ``K'`` and the tail ``K M``
# written out in the step's body (a group of one repeat is a scan of
# one, which the compiler unrolls) and ONE loop of six over ``K K M K``.
# The compiler's report here: a decode segment holds 0.27 GB beside its
# arguments at 8 and 16 rows (its own prefetches of the written-out
# groups' weights), a seed's longest prompt, 1,536 ids, 1.04 GB, of
# which every position's logits are 1.0.
KDA = "kimi-linear-48b-ep16"
KDA_TEMPORARIES, KDA_EXTEND_TEMPORARIES, KDA_PREFILL_TEMPORARIES = 0.4, 0.3, 1.3


@pytest.mark.parametrize("batch", [1, 16])
def test_kda_moe_decode_segment_compiles(one_chip, built, batch):
    eng, params = built(KDA, None)
    compiled, cache = _decode_segment(one_chip, eng, params, batch)
    mem = one_chip.check(compiled)
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    # the 7 latent layers' positions, the 20 delta-rule layers' matrices
    # and tails: all updated in place
    assert [x.shape[0] for x in jax.tree.leaves(cache)
            if x.ndim > 1] == [7, 20, 20]
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < KDA_TEMPORARIES * 1e9, (
        f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries")
    text = compiled.as_text()
    loops = _loops(text)
    steps = [b for b, (holder, _) in loops.items() if holder not in loops]
    assert len(steps) == 1, sorted(loops)
    runs = [b for b, (holder, _) in loops.items() if holder == steps[0]]
    # ONE loop in a step, over the six like runs, and none inside it:
    # the held experts' tiles are a kernel a layer (ISSUE 51; before it
    # a loop over the experts hit: two in the step's body for the tail's
    # two expert layers, four in a run's)
    assert len(runs) == 1 and len(loops) == 2, sorted(loops)
    assert 6 in _trip_bounds(text)

    def kernels(lines):
        found = [x for x in lines if "tpu_custom_call" in x]
        return (sum("kda_state_update" in x for x in found),
                sum("latent_decode_attention" in x for x in found),
                len(_expert_kernels(lines)))

    # 2 + 3 x 6 = 20 state kernels, 1 + 6 = 7 latent ones and 2 + 4 x 6
    # = 26 of the experts' (the first layer's feed-forward is dense) a
    # step
    assert kernels(loops[steps[0]][1]) == (2, 1, 2)
    assert kernels(loops[runs[0]][1]) == (3, 1, 4)


@pytest.mark.parametrize("ids", [64, 256, "longest prompt"])
def test_kda_moe_walks_and_prefill_compile(one_chip, built, ids):
    """The store's ``_extend`` at a stride of one and of four chunks and
    a seed's longest prompt: the chunked rule per channel (sub-blocks of
    16, no exponent above 0) inverts its triangle by matmuls too."""
    eng, params = built(KDA, None)
    if isinstance(ids, str):
        longest = Spec().traffic("longanswer")["prompt"]["max"]
        compiled = jax.jit(eng._prefill_impl).lower(
            params, one_chip.shape((1, longest), jnp.int32),
            one_chip.shape((1,), jnp.int32)).compile()
        limit = KDA_PREFILL_TEMPORARIES
    else:
        store = PrefixCachingEngine(eng, capacity=8, chunk=64)
        row = one_chip.placed(jax.eval_shape(lambda: eng._fresh_cache(1)))
        compiled = store._extend.lower(
            params, row, one_chip.shape((1, ids), jnp.int32)).compile()
        limit = KDA_EXTEND_TEMPORARIES
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < limit * 1e9, (
        f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries")
    text = compiled.as_text()
    for gone in SERIAL_SOLVE:
        assert not re.search(gone, text), gone
    assert gated_delta.CHUNK not in _trip_bounds(text)


# -- the held experts' tiles as one kernel (ISSUE 51) --------------------------
#
# The largest expert of the cells (k-exaone-236b-ep8: 75.5 MB, streamed
# through VMEM in chunks of 128 columns of ``f``) and the claimed cell's
# (sdar-30b-a3b-ep8: 9.4 MB, chunks of 256) in the decode segment at the
# whole depth and at the widths the census above does not compile, so
# that a chip never meets a kernel the compiler refuses for its VMEM.
# sdar's decode call runs ROUNDS over blocks of 4 positions a row: a
# forward is ``batch x 4`` tokens, which fit a tile at every width, so
# every forward takes the kernel in the form of one tile an expert.
SDAR = "sdar-30b-a3b-ep8"
SDAR_TEMPORARIES = 1.2      # GB; the compiler's own report: 1.01


@pytest.mark.parametrize("name,batch", [
    (SWA, 2), (SWA, 4), (SWA, 8), (SDAR, 1), (SDAR, 2), (SDAR, 4), (SDAR, 8)])
def test_the_expert_kernel_fits_the_chip_at_the_cells_widths(
        one_chip, built, name, batch):
    eng, params = built(name, None)
    compiled, _ = _decode_segment(one_chip, eng, params, batch)
    mem = one_chip.check(compiled)
    if name == SDAR:
        assert mem.temp_size_in_bytes < SDAR_TEMPORARIES * 1e9, (
            f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries")
    text = compiled.as_text()
    kernels = _expert_kernels(text.splitlines())
    # one a layer of a layer loop's body (sdar: the denoise forward's and
    # the commit forward's), or one an expert layer written out
    # (k-exaone's seven)
    assert len(kernels) == (2 if name == SDAR else 7), kernels
    d = eng.config.n_embd
    rows = -(-batch * (eng.block.block_length if name == SDAR else 1) // 8) * 8
    assert all(f"f32[16,{rows},{d}]" in x for x in kernels), kernels
    # no layer's stack of experts copied out in front of the kernel, nor
    # one expert's matrix (k-exaone's shared expert has an expert's
    # shape, so only sdar's program can say the second)
    held = eng.config.n_routed_experts
    f = eng.config.moe_intermediate_size
    one = "|(1,1,)?" if name == SDAR else ""
    assert not re.search(rf"= bf16\[((1,)?{held},{one}){d},{f}\]", text)


# -- a block's attention as one kernel (ISSUE 52) ------------------------------
#
# sdar's decode segment at the cell's four widths: a forward of a round
# (the denoise loop's and the commit's: two layer loops) holds ONE
# ``block_decode_attention`` a layer in place of ``attend``'s XLA form,
# whose marks are gone from the program: the layer's whole slice of the
# cache (``bf16[B,4,2048,256]``, 33.5 MB at 8 rows) and the float32
# scores over every key of every lane (``f32[B,32,4,2052]``). The cache
# is aliased through both loops and both kernels, never copied.

@pytest.mark.parametrize("batch", [1, 2, 4, 8])
def test_a_blocks_attention_is_one_kernel_a_layer(one_chip, built, batch):
    eng, params = built(SDAR, None)
    compiled, cache = _decode_segment(one_chip, eng, params, batch)
    mem = one_chip.check(compiled)
    # the compiler's own report: 1.008 GB at every width, the three
    # stacked weights' copies (D8); the kernel's buffers are VMEM's
    assert mem.temp_size_in_bytes < SDAR_TEMPORARIES * 1e9, (
        f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries")
    text = compiled.as_text()
    name = rf"%{block_decode.KERNEL_NAME}[.\d]* = "
    # one call in the body of each of two loops, and none elsewhere
    calls = [[x for x in lines if re.search(name, x)]
             for _, lines in _loops(text).values()]
    calls = [found for found in calls if found]
    assert [len(found) for found in calls] == [1, 1], calls
    assert all("tpu_custom_call" in found[0] for found in calls), calls
    assert len(re.findall(name, text)) == 2
    c = eng.config
    layer = f"{batch},{c.n_kv_head},{eng._cache_seq},{2 * c.head_dim}"
    assert not re.search(rf"= bf16\[{layer}\]", text)
    assert not re.search(
        rf"f32\[{batch},{c.n_head},{eng.block.block_length},"
        rf"{eng._cache_seq + eng.block.block_length}\]", text)
    # the cache: donated, aliased to the result, and no copy of it made
    assert not re.search(rf"= bf16\[{c.n_layer},{layer}\]\S* copy\(", text)
    held = cache.k.size * cache.k.dtype.itemsize
    assert held <= mem.alias_size_in_bytes < held + 1e6


# The cells that run ``ops/decode_attention.py``'s kernel, and the
# sliding-window family beside them, lower their decode segments to the
# PARENT's text (1db1e4a; ``_segment_digest`` with the parent's package
# on the path). A Mosaic kernel's payload carries the source locations
# of its callers, the checkout's path among them, so it is left out of
# the digest: what is compared is every XLA operation, every kernel's
# operands, aliases and shapes. (The kernels' own source,
# ``ops/decode_attention.py`` among it, is not touched by ISSUE 52.)
PARENT_SEGMENTS = {
    ("mistral-7b-l16", 8):
        "fe749cf027c1e570996f1ab183e5946c4210983bb22dd211d284fc46aa0b6754",
    ("falcon-h1-34b-l6", 8):
        "1468ac8620e635431eea0cfae84188ca88b9e202808b3774ed6e0e4965146015",
    (SWA, 4):
        "8a56a0901e767e125abd8a46985ec64fac92cb026f9906f684dbfbde3d79a22f",
}
_PAYLOAD = re.compile(r'(\\22body\\22: \\22)[^\\]*(\\22)')


def _segment_digest(chip, eng, params, batch):
    text = _lowered_segment(chip, eng, params, batch)[0].as_text()
    text, kernels = _PAYLOAD.subn(r"\1\2", text)
    assert kernels == text.count("@tpu_custom_call") > 0
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name,batch", sorted(PARENT_SEGMENTS))
def test_a_family_without_blocks_lowers_to_the_parents_segment(
        one_chip, built, name, batch):
    eng, params = built(name)
    assert eng.block is None and eng._decode_kernel == "device"
    assert (_segment_digest(one_chip, eng, params, batch)
            == PARENT_SEGMENTS[name, batch])
