"""The system under test, brought up the way its own entry point does.

``from_env()`` -> ``create_app(cfg, model=(config, params), tokenizer=...)``
-> ``serving.http.serve(app, block=False)``, in this process: the one
that holds the chip. The weights come from ``reference.init`` (seeded,
made on the device in one jitted call, in the served type); the token
ids go over the wire as decimal text.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional

from . import spec as spec_mod
from . import traffic as traffic_mod
from .tokenizer import IntTokenizer

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Every program XLA builds or loads in this process, from JAX's own
    monitoring events (copied from ``chip_smoke.py``)."""

    def __init__(self):
        import logging
        import jax
        import jax.monitoring as mon
        self.names: list = []       # what was compiled, from JAX's own log
        counter = self

        class _Names(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    counter.names.append(msg[10:330])
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax._src.compiler").setLevel(logging.ERROR)
        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
            log = logging.getLogger(name)
            log.addHandler(_Names())
            log.propagate = False
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == _COMPILE_EVENT:
            self.programs += 1
            self.seconds += seconds

    def _on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self):
        return (self.programs, self.seconds, self.cache_hits)

    def since(self, mark):
        return (self.programs - mark[0], self.seconds - mark[1],
                self.cache_hits - mark[2])


def configure_jax(checkout: str) -> str:
    """The persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else a fixed directory inside the checkout; every program is
    kept, however quickly it compiled (PR 21: 121 of 137 programs fell
    under JAX's one-second default and were rebuilt by every process)."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = os.path.join(checkout, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


def family_config(config: dict):
    cls = spec_mod.resolve(config["family"])
    return cls(**{k: config[v] for k, v in config["family_kwargs"].items()})


def wrap_host_spans(path: str) -> List[str]:
    """Traced runs only: put the program's host work on the profiler's
    clock by wrapping the callables a data file lists
    (``module:Class.method`` -> span name) in ``TraceAnnotation``."""
    import jax
    with open(path, encoding="utf-8") as f:
        table = json.load(f)["spans"]
    for dotted, name in table.items():
        owner_path, _, leaf = dotted.rpartition(".")
        owner = spec_mod.resolve(owner_path)
        inner = getattr(owner, leaf)

        def outer(*a, __inner=inner, __name=name, **kw):
            with jax.profiler.TraceAnnotation(__name):
                return __inner(*a, **kw)
        setattr(owner, leaf, outer)
    return sorted(set(table.values()))


class Served:
    """The app behind a socket, with what the harness reads from it."""

    def __init__(self, config: dict, seed: int, trace_spans: Optional[str]):
        import jax
        from llm_sharding_demo_tpu.serving.app import create_app
        from llm_sharding_demo_tpu.serving.http import serve
        from llm_sharding_demo_tpu.utils import tracing
        from llm_sharding_demo_tpu.utils.config import from_env

        self.config = config
        self.reference = spec_mod.resolve(config["reference"])
        self.model_config = family_config(config)
        t0 = time.perf_counter()
        self.params = self.reference.init(config, seed)
        jax.block_until_ready(self.params)
        self.init_s = time.perf_counter() - t0
        self.host_span_names = wrap_host_spans(trace_spans) \
            if trace_spans else []
        os.environ.update(config["serving_env"])
        self.recorder = tracing.FlightRecorder(capacity=1 << 20)
        t0 = time.perf_counter()
        self.app = create_app(
            from_env(), model=(self.model_config, self.params),
            tokenizer=IntTokenizer(self.model_config.vocab_size),
            recorder=self.recorder)
        self.server = serve(self.app, host="127.0.0.1", port=0, block=False)
        self.port = self.server.server_address[1]
        self.create_s = time.perf_counter() - t0
        runner = self.app.runner
        self.scheduler = runner
        eng = getattr(runner, "engine", runner)
        self.engine = getattr(eng, "plain", eng)
        self.pool = getattr(runner, "pool", None)
        self._n = 0
        self._lock = threading.Lock()

    def close(self) -> None:
        self.server.shutdown()
        self.server.server_close()

    # -- requests made in this process (set-up and checks only) --------------

    def post(self, prompt_ids, max_new: int, rid: Optional[str] = None) -> dict:
        with self._lock:
            self._n += 1
            rid = rid or f"setup-{self._n}"
        body = json.dumps({"prompt": " ".join(map(str, prompt_ids)),
                           "max_new_tokens": int(max_new),
                           "mode": "greedy"}).encode()
        status, payload, _ = self.app.handle(
            "POST", "/generate", body, {"X-Request-ID": rid})
        if status != 200 or "generated" not in payload:
            raise RuntimeError(f"{rid}: HTTP {status}: {payload}")
        ids = [int(t) for t in payload["generated"].split()]
        return {"rid": rid, "new": ids[len(prompt_ids):]}

    def together(self, rows: List[tuple], stagger_s: float = 0.0) -> List[dict]:
        """``(prompt_ids, max_new)`` rows sent at once, each on a thread."""
        out: List[Optional[dict]] = [None] * len(rows)
        errs: List[BaseException] = []

        def go(i, row):
            try:
                out[i] = self.post(*row)
            except BaseException as e:  # noqa: BLE001 -- re-raised below
                errs.append(e)

        threads = []
        for i, row in enumerate(rows):
            t = threading.Thread(target=go, args=(i, row))
            t.start()
            threads.append(t)
            if stagger_s:
                time.sleep(stagger_s)
        for t in threads:
            t.join()
        if errs:
            raise errs[0]
        return out

    # -- counters ------------------------------------------------------------

    def counters(self) -> Dict[str, float]:
        """Program counters, flat: scheduler, prefix store, pool."""
        out: Dict[str, float] = {}
        sched = self.scheduler
        if hasattr(sched, "stats"):
            out.update({f"sched.{k}": v for k, v in sched.stats().items()
                        if isinstance(v, (int, float))})
        prefix = getattr(sched, "prefix", None)
        if prefix is not None:
            out.update({f"prefix.{k}": v for k, v in prefix.stats().items()
                        if isinstance(v, (int, float))})
        if self.pool is not None:
            out.update({f"pool.{k}": v for k, v in self.pool.stats().items()
                        if isinstance(v, (int, float))})
        return out

    def memory_peak_bytes(self) -> int:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        return int(max(peaks))

    def traces(self) -> Dict[str, dict]:
        return {t["request_id"]: t for t in self.recorder.snapshot()}


def warm_iter(served: Served, traffic: dict, sizes: List[tuple],
              vocab: int) -> dict:
    """Meet every program the window's requests can meet, before it opens.

    ``sizes`` is the window's own trace (``traffic.sizes``): set-up warms
    the prompt lengths it holds and no others. The iteration scheduler
    seeds a batch with one prompt (one prefill program per prompt
    bucket), admits the rest through the prefix store's programs (one
    per chunk and per ragged tail width), and grows the live batch
    through the powers of two up to ``MAX_BATCH`` (one decode-segment,
    gather, scatter and grow program per width). Whether a request
    seeds or joins is a matter of milliseconds, so every length is met
    in both roles: (1) alone; (2) rounds of 2, 4, ... ``MAX_BATCH``
    requests at once; (3) joining a live batch as deep as the longest
    prompt; (4) two requests behind one shared prefix joining one after
    the other (a store hit and its column scatter)."""
    import random
    rng = random.Random("warm")
    env = served.config["serving_env"]
    max_batch = int(env.get("MAX_BATCH", 1))
    t0 = time.perf_counter()

    def ids(n):
        return tuple(rng.randrange(vocab) for _ in range(n))

    lengths = sorted({plen for plen, _, _ in sizes})
    for n in lengths:
        served.post(ids(n), 1)
    t_len = time.perf_counter() - t0
    width = 2
    while width <= max_batch:
        served.together([(ids(lengths[0]), 40)]
                        + [(ids(lengths[0]), 8)] * (width - 1),
                        stagger_s=0.001)
        width *= 2
    t_width = time.perf_counter() - t0
    # a live row as deep as the longest prompt admits every length; a
    # group that outlived it is sent again behind the next one
    room = int(env["MAX_SEQ"]) - lengths[-1] - 8
    sp = traffic.get("shared_prefix")
    todo = [[(ids(n), 2)] for n in lengths]
    groups = [sum(todo[i:i + max_batch - 1], [])
              for i in range(0, len(todo), max(max_batch - 1, 1))]
    if sp:
        head = traffic_mod.shared_prefix_ids(traffic, 0, vocab)
        groups += [[(head + ids(1), 2)], [(head + ids(100), 2)]]
    lives = tries = never = 0
    most = 3 * len(groups) + 4       # lives; a fast machine ends them early
    while groups and lives < most:
        # a group costs the live row two or three segments
        live = threading.Thread(
            target=served.post,
            args=(ids(lengths[-1]), min(room, 96 * len(groups) + 64)))
        live.start()
        lives += 1
        time.sleep(0.3)
        while groups and live.is_alive():
            answers = served.together(groups[0], stagger_s=0.001)
            traces = served.traces()
            # whoever seeded a batch instead of joining one goes again,
            # three times at most
            groups[0] = [row for row, a in zip(groups[0], answers)
                         if not _joined(traces.get(a["rid"]))]
            tries += 1
            if not groups[0] or tries == 3:
                never += len(groups.pop(0))
                tries = 0
        live.join()
    return {"lengths": len(lengths), "lengths_s": t_len,
            "widths_s": t_width - t_len,
            "joins_s": time.perf_counter() - t0 - t_width, "lives": lives,
            "never_joined": never + sum(map(len, groups))}


def _joined(trace: Optional[dict]) -> bool:
    """Did this request join a live batch through the prefix store (its
    ``prefill`` span says so) rather than seed one?"""
    from .stats import find_spans
    return trace is not None and any(
        s.get("labels", {}).get("prefix")
        for s in find_spans(trace["spans"], "prefill"))
