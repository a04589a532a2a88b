"""Tracing: request-scoped span trees + ``jax.profiler`` helpers.

SURVEY.md §5 "Tracing / profiling": the reference imports ``time`` and
never uses it (reference server.py:3). Two layers here:

**Profiler helpers** (device-level, attach-a-tool workflows):

- ``trace(dir)``: context manager capturing an XLA/TPU profile viewable
  in TensorBoard/Perfetto (device timelines, HLO cost, HBM traffic);
- ``annotate(name)``: named span that shows up inside those traces
  (``jax.profiler.TraceAnnotation``);
- ``timed(name)``: lightweight host-side wall-clock span recording into
  ``utils.metrics.REGISTRY`` — per-request numbers /metrics exposes.
  ``timed(..., sync=True)`` additionally ``block_until_ready``s the
  value the body hands to ``handle.sync(...)`` before closing the
  window: DEVICE truth instead of the async-dispatch enqueue window
  (utils.graftscope's attribution mode uses it; serving never does).

**Request traces** (always-on, no profiler attached): every /generate
request carries a ``RequestTrace`` — a tree of timed spans (tokenize →
queue wait → prefill → decode segments → detokenize) annotated with
labels (mode, batch width, prefix hit depth, spec acceptance). The
serving layer derives TTFT/TPOT histograms from it and keeps the last N
completed traces in the ``FlightRecorder`` served at ``GET
/debug/requests``, so a slow request is diagnosable after the fact
without a profiler in the loop.

Propagation: the ambient trace rides a ``contextvars.ContextVar`` set by
``use_trace`` — runtime modules record through the module-level ``span``
/ ``record`` helpers, which no-op when no trace is active (zero cost off
the serving path). Batch schedulers run device work for MANY requests on
one worker thread; they wrap shared phases in ``use_trace(fanout(
traces))`` so one measured span lands in every participating request's
tree.

Span timestamps are ``time.perf_counter`` values; serialized timelines
are relative to the request's start. Scheduler-side prefill and decode
spans cover a DISPATCH (segments queue asynchronously on the device),
so their windows are instants of the host, which runs ahead of the
device. **Ready instants** put the device's completions on the same
clock: ``READY.hand(array, [(trace, span), ...])`` gives the array to
one daemon thread that waits in FIFO order (the device finishes
programs in the order they were enqueued) and stamps ``span.ready``
the instant the array exists, serialized as the label ``ready_ms`` on
the trace's own clock. The dispatching thread never waits; a trace is
settled (``RequestTrace.settle``: the caller's thread waits on
whatever is still unstamped) before the flight recorder keeps it.

**State logs** (always on): what a span tree cannot hold is time in
which there is NO request. A thread that serves many requests (the iter
scheduler's) keeps a ``StateLog``: at every instant it is in exactly one
named state, ``enter(state)`` closes the state before it, and the log
keeps cumulative seconds by state, the newest closed intervals on the
spans' clock, and a ``TraceAnnotation`` a state on the profiler's, so a
profile taken with ``trace(dir)`` carries the thread's states beside the
device's operations. ``state_logs()`` hands out the process's logs.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import queue
import threading
import time
import uuid
import weakref
from collections import deque
from typing import Iterator, List, Optional

from . import graftsched, grafttime

log = logging.getLogger(__name__)

# Lock-discipline contract (tools/graftcheck locks pass): a trace's
# committed root spans and the flight recorder's ring are the only
# cross-thread mutable state here (open-span stacks are thread-local by
# design); both live under their instance's ``_lock`` — including the
# fanout commit, which appends to OTHER traces' span lists under each
# target's own lock. So do a trace's handovers still waiting for their
# ready instant (``_unready``: the scheduler thread appends, the
# caller's thread drains) and the ready waiter's lazily started thread.
# A state log's totals, open state and ring are written by the thread
# that owns the log and read by others (a sampler, a debug handler), and
# the process-wide set of logs by whoever builds or lists one.
GUARDED_STATE = {"spans": "_lock", "_traces": "_lock",
                 "_unready": "_lock", "_thread": "_lock",
                 "counters": "_once",
                 "_totals": "_lock", "_open": "_lock", "_ring": "_lock",
                 "_logs": "_lock"}
LOCK_ORDER = ("_lock",)

# Timeline contract (tools/graftcheck timeline pass): every span lands
# on the unified causal stream (utils/grafttime) — open at entry, close
# with its measured window — correlated by the owning trace's
# X-Request-ID (fanout spans carry every participating rid).
TIMELINE_EVENTS = {
    "span_open": "_TraceSink.span",
    "span_close": "_TraceSink.span / add_span / RequestTrace.finish",
}


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False) -> Iterator[None]:
    """Capture a device-level profiler trace into ``log_dir``."""
    import jax
    jax.profiler.start_trace(log_dir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named span visible in profiler traces (device + host timelines)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class _TimedHandle:
    """What ``timed`` yields: hand ``sync(value)`` the dispatch result
    to opt that value into the window's close (device truth when the
    ``sync=`` mode is armed); ``seconds`` carries the measured duration
    after the block exits (graftscope's ring reads it)."""

    __slots__ = ("seconds", "_sync_value", "_armed")

    def __init__(self, armed: bool):
        self._armed = armed
        self._sync_value = None
        self.seconds = 0.0

    def sync(self, value):
        self._sync_value = value
        return value


@contextlib.contextmanager
def timed(name: str, registry=None, sync: bool = False,
          **labels) -> Iterator[_TimedHandle]:
    """Wall-clock span recorded as a histogram observation.

    Truth model: jax dispatch is ASYNC, so by default the window closes
    when the body returns — i.e. when the device work was ENQUEUED (the
    honest serving-thread view; the device may still be executing, so
    device time is silently undercounted). ``sync=True`` closes the
    window only after ``jax.block_until_ready`` on the value the body
    registered via ``handle.sync(...)`` — device truth, at the price of
    a blocking host sync per window (graftscope's attribution runs use
    it; the serving path never does). Both behaviors are pinned by
    tests/test_observability.py.
    """
    from .metrics import REGISTRY
    reg = registry if registry is not None else REGISTRY
    h = _TimedHandle(bool(sync))
    t0 = time.perf_counter()
    body_ok = False
    try:
        yield h
        body_ok = True
    finally:
        if body_ok and h._armed and h._sync_value is not None:
            # only after a SUCCESSFUL body: a body exception must
            # propagate unmasked, not be replaced by whatever a
            # poisoned in-flight computation raises from the sync
            import jax
            jax.block_until_ready(h._sync_value)
        h.seconds = time.perf_counter() - t0
        reg.observe(name, h.seconds, **labels)


# -- request-scoped span trees -----------------------------------------------


def new_request_id() -> str:
    return uuid.uuid4().hex[:12]


class Span:
    """One timed node: name, [t0, t1) perf_counter window, labels,
    children. Append-only while open; read-only once closed — but for
    ``ready``: the perf_counter instant at which the result of the
    dispatch this span covers existed on the device, stamped after the
    span closed (``ReadyWaiter``), or given by a call site that waited
    itself. None where nobody knows."""

    __slots__ = ("name", "t0", "t1", "labels", "children", "ready")

    def __init__(self, name: str, t0: float, t1: Optional[float] = None,
                 labels: Optional[dict] = None,
                 ready: Optional[float] = None):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.labels = dict(labels) if labels else {}
        self.children: List["Span"] = []
        self.ready = ready

    @property
    def duration(self) -> float:
        return (self.t1 if self.t1 is not None else self.t0) - self.t0

    def to_dict(self, origin: float) -> dict:
        d = {"name": self.name,
             "start_ms": round((self.t0 - origin) * 1e3, 3),
             "duration_ms": round(self.duration * 1e3, 3)}
        if self.labels or self.ready is not None:
            d["labels"] = dict(self.labels)
            if self.ready is not None:
                d["labels"]["ready_ms"] = round(
                    (self.ready - origin) * 1e3, 3)
        if self.children:
            d["spans"] = [c.to_dict(origin) for c in self.children]
        return d


class _Handover:
    """One device array and the spans that cover its enqueue. Whoever
    waits for the array first (the waiter thread, or a caller's thread
    settling its trace) stamps ``at``; every span gets that instant."""

    __slots__ = ("array", "covers", "then", "at", "counters", "values")
    _once = threading.Lock()      # one settler reads a handover's counters

    def __init__(self, array, covers: List[Span], then=None,
                 counters=None):
        self.array = array
        self.covers = covers
        self.then = then
        self.at: Optional[float] = None
        # ``(device vector, label(values) -> dict)``: numbers the device
        # computed beside ``array`` (a segment's routing counters), read
        # once the array exists and written onto the covered spans (a
        # list of dicts: one a covered span, in their order)
        self.counters = counters
        self.values = None

    def settle(self) -> None:
        """Wait until the array exists, then stamp. Safe from several
        threads at once: each waits on the same array, the stamps lie
        microseconds apart and any of them is true."""
        array, covers = self.array, self.covers
        if self.at is None and array is not None:
            wait = getattr(array, "block_until_ready", None)
            if wait is not None:      # a host array exists already
                try:
                    wait()
                except Exception:  # noqa: BLE001 — a deleted or poisoned
                    pass           # array: its request fails on its own
                    #                path; the instant it was found out
                    #                is the only one there is
        if self.at is None:
            self.at = time.perf_counter()
        self.array = None             # the waiter keeps no buffer alive
        with self._once:
            # the first settler reads the counters and labels the spans;
            # whoever else settles waits here until the labels are on
            counters, self.counters = self.counters, None
            if counters is not None:
                try:
                    # computed by the program that made ``array``
                    self.values = counters[0].tolist()
                    labels = counters[1](self.values)
                    if isinstance(labels, dict):     # the same on each
                        labels = [labels] * len(covers)
                    for s, said in zip(covers, labels):
                        s.labels.update(said)
                except Exception:  # noqa: BLE001 — as above: the
                    pass           # request fails on its own path
        for s in covers:
            if s.ready is None:
                s.ready = self.at
        self.covers = ()              # ... and no span


class ReadyWaiter:
    """Puts the device's completions on the host's clock. ``hand`` takes
    a device array and the spans covering its enqueue and returns at
    once; one daemon thread waits on the arrays in the order they were
    handed over — the order the device finishes them in — so each
    completion is stamped as it happens. The thread starts with the
    first handover (importing this module starts nothing)."""

    def __init__(self):
        self._lock = graftsched.lock("tracing.ReadyWaiter._lock")
        self._fifo: "queue.SimpleQueue[_Handover]" = queue.SimpleQueue()
        self._thread: Optional[threading.Thread] = None

    def hand(self, array, covered, then=None,
             counters=None) -> _Handover:
        """``covered``: ``(trace, span)`` pairs, each span on that
        trace; the trace will not be flight-recorded before the span is
        stamped. ``then(at)`` runs on the waiter thread, once, after
        every earlier handover's — for series derived from consecutive
        ready instants. ``counters``: see ``_Handover``; ``then`` may
        read the handover's ``values`` (it runs after the stamp)."""
        h = _Handover(array, [s for _, s in covered], then, counters)
        for tr, _ in covered:
            tr._await(h)
        self._fifo.put(h)
        with self._lock:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="tracing-ready", daemon=True)
                self._thread.start()
        return h

    def _run(self) -> None:
        while True:
            h = self._fifo.get()
            h.settle()
            # run once and let go: a ``then`` that holds the handover
            # before it must not chain a whole batch's handovers together
            then, h.then = h.then, None
            if then is not None:
                try:
                    then(h.at)
                except Exception:  # noqa: BLE001 — a metric's arithmetic
                    # must not end the stamping
                    log.exception("ready waiter: then() failed")


class _TraceSink:
    """Span-tree recording shared by ``RequestTrace`` and ``fanout``.

    Nesting is per-thread (a thread-local open-span stack guarded by a
    lock for the cross-thread ``add_span`` form), so a scheduler thread
    adding spans to a caller thread's trace lands them at the root — the
    right shape, since the two threads' phases don't enclose each other.
    """

    def __init__(self):
        self._lock = graftsched.lock("tracing._TraceSink._lock")
        self._tls = threading.local()
        self.spans: List[Span] = []
        self._unready: List[_Handover] = []

    def _await(self, handover: _Handover) -> None:
        with self._lock:
            self._unready.append(handover)

    def settle(self) -> None:
        """Wait, on the calling thread, for every handover of this
        trace that is still unstamped (none, as a rule: the caller
        fetched the same arrays to assemble its tokens)."""
        with self._lock:
            waiting, self._unready = self._unready, []
        for h in waiting:
            h.settle()

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _rid(self):
        """This sink's timeline correlation: the owning request's id
        (a fanout returns every target's — the shared-phase analog);
        the bare sink has none."""
        return getattr(self, "request_id", None)

    def _commit(self, span: Span) -> None:
        stack = self._stack()
        if stack:
            stack[-1].children.append(span)
        else:
            with self._lock:
                self.spans.append(span)

    @contextlib.contextmanager
    def span(self, name: str, **labels) -> Iterator[Span]:
        s = Span(name, time.perf_counter(), labels=labels)
        stack = self._stack()
        stack.append(s)
        grafttime.emit("span_open", name=name, rid=self._rid(), t=s.t0)
        try:
            yield s
        finally:
            s.t1 = time.perf_counter()
            stack.pop()
            self._commit(s)
            grafttime.emit("span_close", name=name, rid=self._rid(),
                           t=s.t1, dur_ms=round(s.duration * 1e3, 3))

    def add_span(self, name: str, t0: float, t1: float,
                 ready: Optional[float] = None, **labels) -> Span:
        """Record an already-timed span (schedulers time phases once and
        attach them to every participating request). ``ready``: the
        instant the covered dispatch's result existed, where the call
        site waited for it itself."""
        s = Span(name, t0, t1, labels=labels, ready=ready)
        self._commit(s)
        grafttime.emit("span_close", name=name, rid=self._rid(), t=t1,
                       dur_ms=round(s.duration * 1e3, 3))
        return s


class RequestTrace(_TraceSink):
    """The span tree of one request, plus identity and summary fields."""

    def __init__(self, request_id: Optional[str] = None, **labels):
        super().__init__()
        self.request_id = request_id or new_request_id()
        self.labels = dict(labels)
        self.t0 = time.perf_counter()
        self.started_unix = time.time()
        self.t1: Optional[float] = None

    def finish(self) -> "RequestTrace":
        if self.t1 is None:
            self.t1 = time.perf_counter()
            # the request's terminal timeline event: the whole-request
            # window closing (the "final span close" a /debug/timeline
            # ?rid= stream ends on)
            grafttime.emit("span_close", name="request",
                           rid=self.request_id, t=self.t1,
                           dur_ms=round(self.duration * 1e3, 3))
        return self

    @property
    def duration(self) -> float:
        return (self.t1 if self.t1 is not None
                else time.perf_counter()) - self.t0

    def find(self, name: str) -> Optional[Span]:
        """First span with ``name``, depth-first."""
        def walk(spans):
            for s in spans:
                if s.name == name:
                    return s
                got = walk(s.children)
                if got is not None:
                    return got
            return None
        with self._lock:
            return walk(self.spans)

    def find_all(self, name: str) -> List[Span]:
        out: List[Span] = []

        def walk(spans):
            for s in spans:
                if s.name == name:
                    out.append(s)
                walk(s.children)
        with self._lock:
            walk(self.spans)
        return out

    def graft(self, name: str, payload: Optional[dict], t0: float,
              t1: float, **labels) -> Span:
        """Join a downstream replica's serialized trace (its
        ``to_dict`` payload, fetched by the propagated X-Request-ID)
        into THIS trace as a hop span over ``[t0, t1)`` whose children
        are the replica's own spans — the fleet router's cross-replica
        stitch, so ``/debug/requests`` shows ONE tree per request with
        the hop visible. The replica's relative timeline is re-based
        onto the hop start (same-process clocks in the harness; across
        real processes the skew is the hop's queueing, which is
        exactly what the offset shows). ``payload=None`` (recorder
        missing, ring entry evicted) degrades to a bare hop span."""
        hop = Span(name, t0, t1, labels=labels)
        if payload is not None:
            hop.labels.setdefault("replica_request_id",
                                  payload.get("request_id"))
            hop.children = [span_from_dict(c, t0)
                            for c in payload.get("spans", ())]
        self._commit(hop)
        return hop

    def to_dict(self) -> dict:
        with self._lock:
            spans = [s.to_dict(self.t0) for s in self.spans]
        d = {"request_id": self.request_id,
             "started_unix": round(self.started_unix, 3),
             "duration_ms": round(self.duration * 1e3, 3),
             "spans": spans}
        if self.labels:
            d["labels"] = dict(self.labels)
        return d


def span_from_dict(d: dict, base: float) -> Span:
    """Rebuild a serialized span (a ``Span.to_dict`` payload) as a live
    Span re-based onto ``base`` (a local perf_counter instant) — the
    cross-replica stitch's unit: a downstream replica's relative-ms
    timeline becomes spans on THIS process's clock, child shape
    preserved."""
    t0 = base + d.get("start_ms", 0.0) / 1e3
    labels = dict(d.get("labels") or {})
    ready_ms = labels.pop("ready_ms", None)
    s = Span(d.get("name", "?"), t0,
             t0 + d.get("duration_ms", 0.0) / 1e3, labels=labels,
             ready=None if ready_ms is None else base + ready_ms / 1e3)
    s.children = [span_from_dict(c, base) for c in d.get("spans", ())]
    return s


class _FanoutTrace(_TraceSink):
    """Records spans once and commits each completed root to every target
    trace — how a batch scheduler attributes one shared device phase
    (prefill, a decode round) to all rows riding it. Nested spans inside
    the fanout keep their tree shape; the shared Span objects are
    read-only after commit, so sharing across traces is safe."""

    def __init__(self, traces: List[RequestTrace]):
        super().__init__()
        self._targets = [t for t in traces if t is not None]

    def _rid(self):
        # one shared phase, every participating request's stream
        return tuple(t.request_id for t in self._targets)

    def _commit(self, span: Span) -> None:
        stack = self._stack()
        if stack:
            stack[-1].children.append(span)
            return
        for t in self._targets:
            with t._lock:
                t.spans.append(span)


def fanout(traces: List[Optional[RequestTrace]]) -> _FanoutTrace:
    return _FanoutTrace(traces)


_current: "contextvars.ContextVar[Optional[_TraceSink]]" = \
    contextvars.ContextVar("request_trace", default=None)


def current_trace() -> Optional[_TraceSink]:
    return _current.get()


@contextlib.contextmanager
def use_trace(trace_obj: Optional[_TraceSink]) -> Iterator[None]:
    token = _current.set(trace_obj)
    try:
        yield
    finally:
        _current.reset(token)


@contextlib.contextmanager
def span(name: str, **labels) -> Iterator[Optional[Span]]:
    """Record a span on the ambient trace; no-op (still yields) when no
    trace is active — runtime modules call this unconditionally."""
    tr = _current.get()
    if tr is None:
        yield None
        return
    with tr.span(name, **labels) as s:
        yield s


def record(name: str, t0: float, t1: float,
           ready: Optional[float] = None, **labels) -> None:
    """Attach an already-timed span to the ambient trace (no-op without
    one) — for call sites that measured the window themselves."""
    tr = _current.get()
    if tr is not None:
        tr.add_span(name, t0, t1, ready=ready, **labels)


def annotate_span(**labels) -> None:
    """Merge labels into the innermost OPEN span of the ambient trace
    (no-op without one) — e.g. the prefix store marking hit depth on the
    enclosing prefill span."""
    tr = _current.get()
    if tr is None:
        return
    stack = tr._stack()
    if stack:
        stack[-1].labels.update(labels)


class FlightRecorder:
    """Bounded ring of the last N completed request traces, served at
    ``GET /debug/requests`` — the after-the-fact view of where a slow
    request's time went, no profiler attached."""

    def __init__(self, capacity: int = 64):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._lock = graftsched.lock("tracing.FlightRecorder._lock")
        self._traces: "deque[RequestTrace]" = deque(maxlen=capacity)

    def record(self, trace_obj: RequestTrace) -> None:
        trace_obj.finish()
        # never keep a trace with a handed-over span unstamped
        trace_obj.settle()
        with self._lock:
            self._traces.append(trace_obj)

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)

    def find(self, request_id: str) -> Optional[dict]:
        """Newest recorded trace with this X-Request-ID as a JSON
        timeline, or None — the join point the fleet router stitches
        replica span trees through (newest wins on rid reuse, same as
        the graftload TTFT join)."""
        with self._lock:
            traces = list(self._traces)
        for t in reversed(traces):
            if t.request_id == request_id:
                return t.to_dict()
        return None

    def snapshot(self, n: Optional[int] = None, slowest: bool = False,
                 errors_only: bool = False,
                 profile: Optional[str] = None) -> List[dict]:
        """Most recent (or slowest) ``n`` traces as JSON timelines,
        newest/slowest first. ``errors_only`` keeps only error-labeled
        traces (failed/shed/degraded requests) — the fault-triage view
        ``/debug/requests?errors=1`` serves. ``profile`` keeps only
        traces whose X-Workload-Profile label matches — the per-
        workload triage view a graftload run uses to isolate one
        traffic shape's slow/failed requests."""
        with self._lock:
            traces = list(self._traces)
        traces.reverse()                      # newest first
        if errors_only:
            traces = [t for t in traces if "error" in t.labels]
        if profile is not None:
            traces = [t for t in traces
                      if t.labels.get("profile") == profile]
        if slowest:
            traces.sort(key=lambda t: t.duration, reverse=True)
        if n is not None:
            traces = traces[:max(n, 0)]
        return [t.to_dict() for t in traces]


class StateLog:
    """The time of ONE thread, cut into named states: at every instant
    the thread is in exactly one, ``enter(state)`` closes the state
    before it, and the states' seconds sum to the log's lifetime.

    Kept three ways: cumulative seconds by state (``totals``: the open
    state counts up to the instant of the read, so a delta between two
    reads is exact whatever state they fall in); the newest ``capacity``
    closed intervals ``(state, t0, t1)`` on the spans' clock
    (``intervals``; ``unix_offset`` puts them on the wall clock, taken
    once, as ``RequestTrace.started_unix`` is); and, for the states
    ``annotations`` names, a ``TraceAnnotation`` on the profiler's
    clock, opened and closed by ``enter``. Only the owning thread calls
    ``enter``; any thread reads."""

    def __init__(self, states, initial: str, annotations=None,
                 label: Optional[str] = None, capacity: int = 65536,
                 clock=time.perf_counter):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.label = label
        self._clock = clock
        self._annotations = dict(annotations or {})
        self._span = None             # the open annotation (owner only)
        self._lock = graftsched.lock("tracing.StateLog._lock")
        self._totals = dict.fromkeys(states, 0.0)
        if initial not in self._totals:
            raise ValueError(f"unknown state {initial!r}")
        self.t_start = clock()
        self.unix_offset = time.time() - self.t_start
        self._open = (initial, self.t_start)
        self._ring: "deque[tuple]" = deque(maxlen=capacity)
        STATE_LOGS.add(self)

    def enter(self, state: str) -> tuple:
        """The thread is in ``state`` from now on. Returns the state it
        left and the seconds it had been there (0.0 where it stays)."""
        with self._lock:
            if state not in self._totals:
                raise ValueError(f"unknown state {state!r}")
            now = self._clock()
            was, since = self._open
            if was == state:
                return was, 0.0
            self._totals[was] += now - since
            self._ring.append((was, since, now))
            self._open = (state, now)
        span, self._span = self._span, None
        if span is not None:
            span.__exit__(None, None, None)
        name = self._annotations.get(state)
        if name is not None:
            self._span = annotate(name)
            self._span.__enter__()
        return was, now - since

    def totals(self) -> dict:
        """Seconds by state so far, the open state up to now."""
        with self._lock:
            now = self._clock()
            out = dict(self._totals)
            state, since = self._open
            out[state] += now - since
        return out

    def intervals(self, n: Optional[int] = None) -> List[tuple]:
        """The newest ``n`` intervals ``(state, t0, t1)``, oldest first,
        the last one the open state up to now."""
        with self._lock:
            now = self._clock()
            out = list(self._ring)
            state, since = self._open
        out.append((state, since, now))
        return out if n is None else out[len(out) - max(n, 1):]

    def snapshot(self, n: Optional[int] = None) -> dict:
        """What ``/debug/requests`` serves: seconds by state and the
        newest ``n`` intervals on the wall clock."""
        off = self.unix_offset
        return {
            "label": self.label,
            "started_unix": round(self.t_start + off, 3),
            "seconds": {k: round(v, 6) for k, v in self.totals().items()},
            "intervals": [{"state": s, "start_unix": round(t0 + off, 6),
                           "duration_ms": round((t1 - t0) * 1e3, 3)}
                          for s, t0, t1 in self.intervals(n)],
        }


class _StateLogs:
    """The process's live state logs (held weakly: a log lives as long
    as the thread's owner does)."""

    def __init__(self):
        self._lock = graftsched.lock("tracing._StateLogs._lock")
        self._logs: "weakref.WeakSet[StateLog]" = weakref.WeakSet()

    def add(self, log: StateLog) -> None:
        with self._lock:
            self._logs.add(log)

    def all(self) -> List[StateLog]:
        with self._lock:
            logs = list(self._logs)
        return sorted(logs, key=lambda log: log.t_start)


def state_logs() -> List[StateLog]:
    """Every live state log of the process, oldest first: one a
    scheduler thread, told apart by ``label`` (the replica) where a
    process holds several."""
    return STATE_LOGS.all()


def debug_requests_payload(recorder: FlightRecorder, query: dict,
                           serving: dict,
                           scheduler: Optional[StateLog] = None):
    """The ``/debug/requests`` response body (?n/?slowest/?errors/
    ?profile) — ONE implementation shared by the replica surface
    (serving/app.py) and the fleet router (serving/router.py), so a
    new query filter cannot land on one debug surface and silently
    desynchronize the other. ``serving`` is the per-app identity
    block; ``scheduler`` the state log of the app's scheduler thread,
    where it has one: its seconds by state and newest ``n`` intervals,
    the time no request's tree holds. Returns ``(422, detail)`` on an
    unparseable ``n``."""
    try:
        n = int(query.get("n", "32"))
    except ValueError:
        return 422, {"detail": "n must be an integer"}
    slowest = query.get("slowest", "").lower() in ("1", "true", "yes")
    errs = query.get("errors", "").lower() in ("1", "true", "yes")
    prof = query.get("profile") or None
    return {
        "serving": serving,
        "capacity": recorder.capacity,
        "recorded": len(recorder),
        "order": "slowest" if slowest else "newest",
        **({"profile": prof} if prof else {}),
        "requests": recorder.snapshot(n=n, slowest=slowest,
                                      errors_only=errs, profile=prof),
        **({"scheduler": scheduler.snapshot(n)}
           if scheduler is not None else {}),
    }


# process-wide default recorder (what serving.app uses; injectable there)
RECORDER = FlightRecorder()

# process-wide set of state logs (``state_logs()``)
STATE_LOGS = _StateLogs()

# process-wide ready waiter: one device queue, one FIFO
READY = ReadyWaiter()
