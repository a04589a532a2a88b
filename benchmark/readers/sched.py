"""Per-layer metrics of the scheduler thread's own time.

The program keeps one state log a scheduler thread
(``utils/tracing.py:StateLog``, kept by ``runtime/iterbatch.py``): at
every instant the thread is ``idle`` (no request), in ``hold`` (ahead of
the device and waiting for it), or at work (``seed``, ``admit``,
``advance``, ``other``). Its seconds by state are counters
(``sched.t_<state>_s`` through ``harness/server.py:counters``); its
intervals are read from the program's module in this process, which is
the one that serves. A program without the counters or the log (an
older commit) gives ``None``.
"""

from __future__ import annotations

from ..harness import stats, xtrace
from . import pairing
from .counters import counter_delta

_STATES = ("idle", "hold", "seed", "admit", "advance", "other")
_WORK = ("seed", "admit", "advance", "other")


def _state_seconds(ctx):
    """Seconds by state between the two counter reads (the window and
    its drain), or ``None`` where a counter is missing."""
    took = {s: counter_delta(ctx, f"sched.t_{s}_s") for s in _STATES}
    if any(v is None for v in took.values()):
        return None
    return took


def sched_idle_share(ctx):
    """Share of the run in which the scheduler had NO request: nothing
    parked, nothing pending, no live batch. At a fixed offered rate a
    faster system idles more."""
    took = _state_seconds(ctx)
    if took is None or not sum(took.values()):
        return None
    return 100.0 * took["idle"] / sum(took.values())


def sched_host_ms_per_call(ctx):
    """The scheduler thread's own work (everything but ``idle`` and
    ``hold``) over the decode calls it dispatched: a call shorter than
    this leaves the device without work."""
    took = _state_seconds(ctx)
    calls = counter_delta(ctx, "sched.segments")
    if took is None or not calls:
        return None
    return 1e3 * sum(took[s] for s in _WORK) / calls


def scheduler_log():
    """The served scheduler's state log, or ``None``. The benchmark
    serves one scheduler a process; where a process holds more, the one
    that spent the most time outside ``idle`` is the one that served."""
    from llm_sharding_demo_tpu.utils import tracing
    logs = getattr(tracing, "state_logs", lambda: [])()
    if not logs:
        return None
    return max(logs, key=lambda log: sum(
        v for state, v in log.totals().items() if state != "idle"))


def idle_split(ctx, pattern):
    """``(with work, no request, window)`` in ns: the device's idle gaps
    of the traced slice (``device_idle_share``'s own gaps and window)
    cut by the log's ``idle`` intervals. A gap inside one is time with
    no request; the rest of the gaps the device had nothing to run while
    a request was live or queued. The intervals go onto the device's
    clock by the offset the slice's pairs give (a segment's ready
    instant less its call's end, the median). ``None`` where there is no
    log, the slice cannot be paired, or the log's ring no longer reaches
    back to the slice."""
    log = scheduler_log()
    pairs = pairing.paired(ctx, pattern)
    if log is None or not pairs:
        return None
    offset = stats.percentile(
        [s["ready"] - (e[1] + e[2]) / 1e9 for e, s in pairs], 50)
    first, last = xtrace.window_ns(ctx.trace)
    intervals = log.intervals()
    if intervals[0][1] + log.unix_offset - offset > first / 1e9:
        return None
    idle = [((t0 + log.unix_offset - offset) * 1e9,
             (t1 + log.unix_offset - offset) * 1e9)
            for state, t0, t1 in intervals if state == "idle"]
    idle = [(i0, i1) for i0, i1 in idle if i0 < last and i1 > first]
    gaps = xtrace.idle_gaps(ctx.trace.ops[pairing.first_device(ctx)])
    no_request = sum(min(g1, i1) - max(g0, i0)
                     for g0, g1 in gaps for i0, i1 in idle
                     if i0 < g1 and i1 > g0)
    with_work = sum(g1 - g0 for g0, g1 in gaps) - no_request
    return with_work, no_request, last - first


def device_idle_with_work_share(ctx, pattern):
    """Share of the traced slice in which the device was idle while a
    request was live or queued: ``device_idle_share`` less the idle time
    under "no request"."""
    split = idle_split(ctx, pattern)
    if split is None:
        return None
    return 100.0 * split[0] / split[2]
