"""From a profiler trace (``.xplane.pb``) to device metrics.

``jax.profiler.ProfileData`` reads the file with nothing but JAX: planes
(one per device, one for the host), their lines, and events with a start
and a duration in nanoseconds. A device plane has a line of whole
programs (``XLA Modules``) and a line of single operations (``XLA Ops``);
the host plane has one line per thread, where the ``TraceAnnotation``
spans that ``server.wrap_host_spans`` puts round the program's host work
appear on the same clock.

The arithmetic works on plain ``(name, start_ns, duration_ns)`` tuples,
so the tests feed it hand-made events as well as a recorded file.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]        # name, start_ns, duration_ns

MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)


@dataclasses.dataclass
class Trace:
    modules: Dict[str, List[Event]]     # device plane -> program events
    ops: Dict[str, List[Event]]         # device plane -> operation events
    host: List[Event]                   # host spans (TraceAnnotation et al.)
    lines: Dict[str, List[str]]         # plane -> its line names (for faults)

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)


def newest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str, host_names: Optional[Iterable[str]] = None) -> Trace:
    """``host_names``: keep only host events with these names (the spans
    the harness put there); None keeps every host event."""
    from jax.profiler import ProfileData
    keep = set(host_names) if host_names is not None else None
    data = ProfileData.from_file(path)
    trace = Trace({}, {}, [], {})
    for plane in data.planes:
        names = []
        is_device = plane.name.startswith("/device:") and \
            "TPU" in plane.name and "SparseCore" not in plane.name
        for line in plane.lines:
            names.append(line.name)
            if is_device and line.name in MODULE_LINES + OP_LINES:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in line.events]
                side = trace.modules if line.name in MODULE_LINES else trace.ops
                side.setdefault(plane.name, []).extend(evs)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if keep is None or e.name in keep:
                        trace.host.append((e.name, float(e.start_ns),
                                           float(e.duration_ns)))
        trace.lines[plane.name] = names
    return trace


# -- interval arithmetic -------------------------------------------------------

def merged(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Union of the events' intervals, as disjoint sorted ``(start, end)``."""
    out: List[List[float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [(a, b) for a, b in out]


def busy_ns(events: Iterable[Event]) -> float:
    return sum(b - a for a, b in merged(events))


def window_ns(trace: Trace) -> Tuple[float, float]:
    """First start to last end over every device operation traced."""
    evs = [e for d in trace.devices for e in trace.ops[d]]
    if not evs:
        raise ValueError(f"no device operation in the trace; planes and "
                         f"lines: {trace.lines}")
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def busy_and_window_s(trace: Trace) -> Tuple[float, float]:
    """Seconds an operation ran, averaged over the devices, and the
    length of the traced window."""
    lo, hi = window_ns(trace)
    busy = [busy_ns(trace.ops[d]) for d in trace.devices]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def idle_gaps(events: Iterable[Event]) -> List[Tuple[float, float]]:
    m = merged(events)
    return [(a[1], b[0]) for a, b in zip(m, m[1:]) if b[0] > a[1]]


def attribute(gap: Tuple[float, float], host: Iterable[Event]) -> str:
    """What the host was doing in an idle gap: the span covering most of
    it, or ``"no host span"``."""
    best, best_ns = "no host span", 0.0
    for name, s, d in host:
        cover = min(gap[1], s + d) - max(gap[0], s)
        if cover > best_ns:
            best, best_ns = name, cover
    return best


def whole_calls(events: Iterable[Event]) -> float:
    """How many whole calls the events amount to. The traced slice cuts
    the call that is running when it opens or closes, and the profiler
    keeps the piece: counted as a call, it would make a step look
    shorter than it is (a roofline share over 100%). A piece counts as
    the share of its program's median call that it lasted."""
    by_name: Dict[str, List[float]] = {}
    for name, _, dur in events:
        by_name.setdefault(name, []).append(dur)
    return sum(sum(d) / statistics.median(d) for d in by_name.values())


def matching(events: Iterable[Event], pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0])]


_HLO = re.compile(r"^%?([\w\-]+?)[.\d]*\s*=\s*(\(?[a-z]\w*\[[\d,]*\])?.*?"
                  r"(fusion|custom-call|while|conditional|copy|[\w\-]+)\(")


def short_name(name: str) -> str:
    """A device operation's event name is its whole HLO text. Shorten it
    to the instruction's name without XLA's numbering, the shape of its
    (first) result and, for a custom call, its target: ``fusion
    bf16[14336]``, ``_call bf16[64,4,128] tpu_custom_call``. A program's
    name loses its fingerprint: ``jit__decode_seg_impl``."""
    m = _HLO.match(name)
    if not m:
        return re.sub(r"(\(\d+\)|[.\d]+)$", "", name) or name
    base, shape = m.group(1), (m.group(2) or "").lstrip("(")
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return " ".join(x for x in (base, shape, target and target.group(1)) if x)


def is_container(name: str) -> bool:
    """Loops and branches span the operations inside them: they count for
    busy time (a union) and never in a sum by name."""
    return bool(re.match(r"^%?(while|conditional)[.\d]*\s*=", name))


def total_by_name(events: Iterable[Event]) -> Dict[str, float]:
    """Seconds by shortened name, containers left out."""
    out: Dict[str, float] = {}
    for name, _, d in events:
        if is_container(name):
            continue
        key = short_name(name)
        out[key] = out.get(key, 0.0) + d / 1e9
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps by what the host was doing (first device; the cells across
    chips read every device through their own readers)."""
    dev = trace.devices[0]
    ops = sorted(total_by_name(trace.ops[dev]).items(),
                 key=lambda kv: -kv[1])[:top]
    gaps: Dict[str, float] = {}
    for g in idle_gaps(trace.ops[dev]):
        who = attribute(g, trace.host)
        gaps[who] = gaps.get(who, 0.0) + (g[1] - g[0]) / 1e9
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:top]]}


def describe(trace: Trace, top: int = 25) -> dict:
    """What a person looks at before trusting a reader's patterns."""
    out = {"lines": trace.lines, "host_names": sorted(
        {e[0] for e in trace.host})[:200]}
    for d in trace.devices:
        mods: Dict[str, list] = {}
        for name, _, dur in trace.modules.get(d, []):
            m = mods.setdefault(name, [0, 0.0])
            m[0] += 1
            m[1] += dur / 1e9
        out[d] = {
            "modules": sorted(([k, n, t, t / n] for k, (n, t) in mods.items()),
                              key=lambda r: -r[2])[:top],
            "ops": sorted(total_by_name(trace.ops[d]).items(),
                          key=lambda kv: -kv[1])[:top]}
    return out
