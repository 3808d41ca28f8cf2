"""Reduction of a profiler trace to device time (``.xplane.pb``, read with
``jax.profiler.ProfileData``).  Kept with the benchmark, and checked on a
recorded trace under ``tests/benchmark_harness/``, so every PR reduces a
trace the same way.

Device planes are the ``/device:<KIND>:<n>`` planes.  A device is busy
while one of its ``XLA Modules`` events runs (the whole program, as the
TPU runtime reports it); the ``XLA Ops`` line names the operations inside.
Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``s on
the ``/host:CPU`` plane, all named ``bench.*``.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple


class Event(NamedTuple):
    name: str
    start: float  # seconds on the trace's clock
    end: float


MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


@dataclass
class Trace:
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # device -> programs
    ops: Dict[str, List[Event]] = field(default_factory=dict)      # device -> operations
    host: List[Event] = field(default_factory=list)                # bench.* spans

    def window(self, name: str = "bench.window") -> Tuple[float, float]:
        spans = [e for e in self.host if e.name == name]
        if not spans:
            raise TraceError(f"no {name!r} span in the trace")
        return spans[0].start, spans[-1].end


class TraceError(RuntimeError):
    """The profiler gave no trace, or one without device activity."""


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise TraceError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                into = {MODULE_LINE: trace.modules, OPS_LINE: trace.ops}.get(line.name)
                if into is None:
                    continue
                into.setdefault(plane.name, []).extend(
                    Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.host.extend(
                    Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith(HOST_PREFIX))
    trace.host.sort(key=lambda e: e.start)
    for events in (*trace.modules.values(), *trace.ops.values()):
        events.sort(key=lambda e: e.start)
    return trace


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals."""
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def busy_seconds(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def mean_busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Busy seconds in [lo, hi), averaged over the devices that ran."""
    if not trace.modules:
        raise TraceError("the trace holds no device programs")
    per = [busy_seconds(ev, lo, hi) for ev in trace.modules.values()]
    return sum(per) / len(per)


def idle_share(trace: Trace, lo: float, hi: float) -> float:
    return 1.0 - mean_busy_seconds(trace, lo, hi) / (hi - lo)


def matches(name: str, patterns: Sequence[str]) -> bool:
    return any(p in name for p in patterns)


def program_seconds(trace: Trace, patterns: Sequence[str], lo: float,
                    hi: float) -> Optional[float]:
    """Device seconds of the programs whose names hold one of ``patterns``,
    summed over events and averaged over devices; None if none ran."""
    total, found = 0.0, False
    for events in trace.modules.values():
        for e in clip(events, lo, hi):
            if matches(e.name, patterns):
                total += e.end - e.start
                found = True
    return total / len(trace.modules) if found else None


def program_name(name: str) -> str:
    """``jit_apply_batch(1556...)`` -> ``jit_apply_batch``."""
    return name.split("(", 1)[0]


def op_name(name: str) -> str:
    """``%while.23 = (s32[] ...) while(...)`` -> ``%while.23``."""
    return name.split(" = ", 1)[0]


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> List[list]:
    """The operations that took most device time, each named after the
    program it ran in (``jit_resolve/%while.23``); where a device reports no
    operations, its programs: ``[[name, seconds], ...]``."""
    totals: Dict[str, float] = {}
    for dev, modules in trace.modules.items():
        mods = clip(modules, lo, hi)
        ops = clip(trace.ops.get(dev, []), lo, hi)
        if not ops:
            for e in mods:
                key = program_name(e.name)
                totals[key] = totals.get(key, 0.0) + (e.end - e.start)
            continue
        starts = [m.start for m in mods]
        for e in ops:
            i = bisect.bisect_right(starts, e.start) - 1
            prog = program_name(mods[i].name) if i >= 0 and e.start < mods[i].end else "?"
            key = f"{prog}/{op_name(e.name)}"
            totals[key] = totals.get(key, 0.0) + (e.end - e.start)
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, lo: float, hi: float,
              host: Sequence[Event] = (), n: int = 10) -> List[list]:
    """The longest device-idle gaps in [lo, hi), each named by what the host
    was doing: the innermost (shortest) host span that covers at least half
    as much of the gap as the span covering most of it (``host.other`` when
    none does): ``[[name, seconds], ...]``."""
    events = next(iter(trace.modules.values()), [])
    busy = union(clip(events, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        cover = [(min(b, e.end) - max(a, e.start), e.end - e.start, e.name)
                 for e in host if e.name != "bench.window"]
        cover = [c for c in cover if c[0] > 0]
        best = "host.other"
        if cover:
            most = max(c[0] for c in cover)
            best = min((c for c in cover if c[0] >= most / 2), key=lambda c: c[1])[2]
        out.append([best, b - a])
    return out
