"""Reduction from a profiler trace to device metrics.

A trace is read once into plain lists (:class:`Trace`); every metric is a
function of those lists, so the reduction is checked on synthetic traces
on the CPU and computes the same number in every run:

* busy time of a device = length of the union of its op intervals;
* idle share = 1 - busy / window, busy averaged over the devices used;
* a kernel's time = the summed durations of the device ops that name it;
* a program's time = the summed durations of its module executions.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Event", "Trace", "union_seconds", "busy_seconds", "matching_seconds",
    "matching_events", "idle_gaps", "breakdown", "load_xplane",
]

# Lines of a TPU plane: one event per op execution, and one per program.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    detail: str = ""   # the event's stats as text (long names, kernel names)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def matches(self, pattern: str) -> bool:
        return pattern in self.name or pattern in self.detail


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]       # device name -> op events
    modules: Dict[str, List[Event]]   # device name -> program executions
    host: List[Event]                 # host-thread events (annotations, calls)


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals given in ns, in s."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total * 1e-9


def _device_events(trace: Trace, device: str) -> List[Event]:
    return trace.ops.get(device) or trace.modules.get(device) or []


def busy_seconds(trace: Trace) -> float:
    """Busy time averaged over the devices that ran anything."""
    devices = [d for d in set(trace.ops) | set(trace.modules)
               if _device_events(trace, d)]
    if not devices:
        return 0.0
    return sum(union_seconds((e.start_ns, e.end_ns)
                             for e in _device_events(trace, d))
               for d in devices) / len(devices)


def matching_events(events: Dict[str, List[Event]], pattern: str) -> List[Event]:
    return [e for evs in events.values() for e in evs if e.matches(pattern)]


def matching_seconds(events: Dict[str, List[Event]], pattern: str) -> float:
    """Summed device durations of the events that name ``pattern``."""
    return sum(e.dur_ns for e in matching_events(events, pattern)) * 1e-9


def idle_gaps(trace: Trace, limit: int = 10) -> List[Tuple[str, float]]:
    """The longest gaps between busy intervals of each device, each named
    by the most specific host event that spans the gap's middle."""
    gaps = []
    for d in set(trace.ops) | set(trace.modules):
        evs = sorted(_device_events(trace, d), key=lambda e: e.start_ns)
        end = None
        for e in evs:
            if end is not None and e.start_ns > end:
                gaps.append((e.start_ns - end, end, e.start_ns))
            end = e.end_ns if end is None else max(end, e.end_ns)
    gaps.sort(reverse=True)
    out = []
    for length, s, e in gaps[:limit]:
        mid = (s + e) / 2
        around = [h for h in trace.host if h.start_ns <= mid <= h.end_ns]
        label = min(around, key=lambda h: h.dur_ns).name if around else "no host event"
        out.append((label, length * 1e-9))
    return out


def breakdown(trace: Trace, limit: int = 10) -> dict:
    """Top device ops by summed time, and the longest idle gaps."""
    by_name: Dict[str, float] = {}
    for evs in trace.ops.values():
        for e in evs:
            by_name[e.name] = by_name.get(e.name, 0.0) + e.dur_ns * 1e-9
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:limit]
    # An op's name is its whole HLO line; its head names it well enough.
    return {"device_ops": [[k[:120], v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle_gaps(trace, limit)]}


def _stats_text(event) -> str:
    try:
        return " ".join(f"{k}={v}" for k, v in event.stats)
    except Exception:  # stats a profiler build cannot render are left out
        return ""


def load_xplane(trace_dir: str, devices: Sequence[str] = ("/device:TPU:",)
                ) -> Optional[Trace]:
    """Read the newest ``.xplane.pb`` under ``trace_dir`` into a Trace.

    Device planes are those whose name starts with one of ``devices``;
    host events come from the host plane's python threads.
    """
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        return None
    data = ProfileData.from_file(files[-1])
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if any(plane.name.startswith(p) for p in devices):
            if "SparseCore" in plane.name:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dest = ops.setdefault(plane.name, [])
                elif line.name == MODULES_LINE:
                    dest = modules.setdefault(plane.name, [])
                else:
                    continue
                dest.extend(Event(e.name, float(e.start_ns),
                                  float(e.duration_ns), _stats_text(e))
                            for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name == "python" or line.name.startswith("python"):
                    host.extend(Event(e.name, float(e.start_ns),
                                      float(e.duration_ns))
                                for e in line.events)
    return Trace(ops=ops, modules=modules, host=host)


def describe(trace_dir: str, limit: int = 40) -> str:
    """A plain-text map of a trace: planes, lines, and the most frequent
    event names of each line with a sample of their stats."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        return "no trace"
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            counts: Dict[str, list] = {}
            for e in line.events:
                c = counts.setdefault(e.name, [0, 0.0, ""])
                c[0] += 1
                c[1] += e.duration_ns
                if not c[2]:
                    c[2] = _stats_text(e)[:300]
            out.append(f"  LINE {line.name}: {sum(c[0] for c in counts.values())} events")
            for name, (n, dur, st) in sorted(counts.items(), key=lambda kv: -kv[1][1])[:limit]:
                out.append(f"    {n:7d} x {dur * 1e-6:12.3f} ms  {name[:120]}  | {st}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    # Look at a trace by hand: python3 benchmarks/chip/chipbench/trace.py DIR
    print(describe(sys.argv[1]))
