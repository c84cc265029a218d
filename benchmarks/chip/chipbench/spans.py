"""Reduction of the program's own spans to times.

The program's tracer (``repro.obs.trace.TRACER``) records each span as a
Chrome ``X`` event with its duration in µs, an ``id``, and the ``parent``
id of the span it ran inside. From those:

* a span's self time = its duration less the durations of its children;
* the children of some spans = the spans whose parent is one of them.

While tracing, every span is also a profiler annotation of the same name
on the host plane, on the device's clock; ``idle_by_span`` splits the
device's idle time by the innermost such span open at each instant.

A program without these spans or ids yields empty lists, so a reader finds
nothing to read and returns None rather than raising.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Sequence

from .trace import Trace, _device_events

__all__ = ["NO_SPAN", "named", "children", "self_times", "idle_by_span"]

# The key of idle time under none of the spans asked for.
NO_SPAN = "(no span)"


def named(spans, name: str) -> List[dict]:
    """The spans called ``name``."""
    return [e for e in spans or [] if e.get("name") == name]


def children(spans, parents: Iterable[dict], name: str) -> List[dict]:
    """The spans called ``name`` whose parent is one of ``parents``."""
    ids = {p["id"] for p in parents if p.get("id") is not None}
    return [e for e in named(spans, name) if e.get("parent") in ids]


def self_times(spans) -> Dict[int, float]:
    """Span id -> its duration less its children's, in µs."""
    own = {e["id"]: e["dur"] for e in spans or [] if e.get("id") is not None}
    for e in spans or []:
        if e.get("parent") in own:
            own[e["parent"]] -= e["dur"]
    return own


def _idle_intervals(events) -> List[tuple]:
    """The gaps between a device's busy intervals, in ns."""
    gaps, end = [], None
    for e in sorted(events, key=lambda e: e.start_ns):
        if end is not None and e.start_ns > end:
            gaps.append((end, e.start_ns))
        end = e.end_ns if end is None else max(end, e.end_ns)
    return gaps


def idle_by_span(trace: Trace, names: Sequence[str]) -> Dict[str, float]:
    """Device idle seconds under the innermost host event named in
    ``names`` (the latest begun of those open), averaged over the devices
    that ran anything; idle time under none of them is keyed ``NO_SPAN``.
    Idle time counts between a device's first and last op."""
    wanted = set(names)
    host = [h for h in trace.host if h.name in wanted]
    out = dict.fromkeys(list(names) + [NO_SPAN], 0.0)
    devices = [d for d in set(trace.ops) | set(trace.modules)
               if _device_events(trace, d)]
    for d in devices:
        idle = _idle_intervals(_device_events(trace, d))
        # Sweep the boundaries of idle intervals and host spans together:
        # (time, order, kind, index); ends sort before starts at one time.
        points = []
        for k, (s, e) in enumerate(idle):
            points += [(s, 1, "idle", k), (e, 0, "idle", k)]
        for k, h in enumerate(host):
            points += [(h.start_ns, 1, "span", k), (h.end_ns, 0, "span", k)]
        points.sort()
        open_spans: set = set()
        in_idle, last = False, None
        for t, starts, kind, k in points:
            if in_idle and t > last:
                key = NO_SPAN
                if open_spans:
                    inner = max(open_spans, key=lambda i: (host[i].start_ns,
                                                           -host[i].dur_ns))
                    key = host[inner].name
                out[key] += (t - last) * 1e-9 / len(devices)
            last = t
            if kind == "idle":
                in_idle = bool(starts)
            elif starts:
                open_spans.add(k)
            else:
                open_spans.discard(k)
    return out
