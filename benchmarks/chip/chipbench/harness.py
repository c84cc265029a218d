"""Runs one cell once: set-up, a measured window, an optional traced
window, then the comparison with the plain reference.

The last line of standard output is one JSON object (see ``result_line``);
the numbers compared, each beside its limit, are the last lines of
standard error and the last key of that object.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import math
import shutil
import sys
import time
import types
from typing import Dict, List, Optional

from . import trace as trace_mod
from .files import ROOT, Cell, load_module
from .peaks import PEAKS, peaks_for

__all__ = ["Check", "NoChip", "run_cell", "TRACE_DIR"]

TRACE_DIR = ROOT / ".chipbench" / "trace"
# Default length of a traced window; a traffic file may set its own.
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit (<=)."""

    name: str
    value: Optional[float]
    limit: float

    @property
    def ok(self) -> bool:
        return self.value is not None and math.isfinite(self.value) \
            and self.value <= self.limit


def compare(cell: Cell, numbers: dict) -> List[Check]:
    """Each number the configuration limits, beside its limit; a number
    the run could not produce fails."""
    return [Check(k, numbers.get(k), float(v))
            for k, v in cell.config["limits"].items()]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class _Events:
    """Counts jax.monitoring events, to show what compiles and when."""

    def __init__(self) -> None:
        from jax import monitoring

        self.counts: collections.Counter = collections.Counter()
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.counts[name] += 1

    def _duration(self, name, _secs, **_):
        self.counts[name] += 1

    def compiles(self) -> Dict[str, int]:
        return {k: v for k, v in self.counts.items()
                if "compile" in k or "trace" in k}


def _delta(after: Dict[str, int], before: Dict[str, int]) -> Dict[str, int]:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)}


def _run_window(driver, seconds: float, trace_seconds: Optional[float]):
    """Run whole units of work until ``seconds`` have passed; with
    ``trace_seconds``, trace from the start and stop the window at the first
    unit boundary after that long."""
    import jax
    from repro.obs.trace import TRACER

    units: List[dict] = []
    if trace_seconds is not None:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        TRACER.configure(enabled=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        seconds = min(seconds, trace_seconds)
    start = time.perf_counter()
    try:
        while True:
            units.append(driver.run_unit())
            if time.perf_counter() - start >= seconds:
                break
        window_s = time.perf_counter() - start
    finally:
        spans = None
        if trace_seconds is not None:
            jax.profiler.stop_trace()
            spans = [e for e in TRACER.events() if e.get("ph") == "X"]
            TRACER.configure(enabled=False)
    return units, window_s, spans


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             *, require_tpu: bool = True, options: Optional[dict] = None,
             out=None) -> dict:
    """Run ``cell`` once; print and return its result object.

    ``require_tpu=False`` (tests only) drives the same path on whatever JAX
    finds, with ``options`` passed to the driver (interpret-mode kernels,
    smoke widths); its numbers are never device metrics.
    """
    out = out or sys.stdout
    import jax

    devs = jax.devices()
    backend_s = time.perf_counter() - t0
    device = device_info(devs)
    if require_tpu:
        if device["platform"] != "tpu":
            raise NoChip(f"JAX found no TPU: {json.dumps(device)}")
        if device["count"] < cell.chips:
            raise NoChip(f"the cell needs {cell.chips} chips, JAX sees "
                         f"{device['count']}")
        peaks = peaks_for(device["kind"])
    else:
        peaks = PEAKS["TPU v5 lite"]
    cache = None
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache

        cache = enable_compile_cache()
        # Cache every program, however fast it compiles, so that set-up
        # does the same work in every run after the first.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    events = _Events()
    log(f"[bench] {cell.name}: device {json.dumps(device)} up at "
        f"{backend_s:.3f} s, compile cache {cache}, seed {seed}, {seconds} s, "
        f"trace {int(trace)}")

    refmod = load_module(cell.reference_path)
    driver = load_module(cell.driver_path).Driver(
        cell, seed, refmod, dict(options or {}))
    driver.setup()
    setup_s = time.perf_counter() - t0
    log(f"[bench] set-up {setup_s:.3f} s; compile events in set-up: "
        f"{json.dumps(events.compiles())}")

    before = events.compiles()
    trace_s = cell.traffic.get("trace_seconds", TRACE_SECONDS) if trace else None
    units, window_s, spans = _run_window(driver, seconds, trace_s)
    in_window = _delta(events.compiles(), before)
    log(f"[bench] window {window_s:.3f} s, {len(units)} units; compile "
        f"events in window: {json.dumps(in_window)}")
    mem = memory_peak(devs)
    attempted, failed = driver.attempted_failed(units)

    ctx = types.SimpleNamespace(
        cell=cell, config=cell.config, traffic=cell.traffic, peaks=peaks,
        units=units, window_s=window_s, setup_s=setup_s, spans=spans,
        trace=None, busy_s=None, chips=cell.chips)
    breakdown = None
    if trace:
        tr = trace_mod.load_xplane(str(TRACE_DIR))
        if tr is None:
            raise RuntimeError("the profiler wrote no trace")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx.trace = tr
        ctx.busy_s = trace_mod.busy_seconds(tr)
        breakdown = trace_mod.breakdown(tr)
        device.update(busy_s=ctx.busy_s, window_s=window_s)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = load_module(cell.metric_path(m["name"])).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device["memory_peak_bytes"] = mem

    driver.release()
    checks = compare(cell, driver.numbers())
    correct = bool(checks) and all(c.ok for c in checks)
    for c in checks:
        log(f"[check] {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAIL'}")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    print(json.dumps(result), file=out, flush=True)
    return result
