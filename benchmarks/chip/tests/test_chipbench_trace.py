"""The reduction from trace to metrics, on synthetic traces."""

import os
import sys

# The benchmark's library, after the paths already there: this directory is
# also named "tests", and must not shadow the repository's own.
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_BENCH, os.path.join(os.path.dirname(os.path.dirname(_BENCH)), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import types

import pytest

from chipbench import trace as tr
from chipbench.files import BENCH_DIR, load_module


def ev(name, start_ms, dur_ms, detail=""):
    return tr.Event(name, start_ms * 1e6, dur_ms * 1e6, detail)


@pytest.mark.parametrize("intervals, want", [
    ([], 0.0),
    ([(0, 10)], 10e-9),
    ([(0, 10), (5, 15)], 15e-9),           # overlap counted once
    ([(0, 10), (10, 20)], 20e-9),          # touching
    ([(20, 30), (0, 10)], 20e-9),          # unsorted, disjoint
    ([(0, 100), (10, 20), (30, 40)], 100e-9),  # nested
])
def test_union_seconds(intervals, want):
    assert tr.union_seconds(intervals) == pytest.approx(want)


def synthetic():
    ops = {"/device:TPU:0": [
        ev("%sweep_columns_call.1 = custom-call", 0, 60),
        ev("%copy.1 = copy", 60, 1),
        ev("%sweep_columns_call.1 = custom-call", 70, 60),
        ev("%fusion.3", 100, 10),  # overlaps the kernel: busy counts it once
    ]}
    modules = {"/device:TPU:0": [ev("jit_sweep_columns_call(1)", 0, 61),
                                 ev("jit__decode(2)", 70, 60)]}
    host = [ev("run_unit", 0, 150), ev("$partition_jax.py sweep_from_columns", 61, 9)]
    return tr.Trace(ops=ops, modules=modules, host=host)


def test_busy_idle_and_kernel_time():
    t = synthetic()
    assert tr.busy_seconds(t) == pytest.approx(121e-3)
    assert tr.matching_seconds(t.ops, "%sweep_columns_call") == pytest.approx(120e-3)
    assert tr.matching_seconds(t.modules, "_decode") == pytest.approx(60e-3)
    assert tr.matching_seconds(t.ops, "no such kernel") == 0.0


def test_busy_is_averaged_over_devices():
    t = tr.Trace(ops={"a": [ev("x", 0, 10)], "b": [ev("x", 0, 30)]},
                 modules={}, host=[])
    assert tr.busy_seconds(t) == pytest.approx(20e-3)


def test_idle_gaps_are_named_by_the_innermost_host_event():
    gaps = tr.idle_gaps(synthetic())
    assert gaps[0][0] == "$partition_jax.py sweep_from_columns"
    assert gaps[0][1] == pytest.approx(9e-3)
    b = tr.breakdown(synthetic())
    assert b["device_ops"][0][0] == "%sweep_columns_call.1 = custom-call"
    assert b["device_ops"][0][1] == pytest.approx(120e-3)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def ctx(**kw):
    from chipbench.peaks import PEAKS

    base = dict(units=[], window_s=1.0, setup_s=5.0, spans=[], trace=None,
                busy_s=None, chips=1, peaks=PEAKS["TPU v5 lite"])
    base.update(kw)
    return types.SimpleNamespace(**base)


def reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py").read


def test_readers_on_a_synthetic_window():
    t = synthetic()
    units = [{"queries": 1, "ops": 197e9, "bytes": 1.0}] * 2
    c = ctx(units=units, window_s=0.2, trace=t, busy_s=tr.busy_seconds(t))
    # 2 queries of 197e9 ops: 2 ms at the peak, over 120 ms of kernel time
    assert reader("sweep_kernel_roofline")(c) == pytest.approx(100 * 2e-3 / 0.12)
    assert reader("solve_mfu")(c) == pytest.approx(100 * 2e-3 / 0.2)
    assert reader("solve_ms")(c) == pytest.approx(100.0)
    assert reader("device_idle.solve")(c) == pytest.approx(100 * (1 - 0.121 / 0.2))
    u = {"tokens": 50, "decode_steps": 10, "flops": 197e12 * 0.01,
         "latencies_s": [0.1 * k for k in range(1, 21)]}
    c = ctx(units=[u], window_s=0.5, trace=t, busy_s=0.25,
            spans=[{"name": "burst", "dur": 2000.0}, {"name": "burst", "dur": 4000.0},
                   {"name": "cycle", "dur": 9.0}])
    assert reader("tokens_per_s")(c) == pytest.approx(100.0)
    assert reader("latency_p95_ms")(c) == pytest.approx(1905.0)
    assert reader("decode_step_ms")(c) == pytest.approx(6.0)
    assert reader("serve_mfu")(c) == pytest.approx(2.0)
    assert reader("runtime_cycle_ms")(c) == pytest.approx(3.0)
    assert reader("device_idle.serve")(c) == pytest.approx(50.0)


@pytest.mark.parametrize("name", [
    "sweep_kernel_roofline", "solve_mfu", "solve_ms", "device_idle.solve",
    "tokens_per_s", "latency_p95_ms", "decode_step_ms", "serve_mfu",
    "runtime_cycle_ms", "device_idle.serve",
])
def test_readers_return_nothing_when_there_is_nothing_to_read(name):
    empty = tr.Trace(ops={}, modules={}, host=[])
    assert reader(name)(ctx(trace=empty)) is None
