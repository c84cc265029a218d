"""The program-span readers and the idle split by span, on synthetic spans
and traces; and on the CPU, the traced small cells read each of them."""

import os
import sys

# The benchmark's library, after the paths already there: this directory is
# also named "tests", and must not shadow the repository's own.
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_BENCH, os.path.join(os.path.dirname(os.path.dirname(_BENCH)), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import time
import types

import pytest

from chipbench import spans as sp
from chipbench import trace as tr
from chipbench.files import BENCH_DIR, load_module
from small_cells import serve_cell, thermal_cell

NEW_READERS = ["solve_host_ms", "decode_host_ms", "request_open_ms"]


def span(name, id, parent, dur_us):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_us, "pid": 1,
            "tid": 0, "id": id, "parent": parent}


def design_spans():
    # Two queries: minimax (price, upload, launch, readback) then sum
    # (launch, readback, assemble), each under its engine.solve.
    return [
        span("sweep.price", 2, 1, 3000.0), span("sweep.upload", 3, 1, 1000.0),
        span("sweep.launch", 4, 1, 500.0), span("sweep.readback", 5, 1, 60000.0),
        span("engine.solve", 1, None, 66000.0),
        span("sweep.launch", 7, 6, 500.0), span("sweep.readback", 8, 6, 64000.0),
        span("sweep.assemble", 9, 6, 20000.0),
        span("engine.solve", 6, None, 86000.0),
        # a readback under no engine.solve is not the query's
        span("sweep.readback", 10, None, 1e6),
    ]


def serve_spans():
    return [
        span("serve.open", 1, None, 4000.0),
        span("cycle", 2, None, 40000.0), span("burst", 3, 2, 39000.0),
        span("serve.token_sync", 5, 4, 9000.0), span("serve.prefill", 4, 3, 20000.0),
        span("serve.token_sync", 7, 6, 8000.0), span("serve.decode", 6, 3, 10000.0),
        span("serve.token_sync", 9, 8, 7000.0), span("serve.decode", 8, 3, 10000.0),
        span("serve.open", 10, None, 2000.0),
    ]


def ctx(**kw):
    base = dict(units=[], window_s=1.0, setup_s=5.0, spans=[], trace=None,
                busy_s=None, chips=1, peaks=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py").read


def test_self_times_and_children():
    s = serve_spans()
    own = sp.self_times(s)
    assert own[3] == 39000.0 - 20000.0 - 10000.0 - 10000.0
    assert own[6] == 2000.0 and own[8] == 3000.0 and own[5] == 9000.0
    assert own[2] == 1000.0
    d = design_spans()
    waits = sp.children(d, sp.named(d, "engine.solve"), "sweep.readback")
    assert sorted(e["id"] for e in waits) == [5, 8]
    assert sp.children(d, [], "sweep.readback") == []


def test_readers_on_synthetic_spans():
    c = ctx(spans=design_spans(), units=[{"queries": 1}, {"queries": 1}])
    # (66 + 86 ms of solves - 60 - 64 ms waiting on the kernel) / 2 queries
    assert reader("solve_host_ms")(c) == pytest.approx(14.0)
    c = ctx(spans=serve_spans())
    # self time of each decode: 10 - 8 and 10 - 7 ms
    assert reader("decode_host_ms")(c) == pytest.approx(2.5)
    assert reader("request_open_ms")(c) == pytest.approx(3.0)


def _without_ids(spans):
    return [{k: v for k, v in e.items() if k not in ("id", "parent")}
            for e in spans]


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_return_nothing_when_there_is_nothing_to_read(name):
    assert reader(name)(ctx(spans=None)) is None
    assert reader(name)(ctx(spans=[])) is None
    # a program whose spans carry no parent ids, nor the new spans: the
    # burst and solve spans alone, as an older program records them
    old = [e for e in _without_ids(design_spans() + serve_spans())
           if e["name"] in ("engine.solve", "burst", "cycle")]
    assert reader(name)(ctx(spans=old, units=[{"queries": 2}])) is None


def ev(name, start_ms, dur_ms):
    return tr.Event(name, start_ms * 1e6, dur_ms * 1e6)


def test_idle_by_span_takes_the_innermost_span():
    ops = {"/device:TPU:0": [ev("k", 0, 10), ev("k", 20, 10), ev("k", 50, 10)]}
    host = [
        ev("engine.solve", 5, 50),      # open over both gaps
        ev("sweep.assemble", 32, 6),    # inner: 32-38 of the 30-50 gap
        ev("$python frame", 40, 5),     # not a program span: ignored
    ]
    t = tr.Trace(ops=ops, modules={}, host=host)
    got = sp.idle_by_span(t, ["engine.solve", "sweep.assemble", "sweep.launch"])
    # gaps 10-20 and 30-50: 10 + 14 ms under engine.solve, 6 under assemble
    assert got["engine.solve"] == pytest.approx(24e-3)
    assert got["sweep.assemble"] == pytest.approx(6e-3)
    assert got["sweep.launch"] == 0.0 and got[sp.NO_SPAN] == 0.0
    assert sum(got.values()) == pytest.approx(30e-3)


def test_idle_by_span_outside_spans_and_over_devices():
    ops = {"a": [ev("k", 0, 10), ev("k", 30, 10)],
           "b": [ev("k", 0, 20), ev("k", 30, 10)]}
    host = [ev("serve.decode", 15, 10)]
    t = tr.Trace(ops=ops, modules={}, host=host)
    got = sp.idle_by_span(t, ["serve.decode"])
    # device a idles 10-30: 10 ms under the span (15-25), 10 under none;
    # device b idles 20-30: 5 ms under it, 5 under none; averaged
    assert got["serve.decode"] == pytest.approx(7.5e-3)
    assert got[sp.NO_SPAN] == pytest.approx(7.5e-3)
    empty = tr.Trace(ops={}, modules={}, host=host)
    assert sp.idle_by_span(empty, ["serve.decode"]) == {
        "serve.decode": 0.0, sp.NO_SPAN: 0.0}


@pytest.mark.parametrize("cell, options, names", [
    (thermal_cell, {"interpret": True}, ["solve_host_ms"]),
    (serve_cell, {"smoke": True}, ["decode_host_ms", "request_open_ms"]),
])
def test_traced_small_cells_read_the_span_metrics(capsys, monkeypatch, tmp_path,
                                                  cell, options, names):
    from chipbench import harness

    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    res = harness.run_cell(cell(), 2**31 + 7, 0.5, True, time.perf_counter(),
                           require_tpu=False, options=options)
    capsys.readouterr()
    for name in names:
        assert res["metrics"][name]["value"] > 0, name
