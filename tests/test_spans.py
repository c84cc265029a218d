"""Program spans on the profiler's clock, and the spans the design query
and a planned request emit.

* a span opened while a ``jax.profiler`` session runs lands on the host
  plane's ``python`` line under its own name, with its own duration;
* a minimax + sum query on the Pallas backend (interpret mode) prices and
  uploads its slots once, then reuses the upload, and each solve's kernel
  steps are children of its ``engine.solve``;
* a planned request at smoke width emits one ``serve.decode`` per decode
  step and one ``runtime.commit`` per committed cycle, each inside its
  ``burst`` and carrying the request's ``rid``.
"""

import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

from repro.obs.trace import PID_SOLVER, TRACER


@pytest.fixture
def tracer():
    # jax is imported above: enabling binds the profiler annotation
    TRACER.configure(enabled=True)
    try:
        yield TRACER
    finally:
        TRACER.reset()


def by_name(events, name):
    return [e for e in events if e.get("ph") == "X" and e["name"] == name]


def test_span_lands_on_the_profilers_host_line(tmp_path, tracer):
    from jax.profiler import ProfileData

    x = jnp.ones(3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("probe.outer"):
            time.sleep(0.02)
            with tracer.span("probe.inner"):
                (x + 1).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                for e in line.events:
                    if e.name.startswith("probe."):
                        found[e.name] = (e.start_ns, e.duration_ns)
    spans = {e["name"]: e for e in tracer.events()}
    assert set(found) == {"probe.outer", "probe.inner"}
    for name, (_, dur_ns) in found.items():
        assert abs(dur_ns * 1e-3 - spans[name]["dur"]) < 1000.0, name
    (o0, od), (i0, idur) = found["probe.outer"], found["probe.inner"]
    assert o0 <= i0 and i0 + idur <= o0 + od


def _tiny_graph():
    from repro.core import CostModel, GraphBuilder, LinearTransfer

    b = GraphBuilder()
    b.packet("x", 8, external=True)
    b.packet("y", 16)
    b.packet("z", 8, keep=True)
    b.task("t0", reads=("x",), writes=("y",), cost=1.0)
    b.task("t1", reads=("y",), writes=("z",), cost=2.0)
    cm = CostModel(e_startup=0.1, read=LinearTransfer(0.01, 0.001),
                   write=LinearTransfer(0.01, 0.001), name="tiny")
    return b.build(), cm


def test_design_query_spans_and_upload_reuse(tracer):
    from repro.api import PartitionSpec, solve
    from repro.kernels.partition_sweep.ops import UPLOAD_COUNT

    g, cm = _tiny_graph()
    before = dict(UPLOAD_COUNT)
    q_min = solve(PartitionSpec(graph=g, cost=cm, objective="minimax",
                                backend="pallas", interpret=True)).q_min()
    solve(PartitionSpec(graph=g, cost=cm, q_grid=(q_min * 1.01, None),
                        backend="pallas", interpret=True))
    ev = tracer.events()
    solves = by_name(ev, "engine.solve")
    assert [s["args"]["objective"] for s in solves] == ["minimax", "sum"]
    assert all(s["parent"] is None and s["pid"] == PID_SOLVER for s in solves)
    assert len(by_name(ev, "sweep.readback")) == 2
    # one miss (the minimax solve prices and uploads), then one hit
    assert UPLOAD_COUNT["miss"] - before["miss"] == 1
    assert UPLOAD_COUNT["hit"] - before["hit"] == 1
    mm, sm = (s["id"] for s in solves)
    kids = {name: [e["parent"] for e in by_name(ev, name)]
            for name in ("sweep.price", "sweep.upload", "sweep.launch",
                         "sweep.readback", "sweep.assemble")}
    assert kids == {"sweep.price": [mm], "sweep.upload": [mm],
                    "sweep.launch": [mm, sm], "sweep.readback": [mm, sm],
                    "sweep.assemble": [sm]}
    assert not by_name(ev, "engine.dispatch")
    # two tasks, one i-tile: a grid program and a tile body per column
    for e in by_name(ev, "sweep.launch"):
        assert e["args"]["grid_programs"] == 2
        assert e["args"]["tile_bodies"] == 2


@pytest.mark.parametrize("n,tile", [(20, 8), (24, 8), (7, 8)])
def test_sweep_launch_counts_live_tile_bodies(tracer, n, tile):
    """``sweep.launch`` carries the kernel's grid programs (one a column)
    and the i-tile bodies they run: column j visits its ⌈j/B⌉ live tiles."""
    from repro.core import GraphBuilder
    from repro.kernels.partition_sweep.ops import sweep_columns

    _, cm = _tiny_graph()
    b = GraphBuilder()
    for t in range(n):
        b.task(f"t{t}", cost=1.0)
    sweep_columns(b.build().to_csr_arrays(), cm, [None], tile=tile,
                  interpret=True)
    (launch,) = by_name(tracer.events(), "sweep.launch")
    B = min(tile, max(8, n))
    assert launch["args"]["grid_programs"] == n
    assert launch["args"]["tile_bodies"] == sum(-(-j // B)
                                                for j in range(1, n + 1))


def test_planned_request_spans(tracer):
    from repro.launch.planner import build_table_for_arch
    from repro.launch.serve import PlannedExecutor
    from repro.launch.traffic import Request, TrafficHarness

    arch, batch, prompt, gen, rid = "qwen1.5-0.5b", 1, 8, 5, 7
    table = build_table_for_arch(arch, [(batch, prompt + gen)], n_q=8)
    plan = table.lookup(batch, prompt + gen, None)
    budget = 2.2 * plan.e_total + table.e_startup  # two steps a cycle
    harness = TrafficHarness(PlannedExecutor(arch, table, smoke=True),
                             cycle_budget=budget)
    tracer.reset()
    tracer.configure(enabled=True)
    rep = harness.run([Request(rid=rid, batch=batch, prompt_len=prompt,
                               gen=gen)])
    assert rep.completed == 1 and rep.cycles_run == 3
    ev = tracer.events()
    assert len(by_name(ev, "serve.open")) == 1
    assert len(by_name(ev, "serve.prefill")) == 1
    assert len(by_name(ev, "serve.decode")) == gen - 1
    assert len(by_name(ev, "serve.token_sync")) == gen
    assert len(by_name(ev, "runtime.commit")) == rep.cycles_run
    assert len(by_name(ev, "runtime.restore")) == rep.cycles_run
    assert not [e for e in ev if e["name"] == "nvm_commit"]
    spans = {e["id"]: e for e in ev if e.get("ph") == "X"}
    for e in by_name(ev, "burst"):
        assert spans[e["parent"]]["name"] == "cycle"
    for name in ("serve.prefill", "serve.decode", "runtime.restore",
                 "runtime.commit"):
        for e in by_name(ev, name):
            assert spans[e["parent"]]["name"] == "burst", name
            assert e["args"]["rid"] == rid, name
    for e in by_name(ev, "serve.token_sync"):
        assert spans[e["parent"]]["name"] in ("serve.prefill", "serve.decode")
        assert e["args"]["rid"] == rid
