"""Unified telemetry (repro.obs): metrics registry semantics, Chrome
trace_event export schema, energy-ledger conservation, and the zero-division
guards on rate fields.

Fast tier throughout — the trace/ledger integration tests drive the real
TrafficHarness over the synthetic-chain executor from tests/test_traffic.py
(no jax). The real-model `--trace-out` CLI path runs in the slow tier of
tests/test_traffic.py and in CI's traffic smoke.
"""

import json
import os

import pytest

from test_traffic import (
    E_STARTUP,
    E_TOTAL,
    GEN,
    FakeTable,
    SyntheticExecutor,
    _req,
)


# -- metrics registry --------------------------------------------------------


def test_counter_labels_and_snapshot_diff():
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("solves")
    c.inc()
    c.inc(2, backend="numpy")
    c.inc(1, backend="scan")
    before = reg.snapshot()
    assert before["solves"] == {"": 1, "backend=numpy": 2, "backend=scan": 1}
    c.inc(5, backend="numpy")
    assert reg.diff(before) == {"solves": {"backend=numpy": 5}}
    reg.reset()
    assert reg.snapshot()["solves"] == {}
    assert c.value(backend="numpy") == 0


def test_gauge_and_histogram():
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    g = reg.gauge("charge")
    g.set(1.5)
    g.set(0.5)
    assert g.value() == 0.5
    h = reg.histogram("latency_ms")
    for v in (2.0, 4.0, 6.0):
        h.observe(v)
    snap = reg.snapshot()["latency_ms"]
    assert snap == {"count": 3, "sum": 12.0, "min": 2.0, "max": 6.0, "mean": 4.0}
    reg.reset()
    assert reg.snapshot()["latency_ms"]["count"] == 0


def test_registry_reregistration_returns_same_instrument():
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    a = reg.counter("x")
    b = reg.counter("x")
    assert a is b
    d1 = reg.counter_dict("y", ("k",))
    d2 = reg.counter_dict("y", ("k",))
    assert d1 is d2


def test_counter_dict_is_plain_dict_to_consumers():
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    d = reg.counter_dict("trace", ("prefill", "decode"))
    assert d == {"prefill": 0, "decode": 0}
    d["prefill"] += 3
    assert dict(d) == {"prefill": 3, "decode": 0}
    d["adhoc"] = 7  # ad-hoc keys are allowed but dropped on reset
    reg.reset()
    assert d == {"prefill": 0, "decode": 0}


def test_reset_all_covers_the_legacy_counter_dicts():
    """The historical reset trio is now one reset_all(); the old names stay
    as thin aliases and plain-dict equality (pinned by the serving tests)
    still holds."""
    from repro.core import runtime
    from repro.obs.metrics import METRICS, reset_all

    runtime.COMMIT_STATS["commits"] += 5
    runtime.COMMIT_STATS["replays"] += 2
    assert METRICS.get("runtime.commit_stats") is runtime.COMMIT_STATS
    reset_all()
    assert runtime.COMMIT_STATS == {"commits": 0, "replays": 0}
    # the alias keeps working
    runtime.COMMIT_STATS["commits"] += 1
    runtime.reset_commit_stats()
    assert runtime.COMMIT_STATS == {"commits": 0, "replays": 0}


def test_metrics_dump_json_roundtrip(tmp_path):
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("n").inc(4)
    path = tmp_path / "metrics.json"
    reg.dump_json(str(path), tool="test")
    payload = json.loads(path.read_text())
    assert payload["tool"] == "test"
    assert payload["metrics"]["n"] == {"": 4}


# -- span tracer -------------------------------------------------------------


def _fresh_tracer():
    from repro.obs.trace import Tracer

    t = Tracer()
    t.configure(enabled=True)
    return t


def test_tracer_disabled_is_noop():
    from repro.obs.trace import Tracer

    t = Tracer()
    assert not t.enabled
    with t.span("work", answer=42):
        pass
    t.instant("tick")
    t.counter("charge", {"charge": 1.0})
    assert t.events() == []
    # the disabled span is one shared object — no per-call allocation
    assert t.span("a") is t.span("b")


def test_span_schema_and_nesting():
    t = _fresh_tracer()
    with t.span("outer", cat="test", pid=7, tid=3, depth=0):
        with t.span("inner", cat="test", pid=7, tid=3, depth=1):
            pass
    t.instant("blip", pid=7, tid=3)
    events = t.events()
    assert [e["name"] for e in events] == ["inner", "outer", "blip"]
    for e in events:
        assert set(e) >= {"name", "ph", "ts", "pid", "tid"}
        assert e["ts"] >= 0
    inner, outer, blip = events
    assert inner["ph"] == outer["ph"] == "X"
    assert blip["ph"] == "i"
    # monotonic nesting: inner is contained in outer on the same track
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6


def test_span_ids_parents_and_self_time():
    t = _fresh_tracer()
    with t.span("outer"):
        with t.span("first"):
            with t.span("leaf"):
                pass
        with t.span("second"):
            pass
    with t.span("next"):
        pass
    ev = {e["name"]: e for e in t.events()}
    assert len({e["id"] for e in ev.values()}) == 5
    assert ev["outer"]["parent"] is None and ev["next"]["parent"] is None
    assert ev["first"]["parent"] == ev["second"]["parent"] == ev["outer"]["id"]
    assert ev["leaf"]["parent"] == ev["first"]["id"]
    own = ev["outer"]["dur"] - ev["first"]["dur"] - ev["second"]["dur"]
    assert 0 <= own <= ev["outer"]["dur"]
    # a clear while a span is open drops it from the new capture's parents
    with t.span("open"):
        t.configure(enabled=True)
        with t.span("after"):
            pass
    (after,) = [e for e in t.events() if e["name"] == "after"]
    assert after["parent"] is None and after["id"] == 1


def test_span_inherits_the_request_id_of_its_parent():
    t = _fresh_tracer()
    with t.span("cycle", rid=3):
        with t.span("burst"):
            with t.span("serve.decode"):
                pass
        with t.span("other", rid=4):
            pass
    with t.span("alone"):
        pass
    rid = {e["name"]: e.get("args", {}).get("rid") for e in t.events()}
    assert rid == {"cycle": 3, "burst": 3, "serve.decode": 3, "other": 4,
                   "alone": None}


def test_obs_imports_and_traces_without_jax():
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from repro.obs import TRACER\n"
        "TRACER.configure(enabled=True)\n"
        "with TRACER.span('work'):\n"
        "    pass\n"
        "assert [e['name'] for e in TRACER.events()] == ['work']\n"
        "assert TRACER._annotation is None\n"
        "assert 'jax' not in sys.modules, 'repro.obs imported jax'\n"
    )
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "src")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env=dict(os.environ, PYTHONPATH=src))


def test_span_records_exception_and_reraises():
    t = _fresh_tracer()
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("x")
    (ev,) = t.events()
    assert ev["args"]["error"] == "ValueError"


def test_track_metadata_and_write(tmp_path):
    t = _fresh_tracer()
    t.set_process(1, "traffic")
    t.set_thread(1, 100, "request 0")
    t.set_thread(1, 100, "request 0")  # idempotent
    with t.span("cycle", tid=100, vt=2.5):
        pass
    path = tmp_path / "trace.json"
    n = t.write(str(path))
    payload = json.loads(path.read_text())
    assert payload["displayTimeUnit"] == "ms"
    events = payload["traceEvents"]
    assert len(events) == n == 3
    meta = [e for e in events if e["ph"] == "M"]
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    (cycle,) = [e for e in events if e["ph"] == "X"]
    assert cycle["args"]["vt"] == 2.5


# -- energy ledger -----------------------------------------------------------


def test_ledger_charge_overhead_and_conservation():
    from repro.obs.ledger import EnergyLedger, LedgerImbalance

    led = EnergyLedger()
    led.charge(0, 0, restore=0.1, compute=0.75, commit=0.0, vt=1.0)
    led.charge(0, 1, restore=0.1, compute=0.25)
    led.overhead(0, 1, 0.35)
    cat = led.by_category()
    assert cat["restore"] == pytest.approx(0.2)
    assert cat["compute"] == pytest.approx(1.0)
    assert cat["replay"] == pytest.approx(0.35)
    assert led.charged_total() == pytest.approx(1.2)
    assert led.overhead_total() == pytest.approx(0.35)
    assert led.overhead_fraction() == pytest.approx(0.35 / 1.2)
    assert led.by_request(0)["compute"] == pytest.approx(1.0)
    led.check_conservation(1.2)  # replay excluded by design
    assert not led.conserves(1.0)
    with pytest.raises(LedgerImbalance):
        led.check_conservation(1.0)


def test_empty_ledger_guards():
    from repro.obs.ledger import EnergyLedger

    led = EnergyLedger()
    assert led.overhead_fraction() == 0.0
    assert led.conserves(0.0)
    assert led.summary()["entries"] == 0


def test_ledger_dump_json(tmp_path):
    from repro.obs.ledger import EnergyLedger

    led = EnergyLedger()
    led.charge(3, 0, restore=0.1, compute=0.2, vt=4.0)
    path = tmp_path / "ledger.json"
    led.dump_json(str(path), run="test")
    payload = json.loads(path.read_text())
    assert payload["run"] == "test"
    assert payload["summary"]["charged_total"] == pytest.approx(0.3)
    assert payload["entries"][0] == {
        "rid": 3, "cycle": 0, "category": "restore", "energy": 0.1, "vt": 4.0,
    }


def test_ledger_dump_json_rows_sorted_by_rid_cycle(tmp_path):
    """Regression: dump_json exports rows in deterministic (rid, cycle)
    order regardless of charge order, so calibration fingerprints built
    from a dumped ledger don't depend on the traffic schedule."""
    from repro.obs.ledger import EnergyLedger

    led = EnergyLedger()
    # charge in a schedule-ish interleaved order: rid 2 first, rid 0 last
    led.charge(2, 0, restore=0.1, compute=0.2)
    led.charge(1, 1, compute=0.4)
    led.overhead(1, 0, 0.05)
    led.charge(1, 0, restore=0.1)
    led.charge(0, 0, commit=0.3)
    path = tmp_path / "ledger.json"
    led.dump_json(str(path))
    rows = json.loads(path.read_text())["entries"]
    keys = [(r["rid"], r["cycle"]) for r in rows]
    assert keys == sorted(keys)
    assert keys[0] == (0, 0) and keys[-1] == (2, 0)
    # stable within one (rid, cycle): replay was appended before the charge
    rid1c0 = [r["category"] for r in rows if (r["rid"], r["cycle"]) == (1, 0)]
    assert rid1c0 == ["replay", "restore"]
    # in-memory to_rows() keeps raw append order — only the export sorts
    assert [(r["rid"], r["cycle"]) for r in led.to_rows()][0] == (2, 0)


def test_ledger_dump_json_interleaving_invariant(tmp_path):
    """Two schedules charging the same (rid, cycle, category, energy) set
    in different orders dump byte-identical entry lists."""
    import random

    from repro.obs.ledger import EnergyLedger

    rng = random.Random(17)
    charges = [(rid, cyc, rng.uniform(0.01, 1.0), rng.uniform(0.0, 0.5))
               for rid in range(3) for cyc in range(4)]
    a, b = EnergyLedger(), EnergyLedger()
    for rid, cyc, compute, commit in charges:
        a.charge(rid, cyc, restore=0.1, compute=compute, commit=commit)
    rng.shuffle(charges)
    for rid, cyc, compute, commit in charges:
        b.charge(rid, cyc, restore=0.1, compute=compute, commit=commit)
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    a.dump_json(str(pa))
    b.dump_json(str(pb))
    assert (json.loads(pa.read_text())["entries"]
            == json.loads(pb.read_text())["entries"])


# -- ledger properties under random request/cycle/crash schedules ------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _random_ledger_schedule(rng):
    """Build a ledger from a random request/cycle/crash schedule, returning
    (ledger, expected per-category totals, expected per-rid totals,
    expected overhead total)."""
    from repro.obs.ledger import CHARGED_CATEGORIES, EnergyLedger

    led = EnergyLedger()
    by_cat = {c: 0.0 for c in CHARGED_CATEGORIES}
    by_rid = {}
    overhead = 0.0
    events = []
    for rid in range(rng.randint(1, 5)):
        for cycle in range(rng.randint(1, 6)):
            for _ in range(rng.randint(0, 2)):  # crashed attempts first
                events.append(("crash", rid, cycle, rng.uniform(0.0, 0.5)))
            events.append(("commit", rid, cycle, rng.uniform(0.0, 0.2),
                           rng.uniform(0.0, 1.0), rng.uniform(0.0, 0.1)))
    rng.shuffle(events)  # schedule interleaving is arbitrary
    for ev in events:
        if ev[0] == "crash":
            _, rid, cycle, e = ev
            led.overhead(rid, cycle, e)
            overhead += e
        else:
            _, rid, cycle, restore, compute, commit = ev
            led.charge(rid, cycle, restore=restore, compute=compute,
                       commit=commit)
            req = by_rid.setdefault(rid, {c: 0.0 for c in CHARGED_CATEGORIES})
            for cat, e in (("restore", restore), ("compute", compute),
                           ("commit", commit)):
                by_cat[cat] += e
                req[cat] += e
    return led, by_cat, by_rid, overhead


def check_ledger_schedule_invariants(rng):
    from repro.obs.ledger import CHARGED_CATEGORIES, LedgerImbalance

    led, by_cat, by_rid, overhead = _random_ledger_schedule(rng)
    charged = sum(by_cat.values())
    # conservation: charged categories sum to the total; replay is booked
    # outside the admission reservation by design
    assert led.charged_total() == pytest.approx(charged, rel=1e-12)
    assert led.overhead_total() == pytest.approx(overhead, rel=1e-12)
    assert led.conserves(charged)
    if charged > 0:
        with pytest.raises(LedgerImbalance):
            led.check_conservation(charged * 1.5 + 1.0)
    # by_category / by_request sum consistency
    cat = led.by_category()
    for c in CHARGED_CATEGORIES:
        assert cat[c] == pytest.approx(by_cat[c], rel=1e-12, abs=1e-15)
        per_req = sum(led.by_request(rid)[c] for rid in by_rid)
        assert per_req == pytest.approx(cat[c], rel=1e-12, abs=1e-15)
    assert cat["replay"] == pytest.approx(overhead, rel=1e-12, abs=1e-15)
    for rid, want in by_rid.items():
        got = led.by_request(rid)
        for c in CHARGED_CATEGORIES:
            assert got[c] == pytest.approx(want[c], rel=1e-12, abs=1e-15)


def test_ledger_random_schedule_invariants_seeded():
    import random

    for seed in range(25):
        check_ledger_schedule_invariants(random.Random(seed))


def test_ledger_crash_heavy_schedule_overhead_fraction():
    """All-crash schedules keep charged_total at 0 and the overhead
    fraction guard still divides safely."""
    from repro.obs.ledger import EnergyLedger

    led = EnergyLedger()
    for attempt in range(4):
        led.overhead(0, 0, 0.25)
    assert led.charged_total() == 0.0
    assert led.overhead_total() == pytest.approx(1.0)
    assert led.overhead_fraction() == 0.0  # guard: no charged base
    assert led.conserves(0.0)


if HAVE_HYPOTHESIS:

    class TestLedgerHypothesis:
        @given(seed=st.integers(min_value=0, max_value=2**32 - 1))
        @settings(max_examples=50, deadline=None)
        def test_random_schedule_invariants(self, seed):
            import random

            check_ledger_schedule_invariants(random.Random(seed))

else:

    def test_ledger_fuzz_skipped_without_hypothesis():
        pytest.importorskip("hypothesis")


# -- CLI log stream rebind ---------------------------------------------------


def test_cli_output_rebinds_over_closed_stream():
    """Regression: a second CLI run must survive the previous run's stream
    having been closed under it (pytest capsys teardown) — setStream
    flushes the old stream, which raises on a closed file."""
    import io

    from repro.obs.log import disable_cli_output, enable_cli_output

    name = "repro.test_rebind"
    try:
        first = io.StringIO()
        enable_cli_output(name, tag="t", stream=first)
        first.close()
        second = io.StringIO()
        h = enable_cli_output(name, tag="t", stream=second)  # must not raise
        assert h.stream is second
        import logging

        logging.getLogger(name).info("alive")
        assert second.getvalue() == "[t] alive\n"
    finally:
        disable_cli_output(name)


# -- zero-division guards (satellite regression tests) -----------------------


def test_hit_rate_guard_zero_lookups():
    from repro.launch.planner import ServePlanner

    planner = ServePlanner(FakeTable([(1, 8)]))
    assert planner.hit_rate == 0.0


def test_traffic_report_rate_guards_zero_duration():
    from repro.launch.traffic import TrafficReport

    report = TrafficReport()
    assert report.requests_per_s == 0.0
    assert report.latency_percentiles_ms() == {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    assert report.retraces == 0


def test_empty_run_reports_zero_rates():
    from repro.launch.planner import ServePlanner
    from repro.launch.traffic import TrafficHarness

    planner = ServePlanner(FakeTable([(1, 8)]))
    report = TrafficHarness(SyntheticExecutor(planner)).run([])
    assert report.arrived == report.completed == 0
    assert report.hit_rate == 0.0
    assert report.requests_per_s == 0.0
    assert report.ledger_conserved is True
    assert report.ledger_conservation_error == 0.0


# -- harness integration: trace export + ledger conservation -----------------


def _validate_chrome_trace(payload):
    """Schema checks for Perfetto-loadable trace_event JSON: required keys
    per phase, and monotonic (properly nested) spans per (pid, tid) track."""
    assert set(payload) >= {"traceEvents"}
    spans_by_track = {}
    for e in payload["traceEvents"]:
        assert set(e) >= {"name", "ph", "pid", "tid"}
        if e["ph"] == "M":
            continue
        assert "ts" in e and e["ts"] >= 0
        if e["ph"] == "X":
            assert "dur" in e and e["dur"] >= 0
            spans_by_track.setdefault((e["pid"], e["tid"]), []).append(e)
    # events are appended at close time, so within a track each span must
    # either contain or be disjoint from every earlier-closing span
    for track, spans in spans_by_track.items():
        for i, a in enumerate(spans):
            for b in spans[i + 1:]:
                a0, a1 = a["ts"], a["ts"] + a["dur"]
                b0, b1 = b["ts"], b["ts"] + b["dur"]
                nested = b0 <= a0 + 1e-6 and a1 <= b1 + 1e-6
                disjoint = a1 <= b0 + 1e-6 or b1 <= a0 + 1e-6
                assert nested or disjoint, (track, a["name"], b["name"])


def _traced_run(requests, **harness_kw):
    from repro.launch.planner import ServePlanner
    from repro.launch.traffic import TrafficHarness
    from repro.obs.trace import TRACER

    planner = ServePlanner(FakeTable([(1, 8), (2, 8)]))
    harness = TrafficHarness(SyntheticExecutor(planner), **harness_kw)
    TRACER.configure(enabled=True)
    try:
        report = harness.run(requests)
        payload = TRACER.chrome_trace()
    finally:
        TRACER.reset()
    return report, payload


def test_traced_run_exports_per_request_tracks():
    from repro.launch.traffic import HarvestModel
    from repro.obs.trace import PID_TRAFFIC, request_tid

    # at Q=0.4 each request splits into 3 one-step cycles paying E_s each:
    # 3 × (0.1 + 0.25) = 1.05 energy units; capacity 1.2 holds one request
    # at a time and the slow trickle (0.1/t) forces the second arrival to
    # defer until the pool refills
    report, payload = _traced_run(
        [_req(0), _req(1, t=0.5)],
        harvest=HarvestModel(capacity=1.2, rate=0.1),
        cycle_budget=0.4,
    )
    assert report.completed == 2
    _validate_chrome_trace(payload)
    events = payload["traceEvents"]
    # one named track per request, plus scheduler/harvest tracks
    thread_names = {
        (e["pid"], e["tid"]): e["args"]["name"]
        for e in events if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert thread_names[(PID_TRAFFIC, request_tid(0))] == "request 0"
    assert thread_names[(PID_TRAFFIC, request_tid(1))] == "request 1"
    assert "scheduler" in thread_names.values()
    assert "harvest" in thread_names.values()
    # request 0's track carries its lifecycle instants and cycle spans
    r0 = [e for e in events if e.get("tid") == request_tid(0)
          and e["ph"] in ("i", "X")]
    kinds = [e["name"] for e in r0]
    assert kinds[0] == "arrive"
    assert "admit" in kinds and "complete" in kinds
    cycles = [e for e in r0 if e["name"] == "cycle"]
    assert len(cycles) == 3  # gen=3 at Q=0.4 → 3 cycles
    assert [c["args"]["cycle"] for c in cycles] == [0, 1, 2]
    assert all("vt" in c["args"] for c in cycles)
    # pool too small for both at once → the deferred request shows it
    assert "defer" in [e["name"] for e in events
                       if e.get("tid") == request_tid(1)]
    # harvest track carries counter samples of the pool charge
    assert any(e["ph"] == "C" and e["name"] == "harvest_charge"
               for e in events)
    # burst runtime spans landed on their own process
    assert any(e["ph"] == "X" and e["name"] == "burst" for e in events)


def test_ledger_conservation_on_synthetic_traffic():
    from repro.launch.traffic import HarvestModel

    e_req = 3 * (E_STARTUP + E_TOTAL)  # 3 one-step cycles at Q=0.4
    report, _ = _traced_run(
        [_req(i, t=0.3 * i) for i in range(4)],
        harvest=HarvestModel(capacity=2 * e_req, rate=0.5),
        cycle_budget=0.4,
    )
    assert report.completed == 4
    assert report.ledger_conserved is True
    assert report.energy_spent == pytest.approx(4 * e_req)
    cat = report.energy_ledger
    # 4 requests × 3 cycles, each cycle pays E_s once
    assert cat["restore"] == pytest.approx(4 * 3 * E_STARTUP)
    assert cat["compute"] == pytest.approx(4 * GEN * E_TOTAL)
    assert cat["commit"] == 0.0  # synthetic cost model prices transfers at 0
    assert cat["replay"] == 0.0
    assert (cat["restore"] + cat["compute"]
            == pytest.approx(report.energy_spent))


def test_crash_replay_attributed_as_overhead():
    """A mid-run PowerFailure books the lost attempt as replay overhead:
    conservation still holds against the pool (the replayed energy was never
    reserved), the trace shows the power_failure instant, and the report's
    overhead fraction is the paper's per-run activation-overhead figure."""
    from repro.core import PowerFailure
    from repro.launch.traffic import HarvestModel
    from repro.obs.trace import request_tid

    class CrashOnce:
        fired = False

        def __call__(self, b, phase):
            if not self.fired and b == 1 and phase == "executed":
                CrashOnce.fired = True
                raise PowerFailure(f"injected at burst {b}")

    report, payload = _traced_run(
        [_req(0)],
        harvest=HarvestModel(capacity=2 * 3 * (E_STARTUP + E_TOTAL), rate=1.0),
        cycle_budget=0.4,
        crash_hook_factory=lambda r: CrashOnce(),
    )
    assert CrashOnce.fired
    assert report.completed == 1 and report.power_failures == 1
    _validate_chrome_trace(payload)
    cat = report.energy_ledger
    # the crashed cycle-1 attempt costs E_s + one step, booked as replay
    e_req = 3 * (E_STARTUP + E_TOTAL)  # 3 one-step cycles at Q=0.4
    assert cat["replay"] == pytest.approx(E_STARTUP + E_TOTAL)
    assert report.ledger_conserved is True
    assert report.energy_spent == pytest.approx(e_req)
    assert report.ledger_overhead_fraction == pytest.approx(
        (E_STARTUP + E_TOTAL) / e_req)
    names = [e["name"] for e in payload["traceEvents"]
             if e.get("tid") == request_tid(0)]
    assert "power_failure" in names
    # ledger rows pin the replayed cycle index
    replays = [e for e in report.ledger.entries if e.category == "replay"]
    assert [(e.rid, e.cycle) for e in replays] == [(0, 1)]


def test_engine_solve_emits_spans():
    from repro.api import PartitionSpec, solve
    from repro.core import CostModel, GraphBuilder, LinearTransfer
    from repro.obs.trace import PID_SOLVER, TRACER

    b = GraphBuilder()
    b.packet("x", 8, external=True)
    b.packet("y", 8, keep=True)
    b.task("t0", reads=("x",), writes=("y",), cost=1.0)
    g = b.build()
    cm = CostModel(e_startup=0.1, read=LinearTransfer(0.0, 0.0),
                   write=LinearTransfer(0.0, 0.0), name="test")
    TRACER.configure(enabled=True)
    try:
        solve(PartitionSpec(graph=g, cost=cm, q_max=2.0, backend="numpy"))
        events = TRACER.events()
        solve(PartitionSpec(graph=g, cost=cm, q_max=2.0, backend="pallas",
                            interpret=True))
        kernel = TRACER.events()[len(events):]
    finally:
        TRACER.reset()
    solves = [e for e in events if e["name"] == "engine.solve"]
    assert len(solves) == 1
    assert solves[0]["pid"] == PID_SOLVER
    assert solves[0]["args"]["backend"] == "numpy"
    # the numpy backend launches no kernel; the pallas solve's kernel steps
    # are children of its own engine.solve
    assert not [e for e in events if e["name"].startswith("sweep.")]
    (pallas,) = [e for e in kernel if e["name"] == "engine.solve"]
    steps = [e for e in kernel if e["name"].startswith("sweep.")]
    assert {e["name"] for e in steps} >= {
        "sweep.launch", "sweep.readback", "sweep.assemble"}
    assert all(e["parent"] == pallas["id"] for e in steps)
