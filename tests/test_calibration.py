"""Calibration-loop differential/property tier (repro.core.calibration).

What this pins, in four layers:

* **accumulator exactness** — the Welford (count, mean, m2) statistics match
  the stdlib ``statistics`` module at tolerance on random data and are
  *bitwise* exact on identical samples (mean stays the sample, m2 stays 0.0)
  — the property the sigma=0 contract stands on;
* **round-trip properties** — ledger → MeasuredCostTable → JSON → table is
  fingerprint-stable, dump_json's deterministic (rid, cycle) row order makes
  calibration fingerprints independent of request interleaving, and
  tampered/mis-versioned files fail loudly;
* **sigma=0 bit-identity differentials** — a measured table whose samples
  match the analytical model materializes the analytical CostModel *object*
  itself, so solves through every backend (numpy / scan / pallas) are
  bit-identical to the analytical path on every smoke config;
* **uncertainty semantics** — confidence pricing (mean + z·sigma) is
  monotone: higher confidence never yields fewer bursts, never a lower
  Q_min, never a lower E_total; and a crash-schedule soak checks the
  headline guarantee — a confidence-c plan completes within budget on ≥ c
  of perturbed-draw replays.

The property checks run under stdlib-``random`` seeded drivers always, and
additionally under hypothesis when it is installed (the test_partition.py
idiom — the seed container has no hypothesis, CI may).
"""

import collections
import json
import math
import os
import random
import statistics
import tempfile

import numpy as np
import pytest

from helpers_random import random_cost_model, random_q_grid, random_task_graph

from repro.api import (
    CalibrationError,
    MeasuredCostTable,
    PartitionSpec,
    SpecError,
    clear_measured_defaults,
    install_measured_default,
    solve,
    use_measured,
)
from repro.configs import SMOKE_CONFIGS
from repro.core import lower_config, q_min
from repro.core.calibration import (
    CALIBRATION_VERSION,
    CATEGORIES as CAL_CATEGORIES,
    KernelStats,
    measured_default,
    z_score,
)
from repro.core.cost import CostModel, LinearTransfer, cost_scalars
from repro.core.layer_profile import analytical_cost_model, default_cost_model
from repro.core.partition import Infeasible
from repro.obs.ledger import CATEGORIES, EnergyLedger

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

ARCHS = sorted(SMOKE_CONFIGS)


def _ledger_matching(cm: CostModel, n_requests: int = 3, n_cycles: int = 4,
                     commit: float = 0.0) -> EnergyLedger:
    """A ledger whose restore rows are exactly the model's e_startup — what a
    run that matched the analytical model would have captured."""
    led = EnergyLedger()
    for rid in range(n_requests):
        for c in range(n_cycles):
            led.charge(rid, c, restore=float(cm.e_startup), compute=0.25,
                       commit=commit, vt=float(rid + c))
    return led


def _stats_table(base: CostModel, *, restore=(), commit=(), compute=(),
                 kind: str = "time") -> MeasuredCostTable:
    mt = MeasuredCostTable(base, kind)
    for x in restore:
        mt.add("restore", x)
    for x in commit:
        mt.add("commit", x)
    for x in compute:
        mt.add("compute", x)
    return mt


# ---------------------------------------------------------------------------
# z-score and Welford accumulator
# ---------------------------------------------------------------------------


def test_z_score_median_and_none_are_exact_zero():
    assert z_score(None) == 0.0
    assert z_score(0.5) == 0.0  # exactly, no inv_cdf rounding residue


def test_z_score_matches_normal_quantiles():
    assert z_score(0.975) == pytest.approx(1.959964, abs=1e-5)
    assert z_score(0.841344746) == pytest.approx(1.0, abs=1e-6)
    assert z_score(0.99) == pytest.approx(2.326348, abs=1e-5)
    # symmetric: sub-median confidence discounts
    assert z_score(0.3) == pytest.approx(-z_score(0.7), abs=1e-12)


@pytest.mark.parametrize("bad", [0.0, 1.0, -0.25, 2.0, float("nan")])
def test_z_score_rejects_out_of_range(bad):
    with pytest.raises(CalibrationError):
        z_score(bad)


def test_kernel_stats_matches_statistics_module():
    rng = random.Random(7)
    for _ in range(20):
        xs = [rng.uniform(1e-6, 10.0) for _ in range(rng.randint(1, 60))]
        s = KernelStats()
        for x in xs:
            s.add(x)
        assert s.count == len(xs)
        assert s.mean == pytest.approx(statistics.fmean(xs), rel=1e-12)
        assert s.variance == pytest.approx(statistics.pvariance(xs),
                                           rel=1e-9, abs=1e-18)
        assert s.std == pytest.approx(math.sqrt(s.variance))


def test_kernel_stats_identical_samples_bit_exact():
    """Welford keeps the mean bitwise equal to x over identical samples
    (delta == 0.0 on every update) and m2 exactly 0.0 — a naive sum/n would
    round. This is the foundation of the sigma=0 bit-identity contract."""
    for x in (0.1, 1e-5, 3.7, 9e-6, 2.0 ** -37):
        s = KernelStats()
        for _ in range(137):
            s.add(x)
        assert s.mean == x  # bitwise, not approx
        assert s.m2 == 0.0
        assert s.std == 0.0
        assert s.cv == 0.0


def test_kernel_stats_rejects_non_finite():
    s = KernelStats()
    for bad in (float("nan"), float("inf"), float("-inf")):
        with pytest.raises(CalibrationError):
            s.add(bad)


def test_calibration_categories_agree_with_ledger():
    assert tuple(CAL_CATEGORIES) == tuple(CATEGORIES)


# ---------------------------------------------------------------------------
# Ingestion and round-trip properties
# ---------------------------------------------------------------------------


def test_from_ledger_counts_and_means():
    cm = analytical_cost_model("time")
    led = _ledger_matching(cm, n_requests=2, n_cycles=3)
    led.overhead(0, 1, 0.5)
    mt = MeasuredCostTable.from_ledger(led, base=cm, kind="time")
    assert mt.stats["restore"].count == 6
    assert mt.stats["restore"].mean == float(cm.e_startup)
    assert mt.stats["compute"].count == 6
    assert mt.stats["commit"].count == 0  # zero commits produce no rows
    assert mt.stats["replay"].count == 1
    assert mt.stats["replay"].mean == 0.5
    assert mt.n_samples == 13


def test_ingest_rejects_unknown_category_and_malformed_rows():
    mt = MeasuredCostTable(analytical_cost_model("time"))
    with pytest.raises(CalibrationError):
        mt.add("warp-drive", 1.0)
    with pytest.raises(CalibrationError):
        mt.ingest_rows([{"energy": 1.0}])  # no category
    with pytest.raises(CalibrationError):
        mt.ingest_rows([3.14])  # not a row at all


def test_base_must_be_cost_model():
    with pytest.raises(CalibrationError):
        MeasuredCostTable("tpu-host-offload")


def test_table_json_round_trip_bitwise(tmp_path):
    rng = random.Random(11)
    mt = _stats_table(
        random_cost_model(rng),
        restore=[rng.uniform(0.01, 1.0) for _ in range(9)],
        commit=[rng.uniform(0.001, 0.1) for _ in range(5)],
        compute=[rng.uniform(0.1, 2.0) for _ in range(7)],
    )
    path = tmp_path / "calib.json"
    mt.to_json(str(path), source="unit-test")
    back = MeasuredCostTable.from_json(str(path))
    assert back.fingerprint() == mt.fingerprint()
    for cat in CAL_CATEGORIES:
        assert back.stats[cat].count == mt.stats[cat].count
        assert back.stats[cat].mean == mt.stats[cat].mean  # bitwise
        assert back.stats[cat].m2 == mt.stats[cat].m2
    assert back.meta["source"] == "unit-test"
    assert np.array_equal(cost_scalars(back.base), cost_scalars(mt.base))


def test_ledger_dump_round_trip_preserves_fingerprint(tmp_path):
    cm = analytical_cost_model("time")
    led = _ledger_matching(cm, commit=1e-6)
    direct = MeasuredCostTable.from_ledger(led, base=cm)
    path = tmp_path / "ledger.json"
    led.dump_json(str(path), kind="time", arch="unit")
    via_file = MeasuredCostTable.from_ledger_json(str(path), base=cm)
    assert via_file.kind == "time"
    assert via_file.fingerprint() == direct.fingerprint()
    assert via_file.meta["arch"] == "unit"


def test_from_json_rejects_version_mismatch(tmp_path):
    mt = MeasuredCostTable(analytical_cost_model("time"))
    payload = mt.to_payload()
    payload["version"] = CALIBRATION_VERSION + 1
    path = tmp_path / "calib.json"
    path.write_text(json.dumps(payload))
    with pytest.raises(CalibrationError, match="version"):
        MeasuredCostTable.from_json(str(path))


def test_from_json_rejects_tampered_stats(tmp_path):
    mt = _stats_table(analytical_cost_model("time"), restore=[1e-5, 2e-5])
    payload = mt.to_payload()
    payload["stats"]["restore"]["mean"] = 5e-5  # edited by hand
    path = tmp_path / "calib.json"
    path.write_text(json.dumps(payload))
    with pytest.raises(CalibrationError, match="fingerprint"):
        MeasuredCostTable.from_json(str(path))


def _fingerprint_free_payload(**corrupt) -> dict:
    """A to_payload dict with the fingerprint key *deleted* and the restore
    stats entry overridden — the load path skips the fingerprint check when
    the key is absent, so these corruptions used to sail straight through
    into confidence pricing."""
    mt = _stats_table(analytical_cost_model("time"), restore=[1e-5, 2e-5])
    payload = mt.to_payload()
    del payload["fingerprint"]
    payload["stats"]["restore"].update(corrupt)
    return payload


def test_fingerprint_free_payload_loads_clean():
    """Sanity: deleting the fingerprint alone is legitimate (hand-authored
    tables) and must keep loading."""
    back = MeasuredCostTable.from_payload(_fingerprint_free_payload())
    assert back.stats["restore"].count == 2


@pytest.mark.parametrize(
    "corrupt, match",
    [
        ({"mean": float("nan")}, "non-finite"),
        ({"mean": float("inf")}, "non-finite"),
        ({"m2": float("-inf")}, "non-finite"),
        ({"count": -3}, "negative count"),
        ({"m2": -1e-9}, "negative m2"),
        ({"count": 0}, "zero samples"),  # mean/m2 stay non-zero
        ({"mean": "fast"}, "malformed"),
        ({"count": None}, "malformed"),
    ],
)
def test_load_rejects_invalid_stats_without_fingerprint(corrupt, match):
    """Welford invariants are enforced on load even when the fingerprint
    check cannot fire: NaN/inf moments, negative counts, negative variance
    accumulators, and zero-sample entries with non-zero moments all raise
    the typed CalibrationError."""
    with pytest.raises(CalibrationError, match=match):
        MeasuredCostTable.from_payload(_fingerprint_free_payload(**corrupt))


def test_load_rejects_missing_stats_field():
    payload = _fingerprint_free_payload()
    del payload["stats"]["restore"]["m2"]
    with pytest.raises(CalibrationError, match="malformed"):
        MeasuredCostTable.from_payload(payload)


def test_from_json_rejects_nan_mean_on_disk(tmp_path):
    """End-to-end through the file loader: json serializes NaN as the
    non-standard ``NaN`` literal, python's json reads it back, and from_json
    must still refuse it."""
    payload = _fingerprint_free_payload(mean=float("nan"))
    path = tmp_path / "nan.json"
    path.write_text(json.dumps(payload))
    with pytest.raises(CalibrationError, match="non-finite"):
        MeasuredCostTable.from_json(str(path))


def test_from_ledger_json_rejects_non_ledger(tmp_path):
    path = tmp_path / "not_a_ledger.json"
    path.write_text(json.dumps({"rows": []}))
    with pytest.raises(CalibrationError):
        MeasuredCostTable.from_ledger_json(str(path))


def _interleaved_ledgers(rng: random.Random):
    """Two ledgers with the same per-(rid, cycle) charges appended in
    different interleavings (the traffic harness's continuation batching
    commits many requests' cycles in schedule-dependent order)."""
    charges = []
    for rid in range(rng.randint(2, 4)):
        for cycle in range(rng.randint(1, 5)):
            charges.append((rid, cycle, rng.uniform(0.01, 1.0),
                            rng.uniform(0.0, 2.0), rng.uniform(0.0, 0.5)))
    a, b = EnergyLedger(), EnergyLedger()
    for rid, cycle, restore, compute, commit in charges:
        a.charge(rid, cycle, restore=restore, compute=compute, commit=commit)
    rng.shuffle(charges)
    for rid, cycle, restore, compute, commit in charges:
        b.charge(rid, cycle, restore=restore, compute=compute, commit=commit)
    return a, b


def test_dump_json_interleaving_invariant_fingerprint(tmp_path):
    """Satellite: deterministic (rid, cycle) export order ⇒ the calibration
    fingerprint built from a dumped ledger is a function of *what was
    charged*, not of the schedule that charged it."""
    cm = analytical_cost_model("time")
    for seed in range(6):
        a, b = _interleaved_ledgers(random.Random(seed))
        pa, pb = tmp_path / f"a{seed}.json", tmp_path / f"b{seed}.json"
        a.dump_json(str(pa))
        b.dump_json(str(pb))
        ra = json.loads(pa.read_text())["entries"]
        rb = json.loads(pb.read_text())["entries"]
        assert ra == rb
        fa = MeasuredCostTable.from_ledger_json(str(pa), base=cm).fingerprint()
        fb = MeasuredCostTable.from_ledger_json(str(pb), base=cm).fingerprint()
        assert fa == fb


def test_fingerprint_sensitive_to_stats_kind_and_base():
    cm = analytical_cost_model("time")
    base_fp = _stats_table(cm, restore=[1e-5]).fingerprint()
    assert _stats_table(cm, restore=[2e-5]).fingerprint() != base_fp
    assert _stats_table(cm, restore=[1e-5],
                        kind="memory").fingerprint() != base_fp
    other = CostModel(e_startup=2e-5, read=cm.read, write=cm.write,
                      name=cm.name)
    assert _stats_table(other, restore=[1e-5]).fingerprint() != base_fp


# ---------------------------------------------------------------------------
# CostModel materialization
# ---------------------------------------------------------------------------


def test_clean_round_trip_returns_base_object():
    """The bit-identity lever: samples matching the model ⇒ cost_model()
    IS the base CostModel (same object — same name, same fingerprint, same
    solves), at any confidence (zero variance prices nothing)."""
    cm = analytical_cost_model("time")
    mt = MeasuredCostTable.from_ledger(_ledger_matching(cm), base=cm)
    assert mt.cost_model() is cm
    assert mt.cost_model(0.5) is cm
    assert mt.cost_model(0.999) is cm


def test_no_samples_returns_base_object():
    cm = analytical_cost_model("time")
    assert MeasuredCostTable(cm).cost_model() is cm
    assert MeasuredCostTable(cm).cost_model(0.9) is cm


def test_drifted_mean_reprices_e_startup():
    cm = analytical_cost_model("time")
    mt = _stats_table(cm, restore=[2e-5, 3e-5])
    priced = mt.cost_model()
    assert priced is not cm
    assert priced.e_startup == mt.stats["restore"].mean  # bitwise
    assert priced.name == cm.name + "+measured"
    # transfers untouched without commit samples
    assert priced.read.c0 == cm.read.c0 and priced.write.c1 == cm.write.c1


def test_confidence_prices_mean_plus_z_sigma():
    cm = analytical_cost_model("time")
    mt = _stats_table(cm, restore=[1e-5, 2e-5, 3e-5, 4e-5])
    r = mt.stats["restore"]
    priced = mt.cost_model(0.975)
    assert priced.e_startup == r.mean + z_score(0.975) * r.std  # bitwise
    assert "@0.975" in priced.name
    # sub-median confidence discounts below the mean
    assert mt.cost_model(0.3).e_startup < r.mean


def test_commit_noise_scales_transfer_curves():
    cm = analytical_cost_model("time")
    mt = _stats_table(cm, commit=[1e-6, 2e-6, 3e-6])
    s = mt.stats["commit"]
    scale = 1.0 + z_score(0.9) * (s.std / s.mean)
    priced = mt.cost_model(0.9)
    assert priced.read.c0 == cm.read.c0 * scale  # bitwise
    assert priced.read.c1 == cm.read.c1 * scale
    assert priced.write.c0 == cm.write.c0 * scale
    assert priced.e_startup == cm.e_startup  # no restore samples
    # at the mean (z=0) commit noise prices nothing
    assert mt.transfer_scale() == 1.0
    assert mt.cost_model() is cm


def test_e_startup_and_scale_monotone_in_confidence():
    rng = random.Random(3)
    mt = _stats_table(
        analytical_cost_model("time"),
        restore=[rng.uniform(1e-5, 3e-5) for _ in range(30)],
        commit=[rng.uniform(1e-6, 4e-6) for _ in range(30)],
    )
    confidences = [0.5, 0.6, 0.75, 0.9, 0.975, 0.999]
    e = [mt.e_startup(c) for c in confidences]
    s = [mt.transfer_scale(c) for c in confidences]
    assert e == sorted(e) and len(set(e)) == len(e)
    assert s == sorted(s) and len(set(s)) == len(s)


# ---------------------------------------------------------------------------
# PartitionSpec / Engine threading
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bad", [0.0, 1.0, -0.5, 2.0, float("nan"), "high"])
def test_spec_confidence_validation(bad):
    with pytest.raises(SpecError, match="confidence"):
        PartitionSpec(config="qwen3-4b", shapes=((2, 16),), smoke=True,
                      confidence=bad)


def test_spec_rejects_non_cost_cost():
    with pytest.raises(SpecError, match="cost="):
        PartitionSpec(config="qwen3-4b", shapes=((2, 16),), smoke=True,
                      cost=object())


def test_confidence_with_plain_cost_model_is_typed_error():
    rng = random.Random(0)
    g, cm = random_task_graph(rng), random_cost_model(rng)
    with pytest.raises(SpecError, match="confidence"):
        solve(PartitionSpec(graph=g, cost=cm, confidence=0.9,
                            backend="numpy"))


def test_solve_accepts_measured_table_as_cost():
    rng = random.Random(1)
    g, cm = random_task_graph(rng), random_cost_model(rng)
    mt = _stats_table(cm)  # no samples → base pass-through
    a = solve(PartitionSpec(graph=g, cost=cm, backend="numpy")).partition()
    b = solve(PartitionSpec(graph=g, cost=mt, backend="numpy")).partition()
    assert a.e_total == b.e_total and a.bounds == b.bounds


def test_measured_default_registry_and_scoping():
    cm = analytical_cost_model("time")
    drifted = _stats_table(cm, restore=[5e-5, 7e-5])
    assert measured_default("time") is None
    try:
        install_measured_default(drifted)
        assert measured_default("time") is drifted
        assert default_cost_model("time").name == cm.name + "+measured"
    finally:
        clear_measured_defaults("time")
    assert measured_default("time") is None
    assert default_cost_model("time").name == cm.name
    # scoped variant restores the previous registration, even nested
    with use_measured(drifted):
        clean = MeasuredCostTable(cm)
        with use_measured(clean):
            assert measured_default("time") is clean
        assert measured_default("time") is drifted
    assert measured_default("time") is None
    with pytest.raises(CalibrationError):
        install_measured_default(cm)  # not a table


def test_installed_default_drives_config_specs():
    """An installed calibration is what config-lowered specs price with —
    including confidence=, with no explicit cost= needed."""
    cm = analytical_cost_model("time")
    drifted = _stats_table(cm, restore=[3e-5, 5e-5])
    spec = PartitionSpec(config="qwen3-4b", shapes=((2, 16),), smoke=True,
                         backend="scan")
    base_e = float(solve(spec).sweep.e_total[0])
    with use_measured(drifted):
        drift_e = float(solve(spec).sweep.e_total[0])
        conf = dataclasses_replace_confidence(spec, 0.975)
        conf_e = float(solve(conf).sweep.e_total[0])
    assert drift_e > base_e           # measured mean drifted upward
    assert conf_e > drift_e           # z·sigma on top of the mean
    assert float(solve(spec).sweep.e_total[0]) == base_e  # registry restored


def dataclasses_replace_confidence(spec, c):
    import dataclasses

    return dataclasses.replace(spec, confidence=c)


# ---------------------------------------------------------------------------
# sigma=0 bit-identity differentials: every smoke config × every backend
# ---------------------------------------------------------------------------


def _assert_sweeps_equal(a, b, ctx=""):
    assert a.n_tasks == b.n_tasks, ctx
    for field in ("dp", "parent", "e_total", "feasible", "starts"):
        assert getattr(a, field).tobytes() == getattr(b, field).tobytes(), \
            (ctx, field)


def _clean_table_for(cm: CostModel) -> MeasuredCostTable:
    mt = MeasuredCostTable.from_ledger(_ledger_matching(cm), base=cm)
    assert mt.cost_model() is cm  # precondition for the differentials
    return mt


@pytest.mark.parametrize("arch", ARCHS)
def test_sigma0_bit_identity_numpy(arch):
    cm = analytical_cost_model("time")
    mt = _clean_table_for(cm)
    g = lower_config(SMOKE_CONFIGS[arch], batch=2, seq=16, kind="time")
    for q in (q_min(g, cm), None):
        a = solve(PartitionSpec(graph=g, cost=cm, q_max=q,
                                backend="numpy")).partition()
        b = solve(PartitionSpec(graph=g, cost=mt, q_max=q, confidence=0.5,
                                backend="numpy")).partition()
        assert a.e_total == b.e_total and a.bounds == b.bounds, (arch, q)
    # infeasible Q raises identically through both cost sources
    for cost in (cm, mt):
        with pytest.raises(Infeasible):
            solve(PartitionSpec(graph=g, cost=cost, q_max=1e-12,
                                backend="numpy")).partition()


@pytest.mark.parametrize("arch", ARCHS)
def test_sigma0_bit_identity_scan(arch):
    cm = analytical_cost_model("time")
    mt = _clean_table_for(cm)
    g = lower_config(SMOKE_CONFIGS[arch], batch=2, seq=16, kind="time")
    qs = (1e-12, q_min(g, cm), None)
    a = solve(PartitionSpec(graph=g, cost=cm, q_grid=qs, backend="scan"))
    b = solve(PartitionSpec(graph=g, cost=mt, q_grid=qs, backend="scan"))
    _assert_sweeps_equal(a.sweep, b.sweep, arch)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_sigma0_bit_identity_pallas(arch):
    cm = analytical_cost_model("time")
    mt = _clean_table_for(cm)
    g = lower_config(SMOKE_CONFIGS[arch], batch=2, seq=16, kind="time")
    qs = (q_min(g, cm), None)
    a = solve(PartitionSpec(graph=g, cost=cm, q_grid=qs, backend="pallas"))
    b = solve(PartitionSpec(graph=g, cost=mt, q_grid=qs, backend="pallas"))
    _assert_sweeps_equal(a.sweep, b.sweep, arch)


def test_sigma0_bit_identity_pallas_smoke():
    """Fast-tier representative of the slow pallas matrix above."""
    cm = analytical_cost_model("time")
    mt = _clean_table_for(cm)
    g = lower_config(SMOKE_CONFIGS["qwen3-4b"], batch=2, seq=16, kind="time")
    qs = (q_min(g, cm), None)
    a = solve(PartitionSpec(graph=g, cost=cm, q_grid=qs, backend="pallas"))
    b = solve(PartitionSpec(graph=g, cost=mt, q_grid=qs, backend="pallas"))
    _assert_sweeps_equal(a.sweep, b.sweep)


def test_measured_scalars_differential_all_backends():
    """The non-trivial direction: a *drifted* table at sigma=0 must solve
    exactly like a hand-built CostModel carrying the measured scalars — the
    measured path adds no computation of its own, it only swaps scalars."""
    cm = analytical_cost_model("time")
    mt = _stats_table(cm, restore=[1.5e-5, 2.5e-5], commit=[1e-6, 1e-6])
    manual = CostModel(
        e_startup=mt.stats["restore"].mean,
        read=cm.read, write=cm.write,  # zero commit variance → scale 1.0
        name=cm.name + "+measured",
    )
    assert np.array_equal(cost_scalars(mt.cost_model()), cost_scalars(manual))
    g = lower_config(SMOKE_CONFIGS["qwen3-4b"], batch=2, seq=16, kind="time")
    qs = (q_min(g, manual), None)
    for backend in ("scan", "pallas"):
        a = solve(PartitionSpec(graph=g, cost=manual, q_grid=qs,
                                backend=backend))
        b = solve(PartitionSpec(graph=g, cost=mt, q_grid=qs,
                                backend=backend))
        _assert_sweeps_equal(a.sweep, b.sweep, backend)
    pa = solve(PartitionSpec(graph=g, cost=manual, q_max=qs[0],
                             backend="numpy")).partition()
    pb = solve(PartitionSpec(graph=g, cost=mt, q_max=qs[0],
                             backend="numpy")).partition()
    assert pa.e_total == pb.e_total and pa.bounds == pb.bounds


# ---------------------------------------------------------------------------
# Monotonicity: higher confidence ⇒ never fewer bursts, never lower Q_min
# ---------------------------------------------------------------------------

CONFIDENCES = (0.5, 0.7, 0.9, 0.99)


def _noisy_table(rng: random.Random, cm: CostModel) -> MeasuredCostTable:
    mu = max(float(cm.e_startup), 0.05)
    return _stats_table(
        cm,
        restore=[rng.gauss(mu, 0.3 * mu) for _ in range(40)],
        commit=[abs(rng.gauss(0.05, 0.02)) for _ in range(40)],
    )


def check_confidence_monotonicity(rng: random.Random) -> None:
    g, cm = random_task_graph(rng, min_tasks=2), random_cost_model(rng)
    mt = _noisy_table(rng, cm)
    # Q_min is non-decreasing in confidence
    qmins = [
        solve(PartitionSpec(graph=g, cost=mt, confidence=c,
                            objective="minimax", backend="numpy")).q_min()
        for c in CONFIDENCES
    ]
    for lo, hi in zip(qmins, qmins[1:]):
        assert hi >= lo
    # at a fixed Q: burst count and E_total non-decreasing, feasibility
    # monotone (feasible at high confidence ⇒ feasible at lower)
    for q in random_q_grid(rng, qmins[0], qmins[-1] * 1.5):
        bursts, totals = [], []
        for c in CONFIDENCES:
            try:
                p = solve(PartitionSpec(graph=g, cost=mt, confidence=c,
                                        q_max=q, backend="numpy")).partition()
                bursts.append(p.n_bursts)
                totals.append(p.e_total)
            except Infeasible:
                bursts.append(math.inf)
                totals.append(math.inf)
        for lo, hi in zip(bursts, bursts[1:]):
            assert hi >= lo, (q, bursts)
        for lo, hi in zip(totals, totals[1:]):
            assert hi >= lo, (q, totals)


def test_confidence_monotonicity_seeded():
    for seed in range(12):
        check_confidence_monotonicity(random.Random(seed))


# ---------------------------------------------------------------------------
# Crash-schedule soak: confidence-c plans survive ≥ c of perturbed replays
# ---------------------------------------------------------------------------


def _soak_completion_rate(confidence, seed: int = 0, n_replays: int = 500,
                          mu: float = 0.2, sigma: float = 0.05) -> float:
    """Plan a chain at `confidence` under its own priced Q_min, then replay
    with the activation draw perturbed (one gaussian draw per replay — the
    device's actual E_s is a fixed property measured with noise). A replay
    completes when every planned cycle fits the budget it was admitted
    under."""
    rng = random.Random(seed)
    from repro.core import GraphBuilder

    b = GraphBuilder()
    prev = None
    for t in range(8):
        name = f"p{t}"
        b.packet(name, 64, keep=t == 7)
        b.task(f"t{t}", reads=(prev,) if prev else (), writes=(name,),
               cost=rng.uniform(0.05, 0.4))
        prev = name
    g = b.build()
    base = CostModel(e_startup=mu, read=LinearTransfer(0.0, 0.0),
                     write=LinearTransfer(0.0, 0.0), name="soak")
    mt = _stats_table(base,
                      restore=[rng.gauss(mu, sigma) for _ in range(400)])
    q = solve(PartitionSpec(graph=g, cost=mt, confidence=confidence,
                            objective="minimax", backend="numpy")).q_min()
    plan = solve(PartitionSpec(graph=g, cost=mt, confidence=confidence,
                               q_max=q, backend="numpy")).partition()
    # non-startup residual per cycle (task energy; transfers priced at 0)
    residuals = [b.e_read + b.e_write + b.e_task for b in plan.bursts]
    completions = 0
    for _ in range(n_replays):
        draw = rng.gauss(mt.stats["restore"].mean, mt.stats["restore"].std)
        if all(r + draw <= q for r in residuals):
            completions += 1
    return completions / n_replays


@pytest.mark.parametrize("confidence", [0.7, 0.9])
def test_confidence_soak_completion_rate(confidence):
    rate = _soak_completion_rate(confidence)
    # binomial noise at n=500 stays well inside 0.04
    assert rate >= confidence - 0.04, (confidence, rate)


def test_soak_higher_confidence_completes_more():
    low = _soak_completion_rate(0.55, seed=3)
    high = _soak_completion_rate(0.99, seed=3)
    assert high >= low
    assert high >= 0.95


# ---------------------------------------------------------------------------
# Plan-table drift probe (staleness vs a refreshed profile)
# ---------------------------------------------------------------------------


def _probe(table, cfg, cm, **kwargs):
    from repro.core.plan_table import probe_plan_table

    return probe_plan_table(table, cfg, cost=cm, **kwargs)


@pytest.fixture(scope="module")
def probe_case(smoke_plan_table):
    cfg, cm, qs, table = smoke_plan_table("qwen3-4b")
    return cfg, cm, table


def test_probe_accepts_clean_measured(probe_case):
    cfg, cm, table = probe_case
    mt = _clean_table_for(cm)
    n = _probe(table, cfg, cm, k=None, measured=mt)
    assert n == table.n_buckets * table.n_q


def test_probe_accepts_drift_within_tolerance(probe_case):
    cfg, cm, table = probe_case
    mt = _stats_table(cm, restore=[float(cm.e_startup) * 1.001] * 4)
    assert _probe(table, cfg, cm, k=None, measured=mt, drift_tol=0.05) > 0


def test_probe_rejects_drifted_measured(probe_case):
    from repro.core.plan_table import StaleTableError

    cfg, cm, table = probe_case
    mt = _stats_table(cm, restore=[float(cm.e_startup) * 50.0] * 4)
    with pytest.raises(StaleTableError, match="drifted"):
        _probe(table, cfg, cm, k=None, measured=mt)


def test_probe_drift_tolerance_is_tunable(probe_case):
    from repro.core.plan_table import PlanTableError, StaleTableError

    cfg, cm, table = probe_case
    mt = _stats_table(cm, restore=[float(cm.e_startup) * 1.001] * 4)
    with pytest.raises(StaleTableError, match="drifted"):
        _probe(table, cfg, cm, k=None, measured=mt, drift_tol=1e-9)
    with pytest.raises(PlanTableError, match="drift_tol"):
        _probe(table, cfg, cm, measured=mt, drift_tol=-0.1)


def test_probe_rejects_kind_mismatch(probe_case):
    from repro.core.plan_table import StaleTableError

    cfg, cm, table = probe_case
    mt = MeasuredCostTable(cm, kind="memory")
    with pytest.raises(StaleTableError, match="kind"):
        _probe(table, cfg, cm, measured=mt)


def test_probe_exact_checks_still_run_with_measured(probe_case):
    """The measured drift check rides on top of — never replaces — the
    bitwise fingerprint check against the analytical model."""
    from repro.core.plan_table import StaleTableError

    cfg, cm, table = probe_case
    mt = _clean_table_for(cm)
    other = CostModel(e_startup=float(cm.e_startup) * 2, read=cm.read,
                      write=cm.write, name=cm.name)
    with pytest.raises(StaleTableError, match="fingerprint"):
        _probe(table, cfg, other, measured=mt)


# ---------------------------------------------------------------------------
# CLI round trips
# ---------------------------------------------------------------------------


def test_dse_calibrate_cli_round_trip(tmp_path, probe_case, capsys):
    from repro.launch.dse import main as dse_main

    cfg, cm, table = probe_case
    table_path = tmp_path / "plan.npz"
    table.save(str(table_path))
    ledger_path = tmp_path / "ledger.json"
    _ledger_matching(cm).dump_json(str(ledger_path), kind="time")
    rc = dse_main(["--arch", "qwen3-4b", "--calibrate", str(ledger_path),
                   "--out", str(table_path), "--probe", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "accepted" in out
    calib_path = tmp_path / "plan.npz.calib.json"
    assert calib_path.exists()
    back = MeasuredCostTable.from_json(str(calib_path))
    assert back.cost_model().name == cm.name  # clean loop


def test_dse_calibrate_cli_rejects_drifted_ledger(tmp_path, probe_case,
                                                  capsys):
    from repro.launch.dse import main as dse_main

    cfg, cm, table = probe_case
    table_path = tmp_path / "plan.npz"
    table.save(str(table_path))
    drifted = EnergyLedger()
    for c in range(3):
        drifted.charge(0, c, restore=float(cm.e_startup) * 50.0, compute=0.1)
    ledger_path = tmp_path / "drifted.json"
    drifted.dump_json(str(ledger_path), kind="time")
    rc = dse_main(["--arch", "qwen3-4b", "--calibrate", str(ledger_path),
                   "--out", str(table_path), "--probe", "2"])
    assert rc == 1
    assert "STALE" in capsys.readouterr().err


@pytest.mark.slow
def test_traffic_replan_cli_round_trip(tmp_path, capsys):
    """One CLI round trip: traffic emits a calibration ledger, replans from
    it in-process (byte-identical on the clean loop), and the emitted
    ledger feeds back through `dse --calibrate` against the emitted table."""
    from repro.launch.dse import main as dse_main
    from repro.launch.traffic import main as traffic_main

    ledger_path = tmp_path / "ledger.json"
    table_path = tmp_path / "table.npz"
    rc = traffic_main([
        "--arch", "qwen3-4b", "--build", "--n", "2", "--shapes", "2x8x6",
        "--seed", "0", "--ledger-out", str(ledger_path),
        "--table-out", str(table_path),
        "--replan", "--expect-replan-identical",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "identical to the original" in out
    payload = json.loads(ledger_path.read_text())
    rows = payload["entries"]
    assert rows == sorted(rows, key=lambda r: (r["rid"], r["cycle"]))
    rc = dse_main(["--arch", "qwen3-4b", "--calibrate", str(ledger_path),
                   "--out", str(table_path), "--probe", "2"])
    assert rc == 0


# ---------------------------------------------------------------------------
# Hypothesis tier (runs when hypothesis is installed; see module docstring)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    energies = st.floats(min_value=1e-9, max_value=1e3, allow_nan=False,
                         allow_infinity=False)

    class TestCalibrationHypothesis:
        @given(xs=st.lists(energies, min_size=1, max_size=80))
        @settings(max_examples=60, deadline=None)
        def test_welford_matches_statistics(self, xs):
            s = KernelStats()
            for x in xs:
                s.add(x)
            assert s.mean == pytest.approx(statistics.fmean(xs), rel=1e-9)
            assert s.variance == pytest.approx(
                statistics.pvariance(xs), rel=1e-6, abs=1e-15)

        @given(x=energies, n=st.integers(min_value=1, max_value=300))
        @settings(max_examples=60, deadline=None)
        def test_identical_samples_stay_bit_exact(self, x, n):
            s = KernelStats()
            for _ in range(n):
                s.add(x)
            assert s.mean == x and s.m2 == 0.0

        @given(
            rows=st.lists(
                st.tuples(st.integers(0, 5), st.integers(0, 5),
                          st.sampled_from(CATEGORIES), energies),
                min_size=1, max_size=60),
            seed=st.integers(0, 2 ** 16),
        )
        @settings(max_examples=40, deadline=None)
        def test_dump_interleaving_invariance(self, rows, seed):
            cm = analytical_cost_model("time")
            # Interleave the cells at random; each (rid, cycle) keeps its
            # own rows in charge order, which sorted_rows() preserves.
            cells = collections.defaultdict(collections.deque)
            for r in rows:
                cells[r[:2]].append(r)
            keys = [r[:2] for r in rows]
            random.Random(seed).shuffle(keys)
            shuffled = [cells[k].popleft() for k in keys]
            a, b = EnergyLedger(), EnergyLedger()
            for ledger, data in ((a, rows), (b, shuffled)):
                for rid, cycle, cat, e in data:
                    if cat == "replay":
                        ledger.overhead(rid, cycle, e)
                    else:
                        ledger.charge(rid, cycle, **{cat: e})
            # A temp dir per example: hypothesis runs the body many times
            # under one function-scoped fixture, so the dir is made here.
            with tempfile.TemporaryDirectory() as d:
                pa, pb = os.path.join(d, "a.json"), os.path.join(d, "b.json")
                a.dump_json(pa)
                b.dump_json(pb)
                fa = MeasuredCostTable.from_ledger_json(pa, base=cm)
                fb = MeasuredCostTable.from_ledger_json(pb, base=cm)
            assert fa.fingerprint() == fb.fingerprint()

        @given(
            restore=st.lists(energies, min_size=1, max_size=40),
            commit=st.lists(energies, min_size=0, max_size=40),
            c1=st.floats(min_value=0.5, max_value=0.999),
            c2=st.floats(min_value=0.5, max_value=0.999),
        )
        @settings(max_examples=60, deadline=None)
        def test_pricing_monotone_in_confidence(self, restore, commit,
                                                c1, c2):
            cm = analytical_cost_model("time")
            mt = _stats_table(cm, restore=restore, commit=commit)
            lo, hi = min(c1, c2), max(c1, c2)
            assert mt.e_startup(hi) >= mt.e_startup(lo)
            assert mt.transfer_scale(hi) >= mt.transfer_scale(lo)

        @given(restore=st.lists(energies, min_size=1, max_size=30))
        @settings(max_examples=40, deadline=None)
        def test_json_round_trip_property(self, restore):
            mt = _stats_table(analytical_cost_model("time"), restore=restore)
            with tempfile.TemporaryDirectory() as d:
                path = os.path.join(d, "calib.json")
                mt.to_json(path)
                loaded = MeasuredCostTable.from_json(path)
            assert loaded.fingerprint() == mt.fingerprint()

else:

    def test_calibration_fuzz_skipped_without_hypothesis():
        pytest.importorskip("hypothesis")


# ---------------------------------------------------------------------------
# Multi-host aggregation: MeasuredCostTable.merge (ISSUE 10 satellite)
# ---------------------------------------------------------------------------


def test_kernel_stats_merge_matches_sequential_ingest():
    """Chan's combine == sequential Welford over the concatenation: counts
    exact, moments to ~ulp (summation order is part of Welford rounding)."""
    rng = random.Random(21)
    for _ in range(30):
        xs = [rng.uniform(1e-6, 5.0) for _ in range(rng.randint(0, 40))]
        ys = [rng.uniform(1e-6, 5.0) for _ in range(rng.randint(0, 40))]
        a, b, ref = KernelStats(), KernelStats(), KernelStats()
        for x in xs:
            a.add(x)
        for y in ys:
            b.add(y)
        for v in xs + ys:
            ref.add(v)
        m = a.merge(b)
        assert m.count == ref.count
        if ref.count:
            assert m.mean == pytest.approx(ref.mean, rel=1e-12)
            assert m.m2 == pytest.approx(ref.m2, rel=1e-9, abs=1e-15)


def test_kernel_stats_merge_empty_side_is_bitwise():
    s = KernelStats()
    for x in (0.3, 1.7, 0.9):
        s.add(x)
    for merged in (s.merge(KernelStats()), KernelStats().merge(s)):
        assert (merged.count, merged.mean, merged.m2) == (s.count, s.mean, s.m2)


def test_kernel_stats_merge_identical_means_stay_bitwise():
    # delta == 0.0 → the shared mean survives bitwise and m2 adds exactly
    x = 2.0 ** -17 * 3.0
    a, b = KernelStats(), KernelStats()
    for _ in range(11):
        a.add(x)
    for _ in range(5):
        b.add(x)
    m = a.merge(b)
    assert m.mean == x and m.m2 == 0.0 and m.count == 16


def test_kernel_stats_merge_rejects_non_stats():
    with pytest.raises(CalibrationError):
        KernelStats().merge("nope")


def _rows_from(rng, n):
    cats = ("restore", "compute", "commit", "replay")
    return [
        {"category": rng.choice(cats), "energy": rng.uniform(1e-6, 2.0)}
        for _ in range(n)
    ]


def test_measured_table_merge_differential_vs_concatenated_ingest():
    """merge(per-device tables) == one table ingesting the concatenated rows
    (counts exact, moments ~ulp) — the multi-host aggregation contract."""
    rng = random.Random(33)
    base = analytical_cost_model("time")
    chunks = [_rows_from(rng, rng.randint(0, 25)) for _ in range(4)]
    parts = []
    for d, chunk in enumerate(chunks):
        t = MeasuredCostTable(base, "time", meta={"device": f"dev{d}"})
        t.ingest_rows(chunk)
        parts.append(t)
    merged = MeasuredCostTable.merge(*parts)
    ref = MeasuredCostTable(base, "time")
    ref.ingest_rows([r for chunk in chunks for r in chunk])
    assert merged.n_samples == ref.n_samples
    for cat in CATEGORIES:
        ms, rs = merged.stats[cat], ref.stats[cat]
        assert ms.count == rs.count
        if rs.count:
            assert ms.mean == pytest.approx(rs.mean, rel=1e-12)
            assert ms.m2 == pytest.approx(rs.m2, rel=1e-9, abs=1e-15)
    # per-device provenance rides in meta → to_payload
    prov = merged.meta["merged_from"]
    assert [p["meta"].get("device") for p in prov] == [
        "dev0", "dev1", "dev2", "dev3"
    ]
    assert [p["fingerprint"] for p in prov] == [t.fingerprint() for t in parts]
    assert sum(p["n_samples"] for p in prov) == merged.n_samples
    assert merged.to_payload()["meta"]["merged_from"] == prov


def test_measured_table_merge_single_table_is_bitwise():
    rng = random.Random(8)
    base = analytical_cost_model("time")
    t = MeasuredCostTable(base, "time")
    t.ingest_rows(_rows_from(rng, 17))
    m = MeasuredCostTable.merge(t)
    assert m.fingerprint() == t.fingerprint()  # stats bitwise-identical


def test_measured_table_merge_identical_fleet_keeps_fingerprint():
    # devices that measured identical draws merge to identical statistics
    base = analytical_cost_model("time")
    rows = [{"category": "restore", "energy": 3e-5}] * 9
    a = MeasuredCostTable(base, "time")
    a.ingest_rows(rows)
    b = MeasuredCostTable(base, "time")
    b.ingest_rows(rows + rows)
    fleet = MeasuredCostTable.merge(a, a)
    assert fleet.fingerprint() == b.fingerprint()


def test_measured_table_merge_typed_errors():
    base = analytical_cost_model("time")
    other = analytical_cost_model("memory")
    t1 = MeasuredCostTable(base, "time")
    with pytest.raises(CalibrationError, match="at least one"):
        MeasuredCostTable.merge()
    with pytest.raises(CalibrationError, match="MeasuredCostTable"):
        MeasuredCostTable.merge(t1, "nope")
    with pytest.raises(CalibrationError, match="different graph kinds"):
        MeasuredCostTable.merge(t1, MeasuredCostTable(base, "memory"))
    with pytest.raises(CalibrationError, match="different base models"):
        MeasuredCostTable.merge(t1, MeasuredCostTable(other, "time"))
