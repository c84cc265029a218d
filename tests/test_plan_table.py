"""Differential suite for the plan-table subsystem (serving-path integration).

* every smoke config × shape bucket: table lookups return segment bounds
  bit-identical to direct single-graph façade solves (``.sweep`` over the
  Q grid, ``.partition()`` at one Q);
* save → load → lookup round-trips exactly (bounds, e_total, cycle energies);
* stale-version and unknown-bucket lookups raise cleanly;
* the fingerprint-keyed build cache short-circuits the solve;
* tabulated cut points drive the offload/remat planners to the same plans a
  direct solve produces (no re-solve on the consuming side);
* request-cycle grouping (the online half of energy-bounded serving) respects
  the shared budget tolerance.

The smoke bucket set and Q-grid derivation live in conftest.py
(``PLAN_BUCKETS`` / ``plan_grid`` / ``smoke_plan_table``), shared with
tests/test_serve_plan.py and the sharded-DSE tier in tests/test_dse_shard.py.
"""

import numpy as np
import pytest

from conftest import PLAN_BUCKETS

from repro.api import PartitionSpec, solve
from repro.configs import SMOKE_CONFIGS
from repro.core import (
    Infeasible,
    PlanTable,
    PlanTableError,
    StaleTableError,
    UnknownBucketError,
    build_plan_table,
    config_fingerprint,
    lower_config,
)
from repro.core import plan_table as pt_mod
from repro.core import partition_jax
from repro.core.offload import plan_offload
from repro.core.remat_policy import plan_remat
from repro.launch.planner import ServePlanner, as_planner, request_cycles

BUCKETS = PLAN_BUCKETS


@pytest.mark.parametrize("arch", sorted(SMOKE_CONFIGS))
def test_lookup_bitidentical_to_direct_solve(arch, smoke_plan_table):
    cfg, cm, qs, table = smoke_plan_table(arch)
    for (b, s) in BUCKETS:
        g = lower_config(cfg, b, s, kind="time")
        direct = solve(PartitionSpec(graph=g, cost=cm, q_grid=qs)).sweep
        for qi, q in enumerate(qs):
            if not direct.feasible[qi]:
                with pytest.raises(Infeasible):
                    table.lookup(b, s, q)
                continue
            plan = table.lookup(b, s, q)
            assert list(plan.bounds) == direct.bounds(qi), (arch, b, s, q)
            assert plan.e_total == direct.e_total[qi], (arch, b, s, q)
            assert plan.n_tasks == g.n_tasks
        # the single-Q convenience API agrees too (bounds bit-identical)
        part = solve(PartitionSpec(graph=g, cost=cm,
                                   q_max=qs[-2])).partition()
        assert list(table.lookup(b, s, qs[-2]).bounds) == part.bounds


def test_bucketing_rounds_seq_up(smoke_plan_table):
    _, _, qs, table = smoke_plan_table("qwen3-4b")
    # seq 20 rounds up to the (2, 32) bucket, not (2, 16)
    plan = table.lookup(2, 20, None)
    assert (plan.batch, plan.seq_bucket) == (2, 32)
    plan = table.lookup(2, 16, None)
    assert (plan.batch, plan.seq_bucket) == (2, 16)
    # budget selection: largest tabulated Q under the budget
    finite = sorted(q for q in qs if q is not None)
    k = table.q_index(finite[-1] * 1.5)
    assert table.q_grid[k] == finite[-1]
    with pytest.raises(Infeasible):
        table.q_index(finite[0] * 1e-6)


def test_roundtrip_save_load_exact(tmp_path, smoke_plan_table):
    _, _, qs, table = smoke_plan_table("whisper-large-v3")
    path = str(tmp_path / "plan.npz")
    table.save(path)
    loaded = PlanTable.load(path)
    assert loaded.header == table.header
    assert loaded.content_digest() == table.content_digest()
    np.testing.assert_array_equal(loaded.q_grid, table.q_grid)
    np.testing.assert_array_equal(loaded.e_total, table.e_total)
    np.testing.assert_array_equal(loaded.cycle_energy, table.cycle_energy)
    for (b, s) in BUCKETS:
        for q in qs:
            try:
                a = table.lookup(b, s, q)
            except Infeasible:
                with pytest.raises(Infeasible):
                    loaded.lookup(b, s, q)
                continue
            z = loaded.lookup(b, s, q)
            assert a == z  # frozen dataclass: full bit-exact equality


def test_stale_version_and_unknown_bucket(tmp_path, monkeypatch,
                                          smoke_plan_table):
    _, _, _, table = smoke_plan_table("xlstm-1.3b")
    path = str(tmp_path / "plan.npz")
    table.save(path)

    with pytest.raises(UnknownBucketError):
        table.lookup(3, 16, None)          # batch never tabulated
    with pytest.raises(UnknownBucketError):
        table.lookup(2, 33, None)          # seq beyond every bucket
    assert issubclass(UnknownBucketError, KeyError)

    monkeypatch.setattr(pt_mod, "PLAN_TABLE_VERSION", pt_mod.PLAN_TABLE_VERSION + 1)
    with pytest.raises(StaleTableError):
        PlanTable.load(path)


def test_build_cache_short_circuits_solve(tmp_path, plan_grid):
    cfg = SMOKE_CONFIGS["tinyllama-1.1b"]
    cm, qs = plan_grid(cfg)
    cache = str(tmp_path)
    built0 = dict(pt_mod.BUILD_STATS)
    t1 = build_plan_table(cfg, BUCKETS, qs, cost=cm, cache_dir=cache)
    assert pt_mod.BUILD_STATS["built"] == built0["built"] + 1
    solves = dict(partition_jax.SOLVE_COUNT)
    t2 = build_plan_table(cfg, BUCKETS, qs, cost=cm, cache_dir=cache)
    assert partition_jax.SOLVE_COUNT == solves, "cache hit must not solve"
    assert pt_mod.BUILD_STATS["cache_hits"] == built0["cache_hits"] + 1
    assert t2.fingerprint == t1.fingerprint
    np.testing.assert_array_equal(t2.e_total, t1.e_total)
    # a different Q grid is a different fingerprint → fresh build
    fp = config_fingerprint(cfg, BUCKETS, qs, "time", cm)
    fp2 = config_fingerprint(cfg, BUCKETS, qs[:-1], "time", cm)
    assert fp != fp2
    # ... but the fingerprint is canonical: call order does not matter
    assert fp == config_fingerprint(cfg, BUCKETS[::-1], qs[::-1], "time", cm)


def test_builder_rejects_malformed_inputs():
    cfg = SMOKE_CONFIGS["qwen3-4b"]
    with pytest.raises(PlanTableError):
        build_plan_table(cfg, [], [None])
    with pytest.raises(PlanTableError):
        build_plan_table(cfg, [(2, 16)], [])
    with pytest.raises(PlanTableError):
        build_plan_table(cfg, [(2, 16), (2, 16)], [None])
    with pytest.raises(PlanTableError):
        build_plan_table(cfg, [(2, 16)], [1e-3, 1e-3, None])


def test_canonical_ordering_is_call_order_invariant(plan_grid):
    """Permuted buckets/Q values build content-identical tables."""
    cfg = SMOKE_CONFIGS["qwen3-4b"]
    cm, qs = plan_grid(cfg)
    a = build_plan_table(cfg, BUCKETS, qs, cost=cm)
    b = build_plan_table(cfg, BUCKETS[::-1], qs[::-1], cost=cm)
    assert a.content_digest() == b.content_digest()
    assert a.buckets() == sorted(BUCKETS)
    assert list(a.q_grid) == sorted(a.q_grid)


def test_tabulated_cuts_drive_offload_and_remat(smoke_plan_table):
    """A kind='memory' table's stored bounds, priced through the planner,
    reproduce the directly-solved OffloadPlan/RematPlan at on-grid budgets."""
    cfg, _, qs, table = smoke_plan_table("zamba2-7b", kind="memory")
    planner = ServePlanner(table)
    b, s = BUCKETS[1]
    budget = sorted(q for q in qs if q is not None)[-1]  # on-grid, feasible

    derived = planner.offload_plan(cfg, b, s, budget)
    direct = plan_offload(cfg, b, s, budget)
    assert derived.bounds == direct.bounds
    assert derived.offload_bytes == direct.offload_bytes
    assert derived.pcie_seconds == direct.pcie_seconds
    assert derived.segment_peak_bytes == direct.segment_peak_bytes

    rem = planner.remat_plan(cfg, b, s, budget)
    assert rem.bounds == list(planner.plan_for(b, s, budget).bounds)
    assert rem.saved_bytes >= 0 and rem.compute_seconds > 0
    cuts = planner.pipeline_cuts(b, s, budget)
    assert cuts == tuple(j for (_, j) in rem.bounds[:-1])

    # a time-kind table refuses memory-model derivation
    _, _, _, t_time = smoke_plan_table("zamba2-7b", kind="time")
    with pytest.raises(PlanTableError):
        ServePlanner(t_time).offload_plan(cfg, b, s, budget)


def test_as_planner_coercions(tmp_path, smoke_plan_table):
    cfg, _, _, table = smoke_plan_table("qwen1.5-0.5b")
    path = str(tmp_path / "t.npz")
    table.save(path)
    assert as_planner(path).table.arch == cfg.name
    p = ServePlanner(table)
    assert as_planner(p) is p
    assert as_planner(table).table is table
    with pytest.raises(TypeError):
        as_planner(123)


class TestRequestCycles:
    def test_unbounded_is_one_cycle(self):
        assert request_cycles(7, 1.0, None) == [(1, 7)]
        assert request_cycles(0, 1.0, None) == []

    def test_exact_fill_uses_shared_tolerance(self):
        # budget exactly 3 steps + startup: float noise must not split it
        assert request_cycles(9, 0.1, 0.3 + 0.01, e_startup=0.01) == [
            (1, 3), (4, 6), (7, 9)
        ]

    def test_oversized_step_gets_own_cycle(self):
        assert request_cycles(3, 5.0, 1.0) == [(1, 1), (2, 2), (3, 3)]

    def test_startup_charged_per_cycle(self):
        # 2 steps/cycle with startup, 3 without
        assert request_cycles(6, 1.0, 3.0, e_startup=0.5) == [
            (1, 2), (3, 4), (5, 6)
        ]
        assert request_cycles(6, 1.0, 3.0, e_startup=0.0) == [
            (1, 3), (4, 6)
        ]
