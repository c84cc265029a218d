"""Differential suite: the jitted engine vs the three numpy oracles.

On ≥100 randomized graphs (1–20 tasks, random Q grids straddling the
feasibility boundary) the jitted sweep (``solve(PartitionSpec(...)).sweep``
under backend="auto") must agree with

* the numpy reference DP (``backend="numpy"``) — e_total AND reconstructed
  bounds
  (bit-exact: the engine replays the numpy accumulation order, so even
  argmin tie-breaks match on unit-``c0_weight`` graphs);
* :func:`dijkstra_partition` — e_total on every feasible Q;
* :func:`brute_force_partition` — e_total on graphs small enough to
  enumerate (n ≤ 9);

including the Infeasible/None cases and the empty graph. A second block
checks every lowerable model-zoo config, the cross-graph vmapped batch
path, and the head-count app (coalesced sub-packet weights, where XLA's
FMA contraction allows ulp-level drift → 1e-6 rel as per spec, asserted
far tighter).
"""

import random

import numpy as np
import pytest

from helpers_random import (
    adversarial_tie_graph,
    random_cost_model,
    random_q_grid,
    random_task_graph,
    tie_cost_model,
    tie_q_grid,
)

from repro.api import PartitionSpec, solve
from repro.configs import REGISTRY
from repro.core import (
    PAPER_FRAM_MODEL,
    GraphBuilder,
    Infeasible,
    brute_force_partition,
    dijkstra_partition,
    lower_zoo,
    q_min,
    stack_graph_arrays,
    tpu_host_offload_model,
    whole_app_partition,
)
from repro.core.apps.headcount import THERMAL, build_graph

CM = PAPER_FRAM_MODEL

# One padded shape for every random graph → a single XLA compilation serves
# the whole 100-graph suite (padding correctness is itself under test).
PAD = dict(n_pad=20, r_pad=3, w_pad=2)

REL = 1e-6  # spec'd tolerance; the engine is asserted exact/1e-9 below


def _numpy_partitions(graph, cost, q_values):
    """The numpy reference DP's per-Q partitions (None where infeasible)."""
    return solve(PartitionSpec(graph=graph, cost=cost, q_grid=q_values,
                               backend="numpy")).partitions()


def _sweep(graph, cost, q_values):
    """The jitted single-graph sweep table (backend='auto')."""
    return solve(PartitionSpec(graph=graph, cost=cost, q_grid=q_values)).sweep


def _sweeps(graphs, cost, q_values):
    """The jitted batched sweep tables, one per graph (backend='auto')."""
    return solve(PartitionSpec(graphs=graphs, cost=cost,
                               q_grid=q_values)).sweeps


def _assert_matches_oracles(g, cm, qs):
    ref = _numpy_partitions(g, cm, qs)
    res = _sweep(g.to_arrays(**PAD), cm, qs)
    parts = res.to_partitions(g, cm)
    for q, r, p in zip(qs, ref, parts):
        if r is None:
            assert p is None, f"jax feasible where numpy Infeasible (Q={q})"
            with pytest.raises(Infeasible):
                dijkstra_partition(g, cm, q)
            continue
        assert p is not None, f"jax Infeasible where numpy feasible (Q={q})"
        # vs the fused numpy DP: bit-exact, including reconstructed bounds
        assert p.e_total == r.e_total
        assert p.bounds == r.bounds
        # vs the paper's explicit state-graph Dijkstra
        dj = dijkstra_partition(g, cm, q)
        assert p.e_total == pytest.approx(dj.e_total, rel=REL, abs=1e-12)
        # vs exhaustive search (test oracle) where enumerable
        if g.n_tasks <= 9:
            bf = brute_force_partition(g, cm, q)
            assert p.e_total == pytest.approx(bf.e_total, rel=REL, abs=1e-12)
        p.validate(g)


@pytest.mark.parametrize("seed", range(100))
def test_differential_random_graphs(seed):
    rng = random.Random(seed)
    g = random_task_graph(rng, max_tasks=20)
    cm = random_cost_model(rng)
    qs = random_q_grid(rng, q_min(g, cm), whole_app_partition(g, cm).e_total)
    _assert_matches_oracles(g, cm, qs)


@pytest.mark.parametrize("seed", range(25))
def test_differential_tie_graphs(seed):
    """Exact-tie audit (ROADMAP): on the adversarial equal-cost family every
    burst cost is a dyadic rational, so DP argmin ties are exact everywhere —
    the engine must reconstruct the *same bounds* as the numpy DP (smallest
    burst start wins), not merely the same totals. The three-way check
    including the CSR/Pallas backend lives in tests/test_partition_sweep.py.
    """
    rng = random.Random(7000 + seed)
    g = adversarial_tie_graph(rng)
    cm = tie_cost_model(rng)
    qs = tie_q_grid(rng, q_min(g, cm), whole_app_partition(g, cm).e_total)
    _assert_matches_oracles(g, cm, qs)


def test_empty_graph_feasible_everywhere():
    g = GraphBuilder().build()
    res = _sweep(g, CM, [None, 0.0, 1.0])
    assert res.feasible.all() and (res.e_total == 0.0).all()
    parts = res.to_partitions(g, CM)
    assert all(p is not None and p.n_bursts == 0 for p in parts)


def test_single_q_convenience_raises_infeasible():
    b = GraphBuilder()
    b.packet("x", 100, keep=True)
    b.task("t", writes=("x",), cost=1.0)
    g = b.build()
    p = solve(PartitionSpec(graph=g, cost=CM, q_max=None)).partition()
    assert p.n_bursts == 1
    with pytest.raises(Infeasible):
        solve(PartitionSpec(graph=g, cost=CM, q_max=1e-6)).partition()


def test_dp_and_parent_tables_match_recurrence():
    """dp[q, j] must be monotone in q and parent must reconstruct dp."""
    rng = random.Random(12345)
    g = random_task_graph(rng, max_tasks=12, min_tasks=8)
    cm = random_cost_model(rng)
    qmn = q_min(g, cm)
    qs = [qmn, qmn * 2.0, None]
    res = _sweep(g, cm, qs)
    n = g.n_tasks
    assert res.dp.shape == (3, res.dp.shape[1]) and res.dp[:, 0].min() == 0.0
    # larger budget → every dp entry no worse
    assert (res.dp[1, : n + 1] <= res.dp[0, : n + 1] + 1e-12).all()
    # bounds from parents cover 1..n contiguously
    for qi in range(3):
        bounds = res.bounds(qi)
        assert bounds is not None
        assert bounds[0][0] == 1 and bounds[-1][1] == n
        for (a, b2), (c, _) in zip(bounds, bounds[1:]):
            assert c == b2 + 1


# -- model zoo ----------------------------------------------------------------


def test_zoo_configs_match_numpy_multi():
    """Every lowerable config, solved in one vmapped batch, matches the
    numpy DP exactly (zoo packets have unit c0_weight)."""
    cm = tpu_host_offload_model()
    zoo = lower_zoo(batch=2, seq=256)
    assert set(zoo) == set(REGISTRY)
    names = sorted(zoo)
    qmns = {name: q_min(zoo[name], cm) for name in names}
    q_hi = max(qmns.values()) * 4
    qs = [None, 0.0, min(qmns.values()), q_hi]
    results = _sweeps([zoo[n] for n in names], cm, qs)
    for name, res in zip(names, results):
        g = zoo[name]
        ref = _numpy_partitions(g, cm, qs)
        parts = res.to_partitions(g, cm)
        for q, r, p in zip(qs, ref, parts):
            if r is None:
                assert p is None, (name, q)
            else:
                assert p is not None, (name, q)
                assert p.e_total == r.e_total, (name, q)
                assert p.bounds == r.bounds, (name, q)


def test_zoo_memory_kind_q_min_sweep():
    """The §4.4 storage-minimization reading: Q_max bounds per-segment
    activation bytes; sweeping tight→loose must be feasible above Q_min."""
    from repro.core import memory_cost_model

    cm = memory_cost_model()
    zoo = lower_zoo(batch=1, seq=128, kind="memory")
    for name, g in sorted(zoo.items()):
        qmn = q_min(g, cm)
        res = _sweep(g, cm, [qmn * 0.5, qmn, qmn * 4])
        assert not res.feasible[0] or qmn == 0.0
        assert res.feasible[1] and res.feasible[2]
        assert res.e_total[2] <= res.e_total[1] + 1e-9


def test_stacked_arrays_roundtrip():
    """stack_graph_arrays pads heterogeneous graphs without changing any
    per-graph solution."""
    rng = random.Random(7)
    graphs = [random_task_graph(rng, max_tasks=6 + 2 * k) for k in range(4)]
    stacked = stack_graph_arrays([g.to_arrays() for g in graphs])
    assert stacked.e_task.shape[0] == len(graphs)
    qs = [None, 0.5]
    for g, res in zip(graphs, _sweeps(graphs, CM, qs)):
        ref = _numpy_partitions(g, CM, qs)
        for r, p in zip(ref, res.to_partitions(g, CM)):
            if r is None:
                assert p is None
            else:
                assert p is not None and p.e_total == r.e_total


# -- the paper's application --------------------------------------------------


def test_headcount_reduced_matches_numpy():
    """Coalesced score arrays carry fractional c0_weight, where XLA FMA
    contraction may drift by ~1 ulp — assert well inside the 1e-6 spec."""
    g = build_graph(THERMAL.reduced(256))
    qmn = q_min(g, CM)
    qs = list(np.geomspace(qmn, g.total_task_cost() * 1.05, 64)) + [None, 0.0]
    ref = _numpy_partitions(g, CM, qs)
    res = _sweep(g, CM, qs)
    parts = res.to_partitions(g, CM)
    for q, r, p in zip(qs, ref, parts):
        if r is None:
            assert p is None
            continue
        assert p is not None
        assert p.e_total == pytest.approx(r.e_total, rel=1e-9)
        assert p.n_bursts == r.n_bursts
        p.validate(g)
