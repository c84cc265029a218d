"""Optimality and invariant tests for the partitioner (paper §4.3–4.4).

Property invariants:

* the fused DP, the paper's state-graph Dijkstra, and exhaustive search agree;
* Q_min from the minimax sweep equals the brute-force bottleneck;
* a partition exists iff Q_max ≥ Q_min;
* E_total and N_bursts are monotone non-increasing in Q_max;
* every returned partition is structurally valid and within budget.

Each property is a plain ``check_*`` function. A stdlib-``random``
seed-parametrized driver always runs them (so the suite works in minimal
environments); when hypothesis is installed the same checks additionally run
under its fuzzer. ``pytest.importorskip`` guards the hypothesis-only class.
"""

import random

import numpy as np
import pytest

from helpers_random import random_cost_model, random_task_graph

from repro.api import PartitionSpec, solve
from repro.core import (
    PAPER_FRAM_MODEL,
    GraphBuilder,
    Infeasible,
    brute_force_partition,
    dijkstra_partition,
    q_min,
    q_min_bruteforce,
    single_task_partition,
    whole_app_partition,
)


def _numpy_partition(graph, cost, q_max=None):
    """The numpy reference DP's optimal partition under one Q_max."""
    return solve(PartitionSpec(graph=graph, cost=cost, q_max=q_max,
                               backend="numpy")).partition()


def _numpy_partitions(graph, cost, q_values):
    """The numpy reference DP's per-Q partitions (None where infeasible)."""
    return solve(PartitionSpec(graph=graph, cost=cost, q_grid=tuple(q_values),
                               backend="numpy")).partitions()


try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

CM = PAPER_FRAM_MODEL


# -- the properties (shared between both drivers) ------------------------------


def check_dp_equals_bruteforce_and_dijkstra(g, cm, qscale):
    qmn = q_min(g, cm)
    whole = whole_app_partition(g, cm).e_total
    q = qmn + qscale * (whole - qmn) / 3.0
    bf = brute_force_partition(g, cm, q)
    dp = _numpy_partition(g, cm, q)
    dj = dijkstra_partition(g, cm, q)
    assert dp.e_total == pytest.approx(bf.e_total, rel=1e-9, abs=1e-12)
    assert dj.e_total == pytest.approx(bf.e_total, rel=1e-9, abs=1e-12)
    dp.validate(g)
    dj.validate(g)


def check_qmin_matches_bruteforce(g, cm):
    assert q_min(g, cm) == pytest.approx(q_min_bruteforce(g, cm), rel=1e-9, abs=1e-12)


def check_feasibility_boundary(g, cm):
    qmn = q_min(g, cm)
    # feasible exactly at Q_min
    p = _numpy_partition(g, cm, qmn)
    assert p.max_burst <= qmn * (1 + 1e-9) + 1e-12
    # infeasible strictly below (when Q_min is positive)
    if qmn > 1e-9:
        with pytest.raises(Infeasible):
            _numpy_partition(g, cm, qmn * 0.99 - 1e-12)


def check_monotonicity_in_qmax(g, cm):
    qmn = q_min(g, cm)
    whole = whole_app_partition(g, cm).e_total
    qs = np.linspace(qmn, max(whole, qmn) * 1.01, 8)
    parts = _numpy_partitions(g, cm, qs)
    assert all(p is not None for p in parts)
    e = [p.e_total for p in parts]
    nb = [p.n_bursts for p in parts]
    assert all(a >= b - 1e-9 for a, b in zip(e, e[1:])), "E_total must not increase"
    # N_bursts is not guaranteed monotone pointwise, but more budget never
    # forces more bursts: the Q_min partition stays feasible at every larger
    # Q, so one with more bursts is chosen only where it ties with it on
    # E_total. The DP minimizes E_total alone and states no tie-break on the
    # burst count (see test_monotonicity_in_qmax_zero_cost_tie).
    for p, n in zip(parts, nb):
        if n > parts[0].n_bursts:
            assert p.e_total == pytest.approx(parts[0].e_total, rel=1e-9,
                                              abs=1e-12), (nb, e)


def check_unbounded_at_most_whole_app(g, cm):
    # With no Q_max the optimum is at most the whole-app cost (one burst is
    # always a candidate).
    p = _numpy_partition(g, cm, None)
    assert p.e_total <= whole_app_partition(g, cm).e_total + 1e-12


def check_optimal_beats_baselines(g, cm):
    qmn = q_min(g, cm)
    p = _numpy_partition(g, cm, None)
    st_ = single_task_partition(g, cm, naive_state_retention=True)
    assert p.e_total <= st_.e_total + 1e-9
    p2 = _numpy_partition(g, cm, qmn)
    st2 = single_task_partition(g, cm, naive_state_retention=False)
    # dependency-optimized single-task is also a valid partition → optimum ≤ it
    if st2.max_burst <= qmn * (1 + 1e-9):
        assert p2.e_total <= st2.e_total + 1e-9


# -- driver 1: stdlib-random fallback (always runs) ----------------------------


@pytest.mark.parametrize("seed", range(40))
def test_dp_equals_bruteforce_and_dijkstra(seed):
    rng = random.Random(seed)
    check_dp_equals_bruteforce_and_dijkstra(
        random_task_graph(rng), random_cost_model(rng), rng.uniform(0.0, 3.0)
    )


@pytest.mark.parametrize("seed", range(40))
def test_qmin_matches_bruteforce(seed):
    rng = random.Random(1000 + seed)
    check_qmin_matches_bruteforce(random_task_graph(rng), random_cost_model(rng))


@pytest.mark.parametrize("seed", range(25))
def test_feasibility_boundary(seed):
    rng = random.Random(2000 + seed)
    check_feasibility_boundary(random_task_graph(rng), random_cost_model(rng))


@pytest.mark.parametrize("seed", range(25))
def test_monotonicity_in_qmax(seed):
    rng = random.Random(3000 + seed)
    check_monotonicity_in_qmax(random_task_graph(rng), random_cost_model(rng))


@pytest.mark.parametrize("seed", range(15))
def test_unbounded_at_most_whole_app(seed):
    rng = random.Random(4000 + seed)
    check_unbounded_at_most_whole_app(random_task_graph(rng), random_cost_model(rng))


@pytest.mark.parametrize("seed", range(15))
def test_optimal_beats_baselines(seed):
    rng = random.Random(5000 + seed)
    check_optimal_beats_baselines(random_task_graph(rng), random_cost_model(rng))


# -- driver 2: hypothesis fuzzing (when installed) -----------------------------

if HAVE_HYPOTHESIS:

    @st.composite
    def task_graphs(draw, max_tasks=9):
        n = draw(st.integers(1, max_tasks))
        n_ext = draw(st.integers(0, 2))
        b = GraphBuilder()
        avail = []
        for i in range(n_ext):
            b.packet(f"e{i}", draw(st.integers(1, 4000)), external=True)
            avail.append(f"e{i}")
        for t in range(n):
            n_reads = draw(st.integers(0, min(3, len(avail))))
            reads = draw(
                st.lists(st.sampled_from(avail), min_size=n_reads,
                         max_size=n_reads, unique=True)
            ) if avail else []
            n_writes = draw(st.integers(0, 2))
            writes = []
            for w in range(n_writes):
                name = f"p{t}_{w}"
                b.packet(name, draw(st.integers(1, 4000)),
                         keep=draw(st.booleans()))
                writes.append(name)
            b.task(f"t{t}", reads=tuple(reads), writes=tuple(writes),
                   cost=draw(st.floats(0.01, 10.0, allow_nan=False)))
            avail.extend(writes)
        return b.build()

    @st.composite
    def cost_models(draw):
        from repro.core import CostModel, LinearTransfer

        return CostModel(
            e_startup=draw(st.floats(0, 1.0)),
            read=LinearTransfer(draw(st.floats(0, 0.1)), draw(st.floats(0, 1e-3))),
            write=LinearTransfer(draw(st.floats(0, 0.1)), draw(st.floats(0, 1e-3))),
        )

    class TestHypothesisFuzz:
        @settings(max_examples=60, deadline=None)
        @given(task_graphs(), cost_models(), st.floats(0.0, 3.0))
        def test_dp_equals_bruteforce_and_dijkstra(self, g, cm, qscale):
            check_dp_equals_bruteforce_and_dijkstra(g, cm, qscale)

        @settings(max_examples=60, deadline=None)
        @given(task_graphs(), cost_models())
        def test_qmin_matches_bruteforce(self, g, cm):
            check_qmin_matches_bruteforce(g, cm)

        @settings(max_examples=40, deadline=None)
        @given(task_graphs(), cost_models())
        def test_feasibility_boundary(self, g, cm):
            check_feasibility_boundary(g, cm)

        @settings(max_examples=40, deadline=None)
        @given(task_graphs(), cost_models())
        def test_monotonicity_in_qmax(self, g, cm):
            check_monotonicity_in_qmax(g, cm)

        @settings(max_examples=30, deadline=None)
        @given(task_graphs(), cost_models())
        def test_unbounded_at_most_whole_app(self, g, cm):
            check_unbounded_at_most_whole_app(g, cm)

        @settings(max_examples=30, deadline=None)
        @given(task_graphs(), cost_models())
        def test_optimal_beats_baselines(self, g, cm):
            check_optimal_beats_baselines(g, cm)

else:

    def test_hypothesis_fuzz_skipped_without_hypothesis():
        pytest.importorskip("hypothesis")


# -- deterministic regressions -------------------------------------------------


def test_sweep_none_for_infeasible():
    b = GraphBuilder()
    b.packet("x", 100, keep=True)
    b.task("t", writes=("x",), cost=1.0)
    g = b.build()
    res = _numpy_partitions(g, CM, [0.1, 2.0])
    assert res[0] is None and res[1] is not None


def test_monotonicity_in_qmax_zero_cost_tie():
    """Pinned example: under an all-zero cost model every partition costs
    the sum of its task costs, so the DP's choices across the Q grid tie on
    E_total while their burst counts differ (3 at Q_min, 4 above it).
    Monotonicity holds as a tie, not as a bound on the burst count."""
    from repro.core import CostModel, LinearTransfer

    b = GraphBuilder()
    for t, c in enumerate([0.9, 0.2, 0.1, 2.0, 1.1, 0.6]):
        b.task(f"t{t}", cost=c)
    g = b.build()
    cm = CostModel(e_startup=0.0, read=LinearTransfer(0.0, 0.0),
                   write=LinearTransfer(0.0, 0.0))
    check_monotonicity_in_qmax(g, cm)
    qmn = q_min(g, cm)
    qs = np.linspace(qmn, whole_app_partition(g, cm).e_total * 1.01, 8)
    nb = [p.n_bursts for p in _numpy_partitions(g, cm, qs)]
    assert max(nb) > nb[0], nb  # the example still exercises the tie


def test_empty_graph():
    g = GraphBuilder().build()
    p = _numpy_partition(g, CM, None)
    assert p.n_bursts == 0 and p.e_total == 0.0


def test_partition_summary_smoke():
    g = GraphBuilder()
    g.packet("x", 10, keep=True)
    g.task("t", writes=("x",), cost=1.0)
    p = _numpy_partition(g.build(), CM, None)
    assert "bursts=1" in p.summary()
