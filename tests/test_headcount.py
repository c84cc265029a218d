"""Paper-claim validation: the head-counting applications (§5–§6).

Every assertion cites the paper's number. Where the reconstruction cannot be
exact (the paper omits the full packet layout) tolerances are documented in
EXPERIMENTS.md §Paper-repro.
"""

import numpy as np
import pytest

from repro.api import PartitionSpec, solve
from repro.core import (
    BurstRuntime,
    MemoryNVM,
    execute_atomic,
    q_min,
    single_task_partition,
    whole_app_partition,
)
from repro.core.apps.headcount import THERMAL, VISUAL, build_graph, paper_cost_model


def _numpy_partition(graph, cost, q_max=None):
    """The numpy reference DP's optimal partition under one Q_max."""
    return solve(PartitionSpec(graph=graph, cost=cost, q_max=q_max,
                               backend="numpy")).partition()


def _numpy_partitions(graph, cost, q_values):
    """The numpy reference DP's per-Q partitions (None where infeasible)."""
    return solve(PartitionSpec(graph=graph, cost=cost, q_grid=tuple(q_values),
                               backend="numpy")).partitions()


CM = paper_cost_model()


@pytest.fixture(scope="module")
def thermal():
    return build_graph(THERMAL)


@pytest.fixture(scope="module")
def visual():
    return build_graph(VISUAL)


class TestEnergyCharacterization:
    def test_task_count_matches_single_task_bursts(self, thermal):
        assert thermal.n_tasks == 5458  # paper Fig. 6: 5458 bursts

    def test_application_energy(self, thermal):
        # §6.4: atomic execution requires harvesting 2.294 J
        assert thermal.total_task_cost() == pytest.approx(2.294, abs=5e-4)

    def test_cnn_energy_sums(self):
        # Table 2 E_sum column
        assert 4125 * 0.396e-3 == pytest.approx(1633.5e-3, rel=1e-3)
        assert 936 * 0.396e-3 == pytest.approx(370.7e-3, rel=1e-3)
        assert 391 * 0.403e-3 == pytest.approx(157.6e-3, rel=1e-3)

    def test_processing_total(self):
        # Table 2: total head-counting 2161.8 mJ
        proc = (
            THERMAL.e_normalize + THERMAL.e_initialize
            + sum(e * n for e, n in zip(THERMAL.e_cnn, THERMAL.n_cnn))
            + THERMAL.e_sort + THERMAL.e_nms
        )
        assert proc == pytest.approx(2161.8e-3, abs=0.05e-3)

    def test_visual_differs_only_in_sense(self, thermal, visual):
        # §5: "the only difference ... is the energy required for the image
        # acquisition itself"
        assert VISUAL.e_sense == pytest.approx(4.4e-3)
        assert (thermal.total_task_cost() - visual.total_task_cost()
                == pytest.approx(131.9e-3 - 4.4e-3, rel=1e-9))


class TestPartitioningResults:
    def test_qmin_is_132mJ(self, thermal):
        # §6.3: "We thus use Q_max=132 mJ as the smallest feasible capacity"
        assert q_min(thermal, CM) == pytest.approx(132e-3, abs=0.5e-3)

    def test_julienning_18_bursts(self, thermal):
        p = _numpy_partition(thermal, CM, 132e-3)
        assert p.n_bursts == 18  # Fig. 6

    def test_overhead_near_paper(self, thermal):
        # Fig. 6 / abstract: 2.79 mJ ≈ 0.12 % overhead. Our reconstruction
        # gives ~1.8 mJ ≈ 0.08 % — same order, see EXPERIMENTS.md.
        p = _numpy_partition(thermal, CM, 132e-3)
        pct = 100 * p.e_overhead / p.e_total
        assert pct < 0.2
        assert p.e_overhead < 3e-3

    def test_single_task_5458_bursts_437MB(self, thermal):
        st = single_task_partition(thermal, CM)
        assert st.n_bursts == 5458
        assert st.transfer_bytes > 437e6  # "over 437 MB"
        assert st.transfer_bytes < 1.2 * 449.8e6
        # Fig. 6: overhead larger than the application energy itself
        assert st.e_overhead > st.e_app

    def test_storage_reduction_94pct(self, thermal):
        # §7: "reduce the energy storage by 94% compared to no partitioning"
        whole = whole_app_partition(thermal, CM)
        reduction = 1 - q_min(thermal, CM) / whole.max_burst
        assert reduction > 0.94

    def test_single_burst_when_qmax_exceeds_app(self, thermal):
        # §6.3: "Once Q_max > E_app + E_bootup, the optimal N_bursts is 1"
        p = _numpy_partition(thermal, CM, thermal.total_task_cost() * 1.01)
        assert p.n_bursts == 1


class TestDesignSpace:
    def test_thermal_feasibility_range(self, thermal):
        # §6.4: thermal feasibility range is 1–18 bursts
        qs = np.geomspace(132e-3, 2.5, 24)
        parts = [p for p in _numpy_partitions(thermal, CM, qs) if p is not None]
        nb = [p.n_bursts for p in parts]
        assert max(nb) == 18 and min(nb) == 1

    def test_nbursts_monotone_nonincreasing(self, visual):
        qs = np.geomspace(4.5e-3, 2.4, 16)
        parts = _numpy_partitions(visual, CM, qs)
        nb = [p.n_bursts for p in parts if p is not None]
        assert all(a >= b for a, b in zip(nb, nb[1:]))

    def test_visual_wider_range_than_thermal(self, thermal, visual):
        # §6.4: visual partitions much finer (456 bursts in the paper;
        # ~500 in our reconstruction) because sensing is only 4.4 mJ
        qv, qt = q_min(visual, CM), q_min(thermal, CM)
        assert qv < qt / 25
        pv = _numpy_partition(visual, CM, qv)
        assert pv.n_bursts > 400

    def test_overhead_below_3pct_at_4p3pct_storage(self, visual, thermal):
        # Fig. 8 caption: overhead stays below 3% for storage bounds as low
        # as 4.3% of E_app.
        for g in (thermal, visual):
            e_app = g.total_task_cost()
            q = max(0.043 * e_app, q_min(g, CM))
            p = _numpy_partition(g, CM, q)
            assert p.e_overhead / p.e_total < 0.03


class TestExecutableCNN:
    def test_reduced_graph_runs_and_matches_atomic(self):
        spec = THERMAL.reduced(scale=128)
        g = build_graph(spec, with_fns=True, seed=3)
        ref = execute_atomic(g, {})
        assert int(ref["headcount"]) > 0
        p = _numpy_partition(g, CM, 132e-3)
        rt = BurstRuntime(g, p, MemoryNVM(), cost=CM)
        out = rt.run({})
        assert out["headcount"] == ref["headcount"]

    def test_thermal_visual_same_pipeline_shape(self):
        gt = build_graph(THERMAL.reduced(128), with_fns=True, seed=3)
        gv = build_graph(VISUAL.reduced(128), with_fns=True, seed=3)
        # same CNN → identical headcount on the same frame (§5)
        assert execute_atomic(gt, {})["headcount"] == execute_atomic(gv, {})["headcount"]
