"""Julienne-planner tests: pipeline / offload / remat over the model zoo,
plus optimal_partition_k invariants.

The partition-k properties are plain ``check_*`` functions driven by a
stdlib-``random`` seed parametrization (always runs) and additionally by
hypothesis when it is installed (``pytest.importorskip`` semantics — the
fuzz class simply does not exist without it).
"""

import random

import numpy as np
import pytest
# These suites pin the *legacy* entry points (deprecation shims) bit-for-bit
# against the facade-era implementations; the CI deprecation gate excludes
# them via -m "not legacy" (see conftest).
pytestmark = pytest.mark.legacy


from repro.configs import REGISTRY
from repro.core import (GraphBuilder, Infeasible, PAPER_FRAM_MODEL,
                        brute_force_partition, optimal_partition_k, q_min)
from repro.core.layer_profile import build_activation_graph, profile_model
from repro.core.offload import min_activation_budget, plan_offload
from repro.core.pipeline import plan_pipeline
from repro.core.remat_policy import plan_remat, segments_for_scan

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def chain_graph(costs, nbytes=1000):
    b = GraphBuilder()
    prev = None
    for i, c in enumerate(costs):
        p = b.packet(f"p{i}", nbytes, keep=(i == len(costs) - 1))
        b.task(f"t{i}", reads=(prev,) if prev else (), writes=(p,), cost=c)
        prev = p
    return b.build()


def check_k_bursts_exact_count(costs, k):
    if k > len(costs):
        k = len(costs)
    g = chain_graph(costs)
    p = optimal_partition_k(g, PAPER_FRAM_MODEL, k)
    assert p.n_bursts == k
    p.validate(g)


def check_minimax_beats_uniform_split(costs):
    g = chain_graph(costs)
    k = 3 if len(costs) >= 3 else len(costs)
    p = optimal_partition_k(g, PAPER_FRAM_MODEL, k, objective="max")
    # uniform split is a candidate → optimum bottleneck ≤ its bottleneck
    n = len(costs)
    bounds, start = [], 1
    for s in range(k):
        end = (s + 1) * n // k
        bounds.append((start, end))
        start = end + 1
    from repro.core.burst import burst_cost
    uniform_max = max(burst_cost(g, PAPER_FRAM_MODEL, i, j) for i, j in bounds)
    assert p.max_burst <= uniform_max + 1e-9


class TestPartitionK:
    @pytest.mark.parametrize("seed", range(30))
    def test_k_bursts_exact_count(self, seed):
        rng = random.Random(seed)
        costs = [rng.uniform(0.1, 5.0) for _ in range(rng.randint(2, 10))]
        check_k_bursts_exact_count(costs, rng.randint(1, 5))

    @pytest.mark.parametrize("seed", range(20))
    def test_minimax_beats_uniform_split(self, seed):
        rng = random.Random(100 + seed)
        costs = [rng.uniform(0.1, 5.0) for _ in range(rng.randint(3, 9))]
        check_minimax_beats_uniform_split(costs)

    def test_k_equals_brute_force(self):
        g = chain_graph([1.0, 3.0, 0.5, 2.0, 1.5])
        p = optimal_partition_k(g, PAPER_FRAM_MODEL, 2)
        # brute force over all 2-burst splits
        from repro.core.burst import burst_cost
        best = min(
            burst_cost(g, PAPER_FRAM_MODEL, 1, c) + burst_cost(g, PAPER_FRAM_MODEL, c + 1, 5)
            for c in range(1, 5))
        assert p.e_total == pytest.approx(best, rel=1e-12)


if HAVE_HYPOTHESIS:

    class TestPartitionKFuzz:
        @settings(max_examples=40, deadline=None)
        @given(st.lists(st.floats(0.1, 5.0), min_size=2, max_size=10),
               st.integers(1, 5))
        def test_k_bursts_exact_count(self, costs, k):
            check_k_bursts_exact_count(costs, k)

        @settings(max_examples=30, deadline=None)
        @given(st.lists(st.floats(0.1, 5.0), min_size=3, max_size=9))
        def test_minimax_beats_uniform_split(self, costs):
            check_minimax_beats_uniform_split(costs)


ARCHS = ["deepseek-coder-33b", "zamba2-7b", "whisper-large-v3",
         "phi3.5-moe-42b-a6.6b", "xlstm-1.3b", "llama-3.2-vision-11b"]


@pytest.mark.parametrize("arch", ARCHS)
class TestPlanners:
    def test_pipeline_balance(self, arch):
        cfg = REGISTRY[arch]
        pp = plan_pipeline(cfg, batch=16, seq=4096, n_stages=8)
        assert pp.n_stages == 8
        assert pp.balance < 1.25  # within 25% of perfect balance
        assert pp.bottleneck_seconds > 0

    def test_offload_respects_budget(self, arch):
        cfg = REGISTRY[arch]
        qmn = min_activation_budget(cfg, 16, 4096)
        plan = plan_offload(cfg, 16, 4096, qmn * 2)
        assert all(s <= qmn * 2 * (1 + 1e-9) for s in plan.segment_peak_bytes)
        with pytest.raises(Infeasible):
            plan_offload(cfg, 16, 4096, qmn * 0.5)

    def test_remat_monotone_in_budget(self, arch):
        cfg = REGISTRY[arch]
        qmn = min_activation_budget(cfg, 4, 4096)
        fracs = []
        for m in (8.0, 16.0, 64.0):
            try:
                fracs.append(plan_remat(cfg, 4, 4096, qmn * m).recompute_fraction)
            except Infeasible:
                fracs.append(None)
        feas = [f for f in fracs if f is not None]
        assert len(feas) >= 2, "budgets too tight for this arch"
        # more memory → no more recompute
        assert all(a >= b - 1e-12 for a, b in zip(feas, feas[1:]))
        plan = plan_remat(cfg, 4, 4096, qmn * 64)
        n, seg = segments_for_scan(cfg.n_layers, plan)
        assert n * seg == cfg.n_layers


class TestDependencyAwareness:
    def test_whisper_keeps_enc_out_resident(self):
        """The encoder output has l_∞ = last decoder layer: a single burst
        over all decoder layers loads it exactly once (the paper's image
        packet pattern)."""
        cfg = REGISTRY["whisper-large-v3"]
        profiles, ll = profile_model(cfg, 16, 4096)
        g = build_activation_graph(profiles, ll, kind="time")
        from repro.core import burst_detail, tpu_pipeline_model
        n_enc = cfg.n_encoder_layers
        d = burst_detail(g, tpu_pipeline_model(), n_enc + 1, g.n_tasks)
        assert d.loads.count("enc_out") == 1

    def test_zamba_boundaries_after_mamba(self):
        """The profile has a shared-block task before each of the 13 hybrid
        Mamba2 layers, each reading embed0; pipeline cuts should not strand
        that input needlessly — every stage reads it at most once."""
        cfg = REGISTRY["zamba2-7b"]
        pp = plan_pipeline(cfg, 16, 4096, 4)
        profiles, ll = profile_model(cfg, 16, 4096)
        names = [p.name for p in profiles]
        assert len(names) == 81 + 13
        for k, h in enumerate(cfg.hybrid_layer_ids):
            at = names.index(f"mamba{h}")
            assert names[at - 1] == f"shared{k}"
            assert profiles[at - 1].extra_reads == ("embed0",)
        g = build_activation_graph(profiles, ll, kind="time")
        from repro.core import burst_detail, tpu_pipeline_model
        for (i, j) in pp.bounds:
            d = burst_detail(g, tpu_pipeline_model(), i, j)
            assert d.loads.count("embed0") <= 1
