"""Test bootstrap: import paths and shared fixtures.

Makes ``repro`` importable without an install (the repo is src-layout and has
no setup.py) and the sibling test helpers importable regardless of how pytest
was invoked.

The plan-table fixtures below are the single source of the smoke-config
table-build helpers shared by tests/test_plan_table.py,
tests/test_serve_plan.py, and tests/test_dse_shard.py (they used to be
duplicated per module). All repro imports stay inside the fixture bodies so
collection never pays the jax import.
"""

import os
import sys

import pytest

_TESTS = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(_TESTS), "src")
for _p in (_SRC, _TESTS):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@pytest.fixture(autouse=True)
def _reset_serving_counters():
    """Zero every process-global counter after each test so test ordering
    can't leak TRACE_COUNT / SOLVE_COUNT / COMMIT_STATS / BUILD_STATS between
    suites. All counter dicts register with the repro.obs metrics registry at
    module import, so one ``reset_all()`` covers whatever subset this test
    actually imported — and repro.obs itself is stdlib-only, so pure-numpy
    tests never pay the jax import just for the reset. The tracer is
    disarmed too, so a test that enabled tracing can't leak spans."""
    yield
    from repro.obs.metrics import reset_all
    from repro.obs.trace import TRACER

    reset_all()
    if TRACER.enabled or TRACER.events():
        TRACER.reset()


# -- shared plan-table fixtures ------------------------------------------------

# The canonical smoke bucket set for plan-table suites (two seq buckets at
# batch 2 plus one at batch 4 — exercises both bucket axes).
PLAN_BUCKETS = [(2, 16), (2, 32), (4, 32)]

# Serving-regression shapes (test_serve_plan.py + the DSE serving check).
SERVE_ARCHS = ["qwen3-4b", "xlstm-1.3b"]  # dense GQA + SSM
SERVE_BATCH, SERVE_PROMPT, SERVE_GEN = 2, 8, 6
SERVE_MAX_SEQ = SERVE_PROMPT + SERVE_GEN


@pytest.fixture(scope="session")
def plan_grid():
    """Factory: cfg → (cost model, small Q grid spanning infeasible →
    whole-app across PLAN_BUCKETS)."""
    import numpy as np

    from repro.core import lower_config, q_min, whole_app_partition
    from repro.core.plan_table import _default_cost

    def _grid(cfg, kind="time"):
        cm = _default_cost(kind)
        graphs = [lower_config(cfg, b, s, kind=kind) for (b, s) in PLAN_BUCKETS]
        qmn = min(q_min(g, cm) for g in graphs)
        hi = max(whole_app_partition(g, cm).e_total for g in graphs)
        qs = [qmn * 0.5] + list(np.geomspace(qmn, hi * 1.1, 4)) + [None]
        return cm, qs

    return _grid


@pytest.fixture(scope="session")
def smoke_plan_table(plan_grid):
    """Factory: smoke arch (or ModelConfig) → (cfg, cm, qs, table) built on
    PLAN_BUCKETS. Extra kwargs (sharding, cache_dir, ...) forward to
    build_plan_table."""
    def _build(arch, kind="time", *, buckets=None, **kwargs):
        from repro.configs import SMOKE_CONFIGS
        from repro.core import build_plan_table

        cfg = SMOKE_CONFIGS[arch] if isinstance(arch, str) else arch
        cm, qs = plan_grid(cfg, kind)
        table = build_plan_table(cfg, buckets or PLAN_BUCKETS, qs, kind=kind,
                                 cost=cm, **kwargs)
        return cfg, cm, qs, table

    return _build


@pytest.fixture(scope="session")
def serve_tables():
    """One derived-grid plan table per serving regression arch."""
    from repro.launch.planner import build_table_for_arch

    return {
        arch: build_table_for_arch(
            arch, [(SERVE_BATCH, SERVE_MAX_SEQ), (SERVE_BATCH, 2 * SERVE_MAX_SEQ)],
            n_q=8,
        )
        for arch in SERVE_ARCHS
    }
