"""Serving regression: the plan-table path changes scheduling, never results.

* ``serve()`` with and without ``plan_table`` produces identical token
  sequences on two smoke archs (different families);
* the planned request path does **zero partitioner solves** and **zero jit
  retraces** across repeated requests (trace/solve counters pinned);
* an energy budget splits the request into multiple committed cycles, and a
  mid-request power failure resumes from the last committed cycle boundary
  with identical output tokens.
"""

import numpy as np
import pytest

from conftest import (
    SERVE_ARCHS,
    SERVE_BATCH,
    SERVE_GEN,
    SERVE_MAX_SEQ,
    SERVE_PROMPT,
)

from repro.core import MemoryNVM, PowerFailure
from repro.core import partition_jax
from repro.core.plan_table import PlanTableError
from repro.launch import serve as serve_mod
from repro.launch.planner import ServePlanner
from repro.launch.serve import serve

pytestmark = pytest.mark.slow  # XLA model compiles; fast job skips these

# Shapes + the table-build fixture live in conftest.py (`serve_tables`),
# shared with the sharded-DSE tier.
ARCHS = SERVE_ARCHS
BATCH, PROMPT, GEN = SERVE_BATCH, SERVE_PROMPT, SERVE_GEN
MAX_SEQ = SERVE_MAX_SEQ


@pytest.fixture(scope="module")
def tables(serve_tables):
    return serve_tables


@pytest.fixture(scope="module")
def plain_tokens():
    return {
        arch: np.asarray(serve(arch, BATCH, PROMPT, GEN)) for arch in ARCHS
    }


@pytest.mark.parametrize("arch", ARCHS)
def test_planned_tokens_identical_to_unplanned(arch, tables, plain_tokens):
    rep = {}
    planned = serve(arch, BATCH, PROMPT, GEN, plan_table=tables[arch],
                    report=rep)
    np.testing.assert_array_equal(plain_tokens[arch], np.asarray(planned))
    assert rep["cycles"] == [(1, GEN)]  # unbounded budget: one cycle
    assert rep["runtime_stats"].bursts_run == 1
    assert rep["planner_stats"]["lookups"] == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_plan_lookup_adds_zero_retraces_and_zero_solves(
    arch, tables, plain_tokens
):
    planner = ServePlanner(tables[arch])
    first = serve(arch, BATCH, PROMPT, GEN, plan_table=planner)
    traces = dict(serve_mod.TRACE_COUNT)
    solves = dict(partition_jax.SOLVE_COUNT)
    dp_traces = partition_jax.TRACE_COUNT["dp_sweep"]
    for _ in range(2):
        again = serve(arch, BATCH, PROMPT, GEN, plan_table=planner)
        np.testing.assert_array_equal(np.asarray(first), np.asarray(again))
    assert dict(serve_mod.TRACE_COUNT) == traces, "request path re-traced"
    assert dict(partition_jax.SOLVE_COUNT) == solves, "request path re-solved"
    assert partition_jax.TRACE_COUNT["dp_sweep"] == dp_traces
    assert planner.stats["lookups"] == 3  # but every request did look up
    np.testing.assert_array_equal(plain_tokens[arch], np.asarray(first))


def test_energy_budget_splits_into_committed_cycles(tables, plain_tokens):
    arch = ARCHS[0]
    table = tables[arch]
    plan = table.lookup(BATCH, MAX_SEQ, None)
    budget = plan.e_total * 2.2 + table.e_startup  # ~2 steps per cycle
    rep = {}
    planned = serve(arch, BATCH, PROMPT, GEN, plan_table=table,
                    energy_budget=budget, report=rep)
    np.testing.assert_array_equal(plain_tokens[arch], np.asarray(planned))
    assert len(rep["cycles"]) == 3
    assert rep["runtime_stats"].bursts_run == 3
    assert rep["nvm"].read_index() == 3
    # modeled energy: 3 activations + GEN activation-graph traversals
    expect = 3 * table.e_startup + GEN * plan.e_total
    assert rep["runtime_stats"].energy == pytest.approx(expect, rel=1e-12)


def test_crash_mid_request_resumes_from_committed_cycle(tables, plain_tokens):
    arch = ARCHS[0]
    table = tables[arch]
    plan = table.lookup(BATCH, MAX_SEQ, None)
    budget = plan.e_total * 2.2 + table.e_startup

    class CrashOnce:
        def __init__(self):
            self.fired = 0
            self.sites = []

        def __call__(self, b, phase):
            self.sites.append((b, phase))
            if b == 1 and phase == "executed" and not self.fired:
                self.fired += 1
                raise PowerFailure("injected mid-request")

    hook = CrashOnce()
    rep = {}
    planned = serve(arch, BATCH, PROMPT, GEN, plan_table=table,
                    energy_budget=budget, nvm=MemoryNVM(), crash_hook=hook,
                    report=rep)
    assert hook.fired == 1
    np.testing.assert_array_equal(plain_tokens[arch], np.asarray(planned))
    st = rep["runtime_stats"]
    assert st.bursts_run == 3                 # each cycle committed once
    assert st.tasks_run > GEN                 # cycle 1 replayed after the crash
    # resume replayed burst 1, not burst 0: cycle 0's commit survived
    assert (0, "loaded") in hook.sites
    assert hook.sites.count((0, "loaded")) == 1


def test_table_arch_mismatch_raises(tables):
    with pytest.raises(PlanTableError):
        serve(ARCHS[1], BATCH, PROMPT, GEN, plan_table=tables[ARCHS[0]])


@pytest.mark.parametrize("arch", ARCHS)
def test_unplanned_requests_add_zero_retraces(arch, plain_tokens):
    # regression: the unplanned path used to rebuild jax.jit(lambda ...)
    # wrappers per call, retracing every repeated same-shape request; it now
    # routes through the cached _step_fns (donate=True fast path)
    first = serve(arch, BATCH, PROMPT, GEN)
    traces = dict(serve_mod.TRACE_COUNT)
    for _ in range(2):
        again = serve(arch, BATCH, PROMPT, GEN)
        np.testing.assert_array_equal(np.asarray(first), np.asarray(again))
    assert dict(serve_mod.TRACE_COUNT) == traces, "unplanned path re-traced"
    np.testing.assert_array_equal(plain_tokens[arch], np.asarray(first))


def test_reset_trace_counts_zeroes_counters():
    serve_mod.TRACE_COUNT["prefill"] += 1  # simulate leaked state
    serve_mod.reset_trace_counts()
    assert serve_mod.TRACE_COUNT == {"prefill": 0, "decode": 0}


@pytest.mark.parametrize("arch", ["zamba2-7b", "qwen1.5-0.5b"])
def test_open_counts_decode_state_bytes_by_kind(arch):
    """``serve.state_bytes`` grows at each open by one decode-state packet's
    recurrent state and KV caches, counted here by hand."""
    from repro.configs import SMOKE_CONFIGS
    from repro.launch.planner import build_table_for_arch
    from repro.launch.serve import STATE_BYTES, PlannedExecutor

    cfg = SMOKE_CONFIGS[arch]
    batch, prompt, gen = 1, 8, 4
    seq = prompt + gen
    ex = PlannedExecutor(arch, build_table_for_arch(arch, [(batch, seq)], n_q=4))
    before = dict(STATE_BYTES)
    for _ in range(2):
        ex.open(batch, prompt, gen)
    kv_pos = 2 * cfg.n_kv_heads * cfg.hd * 2  # k and v, bfloat16
    if cfg.family == "hybrid":
        heads = 2 * cfg.d_model // cfg.ssm_headdim
        conv = 2 * cfg.d_model + 2 * cfg.ssm_ngroups * cfg.ssm_state
        recurrent = cfg.n_layers * 4 * (heads * cfg.ssm_headdim * cfg.ssm_state
                                        + 3 * conv)
        kv = len(cfg.hybrid_layer_ids) * seq * kv_pos
        assert (recurrent, kv) == (6 * 4 * (8 * 16 * 16 + 3 * 192), 2 * 12 * 512)
    else:
        recurrent, kv = 0, cfg.n_layers * seq * kv_pos
    assert STATE_BYTES["recurrent"] - before["recurrent"] == 2 * recurrent
    assert STATE_BYTES["kv"] - before["kv"] == 2 * kv
