"""Algorithm 1 runtime: atomic equivalence, accounting, crash recovery."""

import random
import tempfile

import numpy as np
import pytest

from repro.api import PartitionSpec, solve
from repro.core import (
    PAPER_FRAM_MODEL,
    BurstRuntime,
    DirNVM,
    GraphBuilder,
    MemoryNVM,
    PowerFailure,
    execute_atomic,
    single_task_partition,
)


def _numpy_partition(graph, cost, q_max=None):
    """The numpy reference DP's optimal partition under one Q_max."""
    return solve(PartitionSpec(graph=graph, cost=cost, q_max=q_max,
                               backend="numpy")).partition()


CM = PAPER_FRAM_MODEL


def pipeline_graph(n=12, seed=0):
    """A numeric pipeline with reconvergent dataflow and real bodies."""
    rng = np.random.RandomState(seed)
    b = GraphBuilder()
    b.packet("x0", 64, external=True)
    prev = "x0"
    checkpoints = ["x0"]
    for t in range(n):
        name = f"x{t + 1}"
        b.packet(name, 64, keep=(t == n - 1))
        skip = checkpoints[len(checkpoints) // 2]
        deps = (prev,) if (t % 3 or skip == prev) else (prev, skip)
        c = float(rng.rand() + 0.1)
        mults = rng.randn(len(deps)).astype(np.float64)

        def fn(inp, deps=deps, mults=mults, name=name):
            acc = sum(m * np.asarray(inp[d]) for d, m in zip(deps, mults))
            return {name: np.tanh(acc)}

        b.task(f"t{t}", reads=deps, writes=(name,), cost=c, fn=fn)
        checkpoints.append(name)
        prev = name
    return b.build()


@pytest.fixture
def graph():
    return pipeline_graph()


@pytest.fixture
def inputs():
    return {"x0": np.linspace(-1, 1, 8)}


def test_partitioned_equals_atomic(graph, inputs):
    ref = execute_atomic(graph, inputs)
    from repro.core import q_min
    qmn = q_min(graph, CM)
    for qmax in [None, 3 * qmn, qmn]:
        part = _numpy_partition(graph, CM, qmax)
        rt = BurstRuntime(graph, part, MemoryNVM(), cost=CM)
        out = rt.run(inputs)
        np.testing.assert_array_equal(out["x12"], ref["x12"])


def test_energy_and_bytes_match_model(graph, inputs):
    from repro.core import q_min
    part = _numpy_partition(graph, CM, 1.5 * q_min(graph, CM))
    rt = BurstRuntime(graph, part, MemoryNVM(), cost=CM)
    rt.run(inputs)
    assert rt.stats.energy == pytest.approx(part.e_total, rel=1e-12)
    model_bytes = sum(b.read_bytes + b.write_bytes for b in part.bursts)
    assert rt.stats.bytes_loaded + rt.stats.bytes_stored == model_bytes
    assert rt.stats.tasks_run == graph.n_tasks
    assert rt.stats.bursts_run == part.n_bursts


@pytest.mark.parametrize("crash_p", [0.2, 0.5, 0.8])
def test_crash_recovery_bit_exact(graph, inputs, crash_p):
    from repro.core import q_min
    ref = execute_atomic(graph, inputs)
    part = _numpy_partition(graph, CM, 1.5 * q_min(graph, CM))
    rng = random.Random(int(crash_p * 100))

    def chaos(b, phase):
        if rng.random() < crash_p:
            raise PowerFailure(f"burst {b} @ {phase}")

    rt = BurstRuntime(graph, part, MemoryNVM(), cost=CM, crash_hook=chaos)
    out = rt.run_to_completion(inputs)
    np.testing.assert_array_equal(out["x12"], ref["x12"])
    # committed bursts counted exactly once despite replays
    assert rt.stats.bursts_run == part.n_bursts


def test_crash_before_commit_replays_burst(graph, inputs):
    from repro.core import q_min
    part = _numpy_partition(graph, CM, 1.5 * q_min(graph, CM))
    crashed = []

    def crash_once(b, phase):
        if b == 1 and phase == "stored" and not crashed:
            crashed.append(True)
            raise PowerFailure()

    rt = BurstRuntime(graph, part, MemoryNVM(), cost=CM, crash_hook=crash_once)
    out = rt.run_to_completion(inputs)
    ref = execute_atomic(graph, inputs)
    np.testing.assert_array_equal(out["x12"], ref["x12"])
    assert crashed  # the injection actually fired
    assert rt.stats.tasks_run > graph.n_tasks  # some tasks re-ran (idempotent)


def test_disk_nvm_resume_across_instances(graph, inputs):
    """Simulates full process death: a NEW runtime resumes from disk."""
    from repro.core import q_min
    ref = execute_atomic(graph, inputs)
    part = _numpy_partition(graph, CM, 1.5 * q_min(graph, CM))
    with tempfile.TemporaryDirectory() as d:
        nvm = DirNVM(d)
        hits = [0]

        def crash_at_2(b, phase):
            if b == 2 and phase == "executed" and hits[0] == 0:
                hits[0] = 1
                raise PowerFailure()

        rt1 = BurstRuntime(graph, part, nvm, cost=CM, crash_hook=crash_at_2)
        with pytest.raises(PowerFailure):
            rt1.run(inputs)
        # fresh process, fresh runtime, same NVM directory
        rt2 = BurstRuntime(graph, part, DirNVM(d), cost=CM)
        out = rt2.run()
        np.testing.assert_array_equal(out["x12"], ref["x12"])
        assert rt2.nvm.read_index() == part.n_bursts


def test_single_task_partition_runs(graph, inputs):
    ref = execute_atomic(graph, inputs)
    part = single_task_partition(graph, CM, naive_state_retention=False)
    rt = BurstRuntime(graph, part, MemoryNVM(), cost=CM)
    out = rt.run(inputs)
    np.testing.assert_array_equal(out["x12"], ref["x12"])
    assert rt.stats.bursts_run == graph.n_tasks


def test_missing_external_input_raises(graph):
    part = _numpy_partition(graph, CM, None)
    rt = BurstRuntime(graph, part, MemoryNVM())
    with pytest.raises(ValueError, match="missing external packet"):
        rt.run({})
