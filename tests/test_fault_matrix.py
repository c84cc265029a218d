"""Fault-injection matrix over the executable workloads (paper Algorithm 1).

For every lowered model-zoo graph with runtime bodies, plus the head-count
app, a single ``run_to_completion`` rides through an injected power failure
at *every* (burst, phase) point — 'loaded', 'executed' and 'stored', i.e.
before the index commit — and must still produce outputs identical to
``execute_atomic``. A recording NVM additionally proves replayed bursts are
idempotent: every re-write of a packet is byte-identical (pickle bytes) to
the first write, the paper's consistency argument made literal.
"""

import pickle

import numpy as np
import pytest

from repro.api import PartitionSpec, solve
from repro.configs import SMOKE_CONFIGS
from repro.core import (
    BurstRuntime,
    MemoryNVM,
    PowerFailure,
    execute_atomic,
    external_inputs,
    lower_config,
    q_min,
)
from repro.core.apps.headcount import THERMAL, build_graph


def _numpy_partition(graph, cost, q_max=None):
    """The numpy reference DP's optimal partition under one Q_max."""
    return solve(PartitionSpec(graph=graph, cost=cost, q_max=q_max,
                               backend="numpy")).partition()


class RecordingNVM(MemoryNVM):
    """MemoryNVM that keeps every serialized write per packet."""

    def __init__(self):
        super().__init__()
        self.writes = {}

    def write(self, name, value):
        self.writes.setdefault(name, []).append(
            pickle.dumps(value, protocol=pickle.HIGHEST_PROTOCOL)
        )
        super().write(name, value)


class CrashEverywhere:
    """Raises PowerFailure once at each distinct (burst, phase) site."""

    def __init__(self):
        self.seen = set()
        self.fired = 0

    def __call__(self, b, phase):
        if (b, phase) not in self.seen:
            self.seen.add((b, phase))
            self.fired += 1
            raise PowerFailure(f"injected at burst {b} @ {phase}")


def _zoo_cases():
    for arch, cfg in sorted(SMOKE_CONFIGS.items()):
        yield arch, lower_config(cfg, batch=2, seq=16, with_fns=True)
    yield "headcount-thermal", build_graph(THERMAL.reduced(2048), with_fns=True)


CASES = list(_zoo_cases())


@pytest.mark.parametrize("arch,graph", CASES, ids=[c[0] for c in CASES])
def test_crash_at_every_burst_phase_matches_atomic(arch, graph):
    from repro.core import PAPER_FRAM_MODEL as CM

    inputs = external_inputs(graph)
    ref = execute_atomic(graph, inputs)
    assert ref, f"{arch}: graph has no kept outputs"

    # a mid-granularity partition: several bursts, several tasks per burst
    qmn = q_min(graph, CM)
    part = _numpy_partition(graph, CM, qmn * 1.5)
    hook = CrashEverywhere()
    nvm = RecordingNVM()
    rt = BurstRuntime(graph, part, nvm, cost=CM, crash_hook=hook)
    out = rt.run_to_completion(inputs or None)

    # every (burst, phase) site actually crashed once
    assert hook.fired == part.n_bursts * 3
    # committed bursts counted exactly once despite all the replays
    assert rt.stats.bursts_run == part.n_bursts
    assert rt.stats.tasks_run > graph.n_tasks  # replays really happened

    assert set(out) == set(ref)
    for name in ref:
        np.testing.assert_array_equal(np.asarray(out[name]),
                                      np.asarray(ref[name]), err_msg=name)

    # idempotency: every replayed store wrote byte-identical NVM packets
    replayed = {n: w for n, w in nvm.writes.items() if len(w) > 1}
    assert replayed, f"{arch}: crash matrix produced no replayed stores"
    for name, blobs in nvm.writes.items():
        for blob in blobs[1:]:
            assert blob == blobs[0], f"packet {name!r} not idempotent"


@pytest.mark.parametrize("arch,graph", CASES[:3], ids=[c[0] for c in CASES[:3]])
def test_single_task_bursts_survive_crash_matrix(arch, graph):
    """The Single Task scheme (one task per burst) under the same matrix."""
    from repro.core import PAPER_FRAM_MODEL as CM
    from repro.core import single_task_partition

    inputs = external_inputs(graph)
    ref = execute_atomic(graph, inputs)
    part = single_task_partition(graph, CM, naive_state_retention=False)
    rt = BurstRuntime(graph, part, RecordingNVM(), cost=CM,
                      crash_hook=CrashEverywhere())
    out = rt.run_to_completion(inputs or None)
    for name in ref:
        np.testing.assert_array_equal(np.asarray(out[name]),
                                      np.asarray(ref[name]))
    assert rt.stats.bursts_run == graph.n_tasks
