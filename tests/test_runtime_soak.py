"""Runtime-at-scale soak (ROADMAP): a 500+-task reduced head-count graph
executed through :class:`BurstRuntime` on :class:`DirNVM` under ≥20
randomized, seeded crash schedules.

Every schedule injects power failures at random (burst, phase) sites via
``crash_hook`` — including repeated crashes of the same burst — and half the
schedules additionally simulate full *reboots* by rebuilding the runtime
object from the on-disk NVM between activations. Final outputs (and every
persisted NVM packet file) must bit-match a crash-free run, the paper's
consistency argument at scale.
"""

import os
import pickle
import random

import numpy as np
import pytest

from repro.api import PartitionSpec, solve
from repro.core import (
    BurstRuntime,
    DirNVM,
    PAPER_FRAM_MODEL,
    PowerFailure,
    execute_atomic,
    q_min,
)
from repro.core.apps.headcount import VISUAL, build_graph


def _numpy_partition(graph, cost, q_max=None):
    """The numpy reference DP's optimal partition under one Q_max."""
    return solve(PartitionSpec(graph=graph, cost=cost, q_max=q_max,
                               backend="numpy")).partition()


pytestmark = pytest.mark.slow  # ~30 s of repeated 550-task executions

CM = PAPER_FRAM_MODEL
N_SCHEDULES = 20
CRASH_P = 0.12          # per-(burst, phase) crash probability
MAX_CRASHES = 60        # per schedule, so every schedule terminates


class RandomCrashes:
    """Seeded random PowerFailure injection at any (burst, phase) site."""

    def __init__(self, seed: int):
        self.rng = random.Random(seed)
        self.fired = 0

    def __call__(self, b: int, phase: str) -> None:
        if self.fired < MAX_CRASHES and self.rng.random() < CRASH_P:
            self.fired += 1
            raise PowerFailure(f"injected at burst {b} @ {phase}")


@pytest.fixture(scope="module")
def soak_case(tmp_path_factory):
    """(graph, partition, atomic reference, crash-free DirNVM packet bytes)."""
    graph = build_graph(VISUAL.reduced(10), with_fns=True)
    assert graph.n_tasks >= 500, "soak graph must be large-scale"
    part = _numpy_partition(graph, CM, q_min(graph, CM) * 1.5)
    assert part.n_bursts >= 20, "soak partition should have many crash sites"
    ref = execute_atomic(graph, {})

    clean_dir = tmp_path_factory.mktemp("nvm_clean")
    rt = BurstRuntime(graph, part, DirNVM(str(clean_dir)), cost=CM)
    out = rt.run()
    assert rt.stats.bursts_run == part.n_bursts
    for name in ref:
        np.testing.assert_array_equal(np.asarray(out[name]),
                                      np.asarray(ref[name]), err_msg=name)
    clean_pkts = _packet_bytes(str(clean_dir))
    assert clean_pkts, "crash-free run persisted no packets"
    return graph, part, ref, clean_pkts


def _packet_bytes(nvm_dir: str):
    out = {}
    for fname in sorted(os.listdir(nvm_dir)):
        if fname.startswith("pkt_") and fname.endswith(".pkl"):
            with open(os.path.join(nvm_dir, fname), "rb") as fh:
                out[fname] = fh.read()
    return out


def _run_with_reboots(graph, part, nvm, hook, max_activations=10_000):
    """Each activation uses a *fresh* BurstRuntime over the same DirNVM —
    the strongest recovery claim: nothing survives but the NVM directory."""
    total_tasks = 0
    for _ in range(max_activations):
        rt = BurstRuntime(graph, part, nvm, cost=CM, crash_hook=hook)
        try:
            out = rt.run()
            total_tasks += rt.stats.tasks_run
            return out, total_tasks
        except PowerFailure:
            total_tasks += rt.stats.tasks_run
            continue
    raise RuntimeError("did not complete within max_activations")


@pytest.mark.parametrize("seed", range(N_SCHEDULES))
def test_randomized_crash_schedule_bitmatches_clean_run(
    seed, soak_case, tmp_path
):
    graph, part, ref, clean_pkts = soak_case
    hook = RandomCrashes(1000 + seed)
    nvm = DirNVM(str(tmp_path / "nvm"))

    if seed % 2 == 0:
        # in-place recovery: one runtime rides through all failures
        rt = BurstRuntime(graph, part, nvm, cost=CM, crash_hook=hook)
        out = rt.run_to_completion({})
        tasks_run = rt.stats.tasks_run
    else:
        # reboot recovery: a fresh runtime per activation, state from disk only
        out, tasks_run = _run_with_reboots(graph, part, nvm, hook)

    assert hook.fired >= 1, "schedule injected no crashes — vacuous"
    assert nvm.read_index() == part.n_bursts
    if hook.fired:
        assert tasks_run > graph.n_tasks or hook.fired <= part.n_bursts

    # outputs bit-match the atomic reference
    assert set(out) == set(ref)
    for name in ref:
        a, b = np.asarray(out[name]), np.asarray(ref[name])
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)
        assert pickle.dumps(a, protocol=pickle.HIGHEST_PROTOCOL) == \
            pickle.dumps(b, protocol=pickle.HIGHEST_PROTOCOL), name

    # every persisted NVM packet is byte-identical to the crash-free run's
    pkts = _packet_bytes(str(tmp_path / "nvm"))
    assert set(pkts) == set(clean_pkts)
    for fname, blob in pkts.items():
        assert blob == clean_pkts[fname], f"NVM file {fname} diverged"
