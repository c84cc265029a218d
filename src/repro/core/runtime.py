"""Burst-based application execution (paper Algorithm 1).

The runtime executes a partitioned :class:`~repro.core.graph.TaskGraph`:

    while not done:
        wait for energy            (no-op here: the EMU trigger is the caller)
        start up, read burst index from NVM
        load the burst's input packets from NVM          (dependency-optimized)
        execute the burst's tasks                         (volatile memory only)
        store packets needed by later bursts to NVM
        atomically increment the burst index
        power off                                         (volatile memory cleared)

Key property (tested): bursts are **idempotent**. A power failure at any point
before the index commit loses only volatile state; re-running the burst writes
identical packets (tasks are pure functions of their declared inputs — the
Ladybirds no-side-effects contract), so recovery is simply "run again from the
committed index". This is the paper's consistency argument and the same
protocol used by the training checkpointer (`repro.checkpoint.burst_ckpt`).

Two NVM backends: in-memory (tests, fault-injection) and a directory on disk
(atomic commit via write-to-temp + ``os.replace``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
import tempfile
from typing import Any, Callable, Dict, Mapping, Optional, Set, Tuple

from ..obs.metrics import METRICS
from ..obs.trace import PID_RUNTIME, TRACER
from .burst import burst_detail
from .cost import CostModel
from .graph import TaskGraph
from .partition import Partition

__all__ = [
    "PowerFailure",
    "MemoryNVM",
    "DirNVM",
    "BurstRuntime",
    "ExecutionStats",
    "execute_atomic",
    "COMMIT_STATS",
    "reset_commit_stats",
]

# Process-wide cycle/commit observability for harnesses that drive many
# runtimes at once (repro.launch.traffic): every committed burst and every
# replayed burst (a re-run of an index whose first attempt lost power before
# the commit) counts here, across all BurstRuntime instances. Consumers must
# snapshot-and-diff rather than read absolutes — see reset_commit_stats().
# Registry-backed (repro.obs.metrics) but still a plain dict to consumers.
COMMIT_STATS = METRICS.counter_dict("runtime.commit_stats", ("commits", "replays"))


def reset_commit_stats() -> None:
    """Zero the process-global commit counters (test isolation). This resets
    the *counters* only; NVM state and per-runtime ExecutionStats are
    untouched. Thin alias for the registry reset; one
    ``repro.obs.metrics.reset_all()`` covers this and every other counter."""
    COMMIT_STATS.reset()


class PowerFailure(RuntimeError):
    """Injected power loss: all volatile state is gone."""


class MemoryNVM:
    """Dict-backed NVM (tests / fault injection)."""

    def __init__(self) -> None:
        self._data: Dict[str, Any] = {}
        self._index: int = 0

    # -- packet storage --
    def write(self, name: str, value: Any) -> None:
        self._data[name] = value

    def read(self, name: str) -> Any:
        return self._data[name]

    def has(self, name: str) -> bool:
        return name in self._data

    # -- burst index (the commit point) --
    def read_index(self) -> int:
        return self._index

    def commit_index(self, index: int) -> None:
        self._index = index


class DirNVM:
    """Directory-backed NVM with atomic index commit (rename)."""

    def __init__(self, path: str) -> None:
        self.path = path
        os.makedirs(path, exist_ok=True)

    def _file(self, name: str) -> str:
        h = hashlib.sha1(name.encode()).hexdigest()[:16]
        return os.path.join(self.path, f"pkt_{h}.pkl")

    def write(self, name: str, value: Any) -> None:
        f = self._file(name)
        fd, tmp = tempfile.mkstemp(dir=self.path)
        with os.fdopen(fd, "wb") as fh:
            pickle.dump(value, fh)
        os.replace(tmp, f)

    def read(self, name: str) -> Any:
        with open(self._file(name), "rb") as fh:
            return pickle.load(fh)

    def has(self, name: str) -> bool:
        return os.path.exists(self._file(name))

    def read_index(self) -> int:
        f = os.path.join(self.path, "burst_index")
        if not os.path.exists(f):
            return 0
        with open(f) as fh:
            return int(fh.read().strip())

    def commit_index(self, index: int) -> None:
        f = os.path.join(self.path, "burst_index")
        fd, tmp = tempfile.mkstemp(dir=self.path)
        with os.fdopen(fd, "w") as fh:
            fh.write(str(index))
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, f)


@dataclasses.dataclass
class ExecutionStats:
    """Observed behaviour, comparable against the model's predictions."""

    bursts_run: int = 0
    tasks_run: int = 0
    bytes_loaded: int = 0
    bytes_stored: int = 0
    energy: float = 0.0  # model-accounted energy of what actually ran
    replays: int = 0  # bursts re-entered after a pre-commit power failure


CrashHook = Callable[[int, str], None]
"""Called at (burst_index, phase) with phase ∈ {'loaded', 'executed', 'stored'};
raise :class:`PowerFailure` to simulate power loss at that point."""


class BurstRuntime:
    """Executes a partitioned task graph per Algorithm 1."""

    def __init__(
        self,
        graph: TaskGraph,
        partition: Partition,
        nvm: Optional[Any] = None,
        cost: Optional[CostModel] = None,
        crash_hook: Optional[CrashHook] = None,
        on_commit: Optional[Callable[[int], None]] = None,
    ) -> None:
        partition.validate(graph)
        self.graph = graph
        self.partition = partition
        self.nvm = nvm if nvm is not None else MemoryNVM()
        self.cost = cost
        self.crash_hook = crash_hook
        self.on_commit = on_commit
        self.stats = ExecutionStats()
        self._attempted: Set[int] = set()

    # -- one burst = one "energy quantum" --------------------------------------

    def _run_burst(self, b: int) -> None:
        # Tracing wrapper: one span per energy cycle on the runtime track,
        # with PowerFailure surfaced as an instant. Guarded on the enabled
        # flag so the disabled hot path pays one attribute check.
        if not TRACER.enabled:
            return self._run_burst_impl(b)
        with TRACER.span(
            "burst", cat="runtime", pid=PID_RUNTIME, index=b, replay=b in self._attempted
        ):
            try:
                self._run_burst_impl(b)
            except PowerFailure:
                TRACER.instant("power_failure", cat="runtime", pid=PID_RUNTIME, index=b)
                raise

    def _run_burst_impl(self, b: int) -> None:
        i, j = self.partition.bounds[b]
        g = self.graph
        detail = self.partition.bursts[b]
        volatile: Dict[str, Any] = {}
        if b in self._attempted:  # a prior attempt lost power before commit
            self.stats.replays += 1
            COMMIT_STATS["replays"] += 1
            if TRACER.enabled:
                TRACER.instant("replay", cat="runtime", pid=PID_RUNTIME, index=b)
        self._attempted.add(b)

        # DMA in: dependency-optimized load set
        if TRACER.enabled:
            with TRACER.span("runtime.restore", cat="runtime", pid=PID_RUNTIME, index=b):
                self._restore(i, j, volatile)
        else:
            self._restore(i, j, volatile)
        self._maybe_crash(b, "loaded")

        # execute tasks on volatile memory only
        for k in range(i, j + 1):
            t = g.task(k)
            if t.fn is None:
                raise ValueError(f"task {t.name!r} has no runtime body (fn=None)")
            inputs = {name: volatile[name] for name in t.reads}
            outputs = t.fn(inputs)
            missing = set(t.writes) - set(outputs)
            if missing:
                raise ValueError(f"task {t.name!r} did not produce {sorted(missing)}")
            for name in t.writes:
                volatile[name] = outputs[name]
            self.stats.tasks_run += 1
        self._maybe_crash(b, "executed")

        if TRACER.enabled:
            with TRACER.span("runtime.commit", cat="runtime", pid=PID_RUNTIME, index=b):
                self._commit(b, i, j, volatile)
        else:
            self._commit(b, i, j, volatile)
        self.stats.bursts_run += 1
        COMMIT_STATS["commits"] += 1
        if self.cost is not None:
            self.stats.energy += detail.total
        if self.on_commit is not None:
            # post-commit observer (progress streaming); runs after the
            # linearization point so a crash inside it cannot lose the burst
            self.on_commit(b)
        # power off: volatile memory is dropped on return

    def _restore(self, i: int, j: int, volatile: Dict[str, Any]) -> None:
        for name in self._load_set(i, j):
            volatile[name] = self.nvm.read(name)
            self.stats.bytes_loaded += self.graph.packets[name].nbytes

    def _commit(self, b: int, i: int, j: int, volatile: Dict[str, Any]) -> None:
        # DMA out: packets needed by later bursts
        for name in self._store_set(i, j):
            self.nvm.write(name, volatile[name])
            self.stats.bytes_stored += self.graph.packets[name].nbytes
        self._maybe_crash(b, "stored")
        # linearization point
        self.nvm.commit_index(b + 1)

    def _load_set(self, i: int, j: int) -> Tuple[str, ...]:
        g = self.graph
        out = []
        seen: Set[str] = set()
        for k in range(i, j + 1):
            t = g.task(k)
            for name, lt in zip(t.reads, g.read_last_touch[k - 1]):
                if lt < i and name not in seen:
                    seen.add(name)
                    out.append(name)
        return tuple(out)

    def _store_set(self, i: int, j: int) -> Tuple[str, ...]:
        g = self.graph
        out = []
        for k in range(i, j + 1):
            for name in g.task(k).writes:
                if g.l_inf[name] > j:
                    out.append(name)
        return tuple(out)

    def _maybe_crash(self, b: int, phase: str) -> None:
        if self.crash_hook is not None:
            self.crash_hook(b, phase)

    # -- public API -------------------------------------------------------------

    def seed_inputs(self, inputs: Mapping[str, Any]) -> None:
        """Place external packets into NVM before the first activation."""
        for name, p in self.graph.packets.items():
            if p.external:
                if name not in inputs:
                    raise ValueError(f"missing external packet {name!r}")
                self.nvm.write(name, inputs[name])

    def step(self) -> bool:
        """Run exactly one uncommitted burst — one energy cycle / one system
        activation — and return True once every burst has committed.

        This is the unit the continuous-traffic harness schedules: cycles of
        many concurrent requests interleave by calling each runtime's
        ``step()`` in turn. A :class:`PowerFailure` raised mid-burst leaves
        the committed index unchanged, so the next ``step()`` replays the
        same burst (the idempotent-recovery contract). External inputs must
        already be seeded (:meth:`seed_inputs`).
        """
        b = self.nvm.read_index()
        if b >= self.partition.n_bursts:
            return True
        self._run_burst(b)
        return self.nvm.read_index() >= self.partition.n_bursts

    def run(self, inputs: Optional[Mapping[str, Any]] = None) -> Dict[str, Any]:
        """Execute to completion, resuming from the committed burst index.

        Safe to call repeatedly after :class:`PowerFailure` — each call is one
        or more "system activations".
        """
        if inputs is not None and self.nvm.read_index() == 0:
            self.seed_inputs(inputs)
        n = self.partition.n_bursts
        b = self.nvm.read_index()
        while b < n:
            self._run_burst(b)
            b = self.nvm.read_index()
        return self.outputs()

    def run_to_completion(
        self, inputs: Optional[Mapping[str, Any]] = None, max_activations: int = 10**6
    ) -> Dict[str, Any]:
        """Like :meth:`run`, but rides through injected power failures —
        models the EMU re-triggering the system when the capacitor refills."""
        first = True
        for _ in range(max_activations):
            try:
                return self.run(inputs if first else None)
            except PowerFailure:
                first = False
                continue
        raise RuntimeError("did not complete within max_activations")

    def outputs(self) -> Dict[str, Any]:
        return {
            name: self.nvm.read(name)
            for name, p in self.graph.packets.items()
            if p.keep
        }


def execute_atomic(graph: TaskGraph, inputs: Mapping[str, Any]) -> Dict[str, Any]:
    """Reference semantics: the whole application in one uninterrupted pass."""
    mem: Dict[str, Any] = dict(inputs)
    for t in graph.tasks:
        if t.fn is None:
            raise ValueError(f"task {t.name!r} has no runtime body")
        outs = t.fn({name: mem[name] for name in t.reads})
        for name in t.writes:
            mem[name] = outs[name]
    return {name: mem[name] for name, p in graph.packets.items() if p.keep}
