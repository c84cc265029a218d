"""One Julienning façade: declarative :class:`PartitionSpec` → :class:`Engine`.

The paper's contribution is a *specification model*: an application is
declared once (atomic kernels + explicit data dependencies) and a single
optimization flow produces energy-bounded cycles. This module is that model
for the solver layer: callers build one immutable :class:`PartitionSpec` —

* **what** to partition: a :class:`~repro.core.graph.TaskGraph` (or a
  dense/CSR export of one), a batch of graphs, or a model-zoo config plus
  (batch, seq) shapes to lower;
* **what to optimize**: ``objective="sum"`` (the paper's E_total DP over a
  Q_max grid), ``"minimax"`` (§4.4 storage minimization — Q_min), or
  ``"exact_k"`` (the fixed-burst-count pipeline DP);
* **how** to solve it: ``backend="numpy" | "scan" | "pallas" | "auto"`` and
  an optional :class:`QGridSharding` spreading the Q grid over a device mesh

— and :meth:`Engine.solve` resolves it through a backend *registry*. Backends
self-register via :func:`register_backend` with capability flags
(``supports_sharding``, ``supports_csr``, ``supports_dense``, the supported
objective set): ``backend="auto"`` picks the highest-priority registered
backend whose capabilities match the export kind (and dense-export size) of
each graph, and mismatches raise *typed* errors — :class:`ExportMismatch`
for a layout the backend cannot consume, :class:`UnsupportedObjective` for
an objective it does not implement — identically from every backend. The
backend is resolved here, once per graph (:func:`resolve_jit_backend`);
the implementations in :mod:`repro.core.partition_jax` receive only the
concrete name.

Results come back as a :class:`Solution` whose accessors give the sweep
tables, per-Q :class:`~repro.core.partition.Partition` objects or Q_min
values; tests/test_api.py pins the jit backends against the numpy oracle
through these accessors.

Most callers go through :mod:`repro.api`, which re-exports everything here
plus the module-level :func:`solve` convenience.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..obs.trace import PID_SOLVER, TRACER
from .cost import CostModel
from .graph import (
    GraphArrays,
    GraphCSRArrays,
    TaskGraph,
    dense_export_nbytes,
)
from .partition import Infeasible, Partition
from .placement import PlacementSpec

__all__ = [
    "EngineError",
    "SpecError",
    "UnsupportedObjective",
    "ExportMismatch",
    "BackendInfo",
    "register_backend",
    "backend_names",
    "backend_info",
    "resolve_jit_backend",
    "export_kind",
    "QGridSharding",
    "PartitionSpec",
    "Solution",
    "Engine",
    "default_engine",
    "OBJECTIVES",
]

AnyExport = Union[TaskGraph, GraphArrays, GraphCSRArrays]

OBJECTIVES = ("sum", "minimax", "exact_k")

# backend="auto": route a TaskGraph to the compressed-layout (CSR/Pallas)
# backend once its dense export would cross this size (the full head-count
# graph is ~1 GB dense, ~400 kB CSR). Read at call time, so tests can
# monkeypatch it.
_AUTO_DENSE_BYTES = 32 << 20


# ---------------------------------------------------------------------------
# Typed errors
# ---------------------------------------------------------------------------


class EngineError(ValueError):
    """Base class for façade errors (spec validation, dispatch, capability)."""


class SpecError(EngineError):
    """Malformed or self-contradictory :class:`PartitionSpec`."""


class UnsupportedObjective(EngineError):
    """The selected backend does not implement the requested objective.

    Every built-in backend now implements all of :data:`OBJECTIVES` (the
    §4.4 combines are Pallas kernel modes), so in the default registry this
    only fires for externally registered backends with restricted
    ``objectives`` capability flags — the error-path suite pins the message
    against exactly such a fake backend.
    """


class ExportMismatch(EngineError, TypeError):
    """A graph export the selected backend cannot consume.

    Subclasses :class:`TypeError` for compatibility with the pre-façade
    behavior of ``_as_arrays`` / ``_as_csr``, which raised bare TypeErrors;
    the registry's capability check now produces this one typed error for
    every backend instead of backend-specific failures.
    """


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BackendInfo:
    """Registry entry: a backend class plus its capability flags.

    ``objectives`` is the set of :data:`OBJECTIVES` the backend implements;
    ``supports_dense`` / ``supports_csr`` declare which *export* layouts it
    consumes (every backend accepts a :class:`TaskGraph` and converts it
    itself); ``supports_sharding`` gates :class:`QGridSharding`;
    ``supports_placement`` gates the multi-node placement axis
    (``placement=PlacementSpec(...)``); ``auto_eligible`` marks jit backends
    that ``backend="auto"`` may pick (the numpy reference path is
    explicit-only).
    """

    name: str
    factory: Any
    objectives: frozenset
    supports_sharding: bool = False
    supports_csr: bool = False
    supports_dense: bool = True
    supports_placement: bool = False
    auto_eligible: bool = True


_REGISTRY: Dict[str, BackendInfo] = {}


def register_backend(
    name: str,
    *,
    objectives: Sequence[str] = ("sum",),
    supports_sharding: bool = False,
    supports_csr: bool = False,
    supports_dense: bool = True,
    supports_placement: bool = False,
    auto_eligible: bool = True,
    registry: Optional[Dict[str, BackendInfo]] = None,
):
    """Class decorator: self-register a backend under ``name``.

    ``registry`` defaults to the process-global one; tests pass their own
    dict to exercise registration without touching global dispatch.
    """
    bad = set(objectives) - set(OBJECTIVES)
    if bad:
        raise SpecError(f"unknown objectives {sorted(bad)}; known: {OBJECTIVES}")

    def deco(cls):
        (_REGISTRY if registry is None else registry)[name] = BackendInfo(
            name=name,
            factory=cls,
            objectives=frozenset(objectives),
            supports_sharding=supports_sharding,
            supports_csr=supports_csr,
            supports_dense=supports_dense,
            supports_placement=supports_placement,
            auto_eligible=auto_eligible,
        )
        return cls

    return deco


def backend_names(registry: Optional[Dict[str, BackendInfo]] = None) -> List[str]:
    return sorted(_REGISTRY if registry is None else registry)


def backend_info(
    name: str, registry: Optional[Dict[str, BackendInfo]] = None
) -> BackendInfo:
    reg = _REGISTRY if registry is None else registry
    try:
        return reg[name]
    except KeyError:
        raise SpecError(
            f"unknown backend {name!r}; registered: {sorted(reg)}"
        ) from None


def export_kind(graph: AnyExport) -> str:
    """Classify a solver input: ``"graph"`` / ``"dense"`` / ``"csr"``."""
    if isinstance(graph, TaskGraph):
        return "graph"
    if isinstance(graph, GraphArrays):
        return "dense"
    if isinstance(graph, GraphCSRArrays):
        return "csr"
    raise ExportMismatch(
        f"cannot solve a {type(graph).__name__}: expected a TaskGraph or a "
        f"GraphArrays / GraphCSRArrays export"
    )


def _check_export(
    info: BackendInfo,
    graph: AnyExport,
    registry: Optional[Dict[str, BackendInfo]] = None,
) -> None:
    """The registry capability check guarding every dispatch.

    A :class:`TaskGraph` is accepted by every backend (each converts it to
    its own layout, or — the numpy reference DP — walks it directly); the
    pre-exported array layouts must match the backend's capability flags.
    """
    reg = _REGISTRY if registry is None else registry
    kind = export_kind(graph)
    if kind == "dense" and not info.supports_dense:
        raise ExportMismatch(
            f"backend {info.name!r} does not consume dense GraphArrays "
            f"exports; pass the TaskGraph or pick a backend with "
            f"supports_dense (registered: "
            f"{[b.name for b in reg.values() if b.supports_dense]})"
        )
    if kind == "csr" and not info.supports_csr:
        raise ExportMismatch(
            f"backend {info.name!r} does not consume GraphCSRArrays exports; "
            f"pass the TaskGraph or pick a backend with supports_csr "
            f"(registered: "
            f"{[b.name for b in reg.values() if b.supports_csr]})"
        )


def resolve_jit_backend(
    graph: AnyExport,
    backend: str = "auto",
    objective: str = "sum",
    registry: Optional[Dict[str, BackendInfo]] = None,
) -> str:
    """Resolve ``backend="auto"`` for one graph via the registry flags —
    the one place the scan/Pallas rule lives.

    Among the ``auto_eligible`` backends implementing ``objective``, a CSR
    export picks a ``supports_csr`` backend, a dense export a
    ``supports_dense`` one, and a raw :class:`TaskGraph` routes by
    dense-export size — above ``_AUTO_DENSE_BYTES`` the compressed-layout
    backend wins. Explicit names pass through after a registry existence
    check.
    """
    reg = _REGISTRY if registry is None else registry
    jit = [b for b in reg.values() if b.auto_eligible]
    if backend != "auto":
        if backend not in reg:
            raise SpecError(
                f"unknown backend {backend!r}; registered: {sorted(reg)}"
            )
        if backend not in [b.name for b in jit]:
            # registered, just not a jit-dispatch target — saying "unknown"
            # here sent users hunting for typos that weren't there
            raise SpecError(
                f"backend {backend!r} is registered but not jit-dispatchable "
                f"(auto_eligible=False); registered: {sorted(reg)}; "
                f"jit-dispatchable: {sorted(b.name for b in jit)}"
            )
        return backend
    cands = [b for b in jit if objective in b.objectives]
    if not cands:
        raise UnsupportedObjective(
            f"no registered auto-eligible backend implements objective "
            f"{objective!r} (registered: {sorted(b.name for b in jit)})"
        )
    dense_c = [b for b in cands if b.supports_dense]
    csr_c = [b for b in cands if b.supports_csr]
    kind = export_kind(graph)
    if kind == "csr":
        pool = csr_c
    elif kind == "dense":
        pool = dense_c
    else:
        n = graph.n_tasks
        r = max((len(t.reads) for t in graph.tasks), default=0)
        w = max((len(t.writes) for t in graph.tasks), default=0)
        big = dense_export_nbytes(n, r, w) > _AUTO_DENSE_BYTES
        pool = (csr_c or dense_c) if big else (dense_c or csr_c)
    if not pool:
        # some backend implements the objective, just not for this layout —
        # that is an export problem, not an objective problem
        raise ExportMismatch(
            f"no backend implementing objective {objective!r} consumes a "
            f"{kind!r} export ({sorted(b.name for b in cands)} take "
            f"{'dense' if dense_c else 'csr'} or the TaskGraph itself); "
            f"pass the TaskGraph or re-export in the matching layout"
        )
    return pool[0].name


# ---------------------------------------------------------------------------
# The declarative spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class QGridSharding:
    """Shard the Q_max grid across ``n_shards`` device chunks.

    ``devices`` defaults to ``jax.local_devices()`` at solve time; with
    fewer local devices than shards the same chunk decomposition then runs
    sequentially (bit-identical either way). Explicit ``devices``
    are a placement: too few of them raise ``ValueError`` at solve time.
    Only ``objective="sum"`` has a Q grid to shard; a spec combining
    sharding with ``minimax``/``exact_k`` is rejected at construction
    (:class:`SpecError`).
    """

    n_shards: int
    devices: Optional[Tuple[Any, ...]] = None

    def __post_init__(self):
        if self.n_shards < 1:
            raise SpecError(f"n_shards must be >= 1, got {self.n_shards}")
        if self.devices is not None and not isinstance(self.devices, tuple):
            object.__setattr__(self, "devices", tuple(self.devices))

    # note: only objective="sum" has a Q grid to shard — PartitionSpec
    # rejects sharding for minimax/exact_k uniformly (SpecError), rather
    # than having backends silently ignore it


class _Unset:
    """Sentinel distinguishing 'q_max not given' from 'q_max=None=unbounded'."""

    def __repr__(self):  # pragma: no cover - repr only
        return "<unset>"


_UNSET = _Unset()


@dataclasses.dataclass(frozen=True, eq=False)
class PartitionSpec:
    """Immutable, declarative description of one partitioning problem.

    Exactly one input source::

        PartitionSpec(graph=g, ...)                  # one graph / export
        PartitionSpec(graphs=(g1, g2), ...)          # a batch (one solve)
        PartitionSpec(config="qwen3-4b", shapes=((2, 24), (2, 48)),
                      kind="time", smoke=True, ...)  # model-zoo lowering

    and at most one Q axis: ``q_grid`` (a tuple of Q_max values, ``None`` =
    unbounded) or the single-point ``q_max`` convenience. ``objective`` picks
    the DP: ``"sum"`` minimizes E_total over the grid (the paper's DP),
    ``"minimax"`` computes Q_min (§4.4; no Q axis), ``"exact_k"`` solves the
    fixed-burst-count DP for ``n_bursts`` (``k_objective`` chooses the
    combine: ``"sum"`` for E_total, ``"max"`` for the pipeline bottleneck).

    ``cost`` is required for explicit graphs; config-lowered specs default it
    per ``kind`` exactly like the plan-table builders. ``cost`` also accepts
    a :class:`repro.core.calibration.MeasuredCostTable`, in which case
    ``confidence`` (a level in (0, 1)) prices every cut at measured
    mean + z·sigma; ``confidence=None`` prices at the plain mean, which is
    bit-identical to the analytical model when the measurements match it.
    ``backend`` names a registered backend or ``"auto"``; ``sharding``
    spreads the Q grid over a device mesh; ``interpret`` is forwarded to the
    Pallas kernel.

    ``placement`` adds the multi-node axis (ROADMAP "multi-device
    placement"): a :class:`repro.core.placement.PlacementSpec` describing a
    relay chain of harvesting nodes plus the link-bandwidth / memory / Q
    sweep grids. Placement solves carry their own budget axes, so
    ``q_grid=`` / ``q_max=`` / ``sharding=`` are rejected alongside it, the
    objective must stay ``"sum"`` (the placement DP minimizes swarm
    E_total), and inputs must be :class:`TaskGraph` objects (the per-node
    column sweeps walk the graph structure).
    """

    graph: Optional[AnyExport] = None
    graphs: Optional[Tuple[AnyExport, ...]] = None
    config: Optional[Any] = None          # ModelConfig or registry arch name
    shapes: Tuple[Tuple[int, int], ...] = ((1, 128),)
    kind: str = "time"
    smoke: bool = False
    cost: Optional[CostModel] = None
    q_grid: Optional[Tuple[Optional[float], ...]] = None
    q_max: Any = _UNSET
    objective: str = "sum"
    n_bursts: Optional[int] = None
    k_objective: str = "sum"
    backend: str = "auto"
    sharding: Optional[QGridSharding] = None
    interpret: Optional[bool] = None
    confidence: Optional[float] = None
    placement: Optional[PlacementSpec] = None

    def __post_init__(self):
        sources = [
            s for s, v in (
                ("graph", self.graph),
                ("graphs", self.graphs),
                ("config", self.config),
            ) if v is not None
        ]
        if len(sources) != 1:
            raise SpecError(
                f"exactly one of graph= / graphs= / config= must be given "
                f"(got {sources or 'none'})"
            )
        if self.graphs is not None:
            object.__setattr__(self, "graphs", tuple(self.graphs))
            if not self.graphs:
                raise SpecError("graphs= is empty")
        object.__setattr__(
            self, "shapes", tuple((int(b), int(s)) for (b, s) in self.shapes)
        )
        if self.config is not None and not self.shapes:
            raise SpecError("config= specs need at least one (batch, seq) shape")
        if self.q_grid is not None:
            object.__setattr__(self, "q_grid", tuple(self.q_grid))
            if not self.q_grid:
                raise SpecError("q_grid= is empty")
        if self.objective not in OBJECTIVES:
            raise SpecError(
                f"unknown objective {self.objective!r}; one of {OBJECTIVES}"
            )
        if self.q_grid is not None and self.q_max is not _UNSET:
            raise SpecError("give q_grid= or q_max=, not both")
        if self.objective == "minimax":
            if self.q_grid is not None or self.q_max is not _UNSET:
                raise SpecError(
                    "objective='minimax' computes Q_min and has no Q axis; "
                    "drop q_grid=/q_max="
                )
        if self.objective == "exact_k":
            if self.n_bursts is None or int(self.n_bursts) < 1:
                raise SpecError(
                    "objective='exact_k' needs n_bursts >= 1"
                )
            if self.q_grid is not None:
                raise SpecError(
                    "objective='exact_k' takes a single q_max, not a q_grid"
                )
        elif self.n_bursts is not None:
            raise SpecError("n_bursts= only applies to objective='exact_k'")
        if self.k_objective not in ("sum", "max"):
            raise SpecError(
                f"k_objective must be 'sum' or 'max', got {self.k_objective!r}"
            )
        if self.sharding is not None:
            if not isinstance(self.sharding, QGridSharding):
                raise SpecError(
                    f"sharding= must be a QGridSharding, got "
                    f"{type(self.sharding).__name__}"
                )
            if self.objective != "sum":
                raise SpecError(
                    f"sharding shards the Q grid, which only "
                    f"objective='sum' has; objective={self.objective!r} "
                    f"solves per graph — drop sharding="
                )
        if not isinstance(self.backend, str):
            raise SpecError(f"backend= must be a name, got {self.backend!r}")
        if self.cost is not None and not (
            isinstance(self.cost, CostModel) or hasattr(self.cost, "cost_model")
        ):
            raise SpecError(
                f"cost= must be a CostModel or a calibrated "
                f"MeasuredCostTable (anything with .cost_model(confidence)), "
                f"got {type(self.cost).__name__}"
            )
        if self.placement is not None:
            if not isinstance(self.placement, PlacementSpec):
                raise SpecError(
                    f"placement= must be a PlacementSpec, got "
                    f"{type(self.placement).__name__}"
                )
            if self.objective != "sum":
                raise SpecError(
                    f"placement= solves the multi-node E_total DP, which "
                    f"rides objective='sum'; objective="
                    f"{self.objective!r} has no placement form"
                )
            if self.q_grid is not None or self.q_max is not _UNSET:
                raise SpecError(
                    "placement= sweeps per-node budgets via "
                    "PlacementSpec.q_scales (each node's q_max × the scale "
                    "grid); drop q_grid=/q_max="
                )
            if self.sharding is not None:
                raise SpecError(
                    "placement= has no Q grid to shard (its grid axes are "
                    "links × memory_scales × q_scales); drop sharding="
                )
        if self.confidence is not None:
            try:
                c = float(self.confidence)
            except (TypeError, ValueError):
                raise SpecError(
                    f"confidence= must be a float in (0, 1), got "
                    f"{self.confidence!r}"
                ) from None
            if not 0.0 < c < 1.0 or c != c:
                raise SpecError(
                    f"confidence= must lie strictly in (0, 1), got "
                    f"{self.confidence!r}"
                )
            object.__setattr__(self, "confidence", c)

    # -- normalized views ---------------------------------------------------

    @property
    def batched(self) -> bool:
        """True when the spec describes a batch (graphs= or config=)."""
        return self.graph is None

    @property
    def q_values(self) -> Tuple[Optional[float], ...]:
        """The normalized Q axis: ``()`` for minimax, one entry per grid
        point otherwise (a lone ``None`` = unbounded when nothing was given).
        """
        if self.objective == "minimax":
            return ()
        if self.q_grid is not None:
            return self.q_grid
        return (None if self.q_max is _UNSET else self.q_max,)


# ---------------------------------------------------------------------------
# Solutions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class Solution:
    """What :meth:`Engine.solve` returns: one payload per objective, with
    accessors for each shape of result.

    ``backend`` is the *resolved* backend name (``"scan+pallas"`` for a
    mixed ``auto`` batch); ``graphs`` / ``cost`` / ``q_values`` are the
    resolved inputs (config-lowered graphs included), so downstream pricing
    needs nothing but the solution object.
    """

    spec: PartitionSpec
    backend: str
    graphs: Tuple[AnyExport, ...]
    cost: CostModel
    q_values: Tuple[Optional[float], ...]
    sweeps: Optional[Tuple[Any, ...]] = None      # JaxSweep per graph (sum, jit)
    parts: Optional[Tuple[Tuple[Optional[Partition], ...], ...]] = None
    qmins: Optional[Tuple[float, ...]] = None     # minimax
    placements: Optional[Tuple[Any, ...]] = None  # PlacementSweep per graph

    @property
    def n_graphs(self) -> int:
        return len(self.graphs)

    def _one(self, what: Optional[tuple], label: str):
        if what is None:
            raise EngineError(
                f"this solution (objective={self.spec.objective!r}, "
                f"backend={self.backend!r}) carries no {label}"
            )
        return what

    @property
    def sweep(self):
        """The single :class:`~repro.core.partition_jax.JaxSweep` (one-graph
        specs on a jit backend)."""
        sweeps = self._one(self.sweeps, "JaxSweep results")
        if len(sweeps) != 1:
            raise EngineError(
                f"sweep is for single-graph specs; this one has "
                f"{len(sweeps)} — index .sweeps instead"
            )
        return sweeps[0]

    def partitions(self, graph_index: int = 0) -> List[Optional[Partition]]:
        """Per-Q :class:`Partition` objects for one graph (None where
        infeasible)."""
        if self.spec.objective == "minimax":
            raise EngineError(
                "objective='minimax' yields Q_min values; use .q_min()"
            )
        if self.parts is not None:
            return list(self.parts[graph_index])
        g = self.graphs[graph_index]
        if not isinstance(g, TaskGraph):
            raise EngineError(
                "materializing Partition objects needs the TaskGraph; this "
                "spec was built from a pre-exported array layout — call "
                ".sweeps[i].to_partitions(graph, cost) with the source graph"
            )
        return self._one(self.sweeps, "sweeps")[graph_index].to_partitions(
            g, self.cost
        )

    def partition(self, graph_index: int = 0, q_index: int = 0) -> Partition:
        """One feasible :class:`Partition`. Raises
        :class:`~repro.core.partition.Infeasible` identically across
        backends when that (graph, Q) cell has no partition."""
        p = self.partitions(graph_index)[q_index]
        if p is None:
            raise Infeasible(
                f"Q_max={self.q_values[q_index]} admits no partition"
            )
        return p

    def placement_sweep(self, graph_index: int = 0):
        """The solved :class:`~repro.core.placement.PlacementSweep` for one
        graph (specs with ``placement=``): the full links × memory × Q grid
        plus the raw DP tables the bit-identity gates compare."""
        return self._one(self.placements, "placement sweeps")[graph_index]

    def placement_plan(
        self,
        graph_index: int = 0,
        link_index: int = 0,
        memory_index: int = 0,
        q_index: int = 0,
    ):
        """One grid cell materialized as a
        :class:`~repro.core.placement.PlacementPlan` (spans, per-node burst
        schedules, hop costs); raises
        :class:`~repro.core.placement.PlacementError` where infeasible."""
        return self.placement_sweep(graph_index).plan(
            link_index, memory_index, q_index
        )

    def q_min(self, graph_index: int = 0) -> float:
        """The §4.4 storage minimum for one graph (objective='minimax')."""
        return self._one(self.qmins, "Q_min values")[graph_index]

    @property
    def q_mins(self) -> Tuple[float, ...]:
        return self._one(self.qmins, "Q_min values")

    def e_total(self, graph_index: int = 0) -> np.ndarray:
        """Optimal E_total per Q grid point (inf where infeasible)."""
        if self.sweeps is not None:
            return np.asarray(self.sweeps[graph_index].e_total)
        parts = self.partitions(graph_index)
        return np.array(
            [np.inf if p is None else p.e_total for p in parts]
        )

    def summary(self) -> str:
        return (
            f"Solution[{self.spec.objective}/{self.backend}] "
            f"{self.n_graphs} graph(s) × {max(len(self.q_values), 1)} Q"
        )


# ---------------------------------------------------------------------------
# Backends (self-registering)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class _SolveRequest:
    """Engine-resolved inputs handed to a backend's ``solve``."""

    graphs: Tuple[AnyExport, ...]
    cost: CostModel
    q_values: Tuple[Optional[float], ...]
    objective: str
    n_bursts: Optional[int]
    k_objective: str
    sharding: Optional[QGridSharding]
    interpret: Optional[bool]
    batched: bool
    backends: Tuple[str, ...]    # resolved concrete name, one per graph
    placement: Optional[PlacementSpec] = None


@register_backend(
    "numpy",
    objectives=("sum", "minimax", "exact_k"),
    supports_sharding=False,
    supports_csr=False,
    supports_dense=False,        # the reference DP walks the TaskGraph itself
    supports_placement=True,
    auto_eligible=False,
)
class NumpyBackend:
    """The numpy reference DP (paper §4.3–§4.4) — the bit-exactness oracle.

    Consumes :class:`TaskGraph` objects only (the incremental column sweep
    needs the graph structure); explicit array exports raise
    :class:`ExportMismatch`. The jit backends are tested against it.
    """

    name = "numpy"

    def solve(self, req: _SolveRequest) -> dict:
        from .partition import _optimal_k, _optimal_multi, q_min

        if req.placement is not None:
            from .placement import solve_placement_numpy

            return {
                "placements": tuple(
                    solve_placement_numpy(g, req.cost, req.placement)
                    for g in req.graphs
                )
            }
        if req.objective == "sum":
            return {
                "parts": tuple(
                    tuple(
                        _optimal_multi(g, req.cost, list(req.q_values))
                    )
                    for g in req.graphs
                )
            }
        if req.objective == "minimax":
            return {
                "qmins": tuple(float(q_min(g, req.cost)) for g in req.graphs)
            }
        return {
            "parts": tuple(
                (
                    _optimal_k(
                        g,
                        req.cost,
                        req.n_bursts,
                        req.q_values[0],
                        objective=req.k_objective,
                    ),
                )
                for g in req.graphs
            )
        }


class _JitBackend:
    """Shared dispatch for the jit engines (scan / pallas / mixed-auto).

    The engine hands over one resolved name per graph (``req.backends``);
    a mixed ``auto`` batch is split here into one call per backend — the
    only place a batch is grouped. The partition_jax implementations own
    upload caching and compilation."""

    name = "jit"

    def solve(self, req: _SolveRequest) -> dict:
        from . import partition_jax as pj

        if req.placement is not None:
            from .placement_jax import solve_placement_scan

            return {
                "placements": tuple(
                    solve_placement_scan(g, req.cost, req.placement)
                    for g in req.graphs
                )
            }
        pairs = tuple(zip(req.graphs, req.backends))
        if req.objective == "minimax":
            return {
                "qmins": tuple(
                    pj._q_min_pallas(g, req.cost, interpret=req.interpret)
                    if be == "pallas" else pj._q_min_scan(g, req.cost)
                    for g, be in pairs
                )
            }
        if req.objective == "exact_k":
            k_args = (req.n_bursts, req.q_values[0], req.k_objective)
            return {
                "parts": tuple(
                    (
                        pj._optimal_k_pallas(
                            g, req.cost, *k_args, interpret=req.interpret
                        )
                        if be == "pallas"
                        else pj._optimal_k_scan(g, req.cost, *k_args),
                    )
                    for g, be in pairs
                )
            }
        qs = list(req.q_values)
        sweeps: List[Any] = [None] * len(pairs)
        for be in dict.fromkeys(req.backends):
            idx = [k for k, b in enumerate(req.backends) if b == be]
            group = [req.graphs[k] for k in idx]
            if req.sharding is not None:
                devices = req.sharding.devices
                res = pj._sweep_jax_sharded(
                    group,
                    req.cost,
                    qs,
                    n_shards=req.sharding.n_shards,
                    devices=None if devices is None else list(devices),
                    backend=be,
                    interpret=req.interpret,
                )
            elif req.batched:
                res = pj._sweep_jax_batched(
                    group, req.cost, qs, backend=be, interpret=req.interpret
                )
            else:
                res = [
                    pj._sweep_jax(
                        group[0], req.cost, qs,
                        backend=be, interpret=req.interpret,
                    )
                ]
            for k, r in zip(idx, res):
                sweeps[k] = r
        return {"sweeps": tuple(sweeps)}


@register_backend(
    "scan",
    objectives=("sum", "minimax", "exact_k"),
    supports_sharding=True,
    supports_csr=False,
    supports_dense=True,
    supports_placement=True,     # the one-jit grid solver in placement_jax
)
class ScanBackend(_JitBackend):
    """The jitted ``lax.scan`` engine over dense :class:`GraphArrays`
    exports — Q-grid-heavy DSE on bounded-degree graphs, plus the scan
    re-expressions of the minimax and exact-K DPs (same columns, different
    combine — bit-identical to the numpy oracles on unroll-width graphs)."""

    name = "scan"


@register_backend(
    "pallas",
    objectives=("sum", "minimax", "exact_k"),
    supports_sharding=True,      # host-chunked Q sharding (see partition_jax)
    supports_csr=True,
    supports_dense=False,
)
class PallasBackend(_JitBackend):
    """The fused CSR column-sweep/DP kernel
    (:mod:`repro.kernels.partition_sweep`) over compressed
    :class:`GraphCSRArrays` exports — required for skewed-degree graphs
    (the 5458-task head count is ~1 GB dense, ~500 kB CSR). All three
    objectives are static kernel modes (the §4.4 minimax and exact-K
    combines ride the same slot-chunked column scan), each bit-identical
    to its numpy oracle in interpret mode."""

    name = "pallas"


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class Engine:
    """Resolve a :class:`PartitionSpec` and dispatch it to one backend.

    Stateless apart from its registry reference; the module-level
    :func:`default_engine` instance is what :func:`repro.api.solve` uses.
    """

    def __init__(self, registry: Optional[Dict[str, BackendInfo]] = None):
        self._registry = _REGISTRY if registry is None else registry

    # -- resolution ---------------------------------------------------------

    @staticmethod
    def _price_cost(spec: PartitionSpec, cost) -> CostModel:
        """Materialize the spec's priced CostModel.

        A calibrated source (anything with ``.cost_model(confidence)``, i.e.
        a :class:`repro.core.calibration.MeasuredCostTable` — duck-typed to
        keep the import lazy) is priced at ``spec.confidence``: each cut
        costs measured mean + z·sigma. A plain CostModel passes through —
        and combining it with ``confidence=`` is a typed error, because a
        datasheet model has no variance to price and the flag would
        silently do nothing.
        """
        if not isinstance(cost, CostModel) and hasattr(cost, "cost_model"):
            return cost.cost_model(spec.confidence)
        if spec.confidence is not None:
            raise SpecError(
                f"confidence= prices measured uncertainty and needs cost= "
                f"to be a MeasuredCostTable (repro.core.calibration); a "
                f"plain CostModel ({getattr(cost, 'name', cost)!r}) has no "
                f"variance to price"
            )
        return cost

    def _resolve_graphs(
        self, spec: PartitionSpec
    ) -> Tuple[Tuple[AnyExport, ...], CostModel]:
        if spec.config is not None:
            from ..configs import resolve_config
            from .calibration import measured_default
            from .layer_profile import default_cost_model, lower_config

            cfg = resolve_config(spec.config, smoke=spec.smoke)
            graphs = tuple(
                lower_config(cfg, batch=b, seq=s, kind=spec.kind)
                for (b, s) in spec.shapes
            )
            cost = spec.cost
            if cost is None:
                # an installed calibration is the default measured source, so
                # confidence= works on config-lowered specs without passing
                # the table explicitly
                cost = measured_default(spec.kind) or default_cost_model(spec.kind)
            return graphs, self._price_cost(spec, cost)
        if spec.cost is None:
            raise SpecError(
                "cost= is required for explicit graph specs (config-lowered "
                "specs default it per kind)"
            )
        graphs = (spec.graph,) if spec.graph is not None else spec.graphs
        for g in graphs:
            export_kind(g)  # typed error for non-graph inputs
        return graphs, self._price_cost(spec, spec.cost)

    def resolve_backend(
        self, spec: PartitionSpec, graphs: Sequence[AnyExport]
    ) -> Tuple[str, List[str]]:
        """(label, per-graph concrete names). ``label`` is the Solution's
        resolved-backend string — a concrete name, or ``"a+b"`` for a mixed
        ``auto`` batch (dispatched group-wise by the jit backend). Any
        explicitly named *registered* backend — including ones registered
        by downstream code — passes through directly."""
        if spec.backend != "auto":
            info = backend_info(spec.backend, self._registry)
            return info.name, [info.name] * len(graphs)
        if spec.placement is not None:
            # auto for placement: the first auto-eligible backend declaring
            # supports_placement (the scan grid solver in the default
            # registry) — the layout-based routing below is about per-graph
            # exports, which placement solves don't take
            cands = [
                b.name
                for b in self._registry.values()
                if b.auto_eligible and b.supports_placement
            ]
            if not cands:
                raise SpecError(
                    "no registered auto-eligible backend supports placement "
                    "solves; pass backend='numpy' or register one with "
                    "supports_placement"
                )
            return cands[0], [cands[0]] * len(graphs)
        per_graph = [
            resolve_jit_backend(g, "auto", spec.objective, self._registry)
            for g in graphs
        ]
        names = sorted(set(per_graph))
        return "+".join(names), per_graph

    # -- solve --------------------------------------------------------------

    def solve(self, spec: PartitionSpec) -> Solution:
        """The one entry point: validate, resolve, capability-check,
        dispatch, wrap. See the module docstring for the dispatch rules."""
        if not isinstance(spec, PartitionSpec):
            raise SpecError(
                f"Engine.solve takes a PartitionSpec, got "
                f"{type(spec).__name__}"
            )
        graphs, cost = self._resolve_graphs(spec)
        label, per_graph = self.resolve_backend(spec, graphs)

        infos = [backend_info(n, self._registry) for n in set(per_graph)]
        for info in infos:
            if spec.objective not in info.objectives:
                raise UnsupportedObjective(
                    f"backend {info.name!r} does not implement objective "
                    f"{spec.objective!r} (supported: "
                    f"{sorted(info.objectives)}); backends implementing it: "
                    f"{sorted(b.name for b in self._registry.values() if spec.objective in b.objectives)}"
                )
            if spec.sharding is not None and not info.supports_sharding:
                raise SpecError(
                    f"backend {info.name!r} does not support Q-grid "
                    f"sharding; use a backend registered with "
                    f"supports_sharding"
                )
            if spec.placement is not None and not info.supports_placement:
                raise SpecError(
                    f"backend {info.name!r} does not implement placement "
                    f"solves; backends with supports_placement: "
                    f"{sorted(b.name for b in self._registry.values() if b.supports_placement)}"
                )
        if spec.placement is not None:
            # backend-independent: the per-node column sweeps walk the graph
            # structure, so placement consumes TaskGraphs only
            for g in graphs:
                if not isinstance(g, TaskGraph):
                    raise ExportMismatch(
                        "placement= needs the TaskGraph (the per-node "
                        "column sweeps walk its structure); pass the graph "
                        "rather than a pre-exported layout"
                    )
        if spec.objective == "exact_k":
            # backend-independent: reconstructed bursts are priced on the
            # graph, so exact_k consumes TaskGraphs only — reject here, not
            # deep inside a backend after a full solve
            for g in graphs:
                if not isinstance(g, TaskGraph):
                    raise ExportMismatch(
                        "objective='exact_k' needs the TaskGraph to price "
                        "the reconstructed bursts; pass the graph rather "
                        "than a pre-exported layout"
                    )
        for g, name in zip(graphs, per_graph):
            _check_export(backend_info(name, self._registry), g,
                          self._registry)

        req = _SolveRequest(
            graphs=graphs,
            cost=cost,
            q_values=spec.q_values,
            objective=spec.objective,
            n_bursts=spec.n_bursts,
            k_objective=spec.k_objective,
            sharding=spec.sharding,
            interpret=spec.interpret,
            batched=spec.batched,
            backends=tuple(per_graph),
            placement=spec.placement,
        )
        with TRACER.span(
            "engine.solve",
            cat="engine",
            pid=PID_SOLVER,
            objective=spec.objective,
            backend=label,
            graphs=len(graphs),
            q_points=len(spec.q_values),
        ):
            if "+" in label:
                # mixed auto batch: the jit dispatcher groups per backend
                payload = _JitBackend().solve(req)
            else:
                payload = backend_info(label, self._registry).factory().solve(req)
        return Solution(
            spec=spec,
            backend=label,
            graphs=graphs,
            cost=cost,
            q_values=spec.q_values,
            **payload,
        )


_DEFAULT_ENGINE = Engine()


def default_engine() -> Engine:
    """The process-wide engine over the global backend registry."""
    return _DEFAULT_ENGINE
