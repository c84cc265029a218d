"""The public Julienning API: declarative specs in, solutions out.

Everything the repo can solve — the paper's energy-bounded partition DP, the
§4.4 storage minimax, the exact-K pipeline DP, single graphs, zoo batches,
Q-grid device sharding, numpy/scan/Pallas backends — goes through one call::

    from repro.api import PartitionSpec, solve

    sol = solve(PartitionSpec(graph=g, cost=cm, q_max=132e-3))
    part = sol.partition()                 # a repro.core.Partition

    # the whole design space, batched and sharded
    sol = solve(PartitionSpec(
        config="qwen3-4b", shapes=((2, 24), (2, 48)), smoke=True,
        q_grid=(1e-3, 5e-3, None), sharding=QGridSharding(n_shards=8),
    ))
    sol.sweeps[0].e_total                  # per-Q optima, first bucket

    # §4.4 / pipeline objectives are just another axis of the spec
    solve(PartitionSpec(graph=g, cost=cm, objective="minimax")).q_min()

    # swarm placement: cut the chain across N harvesting nodes, sweeping
    # link bandwidth × node memory × node budget in one batched call
    sol = solve(PartitionSpec(graph=g, cost=cm, placement=PlacementSpec(
        nodes=3, links=tuple(LinkModel(bandwidth_mbps=b)
                             for b in range(900, 3400, 100)))))
    sol.placement_plan(link_index=0).summary()
    solve(PartitionSpec(graph=g, cost=cm, objective="exact_k",
                        n_bursts=4, k_objective="max")).partition()

This is the one way into the solver: the numpy reference DP
(``backend="numpy"``, the oracle the jit backends are tested against), the
``lax.scan`` engine and the Pallas sweep kernel are all reached through
:func:`solve`, and ``backend="auto"`` picks scan or Pallas per graph in one
place (:func:`repro.core.engine.resolve_jit_backend`).

Backends self-register with capability flags; third-party code can add one::

    from repro.api import register_backend

    @register_backend("mine", objectives=("sum",), supports_dense=True)
    class MyBackend:
        def solve(self, req): ...

and address it with ``PartitionSpec(backend="mine")``.
"""

from __future__ import annotations

from .core.calibration import (
    CalibrationError,
    MeasuredCostTable,
    clear_measured_defaults,
    install_measured_default,
    use_measured,
)
from .core.engine import (
    OBJECTIVES,
    BackendInfo,
    Engine,
    EngineError,
    ExportMismatch,
    PartitionSpec,
    QGridSharding,
    Solution,
    SpecError,
    UnsupportedObjective,
    backend_info,
    backend_names,
    default_engine,
    export_kind,
    register_backend,
)
from .core.partition import Infeasible
from .core.placement import (
    LinkModel,
    NodeSpec,
    PlacementError,
    PlacementPlan,
    PlacementSpec,
    PlacementSweep,
    PlacementTable,
)

__all__ = [
    "OBJECTIVES",
    "BackendInfo",
    "CalibrationError",
    "Engine",
    "EngineError",
    "ExportMismatch",
    "Infeasible",
    "LinkModel",
    "MeasuredCostTable",
    "NodeSpec",
    "PartitionSpec",
    "PlacementError",
    "PlacementPlan",
    "PlacementSpec",
    "PlacementSweep",
    "PlacementTable",
    "QGridSharding",
    "Solution",
    "SpecError",
    "UnsupportedObjective",
    "backend_info",
    "backend_names",
    "clear_measured_defaults",
    "default_engine",
    "export_kind",
    "install_measured_default",
    "register_backend",
    "solve",
    "use_measured",
]


def solve(spec: PartitionSpec = None, **kwargs) -> Solution:
    """Solve a :class:`PartitionSpec` on the default engine.

    Accepts a prebuilt spec (positionally or as ``spec=``) or the spec's
    keyword arguments directly (``solve(graph=g, cost=cm, q_max=0.1)`` ≡
    ``solve(PartitionSpec(graph=g, cost=cm, q_max=0.1))``).
    """
    if spec is None:
        spec = PartitionSpec(**kwargs)
    elif kwargs:
        raise SpecError("pass a PartitionSpec or keywords, not both")
    return default_engine().solve(spec)
