"""Julienning: memory-aware partitioning of sequential task graphs.

The paper's contribution (Gomez et al., 2021) as a composable library:
specification model (:mod:`.graph`), burst cost model (:mod:`.burst`),
optimal partitioning + storage minimization (:mod:`.partition`), the
burst execution runtime (:mod:`.runtime`), and the TPU-side applications
of the same optimizer (:mod:`.remat_policy`, :mod:`.offload`,
:mod:`.pipeline`).
"""

from .burst import BurstDetail, ColumnSweep, burst_cost, burst_detail
from .cost import (
    CostModel,
    LinearTransfer,
    PAPER_FRAM_MODEL,
    cost_scalars,
    paper_fram_model,
    tpu_host_offload_model,
    tpu_pipeline_model,
    tpu_remat_model,
)
from .engine import (
    Engine,
    EngineError,
    ExportMismatch,
    PartitionSpec,
    QGridSharding,
    Solution,
    SpecError,
    UnsupportedObjective,
    backend_names,
    default_engine,
    register_backend,
)
from .graph import (
    GraphArrays,
    GraphBuilder,
    GraphCSRArrays,
    Packet,
    Task,
    TaskGraph,
    dense_export_nbytes,
    stack_csr_arrays,
    stack_graph_arrays,
)
from .layer_profile import (
    build_activation_graph,
    default_cost_model,
    external_inputs,
    lower_config,
    lower_zoo,
    memory_cost_model,
    profile_model,
)
from .partition import (
    BUDGET_ABS,
    BUDGET_REL,
    Infeasible,
    Partition,
    brute_force_partition,
    dijkstra_partition,
    q_min,
    q_min_bruteforce,
    single_task_partition,
    whole_app_partition,
    within_budget,
)
from .placement import (
    PLACEMENT_TABLE_VERSION,
    LinkModel,
    NodeSpec,
    PlacementError,
    PlacementPlan,
    PlacementSpec,
    PlacementSweep,
    PlacementTable,
    exhaustive_placement,
    placement_inputs,
    solve_placement_numpy,
)
from .plan_table import (
    PLAN_TABLE_VERSION,
    PlanTable,
    PlanTableError,
    SegmentPlan,
    StaleTableError,
    UnknownBucketError,
    build_plan_table,
    config_fingerprint,
    extend_plan_table,
    probe_plan_table,
)
from .runtime import (
    COMMIT_STATS,
    BurstRuntime,
    DirNVM,
    ExecutionStats,
    MemoryNVM,
    PowerFailure,
    execute_atomic,
    reset_commit_stats,
)

__all__ = [k for k in dir() if not k.startswith("_")]

# The jitted partitioning engine imports jax; load it lazily (PEP 562) so
# pure-numpy analysis (`import repro.core`) stays jax-free.
_JAX_EXPORTS = (
    "JaxSweep",
    "shard_q_grid",
    "sweep_from_columns",
)
__all__ += list(_JAX_EXPORTS)


def __getattr__(name):
    if name in _JAX_EXPORTS:
        from . import partition_jax

        return getattr(partition_jax, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
