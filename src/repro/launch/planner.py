"""Online plan consumption + offline table building (CLI).

:class:`ServePlanner` is the request-path face of a
:class:`repro.core.plan_table.PlanTable`: every query is an O(1) lookup —
no DP solve, no graph lowering — and the planner keeps counters the serving
regression tests pin ("zero partitioner solves on the request path").

Besides the serving plan itself, the stored cut points feed the other three
julienne consumers *without re-solving*:

* :meth:`ServePlanner.offload_plan` — price the tabulated bounds as an
  activation-offload schedule (:func:`repro.core.offload.price_offload_bounds`);
* :meth:`ServePlanner.remat_plan` — price them as remat segment boundaries
  (:func:`repro.core.remat_policy.remat_from_bounds`);
* :meth:`ServePlanner.pipeline_cuts` — the interior segment ends as
  pipeline-stage cuts.

:func:`request_cycles` maps a looked-up plan onto a request's token steps:
each step (prefill or one decode) is one traversal of the activation graph
and costs the plan's ``e_total``; consecutive steps are greedily grouped so
each cycle (E_s + steps) fits the energy budget. This is O(n) bookkeeping,
not a partitioner solve — the *intra*-step segmentation already fits Q by
construction of the table, so a single step over budget still forms a legal
one-step cycle.

CLI (offline build)::

    python -m repro.launch.planner --arch qwen3-4b \
        --buckets 2x24,2x48 --q-points 16 --out plan_qwen.npz

builds the Q grid from the buckets' own Q_min .. E_total(whole-app) range
(plus an unbounded entry), solves the whole grid in one batched engine call,
and writes the versioned table. ``--shards N`` shards the solve across N
devices (byte-identical output; see :mod:`repro.launch.dse`), ``--extend``
grows an existing table in place without re-solving tabulated cells, and
``--probe K`` re-validates K random cells against the live engine after the
build (the load-time staleness check).
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Dict, List, Optional, Tuple, Union

import numpy as np

from ..configs import resolve_config as _resolve_config
from ..configs.base import ModelConfig
from ..core.layer_profile import lower_config, profile_model, build_activation_graph
from ..core.offload import OffloadPlan, price_offload_bounds
from ..core.partition import Infeasible, whole_app_partition, within_budget
from ..core.plan_table import (
    PlanTable,
    PlanTableError,
    SegmentPlan,
    build_plan_table,
    probe_plan_table,
    _default_cost,
)
from ..core.remat_policy import RematPlan, remat_from_bounds
from .compile_cache import enable_compile_cache

__all__ = [
    "ADMISSION_OUTCOMES",
    "ServePlanner",
    "as_planner",
    "request_cycles",
    "build_table_for_arch",
    "derive_q_grid",
    "lower_buckets",
]


def resolve_config(arch: str, smoke: bool = True) -> ModelConfig:
    """Smoke-first view of the shared :func:`repro.configs.resolve_config`
    (the launch CLIs default to the smoke registry; serve.py, the DSE CLI,
    the plan-table builders, and the façade all resolve through the same
    helper)."""
    return _resolve_config(arch, smoke=smoke)


#: Admission-control outcomes the traffic harness reports per request.
ADMISSION_OUTCOMES = ("admitted", "deferred", "rejected")


def _fresh_planner_stats() -> Dict[str, object]:
    return {
        "lookups": 0,
        "hits": 0,       # lookups answered from the table
        "misses": 0,     # UnknownBucketError / Infeasible budget
        "admitted": 0,   # admission-control outcomes (see record_admission)
        "deferred": 0,
        "rejected": 0,
        "by_bucket": {},  # "BATCHxSEQ" -> hit count
    }


class ServePlanner:
    """O(1) plan lookups for the serving loop, with observability counters.

    ``stats`` carries per-bucket hit/miss counters (every :meth:`plan_for`
    call) plus the fleet admission counters the continuous-traffic harness
    reports through :meth:`record_admission`. Counters are process-lifetime
    for the planner instance; consumers that compare across runs must
    snapshot-and-diff (or call :meth:`reset_stats` for a fresh baseline).
    """

    def __init__(self, table: PlanTable) -> None:
        self.table = table
        self.stats: Dict[str, object] = _fresh_planner_stats()

    def reset_stats(self) -> None:
        """Zero all counters (test isolation / per-run baselines)."""
        self.stats = _fresh_planner_stats()

    @classmethod
    def from_file(
        cls,
        path: str,
        *,
        probe: Optional[Union[ModelConfig, str]] = None,
        probe_k: Optional[int] = 4,
        probe_seed: int = 0,
        probe_cost=None,
    ) -> "ServePlanner":
        """Load a table; with ``probe`` (a ModelConfig or registry arch name),
        re-validate ``probe_k`` random cells against the live engine first —
        the load-time staleness check (raises
        :class:`repro.core.plan_table.StaleTableError` on any bit drift).
        ``probe_cost`` must name the table's cost model when it was built
        with a non-default one (defaults per table kind)."""
        table = PlanTable.load(path)
        if probe is not None:
            probe_plan_table(table, probe, k=probe_k, seed=probe_seed,
                             cost=probe_cost)
        return cls(table)

    @property
    def e_startup(self) -> float:
        return self.table.e_startup

    def plan_for(
        self, batch: int, seq: int, energy_budget: Optional[float] = None
    ) -> SegmentPlan:
        """Bucket the request shape and return the precomputed plan.

        A successful lookup counts as a *hit* (per-bucket, under the
        ``"BATCHxSEQ"`` key of the covering bucket); an untabulated shape or
        a budget below the Q grid counts as a *miss* and re-raises.
        """
        self.stats["lookups"] += 1
        try:
            plan = self.table.lookup(batch, seq, energy_budget)
        except (PlanTableError, Infeasible):
            self.stats["misses"] += 1
            raise
        self.stats["hits"] += 1
        key = f"{plan.batch}x{plan.seq_bucket}"
        by = self.stats["by_bucket"]
        by[key] = by.get(key, 0) + 1
        return plan

    @property
    def hit_rate(self) -> float:
        """Fraction of lookups answered from the table (0.0 before any)."""
        n = self.stats["lookups"]
        return self.stats["hits"] / n if n else 0.0

    def record_admission(self, outcome: str) -> None:
        """Fleet admission observability: the traffic harness reports each
        request's outcome ('admitted' | 'deferred' | 'rejected') here so the
        admission counters live beside the lookup counters they gate on."""
        if outcome not in ADMISSION_OUTCOMES:
            raise ValueError(
                f"unknown admission outcome {outcome!r}; "
                f"expected one of {ADMISSION_OUTCOMES}"
            )
        self.stats[outcome] += 1

    # -- derived consumers (no DP solve; bounds come from the table) --------

    def _memory_plan(
        self, cfg: ModelConfig, batch: int, seq: int, hbm_budget: float
    ) -> Tuple[SegmentPlan, list, object]:
        if self.table.kind != "memory":
            raise PlanTableError(
                f"offload/remat derivation needs a kind='memory' table, "
                f"this one is kind={self.table.kind!r}"
            )
        if cfg.name != self.table.arch:
            raise PlanTableError(
                f"table was built for {self.table.arch!r}, not {cfg.name!r}"
            )
        plan = self.plan_for(batch, seq, hbm_budget)
        profiles, long_lived = profile_model(cfg, plan.batch, plan.seq_bucket)
        mem_graph = build_activation_graph(profiles, long_lived, kind="memory")
        return plan, profiles, mem_graph

    def offload_plan(
        self, cfg: ModelConfig, batch: int, seq: int, hbm_budget: float
    ) -> OffloadPlan:
        """Tabulated bounds priced as a PCIe offload schedule."""
        plan, profiles, mem_graph = self._memory_plan(cfg, batch, seq, hbm_budget)
        return price_offload_bounds(
            cfg.name, profiles, mem_graph, list(plan.bounds), hbm_budget
        )

    def remat_plan(
        self, cfg: ModelConfig, batch: int, seq: int, hbm_budget: float
    ) -> RematPlan:
        """Tabulated bounds priced as remat segment boundaries."""
        plan, profiles, mem_graph = self._memory_plan(cfg, batch, seq, hbm_budget)
        return remat_from_bounds(
            cfg.name, profiles, mem_graph, list(plan.bounds), hbm_budget
        )

    def pipeline_cuts(
        self, batch: int, seq: int, energy_budget: Optional[float] = None
    ) -> Tuple[int, ...]:
        """Interior segment ends of the looked-up plan — stage cut points."""
        return self.plan_for(batch, seq, energy_budget).cut_points


def as_planner(obj: Union[str, PlanTable, ServePlanner]) -> ServePlanner:
    """Coerce a path / table / planner into a ServePlanner."""
    if isinstance(obj, ServePlanner):
        return obj
    if isinstance(obj, PlanTable):
        return ServePlanner(obj)
    if isinstance(obj, str):
        return ServePlanner.from_file(obj)
    raise TypeError(f"cannot make a ServePlanner from {type(obj).__name__}")


def request_cycles(
    n_steps: int,
    step_energy: float,
    energy_budget: Optional[float] = None,
    e_startup: float = 0.0,
) -> List[Tuple[int, int]]:
    """Greedy grouping of token steps into energy-bounded cycles (1-based).

    Uses the shared solver tolerance (:func:`within_budget`) so a request
    whose steps exactly fill the budget is not split by float noise. With no
    budget the whole request is one cycle; a single step that alone exceeds
    the budget still forms its own cycle (its interior segmentation fits Q by
    table construction).
    """
    if n_steps <= 0:
        return []
    if energy_budget is None:
        return [(1, n_steps)]
    bounds: List[Tuple[int, int]] = []
    start = 1
    acc = e_startup + step_energy  # step `start` is always admitted
    for k in range(2, n_steps + 1):
        if within_budget(acc + step_energy, energy_budget):
            acc += step_energy
        else:
            bounds.append((start, k - 1))
            start = k
            acc = e_startup + step_energy
    bounds.append((start, n_steps))
    return bounds


def lower_buckets(
    cfg: ModelConfig, shape_buckets: List[Tuple[int, int]], kind: str = "time"
):
    """One lowered activation graph per (batch, seq) bucket."""
    return [lower_config(cfg, batch=b, seq=s, kind=kind)
            for (b, s) in shape_buckets]


def derive_q_grid(graphs, cm, n_q: int = 16) -> List[Optional[float]]:
    """The standard offline Q grid for a bucket set: geometric from
    [min over buckets of Q_min, max whole-app E_total × 1.05] plus one
    unbounded entry, so every bucket has both fully-julienned and
    single-cycle plans tabulated.

    Q_min goes through the façade's minimax objective (``backend="auto"``),
    so the build path picks the same registry backend — scan or the Pallas
    kernel's minimax mode — that the rest of the table build uses, instead
    of hardwiring the numpy DP (which would dense-walk graphs the registry
    routes to the CSR kernel).
    """
    from ..api import PartitionSpec, solve  # lazy: avoid import cycle

    lo = min(
        solve(PartitionSpec(graph=g, cost=cm, objective="minimax")).q_min()
        for g in graphs
    )
    hi = max(whole_app_partition(g, cm).e_total * 1.05 for g in graphs)
    qs: List[Optional[float]] = list(np.geomspace(lo, max(hi, lo * 1.0001), n_q))
    qs.append(None)
    return qs


def build_table_for_arch(
    arch: str,
    shape_buckets: List[Tuple[int, int]],
    n_q: int = 16,
    *,
    smoke: bool = True,
    kind: str = "time",
    cache_dir: Optional[str] = None,
    n_shards: Optional[int] = None,
) -> PlanTable:
    """Convenience offline build: derive the Q grid from the buckets
    (:func:`derive_q_grid`) and solve the whole grid in one batched façade
    call — or, with ``n_shards``, one Q-sharded multi-device call
    (``build_plan_table(..., sharding=QGridSharding(...))``; same bytes
    either way).
    """
    cfg = resolve_config(arch, smoke)
    cm = _default_cost(kind)
    graphs = lower_buckets(cfg, shape_buckets, kind)
    qs = derive_q_grid(graphs, cm, n_q)
    sharding = None
    if n_shards is not None:
        from ..api import QGridSharding
        from .mesh import shard_devices  # jax device state: keep import local

        # shard_devices is None on device-starved hosts (sequential fallback)
        sharding = QGridSharding(n_shards, shard_devices(n_shards))
    return build_plan_table(
        cfg, shape_buckets, qs, kind=kind, cost=cm, cache_dir=cache_dir,
        graphs=graphs, sharding=sharding,
    )


def _parse_buckets(text: str) -> List[Tuple[int, int]]:
    """Parse comma-separated ``BATCHxSEQ`` bucket tokens (e.g. ``2x24,4x48``).

    Each token must be two positive integers joined by an ``x`` (case
    insensitive). Malformed tokens raise a ValueError naming the offending
    entry — previously ``"2x"`` or ``"2x24,48"`` died with an opaque
    "not enough values to unpack".
    """
    out = []
    for part in text.split(","):
        token = part.strip().lower()
        batch_s, sep, seq_s = token.partition("x")
        try:
            if not sep or not batch_s or not seq_s:
                raise ValueError
            bucket = (int(batch_s), int(seq_s))
            if bucket[0] <= 0 or bucket[1] <= 0:
                raise ValueError
        except ValueError:
            raise ValueError(
                f"malformed bucket {part.strip()!r} in {text!r}: expected "
                f"BATCHxSEQ with positive integers (e.g. 2x24)"
            ) from None
        out.append(bucket)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--buckets", default="2x24,2x48",
                    help="comma-separated BATCHxSEQ buckets, e.g. 2x24,4x48")
    ap.add_argument("--q-points", type=int, default=None,
                    help="geometric Q grid size, default 16 (an unbounded "
                    "point is added; fresh builds only)")
    ap.add_argument("--kind", choices=("time", "memory"), default=None,
                    help="cost interpretation, default time (fresh builds "
                    "only — an extension keeps the base table's kind)")
    ap.add_argument("--out", required=True, help="output .npz path")
    ap.add_argument("--full", action="store_true",
                    help="use the full config instead of the smoke config")
    ap.add_argument("--shards", type=int, default=None,
                    help="shard the solve across this many devices "
                    "(byte-identical to the single-host build)")
    ap.add_argument("--extend", action="store_true",
                    help="extend the existing table at --out with any "
                    "missing --buckets instead of rebuilding it")
    ap.add_argument("--probe", type=int, default=0,
                    help="re-validate this many random cells against the "
                    "live engine after the build")
    args = ap.parse_args(argv)
    enable_compile_cache()

    buckets = _parse_buckets(args.buckets)
    t0 = time.time()
    if args.extend:
        if args.kind is not None or args.q_points is not None:
            ap.error("--kind/--q-points are fixed by the base table; "
                     "not valid with --extend")
        from .dse import extend_for_arch  # lazy: avoids a module cycle

        table = extend_for_arch(
            args.out, args.arch, buckets, smoke=not args.full,
            n_shards=args.shards,
        )
        verb = "extended"
    else:
        table = build_table_for_arch(
            args.arch, buckets, args.q_points or 16, smoke=not args.full,
            kind=args.kind or "time", n_shards=args.shards,
        )
        verb = "built"
    table.save(args.out)
    shard_note = "" if args.shards is None else f" ({args.shards} shards)"
    print(f"[planner] {verb} {table.summary()} in {time.time() - t0:.2f}s"
          f"{shard_note} → {args.out}")
    if args.probe:
        n = probe_plan_table(
            table, resolve_config(args.arch, smoke=not args.full), k=args.probe
        )
        print(f"[planner]   probe: {n} cells re-validated — clean")
    for b, (batch, seq) in enumerate(table.buckets()):
        plan = table.plan_at(b, table.q_index(None))
        print(f"[planner]   {plan.summary()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
