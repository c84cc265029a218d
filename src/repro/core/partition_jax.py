"""JAX-native, jit-compiled burst partitioning engine (paper §4.3–§4.4).

Reached through the :mod:`repro.api` façade: the ``scan`` and ``pallas``
registry backends (:mod:`repro.core.engine`) dispatch into the private
implementations here, and the historical public entry points (``sweep_jax``,
``sweep_jax_batched``, ``sweep_jax_sharded``, ``optimal_partition_jax``)
survive as thin :class:`DeprecationWarning` shims over the same code.

This is the batched re-expression of the two numpy reference paths:

* the incremental column sweep (:class:`repro.core.burst.ColumnSweep`)
  becomes a ``lax.scan`` over tasks, carrying the live column ``E⟨·,j⟩`` and
  applying each task's three piecewise-constant updates as masked adds over
  the dense arrays exported by :meth:`TaskGraph.to_arrays`;

* the forward DAG-DP (:func:`repro.core.partition.optimal_partition_multi`)
  rides in the same scan, broadcast across an arbitrary Q_max grid — one
  compiled kernel juliennes the whole design space in one shot.

A second ``vmap`` layer batches across *graphs*: :func:`sweep_jax_batched`
takes padded exports of different applications (the whole model zoo, lowered
via :func:`repro.core.layer_profile.lower_config`) and solves them together.

Two interchangeable backends drive the same host API (``backend=`` on
:func:`sweep_jax` / :func:`sweep_jax_batched` / :func:`optimal_partition_jax`):

* ``"scan"`` — the ``lax.scan`` engine below over the dense
  :meth:`TaskGraph.to_arrays` export. Best for Q-grid-heavy DSE on graphs
  whose read degree is bounded (the padded ``(N, R)`` rectangle stays small).
* ``"pallas"`` — the fused column-sweep/DP kernel in
  :mod:`repro.kernels.partition_sweep` over the compressed
  :meth:`TaskGraph.to_csr_arrays` export. Required for skewed-degree graphs:
  the full 5458-task head-count application has R ≈ 5452 (its sort task reads
  every score packet), which would dense-export ~1 GB; the CSR slot layout is
  ~400 kB and the kernel applies slot contributions in-register.
* ``"auto"`` (default) — picks "pallas" when the dense export would exceed
  ``_AUTO_DENSE_BYTES`` (or when handed a ``GraphCSRArrays``), else "scan".

Serving-path behavior (ROADMAP "hoist dtype handling"): graph uploads are
device-cached per export object, cost scalars per cost model, and both
backends' jitted callables are shape-keyed — so a serving loop re-solving the
same application across Q grids does no per-request re-trace, re-upload, or
global-config churn beyond the thread-local ``enable_x64`` flag entered once
per call (asserted by the no-retrace test in tests/test_partition_sweep.py).

The per-column recurrence, identical to :mod:`.burst` (all 1-based):

    E⟨i,j⟩ = E⟨i,j-1⟩ + E_task(j) + S(j)
           + Σ_{p ∈ reads(j)}  E_r(p) · [i > l_j(p)]            (new loads)
           - Σ_{p ∈ reads(j)}  E_w(p) · [l_∞(p) = j]
                                      · [1 ≤ writer(p)]
                                      · [i ≤ writer(p)]          (store freed)
    E⟨j,j⟩ = E_s + Σ_{p ∈ reads(j)} E_r(p) + E_task(j) + S(j)

with ``S(j) = Σ_{p ∈ writes(j), l_∞(p) > j} E_w(p)``, and the fused DP:

    dp[q, j]  = min_{1 ≤ i ≤ j, E⟨i,j⟩ ≤ Q_max[q]} dp[q, i-1] + E⟨i,j⟩

Numerics run in float64 under :func:`jax.enable_x64` so results
match the numpy oracles to ~ulp; infeasibility uses the same relative budget
tolerance as the numpy path. Tie-breaking (argmin picks the smallest burst
start) also matches, so reconstructed bounds agree bit-for-bit on generic
cost vectors.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.metrics import METRICS
from ..obs.trace import PID_SOLVER, TRACER
from ._cache import weak_id_cache
from ._deprecation import warn_legacy
from .cost import CostModel, cost_scalars
from .engine import ExportMismatch, resolve_jit_backend
from .graph import (
    GraphArrays,
    GraphCSRArrays,
    TaskGraph,
    stack_graph_arrays,
)
from .partition import (
    BUDGET_ABS,
    BUDGET_REL,
    Infeasible,
    Partition,
    _partition_from_bounds,
)

__all__ = [
    "JaxSweep",
    "sweep_jax",
    "sweep_jax_batched",
    "sweep_jax_sharded",
    "shard_q_grid",
    "optimal_partition_jax",
    "sweep_from_columns",
    "cost_scalars",
]

# Budget tolerance: the single source of truth lives in partition.py
# (BUDGET_REL/BUDGET_ABS) so every solver path masks identically.
_REL = BUDGET_REL
_ABS = BUDGET_ABS

# Read-slot count above which the scan backend's column update switches from
# the order-preserving unrolled loop to one masked 2-D reduction.
_UNROLL_MAX = 8

# backend="auto": route to the CSR/Pallas backend once the dense export would
# cross this size (the full head-count graph is ~1 GB dense, ~400 kB CSR).
_AUTO_DENSE_BYTES = 32 << 20

# Trace-count regression hooks (incremented at trace time only; see the
# no-retrace test in tests/test_partition_sweep.py). Registry-backed
# (repro.obs.metrics) but still plain dicts to consumers.
TRACE_COUNT = METRICS.counter_dict(
    "partition_jax.trace_count", ("dp_sweep", "qmin_sweep", "exactk_sweep")
)

# Host-side solve counters (incremented per engine entry, cached or not):
# the plan-table serving tests pin "zero partitioner solves on the request
# path" against these, and the DSE tests pin "extending an untouched table
# never re-solves existing cells".
SOLVE_COUNT = METRICS.counter_dict(
    "partition_jax.solve_count",
    (
        "sweep_jax",
        "sweep_jax_batched",
        "sweep_jax_sharded",
        "q_min_scan",
        "optimal_k_scan",
        "q_min_pallas",
        "optimal_k_pallas",
    ),
)


# ---------------------------------------------------------------------------
# The jitted engine
# ---------------------------------------------------------------------------


def _sweep_inputs(ga: dict, cost_vec):
    """Per-column scan inputs shared by every DP variant (sum / minimax /
    exact-K): slot transfer costs under the cost model, the store term S(j),
    and the stacked ``xs`` the scans consume. Returns ``(xs, e_s)``.
    """
    e_s, r_c0, r_c1, w_c0, w_c1 = (cost_vec[k] for k in range(5))
    N = ga["e_task"].shape[0]
    W = ga["write_bytes"].shape[1]

    # Per-slot transfer costs under this cost model (padding contributes 0).
    read_cost = ga["read_valid"] * (r_c0 * ga["read_c0w"] + r_c1 * ga["read_bytes"])
    # E_w of the *read* packet — charged back when the burst absorbs both the
    # writer and the last reader, making the intermediate store unnecessary.
    read_free = ga["read_valid"] * (w_c0 * ga["read_c0w"] + w_c1 * ga["read_bytes"])
    write_cost = ga["write_valid"] * (
        w_c0 * ga["write_c0w"] + w_c1 * ga["write_bytes"]
    )

    # S(j): accumulated write-slot by write-slot (left-to-right) so the
    # float64 rounding sequence is identical to ColumnSweep's Python sum —
    # that keeps dp tables (and argmin tie-breaks) bit-compatible with numpy.
    j_col = jnp.arange(1, N + 1)
    store_add = jnp.zeros(N)
    for w in range(W):
        keep = ga["write_linf"][:, w] > j_col
        store_add = jnp.where(keep, store_add + write_cost[:, w], store_add)

    xs = (
        jnp.arange(1, N + 1),
        ga["e_task"],
        store_add,
        read_cost,
        read_free,
        ga["read_lt"],
        ga["read_writer"],
        ga["read_linf"],
    )
    return xs, e_s


def _advance_column(col, xs, i_idx, e_s, R):
    """One task's updates to the live column E⟨·,j⟩ (identical op order to
    the numpy :class:`~repro.core.burst.ColumnSweep`, so columns — and hence
    every DP variant's tie-breaks — stay bit-compatible).

    1) extend all existing bursts ⟨i, j-1⟩ with task j. For small R the
    read-slot loop is unrolled at trace time and applies the adds in the
    same order as the numpy sweep, keeping columns bit-identical (so argmin
    tie-breaks — and hence bounds — match numpy exactly). Wide-reader graphs
    (R > ``_UNROLL_MAX``, e.g. head-count's 5k-reader sort task) use one
    masked 2-D reduction instead: same values to ~ulp (XLA's FMA contraction
    already perturbs those graphs anyway). 2) start the new single-task
    burst ⟨j,j⟩.
    """
    j, e_j, s_j, rcost, rfree, rlt, rwriter, rlinf = xs
    prev = (i_idx >= 1) & (i_idx < j)
    col = jnp.where(prev, col + (e_j + s_j), col)
    if R <= _UNROLL_MAX:
        sum_er = e_j * 0.0
        for r in range(R):
            col = jnp.where(prev & (i_idx > rlt[r]), col + rcost[r], col)
            freed = (rlinf[r] == j) & (rwriter[r] >= 1)
            col = jnp.where(
                prev & freed & (i_idx <= rwriter[r]), col - rfree[r], col
            )
            sum_er = sum_er + rcost[r]
    else:
        loads = (rcost[None, :] * (i_idx[:, None] > rlt[None, :])).sum(1)
        freed = (
            rfree[None, :]
            * ((rlinf == j) & (rwriter >= 1))[None, :]
            * (i_idx[:, None] <= rwriter[None, :])
        ).sum(1)
        col = jnp.where(prev, col + loads - freed, col)
        sum_er = rcost.sum()
    col = col.at[j].set(e_s + sum_er + e_j + s_j)
    return col


def _dp_sweep(ga: dict, n_tasks, cost_vec, qs):
    """Column sweep + multi-Q DP + bounds reconstruction for one graph.

    ``ga`` holds the GraphArrays fields as jnp arrays of static shape
    (N,), (N,R), (N,W); ``n_tasks`` is a traced scalar (≤ N); ``qs`` is the
    (nq,) Q_max grid. Returns (dp, parent, e_total, feasible, starts).
    """
    TRACE_COUNT["dp_sweep"] += 1
    N = ga["e_task"].shape[0]
    R = ga["read_bytes"].shape[1]
    nq = qs.shape[0]
    i_idx = jnp.arange(N + 1)
    xs, e_s = _sweep_inputs(ga, cost_vec)

    q_budget = qs * (1.0 + _REL) + _ABS
    i_tail = i_idx[1:]  # i = 1..N
    i_tail32 = i_tail.astype(jnp.int32)

    def make_step(Wc):
        """Scan body for the chunk whose steps all have j ≤ Wc: candidate
        tables are (nq, Wc) instead of (nq, N) — early chunks pay only for
        the bursts that can actually exist yet (~40% less DP work overall)."""

        def step(carry, x):
            col, dp = carry
            j = x[0]
            col = _advance_column(col, x, i_idx, e_s, R)

            # DP relaxation dp[q, j] = min_i dp[q, i-1] + E⟨i,j⟩ over the
            # whole Q grid at once. No i ≤ j mask is needed: dp columns ≥ j
            # are still inf from initialization, so candidates beyond the
            # diagonal are inf automatically.
            c = col[1 : Wc + 1]
            cand = dp[:, :Wc] + jnp.where(
                c[None, :] <= q_budget[:, None], c[None, :], jnp.inf
            )
            # Two single-operand reduces (XLA vectorizes those; its variadic
            # (value, index) reduce lowers to a scalar loop): the min, then
            # the smallest burst start achieving it — numpy's first-minimum
            # argmin, so parents tie-break identically on identical columns.
            mn = jnp.min(cand, axis=1)
            best = jnp.min(
                jnp.where(cand == mn[:, None], i_tail32[None, :Wc], N + 1),
                axis=1,
            )
            # dp carries columns 0..N-1 (column N is never a predecessor);
            # the final table is reassembled from the emitted mins below.
            dp = dp.at[:, j].set(mn, mode="drop")
            return (col, dp), (mn, best)

        return step

    dp0 = jnp.full((nq, N), jnp.inf).at[:, 0].set(0.0)
    carry = (jnp.zeros(N + 1), dp0)
    n_chunks = min(4, N)
    edges = sorted({-(-N * k // n_chunks) for k in range(1, n_chunks + 1)})
    mns_parts, bests_parts = [], []
    start = 0
    for end in edges:
        chunk_xs = tuple(a[start:end] for a in xs)
        carry, (mn_c, best_c) = lax.scan(make_step(end), carry, chunk_xs)
        mns_parts.append(mn_c)
        bests_parts.append(best_c)
        start = end
    mns = jnp.concatenate(mns_parts, axis=0)
    bests = jnp.concatenate(bests_parts, axis=0)

    dp = jnp.concatenate([jnp.zeros((nq, 1)), mns.T], axis=1)  # (nq, N+1)
    parent = jnp.zeros((nq, N + 1), dtype=jnp.int32).at[:, 1:].set(bests.T)
    e_total = lax.dynamic_index_in_dim(mns, n_tasks - 1, axis=0, keepdims=False)
    feasible = jnp.isfinite(e_total)

    # 4) walk the parent pointers back from task n: mark each burst start
    def reconstruct(pq):
        def back(j, _):
            i = jnp.where(j > 0, pq[j], 0)
            emit = jnp.where(j > 0, i, N + 1)  # N+1 = trash slot
            return jnp.where(j > 0, jnp.maximum(i - 1, 0), 0), emit

        _, emits = lax.scan(back, n_tasks, None, length=N)
        return jnp.zeros(N + 2, dtype=bool).at[emits].set(True)[: N + 1]

    starts = jax.vmap(reconstruct)(parent)
    return dp, parent, e_total, feasible, starts


_dp_sweep_jit = jax.jit(_dp_sweep)
_dp_sweep_vmap = jax.jit(
    jax.vmap(_dp_sweep, in_axes=(0, 0, None, None))
)


def _qmin_sweep(ga: dict, n_tasks, cost_vec):
    """§4.4 storage minimization as the same column scan with a minimax
    combine: mm[j] = min_i max(mm[i-1], E⟨i,j⟩). max/min are exact in
    float64, so the result is bit-identical to the numpy :func:`q_min`
    wherever the columns are (i.e. everywhere the sum DP is)."""
    TRACE_COUNT["qmin_sweep"] += 1
    N = ga["e_task"].shape[0]
    R = ga["read_bytes"].shape[1]
    i_idx = jnp.arange(N + 1)
    xs, e_s = _sweep_inputs(ga, cost_vec)

    def step(carry, x):
        col, mm = carry
        j = x[0]
        col = _advance_column(col, x, i_idx, e_s, R)
        # mm entries at positions ≥ j are still inf from initialization, so
        # candidates beyond the diagonal drop out exactly like the sum DP's.
        best = jnp.min(jnp.maximum(mm[:N], col[1 : N + 1]))
        mm = mm.at[j].set(best)
        return (col, mm), best

    mm0 = jnp.full(N + 1, jnp.inf).at[0].set(0.0)
    _, bests = lax.scan(step, (jnp.zeros(N + 1), mm0), xs)
    return lax.dynamic_index_in_dim(bests, n_tasks - 1, keepdims=False)


_qmin_sweep_jit = jax.jit(_qmin_sweep)


def _exactk_sweep(ga: dict, n_tasks, cost_vec, q, *, n_bursts, combine_max):
    """The exact-K pipeline DP riding the same column scan: dp[b, j] =
    min_i combine(dp[b-1, i-1], E⟨i,j⟩) with b ≤ ``n_bursts`` (static, so
    the b-loop unrolls at trace time) and the per-column budget mask applied
    before the combine, exactly like :func:`repro.core.partition._optimal_k`.
    Emits per-column (dp, parent) rows; the host walks the parents back so
    bounds reconstruct bit-identically to the numpy oracle.
    """
    TRACE_COUNT["exactk_sweep"] += 1
    del n_tasks  # the host indexes the emitted tables itself
    N = ga["e_task"].shape[0]
    R = ga["read_bytes"].shape[1]
    K = n_bursts
    i_idx = jnp.arange(N + 1)
    i_tail32 = jnp.arange(1, N + 1, dtype=jnp.int32)
    xs, e_s = _sweep_inputs(ga, cost_vec)
    q_budget = q * (1.0 + _REL) + _ABS

    def step(carry, x):
        col, dp = carry  # dp: (K+1, N) over predecessor columns 0..N-1
        j = x[0]
        col = _advance_column(col, x, i_idx, e_s, R)
        c = jnp.where(col[1 : N + 1] <= q_budget, col[1 : N + 1], jnp.inf)
        # dp rows beyond the diagonal are inf, so stale column entries at
        # i > j are masked exactly like the numpy 0:j slice.
        vals, bests = [jnp.asarray(jnp.inf)], [jnp.int32(0)]
        for b in range(1, K + 1):
            cand = jnp.maximum(dp[b - 1], c) if combine_max else dp[b - 1] + c
            mn = jnp.min(cand)
            # numpy's first-minimum argmin (+1 = burst start), as in _dp_sweep
            bests.append(jnp.min(jnp.where(cand == mn, i_tail32, N + 1)))
            vals.append(mn)
        val, bst = jnp.stack(vals), jnp.stack(bests)
        dp = dp.at[:, j].set(val, mode="drop")
        return (col, dp), (val, bst)

    dp0 = jnp.full((K + 1, N), jnp.inf).at[0, 0].set(0.0)
    _, (vals, bsts) = lax.scan(step, (jnp.zeros(N + 1), dp0), xs)
    return vals, bsts  # (N, K+1) each: dp[b, j] = vals[j-1, b]


_exactk_sweep_jit = jax.jit(
    _exactk_sweep, static_argnames=("n_bursts", "combine_max")
)


# ---------------------------------------------------------------------------
# Host-side wrappers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class JaxSweep:
    """Result of a jitted Q-grid sweep over one graph.

    ``dp`` / ``parent`` are the full DP tables ((nq, N+1)); ``starts[q, i]``
    is True iff some burst starts at task ``i`` under Q_max[q];
    ``e_total[q]`` is inf (and ``feasible[q]`` False) where no partition fits.
    """

    n_tasks: int
    q_values: List[Optional[float]]
    dp: np.ndarray
    parent: np.ndarray
    e_total: np.ndarray
    feasible: np.ndarray
    starts: np.ndarray

    def bounds(self, qi: int) -> Optional[List[Tuple[int, int]]]:
        """Reconstructed burst bounds for Q index ``qi`` (None = infeasible)."""
        if not self.feasible[qi]:
            return None
        s = np.flatnonzero(self.starts[qi, 1 : self.n_tasks + 1]) + 1
        ends = [int(e) for e in s[1:] - 1] + [self.n_tasks]
        return list(zip(s.tolist(), ends))

    def to_partitions(
        self, graph: TaskGraph, cost: CostModel
    ) -> List[Optional[Partition]]:
        """Full :class:`Partition` objects (numpy burst details) per Q value."""
        out: List[Optional[Partition]] = []
        for qi, q in enumerate(self.q_values):
            b = self.bounds(qi)
            if b is None:
                out.append(None)
                continue
            part = _partition_from_bounds(graph, cost, b, q)
            part.validate(graph)
            out.append(part)
        return out


AnyExport = Union[TaskGraph, GraphArrays, GraphCSRArrays]


def _as_arrays(graph: AnyExport) -> GraphArrays:
    """Coerce to the scan backend's dense export. Mixing layouts is a typed
    :class:`repro.core.engine.ExportMismatch` (a TypeError subclass), the
    same error the façade's registry capability check raises."""
    if isinstance(graph, GraphCSRArrays):
        raise ExportMismatch(
            "the scan backend consumes dense GraphArrays; pass the TaskGraph "
            "or use backend='pallas' for a GraphCSRArrays export"
        )
    return graph.to_arrays() if isinstance(graph, TaskGraph) else graph


def _as_csr(graph: AnyExport) -> GraphCSRArrays:
    """Coerce to the Pallas backend's CSR export (see :func:`_as_arrays`)."""
    if isinstance(graph, GraphArrays):
        raise ExportMismatch(
            "the pallas backend consumes GraphCSRArrays; pass the TaskGraph "
            "or use backend='scan' for a dense GraphArrays export"
        )
    return graph.to_csr_arrays() if isinstance(graph, TaskGraph) else graph


def _select_backend(
    graph: AnyExport, backend: str, objective: str = "sum"
) -> str:
    """Resolve ``backend="auto"`` per graph — delegates to the façade's
    backend registry (:func:`repro.core.engine.resolve_jit_backend`), which
    replaced the hand-rolled if-chain that used to live here. The size
    threshold stays in this module as ``_AUTO_DENSE_BYTES`` (read at call
    time, so tests can monkeypatch it)."""
    return resolve_jit_backend(graph, backend, objective)


# Serving-path upload caches (see core/_cache.py for the id+weakref idiom):
# jnp copies of an export, and re-padded CSR rows, are cached per source
# export object — TaskGraph.to_arrays()/to_csr_arrays() return a cached
# object per graph, so a serving loop hits these across requests, and the
# kernel wrapper's own id-keyed device cache (kernels/partition_sweep/ops.py)
# then sees stable objects too.
_GA_DEVICE_CACHE: dict = {}
_CSR_PAD_CACHE: dict = {}


def _padded_csr(a: GraphCSRArrays, n: int, r: int, w: int) -> GraphCSRArrays:
    if (a.n_pad, a.nnz_reads, a.nnz_writes) == (n, r, w):
        return a
    return weak_id_cache(
        _CSR_PAD_CACHE, a, (n, r, w), lambda: a.padded(n, r, w)
    )


def _ga_dict(arrays: GraphArrays) -> dict:
    return weak_id_cache(
        _GA_DEVICE_CACHE,
        arrays,
        (),
        lambda: {
            f.name: jnp.asarray(getattr(arrays, f.name))
            for f in dataclasses.fields(GraphArrays)
            if f.name != "n_tasks"
        },
    )


@functools.lru_cache(maxsize=None)
def _cost_vec(cost: CostModel):
    return jnp.asarray(cost_scalars(cost))


def _qs_array(q_values: Sequence[Optional[float]]) -> np.ndarray:
    return np.array(
        [np.inf if q is None else float(q) for q in q_values], dtype=np.float64
    )


def _empty_sweep(q_values: Sequence[Optional[float]]) -> JaxSweep:
    nq = len(q_values)
    return JaxSweep(
        n_tasks=0,
        q_values=list(q_values),
        dp=np.zeros((nq, 1)),
        parent=np.zeros((nq, 1), dtype=np.int32),
        e_total=np.zeros(nq),
        feasible=np.ones(nq, dtype=bool),
        starts=np.zeros((nq, 1), dtype=bool),
    )


def sweep_from_columns(
    n_tasks: int,
    q_values: Sequence[Optional[float]],
    mns: np.ndarray,
    bests: np.ndarray,
) -> JaxSweep:
    """Assemble a :class:`JaxSweep` from per-column DP tables.

    ``mns[j-1, q]`` = dp[q, j] and ``bests[j-1, q]`` = start of the last
    burst achieving it — the convention emitted by the Pallas sweep kernel
    (:mod:`repro.kernels.partition_sweep`) and its numpy CSR oracle. The
    numpy parent-walk here produces bit-identical bounds to the scan
    backend's in-jit reconstruction. Traced as ``sweep.assemble``.
    """
    if not TRACER.enabled:
        return _sweep_from_columns(n_tasks, q_values, mns, bests)
    with TRACER.span("sweep.assemble", cat="engine", pid=PID_SOLVER):
        return _sweep_from_columns(n_tasks, q_values, mns, bests)


def _sweep_from_columns(n_tasks, q_values, mns, bests) -> JaxSweep:
    N, nq = mns.shape
    dp = np.concatenate([np.zeros((nq, 1)), mns.T], axis=1)
    parent = np.zeros((nq, N + 1), dtype=np.int32)
    parent[:, 1:] = bests.T
    e_total = mns[n_tasks - 1].copy() if n_tasks >= 1 else np.zeros(nq)
    feasible = np.isfinite(e_total)
    starts = np.zeros((nq, N + 1), dtype=bool)
    for qi in range(nq):
        if not feasible[qi]:
            continue
        j = n_tasks
        while j > 0:
            i = int(parent[qi, j])
            starts[qi, i] = True
            j = i - 1
    return JaxSweep(
        n_tasks=int(n_tasks),
        q_values=list(q_values),
        dp=dp,
        parent=parent,
        e_total=e_total,
        feasible=feasible,
        starts=starts,
    )


def _sweep_pallas(
    csr: GraphCSRArrays,
    cost: CostModel,
    q_values: Sequence[Optional[float]],
    interpret: Optional[bool],
) -> JaxSweep:
    from ..kernels.partition_sweep import ops as sweep_ops  # lazy: jax-heavy

    mns, bests = sweep_ops.sweep_columns(
        csr, cost, q_values, interpret=interpret
    )
    return sweep_from_columns(csr.n_tasks, q_values, mns, bests)


def sweep_jax(
    graph: AnyExport,
    cost: CostModel,
    q_values: Sequence[Optional[float]],
    *,
    backend: str = "auto",
    interpret: Optional[bool] = None,
) -> JaxSweep:
    """One jitted pass: optimal E_total + bounds for every Q_max in the grid.

    .. deprecated:: use ``repro.api.solve(PartitionSpec(graph=g, cost=cm,
       q_grid=qs, backend=...)).sweep`` — bit-identical.
    """
    warn_legacy(
        "repro.core.partition_jax.sweep_jax",
        "solve(PartitionSpec(graph=g, cost=cm, q_grid=qs)).sweep",
    )
    return _sweep_jax(graph, cost, q_values, backend=backend,
                      interpret=interpret)


def _sweep_jax(
    graph: AnyExport,
    cost: CostModel,
    q_values: Sequence[Optional[float]],
    *,
    backend: str = "auto",
    interpret: Optional[bool] = None,
) -> JaxSweep:
    """Implementation behind ``sweep_jax`` and the façade's single-graph sum
    dispatch: optimal E_total + bounds for every Q_max in the grid.

    Drop-in analogue of :func:`repro.core.partition.sweep` /
    ``optimal_partition_multi`` — infeasible Q values come back with
    ``feasible == False`` instead of None. An empty graph is trivially
    feasible everywhere (matching the numpy path).

    ``backend`` selects the dense ``lax.scan`` engine, the CSR/Pallas sweep
    kernel, or lets ``"auto"`` route by dense-export size (module
    docstring); ``interpret`` is forwarded to the Pallas backend (``None``
    auto-selects interpret mode on CPU).
    """
    SOLVE_COUNT["sweep_jax"] += 1
    backend = _select_backend(graph, backend)
    if backend == "pallas":
        csr = _as_csr(graph)
        if csr.n_tasks == 0:
            return _empty_sweep(q_values)
        return _sweep_pallas(csr, cost, q_values, interpret)
    arrays = _as_arrays(graph)
    if arrays.n_tasks == 0:
        return _empty_sweep(q_values)
    with jax.enable_x64():
        dp, parent, e_total, feasible, starts = _dp_sweep_jit(
            _ga_dict(arrays),
            jnp.asarray(arrays.n_tasks, dtype=jnp.int32),
            _cost_vec(cost),
            jnp.asarray(_qs_array(q_values)),
        )
        return JaxSweep(
            n_tasks=int(arrays.n_tasks),
            q_values=list(q_values),
            dp=np.asarray(dp),
            parent=np.asarray(parent),
            e_total=np.asarray(e_total),
            feasible=np.asarray(feasible),
            starts=np.asarray(starts),
        )


def sweep_jax_batched(
    graphs: Sequence[AnyExport],
    cost: CostModel,
    q_values: Sequence[Optional[float]],
    *,
    backend: str = "auto",
    interpret: Optional[bool] = None,
) -> List[JaxSweep]:
    """Solve many applications × many Q_max values with one compiled kernel.

    .. deprecated:: use ``repro.api.solve(PartitionSpec(graphs=gs, cost=cm,
       q_grid=qs, backend=...)).sweeps`` — bit-identical.
    """
    warn_legacy(
        "repro.core.partition_jax.sweep_jax_batched",
        "solve(PartitionSpec(graphs=gs, cost=cm, q_grid=qs)).sweeps",
    )
    return _sweep_jax_batched(graphs, cost, q_values, backend=backend,
                              interpret=interpret)


def _sweep_jax_batched(
    graphs: Sequence[AnyExport],
    cost: CostModel,
    q_values: Sequence[Optional[float]],
    *,
    backend: str = "auto",
    interpret: Optional[bool] = None,
) -> List[JaxSweep]:
    """Implementation behind ``sweep_jax_batched`` and the façade's batched
    sum dispatch.

    Scan backend: graphs pad to a common (N, R, W) via
    :func:`stack_graph_arrays` and solve in one ``vmap``. Pallas backend:
    graphs pad to a common (N, nnz_r, nnz_w) — the padded rows are cached
    per export, and :func:`stack_csr_arrays` builds the same layout with a
    leading batch axis for vmap consumers — and the sweep kernel runs per
    graph: one compiled kernel (the padded shape is shared) applied
    sequentially, since the DP grid is already sequential per graph.
    ``backend="auto"`` resolves per member and solves each group with its
    own backend (a mixed batch of dense and CSR exports is legal), keeping
    one compilation per group.
    """
    SOLVE_COUNT["sweep_jax_batched"] += 1
    if backend == "auto":
        resolved = [_select_backend(g, "auto") for g in graphs]
        if "scan" in resolved and "pallas" in resolved:
            out: List[Optional[JaxSweep]] = [None] * len(graphs)
            for be in ("scan", "pallas"):
                idx = [k for k, r in enumerate(resolved) if r == be]
                group = _sweep_jax_batched(
                    [graphs[k] for k in idx], cost, q_values,
                    backend=be, interpret=interpret,
                )
                for k, res in zip(idx, group):
                    out[k] = res
            return out  # type: ignore[return-value]
        backend = resolved[0] if resolved else "scan"
    if backend == "pallas":
        csrs = [_as_csr(g) for g in graphs]
        out = [None] * len(csrs)
        nonempty = [(k, a) for k, a in enumerate(csrs) if a.n_tasks > 0]
        for k, a in enumerate(csrs):
            if a.n_tasks == 0:
                out[k] = _empty_sweep(q_values)
        if nonempty:
            n = max(a.n_pad for _, a in nonempty)
            r = max(max(a.nnz_reads for _, a in nonempty), 1)
            w = max(max(a.nnz_writes for _, a in nonempty), 1)
            for k, a in nonempty:
                out[k] = _sweep_pallas(
                    _padded_csr(a, n, r, w), cost, q_values, interpret
                )
        return out  # type: ignore[return-value]

    arrays = [_as_arrays(g) for g in graphs]
    nonempty = [(k, a) for k, a in enumerate(arrays) if a.n_tasks > 0]
    out = [None] * len(arrays)
    for k, a in enumerate(arrays):
        if a.n_tasks == 0:
            out[k] = _empty_sweep(q_values)
    if nonempty:
        stacked = stack_graph_arrays([a for _, a in nonempty])
        with jax.enable_x64():
            dp, parent, e_total, feasible, starts = _dp_sweep_vmap(
                _ga_dict(stacked),
                jnp.asarray(stacked.n_tasks, dtype=jnp.int32),
                _cost_vec(cost),
                jnp.asarray(_qs_array(q_values)),
            )
        for b, (k, a) in enumerate(nonempty):
            out[k] = JaxSweep(
                n_tasks=int(a.n_tasks),
                q_values=list(q_values),
                dp=np.asarray(dp[b]),
                parent=np.asarray(parent[b]),
                e_total=np.asarray(e_total[b]),
                feasible=np.asarray(feasible[b]),
                starts=np.asarray(starts[b]),
            )
    return out  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# Sharded (multi-device) sweeps — the offline DSE path
# ---------------------------------------------------------------------------
#
# The per-Q DP rows are fully independent (dp[q, j] only ever reads dp[q, ·]),
# so the Q grid is the natural shard axis for the offline design-space
# exploration: each device solves every graph for a contiguous Q chunk, and
# the gathered columns are bit-identical to the single-call solve. The pmap
# wrapper below maps the shard axis over devices; when fewer devices exist
# than shards (e.g. the fast test tier on one CPU device), the same padded
# chunks run sequentially through ``_dp_sweep_vmap`` — same decomposition,
# same bytes (asserted by tests/test_dse_shard.py on 1/2/4/8 devices).


def shard_q_grid(n_q: int, n_shards: int) -> List[Tuple[int, int]]:
    """Balanced contiguous ``[start, stop)`` chunks covering ``range(n_q)``.

    The first ``n_q % n_shards`` chunks are one element longer; ``n_shards``
    is clamped so every chunk is non-empty.
    """
    if n_q < 1:
        raise ValueError("shard_q_grid needs at least one Q point")
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    n_shards = min(n_shards, n_q)
    base, rem = divmod(n_q, n_shards)
    edges = [0]
    for s in range(n_shards):
        edges.append(edges[-1] + base + (1 if s < rem else 0))
    return list(zip(edges[:-1], edges[1:]))


@functools.lru_cache(maxsize=None)
def _dp_sweep_pmap(devices: tuple):
    """pmap of the vmapped engine over a leading Q-shard axis.

    Graph arrays, task counts, and cost scalars broadcast (``in_axes=None``);
    only the ``(n_shards, q_pad)`` Q grid is mapped. Cached per device tuple
    (jax Devices are hashable); pmap itself caches per shape.
    """
    return jax.pmap(
        jax.vmap(_dp_sweep, in_axes=(0, 0, None, None)),
        in_axes=(None, None, None, 0),
        devices=devices,
    )


def _pad_q_shards(
    qs_np: np.ndarray, chunks: Sequence[Tuple[int, int]]
) -> np.ndarray:
    """Stack Q chunks into one rectangle, padding short chunks by repeating
    their last value (padded rows are solved and discarded — per-Q rows are
    independent, so they cannot perturb the real columns)."""
    q_pad = max(hi - lo for (lo, hi) in chunks)
    out = np.empty((len(chunks), q_pad), dtype=np.float64)
    for s, (lo, hi) in enumerate(chunks):
        out[s, : hi - lo] = qs_np[lo:hi]
        out[s, hi - lo :] = qs_np[hi - 1]
    return out


def _merge_sweeps(
    q_values: Sequence[Optional[float]],
    chunk_sweeps: Sequence[Sequence[JaxSweep]],
) -> List[JaxSweep]:
    """Concatenate per-chunk JaxSweeps (chunk-major) back into full-grid ones."""
    out: List[JaxSweep] = []
    for g in range(len(chunk_sweeps[0])):
        parts = [cs[g] for cs in chunk_sweeps]
        out.append(
            JaxSweep(
                n_tasks=parts[0].n_tasks,
                q_values=list(q_values),
                dp=np.concatenate([p.dp for p in parts], axis=0),
                parent=np.concatenate([p.parent for p in parts], axis=0),
                e_total=np.concatenate([p.e_total for p in parts], axis=0),
                feasible=np.concatenate([p.feasible for p in parts], axis=0),
                starts=np.concatenate([p.starts for p in parts], axis=0),
            )
        )
    return out


def sweep_jax_sharded(
    graphs: Sequence[AnyExport],
    cost: CostModel,
    q_values: Sequence[Optional[float]],
    *,
    n_shards: int,
    devices: Optional[Sequence] = None,
    backend: str = "auto",
    interpret: Optional[bool] = None,
) -> List[JaxSweep]:
    """Q-grid-sharded batched sweep: same results, many devices.

    .. deprecated:: use ``repro.api.solve(PartitionSpec(graphs=gs, cost=cm,
       q_grid=qs, sharding=QGridSharding(n_shards, devices))).sweeps`` —
       bit-identical.
    """
    warn_legacy(
        "repro.core.partition_jax.sweep_jax_sharded",
        "solve(PartitionSpec(graphs=gs, cost=cm, q_grid=qs, "
        "sharding=QGridSharding(n_shards, devices))).sweeps",
    )
    return _sweep_jax_sharded(
        graphs, cost, q_values, n_shards=n_shards, devices=devices,
        backend=backend, interpret=interpret,
    )


def _sweep_jax_sharded(
    graphs: Sequence[AnyExport],
    cost: CostModel,
    q_values: Sequence[Optional[float]],
    *,
    n_shards: int,
    devices: Optional[Sequence] = None,
    backend: str = "auto",
    interpret: Optional[bool] = None,
) -> List[JaxSweep]:
    """Q-grid-sharded :func:`_sweep_jax_batched`: same results, many devices.

    The Q grid splits into ``n_shards`` contiguous chunks
    (:func:`shard_q_grid`); every device solves all graphs for one chunk and
    the gathered columns are **bit-identical** to the single-call batched
    solve (per-Q DP independence — the differential tier pins this).

    Scan backend: chunks pad to a common width and run under one
    ``pmap(vmap(...))`` when ``len(devices) >= n_shards``. With ``devices``
    left to default and too few local devices they run sequentially through
    the same vmapped kernel (one compile either way); explicit ``devices``
    that are too few raise ``ValueError``. Pallas/CSR
    backend (or a mixed ``auto`` batch): chunks run as host-side
    ``sweep_jax_batched`` calls — the kernel lanes the Q axis itself, so
    chunked solves are already bit-stable there.
    """
    SOLVE_COUNT["sweep_jax_sharded"] += 1
    qs_np = _qs_array(q_values)
    chunks = shard_q_grid(qs_np.shape[0], n_shards)
    if not graphs:
        return []

    resolved = {_select_backend(g, backend) for g in graphs}
    arrays = [_as_arrays(g) for g in graphs] if resolved == {"scan"} else None
    if arrays is None:
        # CSR/Pallas (or mixed) batch: host-sharded chunk loop.
        qs_list = list(q_values)
        chunk_sweeps = [
            _sweep_jax_batched(
                graphs, cost, qs_list[lo:hi], backend=backend,
                interpret=interpret,
            )
            for (lo, hi) in chunks
        ]
        return _merge_sweeps(q_values, chunk_sweeps)

    out: List[Optional[JaxSweep]] = [None] * len(arrays)
    nonempty = [(k, a) for k, a in enumerate(arrays) if a.n_tasks > 0]
    for k, a in enumerate(arrays):
        if a.n_tasks == 0:
            out[k] = _empty_sweep(q_values)
    if not nonempty:
        return out  # type: ignore[return-value]

    stacked = stack_graph_arrays([a for _, a in nonempty])
    qs_sh = _pad_q_shards(qs_np, chunks)
    devs = tuple(devices) if devices is not None else tuple(jax.local_devices())
    if devices is not None and len(chunks) > 1 and len(devs) < len(chunks):
        # Devices named by the caller are a placement, not a hint: running
        # their shards back to back would hide a missing device.
        raise ValueError(
            f"{len(chunks)} Q shards need {len(chunks)} devices, "
            f"got {len(devs)}"
        )
    with jax.enable_x64():
        ga = _ga_dict(stacked)
        nt = jnp.asarray(stacked.n_tasks, dtype=jnp.int32)
        cv = _cost_vec(cost)
        if len(chunks) > 1 and len(devs) >= len(chunks):
            fn = _dp_sweep_pmap(devs[: len(chunks)])
            shard_outs = fn(ga, nt, cv, jnp.asarray(qs_sh))
            per_shard = [
                tuple(np.asarray(o[s]) for o in shard_outs)
                for s in range(len(chunks))
            ]
        else:
            # Device-starved fallback: same padded chunks, same vmapped
            # kernel, run back to back — bit-identical by construction.
            per_shard = [
                tuple(
                    np.asarray(o)
                    for o in _dp_sweep_vmap(ga, nt, cv, jnp.asarray(qs_sh[s]))
                )
                for s in range(len(chunks))
            ]

    for b, (k, a) in enumerate(nonempty):
        def _cat(i: int) -> np.ndarray:
            return np.concatenate(
                [per_shard[s][i][b, : hi - lo]
                 for s, (lo, hi) in enumerate(chunks)],
                axis=0,
            )

        out[k] = JaxSweep(
            n_tasks=int(a.n_tasks),
            q_values=list(q_values),
            dp=_cat(0),
            parent=_cat(1),
            e_total=_cat(2),
            feasible=_cat(3),
            starts=_cat(4),
        )
    return out  # type: ignore[return-value]


def optimal_partition_jax(
    graph: TaskGraph,
    cost: CostModel,
    q_max: Optional[float] = None,
    *,
    backend: str = "auto",
) -> Partition:
    """Single-Q convenience mirroring the legacy ``optimal_partition``
    (raises :class:`Infeasible` when Q_max < Q_min).

    .. deprecated:: use ``repro.api.solve(PartitionSpec(graph=g, cost=cm,
       q_max=q, backend=...)).partition()`` — bit-identical.
    """
    warn_legacy(
        "repro.core.partition_jax.optimal_partition_jax",
        "solve(PartitionSpec(graph=g, cost=cm, q_max=q)).partition()",
    )
    return _optimal_partition_jax(graph, cost, q_max, backend=backend)


def _optimal_partition_jax(
    graph: TaskGraph,
    cost: CostModel,
    q_max: Optional[float] = None,
    *,
    backend: str = "auto",
) -> Partition:
    res = _sweep_jax(graph, cost, [q_max], backend=backend)
    parts = res.to_partitions(graph, cost)
    if parts[0] is None:
        raise Infeasible(f"Q_max={q_max} admits no partition")
    return parts[0]


# ---------------------------------------------------------------------------
# Jit-backend minimax / exact-K — the façade's objective= axis (scan re-
# expressions + the Pallas kernel modes, routed per backend by the
# _q_min_jit / _optimal_k_jit dispatchers below)
# ---------------------------------------------------------------------------


def _q_min_scan(graph: AnyExport, cost: CostModel) -> float:
    """§4.4 storage minimization on the jitted scan engine — the façade's
    ``objective="minimax"`` on ``backend="scan"``. Bit-identical to the
    numpy :func:`repro.core.partition.q_min` on unroll-width graphs (the
    minimax combine is exact; only the shared columns can differ, and only
    for R > ``_UNROLL_MAX`` — same caveat as the sum DP)."""
    SOLVE_COUNT["q_min_scan"] += 1
    arrays = _as_arrays(graph)
    if arrays.n_tasks == 0:
        return 0.0
    with jax.enable_x64():
        out = _qmin_sweep_jit(
            _ga_dict(arrays),
            jnp.asarray(arrays.n_tasks, dtype=jnp.int32),
            _cost_vec(cost),
        )
        return float(np.asarray(out))


def _optimal_k_scan(
    graph: AnyExport,
    cost: CostModel,
    n_bursts: int,
    q_max: Optional[float] = None,
    objective: str = "sum",
) -> Partition:
    """Exact-K partition on the jitted scan engine — the façade's
    ``objective="exact_k"`` on ``backend="scan"``. The emitted (dp, parent)
    tables reconstruct on the host with the same walk as the numpy
    :func:`repro.core.partition._optimal_k`, so bounds (and tie-breaks)
    match it bit-for-bit on unroll-width graphs."""
    SOLVE_COUNT["optimal_k_scan"] += 1
    if not isinstance(graph, TaskGraph):
        raise ExportMismatch(
            "exact_k needs the TaskGraph to price the reconstructed bursts; "
            "pass the graph rather than a pre-exported layout"
        )
    arrays = _as_arrays(graph)
    n = arrays.n_tasks
    if not 1 <= n_bursts <= max(n, 1):
        raise ValueError(f"n_bursts={n_bursts} out of range for {n} tasks")
    if n == 0:
        return Partition([], [], q_max)
    if objective not in ("sum", "max"):
        raise ValueError(f"objective must be 'sum' or 'max', got {objective!r}")
    q = np.inf if q_max is None else float(q_max)
    with jax.enable_x64():
        vals, bsts = _exactk_sweep_jit(
            _ga_dict(arrays),
            jnp.asarray(n, dtype=jnp.int32),
            _cost_vec(cost),
            jnp.asarray(q, dtype=jnp.float64),
            n_bursts=int(n_bursts),
            combine_max=(objective == "max"),
        )
    vals = np.asarray(vals)  # (N, K+1): dp[b, j] = vals[j-1, b]
    bsts = np.asarray(bsts)
    if not np.isfinite(vals[n - 1, n_bursts]):
        raise Infeasible(f"no {n_bursts}-burst partition within Q_max={q_max}")
    bounds: List[Tuple[int, int]] = []
    j, b = n, n_bursts
    while j > 0:
        i = int(bsts[j - 1, b])
        bounds.append((i, j))
        j, b = i - 1, b - 1
    bounds.reverse()
    part = _partition_from_bounds(graph, cost, bounds, q_max)
    part.validate(graph)
    return part


def _q_min_pallas(
    graph: AnyExport, cost: CostModel, interpret: Optional[bool] = None
) -> float:
    """§4.4 storage minimization on the Pallas kernel's minimax mode — the
    façade's ``objective="minimax"`` on ``backend="pallas"``. The max/min
    combine is exact in float64, so Q_min is bit-identical to the numpy
    :func:`repro.core.partition.q_min` on *every* graph in interpret mode
    (no unroll-width caveat: the CSR kernel replays ColumnSweep's exact
    slot order)."""
    SOLVE_COUNT["q_min_pallas"] += 1
    csr = _as_csr(graph)
    if csr.n_tasks == 0:
        return 0.0
    from ..kernels.partition_sweep import ops as sweep_ops  # lazy: jax-heavy

    mns, _ = sweep_ops.sweep_columns(
        csr, cost, (), objective="minimax", interpret=interpret
    )
    return float(mns[csr.n_tasks - 1, 0])


def _optimal_k_pallas(
    graph: AnyExport,
    cost: CostModel,
    n_bursts: int,
    q_max: Optional[float] = None,
    objective: str = "sum",
    interpret: Optional[bool] = None,
) -> Partition:
    """Exact-K partition on the Pallas kernel's exact_k mode — the façade's
    ``objective="exact_k"`` on ``backend="pallas"``. The kernel's lane axis
    carries the burst count, so its (vals, bsts) tables have the layout of
    the scan backend's ``_exactk_sweep`` and reconstruct with the identical
    host walk — bounds and tie-breaks match the numpy
    :func:`repro.core.partition._optimal_k` bit-for-bit in interpret mode."""
    SOLVE_COUNT["optimal_k_pallas"] += 1
    if not isinstance(graph, TaskGraph):
        raise ExportMismatch(
            "exact_k needs the TaskGraph to price the reconstructed bursts; "
            "pass the graph rather than a pre-exported layout"
        )
    csr = _as_csr(graph)
    n = csr.n_tasks
    if not 1 <= n_bursts <= max(n, 1):
        raise ValueError(f"n_bursts={n_bursts} out of range for {n} tasks")
    if n == 0:
        return Partition([], [], q_max)
    if objective not in ("sum", "max"):
        raise ValueError(f"objective must be 'sum' or 'max', got {objective!r}")
    from ..kernels.partition_sweep import ops as sweep_ops  # lazy: jax-heavy

    vals, bsts = sweep_ops.sweep_columns(
        csr,
        cost,
        (q_max,),
        objective="exact_k",
        n_bursts=int(n_bursts),
        k_objective=objective,
        interpret=interpret,
    )
    if not np.isfinite(vals[n - 1, n_bursts]):
        raise Infeasible(f"no {n_bursts}-burst partition within Q_max={q_max}")
    bounds: List[Tuple[int, int]] = []
    j, b = n, n_bursts
    while j > 0:
        i = int(bsts[j - 1, b])
        bounds.append((i, j))
        j, b = i - 1, b - 1
    bounds.reverse()
    part = _partition_from_bounds(graph, cost, bounds, q_max)
    part.validate(graph)
    return part


def _q_min_jit(
    graph: AnyExport,
    cost: CostModel,
    *,
    backend: str = "auto",
    interpret: Optional[bool] = None,
) -> float:
    """Route the façade's ``objective="minimax"`` to the resolved jit
    backend (scan re-expression or Pallas kernel mode)."""
    if _select_backend(graph, backend, objective="minimax") == "pallas":
        return _q_min_pallas(graph, cost, interpret=interpret)
    return _q_min_scan(graph, cost)


def _optimal_k_jit(
    graph: AnyExport,
    cost: CostModel,
    n_bursts: int,
    q_max: Optional[float] = None,
    objective: str = "sum",
    *,
    backend: str = "auto",
    interpret: Optional[bool] = None,
) -> Partition:
    """Route the façade's ``objective="exact_k"`` to the resolved jit
    backend (scan re-expression or Pallas kernel mode)."""
    if _select_backend(graph, backend, objective="exact_k") == "pallas":
        return _optimal_k_pallas(
            graph, cost, n_bursts, q_max, objective, interpret=interpret
        )
    return _optimal_k_scan(graph, cost, n_bursts, q_max, objective)
