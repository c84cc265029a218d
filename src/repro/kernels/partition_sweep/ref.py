"""Numpy oracles for the CSR column-sweep/DP kernel — all three objectives.

Replays :class:`repro.core.burst.ColumnSweep` and the fused DPs of
:func:`repro.core.partition._optimal_multi` (sum),
:func:`repro.core.partition.q_min` (minimax), and
:func:`repro.core.partition._optimal_k` (exact-K) directly from a
:class:`repro.core.graph.GraphCSRArrays` export — same slot order, same
left-to-right accumulation, same first-minimum argmin and budget tolerance —
so the (mns, bests) column tables are **bit-identical** to the numpy DP
tables on every graph, and the Pallas kernel (which replays the identical
order per i-tile, in the matching mode) is asserted bit-equal against them
in tests/test_partition_sweep.py. All three share one live-column iterator,
so the column bit patterns cannot drift between objectives.

Outputs follow the engine's column convention (see
:func:`repro.core.partition_jax.sweep_from_columns`): ``mns[j-1, q]`` is
``dp[q, j]`` — the optimal cost of tasks 1..j under budget ``q`` — and
``bests[j-1, q]`` is the start task of the last burst achieving it
(smallest such start on ties). Infeasibility is carried by ``mns`` alone
(``inf`` there → ``feasible`` False downstream); on an all-infeasible
column ``bests`` degenerates to 1 — numpy's argmin over an all-inf row —
exactly like the scan engine, and those parents are never walked.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from ...core.cost import CostModel, cost_scalars
from ...core.graph import GraphCSRArrays
from ...core.partition import BUDGET_ABS as _ABS, BUDGET_REL as _REL

__all__ = [
    "slot_costs",
    "store_add_ref",
    "sweep_columns_ref",
    "sweep_columns_minimax_ref",
    "sweep_columns_exactk_ref",
]


def slot_costs(
    csr: GraphCSRArrays, cost: CostModel
) -> Tuple[np.ndarray, np.ndarray]:
    """Per read slot: (E_r of the packet, E_w of the packet).

    ``E_w`` of a *read* packet is the store that gets charged back when one
    burst absorbs both the writer and the last reader (the recurrence's
    freed-store term).
    """
    _, r_c0, r_c1, w_c0, w_c1 = cost_scalars(cost)
    slot_cost = r_c0 * csr.read_c0w + r_c1 * csr.read_bytes
    slot_free = w_c0 * csr.read_c0w + w_c1 * csr.read_bytes
    return slot_cost, slot_free


def store_add_ref(csr: GraphCSRArrays, cost: CostModel) -> np.ndarray:
    """S(j) = Σ_{p ∈ writes(j), l_∞(p) > j} E_w(p), slot-by-slot.

    Computed host-side in write-slot declaration order — the exact float64
    rounding sequence of ``ColumnSweep``'s Python sum — and fed to both the
    Pallas kernel and this oracle so S(j) is one bit pattern everywhere.
    """
    _, _, _, w_c0, w_c1 = cost_scalars(cost)
    n = csr.n_pad
    out = np.zeros(n, dtype=np.float64)
    ptr = csr.write_ptr
    for j in range(1, n + 1):
        s = 0.0
        for k in range(int(ptr[j - 1]), int(ptr[j])):
            if int(csr.write_linf[k]) > j:
                s += w_c0 * float(csr.write_c0w[k]) + w_c1 * float(csr.write_bytes[k])
        out[j - 1] = s
    return out


def _iter_columns(csr: GraphCSRArrays, cost: CostModel):
    """Yield ``(j, col)`` for j = 1..n_pad with ``col[i] = E⟨i,j⟩``.

    The live-column update — extension, loads, freed stores, diagonal — in
    ColumnSweep's exact accumulation order, shared by all three DP oracles
    below so the column bit patterns are one sequence everywhere. ``col`` is
    updated in place; callers must not hold references across iterations.
    """
    n = csr.n_pad
    e_s = float(cost.e_startup)
    slot_cost, slot_free = slot_costs(csr, cost)
    store_add = store_add_ref(csr, cost)
    ptr = csr.read_ptr
    col = np.full(n + 2, np.nan, dtype=np.float64)

    for j in range(1, n + 1):
        e_j = float(csr.e_task[j - 1])
        s_j = float(store_add[j - 1])
        # 1) extend all existing bursts ⟨i, j-1⟩ with task j
        if j > 1:
            col[1:j] += e_j + s_j
        sum_er = 0.0
        for k in range(int(ptr[j - 1]), int(ptr[j])):
            er = float(slot_cost[k])
            sum_er += er
            lt = int(csr.read_lt[k])
            if j > 1 and lt + 1 < j:  # loads for bursts starting after last touch
                col[lt + 1 : j] += er
            if j > 1 and int(csr.read_linf[k]) == j:
                w = int(csr.read_writer[k])
                if w >= 1:  # store freed when the burst absorbs the writer
                    col[1 : w + 1] -= float(slot_free[k])
        # 2) the new single-task burst ⟨j,j⟩
        col[j] = e_s + sum_er + e_j + s_j
        yield j, col


def sweep_columns_ref(
    csr: GraphCSRArrays,
    cost: CostModel,
    q_values: Sequence[Optional[float]],
) -> Tuple[np.ndarray, np.ndarray]:
    """CSR column sweep + multi-Q DP: (mns, bests), each ``(N, nq)``.

    N is the padded task count (padded tasks have zero cost and no slots, so
    their columns just extend bursts with E_s bookkeeping — identical to the
    dense engine's padding behavior).
    """
    n = csr.n_pad
    qs = np.array(
        [np.inf if q is None else float(q) for q in q_values], dtype=np.float64
    )
    nq = qs.shape[0]
    budget = qs * (1.0 + _REL) + _ABS

    mns = np.full((n, nq), np.inf, dtype=np.float64)
    bests = np.zeros((n, nq), dtype=np.int32)  # every column overwritten below
    dp = np.full((nq, n + 1), np.inf, dtype=np.float64)
    dp[:, 0] = 0.0

    for j, col in _iter_columns(csr, cost):
        # DP relaxation over the whole Q grid (first-minimum argmin)
        c = col[1 : j + 1]
        cand = dp[:, 0:j] + c[None, :]
        cand[c[None, :] > budget[:, None]] = np.inf
        best = np.argmin(cand, axis=1)
        dp[:, j] = cand[np.arange(nq), best]
        mns[j - 1] = dp[:, j]
        bests[j - 1] = best + 1

    return mns, bests


def sweep_columns_minimax_ref(
    csr: GraphCSRArrays, cost: CostModel
) -> Tuple[np.ndarray, np.ndarray]:
    """CSR column sweep + §4.4 minimax DP: (mns, bests), each ``(N, 1)``.

    ``mns[j-1, 0] = mm[j] = min_i max(mm[i-1], E⟨i,j⟩)`` — the max/min
    combine is exact in float64, so this matches
    :func:`repro.core.partition.q_min` bit-for-bit at ``mns[n_tasks-1, 0]``
    and the kernel's minimax mode matches it at *every* entry, argmin
    tie-breaks included.
    """
    n = csr.n_pad
    mns = np.full((n, 1), np.inf, dtype=np.float64)
    bests = np.zeros((n, 1), dtype=np.int32)
    mm = np.full(n + 1, np.inf, dtype=np.float64)
    mm[0] = 0.0

    for j, col in _iter_columns(csr, cost):
        cand = np.maximum(mm[0:j], col[1 : j + 1])
        best = int(np.argmin(cand))
        mm[j] = cand[best]
        mns[j - 1, 0] = mm[j]
        bests[j - 1, 0] = best + 1

    return mns, bests


def sweep_columns_exactk_ref(
    csr: GraphCSRArrays,
    cost: CostModel,
    q_max: Optional[float],
    n_bursts: int,
    k_objective: str = "sum",
) -> Tuple[np.ndarray, np.ndarray]:
    """CSR column sweep + exact-K DP: (vals, bsts), each ``(N, K+1)``.

    Lane b of column j holds ``dp[b, j]`` / its parent burst start — the
    same layout the kernel's ``exact_k`` mode emits on its lane axis (and
    :func:`repro.core.partition_jax._exactk_sweep` on its K axis), so the
    host parent walk is shared. Lane b = 0 is the degenerate zero-burst
    row: every candidate is infeasible, so ``vals[:, 0]`` is inf and
    ``bsts[:, 0]`` pins the all-inf argmin at burst start 1, exactly like
    the kernel — those parents are never walked.
    """
    n = csr.n_pad
    K = int(n_bursts)
    q = np.inf if q_max is None else float(q_max)
    budget = q * (1.0 + _REL) + _ABS
    combine = np.maximum if k_objective == "max" else (lambda prev, c: prev + c)

    vals = np.full((n, K + 1), np.inf, dtype=np.float64)
    bsts = np.ones((n, K + 1), dtype=np.int32)
    dp = np.full((K + 1, n + 1), np.inf, dtype=np.float64)
    dp[0, 0] = 0.0

    for j, col in _iter_columns(csr, cost):
        c = col[1 : j + 1].copy()
        c[c > budget] = np.inf
        for b in range(1, K + 1):
            cand = combine(dp[b - 1, 0:j], c)
            best = int(np.argmin(cand))
            dp[b, j] = cand[best]
            vals[j - 1, b] = dp[b, j]
            bsts[j - 1, b] = best + 1

    return vals, bsts
