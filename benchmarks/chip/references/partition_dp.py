"""Plain reference of the energy-bounded partition DP (arXiv:2108.04059,
sections 4.2-4.4), in numpy, independent of the program.

A graph is a list of tasks, each reading and writing named packets; a
burst <i, j> runs tasks i..j after a start-up E_s, loads every packet its
tasks read that was last touched before i, and stores every packet its
tasks write that is used after j (or kept as output). A packet costs
c0 * weight + c1 * bytes to read or write, weight amortising the
initiation over a coalesced packet array. The reference builds the cost
column E<., j> incrementally, and solves the minimax DP (Q_min) and the sum
DP for a list of Q_max values, all in ``dtype`` (float64 by default; a
lower precision gives the control).
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

BUDGET_REL = 1e-9   # a burst fits Q when cost <= Q (1 + REL) + ABS
BUDGET_ABS = 1e-12


@dataclasses.dataclass
class Graph:
    names: List[str]             # packet names
    nbytes: np.ndarray           # per packet
    weight: np.ndarray           # per packet, initiation weight
    keep: np.ndarray             # per packet, output kept after the last task
    e_task: np.ndarray           # per task (0-based)
    reads: List[List[int]]       # per task, packet indices
    writes: List[List[int]]

    @property
    def n(self) -> int:
        return len(self.e_task)

    @property
    def nnz_reads(self) -> int:
        return sum(len(r) for r in self.reads)


def build(config: dict) -> Graph:
    """Expand the configuration's packet and task lists into a Graph.

    A packet with ``count`` k is an array ``name[0..k-1]`` of initiation
    weight 1/k; a task with ``count`` k is k tasks, the m-th writing
    ``name[m]`` for each ``name[]`` and all reading ``name[*]`` as every
    element of the array.
    """
    names, nbytes, weight, keep, arrays = [], [], [], [], {}
    for p in config["packets"]:
        k = p.get("count")
        elems = [p["name"]] if k is None else [f"{p['name']}[{m}]" for m in range(k)]
        arrays[p["name"]] = list(range(len(names), len(names) + len(elems)))
        names += elems
        nbytes += [p["bytes"]] * len(elems)
        weight += [1.0 if k is None else 1.0 / k] * len(elems)
        keep += [bool(p.get("keep", False))] * len(elems)
    index = {n: i for i, n in enumerate(names)}

    def expand(refs, m):
        out = []
        for r in refs:
            if r.endswith("[*]"):
                out += arrays[r[:-3]]
            elif r.endswith("[]"):
                out.append(arrays[r[:-2]][m])
            else:
                out.append(index[r])
        return out

    e_task, reads, writes = [], [], []
    for t in config["tasks"]:
        for m in range(t.get("count", 1)):
            e_task.append(t["cost"])
            reads.append(expand(t["reads"], m))
            writes.append(expand(t["writes"], m))
    return Graph(names, np.array(nbytes, np.float64), np.array(weight),
                 np.array(keep), np.array(e_task, np.float64), reads, writes)


def cost_of(config: dict, scales: Sequence[float]) -> Tuple[float, ...]:
    """(E_s, read c0, read c1, write c0, write c1) with E_s, the read and
    the write per-byte energies multiplied by ``scales``."""
    cm = config["cost_model"]
    s_es, s_r, s_w = scales
    return (cm["e_startup"] * s_es, cm["read"][0], cm["read"][1] * s_r,
            cm["write"][0], cm["write"][1] * s_w)


def _analyse(g: Graph):
    """Writer, last use (n+1 for kept outputs) and, per read, the task
    that last touched the packet before (0 if none)."""
    n = g.n
    writer = np.zeros(len(g.names), np.int64)
    linf = np.zeros(len(g.names), np.int64)
    last = np.zeros(len(g.names), np.int64)
    lt = []
    for j in range(1, n + 1):
        for p in g.writes[j - 1]:
            writer[p] = j
    for j in range(1, n + 1):
        lt.append([int(last[p]) for p in g.reads[j - 1]])
        for p in g.reads[j - 1]:
            linf[p] = j
            last[p] = j
        for p in g.writes[j - 1]:
            last[p] = j
    for p in range(len(g.names)):
        linf[p] = max(linf[p], writer[p])
        if g.keep[p]:
            linf[p] = n + 1
    return writer, linf, lt


def read_slots(g: Graph) -> List[tuple]:
    """(j, lt, writer, linf) per read slot, for counting the column work."""
    writer, linf, lt = _analyse(g)
    return [(j, lt[j - 1][s], int(writer[p]), int(linf[p]))
            for j in range(1, g.n + 1) for s, p in enumerate(g.reads[j - 1])]


def _energies(g: Graph, cost, dtype):
    es, rc0, rc1, wc0, wc1 = cost
    er = (rc0 * g.weight + rc1 * g.nbytes).astype(dtype)
    ew = (wc0 * g.weight + wc1 * g.nbytes).astype(dtype)
    return dtype(es), er, ew


@dataclasses.dataclass
class Answer:
    q_min: float
    e_total: List[float]                         # inf where infeasible
    bounds: List[Optional[List[Tuple[int, int]]]]


def solve(g: Graph, cost, qs: Sequence[Optional[float]],
          dtype=np.float64) -> Answer:
    """Q_min and, per Q in ``qs`` (None = unbounded), the cheapest
    partition whose every burst fits Q; ties go to the smallest start."""
    dtype = np.dtype(dtype).type
    n = g.n
    writer, linf, lt = _analyse(g)
    es, er, ew = _energies(g, cost, dtype)
    store = np.zeros(n + 1, dtype)
    for j in range(1, n + 1):
        store[j] = sum((ew[p] for p in g.writes[j - 1] if linf[p] > j), dtype(0))
    budget = np.array([np.inf if q is None else q * (1 + BUDGET_REL) + BUDGET_ABS
                       for q in qs], np.float64)
    nq = len(qs)
    inf = dtype(np.inf)
    col = np.zeros(n + 1, dtype)
    dp = np.full((nq, n + 1), inf, dtype)
    dp[:, 0] = 0
    parent = np.zeros((nq, n + 1), np.int64)
    mm = np.full(n + 1, inf, dtype)
    mm[0] = 0
    for j in range(1, n + 1):
        e_j = dtype(g.e_task[j - 1])
        if j > 1:
            col[1:j] += e_j + store[j]
        loads = dtype(0)
        for s, p in enumerate(g.reads[j - 1]):
            loads += er[p]
            if lt[j - 1][s] + 1 < j:
                col[lt[j - 1][s] + 1:j] += er[p]
            if linf[p] == j and writer[p] >= 1:
                col[1:writer[p] + 1] -= ew[p]
        col[j] = es + loads + e_j + store[j]
        c = col[1:j + 1]
        mm[j] = np.maximum(mm[:j], c).min()
        cand = dp[:, :j] + c[None, :]
        cand[c.astype(np.float64)[None, :] > budget[:, None]] = inf
        best = np.argmin(cand, axis=1)
        dp[:, j] = cand[np.arange(nq), best]
        parent[:, j] = best + 1
    bounds: List[Optional[List[Tuple[int, int]]]] = []
    for qi in range(nq):
        if not np.isfinite(dp[qi, n]):
            bounds.append(None)
            continue
        b, j = [], n
        while j > 0:
            i = int(parent[qi, j])
            b.append((i, j))
            j = i - 1
        bounds.append(b[::-1])
    return Answer(q_min=float(mm[n]), e_total=[float(x) for x in dp[:, n]],
                  bounds=bounds)


def burst_costs(g: Graph, cost, bounds: Sequence[Tuple[int, int]]) -> List[float]:
    """E<i, j> of each burst in float64, straight from the definition."""
    writer, linf, lt = _analyse(g)
    es, er, ew = _energies(g, cost, np.float64)
    out = []
    for i, j in bounds:
        e = es
        for k in range(i, j + 1):
            e += g.e_task[k - 1]
            e += sum(er[p] for s, p in enumerate(g.reads[k - 1]) if lt[k - 1][s] < i)
            e += sum(ew[p] for p in g.writes[k - 1] if linf[p] > j)
        out.append(float(e))
    return out


def compare(g: Graph, cost, qs: Sequence[Optional[float]], got: Answer,
            ref: Answer) -> dict:
    """The numbers compared: Q_min's relative error; the worst relative
    error of E_total over the Q points (infinite where feasibility
    differs); and the answer's plans re-priced in float64: their worst
    relative distance from the optimum, and their worst burst's excess
    over Q."""
    q_rel = abs(got.q_min - ref.q_min) / ref.q_min
    e_rel = plan_rel = over = 0.0
    for qi, q in enumerate(qs):
        feasible = np.isfinite(ref.e_total[qi])
        if feasible != np.isfinite(got.e_total[qi]) or (
                feasible != (got.bounds[qi] is not None)):
            e_rel = math.inf
            continue
        if not feasible:
            continue
        e_rel = max(e_rel, abs(got.e_total[qi] - ref.e_total[qi]) / ref.e_total[qi])
        costs = burst_costs(g, cost, got.bounds[qi])
        plan_rel = max(plan_rel, abs(sum(costs) - ref.e_total[qi]) / ref.e_total[qi])
        if q is not None:
            over = max(over, (max(costs) - q) / q)
    return {"q_min_rel_err": q_rel, "e_total_rel_err": e_rel,
            "plan_cost_rel_err": plan_rel, "plan_budget_excess": max(over, 0.0)}
