"""Closed loop of design queries on a task graph, back to back.

One query prices the graph under a cost model drawn from the seed (the
configuration's start-up and per-byte read and write energies each scaled
by a factor log-uniform in the traffic's range: a sweep over NVM
technologies), solves the minimax DP for Q_min on the compiled kernel, then
the sum DP over a geometric Q grid from just above Q_min to a multiple of
the application's energy, plus unbounded. A query is complete when its
results are on the host.

After the window, a sample of the completed queries drawn from the seed is
solved again by the plain float64 reference at a sample of their Q points.
"""

from __future__ import annotations

import math
from typing import List

import numpy as np

from chipbench import work


class Driver:
    def __init__(self, cell, seed, ref, options):
        self.cell, self.ref, self.options = cell, ref, options
        self.config, self.traffic = cell.config, cell.traffic
        self.rng = np.random.default_rng(seed)
        self.sample_rng = np.random.default_rng([seed, 1])
        self.kept: List[dict] = []
        self.done = 0

    def reseed(self, seed: int) -> None:
        """Start over from ``seed`` on the same set-up (readings only)."""
        self.rng = np.random.default_rng(seed)
        self.sample_rng = np.random.default_rng([seed, 1])
        self.kept, self.done = [], 0

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        from repro.core import GraphBuilder

        cfg = self.config
        self.rgraph = self.ref.build(cfg)
        b = GraphBuilder()
        for p in cfg["packets"]:
            kw = {"keep": True} if p.get("keep") else {}
            if "count" in p:
                b.packet_array(p["name"], p["count"], p["bytes"], **kw)
            else:
                b.packet(p["name"], p["bytes"], **kw)
        names = self.rgraph.names
        k = 0
        for t in cfg["tasks"]:
            for m in range(t.get("count", 1)):
                name = t["name"] if "count" not in t else f"{t['name']}_{m}"
                b.task(name, reads=[names[p] for p in self.rgraph.reads[k]],
                       writes=[names[p] for p in self.rgraph.writes[k]],
                       cost=t["cost"])
                k += 1
        self.graph = b.build()
        csr = self.graph.to_csr_arrays()
        exp = cfg["expect"]
        if (self.graph.n_tasks, csr.nnz_reads) != (exp["n_tasks"], exp["nnz_reads"]):
            raise ValueError(
                f"graph has {self.graph.n_tasks} tasks and {csr.nnz_reads} "
                f"read slots; the configuration states {exp['n_tasks']} "
                f"and {exp['nnz_reads']}")
        self.e_app = float(self.rgraph.e_task.sum())
        n, nnz = self.rgraph.n, self.rgraph.nnz_reads
        col = work.dp_column_ops(n, self.ref.read_slots(self.rgraph))
        nq = self.traffic["q_points"] + 1
        ops_sum, bytes_sum = work.dp_sweep_work(n, nnz, col, nq, 3)
        ops_mm, bytes_mm = work.dp_sweep_work(n, nnz, col, 1, 2)
        self.ops, self.bytes = ops_sum + ops_mm, bytes_sum + bytes_mm
        nsample = self.traffic["sample_q_points"]
        inner = self.sample_rng.choice(np.arange(1, nq - 1), nsample - 2,
                                       replace=False)
        self.sample_q = [0] + sorted(int(i) for i in inner) + [nq - 1]
        # Warm every program a query runs: one query at the unscaled model.
        self.warm = self._query((1.0, 1.0, 1.0))
        q = self.warm
        if abs(q["q_min"] - exp["q_min"]) > exp["q_min_tolerance"]:
            raise ValueError(f"Q_min {q['q_min']} is not the stated {exp['q_min']}")

    def _cost_model(self, scales):
        from repro.core import CostModel, LinearTransfer

        es, rc0, rc1, wc0, wc1 = self.ref.cost_of(self.config, scales)
        return CostModel(e_startup=es, read=LinearTransfer(rc0, rc1),
                         write=LinearTransfer(wc0, wc1), name="design-query")

    def _query(self, scales) -> dict:
        import jax
        from repro.api import PartitionSpec, solve

        cm = self._cost_model(scales)
        interpret = bool(self.options.get("interpret", False))
        with jax.profiler.TraceAnnotation("query.minimax"):
            q_min = solve(PartitionSpec(graph=self.graph, cost=cm,
                                        objective="minimax", backend="pallas",
                                        interpret=interpret)).q_min()
        tr = self.traffic
        top = tr["q_top_factor"] * self.e_app
        grid = [float(x) for x in np.geomspace(
            q_min * (1.0 + tr["q_min_margin"]), top, tr["q_points"])] + [None]
        with jax.profiler.TraceAnnotation("query.sum"):
            sol = solve(PartitionSpec(graph=self.graph, cost=cm,
                                      q_grid=tuple(grid), backend="pallas",
                                      interpret=interpret))
            sweep = sol.sweep
        return {"scales": scales, "q_min": float(q_min), "grid": grid,
                "sweep": sweep}

    # -- the window -----------------------------------------------------

    def run_unit(self) -> dict:
        lo, hi = self.traffic["scale_range"]
        scales = tuple(float(x) for x in np.exp(
            self.rng.uniform(math.log(lo), math.log(hi), 3)))
        q = self._query(scales)
        # Reservoir sample of the completed queries, drawn from the seed.
        self.done += 1
        keep = self.traffic["sample_queries"]
        if len(self.kept) < keep:
            self.kept.append(q)
        else:
            r = int(self.sample_rng.integers(self.done))
            if r < keep:
                self.kept[r] = q
        return {"queries": 1, "ops": self.ops, "bytes": self.bytes}

    @staticmethod
    def attempted_failed(units):
        return sum(u["queries"] for u in units), 0

    def release(self) -> None:
        self.warm = None

    # -- the comparison -------------------------------------------------

    def answers(self, q: dict):
        """The program's answers at the sampled Q points, as the reference
        states them."""
        sw = q["sweep"]
        return self.ref.Answer(
            q_min=q["q_min"],
            e_total=[float(sw.e_total[i]) if sw.feasible[i] else math.inf
                     for i in self.sample_q],
            bounds=[sw.bounds(i) for i in self.sample_q])

    def numbers(self, control: bool = False) -> dict:
        """The worst of each compared number over the sampled queries. With
        ``control``, the reference computed in bfloat16 (the precision below
        the kernel's float32) stands in for the program."""
        import ml_dtypes

        worst: dict = {}
        for q in self.kept:
            cost = self.ref.cost_of(self.config, q["scales"])
            qs = [q["grid"][i] for i in self.sample_q]
            ref = self.ref.solve(self.rgraph, cost, qs)
            got = (self.ref.solve(self.rgraph, cost, qs, dtype=ml_dtypes.bfloat16)
                   if control else self.answers(q))
            for k, v in self.ref.compare(self.rgraph, cost, qs, got, ref).items():
                worst[k] = max(worst.get(k, 0.0), v)
        return worst
