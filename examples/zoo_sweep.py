"""Fleet-scale design-space exploration: the whole model zoo, one kernel.

Lowers every registered architecture to a Ladybirds task graph
(`lower_zoo`), pads them to a common shape, and solves the optimal burst
partition for all of them across a shared 256-point Q_max grid in a single
vmapped, jit-compiled dispatch (`solve(PartitionSpec(graphs=...)).sweeps`) —
the NS-Optimizer-style "sweep every device config" workflow at hardware
speed.

Two cost readings of the same graphs (DESIGN: time vs memory):

* time    — E_task = seconds at peak FLOPs, transfers over PCIe (offload);
  Q_max bounds per-segment seconds, E_total is end-to-end time.
* memory  — E_task = activation working bytes, E_s = 0; Q_max bounds
  per-segment HBM, Q_min is the smallest feasible activation budget (§4.4).

Run:  PYTHONPATH=src python examples/zoo_sweep.py
"""

import time

import numpy as np

from repro.api import PartitionSpec, solve
from repro.core import (
    lower_zoo,
    memory_cost_model,
    q_min,
    tpu_host_offload_model,
)

B, S, NQ = 8, 4096, 256

print(f"=== time reading: B={B} S={S}, PCIe offload transfers ===")
cm = tpu_host_offload_model()
zoo = lower_zoo(batch=B, seq=S)
names = sorted(zoo)
qmns = {n: q_min(zoo[n], cm) for n in names}
qs = list(np.geomspace(min(qmns.values()), max(qmns.values()) * 64, NQ))

spec = PartitionSpec(graphs=tuple(zoo[n] for n in names), cost=cm,
                     q_grid=tuple(qs))
solve(spec)  # compile once
t0 = time.time()
results = solve(spec).sweeps
dt = time.time() - t0
print(f"{len(names)} graphs x {NQ} Q points in one vmapped call: "
      f"{dt * 1e3:.1f} ms ({len(names) * NQ / dt:.0f} designs/s)\n")

hdr = f"{'arch':<24} {'tasks':>5} {'Q_min':>9} {'bursts@Qmin':>11} {'bursts@8x':>9} {'ovh@8x':>7}"
print(hdr)
print("-" * len(hdr))
for name, res in zip(names, results):
    g = zoo[name]
    feas = np.flatnonzero(res.feasible)
    qi_lo = int(feas[0])
    # closest grid point to 8x this graph's Q_min
    qi_8 = int(np.argmin(np.abs(np.array(qs) - 8 * qmns[name])))
    if not res.feasible[qi_8]:
        qi_8 = qi_lo
    e_app = g.total_task_cost()
    ovh = 100.0 * (res.e_total[qi_8] - e_app) / res.e_total[qi_8]
    print(f"{name:<24} {g.n_tasks:>5} {qmns[name] * 1e3:>7.2f}ms "
          f"{len(res.bounds(qi_lo)):>11} {len(res.bounds(qi_8)):>9} {ovh:>6.2f}%")

print(f"\n=== memory reading: B=1 S=128, Q_max bounds per-segment bytes ===")
cm_m = memory_cost_model()
zoo_m = lower_zoo(batch=1, seq=128, kind="memory")
names_m = sorted(zoo_m)
for name in names_m:
    g = zoo_m[name]
    qmn = q_min(g, cm_m)
    res = solve(PartitionSpec(graph=g, cost=cm_m, q_grid=(qmn, qmn * 4))).sweep
    print(f"{name:<24} min activation budget {qmn / 1e3:8.1f} kB  "
          f"segments: {len(res.bounds(0))} @Qmin, {len(res.bounds(1))} @4x")
