"""Host wrapper for the CSR sweep kernel: numpy in, numpy column tables out.

``sweep_columns`` is the kernel package's public entry point: it takes a
:class:`repro.core.graph.GraphCSRArrays` export plus a cost model and an
objective — a Q_max grid for ``"sum"``, nothing extra for ``"minimax"``,
``(Q_max, n_bursts, k_objective)`` for ``"exact_k"`` — prices the slots
(the export itself is cost-model-independent), and launches
:func:`.kernel.sweep_columns_call` in the matching static mode. The engine
(:mod:`repro.core.partition_jax`, ``backend="pallas"``) assembles the
returned (mns, bests) into a :class:`~repro.core.partition_jax.JaxSweep`
(sum), a Q_min scalar (minimax), or an exact-K parent walk; tests compare
them bit-for-bit against the :mod:`.ref` oracles.

Serving-path notes (ROADMAP "hoist dtype handling"):

* float64 numerics need ``jax.enable_x64``; the scope is
  entered here, around conversion + dispatch only, and it is a cheap
  thread-local flag — the jit cache is keyed per config state, so repeated
  calls reuse one trace (asserted by tests/test_partition_sweep.py).
* the priced slot arrays are device-cached per ``(export, cost model,
  dtype)``, so a serving loop re-solving one application across request
  shapes uploads the graph once, not per request. ``UPLOAD_COUNT`` counts
  the cache's hits and misses.

With ``repro.obs`` tracing on, one launch emits the spans ``sweep.price``
and ``sweep.upload`` (on a cache miss only), ``sweep.launch`` (the
dispatch; its args ``grid_programs`` and ``tile_bodies`` count the kernel's
grid programs and the i-tile bodies they run) and ``sweep.readback`` (the
host waiting for the kernel, then the copy back); off, each site costs one
``TRACER.enabled`` check.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ...core._cache import weak_id_cache
from ...core.cost import CostModel
from ...core.graph import GraphCSRArrays
from ...obs.metrics import METRICS
from ...obs.trace import PID_SOLVER, TRACER
from .kernel import sweep_columns_call, tile_bodies, vmem_bytes
from .ref import (  # noqa: F401  (re-exported oracles)
    _ABS,
    _REL,
    slot_costs,
    store_add_ref,
    sweep_columns_exactk_ref,
    sweep_columns_minimax_ref,
    sweep_columns_ref,
)

__all__ = [
    "SCOPED_VMEM_BYTES",
    "VmemLimitExceeded",
    "sweep_columns",
    "sweep_columns_ref",
    "sweep_columns_minimax_ref",
    "sweep_columns_exactk_ref",
    "slot_costs",
    "store_add_ref",
]


# The TPU compiler's default scoped-VMEM limit for one kernel on v5e.
SCOPED_VMEM_BYTES = 16 * 2**20


class VmemLimitExceeded(ValueError):
    """The compiled kernel's resident tables would not fit scoped VMEM."""


def _needs_interpret() -> bool:
    # Compiled mode is TPU-only (pltpu memory spaces); everything else —
    # CPU and GPU backends alike — takes the interpret path.
    return jax.default_backend() != "tpu"


# (id(csr), cost, dtype name) -> priced + uploaded slot arrays (see
# core/_cache.py for the id+weakref idiom).
_DEVICE_CACHE: dict = {}

# Lookups of _DEVICE_CACHE: a miss prices the slots and uploads them.
UPLOAD_COUNT = METRICS.counter_dict(
    "kernel.partition_sweep.upload", ("hit", "miss"))


def _span(name: str, **args):
    return TRACER.span(name, cat="kernel", pid=PID_SOLVER, **args)


def _device_slots(csr: GraphCSRArrays, cost: CostModel, dtype) -> tuple:
    miss = False

    def upload():
        nonlocal miss
        miss = True
        if not TRACER.enabled:
            return _upload(csr, cost, dtype, *_price(csr, cost))
        with _span("sweep.price"):
            priced = _price(csr, cost)
        with _span("sweep.upload"):
            return _upload(csr, cost, dtype, *priced)

    slots = weak_id_cache(
        _DEVICE_CACHE, csr, (cost, np.dtype(dtype).name), upload
    )
    UPLOAD_COUNT["miss" if miss else "hit"] += 1
    return slots


def _price(csr: GraphCSRArrays, cost: CostModel) -> tuple:
    slot_cost, slot_free = slot_costs(csr, cost)
    return slot_cost, slot_free, store_add_ref(csr, cost)


def _upload(csr, cost, dtype, slot_cost, slot_free, store_add) -> tuple:
    return (
        jnp.asarray(csr.read_ptr),
        jnp.asarray(csr.e_task, dtype=dtype),
        jnp.asarray(store_add, dtype=dtype),
        jnp.asarray(np.array([cost.e_startup]), dtype=dtype),
        jnp.asarray(slot_cost, dtype=dtype),
        jnp.asarray(slot_free, dtype=dtype),
        jnp.asarray(csr.read_lt),
        jnp.asarray(csr.read_writer),
        jnp.asarray(csr.read_linf),
    )


def sweep_columns(
    csr: GraphCSRArrays,
    cost: CostModel,
    q_values: Sequence[Optional[float]],
    *,
    objective: str = "sum",
    n_bursts: Optional[int] = None,
    k_objective: str = "sum",
    tile: int = 512,
    slot_chunk: int = 1,
    interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Solve one CSR export in one kernel mode: → (mns, bests) tables.

    ``objective="sum"`` (default) sweeps the Q grid: ``mns[j-1, q]`` is
    dp[q, j] (optimal cost of tasks 1..j under Q[q]), ``bests[j-1, q]`` the
    start of the last burst achieving it; tables are ``(N, nq)``.

    ``objective="minimax"`` takes no Q grid (pass ``q_values=()``): tables
    are ``(N, 1)`` with ``mns[j-1, 0] = mm[j]`` — Q_min is
    ``mns[n_tasks-1, 0]``.

    ``objective="exact_k"`` takes exactly one Q value (the single Q_max,
    ``None`` for unbounded) plus ``n_bursts=K`` and ``k_objective``
    ("sum" | "max"); tables are ``(N, K+1)`` with lane b = dp[b, j] /
    parent — the layout of :func:`.ref.sweep_columns_exactk_ref`.

    Infeasible entries carry ``inf`` in mns; bests are only meaningful
    where finite. ``interpret=None`` auto-selects interpret mode on every
    non-TPU backend (float64, differential-exact); compiled TPU mode runs
    float32, with ``slot_chunk=1`` only, and raises
    :class:`VmemLimitExceeded` before lowering when the resident tables
    would overrun :data:`SCOPED_VMEM_BYTES`.
    """
    if interpret is None:
        interpret = _needs_interpret()
    dtype = np.float64 if interpret else np.float32

    combine_max = False
    if objective == "sum":
        qs = np.array(
            [np.inf if q is None else float(q) for q in q_values],
            dtype=np.float64,
        )
        nq = qs.shape[0]
        nq_pad = max(8, -(-nq // 8) * 8)
        budget = np.full(nq_pad, -np.inf, dtype=np.float64)
        budget[:nq] = qs * (1.0 + _REL) + _ABS
    elif objective == "minimax":
        if len(tuple(q_values)) != 0:
            raise ValueError("objective='minimax' takes no Q grid")
        combine_max = True
        nq, nq_pad = 1, 8
        # Lane 0 is the single unconstrained minimax lane; padding -inf.
        budget = np.full(nq_pad, -np.inf, dtype=np.float64)
        budget[0] = np.inf
    elif objective == "exact_k":
        qv = tuple(q_values)
        if len(qv) != 1:
            raise ValueError("objective='exact_k' takes exactly one Q_max")
        if n_bursts is None or int(n_bursts) < 1:
            raise ValueError("objective='exact_k' needs n_bursts >= 1")
        if k_objective not in ("sum", "max"):
            raise ValueError(f"unknown k_objective {k_objective!r}")
        combine_max = k_objective == "max"
        K = int(n_bursts)
        q = np.inf if qv[0] is None else float(qv[0])
        nq = K + 1  # lane axis is the burst count b = 0..K
        nq_pad = max(8, -(-nq // 8) * 8)
        budget = np.full(nq_pad, -np.inf, dtype=np.float64)
        budget[:nq] = q * (1.0 + _REL) + _ABS
    else:
        raise ValueError(f"unknown kernel objective {objective!r}")

    if not interpret:
        need = vmem_bytes(csr.n_pad, nq_pad, tile)
        if need > SCOPED_VMEM_BYTES:
            raise VmemLimitExceeded(
                f"compiled sweep of {csr.n_pad} tasks x {nq_pad} lanes "
                f"(tile {tile}) needs {need / 2**20:.2f} MiB of VMEM for its "
                f"resident (N, nq) tables, dpbuf and temporaries; the limit "
                f"is {SCOPED_VMEM_BYTES / 2**20:.0f} MiB. Split the Q grid "
                "into narrower solves or use a smaller tile."
            )

    with jax.enable_x64(bool(interpret)):
        args = (*_device_slots(csr, cost, dtype),
                jnp.asarray(budget, dtype=dtype))
        kw = dict(tile=tile, slot_chunk=slot_chunk, interpret=bool(interpret),
                  mode=objective, combine_max=combine_max)
        if not TRACER.enabled:
            mns, bests = sweep_columns_call(*args, **kw)
            return np.asarray(mns)[:, :nq], np.asarray(bests)[:, :nq]
        with _span("sweep.launch", grid_programs=csr.n_pad,
                   tile_bodies=tile_bodies(csr.n_pad, tile)):
            mns, bests = sweep_columns_call(*args, **kw)
        with _span("sweep.readback"):
            return np.asarray(mns)[:, :nq], np.asarray(bests)[:, :nq]
