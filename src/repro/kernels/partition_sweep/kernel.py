"""Fused CSR column-sweep + multi-Q DP — Pallas TPU kernel (paper §4.2–§4.3).

One kernel juliennes a whole application: it walks tasks j = 1..N carrying
the live burst column E⟨·,j⟩ and the DP table, with a grid of ``(N,)`` —
**one program per column**. The column and the DP table are cut into
i-tiles of ``tile`` rows, resident in VMEM scratch across the sequential
grid, and program j loops over the ⌈j/tile⌉ tiles that hold a live burst
⟨i, j⟩ (i ≤ j), each a ``(tile, 1)`` slice of the column and a
``(tile, nq)`` slice of the DP candidates; the tiles above hold nothing yet
and are skipped (:func:`tile_bodies` counts the tiles visited).

Read-slot contributions come from the CSR-style compressed slot layout of
:class:`repro.core.graph.GraphCSRArrays` (flat ``slot_task_ptr`` /
``slot_cost`` / ``slot_lt`` / ``slot_writer`` / ``slot_linf`` arrays instead
of the dense ``(N, R)`` rectangle), held in SMEM so that the compiled kernel
can read slot ``p0 + s`` as a scalar at a dynamic index: each program loops
over task j's slot range and applies the three piecewise-constant updates
in-register, tile by tile:

    E⟨i,j⟩ = E⟨i,j-1⟩ + E_task(j) + S(j)
           + Σ_{p ∈ reads(j)}  E_r(p) · [i > l_j(p)]             (new loads)
           - Σ_{p ∈ reads(j)}  E_w(p) · [l_∞(p) = j] · [1 ≤ writer(p)]
                                       · [i ≤ writer(p)]          (store freed)
    E⟨j,j⟩ = E_s + Σ E_r(p) + E_task(j) + S(j)

then runs one of three DP combines over the same live column, selected by
the **static** ``mode`` argument (each mode jit-caches its own lowered
kernel — the paper's §4.3 sum DP and both §4.4 variants are all kernel
modes now):

* ``mode="sum"`` — ``dp[q, j] = min_{i ≤ j, E⟨i,j⟩ ≤ Q[q]} dp[q, i-1] +
  E⟨i,j⟩`` for every Q at once (the lane axis is the Q grid);
* ``mode="minimax"`` — the §4.4 storage minimization ``mm[j] = min_i
  max(mm[i-1], E⟨i,j⟩)`` (one real lane, budget +inf — Q_min is
  ``mns[n-1, 0]``);
* ``mode="exact_k"`` — the fixed-burst-count DP ``dp[b, j] = min_{i,
  E⟨i,j⟩ ≤ Q} combine(dp[b-1, i-1], E⟨i,j⟩)``: the lane axis carries the
  burst count b = 0..K, so the K-indexed table tiles through the identical
  slot-chunked column scan; the predecessor table is the previous column's
  lanes shifted one lane right (lane 0 refills +inf). ``combine`` is ``+``
  or ``max`` per the static ``combine_max`` flag (the pipeline-bottleneck
  variant).

Every mode tie-breaks its argmin to the smallest burst start: the tiles
are visited in order and a later tile wins only with a strictly smaller
value. With
``slot_chunk=1`` (default) the slot loop replays numpy's exact
accumulation order, so the emitted column tables are bit-identical to
:mod:`.ref` — and hence to the numpy DP oracles — including argmin
tie-breaks; ``slot_chunk>1`` processes slots in vectorized chunks (one
masked 2-D reduction per chunk, ~ulp drift; on exact dyadic-cost graphs the
chunked reductions are still exact, which the tie audit pins across all
three modes). Chunks are interpret-only: the compiled kernel reads slots
from SMEM one scalar at a time.

Compiled on a TPU the kernel runs float32 (f64 is interpret-only): the
same slot order, but cut positions may differ from numpy among candidates
closer than float32 can order. The engine's bit-identity guarantees are
stated for the f64 interpret path, the CPU path (the whole grid lowers to
one XLA while-loop). The ``(N, nq_pad)`` outputs and ``dpbuf`` stay
resident in VMEM; :func:`vmem_bytes` says how much, and
:func:`repro.kernels.partition_sweep.ops.sweep_columns` refuses sizes over
the scoped limit before lowering.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...obs.metrics import METRICS

# Trace-count regression hook: incremented at trace time only, so tests can
# assert that serving-style loops re-dispatch the cached kernel instead of
# re-tracing (see the enable_x64-hoist note in repro/core/partition_jax.py).
# Registry-backed (repro.obs.metrics) but still a plain dict to consumers.
TRACE_COUNT = METRICS.counter_dict(
    "kernel.partition_sweep.trace_count",
    ("sweep_columns", "sweep_columns_minimax", "sweep_columns_exact_k"),
)


def _sweep_kernel(
    ptr_ref,          # (N+1,)       i32  SMEM  read-slot row pointers
    etask_ref,        # (N,)         f    SMEM  E_task(j)
    store_ref,        # (N,)         f    SMEM  S(j)
    es_ref,           # (1,)         f    SMEM  E_s
    cost_ref,         # (nnz_pad,)   f    SMEM  E_r per read slot
    free_ref,         # (nnz_pad,)   f    SMEM  E_w of the read packet
    lt_ref,           # (nnz_pad,)   i32  SMEM  l_j(p)
    writer_ref,       # (nnz_pad,)   i32  SMEM  writer(p)
    linf_ref,         # (nnz_pad,)   i32  SMEM  l_∞(p)
    budget_ref,       # (1, nq_pad)  f    VMEM  Q·(1+rel)+abs, -inf padding
    mns_ref,          # (N, nq_pad)  f    out   dp[q, j] per column
    best_ref,         # (N, nq_pad)  i32  out   argmin burst start per column
    colbuf,           # (Npad, 1)    f    VMEM scratch: live column E⟨·,j⟩
    dpbuf,            # (Npad, nq)   f    VMEM scratch: dp[q, i-1] table
    *,
    n_tiles: int,
    tile: int,
    slot_chunk: int,
    dtype,
    mode: str,
    combine_max: bool,
):
    B, C = tile, slot_chunk
    j = pl.program_id(0) + np.int32(1)   # task / column index, 1..N

    # Shared scratch is initialized by the very first program in the grid.
    @pl.when(j == 1)
    def _():
        dpbuf[...] = jnp.full(dpbuf.shape, jnp.inf, dtype)
        if mode == "exact_k":
            # dp[b, 0]: the empty prefix is reachable with zero bursts only.
            lane = lax.broadcasted_iota(jnp.int32, (dpbuf.shape[1],), 0)
            dpbuf[0, :] = jnp.where(lane == 0, jnp.asarray(0.0, dtype), jnp.inf)
        else:
            dpbuf[0, :] = jnp.zeros((dpbuf.shape[1],), dtype)  # dp[q, 0] = 0
        colbuf[...] = jnp.zeros(colbuf.shape, dtype)

    e_j = etask_ref[j - 1]
    s_j = store_ref[j - 1]
    p0 = ptr_ref[j - 1]
    p1 = ptr_ref[j]
    rows = lax.broadcasted_iota(jnp.int32, (B, 1), 0)

    def tile_step(t, acc):
        """Advance i-tile t of column j and fold its candidates into acc."""
        base = pl.multiple_of(t * np.int32(B), B)
        i_vec = base + np.int32(1) + rows
        prev = i_vec < j                  # bursts ⟨i, j-1⟩ being extended
        colt = colbuf[pl.ds(base, B), :]
        colt = jnp.where(prev, colt + (e_j + s_j), colt)

        if C == 1:
            # Slot-at-a-time: numpy's exact accumulation order (bit parity).
            def slot(s, carry):
                colt, sum_er = carry
                idx = p0 + s
                sc = cost_ref[idx]
                colt = jnp.where(prev & (i_vec > lt_ref[idx]), colt + sc, colt)
                w = writer_ref[idx]
                freed = (linf_ref[idx] == j) & (w >= np.int32(1))
                colt = jnp.where(
                    prev & freed & (i_vec <= w), colt - free_ref[idx], colt
                )
                return colt, sum_er + sc

            colt, sum_er = lax.fori_loop(
                0, p1 - p0, slot, (colt, jnp.asarray(0.0, dtype))
            )
        else:
            # Chunked: one masked 2-D reduction per C slots (~ulp drift).
            # Interpret mode only (sweep_columns_call refuses it compiled):
            # a C-wide load from SMEM is no scalar load, and from VMEM its
            # dynamic lane offset is not provably 128-aligned.
            def window(ref, idx0):
                return ref[pl.ds(idx0, C)][None, :]

            def chunk(s, carry):
                colt, sum_er = carry
                idx0 = p0 + s * np.int32(C)
                lanes = idx0 + lax.broadcasted_iota(jnp.int32, (1, C), 1)
                valid = lanes < p1
                sc = jnp.where(valid, window(cost_ref, idx0), 0.0)
                sf = jnp.where(valid, window(free_ref, idx0), 0.0)
                slt = window(lt_ref, idx0)
                swr = window(writer_ref, idx0)
                sli = window(linf_ref, idx0)
                loads = jnp.sum(
                    jnp.where(i_vec > slt, sc, 0.0), axis=1, keepdims=True
                )
                freed = jnp.sum(
                    jnp.where(
                        ((sli == j) & (swr >= np.int32(1))) & (i_vec <= swr),
                        sf,
                        0.0,
                    ),
                    axis=1,
                    keepdims=True,
                )
                colt = jnp.where(prev, colt + loads - freed, colt)
                return colt, sum_er + jnp.sum(sc)

            nchunks = lax.div(p1 - p0 + np.int32(C - 1), np.int32(C))
            colt, sum_er = lax.fori_loop(
                0, nchunks, chunk, (colt, jnp.asarray(0.0, dtype))
            )

        # The new single-task burst ⟨j,j⟩ (left-to-right, ColumnSweep's
        # order); it lies in the last live tile, the others leave colt be.
        diag = es_ref[0] + sum_er + e_j + s_j
        colt = jnp.where(i_vec == j, diag, colt)
        colbuf[pl.ds(base, B), :] = colt

        # DP relaxation over this tile. dpbuf rows [base, base+B) hold
        # dp[q, i-1] for the tile's i values; rows ≥ j are still inf, so
        # beyond-diagonal candidates drop out automatically.
        dpt = dpbuf[pl.ds(base, B), :]
        if mode == "exact_k":
            # Lane b needs dp[b-1, i-1]: shift the burst-count axis one lane
            # right; lane 0 (zero bursts covering a non-empty prefix)
            # refills +inf, so the b=0 output row degenerates to an
            # all-infeasible column (val inf, argmin 1) that callers never
            # walk.
            dpt = jnp.concatenate(
                [jnp.full((B, 1), jnp.inf, dtype), dpt[:, :-1]], axis=1
            )
        masked = jnp.where(colt <= budget_ref[...], colt, jnp.inf)
        cand = jnp.maximum(dpt, masked) if combine_max else dpt + masked
        tmin = jnp.min(cand, axis=0, keepdims=True)             # (1, nq_pad)
        # First i achieving the min (the sentinel never survives: inf == inf
        # on an all-infeasible column still selects i = 1, like numpy's
        # argmin — infeasibility is carried by mns, bests are only walked
        # where finite).
        targ = jnp.min(
            jnp.where(cand == tmin, i_vec, np.int32(n_tiles * B + 1)),
            axis=0,
            keepdims=True,
        )

        # Cross-tile combine: strict < keeps the earliest tile on exact
        # ties, matching numpy's first-minimum argmin.
        accmin, accarg = acc
        better = tmin < accmin
        return jnp.minimum(accmin, tmin), jnp.where(better, targ, accarg)

    # Only the tiles with a row i ≤ j hold live bursts ⟨i, j⟩: the ones above
    # would leave colbuf as it is and offer only +inf candidates. (+inf, 1) is
    # what an all-infeasible first tile yields, so folding tile 0 into it
    # gives tile 0's own (min, argmin).
    n_live = lax.div(j + np.int32(B - 1), np.int32(B))
    nq_pad = mns_ref.shape[1]
    accmin, accarg = lax.fori_loop(
        0, n_live, tile_step,
        (jnp.full((1, nq_pad), jnp.inf, dtype),
         jnp.ones((1, nq_pad), jnp.int32)),
    )
    mns_ref[pl.ds(j - 1, 1), :] = accmin
    best_ref[pl.ds(j - 1, 1), :] = accarg

    @pl.when(j < dpbuf.shape[0])
    def _():
        dpbuf[pl.ds(j, 1), :] = accmin


def _tiling(n: int, tile: int) -> tuple:
    """(rows per i-tile B, number of tiles T) for an N-task sweep."""
    b = min(tile, max(8, n))
    return b, -(-n // b)


def tile_bodies(n: int, tile: int = 512) -> int:
    """Tile bodies one N-task sweep runs: Σ_j ⌈j/B⌉ over the columns j."""
    b, t = _tiling(n, tile)
    return b * t * (t - 1) // 2 + t * (n - (t - 1) * b)


def vmem_bytes(n: int, nq_pad: int, tile: int = 512) -> int:
    """Scoped VMEM the compiled kernel allocates for an N × nq_pad sweep.

    Mirrors the specs of :func:`sweep_columns_call` under the TPU's (8, 128)
    VMEM tiling: the two resident ``(N, nq_pad)`` output tables plus the
    ``(T·B, nq_pad)`` ``dpbuf``, ``(T·B, 1)`` ``colbuf`` and the two ``(1,
    nq_pad)`` accumulators the tile loop carries — 18.92 MiB at N=5458,
    nq_pad=256, exactly what the v5e compiler reports. The slot arrays live
    in SMEM and do not count. On top come the kernel body's temporaries,
    bounded by six ``(B, 1)`` columns and one ``(B, nq_pad)`` tile: fitted
    so that every size this estimate keeps under 16 MiB compiled for v5e
    across nq_pad 128–1024 and tile 128–512.
    """
    b, t = _tiling(n, tile)
    f = 4  # float32 and int32: the compiled kernel's dtypes
    up = lambda x, m: -(-x // m) * m
    lanes, rows, npad, b8 = up(nq_pad, 128), up(n, 8), up(t * b, 8), up(b, 8)
    return (
        rows * lanes * 2 * f          # mns + bests
        + npad * lanes * f            # dpbuf
        + npad * 128 * f              # colbuf
        + lanes * 2 * f               # accmin + accarg, loop-carried
        + b8 * (6 * 128 + lanes) * f  # body temporaries
    )


@functools.partial(
    jax.jit,
    static_argnames=("tile", "slot_chunk", "interpret", "mode", "combine_max"),
)
def sweep_columns_call(
    read_ptr,      # (N+1,)  i32
    e_task,        # (N,)    f
    store_add,     # (N,)    f
    e_startup,     # (1,)    f
    slot_cost,     # (nnz,)  f
    slot_free,     # (nnz,)  f
    slot_lt,       # (nnz,)  i32
    slot_writer,   # (nnz,)  i32
    slot_linf,     # (nnz,)  i32
    budget,        # (nq_pad,) f   already tolerance-scaled; -inf padding
    *,
    tile: int = 512,
    slot_chunk: int = 1,
    interpret: bool = True,
    mode: str = "sum",
    combine_max: bool = False,
):
    """Launch the sweep kernel: → (mns, bests), each ``(N, nq_pad)``.

    One grid program per column j, ``grid=(N,)``; inside it a loop over the
    ⌈j/B⌉ live i-tiles of B rows (``B = min(tile, max(8, N))``), so a call
    runs :func:`tile_bodies` tile bodies in all.
    Shapes are static per (N, nnz, nq_pad, tile, slot_chunk); the static
    ``mode`` / ``combine_max`` pair selects the DP combine (see module
    docstring) and keys the jit cache alongside them, so each objective
    caches its own lowered kernel and serving loops re-dispatch without
    re-tracing. The lane axis is the Q grid for ``mode="sum"``, a single
    real lane for ``"minimax"`` (budget lane 0 = +inf), and the burst
    count b = 0..K for ``"exact_k"`` (budget lanes 0..K = the single
    scaled Q_max, -inf beyond). Inputs are taken in whatever float dtype
    ``e_task`` carries (float64 under interpret mode — the
    differential-exact path — float32 for compiled TPU).
    """
    TRACE_COUNT[
        "sweep_columns" if mode == "sum" else f"sweep_columns_{mode}"
    ] += 1
    N = e_task.shape[0]
    nq_pad = budget.shape[0]
    dtype = e_task.dtype
    B, T = _tiling(N, tile)
    C = slot_chunk
    if C > 1 and not interpret:
        raise ValueError(
            f"slot_chunk={C} runs in interpret mode only: the compiled "
            "kernel reads the slot arrays from SMEM one scalar at a time; "
            "use slot_chunk=1"
        )
    nnz = slot_cost.shape[0]
    # Slot pool padded so every C-wide dynamic load stays in bounds without
    # clamping (clamped loads would misalign the validity mask).
    nnz_pad = (-(-max(nnz, 1) // C) + 1) * C

    def pad1(a):
        return jnp.pad(a, (0, nnz_pad - nnz))

    vspec = lambda shape: pl.BlockSpec(shape, lambda j: (0,) * len(shape))
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    kern = functools.partial(
        _sweep_kernel, n_tiles=T, tile=B, slot_chunk=C, dtype=dtype,
        mode=mode, combine_max=combine_max,
    )
    return pl.pallas_call(
        kern,
        grid=(N,),
        in_specs=[
            sspec, sspec, sspec, sspec,
            sspec, sspec, sspec, sspec, sspec,
            vspec((1, nq_pad)),
        ],
        out_specs=[vspec((N, nq_pad)), vspec((N, nq_pad))],
        out_shape=[
            jax.ShapeDtypeStruct((N, nq_pad), dtype),
            jax.ShapeDtypeStruct((N, nq_pad), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((T * B, 1), dtype),
            pltpu.VMEM((T * B, nq_pad), dtype),
        ],
        interpret=interpret,
    )(
        read_ptr, e_task, store_add, e_startup,
        pad1(slot_cost), pad1(slot_free), pad1(slot_lt),
        pad1(slot_writer), pad1(slot_linf), budget[None, :],
    )
