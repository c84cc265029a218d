"""Differential suite for the CSR/Pallas sweep-kernel subsystem.

Layers under test, bottom-up:

* ``TaskGraph.to_csr_arrays`` — round-trips against the dense ``to_arrays``
  export slot-for-slot; padding/stacking never changes a solution.
* ``kernels.partition_sweep.ref`` — the numpy CSR sweep is bit-identical to
  the numpy DP oracle (bounds included).
* ``kernels.partition_sweep.kernel`` (interpret mode) — bit-identical column
  tables (mns *and* argmin bests) against the ref, on random graphs, the
  adversarial equal-cost tie family, and lowered model-zoo graphs.
* backend plumbing through the façade — backend="pallas" returns the same
  JaxSweep as backend="scan"/numpy; backend="auto" routes by export size
  (``engine.resolve_jit_backend``);
  serving loops neither re-trace nor re-upload.
* slow: the full (unreduced) 5458-task head-count graphs solve end-to-end
  through the CSR backend — the dense export would be ~1 GB and is never
  materialized — reproducing the paper's 18-burst @ 132 mJ plan.
"""

import random

import numpy as np
import pytest

from helpers_random import (
    adversarial_tie_graph,
    random_cost_model,
    random_q_grid,
    random_task_graph,
    tie_cost_model,
    tie_q_grid,
)

from repro.core import (
    PAPER_FRAM_MODEL,
    CostModel,
    GraphBuilder,
    Infeasible,
    LinearTransfer,
    dense_export_nbytes,
    lower_config,
    q_min,
    stack_csr_arrays,
    tpu_host_offload_model,
    whole_app_partition,
)
from repro.core import engine, partition_jax
from repro.core.apps.headcount import THERMAL, VISUAL, build_graph
from repro.core.engine import resolve_jit_backend
from repro.core.partition_jax import sweep_from_columns
from repro.configs import REGISTRY
from repro.api import PartitionSpec, solve
from repro.core.partition import _optimal_k
from repro.kernels.partition_sweep import kernel as sweep_kernel
from repro.kernels.partition_sweep.ops import sweep_columns
from repro.kernels.partition_sweep.ref import (
    sweep_columns_exactk_ref,
    sweep_columns_minimax_ref,
    sweep_columns_ref,
)

CM = PAPER_FRAM_MODEL


def _numpy_partitions(graph, cost, q_values):
    """The numpy reference DP's per-Q partitions (None where infeasible)."""
    return solve(PartitionSpec(graph=graph, cost=cost, q_grid=q_values,
                               backend="numpy")).partitions()


def _sweep(graph, cost, q_values, backend="auto"):
    """The jitted single-graph sweep table."""
    return solve(PartitionSpec(graph=graph, cost=cost, q_grid=q_values,
                               backend=backend)).sweep


def _sweeps(graphs, cost, q_values, backend="auto"):
    """The jitted batched sweep tables, one per graph."""
    return solve(PartitionSpec(graphs=graphs, cost=cost, q_grid=q_values,
                               backend=backend)).sweeps


def _case(seed):
    rng = random.Random(seed)
    g = random_task_graph(rng, max_tasks=18)
    cm = random_cost_model(rng)
    qs = random_q_grid(rng, q_min(g, cm), whole_app_partition(g, cm).e_total)
    return g, cm, qs


def _tie_case(seed):
    rng = random.Random(9000 + seed)
    g = adversarial_tie_graph(rng)
    cm = tie_cost_model(rng)
    qs = tie_q_grid(rng, q_min(g, cm), whole_app_partition(g, cm).e_total)
    return g, cm, qs


def _assert_bitequal(a, b, ctx=""):
    assert ((a == b) | (np.isinf(a) & np.isinf(b))).all(), ctx


# -- CSR export ---------------------------------------------------------------


@pytest.mark.parametrize("seed", range(20))
def test_csr_roundtrip_vs_dense(seed):
    """The CSR export carries exactly the dense export's slots, in order."""
    g, _, _ = _case(seed)
    dense = g.to_arrays()
    csr = g.to_csr_arrays()
    assert csr.n_tasks == dense.n_tasks == g.n_tasks
    np.testing.assert_array_equal(csr.e_task, dense.e_task)
    assert csr.read_ptr[0] == 0 and csr.read_ptr[-1] == csr.nnz_reads
    for j in range(1, g.n_tasks + 1):
        lo, hi = int(csr.read_ptr[j - 1]), int(csr.read_ptr[j])
        deg = hi - lo
        assert deg == int(dense.read_valid[j - 1].sum())
        for name_d, name_c in (
            ("read_bytes", "read_bytes"),
            ("read_c0w", "read_c0w"),
            ("read_lt", "read_lt"),
            ("read_writer", "read_writer"),
            ("read_linf", "read_linf"),
        ):
            np.testing.assert_array_equal(
                getattr(csr, name_c)[lo:hi],
                getattr(dense, name_d)[j - 1, :deg],
                err_msg=f"task {j} {name_c}",
            )
        wlo, whi = int(csr.write_ptr[j - 1]), int(csr.write_ptr[j])
        wdeg = whi - wlo
        assert wdeg == int(dense.write_valid[j - 1].sum())
        np.testing.assert_array_equal(
            csr.write_bytes[wlo:whi], dense.write_bytes[j - 1, :wdeg]
        )
        np.testing.assert_array_equal(
            csr.write_linf[wlo:whi], dense.write_linf[j - 1, :wdeg]
        )


def test_csr_cache_and_padding():
    g, cm, qs = _case(3)
    assert g.to_csr_arrays() is g.to_csr_arrays()  # unpadded export cached
    csr = g.to_csr_arrays()
    pad = g.to_csr_arrays(
        n_pad=csr.n_pad + 5, r_pad=csr.nnz_reads + 7, w_pad=csr.nnz_writes + 3
    )
    assert pad.n_pad == csr.n_pad + 5 and pad.read_ptr.shape[0] == pad.n_pad + 1
    # padded rows own no slots
    assert (pad.read_ptr[csr.n_pad:] == csr.nnz_reads).all()
    with pytest.raises(ValueError):
        csr.padded(1, 1, 1)
    # a padded export solves identically
    a = sweep_from_columns(g.n_tasks, qs, *sweep_columns_ref(csr, cm, qs))
    b = sweep_from_columns(g.n_tasks, qs, *sweep_columns_ref(pad, cm, qs))
    _assert_bitequal(a.e_total, b.e_total)
    for qi in range(len(qs)):
        assert a.bounds(qi) == b.bounds(qi)


def test_stack_csr_arrays_batches_heterogeneous_graphs():
    graphs = [_case(s)[0] for s in (11, 12, 13, 14)]
    stacked = stack_csr_arrays([g.to_csr_arrays() for g in graphs])
    assert stacked.e_task.shape[0] == len(graphs)
    assert (np.asarray(stacked.n_tasks) == [g.n_tasks for g in graphs]).all()
    qs = [None, 0.5]
    for g, res in zip(graphs, _sweeps(graphs, CM, qs, backend="pallas")):
        ref = _numpy_partitions(g, CM, qs)
        for r, p in zip(ref, res.to_partitions(g, CM)):
            if r is None:
                assert p is None
            else:
                assert p is not None and p.e_total == r.e_total
                assert p.bounds == r.bounds


# -- ref vs numpy DP ----------------------------------------------------------


@pytest.mark.parametrize("seed", range(20))
def test_ref_matches_numpy_dp(seed):
    """The numpy CSR sweep is bit-identical to the numpy DP —
    e_total AND reconstructed bounds, including Infeasible cases."""
    g, cm, qs = _case(seed)
    ref = _numpy_partitions(g, cm, qs)
    res = sweep_from_columns(
        g.n_tasks, qs, *sweep_columns_ref(g.to_csr_arrays(), cm, qs)
    )
    for q, r, p in zip(qs, ref, res.to_partitions(g, cm)):
        if r is None:
            assert p is None, (seed, q)
        else:
            assert p is not None and p.e_total == r.e_total, (seed, q)
            assert p.bounds == r.bounds, (seed, q)
            p.validate(g)


# -- kernel (interpret) vs ref ------------------------------------------------


@pytest.mark.parametrize("seed", range(20))
def test_kernel_matches_ref_bitexact(seed):
    """Pallas kernel (interpret, slot_chunk=1) replays numpy's accumulation
    order: mns AND argmin bests are bit-identical to the CSR oracle."""
    g, cm, qs = _case(100 + seed)
    csr = g.to_csr_arrays()
    mr, br = sweep_columns_ref(csr, cm, qs)
    mk, bk = sweep_columns(csr, cm, qs, interpret=True)
    _assert_bitequal(mr, mk, seed)
    assert (br == bk).all(), seed


@pytest.mark.parametrize("tile", [8, 64])
def test_kernel_tile_size_invariance(tile):
    """Cross-tile min/argmin combining is associative with the first-minimum
    rule: any i-tiling gives the same tables."""
    g, cm, qs = _tie_case(0)
    csr = g.to_csr_arrays()
    mr, br = sweep_columns_ref(csr, cm, qs)
    mk, bk = sweep_columns(csr, cm, qs, tile=tile, interpret=True)
    _assert_bitequal(mr, mk, tile)
    assert (br == bk).all(), tile


def _tied_graph(n, combine_max):
    """n identical tasks whose every split ties exactly: for an additive
    combine each task costs 0.5 and a burst nothing more (dp[j] = j/2 by
    any split); for a max combine tasks are free and every burst costs its
    start-up 0.5 (a max over bursts is 0.5 by any split)."""
    b = GraphBuilder()
    for t in range(n):
        b.task(f"t{t}", cost=0.0 if combine_max else 0.5)
    zero = LinearTransfer(0.0, 0.0)
    es = 0.5 if combine_max else 0.0
    return b.build(), CostModel(e_startup=es, read=zero, write=zero,
                                name="tied")


def _mode_tables(csr, cm, mode, qs, slot_chunk, tile):
    """(ref, kernel) (mns, bests) of one kernel mode on one export."""
    kw = dict(slot_chunk=slot_chunk, tile=tile, interpret=True)
    if mode == "sum":
        return (sweep_columns_ref(csr, cm, qs),
                sweep_columns(csr, cm, qs, **kw))
    if mode == "minimax":
        return (sweep_columns_minimax_ref(csr, cm),
                sweep_columns(csr, cm, (), objective="minimax", **kw))
    kobj = mode.rsplit("_", 1)[1]
    K = max(1, csr.n_pad // 2)
    return (sweep_columns_exactk_ref(csr, cm, qs[2], K, kobj),
            sweep_columns(csr, cm, (qs[2],), objective="exact_k",
                          n_bursts=K, k_objective=kobj, **kw))


@pytest.mark.parametrize("slot_chunk", [1, 4])
@pytest.mark.parametrize("mode", ["sum", "minimax", "exact_k_sum",
                                  "exact_k_max"])
@pytest.mark.parametrize("n", [5, 8, 9, 24, 29])
def test_kernel_live_tile_loop_bitexact(n, mode, slot_chunk):
    """Each column visits only its ⌈j/B⌉ live i-tiles: at every size around
    the tile B = 8 (N < B, N = B, B + 1, 3B, 3B + 5), in every mode and
    both slot-loop modes, mns AND argmin bests stay bit-identical to the
    oracles — on a dyadic graph (exact whatever the summation order) and
    on one where every split ties, so ties fall across tile boundaries and
    the earliest tile must keep them."""
    B = 8
    rng = random.Random(9500 + n)
    g = adversarial_tie_graph(rng, max_tasks=n, min_tasks=n)
    cm = tie_cost_model(rng)
    qs = tie_q_grid(rng, q_min(g, cm), whole_app_partition(g, cm).e_total)
    gt, cmt = _tied_graph(n, combine_max=mode in ("minimax", "exact_k_max"))
    # a budget of four tied tasks' worth of energy, on the exact lattice
    qst = [None, 2.0, 2.0]
    for graph, cost, grid in ((g, cm, qs), (gt, cmt, qst)):
        csr = graph.to_csr_arrays()
        assert csr.n_pad == n
        (mr, br), (mk, bk) = _mode_tables(csr, cost, mode, grid, slot_chunk,
                                          tile=B)
        ctx = (n, mode, slot_chunk, cost.name)
        _assert_bitequal(mr, mk, ctx)
        assert (br == bk).all(), ctx
    if mode in ("sum", "minimax"):
        # unbounded lane of the tied graph: the last column's optimum is
        # reached from every start i, and i = 1 of the first tile wins
        assert bk[-1, 0] == 1, ctx


def test_kernel_chunked_slots_close_to_ref():
    """slot_chunk>1 vectorizes the slot loop (TPU throughput mode): values
    drift by ulps only; exact dyadic graphs stay bit-equal."""
    g, cm, qs = _case(7)
    csr = g.to_csr_arrays()
    mr, _ = sweep_columns_ref(csr, cm, qs)
    mk, _ = sweep_columns(csr, cm, qs, slot_chunk=4, interpret=True)
    fin = np.isfinite(mr)
    assert (np.isfinite(mk) == fin).all()
    np.testing.assert_allclose(mk[fin], mr[fin], rtol=1e-9, atol=0)
    gt, cmt, qst = _tie_case(3)
    csrt = gt.to_csr_arrays()
    mrt, brt = sweep_columns_ref(csrt, cmt, qst)
    mkt, bkt = sweep_columns(csrt, cmt, qst, slot_chunk=4, interpret=True)
    _assert_bitequal(mrt, mkt)
    assert (brt == bkt).all()


# -- minimax / exact-K kernel modes (§4.4 objective matrix) -------------------


def _exactk_bounds(bsts, n, n_bursts):
    """The shared host parent walk over an exact-K (vals, bsts) table."""
    bounds = []
    j, b = n, n_bursts
    while j > 0:
        i = int(bsts[j - 1, b])
        bounds.append((i, j))
        j, b = i - 1, b - 1
    bounds.reverse()
    return bounds


@pytest.mark.parametrize("seed", range(20))
def test_minimax_ref_matches_numpy_qmin(seed):
    """The minimax CSR oracle's mm[n] is bit-identical to the numpy q_min
    (max/min combines are exact in float64)."""
    g, cm, _ = _case(300 + seed)
    mns, bests = sweep_columns_minimax_ref(g.to_csr_arrays(), cm)
    assert mns[g.n_tasks - 1, 0] == q_min(g, cm), seed
    assert (bests >= 1).all()


@pytest.mark.parametrize("seed", range(20))
def test_minimax_kernel_matches_ref_bitexact(seed):
    """Pallas minimax mode (interpret, slot_chunk=1) is bit-identical to the
    CSR oracle — mns AND argmin bests, every column."""
    g, cm, _ = _case(300 + seed)
    csr = g.to_csr_arrays()
    mr, br = sweep_columns_minimax_ref(csr, cm)
    mk, bk = sweep_columns(csr, cm, (), objective="minimax", interpret=True)
    _assert_bitequal(mr, mk, seed)
    assert (br == bk).all(), seed


@pytest.mark.parametrize("seed", range(12))
def test_exactk_ref_matches_numpy_dp(seed):
    """The exact-K CSR oracle reconstructs the numpy _optimal_k partition —
    bounds AND e_total — for both combines, feasible and infeasible Qs."""
    g, cm, qs = _case(320 + seed)
    n = g.n_tasks
    csr = g.to_csr_arrays()
    for K in sorted({1, max(1, n // 2), n}):
        for kobj in ("sum", "max"):
            for q in (None, qs[2]):
                vals, bsts = sweep_columns_exactk_ref(csr, cm, q, K, kobj)
                try:
                    part = _optimal_k(g, cm, K, q, kobj)
                except Infeasible:
                    assert not np.isfinite(vals[n - 1, K]), (seed, K, kobj, q)
                    continue
                assert np.isfinite(vals[n - 1, K]), (seed, K, kobj, q)
                assert _exactk_bounds(bsts, n, K) == part.bounds, \
                    (seed, K, kobj, q)


@pytest.mark.parametrize("kobj", ["sum", "max"])
@pytest.mark.parametrize("seed", range(12))
def test_exactk_kernel_matches_ref_bitexact(seed, kobj):
    """Pallas exact_k mode (interpret, slot_chunk=1): the burst-count lane
    axis reproduces the CSR oracle's (vals, bsts) bit-for-bit, including
    the degenerate b=0 lane (inf, parent 1)."""
    g, cm, qs = _case(320 + seed)
    n = g.n_tasks
    csr = g.to_csr_arrays()
    for K in sorted({1, max(1, n // 2), n}):
        for q in (None, qs[2]):
            vr, br = sweep_columns_exactk_ref(csr, cm, q, K, kobj)
            vk, bk = sweep_columns(
                csr, cm, (q,), objective="exact_k", n_bursts=K,
                k_objective=kobj, interpret=True,
            )
            _assert_bitequal(vr, vk, (seed, K, kobj, q))
            assert (br == bk).all(), (seed, K, kobj, q)
            _assert_bitequal(vr[:, 0], np.full(vr.shape[0], np.inf))
            assert (br[:, 0] == 1).all()


@pytest.mark.parametrize("tile", [8, 64])
def test_objective_modes_tile_invariance(tile):
    """Cross-tile combining in the minimax and exact-K modes keeps the
    first-minimum rule under any i-tiling (the exact-K lane shift must not
    interact with tile boundaries)."""
    g, cm, qs = _tie_case(0)
    csr = g.to_csr_arrays()
    mr, br = sweep_columns_minimax_ref(csr, cm)
    mk, bk = sweep_columns(
        csr, cm, (), objective="minimax", tile=tile, interpret=True
    )
    _assert_bitequal(mr, mk, tile)
    assert (br == bk).all(), tile
    K = max(1, g.n_tasks // 2)
    vr, brr = sweep_columns_exactk_ref(csr, cm, qs[2], K, "sum")
    vk, bkk = sweep_columns(
        csr, cm, (qs[2],), objective="exact_k", n_bursts=K, tile=tile,
        interpret=True,
    )
    _assert_bitequal(vr, vk, tile)
    assert (brr == bkk).all(), tile


@pytest.mark.parametrize("backend", ["scan", "pallas"])
def test_engine_objectives_match_numpy(backend):
    """Engine.solve routes minimax/exact_k to the named jit backend and both
    reproduce the numpy oracles (pallas bit-identically on every graph)."""
    for seed in (5, 17, 23):
        g, cm, qs = _case(340 + seed)
        s = solve(PartitionSpec(graph=g, cost=cm, objective="minimax",
                                backend=backend))
        assert s.q_min() == q_min(g, cm), (seed, backend)
        K = max(1, g.n_tasks // 2)
        for kobj in ("sum", "max"):
            ref = _optimal_k(g, cm, K, None, kobj)
            p = solve(PartitionSpec(graph=g, cost=cm, objective="exact_k",
                                    n_bursts=K, k_objective=kobj,
                                    backend=backend)).partition()
            assert p.bounds == ref.bounds and p.e_total == ref.e_total, \
                (seed, backend, kobj)


def test_csr_export_minimax_routes_to_pallas():
    """A GraphCSRArrays export now solves minimax under backend='auto' (it
    used to be an ExportMismatch — no minimax-capable backend took CSR)."""
    g, cm, _ = _case(6)
    s = solve(PartitionSpec(graph=g.to_csr_arrays(), cost=cm,
                            objective="minimax"))
    assert s.backend == "pallas"
    assert s.q_min() == q_min(g, cm)
    assert resolve_jit_backend(
        g.to_csr_arrays(), "auto", objective="minimax") == "pallas"


def test_zoo_config_objectives_pallas_matches_numpy():
    """A lowered model-zoo graph (coalesced fractional weights) through the
    minimax and exact-K kernel modes, bit-identical to numpy."""
    cm = tpu_host_offload_model()
    g = lower_config(REGISTRY["qwen1.5-0.5b"], batch=2, seq=256)
    s = solve(PartitionSpec(graph=g, cost=cm, objective="minimax",
                            backend="pallas"))
    assert s.q_min() == q_min(g, cm)
    ref = _optimal_k(g, cm, 4, None, "sum")
    p = solve(PartitionSpec(graph=g, cost=cm, objective="exact_k",
                            n_bursts=4, backend="pallas")).partition()
    assert p.bounds == ref.bounds and p.e_total == ref.e_total


# -- three-way exact-tie audit (ROADMAP) --------------------------------------


@pytest.mark.parametrize("seed", range(20))
def test_tie_audit_numpy_scan_pallas(seed):
    """On the adversarial equal-cost family every summation order is exact,
    so ties are exact ties everywhere — numpy DP, scan backend, and
    CSR/Pallas backend must agree on e_total bits AND reconstructed bounds
    (argmin tie-break: smallest burst start wins)."""
    g, cm, qs = _tie_case(seed)
    ref = _numpy_partitions(g, cm, qs)
    scan = _sweep(g, cm, qs, backend="scan")
    pall = _sweep(g, cm, qs, backend="pallas")
    _assert_bitequal(scan.dp, pall.dp, seed)
    for qi, (q, r) in enumerate(zip(qs, ref)):
        if r is None:
            assert not scan.feasible[qi] and not pall.feasible[qi], (seed, q)
            continue
        assert scan.e_total[qi] == r.e_total == pall.e_total[qi], (seed, q)
        assert scan.bounds(qi) == r.bounds == pall.bounds(qi), (seed, q)


@pytest.mark.parametrize("slot_chunk", [1, 4])
@pytest.mark.parametrize("seed", range(12))
def test_tie_audit_chunked_all_objectives(seed, slot_chunk):
    """The exact-tie audit at both slot-loop modes, all three kernel
    objectives: dyadic costs make even the chunked 2-D reductions exact, so
    slot_chunk>1 must keep mns AND argmin bests bit-identical to the
    oracles — not just ~ulp-close (this pins the chunked max/argmin
    reduction's tie-breaks, which the slot_chunk=1 audit never exercised)."""
    g, cm, qs = _tie_case(seed)
    csr = g.to_csr_arrays()
    mr, br = sweep_columns_ref(csr, cm, qs)
    mk, bk = sweep_columns(csr, cm, qs, slot_chunk=slot_chunk, interpret=True)
    _assert_bitequal(mr, mk, ("sum", seed, slot_chunk))
    assert (br == bk).all(), ("sum", seed, slot_chunk)
    mr2, br2 = sweep_columns_minimax_ref(csr, cm)
    mk2, bk2 = sweep_columns(
        csr, cm, (), objective="minimax", slot_chunk=slot_chunk,
        interpret=True,
    )
    _assert_bitequal(mr2, mk2, ("minimax", seed, slot_chunk))
    assert (br2 == bk2).all(), ("minimax", seed, slot_chunk)
    K = max(1, g.n_tasks // 2)
    for kobj in ("sum", "max"):
        for q in (None, qs[2]):
            vr, brr = sweep_columns_exactk_ref(csr, cm, q, K, kobj)
            vk, bkk = sweep_columns(
                csr, cm, (q,), objective="exact_k", n_bursts=K,
                k_objective=kobj, slot_chunk=slot_chunk, interpret=True,
            )
            _assert_bitequal(vr, vk, ("exact_k", seed, slot_chunk, kobj, q))
            assert (brr == bkk).all(), ("exact_k", seed, slot_chunk, kobj, q)


# -- engine integration -------------------------------------------------------


@pytest.mark.parametrize("seed", range(10))
def test_engine_pallas_vs_scan(seed):
    g, cm, qs = _case(200 + seed)
    a = _sweep(g, cm, qs, backend="scan")
    b = _sweep(g, cm, qs, backend="pallas")
    _assert_bitequal(a.dp, b.dp, seed)
    assert (a.feasible == b.feasible).all()
    for qi in range(len(qs)):
        assert a.bounds(qi) == b.bounds(qi), (seed, qi)


def test_backend_selection():
    g, _, _ = _case(1)
    assert resolve_jit_backend(g, "scan") == "scan"
    assert resolve_jit_backend(g, "pallas") == "pallas"
    assert resolve_jit_backend(g, "auto") == "scan"  # tiny graph
    assert resolve_jit_backend(g.to_arrays(), "auto") == "scan"
    assert resolve_jit_backend(g.to_csr_arrays(), "auto") == "pallas"
    with pytest.raises(ValueError):
        resolve_jit_backend(g, "mosaic")
    # explicit exports refuse the wrong backend instead of silently converting
    with pytest.raises(TypeError):
        _sweep(g.to_csr_arrays(), CM, [None], backend="scan")
    with pytest.raises(TypeError):
        _sweep(g.to_arrays(), CM, [None], backend="pallas")
    # the full head-count shape routes to pallas purely by export size
    full = THERMAL
    n = full.n_tasks
    r = sum(full.n_cnn)  # the sort task's read degree
    assert dense_export_nbytes(n, r, 1) > engine._AUTO_DENSE_BYTES


def test_auto_threshold_routes_small_graph(monkeypatch):
    g, cm, qs = _case(2)
    monkeypatch.setattr(engine, "_AUTO_DENSE_BYTES", 0)
    assert resolve_jit_backend(g, "auto") == "pallas"
    res = _sweep(g, cm, qs)  # default backend="auto" → pallas
    ref = _sweep(g, cm, qs, backend="scan")
    _assert_bitequal(res.dp, ref.dp)


def test_batched_auto_mixed_exports(monkeypatch):
    """A legal mixed batch — dense export, CSR export, TaskGraphs resolving
    to different backends — solves per-group under backend='auto' with
    order preserved."""
    g1, g2, g3 = (_case(40 + s)[0] for s in range(3))
    monkeypatch.setattr(engine, "_AUTO_DENSE_BYTES", 0)  # g3 → pallas
    qs = [None, 0.5]
    batch = [g1.to_arrays(), g2.to_csr_arrays(), g3]
    results = _sweeps(batch, CM, qs)
    for g, res in zip((g1, g2, g3), results):
        ref = _numpy_partitions(g, CM, qs)
        for r, p in zip(ref, res.to_partitions(g, CM)):
            if r is None:
                assert p is None
            else:
                assert p is not None and p.e_total == r.e_total
                assert p.bounds == r.bounds


def test_batched_pallas_reuses_padded_rows():
    """Repeated batched solves must hand identical padded-row objects to the
    kernel wrapper (whose device cache is id-keyed): no per-request
    re-padding, re-pricing, or re-upload."""
    graphs = [_case(50 + s)[0] for s in range(3)]
    csrs = [g.to_csr_arrays() for g in graphs]
    n = max(a.n_pad for a in csrs)
    r = max(max(a.nnz_reads for a in csrs), 1)
    w = max(max(a.nnz_writes for a in csrs), 1)
    rows = [partition_jax._padded_csr(a, n, r, w) for a in csrs]
    again = [partition_jax._padded_csr(a, n, r, w) for a in csrs]
    assert all(x is y for x, y in zip(rows, again))
    # already-matching shapes short-circuit to the export itself
    assert partition_jax._padded_csr(rows[0], n, r, w) is rows[0]
    qs = [None, 1.0]
    first = _sweeps(graphs, CM, qs, backend="pallas")
    traces = sweep_kernel.TRACE_COUNT["sweep_columns"]
    second = _sweeps(graphs, CM, qs, backend="pallas")
    assert sweep_kernel.TRACE_COUNT["sweep_columns"] == traces
    for a, b in zip(first, second):
        _assert_bitequal(a.dp, b.dp)


def test_empty_and_single_task_pallas():
    assert _sweep(GraphBuilder().build(), CM, [None, 0.0],
                     backend="pallas").feasible.all()
    b = GraphBuilder()
    b.packet("x", 128, keep=True)
    b.task("t", writes=("x",), cost=1.0)
    g = b.build()
    p = solve(PartitionSpec(graph=g, cost=CM, q_max=None,
                            backend="pallas")).partition()
    assert p.n_bursts == 1
    with pytest.raises(Infeasible):
        solve(PartitionSpec(graph=g, cost=CM, q_max=1e-9,
                            backend="pallas")).partition()


def test_zoo_config_pallas_matches_numpy():
    cm = tpu_host_offload_model()
    g = lower_config(REGISTRY["qwen1.5-0.5b"], batch=2, seq=256)
    qs = [None, q_min(g, cm), q_min(g, cm) * 4]
    ref = _numpy_partitions(g, cm, qs)
    res = _sweep(g, cm, qs, backend="pallas")
    for q, r, p in zip(qs, ref, res.to_partitions(g, cm)):
        assert p is not None and r is not None
        assert p.e_total == r.e_total and p.bounds == r.bounds, q


def test_headcount_reduced_pallas_matches_numpy():
    """Coalesced sub-packet weights (fractional c0_weight) through the CSR
    path; slot-at-a-time order keeps even these bit-exact vs numpy."""
    g = build_graph(THERMAL.reduced(256))
    qmn = q_min(g, CM)
    qs = list(np.geomspace(qmn, g.total_task_cost() * 1.05, 16)) + [None, 0.0]
    ref = _numpy_partitions(g, CM, qs)
    res = _sweep(g, CM, qs, backend="pallas")
    for q, r, p in zip(qs, ref, res.to_partitions(g, CM)):
        if r is None:
            assert p is None
            continue
        assert p is not None
        assert p.e_total == r.e_total and p.bounds == r.bounds, q
        p.validate(g)


def test_serving_loop_no_retrace_no_reupload():
    """ROADMAP 'hoist dtype handling': repeated solves of one application
    must not re-trace either backend nor re-upload the graph per request."""
    g, cm, _ = _case(4)
    qs1, qs2 = [None, 1.0], [None, 2.0]
    _sweep(g, cm, qs1, backend="scan")
    _sweep(g, cm, qs1, backend="pallas")
    t_scan = partition_jax.TRACE_COUNT["dp_sweep"]
    t_pall = sweep_kernel.TRACE_COUNT["sweep_columns"]
    ga_id = id(partition_jax._ga_dict(g.to_arrays()))
    for qs in (qs1, qs2, qs1):
        a = _sweep(g, cm, qs, backend="scan")
        b = _sweep(g, cm, qs, backend="pallas")
        _assert_bitequal(a.dp, b.dp)
    assert partition_jax.TRACE_COUNT["dp_sweep"] == t_scan
    assert sweep_kernel.TRACE_COUNT["sweep_columns"] == t_pall
    assert id(partition_jax._ga_dict(g.to_arrays())) == ga_id  # device-cached


# -- the paper's application, unreduced (slow) --------------------------------


@pytest.mark.slow
def test_full_headcount_solves_through_csr_backend():
    """The acceptance check: both full 5458-task graphs solve end-to-end via
    the CSR backend (the dense (N, R) read matrix — ~238 MB of float64 —
    is never materialized), the thermal plan reproduces the paper's
    18 bursts @ 132 mJ, bounds on the reduced cross-check are bit-equal to
    the numpy DP oracle, and the CSR export is ≥ 50× smaller than dense.
    """
    for spec in (THERMAL, VISUAL):
        g = build_graph(spec)
        assert resolve_jit_backend(g, "auto") == "pallas"
        csr = g.to_csr_arrays()
        r = max(len(t.reads) for t in g.tasks)
        w = max(len(t.writes) for t in g.tasks)
        dense_bytes = dense_export_nbytes(g.n_tasks, r, w)
        assert dense_bytes >= 50 * csr.nbytes, (dense_bytes, csr.nbytes)

        qs = [132e-3, None]
        res = _sweep(g, CM, qs)  # auto → pallas
        assert res.feasible.all()
        e_app = g.total_task_cost()
        assert res.e_total[1] >= e_app  # total can't beat pure execution
        bounds = res.bounds(0)
        assert bounds is not None and bounds[0][0] == 1
        assert bounds[-1][1] == g.n_tasks
        if spec is THERMAL:
            assert len(bounds) == 18  # paper Fig. 6
            overhead = (res.e_total[0] - e_app) / res.e_total[0]
            assert overhead < 0.0012  # paper: 0.12 %

    # reduced cross-check: same pipeline, bounds bit-equal to the numpy DP
    g = build_graph(THERMAL.reduced(64))
    qs = [132e-3, q_min(g, CM), None]
    ref = _numpy_partitions(g, CM, qs)
    res = _sweep(g, CM, qs, backend="pallas")
    for q, r_, p in zip(qs, ref, res.to_partitions(g, CM)):
        assert r_ is not None and p is not None
        assert p.e_total == r_.e_total and p.bounds == r_.bounds, q


@pytest.mark.slow
def test_full_headcount_minimax_exactk_pallas_vs_numpy():
    """Objective-matrix acceptance on the unreduced 5458-task graph: the
    kernel's minimax and exact-K modes are bit-identical to the numpy
    q_min / _optimal_k oracles at full scale (the numpy side column-sweeps
    the TaskGraph; the kernel side never materializes the dense export)."""
    g = build_graph(THERMAL)
    assert g.n_tasks == 5458

    s = solve(PartitionSpec(graph=g, cost=CM, objective="minimax",
                            backend="pallas"))
    assert s.q_min() == q_min(g, CM)

    # the paper's plan shape: exactly 18 bursts under the 132 mJ capacitor
    ref = _optimal_k(g, CM, 18, 132e-3)
    p = solve(PartitionSpec(graph=g, cost=CM, objective="exact_k",
                            n_bursts=18, q_max=132e-3,
                            backend="pallas")).partition()
    assert p.bounds == ref.bounds and p.e_total == ref.e_total
    assert p.n_bursts == 18


# -- compiled-path guards (checked on the CPU before anything lowers) --------


def test_vmem_estimate_matches_compiler_report():
    """The resident tables + scratch at N=5458, nq_pad=256 are the 18.92 MiB
    the v5e compiler reports when it refuses that size (tests/
    test_chip_compile.py compiles the boundary); the body's temporaries,
    one (B, nq) tile and six (B, 1) columns, come on top."""
    from repro.kernels.partition_sweep.kernel import vmem_bytes

    tables = 5464 * 256 * 8 + 5632 * 256 * 4 + 5632 * 128 * 4 + 256 * 8
    assert round(tables / 2**20, 2) == 18.92
    assert vmem_bytes(5458, 256) == tables + 512 * (6 * 128 + 256) * 4
    # (8, 128) tiling: lane widths round up to 128, rows to 8
    assert vmem_bytes(5458, 16) == vmem_bytes(5458, 128)
    assert vmem_bytes(13, 8) == vmem_bytes(16, 8)


def test_vmem_guard_refuses_before_lowering():
    from repro.kernels.partition_sweep.ops import (
        SCOPED_VMEM_BYTES, VmemLimitExceeded,
    )

    g = build_graph(THERMAL)
    csr = g.to_csr_arrays()
    traced = sweep_kernel.TRACE_COUNT["sweep_columns"]
    # 1024 lanes x 5458 tasks: ~67 MiB resident against 16 MiB
    with pytest.raises(VmemLimitExceeded, match="limit is 16 MiB"):
        sweep_columns(csr, CM, list(np.linspace(0.1, 3.0, 1024)),
                      interpret=False)
    assert sweep_kernel.TRACE_COUNT["sweep_columns"] == traced
    assert SCOPED_VMEM_BYTES == 16 * 2**20


def test_chunked_slots_refused_on_compiled_path():
    csr = build_graph(THERMAL.reduced(16)).to_csr_arrays()
    with pytest.raises(ValueError, match="interpret mode only"):
        sweep_columns(csr, CM, [None], slot_chunk=8, interpret=False)
