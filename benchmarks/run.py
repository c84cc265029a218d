"""Benchmark harness — one function per paper table/figure + framework tables.

Prints ``name,value,derived`` CSV rows (timing rows use µs per call).
Paper tables/figures covered:

* Table 1/2  — kernel energy characterization (model inputs, checked sums)
* Fig. 6     — Single Task vs Julienning vs Whole Application (thermal)
* Fig. 7     — design space: N_bursts vs Q_max (both sensor variants)
* Fig. 8     — design space: E_total overhead vs Q_max
* §4.3       — optimizer scaling (the O(n²) column sweep vs the paper's O(n³·|P|))

Framework tables (beyond paper):

* julienne planners (pipeline / offload / remat) over the model zoo
* roofline summary per (arch × shape × mesh) from experiments/dryrun/*.json
* Pallas kernel microbenches (CPU interpret mode — correctness-path timing)
* partition_sweep: scan vs CSR/Pallas sweep backends + export footprints
  (also written to BENCH_partition_sweep.json)
* plan_table: offline table build vs O(1) request-path lookup vs the
  per-request re-plan it replaces (also written to BENCH_plan_table.json)

CLI: ``--section NAME`` runs one section (default: all);
``--backend {scan,pallas,auto}`` and ``--smoke`` scope the partition_sweep
and plan_table sections so CI can smoke-run them; ``--json-out`` overrides
the JSON path.
"""

import argparse
import glob
import json
import os
import sys
import time

# The sharded-DSE section wants an emulated multi-device host. jax locks the
# device count at first initialization (same constraint as launch/dryrun.py),
# so when that section was explicitly requested and the operator didn't pick
# their own topology, set the flag before anything imports jax.
if "XLA_FLAGS" not in os.environ and any(
    a == "plan_table_sharded" or a.endswith("=plan_table_sharded")
    for a in sys.argv[1:]
):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.api import PartitionSpec, solve  # noqa: E402
from repro.core import (  # noqa: E402
    PAPER_FRAM_MODEL, q_min, single_task_partition, whole_app_partition)
from repro.core.apps.headcount import THERMAL, VISUAL, build_graph  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

CM = PAPER_FRAM_MODEL


def _np_partition(g, cm, q_max):
    """One numpy-backend partition through the façade."""
    return solve(PartitionSpec(graph=g, cost=cm, q_max=q_max,
                               backend="numpy")).partition()


def _np_sweep(g, cm, qs):
    """Numpy-backend Q-grid sweep through the façade (the old ``sweep``)."""
    return solve(PartitionSpec(graph=g, cost=cm, q_grid=tuple(qs),
                               backend="numpy")).partitions()


def _row(name, value, derived=""):
    print(f"{name},{value},{derived}")


def table12_energy_characterization():
    g = build_graph(THERMAL)
    _row("table2.n_tasks", g.n_tasks, "paper=5458")
    _row("table2.e_app_J", f"{g.total_task_cost():.6f}", "paper=2.294")
    _row("table2.cnn1_sum_mJ", f"{4125 * 0.396:.1f}", "paper=1633.5")
    _row("table2.cnn2_sum_mJ", f"{936 * 0.396:.1f}", "paper=370.7")
    _row("table2.cnn3_sum_mJ", f"{391 * 0.403:.1f}", "paper=157.6")
    _row("table1.thermal_sense_mJ", 131.9, "measured in paper")
    _row("table1.visual_sense_mJ", 4.4, "measured in paper")


def fig6_partitioning_comparison():
    g = build_graph(THERMAL)
    t0 = time.time()
    jl = _np_partition(g, CM, 132e-3)
    t_opt = (time.time() - t0) * 1e6
    st = single_task_partition(g, CM)
    wa = whole_app_partition(g, CM)
    _row("fig6.julienne.n_bursts", jl.n_bursts, "paper=18")
    _row("fig6.julienne.overhead_pct",
         f"{100 * jl.e_overhead / jl.e_total:.3f}", "paper=0.12")
    _row("fig6.julienne.overhead_mJ", f"{jl.e_overhead * 1e3:.2f}", "paper=2.79")
    _row("fig6.single_task.n_bursts", st.n_bursts, "paper=5458")
    _row("fig6.single_task.MB_transferred",
         f"{st.transfer_bytes / 1e6:.1f}", "paper>437")
    _row("fig6.single_task.overhead_gt_app",
         int(st.e_overhead > st.e_app), "paper: overhead larger than E_app")
    _row("fig6.whole_app.storage_J", f"{wa.max_burst:.4f}", "needs 2.294 J")
    _row("fig6.storage_reduction_pct",
         f"{100 * (1 - q_min(g, CM) / wa.max_burst):.2f}", "paper>94")
    _row("fig6.optimizer_us_per_call", f"{t_opt:.0f}", "5458-task partition")


def fig7_fig8_design_space():
    for spec in (THERMAL, VISUAL):
        g = build_graph(spec)
        qmn = q_min(g, CM)
        qs = np.geomspace(qmn, g.total_task_cost() * 1.05, 12)
        parts = _np_sweep(g, CM, qs)
        for q, p in zip(qs, parts):
            if p is None:
                continue
            _row(f"fig7.{spec.name}.nbursts@Q={q * 1e3:.1f}mJ", p.n_bursts,
                 f"E_total={p.e_total * 1e3:.2f}mJ")
        feas = [p.n_bursts for p in parts if p is not None]
        _row(f"fig7.{spec.name}.feasible_range", f"1-{max(feas)}",
             "paper: thermal 1-18, visual 1-456")
        # Fig 8 caption: overhead < 3% down to storage bounds of 4.3% E_app
        # (thermal's Q_min is already 5.8% of E_app, so report its smallest
        # feasible point; visual reaches 0.2%).
        small = next(p for p in parts if p is not None)
        _row(f"fig8.{spec.name}.overhead_pct@Qmin",
             f"{100 * small.e_overhead / small.e_total:.3f}",
             f"paper<3% ; Qmin={qs[0] * 1e3:.1f}mJ="
             f"{100 * qs[0] / g.total_task_cost():.1f}%Eapp")


def optimizer_scaling():
    from repro.core import GraphBuilder

    for n in (512, 2048, 8192):
        b = GraphBuilder()
        b.packet("x", 1024, external=True)
        for i in range(n):
            w = b.packet(f"p{i}", 64)
            b.task(f"t{i}", reads=("x",), writes=(w,), cost=1e-4)
        g = b.build()
        t0 = time.time()
        _np_partition(g, CM, 0.05)
        _row(f"scaling.partition_n={n}_us", f"{(time.time() - t0) * 1e6:.0f}",
             "column-sweep O(n^2); paper O(n^3 |P|)")


def partition_jax_engine():
    """Jitted batched engine vs the numpy `sweep` path (same outputs: optimal
    E_total + bounds per Q). Headcount Q-grid sweeps at two reductions, the
    optimizer-scaling ladder, and the whole zoo in one vmapped batch."""
    from repro.core import lower_zoo, q_min as qmin_np, tpu_host_offload_model

    def best_of(f, n=3):
        ts = []
        for _ in range(n):
            t0 = time.time()
            f()
            ts.append(time.time() - t0)
        return min(ts)

    # Output parity note: sweep() eagerly builds full Partition objects
    # (per-burst details) per feasible Q; the engine returns the DSE answers
    # (e_total + bounds per Q) as arrays. The speedup row compares those
    # paths as a consumer would call them; the *_jax_full_parts_ms row adds
    # the cost of materializing every Partition from the jax result too.
    for scale in (192, 128, 64):
        g = build_graph(THERMAL.reduced(scale))
        qmn = qmin_np(g, CM)
        qs = list(np.geomspace(qmn, g.total_task_cost() * 1.05, 4096))
        spec = PartitionSpec(graph=g, cost=CM, q_grid=tuple(qs))
        solve(spec)  # compile outside the timed region
        t_jax = best_of(lambda: solve(spec).sweep)
        t_np = best_of(lambda: _np_sweep(g, CM, qs))
        tag = f"partition_jax.headcount_n{g.n_tasks}"
        _row(f"{tag}.q4096_numpy_ms", f"{t_np * 1e3:.1f}",
             "numpy backend: dp + eager Partition objects")
        _row(f"{tag}.q4096_jax_ms", f"{t_jax * 1e3:.1f}",
             "jitted: e_total + bounds arrays")
        _row(f"{tag}.q4096_speedup", f"{t_np / t_jax:.1f}",
             "acceptance: >=5x (n=33 row); see parity note")
        if scale == 192:
            t_jp = best_of(
                lambda: solve(spec).partitions(), n=2
            )
            _row(f"{tag}.q4096_jax_full_parts_ms", f"{t_jp * 1e3:.1f}",
                 "jax engine + eager Partition objects (parity w/ numpy)")

    # whole model zoo, one vmapped kernel: 10 graphs x 512 Q points
    cm = tpu_host_offload_model()
    zoo = lower_zoo(batch=8, seq=4096)
    names = sorted(zoo)
    qmns = {n: qmin_np(zoo[n], cm) for n in names}
    qs = list(np.geomspace(min(qmns.values()), max(qmns.values()) * 64, 512))
    spec = PartitionSpec(graphs=tuple(zoo[n] for n in names), cost=cm,
                         q_grid=tuple(qs))
    solve(spec)  # compile
    t = best_of(lambda: solve(spec).sweeps, n=2)
    _row("partition_jax.zoo.batched_ms", f"{t * 1e3:.1f}",
         f"{len(names)} graphs x 512 Q, one vmap")
    for n, res in zip(names, solve(spec).sweeps):
        feas = np.flatnonzero(res.feasible)
        lo = feas[0] if len(feas) else -1
        b = res.bounds(int(feas[-1])) if len(feas) else []
        _row(f"partition_jax.zoo.{n}", f"{zoo[n].n_tasks}",
             f"qmin={qmns[n] * 1e3:.2f}ms bursts@qmin="
             f"{len(res.bounds(int(lo))) if lo >= 0 else 0} "
             f"bursts@64x={len(b)}")


def partition_sweep(backend="auto", smoke=False, json_out=None):
    """Scan vs CSR/Pallas sweep backends (same outputs, different layouts).

    Rows: export footprint on the full 5458-task head-count graph (dense
    computed analytically — materializing it is the ~1 GB blow-up the CSR
    layout exists to avoid), solver timings on a reduced graph where both
    backends run, the objective matrix (minimax + exact-K per backend, each
    bit-compared against the numpy oracle — any mismatch exits nonzero),
    and (unless ``smoke``) the full-graph CSR solve. Results are also
    dumped to BENCH_partition_sweep.json for trend tracking.
    """
    from repro.core import dense_export_nbytes, q_min as qmin_np

    records = {}

    def row(name, value, derived=""):
        _row(name, value, derived)
        records[name] = {"value": value, "derived": derived}

    def best_of(f, n=3):
        ts = []
        for _ in range(n):
            t0 = time.time()
            f()
            ts.append(time.time() - t0)
        return min(ts)

    # Export footprint: dense (N, R) rectangles vs CSR slot arrays.
    g_full = build_graph(THERMAL)
    csr = g_full.to_csr_arrays()
    r = max(len(t.reads) for t in g_full.tasks)
    w = max(len(t.writes) for t in g_full.tasks)
    dense_b = dense_export_nbytes(g_full.n_tasks, r, w)
    row("partition_sweep.dense_export_MB", f"{dense_b / 1e6:.0f}",
        f"(N,R)=({g_full.n_tasks},{r}) — never materialized")
    row("partition_sweep.csr_export_kB", f"{csr.nbytes / 1e3:.0f}",
        f"{csr.nnz_reads} read slots")
    row("partition_sweep.export_ratio", f"{dense_b / csr.nbytes:.0f}",
        "acceptance: >=50x")

    # Reduced graph where the dense backend is feasible: time both.
    g = build_graph(THERMAL.reduced(64))
    qmn = qmin_np(g, CM)
    qs = list(np.geomspace(qmn, g.total_task_cost() * 1.05, 64))
    backends = ("scan", "pallas") if backend == "auto" else (backend,)
    times = {}
    for be in backends:
        spec = PartitionSpec(graph=g, cost=CM, q_grid=tuple(qs), backend=be)
        solve(spec)  # compile outside the timed region
        times[be] = best_of(lambda spec=spec: solve(spec).sweep)
        row(f"partition_sweep.n{g.n_tasks}.q64_{be}_ms",
            f"{times[be] * 1e3:.1f}", "same outputs (bit-exact columns)")
    if len(times) == 2:
        row("partition_sweep.n90.scan_over_pallas",
            f"{times['scan'] / times['pallas']:.2f}",
            "dense scan vs CSR kernel at equal N")

    # Objective matrix: the kernel's minimax and exact-K modes, timed per
    # backend and bit-compared against the numpy oracle. The *_bit_identical
    # rows are the acceptance gate — CI runs this section as a named step
    # and any mismatch exits nonzero instead of printing a row nobody reads.
    mismatches = []
    ref_qmin = float(qmin_np(g, CM))
    k = min(18, g.n_tasks)
    ref_part = solve(PartitionSpec(graph=g, cost=CM, objective="exact_k",
                                   n_bursts=k, backend="numpy")).partition()
    for be in backends:
        mm_spec = PartitionSpec(graph=g, cost=CM, objective="minimax",
                                backend=be)
        ek_spec = PartitionSpec(graph=g, cost=CM, objective="exact_k",
                                n_bursts=k, backend=be)
        solve(mm_spec), solve(ek_spec)  # compile outside the timed region
        t_mm = best_of(lambda: solve(mm_spec).q_min())
        t_ek = best_of(lambda: solve(ek_spec).partition())
        row(f"partition_sweep.objectives.minimax_{be}_us",
            f"{t_mm * 1e6:.0f}", f"Q_min over n={g.n_tasks}")
        row(f"partition_sweep.objectives.exact_k_{be}_us",
            f"{t_ek * 1e6:.0f}", f"optimal {k}-burst partition")
        mm_ok = solve(mm_spec).q_min() == ref_qmin
        got = solve(ek_spec).partition()
        ek_ok = (list(got.bounds) == list(ref_part.bounds)
                 and got.e_total == ref_part.e_total)
        row(f"partition_sweep.objectives.minimax_{be}_bit_identical",
            int(mm_ok), "vs numpy q_min; acceptance: 1")
        row(f"partition_sweep.objectives.exact_k_{be}_bit_identical",
            int(ek_ok), "vs numpy optimal_partition_k; acceptance: 1")
        if not mm_ok:
            mismatches.append(f"minimax[{be}] != numpy q_min")
        if not ek_ok:
            mismatches.append(f"exact_k[{be}] != numpy optimal partition")

    # The full graph only exists through the CSR backend.
    if not smoke:
        be = "pallas" if backend == "auto" else backend
        if be != "pallas":
            row("partition_sweep.full.skipped", 1,
                "scan backend cannot materialize the full graph")
        else:
            spec_full = PartitionSpec(graph=g_full, cost=CM,
                                      q_grid=(132e-3, None), backend="pallas")
            solve(spec_full)
            t = best_of(lambda: solve(spec_full).sweep, n=2)
            res = solve(spec_full).sweep
            row("partition_sweep.full.q2_pallas_s", f"{t:.2f}",
                f"{g_full.n_tasks} tasks, one fused kernel")
            row("partition_sweep.full.bursts@132mJ",
                len(res.bounds(0)), "paper=18")

    path = json_out or os.path.join(
        os.path.dirname(__file__), "BENCH_partition_sweep.json"
    )
    _merge_bench_json(path, records, backend=backend, smoke=bool(smoke))
    if mismatches:
        raise SystemExit("partition_sweep objective matrix: "
                         + "; ".join(mismatches))


def _merge_bench_json(path, new_rows, **meta):
    """Read-modify-write a BENCH json: sections share one trend file, so a
    plan_table run must not clobber the plan_table_sharded rows (or vice
    versa) — rows merge by name, metadata keys overwrite."""
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            data = {}
    rows = data.get("rows", {})
    rows.update(new_rows)
    data.update(meta)
    data["rows"] = rows
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")


def plan_table_bench(smoke=False, json_out=None):
    """Plan-table serving subsystem: offline build cost vs online lookup.

    Rows: one-shot table build (the whole bucket × Q grid in one batched
    engine call), table footprint, O(1) lookup latency, and the per-request
    re-plan it replaces (lower the request's graph + solve one Q — what
    serve.py would otherwise do per request). Results also land in
    BENCH_plan_table.json for trend tracking.
    """
    from repro.core.layer_profile import lower_config
    from repro.core.plan_table import _default_cost
    from repro.launch.planner import build_table_for_arch, resolve_config

    records = {}

    def row(name, value, derived=""):
        _row(name, value, derived)
        records[name] = {"value": value, "derived": derived}

    arch = "qwen3-4b"
    buckets = [(2, 24), (2, 48)] if smoke else [(2, 24), (2, 48), (4, 48), (4, 96)]
    n_q = 8 if smoke else 32
    t0 = time.time()
    table = build_table_for_arch(arch, buckets, n_q)
    build_s = time.time() - t0
    row("plan_table.build_ms", f"{build_s * 1e3:.1f}",
        f"{len(buckets)} buckets x {table.n_q} Q, one batched solve")
    row("plan_table.size_kB", f"{table.nbytes() / 1e3:.1f}",
        f"{int(table.feasible.sum())} feasible plans")

    cfg = resolve_config(arch, smoke=True)
    cm = _default_cost("time")
    mid_q = float(np.median(table.q_grid[np.isfinite(table.q_grid)]))

    n_lookups = 2000
    t0 = time.time()
    for _ in range(n_lookups):
        table.lookup(2, 20, mid_q)
    lookup_us = (time.time() - t0) / n_lookups * 1e6
    row("plan_table.lookup_us", f"{lookup_us:.1f}",
        "bucketize + Q select + plan slice (request path)")

    # the per-request alternative: lower the shape and solve one Q
    def _replan():
        g = lower_config(cfg, 2, 24, kind="time")  # per-request lowering
        return solve(PartitionSpec(graph=g, cost=cm, q_max=mid_q)).partition()

    _replan()
    n_replans = 5
    t0 = time.time()
    for _ in range(n_replans):
        _replan()
    replan_us = (time.time() - t0) / n_replans * 1e6
    row("plan_table.replan_us", f"{replan_us:.0f}",
        "lower_config + one-Q solve per request (the path lookups replace)")
    row("plan_table.lookup_speedup", f"{replan_us / max(lookup_us, 1e-9):.0f}",
        "re-plan / lookup")

    path = json_out or os.path.join(
        os.path.dirname(__file__), "BENCH_plan_table.json"
    )
    _merge_bench_json(path, records, smoke=bool(smoke))


def plan_table_sharded(smoke=False, json_out=None):
    """Sharded DSE: multi-device plan-table builds + incremental extension.

    The ROADMAP-scale sweep: 10⁵ Q points × 100 graph variants (100 (batch,
    seq) buckets of the *full* qwen3-4b config — a production bucket fleet)
    solved once single-host and once Q-sharded across an 8-device mesh
    (emulated via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
    which this script sets itself when the section is requested). Rows pin
    the acceptance bit — the sharded table is byte-identical to the
    single-host one — plus build timings, and the incremental-extension
    path: growing the fleet by one batch row re-solves only the new cells
    (SOLVE_COUNT-verified) instead of rebuilding the world. ``--smoke``
    shrinks the grid for CI. Rows merge into BENCH_plan_table.json.
    """
    import jax

    from repro.api import QGridSharding
    from repro.configs import get_config
    from repro.core import partition_jax as pj
    from repro.core.plan_table import (
        _default_cost, build_plan_table, extend_plan_table)
    from repro.launch.mesh import shard_devices
    from repro.launch.planner import derive_q_grid, lower_buckets

    records = {}

    def row(name, value, derived=""):
        _row(name, value, derived)
        records[name] = {"value": value, "derived": derived}

    arch = "qwen3-4b"
    cfg = get_config(arch)
    if smoke:
        batches, seqs, n_q, shards = [2, 4], [64, 128, 256], 511, 4
    else:
        batches = [1, 2, 4, 8, 16]
        seqs = [128 * k for k in range(1, 21)]  # 128..2560
        n_q, shards = 99_999, 8
    buckets = [(b, s) for b in batches for s in seqs]
    cm = _default_cost("time")
    graphs = lower_buckets(cfg, buckets, "time")
    qs = derive_q_grid(graphs, cm, n_q)  # +1 unbounded entry
    n_dev = len(jax.local_devices())
    row("plan_table_sharded.grid", f"{len(buckets)}x{len(qs)}",
        f"buckets x Q points, {arch} full config ({graphs[0].n_tasks} tasks)")
    row("plan_table_sharded.devices", n_dev,
        f"{shards} shards; pmap needs devices >= shards, else seq fallback")

    t0 = time.time()
    single = build_plan_table(cfg, buckets, qs, cost=cm, graphs=graphs)
    t_single = time.time() - t0
    row("plan_table_sharded.single_host_build_s", f"{t_single:.2f}",
        "one batched engine call + vectorized assembly")
    t0 = time.time()
    sharded = build_plan_table(
        cfg, buckets, qs, cost=cm, graphs=graphs,
        sharding=QGridSharding(shards, shard_devices(shards)))
    t_shard = time.time() - t0
    row("plan_table_sharded.sharded_build_s", f"{t_shard:.2f}",
        f"{shards}-way Q-shard "
        f"({'pmap mesh' if n_dev >= shards else 'sequential fallback'})")
    row("plan_table_sharded.byte_identical",
        int(sharded.content_digest() == single.content_digest()),
        "acceptance: 1 (sharded == single-host bytes)")
    row("plan_table_sharded.table_MB", f"{single.nbytes() / 1e6:.1f}",
        f"{int(single.feasible.sum())} feasible plans")

    # Incremental extension: grow the fleet by one batch row without
    # re-solving the existing cells.
    n_keep = len(buckets) - len(seqs)
    base = build_plan_table(cfg, buckets[:n_keep], qs, cost=cm,
                            graphs=graphs[:n_keep])
    solves0 = dict(pj.SOLVE_COUNT)
    t0 = time.time()
    ext = extend_plan_table(base, cfg, add_buckets=buckets[n_keep:], cost=cm)
    t_ext = time.time() - t0
    delta = {k: pj.SOLVE_COUNT[k] - solves0[k] for k in solves0}
    row("plan_table_sharded.extend_s", f"{t_ext:.2f}",
        f"+{len(buckets) - n_keep} buckets x {len(qs)} Q appended")
    row("plan_table_sharded.extend_engine_calls", sum(delta.values()),
        "solves for the new cells only (old cells byte-moved)")
    row("plan_table_sharded.extend_matches_fresh",
        int(ext.content_digest() == single.content_digest()),
        "acceptance: 1 (incremental == fresh bytes)")
    row("plan_table_sharded.extend_speedup", f"{t_single / max(t_ext, 1e-9):.1f}",
        "full rebuild / incremental extension")
    solves0 = dict(pj.SOLVE_COUNT)
    untouched = extend_plan_table(ext, cfg, add_buckets=buckets, cost=cm)
    n_calls = sum(pj.SOLVE_COUNT[k] - solves0[k] for k in solves0)
    if untouched is not ext:  # must be the base object, not a rebuild
        n_calls = -1
    row("plan_table_sharded.untouched_extend_solves", n_calls,
        "acceptance: 0 (re-extend of an untouched base never re-solves)")

    path = json_out or os.path.join(
        os.path.dirname(__file__), "BENCH_plan_table.json"
    )
    _merge_bench_json(path, records, sharded_smoke=bool(smoke))


def api_facade(smoke=False, json_out=None):
    """Façade dispatch overhead: ``solve(PartitionSpec)`` vs calling the
    engine implementation directly.

    The façade validates the spec, resolves the backend through the
    registry's capability flags, and wraps the result — all host-side
    bookkeeping. The acceptance row pins that this costs <1% on the smoke
    config (against a direct ``partition_jax._sweep_jax_batched`` call on
    the resolved backend), so routing every consumer through the one API is
    free at solve granularity. Rows merge into BENCH_partition_sweep.json.
    """
    from repro.core import lower_config, q_min as qmin_np
    from repro.core.partition_jax import _sweep_jax_batched
    from repro.core.plan_table import _default_cost
    from repro.launch.planner import resolve_config

    records = {}

    def row(name, value, derived=""):
        _row(name, value, derived)
        records[name] = {"value": value, "derived": derived}

    def median_of(f, n=25):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            f()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    cfg = resolve_config("qwen3-4b", smoke=True)
    cm = _default_cost("time")
    graphs = [lower_config(cfg, b, s, kind="time")
              for (b, s) in ((2, 24), (2, 48))]
    qmn = min(qmin_np(g, cm) for g in graphs)
    n_q = 1024 if smoke else 8192
    qs = list(np.geomspace(qmn, qmn * 64, n_q)) + [None]
    spec = PartitionSpec(graphs=tuple(graphs), cost=cm, q_grid=tuple(qs),
                         backend="scan")

    _sweep_jax_batched(graphs, cm, qs, backend="scan")  # compile once
    solve(spec)
    t_direct = median_of(
        lambda: _sweep_jax_batched(graphs, cm, qs, backend="scan")
    )
    t_facade = median_of(lambda: solve(spec))

    # The two medians above sit inside the same multi-ms XLA-dispatch noise
    # band, so the *added* cost is also measured in isolation: run the full
    # façade shell (spec validation, registry resolution, capability checks,
    # Solution wrap) against a stubbed-out solver and charge its whole
    # median against the direct solve time. This is the number the <1%
    # acceptance bound actually constrains.
    import repro.core.partition_jax as _pj

    canned = _sweep_jax_batched(graphs, cm, qs, backend="scan")
    real_impl = _pj._sweep_jax_batched
    _pj._sweep_jax_batched = lambda *a, **k: canned
    try:
        t_shell = median_of(lambda: solve(spec), n=200)
    finally:
        _pj._sweep_jax_batched = real_impl
    overhead = 100.0 * t_shell / t_direct

    row("api_facade.direct_ms", f"{t_direct * 1e3:.2f}",
        "engine implementation called directly (old sweep_jax_batched path)")
    row("api_facade.solve_ms", f"{t_facade * 1e3:.2f}",
        "solve(PartitionSpec) end to end (same noise band as direct)")
    row("api_facade.dispatch_us", f"{t_shell * 1e6:.1f}",
        "façade shell alone: validate + registry dispatch + wrap")
    row("api_facade.overhead_pct", f"{overhead:.3f}",
        "dispatch / direct solve; acceptance: <1% on the smoke config")
    row("api_facade.grid", f"{len(graphs)}x{len(qs)}",
        "smoke buckets x Q points, scan backend")

    path = json_out or os.path.join(
        os.path.dirname(__file__), "BENCH_partition_sweep.json"
    )
    _merge_bench_json(path, records, facade_smoke=bool(smoke))
    # This section *is* the acceptance gate (CI runs it as a named step):
    # fail loudly instead of merely printing a row nobody asserts on.
    if overhead >= 1.0:
        raise SystemExit(
            f"api_facade: dispatch overhead {overhead:.3f}% breaks the <1% "
            f"acceptance bound ({t_shell * 1e6:.1f} µs shell vs "
            f"{t_direct * 1e3:.2f} ms solve)"
        )


def serving_traffic(smoke=False, json_out=None):
    """Continuous-traffic serving: the plan table under sustained load.

    Drives :class:`repro.launch.traffic.TrafficHarness` over the real
    planned executor with a deterministic burst of same-shape requests plus
    an admission-controlled run (capacity ≈ 1.5 requests, income ≈ 0.9
    request-energies per unit virtual time → at least one deferral). Rows:
    sustained requests/sec, wall p50/p95/p99 latency, plan-cache hit rate,
    admission/deferral/reject counts, and the zero-retrace acceptance bit.
    Results land in BENCH_serving.json. This section is also the acceptance
    gate: any post-warmup retrace or a failed admission split exits nonzero.
    """
    from repro.launch.planner import build_table_for_arch
    from repro.launch.serve import PlannedExecutor
    from repro.launch.traffic import (
        HarvestModel, TrafficHarness, deterministic_arrivals, request_energy)

    records = {}

    def row(name, value, derived=""):
        _row(name, value, derived)
        records[name] = {"value": value, "derived": derived}

    arch = "qwen3-4b"
    batch, prompt_len, gen = 2, 8, 6
    n_requests = 8 if smoke else 32
    max_seq = prompt_len + gen
    table = build_table_for_arch(arch, [(batch, max_seq)], n_q=8)
    ex = PlannedExecutor(arch, table)
    plan = ex.planner.plan_for(batch, max_seq, None)
    _, e_req = request_energy(plan, gen, None, ex.planner.e_startup)
    reqs = deterministic_arrivals(n_requests, 0.0, (batch, prompt_len, gen))

    # throughput run: unlimited harvest, compile outside the measured window
    harness = TrafficHarness(ex)
    harness.warmup(reqs)
    report = harness.run(reqs)
    pct = report.latency_percentiles_ms()
    row("serving_traffic.requests", report.completed,
        f"{arch} {batch}x{prompt_len}x{gen}, deterministic burst")
    row("serving_traffic.requests_per_s", f"{report.requests_per_s:.1f}",
        "sustained, warm caches")
    row("serving_traffic.latency_p50_ms", f"{pct['p50']:.1f}",
        "wall-clock arrival→complete")
    row("serving_traffic.latency_p95_ms", f"{pct['p95']:.1f}", "")
    row("serving_traffic.latency_p99_ms", f"{pct['p99']:.1f}", "")
    row("serving_traffic.hit_rate", f"{report.hit_rate:.3f}",
        "plan-cache lookups answered from the table; acceptance: 1.0")
    row("serving_traffic.retraces", report.retraces,
        "jit retraces after warmup; acceptance: 0")

    # admission run: pool holds ~1.5 requests, income ~0.9 req/unit-time
    harness2 = TrafficHarness(
        ex, harvest=HarvestModel(capacity=1.5 * e_req, rate=0.9 * e_req))
    report2 = harness2.run(deterministic_arrivals(
        max(3, n_requests // 4), 0.0, (batch, prompt_len, gen)))
    row("serving_traffic.admitted", report2.admitted,
        "capacity=1.5 req, rate=0.9 req/t")
    row("serving_traffic.deferred", report2.deferred,
        "acceptance: >=1 (pool too small for the burst)")
    row("serving_traffic.rejected", report2.rejected, "")
    row("serving_traffic.energy_spent", f"{report2.energy_spent:.4f}",
        f"one request draws {e_req:.4f} (table units)")

    path = json_out or os.path.join(
        os.path.dirname(__file__), "BENCH_serving.json")
    _merge_bench_json(path, records, smoke=bool(smoke))

    failures = []
    if report.retraces:
        failures.append(f"{report.retraces} retraces after warmup "
                        f"({report.trace_delta})")
    if report.completed != n_requests or report.hit_rate != 1.0:
        failures.append(
            f"throughput run: {report.completed}/{n_requests} completed, "
            f"hit rate {report.hit_rate}")
    if report2.deferred < 1 or report2.completed != report2.arrived:
        failures.append(
            f"admission run: {report2.deferred} deferred, "
            f"{report2.completed}/{report2.arrived} completed")
    if failures:
        raise SystemExit("serving_traffic: " + "; ".join(failures))


def telemetry_overhead(smoke=False, json_out=None):
    """Telemetry cost on the instrumented serving hot path (plan lookup +
    admission + burst step), tracing enabled vs disabled.

    Drives the full TrafficHarness request path over tiny numpy chain
    graphs (the fast-tier synthetic-executor shape — no jax, no XLA), so
    the only delta between the timed runs is ``repro.obs`` itself: span
    capture, per-request instants, harvest counters, and the energy
    ledger. Two acceptance rows, both gated here (CI runs this section as
    a named step):

    * enabled: the added wall cost per request must stay under 1% of the
      measured serving pace in BENCH_serving.json (requests_per_s);
    * disabled: tracing compiles down to one ``TRACER.enabled`` attribute
      check per instrumentation site — the residual is measured directly
      and must round to 0% of the same pace.

    Rows merge into BENCH_serving.json.
    """
    from repro.core import (
        BurstRuntime, CostModel, GraphBuilder, LinearTransfer, Partition)
    from repro.core.burst import burst_detail
    from repro.launch.planner import ServePlanner, request_cycles
    from repro.launch.traffic import (
        Continuation, HarvestModel, Request, TrafficHarness,
        deterministic_arrivals)
    from repro.obs.metrics import reset_all
    from repro.obs.trace import TRACER

    records = {}

    def row(name, value, derived=""):
        _row(name, value, derived)
        records[name] = {"value": value, "derived": derived}

    e_total, e_startup = 0.25, 0.1

    class _Plan:
        def __init__(self, batch, seq_bucket):
            self.batch, self.seq_bucket, self.e_total = batch, seq_bucket, e_total

        def summary(self):
            return f"{self.batch}x{self.seq_bucket}"

    class _Table:  # duck-typed PlanTable: exact batch, covering seq bucket
        arch = "synthetic"
        e_startup = 0.1  # == the CostModel e_startup below

        def lookup(self, batch, seq, energy_budget=None):
            return _Plan(batch, max(seq, 16))

    class _Exec:  # the fast-tier synthetic executor shape (numpy chains)
        def __init__(self):
            self.planner = ServePlanner(_Table())
            self._rid = 0

        def open(self, batch, prompt_len, gen, *, seed=0, cycle_budget=None,
                 prompts=None, plan=None, nvm=None, crash_hook=None):
            if plan is None:
                plan = self.planner.plan_for(batch, prompt_len + gen,
                                             cycle_budget)
            b = GraphBuilder()
            b.packet("prompts", 8, external=True)
            for k in range(gen - 1):
                b.packet(f"state{k}", 8)
            b.packet("sequence", 8, keep=True)

            def mk(k):
                def fn(inp):
                    src = inp["prompts"] if k == 0 else inp[f"state{k - 1}"]
                    name = "sequence" if k == gen - 1 else f"state{k}"
                    return {name: np.asarray(src) + 1}
                return fn

            for k in range(gen):
                b.task(f"step{k}",
                       reads=("prompts",) if k == 0 else (f"state{k - 1}",),
                       writes=("sequence",) if k == gen - 1 else (f"state{k}",),
                       cost=plan.e_total, fn=mk(k))
            graph = b.build()
            cycles = request_cycles(gen, plan.e_total, cycle_budget,
                                    e_startup=e_startup)
            cost = CostModel(e_startup=e_startup,
                             read=LinearTransfer(0.0, 0.0),
                             write=LinearTransfer(0.0, 0.0), name="synthetic")
            part = Partition(
                cycles,
                [burst_detail(graph, cost, i, j) for (i, j) in cycles], None)
            rt = BurstRuntime(graph, part, nvm=nvm, cost=cost,
                              crash_hook=crash_hook)
            if rt.nvm.read_index() == 0:
                rt.seed_inputs({"prompts": np.full((batch,), seed, np.int64)})
            rid, self._rid = self._rid, self._rid + 1
            return Continuation(
                request=Request(rid=rid, batch=batch, prompt_len=prompt_len,
                                gen=gen, seed=seed),
                plan=plan, cycles=list(cycles), runtime=rt,
                e_startup=e_startup)

    gen, q = 6, 0.4                      # 6 one-step cycles per request
    n_requests = 16 if smoke else 48
    e_req = gen * (e_startup + e_total)  # E_s is paid per cycle at this Q
    reqs = deterministic_arrivals(n_requests, 0.0, (1, 4, gen))
    n_cycles = n_requests * gen

    def one_run():
        harness = TrafficHarness(
            _Exec(), harvest=HarvestModel(capacity=n_requests * e_req),
            cycle_budget=q)
        report = harness.run(reqs)
        if report.completed != n_requests:
            raise SystemExit(
                f"telemetry_overhead: {report.completed}/{n_requests} "
                f"completed — measurement run is broken")
        return report

    def timed(enabled):
        if enabled:
            TRACER.configure(enabled=True, clear=True)
        try:
            t0 = time.perf_counter()
            one_run()
            return time.perf_counter() - t0
        finally:
            if enabled:
                TRACER.reset()
            reset_all()

    timed(False)  # warm allocators / imports outside the measured window
    timed(True)
    reps = 5 if smoke else 7
    t_dis, t_en = [], []
    for _ in range(reps):  # interleave so drift hits both modes equally
        t_dis.append(timed(False))
        t_en.append(timed(True))
    t_dis, t_en = min(t_dis), min(t_en)  # min-of-N: robust to scheduler noise
    added_us_req = max(0.0, t_en - t_dis) / n_requests * 1e6

    # the disabled-mode residual: one attribute check per instrumentation
    # site (span guard / instant guard / counter guard), measured directly
    n_checks = 1_000_000
    t0 = time.perf_counter()
    for _ in range(n_checks):
        if TRACER.enabled:
            pass
    guard_ns = (time.perf_counter() - t0) / n_checks * 1e9
    # sites per request: ~3 arrival/admission events + ~5 per cycle
    # (cycle span, harvest sample, burst, restore and commit spans)
    sites_per_req = 3 + 5 * gen
    disabled_us_req = guard_ns * sites_per_req / 1e3

    # the pace the <1% bound is charged against: the measured real-model
    # serving throughput from the serving_traffic section of this file
    path = json_out or os.path.join(
        os.path.dirname(__file__), "BENCH_serving.json")
    try:
        with open(path) as f:
            rps = float(json.load(f)["rows"]
                        ["serving_traffic.requests_per_s"]["value"])
    except (OSError, KeyError, ValueError, json.JSONDecodeError):
        raise SystemExit(
            f"telemetry_overhead: no serving_traffic.requests_per_s row in "
            f"{path} — run the serving_traffic section first")
    budget_us_req = 1e6 / rps
    overhead_pct = 100.0 * added_us_req / budget_us_req
    disabled_pct = 100.0 * disabled_us_req / budget_us_req

    row("telemetry_overhead.run_disabled_ms", f"{t_dis * 1e3:.2f}",
        f"{n_requests} requests / {n_cycles} cycles, tracing off (min of "
        f"{reps})")
    row("telemetry_overhead.run_enabled_ms", f"{t_en * 1e3:.2f}",
        "same run: spans + instants + counters + energy ledger captured")
    row("telemetry_overhead.added_us_per_request", f"{added_us_req:.1f}",
        "enabled minus disabled wall, per request")
    row("telemetry_overhead.guard_ns", f"{guard_ns:.1f}",
        "one TRACER.enabled check — all a disabled site costs")
    row("telemetry_overhead.enabled_pct", f"{overhead_pct:.3f}",
        f"added cost vs measured serving pace ({budget_us_req / 1e3:.1f} "
        f"ms/request); acceptance: <1%")
    row("telemetry_overhead.disabled_pct", f"{disabled_pct:.4f}",
        f"{sites_per_req} guard checks/request vs the same pace; "
        f"acceptance: <0.05% (~0)")

    _merge_bench_json(path, records, telemetry_smoke=bool(smoke))

    failures = []
    if overhead_pct >= 1.0:
        failures.append(
            f"enabled tracing adds {added_us_req:.1f} µs/request = "
            f"{overhead_pct:.3f}% of the serving pace (bound: <1%)")
    if disabled_pct >= 0.05:
        failures.append(
            f"disabled residual {disabled_pct:.4f}% is not ~0 — a hot-path "
            f"site is doing work beyond the TRACER.enabled guard")
    if failures:
        raise SystemExit("telemetry_overhead: " + "; ".join(failures))


def calibration_bench(smoke=False, json_out=None):
    """Calibration-loop cost and contract gates (core/calibration.py).

    * ledger → MeasuredCostTable ingest pace (Welford accumulation) and
      fingerprint time;
    * the sigma=0 contract, as a hard gate: a table whose samples match
      the analytical model must materialize the analytical CostModel
      *object* and sweep bit-identically through the engine;
    * confidence pricing overhead: E_total at confidence 0.95 over the
      mean-priced E_total on the qwen3-4b smoke graph — must be >= 1
      (pricing is pessimistic, never optimistic).

    Rows merge into BENCH_serving.json.
    """
    import random

    from repro.api import PartitionSpec, solve
    from repro.core import lower_config
    from repro.core.calibration import MeasuredCostTable
    from repro.core.layer_profile import analytical_cost_model
    from repro.obs.ledger import EnergyLedger
    from repro.configs import SMOKE_CONFIGS

    path = json_out or os.path.join(
        os.path.dirname(__file__), "BENCH_serving.json")
    records = {}

    def row(name, value, derived=""):
        _row(name, value, derived)
        records[name] = {"value": value, "derived": derived}

    cm = analytical_cost_model("time")
    rng = random.Random(0)
    n_rows = 600 if smoke else 3000

    led = EnergyLedger()
    for i in range(n_rows // 3):
        led.charge(i % 7, i // 7, restore=float(cm.e_startup),
                   compute=rng.uniform(1e-5, 1e-4), commit=1e-6)
    t0 = time.time()
    clean = MeasuredCostTable.from_ledger(led, base=cm, kind="time")
    t_ingest = time.time() - t0
    row("calibration.ingest_rows", str(clean.n_samples), "ledger entries")
    row("calibration.ingest_ms", f"{t_ingest * 1e3:.2f}",
        f"{clean.n_samples / max(t_ingest, 1e-9):.0f} rows/s Welford")
    t0 = time.time()
    fp = clean.fingerprint()
    row("calibration.fingerprint_us", f"{(time.time() - t0) * 1e6:.0f}",
        f"sha256 {fp[:12]}…")

    # sigma=0 gate: identical-object materialization + bitwise sweep
    g = lower_config(SMOKE_CONFIGS["qwen3-4b"], batch=2, seq=16, kind="time")
    qs = (5e-5, None)
    base_sweep = solve(PartitionSpec(graph=g, cost=cm, q_grid=qs,
                                     backend="scan")).sweep
    meas_sweep = solve(PartitionSpec(graph=g, cost=clean, q_grid=qs,
                                     backend="scan")).sweep
    identical = clean.cost_model() is cm and all(
        getattr(base_sweep, f).tobytes() == getattr(meas_sweep, f).tobytes()
        for f in ("dp", "parent", "e_total", "feasible", "starts"))
    row("calibration.sigma0_bit_identical", str(int(identical)),
        "clean table materializes the analytical model; acceptance: ==1")

    # confidence overhead on a noisy profile
    noisy = MeasuredCostTable(cm, "time")
    for _ in range(200):
        noisy.add("restore", rng.gauss(float(cm.e_startup) * 2, float(cm.e_startup) * 0.5))
        noisy.add("commit", abs(rng.gauss(1e-6, 3e-7)))
    t0 = time.time()
    e_mean = float(solve(PartitionSpec(
        graph=g, cost=noisy, q_grid=(None,), backend="scan")).sweep.e_total[0])
    e_conf = float(solve(PartitionSpec(
        graph=g, cost=noisy, q_grid=(None,), confidence=0.95,
        backend="scan")).sweep.e_total[0])
    t_solve = time.time() - t0
    ratio = e_conf / e_mean
    row("calibration.confidence_overhead_ratio", f"{ratio:.4f}",
        "E_total@0.95 / E_total@mean on qwen3-4b smoke; acceptance: >=1")
    row("calibration.confident_solve_ms", f"{t_solve / 2 * 1e3:.1f}",
        "mean of the two priced solves above")

    _merge_bench_json(path, records, calibration_smoke=bool(smoke))

    failures = []
    if not identical:
        failures.append(
            "sigma=0 table does not reproduce the analytical sweep "
            "bit-for-bit — the measured path is recomputing, not slotting in")
    if ratio < 1.0:
        failures.append(
            f"confidence pricing lowered E_total ({ratio:.4f} < 1) — "
            f"mean + z*sigma must never be optimistic")
    if failures:
        raise SystemExit("calibration: " + "; ".join(failures))


def placement_bench(smoke=False, json_out=None):
    """Swarm placement grid solver (core/placement.py + placement_jax.py).

    * solve pace: the whole bandwidth × memory × Q grid in ONE batched
      engine call (cold = includes jit compile, warm = steady state);
    * transfer overhead at the best cell of a memory-constrained swarm
      (the NS-Optimizer-style figure: hop TX+RX over swarm E_total);
    * ``placement.oracle_bit_identical`` as a hard gate: the scan backend
      must reproduce the numpy reference on every DP array — values *and*
      argmin parents — and every feasible plan must conserve energy
      node-by-node. Nonzero exit on any mismatch.

    Rows land in BENCH_placement.json.
    """
    import numpy as np

    from repro.api import Engine, PartitionSpec, solve
    from repro.core.layer_profile import default_cost_model
    from repro.core.placement import (
        LinkModel, NodeSpec, PlacementSpec, solve_placement_numpy,
    )
    from repro.core.placement_jax import solve_placement_scan

    path = json_out or os.path.join(
        os.path.dirname(__file__), "BENCH_placement.json")
    records = {}

    def row(name, value, derived=""):
        _row(name, value, derived)
        records[name] = {"value": value, "derived": derived}

    cm = default_cost_model("time")
    # an NS-Optimizer-shaped relay chain: enough layers that per-node NVM
    # caps actually bite (the zoo smoke graphs are 2-6 fused tasks — too
    # coarse to cut; scale is the point of this section)
    from repro.core.graph import GraphBuilder

    n_tasks = 24 if smoke else 64
    b = GraphBuilder()
    prev = None
    for i in range(n_tasks):
        pkt = f"act{i}"
        b.packet(pkt, 50_000 + 10_000 * (i % 7), keep=(i == n_tasks - 1))
        b.task(f"layer{i}", reads=(prev,) if prev else (), writes=(pkt,),
               cost=0.01 + 0.002 * (i % 5))
        prev = pkt
    g = b.build()
    qmin = solve(PartitionSpec(graph=g, cost=cm, objective="minimax")).q_min()
    n_links = 8 if smoke else 25
    bandwidths = [900.0 + 100.0 * i for i in range(n_links)]
    # cap node NVM below the whole-graph footprint so the swarm must split
    from repro.core.placement import placement_inputs

    probe = placement_inputs(
        g, cm, PlacementSpec(nodes=3, link=LinkModel(900.0)))
    full_mem = float(probe.mem[1, g.n_tasks])
    spec = PlacementSpec(
        nodes=tuple(
            NodeSpec(q_max=qmin * 1.25, memory_bytes=full_mem * 0.6)
            for _ in range(3)
        ),
        links=tuple(LinkModel(bw) for bw in bandwidths),
        q_scales=(0.9, 1.0, 1.2),
    )
    L, M, Z = spec.grid_shape

    eng = Engine()
    pspec = PartitionSpec(graph=g, cost=cm, placement=spec)
    t0 = time.time()
    sol = eng.solve(pspec)
    t_cold = time.time() - t0
    t0 = time.time()
    sol = eng.solve(pspec)
    t_warm = time.time() - t0
    sweep = sol.placement_sweep()
    cells = L * M * Z
    row("placement.grid_cells", str(cells),
        f"{L} links x {M} mem x {Z} Q, 3 nodes, {g.n_tasks} tasks")
    row("placement.solve_cold_ms", f"{t_cold * 1e3:.1f}",
        "one batched engine call incl. jit compile")
    row("placement.solve_warm_ms", f"{t_warm * 1e3:.1f}",
        f"{cells / max(t_warm, 1e-9):.0f} cells/s steady state")

    feasible = [p for p in sweep.plans() if p is not None]
    row("placement.feasible_cells", str(len(feasible)), f"of {cells}")
    best = min(feasible, key=lambda p: p.e_total)
    row("placement.transfer_overhead_pct",
        f"{100 * best.transfer_overhead:.2f}",
        f"best cell: {best.n_nodes_used} nodes @ "
        f"{best.link.bandwidth_mbps:g} mbps, "
        f"{best.transfer_bytes:.0f} B over {len(best.hop_boundaries)} hops")

    # the hard gate: scan == numpy bitwise, ledgers conserve
    ref = solve_placement_numpy(g, cm, spec)
    got = solve_placement_scan(g, cm, spec)
    identical = all(
        np.array_equal(getattr(ref, f), getattr(got, f))
        for f in ("e_total", "k_used", "outer_dp", "outer_parent",
                  "inner_S", "inner_A")
    )
    conserved = True
    for p in feasible:
        try:
            p.validate()
            p.check_conservation()
        except Exception:
            conserved = False
            break
    row("placement.oracle_bit_identical", str(int(identical)),
        "scan DP arrays == numpy reference bitwise; acceptance: ==1")
    row("placement.ledger_conserved", str(int(conserved)),
        f"{len(feasible)} feasible plans conserve node-by-node; "
        f"acceptance: ==1")

    _merge_bench_json(path, records, placement_smoke=bool(smoke))

    failures = []
    if not identical:
        failures.append(
            "scan backend diverged from the numpy placement oracle — "
            "bit-identity (values and parents) is the backend contract")
    if not conserved:
        failures.append(
            "a feasible placement plan failed per-node ledger conservation")
    if not feasible:
        failures.append("no feasible cell on the benchmark grid")
    if failures:
        raise SystemExit("placement: " + "; ".join(failures))


def julienne_planners():
    from repro.configs import REGISTRY
    from repro.core.offload import min_activation_budget, plan_offload
    from repro.core.pipeline import plan_pipeline
    from repro.core.remat_policy import plan_remat

    for arch in ("deepseek-coder-33b", "zamba2-7b", "whisper-large-v3",
                 "phi3.5-moe-42b-a6.6b"):
        cfg = REGISTRY[arch]
        pp = plan_pipeline(cfg, 16, 4096, 8)
        _row(f"pipeline.{arch}.balance", f"{pp.balance:.3f}",
             f"bottleneck={pp.bottleneck_seconds * 1e3:.1f}ms")
        qmn = min_activation_budget(cfg, 4, 4096)
        _row(f"offload.{arch}.qmin_GB", f"{qmn / 1e9:.3f}",
             "smallest feasible activation budget (§4.4), B=4")
        op = plan_offload(cfg, 4, 4096, qmn * 2)
        _row(f"offload.{arch}.pcie_overhead_pct",
             f"{100 * op.overhead_fraction:.1f}",
             f"{op.n_segments} segments @ 2×Qmin")
        rp = plan_remat(cfg, 4, 4096, qmn * 16)
        _row(f"remat.{arch}.recompute_pct",
             f"{100 * rp.recompute_fraction:.1f}",
             f"{rp.n_segments} segments @ 16×Qmin")


def roofline_summary():
    recs = []
    for f in glob.glob(os.path.join(os.path.dirname(__file__), "..",
                                    "experiments", "dryrun", "*.json")):
        recs.append(json.load(open(f)))
    ok = [r for r in recs if r.get("status") == "ok"]
    if not ok:
        _row("roofline.cells", 0, "run launch/dryrun first")
        return
    _row("roofline.cells_ok", len(ok),
         f"skipped={sum(r.get('status') == 'skipped' for r in recs)}")
    for r in sorted(ok, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        t = r["roofline"]
        dom = r["dominant"].replace("t_", "")
        _row(f"roofline.{r['arch']}.{r['shape']}.{r['mesh']}",
             f"{max(t.values()) * 1e3:.2f}ms", f"dominant={dom}")


def kernel_microbench():
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.rmsnorm.ops import rmsnorm

    q = jnp.ones((1, 256, 4, 64), jnp.bfloat16)
    k = jnp.ones((1, 256, 2, 64), jnp.bfloat16)
    flash_attention(q, k, k, interpret=True).block_until_ready()
    t0 = time.time()
    for _ in range(3):
        flash_attention(q, k, k, interpret=True).block_until_ready()
    _row("kernel.flash_attention_us", f"{(time.time() - t0) / 3 * 1e6:.0f}",
         "interpret mode (correctness path, not TPU perf)")
    x = jnp.ones((1024, 512), jnp.bfloat16)
    w = jnp.ones((512,), jnp.float32)
    rmsnorm(x, w, interpret=True).block_until_ready()
    t0 = time.time()
    for _ in range(3):
        rmsnorm(x, w, interpret=True).block_until_ready()
    _row("kernel.rmsnorm_us", f"{(time.time() - t0) / 3 * 1e6:.0f}",
         "interpret mode")


SECTIONS = {
    "tables": table12_energy_characterization,
    "fig6": fig6_partitioning_comparison,
    "design_space": fig7_fig8_design_space,
    "scaling": optimizer_scaling,
    "partition_jax": partition_jax_engine,
    "partition_sweep": partition_sweep,
    "plan_table": plan_table_bench,
    "plan_table_sharded": plan_table_sharded,
    "api_facade": api_facade,
    "serving_traffic": serving_traffic,
    "telemetry_overhead": telemetry_overhead,
    "calibration": calibration_bench,
    "placement": placement_bench,
    "planners": julienne_planners,
    "roofline": roofline_summary,
    "kernels": kernel_microbench,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--section", choices=sorted(SECTIONS), default=None,
                    help="run one section instead of all")
    ap.add_argument("--backend", choices=("scan", "pallas", "auto"),
                    default="auto",
                    help="partition_sweep: which solver backend(s) to time")
    ap.add_argument("--smoke", action="store_true",
                    help="partition_sweep: skip the full 5458-task solve")
    ap.add_argument("--json-out", default=None,
                    help="partition_sweep: override the JSON dump path")
    args = ap.parse_args(argv)
    enable_compile_cache()

    print("name,value,derived")
    sections = [args.section] if args.section else list(SECTIONS)
    for name in sections:
        fn = SECTIONS[name]
        if name == "partition_sweep":
            fn(backend=args.backend, smoke=args.smoke, json_out=args.json_out)
        elif name in ("plan_table", "plan_table_sharded", "api_facade",
                      "serving_traffic", "telemetry_overhead", "calibration",
                      "placement"):
            fn(smoke=args.smoke, json_out=args.json_out)
        else:
            fn()


if __name__ == "__main__":
    main()
