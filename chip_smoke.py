#!/usr/bin/env python3
"""Smoke check: the system's main path, run once on a TPU, checked against
its references.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: the Q-sharded table build only
    python chip_smoke.py --rehearse   # CPU rehearsal: interpret-mode kernel and
                                      # smoke-width model; never reports ok

Phases on one chip, all in this one process:

(a) device: platform, kind and count, as JAX reports them;
(b) the paper's application: the 5458-task THERMAL head-count graph solved
    by the compiled Pallas kernel (``backend="pallas"``, ``interpret=False``)
    over the Q grid of ``examples/headcount_full.py``, plus its minimax Q_min,
    against the ``backend="numpy"`` float64 oracle;
(c) the ``scan`` backend under x64 on the lowered full ``qwen1.5-0.5b`` graph
    against numpy, reporting whether the chip's emulated float64 is
    bit-identical;
(d) energy-bounded serving of ``qwen1.5-0.5b`` at its published widths:
    plan table → ``PlannedExecutor`` → ``TrafficHarness``, with several energy
    cycles per request and one injected power failure, against unplanned
    ``serve()``.

Wall times printed on the way are set-up figures of one smoke run, not
benchmark results. The last line of stdout is one JSON object; ``"ok"`` is
true only when every phase passed on a TPU. Off a TPU the script exits 2
and prints no result (``--rehearse`` runs the phases there and exits 1).
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402

ARCH = "qwen1.5-0.5b"


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def timed(phase: str, step: str):
    """Print the wall time of one step, as a set-up figure."""
    t0 = time.perf_counter()
    yield
    log(f"[{phase}] {step}: {time.perf_counter() - t0:.3f} s wall")


# ---------------------------------------------------------------------------
# comparison with the float64 numpy oracle, shared by (b) and (c)
# ---------------------------------------------------------------------------


def unit_roundoff(dtype) -> float:
    """Unit roundoff u of ``dtype`` additions on the device.

    IEEE gives 2^-24 for float32 and 2^-53 for float64. A chip that
    emulates float64 (the TPU does) may round coarser, so u is the larger
    of IEEE's and the worst error of 4096 random device sums and
    differences against numpy's correctly rounded ones, relative to
    |a| + |b|.
    """
    import jax
    import jax.numpy as jnp

    ab = np.random.default_rng(0).uniform(1.0, 2.0, (2, 4096)).astype(dtype)
    with jax.enable_x64(np.dtype(dtype) == np.float64):
        dev = jax.jit(lambda a, b: (a + b, a - b))(*jnp.asarray(ab))
        dev = [np.asarray(x, np.float64) for x in dev]
    a, b = ab.astype(np.float64)
    host = [(ab[0] + ab[1]).astype(np.float64), (ab[0] - ab[1]).astype(np.float64)]
    err = max(np.max(np.abs(d - h) / (np.abs(a) + np.abs(b)))
              for d, h in zip(dev, host))
    return max(float(np.finfo(dtype).eps) / 2, float(err))


def dp_rtol(n_tasks: int, nnz_reads: int, u: float) -> float:
    """Relative tolerance of a DP value against the float64 oracle.

    Every E⟨i,j⟩ and dp value on a DP path is a running sum of at most
    3N + 2·nnz rounded terms (per column: the task/store extension and the
    dp add; per read slot: its load and freed-store updates). The
    first-order bound for recursive summation gives rtol = (3N + 2·nnz)·u.
    """
    return (3 * n_tasks + 2 * nnz_reads) * u


def compare_with_numpy(tag, qs, sol, ref, rtol) -> bool:
    """Check a device sweep against the numpy oracle; True if bit-identical.

    Feasibility per Q must agree, and E_total within ``rtol``. Where
    candidate cuts cost less than ``rtol`` apart, the device may pick
    another cut than numpy, so its bounds are checked as a plan: the same
    burst count, every burst within Q and E_total within ``rtol`` of the
    optimum, all priced by numpy in float64.
    """
    from repro.core.partition import within_budget

    sweep = sol.sweep
    parts = sol.partitions()  # the device's bounds, re-priced in float64
    same = 0
    exact = True
    for qi, (q, r, p) in enumerate(zip(qs, ref, parts)):
        label = f"Q[{qi}]={'inf' if q is None else f'{q:.6g}'}"
        check((r is None) == (not sweep.feasible[qi]),
              f"{label}: feasibility differs from numpy")
        if r is None:
            continue
        e = float(sweep.e_total[qi])
        rel = abs(e - r.e_total) / r.e_total
        plan_rel = (p.e_total - r.e_total) / r.e_total
        log(f"[{tag}] {label}: {p.n_bursts} bursts, e_total rel err "
            f"{rel:.3e}, plan cost vs optimum {plan_rel:+.3e}, bounds "
            f"{'identical' if p.bounds == r.bounds else 'differ (near-tie cuts)'}")
        check(rel <= rtol, f"{label}: e_total rel error {rel:.3e} > {rtol:.3e}")
        check(p.n_bursts == r.n_bursts,
              f"{label}: {p.n_bursts} bursts, numpy has {r.n_bursts}")
        check(q is None or within_budget(p.max_burst, q),
              f"{label}: the device plan's largest burst {p.max_burst!r} "
              "exceeds Q in float64")
        check(plan_rel <= rtol,
              f"{label}: the device plan costs {plan_rel:.3e} more than optimal")
        same += p.bounds == r.bounds
        exact &= p.bounds == r.bounds and e == r.e_total
    log(f"[{tag}] burst bounds identical to numpy at {same}/{len(qs)} Q points")
    return exact


# ---------------------------------------------------------------------------
# (b) the paper's application on the compiled Pallas kernel
# ---------------------------------------------------------------------------


def phase_headcount(compiled: bool) -> None:
    from repro.api import PartitionSpec, solve
    from repro.core import q_min
    from repro.core.apps.headcount import THERMAL, build_graph, paper_cost_model
    from repro.kernels.partition_sweep.kernel import TRACE_COUNT

    g = build_graph(THERMAL)
    cm = paper_cost_model()
    csr = g.to_csr_arrays()
    check(g.n_tasks == 5458, f"THERMAL has {g.n_tasks} tasks, expected 5458")
    qmn = q_min(g, cm)
    e_app = g.total_task_cost()
    qs = tuple([qmn] + list(np.geomspace(qmn * 1.01, e_app * 1.05, 7)) + [None])
    # The compiled kernel computes in float32; interpret mode in float64.
    u = unit_roundoff(np.float32 if compiled else np.float64)
    rtol = dp_rtol(csr.n_tasks, csr.nnz_reads, u)
    log(f"[b] THERMAL: {g.n_tasks} tasks, {csr.nnz_reads} read slots, "
        f"{len(qs)} Q points; u = {u:.3e} (2^{np.log2(u):.1f}), "
        f"rtol = (3N + 2 nnz) u = {rtol:.3e}")

    def kernel(**kw):
        return solve(PartitionSpec(graph=g, cost=cm, backend="pallas",
                                   interpret=not compiled, **kw))

    traced = TRACE_COUNT["sweep_columns"]
    with timed("b", "sum sweep, first call (compile + run)"):
        sol = kernel(q_grid=qs)
    with timed("b", "sum sweep, second call (cached executable)"):
        kernel(q_grid=qs)
    check(sol.backend == "pallas", f"solved on {sol.backend!r}, not pallas")
    check(TRACE_COUNT["sweep_columns"] == traced + 1,
          "the sum sweep did not run through the Pallas kernel exactly once")
    with timed("b", "minimax (compile + run)"):
        qmn_kernel = kernel(objective="minimax").q_min()
    with timed("b", "numpy oracle, sum sweep"):
        ref = solve(PartitionSpec(graph=g, cost=cm, q_grid=qs,
                                  backend="numpy")).partitions()
    compare_with_numpy("b", qs, sol, ref, rtol)

    rel_q = abs(qmn_kernel - qmn) / qmn
    log(f"[b] Q_min: kernel {qmn_kernel!r}, numpy {qmn!r} (rel {rel_q:.3e})")
    check(rel_q <= rtol, f"Q_min rel error {rel_q:.3e} > {rtol:.3e}")
    # The Q point placed exactly at the float64 Q_min is the one a float32
    # column could push over its budget (BUDGET_REL = 1e-9 < 2^-24); the
    # feasibility check above covers it.
    log(f"[b] Q point at Q_min: numpy feasible, kernel "
        f"{'feasible' if sol.sweep.feasible[0] else 'INFEASIBLE'}")
    check(abs(qmn - 132e-3) <= 0.5e-3, f"Q_min {qmn} is not the paper's 132 mJ")
    check(sol.partitions()[0].n_bursts == 18,
          "the kernel's plan at Q_min is not the paper's 18 bursts")
    log("[b] paper figure: 18 bursts at Q_min = 132 mJ")


# ---------------------------------------------------------------------------
# (c) scan backend, float64 under x64
# ---------------------------------------------------------------------------


def phase_scan_x64() -> None:
    import jax

    from repro.api import PartitionSpec, solve
    from repro.configs import resolve_config
    from repro.core.layer_profile import default_cost_model, lower_config
    from repro.launch.planner import derive_q_grid

    cfg = resolve_config(ARCH, smoke=False)
    cm = default_cost_model("time")
    g = lower_config(cfg, batch=2, seq=72)
    qs = tuple(derive_q_grid([g], cm, 64))
    u = unit_roundoff(np.float64)
    rtol = dp_rtol(g.n_tasks, g.to_csr_arrays().nnz_reads, u)
    log(f"[c] {cfg.name}: lowered graph of {g.n_tasks} tasks, {len(qs)} Q "
        f"points; float64 on {jax.devices()[0].platform}: u = "
        f"{u:.3e} (2^{np.log2(u):.1f}), rtol = {rtol:.3e}")
    with timed("c", "scan sweep (compile + run)"):
        sol = solve(PartitionSpec(graph=g, cost=cm, q_grid=qs, backend="scan"))
    check(sol.backend == "scan", f"solved on {sol.backend!r}, not scan")
    with timed("c", "numpy oracle"):
        ref = solve(PartitionSpec(graph=g, cost=cm, q_grid=qs,
                                  backend="numpy")).partitions()
    exact = compare_with_numpy("c", qs, sol, ref, rtol)
    log(f"[c] float64 on {jax.devices()[0].platform} bit-identical to numpy "
        f"(every e_total and every bound): {'yes' if exact else 'no'}")


# ---------------------------------------------------------------------------
# (d) energy-bounded serving at published widths
# ---------------------------------------------------------------------------


def phase_serving(smoke: bool) -> None:
    import repro.launch.serve as serve_mod
    from repro.core import PowerFailure
    from repro.launch.planner import build_table_for_arch
    from repro.launch.serve import PlannedExecutor
    from repro.launch.traffic import (
        HarvestModel, Request, TrafficHarness, request_energy,
    )

    batch, gen, prompts = 2, 8, (32, 64)
    buckets = [(batch, p + gen) for p in prompts]
    with timed("d", "plan table build"):
        table = build_table_for_arch(ARCH, buckets, n_q=16, smoke=smoke)
    ex = PlannedExecutor(ARCH, table, smoke=smoke)
    log(f"[d] {ex.cfg.name}: {ex.cfg.n_layers} layers, d_model "
        f"{ex.cfg.d_model}, vocab {ex.cfg.vocab}; buckets {buckets}")

    # A cycle budget of E_s + 3 token steps of the costliest bucket: every
    # request then takes several energy cycles.
    e_s = ex.planner.e_startup
    step = max(table.lookup(b, s, None).e_total for b, s in buckets)
    budget = e_s + 3 * step
    reqs = [Request(rid=i, batch=batch, prompt_len=prompts[i % 2], gen=gen,
                    time=float(i // 2)) for i in range(4)]
    n_cycles, energies = [], []
    for r in reqs:
        cycles, e_req = request_energy(
            table.lookup(r.batch, r.max_seq, budget), r.gen, budget, e_s)
        check(len(cycles) > 1, f"request {r.rid} runs in one cycle")
        n_cycles.append(len(cycles))
        energies.append(e_req)
    log(f"[d] cycle budget {budget:.4g}: cycles per request {n_cycles}")

    fired = []

    def crash_once(request):
        if request.rid != 1:
            return None

        def hook(b, phase):
            if not fired and b == 1 and phase == "executed":
                fired.append(b)
                raise PowerFailure("injected at cycle 1 of request 1")

        return hook

    harness = TrafficHarness(
        ex, cycle_budget=budget, keep_tokens=True, crash_hook_factory=crash_once,
        harvest=HarvestModel(capacity=2.5 * max(energies), rate=max(energies)))
    with timed("d", "warmup: one request per shape (compile + run)"):
        harness.warmup(reqs)
    with timed("d", "traffic run, 4 requests"):
        report = harness.run(reqs)
    log(f"[d] {report.summary()}")
    check(report.completed == len(reqs), f"{report.completed} of {len(reqs)} completed")
    check(report.power_failures == 1 and fired, "the power failure was not injected")
    check(report.commit_delta.get("replays", 0) >= 1, "the crashed cycle was not replayed")
    check(report.retraces == 0, f"retraces after warmup: {report.trace_delta}")
    check(report.ledger_conserved, "energy ledger does not conserve "
          f"(error {report.ledger_conservation_error})")

    for p in prompts:
        with timed("d", f"unplanned serve(), prompt {p} (compile + run)"):
            ref = np.asarray(serve_mod.serve(ARCH, batch, p, gen, smoke=smoke))
        for r in reqs:
            if r.prompt_len == p:
                check(np.array_equal(report.tokens[r.rid], ref),
                      f"request {r.rid}: planned tokens differ from serve()")
    log("[d] planned tokens equal unplanned serve() for all 4 requests")


# ---------------------------------------------------------------------------
# four chips: the Q-sharded plan-table build
# ---------------------------------------------------------------------------


def phase_sharded_table() -> None:
    from repro.api import QGridSharding
    from repro.configs import resolve_config
    from repro.core import partition_jax
    from repro.core.layer_profile import default_cost_model
    from repro.core.plan_table import build_plan_table
    from repro.launch.mesh import make_shard_mesh
    from repro.launch.planner import derive_q_grid, lower_buckets

    devices = tuple(make_shard_mesh(4).devices.ravel())  # raises below 4
    cfg = resolve_config(ARCH, smoke=False)
    cm = default_cost_model("time")
    buckets = [(2, 40), (2, 72), (4, 136)]
    graphs = lower_buckets(cfg, buckets)
    qs = derive_q_grid(graphs, cm, 256)
    log(f"[4] {cfg.name}: {len(buckets)} buckets x {len(qs)} Q over "
        f"{len(devices)} devices")
    with timed("4", "one-device build"):
        one = build_plan_table(cfg, buckets, qs, cost=cm, graphs=graphs)
    pmaps = partition_jax._dp_sweep_pmap.cache_info().currsize
    with timed("4", "Q-sharded build over 4 devices"):
        four = build_plan_table(cfg, buckets, qs, cost=cm, graphs=graphs,
                                sharding=QGridSharding(4, devices))
    check(partition_jax._dp_sweep_pmap.cache_info().currsize == pmaps + 1,
          "the sharded build did not take the pmap path")
    d1, d4 = one.content_digest(), four.content_digest()
    log(f"[4] content_digest: one device {d1}, four devices {d4}")
    check(d1 == d4, "sharded table differs from the one-device build")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="off a TPU: run the phases anyway (interpret-mode "
                         "kernel, smoke-width model) and exit 1")
    args = ap.parse_args(argv)

    import jax
    from jax import monitoring

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    events = collections.Counter()
    monitoring.register_event_listener(lambda event, **_: events.update([event]))
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU ({json.dumps(device)}); "
              "nothing was run", file=sys.stderr)
        return 2
    log(f"[a] device: {json.dumps(device)}; compile cache {cache}")
    if on_tpu and device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{device['count']} devices", file=sys.stderr)
        return 2

    if args.chips == 4:
        phases = [("sharded plan table", phase_sharded_table)]
    else:
        phases = [
            ("headcount pallas", lambda: phase_headcount(compiled=on_tpu)),
            ("scan x64", phase_scan_x64),
            # published widths on the chip; smoke widths in a CPU rehearsal
            ("serving", lambda: phase_serving(smoke=not on_tpu)),
        ]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
            status = "PASS"
        except Exception as e:  # reported, then the script exits nonzero
            failed.append(name)
            status = f"FAIL: {type(e).__name__}: {e}"
            traceback.print_exc()
        log(f"[phase] {name}: {status} ({time.perf_counter() - t0:.1f} s wall)")
    log(f"[a] persistent compile cache {cache}: "
        f"{events['/jax/compilation_cache/cache_hits']} hits, "
        f"{events['/jax/compilation_cache/cache_misses']} misses")

    ok = on_tpu and not failed
    print(json.dumps({"ok": ok, "device": device}
                     if ok else {"ok": False, "failed": failed, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
