"""Façade tier: PartitionSpec → Engine.solve pins.

Three layers:

* **Differentials on every smoke config**: the jit backends (scan and the
  Pallas kernel in interpret mode) reproduce the numpy reference DP
  (``backend="numpy"``) bit-for-bit through the same spec — single Q,
  Q grids, exact-K, minimax, batched and Q-sharded sweeps — and batching,
  sharding and mixed ``auto`` batches reproduce the plain single solve.
* **Error paths**: ``Infeasible`` and ``UnsupportedObjective`` surface with
  the same type *and message* from every backend (numpy / scan / pallas /
  sharded) for the same spec; export mismatches raise the typed
  :class:`ExportMismatch` everywhere.
* **Registry**: backends self-register with capability flags, custom
  registries dispatch, and the backend is resolved once, in the engine.
"""

import random

import numpy as np
import pytest

from helpers_random import random_cost_model, random_task_graph

from repro.api import (
    Engine,
    EngineError,
    ExportMismatch,
    Infeasible,
    PartitionSpec,
    QGridSharding,
    Solution,
    SpecError,
    UnsupportedObjective,
    backend_names,
    default_engine,
    register_backend,
    solve,
)
from repro.configs import SMOKE_CONFIGS, resolve_config
from repro.core import lower_config, q_min, whole_app_partition
from repro.core.layer_profile import default_cost_model

ARCHS = sorted(SMOKE_CONFIGS)


@pytest.fixture(scope="session")
def arch_case():
    """arch → (graph, cost model, small Q grid spanning infeasible→whole-app),
    lowered once per session (every differential test reuses it)."""
    cache = {}

    def _case(arch):
        if arch not in cache:
            cfg = SMOKE_CONFIGS[arch]
            cm = default_cost_model("time")
            g = lower_config(cfg, batch=2, seq=16, kind="time")
            qmn = q_min(g, cm)
            hi = whole_app_partition(g, cm).e_total
            qs = [qmn * 0.5, qmn, float(np.sqrt(qmn * hi)), hi * 1.1, None]
            cache[arch] = (g, cm, qs)
        return cache[arch]

    return _case


def _assert_parts_equal(a, b, ctx=""):
    """Bit-level equality of two Optional[Partition] lists."""
    assert len(a) == len(b), ctx
    for i, (p, q) in enumerate(zip(a, b)):
        assert (p is None) == (q is None), (ctx, i)
        if p is None:
            continue
        assert p.bounds == q.bounds, (ctx, i)
        assert p.q_max == q.q_max, (ctx, i)
        assert p.e_total == q.e_total, (ctx, i)
        assert [d.total for d in p.bursts] == [d.total for d in q.bursts], (ctx, i)


def _assert_sweeps_equal(a, b, ctx=""):
    assert a.n_tasks == b.n_tasks, ctx
    for field in ("dp", "parent", "e_total", "feasible", "starts"):
        assert getattr(a, field).tobytes() == getattr(b, field).tobytes(), \
            (ctx, field)


# ---------------------------------------------------------------------------
# Differential: the jit backends against the numpy oracle, every smoke config
# ---------------------------------------------------------------------------

JIT_BACKENDS = ("scan", "pallas")


def _numpy_oracle(**spec):
    return solve(PartitionSpec(backend="numpy", **spec))


@pytest.mark.parametrize("arch", ARCHS)
def test_facade_matches_optimal_partition(arch, arch_case):
    """One Q_max: scan and Pallas give the numpy oracle's partition."""
    g, cm, qs = arch_case(arch)
    ref = _numpy_oracle(graph=g, cost=cm, q_max=qs[2]).partition()
    for backend in JIT_BACKENDS:
        sol = solve(PartitionSpec(graph=g, cost=cm, q_max=qs[2],
                                  backend=backend))
        _assert_parts_equal([sol.partition()], [ref], ctx=backend)


@pytest.mark.parametrize("arch", ARCHS)
def test_facade_matches_optimal_partition_multi_and_sweep(arch, arch_case):
    """A Q grid from infeasible to unbounded: per-Q partitions (None where
    infeasible) match the numpy oracle on both jit backends."""
    g, cm, qs = arch_case(arch)
    ref = _numpy_oracle(graph=g, cost=cm, q_grid=tuple(qs)).partitions()
    assert ref[0] is None and ref[-1] is not None
    for backend in JIT_BACKENDS:
        sol = solve(PartitionSpec(graph=g, cost=cm, q_grid=tuple(qs),
                                  backend=backend))
        _assert_parts_equal(sol.partitions(), ref, ctx=backend)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("k_objective", ["sum", "max"])
def test_facade_matches_optimal_partition_k(arch, k_objective, arch_case):
    g, cm, qs = arch_case(arch)
    k = min(3, g.n_tasks)
    ref = _numpy_oracle(graph=g, cost=cm, objective="exact_k", n_bursts=k,
                        k_objective=k_objective).partition()
    assert ref.n_bursts == k
    for backend in JIT_BACKENDS:
        sol = solve(PartitionSpec(graph=g, cost=cm, objective="exact_k",
                                  n_bursts=k, k_objective=k_objective,
                                  backend=backend))
        _assert_parts_equal([sol.partition()], [ref], ctx=backend)


@pytest.mark.parametrize("arch", ARCHS)
def test_facade_minimax_matches_q_min(arch, arch_case):
    """objective='minimax' == the numpy q_min on every backend — numpy,
    scan, and the Pallas kernel's minimax mode — bit-for-bit."""
    g, cm, qs = arch_case(arch)
    ref = q_min(g, cm)
    for backend in ("numpy", "scan", "pallas"):
        sol = solve(PartitionSpec(graph=g, cost=cm, objective="minimax",
                                  backend=backend))
        assert sol.q_min() == ref, backend


def _assert_sweep_matches_oracle(sweep, graph, cm, ref_parts, ctx=""):
    """A JaxSweep against the numpy oracle's per-Q partitions: the same
    feasibility and the same bursts."""
    assert sweep.feasible.tolist() == [p is not None for p in ref_parts], ctx
    _assert_parts_equal(sweep.to_partitions(graph, cm), ref_parts, ctx=ctx)


@pytest.mark.parametrize("arch", ARCHS)
def test_facade_matches_sweep_jax(arch, arch_case):
    """The single-graph sweep table, under auto (scan here) and on Pallas,
    reconstructs the numpy oracle's partitions."""
    g, cm, qs = arch_case(arch)
    ref = _numpy_oracle(graph=g, cost=cm, q_grid=tuple(qs)).partitions()
    for backend in ("auto", "pallas"):
        sol = solve(PartitionSpec(graph=g, cost=cm, q_grid=tuple(qs),
                                  backend=backend))
        assert sol.backend == ("scan" if backend == "auto" else backend)
        _assert_sweep_matches_oracle(sol.sweep, g, cm, ref, ctx=backend)


@pytest.mark.parametrize("arch", ARCHS)
def test_facade_matches_sweep_jax_batched(arch, arch_case):
    """Two buckets in one batched solve: each member matches the numpy
    oracle, and the single-graph solve of that member."""
    g, cm, qs = arch_case(arch)
    g2 = lower_config(SMOKE_CONFIGS[arch], batch=2, seq=24, kind="time")
    ref = _numpy_oracle(graphs=(g, g2), cost=cm, q_grid=tuple(qs))
    for backend in JIT_BACKENDS:
        sol = solve(PartitionSpec(graphs=(g, g2), cost=cm, q_grid=tuple(qs),
                                  backend=backend))
        for i, member in enumerate((g, g2)):
            _assert_sweep_matches_oracle(sol.sweeps[i], member, cm,
                                         ref.partitions(i),
                                         ctx=(arch, backend, i))
            alone = solve(PartitionSpec(graph=member, cost=cm,
                                        q_grid=tuple(qs), backend=backend))
            _assert_parts_equal(sol.partitions(i), alone.partitions(),
                                ctx=(arch, backend, i))


@pytest.mark.parametrize("arch", ARCHS)
def test_facade_matches_sweep_jax_sharded(arch, arch_case):
    """Two Q shards give the unsharded solve's sweep table byte for byte,
    and the numpy oracle's partitions."""
    g, cm, qs = arch_case(arch)
    ref = _numpy_oracle(graph=g, cost=cm, q_grid=tuple(qs)).partitions()
    for backend in JIT_BACKENDS:
        sol = solve(PartitionSpec(graphs=(g,), cost=cm, q_grid=tuple(qs),
                                  sharding=QGridSharding(n_shards=2),
                                  backend=backend))
        whole = solve(PartitionSpec(graphs=(g,), cost=cm, q_grid=tuple(qs),
                                    backend=backend))
        _assert_sweeps_equal(sol.sweeps[0], whole.sweeps[0],
                             ctx=(arch, backend))
        _assert_sweep_matches_oracle(sol.sweeps[0], g, cm, ref,
                                     ctx=(arch, backend))


@pytest.mark.parametrize("arch", ARCHS)
def test_facade_matches_optimal_partition_jax(arch, arch_case):
    """One Q_max under backend='auto' (scan for a smoke graph) == the numpy
    oracle; below Q_min both raise the same Infeasible."""
    g, cm, qs = arch_case(arch)
    sol = solve(PartitionSpec(graph=g, cost=cm, q_max=qs[2]))
    assert sol.backend == "scan"
    _assert_parts_equal(
        [sol.partition()],
        [_numpy_oracle(graph=g, cost=cm, q_max=qs[2]).partition()],
    )
    msgs = []
    for backend in ("auto", "numpy"):
        with pytest.raises(Infeasible) as e:
            solve(PartitionSpec(graph=g, cost=cm, q_max=qs[0],
                                backend=backend)).partition()
        msgs.append(str(e.value))
    assert msgs[0] == msgs[1]


def test_facade_matches_pallas_sweep():
    """The CSR/Pallas backend's sweep table == the scan backend's on the
    same spec (interpret mode; one config keeps the kernel tier fast)."""
    cfg = SMOKE_CONFIGS["qwen3-4b"]
    cm = default_cost_model("time")
    g = lower_config(cfg, batch=2, seq=16, kind="time")
    qs = (q_min(g, cm), None)
    sol = solve(PartitionSpec(graph=g, cost=cm, q_grid=qs, backend="pallas"))
    scan = solve(PartitionSpec(graph=g, cost=cm, q_grid=qs, backend="scan"))
    assert sol.backend == "pallas"
    for field in ("dp", "e_total", "feasible", "starts"):
        assert getattr(sol.sweep, field).tobytes() == \
            getattr(scan.sweep, field).tobytes(), field
    _assert_sweep_matches_oracle(
        sol.sweep, g, cm,
        _numpy_oracle(graph=g, cost=cm, q_grid=qs).partitions(),
    )


def test_build_plan_table_sharding_matches_shard_plan_table(smoke_plan_table):
    """build_plan_table(sharding=QGridSharding(4)) is byte-identical to the
    unsharded build: every payload array and the content digest."""
    from repro.core.plan_table import PlanTable

    cfg, cm, qs, single = smoke_plan_table("qwen3-4b")
    sharded = smoke_plan_table(
        "qwen3-4b", sharding=QGridSharding(4)
    )[3]
    for name in PlanTable._PAYLOAD:
        assert getattr(sharded, name).tobytes() == \
            getattr(single, name).tobytes(), name
    assert sharded.content_digest() == single.content_digest()
    assert sharded.fingerprint == single.fingerprint


def test_facade_mixed_auto_batch_matches_legacy(monkeypatch):
    """A mixed dense/CSR/TaskGraph batch under backend='auto' resolves per
    member and gives each member exactly its single-graph solve (a batch
    pads its members to one task count, so the columns past a member's own
    tasks are left out)."""
    from repro.core import engine

    rng = random.Random(11)
    g1, g2, g3 = (random_task_graph(rng, max_tasks=6) for _ in range(3))
    cm = random_cost_model(rng)
    monkeypatch.setattr(engine, "_AUTO_DENSE_BYTES", 0)  # g3 → pallas
    qs = (None, 0.5)
    batch = (g1.to_arrays(), g2.to_csr_arrays(), g3)
    sol = solve(PartitionSpec(graphs=batch, cost=cm, q_grid=qs))
    assert sol.backend == "pallas+scan"
    for member, got, backend in zip(batch, sol.sweeps,
                                    ("scan", "pallas", "pallas")):
        alone = solve(PartitionSpec(graph=member, cost=cm, q_grid=qs))
        assert alone.backend == backend
        n = alone.sweep.n_tasks + 1
        assert got.n_tasks == alone.sweep.n_tasks
        for field in ("dp", "parent", "starts"):
            assert getattr(got, field)[:, :n].tobytes() == \
                getattr(alone.sweep, field)[:, :n].tobytes(), (backend, field)
        for field in ("e_total", "feasible"):
            assert getattr(got, field).tobytes() == \
                getattr(alone.sweep, field).tobytes(), (backend, field)


# ---------------------------------------------------------------------------
# Error paths: identical surfacing across numpy / scan / pallas / sharded
# ---------------------------------------------------------------------------

BACKEND_VARIANTS = [
    ("numpy", None),
    ("scan", None),
    ("pallas", None),
    ("scan", QGridSharding(n_shards=2)),
]
VARIANT_IDS = ["numpy", "scan", "pallas", "sharded"]


@pytest.fixture(scope="module")
def tiny_case():
    rng = random.Random(3)
    g = random_task_graph(rng, max_tasks=6, min_tasks=3)
    cm = random_cost_model(rng)
    return g, cm


@pytest.mark.parametrize("backend,sharding", BACKEND_VARIANTS, ids=VARIANT_IDS)
def test_infeasible_sum_surfaces_identically(backend, sharding, tiny_case):
    """An infeasible Q cell never fails at solve() time; it surfaces as the
    same Infeasible (same message) from Solution.partition() everywhere."""
    g, cm = tiny_case
    q_bad = q_min(g, cm) * 0.25
    spec = PartitionSpec(graph=g, cost=cm, q_grid=(q_bad, None),
                         backend=backend, sharding=sharding)
    sol = solve(spec)
    assert sol.e_total()[0] == np.inf
    with pytest.raises(Infeasible) as e:
        sol.partition(q_index=0)
    assert str(e.value) == f"Q_max={q_bad} admits no partition"
    sol.partition(q_index=1)  # the unbounded cell is always feasible


@pytest.mark.parametrize("backend", ["numpy", "scan", "pallas"])
def test_objective_matrix_every_builtin_backend(backend, tiny_case):
    """Every built-in backend implements all three objectives (the §4.4
    combines are Pallas kernel modes now): minimax reproduces the numpy
    q_min bit-for-bit and exact_k yields the requested burst count — no
    code path raises UnsupportedObjective for a built-in backend."""
    g, cm = tiny_case
    ref_qmin = q_min(g, cm)
    for objective, extra in (("minimax", {}),
                             ("exact_k", {"n_bursts": 2})):
        spec = PartitionSpec(graph=g, cost=cm, objective=objective,
                             backend=backend, **extra)
        sol = solve(spec)
        if objective == "minimax":
            assert sol.q_min() == ref_qmin
        else:
            assert sol.partition().n_bursts == 2


def test_unsupported_objective_surfaces_identically(tiny_case):
    """The UnsupportedObjective error path, pinned against a fake registered
    backend with a restricted objectives flag (the built-in backends all
    implement the full matrix now, so only capability flags can trip it)."""
    g, cm = tiny_case
    reg = {}

    @register_backend("sumonly", objectives=("sum",), supports_dense=True,
                      registry=reg)
    class SumOnly:
        name = "sumonly"

        def solve(self, req):
            raise AssertionError("capability check must reject pre-dispatch")

    eng = Engine(reg)
    for objective, extra in (("minimax", {}), ("exact_k", {"n_bursts": 2})):
        spec = PartitionSpec(graph=g, cost=cm, objective=objective,
                             backend="sumonly", **extra)
        with pytest.raises(UnsupportedObjective) as e:
            eng.solve(spec)
        msg = str(e.value)
        assert "'sumonly'" in msg and objective in msg
        # the message names who *does* implement it — nobody, here
        assert "implementing it: []" in msg
    # auto resolution over a registry with no capable backend is the same
    # typed error from the registry resolver
    with pytest.raises(UnsupportedObjective):
        eng.solve(PartitionSpec(graph=g, cost=cm, objective="minimax",
                                backend="auto"))


def test_named_backend_dispatch_errors_distinguish_registration(tiny_case):
    """resolve_jit_backend: an unknown name says 'unknown'; a registered but
    non-jit-dispatchable name (numpy) says so and lists both name sets
    instead of the old misleading 'unknown backend' message."""
    from repro.core.engine import resolve_jit_backend

    g, _ = tiny_case
    with pytest.raises(SpecError) as e:
        resolve_jit_backend(g, "numpy")
    msg = str(e.value)
    assert "registered but not jit-dispatchable" in msg
    for name in ("numpy", "scan", "pallas"):
        assert name in msg
    with pytest.raises(SpecError) as e2:
        resolve_jit_backend(g, "mosaic")
    msg2 = str(e2.value)
    assert "unknown backend 'mosaic'" in msg2 and "numpy" in msg2


def test_sharding_requires_a_q_grid_objective(tiny_case):
    """Only objective='sum' has a Q grid to shard: a sharded minimax/exact_k
    spec is rejected at construction, uniformly — no backend gets to
    silently ignore it."""
    g, cm = tiny_case
    for objective, extra in (("minimax", {}), ("exact_k", {"n_bursts": 2})):
        with pytest.raises(SpecError):
            PartitionSpec(graph=g, cost=cm, objective=objective,
                          sharding=QGridSharding(2), **extra)


@pytest.mark.parametrize("backend", ["numpy", "scan"])
def test_infeasible_exact_k_surfaces_identically(backend, tiny_case):
    g, cm = tiny_case
    q_bad = q_min(g, cm) * 0.25  # below Q_min: no 1..n-burst partition fits
    with pytest.raises(Infeasible) as e:
        solve(PartitionSpec(graph=g, cost=cm, objective="exact_k", n_bursts=2,
                            q_max=q_bad, backend=backend))
    assert str(e.value) == f"no 2-burst partition within Q_max={q_bad}"


def test_export_mismatch_is_typed_everywhere(tiny_case):
    g, cm = tiny_case
    cases = [
        (g.to_csr_arrays(), "scan"),    # CSR into the dense backend
        (g.to_arrays(), "pallas"),      # dense into the CSR backend
        (g.to_arrays(), "numpy"),       # any export into the reference DP
        (g.to_csr_arrays(), "numpy"),
    ]
    for export, backend in cases:
        with pytest.raises(ExportMismatch) as e:
            solve(PartitionSpec(graph=export, cost=cm, q_max=None,
                                backend=backend))
        assert isinstance(e.value, TypeError), (backend, type(export))
    with pytest.raises(ExportMismatch):
        solve(PartitionSpec(graph=object(), cost=cm, q_max=None))
    # layout gaps beat objective gaps: in a registry where the only
    # minimax-capable backend is dense-only, a CSR export is an export
    # problem, not an objective problem (the global registry can't hit this
    # branch anymore — pallas covers CSR for every objective)
    reg = {}

    @register_backend("denseonly", objectives=("sum", "minimax"),
                      supports_dense=True, registry=reg)
    class DenseOnly:
        name = "denseonly"

        def solve(self, req):
            raise AssertionError("layout check must reject pre-dispatch")

    with pytest.raises(ExportMismatch):
        Engine(reg).solve(PartitionSpec(graph=g.to_csr_arrays(), cost=cm,
                                        objective="minimax"))
    # exact_k prices bursts on the graph — exports are rejected up front
    # (before any solve), backend-independently
    from repro.core import partition_jax as pj

    solves = dict(pj.SOLVE_COUNT)
    with pytest.raises(ExportMismatch):
        solve(PartitionSpec(graph=g.to_arrays(), cost=cm,
                            objective="exact_k", n_bursts=2, backend="scan"))
    assert dict(pj.SOLVE_COUNT) == solves  # doomed spec never hit the engine


def test_numpy_backend_rejects_sharding(tiny_case):
    g, cm = tiny_case
    with pytest.raises(SpecError):
        solve(PartitionSpec(graph=g, cost=cm, q_grid=(None,),
                            backend="numpy", sharding=QGridSharding(2)))


# ---------------------------------------------------------------------------
# Spec validation + Solution accessors
# ---------------------------------------------------------------------------


def test_spec_validation(tiny_case):
    g, cm = tiny_case
    with pytest.raises(SpecError):
        PartitionSpec()                                  # no input source
    with pytest.raises(SpecError):
        PartitionSpec(graph=g, graphs=(g,))              # two sources
    with pytest.raises(SpecError):
        PartitionSpec(graph=g, q_grid=(None,), q_max=1.0)
    with pytest.raises(SpecError):
        PartitionSpec(graph=g, q_grid=())
    with pytest.raises(SpecError):
        PartitionSpec(graph=g, objective="minimax", q_max=1.0)
    with pytest.raises(SpecError):
        PartitionSpec(graph=g, objective="exact_k")      # n_bursts missing
    with pytest.raises(SpecError):
        PartitionSpec(graph=g, n_bursts=2)               # without exact_k
    with pytest.raises(SpecError):
        PartitionSpec(graph=g, objective="bottleneck")
    with pytest.raises(SpecError):
        PartitionSpec(graph=g, objective="exact_k", n_bursts=2,
                      k_objective="min")
    with pytest.raises(SpecError):
        QGridSharding(0)
    with pytest.raises(SpecError):
        solve(PartitionSpec(graph=g, q_max=None))        # cost required
    with pytest.raises(SpecError):
        solve(PartitionSpec(graph=g, cost=cm, backend="mosaic"))
    with pytest.raises(SpecError):
        solve(PartitionSpec(graph=g, cost=cm), q_max=1.0)  # spec + kwargs
    with pytest.raises(SpecError):
        default_engine().solve("not a spec")


def test_spec_is_immutable_and_normalized(tiny_case):
    g, cm = tiny_case
    spec = PartitionSpec(graph=g, cost=cm, q_grid=[1.0, None])
    assert spec.q_grid == (1.0, None)
    assert spec.q_values == (1.0, None)
    with pytest.raises(Exception):
        spec.backend = "scan"
    assert PartitionSpec(graph=g, cost=cm).q_values == (None,)
    assert PartitionSpec(graph=g, cost=cm,
                         objective="minimax").q_values == ()


def test_config_lowered_spec(arch_case):
    """config= specs lower exactly like the plan-table builders: same graphs,
    default cost per kind, smoke registry honored."""
    g_ref, cm, qs = arch_case("qwen3-4b")
    sol = solve(PartitionSpec(config="qwen3-4b", shapes=((2, 16),),
                              smoke=True, q_grid=tuple(qs)))
    direct = solve(PartitionSpec(graph=g_ref, cost=cm, q_grid=tuple(qs)))
    _assert_sweeps_equal(sol.sweeps[0], direct.sweep)
    assert sol.cost.name == cm.name
    assert resolve_config("qwen3-4b", smoke=True) is SMOKE_CONFIGS["qwen3-4b"]


def test_solution_accessor_guards(tiny_case):
    g, cm = tiny_case
    sum_sol = solve(PartitionSpec(graph=g, cost=cm, q_max=None,
                                  backend="numpy"))
    with pytest.raises(EngineError):
        sum_sol.q_min()
    with pytest.raises(EngineError):
        _ = sum_sol.sweep          # numpy backend has no JaxSweep payload
    mm_sol = solve(PartitionSpec(graph=g, cost=cm, objective="minimax",
                                 backend="scan"))
    with pytest.raises(EngineError):
        mm_sol.partitions()
    multi = solve(PartitionSpec(graphs=(g, g), cost=cm, q_max=None))
    with pytest.raises(EngineError):
        _ = multi.sweep            # 2 graphs: index .sweeps instead
    assert multi.n_graphs == 2 and "2 graph" in multi.summary()


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_registry_flags_and_names():
    from repro.core.engine import backend_info

    assert {"numpy", "scan", "pallas"} <= set(backend_names())
    assert backend_info("scan").supports_sharding
    assert not backend_info("scan").supports_csr
    assert backend_info("pallas").supports_csr
    assert backend_info("pallas").objectives == \
        frozenset({"sum", "minimax", "exact_k"})
    assert not backend_info("numpy").auto_eligible
    assert backend_info("numpy").objectives == \
        frozenset({"sum", "minimax", "exact_k"})


def test_custom_backend_registration(tiny_case):
    """Downstream code can register a backend with capability flags and
    address it by name; capability checks guard its inputs."""
    from repro.core.engine import _REGISTRY

    g, cm = tiny_case
    registry = dict(_REGISTRY)
    seen = {}

    @register_backend("recorder", objectives=("sum",), supports_dense=True,
                      auto_eligible=False, registry=registry)
    class Recorder:
        def solve(self, req):
            seen["req"] = req
            return {"parts": tuple((None,) * len(req.q_values)
                                   for _ in req.graphs)}

    assert "recorder" not in backend_names()          # global untouched
    eng = Engine(registry=registry)
    sol = eng.solve(PartitionSpec(graph=g, cost=cm, q_grid=(1.0, None),
                                  backend="recorder"))
    assert sol.backend == "recorder"
    assert seen["req"].q_values == (1.0, None)
    with pytest.raises(Infeasible):
        sol.partition()                               # recorder said None
    with pytest.raises(ExportMismatch):
        eng.solve(PartitionSpec(graph=g.to_csr_arrays(), cost=cm,
                                backend="recorder"))
    with pytest.raises(SpecError):
        register_backend("bad", objectives=("frobnicate",))


def test_register_backend_rejects_unknown_objective_before_decorating():
    with pytest.raises(SpecError):
        register_backend("x", objectives=("sum", "nope"), registry={})


def test_backend_resolved_once_per_graph(monkeypatch, tiny_case):
    """backend='auto' is resolved in the engine, one call per graph, for
    every objective (that nothing below it takes 'auto' is the next
    test's)."""
    from repro.core import engine

    g, cm = tiny_case
    calls = []
    real = engine.resolve_jit_backend

    def counting(graph, backend="auto", *args, **kwargs):
        calls.append(backend)
        return real(graph, backend, *args, **kwargs)

    monkeypatch.setattr(engine, "resolve_jit_backend", counting)
    csr = g.to_csr_arrays()
    for objective, graphs, extra in (
        ("sum", (g, csr), {"q_grid": (None, 1.0)}),
        ("minimax", (g, csr), {}),
        ("exact_k", (g, g), {"n_bursts": 2}),  # exact_k takes TaskGraphs
    ):
        calls.clear()
        solve(PartitionSpec(graphs=graphs, cost=cm, objective=objective,
                            **extra))
        assert calls == ["auto", "auto"], objective


@pytest.mark.parametrize(
    "fn", ["_sweep_jax", "_sweep_jax_batched", "_sweep_jax_sharded"])
def test_partition_jax_takes_only_resolved_backends(fn, tiny_case):
    """The jit implementations accept the two concrete names the engine
    resolves to; 'auto' or any other name is refused, not re-resolved."""
    from repro.core import partition_jax as pj

    g, cm = tiny_case
    graphs = g if fn == "_sweep_jax" else [g]
    extra = {"n_shards": 2} if fn == "_sweep_jax_sharded" else {}
    for bad in ("auto", "numpy", "mosaic"):
        with pytest.raises(ValueError, match="resolved backend"):
            getattr(pj, fn)(graphs, cm, [None], backend=bad, **extra)
    for good in ("scan", "pallas"):
        getattr(pj, fn)(graphs, cm, [None], backend=good, **extra)


def test_placement_api_exported_through_facade():
    import repro.api as api

    for name in (
        "LinkModel", "NodeSpec", "PlacementError", "PlacementPlan",
        "PlacementSpec", "PlacementSweep", "PlacementTable",
    ):
        assert name in api.__all__, name
        assert getattr(api, name) is not None
    # and through repro.core, still without importing jax
    import repro.core as core

    assert core.PlacementSpec is api.PlacementSpec
    assert core.solve_placement_numpy is not None
