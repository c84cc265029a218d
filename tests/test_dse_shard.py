"""Differential/property tier for the sharded DSE subsystem.

Locks in the three builder paths of the offline design-space exploration:

* **sharded = single-host**: builds on 1/2/4/8 (emulated) devices are
  byte-identical to the single-host table for every smoke config — payload
  arrays, header fingerprint, and content digest all match, and
  ``ServePlanner`` lookups against a sharded table match direct engine
  solves bit-exactly;
* **incremental = fresh**: a bucket/Q grid randomly split into
  ``extend_plan_table`` steps applied in shuffled order reassembles the
  fresh full build bit-for-bit, while an extend of an untouched base never
  re-solves an existing cell (pinned by ``SOLVE_COUNT``);
* **staleness probe**: accepts every clean table and rejects any table with
  one perturbed cell or a mismatched engine config.

The property checks run under a stdlib-``random`` seeded driver always, and
additionally under hypothesis when it is installed (the test_partition.py
idiom). Under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
shard tier) the 2/4/8-shard builds pmap across a real device mesh; on a
one-device host the same chunk decomposition runs sequentially — both must
produce identical bytes, so the suite is environment-agnostic.
"""

import random

import numpy as np
import pytest
# These suites pin the *legacy* entry points (deprecation shims) bit-for-bit
# against the facade-era implementations; the CI deprecation gate excludes
# them via -m "not legacy" (see conftest).
pytestmark = pytest.mark.legacy


from conftest import PLAN_BUCKETS
from helpers_random import random_cost_model, random_q_grid, random_task_graph

from repro.configs import SMOKE_CONFIGS
from repro.core import (
    PlanTable,
    PlanTableError,
    StaleTableError,
    build_plan_table,
    extend_plan_table,
    lower_config,
    probe_plan_table,
    q_min,
    shard_plan_table,
    shard_q_grid,
    sweep_jax,
    sweep_jax_batched,
    sweep_jax_sharded,
    whole_app_partition,
)
from repro.core import partition_jax
from repro.core import plan_table as pt_mod
from repro.launch.planner import ServePlanner
import repro.launch.planner as planner_mod

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

SHARD_COUNTS = (1, 2, 4, 8)


def _clone(table: PlanTable) -> PlanTable:
    return PlanTable(
        dict(table.header),
        *(getattr(table, name).copy() for name in PlanTable._PAYLOAD),
    )


def _assert_tables_bitidentical(a: PlanTable, b: PlanTable) -> None:
    assert a.fingerprint == b.fingerprint
    assert a.header == b.header
    for name in PlanTable._PAYLOAD:
        x, y = getattr(a, name), getattr(b, name)
        assert x.dtype == y.dtype and x.shape == y.shape, name
        assert x.tobytes() == y.tobytes(), f"{name} bytes differ"
    assert a.content_digest() == b.content_digest()


# -- engine level: sharded sweep == batched sweep ------------------------------


def test_shard_q_grid_is_balanced_and_covering():
    assert shard_q_grid(10, 4) == [(0, 3), (3, 6), (6, 8), (8, 10)]
    assert shard_q_grid(3, 8) == [(0, 1), (1, 2), (2, 3)]  # clamped
    assert shard_q_grid(5, 1) == [(0, 5)]
    for nq, ns in [(1, 1), (7, 3), (100, 8)]:
        chunks = shard_q_grid(nq, ns)
        assert chunks[0][0] == 0 and chunks[-1][1] == nq
        assert all(lo < hi for lo, hi in chunks)
        assert all(a[1] == b[0] for a, b in zip(chunks, chunks[1:]))
        assert max(hi - lo for lo, hi in chunks) - min(
            hi - lo for lo, hi in chunks) <= 1
    with pytest.raises(ValueError):
        shard_q_grid(0, 2)
    with pytest.raises(ValueError):
        shard_q_grid(4, 0)


@pytest.mark.parametrize("seed", range(5))
def test_sweep_jax_sharded_matches_batched(seed):
    """Random-graph batches: every output array of the sharded sweep is
    byte-identical to the one-call batched sweep, at every shard count."""
    rng = random.Random(seed)
    graphs = [random_task_graph(rng, max_tasks=7) for _ in range(3)]
    cm = random_cost_model(rng)
    qmn = max(q_min(g, cm) for g in graphs)
    whole = max(whole_app_partition(g, cm).e_total for g in graphs)
    qs = random_q_grid(rng, qmn, whole)
    ref = sweep_jax_batched(graphs, cm, qs, backend="scan")
    for n_shards in (1, 2, 3, len(qs)):
        got = sweep_jax_sharded(graphs, cm, qs, n_shards=n_shards,
                                backend="scan")
        for g_idx, (r, s) in enumerate(zip(ref, got)):
            assert r.n_tasks == s.n_tasks
            for field in ("dp", "parent", "e_total", "feasible", "starts"):
                a, b = getattr(r, field), getattr(s, field)
                assert a.tobytes() == b.tobytes(), (n_shards, g_idx, field)


def test_sweep_jax_sharded_pallas_chunks_match():
    """The CSR/Pallas backend shards as host-side Q chunks — still
    bit-identical (the kernel lanes the Q axis per call)."""
    rng = random.Random(7)
    g = random_task_graph(rng, max_tasks=8, min_tasks=4)
    cm = random_cost_model(rng)
    qs = random_q_grid(rng, q_min(g, cm), whole_app_partition(g, cm).e_total)
    ref = sweep_jax_batched([g], cm, qs, backend="pallas")
    got = sweep_jax_sharded([g], cm, qs, n_shards=3, backend="pallas")
    for field in ("dp", "parent", "e_total", "feasible", "starts"):
        assert getattr(ref[0], field).tobytes() == \
            getattr(got[0], field).tobytes(), field


def test_explicit_devices_too_few_for_shards_raise():
    """Named devices are a placement: a build asked to put 2 shards on one
    named device fails instead of running the shards back to back."""
    import jax

    from repro.api import PartitionSpec, QGridSharding, solve

    rng = random.Random(3)
    g = random_task_graph(rng, max_tasks=6, min_tasks=3)
    cm = random_cost_model(rng)
    qs = tuple(random_q_grid(rng, q_min(g, cm),
                             whole_app_partition(g, cm).e_total))
    spec = PartitionSpec(graph=g, cost=cm, q_grid=qs, backend="scan",
                         sharding=QGridSharding(2, jax.devices()[:1]))
    with pytest.raises(ValueError, match="2 Q shards need 2 devices, got 1"):
        solve(spec)


# -- table level: sharded builds are byte-identical ----------------------------


@pytest.mark.parametrize("arch", sorted(SMOKE_CONFIGS))
def test_sharded_build_bitidentical_to_single_host(arch, smoke_plan_table):
    """Every smoke config: 1/2/4/8-shard builds replay the single-host
    bytes exactly (npz payload + header fingerprint + content digest)."""
    cfg, cm, qs, single = smoke_plan_table(arch)
    for n_shards in SHARD_COUNTS:
        sharded = shard_plan_table(
            cfg, PLAN_BUCKETS, qs, n_shards=n_shards, cost=cm
        )
        _assert_tables_bitidentical(single, sharded)


def test_sharded_save_load_roundtrip_preserves_digest(tmp_path,
                                                      smoke_plan_table):
    _, _, _, table = smoke_plan_table("qwen3-4b", builder=shard_plan_table,
                                      n_shards=4)
    path = str(tmp_path / "sharded.npz")
    table.save(path)
    loaded = PlanTable.load(path)
    _assert_tables_bitidentical(table, loaded)


def test_sharded_table_lookups_match_direct_solves(smoke_plan_table):
    """ServePlanner against a sharded table answers every (bucket, Q) with
    bounds/energies bit-identical to direct engine solves."""
    cfg, cm, qs, table = smoke_plan_table("zamba2-7b",
                                          builder=shard_plan_table,
                                          n_shards=4)
    planner = ServePlanner(table)
    n_feasible = 0
    for (b, s) in PLAN_BUCKETS:
        g = lower_config(cfg, b, s, kind="time")
        direct = sweep_jax(g, cm, qs)
        for qi, q in enumerate(qs):
            if not direct.feasible[qi]:
                continue
            n_feasible += 1
            plan = planner.plan_for(b, s, q)
            assert list(plan.bounds) == direct.bounds(qi), (b, s, q)
            assert plan.e_total == direct.e_total[qi]
    assert n_feasible and planner.stats["lookups"] == n_feasible


# -- incremental extension -----------------------------------------------------


def test_extend_of_untouched_base_never_solves(smoke_plan_table):
    cfg, cm, _, base = smoke_plan_table("tinyllama-1.1b")
    solves = dict(partition_jax.SOLVE_COUNT)
    stats = dict(pt_mod.BUILD_STATS)
    out = extend_plan_table(base, cfg, cost=cm)
    assert out is base
    # re-adding already-tabulated cells is also a no-op
    out = extend_plan_table(
        base, cfg, add_buckets=PLAN_BUCKETS, add_q_values=base.q_values(),
        cost=cm,
    )
    assert out is base
    assert dict(partition_jax.SOLVE_COUNT) == solves, \
        "untouched extend must not hit the engine"
    assert dict(pt_mod.BUILD_STATS) == stats


def test_extend_solves_only_new_cells(plan_grid):
    """Growing (2 buckets × 4 Q) → (3 × 6) re-solves nothing tabulated:
    exactly one batched call for the new bucket × final grid and one for the
    old buckets × new Q points, and the old cells' bytes are moved, not
    recomputed."""
    cfg = SMOKE_CONFIGS["qwen3-4b"]
    cm, qs = plan_grid(cfg)
    base = build_plan_table(cfg, PLAN_BUCKETS[:2], [qs[0], qs[2], qs[4], qs[5]],
                            cost=cm)
    fresh = build_plan_table(cfg, PLAN_BUCKETS, qs, cost=cm)
    solves = dict(partition_jax.SOLVE_COUNT)
    ext = extend_plan_table(
        base, cfg, add_buckets=[PLAN_BUCKETS[2]], add_q_values=[qs[1], qs[3]],
        cost=cm,
    )
    delta = {k: partition_jax.SOLVE_COUNT[k] - solves[k] for k in solves}
    assert delta == {"sweep_jax": 0, "sweep_jax_batched": 2,
                     "sweep_jax_sharded": 0, "q_min_scan": 0,
                     "optimal_k_scan": 0, "q_min_pallas": 0,
                     "optimal_k_pallas": 0}
    _assert_tables_bitidentical(
        _strip_lineage(ext), _strip_lineage(fresh)
    )
    # provenance: the chain records base → extension, fresh is a single link
    assert ext.lineage == [base.fingerprint, fresh.fingerprint]
    assert fresh.lineage == [fresh.fingerprint]
    # old cells were byte-moved from the base table
    b_old = base.buckets().index(PLAN_BUCKETS[0])
    e_old = ext.buckets().index(PLAN_BUCKETS[0])
    for q in base.q_values():
        k_old = base.q_values().index(q)
        k_new = ext.q_values().index(q)
        assert base.e_total[b_old, k_old] == ext.e_total[e_old, k_new]


def _strip_lineage(table: PlanTable) -> PlanTable:
    out = _clone(table)
    out.header.pop("lineage", None)
    return out


def test_extend_sharded_matches_fresh(plan_grid):
    """Sharded extension solves land on the same bytes."""
    cfg = SMOKE_CONFIGS["whisper-large-v3"]
    cm, qs = plan_grid(cfg)
    base = build_plan_table(cfg, PLAN_BUCKETS[:1], qs, cost=cm)
    fresh = build_plan_table(cfg, PLAN_BUCKETS, qs, cost=cm)
    ext = extend_plan_table(base, cfg, add_buckets=PLAN_BUCKETS[1:], cost=cm,
                            n_shards=4)
    assert ext.content_digest() == fresh.content_digest()


def test_extend_rejects_mismatched_engine_config(plan_grid, smoke_plan_table):
    cfg = SMOKE_CONFIGS["qwen3-4b"]
    cm, qs = plan_grid(cfg)
    _, _, _, base = smoke_plan_table("qwen3-4b")
    other = SMOKE_CONFIGS["xlstm-1.3b"]
    with pytest.raises(PlanTableError):
        extend_plan_table(base, other, add_buckets=[(8, 64)], cost=cm)


def test_planner_cli_shard_and_extend_roundtrip(tmp_path):
    """--shards builds and --extend grows the on-disk table; the grown table
    is content-identical to a fresh build of the same final grid."""
    out = str(tmp_path / "cli.npz")
    assert planner_mod.main(
        ["--arch", "qwen3-4b", "--buckets", "2x16,2x24", "--q-points", "5",
         "--out", out, "--shards", "2", "--probe", "3"]
    ) == 0
    base = PlanTable.load(out)
    assert base.buckets() == [(2, 16), (2, 24)]
    assert planner_mod.main(
        ["--arch", "qwen3-4b", "--buckets", "2x16,2x24,2x32", "--out", out,
         "--extend", "--shards", "2"]
    ) == 0
    grown = PlanTable.load(out)
    assert grown.buckets() == [(2, 16), (2, 24), (2, 32)]
    assert grown.lineage[0] == base.fingerprint and len(grown.lineage) == 2
    fresh = build_plan_table(
        SMOKE_CONFIGS["qwen3-4b"], grown.buckets(), grown.q_values(),
        cost=pt_mod._default_cost("time"),
    )
    assert grown.content_digest() == fresh.content_digest()


# -- staleness probe -----------------------------------------------------------


def test_probe_accepts_clean_tables(smoke_plan_table):
    for arch in ("qwen3-4b", "xlstm-1.3b"):
        cfg, cm, _, table = smoke_plan_table(arch)
        assert probe_plan_table(table, cfg, k=4, cost=cm) == 4
        assert probe_plan_table(table, cfg, k=None, cost=cm) == \
            table.n_buckets * table.n_q


def test_probe_rejects_any_single_perturbed_cell(smoke_plan_table):
    """Every feasible cell, perturbed alone (e_total, a cycle energy, or a
    segment bound), turns the full probe into a StaleTableError; flipping
    any feasibility flag does too."""
    cfg, cm, _, table = smoke_plan_table("qwen3-4b")
    nb, nq = table.feasible.shape
    probed = 0
    for b in range(nb):
        for k in range(nq):
            flipped = _clone(table)
            flipped.feasible[b, k] = not flipped.feasible[b, k]
            with pytest.raises(StaleTableError):
                probe_plan_table(flipped, cfg, k=None, cost=cm)
            if not table.feasible[b, k]:
                continue
            probed += 1
            bad = _clone(table)
            bad.e_total[b, k] = np.nextafter(bad.e_total[b, k], np.inf)
            with pytest.raises(StaleTableError):
                probe_plan_table(bad, cfg, k=None, cost=cm)
            lo = int(table.seg_ptr[b * nq + k])
            bad = _clone(table)
            bad.cycle_energy[lo] = np.nextafter(bad.cycle_energy[lo], np.inf)
            with pytest.raises(StaleTableError):
                probe_plan_table(bad, cfg, k=None, cost=cm)
            bad = _clone(table)
            bad.seg_end[lo] = bad.seg_end[lo] + 1 if \
                bad.seg_end[lo] < table.n_tasks[b] else bad.seg_end[lo] - 1
            with pytest.raises(StaleTableError):
                probe_plan_table(bad, cfg, k=None, cost=cm)
    assert probed  # the grid straddles feasibility, so some cells are live


def test_probe_rejects_mismatched_engine_config(smoke_plan_table):
    from repro.core import PAPER_FRAM_MODEL

    cfg, cm, _, table = smoke_plan_table("qwen3-4b")
    with pytest.raises(StaleTableError):
        probe_plan_table(table, cfg, k=2, cost=PAPER_FRAM_MODEL)
    with pytest.raises(StaleTableError):
        probe_plan_table(table, SMOKE_CONFIGS["xlstm-1.3b"], k=2, cost=cm)


def test_from_file_probe_wiring(tmp_path, smoke_plan_table):
    cfg, cm, _, table = smoke_plan_table("whisper-large-v3")
    path = str(tmp_path / "probed.npz")
    table.save(path)
    planner = ServePlanner.from_file(path, probe=cfg, probe_k=3)
    assert planner.table.fingerprint == table.fingerprint
    bad = _clone(table)
    bad.e_total[0, -1] = np.nextafter(bad.e_total[0, -1], np.inf)
    bad.save(path)
    with pytest.raises(StaleTableError):
        ServePlanner.from_file(path, probe=cfg, probe_k=None)


# -- property: shuffled incremental assembly == fresh build --------------------


def check_shuffled_extension_chain(cfg, cm, qs, rng: random.Random):
    """Randomly split PLAN_BUCKETS × qs into a base build plus extension
    steps, apply the steps in shuffled order, and require the final table to
    be content-identical to the fresh full build (with the lineage chain one
    link per applied step)."""
    buckets = list(PLAN_BUCKETS)
    n_base_b = rng.randint(1, len(buckets))
    n_base_q = rng.randint(1, len(qs))
    base_buckets = rng.sample(buckets, n_base_b)
    base_qs = rng.sample(qs, n_base_q)
    rest_b = [b for b in buckets if b not in base_buckets]
    rest_q = [q for q in qs if q not in base_qs]

    steps = []
    for b in rest_b:
        steps.append(("bucket", b))
    for q in rest_q:
        steps.append(("q", q))
    rng.shuffle(steps)
    # group the shuffled atoms into 1..3 extension calls
    n_calls = rng.randint(1, min(3, len(steps))) if steps else 0
    calls = [steps[i::n_calls] for i in range(n_calls)] if n_calls else []

    table = build_plan_table(cfg, base_buckets, base_qs, cost=cm)
    applied = 1
    for call in calls:
        add_b = [x for kind_, x in call if kind_ == "bucket"]
        add_q = [x for kind_, x in call if kind_ == "q"]
        table = extend_plan_table(table, cfg, add_buckets=add_b,
                                  add_q_values=add_q, cost=cm)
        applied += 1
    fresh = build_plan_table(cfg, buckets, qs, cost=cm)
    assert table.content_digest() == fresh.content_digest()
    assert table.fingerprint == fresh.fingerprint
    assert len(table.lineage) == applied
    assert table.lineage[-1] == fresh.fingerprint


@pytest.mark.parametrize("seed", range(6))
def test_shuffled_extension_chain_seeded(seed, plan_grid):
    cfg = SMOKE_CONFIGS["qwen3-4b"]
    cm, qs = plan_grid(cfg)
    check_shuffled_extension_chain(cfg, cm, qs, random.Random(seed))


if HAVE_HYPOTHESIS:

    class TestShuffledExtensionFuzz:
        @settings(max_examples=12, deadline=None)
        @given(seed=st.integers(min_value=0, max_value=2**32 - 1))
        def test_any_extension_order_reassembles_fresh_build(
            self, seed, plan_grid
        ):
            cfg = SMOKE_CONFIGS["qwen3-4b"]
            cm, qs = plan_grid(cfg)
            check_shuffled_extension_chain(cfg, cm, qs, random.Random(seed))

else:

    def test_extension_fuzz_skipped_without_hypothesis():
        pytest.importorskip("hypothesis")
