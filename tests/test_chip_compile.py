"""Compile the main path's programs for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than attached, so these tests catch what interpret mode
cannot: unaligned dynamic slices in a kernel, scoped-VMEM overruns, programs
that do not fit the chip. Nothing runs; only the shapes are real.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the fixture is where a test
worker that cannot load it skips instead of failing collection.
"""

import dataclasses
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

ARCH = "qwen1.5-0.5b"
# The 5458-task THERMAL head-count graph: tasks and read slots of its CSR
# export (tests/test_partition_sweep.py builds the graph itself).
THERMAL_N, THERMAL_NNZ = 5458, 10908


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """ShapeDtypeStructs of ``tree``'s leaves, placed on ``sharding``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding),
        tree)


def _kernel_args(one_chip, n, nnz, nq_pad):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    f32, i32 = jnp.float32, jnp.int32
    return (
        s((n + 1,), i32), s((n,), f32), s((n,), f32), s((1,), f32),
        s((nnz,), f32), s((nnz,), f32),
        s((nnz,), i32), s((nnz,), i32), s((nnz,), i32),
        s((nq_pad,), f32),
    )


# Lane widths as ops.sweep_columns pads them: the 9-point Q grid of
# examples/headcount_full.py, minimax's single lane, exact-K with K = 18.
@pytest.mark.parametrize("mode,nq_pad", [
    ("sum", 16), ("minimax", 8), ("exact_k", 24),
])
def test_sweep_kernel_compiles_at_thermal_size(one_chip, mode, nq_pad):
    from repro.kernels.partition_sweep.kernel import sweep_columns_call

    compiled = sweep_columns_call.lower(
        *_kernel_args(one_chip, THERMAL_N, THERMAL_NNZ, nq_pad),
        interpret=False, mode=mode, combine_max=mode == "minimax",
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("nq_pad,tile", [(128, 512), (512, 256)])
def test_vmem_guard_boundary_compiles(one_chip, nq_pad, tile):
    """The largest N the VMEM guard accepts compiles: the guard never
    refuses less than the compiler takes at its boundary."""
    from repro.kernels.partition_sweep.kernel import sweep_columns_call
    from repro.kernels.partition_sweep.ops import SCOPED_VMEM_BYTES
    from repro.kernels.partition_sweep.kernel import vmem_bytes

    n = 8
    while vmem_bytes(n + 8, nq_pad, tile) <= SCOPED_VMEM_BYTES:
        n += 8
    sweep_columns_call.lower(
        *_kernel_args(one_chip, n, 1000, nq_pad), interpret=False, tile=tile,
    ).compile()


def test_scan_dp_compiles_under_x64(one_chip):
    from repro.configs import resolve_config
    from repro.core.cost import cost_scalars
    from repro.core.graph import GraphArrays
    from repro.core.layer_profile import default_cost_model, lower_config
    from repro.core.partition_jax import _dp_sweep_jit

    g = lower_config(resolve_config(ARCH, smoke=False), batch=2, seq=72)
    arrays = g.to_arrays()
    with jax.enable_x64():
        ga = {f.name: jnp.asarray(getattr(arrays, f.name))
              for f in dataclasses.fields(GraphArrays) if f.name != "n_tasks"}
        cost = jnp.asarray(cost_scalars(default_cost_model("time")))
        qs = jnp.zeros((65,), jnp.float64)
        assert cost.dtype == jnp.float64
        compiled = _dp_sweep_jit.lower(
            _on(one_chip, ga),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
            _on(one_chip, cost), _on(one_chip, qs),
        ).compile()
    assert compiled is not None


def _served(sharding, params):
    """The weights as the serving steps receive them: ``compute_copy``."""
    from repro.models.common import compute_copy

    return _on(sharding, jax.eval_shape(compute_copy, params))


def _weight_converts(text: str, params) -> list:
    """Top-level convert ops of a compiled module (not those inside a
    fusion's body, which write nothing to memory) whose result has the shape
    of a weight, or of one layer of a stacked weight: weights cast inside
    the program, on every call."""
    shapes = set()
    for leaf in jax.tree.leaves(params):
        if leaf.ndim >= 2:
            shapes |= {tuple(leaf.shape), tuple(leaf.shape[1:])}
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    found, comp = [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            comp = head.group(1)
            continue
        op = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* convert\(",
                      line)
        if op and comp not in fused:
            shape = tuple(int(d) for d in op.group(2).split(",") if d)
            if shape in shapes:
                found.append((op.group(1), shape))
    return found


@pytest.fixture(scope="module")
def full_width(one_chip):
    from repro.configs import resolve_config
    from repro.models import api

    cfg = resolve_config(ARCH, smoke=False)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (24, 1024, 151936)
    params, _ = api.init_params(cfg, None)
    return cfg, _served(one_chip, params)


def test_full_width_prefill_compiles(one_chip, full_width):
    from repro.launch.serve import _step_fns

    cfg, params = full_width
    prefill, _ = _step_fns(ARCH, False, 40, donate=False)
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32, sharding=one_chip)
    compiled = prefill.lower(params, {"tokens": tokens}).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    assert _weight_converts(compiled.as_text(), params) == []


@pytest.mark.parametrize("donate", [False, True])
def test_full_width_decode_step_compiles(one_chip, full_width, donate):
    from repro.launch.serve import _step_fns
    from repro.models import api

    cfg, params = full_width
    _, decode = _step_fns(ARCH, False, 40, donate=donate)
    cache, _ = api.cache_shape(cfg, 2, 40)
    compiled = decode.lower(
        params, _on(one_chip, cache),
        jax.ShapeDtypeStruct((2, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    assert _weight_converts(compiled.as_text(), params) == []


def _relayouts_of(text: str, dims) -> list:
    """Transpose and copy ops of a compiled module whose operand has, size-1
    axes aside, the ``dims`` in some order: relayouts of such a value."""
    shapes = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", text))
    want = sorted(dims)
    found = []
    for name, op, arg in re.findall(
            r"%([\w.\-]+) = \S+ (transpose|copy)\(%([\w.\-]+)\)", text):
        shape = [int(d) for d in shapes.get(arg, "").split(",") if d]
        if sorted(d for d in shape if d != 1) == want:
            found.append((name, op, arg))
    return found


def test_full_width_decode_reads_each_layer_cache_in_place(one_chip, full_width):
    """At the longest serving bucket the decode step fits the chip, and no
    transpose or copy takes one layer's K or V slice as its operand: the
    attention reads each layer's cache where it lies."""
    from repro.launch.serve import _step_fns
    from repro.models import api

    cfg, params = full_width
    S = 4128
    _, decode = _step_fns(ARCH, False, S, donate=False)
    cache, _ = api.cache_shape(cfg, 1, S)
    compiled = decode.lower(
        params, _on(one_chip, cache),
        jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9
    assert _relayouts_of(compiled.as_text(), (S, cfg.n_kv_heads, cfg.hd)) == []


def test_float32_weights_are_cast_inside_the_step(one_chip):
    """The check above can fail: on the float32 tree the decode step casts
    each matmul weight (the stacked MLP weights, the embedding) itself."""
    from repro.configs import resolve_config
    from repro.launch.serve import _step_fns
    from repro.models import api

    cfg = resolve_config(ARCH, smoke=False)
    params, _ = api.init_params(cfg, None)
    params = _on(one_chip, params)
    _, decode = _step_fns(ARCH, False, 40, donate=False)
    cache, _ = api.cache_shape(cfg, 2, 40)
    compiled = decode.lower(
        params, _on(one_chip, cache),
        jax.ShapeDtypeStruct((2, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    shapes = {shape for _, shape in _weight_converts(compiled.as_text(), params)}
    assert {(24, 1024, 2816), (24, 2816, 1024), (151936, 1024)} <= shapes


# -- the program names the benchmark's trace readers match ---------------------

def _reader_constant(metric: str, name: str) -> str:
    import importlib.util
    import pathlib

    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "metrics" / f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, name)


def test_decode_step_lowers_under_the_name_its_reader_matches():
    """``decode_step_ms`` finds the decode program by its module name."""
    from repro.configs import resolve_config
    from repro.launch.serve import _step_fns
    from repro.models import api

    cfg = resolve_config(ARCH, smoke=True)
    params, _ = api.init_params(cfg, None)
    cache, _ = api.cache_shape(cfg, 1, 40)
    _, decode = _step_fns(ARCH, True, 40, donate=False)
    lowered = decode.lower(
        _on(None, params), _on(None, cache),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    assert lowered.as_text().startswith("module @jit__decode ")
    assert _reader_constant("decode_step_ms", "PROGRAM") in "jit__decode"


def test_prefill_lowers_under_the_name_its_reader_matches():
    """``prefill_step_ms`` finds the prefill program by its module name."""
    from repro.configs import resolve_config
    from repro.launch.serve import _step_fns
    from repro.models import api

    params, _ = api.init_params(resolve_config(ARCH, smoke=True), None)
    prefill, _ = _step_fns(ARCH, True, 40, donate=False)
    lowered = prefill.lower(_on(None, params),
                            {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)})
    assert lowered.as_text().startswith("module @jit__prefill ")
    assert _reader_constant("prefill_step_ms", "PROGRAM") in "jit__prefill"


def test_sweep_kernel_compiles_under_the_name_its_reader_matches(one_chip):
    """``sweep_kernel_roofline`` finds the kernel by its custom call's name
    on the op line, which the jitted wrapper gives it."""
    from repro.kernels.partition_sweep.kernel import sweep_columns_call

    compiled = sweep_columns_call.lower(
        *_kernel_args(one_chip, 64, 100, 8), interpret=False, mode="sum",
    ).compile()
    calls = [line.split("=")[0].strip() for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and calls[0].startswith("%sweep_columns_call")
    assert calls[0].startswith(_reader_constant("sweep_kernel_roofline", "KERNEL"))


# -- the benchmark's Zamba2 stage: the published widths, the file's depth -----

@pytest.fixture(scope="module")
def zamba_stage(one_chip):
    import json
    import pathlib

    from repro.configs import resolve_config
    from repro.models import api

    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "configs" / "zamba2-7b.json")
    c = json.loads(path.read_text())
    cfg = dataclasses.replace(resolve_config("zamba2-7b"),
                              n_layers=c["num_hidden_layers"],
                              hybrid_layer_ids=tuple(c["hybrid_layer_ids"]))
    params, _ = api.init_params(cfg, None)
    return cfg, _served(one_chip, params)


def test_zamba_stage_prefill_compiles_at_the_longest_prompt(one_chip, zamba_stage):
    """4064 = 15 chunks of 256 and 224 more, into a 4096-position cache, with
    no weight cast inside the program."""
    from repro.launch.serve import _step_fns

    cfg, params = zamba_stage
    prefill, _ = _step_fns(cfg, False, 4096, donate=False)
    tokens = jax.ShapeDtypeStruct((1, 4064), jnp.int32, sharding=one_chip)
    compiled = prefill.lower(params, {"tokens": tokens}).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    assert _weight_converts(compiled.as_text(), params) == []


def test_zamba_stage_decode_compiles_with_its_scopes(one_chip, zamba_stage):
    """The decode program fits the chip, casts no weight, and its ops carry
    the ``mamba2`` and ``zamba.shared`` scopes in their metadata."""
    from repro.launch.serve import _step_fns
    from repro.models import api

    cfg, params = zamba_stage
    _, decode = _step_fns(cfg, False, 4096, donate=False)
    cache, _ = api.cache_shape(cfg, 1, 4096)
    compiled = decode.lower(
        params, _on(one_chip, cache),
        jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    text = compiled.as_text()
    assert _weight_converts(text, params) == []
    assert "/mamba2/" in text and "/zamba.shared/" in text
