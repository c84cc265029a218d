"""Serving from the bf16 compute copy of the weights changes no number.

* every family the planned path serves gives the same logits, bit for bit,
  and the same greedy tokens on its float32 tree and on
  ``compute_copy(tree)``; every leaf left float32 is the source's own object;
* a leaf read in float32 (a norm gain) cast to bf16 does change the logits,
  so the comparison above can fail;
* ``PlannedExecutor`` makes one copy per weight tree however many keys hold
  it (``serve.param_cast``), drops it once no key holds its tree, and a
  traffic run after warm-up re-traces nothing.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, resolve_config
from repro.launch import serve as serve_mod
from repro.launch.serve import PlannedExecutor, _host_mesh, _pre_batch, _step_fns
from repro.models import api, common
from repro.models.common import COMPUTE_DTYPE, PARAM_DTYPE, compute_copy

PROMPT, GEN = 8, 3
MAX_SEQ = PROMPT + GEN


def _normal_tree(cfg, seed):
    """The program's weight tree with every leaf drawn from a seeded normal,
    so that no leaf read in float32 (a gain of ones, a zero bias) is exact
    in bf16."""
    params, _ = api.init_params(cfg, jax.random.PRNGKey(seed), max_seq=MAX_SEQ)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return treedef.unflatten([
        0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for k, leaf in zip(keys, leaves)])


def _greedy(arch, cfg, params, prompts):
    """Prefill, then GEN - 1 decode steps on the serving steps; returns every
    step's logits and the greedy tokens."""
    prefill, decode = _step_fns(arch, True, MAX_SEQ, donate=False)
    logits, cache = prefill(params, _pre_batch(cfg, prompts))
    steps = [np.asarray(logits)]
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    toks = [np.asarray(tok)]
    for i in range(GEN - 1):
        logits, cache = decode(params, cache, tok, jnp.int32(PROMPT + i))
        steps.append(np.asarray(logits))
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        toks.append(np.asarray(tok))
    return steps, np.concatenate(toks, axis=1)


def _run_both(arch, params_of):
    cfg = resolve_config(arch, smoke=True)
    with _host_mesh():
        params = _normal_tree(cfg, 7)
        prompts = jax.random.randint(jax.random.PRNGKey(3), (1, PROMPT), 0,
                                     cfg.vocab)
        return (params, params_of(params),
                _greedy(arch, cfg, params, prompts),
                _greedy(arch, cfg, params_of(params), prompts))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_compute_copy_changes_no_number(arch):
    params, copy, (ref_logits, ref_toks), (logits, toks) = _run_both(
        arch, compute_copy)
    for want, got in zip(ref_logits, logits):
        np.testing.assert_array_equal(want, got)
    np.testing.assert_array_equal(ref_toks, toks)

    src = jax.tree_util.tree_leaves_with_path(params)
    out = jax.tree.leaves(copy)
    cast = 0
    for (path, a), b in zip(src, out):
        name = path[-1].key
        if name in common.COMPUTE_COPY_NAMES:
            assert b.dtype == COMPUTE_DTYPE, jax.tree_util.keystr(path)
            cast += 1
        else:
            assert b is a and b.dtype == PARAM_DTYPE, jax.tree_util.keystr(path)
    assert cast >= 5  # the embedding and at least one layer's matmuls


def test_casting_a_float32_read_leaf_changes_the_logits():
    """The comparison above has teeth: norm gains are read in float32, and a
    copy that rounds them gives other logits."""
    names = common.COMPUTE_COPY_NAMES | {"ln1", "ln2", "final_norm"}

    def rounded(params):
        return jax.tree_util.tree_map_with_path(
            lambda p, a: a.astype(COMPUTE_DTYPE) if p[-1].key in names else a,
            params)

    _, _, (ref_logits, _), (logits, _) = _run_both("qwen1.5-0.5b", rounded)
    assert any(not np.array_equal(a, b) for a, b in zip(ref_logits, logits))


# -- the memo and the counter ------------------------------------------------

ARCH = "qwen1.5-0.5b"
SHAPES = [(1, 6, 4), (1, 12, 4), (1, 20, 4)]  # three (seed, max_seq) keys


@pytest.fixture(scope="module")
def executor():
    from repro.launch.planner import build_table_for_arch

    table = build_table_for_arch(ARCH, [(b, p + g) for b, p, g in SHAPES],
                                 n_q=4)
    ex = PlannedExecutor(ARCH, table)
    tree, _ = api.init_params(ex.cfg, jax.random.PRNGKey(0))
    for b, p, g in SHAPES:
        ex._params[(0, p + g)] = tree
    return ex


def _run(ex, shape):
    cont = ex.open(*shape, seed=0)
    cont.run_to_completion()


def test_one_copy_per_tree_counted_and_released(executor):
    ex = executor
    before = dict(serve_mod.PARAM_CAST)
    for shape in SHAPES:
        _run(ex, shape)
    _run(ex, SHAPES[0])
    opens = len(SHAPES) + 1
    assert serve_mod.PARAM_CAST["miss"] - before["miss"] == 1
    assert serve_mod.PARAM_CAST["hit"] - before["hit"] == opens - 1
    assert len(ex._params._copies) == 1
    (copy,) = ex._params._copies.values()
    old = weakref.ref(copy["embed"])
    del copy

    # a reseed of the benchmark: every key to None, then to a new tree
    for key in list(ex._params):
        ex._params[key] = None
    new, _ = api.init_params(ex.cfg, jax.random.PRNGKey(1))
    for key in list(ex._params):
        ex._params[key] = new
    gc.collect()
    assert old() is None, "a copy outlived every key's tree"
    assert not ex._params._copies

    before = dict(serve_mod.PARAM_CAST)
    _run(ex, SHAPES[1])
    _run(ex, SHAPES[2])
    assert serve_mod.PARAM_CAST["miss"] - before["miss"] == 1
    assert serve_mod.PARAM_CAST["hit"] - before["hit"] == 1


def test_traffic_after_warmup_retraces_nothing(executor):
    from repro.launch.traffic import TrafficHarness, Request

    reqs = [Request(rid=i, batch=b, prompt_len=p, gen=g, time=float(i), seed=0)
            for i, (b, p, g) in enumerate(SHAPES * 2)]
    harness = TrafficHarness(executor, keep_tokens=True)
    harness.warmup(reqs)
    before = dict(serve_mod.PARAM_CAST)
    report = harness.run(reqs)
    assert report.completed == len(reqs)
    assert not any(report.trace_delta.values()), report.trace_delta
    assert serve_mod.PARAM_CAST["miss"] == before["miss"]
    assert serve_mod.PARAM_CAST["hit"] - before["hit"] == len(reqs)
