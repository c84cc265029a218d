"""Zamba2 on the CPU against the benchmark's plain float32 reference
(``benchmarks/chip/references/zamba2_lm.py``), at a smoke size with the
published structure: two shared blocks used alternately, B/C in two
groups, an adapter and a linear per hybrid layer, hybrid layers 2 and 4 of
6, and a chunk of 8 so that prefills of 13 and 16 positions run a ragged and
a whole last chunk."""

import importlib.util
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY, SMOKE_CONFIGS
from repro.models import api, recurrent, ssm

ROOT = pathlib.Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmarks" / "chip"

# Test weights: the reference's matrices scaled by 4 and its adapters by 12,
# so that at d = 64 the shared blocks and each adapter move the logits by
# 32-120% of the largest logit (seeds 1-5), as they do at full width; at the
# reference's own N(0, 0.02^2) a d = 64 adapter moves them by 0.4%, inside
# any bfloat16 tolerance. The program runs bfloat16 matmuls with float32
# accumulation; its logits then lie within 2.7-4.4% of the reference's
# largest logit (seeds 1-5, both lengths). 0.1 leaves twice that; the
# reference with fp8 matmul operands, the precision below the program's,
# lands at 28-54%, and a program without one adapter at 32% or more.
LOGIT_TOL = 0.1
MATRICES = {"in_proj", "out_proj", "wq", "wk", "wv", "wo", "gate_up", "down",
            "linear", "embed"}
SSD_TOL = 1e-4


def _ref():
    spec = importlib.util.spec_from_file_location(
        "zamba2_lm_reference", CHIP / "references" / "zamba2_lm.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _ref()
CFG = SMOKE_CONFIGS["zamba2-7b"]


def smoke_file() -> dict:
    """The benchmark's configuration file at the program's smoke widths."""
    c = json.loads((CHIP / "configs" / "zamba2-7b.json").read_text())
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             attention_head_dim=32, attention_hidden_size=128,
             ffn_hidden_size=160, intermediate_size=160, vocab_size=256,
             mamba_d_state=16, mamba_headdim=16, n_mamba_heads=8, chunk_size=8,
             adapter_rank=8, num_hidden_layers=6, hybrid_layer_ids=[2, 4],
             layers_block_type=["mamba", "mamba", "hybrid", "mamba", "hybrid",
                                "mamba"])
    return c


FILE = smoke_file()


def weights(seed):
    def scale(path, x):
        keys = [getattr(k, "key", None) for k in path]
        if "adapter" in keys:
            return 12 * x
        return 4 * x if keys[-1] in MATRICES else x

    return jax.tree_util.tree_map_with_path(scale, REF.make_params(FILE, seed))


def _rel(a, b):
    return float(np.abs(np.asarray(a, np.float32) - b).max() / np.abs(b).max())


def _served_logits(params, toks, S, steps):
    """Prefill toks[:, :S], then ``steps`` cached decode steps fed the next
    tokens: logits at positions S-1 .. S-1+steps."""
    lg, cache = api.prefill(CFG, params, {"tokens": jnp.asarray(toks[:, :S])},
                            S + steps)
    out = [lg[:, 0]]
    for i in range(steps):
        lg, cache = api.decode_step(CFG, params, cache,
                                    jnp.asarray(toks[:, S + i:S + i + 1]),
                                    jnp.int32(S + i))
        out.append(lg[:, 0])
    return np.stack([np.asarray(o, np.float32) for o in out], axis=1)


def test_reference_weight_tree_is_the_programs():
    abstract, _ = api.init_params(CFG, None)
    shape = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    assert shape(REF.make_params(FILE, 1)) == shape(abstract)


@pytest.mark.parametrize("S", [13, 16])
@pytest.mark.parametrize("seed", [1, 2])
def test_prefill_then_decode_matches_reference(S, seed):
    params = weights(seed)
    steps = 5
    toks = np.random.default_rng(seed).integers(0, 256, (2, S + steps)).astype(np.int32)
    full = np.asarray(REF.logits_full(FILE, params, toks))[:, S - 1:]
    assert _rel(_served_logits(params, toks, S, steps), full) < LOGIT_TOL
    # the control: the reference with fp8 operands fails the same tolerance
    fp8 = np.asarray(REF.logits_full(FILE, params, toks, quant=True))[:, S - 1:]
    assert _rel(fp8, full) > LOGIT_TOL


@pytest.mark.parametrize("S", [5, 13, 16])
def test_chunked_ssd_matches_sequential_recurrence(S, monkeypatch):
    """The program's chunked prefill and one decode step after it, computed
    in float32, against the sequential recurrence over S + 1 positions."""
    monkeypatch.setattr(ssm, "COMPUTE_DTYPE", jnp.float32)
    p = jax.tree.map(lambda a: a[0], weights(3)["runs"][0]["cell"])
    h = jax.random.normal(jax.random.PRNGKey(S), (2, S + 1, CFG.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, s_want, conv_want = REF.mamba2(p, h, G=CFG.ssm_ngroups,
                                             eps=CFG.norm_eps, quant=False)
        _, s_prev, _ = REF.mamba2(p, h[:, :S], G=CFG.ssm_ngroups,
                                  eps=CFG.norm_eps, quant=False)
        got, st = ssm.mamba_chunked(CFG, p, h[:, :S])
        assert _rel(got, want[:, :S]) < SSD_TOL
        assert _rel(st["ssm"], s_prev) < SSD_TOL
        step, st = ssm.mamba_decode_step(CFG, p, h[:, S:], st)
    assert _rel(step, want[:, S:]) < SSD_TOL
    assert _rel(st["ssm"], s_want) < SSD_TOL
    assert _rel(st["conv"], conv_want) < SSD_TOL


def _prefill_states(params, toks):
    _, cache = api.prefill(CFG, params, {"tokens": jnp.asarray(toks)}, toks.shape[1])
    # layer order: runs[0] = layers 0-1, hybrid[0] = 2, runs[1] = 3,
    # hybrid[1] = 4, runs[2] = 5
    return [cache["runs"][0], cache["hybrid"][0], cache["runs"][1],
            cache["hybrid"][1], cache["runs"][2]]


def _same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a),
                                                     jax.tree.leaves(b)))


def test_shared_block_one_alters_only_what_follows_its_first_call():
    params = weights(4)
    toks = np.random.default_rng(4).integers(0, 256, (1, 12)).astype(np.int32)
    base = _prefill_states(params, toks)
    for b, first in ((0, 1), (1, 3)):  # block b is first called at hybrid b
        changed = jax.tree.map(lambda a: a, params)
        changed["shared"][b]["mlp"]["down"] = params["shared"][b]["mlp"]["down"] * 2
        states = _prefill_states(changed, toks)
        assert all(_same(x, y) for x, y in zip(states[:first], base[:first]))
        assert not any(_same(x, y) for x, y in zip(states[first:], base[first:]))


@pytest.mark.parametrize("k", [0, 1])
def test_zeroing_an_adapter_matters(k):
    """A program that left out hybrid layer k's adapter would fail the
    comparison with the reference; with the adapter zeroed in both, it
    passes."""
    params = weights(3)
    toks = np.random.default_rng(3).integers(0, 256, (1, 12)).astype(np.int32)
    zeroed = jax.tree.map(lambda a: a, params)
    zeroed["hybrid"][k]["adapter"] = jax.tree.map(jnp.zeros_like,
                                                  params["hybrid"][k]["adapter"])
    ref = np.asarray(REF.logits_full(FILE, params, toks))
    without = recurrent.zamba_forward(CFG, zeroed, toks, remat=False)
    assert _rel(without, ref) > LOGIT_TOL
    assert _rel(without, np.asarray(REF.logits_full(FILE, zeroed, toks))) < LOGIT_TOL


def test_param_count_is_the_published_models():
    """7.36 B, counted here from the published shapes of config.json."""
    d, d_in, V, ff, L = 3584, 7168, 32000, 14336, 81
    in_proj = d * (2 * d_in + 2 * 2 * 64 + 112)      # z, xBC, dt
    conv = (4 + 1) * (d_in + 2 * 2 * 64)             # weight and bias
    mamba = in_proj + conv + 3 * 112 + d_in + d_in * d + d  # A, dt, D, norms, out
    shared = (2 * d + 3 * 7168 * 7168 + 7168 * d       # norm, q/k/v, o
              + d + d * 2 * ff + ff * d)               # norm, gate/up, down
    per_hybrid = d * 128 + 128 * 2 * ff + d * d        # adapter, linear
    published = V * d + d + L * mamba + 2 * shared + 13 * per_hybrid
    cfg = REGISTRY["zamba2-7b"]
    assert cfg.param_count() == published
    assert round(published / 1e9, 2) == 7.36
    assert recurrent.zamba_layout(cfg)[:4] == [("run", 6), ("hybrid", 0),
                                               ("run", 4), ("hybrid", 1)]


def test_layer_scan_hlo_grows_with_hybrid_layers_not_depth():
    """Deeper runs of plain layers add no loop bodies: the decode program
    has one while loop per run, whatever its length."""
    import dataclasses

    def n_loops(n_layers, ids):
        cfg = dataclasses.replace(CFG, n_layers=n_layers, hybrid_layer_ids=ids)
        params, _ = api.init_params(cfg, None)
        cache, _ = api.cache_shape(cfg, 1, 8)
        text = jax.jit(lambda p, c, t: api.decode_step(cfg, p, c, t, jnp.int32(0))
                       ).lower(params, cache, jax.ShapeDtypeStruct((1, 1), jnp.int32)
                               ).as_text()
        return text.count("stablehlo.while")

    assert n_loops(6, (2, 4)) == n_loops(30, (10, 20)) == 3
