"""A decode step writes its K/V rows at ``pos`` with one dynamic-update-slice
per stacked cache, and the caches it leaves are those of the one-hot write.

The oracle below is the decode attention written the one-hot way: each layer
blends its new row into its whole cache, attends over the blend, and the
stacked caches are blended again after the layer scan. The program's path
must leave the same caches bit for bit, and the same logits, for every
family that decodes through ``decode_attention``.
"""

import functools
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import ALL_ARCHS, SMOKE_CONFIGS
from repro.models import api, attention, encdec, recurrent, transformer
from repro.models.attention import CACHE_WRITE
from repro.models.common import COMPUTE_DTYPE

DECODERS = [a for a in ALL_ARCHS if SMOKE_CONFIGS[a].family != "ssm"]
MAX, PROMPT, STEPS = 40, 24, 3
# float32 logits of a bf16 model: room for a different accumulation order
LOGIT_TOL = 1e-5
SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")


def _onehot(cache, rows, pos):
    oh = (jnp.arange(cache.shape[-3]) == pos).astype(cache.dtype)[:, None, None]
    return cache * (1 - oh) + oh * rows.astype(cache.dtype)


def _onehot_attention(cfg, p, x, cache_k, cache_v, pos, *, cross=False,
                      write=False):
    """Decode attention over the cache with the new row blended in at
    ``pos``; with ``write``, the blended caches are returned, else the rows."""
    if cross:
        return attention.decode_attention(cfg, p, x, cache_k, cache_v, pos,
                                          cross=True)
    positions = jnp.full((1, 1), pos, jnp.int32)
    q, k_new, v_new = attention._project_qkv(cfg, p, x, positions=positions,
                                             kv_positions=positions)
    k, v = _onehot(cache_k, k_new, pos), _onehot(cache_v, v_new, pos)
    B, S, KV, hd = k.shape
    qg = q.reshape(B, 1, KV, cfg.n_heads // KV, hd)
    scale = hd ** -0.5 if cfg.attn_scale is None else cfg.attn_scale
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(COMPUTE_DTYPE),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where((jnp.arange(S) <= pos)[None, None, None, None, :], s,
                  attention.NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", w.astype(COMPUTE_DTYPE),
                   v.astype(COMPUTE_DTYPE), preferred_element_type=jnp.float32)
    o = o.reshape(B, 1, cfg.n_heads * hd).astype(COMPUTE_DTYPE)
    if not write:
        k, v = k_new.astype(cache_k.dtype), v_new.astype(cache_v.dtype)
    return o @ p["wo"].astype(COMPUTE_DTYPE), k, v


def _batch(cfg, B=2):
    b = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0,
                                      cfg.vocab)}
    for fam, key, n in (("vlm", "vision", cfg.n_vision_tokens),
                        ("encdec", "audio", cfg.n_audio_frames)):
        if cfg.family == fam:
            b[key] = jax.random.normal(jax.random.PRNGKey(2),
                                       (B, n, cfg.d_model)).astype(jnp.bfloat16)
    return b


def _decode(cfg, params, cache, toks):
    """STEPS teacher-forced decode steps from ``cache``: (logits, cache)."""
    step = jax.jit(functools.partial(api.decode_step, cfg))
    logits = []
    for i in range(STEPS):
        lg, cache = step(params, cache, toks[:, i:i + 1], jnp.int32(PROMPT + i))
        logits.append(np.asarray(lg, np.float32))
    return np.concatenate(logits, axis=1), cache


def _bits(tree):
    return [np.asarray(a).tobytes() for a in jax.tree.leaves(tree)]


@pytest.mark.parametrize("arch", DECODERS)
def test_row_write_leaves_the_caches_of_the_onehot_write(arch, monkeypatch):
    cfg = SMOKE_CONFIGS[arch]
    params, _ = api.init_params(cfg, jax.random.PRNGKey(0), max_seq=MAX)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, STEPS), 0, cfg.vocab)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with mesh:
        _, cache = jax.jit(functools.partial(api.prefill, cfg, max_seq=MAX))(
            params, _batch(cfg))
        got_logits, got = _decode(cfg, params, cache, toks)
    assert CACHE_WRITE["row"] > 0 and CACHE_WRITE["onehot"] == 0

    for mod in (transformer, encdec, recurrent):
        monkeypatch.setattr(mod, "decode_attention", _onehot_attention)
    for mod in (transformer, encdec):  # the layer scans write after the loop
        monkeypatch.setattr(mod, "write_kv_rows",
                            lambda cfg, cache, rows, pos: _onehot(cache, rows, pos))
    want_logits, want = _decode(cfg, params, cache, toks)

    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert _bits(got) == _bits(want)
    np.testing.assert_allclose(got_logits, want_logits, rtol=LOGIT_TOL,
                               atol=LOGIT_TOL)


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "zamba2-7b"])
def test_serving_on_the_host_mesh_takes_the_row_write(arch):
    """The served models, through ``serve`` on the one-device host mesh."""
    from repro.launch.serve import _step_fns, serve

    _step_fns.cache_clear()  # trace afresh, so the counter sees the write
    serve(arch, 1, 8, 3)
    assert CACHE_WRITE["row"] > 0 and CACHE_WRITE["onehot"] == 0


def _stablehlo_decode(arch):
    from repro.configs import resolve_config
    from repro.launch.serve import _step_fns

    cfg = resolve_config(arch, smoke=True)
    params, _ = api.init_params(cfg, None)
    cache, _ = api.cache_shape(cfg, 1, MAX)
    _, decode = _step_fns(arch, True, MAX, donate=False)
    text = decode.lower(params, cache, jax.ShapeDtypeStruct((1, 1), jnp.int32),
                        jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    return cfg, text


def test_decode_program_writes_rows_and_blends_no_cache():
    """The lowered qwen decode writes ``k`` and ``v`` each with a
    dynamic-update-slice, and no multiply produces a cache-shaped value (the
    one-hot blend did, for every layer)."""
    cfg, text = _stablehlo_decode("qwen1.5-0.5b")
    stacked = f"tensor<{cfg.n_layers}x1x{MAX}x{cfg.n_kv_heads}x{cfg.hd}xbf16>"
    dus = [l for l in text.splitlines()
           if "stablehlo.dynamic_update_slice" in l and l.endswith(stacked)]
    assert len(dus) == 2
    cache_shaped = re.compile(rf"tensor<(\d+x)*{MAX}x{cfg.n_kv_heads}x{cfg.hd}x")
    blends = [l for l in text.splitlines()
              if "stablehlo.multiply" in l and cache_shaped.search(l.split(":")[-1])]
    assert blends == []


_SHARDED = """
import functools, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs import resolve_config
from repro.models import api
from repro.models.attention import CACHE_WRITE
from repro.models.sharding import rules_for, shardings_for_tree

MAX, PROMPT = {MAX}, {PROMPT}
cfg = resolve_config("qwen1.5-0.5b", smoke=True)
params, _ = api.init_params(cfg, jax.random.PRNGKey(0), max_seq=MAX)
toks = jax.random.randint(jax.random.PRNGKey(1), (1, PROMPT), 0, cfg.vocab)
lg, cache = api.prefill(cfg, params, {{"tokens": toks}}, MAX)
tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
pos = jnp.int32(PROMPT)
step = functools.partial(api.decode_step, cfg)
want, _ = jax.jit(step)(params, cache, tok, pos)
assert CACHE_WRITE == {{"row": 2, "onehot": 0}}, CACHE_WRITE
mesh = Mesh(np.array(jax.devices()).reshape(1, 2), ("data", "model"))
_, logical = api.cache_shape(cfg, 1, MAX)
sh = shardings_for_tree(logical, cache, rules_for(cfg.family), mesh)
with mesh:
    got, _ = jax.jit(step)(params, jax.device_put(cache, sh), tok, pos)
print(json.dumps({{"devices": len(jax.devices()), "cache_write": dict(CACHE_WRITE),
                  "kv_spec": str(sh["k"].spec),
                  "gap": float(np.max(np.abs(np.asarray(got, np.float32)
                                             - np.asarray(want, np.float32))))}}))
"""


def test_sequence_sharded_cache_keeps_the_onehot_write():
    """On a 2-device mesh that shards ``kv_seq``, the decode step takes the
    one-hot write and gives the unsharded step's logits."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c",
                          _SHARDED.format(MAX=MAX, PROMPT=PROMPT)],
                         check=True, capture_output=True, text=True, env=env)
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["devices"] == 2
    assert "model" in r["kv_spec"]
    assert r["cache_write"] == {"row": 2, "onehot": 2}
    assert r["gap"] <= LOGIT_TOL
