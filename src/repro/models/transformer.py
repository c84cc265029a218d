"""Decoder-only LM assembly (dense / MoE / VLM families).

Layers are stacked along a leading axis and executed with ``jax.lax.scan`` so
the HLO stays O(1) in depth (essential for 62-layer dry-runs) and FSDP
all-gathers happen per layer inside the loop (overlapping with the previous
layer's compute under XLA's latency-hiding scheduler). VLM groups
(cross_attn_every − 1 self layers + 1 cross-attention layer) scan over groups
with an inner scan over the self layers.

Remat: the scanned body is wrapped in ``jax.checkpoint`` — the scan carry
(one [B, S/SP, d] activation per layer boundary) is all that survives the
forward pass, the paper-faithful "store only what later bursts read" policy.
The remat segmentation itself is chosen by the Julienning partitioner in
``repro.core.remat_policy`` (see §Perf).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import attention, decode_attention, init_attention, write_kv_rows
from .common import COMPUTE_DTYPE, KeyGen, dense_init, ones_init, rmsnorm, softmax_cross_entropy
from .mlp import init_swiglu, swiglu
from .moe import init_moe, moe_block

__all__ = ["init_lm", "lm_forward", "lm_loss", "lm_prefill", "lm_decode_step",
           "lm_cache_shape", "stack_init"]


def stack_init(n: int, init_one, key):
    """Stack ``n`` copies of ``init_one(kg) -> (tree, logical)`` along axis 0.

    ``key=None`` → abstract (ShapeDtypeStruct) tree, no allocation.
    """
    def one(k):
        tree, _ = init_one(KeyGen(k))
        return tree

    _, logical = init_one(_probe())
    if key is None:
        tree = jax.eval_shape(one, jax.random.PRNGKey(0))
        tree = jax.tree.map(lambda l: jax.ShapeDtypeStruct((n, *l.shape), l.dtype), tree)
    else:
        tree = jax.vmap(one)(jax.random.split(key, n))
    logical = jax.tree.map(lambda ax: ("layers", *ax), logical,
                           is_leaf=lambda x: isinstance(x, tuple))
    return tree, logical


class _probe:
    """KeyGen stand-in used only to extract the logical tree."""

    def __call__(self):
        return None


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(cfg, kg):
    attn_p, attn_l = init_attention(cfg, kg)
    p = {"attn": attn_p, "ln1": ones_init(kg(), (cfg.d_model,)),
         "ln2": ones_init(kg(), (cfg.d_model,))}
    l = {"attn": attn_l, "ln1": ("none",), "ln2": ("none",)}
    if cfg.family == "moe":
        p["moe"], l["moe"] = init_moe(cfg, kg)
    else:
        p["mlp"], l["mlp"] = init_swiglu(cfg, kg)
    return p, l


def _init_cross_layer(cfg, kg):
    attn_p, attn_l = init_attention(cfg, kg, cross=True)
    gate = dense_init(kg(), (1,), scale=0.0)  # llama-vision: zero-init attn gate
    p = {"attn": attn_p, "ln": ones_init(kg(), (cfg.d_model,)), "gate": gate}
    l = {"attn": attn_l, "ln": ("none",), "gate": ("none",)}
    return p, l


def init_lm(cfg, key=None):
    """Returns (params, logical). ``key=None`` → abstract params (dry-run)."""
    kg = KeyGen(key) if key is not None else _probe()
    params: Dict[str, Any] = {
        "embed": dense_init(kg() if key is not None else None, (cfg.vocab, cfg.d_model)),
        "final_norm": ones_init(kg() if key is not None else None, (cfg.d_model,)),
    }
    logical: Dict[str, Any] = {"embed": ("vocab", "d_in"), "final_norm": ("none",)}
    if not cfg.tie_embeddings:
        params["head"] = dense_init(kg() if key is not None else None,
                                    (cfg.d_model, cfg.vocab))
        logical["head"] = ("d_in", "vocab")

    lkey = None if key is None else kg()
    if cfg.family == "vlm":
        per = cfg.cross_attn_every
        n_groups = cfg.n_layers // per

        # groups: [n_groups, per-1, ...] self layers + [n_groups, ...] cross
        def init_pair(kg2):
            sp, sl = stack_init(per - 1, lambda kg3: _init_layer(cfg, kg3),
                                kg2() if not isinstance(kg2, _probe) else None)
            cp, cl = _init_cross_layer(cfg, kg2)
            return {"self": sp, "cross": cp}, {"self": sl, "cross": cl}
        params["groups"], logical["groups"] = stack_init(n_groups, init_pair, lkey)
    else:
        params["layers"], logical["layers"] = stack_init(
            cfg.n_layers, lambda kg2: _init_layer(cfg, kg2), lkey)
    return params, logical


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _embed(cfg, params, tokens):
    e = jnp.take(params["embed"].astype(COMPUTE_DTYPE), tokens, axis=0)
    return e


def _head(cfg, params, x):
    w = (params["embed"].T if cfg.tie_embeddings else params["head"])
    return x @ w.astype(COMPUTE_DTYPE)


def _layer_apply(cfg, lp, x, positions, constrain, attn_impl=None):
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    a, kv = attention(cfg, lp["attn"], h, positions=positions,
                      attn_impl=attn_impl, constrain=constrain)
    x = constrain(x + a)
    h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        m, aux = moe_block(cfg, lp["moe"], h)
    else:
        m, aux = swiglu(lp["mlp"], h), jnp.zeros((), jnp.float32)
    x = constrain(x + m)
    return x, aux, kv


def _cross_apply(cfg, cp, x, vision, constrain):
    h = rmsnorm(x, cp["ln"], cfg.norm_eps)
    a, kv = attention(cfg, cp["attn"], h, positions=jnp.arange(x.shape[1])[None],
                      causal=False, kv_x=vision,
                      kv_positions=jnp.arange(vision.shape[1])[None], rope=False,
                      constrain=constrain)
    gate = jnp.tanh(cp["gate"].astype(jnp.float32)).astype(COMPUTE_DTYPE)
    x = constrain(x + gate * a)
    return x, kv


def lm_forward(cfg, params, tokens, constrain=lambda x: x, vision=None,
               remat: bool = True, attn_impl=None, collect_cache: bool = False):
    """tokens [B,S] → logits [B,S,V]. Optionally collects the KV cache."""
    B, S = tokens.shape
    positions = jnp.arange(S)[None, :]
    x = constrain(_embed(cfg, params, tokens))
    aux_total = jnp.zeros((), jnp.float32)

    def body(x, lp):
        x, aux, kv = _layer_apply(cfg, lp, x, positions, constrain, attn_impl)
        return x, (aux, kv if collect_cache else None)

    body_fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) \
        if remat else body

    caches = None
    if cfg.family == "vlm":
        assert vision is not None

        def cross_fn(x, cp):
            return _cross_apply(cfg, cp, x, vision, constrain)

        if remat:
            cross_fn = jax.checkpoint(
                cross_fn, policy=jax.checkpoint_policies.nothing_saveable)

        def group_body(x, gp):
            x, (aux, kvs) = jax.lax.scan(body_fn, x, gp["self"])
            x, ckv = cross_fn(x, gp["cross"])
            return x, (aux, (kvs, ckv) if collect_cache else None)

        x, (auxs, caches) = jax.lax.scan(group_body, x, params["groups"])
        aux_total = auxs.sum()
    else:
        x, (auxs, caches) = jax.lax.scan(body_fn, x, params["layers"])
        aux_total = auxs.sum()

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _head(cfg, params, x)
    return logits, aux_total, caches


def lm_loss(cfg, params, tokens, labels, constrain=lambda x: x, vision=None,
            remat: bool = True, attn_impl=None):
    logits, aux, _ = lm_forward(cfg, params, tokens, constrain, vision,
                                remat=remat, attn_impl=attn_impl)
    ce = softmax_cross_entropy(logits, labels)
    return ce + (0.01 * aux if cfg.family == "moe" else 0.0), ce


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------


def lm_cache_shape(cfg, batch: int, max_seq: int):
    """Abstract KV-cache tree + logical axes (sequence-sharded)."""
    hd, KV = cfg.hd, cfg.n_kv_heads
    kv = jax.ShapeDtypeStruct((cfg.n_layers if cfg.family != "vlm"
                               else cfg.n_layers - cfg.n_layers // cfg.cross_attn_every,
                               batch, max_seq, KV, hd), COMPUTE_DTYPE)
    tree = {"k": kv, "v": kv}
    logical = {"k": ("layers", "batch", "kv_seq", "none", "none"),
               "v": ("layers", "batch", "kv_seq", "none", "none")}
    if cfg.family == "vlm":
        n_groups = cfg.n_layers // cfg.cross_attn_every
        cross = jax.ShapeDtypeStruct((n_groups, batch, cfg.n_vision_tokens, KV, hd),
                                     COMPUTE_DTYPE)
        tree.update({"cross_k": cross, "cross_v": cross})
        logical.update({"cross_k": ("layers", "batch", "none", "none", "none"),
                        "cross_v": ("layers", "batch", "none", "none", "none")})
    return tree, logical


def lm_prefill(cfg, params, tokens, max_seq: int, constrain=lambda x: x,
               vision=None, attn_impl=None):
    """Prefill: forward pass that also materializes the padded KV cache."""
    B, S = tokens.shape
    logits, _, caches = lm_forward(cfg, params, tokens, constrain, vision,
                                   remat=False, attn_impl=attn_impl,
                                   collect_cache=True)

    def pad(kv):  # [L?, B, S, KV, hd] → padded to max_seq along S
        pad_width = [(0, 0)] * kv.ndim
        pad_width[2] = (0, max_seq - kv.shape[2])
        return jnp.pad(kv, pad_width)

    if cfg.family == "vlm":
        kvs, ckv = caches
        k, v = kvs  # [n_groups, per-1, B, S, KV, hd] — merge group dims
        k = k.reshape(-1, *k.shape[2:])
        v = v.reshape(-1, *v.shape[2:])
        ck, cv = ckv
        cache = {"k": pad(_to_cache_layout(k)), "v": pad(_to_cache_layout(v)),
                 "cross_k": _to_cache_layout(ck), "cross_v": _to_cache_layout(cv)}
    else:
        k, v = caches
        cache = {"k": pad(_to_cache_layout(k)), "v": pad(_to_cache_layout(v))}
    return logits[:, -1:, :], cache


def _to_cache_layout(kv):
    # attention() returns k/v as [..., B, S, KV, hd] already
    return kv.astype(COMPUTE_DTYPE)


def lm_decode_step(cfg, params, cache, token, pos, constrain=lambda x: x):
    """token [B,1] int32, pos scalar int32 → (logits [B,1,V], cache).

    The layer scan reads each layer's cache slice and emits only its new
    K/V rows; one write per stacked cache puts all of them in at ``pos``.
    """
    x = constrain(_embed(cfg, params, token))

    def body(x, lin):  # one self-attention layer
        lp, ck_, cv_ = lin
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        a, k_row, v_row = decode_attention(cfg, lp["attn"], h, ck_, cv_, pos)
        x = constrain(x + a)
        h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            m, _ = moe_block(cfg, lp["moe"], h)
        else:
            m = swiglu(lp["mlp"], h)
        return constrain(x + m), (k_row, v_row)

    if cfg.family == "vlm":
        per = cfg.cross_attn_every
        n_groups = cfg.n_layers // per
        k = cache["k"].reshape(n_groups, per - 1, *cache["k"].shape[1:])
        v = cache["v"].reshape(n_groups, per - 1, *cache["v"].shape[1:])

        def group_body(x, gin):
            gp, gk, gv, gck, gcv = gin
            x, rows = jax.lax.scan(body, x, (gp["self"], gk, gv))
            h = rmsnorm(x, gp["cross"]["ln"], cfg.norm_eps)
            a, _, _ = decode_attention(cfg, gp["cross"]["attn"], h, gck, gcv,
                                       pos, cross=True)
            gate = jnp.tanh(gp["cross"]["gate"].astype(jnp.float32)).astype(COMPUTE_DTYPE)
            x = constrain(x + gate * a)
            return x, rows

        x, (k_rows, v_rows) = jax.lax.scan(group_body, x,
                                           (params["groups"], k, v,
                                            cache["cross_k"], cache["cross_v"]))
        k_rows = k_rows.reshape(-1, *k_rows.shape[2:])
        v_rows = v_rows.reshape(-1, *v_rows.shape[2:])
    else:
        x, (k_rows, v_rows) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                                     cache["v"]))
    cache = dict(cache, k=write_kv_rows(cfg, cache["k"], k_rows, pos),
                 v=write_kv_rows(cfg, cache["v"], v_rows, pos))

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _head(cfg, params, x), cache
