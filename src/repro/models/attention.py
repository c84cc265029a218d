"""Attention: GQA with RoPE, optional QKV-bias / qk-norm, cross-attention,
blockwise (flash-style) softmax, and psum-friendly decode over sequence-sharded
KV caches.

Memory discipline mirrors the Pallas kernel (kernels/flash_attention): the
softmax is computed online over KV blocks inside a ``lax.scan``, so the full
[Sq, Sk] score matrix never materializes — this is what lets prefill_32k and
train_4k compile within HBM on the dry-run meshes. The Pallas kernel is a
drop-in replacement for the inner loop on real TPUs (see kernels/ops.py);
the scan version is the oracle it is tested against.

Sharding (see models/sharding.py):
* train/prefill: activations sequence-sharded over "model" (SP); K/V are
  all-gathered per layer (blockwise, inside the scan) — q stays sharded, so
  score blocks are [B, Sq/model, H, blk] per device.
* decode: KV caches are [B, S, kv, hd] sharded along S over "model"; scores
  and the weighted sum reduce over the sharded axis, which GSPMD lowers to
  all-reduces — this works for any (n_heads, n_kv_heads), unlike head-sharded
  TP (DESIGN.md §4). A decode step writes no cache inside its layer loop:
  each layer attends over its cache with the new row selected in at ``pos``
  and returns the row, and :func:`write_kv_rows` writes every layer's row
  after the loop. It writes with a dynamic-update-slice of the rows, or
  with a one-hot blend (shard-local) where the ambient mesh splits the
  sequence axis.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs.metrics import METRICS
from .common import COMPUTE_DTYPE, apply_rope, dense_init, ones_init, rmsnorm, zeros_init
from .sharding import _current_mesh, rules_for

__all__ = ["init_attention", "attention", "decode_attention", "write_kv_rows",
           "blockwise_attention"]

NEG_INF = -1e30

# Trace-time count of decode-cache writes by the form taken: ``row`` (a
# dynamic-update-slice of the step's rows) or ``onehot`` (a blend over every
# position, kept where the mesh shards the cache's sequence axis).
CACHE_WRITE = METRICS.counter_dict("attention.cache_write", ("row", "onehot"))


def init_attention(cfg, kg, cross: bool = False):
    """q/k/v read ``cfg.attn_in`` features (d_model unless set); o writes
    d_model."""
    d, hd = cfg.d_model, cfg.hd
    d_in = cfg.attn_in or d
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    p: Dict[str, Any] = {
        "wq": dense_init(kg(), (d_in, nq)),
        "wk": dense_init(kg(), (d_in, nkv)),
        "wv": dense_init(kg(), (d_in, nkv)),
        "wo": dense_init(kg(), (nq, d)),
    }
    logical: Dict[str, Any] = {
        "wq": ("d_in", "feat"),
        "wk": ("d_in", "feat"),
        "wv": ("d_in", "feat"),
        "wo": ("feat", "d_in"),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = zeros_init(kg(), (nq,))
        p["bk"] = zeros_init(kg(), (nkv,))
        p["bv"] = zeros_init(kg(), (nkv,))
        logical.update({"bq": ("feat",), "bk": ("feat",), "bv": ("feat",)})
    if cfg.qk_norm and not cross:
        p["q_norm"] = ones_init(kg(), (hd,))
        p["k_norm"] = ones_init(kg(), (hd,))
        logical.update({"q_norm": ("none",), "k_norm": ("none",)})
    return p, logical


def _project_qkv(cfg, p, x, kv_x=None, positions=None, kv_positions=None,
                 rope: bool = True):
    """Returns q [B,Sq,H,hd], k/v [B,Sk,KV,hd] (bf16)."""
    hd = cfg.hd
    xq = x
    xkv = x if kv_x is None else kv_x
    q = xq @ p["wq"].astype(COMPUTE_DTYPE)
    k = xkv @ p["wk"].astype(COMPUTE_DTYPE)
    v = xkv @ p["wv"].astype(COMPUTE_DTYPE)
    if "bq" in p:
        q = q + p["bq"].astype(COMPUTE_DTYPE)
        k = k + p["bk"].astype(COMPUTE_DTYPE)
        v = v + p["bv"].astype(COMPUTE_DTYPE)
    q = q.reshape(*q.shape[:-1], cfg.n_heads, hd)
    k = k.reshape(*k.shape[:-1], cfg.n_kv_heads, hd)
    v = v.reshape(*v.shape[:-1], cfg.n_kv_heads, hd)
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions if kv_positions is not None else positions,
                       cfg.rope_theta)
    return q, k, v


def blockwise_attention(q, k, v, *, causal: bool, q_positions=None,
                        kv_positions=None, block_k: int = 1024, scale=None):
    """Online-softmax attention scanned over KV blocks (the flash pattern).

    q: [B, Sq, H, hd];  k, v: [B, Sk, KV, hd];  H % KV == 0 (GQA).
    Positions are absolute token indices used for causal masking; when None,
    iota is used (pure self-attention over a contiguous block). Scores are
    scaled by ``scale``, hd ** -0.5 unless given.
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    blk = min(block_k, Sk)
    if Sk % blk:
        # cross-attention KV lengths (1601 vision tokens, 1500 audio frames)
        # need not divide the default block — use the largest divisor, unless
        # it is degenerate (1601 is prime → divisor 1 → a 1601-step scan whose
        # backward stacks 107 GB of residuals): then take one whole block.
        d = next(d for d in range(blk, 0, -1) if Sk % d == 0)
        blk = d if d >= block_k // 4 else Sk
    n_blocks = Sk // blk

    if q_positions is None:
        q_positions = jnp.arange(Sq)[None, :]
    if kv_positions is None:
        kv_positions = jnp.arange(Sk)[None, :]

    # layout [B·KV, G, Sq, hd] so both contractions are explicit batched GEMMs
    # (dot_general) — a >2-batch/free-dim einsum tempts XLA:CPU into a
    # broadcast-multiply-reduce that materializes [blk, ..., hd] outer
    # products (observed: a 107 GB f32 temp on llama-vision cross-attention).
    qg = (q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4)
          .reshape(B * KV, G, Sq, hd).astype(COMPUTE_DTYPE))
    kb = k.transpose(0, 2, 1, 3).reshape(B * KV, n_blocks, blk, hd)
    vb = v.transpose(0, 2, 1, 3).reshape(B * KV, n_blocks, blk, hd)
    pb = kv_positions.reshape(kv_positions.shape[0], n_blocks, blk)

    def step(carry, blk_in):
        m, l, acc = carry                    # [B·KV, G, Sq], [..., hd]
        kblk, vblk, pblk = blk_in            # [B·KV, blk, hd], [B|1, blk]
        s = jax.lax.dot_general(
            qg, kblk.astype(COMPUTE_DTYPE),
            (((3,), (2,)), ((0,), (0,))),    # contract hd, batch B·KV
            preferred_element_type=jnp.float32) * scale  # [B·KV, G, Sq, blk]
        if causal:
            mask = q_positions[:, :, None] >= pblk[:, None, :]  # [B|1, Sq, blk]
            if mask.shape[0] != 1:
                mask = jnp.repeat(mask, KV, axis=0)             # [B·KV, Sq, blk]
            s = jnp.where(mask[:, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + pexp.sum(axis=-1)
        pv = jax.lax.dot_general(
            pexp.astype(COMPUTE_DTYPE), vblk.astype(COMPUTE_DTYPE),
            (((3,), (1,)), ((0,), (0,))),    # contract blk, batch B·KV
            preferred_element_type=jnp.float32)          # [B·KV, G, Sq, hd]
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B * KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B * KV, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B * KV, G, Sq, hd), jnp.float32)
    kb_t = jnp.moveaxis(kb, 1, 0)
    vb_t = jnp.moveaxis(vb, 1, 0)
    pb_t = jnp.moveaxis(pb, 1, 0)
    # remat each KV block: the backward otherwise saves the f32 score/pexp
    # blocks for every step — ~15 GB/device on deepseek train_4k (§Perf #1)
    step = jax.checkpoint(step, policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kb_t, vb_t, pb_t))
    out = acc / jnp.maximum(l, 1e-30)[..., None]       # [B·KV, G, Sq, hd]
    out = (out.reshape(B, KV, G, Sq, hd).transpose(0, 3, 1, 2, 4)
           .reshape(B, Sq, H, hd))
    return out.astype(COMPUTE_DTYPE)


def attention(cfg, p, x, *, positions, causal: bool = True, kv_x=None,
              kv_positions=None, rope: bool = True, block_k: int = 1024,
              attn_impl=None, constrain=lambda x: x):
    """Full (train/prefill) attention. Returns (output [B,S,d], (k, v))."""
    q, k, v = _project_qkv(cfg, p, x, kv_x=kv_x, positions=positions,
                           kv_positions=kv_positions, rope=rope)
    # re-anchor the sharding after the feature-sharded projections: q stays
    # sequence-sharded; k/v likewise until the blockwise scan gathers them
    # per block (without this, SPMD may materialize full-sequence f32 score
    # tensors — observed 122 GB/device on llama-vision train_4k)
    q = constrain(q)
    if kv_x is None:
        k = constrain(k)
        v = constrain(v)
    impl = attn_impl or blockwise_attention
    o = impl(q, k, v, causal=causal, q_positions=positions,
             kv_positions=kv_positions, block_k=block_k, scale=cfg.attn_scale)
    o = o.reshape(*o.shape[:-2], cfg.n_heads * cfg.hd)
    return o @ p["wo"].astype(COMPUTE_DTYPE), (k, v)


def _kv_seq_shards(cfg) -> int:
    """Devices the ambient mesh splits a decode cache's sequence axis over:
    the product of the mesh's sizes along the axes ``kv_seq`` maps to."""
    mesh = _current_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    return math.prod(sizes.get(a, 1) for a in rules_for(cfg.family)["kv_seq"])


def write_kv_rows(cfg, cache, rows, pos):
    """cache [..., S, KV, hd] ← rows [..., 1, KV, hd] (the cache's dtype) at
    sequence index ``pos``.

    On an unsharded sequence axis, one dynamic-update-slice writes the rows.
    Where the mesh shards that axis, a dynamic-update-slice would gather it,
    so a one-hot blend updates every shard's local slice instead. Both leave
    the same bits: the rows at ``pos``, every other position as it was.
    """
    if _kv_seq_shards(cfg) == 1:
        CACHE_WRITE["row"] += 1
        start = [0] * cache.ndim
        start[-3] = pos
        return jax.lax.dynamic_update_slice(cache, rows, start)
    CACHE_WRITE["onehot"] += 1
    oh = (jnp.arange(cache.shape[-3]) == pos).astype(cache.dtype)[:, None, None]
    return cache * (1 - oh) + oh * rows


def decode_attention(cfg, p, x, cache_k, cache_v, pos, *, cross: bool = False,
                     write: bool = False):
    """Single-token attention against a (sequence-sharded) cache.

    x: [B, 1, d]; cache_k/v: [B, S, KV, hd]; pos: scalar current position.
    Returns (out [B, 1, d], k, v), where k and v depend on who calls:

    * a layer scan (``write=False``): the caches are only read, with
      position ``pos`` replaced by the new row as the dots read it, and k, v
      are the step's rows [B, 1, KV, hd], which the caller writes after the
      loop with :func:`write_kv_rows`. Nothing cache-sized is written
      inside the loop.
    * a caller outside a loop (``write=True``): k, v are the caches with the
      rows written at ``pos``, and the dots read those, so that one pass can
      read each cache, feed the dots and write its copy.

    Cross-attention caches are fixed at prefill: k and v are ``None``.
    """
    positions = jnp.full((1, 1), pos, jnp.int32)
    if cross:
        # cross-attention caches are precomputed at prefill; no update, no rope
        q, _, _ = _project_qkv(cfg, p, x, kv_x=jnp.zeros_like(x), rope=False,
                               positions=positions)
        k, v = cache_k, cache_v
        k_out = v_out = mask = None
    else:
        q, k_out, v_out = _project_qkv(cfg, p, x, positions=positions,
                                       kv_positions=positions)
        k_out = k_out.astype(cache_k.dtype)
        v_out = v_out.astype(cache_v.dtype)
        if write:
            k = k_out = write_kv_rows(cfg, cache_k, k_out, pos)
            v = v_out = write_kv_rows(cfg, cache_v, v_out, pos)
        else:
            at_pos = (jnp.arange(cache_k.shape[1]) == pos)[None, :, None, None]
            k = jnp.where(at_pos, k_out, cache_k)
            v = jnp.where(at_pos, v_out, cache_v)
        mask = (jnp.arange(k.shape[1]) <= pos)[None, None, None, :]  # [1,1,1,S]

    B, S, KV, hd = k.shape
    H = cfg.n_heads
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd)
    scale = hd ** -0.5 if cfg.attn_scale is None else cfg.attn_scale
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(COMPUTE_DTYPE),
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[:, :, None, :, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)  # reduction over sharded S → psum via SPMD
    o = jnp.einsum("bkgqs,bskh->bqkgh", w.astype(COMPUTE_DTYPE),
                   v.astype(COMPUTE_DTYPE), preferred_element_type=jnp.float32)
    o = o.reshape(B, 1, H * hd).astype(COMPUTE_DTYPE)
    return o @ p["wo"].astype(COMPUTE_DTYPE), k_out, v_out
