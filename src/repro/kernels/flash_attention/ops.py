"""Jit'd public wrapper: model-layout flash attention.

Accepts the model's [B, S, H, hd] / [B, S, KV, hd] layout (the signature of
``repro.models.attention.blockwise_attention``), regroups GQA heads, and
dispatches to the Pallas kernel — ``interpret=True`` on CPU (validation),
compiled on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_bkv
from .ref import attention_ref


def _is_cpu() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, *, causal: bool = True, q_positions=None,
                    kv_positions=None, block_k: int = 128,
                    interpret: bool | None = None, scale=None):
    """q: [B, Sq, H, hd]; k, v: [B, Sk, KV, hd] → [B, Sq, H, hd].

    Drop-in for ``blockwise_attention`` (positions args accepted for
    signature compatibility; the kernel assumes contiguous positions from 0,
    which is what train/prefill use). The kernel scales scores by
    hd ** -0.5; another ``scale`` is folded into q.
    """
    B, Sq, H, hd = q.shape
    if scale is not None:
        q = q * (scale * hd ** 0.5)
    _, Sk, KV, _ = k.shape
    G = H // KV
    if interpret is None:
        interpret = _is_cpu()

    qg = q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 1, 3, 4).reshape(B * KV, Sq, G, hd)
    kg = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, hd)
    o = flash_attention_bkv(qg, kg, vg, causal=causal, blk_k=block_k,
                            interpret=interpret)
    o = o.reshape(B, KV, Sq, G, hd).transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, hd)
    return o


def flash_attention_reference(q, k, v, *, causal: bool = True, **_):
    """Oracle in model layout (tests)."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 1, 3, 4).reshape(B * KV, Sq, G, hd)
    kg = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, hd)
    o = attention_ref(qg, kg, vg, causal=causal)
    return o.reshape(B, KV, Sq, G, hd).transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, hd)
