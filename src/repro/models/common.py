"""Shared model components: norms, RoPE, initializers, losses, dtype policy.

Numerics policy (mixed precision, MaxText-style): parameters and optimizer
state in fp32; activations and matmuls in bf16; softmax statistics, norms and
the loss in fp32.

Every read of a matmul weight is ``w.astype(COMPUTE_DTYPE)``. Serving reads
the weights without updating them, so it runs on :func:`compute_copy`: the
leaves named in ``COMPUTE_COPY_NAMES`` cast to bf16 once, where a step would
cast them again on every call. The steps see the same bf16 operands either
way. Every other leaf (norm gains, SSM decay and skip terms, gates) is read
in fp32 and stays the fp32 original.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32

__all__ = [
    "COMPUTE_DTYPE",
    "PARAM_DTYPE",
    "COMPUTE_COPY_NAMES",
    "compute_copy",
    "KeyGen",
    "dense_init",
    "rmsnorm",
    "layernorm",
    "apply_rope",
    "softmax_cross_entropy",
    "Abstract",
]


# Leaf names that every model family reads only as ``.astype(COMPUTE_DTYPE)``
# (matmul weights, the biases added to their bf16 outputs, conv taps,
# embedding and position tables); tests/test_serve_compute_copy.py holds each
# family to it.
COMPUTE_COPY_NAMES = frozenset({
    "wq", "wk", "wv", "wo", "bq", "bk", "bv",            # attention
    "w1", "w2", "w3", "b1", "b2", "gate_up", "down", "up",  # MLPs, adapters
    "router",                                             # MoE
    "in_proj", "out_proj", "conv_w", "linear",            # Mamba2, Zamba2
    "wi", "wf", "wo_gate", "w_in", "ff_w1", "ff_w2", "ff_w3",  # xLSTM
    "embed", "head", "pos_enc", "pos_dec",
})


def compute_copy(params):
    """``params`` with each leaf named in ``COMPUTE_COPY_NAMES`` (its key in
    the innermost dict) cast to ``COMPUTE_DTYPE``; every other leaf is the
    same object as in ``params``. Steps run on the copy give the same
    numbers as on ``params``."""
    def cast(path, leaf):
        key = path[-1] if path else None
        if (isinstance(key, jax.tree_util.DictKey)
                and key.key in COMPUTE_COPY_NAMES):
            return leaf.astype(COMPUTE_DTYPE)
        return leaf

    return jax.tree_util.tree_map_with_path(cast, params)


class KeyGen:
    """Split-on-demand PRNG key source (init-time only)."""

    def __init__(self, key: jax.Array):
        self._key = key

    def __call__(self) -> jax.Array:
        self._key, k = jax.random.split(self._key)
        return k


class Abstract:
    """Stand-in KeyGen that makes init functions produce ShapeDtypeStructs.

    Used by the dry-run: ``jax.eval_shape(init)`` never allocates, but we
    also want a *direct* abstract path so huge configs can be described
    without tracing the initializers at all.
    """

    def __call__(self):
        return None


def dense_init(key, shape: Tuple[int, ...], scale: float = 0.02, dtype=PARAM_DTYPE):
    if key is None:  # abstract init (dry-run)
        return jax.ShapeDtypeStruct(shape, dtype)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


def zeros_init(key, shape: Tuple[int, ...], dtype=PARAM_DTYPE):
    if key is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jnp.zeros(shape, dtype)


def ones_init(key, shape: Tuple[int, ...], dtype=PARAM_DTYPE):
    if key is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jnp.ones(shape, dtype)


def rmsnorm(x, w, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(COMPUTE_DTYPE)


def layernorm(x, w, b, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(COMPUTE_DTYPE)


def _rope_angles(positions, head_dim: int, theta: float):
    # positions: [...]; returns sin/cos [..., head_dim/2]
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x, positions, theta: float = 10000.0):
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    hd = x.shape[-1]
    sin, cos = _rope_angles(positions, hd, theta)  # [..., seq, hd/2]
    sin = sin[..., None, :]  # broadcast over heads
    cos = cos[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def softmax_cross_entropy(logits, labels):
    """Mean token cross-entropy; logits [..., V] any dtype, stats in fp32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)
