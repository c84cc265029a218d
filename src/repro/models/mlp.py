"""Feed-forward blocks: gated SwiGLU (llama family), GELU (whisper), and the
fused gated MLP with a low-rank adapter per invocation (Zamba2's shared
blocks)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import COMPUTE_DTYPE, dense_init, zeros_init

__all__ = ["init_swiglu", "swiglu", "init_gelu_mlp", "gelu_mlp",
           "init_gated_mlp", "init_adapter", "gated_mlp"]

ACTIVATIONS = {"silu": jax.nn.silu,
               "gelu": lambda x: jax.nn.gelu(x, approximate=False)}


def init_swiglu(cfg, kg, d_ff=None):
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    p = {
        "w1": dense_init(kg(), (d, ff)),   # gate
        "w3": dense_init(kg(), (d, ff)),   # up
        "w2": dense_init(kg(), (ff, d)),   # down
    }
    logical = {"w1": ("d_in", "feat"), "w3": ("d_in", "feat"), "w2": ("feat", "d_in")}
    return p, logical


def swiglu(p, x):
    g = x @ p["w1"].astype(COMPUTE_DTYPE)
    u = x @ p["w3"].astype(COMPUTE_DTYPE)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(COMPUTE_DTYPE) * u
    return h @ p["w2"].astype(COMPUTE_DTYPE)


def init_gelu_mlp(cfg, kg):
    d, ff = cfg.d_model, cfg.d_ff
    p = {
        "w1": dense_init(kg(), (d, ff)),
        "b1": zeros_init(kg(), (ff,)),
        "w2": dense_init(kg(), (ff, d)),
        "b2": zeros_init(kg(), (d,)),
    }
    logical = {"w1": ("d_in", "feat"), "b1": ("feat",),
               "w2": ("feat", "d_in"), "b2": ("none",)}
    return p, logical


def gelu_mlp(p, x):
    h = x @ p["w1"].astype(COMPUTE_DTYPE) + p["b1"].astype(COMPUTE_DTYPE)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(COMPUTE_DTYPE)
    return h @ p["w2"].astype(COMPUTE_DTYPE) + p["b2"].astype(COMPUTE_DTYPE)


def init_gated_mlp(cfg, kg):
    """One fused gate/up projection [d, 2·ff] (gate first) and the down
    projection."""
    d, ff = cfg.d_model, cfg.d_ff
    p = {"gate_up": dense_init(kg(), (d, 2 * ff)),
         "down": dense_init(kg(), (ff, d))}
    logical = {"gate_up": ("d_in", "feat"), "down": ("feat", "d_in")}
    return p, logical


def init_adapter(cfg, kg):
    """Low-rank d → adapter_rank → 2·ff term added to the gate/up projection."""
    d, r, ff = cfg.d_model, cfg.adapter_rank, cfg.d_ff
    p = {"down": dense_init(kg(), (d, r)), "up": dense_init(kg(), (r, 2 * ff))}
    logical = {"down": ("d_in", "none"), "up": ("none", "feat")}
    return p, logical


def gated_mlp(p, x, act: str, adapter=None):
    """down(act(g) · u) with [g, u] = x·gate_up (+ (x·A_down)·A_up)."""
    gu = x @ p["gate_up"].astype(COMPUTE_DTYPE)
    if adapter is not None:
        gu = gu + (x @ adapter["down"].astype(COMPUTE_DTYPE)) @ \
            adapter["up"].astype(COMPUTE_DTYPE)
    g, u = jnp.split(gu, 2, axis=-1)
    h = ACTIVATIONS[act](g.astype(jnp.float32)).astype(COMPUTE_DTYPE) * u
    return h @ p["down"].astype(COMPUTE_DTYPE)
