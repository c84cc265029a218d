"""Unified model API: one entry point per (init / loss / prefill / decode),
dispatched on ``cfg.family``, plus ``input_specs`` for the dry-run.

All functions are pure and jit-friendly; ``key=None`` gives abstract
(ShapeDtypeStruct) parameters for allocation-free lowering.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from . import encdec, recurrent, transformer
from .common import COMPUTE_DTYPE

__all__ = ["init_params", "loss", "prefill", "decode_step", "cache_shape",
           "cache_bytes", "input_specs", "extra_inputs"]


def init_params(cfg: ModelConfig, key=None, max_seq: int = 4096):
    if cfg.family in ("dense", "moe", "vlm"):
        return transformer.init_lm(cfg, key)
    if cfg.family == "encdec":
        return encdec.init_encdec(cfg, key, max_seq=max_seq)
    if cfg.family == "ssm":
        return recurrent.init_xlstm_lm(cfg, key)
    if cfg.family == "hybrid":
        return recurrent.init_zamba_lm(cfg, key)
    raise ValueError(cfg.family)


def loss(cfg: ModelConfig, params, batch: Dict[str, Any],
         constrain=lambda x: x, remat: bool = True):
    """batch: {tokens, labels, [vision|audio]} → (loss, ce)."""
    if cfg.family in ("dense", "moe"):
        return transformer.lm_loss(cfg, params, batch["tokens"], batch["labels"],
                                   constrain, remat=remat)
    if cfg.family == "vlm":
        return transformer.lm_loss(cfg, params, batch["tokens"], batch["labels"],
                                   constrain, vision=batch["vision"], remat=remat)
    if cfg.family == "encdec":
        return encdec.encdec_loss(cfg, params, batch["tokens"], batch["labels"],
                                  batch["audio"], constrain, remat=remat)
    if cfg.family == "ssm":
        return recurrent.xlstm_loss(cfg, params, batch["tokens"], batch["labels"],
                                    constrain, remat=remat)
    if cfg.family == "hybrid":
        return recurrent.zamba_loss(cfg, params, batch["tokens"], batch["labels"],
                                    constrain, remat=remat)
    raise ValueError(cfg.family)


def prefill(cfg: ModelConfig, params, batch, max_seq: int, constrain=lambda x: x):
    if cfg.family in ("dense", "moe"):
        return transformer.lm_prefill(cfg, params, batch["tokens"], max_seq, constrain)
    if cfg.family == "vlm":
        return transformer.lm_prefill(cfg, params, batch["tokens"], max_seq,
                                      constrain, vision=batch["vision"])
    if cfg.family == "encdec":
        return encdec.encdec_prefill(cfg, params, batch["tokens"], batch["audio"],
                                     max_seq, constrain)
    if cfg.family == "ssm":
        return recurrent.xlstm_prefill(cfg, params, batch["tokens"], max_seq, constrain)
    if cfg.family == "hybrid":
        return recurrent.zamba_prefill(cfg, params, batch["tokens"], max_seq, constrain)
    raise ValueError(cfg.family)


def decode_step(cfg: ModelConfig, params, cache, token, pos, constrain=lambda x: x):
    if cfg.family in ("dense", "moe", "vlm"):
        return transformer.lm_decode_step(cfg, params, cache, token, pos, constrain)
    if cfg.family == "encdec":
        return encdec.encdec_decode_step(cfg, params, cache, token, pos, constrain)
    if cfg.family == "ssm":
        return recurrent.xlstm_decode_step(cfg, params, cache, token, pos, constrain)
    if cfg.family == "hybrid":
        return recurrent.zamba_decode_step(cfg, params, cache, token, pos, constrain)
    raise ValueError(cfg.family)


def cache_shape(cfg: ModelConfig, batch: int, max_seq: int):
    if cfg.family in ("dense", "moe", "vlm"):
        return transformer.lm_cache_shape(cfg, batch, max_seq)
    if cfg.family == "encdec":
        return encdec.encdec_cache_shape(cfg, batch, max_seq)
    if cfg.family == "ssm":
        return recurrent.xlstm_cache_shape(cfg, batch, max_seq)
    if cfg.family == "hybrid":
        return recurrent.zamba_cache_shape(cfg, batch, max_seq)
    raise ValueError(cfg.family)


# Cache leaves that hold attention keys and values; every other leaf is
# fixed-size recurrent state.
KV_LEAVES = ("k", "v", "cross_k", "cross_v")


def cache_bytes(cfg: ModelConfig, batch: int, max_seq: int) -> Dict[str, int]:
    """Bytes of one decode cache by kind: ``kv`` (attention keys and values)
    and ``recurrent`` (SSM, conv and xLSTM state)."""
    cache, _ = cache_shape(cfg, batch, max_seq)
    out = {"recurrent": 0, "kv": 0}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        key = getattr(path[-1], "key", None)
        kind = "kv" if key in KV_LEAVES else "recurrent"
        out[kind] += math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
    return out


# ---------------------------------------------------------------------------
# dry-run input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------


def extra_inputs(cfg: ModelConfig, batch: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """Modality-frontend stubs: precomputed frame/patch embeddings."""
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    if cfg.family == "vlm":
        out["vision"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_vision_tokens, cfg.d_model), COMPUTE_DTYPE)
    if cfg.family == "encdec":
        out["audio"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_audio_frames, cfg.d_model), COMPUTE_DTYPE)
    return out


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Abstract inputs for one (arch × shape) cell.

    train:   {tokens, labels, extra...}            [B, S]
    prefill: {tokens, extra...}                    [B, S]
    decode:  {token [B,1], pos scalar, cache}      (cache from cache_shape)
    """
    B, S = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if shape.kind == "train":
        return {"tokens": tok, "labels": tok, **extra_inputs(cfg, B)}
    if shape.kind == "prefill":
        return {"tokens": tok, **extra_inputs(cfg, B)}
    if shape.kind == "decode":
        cache, _ = cache_shape(cfg, B, S)
        return {
            "token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
            "cache": cache,
        }
    raise ValueError(shape.kind)
