"""Whisper-style encoder-decoder backbone.

The conv frontend is a stub per the assignment: the encoder consumes
precomputed frame embeddings [B, n_audio_frames, d_model] from
``input_specs()``. LayerNorm + GELU + learned positions (no RoPE), causal
decoder self-attention, cross-attention to the encoder output.

The paper-technique tie-in (DESIGN.md §5): the encoder output is a packet
whose last use is the *final* decoder layer — julienne keeps it resident
across decoder bursts exactly like the head-count image across CNN windows.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .attention import attention, decode_attention, init_attention, write_kv_rows
from .common import COMPUTE_DTYPE, KeyGen, dense_init, layernorm, ones_init, zeros_init
from .mlp import gelu_mlp, init_gelu_mlp
from .transformer import _probe, stack_init

__all__ = ["init_encdec", "encdec_forward", "encdec_loss", "encdec_prefill",
           "encdec_decode_step", "encdec_cache_shape"]


def _init_ln(kg, d):
    return {"w": ones_init(kg(), (d,)), "b": zeros_init(kg(), (d,))}, \
        {"w": ("none",), "b": ("none",)}


def _init_enc_layer(cfg, kg):
    attn_p, attn_l = init_attention(cfg, kg)
    mlp_p, mlp_l = init_gelu_mlp(cfg, kg)
    ln1, ln1_l = _init_ln(kg, cfg.d_model)
    ln2, ln2_l = _init_ln(kg, cfg.d_model)
    return ({"attn": attn_p, "mlp": mlp_p, "ln1": ln1, "ln2": ln2},
            {"attn": attn_l, "mlp": mlp_l, "ln1": ln1_l, "ln2": ln2_l})


def _init_dec_layer(cfg, kg):
    self_p, self_l = init_attention(cfg, kg)
    cross_p, cross_l = init_attention(cfg, kg, cross=True)
    mlp_p, mlp_l = init_gelu_mlp(cfg, kg)
    ln1, ln1_l = _init_ln(kg, cfg.d_model)
    lnc, lnc_l = _init_ln(kg, cfg.d_model)
    ln2, ln2_l = _init_ln(kg, cfg.d_model)
    return ({"self": self_p, "cross": cross_p, "mlp": mlp_p,
             "ln1": ln1, "lnc": lnc, "ln2": ln2},
            {"self": self_l, "cross": cross_l, "mlp": mlp_l,
             "ln1": ln1_l, "lnc": lnc_l, "ln2": ln2_l})


def init_encdec(cfg, key=None, max_seq: int = 4096):
    kg = KeyGen(key) if key is not None else _probe()
    p: Dict[str, Any] = {
        "embed": dense_init(kg() if key is not None else None, (cfg.vocab, cfg.d_model)),
        "pos_enc": dense_init(kg() if key is not None else None,
                              (cfg.n_audio_frames, cfg.d_model)),
        "pos_dec": dense_init(kg() if key is not None else None,
                              (max_seq, cfg.d_model)),
        "head": dense_init(kg() if key is not None else None,
                           (cfg.d_model, cfg.vocab)),
    }
    l: Dict[str, Any] = {
        "embed": ("vocab", "d_in"), "pos_enc": ("none", "d_in"),
        "pos_dec": ("none", "d_in"), "head": ("d_in", "vocab"),
    }
    lkey = None if key is None else kg()
    p["enc"], l["enc"] = stack_init(cfg.n_encoder_layers,
                                    lambda kg2: _init_enc_layer(cfg, kg2), lkey)
    lkey2 = None if key is None else kg()
    p["dec"], l["dec"] = stack_init(cfg.n_layers,
                                    lambda kg2: _init_dec_layer(cfg, kg2), lkey2)
    enc_ln, enc_ln_l = _init_ln(kg if key is not None else _probe(), cfg.d_model)
    dec_ln, dec_ln_l = _init_ln(kg if key is not None else _probe(), cfg.d_model)
    p["enc_ln"], l["enc_ln"] = enc_ln, enc_ln_l
    p["dec_ln"], l["dec_ln"] = dec_ln, dec_ln_l
    return p, l


def _ln(x, lnp, eps):
    return layernorm(x, lnp["w"], lnp["b"], eps)


def encode(cfg, params, audio_embed, constrain=lambda x: x, remat=True):
    """audio_embed [B, F, d] → encoder output [B, F, d]."""
    F = audio_embed.shape[1]
    x = constrain(audio_embed.astype(COMPUTE_DTYPE)
                  + params["pos_enc"][:F].astype(COMPUTE_DTYPE))
    pos = jnp.arange(F)[None, :]

    def body(x, lp):
        h = _ln(x, lp["ln1"], cfg.norm_eps)
        a, _ = attention(cfg, lp["attn"], h, positions=pos, causal=False,
                         rope=False, constrain=constrain)
        x = constrain(x + a)
        h = _ln(x, lp["ln2"], cfg.norm_eps)
        x = constrain(x + gelu_mlp(lp["mlp"], h))
        return x, None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["enc"])
    return _ln(x, params["enc_ln"], cfg.norm_eps)


def encdec_forward(cfg, params, tokens, audio_embed, constrain=lambda x: x,
                   remat: bool = True, collect_cache: bool = False):
    enc_out = encode(cfg, params, audio_embed, constrain, remat)
    B, S = tokens.shape
    pos = jnp.arange(S)[None, :]
    fpos = jnp.arange(enc_out.shape[1])[None, :]
    x = jnp.take(params["embed"].astype(COMPUTE_DTYPE), tokens, axis=0)
    x = constrain(x + params["pos_dec"][:S].astype(COMPUTE_DTYPE))

    def body(x, lp):
        h = _ln(x, lp["ln1"], cfg.norm_eps)
        a, skv = attention(cfg, lp["self"], h, positions=pos, constrain=constrain)
        x = constrain(x + a)
        h = _ln(x, lp["lnc"], cfg.norm_eps)
        a, ckv = attention(cfg, lp["cross"], h, positions=pos, causal=False,
                           kv_x=enc_out, kv_positions=fpos, rope=False,
                           constrain=constrain)
        x = constrain(x + a)
        h = _ln(x, lp["ln2"], cfg.norm_eps)
        x = constrain(x + gelu_mlp(lp["mlp"], h))
        return x, ((skv, ckv) if collect_cache else None)

    body_fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) \
        if remat and not collect_cache else body
    x, caches = jax.lax.scan(body_fn, x, params["dec"])
    x = _ln(x, params["dec_ln"], cfg.norm_eps)
    logits = x @ params["head"].astype(COMPUTE_DTYPE)
    return logits, caches


def encdec_loss(cfg, params, tokens, labels, audio_embed, constrain=lambda x: x,
                remat: bool = True):
    from .common import softmax_cross_entropy

    logits, _ = encdec_forward(cfg, params, tokens, audio_embed, constrain, remat)
    ce = softmax_cross_entropy(logits, labels)
    return ce, ce


def encdec_cache_shape(cfg, batch: int, max_seq: int):
    hd, KV = cfg.hd, cfg.n_kv_heads
    self_kv = jax.ShapeDtypeStruct((cfg.n_layers, batch, max_seq, KV, hd),
                                   COMPUTE_DTYPE)
    cross_kv = jax.ShapeDtypeStruct((cfg.n_layers, batch, cfg.n_audio_frames, KV, hd),
                                    COMPUTE_DTYPE)
    tree = {"k": self_kv, "v": self_kv, "cross_k": cross_kv, "cross_v": cross_kv}
    logical = {"k": ("layers", "batch", "kv_seq", "none", "none"),
               "v": ("layers", "batch", "kv_seq", "none", "none"),
               "cross_k": ("layers", "batch", "none", "none", "none"),
               "cross_v": ("layers", "batch", "none", "none", "none")}
    return tree, logical


def encdec_prefill(cfg, params, tokens, audio_embed, max_seq: int,
                   constrain=lambda x: x):
    logits, caches = encdec_forward(cfg, params, tokens, audio_embed, constrain,
                                    remat=False, collect_cache=True)
    (sk, sv), (ck, cv) = caches

    def pad(kv):
        w = [(0, 0)] * kv.ndim
        w[2] = (0, max_seq - kv.shape[2])
        return jnp.pad(kv, w)

    cache = {"k": pad(sk), "v": pad(sv), "cross_k": ck, "cross_v": cv}
    return logits[:, -1:, :], cache


def encdec_decode_step(cfg, params, cache, token, pos, constrain=lambda x: x):
    x = jnp.take(params["embed"].astype(COMPUTE_DTYPE), token, axis=0)
    x = constrain(x + jnp.take(params["pos_dec"], pos, axis=0).astype(COMPUTE_DTYPE))

    def body(x, lin):
        lp, k_, v_, ck_, cv_ = lin
        h = _ln(x, lp["ln1"], cfg.norm_eps)
        a, k_row, v_row = decode_attention(cfg, lp["self"], h, k_, v_, pos)
        x = constrain(x + a)
        h = _ln(x, lp["lnc"], cfg.norm_eps)
        a, _, _ = decode_attention(cfg, lp["cross"], h, ck_, cv_, pos, cross=True)
        x = constrain(x + a)
        h = _ln(x, lp["ln2"], cfg.norm_eps)
        x = constrain(x + gelu_mlp(lp["mlp"], h))
        return x, (k_row, v_row)

    x, (k_rows, v_rows) = jax.lax.scan(body, x,
                                       (params["dec"], cache["k"], cache["v"],
                                        cache["cross_k"], cache["cross_v"]))
    x = _ln(x, params["dec_ln"], cfg.norm_eps)
    cache = dict(cache, k=write_kv_rows(cfg, cache["k"], k_rows, pos),
                 v=write_kv_rows(cfg, cache["v"], v_rows, pos))
    return x @ params["head"].astype(COMPUTE_DTYPE), cache
