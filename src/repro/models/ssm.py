"""Mamba2 (SSD) mixer — chunked selective-state-space compute (zamba2).

    z, xBC, dt = split(W_in h)                      widths d_in, d_in + 2·G·N, H
    xBC = silu(conv4(xBC) + b);  x, B, C = split(xBC)     B, C: G groups of N
    Δ = softplus(dt + dt_bias);  s_t = exp(Δ·A) s_{t-1} + Δ B_t ⊗ x_t
    y = C_t · s_t + D x_t;       out = W_out(rmsgated(y, z))

Head h reads group ⌊h / (H/G)⌋ of B and C, and ``rmsgated`` normalizes
y · silu(z) over G groups of d_in/G features before its gain.

Prefill uses the chunkwise SSD form: within a chunk (``cfg.ssm_chunk``) the
recurrence is evaluated as a masked quadratic form; across chunks the state
[B, H, P, N] is carried by a ``lax.scan``. A length that is not a multiple
of the chunk is padded at the end with positions that carry no input and no
decay, so the final state is the state after the last real position. Decode
is the single-step recurrence. Both paths share the same discretization, so
decode extends prefill consistently (tested against the sequential
recurrence in tests/test_zamba2.py).

TPU adaptation notes (DESIGN.md §2): heads shard over "model"
(H = expand·d/headdim is a multiple of 16 for zamba2-7b: 112), sequence stays
local to a device (the inter-chunk recurrence is sequential), batch shards
over ("pod","data").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import COMPUTE_DTYPE, dense_init, ones_init, zeros_init

__all__ = ["init_mamba", "mamba_chunked", "mamba_decode_step", "mamba_init_state"]

CONV_K = 4  # causal depthwise conv window
F32 = jnp.float32


def _dims(cfg):
    d_in = cfg.ssm_expand * cfg.d_model
    P = cfg.ssm_headdim
    H = d_in // P
    return d_in, H, P, cfg.ssm_state, cfg.ssm_ngroups


def _conv_width(cfg):
    d_in, _, _, N, G = _dims(cfg)
    return d_in + 2 * G * N


def init_mamba(cfg, kg):
    d = cfg.d_model
    d_in, H, P, N, G = _dims(cfg)
    F = _conv_width(cfg)
    p = {
        "in_proj": dense_init(kg(), (d, d_in + F + H)),  # z, xBC, dt
        "conv_w": dense_init(kg(), (CONV_K, F), scale=0.5),
        "conv_b": zeros_init(kg(), (F,)),
        "A_log": zeros_init(kg(), (H,)),
        "dt_bias": zeros_init(kg(), (H,)),
        "D": ones_init(kg(), (H,)),
        "norm": ones_init(kg(), (d_in,)),
        "out_proj": dense_init(kg(), (d_in, d)),
    }
    logical = {
        "in_proj": ("d_in", "feat"),
        "conv_w": ("none", "feat"),
        "conv_b": ("feat",),
        "A_log": ("none",),
        "dt_bias": ("none",),
        "D": ("none",),
        "norm": ("none",),
        "out_proj": ("feat", "d_in"),
    }
    return p, logical


def _split_proj(cfg, p, x):
    d_in, H, _, _, _ = _dims(cfg)
    zxbcdt = x @ p["in_proj"].astype(COMPUTE_DTYPE)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, d_in + _conv_width(cfg)], axis=-1)
    return z, xbc, dt


def _discretize(p, dt):
    """dt [..., H] → (log decay per step [..., H], effective dt [..., H])."""
    dt_eff = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
    A = -jnp.exp(p["A_log"].astype(F32))  # [H], negative
    return A * dt_eff, dt_eff  # log-decay = A·dt  (≤ 0)


def _conv(p, xbc, conv_state=None):
    """Causal depthwise conv (with bias) over seq, then silu.
    xbc: [B, S, F]; conv_state (decode): [B, CONV_K-1, F] trailing context.
    Returns (out, new_conv_state)."""
    w = p["conv_w"].astype(COMPUTE_DTYPE)  # [K, F]
    if conv_state is None:
        pad = jnp.zeros((xbc.shape[0], CONV_K - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = conv_state.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)  # [B, S+K-1, F]
    out = sum(xp[:, i : i + xbc.shape[1], :] * w[i] for i in range(CONV_K))
    out = out.astype(F32) + p["conv_b"].astype(F32)
    new_state = xp[:, -(CONV_K - 1) :, :].astype(F32)
    return jax.nn.silu(out).astype(COMPUTE_DTYPE), new_state


def _gated_out(cfg, p, y, z):
    """W_out(w · rms_group(y · silu(z))) over G groups of d_in/G; y float32."""
    d_in, _, _, _, G = _dims(cfg)
    h = y * jax.nn.silu(z.astype(F32))
    hg = h.reshape(*h.shape[:-1], G, d_in // G)
    hg = hg * jax.lax.rsqrt(jnp.mean(hg * hg, axis=-1, keepdims=True) + cfg.norm_eps)
    h = (hg.reshape(h.shape) * p["norm"].astype(F32)).astype(COMPUTE_DTYPE)
    return h @ p["out_proj"].astype(COMPUTE_DTYPE)


def mamba_init_state(cfg, batch, dtype=F32):
    _, H, P, N, _ = _dims(cfg)
    return {
        "ssm": jnp.zeros((batch, H, P, N), dtype),
        "conv": jnp.zeros((batch, CONV_K - 1, _conv_width(cfg)), dtype),
    }


def mamba_chunked(cfg, p, x, state=None):
    """x: [B, S, d], any S. Returns (y [B,S,d], final_state)."""
    d_in, H, P, N, G = _dims(cfg)
    Hg = H // G
    B, S, _ = x.shape
    L = min(cfg.ssm_chunk, S)
    nc = -(-S // L)
    extra = nc * L - S

    z, xbc, dt = _split_proj(cfg, p, x)
    xbc, conv_state = _conv(p, xbc, None if state is None else state["conv"])
    logdec, dt_eff = _discretize(p, dt)  # [B,S,H]
    if extra:
        # padded positions: no input (B = x = 0) and no decay (Δ = 0)
        def widen(a):
            return jnp.pad(a, [(0, 0), (0, extra)] + [(0, 0)] * (a.ndim - 2))

        xbc, logdec, dt_eff = widen(xbc), widen(logdec), widen(dt_eff)
    xs, Bmat, Cmat = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)

    # chunk views, heads split by group: [B, nc, L, G, Hg, ...]
    xc = xs.reshape(B, nc, L, G, Hg, P)
    Bc = Bmat.reshape(B, nc, L, G, N).astype(F32)
    Cc = Cmat.reshape(B, nc, L, G, N).astype(F32)
    ld = logdec.reshape(B, nc, L, G, Hg)
    dtc = dt_eff.reshape(B, nc, L, G, Hg)

    cum = jnp.cumsum(ld, axis=2)                       # inclusive
    seg = cum[:, :, :, None] - cum[:, :, None]         # [B,nc,Li,Lj,G,Hg]
    causal = jnp.tril(jnp.ones((L, L), bool))
    seg = jnp.where(causal[None, None, :, :, None, None], seg, -jnp.inf)

    cb = jnp.einsum("bcign,bcjgn->bcijg", Cc, Bc)      # [B,nc,Li,Lj,G]
    scores = cb[..., None] * jnp.exp(seg) * dtc[:, :, None]
    y_intra = jnp.einsum("bcijgh,bcjghp->bcighp",
                         scores.astype(COMPUTE_DTYPE), xc,
                         preferred_element_type=F32)

    # inter-chunk: per-chunk state contribution and the carried recurrence
    decay_to_end = jnp.exp(cum[:, :, -1:] - cum)       # [B,nc,L,G,Hg]
    contrib = jnp.einsum("bclgh,bclgn,bclghp->bcghpn",
                         decay_to_end * dtc, Bc, xc.astype(F32))
    chunk_decay = jnp.exp(cum[:, :, -1])               # [B,nc,G,Hg]

    s0 = (jnp.zeros((B, G, Hg, P, N), F32) if state is None
          else state["ssm"].astype(F32).reshape(B, G, Hg, P, N))

    def step(s, inp):
        dec, con = inp
        return s * dec[..., None, None] + con, s       # out: state BEFORE chunk

    s_final, s_before = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(contrib, 1, 0)))
    s_before = jnp.moveaxis(s_before, 0, 1)            # [B,nc,G,Hg,P,N]

    y_inter = jnp.einsum("bclgn,bclgh,bcghpn->bclghp",
                         Cc, jnp.exp(cum), s_before)
    D = p["D"].astype(F32).reshape(G, Hg)[:, :, None]
    y = y_intra + y_inter + xc.astype(F32) * D
    y = y.reshape(B, nc * L, d_in)[:, :S]
    out = _gated_out(cfg, p, y, z)
    return out, {"ssm": s_final.reshape(B, H, P, N), "conv": conv_state}


def mamba_decode_step(cfg, p, x, state):
    """x: [B, 1, d]; single-step recurrence. Returns (y [B,1,d], state)."""
    d_in, H, P, N, G = _dims(cfg)
    Hg = H // G
    B = x.shape[0]
    z, xbc, dt = _split_proj(cfg, p, x)
    xbc, conv_state = _conv(p, xbc, state["conv"])
    xs, Bmat, Cmat = jnp.split(xbc[:, 0], [d_in, d_in + G * N], axis=-1)
    xh = xs.reshape(B, G, Hg, P).astype(F32)
    Bg = Bmat.reshape(B, G, N).astype(F32)
    Cg = Cmat.reshape(B, G, N).astype(F32)
    logdec, dt_eff = _discretize(p, dt[:, 0, :])       # [B,H]
    dec = jnp.exp(logdec).reshape(B, G, Hg)
    s = state["ssm"].astype(F32).reshape(B, G, Hg, P, N)
    s = (s * dec[..., None, None]
         + jnp.einsum("bgh,bgn,bghp->bghpn", dt_eff.reshape(B, G, Hg), Bg, xh))
    y = jnp.einsum("bgn,bghpn->bghp", Cg, s)
    y = y + xh * p["D"].astype(F32).reshape(G, Hg)[:, :, None]
    out = _gated_out(cfg, p, y.reshape(B, 1, d_in), z)
    return out, {"ssm": s.reshape(B, H, P, N), "conv": conv_state}
