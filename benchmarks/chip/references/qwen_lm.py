"""Plain reference of a Qwen1.5 (Qwen2 architecture) decoder, in float32
``jax.numpy`` at the highest matmul precision, independent of the program.

Pre-norm blocks: x += Attn(RMSNorm(x)) with q/k/v biases, rotary position
embeddings (rotate-half, base ``rope_theta``) and causal softmax; then
x += W2 (silu(W1 h) * W3 h) with h = RMSNorm(x). A final RMSNorm and the
tied embedding give the logits. No cache, no batching tricks: one full
forward pass over each sequence.

``make_params`` makes the weights from a seed on the device in one jitted
call, in float32, laid out with a leading layer axis; the benchmark hands
the same tree to the program. ``quant="fp8"`` rounds every matmul operand
to float8_e4m3 with a per-tensor scale: the control.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
STD = 0.02


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) >> 1), int(b) >> 1)


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, hd=hd,
                q=cfg["num_attention_heads"] * hd,
                kv=cfg["num_key_value_heads"] * hd,
                ff=cfg["intermediate_size"], V=cfg["vocab_size"])


@functools.lru_cache(maxsize=None)
def _maker(L, d, q, kv, ff, V):
    def make(key):
        ks = iter(jax.random.split(key, 16))

        def n(shape):
            return STD * jax.random.normal(next(ks), shape, jnp.float32)

        return {
            "embed": n((V, d)),
            "final_norm": 1.0 + n((d,)),
            "layers": {
                "attn": {"wq": n((L, d, q)), "wk": n((L, d, kv)),
                         "wv": n((L, d, kv)), "wo": n((L, q, d)),
                         "bq": n((L, q)), "bk": n((L, kv)), "bv": n((L, kv))},
                "ln1": 1.0 + n((L, d)),
                "ln2": 1.0 + n((L, d)),
                "mlp": {"w1": n((L, d, ff)), "w3": n((L, d, ff)),
                        "w2": n((L, ff, d))},
            },
        }

    return jax.jit(make)


def make_params(cfg: dict, seed: int):
    m = dims(cfg)
    return _maker(m["L"], m["d"], m["q"], m["kv"], m["ff"], m["V"])(seed_key(seed))


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, quant):
    if quant:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs          # [S, half]
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("first", "n_heads", "n_kv",
                                             "eps", "theta", "quant"))
def _logits_at(params, tokens, *, first, n_heads, n_kv, eps, theta, quant):
    """Logits [B, S - first, V] at positions first..S-1 of ``tokens``."""
    B, S = tokens.shape
    x = params["embed"][tokens]
    pos = jnp.arange(S)
    mask = pos[:, None] >= pos[None, :]

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["ln1"], eps)
        qh = (_mm("bsd,dq->bsq", h, a["wq"], quant) + a["bq"]).reshape(B, S, n_heads, -1)
        kh = (_mm("bsd,dq->bsq", h, a["wk"], quant) + a["bk"]).reshape(B, S, n_kv, -1)
        vh = (_mm("bsd,dq->bsq", h, a["wv"], quant) + a["bv"]).reshape(B, S, n_kv, -1)
        qh, kh = _rope(qh, pos, theta), _rope(kh, pos, theta)
        g = n_heads // n_kv
        kh, vh = jnp.repeat(kh, g, axis=2), jnp.repeat(vh, g, axis=2)
        s = _mm("bqhd,bkhd->bhqk", qh, kh, quant) * (qh.shape[-1] ** -0.5)
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        o = _mm("bhqk,bkhd->bqhd", p, vh, quant).reshape(B, S, -1)
        x = x + _mm("bsq,qd->bsd", o, a["wo"], quant)
        h = _rms(x, lp["ln2"], eps)
        m = lp["mlp"]
        u = jax.nn.silu(_mm("bsd,df->bsf", h, m["w1"], quant)) * \
            _mm("bsd,df->bsf", h, m["w3"], quant)
        return x + _mm("bsf,fd->bsd", u, m["w2"], quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x[:, first:], params["final_norm"], eps)
    return _mm("bsd,vd->bsv", x, params["embed"], quant)


def logits_for(cfg: dict, params, prompts, served, quant: bool = False):
    """Logits that predict each served token: the sequence is the prompt and
    the served tokens, and position P-1+k predicts served token k."""
    seq = np.concatenate([prompts, served[:, :-1]], axis=1).astype(np.int32)
    return _logits_at(params, jnp.asarray(seq), first=prompts.shape[1] - 1,
                      n_heads=cfg["num_attention_heads"],
                      n_kv=cfg["num_key_value_heads"],
                      eps=float(cfg["rms_norm_eps"]),
                      theta=float(cfg["rope_theta"]), quant=quant)


def served_gaps(ref_logits, served) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at its position."""
    ref = np.asarray(ref_logits, np.float64)
    pick = np.take_along_axis(ref, np.asarray(served)[..., None], axis=-1)[..., 0]
    return ref.max(axis=-1) - pick
