"""Plain reference of Zamba2 (hf:Zyphra/Zamba2-7B-Instruct), in float32
``jax.numpy`` at the highest matmul precision, independent of the program.

e0 is the token embedding and ``rms`` an RMSNorm with ε = ``rms_norm_eps``.
Layer ℓ of ``num_hidden_layers``: x ← x + Mamba2(rms(x + t_ℓ)), where t_ℓ =
linear_ℓ(Shared_{k mod num_mem_blocks}(x, e0; adapter_ℓ)) at the k-th of
``hybrid_layer_ids`` and 0 elsewhere.

- Shared_b, with no residual: u = rms(concat([x, e0])); a = o(attn(q(u),
  k(u), v(u))), causal, rotary embeddings (rotate-half, base ``rope_theta``)
  over the whole ``attention_head_dim``-wide head, scores scaled by
  (head_dim / 2)^-1/2; a' = rms(a); g, up = split(W_gu a' + A_up(A_down a'));
  the result is W_down(gelu(g) · up), exact (erf) GELU.
- Mamba2: z, xBC, dt = split(W_in h); xBC = silu(conv4(xBC) + b); x, B, C =
  split(xBC), head h reading group ⌊h / (H / ngroups)⌋ of B and C; Δ =
  softplus(dt + dt_bias); s_t = exp(Δ·A) s_{t-1} + Δ B_t ⊗ x_t, y_t = C_t ·
  s_t + D x_t, run as the **sequential** recurrence over positions (not the
  program's chunked form); out = W_out(w · rms_group(y · silu(z))) over
  ngroups groups.
- A final RMSNorm and the tied embedding give the logits.

No cache, no batching tricks: one full forward pass over each sequence, with
attention computed in blocks of queries so that it fits at 4096 positions.

``make_params`` makes the weights from a seed on the device in one jitted
call, in float32, in the program's layout (plain Mamba2 layers between
hybrid layers stacked along a leading axis); the benchmark hands the same
tree to the program. ``quant=True`` rounds every matmul operand to
float8_e4m3 with a per-tensor scale: the control.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
STD = 0.02
CONV_STD, CONV_BIAS_STD = 0.3, 0.1
DT_MIN, DT_MAX = 1e-3, 0.1   # the published time_step_min / time_step_max
Q_BLOCK = 512                # queries per attention block
PAD = 1024                   # compared sequences are padded to a multiple


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) >> 1), int(b) >> 1)


def dims(cfg: dict) -> dict:
    """The published keys this reference reads, checked for the variant it
    implements."""
    want = {"use_conv_bias": True, "use_mem_rope": True,
            "use_shared_mlp_adapter": True, "use_shared_attention_adapter": False,
            "add_bias_linear": False, "hidden_act": "gelu", "mamba_d_conv": 4}
    wrong = {k: cfg.get(k) for k, v in want.items() if cfg.get(k) != v}
    if wrong:
        raise ValueError(f"this reference implements {want}; the file has {wrong}")
    d = cfg["hidden_size"]
    d_in = cfg["mamba_expand"] * d
    G, N = cfg["mamba_ngroups"], cfg["mamba_d_state"]
    return dict(L=cfg["num_hidden_layers"], d=d, d_in=d_in, P=cfg["mamba_headdim"],
                H=d_in // cfg["mamba_headdim"], N=N, G=G, F=d_in + 2 * G * N,
                K=cfg["mamba_d_conv"], heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], hd=cfg["attention_head_dim"],
                a_in=cfg["attention_hidden_size"], ff=cfg["ffn_hidden_size"],
                r=cfg["adapter_rank"], V=cfg["vocab_size"],
                blocks=cfg["num_mem_blocks"],
                hybrid=tuple(cfg["hybrid_layer_ids"]))


def runs(L: int, hybrid) -> list:
    """Lengths of the runs of plain layers before each hybrid layer and
    after the last, empty runs left out: the program's stacking."""
    edges = [-1, *hybrid, L]
    return [b - a - 1 for a, b in zip(edges, edges[1:]) if b - a > 1]


@functools.lru_cache(maxsize=None)
def _maker(L, d, d_in, H, F, heads, kv_heads, hd, a_in, ff, r, V, blocks, hybrid):
    n_mamba = len(runs(L, hybrid)) + len(hybrid)

    def make(key):
        keys = iter(jax.random.split(key, 2 + 8 * n_mamba + 3 * len(hybrid)
                                     + 9 * blocks))

        def n(shape, std=STD):
            return std * jax.random.normal(next(keys), shape, jnp.float32)

        def gain(shape):
            return 1.0 + n(shape)

        def mamba(lead):
            # A = -(1..H) and Δ log-uniform in [DT_MIN, DT_MAX] as in the
            # published initialization; dt_bias is softplus⁻¹(Δ).
            dt = jnp.exp(jax.random.uniform(next(keys), (*lead, H), jnp.float32,
                                            np.log(DT_MIN), np.log(DT_MAX)))
            return {"cell": {
                "in_proj": n((*lead, d, d_in + F + H)),
                "conv_w": n((*lead, 4, F), CONV_STD),
                "conv_b": n((*lead, F), CONV_BIAS_STD),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
                                          (*lead, H)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "D": gain((*lead, H)),
                "norm": gain((*lead, d_in)),
                "out_proj": n((*lead, d_in, d)),
            }, "ln": gain((*lead, d))}

        q, kv = heads * hd, kv_heads * hd
        return {
            "embed": n((V, d)),
            "final_norm": gain((d,)),
            "runs": [mamba((m,)) for m in runs(L, hybrid)],
            "hybrid": [{"mamba": mamba(()),
                        "adapter": {"down": n((d, r)), "up": n((r, 2 * ff))},
                        "linear": n((d, d))} for _ in hybrid],
            "shared": [{"ln": gain((a_in,)),
                        "attn": {"wq": n((a_in, q)), "wk": n((a_in, kv)),
                                 "wv": n((a_in, kv)), "wo": n((q, d))},
                        "mlp_ln": gain((d,)),
                        "mlp": {"gate_up": n((d, 2 * ff)), "down": n((ff, d))}}
                       for _ in range(blocks)],
        }

    return jax.jit(make)


def make_params(cfg: dict, seed: int):
    m = dims(cfg)
    return _maker(m["L"], m["d"], m["d_in"], m["H"], m["F"], m["heads"],
                  m["kv_heads"], m["hd"], m["a_in"], m["ff"], m["r"], m["V"],
                  m["blocks"], m["hybrid"])(seed_key(seed))


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


def _attention(q, k, v, quant):
    """Causal softmax attention, q [B, S, H, hd], k/v [B, S, KV, hd], in
    blocks of Q_BLOCK queries (the last block may be shorter)."""
    B, S, H, hd = q.shape
    g = H // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scale = (hd / 2) ** -0.5
    out = []
    for a in range(0, S, Q_BLOCK):
        qb = q[:, a:a + Q_BLOCK]
        s = _mm("bqhd,bkhd->bhqk", qb, k, quant) * scale
        mask = (a + jnp.arange(qb.shape[1]))[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        out.append(_mm("bhqk,bkhd->bqhd", p, v, quant))
    return jnp.concatenate(out, axis=1)


def _shared(sp, adapter, x, e0, *, heads, kv_heads, eps, theta, quant):
    B, S, _ = x.shape
    a = sp["attn"]
    u = _rms(jnp.concatenate([x, e0], axis=-1), sp["ln"], eps)
    pos = jnp.arange(S)
    q = _mm("bsd,dq->bsq", u, a["wq"], quant).reshape(B, S, heads, -1)
    k = _mm("bsd,dq->bsq", u, a["wk"], quant).reshape(B, S, kv_heads, -1)
    v = _mm("bsd,dq->bsq", u, a["wv"], quant).reshape(B, S, kv_heads, -1)
    o = _attention(_rope(q, pos, theta), _rope(k, pos, theta), v, quant)
    h = _rms(_mm("bsq,qd->bsd", o.reshape(B, S, -1), a["wo"], quant), sp["mlp_ln"], eps)
    m = sp["mlp"]
    gu = _mm("bsd,df->bsf", h, m["gate_up"], quant) + _mm(
        "bsr,rf->bsf", _mm("bsd,dr->bsr", h, adapter["down"], quant), adapter["up"], quant)
    g, up = jnp.split(gu, 2, axis=-1)
    return _mm("bsf,fd->bsd", jax.nn.gelu(g, approximate=False) * up, m["down"], quant)


def mamba2(p, h, *, G, eps, quant):
    """The Mamba2 mixer over h [B, S, d] as the sequential recurrence.
    Returns (out [B, S, d], final state [B, H, P, N], last 3 conv inputs)."""
    B, S, _ = h.shape
    H, F = p["A_log"].shape[0], p["conv_b"].shape[0]
    d_in = p["out_proj"].shape[0]
    P, N = d_in // H, (F - d_in) // (2 * G)
    z, xbc, dt = jnp.split(_mm("bsd,df->bsf", h, p["in_proj"], quant),
                           [d_in, d_in + F], axis=-1)
    K = p["conv_w"].shape[0]
    xp = jnp.concatenate([jnp.zeros((B, K - 1, F), jnp.float32), xbc], axis=1)
    conv = sum(xp[:, i:i + S] * p["conv_w"][i] for i in range(K)) + p["conv_b"]
    xbc = jax.nn.silu(conv)
    xs, Bm, Cm = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    group = jnp.arange(H) // (H // G)
    xh = xs.reshape(B, S, H, P)
    Bh = Bm.reshape(B, S, G, N)[:, :, group]                 # [B, S, H, N]
    Ch = Cm.reshape(B, S, G, N)[:, :, group]
    delta = jax.nn.softplus(dt + p["dt_bias"])               # [B, S, H]
    A = -jnp.exp(p["A_log"])

    def step(s, t):
        d_t, x_t, b_t, c_t = t
        s = (jnp.exp(d_t * A)[:, :, None, None] * s
             + (d_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t, precision=HIGHEST)

    seq = lambda a: jnp.moveaxis(a, 1, 0)
    s, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), jnp.float32),
                        (seq(delta), seq(xh), seq(Bh), seq(Ch)))
    y = jnp.moveaxis(y, 0, 1) + xh * p["D"][:, None]
    y = (y.reshape(B, S, d_in) * jax.nn.silu(z)).reshape(B, S, G, d_in // G)
    y = (y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
         ).reshape(B, S, d_in) * p["norm"]
    return _mm("bsf,fd->bsd", y, p["out_proj"], quant), s, xp[:, -(K - 1):]


def _layers(params, hybrid, L):
    """(layer params, hybrid index or None) in depth order."""
    run_iter = iter(params["runs"])
    stack, j, k = None, 0, 0
    for layer in range(L):
        if layer in hybrid:
            yield params["hybrid"][k], k
            k += 1
            continue
        if stack is None or j == stack["ln"].shape[0]:
            stack, j = next(run_iter), 0
        yield jax.tree.map(lambda a, j=j: a[j], stack), None
        j += 1


@functools.partial(jax.jit, static_argnames=("first", "L", "hybrid", "G",
                                             "heads", "kv_heads", "eps",
                                             "theta", "quant"))
def _logits_at(params, tokens, *, first, L, hybrid, G, heads, kv_heads, eps,
               theta, quant):
    """Logits [B, S - first, V] at positions first..S-1 of ``tokens``."""
    x = params["embed"][tokens]
    e0 = x
    blocks = len(params["shared"])
    for lp, k in _layers(params, hybrid, L):
        h = x
        if k is not None:
            t = _shared(params["shared"][k % blocks], lp["adapter"], x, e0,
                        heads=heads, kv_heads=kv_heads, eps=eps, theta=theta,
                        quant=quant)
            h = x + _mm("bsd,de->bse", t, lp["linear"], quant)
            lp = lp["mamba"]
        y, _, _ = mamba2(lp["cell"], _rms(h, lp["ln"], eps), G=G, eps=eps, quant=quant)
        x = x + y
    x = _rms(x[:, first:], params["final_norm"], eps)
    return _mm("bsd,vd->bsv", x, params["embed"], quant)


def logits_for(cfg: dict, params, prompts, served, quant: bool = False):
    """Logits that predict each served token: the sequence is the prompt and
    the served tokens, and position P-1+k predicts served token k. The
    sequence is padded at its end to a multiple of PAD positions, so that
    nearby lengths share one compiled reference; causal, the padding moves
    no logit at a real position."""
    seq = np.concatenate([prompts, served[:, :-1]], axis=1).astype(np.int32)
    n = seq.shape[1]
    seq = np.pad(seq, [(0, 0), (0, -n % PAD)])
    first = prompts.shape[1] - 1
    return logits_full(cfg, params, seq, quant=quant)[:, first:n]


def logits_full(cfg: dict, params, tokens, first: int = 0, quant: bool = False):
    """Logits at positions first.. of ``tokens`` [B, S]."""
    m = dims(cfg)
    return _logits_at(params, jnp.asarray(tokens, jnp.int32), first=first,
                      L=m["L"], hybrid=m["hybrid"], G=m["G"], heads=m["heads"],
                      kv_heads=m["kv_heads"], eps=float(cfg["rms_norm_eps"]),
                      theta=float(cfg["rope_theta"]), quant=quant)


def served_gaps(ref_logits, served) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at its position."""
    ref = np.asarray(ref_logits, np.float64)
    pick = np.take_along_axis(ref, np.asarray(served)[..., None], axis=-1)[..., 0]
    return ref.max(axis=-1) - pick
