"""Recurrent / hybrid LM assemblies: xLSTM (ssm family) and Zamba2 (hybrid).

xLSTM-1.3b: blocks in groups of ``slstm_every`` — (slstm_every − 1) mLSTM
blocks followed by 1 sLSTM block — scanned over groups with an inner scan
over the stacked mLSTM blocks.

Zamba2-7b: n_layers Mamba2 layers, ``x ← x + Mamba2(rms(x + t_ℓ))``. At the
k-th of ``hybrid_layer_ids``, t_ℓ = linear_ℓ(Shared_{k mod n_shared_blocks}(x,
e0; adapter_ℓ)), else 0: a shared transformer block (RMSNorm over
concat([x, e0]), attention, RMSNorm, gated MLP with the layer's low-rank
adapter; no residual inside) whose weights every hybrid layer of its parity
reuses, and e0 the token embedding. The plain layers between hybrid layers
are scanned, so the HLO is O(number of hybrid layers), not O(depth); each
hybrid layer keeps its own KV cache beside every layer's recurrent state.

Sharding profile "ssm" (models/sharding.py): sequence local, batch over
("pod","data"), cell feature dims over "model".
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from .attention import attention, decode_attention, init_attention
from .common import COMPUTE_DTYPE, KeyGen, dense_init, ones_init, rmsnorm, softmax_cross_entropy
from .mlp import gated_mlp, init_adapter, init_gated_mlp
from .ssm import (init_mamba, mamba_chunked, mamba_decode_step, mamba_init_state)
from .transformer import _probe, stack_init
from .xlstm import (init_mlstm, init_slstm, mlstm_chunked, mlstm_decode_step,
                    mlstm_init_state, slstm_decode_step, slstm_init_state, slstm_seq)

__all__ = [
    "init_xlstm_lm", "xlstm_forward", "xlstm_loss", "xlstm_prefill",
    "xlstm_decode_step", "xlstm_cache_shape",
    "init_zamba_lm", "zamba_forward", "zamba_loss", "zamba_prefill",
    "zamba_decode_step", "zamba_cache_shape", "zamba_layout",
]


# ---------------------------------------------------------------------------
# xLSTM
# ---------------------------------------------------------------------------


def _xlstm_groups(cfg) -> Tuple[int, int]:
    per = cfg.slstm_every
    assert cfg.n_layers % per == 0
    return cfg.n_layers // per, per - 1  # (n_groups, mlstm per group)


def init_xlstm_lm(cfg, key=None):
    kg = KeyGen(key) if key is not None else _probe()
    p: Dict[str, Any] = {
        "embed": dense_init(kg() if key is not None else None, (cfg.vocab, cfg.d_model)),
        "final_norm": ones_init(kg() if key is not None else None, (cfg.d_model,)),
        "head": dense_init(kg() if key is not None else None, (cfg.d_model, cfg.vocab)),
    }
    l: Dict[str, Any] = {"embed": ("vocab", "d_in"), "final_norm": ("none",),
                         "head": ("d_in", "vocab")}
    n_groups, n_m = _xlstm_groups(cfg)

    def init_group(kg2):
        def init_mblock(kg3):
            mp, ml = init_mlstm(cfg, kg3)
            return ({"cell": mp, "ln": ones_init(kg3(), (cfg.d_model,))},
                    {"cell": ml, "ln": ("none",)})

        mp, ml = stack_init(n_m, init_mblock,
                            kg2() if not isinstance(kg2, _probe) else None)
        sp, sl = init_slstm(cfg, kg2)
        return ({"m": mp, "s": sp, "s_ln": ones_init(kg2(), (cfg.d_model,))},
                {"m": ml, "s": sl, "s_ln": ("none",)})

    lkey = None if key is None else kg()
    p["groups"], l["groups"] = stack_init(n_groups, init_group, lkey)
    return p, l


def _xlstm_stack(cfg, params, x, constrain, remat, states=None, collect=False,
                 single_step=False):
    """Shared group-scan driver. states: optional cache pytree to thread."""
    n_groups, n_m = _xlstm_groups(cfg)
    mstep = mlstm_decode_step if single_step else mlstm_chunked
    sstep = slstm_decode_step if single_step else slstm_seq

    def mblock(x, mp, st):
        y, st2 = mstep(cfg, mp["cell"], rmsnorm(x, mp["ln"], cfg.norm_eps), st)
        return constrain(x + y), st2

    def group_body(carry, gin):
        x = carry
        gp, gst = gin

        def inner(x, lin):
            mp, st = lin
            x, st2 = mblock(x, mp, st)
            return x, st2

        inner_fn = jax.checkpoint(inner, policy=jax.checkpoint_policies.nothing_saveable) \
            if remat else inner
        x, mstates = jax.lax.scan(inner_fn, x, (gp["m"], gst["m"]))
        y, sstate = sstep(cfg, gp["s"], rmsnorm(x, gp["s_ln"], cfg.norm_eps),
                          gst["s"])
        x = constrain(x + y)
        return x, {"m": mstates, "s": sstate}

    if states is None:
        B = x.shape[0]
        m0 = mlstm_init_state(cfg, B)
        s0 = slstm_init_state(cfg, B)
        states = {
            "m": jax.tree.map(lambda a: jnp.broadcast_to(a, (n_groups, n_m, *a.shape)), m0),
            "s": jax.tree.map(lambda a: jnp.broadcast_to(a, (n_groups, *a.shape)), s0),
        }
    x, new_states = jax.lax.scan(group_body, x, (params["groups"], states))
    return x, new_states


def xlstm_forward(cfg, params, tokens, constrain=lambda x: x, remat=True,
                  states=None, single_step=False):
    x = constrain(jnp.take(params["embed"].astype(COMPUTE_DTYPE), tokens, axis=0))
    x, new_states = _xlstm_stack(cfg, params, x, constrain, remat, states,
                                 single_step=single_step)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x @ params["head"].astype(COMPUTE_DTYPE), new_states


def xlstm_loss(cfg, params, tokens, labels, constrain=lambda x: x, remat=True):
    logits, _ = xlstm_forward(cfg, params, tokens, constrain, remat)
    ce = softmax_cross_entropy(logits, labels)
    return ce, ce


def xlstm_cache_shape(cfg, batch: int, max_seq: int):
    """Recurrent state 'cache' — O(1) in sequence length (the 500k story)."""
    n_groups, n_m = _xlstm_groups(cfg)
    m0 = mlstm_init_state(cfg, batch)
    s0 = slstm_init_state(cfg, batch)
    tree = {
        "m": jax.tree.map(lambda a: jax.ShapeDtypeStruct((n_groups, n_m, *a.shape),
                                                         a.dtype), m0),
        "s": jax.tree.map(lambda a: jax.ShapeDtypeStruct((n_groups, *a.shape),
                                                         a.dtype), s0),
    }
    mlog = {"C": ("layers", "none", "batch", "none", "feat", "none"),
            "n": ("layers", "none", "batch", "none", "feat"),
            "m": ("layers", "none", "batch", "none")}
    slog = {k: ("layers", "batch", "none", "none") for k in ("c", "n", "h", "m")}
    return tree, {"m": mlog, "s": slog}


def xlstm_prefill(cfg, params, tokens, max_seq: int, constrain=lambda x: x):
    logits, states = xlstm_forward(cfg, params, tokens, constrain, remat=False)
    return logits[:, -1:, :], states


def xlstm_decode_step(cfg, params, cache, token, pos, constrain=lambda x: x):
    del pos  # recurrent state carries position implicitly
    logits, states = xlstm_forward(cfg, params, token, constrain, remat=False,
                                   states=cache, single_step=True)
    return logits, states


# ---------------------------------------------------------------------------
# Zamba2
# ---------------------------------------------------------------------------


def zamba_layout(cfg) -> List[Tuple[str, int]]:
    """The layer pattern in execution order: ``("run", n)`` for n plain
    Mamba2 layers scanned together, ``("hybrid", k)`` for the k-th hybrid
    layer."""
    steps: List[Tuple[str, int]] = []
    prev = -1
    for k, h in enumerate(cfg.hybrid_layer_ids):
        if h - prev > 1:
            steps.append(("run", h - prev - 1))
        steps.append(("hybrid", k))
        prev = h
    if cfg.n_layers - prev > 1:
        steps.append(("run", cfg.n_layers - prev - 1))
    return steps


def _init_mamba_layer(cfg, kg):
    mp, ml = init_mamba(cfg, kg)
    return ({"cell": mp, "ln": ones_init(kg(), (cfg.d_model,))},
            {"cell": ml, "ln": ("none",)})


def _init_hybrid_layer(cfg, kg):
    """A hybrid layer's own weights: its Mamba2 layer, the shared MLP's
    adapter for this invocation, and the d → d linear after the block."""
    mp, ml = _init_mamba_layer(cfg, kg)
    ap, al = init_adapter(cfg, kg)
    return ({"mamba": mp, "adapter": ap,
             "linear": dense_init(kg(), (cfg.d_model, cfg.d_model))},
            {"mamba": ml, "adapter": al, "linear": ("d_in", "feat")})


def _init_shared_block(cfg, kg):
    ap, al = init_attention(cfg, kg)
    mp, ml = init_gated_mlp(cfg, kg)
    return ({"ln": ones_init(kg(), (cfg.attn_in or cfg.d_model,)), "attn": ap,
             "mlp_ln": ones_init(kg(), (cfg.d_model,)), "mlp": mp},
            {"ln": ("none",), "attn": al, "mlp_ln": ("none",), "mlp": ml})


def _unzip(pairs):
    return [p for p, _ in pairs], [l for _, l in pairs]


def init_zamba_lm(cfg, key=None):
    kg = KeyGen(key) if key is not None else _probe()
    d = cfg.d_model
    p: Dict[str, Any] = {"embed": dense_init(kg(), (cfg.vocab, d)),
                         "final_norm": ones_init(kg(), (d,))}
    l: Dict[str, Any] = {"embed": ("vocab", "d_in"), "final_norm": ("none",)}
    if not cfg.tie_embeddings:
        p["head"], l["head"] = dense_init(kg(), (d, cfg.vocab)), ("d_in", "vocab")
    p["runs"], l["runs"] = _unzip(
        stack_init(n, lambda kg2: _init_mamba_layer(cfg, kg2), kg())
        for kind, n in zamba_layout(cfg) if kind == "run")
    p["hybrid"], l["hybrid"] = _unzip(
        _init_hybrid_layer(cfg, kg) for _ in cfg.hybrid_layer_ids)
    p["shared"], l["shared"] = _unzip(
        _init_shared_block(cfg, kg) for _ in range(cfg.n_shared_blocks))
    return p, l


def _seq_sharded(a):
    # The hybrid profile keeps sequences device-local for the Mamba
    # recurrence, but the shared block is full attention: without sequence
    # sharding its f32 score blocks are [B_local, S, H, blk] per device on
    # prefill_32k (§Perf #3). Shard q/k/v along seq over whatever mesh axes
    # the batch left free.
    from .sharding import constrain, rules_for

    if a.ndim != 4:
        return a
    return constrain(a, rules_for("hybrid"), "batch", "kv_seq", None, None)


def _shared_block(cfg, sp, adapter, x, e0, kv=None, pos=None):
    """Shared_b(x, e0; adapter), which has no residual: attention over
    rms(concat([x, e0])), then the gated MLP of rms(attention). With ``kv``
    (decode) the step attends over and updates that cache. Returns
    (output [B, S, d], (k, v))."""
    u = rmsnorm(jnp.concatenate([x, e0], axis=-1), sp["ln"], cfg.norm_eps)
    if kv is None:
        a, kv = attention(cfg, sp["attn"], u, positions=jnp.arange(x.shape[1])[None],
                          constrain=_seq_sharded)
    else:
        a, ck, cv = decode_attention(cfg, sp["attn"], u, *kv, pos, write=True)
        kv = (ck, cv)
    h = rmsnorm(a, sp["mlp_ln"], cfg.norm_eps)
    return gated_mlp(sp["mlp"], h, cfg.mlp_act, adapter), kv


def _zero_states(cfg, batch, n=None):
    st = mamba_init_state(cfg, batch)
    if n is None:
        return st
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n, *a.shape)), st)


def _zamba_stack(cfg, params, x, constrain, remat, cache=None, pos=None):
    """Every layer over x. ``cache=None`` runs the chunked form from a zero
    state (train / prefill); a decode cache runs one recurrent step against
    it. Returns (x, cache entries {"runs": [...], "hybrid": [...]}), the
    hybrid entries holding the shared block's (unpadded) k and v."""
    mixer = mamba_chunked if cache is None else mamba_decode_step
    e0 = x

    def mamba_layer(x, lp, st, t=None):
        h = rmsnorm(x if t is None else x + t, lp["ln"], cfg.norm_eps)
        with jax.named_scope("mamba2"):
            y, st = mixer(cfg, lp["cell"], h, st)
        return constrain(x + y), st

    def run_body(x, lin):
        return mamba_layer(x, *lin)

    def hybrid_layer(x, hp, sp, st, kv):
        with jax.named_scope("zamba.shared"):
            t, kv = _shared_block(cfg, sp, hp["adapter"], x, e0, kv, pos)
            t = t @ hp["linear"].astype(COMPUTE_DTYPE)
        x, st = mamba_layer(x, hp["mamba"], st, t)
        return x, st, kv

    if remat:
        save_nothing = jax.checkpoint_policies.nothing_saveable
        run_body = jax.checkpoint(run_body, policy=save_nothing)
        hybrid_layer = jax.checkpoint(hybrid_layer, policy=save_nothing)

    B = x.shape[0]
    out: Dict[str, list] = {"runs": [], "hybrid": []}
    for kind, i in zamba_layout(cfg):
        if kind == "run":
            r = len(out["runs"])
            st = _zero_states(cfg, B, i) if cache is None else cache["runs"][r]
            x, st = jax.lax.scan(run_body, x, (params["runs"][r], st))
            out["runs"].append(st)
        else:
            if cache is None:
                st, kv = _zero_states(cfg, B), None
            else:
                hc = cache["hybrid"][i]
                st, kv = {"ssm": hc["ssm"], "conv": hc["conv"]}, (hc["k"], hc["v"])
            sp = params["shared"][i % cfg.n_shared_blocks]
            x, st, (k, v) = hybrid_layer(x, params["hybrid"][i], sp, st, kv)
            out["hybrid"].append(dict(st, k=k, v=v))
    return x, out


def _zamba_logits(cfg, params, x):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return x @ w.astype(COMPUTE_DTYPE)


def zamba_forward(cfg, params, tokens, constrain=lambda x: x, remat=True):
    x = constrain(jnp.take(params["embed"].astype(COMPUTE_DTYPE), tokens, axis=0))
    x, _ = _zamba_stack(cfg, params, x, constrain, remat)
    return _zamba_logits(cfg, params, x)


def zamba_loss(cfg, params, tokens, labels, constrain=lambda x: x, remat=True):
    logits = zamba_forward(cfg, params, tokens, constrain, remat)
    ce = softmax_cross_entropy(logits, labels)
    return ce, ce


def zamba_cache_shape(cfg, batch: int, max_seq: int):
    """Recurrent state for every Mamba2 layer and a KV cache for every
    hybrid layer's shared-block call."""
    st = jax.eval_shape(lambda: mamba_init_state(cfg, batch))
    kv = jax.ShapeDtypeStruct((batch, max_seq, cfg.n_kv_heads, cfg.hd), COMPUTE_DTYPE)
    runs = [jax.tree.map(lambda a: jax.ShapeDtypeStruct((n, *a.shape), a.dtype), st)
            for kind, n in zamba_layout(cfg) if kind == "run"]
    hybrid = [dict(st, k=kv, v=kv) for _ in cfg.hybrid_layer_ids]
    slog = {"ssm": ("batch", "feat", "none", "none"), "conv": ("batch", "none", "feat")}
    rlog = {k: ("layers", *v) for k, v in slog.items()}
    kvlog = ("batch", "kv_seq", "none", "none")
    logical = {"runs": [rlog for _ in runs],
               "hybrid": [dict(slog, k=kvlog, v=kvlog) for _ in hybrid]}
    return {"runs": runs, "hybrid": hybrid}, logical


def zamba_prefill(cfg, params, tokens, max_seq: int, constrain=lambda x: x):
    x = constrain(jnp.take(params["embed"].astype(COMPUTE_DTYPE), tokens, axis=0))
    x, cache = _zamba_stack(cfg, params, x, constrain, remat=False)

    def pad(kv):  # [B, S, KV, hd] → [B, max_seq, KV, hd]
        return jnp.pad(kv.astype(COMPUTE_DTYPE),
                       [(0, 0), (0, max_seq - kv.shape[1]), (0, 0), (0, 0)])

    cache["hybrid"] = [dict(h, k=pad(h["k"]), v=pad(h["v"])) for h in cache["hybrid"]]
    return _zamba_logits(cfg, params, x[:, -1:]), cache


def zamba_decode_step(cfg, params, cache, token, pos, constrain=lambda x: x):
    x = constrain(jnp.take(params["embed"].astype(COMPUTE_DTYPE), token, axis=0))
    x, cache = _zamba_stack(cfg, params, x, constrain, remat=False, cache=cache,
                            pos=pos)
    return _zamba_logits(cfg, params, x), cache
