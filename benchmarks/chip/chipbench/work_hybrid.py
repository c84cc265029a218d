"""Least work of a Zamba2 forward pass, counted from the configuration
file's published keys (2 FLOPs per multiply-add).

Per token and Mamba2 layer: the in and out projections, the depthwise conv
and the SSM recurrence (the state's decay, Δ·B ⊗ x and C · s: 5 FLOPs per
state element). Per token and hybrid layer: the shared block's q/k/v/o and
gated-MLP projections, its adapter and the layer's ``linear``, and the two
attention contractions over the context. The tied head counts only where
logits are needed.
"""

from __future__ import annotations

__all__ = ["dims", "mixer_flops", "shared_flops", "forward_flops",
           "request_flops"]


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    d_in = cfg["mamba_expand"] * d
    G, N = cfg["mamba_ngroups"], cfg["mamba_d_state"]
    return dict(L=cfg["num_hidden_layers"], d=d, d_in=d_in, N=N,
                H=d_in // cfg["mamba_headdim"], F=d_in + 2 * G * N,
                K=cfg["mamba_d_conv"],
                q=cfg["num_attention_heads"] * cfg["attention_head_dim"],
                kv=cfg["num_key_value_heads"] * cfg["attention_head_dim"],
                a_in=cfg["attention_hidden_size"], ff=cfg["ffn_hidden_size"],
                r=cfg["adapter_rank"], V=cfg["vocab_size"],
                n_hybrid=len(cfg["hybrid_layer_ids"]))


def mixer_flops(cfg: dict) -> int:
    """One Mamba2 mixer, one token."""
    m = dims(cfg)
    proj = 2 * m["d"] * (m["d_in"] + m["F"] + m["H"]) + 2 * m["d_in"] * m["d"]
    return proj + 2 * m["K"] * m["F"] + 5 * m["d_in"] * m["N"]


def shared_flops(cfg: dict, context: float) -> float:
    """One shared-block call with its adapter and linear, one token that
    attends to ``context`` keys."""
    m = dims(cfg)
    d, q, ff = m["d"], m["q"], m["ff"]
    proj = m["a_in"] * (q + 2 * m["kv"]) + q * d + 3 * d * ff
    side = m["r"] * (d + 2 * ff) + d * d
    return 2 * (proj + side) + 2 * 2 * context * q


def forward_flops(cfg: dict, n_tokens: int, context: float,
                  with_head: bool) -> float:
    """``n_tokens`` positions through every layer, each attending to
    ``context`` keys on average in each hybrid layer."""
    m = dims(cfg)
    per_token = m["L"] * mixer_flops(cfg) + m["n_hybrid"] * shared_flops(cfg, context)
    if with_head:
        per_token += 2 * m["d"] * m["V"]
    return n_tokens * per_token


def request_flops(cfg: dict, batch: int, prompt: int, gen: int) -> float:
    """A request: the prompt's prefill with the logits of its last position,
    then gen - 1 decode steps."""
    return (forward_flops(cfg, batch * prompt, (prompt + 1) / 2, False)
            + forward_flops(cfg, batch, 0, True) - forward_flops(cfg, batch, 0, False)
            + forward_flops(cfg, batch * (gen - 1), prompt + gen / 2, True))
