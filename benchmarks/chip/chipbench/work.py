"""Least work of the benchmark's computations, counted from shapes.

Operations and bytes are what the algorithm needs, not what an
implementation happens to execute, so a roofline share that uses them
cannot pass 100% unless the time leaves out work.
"""

from __future__ import annotations

from typing import Sequence

__all__ = ["dp_column_ops", "dp_sweep_work", "lm_forward_flops"]


def dp_column_ops(n: int, slots: Sequence[tuple]) -> int:
    """Element updates of the incremental burst column over tasks 1..n.

    ``slots`` holds one ``(j, lt, writer, linf)`` per read slot: task j
    reads a packet last touched by task lt, written by ``writer`` and last
    used by ``linf``. Per column j the live bursts <i, j-1> (i < j) take one
    add each; a read slot adds its load to bursts starting after lt, and a
    packet whose last use is j frees its store for bursts starting at or
    before its writer.
    """
    ops = n * (n - 1) // 2
    for j, lt, writer, linf in slots:
        ops += max(j - 1 - lt, 0)
        if linf == j and writer >= 1:
            ops += writer
    return ops


def dp_sweep_work(n: int, nnz_reads: int, column_ops: int, lanes: int,
                  ops_per_candidate: int) -> tuple:
    """(operations, bytes) of one column sweep with a DP over ``lanes``.

    Every candidate burst <i, j> (i <= j) is combined once per lane:
    ``ops_per_candidate`` is 3 for the sum DP (add, budget test, running
    min) and 2 for minimax (max, running min). Bytes are the least traffic:
    the slot arrays and per-task scalars read once (4-byte words), and the
    two (n, lanes) result tables (value and argmin) written once.
    """
    candidates = n * (n + 1) // 2
    ops = column_ops + candidates * lanes * ops_per_candidate
    bytes_in = 4 * ((n + 1) + 2 * n + 5 * nnz_reads)
    bytes_out = 2 * 4 * n * lanes
    return ops, bytes_in + bytes_out


def lm_forward_flops(cfg: dict, n_tokens: int, context: int,
                     with_head: bool) -> int:
    """Model FLOPs of ``n_tokens`` positions that each attend to
    ``context`` keys on average (2 FLOPs per multiply-add).

    Per layer: the q/k/v/o projections, the gated MLP and the two attention
    contractions (scores and weighted values) over the context. The tied
    output head counts only where logits are needed.
    """
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    ff = cfg["intermediate_size"]
    per_layer = 2 * (d * q + 2 * d * kv + q * d + 3 * d * ff)
    attn = 2 * 2 * context * q
    flops = n_tokens * cfg["num_hidden_layers"] * (per_layer + attn)
    if with_head:
        flops += n_tokens * 2 * d * cfg["vocab_size"]
    return flops
