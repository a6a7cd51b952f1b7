"""Operations and bytes that the work needs, counted from shapes.

These are counts of what the algorithm needs, not of what an
implementation happens to execute: a change that removes work then shows
as a larger share of the peak, never as a smaller one.
"""
from __future__ import annotations


def lm_forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward FLOPs per token of a hybrid Mamba2/attention language model
    (``cfg`` as in ``bench/configs/zamba2-*.json``), 2 per multiply-add.

    Counted: every projection; the Mamba2 state recurrence in its
    recurrent form (state update and read-out, 2 multiply-adds per state
    entry per head); causal attention over the ``seq_len`` positions (a
    token attends to (S+1)/2 keys on average); the output head. Not
    counted: the embedding lookup (no FLOPs), norms, activations and
    other elementwise work, and recomputation from rematerialisation.
    """
    D, V = cfg["d_model"], cfg["vocab_size"]
    flops = 2.0 * D * V  # output head
    for kind in cfg["block_pattern"]:
        if kind == "mamba":
            m = cfg["mamba"]
            d_inner = m["expand"] * D
            heads = d_inner // m["head_dim"]
            n = m["state_dim"]
            flops += 2.0 * D * (2 * d_inner + 2 * n + heads)  # in_proj
            flops += 2.0 * m["conv_width"] * (d_inner + 2 * n)  # depthwise conv
            flops += 2.0 * 2 * n * m["head_dim"] * heads        # state update + read
            flops += 2.0 * d_inner * D                           # out_proj
        elif kind in ("shared", "attn"):
            q_dim = cfg["num_heads"] * cfg["head_dim"]
            kv_dim = cfg["num_kv_heads"] * cfg["head_dim"]
            flops += 2.0 * D * (q_dim + 2 * kv_dim) + 2.0 * q_dim * D
            flops += 2.0 * 2 * q_dim * (seq_len + 1) / 2.0      # QK^T and PV
            flops += 2.0 * 3 * D * cfg["d_ff"]                   # gated MLP
        else:
            raise ValueError(f"no FLOP count for block kind {kind!r}")
    return flops


def lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward: the backward pass needs twice the forward."""
    return 3.0 * lm_forward_flops_per_token(cfg, seq_len)
