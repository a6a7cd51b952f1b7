"""Operations and bytes that the work needs, counted from shapes.

These are counts of what the algorithm needs, not of what an
implementation happens to execute: a change that removes work then shows
as a larger share of the peak, never as a smaller one.

A configuration's forward FLOPs a token are summed by its reference module
(``forward_flops_per_token`` in ``bench/configs/<model>_reference.py``)
from the pieces below, per token and 2 per multiply-add. Not counted
anywhere: the embedding lookup (no FLOPs), norms, activations and other
elementwise work, and recomputation from rematerialisation.
"""
from __future__ import annotations


def mamba2_layer_flops(d_model: int, state_dim: int, head_dim: int, expand: int,
                       conv_width: int) -> float:
    """One Mamba2 layer: every projection, the depthwise convolution, and
    the state recurrence in its recurrent form (state update and read-out,
    2 multiply-adds per state entry per head)."""
    d_inner = expand * d_model
    heads = d_inner // head_dim
    n = state_dim
    flops = 2.0 * d_model * (2 * d_inner + 2 * n + heads)  # in_proj
    flops += 2.0 * conv_width * (d_inner + 2 * n)          # depthwise conv
    flops += 2.0 * 2 * n * head_dim * heads                # state update + read
    flops += 2.0 * d_inner * d_model                       # out_proj
    return flops


def attention_flops(d_model: int, num_heads: int, num_kv_heads: int, head_dim: int,
                    seq_len: int) -> float:
    """One causal attention block: the q, k, v and output projections, and
    QK^T and PV over the ``seq_len`` positions (a token attends to
    (S+1)/2 keys on average)."""
    q_dim = num_heads * head_dim
    kv_dim = num_kv_heads * head_dim
    flops = 2.0 * d_model * (q_dim + 2 * kv_dim) + 2.0 * q_dim * d_model
    flops += 2.0 * 2 * q_dim * (seq_len + 1) / 2.0  # QK^T and PV
    return flops


def gated_mlp_flops(d_model: int, d_ff: int) -> float:
    """One gated MLP: the gate, input and output projections."""
    return 2.0 * 3 * d_model * d_ff


def output_head_flops(d_model: int, vocab_size: int) -> float:
    """The output head's projection onto the vocabulary."""
    return 2.0 * d_model * vocab_size


def train_flops_per_token(forward: float) -> float:
    """Forward and backward: the backward pass needs twice the forward."""
    return 3.0 * forward
