"""Operations and bytes an algorithm needs, from its shapes.

Copies of the program's arithmetic (`bench.py::gpt_train_flops_per_token`,
`tfde_tpu/ops/roofline.py::{mean_attended_keys, attention_flops_per_token,
stacked_attention_flops_per_token}`), kept here so that no later change to
the program can move the yardstick; a test pins them to the originals.
Causal attention is credited at the exact (S+1)/2 mean attended keys;
training is 3 x forward; recomputed operations do not count.
"""

from __future__ import annotations


def mean_attended_keys(seq: int, causal: bool = True) -> float:
    """Mean number of keys a query attends: S, or (S+1)/2 under a causal
    mask (query i sees i+1 keys)."""
    return (seq + 1) / 2.0 if causal else float(seq)


def attention_flops_per_token(attn_width: int, seq: int,
                              causal: bool = True) -> float:
    """Forward attention-matmul FLOPs per token for one layer: per (query,
    key) pair 2*head_dim in the scores and 2*head_dim in the values."""
    return 4.0 * attn_width * mean_attended_keys(seq, causal)


def gpt_forward_flops_per_token(hidden: int, mlp: int, depth: int, seq: int,
                                vocab: int) -> float:
    """Matmul FLOPs per token of one causal forward: q, k, v, o (8 H^2),
    the MLP (4 H M), attention, and the tied head (2 H V)."""
    per_layer = 8 * hidden * hidden + 4 * hidden * mlp
    attn = depth * attention_flops_per_token(hidden, seq, causal=True)
    return depth * per_layer + attn + 2 * hidden * vocab


def gpt_train_flops_per_token(hidden: int, mlp: int, depth: int, seq: int,
                              vocab: int) -> float:
    """One forward and backward step: 3 x forward."""
    return 3.0 * gpt_forward_flops_per_token(hidden, mlp, depth, seq, vocab)


def flash_forward_flops(batch: int, heads: int, seq: int, head_dim: int,
                        causal: bool = True) -> float:
    """FLOPs of one causal flash-attention forward call over
    [batch, heads, seq, head_dim]."""
    return batch * seq * attention_flops_per_token(
        heads * head_dim, seq, causal)


def flash_forward_bytes(batch: int, heads: int, seq: int, head_dim: int,
                        itemsize: int = 2) -> float:
    """Least bytes one flash forward moves: q, k, v read and o written
    once (the log-sum-exp row is 1/head_dim of that and is left out)."""
    return 4.0 * batch * heads * seq * head_dim * itemsize
