"""Operations the forward and backward passes require, from shapes.

Copied from ``tpudp/utils/flops.py`` (matmul operations only, 2 x
multiply-accumulates, backward = 2 x forward) so that no later
PR can move the yardstick by editing the program.  One departure, on
purpose: causal attention is counted at the half of the score and value
products that a causal model requires, where the original counts the
full square."""

from __future__ import annotations


def dense_flops(rows: int, d_in: int, d_out: int) -> int:
    return 2 * rows * d_in * d_out


def gpt2_fwd_flops(batch: int, seq_len: int, *, num_layers: int,
                   d_model: int, vocab_size: int, mlp_ratio: int = 4) -> int:
    tokens = batch * seq_len
    per_layer = dense_flops(tokens, d_model, 3 * d_model)      # qkv
    per_layer += dense_flops(tokens, d_model, d_model)         # out proj
    per_layer += 2 * dense_flops(tokens, d_model, mlp_ratio * d_model)
    # QK^T and AV, causal: each query sees (t + 1) / 2 keys on average
    per_layer += 2 * 2 * batch * seq_len * (seq_len + 1) // 2 * d_model
    return num_layers * per_layer + dense_flops(tokens, d_model, vocab_size)


def train_step_flops(fwd_flops: int) -> int:
    return 3 * fwd_flops
