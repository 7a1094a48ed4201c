"""Operations and bytes a dense GQA decoder needs, from its published sizes.

Counted at true token counts, never at the program's padded shapes, so
the same work reads the same whatever implements it. A multiply-add is 2
operations. Attention is causal: a query at position i attends to i + 1
keys. Elementwise work (norms, RoPE, softmax, biases) is not counted.
Logits are computed for the last prompt position in prefill and for
every token in decode.
"""
from __future__ import annotations

from typing import Iterable

from yardstick.model import Dims

BF16 = 2


def layer_matmul_params(d: Dims) -> int:
    """Weights of one layer's matrix products (q, k, v, o and the MLP)."""
    attn = d.d_model * d.head_dim * (2 * d.heads + 2 * d.kv_heads)
    return attn + 3 * d.d_model * d.d_ff


def layer_params(d: Dims) -> int:
    """Every weight of one layer: products, norms, biases."""
    n = layer_matmul_params(d) + 2 * d.d_model
    if d.qkv_bias:
        n += d.head_dim * (d.heads + 2 * d.kv_heads)
    if d.qk_norm:
        n += 2 * d.head_dim
    return n


def head_flops(d: Dims) -> int:
    return 2 * d.d_model * d.vocab


def prefill_flops(d: Dims, isl: int) -> int:
    """One prompt of ``isl`` tokens, first token's logits included."""
    pairs = isl * (isl + 1) // 2
    per_layer = (2 * isl * layer_matmul_params(d)
                 + 4 * d.heads * d.head_dim * pairs)
    return d.layers * per_layer + head_flops(d)


def decode_flops(d: Dims, context: int) -> int:
    """One decoded token that attends to ``context`` positions (itself
    included)."""
    per_layer = 2 * layer_matmul_params(d) + 4 * d.heads * d.head_dim * context
    return d.layers * per_layer + head_flops(d)


def weight_bytes_per_step(d: Dims) -> int:
    """Weights a decode step reads: every layer and the output head (the
    embedding is read only at the batch's rows, counted per token)."""
    return (d.layers * layer_params(d) + d.d_model * d.vocab
            + d.d_model) * BF16


def decode_step_bytes(d: Dims, contexts: Iterable[int]) -> int:
    """Least bytes one decode step moves: its weights, the live KV of
    every request at its true length, each request's embedding row, and
    each new token's K and V written back."""
    contexts = list(contexts)
    kv = d.kv_bytes_per_token
    return (weight_bytes_per_step(d) + sum(contexts) * kv
            + len(contexts) * (d.d_model * BF16 + kv))
