"""Arithmetic of a decoder with learned sparse attention over grouped-query
heads and routed experts of which one chip holds a share, from shapes alone
and by ``benchmark/arithmetic.py``'s rules: a multiply-add is two
operations, training is the forward pass once and the backward pass twice,
and what a program repeats to save memory is not counted.

What is counted is what the ALGORITHM needs, not what a program executes:
attention's products run over the pairs the selection keeps, ``sum_t
min(t + 1, topk)`` a sequence, so a call that executes every causal pair
reads at most that share of its roofline (44 % at S = 8192, topk = 2048);
the indexer has to score every causal pair once to select among them, and
its loss needs the kept pairs alone.  K and V are as wide as the
key-value heads (grouped-query attention reads them where they are).
"""

from __future__ import annotations

from benchmark import arithmetic
from benchmark.arithmetic_moe import expected_assignments


def selected_pairs(seq: int, topk: int) -> int:
    """Query-key pairs a sequence keeps: query t takes min(t + 1, topk)."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def attention_matmul_params(*, hidden: int, heads: int, kv_heads: int,
                            head_dim: int) -> int:
    return hidden * head_dim * (2 * heads + 2 * kv_heads)


def indexer_matmul_params(*, hidden: int, index_heads: int,
                          index_dim: int) -> int:
    """W_Iq, W_Ik and W_Iw."""
    return hidden * (index_heads * index_dim + index_dim + index_heads)


def index_loss_flops_per_pair(*, heads: int, head_dim: int,
                              index_heads: int, index_dim: int) -> int:
    """The indexer's loss and its gradient on one kept pair: the index
    score again, its two gradient products (dq_I, dk_I), and the attention
    heads' q k^T for the target."""
    return 2 * (3 * index_heads * index_dim + heads * head_dim)


def sparse_moe_train_flops_per_token(
        *, hidden: int, layers: int, heads: int, kv_heads: int,
        head_dim: int, index_heads: int, index_dim: int, topk: int,
        expert_ffn: int, experts: int, held: int, per_token: int,
        vocab: int, seq: int) -> float:
    """Forward + backward operations per token.  The indexer's projections
    have a weight gradient and no input gradient (their input carries
    none), so their backward pass is one product, not two; its scores over
    every causal pair are made once; its loss with its gradient is
    ``index_loss_flops_per_pair`` a kept pair."""
    kept = selected_pairs(seq, topk) / seq
    weights = (layers * (attention_matmul_params(
        hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim)
        + hidden * experts
        + expected_assignments(per_token=per_token, held=held,
                               experts=experts) * 3 * hidden * expert_ffn)
        + hidden * vocab)
    indexer = layers * indexer_matmul_params(
        hidden=hidden, index_heads=index_heads, index_dim=index_dim)
    attention = layers * heads * 2 * 2 * head_dim * kept
    scoring = layers * 2 * index_heads * index_dim * (
        arithmetic.causal_pairs(seq) / seq)
    index_loss = layers * kept * index_loss_flops_per_pair(
        heads=heads, head_dim=head_dim, index_heads=index_heads,
        index_dim=index_dim)
    return (3.0 * (2 * weights + attention) + 2.0 * 2 * indexer + scoring
            + index_loss)


def _kept(batch: int, seq: int, topk: int) -> int:
    return batch * selected_pairs(seq, topk)


def flash_forward_flops(*, batch, seq, heads, head_dim, topk, **_) -> float:
    return 2 * 2 * head_dim * heads * _kept(batch, seq, topk)


def flash_backward_flops(*, batch, seq, heads, head_dim, topk, **_) -> float:
    return 5 * 2 * head_dim * heads * _kept(batch, seq, topk)


def flash_forward_bytes(*, batch, seq, heads, kv_heads, head_dim,
                        itemsize: int = 2, **_) -> float:
    """q read and o written at the query heads, k and v read at the
    key-value heads."""
    return batch * seq * head_dim * itemsize * (2 * heads + 2 * kv_heads)


def flash_backward_bytes(*, batch, seq, heads, kv_heads, head_dim,
                         itemsize: int = 2, **_) -> float:
    """q, o, dO read and dq written at the query heads; k, v read and dk,
    dv written at the key-value heads."""
    return batch * seq * head_dim * itemsize * (4 * heads + 4 * kv_heads)


def select_flops(*, batch, seq, index_heads, index_dim, **_) -> float:
    """The index scores of every causal pair; the selection itself
    compares and counts, which is no operation of the MXU's."""
    return 2 * index_heads * index_dim * batch * arithmetic.causal_pairs(seq)


def select_bytes(*, batch, seq, index_heads, index_dim, itemsize: int = 2,
                 **_) -> float:
    """q_I, k_I and w read; the selection written, a byte a pair."""
    return batch * seq * (itemsize * (index_heads + 1) * index_dim
                          + 4 * index_heads + seq)


def index_loss_flops(*, batch, seq, heads, head_dim, index_heads, index_dim,
                     topk, **_) -> float:
    return _kept(batch, seq, topk) * index_loss_flops_per_pair(
        heads=heads, head_dim=head_dim, index_heads=index_heads,
        index_dim=index_dim)


def index_loss_bytes(*, batch, seq, heads, kv_heads, head_dim, index_heads,
                     index_dim, itemsize: int = 2, **_) -> float:
    """q, k, the flash call's lse, q_I, k_I, w and the selection read;
    dq_I, dk_I and dw written."""
    return batch * seq * (
        itemsize * (heads + kv_heads) * head_dim + 4 * heads
        + 2 * (itemsize * (index_heads + 1) * index_dim + 4 * index_heads)
        + seq)
