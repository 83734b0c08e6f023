"""Arithmetic of a decoder with latent attention (MLA) and routed experts
of which one chip holds a share, from shapes alone and by
``benchmark/arithmetic.py``'s rules: a multiply-add is two operations,
training is the forward pass once and the backward pass twice, and what a
program repeats to save memory is not counted.

The flash kernel's keys are ``d_qk`` wide and its values ``d_v``: a kept
query-key pair costs the forward pass one product of each width (q k^T,
p v) and the backward pass three of ``d_qk`` (q k^T again, dq = ds k,
dk = ds^T q) and two of ``d_v`` (dp = dO v^T, dv = p^T dO); q, k, dq and dk
are ``d_qk`` wide and v, o, dO and dv ``d_v`` wide -- whatever width a
program pads them to.

The routed layer is counted at the rows its held experts expect: a token
sends ``per_token * held / experts`` of its choices here.
"""

from __future__ import annotations

from benchmark import arithmetic


def mla_matmul_params(*, hidden: int, heads: int, qk_nope: int, qk_rope: int,
                      v_dim: int, kv_rank: int) -> int:
    """Weights a token is multiplied with in one latent-attention block:
    W_q, the joint down-projection W_kva (latent and the one rotary key),
    the up-projection W_kvb and W_o."""
    return (hidden * heads * (qk_nope + qk_rope)
            + hidden * (kv_rank + qk_rope)
            + kv_rank * heads * (qk_nope + v_dim)
            + heads * v_dim * hidden)


def expected_assignments(*, per_token: int, held: int, experts: int) -> float:
    """Choices of one token that land on the held experts, on average
    under a uniform router."""
    return per_token * held / experts


def attention_flops_per_token(*, heads: int, qk_dim: int, v_dim: int,
                              seq: int) -> float:
    """Forward operations of causal attention a token and layer: the mean
    query keeps ``(seq + 1) / 2`` keys."""
    return heads * 2 * (qk_dim + v_dim) * arithmetic.causal_pairs(seq) / seq


def moe_decoder_train_flops_per_token(
        *, hidden: int, layers: int, dense_layers: int, heads: int,
        qk_nope: int, qk_rope: int, v_dim: int, kv_rank: int,
        dense_ffn: int, expert_ffn: int, shared: int, experts: int,
        held: int, per_token: int, vocab: int, seq: int) -> float:
    """Forward + backward operations per token: every layer's latent
    attention, the leading layers' dense FFN, the routed layers' router
    (all ``experts`` wide), shared experts and held experts at their
    expected rows, and the head over the vocabulary held."""
    attention = mla_matmul_params(hidden=hidden, heads=heads,
                                  qk_nope=qk_nope, qk_rope=qk_rope,
                                  v_dim=v_dim, kv_rank=kv_rank)
    routed = (hidden * experts + 3 * hidden * shared * expert_ffn
              + expected_assignments(per_token=per_token, held=held,
                                     experts=experts)
              * 3 * hidden * expert_ffn)
    weights = (layers * attention + dense_layers * 3 * hidden * dense_ffn
               + (layers - dense_layers) * routed + hidden * vocab)
    scores = layers * attention_flops_per_token(
        heads=heads, qk_dim=qk_nope + qk_rope, v_dim=v_dim, seq=seq)
    return 3.0 * (2 * weights + scores)


def _pairs(batch: int, seq: int, heads: int) -> int:
    return batch * heads * arithmetic.causal_pairs(seq)


def flash_forward_flops(*, batch: int, seq: int, heads: int, qk_dim: int,
                        v_dim: int) -> float:
    return 2 * (qk_dim + v_dim) * _pairs(batch, seq, heads)


def flash_backward_flops(*, batch: int, seq: int, heads: int, qk_dim: int,
                         v_dim: int) -> float:
    return 2 * (3 * qk_dim + 2 * v_dim) * _pairs(batch, seq, heads)


def flash_forward_bytes(*, batch: int, seq: int, heads: int, qk_dim: int,
                        v_dim: int, itemsize: int = 2) -> float:
    """q, k read and v read, o written."""
    return batch * seq * heads * itemsize * (2 * qk_dim + 2 * v_dim)


def flash_backward_bytes(*, batch: int, seq: int, heads: int, qk_dim: int,
                         v_dim: int, itemsize: int = 2) -> float:
    """q, k, v, o, dO read; dq, dk, dv written."""
    return batch * seq * heads * itemsize * (4 * qk_dim + 4 * v_dim)


def expert_rows(*, tokens: int, per_token: int, held: int,
                experts: int) -> float:
    """Rows the held experts of one layer are sent in a step."""
    return tokens * expected_assignments(per_token=per_token, held=held,
                                         experts=experts)


def expert_products_flops(*, rows: float, hidden: int,
                          expert_ffn: int) -> float:
    """One routed layer's grouped products in a step: gate-up ``[H, 2F]``
    and down ``[F, H]`` over ``rows`` rows, forward and their two gradient
    products each."""
    return 3 * 2 * rows * (hidden * 2 * expert_ffn + expert_ffn * hidden)


def expert_products_bytes(*, rows: float, held: int, hidden: int,
                          expert_ffn: int, itemsize: int = 2) -> float:
    """Each of the six products touches its matrix over the held experts
    once (read, or written as the weight's gradient) and the rows on both
    of its sides once."""
    weights = held * (hidden * 2 * expert_ffn + expert_ffn * hidden)
    sides = rows * ((hidden + 2 * expert_ffn) + (expert_ffn + hidden))
    return 3 * itemsize * (weights + sides)
