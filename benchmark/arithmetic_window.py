"""Arithmetic of a decoder whose softmax layers attend to all their causal
keys or through a sliding window, a layer at a time and each kind at its own
count of query heads over shared key-value heads, with a per-head output
gate and routed experts of which one chip holds a share; from shapes alone
and by ``benchmark/arithmetic.py``'s rules: a multiply-add is two
operations, training is the forward pass once and the backward pass twice,
and what a program repeats to save memory is not counted.

A sliding layer is counted over its BAND alone: query t keeps the keys
``0 <= t - s < window``, ``seq * window - window (window - 1) / 2`` pairs a
head (4,063,488 at 8192 / 512, where the causal mask keeps 33,558,528).
That is the algorithm's count, the same whatever blocks a kernel walks (at
512-row blocks the flash calls execute 31 block pairs a head, 8,126,464
query-key pairs, of which half are masked), so a share built on it cannot
pass 100 %, and executing more than the band lowers it.

The attention's tensors are counted at their own widths: q, o, dO and dq at
the layer's query heads, k, v, dk and dv at the key-value heads (a group's
query heads share them).
"""

from __future__ import annotations

from benchmark import arithmetic, arithmetic_moe


def band_pairs(seq: int, window: int | None) -> int:
    """Query-key pairs one head of one sequence keeps: the causal ones, or
    with ``window`` those fewer than ``window`` positions apart."""
    if window is None or window >= seq:
        return arithmetic.causal_pairs(seq)
    return seq * window - window * (window - 1) // 2


def mixer_matmul_params(*, hidden: int, heads: int, kv_heads: int,
                        head_dim: int, gated: bool) -> int:
    """W_q and W_o at the layer's query heads, W_k and W_v at the key-value
    heads, and the gate's ``[hidden, heads]``."""
    return (hidden * head_dim * (2 * heads + 2 * kv_heads)
            + (hidden * heads if gated else 0))


def routed_params_a_token(*, hidden: int, expert_ffn: int, shared_ffn: int,
                          experts: int, held: int, per_token: int) -> float:
    """Weights a token is multiplied with in a routed layer's feed-forward:
    the router over all ``experts``, the shared expert, and the held
    experts at the share of its choices that lands on them."""
    return (hidden * experts + 3 * hidden * shared_ffn
            + arithmetic_moe.expected_assignments(
                per_token=per_token, held=held, experts=experts)
            * 3 * hidden * expert_ffn)


def train_flops_per_token(*, hidden: int, heads_by_layer, windows_by_layer,
                          kv_heads: int, head_dim: int, gated: bool,
                          dense_layers: int, dense_ffn: int,
                          expert_ffn: int, shared_ffn: int, experts: int,
                          held: int, per_token: int, vocab: int,
                          seq: int) -> float:
    """Forward + backward operations per token: every layer's projections
    and gate at its own head count, its scores over its own pairs (the
    band in a windowed layer), the leading layers' dense FFN, the routed
    layers at their expected rows, and the head over the vocabulary held."""
    layers = len(heads_by_layer)
    weights = sum(mixer_matmul_params(
        hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        gated=gated) for heads in heads_by_layer)
    weights += dense_layers * 3 * hidden * dense_ffn
    weights += (layers - dense_layers) * routed_params_a_token(
        hidden=hidden, expert_ffn=expert_ffn, shared_ffn=shared_ffn,
        experts=experts, held=held, per_token=per_token)
    weights += hidden * vocab
    # QK^T and PV: two products of head_dim multiply-adds per kept pair.
    scores = sum(2 * 2 * heads * head_dim * band_pairs(seq, window) / seq
                 for heads, window in zip(heads_by_layer, windows_by_layer))
    return 3.0 * (2 * weights + scores)


def attention_flops(products: int, *, batch: int, seq: int, heads: int,
                    head_dim: int, window: int | None) -> float:
    """``products`` of ``head_dim`` multiply-adds a kept pair, one layer."""
    return (products * 2 * head_dim * batch * heads
            * band_pairs(seq, window))


def attention_bytes(tensors: int, *, batch: int, seq: int, heads: int,
                    kv_heads: int, head_dim: int, itemsize: int = 2
                    ) -> float:
    """``tensors`` at the query heads' width and as many at the key-value
    heads': forward 2 (q read, o written) and 2 (k, v read); backward 4 (q,
    o, dO read, dq written) and 4 (k, v read, dk, dv written)."""
    return tensors * batch * seq * (heads + kv_heads) * head_dim * itemsize


def attention_work(*, batch: int, seq: int, heads: int, kv_heads: int,
                   head_dim: int, window: int | None) -> dict:
    """One layer's attention in one training step, forward (2 products a
    kept pair) and backward (5: q k^T again, dp, dv, dq, dk), as
    ``kernel_work_per_step`` nests it."""
    shape = dict(batch=batch, seq=seq, heads=heads, head_dim=head_dim,
                 window=window)
    sizes = dict(batch=batch, seq=seq, heads=heads, kv_heads=kv_heads,
                 head_dim=head_dim)
    forward = {"flops": attention_flops(2, **shape),
               "bytes": attention_bytes(2, **sizes)}
    backward = {"flops": attention_flops(5, **shape),
                "bytes": attention_bytes(4, **sizes)}
    return {"flops": forward["flops"] + backward["flops"],
            "bytes": forward["bytes"] + backward["bytes"],
            "forward": forward, "backward": backward}
