"""Arithmetic of a decoder whose mixers are double-gated short convolutions
(LFM2: ``C (taps * (B z))`` between two projections) among grouped-query
softmax layers, over a dense SwiGLU in its leading layers and routed SwiGLU
experts, of which one chip holds a share and none is shared, in the others --
from shapes alone and by ``benchmark/arithmetic.py``'s rules: a multiply-add
is two operations, training is the forward pass once and the backward pass
twice, and what a program repeats to save memory is not counted.

The gated filter's own work is elementwise: the vector unit's operations a
token and a channel, and the bytes of the tensors the ALGORITHM has to move
(B, C and z in, y out; backward those and y's cotangent in, three cotangents
out), which is what bounds it.  The count is from shapes: a share built on it
reads the same whatever runs the pass, a Mosaic call or XLA's fusions.  A
norm, a rotation and the gates of the router are not counted, as a norm is
not in ``decoder_train_flops_per_token``.
"""

from __future__ import annotations

from benchmark import arithmetic, arithmetic_window


def conv_mixer_matmul_params(*, hidden: int) -> int:
    """``in_proj [H, 3 H]`` and ``out_proj [H, H]``."""
    return 4 * hidden * hidden


def gated_conv_flops(*, batch: int, seq: int, channels: int,
                     taps: int) -> float:
    """Operations of one layer's gate, filter and gate in one training step.
    Forward a token and a channel: ``B z`` (1), K multiplies and K - 1 adds,
    ``C c`` (1).  Backward: ``B z`` and the filter again (2 K), ``dc = g C``
    and ``dC = g c`` (2), the transposed filter (2 K - 1), ``dB`` and ``dz``
    (2), and a multiply-add a tap for the filter's own gradient (2 K)."""
    forward = 2 * taps + 1
    backward = 6 * taps + 3
    return float(batch * seq * channels * (forward + backward))


def gated_conv_bytes(*, batch: int, seq: int, channels: int,
                     itemsize: int = 2) -> dict:
    """Bytes one layer's gated filter must move through HBM in one step, by
    pass: forward B, C and z read and y written; backward B, C, z and y's
    cotangent read and the three cotangents written.  The taps and their
    gradient are a few kilobytes and are not counted."""
    tensor = batch * seq * channels * itemsize
    return {"forward": 4.0 * tensor, "backward": 7.0 * tensor}


def train_flops_per_token(*, hidden: int, conv_layers: int,
                          attention_layers: int, dense_layers: int,
                          routed_layers: int, heads: int, kv_heads: int,
                          head_dim: int, dense_ffn: int, expert_ffn: int,
                          experts: int, held: int, per_token: int,
                          vocab: int, seq: int, taps: int) -> float:
    """Forward + backward operations per token: every matrix a token is
    multiplied with in each layer's mixer and feed-forward and in the (tied)
    head, causal softmax attention in the attention layers, and the gated
    filters' elementwise work in the conv layers."""
    weights = (
        conv_layers * conv_mixer_matmul_params(hidden=hidden)
        + attention_layers * arithmetic_window.mixer_matmul_params(
            hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
            gated=False)
        + dense_layers * 3 * hidden * dense_ffn
        # (The router over all ``experts`` and the held experts' three
        # matrices at the share of a token's choices that lands on them.)
        + routed_layers * arithmetic_window.routed_params_a_token(
            hidden=hidden, expert_ffn=expert_ffn, shared_ffn=0,
            experts=experts, held=held, per_token=per_token)
        + hidden * vocab)
    # QK^T and PV: two products of head_dim multiply-adds per kept pair.
    attention = attention_layers * 2 * 2 * heads * head_dim * (
        arithmetic.causal_pairs(seq) / seq)
    filters = conv_layers * gated_conv_flops(
        batch=1, seq=seq, channels=hidden, taps=taps) / seq
    return 3.0 * (2 * weights + attention) + filters
