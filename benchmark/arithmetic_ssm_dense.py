"""Arithmetic of a decoder whose every layer is a mixer AND a dense SwiGLU
(Granite-4.0-H: a Mamba-2 state-space mixer, Dao & Gu, arXiv:2405.21060, or
grouped-query softmax attention, each behind a norm of its own), under a
head tied to the embedding -- from shapes alone and by
``benchmark/arithmetic.py``'s rules: a multiply-add is two operations,
training is the forward pass once and the backward pass twice, and what a
program repeats to save memory is not counted.

The chunked scan's own work is ``benchmark/arithmetic_ssd.py``'s, which
takes the chunk and the group count as arguments (here the published
``mamba_chunk_size`` 256 and ONE group, so ``C B^T`` once for all heads).

What a Mamba-2 layer does between its scan and ``out_proj`` -- the skip ``y
+ D v``, the gate ``silu(z)`` and the RMS norm behind it -- is elementwise:
the vector unit's operations a token and a channel, and the bytes of the
tensors the ALGORITHM has to move (y, v and z in, the result out; backward
those and the result's cotangent in, three cotangents out), which is what
bounds it.  The count is from shapes: a share built on it reads the same
whatever runs the pass, a Mosaic call or XLA's fusions.  A block norm, the
four multipliers, the filter and the decays are not counted, as a norm is
not in ``decoder_train_flops_per_token``.
"""

from __future__ import annotations

from benchmark import arithmetic, arithmetic_ssd, arithmetic_window

# The vector unit's operations an element, by hand: forward the skip (2), the
# sigmoid and ``z sigma`` (4), the gate (1), the square and its sum (2), the
# root's scale and the weight (2); backward all of that again (the pass keeps
# its inputs alone) and ``dn``, its product with n and the sum (3), ``dg``
# (3), ``dt`` and ``du`` (2), ``dz`` (5), and the two partial sums (4).
GATES_FORWARD_OPS = 11
GATES_BACKWARD_OPS = 11 + 17


def gates_flops(*, batch: int, seq: int, channels: int) -> float:
    """Operations of one layer's skip, gate and norm in one training step."""
    return float(batch * seq * channels
                 * (GATES_FORWARD_OPS + GATES_BACKWARD_OPS))


def gates_bytes(*, batch: int, seq: int, channels: int,
                itemsize: int = 2) -> dict:
    """Bytes one layer's skip, gate and norm must move through HBM in one
    step, by pass: forward y, v and z read and the result written; backward
    y, v, z and the result's cotangent read and the three cotangents written.
    D, the norm's weight and their gradients are a few kilobytes and are not
    counted."""
    tensor = batch * seq * channels * itemsize
    return {"forward": 4.0 * tensor, "backward": 7.0 * tensor}


def layer_matmul_params(*, hidden: int, heads: int, kv_heads: int,
                        head_dim: int, mamba_heads: int, mamba_head_dim: int,
                        groups: int, state: int, ffn: int) -> dict:
    """The matrices a token is multiplied with in a layer of each kind: the
    mixer's (``W_in`` and ``W_out``, or q, k, v and o) and the SwiGLU's
    three."""
    mlp = 3 * hidden * ffn
    return {
        "mamba": mlp + arithmetic_ssd.mamba_matmul_params(
            hidden=hidden, heads=mamba_heads, head_dim=mamba_head_dim,
            groups=groups, state=state),
        "attention": mlp + arithmetic_window.mixer_matmul_params(
            hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
            gated=False)}


def train_flops_per_token(*, hidden: int, mamba_layers: int,
                          attention_layers: int, heads: int, kv_heads: int,
                          head_dim: int, mamba_heads: int,
                          mamba_head_dim: int, groups: int, state: int,
                          ffn: int, vocab: int, seq: int,
                          chunk: int) -> float:
    """Forward + backward operations per token: every matrix a token is
    multiplied with in each layer's mixer and SwiGLU and in the (tied) head,
    causal softmax attention in the attention layers and the chunked scan in
    the Mamba ones."""
    a_layer = layer_matmul_params(
        hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        mamba_heads=mamba_heads, mamba_head_dim=mamba_head_dim,
        groups=groups, state=state, ffn=ffn)
    weights = (mamba_layers * a_layer["mamba"]
               + attention_layers * a_layer["attention"] + hidden * vocab)
    # QK^T and PV: two products of head_dim multiply-adds per kept pair.
    attention = attention_layers * 2 * 2 * heads * head_dim * (
        arithmetic.causal_pairs(seq) / seq)
    scan = mamba_layers * arithmetic_ssd.scan_flops(
        batch=1, seq=seq, heads=mamba_heads, groups=groups,
        head_dim=mamba_head_dim, state=state, chunk=chunk) / seq
    return 3.0 * (2 * weights + attention) + scan
