"""Operations and bytes the algorithms need, computed from shapes alone.

The yardstick's arithmetic: no JAX, no device, nothing read from the
program.  A multiply-add counts as two operations.  Training counts the
forward pass once and the backward pass twice (one product for the input's
gradient, one for the weight's); work a program repeats to save memory is
not counted, so a utilisation built on these numbers cannot be raised by
recomputing.
"""

from __future__ import annotations


def causal_pairs(seq: int) -> int:
    """Query-key pairs a causal mask keeps in one head of one sequence."""
    return seq * (seq + 1) // 2


def decoder_layer_matmul_params(hidden: int, heads: int, kv_heads: int,
                                head_dim: int, ffn: int) -> int:
    """Weights of one pre-norm decoder layer that a token is multiplied
    with: q, k, v, o projections and the gated FFN (gate, up, down)."""
    attn = hidden * head_dim * (2 * heads + 2 * kv_heads)
    return attn + 3 * hidden * ffn


def decoder_train_flops_per_token(*, hidden: int, layers: int, heads: int,
                                  kv_heads: int, head_dim: int, ffn: int,
                                  vocab: int, seq: int) -> float:
    """Forward + backward operations per token of a dense decoder with an
    untied head, attention counted causal (the mean query sees
    ``(seq + 1) / 2`` keys).  The embedding is a lookup: no operations."""
    per_layer = decoder_layer_matmul_params(hidden, heads, kv_heads,
                                            head_dim, ffn)
    matmul = 2 * (layers * per_layer + hidden * vocab)
    # QK^T and PV: two products of head_dim multiply-adds per kept pair.
    attention = layers * 2 * 2 * heads * head_dim * causal_pairs(seq) / seq
    return 3.0 * (matmul + attention)


def _flash_flops(products: int, *, batch: int, seq: int, heads: int,
                 head_dim: int) -> float:
    """``products`` of ``head_dim`` multiply-adds a kept query-key pair."""
    return products * 2 * head_dim * batch * heads * causal_pairs(seq)


def _flash_bytes(tensors: int, *, batch: int, seq: int, heads: int,
                 head_dim: int, itemsize: int = 2) -> float:
    """``tensors`` whole ``[batch, seq, heads, head_dim]`` arrays."""
    return tensors * batch * seq * heads * head_dim * itemsize


def flash_forward_flops(**shape) -> float:
    """Operations the forward pass of causal flash attention needs for one
    layer over ``batch`` sequences: QK^T and PV, two products of
    ``head_dim`` multiply-adds per kept pair."""
    return _flash_flops(2, **shape)


def flash_backward_flops(**shape) -> float:
    """The backward pass, with the probabilities not stored: QK^T again,
    dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q.  Five products per
    kept pair -- the least any split into kernels can do; a split that
    repeats products does not raise the count."""
    return _flash_flops(5, **shape)


def flash_forward_bytes(**shape) -> float:
    """Bytes the forward pass must move through HBM: it reads q, k, v and
    writes o.  The fp32 row statistics (lse, delta) are 1/head_dim of a
    tensor each and are left out, here and in the backward pass."""
    return _flash_bytes(4, **shape)


def flash_backward_bytes(**shape) -> float:
    """The backward pass reads q, k, v, o, dO and writes dq, dk, dv."""
    return _flash_bytes(8, **shape)


def flash_step_flops(**shape) -> float:
    """Forward and backward of one layer: seven products per kept pair."""
    return flash_forward_flops(**shape) + flash_backward_flops(**shape)


def flash_step_bytes(**shape) -> float:
    """Forward and backward of one layer: twelve tensors."""
    return flash_forward_bytes(**shape) + flash_backward_bytes(**shape)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, which bound sets it)."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return ((by_flops, "flops") if by_flops >= by_bytes
            else (by_bytes, "bytes"))


def utilisation_percent(flops_per_unit: float, units_per_s_per_chip: float,
                        peaks: dict) -> float:
    """Model FLOP/s utilisation of one chip, in percent of its bf16 peak."""
    return (100.0 * flops_per_unit * units_per_s_per_chip
            / peaks["bf16_flops_per_s"])


def conv_macs(out_hw: int, kernel: int, c_in: int, c_out: int) -> int:
    """Multiply-adds of a square convolution with a square output."""
    return out_hw * out_hw * kernel * kernel * c_in * c_out


def bottleneck_macs(in_hw: int, c_in: int, filters: int, stride: int) -> int:
    """One v1.5 bottleneck: 1x1 reduce at the input's size, 3x3 carrying
    the stride, 1x1 expand x4, and a 1x1 projection on the shortcut where
    the shape changes."""
    out_hw = in_hw // stride
    macs = conv_macs(in_hw, 1, c_in, filters)
    macs += conv_macs(out_hw, 3, filters, filters)
    macs += conv_macs(out_hw, 1, filters, 4 * filters)
    if stride != 1 or c_in != 4 * filters:
        macs += conv_macs(out_hw, 1, c_in, 4 * filters)
    return macs


def resnet_train_flops_per_image(*, image: int, classes: int,
                                 stage_sizes=(3, 4, 6, 3), width: int = 64
                                 ) -> float:
    """Forward + backward operations per image of a bottleneck ResNet
    v1.5: convolutions and the classifier; batch norm, ReLU and pooling
    are elementwise and not counted."""
    hw = image // 2
    macs = conv_macs(hw, 7, 3, width)
    hw //= 2                                   # 3x3 max pool, stride 2
    c_in = width
    for stage, blocks in enumerate(stage_sizes):
        filters = width * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            macs += bottleneck_macs(hw, c_in, filters, stride)
            hw //= stride
            c_in = 4 * filters
    macs += c_in * classes
    return 3.0 * 2 * macs
