"""Arithmetic of a hybrid decoder whose layers are gated delta-rule
linear-attention mixers (Gated DeltaNet, arXiv:2412.06464) among softmax
ones, from shapes alone and by ``benchmark/arithmetic.py``'s rules: a
multiply-add is two operations, training is the forward pass once and the
backward pass twice, and what a program repeats to save memory is not
counted.

The rule's own work is the published CHUNKWISE algorithm's at chunks of
``CHUNK`` = 64 rows, a chunk and a head: what any implementation of that
algorithm has to do, XLA operations or a Mosaic call, so a share built on
it reads the same whatever runs it.  With C rows, keys d_k and values d_v
wide, multiply-adds forward::

    K K^T                       C^2 d_k
    (I + A)^-1 by substitution  C^3 / 6
    W = T K', U0 = T V'         C^2 (d_k + d_v)
    Q K^T                       C^2 d_k
    W S^T, Q S^T, U^T K         3 C d_k d_v        (the state: read, read, written)
    (M * Q K^T) U               C^2 d_v

The convolutions, the norms and the gates are elementwise and are not
counted, as a norm is not in ``decoder_train_flops_per_token``.
"""

from __future__ import annotations

from benchmark import arithmetic

CHUNK = 64


def chunk_rule_macs(*, key_dim: int, value_dim: int, chunk: int = CHUNK
                    ) -> float:
    """Multiply-adds one chunk of one head needs, forward."""
    c, d_k, d_v = chunk, key_dim, value_dim
    return (3 * c * c * d_k + 2 * c * c * d_v + 3 * c * d_k * d_v
            + c ** 3 / 6)


def linear_mixer_matmul_params(*, hidden: int, key_heads: int,
                               value_heads: int, key_dim: int,
                               value_dim: int) -> int:
    """W_q, W_k; W_v, W_g, W_o; W_a, W_b."""
    return hidden * (2 * key_heads * key_dim + 3 * value_heads * value_dim
                     + 2 * value_heads)


def scan_flops(*, batch: int, seq: int, value_heads: int, key_dim: int,
               value_dim: int, chunk: int = CHUNK) -> float:
    """Operations the chunked rule needs for one layer in one training
    step: forward once, backward twice."""
    chunks = -(-seq // chunk)
    return 3.0 * 2 * batch * value_heads * chunks * chunk_rule_macs(
        key_dim=key_dim, value_dim=value_dim, chunk=chunk)


def scan_bytes(*, batch: int, seq: int, value_heads: int, key_dim: int,
               value_dim: int, chunk: int = CHUNK, itemsize: int = 2
               ) -> float:
    """Bytes the rule must move through HBM for one layer in one step.
    Forward it reads q, k, v (``itemsize`` an element) and the two gates
    (float32) and writes o and the state each chunk starts from (float32);
    backward it reads all of those and o's cotangent and writes the five
    gradients."""
    rows = batch * seq * value_heads
    qkv = rows * (2 * key_dim + value_dim) * itemsize
    gates = rows * 2 * 4
    out = rows * value_dim * itemsize
    states = (batch * value_heads * -(-seq // chunk) * key_dim * value_dim
              * 4)
    forward = qkv + gates + out + states
    backward = qkv + gates + out + states + qkv + gates
    return float(forward + backward)


def hybrid_train_flops_per_token(
        *, hidden: int, layer_types, heads: int, head_dim: int,
        key_heads: int, value_heads: int, key_dim: int, value_dim: int,
        ffn: int, vocab: int, seq: int, chunk: int = CHUNK) -> float:
    """Forward + backward operations per token: every matrix a token is
    multiplied with (the mixers' projections, the SiLU-gated FFN of every
    layer, the head), causal softmax attention in the ``full_attention``
    layers (no grouped keys: ``heads`` heads of ``head_dim`` for q, k and
    v) and the chunked rule in the ``linear_attention`` ones."""
    linear = sum(kind == "linear_attention" for kind in layer_types)
    full = len(layer_types) - linear
    weights = (linear * linear_mixer_matmul_params(
        hidden=hidden, key_heads=key_heads, value_heads=value_heads,
        key_dim=key_dim, value_dim=value_dim)
        + full * 4 * hidden * heads * head_dim
        + len(layer_types) * 3 * hidden * ffn + hidden * vocab)
    attention = full * 2 * 2 * heads * head_dim * (
        arithmetic.causal_pairs(seq) / seq)
    rule = linear * scan_flops(
        batch=1, seq=seq, value_heads=value_heads, key_dim=key_dim,
        value_dim=value_dim, chunk=chunk) / seq
    return 3.0 * (2 * weights + attention) + rule
