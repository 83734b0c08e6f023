"""Arithmetic of a hybrid decoder whose layers are gated delta-rule mixers
with FEWER key heads than value heads among gated softmax ones, every layer
over routed experts of which one chip holds a share beside a gated shared
expert; from shapes alone and by ``benchmark/arithmetic.py``'s rules: a
multiply-add is two operations, training is the forward pass once and the
backward pass twice, and what a program repeats to save memory is not
counted.

The rule's operations are ``benchmark/arithmetic_gdn.py``'s, a chunk and a
VALUE head (each value head keeps a state of its own).  Its bytes are counted
here because that file's ``scan_bytes`` reads q and k once a value head: the
algorithm needs them once a KEY head (value head j reads key head ``j //
(value / key)``), so a program that copies q and k to the value heads moves
more than this count and its share of the roofline falls; nothing raises it
over 100 %.

A full layer's W_q is twice as wide as its heads: a query and an
element-wise output gate of ``head_dim`` each.
"""

from __future__ import annotations

from benchmark import arithmetic, arithmetic_gdn, arithmetic_window


def scan_bytes(*, batch: int, seq: int, key_heads: int, value_heads: int,
               key_dim: int, value_dim: int,
               chunk: int = arithmetic_gdn.CHUNK, itemsize: int = 2
               ) -> float:
    """Bytes the rule must move through HBM for one layer in one step, as
    ``arithmetic_gdn.scan_bytes`` counts them (forward: q, k, v and the two
    gates read, o and the state each chunk starts from written; backward:
    all of those and o's cotangent read, the five gradients written), with q
    and k, and their gradients, at the KEY heads."""
    tokens = batch * seq
    qk = tokens * key_heads * 2 * key_dim * itemsize
    v = tokens * value_heads * value_dim * itemsize
    gates = tokens * value_heads * 2 * 4
    states = (batch * value_heads * -(-seq // chunk) * key_dim * value_dim
              * 4)
    forward = qk + v + gates + v + states
    backward = qk + v + gates + v + states + qk + v + gates
    return float(forward + backward)


def full_mixer_matmul_params(*, hidden: int, heads: int, kv_heads: int,
                             head_dim: int) -> int:
    """W_q (a query and a gate a head), W_k, W_v and W_o."""
    return hidden * head_dim * (2 * heads + 2 * kv_heads + heads)


def routed_params_a_token(*, hidden: int, **sizes) -> float:
    """``arithmetic_window.routed_params_a_token`` (the router over all the
    experts, the shared expert, the held experts at the share of a token's
    choices that lands on them: ``per_token * held / experts`` of an expert)
    and the shared expert's ``[hidden, 1]`` gate."""
    return arithmetic_window.routed_params_a_token(
        hidden=hidden, **sizes) + hidden


def train_flops_per_token(*, hidden: int, linear_layers: int,
                          full_layers: int, heads: int, kv_heads: int,
                          head_dim: int, key_heads: int, value_heads: int,
                          key_dim: int, value_dim: int, expert_ffn: int,
                          shared_ffn: int, experts: int, held: int,
                          per_token: int, vocab: int, seq: int) -> float:
    """Forward + backward operations per token: every matrix a token is
    multiplied with (the mixers' projections, every layer's router, gated
    shared expert and held experts at their expected rows, the head), causal
    softmax attention in the full layers and the chunked rule in the linear
    ones."""
    layers = linear_layers + full_layers
    weights = (
        linear_layers * arithmetic_gdn.linear_mixer_matmul_params(
            hidden=hidden, key_heads=key_heads, value_heads=value_heads,
            key_dim=key_dim, value_dim=value_dim)
        + full_layers * full_mixer_matmul_params(
            hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim)
        + layers * routed_params_a_token(
            hidden=hidden, expert_ffn=expert_ffn, shared_ffn=shared_ffn,
            experts=experts, held=held, per_token=per_token)
        + hidden * vocab)
    # QK^T and PV: two products of head_dim multiply-adds per kept pair.
    attention = full_layers * 2 * 2 * heads * head_dim * (
        arithmetic.causal_pairs(seq) / seq)
    rule = linear_layers * arithmetic_gdn.scan_flops(
        batch=1, seq=seq, value_heads=value_heads, key_dim=key_dim,
        value_dim=value_dim) / seq
    return 3.0 * (2 * weights + attention) + rule
