"""Arithmetic of a decoder whose residual path is ``streams`` hyper-connected
streams (mHC, arXiv:2512.24880) around DeepSeek-V3's block: latent attention
with a query latent, a leading dense layer, then routed experts of which one
chip holds a share beside a shared one -- from shapes alone and by
``benchmark/arithmetic.py``'s rules: a multiply-add is two operations,
training is the forward pass once and the backward pass twice, and what a
program repeats to save memory is not counted.

A hyper-connected sublayer makes three maps from a token's ``streams x
hidden`` numbers (one product of ``streams (streams + 2)`` columns; the RMS,
the sigmoids and Sinkhorn's steps are a few hundred operations a token and are
left out, as a norm is) and mixes the streams twice: the read ``h_pre X`` and
the write ``H_res X + h_post^T y``.  The mixes are elementwise and bound by
the bytes the ALGORITHM has to move, each tensor once a pass whatever runs it
(XLA's fusions today, one walk of X tomorrow): a share of a roofline built on
them judges both on the same work.  With n streams and one ``[tokens,
hidden]`` tensor as the unit:

* forward: X read once for the maps and the read (n), ``x_in`` written (1);
  X and the sublayer's output y read (n + 1) and X' written (n): 3 n + 2;
* backward, by the same rule: the write's reads X' 's cotangent, X and y (2 n +
  1) and writes X's partial cotangent and y's (n + 1); the read's and the
  maps' reads X, ``x_in``'s cotangent and the partial (2 n + 1) and writes X's
  cotangent (n): 6 n + 3.

The maps themselves are ``streams (streams + 2)`` float32 numbers a token
(0.8 MB at 8,192 tokens) and their leaves 0.7 MB: not counted.
"""

from __future__ import annotations

from benchmark import arithmetic_moe


def map_columns(streams: int) -> int:
    """Columns of a sublayer's one product: h_pre, h_post, H_res."""
    return streams * (streams + 2)


def map_flops(*, tokens: int, streams: int, hidden: int) -> float:
    """Forward operations of one sublayer's maps' product, ``[tokens,
    streams hidden] x [streams hidden, streams (streams + 2)]``."""
    return 2.0 * tokens * streams * hidden * map_columns(streams)


def mix_flops(*, tokens: int, streams: int, hidden: int) -> float:
    """Forward operations of one sublayer's two mixes: ``h_pre X`` (n
    multiply-adds a lane), ``H_res X`` (n n) and ``h_post^T y`` (n)."""
    return 2.0 * tokens * hidden * map_columns(streams)


def mix_bytes(*, tokens: int, streams: int, hidden: int,
              itemsize: int = 2) -> dict:
    """Bytes one sublayer's mixes must move through HBM in one step, by
    pass (the module's docstring counts them)."""
    tensor = tokens * hidden * itemsize
    return {"forward": (3.0 * streams + 2) * tensor,
            "backward": (6.0 * streams + 3) * tensor}


def mix_work(*, tokens: int, streams: int, hidden: int, sublayers: int) -> dict:
    """``{"flops", "bytes"}`` of a step's mixes over ``sublayers``
    hyper-connected sublayers: forward once and backward twice the
    operations, every tensor once each way."""
    shape = dict(tokens=tokens, streams=streams, hidden=hidden)
    return {"flops": sublayers * 3.0 * mix_flops(**shape),
            "bytes": sublayers * sum(mix_bytes(**shape).values())}


def mla_matmul_params(*, hidden: int, heads: int, qk_nope: int, qk_rope: int,
                      v_dim: int, kv_rank: int, q_rank: int) -> int:
    """Weights a token is multiplied with in one latent-attention block with
    a query latent: W_qa, W_qb, the joint down-projection W_kva, the
    up-projection W_kvb and W_o."""
    return (hidden * q_rank + q_rank * heads * (qk_nope + qk_rope)
            + hidden * (kv_rank + qk_rope)
            + kv_rank * heads * (qk_nope + v_dim)
            + heads * v_dim * hidden)


def train_flops_per_token(
        *, hidden: int, streams: int, layers: int, dense_layers: int,
        heads: int, qk_nope: int, qk_rope: int, v_dim: int, kv_rank: int,
        q_rank: int, dense_ffn: int, expert_ffn: int, shared: int,
        experts: int, held: int, per_token: int, vocab: int,
        seq: int) -> float:
    """Forward + backward operations per token of the work done HERE: every
    layer's latent attention, the leading layers' dense FFN, the routed
    layers' router (all ``experts`` wide), shared expert and HELD experts at
    their expected rows (nothing for the absent ones), the head over the
    vocabulary held, and two hyper-connected sublayers a layer (the maps'
    product and the mixes)."""
    attention = mla_matmul_params(
        hidden=hidden, heads=heads, qk_nope=qk_nope, qk_rope=qk_rope,
        v_dim=v_dim, kv_rank=kv_rank, q_rank=q_rank)
    routed = (hidden * experts + 3 * hidden * shared * expert_ffn
              + arithmetic_moe.expected_assignments(
                  per_token=per_token, held=held, experts=experts)
              * 3 * hidden * expert_ffn)
    weights = (layers * attention + dense_layers * 3 * hidden * dense_ffn
               + (layers - dense_layers) * routed + hidden * vocab)
    scores = layers * arithmetic_moe.attention_flops_per_token(
        heads=heads, qk_dim=qk_nope + qk_rope, v_dim=v_dim, seq=seq)
    shape = dict(tokens=1, streams=streams, hidden=hidden)
    wiring = 2 * layers * (map_flops(**shape) + mix_flops(**shape))
    return 3.0 * (2 * weights + scores + wiring)

