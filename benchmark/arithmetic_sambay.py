"""Arithmetic of a decoder-hybrid-decoder stack -- Mamba-1 selective scans
(Gu & Dao, arXiv:2312.00752) and differential attention (Ye et al.,
arXiv:2410.05258) whose later half reads one scan's output through gated
memory units and one layer's keys and values through cross-attention (Ren et
al., arXiv:2507.06607), each layer with its SwiGLU, under a head tied to the
embedding -- from shapes alone and by ``benchmark/arithmetic.py``'s rules: a
multiply-add is two operations, training is the forward pass once and the
backward pass twice, and what a program repeats to save memory is not
counted.

Differential attention is TWO softmax maps a head pair: ``heads`` maps a
layer, each over keys ``head_dim`` wide (q k^T) and values ``2 head_dim``
wide (p [v1 | v2]); a windowed layer's maps over the band alone
(``arithmetic_window.band_pairs``).  Forward a kept pair costs ``head_dim +
2 head_dim`` multiply-adds; backward q k^T again, dq and dk at ``head_dim``
and dp and dv at ``2 head_dim``.  The tied head is one product forward (the
embedding's lookup is none).

The selective scan's own work is three multiply-adds a token, a channel and a
state entry (the state decayed, the input written, the output read), forward
once and backward twice.  That is VECTOR work: the recurrence has no product
form (a decay a channel AND a state entry), so no implementation puts it on
the MXU, and a share of ``peaks.json``'s two peaks built on it reads low by
construction -- the bf16 matmul peak is ~50 times what the vector unit can
do in float32, and the bytes are few.  It reads the same whatever body runs,
and executing more than the count (the exponentials, the block states made
again in the backward pass) only lowers it: it can never pass 100 %.
"""

from __future__ import annotations

from benchmark import arithmetic_window

BLOCK = 128            # tokens between the states the scan keeps


def scan_flops(*, batch: int, seq: int, channels: int, state: int) -> float:
    """Operations the selective scan needs for one layer in one training
    step: three multiply-adds a token, channel and state entry, forward once
    and backward twice."""
    return 3.0 * 2 * 3 * batch * seq * channels * state


def scan_bytes(*, batch: int, seq: int, channels: int, state: int,
               itemsize: int = 2) -> float:
    """Bytes the scan must move through HBM for one layer in one step.
    Forward it reads u, B and C (``itemsize`` an element) and the step
    (float32) and writes y and the state every ``BLOCK`` tokens (float32);
    backward it reads all of those and y's cotangent and writes the four
    gradients: every tensor once each way."""
    tokens = batch * seq
    u = tokens * channels * itemsize
    bc = tokens * 2 * state * itemsize
    step = tokens * channels * 4
    states = batch * -(-seq // BLOCK) * channels * state * 4
    forward = u + bc + step + u + states
    backward = u + bc + step + u + states + u + bc + step
    return float(forward + backward)


def attention_flops(products_d: int, products_2d: int, *, batch: int,
                    seq: int, heads: int, head_dim: int,
                    window: int | None) -> float:
    """A layer's ``heads`` maps: ``products_d`` products of ``head_dim``
    multiply-adds a kept pair and ``products_2d`` of twice that."""
    return (2 * (products_d + 2 * products_2d) * head_dim * batch * heads
            * arithmetic_window.band_pairs(seq, window))


def attention_bytes(at_heads: int, at_kv_heads: int, *, batch: int, seq: int,
                    heads: int, kv_heads: int, head_dim: int,
                    itemsize: int = 2) -> float:
    """``at_heads`` tensors ``[batch, seq, heads * head_dim]`` and
    ``at_kv_heads`` at the key-value heads' width."""
    return batch * seq * head_dim * itemsize * (at_heads * heads
                                                + at_kv_heads * kv_heads)


def attention_work(*, batch: int, seq: int, heads: int, kv_heads: int,
                   head_dim: int, window: int | None) -> dict:
    """One differential layer's two calls in one training step, as
    ``kernel_work_per_step`` nests it.  Forward q k^T (D) and p v (2 D) a
    kept pair; q, k and v read (v by both calls) and o, twice as wide as q,
    written.  Backward q k^T, dq, dk (D) and dp, dv (2 D); q, k, v, o and dO
    read, dq, dk and dv written."""
    shape = dict(batch=batch, seq=seq, heads=heads, head_dim=head_dim,
                 window=window)
    sizes = dict(batch=batch, seq=seq, heads=heads, kv_heads=kv_heads,
                 head_dim=head_dim)
    forward = {"flops": attention_flops(1, 1, **shape),
               "bytes": attention_bytes(3, 3, **sizes)}
    backward = {"flops": attention_flops(3, 2, **shape),
                "bytes": attention_bytes(6, 6, **sizes)}
    return {"flops": forward["flops"] + backward["flops"],
            "bytes": forward["bytes"] + backward["bytes"],
            "forward": forward, "backward": backward}


def scan_matmul_params(*, hidden: int, inner: int, state: int,
                       rank: int) -> int:
    """W_in (u', z), W_x (r, B, C), W_dt and W_out."""
    return (hidden * 2 * inner + inner * (rank + 2 * state) + rank * inner
            + inner * hidden)


def attention_matmul_params(*, hidden: int, heads: int, kv_heads: int,
                            head_dim: int, cross: bool) -> int:
    """W_q and W_o, and W_k and W_v where the layer projects its own."""
    return hidden * head_dim * (2 * heads + (0 if cross else 2 * kv_heads))


def train_flops_per_token(*, hidden: int, ffn: int, layers: int,
                          scan_layers: int, memory_layers: int,
                          self_layers: int, cross_layers: int,
                          windowed_layers: int, heads: int, kv_heads: int,
                          head_dim: int, inner: int, state: int, rank: int,
                          vocab: int, seq: int, window: int) -> float:
    """Forward + backward operations per token: every matrix a token is
    multiplied with (a SwiGLU a layer, the mixers' projections, the tied
    head once), the attention layers' two maps a pair over their own pairs
    (``windowed_layers`` of them over the band) and the scans."""
    sizes = dict(hidden=hidden, heads=heads, kv_heads=kv_heads,
                 head_dim=head_dim)
    weights = (
        layers * 3 * hidden * ffn
        + scan_layers * scan_matmul_params(hidden=hidden, inner=inner,
                                           state=state, rank=rank)
        + memory_layers * 2 * hidden * inner
        + self_layers * attention_matmul_params(cross=False, **sizes)
        + cross_layers * attention_matmul_params(cross=True, **sizes)
        + hidden * vocab)
    full_layers = self_layers + cross_layers - windowed_layers
    # q k^T at head_dim and p v at twice that, a kept pair and a map.
    attention = 2 * 3 * head_dim * heads * (
        windowed_layers * arithmetic_window.band_pairs(seq, window)
        + full_layers * arithmetic_window.band_pairs(seq, None)) / seq
    scan = scan_layers * scan_flops(batch=1, seq=seq, channels=inner,
                                    state=state) / seq
    return 3.0 * (2 * weights + attention) + scan
