"""Arithmetic of a decoder whose layer is ONE sublayer -- a Mamba-2
state-space mixer (Dao & Gu, arXiv:2405.21060), routed relu^2 experts of which
one chip holds a share beside a shared expert, or grouped-query softmax
attention -- from shapes alone and by ``benchmark/arithmetic.py``'s rules: a
multiply-add is two operations, training is the forward pass once and the
backward pass twice, and what a program repeats to save memory is not
counted.

The scan's own work is the published CHUNKED algorithm's (state-space
duality) at chunks of ``CHUNK`` = 128 rows (the published ``chunk_size``), a
chunk and a head: what any implementation of that algorithm has to do, XLA
operations or a Mosaic call, so a share built on it reads the same whatever
runs it.  With Q rows, P lanes a head, N state entries a lane and R heads
sharing a group's B and C, multiply-adds forward::

    C B^T                  Q^2 N / R       once a GROUP
    (M * C B^T) (D u)      Q^2 P           once a head
    S' += (D u e^..)^T B   Q P N           the state written
    C S^T                  Q N P           the state read

The convolution, the gates, the decays and the norm are elementwise and are
not counted, as a norm is not in ``decoder_train_flops_per_token``.  A relu^2
feed-forward has TWO matrices (``[H, F]`` and ``[F, H]``), no gate.
"""

from __future__ import annotations

from benchmark import arithmetic, arithmetic_moe, arithmetic_window

CHUNK = 128


def chunk_scan_macs(*, head_dim: int, state: int, heads_a_group: int,
                    chunk: int = CHUNK) -> float:
    """Multiply-adds one chunk of one head needs, forward."""
    q, p, n = chunk, head_dim, state
    return q * q * n / heads_a_group + q * q * p + 2 * q * p * n


def scan_flops(*, batch: int, seq: int, heads: int, groups: int,
               head_dim: int, state: int, chunk: int = CHUNK) -> float:
    """Operations the chunked scan needs for one layer in one training step:
    forward once, backward twice."""
    chunks = -(-seq // chunk)
    return 3.0 * 2 * batch * heads * chunks * chunk_scan_macs(
        head_dim=head_dim, state=state, heads_a_group=heads // groups,
        chunk=chunk)


def scan_bytes(*, batch: int, seq: int, heads: int, groups: int,
               head_dim: int, state: int, chunk: int = CHUNK,
               itemsize: int = 2) -> float:
    """Bytes the scan must move through HBM for one layer in one step.
    Forward it reads u (``itemsize`` an element), B and C once a GROUP and
    the step dt (float32) and writes y and the state each chunk starts from
    (float32); backward it reads all of those and y's cotangent and writes
    the four gradients: every tensor once each way, the chunk states once
    each way."""
    tokens = batch * seq
    u = tokens * heads * head_dim * itemsize
    bc = tokens * groups * 2 * state * itemsize
    dt = tokens * heads * 4
    states = batch * heads * -(-seq // chunk) * head_dim * state * 4
    forward = u + bc + dt + u + states
    backward = u + bc + dt + u + states + u + bc + dt
    return float(forward + backward)


def mamba_matmul_params(*, hidden: int, heads: int, head_dim: int,
                        groups: int, state: int) -> int:
    """W_in (z, x, B, C, dt) and W_out."""
    inner = heads * head_dim
    return hidden * (2 * inner + 2 * groups * state + heads) + inner * hidden


def routed_params_a_token(*, hidden: int, expert_ffn: int, shared_ffn: int,
                          experts: int, held: int, per_token: int) -> float:
    """Weights a token is multiplied with in a routed layer: the router over
    all ``experts``, the shared expert's two matrices, and the held experts'
    two at the share of its choices that lands on them (``per_token * held /
    experts`` of an expert)."""
    return (hidden * experts + 2 * hidden * shared_ffn
            + arithmetic_moe.expected_assignments(
                per_token=per_token, held=held, experts=experts)
            * 2 * hidden * expert_ffn)


def expert_products_flops(*, rows: float, hidden: int,
                          expert_ffn: int) -> float:
    """One routed layer's grouped products in a step: up ``[H, F]`` and down
    ``[F, H]`` over ``rows`` rows, forward and their two gradient products
    each."""
    return 3 * 2 * rows * 2 * hidden * expert_ffn


def expert_products_bytes(*, rows: float, held: int, hidden: int,
                          expert_ffn: int, itemsize: int = 2) -> float:
    """Each of the six products touches its matrix over the held experts
    once and the rows on both of its sides once
    (``arithmetic_moe.expert_products_bytes`` for two matrices)."""
    weights = held * 2 * hidden * expert_ffn
    sides = rows * 2 * (hidden + expert_ffn)
    return 3 * itemsize * (weights + sides)


def train_flops_per_token(*, hidden: int, mamba_layers: int,
                          attention_layers: int, routed_layers: int,
                          heads: int, kv_heads: int, head_dim: int,
                          mamba_heads: int, mamba_head_dim: int, groups: int,
                          state: int, expert_ffn: int, shared_ffn: int,
                          experts: int, held: int, per_token: int,
                          vocab: int, seq: int, chunk: int = CHUNK) -> float:
    """Forward + backward operations per token: every matrix a token is
    multiplied with in each layer's ONE sublayer and in the head, causal
    softmax attention in the attention layers and the chunked scan in the
    Mamba ones."""
    weights = (
        mamba_layers * mamba_matmul_params(
            hidden=hidden, heads=mamba_heads, head_dim=mamba_head_dim,
            groups=groups, state=state)
        + attention_layers * arithmetic_window.mixer_matmul_params(
            hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
            gated=False)
        + routed_layers * routed_params_a_token(
            hidden=hidden, expert_ffn=expert_ffn, shared_ffn=shared_ffn,
            experts=experts, held=held, per_token=per_token)
        + hidden * vocab)
    # QK^T and PV: two products of head_dim multiply-adds per kept pair.
    attention = attention_layers * 2 * 2 * heads * head_dim * (
        arithmetic.causal_pairs(seq) / seq)
    scan = mamba_layers * scan_flops(
        batch=1, seq=seq, heads=mamba_heads, groups=groups,
        head_dim=mamba_head_dim, state=state, chunk=chunk) / seq
    return 3.0 * (2 * weights + attention) + scan
