"""Arithmetic of a hybrid decoder whose layers are Kimi Delta Attention
mixers (Kimi Linear, arXiv:2510.26692: the delta rule with a decay a key
CHANNEL) among latent-attention ones that do not rotate, over a leading dense
layer and then routed experts of which one chip holds a share; from shapes
alone and by ``benchmark/arithmetic.py``'s rules: a multiply-add is two
operations, training is the forward pass once and the backward pass twice, and
what a program repeats to save memory is not counted.

The rule's own work is the CHUNKWISE algorithm's at chunks of ``CHUNK`` = 64
rows, a chunk and a head: what any implementation of that algorithm has to do,
XLA operations or a Mosaic call, so a share built on it reads the same
whatever runs it.  With C rows, keys d_k and values d_v wide, multiply-adds
forward (a pair (i, j) of A or of P costs d_k of them however it is formed: a
product of decayed operands on the MXU, or pair by pair on the VPU)::

    A[i, j] = beta_i sum_c k_i[c] e^{G_i[c] - G_j[c]} k_j[c]     C^2 d_k
    (I + A)^-1 by substitution                                   C^3 / 6
    W = T (beta e^G (x) K), U0 = T (beta V)                      C^2 (d_k + d_v)
    P[i, j] = sum_c q_i[c] e^{G_i[c] - G_j[c]} k_j[c]            C^2 d_k
    W S^T, (e^G (x) Q) S^T, U^T (e^{G_C - G} (x) K)              3 C d_k d_v
    P U                                                          C^2 d_v

What is elementwise -- the decays' exponentials (a ``[C, d_k]`` tile a head
where the scalar rule has a column), the filters, the norms, the gates -- is
not counted, as a norm is not in ``decoder_train_flops_per_token``.  The BYTES
are where a decay a channel shows: g is as large as k, in float32.
"""

from __future__ import annotations

from benchmark import arithmetic, arithmetic_moe

CHUNK = 64


def chunk_rule_macs(*, key_dim: int, value_dim: int, chunk: int = CHUNK
                    ) -> float:
    """Multiply-adds one chunk of one head needs, forward."""
    c, d_k, d_v = chunk, key_dim, value_dim
    systems = 2 * c * c * d_k                   # A and P
    solve = c ** 3 / 6
    solved = c * c * (d_k + d_v)                # W and U0
    state = 3 * c * d_k * d_v                   # read, read, written
    mixed = c * c * d_v                         # P U
    return systems + solve + solved + state + mixed


def scan_flops(*, batch: int, seq: int, heads: int, key_dim: int,
               value_dim: int, chunk: int = CHUNK) -> float:
    """Operations the chunked rule needs for one layer in one training
    step: forward once, backward twice."""
    chunks = -(-seq // chunk)
    return 3.0 * 2 * batch * heads * chunks * chunk_rule_macs(
        key_dim=key_dim, value_dim=value_dim, chunk=chunk)


def scan_bytes(*, batch: int, seq: int, heads: int, key_dim: int,
               value_dim: int, chunk: int = CHUNK, itemsize: int = 2
               ) -> float:
    """Bytes the rule must move through HBM for one layer in one step.
    Forward it reads q, k, v (``itemsize`` an element), g (float32, a number a
    key channel) and beta (float32, a number a head) and writes o and the
    state each chunk starts from (float32); backward it reads all of those
    and o's cotangent and writes the five gradients."""
    rows = batch * seq * heads
    qkv = rows * (2 * key_dim + value_dim) * itemsize
    gates = rows * (key_dim + 1) * 4
    out = rows * value_dim * itemsize
    states = batch * heads * -(-seq // chunk) * key_dim * value_dim * 4
    forward = qkv + gates + out + states
    backward = qkv + gates + out + states + qkv + gates
    return float(forward + backward)


def scan_work(*, layers: int, **shape) -> dict:
    """``{"flops", "bytes"}`` of ``layers`` KDA layers' rule in one step."""
    return {"flops": layers * scan_flops(**shape),
            "bytes": layers * scan_bytes(**shape)}


def kda_mixer_matmul_params(*, hidden: int, heads: int, head_dim: int) -> int:
    """W_q, W_k, W_v, W_o; the two low-rank pairs (W_fa, W_fb for the decay,
    W_ga, W_gb for the output gate, each through ``head_dim`` lanes); W_b."""
    wide = heads * head_dim
    return (4 * hidden * wide + 2 * (hidden * head_dim + head_dim * wide)
            + hidden * heads)


def train_flops_per_token(*, hidden: int, kda_layers: int,
                          latent_layers: int, dense_layers: int, heads: int,
                          kda_heads: int, kda_head_dim: int, qk_nope: int,
                          qk_rope: int, v_dim: int, kv_rank: int,
                          dense_ffn: int, expert_ffn: int, shared: int,
                          experts: int, held: int, per_token: int, vocab: int,
                          seq: int) -> float:
    """Forward + backward operations per token: every matrix a token is
    multiplied with (the mixers' projections, the leading layers' dense FFN,
    the routed layers' router over all ``experts``, shared expert and held
    experts at their expected rows, the head over the vocabulary held), causal
    softmax attention in the latent layers and the chunked rule in the KDA
    ones."""
    layers = kda_layers + latent_layers
    routed = (hidden * experts + 3 * hidden * shared * expert_ffn
              + arithmetic_moe.expected_assignments(
                  per_token=per_token, held=held, experts=experts)
              * 3 * hidden * expert_ffn)
    weights = (
        kda_layers * kda_mixer_matmul_params(
            hidden=hidden, heads=kda_heads, head_dim=kda_head_dim)
        + latent_layers * arithmetic_moe.mla_matmul_params(
            hidden=hidden, heads=heads, qk_nope=qk_nope, qk_rope=qk_rope,
            v_dim=v_dim, kv_rank=kv_rank)
        + dense_layers * 3 * hidden * dense_ffn
        + (layers - dense_layers) * routed + hidden * vocab)
    scores = latent_layers * arithmetic_moe.attention_flops_per_token(
        heads=heads, qk_dim=qk_nope + qk_rope, v_dim=v_dim, seq=seq)
    rule = kda_layers * scan_flops(
        batch=1, seq=seq, heads=kda_heads, key_dim=kda_head_dim,
        value_dim=kda_head_dim) / seq
    return 3.0 * (2 * weights + scores) + rule


def roofline_ms(work: dict, peaks: dict):
    """(least milliseconds, which bound) of ``work`` at ``peaks``."""
    least_s, bound = arithmetic.roofline_seconds(
        work["flops"], work["bytes"], peaks)
    return least_s * 1e3, bound
