"""Plain reference of the decoder the ``lfm2-24b-a2b`` cell trains:
LFM2-24B-A2B (its ``config.json``, ``model_type`` ``lfm2_moe``; the LFM2
technical report, Liquid AI, and the published ``lfm2`` / ``lfm2_moe``
modelling code for the layer's form) -- a stack whose mixers are
double-gated short convolutions (``"conv"``) three to one with grouped-query
softmax attention over 64-wide heads with a per-head QK-norm
(``"full_attention"``), as ``layer_types`` spells them, over a dense SwiGLU in
the first ``num_dense_layers`` layers and routed SwiGLU experts behind a
sigmoid router whose choice a bias corrects in the others -- with its loss.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, the convolution as shifted multiply-adds, the causal softmax as a
mask, the held experts as a dense loop, ``jax.lax.top_k``, nothing imported
from the program.  Every number is a key of the configuration's file.  x is
the residual stream, eps ``norm_eps``, N a plain RMSNorm (``u / rms(u) *
gamma``), no bias anywhere (``conv_bias`` false)::

    every layer:  h = x + Mixer(N_op(x));  x' = h + FF(N_ffn(h))
    embedding; ``num_hidden_layers`` layers; N; logits = N(x) E^T
    (``assumed.tie_word_embeddings``)

**conv** (K = ``conv_L_cache`` taps, H = ``hidden_size``), u the normed state::

    [B | C | z] = u W_in                   three thirds of H, in that order
    y_t = C_t (sum_{i < K} w_i (B z)_{t - (K - 1) + i})    one filter a
                                           channel, zero history before
                                           position 0, w_{K-1} on the present
    Mixer(u) = y W_out                     no activation: the two gates are
                                           the non-linearity

**full_attention** (n = ``num_attention_heads`` query heads over m =
``num_key_value_heads`` of D = ``head_dim``): ``q, k, v = u W_q, u
W_k, u W_v``; every head of q and of k normed, ``N(q_j)`` and ``N(k_j)`` with
one ``[D]`` gamma each; all D lanes turned by the rotary positions at
``rope_parameters.rope_theta``, as interleaved pairs (``assumed.rotation``); ``softmax(q k^T
D^-1/2 + causal) v W_o``.

**Feed-forward.**  Layers before ``num_dense_layers``: ``silu(u W_gate) (u
W_up) W_down`` at ``intermediate_size``.  The others (``num_experts`` of
``deployment.num_experts_published`` held, K = ``num_experts_per_tok``),
router in float32, b the choice bias (zeros: the comparison is made on the
state as initialised)::

    s = sigmoid(u W_r)                     over all the published experts
    e_1..e_K = the K largest of s + b      (``use_expert_bias``)
    g_k = s[e_k] / (sum_j s[e_j] + 1e-6) x ``routed_scaling_factor``
                                           (``norm_topk_prob``)
    y = sum_{k: e_k held} g_k E_{e_k}(u)   E a SwiGLU of
                                           ``moe_intermediate_size``; no
                                           shared expert

What an absent expert would add is left out.

**Loss**: mean next-token cross-entropy of every position + ``assumed.
aux_loss_alpha`` x the mean over the routed layers of the batch-wise balance
loss ``E sum_e f[e] P[e]`` (f[e] the share of the batch's assignments that
chose e, a constant; P[e] the mean over the batch of ``s[e] / sum_j s[j]``).

**Departures from the published code**, each a re-arrangement and none a
change of function: the rotation turns interleaved pairs where the
checkpoint turns a head's halves against each other (a fixed permutation of
``W_q``'s and ``W_k``'s columns on loading); the gates, the filter and the
products run in float32 where the published code runs them in the
activations' dtype; the held experts' three matrices are stacked ``[held,
..]``; a balance loss is added (the published recipe balances by the bias
alone: ``assumed.aux_loss_alpha``).

So that 8192 positions fit beside the program in ``benchmark/compare.py``'s
one program, nothing of which changes a number: attention takes ``QUERIES``
queries at a time against all keys; a feed-forward and the head's loss
``ROWS`` rows at a time; each layer and each of those blocks under
``jax.checkpoint``.

Parameters are a plain tree: ``embed [V, H]``; ``layers``, a list, each with
``norm_op [H]``, ``norm_ffn [H]``, and, a conv layer: ``in_proj [H, 3 H]``,
``conv_w [K, H]``, ``out_proj [H, H]``; an attention layer: ``wq [H, n D]``,
``wk wv [H, m D]``, ``q_norm k_norm [D]``, ``wo [n D, H]``; a dense layer:
``w_gate w_up [H, F]``, ``w_down [F, H]``; a routed layer: ``router [H, E]``,
``experts`` (``w_gate w_up [held, H, F_e]``, ``w_down [held, F_e, H]``);
``norm_f [H]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS = 1024            # rows of a feed-forward and of the head's loss at a time
QUERIES = 128          # queries of softmax attention at a time
GATE_EPS = 1e-6        # in the sum that renormalises a token's gates


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma


def _blocks(seq: int, block: int) -> int:
    block = min(block, seq)
    if seq % block:
        raise ValueError(f"sequence {seq} is not a multiple of {block}")
    return seq // block


def by_rows(fn, x, block=ROWS):
    """``fn`` of ``x [B, S, ..]`` a block of rows at a time, each block
    under a checkpoint."""
    batch, seq = x.shape[:2]
    n = _blocks(seq, block)
    rows = x.reshape(batch, n, seq // n, *x.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(jax.checkpoint(fn), rows)
    return out.swapaxes(0, 1).reshape(batch, seq, *out.shape[3:])


# -- conv: the double-gated short convolution ---------------------------------

def short_convolution(x, taps):
    """``y[t] = sum_i taps[i] x[t - (K - 1) + i]``; x ``[B, S, C]``, zero
    history before position 0 of every row."""
    seq, k = x.shape[1], taps.shape[0]
    return sum(jnp.pad(x, ((0, 0), (k - 1 - i, 0), (0, 0)))[:, :seq] * taps[i]
               for i in range(k))


def gated(b, c, z, taps):
    """``C conv(B z)``: the mixer between its two projections."""
    return c * short_convolution(b * z, taps)


def conv_mixer(u, layer, config):
    b, c, z = jnp.split(u @ layer["in_proj"], 3, axis=-1)
    return gated(b, c, z, layer["conv_w"]) @ layer["out_proj"]


# -- full_attention -------------------------------------------------------------

def rotary(x, theta):
    """x: [B, S, heads, D]; position p turns pair i, ``(x[2i], x[2i+1])``, by
    ``p theta^(-2i/D)``.  The pairs are taken apart by a reshape."""
    dim = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * inv_freq[None, :])
    cos, sin = (t[None, :, None, :] for t in (jnp.cos(angle),
                                              jnp.sin(angle)))
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def head_norm(x, gamma, eps):
    """The QK-norm: each head of ``x [B, S, heads, D]`` by its own rms."""
    return rms_norm(x, gamma, eps)


def causal_attention(q, k, v):
    """q, k, v: [B, S, heads, D] (k and v already repeated to the query
    heads) -> [B, S, heads, D], softmax over the keys at or before each
    query, ``QUERIES`` queries at a time."""
    batch, seq, heads, dim = q.shape
    n = _blocks(seq, QUERIES)
    block = seq // n
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def one_block(args):
        q_block, first = args                       # [B, block, heads, D]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) * dim ** -0.5
        keep = (first + jnp.arange(block))[:, None] >= key_pos[None, :]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    q_blocks = q.reshape(batch, n, block, heads, dim).swapaxes(0, 1)
    out = jax.lax.map(one_block, (q_blocks, jnp.arange(n) * block))
    return out.swapaxes(0, 1).reshape(batch, seq, heads, dim)


def attention_mixer(u, layer, config):
    batch, seq, _ = u.shape
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim, eps = config["head_dim"], config["norm_eps"]
    q = (u @ layer["wq"]).reshape(batch, seq, heads, dim)
    k = (u @ layer["wk"]).reshape(batch, seq, kv_heads, dim)
    v = (u @ layer["wv"]).reshape(batch, seq, kv_heads, dim)
    theta = config["rope_parameters"]["rope_theta"]
    q = rotary(head_norm(q, layer["q_norm"], eps), theta)
    k = rotary(head_norm(k, layer["k_norm"], eps), theta)
    # Query head j reads key-value head j // (n / m): written as a repeat.
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    return causal_attention(q, k, v).reshape(batch, seq,
                                             heads * dim) @ layer["wo"]


# -- the feed-forwards ----------------------------------------------------------

def swiglu(x, w):
    """``silu(x W_gate) (x W_up) W_down`` on x: [B, S, H], ``ROWS`` rows at
    a time."""
    return by_rows(lambda rows: (jax.nn.silu(rows @ w["w_gate"])
                                 * (rows @ w["w_up"])) @ w["w_down"], x)


def gates_of(scores, chosen, config):
    """A token's gates from its UNbiased scores at its chosen experts."""
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + GATE_EPS)
    return gates * config["routed_scaling_factor"]


def routed_experts(u, layer, config, bias=None, first=None, experts=None):
    """(the held experts' part of the routed sum, the balance loss).  ``bias
    [E]``: the choice bias; None: zeros.  ``first`` and ``experts``: another
    share than the configuration's (the share test's)."""
    experts_over = layer["router"].shape[1]
    per_token = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ layer["router"])
    corrected = scores if bias is None else scores + bias
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(corrected), per_token)
    gates = gates_of(scores, chosen, config)
    if first is None:
        first = config["deployment"]["first_held_expert"]
    experts = layer["experts"] if experts is None else experts

    def add_expert(y, held):
        expert, index = held
        gate = jnp.sum(jnp.where(chosen == first + index, gates, 0.0),
                       axis=-1)
        return y + gate[..., None] * swiglu(u, expert), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(u),
                        (experts, jnp.arange(experts["w_up"].shape[0])))
    counts = jnp.sum(jax.nn.one_hot(chosen, experts_over), axis=(0, 1, 2))
    share = jax.lax.stop_gradient(
        counts / (per_token * u.shape[0] * u.shape[1]))
    probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    balance = experts_over * jnp.sum(share * jnp.mean(probs, axis=(0, 1)))
    return y, balance


MIXERS = {"conv": conv_mixer, "full_attention": attention_mixer}


def decoder_layer(x, layer, kind, routed, config):
    """(x', the layer's balance loss or None)."""
    eps = config["norm_eps"]
    h = x + MIXERS[kind](rms_norm(x, layer["norm_op"], eps), layer, config)
    u = rms_norm(h, layer["norm_ffn"], eps)
    if routed:
        y, balance = routed_experts(u, layer, config)
        return h + y, balance
    return h + swiglu(u, layer), None


def hidden_states(params, tokens, config):
    """(the final normed states [B, S, H], the routed layers' balance
    losses)."""
    kinds = config["layer_types"]
    if len(kinds) != len(params["layers"]):
        raise ValueError(f"{len(params['layers'])} layers for the types "
                         f"{kinds!r}")
    x = params["embed"][tokens]
    balance = []
    for i, (kind, layer) in enumerate(zip(kinds, params["layers"])):
        routed = i >= config["num_dense_layers"]
        x, layer_balance = jax.checkpoint(
            lambda x, layer, kind=kind, routed=routed: decoder_layer(
                x, layer, kind, routed, config))(x, layer)
        if layer_balance is not None:
            balance.append(layer_balance)
    return (rms_norm(x, params["norm_f"], config["norm_eps"]),
            jnp.stack(balance))


def loss(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]`` plus alpha
    times the mean balance loss of the routed layers."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, balance = hidden_states(params, inputs, config)
    batch, seq, hidden = x.shape
    n_blocks = _blocks(seq, ROWS)

    @jax.checkpoint
    def block_nll(args):
        rows, wanted = args
        logits = rows @ params["embed"].T           # the tied head
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked[..., 0])

    rows = x.reshape(batch, n_blocks, seq // n_blocks, hidden).swapaxes(0, 1)
    wanted = targets.reshape(batch, n_blocks, seq // n_blocks).swapaxes(0, 1)
    nll = jnp.sum(jax.lax.map(block_nll, (rows, wanted))) / (batch * seq)
    return nll + config["assumed"]["aux_loss_alpha"] * jnp.mean(balance)


def loss_and_grads(params, tokens, config):
    """(loss, d loss / d params) in float32 at ``highest`` precision."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return jax.value_and_grad(loss)(params, tokens, config)
