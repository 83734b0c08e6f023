"""Plain reference of the decoder the ``qwen3-next-80b-a3b`` cell trains:
Qwen3-Next-80B-A3B-Instruct (its ``config.json``, ``model_type``
``qwen3_next``) -- gated delta-rule linear-attention layers (Gated DeltaNet;
Yang, Kautz, Hatamizadeh, arXiv:2412.06464) three to one with gated softmax
layers, every layer over routed experts beside a gated shared expert, in a
pre-norm block whose norms are zero-centred -- with its loss.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no chunked form (the rule runs TOKEN BY TOKEN), the causal softmax as
a mask, the held experts as a dense loop, ``jax.lax.top_k``, nothing imported
from the program.  Every number is a key of the configuration's file.  x is
the residual stream, eps ``rms_norm_eps``::

    N0(u) = u / rms(u) * (1 + gamma)      gamma from zeros (block norms, the
                                          final norm, q_norm, k_norm)
    N1(u) = u / rms(u) * gamma            gamma from ones (the rule's output)
    every layer:  h = x + Mixer(N0(x));   x' = h + MoE(N0(h))
    embedding; ``num_hidden_layers`` layers; N0; an untied head

**Linear layer** (layer i with ``(i + 1) % full_attention_interval != 0``):
``linear_num_key_heads`` key heads, ``linear_num_value_heads`` value heads,
d_k = ``linear_key_head_dim``, d_v = ``linear_value_head_dim``, ``*`` a causal
depthwise convolution of ``linear_conv_kernel_dim`` taps (zero history before
position 0, written as shifted multiply-adds), u the normed state::

    q = l2norm(silu(conv_q * (u W_q))) d_k^-1/2     k = l2norm(silu(conv_k * (u W_k)))
    v = silu(conv_v * (u W_v))
    value head j reads key head j // (value heads / key heads)
    beta_t  = sigmoid(u_t W_b)
    alpha_t = exp(-exp(A_log) softplus(u_t W_a + dt_bias))
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T        S_0 = 0
    o_t = S_t q_t
    Mixer(u) = (N1(o) * silu(u W_g)) W_o            one [d_v] scale for all heads

**Full layer**: n = ``num_attention_heads`` query heads over m =
``num_key_value_heads`` key-value heads of D = ``head_dim``::

    [q_j ; g_j] = (u W_q)_j          2 D outputs a head: its query, then its gate
    k, v = u W_k, u W_v              [S, m, D]
    q_j <- N0(q_j);  k_j <- N0(k_j)  a [D] gamma each, shared by the heads
    the first ``partial_rotary_factor`` D lanes of q and k turn (theta
        ``rope_theta``, no scaling), the others pass untouched
    o_j = softmax(q_j k_{j // (n / m)}^T D^-1/2 + causal) v_{j // (n / m)}
    Mixer(u) = concat_j(sigmoid(g_j) * o_j) W_o

**MoE** (every layer; ``decoder_sparse_step`` 1, no ``mlp_only_layers``)::

    s = softmax(u W_r) over all E experts, float32
    e_1..e_K the K = ``num_experts_per_tok`` largest;  w_k = s[e_k] / sum_j s[e_j]
    MoE(u) = sum_{k: e_k held} w_k E_{e_k}(u) + sigmoid(u w_s) S(u)

E_e a SwiGLU of ``moe_intermediate_size``, S one of
``shared_expert_intermediate_size``, w_s ``[H, 1]``.  The parameters hold
``w_gate.shape[0]`` experts, ids ``deployment.first_held_expert`` onwards; the
router, the top-K, the gates' sum and the balance loss are over all E, and
what an absent expert would add is left out.

**Loss**: mean next-token cross-entropy of every position + ``assumed.
aux_loss_alpha`` x the mean over the layers of the batch-wise balance loss
``E sum_e f[e] P[e]`` (f[e] the share of the batch's assignments that chose e,
a constant; P[e] the mean router probability).

**Departures from the published description**, each a re-arrangement of the
checkpoint and none a change of function: the checkpoint's fused
``in_proj_qkvz`` and ``in_proj_ba`` are W_q, W_k, W_v, W_g and W_b, W_a apart,
and its one convolution over the concatenated q, k and v channels is three
filters over their own channels (depthwise: the same numbers in another
order); rotary pairs are interleaved ``(x[2i], x[2i+1])`` where the checkpoint
turns half against half, a fixed permutation of W_q's and W_k's columns; no
multi-token-prediction module (no key of the configuration states one).

So that 8192 positions fit beside the program in ``benchmark/compare.py``'s
one program, nothing of which changes a number: the recurrence is a nested
``lax.scan``, ``TOKENS`` tokens to a checkpoint; attention takes ``QUERIES``
queries at a time against all keys; a SwiGLU and the head's loss ``ROWS`` rows
at a time; each layer and each of those blocks under ``jax.checkpoint``.

Parameters are a plain tree: ``embed [V, H]``; ``layers``, a list, each with
``norm_attn norm_mlp [H]``, ``router [H, E]``, ``experts`` (``w_gate w_up
[held, H, F]``, ``w_down [held, F, H]``), ``shared`` (a SwiGLU's three),
``shared_gate [H, 1]`` and, a linear layer: ``wq wk [H, key heads * d_k]``,
``wv wg [H, value heads * d_v]``, ``wa wb [H, value heads]``, ``conv_q conv_k
conv_v [K, channels]``, ``a_log dt_bias [value heads]``, ``o_norm [d_v]``,
``wo``; a full layer: ``wq [H, n * 2 * D]``, ``wk wv [H, m * D]``, ``q_norm
k_norm [D]``, ``wo [n * D, H]``; ``norm_f [H]``; ``lm_head [H, V]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS = 1024            # rows of a SwiGLU and of the head's loss at a time
QUERIES = 128          # queries of softmax attention at a time
TOKENS = 128           # tokens of the recurrence to a checkpoint


def _normed(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def norm_zero_centred(x, gamma, eps):
    """N0."""
    return _normed(x, eps) * (1.0 + gamma)


def norm_plain(x, gamma, eps):
    """N1."""
    return _normed(x, eps) * gamma


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


def is_linear(index: int, config: dict) -> bool:
    return (index + 1) % config["full_attention_interval"] != 0


# -- the linear layer ---------------------------------------------------------

def short_convolution(x, taps):
    """``y[t] = sum_i taps[i] x[t - (K - 1) + i]``; x ``[B, S, C]``."""
    seq, k = x.shape[1], taps.shape[0]
    return sum(jnp.pad(x, ((0, 0), (k - 1 - i, 0), (0, 0)))[:, :seq] * taps[i]
               for i in range(k))


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, one token a step.  q, k ``[B, S, heads, d_k]``, v
    ``[B, S, heads, d_v]``, alpha, beta ``[B, S, heads]`` -> ``o [B, S,
    heads, d_v]``."""
    batch, seq, heads, d_k = q.shape
    d_v = v.shape[-1]

    def token(state, x):
        q, k, v, alpha, beta = x
        read = jnp.einsum("bhvk,bhk->bhv", state, k)              # S k
        kept = state - beta[..., None, None] * (
            read[..., :, None] * k[..., None, :])
        state = alpha[..., None, None] * kept + beta[..., None, None] * (
            v[..., :, None] * k[..., None, :])
        return state, jnp.einsum("bhvk,bhk->bhv", state, q)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    n = _blocks(seq, TOKENS)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(n, seq // n, *x.shape[:1],
                                             *x.shape[2:])
               for x in (q, k, v, alpha, beta))
    _, o = jax.lax.scan(
        block, jnp.zeros((batch, heads, d_v, d_k), jnp.float32), xs)
    return jnp.moveaxis(o.reshape(seq, batch, heads, d_v), 0, 1)


def linear_mixer(u, layer, config):
    batch, seq, _ = u.shape
    heads = config["linear_num_value_heads"]
    key_heads = config["linear_num_key_heads"]
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]

    def projected(w, taps, heads):
        y = jax.nn.silu(short_convolution(u @ layer[w], layer[taps]))
        return y.reshape(batch, seq, heads, -1)

    q = l2_norm(projected("wq", "conv_q", key_heads)) * d_k ** -0.5
    k = l2_norm(projected("wk", "conv_k", key_heads))
    v = projected("wv", "conv_v", heads)
    # Value head j reads key head j // (heads / key_heads): the checkpoint's
    # repeat_interleave, written as a repeat.
    q = jnp.repeat(q, heads // key_heads, axis=2)
    k = jnp.repeat(k, heads // key_heads, axis=2)
    alpha = jnp.exp(-jnp.exp(layer["a_log"]) * jax.nn.softplus(
        u @ layer["wa"] + layer["dt_bias"]))
    beta = jax.nn.sigmoid(u @ layer["wb"])
    o = delta_rule(q, k, v, alpha, beta)
    normed = norm_plain(o, layer["o_norm"], config["rms_norm_eps"])
    return (normed.reshape(batch, seq, heads * d_v)
            * jax.nn.silu(u @ layer["wg"])) @ layer["wo"]


# -- the full layer -----------------------------------------------------------

def rotary(x, config):
    """x: [B, S, heads, D]: the first ``partial_rotary_factor`` D lanes of
    every head turned, pair i at position p by ``p theta^(-2i/d)`` over the
    rotary width d, the others as they were.  The pairs are taken apart by
    a reshape (strided slices are gathers to XLA, and their transposes
    scatter-adds)."""
    if config["rope_scaling"] is not None:
        raise ValueError("this reference turns by the plain frequencies")
    width = int(config["partial_rotary_factor"] * x.shape[-1])
    freq = float(config["rope_theta"]) ** (
        -2.0 * jnp.arange(width // 2, dtype=jnp.float32) / width)
    angle = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * freq[None, :])
    cos, sin = (t[None, :, None, :] for t in (jnp.cos(angle),
                                              jnp.sin(angle)))
    pairs = x[..., :width].reshape(*x.shape[:-1], -1, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1).reshape(*x.shape[:-1], width)
    return jnp.concatenate([turned, x[..., width:]], axis=-1)


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


def full_mixer(u, layer, config):
    batch, seq, _ = u.shape
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim, eps = config["head_dim"], config["rms_norm_eps"]
    q_and_gate = (u @ layer["wq"]).reshape(batch, seq, heads, 2 * dim)
    q, gate = q_and_gate[..., :dim], q_and_gate[..., dim:]
    k = (u @ layer["wk"]).reshape(batch, seq, kv_heads, dim)
    v = (u @ layer["wv"]).reshape(batch, seq, kv_heads, dim)
    q = rotary(norm_zero_centred(q, layer["q_norm"], eps), config)
    k = rotary(norm_zero_centred(k, layer["k_norm"], eps), config)
    # Query head j reads key-value head j // (n / m): written as a repeat.
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    out = causal_attention(q, k, v) * jax.nn.sigmoid(gate)
    return out.reshape(batch, seq, heads * dim) @ layer["wo"]


# -- the feed-forward ---------------------------------------------------------

def swiglu(x, w):
    """A SiLU-gated feed-forward on x: [B, S, H], ``ROWS`` rows at a
    time."""
    return by_rows(lambda rows: (jax.nn.silu(rows @ w["w_gate"])
                                 * (rows @ w["w_up"])) @ w["w_down"], x)


def routed_experts(u, layer, config):
    """(the held experts' part of the routed sum plus the gated shared
    expert, the balance loss)."""
    experts_over = layer["router"].shape[1]
    per_token = config["num_experts_per_tok"]
    probs = jax.nn.softmax(u @ layer["router"], axis=-1)
    gates, chosen = jax.lax.top_k(probs, per_token)
    if config["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    first = config["deployment"]["first_held_expert"]

    def add_expert(y, held):
        expert, index = held
        gate = jnp.sum(jnp.where(chosen == first + index, gates, 0.0),
                       axis=-1)
        return y + gate[..., None] * swiglu(u, expert), None

    experts = layer["experts"]
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(u),
                        (experts, jnp.arange(experts["w_gate"].shape[0])))
    counts = jnp.sum(jax.nn.one_hot(chosen, experts_over), axis=(0, 1, 2))
    share = jax.lax.stop_gradient(
        counts / (per_token * u.shape[0] * u.shape[1]))
    balance = experts_over * jnp.sum(share * jnp.mean(probs, axis=(0, 1)))
    shared = jax.nn.sigmoid(u @ layer["shared_gate"]) * swiglu(
        u, layer["shared"])
    return y + shared, balance


def decoder_layer(x, layer, index, config):
    """(x', the layer's balance loss)."""
    eps = config["rms_norm_eps"]
    mixer = linear_mixer if is_linear(index, config) else full_mixer
    h = x + mixer(norm_zero_centred(x, layer["norm_attn"], eps), layer,
                  config)
    y, balance = routed_experts(
        norm_zero_centred(h, layer["norm_mlp"], eps), layer, config)
    return h + y, balance


def hidden_states(params, tokens, config):
    """(the final normed states [B, S, H], the layers' balance losses)."""
    x = params["embed"][tokens]
    balance = []
    for index, layer in enumerate(params["layers"]):
        x, layer_balance = jax.checkpoint(
            lambda x, layer, index=index: decoder_layer(
                x, layer, index, config))(x, layer)
        balance.append(layer_balance)
    return (norm_zero_centred(x, params["norm_f"], config["rms_norm_eps"]),
            jnp.stack(balance))


def loss(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]`` plus alpha
    times the mean balance loss of the layers."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, balance = hidden_states(params, inputs, config)
    batch, seq, hidden = x.shape
    n_blocks = _blocks(seq, ROWS)

    @jax.checkpoint
    def block_nll(args):
        rows, wanted = args
        logits = rows @ params["lm_head"]
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
