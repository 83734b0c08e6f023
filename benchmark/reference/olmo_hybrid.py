"""Plain reference of the hybrid decoder the ``olmo-hybrid-7b`` cell trains:
gated delta-rule linear-attention layers (Gated DeltaNet; Yang, Kautz,
Hatamizadeh, arXiv:2412.06464) among softmax-attention layers, as
``layer_types`` of the configuration's file lists them.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision.
No kernel, no chunked form, nothing imported from the program: the
recurrence runs TOKEN BY TOKEN.  A linear layer, a head, with x the block's
input and ``*`` a causal depthwise convolution of ``linear_conv_kernel_dim``
taps (zero history before position 0, written as shifted multiply-adds)::

    q = l2norm(silu(conv_q * (x W_q))) d_k^-1/2     k = l2norm(silu(conv_k * (x W_k)))
    v = silu(conv_v * (x W_v))
    beta_t  = 2 sigmoid(x_t W_b)                     (linear_allow_neg_eigval; else 1 x)
    alpha_t = exp(-exp(A_log) softplus(x_t W_a + dt_bias))
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T         S_0 = 0
    o_t = S_t q_t
    y   = (rms_norm(o) * silu(x W_g)) W_o            one [d_v] scale for all heads

A softmax layer: ``num_attention_heads`` heads of ``head_dim`` (no grouped
keys), an RMSNorm over ALL of a token's q and over all of its k, no rotary
positions (``rope_parameters.rope_theta`` is null), causal softmax.  The
block is OLMo 2's (arXiv:2501.00656): the norm is on each sublayer's output
inside the residual, ``h = x + rms_norm(Mixer(x))``, ``h + rms_norm(FFN(h))``
with a SiLU-gated FFN; a final RMSNorm; an untied head; mean cross-entropy
of every position's next token.  The configuration's ``assumed`` says which
of these the published config states and which are the family's.

So that 8192 positions fit beside float32 weights and gradients, nothing of
which changes a number: the recurrence is a nested ``lax.scan``, ``TOKENS``
tokens to a checkpoint (a flat scan would keep a state a token, 18 GB);
attention takes ``QUERIES`` queries at a time against all keys (the scores
of 30 heads are 250 MB a block); the FFN and the head's loss take ``BLOCK``
rows at a time; each layer and each of those blocks under
``jax.checkpoint``.

Parameters are a plain tree: ``embed [V, H]``, ``layers`` (a list; a linear
layer: ``wq wk [H, heads * d_k]``, ``wv wg [H, heads * d_v]``, ``wa wb [H,
heads]``, ``conv_q conv_k conv_v [K, channels]``, ``a_log dt_bias [heads]``,
``o_norm [d_v]``, ``wo``; a softmax layer: ``wq wk wv wo``, ``q_norm k_norm
[heads * head_dim]``; both: ``norm_attn norm_mlp [H]``, ``w_gate w_up [H,
F]``, ``w_down [F, H]``), ``norm_f [H]``, ``lm_head [H, V]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 1024           # rows of the FFN and of the head's loss at a time
QUERIES = 256          # queries of softmax attention at a time
TOKENS = 128           # tokens of the recurrence to a checkpoint


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _blocks(seq: int, block: int) -> int:
    block = min(block, seq)
    if seq % block:
        raise ValueError(f"sequence {seq} is not a multiple of {block}")
    return seq // block


def by_rows(fn, x, block=BLOCK):
    """``fn`` of ``x [B, S, ..]`` a block of rows at a time, each block
    under a checkpoint."""
    batch, seq = x.shape[:2]
    n = _blocks(seq, block)
    rows = x.reshape(batch, n, seq // n, *x.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(jax.checkpoint(fn), rows)
    return out.swapaxes(0, 1).reshape(batch, seq, *out.shape[3:])


def short_convolution(x, taps):
    """``y[t] = sum_i taps[i] x[t - (K - 1) + i]``; x ``[B, S, C]``."""
    seq, k = x.shape[1], taps.shape[0]
    return sum(jnp.pad(x, ((0, 0), (k - 1 - i, 0), (0, 0)))[:, :seq] * taps[i]
               for i in range(k))


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, one token a step.  q, k ``[B, S, heads, d_k]``, v
    ``[B, S, heads, d_v]``, alpha, beta ``[B, S, heads]``; returns ``(o [B,
    S, heads, d_v]``, the largest ``|S|`` met)."""
    batch, seq, heads, d_k = q.shape
    d_v = v.shape[-1]

    def token(carry, x):
        state, largest = carry
        q, k, v, alpha, beta = x
        read = jnp.einsum("bhvk,bhk->bhv", state, k)          # S k
        kept = state - beta[..., None, None] * (
            read[..., :, None] * k[..., None, :])
        state = alpha[..., None, None] * kept + beta[..., None, None] * (
            v[..., :, None] * k[..., None, :])
        largest = jnp.maximum(largest, jnp.max(jnp.abs(state)))
        return (state, largest), jnp.einsum("bhvk,bhk->bhv", state, q)

    @jax.checkpoint
    def block(carry, xs):
        return jax.lax.scan(token, carry, xs)

    n = _blocks(seq, TOKENS)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(n, seq // n, *x.shape[:1],
                                             *x.shape[2:])
               for x in (q, k, v, alpha, beta))
    (_, largest), o = jax.lax.scan(
        block, (jnp.zeros((batch, heads, d_v, d_k), jnp.float32),
                jnp.float32(0)), xs)
    return jnp.moveaxis(o.reshape(seq, batch, heads, d_v), 0, 1), largest


def gates(x, layer, config):
    """``(alpha, beta) [B, S, heads]``."""
    alpha = jnp.exp(-jnp.exp(layer["a_log"]) * jax.nn.softplus(
        x @ layer["wa"] + layer["dt_bias"]))
    beta = jax.nn.sigmoid(x @ layer["wb"])
    return alpha, beta * (2.0 if config["linear_allow_neg_eigval"] else 1.0)


def linear_mixer(x, layer, config, with_stats=False):
    batch, seq, _ = x.shape
    heads = config["linear_num_value_heads"]
    key_heads = config["linear_num_key_heads"]
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]

    def projected(w, taps, heads):
        y = jax.nn.silu(short_convolution(x @ layer[w], layer[taps]))
        return y.reshape(batch, seq, heads, -1)

    q = l2_norm(projected("wq", "conv_q", key_heads)) * d_k ** -0.5
    k = l2_norm(projected("wk", "conv_k", key_heads))
    v = projected("wv", "conv_v", heads)
    if key_heads != heads:
        q = jnp.repeat(q, heads // key_heads, axis=2)
        k = jnp.repeat(k, heads // key_heads, axis=2)
    alpha, beta = gates(x, layer, config)
    o, largest = delta_rule(q, k, v, alpha, beta)
    normed = rms_norm(o, layer["o_norm"], config["rms_norm_eps"])
    y = (normed.reshape(batch, seq, heads * d_v)
         * jax.nn.silu(x @ layer["wg"])) @ layer["wo"]
    if with_stats:
        return y, {"alpha_mean": jnp.mean(alpha), "alpha_min": jnp.min(alpha),
                   "beta_over_one": jnp.mean(beta > 1.0),
                   "state_max": largest, "out_max": jnp.max(jnp.abs(o))}
    return y


def causal_attention(q, k, v):
    """q, k, v: [B, S, heads, D] -> [B, S, heads, D], softmax over the keys
    at or before each query, ``QUERIES`` queries at a time."""
    batch, seq, heads, dim = q.shape
    n = _blocks(seq, QUERIES)
    block = seq // n
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def one_block(args):
        q_block, first = args                       # [B, block, heads, D]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) / jnp.sqrt(
            jnp.float32(dim))
        keep = (first + jnp.arange(block))[:, None] >= key_pos[None, :]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    q_blocks = q.reshape(batch, n, block, heads, dim).swapaxes(0, 1)
    out = jax.lax.map(one_block, (q_blocks, jnp.arange(n) * block))
    return out.swapaxes(0, 1).reshape(batch, seq, heads, dim)


def softmax_mixer(x, layer, config):
    batch, seq, _ = x.shape
    heads, dim = config["num_attention_heads"], config["head_dim"]
    eps = config["rms_norm_eps"]
    q = rms_norm(x @ layer["wq"], layer["q_norm"], eps)
    k = rms_norm(x @ layer["wk"], layer["k_norm"], eps)
    v = x @ layer["wv"]
    q, k, v = (t.reshape(batch, seq, heads, dim) for t in (q, k, v))
    return causal_attention(q, k, v).reshape(batch, seq, heads * dim) @ layer[
        "wo"]


def decoder_layer(x, layer, kind, config):
    eps = config["rms_norm_eps"]
    mixer = linear_mixer if kind == "linear_attention" else softmax_mixer
    x = x + rms_norm(mixer(x, layer, config), layer["norm_attn"], eps)

    def ffn(rows):
        return (jax.nn.silu(rows @ layer["w_gate"]) * (rows @ layer["w_up"])
                ) @ layer["w_down"]

    return x + rms_norm(by_rows(ffn, x), layer["norm_mlp"], eps)


def loss(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs]
    for layer, kind in zip(params["layers"], config["layer_types"]):
        x = jax.checkpoint(
            lambda x, layer, kind=kind: decoder_layer(x, layer, kind, config)
        )(x, layer)
    x = rms_norm(x, params["norm_f"], config["rms_norm_eps"])

    batch, seq, hidden = x.shape
    n_blocks = _blocks(seq, BLOCK)

    @jax.checkpoint
    def block_nll(args):
        rows, wanted = args                         # [B, block, H], [B, block]
        logits = rows @ params["lm_head"]
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked[..., 0])

    rows = x.reshape(batch, n_blocks, seq // n_blocks, hidden).swapaxes(0, 1)
    wanted = targets.reshape(batch, n_blocks, seq // n_blocks).swapaxes(0, 1)
    return jnp.sum(jax.lax.map(block_nll, (rows, wanted))) / (batch * seq)


def loss_and_grads(params, tokens, config):
    """(loss, d loss / d params) in float32 at ``highest`` precision."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return jax.value_and_grad(loss)(params, tokens, config)


def layer_counters(params, tokens, config):
    """What each linear layer's gates and state do on ``tokens``: a list,
    a linear layer, of ``alpha_mean``, ``alpha_min``, ``beta_over_one``,
    ``state_max`` and ``out_max``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        x = params["embed"][tokens[:, :-1]]
        found = []
        for layer, kind in zip(params["layers"], config["layer_types"]):
            if kind == "linear_attention":
                found.append(linear_mixer(x, layer, config,
                                          with_stats=True)[1])
            x = decoder_layer(x, layer, kind, config)
        return found
