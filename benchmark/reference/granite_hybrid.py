"""Plain reference of the decoder the ``granite-4.0-h-micro`` cell trains:
IBM Granite-4.0-H-Micro (its ``config.json``, ``model_type``
``granitemoehybrid``) -- a stack whose every layer is a mixer AND a dense
SwiGLU, each behind an RMSNorm of its own and each added to the residual
stream times ``residual_multiplier``; the mixer a Mamba-2 state-space layer
(``"mamba"``; Dao & Gu, arXiv:2405.21060) or grouped-query softmax attention
without a position (``"attention"``), as ``layer_types`` names it; the
embedding times ``embedding_multiplier``, the softmax's scale
``attention_multiplier``, a head tied to the embedding whose logits are
divided by ``logits_scaling`` -- with its loss.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no chunked form (the recurrence runs TOKEN BY TOKEN), the
convolution as shifted adds, the causal softmax as a mask, nothing imported
from the program.  Every number is a key of the configuration's file.  x is
the residual stream, eps ``rms_norm_eps``, N a plain RMSNorm (``u / rms(u) *
gamma``), e, a, r, s the four multipliers in the order above::

    x_0 = e E[tokens]
    every layer:  h = x + r Mixer(N_1(x));    x' = h + r MLP(N_2(h))
    logits = N_f(x_L) E^T / s

**"mamba"** (H = ``mamba_n_heads`` heads of P = ``mamba_d_head``, I = H P; N_s
= ``mamba_d_state``; G = ``mamba_n_groups``, head h reads group ``h // (H /
G)``; K = ``mamba_d_conv`` taps), u the normed state::

    [z | xBC | dt] = u W_in                z [I], xBC [I + 2 G N_s], dt [H]
    xBC = silu(conv_K(xBC) + b_conv)       causal, depthwise, zero history
    [v | B | C] = xBC                      v [H, P], B, C [G, N_s]
    D_t = softplus(dt_t + dt_bias)     a_t = exp(-exp(A_log) D_t)
    S_t = a_t S_{t-1} + D_t v_t B_t^T      S [P, N_s] a head, S_0 = 0
    y_t = S_t C_t + D v_t
    out = (N_G(y * silu(z)) * w) W_out     the gate FIRST, then the norm over
                                           each group's I / G lanes (G = 1:
                                           over all I at once)

**"attention"** (n = ``num_attention_heads`` query heads over m =
``num_key_value_heads`` of D = ``head_dim``): ``softmax(a q k^T +
causal) v W_o`` with a = ``attention_multiplier`` IN PLACE of ``D^-1/2``; no
bias, no QK-norm, no gate, NO ROTATION (``position_embedding_type``
``"nope"``; ``rope_theta`` is read by nothing).

**MLP**, every layer: ``(silu(u W_g) * u W_u) W_d`` at
``shared_intermediate_size`` (``num_local_experts`` 0: there is no routed
block, and ``intermediate_size`` is read by nothing).

**Loss**: mean next-token cross-entropy of every position.

**Departures from the published modelling code**, each a re-arrangement and
none a change of function: the MLP's two input matrices are two leaves here
(the checkpoint stores them as one ``[2 F, H]``); matrices are ``[in, out]``;
the filter's taps ``[K, C]``; B and C of a group are stored once a group (the
checkpoint's layout too); the gated norm is written with a group count,
which at ``mamba_n_groups`` 1 is the family's norm over the whole inner
width.

So that 8192 positions fit beside the program in ``benchmark/compare.py``'s
one program, nothing of which changes a number: the recurrence is a nested
``lax.scan``, ``TOKENS`` tokens to a checkpoint; attention takes ``QUERIES``
queries at a time against all keys; the MLP and the head's loss ``ROWS`` rows
at a time; each layer and each of those blocks under ``jax.checkpoint``.

Parameters are a plain tree: ``embed [V, H]``; ``layers``, a list, each with
``norm_attn norm_mlp [H]``, ``w_gate w_up [H, F]``, ``w_down [F, H]`` and, a
"mamba" layer: ``in_proj [H, 2 I + 2 G N_s + heads]``, ``conv_w [K, I + 2 G
N_s]``, ``conv_b``, ``a_log dt_bias d [heads]``, ``norm_w [I]``, ``out_proj
[I, H]``; an "attention" layer: ``wq [H, n D]``, ``wk wv [H, m D]``, ``wo [n
D, H]``; ``norm_f [H]``.  There is no head of its own: it is ``embed``'s
transpose.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS = 1024            # rows of the MLP and of the head's loss at a time
QUERIES = 128          # queries of softmax attention at a time
TOKENS = 256           # tokens of the recurrence to a checkpoint


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


# -- "mamba": the state-space mixer -------------------------------------------

def short_convolution(x, taps, bias):
    """``y[t] = sum_i taps[i] x[t - (K - 1) + i] + bias``; x ``[B, S, C]``."""
    seq, k = x.shape[1], taps.shape[0]
    return bias + sum(
        jnp.pad(x, ((0, 0), (k - 1 - i, 0), (0, 0)))[:, :seq] * taps[i]
        for i in range(k))


def state_space_scan(v, step, decay, b, c):
    """The recurrence, one token a step.  v ``[B, S, heads, P]``, step and
    decay ``[B, S, heads]``, b, c ``[B, S, heads, N]`` (already copied to the
    heads) -> ``y [B, S, heads, P]`` without the skip."""
    batch, seq, heads, width = v.shape

    def token(state, x):
        v, step, decay, b, c = x
        state = decay[..., None, None] * state + (
            (step[..., None] * v)[..., :, None] * b[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    n = _blocks(seq, TOKENS)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(n, seq // n, *x.shape[:1],
                                             *x.shape[2:])
               for x in (v, step, decay, b, c))
    _, y = jax.lax.scan(
        block, jnp.zeros((batch, heads, width, b.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(y.reshape(seq, batch, heads, width), 0, 1)


def gate_then_norm(y, z, weight, groups, eps):
    """``N_G(y silu(z)) w``: the gate FIRST, then the norm over each of
    ``groups`` runs of lanes; y, z ``[B, S, I]``."""
    gated = (y * jax.nn.silu(z)).reshape(*y.shape[:-1], groups, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    return normed.reshape(y.shape) * weight


def skip(y, v, d):
    """``y + D v``, D one scalar a head; y, v ``[B, S, heads, P]``."""
    return y + d[:, None] * v


def mamba_mixer(u, layer, config):
    batch, seq, _ = u.shape
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    groups, state = config["mamba_n_groups"], config["mamba_d_state"]
    inner, bc = heads * width, groups * state
    projected = u @ layer["in_proj"]
    z = projected[..., :inner]
    xbc = projected[..., inner:2 * inner + 2 * bc]
    dt = projected[..., 2 * inner + 2 * bc:]
    xbc = jax.nn.silu(short_convolution(xbc, layer["conv_w"],
                                        layer["conv_b"]))
    v = xbc[..., :inner].reshape(batch, seq, heads, width)
    # Head h reads group h // (heads / groups): written as a repeat.
    b, c = (jnp.repeat(t.reshape(batch, seq, groups, state),
                       heads // groups, axis=2)
            for t in (xbc[..., inner:inner + bc], xbc[..., inner + bc:]))
    step = jax.nn.softplus(dt + layer["dt_bias"])
    decay = jnp.exp(-jnp.exp(layer["a_log"]) * step)
    y = skip(state_space_scan(v, step, decay, b, c), v, layer["d"])
    return gate_then_norm(y.reshape(batch, seq, inner), z, layer["norm_w"],
                          groups, config["rms_norm_eps"]) @ layer["out_proj"]


# -- "attention": the softmax mixer -------------------------------------------

def causal_attention(q, k, v, scale):
    """q, k, v: [B, S, heads, D] (k and v already repeated to the query
    heads) -> [B, S, heads, D], softmax of ``scale q k^T`` over the keys at
    or before each query, ``QUERIES`` queries at a time."""
    batch, seq, heads, dim = q.shape
    n = _blocks(seq, QUERIES)
    block = seq // n
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def one_block(args):
        q_block, first = args                       # [B, block, heads, D]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) * scale
        keep = (first + jnp.arange(block))[:, None] >= key_pos[None, :]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    q_blocks = q.reshape(batch, n, block, heads, dim).swapaxes(0, 1)
    out = jax.lax.map(one_block, (q_blocks, jnp.arange(n) * block))
    return out.swapaxes(0, 1).reshape(batch, seq, heads, dim)


def softmax_scale(config):
    """What multiplies ``q k^T``: the published ``attention_multiplier``."""
    return config["attention_multiplier"]


def attention_mixer(u, layer, config):
    batch, seq, _ = u.shape
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim = config["head_dim"]
    q = (u @ layer["wq"]).reshape(batch, seq, heads, dim)
    k = (u @ layer["wk"]).reshape(batch, seq, kv_heads, dim)
    v = (u @ layer["wv"]).reshape(batch, seq, kv_heads, dim)
    # Query head j reads key-value head j // (n / m): written as a repeat.
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    return causal_attention(q, k, v, softmax_scale(config)).reshape(
        batch, seq, heads * dim) @ layer["wo"]


# -- the MLP, the layer, the stack --------------------------------------------

def swiglu(x, layer):
    """``(silu(x W_g) * x W_u) W_d`` on x: [B, S, H], ``ROWS`` rows at a
    time."""
    return by_rows(lambda rows: (jax.nn.silu(rows @ layer["w_gate"])
                                 * (rows @ layer["w_up"])) @ layer["w_down"],
                   x)


MIXERS = {"mamba": mamba_mixer, "attention": attention_mixer}


def residual_scale(config):
    """What multiplies a sublayer's output before the add."""
    return config["residual_multiplier"]


def decoder_layer(x, layer, kind, config):
    eps, r = config["rms_norm_eps"], residual_scale(config)
    h = x + r * MIXERS[kind](rms_norm(x, layer["norm_attn"], eps), layer,
                             config)
    return h + r * swiglu(rms_norm(h, layer["norm_mlp"], eps), layer)


def embedded(params, tokens, config):
    """``x_0``: the embedding's rows times ``embedding_multiplier``."""
    return config["embedding_multiplier"] * params["embed"][tokens]


def hidden_states(params, tokens, config):
    """The final normed states ``[B, S, H]``."""
    kinds = config["layer_types"]
    if len(kinds) != len(params["layers"]):
        raise ValueError(f"{len(params['layers'])} layers for the types "
                         f"{kinds!r}")
    x = embedded(params, tokens, config)
    for kind, layer in zip(kinds, params["layers"]):
        x = jax.checkpoint(lambda x, layer, kind=kind: decoder_layer(
            x, layer, kind, config))(x, layer)
    return rms_norm(x, params["norm_f"], config["rms_norm_eps"])


def head_logits(hidden, embed, config):
    """``hidden E^T / logits_scaling``: the tied head on normed states, over
    whatever rows of the vocabulary ``embed`` holds."""
    return hidden @ embed.T / config["logits_scaling"]


def loss(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = hidden_states(params, inputs, config)
    batch, seq, hidden = x.shape
    n_blocks = _blocks(seq, ROWS)

    @jax.checkpoint
    def block_nll(args):
        rows, wanted = args
        logits = head_logits(rows, params["embed"], config)
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
