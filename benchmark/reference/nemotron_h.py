"""Plain reference of the decoder the ``nemotron-3-nano-30b-a3b`` cell
trains: NVIDIA-Nemotron-3-Nano-30B-A3B (its ``config.json``, ``model_type``
``nemotron_h``) -- a stack whose layer is ONE sublayer behind one RMSNorm with
one residual add, a Mamba-2 state-space mixer (``M``; Dao & Gu,
arXiv:2405.21060), routed relu^2 experts behind a sigmoid router whose choice
a bias corrects (``E``), or grouped-query softmax attention without a
rotation (``*``), as ``hybrid_override_pattern`` spells it -- with its loss.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no chunked form (the recurrence runs TOKEN BY TOKEN), the
convolution as shifted adds, the causal softmax as a mask, the held experts
as a dense loop, ``jax.lax.top_k``, nothing imported from the program.  Every
number is a key of the configuration's file.  x is the residual stream, eps
``layer_norm_epsilon``, N a plain RMSNorm (``u / rms(u) * gamma``)::

    every layer:  x' = x + Sublayer(N(x))
    embedding; ``num_hidden_layers`` layers; N; an untied head

**M** (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``, I = H P; N_s =
``ssm_state_size``; G = ``n_groups``, head h reads group ``h // (H / G)``; K =
``conv_kernel`` taps), u the normed state::

    [z | xBC | dt] = u W_in                z [I], xBC [I + 2 G N_s], dt [H]
    xBC = silu(conv_K(xBC) + b_conv)       causal, depthwise, zero history
    [v | B | C] = xBC                      v [H, P], B, C [G, N_s]
    D_t = softplus(dt_t + dt_bias)     a_t = exp(-exp(A_log) D_t)
    S_t = a_t S_{t-1} + D_t v_t B_t^T      S [P, N_s] a head, S_0 = 0
    y_t = S_t C_t + D v_t
    out = (N_G(y * silu(z)) * w) W_out     the gate FIRST, then the norm over
                                           each group's I / G lanes

**E** (``n_routed_experts`` of ``deployment.num_experts_published`` held, K =
``num_experts_per_tok``), router in float32, b the choice bias (zeros: the
comparison is made on the state as initialised)::

    s = sigmoid(u W_r)                     over all the published experts
    e_1..e_K = the K largest of s + b;  g_k = s[e_k] / sum_j s[e_j] x
        ``routed_scaling_factor``          (``norm_topk_prob``)
    y = sum_{k: e_k held} g_k E_{e_k}(u) + Sh(u)     E(u) = relu(u W_up)^2 W_down

``n_group`` 1 and ``topk_group`` 1 are one group: group-limited selection is
the identity.  What an absent expert would add is left out.

**\\*** (n = ``num_attention_heads`` query heads over m =
``num_key_value_heads`` of D = ``head_dim``): ``softmax(q k^T D^-1/2 +
causal) v W_o``, no bias, no QK-norm, no gate, NO ROTATION (``assumed.
no_positional_embedding``).

**Loss**: mean next-token cross-entropy of every position + ``assumed.
aux_loss_alpha`` x the mean over the routed layers of the batch-wise balance
loss ``E sum_e f[e] P[e]`` (f[e] the share of the batch's assignments that
chose e, a constant; P[e] the mean over the batch of ``s[e] / sum_j s[j]``).

**Departures from the published description**, each a re-arrangement and none
a change of function: none in the layers' arithmetic; B and C of a group are
stored once a group (the checkpoint's layout too); the held experts' two
matrices are stacked ``[held, ..]``.

So that 8192 positions fit beside the program in ``benchmark/compare.py``'s
one program, nothing of which changes a number: the recurrence is a nested
``lax.scan``, ``TOKENS`` tokens to a checkpoint; attention takes ``QUERIES``
queries at a time against all keys; a feed-forward and the head's loss
``ROWS`` rows at a time; each layer and each of those blocks under
``jax.checkpoint``.

Parameters are a plain tree: ``embed [V, H]``; ``layers``, a list, each with
``norm [H]`` and, an M layer: ``in_proj [H, 2 I + 2 G N_s + heads]``,
``conv_w [K, I + 2 G N_s]``, ``conv_b``, ``a_log dt_bias d [heads]``,
``norm_w [I]``, ``out_proj [I, H]``; an E layer: ``router [H, E]``,
``experts`` (``w_up [held, H, F]``, ``w_down [held, F, H]``), ``shared``
(``w_up``, ``w_down``); a * layer: ``wq [H, n D]``, ``wk wv [H, m D]``, ``wo
[n D, H]``; ``norm_f [H]``; ``lm_head [H, V]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS = 1024            # rows of a feed-forward and of the head's loss at a time
QUERIES = 128          # queries of softmax attention at a time
TOKENS = 128           # tokens of the recurrence to a checkpoint


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


# -- M: the state-space layer -------------------------------------------------

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


def mamba_mixer(u, layer, config):
    batch, seq, _ = u.shape
    heads, width = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
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
    y = state_space_scan(v, step, decay, b, c) + layer["d"][:, None] * v
    gated = (y.reshape(batch, seq, inner) * jax.nn.silu(z)).reshape(
        batch, seq, groups, inner // groups)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True)
        + config["layer_norm_epsilon"])
    return (normed.reshape(batch, seq, inner) * layer["norm_w"]
            ) @ layer["out_proj"]


# -- *: the attention layer ---------------------------------------------------

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
    dim = config["head_dim"]
    q = (u @ layer["wq"]).reshape(batch, seq, heads, dim)
    k = (u @ layer["wk"]).reshape(batch, seq, kv_heads, dim)
    v = (u @ layer["wv"]).reshape(batch, seq, kv_heads, dim)
    # Query head j reads key-value head j // (n / m): written as a repeat.
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    return causal_attention(q, k, v).reshape(batch, seq,
                                             heads * dim) @ layer["wo"]


# -- E: the routed layer ------------------------------------------------------

def relu2_mlp(x, w):
    """``relu(x W_up)^2 W_down`` on x: [B, S, H], ``ROWS`` rows at a time."""
    return by_rows(lambda rows: jnp.square(jax.nn.relu(rows @ w["w_up"]))
                   @ w["w_down"], x)


def routed_experts(u, layer, config, bias=None):
    """(the held experts' part of the routed sum plus the shared expert, the
    balance loss).  ``bias [E]``: the choice bias; None: zeros."""
    experts_over = layer["router"].shape[1]
    per_token = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ layer["router"])
    corrected = scores if bias is None else scores + bias
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(corrected), per_token)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * config["routed_scaling_factor"]
    first = config["deployment"]["first_held_expert"]

    def add_expert(y, held):
        expert, index = held
        gate = jnp.sum(jnp.where(chosen == first + index, gates, 0.0),
                       axis=-1)
        return y + gate[..., None] * relu2_mlp(u, expert), None

    experts = layer["experts"]
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(u),
                        (experts, jnp.arange(experts["w_up"].shape[0])))
    counts = jnp.sum(jax.nn.one_hot(chosen, experts_over), axis=(0, 1, 2))
    share = jax.lax.stop_gradient(
        counts / (per_token * u.shape[0] * u.shape[1]))
    probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    balance = experts_over * jnp.sum(share * jnp.mean(probs, axis=(0, 1)))
    return y + relu2_mlp(u, layer["shared"]), balance


SUBLAYERS = {"M": mamba_mixer, "*": attention_mixer}


def decoder_layer(x, layer, kind, config):
    """(x', the layer's balance loss or None)."""
    u = rms_norm(x, layer["norm"], config["layer_norm_epsilon"])
    if kind == "E":
        y, balance = routed_experts(u, layer, config)
        return x + y, balance
    return x + SUBLAYERS[kind](u, layer, config), None


def hidden_states(params, tokens, config):
    """(the final normed states [B, S, H], the routed layers' balance
    losses)."""
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != len(params["layers"]):
        raise ValueError(f"{len(params['layers'])} layers for the pattern "
                         f"{pattern!r}")
    x = params["embed"][tokens]
    balance = []
    for kind, layer in zip(pattern, params["layers"]):
        x, layer_balance = jax.checkpoint(
            lambda x, layer, kind=kind: decoder_layer(
                x, layer, kind, config))(x, layer)
        if layer_balance is not None:
            balance.append(layer_balance)
    return (rms_norm(x, params["norm_f"], config["layer_norm_epsilon"]),
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
