"""Plain reference of the decoder the ``laguna-s-2.1`` cell trains:
Laguna-S-2.1 (its ``config.json``) -- softmax layers that attend to all
their causal keys or through a sliding window, by ``layer_types``, each
kind with its own count of query heads over the same key-value heads and
its own rotary table, a per-head sigmoid gate on the attention's output, a
leading dense SwiGLU and then routed experts (top-K over all E, gates
renormalised and scaled) beside one shared expert -- with its loss.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, dense scores with the window as a mask, ``jax.lax.top_k``, no
recomputation that changes a number, nothing imported from the program.
Every number is a key of the configuration's file.  For layer l with n_l
query heads (``num_attention_heads_per_layer[l]``: 48 where
``layer_types[l]`` is ``full_attention``, 72 where ``sliding_attention``),
m = 8 key-value heads, D = 128, g_l = n_l / m, x the residual stream, N an
RMSNorm (eps ``rms_norm_eps``)::

    h = N_a(x);  q = h W_q [S, n_l, D];  k = h W_k, v = h W_v [S, m, D]
    q, k <- R_l(q), R_l(k)
    A_l(t) = {s <= t}  (full)   or   {s : 0 <= t - s < sliding_window}
    o[t, j] = sum_{s in A_l(t)} softmax_{A_l(t)}(q[t, j] . k[s, j // g_l]
                                                 / sqrt(D)) v[s, j // g_l]
    gamma = sigmoid(h W_g) [S, n_l];   o[t, j] <- gamma[t, j] o[t, j]
    y = x + [o[t, 1] .. o[t, n_l]] W_o
    u = N_m(y)
    mlp_layer_types[l] == "dense":   x' = y + SwiGLU(u)    (intermediate_size)
    "sparse":  p = softmax(u W_r) over all E = 256, in float32
               e_1..e_K the K = 10 largest;  w_k = p[e_k] / sum_j p[e_j]
               x' = y + moe_routed_scaling_factor sum_{k: e_k held} w_k E_{e_k}(u)
                      + S(u)          E_e, S: SwiGLU of moe_intermediate_size
    logits = N_f(x_L) W_head

    loss = cross-entropy + alpha mean_layers(balance)
    balance = E sum_e f[e] P[e]   f[e] = (the batch's assignments to e) / (K T),
                                  a constant; P[e] = mean_t p[t, e]

**R_l**, from ``rope_parameters[layer_types[l]]``: the first
``partial_rotary_factor * D`` lanes of a head turn and the rest pass
untouched.  Interleaved pairs ``(x[2i], x[2i+1])``, pair i at position p by
``p f_i``.  ``rope_type`` ``default``: ``f_i = theta^(-2i/d)`` over the
rotary width d.  ``yarn`` (Peng et al., arXiv:2309.00071): with
``r(b) = d ln(L0 / (2 pi b)) / (2 ln theta)``, low = floor(r(beta_fast)),
high = ceil(r(beta_slow)) (clamped to 0 .. d - 1) and ramp_i = clip((i -
low) / (high - low), 0, 1), ``f_i = theta^(-2i/d) ((1 - ramp_i) + ramp_i /
factor)``, and cos and sin are multiplied by ``attention_factor``.  The
published checkpoints turn half against half, a fixed permutation of the q
and k columns.

**The held experts**, as ``deepseek_v2_lite.py``: the parameters hold
``w_gate.shape[0]`` experts, ids ``deployment.first_held_expert`` onwards;
the router, the top-K, the gates' sum and the balance loss are over all E,
and what the absent experts would add is left out.  The shared expert and
the router are whole.

Attention runs ``BLOCK`` queries at a time against all keys and a
feed-forward ``ROWS`` positions at a time, each block, each layer and each
block of the head's loss under ``jax.checkpoint``, so
that 8192 positions fit beside the program in ``benchmark/compare.py``'s one
program: the backward pass repeats the forward's work and computes the same
numbers.

Parameters are a plain tree: ``embed [V, H]``; ``layers``, a list of
``norm_attn [H]``, ``wq [H, n_l D]``, ``wk wv [H, m D]``, ``wg [H, n_l]``,
``wo [n_l D, H]``, ``norm_mlp [H]`` and either ``w_gate w_up [H, F]``,
``w_down [F, H]`` or ``router [H, E]``, ``experts`` (``w_gate w_up [held,
H, F_e]``, ``w_down [held, F_e, H]``) and ``shared`` (a SwiGLU's three);
``norm_f [H]``; ``lm_head [H, V]``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.ouro import _blocks, rms_norm

BLOCK = 128            # queries at a time: [72 heads, 128, 8192] scores
ROWS = 1024            # rows of a feed-forward at a time


def frequencies(rope: dict, head_dim: int):
    """(f_i over the rotary width's pairs, what multiplies cos and sin)."""
    width = int(rope["partial_rotary_factor"] * head_dim)
    theta = float(rope["rope_theta"])
    pairs = jnp.arange(width // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * pairs / width)
    if rope["rope_type"] == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"no rotation of type {rope['rope_type']!r}")

    def pair_of(rotations):
        return (width * math.log(rope["original_max_position_embeddings"]
                                 / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rope["beta_slow"])), width - 1)
    ramp = jnp.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain * ((1.0 - ramp) + ramp / rope["factor"]),
            rope["attention_factor"])


def rotary(x, rope: dict):
    """x: [B, S, heads, D]: the first lanes of every head turned, the
    others as they were.  The pairs are taken apart by a reshape (strided
    slices are gathers to XLA, and their transposes scatter-adds)."""
    freq, scale = frequencies(rope, x.shape[-1])
    width = 2 * freq.shape[0]
    angle = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * freq[None, :])
    cos, sin = (scale * t[None, :, None, :] for t in (jnp.cos(angle),
                                                      jnp.sin(angle)))
    pairs = x[..., :width].reshape(*x.shape[:-1], -1, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1).reshape(*x.shape[:-1], width)
    return jnp.concatenate([turned, x[..., width:]], axis=-1)


def attention(q, k, v, window):
    """q, k, v: [B, S, heads, D] (k and v already repeated to the query
    heads) -> [B, S, heads, D]: softmax over the keys at or before each
    query and, with ``window``, fewer than ``window`` positions before
    it."""
    batch, seq, heads, dim = q.shape
    block = min(BLOCK, seq)
    n_blocks = seq // block
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def one_block(args):
        q_block, first = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) * dim ** -0.5
        behind = (first + jnp.arange(block))[:, None] - key_pos[None, :]
        keep = behind >= 0
        if window is not None:
            keep = keep & (behind < window)
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    q_blocks = q.reshape(batch, n_blocks, block, heads, dim).swapaxes(0, 1)
    out = jax.lax.map(one_block, (q_blocks, jnp.arange(n_blocks) * block))
    return out.swapaxes(0, 1).reshape(batch, seq, heads, dim)


def attention_layer(h, layer, index, config):
    """The mixer of layer ``index`` on the normed state h: [B, S, H]."""
    batch, seq, _ = h.shape
    dim, kv_heads = config["head_dim"], config["num_key_value_heads"]
    heads = config["num_attention_heads_per_layer"][index]
    kind = config["layer_types"][index]
    rope = config["rope_parameters"][kind]
    window = (config["sliding_window"] if kind == "sliding_attention"
              else None)
    q = (h @ layer["wq"]).reshape(batch, seq, heads, dim)
    k = (h @ layer["wk"]).reshape(batch, seq, kv_heads, dim)
    v = (h @ layer["wv"]).reshape(batch, seq, kv_heads, dim)
    q, k = rotary(q, rope), rotary(k, rope)
    # Query head j reads key-value head j // (n_l / m): written as a repeat.
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    out = attention(q, k, v, window)
    if config["gating"] == "per-head":
        out = out * jax.nn.sigmoid(h @ layer["wg"])[..., None]
    elif config["gating"]:
        raise ValueError(f"no gate of kind {config['gating']!r}")
    return out.reshape(batch, seq, heads * dim) @ layer["wo"]


def swiglu(x, w):
    """A SiLU-gated feed-forward on x: [B, S, H], ``ROWS`` positions at a
    time and each block under ``jax.checkpoint`` (the dense layer's
    ``[8192, 12288]`` float32 intermediates are 400 MB apiece)."""
    batch, seq, hidden = x.shape
    rows = min(ROWS, seq)

    @jax.checkpoint
    def one_block(block):
        return (jax.nn.silu(block @ w["w_gate"])
                * (block @ w["w_up"])) @ w["w_down"]

    blocks = x.reshape(batch, seq // rows, rows, hidden).swapaxes(0, 1)
    return jax.lax.map(one_block, blocks).swapaxes(0, 1).reshape(x.shape)


def routed_experts(u, layer, config):
    """(the held experts' scaled part of the routed sum plus the shared
    expert, the balance loss)."""
    experts_over = layer["router"].shape[1]
    per_token = config["num_experts_per_tok"]
    probs = jax.nn.softmax(u @ layer["router"], axis=-1)
    gates, chosen = jax.lax.top_k(probs, per_token)
    if config["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * config["moe_routed_scaling_factor"]
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
    return y + swiglu(u, layer["shared"]), balance


def decoder_layer(x, layer, index, config):
    """(x, the layer's balance loss: 0 for a dense layer)."""
    eps = config["rms_norm_eps"]
    x = x + attention_layer(rms_norm(x, layer["norm_attn"], eps), layer,
                            index, config)
    u = rms_norm(x, layer["norm_mlp"], eps)
    if config["mlp_layer_types"][index] == "dense":
        return x + swiglu(u, layer), jnp.float32(0.0)
    y, balance = routed_experts(u, layer, config)
    return x + y, balance


def hidden_states(params, tokens, config):
    """(the final normed states [B, S, H], the routed layers' balance
    losses)."""
    x = params["embed"][tokens]
    balance = []
    for index, layer in enumerate(params["layers"]):
        x, layer_balance = jax.checkpoint(
            lambda x, layer, index=index: decoder_layer(
                x, layer, index, config))(x, layer)
        if config["mlp_layer_types"][index] != "dense":
            balance.append(layer_balance)
    return (rms_norm(x, params["norm_f"], config["rms_norm_eps"]),
            jnp.stack(balance))


def loss(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]`` plus alpha
    times the mean balance loss of the routed layers."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, balance = hidden_states(params, inputs, config)
    batch, seq, hidden = x.shape
    n_blocks = _blocks(seq)

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
