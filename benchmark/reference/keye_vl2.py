"""Plain reference of the decoder the ``keye-vl-2.0-30b-a3b`` cell trains:
the language model of Keye-VL-2.0-30B-A3B (its ``config.json``; the vision
tower is no part of it) -- grouped-query attention with QK-norm over the
keys a learned indexer selects (DeepSeek-V3.2-Exp's sparse attention, sized
by ``sa_config``), and a layer of routed experts with renormalised gates
and no shared expert -- with its three losses.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, dense scores, ``jnp.where`` on the selection, ``jax.lax.top_k``,
nothing imported from the program.  Every number is a key of the
configuration's file.  With H = 2048, n = 32 query heads and m = 4
key-value heads of D = 128, ``sa_config``'s n_I = 16 index heads of d_I = 64
and one index key a token, topk = 2048, E = 128 experts routed over, K = 8,
u = RMSNorm(x) and u_ = stop_gradient(u)::

    q = RoPE(RMSNorm_D(u W_q))  [n, D]     k = RoPE(RMSNorm_D(u W_k))  [m, D]
    v = u W_v  [m, D]                      (the two norms' scales: [D])
    q_I = RoPE_I(u_ W_Iq) [n_I, d_I]   k_I = RoPE_I(u_ W_Ik) [d_I]   w = u_ W_Iw
    I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])              s <= t
    S_t = the min(t + 1, topk) keys s <= t of largest I[t, s]
    o[t, h] = sum_{s in S_t} softmax_{S_t}(q[t, h] . k[s, h // 8] / sqrt(D))
              v[s, h // 8];        y = x + [o[t, 1] .. o[t, n]] W_o

    u' = RMSNorm(y);  p = softmax(u' W_r) over all E, in float32
    e_1..e_K the K largest;  g_k = p[e_k] / sum_k p[e_k]
    out = y + sum_{k: e_k held} g_k E_{e_k}(u'),   E_e a SwiGLU of width 768

    loss = cross-entropy + alpha mean_layers(balance) + lambda mean_layers(L_I)
    balance = E sum_e f[e] P[e]   f[e] = (the batch's assignments to e) / (K T),
                                  a constant; P[e] = mean_t p[t, e]
    L_I = mean_t KL(pbar[t, .] || softmax_{s in S_t}(c I[t, s])),  c = (n_I d_I)^(-1/2)
    pbar[t, s] = stop_gradient(mean_h softmax_{S_t}(q[t, h] . k[s, h // 8] / sqrt(D)))

**The tie rule.**  S_t is ``jax.lax.top_k`` of row t of I with the keys
s > t at -inf: the largest values, and among equal values the lower
position first.  -0.0 counts as 0.0 (a row of relus that are all off can sum
to either).  While t < topk every causal key is in.

**RoPE.**  Interleaved pairs ``(x[2i], x[2i+1])``, pair i of a head turning
by ``p theta^(-2i/D)`` at position p.  ``mrope_section`` names three
position streams; for text all three are the token's position, so this is
plain RoPE.  RoPE_I turns the indexer's 32 pairs by the FIRST 32 of those 64
frequencies (``assumed.index_rope`` in the configuration's file).

**The stop-gradients** make two models that share a forward pass: W_Iq,
W_Ik and W_Iw get their gradient from L_I alone (the selection has none,
and pbar is a target), every other parameter from the other two terms
alone (u_ is a constant to L_I).

**The held experts**, as ``deepseek_v2_lite.py``: the parameters hold
``w_gate.shape[0]`` experts, ids ``deployment.first_held_expert`` onwards;
the router, the top-K, the gates' sum and the balance loss are over all E.

Attention runs ``BLOCK`` queries at a time against all keys, each block,
each layer and each block of the head's loss under ``jax.checkpoint``, so
that 8192 positions fit.  ``selection`` returns what each layer selected,
for the job's agreement counter.

Parameters are a plain tree: ``embed [V, H]``; ``layers``, a list of
``norm_attn [H]``, ``wq [H, n D]``, ``wk wv [H, m D]``, ``q_norm k_norm
[D]``, ``wo [n D, H]``, ``index_wq [H, n_I d_I]``, ``index_wk [H, d_I]``,
``index_ww [H, n_I]``, ``norm_mlp [H]``, ``router [H, E]`` and ``experts``
(``w_gate w_up [held, H, F]``, ``w_down [held, F, H]``); ``norm_f [H]``;
``lm_head [H, V]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.ouro import _blocks, rms_norm

BLOCK = 512            # queries at a time: [32 heads, 512, 8192] scores


def rotary(x, inv_freq):
    """x: [B, S, heads, 2 len(inv_freq)]; position p turns pair i,
    ``(x[2i], x[2i+1])``, by ``p inv_freq[i]``.  The pairs are taken apart
    by a reshape, not by strided slices: those are gathers to XLA, whose
    transposes are scatter-adds."""
    angle = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * inv_freq[None, :])
    cos, sin = (t[None, :, None, :] for t in (jnp.cos(angle),
                                              jnp.sin(angle)))
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def selected_keys(index_scores, first, topk):
    """``[B, block, S]`` bool: row r (query ``first + r``) takes the
    ``min(t + 1, topk)`` largest of its scores at keys s <= t, by
    ``jax.lax.top_k``'s rule.  Read off ``top_k``'s own answer without a
    scatter of its two thousand positions a row (XLA:TPU scatters one
    element at a time): a key is in if its score is above the last value
    ``top_k`` returned, or equals it at a position no later than the last
    position ``top_k`` returned with that value -- among equals it takes
    the lower positions first, so those are the ones."""
    block, seq = index_scores.shape[1:]
    position = jnp.arange(seq)
    causal = (first + jnp.arange(block))[:, None] >= position[None, :]
    scores = jnp.where(causal, jnp.where(index_scores == 0.0, 0.0,
                                         index_scores), -jnp.inf)
    values, chosen = jax.lax.top_k(scores, min(topk, seq))
    least = values[..., -1:]
    last_tied = jnp.max(jnp.where(values == least, chosen, -1), axis=-1,
                        keepdims=True)
    taken = (scores > least) | ((scores == least) & (position <= last_tied))
    return taken & causal


def sparse_attention(q, k, v, q_i, k_i, w, config):
    """(o [B, S, n, D], the sum over queries of the indexer's KL, selected
    [B, S, S]); k and v already repeated to the n query heads."""
    batch, seq, heads, dim = q.shape
    sparse = config["sa_config"]
    scale_i = (sparse["indexer_num_heads"] * sparse["indexer_head_dim"]) ** -0.5
    block = min(BLOCK, seq)
    n_blocks = seq // block

    @jax.checkpoint
    def one_block(args):
        q_blk, qi_blk, w_blk, first = args
        index = jnp.einsum("bqn,bnqk->bqk", w_blk, jax.nn.relu(
            jnp.einsum("bqnd,bkd->bnqk", qi_blk, k_i)))
        taken = selected_keys(jax.lax.stop_gradient(index), first,
                              sparse["topk"])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * dim ** -0.5
        probs = jax.nn.softmax(
            jnp.where(taken[:, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
        log_pi = jax.nn.log_softmax(
            jnp.where(taken, index * scale_i, -jnp.inf), axis=-1)
        there = taken & (target > 0.0)
        kl = jnp.where(there, target * (
            jnp.log(jnp.where(there, target, 1.0))
            - jnp.where(there, log_pi, 0.0)), 0.0)
        return out, jnp.sum(kl), taken

    def blocks(x):
        return x.reshape(batch, n_blocks, block, *x.shape[2:]).swapaxes(0, 1)

    out, kl, taken = jax.lax.map(
        one_block, (blocks(q), blocks(q_i), blocks(w),
                    jnp.arange(n_blocks) * block))
    return (out.swapaxes(0, 1).reshape(batch, seq, heads, dim),
            jnp.sum(kl), taken.swapaxes(0, 1).reshape(batch, seq, seq))


def attention_layer(x, layer, config):
    """(attention's output [B, S, H], the layer's L_I, selected)."""
    batch, seq, _ = x.shape
    heads, dim = config["num_attention_heads"], config["head_dim"]
    kv_heads = config["num_key_value_heads"]
    sparse = config["sa_config"]
    n_i, d_i = sparse["indexer_num_heads"], sparse["indexer_head_dim"]
    eps = config["rms_norm_eps"]
    inv_freq = float(config["rope_theta"]) ** (
        -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    q = (x @ layer["wq"]).reshape(batch, seq, heads, dim)
    k = (x @ layer["wk"]).reshape(batch, seq, kv_heads, dim)
    v = (x @ layer["wv"]).reshape(batch, seq, kv_heads, dim)
    # Query head h reads key-value head h // (n / m): written as a repeat,
    # before the norm and the rotation (which act on a head at a time).
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    q = rotary(rms_norm(q, layer["q_norm"], eps), inv_freq)
    k = rotary(rms_norm(k, layer["k_norm"], eps), inv_freq)
    still = jax.lax.stop_gradient(x)
    q_i = rotary((still @ layer["index_wq"]).reshape(batch, seq, n_i, d_i),
                 inv_freq[:d_i // 2])
    k_i = rotary((still @ layer["index_wk"])[:, :, None, :],
                 inv_freq[:d_i // 2])[:, :, 0]
    w = still @ layer["index_ww"]
    out, kl, taken = sparse_attention(q, k, v, q_i, k_i, w, config)
    return (out.reshape(batch, seq, heads * dim) @ layer["wo"],
            kl / (batch * seq), taken)


def swiglu(x, w):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def routed_experts(x, layer, config):
    """(the held experts' part of the routed sum, the balance loss)."""
    experts_over = layer["router"].shape[1]
    per_token = config["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ layer["router"], axis=-1)
    gates, chosen = jax.lax.top_k(probs, per_token)
    if config["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    first = config["deployment"]["first_held_expert"]

    def add_expert(y, held):
        expert, index = held
        gate = jnp.sum(jnp.where(chosen == first + index, gates, 0.0),
                       axis=-1)
        return y + gate[..., None] * swiglu(x, expert), None

    experts = layer["experts"]
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (experts, jnp.arange(experts["w_gate"].shape[0])))
    counts = jnp.sum(jax.nn.one_hot(chosen, experts_over), axis=(0, 1, 2))
    share = jax.lax.stop_gradient(
        counts / (per_token * x.shape[0] * x.shape[1]))
    balance = experts_over * jnp.sum(share * jnp.mean(probs, axis=(0, 1)))
    return y, balance


def decoder_layer(x, layer, config):
    """(x, the layer's balance loss, its L_I, selected)."""
    eps = config["rms_norm_eps"]
    attended, index_loss, taken = attention_layer(
        rms_norm(x, layer["norm_attn"], eps), layer, config)
    x = x + attended
    y, balance = routed_experts(rms_norm(x, layer["norm_mlp"], eps), layer,
                                config)
    return x + y, balance, index_loss, taken


def _walk(params, tokens, config):
    """(final hidden states, balance losses, index losses, selections)."""
    x = params["embed"][tokens]
    balance, index, taken = [], [], []
    for layer in params["layers"]:
        x, layer_balance, layer_index, layer_taken = jax.checkpoint(
            lambda x, layer: decoder_layer(x, layer, config))(x, layer)
        balance.append(layer_balance)
        index.append(layer_index)
        taken.append(layer_taken)
    return (rms_norm(x, params["norm_f"], config["rms_norm_eps"]),
            jnp.stack(balance), jnp.stack(index), taken)


def loss(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]`` plus alpha
    times the mean balance loss and lambda times the mean indexer loss of
    the layers."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, balance, index, _ = _walk(params, inputs, config)
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
    assumed = config["assumed"]
    return (nll + assumed["aux_loss_alpha"] * jnp.mean(balance)
            + assumed["index_loss_lambda"] * jnp.mean(index))


def selection(params, tokens, config):
    """What each layer selected on ``tokens [B, S + 1]``'s inputs: a list
    of ``[B, S, S]`` bool, in float32 at ``highest`` precision."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return _walk(params, tokens[:, :-1], config)[3]


def loss_and_grads(params, tokens, config):
    """(loss, d loss / d params) in float32 at ``highest`` precision."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return jax.value_and_grad(loss)(params, tokens, config)
