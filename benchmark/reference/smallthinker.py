"""Plain reference of the decoder the ``smallthinker-21b-a3b`` cell trains:
SmallThinker-21BA3B-Instruct (its ``config.json``; arXiv:2507.20984 and the
family's published modelling code for the layer's form) -- a router that
reads the layer's INPUT, before the norm and before attention; softmax
layers that attend to all their causal keys and do not rotate, beside layers
that attend through a sliding window and do, by ``sliding_window_layout``
and ``rope_layout``; ReGLU experts, the gates a softmax over the chosen
logits, no shared expert and no dense layer -- with its loss.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, dense scores with the window as a mask, ``jax.lax.top_k``, no
recomputation that changes a number, nothing imported from the program.
Every number is a key of the configuration's file.  For layer i with input
x ``[S, H]``, n = ``num_attention_heads`` query heads over m =
``num_key_value_heads`` of D = ``head_dim`` lanes (groups of n / m), K =
``moe_num_active_primary_experts``, N an RMSNorm (eps ``rms_norm_eps``), no
bias anywhere::

    r = x W_r                       [S, E] float32: the layer's input, before
                                    N_1 and before attention
    e_1..e_K = the K largest of r;  g = softmax(r[e_1..e_K])
    h = N_1(x);  q = h W_q [S, n, D];  k = h W_k, v = h W_v [S, m, D]
    rope_layout[i] = 1:            q, k <- R(q), R(k), all D lanes, theta
                                   ``rope_theta``, no scaling;  0: untouched
    sliding_window_layout[i] = 1:  A(t) = {s : 0 <= t - s < sliding_window_size}
                             0:    A(t) = {s <= t}
    o[t, j] = sum_{s in A(t)} softmax_{A(t)}(q[t, j] . k[s, j // (n / m)]
                                             / sqrt(D)) v[s, j // (n / m)]
    x' = x + [o[t, 1] .. o[t, n]] W_o
    u  = N_2(x')
    y  = sum_{k: e_k held} g_k (relu(u W_gate^e) * (u W_up^e)) W_down^e   e = e_k
    out = x' + y
    logits = N_f(x_L) W_head

    loss = cross-entropy + alpha mean_layers(balance)
    balance = E sum_e f[e] P[e]   f[e] = (the batch's assignments to e) / (K T),
                                  a constant; P[e] = mean_t softmax(r[t])[e]

(``moe_primary_router_apply_softmax`` with ``norm_topk_prob``: the softmax
over the K chosen logits IS the softmax over all E renormalised over the
chosen K.)  **R**: interleaved pairs ``(x[2i], x[2i+1])``, pair i at
position p turned by ``p theta^(-2i/D)``; the published checkpoints turn
half against half, a fixed permutation of the q and k columns.

**The held experts**, as ``deepseek_v2_lite.py``: the parameters hold
``w_gate.shape[0]`` experts, ids ``deployment.first_held_expert`` onwards;
the router, the top-K, the gates' softmax and the balance loss are over all
E, and what the absent experts would add is left out.  The router is whole.

Attention is ``laguna.py``'s (dense scores of 128 queries at a time against
all keys, the window a mask: ``[28 heads, 128, 16384]`` scores a block) and
an expert runs ``ROWS`` positions at a time, each block, each layer and each
block of the head's loss under ``jax.checkpoint``, so that 16,384 positions
fit beside the program in ``benchmark/compare.py``'s one program: the
backward pass repeats the forward's work and computes the same numbers.

Parameters are a plain tree: ``embed [V, H]``; ``layers``, a list of
``norm_attn [H]``, ``wq [H, n D]``, ``wk wv [H, m D]``, ``wo [n D, H]``,
``norm_mlp [H]``, ``router [H, E]`` and ``experts`` (``w_gate w_up [held,
H, F]``, ``w_down [held, F, H]``); ``norm_f [H]``; ``lm_head [H, V]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.laguna import attention
from benchmark.reference.ouro import _blocks, rms_norm

ROWS = 2048            # rows of an expert at a time


def rotary(x, theta: float):
    """x: [B, S, heads, D], every lane of every head turned.  The pairs are
    taken apart by a reshape (strided slices are gathers to XLA, and their
    transposes scatter-adds)."""
    dim = x.shape[-1]
    freq = float(theta) ** (-2.0 * jnp.arange(dim // 2, dtype=jnp.float32)
                            / dim)
    angle = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * freq[None, :])
    cos, sin = (t[None, :, None, :] for t in (jnp.cos(angle),
                                              jnp.sin(angle)))
    pairs = x.reshape(*x.shape[:-1], dim // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def attention_layer(h, layer, index, config):
    """The mixer of layer ``index`` on the normed state h: [B, S, H]."""
    batch, seq, _ = h.shape
    dim, heads, kv_heads = (config["head_dim"], config["num_attention_heads"],
                            config["num_key_value_heads"])
    q = (h @ layer["wq"]).reshape(batch, seq, heads, dim)
    k = (h @ layer["wk"]).reshape(batch, seq, kv_heads, dim)
    v = (h @ layer["wv"]).reshape(batch, seq, kv_heads, dim)
    if config["rope_layout"][index]:
        if config["rope_scaling"] is not None:
            raise ValueError("this reference turns by rope_theta alone")
        q, k = (rotary(t, config["rope_theta"]) for t in (q, k))
    window = (config["sliding_window_size"]
              if config["sliding_window_layout"][index] else None)
    # Query head j reads key-value head j // (n / m): written as a repeat.
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    out = attention(q, k, v, window)
    return out.reshape(batch, seq, heads * dim) @ layer["wo"]


def reglu(x, w):
    """A ReLU-gated feed-forward on x: [B, S, H], ``ROWS`` positions at a
    time and each block under ``jax.checkpoint``."""
    batch, seq, hidden = x.shape
    rows = min(ROWS, seq)

    @jax.checkpoint
    def one_block(block):
        return (jax.nn.relu(block @ w["w_gate"])
                * (block @ w["w_up"])) @ w["w_down"]

    blocks = x.reshape(batch, seq // rows, rows, hidden).swapaxes(0, 1)
    return jax.lax.map(one_block, blocks).swapaxes(0, 1).reshape(x.shape)


def route(x, router, config):
    """(gates [B, S, K], chosen [B, S, K], the balance loss) from the
    LAYER's input x: the K largest logits, a softmax over them."""
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise ValueError("this reference's gates are a softmax over the "
                         "chosen logits")
    experts_over = router.shape[1]
    per_token = config["moe_num_active_primary_experts"]
    logits = x @ router
    chosen_logits, chosen = jax.lax.top_k(logits, per_token)
    gates = jax.nn.softmax(chosen_logits, axis=-1)
    counts = jnp.sum(jax.nn.one_hot(chosen, experts_over), axis=(0, 1, 2))
    share = jax.lax.stop_gradient(
        counts / (per_token * x.shape[0] * x.shape[1]))
    probs = jax.nn.softmax(logits, axis=-1)
    balance = experts_over * jnp.sum(share * jnp.mean(probs, axis=(0, 1)))
    return gates, chosen, balance


def held_experts(u, gates, chosen, experts, config):
    """The held experts' part of the routed sum: a dense loop over them,
    each on every position under the gate of the tokens that chose it (0
    for the others)."""
    first = config["deployment"]["first_held_expert"]

    def add_expert(y, held):
        expert, index = held
        gate = jnp.sum(jnp.where(chosen == first + index, gates, 0.0),
                       axis=-1)
        return y + gate[..., None] * reglu(u, expert), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(u),
                        (experts, jnp.arange(experts["w_gate"].shape[0])))
    return y


def decoder_layer(x, layer, index, config):
    """(out, the layer's balance loss)."""
    eps = config["rms_norm_eps"]
    gates, chosen, balance = route(x, layer["router"], config)
    x = x + attention_layer(rms_norm(x, layer["norm_attn"], eps), layer,
                            index, config)
    u = rms_norm(x, layer["norm_mlp"], eps)
    return x + held_experts(u, gates, chosen, layer["experts"],
                            config), balance


def hidden_states(params, tokens, config):
    """(the final normed states [B, S, H], the layers' balance losses)."""
    x = params["embed"][tokens]
    balance = []
    for index, layer in enumerate(params["layers"]):
        x, layer_balance = jax.checkpoint(
            lambda x, layer, index=index: decoder_layer(
                x, layer, index, config))(x, layer)
        balance.append(layer_balance)
    return (rms_norm(x, params["norm_f"], config["rms_norm_eps"]),
            jnp.stack(balance))


def loss(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]`` plus alpha
    times the mean balance loss of the layers."""
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
