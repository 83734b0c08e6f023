"""Plain reference of the decoder the ``deepseek-v2-lite`` cell trains:
DeepSeek-V2-Lite (DeepSeek-AI, "DeepSeek-V2", arXiv:2405.04434; the
model's own ``config.json`` and ``modeling_deepseek.py``) -- multi-head
latent attention with YaRN rotary positions, one leading dense layer, then
layers of routed experts beside shared ones -- with its loss and its
sequence-wise balance loss.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
no kernel, no sort, no gather of rows, nothing imported from the program.
Every number is a key of the configuration's file.  With H = 2048, n = 16
heads, d_n = ``qk_nope_head_dim`` 128, d_r = ``qk_rope_head_dim`` 64,
d_v = ``v_head_dim`` 128, r = ``kv_lora_rank`` 512, E = 64 experts routed
over, K = ``num_experts_per_tok`` 6, and x a token after the layer's first
RMSNorm::

    q = x W_q                 [n, d_n + d_r]  ->  q_n, q_r
    c = x W_kva               [r + d_r]       ->  c_kv, k_r  (one for all heads)
    [k_n | v] = RMSNorm(c_kv) W_kvb           [n, d_n + d_v]
    q_r, k_r <- RoPE_yarn at the token's position
    scores = [q_n | q_r] . [k_n | k_r] (d_n + d_r)^(-1/2) m^2,
             m = 0.1 mscale_all_dim ln(factor) + 1
    attention = softmax over the keys at or before the query, times v, W_o

    RoPE_yarn (dim d_r, theta, factor, L0, beta_fast, beta_slow):
      f_i = theta^(-2i/d_r);  pair(n) = d_r ln(L0 / (2 pi n)) / (2 ln theta)
      low = floor(pair(beta_fast)), high = ceil(pair(beta_slow)), in [0, d_r-1]
      ramp_i = clip((i - low) / (high - low), 0, 1)
      inv_freq_i = (f_i / factor) ramp_i + f_i (1 - ramp_i)
      cos, sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim)

    feed-forward of layer l < first_k_dense_replace: SwiGLU(intermediate_size)
    of the others, with x after the layer's second RMSNorm, in float32:
      s = softmax(x W_r) over all E;  e_1..e_K the K largest (greedy)
      g_k = s[e_k] routed_scaling_factor        (norm_topk_prob false)
      y = sum_{k: e_k held} g_k E_{e_k}(x) + S(x)
    E_e a SwiGLU of width moe_intermediate_size, S one SwiGLU of
    n_shared_experts times that width.

    loss = mean next-token cross-entropy + alpha aux
    aux  = mean over sequences b and routed layers of sum_e f[b,e] P[b,e]
           f[b,e] = (assignments of b to e) E / (K S), a constant
           P[b,e] = mean_s s[b,s,e]

**The held experts.**  The configuration is one chip's share of a layer that
eight chips divide (``deployment`` in its file): the parameters hold
``w_gate.shape[0]`` experts, ids ``deployment.first_held_expert`` onwards,
and the sum above runs over those; the router, the top-K and the balance
loss are over all E.  What the absent experts would add is left out, here
as in the program, and the partial y goes on to the next layer.  Given all
E experts this is the whole model.

Departures, none of which changes a value unless it says so:

* rotary positions turn the interleaved pairs ``(x[2i], x[2i+1])``.
  ``modeling_deepseek.py`` reorders a head's rotary part from that layout
  to half-against-half before it turns it, the same reordering on q and k,
  so the scores are the same;
* every expert held is computed for every token and multiplied with the
  token's gate for it, 0 where it was not chosen: the definition, and E_held
  times the work of a program that gathers.  The held experts are walked in
  order by ``lax.scan`` and not by a Python loop: the same sum in the same
  order, in an eighth of the program (unrolled, the comparison's compiled
  program was 168 MiB and pushed the train step out of the machine's 192 MiB
  compile cache in every run, PR 32);
* dense causal attention for ``BLOCK`` queries at a time against all keys,
  each layer and each block of the head's loss under ``jax.checkpoint``
  (``ouro.py``'s manner), so that 4096 positions fit beside the weights;
* the balance loss is averaged over the routed layers (the published code
  adds each layer's to the loss with weight alpha): the configuration's
  ``assumed.aux_loss_alpha`` is the weight of that mean.

Parameters are a plain tree: ``embed [V, H]``; ``layers``, a list of
``norm_attn [H]``, ``wq [H, n (d_n + d_r)]``, ``wkv_a [H, r + d_r]``,
``kv_norm [r]``, ``wkv_b [r, n (d_n + d_v)]``, ``wo [n d_v, H]``,
``norm_mlp [H]`` and either ``w_gate w_up [H, F]``, ``w_down [F, H]`` or
``router [H, E]``, ``experts`` (``w_gate w_up [held, H, F_e]``, ``w_down
[held, F_e, H]``) and ``shared`` (``w_gate w_up [H, F_s]``, ``w_down [F_s,
H]``); ``norm_f [H]``; ``lm_head [H, V]``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.ouro import _blocks, rms_norm


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inverse_frequencies(dim: int, theta: float, scaling: dict):
    """inv_freq ``[dim / 2]`` and the factor on cos and sin."""
    original = scaling["original_max_position_embeddings"]

    def pair(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair(scaling["beta_slow"])), dim - 1)
    index = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = 1.0 / theta ** (2.0 * index / dim)
    ramp = jnp.clip((index - low) / max(high - low, 1e-3), 0.0, 1.0)
    table_scale = (yarn_mscale(scaling["factor"], scaling["mscale"])
                   / yarn_mscale(scaling["factor"],
                                 scaling["mscale_all_dim"]))
    return (plain / scaling["factor"] * ramp + plain * (1.0 - ramp),
            table_scale)


def rotary(x, config):
    """x: [B, S, heads, d_r]; position p turns pair i by p inv_freq_i."""
    seq, dim = x.shape[1], x.shape[3]
    inv, table_scale = yarn_inverse_frequencies(
        dim, float(config["rope_theta"]), config["rope_scaling"])
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(angle) * table_scale)[None, :, None, :]
    sin = (jnp.sin(angle) * table_scale)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1)
    return turned.reshape(x.shape)


def softmax_scale(config) -> float:
    scaling = config["rope_scaling"]
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return width ** -0.5 * yarn_mscale(scaling["factor"],
                                       scaling["mscale_all_dim"]) ** 2


def causal_attention(q, k, v, scale):
    """q, k: [B, S, heads, D]; v: [B, S, heads, Dv] -> [B, S, heads, Dv]."""
    batch, seq, heads, dim = q.shape
    n_blocks = _blocks(seq)
    block = seq // n_blocks
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def one_block(args):
        q_block, first = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) * scale
        keep = (first + jnp.arange(block))[:, None] >= key_pos[None, :]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    q_blocks = q.reshape(batch, n_blocks, block, heads, dim).swapaxes(0, 1)
    out = jax.lax.map(one_block, (q_blocks, jnp.arange(n_blocks) * block))
    return out.swapaxes(0, 1).reshape(batch, seq, heads, v.shape[-1])


def latent_attention(x, layer, config):
    batch, seq, _ = x.shape
    heads = config["num_attention_heads"]
    d_n, d_r = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    d_v, rank = config["v_head_dim"], config["kv_lora_rank"]

    q = (x @ layer["wq"]).reshape(batch, seq, heads, d_n + d_r)
    q_n, q_r = q[..., :d_n], q[..., d_n:]
    latent = x @ layer["wkv_a"]
    c_kv, k_r = latent[..., :rank], latent[..., rank:]
    c_kv = rms_norm(c_kv, layer["kv_norm"], config["rms_norm_eps"])
    kv = (c_kv @ layer["wkv_b"]).reshape(batch, seq, heads, d_n + d_v)
    k_n, v = kv[..., :d_n], kv[..., d_n:]
    q_r = rotary(q_r, config)
    k_r = rotary(k_r[:, :, None, :], config)
    q = jnp.concatenate([q_n, q_r], axis=-1)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r, (batch, seq, heads, d_r))], axis=-1)
    attended = causal_attention(q, k, v, softmax_scale(config))
    return attended.reshape(batch, seq, heads * d_v) @ layer["wo"]


def swiglu(x, w):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def route(x, router, config):
    """(scores [B, S, E], chosen [B, S, K], gates [B, S, K])."""
    scores = jax.nn.softmax(x @ router, axis=-1)
    gates, chosen = jax.lax.top_k(scores, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return scores, chosen, gates * config["routed_scaling_factor"]


def balance(scores, chosen):
    """mean_b sum_e f[b, e] P[b, e]."""
    _, seq, experts = scores.shape
    counts = jnp.sum(jax.nn.one_hot(chosen, experts), axis=(1, 2))
    share = jax.lax.stop_gradient(
        counts * experts / (chosen.shape[-1] * seq))
    return jnp.mean(jnp.sum(share * jnp.mean(scores, axis=1), axis=-1))


def routed_experts(x, layer, config):
    """(y, this layer's balance loss, chosen): the held experts' part of
    the routed sum, and the shared experts."""
    scores, chosen, gates = route(x, layer["router"], config)
    experts = layer["experts"]
    first = config["deployment"]["first_held_expert"]

    def add_expert(y, held):
        expert, index = held
        gate = jnp.sum(jnp.where(chosen == first + index, gates, 0.0),
                       axis=-1)
        return y + gate[..., None] * swiglu(x, expert), None

    y, _ = jax.lax.scan(
        add_expert, swiglu(x, layer["shared"]),
        (experts, jnp.arange(experts["w_gate"].shape[0])))
    return y, balance(scores, chosen), chosen


def decoder_layer(x, layer, config):
    """(x, the layer's balance loss; 0 for a dense layer)."""
    eps = config["rms_norm_eps"]
    x = x + latent_attention(rms_norm(x, layer["norm_attn"], eps), layer,
                             config)
    y = rms_norm(x, layer["norm_mlp"], eps)
    if "router" in layer:
        y, aux, _ = routed_experts(y, layer, config)
        return x + y, aux
    return x + swiglu(y, layer), jnp.float32(0.0)


def loss(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]`` plus alpha
    times the mean balance loss of the routed layers."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs]
    aux = []
    for layer in params["layers"]:
        x, layer_aux = jax.checkpoint(
            lambda x, layer: decoder_layer(x, layer, config))(x, layer)
        if "router" in layer:
            aux.append(layer_aux)
    x = rms_norm(x, params["norm_f"], config["rms_norm_eps"])

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
    alpha = config["assumed"]["aux_loss_alpha"]
    return nll + alpha * jnp.mean(jnp.stack(aux)) if aux else nll


def loss_and_grads(params, tokens, config):
    """(loss, d loss / d params) in float32 at ``highest`` precision."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return jax.value_and_grad(loss)(params, tokens, config)
