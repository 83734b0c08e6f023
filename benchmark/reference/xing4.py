"""Plain reference of the decoder the ``xing4.0-29b-a4b`` cell trains:
Xing4.0-29B-A4B (the model's own ``config.json``, ``model_type`` ``xing4_0``)
-- DeepSeek-V3's block (arXiv:2412.19437: latent attention WITH a query
latent, YaRN rotary positions, leading dense layers, then experts routed by
a sigmoid whose choice a bias corrects, beside a shared one) inside
manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections, arXiv:2409.19606): ``hc_mult`` residual streams that every
sublayer reads as a learned mix and rewrites as another -- with its loss and
its sequence-wise balance loss.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, no
kernel, no sort, no gather of rows, nothing imported from the program.  Every
number is a key of the configuration's file.  n = ``hc_mult`` 4 streams of
width C = ``hidden_size`` 3584; a token's state is X ``[n, C]``.  For a
sublayer F (latent attention, or the feed-forward) with its pre-norm N
(``rms_norm_eps``) and its OWN nine leaves::

    u      = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)          [n C]
    a_pre  = g_pre  (u Phi_pre)  + b_pre       [n]
    a_post = g_post (u Phi_post) + b_post      [n]
    A_res  = g_res  mat(u Phi_res) + B_res     [n, n]   (row-major)
    h_pre  = sigmoid(a_pre);   h_post = 2 sigmoid(a_post)
    M_0    = exp(clip(A_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    M_t    = rows(cols(M_{t-1})),  t = 1..hc_sinkhorn_iters
             cols / rows divide by the column / row sums + hc_eps
    x_in   = h_pre X                           [C]
    X'     = M_last X + h_post^T F(N(x_in))    [n, C]

The embedding is copied into the n streams; behind the last layer the
streams are summed, then the final norm and the head.  F for the mixer, with
H heads, d_n = ``qk_nope_head_dim``, d_r = ``qk_rope_head_dim``, d_v =
``v_head_dim``, r = ``kv_lora_rank``, r_q = ``q_lora_rank``::

    q = RMSNorm(x W_qa) W_qb                   [H, d_n + d_r]
    keys, values, YaRN, the softmax scale and the causal softmax:
    ``deepseek_v2_lite.py``'s, whose functions this file calls

F for layer l < ``first_k_dense_replace`` is a SwiGLU of
``intermediate_size``; for the others, in float32::

    s = sigmoid(x W_r) over all E;  e_1..e_K the K largest of s + b
    g_k = s[e_k] / sum_k s[e_k] x routed_scaling_factor   (norm_topk_prob)
    y = sum_{k: e_k held} g_k E_{e_k}(x) + S(x)

    loss = mean next-token cross-entropy + alpha aux
    aux  = mean over sequences b and routed layers of sum_e f[b,e] P[b,e],
           f[b,e] = (assignments of b to e) E / (K S), a constant, P[b,e] =
           mean_s of s[b,s,e] / sum_e s[b,s,e]

**The held experts** are one chip's share of a layer that eight chips divide
(``deployment`` in the configuration's file), as in ``deepseek_v2_lite.py``:
the sum runs over the experts the parameters hold, ids
``deployment.first_held_expert`` onwards; router, top-K and balance loss are
over all E; what the absent experts would add is left out.

Departures, none of which changes a value unless it says so:

* b, the choice bias (``topk_method`` ``noaux_tc``), is zeros: the
  comparison is on the parameters as initialised, where the bias has not
  moved, and it is state, no parameter;
* rotary positions turn interleaved pairs, the held experts are walked by
  ``lax.scan``, and the balance loss is averaged over the routed layers
  (``deepseek_v2_lite.py``'s manner and reasons); dense causal attention,
  the feed-forwards (behind a router that has seen the whole sequence) and
  the streams' reads and writes run for ``ROWS`` positions at a time, and
  within a layer each read, sublayer and write and each block of positions
  and each group of heads is under ``jax.checkpoint``, and the gradient is
  ``jax.vjp`` a layer in a reverse sweep that keeps no layer's input
  (``loss_and_grads``, which says why; ``loss`` is the definition it
  differentiates);
* where the streams start and end, that ``hc_eps`` serves the RMS and
  Sinkhorn alike, columns before rows, the clamp ahead of ``exp``, no learned
  scale in the maps' RMS and maps a TOKEN are the configuration's
  ``assumed``: the published config names the mechanism by its keys alone;
* ``num_nextn_predict_layers`` is 0 here (``reduced``): no
  multi-token-prediction module;
* THE FIRST SUBLAYER OF THE STACK reads n identical streams, so its h_pre is
  a positive factor on the embedding, which the norm removes, and its H_res,
  whose rows sum to one, leaves the streams as they are: ``x_in`` is the
  embedding and ``X' = X + h_post^T y`` there, whatever the six leaves
  behind h_pre and H_res hold.  Their gradients are zero but for the two
  epsilons (1e-6 of a gradient, under float32's rounding), so the parameter
  tree does not carry them for that sublayer (``hc_attn`` of layer 0 holds
  ``phi_post``, ``b_post``, ``g_post`` alone) and nothing is compared on
  them; this changes the loss by the same 1e-6.

Parameters are a plain tree: ``embed [V, C]``; ``layers``, a list of
``hc_attn`` and ``hc_mlp`` (each ``phi_pre phi_post [n C, n]``, ``phi_res
[n C, n n]``, ``b_pre b_post [n]``, ``b_res [n n]``, ``g_pre g_post g_res``
scalars), ``norm_attn [C]``, ``wq_a [C, r_q]``, ``q_norm [r_q]``, ``wq_b [r_q,
H (d_n + d_r)]``, ``wkv_a``, ``kv_norm``, ``wkv_b``, ``wo``, ``norm_mlp [C]``
and either ``w_gate w_up [C, F]``, ``w_down [F, C]`` or ``router [C, E]``,
``experts`` and ``shared`` as ``deepseek_v2_lite.py``; ``norm_f [C]``;
``lm_head [C, V]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v2_lite import (balance, rotary,
                                                  softmax_scale, swiglu)
from benchmark.reference.ouro import _blocks, rms_norm

ROWS = 256      # queries of attention, and tokens of a dense FFN, at a time


def unit_rms(streams, config):
    """u ``[B, S, n C]``: a token's n streams as one vector of unit RMS."""
    flat = streams.reshape(streams.shape[:2] + (-1,))
    return flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                           + config["hc_eps"])


def logits_of(u, hc, which):
    return hc["g_" + which] * (u @ hc["phi_" + which]) + hc["b_" + which]


def sinkhorn(logits, config):
    """``logits [.., n, n]`` -> M_last: ``exp`` of the clamped logits, then
    ``hc_sinkhorn_iters`` times columns, then rows, divided by their sums."""
    eps = config["hc_eps"]
    m = jnp.exp(jnp.clip(logits, config["mhc_h_res_clamp_min"],
                         config["mhc_h_res_clamp_max"]))
    for _ in range(config["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)      # columns
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)      # rows
    return m


def hyper_maps(streams, hc, config):
    """``streams [B, S, n, C]`` -> ``(h_pre [B, S, n], h_post [B, S, n],
    H_res [B, S, n, n])`` of one sublayer."""
    n = streams.shape[2]
    u = unit_rms(streams, config)
    res = logits_of(u, hc, "res")
    return (jax.nn.sigmoid(logits_of(u, hc, "pre")),
            2.0 * jax.nn.sigmoid(logits_of(u, hc, "post")),
            sinkhorn(res.reshape(res.shape[:2] + (n, n)), config))


def in_row_blocks(f, *xs):
    """``f(*xs)`` for ``ROWS`` positions of ``xs [B, S, ..]`` at a time, each
    block under ``jax.checkpoint`` and cut from ``xs`` where they lie; ``f``
    treats positions alike and returns an array or a tuple of arrays ``[B,
    rows, ..]``."""
    batch, seq = xs[0].shape[:2]
    n_blocks = max(seq // ROWS, 1)
    rows = seq // n_blocks
    if seq % n_blocks:
        raise ValueError(f"sequence {seq} is not a multiple of {ROWS}")

    @jax.checkpoint
    def one_block(start):
        return f(*(jax.lax.dynamic_slice_in_dim(x, start, rows, axis=1)
                   for x in xs))

    return jax.tree.map(
        lambda x: x.swapaxes(0, 1).reshape((batch, seq) + x.shape[3:]),
        jax.lax.map(one_block, jnp.arange(n_blocks) * rows))


def hyper_connected(streams, hc, norm, sublayer, config):
    """``X' = H_res X + h_post^T F(N(h_pre X))`` and what F returns beside
    its output.  The read and the write work on a token alone and run for
    ``ROWS`` tokens at a time; they and F are each under ``jax.checkpoint``,
    so that F's backward pass holds the streams and one cotangent of their
    size and nothing else of the mixes'."""
    first = "phi_pre" not in hc     # the stack's first sublayer: n copies

    def read(block):
        if first:
            return block[:, :, 0], 2.0 * jax.nn.sigmoid(logits_of(
                unit_rms(block, config), hc, "post"))
        h_pre, h_post, h_res = hyper_maps(block, hc, config)
        return jnp.einsum("bsj,bsjc->bsc", h_pre, block), h_post, h_res

    def write(block, y, h_post, *h_res):
        mixed = block if first else jnp.einsum("bsij,bsjc->bsic", *h_res,
                                               block)
        return mixed + h_post[..., None] * y[:, :, None, :]

    x_in, *maps = in_row_blocks(read, streams)
    y, beside = jax.checkpoint(lambda x, norm: sublayer(
        rms_norm(x, norm, config["rms_norm_eps"])))(x_in, norm)
    return in_row_blocks(write, streams, y, *maps), beside


def causal_attention(q, k, v, scale):
    """q, k: [B, S, heads, D]; v: [B, S, heads, Dv] -> [B, S, heads, Dv]:
    an explicit masked softmax for ``ROWS`` queries at a time against all
    keys."""
    key_pos = jnp.arange(q.shape[1])

    def one_block(q_block, query_pos):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) * scale
        keep = query_pos[0, :, None] >= key_pos[None, :]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    return in_row_blocks(one_block, q,
                         jnp.broadcast_to(key_pos, q.shape[:2]))


HEADS = 8       # heads of attention at a time


def latent_attention(x, layer, config):
    """The heads ``HEADS`` at a time, from the two latents to their share of
    W_o's product, each group under ``jax.checkpoint``: q, k and v of all 32
    heads at 8192 tokens are 0.7 GB in float32, and as much again backward."""
    batch, seq, _ = x.shape
    heads = config["num_attention_heads"]
    d_n, d_r = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    d_v, rank = config["v_head_dim"], config["kv_lora_rank"]
    eps = config["rms_norm_eps"]
    group = min(HEADS, heads)

    c_q = rms_norm(x @ layer["wq_a"], layer["q_norm"], eps)
    latent = x @ layer["wkv_a"]
    c_kv = rms_norm(latent[..., :rank], layer["kv_norm"], eps)
    k_r = rotary(latent[:, :, None, rank:], config)

    @jax.checkpoint
    def heads_of(weights):
        wq_b, wkv_b, wo = weights       # [r_q, g, ..], [r, g, ..], [g, d_v, C]
        q = jnp.einsum("bsr,rgd->bsgd", c_q, wq_b)
        kv = jnp.einsum("bsr,rgd->bsgd", c_kv, wkv_b)
        q = jnp.concatenate(
            [q[..., :d_n], rotary(q[..., d_n:], config)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :d_n], jnp.broadcast_to(k_r, (batch, seq, group, d_r))],
            axis=-1)
        attended = causal_attention(q, k, kv[..., d_n:],
                                    softmax_scale(config))
        return jnp.einsum("bsgd,gdc->bsc", attended, wo)

    def grouped(w, lead, width):     # the heads' axis cut into groups, first
        return jnp.moveaxis(w.reshape(
            lead + (heads // group, group, width) + w.shape[len(lead) + 1:]),
            len(lead), 0)

    return jnp.sum(jax.lax.map(heads_of, (
        grouped(layer["wq_b"], layer["wq_b"].shape[:1], d_n + d_r),
        grouped(layer["wkv_b"], layer["wkv_b"].shape[:1], d_n + d_v),
        grouped(layer["wo"], (), d_v))), axis=0)


def route(x, router, config):
    """(scores [B, S, E], chosen [B, S, K], gates [B, S, K]); the choice
    bias is zeros (the module's docstring)."""
    scores = jax.nn.sigmoid(x @ router)
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores),
                              config["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return scores, chosen, gates * config["routed_scaling_factor"]


def routed_experts(x, layer, config, first=None, experts=None):
    """(y, this layer's balance loss): the held experts' part of the routed
    sum, and the shared expert.  ``first`` and ``experts``: another share
    than the configuration's (the share test's)."""
    scores, chosen, gates = route(x, layer["router"], config)
    if first is None:
        first = config["deployment"]["first_held_expert"]
    experts = layer["experts"] if experts is None else experts

    def held_sum(x, chosen, gates):     # of a block of tokens
        def add_expert(y, held):
            expert, index = held
            gate = jnp.sum(jnp.where(chosen == first + index, gates, 0.0),
                           axis=-1)
            return y + gate[..., None] * swiglu(x, expert), None

        return jax.lax.scan(
            add_expert, swiglu(x, layer["shared"]),
            (experts, jnp.arange(experts["w_gate"].shape[0])))[0]

    return (in_row_blocks(held_sum, x, chosen, gates),
            balance(scores / jnp.sum(scores, axis=-1, keepdims=True),
                    chosen))


def decoder_layer(streams, layer, config):
    """(streams ``[B, S, n, C]``, the layer's balance loss; 0 for a dense
    layer)."""
    def mixer(x):
        return latent_attention(x, layer, config), None

    def feed_forward(x):
        if "router" in layer:
            return routed_experts(x, layer, config)
        return in_row_blocks(lambda x: swiglu(x, layer), x), jnp.float32(0.0)

    streams, _ = hyper_connected(streams, layer["hc_attn"],
                                 layer["norm_attn"], mixer, config)
    return hyper_connected(streams, layer["hc_mlp"], layer["norm_mlp"],
                           feed_forward, config)


def embedded(table, inputs, config):
    """The embedding of ``inputs [B, S]`` copied into the n streams."""
    x = table[inputs]
    return jnp.broadcast_to(x[:, :, None, :],
                            x.shape[:2] + (config["hc_mult"], x.shape[2]))


def head_loss(streams, norm_f, lm_head, targets, config):
    """Mean next-token cross-entropy behind the streams' sum, the final norm
    and the head, ``_blocks`` of positions at a time."""
    x = rms_norm(jnp.sum(streams, axis=2), norm_f, config["rms_norm_eps"])
    batch, seq, hidden = x.shape
    n_blocks = _blocks(seq)

    @jax.checkpoint
    def block_nll(args):
        rows, wanted = args
        logits = rows @ lm_head
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked[..., 0])

    rows = x.reshape(batch, n_blocks, seq // n_blocks, hidden).swapaxes(0, 1)
    wanted = targets.reshape(batch, n_blocks, seq // n_blocks).swapaxes(0, 1)
    return jnp.sum(jax.lax.map(block_nll, (rows, wanted))) / (batch * seq)


def aux_weight(params, config) -> float:
    """alpha over the number of routed layers: the weight of ONE layer's
    balance loss in the loss."""
    routed = sum("router" in layer for layer in params["layers"])
    return config["assumed"]["aux_loss_alpha"] / max(routed, 1)


def loss(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]`` plus alpha
    times the mean balance loss of the routed layers: the definition, which
    ``loss_and_grads`` differentiates a layer at a time."""
    streams = embedded(params["embed"], tokens[:, :-1], config)
    aux = jnp.float32(0.0)
    for layer in params["layers"]:
        streams, layer_aux = decoder_layer(streams, layer, config)
        aux = aux + layer_aux
    return (head_loss(streams, params["norm_f"], params["lm_head"],
                      tokens[:, 1:], config)
            + aux_weight(params, config) * aux)


def loss_and_grads(params, tokens, config):
    """(loss, d loss / d params) in float32 at ``highest`` precision:
    ``jax.value_and_grad(loss)``, taken as a reverse sweep of ``jax.vjp`` a
    layer (``tests/test_xing4.py`` holds the two equal).  NO layer's input is
    kept: a layer's backward pass walks the stack again from the embedding to
    its input, behind an ``optimization_barrier`` that ties the walk's first
    operand to the cotangent it waits for, so that the compiler neither
    merges the walks nor starts one early.  That is 10 layer applications
    more than keeping five inputs, and what fits: ``benchmark/compare.py``
    runs the program's loss and gradients in the same program, nothing orders
    the two, and beside the program's parameters, gradients and temporaries
    there are ~6 GB for everything here; four float32 streams of 8192 tokens
    are 470 MB a copy, and ONE layer's float32 parameters are alive at a
    time."""
    def up(tree):
        return jax.tree.map(lambda p: p.astype(jnp.float32), tree)

    @jax.jit        # traced once a kind of layer, however often it is walked
    def layer_fn(streams, layer):
        return decoder_layer(streams, layer, config)

    def entering(depth, inputs):
        """The streams that enter layer ``depth``, from the embedding."""
        streams = embedded(up(params["embed"]), inputs, config)
        for layer in params["layers"][:depth]:
            streams, _ = layer_fn(streams, up(layer))
        return streams

    with jax.default_matmul_precision("highest"):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        weight = aux_weight(params, config)
        depth = len(params["layers"])
        value, head_vjp = jax.vjp(
            lambda streams, norm_f, lm_head: head_loss(
                streams, norm_f, lm_head, targets, config),
            entering(depth, inputs), up(params["norm_f"]),
            up(params["lm_head"]))
        d_streams, d_norm_f, d_lm_head = head_vjp(jnp.float32(1.0))
        d_layers = []
        for at in range(depth - 1, -1, -1):
            again, d_streams = jax.lax.optimization_barrier(
                (inputs, d_streams))
            (_, aux), layer_vjp = jax.vjp(
                layer_fn, entering(at, again), up(params["layers"][at]))
            d_streams, d_layer = layer_vjp((d_streams, jnp.float32(weight)))
            d_layers.append(d_layer)
            value = value + weight * aux
        again, d_streams = jax.lax.optimization_barrier((inputs, d_streams))
        _, embed_vjp = jax.vjp(
            lambda table: embedded(table, again, config),
            up(params["embed"]))
        return value, {"embed": embed_vjp(d_streams)[0],
                       "layers": d_layers[::-1],
                       "norm_f": d_norm_f, "lm_head": d_lm_head}
