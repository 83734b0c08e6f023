"""Plain reference of the decoder the ``kimi-linear-48b-a3b`` cell trains:
Kimi-Linear-48B-A3B-Instruct (its ``config.json``, ``model_type``
``kimi_linear``; Kimi Linear, arXiv:2510.26692) -- Kimi Delta Attention layers
(the delta rule whose decay is a number a key CHANNEL) three to one with
latent attention that does not rotate (DeepSeek-V2's MLA, arXiv:2405.04434,
``mla_use_nope``), a leading dense layer and then routed experts behind a
sigmoid router beside one shared expert (DeepSeek-V3's router,
arXiv:2412.19437) -- with its loss.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no chunked form (the rule runs TOKEN BY TOKEN from the recurrence),
the causal softmax as a mask, the held experts as a dense loop,
``jax.lax.top_k``, nothing imported from the program.  Every number is a key
of the configuration's file.  x is the residual stream, eps ``rms_norm_eps``,
N(u) = u / rms(u) * gamma::

    every layer:  h = x + Mixer(N(x));   x' = h + FFN(N(h))
    embedding; ``num_hidden_layers`` layers; N; an untied head

**KDA layer** (layer i, 1-indexed, in ``linear_attn_config.kda_layers``): H =
``linear_attn_config.num_heads`` heads of d = ``linear_attn_config.head_dim``
(keys and values alike), ``*`` a causal depthwise filter of
``short_conv_kernel_size`` taps (zero history before position 0, written as
shifted multiply-adds), u the normed state::

    q = l2norm(silu(conv_q * (u W_q))) d^-1/2     k = l2norm(silu(conv_k * (u W_k)))
    v = silu(conv_v * (u W_v))
    g_t    = -exp(A_log[h]) softplus((u_t W_fa) W_fb + dt_bias)      [H, d]
    beta_t = sigmoid(u_t W_b)                                         [H]
    S_t = S_{t-1} Diag(exp(g_t)) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T     S_0 = 0
    o_t = S_t q_t                                  S [d_v, d_k] a head
    Mixer(u) = (N(o) * sigmoid((u W_ga) W_gb)) W_o      one [d] gamma for all heads

(the paper's ``S_t = (I - beta k k^T) Diag(a_t) S_{t-1} + beta k v^T`` on the
transpose: the decay FIRST, then the delta step on the decayed state).

**Latent layer** (in ``full_attn_layers``): n = ``num_attention_heads`` heads,
d_n = ``qk_nope_head_dim``, d_r = ``qk_rope_head_dim``, d_v = ``v_head_dim``, r
= ``kv_lora_rank``, ``q_lora_rank`` null (one matrix W_q)::

    q = u W_q                 [n, d_n + d_r]
    c = u W_kva               [r + d_r]   ->  c_kv, k_r   (k_r: all heads')
    [k_n | v] = N(c_kv) W_kvb             [n, d_n + d_v]
    scores = q . [k_n | k_r] (d_n + d_r)^-1/2        NOTHING is rotated
    Mixer(u) = softmax_causal(scores) v W_o

**FFN**: layer 1 (``first_k_dense_replace`` leading layers) a SwiGLU of
``intermediate_size``; every other layer, over all E experts the router
knows (``router.shape[1]``)::

    s = sigmoid(u W_r), float32;  e_1..e_K the K = ``num_experts_per_token``
    largest of s + b (one group: ``num_expert_group`` 1; the choice bias b is
    zeros where the comparison is made, and state, no parameter)
    w_k = s[e_k] / sum_j s[e_j] (``moe_renormalize``) x ``routed_scaling_factor``
    FFN(u) = sum_{k: e_k held} w_k E_{e_k}(u) + S(u)

E_e a SwiGLU of ``moe_intermediate_size``, S one of ``num_shared_experts``
times that.  The parameters hold ``w_gate.shape[0]`` experts, ids
``deployment.first_held_expert`` onwards; what an absent expert would add is
left out.

**Loss**: mean next-token cross-entropy of every position + ``assumed.
aux_loss_alpha`` x the mean over the routed layers of the sequence-wise
balance loss ``mean_b sum_e f[b, e] P[b, e]`` (f the share of sequence b's
assignments that chose e, times E, a constant; P the mean over the sequence
of ``s / sum_e s``).

**Departures from the published description**, each a re-arrangement and none
a change of function: the state is kept ``[d_v, d_k]`` (the transpose); the
three filters are apart (depthwise: the same numbers in another order).
What the row does not state is the configuration's ``assumed``.

So that 8192 positions fit beside the program in ``benchmark/compare.py``'s
one program, nothing of which changes a number: the recurrence is a nested
``lax.scan``, ``TOKENS`` tokens to a checkpoint; attention takes ``HEADS``
heads and ``QUERIES`` queries at a time; a SwiGLU and the head's loss ``ROWS``
rows at a time, each block under ``jax.checkpoint``; and ``loss_and_grads``
differentiates a layer at a time in a reverse sweep, so that one layer's
float32 parameters are alive at a time (``loss`` is the definition it
differentiates; ``tests/test_kimi_linear.py`` holds the two equal).

Parameters are a plain tree: ``embed [V, C]``; ``layers``, a list, each with
``norm_attn norm_mlp [C]`` and, a KDA layer: ``wq wk wv [C, H d]``, ``conv_q
conv_k conv_v [K, H d]``, ``a_log [H]``, ``dt_bias [H d]``, ``f_a g_a [C, d]``,
``f_b g_b [d, H d]``, ``wb [C, H]``, ``o_norm [d]``, ``wo [H d, C]``; a latent
layer: ``wq [C, n (d_n + d_r)]``, ``wkv_a [C, r + d_r]``, ``kv_norm [r]``,
``wkv_b [r, n (d_n + d_v)]``, ``wo [n d_v, C]``; and either ``w_gate w_up [C,
F]``, ``w_down [F, C]`` or ``router [C, E]``, ``experts`` (``w_gate w_up
[held, C, F]``, ``w_down [held, F, C]``) and ``shared`` (a SwiGLU's three);
``norm_f [C]``; ``lm_head [C, V]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS = 1024            # rows of a SwiGLU and of the head's loss at a time
QUERIES = 256          # queries of softmax attention at a time
HEADS = 8              # heads of softmax attention at a time
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


def is_kda(index: int, config: dict) -> bool:
    """Layer ``index`` (0-indexed here; the published lists count from 1)."""
    return index + 1 in config["linear_attn_config"]["kda_layers"]


# -- the KDA layer ------------------------------------------------------------

def short_convolution(x, taps):
    """``y[t] = sum_i taps[i] x[t - (K - 1) + i]``; x ``[B, S, C]``."""
    seq, k = x.shape[1], taps.shape[0]
    return sum(jnp.pad(x, ((0, 0), (k - 1 - i, 0), (0, 0)))[:, :seq] * taps[i]
               for i in range(k))


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """The recurrence, one token a step.  q, k, g ``[B, S, H, d_k]``, v ``[B,
    S, H, d_v]``, beta ``[B, S, H]`` -> ``o [B, S, H, d_v]``."""
    batch, seq, heads, d_k = q.shape
    d_v = v.shape[-1]

    def token(state, x):
        q, k, v, g, beta = x
        state = state * jnp.exp(g)[..., None, :]          # S Diag(a): first
        read = jnp.einsum("bhvk,bhk->bhv", state, k)      # S k
        state = state + beta[..., None, None] * (
            (v - read)[..., :, None] * k[..., None, :])
        return state, jnp.einsum("bhvk,bhk->bhv", state, q)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    n = _blocks(seq, TOKENS)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(n, seq // n, *x.shape[:1],
                                             *x.shape[2:])
               for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        block, jnp.zeros((batch, heads, d_v, d_k), jnp.float32), xs)
    return jnp.moveaxis(o.reshape(seq, batch, heads, d_v), 0, 1)


def kda_mixer(u, layer, config):
    batch, seq, _ = u.shape
    sizes = config["linear_attn_config"]
    heads, d = sizes["num_heads"], sizes["head_dim"]

    def projected(w, taps):
        y = jax.nn.silu(short_convolution(u @ layer[w], layer[taps]))
        return y.reshape(batch, seq, heads, d)

    q = l2_norm(projected("wq", "conv_q")) * d ** -0.5
    k = l2_norm(projected("wk", "conv_k"))
    v = projected("wv", "conv_v")
    g = -jnp.exp(layer["a_log"])[:, None] * jax.nn.softplus(
        (u @ layer["f_a"]) @ layer["f_b"] + layer["dt_bias"]).reshape(
            batch, seq, heads, d)
    beta = jax.nn.sigmoid(u @ layer["wb"])
    o = rms_norm(delta_rule(q, k, v, g, beta), layer["o_norm"],
                 config["rms_norm_eps"])
    gate = jax.nn.sigmoid((u @ layer["g_a"]) @ layer["g_b"])
    return (o.reshape(batch, seq, heads * d) * gate) @ layer["wo"]


# -- the latent layer ---------------------------------------------------------

def causal_attention(q, k, v, scale):
    """q, k ``[B, S, heads, D]``, v ``[B, S, heads, Dv]`` -> ``[B, S, heads,
    Dv]``: a masked softmax for ``QUERIES`` queries at a time against all
    keys."""
    batch, seq, heads, dim = q.shape
    n = _blocks(seq, QUERIES)
    block = seq // n
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def one_block(args):
        q_block, first = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) * scale
        keep = (first + jnp.arange(block))[:, None] >= key_pos[None, :]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    q_blocks = q.reshape(batch, n, block, heads, dim).swapaxes(0, 1)
    out = jax.lax.map(one_block, (q_blocks, jnp.arange(n) * block))
    return out.swapaxes(0, 1).reshape(batch, seq, heads, v.shape[-1])


def latent_mixer(u, layer, config):
    """The heads ``HEADS`` at a time, from the latent to their share of W_o's
    product, each group under a checkpoint.  No lane is rotated
    (``mla_use_nope``)."""
    if not config["mla_use_nope"] or config["q_lora_rank"] is not None:
        raise ValueError("this reference's latent attention neither rotates "
                         "nor has a query latent")
    batch, seq, _ = u.shape
    heads = config["num_attention_heads"]
    d_n, d_r = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    d_v, rank = config["v_head_dim"], config["kv_lora_rank"]
    group = min(HEADS, heads)
    latent = u @ layer["wkv_a"]
    c_kv = rms_norm(latent[..., :rank], layer["kv_norm"],
                    config["rms_norm_eps"])
    k_r = latent[:, :, None, rank:]

    @jax.checkpoint
    def heads_of(weights):
        wq, wkv_b, wo = weights         # [C, g, ..], [r, g, ..], [g, d_v, C]
        q = jnp.einsum("bsc,cgd->bsgd", u, wq)
        kv = jnp.einsum("bsr,rgd->bsgd", c_kv, wkv_b)
        k = jnp.concatenate(
            [kv[..., :d_n], jnp.broadcast_to(k_r, (batch, seq, group, d_r))],
            axis=-1)
        attended = causal_attention(q, k, kv[..., d_n:],
                                    (d_n + d_r) ** -0.5)
        return jnp.einsum("bsgd,gdc->bsc", attended, wo)

    def grouped(w, lead, width):     # the heads' axis cut into groups, first
        return jnp.moveaxis(w.reshape(
            lead + (heads // group, group, width) + w.shape[len(lead) + 1:]),
            len(lead), 0)

    return jnp.sum(jax.lax.map(heads_of, (
        grouped(layer["wq"], layer["wq"].shape[:1], d_n + d_r),
        grouped(layer["wkv_b"], layer["wkv_b"].shape[:1], d_n + d_v),
        grouped(layer["wo"], (), d_v))), axis=0)


# -- the feed-forward ---------------------------------------------------------

def swiglu(x, w):
    """A SiLU-gated feed-forward on ``x [B, S, C]``, ``ROWS`` rows at a
    time."""
    return by_rows(lambda rows: (jax.nn.silu(rows @ w["w_gate"])
                                 * (rows @ w["w_up"])) @ w["w_down"], x)


def route(u, router, config):
    """(scores ``[B, S, E]``, chosen ``[B, S, K]``, gates ``[B, S, K]``); the
    choice bias is zeros (the module's docstring)."""
    if (config["moe_router_activation_func"] != "sigmoid"
            or config["num_expert_group"] != 1 or config["topk_group"] != 1):
        raise ValueError("this reference routes by sigmoid scores in one "
                         "group")
    scores = jax.nn.sigmoid(u @ router)
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores),
                              config["num_experts_per_token"])
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["moe_renormalize"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return scores, chosen, gates * config["routed_scaling_factor"]


def balance(scores, chosen):
    """``mean_b sum_e f[b, e] P[b, e]``, P from the scores' shares of a
    token's sum."""
    _, seq, experts = scores.shape
    shares = scores / jnp.sum(scores, axis=-1, keepdims=True)
    counts = jnp.sum(jax.nn.one_hot(chosen, experts), axis=(1, 2))
    f = jax.lax.stop_gradient(counts * experts / (chosen.shape[-1] * seq))
    return jnp.mean(jnp.sum(f * jnp.mean(shares, axis=1), axis=-1))


def routed_experts(u, layer, config, first=None, experts=None):
    """(y, this layer's balance loss): the held experts' part of the routed
    sum, and the shared expert.  ``first`` and ``experts``: another share
    than the configuration's (the share test's)."""
    scores, chosen, gates = route(u, layer["router"], config)
    if first is None:
        first = config["deployment"]["first_held_expert"]
    experts = layer["experts"] if experts is None else experts

    def add_expert(y, held):
        expert, index = held
        gate = jnp.sum(jnp.where(chosen == first + index, gates, 0.0),
                       axis=-1)
        return y + gate[..., None] * swiglu(u, expert), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(u),
                        (experts, jnp.arange(experts["w_gate"].shape[0])))
    return y + swiglu(u, layer["shared"]), balance(scores, chosen)


def decoder_layer(x, layer, index, config):
    """(x', the layer's balance loss; 0 for a dense layer)."""
    eps = config["rms_norm_eps"]
    mixer = kda_mixer if is_kda(index, config) else latent_mixer
    h = x + mixer(rms_norm(x, layer["norm_attn"], eps), layer, config)
    u = rms_norm(h, layer["norm_mlp"], eps)
    if "router" in layer:
        y, aux = routed_experts(u, layer, config)
    else:
        y, aux = swiglu(u, layer), jnp.float32(0.0)
    return h + y, aux


def head_loss(x, norm_f, lm_head, targets, config):
    """Mean next-token cross-entropy behind the final norm and the head,
    ``ROWS`` positions at a time."""
    x = rms_norm(x, norm_f, config["rms_norm_eps"])
    batch, seq, hidden = x.shape
    n = _blocks(seq, ROWS)

    @jax.checkpoint
    def block_nll(args):
        rows, wanted = args
        logits = rows @ lm_head
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked[..., 0])

    rows = x.reshape(batch, n, seq // n, hidden).swapaxes(0, 1)
    wanted = targets.reshape(batch, n, seq // n).swapaxes(0, 1)
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
    x = params["embed"][tokens[:, :-1]]
    aux = jnp.float32(0.0)
    for index, layer in enumerate(params["layers"]):
        x, layer_aux = decoder_layer(x, layer, index, config)
        aux = aux + layer_aux
    return (head_loss(x, params["norm_f"], params["lm_head"], tokens[:, 1:],
                      config) + aux_weight(params, config) * aux)


def loss_and_grads(params, tokens, config):
    """(loss, d loss / d params) in float32 at ``highest`` precision:
    ``jax.value_and_grad(loss)``, taken as a reverse sweep of ``jax.vjp`` a
    layer from the layers' kept inputs (75 MB each at 8192 tokens), a
    layer's float32 parameters cast inside its own step and tied to the
    cotangent it waits for by an ``optimization_barrier``, so that the
    compiler does not cast every layer's at the start: beside the program's
    parameters, gradients and temporaries in ``benchmark/compare.py``'s one
    program there is no room for all of them at once."""
    def up(tree):
        return jax.tree.map(lambda p: p.astype(jnp.float32), tree)

    with jax.default_matmul_precision("highest"):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        weight = aux_weight(params, config)
        layers = params["layers"]
        entering = [up(params["embed"])[inputs]]
        for index, layer in enumerate(layers):
            entering.append(decoder_layer(entering[-1], up(layer), index,
                                          config)[0])
        value, head_vjp = jax.vjp(
            lambda x, norm_f, lm_head: head_loss(x, norm_f, lm_head, targets,
                                                 config),
            entering[-1], up(params["norm_f"]), up(params["lm_head"]))
        d_x, d_norm_f, d_lm_head = head_vjp(jnp.float32(1.0))
        d_layers = []
        for index in range(len(layers) - 1, -1, -1):
            layer, d_x = jax.lax.optimization_barrier((layers[index], d_x))
            (_, aux), layer_vjp = jax.vjp(
                lambda x, layer, index=index: decoder_layer(
                    x, layer, index, config), entering[index], up(layer))
            d_x, d_layer = layer_vjp((d_x, jnp.float32(weight)))
            d_layers.append(d_layer)
            value = value + weight * aux
        _, embed_vjp = jax.vjp(lambda table: table[inputs],
                               up(params["embed"]))
        return value, {"embed": embed_vjp(d_x)[0], "layers": d_layers[::-1],
                       "norm_f": d_norm_f, "lm_head": d_lm_head}
