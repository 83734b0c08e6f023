"""Plain reference of the decoder the ``phi-4-mini-flash`` cell trains:
Phi-4-mini-flash-reasoning (its ``config.json``, ``model_type``
``phi4flash``), the decoder-hybrid-decoder stack SambaY (Ren et al.,
arXiv:2507.06607) with differential attention (Ye et al., arXiv:2410.05258)
over Mamba (Gu & Dao, arXiv:2312.00752) -- with its loss.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no chunked form (the recurrence runs TOKEN BY TOKEN), the convolution
as shifted adds, the causal softmax and the window as a mask, nothing
imported from the program.  Every number is a key of the configuration's file
(the Mamba sizes under ``assumed``).  x is the residual stream, LN a
LayerNorm with scale and bias at ``layer_norm_eps``::

    every layer:  h = x + Mixer(LN1(x));  x' = h + SwiGLU(LN2(h))
    embedding; N = ``num_hidden_layers`` layers; LN; logits = LN(x) E^T
    (``tie_word_embeddings``); no position is added anywhere

**Placement** (N % 4 = 0, ``mb_per_layer`` = 2): even index a Mamba-kind
mixer, odd an attention-kind one.  Index < N / 2: Mamba; window attention
(query t sees keys s with ``0 <= t - s < sliding_window``).  Index N / 2:
Mamba that also gives the memory m.  Index N / 2 + 1: full causal attention
that also gives its k and v.  Index >= N / 2 + 2: even a gated memory unit
reading m, odd cross-attention to that k and v, full causal.

**Mamba** (I = ``assumed.expand`` x hidden, N_s = ``assumed.d_state``, K =
``assumed.d_conv`` taps, R = ``assumed.dt_rank``), u the normed state::

    [v' | z] = u W_in;   v = silu(conv_K(v') + b_conv)      causal, depthwise
    [r | B | C] = v W_x;   D_t = softplus(r_t W_dt + b_dt);   A = -exp(A_log)
    h_t[d, n] = exp(D_t[d] A[d, n]) h_{t-1}[d, n] + D_t[d] B_t[n] v_t[d]
    y_t[d] = sum_n C_t[n] h_t[d, n] + D[d] v_t[d]            h_0 = 0
    out = (y * silu(z)) W_out;   the memory is m = y (the skip in it, the
                                 gate not)

**Gated memory unit**: ``(m * silu(u W_1)) W_2``.

**Differential attention** (n = ``num_attention_heads`` over m =
``num_key_value_heads`` heads of D = ``head_dim``; window, full and cross
alike): ``[q | k | v] = u W_qkv + b`` (a cross layer: ``q = u W_q + b`` and
the k, v of layer N / 2 + 1); query pair j = heads (2j, 2j + 1) = (q1, q2),
key-value pair i = heads (2i, 2i + 1), pair j reads pair ``j // (n / m)``::

    a1 = softmax(q1 k1^T / sqrt(D)) [v1 | v2];  a2 = softmax(q2 k2^T / sqrt(D)) [v1 | v2]
    lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 index)
    o = RMSNorm_2D(a1 - lambda a2) * g * (1 - lambda_init);   out = o W_o + b_o

**Loss**: mean next-token cross-entropy of every position.

**Departures from the papers**, each noted at its line: none in the
arithmetic; the two maps of a pair attend the pair's values side by side
(arXiv:2410.05258's own form); lambda_init's depth is the layer's index in
the stack as built.

So that 8192 positions fit beside the program in ``benchmark/compare.py``'s
one program, nothing of which changes a number: the recurrence is a nested
``lax.scan``, ``TOKENS`` tokens to a checkpoint; attention takes ``QUERIES``
queries at a time against all keys; a feed-forward and the head's loss
``ROWS`` rows at a time; each layer and each of those blocks under
``jax.checkpoint``.

Parameters are a plain tree: ``embed [V, H]``; ``layers``, a list, each with
``norm1 norm2`` (``scale``, ``bias``), ``w_gate w_up [H, F]``, ``w_down [F,
H]`` and, a Mamba layer: ``in_proj [H, 2 I]``, ``conv_w [K, I]``, ``conv_b``,
``x_proj [I, R + 2 N_s]``, ``dt_proj [R, I]``, ``dt_bias``, ``a_log [I,
N_s]``, ``d [I]``, ``out_proj [I, H]``; a unit: ``gmu_in [H, I]``, ``gmu_out
[I, H]``; an attention layer: ``wqkv [H, (n + 2 m) D]`` (a cross layer ``wq
[H, n D]``) and ``bqkv`` (``bq``), ``wo [n D, H]``, ``bo``, ``lambda_q1
lambda_k1 lambda_q2 lambda_k2 [D]``, ``subln [2 D]``; ``norm_f``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROWS = 1024            # rows of a feed-forward and of the head's loss at a time
QUERIES = 128          # queries of softmax attention at a time
TOKENS = 128           # tokens of the recurrence to a checkpoint


def layer_norm(x, w, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * w["scale"] + w["bias"])


def _blocks(seq: int, block: int) -> int:
    """Blocks of at most ``block`` rows that divide ``seq``."""
    n = -(-seq // block)
    while seq % n:
        n += 1
    return n


def by_rows(fn, x, block=ROWS):
    """``fn`` of ``x [B, S, ..]`` a block of rows at a time, each block
    under a checkpoint."""
    batch, seq = x.shape[:2]
    n = _blocks(seq, block)
    rows = x.reshape(batch, n, seq // n, *x.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(jax.checkpoint(fn), rows)
    return out.swapaxes(0, 1).reshape(batch, seq, *out.shape[3:])


def placement(index: int, layers: int) -> str:
    """``"mamba"``, ``"window"``, ``"full"``, ``"gmu"`` or ``"cross"``."""
    half = layers // 2
    if index % 2 == 0:
        return "mamba" if index <= half else "gmu"
    return ("window" if index < half else
            "full" if index == half + 1 else "cross")


# -- Mamba --------------------------------------------------------------------

def short_convolution(x, taps, bias):
    """``y[t] = sum_i taps[i] x[t - (K - 1) + i] + bias``; x ``[B, S, C]``."""
    seq, k = x.shape[1], taps.shape[0]
    return bias + sum(
        jnp.pad(x, ((0, 0), (k - 1 - i, 0), (0, 0)))[:, :seq] * taps[i]
        for i in range(k))


def selective_scan(v, step, a, b, c):
    """The recurrence, one token a step.  v, step ``[B, S, I]``, a ``[I,
    N]``, b, c ``[B, S, N]`` -> ``y [B, S, I]`` without the skip."""
    batch, seq, inner = v.shape

    def token(state, x):
        v, step, b, c = x
        state = jnp.exp(step[..., None] * a) * state + (
            (step * v)[..., None] * b[:, None, :])
        return state, jnp.einsum("bdn,bn->bd", state, c)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    n = _blocks(seq, TOKENS)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(n, seq // n, batch, x.shape[-1])
               for x in (v, step, b, c))
    _, y = jax.lax.scan(block, jnp.zeros((batch, inner, a.shape[1]),
                                         jnp.float32), xs)
    return jnp.moveaxis(y.reshape(seq, batch, inner), 0, 1)


def mamba_mixer(u, layer, config):
    """(the mixer's output, the memory y)."""
    sizes = config["assumed"]
    inner, state = sizes["expand"] * config["hidden_size"], sizes["d_state"]
    rank = sizes["dt_rank"]
    projected = u @ layer["in_proj"]
    v, z = projected[..., :inner], projected[..., inner:]
    v = jax.nn.silu(short_convolution(v, layer["conv_w"], layer["conv_b"]))
    rbc = v @ layer["x_proj"]
    step = jax.nn.softplus(rbc[..., :rank] @ layer["dt_proj"]
                           + layer["dt_bias"])
    y = selective_scan(v, step, -jnp.exp(layer["a_log"]),
                       rbc[..., rank:rank + state],
                       rbc[..., rank + state:]) + layer["d"] * v
    return (y * jax.nn.silu(z)) @ layer["out_proj"], memory_of(
        y, z, layer["d"] * v)


def memory_of(y, z, skip):
    """What the last Mamba layer shares: y with its skip and BEFORE the gate
    (arXiv:2507.06607)."""
    return y


def memory_unit(u, layer, memory):
    return (memory * jax.nn.silu(u @ layer["gmu_in"])) @ layer["gmu_out"]


# -- differential attention ---------------------------------------------------

def causal_attention(q, k, v, window):
    """q, k ``[B, S, heads, D]``, v ``[B, S, heads, Dv]`` (k and v already
    repeated to the query heads) -> ``[B, S, heads, Dv]``: softmax over the
    keys s with ``0 <= t - s`` (``< window`` where that is not None),
    ``QUERIES`` queries at a time."""
    batch, seq, heads, dim = q.shape
    n = _blocks(seq, QUERIES)
    block = seq // n
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def one_block(args):
        q_block, first = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) * dim ** -0.5
        back = (first + jnp.arange(block))[:, None] - key_pos[None, :]
        keep = back >= 0
        if window is not None:
            keep = keep & (back < window)
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    q_blocks = q.reshape(batch, n, block, heads, dim).swapaxes(0, 1)
    out = jax.lax.map(one_block, (q_blocks, jnp.arange(n) * block))
    return out.swapaxes(0, 1).reshape(batch, seq, heads, v.shape[-1])


def lambda_init(index: int) -> float:
    # The depth in lambda_init is the layer's index in the stack as built.
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def lambda_of(layer, index):
    return (jnp.exp(jnp.sum(layer["lambda_q1"] * layer["lambda_k1"]))
            - jnp.exp(jnp.sum(layer["lambda_q2"] * layer["lambda_k2"]))
            + lambda_init(index))


def pair_norm(diff, scale, eps):
    """RMSNorm over a head pair's 2 D value lanes."""
    return diff * jax.lax.rsqrt(jnp.mean(diff * diff, axis=-1, keepdims=True)
                                + eps) * scale


def out_factor(index: int) -> float:
    return 1.0 - lambda_init(index)


def attention_mixer(u, layer, config, index, window, kv=None):
    """(the mixer's output, the layer's k and v ``[B, S, m D]``)."""
    batch, seq, _ = u.shape
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim = config["head_dim"]
    if kv is None:
        qkv = u @ layer["wqkv"] + layer["bqkv"]
        q = qkv[..., :heads * dim]
        kv = (qkv[..., heads * dim:(heads + kv_heads) * dim],
              qkv[..., (heads + kv_heads) * dim:])
    else:
        q = u @ layer["wq"] + layer["bq"]
    k, v = kv
    q = q.reshape(batch, seq, heads // 2, 2, dim)
    k = k.reshape(batch, seq, kv_heads // 2, 2, dim)
    # A pair's two value heads side by side: the 2 D lanes both maps read.
    pair_v = v.reshape(batch, seq, kv_heads // 2, 2 * dim)
    # Query pair j reads key-value pair j // (n / m): written as a repeat.
    k, pair_v = (jnp.repeat(t, heads // kv_heads, axis=2)
                 for t in (k, pair_v))
    a1 = causal_attention(q[:, :, :, 0], k[:, :, :, 0], pair_v, window)
    a2 = causal_attention(q[:, :, :, 1], k[:, :, :, 1], pair_v, window)
    out = pair_norm(a1 - lambda_of(layer, index) * a2, layer["subln"],
                    config["layer_norm_eps"]) * out_factor(index)
    return (out.reshape(batch, seq, heads * dim) @ layer["wo"] + layer["bo"],
            kv)


# -- the stack ----------------------------------------------------------------

def swiglu(x, layer):
    return by_rows(lambda rows: (rows @ layer["w_up"] * jax.nn.silu(
        rows @ layer["w_gate"])) @ layer["w_down"], x)


def decoder_layer(x, layer, shared, index, config):
    """(x', what the later layers read: ``memory`` and ``kv``)."""
    eps = config["layer_norm_eps"]
    layers = config["num_hidden_layers"]
    kind = placement(index, layers)
    u = layer_norm(x, layer["norm1"], eps)
    if kind == "mamba":
        mixed, y = mamba_mixer(u, layer, config)
        if index == layers // 2:
            shared = {**shared, "memory": y}
    elif kind == "gmu":
        mixed = memory_unit(u, layer, shared["memory"])
    else:
        mixed, kv = attention_mixer(
            u, layer, config, index,
            config["sliding_window"] if kind == "window" else None,
            shared["kv"] if kind == "cross" else None)
        if kind == "full":
            shared = {**shared, "kv": kv}
    h = x + mixed
    return h + swiglu(layer_norm(h, layer["norm2"], eps), layer), shared


def hidden_states(params, tokens, config):
    """The final normed states ``[B, S, H]``."""
    if len(params["layers"]) != config["num_hidden_layers"]:
        raise ValueError(f"{len(params['layers'])} layers for "
                         f"num_hidden_layers {config['num_hidden_layers']}")
    x = params["embed"][tokens]
    shared = {}
    for index, layer in enumerate(params["layers"]):
        x, shared = jax.checkpoint(
            lambda x, layer, shared, index=index: decoder_layer(
                x, layer, shared, index, config))(x, layer, shared)
    return layer_norm(x, params["norm_f"], config["layer_norm_eps"])


def loss(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = hidden_states(params, inputs, config)
    batch, seq, hidden = x.shape
    n_blocks = _blocks(seq, ROWS)

    @jax.checkpoint
    def block_nll(args):
        rows, wanted = args
        logits = rows @ params["embed"].T           # tie_word_embeddings
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
