"""Plain reference of the decoder the ``ouro-2.6b`` cells train.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision
(on a TPU an fp32 product otherwise runs as one bf16 pass).  No kernel, no
fused projection, nothing imported from the program.  It follows the
published Ouro/Llama layer: token embedding; per layer a pre-norm
(RMSNorm) attention block with rotary positions and a pre-norm SiLU-gated
FFN, both residual; a final RMSNorm; an untied output head; mean
cross-entropy of every position's next token.

Departures, none of which changes the mathematics:

* one pass over the stack (``total_ut_steps`` 1), as the configuration's
  file states;
* rotary positions turn the interleaved pairs ``(x[2i], x[2i+1])``, the
  layout ``models/llama.py`` uses; the published checkpoints turn half
  against half, a fixed permutation of the q and k columns;
* dense causal attention is computed for ``BLOCK`` queries at a time
  against all keys, each layer and each block of the head's loss under
  ``jax.checkpoint``, so that 8192 positions fit beside the weights: the
  backward pass repeats the forward's work and computes the same numbers.

Parameters are a plain tree: ``embed [V, H]``, ``layers`` (a list of
``norm_attn [H]``, ``wq wk wv [H, heads * D]``, ``wo [heads * D, H]``,
``norm_mlp [H]``, ``w_gate w_up [H, F]``, ``w_down [F, H]``), ``norm_f
[H]``, ``lm_head [H, V]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 1024           # queries, and rows of the head's loss, at a time


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotary(x, theta):
    """x: [B, S, heads, D]; position p turns pair i by p / theta^(2i/D)."""
    _, seq, _, dim = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1)
    return turned.reshape(x.shape)


def _blocks(seq: int) -> int:
    block = min(BLOCK, seq)
    if seq % block:
        raise ValueError(f"sequence {seq} is not a multiple of {block}")
    return seq // block


def causal_attention(q, k, v):
    """q, k, v: [B, S, heads, D] -> [B, S, heads, D], softmax over the keys
    at or before each query."""
    batch, seq, heads, dim = q.shape
    n_blocks = _blocks(seq)
    block = seq // n_blocks
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def one_block(args):
        q_block, first = args                       # [B, block, heads, D]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) / jnp.sqrt(
            jnp.float32(dim))
        query_pos = first + jnp.arange(block)
        keep = query_pos[:, None] >= key_pos[None, :]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    q_blocks = q.reshape(batch, n_blocks, block, heads, dim).swapaxes(0, 1)
    out = jax.lax.map(one_block,
                      (q_blocks, jnp.arange(n_blocks) * block))
    return out.swapaxes(0, 1).reshape(batch, seq, heads, dim)


def decoder_layer(x, layer, config):
    batch, seq, _ = x.shape
    heads, dim = config["num_attention_heads"], config["head_dim"]
    kv_heads = config["num_key_value_heads"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])

    y = rms_norm(x, layer["norm_attn"], eps)
    q = (y @ layer["wq"]).reshape(batch, seq, heads, dim)
    k = (y @ layer["wk"]).reshape(batch, seq, kv_heads, dim)
    v = (y @ layer["wv"]).reshape(batch, seq, kv_heads, dim)
    q, k = rotary(q, theta), rotary(k, theta)
    if kv_heads != heads:
        k = jnp.repeat(k, heads // kv_heads, axis=2)
        v = jnp.repeat(v, heads // kv_heads, axis=2)
    attended = causal_attention(q, k, v).reshape(batch, seq, heads * dim)
    x = x + attended @ layer["wo"]

    y = rms_norm(x, layer["norm_mlp"], eps)
    gated = jax.nn.silu(y @ layer["w_gate"]) * (y @ layer["w_up"])
    return x + gated @ layer["w_down"]


def loss(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens [B, S + 1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs]
    for layer in params["layers"]:
        x = jax.checkpoint(
            lambda x, layer: decoder_layer(x, layer, config))(x, layer)
    x = rms_norm(x, params["norm_f"], config["rms_norm_eps"])

    batch, seq, hidden = x.shape
    n_blocks = _blocks(seq)

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
