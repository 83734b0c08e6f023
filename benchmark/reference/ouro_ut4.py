"""Plain reference of the looped decoder the ``ouro-2.6b-ut4`` cell trains:
Ouro-2.6B as published, ``total_ut_steps`` weight-shared passes over the
layer stack, the exit gate and the expected-exit loss (ByteDance's LoopLM;
equations as in Zhu et al., "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741, stage I).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
no kernel, nothing imported from the program.  The layer is
``benchmark/reference/ouro.py``'s, which the two configurations share
(pre-norm RMSNorm -> MHA with rotary positions -> residual; RMSNorm ->
SiLU-gated FFN -> residual): they differ in the loop alone.  With E the
embedding, Stack the layers in order, N the final RMSNorm, W the untied
head, ``w_g, b_g`` the gate (one Linear H -> 1 with bias, shared by all
passes), T = ``total_ut_steps`` and beta = ``assumed.exit_entropy_beta``::

    h(0) = E[tokens];      h(t) = N(Stack(h(t-1)))           t = 1..T
    logits(t) = h(t) W;    g(t) = h(t) . w_g + b_g;   l_t = sigmoid(g(t))
    p_t = l_t prod_{j<t} (1 - l_j)   for t < T;   p_T = prod_{j<T} (1 - l_j)
    loss of a token = sum_t p_t CE(logits(t), target) - beta H(p),
                      H(p) = - sum_t p_t ln p_t
    loss = mean over all positions

The same weights and the same rotary positions in every pass; the
normalised h(t) is what pass t + 1 reads and what exit t reads.

Departures, none of which changes a value:

* those of ``ouro.py`` (interleaved rotary pairs; dense causal attention
  for ``BLOCK`` queries at a time; each layer under ``jax.checkpoint``);
* every pass is under a ``jax.checkpoint`` of its own as well, and each
  exit's head and cross-entropy is computed for ``BLOCK`` rows at a time
  under one, so that four float32 passes over 8192 positions fit on one
  chip: the backward pass repeats forward work and computes the same
  numbers;
* the passes are walked by ``lax.scan`` and not by a Python loop.  The
  same operations in the same order; but the scan's backward pass adds
  each pass's weight gradients to one accumulator, where XLA, given the
  four passes unrolled, holds every pass's matmul operands until it can
  sum the four products at once (21.8 GiB at 8192 positions, PR 26).

Parameters: ``ouro.py``'s tree and ``exit_gate`` (``kernel [H, 1]``,
``bias [1]``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import ouro


def one_pass(x, params, config):
    """N(Stack(x)): the layers in order, then the final norm."""
    for layer in params["layers"]:
        x = jax.checkpoint(
            lambda x, layer: ouro.decoder_layer(x, layer, config))(x, layer)
    return ouro.rms_norm(x, params["norm_f"], config["rms_norm_eps"])


def exit_nll(h, lm_head, targets):
    """Per-position cross-entropy ``[B, S]`` of one exit's hidden states
    through the head, ``BLOCK`` rows at a time."""
    batch, seq, hidden = h.shape
    n_blocks = ouro._blocks(seq)

    @jax.checkpoint
    def block_nll(args):
        rows, wanted = args                         # [B, block, H], [B, block]
        logits = rows @ lm_head
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)
        return jax.nn.logsumexp(logits, axis=-1) - picked[..., 0]

    rows = h.reshape(batch, n_blocks, seq // n_blocks, hidden).swapaxes(0, 1)
    wanted = targets.reshape(batch, n_blocks, seq // n_blocks).swapaxes(0, 1)
    return jax.lax.map(block_nll, (rows, wanted)).swapaxes(0, 1).reshape(
        batch, seq)


def exit_distribution(gates):
    """p_t ``[T, B, S]`` from the gate's logits ``[T, B, S]``."""
    leave = jax.nn.sigmoid(gates)
    p, reached = [], jnp.ones_like(leave[0])
    for t in range(len(gates) - 1):
        p.append(leave[t] * reached)
        reached = reached * (1.0 - leave[t])
    return jnp.stack(p + [reached])


def loss(params, tokens, config):
    """Mean expected-exit loss of ``tokens [B, S + 1]``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    beta = config["assumed"]["exit_entropy_beta"]
    gate = params["exit_gate"]

    def pass_and_exit(h, _):
        h = jax.checkpoint(lambda h: one_pass(h, params, config))(h)
        return h, (exit_nll(h, params["lm_head"], targets),
                   (h @ gate["kernel"])[..., 0] + gate["bias"][0])

    _, (nll, gates) = jax.lax.scan(pass_and_exit, params["embed"][inputs],
                                   None, length=config["total_ut_steps"])
    p = exit_distribution(gates)
    # p ln p -> 0 as p -> 0; a float32 sigmoid does not reach 0 before
    # |g| ~ 88, so the guard only keeps a NaN out of the gradient there.
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-37)), axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0) - beta * entropy)


def loss_and_grads(params, tokens, config):
    """(loss, d loss / d params) in float32 at ``highest`` precision."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return jax.value_and_grad(loss)(params, tokens, config)
