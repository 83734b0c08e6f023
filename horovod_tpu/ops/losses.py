"""Loss ops tuned for TPU memory traffic.

No reference equivalent (the reference has no loss library); this exists
because the naive causal-LM loss — ``log_softmax`` then gather —
materializes a full fp32 log-probability tensor the size of the logits
([B, S, V]; 2 GB at B=8, S=2048, V=32k) and then re-reads it, making the
loss a multi-gigabyte HBM round trip.

``softmax_cross_entropy`` computes ``logsumexp(logits) - logits[target]``
with a custom VJP whose residuals are the logits AS GIVEN (bf16 when the
model's head emits bf16 — ``LlamaConfig.logits_dtype``) plus the tiny
fp32 lse ``[B, S]``:

* forward: the fp32 upcast fuses into the reduction passes over the
  logits, so the only logits-sized tensor in memory is the model's own
  output;
* backward: ``softmax - onehot`` is recomputed from those residuals and
  the cotangent is emitted in the logits dtype, so the grad matmuls
  (dW, dX) read half-width operands.

Versus plain autodiff of the lse form (which stores an f32 copy of the
logits and emits an f32 cotangent), this halves every logits-sized
tensor's bytes when the head computes in bf16.  Same math; gradients
match autodiff to bf16 rounding (tests/test_losses.py).

``expected_exit_loss`` is the objective of a looped LM
(``LlamaModel`` with ``total_ut_steps`` > 1): every exit's cross-entropy
through the one shared head, weighted token by token with the learned
exit distribution, less an entropy term.  It is built on the same
``_nll``, one exit at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from horovod_tpu.common import scopes as _scopes

__all__ = ["softmax_cross_entropy", "exit_log_distribution",
           "expected_exit_loss"]


def _nll_impl(logits, targets):
    # Hand-rolled logsumexp: max and gather read the logits dtype
    # directly, and the f32 upcast has exactly ONE consumer (the exp-sum
    # reduce), so XLA fuses the convert into the reduction pass instead
    # of materializing an f32 copy of the logits for multiple readers
    # (profiled: jax.nn.logsumexp over the upcast wrote an f32 [B,S,V]).
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1)).astype(jnp.float32)
    s = jnp.sum(jnp.exp(logits.astype(jnp.float32) - m[..., None]), axis=-1)
    lse = m + jnp.log(s)
    tgt = jnp.take_along_axis(
        logits, targets[..., None].astype(jnp.int32),
        axis=-1)[..., 0].astype(jnp.float32)
    return lse - tgt, lse


@jax.custom_vjp
def _nll(logits, targets):
    """Per-token negative log-likelihood [...], from logits [..., V]."""
    return _nll_impl(logits, targets)[0]


def _nll_fwd(logits, targets):
    nll, lse = _nll_impl(logits, targets)
    return nll, (logits, targets, lse)


def _nll_bwd(res, g):
    logits, targets, lse = res
    # softmax recomputed from the saved (possibly bf16) logits + f32 lse;
    # the onehot subtraction fuses as iota==target, so nothing V-sized
    # materializes beyond the returned cotangent — which is emitted in
    # the logits dtype so the downstream dW/dX matmuls read half-width.
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
              == targets[..., None].astype(jnp.int32))
    d = (p - onehot.astype(jnp.float32)) * g[..., None].astype(jnp.float32)
    return d.astype(logits.dtype), None


_nll.defvjp(_nll_fwd, _nll_bwd)


def softmax_cross_entropy(logits, targets, *, where=None,
                          reduction: str = "mean"):
    """Token cross-entropy from (possibly bf16) logits.

    ``logits``: [..., V]; ``targets``: integer [...]; ``where``: optional
    boolean [...] mask of tokens to include (packing/padding).  Returns a
    scalar fp32 ``reduction`` ("mean" over selected tokens, or "sum" —
    the form sharded losses need when the mean denominator is the GLOBAL
    token count psummed outside).
    """
    if reduction not in ("mean", "sum"):
        raise ValueError(f"unknown reduction {reduction!r}")
    # Reverse-mode only: the custom_vjp that keeps the residuals bf16
    # forfeits forward-mode AD (jax.jvp/jax.hessian over this op raise).
    nll = _nll(logits, targets)
    if where is not None:
        nll = jnp.where(where, nll, 0.0)
    if reduction == "sum":
        return jnp.sum(nll)
    if where is not None:
        return jnp.sum(nll) / jnp.maximum(jnp.sum(where), 1)
    return jnp.mean(nll)


def exit_log_distribution(gate_logits):
    """``ln p_t`` of leaving a looped model at pass t, from the gate's
    logits ``[T, ...]``, in float32.

    With ``lambda_t = sigmoid(g_t)`` the chance of leaving at pass t once
    there: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for t < T, and the
    last pass takes what is left, ``p_T = prod_{j<T} (1 - lambda_j)``
    (``g_T`` is not read).  The ``p_t`` add up to 1.
    """
    g = gate_logits.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g[:-1]), axis=0)   # ln prod(1 - l)
    reached = jnp.concatenate([jnp.zeros_like(g[:1]), stay[:-1]], axis=0)
    return jnp.concatenate(
        [jax.nn.log_sigmoid(g[:-1]) + reached, stay[-1:]], axis=0)


def expected_exit_loss(head, hidden, gate_logits, targets, *,
                       beta: float = 0.1):
    """Mean over all positions of ``sum_t p_t CE_t - beta H(p)``: the
    expected cross-entropy under the exit distribution, with an entropy
    regulariser (a uniform prior over exits; stage I of Zhu et al.,
    arXiv:2510.25741).

    ``hidden [T, ..., H]`` and ``gate_logits [T, ...]`` are what
    ``LlamaModel`` returns with ``total_ut_steps`` = T > 1; ``head`` maps
    one exit's hidden states to logits (``lambda h: model.apply(params, h,
    method="head")``); ``targets``: integer ``[...]``.  The exits are
    walked by ``lax.map``, each exit's head and cross-entropy under
    ``jax.checkpoint``: the forward pass keeps an exit's ``[...]`` losses
    and no logits, and the backward pass is a loop that makes one exit's
    logits again, adds its part of the head's gradient to one accumulator
    and frees them, so one logits tensor and one cotangent of that size
    are alive at a time.  Distribution and entropy in float32.
    """

    @jax.checkpoint
    def exit_nll(h):
        return _nll(head(h), targets)

    with jax.named_scope(_scopes.LOOP_EXIT):
        log_p = exit_log_distribution(gate_logits)
        p = jnp.exp(log_p)
        nll = jax.lax.map(exit_nll, hidden)
        entropy = -jnp.sum(p * log_p, axis=0)
        return jnp.mean(jnp.sum(p * nll, axis=0) - beta * entropy)
