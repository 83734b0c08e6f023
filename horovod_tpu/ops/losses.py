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
exit distribution, less an entropy term.  It walks the exits one at a
time with the same ``_nll_impl`` and ``_nll_bwd``, under a custom VJP of
its own: an exit's weight is known before the walk, so the cotangent of
its logits is known as soon as its loss is, and the walk takes both
gradient products of the head while those logits are alive.  Nothing
logits-sized is kept for the backward pass and no head product is made
again there; the one-pass loss above keeps its logits and has nothing to
gain from this.

``sequence_balance_loss`` is the sequence-wise auxiliary loss of a routed
expert layer (DeepSeek-V2's ``seq_aux``), which
``models/llama.py::RoutedExperts`` sows a layer and ``balance_loss`` gathers
for the training loss to add, weighted, to the cross-entropy.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from horovod_tpu.common import scopes as _scopes

__all__ = ["softmax_cross_entropy", "exit_log_distribution",
           "expected_exit_loss", "sequence_balance_loss",
           "batch_balance_loss", "balance_loss", "indexer_loss"]


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


_nll.defvjp(*_scopes.rules("_nll", _nll_fwd, _nll_bwd))


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
    with _scopes.scope(_scopes.HEAD):
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _weighted_exit_nll(head, consts, hidden, weights, targets):
    """``sum_t sum_tok weights_t nll_t``, exit t's logits ``head(hidden_t,
    *consts)``: one head product an exit, and nothing else, where it is
    not differentiated."""
    def exit_nll(h):
        logits = head(h, *consts)
        with _scopes.scope(_scopes.HEAD):
            return _nll_impl(logits, targets)[0]

    return jnp.sum(weights * jax.lax.map(exit_nll, hidden))


def _weighted_exit_nll_fwd(head, consts, hidden, weights, targets):
    # The cotangent of an exit's logits is known once its loss is:
    # weights_t (softmax - onehot).  So both gradient products are taken
    # here, while the logits are alive, and the backward rule is left with
    # three scalings.  Only dh, the head's summed gradient and the losses
    # leave an iteration: no logits, no lse, no cotangent of their size.
    def exit_and_gradients(head_grads, exit_):
        h, w = exit_
        # This exit's hidden states as a buffer of their own.  Without it
        # XLA:TPU feeds the head's weight-gradient product from a
        # transposed copy of all exits' states that it carries through the
        # loop, which costs that product 1.5 ms an exit at 8192 x 2048 x
        # 49152 on a v5e (PERF.md section 6, PR 31).
        h = jax.lax.optimization_barrier(h)
        # ``head`` enters ``hvd.head`` itself (``LlamaModel.head``), and
        # the pullback's products carry it from there: the scope is
        # entered beside them, never around them.
        logits, pullback = jax.vjp(lambda h, c: head(h, *c), h, consts)
        with _scopes.scope(_scopes.HEAD):
            nll, lse = _nll_impl(logits, targets)
            d_logits, _ = _nll_bwd((logits, targets, lse), w)
        dh, d_consts = pullback(d_logits)
        with _scopes.scope(_scopes.HEAD):
            head_grads = jax.tree.map(jnp.add, head_grads, d_consts)
        return head_grads, (dh, nll)

    head_grads, (dh, nll) = jax.lax.scan(
        exit_and_gradients, jax.tree.map(jnp.zeros_like, consts),
        (hidden, weights))
    return jnp.sum(weights * nll), (head_grads, dh, nll)


def _weighted_exit_nll_bwd(head, res, g):
    head_grads, dh, nll = res

    def scaled(x):
        return (g * x).astype(x.dtype)

    return jax.tree.map(scaled, head_grads), scaled(dh), g * nll, None


_weighted_exit_nll.defvjp(*_scopes.rules(
    "_weighted_exit_nll", _weighted_exit_nll_fwd, _weighted_exit_nll_bwd))


def expected_exit_loss(head, hidden, gate_logits, targets, *,
                       beta: float = 0.1):
    """Mean over all positions of ``sum_t p_t CE_t - beta H(p)``: the
    expected cross-entropy under the exit distribution, with an entropy
    regulariser (a uniform prior over exits; stage I of Zhu et al.,
    arXiv:2510.25741).

    ``hidden [T, ..., H]`` and ``gate_logits [T, ...]`` are what
    ``LlamaModel`` returns with ``total_ut_steps`` = T > 1; ``head`` maps
    one exit's hidden states to logits (``lambda h: model.apply(params, h,
    method="head")``: any callable, and what it closes over is
    differentiated too); ``targets``: integer ``[...]``.  The exits are
    walked one at a time, so one logits tensor and one cotangent of that
    size are alive at a time.  Differentiated, the same walk forms each
    exit's gradients while its logits are there (``_weighted_exit_nll``):
    with ``w_t = p_t / N`` the logits' cotangent is ``w_t (softmax -
    onehot)``, in the logits' dtype, and through the head's own ``jax.vjp``
    it gives the hidden states' gradient and the head's, the latter added
    to one accumulator over the exits in the parameters' dtype.  No logits
    are kept and none are made again; the backward pass scales what the
    walk left.  The gate's gradient flows through ``w`` and the entropy by
    plain autodiff.  Distribution, losses and entropy in float32.
    """
    with _scopes.scope(_scopes.LOOP_EXIT):
        log_p = exit_log_distribution(gate_logits)
        p = jnp.exp(log_p)
        # What ``head`` closes over (the parameters being differentiated)
        # becomes an explicit argument of the custom rule.
        head, consts = jax.closure_convert(head, hidden[0])
        expected_nll = _weighted_exit_nll(
            head, consts, hidden, p / math.prod(targets.shape), targets)
        entropy = -jnp.sum(p * log_p, axis=0)
        return expected_nll - beta * jnp.mean(entropy)


def sequence_balance_loss(scores, chosen):
    """Sequence-wise balance loss of one routed layer (DeepSeek-V2,
    arXiv:2405.04434 eq. 12-14, ``seq_aux``): with ``scores [B, S, E]`` the
    router's softmax over all E experts and ``chosen [B, S, K]`` the K
    experts each token was sent to::

        f[b, e] = (assignments of sequence b to e) E / (K S)
        P[b, e] = mean_s scores[b, s, e]
        loss    = mean_b sum_e f[b, e] P[b, e]

    f counts choices, so it is a constant under differentiation; the
    gradient reaches the router through P.  1 when routing is uniform, E / K
    when every token picks the same K with all the weight.  Float32."""
    _, seq, experts = scores.shape
    k = chosen.shape[-1]
    counts = jnp.sum(chosen[..., None] == jnp.arange(experts), axis=(1, 2),
                     dtype=jnp.float32)                           # [B, E]
    share = jax.lax.stop_gradient(counts * (experts / (k * seq)))
    mean_score = jnp.mean(scores.astype(jnp.float32), axis=1)      # [B, E]
    return jnp.mean(jnp.sum(share * mean_score, axis=-1))


def batch_balance_loss(scores, chosen):
    """Batch-wise balance loss of one routed layer: ``E sum_e f[e] P[e]``
    with f[e] the share of ALL the batch's assignments that chose expert e
    and P[e] its mean router probability over the batch --
    ``sequence_balance_loss`` of the batch as one sequence.  A sequence may
    be lopsided as long as the batch is not."""
    experts = scores.shape[-1]
    return sequence_balance_loss(scores.reshape(1, -1, experts),
                                 chosen.reshape(1, -1, chosen.shape[-1]))


def indexer_loss(collection):
    """Mean over the sparse-attention layers of what each sowed into the
    ``index_losses`` collection: the indexers' loss, whose gradient reaches
    the indexers' matrices alone."""
    collection = collection.get("index_losses", collection)
    return jnp.mean(jnp.stack(jax.tree.leaves(collection)))


def balance_loss(collection):
    """Mean over the routed layers of what each sowed into the ``losses``
    collection (``model.apply(..., mutable=["losses"])``'s second result,
    or its ``"losses"`` entry)."""
    collection = collection.get("losses", collection)
    return jnp.mean(jnp.stack(jax.tree.leaves(collection)))
