"""softmax_cross_entropy (ops/losses.py) vs the naive log_softmax+gather
formulation: identical values and gradients, with and without a token
mask — the op exists purely to avoid materializing fp32 log-probs, so
its whole contract is exact numerical agreement."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.ops.losses import (exit_log_distribution,
                                    expected_exit_loss,
                                    softmax_cross_entropy)


def _naive(logits, targets, where=None):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if where is not None:
        nll = jnp.where(where, nll, 0.0)
        return jnp.sum(nll) / jnp.maximum(jnp.sum(where), 1)
    return jnp.mean(nll)


def _data(B=2, S=16, V=97, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 2)
    logits = jax.random.normal(ks[0], (B, S, V), dtype) * 3.0
    targets = jax.random.randint(ks[1], (B, S), 0, V)
    return logits, targets


def test_matches_naive_values_and_grads():
    logits, targets = _data()
    got = softmax_cross_entropy(logits, targets)
    want = _naive(logits, targets)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)

    g1 = jax.grad(lambda l: softmax_cross_entropy(l, targets))(logits)
    g2 = jax.grad(lambda l: _naive(l, targets))(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=1e-6, rtol=1e-5)


def test_masked_matches_naive():
    logits, targets = _data(seed=1)
    where = jax.random.bernoulli(jax.random.key(2), 0.7, targets.shape)
    got = softmax_cross_entropy(logits, targets, where=where)
    want = _naive(logits, targets, where=where)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)

    g1 = jax.grad(
        lambda l: softmax_cross_entropy(l, targets, where=where))(logits)
    g2 = jax.grad(lambda l: _naive(l, targets, where=where))(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=1e-6, rtol=1e-5)


def test_bf16_logits_fp32_math():
    """bf16 logits (the production dtype): loss is computed in fp32 and
    agrees with converting first."""
    logits, targets = _data(dtype=jnp.bfloat16, seed=3)
    got = softmax_cross_entropy(logits, targets)
    want = _naive(logits.astype(jnp.float32), targets)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    assert got.dtype == jnp.float32


def test_all_masked_returns_zero():
    logits, targets = _data(seed=4)
    where = jnp.zeros_like(targets, bool)
    assert float(softmax_cross_entropy(logits, targets, where=where)) == 0.0


def test_sum_reduction():
    logits, targets = _data(seed=5)
    got = softmax_cross_entropy(logits, targets, reduction="sum")
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    want = jnp.sum(-jnp.take_along_axis(logp, targets[..., None], -1))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    with pytest.raises(ValueError, match="reduction"):
        softmax_cross_entropy(logits, targets, reduction="nope")


def test_bf16_grads_match_autodiff_and_keep_dtype():
    """The custom VJP's bf16 cotangent (half-width residuals + grad
    matmuls, the whole point of the op) matches fp32 autodiff to bf16
    rounding."""
    logits, targets = _data(dtype=jnp.bfloat16, seed=6)
    g_bf = jax.grad(lambda l: softmax_cross_entropy(l, targets))(logits)
    assert g_bf.dtype == jnp.bfloat16
    g_ref = jax.grad(
        lambda l: _naive(l, targets))(logits.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(g_bf, np.float32),
                               np.asarray(g_ref), atol=2e-3, rtol=2e-2)


# -- expected_exit_loss: each exit's gradients are formed in the walk that
# -- computes its loss; the check is plain autodiff of the written-out formula

EXITS, ROWS, SEQ, HIDDEN, VOCAB = 4, 2, 12, 16, 53


def _exit_problem(dtype, live_gate=True, seed=0):
    """A head that closes over TWO parameter leaves (a scale and a
    kernel), ``hidden [T, B, S, H]``, ``gate_logits [T, B, S]``, targets."""
    ks = jax.random.split(jax.random.key(seed), 5)
    head_params = {
        "scale": 1.0 + 0.1 * jax.random.normal(ks[0], (HIDDEN,), dtype),
        "kernel": jax.random.normal(ks[1], (HIDDEN, VOCAB), dtype) * 0.5}
    hidden = jax.random.normal(ks[2], (EXITS, ROWS, SEQ, HIDDEN), dtype)
    gates = (jax.random.normal(ks[3], (EXITS, ROWS, SEQ), jnp.float32)
             if live_gate else jnp.zeros((EXITS, ROWS, SEQ), jnp.float32))
    targets = jax.random.randint(ks[4], (ROWS, SEQ), 0, VOCAB)
    return head_params, hidden, gates, targets


def _apply_head(head_params, h):
    return (h * head_params["scale"]) @ head_params["kernel"]


def _exit_loss(head_params, hidden, gates, targets, beta):
    return expected_exit_loss(lambda h: _apply_head(head_params, h),
                              hidden, gates, targets, beta=beta)


def _written_out(head_params, hidden, gates, targets, beta):
    """``mean(sum_t p_t CE_t - beta H(p))`` from ``jax.nn.log_softmax``: no
    custom rule, no checkpoint, every exit's logits alive at once."""
    log_p = exit_log_distribution(gates)
    p = jnp.exp(log_p)
    log_probs = jax.nn.log_softmax(
        _apply_head(head_params, hidden).astype(jnp.float32))
    ce = -jnp.take_along_axis(
        log_probs, jnp.broadcast_to(targets, gates.shape)[..., None],
        axis=-1)[..., 0]
    return jnp.mean(jnp.sum(p * ce, axis=0) + beta * jnp.sum(p * log_p, 0))


def _assert_trees_close(got, want, rel):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        assert g.shape == w.shape
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(np.asarray(g, np.float32), w, rtol=0,
                                   atol=rel * np.abs(w).max() + 1e-12)


@pytest.mark.parametrize("outside", [1.0, 3.0])
@pytest.mark.parametrize("live_gate", [True, False])
@pytest.mark.parametrize("beta", [0.0, 0.1])
def test_exit_loss_and_gradients_equal_autodiff_of_the_formula(
        beta, live_gate, outside):
    """float32, to 1e-5: the value, and the gradients with respect to both
    of the head's leaves, the hidden states and the gate's logits; with a
    scalar cotangent that is not 1 (``outside``)."""
    problem = _exit_problem(jnp.float32, live_gate)
    targets = problem[3]

    def of(loss):
        return jax.value_and_grad(
            lambda head_params, hidden, gates: outside * loss(
                head_params, hidden, gates, targets, beta),
            argnums=(0, 1, 2))(*problem[:3])

    (got, got_grads), (want, want_grads) = of(_exit_loss), of(_written_out)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    _assert_trees_close(got_grads, want_grads, 1e-5)
    assert float(jnp.abs(got_grads[2]).max()) > 1e-4      # the gate's


@pytest.mark.parametrize("outside", [1.0, 3.0])
@pytest.mark.parametrize("beta", [0.0, 0.1])
def test_bf16_exit_gradients_keep_their_dtype_and_match_to_bf16_rounding(
        beta, outside):
    """The production dtype: logits, their cotangent, ``dh`` and the head's
    accumulated gradient in bf16; the reference is float32 autodiff of the
    formula on the same (bf16-rounded) numbers."""
    head_params, hidden, gates, targets = _exit_problem(jnp.bfloat16)
    got, got_grads = jax.value_and_grad(
        lambda *a: outside * _exit_loss(*a, targets, beta),
        argnums=(0, 1, 2))(head_params, hidden, gates)
    assert got.dtype == jnp.float32
    assert {g.dtype for g in jax.tree.leaves(got_grads[:2])} == {
        jnp.dtype(jnp.bfloat16)}
    assert got_grads[2].dtype == jnp.float32
    want, want_grads = jax.value_and_grad(
        lambda *a: outside * _written_out(*a, targets, beta),
        argnums=(0, 1, 2))(*jax.tree.map(
            lambda x: x.astype(jnp.float32), (head_params, hidden)), gates)
    assert float(got) == pytest.approx(float(want), rel=2e-3)
    _assert_trees_close(got_grads, want_grads, 1.6e-2)    # two bf16 ulps


def test_exit_loss_in_a_jitted_train_step_on_the_mesh():
    """``hvd.make_train_step`` over the CPU mesh: one SGD step with the
    loss lands where the written-out formula's does."""
    import horovod_tpu.jax as hvd
    from jax.sharding import NamedSharding, PartitionSpec as P

    hvd.init()
    mesh = hvd.data_parallel_mesh(jax.devices()[:2])
    head_params, hidden, gates, targets = _exit_problem(jnp.float32)
    params = {"head": head_params,
              "mix": jnp.eye(HIDDEN) + 0.01,          # makes the hidden states
              "gate": jnp.full((HIDDEN,), 0.05)}

    def loss_with(loss):
        def loss_fn(params, batch):
            x, y = batch                              # [B, T, S, H], [B, S]
            h = jnp.moveaxis(x, 1, 0) @ params["mix"]
            return loss(params["head"], h, h @ params["gate"], y, 0.1)
        return loss_fn

    batch = jax.device_put((jnp.moveaxis(hidden, 0, 1), targets),
                           NamedSharding(mesh, P("data")))
    after = {}
    for name, loss in (("got", _exit_loss), ("want", _written_out)):
        opt = optax.sgd(0.5)
        step = hvd.make_train_step(loss_with(loss), opt, mesh, donate=False)
        new, _, value = step(params, opt.init(params), batch)
        after[name] = (new, float(value))
    assert after["got"][1] == pytest.approx(after["want"][1], rel=1e-5)
    _assert_trees_close(after["got"][0], after["want"][0], 1e-5)
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         after["got"][0], params)
    assert all(m > 1e-5 for m in jax.tree.leaves(moved)), moved


def _exit_loops(jaxpr):
    """(``lax.scan``/``lax.map`` equations over the exits, every primitive
    name in the jaxpr), sub-jaxprs walked."""
    loops, names = [], []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            names.append(eqn.primitive.name)
            if eqn.primitive.name == "scan" and eqn.params[
                    "length"] == EXITS:
                loops.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return loops, names


def _products(eqn):
    _, names = _exit_loops(eqn.params["jaxpr"])
    return names.count("dot_general")


def test_one_head_product_an_exit_undifferentiated_and_three_differentiated():
    """Evaluation walks the exits with the head's product alone; the
    differentiated walk adds the two gradient products to that same loop
    body, and no loop is left for the backward pass: nothing is
    checkpointed, so no logits are made again."""
    problem = _exit_problem(jnp.float32)
    plain, names = _exit_loops(jax.make_jaxpr(
        lambda *a: _exit_loss(*a, 0.1))(*problem))
    assert [_products(loop) for loop in plain] == [1]
    graded_jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: _exit_loss(*a, 0.1), argnums=(0, 1, 2)))(*problem)
    graded, graded_names = _exit_loops(graded_jaxpr)
    assert [_products(loop) for loop in graded] == [3]
    for name in names + graded_names:
        assert "checkpoint" not in name and "remat" not in name, name
    assert graded_names.count("dot_general") == 3   # none outside the loop
