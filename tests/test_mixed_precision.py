"""master_weights: bf16 compute params must train like fp32 params
because the optimizer math runs on the fp32 master copy."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu.jax as hvd
from horovod_tpu.ops.mixed_precision import (
    MasterWeightsState,
    cast_compute,
    master_weights,
)


def _problem(seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    W = jax.random.normal(k1, (8, 8))
    X = jax.random.normal(k2, (32, 8))
    Y = X @ W

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    return loss_fn, {"w": jnp.zeros((8, 8), jnp.float32)}, (X, Y)


def test_tracks_fp32_training():
    """bf16 params + master_weights(adam) stays close to pure-fp32 adam
    over many steps (the master carries the precision)."""
    loss_fn, params32, data = _problem()
    opt32 = optax.adam(0.05)
    optmw = master_weights(optax.adam(0.05))

    p32, s32 = params32, opt32.init(params32)
    pbf = cast_compute(params32)
    smw = optmw.init(pbf)
    assert smw.master["w"].dtype == jnp.float32

    for _ in range(60):
        g32 = jax.grad(loss_fn)(p32, data)
        u, s32 = opt32.update(g32, s32, p32)
        p32 = optax.apply_updates(p32, u)

        gbf = jax.grad(loss_fn)(pbf, data)
        assert gbf["w"].dtype == jnp.bfloat16
        u, smw = optmw.update(gbf, smw, pbf)
        assert u["w"].dtype == jnp.bfloat16
        pbf = optax.apply_updates(pbf, u)

    final32 = float(loss_fn(p32, data))
    finalmw = float(loss_fn(cast_compute(pbf, jnp.float32), data))
    # Pure bf16 adam diverges visibly here; master-weight training lands
    # within bf16 rounding of the fp32 trajectory.
    assert finalmw < final32 * 1.5 + 1e-3, (final32, finalmw)
    # Params track the rounded master.
    np.testing.assert_allclose(
        np.asarray(pbf["w"], np.float32),
        np.asarray(smw.master["w"].astype(jnp.bfloat16), np.float32))


def test_composes_with_distributed_optimizer_and_train_step(n_devices):
    loss_fn, params, data = _problem(seed=1)
    mesh = hvd.data_parallel_mesh()
    opt = hvd.DistributedOptimizer(master_weights(optax.adam(0.05)))
    step = hvd.make_train_step(loss_fn, opt, mesh)
    pbf = cast_compute(params)
    state = jax.jit(opt.inner.init)(pbf)
    losses = []
    for _ in range(40):
        pbf, state, loss = step(pbf, state, data)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.1, losses
    assert jax.tree.leaves(pbf)[0].dtype == jnp.bfloat16
    assert state.master["w"].dtype == jnp.float32


def test_requires_params():
    opt = master_weights(optax.sgd(0.1))
    p = {"w": jnp.zeros(3, jnp.bfloat16)}
    s = opt.init(p)
    with pytest.raises(ValueError, match="params"):
        opt.update({"w": jnp.zeros(3, jnp.bfloat16)}, s)


def test_integer_leaves_pass_through():
    opt = master_weights(optax.sgd(0.1))
    p = {"w": jnp.zeros(4, jnp.bfloat16), "step": jnp.zeros((), jnp.int32)}
    s = opt.init(p)
    g = {"w": jnp.ones(4, jnp.bfloat16), "step": jnp.zeros((), jnp.int32)}
    u, s = opt.update(g, s, p)
    assert u["step"].dtype == jnp.int32
    assert float(jnp.sum(jnp.abs(u["step"]))) == 0.0


@pytest.mark.parametrize("compute", [jnp.bfloat16, jnp.float16])
def test_master_reads_the_compute_dtypes_gradient_behind_a_barrier(
        monkeypatch, compute):
    """A large leaf's gradient reaches ``master_weights`` through
    ``DistributedOptimizer``'s barrier (PR 44) in the compute dtype the
    program states, and master, moments and the rounded weight are, to the
    last bit, what the bare transformation gives from that gradient."""
    monkeypatch.setattr(hvd, "ALONE_FROM_ELEMENTS", 32)
    loss_fn, params, data = _problem(seed=2)
    inner = master_weights(optax.adamw(0.05))
    opt = hvd.DistributedOptimizer(inner, reduce_gradients=False)
    low = cast_compute(params, compute)

    def step(update):
        def run(p, s):
            grads = jax.grad(loss_fn)(p, data)
            assert grads["w"].dtype == compute
            updates, s = update(grads, s, p)
            return optax.apply_updates(p, updates), s
        return jax.jit(run)

    got = step(opt.update)(low, inner.init(low))
    assert hvd.update_counts() == {"alone": 1, "fused": 0}
    want = step(inner.update)(low, inner.init(low))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert got[0]["w"].dtype == compute
    assert got[1].master["w"].dtype == jnp.float32
