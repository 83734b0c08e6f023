"""DistributedOptimizer correctness.

Reference parity: gradient-correctness-through-collective tests
(test_tensorflow.py:321-347; test_torch.py:351-403): a distributed step over
N shards must equal a single-process step over the concatenated batch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd


def _make_data(n_devices, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n_devices * 4, 3).astype(np.float32)
    w_true = rng.randn(3, 2).astype(np.float32)
    y = x @ w_true + 0.01 * rng.randn(n_devices * 4, 2).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def _loss_fn(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def test_distributed_step_matches_global_step(n_devices):
    x, y = _make_data(n_devices)
    params = {"w": jnp.zeros((3, 2)), "b": jnp.zeros((2,))}
    opt = optax.sgd(0.1)

    # Single-device reference step over the full batch (computed first:
    # the distributed step donates its params/opt_state buffers).
    grads = jax.grad(_loss_fn)(params, (x, y))
    updates, _ = opt.update(grads, opt.init(params), params)
    p_ref = optax.apply_updates(params, updates)
    ref_loss = _loss_fn(params, (x, y))

    mesh = hvd.data_parallel_mesh()
    step = hvd.make_train_step(_loss_fn, opt, mesh)
    opt_state = opt.init(params)
    p1, s1, loss1 = step(params, opt_state, (x, y))

    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p_ref["w"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(p1["b"]), np.asarray(p_ref["b"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(loss1), float(ref_loss), rtol=1e-5)


def test_distributed_optimizer_optax_interface(n_devices):
    """DistributedOptimizer quacks like an optax transformation, and under
    shard_map reduces gradients across shards."""
    mesh = hvd.data_parallel_mesh()
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), axis_name="data")
    params = {"w": jnp.ones((4,))}
    state = opt.init(params)

    def fn(grads_shard):
        updates, _ = opt.update({"w": grads_shard}, state, params)
        return updates["w"]

    grads = jnp.arange(n_devices * 4, dtype=jnp.float32).reshape(n_devices, 4)
    out = jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=P("data"), out_specs=P(),
                      check_vma=False)
    )(grads)
    mean_grad = np.asarray(grads).mean(axis=0)
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1), -mean_grad, rtol=1e-6
    )


def test_distributed_optimizer_compression(n_devices):
    mesh = hvd.data_parallel_mesh()
    opt = hvd.DistributedOptimizer(
        optax.sgd(1.0), axis_name="data", compression=hvd.Compression.bf16
    )
    params = {"w": jnp.ones((8,))}
    state = opt.init(params)

    def fn(g):
        updates, _ = opt.update({"w": g}, state, params)
        return updates["w"]

    grads = jnp.ones((n_devices, 8), jnp.float32)
    out = jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=P("data"), out_specs=P(),
                      check_vma=False)
    )(grads)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), -1.0, rtol=1e-2)


def test_broadcast_parameters_in_jit(n_devices):
    mesh = hvd.data_parallel_mesh()
    params = {
        "w": jnp.arange(n_devices * 4, dtype=jnp.float32).reshape(n_devices, 4),
        "b": jnp.arange(n_devices * 2, dtype=jnp.float32).reshape(n_devices, 2),
    }

    def fn(p):
        return hvd.broadcast_parameters(p, root_rank=2, axis_name="data")

    out = jax.jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=({"w": P("data"), "b": P("data")},),
            out_specs={"w": P(), "b": P()},
            check_vma=False,
        )
    )(params)
    np.testing.assert_allclose(
        np.asarray(out["w"]), np.asarray(params["w"])[2:3]
    )
    np.testing.assert_allclose(
        np.asarray(out["b"]), np.asarray(params["b"])[2:3]
    )


def test_broadcast_parameters_eager_size1():
    params = {"w": jnp.ones((3,)), "b": jnp.zeros((2,))}
    out = hvd.broadcast_parameters(params, root_rank=0)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        params,
        out,
    )


def test_training_converges(n_devices):
    """End-to-end: distributed SGD actually learns the linear map."""
    x, y = _make_data(n_devices, seed=3)
    params = {"w": jnp.zeros((3, 2)), "b": jnp.zeros((2,))}
    opt = optax.sgd(0.2)
    mesh = hvd.data_parallel_mesh()
    step = hvd.make_train_step(_loss_fn, opt, mesh)
    opt_state = opt.init(params)
    loss = None
    for _ in range(60):
        params, opt_state, loss = step(params, opt_state, (x, y))
    assert float(loss) < 1e-2, float(loss)


def test_make_train_step_binds_mesh_axes(n_devices):
    """Regression: a user DistributedOptimizer with axis_name=None must
    reduce over the step mesh's axes (data AND fsdp), not the default mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu.jax as hvd

    mesh = hvd.build_mesh({"data": 4, "fsdp": 2})
    params = {"w": jnp.zeros((4,))}

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] - y) ** 2)

    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((16, 4), dtype=np.float32))
    Y = jnp.asarray(rng.standard_normal(16, dtype=np.float32))

    opt = optax.sgd(0.1)
    step_plain = hvd.make_train_step(loss_fn, opt, mesh, donate=False)
    step_dist = hvd.make_train_step(
        loss_fn, hvd.DistributedOptimizer(opt), mesh, donate=False
    )
    p1, _, _ = step_plain(params, opt.init(params), (X, Y))
    p2, _, _ = step_dist(params, opt.init(params), (X, Y))
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               rtol=1e-5)


def test_init_identity_validation():
    import pytest

    from horovod_tpu.common.basics import HorovodBasics

    b = HorovodBasics()
    with pytest.raises(ValueError, match="rank"):
        b.init(rank=3, size=1)
    b2 = HorovodBasics()
    with pytest.raises(ValueError, match="half-specified"):
        b2.init(rank=2)
    b3 = HorovodBasics()
    with pytest.raises(ValueError, match="local"):
        b3.init(rank=0, size=2, local_rank=1, local_size=1)


# -- a large weight's update behind its own barrier (PR 44) -------------------
#
# ``hvd.ALONE_FROM_ELEMENTS`` is sized for the chip (40M elements); here it
# is patched to 64 so that a tree of a few hundred numbers has leaves on both
# sides of it.

SMALL_N = 64


def _inner(kind):
    from horovod_tpu.ops.mixed_precision import master_weights

    if kind == "adamw":
        return master_weights(optax.adamw(3e-2, weight_decay=0.1))
    return optax.sgd(0.05, momentum=0.9)


def _bits(tree):
    """Every leaf as raw bytes: equality to the last bit, NaN or not."""
    return [np.asarray(leaf).tobytes() for leaf in jax.tree.leaves(tree)]


def _mixed_tree(dtype):
    """Leaves on both sides of ``SMALL_N``, an integer leaf over it, a
    scalar and a rank-1 scale over it."""
    rng = np.random.RandomState(3)
    return {
        "large": jnp.asarray(rng.randn(16, 8), dtype),       # 128: alone
        "stack": jnp.asarray(rng.randn(2, 8, 8), dtype),     # 128: alone
        "small": jnp.asarray(rng.randn(4, 4), dtype),        # 16: fused
        "scale": jnp.asarray(rng.randn(128), dtype),         # rank 1: fused
        "bias": jnp.asarray(rng.randn(), dtype),             # scalar: fused
        "count": jnp.asarray(rng.randint(0, 9, (16, 8)), jnp.int32),
    }


@pytest.mark.parametrize("kind", ["adamw", "sgd"])
def test_update_with_large_leaves_alone_equals_the_inner_update(
        monkeypatch, kind):
    """Under ``jit``: updates and state of ``DistributedOptimizer.update``
    equal the inner transformation's own to the last bit, two steps on."""
    monkeypatch.setattr(hvd, "ALONE_FROM_ELEMENTS", SMALL_N)
    inner = _inner(kind)
    dtype = jnp.bfloat16 if kind == "adamw" else jnp.float32
    params = _mixed_tree(dtype)
    if kind == "sgd":
        del params["count"]     # optax's momentum takes no integer leaf
    grads = jax.tree.map(lambda p: (p * 0.5 + 1).astype(p.dtype), params)
    opt = hvd.DistributedOptimizer(inner, reduce_gradients=False)

    def two_steps(update):
        def run(grads, state, params):
            for _ in range(2):
                updates, state = update(grads, state, params)
                params = optax.apply_updates(params, updates)
            return params, state
        return jax.jit(run)

    state = inner.init(params)
    got = two_steps(opt.update)(grads, state, params)
    assert hvd.update_counts() == {"alone": 2, "fused": len(params) - 2}
    want = two_steps(inner.update)(grads, state, params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert _bits(got) == _bits(want)


def _two_layer_loss(params, batch):
    x, y = batch
    hidden = jnp.tanh(x @ params["large"].astype(jnp.float32)
                      * params["scale"].astype(jnp.float32))
    out = hidden @ params["small"].astype(jnp.float32) + params["bias"]
    return jnp.mean((out - y) ** 2)


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("kind", ["adamw", "sgd"])
def test_train_step_with_large_leaves_alone_equals_the_step_without(
        monkeypatch, n_devices, kind, chips):
    """``make_train_step`` on one device and on a mesh of four: parameters,
    state and loss after three steps equal, to the last bit, those of the
    same step with no leaf engaged; on one device they also equal the inner
    transformation's own ``update`` on the gradient of the whole batch."""
    from jax.sharding import Mesh

    rng = np.random.RandomState(5)
    dtype = jnp.bfloat16 if kind == "adamw" else jnp.float32
    params = {"large": jnp.asarray(rng.randn(8, 16) * 0.3, dtype),
              "scale": jnp.asarray(1 + 0.1 * rng.randn(16), dtype),
              "small": jnp.asarray(rng.randn(16, 2) * 0.3, dtype),
              "bias": jnp.asarray(0.1, dtype)}
    batch = (jnp.asarray(rng.randn(8, 8), jnp.float32),
             jnp.asarray(rng.randn(8, 2), jnp.float32))
    mesh = Mesh(np.array(jax.devices()[:chips]), ("data",))
    inner = _inner(kind)

    def run(alone_from):
        monkeypatch.setattr(hvd, "ALONE_FROM_ELEMENTS", alone_from)
        step = hvd.make_train_step(
            _two_layer_loss, hvd.DistributedOptimizer(inner), mesh)
        # The step donates its state: give each run its own copy.
        p, s = jax.tree.map(jnp.copy, (params, inner.init(params)))
        for _ in range(3):
            p, s, loss = step(p, s, batch)
        return (p, s, loss), hvd.update_counts()

    got, counts = run(SMALL_N)
    want, none = run(float("inf"))
    assert counts == {"alone": 1, "fused": 3}
    assert none == {"alone": 0, "fused": 4}
    assert _bits(got) == _bits(want)
    if chips == 1:
        @jax.jit
        def plain(p, s):
            loss, grads = jax.value_and_grad(_two_layer_loss)(p, batch)
            updates, s = inner.update(grads, s, p)
            return optax.apply_updates(p, updates), s, loss

        p, s = params, inner.init(params)
        for _ in range(3):
            p, s, loss = plain(p, s)
        assert _bits(got) == _bits((p, s, loss))


@pytest.mark.parametrize("shape, dtype, alone", [
    ((8, 8), jnp.bfloat16, True),          # SMALL_N itself
    ((8, 8), jnp.float32, True),
    ((8, 8), jnp.float16, True),
    ((2, 4, 8), jnp.bfloat16, True),       # a stack of experts
    ((9, 7), jnp.bfloat16, False),         # 63
    ((64,), jnp.bfloat16, False),          # a norm's scale, an embedding row
    ((4096,), jnp.float32, False),
    ((), jnp.float32, False),
    ((8, 8), jnp.int32, False),
    ((16, 16), jnp.int8, False),
])
def test_the_rule_reads_the_leaf_alone(monkeypatch, shape, dtype, alone):
    """Floating, rank 2 or more, ``ALONE_FROM_ELEMENTS`` or more: one
    barrier in the jaxpr for such a leaf, none for any other."""
    monkeypatch.setattr(hvd, "ALONE_FROM_ELEMENTS", SMALL_N)
    opt = hvd.DistributedOptimizer(optax.identity(), reduce_gradients=False)
    grads = {"leaf": jnp.ones(shape, dtype), "other": jnp.ones((2, 2))}
    text = str(jax.make_jaxpr(
        lambda g: opt.update(g, optax.EmptyState(), None))(grads))
    assert text.count("optimization_barrier") == int(alone)
    assert hvd.update_counts() == {"alone": int(alone),
                                   "fused": 2 - int(alone)}


@pytest.mark.parametrize("kind", ["adamw", "sgd"])
def test_a_tree_with_no_large_leaf_traces_to_the_inner_updates_jaxpr(kind):
    """At the constant the chip is given, a tree of small leaves adds
    nothing: the jaxpr is the inner ``update``'s, equation for equation."""
    assert hvd.ALONE_FROM_ELEMENTS >= 1_000_000
    inner = _inner(kind)
    params = {k: v for k, v in _mixed_tree(jnp.float32).items()
              if k != "count"}
    state = inner.init(params)
    opt = hvd.DistributedOptimizer(inner, reduce_gradients=False)
    ours = str(jax.make_jaxpr(opt.update)(params, state, params))
    theirs = str(jax.make_jaxpr(inner.update)(params, state, params))
    assert ours == theirs and "optimization_barrier" not in ours
    assert hvd.update_counts() == {"alone": 0, "fused": len(params)}


def test_an_eager_update_is_not_counted_and_takes_no_barrier(monkeypatch):
    """Concrete gradients (the host-driven path): there is no compiler to
    hold back, and the count of the last TRACED update stays what it was."""
    monkeypatch.setattr(hvd, "ALONE_FROM_ELEMENTS", SMALL_N)
    opt = hvd.DistributedOptimizer(optax.sgd(0.5), reduce_gradients=False)
    grads = {"w": jnp.ones((8, 8))}
    jax.make_jaxpr(lambda g: opt.update(g, opt.init(grads), grads))(grads)
    assert hvd.update_counts() == {"alone": 1, "fused": 0}
    more = {"w": jnp.ones((8, 8)), "b": jnp.ones(8)}
    updates, _ = opt.update(more, opt.init(more), more)
    assert hvd.update_counts() == {"alone": 1, "fused": 0}
    np.testing.assert_array_equal(np.asarray(updates["w"]), -0.5)
