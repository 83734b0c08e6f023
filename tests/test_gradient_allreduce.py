"""The jit path's gradient reduction on the 8-device mesh: each leaf of the
tree is all-reduced as it is (``hvd.allreduce_gradients``, the traced
``hvd.broadcast_parameters``, ``hvd.make_train_step``).  Nothing is packed
and no environment name is read; XLA's combiner does the batching
(``tests/test_gradient_allreduce_v5e_compile.py`` counts what it leaves)."""

import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu import ops

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


def _mixed_tree(n_devices, integers=True):
    """A shard a device (leading axis): matrices and vectors of two float
    dtypes, a scalar, an empty sub-tree and, with ``integers``, a step
    count.  Small integers in bf16, so every reduction of them is exact."""
    rng = np.random.RandomState(7)

    def floats(shape, dtype):
        return jnp.asarray(rng.randint(-3, 4, (n_devices, *shape)), dtype)

    tree = {"blocks": [{"w": floats((64, 32), BF16),
                        "scale": floats((32,), BF16)},
                       {"w": floats((16, 8, 4), F32),
                        "bias": floats((8,), F32)}],
            "temperature": floats((), F32),
            "unused": {}}
    if integers:
        tree["steps"] = jnp.asarray(rng.randint(1, 4, (n_devices,)), I32)
    return tree


def _per_shard(mesh, fn, tree):
    """``fn`` on each device's shard (leading axis dropped), every device's
    result stacked again."""

    def body(t):
        out = fn(jax.tree.map(lambda x: x[0], t))
        return jax.tree.map(lambda x: x[None], out)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"), check_vma=False))(tree)


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape, (g.dtype, w.dtype)
        np.testing.assert_array_equal(np.asarray(g, np.float64),
                                      np.asarray(w, np.float64))


def test_allreduce_gradients_defaults_to_every_data_like_axis(n_devices):
    """No ``axis_name``: the default mesh's ``data`` and ``fsdp`` axes, both.
    The tree comes back as it went, each leaf the mean over all devices."""
    mesh = hvd.build_mesh({"data": 2, "fsdp": n_devices // 2})
    tree = _mixed_tree(n_devices, integers=False)
    with hvd.use_mesh(mesh):
        out = jax.jit(jax.shard_map(
            lambda t: jax.tree.map(
                lambda x: x[None],
                hvd.allreduce_gradients(jax.tree.map(lambda x: x[0], t))),
            mesh=mesh, in_specs=P(("data", "fsdp")),
            out_specs=P(("data", "fsdp")), check_vma=False))(tree)
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    for o, t in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert o.shape == t.shape and o.dtype == t.dtype
        np.testing.assert_allclose(
            np.asarray(o[-1], np.float64),
            np.asarray(t, np.float64).mean(axis=0),
            rtol=1e-2 if t.dtype == BF16 else 1e-6, atol=1e-6)


def test_each_leaf_is_the_pmean_of_itself_to_the_bit(n_devices):
    """An all-reduce is elementwise over devices, so a leaf reduced within
    a tree has the bits of the leaf reduced alone."""
    rng = np.random.RandomState(3)
    tree = jax.tree.map(
        lambda x: jnp.asarray(rng.randn(*x.shape), x.dtype),
        _mixed_tree(n_devices, integers=False))
    mesh = hvd.data_parallel_mesh()
    got = _per_shard(mesh, lambda t: hvd.allreduce_gradients(
        t, axis_name="data"), tree)
    want = _per_shard(mesh, lambda t: jax.tree.map(
        lambda x: jax.lax.pmean(x, "data"), t), tree)
    _assert_trees_equal(got, want)
    for g, t in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        loose = t.dtype == BF16
        np.testing.assert_allclose(
            np.asarray(g[0], np.float32),
            np.asarray(t, np.float32).mean(axis=0),
            rtol=2e-2 if loose else 1e-5, atol=2e-2 if loose else 1e-6)


def _step_and_data(n_devices):
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(n_devices * 4, 64).astype(np.float32))
    y = jnp.asarray(rng.randn(n_devices * 4, 16).astype(np.float32))

    def loss_fn(params, batch):
        x, y = batch
        hidden = jnp.tanh(x @ params["w1"] + params["b1"]) * params["scale"]
        return jnp.mean((hidden @ params["w2"] + params["b2"] - y) ** 2)

    r = np.random.RandomState(9)
    params = {"w1": jnp.asarray(r.randn(64, 64).astype(np.float32) * 0.1),
              "b1": jnp.zeros((64,)), "scale": jnp.ones((64,)),
              "w2": jnp.asarray(r.randn(64, 16).astype(np.float32) * 0.1),
              "b2": jnp.zeros((16,))}
    return loss_fn, params, (x, y)


def _lowered_reduces(text):
    return re.findall(r"stablehlo\.all_reduce", text)


def test_allreduce_gradients_lowers_to_an_all_reduce_a_leaf(n_devices):
    """The program hands XLA one all-reduce a leaf, which is what its
    combiner batches."""
    tree = _mixed_tree(n_devices)
    text = jax.jit(jax.shard_map(
        lambda t: hvd.allreduce_gradients(
            jax.tree.map(lambda x: x[0], t), axis_name="data", op=hvd.Sum),
        mesh=hvd.data_parallel_mesh(), in_specs=P("data"), out_specs=P(),
        check_vma=False)).lower(tree).as_text()
    assert len(_lowered_reduces(text)) == len(jax.tree.leaves(tree))
    assert "concatenate" not in text


def test_lowered_step_concatenates_no_gradient(n_devices):
    """No leaf of any size is copied into a buffer on its way to the
    optimizer."""
    loss_fn, params, batch = _step_and_data(n_devices)
    opt = optax.adam(1e-2)
    step = hvd.make_train_step(loss_fn, opt, hvd.data_parallel_mesh(),
                               donate=False)
    text = step.lower(params, opt.init(params), batch).as_text()
    assert "concatenate" not in text
    # A psum a gradient leaf, and the loss's.
    assert len(_lowered_reduces(text)) == len(jax.tree.leaves(params)) + 1


def test_sharded_step_agrees_with_the_one_device_full_batch_step(n_devices):
    """The reference is the same step on one device with the whole batch."""
    loss_fn, params, batch = _step_and_data(n_devices)
    after = {}
    for name, devices in (("mesh", jax.devices()), ("one", jax.devices()[:1])):
        opt = optax.adam(1e-2)
        step = hvd.make_train_step(
            loss_fn, opt, hvd.data_parallel_mesh(devices), donate=False)
        p, state, losses = params, opt.init(params), []
        for _ in range(3):
            p, state, loss = step(p, state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        after[name] = (p, losses)
    for key in params:
        np.testing.assert_allclose(np.asarray(after["mesh"][0][key]),
                                   np.asarray(after["one"][0][key]),
                                   rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(after["mesh"][1], after["one"][1], rtol=1e-5)


@pytest.mark.parametrize("op, reference", [
    (hvd.Average, lambda x: x.mean(axis=0)),
    (hvd.Sum, lambda x: x.sum(axis=0)),
    (hvd.Min, lambda x: x.min(axis=0)),
    (hvd.Max, lambda x: x.max(axis=0)),
    (hvd.Product, lambda x: x.prod(axis=0)),
], ids=lambda p: getattr(p, "name", ""))
def test_allreduce_gradients_on_a_mixed_tree(n_devices, op, reference):
    """Two float dtypes, ranks 0 to 3, an integer count, an empty sub-tree:
    every leaf is reduced in its own dtype, on every device."""
    tree = _mixed_tree(n_devices, integers=op is not hvd.Average)
    if op is hvd.Product:           # factors of 1 and -1: exact in bf16
        tree = jax.tree.map(lambda x: jnp.where(x < 0, -1, 1).astype(x.dtype),
                            tree)
    out = _per_shard(
        hvd.data_parallel_mesh(),
        lambda t: hvd.allreduce_gradients(t, axis_name="data", op=op), tree)
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    assert out["unused"] == {}
    for o, t in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert o.dtype == t.dtype and o.shape == t.shape
        want = reference(np.asarray(t, np.float64))
        for device in range(n_devices):
            np.testing.assert_allclose(np.asarray(o[device], np.float64),
                                       want, rtol=1e-2 if t.dtype == BF16
                                       and op is hvd.Average else 1e-6)
    if op is not hvd.Average:
        assert out["steps"].dtype == I32


def test_allreduce_gradients_of_an_empty_tree():
    """No leaf: no collective, and the tree back (traced or not is moot)."""
    for empty in ({}, [], {"a": {}, "b": ()}):
        assert hvd.allreduce_gradients(empty, axis_name="data") == empty


@pytest.mark.parametrize("compression, wire", [
    (hvd.Compression.fp16, "f16"), (hvd.Compression.bf16, "bf16")],
    ids=["fp16", "bf16"])
def test_compressed_leaves_come_back_in_their_own_dtype(n_devices,
                                                        compression, wire):
    tree = _mixed_tree(n_devices)
    mesh = hvd.data_parallel_mesh()

    def reduce(t):
        return hvd.allreduce_gradients(t, axis_name="data", op=hvd.Sum,
                                       compression=compression)

    out = _per_shard(mesh, reduce, tree)
    for o, t in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert o.dtype == t.dtype and o.shape == t.shape
        np.testing.assert_array_equal(      # small integers: exact on the wire
            np.asarray(o[0], np.float64),
            np.asarray(t, np.float64).sum(axis=0))
    # Every float leaf travels in the wire dtype, the integer one as it is.
    text = jax.jit(jax.shard_map(
        lambda t: reduce(jax.tree.map(lambda x: x[0], t)), mesh=mesh,
        in_specs=P("data"), out_specs=P(), check_vma=False)).lower(
            tree).as_text()
    reduced = re.findall(r'"stablehlo.all_reduce".*?\}\) : '
                         r'\(tensor<(?:[0-9x]*x)?(\w+)>\)', text, re.DOTALL)
    floats = sum(jnp.issubdtype(t.dtype, jnp.floating)
                 for t in jax.tree.leaves(tree))
    assert sorted(reduced) == sorted([wire] * floats + ["i32"]), reduced


@pytest.mark.parametrize("root", [0, -1], ids=["first", "last"])
def test_traced_broadcast_parameters_from_either_end(n_devices, root):
    root = root % n_devices
    tree = _mixed_tree(n_devices)
    out = _per_shard(
        hvd.data_parallel_mesh(),
        lambda t: hvd.broadcast_parameters(t, root_rank=root,
                                           axis_name="data"), tree)
    want = jax.tree.map(
        lambda x: jnp.broadcast_to(x[root], x.shape), tree)
    _assert_trees_equal(out, want)


def test_traced_broadcast_of_an_optimizer_state(n_devices):
    """Adam's state: an integer count beside float moments."""
    params = {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,), BF16)}
    opt = optax.adam(1e-3)

    def per_device(i):
        state = opt.init(params)
        return jax.tree.map(lambda x: x + i.astype(x.dtype), state)

    states = jax.vmap(per_device)(jnp.arange(n_devices))
    out = _per_shard(
        hvd.data_parallel_mesh(),
        lambda s: hvd.broadcast_optimizer_state(s, root_rank=3,
                                                axis_name="data"), states)
    want = jax.tree.map(lambda x: jnp.broadcast_to(x[3], x.shape), states)
    _assert_trees_equal(out, want)
    assert any(leaf.dtype == I32 for leaf in jax.tree.leaves(out))


def test_the_jit_path_reads_no_environment(n_devices, monkeypatch):
    """``HOROVOD_FUSION_THRESHOLD`` is the host engine's knob: a value the
    jit path could not parse never reaches it."""
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "the engine's business")
    tree = _mixed_tree(n_devices)
    mesh = hvd.data_parallel_mesh()
    out = _per_shard(mesh, lambda t: hvd.allreduce_gradients(
        t, axis_name="data", op=hvd.Sum), tree)
    grouped = _per_shard(mesh, lambda t: hvd.grouped_allreduce(
        jax.tree.leaves(t), axis_name="data", op=hvd.Sum), tree)
    _assert_trees_equal(jax.tree.leaves(out), grouped)


def test_no_switch_selects_a_packer():
    for fn in (hvd.allreduce_gradients, hvd.DistributedOptimizer.__init__,
               hvd.broadcast_parameters, hvd.grouped_allreduce):
        assert not [name for name in inspect.signature(fn).parameters
                    if "fusion" in name or "threshold" in name], fn
    assert not [name for name in ops.__all__ if "usion" in name]
