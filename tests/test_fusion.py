"""Fusion planner tests (reference docs/tensor-fusion.md semantics)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import fusion


def test_plan_groups_by_dtype():
    leaves = [
        jnp.ones((4,), jnp.float32),
        jnp.ones((2,), jnp.bfloat16),
        jnp.ones((3,), jnp.float32),
    ]
    plan = fusion.plan_fusion(leaves, threshold_bytes=1 << 20)
    assert len(plan.buckets) == 2
    dtypes = {b.dtype for b in plan.buckets}
    assert jnp.dtype(jnp.float32) in dtypes
    assert jnp.dtype(jnp.bfloat16) in dtypes
    f32 = next(b for b in plan.buckets if b.dtype == jnp.dtype(jnp.float32))
    assert f32.indices == (0, 2)


def test_plan_respects_threshold():
    # 3 tensors of 1024 f32 = 4 KiB each; threshold 8 KiB -> 2 buckets.
    leaves = [jnp.ones((1024,), jnp.float32) for _ in range(3)]
    plan = fusion.plan_fusion(leaves, threshold_bytes=8 * 1024)
    assert len(plan.buckets) == 2
    assert plan.buckets[0].indices == (0, 1)
    assert plan.buckets[1].indices == (2,)


def test_threshold_zero_disables_fusion():
    leaves = [jnp.ones((8,), jnp.float32) for _ in range(3)]
    plan = fusion.plan_fusion(leaves, threshold_bytes=0)
    assert len(plan.buckets) == 3


def test_fuse_apply_roundtrip():
    rng = np.random.RandomState(0)
    tree = {
        "a": jnp.asarray(rng.randn(3, 4).astype(np.float32)),
        "b": [
            jnp.asarray(rng.randn(7).astype(np.float32)),
            jnp.asarray(rng.randn(2, 2, 2).astype(np.float32)),
        ],
        "c": jnp.asarray(rng.randn(5).astype(np.float64)),
    }
    out = fusion.fuse_apply(tree, lambda buf: buf * 2.0)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(np.asarray(y), np.asarray(x) * 2),
        tree,
        out,
    )
    # Shapes and dtypes preserved exactly.
    jax.tree.map(
        lambda x, y: (x.shape == y.shape, x.dtype == y.dtype), tree, out
    )


def test_fuse_apply_under_jit_single_collective(n_devices):
    """The whole point: one psum per dtype bucket, not one per leaf."""
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvd

    mesh = hvd.data_parallel_mesh()
    tree = [jnp.ones((n_devices, 8), jnp.float32) for _ in range(10)]

    def fn(*shards):
        return tuple(
            fusion.fuse_apply(
                [s.reshape(s.shape[1:]) for s in shards],
                lambda buf: jax.lax.psum(buf, "data"),
            )
        )

    lowered = jax.jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=tuple(P("data") for _ in tree),
            out_specs=tuple(P() for _ in tree),
            check_vma=False,
        )
    ).lower(*tree)
    hlo = lowered.as_text()
    assert hlo.count("all-reduce") <= 2, hlo.count("all-reduce")
    outs = jax.jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=tuple(P("data") for _ in tree),
            out_specs=tuple(P() for _ in tree),
            check_vma=False,
        )
    )(*tree)
    for o in outs:
        np.testing.assert_allclose(np.asarray(o), n_devices)


def test_env_threshold(monkeypatch):
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "12345")
    assert fusion.fusion_threshold_bytes() == 12345
    monkeypatch.delenv("HOROVOD_FUSION_THRESHOLD")
    assert fusion.fusion_threshold_bytes() == fusion.DEFAULT_FUSION_THRESHOLD


def test_fusion_report(monkeypatch, capsys):
    """HOROVOD_FUSION_REPORT=1 prints each distinct plan once (the jit-path
    analogue of the timeline's fused-response visibility)."""
    import jax.numpy as jnp

    from horovod_tpu.ops import fusion

    monkeypatch.setenv("HOROVOD_FUSION_REPORT", "1")
    fusion._reported_plans.clear()
    tree = {"a": jnp.ones(10), "b": jnp.ones(20), "c": jnp.ones(5, jnp.int32)}
    fusion.fuse_apply(tree, lambda x: x)
    fusion.fuse_apply(tree, lambda x: x)  # same plan: reported once
    err = capsys.readouterr().err
    assert err.count("fused collective(s)") == 1
    assert "2 x float32" in err and "1 x int32" in err


# -- the size-aware plan: leaves at or above the cut-off are never packed ----

def _mixed_leaves():
    """Small and large, two dtypes, interleaved: (shape, dtype, large)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    spec = [((8,), f32), ((64, 64), f32), ((16,), bf16), ((4, 4), f32),
            ((128, 64), bf16), ((32,), bf16), ((256,), f32), ((1024,), f32),
            ((8, 8), bf16), ((2048,), bf16)]
    rng = np.random.RandomState(7)
    return [jnp.asarray(rng.randn(*shape), dtype) for shape, dtype in spec]


#: Cut-off for the tests on small arrays: 4 KiB, so f32[1024] and bf16[2048]
#: are the smallest leaves left in place.
LOW_CUTOFF = 4096


@pytest.mark.parametrize("threshold", [1 << 20, 1024, 0])
def test_plan_leaves_large_leaves_alone(monkeypatch, threshold):
    monkeypatch.setattr(fusion, "IN_PLACE_CUTOFF_BYTES", LOW_CUTOFF)
    leaves = _mixed_leaves()
    plan = fusion.plan_fusion(leaves, threshold_bytes=threshold)
    nbytes = [leaf.size * leaf.dtype.itemsize for leaf in leaves]

    assert sorted(i for b in plan.buckets for i in b.indices) == list(
        range(len(leaves)))
    for b in plan.buckets:
        assert list(b.indices) == sorted(b.indices)
        assert {leaves[i].dtype for i in b.indices} == {b.dtype}
        if b.packed:
            assert all(nbytes[i] < LOW_CUTOFF for i in b.indices)
            assert b.nbytes <= threshold
    large = [i for i, n in enumerate(nbytes) if n >= LOW_CUTOFF]
    assert large == [1, 4, 7, 9]
    alone = {b.indices[0] for b in plan.buckets if not b.packed}
    if threshold == 0:
        assert alone == set(range(len(leaves)))
        assert plan.packed == (0, 0)
    else:
        assert set(large) <= alone
    if threshold == 1 << 20:
        # Small leaves keep packing around the large ones, in order.
        assert [b.indices for b in plan.buckets if b.packed] == [
            (0, 3, 6), (2, 5, 8)]
        assert plan.packed == (6, (8 + 16 + 256) * 4 + (16 + 32 + 64) * 2)
        assert plan.in_place == (4, sum(nbytes[i] for i in large))
    assert plan.packed[0] + plan.in_place[0] == plan.n_leaves
    assert plan.packed[1] + plan.in_place[1] == sum(nbytes)


def test_default_cutoff_is_one_mebibyte():
    leaves = [jnp.zeros((512, 512), jnp.float32),       # 1 MiB: in place
              jnp.zeros((512, 511), jnp.float32),       # just under: packed
              jnp.zeros((64,), jnp.float32)]
    plan = fusion.plan_fusion(leaves)
    assert [b.indices for b in plan.buckets] == [(0,), (1, 2)]


def test_fuse_apply_copies_no_large_leaf(monkeypatch):
    """Equal to ``jax.tree.map(fn, tree)`` to the bit, and the program holds
    no concatenate with a large leaf among its operands."""
    monkeypatch.setattr(fusion, "IN_PLACE_CUTOFF_BYTES", LOW_CUTOFF)
    leaves = _mixed_leaves()
    tree = {"blocks": leaves[:5], "rest": tuple(leaves[5:])}

    def fn(buf):
        return buf * 3 + 1

    fused = jax.jit(lambda t: fusion.fuse_apply(t, fn))
    want = jax.jit(lambda t: jax.tree.map(fn, t))(tree)
    got = fused(tree)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    text = fused.lower(tree).as_text()
    concatenates = [line for line in text.splitlines()
                    if "concatenate" in line]
    assert len(concatenates) == 2           # one a dtype, of small leaves
    for line in concatenates:
        operand_types = line.split(":", 1)[1].split("->")[0]
        sizes = [int(n) for n in re.findall(r"tensor<(\d+)x", operand_types)]
        assert sizes and max(sizes) <= 256, line


def _sharded_allreduce(mesh, tree):
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvd

    def body(t):
        reduced = hvd.allreduce_gradients(
            jax.tree.map(lambda x: x[0], t), axis_name="data")
        return jax.tree.map(lambda x: x[None], reduced)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"), check_vma=False))(tree)


def test_allreduce_gradients_same_bits_at_any_cutoff(monkeypatch, n_devices):
    import horovod_tpu.jax as hvd

    mesh = hvd.data_parallel_mesh()
    rng = np.random.RandomState(3)
    tree = {f"leaf{i}": jnp.asarray(
        rng.randn(n_devices, *leaf.shape), leaf.dtype)
        for i, leaf in enumerate(_mixed_leaves())}
    found = {}
    for name, cutoff in (("low", LOW_CUTOFF), ("high", 1 << 40)):
        monkeypatch.setattr(fusion, "IN_PLACE_CUTOFF_BYTES", cutoff)
        found[name] = _sharded_allreduce(mesh, tree)
    for key, leaf in tree.items():
        low, high = (np.asarray(found[name][key], np.float32)
                     for name in ("low", "high"))
        np.testing.assert_array_equal(low, high)
        # Every shard holds the mean over shards.
        mean = np.asarray(leaf, np.float32).mean(axis=0)
        np.testing.assert_allclose(
            low[0], mean, rtol=2e-2 if leaf.dtype == jnp.bfloat16 else 1e-5,
            atol=1e-2 if leaf.dtype == jnp.bfloat16 else 1e-6)


def test_train_step_agrees_at_any_cutoff(monkeypatch, n_devices):
    import optax

    import horovod_tpu.jax as hvd

    mesh = hvd.data_parallel_mesh()
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(n_devices * 4, 64).astype(np.float32))
    y = jnp.asarray(rng.randn(n_devices * 4, 16).astype(np.float32))

    def loss_fn(params, batch):
        x, y = batch
        hidden = jnp.tanh(x @ params["w1"] + params["b1"]) * params["scale"]
        return jnp.mean((hidden @ params["w2"] + params["b2"] - y) ** 2)

    def params():           # w1 (16 KiB) and w2 (4 KiB) reach LOW_CUTOFF
        r = np.random.RandomState(9)
        return {"w1": jnp.asarray(r.randn(64, 64).astype(np.float32) * 0.1),
                "b1": jnp.zeros((64,)), "scale": jnp.ones((64,)),
                "w2": jnp.asarray(r.randn(64, 16).astype(np.float32) * 0.1),
                "b2": jnp.zeros((16,))}

    after = {}
    for name, cutoff in (("low", LOW_CUTOFF), ("high", 1 << 40)):
        monkeypatch.setattr(fusion, "IN_PLACE_CUTOFF_BYTES", cutoff)
        opt = optax.adam(1e-2)
        step = hvd.make_train_step(loss_fn, opt, mesh, donate=False)
        p = params()
        state = opt.init(p)
        losses = []
        for _ in range(3):
            p, state, loss = step(p, state, (x, y))
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        after[name] = (p, losses)
    for key in after["low"][0]:
        np.testing.assert_allclose(np.asarray(after["low"][0][key]),
                                   np.asarray(after["high"][0][key]),
                                   rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(after["low"][1], after["high"][1], rtol=1e-6)


def test_fusion_report_counts_packed_and_in_place(monkeypatch, capsys):
    monkeypatch.setenv("HOROVOD_FUSION_REPORT", "1")
    monkeypatch.setattr(fusion, "IN_PLACE_CUTOFF_BYTES", LOW_CUTOFF)
    fusion._reported_plans.clear()
    fusion.fuse_apply(_mixed_leaves(), lambda x: x)
    err = capsys.readouterr().err
    header = err.splitlines()[0]
    assert "10 tensors -> 2 fused collective(s)" in header
    assert "6 packed leaves (392 elements" in header
    assert "4 leaves in place (0.04 MiB)" in header
    assert err.count(", in place") == 4
    assert "3 x float32, 280 elements" in err
    assert "1 x bfloat16, 8192 elements (0.02 MiB), in place" in err
