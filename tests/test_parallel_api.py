"""GSPMD parallel-training API tests on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.parallel.api import (
    infer_param_spec,
    lm_loss_fn,
    make_parallel_train_step,
    shard_params,
)


@pytest.fixture(scope="module")
def mesh(n_devices):
    return hvd.build_mesh({"data": 2, "fsdp": 2, "tensor": 2})


def test_infer_param_spec_tensor_rules(mesh):
    # Column-parallel projection: output dim on tensor.
    spec = infer_param_spec("layer_0/attn/wq/kernel", (64, 64), mesh)
    assert spec == P("fsdp", "tensor")
    # Row-parallel projection.
    spec = infer_param_spec("layer_0/attn/wo/kernel", (64, 64), mesh)
    assert spec == P("tensor", "fsdp")
    # Norm scales replicate.
    assert infer_param_spec("layer_0/norm_attn/scale", (64,), mesh) == P()


def test_infer_param_spec_drops_nondivisible(mesh):
    # dim 6 not divisible by tensor=2... 6 % 2 == 0 so use 7.
    spec = infer_param_spec("x/wq/kernel", (7, 64), mesh)
    assert spec == P(None, "tensor")


def test_parallel_train_step_runs_and_matches_single_device(mesh):
    cfg = LlamaConfig.tiny()
    model = LlamaModel(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 17),
                                          dtype=np.int32)
    )
    params = model.init(jax.random.key(0), tokens[:, :-1])

    opt = optax.sgd(1e-2)
    loss_fn = lm_loss_fn(model)

    # Single-device ground truth (one program, not an operation at a time).
    loss0, grads0 = jax.jit(jax.value_and_grad(loss_fn))(params, tokens)
    updates0, _ = opt.update(grads0, opt.init(params), params)
    params0 = optax.apply_updates(params, updates0)

    # Parallel step.
    sharded = shard_params(params, mesh)
    step = make_parallel_train_step(model, opt, mesh, donate=False)
    opt_state = jax.jit(opt.init)(sharded)
    params1, _, loss1 = step(sharded, opt_state, tokens)

    # bf16 compute: sharded reduction order shifts the loss at ~1e-3.
    assert np.allclose(np.asarray(loss1), np.asarray(loss0), atol=5e-3)
    flat0 = jax.tree.leaves(params0)
    flat1 = jax.tree.leaves(params1)
    for a, b in zip(flat0, flat1):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-3, rtol=5e-3)


def test_distributed_optimizer_pjit_mode(mesh):
    """DistributedOptimizer drops into the GSPMD path."""
    cfg = LlamaConfig.tiny()
    model = LlamaModel(cfg)
    tokens = jnp.zeros((8, 9), jnp.int32)
    params = shard_params(model.init(jax.random.key(0), tokens[:, :-1]), mesh)
    opt = hvd.DistributedOptimizer(optax.adam(1e-3))
    step = make_parallel_train_step(model, opt, mesh, donate=False)
    opt_state = jax.jit(opt.init)(params)
    params, opt_state, loss = step(params, opt_state, tokens)
    assert np.isfinite(np.asarray(loss))
