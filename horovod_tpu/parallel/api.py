"""High-level parallel-training API: parameter sharding + pjit train steps.

This is the GSPMD path of the framework: parameters and batch get
``NamedSharding`` annotations, everything runs under one ``jax.jit``, and XLA
inserts the ICI collectives (gradient reductions, weight all-gathers for
fsdp, activation collectives for tensor parallelism).  The explicit-collective
path (``shard_map`` + ``lax.psum`` through ``DistributedOptimizer``) lives in
``horovod_tpu.jax.make_train_step``; both are first-class.

Reference parity note: the reference has *only* data parallelism
(SURVEY.md §2.3) — its DistributedOptimizer allreduces gradients.  Here the
same user-visible contract ("wrap your optimizer, gradients arrive reduced")
extends across data/fsdp/tensor/expert axes because reduction placement is
derived from the shardings rather than hard-coded.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.ops.losses import softmax_cross_entropy

__all__ = [
    "SHARDING_RULES",
    "infer_param_spec",
    "shard_params",
    "make_parallel_train_step",
    "lm_loss_fn",
]

# Path-regex → axis names per dimension (None = replicate that dim).
# Megatron-style placement: attention/MLP input projections are
# column-parallel (output dim on ``tensor``), output projections are
# row-parallel (input dim on ``tensor``); everything big also shards one dim
# over ``fsdp``; MoE expert-batched weights shard the expert dim.
SHARDING_RULES: tuple[tuple[str, tuple[Optional[str], ...]], ...] = (
    (r"tok_emb.*embedding$", ("tensor", "fsdp")),
    (r"(pos_emb|type_emb).*embedding$", (None, "fsdp")),
    (r"(wq|wk|wv|qkv|mlp_in|w_gate_up|w_up|in_proj|mlm_transform)/kernel$", ("fsdp", "tensor")),
    (r"(wo|proj|w_down|mlp_out)/kernel$", ("tensor", "fsdp")),
    (r"(lm_head|mlm_out)/kernel$", ("fsdp", "tensor")),
    (r"moe/w_gate_up$", ("expert", "fsdp", "tensor")),
    (r"moe/w_up$", ("expert", "fsdp", "tensor")),
    (r"moe/w_down$", ("expert", "tensor", "fsdp")),
    (r"router/kernel$", ("fsdp", None)),
    (r"head/kernel$", ("fsdp", "tensor")),   # resnet classifier
    (r"kernel$", (None, None, None, "tensor")),  # convs: shard out-channels
)


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def infer_param_spec(path: str, shape: tuple[int, ...], mesh: Mesh,
                     rules=SHARDING_RULES) -> P:
    """PartitionSpec for one parameter.

    Axes not present in the mesh, mesh axes of size 1, and axes that do not
    divide the corresponding dimension are dropped (replicated) — so the same
    rules work on any mesh shape, including single-axis data-parallel meshes.
    """
    for pattern, dims in rules:
        if re.search(pattern, path):
            if len(dims) != len(shape):
                continue
            spec = []
            for dim_size, axis in zip(shape, dims):
                if (axis is None or axis not in mesh.axis_names
                        or mesh.shape[axis] == 1
                        or dim_size % mesh.shape[axis] != 0):
                    spec.append(None)
                else:
                    spec.append(axis)
            return P(*spec)
    return P()  # replicate by default (norms, biases, small tables)


def shard_params(params, mesh: Mesh, rules=SHARDING_RULES):
    """Device-put every parameter with its inferred NamedSharding."""

    def _place(path, leaf):
        spec = infer_param_spec(_path_str(path), jnp.shape(leaf), mesh, rules)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(_place, params)


def param_shardings(params, mesh: Mesh, rules=SHARDING_RULES):
    """The NamedSharding pytree matching ``shard_params`` placement."""

    def _spec(path, leaf):
        return NamedSharding(
            mesh, infer_param_spec(_path_str(path), jnp.shape(leaf), mesh, rules)
        )

    return jax.tree_util.tree_map_with_path(_spec, params)


def lm_loss_fn(model) -> Callable:
    """Next-token cross-entropy on ``tokens`` [B, S+1]."""

    def loss_fn(params, tokens):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logits = model.apply(params, inputs)
        # lse-form CE (ops/losses.py): no [B,S,V] fp32 log-prob tensor.
        return softmax_cross_entropy(logits, targets)

    return loss_fn


def _batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes the batch dimension is sharded over.  ``fsdp`` is a batch
    axis (ZeRO data parallelism shards state, not the batch semantics)."""
    from horovod_tpu.parallel.mesh import data_axes

    return data_axes(mesh)


def make_parallel_train_step(model, optimizer, mesh: Mesh, *,
                             loss_fn: Optional[Callable] = None,
                             rules=SHARDING_RULES,
                             donate: bool = True):
    """Build a jitted GSPMD train step over ``mesh``.

    ``step(params, opt_state, tokens) -> (params, opt_state, loss)`` with
    params sharded per ``rules``, batch sharded over the data-like axes, and
    XLA inserting all collectives (this is the pjit path; DistributedOptimizer
    instances are switched to ``reduce_gradients=False`` because GSPMD already
    reduces gradients — the psum the reference does by hand,
    tensorflow/__init__.py:183-209).
    """
    loss_fn = loss_fn or lm_loss_fn(model)

    from horovod_tpu.jax import DistributedOptimizer

    if isinstance(optimizer, DistributedOptimizer):
        inner = optimizer.inner
    else:
        inner = optimizer

    import optax

    def step(params, opt_state, tokens):
        tokens = jax.lax.with_sharding_constraint(
            tokens, NamedSharding(mesh, P(_batch_axes(mesh) or None))
        )
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = inner.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    donate_args = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_args)
