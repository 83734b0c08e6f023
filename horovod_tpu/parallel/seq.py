"""Context-parallel (sequence-parallel) LM training.

Builds a shard_map train step where the *sequence* dimension is sharded
over the ``seq`` mesh axis and attention runs as ring attention
(``horovod_tpu.parallel.ring_attention``), composing with data parallelism
on the batch axes.  This is the long-context training path: activation
memory per chip scales as S/seq_size, KV blocks ride nearest-neighbor ICI.

No reference equivalent (SURVEY.md §5.7: the reference predates sequence
parallelism); TPU-native new work.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

# This import is where ``import horovod_tpu.jax`` first asks for the model
# zoo (and with it flax and Pallas); the two readings of the clock around it
# become the compile log's span ``import horovod_tpu.models``
# (``horovod_tpu/jax/__init__.py``, its last lines).
_BEGAN = time.perf_counter()
from horovod_tpu.models.llama import LlamaConfig, LlamaModel  # noqa: E402
MODELS_IMPORTED = (_BEGAN, time.perf_counter())
from horovod_tpu.ops.losses import softmax_cross_entropy  # noqa: E402
from horovod_tpu.parallel.ring_attention import make_ring_attention_fn  # noqa: E402,E501

__all__ = ["make_context_parallel_train_step"]


def make_context_parallel_train_step(cfg: LlamaConfig, optimizer,
                                     mesh: Mesh, *,
                                     seq_axis: str = "seq",
                                     attention: str = "auto",
                                     donate: bool = True):
    """Jitted LM train step with sequence sharded over ``seq_axis`` and
    batch sharded over the data-like axes.

    ``step(params, opt_state, inputs, targets) ->
    (params, opt_state, loss)`` where inputs/targets are [B, S] token ids
    (S divisible by the seq-axis size, B by the data axes' product).
    ``attention``: "ulysses" (all-to-all head scatter; the local
    full-sequence attention runs the Pallas flash kernel — shard_map
    bodies are Manual-mesh, so it lowers legally), "ring" (blockwise
    ppermute ring; scales sequence past what one chip's heads allow), or
    "auto" (default): ulysses whenever the head counts divide the
    ``seq_axis`` size — the flash-backed path — ring otherwise.
    """
    import optax

    from horovod_tpu.jax import DistributedOptimizer
    from horovod_tpu.parallel.mesh import data_axes
    from horovod_tpu.parallel.ring_attention import ulysses_attention

    if attention == "auto":
        seq_size = mesh.shape[seq_axis]
        heads_divide = (cfg.num_heads % seq_size == 0
                        and cfg.num_kv_heads % seq_size == 0)
        attention = "ulysses" if heads_divide else "ring"
    if attention == "ring":
        attention_fn = make_ring_attention_fn(seq_axis)
    elif attention == "ulysses":
        def attention_fn(q, k, v, *a, **kw):
            return ulysses_attention(q, k, v, axis_name=seq_axis)
    else:
        raise ValueError(f"unknown attention {attention!r}")

    model = LlamaModel(cfg, attention_fn=attention_fn)
    batch_axes = data_axes(mesh) or ()
    reduce_axes = tuple(batch_axes) + (seq_axis,)

    from horovod_tpu.ops.collective_ops import Sum

    # Per-shard gradients are partial SUMS of the global token mean (each
    # shard holds different tokens), so the cross-shard reduction must be
    # SUM, not average.
    inner = optimizer.inner if isinstance(optimizer, DistributedOptimizer) \
        else optimizer
    optimizer = DistributedOptimizer(inner, axis_name=reduce_axes, op=Sum)

    def _local_loss(params, inputs, targets):
        offset = lax.axis_index(seq_axis) * inputs.shape[1]
        logits = model.apply(params, inputs, positions_offset=offset)
        # Local *sum* in lse form (no fp32 log-prob tensor); the mean
        # denominator is the global token count so the psum over
        # data+seq axes reconstructs the global mean.
        return softmax_cross_entropy(logits, targets, reduction="sum")

    def _step(params, opt_state, inputs, targets):
        n_global = (inputs.shape[0] * lax.axis_size(batch_axes)
                    if batch_axes else inputs.shape[0])
        s_global = inputs.shape[1] * lax.axis_size(seq_axis)
        denom = n_global * s_global
        loss_sum, grads = jax.value_and_grad(_local_loss)(
            params, inputs, targets)
        grads = jax.tree.map(lambda g: g / denom, grads)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        loss = lax.psum(loss_sum, reduce_axes) / denom
        return params, opt_state, loss

    batch_spec = P(tuple(batch_axes) if batch_axes else None, seq_axis)
    step = jax.shard_map(
        _step,
        mesh=mesh,
        in_specs=(P(), P(), batch_spec, batch_spec),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    donate_args = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_args)
