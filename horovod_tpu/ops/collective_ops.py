"""SPMD collective primitives over named mesh axes.

Reference parity: the three collectives of the reference core —
``EnqueueTensorAllreduce/Allgather/Broadcast`` (``horovod/common/
operations.h:100-118``) executed as ``MPI_Allreduce`` / ``MPI_Allgatherv`` /
``MPI_Bcast`` or their NCCL twins (``operations.cc:714-1362``).

TPU-native design: inside ``jit``-compiled SPMD programs there is no enqueue,
no negotiation and no fusion buffer — the program *is* identical on every
device by construction, so collectives are single XLA ops over a named mesh
axis, lowered directly to ICI rings (``psum``/``all_gather``/``ppermute``).
These functions are the building blocks; the eager, named-tensor negotiation
engine (for the torch frontend and host-driven code) lives in
``horovod_tpu.runtime`` and ultimately executes *these same ops*.

The ``broadcast`` trick: XLA has no bcast collective; ``psum`` of a tensor
masked to zero on all non-root shards is mathematically a broadcast and
lowers to the same ring reduction, which is optimal on ICI.
"""

from __future__ import annotations

import enum
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.ops.compression import Compression

__all__ = [
    "ReduceOp",
    "Sum",
    "Average",
    "Min",
    "Max",
    "Product",
    "allreduce",
    "grouped_allreduce",
    "allgather",
    "broadcast",
    "reducescatter",
    "alltoall",
    "axis_rank",
    "axis_size",
]


class ReduceOp(enum.Enum):
    """Reduction ops.  The reference wire protocol supports allreduce-sum
    only, with averaging applied by the framework layer
    (``horovod/torch/mpi_ops_v2.cc:66-72``); later Horovods named these.
    """

    SUM = "sum"
    AVERAGE = "average"
    MIN = "min"
    MAX = "max"
    PRODUCT = "product"


Sum = ReduceOp.SUM
Average = ReduceOp.AVERAGE
Min = ReduceOp.MIN
Max = ReduceOp.MAX
Product = ReduceOp.PRODUCT


def axis_rank(axis_name) -> jax.Array:
    """This shard's index along ``axis_name`` (in-jit)."""
    return lax.axis_index(axis_name)


def axis_size(axis_name) -> int:
    return lax.axis_size(axis_name)


def _reduce(tensor: jax.Array, axis_name, op: ReduceOp) -> jax.Array:
    with _scopes.scope(_scopes.allreduce_scope(axis_name)):
        if op is ReduceOp.SUM:
            return lax.psum(tensor, axis_name)
        if op is ReduceOp.AVERAGE:
            return lax.pmean(tensor, axis_name)
        if op is ReduceOp.MIN:
            return lax.pmin(tensor, axis_name)
        if op is ReduceOp.MAX:
            return lax.pmax(tensor, axis_name)
        if op is ReduceOp.PRODUCT:
            # XLA has no product collective; gather-then-multiply is exact
            # for every dtype (a log/exp trick would lose integer exactness).
            gathered = lax.all_gather(tensor, axis_name, axis=0, tiled=False)
            return jnp.prod(gathered, axis=0)
    raise ValueError(f"unknown op {op}")


def allreduce(
    tensor: jax.Array,
    *,
    axis_name="data",
    op: ReduceOp = Average,
    compression=Compression.none,
    average: Optional[bool] = None,
) -> jax.Array:
    """Allreduce ``tensor`` over mesh axis ``axis_name``.

    ``average`` kwarg keeps the reference signature
    (``horovod/tensorflow/__init__.py:44-87``); ``compression`` casts to the
    wire dtype for the reduction only.
    """
    if average is not None:
        op = Average if average else Sum
    wire, ctx = compression.compress(tensor)
    reduced = _reduce(wire, axis_name, op)
    return compression.decompress(reduced, ctx)


def grouped_allreduce(
    tensors: Sequence[jax.Array],
    *,
    axis_name="data",
    op: ReduceOp = Average,
    compression=Compression.none,
) -> list[jax.Array]:
    """Allreduce a list of tensors, each as it is; XLA's all-reduce
    combiner batches them (reference parity: response fusion,
    operations.cc:1815-1842)."""
    return [allreduce(t, axis_name=axis_name, op=op, compression=compression)
            for t in tensors]


def allgather(
    tensor: jax.Array, *, axis_name="data", axis: int = 0
) -> jax.Array:
    """Concatenate each shard's ``tensor`` along ``axis`` (dim 0 by default),
    matching reference allgather semantics (operations.cc:796-856).

    XLA requires static shapes, so unlike the reference the per-shard dim-0
    sizes must be equal inside jit; ragged gathers are handled by the eager
    engine via pad-to-max (SURVEY.md §3.5).
    """
    return lax.all_gather(tensor, axis_name, axis=axis, tiled=True)


def broadcast(
    tensor: jax.Array, root_rank: int = 0, *, axis_name="data"
) -> jax.Array:
    """Every shard receives root's value (reference operations.cc:1333-1353)."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == root_rank, tensor, jnp.zeros_like(tensor))
    if jnp.issubdtype(tensor.dtype, jnp.inexact) or jnp.issubdtype(
        tensor.dtype, jnp.integer
    ):
        return lax.psum(masked, axis_name)
    raise TypeError(f"broadcast: unsupported dtype {tensor.dtype}")


def reducescatter(
    tensor: jax.Array,
    *,
    axis_name="data",
    op: ReduceOp = Sum,
    scatter_axis: int = 0,
    tiled: bool = True,
) -> jax.Array:
    """Reduce then scatter shards along ``scatter_axis``.

    Not in the 0.15.1 API, but it is the first half of the reference's
    hierarchical allreduce (ncclReduceScatter, operations.cc:1025-1187) and
    the core primitive of the FSDP layer.
    """
    out = lax.psum_scatter(
        tensor, axis_name, scatter_dimension=scatter_axis, tiled=tiled
    )
    if op is Average:
        out = out / lax.axis_size(axis_name)
    return out


def alltoall(
    tensor: jax.Array,
    *,
    axis_name="seq",
    split_axis: int = 0,
    concat_axis: int = 0,
) -> jax.Array:
    """All-to-all over a mesh axis (Ulysses-style sequence parallelism
    building block; no reference equivalent — TPU-native extension)."""
    return lax.all_to_all(
        tensor, axis_name, split_axis=split_axis, concat_axis=concat_axis,
        tiled=True,
    )
