"""Collective ops and compression.

The kernels are modules of their own, imported where they are used:
``flash_attention``, ``paged_attention``, ``sparse_index``, ``rope`` (the
rotation as one Mosaic pass), ``short_conv`` (a linear-attention layer's
convolution, SiLU and L2 norm as one Mosaic pass each way) and
``gated_delta`` (the chunkwise gated delta rule: Mosaic calls over the rows
where a head is whole lane tiles, else ``jax.numpy`` but for its chunks'
triangular systems, solved in one Mosaic call a slab), ``ssd`` (Mamba-2's
chunked state-space scan: a Mosaic call each way, or ``jax.numpy``),
``chunking`` (the
chunks and slabs the two recurrences share), ``selective_scan`` (Mamba-1's
recurrence: a Mosaic call each way, or chunks in ``jax.numpy``),
``grouped_matmul`` (a routed layer's grouped products: XLA's ``ragged_dot``,
or JAX's Mosaic grouped matmul at stated tiles) and ``hyper_connection``
(hyper-connected residual streams: the maps' product, the read, the write
and their transposes as Mosaic passes over rows of X).  None of
them is imported or exported here (``models/llama.py`` imports each beside
the mixer it serves), so ``import horovod_tpu.ops`` pays for no kernel."""

from horovod_tpu.ops.collective_ops import (
    Average,
    Max,
    Min,
    Product,
    ReduceOp,
    Sum,
    allgather,
    allreduce,
    alltoall,
    axis_rank,
    axis_size,
    broadcast,
    grouped_allreduce,
    reducescatter,
)
from horovod_tpu.ops.compression import Compression, Compressor
from horovod_tpu.ops.ragged import (
    bucket_rows,
    compact,
    pad_rows,
    ragged_allgather,
)

__all__ = [
    "Average",
    "Max",
    "Min",
    "Product",
    "ReduceOp",
    "Sum",
    "allgather",
    "allreduce",
    "alltoall",
    "axis_rank",
    "axis_size",
    "broadcast",
    "grouped_allreduce",
    "reducescatter",
    "Compression",
    "Compressor",
    "bucket_rows",
    "compact",
    "pad_rows",
    "ragged_allgather",
]
