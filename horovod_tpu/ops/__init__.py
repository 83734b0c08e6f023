"""Collective ops and compression."""

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
