"""Tensor fusion: batching many small tensors into few large collectives.

Reference parity: the Tensor Fusion buffer (``horovod/common/operations.cc``
149-165, 743-767, 1232-1311 and ``docs/tensor-fusion.md``): a 64 MB persistent
buffer per (device, framework); consecutive same-dtype responses are packed
back-to-back, one collective runs over the packed buffer, results are copied
back out.  Threshold via ``HOROVOD_FUSION_THRESHOLD``.

TPU-native design: under XLA there is no persistent staging buffer — fusion
is *flattening the gradient pytree at trace time*.  Same-dtype leaves UNDER
``IN_PLACE_CUTOFF_BYTES`` are raveled and concatenated into flat buffers up to
the threshold, one ``psum`` runs per buffer, and the result is sliced and
reshaped back: what tensor fusion was invented for, small tensors whose
collective is all latency.  A leaf AT OR ABOVE the cut-off is a bucket of one
and goes to the ``psum`` as it is.  The plan is shape-static, so it traces
once per pytree structure.

What packing EVERY leaf cost, as the parent of PR 25 did (TPU v5e, the
664M-parameter decoder of PERF.md, bf16 gradients, measured under the
``hvd.fusion.pack`` / ``hvd.fusion.unpack`` scopes below; PERF.md, PR 24).
XLA does NOT fold the copies into the collective: the concatenates and slices
it cannot simplify away run as operations of their own, an extra HBM
round-trip of the buffers they touch.  On four chips that was 5.8 ms of a
276 ms step (``fusion_pack_ms``: 3.8 ms of concatenates and slices, 2.1 ms for
the average's division of the fused buffers), beside ~5 ms of layout copies
XLA inserts around the buffers without a name and f32 copies of the unpacked
gradients inside the optimizer pass (37 ms where one chip's takes 13).  On ONE
chip XLA removes the ``psum`` and most of the packing with it, but not all:
4.0-4.8 ms a step of concatenate and slice remained for a collective that no
longer exists, and the one concatenate XLA could not remove kept the AdamW
updates of its leaves out of their gradient matmuls.

What leaving large leaves in place found (PR 25; that model's gradients are
19 norm scales of 4 KB and 56 matrices of 8-201 MB, 1,327.5 of 1,327.6 MB).
XLA's own all-reduce combiner batches them, without a copy: 11 all-reduces,
eight of them variadic over up to nine gradient matrices in the tiled layout
their matmuls wrote, against 12 over 1-D buffers, and they take the same
23.2 ms.  The step fell 276.2 -> 254.3 ms on four chips (``fusion_pack_ms``
5.8 -> 0, optimizer 36.8 -> 26.8, nameless copies 9.7 -> 6.0) and 240.5 ->
229.3 / 293.5 -> 279.5 ms on one, where every matrix but the embedding now
has its AdamW update fused into its gradient matmul (optimizer 13 -> 4.5 ms).
Every all-reduce is still synchronous and stands between backward pass and
optimizer on four chips, so no weight's update fuses into its gradient matmul
there, packed or not.  On a ResNet-50-shaped tree (161 fp32 leaves) the
combiner makes ONE all-reduce at any cut-off, and packing every leaf costs
3-4 % more than packing none (PERF.md §6, PR 25).
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.common import scopes as _scopes

__all__ = [
    "DEFAULT_FUSION_THRESHOLD",
    "IN_PLACE_CUTOFF_BYTES",
    "fusion_threshold_bytes",
    "FusionPlan",
    "plan_fusion",
    "fuse_apply",
]

#: 64 MB, matching the reference default (operations.cc:1595).
DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024

#: A leaf of at least this many bytes is never packed: ``fn`` gets the leaf
#: itself.  Packing moves a leaf through HBM twice more (2.6 us a MiB at the
#: v5e's 819 GB/s) to save a collective launch, which XLA's all-reduce
#: combiner saves anyway; what decides the value is the chip's collective
#: latency against its HBM bandwidth, not the model, so it is no knob.
IN_PLACE_CUTOFF_BYTES = 1024 * 1024


def fusion_threshold_bytes() -> int:
    """Read ``HOROVOD_FUSION_THRESHOLD`` (bytes), reference knob parity
    (operations.cc:1595-1618).  0 disables fusion."""
    value = os.environ.get("HOROVOD_FUSION_THRESHOLD")
    if value is None or value == "":
        return DEFAULT_FUSION_THRESHOLD
    return int(value)


@dataclass(frozen=True)
class _Bucket:
    dtype: Any
    indices: tuple[int, ...]  # leaf positions in flattened order
    sizes: tuple[int, ...]
    shapes: tuple[tuple[int, ...], ...]

    @property
    def packed(self) -> bool:
        """More than one leaf: copied into a flat buffer and sliced out."""
        return len(self.indices) > 1

    @property
    def nbytes(self) -> int:
        return sum(self.sizes) * np.dtype(self.dtype).itemsize


@dataclass(frozen=True)
class FusionPlan:
    """``buckets`` in the order ``fuse_apply`` runs them.  A leaf is *packed*
    when its bucket holds more than one leaf and *in place* when it is a
    bucket of one, which ``fn`` gets as it is."""

    buckets: tuple[_Bucket, ...]
    n_leaves: int

    def _count(self, packed: bool) -> tuple[int, int]:
        chosen = [b for b in self.buckets if b.packed == packed]
        return (sum(len(b.indices) for b in chosen),
                sum(b.nbytes for b in chosen))

    @property
    def packed(self) -> tuple[int, int]:
        """(leaves, bytes) that travel through a packed buffer."""
        return self._count(True)

    @property
    def in_place(self) -> tuple[int, int]:
        """(leaves, bytes) handed to ``fn`` as they are, with no copy."""
        return self._count(False)


def plan_fusion(
    leaves: Sequence[jax.Array], threshold_bytes: int | None = None
) -> FusionPlan:
    """Group the leaves under ``IN_PLACE_CUTOFF_BYTES`` into same-dtype
    buckets of at most ``threshold_bytes``; every larger leaf is a bucket of
    one, which ``fuse_apply`` reduces in place.

    Order within a dtype is preserved; a bucket never mixes dtypes (the
    reference likewise only fuses same-dtype, same-device responses,
    operations.cc:1815-1842).
    """
    if threshold_bytes is None:
        threshold_bytes = fusion_threshold_bytes()
    by_dtype: dict[Any, list[int]] = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(leaf).dtype, []).append(i)

    buckets: list[_Bucket] = []
    for dtype, idxs in by_dtype.items():
        itemsize = np.dtype(dtype).itemsize
        cur: list[int] = []
        cur_bytes = 0
        for i in idxs:
            nbytes = int(np.prod(jnp.shape(leaves[i]), dtype=np.int64)) * itemsize
            if threshold_bytes == 0 or nbytes >= IN_PLACE_CUTOFF_BYTES:
                # Fusion disabled, or nothing to gain from a copy: the leaf
                # alone.  Small leaves keep packing around it.
                buckets.append(_mk_bucket(dtype, [i], leaves))
                continue
            if cur and cur_bytes + nbytes > threshold_bytes:
                buckets.append(_mk_bucket(dtype, cur, leaves))
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nbytes
        if cur:
            buckets.append(_mk_bucket(dtype, cur, leaves))
    return FusionPlan(buckets=tuple(buckets), n_leaves=len(leaves))


def _mk_bucket(dtype, idxs: list[int], leaves) -> _Bucket:
    shapes = tuple(tuple(jnp.shape(leaves[i])) for i in idxs)
    sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
    return _Bucket(dtype=dtype, indices=tuple(idxs), sizes=sizes, shapes=shapes)


#: Plans already reported this process (HOROVOD_FUSION_REPORT dedup).
_reported_plans: set = set()


def _maybe_report(plan: FusionPlan) -> None:
    """HOROVOD_FUSION_REPORT=1: print each distinct fusion plan once.

    The jit-path counterpart of the timeline's negotiation visibility
    (SURVEY.md §5.1): fusion happens at TRACE time here, so a one-shot
    bucket report is the observable record of what got batched into each
    ICI collective — the information the eager engine's timeline shows as
    fused response lists."""
    if os.environ.get("HOROVOD_FUSION_REPORT", "0") in ("", "0"):
        return
    key = tuple((str(b.dtype), b.sizes) for b in plan.buckets)
    if key in _reported_plans:
        return
    _reported_plans.add(key)
    mib = 2.0 ** 20
    packed = [b for b in plan.buckets if b.packed]
    n_packed, packed_bytes = plan.packed
    n_in_place, in_place_bytes = plan.in_place
    print(
        f"horovod_tpu fusion: {plan.n_leaves} tensors -> "
        f"{len(packed)} fused collective(s) of {n_packed} packed leaves "
        f"({sum(sum(b.sizes) for b in packed)} elements, "
        f"{packed_bytes / mib:.2f} MiB) + {n_in_place} leaves in place "
        f"({in_place_bytes / mib:.2f} MiB)",
        file=sys.stderr,
    )
    for n, b in enumerate(plan.buckets):
        print(
            f"  bucket {n}: {len(b.indices)} x {np.dtype(b.dtype).name}, "
            f"{sum(b.sizes)} elements ({b.nbytes / mib:.2f} MiB)"
            + ("" if b.packed else ", in place"),
            file=sys.stderr,
        )


def fuse_apply(
    tree: Any,
    fn: Callable[[jax.Array], jax.Array],
    threshold_bytes: int | None = None,
) -> Any:
    """Apply ``fn`` (e.g. a psum) over fused flat buffers of ``tree``.

    Equivalent to ``jax.tree.map(fn_elementwise, tree)`` when ``fn`` is an
    elementwise-safe collective, but emits one ``fn`` call per bucket of
    ``plan_fusion`` instead of one per leaf: small leaves travel packed, a
    leaf of ``IN_PLACE_CUTOFF_BYTES`` or more goes to ``fn`` as it is.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    plan = plan_fusion(leaves, threshold_bytes)
    _maybe_report(plan)
    out: list[Any] = [None] * plan.n_leaves
    for bucket in plan.buckets:
        if not bucket.packed:
            i = bucket.indices[0]
            out[i] = fn(leaves[i])
            continue
        with jax.named_scope(_scopes.FUSION_PACK):
            flat = jnp.concatenate(
                [jnp.ravel(leaves[i]) for i in bucket.indices], axis=0
            )
        reduced = fn(flat)
        offset = 0
        with jax.named_scope(_scopes.FUSION_UNPACK):
            for i, size, shape in zip(bucket.indices, bucket.sizes,
                                      bucket.shapes):
                out[i] = jax.lax.slice_in_dim(
                    reduced, offset, offset + size).reshape(shape)
                offset += size
    return jax.tree.unflatten(treedef, out)
