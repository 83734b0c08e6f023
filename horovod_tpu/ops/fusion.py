"""Tensor fusion: batching many small tensors into few large collectives.

Reference parity: the Tensor Fusion buffer (``horovod/common/operations.cc``
149-165, 743-767, 1232-1311 and ``docs/tensor-fusion.md``): a 64 MB persistent
buffer per (device, framework); consecutive same-dtype responses are packed
back-to-back, one collective runs over the packed buffer, results are copied
back out.  Threshold via ``HOROVOD_FUSION_THRESHOLD``.

TPU-native design: under XLA there is no persistent staging buffer and no
memcpy — fusion is *flattening the gradient pytree at trace time*.  We
ravel + concatenate same-dtype leaves into flat buffers up to the threshold,
run one ``psum`` per buffer (a single large ICI collective keeps the links
saturated, which is where scaling efficiency is won — SURVEY.md §7 "Fusion on
TPU"), then slice + reshape back.  The plan is shape-static, so it traces once
per pytree structure.

What the packing costs (TPU v5e, the 664M-parameter decoder of PERF.md,
bf16 gradients, measured under the ``hvd.fusion.pack`` / ``hvd.fusion.unpack``
scopes below; PERF.md, PR 24).  XLA does NOT fold the copies into the
collective: the concatenates and slices it cannot simplify away run as
operations of their own, an extra HBM round-trip of the buffers they touch.
On four chips that is 5.8 ms of a 276 ms step (``fusion_pack_ms``: 3.8 ms of
concatenates and slices, 2.1 ms for the average's division of the fused
buffers), beside ~5 ms of layout copies XLA inserts around the buffers without
a name, and the all-reduce between backward pass and optimizer keeps XLA from
fusing each weight's update into its gradient matmul, as it does on one chip
(there the optimizer's own fusions take 13 ms, here 37, the backward pass
17 ms less).  On ONE chip XLA removes the ``psum`` and most of the packing
with it, but not all: 4.0-4.8 ms a step of concatenate and slice remain for
a collective that no longer exists.
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
    "fusion_threshold_bytes",
    "FusionPlan",
    "plan_fusion",
    "fuse_apply",
]

#: 64 MB, matching the reference default (operations.cc:1595).
DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024


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


@dataclass(frozen=True)
class FusionPlan:
    buckets: tuple[_Bucket, ...]
    n_leaves: int


def plan_fusion(
    leaves: Sequence[jax.Array], threshold_bytes: int | None = None
) -> FusionPlan:
    """Group leaves into same-dtype buckets of at most ``threshold_bytes``.

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
            if cur and threshold_bytes > 0 and cur_bytes + nbytes > threshold_bytes:
                buckets.append(_mk_bucket(dtype, cur, leaves))
                cur, cur_bytes = [], 0
            if threshold_bytes == 0:
                # Fusion disabled: one leaf per bucket.
                buckets.append(_mk_bucket(dtype, [i], leaves))
                continue
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
    print(
        f"horovod_tpu fusion: {plan.n_leaves} tensors -> "
        f"{len(plan.buckets)} fused collective(s)",
        file=sys.stderr,
    )
    for n, b in enumerate(plan.buckets):
        nbytes = sum(b.sizes) * np.dtype(b.dtype).itemsize
        print(
            f"  bucket {n}: {len(b.indices)} x {np.dtype(b.dtype).name}, "
            f"{sum(b.sizes)} elements ({nbytes / 2**20:.2f} MiB)",
            file=sys.stderr,
        )


def fuse_apply(
    tree: Any,
    fn: Callable[[jax.Array], jax.Array],
    threshold_bytes: int | None = None,
) -> Any:
    """Apply ``fn`` (e.g. a psum) over fused flat buffers of ``tree``.

    Equivalent to ``jax.tree.map(fn_elementwise, tree)`` when ``fn`` is an
    elementwise-safe collective, but emits one ``fn`` call per fused bucket
    instead of one per leaf.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    plan = plan_fusion(leaves, threshold_bytes)
    _maybe_report(plan)
    out: list[Any] = [None] * plan.n_leaves
    for bucket in plan.buckets:
        if len(bucket.indices) == 1:
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
