"""JAX frontend: the flagship user API of the TPU-native Horovod rebuild.

Reference parity: ``horovod/tensorflow/__init__.py`` (225 LoC) — ``init``,
rank queries, ``allreduce``, ``broadcast_global_variables``,
``DistributedOptimizer`` — re-thought for JAX's functional model:

* ``DistributedOptimizer`` wraps an *optax* ``GradientTransformation``; the
  wrapped ``update`` psums each gradient over the mesh's data axes
  before the inner optimizer sees them.  This is the exact analogue of the
  reference overriding ``compute_gradients`` to allreduce each grad
  (tensorflow/__init__.py:183-209), but it happens inside ``jit`` where XLA
  overlaps the ICI collectives with remaining backward compute — the same
  overlap the reference engineered by hand with its background thread.
* ``broadcast_parameters`` replaces ``BroadcastGlobalVariablesHook``:
  functional in, functional out (no sessions, no variable mutation).
* Collectives dispatch on context: on tracers (inside jit/shard_map) they are
  single XLA ops over a named axis; on concrete arrays they go through the
  eager runtime engine (negotiation across processes), matching the
  reference's eager TF path.

Typical use::

    import horovod_tpu.jax as hvd
    hvd.init()
    mesh = hvd.data_parallel_mesh()
    opt = hvd.DistributedOptimizer(optax.sgd(0.01 * hvd.num_chips()))
    step = hvd.make_train_step(loss_fn, opt, mesh)
"""

from __future__ import annotations

import functools
import sys
import time
from typing import Callable, Optional

# For the import's span (the end of this file): whether the process had
# JAX before this package asked for it.
_JAX_WAS_IMPORTED = "jax" in sys.modules

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from horovod_tpu.common import (
    epoch,
    is_initialized,
    local_rank,
    local_size,
    mpi_threads_supported,
    rank,
    shutdown,
    size,
)
from horovod_tpu.common import init as _init
from horovod_tpu.common import scopes as _scopes
from horovod_tpu.common import trace_counts as _trace_counts
from horovod_tpu.common.compile_cache import (
    add_span as _add_span,
    compile_evicted,
    compile_log,
    compile_spans,
    enable_compile_cache,
    enable_compile_log,
)
from horovod_tpu.common.scopes import TRAIN_STEP_PROGRAM
from horovod_tpu.ops import collective_ops as _cops
from horovod_tpu.ops.collective_ops import (
    Average,
    Max,
    Min,
    Product,
    ReduceOp,
    Sum,
)
from horovod_tpu.ops.compression import Compression
from horovod_tpu.parallel import mesh as _mesh
from horovod_tpu.parallel.mesh import (
    build_mesh,
    data_parallel_mesh,
    default_mesh,
    use_mesh,
)

__all__ = [
    "init", "shutdown", "is_initialized", "rank", "size", "local_rank",
    "local_size", "epoch", "mpi_threads_supported",
    "num_chips", "local_devices",
    "allreduce", "grouped_allreduce", "allgather", "broadcast",
    "reducescatter", "alltoall",
    "Average", "Sum", "Min", "Max", "Product", "ReduceOp", "Compression",
    "DistributedOptimizer", "allreduce_gradients", "update_counts",
    "broadcast_parameters", "broadcast_optimizer_state",
    "build_mesh", "data_parallel_mesh", "default_mesh", "use_mesh",
    "make_train_step", "compile_log", "compile_spans", "compile_evicted",
    "TRAIN_STEP_PROGRAM",
]


@functools.wraps(_init)
def init(*args, **kwargs) -> None:
    # The common init, then the persistent compile cache and the compile
    # log (common/compile_cache.py): this is the frontend whose programs
    # are jitted.  After, because the cache helper asks JAX for its backend.
    # Each part under a span of the log's (``hvd.compile_spans()``).
    with _scopes.span(_scopes.INIT):
        _init(*args, **kwargs)
        with _scopes.span(_scopes.INIT_CACHE):
            enable_compile_cache()
            enable_compile_log()


def num_chips() -> int:
    """Total number of TPU chips across all processes (the unit the
    reference calls ``size`` when run one-process-per-GPU)."""
    return jax.device_count()


def local_devices():
    return jax.local_devices()


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


# ---------------------------------------------------------------------------
# Collectives (context-dispatching wrappers)
# ---------------------------------------------------------------------------

def allreduce(tensor, *, axis_name="data", op=Average, average=None,
              compression=Compression.none, name=None, priority=None):
    """Allreduce. Inside jit/shard_map: one XLA collective over ``axis_name``.

    On concrete values: process-level eager allreduce through the runtime
    engine (identity at size()==1, like the reference under ``-np 1``).
    ``priority`` (host path only; 0 = most urgent) overrides the
    scheduling priority the priority-banded coordinator
    (HOROVOD_PRIORITY_BANDS) orders responses by — every rank must pass
    the same value for a given name.
    """
    if _is_traced(tensor):
        return _cops.allreduce(
            tensor, axis_name=axis_name, op=op, average=average,
            compression=compression,
        )
    from horovod_tpu.runtime import eager

    return eager.allreduce(tensor, op=op, average=average,
                           compression=compression, name=name,
                           priority=priority)


def grouped_allreduce(tensors, *, axis_name="data", op=Average,
                      compression=Compression.none, name=None):
    if tensors and _is_traced(tensors[0]):
        return _cops.grouped_allreduce(
            tensors, axis_name=axis_name, op=op, compression=compression
        )
    from horovod_tpu.runtime import eager

    return eager.grouped_allreduce(tensors, op=op, compression=compression,
                                   name=name)


def allgather(tensor, *, axis_name="data", axis=0, name=None):
    if _is_traced(tensor):
        return _cops.allgather(tensor, axis_name=axis_name, axis=axis)
    from horovod_tpu.runtime import eager

    return eager.allgather(tensor, name=name)


def broadcast(tensor, root_rank=0, *, axis_name="data", name=None):
    if _is_traced(tensor):
        return _cops.broadcast(tensor, root_rank, axis_name=axis_name)
    from horovod_tpu.runtime import eager

    return eager.broadcast(tensor, root_rank=root_rank, name=name)


def reducescatter(tensor, *, axis_name="data", op=Sum, scatter_axis=0,
                  tiled=True, name=None):
    """Reduce-scatter.  Traced: one XLA psum_scatter over ``axis_name``.
    Eager: cross-process ring reduce-scatter through the runtime engine.

    Full axis generality on BOTH paths (``scatter_axis``/``tiled`` match
    ``lax.psum_scatter``): the eager engine scatters dim-0 rows, so other
    axes ride a moveaxis shim around the wire op; ``tiled=False`` removes
    the scattered axis (its length must equal ``size()``)."""
    if _is_traced(tensor):
        return _cops.reducescatter(tensor, axis_name=axis_name, op=op,
                                   scatter_axis=scatter_axis, tiled=tiled)
    import jax.numpy as jnp

    x = jnp.asarray(tensor)
    if not tiled and x.shape[scatter_axis] != size():
        raise ValueError(
            f"tiled=False requires dim {scatter_axis} (length "
            f"{x.shape[scatter_axis]}) to equal size() ({size()}), like "
            "lax.psum_scatter")
    if size() == 1:
        # World of one: reduce is identity, the scatter keeps the full
        # shard — for any op/axis (matches the reference under -np 1).
        return jnp.squeeze(x, scatter_axis) if not tiled else x
    from horovod_tpu.runtime import eager

    moved = jnp.moveaxis(x, scatter_axis, 0)
    out = eager.reducescatter(moved, op=op, name=name)
    out = jnp.moveaxis(out, 0, scatter_axis)
    return jnp.squeeze(out, scatter_axis) if not tiled else out


def alltoall(tensor, *, axis_name="seq", split_axis=0, concat_axis=0,
             name=None, splits=None, wire_dtype=None, priority=None):
    """All-to-all.  Traced: one XLA all_to_all over ``axis_name``.  Eager:
    cross-process ring exchange of equal blocks, axis-general via a
    moveaxis shim (the wire op exchanges dim-0 blocks): split ``tensor``
    into ``size()`` blocks along ``split_axis``; block i goes to rank i;
    the received blocks concatenate along ``concat_axis`` — same
    semantics as ``lax.all_to_all`` on the traced path.

    ``splits`` (eager, dim 0 only) sends VARIABLE per-rank row counts —
    the MoE dispatch/combine primitive; the output's dim 0 is this
    rank's column of the negotiated size matrix, so it is data-dependent
    and only available eagerly."""
    if _is_traced(tensor):
        if splits is not None:
            raise NotImplementedError(
                "variable splits are eager-only (the output shape is "
                "data-dependent; XLA all_to_all exchanges equal blocks)")
        return _cops.alltoall(tensor, axis_name=axis_name,
                              split_axis=split_axis, concat_axis=concat_axis)
    import jax.numpy as jnp

    x = jnp.asarray(tensor)
    if size() == 1:
        return x
    from horovod_tpu.runtime import eager

    if splits is not None and (split_axis != 0 or concat_axis != 0):
        raise NotImplementedError(
            "variable splits address dim-0 rows; use "
            "split_axis=0, concat_axis=0")
    if split_axis == 0 and concat_axis == 0:
        return eager.alltoall(x, name=name, splits=splits,
                              wire_dtype=wire_dtype,
                              priority=priority)  # wire semantics, copy-free
    moved = jnp.moveaxis(x, split_axis, 0)
    z = eager.alltoall(moved, name=name)
    # z: size() received blocks stacked along dim 0, each the moved shape
    # with dim 0 shrunk by size().  Restore each block's axis order, then
    # concatenate where the caller asked.
    blocks = jnp.split(z, size(), axis=0)
    blocks = [jnp.moveaxis(b, 0, split_axis) for b in blocks]
    return jnp.concatenate(blocks, axis=concat_axis)


# ---------------------------------------------------------------------------
# Gradient reduction + DistributedOptimizer
# ---------------------------------------------------------------------------

def allreduce_gradients(grads, *, axis_name=None, op=Average,
                        compression=Compression.none, wire_policy=None):
    """Allreduce of a gradient pytree over the data axes.

    ``axis_name`` may be a name, tuple of names, or None (= every data-like
    axis of the default mesh: ``data`` and ``fsdp``).

    Traced gradients (inside jit/shard_map) are all-reduced leaf by leaf,
    each as it is.  Nothing is packed: XLA's all-reduce combiner batches
    the leaves into a few variadic all-reduces in the layout their
    producers wrote, with no copy (PERF.md §6, PR 24, PR 25 and PR 30).
    CONCRETE gradients — the host-driven DCN path — go
    through the eager engine per leaf, with stable tree-path names: that
    is what lets ``compression=Compression.topk(...)`` keep one
    error-feedback residual per gradient leaf, and the wire-level
    compressors (``Compression.wire_int8`` etc.) negotiate their wire
    dtype per tensor.

    On the host path every leaf is additionally stamped with a
    scheduling PRIORITY equal to its registration (tree-flatten) order —
    first-registered ≈ front layer ≈ needed first by the NEXT step's
    forward — which the priority-banded coordinator
    (HOROVOD_PRIORITY_BANDS) uses to dispatch urgent gradients first.

    ``wire_policy`` (a :class:`horovod_tpu.runtime.wire_policy.WirePolicy`;
    default: the env-configured policy when HOROVOD_WIRE_POLICY=1, else
    off) chooses a per-leaf wire dtype from rolling gradient statistics
    (int8 for large embedding-shaped grads, fp32 for norm/bias leaves),
    stamped as ADVISORY per-tensor overrides so per-rank statistics can
    never split negotiation.
    """
    leaves = jax.tree.leaves(grads)
    if leaves and not _is_traced(leaves[0]):
        from horovod_tpu.ops.compression import TopKCompressor
        from horovod_tpu.runtime import eager
        from horovod_tpu.runtime import wire_policy as _wp

        if wire_policy is None and _wp.policy_enabled():
            wire_policy = _wp.default_policy()
        flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
        if isinstance(compression, TopKCompressor):
            # Sparse path: per-leaf residuals keyed by stable tree-path
            # names.  Sequential by nature (each leaf is two allgathers
            # plus a host scatter-add) — top-k is the opt-in
            # bandwidth-starved regime where that trade is the point.
            out = [
                eager.allreduce(
                    leaf, op=op, compression=compression,
                    name="grad" + (jax.tree_util.keystr(path) or f".{i}"))
                for i, (path, leaf) in enumerate(flat)
            ]
        else:
            # Dense/wire path: enqueue every leaf before draining any —
            # one negotiation cycle covers the burst and the engine's
            # response fusion batches same-dtype/same-wire leaves into
            # few ring collectives (a per-leaf synchronous loop would
            # serialize N round trips and defeat fusion entirely).
            # Priorities = registration order; the wire policy (when on)
            # stamps advisory per-leaf formats keyed by the same stable
            # tree-path names the top-k residuals use.  The original
            # leaves go to grouped_allreduce unchanged, so the default
            # (policy-off) path is exactly the pre-policy one; with the
            # policy ON, the statistics cost one extra host fetch per
            # leaf — the bounded, opt-in price of observing gradients.
            wire_dtypes = None
            if wire_policy is not None:
                import numpy as _np

                wire_dtypes = [
                    wire_policy.observe_and_choose(
                        "grad" + (jax.tree_util.keystr(path) or f".{i}"),
                        _np.asarray(leaf))
                    for i, (path, leaf) in enumerate(flat)
                ]
            out = eager.grouped_allreduce(
                [leaf for _, leaf in flat], op=op,
                compression=compression, name="grad",
                priorities=list(range(len(flat))),
                wire_dtypes=wire_dtypes, wire_advisory=True)
        return jax.tree_util.tree_unflatten(treedef, out)
    if axis_name is None:
        axis_name = _mesh.data_axes() or ("data",)

    return jax.tree.map(
        lambda leaf: _cops.allreduce(leaf, axis_name=axis_name, op=op,
                                     compression=compression),
        grads)


#: A weight matrix of this many elements or more has its update taken out of
#: its gradient's matmul (``_alone``).  XLA:TPU fuses a weight's whole update
#: (for ``master_weights(adamw)`` the master, mu, nu and the bf16 weight, ~30
#: bytes a weight) into the product that forms its gradient.  Up to ~23M
#: weights such a fusion costs its parts, FLOP time + byte time; from ~42M it
#: runs at 1.5-2x its parts (PERF.md §5, "The update inside the gradient
#: matmuls": the by-size table and the sweep over this constant on the v5e,
#: PR 44).
ALONE_FROM_ELEMENTS = 40_000_000

_UPDATE = "optimizer.update"     # its kind in common/trace_counts.py


def update_counts() -> dict:
    """``{"alone": n, "fused": n}``: how the last traced
    ``DistributedOptimizer.update`` split its gradient leaves.  ``alone``:
    behind a barrier of its own, so the leaf's update is one loop fusion
    under ``hvd.optimizer`` / ``hvd.apply`` after a plain gradient matmul; ``fused``: left
    to the compiler, which may put the update into the gradient's fusion.
    Counted at trace time (readable after ``step.lower``), process-global,
    like ``ops/short_conv.py::body_counts``."""
    return {"alone": 0, "fused": 0} | _trace_counts.counts(_UPDATE)


def _alone(grads):
    """``grads`` with every large weight matrix's gradient behind its own
    ``optimization_barrier`` (the identity; XLA fuses nothing across it).
    A leaf at a time, never the tree: a barrier over all of them would keep
    every gradient alive until the last is formed.  The rule reads the leaf
    alone: floating, rank 2 or more, ``ALONE_FROM_ELEMENTS`` or more.
    Concrete gradients (the host-driven path) pass as they are: there is no
    compiler to hold back."""
    leaves, treedef = jax.tree.flatten(grads)
    if not any(map(_is_traced, leaves)):
        return grads
    engaged = [_is_traced(g) and g.ndim >= 2
               and g.size >= ALONE_FROM_ELEMENTS
               and jnp.issubdtype(g.dtype, jnp.floating) for g in leaves]
    _trace_counts.note_last(_UPDATE, {
        "alone": sum(engaged), "fused": len(leaves) - sum(engaged)})
    return jax.tree.unflatten(treedef, [
        jax.lax.optimization_barrier(g) if taken else g
        for g, taken in zip(leaves, engaged)])


class DistributedOptimizer:
    """Wrap an optax ``GradientTransformation`` so that ``update`` averages
    gradients across the mesh before applying the inner optimizer.

    Reference parity: ``hvd.DistributedOptimizer`` (tensorflow/__init__.py:
    135-209).  Implements the optax interface, so it drops into any optax
    pipeline (including ``optax.chain``) and into flax's TrainState.

    Must be called inside a context with the mesh axes bound (shard_map or
    pmap); under plain pjit-with-sharded-batch XLA already inserts the psum,
    in which case wrap with ``reduce_gradients=False`` to keep only the
    bookkeeping.

    Inside ``jit`` the gradient of every large weight matrix (a floating
    leaf of rank 2 or more with ``ALONE_FROM_ELEMENTS`` elements or more)
    passes its own ``jax.lax.optimization_barrier`` on its way to the
    all-reduce and the inner ``update``: on one chip XLA would otherwise fuse
    the leaf's whole update into the matmul that forms its gradient, which
    for a matrix that large costs up to twice the two apart.  The barrier
    is the identity; the leaf's update then runs as one loop fusion under
    ``hvd.optimizer`` / ``hvd.apply`` behind a plain gradient matmul, and
    :func:`update_counts` says how the last traced update split its leaves.

    ``local_sgd_steps=H`` (default: ``HOROVOD_LOCAL_SGD_STEPS``, 1)
    switches the host-driven (eager/DCN) path to communication-relaxed
    local SGD: ``update`` applies gradients purely LOCALLY (no per-step
    allreduce), and the attached :class:`horovod_tpu.elastic.LocalSGD`
    policy syncs the model delta every ``H`` steps — the training loop
    calls ``params = opt.local_sgd.maybe_sync(params)`` after
    ``optax.apply_updates``.  ``H <= 1`` is byte-identical to the plain
    synchronous path (the policy is not even constructed).  The outer
    delta sync is epoch-stamped: an elastic resize re-anchors instead of
    leaking a dead incarnation's delta, and it composes unchanged with
    wire compression and backup-worker partial commits.  With a
    ``Compression.topk(ratio)`` compression, the outer sync itself ships
    the model DELTA through the top-k sparse path with its own
    epoch-stamped error-feedback residuals (docs/elastic.md).

    ``sharded=True`` (default: ``HOROVOD_SHARDED``) turns the host-driven
    path into a ZeRO-1 sharded optimizer: gradients are flattened into
    ONE fp32 vector, reduced by ``reducescatter`` (half an allreduce's
    wire bytes), the inner optax transformation keeps state ONLY for this
    rank's shard (~1/N of the optimizer memory), and the shard's updates
    ride back on ``allgather``.  Elementwise inner optimizers (sgd,
    momentum, adam, adamw) make the step BIT-IDENTICAL to the equivalent
    unsharded flat step — asserted per dtype in tests.  Host path only
    (inside jit use the fsdp mesh axis instead); fp32 params only; see
    docs/zero.md for the memory math and resize semantics.

    ``fsdp=True`` (default: ``HOROVOD_FSDP``) climbs one more rung of the
    sharding ladder (ZeRO-3/FSDP): the model is cut into per-layer
    UNITS — one per top-level key of the param tree, or explicit groups
    via ``fsdp_units=[["embed", "lm_head"], ...]`` — and each unit gets
    its own :class:`~horovod_tpu.runtime.fsdp.FsdpPlane` window.
    ``update`` enqueues every unit's gradient reducescatter up front in
    reverse unit order with priority band = unit index (the backward
    cascade: early-forward units land in urgent bands because the next
    step needs them first), runs each unit's inner update on the owned
    shard as its reduction drains, and pipelines the per-unit update
    allgathers at band 0 so they overlap later units' shard updates.
    Inner optimizer state is per-unit shard-sized (the same ~1/N as
    ZeRO-1), the step stays bit-identical to the unsharded anchor, and
    the optax interface is unchanged (full ``updates`` tree out).  Full
    1/N *parameter* residency — gather/free around each layer's
    compute — is the plane's own API
    (:meth:`horovod_tpu.runtime.fsdp.FsdpPlane.gather`); a tree-in/
    tree-out optax wrapper cannot free params it does not own, and
    docs/zero.md is honest about that line.
    """

    def __init__(self, optimizer, *, axis_name=None, op=Average,
                 compression=Compression.none, reduce_gradients=True,
                 name=None, local_sgd_steps=None, sharded=None, fsdp=None,
                 fsdp_units=None, fsdp_prefetch=None):
        from horovod_tpu.elastic.state import (LocalSGD,
                                               default_local_sgd_steps)
        from horovod_tpu.runtime.fsdp import fsdp_default
        from horovod_tpu.runtime.sharded import sharded_default

        self._inner = optimizer
        self._axis_name = axis_name
        self._op = op
        self._compression = compression
        self._reduce = reduce_gradients
        self.name = name or "DistributedOptimizer"
        self._local_sgd_steps = (default_local_sgd_steps()
                                 if local_sgd_steps is None
                                 else max(1, int(local_sgd_steps)))
        self._sharded = (sharded_default() if sharded is None
                         else bool(sharded))
        self._fsdp = fsdp_default() if fsdp is None else bool(fsdp)
        if self._fsdp and self._sharded:
            raise ValueError(
                "fsdp=True and sharded=True are mutually exclusive: "
                "FSDP subsumes the ZeRO-1 step (pick one rung of the "
                "ladder; see docs/zero.md)")
        if (self._sharded or self._fsdp) and self._local_sgd_steps > 1:
            raise ValueError(
                "sharded/fsdp and local_sgd_steps>1 are mutually "
                "exclusive: local SGD skips the per-step reduction the "
                "sharded step is built around")
        if (self._sharded or self._fsdp) and not reduce_gradients:
            raise ValueError(
                "sharded/fsdp requires reduce_gradients=True: the ZeRO "
                "step IS the reduction (reducescatter -> shard update "
                "-> allgather); without it the shard-sized state cannot "
                "apply and ranks would silently diverge")
        if (self._sharded or self._fsdp) and op not in (Average, Sum):
            raise ValueError(
                "sharded/fsdp reduces gradients with SUM/AVERAGE only")
        #: Lazy ZeRO state (built on first init() from the param tree).
        self._sharder = None
        self._tree_shapes = None
        #: Lazy FSDP state (unit planes built on first init()).
        self._fsdp_plane = None
        self._fsdp_groups = None
        self._fsdp_unit_spec = fsdp_units
        self._fsdp_prefetch = fsdp_prefetch
        #: The periodic-sync policy (None when H <= 1 — fully
        #: synchronous, the pre-local-SGD contract, byte-identical).
        self.local_sgd = (LocalSGD(self._local_sgd_steps,
                                   compression=compression)
                          if self._local_sgd_steps > 1 else None)

    @property
    def inner(self):
        """The wrapped optax transformation."""
        return self._inner

    def with_axis_name(self, axis_name):
        """A copy bound to ``axis_name`` (used by train-step builders to pin
        reduction to the mesh they run on)."""
        copy = DistributedOptimizer(
            self._inner, axis_name=axis_name, op=self._op,
            compression=self._compression,
            reduce_gradients=self._reduce, name=self.name,
            local_sgd_steps=self._local_sgd_steps,
            sharded=self._sharded, fsdp=self._fsdp,
            fsdp_units=self._fsdp_unit_spec,
            fsdp_prefetch=self._fsdp_prefetch,
        )
        # Share the policy/sharder instances: anchors and counters live
        # with the training run, not with any one bound copy.
        copy.local_sgd = self.local_sgd
        copy._sharder = self._sharder
        copy._tree_shapes = self._tree_shapes
        copy._fsdp_plane = self._fsdp_plane
        copy._fsdp_groups = self._fsdp_groups
        return copy

    def init(self, params):
        if self._fsdp:
            return self._fsdp_init(params)
        if not self._sharded:
            return self._inner.init(params)
        return self._sharded_init(params)

    def update(self, grads, state, params=None, **extra):
        # FSDP path: per-unit RS cascade → shard updates → banded AGs.
        if self._fsdp and self._reduce:
            return self._fsdp_update(grads, state, params, **extra)
        # ZeRO path: RS(flat grads) → shard-local inner update → AG.
        if self._sharded and self._reduce:
            return self._sharded_update(grads, state, params, **extra)
        # Ahead of the all-reduce, not behind it: on one chip the two places
        # compile to one module; over several chips a gradient is whole at
        # its all-reduce anyway, and a barrier behind it would only take the
        # average's division out of the update's fusion (PERF.md §6, PR 44).
        grads = _alone(grads)
        # Local-SGD phase: gradients apply purely locally; the policy's
        # maybe_sync (called by the training loop on the params) is the
        # only wire traffic — H× fewer syncs by construction.
        if self._reduce and self._local_sgd_steps <= 1:
            grads = allreduce_gradients(
                grads,
                axis_name=self._axis_name,
                op=self._op,
                compression=self._compression,
            )
        with _scopes.scope(_scopes.OPTIMIZER):
            return self._inner.update(grads, state, params, **extra)

    # -- ZeRO-1 sharded path (host-driven; see docs/zero.md) --

    def _sharded_init(self, params):
        import numpy as np
        import jax.numpy as jnp
        from horovod_tpu.ops.compression import TopKCompressor
        from horovod_tpu.runtime.sharded import FlatSharder

        if isinstance(self._compression, TopKCompressor):
            raise ValueError(
                "sharded=True reduces gradients with reducescatter; the "
                "top-k sparse path has no scatter half — use a wire "
                "compressor (Compression.wire_bf16 etc.) instead")
        leaves = jax.tree.leaves(params)
        for leaf in leaves:
            if jnp.asarray(leaf).dtype != jnp.float32:
                raise TypeError(
                    "sharded=True requires float32 params (the fp32-"
                    "master-weight mixed-precision variant lives in the "
                    "torch sharded optimizer; see docs/zero.md) — got "
                    f"{jnp.asarray(leaf).dtype}")
        shapes = [tuple(np.shape(leaf)) for leaf in leaves]
        n = int(sum(int(np.prod(s)) if s else 1 for s in shapes))
        self._tree_shapes = shapes
        self._sharder = FlatSharder(n, np.float32, name=self.name)
        shard = FlatSharder.slice_flat(
            [np.asarray(leaf) for leaf in leaves],
            self._sharder.offset, self._sharder.count, np.float32)
        # The inner transformation sees ONLY the owned shard: its state
        # (momenta etc.) is ~1/N of the unsharded footprint, which is
        # the whole point.
        return self._inner.init(jnp.asarray(shard))

    def _sharded_update(self, grads, state, params=None, **extra):
        import numpy as np
        import jax.numpy as jnp
        from horovod_tpu.runtime.sharded import FlatSharder

        leaves, treedef = jax.tree.flatten(grads)
        if leaves and _is_traced(leaves[0]):
            raise RuntimeError(
                "sharded=True is the host-driven (eager/DCN) path; "
                "inside jit shard optimizer state with the mesh's "
                "'fsdp' axis instead (parallel/mesh.py)")
        if self._sharder is None:
            raise RuntimeError(
                "sharded DistributedOptimizer.update() before init(): "
                "the shard layout is anchored at init(params)")
        flat_g = FlatSharder.flatten(
            [np.asarray(leaf) for leaf in leaves], np.float32)
        sh = self._sharder
        # Params: slice ONLY the owned window out of the virtual concat
        # (a full flat copy of the model every step would reintroduce
        # the O(N) host buffer sharding exists to avoid).
        p_shard = None
        if params is not None:
            p_shard = FlatSharder.slice_flat(
                [np.asarray(leaf) for leaf in jax.tree.leaves(params)],
                sh.offset, sh.count, np.float32)
        box = {}

        def local_update(shard_g):
            sp = jnp.asarray(p_shard) if p_shard is not None else None
            upd, box["state"] = self._inner.update(
                jnp.asarray(shard_g), state, sp, **extra)
            return np.asarray(upd, dtype=np.float32)

        wire = getattr(self._compression, "engine_wire_dtype", None)
        wire = wire if wire in ("fp16", "bf16", "int8", "fp8") else None
        full = sh.step(flat_g, local_update,
                       average=(self._op is Average), wire_dtype=wire)
        outs = FlatSharder.unflatten(full, self._tree_shapes)
        updates = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(o) for o in outs])
        return updates, box["state"]

    # -- ZeRO-3/FSDP path (host-driven; see docs/zero.md) --

    def _fsdp_init(self, params):
        import numpy as np
        import jax.numpy as jnp
        from horovod_tpu.ops.compression import TopKCompressor
        from horovod_tpu.runtime.fsdp import FsdpPlane

        if isinstance(self._compression, TopKCompressor):
            raise ValueError(
                "fsdp=True reduces gradients with reducescatter; the "
                "top-k sparse path has no scatter half — use a wire "
                "compressor (Compression.wire_bf16 etc.) instead")
        leaves = jax.tree.leaves(params)
        for leaf in leaves:
            if jnp.asarray(leaf).dtype != jnp.float32:
                raise TypeError(
                    "fsdp=True requires float32 params (the fp32-master "
                    "mixed-precision variant lives in the torch FSDP "
                    "optimizer; see docs/zero.md) — got "
                    f"{jnp.asarray(leaf).dtype}")
        self._fsdp_groups = _fsdp_unit_groups(params,
                                              self._fsdp_unit_spec)
        wire = getattr(self._compression, "engine_wire_dtype", None)
        wire = wire if wire in ("fp16", "bf16", "int8", "fp8") else None
        np_leaves = [np.asarray(leaf) for leaf in leaves]
        self._fsdp_plane = FsdpPlane(
            [[np_leaves[j] for j in idxs]
             for _, idxs in self._fsdp_groups],
            name=self.name, prefetch=self._fsdp_prefetch,
            wire_dtype=wire, average=(self._op is Average))
        # Per-unit inner states, each shard-sized: the whole optimizer
        # footprint is ~1/N like ZeRO-1, but reductions/gathers are now
        # per-unit so the banded scheduler can overlap them.
        return tuple(self._inner.init(jnp.asarray(self._fsdp_plane.shard(i)))
                     for i in range(self._fsdp_plane.n_units))

    def _fsdp_update(self, grads, state, params=None, **extra):
        import numpy as np
        import jax.numpy as jnp
        from horovod_tpu.runtime import engine_or_none
        from horovod_tpu.runtime.fsdp import _note_prefetch
        from horovod_tpu.runtime.sharded import FlatSharder

        leaves, treedef = jax.tree.flatten(grads)
        if leaves and _is_traced(leaves[0]):
            raise RuntimeError(
                "fsdp=True is the host-driven (eager/DCN) path; inside "
                "jit shard params with the mesh's 'fsdp' axis instead "
                "(parallel/mesh.py)")
        plane = self._fsdp_plane
        if plane is None:
            raise RuntimeError(
                "fsdp DistributedOptimizer.update() before init(): the "
                "unit layout is anchored at init(params)")
        p_leaves = ([np.asarray(leaf) for leaf in jax.tree.leaves(params)]
                    if params is not None else None)
        g_leaves = [np.asarray(leaf) for leaf in leaves]
        eng = engine_or_none()
        new_states = [None] * plane.n_units
        unit_updates = [None] * plane.n_units
        ag_handles = {}
        try:
            # Backward cascade: enqueue EVERY unit's reducescatter up
            # front, last unit first (its grads finish first in a real
            # vjp), priority band = unit index so the units the next
            # forward needs first win the wire.
            for i in reversed(range(plane.n_units)):
                _, idxs = self._fsdp_groups[i]
                plane.reduce_grads(i, [g_leaves[j] for j in idxs])
            for i in range(plane.n_units):
                u = plane.units[i]
                g_shard = plane.wait_grads(i)
                p_shard = None
                if p_leaves is not None:
                    _, idxs = self._fsdp_groups[i]
                    p_shard = jnp.asarray(FlatSharder.slice_flat(
                        [p_leaves[j] for j in idxs],
                        u.sharder.offset, u.sharder.count, np.float32))
                upd, new_states[i] = self._inner.update(
                    jnp.asarray(g_shard), state[i], p_shard, **extra)
                upd = np.asarray(upd, dtype=np.float32)
                if eng is None:
                    unit_updates[i] = upd
                else:
                    # Band-0 update allgather: in flight while LATER
                    # units' reductions drain and shards update.
                    ag_handles[i] = eng.enqueue_allgather(
                        upd, name=f"{plane._wire_name}.u{i}.agu",
                        priority=0)
            for i in sorted(ag_handles):
                # Overlap accounting: the gather was free iff it landed
                # before this drain reached it.
                _note_prefetch(eng.poll(ag_handles[i]))
                unit_updates[i] = np.asarray(
                    eng.synchronize(ag_handles.pop(i)))
        except BaseException:
            # Drain hygiene: never strand a handle (StepSkipped on one
            # unit must not leave the others' buffers in flight).
            plane.drain()
            for i in list(ag_handles):
                try:
                    eng.synchronize(ag_handles.pop(i))
                except BaseException:
                    pass
            raise
        out_leaves = [None] * len(leaves)
        for i, (_, idxs) in enumerate(self._fsdp_groups):
            u = plane.units[i]
            outs = FlatSharder.unflatten(unit_updates[i], u.shapes)
            for j, o in zip(idxs, outs):
                out_leaves[j] = jnp.asarray(o)
        plane.step()
        updates = jax.tree_util.tree_unflatten(treedef, out_leaves)
        return updates, tuple(new_states)

    # Make it quack like an optax.GradientTransformation namedtuple.
    def __iter__(self):
        return iter((self.init, self.update))


def _fsdp_unit_groups(params, fsdp_units=None):
    """FSDP unit boundaries from the param tree's TOP-LEVEL structure:
    ``[(unit_name, [global leaf indices])]`` in jax flatten order.  A
    dict tree gets one unit per key (jax flattens dicts key-sorted); a
    list/tuple one per element; anything else is a single unit.
    ``fsdp_units=[["embed", "lm_head"], ["blocks"]]`` overrides with
    explicit key groups — every top-level key exactly once (tied layers
    that must share a window, or tiny layers worth coalescing)."""
    if isinstance(params, dict):
        try:
            keys = sorted(params)
        except TypeError as e:
            raise TypeError(
                "fsdp=True needs sortable top-level dict keys (jax's own "
                "dict flatten order)") from e
        spans, off = {}, 0
        for k in keys:
            cnt = len(jax.tree_util.tree_leaves(params[k]))
            spans[k] = list(range(off, off + cnt))
            off += cnt
        if fsdp_units is not None:
            groups, seen = [], set()
            for gi, group in enumerate(fsdp_units):
                idxs = []
                for k in group:
                    if k not in spans:
                        raise ValueError(
                            f"fsdp_units names unknown top-level key "
                            f"{k!r} (have {sorted(map(str, keys))})")
                    if k in seen:
                        raise ValueError(
                            f"fsdp_units lists key {k!r} twice")
                    seen.add(k)
                    idxs.extend(spans[k])
                if idxs:
                    groups.append(("+".join(map(str, group)), idxs))
            missing = [str(k) for k in keys if k not in seen and spans[k]]
            if missing:
                raise ValueError(
                    f"fsdp_units must cover every top-level key; "
                    f"missing {missing}")
            return groups
        return [(str(k), spans[k]) for k in keys if spans[k]]
    if isinstance(params, (list, tuple)):
        groups, off = [], 0
        for i, sub in enumerate(params):
            cnt = len(jax.tree_util.tree_leaves(sub))
            if cnt:
                groups.append((str(i), list(range(off, off + cnt))))
            off += cnt
        if fsdp_units is not None:
            raise ValueError(
                "fsdp_units grouping needs a dict param tree")
        return groups
    n = len(jax.tree_util.tree_leaves(params))
    return [("all", list(range(n)))]


def broadcast_parameters(params, root_rank=0, *, axis_name=None):
    """Return ``params`` with every leaf replaced by root's value.

    Reference parity: ``broadcast_global_variables`` / torch
    ``broadcast_parameters`` (tensorflow/__init__.py:90-98,
    torch/__init__.py:153-182).  Functional: returns the synced pytree.

    On tracers this is an in-jit masked-psum broadcast; on concrete arrays it
    is a cross-process broadcast through the runtime (host path), which at
    ``size()==1`` is the identity.
    """
    leaves = jax.tree.leaves(params)
    if leaves and _is_traced(leaves[0]):
        if axis_name is None:
            axis_name = _mesh.data_axes() or ("data",)

        return jax.tree.map(
            lambda leaf: _cops.broadcast(leaf, root_rank,
                                         axis_name=axis_name),
            params)
    from horovod_tpu.runtime import eager

    return jax.tree.map(
        lambda x: eager.broadcast(x, root_rank=root_rank), params
    )


def broadcast_optimizer_state(opt_state, root_rank=0, *, axis_name=None):
    """Broadcast optimizer state from root (reference torch/__init__.py:
    185-301).  Optax states are pytrees of arrays, so no scalar
    tensor-ization dance is needed — one broadcast a leaf covers it."""
    return broadcast_parameters(opt_state, root_rank, axis_name=axis_name)


# ---------------------------------------------------------------------------
# Train-step builder (the minimum end-to-end slice, SURVEY.md §7 step 4)
# ---------------------------------------------------------------------------

def make_train_step(loss_fn: Callable, optimizer, mesh: Optional[Mesh] = None,
                    *, donate=True, has_aux=False):
    """Build a jitted SPMD train step: shard batch over data axes, compute
    grads, all-reduce them leaf by leaf, apply the optimizer.

    ``loss_fn(params, batch) -> scalar loss``, or with ``has_aux=True``
    ``loss_fn(params, aux_state, batch) -> (loss, new_aux_state)`` where
    ``aux_state`` is non-differentiated model state (e.g. batch-norm
    statistics), averaged across the data axes each step (cross-replica
    batch norm).  ``optimizer`` may be a plain optax transformation (it will
    be wrapped) or a ``DistributedOptimizer``.

    Returns ``step(params, opt_state, batch) -> (params, opt_state, loss)``
    (with ``has_aux``: ``step(params, opt_state, aux_state, batch) ->
    (params, opt_state, aux_state, loss)``); params/opt_state replicated,
    batch sharded on the data axes.

    ``step`` is the ``jax.jit`` object itself (``.lower``, no Python runs
    around a call).  JAX reports it as ``hvd.TRAIN_STEP_PROGRAM``
    (``hvd.compile_log(hvd.TRAIN_STEP_PROGRAM)``; ``jit_hvd_train_step`` in
    a profile), and its device operations carry the scopes of
    ``horovod_tpu/common/scopes.py`` in their names: ``hvd.loss`` (forward
    under ``jvp``, backward under ``transpose``), ``hvd.optimizer``,
    ``hvd.apply``, and ``hvd.allreduce.<axes>`` (docs/timeline.md).
    """
    mesh = mesh or default_mesh()
    axes = _mesh.data_axes(mesh) or mesh.axis_names
    if not isinstance(optimizer, DistributedOptimizer):
        optimizer = DistributedOptimizer(optimizer, axis_name=axes)
    elif optimizer._axis_name is None:
        # Bind reduction to THIS mesh's data-like axes — resolving from the
        # thread-local default mesh would silently skip e.g. 'fsdp'.
        optimizer = optimizer.with_axis_name(axes)

    import optax

    def _loss_and_grads(params, *rest):
        """``jax.value_and_grad(loss_fn, has_aux=has_aux)(params, *rest)``
        as the pair it is, the forward pass and its pullback on one, with
        the open span (``hvd.loss``) told when the first was traced: its
        flag ``forward_seconds`` (``common/scopes.py``)."""
        loss, pullback, *aux = jax.vjp(
            lambda p: loss_fn(p, *rest), params, has_aux=has_aux)
        _scopes.stamp(_scopes.FORWARD_SECONDS)
        if jnp.shape(loss) or not jnp.issubdtype(loss.dtype, jnp.floating):
            raise TypeError(
                f"loss_fn must return a real scalar loss, and gave "
                f"{jax.typeof(loss).str_short()}")
        (grads,) = pullback(jnp.ones_like(loss))
        return (loss, *aux), grads

    def _sharded_step(params, opt_state, batch):
        with _scopes.scope(_scopes.LOSS):
            (loss,), grads = _loss_and_grads(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        with _scopes.scope(_scopes.APPLY):
            params = optax.apply_updates(params, updates)
        loss = _cops.allreduce(loss, axis_name=axes, op=Average)
        return params, opt_state, loss

    def _sharded_step_aux(params, opt_state, aux_state, batch):
        with _scopes.scope(_scopes.LOSS):
            (loss, aux_state), grads = _loss_and_grads(
                params, aux_state, batch)
        with _scopes.scope(_scopes.AUX_ALLREDUCE):
            aux_state = jax.tree.map(
                lambda x: _cops.allreduce(x, axis_name=axes, op=Average)
                if _is_inexact(x) else x,
                aux_state,
            )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        with _scopes.scope(_scopes.APPLY):
            params = optax.apply_updates(params, updates)
        loss = _cops.allreduce(loss, axis_name=axes, op=Average)
        return params, opt_state, aux_state, loss

    batch_spec = PartitionSpec(axes)
    replicated = PartitionSpec()
    n_state = 3 if has_aux else 2
    # check_vma=False because this step implements the Horovod pattern —
    # an EXPLICIT grad psum in DistributedOptimizer.update — whereas
    # VMA-aware AD would itself psum the cotangents of the replicated
    # params (double-reduction).  pipeline_apply composes with this
    # builder: its broadcast-from-last-stage pins its own vjp, so it
    # differentiates identically with VMA checking on or off
    # (parallel/pipeline.py).
    step = jax.shard_map(
        _sharded_step_aux if has_aux else _sharded_step,
        mesh=mesh,
        in_specs=(replicated,) * n_state + (batch_spec,),
        out_specs=(replicated,) * n_state + (replicated,),
        check_vma=False,
    )
    # One name for both bodies: what JAX reports the program under
    # (hvd.compile_log(), the profiler's XLA Modules line).
    step.__name__ = step.__qualname__ = _scopes.TRAIN_STEP_PROGRAM
    donate_args = tuple(range(n_state)) if donate else ()
    return jax.jit(step, donate_argnums=donate_args)


def _is_inexact(x) -> bool:
    import jax.numpy as jnp

    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)


# The import's two spans, from stamps of the clock: the first line of the
# package's ``__init__`` to this one, the last of this file, and inside it
# what ``parallel/seq.py`` pulled in (the model zoo, flax, Pallas).  Where
# the process imported ``horovod_tpu`` earlier for another reason the outer
# span holds what ran between, too.
from horovod_tpu import IMPORT_BEGAN as _IMPORT_BEGAN  # noqa: E402
from horovod_tpu.parallel.seq import MODELS_IMPORTED as _MODELS_IMPORTED  # noqa: E402,E501

_add_span(_scopes.IMPORT_MODELS, *_MODELS_IMPORTED, parent=_add_span(
    _scopes.IMPORT, _IMPORT_BEGAN, time.perf_counter(),
    jax_was_imported=_JAX_WAS_IMPORTED))
