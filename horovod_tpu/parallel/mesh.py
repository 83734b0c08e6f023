"""Device-mesh construction and global default-mesh management.

Reference parity: the MPI communicator setup in ``BackgroundThreadLoop``
(``horovod/common/operations.cc:1469-1532``) — world comm, the
``MPI_Comm_split_type(MPI_COMM_TYPE_SHARED)`` *local* comm and the
``MPI_Comm_split(local_rank)`` *cross* comm that power hierarchical
allreduce (operations.cc:1025-1187).

TPU-native design: the communicator hierarchy becomes a ``jax.sharding.Mesh``.
The local/cross split maps onto ICI-within-slice vs DCN-across-slices: when
the devices span several slices we build a *hybrid* device mesh
(``mesh_utils.create_hybrid_device_mesh``) so that the innermost mesh axes
ride ICI and only the outermost crosses DCN — the exact analogue of
NCCL-reduce-scatter → cross-node-MPI-allreduce → NCCL-all-gather, except XLA
inserts the decomposition for us.

Named axes follow the scaling-book convention:
  ``data``    — pure data parallelism (gradient psum)
  ``fsdp``    — data parallelism with sharded params/optimizer state
  ``tensor``  — tensor/model parallelism (activations sharded)
  ``seq``     — sequence/context parallelism (ring attention / all-to-all)
  ``expert``  — expert parallelism for MoE layers
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional, Sequence

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh

__all__ = [
    "AXIS_DATA",
    "AXIS_FSDP",
    "AXIS_TENSOR",
    "AXIS_SEQ",
    "AXIS_EXPERT",
    "build_mesh",
    "data_parallel_mesh",
    "default_mesh",
    "set_default_mesh",
    "use_mesh",
    "mesh_axis_size",
    "data_axes",
]

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_TENSOR = "tensor"
AXIS_SEQ = "seq"
AXIS_EXPERT = "expert"

# Axes over which gradients are reduced (batch-like axes).
_DATA_LIKE_AXES = (AXIS_DATA, AXIS_FSDP)

_state = threading.local()


def _resolve_shape(axes: dict[str, int], n_devices: int) -> dict[str, int]:
    """Fill in a single -1 wildcard so the product equals n_devices."""
    shape = dict(axes)
    wild = [k for k, v in shape.items() if v == -1]
    if len(wild) > 1:
        raise ValueError("at most one mesh axis may be -1")
    fixed = math.prod(v for v in shape.values() if v != -1)
    if wild:
        if n_devices % fixed != 0:
            raise ValueError(
                f"cannot infer axis {wild[0]!r}: {n_devices} devices not "
                f"divisible by {fixed}"
            )
        shape[wild[0]] = n_devices // fixed
    elif fixed != n_devices:
        raise ValueError(
            f"mesh shape {shape} does not cover {n_devices} devices"
        )
    return shape


def build_mesh(
    axes: Optional[dict[str, int]] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    allow_split_physical_axes: bool = False,
) -> Mesh:
    """Build a Mesh with named axes over all (or given) devices.

    ``axes`` maps axis name -> size, with at most one ``-1`` wildcard, e.g.
    ``{"data": -1}`` or ``{"data": -1, "tensor": 4}``.  Axis order is
    significant: later axes are innermost (most-contiguous on ICI), so put
    the most communication-hungry axis (tensor/seq) last.

    Multi-slice topologies get a hybrid mesh whose outermost axes span
    slices (DCN) — the TPU-native "cross communicator".
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axes is None:
        axes = {AXIS_DATA: n}
    shape = _resolve_shape(axes, n)
    names = tuple(shape.keys())
    sizes = tuple(shape[k] for k in names)

    # The DCN granule is the slice, which TPU devices report themselves
    # (CPU/GPU devices have no slice_index: one granule).  mesh_utils
    # orders devices along the physical ICI torus; its errors are real —
    # a reshape in enumeration order would silently drop that order.
    n_slices = len({getattr(d, "slice_index", 0) for d in devices})
    if n_slices > 1:
        # Split each mesh axis into a DCN (across-slice) component and an
        # ICI component, outermost-first, mirroring cross/local comms.
        dcn_left = n_slices
        dcn_shape, ici_shape = [], []
        for s in sizes:
            g = math.gcd(s, dcn_left)
            dcn_shape.append(g)
            ici_shape.append(s // g)
            dcn_left //= g
        if dcn_left != 1:
            raise ValueError(
                f"mesh shape {shape} cannot be laid over {n_slices} slices: "
                "the outer axes must absorb the slice count")
        mesh_devices = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices,
            allow_split_physical_axes=allow_split_physical_axes,
        )
    else:
        mesh_devices = mesh_utils.create_device_mesh(
            sizes, devices=devices,
            allow_split_physical_axes=allow_split_physical_axes,
        )
    return Mesh(mesh_devices, names)


def data_parallel_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """The default Horovod-like topology: every chip on one ``data`` axis."""
    return build_mesh({AXIS_DATA: -1}, devices=devices)


def default_mesh() -> Mesh:
    """Return the active mesh, building a data-parallel one on first use."""
    mesh = getattr(_state, "mesh", None)
    if mesh is None:
        mesh = data_parallel_mesh()
        _state.mesh = mesh
    return mesh


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Context manager installing ``mesh`` as the default."""
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def mesh_axis_size(axis_name, mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or default_mesh()
    if isinstance(axis_name, (tuple, list)):
        return math.prod(mesh.shape[a] for a in axis_name)
    return mesh.shape[axis_name]


def data_axes(mesh: Optional[Mesh] = None) -> tuple[str, ...]:
    """The batch-like axes of ``mesh`` (gradient-reduction axes)."""
    mesh = mesh or default_mesh()
    return tuple(a for a in mesh.axis_names if a in _DATA_LIKE_AXES)
