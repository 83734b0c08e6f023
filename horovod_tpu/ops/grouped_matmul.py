"""A routed layer's grouped product: rows sorted by expert, each group of rows
times its own expert's matrix, forward and backward.

``rows [m, k]`` hold ``sizes[0]`` rows of group 0, then ``sizes[1]`` of group
1 and so on, ``sum(sizes) <= m``; ``w [g, k, n]``::

    out[r] = rows[r] @ w[group of r]                    r < sum(sizes)

and what the rows past the last group read is undefined, in the result and in
the gradient that comes back to ``rows`` (the caller cuts both off,
``models/llama.py::_one_buffer``).  bf16 or float32 operands, float32
accumulation, the result in the dtype of ``rows``.

ONE entry, ``grouped_matmul``, and two bodies, by the rule of
``ops/short_conv.py::convolved``; ``body_counts()`` says which a trace took,
and why:

* **XLA's** (``jax.lax.ragged_dot``): XLA:TPU makes it Mosaic calls of its own
  (``ragged-dot-*``) that visit only tiles that hold rows, and autodiff makes
  the two gradient products more of the same.  Any shape, any backend, any
  partitioning.  Its tiles are chosen by XLA from the widths: alone, forward
  and backward of a layer's chain at each routed cell's own call, it runs at
  22-42 % of the MXU's peak at whole lane tiles (my chip runs, PR 68) and at
  10 % at ``k, n = 2688, 1856`` (21 and 14.5 lane tiles; PR 57; PERF.md §5).
* **Mosaic** (``_mosaic``, one ``jax.custom_vjp``): the grouped matmul that JAX
  ships for the TPU (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` for
  the product and for the rows' gradient, with the matrices read transposed,
  ``tgmm`` for the matrices' gradient), CALLED with the tiles that ``_tiles``
  states for each of the three from the call's shape, not copied: 38-59 % of
  the peak at those same calls, 0.60-0.71 of XLA's time at every one of them.
  A tile that runs over a width's end is masked by the kernel, so no operand
  is padded and parameters, gradients and optimizer state keep their shapes.

The Mosaic body is taken wherever it can be: where the caller says
``in_place`` (the trace is not partitioned: the partitioner cannot split a
Mosaic call of the program's, PERF.md §3.3), a tile of rows divides m and the
backend is a TPU (interpreted, the calls are many times slower than XLA:CPU's
own product; the tests run them so by lifting that last reason).  No width
decides: at none of the eight routed cells' calls, widths of 512 to 3584 in
groups of 320 to 1,536 rows, does XLA's body win alone (PERF.md §5).

What a start pays for it (PERF.md §6, PR 68).  ``gmm`` and ``tgmm`` are
``jax.jit`` s with static tiles, so a kernel is traced once a shape, not once a
layer; but (1) each makes its group metadata for itself, forty-odd small
``jnp`` operations with three search loops among them: most of a cold kernel's
trace on the chip's host, and, in the program, hundreds of small fusions and
three ``while`` s in every computation that holds a call; and (2) ``jax.jit``
keeps its traces by the tracing context too, of which the mesh in scope is a
part: ``None`` in a forward trace, an empty ``AbstractMesh`` in a backward
rule, so a forward kernel that the backward pass runs again is traced again.
Against (1) the kernels are handed ``_group_metadata``, set over the module
global ``make_group_metadata`` that ``gmm`` and ``tgmm`` look up at each call:
the same integers from a dozen fused operations, under one ``jax.jit`` (with
one tile of rows for all six products a start traces it twice, for ``gmm``
and for ``tgmm``).  That one function is the module's own; the kernels stay
megablox's, called.  Against (2) the calls are made under the mesh that is in
scope, said aloud (``_called``).  Both lean on the pinned JAX (0.9.0), and
``tests/test_grouped_matmul.py`` holds the integers to megablox's own and
counts the traces.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox.ops import backend as _megablox

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.common import trace_counts as _trace_counts

__all__ = ["LANES", "grouped_matmul", "body_counts", "metadata_traces",
           "NOT_IN_PLACE", "NO_TPU"]

LANES = 128
# A call's tiles (``_tiles``): of the rows the first of these that divides
# m, one width whole and the other in slices of four lane tiles or of three,
# within a budget of VMEM for the blocks, two buffers each, and the
# accumulator.
_ROW_TILES = (256, 128, 64, 32, 16, 8)
_SLICES = (4 * LANES, 3 * LANES)
_WIDE_TILE = 1024           # of a width too wide to be held whole
_VMEM_BUDGET = 12 * 1024 * 1024

_BODY = "grouped_matmul.body"
_METADATA = "grouped_matmul.metadata"
_MOSAIC = "megablox's calls at stated tiles"
NOT_IN_PLACE = "the attention_fn does not read its operands in place"
_NO_ROW_TILE = "no tile of rows divides the buffer"
NO_TPU = "no TPU: the calls would run interpreted"


def body_counts() -> dict:
    """``{"mosaic": n, "xla": {reason: n}}``: how many traced calls of
    ``grouped_matmul`` took the Mosaic body, and how many
    ``jax.lax.ragged_dot``, by reason.  Process-global, counted once a
    TRACE."""
    xla = _trace_counts.counts(_BODY)
    return {"mosaic": xla.pop(_MOSAIC, 0), "xla": xla}


def metadata_traces() -> dict:
    """``{"gmm": n, "tgmm": n}``: how many times this process traced the
    kernels' group metadata, by the kernel it was for.  A start's price: one
    each, whatever the layers and the passes (the module's docstring)."""
    return _trace_counts.counts(_METADATA)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _row_tile(m: int) -> int:
    return next((tile for tile in _ROW_TILES if m % tile == 0), 0)


def _why_not(m: int, in_place: bool):
    """None where the Mosaic body takes a buffer of m rows, else the reason
    it does not."""
    if not in_place:
        return NOT_IN_PLACE
    if not _row_tile(m):
        return _NO_ROW_TILE
    return NO_TPU if _interpret() else None


def _slice(width: int) -> int:
    """The tile of a sliced width: of ``_SLICES`` the one whose tiles run
    less far over the width's end, the wider where they run as far."""
    return min(width, min(_SLICES, key=lambda s: (-(-width // s) * s, -s)))


def _tiles(m: int, k: int, n: int, itemsize: int, whole: str) -> tuple:
    """``(tm, tk, tn)`` of a megablox call on m rows, k and n wide (``gmm``:
    k contracted, n the result's; ``tgmm``: the result is ``[k, n]``), the
    width named ``whole`` held whole and the other sliced.

    By a sweep on the v5e at 12,288 rows in 8 uneven groups, 2688 x 1856,
    bf16, 77 tilings a call (my chip run, PR 57; PERF.md §5): rows of 256
    beat 512 and 1024 (a group's last tile is half empty on average), and
    the best tiles of all six products keep the contracted width whole in
    ``gmm`` (the accumulator is written once, 0.62-0.81 ms a call where
    tiles of ``(512, 1024, 1024)`` take 0.82-1.11) and the result's last
    width whole in ``tgmm`` (0.74-0.87 where they take 0.97-1.11).  By a
    second at each routed cell's own call (rows of 128, 256, 512; slices of
    256, 384, 512; my chip run, PR 68): rows of 256 again, in groups of 320
    rows as of 1,536, and slices of 512 wherever 384 wastes no less of its
    last tile (0.82-0.95 of the time at 384; 2688 and 1856 are 7 and 4.8
    slices of 384 and keep them, 1.14 of the time at 512)."""
    tm = _row_tile(m)
    tk, tn = (k, _slice(n)) if whole == "k" else (_slice(k), n)
    accumulator = (tm if whole == "k" else tk) * tn
    blocks = tm * tk + tk * tn + tm * tn
    if 2 * itemsize * blocks + 4 * accumulator > _VMEM_BUDGET:
        tk, tn = min(k, _WIDE_TILE), min(n, _WIDE_TILE)
    return tm, tk, tn


# megablox's own, which the tests hold ``_group_metadata`` to.
_MEGABLOX_METADATA = _megablox.make_group_metadata


@functools.partial(jax.jit, static_argnames=(
    "m", "tm", "num_nonzero_groups", "visit_empty_groups"))
def _group_metadata(*, group_sizes, m: int, tm: int, start_group,
                    num_nonzero_groups: int,
                    visit_empty_groups: bool = True):
    """What megablox's ``make_group_metadata`` gives, under its signature:
    ``((group_offsets [g + 1], group_ids [L], m_tile_ids [L]), num_tiles)``,
    int32, ``L = m // tm + g - 1``.  The walk's step i multiplies tile
    ``m_tile_ids[i]`` of the rows with the matrix of group ``group_ids[i]``:
    a tile once for each group that has rows in it, a group once for each
    tile it reaches into (an empty group none, or one where
    ``visit_empty_groups``: ``tgmm`` has its zeros to write), both padded
    with their last index.

    The same integers (``tests/test_grouped_matmul.py`` holds them to
    megablox's own, array for array) from a dozen fused operations: the two
    ``repeat`` s are counts of running sums, the histogram a comparison with
    an iota.  megablox's own is two ``repeat`` s and a ``histogram`` over
    ``searchsorted``: forty-odd operations and three ``while`` s on the
    device in each computation that holds a call (the first buffer and the
    loop's body, forward, recomputed and backward: 867 fusions and 24
    ``while`` s more in the lfm2 cell's step, a second of every warm start
    in reading the executable back; PERF.md §6, PR 68)."""
    _trace_counts.note(_METADATA, "tgmm" if visit_empty_groups else "gmm")
    groups, tiles_m = group_sizes.shape[0], m // tm
    length = tiles_m + groups - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    empty = group_sizes == 0
    tiles = jnp.where(empty, int(visit_empty_groups),
                      (ends + tm - 1) // tm - starts // tm)
    # A tile is walked once by the group that holds its first row, and once
    # more by each group that starts inside it.
    inside = ~empty & (starts % tm != 0)
    if visit_empty_groups:
        inside |= empty
    visits = 1 + jnp.sum(
        jnp.where(inside, starts // tm, tiles_m)[None, :]
        == jnp.arange(tiles_m)[:, None], axis=1, dtype=jnp.int32)

    def repeated(counts, n):
        """``jnp.repeat(arange(n), counts, total_repeat_length=length)``."""
        return jnp.minimum(jnp.sum(
            jnp.cumsum(counts)[None, :] <= jnp.arange(length)[:, None],
            axis=1, dtype=jnp.int32), n - 1)

    group_ids, m_tile_ids = repeated(tiles, groups), repeated(visits, tiles_m)
    # A shard of the groups (none here: ``start_group`` is 0) walks from its
    # first group's first tile on, its own groups' tiles only.
    skipped = jnp.sum(group_ids < start_group)
    group = jnp.arange(groups)
    ours = (group >= start_group) & (group < start_group + num_nonzero_groups)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return ((offsets, jnp.roll(group_ids, -skipped),
             jnp.roll(m_tile_ids, -skipped)),
            jnp.sum(jnp.where(ours, tiles, 0)))


_megablox.make_group_metadata = _group_metadata


def _called(kernel, *operands, **stated):
    """``kernel`` (``gmm`` or ``tgmm``) on ``operands`` under the program's
    span, the mesh in scope said aloud so that a forward trace and a
    backward rule share the kernel's trace."""
    mesh = jax.sharding.get_abstract_mesh()
    with _scopes.span(_scopes.MOSAIC_GROUPED_MATMUL), \
            jax.sharding.use_abstract_mesh(mesh):
        return kernel(*operands, **stated, interpret=_interpret())


@jax.custom_vjp
def _mosaic(rows, w, sizes):
    m, (_, k, n) = rows.shape[0], w.shape
    return _called(_megablox.gmm, rows, w, sizes, rows.dtype,
                   _tiles(m, k, n, rows.dtype.itemsize, "k"))


def _mosaic_fwd(rows, w, sizes):
    return _mosaic(rows, w, sizes), (rows, w, sizes)


def _mosaic_bwd(res, g):
    rows, w, sizes = res
    m, (_, k, n) = rows.shape[0], w.shape
    itemsize = rows.dtype.itemsize
    d_rows = _called(_megablox.gmm, g, w, sizes, rows.dtype,
                     _tiles(m, n, k, itemsize, "k"), transpose_rhs=True)
    # (``tgmm`` takes the rows as ``[k, m]`` and turns them back itself.)
    d_w = _called(_megablox.tgmm, rows.swapaxes(0, 1), g, sizes, w.dtype,
                  _tiles(m, k, n, itemsize, "n"))
    return d_rows, d_w, np.zeros(sizes.shape, jax.dtypes.float0)


_mosaic.defvjp(*_scopes.rules(
    "grouped_matmul._mosaic", _mosaic_fwd, _mosaic_bwd))


def grouped_matmul(rows, w, sizes, in_place: bool = False):
    """``rows [m, k]`` times ``w [g, k, n]`` by groups of ``sizes [g]`` rows
    (int32, their sum at most m): ``[m, n]`` in the dtype of ``rows``.
    ``in_place``: the caller's word that the trace may hold Mosaic calls of
    the program's (``models/llama.py::_reads_in_place``).  Which body a
    trace took, and why, ``body_counts()`` says."""
    why = _why_not(rows.shape[0], in_place)
    _trace_counts.note(_BODY, why or _MOSAIC)
    if why:
        return jax.lax.ragged_dot(rows, w, sizes)
    return _mosaic(rows, w, sizes)
