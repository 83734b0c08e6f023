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
  partitioning.  At widths that are powers of two it runs a product alone at
  45 % of the MXU's peak; its tiles are chosen by XLA from the widths, and at
  ``k, n = 2688, 1856`` (21 and 14.5 lane tiles) it runs at 12 %, no faster
  with 1856 zero-padded to 1920 = 15 tiles (11 %), at 25 % padded to 2048 (my
  chip runs, PR 57; PERF.md §5).
* **Mosaic** (``_mosaic``, one ``jax.custom_vjp``): the grouped matmul that JAX
  ships for the TPU (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` for
  the product and for the rows' gradient, with the matrices read transposed,
  ``tgmm`` for the matrices' gradient), CALLED with the tiles that ``_tiles``
  states for each of the three from the call's shape, not copied.  A tile that
  runs over a width's end is masked by the kernel, so no operand is padded and
  parameters, gradients and optimizer state keep their shapes.

The Mosaic body is taken where the caller says ``in_place`` (the trace is not
partitioned: the partitioner cannot split a Mosaic call of the program's,
PERF.md §3.3), a width of ``w`` is no whole number of lane tiles, a tile of
rows divides m and the backend is a TPU (interpreted, the calls are many times
slower than XLA:CPU's own product; the tests run them so by lifting that last
reason).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox.ops import backend as _megablox

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.common import trace_counts as _trace_counts

__all__ = ["LANES", "grouped_matmul", "body_counts", "NOT_IN_PLACE",
           "WHOLE_TILES", "NO_TPU"]

LANES = 128
# A call's tiles (``_tiles``): of the rows the first of these that divides
# m, one width whole and the other in slices of three lane tiles, within a
# budget of VMEM for the blocks, two buffers each, and the accumulator.
_ROW_TILES = (256, 128, 64, 32, 16, 8)
_SLICE = 3 * LANES
_WIDE_TILE = 1024           # of a width too wide to be held whole
_VMEM_BUDGET = 12 * 1024 * 1024

_BODY = "grouped_matmul.body"
_MOSAIC = "megablox's calls at stated tiles"
NOT_IN_PLACE = "the attention_fn does not read its operands in place"
WHOLE_TILES = "both widths are whole lane tiles"
_NO_ROW_TILE = "no tile of rows divides the buffer"
NO_TPU = "no TPU: the calls would run interpreted"


def body_counts() -> dict:
    """``{"mosaic": n, "xla": {reason: n}}``: how many traced calls of
    ``grouped_matmul`` took the Mosaic body, and how many
    ``jax.lax.ragged_dot``, by reason.  Process-global, counted once a
    TRACE."""
    xla = _trace_counts.counts(_BODY)
    return {"mosaic": xla.pop(_MOSAIC, 0), "xla": xla}


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _row_tile(m: int) -> int:
    return next((tile for tile in _ROW_TILES if m % tile == 0), 0)


def _why_not(m: int, k: int, n: int, in_place: bool):
    """None where the Mosaic body takes ``[m, k] x [g, k, n]``, else the
    reason it does not."""
    if not in_place:
        return NOT_IN_PLACE
    if k % LANES == 0 and n % LANES == 0:
        return WHOLE_TILES
    if not _row_tile(m):
        return _NO_ROW_TILE
    return NO_TPU if _interpret() else None


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
    width whole in ``tgmm`` (0.74-0.87 where they take 0.97-1.11)."""
    tm = _row_tile(m)
    tk, tn = (k, min(_SLICE, n)) if whole == "k" else (min(_SLICE, k), n)
    accumulator = (tm if whole == "k" else tk) * tn
    blocks = tm * tk + tk * tn + tm * tn
    if 2 * itemsize * blocks + 4 * accumulator > _VMEM_BUDGET:
        tk, tn = min(k, _WIDE_TILE), min(n, _WIDE_TILE)
    return tm, tk, tn


@jax.custom_vjp
def _mosaic(rows, w, sizes):
    m, (_, k, n) = rows.shape[0], w.shape
    with _scopes.span(_scopes.MOSAIC_GROUPED_MATMUL):
        return _megablox.gmm(
            rows, w, sizes, rows.dtype,
            _tiles(m, k, n, rows.dtype.itemsize, "k"),
            interpret=_interpret())


def _mosaic_fwd(rows, w, sizes):
    return _mosaic(rows, w, sizes), (rows, w, sizes)


def _mosaic_bwd(res, g):
    rows, w, sizes = res
    m, (_, k, n) = rows.shape[0], w.shape
    itemsize = rows.dtype.itemsize
    with _scopes.span(_scopes.MOSAIC_GROUPED_MATMUL):
        d_rows = _megablox.gmm(
            g, w, sizes, rows.dtype, _tiles(m, n, k, itemsize, "k"),
            transpose_rhs=True, interpret=_interpret())
        # (``tgmm`` takes the rows as ``[k, m]`` and turns them back itself.)
        d_w = _megablox.tgmm(
            rows.swapaxes(0, 1), g, sizes, w.dtype,
            _tiles(m, k, n, itemsize, "n"), interpret=_interpret())
    return d_rows, d_w, np.zeros(sizes.shape, jax.dtypes.float0)


_mosaic.defvjp(*_scopes.rules(
    "grouped_matmul._mosaic", _mosaic_fwd, _mosaic_bwd))


def grouped_matmul(rows, w, sizes, in_place: bool = False):
    """``rows [m, k]`` times ``w [g, k, n]`` by groups of ``sizes [g]`` rows
    (int32, their sum at most m): ``[m, n]`` in the dtype of ``rows``.
    ``in_place``: the caller's word that the trace may hold Mosaic calls of
    the program's (``models/llama.py::_reads_in_place``).  Which body a
    trace took, and why, ``body_counts()`` says."""
    why = _why_not(rows.shape[0], *w.shape[1:], in_place)
    _trace_counts.note(_BODY, why or _MOSAIC)
    if why:
        return jax.lax.ragged_dot(rows, w, sizes)
    return _mosaic(rows, w, sizes)
