"""The rotary rotation: one lane-local Mosaic pass, or its ``jnp`` body.

``rotate`` (what ``models/llama.py::apply_rope`` calls) turns interleaved
pairs ``(x[2i], x[2i+1])`` by the angle of their position.  Written in
``jnp`` (``_rotate_plain``) it takes the pairs apart with stride-2 slices on
the lane axis, which XLA:TPU compiles to a gather (its transpose: a
scatter-add) and to copies of q and k either side of it: XLA has no lane
rotate.  Mosaic has (``pltpu.roll``, the XLU), so in ``rotate_pairs`` a
pair's partner comes from its neighbouring lane and nothing leaves the layout
the projection's matmul wrote: it reads and writes the ``[B, S, H * D]`` view
that the flash calls index heads in (``ops/flash_attention.py``), one pass
over HBM.

The arithmetic is the same, product for product: to float32, ``x1 * c - x2 *
s`` on a pair's even lane and ``x1 * s + x2 * c`` on its odd one, one
rounding to the input's dtype.  On both lanes that is ``x * c + partner * t``
with ``t = -s`` (even) or ``+s`` (odd), so the call takes the two tables
expanded to a head's lanes, ``[S, D]`` float32 each (never ``[S, H * D]``: a
block's heads share them).  The transpose of a rotation is the rotation by
the opposite angle: the same call with ``-sin``, and the tables are the only
residuals.

``rotate`` takes the pass only where its caller says that the trace may hold
Mosaic calls on operands where they lie (``in_place``: q and k on their way
to the flash seam; the partitioner cannot split a Mosaic call, so it is the
caller's choice), and only at widths on the 128-lane tiling: an indexer's 64,
latent attention's 64 rotating dims, and every model with dense or ring
attention keep the ``jnp`` body.  Which a trace took, and why, is noted in
``common/trace_counts.py`` under ``rope.body``.  Off-TPU the call runs in
interpret mode, as the flash calls do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.common import trace_counts as _trace_counts

__all__ = ["rotate", "rotate_pairs"]

# Which body ``rotate`` took, by reason (``common/trace_counts.py``).
BODY = "rope.body"
IN_PLACE = "one Mosaic pass"
NOT_IN_PLACE = "the caller's trace may hold no Mosaic call"
OFF_TILING = "head width off the lane tiling, or no block of rows"

_LANES = 128
# Upper bound on a block of x, counted at four bytes an element (its float32
# values are what the kernel works on): all heads of as many rows.
_BLOCK_BYTES = 2 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pick_rows(s: int, width: int) -> int:
    """The largest block of rows that divides ``s`` and keeps a block of x
    under ``_BLOCK_BYTES``; 0 if none of the tiling's sizes divides it."""
    for rows in (1024, 512, 256, 128, 64, 32, 16):
        if s % rows == 0 and rows * width * 4 <= _BLOCK_BYTES:
            return rows
    return 0


def _rotates_in_place(shape, head_dim: int) -> bool:
    """Whether ``rotate_pairs`` takes ``x [B, S, H * head_dim]``: whole lane
    tiles a head, and a block of rows that divides S."""
    return (len(shape) == 3 and head_dim % _LANES == 0
            and shape[2] % head_dim == 0
            and _pick_rows(shape[1], shape[2]) > 0)


def _kernel(x_ref, cos_ref, sin_ref, o_ref):
    # x_ref, o_ref: [rows, H * D]; cos_ref, sin_ref: [rows, D] float32, the
    # sine signed (-s on a pair's even lane, +s on its odd one).  A tile of
    # 128 lanes at a time: pairs never straddle one.
    rows, width = x_ref.shape
    d = cos_ref.shape[1]
    even = jnp.bitwise_and(jax.lax.broadcasted_iota(
        jnp.int32, (rows, _LANES), 1), 1) == 0
    for at in range(0, width, _LANES):
        lanes = pl.dslice(at, _LANES)
        table = pl.dslice(at % d, _LANES)
        x = x_ref[:, lanes].astype(jnp.float32)
        # roll(x, n)[l] = x[l - n]: the next lane for a pair's first
        # element, the one before for its second.
        partner = jnp.where(even, pltpu.roll(x, _LANES - 1, 1),
                            pltpu.roll(x, 1, 1))
        o_ref[:, lanes] = (x * cos_ref[:, table]
                           + partner * sin_ref[:, table]).astype(o_ref.dtype)


# (Inlined jit: a step traces this once a shape, not once a layer and pass.
# ``interpret`` is static, so the cached trace is of the mode asked for.)
@functools.partial(jax.jit, static_argnames="interpret", inline=True)
def _rotate(x, cos, sin, interpret):
    """x: [B, S, H * D]; cos, sin: [S, D / 2] float32."""
    b, s, width = x.shape
    d = 2 * cos.shape[-1]
    # Each pair's two lanes share its cosine; its sine enters with the sign
    # of the lane's own formula.  [S, D] float32, a head's worth: small
    # beside x, and the same for every layer of a step.
    cos = jnp.repeat(cos.astype(jnp.float32), 2, axis=-1)
    sin = jnp.stack([-sin, sin], axis=-1).astype(jnp.float32).reshape(s, d)
    rows = _pick_rows(s, width)
    block = pl.BlockSpec((None, rows, width), lambda i, j: (j, i, 0))
    # The batch is the inner grid axis, so a block of the tables is fetched
    # once for all of it.
    table = pl.BlockSpec((rows, d), lambda i, j: (i, 0))
    with jax.named_scope(_scopes.ROPE):
        return pl.pallas_call(
            _kernel,
            grid=(s // rows, b),
            in_specs=[block, table, table],
            out_specs=block,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(x, cos, sin)


@jax.custom_vjp
def rotate_pairs(x, cos, sin):
    """Rotate the interleaved pairs of every head of ``x [B, S, H * D]`` by
    ``cos``, ``sin`` ``[S, D / 2]`` (``rope_freqs``): the rotation on the
    layout the projections write and the flash calls read, as one Mosaic
    call under ``hvd.rope``.  ``D % 128 == 0`` and S a multiple of 16
    (``_rotates_in_place``).  Differentiable in x alone: the tables get zero
    cotangents."""
    return _rotate(x, cos, sin, interpret=_interpret())


def _rotate_fwd(x, cos, sin):
    return _rotate(x, cos, sin, interpret=_interpret()), (cos, sin)


def _rotate_bwd(tables, g):
    cos, sin = tables
    return (_rotate(g, cos, -sin, interpret=_interpret()),
            jnp.zeros_like(cos), jnp.zeros_like(sin))


rotate_pairs.defvjp(_rotate_fwd, _rotate_bwd)


def _rotate_plain(x, cos, sin):
    """The rotation in ``jnp``, ``x [B, S, H, D]``: the pairs are taken apart
    by stride-2 slices (a gather and copies to XLA:TPU), turned in float32
    and interleaved again: any width, any partitioning.  The body of every
    path that may hold no Mosaic call, and the tests' yardstick."""
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    r1 = x1 * c - x2 * s
    r2 = x1 * s + x2 * c
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def rotate(x, cos, sin, in_place: bool):
    """Rotate the pairs ``(x[..., ::2], x[..., 1::2])`` of ``x [B, S, H, D]``
    by ``cos``, ``sin`` ``[S, D / 2]``.  ``in_place`` is the caller's word
    that this trace may hold Mosaic calls on operands where they lie: a
    shape ``rotate_pairs`` takes is then turned by its one pass over the
    ``[B, S, H * D]`` view (the same bits, the layout left alone); else,
    and at every other shape, ``_rotate_plain``."""
    B, S, H, D = x.shape
    why = (NOT_IN_PLACE if not in_place else
           None if _rotates_in_place((B, S, H * D), D) else OFF_TILING)
    _trace_counts.note(BODY, why or IN_PLACE)
    if why is None:
        return rotate_pairs(x.reshape(B, S, H * D), cos, sin).reshape(x.shape)
    return _rotate_plain(x, cos, sin)
