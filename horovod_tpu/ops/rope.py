"""The rotary rotation, and a head's RMSNorm before it: one lane-local Mosaic
pass, or its ``jnp`` body.

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

A per-head QK-norm (Qwen3's and its descendants', Gemma 3's) sits straight
before the rotation, and as an ``RMSNorm`` over the last axis of ``[B, S, H,
D]`` it has XLA:TPU relay q and k in float32 either side of it (the
projection writes rows by lanes, the norm wants heads by lanes, the pass and
the flash calls the first again: ~56 ms of a 609 ms step in five layers, v5e,
PR 48).  A head of 128 is one lane tile of the view (a head of 256 two), so
with ``scale`` and ``eps`` ``rotate`` norms in the pass
(``norm_rotate_pairs``): the sum of squares is a reduction inside tiles the
pass already holds, ``x * rsqrt(mean + eps) * scale`` in float32, rounded to
the dtype as the module rounds it, then turned: no byte of HBM more.  Its
transpose is one call too, from the kept x (the projection's output): the
cotangent turned back, then dx and the scale's gradient as float32 partial
sums a grid step.  The answer is ``_norm_plain`` then ``_rotate_plain``'s up
to the order of a head's float32 sum.

``rotate`` takes the pass only where its caller says that the trace may hold
Mosaic calls on operands where they lie (``in_place``: q and k on their way
to the flash seam; the partitioner cannot split a Mosaic call, so it is the
caller's choice), and only at widths on the 128-lane tiling: an indexer's 64,
latent attention's 64 rotating dims, and every model with dense or ring
attention keep the ``jnp`` bodies.  Which a trace took, and why, is noted in
``common/trace_counts.py`` under ``rope.body``.  Off-TPU the calls run in
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

__all__ = ["rotate", "rotate_pairs", "norm_rotate_pairs"]

# Which body ``rotate`` took, by reason (``common/trace_counts.py``).
BODY = "rope.body"
IN_PLACE = "one Mosaic pass"
NORMED = "normed in the pass"
NORM_OVER_ALL = "the norm is over all of a token's heads, not a head's lanes"
NO_TABLES = "no tables: normed and not turned"
NOT_IN_PLACE = "the caller's trace may hold no Mosaic call"
OFF_TILING = "head width off the lane tiling, or no block of rows"

_LANES = 128
# Upper bound on a block of x: all heads of as many rows.  The plain pass
# counts four bytes an element (its float32 values are what the kernel works
# on); the normed pass the dtype's own, so twice the rows in bf16, which is
# what its three blocks a backward step (g, x, dx; each buffered twice) leave
# room for under the default scoped VMEM, and a tenth faster (v5e, PR 48).
_BLOCK_BYTES = 2 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pick_rows(s: int, width: int, itemsize: int = 4) -> int:
    """The largest block of rows that divides ``s`` and keeps a block of x
    under ``_BLOCK_BYTES`` at ``itemsize`` bytes an element; 0 if none of
    the tiling's sizes divides it."""
    for rows in (1024, 512, 256, 128, 64, 32, 16):
        if s % rows == 0 and rows * width * itemsize <= _BLOCK_BYTES:
            return rows
    return 0


def _rotates_in_place(shape, head_dim: int) -> bool:
    """Whether ``rotate_pairs`` takes ``x [B, S, H * head_dim]``: whole lane
    tiles a head, and a block of rows that divides S."""
    return (len(shape) == 3 and head_dim % _LANES == 0
            and shape[2] % head_dim == 0
            and _pick_rows(shape[1], shape[2]) > 0)


def _even_lanes(rows):
    """Which lanes of a ``[rows, 128]`` tile hold a pair's first element."""
    return jnp.bitwise_and(jax.lax.broadcasted_iota(
        jnp.int32, (rows, _LANES), 1), 1) == 0


def _partner(x, even):
    # roll(x, n)[l] = x[l - n]: the next lane for a pair's first element,
    # the one before for its second.
    return jnp.where(even, pltpu.roll(x, _LANES - 1, 1), pltpu.roll(x, 1, 1))


def _kernel(x_ref, cos_ref, sin_ref, o_ref):
    # x_ref, o_ref: [rows, H * D]; cos_ref, sin_ref: [rows, D] float32, the
    # sine signed (-s on a pair's even lane, +s on its odd one).  A tile of
    # 128 lanes at a time: pairs never straddle one.
    rows, width = x_ref.shape
    d = cos_ref.shape[1]
    even = _even_lanes(rows)
    for at in range(0, width, _LANES):
        lanes = pl.dslice(at, _LANES)
        table = pl.dslice(at % d, _LANES)
        x = x_ref[:, lanes].astype(jnp.float32)
        partner = _partner(x, even)
        o_ref[:, lanes] = (x * cos_ref[:, table]
                           + partner * sin_ref[:, table]).astype(o_ref.dtype)


def _tables(cos, sin, s, d):
    """``cos``, ``sin`` ``[S, D / 2]`` on a head's lanes, ``[S, D]`` float32:
    each pair's two lanes share its cosine; its sine enters with the sign of
    the lane's own formula.  A head's worth: small beside x, and the same
    for every layer of a step."""
    cos = jnp.repeat(cos.astype(jnp.float32), 2, axis=-1)
    sin = jnp.stack([-sin, sin], axis=-1).astype(jnp.float32).reshape(s, d)
    return cos, sin


def _specs(b, s, width, d, itemsize=4):
    """(grid, a block of x, a block of the tables).  The batch is the inner
    grid axis, so a block of the tables is fetched once for all of it."""
    rows = _pick_rows(s, width, itemsize)
    return ((s // rows, b),
            pl.BlockSpec((None, rows, width), lambda i, j: (j, i, 0)),
            pl.BlockSpec((rows, d), lambda i, j: (i, 0)))


_PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"))


# (Inlined jit: a step traces this once a shape, not once a layer and pass.
# ``interpret`` is static, so the cached trace is of the mode asked for.)
@functools.partial(jax.jit, static_argnames="interpret", inline=True)
def _rotate(x, cos, sin, interpret):
    """x: [B, S, H * D]; cos, sin: [S, D / 2] float32."""
    b, s, width = x.shape
    d = 2 * cos.shape[-1]
    grid, block, table = _specs(b, s, width, d)
    cos, sin = _tables(cos, sin, s, d)
    with _scopes.scope(_scopes.ROPE), _scopes.span(_scopes.MOSAIC_ROPE):
        return pl.pallas_call(
            _kernel,
            grid=grid,
            in_specs=[block, table, table],
            out_specs=block,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=_PARALLEL,
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


rotate_pairs.defvjp(*_scopes.rules("rotate_pairs", _rotate_fwd, _rotate_bwd))


# -- the norm in the pass ------------------------------------------------------
#
# A head of 128 is one lane tile of the [B, S, H * D] view (a head of 256
# two), so a per-head RMSNorm before the rotation is a reduction inside tiles
# the pass already holds: x / rms(x) * scale in float32, rounded to the
# model's dtype as ``_norm_plain`` rounds it, then turned.  The sum is the
# XLU's lane reduction, a head's tiles added up first; as products with a
# block of ones on the MXU (three bf16 pieces) the forward call is 5 % faster
# and the backward call no faster (v5e, PR 48), so there is one form.

def _head_sum(tiles):
    """The sum over a head's lanes ``[rows, 1]`` of its tiles ``[rows,
    128]`` each: the tiles added up first, one lane reduction a head."""
    return jnp.sum(functools.reduce(jnp.add, tiles), axis=-1, keepdims=True)


def _each_head(width, d, head, carry=None):
    """``head(the lanes of its tiles, carry)`` for every head of a block
    ``width`` wide, in a loop: one copy of the body in the program whatever
    the count of heads, so a step that holds the call thirty times traces
    and lowers it at a thirtieth of the unrolled body's cost (``setup_s``
    +2.7 s warm unrolled, v5e, PR 48)."""
    def step(h, carry):
        return head([pl.ds(pl.multiple_of(h * d + at, _LANES), _LANES)
                     for at in range(0, d, _LANES)], carry)

    return jax.lax.fori_loop(0, width // d, step, carry)


def _norm_kernel(x_ref, scale_ref, cos_ref, sin_ref, o_ref, *, eps):
    # As ``_kernel``, with scale_ref [1, D] float32 the norm's scale.
    rows, width = x_ref.shape
    d = cos_ref.shape[1]
    table = [pl.dslice(at, _LANES) for at in range(0, d, _LANES)]
    even = _even_lanes(rows)

    def head(lanes, _):
        x = [x_ref[:, at].astype(jnp.float32) for at in lanes]
        inv = jax.lax.rsqrt(_head_sum([v * v for v in x]) * (1.0 / d) + eps)
        for v, at, t in zip(x, lanes, table):
            n = (v * inv * scale_ref[:, t]).astype(
                o_ref.dtype).astype(jnp.float32)
            o_ref[:, at] = (n * cos_ref[:, t] + _partner(n, even)
                            * sin_ref[:, t]).astype(o_ref.dtype)

    _each_head(width, d, head)


def _norm_bwd_kernel(g_ref, x_ref, scale_ref, cos_ref, sin_ref, dx_ref,
                     ds_ref, *, eps):
    # g_ref: the cotangent of the pass's output; sin_ref signed for the
    # opposite angle.  dx_ref as x_ref; ds_ref [8, D] float32: this block's
    # share of the scale's gradient, eight partial sums a lane.
    rows, width = x_ref.shape
    d = cos_ref.shape[1]
    table = [pl.dslice(at, _LANES) for at in range(0, d, _LANES)]
    even = _even_lanes(rows)

    def head(lanes, ds):
        x = [x_ref[:, at].astype(jnp.float32) for at in lanes]
        inv = jax.lax.rsqrt(_head_sum([v * v for v in x]) * (1.0 / d) + eps)
        n = [v * inv for v in x]
        g = []
        for at, t in zip(lanes, table):
            v = g_ref[:, at].astype(jnp.float32)
            # The cotangent turned back, rounded as the rotation's own
            # transpose rounds it.
            g.append((v * cos_ref[:, t] + _partner(v, even) * sin_ref[:, t]
                      ).astype(dx_ref.dtype).astype(jnp.float32))
        dn = [v * scale_ref[:, t] for v, t in zip(g, table)]
        mean = _head_sum([a * b for a, b in zip(dn, n)]) * (1.0 / d)
        for at, dn_, n_ in zip(lanes, dn, n):
            dx_ref[:, at] = (inv * (dn_ - n_ * mean)).astype(dx_ref.dtype)
        # (Eight partial sums a lane and head: the carry stays a register.)
        return tuple(acc + (a * b).reshape(rows // 8, 8, _LANES).sum(axis=0)
                     for acc, a, b in zip(ds, g, n))

    ds = _each_head(width, d, head, tuple(
        jnp.zeros((8, _LANES), jnp.float32) for _ in table))
    for t, part in zip(table, ds):
        ds_ref[:, t] = part


@functools.partial(jax.jit, static_argnames=("eps", "interpret"), inline=True)
def _norm_rotate(x, scale, cos, sin, eps, interpret):
    """x: [B, S, H * D]; scale: [D] float32; cos, sin: [S, D / 2]."""
    b, s, width = x.shape
    d = 2 * cos.shape[-1]
    grid, block, table = _specs(b, s, width, d, x.dtype.itemsize)
    cos, sin = _tables(cos, sin, s, d)
    scale = scale.astype(jnp.float32).reshape(1, d)
    with _scopes.scope(_scopes.ROPE), _scopes.span(_scopes.MOSAIC_ROPE):
        return pl.pallas_call(
            functools.partial(_norm_kernel, eps=eps),
            grid=grid,
            in_specs=[block, pl.BlockSpec((1, d), lambda i, j: (0, 0)),
                      table, table],
            out_specs=block,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=_PARALLEL,
            interpret=interpret,
        )(x, scale, cos, sin)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"), inline=True)
def _norm_rotate_bwd(g, x, scale, cos, sin, eps, interpret):
    """(dx, dscale) of ``_norm_rotate`` at x for the cotangent g."""
    b, s, width = x.shape
    d = 2 * cos.shape[-1]
    grid, block, table = _specs(b, s, width, d, x.dtype.itemsize)
    cos, sin = _tables(cos, -sin, s, d)
    with _scopes.scope(_scopes.ROPE):
        call = pl.pallas_call(
            functools.partial(_norm_bwd_kernel, eps=eps),
            grid=grid,
            in_specs=[block, block, pl.BlockSpec((1, d), lambda i, j: (0, 0)),
                      table, table],
            out_specs=[block, pl.BlockSpec((None, None, 8, d),
                                           lambda i, j: (i, j, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct(grid + (8, d), jnp.float32)],
            compiler_params=_PARALLEL,
            interpret=interpret,
        )
        with _scopes.span(_scopes.MOSAIC_ROPE):
            dx, ds = call(g, x, scale.astype(jnp.float32).reshape(1, d),
                          cos, sin)
        return dx, ds.sum(axis=(0, 1, 2)).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def norm_rotate_pairs(x, scale, cos, sin, eps):
    """``rotate_pairs`` of every head's RMSNorm: ``x [B, S, H * D]`` normed
    over each head's D lanes (``x / rms(x) * scale``, ``scale [D]``, in
    float32 and rounded to x's dtype) and then turned, as one Mosaic call
    under ``hvd.rope``; its transpose one call too, from the kept x: dx and
    the scale's gradient (float32 partial sums a grid step, added up
    outside), so no float32 array of x's size is written either way."""
    return _norm_rotate(x, scale, cos, sin, eps=eps, interpret=_interpret())


def _norm_rotate_pairs_fwd(x, scale, cos, sin, eps):
    return (_norm_rotate(x, scale, cos, sin, eps=eps, interpret=_interpret()),
            (x, scale, cos, sin))


def _norm_rotate_pairs_bwd(eps, kept, g):
    x, scale, cos, sin = kept
    dx, ds = _norm_rotate_bwd(g, x, scale, cos, sin, eps=eps,
                              interpret=_interpret())
    return dx, ds, jnp.zeros_like(cos), jnp.zeros_like(sin)


norm_rotate_pairs.defvjp(*_scopes.rules(
    "norm_rotate_pairs", _norm_rotate_pairs_fwd, _norm_rotate_pairs_bwd))


def _norm_plain(x, scale, eps):
    """``models/llama.py::RMSNorm``'s arithmetic over the last axis of x:
    float32, one rounding to x's dtype."""
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                              + eps)
    return (x32 * scale).astype(x.dtype)


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


def rotate(x, cos, sin, in_place: bool, scale=None, eps=None):
    """Rotate the pairs ``(x[..., ::2], x[..., 1::2])`` of ``x [B, S, H, D]``
    by ``cos``, ``sin`` ``[S, D / 2]``; with ``scale [D]`` and ``eps`` each
    head's RMSNorm first (``x / rms(x) * scale`` in float32, one rounding to
    x's dtype between the two).  ``in_place`` is the caller's word that this
    trace may hold Mosaic calls on operands where they lie: a shape
    ``rotate_pairs`` takes is then turned, and normed where asked, by its
    one pass over the ``[B, S, H * D]`` view (the same bits up to the order
    of the norm's sum, the layout left alone); else, and at every other
    shape, ``_norm_plain`` and ``_rotate_plain``."""
    B, S, H, D = x.shape
    why = (NO_TABLES if cos is None else NOT_IN_PLACE if not in_place else
           None if _rotates_in_place((B, S, H * D), D) else OFF_TILING)
    _trace_counts.note(BODY, why or (IN_PLACE if scale is None else NORMED))
    if why is not None:
        if scale is not None:
            x = _norm_plain(x, scale, eps)
        return x if cos is None else _rotate_plain(x, cos, sin)
    x = x.reshape(B, S, H * D)
    if scale is None:
        return rotate_pairs(x, cos, sin).reshape(B, S, H, D)
    return norm_rotate_pairs(x, scale, cos, sin, eps).reshape(B, S, H, D)
