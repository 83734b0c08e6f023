"""What a Mamba-2 layer does between its scan and ``out_proj``, the skip,
the gate and the grouped RMS norm, as one Mosaic pass forward and one
backward.

With y the scan's output, u the filter's, z the gate's channels, D one
scalar a head (``d``, spread to the head's lanes) and w the norm's weight,
all over C = ``heads * head_dim`` channels, G groups of ``C / G`` lanes::

    t = y + D u                     the skip
    g = t silu(z)                   the gate, BEFORE the norm
    out = g rsqrt(mean_group(g g) + eps) w

Written in ``jnp`` (``skipped`` rounded to u's dtype, then ``_gate_then_norm``,
the gate and the norm in float32 under a ``jax.checkpoint``: what
``models/llama.py::Mamba2`` held before this module, and what every path that
may hold no Mosaic call still runs) XLA:TPU makes several float32 fusions
over ``[B, S, C]`` of it and, since the filter became a Mosaic call whose
result is row-major while the scan's output is not, three float32 relayouts:
77 ms of a 545 ms step at 2 x 8192 x 4096 in four layers, where the bytes of
the bf16 tensors allow about ten (PERF.md §5, PR 53 and PR 59).

**The operands where they lie.**  y is ``[B, S, C]`` (the scan's ``[B, S,
H, P]``, a bitcast).  u and z are the FIRST C channels of wider arrays, the
filter's ``[B, S, C + 2 G N]`` result and ``in_proj``'s whole output: a
block of rows of either is its first C lanes (a blocked window at lane 0; C
whole lane tiles), so neither is cut out before the calls, the residuals are
the arrays the layer holds anyway, and the cotangents go back padded with
zeros, which XLA joins with the other channels' as it did.

**Forward.**  A grid over (batch row, block of rows).  A step walks its
block in pieces that stay in vector registers (``ops/short_conv.py``'s
reason): 64 rows, and of them one norm group's lanes at a time, where a
group is at most 512 lanes (Nemotron-H's 8 groups of 4096 channels).  A
WIDER group (Granite-4.0-H's ONE group of 4096 lanes: 256 vector registers a
tensor at 64 rows, of the v5e's 64) is a shape the pass takes too, in two
sweeps over the group's pieces of at most 512 lanes (``_pieces``: the widest
piece of whole lane tiles that divides the group, and as many rows as keep a
step at 64 x 512 elements): first the sum of squares, then g made again from
the block in VMEM and scaled; backward the two group means first (of g g,
and of ``dn n = r go w g``), then the gradients piece by piece.  The same
mathematics in the same precision; the sigmoid is evaluated twice and no
float32 copy of g is stored between the sweeps.
float32 inside, rounded ONCE, where the result is stored: the rounding of t
to bf16 that the ``jnp`` body has between the skip and the gate is not made
here, which is more precision, not less.  A group's sum of squares is a sum
over its lanes' vector registers and one reduction across a register's
lanes; no product with an indicator (a group is whole lane tiles: the rule).

**Backward.**  One call for every gradient, on the INPUTS alone: it makes
t, ``s = silu(z)``, g and ``r = rsqrt(.)`` again and, with ``n = g r`` and
go the cotangent::

    dn = go w                          dw = sum_rows(go n)
    dg = r (dn - n mean_group(dn n))
    dy = dt = dg s                     du = D dt       dD = sum_rows,lanes(dt u)
    dz = dg t sigma(z) (1 + z (1 - sigma(z)))

dw and dD leave the call as eight partial sums a lane and batch row (a
``[B, 16, C]`` float32 output whose block stays put while the grid walks a
batch row's blocks, as ``short_conv``'s taps); XLA adds the eight, the batch
rows and, for D, a head's lanes.  No float32 array of the activations' shape
is written to HBM in either call.

Which body a trace took is counted (``body_counts``).  The Mosaic pass is
taken where the caller says ``in_place`` (the trace is not partitioned:
PERF.md §3.3), the channels are whole lane tiles and a group a whole number
of them, a block of rows divides the sequence and the backend is a TPU
(interpreted, a call is many times slower than XLA:CPU's fusions, and every
tiny CPU model would run it; the tests run the pair so by calling it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.common import trace_counts as _trace_counts
from horovod_tpu.ops.short_conv import _each_chunk, _sigmoid

__all__ = ["gated_norm", "skip_gate_norm", "skipped", "body_counts",
           "NOT_IN_PLACE", "OFF_THE_LANE_TILE", "GROUP_OFF_THE_TILE",
           "NO_ROW_BLOCK", "NO_TPU"]

_LANES = 128
_TILE = 8          # rows of a float32 sublane tile: a partial sum's
# Rows of a block, the largest that divides the sequence: three operands and
# a result forward, four and three backward, two buffers each.  (128, 256
# and 512 read the same on the v5e: the walk paces a call, not the blocks.)
_ROWS = (256, 128, 64, 32, 16)
# Rows a step of a body's walk works on, where the block has as many.  A
# step is one dependent chain an element (the sigmoid's exponential and
# reciprocal, the group's reduction, the root), and 16 rows of 512 lanes are
# too few vector registers to hide its latency: alone on the v5e a forward
# call takes 1.20 ms at 16 rows, 0.84 at 32 and 0.80 at 64 (1.46 for 2.04
# backward), with no gate and no norm at all still 0.99 at 16 (my chip run,
# PR 59; PERF.md §5).
_WALK = 64
# Lanes of a norm group that a walk's step holds at once.  A wider group
# (Granite-4.0-H's ONE group of 4096: 256 vector registers a tensor at 64
# rows, where the v5e has 64) is taken in two sweeps over pieces of at most
# this many lanes (``_pieces``).
_GROUP_LANES = 512
_VMEM_LIMIT = 64 * 1024 * 1024

_BODY = "gated_norm.body"
_MOSAIC = "one Mosaic pass each way"
NOT_IN_PLACE = "the attention_fn does not read its operands in place"
OFF_THE_LANE_TILE = "the channels are no whole lane tiles"
GROUP_OFF_THE_TILE = "a group is no whole number of lane tiles"
NO_ROW_BLOCK = "no block of rows divides the sequence"
NO_TPU = "no TPU: the calls would run interpreted"


def body_counts() -> dict:
    """``{"mosaic": n, "plain": {reason: n}}``: how many traced calls of
    ``gated_norm`` took the Mosaic pass, and how many the ``jnp`` body, by
    reason.  Process-global, counted once a TRACE."""
    plain = _trace_counts.counts(_BODY)
    return {"mosaic": plain.pop(_MOSAIC, 0), "plain": plain}


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pick_rows(s: int) -> int:
    return next((rows for rows in _ROWS if s % rows == 0), 0)


def _why_not(shape, groups: int, in_place: bool):
    """None where the Mosaic pass takes ``y`` of ``shape [B, S, C]`` normed
    in ``groups`` groups, else the reason it does not."""
    if not in_place:
        return NOT_IN_PLACE
    if len(shape) != 3 or shape[2] % _LANES:
        return OFF_THE_LANE_TILE
    if shape[2] % groups or (shape[2] // groups) % _LANES:
        return GROUP_OFF_THE_TILE
    if not _pick_rows(shape[1]):
        return NO_ROW_BLOCK
    return NO_TPU if _interpret() else None


# -- the two bodies' walk ----------------------------------------------------

def _walked(rows: int, step, carry, walk: int = _WALK):
    """``carry = step(which rows, carry)`` for each ``walk`` rows of a
    block of ``rows`` (the whole of a smaller block): the last carry."""
    walk = min(rows, walk)
    return jax.lax.fori_loop(
        0, rows // walk, lambda j, carry: step(
            pl.ds(pl.multiple_of(j * walk, walk), walk), carry), carry)


def _mean(x):
    """The mean over a norm group's lanes ``[rows, 1]``."""
    return jnp.sum(x, axis=-1, keepdims=True) * (1.0 / x.shape[-1])


def _fwd_kernel(y_ref, u_ref, z_ref, d_ref, w_ref, o_ref, *, groups, eps):
    # y_ref, u_ref, z_ref, o_ref: [rows, C]; d_ref (D on its head's lanes)
    # and w_ref: [1, C] float32.
    rows, width = y_ref.shape

    def chunk(lanes, _):
        def step(here, carry):
            y, u, z = (ref[here, lanes].astype(jnp.float32)
                       for ref in (y_ref, u_ref, z_ref))
            g = (y + d_ref[:, lanes] * u) * (z * _sigmoid(z))
            o_ref[here, lanes] = (
                g * jax.lax.rsqrt(_mean(g * g) + eps) * w_ref[:, lanes]
            ).astype(o_ref.dtype)
            return carry

        _walked(rows, step, 0)

    _each_chunk(width, width // groups, chunk)    # a norm group's lanes


def _pieces(rows: int, per: int):
    """``(rows a step, lanes a piece)`` of the walk over a group of ``per``
    lanes that is wider than ``_GROUP_LANES``: the widest piece of whole
    lane tiles that divides the group, and as many rows as keep a step at
    ``_WALK`` rows of ``_GROUP_LANES`` lanes (of a block's ``rows``)."""
    piece = next(lanes for lanes in range(_GROUP_LANES, 0, -_LANES)
                 if per % lanes == 0)
    return min(rows, _WALK * (_GROUP_LANES // piece)), piece


def _piece(lanes, j, piece: int):
    """Piece ``j`` (traced) of ``piece`` lanes of a group's ``lanes``
    (``_each_chunk``'s: a slice, or a ``pl.ds``)."""
    return pl.ds(pl.multiple_of(lanes.start + j * piece, _LANES), piece)


def _fwd_kernel_wide(y_ref, u_ref, z_ref, d_ref, w_ref, o_ref, *, groups,
                     eps):
    # As _fwd_kernel for a group wider than a step holds: a step's rows
    # take the group's pieces twice, for the sum of squares and then for
    # the scaling, g made again from the block in VMEM (the sigmoid twice:
    # no float32 copy of g is stored between the sweeps).
    rows, width = y_ref.shape
    per = width // groups
    walk, piece = _pieces(rows, per)

    def group(lanes, _):
        def gated(here, at):
            y, u, z = (ref[here, at].astype(jnp.float32)
                       for ref in (y_ref, u_ref, z_ref))
            return (y + d_ref[:, at] * u) * (z * _sigmoid(z))

        def step(here, carry):
            def squares(j, total):
                g = gated(here, _piece(lanes, j, piece))
                return total + jnp.sum(g * g, axis=-1, keepdims=True)

            r = jax.lax.rsqrt(jax.lax.fori_loop(
                0, per // piece, squares, jnp.zeros((walk, 1), jnp.float32))
                * (1.0 / per) + eps)

            def scaled(j, carry):
                at = _piece(lanes, j, piece)
                o_ref[here, at] = (gated(here, at) * r * w_ref[:, at]
                                   ).astype(o_ref.dtype)
                return carry

            return jax.lax.fori_loop(0, per // piece, scaled, carry)

        _walked(rows, step, 0, walk)

    _each_chunk(width, per, group)


def _bwd_kernel_wide(y_ref, u_ref, z_ref, go_ref, d_ref, w_ref, dy_ref,
                     du_ref, dz_ref, sums_ref, *, groups, eps):
    # As _bwd_kernel for a group wider than a step holds: the two group
    # means first (of g g, and of dn n = r go w g), then the gradients piece
    # by piece; a piece's partial sums of dw and dD go straight to sums_ref.
    rows, width = y_ref.shape
    per = width // groups
    walk, piece = _pieces(rows, per)

    @pl.when(pl.program_id(1) == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def group(lanes, _):
        def read(here, at):
            return (ref[here, at].astype(jnp.float32)
                    for ref in (y_ref, u_ref, z_ref, go_ref))

        def step(here, carry):
            def means(j, totals):
                at = _piece(lanes, j, piece)
                y, u, z, go = read(here, at)
                g = (y + d_ref[:, at] * u) * (z * _sigmoid(z))
                return tuple(
                    total + jnp.sum(x, axis=-1, keepdims=True) for total, x
                    in zip(totals, (g * g, go * w_ref[:, at] * g)))

            squares, dn_g = jax.lax.fori_loop(
                0, per // piece, means,
                (jnp.zeros((walk, 1), jnp.float32),) * 2)
            r = jax.lax.rsqrt(squares * (1.0 / per) + eps)
            dn_n = r * dn_g * (1.0 / per)          # mean_group(dn n)

            def grads(j, carry):
                at = _piece(lanes, j, piece)
                y, u, z, go = read(here, at)
                t = y + d_ref[:, at] * u
                sig = _sigmoid(z)
                s = z * sig
                n = t * s * r
                dg = r * (go * w_ref[:, at] - n * dn_n)
                dt = dg * s
                dy_ref[here, at] = dt.astype(dy_ref.dtype)
                du_ref[here, at] = (d_ref[:, at] * dt).astype(du_ref.dtype)
                dz_ref[here, at] = (dg * t * sig * (1.0 + z * (1.0 - sig))
                                    ).astype(dz_ref.dtype)
                sums_ref[:_TILE, at] += (go * n).reshape(
                    -1, _TILE, piece).sum(axis=0)
                sums_ref[_TILE:, at] += (dt * u).reshape(
                    -1, _TILE, piece).sum(axis=0)
                return carry

            return jax.lax.fori_loop(0, per // piece, grads, carry)

        _walked(rows, step, 0, walk)

    _each_chunk(width, per, group)


def _kernels(width: int, groups: int):
    """The two bodies for ``width`` channels normed in ``groups`` runs."""
    if width // groups > _GROUP_LANES:
        return _fwd_kernel_wide, _bwd_kernel_wide
    return _fwd_kernel, _bwd_kernel


def _bwd_kernel(y_ref, u_ref, z_ref, go_ref, d_ref, w_ref, dy_ref, du_ref,
                dz_ref, sums_ref, *, groups, eps):
    # As _fwd_kernel, with the cotangent go_ref and the three results
    # [rows, C]; sums_ref: [2 * _TILE, C] float32, eight partial sums a lane
    # of dw and then of dD's, the same block for every step of a batch row.
    rows, width = y_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def chunk(lanes, per):
        def step(here, sums):
            y, u, z, go = (ref[here, lanes].astype(jnp.float32)
                           for ref in (y_ref, u_ref, z_ref, go_ref))
            t = y + d_ref[:, lanes] * u
            sig = _sigmoid(z)
            s = z * sig
            g = t * s
            r = jax.lax.rsqrt(_mean(g * g) + eps)
            n = g * r
            dn = go * w_ref[:, lanes]
            dg = r * (dn - n * _mean(dn * n))
            dt = dg * s
            dy_ref[here, lanes] = dt.astype(dy_ref.dtype)
            du_ref[here, lanes] = (d_ref[:, lanes] * dt).astype(du_ref.dtype)
            dz_ref[here, lanes] = (dg * t * sig * (1.0 + z * (1.0 - sig))
                                   ).astype(dz_ref.dtype)
            return tuple(acc + x.reshape(-1, _TILE, per).sum(axis=0)
                         for acc, x in zip(sums, (go * n, dt * u)))

        d_w, d_d = _walked(
            rows, step, (jnp.zeros((_TILE, per), jnp.float32),) * 2)
        sums_ref[:_TILE, lanes] += d_w
        sums_ref[_TILE:, lanes] += d_d

    _each_chunk(width, width // groups, chunk)    # a norm group's lanes


# -- the two calls -----------------------------------------------------------

def _specs(rows: int, width: int):
    """A block of rows of an array's first ``width`` channels, and an
    operand ``[1, width]`` that every step sees whole."""
    return (pl.BlockSpec((None, rows, width), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, width), lambda b, i: (0, 0)))


def _constants(d, w, width: int):
    """D on its head's lanes and the norm's weight, ``[1, C]`` float32."""
    return (jnp.repeat(d.astype(jnp.float32), width // d.shape[0])[None],
            w.astype(jnp.float32)[None])


# (Jits, as ``short_conv``'s: a step traces each body once a shape, not
# once a layer and pass.  ``interpret`` is static, so the cached trace is of
# the mode asked for.)
@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def _forward(y, u, z, d, w, groups, eps, interpret):
    b, s, width = y.shape
    rows = _pick_rows(s)
    block, whole = _specs(rows, width)
    call = pl.pallas_call(
        functools.partial(_kernels(width, groups)[0], groups=groups, eps=eps),
        grid=(b, s // rows),
        in_specs=[block, block, block, whole, whole],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(y.shape, z.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_GATED_NORM):
        return call(y, u, z, *_constants(d, w, width))


@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def _backward(y, u, z, d, w, go, groups, eps, interpret):
    b, s, width = y.shape
    rows = _pick_rows(s)
    block, whole = _specs(rows, width)
    call = pl.pallas_call(
        functools.partial(_kernels(width, groups)[1], groups=groups, eps=eps),
        grid=(b, s // rows),
        in_specs=[block, block, block, block, whole, whole],
        out_specs=[block, block, block,
                   pl.BlockSpec((None, 2 * _TILE, width),
                                lambda b, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(y.shape, u.dtype),
                   jax.ShapeDtypeStruct(y.shape, z.dtype),
                   jax.ShapeDtypeStruct((b, 2 * _TILE, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_GATED_NORM):
        dy, du, dz, sums = call(y, u, z, go, *_constants(d, w, width))
    # Nothing comes back to the channels behind the first C.
    du, dz = (jnp.pad(x, ((0, 0), (0, 0), (0, wide.shape[2] - width)))
              for x, wide in ((du, u), (dz, z)))
    sums = sums.reshape(b, 2, _TILE, width).sum(axis=(0, 2))
    return (dy, du, dz,
            sums[1].reshape(d.shape[0], -1).sum(axis=1).astype(d.dtype),
            sums[0].astype(w.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def skip_gate_norm(y, u, z, d, w, groups, eps):
    """``rms_norm_G((y + d u) silu(z)) w`` for ``y [B, S, C]``, the first C
    channels of ``u [B, S, >= C]`` and of ``z [B, S, >= C]``, ``d [heads]``
    (head h's scalar on lanes ``h C / heads ..``) and ``w [C]``, the norm
    over each of ``groups`` runs of ``C / groups`` lanes; float32 inside,
    rounded once, to the dtype of z.  One Mosaic call, and one for all five
    gradients; ``_why_not`` says which shapes it takes."""
    return _forward(y, u, z, d, w, groups=groups, eps=eps,
                    interpret=_interpret())


def _skip_gate_norm_fwd(y, u, z, d, w, groups, eps):
    return skip_gate_norm(y, u, z, d, w, groups, eps), (y, u, z, d, w)


def _skip_gate_norm_bwd(groups, eps, kept, go):
    return _backward(*kept, go, groups=groups, eps=eps,
                     interpret=_interpret())


skip_gate_norm.defvjp(_skip_gate_norm_fwd, _skip_gate_norm_bwd)


# -- the plain body, and the one entry ----------------------------------------

@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _gate_then_norm(y, z, scale, groups, eps):
    """``rms_norm_g(y * silu(z)) * scale``: the gate FIRST, then the norm
    over each of ``groups`` runs of lanes (Mamba-2's ``norm_before_gate=
    False``); y, z ``[B, S, C]``, ``scale [C]``; float32 inside, the dtype
    of z out, and under a checkpoint as ``models/llama.py::_gated_norm``."""
    y = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(*y.shape[:-1], groups, -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return (grouped.reshape(y.shape) * scale).astype(z.dtype)


def skipped(y, u, d):
    """``y + d u`` a head (``y, u [B, S, C]``, ``d [heads]``): float32
    inside, the dtype of u out.  What the ``jnp`` body gates, and what a
    Mamba-2 layer's ``out_max`` counter reads."""
    heads = (*y.shape[:-1], d.shape[0], -1)
    return (y.reshape(heads).astype(jnp.float32) + d[:, None]
            * u.reshape(heads).astype(jnp.float32)).astype(u.dtype).reshape(
                y.shape)


def gated_norm(y, u, z, d, w, groups: int, eps: float, in_place: bool):
    """A Mamba-2 layer between its scan and ``out_proj``: ``rms_norm_G((y +
    d u) silu(z)) w``, ``[B, S, C]`` in the dtype of z.  ``y [B, S, C]``;
    u and z ``[B, S, >= C]``, read in their first C channels (where the
    filter and ``in_proj`` leave them: the pass reads them there, the
    ``jnp`` body cuts them out); ``d [heads]``, ``w [C]``; the norm over each
    of ``groups`` (static) runs of lanes, ``eps`` (static) under the root.
    ``in_place`` is the caller's word that this trace may hold Mosaic calls
    on operands where they lie: the chain is then ``skip_gate_norm``'s one
    pass forward and one backward, where the shape is one it takes
    (``_why_not``) and the backend a TPU.  Elsewhere the skip rounded to
    u's dtype and ``_gate_then_norm``.  Which body a trace took, and why,
    ``body_counts()`` says."""
    why = _why_not(y.shape, groups, in_place)
    _trace_counts.note(_BODY, why or _MOSAIC)
    if why is None:
        return skip_gate_norm(y, u, z, d, w, groups, eps)
    width = y.shape[-1]
    return _gate_then_norm(skipped(y, u[..., :width], d), z[..., :width], w,
                           groups, eps)
