"""A hyper-connected sublayer's passes over its residual streams as Mosaic
calls over rows of X (manifold-constrained hyper-connections, mHC,
arXiv:2512.24880; ``models/llama.py::HyperConnection`` has the equations).

A token's n = ``hc_mult`` streams ``X [B, S, n, H]`` are ROWS ``[T, n H]`` (a
stream is ``H / 128`` whole lane tiles of a row; the reshape is free), and
every pass that touches a tensor of that size walks row blocks of it once,
float32 inside, X's dtype in HBM:

====  ==================================================  =================
call  reads -> writes                                     scope
====  ==================================================  =================
F1    X, Phi -> ``ss [T]`` = sum x^2, ``raw [T, m]`` = X  ``hvd.hc.map``
      Phi (m = n (n + 2) columns)
F2    X, ``h_pre`` -> ``x_in = h_pre X``                  ``hvd.hc.mix``
F3    X, y, ``h_post``, ``H_res`` -> ``X' = H_res X +     ``hvd.hc.mix``
      h_post^T y``
B3    dX', X, y, the maps -> ``dX_res = H_res^T dX'``,    ``hvd.hc.mix``
      ``dy = h_post dX'``, ``dh_post``, ``dH_res`` (lane
      reductions a token)
B2a   X, d ``x_in`` -> ``dh_pre``                         ``hvd.hc.mix``
B2b   X, d ``x_in``, ``dX_res``, d ``raw``, d ``ss``,     ``hvd.hc.mix``
      ``h_pre``, Phi -> ``dX = dX_res + h_pre d x_in + d
      raw Phi^T + 2 d ss X``; ``dPhi`` summed over the row
      blocks in an output that stays put
====  ==================================================  =================

What is m numbers a token (gains, biases, the RMS's root, the sigmoids,
Sinkhorn's steps and their transposes) stays the caller's ``jnp`` function
``maps(logits [m, T], mean_square [T], gain, bias)``, under ``hvd.hc.map``,
and its backward pass is ``jax.vjp`` of it inside the backward rule.

**X's cotangent is threaded.**  X is read by the statistics, the read and the
write.  As three functions of X each backward rule would write a ``[T, n H]``
cotangent and JAX would add the three outside any call.  So ``streams`` is
ONE ``custom_vjp`` (F1, the maps, F2) that RETURNS X beside ``x_in``,
``h_post`` and ``H_res``; ``write`` (F3) takes that returned X, and its
backward rule (B3) hands ``dX_res`` back as the cotangent of it, to which
``streams``' backward rule (B2a, the maps' transpose, B2b) adds the rest
inside B2b: one ``[T, n H]`` cotangent is written a sublayer and pass.

**Precision.**  Sums, mixes and reductions are float32 inside a call and
rounded once where a tensor is written, as the ``jnp`` bodies round.  The
three products with Phi run on the MXU as ONE pass at X's dtype (Phi and d
``raw`` rounded to it on the way in, float32 accumulation): what XLA:TPU
makes of the ``jnp`` body's float32 ``einsum`` at default precision (the
compiled step before this module held Phi as ``bf16[14336,24]`` and no
``operand_precision``).  ``ss``, ``raw``, ``dPhi`` and the maps' cotangents
are float32 in HBM.

**The maps as a call reads them.**  A token's scalars multiply that token's
ROW, so a call takes them with the tokens on the sublanes, ``[T, k]``
float32 (XLA turns ``[k, T]``; padded to a lane tile that is 4 MB where X is
235), and gives a token's reductions back as ``[T, 128]`` float32, the
first k lanes in use.

Which body a trace took is counted (``body_counts``).  The calls are taken
where the caller says ``in_place`` (the trace is not partitioned), a stream is
whole lane tiles, a block of rows divides T and the backend is a TPU; every
other trace runs the ``jnp`` bodies of ``models/llama.py`` and no
``custom_vjp``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.common import trace_counts as _trace_counts
from horovod_tpu.ops.gated_norm import (NO_ROW_BLOCK, NO_TPU, NOT_IN_PLACE,
                                        OFF_THE_LANE_TILE, _interpret,
                                        _pick_rows, _walked)
from horovod_tpu.ops.short_conv import _each_chunk, _moved
from horovod_tpu.ops.ssd import _NT, _TN, _mm

__all__ = ["streams", "write", "note", "takes", "body_counts", "NOT_IN_PLACE",
           "OFF_THE_LANE_TILE", "NO_ROW_BLOCK", "NO_TPU"]

_LANES = 128
_TILE = 16             # rows of a bf16 tile: Phi's columns are padded to whole
# ones, and a block of X has no fewer rows
# A block of X at most: B2b holds three such (X, the returned X's cotangent,
# the result) twice and a float32 one, 37 MB at the cell's 128 rows of 14,336
# bf16, under the limit the calls state.  (Alone on the v5e at [8192, 4 x
# 3584]: 64 rows read 0.59 ms for F1 and 1.45 for B2b where 128 read 0.42 and
# 1.22, the products' weights loaded half as often; the other four read the
# same: my chip runs, PR 66.)
_BLOCK_BYTES = 4 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024
# Rows a step of a body's walk works on and lanes of a stream it holds at
# once: every scalar of a token's maps is a vector register a sublane tile
# of rows (spread over the lanes), so the mixes' 20 leave room for few rows.
# (16, 32 and 64 rows read within 1 % of each other; 8 rows or 256 lanes a
# fifth to a half slower.)
_WALK = 16
_PIECE = 512

_BODY = "hyper_connection.body"
_MOSAIC = "Mosaic passes over rows of X"


def body_counts() -> dict:
    """``{"mosaic": n, "plain": {reason: n}}``: how many traced sublayers
    (``note``) mixed their streams by the Mosaic calls, and how many by the
    ``jnp`` bodies, by reason.  Process-global, counted once a TRACE."""
    plain = _trace_counts.counts(_BODY)
    return {"mosaic": plain.pop(_MOSAIC, 0), "plain": plain}


def _why_not(shape, in_place: bool):
    """None where the calls take streams ``x`` of ``shape [B, S, n, H]``
    whose caller says ``in_place``, else the reason they do not."""
    if not in_place:
        return NOT_IN_PLACE
    if len(shape) != 4 or shape[3] % _LANES:
        return OFF_THE_LANE_TILE
    if not _pick_rows(shape[0] * shape[1]):
        return NO_ROW_BLOCK
    return NO_TPU if _interpret() else None


def note(x, in_place: bool):
    """``_why_not`` for the streams ``x``, counted: for the sublayer's one
    entry."""
    why = _why_not(x.shape, in_place)
    _trace_counts.note(_BODY, why or _MOSAIC)
    return why


def takes(x, in_place: bool) -> bool:
    """Whether the calls take the streams ``x`` (``_why_not``), uncounted:
    for the sublayer's way out, which ``note`` has counted on its way in."""
    return _why_not(x.shape, in_place) is None


# -- the bodies ---------------------------------------------------------------
#
# x_ref, and what has X's shape: [rows, n H]; y's: [rows, H]; a token's
# scalars s_ref: [rows, k] float32 (read), [rows, 128] float32 (written).

def _piece(hidden: int) -> int:
    """Lanes of a stream a step holds: the widest piece of whole lane tiles,
    at most ``_PIECE``, that divides it."""
    return next(lanes for lanes in range(_PIECE, 0, -_LANES)
                if hidden % lanes == 0)


def _columns(s, k: int):
    """Column j of ``s [rows, >= k]`` as ``[rows, 1]``, j < k."""
    return [s[:, j:j + 1] for j in range(k)]


def _f32(ref, here, lanes):
    return ref[here, lanes].astype(jnp.float32)


def _over_lanes(x):
    """``x [rows, L]`` summed to one lane tile ``[rows, 128]``: adds of
    whole vector registers; the sum across a tile's lanes is made once, of
    the walk's total (``_on_lanes``)."""
    return functools.reduce(jnp.add, (
        x[:, at:at + _LANES] for at in range(0, x.shape[1], _LANES)))


def _on_lanes(totals):
    """``[rows, 128]`` float32 whose lane c is the sum over the lanes of
    ``totals[c] [rows, 128]``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, totals[0].shape, 1)
    out = jnp.zeros_like(totals[0])
    for c, total in enumerate(totals):
        out = jnp.where(lane == c, jnp.sum(total, axis=1, keepdims=True), out)
    return out


def _pieces_summed(width: int, piece: int, step, zeros):
    """``carry = step(which lanes, carry)`` over a stream's pieces."""
    def body(at, carry):
        return step(pl.ds(pl.multiple_of(at * piece, _LANES), piece), carry)

    if width == piece:
        return step(slice(0, piece), zeros)
    return jax.lax.fori_loop(0, width // piece, body, zeros)


def _stats_kernel(x_ref, phi_ref, raw_ref, ss_ref):
    # phi_ref [m', n H] in x's dtype; raw_ref [rows, m'], ss_ref [rows, 128]
    # (a token's sum of squares on every lane) float32.
    rows, width = x_ref.shape

    def step(lanes, carry):
        raw, squares = carry
        x = x_ref[:, lanes]
        x32 = x.astype(jnp.float32)
        return (raw + _mm(x, phi_ref[:, lanes], _NT),
                squares + _over_lanes(x32 * x32))

    raw, squares = _pieces_summed(width, _piece(width), step, (
        jnp.zeros(raw_ref.shape, jnp.float32),
        jnp.zeros((rows, _LANES), jnp.float32)))
    raw_ref[...] = raw
    ss_ref[...] = jnp.broadcast_to(
        jnp.sum(squares, axis=1, keepdims=True), ss_ref.shape)


def _read_kernel(x_ref, s_ref, o_ref, *, n):
    rows, hidden = o_ref.shape

    def step(here, carry):
        h_pre = _columns(s_ref[here, :], n)

        def piece(lanes, _):
            o_ref[here, lanes] = functools.reduce(jnp.add, (
                h_pre[j] * _f32(x_ref, here, _moved(lanes, j * hidden))
                for j in range(n))).astype(o_ref.dtype)

        _each_chunk(hidden, _piece(hidden), piece)
        return carry

    _walked(rows, step, 0, _WALK)


def _maps_of(s, n: int):
    """``(h_post [i], H_res [i][j])`` of a token's ``n + n n`` scalars."""
    cols = _columns(s, n + n * n)
    return cols[:n], [cols[n + i * n:n + (i + 1) * n] for i in range(n)]


def _write_kernel(x_ref, y_ref, s_ref, o_ref, *, n):
    rows, hidden = y_ref.shape

    def step(here, carry):
        h_post, h_res = _maps_of(s_ref[here, :], n)

        def piece(lanes, _):
            xs = [_f32(x_ref, here, _moved(lanes, j * hidden))
                  for j in range(n)]
            y = _f32(y_ref, here, lanes)
            for i in range(n):
                mixed = functools.reduce(jnp.add, (
                    h_res[i][j] * xs[j] for j in range(n)))
                o_ref[here, _moved(lanes, i * hidden)] = (
                    mixed + h_post[i] * y).astype(o_ref.dtype)

        _each_chunk(hidden, _piece(hidden), piece)
        return carry

    _walked(rows, step, 0, _WALK)


def _write_bwd_kernel(g_ref, x_ref, y_ref, s_ref, dx_ref, dy_ref, ds_ref, *,
                      n):
    # g_ref: X' 's cotangent.  ds_ref [rows, 128]: dh_post's n lanes, then
    # dH_res's n n, a token's sums over a stream's lanes.
    rows, hidden = y_ref.shape
    zeros = (jnp.zeros((min(rows, _WALK), _LANES), jnp.float32),) * (n + n * n)

    def step(here, carry):
        h_post, h_res = _maps_of(s_ref[here, :], n)

        def piece(lanes, totals):
            gs, xs = ([_f32(ref, here, _moved(lanes, j * hidden))
                       for j in range(n)] for ref in (g_ref, x_ref))
            y = _f32(y_ref, here, lanes)
            for j in range(n):
                dx_ref[here, _moved(lanes, j * hidden)] = functools.reduce(
                    jnp.add, (h_res[i][j] * gs[i] for i in range(n))
                ).astype(dx_ref.dtype)
            dy_ref[here, lanes] = functools.reduce(jnp.add, (
                h_post[i] * gs[i] for i in range(n))).astype(dy_ref.dtype)
            found = [gs[i] * y for i in range(n)] + [
                gs[i] * xs[j] for i in range(n) for j in range(n)]
            return tuple(total + _over_lanes(x)
                         for total, x in zip(totals, found))

        ds_ref[here, :] = _on_lanes(_pieces_summed(
            hidden, _piece(hidden), piece, zeros))
        return carry

    _walked(rows, step, 0, _WALK)


def _read_bwd_kernel(x_ref, g_ref, dh_ref, *, n):
    # g_ref: x_in's cotangent; dh_ref [rows, 128]: dh_pre's n lanes.
    rows, hidden = g_ref.shape
    zeros = (jnp.zeros((min(rows, _WALK), _LANES), jnp.float32),) * n

    def step(here, carry):
        def piece(lanes, totals):
            g = _f32(g_ref, here, lanes)
            return tuple(
                total + _over_lanes(
                    g * _f32(x_ref, here, _moved(lanes, j * hidden)))
                for j, total in enumerate(totals))

        dh_ref[here, :] = _on_lanes(_pieces_summed(
            hidden, _piece(hidden), piece, zeros))
        return carry

    _walked(rows, step, 0, _WALK)


def _streams_bwd_kernel(x_ref, gx_ref, gin_ref, s_ref, draw_ref, phi_ref,
                        dx_ref, dphi_ref, map_ref, *, n):
    # gx_ref: the returned X's cotangent; gin_ref: x_in's; s_ref [rows, n +
    # 1]: h_pre, then d ss; draw_ref [rows, m'] and phi_ref [m', n H] in x's
    # dtype.  dphi_ref [m', n H] float32 stays put while the grid walks the
    # row blocks; map_ref [rows, n H] float32 scratch: d raw Phi^T.
    rows, hidden = gin_ref.shape
    width = n * hidden

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)

    def products(lanes, _):             # the MXU's sweep, a block's rows
        draw = draw_ref[...]
        dphi_ref[:, lanes] += _mm(draw, x_ref[:, lanes], _TN)
        map_ref[:, lanes] = _mm(draw, phi_ref[:, lanes])

    _each_chunk(width, _piece(width), products)

    def step(here, carry):
        cols = _columns(s_ref[here, :], n + 1)
        twice = 2.0 * cols[n]

        def piece(lanes, _):
            g = _f32(gin_ref, here, lanes)
            for j in range(n):
                at = _moved(lanes, j * hidden)
                dx_ref[here, at] = (
                    _f32(gx_ref, here, at) + cols[j] * g + map_ref[here, at]
                    + twice * _f32(x_ref, here, at)).astype(dx_ref.dtype)

        _each_chunk(hidden, _piece(hidden), piece)
        return carry

    _walked(rows, step, 0, _WALK)


# -- the calls ----------------------------------------------------------------

def _call(kernel, tokens: int, rows: int, blocks, wholes, results, put=(),
          scratch=(), *, interpret):
    """One pass of ``kernel`` over the row blocks of ``tokens`` tokens:
    ``blocks`` ``[T, w]`` are read ``rows`` rows a step, ``wholes`` seen whole
    by every step; ``results`` (``ShapeDtypeStruct``s ``[T, w]``) leave as
    blocks of rows and ``put`` as arrays that stay put while the grid walks
    (summed over it)."""
    def block(x):
        return pl.BlockSpec((rows, x.shape[1]), lambda i: (i, 0))

    def whole(x):
        return pl.BlockSpec(x.shape, lambda i: (0, 0))

    call = pl.pallas_call(
        kernel,
        grid=(tokens // rows,),
        in_specs=[block(x) for x in blocks] + [whole(x) for x in wholes],
        out_specs=[block(x) for x in results] + [whole(x) for x in put],
        out_shape=[*results, *put],
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if put else "parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_HC_STREAMS):
        return call(*blocks, *wholes)


def _rows_of(x) -> int:
    """Rows of a block of ``x [T, n H]``: the most that divide T
    (``gated_norm``'s sizes), halved while a block is over
    ``_BLOCK_BYTES``."""
    rows = _pick_rows(x.shape[0])
    while (rows > _TILE
           and rows * x.shape[1] * x.dtype.itemsize > _BLOCK_BYTES):
        rows //= 2
    return rows


def _small(tokens: int):
    return jax.ShapeDtypeStruct((tokens, _LANES), jnp.float32)


def _phi_rows(phi, dtype):
    """``phi [n H, m]`` as the calls read it: ``[m', n H]`` in X's dtype,
    m padded with zero rows to whole sublane tiles."""
    m = phi.shape[1]
    return jnp.pad(phi.T.astype(dtype), ((0, -m % _TILE), (0, 0)))


# (Jits, as ``gated_norm``'s: a step traces each body once a shape, not once
# a sublayer and pass.  ``interpret`` is static.)
@functools.partial(jax.jit, static_argnames=("interpret",))
def _stats(x, phi_t, interpret):
    """F1: ``(raw [T, m'], ss [T])`` of ``x [T, n H]``."""
    tokens = x.shape[0]
    raw, ss = _call(
        _stats_kernel, tokens, _rows_of(x), (x,), (phi_t,),
        (jax.ShapeDtypeStruct((tokens, phi_t.shape[0]), jnp.float32),
         _small(tokens)), interpret=interpret)
    return raw, ss[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _read(x, h_pre, interpret):
    """F2: ``x_in [T, H]`` of ``x [T, n H]`` and ``h_pre [n, T]``."""
    tokens, n = x.shape[0], h_pre.shape[0]
    return _call(
        functools.partial(_read_kernel, n=n), tokens, _rows_of(x),
        (x, h_pre.T), (),
        (jax.ShapeDtypeStruct((tokens, x.shape[1] // n), x.dtype),),
        interpret=interpret)[0]


def _scalars(h_post, h_res):
    """``[T, n + n n]``: a token's ``h_post``, then its ``H_res`` row by
    row."""
    n, tokens = h_post.shape
    return jnp.concatenate([h_post, h_res.reshape(n * n, tokens)]).T


@functools.partial(jax.jit, static_argnames=("interpret",))
def _write(x, y, h_post, h_res, interpret):
    """F3: ``X' [T, n H]``."""
    return _call(
        functools.partial(_write_kernel, n=h_post.shape[0]), x.shape[0],
        _rows_of(x), (x, y, _scalars(h_post, h_res)), (),
        (jax.ShapeDtypeStruct(x.shape, x.dtype),), interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _write_bwd(g, x, y, h_post, h_res, interpret):
    """B3: ``(dX_res [T, n H], dy [T, H], dh_post [n, T], dH_res [n, n,
    T])``."""
    n, tokens = h_post.shape
    dx, dy, ds = _call(
        functools.partial(_write_bwd_kernel, n=n), tokens, _rows_of(x),
        (g, x, y, _scalars(h_post, h_res)), (),
        (jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct(y.shape, y.dtype), _small(tokens)),
        interpret=interpret)
    ds = ds[:, :n + n * n].T
    return dx, dy, ds[:n], ds[n:].reshape(n, n, tokens)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _read_bwd(x, g, n, interpret):
    """B2a: ``dh_pre [n, T]``."""
    tokens = x.shape[0]
    return _call(
        functools.partial(_read_bwd_kernel, n=n), tokens, _rows_of(x),
        (x, g), (), (_small(tokens),), interpret=interpret)[0][:, :n].T


@functools.partial(jax.jit, static_argnames=("interpret",))
def _streams_bwd_call(x, gx, gin, h_pre, dss, draw, phi_t, interpret):
    """B2b: ``(dX [T, n H], dPhi [m', n H])``."""
    tokens, width = x.shape
    rows = _rows_of(x)
    return _call(
        functools.partial(_streams_bwd_kernel, n=h_pre.shape[0]), tokens,
        rows, (x, gx, gin, jnp.concatenate([h_pre, dss[None]]).T,
               draw.astype(x.dtype)), (phi_t,),
        (jax.ShapeDtypeStruct(x.shape, x.dtype),),
        (jax.ShapeDtypeStruct(phi_t.shape, jnp.float32),),
        (pltpu.VMEM((rows, width), jnp.float32),), interpret=interpret)


# -- the two entries ----------------------------------------------------------

def _as_rows(x):
    """``[B, S, n, H] -> [T, n H]``, ``[B, S, H] -> [T, H]``."""
    return x.reshape(x.shape[0] * x.shape[1], -1)


def _the_maps(maps, columns: int, width: int):
    """``maps`` on what F1 leaves: ``raw [T, m']`` and ``ss [T]``."""
    return lambda raw, ss, gain, bias: maps(
        raw[:, :columns].T, ss / width, gain, bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def streams(maps, x, phi, gain, bias):
    """A hyper-connected sublayer's way in: ``(x, x_in, h_post, H_res)`` of
    the streams ``x [B, S, n, H]``, ``phi [n H, m]`` and ``gain``, ``bias``
    ``[m]``, with ``(h_pre, h_post, H_res) = maps(logits [m, T], mean_square
    [T], gain, bias)`` the caller's ``jnp`` function of ``logits = (X
    phi)^T`` and a token's mean of squares, and ``x_in = h_pre X`` ``[B, S,
    H]``.  The x it returns is x, and is what ``write`` is to be handed: X's
    whole cotangent is then made by this function's backward rule (the
    module's docstring).  F1, the maps, F2; ``_why_not`` says which shapes."""
    return _streams_fwd(maps, x, phi, gain, bias)[0]


def _streams_fwd(maps, x, phi, gain, bias):
    rows = _as_rows(x)
    with _scopes.scope(_scopes.HC_MAP):
        raw, ss = _stats(rows, _phi_rows(phi, x.dtype),
                         interpret=_interpret())
        h_pre, h_post, h_res = _the_maps(maps, phi.shape[1], rows.shape[1])(
            raw, ss, gain, bias)
    with _scopes.scope(_scopes.HC_MIX):
        x_in = _read(rows, h_pre, interpret=_interpret())
    return ((x, x_in.reshape(*x.shape[:2], -1), h_post, h_res),
            (x, phi, gain, bias, raw, ss))


def _streams_bwd(maps, kept, cotangents):
    x, phi, gain, bias, raw, ss = kept
    gx, gin, dh_post, dh_res = cotangents
    rows, gin = _as_rows(x), _as_rows(gin)
    n = x.shape[2]
    with _scopes.scope(_scopes.HC_MIX):
        dh_pre = _read_bwd(rows, gin, n=n, interpret=_interpret())
    with _scopes.scope(_scopes.HC_MAP):
        (h_pre, _, _), back = jax.vjp(
            _the_maps(maps, phi.shape[1], rows.shape[1]), raw, ss, gain, bias)
        draw, dss, dgain, dbias = back((dh_pre, dh_post, dh_res))
    with _scopes.scope(_scopes.HC_MIX):
        dx, dphi = _streams_bwd_call(
            rows, _as_rows(gx), gin, h_pre, dss, draw,
            _phi_rows(phi, x.dtype), interpret=_interpret())
    return (dx.reshape(x.shape), dphi[:phi.shape[1]].T.astype(phi.dtype),
            dgain, dbias)


streams.defvjp(*_scopes.rules("streams", _streams_fwd, _streams_bwd))


@jax.custom_vjp
def write(x, y, h_post, h_res):
    """A hyper-connected sublayer's way out: ``X' = H_res X + h_post^T y``
    ``[B, S, n, H]`` of the streams x as ``streams`` RETURNED them, the
    sublayer's output ``y [B, S, H]``, ``h_post [n, T]`` and ``H_res [n, n,
    T]``; float32 inside, rounded once.  F3, and B3 for every gradient."""
    with _scopes.scope(_scopes.HC_MIX):
        return _write(_as_rows(x), _as_rows(y), h_post, h_res,
                      interpret=_interpret()).reshape(x.shape)


def _write_fwd(x, y, h_post, h_res):
    return write(x, y, h_post, h_res), (x, y, h_post, h_res)


def _write_rule(kept, g):
    x, y, h_post, h_res = kept
    with _scopes.scope(_scopes.HC_MIX):
        dx, dy, dh_post, dh_res = _write_bwd(
            _as_rows(g), _as_rows(x), _as_rows(y), h_post, h_res,
            interpret=_interpret())
    return dx.reshape(x.shape), dy.reshape(y.shape), dh_post, dh_res


write.defvjp(*_scopes.rules("write", _write_fwd, _write_rule))
