"""Mamba's selective scan (Gu & Dao, arXiv:2312.00752): the recurrence of a
``Mamba1`` layer on the training path, forward and backward.

Every channel d of the layer's input u keeps N state entries of its own, in
float32.  With a step ``D_t[d] > 0`` (the caller's ``softplus``), ``A[d, n] =
-exp(a_log[d, n])`` and B and C ``[N]`` a token, shared by the channels::

    h_t[d, n] = exp(D_t[d] A[d, n]) h_{t-1}[d, n] + D_t[d] B_t[n] u_t[d]
    y_t[d]    = sum_n C_t[n] h_t[d, n] + D[d] u_t[d]            h_0 = 0

The decay is one number a channel AND a state entry (Mamba-2 has one a head,
which is what lets ``ops/ssd.py`` turn its chunks into products on the MXU):
there is no product form here, the work is ~20 vector operations a token, a
channel and a state entry's tile, and a ``[S, channels, N]`` tensor (2.7 GB at
8192 x 5120 x 16) is what an implementation must never put in HBM.  Neither
body does: what is kept for the backward pass is the operands and the state at
each block's start (``[S / T, N, channels]`` float32), and a block's states
are made again from its start.

ONE entry, ``selective_scan``, and two bodies, by the rule of
``ops/short_conv.py::convolved``: the Mosaic pair where the caller says
``in_place``, the backend is a TPU and the shape is one it takes
(``_why_not``), else the ``jnp`` body; ``body_counts()`` says which a trace
took, and why.

* **Mosaic** (``_forward``, ``_backward``, one ``jax.custom_vjp``): grid
  (batch, channel blocks, time blocks), the time blocks innermost and in
  order, the state ``[N, lanes]`` float32 in VMEM scratch with the channels on
  the lanes and the state entries on the sublanes, a ``fori_loop`` over groups
  of ``_GROUP`` rows whose tokens are unrolled.  B and C reach the kernel as
  ``[S / G, N, G]`` (made outside: 0.5 MB), so that a token's B is a column
  that broadcasts over the lanes.  The backward call walks the time blocks in
  reverse: it makes a block's states again from the saved start into VMEM,
  then ``dh_t = a_{t+1} dh_{t+1} + C_t dy_t`` and from it the gradients of u
  and the step (written where they lie), of B and C (a lane reduction a
  token; partial sums a channel block, added up outside), of A (accumulated
  in the call over time, a row a batch entry) and of D (likewise).
* **jnp** (``_plain``): the sequence in chunks of ``CHUNK`` rows
  (``ops/chunking.py``'s padding), a chunk's states by
  ``jax.lax.associative_scan`` over its rows (``[B, chunk, channels, N]``
  float32: 42 MB a tensor at this module's cell), the state carried from
  chunk to chunk by a ``lax.scan``, each chunk under a checkpoint; autodiff.
  Any shape, any backend, any partitioning.

State, decays and steps are float32 whatever the inputs' dtype (u, B and C
may be bf16; a bf16 carry loses the recurrence in a few hundred tokens); y
comes back in the dtype of u, rounded once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.common import trace_counts as _trace_counts
from horovod_tpu.ops.chunking import padded

__all__ = ["CHUNK", "selective_scan", "selective_scan_states", "body_counts"]

CHUNK = 128            # rows of the jnp body's chunk
_LANES = 128
_GROUP = 16            # tokens a step of a body's walk unrolls: a bf16 tile
_BLOCK_LANES = (512, 256, 128)      # channels of a block, the widest first
_BLOCK_ROWS = (128, 64, 32, 16)     # tokens of a time block
_VMEM_LIMIT = 64 * 1024 * 1024

_BODY = "selective_scan.body"
_MOSAIC = "one Mosaic call each way"
NOT_IN_PLACE = "the attention_fn does not read its operands in place"
NO_TPU = "the backend is no TPU"
_OFF_TILING = "channels off the lane tiling or a state off the sublane's"
_NO_ROW_BLOCK = "no block of rows divides the sequence"


def body_counts() -> dict:
    """``{"mosaic": n, "plain": {reason: n}}``: how many traced calls of
    ``selective_scan`` took the Mosaic pair, and how many the ``jnp`` body,
    by reason.  Process-global, counted once a TRACE."""
    plain = _trace_counts.counts(_BODY)
    return {"mosaic": plain.pop(_MOSAIC, 0), "plain": plain}


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _first_dividing(n: int, sizes) -> int:
    return next((size for size in sizes if n % size == 0), 0)


def _why_not(shape, states: int):
    """None where the Mosaic pair takes ``u`` of ``shape [B, S, channels]``
    with ``states`` state entries a channel, else the reason it does not."""
    if len(shape) != 3 or shape[2] % _LANES or states % 8:
        return _OFF_TILING
    if not _first_dividing(shape[1], _BLOCK_ROWS):
        return _NO_ROW_BLOCK
    return NO_TPU if _interpret() else None


# -- the jnp body ------------------------------------------------------------

def _combine(earlier, later):
    """``h -> a h + x`` twice: the later step after the earlier one."""
    a_e, x_e = earlier
    a_l, x_l = later
    return a_e * a_l, a_l * x_e + x_l


@jax.checkpoint
def _chunk(state, operands):
    """A chunk's rows from ``state [B, C, N]`` (float32): the state it
    leaves, and ``(y [B, Q, C]`` float32 without the skip, the state it
    started from)``.  ``operands``: a ``[C, N]``, and the chunk's u, step
    ``[B, Q, C]`` and b, c ``[B, Q, N]``, all float32."""
    a, u, delta, b, c = operands
    decay = jnp.exp(delta[..., None] * a)
    written = (delta * u)[..., None] * b[:, :, None, :]
    decays, states = jax.lax.associative_scan(_combine, (decay, written),
                                              axis=1)
    states = states + decays * state[:, None]
    return states[:, -1], (jnp.einsum("bqcn,bqn->bqc", states, c), state)


def _walk(u, delta, a_log, b, c, chunk):
    """y ``[B, S', C]`` float32 without the skip (S' whole chunks) and the
    chunks' starting states ``[S' / chunk, B, C, N]`` float32."""
    batch, _, channels = u.shape
    a = -jnp.exp(a_log.astype(jnp.float32))

    def by_chunk(x):
        # Rows that pad the last chunk neither write nor decay (step 0).
        x = padded(x.astype(jnp.float32), chunk)
        return jnp.moveaxis(x.reshape(batch, -1, chunk, x.shape[-1]), 1, 0)

    u, delta, b, c = (by_chunk(x) for x in (u, delta, b, c))
    _, (y, started) = jax.lax.scan(
        lambda state, x: _chunk(state, (a, *x)),
        jnp.zeros((batch, channels, a.shape[1]), jnp.float32),
        (u, delta, b, c))
    return jnp.moveaxis(y, 0, 1).reshape(batch, -1, channels), started


def _plain(u, delta, a_log, b, c, d, chunk=CHUNK):
    y = _walk(u, delta, a_log, b, c, chunk)[0][:, :u.shape[1]]
    return (y + d.astype(jnp.float32) * u.astype(jnp.float32)).astype(u.dtype)


def selective_scan_states(u, delta, a_log, b, c, *, chunk: int = CHUNK):
    """The state each chunk of ``chunk`` rows started from, ``[S / chunk, B,
    channels, N]`` float32 (the ``jnp`` body's): for counters and tests."""
    return _walk(u, delta, a_log, b, c, chunk)[1]


# -- the Mosaic pair ----------------------------------------------------------

def _column(x, j):
    """Token j's column ``[N, 1]`` of a group's ``[N, G]``: it broadcasts
    over the lanes."""
    return x[:, j:j + 1]


def _fwd_kernel(u_ref, delta_ref, a_ref, b_ref, c_ref, d_ref, y_ref,
                start_ref, h_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    start_ref[...] = h_ref[...]
    a = a_ref[...]
    skip = d_ref[...]

    def group(g, h):
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        delta = delta_ref[rows, :]
        u = u_ref[rows, :].astype(jnp.float32)
        written = delta * u
        b, c = b_ref[g], c_ref[g]
        ys = []
        for j in range(_GROUP):
            h = (jnp.exp(delta[j:j + 1] * a) * h
                 + written[j:j + 1] * _column(b, j))
            ys.append(jnp.sum(h * _column(c, j), axis=0, keepdims=True))
        y_ref[rows, :] = (jnp.concatenate(ys, axis=0) + skip * u
                          ).astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, u_ref.shape[0] // _GROUP, group,
                                   h_ref[...])


def _bwd_kernel(u_ref, delta_ref, a_ref, b_ref, c_ref, d_ref, g_ref,
                start_ref, du_ref, ddelta_ref, db_ref, dc_ref, da_ref,
                dd_ref, dh_ref, hs_ref):
    """A time block, the blocks in reverse.  ``hs_ref [T + 1, N, lanes]``:
    the state before each of the block's tokens and behind the last, made
    again from ``start_ref``; ``dh_ref``: what the later blocks send back
    into the state behind this block's last token."""
    n_groups = u_ref.shape[0] // _GROUP

    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    a = a_ref[...]
    skip = d_ref[...]

    def again(g, h):
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        delta = delta_ref[rows, :]
        written = delta * u_ref[rows, :].astype(jnp.float32)
        b = b_ref[g]
        for j in range(_GROUP):
            hs_ref[g * _GROUP + j] = h
            h = (jnp.exp(delta[j:j + 1] * a) * h
                 + written[j:j + 1] * _column(b, j))
        return h

    hs_ref[u_ref.shape[0]] = jax.lax.fori_loop(0, n_groups, again,
                                               start_ref[...])
    lane = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape[1:], 1)

    def group(k, carry):
        dh, da = carry
        g = n_groups - 1 - k
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        delta = delta_ref[rows, :]
        u = u_ref[rows, :].astype(jnp.float32)
        dy = g_ref[rows, :].astype(jnp.float32)
        written = delta * u
        b, c = b_ref[g], c_ref[g]
        db, dc = jnp.zeros_like(b), jnp.zeros_like(c)
        dwritten, ddecayed = [], []
        for j in reversed(range(_GROUP)):
            t = g * _GROUP + j
            dy_t = dy[j:j + 1]
            dh = dh + _column(c, j) * dy_t
            dc = jnp.where(lane == j, jnp.sum(
                hs_ref[t + 1] * dy_t, axis=1, keepdims=True), dc)
            db = jnp.where(lane == j, jnp.sum(
                dh * written[j:j + 1], axis=1, keepdims=True), db)
            dwritten.append(jnp.sum(dh * _column(b, j), axis=0,
                                    keepdims=True))
            decay = jnp.exp(delta[j:j + 1] * a)
            # d loss / d (delta_t a): the decay's own derivative is itself.
            dlog = dh * hs_ref[t] * decay
            ddecayed.append(jnp.sum(dlog * a, axis=0, keepdims=True))
            da = da + dlog * delta[j:j + 1]
            dh = decay * dh
        dwritten = jnp.concatenate(dwritten[::-1], axis=0)
        du_ref[rows, :] = (dwritten * delta + skip * dy).astype(du_ref.dtype)
        ddelta_ref[rows, :] = (jnp.concatenate(ddecayed[::-1], axis=0)
                               + dwritten * u)
        db_ref[g] = db
        dc_ref[g] = dc
        dd_ref[...] += dy * u
        return dh, da

    dh, da = jax.lax.fori_loop(0, n_groups, group,
                               (dh_ref[...], jnp.zeros_like(a)))
    dh_ref[...] = dh
    da_ref[...] += da


def _blocks(shape):
    """(rows of a time block, lanes of a channel block) for u ``shape``."""
    return (_first_dividing(shape[1], _BLOCK_ROWS),
            _first_dividing(shape[2], _BLOCK_LANES))


def _by_group(x):
    """``[B, S, N] -> [B, S / G, N, G]`` float32: a group's tokens on the
    lanes, the state entries on the sublanes."""
    batch, seq, states = x.shape
    return jnp.swapaxes(x.astype(jnp.float32).reshape(
        batch, seq // _GROUP, _GROUP, states), 2, 3)


def _specs(shape, states, reverse):
    """The block specs both calls share: (a block of ``[B, S, C]``, of A
    ``[N, C]``, of B and C by group, of D ``[1, C]``, of the saved
    starts), the grid, and the two block sizes.  ``reverse``: the time
    blocks from the last to the first."""
    batch, seq, channels = shape
    rows, lanes = _blocks(shape)
    n_time = seq // rows

    def at(i):
        return n_time - 1 - i if reverse else i

    return ((pl.BlockSpec((None, rows, lanes),
                          lambda b, ch, i: (b, at(i), ch)),
             pl.BlockSpec((states, lanes), lambda b, ch, i: (0, ch)),
             pl.BlockSpec((None, rows // _GROUP, states, _GROUP),
                          lambda b, ch, i: (b, at(i), 0, 0)),
             pl.BlockSpec((1, lanes), lambda b, ch, i: (0, ch)),
             pl.BlockSpec((None, None, states, lanes),
                          lambda b, ch, i: (b, at(i), 0, ch))),
            (batch, channels // lanes, n_time), rows, lanes)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _forward(u, delta, a, b, c, d, interpret):
    """``(y, the state each time block started from [B, S / T, N, C])``;
    ``a [N, C]`` is A transposed, b and c ``_by_group``'s, ``d [1, C]``."""
    batch, seq, channels = u.shape
    states = a.shape[0]
    (block, of_a, by_group, of_d, start), grid, rows, lanes = _specs(
        u.shape, states, reverse=False)
    call = pl.pallas_call(
        _fwd_kernel, grid=grid,
        in_specs=[block, block, of_a, by_group, by_group, of_d],
        out_specs=[block, start],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(
                       (batch, seq // rows, states, channels), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((states, lanes), jnp.float32)],
        compiler_params=_params(), interpret=interpret)
    with _scopes.span(_scopes.MOSAIC_SSCAN):
        return call(u, delta, a, b, c, d)


def _backward(u, delta, a, b, c, d, g, starts, interpret):
    """The gradients of u, the step, A ``[N, C]``, B and C by group and D
    ``[1, C]``, in float32 but for u's, from y's cotangent g."""
    batch, seq, channels = u.shape
    states = a.shape[0]
    (block, of_a, by_group, of_d, start), grid, rows, lanes = _specs(
        u.shape, states, reverse=True)
    n_time = grid[2]
    partial = pl.BlockSpec(
        (None, None, rows // _GROUP, states, _GROUP),
        lambda b, ch, i: (b, ch, n_time - 1 - i, 0, 0))
    of_batch = pl.BlockSpec((None, states, lanes),
                            lambda b, ch, i: (b, 0, ch))
    of_rows = pl.BlockSpec((None, _GROUP, lanes),
                           lambda b, ch, i: (b, 0, ch))
    groups = (batch, grid[1], seq // _GROUP, states, _GROUP)
    call = pl.pallas_call(
        _bwd_kernel, grid=grid,
        in_specs=[block, block, of_a, by_group, by_group, of_d, block,
                  start],
        out_specs=[block, block, partial, partial, of_batch, of_rows],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(u.shape, jnp.float32),
                   jax.ShapeDtypeStruct(groups, jnp.float32),
                   jax.ShapeDtypeStruct(groups, jnp.float32),
                   jax.ShapeDtypeStruct((batch, states, channels),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((batch, _GROUP, channels),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((states, lanes), jnp.float32),
                        pltpu.VMEM((rows + 1, states, lanes), jnp.float32)],
        compiler_params=_params(), interpret=interpret)
    with _scopes.span(_scopes.MOSAIC_SSCAN):
        du, ddelta, db, dc, da, dd = call(u, delta, a, b, c, d, g, starts)

    def by_token(x):
        """``[B, blocks, S / G, N, G] -> [B, S, N]``, the blocks added."""
        return jnp.swapaxes(x.sum(axis=1), 2, 3).reshape(batch, seq, states)

    return (du, ddelta, da.sum(axis=0), by_token(db), by_token(dc),
            dd.sum(axis=(0, 1)))


def _operands(u, delta, a_log, b, c, d):
    return (u, delta.astype(jnp.float32),
            -jnp.exp(a_log.astype(jnp.float32)).T, _by_group(b),
            _by_group(c), d.astype(jnp.float32)[None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _mosaic(u, delta, a_log, b, c, d, interpret):
    return _forward(*_operands(u, delta, a_log, b, c, d), interpret)[0]


def _mosaic_fwd(u, delta, a_log, b, c, d, interpret):
    y, starts = _forward(*_operands(u, delta, a_log, b, c, d), interpret)
    return y, (u, delta, a_log, b, c, d, starts)


def _mosaic_bwd(interpret, kept, g):
    u, delta, a_log, b, c, d, starts = kept
    operands = _operands(u, delta, a_log, b, c, d)
    du, ddelta, da, db, dc, dd = _backward(*operands, g, starts, interpret)
    # A = -exp(a_log): d A / d a_log = A.
    return (du, ddelta.astype(delta.dtype),
            (da * operands[2]).T.astype(a_log.dtype), db.astype(b.dtype),
            dc.astype(c.dtype), dd.astype(d.dtype))


_mosaic.defvjp(*_scopes.rules(
    "selective_scan._mosaic", _mosaic_fwd, _mosaic_bwd))


# -- the one entry ------------------------------------------------------------

def selective_scan(u, delta, a_log, b, c, d, in_place: bool):
    """``y [B, S, C]`` of the recurrence above, in the dtype of u.

    u ``[B, S, C]``; ``delta [B, S, C]`` the steps (positive: the caller's
    ``softplus``; float32); ``a_log [C, N]`` (``A = -exp(a_log)``); b, c
    ``[B, S, N]``; ``d [C]`` the skip.  The state starts at zero and ends
    with the sequence.  ``in_place`` is the caller's word that this trace may
    hold Mosaic calls on operands where they lie (``models/llama.py::
    _reads_in_place``): the scan is then the Mosaic pair, on a TPU and where
    the shape is one it takes (``_why_not``); elsewhere ``_plain``.  Which
    body a trace took, and why, ``body_counts()`` says."""
    why = _why_not(u.shape, a_log.shape[1]) if in_place else NOT_IN_PLACE
    _trace_counts.note(_BODY, why or _MOSAIC)
    if why is None:
        return _mosaic(u, delta, a_log, b, c, d, False)
    return _plain(u, delta, a_log, b, c, d)
