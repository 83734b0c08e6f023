"""Mamba-2's state-space recurrence in its chunked form (state-space duality;
Dao & Gu, arXiv:2405.21060): the mixer of a ``Mamba2`` layer on the training
path.

A head keeps a state ``S`` (``[P, N]``, float32): P lanes of the head's
input u, each with N state entries.  With a step ``D_t > 0``, a decay ``a_t
= exp(D_t A)`` in (0, 1) (``A = -exp(A_log)``, one scalar a head) and B and C
``[N]`` shared by the heads of a group::

    S_t = a_t S_{t-1} + D_t u_t B_t^T        S_0 = 0
    y_t = S_t C_t  (+ D u_t, the skip)

Token by token that is 8192 dependent rank-one updates.  Here the sequence
is cut into chunks of Q rows (``CHUNK`` = 128, the published ``chunk_size``).
With ``l_i`` the log-decay summed from the chunk's start to its row i (so
every decay below is ``exp`` of a difference that is at most 0: no ``exp(-
l)`` is ever formed) and S the state the chunk starts from::

    Y = (M * (C B^T)) (D u) + e^l (C S^T)      M[i, j] = exp(l_i - l_j), j <= i
    S' = e^{l_Q} S + ((D u) e^{l_Q - l})^T B

``C B^T`` is formed once a GROUP, the masked product once a head.  The sums
``l`` are a product with a lower-triangular matrix of ones (``_summed``:
float32 to rounding; as ``jnp.cumsum`` XLA:TPU made a ``reduce-window`` of
them that took a third of the scan's time).  A chunk's ``[Q, Q]`` pieces are
of the configuration's ``chunk_size``; nothing here changes it.

**Two bodies, one rule** (``_why_not``; ``body_counts()`` says which a trace
took, and why not the other).

*The Mosaic pair* (``scan_rows``: one call forward, one backward; taken where
the caller says ``in_place`` -- the trace is not partitioned, PERF.md §3.3 --
the shapes are ones it takes and the backend is a TPU).  The grid is (batch
row, chunk, block of heads), the chunks in sequence (backward: in reverse), a
block of heads 512 lanes of u that share ONE group (8 heads of 64).  The
operands are read where they lie: u, B and C as lane blocks of the filter's
``[B, S, H P + 2 G N]`` result (u's block ``[Q, 512]``, B's and C's ``[Q,
N]``, N one lane tile); y leaves as rows ``[B, S, H P]``, which is what the
gates' call reads.  The heads' states stay in VMEM scratch as ``S^T [N,
512]`` float32 (rows the state's entries, lanes the heads'), so that ``C
S^T`` and ``B^T (D u e^{l_Q - l})`` are ONE product each for the block's
every head; only ``M * (C B^T)`` is a head's own, ``[Q, Q]`` walked as pieces
of 128 x 128 on and under the diagonal, two heads of 64 lanes sharing a lane
tile of u (each head's lanes kept under a mask on the bf16 operand, no
half-tile slice).  No ``[.., Q, Q]`` array leaves VMEM.

What is one number a row and head -- l, ``e^l``, ``e^{l_Q - l}``, D and ``D
e^{l_Q - l}``, five ``[B, S, H]`` float32 arrays of 4 MB -- XLA makes
(``_prepared``: the sums by the triangular product) and hands over in the
two layouts a step reads, both with the sequence along the lanes: l and D of
a block's heads as rows (what ``M`` reads across a row), and every quantity
as three bf16 pieces whose sum is the float32 value (``short_conv._pieces``'
way), which ONE product with the heads' 0/1 indicator puts on the heads'
lanes exactly (a float32 product on the MXU is one bf16 pass; in the kernel
the picking, summing and spreading took more passes than the recurrence:
PERF.md §5, PR 62).

Backward, the same walk in reverse with the state's cotangent in scratch.
The forward call of a differentiated trace also writes the state each chunk
started from (float32, ``[B, S / Q, N, H P]``: 268 MB a layer at 2 x 8192 x
4096, held while the layer's backward runs); the backward call makes ``M``
and ``B C^T`` again and, with ``v = (M * C B^T)^T dY + e^{l_Q - l} B dS'^T``
(so that ``du = D v``) and G the group's sum over heads of ``(D u) dY^T *
M^T``::

    dB = G C + (D u e^{l_Q - l}) dS'         dC = G^T B + (e^l dY) S
    dS = e^{l_Q} dS' + (e^l dY)^T C          d D = u . v + A revcumsum(d l)
    d l_i = dY_i . Y_i - (D u)_i . v_i       d A = sum D revcumsum(d l)
            (+ e^{l_Q} <dS', S> + sum_j (D u)_j . (v_j - v1_j) at row Q)

The row sums of ``dM * M`` ARE ``dY . Y`` and its column sums ``(D u) . v``,
so no ``[Q, Q]`` reduction is made; but inside a chunk the two cancel in the
sum, so both sides are formed from the SAME rounded operands (Y's intra-chunk
part is made again from the bf16 ``M * C B^T`` that v reads: with the
forward's rounded y the cancellation was lost and d dt was 3 % off, d a_log
90 %).  dB and dC are summed over a group's heads inside the call; a head's
sums over its lanes (d l, ``u . v``) leave as ``[B, H / 8, 16, S]`` float32
and XLA makes d dt and d a_log of them.

*The ``jnp`` body* (``_walk``): for every trace that may hold no Mosaic call
(CPU, GSPMD, ring attention), for shapes the rule refuses, and for
``ssd_states``.  All but the state's way from chunk to chunk runs for a slab
of chunks at once (``ops/chunking.py``, which the gated delta rule shares);
the chunks' states are carried in float32 by a ``lax.scan`` over the slab's
chunks whose step is two elementwise operations.  The backward pass is
autodiff of that under a checkpoint a slab.

**Precision, the same in both bodies.**  Cumulative log-decays, the masks,
the state and its cotangent stay in float32 whatever the inputs' dtype; the
products take their inputs in the dtype of u (bf16 on the training path) and
accumulate in float32, and the state is cast to that dtype only where a
product reads it, as ``ops/gated_delta.py`` does.  That module asks for
``highest`` precision where it inverts a triangular system in float32; there
is no such system here, and only the log-decays' sums ask for it.  (The
Mosaic pair rounds ``M * C B^T * D`` where the ``jnp`` body rounds ``M * C
B^T`` and ``D u``: a sum in another order, not another precision.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.common import trace_counts as _trace_counts
from horovod_tpu.ops.chunking import chunked, padded, slabs, unchunked
from horovod_tpu.ops.short_conv import _pieces

__all__ = ["CHUNK", "ssd_scan", "ssd_scan_rows", "scan_rows", "ssd_states",
           "body_counts", "NOT_IN_PLACE", "HEADS_OFF_THE_TILE",
           "STATE_OFF_THE_TILE", "NO_HEAD_BLOCK", "CHUNK_OFF_THE_TILE",
           "NO_TPU"]

CHUNK = 128

_LANES = 128
_PIECE = 128           # rows and columns of a piece of a chunk's [Q, Q]
_STEP_LANES = 512      # lanes of u a grid step takes: heads of ONE group
_VMEM_LIMIT = 64 * 1024 * 1024

_BODY = "ssd.body"
_MOSAIC = "one Mosaic call each way"
NOT_IN_PLACE = "the attention_fn does not read its operands in place"
HEADS_OFF_THE_TILE = "no one or two heads are a lane tile"
STATE_OFF_THE_TILE = "the state's entries are not one lane tile"
NO_HEAD_BLOCK = "no block of a group's heads is whole lane tiles"
CHUNK_OFF_THE_TILE = "the chunk is no multiple of 128 rows"
NO_TPU = "no TPU: the calls would run interpreted"


def body_counts() -> dict:
    """``{"mosaic": n, "plain": {reason: n}}``: how many traced calls of
    ``ssd_scan_rows`` took the Mosaic pair, and how many the ``jnp`` body,
    by reason.  Process-global, counted once a TRACE."""
    plain = _trace_counts.counts(_BODY)
    return {"mosaic": plain.pop(_MOSAIC, 0), "plain": plain}


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _heads_a_step(per: int, width: int) -> int:
    """How many of a group's ``per`` heads of ``width`` lanes a grid step
    takes: the most that divide the group, are whole lane tiles and at most
    ``_STEP_LANES``; 0 where none are."""
    return next((k for k in range(_STEP_LANES // width, 0, -1)
                 if per % k == 0 and (k * width) % _LANES == 0), 0)


def _why_not(heads: int, width: int, groups: int, state: int, chunk: int,
             in_place: bool):
    """None where the Mosaic pair takes H = ``heads`` heads of P = ``width``
    lanes in ``groups`` groups with N = ``state`` entries a lane at chunks
    of ``chunk`` rows, else the reason it does not."""
    if not in_place:
        return NOT_IN_PLACE
    if width not in (_LANES // 2, _LANES):
        return HEADS_OFF_THE_TILE
    if state != _LANES:
        return STATE_OFF_THE_TILE
    if heads % groups or not _heads_a_step(heads // groups, width):
        return NO_HEAD_BLOCK
    if chunk % _PIECE:
        return CHUNK_OFF_THE_TILE
    return NO_TPU if _interpret() else None


def _dot(spec, x, y):
    """A product on the MXU: inputs as they are, float32 out."""
    return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)


def _summed(x, reverse: bool = False):
    """``x [.., Q]`` (float32) summed along its last axis from the start to
    each entry (``reverse``: from each entry to the end): the product with
    a triangular matrix of ones, float32 to rounding (``highest``: on the
    MXU a float32 product is otherwise ONE bf16 pass).  As ``jnp.cumsum``
    XLA:TPU makes a ``reduce-window`` of it that runs at 6 GB/s."""
    q = x.shape[-1]
    before = jnp.arange(q)[:, None] <= jnp.arange(q)[None, :]
    ones = (before.T if reverse else before).astype(x.dtype)
    return jnp.matmul(x, ones, precision=jax.lax.Precision.HIGHEST)


# -- the Mosaic pair ----------------------------------------------------------
#
# A grid step: chunk n of batch row b, block ``hb`` of the heads.  What is
# one number a row and head (the summed log-decays l, e^l, e^{l_Q - l}, D
# and D e^{l_Q - l}: [B, S, H] float32, 4 MB a layer) XLA makes, and hands
# over in the two layouts a step reads (``_prepared``).

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_ROWS = 8              # rows a quantity takes in ``_prepared``'s second array
(_ELL, _E_ELL, _E_AFTER, _DT, _WRITTEN) = range(5)


def _mm(x, y, dims=_NN):
    return jax.lax.dot_general(x, y, dims,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _prepared(dt, a_log, a_step: int, chunk: int):
    """From ``dt [B, S, H]`` (float32) and ``a_log``, for blocks of ``a_step``
    heads, both with the SEQUENCE along the lanes (one transposition of dt,
    4 MB, and nothing of XLA's is moved across lanes after it): ``packed
    [B, H / a_step, 128, S]`` bf16, a block's rows the three bf16 pieces
    (``_rounded_pieces``: their sum is the float32 value) of five
    quantities for each of its heads -- row ``(3 k + p) a_step + j`` is piece
    p of quantity k of head j -- so that ONE product with a 0/1 matrix
    (``_spreads``) puts a quantity on its heads' lanes, exactly; and ``across
    [B, H / a_step, 16, S]`` float32, l and then D of the block's heads
    (what ``M`` reads across a row)."""
    batch, seq, heads = dt.shape
    blocks = heads // a_step
    dt = dt.transpose(0, 2, 1)                              # [B, H, S]
    log_decay = dt * -jnp.exp(a_log.astype(jnp.float32))[:, None]
    ell = _summed(log_decay.reshape(batch, heads, seq // chunk, chunk))
    after = jnp.exp(ell[..., -1:] - ell).reshape(dt.shape)
    ell = ell.reshape(dt.shape)

    def by_block(x, rows):
        x = x.reshape(batch, blocks, a_step, seq)
        return jnp.pad(x, ((0, 0), (0, 0), (0, rows - a_step), (0, 0)))

    pieces = [by_block(piece, a_step) for x in (
        ell, jnp.exp(ell), after, dt, dt * after)
        for piece in _rounded_pieces(x)]
    packed = jnp.concatenate(pieces + [jnp.zeros(
        (batch, blocks, _LANES - len(pieces) * a_step, seq), jnp.bfloat16)],
        axis=2)
    return packed, jnp.concatenate([by_block(ell, _ROWS),
                                    by_block(dt, _ROWS)], axis=2)


def _rounded_pieces(x):
    """``short_conv._pieces`` for XLA: float32 x as three bf16 pieces whose
    sum is x to float32 rounding.  The rounding is ``reduce_precision``'s:
    a convert to bf16 and back XLA may drop (it allows itself excess
    precision), and the pieces behind the first would then be zero."""
    pieces = []
    for _ in range(3):
        piece = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        pieces.append(piece.astype(jnp.bfloat16))
        x = x - piece
    return pieces


def _spreads(a_step: int, width: int):
    """``[5, 128, L]`` bf16: quantity k of ``_prepared``'s ``packed`` on its
    heads' lanes is ``packed^T @ spreads[k]`` (the three pieces add up in
    the float32 accumulator)."""
    row = jnp.arange(_LANES)[None, :, None]
    lane = jnp.arange(a_step * width)[None, None, :]
    k = jnp.arange(5)[:, None, None]
    return ((row // (3 * a_step) == k)
            & (row % a_step == lane // width)).astype(jnp.bfloat16)


def _gathers(a_step: int, width: int):
    """``[2, 16, L]`` bf16: a head's sum over its lanes of ``x [Q, L]`` is
    row j of ``gathers[0] x^T`` (j the head), and of ``gathers[1] x^T`` row
    ``8 + j``."""
    row = jnp.arange(2 * _ROWS)[None, :, None]
    lane = jnp.arange(a_step * width)[None, None, :]
    k = jnp.arange(2)[:, None, None]
    return ((row // _ROWS == k)
            & (row % _ROWS == lane // width)).astype(jnp.bfloat16)


def _pieces_of(q: int):
    """The pieces ``(i, j)`` of a chunk's ``[Q, Q]`` on and under the
    diagonal, and the rows (or columns) of piece k."""
    n = q // _PIECE
    return ([(i, j) for i in range(n) for j in range(i + 1)],
            lambda k: slice(k * _PIECE, (k + 1) * _PIECE))


def _is_own(shape, k: int, width: int):
    """Where a lane of a tile ``shape [Q, 128]`` is head k's of the tile's."""
    lane = _iota(shape, 1)
    return (lane >= k * width) & (lane < (k + 1) * width)


def _own_lanes(tile, k: int, width: int):
    """``tile [Q, 128]`` (bf16) with head k's ``width`` lanes kept and the
    tile's other head's at zero."""
    if width == _LANES:
        return tile
    return jnp.where(_is_own(tile.shape, k, width), tile,
                     jnp.zeros_like(tile))


def _on_every_lane(tile, k: int, width: int):
    """``tile [Q, 128]`` (float32, a head's value on each of its lanes)
    with head k's value on EVERY lane: the half turn puts it on the other
    head's."""
    if width == _LANES:
        return tile
    return jnp.where(_is_own(tile.shape, k, width), tile,
                     pltpu.roll(tile, width, 1))


def _whole_of(pieces):
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=0)


def _fwd_kernel(u_ref, b_ref, c_ref, packed_ref, rows_ref, e_ref, y_ref,
                *rest, width, keep):
    # u_ref, y_ref [Q, L]: the step's heads' lanes; b_ref, c_ref [Q, N];
    # packed_ref [128, Q] bf16 and rows_ref [16, Q] float32 (_prepared's);
    # e_ref [5, 128, L] bf16 (_spreads').  With ``keep`` a result more,
    # started_ref [N, L] float32: the state the chunk started from.
    # Scratch: s_ref [blocks, N, L] float32 (S^T of every block of heads),
    # cb_ref [Q, Q] float32.
    if keep:
        started_ref, s_ref, cb_ref = rest
    else:
        s_ref, cb_ref = rest
    q, lanes = u_ref.shape
    block = pl.program_id(2)
    pairs, rows = _pieces_of(q)
    a_tile = _LANES // width                    # heads a lane tile

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[block] = jnp.zeros(s_ref.shape[1:], jnp.float32)

    packed = packed_ref[...].T                              # [Q, 128]
    cb_ref[...] = _mm(c_ref[...], b_ref[...], _NT)
    under = _iota((_PIECE, _PIECE), 0) >= _iota((_PIECE, _PIECE), 1)
    for t in range(lanes // _LANES):
        tile = slice(t * _LANES, (t + 1) * _LANES)
        u = u_ref[:, tile]
        state = s_ref[block, :, tile]                       # [N, 128]
        if keep:
            started_ref[:, tile] = state
        ell, e_l, w = (_mm(packed, e_ref[k, :, tile])       # [Q, 128]
                       for k in (_ELL, _E_ELL, _WRITTEN))
        masked = [[] for _ in range(q // _PIECE)]   # a piece of y's rows:
        stepped = [[] for _ in range(q // _PIECE)]  # its products' operands
        for k in range(a_tile):
            h = t * a_tile + k
            own = _own_lanes(u, k, width)
            down = _on_every_lane(ell, k, width)
            for i, j in pairs:
                z = down[rows(i)] - rows_ref[h:h + 1, rows(j)]
                if i == j:
                    # exp of what is masked away never runs: above the
                    # diagonal the difference is positive.
                    z = jnp.where(under, z, -jnp.inf)
                m = (jnp.exp(z) * cb_ref[rows(i), rows(j)]
                     * rows_ref[_ROWS + h:_ROWS + h + 1, rows(j)])
                masked[i].append(m.astype(u.dtype))
                stepped[i].append(own[rows(j)])
        # A piece's products (a head's, a piece of the columns') are ONE,
        # their operands side by side along the contraction: the sum stays
        # in the MXU (6-8 % of the call alone on the v5e: PERF.md §5, PR 62).
        y = [_mm(jnp.concatenate(a, axis=1), jnp.concatenate(b, axis=0))
             for a, b in zip(masked, stepped)]
        read = _mm(c_ref[...], state.astype(u.dtype))       # C S^T
        y_ref[:, tile] = (_whole_of(y) + e_l * read).astype(y_ref.dtype)
        s_ref[block, :, tile] = state * e_l[q - 1:q] + _mm(
            b_ref[...], (u.astype(jnp.float32) * w).astype(u.dtype), _TN)


def _bwd_kernel(u_ref, b_ref, c_ref, packed_ref, rows_ref, e_ref, red_ref,
                dy_ref, started_ref, du_ref, db_ref, dc_ref, sums_ref,
                ds_ref, bc_ref, g_ref, *acc, width, per_group):
    # As _fwd_kernel (the chunks arrive in reverse), with red_ref [2, 16, L]
    # bf16 (_gathers'), dy_ref [Q, L] the result's cotangent, started_ref
    # [N, L] float32.  Results: du_ref [Q, L]; db_ref, dc_ref [Q, N], the
    # same block for a group's ``per_group`` steps; sums_ref [16, Q]
    # float32, a head's d l and then its u . v, the sequence along the
    # lanes.  Scratch: ds_ref [blocks, N, L] float32 (the cotangent of the
    # state the chunk leaves), bc_ref and g_ref [Q, Q] float32 (B C^T, and
    # the group's sum of (D u) dY^T * M^T so far) and, where a group is
    # several steps, acc [2, Q, N] float32.
    q, lanes = u_ref.shape
    block = pl.program_id(2)
    pairs, rows = _pieces_of(q)
    a_tile = _LANES // width
    dtype = u_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[block] = jnp.zeros(ds_ref.shape[1:], jnp.float32)

    packed = packed_ref[...].T                              # [Q, 128]
    bc_ref[...] = _mm(b_ref[...], c_ref[...], _NT)          # [j, i]
    g_ref[...] = jnp.zeros_like(g_ref)
    over = _iota((_PIECE, _PIECE), 1) >= _iota((_PIECE, _PIECE), 0)
    last_row = _iota((q, _LANES), 0) == q - 1
    sums = jnp.zeros(sums_ref.shape, jnp.float32)
    d_b = jnp.zeros(b_ref.shape, jnp.float32)
    d_c = jnp.zeros(c_ref.shape, jnp.float32)
    for t in range(lanes // _LANES):
        tile = slice(t * _LANES, (t + 1) * _LANES)
        u, dy = u_ref[:, tile], dy_ref[:, tile]
        ell, e_l, e_after, d_t = (_mm(packed, e_ref[k, :, tile])
                                  for k in (_ELL, _E_ELL, _E_AFTER, _DT))
        u32, dy32 = u.astype(jnp.float32), dy.astype(jnp.float32)
        stepped = (u32 * d_t).astype(dtype)                 # D u
        d_state = ds_ref[block, :, tile]                    # [N, 128]
        started = started_ref[:, tile]
        zeros = [jnp.zeros((_PIECE, _LANES), jnp.float32)] * (q // _PIECE)
        v, y = list(zeros), list(zeros)
        for k in range(a_tile):
            h = t * a_tile + k
            own_u = _own_lanes(stepped, k, width)
            own_dy = _own_lanes(dy, k, width)
            down = _on_every_lane(ell, k, width)
            for i, j in pairs:                  # rows j, lanes i: M^T
                z = rows_ref[h:h + 1, rows(i)] - down[rows(j)]
                if i == j:
                    z = jnp.where(over, z, -jnp.inf)
                m = jnp.exp(z)
                g_ref[rows(j), rows(i)] += m * _mm(
                    own_u[rows(j)], dy[rows(i)], _NT)
                m = (m * bc_ref[rows(j), rows(i)]).astype(dtype)
                v[j] = v[j] + _mm(m, own_dy[rows(i)])
                y[i] = y[i] + _mm(m, own_u[rows(j)], _TN)
        # d l: a row's dY . Y less a column's (D u) . v, the SAME rounded
        # operands on both sides (within a chunk the two cancel in the sum).
        x = dy32 * _whole_of(y) - stepped.astype(jnp.float32) * _whole_of(v)
        behind = e_after * _mm(b_ref[...], d_state.astype(dtype))
        v = _whole_of(v) + behind
        du_ref[:, tile] = (d_t * v).astype(du_ref.dtype)
        # The state's share: read by every row (e^l C S^T), kept by e^{l_Q}
        # and written by every row's D e^{l_Q - l}.
        through = d_t * u32 * behind
        x = x + dy32 * e_l * _mm(c_ref[...], started.astype(dtype)) - through
        x = x + jnp.where(last_row, e_l[q - 1:q] * jnp.sum(
            d_state * started, axis=0, keepdims=True) + jnp.sum(
                through, axis=0, keepdims=True), 0.0)
        for k, summed in enumerate((x, u32 * v)):
            sums = sums + sum(_mm(red_ref[k, :, tile], piece, _NT)
                              for piece in _pieces(summed))
        read = (dy32 * e_l).astype(dtype)                   # e^l dY
        d_b = d_b + _mm((u32 * d_t * e_after).astype(dtype),
                        d_state.astype(dtype), _NT)
        d_c = d_c + _mm(read, started.astype(dtype), _NT)
        ds_ref[block, :, tile] = d_state * e_l[q - 1:q] + _mm(
            c_ref[...], read, _TN)
    sums_ref[...] = sums
    g = g_ref[...].astype(dtype)                            # [j, i]
    d_b = d_b + _mm(g, c_ref[...])
    d_c = d_c + _mm(g, b_ref[...], _TN)
    if per_group == 1:
        db_ref[...] = d_b.astype(db_ref.dtype)
        dc_ref[...] = d_c.astype(dc_ref.dtype)
        return
    acc_ref, = acc
    first = block % per_group == 0

    @pl.when(first)
    def _():
        acc_ref[0] = d_b
        acc_ref[1] = d_c

    @pl.when(jnp.logical_not(first))
    def _():
        acc_ref[0] += d_b
        acc_ref[1] += d_c

    @pl.when(block % per_group == per_group - 1)
    def _():
        db_ref[...] = acc_ref[0].astype(db_ref.dtype)
        dc_ref[...] = acc_ref[1].astype(dc_ref.dtype)


def _grid(x, heads: int, groups: int, chunk: int, reverse: bool):
    """The grid and the blocks of the operands where they lie in ``x [B, S,
    H P + 2 G N]``: u's ``[Q, L]``, B's and C's ``[Q, N]``; of
    ``_prepared``'s two arrays; and a block ``[Q, L]`` of an array of rows
    ``[B, S, H P]``; the chunks in reverse for the backward call."""
    batch, seq, _ = x.shape
    state = _LANES
    width = (x.shape[2] - 2 * groups * state) // heads
    a_step = _heads_a_step(heads // groups, width)
    lanes = a_step * width
    per_group = heads // groups // a_step
    chunks = seq // chunk
    b_at = heads * width // state

    def at(n):
        return chunks - 1 - n if reverse else n

    specs = {
        "rows": pl.BlockSpec((None, chunk, lanes),
                             lambda b, n, h: (b, at(n), h)),
        "b": pl.BlockSpec((None, chunk, state),
                          lambda b, n, h: (b, at(n), b_at + h // per_group)),
        "c": pl.BlockSpec((None, chunk, state), lambda b, n, h: (
            b, at(n), b_at + groups + h // per_group)),
        "group": pl.BlockSpec((None, chunk, state),
                              lambda b, n, h: (b, at(n), h // per_group)),
        "packed": pl.BlockSpec((None, None, _LANES, chunk),
                               lambda b, n, h: (b, h, 0, at(n))),
        "across": pl.BlockSpec((None, None, 2 * _ROWS, chunk),
                               lambda b, n, h: (b, h, 0, at(n))),
        "started": pl.BlockSpec((None, None, state, lanes),
                                lambda b, n, h: (b, at(n), 0, h)),
    }
    grid = (batch, chunks, heads // a_step)
    return grid, specs, (width, a_step, per_group)


def _whole(x):
    return pl.BlockSpec(x.shape, lambda b, n, h: (0,) * x.ndim)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


# (Jits, as ``short_conv``'s: a step traces each body once a shape, not once
# a layer and pass.  ``interpret`` is static, so the cached trace is of the
# mode asked for.)
@functools.partial(jax.jit, static_argnames=("heads", "groups", "chunk",
                                             "keep", "interpret"))
def _forward(x, dt, a_log, heads, groups, chunk, keep, interpret):
    batch, seq, _ = x.shape
    grid, specs, (width, a_step, _) = _grid(x, heads, groups, chunk, False)
    lanes, inner = a_step * width, heads * width
    packed, across = _prepared(dt, a_log, a_step, chunk)
    spreads = _spreads(a_step, width)
    out_shape = [jax.ShapeDtypeStruct((batch, seq, inner), x.dtype)]
    out_specs = [specs["rows"]]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, seq // chunk, _LANES, inner), jnp.float32))
        out_specs.append(specs["started"])
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, width=width, keep=keep),
        grid=grid,
        in_specs=[specs["rows"], specs["b"], specs["c"], specs["packed"],
                  specs["across"], _whole(spreads)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((grid[2], _LANES, lanes), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_SSD_SCAN):
        return (*call(x, x, x, packed, across, spreads), packed, across)


@functools.partial(jax.jit, static_argnames=("heads", "groups", "chunk",
                                             "interpret"))
def _backward(x, a_log, packed, across, started, dy, heads, groups, chunk,
              interpret):
    batch, seq, _ = x.shape
    grid, specs, (width, a_step, per_group) = _grid(x, heads, groups, chunk,
                                                    True)
    lanes, inner, wide = a_step * width, heads * width, groups * _LANES
    spreads, gathers = _spreads(a_step, width), _gathers(a_step, width)
    scratch = [pltpu.VMEM((grid[2], _LANES, lanes), jnp.float32)] + [
        pltpu.VMEM((chunk, chunk), jnp.float32)] * 2
    if per_group > 1:
        scratch.append(pltpu.VMEM((2, chunk, _LANES), jnp.float32))
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, width=width, per_group=per_group),
        grid=grid,
        in_specs=[specs["rows"], specs["b"], specs["c"], specs["packed"],
                  specs["across"], _whole(spreads), _whole(gathers),
                  specs["rows"], specs["started"]],
        out_specs=[specs["rows"], specs["group"], specs["group"],
                   specs["across"]],
        out_shape=[jax.ShapeDtypeStruct((batch, seq, inner), x.dtype),
                   jax.ShapeDtypeStruct((batch, seq, wide), x.dtype),
                   jax.ShapeDtypeStruct((batch, seq, wide), x.dtype),
                   jax.ShapeDtypeStruct(across.shape, jnp.float32)],
        scratch_shapes=scratch,
        compiler_params=_params(),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_SSD_SCAN):
        du, db, dc, sums = call(x, x, x, packed, across, spreads, gathers,
                                dy, started)

    def a_head(rows, at):
        """``[B, blocks, 16, S]`` rows ``at ..`` -> ``[B, H, S]``."""
        return rows[:, :, at:at + a_step].reshape(batch, heads, seq)

    # d(dt A) is d l summed from each row to its chunk's end; A = -exp(a_log):
    # d a_log = A dA, dA the sum of dt times d(dt A).
    a = -jnp.exp(a_log.astype(jnp.float32))
    d_decay = _summed(a_head(sums, 0).reshape(batch, heads, -1, chunk),
                      reverse=True).reshape(batch, heads, seq)
    d_dt = a_head(sums, _ROWS) + a[:, None] * d_decay
    d_a = a * jnp.sum(a_head(across, _ROWS) * d_decay, axis=(0, 2))
    return (jnp.concatenate([du, db, dc], axis=-1), d_dt.transpose(0, 2, 1),
            d_a.astype(a_log.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def scan_rows(x, dt, a_log, heads, groups, chunk=CHUNK):
    """``y [B, S, H P]`` of the recurrence, in the dtype of x, for ``x [B,
    S, H P + 2 G N]`` that holds u, B and C side by side (the filter's
    result; N = 128), ``dt [B, S, H]`` float32 and ``a_log [H]``; S whole
    chunks.  One Mosaic call, and one for every gradient; ``_why_not`` says
    which shapes it takes (called directly it runs interpreted off the
    TPU)."""
    return _forward(x, dt, a_log, heads=heads, groups=groups, chunk=chunk,
                    keep=False, interpret=_interpret())[0]


def _scan_rows_fwd(x, dt, a_log, heads, groups, chunk):
    y, *kept = _forward(x, dt, a_log, heads=heads, groups=groups,
                        chunk=chunk, keep=True, interpret=_interpret())
    return y, (x, a_log, *kept)


def _scan_rows_bwd(heads, groups, chunk, kept, dy):
    x, a_log, started, packed, across = kept
    return _backward(x, a_log, packed, across, started, dy, heads=heads,
                     groups=groups, chunk=chunk, interpret=_interpret())


scan_rows.defvjp(*_scopes.rules("scan_rows", _scan_rows_fwd, _scan_rows_bwd))


# -- the plain body -----------------------------------------------------------

def _slab(state, operands):
    """A slab's chunks from ``state [B, G, R, P, N]`` (float32; R heads a
    group): the state the slab leaves, and ``(y [n, B, G, R, Q, P], the
    state each chunk started from [n, B, G, R, P, N])``.  ``operands``: u
    ``[n, B, G, R, Q, P]``, dt and the log-decay ``[n, B, G, R, Q]``
    (float32), b and c ``[n, B, G, Q, N]``."""
    u, dt, log_decay, b, c = operands
    dtype = u.dtype
    q = u.shape[-2]
    ell = _summed(log_decay)
    last = ell[..., -1:]
    rows = jnp.arange(q)[:, None]
    cols = jnp.arange(q)[None, :]
    # exp of what is masked away never runs: above the diagonal the
    # difference is positive and may overflow.
    decay = jnp.exp(jnp.where(rows >= cols,
                              ell[..., :, None] - ell[..., None, :],
                              -jnp.inf))
    cb = _dot("nbgik,nbgjk->nbgij", c, b)                   # once a group
    du = u.astype(jnp.float32) * dt[..., None]
    y = _dot("nbgrij,nbgrjp->nbgrip",
             (decay * cb[:, :, :, None]).astype(dtype), du.astype(dtype))
    written = _dot("nbgrjp,nbgjk->nbgrpk",
                   (du * jnp.exp(last - ell)[..., None]).astype(dtype), b)
    kept = jnp.exp(last[..., 0])[..., None, None]           # e^{l_Q}

    def chunk(state, x):
        kept, written = x
        return kept * state + written, state

    state, started = jax.lax.scan(chunk, state, (kept, written))
    y = y + jnp.exp(ell)[..., None] * _dot(
        "nbgik,nbgrpk->nbgrip", c, started.astype(dtype))
    return state, (y.astype(dtype), started)


def _walk(u, dt, a_log, b, c, chunk):
    """y ``[B, S', H, P]`` (S' whole chunks) and the chunks' starting
    states ``[N, B, H, P, N_state]`` float32, slab by slab."""
    batch, _, heads, width = u.shape
    groups, state = b.shape[2:]
    per = heads // groups
    dt = dt.astype(jnp.float32)
    log_decay = dt * -jnp.exp(a_log.astype(jnp.float32))
    # Rows that pad the last chunk neither write (dt 0) nor decay (0).
    u, dt, log_decay, b, c = (padded(x, chunk)
                              for x in (u, dt, log_decay, b, c))

    def by_group(x):
        """``[N, B, H, Q, ..] -> [N, B, G, R, Q, ..]``."""
        return x.reshape(*x.shape[:2], groups, per, *x.shape[3:])

    operands = tuple(slabs(x) for x in (
        *(by_group(chunked(x, chunk)) for x in (u, dt, log_decay)),
        chunked(b.astype(u.dtype), chunk), chunked(c.astype(u.dtype), chunk)))
    _, (y, started) = jax.lax.scan(
        jax.checkpoint(_slab),
        jnp.zeros((batch, groups, per, width, state), jnp.float32), operands)
    y = y.reshape(-1, batch, heads, *y.shape[-2:])
    return unchunked(y), started.reshape(-1, batch, heads, width, state)


# -- the entries --------------------------------------------------------------

def _skipped(y, u, d):
    if d is None:
        return y
    return (y.astype(jnp.float32) + d.astype(jnp.float32)[:, None]
            * u.astype(jnp.float32)).astype(u.dtype)


def ssd_scan(u, dt, a_log, b, c, d=None, *, chunk: int = CHUNK):
    """``y [B, S, H, P]`` of the recurrence above, in the dtype of u: the
    ``jnp`` body on operands cut out (a layer's entry is ``ssd_scan_rows``).

    u ``[B, S, H, P]``; ``dt [B, S, H]`` the steps (positive: the caller's
    ``softplus``; taken to float32); ``a_log [H]`` (``A = -exp(a_log)``); b,
    c ``[B, S, G, N]`` with H a multiple of G (head h reads group ``h // (H
    / G)``); ``d [H]`` the skip, or None for a caller that adds ``D u``
    itself.  The state starts at zero and ends with the sequence; a length
    that is no multiple of ``chunk`` is padded with rows that neither write
    nor decay."""
    seq = u.shape[1]
    return _skipped(_walk(u, dt, a_log, b, c, chunk)[0][:, :seq], u, d)


def ssd_scan_rows(x, dt, a_log, heads: int, groups: int, state: int,
                  in_place: bool, *, chunk: int = CHUNK):
    """``y [B, S, H P]`` (rows) of the recurrence for u, B and C where the
    filter left them: ``x [B, S, H P + 2 G N]`` holds u's ``H P`` channels,
    then B's and C's ``G N`` each (N = ``state``); ``dt [B, S, H]`` and
    ``a_log [H]`` as ``ssd_scan``'s; no skip (the gates add it).  ``in_place`` is the
    caller's word that this trace may hold Mosaic calls on operands where
    they lie: the scan is then ``scan_rows``'s one call forward and one
    backward, where the shapes are ones it takes (``_why_not``) and the
    backend a TPU.  Elsewhere the three are cut out and ``ssd_scan`` runs.
    Which body a trace took, and why, ``body_counts()`` says."""
    batch, seq, total = x.shape
    inner = total - 2 * groups * state
    why = _why_not(heads, inner // heads, groups, state, chunk, in_place)
    _trace_counts.note(_BODY, why or _MOSAIC)
    if why is None:
        # Rows that pad the last chunk neither write (dt 0) nor decay.
        y = scan_rows(padded(x, chunk), padded(dt.astype(jnp.float32), chunk),
                      a_log, heads, groups, chunk)
        return y[:, :seq]
    wide = groups * state
    y = ssd_scan(x[..., :inner].reshape(batch, seq, heads, -1), dt, a_log,
                 x[..., inner:inner + wide].reshape(batch, seq, groups, -1),
                 x[..., inner + wide:].reshape(batch, seq, groups, -1),
                 chunk=chunk)
    return y.reshape(batch, seq, inner)


def ssd_states(u, dt, a_log, b, c, *, chunk: int = CHUNK):
    """The state each chunk started from, ``[N, B, H, P, N_state]``
    float32: for counters and tests, no gradient of its own."""
    return _walk(u, dt, a_log, b, c, chunk)[1]
