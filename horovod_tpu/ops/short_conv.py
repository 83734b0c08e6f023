"""A linear-attention or state-space layer's short convolution, its bias,
its SiLU and its heads' L2 norm (or a double-gated layer's two gates around
it) as one Mosaic pass forward and one backward.

``models/llama.py::GatedDeltaNet`` sends q, k and v through a causal
depthwise convolution of a few taps, a SiLU and (q and k) a per-head L2 norm;
``Mamba2`` sends its x, B and C channels through one such convolution with a
BIAS a channel, and no norm: elementwise work on a row and the rows just
before it (``convolved``, this module's one entry).  Written in ``jnp``
(``_convolved_plain``, which stays
for every path that may hold no Mosaic call, and as the tests' yardstick)
XLA:TPU runs it as chains of float32 fusions that write and read ``[B, S, channels]`` float32 arrays
between them: 51 ms of a 594 ms step at 8192 x 11,520 channels, where the
bytes of the bf16 tensors allow 5 (PERF.md, PR 38 and PR 40).  Here the
chain crosses HBM once each way: ``short_conv`` is a ``jax.custom_vjp`` of
two calls that read and write the ``[B, S, heads * d]`` tensors where the
projections leave them, whatever d is (96 and 192 are no lane tiles: a
block takes the whole last axis).

**Forward.**  A grid over (batch row, block of rows).  A step holds a block
of rows of y, all channels, and the sublane tile of rows just before it (the
same operand a second time; zero before position 0 of every batch row).  In
VMEM, in float32: K shifted multiply-adds (the shift is a sublane rotate of
the block with its history in front), the bias, SiLU, each head's sum of
squares, ``rsqrt(. + 1e-6)``, the scale, one cast, one store.

**A bias or none.**  Whether the filter has a bias is what the trace sees
(``bias is None``): with one it is one more whole operand, ``[1, C]``
float32 beside the taps, added where ``c`` is formed (``_conv``), and its
gradient eight rows more in the taps' output block.  Without one the two
calls have no operand for it and no row for its gradient: they lower to
what they lowered to before the pass took a bias (a linear layer runs nine
such calls a step).

**Gates or none.**  ``models/llama.py::GatedShortConv`` (LFM2's ``"conv"``
layer) has no SiLU and no norm: one projection gives B, C and z, and the
mixer is ``C (taps * (B z))``, two multiplicative gates around a filter of 3
taps.  ``gated`` is what the trace sees (static, like a bias or a norm): y
is then ``[B, S, 3 C]``, the projection's output where it lies, a block of
rows holds all three thirds (each starts at a lane tile), and the same two
walks form ``B z`` where they read y, leave the SiLU out and multiply by C;
backward ``dc = g C`` takes the place of the SiLU's derivative, and the one
result beside the taps' partial sums is the ``[B, S, 3 C]`` cotangent, ``dB
= dp z``, ``dC = g c`` and ``dz = dp B`` each where its third is (dp the
filter's transpose on dc).  The calls' operand counts are a call's without
gates, and a call without gates lowers to what it lowered to before.

**Channels where they lie.**  ``Mamba2``'s x, B and C are a run of channels
in the middle of ``in_proj``'s output.  Cut out for the call they are a
copy a pass and, as the backward call's residual, 201 MB beside the
projection through the scan's backward pass (``peak_hbm_gb`` 12.707 ->
12.876 in the nemotron cell: my chip runs, PR 53).  ``first`` names the
channel they start at instead: y's three operands are then windows of the
wider array placed by element (``pl.Element``; a lane tile's multiple), the
residual is the projection itself, and the cotangent goes back padded with
zeros, which XLA joins with the other channels' as it did.  ``first is
None`` builds the blocked specs of every call before it.

**A head's sum.**  Heads are runs of d lanes that straddle the 128-lane
tiles, so a head's sum and its way back to the head's lanes are products
with the heads' 0/1 indicator on the MXU, inside the body.  To float32
rounding, not one bf16 pass: the float32 operand is cut into three bf16
pieces (``_pieces``: 24 bits of mantissa between them), each piece's
product with the 0/1 matrix is exact in the float32 accumulator, and the
three are added.  Three passes where ``highest`` takes six.

**Backward.**  The same walk.  It reads y with a tile of rows before AND
after the block and the cotangent g with a tile after, makes ``c = conv(y)
+ bias``,
``s = silu(c)`` and the norm again for the block's rows and the K - 1 behind
them, and with ``u = s n`` (n the head's ``rsqrt``)::

    ds = scale n (g - u sum_head(g u))
    dc = ds sigma(c) (1 + c (1 - sigma(c)))
    dy[t] = sum_i taps[i] dc[t + K - 1 - i]          nothing from beyond the row's end
    dtaps[i] = sum_t dc[t] y[t - (K - 1) + i]
    dbias = sum_t dc[t]                              the block's own rows alone

The taps' gradient is a ``[K, 8, channels]`` float32 output whose block stays
put while the grid walks a batch row's blocks (eight partial sums a tap: the
adds stay on the VPU; XLA adds the eight and the batch rows); the bias's is
a ``[K + 1]``-th such group in the same block.  No float32 array of the
activations' shape is written to HBM in either call, and the residuals are
y, the taps and the bias: what ``jax.checkpoint`` kept for the plain body.

Which body a trace took is counted (``body_counts``), as
``ops/flash_attention.py::layout_counts`` counts layouts.  A Mosaic call is
the caller's choice (the partitioner cannot split one): ``convolved`` takes
this pass only where its caller says that the trace may hold Mosaic calls
on operands where they lie (``in_place``: ``llama.py::LlamaLayer`` reads it
off the model's ``attention_fn``), as for the rotation.  Off-TPU the calls
run in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.common import trace_counts as _trace_counts

__all__ = ["convolved", "short_conv", "body_counts", "NOT_IN_PLACE"]

_LANES = 128
_TILE = 8         # rows of a float32 sublane tile: the history the body uses
_HALO = 16        # rows of the block that brings it: a bf16 sublane tile
_GROUP = 16       # rows a step of a body's walk works on: divides every block
_FWD_LANES = 1024  # lanes a step of the forward body's walk works on
_BWD_LANES = 512   # and of the backward body's, which carries five arrays
_EPS = 1e-6
# Upper bound on a block of y in either call, counted at four bytes an element
# (its float32 values are what the body works on): all channels of as many rows.
_BLOCK_BYTES = 6 * 1024 * 1024
_VMEM_LIMIT = 100 * 1024 * 1024

# Which body ``convolved`` took (``common/trace_counts.py``): the Mosaic
# pass, or the plain one by reason.
_BODY = "short_conv.body"
_FUSED = "one Mosaic pass each way"
NOT_IN_PLACE = "the attention_fn does not read its operands in place"
_NO_ROW_BLOCK = "no block of rows divides the sequence"
_TOO_MANY_TAPS = "more taps than a sublane tile of history holds"
_NOT_WHOLE_HEADS = "the channels are not whole heads"
_NOT_AT_A_TILE = "the channels are no run from a lane tile on in the array"


def body_counts() -> dict:
    """``{"fused": n, "plain": {reason: n}}``: how many traced calls of
    ``convolved`` took the Mosaic pass, and how many the ``jnp`` body, by
    reason.  Process-global, counted once a TRACE."""
    plain = _trace_counts.counts(_BODY)
    return {"fused": plain.pop(_FUSED, 0), "plain": plain}


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pick_rows(s: int, width: int) -> int:
    """The largest block of rows that divides ``s`` and keeps a block of y
    under ``_BLOCK_BYTES``; 0 if none of the tiling's sizes divides it."""
    for rows in (512, 256, 128, 64, 32, _GROUP):
        if s % rows == 0 and rows * width * 4 <= _BLOCK_BYTES:
            return rows
    return 0


def _why_not(shape, taps_shape, heads: int, first=None, gated=False):
    """None where ``short_conv`` takes ``y`` of ``shape [B, S, channels]``
    with ``taps_shape [K, channels]`` (or, with ``first``, the channels
    ``first : first + taps_shape[1]`` of a wider y; with ``gated`` three
    times the channels, each third from a lane tile on), else the reason it
    does not."""
    width = taps_shape[1]
    if len(shape) != 3 or width % heads or (
            first is None and (3 * width if gated else width) != shape[2]):
        return _NOT_WHOLE_HEADS
    if gated and width % _LANES:
        return _NOT_AT_A_TILE
    # (A window of a wider array is whole lane tiles from a lane tile on.)
    if first is not None and (first % _LANES or width % _LANES
                              or first + width > shape[2]):
        return _NOT_AT_A_TILE
    if taps_shape[0] - 1 > _TILE:
        return _TOO_MANY_TAPS
    if not _pick_rows(shape[1], shape[2]):
        return _NO_ROW_BLOCK
    return None


# -- what both bodies share ------------------------------------------------
#
# A body walks its block in pieces that stay in vector registers: a loop
# over chunks of lanes, and inside it a loop over groups of _GROUP rows (a
# bf16 sublane tile).  Written as operations on the whole block, every
# intermediate is a block-sized array that the compiler stores and loads
# again, and the ONE vector-store slot paces the pass (0.65 ms a forward
# call of q's on the v5e where the pieces take 0.32: my chip runs, PR 40).

def _each_chunk(width: int, lanes: int, chunk) -> None:
    """``chunk(which lanes, how many)`` for every chunk of ``lanes`` lanes:
    the whole ones in a loop (one copy of the body in the program, one trace
    of it), what is left of the width beside it."""
    whole = width // lanes
    if whole == 1:
        chunk(slice(0, lanes), lanes)
    elif whole:
        def step(at, carry):
            chunk(pl.ds(pl.multiple_of(at * lanes, _LANES), lanes), lanes)
            return carry

        jax.lax.fori_loop(0, whole, step, 0)
    if width % lanes:
        chunk(slice(whole * lanes, width), width % lanes)


_AFTER = "after"      # in place of a group's number: the rows after the block


def _group(j, rows: int):
    """The rows of group ``j`` (traced) of a block of ``rows``, or with
    ``_AFTER`` the ``_HALO`` rows that follow the block in the backward
    body's scratch."""
    if j is _AFTER:
        return pl.ds(rows, _HALO)
    return pl.ds(pl.multiple_of(j * _GROUP, _GROUP), _GROUP)


def _rows_of(ref, j, lanes):
    return ref[_group(j, ref.shape[0]), lanes].astype(jnp.float32)


def _moved(lanes, by: int):
    """A chunk's ``lanes`` (``_each_chunk``'s: a slice, or a ``pl.ds`` from a
    lane tile's multiple) ``by`` lanes on, ``by`` whole lane tiles."""
    if isinstance(lanes, slice):
        return slice(lanes.start + by, lanes.stop + by)
    return pl.ds(pl.multiple_of(lanes.start + by, _LANES), lanes.size)


def _filter_reads(read, gated: bool, width: int):
    """What the filter reads at a chunk's lanes, through ``read(lanes)`` of y:
    y itself, or with gates ``B z``, y's first third times its last."""
    if not gated:
        return read
    return lambda lanes: read(lanes) * read(_moved(lanes, 2 * width))


def _shifted(prev, cur, k):
    """``cur [m, L]`` and the ``_TILE`` rows before it: cur as it is and
    moved down by 1 .. k - 1 rows, the rows before it moving in."""
    ext = jnp.concatenate([prev, cur], axis=0)
    # roll(x, b)[t] = x[t - b]: the row b before.
    return [cur] + [pltpu.roll(ext, back, 0)[_TILE:] for back in range(1, k)]


def _conv(seen, taps_ref, bias_ref, lanes):
    """The causal convolution from ``_shifted``'s list, and the filter's
    bias where it has one (``bias_ref [1, C]``, else None)."""
    k = taps_ref.shape[0]
    c = seen[0] * taps_ref[k - 1:k, lanes]
    for back in range(1, k):
        c = c + seen[back] * taps_ref[k - 1 - back:k - back, lanes]
    if bias_ref is not None:
        c = c + bias_ref[:, lanes]
    return c


def _bias_first(rest, biased: bool):
    """A body's references behind the taps: the bias's (None where the
    filter has none), and the others."""
    return (rest[0], rest[1:]) if biased else (None, rest)


def _pieces(x):
    """float32 x as three bf16 pieces whose sum is x to float32 rounding."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, low


def _sigmoid(c):
    """``1 / (1 + exp(-c))`` to float32 rounding: the transcendental unit's
    approximate reciprocal and two Newton steps on it.  Two, because the
    interpreted call's approximate reciprocal is a bfloat16 quotient (nine
    bits, 36 after two steps); on the v5e the second moves no digit of a
    float32 result (PERF.md, PR 40).  The exact quotient costs a dozen
    operations more (its special cases; the denominator here is in
    [1, 1e35]), and ``tanh`` on that unit is good to five digits only."""
    x = 1.0 + jnp.exp(-jnp.maximum(c, -80.0))
    r = pl.reciprocal(x, approx=True)
    for _ in range(2):
        r = r * (2.0 - x * r)
    return r


def _head_sums(p_ref, first, to_head_ref):
    """The heads' sums ``[n, Kp]`` of the float32 array whose three pieces
    are ``p_ref[first:first + 3]``: each piece's product with the 0/1
    indicator is exact in the float32 accumulator.  A head's sum arrives
    three times, once a group of ``to_head_ref``'s columns."""
    return sum(jnp.dot(p_ref[first + j], to_head_ref[...],
                       preferred_element_type=jnp.float32) for j in range(3))


def _over_lanes(a_head, to_lanes_ref, heads):
    """``a_head [n, Kp]`` float32, a head's value in each of the three
    groups of columns (as ``_head_sums`` leaves it), on the head's lanes
    ``[n, C]``: group g keeps piece g of the value, so ONE product with the
    indicator's transpose adds the three pieces up."""
    hi, mid, low = _pieces(a_head)
    group = jax.lax.broadcasted_iota(jnp.int32, a_head.shape, 1) // (
        _group_width(heads))
    pieces = jnp.where(group == 0, hi, jnp.where(group == 1, mid, low))
    return jnp.dot(pieces, to_lanes_ref[...],
                   preferred_element_type=jnp.float32)


def _before(y_ref, before_ref, j, lanes, at_start):
    """The ``_TILE`` rows before group j of the block: the end of group j - 1,
    or for the block's first group the end of the rows that came with it
    (zero at a batch row's start).  One expression for every group, so that
    a walk is one loop with one copy of its body."""
    inside = _rows_of(y_ref, jnp.maximum(j - 1, 0), lanes)[_TILE:]
    outside = before_ref[:, lanes].astype(jnp.float32)[_HALO - _TILE:]
    return jnp.where(j > 0, inside, jnp.where(at_start, 0.0, outside))


def _for_groups(n_groups: int, group) -> None:
    def step(j, carry):
        group(j)
        return carry

    jax.lax.fori_loop(0, n_groups, step, 0)


def _fwd_kernel(y_ref, before_ref, taps_ref, *rest, heads, biased, gated):
    # y_ref, o_ref: [rows, C]; before_ref: the _HALO rows before the block;
    # taps_ref: [K, C] float32; with a bias (biased) bias_ref [1, C] float32
    # behind it.  With a norm (heads is not None): scale_ref
    # [1, 1] in SMEM (an operand, so that q's call and k's are one program),
    # to_head_ref [C, Kp] and to_lanes_ref [Kp, C], the heads' 0/1 indicator
    # (_indicators) and its transpose; scratch s_ref [rows, C] float32 and
    # p_ref [3, rows, C] bf16.  With gates (gated) y_ref and before_ref are
    # [.., 3 C], the thirds B, C and z: o = C conv(B z), no SiLU.
    rows = y_ref.shape[0]
    k, width = taps_ref.shape
    normed = heads is not None
    bias_ref, rest = _bias_first(rest, biased)
    if normed:
        scale_ref, to_head_ref, to_lanes_ref, o_ref, s_ref, p_ref = rest
    else:
        o_ref, = rest
    at_start = pl.program_id(1) == 0

    def chunk(lanes, _):
        def group(j):
            prev = _filter_reads(lambda at: _before(
                y_ref, before_ref, j, at, at_start), gated, width)(lanes)
            cur = _filter_reads(lambda at: _rows_of(y_ref, j, at), gated,
                                width)(lanes)
            c = _conv(_shifted(prev, cur, k), taps_ref, bias_ref, lanes)
            if gated:
                o_ref[_group(j, rows), lanes] = (_rows_of(
                    y_ref, j, _moved(lanes, width)) * c).astype(o_ref.dtype)
                return
            s = c * _sigmoid(c)
            here = _group(j, rows)
            if not normed:
                o_ref[here, lanes] = s.astype(o_ref.dtype)
                return
            s_ref[here, lanes] = s
            for piece, part in enumerate(_pieces(s * s)):
                p_ref[piece, here, lanes] = part

        _for_groups(rows // _GROUP, group)

    _each_chunk(width, _FWD_LANES, chunk)
    if normed:
        inv = scale_ref[0, 0] * jax.lax.rsqrt(
            _head_sums(p_ref, 0, to_head_ref) + _EPS)
        o_ref[...] = (s_ref[...] * _over_lanes(
            inv, to_lanes_ref, heads)).astype(o_ref.dtype)


def _bwd_kernel(y_ref, before_ref, after_ref, g_ref, g_after_ref, taps_ref,
                *rest, heads, biased, gated):
    # As _fwd_kernel, with the _HALO rows after the block of y and of the
    # cotangent g; dy_ref: [rows, C]; dtaps_ref: [K * _TILE, C] float32,
    # eight partial sums a tap, the same block for every step of a batch
    # row; with a bias eight rows more behind them, its gradient's partial
    # sums.  With a norm, scratch over the block's rows AND the group after
    # them: p_ref [6, n, C] bf16 (the pieces of s s and of g s), norm_ref and
    # back_ref [n, C] float32 (the two sums on their way back).  With gates
    # y_ref, its two halos and dy_ref are [.., 3 C], the thirds B, C and z
    # and their cotangents; g_ref is [rows, C].
    rows = y_ref.shape[0]
    k, width = taps_ref.shape
    groups = rows // _GROUP    # and then the rows after
    normed = heads is not None
    bias_ref, rest = _bias_first(rest, biased)
    if normed:
        (scale_ref, to_head_ref, to_lanes_ref, dy_ref, dtaps_ref,
         p_ref, norm_ref, back_ref) = rest
    else:
        dy_ref, dtaps_ref = rest
    i = pl.program_id(1)
    at_start, at_end = i == 0, i == pl.num_programs(1) - 1

    @pl.when(at_start)
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    def operands(j, lanes):
        """Group j's rows of y, the tile of rows before them, and its rows
        of g; ``_AFTER`` is the ``_HALO`` rows after the block, from where no
        cotangent comes back at a batch row's end."""
        if j is _AFTER:
            cur = _filter_reads(lambda at: after_ref[:, at].astype(
                jnp.float32), gated, width)(lanes)
            g = jnp.where(at_end, 0.0,
                          g_after_ref[:, lanes].astype(jnp.float32))
            return cur, _filter_reads(lambda at: _rows_of(
                y_ref, groups - 1, at), gated, width)(lanes)[_TILE:], g
        return (_filter_reads(lambda at: _rows_of(y_ref, j, at), gated,
                              width)(lanes),
                _filter_reads(lambda at: _before(
                    y_ref, before_ref, j, at, at_start), gated, width)(lanes),
                _rows_of(g_ref, j, lanes))

    if normed:
        # With n the head's rsqrt and u = s n: sum_head(g u) = n sum_head(g
        # s), so both sums are of products the first walk can form.
        def chunk_sums(lanes, _):
            def sums(j):
                cur, prev, g = operands(j, lanes)
                c = _conv(_shifted(prev, cur, k), taps_ref, bias_ref, lanes)
                s = c * _sigmoid(c)
                here = _group(j, rows)
                for piece, part in enumerate(_pieces(s * s) + _pieces(g * s)):
                    p_ref[piece, here, lanes] = part

            _for_groups(groups, sums)
            sums(_AFTER)

        _each_chunk(width, _FWD_LANES, chunk_sums)
        inv = jax.lax.rsqrt(_head_sums(p_ref, 0, to_head_ref) + _EPS)
        norm_ref[...] = _over_lanes(inv, to_lanes_ref, heads)
        back_ref[...] = _over_lanes(
            inv * inv * inv * _head_sums(p_ref, 3, to_head_ref),
            to_lanes_ref, heads)

    def chunk(lanes, size):
        def dc_of(j):
            cur, prev, g = operands(j, lanes)
            seen = _shifted(prev, cur, k)
            c = _conv(seen, taps_ref, bias_ref, lanes)
            if gated:
                # y = C c: dc = g C, and dC = g c beside it.
                of_gate = _moved(lanes, width)
                gate = (after_ref[:, of_gate].astype(jnp.float32)
                        if j is _AFTER else _rows_of(y_ref, j, of_gate))
                return g * gate, seen, g * c
            sig = _sigmoid(c)
            if normed:
                here = _group(j, rows)
                ds = scale_ref[0, 0] * (norm_ref[here, lanes] * g
                                        - c * sig * back_ref[here, lanes])
            else:
                ds = g
            return ds * sig * (1.0 + c * (1.0 - sig)), seen, None

        def group(j, carry):
            # From the block's last group to its first: dc of the rows
            # behind a group is the carry.  dy[t] = sum_a taps[K-1-a] dc[t+a].
            behind, sums = carry
            dc, seen, d_gate = dc_of(j)
            ext = jnp.concatenate([dc, behind], axis=0)
            dy = dc * taps_ref[k - 1:k, lanes]
            for ahead in range(1, k):
                # roll(x, n - a)[t] = x[t + a]: the row a behind.
                dy = dy + pltpu.roll(ext, ext.shape[0] - ahead, 0)[
                    :dc.shape[0]] * taps_ref[k - 1 - ahead:k - ahead, lanes]
            if gated:
                # dy is d(B z): dB = dy z, dz = dy B, each where its third is.
                here, of_z = _group(j, rows), _moved(lanes, 2 * width)
                for at, d in ((lanes, dy * _rows_of(y_ref, j, of_z)),
                              (_moved(lanes, width), d_gate),
                              (of_z, dy * _rows_of(y_ref, j, lanes))):
                    dy_ref[here, at] = d.astype(dy_ref.dtype)
            else:
                dy_ref[_group(j, rows), lanes] = dy.astype(dy_ref.dtype)
            # dtaps[K-1-b] = sum_t dc[t] y[t - b], eight partial sums a tap;
            # behind the taps' (zip stops there) dbias = sum_t dc[t].
            sums = tuple(
                acc + (dc * y_back).reshape(-1, _TILE, size).sum(axis=0)
                for acc, y_back in zip(sums, seen)) + tuple(
                acc + dc.reshape(-1, _TILE, size).sum(axis=0)
                for acc in sums[k:])
            return dc[:_TILE], sums

        carry = (dc_of(_AFTER)[0][:_TILE],
                 (jnp.zeros((_TILE, size), jnp.float32),) * (k + biased))
        _, sums = jax.lax.fori_loop(
            0, groups, lambda t, carry: group(groups - 1 - t, carry), carry)
        for back, acc in enumerate(sums[:k]):
            at = (k - 1 - back) * _TILE
            dtaps_ref[at:at + _TILE, lanes] += acc
        for acc in sums[k:]:
            dtaps_ref[k * _TILE:, lanes] += acc

    _each_chunk(width, _BWD_LANES, chunk)


# -- the two calls ---------------------------------------------------------

def _group_width(heads: int) -> int:
    """Columns a group of the indicator takes: the heads, to a multiple of
    eight."""
    return -(-heads // _TILE) * _TILE


def _indicators(width: int, heads: int):
    """The heads' 0/1 indicator three times side by side ``[width, Kp]``
    (column ``g * _group_width + h`` is head h, for g in 0, 1, 2; Kp whole
    lane tiles, the columns left over belong to no lane), and its transpose:
    bf16.  Three times because a float32's three bf16 pieces then meet the
    transpose in ONE product (``_over_lanes``); 30 heads are 96 columns of
    one 128-lane tile, so the second and third copy cost nothing."""
    group = _group_width(heads)
    padded = -(-3 * group // _LANES) * _LANES
    column = jnp.arange(padded)
    head = jnp.where(column < 3 * group, column % group, -1)
    to_head = (jnp.arange(width)[:, None] // (width // heads)
               == head[None, :]).astype(jnp.bfloat16)
    return to_head, to_head.T


def _specs(rows: int, width: int, n_blocks: int, first=None):
    """A block of rows, and the ``_HALO`` rows before and after it (the
    sequence's first and last where there are none: the body masks them).
    With ``first`` the array is wider than the filter: the three are
    windows of it from that channel on, placed by element."""
    per = rows // _HALO

    def before_at(i):
        return jnp.maximum(i * per - 1, 0)

    def after_at(i):
        return jnp.minimum((i + 1) * per, n_blocks * per - 1)

    if first is not None:
        def window(n, at):
            return pl.BlockSpec((None, pl.Element(n), pl.Element(width)),
                                lambda b, i: (b, at(i) * _HALO, first))

        return (window(rows, lambda i: i * per), window(_HALO, before_at),
                window(_HALO, after_at))
    block = pl.BlockSpec((None, rows, width), lambda b, i: (b, i, 0))
    before = pl.BlockSpec((None, _HALO, width),
                          lambda b, i: (b, before_at(i), 0))
    after = pl.BlockSpec((None, _HALO, width),
                         lambda b, i: (b, after_at(i), 0))
    return block, before, after


def _with_constants(operands, specs, taps, bias, scale, heads, width):
    """The operands every step sees whole: the taps in float32, the bias
    ``[1, C]`` in float32 where there is one (None: no operand) and, with a
    norm, the scale (a scalar in SMEM) and the two indicator matrices."""
    def whole(x):
        operands.append(x)
        specs.append(pl.BlockSpec(x.shape, lambda b, i: (0, 0)))

    whole(taps.astype(jnp.float32))
    if bias is not None:
        whole(bias.astype(jnp.float32).reshape(1, width))
    if heads is not None:
        operands.append(scale.astype(jnp.float32).reshape(1, 1))
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        for indicator in _indicators(width, heads):
            whole(indicator)


# (Jits: a step traces each body once a shape, and lowers it once for the
# forward pass, once for the recomputation and once backward, not once a
# layer and call: a body is a few hundred operations, and 27 of them cost
# the cell 10 s of set-up.  q's call and k's are one program: the scale is
# an operand, and ``heads`` is None where there is no norm.  ``bias`` is None
# where the filter has none: another trace, with no operand for it; ``first``
# is None where y is the filter's channels and no more.  ``interpret`` is
# static, so the cached trace is of the mode asked for.)
@functools.partial(jax.jit, static_argnames=("heads", "first", "interpret",
                                             "gated"))
def _forward(y, taps, bias, scale, heads, first, interpret, gated=False):
    (b, s, _), width = y.shape, taps.shape[1]
    across = 3 * width if gated else width      # of y's blocks: B, C and z
    rows = _pick_rows(s, across)
    block = _specs(rows, width, s // rows)[0]
    operands, specs = [y, y], list(_specs(rows, across, s // rows, first)[:2])
    _with_constants(operands, specs, taps, bias, scale, heads, width)
    scratch = [] if heads is None else [
        pltpu.VMEM((rows, width), jnp.float32),
        pltpu.VMEM((3, rows, width), jnp.bfloat16)]
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, biased=bias is not None,
                          gated=gated),
        grid=(b, s // rows),
        in_specs=specs,
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, s, width), y.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_SHORT_CONV):
        return call(*operands)


@functools.partial(jax.jit, static_argnames=("heads", "first", "interpret",
                                             "gated"))
def _backward(y, taps, bias, g, scale, heads, first, interpret, gated=False):
    (b, s, total), (k, width) = y.shape, taps.shape
    sums = k + (bias is not None)     # a tap's eight partial sums, the bias's
    across = 3 * width if gated else width
    rows = _pick_rows(s, across)
    block, _, after = _specs(rows, width, s // rows)
    operands = [y, y, y, g, g]
    specs = [*_specs(rows, across, s // rows, first), block, after]
    _with_constants(operands, specs, taps, bias, scale, heads, width)
    n = rows + _HALO                  # the block's rows and those after
    scratch = [] if heads is None else [
        pltpu.VMEM((6, n, width), jnp.bfloat16),
        pltpu.VMEM((n, width), jnp.float32),
        pltpu.VMEM((n, width), jnp.float32)]
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, biased=bias is not None,
                          gated=gated),
        grid=(b, s // rows),
        in_specs=specs,
        out_specs=[specs[0] if gated else block,
                   pl.BlockSpec((None, sums * _TILE, width),
                                lambda b, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(y.shape if gated else g.shape,
                                        y.dtype),
                   jax.ShapeDtypeStruct((b, sums * _TILE, width),
                                        jnp.float32)],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_SHORT_CONV):
        dy, dtaps = call(*operands)
    dtaps = dtaps.reshape(b, sums, _TILE, width).sum(axis=(0, 2))
    if first is not None:             # nothing comes back to the others
        dy = jnp.pad(dy, ((0, 0), (0, 0), (first, total - first - width)))
    if bias is None:
        return dy, dtaps.astype(taps.dtype), None
    return dy, dtaps[:k].astype(taps.dtype), dtaps[k].astype(bias.dtype)


def _norm_of(heads, scale):
    """The two calls' ``scale`` and ``heads``: no heads where no norm."""
    if scale is None:
        return jnp.float32(0.0), None
    return jnp.float32(scale), heads


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 5, 6))
def short_conv(y, taps, heads, scale, bias=None, first=None, gated=False):
    """``silu(taps * y + bias)`` for ``y [B, S, heads * d]``, ``taps [K,
    heads * d]`` and ``bias [heads * d]`` or None (``*`` the causal
    depthwise convolution, zero history before position 0 of every batch
    row), each head L2-normed (``rsqrt(sum of squares + 1e-6)``) and
    multiplied by ``scale`` where that is not None; float32 inside, the
    dtype of y out.  With ``first`` y is wider and the filter reads its
    channels ``first : first + heads * d``.  With ``gated`` y is ``[B, S, 3
    C]``, its thirds B, C and z, and the result ``C (taps * (B z))``: two
    multiplicative gates and no SiLU, bias or norm.  One Mosaic call, and
    one for all the gradients; ``_why_not`` says which shapes it takes."""
    scale, heads = _norm_of(heads, scale)
    return _forward(y, taps, bias, scale, heads=heads, first=first,
                    interpret=_interpret(), gated=gated)


def _short_conv_fwd(y, taps, heads, scale, bias, first, gated):
    return (short_conv(y, taps, heads, scale, bias, first, gated),
            (y, taps, bias))


def _short_conv_bwd(heads, scale, first, gated, kept, g):
    y, taps, bias = kept
    scale, heads = _norm_of(heads, scale)
    return _backward(y, taps, bias, g, scale, heads=heads, first=first,
                     interpret=_interpret(), gated=gated)


short_conv.defvjp(*_scopes.rules(
    "short_conv", _short_conv_fwd, _short_conv_bwd))


# -- the plain body, and the one entry --------------------------------------

def _short_convolution(x, taps):
    """Causal depthwise convolution along the sequence, one filter a
    channel and zero history before position 0: ``y[t] = sum_i taps[i] *
    x[t - (K - 1) + i]``.  x ``[B, S, C]``, taps ``[K, C]``; float32 out.
    K shifted multiply-adds.  XLA:TPU does NOT make one pass of them and
    what follows: with the SiLU, the heads' norm and their gradients the
    trace shows chains of float32 fusions over ``[B, S, C]``, 51 ms of a
    594 ms step at 8192 x 11,520 channels (PERF.md, PR 38).  The one pass
    is ``short_conv``, which ``convolved`` takes where it may; this is the
    body of every other path, and the tests' yardstick."""
    seq, k = x.shape[1], taps.shape[0]
    x = x.astype(jnp.float32)
    y = x * taps[k - 1]
    for back in range(1, k):
        y = y + jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :seq] * taps[
            k - 1 - back]
    return y


def over_heads(x, heads):
    """For ``x [.., heads * d]``: each head's sum ``[.., heads]``, and the
    function that spreads a value a head back over its d lanes.  Both are
    products with the heads' 0/1 indicator ``[heads * d, heads]`` (exact in
    float32 at ``highest``, and nothing beside the other products): a
    reshape to ``[.., heads, d]`` where d is no multiple of the 128 lanes
    (96, 192) has XLA:TPU relay the tensor, in float32, either side of
    every reduction (PERF.md, PR 38)."""
    width = x.shape[-1]
    of_head = (jnp.arange(width)[:, None] // (width // heads)
               == jnp.arange(heads)[None, :]).astype(jnp.float32)
    precision = jax.lax.Precision.HIGHEST
    return (jnp.matmul(x, of_head, precision=precision),
            lambda a_head: jnp.matmul(a_head, of_head.T, precision=precision))


@functools.partial(jax.checkpoint, static_argnums=(2, 3))
def _convolved_plain(y, taps, heads, scale, bias=None):
    """``convolved`` in ``jnp``: any shape, any partitioning.  Under a
    checkpoint: the backward pass keeps y and makes the float32 values
    between again."""
    out = _short_convolution(y, taps)
    if bias is not None:
        out = out + bias
    out = jax.nn.silu(out)
    if scale is not None:
        squares, spread = over_heads(out * out, heads)
        out = out * spread(scale * jax.lax.rsqrt(squares + 1e-6))
    return out.astype(y.dtype)


@jax.checkpoint
def _gated_plain(y, taps):
    """``convolved``'s gated form in ``jnp``: ``C (taps * (B z))`` of y's
    thirds B, C and z, float32 inside; under a checkpoint as
    ``_convolved_plain``."""
    b, c, z = (t.astype(jnp.float32) for t in jnp.split(y, 3, axis=-1))
    return (c * _short_convolution(b * z, taps)).astype(y.dtype)


def convolved(y, taps, heads, scale, in_place: bool, bias=None, first=None,
              gated: bool = False):
    """``silu(taps * y)``, ``[B, S, heads * d]`` in the dtype of y; each
    head L2-normed and multiplied by ``scale`` where that is not None.
    ``bias [heads * d]`` (a Mamba-2 layer's ``use_conv_bias``) or None is
    added before the SiLU, in either body; a call without one holds no
    operand for it.  ``first`` (static) or None: y is ``[B, S, wider]`` and
    the filter's channels are ``y[..., first : first + heads * d]``, which
    the pass reads where they lie and the ``jnp`` body cuts out.
    ``gated`` (static; an LFM2 layer's double-gated filter, ``models/llama.py
    ::GatedShortConv``): y is ``[B, S, 3 C]``, the thirds B, C and z as one
    projection left them, and the result ``C (taps * (B z))``, ``[B, S, C]``
    with no SiLU; ``heads`` 1 and neither ``scale``, ``bias`` nor ``first``
    go with it.
    ``in_place`` is the caller's word that this trace may hold Mosaic calls
    on operands where they lie: the chain is then ``short_conv``'s one pass
    forward and one backward, where the shape is one it takes
    (``_why_not``).  Elsewhere ``_convolved_plain``.  Which body a trace
    took, and why, ``body_counts()`` says."""
    if gated and not (heads == 1 and all(
            option is None for option in (scale, bias, first))):
        raise ValueError("a gated filter is C (taps * (B z)) alone: heads 1, "
                         "and no scale, bias or first")
    why = (_why_not(y.shape, taps.shape, heads, first, gated) if in_place
           else NOT_IN_PLACE)
    _trace_counts.note(_BODY, why or _FUSED)
    if why is None:
        return short_conv(y, taps, heads, scale, bias, first, gated)
    if gated:
        return _gated_plain(y, taps)
    if first is not None:
        y = y[..., first:first + taps.shape[1]]
    return _convolved_plain(y, taps, heads, scale, bias)
