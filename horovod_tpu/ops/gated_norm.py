"""A recurrent mixer's output norm and its gate as one Mosaic pass forward and
one backward, in both orders: what a Mamba-2 layer does between its scan and
``out_proj`` (the skip, the gate, THEN the grouped RMS norm: ``gated_norm``)
and what a gated delta-rule layer does between its rule and ``wo`` (a head's
RMS norm, THEN the gate: ``norm_gated``, at the end of this text).  One
module: one rule (``_why_not``), one counter (``body_counts``), one walk
(``_walked``, ``_each_chunk``, ``_specs``, ``_pick_rows``, ``_call``), and a
pair of bodies an order, picked by which layer calls and by the shape.

**The gate first (Mamba-2).**

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

**The norm first (Gated DeltaNet).**  With o the rule's output ``[B, S, H
d_v]``, z the gate's projection and w ONE weight ``[d_v]`` for all H heads::

    r = rsqrt(mean_head(o o) + eps)     n = o r     s = silu(z)
    out = n w s

and backward, again on the inputs alone (o, z, go)::

    dn = go w s                         dw = sum_rows,heads(go n s)
    do = r (dn - n mean_head(dn n))
    dz = go w n sigma(z) (1 + z (1 - sigma(z)))

No skip, no u, no D; w is tiled to the lanes outside (``_on_every_head``) and
dw leaves as eight partial sums a lane, which XLA adds over the eight, the
batch rows and a weight's lane of every head.  The same walk (``norm_gate``'s
two bodies, ``_norm_gate_fwd_kernel`` and ``_norm_gate_bwd_kernel``); what
differs is the way to a HEAD's mean, read from the shape (``_heads_a_step``,
``_on_heads``):

- a head that is whole lane tiles (Qwen3-Next's 32 heads of 128): a step
  takes as many heads as are 512 lanes (four) at 64 rows, and each head's
  mean is the sum of its tiles and one reduction across lanes;
- a head that straddles tiles where two or four heads do not (Olmo-Hybrid's
  30 heads of 192: a tile and a half, two are three tiles, 15 such pieces):
  a step takes such a piece, the shared tile enters each neighbour's sum
  under a lane mask, and gets both heads' roots back under the same mask.
  (The other way, the squares' three bf16 pieces times the heads' 0/1
  indicator on the MXU as ``short_conv._head_sums``, was timed alone and is
  slower: PERF.md §5, PR 61.)

A head wider than a step holds (over 512 lanes), or heads of which no one,
two or four are whole tiles (160 lanes), keep the ``jnp`` body,
``_norm_then_gate``: what ``models/llama.py::_gated_norm`` was.

**Where o lies.**  The rule's result is ``[N, B, H, chunk, d_v]`` before
its last transposition makes it ``[B, S, H, d_v]``, and the ``jnp`` body's
float32 fusion used to carry that transposition.  Where a head is whole lane
tiles and a step's rows are one chunk (``_rule_chunks``: heads of 128 at
chunks of 64) the calls take o, and give ``do``, AS THE RULE LEFT IT
(``_as_the_rule_left`` undoes the transposition and XLA cancels the two: in
the compiled step the operand is a bitcast of the rule's result), a grid
step's block being its chunks' every head, ``[rows / chunk, H, chunk,
d_v]`` beside z's ``[rows, C]``; a step of the walk puts the heads' tiles
side by side (``_heads_rows``).  At 192 the rule's last dimension pads to
256 lanes and a head's lanes are not where z's are: o then reaches the call
as rows, through XLA's transposing copy and reshape in bf16 (0.8 ms a pass
and layer at ``[1, 8192, 5760]``: PERF.md §5, PR 61).  The layer says which
by ``chunk``; a result never depends on it.

Which body a trace took is counted (``body_counts``), both orders under one
kind.  The Mosaic pass is taken where the caller says ``in_place`` (the
trace is not partitioned: PERF.md §3.3), the channels are whole lane tiles
and a group a whole number of them (the norm first: one, two or four heads),
a block of rows divides the sequence and the backend is a TPU (interpreted,
a call is many times slower than XLA:CPU's fusions, and every tiny CPU model
would run it; the tests run the pairs so by calling them).
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
from horovod_tpu.ops.short_conv import (_each_chunk, _sigmoid,
                                        over_heads)

__all__ = ["gated_norm", "skip_gate_norm", "skipped", "norm_gated",
           "norm_gate", "body_counts", "NOT_IN_PLACE", "OFF_THE_LANE_TILE",
           "GROUP_OFF_THE_TILE", "HEADS_OFF_THE_TILE", "NO_ROW_BLOCK",
           "NO_TPU"]

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
HEADS_OFF_THE_TILE = ("no one, two or four heads are whole lane tiles that a "
                      "step holds")
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


def _heads_a_step(heads: int, per: int) -> int:
    """How many heads of ``per`` lanes a step of the norm-first bodies' walk
    takes: the fewest of one, two or four that are whole lane tiles (a head
    of 192 lanes is one and a half, two are three), doubled while they
    divide the heads and stay within ``_GROUP_LANES`` (four heads of 128:
    a step's chain wants that many lanes to hide behind, ``_WALK``); 0
    where no such few heads are."""
    few = next((k for k in (1, 2, 4) if heads % k == 0
                and (k * per) % _LANES == 0 and k * per <= _GROUP_LANES), 0)
    while few and heads % (2 * few) == 0 and 2 * few * per <= _GROUP_LANES:
        few *= 2
    return few


def _why_not(shape, groups: int, in_place: bool, norm_first: bool = False):
    """None where the Mosaic pass takes ``y`` of ``shape [B, S, C]`` normed
    in ``groups`` groups (``norm_first``: heads, normed BEFORE the gate),
    else the reason it does not."""
    if not in_place:
        return NOT_IN_PLACE
    if len(shape) != 3 or shape[2] % _LANES:
        return OFF_THE_LANE_TILE
    if norm_first:
        if shape[2] % groups or not _heads_a_step(groups,
                                                  shape[2] // groups):
            return HEADS_OFF_THE_TILE
    elif shape[2] % groups or (shape[2] // groups) % _LANES:
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


# -- the norm FIRST, then the gate: a gated delta-rule layer's ---------------
#
# out = o rsqrt(mean_head(o o) + eps) w silu(z), the heads' ONE weight w
# [d_v] on every head's lanes.  A step of the walk takes ``_heads_a_step``
# heads' lanes and forms each head's mean from the step's lane tiles.

def _on_heads(x, per: int, then=None):
    """For ``x [rows, L]`` of ``L / per`` heads, L whole lane tiles: each
    head's mean over its ``per`` lanes (``then`` applied to it, ``[rows,
    1]``), ON the head's lanes ``[rows, L]``.  A head that is whole lane
    tiles is the sum of its tiles and one reduction across lanes; one that
    shares a tile with its neighbour (192 lanes: a tile and a half) takes
    its lanes of the shared tile under a mask, and the tile gets both
    heads' values back under the same mask."""
    rows, width = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    # A tile's heads: (head, its first lane of the tile, the lane behind).
    owners = [[(h, max(h * per - t, 0), min((h + 1) * per - t, _LANES))
               for h in range(t // per, (t + _LANES - 1) // per + 1)]
              for t in range(0, width, _LANES)]
    sums = {}
    for t, heads in enumerate(owners):
        tile = x[:, t * _LANES:(t + 1) * _LANES]
        for h, lo, hi in heads:
            part = tile if (lo, hi) == (0, _LANES) else jnp.where(
                (lane >= lo) & (lane < hi), tile, 0.0)
            sums[h] = part if h not in sums else sums[h] + part
    means = {h: jnp.sum(total, axis=-1, keepdims=True) * (1.0 / per)
             for h, total in sums.items()}
    if then is not None:
        means = {h: then(mean) for h, mean in means.items()}
    spread = []
    for heads in owners:
        on = jnp.broadcast_to(means[heads[-1][0]], (rows, _LANES))
        for h, _, hi in reversed(heads[:-1]):
            on = jnp.where(lane < hi, means[h], on)
        spread.append(on)
    return spread[0] if len(spread) == 1 else jnp.concatenate(spread, axis=1)


def _step_rows(rows: int, lanes: int) -> int:
    """Rows a step of the norm-first walk takes at ``lanes`` lanes: as
    many as keep it at no less than ``_WALK`` rows of ``_GROUP_LANES`` lanes
    (384 lanes: 128 rows, which read 3 % faster backward than 64 alone on
    the v5e, and 32 rows 27 % slower: my chip run, PR 61)."""
    return min(rows, _WALK * -(-_GROUP_LANES // lanes))


def _rule_chunks(shape, heads: int, chunk: int) -> bool:
    """Whether the norm-first bodies read o (and write do) where the gated
    delta rule leaves it, ``[N, B, H, chunk, d_v]``: a head is whole lane
    tiles (at 192 the last dimension pads to 256 and a head's lanes are not
    where z's are) and ONE chunk is a step's rows."""
    per = shape[2] // heads
    rows = _pick_rows(shape[1])
    return bool(chunk and per % _LANES == 0 and rows % chunk == 0
                and chunk == _step_rows(rows, _heads_a_step(heads, per) * per))


def _heads_rows(ref, here, at, lanes: int):
    """``ref[here, at]`` of a block of rows ``[rows, C]``; of a block as
    the rule leaves it, ``[chunks, H, rows a chunk, d_v]``, the same rows
    and ``lanes`` lanes: ``here`` is ONE chunk's rows, ``at`` whole heads'
    lanes, and the heads' ``[rows, d_v]`` tiles are put side by side."""
    if len(ref.shape) == 2:
        return ref[here, at]
    _, _, chunk, per = ref.shape
    heads = ref[here.start // chunk, pl.ds(at.start // per, lanes // per)]
    return jnp.concatenate(list(heads), axis=1)


def _store_heads_rows(ref, here, at, value):
    """``ref[here, at] = value``, for either block of ``_heads_rows``."""
    if len(ref.shape) == 2:
        ref[here, at] = value
        return
    _, _, chunk, per = ref.shape
    which, first = here.start // chunk, at.start // per
    for k in range(value.shape[1] // per):
        ref[which, first + k] = value[:, k * per:(k + 1) * per]


def _norm_gate_fwd_kernel(o_ref, z_ref, w_ref, out_ref, *, per, lanes, eps,
                          sigmoid=False):
    # z_ref, out_ref: [rows, C]; o_ref as they, or as the rule leaves it
    # (``_heads_rows``); w_ref (the [d_v] weight on every head's lanes):
    # [1, C] float32.  ``lanes`` a step: whole heads of per.  ``sigmoid``:
    # the gate is sigmoid(z), not silu(z).
    rows, width = z_ref.shape

    def chunk(at, _):
        def step(here, carry):
            o = _heads_rows(o_ref, here, at, lanes).astype(jnp.float32)
            z = z_ref[here, at].astype(jnp.float32)
            r = _on_heads(o * o, per, lambda m: jax.lax.rsqrt(m + eps))
            gate = _sigmoid(z) if sigmoid else z * _sigmoid(z)
            out_ref[here, at] = (o * r * w_ref[:, at] * gate
                                 ).astype(out_ref.dtype)
            return carry

        _walked(rows, step, 0, _step_rows(rows, lanes))

    _each_chunk(width, lanes, chunk)


def _norm_gate_bwd_kernel(o_ref, z_ref, go_ref, w_ref, do_ref, dz_ref,
                          sums_ref, *, per, lanes, eps, sigmoid=False):
    # As _norm_gate_fwd_kernel, with the cotangent go_ref and the two
    # results (do_ref a block as o_ref is); sums_ref: [_TILE, C] float32,
    # eight partial sums a lane of dw's (a head's lanes' owners are added
    # up outside), the same block for every step of a batch row.
    rows, width = z_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def chunk(at, size):
        def step(here, d_w):
            o = _heads_rows(o_ref, here, at, lanes).astype(jnp.float32)
            z, go = (ref[here, at].astype(jnp.float32)
                     for ref in (z_ref, go_ref))
            sig = _sigmoid(z)
            s = sig if sigmoid else z * sig
            r = _on_heads(o * o, per, lambda m: jax.lax.rsqrt(m + eps))
            n = o * r
            gw = go * w_ref[:, at]
            dn = gw * s
            _store_heads_rows(do_ref, here, at, (
                r * (dn - n * _on_heads(dn * n, per))).astype(do_ref.dtype))
            slope = (1.0 - sig) if sigmoid else (1.0 + z * (1.0 - sig))
            dz_ref[here, at] = (gw * n * sig * slope).astype(dz_ref.dtype)
            return d_w + (go * n * s).reshape(-1, _TILE, size).sum(axis=0)

        sums_ref[:, at] += _walked(
            rows, step, jnp.zeros((_TILE, size), jnp.float32),
            _step_rows(rows, lanes))

    _each_chunk(width, lanes, chunk)


# -- the two calls -----------------------------------------------------------

def _specs(rows: int, width: int):
    """A block of rows of an array's first ``width`` channels, and an
    operand ``[1, width]`` that every step sees whole."""
    return (pl.BlockSpec((None, rows, width), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, width), lambda b, i: (0, 0)))


def _chunks_spec(rows: int, shape):
    """The same block of rows of an array ``[N, B, H, chunk, d_v]``: a
    batch row's ``rows / chunk`` chunks, every head."""
    _, _, heads, chunk, per = shape
    return pl.BlockSpec((rows // chunk, None, heads, chunk, per),
                        lambda b, i: (i, b, 0, 0, 0))


def _constants(d, w, width: int):
    """D on its head's lanes and the norm's weight, ``[1, C]`` float32."""
    return (jnp.repeat(d.astype(jnp.float32), width // d.shape[0])[None],
            w.astype(jnp.float32)[None])


def _call(kernel, shape, blocks, wholes, results, sums: int, interpret):
    """One pass of ``kernel`` over the grid (batch row, block of rows) of
    ``shape [B, S, C]``: ``blocks`` are read a block of rows of their first
    C channels (an array of five dimensions: of the rule's chunks,
    ``_chunks_spec``), ``wholes`` ``[1, C]`` seen whole by every step;
    ``results`` (``ShapeDtypeStruct``s) leave the same way and, where
    ``sums`` is not 0, a float32 ``[B, sums, C]`` whose block stays put
    while the grid walks a batch row's blocks."""
    b, s, width = shape
    rows = _pick_rows(s)
    block, whole = _specs(rows, width)

    def spec(x):
        return block if x.ndim == 3 else _chunks_spec(rows, x.shape)

    out_specs = [spec(x) for x in results]
    out_shape = list(results)
    if sums:
        out_specs.append(pl.BlockSpec((None, sums, width),
                                      lambda b, i: (b, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, sums, width), jnp.float32))
    call = pl.pallas_call(
        kernel,
        grid=(b, s // rows),
        in_specs=[spec(x) for x in blocks] + [whole] * len(wholes),
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",
                                 "arbitrary" if sums else "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_GATED_NORM):
        return call(*blocks, *wholes)


# (Jits, as ``short_conv``'s: a step traces each body once a shape, not
# once a layer and pass.  ``interpret`` is static, so the cached trace is of
# the mode asked for.)
@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def _forward(y, u, z, d, w, groups, eps, interpret):
    width = y.shape[2]
    return _call(
        functools.partial(_kernels(width, groups)[0], groups=groups, eps=eps),
        y.shape, (y, u, z), _constants(d, w, width),
        (jax.ShapeDtypeStruct(y.shape, z.dtype),), 0, interpret)[0]


@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def _backward(y, u, z, d, w, go, groups, eps, interpret):
    b, _, width = y.shape
    dy, du, dz, sums = _call(
        functools.partial(_kernels(width, groups)[1], groups=groups, eps=eps),
        y.shape, (y, u, z, go), _constants(d, w, width),
        [jax.ShapeDtypeStruct(y.shape, x.dtype) for x in (y, u, z)],
        2 * _TILE, interpret)
    # Nothing comes back to the channels behind the first C.
    du, dz = (jnp.pad(x, ((0, 0), (0, 0), (0, wide.shape[2] - width)))
              for x, wide in ((du, u), (dz, z)))
    sums = sums.reshape(b, 2, _TILE, width).sum(axis=(0, 2))
    return (dy, du, dz,
            sums[1].reshape(d.shape[0], -1).sum(axis=1).astype(d.dtype),
            sums[0].astype(w.dtype))


def _norm_gate_kernel(kernel, width: int, heads: int, eps: float,
                      sigmoid: bool = False):
    per = width // heads
    return functools.partial(kernel, per=per, eps=eps,
                             lanes=_heads_a_step(heads, per) * per,
                             sigmoid=sigmoid)


def _on_every_head(w, heads: int):
    """The heads' one weight ``[d_v]`` on every head's lanes, ``[1, C]``
    float32."""
    return jnp.tile(w.astype(jnp.float32), heads)[None]


def _as_the_rule_left(o, heads: int, chunk: int):
    """``o [B, S, H d_v]`` as the calls take it: where ``_rule_chunks``
    allows ``[N, B, H, chunk, d_v]``, the inverse of what
    ``ops/gated_delta.py::gated_delta_rule`` does last, so that XLA cancels
    the two and the call reads the rule's result where it lies; else o."""
    if not _rule_chunks(o.shape, heads, chunk):
        return o
    b, s, width = o.shape
    return o.reshape(b, s // chunk, chunk, heads, width // heads).transpose(
        1, 0, 3, 2, 4)


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "chunk", "interpret", "sigmoid"))
def _norm_gate_forward(o, z, w, heads, eps, chunk, interpret, sigmoid=False):
    return _call(
        _norm_gate_kernel(_norm_gate_fwd_kernel, o.shape[2], heads, eps,
                          sigmoid),
        o.shape, (_as_the_rule_left(o, heads, chunk), z),
        (_on_every_head(w, heads),),
        (jax.ShapeDtypeStruct(o.shape, z.dtype),), 0, interpret)[0]


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "chunk", "interpret", "sigmoid"))
def _norm_gate_backward(o, z, w, go, heads, eps, chunk, interpret,
                        sigmoid=False):
    shape = o.shape
    o = _as_the_rule_left(o, heads, chunk)
    d_o, dz, sums = _call(
        _norm_gate_kernel(_norm_gate_bwd_kernel, shape[2], heads, eps,
                          sigmoid),
        shape, (o, z, go), (_on_every_head(w, heads),),
        (jax.ShapeDtypeStruct(o.shape, o.dtype),
         jax.ShapeDtypeStruct(shape, z.dtype)), _TILE, interpret)
    if d_o.ndim == 5:               # as o lay: back the way it came
        d_o = d_o.transpose(1, 0, 3, 2, 4).reshape(shape)
    # The eight partial sums, the batch rows and a weight's lane of every
    # head.
    return d_o, dz, sums.reshape(-1, w.shape[0]).sum(axis=0).astype(w.dtype)


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


skip_gate_norm.defvjp(*_scopes.rules(
    "skip_gate_norm", _skip_gate_norm_fwd, _skip_gate_norm_bwd))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def norm_gate(o, z, w, heads, eps, chunk=0, sigmoid=False):
    """``rms_norm_head(o) w silu(z)`` (``sigmoid``, static: ``w sigmoid(z)``,
    Kimi Delta Attention's gate) for o and z ``[B, S, heads * d_v]``
    and the heads' one weight ``w [d_v]``, the norm over each head's lanes;
    float32 inside, rounded once, to the dtype of z.  ``chunk`` (static):
    o is the gated delta rule's result, made in chunks of that many rows
    ``[N, B, H, chunk, d_v]`` before it was ``[B, S, ..]``, and the calls
    read it (and write its cotangent) as the rule left it where
    ``_rule_chunks`` allows; 0: as rows.  One Mosaic call, and one for all
    three gradients; ``_why_not`` says which shapes it takes."""
    return _norm_gate_forward(o, z, w, heads=heads, eps=eps, chunk=chunk,
                              interpret=_interpret(), sigmoid=sigmoid)


def _norm_gate_fwd(o, z, w, heads, eps, chunk, sigmoid):
    return norm_gate(o, z, w, heads, eps, chunk, sigmoid), (o, z, w)


def _norm_gate_bwd(heads, eps, chunk, sigmoid, kept, go):
    return _norm_gate_backward(*kept, go, heads=heads, eps=eps, chunk=chunk,
                               interpret=_interpret(), sigmoid=sigmoid)


norm_gate.defvjp(*_scopes.rules("norm_gate", _norm_gate_fwd, _norm_gate_bwd))


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


@functools.partial(jax.checkpoint, static_argnums=(3, 4, 5))
def _norm_then_gate(o, z, scale, heads, eps, sigmoid=False):
    """``rms_norm(o) * scale * silu(z)`` (``sigmoid``: ``* sigmoid(z)``): o
    and z ``[B, S, heads * d_v]``,
    o normed a head, ``scale [d_v]`` shared by the heads (Gated DeltaNet's
    output norm: the norm FIRST, then the gate); float32 inside, the dtype
    of z out, and under a checkpoint as ``_gate_then_norm``.  A head's mean
    through ``short_conv.over_heads``'s indicator products: a reshape to
    ``[.., 30, 192]`` has XLA:TPU relay the float32 tensor."""
    o = o.astype(jnp.float32)
    squares, spread = over_heads(o * o, heads)
    o = o * spread(jax.lax.rsqrt(squares * (heads / o.shape[-1]) + eps))
    gate = nn.sigmoid if sigmoid else nn.silu
    return (o * jnp.tile(scale, heads) * gate(z.astype(jnp.float32))
            ).astype(z.dtype)


def norm_gated(o, z, w, heads: int, eps: float, in_place: bool,
               chunk: int = 0, sigmoid: bool = False):
    """A gated delta-rule layer between its rule and ``wo``:
    ``rms_norm_head(o) w silu(z)`` (``sigmoid``, static: ``w sigmoid(z)``, a
    Kimi Delta Attention layer's), ``[B, S, C]`` in the dtype of z.  o and
    z ``[B, S, C = heads * d_v]``, ``w [d_v]`` the heads' one weight;
    ``heads`` and ``eps`` static.  ``in_place`` as ``gated_norm``'s: the
    chain is then ``norm_gate``'s one pass forward and one backward, where
    one, two or four heads are whole lane tiles (``_why_not``) and the
    backend a TPU; ``chunk`` (static) is the caller's word that o is the
    rule's result, made in chunks of that many rows (``norm_gate``).
    Elsewhere ``_norm_then_gate``.  Counted with ``gated_norm``'s traces in
    ``body_counts()``."""
    why = _why_not(o.shape, heads, in_place, norm_first=True)
    _trace_counts.note(_BODY, why or _MOSAIC)
    if why is None:
        return norm_gate(o, z, w, heads, eps, chunk, sigmoid)
    return _norm_then_gate(o, z, w, heads, eps, sigmoid)
