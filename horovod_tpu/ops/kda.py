"""The delta rule whose decay is a number a CHANNEL, in its chunkwise form:
the recurrent mixer of a Kimi Delta Attention layer (Kimi Linear,
arXiv:2510.26692) on the training path.

A head keeps a state ``S`` (``[d_v, d_k]``, float32, this package's
orientation: ``ops/gated_delta.py``) and reads it with a query.  With ``a_t =
exp(g_t)`` in (0, 1]^{d_k} the decay of every key channel and ``beta_t`` the
writing strength::

    S_t = S_{t-1} Diag(a_t) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T     S_0 = 0
    o_t = S_t q_t

(the paper's ``S_t = (I - beta k k^T) Diag(a_t) S_{t-1} + beta k v^T`` on the
transpose).  ``ops/gated_delta.py`` is the same rule with ONE decay a head and
step; there everything the decay enters is a number a row, here it is a ``[C,
d_k]`` tile as large as k itself.  Chunks of ``CHUNK`` = 64 tokens, ``G_i`` in
R^{d_k} the log-decays summed from the chunk's start to its row i, ``(x)`` the
product a channel, ``S`` the state the chunk starts from::

    A[i, j] = beta_i sum_c k_i[c] exp(G_i[c] - G_j[c]) k_j[c]        j < i, else 0
    T = (I + A)^-1        W = T (beta e^G (x) K)      U0 = T (beta V)      U = U0 - W S^T
    O  = (e^G (x) Q) S^T + P U      P[i, j] = sum_c q_i[c] exp(G_i[c] - G_j[c]) k_j[c], j <= i
    S' = S Diag(e^{G_C}) + U^T (e^{G_C - G} (x) K)

**No exponent above zero, and no clamp.**  ``e^{-G_j}`` alone overflows
float32 (a channel that decays by e^-1.4 a step passes e^88 inside 64 rows), so
A and P are never ``(e^G (x) K)(e^-G (x) K)^T``.  They are built by halves
(``_systems``): a segment of 2 s rows (s = 1, 2, .. 32) is its two halves'
own blocks on the diagonal, and under them the upper half's rows against the
lower half's columns, BOTH taken against the upper half's first row m --
``exp(G_i - G_m)`` on the rows and ``exp(G_m - G_j)`` on the columns (i >= m >
j: both differences are sums of log-decays, at most zero) -- which is one
product of decayed operands on the MXU a level, six levels a chunk, half the
multiply-adds of a full ``[C, C]`` product in all; the diagonal of P is ``q_i
. k_i`` and A's is zero.  g is taken as the layer gives it: nothing here
bounds it, and no exponent is formed that could pass zero.  (The published
kernel takes sub-chunks of 16 rows against their first row and forms the 16 x
16 diagonal blocks pair by pair on the vector unit; with those blocks pair
by pair this body read 82.1 ms a layer forward and backward on the v5e, by
halves down to single rows 76.3: PERF.md, PR 69.)

**Two bodies, one rule** (``kda_rule``; ``walk_counts()`` says which a trace
took, and why not the other), under ``ops/gated_delta.py``'s rules.  Either
way the chunks go by slabs of eight (``ops/chunking.py``): a slab is prepared
at once, then walked chunk by chunk with the float32 state as the carry; the
rule is a ``custom_vjp`` whose backward pass goes over the slabs in reverse,
prepares a slab again, walks its chunks in reverse from the kept states (U
made again) and sends the cotangents back through the preparation.

*The ``jnp`` body* (``_prepare``, ``_walk``, ``_walk_back``), for every trace
that may hold no Mosaic call (CPU, a partitioned trace, ``in_place`` false)
and for heads off the lane tile: XLA makes A and P by ``_systems``, T by the
merges, W, U0 and the decayed operands, a slab at a time; the walks are
``lax.scan``s over the slab's chunks; the preparation's transpose is
``jax.vjp``'s (the same differences: no exponent above zero there either).

*The Mosaic calls* (where the caller says ``in_place``, the backend is a TPU
and d_k and d_v are whole lane tiles), on the slab's arrays where they lie,
``[n, B, H, C, d]``.  Forward three: ``systems_call`` forms A and P of a block
of ``_STEP`` heads in VMEM (by halves down to segments of 8 rows, one product
a level under the level's mask; the 4 x 4 blocks left on the diagonal by
offset, a rotation of the sublanes bringing row i - s beside row i),
``gated_delta._solve`` turns A into T, and the walk (``_walk_call``: grid
(batch row, block of heads, chunk), the chunks innermost and in sequence)
makes, for a chunk of a head, e^G, e^G (x) K, W, U0, e^G (x) Q and e^{G_C - G}
(x) K in VMEM and nowhere else, with every head's float32 state resident in
the call's result block from the first chunk to the last.  XLA's part of a
slab is gamma (a triangular product), beta on A's rows and on T's columns
(``W = (T Diag(beta)) (e^G (x) K)``: no ``[C, 1]`` array crosses HBM), and the
copies into the chunked layout.  Backward two: the reverse walk
(``_walk_back_call``) with the state's cotangent resident, U made again,
writing the cotangents of q, k, v, gamma, T and P; and, behind the solve's
transpose (``-T^T dT T^T``: XLA's, two products at ``highest``),
``systems_call``'s transpose, which carries dA and dP back through every
level and offset to q, k and gamma in VMEM.

**Precision** is ``ops/gated_delta.py``'s: cumulative log-decays, the solve,
the state and its cotangent in float32; the products take their operands in
the dtype of q and accumulate in float32; a decayed operand is rounded to
that dtype once, behind its float32 decay.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.common import trace_counts as _trace_counts
from horovod_tpu.ops import gated_delta as _gdn
from horovod_tpu.ops.chunking import (chunked as _chunked, padded as _padded,
                                      slabs as _slabs)
from horovod_tpu.ops.gated_delta import (CHUNK, HEADS_OFF_THE_TILE,
                                         NOT_IN_PLACE, _chunk_values, _dot,
                                         _tril_inverse, called_in_place)
from horovod_tpu.ops.ssd import _NT, _TN, _iota, _mm

__all__ = ["CHUNK", "kda_rule", "kda_states", "systems_call", "walk_counts",
           "solve_counts"]

_SOLVE = "kda_solve"
_WALK = "kda_walk"
_MOSAIC = "mosaic"
_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
_STEP = 8              # heads a grid step of the Mosaic walk takes at most


def solve_counts() -> dict:
    """``{"mosaic": n, "plain": {reason: n}}``: how many traced calls of
    ``kda_rule`` solved their chunks' systems in ``gated_delta._solve``'s
    Mosaic call, and how many by the ``jnp`` merges, by reason."""
    plain = _trace_counts.counts(_SOLVE)
    return {"mosaic": plain.pop(_MOSAIC, 0), "plain": plain}


def walk_counts() -> dict:
    """``{"mosaic": n, "plain": {reason: n}}``: how many traced calls of
    ``kda_rule`` made their chunks' systems and walked them in the Mosaic
    calls, and how many in the ``jnp`` body, by reason.  Process-global, once
    a TRACE."""
    plain = _trace_counts.counts(_WALK)
    return {"mosaic": plain.pop(_MOSAIC, 0), "plain": plain}


def _systems(q, k, gamma, beta):
    """A ``[.., C, C]`` (under the diagonal, times beta) and P (on and under
    it), float32, for q, k ``[.., C, d_k]`` and gamma float32 of that shape
    (the log-decays summed from the chunk's start), beta ``[.., C]``; by
    halves, every exponent a difference that is at most zero (the module's
    docstring)."""
    dtype = q.dtype
    *lead, chunk, d_k = q.shape
    # Segments of one row: A has nothing there, P the row's own q . k.
    a = jnp.zeros((*lead, chunk, 1, 1), jnp.float32)
    p = jnp.sum(q.astype(jnp.float32) * k.astype(jnp.float32),
                axis=-1)[..., None, None]
    size = 1
    while size < chunk:
        def halves(x):
            x = x.reshape(*lead, chunk // (2 * size), 2, size, x.shape[-1])
            return x[..., 0, :, :], x[..., 1, :, :]

        g_low, g_up = halves(gamma)
        pivot = g_up[..., :1, :]
        into = jnp.exp(g_up - pivot)
        k_low, k_up = halves(k)
        kc = (k_low * jnp.exp(pivot - g_low)).astype(dtype)
        under = [_dot("...id,...jd->...ij", (x * into).astype(dtype), kc)
                 for x in (k_up, halves(q)[1])]

        def merged(diagonal, block):
            low, up = halves(diagonal)
            return jnp.concatenate([
                jnp.concatenate([low, jnp.zeros_like(block)], axis=-1),
                jnp.concatenate([block, up], axis=-1)], axis=-2)

        a, p = merged(a, under[0]), merged(p, under[1])
        size *= 2
    return beta[..., None] * a[..., 0, :, :], p[..., 0, :, :]


def _summed(g):
    """``g [.., C, d]`` summed from the chunk's start to each row, by a
    triangular product at ``highest`` (as ``jnp.cumsum`` XLA:TPU makes a
    ``reduce-window`` of it that runs at 6 GB/s: ``ops/ssd.py::_summed``)."""
    chunk = g.shape[-2]
    upto = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    return jnp.einsum("ij,...jd->...id", upto.astype(g.dtype), g,
                      precision=_HIGHEST)


def _prepare(q, k, v, g, beta, mosaic=False):
    """What every chunk needs and no chunk's state enters.  q, k ``[N, B, H,
    C, d_k]``, v ``[.., d_v]``, g ``[.., C, d_k]`` and beta ``[N, B, H, C]``
    float32.  Returns W, U0, P, e^G (x) Q and e^{G_C - G} (x) K (the dtype of
    q) and e^{G_C} ``[N, B, H, d_k]`` (float32)."""
    dtype = q.dtype
    gamma = _summed(g)
    last = gamma[..., -1:, :]
    a, p = _systems(q, k, gamma, beta)
    t = _tril_inverse(a, mosaic).astype(dtype)
    into = jnp.exp(gamma)
    w = _dot("...ij,...jd->...id", t,
             (k * (beta[..., None] * into)).astype(dtype)).astype(dtype)
    u0 = _dot("...ij,...jd->...id", t,
              (v * beta[..., None]).astype(dtype)).astype(dtype)
    return (w, u0, p.astype(dtype), (q * into).astype(dtype),
            (k * jnp.exp(last - gamma)).astype(dtype),
            jnp.exp(last[..., 0, :]))


def _walk(prepared, state):
    """The chunks of one slab in order, from ``state`` (float32): the state
    the slab leaves, O ``[n, B, H, C, d_v]`` and the state each chunk started
    from ``[n, B, H, d_v, d_k]``, both in the dtype of W."""

    def step(state, chunk):
        w, u0, p, qg, kg, a_last = chunk
        read = state.astype(w.dtype)
        u = _chunk_values(w, u0, read).astype(w.dtype)
        o = _dot("bhck,bhvk->bhcv", qg, read) + _dot("bhcj,bhjv->bhcv", p, u)
        new = (a_last[..., None, :] * state
               + _dot("bhcv,bhck->bhvk", u, kg))
        return new, (o.astype(w.dtype), read)

    return jax.lax.scan(step, state, prepared)


def _walk_back(prepared, states, d_o, d_state):
    """The chunks of one slab in reverse: the cotangent of the state the
    slab started from and those of ``prepared``; U made again."""
    dtype = d_o.dtype

    def step(d_new, chunk):
        w, u0, p, qg, kg, a_last, state, d_o = chunk
        u = _chunk_values(w, u0, state).astype(dtype)
        d_new_t = d_new.astype(dtype)
        d_u = (_dot("bhcj,bhcv->bhjv", p, d_o)
               + _dot("bhck,bhvk->bhcv", kg, d_new_t)).astype(dtype)
        d_state = (a_last[..., None, :] * d_new
                   + _dot("bhcv,bhck->bhvk", d_o, qg)
                   - _dot("bhcv,bhck->bhvk", d_u, w))
        return d_state, (
            (-_dot("bhcv,bhvk->bhck", d_u, state)).astype(dtype),    # W
            d_u,                                                    # U0
            _dot("bhcv,bhjv->bhcj", d_o, u).astype(dtype),          # P
            _dot("bhcv,bhvk->bhck", d_o, state).astype(dtype),      # e^G Q
            _dot("bhcv,bhvk->bhck", u, d_new_t).astype(dtype),      # .. K
            jnp.sum(d_new * state, axis=-2))                        # e^G_C

    return jax.lax.scan(step, d_state, (*prepared, states, d_o),
                        reverse=True)


# -- the chunks' systems as one Mosaic call each way --------------------------
#
# A grid step: the ``[C, d_k]`` tiles of q, k and gamma of ``_STEP`` heads of
# one chunk, where the slab's arrays lie (``[n, B, H, C, d_k]``); A (float32,
# before beta) and P (the operands' dtype) leave as ``[n, B, H, C, C]``.  The
# pairs of a chunk are ``_systems``' by halves down to segments of 8 rows (a
# sublane tile: the pivot row is one row of a tile spread over it), one
# product of decayed operands a level under a mask of the level's pairs; the 4
# x 4 blocks left on the diagonal are taken by OFFSET s = 1, 2, 3: row i
# against row i - s, which a rotation of the sublanes brings beside it, ``exp(G_i
# - G_{i-s})`` where both lie in one block of four (elsewhere nothing runs
# through exp), a sum over the lanes a row.  Backward the same quantities
# again, and every level's and offset's transposes, in VMEM.

_LOW = 4               # rows of the diagonal blocks that are taken by offset


def _in_blocks(rows, offset: int):
    """Whether row i and row ``i - offset`` lie in one block of ``_LOW``."""
    return (rows & (_LOW - 1)) >= offset


def _offset(x, s: int):
    """``x [C, d]`` with row ``i - s`` at row i (rows rotate)."""
    return pltpu.roll(x, s, 0)


def _level(gamma, rows, half: int):
    """For segments of ``2 half`` rows (whole sublane tiles): ``exp`` of each
    row's log-decay against its segment's pivot row (the upper half's first),
    rows of the upper half ``G_i - G_m``, of the lower ``G_m - G_j``: at most
    zero; and which rows are the upper half's."""
    chunk, d = gamma.shape
    seg = 2 * half
    tiles = gamma.reshape(chunk // seg, seg, d)
    pivot = jnp.broadcast_to(tiles[:, half:half + 1, :],
                             tiles.shape).reshape(chunk, d)
    upper = (rows & (seg - 1)) >= half
    return jnp.exp(jnp.where(upper, gamma - pivot, pivot - gamma)), upper


def _level_pairs(chunk: int, half: int):
    """``[C, C]``: row i in the upper half and column j in the lower half of
    one segment of ``2 half`` rows."""
    row, col = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    shift = (2 * half).bit_length() - 1
    return ((row >> shift) == (col >> shift)) & (
        (row & (2 * half - 1)) >= half) & ((col & (2 * half - 1)) < half)


def _systems_kernel(q_ref, k_ref, g_ref, a_ref, p_ref):
    # q_ref, k_ref [a, C, d]; g_ref [a, C, d] float32 (gamma); a_ref [a, C, C]
    # float32; p_ref [a, C, C].
    heads, chunk, d = q_ref.shape
    f32 = jnp.float32
    dtype = q_ref.dtype
    row, col = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    rows = _iota((chunk, d), 0)

    def head(j, carry):
        q32, k32, gamma = q_ref[j].astype(f32), k_ref[j].astype(f32), g_ref[j]
        a = jnp.zeros((chunk, chunk), f32)
        p = jnp.where(row == col, jnp.sum(q32 * k32, axis=1, keepdims=True),
                      0.0)
        for s in range(1, _LOW):
            e = jnp.exp(jnp.where(_in_blocks(rows, s),
                                  gamma - _offset(gamma, s), -jnp.inf))
            ke = _offset(k32, s) * e
            at = row - col == s
            a = a + jnp.where(at, jnp.sum(k32 * ke, axis=1, keepdims=True),
                              0.0)
            p = p + jnp.where(at, jnp.sum(q32 * ke, axis=1, keepdims=True),
                              0.0)
        half = _LOW
        while half < chunk:
            e, _ = _level(gamma, rows, half)
            zk = (k32 * e).astype(dtype)
            pairs = _level_pairs(chunk, half)
            a = a + jnp.where(pairs, _mm(zk, zk, _NT), 0.0)
            p = p + jnp.where(pairs, _mm((q32 * e).astype(dtype), zk, _NT),
                              0.0)
            half *= 2
        a_ref[j] = a
        p_ref[j] = p.astype(p_ref.dtype)
        return carry

    jax.lax.fori_loop(0, heads, head, 0)


def _systems_back_kernel(q_ref, k_ref, g_ref, da_ref, dp_ref, dq_ref, dk_ref,
                         dg_ref):
    # As _systems_kernel, with the cotangents da_ref (float32) and dp_ref;
    # results dq_ref, dk_ref [a, C, d] and dg_ref [a, C, d] float32 (gamma's).
    heads, chunk, d = q_ref.shape
    f32 = jnp.float32
    dtype = q_ref.dtype
    row, col = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    rows = _iota((chunk, d), 0)

    def column(x, at):
        """``x [C, C]``'s entries where ``at``, one a row: ``[C, 1]``."""
        return jnp.sum(jnp.where(at, x, 0.0), axis=1, keepdims=True)

    def head(j, carry):
        q32, k32, gamma = q_ref[j].astype(f32), k_ref[j].astype(f32), g_ref[j]
        d_a, d_p = da_ref[j], dp_ref[j].astype(f32)
        on = column(d_p, row == col)
        d_q, d_k, d_g = on * k32, on * q32, jnp.zeros((chunk, d), f32)
        for s in range(1, _LOW):
            e = jnp.exp(jnp.where(_in_blocks(rows, s),
                                  gamma - _offset(gamma, s), -jnp.inf))
            ke = _offset(k32, s) * e
            at = row - col == s
            c_a, c_p = column(d_a, at), column(d_p, at)
            d_k = d_k + c_a * ke
            d_q = d_q + c_p * ke
            both = c_a * k32 + c_p * q32        # what row i - s meets, at row i
            d_k = d_k + _offset(both * e, chunk - s)
            moved = both * ke                   # the exponent's cotangent
            d_g = d_g + moved - _offset(moved, chunk - s)
        half = _LOW
        while half < chunk:
            seg = 2 * half
            e, upper = _level(gamma, rows, half)
            zk, zq = (k32 * e).astype(dtype), (q32 * e).astype(dtype)
            pairs = _level_pairs(chunk, half)
            m_a = jnp.where(pairs, d_a, 0.0).astype(dtype)
            m_p = jnp.where(pairs, d_p, 0.0).astype(dtype)
            d_zk = _mm(m_a, zk) + _mm(m_a, zk, _TN) + _mm(m_p, zq, _TN)
            d_zq = _mm(m_p, zk)
            d_k = d_k + d_zk * e
            d_q = d_q + d_zq * e
            moved = (d_zk * k32 + d_zq * q32) * e
            signed = jnp.where(upper, moved, -moved)
            total = jnp.sum(signed.reshape(chunk // seg, seg, d), axis=1,
                            keepdims=True)
            d_g = d_g + signed - jnp.where(
                (rows & (seg - 1)) == half, jnp.broadcast_to(
                    total, (chunk // seg, seg, d)).reshape(chunk, d), 0.0)
            half *= 2
        dq_ref[j] = d_q.astype(dq_ref.dtype)
        dk_ref[j] = d_k.astype(dk_ref.dtype)
        dg_ref[j] = d_g
        return carry

    jax.lax.fori_loop(0, heads, head, 0)


def _systems_specs(q):
    """The grid (slab chunk, batch row, block of heads) and a block of a
    slab's array ``[n, B, H, rows, lanes]``."""
    chunks, batch, heads = q.shape[:3]
    a = _heads_a_step(heads)

    def block(lanes):
        return pl.BlockSpec((None, None, a, q.shape[3], lanes),
                            lambda n, b, h: (n, b, h, 0, 0))

    return (chunks, batch, heads // a), block


def _all_parallel():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _systems_forward(q, k, gamma, interpret):
    grid, block = _systems_specs(q)
    chunk, d = q.shape[3:]
    square = q.shape[:4] + (chunk,)
    call = pl.pallas_call(
        _systems_kernel,
        grid=grid,
        in_specs=[block(d)] * 3,
        out_specs=[block(chunk)] * 2,
        out_shape=[jax.ShapeDtypeStruct(square, jnp.float32),
                   jax.ShapeDtypeStruct(square, q.dtype)],
        compiler_params=_all_parallel(),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_KDA_SCAN):
        return tuple(call(q, k, gamma))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _systems_backward(q, k, gamma, d_a, d_p, interpret):
    grid, block = _systems_specs(q)
    chunk, d = q.shape[3:]
    call = pl.pallas_call(
        _systems_back_kernel,
        grid=grid,
        in_specs=[block(d)] * 3 + [block(chunk)] * 2,
        out_specs=[block(d)] * 3,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(gamma.shape, gamma.dtype)],
        compiler_params=_all_parallel(),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_KDA_SCAN):
        return tuple(call(q, k, gamma, d_a, d_p))


@jax.custom_vjp
def systems_call(q, k, gamma):
    """``_systems``' A (float32, before beta) and P (the dtype of q) for q, k
    ``[n, B, H, C, d_k]`` and gamma float32 of that shape, C a multiple of 8
    and d_k whole lane tiles: one Mosaic call, and one for the three
    gradients; called directly it runs interpreted off the TPU."""
    return _systems_forward(q, k, gamma, interpret=_gdn._interpret())


def _systems_call_fwd(q, k, gamma):
    return systems_call(q, k, gamma), (q, k, gamma)


def _systems_call_bwd(kept, cotangents):
    return _systems_backward(*kept, *cotangents,
                             interpret=_gdn._interpret())


systems_call.defvjp(*_scopes.rules(
    "kda.systems_call", _systems_call_fwd, _systems_call_bwd))


# -- the walk as one Mosaic call each way --------------------------------------
#
# A grid step: chunk n of block ``hb`` of batch row b's heads (``_STEP`` of
# them at most), the chunks innermost.  q, k, v, gamma, T and P of the slab
# are read where they lie, ``[n, B, H, C, d]`` (a block: the step's heads'
# tiles); the state the slab starts from comes in as ``[B, H, d_v, d_k]``
# float32 and the one it leaves goes out so, its block the same for every
# chunk of a block of heads: resident in VMEM from the first chunk to the
# last, it IS the carry.

def _heads_a_step(heads: int) -> int:
    return next(a for a in range(min(_STEP, heads), 0, -1) if heads % a == 0)


def _prepare_call(q, k, v, g, beta, mosaic=True):
    """What the Mosaic walk reads of a slab ``[n, B, H, C, d]``: q, k and v
    as they are, gamma, T with beta on its columns (``W = (T Diag(beta)) (e^G
    (x) K)``, ``U0 = (T Diag(beta)) V``: no ``[C, 1]`` array of beta's crosses
    HBM) in the dtype of q, and P; A and P by ``systems_call``."""
    gamma = _summed(g)
    a, p = systems_call(q, k, gamma)
    t = _tril_inverse(beta[..., None] * a, mosaic)
    return q, k, v, gamma, (t * beta[..., None, :]).astype(q.dtype), p


def _a_head(j, q_ref, k_ref, v_ref, g_ref, t_ref):
    """What head j of the step's chunk needs and no state enters, in VMEM and
    nowhere else: q and k in float32, v, T, e^G, e^{G_C - G}, e^{G_C} ``[1,
    d_k]``, e^G (x) K, W, U0, e^G (x) Q and e^{G_C - G} (x) K."""
    dtype = q_ref.dtype
    chunk = q_ref.shape[1]
    q32, k32 = q_ref[j].astype(jnp.float32), k_ref[j].astype(jnp.float32)
    v, t, gamma = v_ref[j], t_ref[j], g_ref[j]
    into = jnp.exp(gamma)
    last = gamma[chunk - 1:chunk, :]
    after = jnp.exp(last - gamma)
    ke = (k32 * into).astype(dtype)
    return dict(
        q32=q32, k32=k32, v=v, t=t, into=into, after=after,
        kept=jnp.exp(last), ke=ke, w=_mm(t, ke).astype(dtype),
        u0=_mm(t, v).astype(dtype), qg=(q32 * into).astype(dtype),
        kg=(k32 * after).astype(dtype))


def _walk_kernel(q_ref, k_ref, v_ref, g_ref, t_ref, p_ref, s0_ref, o_ref,
                 started_ref, s_ref):
    # q_ref, k_ref [a, C, d_k], v_ref, o_ref [a, C, d_v], g_ref [a, C, d_k]
    # float32 (gamma), t_ref, p_ref [a, C, C]; s0_ref, s_ref [a, d_v, d_k]
    # float32; started_ref [a, d_v, d_k]: the state the chunk started from, as
    # the products read it.
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    dtype = q_ref.dtype
    for j in range(q_ref.shape[0]):
        head = _a_head(j, q_ref, k_ref, v_ref, g_ref, t_ref)
        state = s_ref[j]
        read = state.astype(dtype)
        started_ref[j] = read
        u = (head["u0"].astype(jnp.float32)
             - _mm(head["w"], read, _NT)).astype(dtype)
        o = _mm(head["qg"], read, _NT) + _mm(p_ref[j], u)
        o_ref[j] = o.astype(o_ref.dtype)
        s_ref[j] = head["kept"] * state + _mm(u, head["kg"], _TN)


def _walk_back_kernel(q_ref, k_ref, v_ref, g_ref, t_ref, p_ref, started_ref,
                      do_ref, ds0_ref, dq_ref, dk_ref, dv_ref, dg_ref, dt_ref,
                      dp_ref, ds_ref):
    # As _walk_kernel (the chunks arrive in reverse), with started_ref the
    # kept states, do_ref [a, C, d_v] the result's cotangent and ds0_ref the
    # cotangent of the state the slab left.  Results: the cotangents of q, k,
    # v, gamma (float32), T (beta on its columns) and P, and ds_ref [a, d_v,
    # d_k] float32: the cotangent of the state the chunk started from, the
    # carry.  W's, U0's and the decayed operands' never leave VMEM.
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = ds0_ref[...]

    dtype = q_ref.dtype
    chunk, d_k = q_ref.shape[1:]
    at_last = _iota((chunk, d_k), 0) == chunk - 1
    for j in range(q_ref.shape[0]):
        head = _a_head(j, q_ref, k_ref, v_ref, g_ref, t_ref)
        w, qg, kg, t = (head[name] for name in ("w", "qg", "kg", "t"))
        state, d_o = started_ref[j], do_ref[j]
        u = (head["u0"].astype(jnp.float32)
             - _mm(w, state, _NT)).astype(dtype)
        d_new = ds_ref[j]
        d_new_t = d_new.astype(dtype)
        d_u = (_mm(p_ref[j], d_o, _TN) + _mm(kg, d_new_t, _NT)).astype(dtype)
        ds_ref[j] = (head["kept"] * d_new + _mm(d_o, qg, _TN)
                     - _mm(d_u, w, _TN))
        d_w = (-_mm(d_u, state)).astype(dtype)
        dp_ref[j] = _mm(d_o, u, _NT).astype(dp_ref.dtype)
        dt_ref[j] = (_mm(d_w, head["ke"], _NT)
                     + _mm(d_u, head["v"], _NT)).astype(dt_ref.dtype)
        dv_ref[j] = _mm(t, d_u, _TN).astype(dv_ref.dtype)
        d_ke = _mm(t, d_w, _TN) * head["into"]           # through e^G (x) K
        d_qg = _mm(d_o, state) * head["into"]            # .. e^G (x) Q
        d_kg = _mm(u, d_new_t) * head["after"]           # .. e^{G_C - G} (x) K
        dq_ref[j] = d_qg.astype(dq_ref.dtype)
        dk_ref[j] = (d_ke + d_kg).astype(dk_ref.dtype)
        written = d_kg * head["k32"]
        # gamma_C: every row's e^{G_C - G}, and what the state keeps.
        dg_ref[j] = (d_ke * head["k32"] + d_qg * head["q32"] - written
                     + jnp.where(at_last, jnp.sum(
                         written, axis=0, keepdims=True) + head["kept"]
                         * jnp.sum(d_new * state.astype(jnp.float32), axis=0,
                                   keepdims=True), 0.0))


def _walk_specs(prepared, reverse: bool):
    """The grid and the blocks of ``prepared`` (``_prepare_call``'s six), of
    a slab's arrays ``[n, B, H, rows, lanes]`` and of a state ``[B, H, d_v,
    d_k]``."""
    q, _, v = prepared[:3]
    chunks, batch, heads = q.shape[:3]
    a = _heads_a_step(heads)

    def at(n):
        return chunks - 1 - n if reverse else n

    def slab(x):
        return pl.BlockSpec((None, None, a) + x.shape[3:],
                            lambda b, h, n: (at(n), b, h) + (0,) * (x.ndim - 3))

    state = pl.BlockSpec((None, a, v.shape[-1], q.shape[-1]),
                         lambda b, h, n: (b, h, 0, 0))
    return (batch, heads // a, chunks), slab, state


def _params():
    """(No limit stated: the compiler's default holds a step's blocks of
    eight heads; ``tests/test_kimi_linear_v5e_compile.py`` compiles both
    calls at the cell's shapes under it.)"""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# (Jits, as ``gated_delta._solve``'s: a step traces each body once a shape.)
@functools.partial(jax.jit, static_argnames=("interpret",))
def _walk_call(prepared, state, interpret):
    """``_walk`` as one Mosaic call."""
    q, _, v = prepared[:3]
    grid, slab, carried = _walk_specs(prepared, False)
    started = jax.ShapeDtypeStruct(
        q.shape[:3] + (v.shape[-1], q.shape[-1]), q.dtype)
    call = pl.pallas_call(
        _walk_kernel,
        grid=grid,
        in_specs=[slab(x) for x in prepared] + [carried],
        out_specs=[slab(v), slab(started), carried],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype), started,
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        compiler_params=_params(),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_KDA_SCAN):
        o, states, new = call(*prepared, state)
    return new, (o, states)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _walk_back_call(prepared, states, d_o, d_state, interpret):
    """``_walk_back`` as one Mosaic call."""
    grid, slab, carried = _walk_specs(prepared, True)
    call = pl.pallas_call(
        _walk_back_kernel,
        grid=grid,
        in_specs=[slab(x) for x in (*prepared, states, d_o)] + [carried],
        out_specs=[slab(x) for x in prepared] + [carried],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in prepared]
        + [jax.ShapeDtypeStruct(d_state.shape, d_state.dtype)],
        compiler_params=_params(),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_KDA_SCAN):
        *d_prepared, d_state = call(*prepared, states, d_o, d_state)
    return d_state, tuple(d_prepared)


def _why_no_walk(d_k: int, d_v: int):
    """None where a rule that may hold Mosaic calls (``gated_delta._why_not``)
    walks heads of ``d_k`` and ``d_v`` lanes by the Mosaic calls, else the
    reason it does not: a step's tiles are whole heads ``[C, d]``."""
    return HEADS_OFF_THE_TILE if d_k % _LANES or d_v % _LANES else None


def _rule_walk(q, k, v, g, beta, mosaic=False, walk=False):
    """O and the chunks' starting states for chunked inputs, slab by slab;
    ``mosaic``: the systems solved by ``gated_delta._solve``'s call; ``walk``:
    the slabs walked by ``_walk_call``."""
    _, batch, heads, _, d_k = q.shape

    def slab(state, inputs):
        if walk:
            return _walk_call(_prepare_call(*inputs, mosaic), state,
                              interpret=_gdn._interpret())
        return _walk(_prepare(*inputs, mosaic), state)

    _, (o, states) = jax.lax.scan(
        slab, jnp.zeros((batch, heads, v.shape[-1], d_k), jnp.float32),
        tuple(_slabs(x) for x in (q, k, v, g, beta)))
    return (o.reshape(-1, *o.shape[2:]),
            states.reshape(-1, *states.shape[2:]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, mosaic, walk):
    return _rule_walk(q, k, v, g, beta, mosaic, walk)[0]


def _rule_fwd(q, k, v, g, beta, mosaic, walk):
    o, states = _rule_walk(q, k, v, g, beta, mosaic, walk)
    return o, (q, k, v, g, beta, states)


def _rule_bwd(mosaic, walk, res, d_o):
    *inputs, states = res

    def slab(d_state, xs):
        *inputs, states, d_o = xs
        prepared, pull = jax.vjp(functools.partial(
            _prepare_call if walk else _prepare, mosaic=mosaic), *inputs)
        if walk:
            d_state, d_prepared = _walk_back_call(
                prepared, states, d_o, d_state, interpret=_gdn._interpret())
        else:
            d_state, d_prepared = _walk_back(prepared, states, d_o, d_state)
        return d_state, pull(d_prepared)

    _, grads = jax.lax.scan(
        slab, jnp.zeros(states.shape[1:], jnp.float32),
        tuple(_slabs(x) for x in (*inputs, states, d_o)), reverse=True)
    return tuple(x.reshape(-1, *x.shape[2:]) for x in grads)


_rule.defvjp(*_scopes.rules("kda._rule", _rule_fwd, _rule_bwd))


def _chunks(q, k, v, g, beta):
    """The sequence cut into chunks, padded to whole ones with rows that
    neither write (beta 0) nor decay (g 0)."""
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    return tuple(_chunked(_padded(x, CHUNK), CHUNK)
                 for x in (q, k, v.astype(q.dtype), g, beta))


def kda_rule(q, k, v, g, beta, in_place: bool | None = None):
    """``o [B, S, H, d_v]`` of the recurrence above, in the dtype of v.

    q, k ``[B, S, H, d_k]`` (as the rule reads them: normed and scaled by the
    caller), v ``[B, S, H, d_v]``, ``g = log a <= 0`` ``[B, S, H, d_k]`` (a
    number a channel) and beta ``[B, S, H]``, both taken to float32.  The
    state starts at zero and ends with the sequence; a length that is no
    multiple of ``CHUNK`` is padded.  ``in_place`` as
    ``gated_delta_rule``'s (said beside the operands, or around the call by
    ``gated_delta.calls_in_place``).  Which body a trace took, and why,
    ``solve_counts()`` and ``walk_counts()`` say."""
    batch, seq, heads, d_v = v.shape
    if in_place is None:
        in_place = called_in_place()
    why = _gdn._why_not() if in_place else NOT_IN_PLACE
    _trace_counts.note(_SOLVE, why or _MOSAIC)
    why_walk = why or _why_no_walk(q.shape[-1], d_v)
    _trace_counts.note(_WALK, why_walk or _MOSAIC)
    o = _rule(*_chunks(q, k, v, g, beta), why is None, why_walk is None)
    o = jnp.moveaxis(o, (0, 2), (1, 3))                  # [B, N, C, H, d_v]
    return o.reshape(batch, -1, heads, d_v)[:, :seq].astype(v.dtype)


def kda_states(q, k, v, g, beta):
    """The state each chunk started from, ``[N, B, H, d_v, d_k]`` in the
    dtype of q: for counters and tests, no gradient of its own."""
    return _rule_walk(*_chunks(q, k, v, g, beta))[1]
