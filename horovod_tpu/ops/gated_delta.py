"""The gated delta rule in its chunkwise form: the recurrent mixer of a
Gated DeltaNet layer (Yang, Kautz, Hatamizadeh, arXiv:2412.06464) on the
training path.

A head keeps a state ``S`` (``[d_v, d_k]``, float32) and reads it with a
query.  With ``alpha_t = exp(g_t)`` in (0, 1] the decay and ``beta_t`` the
writing strength (in (0, 2) where negative eigenvalues are allowed)::

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T     S_0 = 0
    o_t = S_t q_t

Token by token that is 8192 dependent steps of rank-one updates.  Here the
sequence is cut into chunks of ``CHUNK`` = 64 tokens (section 3 of the
paper).  With ``gamma_i`` the log-decay summed from the chunk's start to
its row i (so every decay below is ``exp(gamma_i - gamma_j)`` with i >= j,
at most 1), ``S`` the state the chunk starts from and ``u_i = beta_i (v_i -
alpha_i S_{i-1} k_i)`` the value a row really writes::

    A[i, j] = beta_i exp(gamma_i - gamma_j) (k_i . k_j)       j < i, else 0
    T       = (I + A)^-1                          the chunk's system, solved once
    W = T (beta e^gamma K)     U0 = T (beta V)         U = U0 - W S^T
    O  = (e^gamma Q) S^T + (M * Q K^T) U          M[i, j] = exp(gamma_i - gamma_j), j <= i
    S' = e^{gamma_C} S + U^T (e^{gamma_C - gamma} K)

**Two bodies, one rule** (``gated_delta_rule``; ``walk_counts()`` says which
a trace took, and why not the other).

*The Mosaic calls* (``walk_rows``: taken where the caller says ``in_place``
-- the trace is not partitioned, PERF.md §3.3 -- a head's d_k and d_v are
whole lane tiles and the heads an even number, ``_why_no_walk``, and the
backend is a TPU).  The grid is
(batch row, chunk, block of heads), the chunks in sequence (backward: in
reverse, by the index map), a block ``_STEP`` = 8 heads (the most that
divide H: a step's independent chains, W S^T -> U -> U^T K of one head behind
another's).  q, k and v are read where they lie, as ``[C, heads x d]`` lane
blocks of the rows ``[B, S, H d]`` the filters' calls wrote, and o leaves as
rows ``[B, S, H d_v]``, which is what the output norm's call reads.  Forward
three calls: a stateless one forms A (``_systems_kernel``; K K^T, the decays
and beta in VMEM), ``_solve`` turns it into T, and the walk makes, for a
chunk of a head, beta e^gamma K, beta V, W, U0, M, P = M * Q K^T, e^gamma Q,
e^{gamma_C - gamma} K and U in VMEM and nowhere else, with every head's
float32 state ``[d_v, d_k]`` in scratch (a reference a head).  A and T lie
two heads side by side, ``[B, N, H / 2, C, 2 C]`` (a whole lane tile at C =
64; a ``[.., 64, 64]`` array pads its rows to 128 lanes in HBM: twice the
bytes to hold, to move, and for XLA to turn around ``_solve``).  What is
one number a row and head (gamma, e^gamma, e^{gamma_C - gamma}, beta, beta
e^gamma, e^{gamma_C}) XLA makes from g and beta (``_quantities``; gamma by a
triangular product, ``ops/ssd.py::_summed``) and a step spreads over a head's
lanes on the VPU.  No ``[.., C, C]`` array but A and T crosses HBM.  A
differentiated trace also keeps T (in the dtype the products read it in) and
the state each chunk started from (bf16 ``[N, B, H, d_v, d_k]``, as the
``jnp`` walk keeps it).  Backward ONE call: the reverse walk with the state's
cotangent in scratch, U made again from the kept state, writing dq, dk, dv
as rows (in the place of q, k and dO, which a layer no longer needs) and a
row's sums over its head's lanes for d gamma and d beta; T's cotangent, A's
(``-T^T dT T^T`` under the diagonal) and what A sends on to k, beta and
gamma never leave VMEM.  Where a key head serves several value heads the
caller copies it there (``key_heads_copied``: a Mosaic pass over the rows).

*The ``jnp`` body* (``_rule``): for every trace that may hold no Mosaic call
(CPU, a partitioned trace, ``in_place`` false), for heads off the lane tile
(``olmo-hybrid-7b``'s 96 | 192) and for ``gated_delta_states``.  Everything
but the header's last three lines is the same for every chunk and runs for a
slab of 8 chunks at once (``ops/chunking.py``) (``_prepare``); those three
carry the state from chunk to chunk in float32 (``_walk``, a ``lax.scan``
over the slab's chunks).  The rule is a ``custom_vjp``: the forward pass
keeps q, k, v, the gates and the state each chunk started from; the backward
pass goes over the slabs in reverse, prepares a slab again, walks its chunks
in reverse from those states (U made again), and sends the cotangents of
what was prepared back through the preparation.  No step of either walk is a
single token.

``T`` is the inverse of a unit lower-triangular matrix, formed exactly by
forward substitution in float32 and no series that could cancel
(``_tril_inverse``; its transpose is ``-T^T dT T^T`` under the diagonal, two
products at ``highest``).  Two bodies, one answer to float32 rounding.  The
``jnp`` one (``_tril_inverse_impl``, every path that may hold no Mosaic
call, and the tests' yardstick) substitutes by blocks in six merges, ``T <-
T - T L_b T`` for blocks of b = 1, 2, .., 32 rows (``L_b``: the part of A
under the diagonal of each pair of b-blocks): twelve 64 x 64 products at
``highest`` precision, six bf16 passes each on a quarter of the MXU, 33
times the multiply-adds the entries that change need.  The Mosaic one
(``_solve``, PR 47) substitutes row by row, ``T[i, :] = e_i - sum_{j < i}
A[i, j] T[j, :]``, on the VPU with 128 matrices side by side on the lanes:
one call, 70 us a slab of 512 on the v5e where the merges take 505
(PERF.md).

**Precision, the same in both bodies.**  Cumulative log-decays, the solve,
the state and its cotangent stay in float32 whatever the inputs' dtype; the
other products take their inputs in the dtype of q (bf16 on the training
path) and accumulate in float32, and the state is cast to that dtype only
where a product reads it.  T's cotangent is rounded to that dtype in both;
the Mosaic call then forms ``T^T dT T^T`` from the rounding of T that W and
U0 were made of, the inner product kept as two bf16 pieces (three passes,
``_solved_back``) where the ``jnp`` body asks for ``highest`` on the float32
T, and sums a row's cotangents in float32 before it rounds them once where
the ``jnp`` body rounds each: sums in another order, and no operand below
the dtype of q.

A Mosaic call is the caller's choice (the partitioner cannot split one):
``gated_delta_rule`` takes the calls only where its caller says that the
trace may hold Mosaic calls (``in_place``: ``llama.py::LlamaLayer`` reads it
off the model's ``attention_fn``, as for the rotation and the convolutions)
and a TPU runs the trace; which body a trace took is counted
(``solve_counts``, ``walk_counts``).
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.common import trace_counts as _trace_counts
from horovod_tpu.ops.chunking import (chunked as _chunked, padded as _padded,
                                      slabs as _slabs)
from horovod_tpu.ops.short_conv import _pieces
from horovod_tpu.ops.ssd import _NT, _TN, _iota, _mm, _summed

__all__ = ["CHUNK", "gated_delta_rule", "gated_delta_states", "walk_rows",
           "walks_rows", "key_heads_copied", "solve_counts", "walk_counts", "calls_in_place",
           "called_in_place",
           "NOT_IN_PLACE", "NO_TPU", "HEADS_OFF_THE_TILE", "HEADS_ODD"]

CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128           # matrices a grid step of the solve's call works on
_TILE = 8              # rows of a float32 sublane tile

# Which body solved a traced rule's chunk systems (``common/trace_counts.py``):
# the Mosaic call, or the ``jnp`` one by reason.
_SOLVE = "gdn_solve"
_MOSAIC = "mosaic"
NOT_IN_PLACE = "the attention_fn does not read its operands in place"
NO_TPU = "no TPU: the call would run interpreted"
_WALK = "gdn_walk"
HEADS_OFF_THE_TILE = "a head's lanes are no whole lane tiles"
HEADS_ODD = "an odd number of heads: their systems go in pairs"


def solve_counts() -> dict:
    """``{"mosaic": n, "plain": {reason: n}}``: how many traced calls of
    ``gated_delta_rule`` solved their chunks' systems in the Mosaic call,
    and how many in the ``jnp`` body, by reason.  Process-global, counted
    once a TRACE."""
    plain = _trace_counts.counts(_SOLVE)
    return {"mosaic": plain.pop(_MOSAIC, 0), "plain": plain}


def walk_counts() -> dict:
    """``{"mosaic": n, "plain": {reason: n}}``: how many traced calls of
    ``gated_delta_rule`` walked their chunks in ``walk_rows``' Mosaic calls,
    and how many in the ``jnp`` walk, by reason.  Process-global, counted
    once a TRACE."""
    plain = _trace_counts.counts(_WALK)
    return {"mosaic": plain.pop(_MOSAIC, 0), "plain": plain}


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


_around = threading.local()


@contextlib.contextmanager
def calls_in_place(answer: bool):
    """``gated_delta_rule`` calls traced inside take ``answer`` for the
    ``in_place`` they are not given.  For ``models/llama.py``, which calls
    the rule by its module's name with the five operands and nothing else,
    as the accepted benchmark's tests wrap it
    (``tests/benchmark/test_benchmark_hybrid.py::_with_rule``)."""
    was = called_in_place()
    _around.in_place = bool(answer)
    try:
        yield
    finally:
        _around.in_place = was


def called_in_place() -> bool:
    """The answer of the ``calls_in_place`` this code is traced inside;
    False outside any.  (``models/llama.py::_hc_write``, which the accepted
    benchmark's tests wrap by name with its four operands, reads it too.)"""
    return getattr(_around, "in_place", False)


def _why_not():
    """None where a rule whose caller said ``in_place`` solves its chunks'
    systems by ``_solve``'s call, else the reason it does not.  The call
    takes any number of ``CHUNK`` x ``CHUNK`` systems (whole sublane tiles,
    ``_solve_kernel``), so the shape never refuses.  Off the TPU the call
    would run interpreted, many times slower than the six merges it
    replaces: the ``jnp`` body there, and the bits it always gave."""
    return NO_TPU if _interpret() else None


def _why_no_walk(d_k: int, d_v: int, heads: int):
    """None where a rule that may hold Mosaic calls (``_why_not``) walks
    ``heads`` heads of ``d_k`` and ``d_v`` lanes by ``walk_rows``' calls,
    else the reason it does not: a step's blocks of q, k, v and o are whole
    heads where they lie in the rows, so a head is whole lane tiles; two
    heads' systems lie side by side, so the heads go in pairs."""
    if d_k % _LANES or d_v % _LANES:
        return HEADS_OFF_THE_TILE
    if heads % 2:
        return HEADS_ODD
    return None


def _merge_masks(chunk: int):
    """For b = 1, 2, 4, .. < chunk: where A lies under the diagonal of a
    pair of b-blocks (rows of the pair's second block, columns of its
    first)."""
    rows = jnp.arange(chunk)[:, None]
    cols = jnp.arange(chunk)[None, :]
    b, masks = 1, []
    while b < chunk:
        masks.append(((rows // b) % 2 == 1) & (cols // b == rows // b - 1))
        b *= 2
    return masks


def _tril_inverse_impl(a):
    chunk = a.shape[-1]
    t = jnp.broadcast_to(jnp.eye(chunk, dtype=a.dtype), a.shape)
    for mask in _merge_masks(chunk):
        lower = jnp.where(mask, a, 0.0)
        t = t - jnp.matmul(t, jnp.matmul(lower, t, precision=_HIGHEST),
                           precision=_HIGHEST)
    return t


# -- the solve as one Mosaic call --------------------------------------------
#
# Row i of T = (I + A)^-1 is e_i - sum_{j < i} A[i, j] T[j, :]: 63 dependent
# rows, each a sum over the rows before it.  With ONE matrix on the sublanes
# and lanes that is all broadcasts.  With 128 matrices side by side on the
# lanes (entry (i, j) of all of them one vector) it is plain multiply-adds
# on the VPU in float32, 2 * 64^3 / 6 a matrix where the six merges multiply
# 11 * 2 * 64^3 at six bf16 passes each.  So the call takes ``[C, C, M]``,
# the matrices on the last axis, and XLA turns A there and T back (a copy
# each way, 44 of the 70 us a slab of 512 takes on the v5e where the merges
# take 505; turned inside the call, row by row in VMEM, the same slab takes
# 116: PERF.md, PR 47).

def _solve_kernel(a_ref, t_ref):
    """``t[i] = e_i - sum_{j < i} a[i, j] t[j]`` for ``a_ref, t_ref [C, C,
    L]``: entry (i, j) of L matrices, one a lane; a row of T is ``[C, L]``,
    its columns on the sublanes.  What A holds on and above the diagonal is
    not read.  Rows go by sublane tiles of eight: a row's sum over the
    tiles before its own is straight-line code (T[j, c] is zero for c > j,
    so row j gives ``j // 8 + 1`` tiles of columns), the rows of its own
    tile that came before it are masked in."""
    chunk, _, lanes = a_ref.shape
    assert chunk % _TILE == 0, chunk
    sublane = jax.lax.broadcasted_iota(jnp.int32, (_TILE, lanes), 0)
    t_ref[...] = jnp.zeros_like(t_ref)
    for tile in range(chunk // _TILE):
        first = tile * _TILE

        def row(r, carry, tile=tile, first=first):
            i = first + r
            # acc[c]: columns 8 c .. 8 c + 7 of row i, from e_i.
            acc = [jnp.zeros((_TILE, lanes), jnp.float32)] * tile + [
                jnp.where(sublane == r, 1.0, 0.0)]

            def take(j, coef, tiles):
                for c in range(tiles):
                    acc[c] = acc[c] - coef * t_ref[
                        j, pl.ds(c * _TILE, _TILE), :]

            for j in range(first):
                take(j, a_ref[i, pl.ds(j, 1), :], j // _TILE + 1)
            for s in range(_TILE - 1):        # the rows of i's own tile
                j = first + s
                take(j, jnp.where(s < r, a_ref[i, pl.ds(j, 1), :], 0.0),
                     tile + 1)
            for c in range(tile + 1):
                t_ref[i, pl.ds(c * _TILE, _TILE), :] = acc[c]
            return carry

        jax.lax.fori_loop(0, _TILE, row, 0)


# (A jit: a step traces the body once a shape, not once a layer and pass, as
# ``ops/short_conv.py``'s calls; ``interpret`` is static, so the cached trace
# is of the mode asked for.)
@functools.partial(jax.jit, static_argnames=("interpret", "pairs"))
def _solve(a, interpret, pairs=False):
    """``(I + a)^-1`` for ``a [.., C, C]`` float32 by one Mosaic call: 128
    matrices a grid step (2 MiB a block of A and of T, two buffers each:
    half the default scoped VMEM, so the call states no limit).  Where the
    count is no multiple of 128 the last step's spare lanes hold whatever
    the block brought, are worked on like the others (a lane never reads
    another) and are not written back.  ``pairs``: a and the result are
    ``[.., C, 2 C]``, two matrices side by side along the lanes (at C = 64 a
    whole lane tile: what ``walk_rows``' calls write and read; a ``[.., 64,
    64]`` float32 array pads its rows to 128 lanes in HBM, twice the bytes to
    hold and to move)."""
    chunk = a.shape[-2]
    count = math.prod(a.shape[:-2])
    # Entry (i, j) of every matrix one vector: the matrices LAST.  Of pairs
    # ``[count, C, 2 C] -> [C, 2 C, count]``: a block takes the first or the
    # second C of the middle axis.
    lanes = jnp.transpose(a.reshape(count, chunk, -1), (1, 2, 0))
    steps = pl.cdiv(count, _LANES)
    if pairs:
        grid = (2, steps)
        block = pl.BlockSpec((chunk, chunk, _LANES), lambda p, m: (0, p, m))
    else:
        grid = (steps,)
        block = pl.BlockSpec((chunk, chunk, _LANES), lambda m: (0, 0, m))
    call = pl.pallas_call(
        _solve_kernel,
        grid=grid,
        in_specs=[block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(lanes.shape, lanes.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid)),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_GDN_SOLVE):
        t = call(lanes)
    return jnp.transpose(t, (2, 0, 1)).reshape(a.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tril_inverse(a, mosaic=False):
    """``(I + a)^-1`` for ``a [.., C, C]`` float32, zero on and above the
    diagonal, C a power of two; by ``_solve``'s call where ``mosaic``, else
    by the six merges."""
    with _scopes.scope(_scopes.GDN_SOLVE):
        if mosaic:
            return _solve(a, interpret=_interpret())
        return _tril_inverse_impl(a)


def _tril_inverse_fwd(a, mosaic):
    t = _tril_inverse(a, mosaic)
    return t, t


def _tril_inverse_bwd(mosaic, t, dt):
    with _scopes.scope(_scopes.GDN_SOLVE):
        tt = jnp.swapaxes(t, -1, -2)
        da = -jnp.matmul(tt, jnp.matmul(dt, tt, precision=_HIGHEST),
                         precision=_HIGHEST)
        return (jnp.tril(da, -1),)


_tril_inverse.defvjp(*_scopes.rules(
    "_tril_inverse", _tril_inverse_fwd, _tril_inverse_bwd))


def _dot(spec, x, y):
    """A product on the MXU: inputs as they are, float32 out."""
    return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)


def _prepare(q, k, v, g, beta, mosaic=False):
    """What every chunk needs and no chunk's state enters.  q, k ``[N, B,
    H, C, d_k]``, v ``[.., d_v]``, g and beta ``[N, B, H, C]`` float32;
    ``mosaic``: the chunks' systems by ``_solve``'s call.  Returns W, U0,
    P = M * Q K^T, e^gamma Q and e^{gamma_C - gamma} K (the dtype of q) and
    e^{gamma_C} ``[N, B, H]`` (float32)."""
    dtype = q.dtype
    chunk = q.shape[-2]
    gamma = jnp.cumsum(g, axis=-1)
    last = gamma[..., -1:]
    rows = jnp.arange(chunk)[:, None]
    cols = jnp.arange(chunk)[None, :]
    # exp of what is masked away never runs: above the diagonal the
    # difference is positive and may overflow.
    decay = jnp.exp(jnp.where(rows >= cols,
                              gamma[..., :, None] - gamma[..., None, :],
                              -jnp.inf))
    a = jnp.where(rows > cols,
                  beta[..., None] * decay * _dot("...id,...jd->...ij", k, k),
                  0.0)
    t = _tril_inverse(a, mosaic).astype(dtype)
    into = jnp.exp(gamma)
    w = _dot("...ij,...jd->...id", t,
             (k * (beta * into)[..., None]).astype(dtype)).astype(dtype)
    u0 = _dot("...ij,...jd->...id", t,
              (v * beta[..., None]).astype(dtype)).astype(dtype)
    p = (decay * _dot("...id,...jd->...ij", q, k)).astype(dtype)
    qg = (q * into[..., None]).astype(dtype)
    kg = (k * jnp.exp(last - gamma)[..., None]).astype(dtype)
    return w, u0, p, qg, kg, jnp.exp(last[..., 0])


def _chunk_values(w, u0, state):
    """U = U0 - W S^T, float32; ``state`` in the dtype of W."""
    return u0.astype(jnp.float32) - _dot("bhck,bhvk->bhcv", w, state)


def _walk(prepared, state):
    """The chunks of one slab in order, from ``state`` (float32): the
    state the slab leaves, O ``[n, B, H, C, d_v]`` and the state each chunk
    started from ``[n, B, H, d_v, d_k]``, both in the dtype of W (what is
    kept of a state is the rounding that the chunk's products read)."""

    def step(state, chunk):
        w, u0, p, qg, kg, a_last = chunk
        read = state.astype(w.dtype)
        u = _chunk_values(w, u0, read).astype(w.dtype)
        o = _dot("bhck,bhvk->bhcv", qg, read) + _dot("bhcj,bhjv->bhcv", p, u)
        new = (a_last[..., None, None] * state
               + _dot("bhcv,bhck->bhvk", u, kg))
        return new, (o.astype(w.dtype), read)

    return jax.lax.scan(step, state, prepared)


def _walk_back(prepared, states, d_o, d_state):
    """The chunks of one slab in reverse.  A step gets the cotangent of
    the state its chunk left and gives that of the state it started from;
    U is made again from that state.  Returns the cotangent of the state
    the slab started from and those of ``prepared``."""
    dtype = d_o.dtype

    def step(d_new, chunk):
        w, u0, p, qg, kg, a_last, state, d_o = chunk
        u = _chunk_values(w, u0, state).astype(dtype)
        d_new_t = d_new.astype(dtype)
        d_u = (_dot("bhcj,bhcv->bhjv", p, d_o)
               + _dot("bhck,bhvk->bhcv", kg, d_new_t)).astype(dtype)
        d_state = (a_last[..., None, None] * d_new
                   + _dot("bhcv,bhck->bhvk", d_o, qg)
                   - _dot("bhcv,bhck->bhvk", d_u, w))
        return d_state, (
            (-_dot("bhcv,bhvk->bhck", d_u, state)).astype(dtype),    # W
            d_u,                                                    # U0
            _dot("bhcv,bhjv->bhcj", d_o, u).astype(dtype),          # P
            _dot("bhcv,bhvk->bhck", d_o, state).astype(dtype),      # e^g Q
            _dot("bhcv,bhvk->bhck", u, d_new_t).astype(dtype),      # .. K
            jnp.sum(d_new * state, axis=(-1, -2)))                  # e^g_C

    return jax.lax.scan(step, d_state, (*prepared, states, d_o),
                        reverse=True)


def _rule_walk(q, k, v, g, beta, mosaic=False):
    """O and the chunks' starting states for chunked inputs, slab by slab:
    a slab is prepared, then walked."""
    _, batch, heads, _, d_k = q.shape

    def slab(state, inputs):
        return _walk(_prepare(*inputs, mosaic), state)

    _, (o, states) = jax.lax.scan(
        slab, jnp.zeros((batch, heads, v.shape[-1], d_k), jnp.float32),
        tuple(_slabs(x) for x in (q, k, v, g, beta)))
    return (o.reshape(-1, *o.shape[2:]),
            states.reshape(-1, *states.shape[2:]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, mosaic):
    return _rule_walk(q, k, v, g, beta, mosaic)[0]


def _rule_fwd(q, k, v, g, beta, mosaic):
    o, states = _rule_walk(q, k, v, g, beta, mosaic)
    return o, (q, k, v, g, beta, states)


def _rule_bwd(mosaic, res, d_o):
    """The slabs in reverse: a slab is prepared again (with what its
    transpose needs), its chunks are walked in reverse from the states the
    forward walk kept, and the cotangents of what was prepared go back
    through the preparation to q, k, v and the gates."""
    *inputs, states = res

    def slab(d_state, xs):
        *inputs, states, d_o = xs
        prepared, pull = jax.vjp(
            functools.partial(_prepare, mosaic=mosaic), *inputs)
        d_state, d_prepared = _walk_back(prepared, states, d_o, d_state)
        return d_state, pull(d_prepared)

    _, grads = jax.lax.scan(
        slab, jnp.zeros(states.shape[1:], jnp.float32),
        tuple(_slabs(x) for x in (*inputs, states, d_o)), reverse=True)
    return tuple(x.reshape(-1, *x.shape[2:]) for x in grads)


_rule.defvjp(*_scopes.rules("_rule", _rule_fwd, _rule_bwd))


# -- the walk as one Mosaic call each way -------------------------------------
#
# A grid step: chunk n of batch row b, block ``hb`` of the heads (``_STEP``
# of them, each whole lane tiles).  q, k, v are read, and o, dq, dk, dv
# written, as ``[C, heads x d]`` lane blocks of the rows ``[B, S, H d]``; A
# (float32) and T are ``[B, N, H / 2, C, 2 C]``, two heads' side by side (A
# formed by a stateless call of the same grid, T ``_solve``'s).  What is one
# number a row and head XLA makes from gamma and beta (``_quantities``) and
# hands over in the two layouts a step reads: down the rows, one lane a
# quantity and head (a column ``[C, 1]`` that the VPU spreads over a head's
# lanes: nothing to round), and gamma and e^{gamma_C} along the lanes (what
# the decays read across a row, and what the state keeps).

_STEP = 8              # heads a grid step takes at most
_SLOTS = 8             # lanes a quantity takes in ``_quantities``' columns
(_GAMMA, _INTO, _AFTER, _BETA, _BETA_INTO) = range(5)


def _heads_a_step(heads: int) -> int:
    """The most pairs of heads, at most ``_STEP`` heads, that divide
    ``heads``: a step's independent chains (W S^T -> U -> U^T K of one head
    behind another's); pairs, because two heads' systems lie side by side."""
    return next(k for k in range(_STEP, 0, -2) if heads % k == 0)


def _quantities(gamma, beta, a_step: int):
    """From gamma (the log-decay summed from a chunk's start) and beta ``[B,
    H, N, C]`` float32: ``columns [B, H / a_step, S, 128]``, lane ``8 k + j``
    quantity k (gamma, e^gamma, e^{gamma_C - gamma}, beta, beta e^gamma) of
    the block's head j; and ``across [B, N, H / a_step, 16, 128]``, row j
    head j's gamma along the lanes and row ``8 + j`` its e^{gamma_C} on
    every lane."""
    batch, heads, chunks, chunk = gamma.shape
    blocks = heads // a_step
    last = gamma[..., -1:]
    into = jnp.exp(gamma)

    def by_block(x):
        """``[B, H, N, c] -> [B, blocks, 8, N, c]``."""
        x = x.reshape(batch, blocks, a_step, chunks, x.shape[-1])
        return jnp.pad(x, ((0, 0), (0, 0), (0, _SLOTS - a_step), (0, 0),
                           (0, 0)))

    columns = jnp.stack([by_block(x) for x in (
        gamma, into, jnp.exp(last - gamma), beta, beta * into)], axis=2)
    columns = columns.reshape(batch, blocks, 5 * _SLOTS, chunks * chunk)
    columns = jnp.pad(columns.transpose(0, 1, 3, 2), (
        (0, 0), (0, 0), (0, 0), (0, _LANES - 5 * _SLOTS)))
    across = jnp.concatenate([
        jnp.pad(by_block(gamma), ((0, 0),) * 4 + ((0, _LANES - chunk),)),
        jnp.broadcast_to(by_block(jnp.exp(last)),
                         (batch, blocks, _SLOTS, chunks, _LANES))], axis=2)
    return columns, across.transpose(0, 3, 1, 2, 4)


def _solved_back(t, d_t):
    """``T^T dT T^T`` for ``t`` and ``d_t [C, C]`` in the operands' dtype
    (the rounding of T that W and U0 were made of, and of dT that the ``jnp``
    body's transpose reads): the inner product kept as two bf16 pieces, three
    passes (``highest`` on float32 operands takes twelve)."""
    if d_t.dtype != jnp.bfloat16:
        return jnp.matmul(t.T, jnp.matmul(d_t, t.T, precision=_HIGHEST),
                          precision=_HIGHEST)
    solved = t.T
    hi, mid, _ = _pieces(_mm(d_t, solved))
    return _mm(solved, hi) + _mm(solved, mid)


def _scaled(x, column):
    """``x [C, d]`` times ``column [C, 1]`` (float32), in the dtype of x."""
    return (x.astype(jnp.float32) * column).astype(x.dtype)


def _on_lanes(row, width: int):
    """``row [1, 128]`` (one value on every lane) as ``[1, width]``."""
    return row if width == _LANES else jnp.tile(row, (1, width // _LANES))


def _columns_of(j, columns_ref):
    """Head j's five quantities, ``[C, 1]`` each."""
    return [columns_ref[:, _SLOTS * n + j:_SLOTS * n + j + 1]
            for n in range(5)]


def _decays(j, gamma, across_ref, strictly=False):
    """``exp(gamma_i - gamma_j)`` of head j for j <= i (``strictly``: j <
    i), else 0; ``gamma [C, 1]``.  exp of what is masked away never runs:
    above the diagonal the difference is positive and may overflow."""
    chunk = gamma.shape[0]
    rows, cols = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    return jnp.exp(jnp.where(rows > cols if strictly else rows >= cols,
                             gamma - across_ref[j:j + 1, :chunk], -jnp.inf))


def _systems_kernel(k_ref, columns_ref, across_ref, a_ref):
    # k_ref [C, a d_k]; columns_ref, across_ref: _quantities'; a_ref [a / 2,
    # C, 2 C] float32, two heads' A side by side: A[i, j] = beta_i
    # exp(gamma_i - gamma_j) (k_i . k_j) under the diagonal, zero on and
    # above it.
    a_step = 2 * a_ref.shape[0]
    d_k = k_ref.shape[1] // a_step

    def system(j):
        k = k_ref[:, j * d_k:(j + 1) * d_k]
        col = _columns_of(j, columns_ref)
        return (col[_BETA] * _decays(j, col[_GAMMA], across_ref, True)
                * _mm(k, k, _NT))

    for j in range(0, a_step, 2):
        a_ref[j // 2] = jnp.concatenate([system(j), system(j + 1)], axis=1)


def _system_of(j, t_ref):
    """Head j's ``[C, C]`` of ``t_ref [a / 2, C, 2 C]``: two heads' side by
    side."""
    chunk = t_ref.shape[1]
    return t_ref[j // 2][:, j % 2 * chunk:(j % 2 + 1) * chunk]


def _a_head(j, q_ref, k_ref, v_ref, t_ref, columns_ref, across_ref, d_k,
            d_v, p_first=False):
    """What head j of the step's chunk needs and no state enters: q, k, v,
    T, the five columns, e^{gamma_C} on the lanes, the decays M, Q K^T, P
    = M * Q K^T, beta e^gamma K, beta V, W, U0, e^gamma Q and e^{gamma_C -
    gamma} K; in VMEM and nowhere else."""
    dtype = q_ref.dtype
    q = q_ref[:, j * d_k:(j + 1) * d_k]
    k = k_ref[:, j * d_k:(j + 1) * d_k]
    v = v_ref[:, j * d_v:(j + 1) * d_v]
    t = _system_of(j, t_ref)
    col = _columns_of(j, columns_ref)
    decay = _decays(j, col[_GAMMA], across_ref)
    kb, vb = _scaled(k, col[_BETA_INTO]), _scaled(v, col[_BETA])
    # (The order is the schedule's: the backward call's is 7 % shorter with
    # P ahead of W and U0, the forward call's 8 % longer; bundles counted.)
    if p_first:
        p32 = decay * _mm(q, k, _NT)
    w, u0 = _mm(t, kb).astype(dtype), _mm(t, vb).astype(dtype)
    if not p_first:
        p32 = decay * _mm(q, k, _NT)
    return dict(
        q=q, k=k, v=v, t=t, col=col, decay=decay, kb=kb, vb=vb, w=w, u0=u0,
        last=across_ref[_SLOTS + j:_SLOTS + j + 1, :], p32=p32,
        p=p32.astype(dtype), qg=_scaled(q, col[_INTO]),
        kg=_scaled(k, col[_AFTER]))


def _values(head, read):
    """U = U0 - W S^T in the operands' dtype; ``read``: the state as the
    products read it."""
    return (head["u0"].astype(jnp.float32)
            - _mm(head["w"], read, _NT)).astype(read.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, t_ref, columns_ref, across_ref, o_ref,
                *rest, keep):
    # q_ref, k_ref [C, a d_k], v_ref, o_ref [C, a d_v]: the step's heads'
    # lanes; t_ref [a / 2, C, 2 C]; columns_ref [C, 128] and across_ref
    # [16, 128] float32 (_quantities').  With ``keep`` a result more,
    # started_ref [a, d_v, d_k]: the state the chunk started from, as the
    # products read it.  Scratch: a of s_ref [blocks, d_v, d_k] float32, the
    # state of head j of every block (a reference a head: a step's heads are
    # independent chains, and the stores and loads of ONE reference keep
    # their order).
    if keep:
        started_ref, *s_refs = rest
    else:
        s_refs = rest
    _, d_v, d_k = s_refs[0].shape
    block = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        for s_ref in s_refs:
            s_ref[block] = jnp.zeros(s_ref.shape[1:], jnp.float32)

    for j, s_ref in enumerate(s_refs):
        head = _a_head(j, q_ref, k_ref, v_ref, t_ref, columns_ref,
                       across_ref, d_k, d_v)
        state = s_ref[block]
        read = state.astype(q_ref.dtype)
        if keep:
            started_ref[j] = read
        u = _values(head, read)
        o = _mm(head["qg"], read, _NT) + _mm(head["p"], u)
        o_ref[:, j * d_v:(j + 1) * d_v] = o.astype(o_ref.dtype)
        s_ref[block] = (_on_lanes(head["last"], d_k) * state
                        + _mm(u, head["kg"], _TN))


def _bwd_kernel(q_ref, k_ref, v_ref, t_ref, columns_ref, across_ref,
                do_ref, started_ref, dq_ref, dk_ref, dv_ref, sums_ref,
                *ds_refs):
    # As _fwd_kernel (the chunks arrive in reverse), with do_ref [C, a d_v]
    # the result's cotangent and started_ref [a, d_v, d_k].  Results: dq_ref,
    # dk_ref [C, a d_k], dv_ref [C, a d_v] and sums_ref [C, 128] float32:
    # lane j head j's d gamma, lane 8 + j its d beta.  Scratch: a of ds_ref
    # [blocks, d_v, d_k] float32, the cotangent of the state the chunk
    # leaves, a head each.  dT, dA = -T^T dT T^T under the diagonal
    # (``_solved_back``) and what A sends on to k, beta and gamma never leave
    # VMEM.
    _, d_v, d_k = ds_refs[0].shape
    chunk = q_ref.shape[0]
    dtype = q_ref.dtype
    block = pl.program_id(2)
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _():
        for ds_ref in ds_refs:
            ds_ref[block] = jnp.zeros(ds_ref.shape[1:], f32)

    lane = _iota((chunk, _LANES), 1)
    sums = jnp.zeros((chunk, _LANES), f32)

    def folded(x):
        """``x [C, n 128]`` with its lane tiles added: ``[C, 128]``."""
        return sum(x[:, at:at + _LANES] for at in range(0, x.shape[1],
                                                        _LANES))

    for j, ds_ref in enumerate(ds_refs):
        head = _a_head(j, q_ref, k_ref, v_ref, t_ref, columns_ref,
                       across_ref, d_k, d_v, p_first=True)
        q, k, v, col = (head[name] for name in ("q", "k", "v", "col"))
        q32, k32, v32 = (x.astype(f32) for x in (q, k, v))
        state = started_ref[j]
        u = _values(head, state)
        d_o = do_ref[:, j * d_v:(j + 1) * d_v]
        d_new = ds_ref[block]
        d_new_t = d_new.astype(dtype)
        kept = _on_lanes(head["last"], d_k)                 # e^{gamma_C}
        d_u = (_mm(head["p"], d_o, _TN)
               + _mm(head["kg"], d_new_t, _NT)).astype(dtype)
        # (Two products that contract over the chunk's rows are ONE, their
        # operands end to end along the contraction, here and below.)
        ds_ref[block] = kept * d_new + _mm(
            jnp.concatenate([d_o, d_u], axis=0),
            jnp.concatenate([head["qg"], -head["w"]], axis=0), _TN)
        d_w = (-_mm(d_u, state)).astype(dtype)
        d_qg = _mm(d_o, state)
        d_kg = _mm(u, d_new_t)
        d_p = _mm(d_o, u, _NT)
        solved = head["t"].T
        d_kb, d_vb = _mm(solved, d_w), _mm(solved, d_u)
        # T's cotangent, rounded as the ``jnp`` body's, then A's: -T^T dT
        # T^T under the diagonal, and through A = beta M (K K^T) on to k,
        # beta and gamma.
        d_t = _mm(jnp.concatenate([d_w, d_u], axis=1), jnp.concatenate(
            [head["kb"], head["vb"]], axis=1), _NT).astype(dtype)
        d_a = -_solved_back(head["t"], d_t)
        d_a = d_a * _decays(j, col[_GAMMA], across_ref, True)
        d_kk = col[_BETA] * d_a
        d_qk = d_p * head["decay"]
        dq_ref[:, j * d_k:(j + 1) * d_k] = (
            col[_INTO] * d_qg + _mm(d_qk.astype(dtype), k)).astype(
                dq_ref.dtype)
        dk_ref[:, j * d_k:(j + 1) * d_k] = (
            col[_BETA_INTO] * d_kb + col[_AFTER] * d_kg + _mm(
                jnp.concatenate([d_kk + d_kk.T, d_qk.T], axis=1).astype(
                    dtype), jnp.concatenate([k, q], axis=0))).astype(
                        dk_ref.dtype)
        dv_ref[:, j * d_v:(j + 1) * d_v] = (col[_BETA] * d_vb).astype(
            dv_ref.dtype)
        # A row's sums over its head's lanes.
        through = d_kb * k32                                # d (beta e^gamma)
        written = col[_AFTER] * d_kg * k32
        d_gamma = (col[_BETA_INTO] * through + col[_INTO] * d_qg * q32
                   - written)
        # gamma_C: what the state keeps, and every row's e^{gamma_C - gamma}.
        d_gamma = d_gamma + jnp.where(
            _iota((chunk, d_k), 0) == chunk - 1, kept * jnp.sum(
                d_new * state.astype(f32), axis=0, keepdims=True) + jnp.sum(
                    written, axis=0, keepdims=True), 0.0)
        # Through the decays, M's and A's: a row's sum of dP * P + dA * A
        # less a column's, as ONE sum of the matrix less its transpose
        # (within a chunk the two cancel in the sum: the same float32
        # entries on both sides, as in ``ops/ssd.py``).
        d_a = d_a * _mm(k, k, _NT)              # dA * M * K K^T: d beta's
        moved = d_p * head["p32"] + col[_BETA] * d_a
        d_gamma = jnp.sum(folded(d_gamma), axis=1, keepdims=True) + jnp.sum(
            moved - moved.T, axis=1, keepdims=True)
        d_beta = jnp.sum(
            folded(col[_INTO] * through) + folded(d_vb * v32), axis=1,
            keepdims=True) + jnp.sum(d_a, axis=1, keepdims=True)
        for at, x in ((j, d_gamma), (_SLOTS + j, d_beta)):
            sums = jnp.where(lane == at, x, sums)
    sums_ref[...] = sums


def _grid(q, v, heads: int, chunk: int, reverse: bool):
    """The grid and the blocks: of the rows of q and k ``[B, S, H d_k]`` and
    of v and o ``[B, S, H d_v]``, of A and T ``[B, N, H / 2, C, 2 C]``, of
    ``_quantities``' two arrays, of the kept states ``[N, B, H, d_v, d_k]``,
    and the scratch; the chunks in reverse for the backward call."""
    batch, seq, _ = q.shape
    a_step = _heads_a_step(heads)
    d_k, d_v = q.shape[2] // heads, v.shape[2] // heads
    chunks = seq // chunk

    def at(n):
        return chunks - 1 - n if reverse else n

    specs = {
        "k": pl.BlockSpec((None, chunk, a_step * d_k),
                          lambda b, n, h: (b, at(n), h)),
        "v": pl.BlockSpec((None, chunk, a_step * d_v),
                          lambda b, n, h: (b, at(n), h)),
        "t": pl.BlockSpec((None, None, a_step // 2, chunk, 2 * chunk),
                          lambda b, n, h: (b, at(n), h, 0, 0)),
        "columns": pl.BlockSpec((None, None, chunk, _LANES),
                                lambda b, n, h: (b, h, at(n), 0)),
        "across": pl.BlockSpec((None, None, None, 2 * _SLOTS, _LANES),
                               lambda b, n, h: (b, at(n), h, 0, 0)),
        "started": pl.BlockSpec((None, None, a_step, d_v, d_k),
                                lambda b, n, h: (at(n), b, h, 0, 0)),
    }
    grid = (batch, chunks, heads // a_step)
    return grid, specs, [pltpu.VMEM((grid[2], d_v, d_k),
                                    jnp.float32)] * a_step


def _params(*semantics):
    """(No limit stated: a step's blocks, the heads' states and what spills
    take 4 to 8 MB of the default 16.)"""
    return pltpu.CompilerParams(dimension_semantics=semantics)


# (Jits, as ``_solve``'s: a step traces each body once a shape, not once a
# layer and pass.)
@functools.partial(jax.jit, static_argnames=("keep", "interpret"))
def _forward(q, k, v, gamma, beta, keep, interpret):
    batch, seq, _ = q.shape
    heads, chunks, chunk = gamma.shape[1:]
    grid, specs, scratch = _grid(q, v, heads, chunk, False)
    columns, across = _quantities(gamma, beta, len(scratch))
    systems = jax.ShapeDtypeStruct(
        (batch, chunks, heads // 2, chunk, 2 * chunk), jnp.float32)
    with _scopes.span(_scopes.MOSAIC_GDN_SCAN):
        a = pl.pallas_call(
            _systems_kernel,
            grid=grid,
            in_specs=[specs["k"], specs["columns"], specs["across"]],
            out_specs=specs["t"],
            out_shape=systems,
            compiler_params=_params("parallel", "parallel", "parallel"),
            interpret=interpret,
        )(k, columns, across)
    with _scopes.scope(_scopes.GDN_SOLVE):
        # (Kept, and read, in the dtype the products take it in: half the
        # bytes a layer holds for its backward pass.)
        t = _solve(a, interpret=interpret, pairs=True).astype(q.dtype)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [specs["v"]]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct(
            (chunks, batch, heads, *scratch[0].shape[1:]), q.dtype))
        out_specs.append(specs["started"])
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, keep=keep),
        grid=grid,
        in_specs=[specs["k"], specs["k"], specs["v"], specs["t"],
                  specs["columns"], specs["across"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_GDN_SCAN):
        return (*call(q, k, v, t, columns, across), t)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(q, k, v, gamma, beta, t, started, d_o, interpret):
    batch, seq, _ = q.shape
    heads, chunks, chunk = gamma.shape[1:]
    grid, specs, scratch = _grid(q, v, heads, chunk, True)
    columns, across = _quantities(gamma, beta, len(scratch))
    call = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[specs["k"], specs["k"], specs["v"], specs["t"],
                  specs["columns"], specs["across"], specs["v"],
                  specs["started"]],
        out_specs=[specs["k"], specs["k"], specs["v"], specs["columns"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(columns.shape, jnp.float32)],
        scratch_shapes=scratch,
        # dq, dk, dv and the sums take the place of q, k, dO and the columns
        # (a step's blocks are read before they are written, at the same
        # index): where the caller's are dead behind this call, as a layer's
        # are, 0.43 GB less is held at the step's fullest point.
        input_output_aliases={0: 0, 1: 1, 6: 2, 4: 3},
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_GDN_SCAN):
        d_q, d_k, d_v, sums = call(q, k, v, t, columns, across, d_o, started)

    def a_head(at):
        """Lanes ``at ..`` of the sums ``[B, blocks, S, 128] -> [B, H, N,
        C]``."""
        x = sums[..., at:at + len(scratch)].transpose(0, 1, 3, 2)
        return x.reshape(batch, heads, chunks, chunk)

    return d_q, d_k, d_v, a_head(0), a_head(_SLOTS)


@jax.custom_vjp
def walk_rows(q, k, v, gamma, beta):
    """``o [B, S, H d_v]`` of the rule for rows q, k ``[B, S, H d_k]`` and v
    ``[B, S, H d_v]`` (d_k and d_v whole lane tiles, S whole chunks), gamma
    (the log-decay summed from each chunk's start) and beta ``[B, H, N, C]``
    float32.  Three Mosaic calls -- the chunks' systems A, their solve, the
    walk -- and ONE for every gradient; called directly it runs interpreted
    off the TPU."""
    return _forward(q, k, v, gamma, beta, keep=False,
                    interpret=_interpret())[0]


def _walk_rows_fwd(q, k, v, gamma, beta):
    o, started, t = _forward(q, k, v, gamma, beta, keep=True,
                             interpret=_interpret())
    return o, (q, k, v, gamma, beta, t, started)


def _walk_rows_bwd(kept, d_o):
    return _backward(*kept, d_o, interpret=_interpret())


walk_rows.defvjp(*_scopes.rules("walk_rows", _walk_rows_fwd, _walk_rows_bwd))


def _rule_rows(q, k, v, g, beta):
    """``o [B, S, H, d_v]`` by ``walk_rows``' calls for operands whose
    heads are whole lane tiles and whose rows are whole chunks; gamma by the
    triangular product (``ops/ssd.py::_summed``), which XLA transposes."""
    batch, seq, heads, d_v = v.shape

    def lanes(x):
        """``[B, S, H] -> [B, H, N, C]``: the sequence along the lanes."""
        return x.transpose(0, 2, 1).reshape(batch, heads, seq // CHUNK,
                                            CHUNK)

    o = walk_rows(q.reshape(batch, seq, -1), k.reshape(batch, seq, -1),
                  v.reshape(batch, seq, -1), _summed(lanes(g)), lanes(beta))
    return o.reshape(batch, seq, heads, d_v)


def _chunks(q, k, v, g, beta):
    """The sequence cut into chunks, padded to whole ones with rows that
    neither write (beta 0) nor decay (g 0)."""
    pad = -q.shape[1] % CHUNK
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    return tuple(_chunked(x, CHUNK)
                 for x in (q, k, v.astype(q.dtype), g, beta))


def gated_delta_rule(q, k, v, g, beta, in_place: bool | None = None):
    """``o [B, S, H, d_v]`` of the recurrence above, in the dtype of v.

    q, k ``[B, S, H, d_k]`` (as the rule reads them: normed and scaled by
    the caller), v ``[B, S, H, d_v]``, ``g = log alpha <= 0`` and beta
    ``[B, S, H]`` (taken to float32).  The state starts at zero and ends
    with the sequence; a length that is no multiple of ``CHUNK`` is
    padded.  ``in_place`` is the caller's word that this trace may hold
    Mosaic calls (``models/llama.py::LlamaLayer`` reads it off the model's
    ``attention_fn``): the chunks' systems are then solved by ``_solve``'s
    call, else by the six merges, and the chunks are walked by
    ``walk_rows``' calls where the heads are whole lane tiles
    (``_why_no_walk``), else by the ``jnp`` walk.  A caller that cannot say
    it beside the operands says it around the call (``calls_in_place``).
    Which a trace took, and why, ``solve_counts()`` and ``walk_counts()``
    say."""
    batch, seq, heads, d_v = v.shape
    if in_place is None:
        in_place = called_in_place()
    why = _why_not() if in_place else NOT_IN_PLACE
    _trace_counts.note(_SOLVE, why or _MOSAIC)
    why_walk = why or _why_no_walk(q.shape[-1], d_v, heads)
    _trace_counts.note(_WALK, why_walk or _MOSAIC)
    if why_walk is None:
        # Rows that pad the last chunk neither write (beta 0) nor decay.
        o = _rule_rows(*(_padded(x, CHUNK) for x in (
            q, k, v.astype(q.dtype), g.astype(jnp.float32),
            beta.astype(jnp.float32))))
        return o[:, :seq].astype(v.dtype)
    o = _rule(*_chunks(q, k, v, g, beta), why is None)
    o = jnp.moveaxis(o, (0, 2), (1, 3))                  # [B, N, C, H, d_v]
    return o.reshape(batch, -1, heads, d_v)[:, :seq].astype(v.dtype)


def walks_rows(d_k: int, d_v: int, heads: int, in_place: bool) -> bool:
    """Whether a rule of such heads whose caller says ``in_place`` walks its
    chunks by ``walk_rows``' calls: q, k and v read, and o written, as rows
    ``[B, S, H d]``.  Else the ``jnp`` walk makes o chunk by chunk, ``[N, B,
    H, CHUNK, d_v]`` before it is rows, which
    ``ops/gated_norm.py::norm_gated`` can read as it lies."""
    return bool(in_place) and (
        _why_not() or _why_no_walk(d_k, d_v, heads)) is None


def _copy_heads_kernel(x_ref, o_ref, *, heads, times):
    # x_ref [R, H d] -> o_ref [R, H times d]: a head's lanes ``times`` times.
    width = x_ref.shape[1] // heads
    for j in range(heads):
        tile = x_ref[:, j * width:(j + 1) * width]
        for at in range(j * times, (j + 1) * times):
            o_ref[:, at * width:(at + 1) * width] = tile


def _sum_heads_kernel(d_ref, o_ref, *, heads, times):
    # d_ref [R, H times d] -> o_ref [R, H d]: a head's ``times`` copies'
    # cotangents added in float32.
    width = o_ref.shape[1] // heads
    for j in range(heads):
        o_ref[:, j * width:(j + 1) * width] = sum(
            d_ref[:, at * width:(at + 1) * width].astype(jnp.float32)
            for at in range(j * times, (j + 1) * times)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "times", "back",
                                             "interpret"))
def _heads_call(rows, heads, times, back, interpret):
    batch, seq, lanes = rows.shape
    out = lanes // times if back else lanes * times
    step = math.gcd(seq, 256)

    def block(width):
        return pl.BlockSpec((None, step, width), lambda b, r: (b, r, 0))

    call = pl.pallas_call(
        functools.partial(_sum_heads_kernel if back else _copy_heads_kernel,
                          heads=heads, times=times),
        grid=(batch, seq // step),
        in_specs=[block(lanes)],
        out_specs=block(out),
        out_shape=jax.ShapeDtypeStruct((batch, seq, out), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )
    with _scopes.span(_scopes.MOSAIC_GDN_SCAN):
        return call(rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _heads_copied(rows, heads, times):
    """``rows [B, S, H d]`` with each head's d lanes (whole lane tiles)
    ``times`` times in a row, ``[B, S, H times d]``: one Mosaic pass that
    copies lane tiles where they lie, and one that adds them for the
    gradient.  (As ``jnp.repeat`` of ``[B, S, H, d]`` XLA:TPU broadcasts into
    ``[.., H, times, d]``, whose tiles are not the rows': a relayout each
    way, 1.1 ms a tensor and pass at ``[2, 8192, 32 x 128]``, and the rows
    kept twice: PERF.md, PR 64.)"""
    return _heads_call(rows, heads=heads, times=times, back=False,
                       interpret=_interpret())


def _heads_copied_fwd(rows, heads, times):
    return _heads_copied(rows, heads, times), None


def _heads_copied_bwd(heads, times, _, d_rows):
    return (_heads_call(d_rows, heads=heads, times=times, back=True,
                        interpret=_interpret()),)


_heads_copied.defvjp(*_scopes.rules(
    "_heads_copied", _heads_copied_fwd, _heads_copied_bwd))


def key_heads_copied(x, times: int, rows: bool):
    """``x [B, S, H, d] -> [B, S, H times, d]``: value head j reads key head
    ``j // times``.  ``rows``: the rule will read the result as rows
    (``walks_rows``), so the copies are made there, lane tile by lane tile
    (``_heads_copied``: whole tiles of 16 rows or more); else
    ``jnp.repeat``."""
    batch, seq, heads, width = x.shape
    if not rows or width % _LANES or seq % 16:
        return jnp.repeat(x, times, axis=2)
    return _heads_copied(x.reshape(batch, seq, heads * width), heads,
                         times).reshape(batch, seq, heads * times, width)


def gated_delta_states(q, k, v, g, beta):
    """The state each chunk started from, ``[N, B, H, d_v, d_k]`` in the
    dtype of q: for counters and tests, no gradient of its own."""
    return _rule_walk(*_chunks(q, k, v, g, beta))[1]
