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

Everything but the last three lines is the same for every chunk and runs
for a slab of 8 chunks at once (``ops/chunking.py``) (``_prepare``); those three
carry the state from chunk to chunk in float32 (``_walk``, a ``lax.scan``
over the slab's chunks).  The rule is a ``custom_vjp`` (``_rule``): the
forward pass keeps q, k, v, the gates and the state each chunk started
from; the backward pass goes over the slabs in reverse, prepares a slab
again, walks its chunks in reverse from those states (U made again), and
sends the cotangents of what was prepared back through the preparation.
No step of either walk is a single token.

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
one call a slab, 70 us on the v5e where the merges take 505 (PERF.md).
Cumulative log-decays and the solve stay in float32 whatever the inputs'
dtype; the other products take their inputs in the dtype of q (bf16 on the
training path) and accumulate in float32, and the state is cast to that
dtype where a product reads it and carried in float32.

A Mosaic call is the caller's choice (the partitioner cannot split one):
``gated_delta_rule`` takes the call only where its caller says that the
trace may hold Mosaic calls (``in_place``: ``llama.py::LlamaLayer`` reads it
off the model's ``attention_fn``, as for the rotation and the convolutions)
and a TPU runs the trace; which body a trace took is counted
(``solve_counts``).  Everything else is ``jax.numpy``: XLA:TPU runs the
products on the MXU and the scans as ``while``s.  What further calls would
buy is in PERF.md (the tiles are 64 x 96 and 64 x 192 against the MXU's
128 x 128).
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
from horovod_tpu.ops.chunking import chunked as _chunked, slabs as _slabs

__all__ = ["CHUNK", "gated_delta_rule", "gated_delta_states", "solve_counts",
           "calls_in_place", "NOT_IN_PLACE", "NO_TPU"]

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


def solve_counts() -> dict:
    """``{"mosaic": n, "plain": {reason: n}}``: how many traced calls of
    ``gated_delta_rule`` solved their chunks' systems in the Mosaic call,
    and how many in the ``jnp`` body, by reason.  Process-global, counted
    once a TRACE."""
    plain = _trace_counts.counts(_SOLVE)
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
    was = getattr(_around, "in_place", False)
    _around.in_place = bool(answer)
    try:
        yield
    finally:
        _around.in_place = was


def _why_not():
    """None where a rule whose caller said ``in_place`` solves its chunks'
    systems by ``_solve``'s call, else the reason it does not.  The call
    takes any number of ``CHUNK`` x ``CHUNK`` systems (whole sublane tiles,
    ``_solve_kernel``), so the shape never refuses.  Off the TPU the call
    would run interpreted, many times slower than the six merges it
    replaces: the ``jnp`` body there, and the bits it always gave."""
    return NO_TPU if _interpret() else None


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
@functools.partial(jax.jit, static_argnames=("interpret",))
def _solve(a, interpret):
    """``(I + a)^-1`` for ``a [.., C, C]`` float32 by one Mosaic call: 128
    matrices a grid step (2 MiB a block of A and of T, two buffers each:
    half the default scoped VMEM, so the call states no limit).  Where the
    count is no multiple of 128 the last step's spare lanes hold whatever
    the block brought, are worked on like the others (a lane never reads
    another) and are not written back."""
    chunk = a.shape[-1]
    count = math.prod(a.shape[:-2])
    lanes = jnp.transpose(a.reshape(count, chunk, chunk), (1, 2, 0))
    block = pl.BlockSpec((chunk, chunk, _LANES), lambda m: (0, 0, m))
    call = pl.pallas_call(
        _solve_kernel,
        grid=(pl.cdiv(count, _LANES),),
        in_specs=[block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(lanes.shape, lanes.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
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


_tril_inverse.defvjp(_tril_inverse_fwd, _tril_inverse_bwd)


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


_rule.defvjp(_rule_fwd, _rule_bwd)


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
    call, else by the six merges.  A caller that cannot say it beside the
    operands says it around the call (``calls_in_place``).  Which a trace
    took, and why, ``solve_counts()`` says."""
    batch, seq, heads, d_v = v.shape
    if in_place is None:
        in_place = getattr(_around, "in_place", False)
    why = _why_not() if in_place else NOT_IN_PLACE
    _trace_counts.note(_SOLVE, why or _MOSAIC)
    o = _rule(*_chunks(q, k, v, g, beta), why is None)
    o = jnp.moveaxis(o, (0, 2), (1, 3))                  # [B, N, C, H, d_v]
    return o.reshape(batch, -1, heads, d_v)[:, :seq].astype(v.dtype)


def gated_delta_states(q, k, v, g, beta):
    """The state each chunk started from, ``[N, B, H, d_v, d_k]`` in the
    dtype of q: for counters and tests, no gradient of its own."""
    return _rule_walk(*_chunks(q, k, v, g, beta))[1]
