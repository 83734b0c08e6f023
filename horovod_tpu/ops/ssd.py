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

``C B^T`` is formed once a GROUP, the masked product once a head.  All of
that but the state's way from chunk to chunk is the same for every chunk
and runs for a slab of chunks at once (``ops/chunking.py``, which the gated
delta rule shares); the chunks' states are carried in float32 by a
``lax.scan`` over the slab's chunks whose step is two elementwise
operations (a chunk's own contribution to the state does not depend on the
state: the recurrence is linear, unlike the delta rule's).  The backward
pass is autodiff of that under a checkpoint a slab: a slab keeps the state
it started from and its operands, and makes the ``[Q, Q]`` arrays again.

Cumulative log-decays, the masks and the state stay in float32 whatever the
inputs' dtype; the products take their inputs in the dtype of u (bf16 on
the training path) and accumulate in float32, and the state is cast to that
dtype where a product reads it, as ``ops/gated_delta.py`` does.  That module
asks for ``highest`` precision where it inverts a triangular system in
float32; there is no such system here and nothing here asks for it.
Everything is ``jax.numpy``: XLA:TPU runs the products on the MXU and the
walk as a ``while``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from horovod_tpu.ops.chunking import chunked, padded, slabs, unchunked

__all__ = ["CHUNK", "ssd_scan", "ssd_states"]

CHUNK = 128


def _dot(spec, x, y):
    """A product on the MXU: inputs as they are, float32 out."""
    return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)


def _slab(state, operands):
    """A slab's chunks from ``state [B, G, R, P, N]`` (float32; R heads a
    group): the state the slab leaves, and ``(y [n, B, G, R, Q, P], the
    state each chunk started from [n, B, G, R, P, N])``.  ``operands``: u
    ``[n, B, G, R, Q, P]``, dt and the log-decay ``[n, B, G, R, Q]``
    (float32), b and c ``[n, B, G, Q, N]``."""
    u, dt, log_decay, b, c = operands
    dtype = u.dtype
    q = u.shape[-2]
    ell = jnp.cumsum(log_decay, axis=-1)
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


def ssd_scan(u, dt, a_log, b, c, d=None, *, chunk: int = CHUNK):
    """``y [B, S, H, P]`` of the recurrence above, in the dtype of u.

    u ``[B, S, H, P]``; ``dt [B, S, H]`` the steps (positive: the caller's
    ``softplus``; taken to float32); ``a_log [H]`` (``A = -exp(a_log)``); b,
    c ``[B, S, G, N]`` with H a multiple of G (head h reads group ``h // (H
    / G)``); ``d [H]`` the skip, or None for a caller that adds ``D u``
    itself.  The state starts at zero and ends with the sequence; a length
    that is no multiple of ``chunk`` is padded with rows that neither write
    nor decay."""
    seq = u.shape[1]
    y = _walk(u, dt, a_log, b, c, chunk)[0][:, :seq]
    if d is not None:
        y = (y.astype(jnp.float32) + d.astype(jnp.float32)[:, None]
             * u.astype(jnp.float32)).astype(u.dtype)
    return y


def ssd_states(u, dt, a_log, b, c, *, chunk: int = CHUNK):
    """The state each chunk started from, ``[N, B, H, P, N_state]``
    float32: for counters and tests, no gradient of its own."""
    return _walk(u, dt, a_log, b, c, chunk)[1]
