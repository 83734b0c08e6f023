"""What the chunked recurrences share: ``ops/gated_delta.py`` (the gated
delta rule) and ``ops/ssd.py`` (Mamba-2's state-space scan) both cut a
sequence into chunks, prepare the chunks a slab at a time and walk the slabs
with the state as the carry.  Reshapes alone: nothing here changes a bit."""

from __future__ import annotations

import math

import jax.numpy as jnp

__all__ = ["SLAB", "chunked", "unchunked", "slabs", "padded"]

SLAB = 8               # chunks prepared together, then walked one by one


def chunked(x, chunk):
    """``[B, S, H, ..] -> [N, B, H, C, ..]``."""
    batch, seq, heads = x.shape[:3]
    x = x.reshape(batch, seq // chunk, chunk, heads, *x.shape[3:])
    return jnp.moveaxis(x, (1, 3), (0, 2))


def unchunked(x):
    """``[N, B, H, C, ..] -> [B, N * C, H, ..]``: ``chunked`` undone."""
    x = jnp.moveaxis(x, (0, 2), (1, 3))
    return x.reshape(x.shape[0], -1, *x.shape[3:])


def padded(x, chunk):
    """``[B, S, ..]`` with zero rows behind it up to whole chunks."""
    pad = -x.shape[1] % chunk
    if not pad:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


def slabs(x, slab=SLAB):
    """``[N, ..] -> [N / n, n, ..]``: the chunks in slabs of n, at most
    ``slab``.  What a slab's preparation makes beside its results (``[.., C,
    C]`` float32 arrays, float32 copies of its operands) is made a slab at a
    time.  Eight chunks of 64: at 30 heads such an array is 4 MB, and on the
    v5e the delta rule at 8192 tokens takes 21.9 ms forward and backward
    where slabs of 32 (16 MB an array, 0.5 GB more of temporaries) take 32.3
    and all 128 chunks together would hold 1.5 GB; 2 to 8 read alike
    (PERF.md, PR 38).  The slab batches chunks and changes no bit."""
    n = math.gcd(x.shape[0], slab)
    return x.reshape(x.shape[0] // n, n, *x.shape[1:])
