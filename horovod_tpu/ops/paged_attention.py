"""Fused paged-attention decode: attend K/V straight through the block
table — no gather, no contiguous staging.

The serving data path (``horovod_tpu/serve/``) keeps each layer's KV
cache as a pool of fixed-size blocks ``[NB, BS, Hkv, D]`` plus a
per-sequence table of physical block ids.  The oracle decode path
(``models/generation.py::_paged_layer``) gathers every sequence's blocks
back into a contiguous ``[B, MAXB*BS, Hkv, D]`` view before the
attention call — bit-exact against the contiguous cache, but it copies
the whole live cache through HBM on every decode step.  This module is
the vLLM/PagedAttention recipe on that pool: one fused kernel walks the
block table and streams each block through an online softmax, so the
cache is read exactly once and never materialized contiguously.

Decode-step geometry (one query token per sequence): ``q_pos == pos``
and ``k_len == pos + 1`` collapse the oracle's causal+length mask to a
single ``k_pos <= pos`` predicate, which is what both implementations
apply.  Scores and the softmax accumulators are fp32; every row is
computed independently of its batch neighbours, so the output is
deterministic across reruns and invariant to the padded batch width —
the same contract the gather path carries (tests/test_serve.py pins
both).  Unfunded table entries and padded rows point at trash block 0
(a real, finite block), so walking the full table is always safe; fully
masked blocks contribute exactly zero.

Two implementations share that math:

* a Pallas TPU kernel (``grid=(B, MAXB)``) whose pool BlockSpecs index
  through the block table via scalar prefetch
  (``pltpu.PrefetchScalarGridSpec``) — each grid step DMAs exactly one
  physical block into VMEM, the online-softmax state lives in VMEM
  scratch across the table walk;
* a blockwise XLA path (``lax.fori_loop`` over table-column chunks,
  ``HOROVOD_PAGED_ATTN_CHUNK`` columns per online-softmax iteration)
  with the identical masking and fp32 online softmax — the default
  off-TPU, where interpret-mode Pallas inside every jitted decode step
  would dominate the step time.  The chunk default is the whole table
  (one gather + one dense pass: per-block dispatch, not flops, is the
  CPU cost); ``=1`` restores the kernel's exact per-block reduction
  order, which the bitwise-parity suite pins.

``HOROVOD_PAGED_ATTN_IMPL=pallas|xla`` forces one implementation; the
parity suite forces ``pallas`` so CPU CI exercises the actual kernel
logic in interpret mode.  The fused path is numerically equivalent to
the gather oracle, not bitwise: the online softmax re-associates the
reduction over keys.  Observed max |logit| delta on the test corpus is
~1e-6 at fp32 (documented tolerance 1e-4 with argmax stability asserted
on the greedy corpus); ``HOROVOD_SERVE_FUSED_ATTN=0`` keeps the oracle
and is byte-identical to the pre-kernel serve plane.

On the chip (v5e, ``chip_smoke.py``) the compiled kernel sits ~7e-7 from
the XLA path when both run at ``highest`` matmul precision.  At default
precision an fp32 dot on the MXU is one bf16 pass — in the kernel and in
XLA alike — which measured 6.3e-3 from a ``highest`` reference; bf16
pools (what a bf16 model serves) multiply exactly either way.  When the
implementation is ``pallas`` a kernel error is an error: nothing swaps in
the XLA walk behind it.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common import scopes as _scopes

__all__ = ["paged_attention_decode"]

_NEG_INF = -1e30  # matches ops/flash_attention.py (never -inf on TPU)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _impl() -> str:
    forced = os.environ.get("HOROVOD_PAGED_ATTN_IMPL", "").strip().lower()
    if forced in ("pallas", "xla"):
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ---------------------------------------------------------------------------
# Blockwise XLA implementation (off-TPU default; same math as the kernel)
# ---------------------------------------------------------------------------


def _chunk_cols(maxb: int) -> int:
    """Table columns folded into one online-softmax iteration.

    The loop body's per-iteration cost off-TPU is dominated by dispatch
    (a tiny gather + tiny einsums per block), not flops, so the default
    folds the WHOLE table into a single pass — one gather, one dense
    masked softmax, oracle-speed on CPU where this path is only the
    stand-in for the Pallas kernel.  ``HOROVOD_PAGED_ATTN_CHUNK=1``
    restores the per-block walk whose reduction order matches the TPU
    kernel exactly (the bitwise-parity suite pins it).  Read at trace
    time: the engine's per-batch-width jit caches each bake the value
    in effect at first trace.
    """
    raw = os.environ.get("HOROVOD_PAGED_ATTN_CHUNK", "").strip()
    if not raw:
        return maxb
    return max(1, min(int(raw), maxb))


def _decode_blockwise(q, pool_k, pool_v, tables, pos):
    """Online-softmax walk over table-column chunks without contiguous
    staging.

    q: [B, 1, Hq, D]; pool_k/pool_v: [NB, BS, Hkv, D];
    tables: [B, MAXB] int32; pos: [B].  Returns [B, 1, Hq, D].
    """
    B, _, Hq, D = q.shape
    BS, Hkv = pool_k.shape[1], pool_k.shape[2]
    G = Hq // Hkv
    maxb = tables.shape[1]
    C = _chunk_cols(maxb)
    nchunks = -(-maxb // C)
    if nchunks * C != maxb:
        # Pad ragged tails with trash block 0: real memory, and every
        # padded column's k_pos >= MAXB*BS > pos, so the mask kills it.
        tables = jnp.concatenate(
            [tables, jnp.zeros((B, nchunks * C - maxb), tables.dtype)],
            axis=1)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    qg = q.reshape(B, Hkv, G, D)

    def body(j, carry):
        m, l, acc = carry
        bids = jax.lax.dynamic_slice_in_dim(tables, j * C, C, axis=1)
        kb = pool_k[bids].reshape(B, C * BS, Hkv, D)
        vb = pool_v[bids].reshape(B, C * BS, Hkv, D)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        k_pos = j * (C * BS) + jnp.arange(C * BS)
        live = k_pos[None, :] <= pos[:, None]       # [B, C*BS]
        s = jnp.where(live[:, None, None, :], s, _NEG_INF)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgk,bkhd->bhgd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return new_m, l, acc

    m0 = jnp.full((B, Hkv, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, D), jnp.float32)
    # Block 0 always holds the row's position-0 slot, so l > 0 for every
    # row (padded rows attend one trash slot; their output is discarded).
    m, l, acc = jax.lax.fori_loop(0, nchunks, body, (m0, l0, a0))
    out = (acc / l[..., None]).astype(q.dtype)
    return out.reshape(B, 1, Hq, D)


# ---------------------------------------------------------------------------
# Pallas TPU kernel: the block table rides scalar prefetch, so each grid
# step's BlockSpec index map picks the PHYSICAL block to DMA — the fused
# "no gather" read path.
# ---------------------------------------------------------------------------


def _decode_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block_size):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nblk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p0 = pos_ref[b]

    # Blocks wholly beyond the row's live length are fully masked —
    # skip their flops (their DMA already happened; the table points
    # unfunded entries at trash block 0, a real block, so it is safe).
    @pl.when(j * block_size <= p0)
    def _accumulate():
        Hq, D = q_ref.shape
        BS, Hkv, _ = k_ref.shape
        G = Hq // Hkv
        qg = q_ref[...].reshape(Hkv, G, D)
        k = k_ref[...]                              # [BS, Hkv, D]
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)     # [Hkv, G, BS]
        s = s * (1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32)))
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (Hkv, G, BS), 2) \
            + j * block_size
        s = jnp.where(k_pos <= p0, s, _NEG_INF)
        m = m_ref[...]
        new_m = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m[..., None])
        m_ref[...] = new_m
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...],
            (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)     # [Hkv, G, D]

    @pl.when(j == nblk - 1)
    def _finish():
        out = acc_ref[...] / l_ref[...][..., None]
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def _decode_pallas(q, pool_k, pool_v, tables, pos):
    B, _, Hq, D = q.shape
    BS, Hkv = pool_k.shape[1], pool_k.shape[2]
    G = Hq // Hkv
    maxb = tables.shape[1]
    kernel = functools.partial(_decode_kernel, block_size=BS)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, maxb),
        in_specs=[
            pl.BlockSpec((None, Hq, D),
                         lambda b, j, tables, pos: (b, 0, 0)),
            pl.BlockSpec((None, BS, Hkv, D),
                         lambda b, j, tables, pos: (tables[b, j], 0, 0, 0)),
            pl.BlockSpec((None, BS, Hkv, D),
                         lambda b, j, tables, pos: (tables[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, Hq, D),
                               lambda b, j, tables, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=_interpret(),
    )
    with _scopes.span(_scopes.MOSAIC_PAGED_ATTENTION):
        out = call(tables.astype(jnp.int32), pos.astype(jnp.int32),
                   q.reshape(B, Hq, D), pool_k, pool_v)
    return out.reshape(B, 1, Hq, D)


def paged_attention_decode(q, pool_k, pool_v, tables, pos):
    """Fused paged-attention for one decode step.

    q: [B, 1, Hq, D] query (this step's token, post-RoPE); pool_k/pool_v:
    one layer's pool [NB, BS, Hkv, D] with the step's K/V already written
    at each row's ``pos`` slot; tables: [B, MAXB] int32 physical block
    ids; pos: [B] global position per row.  Returns [B, 1, Hq, D] in
    ``q.dtype`` — the drop-in replacement for the gather +
    ``_attend_b(..., q_pos=pos, k_len=pos+1)`` pair in
    ``models/generation.py::_paged_layer``.
    """
    if _impl() == "pallas":
        return _decode_pallas(q, pool_k, pool_v, tables, pos)
    return _decode_blockwise(q, pool_k, pool_v, tables, pos)
