"""Flash attention as a Pallas TPU kernel.

No reference equivalent (the reference has no attention at all, SURVEY.md
§5.7); this is the framework's hot-op kernel for transformer training
(/opt/skills/guides/pallas_guide.md is the API playbook).

Design (FlashAttention-2 style):
* forward: one Mosaic call, grid over (batch*heads, query blocks); K/V live
  in VMEM for the whole row of the grid; online softmax (running max +
  normalizer) in fp32 scratch, so the [S, S] score matrix never exists and
  HBM traffic is O(S·D) instead of O(S²);
* backward: ONE Mosaic call for dq, dk and dv, grid over (batch*heads, key
  blocks), a loop over the live query blocks.  A (query block, key block)
  pair recomputes its probabilities from the saved log-sum-exp (the
  standard FLOPs-for-memory trade) and forms scores, p, dp and ds once:
  five ``head_dim``-deep products a pair (q·kᵀ, pᵀ·do, do·vᵀ, dsᵀ·q, ds·k).
  dk and dv of the key block leave with the grid step; dq adds up in an
  fp32 ``[S, D]`` VMEM scratch that stays over the key blocks of a head
  (zeroed at the first, written out at the last), so that grid axis runs
  in order (``arbitrary``) and the head axis is ``parallel``.  Q, dO and dq
  are whole rows in VMEM; the call states its own scoped-VMEM limit from
  its shapes (``_bwd_vmem_limit``);
* fp32 accumulation on the MXU via ``preferred_element_type``; bf16 in /
  bf16 out;
* causal masking is block-aware: block pairs entirely above the diagonal
  are skipped (the loop bound, not a mask), the diagonal block gets the
  intra-block triangle; packed rows skip the pairs before a segment's start
  the same way.  The pair at the diagonal's end of a causal walk (the last
  key block of a query block, the first query block of a key block) is
  straight-line code and the loop holds the others (``_walk``;
  ``pair_counts``: 136 live pairs a head at S = 8192, 16 of them on the
  diagonal; 36 / 8 at 4096; 10 / 4 at 2048);
* the values may have a width of their own (``d_v`` != ``d_qk``: latent
  attention's 192-wide keys and 128-wide values): q, k, dq and dk are
  ``d_qk`` wide, v, o, dO and dv ``d_v`` wide, in the same two calls.

``flash_attention`` is a drop-in for the model zoo's ``attention_fn``
seam ([B, S, H, D] layout, GQA via KV-head repetition).  Shapes off the
kernel's tiling are zero-padded onto it (sequence to the next 128,
head dim to the next 64 with the TRUE head dim's softmax scale riding as
the kernel's fp32 ``sm_scale``) and sliced back, so models keep the kernel
— and its O(S) memory contract — unchanged on any shape;
``interpret=True`` is used automatically off-TPU so tests exercise the
same kernel logic on CPU.  The longest row is bounded by VMEM: the forward
call holds K and V whole and refuses S = 16384 at D = 128 in bf16 under
the compiler's default limit.

On one v5e (197 TFLOP/s bf16), in the benchmark's decoder step (bf16,
16 heads of 128, causal; PERF.md §5 keeps the current figures): the
forward call runs at 59 % of the MXU's peak on its two products a kept pair
at S = 8192 and 46 % at 2048 (57 % and 41 % with the diagonal's pair inside
the loop); the backward call at 71 % and 59 % on its five (70 % and 57 %
so; 52 % and 42 % when two calls executed seven).
"""

from __future__ import annotations

import functools
import math
import threading
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common import scopes as _scopes

__all__ = ["flash_attention", "flash_attention_fn", "flash_attention_lse",
           "flash_attention_selected", "flash_lse_supported",
           "fallback_count"]

# Non-kernel-path observability: a production config losing a Pallas
# kernel should not do so silently.  flash_attention itself pads any
# shape to the kernel, so the counter tracks COMPOSING callers choosing
# a non-kernel implementation (e.g. ring attention's XLA online-softmax
# hop when the strict lse kernel's tiling is off).  Each distinct reason
# warns once per process; the counter counts every fallback TRACE (not
# execution — under jit the choice is made at trace time).  Guarded by a
# lock: jax tracing can run on multiple threads.
_fallbacks: dict = {}
_fallbacks_lock = threading.Lock()


def fallback_count() -> int:
    """Number of times a composing caller chose a non-kernel attention
    path at trace time (``flash_attention`` itself always pads onto the
    kernel; e.g. ring attention's XLA online-softmax hop counts here),
    summed over every reason and call site in this process (the counter
    is process-global, incremented once per traced fallback, not per
    kernel execution)."""
    with _fallbacks_lock:
        return sum(_fallbacks.values())


def _note_fallback(reason: str) -> None:
    with _fallbacks_lock:
        first = reason not in _fallbacks
        _fallbacks[reason] = _fallbacks.get(reason, 0) + 1
    if first:
        warnings.warn("flash kernel not used: " + reason,
                      RuntimeWarning, stacklevel=3)

_NEG_INF = float("-inf")

BLOCK_Q = 512     # upper bounds; shrunk to the largest divisor of S
BLOCK_K = 512


def _pick_block(s: int, cap: int) -> int:
    for b in (cap, 256, 128):
        if b <= cap and s % b == 0:
            return b
    return 0


def pair_counts(s: int, block_q: int, block_k: int, causal: bool = True):
    """``(pairs, diagonal)`` of one head's row of ``s``: the block pairs the
    two walks run, and those of them the diagonal crosses (S = 8192 at
    512-row blocks: 136 / 16; 4096: 36 / 8; 2048: 10 / 4; not causal: every
    pair, none crossed).  With equal blocks the crossed pairs are ``ki ==
    qi``, the ones ``_walk`` takes out of the loop."""
    n_q, n_k = s // block_q, s // block_k
    if not causal:
        return n_q * n_k, 0
    pairs = diagonal = 0
    for qi in range(n_q):
        live = min(-(-(qi + 1) * block_q // block_k), n_k)
        # Wholly under the diagonal: the block's last key is no later than
        # the query block's first row.
        under = min((qi * block_q + 1) // block_k, live)
        pairs += live
        diagonal += live - under
    return pairs, diagonal


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _walk(first, end, body, carry, apart=None):
    """``body`` over the block pairs ``first .. end - 1`` in ascending order.

    ``apart`` names the end of a causal walk that lies on the diagonal
    (``"last"`` key block of a query block, ``"first"`` query block of a key
    block).  That pair is live whatever the segments (a token's segment
    starts at or before it), so it runs as straight-line code after or before
    the loop over the others: same pairs, same order, same sums, the
    parent's bits.  On the v5e the pair costs less beside the loop than as
    one more iteration of it (PERF.md section 6, PR 35)."""
    if apart == "last":
        return body(end - 1, jax.lax.fori_loop(first, end - 1, body, carry))
    if apart == "first":
        return jax.lax.fori_loop(first + 1, end, body, body(first, carry))
    return jax.lax.fori_loop(first, end, body, carry)


def _seg_mask(scores, seg_start, ki, block_k):
    """Mask keys below each query's segment start (packed causal
    attention); shared by the forward and the backward kernel."""
    block_q = scores.shape[0]
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(k_pos >= seg_start[:, None], scores, -1e30)


def _selected(scores, mask):
    """Scores of the keys a selection leaves out go to -1e30.  A query's
    block may hold none of its keys: the running max then stays where it
    was (or at -1e30, and what that block added is wiped by the first block
    that holds one, as for a padded key)."""
    return jnp.where(mask.astype(jnp.int32) != 0, scores, -1e30)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, sm_scale,
                block_k, bias_ref=None, seg_ref=None, mask_ref=None):
    # q_ref: [block_q, D]; k_ref: [S, D]; v_ref: [S, Dv]; o_ref: [block_q, Dv];
    # bias_ref (optional): [8, S] additive key bias (0 valid / -1e30
    # masked), sublane-replicated like lse — key-padding masks for
    # bidirectional (BERT-style) attention.
    # seg_ref (optional, causal only): [8, S] int32 — per-position START of
    # the position's segment; queries only attend keys at positions
    # >= their segment start.  With the causal upper bound this yields
    # block-diagonal attention for PACKED sequences (row i attends
    # [seg_start[i], i]) without a [S, S] mask.
    # mask_ref (optional): [block_q, S] int8, this query block's rows of a
    # per-batch selection (nonzero = the key is in), shared by every head:
    # learned sparse attention, whose mask is data and differs by query.
    qi = pl.program_id(1)
    block_q, d = q_ref.shape
    s = k_ref.shape[0]
    q = q_ref[:]
    seg_start = None
    if seg_ref is not None:
        seg_start = seg_ref[0, pl.dslice(qi * block_q, block_q)]

    m = jnp.full((block_q,), -1e30, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, v_ref.shape[1]), jnp.float32)

    n_kv = s // block_k
    if causal:
        # Query block qi covers rows [qi*bq, (qi+1)*bq); KV blocks fully
        # above the diagonal contribute nothing — bound the loop instead
        # of masking.
        # ceil((qi+1)*bq / bk): every KV block touching or below the
        # diagonal, valid for ANY bq/bk ratio (bq < bk included).
        n_kv_live = jnp.minimum(
            ((qi + 1) * block_q + block_k - 1) // block_k, n_kv)
    else:
        n_kv_live = n_kv
    kv_first = 0
    if seg_start is not None:
        # Packed rows: KV blocks wholly before this query block's earliest
        # segment start are 100% masked — skip them (the lower-bound twin
        # of the causal upper bound), preserving packing's FLOP savings.
        kv_first = jnp.min(seg_start) // block_k

    def body(ki, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.dslice(ki * block_k, block_k), :]
        v_blk = v_ref[pl.dslice(ki * block_k, block_k), :]
        # Native-dtype (bf16) operands feed the MXU directly; fp32
        # accumulation via preferred_element_type; scale after the dot.
        scores = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [bq, bk]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            # Large-negative (not -inf) keeps exp() finite with no NaN
            # guards on the hot path.
            scores = jnp.where(q_pos >= k_pos, scores, -1e30)
        if bias_ref is not None:
            scores = scores + bias_ref[0, pl.dslice(ki * block_k,
                                                    block_k)][None, :]
        if seg_start is not None:
            scores = _seg_mask(scores, seg_start, ki, block_k)
        if mask_ref is not None:
            scores = _selected(scores, mask_ref[:, pl.dslice(ki * block_k,
                                                             block_k)])
        new_m = jnp.maximum(m, jnp.max(scores, axis=1))
        alpha = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[:, None])
        new_l = l * alpha + jnp.sum(p, axis=1)
        new_acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return new_m, new_l, new_acc

    m, l, acc = _walk(kv_first, n_kv_live, body, (m, l, acc),
                      apart="last" if causal else None)
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    # log-sum-exp per row, consumed by the backward kernel.  lse_ref holds
    # the full row (TPU blocks must tile (8, 128)); write this q-block's
    # slice dynamically.
    lse_row = m + jnp.log(jnp.maximum(l, 1e-30))
    # lse lives as [8, S] per head (sublane-replicated) because TPU blocks
    # must tile (8, 128); row 0 is the value.
    lse_ref[:, pl.dslice(qi * block_q, block_q)] = jnp.broadcast_to(
        lse_row[None, :], (8, block_q))


def _bias_spec(bias, bh, s):
    """BlockSpec for the [B, 8, S] per-BATCH key bias: the grid runs over
    B*H, so the index map folds heads away instead of replicating the bias
    per head in HBM."""
    heads = bh // bias.shape[0]
    return pl.BlockSpec((None, 8, s), lambda b, i: (b // heads, 0, 0))


def _extras(bh, s, bias, seg, mask=None, mask_spec=None):
    """(kwarg names, arrays, BlockSpecs) for the optional per-batch
    sidebands: additive key bias and/or per-query segment starts, each
    [B, 8, S], and a selection [B, S, S] int8 whose block the caller
    gives (rows of a query block forward, columns of a key block
    backward)."""
    names, arrays, specs = [], [], []
    if mask is not None:
        names.append("mask_ref")
        arrays.append(mask)
        specs.append(mask_spec)
    if bias is not None:
        names.append("bias_ref")
        arrays.append(bias)
        specs.append(_bias_spec(bias, bh, s))
    if seg is not None:
        names.append("seg_ref")
        arrays.append(seg)
        specs.append(_bias_spec(seg, bh, s))
    return names, arrays, specs


def _with_extras(base_kernel, n_outs, names, **fixed):
    """Wrap a kernel so trailing sideband inputs arrive as keyword refs."""
    if not names:
        return functools.partial(base_kernel, **fixed)

    def kernel(*refs):
        # ref layout: positional inputs, sideband inputs, then outputs.
        n_extra = len(names)
        n_main = len(refs) - n_outs - n_extra
        main_in = refs[:n_main]
        extra = dict(zip(names, refs[n_main:n_main + n_extra]))
        outs = refs[n_main + n_extra:]
        base_kernel(*main_in, *outs, **fixed, **extra)

    return kernel


# What Mosaic gives a call that asks for nothing (v5e; no chip gives less).
_DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024


def _kv_block(group, index):
    """Index map of a key-value block for the grid's (query head, step):
    ``index`` of the head's own block, at the key-value head its ``group``
    of query heads shares (grouped-query attention reads K and V where
    they are; nothing is repeated in HBM)."""
    if group == 1:
        return index
    return lambda b, i: (b // group,) + index(b, i)[1:]


def _fwd_vmem_limit(s, d, d_v, bq, bk, itemsize):
    """``vmem_limit_bytes`` of the forward call that reads a selection:
    K and V whole, the query block's ``[bq, S]`` int8 rows of the mask, q,
    o and lse, each held twice by the pipeline, and the pair's live
    ``[bq, bk]`` float32 arrays; never under the compiler's default."""
    d, d_v = -(-d // 128) * 128, -(-d_v // 128) * 128
    blocks = (s * (d + d_v) * itemsize + bq * s
              + bq * (d + d_v) * itemsize + 8 * s * 4)
    return max(_DEFAULT_SCOPED_VMEM, 2 * blocks + 6 * bq * bk * 4)


def _fwd(q, k, v, causal, sm_scale, bias=None, seg=None, mask=None):
    # q: [BH, S, D]; k: [BHkv, S, D]; v: [BHkv, S, Dv] (BH a multiple of
    # BHkv: query head b reads key-value head b // group); bias/seg
    # (optional): [B, 8, S] sidebands; mask (optional): [B, S, S] int8.
    bh, s, d = q.shape
    dv = v.shape[-1]
    group = bh // k.shape[0]
    bq = _pick_block(s, BLOCK_Q)
    bk = _pick_block(s, BLOCK_K)
    grid = (bh, s // bq)
    mask_spec = None
    params = {}
    if mask is not None:
        heads = bh // mask.shape[0]
        mask_spec = pl.BlockSpec((None, bq, s),
                                 lambda b, i: (b // heads, i, 0))
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_fwd_vmem_limit(s, d, dv, bq, bk,
                                             q.dtype.itemsize))
    names, arrays, bias_specs = _extras(bh, s, bias, seg, mask, mask_spec)
    kernel = _with_extras(_fwd_kernel, 2, names, causal=causal,
                          sm_scale=sm_scale, block_k=bk)
    inputs = (q, k, v, *arrays)
    whole_row = _kv_block(group, lambda b, i: (b, 0, 0))
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, s, d), whole_row),
            pl.BlockSpec((None, s, dv), whole_row),
        ] + bias_specs,
        out_specs=[
            pl.BlockSpec((None, bq, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 8, s), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, s), jnp.float32),
        ],
        interpret=_interpret(),
        **params,
    )
    with jax.named_scope(_scopes.FLASH_FWD):
        out, lse = call(*inputs)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernel
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, *, causal, sm_scale,
                block_q, bias_ref=None, seg_ref=None, mask_ref=None):
    # Grid (head, key block).  q_ref: [S, D] and do_ref: [S, Dv], the head's
    # whole row; k_ref, dk_ref: [block_k, D] and v_ref, dv_ref: [block_k, Dv],
    # this step's key block;
    # dq_ref: [S, D], the same block at every key block of a head, so it
    # stays in VMEM until the head changes; dq_acc: [S, D] fp32 scratch.
    # One walk over the live (query block, key block) pairs: each forms
    # scores, p, dp and ds once and feeds all three gradients.
    # mask_ref (optional): [S, block_k] int8, this key block's columns of
    # the selection the forward call read by rows.
    ki = pl.program_id(1)
    block_k, d = k_ref.shape
    s = q_ref.shape[0]
    k_blk = k_ref[:]
    v_blk = v_ref[:]

    n_q = s // block_q

    def each_query_block(fn):
        # Block by block, so that no whole-row [S, D] value has to exist
        # beside the scratch.
        def body(qi, _):
            fn(pl.dslice(qi * block_q, block_q))
        jax.lax.fori_loop(0, n_q, body, None)

    @pl.when(ki == 0)
    def _():
        # Every block, not the live ones: a query block that some key
        # block skips (causal, packed) still starts from zero.
        def zero(rows):
            dq_acc[rows, :] = jnp.zeros((block_q, d), jnp.float32)
        each_query_block(zero)

    if causal:
        # Query blocks touching or below the KV block's diagonal start.
        first_q = (ki * block_k) // block_q
    else:
        first_q = 0
    n_q_live = n_q
    if seg_ref is not None:
        # Packed rows: segment starts are NONDECREASING, so queries that
        # can see this KV block (seg_start <= kv block end) are a prefix
        # of rows — bound the loop instead of iterating fully-masked
        # blocks (the twin of the forward call's kv_first skip).
        kv_end = (ki + 1) * block_k - 1
        valid_rows = jnp.sum(
            (seg_ref[0, :] <= kv_end).astype(jnp.int32))
        n_q_live = jnp.minimum(n_q, (valid_rows + block_q - 1) // block_q)

    def body(qi, carry):
        dk, dv = carry
        rows = pl.dslice(qi * block_q, block_q)
        q_blk = q_ref[rows, :]
        do_blk = do_ref[rows, :]
        lse_blk = lse_ref[0, rows]
        delta_blk = delta_ref[0, rows]
        scores = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [bq, bk]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            scores = jnp.where(q_pos >= k_pos, scores, -1e30)
        if bias_ref is not None:
            # The grid owns a fixed key block: bias slice at this step's
            # own block index.
            scores = scores + bias_ref[0, pl.dslice(ki * block_k,
                                                    block_k)][None, :]
        if seg_ref is not None:
            scores = _seg_mask(scores, seg_ref[0, rows], ki, block_k)
        if mask_ref is not None:
            scores = _selected(scores, mask_ref[rows, :])
        p = jnp.exp(scores - lse_blk[:, None])
        dv = dv + jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_blk[:, None]) * sm_scale).astype(q_blk.dtype)
        dk = dk + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # Key blocks arrive in ascending order, so a query block's dq adds
        # up in the order a loop over its key blocks would.
        dq_acc[rows, :] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = _walk(
        first_q, n_q_live, body,
        (jnp.zeros((block_k, d), jnp.float32),
         jnp.zeros((block_k, v_ref.shape[1]), jnp.float32)),
        apart="first" if causal else None)
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _():
        def write(rows):
            dq_ref[rows, :] = dq_acc[rows, :].astype(dq_ref.dtype)
        each_query_block(write)


def _bwd_vmem_limit(s, d, bq, bk, itemsize, n_sidebands, d_v=None,
                    masked=False):
    """``vmem_limit_bytes`` of the backward call, from the shapes it sees:
    twice the bytes of its blocks, its scratch and the pair's live fp32
    ``[bq, bk]`` arrays (scores, p, dp, ds), and never under the compiler's
    default.  Twice, because the pipeline may hold every block two times
    (it fetches the next head's while this one is at work), and because
    inside a train step XLA puts operands of its own choosing (lse and
    delta whole, at 8k) into the call's scope: compiled alone the call
    took 12 MiB at ``[S, D]`` = [8192, 128] in bf16, inside the decoder's
    step 19.8, of the 30 asked for here; the default holds neither.
    ``d`` is the width of q, k, dq and dk, ``d_v`` that of v, dO and dv
    (``d`` where it is not given)."""
    d = -(-d // 128) * 128                   # VMEM pads the lanes
    d_v = d if d_v is None else -(-d_v // 128) * 128
    rows = s * (2 * d + d_v) * itemsize      # q, do in and dq out: whole rows
    key_blocks = 2 * bk * (d + d_v) * itemsize   # k, v in; dk, dv out
    stats = (2 + n_sidebands) * 8 * s * 4    # lse, delta, bias / seg
    if masked:
        stats += s * bk                      # the key block's int8 columns
    dq_acc = s * d * 4
    live = 4 * bq * bk * 4
    return max(_DEFAULT_SCOPED_VMEM,
               2 * (rows + key_blocks + stats + dq_acc + live))


def _bwd_impl(causal, sm_scale, res, do, bias=None, seg=None, g_lse=None,
              mask=None):
    q, k, v, out, lse = res
    bh, s, d = q.shape
    group = bh // k.shape[0]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # [BH, S]
    if g_lse is not None:
        # lse cotangent folds into delta: dL/ds_ij = p_ij * (dp_ij -
        # delta_i + g_lse_i), so delta_eff = delta - g_lse feeds the
        # UNCHANGED backward kernel (dv = p^T do has no lse term).
        delta = delta - g_lse.astype(jnp.float32)
    # Same sublane-replicated [BH, 8, S] layout as lse (TPU block tiling).
    delta = jnp.broadcast_to(delta[:, None, :], delta.shape[:1] + (8,)
                             + delta.shape[1:])
    bq = _pick_block(s, BLOCK_Q)
    bk = _pick_block(s, BLOCK_K)
    mask_spec = None
    if mask is not None:
        heads = bh // mask.shape[0]
        mask_spec = pl.BlockSpec((None, s, bk),
                                 lambda b, i: (b // heads, 0, i))
    names, bias_inputs, bias_specs = _extras(bh, s, bias, seg, mask,
                                             mask_spec)

    # The scratch ref follows the outputs, so it counts among them here.
    kernel = _with_extras(_bwd_kernel, 4, names, causal=causal,
                          sm_scale=sm_scale, block_q=bq)
    dv = v.shape[-1]

    def row(width):
        return pl.BlockSpec((None, s, width), lambda b, i: (b, 0, 0))

    def key_block(width, group=1):
        return pl.BlockSpec((None, bk, width),
                            _kv_block(group, lambda b, i: (b, i, 0)))

    stat = pl.BlockSpec((None, 8, s), lambda b, i: (b, 0, 0))
    call = pl.pallas_call(
        kernel,
        grid=(bh, s // bk),
        in_specs=[row(d), key_block(d, group), key_block(dv, group),
                  row(dv), stat, stat] + bias_specs,
        out_specs=[row(d), key_block(d), key_block(dv)],
        # dk and dv leave a query head at a time: the heads of a group add
        # theirs up below.
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, dv), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((s, d), jnp.float32)],
        # dq adds up over the key blocks of a head: that axis runs in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_bwd_vmem_limit(
                s, d, bq, bk, q.dtype.itemsize, len(names), d_v=dv,
                masked=mask is not None)),
        interpret=_interpret(),
    )
    with jax.named_scope(_scopes.FLASH_BWD):
        dq, dk, dv = call(q, k, v, do, lse, delta, *bias_inputs)
    if group > 1:
        def over_group(x):
            return jnp.sum(x.reshape(-1, group, *x.shape[1:]),
                           axis=1, dtype=jnp.float32).astype(x.dtype)
        dk, dv = over_group(dk), over_group(dv)
    return dq, dk, dv


def _bwd(causal, sm_scale, res, do):
    return _bwd_impl(causal, sm_scale, res, do)


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, sm_scale):
    out, _ = _fwd(q, k, v, causal, sm_scale)
    return out


def _flash_fwd(q, k, v, causal, sm_scale):
    out, lse = _fwd(q, k, v, causal, sm_scale)
    # Named for recomputation policies (LlamaConfig.remat): a policy that
    # keeps both spares the backward pass this call.  Identity otherwise.
    out = checkpoint_name(out, _scopes.FLASH_OUT_NAME)
    lse = checkpoint_name(lse, _scopes.FLASH_LSE_NAME)
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


def _flat_layout(q, k, v):
    """[B, S, H, D] -> the kernels' flat [B*H, S, D] operands, each with
    its own D and its own H: with fewer key-value heads than query heads
    (GQA) k and v stay ``[B*Hkv, S, D]`` and the calls' index maps send
    query head h to key-value head h // (Hq / Hkv) (shared by the public
    entry points)."""
    B, S = q.shape[:2]

    def t(x):
        return x.transpose(0, 2, 1, 3).reshape(B * x.shape[2], S,
                                               x.shape[-1])

    return t(q), t(k), t(v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_lse(q, k, v, causal, sm_scale):
    """Like ``_flash`` but ALSO returns the per-row log-sum-exp [BH, S]
    as a differentiable output — the merge statistic blockwise consumers
    (ring attention) need to combine partial attentions."""
    out, lse = _fwd(q, k, v, causal, sm_scale)
    return out, lse[:, 0, :]


def _flash_lse_fwd(q, k, v, causal, sm_scale):
    out, lse = _fwd(q, k, v, causal, sm_scale)
    return (out, lse[:, 0, :]), (q, k, v, out, lse)


def _flash_lse_bwd(causal, sm_scale, res, cts):
    do, g_lse = cts
    return _bwd_impl(causal, sm_scale, res, do, g_lse=g_lse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _pad_head_dim(q, k, v):
    """Zero-pad D to the next MXU tile (64).  Zero dims contribute
    nothing to any q·k score, so the padded kernel computes identical
    attention PROVIDED the caller threads the TRUE head dim's softmax
    scale through as the kernel's fp32 ``sm_scale`` (a nondiff Python
    float).  It must NOT be folded into q: pre-multiplying by a
    ``q.dtype``-rounded ``sqrt(Dpad)/sqrt(D)`` constant perturbs every
    score's softmax temperature in bf16 (~0.4% max), smearing padded vs
    dense parity.  Autodiff slices the grads back through the pad
    (grad-of-pad = slice).  v is padded to the next tile of its own
    width.  Returns padded (q, k, v)."""
    def pad(x):
        d = x.shape[-1]
        return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, -d % 64)))

    return pad(q), pad(k), pad(v)


def flash_attention_lse(q, k, v, *, causal: bool = True,
                        _sm_scale: Optional[float] = None):
    """Flash attention returning ``(out [B,S,H,D], lse [B,H,S] fp32)``.

    The lse output makes partial attentions COMPOSABLE: blockwise
    consumers (ring attention over a sequence-sharded mesh) merge per-
    block results as ``out = sum_t exp(lse_t - logsumexp_t(lse)) out_t``
    and AD flows through both outputs (the lse cotangent folds into the
    backward kernel's delta sideband — see ``_bwd_impl``).

    Kernel-only surface: requires S % 128 == 0 (no dense fallback, no
    sequence padding — a blockwise caller owns the sequence layout, so
    callers check ``flash_lse_supported`` and keep their own fallback;
    a silent dense path would defeat the memory contract the caller is
    composing for).  Off-tile head dims ARE handled: D % 64 != 0 is
    zero-padded to the next MXU tile and sliced back (zero dims change
    neither the scores nor the lse; the TRUE head dim's 1/sqrt(D) rides
    through as the kernel's fp32 sm_scale rather than a q.dtype-rounded
    multiplier on q — see ``_pad_head_dim``), so ring attention keeps
    its per-hop kernel for small-head models.
    """
    B, S, Hq, D = q.shape
    Dv = v.shape[-1]
    if not flash_lse_supported(S, D):
        raise ValueError(
            f"flash_attention_lse requires S % 128 == 0, "
            f"got S={S}, D={D}; gate on flash_lse_supported()")
    if D % 64 != 0 or Dv % 64 != 0:
        qp, kp, vp = _pad_head_dim(q, k, v)
        out, lse = flash_attention_lse(
            qp, kp, vp, causal=causal,
            _sm_scale=_sm_scale if _sm_scale is not None
            else 1.0 / math.sqrt(D))
        return out[..., :Dv], lse
    sm_scale = _sm_scale if _sm_scale is not None else 1.0 / math.sqrt(D)
    qt, kt, vt = _flat_layout(q, k, v)
    out, lse = _flash_lse(qt, kt, vt, causal, sm_scale)
    return (out.reshape(B, Hq, S, Dv).transpose(0, 2, 1, 3),
            lse.reshape(B, Hq, S))


def flash_lse_supported(S: int, D: int) -> bool:
    """Shapes the lse-returning kernel path accepts (off-tile D is
    padded internally; S stays strict — the blockwise caller owns the
    sequence layout)."""
    return S % 128 == 0 and _pick_block(S, BLOCK_Q) > 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_biased(q, k, v, bias, causal, sm_scale):
    out, _ = _fwd(q, k, v, causal, sm_scale, bias)
    return out


def _flash_biased_fwd(q, k, v, bias, causal, sm_scale):
    out, lse = _fwd(q, k, v, causal, sm_scale, bias)
    return out, (q, k, v, bias, out, lse)


def _flash_biased_bwd(causal, sm_scale, res, do):
    q, k, v, bias, out, lse = res
    dq, dk, dv = _bwd_impl(causal, sm_scale, (q, k, v, out, lse), do,
                           bias=bias)
    # The bias is a constant mask encoding (0 / -1e30); no useful gradient.
    return dq, dk, dv, jnp.zeros_like(bias)


_flash_biased.defvjp(_flash_biased_fwd, _flash_biased_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_seg(q, k, v, seg, causal, sm_scale):
    out, _ = _fwd(q, k, v, causal, sm_scale, seg=seg)
    return out


def _flash_seg_fwd(q, k, v, seg, causal, sm_scale):
    out, lse = _fwd(q, k, v, causal, sm_scale, seg=seg)
    return out, (q, k, v, seg, out, lse)


def _flash_seg_bwd(causal, sm_scale, res, do):
    import numpy as np

    q, k, v, seg, out, lse = res
    dq, dk, dv = _bwd_impl(causal, sm_scale, (q, k, v, out, lse), do,
                           seg=seg)
    # Integer input: JAX requires a float0 cotangent.
    return dq, dk, dv, np.zeros(seg.shape, dtype=jax.dtypes.float0)


_flash_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_selected(q, k, v, mask, causal, sm_scale):
    """Attention over the keys ``mask [B, S, S]`` (int8, nonzero = in)
    selects for each query, the same for every head: ``(out, lse [BH, S])``.
    The lse is for a consumer that does not differentiate it (the target of
    an indexer's loss): its cotangent is dropped."""
    out, lse = _fwd(q, k, v, causal, sm_scale, mask=mask)
    return out, lse[:, 0, :]


def _flash_selected_fwd(q, k, v, mask, causal, sm_scale):
    out, lse = _fwd(q, k, v, causal, sm_scale, mask=mask)
    out = checkpoint_name(out, _scopes.FLASH_OUT_NAME)
    lse = checkpoint_name(lse, _scopes.FLASH_LSE_NAME)
    return (out, lse[:, 0, :]), (q, k, v, mask, out, lse)


def _flash_selected_bwd(causal, sm_scale, res, cts):
    import numpy as np

    q, k, v, mask, out, lse = res
    dq, dk, dv = _bwd_impl(causal, sm_scale, (q, k, v, out, lse), cts[0],
                           mask=mask)
    return dq, dk, dv, np.zeros(mask.shape, dtype=jax.dtypes.float0)


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)


def flash_attention_selected(q, k, v, selected, *,
                             _sm_scale: Optional[float] = None):
    """Causal attention over a selection of each query's keys (learned
    sparse attention): ``q [B, S, Hq, D]``, ``k``, ``v [B, S, Hkv, D]`` and
    ``selected [B, S, S]`` int8, nonzero where query t takes key s (a
    subset of s <= t that holds at least one key a query), shared by all
    heads.  Returns ``(out [B, S, Hq, Dv], lse [B, Hq, S])``: the
    log-sum-exp of each query's scores over ITS keys, with no gradient (an
    indexer's loss forms its target from it).  The two calls of
    ``flash_attention``, under the same scopes, each reading the selection
    by blocks: no score or probability leaves VMEM.  A block pair above
    the diagonal is skipped; one below it runs whatever it holds.
    S % 128 == 0 and D % 64 == 0: the caller owns the layout."""
    B, S, Hq, D = q.shape
    if S % 128 or not (_supported(S, D) and _supported(S, v.shape[-1])):
        raise ValueError(f"flash_attention_selected needs S % 128 == 0 and "
                         f"head widths in whole tiles of 64; got S={S}, "
                         f"D={D}, Dv={v.shape[-1]}")
    sm_scale = _sm_scale if _sm_scale is not None else 1.0 / math.sqrt(D)
    qt, kt, vt = _flat_layout(q, k, v)
    out, lse = _flash_selected(qt, kt, vt, selected.astype(jnp.int8), True,
                               sm_scale)
    return (out.reshape(B, Hq, S, -1).transpose(0, 2, 1, 3),
            jax.lax.stop_gradient(lse.reshape(B, Hq, S)))


def _segment_starts(segment_ids):
    """[B, S] segment ids (contiguous runs) -> [B, S] int32 index of each
    position's segment start, via a cummax over run boundaries."""
    B, S = segment_ids.shape
    pos = jnp.arange(S, dtype=jnp.int32)
    change = jnp.concatenate(
        [jnp.ones((B, 1), bool),
         segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)
    return jax.lax.cummax(
        jnp.where(change, pos[None, :], 0).astype(jnp.int32), axis=1)


def _supported(S: int, D: int) -> bool:
    # D=64 (BERT-family head dim) runs at reduced lane utilization (Mosaic
    # pads the minor dim) but still beats XLA's dense attention on-chip:
    # measured 1.25x at S=2048 and 1.6x at S=4096 (bf16, masked).
    # S is NOT constrained here: off-tile sequence lengths are padded to
    # the next multiple of 128 in flash_attention (see _pad_to_tile).
    return D % 64 == 0


def _pad_to_tile(q, k, v, causal, key_padding_mask, segment_ids):
    """Zero-pad the sequence dim to the next multiple of 128 and arrange
    masking so padded KEYS are never attended: pure-causal configs exclude
    trailing positions via the causal triangle already; masked configs get
    the pad marked invalid; bare bidirectional configs gain a key-padding
    mask; packed configs put the pad in a fresh trailing segment.  Padded
    QUERY rows produce garbage that the caller slices off, and their
    upstream cotangents are exactly zero (the slice's transpose), so they
    contribute nothing to dQ/dK/dV."""
    B, S = q.shape[:2]
    pad = -S % 128
    zpad = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
    q, k, v = zpad(q), zpad(k), zpad(v)
    if segment_ids is not None:
        segment_ids = jnp.concatenate(
            [segment_ids,
             jnp.broadcast_to(segment_ids[:, -1:] + 1, (B, pad))], axis=1)
    elif key_padding_mask is not None:
        key_padding_mask = zpad(key_padding_mask)  # zero-pad == False
    elif not causal:
        key_padding_mask = jnp.concatenate(
            [jnp.ones((B, S), bool), jnp.zeros((B, pad), bool)], axis=1)
    return q, k, v, key_padding_mask, segment_ids


def flash_attention(q, k, v, *, causal: bool = True,
                    key_padding_mask=None, segment_ids=None,
                    _sm_scale: Optional[float] = None):
    """Flash attention on [B, S, H, D] tensors (the model zoo seam).

    ``key_padding_mask``: optional [B, S] boolean (True = attend to that
    key) — BERT-style padding masks; carried through the kernel as an
    additive key bias in the same sublane-replicated layout as the LSE.
    ``v`` may be ``[B, S, H, Dv]`` with ``Dv != D``: the output then has
    the values' width.
    ``segment_ids``: optional [B, S] integer ids of contiguous packed
    sequences (causal only, exclusive with the padding mask): each query
    attends only within its own segment — block-diagonal causal attention
    for packed pretraining, at O(S) sideband cost instead of an [S, S]
    mask.  GQA (fewer KV heads) is handled by repeating KV heads.

    Off-tile sequence lengths (S not a multiple of 128) are zero-padded to
    the next tile and sliced back, so BERT/packed configs one token off
    the block size keep the kernel.  Head dims off the MXU tiling (D not
    a multiple of 64) are likewise zero-padded to the next multiple of 64
    and sliced back — zero dims contribute nothing to the scores, and the
    TRUE head dim's 1/sqrt(D) is threaded through as the kernel's fp32
    sm_scale (never a q.dtype-rounded multiplier on q, which would shift
    every score's softmax temperature in bf16; see ``_pad_head_dim``) —
    so small-head models keep
    the kernel and its O(S) memory contract instead of materializing the
    [B, H, S, S] dense scores (measured 1.2x faster than the dense path
    at D=32, S=4096 fwd+bwd on v5e, and the only option that does not
    OOM at long S).  ``fallback_count`` still tracks the composing
    callers' own fallbacks (:func:`flash_attention_lse` keeps its strict
    no-shim contract).

    Fully-masked query rows (every key excluded by ``key_padding_mask``)
    produce UNDEFINED outputs — the -1e30 mask bias and the -1e30 running
    max cancel, yielding uniform attention over the masked keys — and, if
    given nonzero upstream cotangents, contribute garbage to dK/dV.  This
    matches the dense fallback's behavior; callers must not consume such
    rows (standard BERT practice masks them out of the loss).
    """
    B, S, Hq, D = q.shape
    Dv = v.shape[-1]        # the values' own width (latent attention)
    if segment_ids is not None:
        if not causal:
            raise NotImplementedError(
                "segment_ids implies packed causal attention; bidirectional"
                " segment masking is not supported")
        if key_padding_mask is not None:
            raise NotImplementedError(
                "segment_ids and key_padding_mask are mutually exclusive "
                "(mark padding as its own trailing segment instead)")
    if not (_supported(S, D) and _supported(S, Dv)):
        qp, kp, vp = _pad_head_dim(q, k, v)  # see _pad_head_dim
        out = flash_attention(
            qp, kp, vp, causal=causal,
            key_padding_mask=key_padding_mask, segment_ids=segment_ids,
            _sm_scale=_sm_scale if _sm_scale is not None
            else 1.0 / math.sqrt(D))
        return out[..., :Dv]
    if S % 128 != 0:
        q, k, v, key_padding_mask, segment_ids = _pad_to_tile(
            q, k, v, causal, key_padding_mask, segment_ids)
        return flash_attention(
            q, k, v, causal=causal, key_padding_mask=key_padding_mask,
            segment_ids=segment_ids, _sm_scale=_sm_scale)[:, :S]
    sm_scale = _sm_scale if _sm_scale is not None else 1.0 / math.sqrt(D)
    qt, kt, vt = _flat_layout(q, k, v)
    if segment_ids is not None:
        starts = _segment_starts(jnp.asarray(segment_ids))
        # [B, S] -> [B, 8, S]: sublane-replicated (TPU tiling); heads are
        # folded away in the kernels' sideband BlockSpec.
        seg = jnp.broadcast_to(starts[:, None, :], (B, 8, S))
        out = _flash_seg(qt, kt, vt, seg, causal, sm_scale)
    elif key_padding_mask is None:
        out = _flash(qt, kt, vt, causal, sm_scale)
    else:
        bias = jnp.where(key_padding_mask, 0.0, -1e30).astype(jnp.float32)
        bias = jnp.broadcast_to(bias[:, None, :], (B, 8, S))
        out = _flash_biased(qt, kt, vt, bias, causal, sm_scale)
    return out.reshape(B, Hq, S, Dv).transpose(0, 2, 1, 3)


def flash_attention_fn(q, k, v, mask=None, *, scale=None, selected=None,
                       **kwargs):
    """Adapter matching the model zoo's pluggable ``attention_fn``.

    ``mask`` follows the zoo's convention (broadcastable [B, 1, 1, S]
    key-padding mask, True = attend; what BertEncoder passes).  With a
    mask the attention is bidirectional-masked (BERT semantics); without
    one it is causal (decoder semantics).  Richer mask structures
    (arbitrary [B, H, S, S]) are not supported by the kernel — use the
    dense path for those.  ``scale`` multiplies the scores in place of
    ``1 / sqrt(D)`` (latent attention's YaRN-corrected scale); ``v`` may
    have a width of its own.  ``selected [B, S, S]`` (the keys each query
    of a causal decoder takes, ``flash_attention_selected``) makes the
    result ``(out, lse)``."""
    if selected is not None:
        return flash_attention_selected(q, k, v, selected, _sm_scale=scale)
    if mask is None:
        return flash_attention(q, k, v, causal=True, _sm_scale=scale)
    mask = jnp.asarray(mask)
    if mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1:
        key_mask = mask[:, 0, 0, :]
    elif mask.ndim == 2:
        key_mask = mask
    else:
        raise NotImplementedError(
            "flash_attention_fn supports key-padding masks ([B, S] or "
            "[B, 1, 1, S]); got shape " + str(mask.shape) + " — use the "
            "dense attention path for richer mask structures"
        )
    return flash_attention(q, k, v, causal=False, _sm_scale=scale,
                           key_padding_mask=key_mask.astype(bool))
