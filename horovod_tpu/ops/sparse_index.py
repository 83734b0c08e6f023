"""The indexer of learned sparse attention (DeepSeek-V3.2-Exp's lightning
indexer, "DSA"): which keys each query takes, and the loss that teaches it.

With n index heads of width d, one index key a token and x a token's
normed state (no gradient flows back into it)::

    q_j = W_q x  [n, d]      k = W_k x  [d]      w = W_w x  [n]
    I[t, s] = sum_j w[t, j] relu(q_j[t] . k[s])            s <= t
    S_t = the ``topk`` keys s <= t of largest I[t, s]; all of them while
          t < topk; among equal scores the lower position first (what
          ``jax.lax.top_k`` does), -0.0 counted as 0.0
    L = mean_t KL(pbar[t, .] || softmax_{s in S_t}(scale I[t, s]))

pbar[t, s] is the mean over the attention heads of the probability each
gives key s among S_t: a target, with no gradient.  d L / d I[t, s] =
scale (softmax - pbar)[t, s] / T on S_t, since both sum to one there.

Two Mosaic calls, neither under a scope of the flash kernel's:

``select_keys`` (``hvd.sparse.select``) forms a block of queries' scores
against every key at or before them in VMEM, one ``[block_q, S]`` row
block, and finds each query's ``topk``-th largest EXACTLY by bisection over
the scores' ordered bit patterns (a float32's bits, with the negative ones
reversed, order as the floats do): 32 passes that compare and count, one
bit of the threshold each.  Where more scores equal the threshold than
there is room for, a second bisection over the position finds the last of
them that is in.  It writes the selection ``[B, S, S]`` as int8 (1 = in),
the log-sum-exp of ``scale I`` over S_t and the number of keys taken; the
scores themselves never reach HBM.  ``jax.lax.top_k`` with k = 2048 of 8192
is a sort.

``index_loss`` (``hvd.sparse.index``) walks the (query block, key block)
pairs at or below the diagonal once: it forms the pair's index scores
again, the attention heads' probabilities from q, k and the flash call's
log-sum-exp (its second q k^T product), their mean, the pair's part of the
KL, and from ``softmax - pbar`` the gradients of q_j, k and w, while the
tile is in VMEM.  So the loss's backward pass is three scalings
(``custom_vjp``), and nothing ``[S, S]`` in float32 is written.

Off the kernels' tiling (S % 128 != 0: a model initialised on a few
positions) and as the tests' oracle, ``dense=True`` computes both in plain
``jax.numpy`` with whole ``[B, n, S, S]`` arrays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.ops import flash_attention as _flash

__all__ = ["select_keys", "index_loss", "index_scores"]

_INT_MIN = np.iinfo(np.int32).min
SELECT_BLOCK_Q = 256
LOSS_BLOCK_Q = 256
BLOCK_K = 512


def _ordered(x):
    """float32 -> int32 that orders as the floats do (and back: the map is
    its own inverse on the bits)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _unordered(key):
    return jax.lax.bitcast_convert_type(
        key ^ ((key >> 31) & 0x7FFFFFFF), jnp.float32)


def _tile_scores(qi_ref, k_blk, w):
    """``I`` of one tile: ``qi_ref [n, bq, d]``, ``k_blk [bk, d]``,
    ``w [bq, n]`` float32 -> ``[bq, bk]`` float32."""
    acc = None
    for j in range(qi_ref.shape[0]):
        a = jax.lax.dot_general(qi_ref[j], k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        term = w[:, j:j + 1] * jnp.maximum(a, 0.0)
        acc = term if acc is None else acc + term
    return acc


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------

def _select_kernel(qi_ref, ki_ref, w_ref, mask_ref, stats_ref, keys, *,
                   topk, scale, block_k):
    # Grid (batch, query block).  qi_ref: [n, bq, d]; ki_ref: [S, d], the
    # sequence's keys whole; w_ref: [bq, n]; mask_ref: [bq, S] int8;
    # stats_ref: [8, bq] (row 0 the log-sum-exp of scale * I over the keys
    # taken, row 1 their number); keys: [bq, S] int32 scratch, the block's
    # scores as ordered bit patterns.
    qi = pl.program_id(1)
    bq, s = mask_ref.shape
    n_k = s // block_k
    n_live = jnp.minimum(((qi + 1) * bq + block_k - 1) // block_k, n_k)
    w = w_ref[...]
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def k_pos(kb):
        return kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)

    def fill(kb, _):
        cols = pl.dslice(kb * block_k, block_k)
        scores = _tile_scores(qi_ref, ki_ref[cols, :], w)
        scores = jnp.where(scores == 0.0, 0.0, scores)        # -0.0 is 0.0
        keys[:, cols] = jnp.where(q_pos >= k_pos(kb), _ordered(scores),
                                  _INT_MIN)
        return 0

    jax.lax.fori_loop(0, n_live, fill, 0)

    def count(pred):
        """Keys of each row, among the live blocks, that ``pred(block's
        patterns, block's positions)`` holds for: ``[bq, 1]`` int32."""
        def body(kb, total):
            blk = keys[:, pl.dslice(kb * block_k, block_k)]
            return total + jnp.sum(pred(blk, k_pos(kb)).astype(jnp.int32),
                                   axis=1, keepdims=True)
        return jax.lax.fori_loop(0, n_live, body,
                                 jnp.zeros((bq, 1), jnp.int32))

    # A row takes min(t + 1, topk) keys: the threshold is the largest
    # pattern that at least so many reach, found from the sign bit down.
    wanted = jnp.minimum(q_pos[:, :1] + 1, topk)

    def reach(candidate):
        return count(lambda blk, _: blk >= candidate) >= wanted

    zero = jnp.zeros((bq, 1), jnp.int32)
    threshold = jnp.where(reach(zero), zero, _INT_MIN)

    def one_bit(i, threshold):
        candidate = threshold | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(reach(candidate), candidate, threshold)

    threshold = jax.lax.fori_loop(0, 31, one_bit, threshold)
    above = count(lambda blk, _: blk > threshold)
    room = wanted - above                   # for the scores AT the threshold
    at = count(lambda blk, _: blk == threshold)

    def last_tie(_):
        # The largest position before which fewer than ``room`` keys sit at
        # the threshold: the position of the last one that is in.
        def one_bit(i, limit):
            candidate = limit | jnp.left_shift(
                jnp.int32(1), (s - 1).bit_length() - 1 - i)
            fewer = count(lambda blk, pos: (blk == threshold)
                          & (pos < candidate)) < room
            return jnp.where(fewer, candidate, limit)
        return jax.lax.fori_loop(0, (s - 1).bit_length(), one_bit, zero)

    limit = jax.lax.cond(jnp.max(at - room) > 0, last_tie,
                         lambda _: jnp.full((bq, 1), s, jnp.int32), 0)

    def write(kb, carry):
        m, l, taken = carry
        cols = pl.dslice(kb * block_k, block_k)
        blk = keys[:, cols]
        pos = k_pos(kb)
        keep = (q_pos >= pos) & ((blk > threshold) | (
            (blk == threshold) & (pos <= limit)))
        mask_ref[:, cols] = keep.astype(jnp.int32).astype(jnp.int8)
        scaled = jnp.where(keep, _unordered(blk) * scale, -1e30)
        new_m = jnp.maximum(m, jnp.max(scaled, axis=1, keepdims=True))
        l = l * jnp.exp(m - new_m) + jnp.sum(
            jnp.where(keep, jnp.exp(scaled - new_m), 0.0), axis=1,
            keepdims=True)
        return new_m, l, taken + jnp.sum(keep.astype(jnp.int32), axis=1,
                                         keepdims=True)

    m, l, taken = jax.lax.fori_loop(
        0, n_live, write, (jnp.full((bq, 1), -1e30, jnp.float32),
                           jnp.zeros((bq, 1), jnp.float32), zero))

    def blank(kb, _):
        mask_ref[:, pl.dslice(kb * block_k, block_k)] = jnp.zeros(
            (bq, block_k), jnp.int8)
        return 0

    jax.lax.fori_loop(n_live, n_k, blank, 0)
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[:, 0]
    row = jax.lax.broadcasted_iota(jnp.int32, (8, bq), 0)
    stats_ref[...] = jnp.where(
        row == 0, lse[None, :],
        jnp.where(row == 1, taken[:, 0].astype(jnp.float32)[None, :], 0.0))


def _select_vmem_limit(s, bq, bk, n, d):
    lanes = -(-d // 128) * 128
    blocks = n * bq * lanes * 2 + s * lanes * 2 + bq * 128 * 4 + bq * s
    return max(_flash._DEFAULT_SCOPED_VMEM,
               2 * blocks + bq * s * 4 + 8 * bq * bk * 4)


def _select_call(q_i, k_i, w, topk, scale):
    # q_i: [B, n, S, d]; k_i: [B, S, d]; w: [B, S, n] float32.
    b, n, s, d = q_i.shape
    bq = _flash._pick_block(s, SELECT_BLOCK_Q)
    bk = _flash._pick_block(s, BLOCK_K)
    call = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, scale=scale,
                          block_k=bk),
        grid=(b, s // bq),
        in_specs=[
            pl.BlockSpec((None, n, bq, d), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((None, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, bq, n), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, s), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 8, bq), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, s), jnp.int8),
            jax.ShapeDtypeStruct((b, 8, s), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_select_vmem_limit(s, bq, bk, n, d)),
        interpret=_flash._interpret(),
    )
    with (_scopes.scope(_scopes.SPARSE_SELECT),
          _scopes.span(_scopes.MOSAIC_SPARSE_SELECT)):
        mask, stats = call(q_i, k_i, w)
    return mask, stats[:, 0, :], stats[:, 1, :].astype(jnp.int32)


def index_scores(q_i, k_i, w):
    """``I [B, S, S]`` in float32, whole (the dense path and the tests):
    ``q_i [B, S, n, d]``, ``k_i [B, S, d]``, ``w [B, S, n]``."""
    a = jnp.einsum("bqnd,bkd->bnqk", q_i, k_i,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bqn,bnqk->bqk", w.astype(jnp.float32),
                      jnp.maximum(a, 0.0))


def _select_dense(q_i, k_i, w, topk, scale):
    s = q_i.shape[1]
    scores = index_scores(q_i, k_i, w)
    scores = jnp.where(scores == 0.0, 0.0, scores)
    causal = jnp.tril(jnp.ones((s, s), bool))
    _, chosen = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                              min(topk, s))
    keep = jnp.any(chosen[..., None] == jnp.arange(s), axis=-2) & causal
    lse = jax.nn.logsumexp(jnp.where(keep, scores * scale, -jnp.inf),
                           axis=-1)
    return (keep.astype(jnp.int8), lse,
            jnp.sum(keep, axis=-1, dtype=jnp.int32))


def select_keys(q_i, k_i, w, topk: int, *, scale: float, dense=None):
    """Which keys each query takes: ``(selected [B, S, S] int8, lse [B, S]
    float32, taken [B, S] int32)`` from the indexer's ``q_i [B, S, n, d]``,
    ``k_i [B, S, d]`` and ``w [B, S, n]``.  ``selected[b, t, s]`` is 1 for
    the ``min(t + 1, topk)`` keys s <= t of largest index score, ``lse``
    the log-sum-exp of ``scale I`` over them, ``taken`` their number.
    Nothing is differentiated: a selection has no gradient.  ``dense``
    (default: where S is off the kernel's tiling) computes the same with
    whole arrays and ``jax.lax.top_k``."""
    q_i, k_i, w = map(jax.lax.stop_gradient, (q_i, k_i, w))
    if dense is None:
        dense = q_i.shape[1] % 128 != 0
    if dense:
        with _scopes.scope(_scopes.SPARSE_SELECT):
            out = _select_dense(q_i, k_i, w, topk, scale)
    else:
        out = _select_call(q_i.transpose(0, 2, 1, 3), k_i,
                           w.astype(jnp.float32), topk, scale)
    return tuple(checkpoint_name(x, _scopes.SPARSE_SELECTED_NAME)
                 for x in out)


# ---------------------------------------------------------------------------
# The indexer's loss, and its gradients in the same walk
# ---------------------------------------------------------------------------

def _loss_kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, mask_ref,
                 lsei_ref, kl_ref, dqi_ref, dw_ref, dki_ref, dqi_acc, *,
                 sm_scale, scale, group):
    # Grid (batch, query block, key block), the last in order.  q_ref:
    # [H, bq, D]; k_ref: [Hkv, bk, D]; lse_ref: [bq, H]; qi_ref: [n, bq, d];
    # ki_ref: [bk, d]; w_ref: [bq, n]; mask_ref: [bq, bk] int8; lsei_ref:
    # [bq, 1].  Out: kl_ref [bq, 1] and dw_ref [bq, n] add up over the key
    # blocks, dqi_ref [n, bq, d] leaves with the last from dqi_acc (f32);
    # dki_ref [bk, d] is this PAIR's part of dk (the caller adds the query
    # blocks up).
    qb, kb = pl.program_id(1), pl.program_id(2)
    heads, bq, _ = q_ref.shape
    bk = ki_ref.shape[0]
    n = qi_ref.shape[0]

    @pl.when(kb == 0)
    def _():
        kl_ref[...] = jnp.zeros_like(kl_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)
        dqi_acc[...] = jnp.zeros_like(dqi_acc)

    live = kb * bk < (qb + 1) * bq

    @pl.when(jnp.logical_not(live))
    def _():
        dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when(live)
    def _():
        keep = mask_ref[...].astype(jnp.int32) != 0
        k_i = ki_ref[...]
        w = w_ref[...]
        log_pi = _tile_scores(qi_ref, k_i, w) * scale - lsei_ref[...]
        pi = jnp.where(keep, jnp.exp(jnp.where(keep, log_pi, 0.0)), 0.0)
        lse = lse_ref[...]
        pbar = jnp.zeros((bq, bk), jnp.float32)
        for h in range(heads):
            scores = jax.lax.dot_general(
                q_ref[h], k_ref[h // group], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            pbar = pbar + jnp.exp(jnp.where(keep, scores - lse[:, h:h + 1],
                                            -1e30))
        pbar = pbar * (1.0 / heads)
        there = keep & (pbar > 0.0)
        kl_ref[...] += jnp.sum(
            jnp.where(there, pbar * (jnp.log(jnp.where(there, pbar, 1.0))
                                     - log_pi), 0.0), axis=1, keepdims=True)
        g = (pi - pbar) * scale                            # d KL_t / d I
        lane = jax.lax.broadcasted_iota(jnp.int32, (bq, n), 1)
        dw = jnp.zeros((bq, n), jnp.float32)
        dk = jnp.zeros(ki_ref.shape, jnp.float32)
        for j in range(n):
            q_j = qi_ref[j]
            a = jax.lax.dot_general(q_j, k_i, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            dw = dw + jnp.where(
                lane == j, jnp.sum(g * jnp.maximum(a, 0.0), axis=1,
                                   keepdims=True), 0.0)
            da = jnp.where(a > 0.0, g * w[:, j:j + 1], 0.0).astype(k_i.dtype)
            dqi_acc[j] += jax.lax.dot_general(
                da, k_i, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk = dk + jax.lax.dot_general(
                da, q_j, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dw_ref[...] += dw
        dki_ref[...] = dk

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        dqi_ref[...] = dqi_acc[...].astype(dqi_ref.dtype)


def _loss_vmem_limit(heads, kv_heads, d_head, n, d, bq, bk):
    lanes = -(-d // 128) * 128
    blocks = ((heads * bq + kv_heads * bk) * d_head * 2 + bq * 128 * 4 * 4
              + 2 * n * bq * lanes * 2 + bk * lanes * (2 + 4) + bq * bk)
    # XLA:TPU puts small whole operands of its choosing into the call's own
    # scope (the per-query columns, padded to 128 lanes: 8 MiB each at 2 x
    # 8192); twice the sum leaves room for them.
    return max(_flash._DEFAULT_SCOPED_VMEM,
               2 * (2 * blocks + n * bq * lanes * 4 + 12 * bq * bk * 4))


def _loss_call(q, k, lse, q_i, k_i, w, mask, lse_i, sm_scale, scale):
    # q: [B, H, S, D]; k: [B, Hkv, S, D]; lse: [B, S, H]; q_i: [B, n, S, d];
    # k_i: [B, S, d]; w: [B, S, n]; mask: [B, S, S] int8; lse_i: [B, S, 1].
    b, heads, s, d_head = q.shape
    kv_heads = k.shape[1]
    n, d = q_i.shape[1], q_i.shape[3]
    bq = _flash._pick_block(s, LOSS_BLOCK_Q)
    bk = _flash._pick_block(s, BLOCK_K)
    n_q = s // bq

    def last_live(i):
        return ((i + 1) * bq - 1) // bk

    def query_rows(*block):
        return pl.BlockSpec((None, *block), lambda b, i, j: (
            (b,) + (0,) * (len(block) - 2) + (i, 0)))

    # A pair above the diagonal does nothing: its key block is the last
    # live one's again, so nothing is fetched for it.
    def key_rows(*block):
        return pl.BlockSpec((None, *block), lambda b, i, j: (
            (b,) + (0,) * (len(block) - 2)
            + (jnp.minimum(j, last_live(i)), 0)))

    call = pl.pallas_call(
        functools.partial(_loss_kernel, sm_scale=sm_scale, scale=scale,
                          group=heads // kv_heads),
        grid=(b, n_q, s // bk),
        in_specs=[
            query_rows(heads, bq, d_head),
            key_rows(kv_heads, bk, d_head),
            query_rows(bq, heads),
            query_rows(n, bq, d),
            key_rows(bk, d),
            query_rows(bq, n),
            pl.BlockSpec((None, bq, bk), lambda b, i, j: (
                b, i, jnp.minimum(j, last_live(i)))),
            query_rows(bq, 1),
        ],
        out_specs=[
            query_rows(bq, 1),
            query_rows(n, bq, d),
            query_rows(bq, n),
            pl.BlockSpec((None, None, bk, d), lambda b, i, j: (b, i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, 1), jnp.float32),
            jax.ShapeDtypeStruct(q_i.shape, q_i.dtype),
            jax.ShapeDtypeStruct((b, s, n), jnp.float32),
            jax.ShapeDtypeStruct((b, n_q, s, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_loss_vmem_limit(heads, kv_heads, d_head, n, d,
                                              bq, bk)),
        interpret=_flash._interpret(),
    )
    with _scopes.span(_scopes.MOSAIC_INDEX_LOSS):
        kl, dq_i, dw, dk_parts = call(q, k, lse, q_i, k_i, w, mask, lse_i)
    return kl[..., 0], dq_i, dw, jnp.sum(dk_parts, axis=1)


def _loss_dense(q, k, lse, q_i, k_i, w, selected, sm_scale, scale):
    """The same loss with whole arrays, for ``jax.grad`` (which has to see
    the softmax's normaliser as a function of the scores, so it is formed
    here and ``select_keys``'s is not read)."""
    heads, kv_heads = q.shape[2], k.shape[2]
    keep = selected != 0
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    p = jnp.where(keep[:, None], jnp.exp(jnp.where(
        keep[:, None], scores - lse[..., None], -jnp.inf)), 0.0)
    pbar = jax.lax.stop_gradient(jnp.mean(p, axis=1))
    log_pi = jax.nn.log_softmax(jnp.where(
        keep, index_scores(q_i, k_i, w) * scale, -jnp.inf), axis=-1)
    there = keep & (pbar > 0.0)
    kl = jnp.where(there, pbar * (jnp.log(jnp.where(there, pbar, 1.0))
                                  - jnp.where(there, log_pi, 0.0)), 0.0)
    return jnp.mean(jnp.sum(kl, axis=-1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _index_loss(q, k, lse, q_i, k_i, w, mask, lse_i, sm_scale, scale):
    return _index_loss_fwd(q, k, lse, q_i, k_i, w, mask, lse_i, sm_scale,
                           scale)[0]


def _index_loss_fwd(q, k, lse, q_i, k_i, w, mask, lse_i, sm_scale, scale):
    kl, dq_i, dw, dk_i = _loss_call(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        lse.transpose(0, 2, 1), q_i.transpose(0, 2, 1, 3), k_i,
        w.astype(jnp.float32), mask, lse_i[..., None], sm_scale, scale)
    # The gradients of the MEAN over all queries, in the shapes and dtypes
    # they leave in; kept by name, so that a backward pass that recomputes
    # the layer does not run the walk again.
    per_query = 1.0 / kl.size
    loss, grads = checkpoint_name(
        (jnp.sum(kl) * per_query,
         ((dq_i.transpose(0, 2, 1, 3).astype(jnp.float32)
           * per_query).astype(q_i.dtype),
          (dk_i * per_query).astype(k_i.dtype),
          (dw * per_query).astype(w.dtype))),
        _scopes.SPARSE_INDEX_LOSS_NAME)
    return loss, grads


def _index_loss_bwd(sm_scale, scale, grads, g):
    def scaled(x):
        return (g * x.astype(jnp.float32)).astype(x.dtype)

    # q, k, lse, the selection and lse_i carry no gradient (None).
    return (None, None, None, *map(scaled, grads), None, None)


_index_loss.defvjp(*_scopes.rules(
    "_index_loss", _index_loss_fwd, _index_loss_bwd))


def index_loss(q, k, lse, q_i, k_i, w, selected, lse_i, *, sm_scale: float,
               scale: float, dense=None):
    """``mean_t KL(pbar[t] || softmax over S_t of scale I[t])``, a scalar
    in float32, differentiable in the indexer's ``q_i [B, S, n, d]``,
    ``k_i [B, S, d]`` and ``w [B, S, n]`` alone.  The target is made from
    the attention's own ``q [B, S, H, D]``, ``k [B, S, Hkv, D]`` (after
    norm and rotation, as the kernel saw them) and the flash call's ``lse
    [B, H, S]`` over ``selected [B, S, S]``; ``lse_i [B, S]`` is
    ``select_keys``'s.  None of those five carries a gradient."""
    q, k, lse, lse_i = map(jax.lax.stop_gradient, (q, k, lse, lse_i))
    if dense is None:
        dense = q.shape[1] % 128 != 0
    with _scopes.scope(_scopes.SPARSE_INDEX):
        if dense:
            return _loss_dense(q, k, lse, q_i, k_i, w, selected, sm_scale,
                               scale)
        return _index_loss(q, k, lse, q_i, k_i, w, selected, lse_i,
                           sm_scale, scale)
