"""Ring attention: sequence/context parallelism over ICI neighbors.

No reference equivalent — Horovod 0.15.1 has no attention or sequence
machinery (SURVEY.md §5.7) — but long-context support is first-class in
this framework.  Design follows the blockwise ring-attention construction
(Liu et al.; "How to Scale Your Model" ch. on context parallelism):

* the sequence axis is sharded over a mesh axis (``seq``);
* each device holds one query block Q_i and starts with its KV block;
* KV blocks rotate around the ring via ``lax.ppermute`` (nearest-neighbor
  ICI transfers that overlap with each block's attention compute);
* softmax is accumulated *online* (running max + normalizer), so the full
  [S, S] score matrix never materializes — memory is O(S_local²) per step;
* causal masking is block-aware: with Q block index i and KV block j,
  j > i contributes nothing (skipped numerically via full masking), j == i
  applies the intra-block triangle, j < i is unmasked.

Gradients flow through ppermute (its transpose is the reverse rotation),
so ``jax.grad`` of a ring-attention loss is itself a ring computation —
no custom VJP needed for correctness.  Use inside ``shard_map`` with the
``seq`` axis bound; wrap with ``make_ring_attention_fn`` to drop into the
model zoo's pluggable ``attention_fn`` seam.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "make_ring_attention_fn", "ulysses_attention"]

_NEG_INF = jnp.finfo(jnp.float32).min


def _block_attend(q, k, v, mask):
    """Scores and weighted values for one (Q block, KV block) pair.

    q: [B, Sq, H, D]; k, v: [B, Sk, Hkv, D] (GQA-aware).
    Returns (scores [B, H, Sq, Sk] fp32, values path deferred to caller).
    """
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, group, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(D, jnp.float32))
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG_INF)
    return scores


def _ring_attention_flash(q, k, v, axis_name, causal):
    """Ring attention with the Pallas flash kernel as the per-hop block
    attention: each hop computes ``(out_t, lse_t)`` via
    ``flash_attention_lse`` and the hops merge by log-sum-exp weights —
    so no [B, H, S_loc, S_loc] fp32 score block ever materializes, per
    hop memory is O(S_loc * D), and AD flows through both kernel outputs
    (the lse cotangent rides the backward kernel's delta sideband).

    Hop visibility under causality is BLOCK-level: hop t carries the KV
    block of shard ``src = (my - t) mod n``; t == 0 is the causal
    diagonal (static flag), src < my is fully visible, src > my is
    killed by setting its lse to -inf (weight 0 in the merge — the
    compute still runs, matching the XLA path's lockstep cost).
    """
    from horovod_tpu.ops.flash_attention import flash_attention_lse

    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    # Online pairwise merge: O(1)-hop accumulators, like the XLA path
    # below — never a [T, ...] stack of hop outputs.
    out_acc = lse_acc = None                        # f32 [B,Sq,H,D]/[B,H,Sq]
    k_blk, v_blk = k, v
    for t in range(axis_size):
        o_t, lse_t = flash_attention_lse(q, k_blk, v_blk,
                                         causal=(causal and t == 0))
        o_t = o_t.astype(jnp.float32)
        if causal and t > 0:
            src = (my_idx - t) % axis_size
            lse_t = jnp.where(src < my_idx, lse_t, -jnp.inf)
        if t == 0:
            # The t=0 hop (the causal diagonal) is never masked, so the
            # accumulators start finite.
            out_acc, lse_acc = o_t, lse_t
        else:
            new_lse = jnp.logaddexp(lse_acc, lse_t)  # -inf hops: no-op
            w_old = jnp.exp(lse_acc - new_lse)
            w_new = jnp.exp(lse_t - new_lse)
            out_acc = (out_acc * jnp.moveaxis(w_old, 1, 2)[..., None]
                       + o_t * jnp.moveaxis(w_new, 1, 2)[..., None])
            lse_acc = new_lse
        if t < axis_size - 1:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
    return out_acc.astype(q.dtype)


def ring_attention(q, k, v, *, axis_name: str = "seq", causal: bool = True):
    """Blockwise attention with KV rotating around the ``axis_name`` ring.

    Shapes (per shard): q [B, S_loc, H, D]; k, v [B, S_loc, Hkv, D] with
    H % Hkv == 0 (GQA).  Sequence order is the natural shard order: shard
    ``i`` holds positions [i*S_loc, (i+1)*S_loc).  Returns [B, S_loc, H, D].

    When the local shard fits the flash kernel (S_loc a block multiple;
    off-tile head dims are padded inside the kernel wrapper), each hop's
    block attention runs the Pallas kernel and hops merge by log-sum-exp
    (see :func:`_ring_attention_flash`); otherwise the XLA
    online-softmax path below runs.
    """
    from horovod_tpu.common import trace_counts
    from horovod_tpu.ops.flash_attention import (_FALLBACK,
                                                 flash_lse_supported)

    if flash_lse_supported(q.shape[1], q.shape[3]) \
            and k.shape[1] == q.shape[1]:
        return _ring_attention_flash(q, k, v, axis_name, causal)
    # The lse-returning kernel owns no sequence-padding shim; count the
    # XLA-path choice so losing the per-hop kernel is visible
    # (ops.flash_attention.fallback_count telemetry) whichever condition
    # failed.
    if not flash_lse_supported(q.shape[1], q.shape[3]):
        why = (f"local shard length {q.shape[1]} is off the lse-kernel "
               f"tiling (needs a multiple of 128)")
    else:
        why = f"KV shard length {k.shape[1]} != Q shard length {q.shape[1]}"
    trace_counts.note(
        _FALLBACK,
        "ring attention hop uses the XLA online-softmax path: " + why,
        warn="flash kernel not used: ")

    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv

    # Online-softmax accumulators (fp32).
    o = jnp.zeros((B, Hkv, group, Sq, D), jnp.float32)
    m = jnp.full((B, Hkv, group, Sq), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, Hkv, group, Sq), jnp.float32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    q_pos = jnp.arange(Sq)
    k_pos = jnp.arange(k.shape[1])

    def step(carry, t):
        o, m, l, k_blk, v_blk = carry
        # KV block t hops ago originated at shard (my_idx - t) mod size.
        src = (my_idx - t) % axis_size
        scores = _block_attend(q, k_blk, v_blk, None)  # [B,Hkv,g,Sq,Sk]
        if causal:
            # Global positions: q at my_idx*Sq + q_pos, k at src*Sk + k_pos.
            qg = my_idx * Sq + q_pos
            kg = src * k.shape[1] + k_pos
            mask = qg[:, None] >= kg[None, :]
            scores = jnp.where(mask[None, None, None], scores, _NEG_INF)
        blk_max = jnp.max(scores, axis=-1)                     # [B,Hkv,g,Sq]
        new_m = jnp.maximum(m, blk_max)
        # Guard fully-masked rows (new_m == -inf): exp(0)=1 would poison l;
        # alpha/beta formulation keeps them at zero contribution.
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        p = jnp.exp(scores - safe_m[..., None])
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
        new_l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype),
                        v_blk).astype(jnp.float32)
        new_o = o * alpha[..., None] + pv
        # Rotate KV to the next shard (overlaps with next block's compute).
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (new_o, new_m, new_l, k_next, v_next), None

    carry = (o, m, l, k, v)
    for t in range(axis_size):
        carry, _ = step(carry, t)
    o, m, l, _, _ = carry
    out = o / jnp.maximum(l[..., None], 1e-30)
    # [B,Hkv,g,Sq,D] -> [B,Sq,H,D]
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)


def make_ring_attention_fn(axis_name: str = "seq"):
    """Adapter matching the model zoo's ``attention_fn(q, k, v)`` seam
    (horovod_tpu.models.llama.causal_attention signature)."""

    def attention_fn(q, k, v, *args, **kwargs):
        return ring_attention(q, k, v, axis_name=axis_name, causal=True)

    return attention_fn


def ulysses_attention(q, k, v, *, axis_name: str = "seq",
                      causal: bool = True):
    """DeepSpeed-Ulysses-style sequence parallelism: all-to-all from
    sequence-sharded to head-sharded, full-sequence attention locally,
    all-to-all back.  One big ICI transfer instead of ring hops — better
    when heads >= ring size and sequence blocks are small.

    Per-shard shapes as in :func:`ring_attention`; requires H (and Hkv)
    divisible by the axis size.
    """
    axis_size = lax.axis_size(axis_name)
    B, Sq, Hq, D = q.shape
    if Hq % axis_size != 0 or k.shape[2] % axis_size != 0:
        raise ValueError(
            f"ulysses requires heads ({Hq}, kv {k.shape[2]}) divisible by "
            f"the {axis_name!r} axis size {axis_size}")
    # [B, S_loc, H, D] -> [B, S_full, H/P, D]
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    # The local attention runs over the FULL sequence — exactly where the
    # Pallas flash kernel earns its keep (the dense path materializes
    # [B, H, S, S] scores).  shard_map bodies are Manual-mesh, so the
    # kernel lowers legally here; off-tile head dims are zero-padded to
    # the kernel inside flash_attention (no dense path).
    from horovod_tpu.ops.flash_attention import flash_attention

    out = flash_attention(qh, kh, vh, causal=causal)
    # [B, S_full, H/P, D] -> [B, S_loc, H, D]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)
