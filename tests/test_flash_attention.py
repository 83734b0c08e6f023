"""Pallas flash attention vs dense reference (interpret mode on CPU, the
real kernel on TPU): every variant's values and gradients, and the one-call
backward pass.  The walks and the layouts are in
``tests/test_flash_walks.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.llama import causal_attention
from horovod_tpu.ops.flash_attention import flash_attention


def _qkv(B=2, S=256, H=4, Hkv=4, D=128, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    return q, k, v


def test_flash_forward_matches_dense():
    q, k, v = _qkv()
    expected = causal_attention(q, k, v)
    got = jax.jit(flash_attention)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_flash_forward_gqa():
    q, k, v = _qkv(H=8, Hkv=2)
    expected = causal_attention(q, k, v)
    got = jax.jit(flash_attention)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_flash_gradients_match_dense():
    q, k, v = _qkv(B=1, S=256, H=2, Hkv=2)

    def dense_loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v) ** 2)

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    dg = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    fg = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(dg, fg, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch")


def test_flash_gqa_gradients_match_dense():
    """GQA grads: the calls read a group's K and V where they are, dk and
    dv leave a query head at a time and the group's are added up in
    float32 -- exercised end-to-end here against the dense reference."""
    q, k, v = _qkv(B=1, S=256, H=8, Hkv=2)

    def dense_loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v) ** 2)

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    dg = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    fg = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(dg, fg, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch (gqa)")


def test_flash_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    expected = causal_attention(q, k, v)
    got = jax.jit(flash_attention)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(expected, np.float32),
        atol=3e-2, rtol=3e-2)


def test_flash_padded_tail_causal():
    """S not a multiple of 128 → zero-padded to the next tile and sliced
    back (the kernel, not the dense fallback)."""
    q, k, v = _qkv(S=100, D=64)
    expected = causal_attention(q, k, v)
    got = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_flash_padded_tail_gradients():
    q, k, v = _qkv(B=1, S=200, H=2, Hkv=2)

    def dense_loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v) ** 2)

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    dg = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    fg = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(dg, fg, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch")


def test_flash_padded_tail_bidirectional_no_mask():
    """Bare bidirectional attention with off-tile S: padded keys must be
    excluded via the synthesized key-padding mask."""
    from horovod_tpu.models.bert import dot_product_attention

    q, k, v = _qkv(S=100, D=64)
    expected = dot_product_attention(q, k, v)
    got = flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_flash_small_head_dim_pads_to_kernel():
    """D off the MXU tiling (32) is zero-padded to 64 and sliced back —
    still the kernel with its O(S) memory contract, NOT the dense
    fallback — with the true 1/sqrt(32) softmax scale threaded through
    as the kernel's fp32 sm_scale, and gradients flowing back through
    the pad."""
    from horovod_tpu.ops import flash_attention as fa

    q, k, v = _qkv(S=128, D=32)
    before = fa.fallback_count()
    expected = causal_attention(q, k, v)
    got = flash_attention(q, k, v)
    assert fa.fallback_count() == before, "dense fallback fired"
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)

    def dense_loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v) ** 2)

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    dg = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    fg = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(dg, fg, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch (padded D)")


def test_flash_small_head_dim_masked_and_gqa():
    """The D-padding shim composes with key-padding masks, GQA, and
    off-tile S (both pads at once)."""
    from horovod_tpu.models.bert import dot_product_attention

    q, k, v = _qkv(S=100, H=8, Hkv=2, D=48)
    mask = np.ones((2, 100), bool)
    mask[:, 77:] = False
    kr = jnp.repeat(k, 4, axis=2)
    vr = jnp.repeat(v, 4, axis=2)
    expected = dot_product_attention(q, kr, vr,
                                     mask=jnp.asarray(mask)[:, None, None, :])
    got = flash_attention(q, k, v, causal=False,
                          key_padding_mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_flash_small_head_dim_bf16_scale_exact():
    """The padded-D softmax scale stays EXACT in bf16: the true
    1/sqrt(D) rides through as the kernel's fp32 sm_scale, never a
    q.dtype-rounded sqrt(Dpad)/sqrt(D) multiplier baked into q (bf16's
    8 mantissa bits round that constant, shifting every score's softmax
    temperature relative to the dense path).  Asserted two ways: the pad
    helper leaves q's values untouched, and the padded bf16 kernel holds
    the SAME parity bound vs dense that the aligned-D bf16 path does —
    plus a tighter bound vs the fp32 padded kernel, where bf16 input
    rounding is the only remaining error source."""
    from horovod_tpu.ops.flash_attention import _pad_head_dim

    q, k, v = _qkv(S=128, D=32, dtype=jnp.bfloat16)
    qp, kp, vp = _pad_head_dim(q, k, v)
    assert qp.shape[-1] == 64
    np.testing.assert_array_equal(np.asarray(qp[..., :32], np.float32),
                                  np.asarray(q, np.float32))
    np.testing.assert_array_equal(np.asarray(qp[..., 32:], np.float32), 0.0)

    expected = causal_attention(q, k, v)
    got = flash_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(expected, np.float32),
        atol=3e-2, rtol=3e-2)  # same bound test_flash_bf16 holds at D=128
    ref32 = flash_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref32), atol=1.5e-2,
        rtol=1.5e-2)


def test_llama_with_flash_attention():
    """Full model with the kernel plugged into the attention seam."""
    import dataclasses

    from horovod_tpu.models import LlamaConfig, LlamaModel
    from horovod_tpu.ops.flash_attention import flash_attention_fn

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32,
                               logits_dtype=jnp.float32,
                              hidden_size=512, num_heads=4, num_kv_heads=4)
    ids = jax.random.randint(jax.random.key(0), (2, 256), 0, cfg.vocab_size)
    dense = LlamaModel(cfg)
    params = dense.init(jax.random.key(1), ids)
    expected = dense.apply(params, ids)
    flash_model = LlamaModel(cfg, attention_fn=flash_attention_fn)
    got = jax.jit(flash_model.apply)(params, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=5e-4, rtol=5e-4)


def test_flash_key_padding_mask_matches_dense():
    """Masked bidirectional (BERT-style) attention: the kernel's additive
    key bias must match the dense path's where-masked softmax, in the
    values AND at padded-query rows' gradients."""
    from horovod_tpu.models.bert import dot_product_attention
    from horovod_tpu.ops.flash_attention import flash_attention_fn

    q, k, v = _qkv(B=2, S=256, H=2, Hkv=2)
    lengths = jnp.array([256, 100])
    mask = (jnp.arange(256)[None, :] < lengths[:, None])  # [B, S] bool

    expected = dot_product_attention(q, k, v, mask=mask[:, None, None, :])
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=False, key_padding_mask=mask))(q, k, v)
    valid = np.asarray(mask)  # compare only rows that attend to real keys
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(expected)[valid],
                               atol=2e-5, rtol=2e-5)

    # The attention_fn seam accepts the encoder's [B, 1, 1, S] convention.
    got2 = jax.jit(flash_attention_fn)(q, k, v, mask[:, None, None, :])
    np.testing.assert_allclose(np.asarray(got2)[valid],
                               np.asarray(got)[valid], atol=1e-6)


def test_flash_key_padding_mask_gradients():
    from horovod_tpu.models.bert import dot_product_attention

    q, k, v = _qkv(B=1, S=256, H=2, Hkv=2)
    mask = (jnp.arange(256)[None, :] < 192)
    w = mask[:, :, None, None].astype(jnp.float32)  # zero padded-row loss

    def dense_loss(q, k, v):
        out = dot_product_attention(q, k, v, mask=mask[:, None, None, :])
        return jnp.sum((out * w) ** 2)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=False, key_padding_mask=mask)
        return jnp.sum((out * w) ** 2)

    dg = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    fg = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(dg, fg, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch")


def test_bert_encoder_with_flash_attention_seam():
    """BertModel(attention_fn=flash_attention_fn) with a padding mask must
    match the dense default — the seam the reference-era advisory flagged
    as silently dropping masks now honors them."""
    from horovod_tpu.models.bert import BertConfig, BertEncoder
    from horovod_tpu.ops.flash_attention import flash_attention_fn

    cfg = BertConfig(vocab_size=512, hidden_size=256, num_layers=2,
                     num_heads=2, intermediate_size=512, max_position=128,
                     dropout_rate=0.0, dtype=jnp.float32)
    ids = jax.random.randint(jax.random.key(0), (2, 128), 0, 512)
    attn_mask = (jnp.arange(128)[None, :]
                 < jnp.array([128, 80])[:, None]).astype(jnp.int32)

    dense = BertEncoder(cfg)
    flash = BertEncoder(cfg, attention_fn=flash_attention_fn)
    params = dense.init(jax.random.key(1), ids)
    out_d = dense.apply(params, ids, attention_mask=attn_mask)
    out_f = flash.apply(params, ids, attention_mask=attn_mask)
    valid = np.asarray(attn_mask, bool)
    np.testing.assert_allclose(np.asarray(out_f)[valid],
                               np.asarray(out_d)[valid],
                               atol=2e-4, rtol=2e-4)


def test_flash_segment_ids_packed_sequences():
    """Packed-sequence (block-diagonal causal) attention via segment_ids:
    O(S) sideband instead of an [S, S] mask, matching the dense reference
    in values and gradients.  S=384 -> block 128: a 3x3 block grid, so the
    per-block seg-slice offsets and the dynamic lower loop bound run with
    NONZERO block indices (a 256-long test would collapse to one block)."""
    from horovod_tpu.models.bert import dot_product_attention

    S = 384
    q, k, v = _qkv(B=2, S=S, H=2, Hkv=2)
    # Three packed docs per row (different split points per batch row).
    seg = jnp.stack([
        jnp.where(jnp.arange(S) < 100, 0,
                  jnp.where(jnp.arange(S) < 290, 1, 2)),
        jnp.where(jnp.arange(S) < 192, 7, 9),  # ids need not be 0-based
    ])

    tri = jnp.tril(jnp.ones((S, S), bool))
    same = seg[:, :, None] == seg[:, None, :]
    dense_mask = same[:, None, :, :] & tri[None, None, :, :]
    expected = dot_product_attention(q, k, v, mask=dense_mask)
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, segment_ids=seg))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=3e-5, rtol=3e-5)

    # Gradients through the packed kernel match the dense path.
    def dense_loss(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, mask=dense_mask) ** 2)

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       segment_ids=seg) ** 2)

    dg = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    fg = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(dg, fg, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch")


def test_flash_segment_ids_guards():
    import pytest

    q, k, v = _qkv(B=1, S=256, H=2, Hkv=2)
    seg = jnp.zeros((1, 256), jnp.int32)
    with pytest.raises(NotImplementedError):
        flash_attention(q, k, v, causal=False, segment_ids=seg)
    with pytest.raises(NotImplementedError):
        flash_attention(q, k, v, causal=True, segment_ids=seg,
                        key_padding_mask=jnp.ones((1, 256), bool))


def test_flash_d64_bert_head_dim():
    """head_dim 64 (the BERT-family size) engages the kernel — Mosaic pads
    the minor dim; measured faster than dense on-chip from S=2048."""
    from horovod_tpu.models.bert import dot_product_attention

    q, k, v = _qkv(B=1, S=256, H=2, Hkv=2, D=64)
    mask = (jnp.arange(256)[None, :] < 200)
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=False, key_padding_mask=mask))(q, k, v)
    expected = dot_product_attention(q, k, v, mask=mask[:, None, None, :])
    valid = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(expected)[valid],
                               atol=2e-5, rtol=2e-5)

    # Backward at D=64 through the masked (biased) kernels — the exact
    # path the BERT example's value_and_grad drives.
    w = mask[:, :, None, None].astype(jnp.float32)

    def dense_loss(q, k, v):
        out = dot_product_attention(q, k, v, mask=mask[:, None, None, :])
        return jnp.sum((out * w) ** 2)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=False, key_padding_mask=mask)
        return jnp.sum((out * w) ** 2)

    dg = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    fg = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(dg, fg, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch at D=64")


def test_flash_padded_tail_key_padding_mask():
    """Off-tile S with a BERT-style padding mask: the pad extends the mask
    (never attended) and valid rows match the dense reference."""
    from horovod_tpu.models.bert import dot_product_attention

    S = 200
    q, k, v = _qkv(B=2, S=S, H=2, Hkv=2, D=64)
    mask = (jnp.arange(S)[None, :] < jnp.array([S, 160])[:, None])
    expected = dot_product_attention(q, k, v, mask=mask[:, None, None, :])
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=False, key_padding_mask=mask))(q, k, v)
    valid = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(expected)[valid],
                               atol=2e-5, rtol=2e-5)


def test_flash_padded_tail_segment_ids():
    """Off-tile S with packed segments: the pad becomes a fresh trailing
    segment, values and gradients match the dense block-diagonal mask."""
    from horovod_tpu.models.bert import dot_product_attention

    S = 300
    q, k, v = _qkv(B=1, S=S, H=2, Hkv=2)
    seg = jnp.where(jnp.arange(S) < 130, 0, 1)[None, :]

    tri = jnp.tril(jnp.ones((S, S), bool))
    same = seg[:, :, None] == seg[:, None, :]
    dense_mask = same[:, None, :, :] & tri[None, None, :, :]
    expected = dot_product_attention(q, k, v, mask=dense_mask)
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, segment_ids=seg))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=3e-5, rtol=3e-5)

    def dense_loss(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, mask=dense_mask) ** 2)

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       segment_ids=seg) ** 2)

    dg = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    fg = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(dg, fg, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch")


# -- the backward pass is ONE call --------------------------------------------
#
# ``_bwd_impl`` made two calls until PR 29: one for dq (grid over query
# blocks, loop over key blocks) and one for dk and dv (grid over key blocks,
# loop over query blocks), each forming scores, p, dp and ds for itself.
# ``_two_call_bwd_impl`` is that pair in plain ``jnp``, block for block: the
# same products on the same operands, added up in the same order.  Against
# the parent commit's two Pallas calls themselves the fused call's dq, dk and
# dv were bit-identical in interpret mode (PR 29: 28 cases, fp32 and bf16,
# every variant below).  This copy holds to rounding only: whether XLA:CPU
# folds an accumulator's add into the dot before it differs between a
# kernel's loop and straight-line code, a unit in the last place.

def _assert_same_to_rounding(got, want, what):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    # At the peak's size: one unit in the last place of bf16 (a result
    # rounded the other way), eight of fp32.
    ulp = 2.0 ** -8 if what.endswith("bfloat16") else 2.0 ** -20
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=ulp * np.abs(want).max(), err_msg=what)


def _pair(q_blk, k_blk, v_blk, do_blk, lse_blk, delta_blk, qi, ki, bq, bk,
          causal, sm_scale, bias, seg_blk):
    """p and ds of one (query block, key block) pair of one head, as the
    kernels form them."""
    scores = jax.lax.dot_general(
        q_blk, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    q_pos = qi * bq + jnp.arange(bq)[:, None]
    k_pos = ki * bk + jnp.arange(bk)[None, :]
    if causal:
        scores = jnp.where(q_pos >= k_pos, scores, -1e30)
    if bias is not None:
        scores = scores + bias[None, ki * bk:(ki + 1) * bk]
    if seg_blk is not None:
        scores = jnp.where(k_pos >= seg_blk[:, None], scores, -1e30)
    p = jnp.exp(scores - lse_blk[:, None])
    dp = jax.lax.dot_general(
        do_blk, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = (p * (dp - delta_blk[:, None]) * sm_scale).astype(q_blk.dtype)
    return p, ds


def _two_call_bwd_impl(causal, sm_scale, res, do, bias=None, seg=None,
                       g_lse=None, mask=None, heads=1, window=None):
    from horovod_tpu.ops import flash_attention as fa

    assert mask is None and window is None    # the pair of PR 28 had neither
    q, k, v, out, lse = res
    if heads > 1:
        # Operands in place, [B, S, heads * D]: this copy works on the flat
        # [B * H, S, D] form and hands its results back side by side.
        b, s = q.shape[:2]
        kv_heads = k.shape[2] // (q.shape[2] // heads)

        def flat(x, n):
            return x.reshape(b, s, n, -1).transpose(0, 2, 1, 3).reshape(
                b * n, s, -1)

        def side_by_side(x, n):
            return x.reshape(b, n, s, -1).transpose(0, 2, 1, 3).reshape(
                b, s, -1)

        dq, dk, dv = _two_call_bwd_impl(
            causal, sm_scale,
            (flat(q, heads), flat(k, kv_heads), flat(v, kv_heads),
             flat(out, heads), lse), flat(do, heads), bias=bias, seg=seg,
            g_lse=g_lse)
        return (side_by_side(dq, heads), side_by_side(dk, kv_heads),
                side_by_side(dv, kv_heads))
    bh, s, d = q.shape
    # Grouped-query heads: the program's calls read a group's K and V where
    # they are; this copy repeats them, and adds a group's dk and dv up as
    # the program does, in float32.
    group = bh // k.shape[0]
    if group > 1:
        dq, dk, dv = _two_call_bwd_impl(
            causal, sm_scale, (q, jnp.repeat(k, group, axis=0),
                               jnp.repeat(v, group, axis=0), out, lse),
            do, bias=bias, seg=seg, g_lse=g_lse)

        def over_group(x):
            return jnp.sum(x.reshape(-1, group, *x.shape[1:]), axis=1,
                           dtype=jnp.float32).astype(x.dtype)
        return dq, over_group(dk), over_group(dv)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    bq, bk = fa._pick_block(s, fa.BLOCK_Q), fa._pick_block(s, fa.BLOCK_K)
    sideband = bias if bias is not None else seg      # [B, 8, S], or none
    heads = 1 if sideband is None else bh // sideband.shape[0]

    def one_head(q, k, v, do, lse, delta, bias, seg):
        def blk(x, i, b):
            return x[i * b:(i + 1) * b]

        def pair(qi, ki):
            return _pair(blk(q, qi, bq), blk(k, ki, bk), blk(v, ki, bk),
                         blk(do, qi, bq), blk(lse, qi, bq),
                         blk(delta, qi, bq), qi, ki, bq, bk, causal,
                         sm_scale, bias, None if seg is None
                         else blk(seg, qi, bq))

        def live(qi, ki):      # the causal loop bounds of both kernels
            return not causal or ki * bk < (qi + 1) * bq

        dq = []
        for qi in range(s // bq):                     # the dq call
            acc = jnp.zeros((bq, d), jnp.float32)
            for ki in range(s // bk):
                if live(qi, ki):
                    _, ds = pair(qi, ki)
                    acc = acc + jax.lax.dot_general(
                        ds, blk(k, ki, bk), (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
            dq.append(acc.astype(q.dtype))
        dk, dv = [], []
        for ki in range(s // bk):                     # the dkv call
            dk_acc = jnp.zeros((bk, d), jnp.float32)
            dv_acc = jnp.zeros((bk, d), jnp.float32)
            for qi in range(s // bq):
                if live(qi, ki):
                    p, ds = pair(qi, ki)
                    dv_acc = dv_acc + jax.lax.dot_general(
                        p.astype(do.dtype), blk(do, qi, bq),
                        (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    dk_acc = dk_acc + jax.lax.dot_general(
                        ds, blk(q, qi, bq), (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
            dk.append(dk_acc.astype(k.dtype))
            dv.append(dv_acc.astype(v.dtype))
        return jnp.concatenate(dq), jnp.concatenate(dk), jnp.concatenate(dv)

    per_head = lambda x: None if x is None else jnp.repeat(
        x[:, 0, :], heads, axis=0)
    grads = [one_head(q[b], k[b], v[b], do[b], lse[b, 0], delta[b],
                      None if bias is None else per_head(bias)[b],
                      None if seg is None else per_head(seg)[b])
             for b in range(bh)]
    return tuple(jnp.stack(g) for g in zip(*grads))


def _dense(q, k, v, *, causal=True, key_padding_mask=None, segment_ids=None):
    """Attention with the whole [S, S] score matrix, fp32."""
    B, S, H, D = q.shape
    if k.shape[2] != H:
        k = jnp.repeat(k, H // k.shape[2], axis=2)
        v = jnp.repeat(v, H // v.shape[2], axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    mask = jnp.ones((B, 1, S, S), bool)
    if causal:
        mask = mask & jnp.tril(jnp.ones((S, S), bool))[None, None]
    if key_padding_mask is not None:
        mask = mask & key_padding_mask[:, None, None, :]
    if segment_ids is not None:
        mask = mask & (segment_ids[:, None, :, None]
                       == segment_ids[:, None, None, :])
    p = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _segments(S, *bounds):
    return jnp.sum(jnp.arange(S)[:, None] >= jnp.asarray(bounds)[None, :],
                   axis=-1)


S_BWD = 1024     # 512-row blocks: a 2 x 2 grid of block pairs
BWD_CASES = {
    "causal": dict(),
    "bidirectional": dict(kwargs=dict(causal=False)),
    "key_bias": dict(kwargs=dict(
        causal=False, key_padding_mask=jnp.arange(S_BWD)[None, :]
        < jnp.array([S_BWD - 37, S_BWD // 2 + 5])[:, None])),
    # A boundary inside a block (300), one on a block edge (512), and in
    # the second row a segment of one token just behind the edge.
    "packed": dict(kwargs=dict(segment_ids=jnp.stack([
        _segments(S_BWD, 300, 512, 900), _segments(S_BWD, 512, 513)]))),
    "gqa": dict(shape=dict(H=4, Hkv=2)),
    "padded_tail": dict(shape=dict(S=S_BWD - 100)),
    "head_dim_64": dict(shape=dict(D=64)),
    "block_q_over_block_k": dict(blocks=(512, 256)),
    "block_q_under_block_k": dict(blocks=(128, 512)),
}


def _bwd_case(name, monkeypatch, dtype):
    from horovod_tpu.ops import flash_attention as fa

    case = BWD_CASES[name]
    if "blocks" in case:
        monkeypatch.setattr(fa, "BLOCK_Q", case["blocks"][0])
        monkeypatch.setattr(fa, "BLOCK_K", case["blocks"][1])
        assert (fa._pick_block(S_BWD, fa.BLOCK_Q),
                fa._pick_block(S_BWD, fa.BLOCK_K)) == case["blocks"]
    shape = dict(B=2, S=S_BWD, H=2, Hkv=2, D=128)
    shape.update(case.get("shape", {}))
    q, k, v = _qkv(**shape, dtype=dtype)
    w = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)
    kwargs = case.get("kwargs", {})
    if "key_padding_mask" in kwargs:    # a masked-out row's output is undefined
        w = w * kwargs["key_padding_mask"][:, :, None, None]
    return q, k, v, w, kwargs


def _grads(attn, q, k, v, w, **kwargs):
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v, **kwargs).astype(jnp.float32) * w)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


@pytest.mark.parametrize("name", list(BWD_CASES))
def test_fused_backward_matches_dense(name, monkeypatch):
    q, k, v, w, kwargs = _bwd_case(name, monkeypatch, jnp.float32)
    got = _grads(flash_attention, q, k, v, w, **kwargs)
    want = _grads(_dense, q, k, v, w, **kwargs)
    for g, r, which in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, r, atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{which} of {name}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(BWD_CASES))
def test_fused_backward_is_the_two_calls_result(name, dtype, monkeypatch):
    from horovod_tpu.ops import flash_attention as fa

    q, k, v, w, kwargs = _bwd_case(name, monkeypatch, dtype)
    got = _grads(flash_attention, q, k, v, w, **kwargs)
    monkeypatch.setattr(fa, "_bwd_impl", _two_call_bwd_impl)
    want = _grads(flash_attention, q, k, v, w, **kwargs)
    for g, r, which in zip(got, want, "qkv"):
        _assert_same_to_rounding(g, r, f"d{which} of {name}, {g.dtype.name}")


@pytest.mark.parametrize("causal", [True, False])
def test_fused_backward_takes_the_lse_cotangent(causal, monkeypatch):
    """``flash_attention_lse`` (ring attention's hop): a cotangent on lse
    folds into delta.  Against dense, and against the two calls."""
    from horovod_tpu.ops import flash_attention as fa

    q, k, v = _qkv(B=1, S=S_BWD, H=2, Hkv=2)
    w = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)

    def grads(attn_lse):
        def loss(q, k, v):
            out, lse = attn_lse(q, k, v)
            return jnp.sum(out * w) + jnp.sum(jnp.sin(lse))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    def dense_lse(q, k, v):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        if causal:
            scores = jnp.where(jnp.tril(jnp.ones((S_BWD, S_BWD), bool)),
                               scores, -1e30)
        return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v),
                jax.nn.logsumexp(scores, -1))

    flash_lse = lambda q, k, v: fa.flash_attention_lse(q, k, v, causal=causal)
    got = grads(flash_lse)
    for g, r, which in zip(got, grads(dense_lse), "qkv"):
        np.testing.assert_allclose(g, r, atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{which}")
    monkeypatch.setattr(fa, "_bwd_impl", _two_call_bwd_impl)
    for g, r, which in zip(got, grads(flash_lse), "qkv"):
        _assert_same_to_rounding(g, r, f"d{which}, float32")


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


@pytest.mark.parametrize("name", ["causal", "key_bias", "packed"])
def test_forward_and_backward_are_one_call_each(name, monkeypatch):
    q, k, v, w, kwargs = _bwd_case(name, monkeypatch, jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, **kwargs) * w)

    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    calls = _pallas_calls(closed.jaxpr)
    assert sorted(len(eqn.outvars) for eqn in calls) == [2, 3], calls
    backward, = (eqn for eqn in calls if len(eqn.outvars) == 3)   # dq, dk, dv
    assert [tuple(var.aval.shape) for var in backward.outvars] == [
        (q.shape[0] * q.shape[2], q.shape[1], q.shape[3])] * 3


def test_backward_vmem_limit_follows_the_shapes():
    """Twice the call's blocks, scratch and live arrays, never under the
    compiler's default: 30 MiB at the benchmark's 8k shape (bf16, D = 128),
    where the decoder's step was seen to use 19.8; the default at 2k."""
    from horovod_tpu.ops import flash_attention as fa

    mib = 2 ** 20
    assert fa._bwd_vmem_limit(8192, 128, 512, 512, 2, 0) == 30 * mib
    assert fa._bwd_vmem_limit(2048, 128, 512, 512, 2, 0) == 16 * mib
    assert fa._bwd_vmem_limit(128, 128, 128, 128, 2, 0) == 16 * mib
    # A sideband is one more [8, S] fp32 block.
    assert (fa._bwd_vmem_limit(8192, 128, 512, 512, 2, 1)
            - fa._bwd_vmem_limit(8192, 128, 512, 512, 2, 0)) == mib // 2
    # Lanes are padded to 128; fp32 rows are twice bf16's.
    assert fa._bwd_vmem_limit(8192, 64, 512, 512, 2, 0) == 30 * mib
    assert fa._bwd_vmem_limit(8192, 128, 512, 512, 4, 0) == 43 * mib
    at_16k = fa._bwd_vmem_limit(16384, 128, 512, 512, 2, 0)
    assert 2 * 16384 * 128 * (3 * 2 + 4) < at_16k < 64 * mib
    assert at_16k < fa._bwd_vmem_limit(32768, 128, 512, 512, 2, 0) < 128 * mib


# -- a kept forward call is kept whatever the sidebands ------------------------

def _calls_under(jaxpr, scope, inside=False):
    """The ``pallas_call`` equations whose own name stack, or an enclosing
    equation's, holds ``scope``; sub-jaxprs walked."""
    found = []
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack)
        if eqn.primitive.name == "pallas_call" and here:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_calls_under(sub, scope, here))
    return found


@pytest.mark.parametrize("name", ["plain", "segment_ids", "key_padding_mask"])
def test_layer_keep_attention_keeps_the_forward_call_of_every_variant(name):
    """One forward rule names the flash call's output and row statistics
    (``hvd.flash.out``, ``hvd.flash.lse``) whatever sidebands ride along, so
    under ``REMAT_POLICIES["layer_keep_attention"]`` the gradient of one
    recomputed layer holds ONE forward call and one backward: a packed or a
    padded step does not run the forward call again in its backward pass
    (until PR 45 the packed and the masked wrapper named nothing, and did)."""
    import functools

    import flax.linen as nn

    from horovod_tpu.common import scopes
    from horovod_tpu.models.llama import (REMAT_POLICIES, LlamaConfig,
                                          LlamaLayer, rope_freqs)
    from horovod_tpu.ops.flash_attention import flash_attention_fn

    S = 256
    attention_fn = {
        "plain": flash_attention_fn,
        "segment_ids": functools.partial(
            flash_attention_fn, segment_ids=_segments(S, 100, 128)[None]),
        "key_padding_mask": functools.partial(
            flash_attention_fn, mask=(jnp.arange(S) < S - 37)[None]),
    }[name]
    config = LlamaConfig(
        vocab_size=64, hidden_size=128, num_layers=1, num_heads=2,
        num_kv_heads=1, intermediate_size=128, max_seq_len=S,
        dtype=jnp.float32)
    layer = nn.remat(LlamaLayer, policy=REMAT_POLICIES[
        "layer_keep_attention"])(config, attention_fn=attention_fn)
    x = jnp.ones((1, S, 128), jnp.float32)
    tables = rope_freqs(config.head_dim, S, 1e4)
    params = jax.eval_shape(layer.init, jax.random.key(0), x, *tables)

    def loss(params, x):
        return jnp.sum(layer.apply(params, x, *tables))

    closed = jax.make_jaxpr(jax.grad(loss))(params, x)
    assert len(_calls_under(closed.jaxpr, scopes.FLASH_BWD)) == 1
    assert len(_calls_under(closed.jaxpr, scopes.FLASH_FWD)) == 1
