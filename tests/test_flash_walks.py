"""The flash kernels' walks and layouts (interpret mode on CPU): the
diagonal's pair beside the loop, ``pair_counts`` against every score, heads
in place against the flat form's bits, and which layout a width takes.  The
variants against dense attention, and the one-call backward pass, are in
``tests/test_flash_attention.py``, whose helpers these tests share."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.llama import causal_attention
from tests.test_flash_attention import _pallas_calls, _qkv, _segments

# The walks here run interpreted kernels over 1,024 to 2,048 rows, for longer
# than they take to compile, and one test states two programs' bits equal:
# under the suite's unoptimised level (``tests/conftest.py``) the file took
# twice its CPU seconds and that test failed in all eleven cases (PR 55).
pytestmark = pytest.mark.usefixtures("optimised_code")


# -- the diagonal's pair leaves the loop ---------------------------------------
#
# Until PR 35 each kernel ran every live block pair in one loop.  Now the pair
# at the diagonal's end of a causal walk is straight-line code (the last key
# block of a query block forward, the first query block of a key block
# backward) and the loop holds the others: the same body on the same pairs in
# the same order, so the results are the one-loop walk's to the last bit.
# That walk stays reachable from here alone, by ``_walk`` without its
# ``apart``.

def _walk_case(name):
    """(q [BH, S, D], k, v [BHkv, S, D(v)], do, sidebands) of one case."""
    case = WALK_CASES[name]
    shape = dict(S=1024, H=2, Hkv=2, D=128, Dv=128)
    shape.update(case.get("shape", {}))
    s = shape["S"]
    keys = jax.random.split(jax.random.key(35), 5)

    def normal(key, heads, width):
        return jax.random.normal(key, (heads, s, width), jnp.bfloat16)

    q = normal(keys[0], shape["H"], shape["D"])
    k = normal(keys[1], shape["Hkv"], shape["D"])
    v = normal(keys[2], shape["Hkv"], shape["Dv"])
    do = normal(keys[3], shape["H"], shape["Dv"])
    sidebands = {}
    if "segments" in case:
        from horovod_tpu.ops.flash_attention import _segment_starts
        starts = _segment_starts(_segments(s, *case["segments"])[None, :])
        sidebands["seg"] = jnp.broadcast_to(starts[:, None, :], (1, 8, s))
    if "masked_keys" in case:
        pos = jnp.arange(s)
        out = jnp.zeros((s,), bool)
        for lo, hi in case["masked_keys"]:
            out = out | ((pos >= lo) & (pos < hi))
        sidebands["bias"] = jnp.broadcast_to(
            jnp.where(out, -1e30, 0.0).astype(jnp.float32), (1, 8, s))
    if "keep" in case:
        # A causal selection that holds each query's own key, one a batch.
        kept = jax.random.uniform(keys[4], (1, s, s)) < case["keep"]
        sidebands["mask"] = ((kept | jnp.eye(s, dtype=bool))
                             & jnp.tril(jnp.ones((s, s), bool))).astype(
                                 jnp.int8)
    return q, k, v, do, sidebands


WALK_CASES = {
    "s1024": dict(),
    "s2048": dict(shape=dict(S=2048)),
    "blocks_of_256": dict(shape=dict(S=768), blocks=(256, 256)),
    "blocks_of_128": dict(shape=dict(S=640), blocks=(128, 128)),
    # A boundary inside a block, one on a block edge, and a late one: the
    # segment's first key block bounds both walks from below, and a block
    # row whose segment starts in its own block has no pair but the
    # diagonal's.
    "packed": dict(shape=dict(S=1536), segments=(300, 512, 1400)),
    "key_bias": dict(masked_keys=((100, 130), (600, 700))),
    "selected": dict(shape=dict(H=4, Hkv=2), keep=0.3),
    "gqa": dict(shape=dict(H=4, Hkv=2)),
    "value_width_of_its_own": dict(shape=dict(D=192, Dv=128)),
    # Unequal blocks: the diagonal crosses two or four pairs of a block row
    # or column, one leaves the loop, the others mask inside it.
    "block_q_over_block_k": dict(force=(512, 256), blocks=(512, 256)),
    "block_q_under_block_k": dict(force=(128, 512), blocks=(128, 512)),
}


@pytest.mark.parametrize("name", list(WALK_CASES))
def test_diagonal_pair_outside_the_loop_gives_the_one_loop_walks_bits(
        name, monkeypatch):
    from horovod_tpu.ops import flash_attention as fa

    case = WALK_CASES[name]
    if "force" in case:
        monkeypatch.setattr(fa, "BLOCK_Q", case["force"][0])
        monkeypatch.setattr(fa, "BLOCK_K", case["force"][1])
    q, k, v, do, sidebands = _walk_case(name)
    s = q.shape[1]
    bq, bk = fa._pick_block(s, fa.BLOCK_Q), fa._pick_block(s, fa.BLOCK_K)
    assert (bq, bk) == case.get("blocks", (512, 512))
    sm_scale = q.shape[-1] ** -0.5

    def both():
        out, lse = jax.jit(lambda: fa._fwd(q, k, v, True, sm_scale,
                                           **sidebands))()
        return (out, lse[:, 0]) + tuple(jax.jit(lambda: fa._bwd_impl(
            True, sm_scale, (q, k, v, out, lse), do, **sidebands))())

    got = both()
    walk, taken_apart = fa._walk, []

    def one_loop(first, end, body, carry, apart):
        taken_apart.append(apart)
        return walk(first, end, body, carry)

    monkeypatch.setattr(fa, "_walk", one_loop)
    want = both()
    assert taken_apart == ["last", "first"]
    for a, b, what in zip(got, want, ("o", "lse", "dq", "dk", "dv")):
        assert a.dtype == b.dtype and a.shape == b.shape, what
        assert a.dtype == (jnp.float32 if what == "lse" else jnp.bfloat16)
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint32 if what == "lse" else np.uint16),
            np.asarray(b).view(np.uint32 if what == "lse" else np.uint16),
            err_msg=f"{what} of {name}")


def _has_iota(jaxpr):
    return any(eqn.primitive.name == "iota"
               or any(_has_iota(sub) for sub in
                      jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def _where_the_mask_is_built(kernel_jaxpr):
    """(for each loop over block pairs at a kernel's top level, in order,
    whether its body builds positions; whether straight-line code does)."""
    loops = [any(_has_iota(sub) for sub in
                 jax.core.jaxprs_in_params(eqn.params))
             for eqn in kernel_jaxpr.eqns
             if eqn.primitive.name in ("while", "scan")]
    return loops, any(eqn.primitive.name == "iota"
                      for eqn in kernel_jaxpr.eqns)


@pytest.mark.parametrize("causal, blocks, want", [
    # One loop, whose pairs mask, and the diagonal's pair beside it.
    (True, (512, 512), ([True], True)),
    (True, (512, 256), ([True], True)),
    # Not causal: the one loop there was, and no positions anywhere.
    (False, (512, 512), ([False], False))])
def test_one_loop_a_kernel_and_the_diagonals_pair_beside_it(
        causal, blocks, want, monkeypatch):
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "BLOCK_Q", blocks[0])
    monkeypatch.setattr(fa, "BLOCK_K", blocks[1])
    q = jnp.zeros((2, 1024, 128), jnp.bfloat16)

    def both(q, k, v):
        out, lse = fa._fwd(q, k, v, causal, 0.1)
        return fa._bwd_impl(causal, 0.1, (q, k, v, out, lse), out)

    fwd, bwd = _pallas_calls(jax.make_jaxpr(both)(q, q, q).jaxpr)
    assert _where_the_mask_is_built(fwd.params["jaxpr"]) == want
    assert _where_the_mask_is_built(bwd.params["jaxpr"]) == want


@pytest.mark.parametrize("s, bq, bk, want", [
    (8192, 512, 512, (136, 16)), (4096, 512, 512, (36, 8)),
    (2048, 512, 512, (10, 4)), (1024, 512, 512, (3, 2)),
    (768, 256, 256, (6, 3)), (640, 128, 128, (15, 5)),
    (1024, 512, 256, None), (1024, 128, 512, None), (1536, 256, 128, None),
    (1536, 128, 256, None)])
def test_pair_counts_against_every_score(s, bq, bk, want):
    """A pair is live when some key is at or before some query, and the
    diagonal crosses it when besides some key is after some query."""
    from horovod_tpu.ops import flash_attention as fa

    pairs = diagonal = 0
    for qi in range(s // bq):
        for ki in range(s // bk):
            keep = (np.arange(qi * bq, (qi + 1) * bq)[:, None]
                    >= np.arange(ki * bk, (ki + 1) * bk)[None, :])
            pairs += keep.any()
            diagonal += keep.any() and not keep.all()
    assert fa.pair_counts(s, bq, bk, True) == (pairs, diagonal)
    assert want is None or (pairs, diagonal) == want
    if bq == bk:
        assert diagonal == s // bq
    assert fa.pair_counts(s, bq, bk, False) == ((s // bq) * (s // bk), 0)


# -- heads indexed in place -----------------------------------------------------
#
# Through the model zoo's seam the two calls take q, k, v, dO and give o, dq,
# dk, dv as ``[B, S, H * D]``, where the projections' matmuls leave them, and
# index a head by its block of the last axis.  The flat ``[B * H, S, D]``
# operand is the same call with one head an operand, so the two forms run the
# same body on the same blocks in the same order: their results are equal to
# the last bit.  ``_kernel_layout`` chooses; forced flat here it is the
# reference.

def _bits(x):
    return np.asarray(jnp.asarray(x, jnp.float32)).view(np.uint32)


def _seam_variant(name, q):
    """``(attend, extra loss term)`` of a variant of the seam: what the
    public entry points hand the two calls beside q, k and v."""
    from horovod_tpu.ops import flash_attention as fa

    B, S = q.shape[:2]
    if name == "causal":
        return lambda q, k, v: (fa.flash_attention_fn(q, k, v), 0.0)
    if name == "key_bias":
        mask = jnp.arange(S)[None, :] < jnp.array([S - 37, S // 2 + 5])[:B, None]
        return lambda q, k, v: (
            fa.flash_attention_fn(q, k, v, mask[:, None, None, :]), 0.0)
    if name == "packed":
        ids = jnp.stack([_segments(S, 100, 128, 200),
                         _segments(S, 128, 129)])[:B]
        return lambda q, k, v: (
            fa.flash_attention_fn(q, k, v, segment_ids=ids), 0.0)
    if name == "selected":
        keep = jax.random.bernoulli(jax.random.key(5), 0.5, (B, S, S))
        selected = (jnp.tril(keep) | jnp.eye(S, dtype=bool)).astype(jnp.int8)
        return lambda q, k, v: (
            fa.flash_attention_fn(q, k, v, selected=selected)[0], 0.0)
    assert name == "lse"

    def with_lse_cotangent(q, k, v):
        out, lse = fa.flash_attention_lse(q, k, v)
        return out, jnp.sum(jnp.sin(lse))

    return with_lse_cotangent


def _out_and_grads(attend, q, k, v, w):
    def loss(q, k, v):
        out, extra = attend(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w) + extra, out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return (out, *grads)


@pytest.mark.parametrize("D", [128, 256])
@pytest.mark.parametrize("H, Hkv", [(16, 16), (32, 4), (8, 2)])
@pytest.mark.parametrize("name", ["causal", "key_bias", "packed", "selected",
                                  "lse"])
def test_heads_in_place_give_the_flat_forms_bits(name, H, Hkv, D,
                                                 monkeypatch):
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "BLOCK_Q", 128)
    monkeypatch.setattr(fa, "BLOCK_K", 128)
    q, k, v = _qkv(B=2, S=256, H=H, Hkv=Hkv, D=D, seed=3, dtype=jnp.bfloat16)
    w = jax.random.normal(jax.random.key(4), q.shape, jnp.float32)
    attend = _seam_variant(name, q)
    before = fa.layout_counts()
    got = _out_and_grads(attend, q, k, v, w)
    after = fa.layout_counts()
    assert after["in_place"] > before["in_place"]
    assert after["flat"] == before["flat"]
    # The calls really took [B, S, H * D]: one reshape, no transpose.
    closed = jax.make_jaxpr(lambda q, k, v: attend(q, k, v)[0])(q, k, v)
    call, = _pallas_calls(closed.jaxpr)
    assert [tuple(x.aval.shape) for x in call.invars[:3]] == [
        (2 * 256, H * D), (2 * 256, Hkv * D), (2 * 256, Hkv * D)]
    assert "transpose" not in {e.primitive.name for e in closed.jaxpr.eqns}

    layout = fa._kernel_layout
    monkeypatch.setattr(fa, "_kernel_layout",
                        lambda q, k, v, in_place=True: layout(q, k, v, False))
    after = fa.layout_counts()
    want = _out_and_grads(attend, q, k, v, w)
    assert fa.layout_counts()["in_place"] == after["in_place"]
    for g, r, what in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.shape == r.shape and g.dtype == r.dtype
        np.testing.assert_array_equal(_bits(g), _bits(r), err_msg=what)


@pytest.mark.parametrize("D, Dv, why", [
    (192, 128, "head width off the lane tiling"),
    (64, 64, "head width off the lane tiling"),
    (128, 128, "flash_attention called with operands of the caller's own")])
def test_widths_off_the_lane_tiling_take_the_flat_form_and_say_so(D, Dv, why):
    """A block's last dimension must be whole 128-lane tiles: latent
    attention's 192 / 128 and BERT's 64 are transposed into ``[B * H, S,
    D]`` as before, through the seam too; and so is a call of
    ``flash_attention`` itself at any width."""
    from horovod_tpu.ops import flash_attention as fa

    q, k, _ = _qkv(B=1, S=128, H=2, Hkv=2, D=D, dtype=jnp.bfloat16)
    v = _qkv(B=1, S=128, H=2, Hkv=2, D=Dv, dtype=jnp.bfloat16)[2]
    attend = fa.flash_attention if D == 128 else fa.flash_attention_fn
    before = fa.layout_counts()
    closed = jax.make_jaxpr(attend)(q, k, v)
    after = fa.layout_counts()
    assert after["in_place"] == before["in_place"]
    assert after["flat"][why] == before["flat"].get(why, 0) + 1
    call, = _pallas_calls(closed.jaxpr)
    assert [tuple(x.aval.shape) for x in call.invars[:3]] == [
        (2, 128, D), (2, 128, D), (2, 128, Dv)]
    np.testing.assert_allclose(
        attend(q, k, v).astype(jnp.float32),
        causal_attention(q, k, v).astype(jnp.float32), atol=2e-2)


def test_a_flat_operand_is_the_one_head_case_of_the_same_call(monkeypatch):
    """``_flash`` on ``[B * H, S, D]`` is ``_flash`` on ``[B, S, H * D]``
    with ``heads = 1``: one custom_vjp, one pair of kernels, the block
    specs alone differ; output and gradients equal to the bit, grouped
    heads included."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "BLOCK_Q", 128)
    monkeypatch.setattr(fa, "BLOCK_K", 128)
    B, S, H, Hkv, D = 2, 256, 4, 2, 128
    q, k, v = _qkv(B=B, S=S, H=H, Hkv=Hkv, D=D, seed=6, dtype=jnp.bfloat16)
    w = jax.random.normal(jax.random.key(7), q.shape, jnp.float32)
    scale = D ** -0.5

    def out_and_grads(operands, heads, weight):
        def loss(q, k, v):
            out, _ = fa._flash(q, k, v, None, None, None, True, scale,
                               heads)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(*operands)
        return (out, *grads)

    def rows(x):                     # [B, S, n, D] -> [B, S, n * D]
        return x.reshape(B, S, -1)

    def back(x, n):                  # [B * n, S, D] -> [B, S, n * D]
        return rows(x.reshape(B, n, S, D).transpose(0, 2, 1, 3))

    flat = out_and_grads(fa._flat_layout(q, k, v), 1,
                         w.transpose(0, 2, 1, 3).reshape(B * H, S, D))
    in_place = out_and_grads((rows(q), rows(k), rows(v)), H, rows(w))
    for g, r, n, what in zip(in_place, flat, (H, H, Hkv, Hkv),
                             ("out", "dq", "dk", "dv")):
        np.testing.assert_array_equal(_bits(g), _bits(back(r, n)),
                                      err_msg=what)
