"""``ops/rope.py::rotate_pairs`` (interpret mode on CPU, the Mosaic pass on
TPU; ``apply_rope(..., in_place=True)``) against ``apply_rope``'s ``jnp``
body, which stays the form for widths off the 128-lane tiling and for every
model whose ``attention_fn`` does not read its operands in place.

The two form the same products and sums and round once, so they agree to the
last bit wherever the arithmetic is the same.  XLA:CPU is free to contract a
multiply and an add into one fused operation (it always allows it), and
contracts the ``jnp`` body's ``x1 * c - x2 * s`` and the kernel's ``x * c +
partner * t`` by its own lights, which moves a result by a unit in the last
place.  So the bits are compared on tables rounded to eight bits, where every
product of a bfloat16-valued x is exact in float32 and a fused multiply-add
rounds as the separate operations do; on the tables as ``rope_freqs`` makes
them the two are held to one unit in the last place."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import scopes, trace_counts
from horovod_tpu.models import llama
from horovod_tpu.models.llama import YarnScaling, apply_rope, rope_freqs
from horovod_tpu.ops import rope
from horovod_tpu.ops.flash_attention import flash_attention_fn

in_place = functools.partial(apply_rope, in_place=True)

B, S, H = 2, 64, 3
YARN = YarnScaling(40, 4096, 32, 1, 0.707, 0.707)


def _taken(reason):
    """How many traces of ``ops/rope.py::rotate`` went ``reason``'s way."""
    return trace_counts.counts(rope.BODY).get(reason, 0)


def _bits(x):
    return np.asarray(jnp.asarray(x, jnp.float32)).view(np.uint32)


def _eight_bits(table):
    return table.astype(jnp.bfloat16).astype(jnp.float32)


def _pallas_calls(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


def _rotated_and_cotangent(rotate, x, g, offset, tables, exact):
    """Forward and ``jax.vjp`` of ``rotate`` on tables made inside the jit
    from a TRACED offset (a sequence shard passes ``axis_index * S``)."""
    def run(x, g, offset):
        cos, sin = rope_freqs(x.shape[-1], x.shape[1], 1e4, offset=offset,
                              scaling=YARN if tables == "yarn" else None)
        if exact:
            cos, sin = _eight_bits(cos), _eight_bits(sin)
        out, vjp = jax.vjp(lambda x: rotate(x, cos, sin), x)
        return out, vjp(g)[0]
    return jax.jit(run)(x, g, offset)


@pytest.mark.parametrize("tables", ["plain", "yarn"])
@pytest.mark.parametrize("D", [128, 256])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_rotate_pairs_gives_the_jnp_bodys_bits(dtype, D, tables):
    kx, kg = jax.random.split(jax.random.key(D))
    # bfloat16-valued in either dtype: see the module's docstring.
    x = jax.random.normal(kx, (B, S, H, D), jnp.bfloat16).astype(dtype)
    g = jax.random.normal(kg, (B, S, H, D), jnp.bfloat16).astype(dtype)
    offset = jnp.int32(5)
    before = _taken(rope.IN_PLACE)
    got = _rotated_and_cotangent(in_place, x, g, offset, tables, True)
    assert _taken(rope.IN_PLACE) == before + 1
    want = _rotated_and_cotangent(apply_rope, x, g, offset,
                                  tables, True)
    for a, b, what in zip(got, want, ("rotated", "cotangent")):
        assert a.shape == b.shape and a.dtype == b.dtype == dtype
        np.testing.assert_array_equal(_bits(a), _bits(b), err_msg=what)
    # On the tables as they are: a unit in the last place at most.
    got = _rotated_and_cotangent(in_place, x, g, offset, tables, False)
    want = _rotated_and_cotangent(apply_rope, x, g, offset,
                                  tables, False)
    # A unit in the last place of a value is up to 2^-7 (2^-23) of it.
    ulp = 2.0 ** (-7 if dtype == jnp.bfloat16 else -22)
    for a, b, turned, what in zip(got, want, (x, g),
                                  ("rotated", "cotangent")):
        a, b = (np.asarray(t, np.float32) for t in (a, b))
        # Of the pair's length, which a rotation keeps: each of the two
        # products is at most that, whatever their sum.
        pairs = np.asarray(turned, np.float32).reshape(B, S, H, D // 2, 2)
        length = np.repeat(np.linalg.norm(pairs, axis=-1), 2, axis=-1)
        assert np.all(np.abs(a - b) <= ulp * length), what
    # It rotates: norms of pairs kept, position 0 of a zero offset unmoved.
    at_zero = _rotated_and_cotangent(in_place, x, g, jnp.int32(0), "plain",
                                     False)[0]
    np.testing.assert_array_equal(_bits(at_zero[:, 0]), _bits(x[:, 0]))


def test_the_pass_is_one_mosaic_call_under_its_own_scope():
    """``apply_rope`` in place at D = 128 is a reshape, ONE ``pallas_call`` on the
    ``[B, S, H * D]`` view and a reshape back: no strided slice (XLA:TPU's
    gather), no stack; its transpose is the same call; the call is under
    ``hvd.rope`` and under no flash scope."""
    x = jnp.zeros((B, S, H, 128), jnp.bfloat16)
    cos, sin = rope_freqs(128, S, 1e4)

    def both(x):
        out, vjp = jax.vjp(lambda x: in_place(x, cos, sin), x)
        return out, vjp(out)[0]

    closed = jax.make_jaxpr(both)(x)
    calls = _pallas_calls(closed.jaxpr)
    assert len(calls) == 2
    for call in calls:
        assert [tuple(v.aval.shape) for v in call.invars] == [
            (B, S, H * 128), (S, 128), (S, 128)]
        assert call.invars[1].aval.dtype == jnp.float32
    names = {e.primitive.name for e in closed.jaxpr.eqns}
    assert not names & {"gather", "scatter-add", "scatter_add", "pad"}
    text = jax.jit(both).lower(x).as_text(debug_info=True)
    lines = [line for line in text.splitlines() if scopes.ROPE in line]
    assert lines and not any("hvd.flash" in line for line in lines)


@pytest.mark.parametrize("shape, why", [
    ((B, S, H, 64), "an indexer's 64, latent attention's rotating dims"),
    ((B, S, 1, 192), "no whole lane tiles"),
    ((B, 7, H, 128), "a prompt of 7 tokens: no block of rows divides it")])
def test_other_widths_keep_the_jnp_body(shape, why):
    x = jax.random.normal(jax.random.key(0), shape, jnp.bfloat16)
    cos, sin = rope_freqs(shape[-1], shape[1], 1e4)
    before = _taken(rope.OFF_TILING)
    closed = jax.make_jaxpr(lambda x: in_place(x, cos, sin))(x)
    assert _taken(rope.OFF_TILING) == before + 1, why
    assert _pallas_calls(closed.jaxpr) == []
    np.testing.assert_array_equal(
        _bits(in_place(x, cos, sin)), _bits(apply_rope(x, cos, sin)))


@pytest.mark.parametrize("name, attention_fn, passes", [
    ("the model's own dense attention", llama.causal_attention, 0),
    ("a caller's closure",
     lambda q, k, v, *a, **kw: llama.causal_attention(q, k, v), 0),
    ("the flash seam", flash_attention_fn, 2),
    ("the flash seam with segments bound", functools.partial(
        flash_attention_fn, segment_ids=jnp.zeros((B, 128), jnp.int32)), 2)])
def test_the_attention_fn_chooses_the_rotation(name, attention_fn, passes):
    """A Mosaic call is the caller's choice (the partitioner cannot split
    one): q and k of a 128-wide head are turned by the pass only where the
    ``attention_fn`` the model was given says it reads them in place, by its
    attribute ``in_place`` (a ``functools.partial`` of it too); with the
    default attention the model holds no ``pallas_call`` at all."""
    config = llama.LlamaConfig(
        vocab_size=64, hidden_size=256, num_layers=1, num_heads=2,
        num_kv_heads=1, intermediate_size=128, max_seq_len=128)
    assert config.head_dim == 128
    assert llama._reads_in_place(attention_fn) == bool(passes), name
    model = llama.LlamaModel(config, attention_fn=attention_fn)
    tokens = jnp.zeros((B, 128), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)
    closed = jax.make_jaxpr(model.apply)(params, tokens)
    calls = _pallas_calls(closed.jaxpr)
    rotations = [c for c in calls
                 if scopes.ROPE in str(c.source_info.name_stack)]
    assert len(rotations) == passes, name
    assert len(calls) == (passes + 1 if passes else 0), name


def test_interpreted_or_not_is_part_of_the_rotations_trace(monkeypatch):
    """``_rotate`` is a jit, whose cache of traces knows shapes and static
    arguments and no module global: the mode is a static argument, so a
    process that compiles the pass for a described chip after it has run
    the same shape interpreted (or the other way round) gets what it asks
    for."""
    x = jnp.zeros((1, 16, 128), jnp.bfloat16)
    cos, sin = rope_freqs(128, 16, 1e4)

    def mode():
        call, = _pallas_calls(jax.make_jaxpr(
            lambda x: rope.rotate_pairs(x, cos, sin))(x).jaxpr)
        return bool(call.params["interpret"])

    assert mode() is True
    monkeypatch.setattr(rope, "_interpret", lambda: False)
    assert mode() is False
    monkeypatch.undo()
    assert mode() is True


# -- a head's norm in the pass ---------------------------------------------------

EPS = 1e-6


def _normed_case(H, D, rotary, zero_centered, dtype, seed=0):
    """(x, the module's parameters, its effective scale, tables, g) at a
    scale away from its start, so that it shows."""
    kx, ks, kg = jax.random.split(jax.random.key(seed + H + D), 3)
    # (Interpreted, a call costs by its heads: the widest case has 16 rows.)
    S = 16 if H == 32 else 64
    x = (3 * jax.random.normal(kx, (B, S, H, D))).astype(dtype)
    scale = 0.3 * jax.random.normal(ks, (D,), jnp.float32)
    scale = scale if zero_centered else 1 + scale
    g = jax.random.normal(kg, (B, S, H, D)).astype(dtype)
    cos, sin = rope_freqs(D, S, 1e6, rotary_dim=rotary)
    return x, {"params": {"scale": scale}}, (
        1 + scale if zero_centered else scale), (cos, sin), g


def _module_then_jnp(x, params, tables, zero_centered):
    """Today's pair: the ``RMSNorm`` module over a head, ``_rotate_plain``."""
    normed = llama.RMSNorm(EPS, x.dtype, zero_centered).apply(params, x)
    return rope._rotate_plain(normed, *tables)


NORMED_SHAPES = pytest.mark.parametrize(
    "H, D, rotary, zero_centered",
    [(32, 128, None, False), (4, 128, None, False), (2, 256, 64, True)],
    ids=["32x128", "4x128", "2x256-zero-centred-a-quarter-turns"])


@NORMED_SHAPES
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_normed_pass_is_the_module_then_the_jnp_rotation(
        dtype, H, D, rotary, zero_centered):
    """The pass norms in float32, rounds to the dtype and turns, as the
    module and ``_rotate_plain`` do one after the other; only the order of
    a head's D-term float32 sum is its own.  So in bf16 the two are equal
    in more than 99.9 % of entries and never more than one bf16 unit apart;
    in float32 within a few units of the last place of the head's largest
    entry (2^-21 of it: the sum's rounding moves ``rsqrt`` by a unit or two,
    the rotation's two products one more)."""
    x, params, scale, tables, _ = _normed_case(H, D, rotary, zero_centered,
                                               dtype)
    before = _taken(rope.NORMED)
    got = jax.jit(lambda x: in_place(x, *tables, scale=scale, eps=EPS))(x)
    assert _taken(rope.NORMED) == before + 1
    want = jax.jit(lambda x: _module_then_jnp(x, params, tables,
                                              zero_centered))(x)
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    # ``rotate``'s own jnp body is the same arithmetic as the module's.
    np.testing.assert_array_equal(_bits(want), _bits(jax.jit(
        lambda x: apply_rope(x, *tables, scale=scale, eps=EPS))(x)))
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    largest = np.abs(want).max(axis=-1, keepdims=True)
    if dtype == jnp.bfloat16:
        assert (got == want).mean() > 0.999
        assert np.all(np.abs(got - want) <= 2.0 ** -7 * largest)
    else:
        assert np.all(np.abs(got - want) <= 2.0 ** -21 * largest)
    if rotary is not None and dtype == jnp.bfloat16:    # still lanes: the norm
        still = np.asarray(llama.RMSNorm(EPS, dtype, zero_centered).apply(
            params, x), np.float32)[..., rotary:]
        assert (got[..., rotary:] == still).mean() > 0.999


@NORMED_SHAPES
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_normed_pass_transposes_as_autodiff_does(dtype, H, D, rotary,
                                                     zero_centered):
    """dx and the scale's gradient from the backward call (the norm made
    again from the kept x) against ``jax.vjp`` of the jnp body.  The scale's
    gradient is a float32 sum over B * S * H = up to 1024 products a lane
    here (2 x 8192 x 32 in the cell), taken in another order: both orders
    are within ``terms * 2^-24`` of the exact sum relative to the sum of the
    products' magnitudes, and in practice (random signs) within its square
    root, so 1e-5 of the largest entry holds in float32.  In bf16 the two
    round the cotangent between rotation and norm to the dtype and differ in
    a few entries by one unit of it (2^-8), which moves a lane's sum by up
    to 1e-3 of its largest entry; dx, itself rounded to bf16, is one unit
    apart at most and equal nearly everywhere."""
    x, _, scale, tables, g = _normed_case(H, D, rotary, zero_centered, dtype,
                                          seed=1)

    def transposed(rotate):
        def run(x, scale, g):
            _, vjp = jax.vjp(lambda x, scale: rotate(
                x, *tables, scale=scale, eps=EPS), x, scale)
            return vjp(g)
        return jax.jit(run)(x, scale, g)

    (dx, ds), (dx_want, ds_want) = transposed(in_place), transposed(apply_rope)
    assert dx.dtype == dtype and ds.dtype == jnp.float32
    assert ds.shape == (D,)
    dx, dx_want = (np.asarray(t, np.float32) for t in (dx, dx_want))
    largest = np.abs(dx_want).max(axis=-1, keepdims=True)
    if dtype == jnp.bfloat16:
        assert (dx == dx_want).mean() > 0.999
        assert np.all(np.abs(dx - dx_want) <= 2.0 ** -7 * largest)
    else:
        assert np.all(np.abs(dx - dx_want) <= 2.0 ** -20 * largest)
    limit = 1e-3 if dtype == jnp.bfloat16 else 1e-5
    assert np.abs(np.asarray(ds) - np.asarray(ds_want)).max() <= (
        limit * np.abs(np.asarray(ds_want)).max())


@pytest.mark.parametrize("shape, where, reason", [
    ((B, S, 2, 128), "in place", rope.NORMED),
    ((B, S, 1, 256), "in place", rope.NORMED),
    ((B, S, 2, 128), "plain", rope.NOT_IN_PLACE),
    ((B, S, 2, 64), "in place", rope.OFF_TILING),
    ((B, 1, 2, 128), "in place", rope.OFF_TILING),
    ((B, S, 2, 128), "no tables", rope.NO_TABLES)], ids=[
        "128", "256", "not-in-place", "a-head-of-64", "decodes-one-token",
        "no-tables"])
def test_a_normed_trace_says_which_body_it_took(shape, where, reason):
    x = jax.random.normal(jax.random.key(0), shape, jnp.bfloat16)
    scale = jnp.full((shape[-1],), 1.5, jnp.float32)
    tables = (None, None) if where == "no tables" else rope_freqs(
        shape[-1], shape[1], 1e4)
    before = _taken(reason)
    closed = jax.make_jaxpr(lambda x: rope.rotate(
        x, *tables, where != "plain", scale, EPS))(x)
    assert _taken(reason) == before + 1
    assert len(_pallas_calls(closed.jaxpr)) == (reason == rope.NORMED)
    if reason != rope.NORMED:       # the jnp body, whatever kept the pass
        want = rope._norm_plain(x, scale, EPS)
        if tables[0] is not None:
            want = rope._rotate_plain(want, *tables)
        np.testing.assert_array_equal(_bits(want), _bits(rope.rotate(
            x, *tables, where != "plain", scale, EPS)))


@pytest.mark.parametrize("over", ["head", "all"])
def test_the_model_norms_a_head_in_the_pass_and_a_token_apart(over):
    """With the flash seam a per-head QK-norm is ONE Mosaic call a tensor,
    forward and backward, under ``hvd.rope`` inside ``hvd.attn.qknorm``; a
    norm over all of a token's heads stays the module's, the rotation
    behind it the plain pass, and the trace says so.  The parameters are
    where they were."""
    config = llama.LlamaConfig(
        vocab_size=64, hidden_size=256, num_layers=1, num_heads=2,
        num_kv_heads=1, intermediate_size=128, max_seq_len=128,
        qk_norm=True, qk_norm_over=over, **(
            dict(norm_placement="post") if over == "all" else {}))
    model = llama.LlamaModel(config, attention_fn=flash_attention_fn)
    tokens = jnp.zeros((B, 128), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)
    attn = params["params"]["layer_0"]["attn"]
    width = {"head": (128,), "all": (256,)}[over]
    assert attn["q_norm"]["scale"].shape == width
    assert attn["k_norm"]["scale"].shape == (width[0] // 2,) if (
        over == "all") else width
    reasons = (rope.NORMED, rope.IN_PLACE, rope.NORM_OVER_ALL)
    before = [_taken(reason) for reason in reasons]
    closed = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
        model.apply(p, tokens).astype(jnp.float32))))(params)
    after = [_taken(reason) - b for reason, b in zip(reasons, before)]
    assert after == ([2, 0, 0] if over == "head" else [0, 2, 2])
    calls = [c for c in _pallas_calls(closed.jaxpr)
             if scopes.ROPE in str(c.source_info.name_stack)]
    assert len(calls) == 4          # q and k, forward and backward
    for call in calls:
        stack = str(call.source_info.name_stack)
        assert stack.index(scopes.BLOCK_ATTN) < stack.index(
            scopes.QK_NORM) < stack.index(scopes.ROPE)
    operands = sorted(len(call.invars) for call in calls)
    assert operands == ([4, 4, 5, 5] if over == "head" else [3, 3, 3, 3])
    if over == "head":
        # No float32 array of q's or k's size with the heads apart.
        for eqn in closed.jaxpr.eqns:
            for v in eqn.outvars:
                assert not (v.aval.dtype == jnp.float32 and v.aval.shape in (
                    (B, 128, 2, 128), (B, 128, 1, 128))), eqn
