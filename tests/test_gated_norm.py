"""``ops/gated_norm.py``: a Mamba-2 layer's skip, gate and grouped RMS norm,
and a gated delta-rule layer's output norm and gate (the norm FIRST: the
second half of this file).
The Mosaic pair (``skip_gate_norm``, interpreted here) against the ``jnp``
body, which is what ``models/llama.py::Mamba2`` held before the module (the
skip rounded to u's dtype, then ``_gate_then_norm`` under a checkpoint) and
what the entry still runs wherever its rule refuses the pair.

The pair rounds once where the ``jnp`` body rounds twice (t = y + D u to
bf16, then the result), so in float32 the two agree to rounding, and in bf16
the pair is held to one unit in the last place of the ``jnp`` body's FLOAT32
values on the same operands."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from horovod_tpu.ops import gated_norm as gn
from horovod_tpu.ops.short_conv import over_heads

EPS = 1e-5
# (B, S, C, groups, heads), u and z wider than C as the layer hands them:
# groups of 512 lanes as the cell's, and a single group.
SHAPES = {"2 groups of 512": (2, 256, 1024, 2, 16),
          "one group": (2, 96, 384, 1, 6)}


def _f32(x):
    return np.asarray(jnp.asarray(x, jnp.float32))


def _operands(shape, dtype):
    b, s, c, _, heads = shape
    ks = jax.random.split(jax.random.key(59), 6)

    def normal(k, width):
        return jax.random.normal(k, (b, s, width), jnp.float32).astype(dtype)

    return (normal(ks[0], c), normal(ks[1], c + 256), normal(ks[2], c + 384),
            1.0 + 0.3 * jax.random.normal(ks[3], (heads,)),
            1.0 + 0.3 * jax.random.normal(ks[4], (c,)), normal(ks[5], c))


def _both_ways(f):
    """The result and every gradient of ``f(*operands)``, jitted: called
    with the operands and the cotangent."""
    def run(*operands_and_go):
        *operands, go = operands_and_go
        out, vjp = jax.vjp(f, *operands)
        return (out, *vjp(go.astype(out.dtype)))
    return jax.jit(run)


def _former(y, u, z, d, w, groups):
    """``Mamba2.__call__`` between the scan and ``out_proj`` as it stood
    before the module: u and z cut out, y and u a head ``[B, S, H, P]``."""
    @jax.checkpoint
    def gate_then_norm(y, z, scale):
        y = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
        grouped = y.reshape(*y.shape[:-1], groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True) + EPS)
        return (grouped.reshape(y.shape) * scale).astype(z.dtype)

    b, s, c = y.shape
    u, z = u[..., :c], z[..., :c]
    heads = (b, s, d.shape[0], -1)
    y = (y.reshape(heads).astype(jnp.float32) + d[:, None] * u.reshape(
        heads).astype(jnp.float32)).astype(u.dtype)
    return gate_then_norm(y.reshape(b, s, c), z, w)


NAMES = ("out", "dy", "du", "dz", "dd", "dw")


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_pair_gives_the_jnp_bodys_values_and_gradients(dtype, shape):
    c, groups = shape[2], shape[3]
    args = _operands(shape, dtype)
    assert gn._pick_rows(shape[1]) in (32, 256)
    got = _both_ways(lambda *a: gn.skip_gate_norm(*a, groups, EPS))(*args)
    # In float32 the jnp body itself; in bf16 its float32 values.
    want = _both_ways(lambda *a: gn.gated_norm(*a, groups, EPS, False))(
        *(x.astype(jnp.float32) for x in args))
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape, name
        assert a.dtype == (dtype if a.ndim == 3 else jnp.float32), name
        a, b = _f32(a), _f32(b)
        size = np.abs(b).max()
        if dtype == jnp.float32 or a.ndim == 1:
            # (dd and dw are float32 sums of float32 values either way.)
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * max(
                size, 1.0), err_msg=name)
        else:
            # A unit in the last place of a bfloat16 value is up to 2^-7.
            assert np.all(np.abs(a - b) <= 2.0 ** -7 * np.abs(b)
                          + 1e-6 * size), name
    # Nothing comes back to the channels behind the first C of u and z.
    assert not np.any(_f32(got[2])[..., c:]) and np.any(_f32(got[2]))
    assert not np.any(_f32(got[3])[..., c:]) and np.any(_f32(got[3]))


def test_in_bf16_the_pair_is_nearer_float32_than_the_jnp_body():
    """One rounding for two: the pair's result and its cotangents of y, u
    and z are no further from the float32 values than the ``jnp`` body's."""
    shape = SHAPES["2 groups of 512"]
    args = _operands(shape, jnp.bfloat16)
    pair = _both_ways(lambda *a: gn.skip_gate_norm(*a, shape[3], EPS))(*args)
    plain = _both_ways(lambda *a: gn.gated_norm(*a, shape[3], EPS, False))
    exact = plain(*(x.astype(jnp.float32) for x in args))
    for name, a, b, want in zip(NAMES[:4], pair, plain(*args), exact):
        off = [float(np.linalg.norm(_f32(x) - _f32(want))) for x in (a, b)]
        assert off[0] <= off[1] * 1.01, (name, off)


@pytest.mark.parametrize("shape, groups, in_place, why, norm_first", [
    ((2, 8192, 4096), 8, True, gn.NO_TPU, False),      # (on a TPU: None)
    ((2, 256, 1024), 2, True, gn.NO_TPU, False),
    ((2, 8192, 4096), 8, False, gn.NOT_IN_PLACE, False),
    ((2, 8192, 4000), 8, True, gn.OFF_THE_LANE_TILE, False),
    ((2, 8192, 1024), 16, True, gn.GROUP_OFF_THE_TILE, False),  # of 64
    ((2, 8192, 1024), 3, True, gn.GROUP_OFF_THE_TILE, False),
    ((2, 8200, 4096), 8, True, gn.NO_ROW_BLOCK, False),
    # The norm first, a head a group: heads of 128 (qwen3-next's 32), of
    # 192 (the hybrid cell's 30: two are three lane tiles), of 96 (four are
    # three; where four do not divide the heads the channels are no whole
    # tiles either), of 160 (four are 640 lanes) and of 1024: wider than a
    # step.
    ((2, 8192, 4096), 32, True, gn.NO_TPU, True),
    ((1, 8192, 5760), 30, True, gn.NO_TPU, True),
    ((1, 8192, 3072), 32, True, gn.NO_TPU, True),
    ((1, 8192, 2880), 30, True, gn.OFF_THE_LANE_TILE, True),
    ((1, 8192, 640), 4, True, gn.HEADS_OFF_THE_TILE, True),
    ((1, 8192, 4096), 4, True, gn.HEADS_OFF_THE_TILE, True),
    ((1, 8192, 5760), 30, False, gn.NOT_IN_PLACE, True),
    ((1, 8200, 5760), 30, True, gn.NO_ROW_BLOCK, True),
], ids=["the cell's", "a cut shape", "not in place", "off the lane tile",
        "groups of half a tile", "groups that do not divide",
        "no block of rows", "norm first: heads of 128",
        "norm first: heads of 192", "norm first: 32 heads of 96",
        "norm first: 30 heads of 96", "norm first: heads of 160",
        "norm first: heads of 1024", "norm first: not in place",
        "norm first: no block of rows"])
def test_the_rule_reads_the_shape_and_the_callers_word(shape, groups,
                                                       in_place, why,
                                                       norm_first,
                                                       monkeypatch):
    assert gn._why_not(shape, groups, in_place, norm_first) == why
    monkeypatch.setattr(gn, "_interpret", lambda: False)
    assert gn._why_not(shape, groups, in_place, norm_first) == (
        None if why == gn.NO_TPU else why)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_off_the_tpu_the_entry_is_the_former_layers_code_bit_for_bit(dtype):
    """A shape the pair takes, in place, on the CPU: the ``jnp`` body, for
    ``"no TPU"``, and result and gradients are those of the code
    ``Mamba2`` held before the module, to the bit."""
    shape = SHAPES["2 groups of 512"]
    args = _operands(shape, dtype)
    before = gn.body_counts()
    got = _both_ways(lambda *a: gn.gated_norm(*a, shape[3], EPS, True))(*args)
    after = gn.body_counts()
    assert after["mosaic"] == before["mosaic"]
    assert after["plain"][gn.NO_TPU] == before["plain"].get(gn.NO_TPU, 0) + 1
    want = _both_ways(lambda *a: _former(*a, shape[3]))(*args)
    for name, a, b in zip(NAMES, got, want):
        np.testing.assert_array_equal(_f32(a), _f32(b), err_msg=name)


def _equations(jaxpr, name):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_equations(sub, name))
    return found


@pytest.mark.parametrize("way", ["mosaic", gn.NOT_IN_PLACE,
                                 gn.GROUP_OFF_THE_TILE])
def test_the_entry_counts_the_body_it_took(way, monkeypatch):
    """Where the rule allows it (its last reason lifted: a TPU's answer)
    the entry under ``jax.grad`` is two Mosaic calls, one each way, on u and
    z as wide as they came; elsewhere none.  ``body_counts()`` says which
    way the ONE traced call went."""
    rule = gn._why_not
    monkeypatch.setattr(gn, "_why_not", lambda *a: (
        None if rule(*a) == gn.NO_TPU else rule(*a)))
    shape = SHAPES["2 groups of 512"]
    groups = 16 if way == gn.GROUP_OFF_THE_TILE else shape[3]
    args = _operands(shape, jnp.bfloat16)[:5]
    before = gn.body_counts()
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(gn.gated_norm(
        *a, groups, EPS, way != gn.NOT_IN_PLACE).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4)))(*args).jaxpr
    after = gn.body_counts()
    moved = {"mosaic": after["mosaic"] - before["mosaic"], **{
        why: n - before["plain"].get(why, 0)
        for why, n in after["plain"].items()}}
    assert {why: n for why, n in moved.items() if n} == {way: 1}
    calls = _equations(jaxpr, "pallas_call")
    assert len(calls) == (2 if way == "mosaic" else 0)
    for call in calls:
        widths = [v.aval.shape[-1] for v in call.invars[:3]]
        assert widths == [shape[2], shape[2] + 256, shape[2] + 384]


# -- the norm FIRST, then the gate: a gated delta-rule layer's ---------------

# (B, S, C, heads): four heads of 128 lanes a step, two of 192 (a lane tile
# shared under a mask), an ODD number of such pairs, and four of 96.
HEAD_SHAPES = {"4 heads of 128": (2, 256, 512, 4),
               "4 heads of 192": (1, 128, 768, 4),
               "6 heads of 192": (1, 64, 1152, 6),
               "8 heads of 96": (1, 32, 768, 8)}
HEAD_NAMES = ("out", "do", "dz", "dw")


def _head_operands(shape, dtype):
    b, s, c, heads = shape
    ks = jax.random.split(jax.random.key(61), 4)
    o, z, go = (jax.random.normal(k, (b, s, c), jnp.float32).astype(dtype)
                for k in ks[:3])
    return o, z, 1.0 + 0.3 * jax.random.normal(ks[3], (c // heads,)), go


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _former_gated_norm(o, z, scale, heads, eps):
    """``models/llama.py::_gated_norm`` as ``GatedDeltaNet`` held it before
    the module took it."""
    o = o.astype(jnp.float32)
    squares, spread = over_heads(o * o, heads)
    o = o * spread(jax.lax.rsqrt(squares * (heads / o.shape[-1]) + eps))
    return (o * jnp.tile(scale, heads) * nn.silu(z.astype(jnp.float32))
            ).astype(z.dtype)


@pytest.mark.parametrize("shape", HEAD_SHAPES.values(), ids=HEAD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_norm_first_pair_gives_the_jnp_bodys_values_and_gradients(dtype,
                                                                      shape):
    heads = shape[3]
    per = shape[2] // heads
    assert gn._heads_a_step(heads, per) * per in (384, 512)
    args = _head_operands(shape, dtype)
    got = _both_ways(lambda *a: gn.norm_gate(*a, heads, EPS))(*args)
    # In float32 the jnp body itself; in bf16 its float32 values.
    want = _both_ways(lambda *a: _former_gated_norm(*a, heads, EPS))(
        *(x.astype(jnp.float32) for x in args))
    for name, a, b in zip(HEAD_NAMES, got, want):
        assert a.shape == b.shape, name
        assert a.dtype == (dtype if a.ndim == 3 else jnp.float32), name
        a, b = _f32(a), _f32(b)
        size = np.abs(b).max()
        if dtype == jnp.float32 or a.ndim == 1:
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * max(
                size, 1.0), err_msg=name)
        else:
            assert np.all(np.abs(a - b) <= 2.0 ** -7 * np.abs(b)
                          + 1e-6 * size), name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_off_the_tpu_the_norm_first_entry_is_the_former_code_bit_for_bit(
        dtype):
    """A shape the pair takes, in place, on the CPU: ``_norm_then_gate``,
    for ``"no TPU"``, and result and gradients are those of
    ``models/llama.py::_gated_norm`` as it stood, to the bit."""
    shape = HEAD_SHAPES["4 heads of 192"]
    args = _head_operands(shape, dtype)
    before = gn.body_counts()
    got = _both_ways(lambda *a: gn.norm_gated(*a, shape[3], EPS, True))(*args)
    after = gn.body_counts()
    assert after["mosaic"] == before["mosaic"]
    assert after["plain"][gn.NO_TPU] == before["plain"].get(gn.NO_TPU, 0) + 1
    want = _both_ways(lambda *a: _former_gated_norm(*a, shape[3], EPS))(*args)
    for name, a, b in zip(HEAD_NAMES, got, want):
        np.testing.assert_array_equal(_f32(a), _f32(b), err_msg=name)


@pytest.mark.parametrize("way", ["mosaic", gn.NOT_IN_PLACE,
                                 gn.HEADS_OFF_THE_TILE])
def test_the_norm_first_entry_counts_the_body_it_took(way, monkeypatch):
    """Where the rule allows it (its last reason lifted: a TPU's answer)
    the entry under ``jax.grad`` is two Mosaic calls, one each way (o, z
    and the weight on every head's lanes in; with the cotangent, ``do``,
    ``dz`` and dw's eight partial sums a lane out); elsewhere none.
    ``body_counts()`` says which way the ONE traced call went, under the
    gate-first calls' kind."""
    rule = gn._why_not
    monkeypatch.setattr(gn, "_why_not", lambda *a, **k: (
        None if rule(*a, **k) == gn.NO_TPU else rule(*a, **k)))
    b, s, c, heads = HEAD_SHAPES["4 heads of 192"]
    if way == gn.HEADS_OFF_THE_TILE:
        heads = 1                       # 768 lanes a head: over a step's
        assert gn._heads_a_step(heads, c // heads) == 0
    o, z, w, _ = _head_operands((b, s, c, heads), jnp.bfloat16)
    before = gn.body_counts()
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(gn.norm_gated(
        *a, heads, EPS, way != gn.NOT_IN_PLACE).astype(jnp.float32)),
        argnums=(0, 1, 2)))(o, z, w).jaxpr
    after = gn.body_counts()
    moved = {"mosaic": after["mosaic"] - before["mosaic"], **{
        why: n - before["plain"].get(why, 0)
        for why, n in after["plain"].items()}}
    assert {why: n for why, n in moved.items() if n} == {way: 1}
    calls = _equations(jaxpr, "pallas_call")
    assert len(calls) == (2 if way == "mosaic" else 0)
    for call, (ins, outs) in zip(calls, ((3, 1), (4, 3))):
        assert len(call.invars) == ins and len(call.outvars) == outs
        assert call.invars[-1].aval.shape == (1, c)      # the tiled weight
    if calls:
        assert calls[1].outvars[-1].aval.shape == (b, 8, c)
        assert calls[1].outvars[-1].aval.dtype == jnp.float32


@pytest.mark.parametrize("shape, heads, chunk, reads", [
    ((2, 8192, 4096), 32, 64, True),      # qwen3-next's: four heads a step
    ((2, 8192, 4096), 16, 64, True),      # heads of 256: two a step
    ((1, 8192, 5760), 30, 64, False),     # 192 lanes pad to 256 there
    ((2, 8192, 4096), 32, 0, False),      # not the rule's result
    ((2, 8192, 4096), 32, 32, False),     # a step's rows are two chunks
    ((2, 8192, 128), 1, 64, False),       # ... and here a quarter of one
], ids=["heads of 128", "heads of 256", "heads of 192", "no chunks",
        "chunks of 32", "256 rows a step"])
def test_the_rules_chunks_are_read_where_a_head_is_whole_tiles(shape, heads,
                                                               chunk, reads):
    assert gn._rule_chunks(shape, heads, chunk) == reads


@pytest.mark.parametrize("shape", [(2, 256, 512, 4), (2, 128, 1024, 4)],
                         ids=["4 heads of 128", "4 heads of 256"])
def test_read_where_the_rule_left_it_the_pair_gives_the_same_bits(shape):
    """``chunk=64``: o is handed to the calls ``[N, B, H, 64, d_v]`` (the
    inverse of the rule's last transposition, which XLA cancels against it
    in a step) and ``do`` leaves the same way; the arithmetic is the rows'
    own, so every result is equal to the bit."""
    heads = shape[3]
    b, s, c = shape[:3]
    assert gn._rule_chunks(shape[:3], heads, 64)
    args = _head_operands(shape, jnp.bfloat16)
    rows = _both_ways(lambda *a: gn.norm_gate(*a, heads, EPS))(*args)
    left = _both_ways(lambda *a: gn.norm_gate(*a, heads, EPS, 64))(*args)
    for name, a, b_ in zip(HEAD_NAMES, left, rows):
        np.testing.assert_array_equal(_f32(a), _f32(b_), err_msg=name)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(gn.norm_gate(
        *a, heads, EPS, 64).astype(jnp.float32)), argnums=(0, 1, 2)))(
            *args[:3]).jaxpr
    forward, backward = _equations(jaxpr, "pallas_call")
    chunks = (s // 64, b, heads, 64, c // heads)
    assert forward.invars[0].aval.shape == chunks
    assert backward.invars[0].aval.shape == chunks
    assert backward.outvars[0].aval.shape == chunks      # do, as o lies
    assert backward.outvars[1].aval.shape == (b, s, c)   # dz, as rows
