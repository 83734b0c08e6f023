"""``ops/gated_norm.py``: a Mamba-2 layer's skip, gate and grouped RMS norm.
The Mosaic pair (``skip_gate_norm``, interpreted here) against the ``jnp``
body, which is what ``models/llama.py::Mamba2`` held before the module (the
skip rounded to u's dtype, then ``_gate_then_norm`` under a checkpoint) and
what the entry still runs wherever its rule refuses the pair.

The pair rounds once where the ``jnp`` body rounds twice (t = y + D u to
bf16, then the result), so in float32 the two agree to rounding, and in bf16
the pair is held to one unit in the last place of the ``jnp`` body's FLOAT32
values on the same operands."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from horovod_tpu.ops import gated_norm as gn

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
    """The result and the five gradients of ``f(y, u, z, d, w)``, jitted."""
    def run(y, u, z, d, w, go):
        out, vjp = jax.vjp(f, y, u, z, d, w)
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


@pytest.mark.parametrize("shape, groups, in_place, why", [
    ((2, 8192, 4096), 8, True, gn.NO_TPU),             # (on a TPU: None)
    ((2, 256, 1024), 2, True, gn.NO_TPU),
    ((2, 8192, 4096), 8, False, gn.NOT_IN_PLACE),
    ((2, 8192, 4000), 8, True, gn.OFF_THE_LANE_TILE),
    ((2, 8192, 1024), 16, True, gn.GROUP_OFF_THE_TILE),   # groups of 64
    ((2, 8192, 1024), 3, True, gn.GROUP_OFF_THE_TILE),
    ((2, 8200, 4096), 8, True, gn.NO_ROW_BLOCK),
], ids=["the cell's", "a cut shape", "not in place", "off the lane tile",
        "groups of half a tile", "groups that do not divide",
        "no block of rows"])
def test_the_rule_reads_the_shape_and_the_callers_word(shape, groups,
                                                       in_place, why,
                                                       monkeypatch):
    assert gn._why_not(shape, groups, in_place) == why
    monkeypatch.setattr(gn, "_interpret", lambda: False)
    assert gn._why_not(shape, groups, in_place) == (
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
