"""``ops/grouped_matmul.py`` alone, on the CPU: its Mosaic body (megablox's
calls, interpreted) against ``jax.lax.ragged_dot`` forward and backward, at
widths off the lane tile, uneven and empty groups and rows past the last one;
the tiles it states; and the reasons its rule gives.  The routed layer through
it is in ``tests/test_nemotron_h.py``, its lowering for the v5e in
``tests/test_nemotron_h_v5e_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import grouped_matmul as gm

# (rows, k, n, sizes): widths off the lane tile; groups uneven, one empty,
# and the rows past their sum no group's.
CASES = {
    "2 groups, 72 wide": (512, 64, 72, (100, 57)),
    "an empty group": (256, 72, 200, (90, 0, 131)),
    "every row held": (256, 200, 64, (128, 128)),
    "no row held": (128, 64, 72, (0, 0)),
    "one tile a group": (96, 40, 136, (33, 30, 20)),
}


def _operands(case, dtype):
    m, k, n, sizes = CASES[case]
    k_rows, k_w, k_g = jax.random.split(jax.random.key(len(case)), 3)
    live = (jnp.arange(m) < sum(sizes))[:, None]
    rows = jnp.where(live, jax.random.normal(k_rows, (m, k)), 0)
    w = jax.random.normal(k_w, (len(sizes), k, n)) * k ** -0.5
    g = jnp.where(live, jax.random.normal(k_g, (m, n)), 0)
    return (rows.astype(dtype), w.astype(dtype),
            jnp.asarray(sizes, jnp.int32), g.astype(dtype), live)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_the_mosaic_body_is_ragged_dot_forward_and_backward(case, dtype):
    rows, w, sizes, g, live = _operands(case, dtype)

    def both(product):
        out, back = jax.vjp(lambda rows, w: product(rows, w, sizes), rows, w)
        d_rows, d_w = back(g)
        # What the rows past the last group read is undefined, either way.
        return jnp.where(live, out, 0), jnp.where(live, d_rows, 0), d_w

    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda: both(gm._mosaic))()
        want = jax.jit(lambda: both(jax.lax.ragged_dot))()
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for name, a, b in zip(("out", "d_rows", "d_w"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype == dtype, name
        np.testing.assert_allclose(a.astype(jnp.float32),
                                   b.astype(jnp.float32), rtol=tol, atol=tol,
                                   err_msg=name)


@pytest.mark.parametrize("shape, whole, tiles", [
    # the nemotron cell's six products (bf16, buffers of 12,288 rows)
    ((12288, 2688, 1856, 2), "k", (256, 2688, 384)),
    ((12288, 1856, 2688, 2), "k", (256, 1856, 384)),
    ((12288, 2688, 1856, 2), "n", (256, 384, 1856)),
    ((12288, 1856, 2688, 2), "n", (256, 384, 2688)),
    # narrower than a slice: the width itself
    ((512, 64, 72, 4), "k", (256, 64, 72)),
    ((192, 64, 144, 4), "n", (64, 64, 144)),
    # too wide to hold whole within the budget of VMEM
    ((12288, 8192, 3712, 2), "k", (256, 1024, 1024)),
])
def test_the_tiles_it_states(shape, whole, tiles):
    assert gm._tiles(*shape, whole) == tiles
    tm, tk, tn = tiles
    m, k, n, itemsize = shape
    assert m % tm == 0
    for tile, width in ((tk, k), (tn, n)):
        assert tile == width or tile % gm.LANES == 0
    blocks = 2 * itemsize * (tm * tk + tk * tn + tm * tn)
    assert blocks + 4 * (tm if whole == "k" else tk) * tn <= gm._VMEM_BUDGET


@pytest.mark.parametrize("shape, in_place, why", [
    ((12288, 2688, 1856), True, gm.NO_TPU),        # (on a TPU: None)
    ((12288, 1856, 2688), True, gm.NO_TPU),
    ((12288, 2688, 1856), False, gm.NOT_IN_PLACE),
    ((12288, 2048, 1536), True, gm.WHOLE_TILES),
    ((12288, 2688, 1920), True, gm.WHOLE_TILES),
    ((4100, 2688, 1856), True, gm._NO_ROW_TILE),
])
def test_the_rule_reads_the_shape_and_the_callers_word(shape, in_place, why,
                                                       monkeypatch):
    assert gm._why_not(*shape, in_place) == why
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    assert gm._why_not(*shape, in_place) == (None if why == gm.NO_TPU
                                             else why)


def test_off_the_tpu_the_entry_is_ragged_dot_and_says_so():
    rows, w, sizes, _, live = _operands("2 groups, 72 wide", jnp.float32)
    before = gm.body_counts()
    got = gm.grouped_matmul(rows, w, sizes, in_place=True)
    after = gm.body_counts()
    assert after["mosaic"] == before["mosaic"]
    assert after["xla"][gm.NO_TPU] == before["xla"].get(gm.NO_TPU, 0) + 1
    np.testing.assert_array_equal(
        jnp.where(live, got, 0),
        jnp.where(live, jax.lax.ragged_dot(rows, w, sizes), 0))
