"""``ops/grouped_matmul.py`` alone, on the CPU: its Mosaic body (megablox's
calls, interpreted) against ``jax.lax.ragged_dot`` forward and backward, at
widths off the lane tile and on it, uneven and empty groups and rows past the
last one; the tiles it states; the reasons its rule gives at every routed
cell's own calls; and what a start pays for the kernels, counted.  The routed
layer through it is in ``tests/test_nemotron_h.py``, its lowering for the v5e
in ``tests/test_nemotron_h_v5e_compile.py`` and
``tests/test_lfm2_v5e_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from horovod_tpu.models import llama
from horovod_tpu.ops import grouped_matmul as gm
from tiny_sizes import TINY

# (rows, k, n, sizes): widths off the lane tile; groups uneven, one empty,
# and the rows past their sum no group's.
CASES = {
    "2 groups, 72 wide": (512, 64, 72, (100, 57)),
    "an empty group": (256, 72, 200, (90, 0, 131)),
    "every row held": (256, 200, 64, (128, 128)),
    "no row held": (128, 64, 72, (0, 0)),
    "one tile a group": (96, 40, 136, (33, 30, 20)),
    # whole lane tiles, as seven of the eight routed cells': a gated expert's
    # first product, 2F = 512 wide (one slice), and its second, F deep
    "whole tiles, a gated first product": (512, 128, 512, (200, 0, 57, 131)),
    "whole tiles, the second product": (512, 256, 128, (200, 0, 57, 131)),
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


# (m, H, the first product's n, F, held groups) of each routed cell's
# buffer, as its step is lowered: ``w_gate_up [g, H, 2F]`` (nemotron's
# ``w_up [g, H, F]``) and ``w_down [g, F, H]``.
CELLS = {
    "deepseek-v2-lite": (24576, 2048, 2816, 1408, 8),
    "lfm2-24b-a2b": (32768, 2048, 3072, 1536, 16),
    "smallthinker-21b-a3b": (49152, 2560, 1536, 768, 16),
    "keye-vl-2.0-30b-a3b": (32768, 2048, 1536, 768, 16),
    "xing4.0-29b-a4b": (8192, 3584, 2048, 1024, 8),
    "laguna-s-2.1": (5120, 3072, 2048, 1024, 8),
    "qwen3-next-80b-a3b": (20480, 2048, 1024, 512, 32),
    "nemotron-3-nano-30b-a3b": (12288, 2688, 1856, 1856, 8),
}
# The six products of a cell's layer, in the order a step meets them: the
# two forward, their rows' gradients, their matrices' gradients.
SIX = {
    "deepseek-v2-lite": [
        (256, 2048, 512), (256, 1408, 512), (256, 2048, 512),
        (256, 2816, 512), (256, 1024, 1024), (256, 512, 2048)],
    "lfm2-24b-a2b": [
        (256, 2048, 512), (256, 1536, 512), (256, 2048, 512),
        (256, 3072, 512), (256, 1024, 1024), (256, 512, 2048)],
    "smallthinker-21b-a3b": [
        (256, 2560, 512), (256, 768, 512), (256, 2560, 384),
        (256, 1536, 512), (256, 512, 1536), (256, 384, 2560)],
    "keye-vl-2.0-30b-a3b": [
        (256, 2048, 512), (256, 768, 512), (256, 2048, 384),
        (256, 1536, 512), (256, 512, 1536), (256, 384, 2048)],
    "xing4.0-29b-a4b": [
        (256, 3584, 512), (256, 1024, 512), (256, 3584, 512),
        (256, 2048, 512), (256, 512, 2048), (256, 1024, 1024)],
    "laguna-s-2.1": [
        (256, 3072, 512), (256, 1024, 512), (256, 3072, 512),
        (256, 2048, 512), (256, 512, 2048), (256, 1024, 1024)],
    "qwen3-next-80b-a3b": [
        (256, 2048, 512), (256, 512, 512), (256, 2048, 512),
        (256, 1024, 512), (256, 512, 1024), (256, 512, 2048)],
    "nemotron-3-nano-30b-a3b": [
        (256, 2688, 384), (256, 1856, 384), (256, 2688, 384),
        (256, 1856, 384), (256, 384, 1856), (256, 384, 2688)],
}


def _six(cell):
    """``(shape, whole)`` of ``_tiles`` for the six products of a cell."""
    m, hidden, wide, deep, _ = CELLS[cell]
    up, down = (m, hidden, wide, 2), (m, deep, hidden, 2)
    # (the rows' gradients contract the result's width)
    return [(up, "k"), (down, "k"), ((m, hidden, deep, 2), "k"),
            ((m, wide, hidden, 2), "k"), (up, "n"), (down, "n")]


@pytest.mark.parametrize("shape, whole, tiles", [
    *[(shape, whole, tiles) for cell in CELLS
      for (shape, whole), tiles in zip(_six(cell), SIX[cell])],
    # narrower than a slice: the width itself
    ((512, 64, 72, 4), "k", (256, 64, 72)),
    ((192, 64, 144, 4), "n", (64, 64, 144)),
    ((512, 64, 448, 4), "k", (256, 64, 448)),
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


def test_one_tile_of_rows_serves_every_product_of_every_cell():
    """So a start traces the kernels' group metadata twice, for ``gmm`` and
    for ``tgmm``, whatever the cell."""
    assert {tiles[0] for cell in SIX for tiles in SIX[cell]} == {256}


def _body_of(m, k, n, groups, in_place):
    """Which body a trace of the entry on ``[m, k] x [groups, k, n]`` (bf16,
    shapes alone: nothing runs) takes: ``"mosaic"`` or XLA's reason."""
    before = gm.body_counts()
    jax.eval_shape(
        lambda rows, w, sizes: gm.grouped_matmul(rows, w, sizes, in_place),
        jax.ShapeDtypeStruct((m, k), jnp.bfloat16),
        jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16),
        jax.ShapeDtypeStruct((groups,), jnp.int32))
    after = gm.body_counts()
    moved = {"mosaic": after["mosaic"] - before["mosaic"], **{
        why: n - before["xla"].get(why, 0) for why, n in after["xla"].items()}}
    (body,) = [why for why, n in moved.items() if n]
    assert moved[body] == 1
    return body


@pytest.mark.parametrize("product", ["first", "second"])
@pytest.mark.parametrize("cell", CELLS)
def test_the_rule_at_every_routed_cells_own_calls(cell, product,
                                                  monkeypatch):
    """Each routed cell's two calls ``[m, k] x [g, k, n]`` through the
    entry: in place and on a TPU every one takes the Mosaic body, the faster
    alone at that shape (PERF.md §5), at whole lane tiles (which
    ``WHOLE_TILES`` kept with XLA until PR 68) as off them, at 512 wide in
    32 groups of 320 rows as at 2816 wide in 8 of 1,536; elsewhere
    ``ragged_dot``, and the count says why."""
    m, hidden, wide, deep, groups = CELLS[cell]
    k, n = (hidden, wide) if product == "first" else (deep, hidden)
    assert _body_of(m, k, n, groups, True) == gm.NO_TPU
    assert _body_of(m, k, n, groups, False) == gm.NOT_IN_PLACE
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    assert _body_of(m, k, n, groups, True) == "mosaic"
    assert _body_of(m, k, n, groups, False) == gm.NOT_IN_PLACE


@pytest.mark.parametrize("shape, in_place, why", [
    ((12288, 2688, 1856, 8), True, gm.NO_TPU),        # (on a TPU: mosaic)
    ((12288, 1856, 2688, 8), True, gm.NO_TPU),
    ((12288, 2688, 1856, 8), False, gm.NOT_IN_PLACE),
    # whole lane tiles: XLA's until PR 68, by no measurement of theirs
    ((12288, 2048, 1536, 8), True, gm.NO_TPU),
    ((12288, 2688, 1920, 8), True, gm.NO_TPU),
    ((12288, 2048, 1536, 8), False, gm.NOT_IN_PLACE),
    # PR 57's 2048 x 512 at 768 rows a group, where XLA's won at slices of
    # 384: at 512 it does not (0.74 ms for 0.90, my chip run, PR 68)
    ((12288, 2048, 512, 8), True, gm.NO_TPU),
    ((4100, 2688, 1856, 8), True, gm._NO_ROW_TILE),
    ((4100, 2048, 1536, 8), True, gm._NO_ROW_TILE),
])
def test_the_rule_reads_the_shape_and_the_callers_word(shape, in_place, why,
                                                       monkeypatch):
    assert _body_of(*shape, in_place) == why
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    assert _body_of(*shape, in_place) == ("mosaic" if why == gm.NO_TPU
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


# -- what a start pays for the kernels ----------------------------------------

@pytest.mark.parametrize("visit_empty_groups", [False, True],
                         ids=["gmm's", "tgmm's"])
@pytest.mark.parametrize("groups, tm, tiles_m", [
    (1, 8, 1), (2, 8, 4), (4, 16, 6), (8, 32, 3), (3, 8, 8)])
def test_the_group_metadata_is_megabloxs_own(groups, tm, tiles_m,
                                             visit_empty_groups):
    """``_group_metadata`` against megablox's ``make_group_metadata``, array
    for array, on forty seeded draws a case: uneven groups, groups that
    start and end on a tile's edge and inside one, empty groups (at the
    front, in the middle, behind the last row), no row at all, every row,
    and a shard of the groups (``start_group`` 1)."""
    rng = np.random.default_rng(groups * 1000 + tm * 10 + tiles_m)
    m = tm * tiles_m
    for draw in range(40):
        live = int(rng.integers(0, m + 1)) if draw else m
        cuts = np.sort(rng.integers(0, live + 1, groups - 1))
        if draw % 3 == 0:
            cuts = cuts // tm * tm
        sizes = np.diff(np.concatenate([[0], cuts, [live]])).astype(np.int32)
        if draw % 4 == 1:
            sizes[rng.integers(0, groups)] = 0
        for start, count in {(0, groups), (min(1, groups - 1),
                                           max(1, groups - 2))}:
            stated = dict(group_sizes=jnp.asarray(sizes), m=m, tm=tm,
                          start_group=jnp.int32(start),
                          num_nonzero_groups=count,
                          visit_empty_groups=visit_empty_groups)
            want = gm._MEGABLOX_METADATA(**stated)
            got = gm._group_metadata(**stated)
            assert jax.tree.structure(got) == jax.tree.structure(want)
            for ours, theirs in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(want)):
                assert (ours.dtype, ours.shape) == (theirs.dtype, theirs.shape)
                np.testing.assert_array_equal(ours, theirs, err_msg=str(
                    (sizes, start, count)))


def test_the_kernels_are_handed_the_modules_metadata():
    assert gm._megablox.make_group_metadata is gm._group_metadata
    assert gm._MEGABLOX_METADATA.__module__ == gm._megablox.__name__


@pytest.fixture
def counted(monkeypatch):
    """The names of megablox's kernels as they are traced, with the Mosaic
    body taken wherever a TPU would take it, interpreted, and every trace it
    keeps forgotten."""
    kernels = []
    call = gm._megablox.pl.pallas_call

    def counting_call(kernel, **stated):
        if kernel.__module__ == gm._megablox.__name__:
            kernels.append(kernel.__qualname__)
        return call(kernel, **stated)

    rule = gm._why_not
    monkeypatch.setattr(gm, "_why_not", lambda *shape_and_place: (
        None if rule(*shape_and_place) == gm.NO_TPU
        else rule(*shape_and_place)))
    monkeypatch.setattr(gm._megablox.pl, "pallas_call", counting_call)
    kept = (gm._group_metadata, gm._megablox.gmm, gm._megablox.tgmm,
            llama._one_buffer)
    for jitted in kept:
        jitted.clear_cache()
    yield kernels
    for jitted in kept:
        jitted.clear_cache()


@pytest.mark.parametrize("pattern", ["M*E", "MEMEM*EME"],
                         ids=["one routed layer", "four routed layers"])
def test_a_start_pays_for_six_kernels_once_and_their_metadata_twice(
        pattern, counted):
    """The gradient's trace of the tiny Nemotron job, with one routed layer
    and with four (the cell's pattern): the kernels' group metadata is
    traced TWICE, once for ``gmm`` and once for ``tgmm``, and SIX kernels
    are, the six products of a layer; neither grows with the layers, nor
    with the passes that run a product again (the first buffer and the
    loop's, the forward rule, the recomputation under ``jax.vjp``).  Before
    PR 68 it was eight and eight: each kernel traced its own metadata, and
    the two forward kernels were traced again in the backward rule, whose
    tracing context (the mesh in scope) is another.  The next kernel PR
    that makes a start pay again fails here."""
    cell = manifest.cell("nemotron-3-nano-30b-a3b.train-s8k-b2")
    over = TINY["ssm_moe_lm"]
    config = {**cell["config"], **over["config"],
              "hybrid_override_pattern": pattern,
              "num_hidden_layers": len(pattern)}
    config["deployment"] = {**config["deployment"],
                            "num_hidden_layers_published": len(pattern)}
    job = manifest.load_job("ssm_moe_lm").build(
        config, {**cell["traffic"], **over["traffic"]}, 1)
    (params, _, bias), batch = (jax.eval_shape(make, jax.random.key(0))
                                for make in (job.init_state, job.make_batch))
    before, traced = gm.body_counts()["mosaic"], gm.metadata_traces()
    jax.make_jaxpr(jax.value_and_grad(job.loss_fn, has_aux=True))(
        params, bias, batch)
    assert gm.body_counts()["mosaic"] > before
    assert gm.metadata_traces() == {"gmm": traced.get("gmm", 0) + 1,
                                    "tgmm": traced.get("tgmm", 0) + 1}
    assert sorted(counted) == 4 * ["gmm.<locals>.kernel"] + 2 * [
        "tgmm.<locals>.kernel"]
