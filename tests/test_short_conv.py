"""``ops/short_conv.py::short_conv`` (interpret mode on CPU, the Mosaic pass
on TPU; ``convolved(..., in_place=True)``) against ``convolved``'s
``jnp`` body, which stays the form for every model whose ``attention_fn``
does not read its operands in place and for every shape the pass refuses.

The two compute the same float32 values in another order (a head's sum of
squares is a product with the heads' 0/1 indicator in both, at ``highest``
in the ``jnp`` body and as three bf16 pieces in the pass), so they agree to
float32 rounding, and to one unit in the last place of a bfloat16 result."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import scopes
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.models.llama import causal_attention
from horovod_tpu.ops import short_conv
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.short_conv import convolved

B, S, H, D, K = 2, 96, 3, 96, 4       # heads of 96: 2.25 lane tiles in all
ROWS = 32                             # the block of rows S = 96 gets: three


def _f32(x):
    return np.asarray(jnp.asarray(x, jnp.float32))


def _pallas_calls(jaxpr, under=None):
    """Every ``pallas_call``; with ``under``, those alone whose own name
    stack or an enclosing equation's (a custom_vjp's, an inlined jit's)
    holds that scope."""
    found = []
    for eqn in jaxpr.eqns:
        inside = under is None or under in str(eqn.source_info.name_stack)
        if eqn.primitive.name == "pallas_call" and inside:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub, None if inside else under))
    return found


def _both_ways(in_place, scale):
    """Forward and ``jax.vjp`` (dy and dtaps) of ``convolved``, jitted."""
    def run(y, taps, g):
        out, vjp = jax.vjp(
            lambda y, taps: convolved(y, taps, H, scale, in_place), y, taps)
        return (out,) + vjp(g)
    return jax.jit(run)


def _close(got, want, dtype, what):
    got, want = _f32(got), _f32(want)
    size = np.abs(want).max()
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * max(
            size, 1.0), err_msg=what)
    else:
        # A unit in the last place of a bfloat16 value is up to 2^-7 of it.
        assert np.all(np.abs(got - want) <= 2.0 ** -7 * np.abs(want)
                      + 1e-6 * size), what


@pytest.mark.parametrize("scale", [None, 1.0, D ** -0.5],
                         ids=["v", "k", "q"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_pass_gives_the_jnp_bodys_values_and_gradients(dtype, scale):
    ky, kt, kg = jax.random.split(jax.random.key(3), 3)
    y = jax.random.normal(ky, (B, S, H * D), jnp.float32).astype(dtype)
    g = jax.random.normal(kg, (B, S, H * D), jnp.float32).astype(dtype)
    taps = jax.random.uniform(kt, (K, H * D), jnp.float32, -0.5, 0.5)
    assert short_conv._pick_rows(S, H * D) == ROWS
    fused, plain = _both_ways(True, scale), _both_ways(False, scale)
    before = short_conv.body_counts()["fused"]
    got, want = fused(y, taps, g), plain(y, taps, g)
    assert short_conv.body_counts()["fused"] == before + 1
    for a, b, what in zip(got, want, ("out", "dy", "dtaps")):
        assert a.shape == b.shape and a.dtype == b.dtype, what
        # The taps' gradient is a float32 sum over B * S rows either way.
        _close(a, b, jnp.float32 if what == "dtaps" else dtype, what)

    # Rows of the batch share nothing: row 0's last token moves nothing in
    # row 1, forward (no history leaks into position 0) or backward.
    moved = fused(y.at[0, S - 1].add(1.0), taps, g.at[0, S - 1].add(1.0))
    for a, b in zip(moved[:2], got[:2]):
        np.testing.assert_array_equal(_f32(a[1]), _f32(b[1]))
        assert not np.array_equal(_f32(a[0, S - 1]), _f32(b[0, S - 1]))

    # The halo: the last row of a block moves itself and the first K - 1
    # rows of the next block, nothing before and nothing further on.
    at = ROWS - 1
    moved = fused(y.at[:, at].add(1.0), taps, g)[0]
    changed = np.any(_f32(moved) != _f32(got[0]), axis=(0, 2))
    assert changed[at:at + K].all()
    assert not changed[:at].any() and not changed[at + K:].any()
    # And backward: the cotangent of a block's first row reaches the K - 1
    # rows before it, in the block before.
    moved = fused(y, taps, g.at[:, ROWS].add(1.0))[1]
    changed = np.any(_f32(moved) != _f32(got[1]), axis=(0, 2))
    assert changed[ROWS - K + 1:ROWS + 1].all()
    assert not changed[:ROWS - K + 1].any() and not changed[ROWS + 1:].any()


# A filter with a bias (a Mamba-2 layer's): two batch rows, three blocks of
# 32 rows, and 1152 lanes, which is one whole chunk of the forward walk's
# 1024 and two of the backward walk's 512 with 128 left over in both; heads
# of 192 straddle the lane tiles.
BIASED_HEADS, BIASED_WIDTH = 6, 1152


@pytest.mark.parametrize("first", [None, 256], ids=["whole", "window"])
@pytest.mark.parametrize("scale", [None, 192 ** -0.5],
                         ids=["plain", "normed"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_pass_takes_the_filters_bias(dtype, scale, first):
    """``silu(taps * y + bias)``: the bias joins c inside the pass, forward
    and where the backward call makes c again, and its gradient is the sum
    of dc over every row of every batch row.  With ``first`` the filter's
    channels are a run in the middle of a wider y (a Mamba-2 layer's x, B
    and C in ``in_proj``'s output): the pass reads them where they lie, and
    the other channels' cotangent is zero."""
    ky, kt, kb, kg = jax.random.split(jax.random.key(5), 4)
    shape = (B, S, BIASED_WIDTH)
    y = jax.random.normal(ky, shape if first is None else (
        B, S, first + BIASED_WIDTH + 128), jnp.float32).astype(dtype)
    g = jax.random.normal(kg, shape, jnp.float32).astype(dtype)
    taps = jax.random.uniform(kt, (K, BIASED_WIDTH), jnp.float32, -0.5, 0.5)
    bias = jax.random.normal(kb, (BIASED_WIDTH,), jnp.float32)
    assert short_conv._pick_rows(S, BIASED_WIDTH) == ROWS
    assert BIASED_WIDTH % short_conv._FWD_LANES
    assert BIASED_WIDTH % short_conv._BWD_LANES > 0 < (
        BIASED_WIDTH // short_conv._BWD_LANES)

    def both_ways(in_place):
        def run(y, taps, bias, g):
            out, vjp = jax.vjp(lambda *x: convolved(
                x[0], x[1], BIASED_HEADS, scale, in_place, bias=x[2],
                first=first), y, taps, bias)
            return (out,) + vjp(g)
        return jax.jit(run)

    before = short_conv.body_counts()
    got = both_ways(True)(y, taps, bias, g)
    after = short_conv.body_counts()
    assert after["fused"] == before["fused"] + 1
    assert after["plain"] == before["plain"]
    want = both_ways(False)(y, taps, bias, g)
    assert got[0].shape == shape and got[1].shape == y.shape
    if first is not None:
        outside = np.r_[:first, first + BIASED_WIDTH:y.shape[2]]
        assert not _f32(got[1])[..., outside].any()
    for a, b, what in zip(got, want, ("out", "dy", "dtaps", "dbias")):
        assert a.shape == b.shape and a.dtype == b.dtype, what
        # The taps' and the bias's gradients are float32 sums over B * S
        # rows either way.
        _close(a, b, dtype if what in ("out", "dy") else jnp.float32, what)
    # It is the bias that was added, before the SiLU: without it the values
    # differ, and the last batch row's last rows count in its gradient.
    bare = both_ways(True)(y, taps, jnp.zeros_like(bias), g)
    assert not np.allclose(_f32(bare[0]), _f32(got[0]), atol=1e-2)
    moved = both_ways(True)(y, taps, bias, g.at[B - 1, S - 1].add(1.0))
    assert np.any(_f32(moved[3]) != _f32(got[3]))


def _mosaic_calls_lowered(fn, *args):
    """``(operands, results)`` of every Mosaic call in ``fn``'s text as it
    is LOWERED for a TPU (no chip, and no compiler: the text alone)."""
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    return [(len(call.group(1).split(",")), len(call.group(2).split(",")))
            for call in re.finditer(
                r"stablehlo\.custom_call @tpu_custom_call\(([^)]*)\).*"
                r"-> \(?([^)\n]*)\)?$", text, flags=re.M)]


def test_a_call_without_a_bias_lowers_to_the_operands_it_always_had(
        monkeypatch):
    """The bias is an operand only where there is one: a linear layer's nine
    calls carry none, and neither a zero bias nor a third result for its
    gradient.  Forward y, the rows before, the taps -> out; backward the
    rows after, g and its own as well -> dy and the taps' partial sums;
    three constants more with a norm.  With a bias: one operand more each
    way, and its partial sums ride the taps' block."""
    monkeypatch.setattr(short_conv, "_interpret", lambda: False)
    # (A shape no other test of the file traces: ``_forward`` and
    # ``_backward`` are jits, and a cached trace would be the interpreted.)
    y = jax.ShapeDtypeStruct((1, 64, 512), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((K, 512), jnp.float32)
    bias = jax.ShapeDtypeStruct((512,), jnp.float32)

    def both(scale, biased, first=None):
        def run(y, taps, bias):
            out, vjp = jax.vjp(lambda *x: convolved(
                x[0], x[1], 4, scale, True, bias=x[2] if biased else None,
                first=first), y, taps, bias)
            return out, vjp(out)
        return run

    assert _mosaic_calls_lowered(both(None, False), y, taps, bias) == [
        (3, 1), (6, 2)]
    assert _mosaic_calls_lowered(both(1.0, False), y, taps, bias) == [
        (6, 1), (9, 2)]
    assert _mosaic_calls_lowered(both(None, True), y, taps, bias) == [
        (4, 1), (7, 2)]
    assert _mosaic_calls_lowered(both(1.0, True), y, taps, bias) == [
        (7, 1), (10, 2)]
    # A window of a wider y is the same operands, placed otherwise.
    wider = jax.ShapeDtypeStruct((1, 64, 1024), jnp.bfloat16)
    assert _mosaic_calls_lowered(both(None, True, 384), wider, taps, bias
                                 ) == [(4, 1), (7, 2)]


def test_each_pass_is_one_mosaic_call_on_the_projections_layout():
    y = jnp.zeros((B, S, H * D), jnp.bfloat16)
    taps = jnp.zeros((K, H * D), jnp.float32)
    # y and the rows before it, the taps; with a norm the scale and the
    # heads' indicator both ways.  Backward: the rows after, g and its own.
    for scale, operands in ((None, 3), (1.0, 6)):
        def both(y, taps):
            out, vjp = jax.vjp(
                lambda y, taps: convolved(y, taps, H, scale, True), y, taps)
            return out, vjp(out)
        forward, backward = _pallas_calls(jax.make_jaxpr(both)(y, taps).jaxpr)
        assert len(forward.invars) == operands
        assert len(backward.invars) == operands + 3
        for call in (forward, backward):
            # No float32 array of the activations' shape goes in or out.
            for v in call.invars + call.outvars:
                assert (v.aval.shape[-2:] != (S, H * D)
                        or v.aval.dtype == jnp.bfloat16)


@pytest.mark.parametrize("shape, taps, in_place, why", [
    ((B, 40, H * D), K, True, short_conv._NO_ROW_BLOCK),
    ((1, 8, H * D), K, True, short_conv._NO_ROW_BLOCK),
    ((B, S, H * D), 10, True, short_conv._TOO_MANY_TAPS),
    ((B, S, H * D), K, False, short_conv.NOT_IN_PLACE)])
def test_a_refused_shape_takes_the_jnp_body_and_the_counter_says_why(
        shape, taps, in_place, why):
    y = jax.random.normal(jax.random.key(0), shape, jnp.bfloat16)
    taps = jax.random.uniform(jax.random.key(1), (taps, shape[2]))
    before = short_conv.body_counts()
    closed = jax.make_jaxpr(
        lambda y, taps: convolved(y, taps, H, 1.0, in_place))(y, taps)
    after = short_conv.body_counts()
    assert _pallas_calls(closed.jaxpr) == []
    assert after["fused"] == before["fused"]
    assert after["plain"][why] == before["plain"].get(why, 0) + 1
    # A shape it takes, asked for in place, counts as fused.
    good = jnp.zeros((B, S, H * D), jnp.bfloat16)
    jax.make_jaxpr(lambda y: convolved(
        y, jnp.zeros((K, H * D)), H, 1.0, True))(good)
    assert short_conv.body_counts()["fused"] == after["fused"] + 1
    assert short_conv.body_counts()["plain"] == after["plain"]


def test_a_window_off_the_lane_tiles_is_cut_out_for_the_jnp_body():
    """``first`` no multiple of 128 lanes: the channels are cut out and the
    ``jnp`` body runs on them, counted by reason; the values are those of
    the cut handed in whole."""
    y = jax.random.normal(jax.random.key(0), (B, S, 64 + H * D + 64),
                          jnp.bfloat16)
    taps = jax.random.uniform(jax.random.key(1), (K, H * D))
    before = short_conv.body_counts()
    got = convolved(y, taps, H, 1.0, True, first=64)
    after = short_conv.body_counts()
    assert after["fused"] == before["fused"]
    assert after["plain"][short_conv._NOT_AT_A_TILE] == before["plain"].get(
        short_conv._NOT_AT_A_TILE, 0) + 1
    want = convolved(y[..., 64:64 + H * D], taps, H, 1.0, False)
    np.testing.assert_array_equal(_f32(got), _f32(want))


@pytest.mark.parametrize("name, attention_fn, fused", [
    ("the model's own dense attention", causal_attention, False),
    ("the flash seam", flash_attention_fn, True)])
def test_the_attention_fn_chooses_the_convolutions_body(name, attention_fn,
                                                        fused):
    """A Mosaic call is the caller's choice (the partitioner cannot split
    one): a linear layer's three convolutions are the pass only where the
    model's ``attention_fn`` says it reads its operands in place; with the
    default attention a hybrid model holds no ``pallas_call`` at all."""
    config = LlamaConfig(
        vocab_size=64, hidden_size=64, num_layers=2, num_heads=2,
        num_kv_heads=2, intermediate_size=128, max_seq_len=64,
        rope_theta=None, layer_types=("linear_attention", "full_attention"),
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=24, linear_value_head_dim=48,
        linear_conv_kernel_dim=4)
    model = LlamaModel(config, attention_fn=attention_fn)
    tokens = jnp.zeros((B, 64), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)
    before = short_conv.body_counts()
    closed = jax.make_jaxpr(model.apply)(params, tokens)
    after = short_conv.body_counts()
    calls = _pallas_calls(closed.jaxpr, under=scopes.GDN_CONV)
    assert len(calls) == (3 if fused else 0), name
    if fused:
        assert after["fused"] == before["fused"] + 3
        assert after["plain"] == before["plain"]
    else:
        assert _pallas_calls(closed.jaxpr) == []
        assert after["fused"] == before["fused"]
        assert after["plain"][short_conv.NOT_IN_PLACE] == before[
            "plain"].get(short_conv.NOT_IN_PLACE, 0) + 3


def test_interpreted_or_not_is_part_of_the_calls_trace(monkeypatch):
    """As the rotation's: the mode is a static argument of the inlined
    jits, so a process that compiles the pass for a described chip after it
    ran the same shape interpreted gets what it asks for."""
    y = jnp.zeros((1, 16, 8), jnp.bfloat16)
    taps = jnp.zeros((K, 8), jnp.float32)

    def mode():
        call, = _pallas_calls(jax.make_jaxpr(
            lambda y: short_conv.short_conv(y, taps, 1, None))(y).jaxpr)
        return bool(call.params["interpret"])

    assert mode() is True
    monkeypatch.setattr(short_conv, "_interpret", lambda: False)
    assert mode() is False
    monkeypatch.undo()
    assert mode() is True
