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
