"""The gated delta-rule linear-attention layers of the ``olmo-hybrid-7b``
configuration at a small size on the CPU: the chunkwise rule
(``ops/gated_delta.py``) against the recurrence run token by token, forward
and every gradient, at the lengths and gates that break a careless one; the
convolution's first positions; the whole program against
``benchmark/reference/olmo_hybrid.py``; a configuration without
``layer_types`` lowering to the program it had; and the refusals by name."""

import dataclasses
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.reference import olmo_hybrid
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.models.llama import GatedDeltaNet
from horovod_tpu.ops import gated_delta
from horovod_tpu.ops import short_conv
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.gated_delta import (gated_delta_rule,
                                         gated_delta_states)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))
from tiny_sizes import TINY  # noqa: E402

CELL = "olmo-hybrid-7b.train-s8k"
HIGHEST = jax.default_matmul_precision("highest")
INPUTS = ("q", "k", "v", "g", "beta")


def _inputs(seed, seq, *, batch=2, heads=3, d_k=24, d_v=48, decay=-2.0,
            write=0.0):
    """q and k as the rule reads them (unit, q scaled), v, and gates whose
    centres ``decay`` (log of -g) and ``write`` (beta's logit) move."""
    keys = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(keys[0], (batch, seq, heads, d_k))
    k = jax.random.normal(keys[1], (batch, seq, heads, d_k))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d_k ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (batch, seq, heads, d_v))
    g = -jnp.exp(jax.random.normal(keys[3], (batch, seq, heads)) + decay)
    beta = 2 * jax.nn.sigmoid(
        2 * jax.random.normal(keys[4], (batch, seq, heads)) + write)
    return q, k, v, g, beta


def _token_by_token(q, k, v, g, beta):
    """The reference's recurrence; its blocks of tokens to a checkpoint
    want a multiple of ``TOKENS``, so a test's sequence is one block."""
    with mock.patch.object(olmo_hybrid, "TOKENS", q.shape[1]):
        o, _ = olmo_hybrid.delta_rule(q, k, v, jnp.exp(g), beta)
    return o


def _both(inputs, rule=gated_delta_rule):
    """(value, gradients) of a fixed random functional of the output, by
    ``rule`` and token by token, in float32 at ``highest``."""
    weight = jax.random.normal(jax.random.key(99), inputs[2].shape)
    with HIGHEST:
        return [jax.jit(jax.value_and_grad(
            lambda *x, fn=fn: jnp.sum(fn(*x) * weight),
            argnums=tuple(range(5))))(*inputs)
            for fn in (rule, _token_by_token)]


def _assert_close(found, wanted, tolerance=2e-4):
    (value, grads), (ref_value, ref_grads) = found, wanted
    assert float(value) == pytest.approx(float(ref_value), rel=tolerance,
                                         abs=tolerance)
    for name, got, want in zip(INPUTS, grads, ref_grads):
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0, name
        np.testing.assert_allclose(got, want, atol=tolerance * scale,
                                   err_msg=name)


# -- the chunked rule against the recurrence -----------------------------------

@pytest.mark.parametrize("seq", [64, 200, 33, 640],
                         ids=["one-chunk", "no-multiple-of-64",
                              "under-a-chunk", "ten-chunks"])
def test_chunked_rule_agrees_with_the_recurrence(seq):
    """Forward and the gradients of q, k, v, the log-decay and beta; 640
    tokens are ten chunks in five slabs of two (``_slabs`` takes the largest
    slab that divides them), so states cross slabs in both walks."""
    if seq == 640:
        assert gated_delta._slabs(jnp.zeros((10, 1))).shape == (5, 2, 1)
    _assert_close(*_both(_inputs(0, seq)))


@pytest.mark.parametrize("decay,write,what", [
    (3.0, 0.0, "alpha near 0"), (-9.0, 0.0, "alpha near 1"),
    (-2.0, 3.0, "beta over 1"), (1.0, 3.0, "fast decay, strong writes")])
def test_chunked_rule_at_the_gates_ends(decay, write, what):
    q, k, v, g, beta = inputs = _inputs(1, 192, decay=decay, write=write)
    alpha = np.exp(np.asarray(g))
    if what == "alpha near 0":
        assert alpha.min() < 1e-30 and np.median(alpha) < 1e-4
    if what == "alpha near 1":
        assert alpha.min() > 0.99
    if "beta" in what or "writes" in what:
        assert float(jnp.mean(beta > 1)) > 0.8
    found, wanted = _both(inputs)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in found[1])
    _assert_close(found, wanted)


def test_identical_keys_with_beta_two_stay_bounded():
    """The case a power series for ``(I + A)^-1`` cancels itself on: every
    key the same, beta at 2, no decay, so A is 2 under the whole diagonal
    and the state is reflected by every token.  Block substitution is
    exact."""
    q, k, v, g, beta = _inputs(2, 128, batch=1, heads=1)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    inputs = (q, k, v, jnp.zeros_like(g), jnp.full_like(beta, 1.999))
    found, wanted = _both(inputs)
    assert float(jnp.max(jnp.abs(found[1][2]))) < 1e3
    _assert_close(found, wanted, tolerance=2e-3)


@pytest.mark.parametrize("mosaic", [False, True], ids=["merges", "call"])
def test_tril_inverse_is_the_inverse_and_transposes(mosaic):
    """By the six merges and by ``_solve``'s call (interpreted here), both
    through ``_tril_inverse``'s ``custom_vjp``."""
    a = 0.2 * jnp.tril(jax.random.normal(jax.random.key(3), (5, 64, 64)), -1)
    with HIGHEST:
        t = gated_delta._tril_inverse(a, mosaic)
        np.testing.assert_allclose(
            t @ (jnp.eye(64) + a), jnp.broadcast_to(jnp.eye(64), a.shape),
            atol=2e-4)
        weight = jax.random.normal(jax.random.key(4), a.shape)
        got = jax.grad(lambda a: jnp.sum(gated_delta._tril_inverse(a, mosaic)
                                         * weight))(a)
        want = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(
            jnp.eye(64) + jnp.tril(a, -1)) * weight))(a)
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-3 * float(jnp.max(jnp.abs(want))))
    assert not np.asarray(jnp.triu(got)).any()


def _systems(seed, lead, scale=1.0):
    """A ``[*lead, 64, 64]`` as ``_prepare`` forms it, at the rule's own
    scale (unit keys, beta in (0, 2), decays in (0, 1]) times ``scale``."""
    keys = jax.random.split(jax.random.key(seed), 3)
    k = jax.random.normal(keys[0], (*lead, 64, 24))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(keys[1], (*lead, 64)))
    gamma = jnp.cumsum(-jnp.exp(jax.random.normal(keys[2], (*lead, 64)) - 2),
                       axis=-1)
    rows, cols = jnp.arange(64)[:, None], jnp.arange(64)[None, :]
    decay = jnp.exp(jnp.where(rows >= cols, gamma[..., :, None]
                              - gamma[..., None, :], -jnp.inf))
    with HIGHEST:
        a = beta[..., None] * decay * jnp.einsum("...id,...jd->...ij", k, k)
    return jnp.where(rows > cols, scale * a, 0.0)


@pytest.mark.parametrize("scale", [1.0, 10.0], ids=["rule-scale", "tenfold"])
@pytest.mark.parametrize("lead", [(5,), (2, 2, 32), (2, 1, 30)], ids=[
    "five", "qwen3-next-slab-of-2-chunks", "olmo-hybrid-slab-of-2-chunks"])
def test_solve_call_against_the_merges_and_float64(lead, scale):
    """``_solve``'s call in interpret mode at the two cells' slab shapes cut
    to two chunks (2 x 2 rows x 32 heads = 128 matrices, one grid step;
    2 x 30 = 60, padded) against the inverse in float64: no further from it
    than the six merges are, by each matrix's largest entry.  Tenfold the
    rule's scale T's entries reach 1e6 and more."""
    a = _systems(7, lead, scale)
    assert not np.asarray(jnp.triu(a)).any()
    with HIGHEST:       # (for the merges; and one trace a shape of the call)
        got = np.asarray(gated_delta._solve(a, interpret=True), np.float64)
        merged = np.asarray(gated_delta._tril_inverse_impl(a), np.float64)
    want = np.linalg.inv(np.eye(64) + np.asarray(a, np.float64))
    size = np.abs(want).max(axis=(-1, -2), keepdims=True)
    assert scale == 1 or size.max() > 1e4

    def error(found):
        return float((np.abs(found - want) / size).max())

    assert error(got) < max(2 * error(merged), 2e-6), (
        error(got), error(merged))
    assert not np.triu(got, 1).any()
    np.testing.assert_array_equal(np.diagonal(got, axis1=-2, axis2=-1), 1.0)


def test_solve_call_reads_nothing_on_or_above_the_diagonal():
    """As the merges' masks: what A holds there is not the system's."""
    a = _systems(8, (5,))
    junk = a + jnp.triu(jnp.full_like(a, 7.0))
    with HIGHEST:
        np.testing.assert_array_equal(
            gated_delta._solve(junk, interpret=True),
            gated_delta._solve(a, interpret=True))


@pytest.mark.parametrize("heads, key_heads", [(30, 30), (32, 16)],
                         ids=["olmo-hybrid-30", "qwen3-next-32-over-16"])
def test_rule_with_the_call_agrees_with_the_merges(heads, key_heads,
                                                   monkeypatch):
    """``gated_delta_rule(.., in_place=True)`` against ``in_place=False``,
    forward and the five gradients, at the two cells' head counts (value
    heads 32 over 16 key heads: q and k copied, as the mixer does) over
    three chunks in slabs of one.  The CPU takes the ``jnp`` body by rule
    (``NO_TPU``); lifted here, the call runs interpreted.  In chunks of 16,
    which is what keeps two traces of the interpreted call a case under ten
    seconds (a chunk of 64 is 1,300 multiply-adds of straight-line code to
    the interpreter); the call itself is held to the merges at 64 above."""
    monkeypatch.setattr(gated_delta, "CHUNK", 16)
    q, k, v, g, beta = _inputs(11, 48, batch=1, heads=heads, d_k=8, d_v=16)
    q, k = (jnp.repeat(x[:, :, :key_heads], heads // key_heads, axis=2)
            for x in (q, k))
    inputs = (q, k, v, g, beta)
    weight = jax.random.normal(jax.random.key(98), v.shape)

    def both(in_place):
        with HIGHEST:
            return jax.jit(jax.value_and_grad(
                lambda *x: jnp.sum(gated_delta_rule(*x, in_place=in_place)
                                   * weight), argnums=tuple(range(5))))(
                                       *inputs)

    before = gated_delta.solve_counts()
    merged = both(False)
    assert both(True)[0] == merged[0]          # off the TPU: the same body
    monkeypatch.setattr(gated_delta, "_why_not", lambda: None)
    called = both(True)
    after = gated_delta.solve_counts()
    assert after["mosaic"] == before["mosaic"] + 1
    assert after["plain"][gated_delta.NOT_IN_PLACE] == before["plain"].get(
        gated_delta.NOT_IN_PLACE, 0) + 1
    assert after["plain"][gated_delta.NO_TPU] == before["plain"].get(
        gated_delta.NO_TPU, 0) + 1
    _assert_close(called, merged, tolerance=1e-4)
    assert float(called[0]) != float(merged[0]) or any(
        np.any(np.asarray(x) != np.asarray(y))
        for x, y in zip(called[1], merged[1]))      # another body did run


def test_the_mixer_hands_the_rule_what_its_layer_read(monkeypatch):
    """``GatedDeltaNet`` says ``in_place`` around the rule's call, which it
    makes with the five operands alone (the accepted benchmark's tests wrap
    that name); a rule called inside another's context takes that answer, a
    rule that is told takes what it is told."""
    monkeypatch.setattr(gated_delta, "_interpret", lambda: False)
    cfg = dataclasses.replace(_tiny()[0].llama, dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((1, 128, cfg.hidden_size), jnp.float32)

    def counted(in_place):
        mixer = GatedDeltaNet(cfg, in_place=in_place)
        before = gated_delta.solve_counts()
        params = jax.eval_shape(mixer.init, jax.random.key(0), x)
        after = gated_delta.solve_counts()
        jax.eval_shape(mixer.apply, params, x)
        return before, after, gated_delta.solve_counts()

    before, _, after = counted(True)
    assert after["mosaic"] == before["mosaic"] + 2       # init and apply
    assert after["plain"] == before["plain"]
    before, _, after = counted(False)
    assert after["mosaic"] == before["mosaic"]
    assert after["plain"][gated_delta.NOT_IN_PLACE] == before["plain"].get(
        gated_delta.NOT_IN_PLACE, 0) + 2
    inputs = jax.eval_shape(lambda: _inputs(0, 64))
    before = gated_delta.solve_counts()
    # (A lambda each: ``eval_shape`` keeps a function's trace.)
    with gated_delta.calls_in_place(True):
        jax.eval_shape(lambda *x: gated_delta_rule(*x), *inputs)
        jax.eval_shape(lambda *x: gated_delta_rule(*x, in_place=False),
                       *inputs)
    jax.eval_shape(lambda *x: gated_delta_rule(*x), *inputs)
    after = gated_delta.solve_counts()
    assert after["mosaic"] == before["mosaic"] + 1
    assert after["plain"][gated_delta.NOT_IN_PLACE] == before["plain"].get(
        gated_delta.NOT_IN_PLACE, 0) + 2


def test_states_are_the_recurrences_at_each_chunks_start():
    q, k, v, g, beta = _inputs(5, 256, batch=1, heads=2)
    with HIGHEST:
        states = gated_delta_states(q, k, v, g, beta)
        assert states.shape == (4, 1, 2, 48, 24)
        assert not np.asarray(states[0]).any()
        # The state after 64 tokens, by the rule on that prefix alone: what
        # a query that reads only the state (decay 1, no write) returns.
        probe = jnp.eye(24)[None, :, None, :].repeat(2, axis=2)
        pad = lambda x, fill: jnp.concatenate(     # noqa: E731
            [x[:, :64], jnp.full((1, 24) + x.shape[2:], fill)], axis=1)
        read = gated_delta_rule(
            jnp.concatenate([q[:, :64], probe], axis=1), pad(k, 0.0),
            pad(v, 0.0), pad(g, 0.0), pad(beta, 0.0))[:, 64:]
    np.testing.assert_allclose(jnp.einsum("bkhv->bhvk", read), states[1],
                               atol=1e-5)


def test_bf16_inputs_keep_a_float32_state():
    """The training path: bf16 q, k, v, float32 gates.  The output is
    bf16's distance from the float32 recurrence, not a state's that lost
    its low bits over 16 chunks."""
    inputs = _inputs(6, 1024, decay=-5.0)
    low = tuple(x.astype(jnp.bfloat16) for x in inputs[:3]) + inputs[3:]
    out = gated_delta_rule(*low)
    assert out.dtype == jnp.bfloat16
    with HIGHEST:
        want = _token_by_token(*inputs)
    error = jnp.linalg.norm(out.astype(jnp.float32) - want) / (
        jnp.linalg.norm(want))
    assert float(error) < 0.02


# -- the mixer -----------------------------------------------------------------

@pytest.mark.parametrize("in_place, seq", [(False, 6), (True, 32)],
                         ids=["jnp", "mosaic"])
def test_convolution_has_no_history_before_position_zero(in_place, seq):
    """On both of ``convolved``'s bodies; the Mosaic pass (interpreted
    here) wants whole blocks of 16 rows, so its sequence is two."""
    x = jax.random.normal(jax.random.key(7), (1, seq, 3))
    taps = jax.random.normal(jax.random.key(8), (4, 3))
    before = short_conv.body_counts()["fused"]
    if in_place:
        def convolution(x, taps):
            return short_conv.convolved(x, taps, 1, None, True)
        first = jax.nn.silu
    else:
        convolution, first = short_conv._short_convolution, lambda c: c
    y = convolution(x, taps)
    assert short_conv.body_counts()["fused"] == before + int(in_place)
    np.testing.assert_allclose(y[0, 0], first(taps[3] * x[0, 0]), rtol=1e-5)
    np.testing.assert_allclose(
        y[0, 1], first(taps[3] * x[0, 1] + taps[2] * x[0, 0]), rtol=1e-5,
        atol=1e-6)
    np.testing.assert_allclose(
        y[0, 2], first(taps[3] * x[0, 2] + taps[2] * x[0, 1]
                       + taps[1] * x[0, 0]), rtol=1e-5, atol=1e-6)
    for t in (5, seq - 1):      # and, in place, across the blocks' seam
        np.testing.assert_allclose(
            y[0, t], first(sum(taps[i] * x[0, t - 3 + i] for i in range(4))),
            rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        y, first(olmo_hybrid.short_convolution(x, taps)), rtol=1e-5,
        atol=1e-6)
    # Causal: a later token changes nothing before it.
    later = convolution(x.at[0, 4].add(1.0), taps)
    np.testing.assert_array_equal(later[0, :4], y[0, :4])
    unit = short_conv.convolved(x, taps, 1, 1.0, in_place)
    np.testing.assert_allclose(jnp.linalg.norm(unit, axis=-1), 1.0,
                               atol=1e-3)


def _tiny(**changes):
    cell = manifest.cell(CELL)
    tiny = TINY[cell["config"]["job"]]
    config = {**cell["config"], **tiny["config"], **changes}
    traffic = {**cell["traffic"], **tiny["traffic"]}
    job = manifest.load_job(config["job"]).build(config, traffic, 1)
    return job, config


def test_tiny_sizes_keep_the_published_pattern_and_ratios():
    job, config = _tiny()
    c = job.llama
    assert c.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert (c.linear_value_head_dim, c.linear_conv_kernel_dim) == (
        2 * c.linear_key_head_dim, 4)
    assert c.rope_theta is None and c.norm_placement == "post"
    assert c.qk_norm and c.qk_norm_over == "all"
    params = jax.eval_shape(job.init_state, jax.random.key(0))[0]["params"]
    assert set(params["layer_0"]) == {"linear", "mlp", "norm_attn",
                                      "norm_mlp"}
    assert set(params["layer_3"]) == {"attn", "mlp", "norm_attn", "norm_mlp"}
    assert set(params["layer_0"]["linear"]) == {
        "wq", "wk", "wv", "wg", "wa", "wb", "wo", "conv_q", "conv_k",
        "conv_v", "a_log", "dt_bias", "o_norm"}
    assert params["layer_3"]["attn"]["q_norm"]["scale"].shape == (
        c.num_heads * c.head_dim,)


def test_whole_model_agrees_with_the_plain_reference_in_float32():
    """Loss and every gradient leaf, on seeded weights moved off their
    initial values, the program's layers in float32: the chunked rule
    inside the model against the reference's token-by-token one."""
    job, config = _tiny()
    reference = manifest.load_reference(config["reference"])
    leaves, treedef = jax.tree.flatten(
        jax.jit(job.init_state)(jax.random.key(0))[0])
    keys = jax.random.split(jax.random.key(1), len(leaves))
    params = treedef.unflatten([
        (leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)).astype(
            jnp.float32) for leaf, k in zip(leaves, keys)])
    job.model = LlamaModel(
        dataclasses.replace(job.llama, dtype=jnp.float32,
                            logits_dtype=jnp.float32),
        attention_fn=flash_attention_fn)
    sample = job.make_batch(jax.random.key(2), 1)

    @jax.jit
    def both(params, sample):
        with HIGHEST:
            loss, grads = jax.value_and_grad(job.loss_fn)(params, sample)
        return loss, grads, reference.loss_and_grads(
            job.to_reference(params), sample, config)

    loss, grads, (ref_loss, ref_grads) = both(params, sample)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-5)
    mapped = job.to_reference(grads)
    assert jax.tree.structure(mapped) == jax.tree.structure(ref_grads)
    for got, want in zip(jax.tree.leaves(mapped), jax.tree.leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0
        np.testing.assert_allclose(got, want, atol=2e-3 * scale)


def test_counters_agree_with_the_references_and_are_spread():
    job, config = _tiny()
    reference = manifest.load_reference(config["reference"])
    params = jax.jit(job.init_state)(jax.random.key(3))[0]
    sample = job.make_batch(jax.random.key(4), 1)
    program = jax.jit(job.counters)(params, sample)
    plain = jax.jit(lambda p, s: reference.layer_counters(
        job.to_reference(p), s, config))(params, sample)
    assert len(plain) == 3 and set(params) == {"params"}
    for name, values in program.items():
        assert values.shape == (3,) and bool(jnp.all(jnp.isfinite(values)))
        wanted = np.asarray([layer[name] for layer in plain])
        if name == "state_max":
            # The program looks at the state each chunk starts from, the
            # reference at the state behind every token.
            assert np.all(values <= 1.05 * wanted)
            assert np.all(values > 0.2 * wanted)
        else:
            np.testing.assert_allclose(values, wanted, rtol=0.1, atol=0.02)
    # The builder's initialisation: betas on both sides of 1, decays that
    # neither all forget nor all keep.
    assert 0.3 < float(program["beta_over_one"].mean()) < 0.7
    assert 0.5 < float(program["alpha_mean"].mean()) < 0.999
    assert float(program["alpha_min"].min()) < 0.9


def test_more_value_heads_than_key_heads():
    cfg = dataclasses.replace(
        _tiny()[0].llama, dtype=jnp.float32, linear_num_key_heads=2,
        linear_num_value_heads=4)
    x = jax.random.normal(jax.random.key(5), (1, 96, cfg.hidden_size))
    mixer = GatedDeltaNet(cfg)
    params = mixer.init(jax.random.key(6), x)
    assert params["params"]["wq"]["kernel"].shape[1] == 2 * 32
    assert params["params"]["wv"]["kernel"].shape[1] == 4 * 64
    assert mixer.apply(params, x).shape == x.shape


# -- the stack -----------------------------------------------------------------

def _lowered(cfg, seq=128, debug_info=False):
    model = LlamaModel(cfg, attention_fn=flash_attention_fn)
    ids = jnp.zeros((1, seq), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), ids)
    return params, jax.jit(jax.grad(lambda p, ids: jnp.sum(model.apply(
        p, ids).astype(jnp.float32)))).lower(params, ids).as_text(
            debug_info=debug_info)


@pytest.mark.parametrize("remat", ["none", "layer_keep_attention"])
def test_without_layer_types_the_program_is_what_it_was(remat):
    """A configuration that names no ``layer_types`` and one that names
    every layer ``full_attention`` are one parameter tree and one lowered
    program, forward and backward: the stack reads the key and changes
    nothing for the configurations that lack it."""
    plain = dataclasses.replace(LlamaConfig.tiny(), remat=remat)
    named = dataclasses.replace(plain,
                                layer_types=("full_attention",) * 2)
    params, text = _lowered(plain)
    params_named, text_named = _lowered(named)
    assert jax.tree.structure(params) == jax.tree.structure(params_named)
    assert text == text_named
    assert "hvd.gdn" not in text and "linear" not in str(
        jax.tree.structure(params))
    hybrid = dataclasses.replace(
        plain, layer_types=("linear_attention", "full_attention"),
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=16, linear_value_head_dim=32)
    params_hybrid, text_hybrid = _lowered(hybrid, debug_info=True)
    assert "linear" in params_hybrid["params"]["layer_0"]
    assert "attn" in params_hybrid["params"]["layer_1"]
    assert "hvd.gdn.scan" in text_hybrid


def test_config_refuses_what_it_cannot_be():
    with pytest.raises(ValueError, match="layer_types"):
        LlamaConfig.tiny().__class__(num_layers=2,
                                     layer_types=("full_attention",))
    with pytest.raises(ValueError, match="layer_types"):
        LlamaConfig(num_layers=1, layer_types=("sliding_attention",))
    with pytest.raises(ValueError, match="linear_num_key_heads"):
        LlamaConfig(num_layers=1, layer_types=("linear_attention",))
    with pytest.raises(ValueError, match="multiple of the key heads"):
        LlamaConfig(num_layers=1, layer_types=("linear_attention",),
                    linear_num_key_heads=2, linear_num_value_heads=3,
                    linear_key_head_dim=8, linear_value_head_dim=8)
    with pytest.raises(ValueError, match="norm_placement"):
        LlamaConfig(norm_placement="sandwich")
    with pytest.raises(ValueError, match="qk_norm_over"):
        LlamaConfig(qk_norm_over="group")


@pytest.mark.parametrize("who", ["generation", "serve", "pipeline"])
@pytest.mark.parametrize("what,word", [
    ("linear", "gated delta-rule linear attention"),
    ("block", "pre-norm layer of its own")])
def test_the_other_paths_refuse_the_new_kinds_by_name(who, what, word):
    from horovod_tpu.models.generation import prefill
    from horovod_tpu.parallel.pipeline import init_pipelined_llama

    cfg = _tiny()[0].llama
    if what == "block":       # OLMo 2's block without a linear layer
        cfg = dataclasses.replace(cfg, layer_types=None)
    with pytest.raises(NotImplementedError, match=word) as refusal:
        if who == "generation":
            prefill(cfg, {}, jnp.zeros((1, 4), jnp.int32), cache_len=8)
        elif who == "serve":
            cfg.refuse_new_kinds("the paged KV cache")
        else:
            init_pipelined_llama(cfg, jax.random.key(0), 1)
    if what == "linear":
        assert "'linear_attention'" in str(refusal.value)
    else:
        assert "norm_placement='post'" in str(refusal.value)
