"""The looped decoder (``LlamaConfig.total_ut_steps`` > 1: Ouro / LoopLM):
weight-shared passes, the exit gate, ``expected_exit_loss``, recomputation,
and every path that must refuse it.  Tiny sizes, CPU, float32 unless said;
the comparison with the plain reference is tests/benchmark's."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import scopes
from horovod_tpu.models import LlamaConfig, LlamaModel, generation
from horovod_tpu.models.llama import LlamaLayer, RMSNorm, rope_freqs
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.losses import (exit_log_distribution,
                                    expected_exit_loss,
                                    softmax_cross_entropy)

F32 = dict(dtype=jnp.float32, logits_dtype=jnp.float32)
TINY = dataclasses.replace(LlamaConfig.tiny(), **F32)
LOOPED = dataclasses.replace(TINY, total_ut_steps=4)
TOKENS = jax.random.randint(jax.random.key(0), (2, 129), 0, TINY.vocab_size)


def _init(cfg, live_gate=True):
    params = LlamaModel(cfg).init(jax.random.key(1), TOKENS[:, :8])
    if live_gate and cfg.total_ut_steps > 1:
        gate = params["params"]["exit_gate"]
        gate["kernel"] = 0.3 * jax.random.normal(jax.random.key(2),
                                                 gate["kernel"].shape)
        gate["bias"] = gate["bias"] + 0.2
    return params


def _loss(model, params, beta=0.1):
    hidden, gates = model.apply(params, TOKENS[:, :-1])
    return expected_exit_loss(
        lambda h: model.apply(params, h, method="head"), hidden, gates,
        TOKENS[:, 1:], beta=beta)


# -- one pass is today's model -----------------------------------------------

def test_one_pass_has_todays_parameter_tree_and_logits_bit_for_bit():
    """``total_ut_steps`` 1: the tree holds no gate, and the logits are
    those of the layers walked once by hand with the same parameters."""
    model = LlamaModel(TINY)
    params = _init(TINY)
    assert sorted(params["params"]) == ["layer_0", "layer_1", "lm_head",
                                        "norm_f", "tok_emb"]
    logits = model.apply(params, TOKENS[:, :-1])

    p = params["params"]
    x = p["tok_emb"]["embedding"][TOKENS[:, :-1]]
    cos, sin = rope_freqs(TINY.head_dim, 128, TINY.rope_theta)
    for i in range(TINY.num_layers):
        x = LlamaLayer(TINY).apply({"params": p[f"layer_{i}"]}, x, cos, sin)
    x = RMSNorm(TINY.rms_eps, TINY.dtype).apply({"params": p["norm_f"]}, x)
    np.testing.assert_array_equal(logits, x @ p["lm_head"]["kernel"])


def test_a_looped_tree_adds_the_gate_alone_and_starts_it_at_zero():
    plain, looped = _init(TINY), _init(LOOPED, live_gate=False)
    gate = looped["params"].pop("exit_gate")
    assert gate["kernel"].shape == (TINY.hidden_size, 1)
    assert gate["bias"].shape == (1,)
    assert not np.any(gate["kernel"]) and not np.any(gate["bias"])
    assert (jax.tree.map(jnp.shape, looped)
            == jax.tree.map(jnp.shape, plain))
    # The same seed makes the same weights: the layers are built once.
    for a, b in zip(jax.tree.leaves(looped), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b)


# -- the loop ----------------------------------------------------------------

def test_every_pass_applies_the_same_stack_to_the_last_normalised_state():
    model = LlamaModel(LOOPED)
    params = _init(LOOPED)
    hidden, gates = model.apply(params, TOKENS[:, :-1])
    assert hidden.shape == (4, 2, 128, TINY.hidden_size)
    assert gates.shape == (4, 2, 128) and gates.dtype == jnp.float32

    p = params["params"]
    cos, sin = rope_freqs(TINY.head_dim, 128, TINY.rope_theta)
    x = p["tok_emb"]["embedding"][TOKENS[:, :-1]]
    for t in range(4):
        for i in range(TINY.num_layers):
            x = LlamaLayer(TINY).apply({"params": p[f"layer_{i}"]}, x,
                                       cos, sin)
        x = RMSNorm(TINY.rms_eps, TINY.dtype).apply(
            {"params": p["norm_f"]}, x)
        np.testing.assert_allclose(hidden[t], x, atol=1e-5)
        np.testing.assert_allclose(
            gates[t], (x @ p["exit_gate"]["kernel"])[..., 0]
            + p["exit_gate"]["bias"][0], atol=1e-5)
    # One pass of the looped model is the plain model's last hidden state.
    logits = LlamaModel(TINY).apply(
        {"params": {k: v for k, v in p.items() if k != "exit_gate"}},
        TOKENS[:, :-1])
    np.testing.assert_allclose(
        model.apply(params, hidden[0], method="head"), logits, atol=1e-4)


# -- the exit distribution and the loss --------------------------------------

def test_exit_distribution_sums_to_one_and_is_the_stick_breaking_product():
    g = 3.0 * jax.random.normal(jax.random.key(3), (4, 5, 7))
    p = np.exp(exit_log_distribution(g))
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    leave = np.asarray(jax.nn.sigmoid(g), np.float64)
    np.testing.assert_allclose(p[0], leave[0], atol=1e-6)
    np.testing.assert_allclose(p[2], leave[2] * (1 - leave[0])
                               * (1 - leave[1]), atol=1e-6)
    np.testing.assert_allclose(
        p[3], (1 - leave[0]) * (1 - leave[1]) * (1 - leave[2]), atol=1e-6)
    # A zero gate: 1/2, 1/4, 1/8 and the last pass takes what is left.
    zero = np.exp(exit_log_distribution(jnp.zeros((4, 1))))[:, 0]
    np.testing.assert_allclose(zero, [0.5, 0.25, 0.125, 0.125])
    # Saturated gates give finite logs (no 0 x -inf in the entropy).
    assert np.all(np.isfinite(exit_log_distribution(
        jnp.full((4, 2), 80.0)))) and np.all(np.isfinite(
            exit_log_distribution(jnp.full((4, 2), -80.0))))


@pytest.mark.parametrize("exit_at", [0, 2, 3])
def test_a_gate_forced_to_one_exit_gives_that_exits_plain_cross_entropy(
        exit_at):
    model = LlamaModel(LOOPED)
    params = _init(LOOPED)
    hidden, _ = model.apply(params, TOKENS[:, :-1])
    gates = jnp.full((4, 2, 128), -40.0).at[exit_at].set(40.0)
    head = lambda h: model.apply(params, h, method="head")  # noqa: E731
    forced = expected_exit_loss(head, hidden, gates, TOKENS[:, 1:],
                                beta=0.1)
    plain = softmax_cross_entropy(head(hidden[exit_at]), TOKENS[:, 1:])
    assert float(forced) == pytest.approx(float(plain), abs=1e-5)


def test_the_loss_is_the_expected_cross_entropy_less_beta_times_entropy():
    model = LlamaModel(LOOPED)
    params = _init(LOOPED)
    hidden, gates = model.apply(params, TOKENS[:, :-1])
    p = np.exp(exit_log_distribution(gates)).astype(np.float64)
    logits = np.stack([model.apply(params, h, method="head")
                       for h in hidden]).astype(np.float64)
    log_probs = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    ce = -np.take_along_axis(
        log_probs, np.asarray(TOKENS[None, :, 1:, None]).repeat(4, 0),
        axis=-1)[..., 0]
    for beta in (0.0, 0.1):
        want = np.mean((p * ce).sum(0) + beta * (p * np.log(p)).sum(0))
        assert float(_loss(model, params, beta)) == pytest.approx(
            want, abs=2e-5)


def test_first_loss_of_a_zero_gate_is_ln_vocab_plus_half_less_the_entropy():
    from benchmark.jobs import looped_lm
    assert looped_lm.exit_entropy(4) == pytest.approx(1.75 * np.log(2))
    model = LlamaModel(LOOPED)
    loss = float(_loss(model, _init(LOOPED, live_gate=False)))
    assert loss == pytest.approx(
        np.log(TINY.vocab_size) + 0.5 - 0.1 * 1.75 * np.log(2), abs=0.25)


def test_gradients_reach_the_gate_and_sum_over_the_passes():
    """A weight's gradient is the sum over the four uses of it: the same as
    the gradient of four separate copies, added up."""
    model = LlamaModel(LOOPED)
    params = _init(LOOPED)
    grads = jax.grad(lambda p: _loss(model, p))(params)
    gate = grads["params"]["exit_gate"]
    assert float(jnp.abs(gate["kernel"]).max()) > 1e-4
    assert float(jnp.abs(gate["bias"]).max()) > 1e-4

    layers = [LlamaLayer(TINY) for _ in range(TINY.num_layers)]
    norm = RMSNorm(TINY.rms_eps, TINY.dtype)
    cos, sin = rope_freqs(TINY.head_dim, 128, TINY.rope_theta)

    def unshared(copies):
        p = params["params"]
        x = p["tok_emb"]["embedding"][TOKENS[:, :-1]]
        hidden, gates = [], []
        for copy in copies:                       # one copy a pass
            for layer, weights in zip(layers, copy):
                x = layer.apply({"params": weights}, x, cos, sin)
            x = norm.apply({"params": p["norm_f"]}, x)
            hidden.append(x)
            gates.append((x @ p["exit_gate"]["kernel"])[..., 0]
                         + p["exit_gate"]["bias"][0])
        return expected_exit_loss(
            lambda h: model.apply(params, h, method="head"),
            jnp.stack(hidden), jnp.stack(gates), TOKENS[:, 1:])

    stack = [params["params"][f"layer_{i}"]
             for i in range(TINY.num_layers)]
    by_copy = jax.grad(unshared)([stack] * 4)
    summed = jax.tree.map(lambda *g: sum(g), *by_copy)
    for i, want in enumerate(summed):
        for got, w in zip(jax.tree.leaves(grads["params"][f"layer_{i}"]),
                          jax.tree.leaves(want)):
            np.testing.assert_allclose(
                got, w, atol=2e-5 * float(jnp.abs(w).max()) + 1e-9)


# -- recomputation -----------------------------------------------------------

@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("remat", ["layer", "layer_keep_attention"])
def test_recomputation_changes_no_value(remat, attention):
    kwargs = ({"attention_fn": flash_attention_fn}
              if attention == "flash" else {})
    params = _init(LOOPED)
    want = jax.value_and_grad(
        lambda p: _loss(LlamaModel(LOOPED, **kwargs), p))(params)
    got = jax.value_and_grad(lambda p: _loss(LlamaModel(
        dataclasses.replace(LOOPED, remat=remat), **kwargs), p))(params)
    assert float(got[0]) == pytest.approx(float(want[0]), abs=1e-6)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(
            g, w, atol=1e-5 * float(jnp.abs(w).max()) + 1e-9)


@pytest.mark.parametrize("remat, calls_a_layer", [
    ("none", 2), ("layer", 3), ("layer_keep_attention", 2)])
def test_keeping_the_flash_output_spares_its_forward_call(remat,
                                                          calls_a_layer):
    """A forward and a backward call a layer; a recomputed layer calls the
    forward kernel again unless the policy keeps its output.  The passes are
    a scan, so the program holds one pass's calls."""
    model = LlamaModel(dataclasses.replace(LOOPED, remat=remat),
                       attention_fn=flash_attention_fn)
    params = _init(LOOPED)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda p: _loss(model, p)))(params))
    assert jaxpr.count("pallas_call[") == calls_a_layer * TINY.num_layers


def test_recomputed_work_carries_jaxs_own_name():
    """The spelling ``common/scopes.py`` records, in the compiled step's
    ``op_name``s: what the backward pass runs again is under
    ``rematted_computation``, the loop's scope and a ``transpose(...)``:
    each layer, and the norm and gate that end a pass.  No exit's head is
    among it: its gradient products run beside its forward product, under
    ``transpose(jvp(LlamaModel.head))`` inside the forward walk."""
    model = LlamaModel(dataclasses.replace(LOOPED, remat="layer"))
    params = _init(LOOPED)

    def grads(params):
        with jax.named_scope(scopes.LOSS):
            return jax.grad(lambda p: _loss(model, p))(params)

    text = jax.jit(grads).lower(params).compile().as_text()
    # (A reduction's scalar body keeps a relative name: no "jit(...)/".)
    op_names = set(re.findall(r'op_name="(jit\([^"]*)"', text))
    again = [n for n in op_names if f"/{scopes.REMATTED}/" in n]
    assert any(scopes.LOOP_PASS in n and "/layer_0/" in n for n in again)
    assert any(scopes.LOOP_EXIT in n and "/norm_f/" in n for n in again)
    assert not [n for n in again if "/lm_head/" in n]
    head = {n for n in op_names if n.endswith("/lm_head/dot_general")}
    assert head and all(f"jvp({scopes.LOOP_EXIT})" in n for n in head)
    assert any("/transpose(jvp(LlamaModel.head))/" in n for n in head)
    assert all("transpose(" in n and scopes.LOSS in n for n in again)
    first = [n for n in op_names if "/layer_0/" in n and scopes.LOSS in n
             and scopes.REMATTED not in n and "transpose(" not in n]
    assert first and all(scopes.LOOP_PASS in n for n in first)


def test_recomputation_applies_to_the_plain_model_too():
    params = _init(TINY)
    want = LlamaModel(TINY).apply(params, TOKENS[:, :-1])
    got = LlamaModel(dataclasses.replace(TINY, remat="layer")).apply(
        params, TOKENS[:, :-1])
    np.testing.assert_array_equal(got, want)


# -- what must refuse --------------------------------------------------------

def test_config_refuses_what_it_does_not_know():
    with pytest.raises(ValueError, match="total_ut_steps"):
        LlamaConfig(total_ut_steps=0)
    with pytest.raises(ValueError, match="remat"):
        LlamaConfig(remat="everything")


def test_generation_refuses_a_looped_model():
    params = _init(LOOPED)
    prompt = TOKENS[:, :8]
    with pytest.raises(NotImplementedError, match="total_ut_steps=4"):
        generation.prefill(LOOPED, params, prompt, cache_len=16)
    with pytest.raises(NotImplementedError, match="total_ut_steps=4"):
        generation.generate(LOOPED, params, prompt, max_new_tokens=2)
    pool = jnp.zeros((TINY.num_layers, 4, 8, TINY.num_kv_heads,
                      TINY.head_dim))
    table = jnp.zeros((2,), jnp.int32)
    with pytest.raises(NotImplementedError, match="total_ut_steps=4"):
        generation.paged_prefill(LOOPED, params, prompt[:1], pool, pool,
                                 table, prompt_len=8)
    with pytest.raises(NotImplementedError, match="total_ut_steps=4"):
        generation.paged_decode_step(LOOPED, params, prompt[:, 0], pool,
                                     pool, table[None], jnp.zeros((2,)))


def test_the_serve_plane_refuses_a_looped_model(monkeypatch):
    from horovod_tpu.serve import engine
    from horovod_tpu.serve.config import ServeConfig

    monkeypatch.setattr(LlamaConfig, "looped_tiny",
                        staticmethod(lambda: LOOPED), raising=False)
    with pytest.raises(ValueError, match="looped model"):
        engine.build_model_config(ServeConfig(model="looped_tiny"))
    assert engine.build_model_config(
        ServeConfig(model="tiny")).total_ut_steps == 1


def test_the_pipelined_step_refuses_a_looped_model():
    from horovod_tpu.parallel import init_pipelined_llama

    with pytest.raises(NotImplementedError, match="one pass silently"):
        init_pipelined_llama(LOOPED, jax.random.key(0), n_stages=2)
