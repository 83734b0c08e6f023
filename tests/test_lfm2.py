"""LFM2's layers (``models/llama.py`` with ``"conv"`` in ``layer_types``:
``GatedShortConv`` mixers among grouped-query layers of 64-wide heads with a
QK-norm, a dense SwiGLU and routed SwiGLU experts behind a sigmoid,
bias-corrected router, a tied head) against the plain reference
(``benchmark/reference/lfm2_moe.py``), on the CPU in float32 at a small size
with seeded random weights moved off their start; the gated form of
``ops/short_conv.py``'s Mosaic pass, interpreted, against its ``jnp`` body."""

import copy
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.reference import lfm2_moe as reference
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.models import llama
from horovod_tpu.ops import short_conv
from tiny_sizes import TINY

CELL = "lfm2-24b-a2b.train-s8k-b2"


@pytest.fixture(scope="module")
def tiny():
    """The tiny job at the CELL's five layers (conv, full_attention, conv,
    conv, conv: one dense, four routed) in float32 with the model's own
    dense attention, its parameters moved off their start (every scale
    differs from 1), a batch, and the configuration the reference reads."""
    cell = manifest.cell(CELL)
    over = TINY["lconv_moe_lm"]
    config = {**cell["config"], **over["config"],
              "num_hidden_layers": cell["config"]["num_hidden_layers"],
              "layer_types": cell["config"]["layer_types"]}
    job = manifest.load_job("lconv_moe_lm").build(
        config, {**cell["traffic"], **over["traffic"]}, 1)
    job.llama = dataclasses.replace(job.llama, dtype=jnp.float32,
                                    logits_dtype=jnp.float32, remat="none")
    job.model = LlamaModel(job.llama)
    k_init, k_move, k_batch = jax.random.split(jax.random.key(56), 3)
    variables = job.model.init(k_init, jnp.zeros((1, 8), jnp.int32))
    leaves, tree = jax.tree.flatten(variables["params"])
    keys = jax.random.split(k_move, len(leaves))
    params = {"params": jax.tree.unflatten(tree, [
        leaf + 0.1 * (jnp.std(leaf) or 1.0) * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])}
    batch = job.make_batch(k_batch)
    with jax.default_matmul_precision("highest"):
        wanted = jax.jit(lambda p: reference.loss_and_grads(
            job.to_reference(p), batch, config))(params)
    return job, config, params, variables[llama.ROUTER_STATE], batch, wanted


def _distance(job, params, bias, batch, wanted):
    """(|loss - reference loss|, the gradient's relative distance over all
    leaves, the worst leaf's) of the program on ``params`` from ``wanted``,
    the reference's loss and gradients."""
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.jit(jax.value_and_grad(
            job.loss_fn, has_aux=True))(params, bias, batch)
    ref_loss, ref_grads = wanted
    off = jax.tree.map(lambda g, r: jnp.sum(jnp.square(g - r)),
                       job.to_reference(grads), ref_grads)
    size = jax.tree.map(lambda r: jnp.sum(jnp.square(r)), ref_grads)
    off, size = (np.asarray(jax.tree.leaves(t)) for t in (off, size))
    return (abs(float(loss - ref_loss)),
            float(np.sqrt(off.sum() / size.sum())),
            float(np.max(np.sqrt(off / (size + 1e-30)))))


def test_program_matches_reference_loss_and_every_gradient_leaf(tiny):
    """Both layer kinds, the dense and the routed feed-forward, the tied
    head: the loss and every leaf's gradient."""
    job, _, params, bias, batch, wanted = tiny
    loss_off, grad_off, worst_leaf = _distance(job, params, bias, batch,
                                               wanted)
    assert loss_off < 2e-5 and grad_off < 1e-4 and worst_leaf < 2e-3


def _one_token_off(x, taps):
    """The filter moved one token on: y[t] reads x[t + 1] (not causal)."""
    ahead = jnp.pad(x, ((0, 0), (0, 1), (0, 0)))[:, 1:]
    return short_conv._short_convolution(ahead, taps)


def _plain(mixer):
    """A stand-in for ``short_conv._gated_plain`` that computes ``mixer(B,
    C, z, taps)`` of y's thirds."""
    return jax.checkpoint(lambda y, taps: mixer(
        *(t.astype(jnp.float32) for t in jnp.split(y, 3, axis=-1)), taps))


@pytest.mark.parametrize("variant", [
    "c_gate_left_out", "filter_one_token_off", "qk_norm_dropped",
    "silu_in_the_filter", "untied_head_of_ones"])
def test_a_wrong_version_fails_the_comparison(tiny, monkeypatch, variant):
    """Each departure from the published layer, alone, in the PROGRAM: on
    parameters moved off their start the LOSS is already outside what the
    job as it is keeps to (2e-5), so the forward pass alone is compiled."""
    job, _, params, bias, batch, wanted = tiny
    wrong = params
    if variant == "c_gate_left_out":
        monkeypatch.setattr(short_conv, "_gated_plain", _plain(
            lambda b, c, z, taps: short_conv._short_convolution(b * z, taps)))
    elif variant == "filter_one_token_off":
        monkeypatch.setattr(short_conv, "_gated_plain", _plain(
            lambda b, c, z, taps: c * _one_token_off(b * z, taps)))
    elif variant == "qk_norm_dropped":
        job = _copy_with(job, qk_norm=False)
        wrong = {"params": {
            name: ({**layer, "attn": {
                n: w for n, w in layer["attn"].items()
                if n not in ("q_norm", "k_norm")}}
                   if "attn" in layer else layer)
            for name, layer in params["params"].items()}}
    elif variant == "silu_in_the_filter":
        monkeypatch.setattr(short_conv, "_gated_plain", _plain(
            lambda b, c, z, taps: c * jax.nn.silu(
                short_conv._short_convolution(b * z, taps))))
    elif variant == "untied_head_of_ones":
        job = _copy_with(job, tie_word_embeddings=False)
        wrong = {"params": {**params["params"], "lm_head": {
            "kernel": params["params"]["tok_emb"]["embedding"].T * 1.01}}}
    try:
        loss, _ = jax.jit(job.loss_fn)(wrong, bias, batch)
    finally:
        monkeypatch.undo()
    assert abs(float(loss - wanted[0])) > 2e-4, (variant, float(loss))


def _copy_with(job, **changes):
    other = copy.copy(job)
    other.llama = dataclasses.replace(job.llama, **changes)
    other.model = LlamaModel(other.llama)
    return other


# -- the router and the experts ---------------------------------------------

def _routed_layer(tiny, held=None, first=None):
    job, config, params = tiny[:3]
    cfg = job.llama if held is None else dataclasses.replace(
        job.llama, held_experts=held, first_held_expert=first)
    return cfg, config, params["params"]["layer_2"]["moe"]


def _reference_layer(cfg, moe):
    width = cfg.moe_intermediate_size
    return {"router": moe["router"]["kernel"],
            "experts": {"w_gate": moe["w_gate_up"][..., :width],
                        "w_up": moe["w_gate_up"][..., width:],
                        "w_down": moe["w_down"]}}


def test_nonzero_bias_moves_the_choice_and_not_the_gates(tiny):
    """The bias added to the gates, and not to the choice alone, is another
    layer: the reference given the bias for its CHOICE agrees with the
    program to float32, and given it in the gates too it does not."""
    cfg, config, moe = _routed_layer(tiny)
    experts = cfg.num_experts
    u = jax.random.normal(jax.random.key(5), (2, 64, cfg.hidden_size))
    bias = jnp.zeros(experts).at[5].set(5.0).at[4].set(-5.0)

    def apply(moe, bias):
        return llama.RoutedExperts(cfg).apply(
            {"params": moe, llama.ROUTER_STATE: {"bias": bias}}, u,
            mutable=["moe_stats", "losses"])

    plain, stats0 = apply(moe, jnp.zeros(experts))
    biased, stats = apply(moe, bias)
    counts0 = stats0["moe_stats"]["assignments_per_expert"][0]
    counts = stats["moe_stats"]["assignments_per_expert"][0]
    assert counts[5] == 2 * 64 and counts[4] == 0       # every token, none
    assert 0 < counts0[5] < 2 * 64 and counts0[4] > 0
    layer = _reference_layer(cfg, moe)
    with jax.default_matmul_precision("highest"):
        wanted, _ = reference.routed_experts(u, layer, config, bias)
        wanted0, _ = reference.routed_experts(u, layer, config)
    np.testing.assert_allclose(biased, wanted, atol=2e-5)
    np.testing.assert_allclose(plain, wanted0, atol=2e-5)
    assert float(jnp.max(jnp.abs(biased - plain))) > 1e-2

    def biased_gates(scores, chosen, config):
        gates = jnp.take_along_axis(scores + bias, chosen, axis=-1)
        return gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)

    right = reference.gates_of
    reference.gates_of = biased_gates
    try:
        with jax.default_matmul_precision("highest"):
            wrong, _ = reference.routed_experts(u, layer, config, bias)
    finally:
        reference.gates_of = right
    assert float(jnp.max(jnp.abs(biased - wrong))) > 1e-2
    # No gradient reaches the bias.
    g_bias = jax.grad(lambda b: jnp.sum(apply(moe, b)[0] ** 2))(bias)
    assert not np.asarray(g_bias).any()


def test_four_shares_add_up_to_the_uncut_references_layer(tiny):
    """The cut's arithmetic (model-configs guide, section 4): the four
    shares' routed parts, each chip holding its own four experts of sixteen
    (ids 0-3 .. 12-15), add up to the UNCUT REFERENCE's routed layer; there
    is no shared expert to count once.  The router and its bias are whole on
    every chip."""
    cfg, config, moe = _routed_layer(tiny)
    experts, width = cfg.num_experts, cfg.moe_intermediate_size
    k_up, k_down, k_u = jax.random.split(jax.random.key(9), 3)
    w_gate_up = jax.random.normal(
        k_up, (experts, *moe["w_gate_up"].shape[1:])) * 0.1
    w_down = jax.random.normal(k_down,
                               (experts, *moe["w_down"].shape[1:])) * 0.1
    u = jax.random.normal(k_u, (2, 32, cfg.hidden_size))
    bias = 0.05 * jnp.arange(experts, dtype=jnp.float32)
    held = experts // 4

    def share(first):
        part = dataclasses.replace(cfg, held_experts=held,
                                   first_held_expert=first)
        weights = {**moe, "w_gate_up": w_gate_up[first:first + held],
                   "w_down": w_down[first:first + held]}
        return llama.RoutedExperts(part).apply(
            {"params": weights, llama.ROUTER_STATE: {"bias": bias}}, u)

    whole = {"w_gate": w_gate_up[..., :width], "w_up": w_gate_up[..., width:],
             "w_down": w_down}
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.routed_experts(
            u, {"router": moe["router"]["kernel"]}, config, bias, first=0,
            experts=whole)
        shares = sum(share(first) for first in range(0, experts, held))
    np.testing.assert_allclose(shares, uncut, atol=2e-5)
    # And a share alone is not the layer.
    assert float(jnp.max(jnp.abs(share(0) - uncut))) > 1e-2


# -- the gated filter's two bodies ------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_gated_pass_interpreted_is_the_jnp_body(dtype):
    """``C (taps * (B z))`` through the Mosaic pass (interpreted here) and
    through ``_gated_plain``, forward and all the gradients, two rows of 96
    tokens: three blocks of 32 rows, so every row's history crosses a block
    edge twice and a batch row's start once."""
    width, seq = 256, 96
    k_y, k_t, k_g = jax.random.split(jax.random.key(3), 3)
    y = jax.random.normal(k_y, (2, seq, 3 * width)).astype(dtype)
    taps = jax.random.uniform(k_t, (3, width), jnp.float32, -0.6, 0.6)
    g = jax.random.normal(k_g, (2, seq, width)).astype(dtype)
    assert short_conv._pick_rows(seq, 3 * width) == 32

    def both(in_place):
        def run(y, taps):
            out, vjp = jax.vjp(lambda y, t: short_conv.convolved(
                y, t, 1, None, in_place, gated=True), y, taps)
            return out, vjp(g)
        return jax.jit(run)(y, taps)

    before = short_conv.body_counts()
    out, (dy, dtaps) = both(True)
    after = short_conv.body_counts()
    assert after["fused"] == before["fused"] + 1
    assert after["plain"] == before["plain"]
    want, (want_dy, want_dtaps) = both(False)
    assert out.shape == (2, seq, width) and dy.shape == y.shape
    assert out.dtype == dy.dtype == dtype and dtaps.dtype == jnp.float32
    rounding = 1e-5 if dtype == jnp.float32 else 1e-2
    for got, wanted in ((out, want), (dy, want_dy), (dtaps, want_dtaps)):
        got, wanted = (np.asarray(t, np.float32) for t in (got, wanted))
        assert np.max(np.abs(got - wanted)) <= rounding * np.max(
            np.abs(wanted)), np.max(np.abs(got - wanted))
    # Against the definition, token by token, in float64.
    b, c, z = (np.asarray(t, np.float64) for t in jnp.split(y, 3, axis=-1))
    p, w = b * z, np.asarray(taps, np.float64)
    direct = np.zeros_like(p)
    for t in range(seq):
        for i in range(3):
            if t - 2 + i >= 0:
                direct[:, t] += w[i] * p[:, t - 2 + i]
    np.testing.assert_allclose(np.asarray(want, np.float64), c * direct,
                               atol=30 * rounding)


def test_a_gated_call_refuses_what_does_not_go_with_it():
    y, taps = jnp.zeros((1, 32, 384)), jnp.zeros((3, 128))
    with pytest.raises(ValueError, match="gated filter"):
        short_conv.convolved(y, taps, 1, 1.0, False, gated=True)
    with pytest.raises(ValueError, match="gated filter"):
        short_conv.convolved(y, taps, 1, None, False, bias=jnp.zeros(128),
                             gated=True)
    # Thirds that start at no lane tile take the ``jnp`` body, by name.
    before = short_conv.body_counts()["plain"].get(
        short_conv._NOT_AT_A_TILE, 0)
    short_conv.convolved(jnp.zeros((1, 32, 3 * 96)), jnp.zeros((3, 96)), 1,
                         None, True, gated=True)
    assert short_conv.body_counts()["plain"][short_conv._NOT_AT_A_TILE] == (
        before + 1)


_OPERAND = re.compile(r"tensor<[^>]*>")


def _mosaic_signatures(fn, *args):
    """(operands, results) of each ``tpu_custom_call`` in ``fn`` lowered for
    ``tpu`` (no compiler needed), in order."""
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = []
    for line in text.splitlines():
        if "stablehlo.custom_call @tpu_custom_call" not in line:
            continue
        ins, outs = line.rsplit(" : ", 1)[1].split(" -> ")
        calls.append((len(_OPERAND.findall(ins)), len(_OPERAND.findall(outs))))
    return calls


def test_operand_counts_of_the_two_calls_with_gates_and_without(monkeypatch):
    """A gated call reads ONE array (the projection's output: block, rows
    before, and backward rows after) and the taps, and writes one: the
    operand counts of a call without gates, which are the parent's (forward
    3 in / 1 out, backward 6 in / 2 out)."""
    monkeypatch.setattr(short_conv, "_interpret", lambda: False)

    def grads(gated, width):
        def run(y, taps):
            return jax.vjp(lambda y, t: short_conv.convolved(
                y, t, 1, None, True, gated=gated), y, taps)[1](
                    jnp.ones((2, 64, 128), jnp.bfloat16))
        return _mosaic_signatures(
            run, jax.ShapeDtypeStruct((2, 64, width), jnp.bfloat16),
            jax.ShapeDtypeStruct((3, 128), jnp.float32))

    def forward(gated, width):
        return _mosaic_signatures(
            lambda y, t: short_conv.convolved(y, t, 1, None, True,
                                              gated=gated),
            jax.ShapeDtypeStruct((2, 64, width), jnp.bfloat16),
            jax.ShapeDtypeStruct((3, 128), jnp.float32))

    assert forward(False, 128) == forward(True, 384) == [(3, 1)]
    assert grads(False, 128) == grads(True, 384) == [(6, 2)]


def test_a_conv_layer_in_place_is_the_layer_it_is_elsewhere(tiny):
    """``GatedShortConv(in_place=True)`` sends its gates and its filter
    through the Mosaic pass (interpreted here), which reads B, C and z where
    ``in_proj`` left them; ``in_place=False`` through the ``jnp`` body,
    which the reference holds above: the same output and the same gradient
    of every leaf and of x."""
    config = tiny[0].llama
    k_init, k_x = jax.random.split(jax.random.key(57))
    x = jax.random.normal(k_x, (2, 96, config.hidden_size))
    layer = llama.GatedShortConv(config).init(k_init, x)["params"]
    assert sorted(layer) == ["conv_w", "in_proj", "out_proj"]

    def both_ways(in_place):
        module = llama.GatedShortConv(config, in_place=in_place)

        def run(p, x):
            out, vjp = jax.vjp(lambda p, x: module.apply({"params": p}, x),
                               p, x)
            return {"out": out, "grads": vjp(jnp.cos(out))}
        return {jax.tree_util.keystr(path): leaf for path, leaf in
                jax.tree_util.tree_leaves_with_path(jax.jit(run)(layer, x))}

    before = short_conv.body_counts()
    got = both_ways(True)
    assert short_conv.body_counts()["fused"] == before["fused"] + 1
    wanted = both_ways(False)
    assert "['grads'][0]['conv_w']" in got and len(got) == 5
    for name, want in wanted.items():
        off = float(jnp.linalg.norm(got[name] - want) / jnp.linalg.norm(want))
        assert off < 1e-5, (name, off)


# -- the stack and the config -----------------------------------------------

def test_layers_by_type_and_a_tied_head(tiny):
    _, _, params, bias = tiny[:4]
    layers = params["params"]
    assert {k: sorted(v) for k, v in layers.items() if k.startswith(
        "layer_")} == {
            "layer_0": ["conv", "mlp", "norm_attn", "norm_mlp"],
            "layer_1": ["attn", "moe", "norm_attn", "norm_mlp"],
            "layer_2": ["conv", "moe", "norm_attn", "norm_mlp"],
            "layer_3": ["conv", "moe", "norm_attn", "norm_mlp"],
            "layer_4": ["conv", "moe", "norm_attn", "norm_mlp"]}
    assert "lm_head" not in layers
    assert sorted(bias) == ["layer_1", "layer_2", "layer_3", "layer_4"]
    assert sorted(layers["layer_2"]["moe"]) == ["router", "w_down",
                                                "w_gate_up"]
    assert sorted(layers["layer_1"]["attn"]) == ["k_norm", "q_norm", "wk",
                                                 "wo", "wq", "wv"]


def test_config_knows_and_refuses_the_new_kind():
    config = LlamaConfig.tiny()
    stack = dataclasses.replace(
        config, layer_types=("conv", "full_attention"), conv_L_cache=3)
    assert [stack.is_conv(i) for i in range(2)] == [True, False]
    assert [stack.is_linear(i) for i in range(2)] == [False, False]
    assert stack.has_conv_layers and not config.has_conv_layers
    assert [stack.layer_type(i) for i in range(2)] == ["conv",
                                                       "full_attention"]
    assert stack.window_of(0) is None and stack.kind_of(0) is None
    with pytest.raises(ValueError, match="conv_bias is True"):
        dataclasses.replace(stack, conv_bias=True)
    with pytest.raises(ValueError, match="at least one tap"):
        dataclasses.replace(stack, conv_L_cache=0)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(config, layer_types=("conv", "convolution"))
    with pytest.raises(NotImplementedError,
                       match=r"generation .* short-convolution .*'conv'"):
        stack.refuse_new_kinds("generation")


@pytest.mark.parametrize("who", ["generation", "serve", "pipeline"])
def test_the_other_paths_refuse_a_conv_layer_by_name(who):
    from horovod_tpu.models.generation import prefill
    from horovod_tpu.parallel.pipeline import init_pipelined_llama

    stack = dataclasses.replace(LlamaConfig.tiny(),
                                layer_types=("conv", "full_attention"))
    with pytest.raises(NotImplementedError,
                       match="layer_types holds 'conv'") as refusal:
        if who == "generation":
            prefill(stack, {}, jnp.zeros((1, 4), jnp.int32), cache_len=8)
        elif who == "serve":
            stack.refuse_new_kinds("the paged KV cache")
        else:
            init_pipelined_llama(stack, jax.random.key(0), 1)
    assert "last 2 gated inputs" in str(refusal.value)
