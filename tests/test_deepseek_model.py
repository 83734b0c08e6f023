"""DeepSeek-V2-Lite's attention and whole model in ``models/llama.py``
against the plain reference (``benchmark/reference/deepseek_v2_lite.py``), on
the CPU at tiny widths with seeded weights: YaRN, latent attention through
the flash kernel at a value width of its own, the whole model's loss and
gradient, what the comparison's limits catch, and who refuses the new kinds.
The routed layer alone is in ``tests/test_deepseek_moe.py``, whose helpers
these tests share."""

import dataclasses
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v2_lite as ref
from horovod_tpu.models import llama
from horovod_tpu.models.llama import (LatentAttention, LlamaConfig,
                                      LlamaModel, YarnScaling,
                                      causal_attention, rope_freqs)
from horovod_tpu.ops.flash_attention import (flash_attention,
                                             flash_attention_fn)
from horovod_tpu.ops.losses import balance_loss, softmax_cross_entropy
from tests.test_deepseek_moe import (REF, YARN, routed_reference_params,
                                     seeded, tiny)


# -- YaRN -----------------------------------------------------------------------

def test_yarn_frequencies_and_scale_by_hand():
    """DeepSeek-V2-Lite's rope_scaling on 64 rotary dims: pairs below 10
    keep theta^(-2i/64), pairs from 23 turn 40 times slower, a linear blend
    between; cos and sin are not scaled; m = 0.1 x 0.707 ln 40 + 1."""
    yarn = YarnScaling(**YARN)
    assert yarn.correction_range(64, 10000.0) == (10, 23)
    assert yarn.table_scale == pytest.approx(1.0)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert yarn.softmax_scale == pytest.approx(1.5896, abs=1e-4)
    assert ref.softmax_scale({**REF, "qk_nope_head_dim": 128,
                              "qk_rope_head_dim": 64}) == pytest.approx(
        192 ** -0.5 * m * m)
    cos, sin = rope_freqs(64, 3, 10000.0, scaling=yarn)
    plain = 10000.0 ** (-2 * np.arange(32) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = plain / 40 * ramp + plain * (1 - ramp)
    np.testing.assert_allclose(np.arctan2(sin[1], cos[1]), want, rtol=1e-5)
    np.testing.assert_allclose(np.arctan2(sin[2], cos[2]) / 2, want,
                               rtol=1e-5)
    assert want[9] == pytest.approx(plain[9])
    assert want[23] == pytest.approx(plain[23] / 40)
    assert want[16] == pytest.approx(plain[16] * (1 - 6 / 13 * 39 / 40))
    ours, _ = ref.yarn_inverse_frequencies(64, 10000.0, YARN)
    np.testing.assert_allclose(ours, want, rtol=1e-6)
    unscaled, _ = rope_freqs(64, 3, 10000.0)
    unscaled_sin = rope_freqs(64, 3, 10000.0)[1]
    np.testing.assert_allclose(np.arctan2(unscaled_sin[1], unscaled[1]),
                               plain, rtol=1e-5)


# -- latent attention -----------------------------------------------------------

def attention_reference_params(attn):
    return {"wq": attn["wq"]["kernel"], "wkv_a": attn["wkv_a"]["kernel"],
            "kv_norm": attn["kv_norm"]["scale"],
            "wkv_b": attn["wkv_b"]["kernel"], "wo": attn["wo"]["kernel"]}


@pytest.mark.parametrize("attention_fn", [causal_attention,
                                          flash_attention_fn])
def test_latent_attention_forward_and_gradient(attention_fn):
    """Against the reference's dense attention in float32; through the flash
    kernel (interpret mode) the keys are 16 + 8 wide and the values 16, both
    padded to the kernel's tile and the scale riding as sm_scale."""
    cfg = tiny()
    x = jax.random.normal(jax.random.key(2), (2, 32, cfg.hidden_size))
    cos, sin = rope_freqs(cfg.rope_dim, 32, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    module = LatentAttention(cfg, attention_fn=attention_fn)
    params = seeded(module, x, cos, sin)
    params["params"]["kv_norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.key(9), (cfg.kv_lora_rank,))
    weight = jax.random.normal(jax.random.key(4), x.shape)

    def program(params, x):
        return jnp.sum(module.apply(params, x, cos, sin) * weight)

    def reference(attn, x):
        return jnp.sum(ref.latent_attention(
            x, attention_reference_params(attn), REF) * weight)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(module.apply)(params, x, cos, sin)
        want = jax.jit(lambda attn, x: ref.latent_attention(
            x, attention_reference_params(attn), REF))(params["params"], x)
        grads, grad_x = jax.jit(jax.grad(program, argnums=(0, 1)))(params, x)
        want_grads, want_x = jax.jit(jax.grad(reference, argnums=(0, 1)))(
            params["params"], x)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(grad_x, want_x, rtol=2e-4, atol=2e-5)
    for g, w in zip(jax.tree.leaves(grads["params"]),
                    jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=5e-5)


@pytest.mark.parametrize("d_qk, d_v", [(192, 128), (128, 64), (64, 128)])
def test_flash_kernel_at_a_value_width_of_its_own(d_qk, d_v):
    """The kernel itself, un-padded widths, interpret mode, forward and the
    one backward call, against dense attention."""
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (1, 256, 2, d_qk))
    k = jax.random.normal(keys[1], (1, 256, 2, d_qk))
    v = jax.random.normal(keys[2], (1, 256, 2, d_v))
    weight = jax.random.normal(keys[3], (1, 256, 2, d_v))
    scale = 1.5896 * d_qk ** -0.5

    def through(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * weight)

    flash = lambda q, k, v: flash_attention(q, k, v, _sm_scale=scale)
    dense = lambda q, k, v: causal_attention(q, k, v, scale=scale)
    with jax.default_matmul_precision("highest"):
        out = flash(q, k, v)
        assert out.shape == (1, 256, 2, d_v)
        np.testing.assert_allclose(out, dense(q, k, v), rtol=2e-5,
                                   atol=2e-5)
        got = jax.grad(through(flash), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(through(dense), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


# -- the whole model -------------------------------------------------------------

def model_reference_params(cfg, params):
    p = params["params"]
    layers = []
    for i in range(cfg.num_layers):
        layer = p[f"layer_{i}"]
        out = {"norm_attn": layer["norm_attn"]["scale"],
               "norm_mlp": layer["norm_mlp"]["scale"],
               **attention_reference_params(layer["attn"])}
        if cfg.is_routed(i):
            out.update(routed_reference_params(layer["moe"], 16))
        else:
            gate_up = layer["mlp"]["w_gate_up"]["kernel"]
            out.update({"w_gate": gate_up[:, :80], "w_up": gate_up[:, 80:],
                        "w_down": layer["mlp"]["w_down"]["kernel"]})
        layers.append(out)
    return {"embed": p["tok_emb"]["embedding"], "layers": layers,
            "norm_f": p["norm_f"]["scale"], "lm_head": p["lm_head"]["kernel"]}


def _model_case(dtype=jnp.float32, alpha=0.5, **changes):
    cfg = tiny(held_experts=8, first_held_expert=16, dtype=dtype,
               logits_dtype=dtype, **changes)
    model = LlamaModel(cfg, attention_fn=flash_attention_fn)
    tokens = jax.random.randint(jax.random.key(6), (2, 65), 0, 128)
    params = seeded(LlamaModel(cfg), tokens[:, :8], scale=4.0)
    config = {**REF, "deployment": {"first_held_expert": 16},
              "assumed": {"aux_loss_alpha": alpha}}

    def loss_fn(params, tokens, model=model):
        logits, sown = model.apply(params, tokens[:, :-1],
                                   mutable=["losses"])
        return (softmax_cross_entropy(logits, tokens[:, 1:])
                + alpha * balance_loss(sown))

    return cfg, model, params, tokens, config, loss_fn


def _reference(cfg, params, tokens, config):
    """The float32 reference's (loss, gradients), as one program."""
    return jax.jit(lambda p, t: ref.loss_and_grads(p, t, config))(
        model_reference_params(cfg, params), tokens)


def _distance(got, want):
    off = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32) - w)))
              for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    size = sum(float(jnp.sum(jnp.square(w))) for w in jax.tree.leaves(want))
    return math.sqrt(off / size)


@pytest.mark.parametrize("remat", ["none", "layer_keep_attention"])
def test_whole_model_loss_and_gradient_are_the_references(remat):
    """One dense and two routed layers holding experts 16 to 23 of 64,
    latent attention through the flash kernel, the balance loss at a weight
    that shows: float32 against the reference, with and without each layer
    recomputed (the routed layer's sown loss crosses ``nn.remat``)."""
    cfg, _, params, tokens, config, loss_fn = _model_case(remat=remat)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, tokens)
        want, want_grads = _reference(cfg, params, tokens, config)
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    assert _distance(model_reference_params(cfg, grads),
                     want_grads) < 2e-4


# What the benchmark's comparison must catch, at the tiny size in bf16: the
# job as it is reads a few per cent, each defect several times that.

def _an_expert_left_out(cfg, model, params):
    moe = params["params"]["layer_1"]["moe"]
    zeroed = moe["w_down"].at[3].set(0.0)
    return {"params": {**params["params"], "layer_1": {
        **params["params"]["layer_1"], "moe": {**moe, "w_down": zeroed}}}}


DEFECTS = {
    "an_expert_left_out": dict(params=_an_expert_left_out),
    "shared_experts_left_out": dict(config=dict(shared_experts=0)),
    "renormalised_gates": dict(config=dict(norm_topk_prob=True)),
    "softmax_scale_without_m2": dict(config=dict(
        rope_scaling=YarnScaling(**{**YARN, "mscale_all_dim": 0.0}))),
}


@pytest.fixture(scope="module")
def bf16_case():
    """The bf16 case of the eight tests below, computed once: the model and
    its parameters (float32 for the reference, bf16 to run), the reference's
    loss and gradients, and those of the program as it is."""
    cfg, model, params, tokens, config, loss_fn = _model_case(
        dtype=jnp.bfloat16, alpha=0.001)
    want, want_grads = _reference(cfg, params, tokens, config)
    run_params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(p, tokens))(run_params)
    return types.SimpleNamespace(
        cfg=cfg, model=model, run_params=run_params, tokens=tokens,
        loss_fn=loss_fn, want=want, want_grads=want_grads, loss=loss,
        grads=grads)


@pytest.mark.parametrize("defect, least", [
    (None, 0.0), ("an_expert_left_out", 0.055), ("renormalised_gates", 0.055),
    ("shared_experts_left_out", 0.12), ("softmax_scale_without_m2", 0.12)])
def test_what_the_limits_catch_in_bf16(defect, least, bf16_case):
    """bf16 weights and activations against the float32 reference, by the
    benchmark's distance (L2 over all parameters): the model as it is reads
    3.9 % here, inside the tiny cell's 6 %.  The shared experts left out and
    m squared dropped from the softmax scale read far beyond it.  One held
    expert's output left out and renormalised gates read 6 %, at the edge:
    with 8 of 64 experts held and gates that are not renormalised the held
    experts carry a few per cent of a layer's signal, so the distance over
    ALL parameters moves little when one of them is wrong (in float32, where
    nothing else differs, they read 4.6 % and 4.2 %).  What catches those is
    the share test above and the layer's own counters."""
    cfg, model, tokens = bf16_case.cfg, bf16_case.model, bf16_case.tokens
    if defect is None:
        distance = _distance(model_reference_params(cfg, bf16_case.grads),
                             bf16_case.want_grads)
        assert distance < 0.06
        assert abs(float(bf16_case.loss) - float(bf16_case.want)) < 0.02
        return
    # A defect edits dictionaries of its own, not the fixture's.
    run_params = jax.tree.map(lambda p: p, bf16_case.run_params)
    change = DEFECTS[defect]
    if "config" in change:
        # The same parameters run by a model with the defect.
        wrong = dataclasses.replace(cfg, **change["config"])
        model = LlamaModel(wrong, attention_fn=flash_attention_fn)
    else:
        run_params = change["params"](cfg, model, run_params)
    shared = {i: run_params["params"][f"layer_{i}"]["moe"]["shared"]
              for i in (1, 2)}
    if defect == "shared_experts_left_out":
        for i in (1, 2):
            run_params["params"][f"layer_{i}"]["moe"].pop("shared")
    _, grads = jax.value_and_grad(
        lambda p: bf16_case.loss_fn(p, tokens, model))(run_params)
    if defect == "shared_experts_left_out":
        for i in (1, 2):
            grads["params"][f"layer_{i}"]["moe"]["shared"] = jax.tree.map(
                jnp.zeros_like, shared[i])
    distance = _distance(model_reference_params(cfg, grads),
                         bf16_case.want_grads)
    assert distance > least, (defect, distance)


@pytest.mark.parametrize("precision, least", [("bfloat16", 0.0),
                                              ("router_bf16", 0.0),
                                              ("float8_e5m2", 0.15)])
def test_a_precision_below_bf16_is_outside_the_limit(precision, least,
                                                     monkeypatch, bf16_case):
    """Matmul inputs rounded to float8 (e5m2, bf16's exponent range and two
    mantissa bits) read several times what bf16 reads; the router's product
    in bf16 (where the configuration states float32) flips choices and
    reads more than the model as it is."""
    cfg, tokens, loss_fn = bf16_case.cfg, bf16_case.tokens, bf16_case.loss_fn
    want_grads, run_params = bf16_case.want_grads, bf16_case.run_params

    def rounded(p):
        if precision != "float8_e5m2":
            return p
        return jax.tree.map(
            lambda w: w.astype(jnp.float8_e5m2).astype(w.dtype)
            if w.ndim >= 2 else w, p)

    if precision == "router_bf16":
        real = llama.nn.Dense

        def dense(features, **options):
            if options.get("name") == "router":
                options["dtype"] = jnp.bfloat16
            return real(features, **options)

        monkeypatch.setattr(llama.nn, "Dense", dense)
    _, grads = jax.value_and_grad(
        lambda p: loss_fn(rounded(p), tokens))(run_params)
    monkeypatch.undo()
    distance = _distance(model_reference_params(cfg, grads), want_grads)
    as_it_is = _distance(model_reference_params(cfg, bf16_case.grads),
                         want_grads)
    if precision == "bfloat16":
        assert distance == pytest.approx(as_it_is) and distance < 0.06
    elif precision == "router_bf16":
        assert distance > as_it_is
    else:
        assert distance > least and distance > 3 * as_it_is


# -- who refuses the new kinds ---------------------------------------------------

def test_generation_serve_and_pipeline_refuse_the_new_kinds_by_name():
    from horovod_tpu.models.generation import prefill
    from horovod_tpu.parallel.pipeline import init_pipelined_llama

    latent = dataclasses.replace(tiny(), num_experts=1)
    routed = LlamaConfig.tiny(num_experts=4)
    ids = jnp.zeros((1, 4), jnp.int32)
    for cfg, word in ((latent, "latent"), (routed, "routed")):
        with pytest.raises(NotImplementedError, match=word):
            prefill(cfg, {}, ids, cache_len=8)
        with pytest.raises(NotImplementedError, match=word):
            cfg.refuse_new_kinds("the paged KV cache")
        with pytest.raises(NotImplementedError, match=word):
            init_pipelined_llama(cfg, jax.random.key(0), 1)


def test_config_refuses_what_it_cannot_be():
    with pytest.raises(ValueError, match="attention_kind"):
        LlamaConfig(attention_kind="linear")
    with pytest.raises(ValueError, match="kv_lora_rank"):
        LlamaConfig(attention_kind="latent")
    with pytest.raises(ValueError, match="not among"):
        tiny(held_experts=8, first_held_expert=60)
