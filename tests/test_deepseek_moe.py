"""DeepSeek-V2-Lite's layers in ``models/llama.py`` against the plain
reference (``benchmark/reference/deepseek_v2_lite.py``), on the CPU at tiny
widths with seeded weights: the routed layer and its shares, latent
attention through the flash kernel at a value width of its own, YaRN, the
balance loss, the whole model's loss and gradient, and what the
comparison's limits catch."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v2_lite as ref
from horovod_tpu.models import llama
from horovod_tpu.models.llama import (LatentAttention, LlamaConfig,
                                      LlamaModel, RoutedExperts,
                                      YarnScaling, causal_attention,
                                      rope_freqs)
from horovod_tpu.ops.flash_attention import (flash_attention,
                                             flash_attention_fn)
from horovod_tpu.ops.losses import (balance_loss, sequence_balance_loss,
                                    softmax_cross_entropy)

YARN = dict(beta_fast=32, beta_slow=1, factor=40, mscale=0.707,
            mscale_all_dim=0.707, original_max_position_embeddings=4096)
EXPERTS, PER_TOKEN, SHARES = 64, 6, 8

# The published config's keys at tiny widths, as the reference reads them.
REF = {
    "num_attention_heads": 2, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 24, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "rope_scaling": {**YARN, "type": "yarn"},
    "num_experts_per_tok": PER_TOKEN, "norm_topk_prob": False,
    "routed_scaling_factor": 1.0,
    "deployment": {"first_held_expert": 0},
    "assumed": {"aux_loss_alpha": 0.001},
}


def tiny(**changes) -> LlamaConfig:
    base = dict(
        vocab_size=128, hidden_size=32, num_layers=3, num_heads=2,
        num_kv_heads=2, intermediate_size=80, max_seq_len=64, rms_eps=1e-6,
        num_experts=EXPERTS, experts_per_token=PER_TOKEN,
        moe_intermediate_size=16, shared_experts=2, first_dense_layers=1,
        norm_topk_prob=False, attention_kind="latent", kv_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_scaling=YarnScaling(**YARN), dtype=jnp.float32,
        logits_dtype=jnp.float32)
    return LlamaConfig(**{**base, **changes})


def seeded(module, *inputs, seed=0, scale=None):
    """Variables of ``module``; the router's kernel scaled up so that the
    scores are far from uniform."""
    params = module.init(jax.random.key(seed), *inputs)
    if scale:
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf * scale
            if "router" in jax.tree_util.keystr(path) else leaf, params)
    return params


def routed_reference_params(moe, width):
    gate_up = moe["shared"]["w_gate_up"]["kernel"]
    return {"router": moe["router"]["kernel"],
            "experts": {"w_gate": moe["w_gate_up"][..., :width],
                        "w_up": moe["w_gate_up"][..., width:],
                        "w_down": moe["w_down"]},
            "shared": {"w_gate": gate_up[:, :gate_up.shape[1] // 2],
                       "w_up": gate_up[:, gate_up.shape[1] // 2:],
                       "w_down": moe["shared"]["w_down"]["kernel"]}}


@pytest.fixture(scope="module")
def whole_layer():
    """All 64 experts held: (config, params, x)."""
    cfg = tiny()
    x = jax.random.normal(jax.random.key(1), (2, 24, cfg.hidden_size))
    params = seeded(RoutedExperts(cfg), x, scale=6.0)
    return cfg, params, x


# -- the routed layer ---------------------------------------------------------

def test_whole_layer_is_the_reference(whole_layer):
    cfg, params, x = whole_layer
    y, sown = RoutedExperts(cfg).apply(params, x,
                                       mutable=["losses", "moe_stats"])
    with jax.default_matmul_precision("highest"):
        want, aux, chosen = ref.routed_experts(
            x, routed_reference_params(params["params"], 16), REF)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(sown["losses"]["balance"][0], aux, rtol=1e-6)
    rows = np.asarray(sown["moe_stats"]["rows_per_expert"][0])
    np.testing.assert_array_equal(
        rows, np.bincount(np.asarray(chosen).ravel(), minlength=EXPERTS))
    assert rows.sum() == 2 * 24 * PER_TOKEN and rows.max() >= 2 * rows.mean()
    assert int(sown["moe_stats"]["rows_dropped"][0]) == 0


def test_the_eight_shares_add_up_to_the_uncut_layer(whole_layer):
    """The guide's test of the cut: each share of 8 experts computed by the
    program with those experts' weights alone, the routed parts summed and
    the shared experts counted once, is the uncut 64-expert reference
    layer; and each share is the reference's for the same share."""
    cfg, params, x = whole_layer
    moe = params["params"]
    held = EXPERTS // SHARES
    shared_only = dataclasses.replace(cfg, held_experts=held)
    with jax.default_matmul_precision("highest"):
        whole, _, _ = ref.routed_experts(
            x, routed_reference_params(moe, 16), REF)
        shared = ref.swiglu(x, routed_reference_params(moe, 16)["shared"])
    routed_sum = jnp.zeros_like(whole)
    rows = []
    for share in range(SHARES):
        first = share * held
        share_cfg = dataclasses.replace(shared_only, first_held_expert=first)
        share_params = {"params": {
            **moe, "w_gate_up": moe["w_gate_up"][first:first + held],
            "w_down": moe["w_down"][first:first + held]}}
        y, sown = RoutedExperts(share_cfg).apply(
            share_params, x, mutable=["moe_stats"])
        with jax.default_matmul_precision("highest"):
            want, _, _ = ref.routed_experts(
                x, routed_reference_params(share_params["params"], 16),
                {**REF, "deployment": {"first_held_expert": first}})
        np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
        routed_sum = routed_sum + (y - shared)
        rows.append(np.asarray(sown["moe_stats"]["rows_per_expert"][0]))
        assert int(sown["moe_stats"]["rows_dropped"][0]) == 0
    np.testing.assert_allclose(routed_sum + shared, whole, rtol=5e-5,
                               atol=5e-6)
    assert np.concatenate(rows).sum() == 2 * 24 * PER_TOKEN


@pytest.mark.parametrize("case", ["all_held", "all_on_one", "none_held"])
def test_no_row_is_dropped_whatever_the_imbalance(case):
    """A router whose columns decide the choice: every token's six choices
    among the 8 held (the buffer's worst case: T x K rows), all tokens on
    the same six so that one held expert gets a row of every token, and no
    choice held at all."""
    cfg = tiny(held_experts=8, first_held_expert=8)
    x = jax.random.normal(jax.random.key(3), (2, 16, cfg.hidden_size))
    params = seeded(RoutedExperts(cfg), x)
    kernel = np.zeros((cfg.hidden_size, EXPERTS), np.float32)
    tokens = 2 * 16
    if case == "all_held":
        # Feature 0 and 1 split the tokens between experts 8-13 and 10-15.
        x = x.at[..., 0].set(jnp.where(jnp.arange(16) % 2, 9.0, -9.0))
        kernel[0, 8:14], kernel[0, 10:16] = -3.0, 3.0
        kernel[0, 10:14] = 0.0
        kernel[1, 8:16] = 0.0
        kernel[:, :8] = kernel[:, 16:] = 0.0
        bias_to_held = np.zeros(EXPERTS, np.float32)
        bias_to_held[8:16] = 50.0
        x = x.at[..., 2].set(1.0)
        kernel[2] = bias_to_held
    elif case == "all_on_one":
        x = x.at[..., 2].set(1.0)
        kernel[2, [9, 20, 21, 22, 23, 24]] = 50.0
    else:
        x = x.at[..., 2].set(1.0)
        kernel[2, 30:36] = 50.0
    params["params"]["router"]["kernel"] = jnp.asarray(kernel)
    y, sown = RoutedExperts(cfg).apply(params, x, mutable=["moe_stats"])
    rows = np.asarray(sown["moe_stats"]["rows_per_expert"][0])
    assert int(sown["moe_stats"]["rows_dropped"][0]) == 0
    assert rows.sum() == {"all_held": tokens * PER_TOKEN,
                          "all_on_one": tokens, "none_held": 0}[case]
    if case == "all_on_one":
        assert rows[1] == tokens
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.routed_experts(
            x, routed_reference_params(params["params"], 16),
            {**REF, "deployment": {"first_held_expert": 8}})
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
    grads = jax.grad(lambda p: jnp.sum(
        RoutedExperts(cfg).apply(p, x) ** 2))(params)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))


def test_gate_weights_are_not_renormalised(whole_layer):
    """``norm_topk_prob`` false: the six gates are the softmax's own values
    and sum to less than one; renormalised they give another layer."""
    cfg, params, x = whole_layer
    scores, _, gates = ref.route(x, params["params"]["router"]["kernel"],
                                 REF)
    assert float(jnp.max(jnp.sum(gates, -1))) < 1.0
    y = RoutedExperts(cfg).apply(params, x)
    renormalised = RoutedExperts(
        dataclasses.replace(cfg, norm_topk_prob=True)).apply(params, x)
    assert float(jnp.max(jnp.abs(y - renormalised))) > 1e-3
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.routed_experts(
            x, routed_reference_params(params["params"], 16),
            {**REF, "norm_topk_prob": True})
    np.testing.assert_allclose(renormalised, want, rtol=2e-5, atol=2e-6)


def test_routed_layer_gradient_is_the_references(whole_layer):
    cfg, params, x = whole_layer
    weight = jax.random.normal(jax.random.key(5), x.shape)

    def program(params, x):
        y, sown = RoutedExperts(cfg).apply(params, x, mutable=["losses"])
        return jnp.sum(y * weight) + 3.0 * balance_loss(sown)

    def reference(moe, x):
        y, aux, _ = ref.routed_experts(x, routed_reference_params(moe, 16),
                                       REF)
        return jnp.sum(y * weight) + 3.0 * aux

    got, got_x = jax.grad(program, argnums=(0, 1))(params, x)
    with jax.default_matmul_precision("highest"):
        want, want_x = jax.grad(reference, argnums=(0, 1))(
            params["params"], x)
    np.testing.assert_allclose(got_x, want_x, rtol=1e-4, atol=1e-5)
    for g, w in zip(jax.tree.leaves(got["params"]), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_balance_loss_and_its_gradient():
    """By hand: 1 under uniform routing, E / K when all pick the same K
    with all the weight; the gradient reaches the scores through P only."""
    scores = jnp.full((2, 8, 4), 0.25)
    chosen = jnp.tile(jnp.array([[0, 1], [2, 3]]), (2, 4, 1))
    assert float(sequence_balance_loss(scores, chosen)) == pytest.approx(1.0)
    peaked = jnp.tile(jnp.array([0.5, 0.5, 0.0, 0.0]), (2, 8, 1))
    same = jnp.zeros((2, 8, 2), jnp.int32).at[..., 1].set(1)
    assert float(sequence_balance_loss(peaked, same)) == pytest.approx(2.0)
    # d/d scores[b, s, e] = f[b, e] / (S B), f = counts E / (K S).
    grad = jax.grad(sequence_balance_loss)(scores, same)
    np.testing.assert_allclose(grad[0, 0], [2.0 / 16, 2.0 / 16, 0, 0])
    key = jax.random.key(7)
    scores = jax.nn.softmax(jax.random.normal(key, (3, 10, 16)))
    _, chosen = jax.lax.top_k(scores, 4)
    assert float(sequence_balance_loss(scores, chosen)) == pytest.approx(
        float(ref.balance(scores, chosen)), rel=1e-6)


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
        got = module.apply(params, x, cos, sin)
        want = ref.latent_attention(
            x, attention_reference_params(params["params"]), REF)
        grads, grad_x = jax.grad(program, argnums=(0, 1))(params, x)
        want_grads, want_x = jax.grad(reference, argnums=(0, 1))(
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
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        want, want_grads = ref.loss_and_grads(
            model_reference_params(cfg, params), tokens, config)
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


@pytest.mark.parametrize("defect, least", [
    (None, 0.0), ("an_expert_left_out", 0.055), ("renormalised_gates", 0.055),
    ("shared_experts_left_out", 0.12), ("softmax_scale_without_m2", 0.12)])
def test_what_the_limits_catch_in_bf16(defect, least):
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
    cfg, model, params, tokens, config, loss_fn = _model_case(
        dtype=jnp.bfloat16, alpha=0.001)
    reference = model_reference_params(cfg, params)
    run_params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    if defect:
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
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(p, tokens, model))(run_params)
    if defect == "shared_experts_left_out":
        for i in (1, 2):
            grads["params"][f"layer_{i}"]["moe"]["shared"] = jax.tree.map(
                jnp.zeros_like, shared[i])
    want, want_grads = ref.loss_and_grads(reference, tokens, config)
    distance = _distance(model_reference_params(cfg, grads), want_grads)
    if defect is None:
        assert distance < 0.06 and abs(float(loss) - float(want)) < 0.02
    else:
        assert distance > least, (defect, distance)


@pytest.mark.parametrize("precision, least", [("bfloat16", 0.0),
                                              ("router_bf16", 0.0),
                                              ("float8_e5m2", 0.15)])
def test_a_precision_below_bf16_is_outside_the_limit(precision, least,
                                                     monkeypatch):
    """Matmul inputs rounded to float8 (e5m2, bf16's exponent range and two
    mantissa bits) read several times what bf16 reads; the router's product
    in bf16 (where the configuration states float32) flips choices and
    reads more than the model as it is."""
    cfg, model, params, tokens, config, loss_fn = _model_case(
        dtype=jnp.bfloat16, alpha=0.001)
    reference = model_reference_params(cfg, params)
    want, want_grads = ref.loss_and_grads(reference, tokens, config)
    run_params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)

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
    _, plain = jax.value_and_grad(lambda p: loss_fn(p, tokens))(run_params)
    as_it_is = _distance(model_reference_params(cfg, plain), want_grads)
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


def _scans(jaxpr, length):
    """The ``lax.scan`` equations of that length, sub-jaxprs walked."""
    found = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "scan"
             and eqn.params["length"] == length]
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scans(sub, length)
    return found


# case: (experts, tokens a batch row, the tokens lifted (of 1 in `every`),
#        the lift of the held experts' logits, buffers that hold rows)
ROW_BUFFER_CASES = {
    "uniform": (64, 512, 0, 60.0, 1),
    "twice": (64, 512, (2, 5), 60.0, 2),
    "all_held": (64, 512, (1, 1), 60.0, 4),
    "a_32nd_uniform": (256, 680, 0, 60.0, 1),
    "a_32nd_thrice": (256, 680, (5, 34), 60.0, 3),
    "a_32nd_all_held": (256, 680, (1, 1), 60.0, 16),
    "a_32nd_none_held": (256, 680, (1, 1), -60.0, 0),
}


@pytest.mark.parametrize("case", ROW_BUFFER_CASES)
def test_as_many_row_buffers_run_as_there_are_rows_for(case, monkeypatch):
    """Experts 8 to 15 held, 6 choices a token.  Of 64, 1024 tokens: a
    buffer of 1536 rows (twice the 768 expected), four of which are the
    worst case.  A router that spreads its choices fills one; one that sends
    two tokens in five to the held eight two, with experts' rows split
    across buffers; every token all four.  Of 256, 1360 tokens (the
    ``laguna-s-2.1`` cell's share): sixteen buffers of 512 rows for 8160
    assignments, the last cut short, of which one, three, all sixteen and
    none hold rows.  Each is the reference, output and gradients with
    respect to parameters and input; the backward pass runs a buffer's body
    as often as the forward pass did, and no loop over all the buffers is
    left in it."""
    assert llama._row_chunk(98304, 8 / 64) == 24576
    assert llama._row_chunk(6144, 8 / 64) == 1536
    assert llama._row_chunk(6144, 1.0) == 6144
    assert llama._row_chunk(192, 8 / 64) == 192
    assert llama._row_chunk(81920, 8 / 256) == 5120
    assert llama._row_chunk(8160, 8 / 256) == 512
    experts, seq, lifted, lift, buffers = ROW_BUFFER_CASES[case]
    chunk, n_chunks = (1536, 4) if experts == 64 else (512, 16)
    cfg = tiny(num_experts=experts, held_experts=8, first_held_expert=8)
    x = jax.random.normal(jax.random.key(3), (2, seq, cfg.hidden_size))
    params = seeded(RoutedExperts(cfg), x, scale=2.0)
    if lifted:
        # A constant feature that lifts (or sinks) the held experts' logits
        # for some tokens in every so many: all six of their choices held.
        some, every = lifted
        x = x.at[..., 2].set(jnp.tile(
            (jnp.arange(seq) % every < some).astype(jnp.float32), (2, 1)))
        kernel = params["params"]["router"]["kernel"]
        params["params"]["router"]["kernel"] = kernel.at[2].set(
            jnp.where((jnp.arange(experts) >= 8)
                      & (jnp.arange(experts) < 16), lift, 0.0))
    y, sown = RoutedExperts(cfg).apply(params, x, mutable=["moe_stats"])
    rows = int(np.asarray(sown["moe_stats"]["rows_per_expert"][0]).sum())
    assert int(sown["moe_stats"]["row_buffers_run"][0]) == buffers, rows
    assert int(sown["moe_stats"]["rows_dropped"][0]) == 0
    assert -(-rows // chunk) == buffers

    # A mark on each buffer's tokens whose transpose counts the executions
    # of the buffer's backward body (outside the body's own ``jit``, whose
    # cache outlives a test).
    backward_bodies = []

    def counted(_, g):
        jax.debug.callback(lambda: backward_bodies.append(1))
        return (g,)

    mark = jax.custom_vjp(lambda tokens: tokens)
    mark.defvjp(lambda tokens: (tokens, None), counted)
    one_buffer = llama._one_buffer
    monkeypatch.setattr(
        llama, "_one_buffer",
        lambda tokens, *others: one_buffer(mark(tokens), *others))

    config = {**REF, "deployment": {"first_held_expert": 8}}
    weight = jax.random.normal(jax.random.key(8), x.shape)

    def weighted_sum(cfg):
        return jax.grad(lambda p, x: jnp.sum(
            RoutedExperts(cfg).apply(p, x) * weight), argnums=(0, 1))

    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.routed_experts(
            x, routed_reference_params(params["params"], 16), config)
        got_grads, got_dx = weighted_sum(cfg)(params, x)
        want_grads, want_dx = jax.grad(lambda moe, x: jnp.sum(
            ref.routed_experts(x, routed_reference_params(moe, 16),
                               config)[0] * weight), argnums=(0, 1))(
            params["params"], x)
    jax.effects_barrier()
    # The first buffer's body runs whatever it holds.
    assert len(backward_bodies) == max(buffers, 1)
    assert not _scans(jax.make_jaxpr(weighted_sum(cfg))(params, x).jaxpr,
                      n_chunks)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got_dx, want_dx, rtol=2e-4, atol=2e-4)
    for g, w in zip(jax.tree.leaves(got_grads["params"]),
                    jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
    if not buffers:
        # Without the shared experts the layer is the routed sum alone:
        # nothing of it, and nothing that is not a number.
        alone = dataclasses.replace(cfg, shared_experts=0)
        routed = {"params": {name: leaf for name, leaf in
                             params["params"].items() if name != "shared"}}
        for leaf in jax.tree.leaves((RoutedExperts(alone).apply(routed, x),
                                     weighted_sum(alone)(routed, x))):
            np.testing.assert_array_equal(leaf, 0.0)


# -- rows back to their tokens, slot by slot ----------------------------------

def _slots(seed, tokens, k, rows):
    """``position [tokens * k]`` over a buffer of ``rows`` rows: token 0 has
    all its k slots in the buffer and token 1 none, the others some; the
    slots that are not lie below 0, at ``rows`` and beyond."""
    rng = np.random.default_rng(seed)
    position = rng.integers(0, rows, size=(tokens, k))
    outside = np.array([-rows - 1, -3, -1, rows, rows + 1, 4 * rows])
    away = rng.random((tokens, k)) < 0.5
    away[0] = False
    position = np.where(away, rng.choice(outside, size=(tokens, k)), position)
    position[1] = outside[:k]
    return position.reshape(-1).astype(np.int32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("k", [1, 2, 6])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["gates", "liveness"])
def test_rows_to_tokens_is_the_plain_sum_over_a_tokens_slots(weighted, k,
                                                             dtype):
    """``y[t] = sum_j scale[t, j] rows[position[t, j]]`` over the slots whose
    position is in ``[0, rows)``, written out in numpy in float64 from the
    rows as they are (a bf16 row cast up is exact)."""
    tokens, n_rows, hidden = 37, 23, 16
    rng = np.random.default_rng(k)
    # On a grid of sixteenths, so that bf16 holds them and a float32 sum of
    # six is exact.
    rows = jnp.asarray(np.round(rng.normal(size=(n_rows, hidden)) * 16) / 16,
                       dtype)
    if dtype == jnp.float32:
        rows = rows + jnp.asarray(rng.normal(size=rows.shape), dtype) * 1e-3
    position = _slots(k, tokens, k, n_rows)
    weights = (jnp.asarray(rng.random((tokens, k)), jnp.float32)
               if weighted else None)
    got = jax.jit(llama._rows_to_tokens, static_argnums=2)(
        rows, jnp.asarray(position), k, weights)
    assert got.dtype == jnp.float32 and got.shape == (tokens, hidden)

    plain = np.asarray(rows.astype(jnp.float32), np.float64)
    scale = (np.ones((tokens, k)) if weights is None
             else np.asarray(weights, np.float64))
    want = np.zeros((tokens, hidden))
    live = np.zeros(tokens, int)
    for t in range(tokens):
        for j in range(k):
            p = position[t * k + j]
            if 0 <= p < n_rows:
                want[t] += scale[t, j] * plain[p]
                live[t] += 1
    assert live[0] == k and live[1] == 0 and not np.any(got[1])
    if dtype == jnp.bfloat16 and not weighted:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _one_buffer(seed, tokens, k, first, chunk):
    """What ``RoutedExperts`` hands the two functions for the buffer that
    holds sorted rows ``first`` to ``first + chunk``: which assignment each
    row is, and where in the buffer each assignment's row is."""
    order = np.random.default_rng(seed).permutation(tokens * k)
    inverse = np.argsort(order)
    return (jnp.asarray(order[first:first + chunk], jnp.int32),
            jnp.asarray(inverse - first, jnp.int32))


@pytest.mark.parametrize("k", [1, 2, 6])
def test_gradient_through_rows_of_tokens_is_autodiffs_of_the_gather(k):
    tokens, hidden, first, chunk = 19, 8, 5, 11
    assignments, position = _one_buffer(k, tokens, k, first, chunk)
    x = jax.random.normal(jax.random.key(k), (tokens, hidden))
    weight = jax.random.normal(jax.random.key(9), (chunk, hidden))
    np.testing.assert_array_equal(
        llama._rows_of_tokens(x, assignments, position, k),
        x[assignments // k])
    got = jax.grad(lambda x: jnp.sum(llama._rows_of_tokens(
        x, assignments, position, k) * weight))(x)
    want = jax.grad(lambda x: jnp.sum(x[assignments // k] * weight))(x)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("k", [1, 2, 6])
def test_gradient_through_the_weighted_sum_is_autodiffs_of_the_scatter(k):
    tokens, hidden, first, chunk = 19, 8, 5, 11
    assignments, position = _one_buffer(k, tokens, k, first, chunk)
    rows = jax.random.normal(jax.random.key(k), (chunk, hidden))
    weights = jax.random.uniform(jax.random.key(7), (tokens, k))
    weight = jax.random.normal(jax.random.key(9), (tokens, hidden))

    def plain(rows, weights):
        gated = rows * weights.reshape(-1)[assignments][:, None]
        return jnp.zeros((tokens, hidden)).at[assignments // k].add(gated)

    def ours(rows, weights):
        return llama._weighted_rows_to_tokens(rows, weights, assignments,
                                              position, k)

    np.testing.assert_allclose(ours(rows, weights), plain(rows, weights),
                               rtol=1e-6, atol=1e-6)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * weight), argnums=(0, 1))(
        rows, weights)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weight), argnums=(0, 1))(
        rows, weights)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
