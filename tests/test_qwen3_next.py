"""Qwen3-Next's layers in ``models/llama.py`` against the plain reference
(``benchmark/reference/qwen3_next.py``), on the CPU at small sizes with
seeded weights: the whole model's loss and every gradient in float32 (gated
delta-rule layers whose 2 key heads serve 4 value heads over routed experts
beside a gated shared expert, a softmax layer whose W_q holds a query and an
element-wise gate a head, a quarter of a head turning, zero-centred norms);
the 4 shares of 4 experts; value head j reading key head j // 2; the
element-wise gate beside the per-head one; the zero-centred norm beside the
plain one, in function and under AdamW; the flash calls and the rotation's
Mosaic pass at heads of 256; the forward call's VMEM limit; and the refusals
by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import qwen3_next as ref
from horovod_tpu.models import llama
from horovod_tpu.models.llama import (GatedDeltaNet, LlamaAttention,
                                      LlamaConfig, LlamaModel, RMSNorm,
                                      RopeParameters, RoutedExperts,
                                      apply_rope, causal_attention,
                                      rope_freqs)
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.losses import balance_loss, softmax_cross_entropy

EXPERTS, PER_TOKEN, HELD = 16, 3, 4
TYPES = ("linear_attention",) * 3 + ("full_attention",)
LINEAR_NAMES = ("wq", "wk", "wv", "wg", "wa", "wb", "wo")
LINEAR_PARAMS = ("conv_q", "conv_k", "conv_v", "a_log", "dt_bias", "o_norm")


def tiny(**changes) -> LlamaConfig:
    """Hidden 64; one period: three linear layers of 2 key heads serving 4
    value heads of 16, a full layer of 4 query heads over 2 key-value heads
    of 32 of which a quarter turns; 4 of 16 experts held, top-3."""
    base = dict(
        vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
        num_kv_heads=2, attention_head_dim=32, intermediate_size=96,
        max_seq_len=256, rms_eps=1e-6, layer_types=TYPES,
        rope_parameters=(("full_attention",
                          RopeParameters(1e7, None, 0.25)),),
        qk_norm=True, zero_centered_norm=True, gating="elementwise",
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16,
        num_experts=EXPERTS, experts_per_token=PER_TOKEN, held_experts=HELD,
        moe_intermediate_size=16, shared_experts=1, shared_expert_gate=True,
        norm_topk_prob=True, balance_over="batch", dtype=jnp.float32,
        logits_dtype=jnp.float32)
    return LlamaConfig(**{**base, **changes})


def reference_config(cfg: LlamaConfig) -> dict:
    """The published config's keys at ``cfg``'s widths, as the reference
    reads them."""
    return {
        "full_attention_interval": 4, "rms_norm_eps": cfg.rms_eps,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rope_theta": 1e7, "rope_scaling": None,
        "partial_rotary_factor": 0.25,
        "linear_num_key_heads": cfg.linear_num_key_heads,
        "linear_num_value_heads": cfg.linear_num_value_heads,
        "linear_key_head_dim": cfg.linear_key_head_dim,
        "linear_value_head_dim": cfg.linear_value_head_dim,
        "num_experts_per_tok": cfg.experts_per_token, "norm_topk_prob": True,
        "deployment": {"first_held_expert": cfg.first_held_expert},
        "assumed": {"aux_loss_alpha": 0.001}}


def routed_reference(moe, width):
    shared = moe["shared"]["w_gate_up"]["kernel"]
    return {"router": moe["router"]["kernel"],
            "experts": {"w_gate": moe["w_gate_up"][..., :width],
                        "w_up": moe["w_gate_up"][..., width:],
                        "w_down": moe["w_down"]},
            "shared": {"w_gate": shared[:, :width], "w_up": shared[:, width:],
                       "w_down": moe["shared"]["w_down"]["kernel"]},
            "shared_gate": moe["shared_gate"]["kernel"]}


def linear_reference(mixer):
    return {**{name: mixer[name]["kernel"] for name in LINEAR_NAMES},
            **{name: mixer[name] for name in LINEAR_PARAMS}}


def full_reference(mixer):
    return {**{name: mixer[name]["kernel"]
               for name in ("wq", "wk", "wv", "wo")},
            "q_norm": mixer["q_norm"]["scale"],
            "k_norm": mixer["k_norm"]["scale"]}


def to_reference(params, cfg):
    p = params["params"]
    layers = []
    for i in range(cfg.num_layers):
        layer = p[f"layer_{i}"]
        layers.append({
            **(linear_reference(layer["linear"]) if cfg.is_linear(i)
               else full_reference(layer["attn"])),
            "norm_attn": layer["norm_attn"]["scale"],
            "norm_mlp": layer["norm_mlp"]["scale"],
            **routed_reference(layer["moe"], cfg.moe_intermediate_size)})
    return {"embed": p["tok_emb"]["embedding"], "layers": layers,
            "norm_f": p["norm_f"]["scale"],
            "lm_head": p["lm_head"]["kernel"]}


def spread(params, seed=5, width=0.1):
    """Every vector of the tree (the norms' scales among them, which start
    from zeros and ones) moved off its start, so that a scale read wrongly
    shows."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + width * jax.random.normal(key, leaf.shape) if leaf.ndim == 1
        else leaf for leaf, key in zip(leaves, keys)])


def rel(found, wanted):
    return float(jnp.linalg.norm(found - wanted)
                 / (jnp.linalg.norm(wanted) + 1e-30))


# -- the whole model ----------------------------------------------------------

def model_loss(cfg, attention_fn, params, tokens):
    logits, sown = LlamaModel(cfg, attention_fn=attention_fn).apply(
        params, tokens[:, :-1], mutable=["losses"])
    return (softmax_cross_entropy(logits, tokens[:, 1:])
            + 0.001 * balance_loss(sown))


@pytest.fixture(scope="module")
def whole_model():
    cfg = tiny()
    tokens = jax.random.randint(jax.random.key(3), (2, 129), 0,
                                cfg.vocab_size)
    params = spread(LlamaModel(cfg).init(jax.random.key(0),
                                         tokens[:, :8]))
    with jax.default_matmul_precision("highest"):
        wanted = jax.jit(lambda p, t: ref.loss_and_grads(
            p, t, reference_config(cfg)))(to_reference(params, cfg), tokens)
    return cfg, params, tokens, wanted


def test_tiny_sizes_keep_the_published_pattern_and_ratios():
    cfg = tiny()
    assert [cfg.is_linear(i) for i in range(4)] == [True, True, True, False]
    assert all(cfg.is_routed(i) for i in range(4))
    assert cfg.linear_num_value_heads == 2 * cfg.linear_num_key_heads
    assert cfg.linear_key_head_dim == cfg.linear_value_head_dim
    assert (cfg.num_heads, cfg.num_kv_heads) == (4, 2)
    # A linear layer has no rotation and no entry in rope_parameters.
    assert cfg.rope_of(0) is None
    assert cfg.rope_of(3) == RopeParameters(1e7, None, 0.25)
    shapes = jax.eval_shape(LlamaModel(cfg).init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    attn = shapes["layer_3"]["attn"]
    assert attn["wq"]["kernel"].shape == (64, 4 * 2 * 32)
    assert attn["q_norm"]["scale"].shape == (32,) and "wg" not in attn
    assert shapes["layer_0"]["linear"]["wq"]["kernel"].shape == (64, 2 * 16)
    assert shapes["layer_0"]["linear"]["wv"]["kernel"].shape == (64, 4 * 16)
    assert shapes["layer_0"]["linear"]["o_norm"].shape == (16,)
    assert shapes["layer_0"]["moe"]["shared_gate"]["kernel"].shape == (64, 1)
    assert shapes["layer_0"]["moe"]["w_gate_up"].shape == (HELD, 64, 32)


@pytest.mark.parametrize("attention_fn", [flash_attention_fn,
                                          causal_attention])
def test_whole_model_agrees_with_the_plain_reference_in_float32(
        whole_model, attention_fn):
    """Loss and EVERY gradient, a leaf at a time, to 2e-3 (as
    ``test_olmo_hybrid.py``): the chunked rule against the token-by-token
    one, grouped products against a dense loop over the held experts, the
    flash calls against a masked softmax."""
    cfg, params, tokens, (ref_loss, ref_grads) = whole_model
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: model_loss(cfg, attention_fn, p, t)))(params, tokens)
    assert abs(float(loss) - float(ref_loss)) < 1e-4
    found = jax.tree_util.tree_flatten_with_path(to_reference(grads, cfg))[0]
    for (path, leaf), wanted in zip(found, jax.tree.leaves(ref_grads)):
        assert rel(leaf, wanted) < 2e-3, jax.tree_util.keystr(path)


def _patched(monkeypatch, owner, name, value):
    monkeypatch.setattr(owner, name, value(getattr(owner, name)))


@pytest.mark.parametrize("defect", [
    "gate left out", "shared gate left out", "plain norms",
    "keys expanded in another order"])
def test_each_new_piece_shows_in_the_loss_and_gradient(whole_model,
                                                       monkeypatch, defect):
    """One mechanism wrong at a time, the gradient is percents away from the
    reference's where the program as it is reads 1e-5."""
    cfg, params, tokens, (ref_loss, ref_grads) = whole_model
    if defect == "gate left out":
        _patched(monkeypatch, llama, "_gated_lanes",
                 lambda _: lambda out, logits: out)
    elif defect == "shared gate left out":
        _patched(monkeypatch, jax.nn, "sigmoid", lambda sigmoid: (
            lambda x: jnp.ones_like(x) if x.shape[-1] == 1 else sigmoid(x)))
    elif defect == "plain norms":
        # (1 + gamma read as gamma: the scales' spread about 0 then scales
        # the stream by ~0.1.)
        cfg = dataclasses.replace(cfg, zero_centered_norm=False)
    else:
        _patched(monkeypatch, jnp, "repeat", lambda repeat: (
            lambda x, n, axis=None: jnp.tile(
                x, [n if a == axis else 1 for a in range(x.ndim)])
            if axis == 2 and x.ndim == 4 and x.shape[2] == 2
            else repeat(x, n, axis=axis)))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p, t: model_loss(cfg, causal_attention, p, t))(params,
                                                                  tokens)
    off = max(rel(g, r) for g, r in zip(
        jax.tree.leaves(to_reference(grads, cfg)),
        jax.tree.leaves(ref_grads)))
    assert off > 0.02, (defect, off, float(loss), float(ref_loss))


# -- the pieces ---------------------------------------------------------------

def test_value_head_j_reads_key_head_j_over_2():
    """The mixer alone against the reference's: agreement with the repeat
    (value heads 0, 1 read key head 0; 2, 3 read key head 1), and none with
    the other expansion of 2 key heads to 4 (0, 1, 0, 1)."""
    cfg = tiny()
    x = jax.random.normal(jax.random.key(0), (2, 96, cfg.hidden_size))
    params = spread(GatedDeltaNet(cfg).init(jax.random.key(1), x))
    config = reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        found = GatedDeltaNet(cfg).apply(params, x)
        wanted = ref.linear_mixer(x, linear_reference(params["params"]),
                                  config)
    assert rel(found, wanted) < 1e-4
    lowered = jax.jit(lambda p, x: GatedDeltaNet(cfg).apply(p, x)).lower(
        params, x).as_text(debug_info=True)
    assert "hvd.gdn.heads" in lowered
    # The reference with its keys tiled instead of repeated is another
    # function: the program does not agree with it.
    real = jnp.repeat
    try:
        ref.jnp.repeat = lambda t, n, axis: jnp.tile(t, (1, 1, n, 1))
        with jax.default_matmul_precision("highest"):
            other = ref.linear_mixer(x, linear_reference(params["params"]),
                                     config)
    finally:
        ref.jnp.repeat = real
    assert rel(found, other) > 0.1


def test_equal_head_counts_never_enter_the_heads_scope():
    cfg = tiny(linear_num_key_heads=4)
    x = jnp.zeros((1, 64, cfg.hidden_size))
    params = jax.eval_shape(GatedDeltaNet(cfg).init, jax.random.key(0), x)
    text = jax.jit(lambda p, x: GatedDeltaNet(cfg).apply(p, x)).lower(
        params, x).as_text(debug_info=True)
    assert "hvd.gdn.scan" in text and "hvd.gdn.heads" not in text


@pytest.mark.parametrize("attention_fn", [flash_attention_fn,
                                          causal_attention])
def test_the_elementwise_gate_is_not_the_per_head_one(attention_fn):
    cfg = tiny()
    x = jax.random.normal(jax.random.key(0), (2, 128, cfg.hidden_size))
    layer = LlamaAttention(cfg, attention_fn=attention_fn, index=3)
    cos, sin = rope_freqs(cfg.head_dim, 128, 1e7, rotary_dim=8)
    params = spread(layer.init(jax.random.key(1), x, cos, sin))
    with jax.default_matmul_precision("highest"):
        found = layer.apply(params, x, cos, sin)
        wanted = ref.full_mixer(x, full_reference(params["params"]),
                                reference_config(cfg))
    assert rel(found, wanted) < 1e-4
    # A per-head gate is another parameter tree (W_q as wide as the heads,
    # a [hidden, heads] W_g) ...
    per_head = jax.eval_shape(
        LlamaAttention(dataclasses.replace(cfg, gating="per-head"),
                       index=3).init, jax.random.key(1), x, cos, sin)
    assert per_head["params"]["wq"]["kernel"].shape == (64, 4 * 32)
    assert per_head["params"]["wg"]["kernel"].shape == (64, 4)
    assert params["params"]["wq"]["kernel"].shape == (64, 4 * 2 * 32)
    # ... and another function: a head's lanes gated by the MEAN of their
    # logits (one gate a head) is far from the lane-by-lane one.
    real = llama._gated_lanes
    try:
        llama._gated_lanes = lambda out, logits: real(out, jnp.repeat(
            jnp.mean(logits.reshape(*logits.shape[:-1], 4, 32), axis=-1),
            32, axis=-1))
        with jax.default_matmul_precision("highest"):
            a_head = layer.apply(params, x, cos, sin)
    finally:
        llama._gated_lanes = real
    assert rel(a_head, wanted) > 0.05


def test_zero_centred_norm_is_the_plain_one_at_its_start_and_not_under_adamw():
    """N0 with gamma = 0 is N1 with gamma = 1, bit for bit; one AdamW step on
    the same gradient later they are not the same function: the decay pulls
    N1's multiplier towards 0 and N0's towards 1."""
    x = jax.random.normal(jax.random.key(0), (4, 64)).astype(jnp.bfloat16)
    zero, plain = RMSNorm(1e-6, zero_centered=True), RMSNorm(1e-6)
    p0 = zero.init(jax.random.key(1), x)
    p1 = plain.init(jax.random.key(1), x)
    assert not p0["params"]["scale"].any() and (p1["params"]["scale"]
                                                == 1).all()
    np.testing.assert_array_equal(
        np.asarray(zero.apply(p0, x)).view(np.uint16),
        np.asarray(plain.apply(p1, x)).view(np.uint16))

    def loss(norm):
        return lambda p: jnp.sum(norm.apply(p, x).astype(jnp.float32) ** 2)

    g0, g1 = jax.grad(loss(zero))(p0), jax.grad(loss(plain))(p1)
    np.testing.assert_allclose(g0["params"]["scale"], g1["params"]["scale"],
                               rtol=1e-6)
    opt = optax.adamw(1e-2, weight_decay=0.1)
    u0, _ = opt.update(g0, opt.init(p0), p0)
    u1, _ = opt.update(g1, opt.init(p1), p1)
    multiplier0 = 1.0 + optax.apply_updates(p0, u0)["params"]["scale"]
    multiplier1 = optax.apply_updates(p1, u1)["params"]["scale"]
    # The same Adam step; the decay's 1e-2 x 0.1 x 1 on N1's scale alone.
    np.testing.assert_allclose(multiplier0 - multiplier1, 1e-3, rtol=1e-3)


def test_the_4_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut: each share of 4 experts computed by the
    program with those experts' weights alone (router and top-3 over all 16,
    gates renormalised), the routed parts summed and the GATED shared expert
    counted once, is the uncut 16-expert reference layer."""
    cfg = tiny(held_experts=0, hidden_size=32, moe_intermediate_size=8)
    x = jax.random.normal(jax.random.key(0), (2, 24, cfg.hidden_size))
    moe = RoutedExperts(cfg).init(jax.random.key(1), x)["params"]
    assert moe["w_gate_up"].shape == (EXPERTS, 32, 16)
    config = reference_config(cfg)
    as_reference = routed_reference(moe, 8)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.routed_experts(x, as_reference, config)
        shared = jax.nn.sigmoid(x @ as_reference["shared_gate"]) * ref.swiglu(
            x, as_reference["shared"])
    layer = jax.jit(lambda cfg, params, x: RoutedExperts(cfg).apply(
        params, x, mutable=["moe_stats"]), static_argnums=0)
    routed_sum = jnp.zeros_like(whole)
    rows = 0
    for share in range(EXPERTS // HELD):
        first = share * HELD
        share_cfg = dataclasses.replace(cfg, held_experts=HELD,
                                        first_held_expert=first)
        share_params = {"params": {
            **moe, "w_gate_up": moe["w_gate_up"][first:first + HELD],
            "w_down": moe["w_down"][first:first + HELD]}}
        with jax.default_matmul_precision("highest"):
            y, sown = layer(share_cfg, share_params, x)
        routed_sum = routed_sum + (y - shared)
        rows += int(jnp.sum(sown["moe_stats"]["rows_per_expert"][0]))
        assert int(sown["moe_stats"]["rows_dropped"][0]) == 0
    assert float(jnp.max(jnp.abs(routed_sum + shared - whole))) <= 5e-5
    assert rows == 2 * 24 * PER_TOKEN
    # The gate is live: without it the layer's shared part is another.
    ungated, _ = layer(dataclasses.replace(cfg, shared_expert_gate=False),
                       {"params": {k: v for k, v in moe.items()
                                   if k != "shared_gate"}}, x)
    assert float(jnp.max(jnp.abs(ungated - whole))) > 1e-2


def test_without_a_gate_the_routed_layer_is_the_program_it_was():
    cfg = tiny(shared_expert_gate=False)
    x = jnp.zeros((2, 16, cfg.hidden_size))
    params = jax.eval_shape(RoutedExperts(cfg).init, jax.random.key(0), x)
    assert "shared_gate" not in params["params"]
    plain = str(jax.make_jaxpr(lambda p, x: RoutedExperts(cfg).apply(
        p, x))(params, x))
    gated_cfg = tiny()
    gated_params = jax.eval_shape(RoutedExperts(gated_cfg).init,
                                  jax.random.key(0), x)
    gated = str(jax.make_jaxpr(lambda p, x: RoutedExperts(gated_cfg).apply(
        p, x))(gated_params, x))
    assert gated.count("logistic") == plain.count("logistic") + 1


# -- heads of 256 in the two calls and in the rotation -------------------------

@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 2e-5),
                                          (jnp.bfloat16, 2e-2)])
def test_flash_calls_at_heads_of_256_in_groups_of_8(dtype, limit):
    """16 query heads over 2 key-value heads of 256, in place, forward and
    all three gradients against the dense attention."""
    keys = jax.random.split(jax.random.key(0), 4)
    q, w = (jax.random.normal(k, (1, 256, 16, 256), dtype)
            for k in keys[::3])
    k, v = (jax.random.normal(k, (1, 256, 2, 256), dtype) for k in keys[1:3])

    def value_and_grads(attend):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    before = fa.layout_counts()["in_place"]
    with jax.default_matmul_precision("highest"):
        found = value_and_grads(flash_attention_fn)
        wanted = value_and_grads(causal_attention)
    assert fa.layout_counts()["in_place"] == before + 1
    for got, want in zip(jax.tree.leaves(found), jax.tree.leaves(wanted)):
        assert rel(got.astype(jnp.float32), want.astype(jnp.float32)) < limit


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_mosaic_pass_turns_a_quarter_of_a_head_of_256(dtype):
    """The first 64 lanes of each head of 256 turn and 192 pass with their
    own bits, through the rotation's Mosaic pass and through ``jnp`` alike,
    and as the reference turns them."""
    cos, sin = rope_freqs(256, 64, 1e7, rotary_dim=64)
    assert cos.shape == (64, 128)
    assert (cos[:, 32:] == 1).all() and (sin[:, 32:] == 0).all()
    x = jax.random.normal(jax.random.key(0), (2, 64, 4, 256), dtype)
    in_place = apply_rope(x, cos, sin, in_place=True)
    plain = apply_rope(x, cos, sin)
    bits = np.uint32 if dtype == jnp.float32 else np.uint16
    np.testing.assert_array_equal(np.asarray(in_place[..., 64:]).view(bits),
                                  np.asarray(x[..., 64:]).view(bits))
    assert rel(in_place.astype(jnp.float32), plain.astype(jnp.float32)) < (
        1e-6 if dtype == jnp.float32 else 1e-2)
    wanted = ref.rotary(x.astype(jnp.float32), {
        "rope_scaling": None, "partial_rotary_factor": 0.25,
        "rope_theta": 1e7})
    assert rel(in_place.astype(jnp.float32), wanted) < (
        1e-5 if dtype == jnp.float32 else 1e-2)


@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 1e-4),
                                          (jnp.bfloat16, 0.1)],
                         ids=["f32", "bf16"])
def test_the_full_layer_norms_heads_of_256_in_the_rotations_pass(dtype,
                                                                 limit):
    """A full layer at heads of 256 (2 over 1, zero-centred scales, a
    quarter of a head turning, the element-wise gate) with the flash seam:
    q and k are normed and turned by ``ops/rope.py::norm_rotate_pairs``
    (interpreted), ONE call each; with the model's dense attention by the
    module's arithmetic and the jnp rotation.  The same tree (``q_norm/
    scale``, ``k_norm/scale`` ``[256]``), the same loss, every gradient: in
    float32 to the flash calls' own distance from dense attention, in bf16
    to its rounding through a routed layer (the router's gradient 7 %)."""
    from horovod_tpu.common import trace_counts
    from horovod_tpu.ops import rope

    cfg = tiny(num_layers=1, layer_types=("full_attention",), num_heads=2,
               num_kv_heads=1, attention_head_dim=256, dtype=dtype,
               logits_dtype=dtype)
    tokens = jax.random.randint(jax.random.key(3), (2, 65), 0,
                                cfg.vocab_size)
    params = spread(LlamaModel(cfg).init(jax.random.key(0), tokens[:, :8]),
                    width=0.3)
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    attn = params["params"]["layer_0"]["attn"]
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (
        256,)
    assert attn["wq"]["kernel"].shape == (64, 2 * 2 * 256)

    def loss_and_grads(attention_fn):
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(lambda p, t: model_loss(
                cfg, attention_fn, p, t)))(params, tokens)

    def normed():
        return trace_counts.counts(rope.BODY).get(rope.NORMED, 0)

    before = normed()
    loss, grads = loss_and_grads(flash_attention_fn)
    assert normed() == before + 2
    want_loss, want = loss_and_grads(causal_attention)
    assert normed() == before + 2
    assert abs(float(loss) - float(want_loss)) < limit
    found = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert sum("_norm" in jax.tree_util.keystr(path) and "attn" in
               jax.tree_util.keystr(path) for path, _ in found) == 2
    for (path, leaf), wanted in zip(found, jax.tree.leaves(want)):
        assert rel(leaf.astype(jnp.float32), wanted.astype(
            jnp.float32)) < limit, jax.tree_util.keystr(path)


@pytest.mark.parametrize("s, d, masked, stated", [
    (8192, 128, False, False), (2048, 128, False, False),
    (4096, 192, False, False), (8192, 256, False, True),
    (16384, 128, False, True), (8192, 128, True, True)])
def test_forward_call_states_a_limit_only_past_the_default(s, d, masked,
                                                           stated):
    """The rule of ``_fwd``: K and V whole and twice, with q, o, lse and the
    pair's live arrays; a call that fits the compiler's default states
    nothing (every cell's before this one), one that does not states what it
    computes, and a selection's call states its own as ever."""
    d_v = 128 if d == 192 else d
    limit = fa._fwd_vmem_limit(s, d, d_v, 512, 512, 2, masked=masked)
    assert (limit > fa._DEFAULT_SCOPED_VMEM) == (stated or masked)
    if masked:
        assert limit == fa._fwd_vmem_limit(s, d, d_v, 512, 512, 2)
        return
    q = jnp.zeros((1, s, 2 * d), jnp.bfloat16)
    v = jnp.zeros((1, s, 2 * d_v), jnp.bfloat16)

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    forward, = calls(jax.make_jaxpr(
        lambda q, v: fa._fwd(q, q, v, True, d ** -0.5, heads=2))(q, v).jaxpr)
    params = forward.params["compiler_params"]
    limits = [getattr(p, "vmem_limit_bytes", None)
              for p in (params or {}).values()]
    assert [x for x in limits if x is not None] == ([limit] if stated
                                                    else [])


# -- what the config and the other paths refuse --------------------------------

def test_config_refuses_what_it_cannot_be():
    with pytest.raises(ValueError, match="gating"):
        tiny(gating="per-lane")
    with pytest.raises(ValueError, match="shared_expert_gate"):
        tiny(shared_experts=0)
    with pytest.raises(ValueError, match="shared_expert_gate"):
        LlamaConfig.tiny().__class__(shared_expert_gate=True)
    with pytest.raises(ValueError, match="element-wise gate"):
        LlamaConfig(attention_kind="latent", gating="elementwise",
                    kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8,
                    v_head_dim=8)
    plain = LlamaConfig.tiny()
    assert (plain.gating, plain.zero_centered_norm,
            plain.shared_expert_gate) == (None, False, False)


@pytest.mark.parametrize("who", ["generation", "serve", "pipeline"])
@pytest.mark.parametrize("what, word, states", [
    ("the element-wise gate", "element-wise output gate",
     "gating='elementwise'"),
    ("the gated shared expert", "gated shared expert",
     "shared_expert_gate=True"),
    ("zero-centred norms", "zero-centred norms",
     "zero_centered_norm=True")])
def test_the_other_paths_refuse_the_new_kinds_by_name(who, what, word,
                                                      states):
    from horovod_tpu.models.generation import prefill
    from horovod_tpu.parallel.pipeline import init_pipelined_llama

    plain = dict(layer_types=None, rope_parameters=None, qk_norm=False,
                 linear_num_key_heads=0, linear_num_value_heads=0)
    dense = dict(num_experts=1, held_experts=0, shared_experts=0,
                 shared_expert_gate=False)
    if what == "the element-wise gate":
        cfg = tiny(**plain, **dense, zero_centered_norm=False)
    elif what == "the gated shared expert":
        # Named before the routed layer it sits in.
        cfg = tiny(**plain, gating=None, zero_centered_norm=False)
    else:
        cfg = tiny(**plain, **dense, gating=None)
    with pytest.raises(NotImplementedError, match=word) as refusal:
        if who == "generation":
            prefill(cfg, {}, jnp.zeros((1, 4), jnp.int32), cache_len=8)
        elif who == "serve":
            cfg.refuse_new_kinds("the paged KV cache")
        else:
            init_pipelined_llama(cfg, jax.random.key(0), 1)
    assert states in str(refusal.value)
    assert "not built" in str(refusal.value)
