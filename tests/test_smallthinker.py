"""SmallThinker-21BA3B-Instruct's layers in ``models/llama.py`` against the
plain reference (``benchmark/reference/smallthinker.py``), on the CPU at
small sizes with seeded weights: the whole model's loss and every gradient
leaf on parameters moved off their start; the router's logits on the
layer's INPUT; the softmax over the chosen logits; a global layer that does
not see positions beside a window layer that does; the window's edge; the
four shares of 16 experts; each new field an identity at its default; and
the refusals by name."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import smallthinker as ref
from horovod_tpu.models.llama import (LlamaAttention, LlamaConfig, LlamaLayer,
                                      LlamaModel, RopeParameters,
                                      RoutedExperts, causal_attention,
                                      rope_freqs)
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.losses import balance_loss, softmax_cross_entropy

EXPERTS, PER_TOKEN, HELD = 64, 6, 16
LAYOUT = [0, 1, 1, 1]
THETA = 1.5e6


def reference_config(heads=4, head_dim=32, kv_heads=2, window=48, first=0,
                     layout=LAYOUT):
    """The published config's keys at tiny widths, as the reference reads
    them."""
    return {
        "head_dim": head_dim, "num_attention_heads": heads,
        "num_key_value_heads": kv_heads, "rope_layout": layout,
        "sliding_window_layout": layout, "sliding_window_size": window,
        "rope_theta": THETA, "rope_scaling": None, "rms_norm_eps": 1e-6,
        "moe_num_active_primary_experts": PER_TOKEN,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "deployment": {"first_held_expert": first},
        "assumed": {"aux_loss_alpha": 0.001}}


def tiny(heads=4, head_dim=32, kv_heads=2, window=48, layout=LAYOUT,
         **changes) -> LlamaConfig:
    kinds = ("full_attention", "sliding_attention")
    base = dict(
        vocab_size=128, hidden_size=64, num_layers=len(layout),
        num_heads=heads, num_kv_heads=kv_heads, attention_head_dim=head_dim,
        intermediate_size=96, max_seq_len=256, rms_eps=1e-6,
        layer_types=tuple(kinds[flag] for flag in layout),
        sliding_window=window,
        rope_parameters=(("full_attention", None),
                         ("sliding_attention", RopeParameters(THETA))),
        num_experts=EXPERTS, experts_per_token=PER_TOKEN, held_experts=HELD,
        moe_intermediate_size=16, norm_topk_prob=True, balance_over="batch",
        mlp_hidden_act="relu", router_input="layer",
        dtype=jnp.float32, logits_dtype=jnp.float32)
    return LlamaConfig(**{**base, **changes})


def routed_reference(moe, width):
    return {"router": moe["router"]["kernel"],
            "experts": {"w_gate": moe["w_gate_up"][..., :width],
                        "w_up": moe["w_gate_up"][..., width:],
                        "w_down": moe["w_down"]}}


def to_reference(params, cfg):
    p = params["params"]
    layers = []
    for i in range(cfg.num_layers):
        layer = p[f"layer_{i}"]
        layers.append({
            "norm_attn": layer["norm_attn"]["scale"],
            **{name: layer["attn"][name]["kernel"]
               for name in ("wq", "wk", "wv", "wo")},
            "norm_mlp": layer["norm_mlp"]["scale"],
            **routed_reference(layer["moe"], cfg.moe_intermediate_size)})
    return {"embed": p["tok_emb"]["embedding"], "layers": layers,
            "norm_f": p["norm_f"]["scale"],
            "lm_head": p["lm_head"]["kernel"]}


def moved(params, seed=7, by=0.05):
    """``params`` off their start: every leaf (the norms' ones too) plus
    seeded noise of ``by`` times its own root mean square."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + by * jnp.sqrt(jnp.mean(leaf * leaf))
        * jax.random.normal(key, leaf.shape, leaf.dtype)
        for leaf, key in zip(leaves, keys)])


def model_loss(cfg, attention_fn, params, tokens):
    logits, sown = LlamaModel(cfg, attention_fn=attention_fn).apply(
        params, tokens[:, :-1], mutable=["losses"])
    return (softmax_cross_entropy(logits, tokens[:, 1:])
            + 0.001 * balance_loss(sown))


# -- the whole model -----------------------------------------------------------

@pytest.fixture(scope="module")
def whole_model():
    cfg = tiny(remat="layer_keep_attention")
    tokens = jax.random.randint(jax.random.key(1), (2, 129), 0,
                                cfg.vocab_size)
    params = LlamaModel(cfg).init(jax.random.key(0), tokens[:, :-1])
    # An embedding of unit variance, as the job's: the first router reads it.
    table = params["params"]["tok_emb"]
    table["embedding"] = table["embedding"] * cfg.hidden_size ** 0.5
    params = moved(params)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.loss_and_grads(
            to_reference(p, cfg), tokens, reference_config()))(params)
    return cfg, params, tokens, want


@pytest.mark.parametrize("attention_fn", [flash_attention_fn,
                                          causal_attention])
def test_whole_model_agrees_with_the_plain_reference_in_float32(
        whole_model, attention_fn):
    """Loss and EVERY gradient leaf, the router's among them (its gradient
    enters the stream ahead of attention), under ``remat``."""
    cfg, params, tokens, (want_loss, want_grads) = whole_model
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(functools.partial(
            model_loss, cfg, attention_fn)))(params, tokens)
    assert float(loss) == pytest.approx(float(want_loss), abs=2e-5)
    got, want = (jax.tree.leaves_with_path(tree) for tree in (
        to_reference(grads, cfg), want_grads))
    assert len(got) == len(want) == 2 + 4 * 10 + 1
    for (path, g), (_, w) in zip(got, want):
        assert float(jnp.linalg.norm(w)) > 0, path
        assert float(jnp.linalg.norm(g - w)) <= 2e-4 * float(
            jnp.linalg.norm(w)) + 1e-7, path


def test_whole_model_in_bf16_is_bf16s_distance_from_the_reference(
        whole_model):
    cfg, params, tokens, (want_loss, want_grads) = whole_model
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16,
                              logits_dtype=jnp.bfloat16)
    loss, grads = jax.jit(jax.value_and_grad(functools.partial(
        model_loss, low, flash_attention_fn)))(params, tokens)
    assert float(loss) == pytest.approx(float(want_loss), abs=0.02)
    off = sum(float(jnp.sum(jnp.square(g - w))) for g, w in zip(
        jax.tree.leaves(to_reference(grads, cfg)),
        jax.tree.leaves(want_grads)))
    size = sum(float(jnp.sum(jnp.square(w)))
               for w in jax.tree.leaves(want_grads))
    assert 1e-4 < math.sqrt(off / size) < 0.1


@pytest.mark.parametrize("what, changes", [
    ("the router reads the post-attention normed state",
     {"router_input": "experts"}),
    ("silu for relu in the experts", {"mlp_hidden_act": "silu"}),
    ("the global layer rotated", {"rope_parameters": (
        ("full_attention", RopeParameters(THETA)),
        ("sliding_attention", RopeParameters(THETA)))}),
    ("one window layer not rotated, none windowed",
     {"layer_types": ("full_attention",) * 4, "sliding_window": None}),
])
def test_each_new_piece_shows_in_the_loss_and_gradient(whole_model, what,
                                                       changes):
    cfg, params, tokens, (want_loss, want_grads) = whole_model
    wrong = dataclasses.replace(cfg, **changes)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(functools.partial(
            model_loss, wrong, causal_attention)))(params, tokens)
    off = sum(float(jnp.sum(jnp.square(g - w))) for g, w in zip(
        jax.tree.leaves(to_reference(grads, cfg)),
        jax.tree.leaves(want_grads)))
    size = sum(float(jnp.sum(jnp.square(w)))
               for w in jax.tree.leaves(want_grads))
    assert math.sqrt(off / size) > 0.02, what


# -- the router ---------------------------------------------------------------

def _one_layer(cfg, x, index=1):
    layer = LlamaLayer(cfg, index=index)
    cos, sin = rope_freqs(cfg.head_dim, x.shape[1], THETA)
    tables = (cos, sin) if cfg.rope_of(index) is not None else (None, None)
    params = moved(layer.init(jax.random.key(3), x, *tables))
    return layer, params, tables


def test_the_routers_logits_are_of_the_layers_input():
    """``x W_r`` on the layer's INPUT chooses the experts: the counts the
    layer sows are the counts of that product's six largest, and not of the
    same product on the post-attention normed state (what every other
    configuration's router reads)."""
    cfg = tiny(held_experts=0)
    x = jax.random.normal(jax.random.key(2), (2, 40, cfg.hidden_size))
    layer, params, tables = _one_layer(cfg, x)
    with jax.default_matmul_precision("highest"):
        _, sown = layer.apply(params, x, *tables, mutable=["moe_stats"])
        behind, sown_behind = LlamaLayer(
            dataclasses.replace(cfg, router_input="experts"), index=1).apply(
                params, x, *tables, mutable=["moe_stats"])
        w_r = params["params"]["moe"]["router"]["kernel"]
        logits = x @ w_r

    def counts(logits):
        _, chosen = jax.lax.top_k(logits, PER_TOKEN)
        return np.bincount(np.asarray(chosen).ravel(), minlength=EXPERTS)

    got = np.asarray(sown["moe_stats"]["moe"]["assignments_per_expert"][0])
    np.testing.assert_array_equal(got, counts(logits))
    other = np.asarray(
        sown_behind["moe_stats"]["moe"]["assignments_per_expert"][0])
    assert (other != got).any()
    # The other reading is the product on the normed state behind attention.
    with jax.default_matmul_precision("highest"):
        p = params["params"]
        attn = LlamaAttention(cfg, index=1).apply(
            {"params": p["attn"]}, ref.rms_norm(x, p["norm_attn"]["scale"],
                                                1e-6), *tables)
        u = ref.rms_norm(x + attn, p["norm_mlp"]["scale"], 1e-6)
    np.testing.assert_array_equal(other, counts(u @ w_r))


def test_the_layer_is_the_references_layer_and_its_routers_gradient():
    """One layer against ``ref.decoder_layer``: the output, and the
    gradient to the layer's input, which holds the router's part."""
    cfg = tiny()
    config = reference_config()
    x = jax.random.normal(jax.random.key(2), (2, 64, cfg.hidden_size))
    weight = jax.random.normal(jax.random.key(4), x.shape)
    for index in (0, 1):
        layer, params, tables = _one_layer(cfg, x, index)
        p = params["params"]
        plain = {"norm_attn": p["norm_attn"]["scale"],
                 **{n: p["attn"][n]["kernel"] for n in ("wq", "wk", "wv",
                                                        "wo")},
                 "norm_mlp": p["norm_mlp"]["scale"],
                 **routed_reference(p["moe"], cfg.moe_intermediate_size)}
        with jax.default_matmul_precision("highest"):
            got, got_dx = jax.value_and_grad(lambda x: jnp.sum(
                layer.apply(params, x, *tables) * weight))(x)
            want, want_dx = jax.value_and_grad(lambda x: jnp.sum(
                ref.decoder_layer(x, plain, index, config)[0] * weight))(x)
        assert float(got) == pytest.approx(float(want), rel=1e-5)
        np.testing.assert_allclose(got_dx, want_dx, rtol=1e-3, atol=1e-4)


def test_softmax_over_the_six_is_softmax_then_renormalise():
    """``g = softmax(r[e_1..e_6])`` (the reference) against the program's
    softmax over all 64 divided by the chosen six's sum: float32 rounding
    apart, the same gates, from the same six experts."""
    logits = 3.0 * jax.random.normal(jax.random.key(5), (4, 50, EXPERTS))
    scores = jax.nn.softmax(logits, axis=-1)
    gates, chosen = jax.lax.top_k(scores, PER_TOKEN)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    config = reference_config()
    want, want_chosen, _ = ref.route(
        logits, jnp.eye(EXPERTS, dtype=jnp.float32), config)
    np.testing.assert_array_equal(chosen, want_chosen)
    np.testing.assert_allclose(gates, want, rtol=2e-6, atol=1e-8)
    np.testing.assert_allclose(jnp.sum(want, axis=-1), 1.0, rtol=1e-6)
    # And through the layer: the weights RoutedExperts applies are these.
    cfg = tiny(held_experts=0)
    x = jax.random.normal(jax.random.key(6), (2, 24, cfg.hidden_size))
    r_x = jax.random.normal(jax.random.key(7), x.shape)
    moe = moved(RoutedExperts(cfg).init(jax.random.key(1), x, r_x))
    plain = routed_reference(moe["params"], cfg.moe_intermediate_size)
    with jax.default_matmul_precision("highest"):
        got = RoutedExperts(cfg).apply(moe, x, r_x)
        g, e, _ = ref.route(r_x, plain["router"], config)
        want = ref.held_experts(x, g, e, plain["experts"], config)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut: each share of 16 experts computed by
    the program with those experts' weights alone (router and top-6 over all
    64 on the layer's input, gates a softmax over the six wherever they
    live), the four partial routed sums added with nothing counted twice --
    there is no shared expert to count once -- is the uncut 64-expert
    reference layer."""
    cfg = tiny(held_experts=0, hidden_size=32, moe_intermediate_size=8)
    u = jax.random.normal(jax.random.key(0), (2, 24, cfg.hidden_size))
    x = jax.random.normal(jax.random.key(9), u.shape)   # the layer's input
    moe = moved(RoutedExperts(cfg).init(jax.random.key(1), u, x))["params"]
    assert moe["w_gate_up"].shape == (EXPERTS, 32, 16)
    assert set(moe) == {"router", "w_gate_up", "w_down"}
    config = reference_config()
    plain = routed_reference(moe, 8)
    with jax.default_matmul_precision("highest"):
        gates, chosen, _ = ref.route(x, plain["router"], config)
        whole = ref.held_experts(u, gates, chosen, plain["experts"], config)
    layer = jax.jit(lambda cfg, params, u, x: RoutedExperts(cfg).apply(
        params, u, x, mutable=["moe_stats"]), static_argnums=0)
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
            y, sown = layer(share_cfg, share_params, u, x)
        stats = sown["moe_stats"]
        routed_sum = routed_sum + y
        rows += int(jnp.sum(stats["rows_per_expert"][0]))
        assert int(stats["rows_dropped"][0]) == 0
        # The reference's share is the program's, share by share.
        with jax.default_matmul_precision("highest"):
            want = ref.held_experts(
                u, gates, chosen,
                jax.tree.map(lambda w: w[first:first + HELD],
                             plain["experts"]),
                reference_config(first=first))
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(routed_sum, whole, rtol=1e-4, atol=1e-5)
    assert rows == 2 * 24 * PER_TOKEN


def test_the_relu_gates_by_hand():
    """One held expert of two, every token sent to it: the layer is ``g
    (relu(u W_gate) * (u W_up)) W_down``, and with ``silu`` it is not."""
    cfg = tiny(num_experts=2, experts_per_token=2, held_experts=1,
               hidden_size=32, moe_intermediate_size=8)
    u = jax.random.normal(jax.random.key(0), (1, 16, 32))
    moe = RoutedExperts(cfg).init(jax.random.key(1), u)
    p = moe["params"]
    with jax.default_matmul_precision("highest"):
        y, sown = RoutedExperts(cfg).apply(moe, u, mutable=["moe_stats"])
        gate_lanes = u @ p["w_gate_up"][0, :, :8]
        g = jax.nn.softmax(u @ p["router"]["kernel"], axis=-1)[..., :1]
        want = g * ((jax.nn.relu(gate_lanes) * (u @ p["w_gate_up"][0, :, 8:]))
                    @ p["w_down"][0])
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-6)
    assert int(sown["moe_stats"]["rows_per_expert"][0][0]) == 16
    cfg = dataclasses.replace(cfg, mlp_hidden_act="silu")
    with jax.default_matmul_precision("highest"):
        swiglu = RoutedExperts(cfg).apply(moe, u)
    assert float(jnp.max(jnp.abs(swiglu - want))) > 1e-3


# -- positions and the window ---------------------------------------------------

@pytest.mark.parametrize("attention_fn", [flash_attention_fn,
                                          causal_attention])
def test_a_global_layer_does_not_see_positions_and_a_window_layer_does(
        attention_fn):
    """The model with ``positions_offset`` 1000: layer 0 alone (NoPE, all
    causal keys) gives the same output; with a window layer that rotates
    behind it the output is the same too (a rotation is relative), but a
    table of ANOTHER theta moves it, and moves the global layer not at
    all."""
    global_alone = tiny(layout=[0], window=None)
    with_window = tiny(layout=[0, 1], window=16)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, 128)

    def logits(cfg, offset=0, theta=THETA):
        cfg = dataclasses.replace(cfg, rope_parameters=(
            ("full_attention", None),
            ("sliding_attention", RopeParameters(theta))))
        model = LlamaModel(cfg, attention_fn=attention_fn)
        params = LlamaModel(cfg).init(jax.random.key(0), tokens)
        with jax.default_matmul_precision("highest"):
            return model.apply(params, tokens, positions_offset=offset)

    np.testing.assert_array_equal(logits(global_alone),
                                  logits(global_alone, 1000))
    np.testing.assert_array_equal(logits(global_alone),
                                  logits(global_alone, theta=1e4))
    np.testing.assert_allclose(logits(with_window),
                               logits(with_window, 1000), atol=2e-3)
    assert float(jnp.max(jnp.abs(logits(with_window)
                                 - logits(with_window, theta=1e4)))) > 1e-2
    # The layers' own reading: q and k of layer 0 are handed no table.
    assert with_window.rope_of(0) is None
    assert with_window.rope_of(1) == RopeParameters(THETA)
    assert [with_window.window_of(i) for i in range(2)] == [None, 16]


def test_a_global_layers_attention_is_a_set_function_of_its_keys():
    """What "does not see positions" means for one layer: permuting the
    keys and values BEHIND the last query's causal edge leaves that query's
    output as it was in a layer that does not rotate, and moves it in a
    layer that does."""
    cfg = tiny(layout=[0, 1], window=64)
    x = jax.random.normal(jax.random.key(2), (1, 48, cfg.hidden_size))
    perm = jnp.concatenate([jax.random.permutation(jax.random.key(3), 47),
                            jnp.array([47])])
    tables = rope_freqs(cfg.head_dim, 48, THETA)

    def last_row(index, x):
        module = LlamaAttention(cfg, index=index)
        beside = tables if cfg.rope_of(index) is not None else (None, None)
        params = module.init(jax.random.key(0), x, *beside)
        with jax.default_matmul_precision("highest"):
            return module.apply(params, x, *beside)[0, -1]

    np.testing.assert_allclose(last_row(0, x), last_row(0, x[:, perm]),
                               rtol=1e-4, atol=1e-6)
    assert float(jnp.max(jnp.abs(last_row(1, x)
                                 - last_row(1, x[:, perm])))) > 1e-3


@pytest.mark.parametrize("attention_fn", [flash_attention_fn,
                                          causal_attention])
def test_key_4096_back_is_masked_and_key_4095_is_not(attention_fn):
    """The window's edge at the published size, ``0 <= t - s < 4096``: the
    last query of 4,224 positions moves with the value 4,095 positions
    behind it and does not with the one 4,096 behind."""
    seq, window, dim = 4224, 4096, 64
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (1, seq, 1, dim))
    k = jax.random.normal(keys[1], (1, seq, 1, dim))
    v = jax.random.normal(keys[2], (1, seq, 1, dim))
    last = seq - 1

    def out(v):
        return attention_fn(q, k, v, window=window)[0, last]

    base = out(v)
    inside = out(v.at[0, last - 4095].add(100.0))
    outside = out(v.at[0, last - 4096].add(100.0))
    np.testing.assert_array_equal(outside, base)
    assert float(jnp.max(jnp.abs(inside - base))) > 1e-3
    # And query 4,095 still sees key 0, as every causal key it has.
    early = attention_fn(q, k, v.at[0, 0].add(100.0), window=window)
    assert float(jnp.max(jnp.abs(early[0, 4095] - attention_fn(
        q, k, v, window=window)[0, 4095]))) > 1e-3
    np.testing.assert_array_equal(early[0, 4096],
                                  attention_fn(q, k, v,
                                               window=window)[0, 4096])


# -- every new field at its default is the program it was ----------------------

def _jaxpr(cfg, tokens):
    model = LlamaModel(cfg, attention_fn=flash_attention_fn)
    params = jax.eval_shape(LlamaModel(cfg).init, jax.random.key(0), tokens)
    return str(jax.make_jaxpr(lambda p, t: jax.value_and_grad(
        lambda p: jnp.sum(model.apply(p, t, mutable=["losses"])[0].astype(
            jnp.float32)))(p))(params, tokens))


def test_each_new_field_at_its_default_leaves_an_accepted_trace_unchanged():
    """An accepted configuration's kind of stack (window and full layers,
    each type its own table, routed SwiGLU experts beside a shared one:
    ``laguna-s-2.1``'s) names none of the new values, and its traced jaxpr,
    forward and backward, is the one it had with the fields stated at their
    defaults; each new value changes it."""
    accepted = dict(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, attention_head_dim=32, intermediate_size=96,
        max_seq_len=256, layer_types=("full_attention", "sliding_attention"),
        sliding_window=48, rope_parameters=(
            ("full_attention", RopeParameters(5e5, None, 0.5)),
            ("sliding_attention", RopeParameters(1e4))),
        num_experts=16, experts_per_token=3, held_experts=4,
        moe_intermediate_size=16, shared_experts=1, balance_over="batch",
        remat="layer_keep_attention")
    tokens = jnp.zeros((2, 64), jnp.int32)
    fields = LlamaConfig.__dataclass_fields__
    assert fields["router_input"].default == "experts"
    assert fields["mlp_hidden_act"].default == "silu"
    base = _jaxpr(LlamaConfig(**accepted), tokens)
    assert base == _jaxpr(LlamaConfig(
        **accepted, router_input="experts", mlp_hidden_act="silu"), tokens)
    moved_router = _jaxpr(LlamaConfig(**accepted, router_input="layer"),
                          tokens)
    assert moved_router != base
    no_shared = {**accepted, "shared_experts": 0}
    assert _jaxpr(LlamaConfig(**no_shared), tokens) != _jaxpr(
        LlamaConfig(**no_shared, mlp_hidden_act="relu"), tokens)
    still = {**accepted, "rope_parameters": (
        ("full_attention", None), ("sliding_attention", RopeParameters(1e4)))}
    assert _jaxpr(LlamaConfig(**still), tokens) != base
    # ``RoutedExperts`` handed its own input as the router's traces what it
    # traced with none.
    cfg = LlamaConfig(**accepted)
    x = jnp.zeros((2, 16, 64))
    params = jax.eval_shape(RoutedExperts(cfg).init, jax.random.key(0), x)
    assert str(jax.make_jaxpr(lambda p, x: RoutedExperts(cfg).apply(
        p, x))(params, x)) == str(jax.make_jaxpr(
            lambda p, x: RoutedExperts(cfg).apply(p, x, x))(params, x))


# -- what the config and the other paths refuse --------------------------------

def test_config_refuses_what_it_cannot_be():
    with pytest.raises(ValueError, match="router_input"):
        tiny(router_input="attention")
    with pytest.raises(ValueError, match="router_input"):
        tiny(router_input="layer", num_experts=1, held_experts=0,
             mlp_hidden_act="silu")
    with pytest.raises(ValueError, match="router_input"):
        # An 'E' layer of a pattern is that sublayer alone: no mixer's input.
        LlamaConfig(num_layers=2, hybrid_override_pattern="*E",
                    num_experts=4, router_input="layer", rope_theta=None)
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        tiny(mlp_hidden_act="gelu")
    with pytest.raises(ValueError, match="mlp_hidden_act 'relu'"):
        tiny(shared_experts=1)
    with pytest.raises(ValueError, match="mlp_hidden_act 'relu'"):
        tiny(first_dense_layers=1)
    with pytest.raises(ValueError, match="mlp_hidden_act 'relu'"):
        tiny(num_experts=1, held_experts=0, router_input="experts")
    with pytest.raises(ValueError, match="rope_parameters"):
        tiny(rope_parameters=(("full_attention", None),))   # sliding: none
    cfg = tiny()
    assert [cfg.rope_of(i) for i in range(4)] == [
        None] + [RopeParameters(THETA)] * 3
    assert [cfg.window_of(i) for i in range(4)] == [None, 48, 48, 48]
    assert all(cfg.is_routed(i) for i in range(4))
    assert cfg.layers[0].rope is None and cfg.layers[0].mixer == "attention"


@pytest.mark.parametrize("what, changes, word", [
    ("the router's input", {"mlp_hidden_act": "silu"},
     "router ahead of the mixer"),
    ("the activation", {"router_input": "experts"}, "ReLU-gated experts"),
    ("a type that does not rotate",
     {"router_input": "experts", "mlp_hidden_act": "silu", "num_experts": 1,
      "held_experts": 0, "layer_types": None, "sliding_window": None},
     "layer type that does not rotate beside one that does"),
])
def test_the_other_paths_refuse_the_new_kinds_by_name(what, changes, word):
    from horovod_tpu.models.generation import prefill
    from horovod_tpu.parallel.pipeline import init_pipelined_llama

    cfg = dataclasses.replace(tiny(), **changes)
    for who in ("KV-cache decode", "the pipelined step",
                "serve model 'x': the paged KV cache"):
        with pytest.raises(NotImplementedError, match=word) as raised:
            cfg.refuse_new_kinds(who)
        assert who in str(raised.value) and "not built" in str(raised.value)
    with pytest.raises(NotImplementedError, match=word):
        prefill(cfg, {}, jnp.zeros((1, 4), jnp.int32), cache_len=8)
    with pytest.raises(NotImplementedError, match=word):
        init_pipelined_llama(cfg, jax.random.key(0), 1)
