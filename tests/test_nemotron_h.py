"""Nemotron-3-Nano's three layers (``models/llama.py`` with a
``hybrid_override_pattern``: ``Mamba2``, ``RoutedExperts`` with relu2 experts
behind a sigmoid, bias-corrected router, attention without a rotation) against
the plain reference (``benchmark/reference/nemotron_h.py``), on the CPU in
float32 at a small size with seeded random weights moved off their start."""

import copy
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu.jax as hvd
from benchmark import manifest
from benchmark.reference import nemotron_h as reference
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.models import llama
from horovod_tpu.ops import gated_norm, grouped_matmul
from tiny_sizes import TINY

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "nemotron-3-nano-30b-a3b.train-s8k-b2"


@pytest.fixture(scope="module")
def tiny():
    """The tiny job in float32 with the model's own dense attention, its
    parameters moved off their start (every bias, scale and skip differs
    from 0 or 1), a batch, and the configuration the reference reads."""
    cell = manifest.cell(CELL)
    over = TINY["ssm_moe_lm"]
    config = {**cell["config"], **over["config"]}
    job = manifest.load_job("ssm_moe_lm").build(
        config, {**cell["traffic"], **over["traffic"]}, 1)
    job.llama = dataclasses.replace(job.llama, dtype=jnp.float32,
                                    logits_dtype=jnp.float32, remat="none")
    job.model = LlamaModel(job.llama)
    k_init, k_move, k_batch = jax.random.split(jax.random.key(50), 3)
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
    job, _, params, bias, batch, wanted = tiny
    loss_off, grad_off, worst_leaf = _distance(job, params, bias, batch,
                                               wanted)
    assert loss_off < 2e-5 and grad_off < 1e-4 and worst_leaf < 2e-3


def _norm_then_gate(y, z, scale, groups, eps):
    y = y.astype(jnp.float32)
    grouped = y.reshape(*y.shape[:-1], groups, -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return grouped.reshape(y.shape) * scale * jax.nn.silu(z)


def _without(params, name):
    """The parameters with every leaf called ``name`` zeroed."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf)
        if getattr(path[-1], "key", None) == name else leaf, params)


@pytest.mark.parametrize("variant", [
    "norm_before_gate", "no_skip_d", "no_filter_bias", "plain_relu",
    "no_scaling_factor"])
def test_a_wrong_version_fails_the_comparison(tiny, monkeypatch, variant):
    """Each departure from the published layer, alone, in the program: on
    parameters moved off their start the LOSS is already outside what the
    job as it is keeps to (2e-5), so the forward pass alone is compiled."""
    job, _, params, bias, batch, wanted = tiny
    wrong = params
    if variant == "norm_before_gate":
        monkeypatch.setattr(gated_norm, "_gate_then_norm", _norm_then_gate)
    elif variant == "no_skip_d":
        wrong = _without(params, "d")
    elif variant == "no_filter_bias":
        wrong = _without(params, "conv_b")
    elif variant == "plain_relu":
        monkeypatch.setattr(llama, "_relu2", jax.nn.relu)
    elif variant == "no_scaling_factor":
        job = _copy_with_factor(job, 1.0)
    # ``_one_buffer`` is a jit with traces of its own: the one variant that
    # changes what it traces to clears them, before and behind itself.
    cached = variant == "plain_relu"
    if cached:
        jax.clear_caches()
    try:
        loss, _ = jax.jit(job.loss_fn)(wrong, bias, batch)
    finally:
        monkeypatch.undo()
        if cached:
            jax.clear_caches()
    assert abs(float(loss - wanted[0])) > 2e-4, (variant, float(loss))


def _copy_with_factor(job, factor):
    other = copy.copy(job)
    other.llama = dataclasses.replace(job.llama,
                                      routed_scaling_factor=factor)
    other.model = LlamaModel(other.llama)
    return other


# -- the router and the experts ---------------------------------------------

def _routed_layer(tiny, held=None, first=None):
    job, config, params = tiny[:3]
    cfg = job.llama if held is None else dataclasses.replace(
        job.llama, held_experts=held, first_held_expert=first)
    return cfg, config, params["params"]["layer_2"]["moe"]


def test_nonzero_bias_moves_the_choice_and_not_the_gates(tiny):
    cfg, config, moe = _routed_layer(tiny)
    experts = cfg.num_experts
    u = jax.random.normal(jax.random.key(5), (2, 64, cfg.hidden_size))
    bias = jnp.zeros(experts).at[3].set(5.0).at[0].set(-5.0)

    def apply(moe, bias):
        return llama.RoutedExperts(cfg).apply(
            {"params": moe, llama.ROUTER_STATE: {"bias": bias}}, u,
            mutable=["moe_stats", "losses"])

    plain, stats0 = apply(moe, jnp.zeros(experts))
    biased, stats = apply(moe, bias)
    counts0 = stats0["moe_stats"]["assignments_per_expert"][0]
    counts = stats["moe_stats"]["assignments_per_expert"][0]
    assert counts[3] == 2 * 64 and counts[0] == 0       # every token, none
    assert 0 < counts0[3] < 2 * 64 and counts0[0] > 0
    assert stats["moe_stats"]["bias_abs_max"][0] == 5.0
    layer = {"router": moe["router"]["kernel"],
             "experts": {"w_up": moe["w_up"], "w_down": moe["w_down"]},
             "shared": {n: moe["shared"][n]["kernel"]
                        for n in ("w_up", "w_down")}}
    with jax.default_matmul_precision("highest"):
        wanted, _ = reference.routed_experts(u, layer, config, bias)
        wanted0, _ = reference.routed_experts(u, layer, config)
    # The gates are the UNbiased scores: the reference with the same bias
    # agrees, to float32; and the output did move.
    np.testing.assert_allclose(biased, wanted, atol=2e-5)
    np.testing.assert_allclose(plain, wanted0, atol=2e-5)
    assert float(jnp.max(jnp.abs(biased - plain))) > 1e-2
    # No gradient reaches the bias.
    g_bias = jax.grad(lambda b: jnp.sum(apply(moe, b)[0] ** 2))(bias)
    assert not np.asarray(g_bias).any()


def test_sixteen_shares_add_up_to_the_uncut_layer(tiny):
    """The cut's arithmetic (model-configs guide, section 4): the shares'
    routed parts, each chip holding its own experts of all of them, plus
    the shared expert counted once, are the layer with every expert held.
    Here four shares of two experts; the router, its bias and the shared
    expert are whole on every chip."""
    cfg, _, moe = _routed_layer(tiny)
    experts = cfg.num_experts
    k_up, k_down, k_u = jax.random.split(jax.random.key(9), 3)
    w_up = jax.random.normal(k_up, (experts, *moe["w_up"].shape[1:])) * 0.1
    w_down = jax.random.normal(k_down,
                               (experts, *moe["w_down"].shape[1:])) * 0.1
    u = jax.random.normal(k_u, (2, 32, cfg.hidden_size))
    bias = {"bias": 0.05 * jnp.arange(experts, dtype=jnp.float32)}

    def layer(held, first):
        part = dataclasses.replace(cfg, held_experts=held,
                                   first_held_expert=first)
        weights = {**moe, "w_up": w_up[first:first + held],
                   "w_down": w_down[first:first + held]}
        return llama.RoutedExperts(part).apply(
            {"params": weights, llama.ROUTER_STATE: bias}, u)

    shared = llama.Relu2MLP(
        cfg, cfg.moe_shared_expert_intermediate_size).apply(
            {"params": moe["shared"]}, u)
    whole = layer(experts, 0)
    shares = sum(layer(2, first) - shared for first in range(0, experts, 2))
    np.testing.assert_allclose(shares + shared, whole, atol=2e-5)


def test_bias_update_sign_and_rate_over_two_steps(tiny):
    """Through ``make_train_step(has_aux=True)``: after a step every entry
    moved by the rate, up where the expert got fewer assignments than the
    mean and down where more; the optimizer never sees the bias."""
    job, _, params, bias, batch, _ = tiny
    mesh = hvd.build_mesh({"data": 1}, devices=jax.devices()[:1])
    step = hvd.make_train_step(job.loss_fn, job.optimizer, mesh,
                               has_aux=True, donate=False)
    moe, _ = job.layer_counters(params, bias, batch)
    counts = np.asarray(moe["assignments_per_expert"], np.float64)
    mean = counts.sum(axis=1, keepdims=True) / counts.shape[1]
    rate = job.llama.router_bias_update_rate
    opt_state = job.optimizer.init(params)
    assert not any("bias" in str(path) for path, _ in
                   jax.tree_util.tree_flatten_with_path(opt_state)[0]
                   if "dt_bias" not in str(path))
    params1, opt_state, bias1, _ = step(params, opt_state, bias, batch)
    got = np.stack([np.asarray(bias1["layer_2"]["moe"]["bias"])])
    np.testing.assert_allclose(got, rate * np.sign(mean - counts),
                               atol=1e-9)
    _, _, bias2, _ = step(params1, opt_state, bias1, batch)
    got2 = np.stack([np.asarray(bias2["layer_2"]["moe"]["bias"])])
    steps = np.abs(got2 - got) / rate
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-4)
    assert steps.max() <= 1.0 and np.abs(got2).max() <= 2 * rate + 1e-9


# -- the widths the grouped products see --------------------------------------

def _off_tile_layer(act, width, lift, hidden=64):
    """A routed layer of 2 held experts (ids 4 and 5) of 16, two
    choices a token of 1,024: four row buffers of 512, of which the router
    as it is drawn fills one, and all four where feature 0 lifts the held
    experts' logits by ``lift``.  ``(config, params, x)``."""
    cfg = LlamaConfig(
        vocab_size=64, hidden_size=hidden, num_layers=1, num_heads=2,
        num_kv_heads=2, intermediate_size=64, max_seq_len=1024,
        num_experts=16, experts_per_token=2, moe_intermediate_size=width,
        shared_experts=0, held_experts=2, first_held_expert=4,
        norm_topk_prob=False, mlp_hidden_act=act, dtype=jnp.float32,
        logits_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(57), (1, 1024, hidden)).at[..., 0].set(1)
    params = llama.RoutedExperts(cfg).init(jax.random.key(7), x)["params"]
    params["router"]["kernel"] = params["router"]["kernel"].at[0].set(
        jnp.where((jnp.arange(16) >= 4) & (jnp.arange(16) < 6), lift, 0.0))
    return cfg, params, x


@pytest.fixture
def interpreted(monkeypatch):
    """The Mosaic grouped matmul wherever a TPU would take it, interpreted
    (``grouped_matmul._why_not``'s last reason lifted)."""
    rule = grouped_matmul._why_not

    def lifted(*shape_and_place):
        why = rule(*shape_and_place)
        return None if why == grouped_matmul.NO_TPU else why

    assert rule(512, True) == grouped_matmul.NO_TPU
    monkeypatch.setattr(grouped_matmul, "_why_not", lifted)
    llama._one_buffer.clear_cache()     # it keeps its traces by shape
    yield
    llama._one_buffer.clear_cache()


def _dense_loop(cfg, params, x):
    """The layer as a loop over the held experts, every token through every
    one of them and the gates of those that did not choose it zero."""
    scores = jax.nn.softmax(x @ params["router"]["kernel"], axis=-1)
    gates, chosen = jax.lax.top_k(scores, cfg.experts_per_token)
    y = 0.0
    for held in range(cfg.experts_held):
        up = x @ params["w_gate_up" if cfg.mlp_hidden_act == "silu"
                        else "w_up"][held]
        if cfg.mlp_hidden_act == "silu":
            gate, up = jnp.split(up, 2, axis=-1)
            rows = jax.nn.silu(gate) * up
        else:
            rows = jnp.square(jax.nn.relu(up))
        gate_of = jnp.sum(jnp.where(
            chosen == held + cfg.first_held_expert, gates, 0.0), axis=-1)
        y = y + gate_of[..., None] * (rows @ params["w_down"][held])
    return y


@pytest.mark.parametrize("buffers", ["one buffer", "several buffers"])
@pytest.mark.parametrize("act", ["relu2", "silu"])
def test_experts_off_the_lane_tile_are_the_dense_loop(act, buffers,
                                                      interpreted):
    """F = 72 is no whole lane tile: in place the grouped products are the
    Mosaic grouped matmul (interpreted here; a gated expert's first one 144
    wide, the split in the middle), on the matrices as they are, and output,
    the tokens' gradient and every gradient leaf, at the parameters' own
    shapes, are the plain loop's."""
    assert 72 % grouped_matmul.LANES
    cfg, params, x = _off_tile_layer(act, 72, 8.0 if "several" in buffers
                                     else 0.0)
    module = llama.RoutedExperts(cfg, in_place=True)
    _, sown = module.apply({"params": params}, x, mutable=["moe_stats"])
    run = int(sown["moe_stats"]["row_buffers_run"][0])
    assert (run > 1) == ("several" in buffers), run
    assert int(sown["moe_stats"]["rows_dropped"][0]) == 0
    weight = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def both(layer):
        return jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(
            layer(p, x) * weight), argnums=(0, 1)))(params, x)

    before = grouped_matmul.body_counts()
    with jax.default_matmul_precision("highest"):
        y, (grads, dx) = both(lambda p, x: module.apply({"params": p}, x))
        y_loop, (grads_loop, dx_loop) = both(
            lambda p, x: _dense_loop(cfg, p, x))
    assert grouped_matmul.body_counts()["xla"] == before["xla"]
    np.testing.assert_allclose(y, y_loop, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(dx, dx_loop, rtol=2e-4, atol=2e-4)
    assert jax.tree.map(jnp.shape, grads) == jax.tree.map(jnp.shape, params)
    assert grads["w_down"].shape == (2, 72, 64)
    assert grads["w_gate_up" if act == "silu" else "w_up"].shape == (
        2, 64, 144 if act == "silu" else 72)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads),
                                 jax.tree.leaves(grads_loop)):
        assert float(jnp.max(jnp.abs(want))) > 1e-3, path
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("width, in_place, way", [
    (72, True, "mosaic"), (200, True, "mosaic"),
    (128, True, "mosaic"), (256, True, "mosaic"),
    (72, False, grouped_matmul.NOT_IN_PLACE),
    (128, False, grouped_matmul.NOT_IN_PLACE)],
    ids=["72", "200", "128", "256", "72, not in place",
         "128, not in place"])
@pytest.mark.parametrize("act", ["relu2", "silu"])
def test_the_widths_alone_say_which_grouped_product_runs(
        act, width, in_place, way, interpreted):
    """The widths decide nothing (hidden 128; experts off the lane tile, 72
    and 200 wide, and on it, 128 and 256, which kept XLA's body until PR 68
    by a rule that no measurement of theirs bore out): in a trace that may
    hold Mosaic calls every grouped product is a Mosaic call and none is
    ``ragged_dot``; in a trace that may not, every one is ``ragged_dot`` on
    the parameters as they are and no Mosaic call is traced.  Nothing is
    padded either way.  ``grouped_matmul.body_counts()`` says which way each
    traced product went."""
    cfg, params, x = _off_tile_layer(act, width, 0.0, hidden=128)
    before = grouped_matmul.body_counts()
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(
        llama.RoutedExperts(cfg, in_place=in_place).apply(
            {"params": p}, x))))(params, x).jaxpr
    after = grouped_matmul.body_counts()
    moved = {"mosaic": after["mosaic"] - before["mosaic"], **{
        why: n - before["xla"].get(why, 0) for why, n in after["xla"].items()}}
    assert [why for why, n in moved.items() if n] == [way], moved
    xla = _equations(jaxpr, "ragged_dot_general")
    mosaic = _equations(jaxpr, "pallas_call")
    # (the first buffer and the loop's, forward, again and backward)
    assert (len(xla), len(mosaic)) == ((0, 16) if way == "mosaic"
                                       else (16, 0))
    halves = 2 if act == "silu" else 1
    for eqn in xla + mosaic:
        # (the scalars a Mosaic call prefetches aside; the rows' gradients
        # multiply with the matrices as they are, read transposed)
        shapes = {v.aval.shape for v in (*eqn.invars, *eqn.outvars)
                  if v.aval.ndim > 1}
        assert shapes <= {(512, 128), (512, width), (512, halves * width),
                          (2, 128, halves * width), (2, width, 128),
                          (2, halves * width, 128), (2, 128, width)}, shapes
    assert not [eqn for eqn in _equations(jaxpr, "pad")
                if eqn.invars[0].aval.ndim > 1]


def _equations(jaxpr, primitive):
    """The equations of that primitive, sub-jaxprs walked."""
    found = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == primitive]
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _equations(sub, primitive)
    return found


# -- the filter's two bodies --------------------------------------------------

@pytest.mark.parametrize("width, gates", [(16, "plain"), (32, "mosaic")],
                         ids=["heads of 16: the filter", "heads of 32: and "
                              "the gates"])
def test_a_mamba_layer_in_place_is_the_layer_it_is_elsewhere(tiny, width,
                                                             gates,
                                                             monkeypatch):
    """``Mamba2(in_place=True)`` sends its biased filter through
    ``ops/short_conv.py``'s Mosaic pass (interpreted here), which reads x, B
    and C where ``in_proj`` left them; ``in_place=False`` through the
    ``jnp`` body, which the reference holds above.  On a Mamba layer of 8
    heads of 16 (the filter's channels then start at a lane tile, 128, as
    the cell's at 4096) moved off its start (``conv_b`` no zeros), two rows
    of 96 tokens (three blocks of rows), the two give the same output and
    the same gradient of every leaf: a bias the pass dropped shows in
    ``conv_b``'s leaf, by name.  With heads of 32 a norm group is a lane
    tile, and the skip, the gate and the norm are ``ops/gated_norm.py``'s
    pair too (its rule's last reason lifted: a TPU's answer), on the
    filter's result and ``in_proj``'s output where they lie."""
    from horovod_tpu.ops import short_conv

    rule = gated_norm._why_not
    monkeypatch.setattr(gated_norm, "_why_not", lambda *a: (
        None if rule(*a) == gated_norm.NO_TPU else rule(*a)))
    config = dataclasses.replace(tiny[0].llama, mamba_num_heads=8,
                                 mamba_head_dim=width)
    k_init, k_move, k_x = jax.random.split(jax.random.key(53), 3)
    x = jax.random.normal(k_x, (2, 96, config.hidden_size))
    layer = llama.Mamba2(config).init(k_init, x)["params"]
    leaves, tree = jax.tree.flatten(layer)
    layer = jax.tree.unflatten(tree, [
        leaf + 0.1 * (jnp.std(leaf) or 1.0) * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, jax.random.split(k_move, len(leaves)))])
    assert float(jnp.min(jnp.abs(layer["conv_b"]))) > 0

    def both_ways(in_place):
        module = llama.Mamba2(config, in_place=in_place)

        def run(p, x):
            out, vjp = jax.vjp(lambda p, x: module.apply({"params": p}, x),
                               p, x)
            return {"out": out, "grads": vjp(jnp.cos(out))}
        return {jax.tree_util.keystr(path): leaf for path, leaf in
                jax.tree_util.tree_leaves_with_path(jax.jit(run)(layer, x))}

    before = short_conv.body_counts(), gated_norm.body_counts()
    got = both_ways(True)
    after = short_conv.body_counts(), gated_norm.body_counts()
    assert after[0]["fused"] == before[0]["fused"] + 1
    assert after[0]["plain"] == before[0]["plain"]
    assert after[1]["mosaic"] - before[1]["mosaic"] == (gates == "mosaic")
    assert after[1]["plain"].get(gated_norm.GROUP_OFF_THE_TILE, 0) - before[
        1]["plain"].get(gated_norm.GROUP_OFF_THE_TILE, 0) == (gates == "plain")
    wanted = both_ways(False)
    assert "['grads'][0]['conv_b']" in got and len(got) == len(
        jax.tree.leaves(layer)) + 2
    for name, want in wanted.items():
        off = float(jnp.linalg.norm(got[name] - want) / jnp.linalg.norm(want))
        assert off < 1e-5, (name, off)


# -- the stack and the config -----------------------------------------------

def test_one_norm_and_one_sublayer_a_layer(tiny):
    _, _, params, bias = tiny[:4]
    layers = params["params"]
    assert {k: sorted(v) for k, v in layers.items() if k.startswith(
        "layer_")} == {
            "layer_0": ["mamba", "norm"], "layer_1": ["attn", "norm"],
            "layer_2": ["moe", "norm"]}
    assert sorted(bias) == ["layer_2"]
    assert sorted(layers["layer_2"]["moe"]) == ["router", "shared", "w_down",
                                                "w_up"]
    assert sorted(layers["layer_1"]["attn"]) == ["wk", "wo", "wq", "wv"]


def test_layers_without_a_pattern_build_the_trees_they_built():
    """Names and shapes of two accepted tiny sizes, stored from the commit
    before the pattern came (``tests/testdata/llama_param_trees.json``)."""
    with open(os.path.join(HERE, "testdata", "llama_param_trees.json")) as f:
        stored = json.load(f)
    for workload, wanted in stored.items():
        cell = manifest.cell(workload)
        over = TINY[cell["config"]["job"]]
        job = manifest.load_job(cell["config"]["job"]).build(
            {**cell["config"], **over["config"]},
            {**cell["traffic"], **over["traffic"]}, 1)
        shapes = jax.eval_shape(lambda k, job=job: LlamaModel(job.llama).init(
            k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
        got = {"/".join(str(getattr(k, "key", k)) for k in path):
               list(leaf.shape) for path, leaf in
               jax.tree_util.tree_flatten_with_path(shapes)[0]}
        assert got == wanted, workload


PATTERN = dict(num_layers=3, hybrid_override_pattern="ME*", num_experts=4,
               mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
               n_groups=2, hidden_size=64, num_heads=4, num_kv_heads=2)


@pytest.mark.parametrize("changes,says", [
    (dict(hybrid_override_pattern="M-*"), "dense"),
    (dict(hybrid_override_pattern="ME"), "for each of 3 layers"),
    (dict(hybrid_override_pattern="MEX"), "for each of 3 layers"),
    (dict(num_experts=1), "num_experts > 1"),
    (dict(mamba_num_heads=0), "mamba_num_heads"),
    (dict(n_groups=3), "n_groups"),
    (dict(norm_placement="post"), "norm_placement"),
    (dict(scoring_func="tanh"), "scoring_func"),
    (dict(topk_method="group"), "topk_method"),
    (dict(mlp_hidden_act="gelu"), "mlp_hidden_act"),
])
def test_config_refuses(changes, says):
    with pytest.raises(ValueError, match=says):
        LlamaConfig(**{**PATTERN, **changes})


def test_pattern_as_a_tuple_is_the_string():
    as_tuple = LlamaConfig(**{**PATTERN,
                              "hybrid_override_pattern": ("M", "E", "*")})
    assert [as_tuple.kind_of(i) for i in range(3)] == ["M", "E", "*"]
    assert [as_tuple.is_routed(i) for i in range(3)] == [False, True, False]
    assert LlamaConfig().kind_of(0) is None


@pytest.mark.parametrize("changes,says", [
    (dict(), "Mamba-2 state-space"),
    (dict(hybrid_override_pattern="*E*", topk_method="noaux_tc"),
     "bias-corrected router"),
    (dict(hybrid_override_pattern="*E*"), "layer pattern"),
])
def test_the_paths_with_a_layer_of_their_own_refuse_by_name(changes, says):
    config = LlamaConfig(**{**PATTERN, **changes})
    with pytest.raises(NotImplementedError, match=says):
        config.refuse_new_kinds("generation")
