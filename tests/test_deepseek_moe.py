"""DeepSeek-V2-Lite's routed layer in ``models/llama.py`` against the plain
reference (``benchmark/reference/deepseek_v2_lite.py``), on the CPU at tiny
widths with seeded weights: the layer and its shares, the balance loss, the
walk over the row buffers and the rows' way back to their tokens.  YaRN,
latent attention, the whole model and what the comparison's limits catch are
in ``tests/test_deepseek_model.py``, which shares this file's helpers."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v2_lite as ref
from horovod_tpu.models import llama
from horovod_tpu.models.llama import (LlamaConfig, RoutedExperts,
                                      YarnScaling)
from horovod_tpu.ops.losses import balance_loss, sequence_balance_loss

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
