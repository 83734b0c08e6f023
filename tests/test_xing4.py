"""Residual streams under manifold-constrained hyper-connections, and latent
attention's query latent (``xing4.0-29b-a4b``), at a small size on the CPU:
the program against ``benchmark/reference/xing4.py`` on seeded weights -- one
sublayer's maps, the mixes, the query latent alone, a layer, the loss and
every gradient; the shares of a routed layer add up to the uncut layer; with
``hc_mult`` 1 and no query latent an accepted configuration traces what it
traced; the other paths refuse both by name."""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.reference import xing4 as ref
from horovod_tpu.common import scopes
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.models import llama
from horovod_tpu.models.llama import (HyperConnection, LatentAttention,
                                      LlamaLayer, YarnScaling,
                                      causal_attention, rope_freqs)
from horovod_tpu.ops.flash_attention import flash_attention_fn
from tiny_sizes import TINY          # tests/conftest.py put it on the path

CELL = "xing4.0-29b-a4b.train-s8k"
STREAMS = 4


def tiny_job(**changes):
    """The cell's own job at the tests' tiny widths (``tests/conftest.py``),
    in float32, and the configuration the reference reads."""
    cell = manifest.cell(CELL)
    config = {**cell["config"], **TINY["hc_moe_lm"]["config"], **changes}
    traffic = {**cell["traffic"], **TINY["hc_moe_lm"]["traffic"]}
    job = manifest.load_job(config["job"]).build(config, traffic, 1)
    job.llama = dataclasses.replace(job.llama, dtype=jnp.float32,
                                    logits_dtype=jnp.float32)
    return job, config


def moved(params, seed=7, by=0.05):
    """``params`` off their start: every leaf (norms, gains and biases too)
    plus seeded noise of ``by`` times its own root mean square, or of ``by``
    where it is zero."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + by * jnp.maximum(jnp.sqrt(jnp.mean(leaf * leaf)), 1.0 * (
            jnp.max(jnp.abs(leaf)) == 0))
        * jax.random.normal(key, leaf.shape, leaf.dtype)
        for leaf, key in zip(leaves, keys)])


def streams_of(key, batch=2, seq=24, width=128):
    """Four DISTINCT streams a token, of unequal size."""
    x = jax.random.normal(key, (batch, seq, STREAMS, width))
    return x * jnp.array([0.5, 1.0, 1.5, 2.0])[:, None]


# -- one sublayer's maps and mixes ---------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_a_sublayers_maps_are_the_references(seed):
    """``HyperConnection`` on distinct streams, gains of order 1 and biases
    off zero: h_pre, h_post and H_res are the reference's (tokens on the
    lanes here, token-major there); h_pre lies in (0, 1) and h_post in (0,
    2); H_res's rows sum to 1 (the last step divides them) and its columns
    to within 1e-3: a Sinkhorn step contracts the distance from the doubly
    stochastic matrices by the square of the matrix's second singular
    value at least, under 0.7 for entries of order 1 (logits of a few
    units), so 20 steps leave under 0.7 ** 20 < 1e-3 of the first
    distance."""
    job, config = tiny_job()
    x = streams_of(jax.random.key(seed))
    module = HyperConnection(job.llama)
    params = moved(module.init(jax.random.key(seed + 10), x), by=0.3)
    assert {name: leaf.shape for name, leaf in params["params"].items()} == {
        "phi_pre": (512, 4), "phi_post": (512, 4), "phi_res": (512, 16),
        "b_pre": (4,), "b_post": (4,), "b_res": (16,),
        "g_pre": (), "g_post": (), "g_res": ()}
    full = {**config, "hc_sinkhorn_iters": 20}
    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = HyperConnection(dataclasses.replace(
            job.llama, hc_sinkhorn_iters=20)).apply(params, x)
        want = ref.hyper_maps(x, params["params"], full)
    assert h_pre.shape == h_post.shape == (4, 48)
    assert h_res.shape == (4, 4, 48)
    np.testing.assert_allclose(h_pre.T.reshape(2, 24, 4), want[0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h_post.T.reshape(2, 24, 4), want[1],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        jnp.moveaxis(h_res, -1, 0).reshape(2, 24, 4, 4), want[2], rtol=1e-5,
        atol=1e-6)
    assert 0 < float(h_pre.min()) and float(h_pre.max()) < 1
    assert 0 < float(h_post.min()) and float(h_post.max()) < 2
    assert float(jnp.std(h_pre)) > 0.05 and float(jnp.std(h_res)) > 0.05
    np.testing.assert_allclose(jnp.sum(h_res, axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(jnp.sum(h_res, axis=0), 1.0, atol=1e-3)
    # One step is not twenty: the columns are then far from 1.
    one = HyperConnection(dataclasses.replace(
        job.llama, hc_sinkhorn_iters=1)).apply(params, x)[2]
    assert float(jnp.max(jnp.abs(jnp.sum(one, axis=0) - 1.0))) > 0.02


def test_the_residual_logits_are_clamped_ahead_of_exp():
    """Logits of +-1000 through a clamp of +-30 give a finite, nearly
    doubly stochastic H_res and a finite gradient; the clamp is where the
    configuration puts it."""
    job, _ = tiny_job()
    x = streams_of(jax.random.key(3))
    params = HyperConnection(job.llama).init(jax.random.key(4), x)
    params["params"]["g_res"] = jnp.float32(1000.0)

    def total(params):
        return jnp.sum(HyperConnection(job.llama).apply(params, x)[2] ** 2)

    value, grads = jax.value_and_grad(total)(params)
    assert np.isfinite(float(value))
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree.leaves(grads))
    # Under +-30 the projection is all but a permutation; under +-1 the
    # entries start within e^2 of each other and none can vanish.
    assert float(HyperConnection(job.llama).apply(params, x)[2].min()) < 1e-6
    narrow = dataclasses.replace(job.llama, hc_res_clamp=(-1.0, 1.0))
    assert float(HyperConnection(narrow).apply(params, x)[2].min()) > 0.01


def test_the_mixes_are_the_references():
    x = streams_of(jax.random.key(5))
    y = jax.random.normal(jax.random.key(6), (2, 24, 128))
    h_pre = jax.random.uniform(jax.random.key(7), (4, 48))
    h_post = 2 * jax.random.uniform(jax.random.key(8), (4, 48))
    h_res = jax.random.uniform(jax.random.key(9), (4, 4, 48))
    by_token = lambda h: jnp.moveaxis(h, -1, 0).reshape((2, 24) + h.shape[:-1])
    np.testing.assert_allclose(
        llama._hc_read(x, h_pre),
        jnp.einsum("bsj,bsjc->bsc", by_token(h_pre), x), rtol=1e-5,
        atol=1e-6)
    np.testing.assert_allclose(
        llama._hc_write(x, y, h_post, h_res),
        jnp.stack([sum(by_token(h_res)[:, :, i, j, None] * x[:, :, j]
                       for j in range(4))
                   + by_token(h_post)[:, :, i, None] * y
                   for i in range(4)], axis=2), rtol=1e-5, atol=1e-5)
    # bf16 streams are summed in float32 and rounded once.
    wrote = llama._hc_write(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
                            h_post, h_res)
    assert wrote.dtype == jnp.bfloat16 and wrote.shape == x.shape


# -- the query latent alone ------------------------------------------------------

@pytest.mark.parametrize("attention_fn", [flash_attention_fn,
                                          causal_attention])
def test_the_query_latent_is_the_references(attention_fn):
    """``LatentAttention`` with ``q_lora_rank`` and no streams: ``wq_a``,
    ``q_norm``, ``wq_b`` in place of ``wq``, the reference's block."""
    job, config = tiny_job()
    cfg = dataclasses.replace(job.llama, hc_mult=1)
    x = jax.random.normal(jax.random.key(0), (2, 128, cfg.hidden_size))
    cos, sin = rope_freqs(cfg.rope_dim, 128, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    module = LatentAttention(cfg, attention_fn=attention_fn)
    params = moved(LatentAttention(cfg).init(jax.random.key(1), x, cos, sin))
    leaves = params["params"]
    assert set(leaves) == {"wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
                           "wkv_b", "wo"}
    assert leaves["wq_a"]["kernel"].shape == (128, 48)
    assert leaves["wq_b"]["kernel"].shape == (48, 2 * 128)
    plain = {**{name: leaves[name]["kernel"] for name in (
        "wq_a", "wq_b", "wkv_a", "wkv_b", "wo")},
        **{name: leaves[name]["scale"] for name in ("q_norm", "kv_norm")}}
    with jax.default_matmul_precision("highest"):
        got = module.apply(params, x, cos, sin)
        want = ref.latent_attention(x, plain, config)
        # ``q_norm`` left out is another function.
        no_norm = ref.latent_attention(
            x, {**plain, "q_norm": jnp.ones_like(plain["q_norm"])
                * jnp.sqrt(jnp.mean((x @ plain["wq_a"]) ** 2))}, config)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(no_norm - want))) > 1e-2


# -- a layer, the loss and every gradient ----------------------------------------

@pytest.fixture(scope="module")
def whole_model():
    """Three layers (a dense one and two routed) under ``remat``, on moved
    parameters, with the reference's loss and gradients."""
    job, config = tiny_job(num_hidden_layers=3)
    state = jax.jit(job.init_state)(jax.random.key(0))
    params = moved(jax.tree.map(lambda p: p.astype(jnp.float32), state[0]))
    tokens = job.make_batch(jax.random.key(1), 2)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.loss_and_grads(
            job.to_reference(p), tokens, config))(params)
    return job, config, params, state[2], tokens, want


@pytest.mark.parametrize("attention_fn", [flash_attention_fn,
                                          causal_attention])
def test_whole_model_agrees_with_the_plain_reference_in_float32(
        whole_model, attention_fn):
    job, _, params, bias, tokens, (want_loss, want_grads) = whole_model
    job.model = LlamaModel(job.llama, attention_fn=attention_fn)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.jit(jax.value_and_grad(
            job.loss_fn, has_aux=True))(params, bias, tokens)
    assert float(loss) == pytest.approx(float(want_loss), abs=2e-5)
    got = job.to_reference(grads)
    assert jax.tree.structure(got) == jax.tree.structure(want_grads)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path
        np.testing.assert_allclose(g, w, atol=2e-3 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_references_sweep_is_the_gradient_of_its_loss(whole_model):
    """``loss_and_grads`` walks the layers backward with ``jax.vjp`` (one
    layer's float32 parameters alive at a time); it is
    ``jax.value_and_grad`` of ``loss``, the definition."""
    job, config, params, _, tokens, (want_loss, want_grads) = whole_model
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, tokens, config)))(job.to_reference(params))
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            g, w, atol=1e-5 * float(jnp.max(jnp.abs(w))), rtol=1e-5)


def test_the_first_sublayers_read_and_residual_maps_have_no_gradient(
        whole_model):
    """Four copies of the embedding: h_pre scales what the norm norms and
    H_res, whose rows sum to one, leaves the copies as they are, so the six
    leaves behind them get a gradient of rounding's size (the reference
    does not take them: its docstring) while ``*_post`` and every leaf of
    the next sublayer get a live one."""
    job, _, params, bias, tokens, _ = whole_model
    job.model = LlamaModel(job.llama, attention_fn=causal_attention)
    grads = jax.jit(jax.grad(lambda p: job.loss_fn(p, bias, tokens)[0]))(
        params)["params"]["layer_0"]
    live = float(jnp.max(jnp.abs(grads["hc_attn"]["phi_post"])))
    for name in ("phi_pre", "b_pre", "g_pre", "phi_res", "b_res", "g_res"):
        assert float(jnp.max(jnp.abs(grads["hc_attn"][name]))) < 1e-4 * live
        assert float(jnp.max(jnp.abs(grads["hc_mlp"][name]))) > 1e-3 * live


def test_a_routed_layer_is_the_references_layer(whole_model):
    """``LlamaLayer`` 1 on distinct streams against ``decoder_layer``."""
    job, config, params, bias, _, _ = whole_model
    cfg = job.llama
    x = streams_of(jax.random.key(2), seq=32)
    cos, sin = rope_freqs(cfg.rope_dim, 32, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    layer = {"params": params["params"]["layer_1"],
             llama.ROUTER_STATE: bias["layer_1"]}
    with jax.default_matmul_precision("highest"):
        got = LlamaLayer(cfg, index=1).apply(layer, x, cos, sin)
        want, _ = ref.decoder_layer(
            x, job.to_reference(params)["layers"][1], config)
    assert got.shape == x.shape
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("what, changes", [
    ("one Sinkhorn step", {"hc_sinkhorn_iters": 1}),
    ("no query norm", {"rms_norm_eps": 1e6}),
    ("gates not renormalised", {"norm_topk_prob": False}),
    ("the factor 2 on the gates dropped", {"routed_scaling_factor": 1}),
])
def test_each_piece_shows_in_the_loss_and_gradient(whole_model, what,
                                                   changes):
    """The reference given another configuration is another function of the
    same parameters: the pieces the comparison has to tell are live at
    these sizes."""
    job, config, params, _, tokens, (want_loss, want_grads) = whole_model
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(lambda p: ref.loss_and_grads(
            job.to_reference(p), tokens, {**config, **changes}))(params)
    off = sum(float(jnp.sum((g - w) ** 2)) for g, w in zip(
        jax.tree.leaves(grads), jax.tree.leaves(want_grads)))
    size = sum(float(jnp.sum(w ** 2)) for w in jax.tree.leaves(want_grads))
    assert (off / size) ** 0.5 > 0.02, what


# -- the shares of a routed layer ------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut, on a hyper-connected routed layer: each
    of four shares of 4 experts computed by the program with those experts'
    weights alone (router, bias-corrected top-3 and renormalised gates over
    all 16); what every chip computes alike -- attention, the maps, the
    shared expert, the streams' mix -- counted once (the reference's layer
    holding NO expert); the four routed parts on top of it are the uncut
    16-expert reference layer.  The write is linear in the sublayer's
    output, which is what lets the parts add."""
    job, config = tiny_job(num_hidden_layers=2)
    cfg = job.llama
    state = jax.jit(job.init_state)(jax.random.key(3))
    whole = dataclasses.replace(cfg, held_experts=0, first_held_expert=0)
    x = streams_of(jax.random.key(4), seq=32)
    cos, sin = rope_freqs(cfg.rope_dim, 32, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    full = LlamaLayer(whole, index=1).init(jax.random.key(5), x, cos, sin)
    full = {**full, "params": moved(full["params"])}    # the bias stays 0
    moe = full["params"]["moe"]
    assert moe["w_gate_up"].shape[0] == 16
    job.llama = dataclasses.replace(whole, num_layers=2, first_dense_layers=0)
    plain = job.to_reference({"params": {
        "layer_0": full["params"], "layer_1": full["params"],
        "tok_emb": {"embedding": 0}, "norm_f": {"scale": 0},
        "lm_head": {"kernel": 0}}})["layers"][1]
    uncut = {**config, "deployment": {"first_held_expert": 0}}
    none_held = {**plain, "experts": jax.tree.map(lambda w: w[:0],
                                                  plain["experts"])}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.decoder_layer(x, plain, uncut)
        alike, _ = ref.decoder_layer(x, none_held, uncut)
    apply = jax.jit(lambda cfg, params: LlamaLayer(cfg, index=1).apply(
        params, x, cos, sin, mutable=["moe_stats"]), static_argnums=0)
    total, rows = alike, 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(whole, held_experts=4,
                                    first_held_expert=first)
        params = {**full, "params": {**full["params"], "moe": {
            **moe, "w_gate_up": moe["w_gate_up"][first:first + 4],
            "w_down": moe["w_down"][first:first + 4]}}}
        with jax.default_matmul_precision("highest"):
            out, sown = apply(share, params)
        stats = sown["moe_stats"]["moe"]
        assert int(stats["rows_dropped"][0]) == 0
        rows += int(jnp.sum(stats["rows_per_expert"][0]))
        total = total + (out - alike)
    assert rows == 2 * 32 * 3
    assert float(jnp.max(jnp.abs(want - alike))) > 0.05
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=2e-4)


# -- nothing changes without them --------------------------------------------------

ACCEPTED = {
    # ``deepseek-v2-lite``'s kind of stack: latent attention under YaRN, a
    # dense layer, routed experts beside shared ones, remat.
    "latent_routed": dict(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=2,
        num_kv_heads=2, intermediate_size=96, max_seq_len=256, rms_eps=1e-6,
        num_experts=16, experts_per_token=3, held_experts=4,
        first_held_expert=4, moe_intermediate_size=16, shared_experts=2,
        first_dense_layers=1, norm_topk_prob=False, attention_kind="latent",
        kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=32,
        v_head_dim=32, rope_scaling=YarnScaling(
            factor=40, original_max_position_embeddings=64, mscale=0.707,
            mscale_all_dim=0.707), remat="layer_keep_attention"),
    # ``ouro-2.6b``'s: the plain dense decoder.
    "dense": dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=2,
                  num_kv_heads=2, intermediate_size=96, max_seq_len=256,
                  rms_eps=1e-6),
}
# sha256 of ``_trace`` at the parent commit (830aeed, PR 64), made there by
# ``.study/parent_digests.py`` on a ``git archive`` of it: what these two
# stacks traced before the streams and the query latent existed.  A PR that
# changes what they trace on purpose pins them again and says why.
PARENTS = {
    "latent_routed":
        "d43cc7819c9403635e5ca5bdc908b52d97c3e2bc7f528c44c34758542434750f",
    "dense":
        "d58e562edd39d8f1a06184037cbb3bc94cfae11cbe3ce0dce8cdefd49f3e3068",
}


def _trace(config: dict, **fields) -> str:
    """The jaxpr of loss and gradient, forward and backward, scopes (the
    equations' name stacks) and leaf names included; addresses masked."""
    cfg = LlamaConfig(**config, **fields)
    tokens = jnp.zeros((2, 64), jnp.int32)
    model = LlamaModel(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)
    leaves = [jax.tree_util.keystr(path) for path, _ in
              jax.tree_util.tree_leaves_with_path(params)]
    jaxpr = jax.make_jaxpr(lambda p, t: jax.value_and_grad(
        lambda p: jnp.sum(model.apply(p, t, mutable=["losses"])[0].astype(
            jnp.float32)))(p))(params, tokens)
    text = jaxpr.pretty_print(name_stack=True)
    return "\n".join(leaves) + "\n" + re.sub(r"0x[0-9a-f]+", "0x", text)


@pytest.mark.parametrize("kind", sorted(ACCEPTED))
def test_one_stream_and_no_query_latent_trace_what_the_parent_traced(kind):
    """``hc_mult`` 1 and ``q_lora_rank`` None: no new leaf, no new scope, the
    parent's jaxpr; stated at their defaults, the same; each new value,
    another."""
    fields = LlamaConfig.__dataclass_fields__
    assert fields["hc_mult"].default == 1
    assert fields["q_lora_rank"].default is None
    base = _trace(ACCEPTED[kind])
    assert scopes.HC_MAP not in base and scopes.HC_MIX not in base
    assert "hc_" not in base and "wq_a" not in base
    assert hashlib.sha256(base.encode()).hexdigest() == PARENTS[kind]
    assert base == _trace(ACCEPTED[kind], hc_mult=1, q_lora_rank=None)
    streams = _trace(ACCEPTED[kind], hc_mult=4)
    assert scopes.HC_MAP in streams and scopes.HC_MIX in streams
    assert "hc_attn" in streams and "hc_mlp" in streams
    if kind == "latent_routed":
        latent = _trace(ACCEPTED[kind], q_lora_rank=16)
        assert latent != base and "wq_a" in latent and "q_norm" in latent


def test_small_leaves_pass_the_optimizer_wrappers_as_they_are():
    """Gains of rank 0 and biases of rank 1, created float32, through
    ``cast_compute``, ``master_weights`` and ``DistributedOptimizer``: bf16
    beside float32 masters, every new leaf under ``ALONE_FROM_ELEMENTS``
    (their updates fuse), and a step moves them."""
    import optax

    import horovod_tpu.jax as hvd
    from horovod_tpu.ops.mixed_precision import cast_compute, master_weights

    job, _ = tiny_job()
    x = streams_of(jax.random.key(0))
    created = HyperConnection(job.llama).init(jax.random.key(1), x)
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree.leaves(created))
    assert {leaf.ndim for leaf in jax.tree.leaves(created)} == {0, 1, 2}
    # At the published widths the largest is phi_res, 14336 x 16.
    assert 4 * 3584 * 16 < hvd.ALONE_FROM_ELEMENTS
    params = cast_compute(created)
    optimizer = hvd.DistributedOptimizer(master_weights(optax.adamw(1e-2)))
    state = optimizer.init(params)
    grads = jax.tree.map(jnp.ones_like, params)
    updates, state = optimizer.update(grads, state, params)
    after = optax.apply_updates(params, updates)
    for before, now in zip(jax.tree.leaves(params), jax.tree.leaves(after)):
        assert now.dtype == jnp.bfloat16 and now.shape == before.shape
        assert bool(jnp.all(now != before))


# -- what the config and the other paths refuse ------------------------------------

def test_config_refuses_what_it_cannot_be():
    base = ACCEPTED["dense"]
    with pytest.raises(ValueError, match="hc_mult"):
        LlamaConfig(**base, hc_mult=0)
    with pytest.raises(ValueError, match="hc_mult"):
        LlamaConfig(**base, hc_mult=4, hc_sinkhorn_iters=0)
    with pytest.raises(ValueError, match="hc_mult"):
        LlamaConfig(**base, hc_mult=4, hc_res_clamp=(1.0, -1.0))
    with pytest.raises(ValueError, match="residual streams"):
        LlamaConfig(**base, hc_mult=4, norm_placement="post")
    with pytest.raises(ValueError, match="residual streams"):
        LlamaConfig(**base, hc_mult=4, total_ut_steps=2)
    with pytest.raises(ValueError, match="q_lora_rank"):
        LlamaConfig(**base, q_lora_rank=16)         # not latent attention
    with pytest.raises(ValueError, match="q_lora_rank"):
        LlamaConfig(**ACCEPTED["latent_routed"], q_lora_rank=0)


@pytest.mark.parametrize("what, kind, changes, word", [
    ("streams", "dense", {"hc_mult": 4}, "residual streams"),
    ("query latent", "latent_routed", {"q_lora_rank": 16}, "query latent"),
])
def test_the_other_paths_refuse_the_new_kinds_by_name(what, kind, changes,
                                                      word):
    """Generation, the serve engine and the pipelined step keep a layer of
    their own: each refuses, naming the kind and what it would need."""
    from horovod_tpu.models.generation import prefill
    from horovod_tpu.parallel.pipeline import init_pipelined_llama

    cfg = LlamaConfig(**ACCEPTED[kind], **changes)
    for who in ("KV-cache decode", "the pipelined step",
                "serve model 'x': the paged KV cache"):
        with pytest.raises(NotImplementedError, match=word) as raised:
            cfg.refuse_new_kinds(who)
        assert who in str(raised.value) and "not built" in str(raised.value)
    with pytest.raises(NotImplementedError, match=word):
        prefill(cfg, {}, jnp.zeros((1, 4), jnp.int32), cache_len=8)
    with pytest.raises(NotImplementedError, match=word):
        init_pipelined_llama(cfg, jax.random.key(0), 1)
