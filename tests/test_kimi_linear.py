"""The delta rule with a decay a CHANNEL (Kimi Delta Attention), latent
attention that does not rotate and the sigmoid output gate
(``kimi-linear-48b-a3b``), at a small size on the CPU: the chunked rule
against the token-by-token recurrence (mild decays, and channels at e^-5 a
step: nothing overflows, nothing is clamped), against the scalar rule where
every channel decays alike, and its Mosaic walk interpreted against its
``jnp`` walk; ``norm_gated`` with a sigmoid gate; the program against
``benchmark/reference/kimi_linear.py`` on seeded weights -- the latent layer,
the loss and every gradient; the shares of a routed layer add up to the uncut
layer; the counters; what the config and the other paths refuse."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.reference import kimi_linear as ref
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.models.llama import (KimiDeltaAttention, LatentAttention,
                                      LlamaLayer, causal_attention)
from horovod_tpu.ops import gated_delta, gated_norm, kda
from horovod_tpu.ops.flash_attention import flash_attention_fn
from tiny_sizes import TINY          # tests/conftest.py put it on the path

CELL = "kimi-linear-48b-a3b.train-s8k-b2"
# The cell's own stack at the tiny widths: KDA over the dense SwiGLU, then
# KDA, KDA, latent, KDA over routed experts.
FIVE = {"num_hidden_layers": 5, "linear_attn_config": {
    **TINY["kda_moe_lm"]["config"]["linear_attn_config"],
    "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4]}}


def tiny_job(**changes):
    """The cell's own job at the tests' tiny widths (``tests/conftest.py``),
    in float32, and the configuration the reference reads."""
    cell = manifest.cell(CELL)
    config = {**cell["config"], **TINY["kda_moe_lm"]["config"], **changes}
    traffic = {**cell["traffic"], **TINY["kda_moe_lm"]["traffic"]}
    job = manifest.load_job(config["job"]).build(config, traffic, 1)
    job.llama = dataclasses.replace(job.llama, dtype=jnp.float32,
                                    logits_dtype=jnp.float32)
    return job, config


def moved(params, seed=7, by=0.05):
    """``params`` off their start: every leaf plus seeded noise of ``by``
    times its own root mean square."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + by * jnp.sqrt(jnp.mean(leaf * leaf))
        * jax.random.normal(key, leaf.shape, leaf.dtype)
        for leaf, key in zip(leaves, keys)])


# -- the rule ------------------------------------------------------------------

def recurrence(q, k, v, g, beta):
    """``S_t = S_{t-1} Diag(e^g_t) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``,
    ``o_t = S_t q_t``, one token a step."""
    batch, _, heads, d_k = q.shape

    def token(state, x):
        q, k, v, g, beta = x
        state = state * jnp.exp(g)[..., None, :]
        read = jnp.einsum("bhvk,bhk->bhv", state, k)
        state = state + beta[..., None, None] * (
            (v - read)[..., :, None] * k[..., None, :])
        return state, jnp.einsum("bhvk,bhk->bhv", state, q)

    _, o = jax.lax.scan(
        token, jnp.zeros((batch, heads, v.shape[-1], d_k)),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def operands(seed, batch=2, seq=160, heads=2, d_k=32, d_v=16, decay=0.1,
             dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(keys[0], (batch, seq, heads, d_k))
    k = jax.random.normal(keys[1], (batch, seq, heads, d_k))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d_k ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (batch, seq, heads, d_v))
    g = -decay * jax.random.uniform(keys[3], (batch, seq, heads, d_k),
                                    minval=0.01, maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, seq, heads)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def both_ways(rule, x, weight):
    """(o, the five gradients of ``sum(o * weight)``)."""
    def total(*x):
        return jnp.sum((rule(*x) * weight).astype(jnp.float32))

    return jax.jit(lambda *x: (rule(*x), jax.grad(
        total, argnums=(0, 1, 2, 3, 4))(*x)))(*x)


@pytest.mark.parametrize("decays", ["mild", "e^-5 a step"])
def test_the_chunked_rule_is_the_recurrence(decays):
    """Forward and the five gradients in float32, at a length that is no
    multiple of the chunk.  Mild: every channel by its own g in (-0.1, 0).
    Steep: every second channel at e^-5 a step (e^-320 across a chunk:
    ``exp(-G_j)`` alone would pass float32's largest inside 18 rows), the
    others nearly kept; nothing overflows, nothing is clamped."""
    x = operands(0)
    if decays != "mild":
        g = jnp.where(jnp.arange(x[3].shape[-1]) % 2 == 0, -5.0, 0.02 * x[3])
        x = x[:3] + (g,) + x[4:]
    weight = jax.random.normal(jax.random.key(9), x[2].shape)
    with jax.default_matmul_precision("highest"):
        got = both_ways(kda.kda_rule, x, weight)
        want = both_ways(recurrence, x, weight)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.max(jnp.abs(w))), rtol=1e-4)


def test_one_decay_for_all_channels_is_the_scalar_rule():
    """Every channel of a head given the same g: ``gated_delta_rule``, to
    float32 rounding, forward and backward (g's gradient summed over the
    channels)."""
    q, k, v, g, beta = operands(1)
    alike = jnp.broadcast_to(g[..., :1], g.shape)
    weight = jax.random.normal(jax.random.key(9), v.shape)
    with jax.default_matmul_precision("highest"):
        got = both_ways(kda.kda_rule, (q, k, v, alike, beta), weight)
        want = both_ways(gated_delta.gated_delta_rule,
                         (q, k, v, g[..., 0], beta), weight)
    got = (got[0], (*got[1][:3], jnp.sum(got[1][3], axis=-1), got[1][4]))
    for g_, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g_, w, atol=2e-5 * float(jnp.max(jnp.abs(w))), rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_mosaic_walk_interpreted_is_the_jnp_walk(monkeypatch, dtype):
    """Heads of 128 | 128 (whole lane tiles), two chunks, two heads a step:
    with ``gated_delta._why_not`` answering as on a TPU the rule takes its
    Mosaic calls (interpreted here): the chunks' systems, the solve, the walk
    and the two backward calls.  The same mathematics in another order of
    rounding: float32 to rounding, bf16 operands to a few ulps of the largest
    gradient."""
    x = operands(3, batch=1, seq=2 * kda.CHUNK, heads=2, d_k=128, d_v=128,
                 decay=0.5, dtype=dtype)
    weight = jax.random.normal(jax.random.key(9), x[2].shape).astype(dtype)
    before = kda.walk_counts()
    with jax.default_matmul_precision("highest"):
        want = both_ways(lambda *x: kda.kda_rule(*x, in_place=False), x,
                         weight)
        monkeypatch.setattr(gated_delta, "_why_not", lambda: None)
        got = both_ways(lambda *x: kda.kda_rule(*x, in_place=True), x,
                        weight)
    after = kda.walk_counts()
    assert after["mosaic"] > before["mosaic"]
    assert (after["plain"][gated_delta.NOT_IN_PLACE]
            > before["plain"].get(gated_delta.NOT_IN_PLACE, 0))
    tight = 2e-6 if dtype == jnp.float32 else 2e-2
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        np.testing.assert_allclose(
            g, w, atol=tight * float(jnp.max(jnp.abs(w))))


def test_heads_off_the_lane_tile_keep_the_jnp_walk(monkeypatch):
    monkeypatch.setattr(gated_delta, "_why_not", lambda: None)
    before = kda.walk_counts()["plain"].get(gated_delta.HEADS_OFF_THE_TILE, 0)
    jax.eval_shape(lambda *x: kda.kda_rule(*x, in_place=True),
                   *operands(0, seq=64))
    assert kda.walk_counts()["plain"][
        gated_delta.HEADS_OFF_THE_TILE] == before + 1


# -- the output norm under a sigmoid gate --------------------------------------

@pytest.mark.parametrize("body", ["jnp", "mosaic interpreted"])
def test_the_output_norm_takes_a_sigmoid_gate(body):
    """``rms_norm_head(o) w sigmoid(z)`` against its plain form, forward and
    the three gradients; the SiLU gate is another function of the same z."""
    heads, d, eps = 4, 128, 1e-5
    keys = jax.random.split(jax.random.key(0), 4)
    o, z = (jax.random.normal(key, (2, 128, heads * d)) for key in keys[:2])
    w = 1.0 + 0.1 * jax.random.normal(keys[2], (d,))
    weight = jax.random.normal(keys[3], o.shape)

    def plain(o, z, w, gate=jax.nn.sigmoid):
        heads_of = o.reshape(*o.shape[:2], heads, d)
        normed = heads_of * jax.lax.rsqrt(
            jnp.mean(heads_of * heads_of, axis=-1, keepdims=True) + eps) * w
        return normed.reshape(o.shape) * gate(z)

    if body == "jnp":
        def got_fn(o, z, w):
            return gated_norm.norm_gated(o, z, w, heads, eps, False,
                                         sigmoid=True)
    else:
        def got_fn(o, z, w):
            return gated_norm.norm_gate(o, z, w, heads, eps, 0, True)

    def both(fn):
        return jax.jit(lambda *x: (fn(*x), jax.grad(
            lambda *x: jnp.sum(fn(*x) * weight), argnums=(0, 1, 2))(*x)))(
                o, z, w)

    for g, want in zip(jax.tree.leaves(both(got_fn)),
                       jax.tree.leaves(both(plain))):
        np.testing.assert_allclose(
            g, want, atol=1e-5 * float(jnp.max(jnp.abs(want))), rtol=1e-4)
    silu = plain(o, z, w, jax.nn.silu)
    assert float(jnp.max(jnp.abs(silu - plain(o, z, w)))) > 0.1
    np.testing.assert_allclose(
        gated_norm.norm_gated(o, z, w, heads, eps, False), silu, atol=1e-5)


# -- the model against the reference -------------------------------------------

@pytest.mark.parametrize("attention_fn", [flash_attention_fn,
                                          causal_attention])
def test_latent_attention_without_rotation_is_the_references(attention_fn):
    """``mla_use_nope``: ``LatentAttention`` handed NO tables against the
    reference's latent layer; the same weights under a rotation give
    something else."""
    job, config = tiny_job()
    cfg = job.llama
    assert cfg.rope_of(1) is None and cfg.rope_theta == 10000.0
    x = jax.random.normal(jax.random.key(0), (2, 128, cfg.hidden_size))
    module = LatentAttention(cfg, attention_fn=attention_fn)
    params = moved(module.init(jax.random.key(1), x, None, None))
    assert set(params["params"]) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    plain = {**{name: params["params"][name]["kernel"]
                for name in ("wq", "wkv_a", "wkv_b", "wo")},
             "kv_norm": params["params"]["kv_norm"]["scale"]}
    with jax.default_matmul_precision("highest"):
        got = module.apply(params, x, None, None)
        want = ref.latent_mixer(x, plain, config)
        turning = dataclasses.replace(cfg, mla_use_nope=False,
                                      linear_attn_config=None)
        from horovod_tpu.models.llama import rope_freqs
        turned = LatentAttention(turning, attention_fn=attention_fn).apply(
            params, x, *rope_freqs(cfg.qk_rope_head_dim, 128, 10000.0))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(turned - got))) > 0.05


@pytest.fixture(scope="module")
def whole_model():
    """The cell's five layers under ``remat``, on moved parameters, with the
    reference's loss and gradients."""
    job, config = tiny_job(**FIVE)
    assert [spec.mixer for spec in job.llama.layers] == [
        "kda", "kda", "kda", "attention", "kda"]
    assert [spec.ffn for spec in job.llama.layers] == [
        "dense"] + ["routed"] * 4
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
    job, config, params, _, tokens, (want_loss, want_grads) = whole_model
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, tokens, config)))(job.to_reference(params))
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            g, w, atol=1e-5 * float(jnp.max(jnp.abs(w))), rtol=1e-5)


def test_a_kda_layer_sows_its_stats_and_counts_its_bodies(whole_model):
    job, _, params, bias, tokens, _ = whole_model
    before = kda.walk_counts()["plain"].get(gated_delta.NO_TPU, 0)
    job.model = LlamaModel(job.llama, attention_fn=flash_attention_fn)
    stats = jax.jit(job.kda_counters)(params, bias, tokens)
    assert set(stats) == {"alpha_min", "state_max", "out_max"}
    for value in stats.values():
        assert value.shape == (4,) and bool(jnp.all(jnp.isfinite(value)))
    assert bool(jnp.all((stats["alpha_min"] > 0) & (stats["alpha_min"] < 1)))
    # flash_attention_fn reads in place; on the CPU the rule says why it
    # keeps the jnp bodies.
    assert kda.walk_counts()["plain"][gated_delta.NO_TPU] >= before + 4
    assert kda.solve_counts()["plain"][gated_delta.NO_TPU] >= 4


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut, on a KDA layer over routed experts: each
    of four shares of 4 experts computed by the program with those experts'
    weights alone (router, top-3 and renormalised gates x 2.446 over all 16);
    what every chip computes alike -- the mixer, the shared expert --
    counted once (the reference's layer holding NO expert); the four routed
    parts on top of it are the uncut 16-expert reference layer."""
    job, config = tiny_job()
    cfg = job.llama
    whole = dataclasses.replace(cfg, held_experts=0, first_held_expert=0)
    x = jax.random.normal(jax.random.key(4), (2, 64, cfg.hidden_size))
    full = LlamaLayer(whole, index=2).init(jax.random.key(5), x, None, None)
    full = {**full, "params": moved(full["params"])}    # the bias stays 0
    moe = full["params"]["moe"]
    assert moe["w_gate_up"].shape[0] == 16
    job.llama = whole
    plain = job.to_reference({"params": {
        "layer_0": {**full["params"], "mlp": {
            "w_gate_up": {"kernel": jnp.zeros((1, 2))},
            "w_down": {"kernel": 0}}},
        "layer_1": {**full["params"], "attn": {
            name: {"kernel": 0} for name in ("wq", "wkv_a", "wkv_b", "wo")}
            | {"kv_norm": {"scale": 0}}},
        "layer_2": full["params"],
        "tok_emb": {"embedding": 0}, "norm_f": {"scale": 0},
        "lm_head": {"kernel": 0}}})["layers"][2]
    uncut = {**config, "deployment": {"first_held_expert": 0}}
    none_held = {**plain, "experts": jax.tree.map(lambda w: w[:0],
                                                  plain["experts"])}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.decoder_layer(x, plain, 2, uncut)
        alike, _ = ref.decoder_layer(x, none_held, 2, uncut)
    apply = jax.jit(lambda cfg, params: LlamaLayer(cfg, index=2).apply(
        params, x, None, None, mutable=["moe_stats"]), static_argnums=0)
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
    assert rows == 2 * 64 * 3
    assert float(jnp.max(jnp.abs(want - alike))) > 0.05
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=2e-4)


# -- what the config and the other paths refuse --------------------------------

BASE = dict(vocab_size=128, hidden_size=64, num_layers=3, num_heads=2,
            num_kv_heads=2, intermediate_size=128, attention_kind="latent",
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16)
SIZES = (("num_heads", 2), ("head_dim", 32), ("short_conv_kernel_size", 4))


def test_config_refuses_what_it_cannot_be():
    lists = (("kda_layers", (1, 3)), ("full_attn_layers", (2,)))
    LlamaConfig(**BASE, linear_attn_config=lists + SIZES)
    with pytest.raises(ValueError, match="linear_attn_config"):     # layer 2
        LlamaConfig(**BASE, linear_attn_config=(
            ("kda_layers", (1, 3)), ("full_attn_layers", ())) + SIZES)
    with pytest.raises(ValueError, match="linear_attn_config"):     # twice
        LlamaConfig(**BASE, linear_attn_config=(
            ("kda_layers", (1, 2, 3)), ("full_attn_layers", (2,))) + SIZES)
    with pytest.raises(ValueError, match="linear_attn_config"):     # no size
        LlamaConfig(**BASE, linear_attn_config=lists + SIZES[:2])
    with pytest.raises(ValueError, match="linear_attn_config"):
        LlamaConfig(**BASE, linear_attn_config=lists + SIZES,
                    layer_types=("full_attention",) * 3)
    with pytest.raises(ValueError, match="mla_use_nope"):
        LlamaConfig(**{**BASE, "attention_kind": "full"}, mla_use_nope=True)
    from horovod_tpu.models.llama import YarnScaling
    with pytest.raises(ValueError, match="mla_use_nope"):
        LlamaConfig(**BASE, mla_use_nope=True, rope_scaling=YarnScaling(
            factor=4.0, original_max_position_embeddings=64))


@pytest.mark.parametrize("what, changes, word", [
    ("KDA layers", {"linear_attn_config": (
        ("kda_layers", (1, 3)), ("full_attn_layers", (2,))) + SIZES},
     "Kimi Delta Attention"),
    ("unrotated latent attention", {"mla_use_nope": True},
     "does not rotate"),
])
def test_the_other_paths_refuse_the_new_kinds_by_name(what, changes, word):
    """Generation, the serve engine and the pipelined step keep a layer of
    their own: each refuses, naming the kind and what it would need."""
    from horovod_tpu.models.generation import prefill
    from horovod_tpu.parallel.pipeline import init_pipelined_llama

    cfg = LlamaConfig(**BASE, **changes)
    for who in ("KV-cache decode", "the pipelined step",
                "serve model 'x': the paged KV cache"):
        with pytest.raises(NotImplementedError, match=word) as raised:
            cfg.refuse_new_kinds(who)
        assert who in str(raised.value) and "not built" in str(raised.value)
    with pytest.raises(NotImplementedError, match=word):
        prefill(cfg, {}, jnp.zeros((1, 4), jnp.int32), cache_len=8)
    with pytest.raises(NotImplementedError, match=word):
        init_pipelined_llama(cfg, jax.random.key(0), 1)


def test_a_kda_mixer_has_the_published_leaves():
    """The cell's widths, shapes alone: 39,514,272 parameters a mixer, as the
    configuration's ``reduced_why`` reckons them."""
    cell = manifest.cell(CELL)
    job = manifest.load_job(cell["config"]["job"]).build(
        cell["config"], cell["traffic"], 1)
    x = jax.ShapeDtypeStruct((1, 64, 2304), jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda x: KimiDeltaAttention(job.llama).init(jax.random.key(0), x),
        x)["params"]
    assert shapes["f_a"]["kernel"].shape == (2304, 128)
    assert shapes["f_b"]["kernel"].shape == (128, 4096)
    assert shapes["dt_bias"].shape == (4096,)
    assert shapes["a_log"].shape == (32,)
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree.leaves(shapes)) == 39_514_272
