"""Laguna-S-2.1's layers in ``models/llama.py`` and the window in the flash
kernel's two calls against the plain reference
(``benchmark/reference/laguna.py``) and the dense ``causal_attention``, on the
CPU at small sizes with seeded weights: the band walk forward and backward at
windows under, at and off the block size, unequal blocks, groups of 9 and 6
and packed rows; a call without a window is the call it was; the pair counts
against a brute-force count; the half-rotating YaRN table through the
rotation's Mosaic pass; the per-head gate; the whole model's loss and
gradient; the 32 shares of 8 experts; and the refusals by name."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import laguna as ref
from horovod_tpu.models import llama
from horovod_tpu.models.llama import (LlamaAttention, LlamaConfig,
                                      LlamaModel, RopeParameters,
                                      RoutedExperts, YarnScaling, apply_rope,
                                      causal_attention, rope_freqs)
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.flash_attention import (flash_attention,
                                             flash_attention_fn, pair_counts)
from horovod_tpu.ops.losses import balance_loss, softmax_cross_entropy
from horovod_tpu.ops.rope import rotate_pairs

YARN = dict(factor=8, original_max_position_embeddings=64, beta_fast=32,
            beta_slow=1, attention_factor=0.1 * math.log(8) + 1)
ROPE = {"full_attention": {"rope_theta": 500000, "rope_type": "yarn", **YARN,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
TYPES = ["full_attention", "sliding_attention", "sliding_attention",
         "sliding_attention", "full_attention"]
EXPERTS, PER_TOKEN, HELD = 256, 10, 8


def reference_config(heads, head_dim, kv_heads, window, first=0):
    """The published config's keys at tiny widths, as the reference reads
    them."""
    return {
        "head_dim": head_dim, "num_key_value_heads": kv_heads,
        "num_attention_heads_per_layer": heads, "layer_types": TYPES,
        "mlp_layer_types": ["dense"] + ["sparse"] * 4,
        "sliding_window": window, "rope_parameters": ROPE,
        "gating": "per-head", "rms_norm_eps": 1e-6,
        "num_experts_per_tok": PER_TOKEN, "norm_topk_prob": True,
        "moe_routed_scaling_factor": 2.5,
        "deployment": {"first_held_expert": first},
        "assumed": {"aux_loss_alpha": 0.001}}


def tiny(heads=(4, 6, 6, 6, 4), head_dim=32, kv_heads=2, window=48,
         **changes) -> LlamaConfig:
    base = dict(
        vocab_size=128, hidden_size=64, num_layers=5, num_heads=heads[0],
        num_kv_heads=kv_heads, attention_head_dim=head_dim,
        intermediate_size=96, max_seq_len=256, rms_eps=1e-6,
        layer_types=tuple(TYPES), sliding_window=window,
        num_attention_heads_per_layer=tuple(heads), gating="per-head",
        rope_parameters=(
            ("full_attention", RopeParameters(
                500000.0, YarnScaling(**YARN), 0.5)),
            ("sliding_attention", RopeParameters(10000.0))),
        num_experts=EXPERTS, experts_per_token=PER_TOKEN, held_experts=HELD,
        moe_intermediate_size=16, shared_experts=1, first_dense_layers=1,
        norm_topk_prob=True, routed_scaling_factor=2.5, balance_over="batch",
        dtype=jnp.float32, logits_dtype=jnp.float32)
    return LlamaConfig(**{**base, **changes})


def to_reference(params, cfg):
    p = params["params"]

    def swiglu(block, width):
        gate_up = block["w_gate_up"]["kernel"]
        return {"w_gate": gate_up[:, :width], "w_up": gate_up[:, width:],
                "w_down": block["w_down"]["kernel"]}

    layers = []
    for i in range(cfg.num_layers):
        layer = p[f"layer_{i}"]
        out = {"norm_attn": layer["norm_attn"]["scale"],
               **{name: layer["attn"][name]["kernel"]
                  for name in ("wq", "wk", "wv", "wg", "wo")},
               "norm_mlp": layer["norm_mlp"]["scale"]}
        if cfg.is_routed(i):
            out.update(routed_reference(layer["moe"],
                                        cfg.moe_intermediate_size))
        else:
            out.update(swiglu(layer["mlp"], cfg.intermediate_size))
        layers.append(out)
    return {"embed": p["tok_emb"]["embedding"], "layers": layers,
            "norm_f": p["norm_f"]["scale"],
            "lm_head": p["lm_head"]["kernel"]}


def routed_reference(moe, width):
    shared = moe["shared"]["w_gate_up"]["kernel"]
    return {"router": moe["router"]["kernel"],
            "experts": {"w_gate": moe["w_gate_up"][..., :width],
                        "w_up": moe["w_gate_up"][..., width:],
                        "w_down": moe["w_down"]},
            "shared": {"w_gate": shared[:, :width], "w_up": shared[:, width:],
                       "w_down": moe["shared"]["w_down"]["kernel"]}}


def qkv(seq, heads, kv_heads, dim, batch=2, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(keys[0], (batch, seq, heads, dim), dtype),
            jax.random.normal(keys[1], (batch, seq, kv_heads, dim), dtype),
            jax.random.normal(keys[2], (batch, seq, kv_heads, dim), dtype),
            jax.random.normal(keys[3], (batch, seq, heads, dim), dtype))


def value_and_grads(attend, q, k, v, weight):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)
                                * weight), argnums=(0, 1, 2))(q, k, v)


# -- the band walk in the two calls -------------------------------------------

@pytest.mark.parametrize("window, block_q, block_k, heads, packed", [
    (48, 128, 128, 9, False),       # under a block, groups of 9
    (128, 128, 128, 6, False),      # a block, groups of 6
    (200, 128, 128, 9, True),       # off the block size, packed rows
    (129, 256, 128, 6, False),      # bq > bk, one key past a block
    (130, 128, 256, 9, True),       # bq < bk, packed rows
    (1, 128, 128, 6, False),        # a query's own position alone
    (383, 128, 128, 9, False),      # one short of three blocks
])
def test_windowed_calls_agree_with_dense_attention(monkeypatch, window,
                                                   block_q, block_k, heads,
                                                   packed):
    """Forward and all three gradients through the seam, in place (heads of
    128), against ``causal_attention(window=...)`` and the plain
    reference's masked attention."""
    monkeypatch.setattr(fa, "BLOCK_Q", block_q)
    monkeypatch.setattr(fa, "BLOCK_K", block_k)
    seq = 512
    q, k, v, weight = qkv(seq, heads, 1, 128)
    segments = None
    if packed:
        at = jnp.arange(seq)
        segments = jnp.stack([(at >= 170).astype(jnp.int32),
                              (at >= 200).astype(jnp.int32)
                              + (at >= 412).astype(jnp.int32)])

    def dense(q, k, v):
        if segments is None:
            return causal_attention(q, k, v, window=window)
        # A packed row's band stops at its segment's start.
        same = segments[:, :, None] == segments[:, None, :]
        return causal_attention(q, k, v, window=window, selected=same)[0]

    before = fa.layout_counts()["in_place"]
    with jax.default_matmul_precision("highest"):
        got = value_and_grads(functools.partial(
            flash_attention_fn, window=window, segment_ids=segments),
            q, k, v, weight)
        want = value_and_grads(dense, q, k, v, weight)
        if segments is None:
            plain = value_and_grads(
                lambda q, k, v: ref.attention(
                    q, jnp.repeat(k, heads, 2), jnp.repeat(v, heads, 2),
                    window), q, k, v, weight)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(plain)):
                np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    assert fa.layout_counts()["in_place"] == before + 1
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [512, 513, 4096])
def test_a_window_no_shorter_than_the_row_is_causal_attention(window):
    """The same program, so the same bits, forward and backward; through
    the bare call as well."""
    q, k, v, weight = qkv(512, 4, 2, 64, dtype=jnp.bfloat16)
    causal = value_and_grads(flash_attention_fn, q, k, v, weight)
    banded = value_and_grads(functools.partial(flash_attention_fn,
                                               window=window), q, k, v, weight)
    for a, b in zip(jax.tree.leaves(causal), jax.tree.leaves(banded)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k, v, window=window), np.float32),
        np.asarray(flash_attention(q, k, v), np.float32))


@pytest.mark.parametrize("packed", [False, True])
def test_without_a_window_the_calls_are_the_calls_they_were(packed):
    """``window=None`` adds nothing to the traced program: the jaxpr of the
    seam's forward and backward, kernel bodies included (the CPU interprets
    them inline), names no window, is the one a caller that never heard of
    the argument gets, and differs from a windowed one."""
    q, k, v, weight = qkv(256, 4, 2, 128, dtype=jnp.bfloat16)
    segments = (jnp.arange(256) >= 100).astype(jnp.int32)[None].repeat(2, 0)
    extra = {"segment_ids": segments} if packed else {}

    def text(**kwargs):
        return str(jax.make_jaxpr(lambda q, k, v: value_and_grads(
            functools.partial(flash_attention_fn, **extra, **kwargs),
            q, k, v, weight))(q, k, v))

    assert text() == text(window=None) == text(window=256)
    assert text() != text(window=100)
    assert len(text(window=100)) > len(text())


@pytest.mark.parametrize("seq, block_q, block_k, window", [
    (8192, 512, 512, 512), (2048, 512, 512, None), (2048, 256, 512, 300),
    (2048, 512, 256, 1), (1024, 128, 128, 128), (1024, 128, 128, 129),
    (1024, 128, 128, 127), (1024, 256, 128, 700), (1024, 128, 128, 5000)])
def test_pair_counts_against_a_brute_force_count(seq, block_q, block_k,
                                                 window):
    at = np.arange(seq)
    kept = at[:, None] >= at[None, :]
    if window is not None:
        kept &= at[:, None] - at[None, :] < window
    pairs = crossed = 0
    for qi in range(seq // block_q):
        for ki in range(seq // block_k):
            block = kept[qi * block_q:(qi + 1) * block_q,
                         ki * block_k:(ki + 1) * block_k]
            pairs += bool(block.any())
            crossed += bool(block.any() and not block.all())
    assert pair_counts(seq, block_q, block_k, True, window) == (pairs,
                                                                crossed)


def test_pair_counts_at_the_cells_size():
    assert pair_counts(8192, 512, 512) == (136, 16)
    # A query block's own pair (the diagonal's) and the one before it (the
    # lower edge's); the first block has no pair before it.
    assert pair_counts(8192, 512, 512, True, 512) == (31, 31)
    assert pair_counts(8192, 256, 256, True, 512) == (93, 62)
    assert pair_counts(8192, 512, 512, True, 8192) == (136, 16)


def test_windows_the_calls_have_no_path_for():
    q, k, v, _ = qkv(128, 2, 2, 64)
    with pytest.raises(NotImplementedError, match="causal layer's band"):
        flash_attention(q, k, v, causal=False, window=16)
    with pytest.raises(NotImplementedError, match="causal layer's band"):
        flash_attention_fn(q, k, v, jnp.ones((2, 128), bool), window=16)
    with pytest.raises(ValueError, match="at least the query's own"):
        flash_attention_fn(q, k, v, window=0)
    with pytest.raises(NotImplementedError, match="selection and a window"):
        flash_attention_fn(q, k, v, selected=jnp.ones((2, 128, 128)),
                           window=16)


# -- the rotation --------------------------------------------------------------

def test_half_rotating_yarn_table_by_hand():
    """Rotary width 64 of 128: pairs 0..31 turn by YaRN's frequencies over
    that width, times the stated factor; pairs 32..63 are the identity."""
    scaling = YarnScaling(factor=128, original_max_position_embeddings=8192,
                          beta_fast=32, beta_slow=1,
                          attention_factor=1.4852030263919618)
    assert scaling.correction_range(64, 500000.0) == (9, 18)
    assert scaling.table_scale == 1.4852030263919618
    assert scaling.softmax_scale == 1.0
    # The stated factor is what YaRN's own formula gives.
    assert YarnScaling(128, 8192).table_scale == pytest.approx(
        1.4852030263919618, rel=1e-15)
    cos, sin = rope_freqs(128, 16, 500000.0, scaling=scaling, rotary_dim=64)
    assert cos.shape == sin.shape == (16, 64)
    np.testing.assert_array_equal(cos[:, 32:], 1.0)
    np.testing.assert_array_equal(sin[:, 32:], 0.0)
    pair = np.arange(32)
    plain = 500000.0 ** (-2.0 * pair / 64)
    ramp = np.clip((pair - 9) / 9, 0, 1)
    freq = plain * ((1 - ramp) + ramp / 128)
    angle = np.arange(16)[:, None] * freq[None, :]
    np.testing.assert_allclose(cos[:, :32], 1.4852030263919618
                               * np.cos(angle), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sin[:, :32], 1.4852030263919618
                               * np.sin(angle), rtol=1e-5, atol=1e-6)
    got, scale = ref.frequencies({**ROPE["full_attention"], "factor": 128,
                                  "original_max_position_embeddings": 8192},
                                 128)
    np.testing.assert_allclose(got, freq, rtol=1e-6)
    assert scale == YARN["attention_factor"]
    # A whole head that turns is the table it was.
    for a, b in zip(rope_freqs(128, 16, 1e4), rope_freqs(128, 16, 1e4,
                                                         rotary_dim=128)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_mosaic_pass_serves_a_head_that_rotates_by_half(dtype):
    """``rotate_pairs`` on the half-rotating table against ``apply_rope``'s
    ``jnp`` body and the reference: the lanes that do not turn come back
    with their own bits, the others as the whole-head pass gives them."""
    heads, dim, seq = 3, 128, 64
    x = jax.random.normal(jax.random.key(0), (2, seq, heads, dim), dtype)
    cos, sin = rope_freqs(dim, seq, 500000.0, scaling=YarnScaling(**YARN),
                          rotary_dim=64)
    in_place = rotate_pairs(x.reshape(2, seq, heads * dim), cos,
                            sin).reshape(x.shape)
    body = apply_rope(x, cos, sin)
    np.testing.assert_array_equal(np.asarray(in_place[..., 64:], np.float32),
                                  np.asarray(x[..., 64:], np.float32))
    np.testing.assert_array_equal(np.asarray(body[..., 64:], np.float32),
                                  np.asarray(x[..., 64:], np.float32))
    ulp = 2e-7 if dtype == jnp.float32 else 8e-3
    np.testing.assert_allclose(np.asarray(in_place, np.float32),
                               np.asarray(body, np.float32), rtol=ulp,
                               atol=ulp)
    want = ref.rotary(x.astype(jnp.float32), ROPE["full_attention"])
    np.testing.assert_allclose(np.asarray(body, np.float32), want,
                               rtol=2e-5 + ulp, atol=2e-5 + ulp)
    # The transpose: lanes that do not turn hand their cotangent through.
    grad = jax.grad(lambda x: jnp.sum(rotate_pairs(
        x, cos, sin).astype(jnp.float32)))(x.reshape(2, seq, heads * dim))
    np.testing.assert_array_equal(
        np.asarray(grad.reshape(x.shape)[..., 64:], np.float32), 1.0)


# -- the gate ------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gated_heads_is_a_sigmoid_a_head(dtype):
    out = jax.random.normal(jax.random.key(0), (2, 16, 3 * 128), dtype)
    logits = jax.random.normal(jax.random.key(1), (2, 16, 3), dtype)
    gate = jax.nn.sigmoid(logits.astype(jnp.float32)).astype(dtype)
    want = (out.reshape(2, 16, 3, 128) * gate[..., None]).reshape(out.shape)
    np.testing.assert_array_equal(
        np.asarray(llama._gated_heads(out, logits), np.float32),
        np.asarray(want, np.float32))


def test_attention_layer_is_the_references_by_kind():
    """One mixer of each kind alone (72-like and 48-like head counts at
    groups of 3 and 2), the gate and the layer's own table included."""
    cfg = tiny()
    config = reference_config(list(cfg.num_attention_heads_per_layer), 32, 2,
                              48)
    x = jax.random.normal(jax.random.key(0), (2, 128, cfg.hidden_size))
    for index in (0, 1):
        rope = cfg.rope_of(index)
        cos, sin = rope_freqs(32, 128, rope.rope_theta, scaling=rope.scaling,
                              rotary_dim=int(rope.partial_rotary_factor * 32))
        mixer = LlamaAttention(cfg, attention_fn=flash_attention_fn,
                               index=index)
        params = mixer.init(jax.random.key(index), x, cos, sin)
        kernels = {name: leaf["kernel"]
                   for name, leaf in params["params"].items()}
        assert kernels["wq"].shape == (64, cfg.heads_of(index) * 32)
        assert kernels["wg"].shape == (64, cfg.heads_of(index))
        with jax.default_matmul_precision("highest"):
            got = mixer.apply(params, x, cos, sin)
            want = ref.attention_layer(x, kernels, index, config)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        # Without the gate it is another function of the same weights.
        plain = LlamaAttention(dataclasses.replace(cfg, gating=None),
                               attention_fn=flash_attention_fn, index=index)
        ungated = {"params": {k: v for k, v in params["params"].items()
                              if k != "wg"}}
        assert float(jnp.max(jnp.abs(
            plain.apply(ungated, x, cos, sin) - got))) > 0.05


# -- the whole model -----------------------------------------------------------

def model_loss(cfg, attention_fn, params, tokens):
    logits, sown = LlamaModel(cfg, attention_fn=attention_fn).apply(
        params, tokens[:, :-1], mutable=["losses"])
    return (softmax_cross_entropy(logits, tokens[:, 1:])
            + 0.001 * balance_loss(sown))


@pytest.fixture(scope="module")
def whole_model():
    cfg = tiny(remat="layer_keep_attention")
    tokens = jax.random.randint(jax.random.key(1), (2, 129), 0,
                                cfg.vocab_size)
    params = LlamaModel(cfg).init(jax.random.key(0), tokens[:, :-1])
    config = reference_config(list(cfg.num_attention_heads_per_layer), 32, 2,
                              48)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.loss_and_grads(
            to_reference(p, cfg), tokens, config))(params)
    return cfg, params, tokens, want


@pytest.mark.parametrize("attention_fn", [flash_attention_fn,
                                          causal_attention])
def test_whole_model_agrees_with_the_plain_reference_in_float32(
        whole_model, attention_fn):
    cfg, params, tokens, (want_loss, want_grads) = whole_model
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(functools.partial(
            model_loss, cfg, attention_fn)))(params, tokens)
    assert float(loss) == pytest.approx(float(want_loss), abs=2e-5)
    for got, want in zip(jax.tree.leaves(to_reference(grads, cfg)),
                         jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(got - want)) <= 2e-4 * float(
            jnp.linalg.norm(want)) + 1e-7


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


def _one_layer(method, layer, instead):
    """``LlamaConfig.method`` answering for ``layer`` what ``instead(cfg)``
    says."""
    original = getattr(LlamaConfig, method)
    return (LlamaConfig, method, lambda self, i: (
        instead(self) if i == layer else original(self, i)))


@pytest.mark.parametrize("defect, owner, name, value", [
    ("the window ignored in one sliding layer",
     *_one_layer("window_of", 2, lambda cfg: None)),
    ("the gate left out", llama, "_gated_heads", lambda out, logits: out),
    ("the full layers' table in a sliding layer",
     *_one_layer("rope_of", 2, lambda cfg: cfg.rope_of(0))),
    ("2.5 left out", LlamaConfig, "routed_scaling_factor", 1.0),
])
def test_each_new_piece_shows_in_the_loss_and_gradient(
        whole_model, monkeypatch, defect, owner, name, value):
    """The four defects the comparison on the chip was held to
    (``benchmark/configs/laguna-s-2.1.json``), each alone, in float32."""
    cfg, params, tokens, (_, want_grads) = whole_model
    if name == "routed_scaling_factor":
        cfg = dataclasses.replace(cfg, routed_scaling_factor=value)
    else:
        monkeypatch.setattr(owner, name, value)
    with jax.default_matmul_precision("highest"):
        _, grads = jax.jit(jax.value_and_grad(functools.partial(
            model_loss, cfg, flash_attention_fn)))(params, tokens)
    off = sum(float(jnp.sum(jnp.square(g - w))) for g, w in zip(
        jax.tree.leaves(to_reference(grads, cfg)),
        jax.tree.leaves(want_grads)))
    size = sum(float(jnp.sum(jnp.square(w)))
               for w in jax.tree.leaves(want_grads))
    assert math.sqrt(off / size) > 0.02, defect


# -- the cut: 32 shares of 8 experts -------------------------------------------

def test_the_32_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut: each share of 8 experts computed by the
    program with those experts' weights alone (router and top-10 over all
    256, gates renormalised and times 2.5), the routed parts summed and the
    shared expert counted once, is the uncut 256-expert reference layer."""
    cfg = tiny(held_experts=0, hidden_size=32, moe_intermediate_size=8)
    x = jax.random.normal(jax.random.key(0), (2, 24, cfg.hidden_size))
    moe = RoutedExperts(cfg).init(jax.random.key(1), x)["params"]
    assert moe["w_gate_up"].shape == (EXPERTS, 32, 16)
    config = reference_config([4] * 5, 32, 2, 48)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.routed_experts(x, routed_reference(moe, 8), config)
        shared = ref.swiglu(x, routed_reference(moe, 8)["shared"])
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
    np.testing.assert_allclose(routed_sum + shared, whole, rtol=1e-4,
                               atol=1e-5)
    assert rows == 2 * 24 * PER_TOKEN
    # The gates' scale is on the routed part alone: without it the routed
    # part is 1 / 2.5 of what it was and the shared expert's as it was.
    unscaled, _ = layer(dataclasses.replace(cfg, routed_scaling_factor=1.0),
                        {"params": moe}, x)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose((unscaled - shared) * 2.5, whole - shared,
                                   rtol=1e-3, atol=1e-4)


def test_a_scale_of_one_is_the_program_it_was():
    cfg = tiny(held_experts=8, routed_scaling_factor=1.0)
    x = jnp.zeros((2, 16, cfg.hidden_size))
    params = jax.eval_shape(RoutedExperts(cfg).init, jax.random.key(0), x)
    text = str(jax.make_jaxpr(lambda p, x: RoutedExperts(cfg).apply(
        p, x))(params, x))
    scaled = str(jax.make_jaxpr(lambda p, x: RoutedExperts(
        dataclasses.replace(cfg, routed_scaling_factor=2.5)).apply(
            p, x))(params, x))
    assert text.count(" mul ") + 1 == scaled.count(" mul ")


# -- what the config and the other paths refuse --------------------------------

def test_config_refuses_what_it_cannot_be():
    with pytest.raises(ValueError, match="sliding_window"):
        tiny(layer_types=("full_attention",) * 5)      # a window, no layer
    with pytest.raises(ValueError, match="sliding_window"):
        tiny(window=None)                               # a layer, no window
    with pytest.raises(ValueError, match="sliding_window"):
        tiny(window=0)
    with pytest.raises(ValueError, match="num_attention_heads_per_layer"):
        tiny(heads=(4, 6, 6, 6))                        # four for five
    with pytest.raises(ValueError, match="num_attention_heads_per_layer"):
        tiny(heads=(4, 5, 6, 6, 4))                     # 2 does not divide 5
    with pytest.raises(ValueError, match="gating"):
        tiny(gating="per-token")        # ("elementwise" is a kind since PR 46)
    with pytest.raises(ValueError, match="rope_parameters"):
        tiny(rope_parameters=(("full_attention", RopeParameters(1e4)),))
    with pytest.raises(ValueError, match="rope_parameters"):
        tiny(rope_parameters=(
            ("full_attention", RopeParameters(1e4, None, 0.3)),
            ("sliding_attention", RopeParameters(1e4))))
    cfg = tiny()
    assert [cfg.heads_of(i) for i in range(5)] == [4, 6, 6, 6, 4]
    assert [cfg.window_of(i) for i in range(5)] == [None, 48, 48, 48, None]
    assert cfg.rope_of(0).partial_rotary_factor == 0.5
    assert cfg.rope_of(1) == RopeParameters(10000.0)
    # A stack with one rotation hands every layer the one table it had.
    plain = LlamaConfig.tiny()
    assert {plain.rope_of(i) for i in range(2)} == {
        RopeParameters(10000.0, None, 1.0)}
    assert plain.heads_of(1) == plain.num_heads
    assert plain.window_of(1) is None


@pytest.mark.parametrize("what, changes, word", [
    ("window layers", {}, "sliding-window layers"),
    ("heads a layer", {"layer_types": None, "window": None,
                       "rope_parameters": None, "gating": None},
     "head count a layer"),
    ("the gate", {"layer_types": None, "window": None, "heads": None,
                  "rope_parameters": None}, "per-head output gate"),
    ("a rotation a layer type",
     {"layer_types": None, "window": None, "heads": None, "gating": None,
      "rope_parameters": (("full_attention",
                           RopeParameters(5e5, None, 0.5)),)},
     "rotation a layer type or a partial one"),
])
def test_the_other_paths_refuse_the_new_kinds_by_name(what, changes, word):
    from horovod_tpu.models.generation import prefill

    changes = dict(changes)
    fields = {"num_experts": 1, "held_experts": 0, "shared_experts": 0,
              "first_dense_layers": 0, "routed_scaling_factor": 1.0}
    if "heads" in changes and changes.pop("heads") is None:
        fields["num_attention_heads_per_layer"] = None
    if "window" in changes:
        fields["sliding_window"] = changes.pop("window")
    cfg = dataclasses.replace(tiny(), **fields, **changes)
    for who in ("KV-cache decode", "the pipelined step"):
        with pytest.raises(NotImplementedError, match=word) as raised:
            cfg.refuse_new_kinds(who)
        assert who in str(raised.value) and "not built" in str(raised.value)
    with pytest.raises(NotImplementedError, match=word):
        prefill(cfg, None, None, cache_len=16)
