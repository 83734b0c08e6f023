"""Model zoo shape/forward tests (CPU, tiny configs)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (
    BertConfig,
    BertForPretraining,
    LlamaConfig,
    LlamaModel,
    MnistConvNet,
    MnistMLP,
    ResNet18,
    ResNet50,
    SkipGramModel,
    nce_loss,
)
from horovod_tpu.models.llama import LayerSpec, RopeParameters


def test_mnist_convnet_forward():
    model = MnistConvNet(dtype=jnp.float32)
    x = jnp.zeros((4, 28, 28, 1))
    params = model.init(jax.random.key(0), x)
    out = model.apply(params, x)
    assert out.shape == (4, 10)
    assert out.dtype == jnp.float32


def test_mnist_mlp_forward():
    model = MnistMLP(dtype=jnp.float32)
    x = jnp.zeros((4, 28, 28, 1))
    params = model.init(jax.random.key(0), x)
    assert model.apply(params, x).shape == (4, 10)


@pytest.mark.parametrize("factory,n_params_expected", [
    (ResNet50, 25_557_032),   # the canonical ResNet-50 parameter count
])
def test_resnet50_param_count(factory, n_params_expected):
    model = factory(dtype=jnp.float32)
    x = jnp.zeros((1, 224, 224, 3))
    # Shapes alone count: nothing is initialised or run.
    variables = jax.eval_shape(
        lambda key: model.init(key, x, train=False), jax.random.key(0))
    n = sum(p.size for p in jax.tree.leaves(variables["params"]))
    assert n == n_params_expected


def test_resnet18_forward_small():
    model = ResNet18(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.key(0), x, train=False)
    out, updates = model.apply(
        variables, x, train=True, mutable=["batch_stats"]
    )
    assert out.shape == (2, 10)
    assert "batch_stats" in updates


def test_bert_tiny_forward():
    cfg = BertConfig.tiny()
    model = BertForPretraining(cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.key(0), ids)
    mlm, nsp = model.apply(params, ids)
    assert mlm.shape == (2, 16, cfg.vocab_size)
    assert nsp.shape == (2, 2)


def test_llama_tiny_forward_and_causality():
    cfg = LlamaConfig.tiny()
    model = LlamaModel(cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    params = model.init(jax.random.key(0), ids)
    logits = model.apply(params, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    # Causality: changing a future token must not affect earlier logits.
    ids2 = ids.at[:, 10].set((ids[:, 10] + 1) % cfg.vocab_size)
    logits2 = model.apply(params, ids2)
    assert jnp.allclose(logits[:, :10], logits2[:, :10], atol=1e-5)
    assert not jnp.allclose(logits[:, 10:], logits2[:, 10:], atol=1e-5)


def test_llama_moe_forward():
    cfg = LlamaConfig.tiny(num_experts=4)
    model = LlamaModel(cfg)
    ids = jnp.zeros((2, 8), jnp.int32)
    params = model.init(jax.random.key(0), ids)
    assert model.apply(params, ids).shape == (2, 8, cfg.vocab_size)


def test_word2vec_nce_loss():
    model = SkipGramModel(vocab_size=100, embedding_size=16)
    center = jnp.array([1, 2, 3])
    labels = jnp.array([4, 5, 6])
    negatives = jnp.array([[7, 8], [9, 10], [11, 12]])
    params = model.init(jax.random.key(0), center)
    loss = nce_loss(model, params, center, labels, negatives)
    assert loss.shape == ()
    assert jnp.isfinite(loss)


# -- RMSNorm, the norm every decoder cell runs, against the formula ----------

def _rms_norm_case(dtype, seed=0, eps=1e-6):
    from horovod_tpu.models.llama import RMSNorm

    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(3, 5, 256) * 2.0, dtype)
    scale = jnp.asarray(1.0 + 0.5 * rng.randn(256), jnp.float32)
    cotangent = jnp.asarray(rng.randn(3, 5, 256), jnp.float32)
    module = RMSNorm(eps, dtype)
    variables = module.init(jax.random.key(0), x)
    assert jax.tree.map(jnp.shape, variables) == {"params": {"scale": (256,)}}
    assert bool(jnp.all(variables["params"]["scale"] == 1.0))

    def apply(x, scale):
        return module.apply({"params": {"scale": scale}}, x)

    # The formula in float64 on the values the module is given.
    x64, s64 = np.asarray(x, np.float64), np.asarray(scale, np.float64)
    rstd = 1.0 / np.sqrt((x64 * x64).mean(-1, keepdims=True) + eps)
    return apply, (x, scale, cotangent), (x64, s64, rstd)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rms_norm_forward_is_the_formula(dtype):
    apply, (x, scale, _), (x64, s64, rstd) = _rms_norm_case(dtype)
    got = apply(x, scale)
    assert got.dtype == dtype and got.shape == x.shape
    # fp32 arithmetic inside whatever comes in; bf16 rounds once, at the end.
    tolerance = 2e-6 if dtype == jnp.float32 else 2.0 ** -8
    np.testing.assert_allclose(np.asarray(got, np.float64), x64 * rstd * s64,
                               rtol=tolerance, atol=tolerance)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rms_norm_gradient_is_the_formulas(dtype):
    """d/dx and d/dscale of sum(y * c), derived by hand: with g = c * scale
    and r = rstd, dx = r * g - x * r^3 * mean(g * x), dscale = sum(c * x * r)
    over the rows."""
    apply, (x, scale, c), (x64, s64, rstd) = _rms_norm_case(dtype, seed=1)

    def loss(x, scale):
        return jnp.sum(apply(x, scale).astype(jnp.float32) * c)

    dx, dscale = jax.grad(loss, argnums=(0, 1))(x, scale)
    assert dx.dtype == dtype and dscale.dtype == jnp.float32
    c64 = np.asarray(c, np.float64)
    g = c64 * s64
    want_dx = rstd * g - x64 * rstd ** 3 * (g * x64).mean(-1, keepdims=True)
    want_dscale = (c64 * x64 * rstd).sum(axis=(0, 1))
    loose = dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(dx, np.float64), want_dx,
                               rtol=2.0 ** -7 if loose else 1e-5,
                               atol=2.0 ** -7 if loose else 1e-5)
    np.testing.assert_allclose(np.asarray(dscale, np.float64), want_dscale,
                               rtol=2e-2 if loose else 1e-5,
                               atol=5e-2 if loose else 1e-4)


# -- what a decoder layer is: ``LlamaConfig.layers`` ---------------------------

def _spec(mixer="attention", ffn="dense", heads=4, window=None,
          rope=RopeParameters(10000.0), type="full_attention", **wiring):
    return LayerSpec(mixer, ffn, heads, window, rope, type, **wiring)


def _tiny(**changes):
    return dataclasses.replace(LlamaConfig.tiny(), **changes)


_HALF_TURN = RopeParameters(5e5, None, 0.5)
_ONE, _STILL = dict(norms=("norm",)), dict(rope=None)


@pytest.mark.parametrize("cfg, specs", [
    pytest.param(_tiny(), (_spec(), _spec()), id="plain"),
    pytest.param(
        _tiny(num_layers=3, num_experts=4, first_dense_layers=1),
        (_spec(), _spec(ffn="routed"), _spec(ffn="routed")),
        id="first_dense_layers"),
    pytest.param(
        _tiny(num_layers=4, sliding_window=16, layer_types=(
            "sliding_attention", "linear_attention", "conv",
            "full_attention"), linear_num_key_heads=2,
            linear_num_value_heads=2, linear_key_head_dim=8,
            linear_value_head_dim=8),
        (_spec(window=16, type="sliding_attention"),
         _spec("linear_attention", type="linear_attention"),
         _spec("conv", type="conv"), _spec()),
        id="layer_types"),
    pytest.param(
        _tiny(num_layers=5, hybrid_override_pattern="MEM*E", num_experts=4,
              mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16),
        (_spec("mamba2", None, **_ONE), _spec(None, "routed", **_ONE),
         _spec("mamba2", None, **_ONE), _spec("attention", None, **_ONE),
         _spec(None, "routed", **_ONE)),
        id="hybrid_override_pattern"),
    pytest.param(
        _tiny(num_layers=8, mb_per_layer=2, sliding_window=16,
              ssm_state_size=8, rope_theta=None,
              attention_kind="differential"),
        (_spec("mamba", window=16, type="sliding_attention", **_STILL),
         _spec("attention", window=16, type="sliding_attention", **_STILL),
         _spec("mamba", window=16, type="sliding_attention", **_STILL),
         _spec("attention", window=16, type="sliding_attention", **_STILL),
         _spec("mamba", writes="memory", **_STILL),
         _spec("attention", writes="kv", **_STILL),
         _spec("gated_memory", reads="memory", **_STILL),
         _spec("cross_attention", reads="kv", **_STILL)),
        id="mb_per_layer"),
    pytest.param(
        _tiny(num_layers=3, attention_head_dim=16, sliding_window=8,
              layer_types=("full_attention",) + ("sliding_attention",) * 2,
              num_attention_heads_per_layer=(4, 6, 6), rope_parameters=(
                  ("full_attention", _HALF_TURN),
                  ("sliding_attention", RopeParameters(1e4)))),
        (_spec(rope=_HALF_TURN),
         _spec(heads=6, window=8, type="sliding_attention"),
         _spec(heads=6, window=8, type="sliding_attention")),
        id="heads_and_rope_a_layer"),
])
def test_layer_specs(cfg, specs):
    """One mechanism a case: the whole stack as ``_layer_specs`` resolves
    it, and the nine accessors as nothing but views of it."""
    assert cfg.layers == specs
    assert cfg.num_layers == len(specs)
    shares = any(s.reads or s.writes for s in specs)
    published = {("mamba2", None): "M", (None, "routed"): "E",
                 ("attention", None): "*"}
    for i, s in enumerate(specs):
        assert (cfg.kind_of(i), cfg.is_routed(i), cfg.is_linear(i),
                cfg.is_conv(i), cfg.layer_type(i), cfg.mixer_of(i),
                cfg.heads_of(i), cfg.window_of(i), cfg.rope_of(i)) == (
            published[s.mixer, s.ffn] if s.norms == ("norm",) else None,
            s.ffn == "routed", s.mixer == "linear_attention",
            s.mixer == "conv", s.type, s.mixer if shares else None,
            s.heads, s.window, s.rope), i
    assert cfg.has_linear_layers == any(
        s.mixer == "linear_attention" for s in specs)
    assert cfg.has_conv_layers == any(s.mixer == "conv" for s in specs)
    # No field: a copy resolves its own, and equality, hashing and what
    # ``dataclasses.replace`` hands to ``__init__`` are the fields' alone.
    assert "layers" not in {f.name for f in dataclasses.fields(cfg)}
    copy = dataclasses.replace(cfg, vocab_size=cfg.vocab_size)
    assert copy.layers == specs and copy == cfg and hash(copy) == hash(cfg)


@pytest.mark.parametrize("two", [
    dict(layer_types=("full_attention",) * 2, hybrid_override_pattern="**"),
    dict(layer_types=("full_attention",) * 2, mb_per_layer=2),
    dict(hybrid_override_pattern="**", mb_per_layer=2),
])
def test_one_mechanism_names_the_layers_kinds(two):
    with pytest.raises(ValueError, match=" and ".join(two) + " each name"):
        _tiny(**two)
