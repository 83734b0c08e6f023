"""The ``lfm2-24b-a2b.train-s8k-b2`` cell's new calls compiled for a described
``v5e:2x2`` (no chip attached), at the cell's size, beside
``tests/test_phi4_flash_v5e_compile.py`` and in its manner: the gated short
convolution's Mosaic pair over ``bf16[2, 8192, 3 x 2048]``; a
``GatedShortConv`` layer whole, forward and backward, whose Mosaic calls are
that pair under its scope; the flash calls at 32 query heads over 8
key-value heads of 64; and a routed layer's grouped products, Mosaic calls
at this cell's widths too since PR 68.  The whole step at 2 x 8192 is
compiled by the builder's study and on the chip, not here (it takes most of
a minute)."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import manifest
from horovod_tpu.common import scopes
from horovod_tpu.models import llama
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import grouped_matmul, short_conv

CELL = "lfm2-24b-a2b.train-s8k-b2"
_MOSAIC_CALL = re.compile(r' = .*custom_call_target="tpu_custom_call"')
B, S = 2, 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as error:
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")


@pytest.fixture
def one_chip(topo, monkeypatch):
    """The kernels' non-interpreted bodies, and no persistent cache (a
    deviceless executable cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    for module in (fa, short_conv, grouped_matmul):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def config():
    cell = manifest.cell(CELL)
    return manifest.load_job(cell["config"]["job"]).build(
        cell["config"], cell["traffic"], 1).llama


def _mosaic_calls(text):
    return [line for line in text.splitlines() if _MOSAIC_CALL.search(line)]


def test_the_gated_pair_at_2048_channels(one_chip, config):
    """Forward and backward of the gated ``convolved`` on ``bf16[2, 8192,
    6144]``: two Mosaic calls, blocks of 256 rows, and no float32 array of
    the activations' shape anywhere in the program."""
    width = config.hidden_size
    assert (width, config.conv_L_cache) == (2048, 3)
    assert short_conv._pick_rows(S, 3 * width) == 256

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(y, taps, g):
        out, vjp = jax.vjp(lambda y, t: short_conv.convolved(
            y, t, 1, None, True, gated=True), y, taps)
        return out, vjp(g)

    before = short_conv.body_counts()
    compiled = jax.jit(run).lower(
        sds((B, S, 3 * width), jnp.bfloat16), sds((3, width), jnp.float32),
        sds((B, S, width), jnp.bfloat16)).compile()
    after = short_conv.body_counts()
    assert after["fused"] == before["fused"] + 1
    assert after["plain"] == before["plain"]
    text = compiled.as_text()
    assert len(_mosaic_calls(text)) == 2
    assert not re.findall(rf"f32\[{B},{S},(?:{width}|{3 * width})\]", text)
    # Beside its operands and results the pair keeps the taps' partial sums.
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6


def test_a_conv_layer_whole(one_chip, config):
    """``GatedShortConv`` at the cell's size, forward and backward with its
    parameters as it initialises them: two Mosaic calls, each under
    ``hvd.lconv.conv``, between products under ``hvd.lconv.proj``."""
    module = llama.GatedShortConv(config, in_place=True)
    hidden = config.hidden_size

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    x = jax.ShapeDtypeStruct((B, S, hidden), jnp.bfloat16, sharding=one_chip)
    variables = jax.eval_shape(
        lambda k: module.init(k, jnp.zeros((1, 16, hidden), jnp.bfloat16)),
        jax.random.key(0))

    def grads(variables, x):
        return jax.grad(lambda p, x: jnp.sum(module.apply(p, x).astype(
            jnp.float32)), argnums=(0, 1))(variables, x)

    text = jax.jit(grads).lower(jax.tree.map(sds, variables),
                                x).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 2
    assert all(scopes.LCONV_CONV in call for call in calls)
    assert scopes.LCONV_PROJ in text


def test_the_flash_calls_at_32_heads_over_8_of_64(one_chip, config):
    """Grouped-query heads of 64, groups of 4: two Mosaic calls in the flat
    layout and no ``[S, S]`` array."""
    assert (config.num_heads, config.num_kv_heads, config.head_dim) == (
        32, 8, 64)

    def sds(heads):
        return jax.ShapeDtypeStruct((B, S, heads, 64), jnp.bfloat16,
                                    sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *x: jnp.sum(fa.flash_attention_fn(*x).astype(
            jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(sds(32), sds(8), sds(8)).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 2
    assert sum(scopes.FLASH_FWD in c for c in calls) == 1
    assert sum(scopes.FLASH_BWD in c for c in calls) == 1
    assert not re.findall(rf"\w+\[(?:\d+,)*{S},{S}\]", text)


def test_a_routed_layers_grouped_products_are_mosaic_calls(one_chip, config):
    """16 of 64 SwiGLU experts at 2 x 8192 tokens, 4 choices a token, IN
    PLACE: 65,536 assignments through buffers of 32,768 rows (the first
    buffer and the loop's body), forward.  Hidden 2048 and the experts' 1536
    are whole lane tiles (16 and 12), which kept every grouped product with
    ``ragged_dot`` until PR 68; alone at this call the Mosaic grouped matmul
    takes 0.69 of its time (PERF.md §5), so the four are Mosaic calls
    (``ops/grouped_matmul.py``: ``gmm`` in blocks of 256 rows with the
    contracted width whole) on the parameters as they are (``w_gate_up [16,
    2048, 3072]``, ``w_down [16, 1536, 2048]``): no padded copy, no
    ``ragged_dot``, and the calls carry the scope ``moe_experts_ms``
    reads."""
    module = llama.RoutedExperts(config, in_place=True)
    variables = jax.eval_shape(
        lambda k: module.init(k, jnp.zeros((1, 8, 2048), jnp.bfloat16)),
        jax.random.key(0))
    x = jax.ShapeDtypeStruct((B, S, 2048), jnp.bfloat16, sharding=one_chip)
    before = grouped_matmul.body_counts()
    llama._one_buffer.clear_cache()     # it keeps its traces by shape
    lowered = jax.jit(module.apply).lower(jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip),
        variables), x)
    text = lowered.as_text()
    after = grouped_matmul.body_counts()
    assert after["mosaic"] == before["mosaic"] + 4
    assert after["xla"] == before["xla"]
    assert "ragged_dot" not in text
    products = [line for line in text.splitlines()
                if "tpu_custom_call" in line]
    assert len(products) == 4
    for line in products:
        assert ("16x2048x3072xbf16" in line) != ("16x1536x2048xbf16" in line)
        assert "32768x2048xbf16" in line
        assert ("32768x3072xbf16" in line) != ("32768x1536xbf16" in line)
    assert "stablehlo.pad" not in "".join(
        line for line in text.splitlines() if "16x2048x" in line
        or "16x1536x" in line)
    calls = _mosaic_calls(lowered.compile().as_text())
    assert len(calls) == 4
    assert all(scopes.MOE_EXPERTS in call for call in calls)
