"""The ``phi-4-mini-flash.train-s8k`` cell's new calls compiled for a
described ``v5e:2x2`` (no chip attached), at the cell's size, beside
``tests/test_nemotron_h_v5e_compile.py`` and in its manner: the selective
scan's Mosaic pair over 5,120 channels of 16 state entries; a ``Mamba1``
layer whole, forward and backward, whose Mosaic calls are that pair and the
filter's; and differential attention's flash calls at 20 query heads over 10
key-value heads, keys 64 wide and values 128, under the window.  The whole
step at 1 x 8192 is compiled by the builder's study and on the chip, not here
(it takes most of a minute)."""

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
from horovod_tpu.ops import selective_scan, short_conv

CELL = "phi-4-mini-flash.train-s8k"
_MOSAIC_CALL = re.compile(r' = .*custom_call_target="tpu_custom_call"')
B, S = 1, 8192


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

    for module in (fa, short_conv, selective_scan):
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


def test_the_scans_mosaic_pair_at_5120_channels(one_chip, config):
    """Forward and backward of ``selective_scan`` on ``bf16[1, 8192, 5120]``
    with a float32 step: two Mosaic calls, and no ``[S, channels, N]``
    array anywhere in the program (2.7 GB in float32)."""
    inner, states = config.scan_inner, config.ssm_state_size
    assert (inner, states) == (5120, 16)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((B, S, inner), jnp.bfloat16), sds((B, S, inner), jnp.float32),
            sds((inner, states), jnp.float32),
            sds((B, S, states), jnp.bfloat16),
            sds((B, S, states), jnp.bfloat16), sds((inner,), jnp.float32))

    def grads(*xs):
        return jax.grad(lambda *xs: jnp.sum(selective_scan.selective_scan(
            *xs, in_place=True).astype(jnp.float32)),
            argnums=tuple(range(6)))(*xs)

    before = selective_scan.body_counts()
    compiled = jax.jit(grads).lower(*args).compile()
    assert selective_scan.body_counts()["mosaic"] == before["mosaic"] + 1
    text = compiled.as_text()
    assert len(_mosaic_calls(text)) == 2
    assert not re.findall(rf"\[(?:\d+,)*{S},(?:{inner},{states}|"
                          rf"{states},{inner})\]", text)
    # What the pair keeps beside its operands: the state every 128 tokens
    # (21 MB) and the backward call's partial sums for B and C.
    assert compiled.memory_analysis().temp_size_in_bytes < 200e6


def test_a_mamba_layer_whole(one_chip, config):
    """``Mamba1`` at the cell's size, forward and backward with its
    parameters as it initialises them: four Mosaic calls (the filter's pass
    and the scan, each way), each under its scope."""
    module = llama.Mamba1(config, in_place=True)
    hidden = config.hidden_size

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    x = jax.ShapeDtypeStruct((B, S, hidden), jnp.bfloat16, sharding=one_chip)
    variables = jax.eval_shape(
        lambda k: module.init(k, jnp.zeros((1, 16, hidden), jnp.bfloat16)),
        jax.random.key(0))

    def grads(variables, x):
        return jax.grad(lambda p, x: sum(jnp.sum(out.astype(jnp.float32))
                                         for out in module.apply(p, x)),
                        argnums=(0, 1))(variables, x)

    text = jax.jit(grads).lower(jax.tree.map(sds, variables),
                                x).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 4
    assert sum(scopes.SSCAN_CONV in c for c in calls) == 2
    assert sum(scopes.SSCAN_SCAN in c for c in calls) == 2


def test_differential_attentions_flash_calls(one_chip, config):
    """One map of a windowed layer's two: 20 query heads in groups of 2 over
    10 key-value heads, keys 64 wide and values 128, a window of 512: two
    Mosaic calls and no ``[S, S]`` array."""
    assert (config.num_heads, config.num_kv_heads, config.head_dim,
            config.sliding_window) == (40, 20, 64, 512)

    def sds(heads, width):
        return jax.ShapeDtypeStruct((B, S, heads, width), jnp.bfloat16,
                                    sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *x: jnp.sum(fa.flash_attention_fn(
            *x, window=512).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(sds(20, 64), sds(10, 64),
                                sds(10, 128)).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 2
    assert sum(scopes.FLASH_FWD in c for c in calls) == 1
    assert sum(scopes.FLASH_BWD in c for c in calls) == 1
    assert not re.findall(rf"\w+\[(?:\d+,)*{S},{S}\]", text)
