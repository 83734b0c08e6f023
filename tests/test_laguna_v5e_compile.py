"""The ``laguna-s-2.1.train-s8k`` cell compiled for a described ``v5e:2x2``
(no chip attached), beside ``tests/test_flash_v5e_compile.py`` and in its
manner: the flash kernel's two calls with a window at the cell's shapes (72
heads in groups of 9 and 48 in groups of 6 over 8 key-value heads, in
place), and the cell's train step at one layer of each kind, which walks
the band in its sliding layer and holds no ``[.., S, S]`` array.  That the
cell's depth fits the chip is the chip's to say (``peak_hbm_gb``, every
PR)."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import horovod_tpu.jax as hvd
from benchmark import manifest
from horovod_tpu.common import scopes
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import rope

CELL = "laguna-s-2.1.train-s8k"
_MOSAIC_CALL = re.compile(r' = .*custom_call_target="tpu_custom_call"')
#: The depth the whole step is compiled at, the shortest prefix of the
#: cell's five layers that holds every kind: a full layer of 48 heads before
#: a dense MLP, then a sliding one of 72 before routed experts.
LAYERS = 2
#: The configuration's lists that state a value a layer.
A_LAYER = ("layer_types", "mlp_layer_types", "gating_types",
           "num_attention_heads_per_layer")


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
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(rope, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("heads, window", [(72, 512), (48, None), (72, 300)])
def test_the_two_calls_compile_at_the_cells_head_counts(one_chip, heads,
                                                        window):
    """Forward and backward through the seam at 8192 tokens, ``heads``
    query heads over 8 key-value heads of 128, in place: two Mosaic calls
    and no array with two sequence-long axes."""
    seq, kv_heads, dim = 8192, 8, 128

    def sds(n):
        return jax.ShapeDtypeStruct((1, seq, n, dim), jnp.bfloat16,
                                    sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *x: jnp.sum(fa.flash_attention_fn(
            *x, window=window).astype(jnp.float32)), argnums=(0, 1, 2))(
                q, k, v)

    before = fa.layout_counts()
    text = jax.jit(grads).lower(sds(heads), sds(kv_heads),
                                sds(kv_heads)).compile().as_text()
    assert fa.layout_counts()["in_place"] == before["in_place"] + 1
    calls = [line for line in text.splitlines() if _MOSAIC_CALL.search(line)]
    assert sum(scopes.FLASH_FWD in c for c in calls) == 1
    assert sum(scopes.FLASH_BWD in c for c in calls) == 1
    assert not re.findall(rf"\w+\[(?:\d+,)*{seq},{seq}\]", text)


def test_the_cells_whole_step_fits_and_walks_the_band(topo, one_chip):
    """The first two of the cell's five layers at the published widths and
    1 x 8192 tokens, one of each kind.  Each layer is two flash calls (the
    policy keeps the forward call's output, so it is not run again) and the
    rotation's calls; the sliding layer's are under ``hvd.attn.window``,
    the full layer's are not; the gate is there; and no ``[.., S, S]``
    array exists anywhere.  That the five layers' 11.35 GB of state and
    their temporaries fit the chip is no longer summed here: the chip's
    ``peak_hbm_gb`` in this cell says it in every PR, and
    ``tests/benchmark/test_benchmark_reference.py::
    test_whole_step_compiles_for_v5e_and_fits`` compiles a whole step."""
    cell = manifest.cell(CELL)
    config = {**cell["config"], "num_hidden_layers": LAYERS,
              **{key: cell["config"][key][:LAYERS] for key in A_LAYER}}
    job = manifest.load_job(config["job"]).build(config, cell["traffic"], 1)
    mesh = Mesh([topo.devices[0]], ("data",))
    replicated = NamedSharding(mesh, P())

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=replicated), tree)

    state = jax.eval_shape(job.init_state, jax.random.key(0))
    batch = jax.eval_shape(job.make_batch, jax.random.key(0))
    step = hvd.make_train_step(job.loss_fn, job.optimizer, mesh)
    before = fa.layout_counts()
    compiled = step.lower(*described(state), described(batch)).compile()
    after = fa.layout_counts()
    sliding = config["layer_types"].count("sliding_attention")
    assert (LAYERS, sliding) == (2, 1)
    assert config["mlp_layer_types"] == ["dense", "sparse"]
    assert after["in_place"] - before["in_place"] == LAYERS
    assert after["flat"] == before["flat"]
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if _MOSAIC_CALL.search(line)]
    forward = [c for c in calls if scopes.FLASH_FWD in c]
    backward = [c for c in calls if scopes.FLASH_BWD in c]
    assert len(forward) == len(backward) == LAYERS
    assert sum(scopes.ATTN_WINDOW in c for c in forward) == sliding
    assert sum(scopes.ATTN_WINDOW in c for c in backward) == sliding
    assert not any(scopes.REMATTED in c for c in forward)
    rotations = [c for c in calls if scopes.ROPE in c]
    # q and k forward, again under recomputation, and their cotangents.
    assert len(rotations) == 6 * LAYERS
    assert sum(scopes.ATTN_WINDOW in c for c in rotations) == 6 * sliding
    assert any(scopes.ATTN_GATE in line for line in text.splitlines())
    seq = job.seq
    assert not re.findall(rf"\w+\[(?:\d+,)*{seq},{seq}\]", text)
    memory = compiled.memory_analysis()
    print(f"arguments {memory.argument_size_in_bytes} + "
          f"temporaries {memory.temp_size_in_bytes}")
    # Read at these two layers (all five: 11.354 GB of arguments).
    assert memory.argument_size_in_bytes == pytest.approx(5.3673e9, rel=1e-3)
    assert memory.temp_size_in_bytes <= 2.048e9
