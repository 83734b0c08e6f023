"""The ``smallthinker-21b-a3b.train-s16k`` cell compiled for a described
``v5e:2x2`` (no chip attached), beside ``tests/test_laguna_v5e_compile.py``
and in its manner: the flash kernel's two calls at the cell's shapes (28
query heads in groups of 7 over 4 key-value heads of 128, 16,384 positions,
in place) with the window of 4096 (eight blocks of 512: most of the band is
unmasked interior) and without, and the cell's train step at one layer of
each kind, which walks the band in its window layer, rotates there alone,
and holds no ``[.., S, S]`` array.  That the cell's depth fits the chip is
the chip's to say (``peak_hbm_gb``, every PR)."""

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

CELL = "smallthinker-21b-a3b.train-s16k"
_MOSAIC_CALL = re.compile(r' = .*custom_call_target="tpu_custom_call"')
#: The depth the whole step is compiled at, the shortest prefix of the
#: cell's four layers that holds both kinds: the global layer that does not
#: rotate, then a window layer that does.
LAYERS = 2
SEQ = 16384


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


@pytest.mark.parametrize("window", [4096, None, 4000])
def test_the_two_calls_compile_at_the_cells_shapes(one_chip, window):
    """Forward and backward through the seam at 16,384 tokens, 28 query
    heads over 4 key-value heads of 128, in place: two Mosaic calls and no
    array with two sequence-long axes."""
    heads, kv_heads, dim = 28, 4, 128

    def sds(n):
        return jax.ShapeDtypeStruct((1, SEQ, n, dim), jnp.bfloat16,
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
    assert not re.findall(rf"\w+\[(?:\d+,)*{SEQ},{SEQ}\]", text)


def test_the_band_at_the_cells_size_is_mostly_interior():
    """At 512-row blocks a head of a window layer walks 252 block pairs
    (nine a row of queries from row 8 on: the diagonal block, seven whole
    ones and the far edge's) of which 56 are crossed by an edge of the mask
    (Laguna's window of 512: every one of its 31); the global layer walks
    528, 32 on the diagonal."""
    block = fa._pick_block(SEQ, fa.BLOCK_Q)
    assert block == 512
    live, crossed = fa.pair_counts(SEQ, block, block, True, 4096)
    assert (live, crossed) == (252, 56) == (36 + 24 * 9, 32 + 24)
    assert fa.pair_counts(SEQ, block, block, True, None) == (528, 32)
    assert fa.pair_counts(8192, block, block, True, 512) == (31, 31)


def test_the_cells_whole_step_walks_the_band_and_rotates_there_alone(
        topo, one_chip):
    """The first two of the cell's four layers at the published widths and
    1 x 16,384 tokens, one of each kind.  Each layer is two flash calls
    (the policy keeps the forward call's output, so it is not run again);
    the window layer's are under ``hvd.attn.window`` and so are the
    rotation's calls, ALL of them: the global layer has none; the routing
    is traced under ``hvd.moe.route`` in both; and no ``[.., S, S]`` array
    exists anywhere."""
    cell = manifest.cell(CELL)
    config = {**cell["config"], "num_hidden_layers": LAYERS,
              "rope_layout": cell["config"]["rope_layout"][:LAYERS],
              "sliding_window_layout":
                  cell["config"]["sliding_window_layout"][:LAYERS]}
    assert config["rope_layout"] == [0, 1]
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
    assert after["in_place"] - before["in_place"] == LAYERS
    assert after["flat"] == before["flat"]
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if _MOSAIC_CALL.search(line)]
    forward = [c for c in calls if scopes.FLASH_FWD in c]
    backward = [c for c in calls if scopes.FLASH_BWD in c]
    assert len(forward) == len(backward) == LAYERS
    assert sum(scopes.ATTN_WINDOW in c for c in forward) == 1
    assert sum(scopes.ATTN_WINDOW in c for c in backward) == 1
    assert not any(scopes.REMATTED in c for c in forward)
    rotations = [c for c in calls if scopes.ROPE in c]
    # q and k forward, again under recomputation, and their cotangents: of
    # the window layer alone.
    assert len(rotations) == 6
    assert all(scopes.ATTN_WINDOW in c and "layer_1" in c for c in rotations)
    lines = text.splitlines()
    for layer in ("layer_0", "layer_1"):
        assert any(scopes.MOE_ROUTE in line and layer in line
                   for line in lines)
        assert any(scopes.MOE_EXPERTS in line and layer in line
                   for line in lines)
    assert not any(scopes.MOE_SHARED in line for line in lines)
    assert not re.findall(rf"\w+\[(?:\d+,)*{SEQ},{SEQ}\]", text)
    memory = compiled.memory_analysis()
    print(f"arguments {memory.argument_size_in_bytes} + "
          f"temporaries {memory.temp_size_in_bytes}")
    # Two layers and embedding, head and final norm, 14 bytes a parameter
    # (all four layers: 9.19 GB of arguments, 3.79 GB of temporaries).
    parameters = 2 * 115_512_320 + 194_478_080 + 2_560
    assert memory.argument_size_in_bytes == pytest.approx(
        14 * parameters, rel=1e-3)
    assert memory.temp_size_in_bytes <= 3.9e9
