"""The ``xing4.0-29b-a4b.train-s8k`` cell compiled for a described
``v5e:2x2`` (no chip attached), beside ``tests/test_smallthinker_v5e_compile.py``
and in its manner: the flash kernel's two calls at the cell's shapes (32 heads,
keys 192 wide and values 128, 8,192 positions: K padded to 256 lanes, whole
and held twice, passes the compiler's default limit, so the forward call
states the one it computes), and the cell's train step at one layer of each
kind -- the dense one and a routed one, four streams through both -- with its
arguments, its temporaries, the two new scopes around both sublayers of both
layers, the query latent under ``hvd.mla.latent``, and the maps with tokens on
the lanes.  That the cell's depth fits the chip is the chip's to say
(``peak_hbm_gb``, every PR); deviceless at the cell's five layers the step
reads 10.63 GB of arguments (759,346,190 parameters at 14 bytes) and 4.30 GB
of temporaries under ``layer_keep_attention`` (PR 65; 82 s of compiling where
two layers take 33)."""

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

CELL = "xing4.0-29b-a4b.train-s8k"
_MOSAIC_CALL = re.compile(r' = .*custom_call_target="tpu_custom_call"')
#: The depth the whole step is compiled at: the leading dense layer and one
#: routed layer, the shortest prefix of the cell's five that holds both kinds.
LAYERS = 2
SEQ, HEADS, QK, V = 8192, 32, 192, 128
#: One layer of each kind, embedding, head and final norm, from the built
#: leaves (``tests/benchmark/test_benchmark_hc.py`` has the table).
PARAMETERS = 128_196_918 + 128_426_294 + 117_444_096


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


def _calls(text):
    return [line for line in text.splitlines() if _MOSAIC_CALL.search(line)]


def _scoped_vmem(calls, which="used_scoped_memory_configs"):
    """Bytes of scoped VMEM the compiler gave each of these calls, or (with
    ``scoped_memory_configs``) the limit each was compiled under."""
    return [int(n) for call in calls for n in re.findall(
        rf'"{which}":\[\{{[^}}]*"size":"(\d+)"', call)]


def test_the_two_calls_compile_at_the_cells_shapes(one_chip):
    """Forward and backward through the seam at 8,192 tokens, 32 heads, keys
    192 and values 128 wide, in place: two Mosaic calls, no array with two
    sequence-long axes, and the forward call compiled under the limit it
    states -- 20.7 MB, over the compiler's default of 16 MiB, which
    ``deepseek-v2-lite``'s 4,096 rows stay under."""
    def sds(width):
        return jax.ShapeDtypeStruct((1, SEQ, HEADS, width), jnp.bfloat16,
                                    sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *x: jnp.sum(fa.flash_attention_fn(
            *x, scale=2.005 * QK ** -0.5).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(sds(QK), sds(QK), sds(V)).compile().as_text()
    calls = _calls(text)
    forward = [c for c in calls if scopes.FLASH_FWD in c]
    backward = [c for c in calls if scopes.FLASH_BWD in c]
    assert len(forward) == len(backward) == 1
    assert not re.findall(rf"\w+\[(?:\d+,)*{SEQ},{SEQ}\]", text)
    block = fa._pick_block(SEQ, fa.BLOCK_Q)
    stated = fa._fwd_vmem_limit(SEQ, QK, V, block, block, 2, masked=False)
    assert stated == 20_709_376 > fa._DEFAULT_SCOPED_VMEM
    assert fa._fwd_vmem_limit(4096, QK, V, block, block, 2,
                              masked=False) == fa._DEFAULT_SCOPED_VMEM
    # The call is compiled under the limit it states and takes 12.9 MB of
    # it: a head's K and V in the flat layout, not all heads' side by side.
    assert _scoped_vmem(forward, "scoped_memory_configs") == [stated]
    used = _scoped_vmem(forward)
    assert used and max(used) <= fa._DEFAULT_SCOPED_VMEM, used
    assert max(_scoped_vmem(backward)) <= fa._bwd_vmem_limit(
        SEQ, QK, block, block, 2, 0, d_v=V)


def test_the_cells_whole_step_carries_four_streams_through_both_kinds(
        topo, one_chip):
    """The first two of the cell's five layers at the published widths and
    1 x 8,192 tokens.  Each layer is two flash calls (the policy keeps the
    forward call's output); both sublayers of both layers make their maps
    under ``hvd.hc.map`` and mix under ``hvd.hc.mix``, inside their block's
    scope; the query's latent is under ``hvd.mla.latent``; nothing under
    ``hvd.hc.map`` is a float32 tensor of T rows whose minor axes are the
    4 x 4 of a token's map (32 MB where the data are 0.5), and the module
    holds such a tensor once a sublayer alone, where the write's backward
    reduction leaves H_res's cotangent before it is turned to the lanes."""
    cell = manifest.cell(CELL)
    config = {**cell["config"], "num_hidden_layers": LAYERS}
    assert cell["config"]["num_hidden_layers"] == 5
    assert cell["config"]["training"]["remat"] == "layer_keep_attention"
    job = manifest.load_job(config["job"]).build(config, cell["traffic"], 1)
    mesh = Mesh([topo.devices[0]], ("data",))
    replicated = NamedSharding(mesh, P())

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=replicated), tree)

    state = jax.eval_shape(job.init_state, jax.random.key(0))
    batch = jax.eval_shape(job.make_batch, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(state[0])) == PARAMETERS
    step = hvd.make_train_step(job.loss_fn, job.optimizer, mesh,
                               has_aux=True)
    before = fa.layout_counts()
    compiled = step.lower(*described(state), described(batch)).compile()
    after = fa.layout_counts()
    # Keys of 192 lanes are no whole number of lane tiles: the flat layout,
    # as ``deepseek-v2-lite``'s.
    assert after["in_place"] == before["in_place"]
    assert sum(after["flat"].values()) - sum(
        before["flat"].values()) == LAYERS
    text = compiled.as_text()
    lines = text.splitlines()
    calls = _calls(text)
    forward = [c for c in calls if scopes.FLASH_FWD in c]
    backward = [c for c in calls if scopes.FLASH_BWD in c]
    assert len(forward) == len(backward) == LAYERS
    assert not any(scopes.REMATTED in c for c in forward)
    assert set(_scoped_vmem(forward, "scoped_memory_configs")) == {
        20_709_376}
    for layer in ("layer_0", "layer_1"):
        for block, module in ((scopes.BLOCK_ATTN, "hc_attn"),
                              (scopes.BLOCK_FFN, "hc_mlp")):
            assert any(f"{layer}/{block}/{module}/{scopes.HC_MAP}" in line
                       for line in lines), (layer, module)
            assert any(f"{layer}/{block}/{scopes.HC_MIX}" in line
                       for line in lines), (layer, block)
        assert any(scopes.MLA_LATENT in line and "wq_a" in line
                   and layer in line for line in lines)
    assert any(scopes.MOE_ROUTE in line and "layer_1" in line
               for line in lines)
    assert not any(scopes.MOE_ROUTE in line and "layer_0" in line
                   for line in lines)
    token_major_map = re.compile(rf"f32\[(?:\d+,)*{SEQ},4,4\]")
    assert not [line for line in lines if scopes.HC_MAP in line
                and token_major_map.search(line.split(" = ")[-1][:80])]
    # In the scheduled program itself (the entry computation) such a tensor
    # is made twice a sublayer, both times by the write's backward pass:
    # H_res's cotangent as its reduction leaves it, and its copy on the way
    # to the lanes.
    made = [line for line in text[text.index("\nENTRY "):].splitlines()
            if re.match(rf"\s*(ROOT )?%?[\w.\-]+ = f32\[(\d+,)*{SEQ},4,4\]",
                        line)]
    assert len(made) == 2 * 2 * LAYERS, len(made)
    assert all(scopes.HC_MIX in line and "transpose(" in line
               for line in made)
    # No score matrix: the only [.., 8192, 8192] is W_kvb's output, 32 heads
    # of 128 + 128 lanes a token.
    assert not re.findall(rf"\w+\[(?:\d+,)*{HEADS},{SEQ},{SEQ}\]", text)
    assert not re.findall(rf"f32\[(?:\d+,)*{SEQ},{SEQ}\]", text)
    memory = compiled.memory_analysis()
    print(f"arguments {memory.argument_size_in_bytes} + "
          f"temporaries {memory.temp_size_in_bytes}")
    assert memory.argument_size_in_bytes == pytest.approx(
        14 * PARAMETERS, rel=1e-3)
    # 3.34 GB at this depth; at the cell's five layers 4.30 GB beside 10.63
    # of arguments, 14.93 of a chip's 15.75.
    assert memory.temp_size_in_bytes <= 3.6e9
