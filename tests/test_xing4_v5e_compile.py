"""The ``xing4.0-29b-a4b.train-s8k`` cell compiled for a described
``v5e:2x2`` (no chip attached), beside ``tests/test_smallthinker_v5e_compile.py``
and in its manner: the flash kernel's two calls at the cell's shapes (32 heads,
keys 192 wide and values 128, 8,192 positions: K padded to 256 lanes, whole
and held twice, passes the compiler's default limit, so the forward call
states the one it computes), and the cell's train step at one layer of each
kind -- the dense one and a routed one, four streams through both -- with its
arguments, its temporaries, the streams' Mosaic calls
(``ops/hyper_connection.py``) under the two scopes around both sublayers of
both layers, the query latent under ``hvd.mla.latent``, the maps with tokens
on the lanes, and the streams as rows from the embedding to the head.  That the cell's depth fits the chip is the chip's to say
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
from horovod_tpu.ops import hyper_connection as hc
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
    monkeypatch.setattr(hc, "_interpret", lambda: False)
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
    scope, by the streams' Mosaic calls (``body_counts``: every sublayer,
    none by the ``jnp`` bodies); the query's latent is under
    ``hvd.mla.latent``; the streams are rows ``[T, 4 x 3584]`` in bf16
    wherever they lie."""
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
    mosaic = hc.body_counts()["mosaic"]
    plain = sum(hc.body_counts()["plain"].values())
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
    # A sublayer's calls, in its block's scope: the statistics under the
    # maps' scope, forward and again under the layer's recomputation (2);
    # under the mixes' the read (2), its transpose and the one call that
    # writes x's whole cotangent, all inside the sublayer's module, and
    # beside the module the write (again only where a sublayer follows it
    # in the layer: the first) and its transpose.
    assert sum(hc.body_counts()["plain"].values()) == plain
    assert hc.body_counts()["mosaic"] == mosaic + 2 * LAYERS
    for layer in ("layer_0", "layer_1"):
        for block, module, writes in ((scopes.BLOCK_ATTN, "hc_attn", 2),
                                      (scopes.BLOCK_FFN, "hc_mlp", 1)):
            inside = [c for c in calls if f"{layer}/{block}/{module}/" in c]
            assert len([c for c in inside if scopes.HC_MAP in c]) == 2
            assert len([c for c in inside if scopes.HC_MIX in c]) == 4
            assert len([c for c in calls
                        if f"{layer}/{block}/{scopes.HC_MIX}" in c]
                       ) == writes + 1, (layer, block)
            assert any(f"{layer}/{block}/{module}/{scopes.HC_MAP}" in line
                       and "exponential" in line for line in lines)
        assert any(scopes.MLA_LATENT in line and "wq_a" in line
                   and layer in line for line in lines)
    assert any(scopes.MOE_ROUTE in line and "layer_1" in line
               for line in lines)
    assert not any(scopes.MOE_ROUTE in line and "layer_0" in line
                   for line in lines)
    # The streams are rows from the embedding to the head: no float32
    # tensor of their size, by either shape; no array of theirs in tiles of
    # four rows (how XLA lays ``[.., 4, 3584]`` out), so no relaying copy on
    # the way into a call or out of one; and a token's 4 x 4 map is nowhere
    # the two minor axes of a tensor of T rows (32 MB where the data are
    # 0.5): the write's transpose leaves the maps' cotangents as lanes of
    # ``[T, 128]``.
    # (In the scheduled program itself, the entry computation: what a
    # fusion holds inside is no array.)
    entry = text[text.index("\nENTRY "):]
    assert not re.findall(rf"f32\[(?:\d+,)*{SEQ},(?:4,3584|14336)\]", entry)
    assert not re.findall(r"bf16\[[\d,]*4,3584\]\{[\d,]*:T\(4,128\)", entry)
    assert not [line for line in entry.splitlines() if " copy(" in line
                and re.search(r"bf16\[1024,8,4,3584\]", line)]
    assert not re.findall(rf"f32\[(?:\d+,)*{SEQ},4,4\]", entry)
    assert re.findall(rf"f32\[4,4,{SEQ}\]", entry)
    # No score matrix: the only [.., 8192, 8192] is W_kvb's output, 32 heads
    # of 128 + 128 lanes a token.
    assert not re.findall(rf"\w+\[(?:\d+,)*{HEADS},{SEQ},{SEQ}\]", text)
    assert not re.findall(rf"f32\[(?:\d+,)*{SEQ},{SEQ}\]", text)
    memory = compiled.memory_analysis()
    print(f"arguments {memory.argument_size_in_bytes} + "
          f"temporaries {memory.temp_size_in_bytes}")
    assert memory.argument_size_in_bytes == pytest.approx(
        14 * PARAMETERS, rel=1e-3)
    # 2.71 GB at this depth (3.34 before the streams' calls, PR 66); at the
    # cell's five layers 3.79 GB (4.30) beside 10.63 of arguments.
    assert memory.temp_size_in_bytes <= 3.0e9
