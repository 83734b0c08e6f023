"""The ``olmo-hybrid-7b.train-s8k`` cell compiled for a described
``v5e:2x2`` (no chip attached), beside ``tests/test_flash_v5e_compile.py``
and in its manner: the chunkwise gated delta rule at the cell's shape, whose
walks are ``while`` loops over chunks and never over tokens; and the cell's
whole train step, which fits the chip with every head of both mixers (the
test that ISSUE 38 made the condition of halving them) and whose linear
layers' convolutions are ``ops/short_conv.py``'s Mosaic calls; and a hybrid
model under the GSPMD step over all four chips, which holds none."""

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
from horovod_tpu.ops import short_conv
from horovod_tpu.ops.gated_delta import CHUNK, gated_delta_rule

CELL = "olmo-hybrid-7b.train-s8k"
_MOSAIC_CALL = re.compile(r' = .*custom_call_target="tpu_custom_call"')
B, S, HEADS, D_K, D_V = 1, 8192, 30, 96, 192
HBM = 15.75 * 2 ** 30      # what the compiler has of the chip's 16 GB


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
    monkeypatch.setattr(short_conv, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_rule_walks_chunks_not_tokens_at_the_cells_shape(one_chip):
    """Forward and backward at 8192 tokens, 30 heads of 96 x 192: the
    compiled program has four loops, over 16 slabs and over a slab's 8
    chunks in each direction, and none over the 8192 tokens."""
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, v = sds((B, S, HEADS, D_K)), sds((B, S, HEADS, D_V))
    gate = sds((B, S, HEADS), jnp.float32)

    def grads(q, k, v, g, beta):
        return jax.grad(lambda *x: jnp.sum(gated_delta_rule(*x).astype(
            jnp.float32)), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    compiled = jax.jit(grads).lower(q, q, v, gate, gate).compile()
    text = compiled.as_text()
    loops = [line for line in text.splitlines() if " while(" in line]
    # What a loop carries says what it walks: a slab's 8 chunks of q
    # (inside), or the sequence's 16 slabs of them (outside); a walk each way.
    chunks = f"bf16[8,{B},{HEADS},{CHUNK},{D_K}]"
    slabs = f"bf16[16,8,{B},{HEADS},{CHUNK},{D_K}]"
    assert len(loops) == 4, len(loops)
    assert sum(slabs in loop for loop in loops) == 2
    assert sum(chunks in loop and slabs not in loop for loop in loops) == 2
    assert 16 * 8 * CHUNK == S
    assert f"[{S}," not in "".join(loops)
    assert not _MOSAIC_CALL.search(text)      # XLA operations alone, today
    memory = compiled.memory_analysis()
    # Inputs, the five gradients, the kept states (bf16) and a slab's
    # preparation: well under 2 GB.
    assert memory.temp_size_in_bytes < 2e9


def test_the_cells_whole_step_fits_with_every_head(topo, one_chip):
    """Four layers of the published widths at 1 x 8192 tokens, all 30
    heads of both mixers: 13.0 GB of state, and arguments + temporaries
    under what the compiler has of the chip.  The softmax layer is two
    flash calls (its forward call is not run again: the policy keeps its
    output).  A linear layer is nine Mosaic calls, all under
    ``hvd.gdn.conv``: q's, k's and v's convolution forward, again under
    recomputation, and backward; the rule is none, and no float32 array of
    an activation's shape is left under that scope."""
    cell = manifest.cell(CELL)
    job = manifest.load_job(cell["config"]["job"]).build(
        cell["config"], cell["traffic"], 1)
    assert cell["config"]["num_attention_heads"] == 30
    assert cell["config"]["linear_num_value_heads"] == 30
    mesh = Mesh([topo.devices[0]], ("data",))
    replicated = NamedSharding(mesh, P())

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=replicated), tree)

    state = jax.eval_shape(job.init_state, jax.random.key(0))
    assert set(state[0]) == {"params"}
    batch = jax.eval_shape(job.make_batch, jax.random.key(0))
    step = hvd.make_train_step(job.loss_fn, job.optimizer, mesh)
    before = short_conv.body_counts()
    compiled = step.lower(*described(state), described(batch)).compile()
    # The trace took the pass for q, k and v of each linear layer, and the
    # plain body for none.
    after = short_conv.body_counts()
    linear = sum(map(job.llama.is_linear, range(job.llama.num_layers)))
    assert after["fused"] - before["fused"] == 3 * linear == 9
    assert after["plain"] == before["plain"]
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if _MOSAIC_CALL.search(line)]
    assert sum(scopes.FLASH_FWD in c for c in calls) == 1
    assert sum(scopes.FLASH_BWD in c for c in calls) == 1
    assert not any(scopes.ROPE in c or scopes.GDN_SCAN in c for c in calls)
    convolutions = [c for c in calls if scopes.GDN_CONV in c]
    assert len(convolutions) == len(calls) - 2 == 9 * linear
    again = [c for c in convolutions if scopes.REMATTED in c]
    # A backward call gives two results: dy and the taps' partial sums.
    backward = [c for c in convolutions if " = (" in c]
    assert len(again) == len(backward) == 3 * linear
    assert not set(again) & set(backward)
    seq = job.seq
    for width in (2880, 5760):
        assert not [line for line in text.splitlines()
                    if scopes.GDN_CONV in line
                    and f" = f32[1,{seq},{width}]" in line], width
    assert not re.findall(rf"\w+\[(?:\d+,)*{seq},{seq}\]", text)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(13.005e9, rel=1e-3)
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < HBM


def test_gspmd_step_of_a_hybrid_model_holds_no_mosaic_call(one_chip, topo):
    """``parallel/api.py::make_parallel_train_step`` over all four chips
    with a hybrid model and its default dense attention, at a sequence the
    convolutions' pass would take: a Mosaic call cannot be partitioned
    automatically, so the linear layers' convolutions are the ``jnp`` body
    unless the ``attention_fn`` the model was given reads its operands in
    place (``tests/test_flash_v5e_compile.py`` has the same for the
    rotation)."""
    import flax.linen as nn
    import numpy as np
    import optax

    from horovod_tpu.models.llama import LlamaConfig, LlamaModel
    from horovod_tpu.parallel.api import (make_parallel_train_step,
                                          param_shardings)

    config = LlamaConfig(
        vocab_size=1024, hidden_size=256, num_layers=2, num_heads=2,
        num_kv_heads=2, intermediate_size=512, max_seq_len=64,
        rope_theta=None, layer_types=("linear_attention", "full_attention"),
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, dtype=jnp.bfloat16)
    assert short_conv.why_not((8, 64, 2 * 96), (4, 2 * 96), 2) is None
    mesh = Mesh(np.array(topo.devices).reshape(1, 2, 2),
                ("data", "fsdp", "tensor"))
    model = LlamaModel(config)
    optimizer = optax.sgd(1e-2)
    params = nn.meta.unbox(jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 64), jnp.int32)))

    def placed(tree, shardings):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, shardings)

    params = placed(params, param_shardings(params, mesh))
    state = jax.eval_shape(optimizer.init, params)
    state = placed(state, jax.tree.map(
        lambda _: NamedSharding(mesh, P()), state))
    tokens = jax.ShapeDtypeStruct((8, 65), jnp.int32,
                                  sharding=NamedSharding(mesh, P("fsdp")))
    before = short_conv.body_counts()
    step = make_parallel_train_step(model, optimizer, mesh)
    compiled = step.lower(params, state, tokens).compile()
    after = short_conv.body_counts()
    assert not _MOSAIC_CALL.search(compiled.as_text())
    assert "all-reduce" in compiled.as_text()
    assert after["fused"] == before["fused"]
    assert after["plain"][short_conv.NOT_IN_PLACE] == before["plain"].get(
        short_conv.NOT_IN_PLACE, 0) + 3
