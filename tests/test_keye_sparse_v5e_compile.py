"""Learned sparse attention compiled for a described ``v5e:2x2`` (no chip
attached), beside ``tests/test_flash_v5e_compile.py`` and in its manner: the
selection, the flash kernel's two calls over a selection with 32 query
heads on 4 key-value heads, and the indexer's loss, each ONE Mosaic call at
the ``keye-vl-2.0-30b-a3b.train-s8k-b2`` cell's shape; and the cell's
train step at one of its five layers (they are of one kind), which holds no
array of scores or probabilities a head.  That the cell's depth fits the
chip is the chip's to say (``peak_hbm_gb``, every PR)."""

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
from horovod_tpu.ops import sparse_index

CELL = "keye-vl-2.0-30b-a3b.train-s8k-b2"
_MOSAIC_CALL = re.compile(r' = .*custom_call_target="tpu_custom_call"')
_USED = re.compile(r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"')
_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20
B, S, HEADS, KV_HEADS, D = 2, 8192, 32, 4, 128
INDEX_HEADS, INDEX_DIM, TOPK = 16, 64, 2048
SCALE = (INDEX_HEADS * INDEX_DIM) ** -0.5
#: The depth the whole step is compiled at: every layer of the cell's five is
#: of one kind, so one shows what a layer holds.
LAYERS = 1


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


def _mosaic_calls(compiled):
    return [line for line in compiled.as_text().splitlines()
            if _MOSAIC_CALL.search(line)]


def _square_arrays(compiled, s):
    """Arrays whose two trailing dimensions are both the sequence."""
    return set(re.findall(rf"\w+\[(?:\d+,)*{s},{s}\]", compiled.as_text()))


def _shapes(one_chip):
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return dict(
        q=sds((B, S, HEADS, D)), k=sds((B, S, KV_HEADS, D)),
        q_i=sds((B, S, INDEX_HEADS, INDEX_DIM)), k_i=sds((B, S, INDEX_DIM)),
        w=sds((B, S, INDEX_HEADS), jnp.float32),
        selected=sds((B, S, S), jnp.int8),
        lse=sds((B, HEADS, S), jnp.float32), lse_i=sds((B, S), jnp.float32))


def test_selection_is_one_mosaic_call_at_the_cells_shape(one_chip):
    x = _shapes(one_chip)
    compiled = jax.jit(lambda q_i, k_i, w: sparse_index.select_keys(
        q_i, k_i, w, TOPK, scale=SCALE)).lower(
            x["q_i"], x["k_i"], x["w"]).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == 1 and scopes.SPARSE_SELECT in calls[0], calls
    # The selection itself, a byte a pair, and no score.
    assert _square_arrays(compiled, S) == {f"s8[{B},{S},{S}]"}


def test_flash_over_a_selection_is_two_calls_with_grouped_heads(one_chip):
    """32 query heads read 4 key-value heads where they are: no K or V of
    32 heads exists, and dk, dv leave a query head at a time.  Heads of 128:
    the calls take rows of tokens, ``[B * S, heads * D]``, in place."""
    x = _shapes(one_chip)

    def grads(q, k, v, selected, weight):
        return jax.grad(lambda q, k, v: jnp.sum(
            fa.flash_attention_selected(q, k, v, selected)[0].astype(
                jnp.float32) * weight), argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(
        x["q"], x["k"], x["k"], x["selected"],
        jax.ShapeDtypeStruct(x["q"].shape, jnp.float32,
                             sharding=one_chip)).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == 2, calls
    assert sum(scopes.FLASH_FWD in c for c in calls) == 1
    assert sum(scopes.FLASH_BWD in c for c in calls) == 1
    assert _square_arrays(compiled, S) == {f"s8[{B},{S},{S}]"}
    forward = next(c for c in calls if scopes.FLASH_FWD in c)
    assert f"bf16[{B * S},{KV_HEADS * D}]" in forward
    assert f"bf16[{B * S},{HEADS * D}]" in forward


def test_index_loss_is_one_mosaic_call_at_the_cells_shape(one_chip):
    x = _shapes(one_chip)

    def loss_and_grads(q, k, lse, q_i, k_i, w, selected, lse_i):
        return jax.value_and_grad(
            lambda q_i, k_i, w: sparse_index.index_loss(
                q, k, lse, q_i, k_i, w, selected, lse_i, sm_scale=D ** -0.5,
                scale=SCALE), argnums=(0, 1, 2))(q_i, k_i, w)

    compiled = jax.jit(loss_and_grads).lower(
        x["q"], x["k"], x["lse"], x["q_i"], x["k_i"], x["w"], x["selected"],
        x["lse_i"]).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == 1 and scopes.SPARSE_INDEX in calls[0], calls
    assert _square_arrays(compiled, S) == {f"s8[{B},{S},{S}]"}


@pytest.mark.parametrize("heads", [HEADS, KV_HEADS], ids=["q", "k"])
def test_norm_and_rotation_are_one_call_a_pass_at_the_cells_shape(one_chip,
                                                                   heads):
    """``ops/rope.py::norm_rotate_pairs`` on ``bf16[2, 8192, heads * 128]``:
    ONE Mosaic call forward and ONE backward (dx and the scale's partial
    sums), no limit stated and the default scoped VMEM not filled, and no
    float32 array of x's size around either."""
    from horovod_tpu.models.llama import rope_freqs

    x = jax.ShapeDtypeStruct((B, S, heads * D), jnp.bfloat16,
                             sharding=one_chip)
    scale = jax.ShapeDtypeStruct((D,), jnp.float32, sharding=one_chip)

    def both(x, scale, g):
        cos, sin = rope_freqs(D, S, 1e7)
        out, vjp = jax.vjp(lambda x, scale: rope.norm_rotate_pairs(
            x, scale, cos, sin, 1e-6), x, scale)
        return out, vjp(g)

    text = jax.jit(both).lower(x, scale, x).compile().as_text()
    calls = [line for line in text.splitlines() if _MOSAIC_CALL.search(line)]
    assert len(calls) == 2 and all(scopes.ROPE in c for c in calls)
    assert max(int(_USED.search(c)[1]) for c in calls) < _DEFAULT_SCOPED_VMEM
    assert "vmem_limit_bytes" not in "".join(calls)
    assert f"f32[{B},{S},{heads * D}]" not in text
    assert f"f32[{B},{S},{heads},{D}]" not in text


def test_the_cells_whole_step_fits_and_holds_no_scores(topo, one_chip):
    """One layer of the published widths (the cell has five, all of one
    kind) at 2 x 8192 tokens: a layer's
    attention is four Mosaic calls (select, flash forward, the indexer's
    loss, flash backward), none of them run again by the recomputing
    backward pass, and six passes that norm and turn (q and k: forward,
    recomputed, backward; under ``hvd.rope`` inside ``hvd.attn.qknorm``),
    around which no float32 array of q's or k's size is left, heads apart
    or together (PR 48; the parent held 75 ``f32[2,8192,32,128]``, 155
    ``f32[2,8192,4096]`` and 30 ``f32[2048,8,32,128]``); the only ``[.., S,
    S]`` array is the int8 selection; and the temporaries are no more than
    they were read at this depth.  That the cell's five layers fit the
    chip's 16 GiB is no longer summed here: the chip's ``peak_hbm_gb`` in
    this cell says it in every PR, and ``tests/benchmark/
    test_benchmark_reference.py::test_whole_step_compiles_for_v5e_and_fits``
    compiles a whole step."""
    cell = manifest.cell(CELL)
    config = {**cell["config"], "num_hidden_layers": LAYERS}
    job = manifest.load_job(config["job"]).build(config, cell["traffic"], 1)
    mesh = Mesh([topo.devices[0]], ("data",))
    replicated = NamedSharding(mesh, P())

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=replicated), tree)

    state = jax.eval_shape(job.init_state, jax.random.key(0))
    batch = jax.eval_shape(job.make_batch, jax.random.key(0))
    step = hvd.make_train_step(job.loss_fn, job.optimizer, mesh)
    compiled = step.lower(*described(state), described(batch)).compile()
    layers = job.llama.num_layers
    assert layers == LAYERS
    calls = _mosaic_calls(compiled)
    for scope in (scopes.SPARSE_SELECT, scopes.FLASH_FWD,
                  scopes.SPARSE_INDEX, scopes.FLASH_BWD):
        assert sum(scope in c for c in calls) == layers, scope
    assert sum(scopes.ROPE in c for c in calls) == 6 * layers
    assert all(scopes.QK_NORM in c for c in calls if scopes.ROPE in c)
    text = compiled.as_text()
    for gone in ("f32[2,8192,32,128]", "f32[2048,8,32,128]",
                 "f32[2,8192,4,128]", "f32[2,8192,4096]"):
        assert gone not in text, gone
    # (What XLA still copies under the scope is bf16: the indexer's loss
    # reads q and k transposed, ``ops/sparse_index.py``'s own.)
    under_norm = [line for line in text.splitlines()
                  if scopes.QK_NORM in line and " = " in line]
    assert under_norm and not [line for line in under_norm
                               if re.search(r"= f32\[2,8192,", line)]
    assert not any(scopes.REMATTED in c for c in calls
                   if scopes.RAGGED_DOT_PREFIX not in c
                   and scopes.ROPE not in c)
    seq = job.seq
    assert _square_arrays(compiled, seq) == {f"s8[2,{seq},{seq}]"}
    memory = compiled.memory_analysis()
    print(f"arguments {memory.argument_size_in_bytes} + "
          f"temporaries {memory.temp_size_in_bytes}")
    # Read at this one layer (all five: 7.872 GB of arguments, and 4.4345 GB
    # of temporaries before the norm joined the pass, no more since).
    assert memory.argument_size_in_bytes == pytest.approx(2.4458e9, rel=1e-3)
    assert memory.temp_size_in_bytes <= 2.4796e9
