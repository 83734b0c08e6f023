"""The ``olmo-hybrid-7b.train-s8k`` cell compiled for a described
``v5e:2x2`` (no chip attached), beside ``tests/test_flash_v5e_compile.py``
and in its manner: the chunkwise gated delta rule at the cell's shape, whose
walks are ``while`` loops over chunks and never over tokens; and the cell's
train step at one layer of each kind with every head of both mixers (that
all four layers fit the chip so, which ISSUE 38 made the condition of
halving the heads, is since PR 51 the chip's ``peak_hbm_gb`` to say), whose
linear layer's convolutions are ``ops/short_conv.py``'s Mosaic calls and
whose chunk systems are solved by ``ops/gated_delta.py``'s (PR 47) and whose
output norm and gate are ``ops/gated_norm.py``'s norm-first pair at heads of
192 lanes (PR 61); and a hybrid model under the GSPMD step over all four
chips, which holds none.  Since
PR 44 also where each weight's optimizer update sits in the compiled step of
this cell and of ``ouro-2.6b.train-s2k``: alone behind its gradient's matmul
for a leaf of ``hvd.ALONE_FROM_ELEMENTS`` elements or more, inside the
matmul's fusion for the others."""

import collections
import contextlib
import math
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
from horovod_tpu.ops import gated_delta
from horovod_tpu.ops import gated_norm
from horovod_tpu.ops import rope
from horovod_tpu.ops import short_conv
from horovod_tpu.ops.gated_delta import CHUNK, gated_delta_rule

CELL = "olmo-hybrid-7b.train-s8k"
OURO = "ouro-2.6b.train-s2k"
_MOSAIC_CALL = re.compile(r' = .*custom_call_target="tpu_custom_call"')
_USED = re.compile(r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"')
_REMAT = re.compile(r"\.remat[\w.]* = ")    # XLA's own rematerialisations
DEFAULT_SCOPED_VMEM = 16 * 2 ** 20
B, S, HEADS, D_K, D_V = 1, 8192, 30, 96, 192
#: The layers the hybrid cell's whole step is compiled at: one of each kind
#: of the cell's period (linear, linear, linear, full).
LAYER_TYPES = ("linear_attention", "full_attention")
# An instruction's result type is all between "= " and the opcode.
_RESULT = re.compile(r" = (.*?)\s[a-z][\w-]*\(")
_F32 = re.compile(r"\bf32\[([0-9,]+)\]")
_KIND = re.compile(r"kind=(k\w+)")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as error:
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")


@contextlib.contextmanager
def _compiling_for_the_chip():
    """The five kernels' non-interpreted bodies, and no persistent cache
    (a deviceless executable cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    patch = pytest.MonkeyPatch()
    for module in (fa, rope, short_conv, gated_delta, gated_norm):
        patch.setattr(module, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        patch.undo()
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture
def one_chip(topo):
    with _compiling_for_the_chip():
        yield SingleDeviceSharding(topo.devices[0])


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
    # Nobody said ``in_place``: XLA operations alone, the six merges.
    assert not _MOSAIC_CALL.search(text)
    memory = compiled.memory_analysis()
    # Inputs, the five gradients, the kept states (bf16) and a slab's
    # preparation: well under 2 GB.
    assert memory.temp_size_in_bytes < 2e9


def _compiled_step(topo, workload, layer_types):
    """The cell's step at the layers ``layer_types`` names (one of each
    kind the cell has; its widths, sequence, batch and remat) compiled for
    one described chip, the job, and what the trace counted: the
    convolutions' bodies, who solved the rule's systems, which body the
    output norm and gate took, and the update's split
    (``hvd.update_counts``)."""
    cell = manifest.cell(workload)
    config = {**cell["config"], "num_hidden_layers": len(layer_types),
              "layer_types": list(layer_types)}
    job = manifest.load_job(config["job"]).build(config, cell["traffic"], 1)
    mesh = Mesh([topo.devices[0]], ("data",))
    replicated = NamedSharding(mesh, P())

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=replicated), tree)

    state = jax.eval_shape(job.init_state, jax.random.key(0))
    assert set(state[0]) == {"params"}
    batch = jax.eval_shape(job.make_batch, jax.random.key(0))
    step = hvd.make_train_step(job.loss_fn, job.optimizer, mesh)
    before = (short_conv.body_counts(), gated_delta.solve_counts(),
              gated_norm.body_counts(), gated_delta.walk_counts())
    compiled = step.lower(*described(state), described(batch)).compile()
    after = (short_conv.body_counts(), gated_delta.solve_counts(),
             gated_norm.body_counts(), gated_delta.walk_counts())
    off_the_tile = gated_delta.HEADS_OFF_THE_TILE
    bodies = {"fused": after[0]["fused"] - before[0]["fused"],
              "plain": after[0]["plain"] == before[0]["plain"],
              "solved": after[1]["mosaic"] - before[1]["mosaic"],
              "merged": after[1]["plain"] != before[1]["plain"],
              "normed": after[2]["mosaic"] - before[2]["mosaic"],
              "normed plain": after[2]["plain"] != before[2]["plain"],
              "walked": after[3]["mosaic"] - before[3]["mosaic"],
              "walked off the tile": after[3]["plain"].get(off_the_tile, 0)
              - before[3]["plain"].get(off_the_tile, 0)}
    return (compiled, job, jax.tree.leaves(state[0]), bodies,
            hvd.update_counts())


def _updates(text, leaves):
    """``{(shape, fusion kind): [op_name, ...]}`` of the fusions that hold a
    weight's optimizer update: their result tuple holds two or more float32
    arrays of a parameter matrix's shape (master, mu, nu beside the bf16
    weight).  ``kOutput`` is a matmul's fusion with the update in its
    epilogue, ``kLoop`` the update alone."""
    shapes = {tuple(leaf.shape) for leaf in leaves if leaf.ndim >= 2}
    found = collections.defaultdict(list)
    for line in text.splitlines():
        result = _RESULT.search(line) if " fusion(" in line else None
        if not result or not result[1].startswith("("):
            continue
        held = collections.Counter(
            tuple(map(int, dims.split(","))) for dims in _F32.findall(
                result[1]))
        for shape, n in held.items():
            if n >= 2 and shape in shapes:
                name = re.search(r'op_name="([^"]*)"', line)
                found[shape, _KIND.search(line)[1]].append(
                    name[1] if name else "")
    return found


def _engaged(leaves):
    """The shapes of the leaves the rule takes, with their number."""
    return collections.Counter(
        tuple(leaf.shape) for leaf in leaves
        if leaf.ndim >= 2 and math.prod(leaf.shape) >= hvd.ALONE_FROM_ELEMENTS
        and jnp.issubdtype(leaf.dtype, jnp.floating))


@pytest.fixture(scope="module")
def hybrid_step(topo):
    """The hybrid cell's step, compiled once for this module's two tests
    of it (~1 min)."""
    with _compiling_for_the_chip():
        yield _compiled_step(topo, CELL, LAYER_TYPES)


def test_the_cells_whole_step_fits_with_every_head(hybrid_step):
    """One linear and one softmax layer of the published widths at
    1 x 8192 tokens, all 30 heads of both mixers.  The softmax layer is two
    flash calls (its forward call is not run again: the policy keeps its
    output).  A linear layer is nine Mosaic calls under ``hvd.gdn.conv``:
    q's, k's and v's convolution forward, again under recomputation, and
    backward, and no float32 array of an activation's shape is left under
    that scope; and since PR 47 three under ``hvd.gdn.solve`` inside
    ``hvd.gdn.scan``, the slab's systems forward, again, and in the
    backward slab's ``jax.vjp`` of its preparation, each inside the default
    scoped VMEM; and since PR 61 three under ``hvd.gdn.gates``, the output
    norm and gate forward, again, and backward (``gated_norm.norm_gate``:
    ONE trace a layer took the Mosaic pass, none the ``jnp`` body), and no
    float32 array of the activations' shape is left under that scope.
    XLA's own rematerialisation pass still runs one gate-up
    product a third time (PERF.md, Open question 39): one ``.remat``
    instruction, as at the parent."""
    compiled, job, _, bodies, _ = hybrid_step
    config = manifest.cell(CELL)["config"]
    assert config["num_attention_heads"] == 30
    assert config["linear_num_value_heads"] == 30
    # The trace took the pass for q, k and v of each linear layer, and the
    # plain body for none.
    linear = sum(map(job.llama.is_linear, range(job.llama.num_layers)))
    assert bodies == {"fused": 3 * linear, "plain": True, "solved": linear,
                      "merged": False, "normed": linear,
                      "normed plain": False, "walked": 0,
                      "walked off the tile": linear} and linear == 1
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if _MOSAIC_CALL.search(line)]
    assert sum(scopes.FLASH_FWD in c for c in calls) == 1
    assert sum(scopes.FLASH_BWD in c for c in calls) == 1
    assert not any(scopes.ROPE in c for c in calls)
    solves = [c for c in calls if scopes.GDN_SOLVE in c]
    assert len(solves) == 3 * linear
    assert all(scopes.GDN_SCAN in c for c in solves)
    assert sum(scopes.REMATTED in c for c in solves) == linear
    used = [int(_USED.search(c)[1]) for c in solves]
    assert max(used) <= DEFAULT_SCOPED_VMEM // 2, used
    # Heads of 96 and 192 lanes are no lane tiles: the chunks by the ``jnp``
    # walk, counted under its reason (PR 64), its ``while``s under the scope
    # and no call there but the solves.
    assert not any(scopes.GDN_SCAN in c for c in calls if c not in solves)
    assert [line for line in text.splitlines()
            if scopes.GDN_SCAN in line and " while(" in line]
    gates = [c for c in calls if scopes.GDN_GATES in c]
    assert len(gates) == 3 * linear
    assert sum(scopes.REMATTED in c for c in gates) == linear
    # (The backward call: do, dz and dw's partial sums.)
    assert sum(" = (" in c for c in gates) == linear
    convolutions = [c for c in calls if scopes.GDN_CONV in c]
    assert len(convolutions) == (len(calls) - len(solves) - len(gates) - 2
                                 ) == 9 * linear
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
    assert not [line for line in text.splitlines()
                if scopes.GDN_GATES in line
                and f" = f32[1,{seq},5760]" in line]
    assert not re.findall(rf"\w+\[(?:\d+,)*{seq},{seq}\]", text)
    remats = [line.split(" = ")[0].strip() for line in text.splitlines()
              if _REMAT.search(line)]
    print(f"XLA's rematerialisations: {len(remats)} (the parent's: 1) "
          f"{remats}")
    assert len(remats) <= 1, remats
    memory = compiled.memory_analysis()
    print(f"\narguments {memory.argument_size_in_bytes} + "
          f"temporaries {memory.temp_size_in_bytes}")
    # Read at these two layers (all four: 13.005 GB and 3.0783 GB, 3.0946
    # before the solve's call).  That the cell's four fit the chip is no
    # longer summed here: the chip's ``peak_hbm_gb`` in this cell says it in
    # every PR, and ``tests/benchmark/test_benchmark_reference.py::
    # test_whole_step_compiles_for_v5e_and_fits`` compiles a whole step.
    assert memory.argument_size_in_bytes == pytest.approx(6.9684e9, rel=1e-3)
    assert memory.temp_size_in_bytes <= 2.536e9


def test_the_cells_large_weights_are_updated_alone(hybrid_step):
    """``w_gate_up`` (84.5M) and ``w_down`` (42.3M) of each layer, the
    head and the embedding (48.2M each) pass ``DistributedOptimizer``'s
    barrier: no matmul fusion (``kOutput``) holds the float32 master and
    moments of such a shape, one loop fusion each does, named by what
    ``optimizer_ms`` reads (its root is ``optax.apply_updates``' add, so
    ``hvd.apply``; the moments inside it are ``hvd.optimizer``'s).  The
    projections of 11-22M (five a linear layer, four a softmax one) and a
    linear layer's two ``[3840, 30]`` gates stay in their matmuls.  At the
    cell's four layers the gradients' longer lives cost 0.11 GB of
    temporaries (2.987 GB with no leaf engaged; ledger, PR 43)."""
    compiled, _, leaves, _, counts = hybrid_step
    engaged = _engaged(leaves)
    layers = len(LAYER_TYPES)
    linear = LAYER_TYPES.count("linear_attention")
    assert engaged == {(3840, 22016): layers, (11008, 3840): layers,
                       (3840, 12544): 1, (12544, 3840): 1}
    alone = 2 * layers + 2
    assert counts == {"alone": alone, "fused": len(leaves) - alone}
    updates = _updates(compiled.as_text(), leaves)
    for shape, n in engaged.items():
        assert (shape, "kOutput") not in updates, shape
        alone = updates[shape, "kLoop"]
        assert len(alone) == n, (shape, alone)
        for name in alone:
            assert scopes.OPTIMIZER in name or scopes.APPLY in name, name
    inside = {shape: len(names) for (shape, kind), names in updates.items()
              if kind == "kOutput"}
    assert inside == {(3840, 2880): 2 * linear, (3840, 5760): 2 * linear,
                      (3840, 3840): 4 * (layers - linear),
                      (5760, 3840): linear, (3840, 30): 2 * linear}
    # 2.5358 GB at these two layers (all four: 3.0946, under 3.15).
    assert compiled.memory_analysis().temp_size_in_bytes <= 2.58e9


def test_ouro_s2k_keeps_every_update_in_its_matmul_but_the_heads(
        topo, one_chip):
    """``ouro-2.6b.train-s2k`` at one of its nine layers (they are of one
    kind): the head's ``[2048, 49152]`` (100.7M) leaves its matmul, a
    layer's six weight matrices (23.1M, 11.5M and 4.2M, which cost their
    parts fused: PERF.md §5) stay; the embedding's update was alone before.
    The four-chip step has no fused update at all:
    ``tests/test_gradient_allreduce_v5e_compile.py``."""
    compiled, _, leaves, _, counts = _compiled_step(
        topo, OURO, ["full_attention"])
    assert _engaged(leaves) == {(2048, 49152): 1, (49152, 2048): 1}
    layers = 1
    assert len(leaves) == 8 * layers + 3
    assert counts == {"alone": 2, "fused": len(leaves) - 2}
    updates = {key: len(names) for key, names in _updates(
        compiled.as_text(), leaves).items()}
    assert updates == {((2048, 2048), "kOutput"): 4 * layers,
                       ((2048, 11264), "kOutput"): layers,
                       ((5632, 2048), "kOutput"): layers,
                       ((2048, 49152), "kLoop"): 1,
                       ((49152, 2048), "kLoop"): 1}
    memory = compiled.memory_analysis()
    print(f"\narguments {memory.argument_size_in_bytes} + "
          f"temporaries {memory.temp_size_in_bytes}")
    # 1.5570 GB at one layer (all nine: 4.624 GB with the head's update
    # fused, under 4.63; ledger, PR 43).
    assert memory.temp_size_in_bytes <= 1.559e9


def test_gspmd_step_of_a_hybrid_model_holds_no_mosaic_call(one_chip, topo):
    """``parallel/api.py::make_parallel_train_step`` over all four chips
    with a hybrid model and its default dense attention, at a sequence the
    convolutions' pass would take: a Mosaic call cannot be partitioned
    automatically, so the linear layers' convolutions and their output norm
    and gate are the ``jnp`` bodies unless the ``attention_fn`` the model
    was given reads its operands in place (``tests/test_flash_v5e_compile.py`` has the same for the
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
    # (A shape the convolutions' pass takes where it is asked to.)
    taken = short_conv.body_counts()["fused"]
    jax.eval_shape(
        lambda y, taps: short_conv.convolved(y, taps, 2, 1.0, True),
        jax.ShapeDtypeStruct((8, 64, 2 * 96), jnp.bfloat16),
        jax.ShapeDtypeStruct((4, 2 * 96), jnp.float32))
    assert short_conv.body_counts()["fused"] == taken + 1
    normed = gated_norm.body_counts()["mosaic"]
    jax.eval_shape(
        lambda o, z, w: gated_norm.norm_gated(o, z, w, 2, 1e-6, True),
        *(jax.ShapeDtypeStruct((8, 64, 2 * 192), jnp.bfloat16),) * 2,
        jax.ShapeDtypeStruct((192,), jnp.float32))
    assert gated_norm.body_counts()["mosaic"] == normed + 1
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
    gates = gated_norm.body_counts()
    step = make_parallel_train_step(model, optimizer, mesh)
    compiled = step.lower(params, state, tokens).compile()
    after = short_conv.body_counts()
    assert not _MOSAIC_CALL.search(compiled.as_text())
    assert gated_norm.body_counts() == {"mosaic": gates["mosaic"], "plain": {
        **gates["plain"], gated_norm.NOT_IN_PLACE: gates["plain"].get(
            gated_norm.NOT_IN_PLACE, 0) + 1}}
    assert "all-reduce" in compiled.as_text()
    assert after["fused"] == before["fused"]
    assert after["plain"][short_conv.NOT_IN_PLACE] == before["plain"].get(
        short_conv.NOT_IN_PLACE, 0) + 3
