"""What the chip's compiler makes of gradients that are all-reduced leaf by
leaf: the four-chip decoder step of the benchmark and ResNet-50's gradient
tree, compiled at full size for a described ``v5e:2x2`` (no chip attached).
The program packs nothing, so the few all-reduces counted here are the
work of XLA's all-reduce combiner: what the deletion of the program's own
packer rests on (PERF.md §6, PR 30).  The TPU compiler is loaded inside a
fixture (the on-chip-measurement guide says why); the recipe is
``tests/benchmark/test_benchmark_reference.py``'s."""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.common import scopes

DECODER = "ouro-2.6b.train-s2k-dp4"
RESNET = "resnet50-v1.5.train-b256"
#: The depth the four-chip step is compiled at (the cell has nine layers of
#: this one kind; what nine hold on the chip is the cell's ``peak_hbm_gb``).
LAYERS = 1
#: ``temp_size_in_bytes`` of that step as PR 51 read it at this depth; with
#: all nine layers it was under the 4,937,175,552 of the step that packed
#: every leaf (ledger, PR 24: ``hbm_temporaries_gb`` 4.9372).
TEMPORARIES = 1_588_023_808
#: A leaf of this many bytes or more must reach its all-reduce as it is.
LARGE = 1024 * 1024

# The result's type is all between "= " and the opcode; a tuple's layouts
# hold brackets of their own, "{1,0:T(8,128)(2,1)}".
_RESULT = re.compile(r" = (.*?)\s[a-z][\w-]*\(")
_ARRAY = re.compile(r"\b(pred|[a-z]+([0-9]+)[a-z0-9]*)\[([0-9,]*)\]")
_ALL_REDUCE = re.compile(r" all-reduce(-start)?\(")


def _result_arrays(line):
    """(shape, bytes) of every array in an HLO instruction's result type,
    which is one array or a tuple of them."""
    arrays = []
    for _, bits, dims in _ARRAY.findall(_RESULT.search(line)[1]):
        shape = tuple(int(d) for d in dims.split(",") if d)
        arrays.append((shape, math.prod(shape) * int(bits or 8) // 8))
    return arrays


def _instructions(compiled):
    return [line for line in compiled.as_text().splitlines() if " = " in line]


def _all_reduces(lines):
    return [line for line in lines if _ALL_REDUCE.search(line)]


def test_result_arrays_reads_plain_and_tuple_types():
    plain = ("  %slice.1 = bf16[2048]{0:T(1024)(128)(2,1)} slice(%p), "
             "slice={[0:2048]}")
    both = ("  %all-reduce.7 = (bf16[2048,2048]{1,0:T(8,128)(2,1)}, "
            "/*index=1*/f32[]{:T(128)}) all-reduce(%a, %b), channel_id=1")
    assert _result_arrays(plain) == [((2048,), 4096)]
    assert _result_arrays(both) == [((2048, 2048), 8388608), ((), 4)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as error:
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")


@pytest.fixture(scope="module")
def four_chips(topo):
    """A ``data=4`` mesh of described chips.  Kernels take their
    non-interpreted path, and nothing is read from or written to a
    persistent cache (a deviceless executable cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    from horovod_tpu.ops import flash_attention, rope

    patch = pytest.MonkeyPatch()
    patch.setattr(flash_attention, "_interpret", lambda: False)
    patch.setattr(rope, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield Mesh(np.array(topo.devices[:4]), ("data",))
    patch.undo()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _job(workload, **changes):
    """The cell's job, built for four chips whatever the cell has, with
    ``changes`` to its configuration."""
    from benchmark import manifest

    cell = manifest.cell(workload)
    config = {**cell["config"], **changes}
    return manifest.load_job(config["job"]).build(config, cell["traffic"], 4)


def _placed(mesh, tree, spec):
    sharding = NamedSharding(mesh, spec)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


@pytest.fixture(scope="module")
def decoder_step(four_chips):
    """(compiled step, parameter shapes, how the traced update split its
    leaves) of the benchmark's four-chip cell at ``LAYERS`` of its nine
    layers, which are of one kind: its widths, tokens a chip and mesh."""
    import horovod_tpu.jax as hvd

    job = _job(DECODER, num_hidden_layers=LAYERS,
               layer_types=["full_attention"] * LAYERS)

    def make(seed):
        k_state, k_batch = jax.random.split(jax.random.key(seed))
        return job.init_state(k_state), job.make_batch(k_batch)

    state, batch = jax.eval_shape(make, jnp.uint32(0))
    step = hvd.make_train_step(job.loss_fn, job.optimizer, four_chips,
                               has_aux=job.has_aux)
    compiled = step.lower(*_placed(four_chips, state, P()),
                          _placed(four_chips, batch, P("data"))).compile()
    return compiled, jax.tree.leaves(state[0]), hvd.update_counts()


def test_four_chip_step_reduces_large_gradients_in_place(decoder_step):
    compiled, params, _ = decoder_step
    lines = _instructions(compiled)
    leaf_shapes = {tuple(leaf.shape) for leaf in params}

    packing = [line for line in lines
               if scopes.FUSION_PACK in line or scopes.FUSION_UNPACK in line]
    assert not packing, packing[:3]

    large = [(shape, line) for line in _all_reduces(lines)
             for shape, nbytes in _result_arrays(line) if nbytes >= LARGE]
    assert len(large) >= sum(
        math.prod(s.shape) * s.dtype.itemsize >= LARGE for s in params)
    for shape, line in large:
        assert shape in leaf_shapes, (shape, line[:200])

    memory = compiled.memory_analysis()
    print(f"arguments {memory.argument_size_in_bytes} + "
          f"temporaries {memory.temp_size_in_bytes}")
    assert memory.temp_size_in_bytes <= TEMPORARIES


def test_four_chip_step_leaves_the_batching_to_xlas_combiner(decoder_step):
    """Eight gradient leaves a layer, three more and the loss enter XLA as
    an all-reduce each and leave its combiner as fewer: 12 as 4 at this
    one layer (all nine layers: 76 as 11, the same 11 as with the 19 norm
    scales packed into one buffer: builder's compiles of both trees,
    PR 30), no norm scale alone."""
    compiled, params, _ = decoder_step
    reduces = _all_reduces(_instructions(compiled))
    assert len(params) == 8 * LAYERS + 3
    print(f"all-reduces {len(reduces)}")
    assert 3 <= len(reduces) <= 5, len(reduces)

    scales = [s for s in params if s.ndim == 1]
    assert len(scales) == 2 * LAYERS + 1
    assert {s.shape for s in scales} == {(2048,)}
    carrying = [line for line in reduces
                if ((2048,), 4096) in _result_arrays(line)]
    assert carrying
    for line in carrying:                     # never an all-reduce to itself
        assert len(_result_arrays(line)) > 1, line[:200]
    assert sum(_result_arrays(line).count(((2048,), 4096))
               for line in carrying) == len(scales)


def test_four_chip_step_holds_no_update_inside_a_matmul(decoder_step):
    """An all-reduce sits between a gradient and its update, so no matmul
    fusion (``kOutput``) holds the float32 master and moments of a weight's
    shape, with or without ``DistributedOptimizer``'s barrier (PR 44),
    which stands ahead of the all-reduce on the two leaves of 100.7M and
    changes nothing of the compiled step there."""
    compiled, params, counts = decoder_step
    matrices = {tuple(leaf.shape) for leaf in params if leaf.ndim >= 2}
    for line in _instructions(compiled):
        if " fusion(" in line and "kind=kOutput" in line:
            float32 = [shape for shape, nbytes in _result_arrays(line)
                       if shape in matrices and nbytes == 4 * math.prod(shape)]
            assert len(float32) < 2, line[:300]
    assert counts == {"alone": 2, "fused": len(params) - 2}


def test_resnet50_gradient_tree_needs_no_packer(four_chips):
    """161 leaves, 132 of them under 1 MiB: what tensor fusion was invented
    for.  Reduced leaf by leaf, XLA's combiner leaves one or two
    all-reduces and copies nothing."""
    import horovod_tpu.jax as hvd

    job = _job(RESNET)
    params = jax.eval_shape(
        lambda seed: job.init_state(jax.random.key(seed))[0], jnp.uint32(0))
    leaves = jax.tree.leaves(params)
    nbytes = [math.prod(s.shape) * s.dtype.itemsize for s in leaves]
    assert len(leaves) == 161 and sum(n < LARGE for n in nbytes) == 132

    reduce = jax.jit(jax.shard_map(
        lambda grads: hvd.allreduce_gradients(grads, axis_name="data"),
        mesh=four_chips, in_specs=P(), out_specs=P(), check_vma=False))
    compiled = reduce.lower(_placed(four_chips, params, P())).compile()
    lines = _instructions(compiled)

    reduces = _all_reduces(lines)
    assert 1 <= len(reduces) <= 3, len(reduces)
    reduced = [array for line in reduces for array in _result_arrays(line)]
    assert sorted(reduced) == sorted(
        (tuple(s.shape), n) for s, n in zip(leaves, nbytes))
    assert not [line for line in lines if " concatenate(" in line]
