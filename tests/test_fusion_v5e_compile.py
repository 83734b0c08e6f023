"""The four-chip decoder step of the benchmark, compiled at full size for a
described ``v5e:2x2`` (no chip attached): what the size-aware fusion plan
leaves of the packing in the scheduled program.  The TPU compiler is loaded
inside a fixture (the on-chip-measurement guide says why); the recipe is
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
from horovod_tpu.ops import fusion

WORKLOAD = "ouro-2.6b.train-s2k-dp4"
#: ``temp_size_in_bytes`` of this step with every leaf packed (ledger, PR 24:
#: ``hbm_temporaries_gb`` 4.9372).
PARENT_TEMPORARIES = 4_937_175_552

# The result's type is all between "= " and the opcode; a tuple's layouts
# hold brackets of their own, "{1,0:T(8,128)(2,1)}".
_RESULT = re.compile(r" = (.*?)\s[a-z][\w-]*\(")
_ARRAY = re.compile(r"\b(pred|[a-z]+([0-9]+)[a-z0-9]*)\[([0-9,]*)\]")


def _result_arrays(line):
    """(shape, bytes) of every array in an HLO instruction's result type,
    which is one array or a tuple of them."""
    arrays = []
    for _, bits, dims in _ARRAY.findall(_RESULT.search(line)[1]):
        shape = tuple(int(d) for d in dims.split(",") if d)
        arrays.append((shape, math.prod(shape) * int(bits or 8) // 8))
    return arrays


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


@pytest.fixture
def compiled_for_tpu(topo, monkeypatch):
    """Kernels take their non-interpreted path, and nothing is read from or
    written to a persistent cache (a deviceless executable cannot be read
    back)."""
    from jax.experimental.compilation_cache import compilation_cache

    from horovod_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_four_chip_step_reduces_large_gradients_in_place(compiled_for_tpu):
    import horovod_tpu.jax as hvd
    from benchmark import manifest

    cell = manifest.cell(WORKLOAD)
    config, traffic, chips = cell["config"], cell["traffic"], cell["chips"]
    assert chips == 4
    mesh = Mesh(np.array(compiled_for_tpu.devices[:chips]), ("data",))
    job = manifest.load_job(config["job"]).build(config, traffic, chips)

    def make(seed):
        k_state, k_batch = jax.random.split(jax.random.key(seed))
        return job.init_state(k_state), job.make_batch(k_batch)

    def placed(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    state, batch = jax.eval_shape(make, jnp.uint32(0))
    step = hvd.make_train_step(job.loss_fn, job.optimizer, mesh,
                               has_aux=job.has_aux)
    compiled = step.lower(*placed(state, P()),
                          placed(batch, P("data"))).compile()
    lines = [line for line in compiled.as_text().splitlines()
             if " = " in line]
    cutoff = fusion.IN_PLACE_CUTOFF_BYTES
    leaf_shapes = {tuple(leaf.shape) for leaf in jax.tree.leaves(state[0])}

    packing = [line for line in lines
               if scopes.FUSION_PACK in line or scopes.FUSION_UNPACK in line]
    assert packing, "the norm scales are still packed"
    for line in packing:
        assert all(nbytes < cutoff for _, nbytes in _result_arrays(line)), line

    reduces = [line for line in lines if re.search(
        r" all-reduce(-start)?\(", line)]
    assert reduces
    large = [(shape, line) for line in reduces
             for shape, nbytes in _result_arrays(line) if nbytes >= cutoff]
    assert len(large) >= sum(
        math.prod(s.shape) * s.dtype.itemsize >= cutoff
        for s in jax.tree.leaves(state[0]))
    for shape, line in large:
        assert shape in leaf_shapes, (shape, line[:200])

    assert compiled.memory_analysis().temp_size_in_bytes <= PARENT_TEMPORARIES
