"""The ``kimi-linear-48b-a3b.train-s8k-b2`` cell's new mechanism compiled for
a described ``v5e:2x2`` (no chip attached), beside
``tests/test_xing4_v5e_compile.py`` and in its manner: ONE Kimi Delta
Attention layer forward and backward at the cell's shapes (2 x 8,192 tokens,
hidden 2304, 32 heads of 128 | 128 behind 4 taps, chunks of 64), in place: the
filters', the solve's, the walk's and the output norm's Mosaic calls under
the three scopes, and the scoped VMEM the compiler reports for each new call
(neither states a limit).  That the cell's depth fits the chip is the
chip's to say (``peak_hbm_gb``, every PR); deviceless at the cell's five
layers and 8 held experts the step reads 8.43 GB of arguments (602,433,408
parameters at 14 bytes) and 4.78 GB of temporaries under
``layer_keep_attention`` (PR 69; two minutes of compiling)."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import manifest
from horovod_tpu.common import scopes
from horovod_tpu.models.llama import KimiDeltaAttention
from horovod_tpu.ops import (flash_attention as fa, gated_delta, gated_norm,
                             kda, short_conv)

CELL = "kimi-linear-48b-a3b.train-s8k-b2"
_MOSAIC_CALL = re.compile(r' = .*custom_call_target="tpu_custom_call"')
BATCH, SEQ, HIDDEN = 2, 8192, 2304
_DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024


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

    for module in (fa, gated_delta, gated_norm, short_conv):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _calls(text):
    return [line for line in text.splitlines() if _MOSAIC_CALL.search(line)]


def _scoped_vmem(calls, which="used_scoped_memory_configs"):
    return [int(n) for call in calls for n in re.findall(
        rf'"{which}":\[\{{[^}}]*"size":"(\d+)"', call)]


def test_one_kda_layer_compiles_forward_and_backward_at_the_cells_shapes(
        one_chip):
    """The mixer alone, its parameters and x as arguments, every gradient
    taken.  Under ``hvd.kda.scan``, inside the slabs' loops: the systems'
    call (forward, and again as the backward slab's preparation) and its
    transpose, the solve's (``hvd.gdn.solve`` nests inside: likewise twice)
    and the walk's two; under ``hvd.kda.conv`` the three filters'
    calls each way; under ``hvd.kda.gates`` the output norm's pair.  The
    walk's calls state no limit."""
    cell = manifest.cell(CELL)
    job = manifest.load_job(cell["config"]["job"]).build(
        cell["config"], cell["traffic"], 1)
    module = KimiDeltaAttention(job.llama, in_place=True)
    x = jax.ShapeDtypeStruct((BATCH, SEQ, HIDDEN), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.eval_shape(
        lambda: KimiDeltaAttention(job.llama).init(
            jax.random.key(0), jnp.zeros((1, 64, HIDDEN), jnp.bfloat16)))
    params = jax.tree.map(lambda p: jax.ShapeDtypeStruct(
        p.shape, jnp.bfloat16 if p.ndim == 2 and p.shape[0] > 4
        else p.dtype, sharding=one_chip), params)
    before = kda.walk_counts()["mosaic"], kda.solve_counts()["mosaic"]

    def grads(params, x):
        return jax.grad(lambda params, x: jnp.sum(
            module.apply(params, x).astype(jnp.float32)),
            argnums=(0, 1))(params, x)

    compiled = jax.jit(grads).lower(params, x).compile()
    assert (kda.walk_counts()["mosaic"], kda.solve_counts()["mosaic"]) == (
        before[0] + 1, before[1] + 1)
    text = compiled.as_text()
    calls = _calls(text)
    scan = [c for c in calls if scopes.KDA_SCAN in c]
    walk = [c for c in scan if "jit(_walk_call)" in c]
    back = [c for c in scan if "jit(_walk_back_call)" in c]
    systems = [c for c in scan if "jit(_systems_forward)" in c]
    transposed = [c for c in scan if "jit(_systems_backward)" in c]
    solve = [c for c in scan if scopes.GDN_SOLVE in c]
    assert len(walk) == len(back) == len(transposed) == 1
    assert len(systems) == len(solve) == 2 and len(scan) == 7
    assert len([c for c in calls if scopes.KDA_CONV in c]) == 6
    assert len([c for c in calls if scopes.KDA_GATES in c]) == 2
    # None of the new calls states a limit: each is compiled under the
    # compiler's default, and the compiler reports 19 to 23 MB of scoped
    # memory for them, as it reports 27.3 MB for the accepted solve beside
    # them under the same default (PR 69, my compile).
    for which in (walk, back, systems, transposed):
        assert set(_scoped_vmem(which, "scoped_memory_configs")) == {
            _DEFAULT_SCOPED_VMEM}
        assert max(_scoped_vmem(which)) < max(_scoped_vmem(solve))
    memory = compiled.memory_analysis()
    # The layer's 39.5 M parameters and x twice (bf16), and what one KDA
    # layer holds between its passes when nothing recomputes it.
    assert memory.argument_size_in_bytes < 0.2e9
    assert memory.temp_size_in_bytes < 4.0e9
