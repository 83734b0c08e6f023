"""Both plain references against the program at tiny sizes on the CPU, and
deviceless ``v5e:2x2`` compiles at full size of the flash kernel and of one
whole train step.  The TPU compiler is loaded by this file alone, inside a
fixture: see the on-chip-measurement guide."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark import compare, manifest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_sizes import TINY  # noqa: E402

MANIFEST = manifest.load()
ONE_CELL_PER_CONFIG = list({w["config"]: w["name"]
                            for w in MANIFEST["workloads"]
                            if w["chips"] == 1}.values())


def _tiny_job(workload, chips=1):
    cell = manifest.cell(workload)
    tiny = TINY[cell["config"]["job"]]
    config = {**cell["config"], **tiny["config"]}
    traffic = {**cell["traffic"], **tiny["traffic"]}
    job = manifest.load_job(config["job"]).build(config, traffic, chips)
    return job, manifest.load_reference(config["reference"]), config


def _perturbed(tree, key):
    """Zero-initialised scales moved off zero, so that every path is live
    and every gradient is compared."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


@pytest.mark.parametrize("workload", ONE_CELL_PER_CONFIG)
def test_reference_agrees_with_the_program_in_float32(workload):
    """fp32 parameters through the program's model at fp32 compute: the
    same mathematics twice, so loss and every gradient leaf agree to
    rounding.  (bf16 compute against the reference is what every tiny run
    of test_benchmark_cells.py checks, through benchmark/compare.py.)"""
    job, reference, config = _tiny_job(workload)
    state = jax.jit(job.init_state)(jax.random.key(0))
    params = jax.tree.map(lambda p: p.astype(jnp.float32),
                          _perturbed(state[0], jax.random.key(1)))
    if hasattr(job, "llama"):
        import dataclasses

        from horovod_tpu.models import LlamaModel
        from horovod_tpu.ops.flash_attention import flash_attention_fn
        job.model = LlamaModel(
            dataclasses.replace(job.llama, dtype=jnp.float32,
                                logits_dtype=jnp.float32),
            attention_fn=flash_attention_fn)
    else:
        job.model = job.model.clone(dtype=jnp.float32)
    sample = job.make_batch(jax.random.key(2), job.sample_rows)

    @jax.jit
    def both(params, aux, sample):
        with jax.default_matmul_precision("highest"):
            if job.has_aux:
                (loss, _), grads = jax.value_and_grad(
                    job.loss_fn, has_aux=True)(params, aux, sample)
            else:
                loss, grads = jax.value_and_grad(job.loss_fn)(params, sample)
        return loss, grads, reference.loss_and_grads(
            job.to_reference(params), sample, config)

    loss, grads, (ref_loss, ref_grads) = both(
        params, state[2] if job.has_aux else (), sample)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-5)
    mapped = job.to_reference(grads)
    assert jax.tree.structure(mapped) == jax.tree.structure(ref_grads)
    for got, want in zip(jax.tree.leaves(mapped), jax.tree.leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0
        np.testing.assert_allclose(got, want, atol=2e-3 * scale)


def test_comparison_fails_on_a_sum_where_a_mean_belongs(monkeypatch):
    """Four virtual chips: gradients summed over the data axis, and not
    averaged, are four times the reference's and must not pass."""
    workload = next(w["name"] for w in MANIFEST["workloads"]
                    if w["chips"] == 4)
    job, reference, config = _tiny_job(workload, chips=4)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    state = jax.jit(job.init_state, out_shardings=NamedSharding(mesh, P()))(
        jax.random.key(0))
    sample = jax.device_put(
        job.make_batch(jax.random.key(2), job.sample_rows),
        NamedSharding(mesh, P("data")))
    good = compare.against_reference(job, reference, config, mesh, state,
                                     sample)
    assert good["reference_loss_close"] and good["reference_grad_close"]

    import horovod_tpu.jax as hvd
    averaged = hvd.allreduce_gradients
    monkeypatch.setattr(
        compare.hvd, "allreduce_gradients",
        lambda grads, **kw: averaged(grads, op=hvd.Sum, **kw))
    bad = compare.against_reference(job, reference, config, mesh, state,
                                    sample)
    assert not bad["reference_grad_close"]
    assert bad["grad_rel_err"] == pytest.approx(3.0, rel=0.1)


# -- deviceless compiles for the real chip, at full size ---------------------

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
    """Kernels take their non-interpreted path, and nothing is read from
    or written to a persistent cache (a deviceless executable cannot be
    read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    from horovod_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _decoder_cells():
    return [w["name"] for w in MANIFEST["workloads"]
            if w["chips"] == 1 and "sequence" in manifest.cell(
                w["name"])["traffic"]]


MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def _compiled_attention(topo, cell, attention) -> str:
    """The compiled text of one forward + backward of ``attention`` at the
    cell's shape, one chip's rows."""
    from jax.sharding import SingleDeviceSharding

    config, traffic = cell["config"], cell["traffic"]
    shape = jax.ShapeDtypeStruct(
        (traffic["batch_per_chip"], traffic["sequence"],
         config["num_attention_heads"], config["head_dim"]),
        jnp.bfloat16, sharding=SingleDeviceSharding(topo.devices[0]))

    def forward_and_backward(q, k, v):
        out, vjp = jax.vjp(attention, q, k, v)
        return out, vjp(out)

    return jax.jit(forward_and_backward).lower(
        shape, shape, shape).compile().as_text()


def _square_arrays(text: str, sequence: int) -> list:
    """Arrays whose two trailing dimensions are both ``sequence``: the
    scores or probabilities of attention that did not go through the
    kernel."""
    return re.findall(rf"\w+\[(?:\d+,)*{sequence},{sequence}\]", text)


@pytest.mark.parametrize("workload", _decoder_cells())
def test_flash_kernel_compiles_for_v5e_at_the_cells_shape(compiled_for_tpu,
                                                          workload):
    """What every correct implementation has, however it splits its work:
    a forward and a backward pass took the kernel path, and nothing built
    an ``[S, S]`` array."""
    from horovod_tpu.ops.flash_attention import flash_attention

    cell = manifest.cell(workload)
    text = _compiled_attention(compiled_for_tpu, cell, flash_attention)
    assert text.count(MOSAIC_CALL) >= 2
    assert _square_arrays(text, cell["traffic"]["sequence"]) == []


def test_dense_attention_at_the_cells_shape_would_be_caught(
        compiled_for_tpu):
    """The control of the test above: the model's own dense attention, at
    the shortest decoder cell's shape, is no Mosaic call and builds the
    ``[S, S]`` scores."""
    from horovod_tpu.models.llama import causal_attention

    cell = min((manifest.cell(name) for name in _decoder_cells()),
               key=lambda c: c["traffic"]["sequence"])
    text = _compiled_attention(compiled_for_tpu, cell, causal_attention)
    assert text.count(MOSAIC_CALL) == 0
    assert _square_arrays(text, cell["traffic"]["sequence"])


def test_whole_step_compiles_for_v5e_and_fits(compiled_for_tpu):
    """The first cell's train step at full size: every layer takes the
    kernel path (as many Mosaic calls a layer as one forward + backward of
    ``flash_attention`` at that shape compiles to, however many that is),
    and arguments + temporaries fit inside one chip's memory."""
    import horovod_tpu.jax as hvd
    from horovod_tpu.ops.flash_attention import flash_attention

    cell = manifest.cell(MANIFEST["workloads"][0]["name"])
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1
    mesh = Mesh(np.array(compiled_for_tpu.devices[:1]), ("data",))
    job = manifest.load_job(config["job"]).build(config, traffic, 1)

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
    memory = compiled.memory_analysis()
    calls_a_layer = _compiled_attention(
        compiled_for_tpu, cell, flash_attention).count(MOSAIC_CALL)
    assert calls_a_layer >= 2
    assert compiled.as_text().count(MOSAIC_CALL) == (
        config["num_hidden_layers"] * calls_a_layer)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < manifest.peaks("TPU v5 lite")["hbm_bytes"])
    assert memory.argument_size_in_bytes > 0.25 * 16e9


def _leaky(q, k, v):
    from horovod_tpu.models.llama import causal_attention
    return causal_attention(q, k, v, q_offset=1)      # sees one token ahead


def _dropped_block(q, k, v):
    from horovod_tpu.models.llama import causal_attention
    return causal_attention(q, k.at[:, 32:64].set(0), v.at[:, 32:64].set(0))


@pytest.mark.parametrize("defect", [_leaky, _dropped_block])
def test_comparison_fails_on_a_broken_attention(defect):
    """A wrong causal edge and a dropped block of keys move the gradient
    by tens of per cent, where bf16 rounding moves it by 1.5."""
    from horovod_tpu.models import LlamaModel

    workload = next(w for w in ONE_CELL_PER_CONFIG if hasattr(
        _tiny_job(w)[0], "llama"))
    job, reference, config = _tiny_job(workload)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    state = jax.jit(job.init_state)(jax.random.key(0))
    sample = job.make_batch(jax.random.key(2), job.sample_rows)
    job.model = LlamaModel(job.llama, attention_fn=defect)
    found = compare.against_reference(job, reference, config, mesh, state,
                                      sample)
    assert found["grad_rel_err"] > 0.2 and not found["reference_grad_close"]
