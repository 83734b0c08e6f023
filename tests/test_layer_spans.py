"""A tiny routed decoder, a tiny hybrid one and the tiny ResNet traced through
``hvd.make_train_step``: one ``layer.<kind>`` span a layer call, of the kind
the layer's ``LayerSpec`` says, the block spans inside it, the backward rules
behind ``hvd.loss``'s ``forward_seconds`` stamp, nothing of the two families
in the lowered text, and the lowered text that of the same step built with
``jax.value_and_grad``.  Traced and lowered, never compiled; no test here
reads a clock's value."""

import time

import jax
import optax
import pytest
from jax.sharding import PartitionSpec

import horovod_tpu
import horovod_tpu.jax as hvd
from benchmark import manifest
from horovod_tpu.common import scopes
from horovod_tpu.ops import collective_ops
from tiny_sizes import TINY          # tests/conftest.py put it on the path

CELLS = {"routed": "deepseek-v2-lite.train-s4k",
         "hybrid": "olmo-hybrid-7b.train-s8k",
         "resnet": "resnet50-v1.5.train-b256"}


def _job(workload):
    """The cell's own job at the tests' tiny sizes, its state and batch
    abstract."""
    cell = manifest.cell(workload)
    tiny = TINY[cell["config"]["job"]]
    job = manifest.load_job(cell["config"]["job"]).build(
        {**cell["config"], **tiny["config"]},
        {**cell["traffic"], **tiny["traffic"]}, 1)
    state = jax.eval_shape(job.init_state, jax.random.key(0))
    batch = jax.eval_shape(job.make_batch, jax.random.key(1))
    return job, state, batch


def _mesh():
    hvd.init()
    return hvd.build_mesh({"data": 1}, devices=jax.devices()[:1])


def _value_and_grad_step(loss_fn, optimizer, mesh, has_aux):
    """``make_train_step`` as it stood before the loss was taken as a
    ``jax.vjp`` and its pullback: the same scopes, ``jax.value_and_grad``."""
    axes = ("data",)
    average = hvd.Average

    def body(params, opt_state, *rest):
        with scopes.scope(scopes.LOSS):
            value, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(
                params, *rest)
        loss, aux = value if has_aux else (value, None)
        if has_aux:
            with scopes.scope(scopes.AUX_ALLREDUCE):
                aux = jax.tree.map(
                    lambda x: collective_ops.allreduce(
                        x, axis_name=axes, op=average)
                    if hvd._is_inexact(x) else x, aux)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        with scopes.scope(scopes.APPLY):
            params = optax.apply_updates(params, updates)
        loss = collective_ops.allreduce(loss, axis_name=axes, op=average)
        return (params, opt_state, *((aux,) if has_aux else ()), loss)

    n_state = 3 if has_aux else 2
    step = jax.shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(),) * n_state + (PartitionSpec(axes),),
        out_specs=(PartitionSpec(),) * (n_state + 1), check_vma=False)
    step.__name__ = step.__qualname__ = scopes.TRAIN_STEP_PROGRAM
    return jax.jit(step, donate_argnums=tuple(range(n_state)))


@pytest.fixture(scope="module", params=list(CELLS))
def traced(request):
    """``(kind, job, the step's spans, its lowered text with the op_names,
    its lowered text without, that of the ``value_and_grad`` step)``."""
    job, state, batch = _job(CELLS[request.param])
    mesh = _mesh()
    optimizer = job.optimizer
    if not isinstance(optimizer, hvd.DistributedOptimizer):
        optimizer = hvd.DistributedOptimizer(optimizer, axis_name=("data",))
    jax.clear_caches()      # an inlined jit's Python runs once a shape
    began = time.perf_counter() - horovod_tpu.IMPORT_BEGAN
    lowered = hvd.make_train_step(job.loss_fn, optimizer, mesh,
                                  has_aux=job.has_aux).lower(*state, batch)
    spans = [s for s in hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM)
             if s["began"] >= began]
    reference = _value_and_grad_step(
        job.loss_fn, optimizer, mesh, job.has_aux).lower(*state, batch)
    return (request.param, job, spans, lowered.as_text(debug_info=True),
            lowered.as_text(), reference.as_text())


def _kind(spec) -> str:
    mixer = (spec.mixer or "none") + ("+window" if spec.window else "")
    return f"{scopes.LAYER}{mixer}.{spec.ffn or 'none'}"


def test_one_layer_span_a_layer_call_of_its_specs_kind(traced):
    kind, job, spans, *_ = traced
    layers = [s for s in spans if s["name"].startswith(scopes.LAYER)]
    if kind == "resnet":
        wanted = [f"{scopes.LAYER}resnet.stage{i}"
                  for i in range(len(job.model.stage_sizes))]
    else:
        wanted = [_kind(spec) for spec in job.llama.layers]
        assert len(set(wanted)) > 1         # a stack of more than one kind
    assert [s["name"] for s in layers] == wanted
    # Every one a child of the loss, in its forward half; none in another.
    loss, = (s for s in spans if s["name"] == scopes.LOSS)
    for span in layers:
        assert span["path"] == f"{scopes.LOSS}/{span['name']}"
        assert span["began"] + span["seconds"] <= (
            loss["began"] + loss[scopes.FORWARD_SECONDS])
        assert 0 <= span["self_seconds"] <= span["seconds"]


def test_the_block_spans_nest_inside_their_layers(traced):
    kind, _, spans, *_ = traced
    blocks = [s for s in spans
              if s["name"] in (scopes.BLOCK_ATTN, scopes.BLOCK_FFN)]
    assert bool(blocks) == (kind != "resnet")
    for span in blocks:
        assert span["path"].split("/")[1].startswith(scopes.LAYER), (
            span["path"])


def test_the_loss_says_when_its_forward_half_ended(traced):
    kind, _, spans, *_ = traced
    loss, = (s for s in spans if s["name"] == scopes.LOSS)
    assert loss["path"] == scopes.LOSS
    assert 0 < loss[scopes.FORWARD_SECONDS] < loss["seconds"]
    turned = loss["began"] + loss[scopes.FORWARD_SECONDS]
    backward = [s for s in spans if s["name"].startswith(scopes.RULE)
                and s["name"].endswith(".bwd")]
    assert bool(backward) == (kind != "resnet")
    for span in backward:
        # Behind the stamp, at the top of the loss or inside the backward
        # rule that called JAX back for it (``_live_buffers_bwd`` takes a
        # buffer's ``jax.vjp``; ``_rule_bwd`` a slab's preparation's).
        assert span["began"] >= turned, span["path"]
        top, *between, last = span["path"].split("/")
        assert top == scopes.LOSS and last == span["name"]
        assert all(name.startswith(scopes.RULE) and name.endswith(".bwd")
                   for name in between), span["path"]
    if kind == "routed":
        assert f"{scopes.RULE}_live_buffers.bwd" in {
            s["name"] for s in backward}
    # No span of another name carries the flag.
    assert all(scopes.FORWARD_SECONDS not in s for s in spans if s is not loss)


def test_no_name_of_the_two_families_reaches_the_lowered_text(traced):
    _, _, spans, with_names, *_ = traced
    recorded = {s["name"] for s in spans
                if s["name"].startswith((scopes.LAYER, scopes.RULE))}
    assert recorded
    assert scopes.LOSS in with_names            # the text holds op_names
    for name in recorded:
        assert name not in with_names, name
    assert scopes.LAYER + "attention" not in with_names
    assert scopes.FORWARD_SECONDS not in with_names


def test_the_lowered_step_is_the_value_and_grad_steps(traced):
    *_, text, reference = traced
    assert text == reference


@pytest.mark.parametrize("wrong", ["a vector", "an integer"])
def test_a_loss_that_is_no_real_scalar_is_refused(wrong):
    """What ``jax.value_and_grad`` refused, the pair refuses."""
    import jax.numpy as jnp

    def loss_fn(params, batch):
        out = params["w"] * batch
        return out if wrong == "a vector" else jnp.sum(out).astype(jnp.int32)

    step = hvd.make_train_step(loss_fn, optax.sgd(0.1), _mesh())
    params = {"w": jnp.ones((4,))}
    with pytest.raises(TypeError, match="real scalar loss"):
        step.lower(params, optax.sgd(0.1).init(params), jnp.ones((4,)))
