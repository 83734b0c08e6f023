"""The jit path names its own work: the scopes of
``horovod_tpu/common/scopes.py`` in the optimised HLO of a tiny train step
on four virtual devices, and the compile log behind ``hvd.compile_log()``.
No test here reads a clock."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu.common import compile_cache, scopes
from horovod_tpu.ops.flash_attention import flash_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: The names the program enters.  ``FUSION_PACK`` and ``FUSION_UNPACK`` are
#: still in the table for the benchmark's reader and entered by nothing.
TABLE = [scopes.LOSS, scopes.ALLREDUCE, scopes.AUX_ALLREDUCE,
         scopes.OPTIMIZER, scopes.APPLY, scopes.FLASH_FWD, scopes.FLASH_BWD]


def _mesh():
    hvd.init()
    return hvd.data_parallel_mesh(jax.devices()[:4])


def _params():
    rng = np.random.default_rng(0)
    return {"w": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32),
            "v": jnp.asarray(rng.standard_normal((16, 1)), jnp.float32),
            "b": jnp.zeros((16,), jnp.float32)}


def _batch():
    return (jnp.ones((16, 8), jnp.float32), jnp.ones((16, 1), jnp.float32))


def _loss(params, batch):
    x, y = batch
    hidden = jnp.tanh(x @ params["w"] + params["b"])
    return jnp.mean((hidden @ params["v"] - y) ** 2)


def _loss_aux(params, stats, batch):
    loss = _loss(params, batch)
    return loss, {"mean": 0.9 * stats["mean"] + 0.1 * loss,
                  "steps": stats["steps"] + 1}


def _loss_flash(params, batch):
    x, y = batch                                  # a shard's rows
    rows = x.shape[0]
    q = (x @ params["w"]).reshape(1, rows, 1, 16)  # [B, S, H, D]
    q = jnp.tile(q, (1, 128 // rows, 2, 4))        # S = 128, H = 2, D = 64
    out = flash_attention(q, q, q, causal=True)
    return jnp.mean(out) + _loss(params, batch)


def _op_names(step, *args) -> set:
    text = step.lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def _under(names: set, *parts: str) -> list:
    """The op_names that hold ``parts`` as consecutive path components.
    JAX wraps the first scope entered inside a transformation:
    ``jvp(hvd.flash.fwd)`` counts as ``hvd.flash.fwd``."""
    wanted = "/" + "/".join(parts) + "/"
    unwrapped = re.compile(r"(?:\w+\()+([^()/]+)\)+")
    return [n for n in names
            if wanted in "/" + unwrapped.sub(r"\1", n) + "/"]


@pytest.fixture(scope="module")
def plain_names():
    opt = hvd.DistributedOptimizer(optax.adam(1e-2))
    step = hvd.make_train_step(_loss, opt, _mesh())
    params = _params()
    return _op_names(step, params, opt.init(params), _batch())


@pytest.mark.parametrize("scope", [
    scopes.OPTIMIZER, scopes.APPLY, scopes.allreduce_scope("data")])
def test_step_hlo_holds_the_scope(plain_names, scope):
    assert _under(plain_names, scope), sorted(plain_names)
    # Under the program's exported name, which leads the path (a
    # reduction's own small computation starts at the scope).
    assert any(n.startswith(f"jit({scopes.TRAIN_STEP_PROGRAM})/")
               for n in _under(plain_names, scope))


@pytest.mark.parametrize("scope", [scopes.FUSION_PACK, scopes.FUSION_UNPACK])
def test_no_operation_of_the_step_is_under_a_packing_scope(plain_names,
                                                           scope):
    """Gradients go to their all-reduce as they are: the two names are in
    the table for the benchmark's ``fusion_pack_ms`` alone."""
    assert not [n for n in plain_names if scope in n]


def test_one_scope_splits_forward_from_backward(plain_names):
    under_loss = _under(plain_names, scopes.LOSS)
    forward = [n for n in under_loss if "transpose(" not in n]
    backward = [n for n in under_loss if "/transpose(jvp(" in n]
    assert forward and backward
    assert all(f"{scopes.LOSS}/jvp(" in n for n in forward)
    assert len(forward) + len(backward) == len(under_loss)
    # The matmuls: two forward, and their transposes.
    assert any(n.endswith("/dot_general") for n in forward)
    assert any(n.endswith("/dot_general") for n in backward)
    # Nothing of the optimizer is under the loss.
    for scope in (scopes.OPTIMIZER, scopes.APPLY):
        assert not set(_under(plain_names, scope)) & set(under_loss)


def test_every_traced_allreduce_is_under_its_axes(plain_names):
    reductions = [n for n in plain_names if n.endswith("/psum")]
    assert reductions
    assert all(_under({n}, scopes.allreduce_scope("data"))
               for n in reductions)
    assert scopes.allreduce_scope(("data", "fsdp")) == "hvd.allreduce.data+fsdp"


def test_aux_state_allreduce_has_its_scope():
    opt = hvd.DistributedOptimizer(optax.sgd(1e-2))
    step = hvd.make_train_step(_loss_aux, opt, _mesh(), has_aux=True)
    params = _params()
    stats = {"mean": jnp.zeros(()), "steps": jnp.zeros((), jnp.int32)}
    names = _op_names(step, params, opt.init(params), stats, _batch())
    assert _under(names, scopes.AUX_ALLREDUCE,
                  scopes.allreduce_scope("data"))
    assert _under(names, scopes.LOSS) and _under(names, scopes.APPLY)


def test_flash_calls_have_their_scopes():
    """Interpret mode lowers each kernel to plain HLO under its scope."""
    opt = hvd.DistributedOptimizer(optax.sgd(1e-2))
    step = hvd.make_train_step(_loss_flash, opt, _mesh())
    params = _params()
    names = _op_names(step, params, opt.init(params), _batch())
    forward = _under(names, scopes.FLASH_FWD)
    assert forward and all("transpose(" not in n for n in forward)
    backward = _under(names, scopes.FLASH_BWD)
    assert backward and all("/transpose(" in n for n in backward)
    assert all(n.index(scopes.LOSS) < n.index("transpose(")
               for n in backward)
    assert all(_under({n}, scopes.LOSS) for n in forward)
    # One backward scope: no operation sits under another ``hvd.flash.*``.
    prefix = scopes.FLASH_FWD.rsplit(".", 1)[0] + "."
    assert {n for n in names if prefix in n} == set(forward) | set(backward)


@pytest.mark.parametrize("has_aux", [False, True])
def test_make_train_step_returns_the_jit_object(has_aux):
    loss = _loss_aux if has_aux else _loss
    step = hvd.make_train_step(loss, optax.sgd(1e-2), _mesh(),
                               has_aux=has_aux)
    assert callable(step.lower) and step._cache_size() == 0
    assert step.__name__ == scopes.TRAIN_STEP_PROGRAM == hvd.TRAIN_STEP_PROGRAM


def test_each_scope_name_is_spelled_in_one_place():
    table = os.path.join(REPO, "horovod_tpu", "common", "scopes.py")
    spelled = re.compile("|".join(
        rf'["\']{re.escape(name)}' for name in TABLE))
    scoped = []
    for folder, _, files in os.walk(os.path.join(REPO, "horovod_tpu")):
        for name in files:
            path = os.path.join(folder, name)
            if not name.endswith(".py") or path == table:
                continue
            with open(path) as f:
                text = f.read()
            assert not spelled.search(text), path
            scoped += re.findall(r"named_scope\(\s*_?scopes\.(\w+)", text)
    # Every name of the table has a named_scope somewhere in the program.
    used = {getattr(scopes, attribute, None) for attribute in scoped}
    assert scopes.allreduce_scope("data").startswith(scopes.ALLREDUCE)
    assert set(TABLE) - {scopes.ALLREDUCE} <= used
    assert "allreduce_scope" in scoped


# -- the compile log ---------------------------------------------------------

def _events(log, program):
    return [r["event"] for r in log.records(program)]


def test_compile_log_of_a_train_step():
    """One trace, one lowering and one backend record after the first
    call, none more after the second."""
    mesh = _mesh()
    log = compile_cache.CompileLog()      # its own: the process's is shared
    log.listen()
    opt = hvd.DistributedOptimizer(optax.sgd(1e-2))
    step = hvd.make_train_step(_loss, opt, mesh)
    replicated = NamedSharding(mesh, P())
    params = jax.device_put(_params(), replicated)
    state = jax.device_put(opt.init(params), replicated)
    batch = jax.device_put(_batch(), NamedSharding(mesh, P("data")))
    before = len(hvd.compile_log(hvd.TRAIN_STEP_PROGRAM))
    assert _events(log, hvd.TRAIN_STEP_PROGRAM) == []
    placed = len(log.records())

    params, state, _ = step(params, state, batch)
    events = _events(log, hvd.TRAIN_STEP_PROGRAM)
    assert [e for e in events if not e.startswith("cache_")] == [
        "trace", "lower", "backend"]
    records = log.records(hvd.TRAIN_STEP_PROGRAM)
    assert records[0]["program"] == hvd.TRAIN_STEP_PROGRAM
    assert records[-1]["program"] == f"jit({hvd.TRAIN_STEP_PROGRAM})"
    assert all(r["seconds"] > 0 for r in records
               if not r["event"].startswith("cache_"))
    # What the step traced on its way (jnp functions, optax) is in its
    # own record, not beside it.
    assert [r["event"] for r in log.records()[placed:]].count("trace") == 1

    params, state, loss = step(params, state, batch)
    assert _events(log, hvd.TRAIN_STEP_PROGRAM) == events
    assert step._cache_size() == 1 and np.isfinite(float(loss))
    # hvd.init() started the process's log, which saw the same.
    assert len(hvd.compile_log(hvd.TRAIN_STEP_PROGRAM)) >= min(
        before + 3, compile_cache.CompileLog.MAX_RECORDS)


def _report(log, event, seconds, **kwargs):
    log._on_duration(event, seconds, **kwargs)


TRACE, LOWER, BACKEND, RETRIEVAL = compile_cache.CompileLog.DURATIONS
REQUEST, HIT = compile_cache.CompileLog.COUNTS


def test_compile_log_keeps_the_outermost_trace():
    log = compile_cache.CompileLog()
    _report(log, TRACE, 1e-6, fun_name="before")
    _report(log, TRACE, 1e-9, fun_name="inner_a")
    _report(log, LOWER, 1e-9, fun_name="jit(eager_constant)")
    _report(log, TRACE, 1e-9, fun_name="inner_b")
    _report(log, TRACE, 3600.0, fun_name="outer")   # began before them all
    assert [(r["program"], r["event"]) for r in log.records()] == [
        ("jit(eager_constant)", "lower"), ("outer", "trace")]
    _report(log, TRACE, 1e-9, fun_name="after")
    assert [r["program"] for r in log.records()][-2:] == ["outer", "after"]


def test_compile_log_gives_cache_events_their_program():
    log = compile_cache.CompileLog()
    _report(log, BACKEND, 0.5, fun_name="jit(first)")
    log._on_event(REQUEST)
    log._on_event(HIT)
    _report(log, RETRIEVAL, 0.25)
    _report(log, BACKEND, 0.3, fun_name="jit(step)")
    log._on_event("/jax/some/other/event")
    _report(log, "/jax/some/other/duration", 1.0, fun_name="x")
    assert log.records("step") == [
        {"program": "jit(step)", "event": "cache_request", "seconds": None},
        {"program": "jit(step)", "event": "cache_hit", "seconds": None},
        {"program": "jit(step)", "event": "cache_retrieval",
         "seconds": 0.25},
        {"program": "jit(step)", "event": "backend", "seconds": 0.3}]
    assert len(log.records()) == 5 and len(log.records("first")) == 1


def test_compile_log_is_bounded_and_gives_copies():
    log = compile_cache.CompileLog()
    for i in range(log.MAX_RECORDS + 10):
        _report(log, LOWER, 1e-3, fun_name=f"jit(f{i})")
    records = log.records()
    assert len(records) == log.MAX_RECORDS
    assert records[-1]["program"] == f"jit(f{log.MAX_RECORDS + 9})"
    records[-1]["program"] = "changed"
    assert log.records()[-1]["program"] != "changed"
