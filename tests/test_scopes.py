"""The jit path names its own work: the scopes of
``horovod_tpu/common/scopes.py`` in the optimised HLO of a tiny train step
on four virtual devices, and the compile log behind ``hvd.compile_log()``.
No test here reads a clock."""

import contextlib
import functools
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu
import horovod_tpu.jax as hvd
from horovod_tpu.common import compile_cache, scopes
from horovod_tpu.ops.flash_attention import flash_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: The names the program enters.  ``FUSION_PACK`` and ``FUSION_UNPACK`` are
#: still in the table for the benchmark's reader and entered by nothing.
TABLE = [scopes.LOSS, scopes.ALLREDUCE, scopes.AUX_ALLREDUCE,
         scopes.OPTIMIZER, scopes.APPLY, scopes.FLASH_FWD, scopes.FLASH_BWD,
         scopes.ROPE]


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


def _loss_rope(params, batch, heads=2):
    from horovod_tpu.models.llama import apply_rope, rope_freqs

    x, _ = batch                                  # a shard's rows
    q = (x @ params["w"]).reshape(1, x.shape[0], 1, 16)
    q = jnp.tile(q, (1, 128 // x.shape[0], heads, 8))  # S = 128, D = 128
    q = apply_rope(q, *rope_freqs(128, 128, 1e4), in_place=True)
    return jnp.mean(flash_attention(q, q, q, causal=True)) + _loss(params,
                                                                   batch)


def test_the_rotation_has_a_scope_of_its_own():
    """``hvd.rope`` holds the rotation's pass forward and backward, beside
    the flash calls' scopes and under neither of them."""
    opt = hvd.DistributedOptimizer(optax.sgd(1e-2))
    step = hvd.make_train_step(_loss_rope, opt, _mesh())
    params = _params()
    names = _op_names(step, params, opt.init(params), _batch())
    rotation = _under(names, scopes.ROPE)
    assert any("transpose(" not in n for n in rotation)
    assert any("/transpose(" in n for n in rotation)
    assert all(_under({n}, scopes.LOSS) for n in rotation)
    flash = scopes.FLASH_FWD.rsplit(".", 1)[0] + "."
    assert not any(flash in n for n in rotation)
    assert _under(names, scopes.FLASH_FWD) and _under(names, scopes.FLASH_BWD)
    assert "ROPE" in scopes.__all__ and scopes.ROPE == "hvd.rope"


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
            # ``scopes.scope`` is the one way into a scope: the table's own
            # file holds the one ``jax.named_scope(`` of the package.
            assert "named_scope(" not in text, path
            scoped += re.findall(r"_?scopes\.scope\(\s*_?scopes\.(\w+)",
                                 text)
    with open(table) as f:
        assert f.read().count("jax.named_scope(name):") == 1
    # Every name of the table is entered somewhere in the program.
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
    records = log.records("step")
    # ``began``: seconds from the log's origin, on the spans' axis.
    began = [r.pop("began") for r in records]
    assert all(b > -0.3 for b in began) and began[-1] == min(began)
    assert records == [
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


# -- the spans ---------------------------------------------------------------

def _by_path(spans) -> dict:
    out = {}
    for span in spans:
        out.setdefault(span["path"], []).append(span)
    return out


def test_spans_nest_and_a_parent_covers_its_children():
    log = compile_cache.CompileLog()
    with log.span("outer", why="a flag"):
        with log.span("a"):
            with log.span("leaf"):
                pass
        with log.span("a"):
            pass
        assert [s["name"] for s in log.spans()] == ["a", "leaf", "a"]
    spans = log.spans()
    assert [s["path"] for s in spans] == [
        "outer", "outer/a", "outer/a/leaf", "outer/a"]
    assert spans[0]["why"] == "a flag" and "why" not in spans[1]
    outer, first, leaf, second = spans
    for parent, children in ((outer, [first, second]), (first, [leaf]),
                             (leaf, []), (second, [])):
        assert parent["self_seconds"] >= 0 and parent["seconds"] > 0
        assert parent["self_seconds"] + sum(
            c["seconds"] for c in children) == pytest.approx(
                parent["seconds"], abs=1e-9)
        for child in children:
            assert parent["began"] <= child["began"]
            assert (child["began"] + child["seconds"]
                    <= parent["began"] + parent["seconds"] + 1e-9)
    # By their start, on the axis of the records' ``began``.
    assert [s["began"] for s in spans] == sorted(s["began"] for s in spans)
    with log.span("later"):
        pass
    assert log.spans()[-1]["path"] == "later"


def test_a_span_that_raises_is_closed_and_leaves_the_stack():
    log = compile_cache.CompileLog()
    with pytest.raises(KeyError):
        with log.span("outer"):
            with log.span("inner"):
                raise KeyError("x")
    with log.span("next"):
        pass
    assert [s["path"] for s in log.spans()] == [
        "outer", "outer/inner", "next"]


def test_a_finished_span_goes_in_from_two_stamps_and_can_be_a_parent():
    log = compile_cache.CompileLog(origin=100.0)
    whole = log.add_span("import", 100.0, 104.0, jax_was_imported=False)
    log.add_span("models", 101.0, 103.5, parent=whole)
    assert log.spans() == [
        {"name": "import", "path": "import", "began": 0.0, "seconds": 4.0,
         "self_seconds": 1.5, "jax_was_imported": False},
        {"name": "models", "path": "import/models", "began": 1.0,
         "seconds": 2.5, "self_seconds": 2.5}]


def test_two_threads_keep_two_stacks():
    log = compile_cache.CompileLog()
    inside, leave = threading.Event(), threading.Event()

    def other():
        with log.span("theirs"):
            inside.set()
            leave.wait(30)
            with log.span("their_child"):
                pass

    thread = threading.Thread(target=other)
    with log.span("mine"):
        thread.start()
        assert inside.wait(30)
        with log.span("my_child"):
            pass
        leave.set()
        thread.join(30)
    assert sorted(s["path"] for s in log.spans()) == [
        "mine", "mine/my_child", "theirs", "theirs/their_child"]


def test_the_spans_are_bounded_and_given_out_as_copies(monkeypatch):
    monkeypatch.setattr(compile_cache.CompileLog, "MAX_SPANS", 64)
    log = compile_cache.CompileLog()
    for i in range(64 + 10):
        with log.span(f"s{i}"):
            pass
    spans = log.spans()
    assert len(spans) == 64 and spans[-1]["name"] == "s73"
    spans[-1]["name"] = "changed"
    del spans[:10]
    assert len(log.spans()) == 64 and log.spans()[-1]["name"] == "s73"


def _lowered(loss, has_aux=False, stats=()):
    """The tiny step traced and lowered, never compiled: a new step an
    entry, so JAX's cache of traces holds none of them."""
    opt = hvd.DistributedOptimizer(optax.sgd(1e-2))
    step = hvd.make_train_step(loss, opt, _mesh(), has_aux=has_aux)
    params = _params()
    return step.lower(params, opt.init(params), *stats, _batch())


def _now():
    """This moment on the axis of the log's ``began``."""
    return time.perf_counter() - horovod_tpu.IMPORT_BEGAN


def _spans_since(mark, program=None):
    """The spans that began after ``mark``.  By their time and not by their
    place in the list: the log keeps its newest records and spans, so on a
    worker that compiled a few thousand programs before this file the list
    loses old entries at its front while it gains these at its end (PR 55:
    the files' order moved, and ``[was:]`` was empty)."""
    return [s for s in hvd.compile_spans(program) if s["began"] >= mark]


def test_the_steps_spans_are_the_steps_and_not_another_programs():
    # A name no program of the repository has: ``benchmark/run.py`` jits a
    # ``make_state`` of its own, whose spans a worker that ran a cell before
    # this file has in the process's log (PR 55: the files' order moved).
    def scopes_test_state(x):
        with scopes.scope(scopes.HEAD):
            return x + 1

    began = _now()
    assert hvd.compile_spans("scopes_test_state") == []
    jax.jit(scopes_test_state).lower(jnp.zeros(()))
    state, = hvd.compile_spans("scopes_test_state")
    assert state["path"] == state["name"] == scopes.HEAD
    assert _spans_since(began, hvd.TRAIN_STEP_PROGRAM) == []

    _lowered(_loss)
    new = _spans_since(began, hvd.TRAIN_STEP_PROGRAM)
    assert new[0]["path"] == scopes.LOSS
    assert {s["path"] for s in new} == {
        scopes.LOSS, scopes.allreduce_scope("data"), scopes.OPTIMIZER,
        scopes.APPLY}
    assert hvd.compile_spans("scopes_test_state") == [state]
    # Inside the step's ``trace`` record, on the one axis.
    trace = [r for r in hvd.compile_log(hvd.TRAIN_STEP_PROGRAM)
             if r["event"] == "trace"][-1]
    for span in new:
        assert trace["began"] <= span["began"]
        assert (span["began"] + span["seconds"]
                <= trace["began"] + trace["seconds"])
    assert state in hvd.compile_spans()
    assert all(span in hvd.compile_spans() for span in new)


def test_every_name_the_tiny_steps_enter_is_a_span():
    """The rotation and the flash pair under ``hvd.loss``, forward where
    the loss calls them and backward at its top (differentiation replays
    jaxprs, and runs the backward rules' Python); each Mosaic call's bind
    inside its scope's span."""
    began = _now()
    # The rotation is an inlined ``jit`` that JAX traces once a shape and
    # process: at a shape no other test rotates, its Python runs here.
    _lowered(functools.partial(_loss_rope, heads=4))
    stats = {"mean": jnp.zeros(()), "steps": jnp.zeros((), jnp.int32)}
    _lowered(_loss_aux, has_aux=True, stats=(stats,))
    spans = _spans_since(began, hvd.TRAIN_STEP_PROGRAM)
    names = {s["name"] for s in spans}
    assert set(TABLE) - {scopes.ALLREDUCE} <= names
    assert scopes.allreduce_scope("data") in names
    paths = _by_path(spans)
    # Each inside the span of the differentiation rule that entered it
    # (``rule.<op>.<pass>``, PR 67: ``tests/test_rule_spans.py``).
    for rule, scope, bind in (
            ("rotate_pairs.fwd", scopes.ROPE, scopes.MOSAIC_ROPE),
            ("_flash.fwd", scopes.FLASH_FWD, scopes.MOSAIC_FLASH_FWD),
            ("_flash.bwd", scopes.FLASH_BWD, scopes.MOSAIC_FLASH_BWD)):
        assert paths[f"{scopes.LOSS}/{scopes.RULE}{rule}/{scope}/{bind}"], (
            sorted(paths))
        assert bind.startswith(scopes.MOSAIC)
    assert paths[f"{scopes.AUX_ALLREDUCE}/{scopes.allreduce_scope('data')}"]
    assert {s["name"] for s in spans if s["name"].startswith(
        scopes.MOSAIC)} == {scopes.MOSAIC_ROPE, scopes.MOSAIC_FLASH_FWD,
                            scopes.MOSAIC_FLASH_BWD}


def test_a_second_call_of_a_traced_step_adds_no_span():
    mesh = _mesh()
    opt = hvd.DistributedOptimizer(optax.sgd(1e-2))
    step = hvd.make_train_step(_loss, opt, mesh)
    replicated = NamedSharding(mesh, P())
    params = jax.device_put(_params(), replicated)
    state = jax.device_put(opt.init(params), replicated)
    batch = jax.device_put(_batch(), NamedSharding(mesh, P("data")))
    params, state, _ = step(params, state, batch)
    spans = hvd.compile_spans()
    assert spans[-1]["name"] == scopes.allreduce_scope("data")
    params, state, loss = step(params, state, batch)
    assert step._cache_size() == 1 and np.isfinite(float(loss))
    assert hvd.compile_spans() == spans


def test_the_lowered_step_is_the_same_without_the_logs_span(monkeypatch):
    """``scopes.scope`` changes no ``op_name``, and a ``mosaic.*`` span
    reaches no HLO: with the log's span a no-op the text is the same."""
    texts, counts = [], []
    for span in (compile_cache.span,
                 lambda name, **flags: contextlib.nullcontext()):
        monkeypatch.setattr(compile_cache, "span", span)
        began = _now()
        # With the locations, which hold the op_names; from one line of
        # this file, which they hold too.
        texts.append(_lowered(_loss_rope).as_text(debug_info=True))
        counts.append(len(_spans_since(began)))
    assert scopes.ROPE in texts[0] and scopes.MOSAIC not in texts[0]
    assert texts[0] == texts[1]
    assert counts[0] > 0 and counts[1] == 0


def test_init_and_the_import_have_their_spans():
    hvd.shutdown()
    hvd.init()
    spans = hvd.compile_spans()
    whole, models = (next(s for s in spans if s["name"] == name)
                     for name in (scopes.IMPORT, scopes.IMPORT_MODELS))
    assert whole["began"] == 0.0 and whole["jax_was_imported"] is True
    assert models["path"] == f"{scopes.IMPORT}/{scopes.IMPORT_MODELS}"
    assert 0 <= models["seconds"] <= whole["seconds"]
    assert whole["self_seconds"] == pytest.approx(
        whole["seconds"] - models["seconds"])
    init = [s for s in spans if s["name"] == scopes.INIT][-1]
    parts = [s for s in spans if s["began"] >= init["began"]
             and s["path"].startswith(scopes.INIT + "/")]
    assert [s["name"] for s in parts] == [scopes.INIT_NATIVE,
                                          scopes.INIT_CACHE]
    assert sum(s["seconds"] for s in parts) <= init["seconds"]
    assert "compile_spans" in hvd.__all__
