"""The readers of the layer and rule spans, of ``hvd.loss``'s two halves and
of the compile log's records of the state's program and the executable's load
(``benchmark/startup_rules.py`` and the nine ``benchmark/metrics`` files that
call it) against hand-made spans and records, against a program without
them, against a log that dropped its oldest part, and their entries in the
manifest.  No test here traces a step or reads a clock."""

import pytest

import horovod_tpu.jax as hvd
from benchmark import manifest, startup_rules, startup_spans
from horovod_tpu.common import scopes

if not hasattr(scopes, "RULE"):         # these files laid over the parent
    pytest.skip("the program keeps no layer or rule spans",
                allow_module_level=True)

ALL_CELLS = [w["name"] for w in manifest.load()["workloads"]]
DECODERS = [name for name in ALL_CELLS if not name.startswith("resnet")]
#: name -> (source, the cells that report it)
METRICS = {
    "trace_loss_forward_ms": ("program_span", ALL_CELLS),
    "trace_loss_backward_ms": ("program_span", ALL_CELLS),
    "trace_layers_self_ms": ("program_span", ALL_CELLS),
    "trace_rules_ms": ("program_span", DECODERS),
    "trace_backward_self_ms": ("program_span", ALL_CELLS),
    "step_cache_retrieval_ms": ("program_counter", ALL_CELLS),
    "step_load_ms": ("program_counter", ALL_CELLS),
    "state_trace_ms": ("program_counter", ALL_CELLS),
    "state_backend_ms": ("program_counter", ALL_CELLS),
}


def _span(path, began, seconds, self_seconds=None, **flags):
    return {"name": path.rsplit("/", 1)[-1], "path": path, "began": began,
            "seconds": seconds, "self_seconds":
                seconds if self_seconds is None else self_seconds, **flags}


L, A, F = scopes.LOSS, scopes.BLOCK_ATTN, scopes.BLOCK_FFN
DENSE, ROUTED = "layer.attention.dense", "layer.attention.routed"
FLASH_F, FLASH_B = "rule._flash.fwd", "rule._flash.bwd"
LIVE_F, LIVE_B = "rule._live_buffers.fwd", "rule._live_buffers.bwd"
#: A step of two layers traced ONCE: the loss 10 s, its forward half 6 s.
STEP = [
    _span(L, 10.0, 10.0, 2.25, **{scopes.FORWARD_SECONDS: 6.0}),
    _span(f"{L}/{DENSE}", 10.25, 2.0, 0.5),
    _span(f"{L}/{DENSE}/{A}", 10.5, 1.0),
    _span(f"{L}/{DENSE}/{FLASH_F}", 11.5, 0.5, 0.125),
    _span(f"{L}/{DENSE}/{FLASH_F}/{scopes.FLASH_FWD}", 11.5, 0.375),
    _span(f"{L}/{ROUTED}", 12.5, 3.0, 1.0),
    _span(f"{L}/{ROUTED}/{F}", 12.5, 1.5),
    _span(f"{L}/{ROUTED}/{LIVE_F}", 14.5, 0.5),
    _span(f"{L}/{scopes.HEAD}", 15.75, 0.25),
    # Behind the stamp (16.0): the backward rules at the top of the loss,
    # one of which called JAX back for another.
    _span(f"{L}/{LIVE_B}", 16.5, 2.0, 1.5),
    _span(f"{L}/{LIVE_B}/{FLASH_B}", 17.0, 0.5),
    _span(f"{L}/{FLASH_B}", 19.0, 0.5, 0.25),
    _span(f"{L}/{FLASH_B}/{scopes.FLASH_BWD}", 19.0, 0.25),
    _span(scopes.OPTIMIZER, 20.5, 0.5),
]
STEP_RECORDS = [
    {"program": "hvd_train_step", "event": "trace", "seconds": 11.0,
     "began": 9.5},
    {"program": "jit(hvd_train_step)", "event": "lower", "seconds": 2.0,
     "began": 21.0},
    {"program": "jit(hvd_train_step)", "event": "cache_request",
     "seconds": None, "began": 23.0},
    {"program": "jit(hvd_train_step)", "event": "cache_hit",
     "seconds": None, "began": 23.0},
    {"program": "jit(hvd_train_step)", "event": "cache_retrieval",
     "seconds": 0.25, "began": 23.0},
    {"program": "jit(hvd_train_step)", "event": "backend", "seconds": 1.5,
     "began": 23.0},
]
STATE_RECORDS = [
    {"program": "make_state", "event": "trace", "seconds": 0.75,
     "began": 4.0},
    {"program": "jit(make_state)", "event": "lower", "seconds": 0.5,
     "began": 4.75},
    {"program": "jit(make_state)", "event": "backend", "seconds": 2.25,
     "began": 5.25},
]
EXPECTED = {
    "trace_loss_forward_ms": 6000.0, "trace_loss_backward_ms": 4000.0,
    "trace_layers_self_ms": 1500.0,
    # 125 + 500 forward, 1500 + 500 + 250 backward.
    "trace_rules_ms": 2875.0,
    # 4 s less the two rules at the top of the backward half (2 + 0.5).
    "trace_backward_self_ms": 1500.0,
    "step_cache_retrieval_ms": 250.0, "step_load_ms": 1250.0,
    "state_trace_ms": 750.0, "state_backend_ms": 2250.0,
}


def _made_up(monkeypatch, spans=STEP, step_records=STEP_RECORDS,
             evicted=(0, 0)):
    def compile_spans(program=None):
        assert program in (None, hvd.TRAIN_STEP_PROGRAM)
        return [dict(s) for s in spans]

    def compile_log(program=None):
        return [dict(r) for r in {
            hvd.TRAIN_STEP_PROGRAM: step_records,
            startup_rules.STATE_PROGRAM: STATE_RECORDS}[program]]

    monkeypatch.setattr(hvd, "compile_spans", compile_spans)
    monkeypatch.setattr(hvd, "compile_log", compile_log)
    monkeypatch.setattr(hvd, "compile_evicted", lambda: dict(
        zip(("spans", "records"), evicted)))
    startup_rules._say_rules.cache_clear()
    startup_spans._say_tree.cache_clear()


@pytest.fixture(autouse=True)
def _said_once_a_test():
    yield
    startup_rules._say_rules.cache_clear()
    startup_spans._say_tree.cache_clear()


def _read(metric):
    return manifest.load_reader(metric)({"trace": None})


@pytest.mark.parametrize("metric", list(METRICS))
def test_a_reader_against_hand_made_spans_and_records(monkeypatch, metric):
    _made_up(monkeypatch)
    value = _read(metric)
    assert value == EXPECTED[metric] and type(value) is float


def test_the_parts_add_up(monkeypatch):
    _made_up(monkeypatch)
    loss = STEP[0]
    assert (_read("trace_loss_forward_ms") + _read("trace_loss_backward_ms")
            == 1e3 * loss["seconds"])
    backend = startup_rules.event_ms(STEP_RECORDS, "backend")
    assert (_read("step_cache_retrieval_ms") + _read("step_load_ms")
            == backend == 1500.0)
    # The self time of the loss and of every span under it is the loss.
    under = [s for s in STEP if s["path"].split("/")[0] == L]
    assert sum(s["self_seconds"] for s in under) == loss["seconds"]


@pytest.mark.parametrize("metric", list(METRICS))
@pytest.mark.parametrize("missing", ["compile_evicted", "compile_spans"])
def test_a_reader_gives_nothing_for_the_parent(monkeypatch, capsys, metric,
                                               missing):
    """A program without the new spans (or without any): no number, no
    line, no error -- for the records' readers too, whose sums a log that
    cannot say what it dropped does not vouch for."""
    _made_up(monkeypatch)
    monkeypatch.delattr(hvd, missing)
    assert _read(metric) is None
    assert capsys.readouterr().out == ""


def test_a_program_whose_table_lacks_the_prefixes_gives_nothing(monkeypatch):
    _made_up(monkeypatch)
    monkeypatch.delattr(scopes, "RULE")
    assert all(_read(metric) is None for metric in METRICS)


@pytest.mark.parametrize("evicted", [(3, 0), (0, 1)])
def test_a_log_that_lost_its_oldest_part_gives_nothing_and_says_why(
        monkeypatch, capsys, evicted):
    _made_up(monkeypatch, evicted=evicted)
    assert all(_read(metric) is None for metric in METRICS)
    line, = capsys.readouterr().out.splitlines()
    assert line.startswith("[benchmark] start-up rules: the compile log "
                           f"dropped {evicted[0]} span(s) and {evicted[1]} "
                           "record(s)")


def test_spans_the_program_never_opened_give_nothing(monkeypatch):
    """ResNet-50's step has no rule; a step traced before PR 67's
    ``make_train_step`` has no stamp; a cache miss has no load."""
    _made_up(monkeypatch, spans=[
        {k: v for k, v in s.items() if k != scopes.FORWARD_SECONDS}
        for s in STEP if not s["name"].startswith(scopes.RULE)])
    assert _read("trace_rules_ms") is None
    assert _read("trace_layers_self_ms") == 1500.0
    for metric in ("trace_loss_forward_ms", "trace_loss_backward_ms",
                   "trace_backward_self_ms"):
        assert _read(metric) is None
    missed = [r for r in STEP_RECORDS if r["event"] != "cache_hit"]
    _made_up(monkeypatch, step_records=missed)
    assert _read("step_cache_retrieval_ms") is None
    assert _read("step_load_ms") is None
    _made_up(monkeypatch, step_records=[
        r for r in STEP_RECORDS if not r["event"].startswith("cache")])
    assert _read("step_load_ms") is None       # the CPU's: no cache asked
    assert _read("state_backend_ms") == 2250.0


def test_the_rules_line_is_said_once_a_run(monkeypatch, capsys):
    _made_up(monkeypatch)
    for metric in METRICS:
        _read(metric)
    line, = capsys.readouterr().out.splitlines()
    assert line.startswith("[benchmark] start-up rules, ms: ")
    for part in (f"{DENSE} x1 2000.000 (self 500.000)",
                 f"{ROUTED} x1 3000.000 (self 1000.000)",
                 f"{FLASH_F} x1 500.000 (self 125.000)",
                 f"{FLASH_B} x2 1000.000 (self 750.000)",
                 f"{LIVE_B} x1 2000.000 (self 1500.000)",
                 f"{L} 10000.000 = forward 6000.000 + backward 4000.000 "
                 f"(backward self 1500.000)",
                 "self seconds of the 13 spans under it add up to 10000.000",
                 "2 layer.* and 5 rule.* spans in the step's trace",
                 "evicted: 0 spans, 0 records"):
        assert part in line, part


def test_the_reductions():
    assert startup_rules.by_name(STEP)[1] == [DENSE, 1, 2000.0, 500.0]
    assert startup_rules.halves(STEP, L, scopes.FORWARD_SECONDS) == {
        "forward": 6000.0, "backward": 4000.0, "backward_self": 1500.0}
    assert startup_rules.halves(STEP[1:], L, scopes.FORWARD_SECONDS) is None
    # A step traced twice: both losses' halves, each with its own children.
    twice = STEP + [{**s, "began": s["began"] + 100.0} for s in STEP]
    assert startup_rules.halves(twice, L, scopes.FORWARD_SECONDS) == {
        "forward": 12000.0, "backward": 8000.0, "backward_self": 3000.0}
    assert startup_rules.event_ms(STATE_RECORDS, "cache_retrieval") is None
    assert startup_rules.retrieval_and_load(STEP_RECORDS) == {
        "retrieval": 250.0, "load": 1250.0}
    assert startup_rules.retrieval_and_load(STATE_RECORDS) is None


def test_the_programs_own_log_is_read(capsys):
    """Against the process's real log: no evictions are assumed (a worker
    that compiled thousands of programs may have some), so either a line
    of rules or the line that says why not."""
    hvd.init()
    startup_rules._say_rules.cache_clear()
    _read("state_trace_ms")
    assert "[benchmark] start-up rules" in capsys.readouterr().out


def test_the_manifests_nine_entries():
    listed = manifest.load()
    entries = {m["name"]: m for m in listed["per_layer"]}
    names = [m["name"] for m in listed["per_layer"]]
    # In the issue's order, side by side (a later PR's entries come behind).
    first = names.index("trace_loss_forward_ms")
    assert names[first:first + len(METRICS)] == list(METRICS)
    for name, (source, cells) in METRICS.items():
        # (A later cell is appended to the list; these are all in it.)
        assert entries[name] == {
            "name": name, "unit": "ms", "better": "lower", "source": source,
            "layer": "entry and init", "moves": "setup_s",
            "workloads": entries[name]["workloads"]}
        assert entries[name]["workloads"][:len(cells)] == cells or set(
            cells) <= set(entries[name]["workloads"])
    for cell in ALL_CELLS:
        reported = {m["name"] for m in manifest.cell(cell)["per_layer"]}
        assert set(METRICS) - {"trace_rules_ms"} <= reported
        assert ("trace_rules_ms" in reported) == (cell in DECODERS)
    # The accepted readers of the same layer stay, and stay as they were.
    assert {"trace_loss_self_ms", "step_backend_ms", "state_s"} <= set(names)
