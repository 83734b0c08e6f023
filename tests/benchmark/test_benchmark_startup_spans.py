"""The readers of the program's start-up spans (``benchmark/startup_spans.py``
and the ten ``benchmark/metrics`` files that call it) against hand-made span
lists, against a program that keeps no spans, and their entries in the
manifest.  No test here traces a step or reads a clock."""

import pytest

import horovod_tpu.jax as hvd
from benchmark import manifest, startup_spans
from horovod_tpu.common import scopes

if not hasattr(scopes, "MOSAIC"):       # these files laid over the parent
    pytest.skip("the program keeps no spans", allow_module_level=True)

ALL_CELLS = [w["name"] for w in manifest.load()["workloads"]]
DECODERS = [name for name in ALL_CELLS if not name.startswith("resnet")]
#: name -> (unit, source, the cells that report it)
METRICS = {
    "import_hvd_ms": ("ms", "program_span", ALL_CELLS),
    "init_ms": ("ms", "program_span", ALL_CELLS),
    "init_native_ms": ("ms", "program_span", ALL_CELLS),
    "trace_attn_ms": ("ms", "program_span", DECODERS),
    "trace_ffn_ms": ("ms", "program_span", DECODERS),
    "trace_head_ms": ("ms", "program_span", DECODERS),
    "trace_optimizer_ms": ("ms", "program_span", ALL_CELLS),
    "trace_kernels_ms": ("ms", "program_span", DECODERS),
    "trace_kernel_calls": ("count", "program_counter", DECODERS),
    "trace_loss_self_ms": ("ms", "program_span", ALL_CELLS),
}


def _span(path, began, seconds, self_seconds=None, **flags):
    return {"name": path.rsplit("/", 1)[-1], "path": path, "began": began,
            "seconds": seconds, "self_seconds":
                seconds if self_seconds is None else self_seconds, **flags}


L, A, F, H = scopes.LOSS, scopes.BLOCK_ATTN, scopes.BLOCK_FFN, scopes.HEAD
#: What ``hvd.compile_spans()`` of a made-up process gives: the start-up,
#: another program's trace, and a step of two layers traced ONCE.
START = [
    _span(scopes.IMPORT, 0.0, 2.5, 1.5, jax_was_imported=True),
    _span(f"{scopes.IMPORT}/{scopes.IMPORT_MODELS}", 1.0, 1.0),
    _span(scopes.INIT, 3.0, 0.25, 0.05),
    _span(f"{scopes.INIT}/{scopes.INIT_NATIVE}", 3.0, 0.125),
    _span(f"{scopes.INIT}/{scopes.INIT_CACHE}", 3.2, 0.075),
]
OTHER = [_span(H, 4.0, 64.0)]            # make_state's: in no step reader
STEP = [
    _span(L, 10.0, 8.0, 2.0),
    _span(f"{L}/{A}", 10.5, 1.5, 0.5),
    _span(f"{L}/{A}/{scopes.FLASH_FWD}", 10.6, 1.0, 0.25),
    _span(f"{L}/{A}/{scopes.FLASH_FWD}/{scopes.MOSAIC_FLASH_FWD}", 10.7,
          0.75),
    _span(f"{L}/{F}", 12.0, 0.5),
    _span(f"{L}/{A}", 12.5, 0.5),
    # A block entered inside a block counts once, in the outer one.
    _span(f"{L}/{A}/{A}", 12.6, 0.25),
    _span(f"{L}/{F}", 13.0, 1.0),
    _span(f"{L}/{scopes.LOOP_EXIT}/{H}", 14.0, 0.25),
    _span(f"{L}/{H}", 14.5, 0.5, 0.25),
    _span(f"{L}/{H}/{scopes.LOOP_EXIT}/{H}", 14.6, 0.25),
    # The backward rule's Python, at the top of the loss.
    _span(f"{L}/{scopes.FLASH_BWD}", 16.0, 1.0, 0.5),
    _span(f"{L}/{scopes.FLASH_BWD}/{scopes.MOSAIC_FLASH_BWD}", 16.25, 0.5),
    _span(scopes.OPTIMIZER, 18.0, 0.5),
    _span(f"{scopes.OPTIMIZER}/{scopes.allreduce_scope('data')}", 18.1, 0.1),
    _span(scopes.APPLY, 18.5, 0.25),
    _span(scopes.AUX_ALLREDUCE, 18.75, 0.125),
]
EXPECTED = {
    "import_hvd_ms": 2500.0, "init_ms": 250.0, "init_native_ms": 125.0,
    "trace_attn_ms": 2000.0, "trace_ffn_ms": 1500.0, "trace_head_ms": 750.0,
    "trace_optimizer_ms": 875.0, "trace_kernels_ms": 1250.0,
    "trace_kernel_calls": 2, "trace_loss_self_ms": 2000.0,
}


@pytest.fixture
def made_up(monkeypatch):
    def compile_spans(program=None):
        if program is None:
            return [dict(s) for s in START + OTHER + STEP]
        assert program == hvd.TRAIN_STEP_PROGRAM
        return [dict(s) for s in STEP]

    monkeypatch.setattr(hvd, "compile_spans", compile_spans)
    startup_spans._say_tree.cache_clear()
    yield
    startup_spans._say_tree.cache_clear()


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_reader_against_a_hand_made_span_list(made_up, metric):
    value = manifest.load_reader(metric)({"trace": None})
    assert value == EXPECTED[metric]
    assert type(value) is type(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_reader_gives_nothing_for_a_program_without_spans(monkeypatch,
                                                            capsys, metric):
    """The parent of the PR that added ``compile_spans``: no number, no
    line, no error."""
    monkeypatch.delattr(hvd, "compile_spans")
    startup_spans._say_tree.cache_clear()
    assert manifest.load_reader(metric)({"trace": None}) is None
    assert capsys.readouterr().out == ""


def test_a_span_the_program_never_opened_gives_nothing(monkeypatch):
    """A step without a block (ResNet-50's) or without a Mosaic call: None
    for the sums, 0 for the count."""
    monkeypatch.setattr(hvd, "compile_spans", lambda program=None: [
        dict(s) for s in STEP if s["path"].split("/")[0] != L])
    startup_spans._say_tree.cache_clear()
    for metric in ("trace_attn_ms", "trace_ffn_ms", "trace_head_ms",
                   "trace_kernels_ms", "trace_loss_self_ms", "init_ms"):
        assert manifest.load_reader(metric)({"trace": None}) is None
    assert manifest.load_reader("trace_kernel_calls")({"trace": None}) == 0
    assert manifest.load_reader("trace_optimizer_ms")(
        {"trace": None}) == EXPECTED["trace_optimizer_ms"]
    startup_spans._say_tree.cache_clear()


def test_the_whole_tree_is_said_once_a_run(made_up, capsys):
    for metric in sorted(METRICS):
        manifest.load_reader(metric)({"trace": None})
    said = capsys.readouterr().out.splitlines()
    assert len(said) == 1
    line, = said
    assert line.startswith("[benchmark] start-up spans, ms: ")
    # The start-up's spans by their totals; the step's paths with their
    # entries, total and self time; nothing of another program's trace.
    for part in (f"{scopes.IMPORT} 2500.000",
                 f"{scopes.IMPORT}/{scopes.IMPORT_MODELS} 1000.000",
                 f"{scopes.INIT}/{scopes.INIT_NATIVE} 125.000",
                 f"{L} x1 8000.000 (self 2000.000)",
                 f"{L}/{A} x2 2000.000 (self 1000.000)",
                 f"{L}/{F} x2 1500.000 (self 1500.000)",
                 f"{L}/{scopes.FLASH_BWD}/{scopes.MOSAIC_FLASH_BWD} x1 "
                 f"500.000 (self 500.000)",
                 f"{len(STEP)} spans in the step's trace, "
                 f"{len(START + OTHER + STEP)} in the log"):
        assert part in line, part
    assert "64000" not in line


def test_the_reductions():
    assert startup_spans.tree(STEP)[1] == [f"{L}/{A}", 2, 2000.0, 1000.0]
    assert [s["path"] for s in startup_spans.outermost(STEP, H)] == [
        f"{L}/{scopes.LOOP_EXIT}/{H}", f"{L}/{H}"]
    assert len(startup_spans.named(STEP, H)) == 3
    assert startup_spans.total_ms([]) is None
    # The parts of the loss and its self time are at most the loss.
    parts = sum(EXPECTED[m] for m in ("trace_attn_ms", "trace_ffn_ms",
                                      "trace_head_ms", "trace_loss_self_ms"))
    assert parts <= 1e3 * STEP[0]["seconds"]


def test_the_programs_own_spans_are_read(capsys):
    """Against the process's real log: the import and ``hvd.init()`` ran
    before any test did."""
    hvd.init()
    startup_spans._say_tree.cache_clear()
    imported = manifest.load_reader("import_hvd_ms")({"trace": None})
    init = manifest.load_reader("init_ms")({"trace": None})
    assert imported > 0 and init > 0
    assert "start-up spans, ms: " in capsys.readouterr().out
    startup_spans._say_tree.cache_clear()


def test_the_manifests_ten_entries():
    listed = manifest.load()
    entries = listed["per_layer"][-len(METRICS):]
    assert [m["name"] for m in entries] == list(METRICS)
    for metric in entries:
        unit, source, cells = METRICS[metric["name"]]
        assert metric == {
            "name": metric["name"], "unit": unit, "better": "lower",
            "source": source, "layer": "entry and init", "moves": "setup_s",
            "workloads": cells}
    assert len(DECODERS) == len(ALL_CELLS) - 1 >= 10
    for cell in ALL_CELLS:
        reported = {m["name"] for m in manifest.cell(cell)["per_layer"]}
        assert {name for name, (_, _, cells) in METRICS.items()
                if cell in cells} <= reported
        assert ("trace_attn_ms" in reported) == (cell in DECODERS)
