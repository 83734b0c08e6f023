"""``gdn_solve_ms`` (PR 47): the manifest's one new entry, and the reader of
``hvd.gdn.solve`` (``benchmark/gdn_solve_scopes.py``) on hand-built events and
on a tiny hybrid step traced on a v5e: what is under the scope counts in
``gdn_solve_ms`` AND, the scope lying inside ``hvd.gdn.scan``, in
``gdn_scan_ms``; a program without the scope gives no number."""

import argparse
import gzip
import os

import pytest

from benchmark import gdn_scopes, gdn_solve_scopes, manifest, scopes
from horovod_tpu.common import scopes as names

METRIC = "gdn_solve_ms"
CELLS = ["olmo-hybrid-7b.train-s8k", "qwen3-next-80b-a3b.train-s8k-b2"]
# Hidden 256; three linear layers of 2 heads, keys 96 and values 192 wide, 4
# taps, and one softmax layer of 2 heads of 128 that do not rotate; 1 x 512
# tokens (one slab of 8 chunks, 16 systems a layer), ``layer_keep_attention``:
# traced on one TPU v5e chip by this harness (PR 47), cut by
# ``benchmark.xspace.trim`` to its first two steps and to the lines the
# reductions read; gzipped.  Named ``.xspace.gz`` as PERF.md's Open question
# 23 says (its Mosaic calls are not all the flash kernel's).
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-hybrid-solve-v5e.xspace.gz")

STEP = "jit(hvd_train_step)/hvd.loss/"
FWD = (STEP + "jvp(LlamaModel)/layer_1/hvd.block.attn/linear/hvd.gdn.scan/"
       "while/body/closed_call/")
REC = (STEP + "transpose(jvp(LlamaModel))/hvd.loss/jvp(LlamaModel)/"
       "checkpoint/rematted_computation/layer_1/hvd.block.attn/linear/"
       "hvd.gdn.scan/while/body/closed_call/")
BWD = (STEP + "transpose(jvp(LlamaModel))/layer_1/hvd.block.attn/linear/"
       "hvd.gdn.scan/while/body/closed_call/")
CALL = ('%_solve.1 = f32[64,64,240]{2,1,0} custom-call(%copy.2), '
        'custom_call_target="tpu_custom_call"')
COPY = "%copy.2 = f32[240,64,64]{0,2,1} copy(%bitcast.4)"
FUSION = "%fusion.7 = f32[8,1,30,64,64]{4,3,2,1,0} fusion(%a), kind=kOutput"


def test_the_manifests_one_new_entry():
    listed = manifest.load()
    metric = listed["per_layer"][-1]
    assert metric == {
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "kernels",
        "moves": "step_ms_p90", "workloads": CELLS}
    assert [m["workloads"] for m in listed["per_layer"]
            if m["name"] == "gdn_scan_ms"] == [CELLS]
    assert callable(manifest.load_reader(METRIC))


@pytest.mark.parametrize("op_name, solve, scan", [
    (FWD + "hvd.gdn.solve/jit(_solve)/pallas_call", True, True),
    (FWD + "hvd.gdn.solve/jit(_solve)/transpose", True, True),
    (REC + "hvd.gdn.solve/jit(_solve)/pallas_call", True, True),
    (BWD + "jvp(hvd.gdn.solve)/jit(_solve)/pallas_call", True, True),
    (BWD + "transpose(jvp(hvd.gdn.solve))/dot_general", True, True),
    (BWD + "hvd.gdn.solve/dot_general", True, True),
    (FWD + "dot_general", False, True),
    (STEP + "jvp(LlamaModel)/layer_1/hvd.block.attn/linear/hvd.gdn.conv/"
     "pallas_call", False, False),
    (STEP + "jvp(LlamaModel)/layer_3/hvd.block.attn/attn/wq/dot_general",
     False, False),
])
def test_classify_by_the_new_scope(op_name, solve, scan):
    assert gdn_solve_scopes.under_solve(op_name, names) is solve
    # The accepted reader of the rule's three kinds does not know the scope
    # and walks outward past it: the solve is the scan's.
    assert (gdn_scopes.classify(op_name, names) == "scan") is scan


def test_reader_on_hand_built_events(monkeypatch):
    step = "jit_hvd_train_step(1)"
    solve = "hvd.gdn.solve/jit(_solve)/"
    ops = [((FUSION, FWD + "dot_general"), 0.0, 1e-3),
           ((COPY, FWD + solve + "transpose"), 1e-3, 1.5e-3),
           ((CALL, FWD + solve + "pallas_call"), 1.5e-3, 2.5e-3),
           ((CALL, REC + solve + "pallas_call"), 3e-3, 4e-3),
           ((CALL, BWD + "jvp(hvd.gdn.solve)/jit(_solve)/pallas_call"),
            5e-3, 6e-3),
           ((FUSION, BWD + "hvd.gdn.solve/dot_general"), 6e-3, 6.5e-3),
           ((FUSION, BWD + "dot_general"), 7e-3, 10e-3)]
    events = {"devices": {0: {
        "ops": ops + [((n, o), a + 10e-3, b + 10e-3) for (n, o), a, b in ops],
        "modules": [(step, 0.0, 10e-3), (step, 10e-3, 20e-3)]}}}
    assert gdn_solve_scopes.solve_ms(events, names) == pytest.approx(4.0)
    # .. and in the scan's metric too, with the rule's other operations.
    assert gdn_scopes.partition(events, names)["scan"] == pytest.approx(8.0)
    # A rule whose systems the ``jnp`` body solved under no scope of this
    # name (the parent's program run by this reader) has nothing to count.
    assert gdn_solve_scopes.solve_ms(
        {"devices": {0: {"ops": ops[:1], "modules": [(step, 0.0, 10e-3)]}}},
        names) is None
    monkeypatch.setattr(gdn_solve_scopes.scopes, "read_events",
                        lambda path: events)
    monkeypatch.setattr(gdn_solve_scopes.trace, "find_xplane",
                        lambda trace_dir: __file__)
    gdn_solve_scopes._reduce_file.cache_clear()
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {}}}
    assert manifest.load_reader(METRIC)(ctx) == pytest.approx(4.0)
    assert manifest.load_reader(METRIC)({**ctx, "trace": None}) is None
    # A program without the scope (the parent) gives no number, and no error.
    monkeypatch.setattr(gdn_solve_scopes.scopes, "program_scopes",
                        lambda: argparse.Namespace(LOSS="hvd.loss",
                                                   GDN_SCAN="hvd.gdn.scan"))
    gdn_solve_scopes._reduce_file.cache_clear()
    assert manifest.load_reader(METRIC)(ctx) is None
    monkeypatch.setattr(gdn_solve_scopes.scopes, "program_scopes",
                        lambda: None)
    gdn_solve_scopes._reduce_file.cache_clear()
    assert manifest.load_reader(METRIC)(ctx) is None
    gdn_solve_scopes._reduce_file.cache_clear()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_recorded_trace_holds_the_solve_inside_the_scan(recorded):
    events = scopes.read_events(recorded)
    assert sorted(events["devices"]) == [0]
    ops = events["devices"][0]["ops"]
    solved = [(text, op_name) for (text, op_name), _, _ in ops
              if gdn_solve_scopes.under_solve(op_name, names)]
    assert solved
    # In the three linear layers and not in the softmax one, inside the
    # rule's scope, in all three passes.
    assert {op.split("/layer_")[1][0] for _, op in solved} == {"0", "1", "2"}
    for _, op_name in solved:
        assert gdn_scopes.classify(op_name, names) == "scan", op_name
    calls = [op for text, op in solved
             if scopes.trace.op_kind(text) == "mosaic"]
    assert calls and any(names.REMATTED in op for op in calls)
    assert any("transpose(" in op for op in calls)
    assert any("transpose(" not in op for op in calls)
    # The rule's own Mosaic calls are the solve's and no other's.
    assert not [op for (text, op), _, _ in ops
                if scopes.trace.op_kind(text) == "mosaic"
                and names.GDN_SCAN in op and names.GDN_SOLVE not in op]
    assert os.path.getsize(RECORDED) < 500_000


def test_recorded_step_by_the_new_scope(recorded, monkeypatch):
    events = scopes.read_events(recorded)
    solve = gdn_solve_scopes.solve_ms(events, names)
    rule = gdn_scopes.partition(events, names)
    assert 0 < solve < rule["scan"]
    monkeypatch.setattr(gdn_solve_scopes.trace, "find_xplane",
                        lambda trace_dir: recorded)
    gdn_solve_scopes._reduce_file.cache_clear()
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {}}}
    assert manifest.load_reader(METRIC)(ctx) == pytest.approx(solve)
    gdn_solve_scopes._reduce_file.cache_clear()
