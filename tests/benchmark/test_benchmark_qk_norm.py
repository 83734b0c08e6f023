"""``qk_norm_ms`` (PR 48): the manifest's one new entry, and the reader of
``hvd.attn.qknorm`` (``benchmark/qk_norm_scopes.py``) on hand-built events
and on a tiny sparse-attention step traced on a v5e: what is under the
scope counts in ``qk_norm_ms``, Mosaic calls (``hvd.rope`` nests inside it)
and XLA operations alike, whatever implements the norm; the scope lies
inside ``hvd.block.attn``, so no class of ``benchmark/scopes.py`` moves; a
program without the scope gives no number."""

import argparse
import gzip
import os

import pytest

from benchmark import manifest, qk_norm_scopes, scopes
from horovod_tpu.common import scopes as names

METRIC = "qk_norm_ms"
CELLS = ["keye-vl-2.0-30b-a3b.train-s8k-b2",
         "qwen3-next-80b-a3b.train-s8k-b2"]
# Hidden 256; two layers of 2 query heads over 1 key-value head of 128 (so
# the normed pass engages), an indexer of 2 heads of 64 that keeps 128 of
# 512 keys, experts 4 to 7 of 16 held; 1 x 512 tokens: traced on one TPU v5e
# chip by this harness (PR 48), cut by ``benchmark.xspace.trim`` to its
# first two steps and to the lines the reductions read; gzipped.
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-sparse-qknorm-v5e.xspace.gz")

STEP = "jit(hvd_train_step)/hvd.loss/"
ATTN = "layer_1/hvd.block.attn/attn/"
FWD = STEP + "jvp(LlamaModel)/" + ATTN
REC = (STEP + "transpose(jvp(LlamaModel))/hvd.loss/jvp(LlamaModel)/"
       "checkpoint/rematted_computation/" + ATTN)
BWD = STEP + "transpose(jvp(LlamaModel))/" + ATTN
CALL = ('%rope.1 = bf16[2,8192,4096]{2,1,0} custom-call(%fusion.2), '
        'custom_call_target="tpu_custom_call"')
RESHAPE = "%reshape.2 = f32[2,8192,4096]{2,1,0} reshape(%fusion.4)"
FUSION = "%fusion.7 = bf16[2,8192,4096]{2,1,0} fusion(%a), kind=kOutput"


def test_the_manifests_one_new_entry():
    listed = manifest.load()
    metric, = (m for m in listed["per_layer"] if m["name"] == METRIC)
    assert metric == {
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "kernels",
        "moves": "step_ms_p90", "workloads": CELLS}
    # Every cell whose model norms q and k a head at a time, and no other
    # (``olmo-hybrid-7b`` norms all of a token's heads at once).
    def norms_a_head(workload):
        cell = manifest.cell(workload)
        llama = getattr(manifest.load_job(cell["config"]["job"]).build(
            cell["config"], cell["traffic"], cell["chips"]), "llama", None)
        return bool(llama and llama.qk_norm and llama.qk_norm_over == "head")

    assert [w["name"] for w in listed["workloads"]
            if norms_a_head(w["name"])] == CELLS
    assert callable(manifest.load_reader(METRIC))
    assert os.path.exists(manifest.metric_path(METRIC))


@pytest.mark.parametrize("op_name, under", [
    (FWD + "hvd.attn.qknorm/hvd.rope/pallas_call", True),
    (FWD + "hvd.attn.qknorm/q_norm/mul", True),
    (FWD + "hvd.attn.window/hvd.attn.qknorm/hvd.rope/pallas_call", True),
    (REC + "hvd.attn.qknorm/hvd.rope/pallas_call", True),
    (BWD + "hvd.attn.qknorm/hvd.rope/pallas_call", True),
    (BWD + "transpose(jvp(hvd.attn.qknorm))/k_norm/reduce_sum", True),
    (FWD + "hvd.rope/pallas_call", False),
    (FWD + "q_norm/mul", False),
    (FWD + "wq/dot_general", False),
    (FWD + "hvd.flash.fwd/pallas_call", False),
])
def test_classify_by_the_new_scope(op_name, under):
    assert qk_norm_scopes.under_qk_norm(op_name, names) is under
    # Inside the mixer's block either way: no class of the accepted
    # partition moves.
    assert scopes.classify(FUSION, op_name, names) == (
        "backward" if "transpose(" in op_name else "forward")


def test_reader_on_hand_built_events(monkeypatch):
    step = "jit_hvd_train_step(1)"
    norm = "hvd.attn.qknorm/"
    ops = [((FUSION, FWD + "wq/dot_general"), 0.0, 1e-3),
           ((CALL, FWD + norm + "hvd.rope/pallas_call"), 1e-3, 2e-3),
           ((RESHAPE, FWD + norm + "q_norm/mul"), 2e-3, 2.5e-3),
           ((CALL, REC + norm + "hvd.rope/pallas_call"), 3e-3, 4e-3),
           ((CALL, BWD + norm + "hvd.rope/pallas_call"), 5e-3, 6.5e-3),
           ((FUSION, BWD + "wq/dot_general"), 7e-3, 10e-3)]
    events = {"devices": {0: {
        "ops": ops + [((n, o), a + 10e-3, b + 10e-3) for (n, o), a, b in ops],
        "modules": [(step, 0.0, 10e-3), (step, 10e-3, 20e-3)]}}}
    assert qk_norm_scopes.qk_norm_ms(events, names) == pytest.approx(4.0)
    # A model without a QK-norm never enters the scope: nothing to count.
    assert qk_norm_scopes.qk_norm_ms(
        {"devices": {0: {"ops": ops[:1], "modules": [(step, 0.0, 10e-3)]}}},
        names) is None
    monkeypatch.setattr(qk_norm_scopes.scopes, "read_events",
                        lambda path: events)
    monkeypatch.setattr(qk_norm_scopes.trace, "find_xplane",
                        lambda trace_dir: __file__)
    qk_norm_scopes._reduce_file.cache_clear()
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {}}}
    assert manifest.load_reader(METRIC)(ctx) == pytest.approx(4.0)
    assert manifest.load_reader(METRIC)({**ctx, "trace": None}) is None
    # A program without the scope (the parent) gives no number, and no error.
    monkeypatch.setattr(qk_norm_scopes.scopes, "program_scopes",
                        lambda: argparse.Namespace(LOSS="hvd.loss",
                                                   ROPE="hvd.rope"))
    qk_norm_scopes._reduce_file.cache_clear()
    assert manifest.load_reader(METRIC)(ctx) is None
    monkeypatch.setattr(qk_norm_scopes.scopes, "program_scopes",
                        lambda: None)
    qk_norm_scopes._reduce_file.cache_clear()
    assert manifest.load_reader(METRIC)(ctx) is None
    qk_norm_scopes._reduce_file.cache_clear()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_recorded_trace_holds_the_normed_pass_under_the_scope(recorded):
    events = scopes.read_events(recorded)
    assert sorted(events["devices"]) == [0]
    ops = events["devices"][0]["ops"]
    under = [(text, op_name) for (text, op_name), _, _ in ops
             if qk_norm_scopes.under_qk_norm(op_name, names)]
    assert under
    # In both layers, inside the mixer's block, in all three passes.
    assert {op.split("/layer_")[1][0] for _, op in under} == {"0", "1"}
    assert all(names.BLOCK_ATTN in op for _, op in under)
    calls = [op for text, op in under
             if scopes.trace.op_kind(text) == "mosaic"]
    assert calls and all(names.ROPE in op for op in calls)
    assert any(names.REMATTED in op for op in calls)
    assert any("transpose(" in op for op in calls)
    assert any("transpose(" not in op for op in calls)
    # Every rotation of q and k is under the scope; the indexer's own
    # (64 wide, jnp) is not.
    assert not [op for (text, op), _, _ in ops
                if names.ROPE in op and names.QK_NORM not in op]
    # No operation of the module's is left: the norm is in the calls.
    assert not [op for _, op in under if "q_norm" in op or "k_norm" in op]
    assert os.path.getsize(RECORDED) < 500_000


def test_recorded_step_by_the_new_scope(recorded, monkeypatch):
    events = scopes.read_events(recorded)
    ms = qk_norm_scopes.qk_norm_ms(events, names)
    attn = scopes.partition(events, names)
    assert attn is not None and 0 < ms
    monkeypatch.setattr(qk_norm_scopes.trace, "find_xplane",
                        lambda trace_dir: recorded)
    qk_norm_scopes._reduce_file.cache_clear()
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {}}}
    assert manifest.load_reader(METRIC)(ctx) == pytest.approx(ms)
    qk_norm_scopes._reduce_file.cache_clear()
