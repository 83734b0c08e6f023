"""The looped configuration (``ouro-2.6b-ut4``): its job against wrong
versions of itself through the comparison that decides ``correct``, its
arithmetic, the readers of the loop's scopes on hand-built events and on a
tiny looped step traced on a v5e, and the traced tiny run that the
four-chip cell had while it was the manifest's last entry."""

import argparse
import dataclasses
import gzip
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark import (arithmetic_loop, compare, loop_scopes, manifest, run,
                       scopes)
from horovod_tpu.common import scopes as names
from horovod_tpu.models import LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.losses import softmax_cross_entropy

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_sizes import TINY  # noqa: E402

CELL = "ouro-2.6b-ut4.train-s8k"
# Hidden 256, 2 heads x 128, FFN 512, vocab 1024, two layers, four passes,
# 2 x 256 tokens, ``layer_keep_attention``: traced on one TPU v5e chip by
# this harness (PR 26), cut by ``benchmark.xspace.trim`` to its first three
# steps and to the lines the reductions read; gzipped.
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-looped-decoder-v5e.xplane.pb.gz")


def _tiny_job(workload=CELL):
    cell = manifest.cell(workload)
    tiny = TINY[cell["config"]["job"]]
    config = {**cell["config"], **tiny["config"]}
    traffic = {**cell["traffic"], **tiny["traffic"]}
    job = manifest.load_job(config["job"]).build(config, traffic, 1)
    return job, manifest.load_reference(config["reference"]), config


# -- the configuration and its job -------------------------------------------

def test_the_configuration_is_ouro_as_published_but_for_depth():
    looped = manifest.cell(CELL)["config"]
    plain = manifest.cell("ouro-2.6b.train-s8k")["config"]
    assert looped["total_ut_steps"] == 4 and plain["total_ut_steps"] == 1
    assert looped["reduced"] == ["num_hidden_layers", "layer_types"]
    differ = {key for key in plain if key in looped
              and plain[key] != looped[key]} - {
        "job", "reference", "reduced", "reduced_why", "assumed", "checks",
        "training"}
    assert differ == {"total_ut_steps"}        # the loop alone
    assert {"exit_entropy_beta", "exit_gate_init", "norm_between_passes",
            "training", "rope_layout"} <= set(looped["assumed"])
    assert manifest.cell(CELL)["traffic"] == manifest.cell(
        "ouro-2.6b.train-s8k")["traffic"]


def test_job_counts_four_passes_and_four_heads_and_nothing_recomputed():
    job, _, _ = _tiny_job()
    plain, _, _ = _tiny_job("ouro-2.6b.train-s8k")
    assert job.llama.total_ut_steps == 4
    assert job.llama.remat == "layer_keep_attention"
    assert job.flops_per_unit() == 4 * plain.flops_per_unit()
    assert job.units_per_step == plain.units_per_step
    flash, plain_flash = (j.kernel_work_per_step()["flash"]
                          for j in (job, plain))
    assert set(flash) == {"flops", "bytes", "forward", "backward"}
    for work, plain_work in ((flash, plain_flash),
                             (flash["forward"], plain_flash["forward"]),
                             (flash["backward"], plain_flash["backward"])):
        assert work["flops"] == 4 * plain_work["flops"] > 0
        assert work["bytes"] == 4 * plain_work["bytes"] > 0
    assert flash["flops"] == (flash["forward"]["flops"]
                              + flash["backward"]["flops"])
    assert flash["bytes"] == (flash["forward"]["bytes"]
                              + flash["backward"]["bytes"])
    assert job.expected_first_loss() == pytest.approx(
        plain.expected_first_loss() - 0.1 * 1.75 * np.log(2))
    with pytest.raises(ValueError, match="looped"):
        manifest.load_job("looped_lm").build(
            {**job.config, "total_ut_steps": 1},
            manifest.cell(CELL)["traffic"], 1)


def test_the_stack_does_86_per_cent_of_the_cells_matmul_work():
    """The share the issue sized the cell by: nine layers of 51.4M weights
    and 16.8M multiply-adds of causal attention a token at 8192, against a
    100.7M head at every exit."""
    config, traffic = (manifest.cell(CELL)[k] for k in ("config", "traffic"))
    sizes = dict(hidden=config["hidden_size"],
                 layers=config["num_hidden_layers"],
                 heads=config["num_attention_heads"],
                 kv_heads=config["num_key_value_heads"],
                 head_dim=config["head_dim"],
                 ffn=config["intermediate_size"],
                 vocab=config["vocab_size"], seq=traffic["sequence"])
    assert arithmetic_loop.stack_share_of_matmul_work(
        **sizes) == pytest.approx(0.859, abs=0.001)


# -- wrong versions are outside the comparison's limits ----------------------

def _last_exit_alone(job):
    def loss_fn(params, batch):
        hidden, _ = job.model.apply(params, batch[:, :-1])
        logits = job.model.apply(params, hidden[-1], method="head")
        return softmax_cross_entropy(logits, batch[:, 1:])
    return loss_fn


def _a_pass_left_out(job):
    shorter = LlamaModel(dataclasses.replace(job.llama, total_ut_steps=3),
                         attention_fn=flash_attention_fn)
    full = job.model

    def loss_fn(params, batch):
        job.model = shorter
        try:
            return type(job).loss_fn(job, params, batch)
        finally:
            job.model = full
    return loss_fn


def _no_entropy_term(job):
    def loss_fn(params, batch):
        job.beta, beta = 0.0, job.beta
        try:
            return type(job).loss_fn(job, params, batch)
        finally:
            job.beta = beta
    return loss_fn


@pytest.mark.parametrize("defect, least", [
    (None, 0.0), (_last_exit_alone, 0.5), (_a_pass_left_out, 0.2),
    (_no_entropy_term, 0.0)])
def test_comparison_passes_the_job_and_fails_wrong_versions_of_it(defect,
                                                                  least):
    """bf16 against the float32 reference at the tiny size, a live gate:
    the job as it is passes; the last exit's loss alone, three passes for
    four, and a dropped entropy term (which moves the gate's gradient and
    the loss) do not."""
    job, reference, config = _tiny_job()
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    state = jax.jit(job.init_state)(jax.random.key(0))
    gate = state[0]["params"]["exit_gate"]
    gate["kernel"] = (0.3 * jax.random.normal(
        jax.random.key(4), gate["kernel"].shape)).astype(jnp.bfloat16)
    sample = job.make_batch(jax.random.key(2), job.sample_rows)
    if defect is not None:
        job.loss_fn = defect(job)
    found = compare.against_reference(job, reference, config, mesh, state,
                                      sample)
    good = found["reference_loss_close"] and found["reference_grad_close"]
    assert good == (defect is None), found
    assert found["grad_rel_err"] >= least


# -- the readers of the loop's scopes ----------------------------------------

STEP = "jit(hvd_train_step)/hvd.loss/"
BODY = "hvd.loop.pass/while/body/closed_call/LlamaModel.pass_and_exit/"
FWD = STEP + "jvp(LlamaModel)/" + BODY + "layer_0/"
BWD = (STEP + "transpose(jvp(LlamaModel))/" + BODY
       + "LlamaModel.pass_and_exit/checkpoint/")


@pytest.mark.parametrize("op_name, kind, recomputed", [
    (FWD + "mlp/w_down/dot_general", "stack", False),
    (BWD + "layer_0/mlp/w_down/dot_general", "stack", False),
    (BWD + "rematted_computation/layer_0/mlp/w_down/dot_general", "stack",
     True),
    (STEP + "jvp(LlamaModel)/" + BODY + "hvd.loop.exit/"
     "LlamaModel.norm_and_gate/norm_f/mul", "exit", False),
    (STEP + "jvp(hvd.loop.exit)/while/body/closed_call/LlamaModel.head/"
     "lm_head/dot_general", "exit", False),
    (STEP + "transpose(jvp(hvd.loop.exit))/while/body/closed_call/"
     "checkpoint/rematted_computation/LlamaModel.head/lm_head/dot_general",
     "exit", True),
    # The scan's own work: adding up a weight's gradient over the passes.
    (STEP + "transpose(jvp(LlamaModel))/hvd.loop.pass/while/body/add_any",
     "stack", False),
    # The scan's hoisted constants keep the loop's scope and lose the
    # loss's: no part of forward or backward, so none of the loop's.
    ("jit(hvd_train_step)/LlamaModel.pass_and_exit/hvd.loop.pass/layer_0/"
     "attn/mul", None, False),
    (STEP + "jvp(LlamaModel)/tok_emb/take", None, False),
    # A module a user calls rematted_computation outside the loss is none.
    ("jit(hvd_train_step)/hvd.optimizer/rematted_computation/mul", None,
     False),
    ("", None, False),
])
def test_classify_by_the_loops_scopes(op_name, kind, recomputed):
    assert loop_scopes.classify(op_name, names) == (kind, recomputed)


def test_partition_on_hand_built_events():
    fusion = "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop"
    ms = 1e-3
    ops = []
    for t in (0.0, 10 * ms):
        ops += [
            ((fusion, STEP + "jvp(LlamaModel)/tok_emb/take"), t, t + ms),
            ((fusion, FWD + "mul"), t + ms, t + 3 * ms),
            ((fusion, STEP + "jvp(LlamaModel)/" + BODY
              + "hvd.loop.exit/norm_f/mul"),
             t + 3 * ms, t + 3.5 * ms),
            ((fusion, BWD + "rematted_computation/layer_0/mul"),
             t + 4 * ms, t + 5 * ms),
            ((fusion, BWD + "layer_0/mul"), t + 5 * ms, t + 8 * ms),
            ((fusion, STEP + "transpose(jvp(hvd.loop.exit))/checkpoint/"
              "rematted_computation/exp"), t + 8 * ms, t + 8.25 * ms),
            ((fusion, "jit(hvd_train_step)/hvd.apply/add"),
             t + 9 * ms, t + 10 * ms),
        ]
    events = {"devices": {0: {"ops": ops, "modules": [
        ("jit_hvd_train_step(1)", 0.0, 10 * ms),
        ("jit_hvd_train_step(1)", 10 * ms, 20 * ms)]}}}
    assert loop_scopes.partition(events, names) == pytest.approx({
        "stack": 6.0, "exit": 0.75, "recompute": 1.25,
        "recompute_exit": 0.25})
    by_class = scopes.partition(events, names)["classes"]
    assert by_class["forward"] + by_class["backward"] == pytest.approx(
        6.0 + 0.75 + 1.0)                      # and the embedding's lookup


def test_a_program_without_the_loop_gives_no_number():
    fusion = "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop"
    events = {"devices": {0: {
        "ops": [((fusion, STEP + "jvp(LlamaModel)/layer_0/mul"), 0.0, 1.0)],
        "modules": [("jit_hvd_train_step(1)", 0.0, 1.0)]}}}
    assert loop_scopes.partition(events, names) is None
    assert loop_scopes.partition({"devices": {}}, names) is None
    for metric in ("loop_stack_ms", "loop_exit_ms", "recompute_ms"):
        assert manifest.load_reader(metric)({"trace": None}) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_recorded_trace_holds_the_loops_scopes_and_jaxs_name(recorded):
    events = scopes.read_events(recorded)
    assert sorted(events["devices"]) == [0]
    op_names = {op_name for (_, op_name), _, _
                in events["devices"][0]["ops"]}
    held = {scopes.bare(part) for n in op_names
            for part in scopes.components(n)}
    assert {names.LOSS, names.LOOP_PASS, names.LOOP_EXIT, names.REMATTED,
            names.FLASH_FWD} <= held
    assert {scope for scope in held if scope.startswith("hvd.flash.")
            } - {names.FLASH_FWD}                # and a backward pass
    again = [n for n in op_names
             if names.REMATTED in scopes.components(n)]
    assert again and all("transpose(" in n for n in again)
    assert os.path.getsize(RECORDED) < 400_000


def test_recorded_loop_is_the_forward_and_backward_pass_but_for_the_embedding(
        recorded):
    """``loop_stack_ms`` + ``loop_exit_ms`` fall short of ``forward_ms`` +
    ``backward_ms`` by the embedding's lookup and scatter-add and what XLA
    hoists out of the passes, and by little; the layers' recomputed
    forward work is an eighth to a third of the stack's time (a quarter if
    everything ran again; ``layer_keep_attention`` spares the flash call,
    and a repeated forward writes no residuals)."""
    events = scopes.read_events(recorded)
    loop = loop_scopes.partition(events, names)
    by_class = scopes.partition(events, names)["classes"]
    whole = by_class["forward"] + by_class["backward"]
    inside = loop["stack"] + loop["exit"]
    assert 0.0 < whole - inside < 0.1 * whole
    assert loop["stack"] > loop["exit"] > 0.0
    assert 0.125 < (loop["recompute"] - loop["recompute_exit"]) \
        / loop["stack"] < 1 / 3
    assert 0.0 < loop["recompute_exit"] < loop["exit"]


# -- the four-chip cell's traced tiny run ------------------------------------

def test_four_chip_cell_traced_tiny():
    """``test_cell_traced_tiny`` traces the manifest's first and last
    cells; the looped cell is the last now, so the four-chip cell's traced
    run is kept here."""
    workload = next(w["name"] for w in manifest.load()["workloads"]
                    if w["chips"] == 4)
    cell = manifest.cell(workload)
    args = argparse.Namespace(workload=workload, seed=2 ** 31 + 11,
                              seconds=1.0, trace=1)
    result = json.loads(json.dumps(run.run(
        args, start=time.perf_counter(),
        overrides=TINY[cell["config"]["job"]], allow_cpu=True)))
    assert result["correct"] is True, result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) <= {m["name"] for m in cell["per_layer"]}
    assert metrics["compiles_in_window"] == 0
    assert metrics["collective_mb_per_step"] > 0
