"""The ``olmo-hybrid-7b`` configuration and its cell: the manifest's new
entries, the configuration's file against the published config, the job and
its arithmetic against hand counts, the job against wrong versions of itself
through the comparison that decides ``correct``, the readers of the new
scopes on hand-built events and on a tiny step traced on a v5e, and the
traced tiny run that the ``keye-vl-2.0-30b-a3b`` cell had while it was the
manifest's last entry."""

import argparse
import dataclasses
import functools
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

from benchmark import (arithmetic, arithmetic_gdn, compare, gdn_scopes,
                       manifest, run, scopes)
from horovod_tpu.common import scopes as names
from horovod_tpu.models import LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_sizes import TINY  # noqa: E402

CELL = "olmo-hybrid-7b.train-s8k"
METRICS = ("gdn_conv_ms", "gdn_gates_ms", "gdn_scan_ms", "gdn_scan_roofline")
JOINED = ("tokens_per_s_per_chip", "mfu", "block_attn_ms", "block_ffn_ms",
          "head_ms", "flash_ms", "flash_roofline", "flash_fwd_ms",
          "flash_bwd_ms", "flash_fwd_roofline", "flash_bwd_roofline")
# Hidden 256; three linear layers of 2 heads, keys 96 and values 192 wide
# (the published widths of a head), 4 taps, and one softmax layer of 2 heads
# of 128 that do not rotate; 1 x 512 tokens, ``layer_keep_attention``: traced
# on one TPU v5e chip by this harness (PR 38), cut by
# ``benchmark.xspace.trim`` to its first three steps and to the lines the
# reductions read; gzipped.  Named ``.xspace.gz`` as PERF.md's Open question
# 23 says (the accepted tests take every ``*.xplane.pb*`` for a step whose
# Mosaic calls are all the flash kernel's, which this one's happen to be).
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-hybrid-decoder-v5e.xspace.gz")

# https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": ["linear_attention", "linear_attention",
                    "linear_attention", "full_attention"] * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}


def _tiny_job(workload=CELL, **config_changes):
    cell = manifest.cell(workload)
    tiny = TINY[cell["config"]["job"]]
    config = {**cell["config"], **tiny["config"], **config_changes}
    traffic = {**cell["traffic"], **tiny["traffic"]}
    job = manifest.load_job(config["job"]).build(config, traffic, 1)
    return job, manifest.load_reference(config["reference"]), config


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(CELL)


@pytest.fixture(scope="module")
def job(cell):
    return manifest.load_job("hybrid_lm").build(cell["config"],
                                                cell["traffic"], 1)


# -- the manifest and the configuration's file --------------------------------

def test_the_configuration_is_the_published_one_but_for_its_three_cuts(cell):
    config = cell["config"]
    differ = {key for key, value in PUBLISHED.items()
              if config[key] != value}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert (config["num_hidden_layers"], config["vocab_size"]) == (4, 12544)
    # One whole period of the published pattern, which is the floor of four.
    assert config["layer_types"] == PUBLISHED["layer_types"][:4]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 8
    assert deployment["num_hidden_layers_published"] == 32
    assert deployment["vocab_size_published"] == 100352
    assert {"head_dim", "norm_placement", "qk_norm", "rope", "linear_layer",
            "chunk", "initialisation", "training"} <= set(config["assumed"])
    assert config["head_dim"] * config["num_attention_heads"] == (
        config["hidden_size"])
    assert config["training"]["remat"] == "layer_keep_attention"
    limits = config["checks"]["reference"]
    assert limits["parameters"] == "initial" and len(limits["why"]) > 500


def test_the_manifests_new_entries(cell):
    listed = manifest.load()
    entry = next(c for c in listed["configs"] if c["name"] == "olmo-hybrid-7b")
    assert entry["source"] == cell["config"]["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    assert entry["reduced"] == cell["config"]["reduced"]
    assert entry["file"] == "benchmark/configs/olmo-hybrid-7b.json"
    workload = next(w for w in listed["workloads"] if w["name"] == CELL)
    assert workload == {"name": CELL, "config": "olmo-hybrid-7b",
                        "traffic": "train-s8k", "chips": 1,
                        "why": workload["why"]}
    assert cell["traffic"] == manifest.cell("ouro-2.6b.train-s8k")["traffic"]
    assert sum(w["chips"] == 4 for w in listed["workloads"]) == 1
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "step_ms_p90", "peak_hbm_gb", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(METRICS) | set(JOINED[1:]) <= reported
    # Readers that find nothing to read in this cell: no loop to tell
    # recomputed work by, and layers that are no plain decoder's.
    assert not {"recompute_ms", "dense_roofline", "moe_route_ms",
                "sparse_index_ms"} & reported
    per_layer = {m["name"]: m for m in listed["per_layer"]}
    for name in METRICS:
        metric = per_layer[name]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "step_ms_p90"
        assert os.path.exists(manifest.metric_path(name))
        assert metric["layer"] == ("model" if name in METRICS[:2]
                                   else "kernels")
        assert (metric["unit"], metric["better"], metric["source"]) == (
            ("%", "higher", "device_trace") if name.endswith("_roofline")
            else ("ms", "lower", "program_span"))
    for name in JOINED:
        metric = per_layer.get(name) or next(
            m for m in listed["end_to_end"] if m["name"] == name)
        assert metric["workloads"][-1] == CELL


# -- the job and its arithmetic ------------------------------------------------

def test_arithmetic_against_hand_counts(job):
    # One chunk of one head, forward: K K^T, Q K^T, W: 3 x 64^2 x 96; U0 and
    # P U: 2 x 64^2 x 192; W S^T, Q S^T, U^T K: 3 x 64 x 96 x 192; the solve.
    macs = arithmetic_gdn.chunk_rule_macs(key_dim=96, value_dim=192)
    assert macs == 1_179_648 + 1_572_864 + 3_538_944 + 64 ** 3 / 6
    assert arithmetic_gdn.linear_mixer_matmul_params(
        hidden=3840, key_heads=30, value_heads=30, key_dim=96,
        value_dim=192) == 3840 * (2 * 2880 + 3 * 5760 + 60) == 88_704_000
    shape = dict(batch=1, seq=8192, value_heads=30, key_dim=96, value_dim=192)
    assert arithmetic_gdn.scan_flops(**shape) == 3 * 2 * 30 * 128 * macs
    rows = 8192 * 30
    qkv, gates, out = rows * 384 * 2, rows * 8, rows * 192 * 2
    states = 30 * 128 * 96 * 192 * 4
    assert states == 283_115_520
    assert arithmetic_gdn.scan_bytes(**shape) == (
        2 * (qkv + gates + out + states) + qkv + gates)
    # A length that is no multiple of 64 pays for its last chunk whole.
    assert arithmetic_gdn.scan_flops(**{**shape, "seq": 8193}) == (
        3 * 2 * 30 * 129 * macs)
    # One token of the cell, by hand.
    weights = (3 * 88_704_000 + 4 * 3840 * 3840 + 4 * 3 * 3840 * 11008
               + 3840 * 12544)
    pairs = arithmetic.causal_pairs(8192) / 8192
    by_hand = (3 * (2 * weights + 2 * 2 * 30 * 128 * pairs)
               + 3 * 3 * 2 * 30 * 128 * macs / 8192)
    assert job.flops_per_unit() == pytest.approx(by_hand, rel=1e-12)
    # The rule is under one per cent of a step's operations: what it costs
    # is time, not arithmetic.
    assert 3 * arithmetic_gdn.scan_flops(**shape) / 8192 < (
        0.01 * job.flops_per_unit())


def test_kernel_work_counts_one_softmax_layer_and_three_linear_ones(job):
    work = job.kernel_work_per_step()
    shape = dict(batch=1, seq=8192, heads=30, head_dim=128)
    assert work["flash"]["forward"]["flops"] == (
        arithmetic.flash_forward_flops(**shape))
    assert work["flash"]["backward"]["bytes"] == (
        arithmetic.flash_backward_bytes(**shape))
    assert work["flash"]["flops"] == (work["flash"]["forward"]["flops"]
                                      + work["flash"]["backward"]["flops"])
    rule = dict(batch=1, seq=8192, value_heads=30, key_dim=96, value_dim=192)
    assert work["gdn_scan"] == {
        "flops": 3 * arithmetic_gdn.scan_flops(**rule),
        "bytes": 3 * arithmetic_gdn.scan_bytes(**rule)}
    peaks = manifest.peaks("TPU v5 lite")
    least, bound = arithmetic.roofline_seconds(
        work["gdn_scan"]["flops"], work["gdn_scan"]["bytes"], peaks)
    assert bound == "bytes" and least == pytest.approx(4.861e-3, rel=1e-3)


def test_job_builds_the_published_layers(job, cell):
    c = job.llama
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
            c.intermediate_size) == (3840, 30, 30, 128, 11008)
    assert (c.linear_num_key_heads, c.linear_num_value_heads,
            c.linear_key_head_dim, c.linear_value_head_dim,
            c.linear_conv_kernel_dim, c.linear_allow_neg_eigval) == (
                30, 30, 96, 192, 4, True)
    assert c.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert (c.norm_placement, c.qk_norm, c.qk_norm_over, c.rope_theta) == (
        "post", True, "all", None)
    assert c.remat == "layer_keep_attention" and c.num_experts == 1
    shapes = jax.eval_shape(job.init_state, jax.random.key(0))[0]
    assert set(shapes) == {"params"}
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    linear = 88_704_000 + 4 * 11520 + 30 + 30 + 192
    softmax = 4 * 3840 * 3840 + 2 * 3840
    assert count == (3 * linear + softmax + 4 * (3 * 3840 * 11008 + 2 * 3840)
                     + 2 * 12544 * 3840 + 3840) == 928_862_196
    assert count * 14 == pytest.approx(13.004e9, rel=1e-3)
    mixer = shapes["params"]["layer_0"]["linear"]
    assert mixer["wq"]["kernel"].shape == (3840, 2880)
    assert mixer["wv"]["kernel"].shape == (3840, 5760)
    assert mixer["wa"]["kernel"].shape == (3840, 30)
    assert mixer["conv_v"].shape == (4, 5760)
    assert mixer["o_norm"].shape == (192,)
    assert job.expected_first_loss() == pytest.approx(np.log(12544) + 0.5)
    with pytest.raises(ValueError, match="Olmo-Hybrid's decoder layers"):
        manifest.load_job("hybrid_lm").build(
            {**cell["config"], "rope_parameters": {"rope_theta": 500000}},
            cell["traffic"], 1)


# -- wrong versions are outside the comparison's limits ----------------------

def _with_model(job, **changes):
    wrong = LlamaModel(dataclasses.replace(job.llama, **changes),
                       attention_fn=flash_attention_fn)
    right = job.model

    def loss_fn(params, batch):
        job.model = wrong
        try:
            return type(job).loss_fn(job, params, batch)
        finally:
            job.model = right
    return loss_fn


def _beta_under_one(job):
    return _with_model(job, linear_allow_neg_eigval=False)


def _softmax_layers_that_rotate(job):
    return _with_model(job, rope_theta=500000.0)


def _with_rule(job, rule):
    """The job's loss with ``rule(original, q, k, v, g, beta)`` where the
    mixer calls the chunked rule."""
    from horovod_tpu.models import llama

    original = llama.gated_delta_rule

    def loss_fn(params, batch):
        llama.gated_delta_rule = functools.partial(rule, original)
        try:
            return type(job).loss_fn(job, params, batch)
        finally:
            llama.gated_delta_rule = original
    return loss_fn


def _no_decay(job):
    """alpha = 1: the delta rule without its gate."""
    return _with_rule(job, lambda rule, q, k, v, g, beta: rule(
        q, k, v, jnp.zeros_like(g), beta))


def _state_reset_every_chunk(job):
    """Each chunk of 64 from a zero state: attention inside a chunk alone."""
    def chunk_alone(rule, *inputs):
        return rule(*(x.reshape(-1, 64, *x.shape[2:]) for x in inputs)
                    ).reshape(inputs[2].shape)
    return _with_rule(job, chunk_alone)


@pytest.mark.parametrize("defect, least", [
    (None, 0.0), (_beta_under_one, 0.05), (_no_decay, 0.05),
    (_state_reset_every_chunk, 0.05), (_softmax_layers_that_rotate, 0.02)])
def test_comparison_passes_the_job_and_fails_wrong_versions_of_it(defect,
                                                                  least):
    """In float32 at the tiny size, where the job as it is reads 1e-5 and
    every wrong version has to show: beta held under 1, the decay left
    out, the state dropped at every chunk's end, softmax layers that
    rotate.  (At the cell's size in bf16 the limits of the configuration's
    file decide; ``checks.reference.why`` says what they caught there.)"""
    job, reference, config = _tiny_job()
    job.llama = dataclasses.replace(job.llama, dtype=jnp.float32,
                                    logits_dtype=jnp.float32)
    job.model = LlamaModel(job.llama, attention_fn=flash_attention_fn)
    config = {**config, "checks": {**config["checks"], "reference": {
        "parameters": "initial", "loss_abs": 1e-4, "grad_rel": 1e-3}}}
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    state = (jax.tree.map(lambda p: p.astype(jnp.float32),
                          jax.jit(job.init_state)(jax.random.key(0))[0]),
             None)
    sample = job.make_batch(jax.random.key(2), job.sample_rows)
    if defect is not None:
        job.loss_fn = defect(job)
    with jax.default_matmul_precision("highest"):
        found = compare.against_reference(job, reference, config, mesh,
                                          state, sample)
    good = found["reference_loss_close"] and found["reference_grad_close"]
    assert good == (defect is None), found
    assert found["grad_rel_err"] >= least


# -- the readers of the new scopes --------------------------------------------

STEP = "jit(hvd_train_step)/hvd.loss/"
FWD = STEP + "jvp(LlamaModel)/layer_1/hvd.block.attn/linear/"
REC = (STEP + "transpose(jvp(LlamaModel))/hvd.loss/jvp(LlamaModel)/"
       "checkpoint/rematted_computation/layer_1/hvd.block.attn/linear/")
BWD = STEP + "transpose(jvp(LlamaModel))/layer_1/hvd.block.attn/linear/"
MOSAIC = ('%custom-call.7 = (bf16[512,384]{1,0}) custom-call(%a), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.3 = bf16[1,512,384]{2,1,0} fusion(%a), kind=kLoop"


@pytest.mark.parametrize("op_name, kind", [
    (FWD + "hvd.gdn.conv/checkpoint/mul", "conv"),
    (BWD + "hvd.gdn.conv/checkpoint/rematted_computation/mul", "conv"),
    (FWD + "hvd.gdn.gates/wa/dot_general", "gates"),
    (FWD + "hvd.gdn.scan/while/body/closed_call/while/body/dot_general",
     "scan"),
    (REC + "hvd.gdn.scan/while/body/closed_call/while/body/dot_general",
     "scan"),
    (BWD + "transpose(jvp(hvd.gdn.scan))/while/body/dot_general", "scan"),
    (FWD + "wq/dot_general", None),
    (STEP + "jvp(LlamaModel)/layer_3/hvd.block.attn/attn/hvd.flash.fwd/"
     "pallas_call", None),
])
def test_classify_by_the_new_scopes(op_name, kind):
    assert gdn_scopes.classify(op_name, names) == kind


def test_partition_and_readers_on_hand_built_events(monkeypatch):
    step = "jit_hvd_train_step(1)"
    ops = [((FUSION, FWD + "hvd.gdn.conv/checkpoint/mul"), 0.0, 1e-3),
           ((FUSION, FWD + "hvd.gdn.gates/wa/dot_general"), 1e-3, 3e-3),
           ((FUSION, FWD + "hvd.gdn.scan/while/body/dot_general"),
            3e-3, 4e-3),
           # A Mosaic call under the scan's scope counts with it.
           ((MOSAIC, REC + "hvd.gdn.scan/pallas_call"), 4e-3, 6e-3),
           ((FUSION, BWD + "transpose(jvp(hvd.gdn.scan))/while/body/mul"),
            6e-3, 7e-3),
           ((FUSION, FWD + "wq/dot_general"), 7e-3, 9e-3)]
    events = {"devices": {0: {
        "ops": ops + [((n, o), a + 10e-3, b + 10e-3) for (n, o), a, b in ops],
        "modules": [(step, 0.0, 10e-3), (step, 10e-3, 20e-3)]}}}
    kinds = gdn_scopes.partition(events, names)
    assert kinds == pytest.approx({"conv": 1.0, "gates": 2.0, "scan": 4.0,
                                   "scan_recomputed": 2.0})
    assert gdn_scopes.partition(
        {"devices": {0: {"ops": ops[5:], "modules": [(step, 0.0, 10e-3)]}}},
        names) is None
    monkeypatch.setattr(gdn_scopes.scopes, "read_events",
                        lambda path: events)
    monkeypatch.setattr(gdn_scopes.trace, "find_xplane",
                        lambda trace_dir: __file__)
    gdn_scopes._reduce_file.cache_clear()
    work = {"flops": 197e9, "bytes": 1e6}            # 1 ms at the peak
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {"gdn_scan": work}}}
    for metric, value in (("gdn_conv_ms", 1.0), ("gdn_gates_ms", 2.0),
                          ("gdn_scan_ms", 4.0), ("gdn_scan_roofline", 25.0)):
        assert manifest.load_reader(metric)(ctx) == pytest.approx(value)
    ctx["job"]["kernel_work_per_step"] = {}
    assert manifest.load_reader("gdn_scan_roofline")(ctx) is None
    for metric in METRICS:
        assert manifest.load_reader(metric)({**ctx, "trace": None}) is None
    # A program without the scopes (the parent) gives no number.
    monkeypatch.setattr(gdn_scopes.scopes, "program_scopes",
                        lambda: argparse.Namespace(LOSS="hvd.loss"))
    gdn_scopes._reduce_file.cache_clear()
    for metric in METRICS:
        assert manifest.load_reader(metric)(ctx) is None
    gdn_scopes._reduce_file.cache_clear()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_recorded_trace_holds_the_new_scopes(recorded):
    events = scopes.read_events(recorded)
    assert sorted(events["devices"]) == [0]
    ops = events["devices"][0]["ops"]
    held = {scopes.bare(part) for (_, op_name), _, _ in ops
            for part in scopes.components(op_name)}
    assert {names.LOSS, names.GDN_CONV, names.GDN_GATES, names.GDN_SCAN,
            names.BLOCK_ATTN, names.BLOCK_FFN, names.HEAD, names.FLASH_FWD,
            names.FLASH_BWD, names.REMATTED} <= held
    # The rule is XLA operations today, every one inside the mixer's block;
    # the one softmax layer's flash calls are the step's Mosaic calls, and
    # the policy keeps the forward call from running again.
    mosaic = [op_name for (text, op_name), _, _ in ops
              if scopes.trace.op_kind(text) == "mosaic"]
    assert mosaic and all(names.FLASH_FWD in op or names.FLASH_BWD in op
                          for op in mosaic)
    assert not any(names.REMATTED in op for op in mosaic)
    for (_, op_name), _, _ in ops:
        if gdn_scopes.classify(op_name, names):
            assert names.BLOCK_ATTN in op_name and "/linear/" in op_name
    assert os.path.getsize(RECORDED) < 400_000


def test_recorded_step_by_the_new_scopes(recorded, monkeypatch):
    events = scopes.read_events(recorded)
    kinds = gdn_scopes.partition(events, names)
    assert all(kinds[kind] > 0.0 for kind in gdn_scopes.KINDS)
    # The forward's walk runs again under recomputation, and the backward
    # pass prepares a third time and walks back: more than twice the rest.
    assert 0 < kinds["scan_recomputed"] < 0.5 * kinds["scan"]
    by_class = scopes.partition(events, names)
    assert sum(kinds[kind] for kind in gdn_scopes.KINDS) < (
        by_class["classes"]["forward"] + by_class["classes"]["backward"])
    assert by_class["flash"]["fwd"] > 0 and by_class["flash"]["bwd"] > 0
    monkeypatch.setattr(gdn_scopes.trace, "find_xplane",
                        lambda trace_dir: recorded)
    gdn_scopes._reduce_file.cache_clear()
    work = {"flops": 1e9, "bytes": 1e6}
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {"gdn_scan": work}}}
    for metric, kind in (("gdn_conv_ms", "conv"), ("gdn_gates_ms", "gates"),
                         ("gdn_scan_ms", "scan")):
        assert manifest.load_reader(metric)(ctx) == pytest.approx(kinds[kind])
    share = manifest.load_reader("gdn_scan_roofline")(ctx)
    assert share == pytest.approx(100 * 1e9 / 197e12 * 1e3 / kinds["scan"])
    assert 0 < share < 100
    gdn_scopes._reduce_file.cache_clear()


# -- the keye cell's traced tiny run ------------------------------------------

def test_keye_cell_traced_tiny():
    """``test_cell_traced_tiny`` traces the manifest's first and last
    cells; this configuration's cell is the last now, so the
    ``keye-vl-2.0-30b-a3b`` cell's traced run is kept here."""
    workload = "keye-vl-2.0-30b-a3b.train-s8k-b2"
    sparse = manifest.cell(workload)
    args = argparse.Namespace(workload=workload, seed=2 ** 31 + 11,
                              seconds=1.0, trace=1)
    result = json.loads(json.dumps(run.run(
        args, start=time.perf_counter(),
        overrides=TINY[sparse["config"]["job"]], allow_cpu=True)))
    assert result["correct"] is True, result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) <= {m["name"] for m in sparse["per_layer"]}
    assert metrics["compiles_in_window"] == 0
    assert metrics["hbm_arguments_gb"] > 0
