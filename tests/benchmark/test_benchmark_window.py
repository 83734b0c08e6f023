"""The ``laguna-s-2.1`` configuration and its cell: the manifest's new
entries, the configuration's file against the catalog's row, the parameter
table, the job and its arithmetic against hand counts (a sliding layer over
its band alone), the job against wrong versions of itself through the
comparison that decides ``correct``, the readers of the new scopes on
hand-built events and on a tiny step traced on a v5e, and the traced tiny
run that the ``olmo-hybrid-7b`` cell had while it was the manifest's last
entry."""

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

from benchmark import (arithmetic, arithmetic_moe, arithmetic_window, compare,
                       manifest, run, scopes, window_scopes)
from horovod_tpu.common import scopes as names
from horovod_tpu.models import LlamaModel, llama
from horovod_tpu.models.llama import LlamaConfig
from horovod_tpu.ops.flash_attention import flash_attention_fn

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_sizes import TINY  # noqa: E402

CELL = "laguna-s-2.1.train-s8k"
SOURCE = "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = ("window_attn_ms", "window_attn_roofline", "attn_gate_ms")
JOINED = ("tokens_per_s_per_chip", "mfu", "block_attn_ms", "block_ffn_ms",
          "head_ms", "flash_ms", "flash_roofline", "flash_fwd_ms",
          "flash_bwd_ms", "flash_fwd_roofline", "flash_bwd_roofline",
          "moe_route_ms", "moe_experts_ms", "moe_experts_roofline",
          "moe_shared_ms")
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "gating_types", "num_attention_heads_per_layer", "num_experts",
           "vocab_size"]
# Hidden 256; the published pattern's first five layers at 2 and 3 query
# heads of 128 over one key-value head (so the calls go in place and the
# rotation is its Mosaic pass), a window of 128 keys at 1 x 512 tokens, a
# dense layer and four routed ones that hold 4 of 16 experts,
# ``layer_keep_attention``: traced on one TPU v5e chip by this harness (PR
# 42), cut by ``benchmark.xspace.trim`` to its first three steps and to the
# lines the reductions read; gzipped.  Named ``.xspace.gz`` as PERF.md's
# Open question 23 says (the accepted tests take every ``*.xplane.pb*`` for
# a step whose Mosaic calls are all the flash kernel's).
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-window-decoder-v5e.xspace.gz")
BAND = 8192 * 512 - 512 * 511 // 2


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
    return manifest.load_job("window_moe_lm").build(cell["config"],
                                                    cell["traffic"], 1)


# -- the manifest and the configuration's file --------------------------------

def test_the_configuration_is_the_catalogs_row_but_for_its_seven_cuts(cell):
    config = cell["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of published configurations here")
    with open(CATALOG) as f:
        published = next(row["config"] for row in map(json.loads, f)
                         if row["source_url"] == SOURCE)
    differ = [key for key, value in published.items()
              if config[key] != value]
    assert sorted(differ) == sorted(config["reduced"]) == sorted(REDUCED)
    assert config["reduced"] == REDUCED
    assert config["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert config[key] == published[key][:5]
    assert config["layer_types"] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert config["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert config["num_experts"] == 8 and published["num_experts"] == 256
    assert config["vocab_size"] * 8 == published["vocab_size"] == 100352
    # Every width as published.
    assert (config["hidden_size"], config["head_dim"],
            config["num_key_value_heads"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["sliding_window"], config["moe_routed_scaling_factor"]
            ) == (3072, 128, 8, 12288, 1024, 10, 512, 2.5)
    assert config["rope_parameters"] == published["rope_parameters"]
    assert set(config["reduced_why"]) == set(REDUCED)
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 32
    assert deployment["num_experts_published"] == 256
    assert deployment["vocab_size_published"] == 100352
    assert deployment["num_hidden_layers_published"] == 48
    assert {"gate", "router", "block", "window", "rope_layout",
            "aux_loss_alpha", "initialisation", "training"} <= set(
                config["assumed"])
    assert "2505.06708" in config["assumed"]["gate"]
    assert config["training"]["remat"] in ("layer", "layer_keep_attention")
    limits = config["checks"]["reference"]
    assert limits["parameters"] == "initial" and len(limits["why"]) > 500


def test_the_manifests_new_entries(cell):
    listed = manifest.load()
    entry = next(c for c in listed["configs"] if c["name"] == "laguna-s-2.1")
    assert entry["source"] == cell["config"]["source"] == SOURCE
    assert entry["reduced"] == cell["config"]["reduced"]
    assert entry["file"] == "benchmark/configs/laguna-s-2.1.json"
    # (By name, not by place: a later PR's entries come behind these.)
    workload = next(w for w in listed["workloads"] if w["name"] == CELL)
    assert workload == {"name": CELL, "config": "laguna-s-2.1",
                        "traffic": "train-s8k", "chips": 1,
                        "why": workload["why"]}
    assert "320 rows" in workload["why"] and len(workload["why"]) <= 200
    assert cell["traffic"] == manifest.cell("ouro-2.6b.train-s8k")["traffic"]
    assert len(listed["configs"]) >= 7 and len(listed["workloads"]) >= 9
    assert sum(w["chips"] == 4 for w in listed["workloads"]) <= (
        len(listed["workloads"]) // 4)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "step_ms_p90", "peak_hbm_gb", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(METRICS) | set(JOINED[1:]) <= reported
    # Readers that find nothing to read in this cell (PERF.md, Open
    # question 32): no loop to tell recomputed work by, no plain decoder's
    # matrices; and no other configuration's layers.
    assert not {"recompute_ms", "dense_roofline", "mla_latent_ms",
                "sparse_index_ms", "gdn_scan_ms"} & reported
    per_layer = {m["name"]: m for m in listed["per_layer"]}
    order = [m["name"] for m in listed["per_layer"]]
    assert sorted(METRICS, key=order.index) == list(METRICS)
    for name in METRICS:
        metric = per_layer[name]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "step_ms_p90"
        assert "flash" not in name
        assert os.path.exists(manifest.metric_path(name))
        assert metric["layer"] == ("model" if name == "attn_gate_ms"
                                   else "kernels")
        assert (metric["unit"], metric["better"], metric["source"]) == (
            ("%", "higher", "device_trace") if name.endswith("_roofline")
            else ("ms", "lower", "program_span"))
    for name in JOINED:
        metric = per_layer.get(name) or next(
            m for m in listed["end_to_end"] if m["name"] == name)
        assert CELL in metric["workloads"]


# -- the parameter table, the job and its arithmetic ---------------------------

def test_the_parameter_table(job):
    """ISSUE 42's table, matrix by matrix, and the program's own count: the
    table's 810.98M and the 11 norm scales beside them."""
    hidden, dim = 3072, 128
    full = 2 * hidden * 48 * dim + 2 * hidden * 8 * dim + hidden * 48
    sliding = 2 * hidden * 72 * dim + 2 * hidden * 8 * dim + hidden * 72
    dense, expert, router = 3 * hidden * 12288, 3 * hidden * 1024, hidden * 256
    assert (full, sliding) == (44_187_648, 63_135_744)
    assert (dense, expert, router) == (113_246_208, 9_437_184, 786_432)
    routed = expert + router + 8 * expert
    assert full + dense == 157_433_856
    assert sliding + routed == 148_856_832 and full + routed == 129_908_736
    table = (full + dense + 3 * (sliding + routed) + full + routed
             + 2 * 12544 * hidden)
    assert table == 810_983_424
    shapes = jax.eval_shape(job.init_state, jax.random.key(0))[0]
    assert set(shapes) == {"params"}
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == table + 11 * hidden == 811_017_216
    assert count * 14 == pytest.approx(11.354e9, rel=1e-3)
    params = shapes["params"]
    assert params["layer_0"]["attn"]["wq"]["kernel"].shape == (3072, 6144)
    assert params["layer_1"]["attn"]["wq"]["kernel"].shape == (3072, 9216)
    assert params["layer_1"]["attn"]["wk"]["kernel"].shape == (3072, 1024)
    assert params["layer_1"]["attn"]["wg"]["kernel"].shape == (3072, 72)
    assert params["layer_4"]["attn"]["wg"]["kernel"].shape == (3072, 48)
    assert params["layer_0"]["mlp"]["w_gate_up"]["kernel"].shape == (
        3072, 2 * 12288)
    assert params["layer_2"]["moe"]["w_gate_up"].shape == (8, 3072, 2048)
    assert params["layer_2"]["moe"]["router"]["kernel"].shape == (3072, 256)
    assert params["lm_head"]["kernel"].shape == (3072, 12544)


def test_arithmetic_counts_a_sliding_layer_over_its_band(job):
    assert arithmetic_window.band_pairs(8192, 512) == BAND == 4_063_488
    assert arithmetic_window.band_pairs(8192, None) == (
        arithmetic.causal_pairs(8192)) == 33_558_528
    assert arithmetic_window.band_pairs(8192, 8192) == 33_558_528
    assert arithmetic_window.band_pairs(8, 3) == 1 + 2 + 6 * 3
    work = job.kernel_work_per_step()
    # Seven products a kept pair: the band at 72 heads in three layers, all
    # causal pairs at 48 heads in two.
    band = 7 * 2 * 128 * 72 * 3 * BAND
    causal = 7 * 2 * 128 * 48 * 2 * 33_558_528
    assert work["window_attn"]["flops"] == band
    assert band == pytest.approx(1.573e12, rel=1e-3)
    assert work["flash"]["flops"] == band + causal
    assert causal == pytest.approx(5.773e12, rel=1e-3)
    assert work["flash"]["forward"]["flops"] * 7 == work["flash"]["flops"] * 2
    assert work["flash"]["backward"]["flops"] * 7 == (
        work["flash"]["flops"] * 5)
    # A count that gave a sliding layer causal pairs would read 8.26 times
    # the band's work.
    assert 7 * 2 * 128 * 72 * 3 * 33_558_528 / band == pytest.approx(
        8.2585, rel=1e-4)
    # q, o, dO, dq at the layer's heads; k, v, dk, dv at the 8 key-value
    # heads: twelve tensors a layer, bf16.
    tensor = 8192 * 128 * 2
    assert work["window_attn"]["bytes"] == 3 * 6 * (72 + 8) * tensor
    assert work["flash"]["bytes"] == (3 * 6 * (72 + 8) + 2 * 6 * (48 + 8)
                                      ) * tensor
    assert work["flash"]["forward"]["bytes"] * 3 == work["flash"]["bytes"]
    peaks = manifest.peaks("TPU v5 lite")
    least, bound = arithmetic.roofline_seconds(
        work["window_attn"]["flops"], work["window_attn"]["bytes"], peaks)
    assert bound == "flops" and least == pytest.approx(7.984e-3, rel=1e-3)
    rows = 8192 * 10 * 8 / 256
    assert rows == 2560 and rows / 8 == 320
    assert work["moe_experts"]["flops"] == 4 * (
        arithmetic_moe.expert_products_flops(rows=rows, hidden=3072,
                                             expert_ffn=1024))


def test_a_step_is_31_tflop_at_seven_products_and_30_by_the_benchmarks_rule(
        job):
    """ISSUE 42's 31.05 TFLOP a step counts the flash calls' seven products
    a kept pair; ``mfu``'s rule (``benchmark/arithmetic.py``: the forward
    pass once and the backward pass twice) counts six, as in every other
    cell, which is what ``flops_per_unit`` gives: 30.00."""
    hidden = 3072
    attention = 2 * 44_187_648 + 3 * 63_135_744
    routed = 786_432 + 9_437_184 + 10 * 8 / 256 * 9_437_184
    weights = attention + 113_246_208 + 4 * routed + hidden * 12544
    assert weights == pytest.approx(482.3e6, rel=1e-3)
    scores = 2 * 2 * 128 * (2 * 48 * 33_558_528 + 3 * 72 * BAND)
    assert job.flops_per_unit() * 8192 == pytest.approx(
        3 * (2 * weights * 8192 + scores), rel=1e-12)
    assert job.flops_per_unit() * 8192 == pytest.approx(30.00e12, rel=1e-3)
    flash = job.kernel_work_per_step()["flash"]["flops"]
    assert 6 * weights * 8192 + flash == pytest.approx(31.05e12, rel=1e-3)


def test_job_builds_the_published_layers(job, cell):
    c = job.llama
    assert (c.hidden_size, c.num_kv_heads, c.head_dim, c.intermediate_size,
            c.moe_intermediate_size) == (3072, 8, 128, 12288, 1024)
    assert [c.heads_of(i) for i in range(5)] == [48, 72, 72, 72, 48]
    assert [c.window_of(i) for i in range(5)] == [None, 512, 512, 512, None]
    assert [c.is_routed(i) for i in range(5)] == [False] + [True] * 4
    assert (c.num_experts, c.experts_held, c.experts_per_token,
            c.shared_experts, c.norm_topk_prob, c.routed_scaling_factor,
            c.balance_over) == (256, 8, 10, 1, True, 2.5, "batch")
    assert c.gating == "per-head" and c.qk_norm is False
    full, sliding = c.rope_of(0), c.rope_of(1)
    assert (full.rope_theta, full.partial_rotary_factor,
            full.scaling.factor, full.scaling.table_scale) == (
                500000.0, 0.5, 128, 1.4852030263919618)
    assert sliding == llama.RopeParameters(10000.0, None, 1.0)
    assert c.remat == cell["config"]["training"]["remat"]
    assert job.expected_first_loss() == pytest.approx(
        np.log(12544) + 0.5 + 0.001)
    with pytest.raises(ValueError, match="Laguna's decoder layers"):
        manifest.load_job("window_moe_lm").build(
            {**cell["config"], "gating": True}, cell["traffic"], 1)
    with pytest.raises(ValueError, match="rope_type"):
        manifest.load_job("window_moe_lm").build(
            {**cell["config"], "rope_parameters": {
                **cell["config"]["rope_parameters"],
                "sliding_attention": {"rope_type": "llama3",
                                      "rope_theta": 1e4,
                                      "partial_rotary_factor": 1}}},
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


def _patched(job, owner, name, value):
    """The job's loss with ``owner.name`` replaced while it is traced."""
    original = getattr(owner, name)

    def loss_fn(params, batch):
        setattr(owner, name, value(original))
        try:
            return type(job).loss_fn(job, params, batch)
        finally:
            setattr(owner, name, original)
    return loss_fn


def _window_ignored_in_one_layer(job):
    return _patched(job, LlamaConfig, "window_of", lambda window_of: (
        lambda self, layer: None if layer == 2 else window_of(self, layer)))


def _gate_left_out(job):
    return _patched(job, llama, "_gated_heads",
                    lambda gated: lambda out, logits: out)


def _full_layers_table_in_a_sliding_layer(job):
    return _patched(job, LlamaConfig, "rope_of", lambda rope_of: (
        lambda self, layer: rope_of(self, 0 if layer == 2 else layer)))


def _scale_left_out(job):
    return _with_model(job, routed_scaling_factor=1.0)


@pytest.mark.parametrize("defect, least", [
    (None, 0.0), (_window_ignored_in_one_layer, 0.02),
    (_gate_left_out, 0.05), (_full_layers_table_in_a_sliding_layer, 0.02),
    (_scale_left_out, 0.05)])
def test_comparison_passes_the_job_and_fails_wrong_versions_of_it(defect,
                                                                  least):
    """In float32 at the tiny size, where the job as it is reads 1e-6 and
    every wrong version has to show: the window ignored in one sliding
    layer, the gate left out, the full layers' table in a sliding layer,
    the gates' 2.5 left out.  (At the cell's size in bf16 the limits of the
    configuration's file decide; ``checks.reference.why`` says what they
    caught there.)"""
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
FWD = STEP + "jvp(LlamaModel)/layer_1/hvd.block.attn/attn/"
REC = (STEP + "transpose(jvp(LlamaModel))/hvd.loss/jvp(LlamaModel)/"
       "checkpoint/rematted_computation/layer_1/hvd.block.attn/attn/")
BWD = STEP + "transpose(jvp(LlamaModel))/layer_1/hvd.block.attn/attn/"
FULL = STEP + "jvp(LlamaModel)/layer_0/hvd.block.attn/attn/"
MOSAIC = ('%custom-call.7 = (bf16[512,384]{1,0}) custom-call(%a), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.3 = bf16[1,512,384]{2,1,0} fusion(%a), kind=kLoop"


@pytest.mark.parametrize("op_name, kind", [
    (FWD + "hvd.attn.window/hvd.flash.fwd/pallas_call", "window"),
    (FWD + "hvd.attn.window/hvd.rope/pallas_call", "window"),
    (REC + "hvd.attn.window/hvd.rope/pallas_call", "window"),
    (BWD + "hvd.attn.window/transpose(jvp(hvd.flash.bwd))/pallas_call",
     "window"),
    (BWD + "transpose(jvp(hvd.attn.window))/hvd.flash.bwd/pallas_call",
     "window"),
    (FWD + "hvd.attn.gate/wg/dot_general", "gate"),
    (BWD + "hvd.attn.gate/mul", "gate"),
    (FULL + "hvd.attn.gate/logistic", "gate"),
    (FULL + "hvd.flash.fwd/pallas_call", None),
    (FULL + "hvd.rope/pallas_call", None),
    (FWD + "wq/dot_general", None),
])
def test_classify_by_the_new_scopes(op_name, kind):
    assert window_scopes.classify(op_name, names) == kind


def test_partition_and_readers_on_hand_built_events(monkeypatch):
    step = "jit_hvd_train_step(1)"
    ops = [((MOSAIC, FWD + "hvd.attn.window/hvd.rope/pallas_call"),
            0.0, 1e-3),
           ((MOSAIC, FWD + "hvd.attn.window/hvd.flash.fwd/pallas_call"),
            1e-3, 3e-3),
           # An XLA operation under the window's scope counts with it.
           ((FUSION, BWD + "hvd.attn.window/reduce_sum"), 3e-3, 4e-3),
           ((MOSAIC, BWD + "hvd.attn.window/hvd.flash.bwd/pallas_call"),
            4e-3, 8e-3),
           ((FUSION, FWD + "hvd.attn.gate/wg/dot_general"), 8e-3, 9e-3),
           ((FUSION, FULL + "hvd.attn.gate/mul"), 9e-3, 9.5e-3),
           # A full layer's flash call is no window's.
           ((MOSAIC, FULL + "hvd.flash.fwd/pallas_call"), 9.5e-3, 10e-3)]
    events = {"devices": {0: {
        "ops": ops + [((n, o), a + 10e-3, b + 10e-3) for (n, o), a, b in ops],
        "modules": [(step, 0.0, 10e-3), (step, 10e-3, 20e-3)]}}}
    kinds = window_scopes.partition(events, names)
    assert kinds == pytest.approx({"window": 8.0, "gate": 1.5,
                                   "window_mosaic": 7.0})
    assert window_scopes.partition(
        {"devices": {0: {"ops": ops[6:], "modules": [(step, 0.0, 10e-3)]}}},
        names) is None
    monkeypatch.setattr(window_scopes.scopes, "read_events",
                        lambda path: events)
    monkeypatch.setattr(window_scopes.trace, "find_xplane",
                        lambda trace_dir: __file__)
    window_scopes._reduce_file.cache_clear()
    work = {"flops": 2 * 197e9, "bytes": 1e6}            # 2 ms at the peak
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {"window_attn": work}}}
    for metric, value in (("window_attn_ms", 8.0), ("attn_gate_ms", 1.5),
                          ("window_attn_roofline", 25.0)):
        assert manifest.load_reader(metric)(ctx) == pytest.approx(value)
    ctx["job"]["kernel_work_per_step"] = {}
    assert manifest.load_reader("window_attn_roofline")(ctx) is None
    for metric in METRICS:
        assert manifest.load_reader(metric)({**ctx, "trace": None}) is None
    # A program without the scopes (the parent) gives no number.
    monkeypatch.setattr(window_scopes.scopes, "program_scopes",
                        lambda: argparse.Namespace(LOSS="hvd.loss"))
    window_scopes._reduce_file.cache_clear()
    for metric in METRICS:
        assert manifest.load_reader(metric)(ctx) is None
    window_scopes._reduce_file.cache_clear()


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
    assert {names.LOSS, names.ATTN_WINDOW, names.ATTN_GATE, names.ROPE,
            names.BLOCK_ATTN, names.BLOCK_FFN, names.HEAD, names.FLASH_FWD,
            names.FLASH_BWD, names.MOE_ROUTE, names.REMATTED} <= held
    mosaic = [op_name for (text, op_name), _, _ in ops
              if scopes.trace.op_kind(text) == "mosaic"
              and not op_name.startswith(names.RAGGED_DOT_PREFIX)]
    assert mosaic and all(names.FLASH_FWD in op or names.FLASH_BWD in op
                          or names.ROPE in op for op in mosaic)
    # The sliding layers' calls are under the window's scope and the full
    # layers' are not; both kinds of layer gate.
    windowed = {op.split("/layer_")[1][0] for op in mosaic
                if names.ATTN_WINDOW in op}
    plain = {op.split("/layer_")[1][0] for op in mosaic
             if names.ATTN_WINDOW not in op}
    assert (windowed, plain) == ({"1", "2", "3"}, {"0", "4"})
    gated = {op_name.split("/layer_")[1][0] for (_, op_name), _, _ in ops
             if window_scopes.classify(op_name, names) == "gate"}
    assert gated == {"0", "1", "2", "3", "4"}
    for (_, op_name), _, _ in ops:
        if window_scopes.classify(op_name, names):
            assert names.BLOCK_ATTN in op_name and "/attn/" in op_name
    assert os.path.getsize(RECORDED) < 500_000


def test_recorded_step_by_the_new_scopes(recorded, monkeypatch):
    events = scopes.read_events(recorded)
    kinds = window_scopes.partition(events, names)
    assert kinds["window"] > 0 and kinds["gate"] > 0
    # The band is Mosaic calls but for delta's row sums and the tables.
    assert 0.5 * kinds["window"] < kinds["window_mosaic"] <= kinds["window"]
    by_class = scopes.partition(events, names)
    assert kinds["window"] + kinds["gate"] < (
        by_class["classes"]["forward"] + by_class["classes"]["backward"])
    # Three of the five layers' flash calls are the window's.
    assert by_class["flash"]["fwd"] > 0 and by_class["flash"]["bwd"] > 0
    assert kinds["window_mosaic"] < (by_class["flash"]["fwd"]
                                     + by_class["flash"]["bwd"]
                                     + kinds["window"])
    monkeypatch.setattr(window_scopes.trace, "find_xplane",
                        lambda trace_dir: recorded)
    window_scopes._reduce_file.cache_clear()
    work = {"flops": 1e9, "bytes": 1e6}
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {"window_attn": work}}}
    assert manifest.load_reader("window_attn_ms")(ctx) == pytest.approx(
        kinds["window"])
    assert manifest.load_reader("attn_gate_ms")(ctx) == pytest.approx(
        kinds["gate"])
    share = manifest.load_reader("window_attn_roofline")(ctx)
    assert share == pytest.approx(100 * 1e9 / 197e12 * 1e3 / kinds["window"])
    assert 0 < share < 100
    window_scopes._reduce_file.cache_clear()


# -- the olmo cell's traced tiny run -------------------------------------------

def test_olmo_cell_traced_tiny():
    """``test_cell_traced_tiny`` traces the manifest's first and last
    cells; this configuration's cell is the last now, so the
    ``olmo-hybrid-7b`` cell's traced run is kept here."""
    workload = "olmo-hybrid-7b.train-s8k"
    hybrid = manifest.cell(workload)
    args = argparse.Namespace(workload=workload, seed=2 ** 31 + 11,
                              seconds=1.0, trace=1)
    result = json.loads(json.dumps(run.run(
        args, start=time.perf_counter(),
        overrides=TINY[hybrid["config"]["job"]], allow_cpu=True)))
    assert result["correct"] is True, result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) <= {m["name"] for m in hybrid["per_layer"]}
    assert metrics["compiles_in_window"] == 0
    assert metrics["hbm_arguments_gb"] > 0
