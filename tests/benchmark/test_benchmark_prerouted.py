"""The ``smallthinker-21b-a3b`` configuration and its cell: the manifest's new
entries, the configuration's file against the catalog's row, the parameter
table from the built leaves, the job and its arithmetic against hand counts
(the band of 4096 at 16,384 positions, 1,536 rows a held expert), the job
against wrong versions of itself through the comparison that decides
``correct``, the readers of the scopes the cell reports on a tiny step traced
on a v5e, and the traced tiny run that the ``granite-4.0-h-micro`` cell had
while it was the manifest's last entry."""

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

from benchmark import (arithmetic, arithmetic_moe, arithmetic_window, manifest,
                       moe_scopes, run, scopes, window_scopes)
from horovod_tpu.common import scopes as names
from horovod_tpu.models import LlamaModel
from horovod_tpu.models.llama import RopeParameters
from horovod_tpu.ops.flash_attention import flash_attention_fn

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(manifest.HERE), "tools"))
import smallthinker_wrong_versions as wrong_versions  # noqa: E402
from tiny_sizes import TINY  # noqa: E402

CELL = "smallthinker-21b-a3b.train-s16k"
NAME = "smallthinker-21b-a3b"
SOURCE = ("https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
          "blob/main/config.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
JOINED = ("tokens_per_s_per_chip", "mfu", "flash_ms", "flash_roofline",
          "flash_fwd_ms", "flash_bwd_ms", "flash_fwd_roofline",
          "flash_bwd_roofline", "window_attn_ms", "window_attn_roofline",
          "moe_route_ms", "moe_experts_ms", "moe_experts_roofline",
          "block_attn_ms", "block_ffn_ms", "head_ms", "import_hvd_ms",
          "init_ms", "init_native_ms", "trace_attn_ms", "trace_ffn_ms",
          "trace_head_ms", "trace_optimizer_ms", "trace_kernels_ms",
          "trace_kernel_calls", "trace_loss_self_ms")
REDUCED = ["num_hidden_layers", "rope_layout", "sliding_window_layout",
           "moe_num_primary_experts", "vocab_size"]
# Hidden 256; the period of four layers (a global layer that does not rotate,
# then three that do under a window of 128 keys) at 2 query heads over 1
# key-value head of 128 (so the calls go in place and the rotation is its
# Mosaic pass), 1 x 512 tokens, experts 4 to 7 of 16 held, top-3, ReGLU of
# 128, the router on the layer's input, ``layer_keep_attention``: traced on
# one TPU v5e chip by this harness (PR 63), cut by ``benchmark.xspace.trim``
# to its first three steps and to the lines the reductions read; gzipped.
# Named ``.xspace.gz`` as PERF.md's Open question 23 says.
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-prerouted-v5e.xspace.gz")
SEQ, WINDOW = 16384, 4096
BAND = 58_722_304
CAUSAL = 134_225_920


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
    return manifest.load_job("prerouted_moe_lm").build(cell["config"],
                                                       cell["traffic"], 1)


# -- the manifest and the configuration's file --------------------------------

def test_the_configuration_is_the_catalogs_row_but_for_its_five_cuts(cell):
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
    assert config["num_hidden_layers"] == 4
    for key in ("rope_layout", "sliding_window_layout"):
        assert config[key] == published[key][:4] == [0, 1, 1, 1]
        assert published[key] == [0, 1, 1, 1] * 13
    assert (config["moe_num_primary_experts"],
            published["moe_num_primary_experts"]) == (16, 64)
    assert config["vocab_size"] * 4 == published["vocab_size"] == 151936
    # Every width as published.
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_ffn_hidden_size"],
            config["moe_num_active_primary_experts"],
            config["sliding_window_size"], config["rope_theta"],
            config["rms_norm_eps"], config["max_position_embeddings"],
            config["tie_word_embeddings"]) == (
                2560, 28, 4, 128, 768, 6, 4096, 1500000, 1e-6, 16384, False)
    assert set(config["reduced_why"]) == set(REDUCED)
    assert "9.19 GB" in config["reduced_why"]["num_hidden_layers"]
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 4
    assert deployment["first_held_expert"] == 0
    assert deployment["num_experts_published"] == 64
    assert deployment["vocab_size_published"] == 151936
    assert deployment["num_hidden_layers_published"] == 52
    assumed = config["assumed"]
    assert {"router_input", "router_input_why", "qk_norm", "attention_bias",
            "output_gate", "attention_why", "window", "rope_pairs",
            "aux_loss_alpha", "aux_loss_alpha_why", "initialisation",
            "training"} <= set(assumed)
    assert assumed["router_input"] == "layer_input"
    assert "RMSNorm_1" in assumed["router_input_why"]
    assert config["training"]["remat"] in ("layer", "layer_keep_attention")
    limits = config["checks"]["reference"]
    assert limits["parameters"] == "initial" and len(limits["why"]) > 500


def test_the_manifests_new_entries(cell):
    listed = manifest.load()
    entry = next(c for c in listed["configs"] if c["name"] == NAME)
    assert entry["source"] == cell["config"]["source"] == SOURCE
    assert entry["reduced"] == cell["config"]["reduced"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200
    # (By name, not by place: a later PR's entries come behind these.)
    workload = next(w for w in listed["workloads"] if w["name"] == CELL)
    assert workload == {"name": CELL, "config": NAME,
                        "traffic": "train-s16k", "chips": 1,
                        "why": workload["why"]}
    assert "1536 rows" in workload["why"] and len(workload["why"]) <= 200
    assert "62 %" in workload["why"] and "28 %" in workload["why"]
    assert "4x its share" in workload["why"]
    assert cell["traffic"] == {
        **manifest.cell("ouro-2.6b.train-s8k")["traffic"], "sequence": SEQ}
    assert len(listed["configs"]) >= 13 and len(listed["workloads"]) >= 15
    assert sum(w["chips"] == 4 for w in listed["workloads"]) <= (
        len(listed["workloads"]) // 4)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "step_ms_p90", "peak_hbm_gb", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(JOINED[1:]) <= reported
    # No shared expert, no output gate, no plain decoder's matrices, and no
    # other configuration's layers.
    assert not {"moe_shared_ms", "attn_gate_ms", "recompute_ms",
                "dense_roofline", "mla_latent_ms", "qk_norm_ms",
                "gdn_scan_ms", "ssd_scan_ms", "lconv_conv_ms"} & reported
    per_layer = {m["name"]: m for m in listed["per_layer"]}
    for name in JOINED:
        metric = per_layer.get(name) or next(
            m for m in listed["end_to_end"] if m["name"] == name)
        assert metric["workloads"][-1] == CELL or CELL in metric["workloads"]
    # The cell brought no metric and no reader of its own.
    assert not [m for m in listed["per_layer"]
                if m.get("workloads") == [CELL]]


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(manifest.HERE, "reference", "smallthinker.py")
    with open(path) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp",
                       "from benchmark.reference.laguna import attention",
                       "from benchmark.reference.ouro import _blocks, "
                       "rms_norm"]
    assert "horovod_tpu" not in text.split('"""')[2]
    assert 'default_matmul_precision("highest")' in text


# -- the parameter table, the job and its arithmetic ---------------------------

def test_the_parameter_table(job):
    """ISSUE 63's table, matrix by matrix, from the built model's leaves:
    656,529,920 parameters, 9.19 GB of state at 14 bytes."""
    hidden, dim = 2560, 128
    mixer = 2 * hidden * 28 * dim + 2 * hidden * 4 * dim
    expert, router = 3 * hidden * 768, hidden * 64
    assert (mixer, expert, router) == (20_971_520, 5_898_240, 163_840)
    layer = mixer + 2 * hidden + router + 16 * expert
    assert 16 * expert == 94_371_840 and layer == 115_512_320
    table = 4 * layer + 2 * 37_984 * hidden + hidden
    assert 4 * layer == 462_049_280 and 2 * 37_984 * hidden == 194_478_080
    assert table == 656_529_920
    shapes = jax.eval_shape(job.init_state, jax.random.key(0))[0]
    assert set(shapes) == {"params"}
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == table
    assert count * 14 == 9_191_418_880
    assert all(leaf.dtype == jnp.bfloat16
               for leaf in jax.tree.leaves(shapes))
    # The published stack by the same leaves: the card's 21B.
    whole = mixer + 2 * hidden + router + 64 * expert
    assert whole == 398_627_840
    assert 52 * whole + 2 * 151_936 * hidden + hidden == 21_506_562_560
    # Eight layers at 16 held: no room for a step.
    assert (8 * layer + 2 * 37_984 * hidden + hidden) * 14 == pytest.approx(
        15.66e9, rel=1e-3)
    # The floor of the vocabulary, an eighth, had the step not fitted.
    assert 4 * layer + 2 * 18_992 * hidden + hidden == 559_290_880
    params = shapes["params"]
    assert set(params) == {"tok_emb", "norm_f", "lm_head",
                           *(f"layer_{i}" for i in range(4))}
    for i in range(4):
        layer_i = params[f"layer_{i}"]
        assert set(layer_i) == {"norm_attn", "attn", "norm_mlp", "moe"}
        assert set(layer_i["attn"]) == {"wq", "wk", "wv", "wo"}
        assert set(layer_i["moe"]) == {"router", "w_gate_up", "w_down"}
        assert layer_i["attn"]["wq"]["kernel"].shape == (2560, 3584)
        assert layer_i["attn"]["wk"]["kernel"].shape == (2560, 512)
        assert layer_i["attn"]["wo"]["kernel"].shape == (3584, 2560)
        assert layer_i["moe"]["w_gate_up"].shape == (16, 2560, 1536)
        assert layer_i["moe"]["w_down"].shape == (16, 768, 2560)
        assert layer_i["moe"]["router"]["kernel"].shape == (2560, 64)
    assert params["lm_head"]["kernel"].shape == (2560, 37_984)
    assert params["tok_emb"]["embedding"].shape == (37_984, 2560)


def test_kernel_work_against_hand_counts(job):
    assert arithmetic_window.band_pairs(SEQ, WINDOW) == BAND
    assert BAND == SEQ * WINDOW - WINDOW * (WINDOW - 1) // 2
    assert arithmetic_window.band_pairs(SEQ, None) == (
        arithmetic.causal_pairs(SEQ)) == CAUSAL == SEQ * (SEQ + 1) // 2
    assert BAND / CAUSAL == pytest.approx(0.4375, abs=5e-4)
    # At 8,192 the same window would keep three quarters of the pairs.
    assert arithmetic_window.band_pairs(8192, WINDOW) / (
        arithmetic.causal_pairs(8192)) == pytest.approx(0.75, abs=2e-3)
    work = job.kernel_work_per_step()
    # Seven products a kept pair at 28 heads of 128: three bands, one
    # global layer.
    band = 7 * 2 * 128 * 28 * 3 * BAND
    causal = 7 * 2 * 128 * 28 * CAUSAL
    assert work["window_attn"]["flops"] == band
    assert work["flash"]["flops"] == band + causal
    assert band == pytest.approx(8.839e12, rel=1e-3)
    assert causal == pytest.approx(6.735e12, rel=1e-3)
    # The ONE global layer is 43 % of the attention.
    assert causal / (band + causal) == pytest.approx(0.432, abs=2e-3)
    assert work["flash"]["forward"]["flops"] * 7 == work["flash"]["flops"] * 2
    assert work["flash"]["backward"]["flops"] * 7 == (
        work["flash"]["flops"] * 5)
    # q, o, dO, dq at 28 heads; k, v, dk, dv at the 4 key-value heads:
    # twelve tensors a layer, bf16.
    tensor = SEQ * 128 * 2
    assert work["window_attn"]["bytes"] == 3 * 6 * (28 + 4) * tensor
    assert work["flash"]["bytes"] == 4 * 6 * (28 + 4) * tensor
    peaks = manifest.peaks("TPU v5 lite")
    least, bound = arithmetic.roofline_seconds(
        work["window_attn"]["flops"], work["window_attn"]["bytes"], peaks)
    assert bound == "flops" and least == pytest.approx(44.87e-3, rel=1e-3)
    # 1,536 rows a held expert: a quarter of a token's six choices.
    rows = SEQ * 6 * 16 / 64
    assert rows == 24_576 and rows / 16 == 1536
    products = 3 * 2 * rows * (2560 * 1536 + 768 * 2560)
    assert work["moe_experts"]["flops"] == 4 * products == 4 * (
        arithmetic_moe.expert_products_flops(rows=rows, hidden=2560,
                                             expert_ffn=768))
    assert 4 * products == pytest.approx(3.479e12, rel=1e-3)
    matrices = 16 * (2560 * 1536 + 768 * 2560)
    sides = rows * ((2560 + 1536) + (768 + 2560))
    assert work["moe_experts"]["bytes"] == 4 * 3 * 2 * (matrices + sides)
    assert set(work) == {"flash", "window_attn", "moe_experts"}


def test_a_token_is_705_mflop_forward_and_a_step_34_7_tflop(job):
    """ISSUE 63's count a token forward (projections 168, scores and values
    272, held experts 71, head 194: 705 MFLOP, attention 62 % of it, the
    head 28 %) and ``mfu``'s rule over it (the forward pass once and the
    backward pass twice; what ``remat`` repeats is not counted)."""
    hidden = 2560
    projections = 4 * 2 * 20_971_520
    routers = 4 * 2 * hidden * 64
    experts = 4 * 2 * (6 * 16 / 64) * 5_898_240
    head = 2 * hidden * 37_984
    scores = 2 * 2 * 128 * 28 * (CAUSAL + 3 * BAND) / SEQ
    assert projections == pytest.approx(167.8e6, rel=1e-3)
    assert scores == pytest.approx(271.5e6, rel=1e-3)
    assert 2 * 2 * 128 * 28 * CAUSAL / SEQ == pytest.approx(117.4e6, rel=1e-3)
    assert 2 * 2 * 128 * 28 * BAND / SEQ == pytest.approx(51.4e6, rel=1e-3)
    assert experts == pytest.approx(70.8e6, rel=1e-3)
    assert head == pytest.approx(194.5e6, rel=1e-3)
    forward = projections + routers + experts + head + scores
    assert forward == pytest.approx(705.9e6, rel=1e-3)
    assert (projections + scores) / forward == pytest.approx(0.622, abs=2e-3)
    assert head / forward == pytest.approx(0.275, abs=2e-3)
    assert job.flops_per_unit() == pytest.approx(3 * forward, rel=1e-12)
    assert job.flops_per_unit() * SEQ == pytest.approx(34.70e12, rel=1e-3)
    assert job.units_per_step == SEQ and job.unit == "tokens"


def test_job_builds_the_published_layers(job, cell):
    c = job.llama
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
            c.moe_intermediate_size, c.vocab_size, c.rms_eps) == (
                2560, 28, 4, 128, 768, 37_984, 1e-6)
    assert [c.window_of(i) for i in range(4)] == [None, 4096, 4096, 4096]
    assert [c.rope_of(i) for i in range(4)] == [
        None] + [RopeParameters(1.5e6, None, 1.0)] * 3
    assert [c.heads_of(i) for i in range(4)] == [28] * 4
    assert all(c.is_routed(i) for i in range(4))
    assert (c.num_experts, c.experts_held, c.first_held_expert,
            c.experts_per_token, c.shared_experts, c.first_dense_layers,
            c.norm_topk_prob, c.routed_scaling_factor, c.scoring_func,
            c.balance_over) == (64, 16, 0, 6, 0, 0, True, 1.0, "softmax",
                                "batch")
    assert (c.router_input, c.mlp_hidden_act) == ("layer", "relu")
    assert c.gating is None and c.qk_norm is False
    assert c.tie_word_embeddings is False
    assert c.remat == cell["config"]["training"]["remat"]
    assert job.expected_first_loss() == pytest.approx(
        np.log(37_984) + 0.5 + 0.001)
    build = manifest.load_job("prerouted_moe_lm").build
    with pytest.raises(ValueError, match="SmallThinker's decoder layers"):
        build({**cell["config"], "tie_word_embeddings": True},
              cell["traffic"], 1)
    with pytest.raises(ValueError, match="SmallThinker's decoder layers"):
        # A window layer that does not rotate: a type has one rotation.
        build({**cell["config"], "rope_layout": [0, 1, 1, 0]},
              cell["traffic"], 1)
    with pytest.raises(ValueError, match="SmallThinker's decoder layers"):
        build({**cell["config"], "assumed": {
            **cell["config"]["assumed"], "router_input": "normed_input"}},
            cell["traffic"], 1)
    with pytest.raises(ValueError, match="master AdamW"):
        build({**cell["config"], "training": {
            **cell["config"]["training"], "optimizer": "sgd"}},
            cell["traffic"], 1)


def test_the_jobs_embedding_has_unit_variance_and_its_counters_count():
    """The first router reads the raw embedding: unit variance, so its
    logits differ by token.  And the counters the job's ``main`` prints,
    on the tiny job."""
    job, _, _ = _tiny_job()
    params, _ = jax.jit(job.init_state)(jax.random.key(0))
    table = params["params"]["tok_emb"]["embedding"].astype(jnp.float32)
    assert float(jnp.std(table)) == pytest.approx(1.0, abs=0.02)
    rows, dropped, buffers, load = map(np.asarray, jax.jit(
        job.layer_counters)(params, job.make_batch(jax.random.key(1))))
    assert rows.shape == (2, 4) and dropped.shape == buffers.shape == (2,)
    assert not dropped.any() and (buffers >= 1).all()
    # 2 x 128 tokens, 3 choices, 4 of 16 held: 192 rows a layer expected.
    assert 100 < rows.sum(axis=1).min() and rows.sum(axis=1).max() < 300
    assert (load >= 1.0).all() and (load < 2.5).all()


# -- wrong versions are outside the comparison's limits ----------------------

@pytest.mark.parametrize("version, least", [
    ("right", 0.0), ("router_reads_post_attention_normed_state", 0.02),
    ("window_ignored_in_one_sliding_layer", 0.02),
    ("global_layer_rotated", 0.02), ("one_window_layer_not_rotated", 0.02),
    ("silu_for_relu", 0.05), ("softmax_over_64_not_renormalised", 0.05),
    ("float8_e4m3", 0.01)])
def test_comparison_passes_the_job_and_fails_wrong_versions_of_it(version,
                                                                  least):
    """``tools/smallthinker_wrong_versions.py``'s table, in float32 at the
    tiny size, where the job as it is reads 1e-6 and every wrong version has
    to show: the router reading the post-attention normed state, the window
    ignored in the sliding layer, the global layer rotated, the window layer
    not rotated, ``silu`` for ``relu``, the softmax over all 64 left
    un-renormalised, matmul inputs rounded to float8.  (At the cell's size
    in bf16 the same table runs on the chip under the limits of the
    configuration's file; ``checks.reference.why`` has its verdicts.)"""
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
    with jax.default_matmul_precision("highest"):
        found = wrong_versions.judge(job, reference, config, mesh, state,
                                     sample, version)
    assert found["correct"] == (version == "right"), found
    assert found["grad_rel_err"] >= least
    assert "loss_fn" not in vars(job)


def test_the_table_of_wrong_versions_is_the_issues():
    job, _, _ = _tiny_job()
    assert list(wrong_versions.versions(job)) == [
        "right", "router_reads_post_attention_normed_state",
        "window_ignored_in_one_sliding_layer", "global_layer_rotated",
        "one_window_layer_not_rotated", "silu_for_relu",
        "softmax_over_64_not_renormalised", "float8_e4m3", "float8_e5m2"]
    assert wrong_versions.CELL == CELL


def test_each_limit_lies_between_its_two_readings(cell):
    """The v5e's readings at the cell's size (``checks.reference.why``): the
    job as it is at most 0.00026 and 2.84 % from the reference on fourteen
    seeds, float8 e4m3 matmul inputs at least 0.0014 and 5.08 %.  Each limit
    has to tell the two apart with room on both sides."""
    limits = cell["config"]["checks"]["reference"]
    assert 2 * 0.00026 < limits["loss_abs"] < 0.0014 / 2
    assert 1.3 * 0.0284 < limits["grad_rel"] < 0.0508 / 1.3


# -- the readers of the scopes the cell reports ---------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_recorded_trace_holds_the_cells_scopes(recorded):
    events = scopes.read_events(recorded)
    assert sorted(events["devices"]) == [0]
    ops = events["devices"][0]["ops"]
    held = {scopes.bare(part) for (_, op_name), _, _ in ops
            for part in scopes.components(op_name)}
    assert {names.LOSS, names.ATTN_WINDOW, names.ROPE, names.BLOCK_ATTN,
            names.BLOCK_FFN, names.HEAD, names.FLASH_FWD, names.FLASH_BWD,
            names.MOE_ROUTE, names.MOE_EXPERTS, names.MOE_COMBINE,
            names.REMATTED} <= held
    assert not {names.MOE_SHARED, names.ATTN_GATE, names.QK_NORM} & held
    mosaic = [op_name for (text, op_name), _, _ in ops
              if scopes.trace.op_kind(text) == "mosaic"
              and not op_name.startswith(names.RAGGED_DOT_PREFIX)]
    assert mosaic and all(names.FLASH_FWD in op or names.FLASH_BWD in op
                          or names.ROPE in op for op in mosaic)
    # The three window layers' calls are under the window's scope and turn;
    # the global layer's are not and it has no rotation at all.
    windowed = {op.split("/layer_")[1][0] for op in mosaic
                if names.ATTN_WINDOW in op}
    plain = {op.split("/layer_")[1][0] for op in mosaic
             if names.ATTN_WINDOW not in op}
    assert (windowed, plain) == ({"1", "2", "3"}, {"0"})
    turned = {op.split("/layer_")[1][0] for op in mosaic if names.ROPE in op}
    assert turned == {"1", "2", "3"}
    # The routing is entered from the feed-forward's block, in every layer,
    # though the tensor it reads is the layer's input.
    routed = {op_name.split("/layer_")[1][0] for (_, op_name), _, _ in ops
              if "/layer_" in op_name
              and moe_scopes.classify(op_name, names) == "route"}
    assert routed == {"0", "1", "2", "3"}
    for (_, op_name), _, _ in ops:
        if "/layer_" in op_name and moe_scopes.classify(op_name, names):
            assert names.BLOCK_FFN in op_name and "/moe/" in op_name
    assert os.path.getsize(RECORDED) < 500_000


def test_recorded_step_by_the_scopes_the_cell_reports(recorded, monkeypatch):
    events = scopes.read_events(recorded)
    window = window_scopes.partition(events, names)
    routed = moe_scopes.partition(events, names)
    assert window["window"] > 0 and window["gate"] == 0
    assert 0.5 * window["window"] < window["window_mosaic"] <= (
        window["window"])
    assert routed["route"] > 0 and routed["experts"] > 0
    assert routed["shared"] == 0 and routed["latent"] == 0
    by_class = scopes.partition(events, names)
    assert by_class["flash"]["fwd"] > 0 and by_class["flash"]["bwd"] > 0
    # Three of the four layers' flash calls are the window's.
    assert window["window_mosaic"] < (by_class["flash"]["fwd"]
                                      + by_class["flash"]["bwd"]
                                      + window["window"])
    for module in (window_scopes, moe_scopes):
        monkeypatch.setattr(module.trace, "find_xplane",
                            lambda trace_dir: recorded)
        module._reduce_file.cache_clear()
    work = {"flops": 1e9, "bytes": 1e6}
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {"window_attn": work,
                                            "moe_experts": work}}}
    assert manifest.load_reader("window_attn_ms")(ctx) == pytest.approx(
        window["window"])
    assert manifest.load_reader("moe_route_ms")(ctx) == pytest.approx(
        routed["route"])
    assert manifest.load_reader("moe_experts_ms")(ctx) == pytest.approx(
        routed["experts"])
    assert manifest.load_reader("moe_shared_ms")(ctx) is None
    assert manifest.load_reader("attn_gate_ms")(ctx) is None
    for metric, ms in (("window_attn_roofline", window["window"]),
                       ("moe_experts_roofline", routed["experts"])):
        share = manifest.load_reader(metric)(ctx)
        assert share == pytest.approx(100 * 1e9 / 197e12 * 1e3 / ms)
        assert 0 < share < 100
    for module in (window_scopes, moe_scopes):
        module._reduce_file.cache_clear()


# -- the granite cell's traced tiny run ------------------------------------------

def test_granite_cell_traced_tiny():
    """``test_cell_traced_tiny`` traces the manifest's first and last
    cells; this configuration's cell is the last now, so the
    ``granite-4.0-h-micro`` cell's traced run is kept here."""
    workload = "granite-4.0-h-micro.train-s8k"
    granite = manifest.cell(workload)
    args = argparse.Namespace(workload=workload, seed=2 ** 31 + 11,
                              seconds=1.0, trace=1)
    result = json.loads(json.dumps(run.run(
        args, start=time.perf_counter(),
        overrides=TINY[granite["config"]["job"]], allow_cpu=True)))
    assert result["correct"] is True, result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) <= {m["name"] for m in granite["per_layer"]}
    assert metrics["compiles_in_window"] == 0
    assert metrics["hbm_arguments_gb"] > 0
