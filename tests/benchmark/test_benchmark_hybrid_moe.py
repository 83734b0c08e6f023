"""The ``qwen3-next-80b-a3b`` configuration and its cell: the manifest's new
entries, the configuration's file against the catalog's row, the parameter
table, the job and its arithmetic against hand counts (the rule's bytes with
q and k once a key head), the job against wrong versions of itself through
the comparison that decides ``correct``, the reader of the new scope on
hand-built events and on a tiny step traced on a v5e, and the traced tiny run
that the ``laguna-s-2.1`` cell had while it was the manifest's last entry."""

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

from benchmark import (arithmetic, arithmetic_gdn, arithmetic_hybrid_moe,
                       arithmetic_moe, compare, gdn_heads_scopes, gdn_scopes,
                       manifest, moe_scopes, run, scopes, window_scopes)
from horovod_tpu.common import scopes as names
from horovod_tpu.models import LlamaModel, llama
from horovod_tpu.ops.flash_attention import flash_attention_fn

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_sizes import TINY  # noqa: E402

CELL = "qwen3-next-80b-a3b.train-s8k-b2"
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
          "config.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRIC = "gdn_heads_ms"
JOINED = ("tokens_per_s_per_chip", "mfu", "flash_ms", "flash_roofline",
          "flash_fwd_ms", "flash_bwd_ms", "flash_fwd_roofline",
          "flash_bwd_roofline", "block_attn_ms", "block_ffn_ms", "head_ms",
          "gdn_conv_ms", "gdn_gates_ms", "gdn_scan_ms", "gdn_scan_roofline",
          "moe_route_ms", "moe_experts_ms", "moe_experts_roofline",
          "moe_shared_ms", "attn_gate_ms")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
# Hidden 256; one period: linear layers of 2 key heads serving 4 value heads
# of 128 (so their convolutions are the Mosaic calls), a full layer of 2
# query heads over one key-value head of 128 of which a quarter turns (so the
# calls go in place and the rotation is its Mosaic pass), 4 of 16 experts
# held, top-3, a gated shared expert, 1 x 512 tokens,
# ``layer_keep_attention``: traced on one TPU v5e chip by this harness
# (PR 46), cut by ``benchmark.xspace.trim`` to its first three steps and to
# the lines the reductions read; gzipped.  Named ``.xspace.gz`` as PERF.md's
# Open question 23 says.
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-hybrid-moe-decoder-v5e.xspace.gz")
TOKENS = 2 * 8192


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
    return manifest.load_job("hybrid_moe_lm").build(cell["config"],
                                                    cell["traffic"], 1)


# -- the manifest and the configuration's file --------------------------------

def test_the_configuration_is_the_catalogs_row_but_for_its_three_cuts(cell):
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
    assert (config["num_hidden_layers"], published["num_hidden_layers"]) == (
        4, 48)
    assert config["num_hidden_layers"] == config["full_attention_interval"]
    assert (config["num_experts"], published["num_experts"]) == (32, 512)
    assert config["vocab_size"] * 8 == published["vocab_size"] == 151936
    # Every width as published.
    assert (config["hidden_size"], config["head_dim"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["partial_rotary_factor"], config["rope_theta"],
            config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"], config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"], config["num_experts_per_tok"],
            config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"],
            config["intermediate_size"], config["max_position_embeddings"]
            ) == (2048, 256, 16, 2, 0.25, 1e7, 16, 32, 128, 128, 4, 10, 512,
                  512, 5120, 262144)
    assert config["mlp_only_layers"] == [] and config["norm_topk_prob"]
    assert set(config["reduced_why"]) == set(REDUCED)
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 16
    assert deployment["num_experts_published"] == 512
    assert deployment["first_held_expert"] == 0
    assert deployment["vocab_size_published"] == 151936
    assert deployment["num_hidden_layers_published"] == 48
    assert {"aux_loss_alpha", "no_mtp", "fused_projections", "full_layer",
            "norms", "linear_layer", "rope_layout", "router",
            "initialisation", "training"} <= set(config["assumed"])
    assert config["assumed"]["aux_loss_alpha"] == 0.001
    assert "2412.06464" in config["assumed"]["linear_layer"]
    assert config["training"]["remat"] in ("layer", "layer_keep_attention")
    limits = config["checks"]["reference"]
    assert limits["parameters"] == "initial" and len(limits["why"]) > 500


def test_the_manifests_new_entries(cell):
    listed = manifest.load()
    entry = next(c for c in listed["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert entry["source"] == cell["config"]["source"] == SOURCE
    assert entry["reduced"] == cell["config"]["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/qwen3-next-80b-a3b.json"
    # (By name, not by place: a later PR's entries come behind these.)
    workload = next(w for w in listed["workloads"] if w["name"] == CELL)
    assert workload == {"name": CELL, "config": "qwen3-next-80b-a3b",
                        "traffic": "train-s8k-b2", "chips": 1,
                        "why": workload["why"]}
    assert "320 rows" in workload["why"] and len(workload["why"]) <= 200
    assert cell["traffic"] == manifest.cell(
        "keye-vl-2.0-30b-a3b.train-s8k-b2")["traffic"]
    assert len(listed["configs"]) >= 8 and len(listed["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in listed["workloads"]) <= (
        len(listed["workloads"]) // 4)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "step_ms_p90", "peak_hbm_gb", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert {METRIC} | set(JOINED[1:]) <= reported
    # Readers that find nothing to read in this cell: no loop to tell
    # recomputed work by, no plain decoder's matrices, no window, and no
    # other configuration's layers.
    assert not {"recompute_ms", "dense_roofline", "mla_latent_ms",
                "sparse_index_ms", "window_attn_ms"} & reported
    per_layer = {m["name"]: m for m in listed["per_layer"]}
    metric = per_layer[METRIC]
    assert metric == {"name": METRIC, "unit": "ms", "better": "lower",
                      "source": "program_span", "layer": "model",
                      "moves": "step_ms_p90", "workloads": [CELL]}
    assert os.path.exists(manifest.metric_path(METRIC))
    for name in JOINED:
        joined = per_layer.get(name) or next(
            m for m in listed["end_to_end"] if m["name"] == name)
        assert CELL in joined["workloads"]


# -- the parameter table, the job and its arithmetic ---------------------------

def test_the_parameter_table(job):
    """ISSUE 46's table, matrix by matrix, and the program's own count."""
    hidden = 2048
    linear = (2 * hidden * 16 * 128 + 3 * hidden * 32 * 128
              + 2 * hidden * 32 + 4 * (2 * 2048 + 4096) + 2 * 32 + 128)
    full = (hidden * 16 * 2 * 256 + 2 * hidden * 2 * 256
            + 16 * 256 * hidden + 2 * 256)
    expert, router = 3 * hidden * 512, hidden * 512
    assert (linear, full) == (33_718_464, 27_263_488)
    assert (expert, router) == (3_145_728, 1_048_576)
    routed = router + expert + hidden + 32 * expert + 2 * hidden  # + norms
    assert linear + routed == 138_582_208
    assert full + routed == 132_127_232
    table = 3 * (linear + routed) + full + routed + 2 * 18992 * hidden
    shapes = jax.eval_shape(job.init_state, jax.random.key(0))[0]
    assert set(shapes) == {"params"}
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == table + hidden == 625_667_136
    assert count * 14 == pytest.approx(8.759e9, rel=1e-3)
    params = shapes["params"]
    assert params["layer_3"]["attn"]["wq"]["kernel"].shape == (2048, 8192)
    assert params["layer_3"]["attn"]["wk"]["kernel"].shape == (2048, 512)
    assert params["layer_3"]["attn"]["wo"]["kernel"].shape == (4096, 2048)
    assert params["layer_3"]["attn"]["q_norm"]["scale"].shape == (256,)
    assert params["layer_0"]["linear"]["wq"]["kernel"].shape == (2048, 2048)
    assert params["layer_0"]["linear"]["wv"]["kernel"].shape == (2048, 4096)
    assert params["layer_0"]["linear"]["wa"]["kernel"].shape == (2048, 32)
    assert params["layer_0"]["linear"]["conv_v"].shape == (4, 4096)
    assert params["layer_2"]["moe"]["w_gate_up"].shape == (32, 2048, 1024)
    assert params["layer_2"]["moe"]["router"]["kernel"].shape == (2048, 512)
    assert params["layer_2"]["moe"]["shared_gate"]["kernel"].shape == (
        2048, 1)
    assert params["lm_head"]["kernel"].shape == (2048, 18992)


def test_arithmetic_counts_the_rule_a_value_head_and_its_keys_a_key_head(job):
    work = job.kernel_work_per_step()
    # Seven products a kept pair at 16 heads of 256, one layer of four.
    causal = 7 * 2 * 256 * 16 * 2 * arithmetic.causal_pairs(8192)
    assert work["flash"]["flops"] == causal
    assert causal == pytest.approx(3.849e12, rel=1e-3)
    assert work["flash"]["forward"]["flops"] * 7 == work["flash"]["flops"] * 2
    # q, o, dO, dq at 16 heads; k, v, dk, dv at the 2 key-value heads.
    tensor = 2 * 8192 * 256 * 2
    assert work["flash"]["bytes"] == 6 * (16 + 2) * tensor
    assert work["flash"]["forward"]["bytes"] * 3 == work["flash"]["bytes"]
    # The rule: 128 chunks of 64 a sequence, a chunk and a VALUE head.
    macs = arithmetic_gdn.chunk_rule_macs(key_dim=128, value_dim=128)
    assert macs == 3 * 64 * 64 * 128 + 2 * 64 * 64 * 128 + 3 * 64 * 128 * 128 \
        + 64 ** 3 / 6
    assert work["gdn_scan"]["flops"] == 3 * (3 * 2 * 2 * 32 * 128 * macs)
    assert work["gdn_scan"]["flops"] == pytest.approx(0.857e12, rel=1e-3)
    # Its bytes: q and k ONCE A KEY HEAD (16), v and o at the 32 value heads.
    qk = TOKENS * 16 * 2 * 128 * 2
    v = TOKENS * 32 * 128 * 2
    gates = TOKENS * 32 * 2 * 4
    states = 2 * 32 * 128 * 128 * 128 * 4
    a_layer = (qk + v + gates + v + states) + (qk + v + gates + v + states
                                                + qk + v + gates)
    assert work["gdn_scan"]["bytes"] == 3 * a_layer
    assert arithmetic_hybrid_moe.scan_bytes(
        batch=2, seq=8192, key_heads=16, value_heads=32, key_dim=128,
        value_dim=128) == a_layer
    # arithmetic_gdn.scan_bytes reads q and k once a VALUE head: twice these.
    copied = arithmetic_gdn.scan_bytes(batch=2, seq=8192, value_heads=32,
                                       key_dim=128, value_dim=128)
    assert copied - a_layer == 3 * qk
    # The held experts at the rows this chip computes: 10 x 32 / 512 of an
    # expert a token, ~320 rows an expert.
    rows = TOKENS * 10 * 32 / 512
    assert rows == 10240 and rows / 32 == 320
    assert work["moe_experts"]["flops"] == 4 * (
        arithmetic_moe.expert_products_flops(rows=rows, hidden=2048,
                                             expert_ffn=512))
    peaks = manifest.peaks("TPU v5 lite")
    least, bound = arithmetic.roofline_seconds(
        work["gdn_scan"]["flops"], work["gdn_scan"]["bytes"], peaks)
    assert bound == "bytes" and least == pytest.approx(7.91e-3, rel=1e-2)


def test_a_step_is_23_tflop_by_the_benchmarks_rule(job):
    hidden = 2048
    linear = hidden * (2 * 16 * 128 + 3 * 32 * 128 + 2 * 32)
    full = hidden * 256 * (2 * 16 + 2 * 2 + 16)
    routed = (hidden * 512 + 3 * hidden * 512 + hidden
              + 10 * 32 / 512 * 3 * hidden * 512)
    weights = 3 * linear + full + 4 * routed + hidden * 18992
    assert weights == pytest.approx(191.86e6, rel=1e-3)
    scores = 2 * 2 * 16 * 256 * arithmetic.causal_pairs(8192)
    macs = arithmetic_gdn.chunk_rule_macs(key_dim=128, value_dim=128)
    rule = 3 * 3 * 2 * 32 * 128 * macs
    assert job.flops_per_unit() * 8192 == pytest.approx(
        3 * (2 * weights * 8192 + scores) + rule, rel=1e-12)
    assert job.flops_per_unit() * TOKENS == pytest.approx(23.02e12, rel=1e-3)


def test_job_builds_the_published_layers(job, cell):
    c = job.llama
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
            c.moe_intermediate_size) == (2048, 16, 2, 256, 512)
    assert c.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert (c.linear_num_key_heads, c.linear_num_value_heads,
            c.linear_key_head_dim, c.linear_value_head_dim,
            c.linear_conv_kernel_dim, c.linear_allow_neg_eigval) == (
                16, 32, 128, 128, 4, False)
    assert all(c.is_routed(i) for i in range(4))
    assert (c.num_experts, c.experts_held, c.experts_per_token,
            c.shared_experts, c.shared_expert_gate, c.norm_topk_prob,
            c.routed_scaling_factor, c.balance_over) == (
                512, 32, 10, 1, True, True, 1.0, "batch")
    assert (c.gating, c.qk_norm, c.qk_norm_over, c.zero_centered_norm,
            c.norm_placement) == ("elementwise", True, "head", True, "pre")
    assert c.rope_of(0) is None
    assert c.rope_of(3) == llama.RopeParameters(1e7, None, 0.25)
    assert c.remat == cell["config"]["training"]["remat"]
    assert job.expected_first_loss() == pytest.approx(
        np.log(18992) + 0.5 + 0.001)
    module = manifest.load_job("hybrid_moe_lm")
    assert module.layer_types(8, 4) == (
        ("linear_attention",) * 3 + ("full_attention",)) * 2
    with pytest.raises(ValueError, match="Qwen3-Next's decoder layers"):
        module.build({**cell["config"], "mlp_only_layers": [0]},
                     cell["traffic"], 1)
    with pytest.raises(ValueError, match="master AdamW"):
        module.build({**cell["config"], "training": {
            **cell["config"]["training"], "optimizer": "sgd"}},
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


def _gate_left_out(job):
    return _patched(job, llama, "_gated_lanes",
                    lambda gated: lambda out, logits: out)


def _shared_gate_left_out(job):
    return _patched(job, jax.nn, "sigmoid", lambda sigmoid: (
        lambda x: jnp.ones_like(x) if x.shape[-1] == 1 else sigmoid(x)))


def _keys_expanded_in_another_order(job):
    return _patched(job, jnp, "repeat", lambda repeat: (
        lambda x, n, axis=None: jnp.tile(x, (1, 1, n, 1))
        if axis == 2 and x.ndim == 4 else repeat(x, n, axis=axis)))


def _whole_head_turned(job):
    return _with_model(job, rope_parameters=(
        ("full_attention", llama.RopeParameters(1e7, None, 1.0)),))


@pytest.mark.parametrize("defect, least", [
    (None, 0.0), (_gate_left_out, 0.05), (_shared_gate_left_out, 0.02),
    (_keys_expanded_in_another_order, 0.05), (_whole_head_turned, 0.005)])
def test_comparison_passes_the_job_and_fails_wrong_versions_of_it(defect,
                                                                  least):
    """In float32 at the tiny size, where the job as it is reads 1e-5 and
    every wrong version has to show: the element-wise gate left out, the
    shared expert's gate left out, the key heads expanded in another order,
    the whole head turned where a quarter does.  (At the cell's size in bf16
    the limits of the configuration's file decide;
    ``checks.reference.why`` says what they caught there.)"""
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


# -- the reader of the new scope ----------------------------------------------

STEP = "jit(hvd_train_step)/hvd.loss/"
FWD = STEP + "jvp(LlamaModel)/layer_1/hvd.block.attn/linear/"
REC = (STEP + "transpose(jvp(LlamaModel))/hvd.loss/jvp(LlamaModel)/"
       "checkpoint/rematted_computation/layer_1/hvd.block.attn/linear/")
BWD = STEP + "transpose(jvp(LlamaModel))/layer_1/hvd.block.attn/linear/"
FUSION = "%fusion.3 = bf16[2,8192,32,128]{3,2,1,0} fusion(%a), kind=kLoop"


@pytest.mark.parametrize("op_name, under", [
    (FWD + "hvd.gdn.heads/broadcast_in_dim", True),
    (REC + "hvd.gdn.heads/reshape", True),
    (BWD + "hvd.gdn.heads/reduce_sum", True),
    (BWD + "transpose(jvp(hvd.gdn.heads))/reduce_sum", True),
    (FWD + "hvd.gdn.scan/dot_general", False),
    (FWD + "hvd.gdn.conv/pallas_call", False),
    (FWD + "wq/dot_general", False),
])
def test_classify_by_the_new_scope(op_name, under):
    assert gdn_heads_scopes.under_heads(op_name, names) is under
    # The accepted readers' three kinds know nothing of the new scope: what
    # is under it is in none of them.
    if under:
        assert gdn_scopes.classify(op_name, names) is None


def test_reader_on_hand_built_events(monkeypatch):
    step = "jit_hvd_train_step(1)"
    ops = [((FUSION, FWD + "hvd.gdn.heads/broadcast_in_dim"), 0.0, 1e-3),
           ((FUSION, FWD + "hvd.gdn.scan/dot_general"), 1e-3, 5e-3),
           ((FUSION, REC + "hvd.gdn.heads/broadcast_in_dim"), 5e-3, 6e-3),
           ((FUSION, BWD + "hvd.gdn.heads/reduce_sum"), 6e-3, 8e-3),
           ((FUSION, BWD + "hvd.gdn.scan/dot_general"), 8e-3, 10e-3)]
    events = {"devices": {0: {
        "ops": ops + [((n, o), a + 10e-3, b + 10e-3) for (n, o), a, b in ops],
        "modules": [(step, 0.0, 10e-3), (step, 10e-3, 20e-3)]}}}
    assert gdn_heads_scopes.heads_ms(events, names) == pytest.approx(4.0)
    assert gdn_scopes.partition(events, names)["scan"] == pytest.approx(6.0)
    # A layer with as many key heads as value heads never enters the scope.
    assert gdn_heads_scopes.heads_ms(
        {"devices": {0: {"ops": ops[1:2], "modules": [(step, 0.0, 10e-3)]}}},
        names) is None
    monkeypatch.setattr(gdn_heads_scopes.scopes, "read_events",
                        lambda path: events)
    monkeypatch.setattr(gdn_heads_scopes.trace, "find_xplane",
                        lambda trace_dir: __file__)
    gdn_heads_scopes._reduce_file.cache_clear()
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {}}}
    assert manifest.load_reader(METRIC)(ctx) == pytest.approx(4.0)
    assert manifest.load_reader(METRIC)({**ctx, "trace": None}) is None
    # A program without the scope (the parent) gives no number.
    monkeypatch.setattr(gdn_heads_scopes.scopes, "program_scopes",
                        lambda: argparse.Namespace(LOSS="hvd.loss",
                                                   GDN_SCAN="hvd.gdn.scan"))
    gdn_heads_scopes._reduce_file.cache_clear()
    assert manifest.load_reader(METRIC)(ctx) is None
    monkeypatch.setattr(gdn_heads_scopes.scopes, "program_scopes",
                        lambda: None)
    gdn_heads_scopes._reduce_file.cache_clear()
    assert manifest.load_reader(METRIC)(ctx) is None
    gdn_heads_scopes._reduce_file.cache_clear()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_recorded_trace_holds_the_new_scope(recorded):
    events = scopes.read_events(recorded)
    assert sorted(events["devices"]) == [0]
    ops = events["devices"][0]["ops"]
    held = {scopes.bare(part) for (_, op_name), _, _ in ops
            for part in scopes.components(op_name)}
    assert {names.LOSS, names.GDN_HEADS, names.GDN_CONV, names.GDN_GATES,
            names.GDN_SCAN, names.ATTN_GATE, names.ROPE, names.BLOCK_ATTN,
            names.BLOCK_FFN, names.HEAD, names.FLASH_FWD, names.FLASH_BWD,
            names.MOE_ROUTE, names.MOE_SHARED, names.REMATTED} <= held
    # The expansion is in the three linear layers and not in the full one,
    # inside the mixer's block and under none of the rule's other scopes.
    expanded = [op_name for (_, op_name), _, _ in ops
                if gdn_heads_scopes.under_heads(op_name, names)]
    assert {op.split("/layer_")[1][0] for op in expanded} == {"0", "1", "2"}
    for op_name in expanded:
        assert names.BLOCK_ATTN in op_name and "/linear/" in op_name
        assert gdn_scopes.classify(op_name, names) is None
    mosaic = [op_name for (text, op_name), _, _ in ops
              if scopes.trace.op_kind(text) == "mosaic"
              and not op_name.startswith(names.RAGGED_DOT_PREFIX)]
    assert mosaic and all(
        names.FLASH_FWD in op or names.FLASH_BWD in op or names.ROPE in op
        or names.GDN_CONV in op for op in mosaic)
    gated = {op_name.split("/layer_")[1][0] for (_, op_name), _, _ in ops
             if window_scopes.classify(op_name, names) == "gate"}
    assert gated == {"3"}
    assert os.path.getsize(RECORDED) < 700_000


def test_recorded_step_by_the_scopes_the_cell_reports(recorded, monkeypatch):
    events = scopes.read_events(recorded)
    heads = gdn_heads_scopes.heads_ms(events, names)
    rule = gdn_scopes.partition(events, names)
    routed = moe_scopes.partition(events, names)
    gate = window_scopes.partition(events, names)
    assert heads > 0 and gate["gate"] > 0 and gate["window"] == 0
    assert all(rule[kind] > 0 for kind in ("conv", "gates", "scan"))
    assert all(routed[kind] > 0 for kind in ("route", "experts", "shared"))
    by_class = scopes.partition(events, names)
    assert by_class["flash"]["fwd"] > 0 and by_class["flash"]["bwd"] > 0
    assert heads + sum(rule[k] for k in ("conv", "gates", "scan")) < (
        by_class["classes"]["forward"] + by_class["classes"]["backward"])
    monkeypatch.setattr(gdn_heads_scopes.trace, "find_xplane",
                        lambda trace_dir: recorded)
    gdn_heads_scopes._reduce_file.cache_clear()
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {}}}
    assert manifest.load_reader(METRIC)(ctx) == pytest.approx(heads)
    gdn_heads_scopes._reduce_file.cache_clear()


# -- the laguna cell's traced tiny run -----------------------------------------

def test_laguna_cell_traced_tiny():
    """``test_cell_traced_tiny`` traces the manifest's first and last
    cells; this configuration's cell is the last now, so the
    ``laguna-s-2.1`` cell's traced run is kept here (as
    ``test_benchmark_window.py`` keeps the ``olmo-hybrid-7b`` cell's)."""
    workload = "laguna-s-2.1.train-s8k"
    laguna = manifest.cell(workload)
    args = argparse.Namespace(workload=workload, seed=2 ** 31 + 11,
                              seconds=1.0, trace=1)
    result = json.loads(json.dumps(run.run(
        args, start=time.perf_counter(),
        overrides=TINY[laguna["config"]["job"]], allow_cpu=True)))
    assert result["correct"] is True, result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) <= {m["name"] for m in laguna["per_layer"]}
    assert metrics["compiles_in_window"] == 0
    assert metrics["hbm_arguments_gb"] > 0
