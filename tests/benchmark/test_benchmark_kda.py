"""The ``kimi-linear-48b-a3b`` configuration and its cell: the manifest's new
entries, the configuration's file against the catalog's row, the parameter
table from the built leaves, ``benchmark/arithmetic_kda.py`` and the job's
counts against hand counts, the job against wrong versions of itself through
the comparison that decides ``correct``, and the readers of the three scopes
a Kimi Delta Attention layer adds on a tiny step traced on a v5e."""

import dataclasses
import gzip
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark import (arithmetic, arithmetic_kda, arithmetic_moe,
                       gdn_solve_scopes, kda_scopes, manifest, moe_scopes,
                       scopes)
from horovod_tpu.common import scopes as names
from horovod_tpu.models import LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(manifest.HERE), "tools"))
import kimi_linear_wrong_versions as wrong_versions  # noqa: E402
from tiny_sizes import TINY  # noqa: E402

CELL = "kimi-linear-48b-a3b.train-s8k-b2"
NAME = "kimi-linear-48b-a3b"
SOURCE = ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
          "blob/main/config.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
JOINED = ("tokens_per_s_per_chip", "mfu", "flash_ms", "flash_roofline",
          "flash_fwd_ms", "flash_bwd_ms", "flash_fwd_roofline",
          "flash_bwd_roofline", "moe_route_ms", "moe_experts_ms",
          "moe_experts_roofline", "moe_shared_ms", "mla_latent_ms",
          "gdn_solve_ms", "block_attn_ms", "block_ffn_ms", "head_ms",
          "import_hvd_ms", "init_ms", "init_native_ms", "trace_attn_ms",
          "trace_ffn_ms", "trace_head_ms", "trace_optimizer_ms",
          "trace_kernels_ms", "trace_kernel_calls", "trace_loss_self_ms",
          "trace_loss_forward_ms", "trace_loss_backward_ms",
          "trace_layers_self_ms", "trace_rules_ms", "trace_backward_self_ms",
          "step_cache_retrieval_ms", "step_load_ms", "state_trace_ms",
          "state_backend_ms")
NEW = {"kda_conv_ms": ("model", "ms", "lower", "program_span"),
       "kda_gates_ms": ("model", "ms", "lower", "program_span"),
       "kda_scan_ms": ("kernels", "ms", "lower", "program_span"),
       "kda_scan_roofline": ("kernels", "%", "higher", "device_trace")}
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size"]
RECORDED = os.path.join(manifest.HERE, "testdata", "tiny-kda-v5e.xspace.gz")
#: ``kda_scopes.partition`` of the recorded trace, ms a step (my chip run,
#: PR 69: ``.study/record_kda.py``'s own line).
RECORDED_MS = {"conv": 0.061, "gates": 0.030, "scan": 0.130}
BATCH, SEQ, HIDDEN, HEADS, DIM = 2, 8192, 2304, 32, 128
TOKENS = BATCH * SEQ


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
    return manifest.load_job("kda_moe_lm").build(cell["config"],
                                                 cell["traffic"], 1)


# -- the manifest and the configuration's file --------------------------------

def test_the_manifests_entries_are_the_issues():
    listed = manifest.load()
    entry = next(c for c in listed["configs"] if c["name"] == NAME)
    assert entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["reduced"] == REDUCED and len(entry["why"]) <= 200
    mine = [w for w in listed["workloads"] if w["config"] == NAME]
    assert [(w["name"], w["traffic"], w["chips"]) for w in mine] == [
        (CELL, "train-s8k-b2", 1)]
    assert len(mine[0]["why"]) <= 200 and "8192" in mine[0]["why"]
    # Seventeen cells of 24; a quarter of them, rounded down, may take four
    # chips, and one does.
    cells = listed["workloads"][:[w["name"] for w in listed["workloads"]
                                  ].index(CELL) + 1]
    assert len(cells) == 17 and len(cells) // 4 == 4
    assert sum(w["chips"] == 4 for w in cells) == 1
    metrics = {m["name"]: m for m in
               listed["end_to_end"] + listed["per_layer"]}
    for name in JOINED:
        assert CELL in metrics[name]["workloads"], name
    for name, (layer, unit, better, source) in NEW.items():
        assert metrics[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "step_ms_p90", "workloads": [CELL]}
    # Every other list is some other mechanism's.
    others = {name for name, m in metrics.items()
              if CELL in m.get("workloads", ())} - set(JOINED) - set(NEW)
    assert not others, others
    # The cell resolves to its files by name alone.
    resolved = manifest.cell(CELL)
    assert resolved["config"]["job"] == "kda_moe_lm"
    assert resolved["config"]["reference"] == "kimi_linear"
    assert resolved["traffic"] == {
        "chips": 1, "mesh": {"data": 1}, "batch_per_chip": 2,
        "sequence": 8192, "pool": 8, "sample_per_chip": 1}
    for name in NEW:
        assert callable(manifest.load_reader(name))


def test_the_configuration_is_the_catalogs_row_but_for_its_four_cuts(cell):
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
    assert [(config[key], published[key]) for key in REDUCED
            if key != "linear_attn_config"] == [
                (5, 27), (8, 256), (20480, 163840)]
    # The lists cut to layers 1 to 5, the delta-rule layers' sizes kept.
    sizes, whole = config["linear_attn_config"], published[
        "linear_attn_config"]
    assert sizes["kda_layers"] == [1, 2, 3, 5] == [
        i for i in whole["kda_layers"] if i <= 5]
    assert sizes["full_attn_layers"] == [4] == [
        i for i in whole["full_attn_layers"] if i <= 5]
    assert {key: sizes[key] for key in (
        "num_heads", "head_dim", "short_conv_kernel_size")} == {
            key: whole[key] for key in (
                "num_heads", "head_dim", "short_conv_kernel_size")} == {
                    "num_heads": 32, "head_dim": 128,
                    "short_conv_kernel_size": 4}
    # Every width as published.
    assert (config["hidden_size"], config["num_attention_heads"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["q_lora_rank"],
            config["kv_lora_rank"], config["intermediate_size"],
            config["moe_intermediate_size"],
            config["num_experts_per_token"], config["num_shared_experts"],
            config["routed_scaling_factor"], config["moe_renormalize"],
            config["mla_use_nope"], config["tie_word_embeddings"]) == (
                2304, 32, 128, 64, 128, None, 512, 9216, 1024, 8, 1, 2.446,
                True, True, False)
    assert set(config["reduced_why"]) == set(REDUCED)
    assert "8.43 GB" in config["reduced_why"]["num_hidden_layers"]
    assert "602,433,408" in config["reduced_why"]["num_hidden_layers"]
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 32
    assert (deployment["num_experts_published"],
            deployment["first_held_expert"],
            deployment["vocab_size_published"],
            deployment["num_hidden_layers_published"]) == (256, 0, 163840, 27)
    for key in ("dt_bias", "a_log", "gate_bias", "unrotated_lanes",
                "head_dim", "choice_bias", "aux_loss_alpha",
                "router_bias_update_rate", "initialisation", "training",
                "warmup_steps"):
        assert key in config["assumed"], key
    assert config["head_dim"] == 72
    assert config["training"]["remat"] == "layer_keep_attention"


def test_the_parameter_table_is_the_built_models(job):
    """The configuration's table, re-reckoned from the BUILT model's leaves:
    602,433,408 parameters, 8.43 GB at the 14 bytes this repo keeps."""
    params = jax.eval_shape(job.init_state, jax.random.key(0))[0]["params"]

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    dense, routed, latent = (params[f"layer_{i}"] for i in (0, 1, 3))
    mixer = dense["kda"]
    assert count(mixer) == count(routed["kda"]) == 39_514_272
    assert sum(count(mixer[name]) for name in ("wq", "wk", "wv")) == (
        3 * HIDDEN * HEADS * DIM) == 28_311_552
    assert sum(count(mixer[name]) for name in (
        "conv_q", "conv_k", "conv_v")) == 3 * 4 * 4096 == 49_152
    assert (mixer["a_log"].shape, mixer["dt_bias"].shape,
            mixer["o_norm"].shape) == ((32,), (4096,), (128,))
    for low_rank in (("f_a", "f_b"), ("g_a", "g_b")):
        assert sum(count(mixer[name]) for name in low_rank) == 819_200
    assert count(mixer["wb"]) == 73_728 and count(mixer["wo"]) == 9_437_184
    assert count(latent["attn"]) == 29_114_880
    assert set(latent["attn"]) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    for layer in (dense, routed, latent):
        assert count(layer["norm_attn"]) + count(layer["norm_mlp"]) == 4_608
    assert count(dense["mlp"]) == 63_700_992
    moe = routed["moe"]
    assert moe["w_gate_up"].shape == (8, HIDDEN, 2048)
    assert count(moe["shared"]) == 7_077_888
    assert count(moe["w_gate_up"]) + count(moe["w_down"]) == 8 * 7_077_888
    assert count(moe["router"]) == 589_824
    assert count(dense) == 103_219_872
    assert count(routed) == 103_809_696
    assert count(latent) == 93_410_304
    ends = (count(params["tok_emb"]) + count(params["lm_head"])
            + count(params["norm_f"]))
    assert ends == 94_374_144
    total = count(params)
    assert total == (103_219_872 + 3 * 103_809_696 + 93_410_304
                     + ends) == 602_433_408
    assert 14 * total / 1e9 == pytest.approx(8.43, abs=0.005)
    # Sixteen held would be the issue's other row.
    assert total + 4 * 8 * 7_077_888 == 828_925_824      # 11.61 GB
    assert all(leaf.dtype == jnp.bfloat16 for leaf in jax.tree.leaves(params))


# -- arithmetic, by hand ----------------------------------------------------------

def test_the_rules_operations_and_bytes_by_hand():
    """One chunk of one head at C = 64, d_k = d_v = 128, forward."""
    macs = arithmetic_kda.chunk_rule_macs(key_dim=DIM, value_dim=DIM)
    assert macs == (
        2 * 64 * 64 * 128           # A and P: a pair costs d_k
        + 64 ** 3 / 6               # the solve by substitution
        + 64 * 64 * (128 + 128)     # W = T K', U0 = T V'
        + 3 * 64 * 128 * 128        # W S^T, Q S^T, U^T K
        + 64 * 64 * 128)            # P U
    assert macs == pytest.approx(5_810_858.67, abs=0.01)
    shape = dict(batch=BATCH, seq=SEQ, heads=HEADS, key_dim=DIM,
                 value_dim=DIM)
    chunks = BATCH * HEADS * (SEQ // 64)
    assert arithmetic_kda.scan_flops(**shape) == 3 * 2 * chunks * macs
    rows = TOKENS * HEADS
    qkv, gates, out = rows * 3 * 128 * 2, rows * (128 + 1) * 4, rows * 128 * 2
    states = chunks * 128 * 128 * 4
    # g is as large as k, in float32: two fifths of what the rule reads
    # (the scalar rule's gates are 1 % of it).
    assert gates == pytest.approx(0.40 * (qkv + gates), rel=0.01)
    assert arithmetic_kda.scan_bytes(**shape) == (
        2 * (qkv + gates + out + states) + qkv + gates)
    work = arithmetic_kda.scan_work(layers=4, **shape)
    assert work == {"flops": 4 * arithmetic_kda.scan_flops(**shape),
                    "bytes": 4 * arithmetic_kda.scan_bytes(**shape)}
    least_ms, bound = arithmetic_kda.roofline_ms(
        work, manifest.peaks("TPU v5 lite"))
    assert bound == "bytes" and least_ms == pytest.approx(16.4, abs=0.1)
    assert arithmetic_kda.kda_mixer_matmul_params(
        hidden=HIDDEN, heads=HEADS, head_dim=DIM) == 39_514_272 - (
            49_152 + 32 + 4096 + 128)


def test_the_jobs_counts_by_hand(job):
    c = job.llama
    assert (c.num_layers, c.first_dense_layers, c.experts_held,
            c.num_experts, c.experts_per_token, c.mla_use_nope,
            c.q_lora_rank, c.routed_scaling_factor) == (
                5, 1, 8, 256, 8, True, None, 2.446)
    assert [spec.mixer for spec in c.layers] == [
        "kda", "kda", "kda", "attention", "kda"]
    assert all(spec.rope is None for spec in c.layers)
    work = job.kernel_work_per_step()
    assert set(work) == {"flash", "kda_scan", "moe_experts"}
    assert work["kda_scan"] == arithmetic_kda.scan_work(
        layers=4, batch=BATCH, seq=SEQ, heads=HEADS, key_dim=DIM,
        value_dim=DIM)
    pairs = BATCH * 32 * arithmetic.causal_pairs(SEQ)
    assert work["flash"]["forward"]["flops"] == 2 * (192 + 128) * pairs
    assert work["flash"]["backward"]["flops"] == 2 * (
        3 * 192 + 2 * 128) * pairs
    rows = arithmetic_moe.expert_rows(tokens=TOKENS, per_token=8, held=8,
                                      experts=256)
    assert rows == 4096             # 512 a held expert
    assert work["moe_experts"]["flops"] == (
        4 * 3 * 2 * rows * 3 * HIDDEN * 1024)
    # A token's forward multiply-adds, by the table less what is no product
    # (norms, filters, A_log, dt_bias), at the held experts' expected share.
    mixer = 39_514_272 - (49_152 + 32 + 4096 + 128)
    latent = 29_114_880 - 512
    routed = 589_824 + 7_077_888 + 0.25 * 7_077_888
    weights = (4 * mixer + latent + 63_700_992 + 4 * routed
               + 20480 * HIDDEN)
    scores = 32 * 2 * (192 + 128) * (SEQ + 1) / 2
    rule = 4 * arithmetic_kda.scan_flops(
        batch=1, seq=SEQ, heads=HEADS, key_dim=DIM, value_dim=DIM) / SEQ
    assert job.flops_per_unit() == pytest.approx(
        3 * (2 * weights + scores) + rule, rel=1e-9)
    assert job.flops_per_unit() * TOKENS == pytest.approx(38.26e12, rel=0.01)
    assert job.expected_first_loss() == pytest.approx(
        np.log(20480) + 0.5 + 0.001)


# -- the job against wrong versions of itself -------------------------------------

@pytest.mark.parametrize("version, least", [
    ("right", 0.0), ("scalar_decay_a_head", 0.02),
    ("decay_behind_the_delta_step", 0.02),
    ("silu_for_the_sigmoid_output_gate", 0.05),
    ("l2_norm_of_q_and_k_left_out", 0.05), ("latent_lanes_rotated", 0.02),
    ("float8_e4m3", 0.01)])
def test_comparison_passes_the_job_and_fails_wrong_versions_of_it(version,
                                                                  least):
    """``tools/kimi_linear_wrong_versions.py``'s table, in float32 at the
    tiny size, where the job as it is reads 1e-6 and every wrong version has
    to show (the two versions of the gates are ``xing4``'s, held tiny by
    ``test_benchmark_hc.py``).  (At the cell's size in bf16 the whole table
    runs on the chip under the limits of the configuration's file;
    ``checks.reference.why`` has its verdicts.)"""
    job, reference, config = _tiny_job()
    job.llama = dataclasses.replace(job.llama, dtype=jnp.float32,
                                    logits_dtype=jnp.float32)
    job.model = LlamaModel(job.llama, attention_fn=flash_attention_fn)
    config = {**config, "checks": {**config["checks"], "reference": {
        "parameters": "initial", "loss_abs": 1e-4, "grad_rel": 1e-3}}}
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    params, _, bias = jax.jit(job.init_state)(jax.random.key(0))
    state = (jax.tree.map(lambda p: p.astype(jnp.float32), params), None,
             bias)
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
        "right", "scalar_decay_a_head", "decay_behind_the_delta_step",
        "silu_for_the_sigmoid_output_gate", "l2_norm_of_q_and_k_left_out",
        "latent_lanes_rotated", "gates_not_renormalised",
        "gates_without_their_2.446", "float8_e4m3"]
    assert wrong_versions.CELL == CELL


def test_each_limit_lies_between_its_two_readings(cell):
    """The v5e's readings at the cell's size (``checks.reference.why``): the
    job as it is and the mildest wrong version; each limit has to tell the
    two apart with room on both sides."""
    reference = cell["config"]["checks"]["reference"]
    readings = reference["readings"]
    widest = max(readings["right_grad_rel"])
    assert len(readings["right_grad_rel"]) >= 5
    assert 1.2 * widest < reference["grad_rel"]
    assert reference["grad_rel"] < readings["mildest_wrong_grad_rel"] / 1.1
    assert 2 * max(readings["right_loss_abs"]) < reference["loss_abs"]
    assert reference["loss_abs"] < readings["float8_e4m3_loss_abs"] / 1.5
    for number in (widest, readings["mildest_wrong_grad_rel"],
                   readings["float8_e4m3_loss_abs"]):
        assert f"{number:g}"[:6] in reference["why"], number


# -- the readers of the three scopes a KDA layer adds -----------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


@pytest.mark.parametrize("op_name, kind", [
    ("jit(hvd_train_step)/hvd.loss/jvp(LlamaModel)/layer_0/hvd.block.attn/"
     "kda/hvd.kda.conv/convolved/mul", "conv"),
    ("jit(hvd_train_step)/hvd.loss/jvp(LlamaModel)/layer_2/hvd.block.attn/"
     "kda/hvd.kda.gates/f_b/dot_general", "gates"),
    ("jit(hvd_train_step)/hvd.loss/transpose(jvp(LlamaModel))/checkpoint/"
     "rematted_computation/layer_2/hvd.block.attn/kda/hvd.kda.scan/while/"
     "body/_walk_call/pallas_call", "scan"),
    # The shared solve nests inside the rule's scope: counted there.
    ("jit(hvd_train_step)/hvd.loss/jvp(LlamaModel)/layer_0/hvd.block.attn/"
     "kda/hvd.kda.scan/while/body/hvd.gdn.solve/_solve/pallas_call", "scan"),
    ("jit(hvd_train_step)/hvd.loss/jvp(LlamaModel)/layer_1/hvd.block.attn/"
     "attn/hvd.mla.latent/wkv_a/dot_general", None),
    ("jit(hvd_train_step)/hvd.loss/jvp(LlamaModel)/hvd.head/reduce_sum",
     None),
])
def test_an_operation_is_of_one_of_the_three_or_of_none(op_name, kind):
    assert kda_scopes.classify(op_name, names) == kind


def test_recorded_trace_holds_the_layers_scopes(recorded):
    events = scopes.read_events(recorded)
    assert sorted(events["devices"]) == [0]
    ops = events["devices"][0]["ops"]
    held = {scopes.bare(part) for (_, op_name), _, _ in ops
            for part in scopes.components(op_name)}
    assert {names.LOSS, names.KDA_CONV, names.KDA_GATES, names.KDA_SCAN,
            names.GDN_SOLVE, names.MLA_LATENT, names.BLOCK_ATTN,
            names.BLOCK_FFN, names.HEAD, names.FLASH_FWD, names.FLASH_BWD,
            names.MOE_ROUTE, names.MOE_EXPERTS, names.MOE_COMBINE,
            names.MOE_SHARED, names.REMATTED} <= held
    # No rotation anywhere, and none of the scalar rule's own scopes.
    assert not {names.ROPE, names.GDN_SCAN, names.GDN_CONV,
                names.GDN_GATES} & held
    # All three scopes in the two KDA layers (0 and 2) and not in the latent
    # one, inside the mixer's block.
    for kind in kda_scopes.KINDS:
        mine = [op_name for (_, op_name), _, _ in ops
                if "/layer_" in op_name
                and kda_scopes.classify(op_name, names) == kind]
        assert {op.split("/layer_")[1][0] for op in mine} == {"0", "2"}, kind
        assert all(names.BLOCK_ATTN in op for op in mine)
    # The rule's five Mosaic calls ran on the chip: the systems, the solve
    # and the walk forward (and again under recomputation), the walk and the
    # systems backward.
    calls = [op_name for (_, op_name), _, _ in ops
             if names.KDA_SCAN in op_name and "pallas_call" in op_name]
    for body in ("_systems_forward", "_solve", "_walk_call",
                 "_walk_back_call", "_systems_backward"):
        assert any(f"jit({body})" in op for op in calls), body
    assert any("_walk_call" in op and names.REMATTED in op for op in calls)
    assert os.path.getsize(RECORDED) < 400_000


def test_recorded_step_by_the_scopes_the_cell_reports(recorded, monkeypatch):
    events = scopes.read_events(recorded)
    layers = kda_scopes.partition(events, names)
    # The traced run's own line (my chip run, PR 69).
    assert layers["conv"] == pytest.approx(RECORDED_MS["conv"], abs=0.002)
    assert layers["gates"] == pytest.approx(RECORDED_MS["gates"], abs=0.002)
    assert layers["scan"] == pytest.approx(RECORDED_MS["scan"], abs=0.002)
    assert 0 < layers["scan_recomputed"] < 0.5 * layers["scan"]
    routed = moe_scopes.partition(events, names)
    assert min(routed.values()) > 0
    solve = gdn_solve_scopes.solve_ms(events, names)
    assert 0 < solve < layers["scan"]
    for module in (kda_scopes, moe_scopes, gdn_solve_scopes):
        monkeypatch.setattr(module.trace, "find_xplane",
                            lambda trace_dir: recorded)
        module._reduce_file.cache_clear()
    work = arithmetic_kda.scan_work(layers=2, batch=1, seq=512, heads=2,
                                    key_dim=128, value_dim=128)
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {"kda_scan": work}}}
    for kind in kda_scopes.KINDS:
        assert manifest.load_reader(f"kda_{kind}_ms")(ctx) == pytest.approx(
            layers[kind])
    assert manifest.load_reader("mla_latent_ms")(ctx) == pytest.approx(
        routed["latent"])
    assert manifest.load_reader("gdn_solve_ms")(ctx) == pytest.approx(solve)
    share = manifest.load_reader("kda_scan_roofline")(ctx)
    assert share == pytest.approx(
        100 * work["bytes"] / 819e9 * 1e3 / layers["scan"])
    assert 0 < share < 100
    # A job that counts no rule, a run without a trace, a program without
    # the scopes: no number, no error.
    assert manifest.load_reader("kda_scan_roofline")(
        {**ctx, "job": {"kernel_work_per_step": {}}}) is None
    assert manifest.load_reader("kda_scan_ms")({**ctx, "trace": None}) is None
    kda_scopes._reduce_file.cache_clear()
    monkeypatch.delattr(names, "KDA_SCAN")
    for name in NEW:
        assert manifest.load_reader(name)(ctx) is None
    for module in (kda_scopes, moe_scopes, gdn_solve_scopes):
        module._reduce_file.cache_clear()


# -- the xing4 cell's traced tiny run ----------------------------------------------

def test_xing4_cell_traced_tiny():
    """``test_cell_traced_tiny`` traces the manifest's first and last cells;
    this configuration's cell is the last now, so the ``xing4.0-29b-a4b``
    cell's traced run is kept here."""
    import argparse
    import time

    from benchmark import run

    workload = "xing4.0-29b-a4b.train-s8k"
    before = manifest.cell(workload)
    args = argparse.Namespace(workload=workload, seed=2 ** 31 + 11,
                              seconds=1.0, trace=1)
    result = json.loads(json.dumps(run.run(
        args, start=time.perf_counter(),
        overrides=TINY[before["config"]["job"]], allow_cpu=True)))
    assert result["correct"] is True, result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) <= {m["name"] for m in before["per_layer"]}
    assert metrics["compiles_in_window"] == 0
    assert metrics["hbm_arguments_gb"] > 0
