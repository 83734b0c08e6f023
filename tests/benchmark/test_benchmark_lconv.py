"""The ``lfm2-24b-a2b`` configuration and its cell: the manifest's new
entries, the configuration's file against the catalog's row, the parameter
table from the built model's leaves, the job and
``benchmark/arithmetic_lconv.py`` against hand counts, the three new readers
on hand-built events and on a tiny step traced on a v5e, and the reference's
independence of the program.  (``test_cell_traced_tiny`` traces the
manifest's first and last cells, so this cell's traced tiny run is there.)"""

import argparse
import gzip
import json
import os

import jax
import numpy as np
import pytest

from benchmark import (arithmetic, arithmetic_lconv, arithmetic_moe,
                       lconv_scopes, manifest, moe_scopes, scopes)
from horovod_tpu.common import scopes as names

CELL = "lfm2-24b-a2b.train-s8k-b2"
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = {"lconv_proj_ms": ("model", "program_span", "ms", "lower"),
           "lconv_conv_ms": ("kernels", "program_span", "ms", "lower"),
           "lconv_conv_roofline": ("kernels", "device_trace", "%", "higher")}
JOINED = ("tokens_per_s_per_chip", "mfu", "flash_ms", "flash_roofline",
          "flash_fwd_ms", "flash_bwd_ms", "flash_fwd_roofline",
          "flash_bwd_roofline", "moe_route_ms", "moe_experts_ms",
          "moe_experts_roofline", "block_attn_ms", "block_ffn_ms", "head_ms",
          "qk_norm_ms", "import_hvd_ms", "init_ms", "init_native_ms",
          "trace_attn_ms", "trace_ffn_ms", "trace_head_ms",
          "trace_optimizer_ms", "trace_kernels_ms", "trace_kernel_calls",
          "trace_loss_self_ms")
HERE_TYPES = ["conv", "full_attention", "conv", "conv", "conv"]
REDUCED = {"num_hidden_layers": (5, 40), "layer_types": (HERE_TYPES, None),
           "num_dense_layers": (1, 2), "num_experts": (16, 64),
           "vocab_size": (16384, 65536)}
# Hidden 256; the cell's five layers (conv, full_attention, conv, conv, conv)
# with 3-tap gated filters over 256 channels (the Mosaic pass engages), 4
# query heads over 2 key-value heads of 64 with a QK-norm, a dense layer of
# 512 and four routed ones that hold experts 4 to 7 of 16, top-3, a tied head
# over 1,024 ids, 1 x 512 tokens, ``layer_keep_attention``: traced on one
# TPU v5e chip by this harness (PR 56), cut by ``benchmark.xspace.trim`` to
# its first three steps and to the lines the reductions read; gzipped.
# Named ``.xspace.gz`` as PERF.md's Open question 23 says.
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-lconv-moe-decoder-v5e.xspace.gz")
TOKENS = 2 * 8192
HIDDEN = 2048


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(CELL)


@pytest.fixture(scope="module")
def job(cell):
    return manifest.load_job("lconv_moe_lm").build(cell["config"],
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
    assert config["reduced"] == list(REDUCED)
    for key, (here, there) in REDUCED.items():
        assert config[key] == here, key
        assert there is None or published[key] == there, key
    # Layers 1 to 5 of the published forty, in their order.
    assert published["layer_types"][1:6] == HERE_TYPES
    assert config["deployment"]["layer_types_published"] == (
        published["layer_types"])
    assert (published["layer_types"].count("conv"),
            published["layer_types"].count("full_attention")) == (30, 10)
    # Every width as published.
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["conv_L_cache"], config["conv_bias"], config["norm_eps"],
            config["rope_parameters"], config["routed_scaling_factor"],
            config["max_position_embeddings"], config["use_expert_bias"],
            config["norm_topk_prob"]) == (
                2048, 32, 8, 11776, 1536, 4, 3, False, 1e-5,
                {"rope_theta": 1000000, "rope_type": "default"}, 1, 128000,
                True, True)
    assert set(config["reduced_why"]) == set(REDUCED)
    deployment = config["deployment"]
    assert (deployment["chips_sharing_a_layer"],
            deployment["num_experts_published"],
            deployment["first_held_expert"],
            deployment["vocab_size_published"],
            deployment["num_hidden_layers_published"],
            deployment["num_dense_layers_published"]) == (
                4, 64, 0, 65536, 40, 2)
    assumed = config["assumed"]
    assert {"tie_word_embeddings", "head_dim_why", "qk_layernorm",
            "in_proj_order", "rotation", "gate_sum_eps", "bias_update_rate",
            "aux_loss_alpha", "dense_width", "initialisation",
            "training"} <= set(assumed)
    assert (assumed["tie_word_embeddings"], config["head_dim"],
            assumed["gate_sum_eps"]) == (True, 64, 1e-6)
    assert config["training"]["remat"] in ("layer", "layer_keep_attention")
    limits = config["checks"]["reference"]
    assert limits["parameters"] == "initial" and len(limits["why"]) > 500


def test_the_manifests_new_entries(cell):
    listed = manifest.load()
    entry = next(c for c in listed["configs"] if c["name"] == "lfm2-24b-a2b")
    assert entry["source"] == cell["config"]["source"] == SOURCE
    assert entry["reduced"] == cell["config"]["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/lfm2-24b-a2b.json"
    assert len(entry["why"]) <= 200
    # (By name, not by place: a later PR's entries come behind these.)
    workload = next(w for w in listed["workloads"] if w["name"] == CELL)
    assert workload == {"name": CELL, "config": "lfm2-24b-a2b",
                        "traffic": "train-s8k-b2", "chips": 1,
                        "why": workload["why"]}
    assert "1024 rows" in workload["why"] and len(workload["why"]) <= 200
    assert cell["traffic"] == manifest.cell(
        "nemotron-3-nano-30b-a3b.train-s8k-b2")["traffic"]
    assert len(listed["configs"]) >= 11 and len(listed["workloads"]) >= 13
    assert sum(w["chips"] == 4 for w in listed["workloads"]) == 1
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "step_ms_p90", "peak_hbm_gb", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(METRICS) | set(JOINED[1:]) <= reported
    # Readers that find nothing to read in this cell: it has no shared
    # expert, and none of the other mixers.
    assert not {"moe_shared_ms", "recompute_ms", "dense_roofline",
                "mla_latent_ms", "sparse_index_ms", "window_attn_ms",
                "gdn_scan_ms", "gdn_conv_ms", "ssd_conv_ms", "sscan_conv_ms",
                "attn_gate_ms", "gmu_ms"} & reported
    per_layer = {m["name"]: m for m in listed["per_layer"]}
    for name, (layer, source, unit, better) in METRICS.items():
        assert per_layer[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "step_ms_p90", "workloads": [CELL]}
        assert os.path.exists(manifest.metric_path(name))
    for name in JOINED:
        joined = per_layer.get(name) or next(
            m for m in listed["end_to_end"] if m["name"] == name)
        assert CELL in joined["workloads"]


def test_the_reference_imports_nothing_from_the_program():
    path = manifest.load_reference("lfm2_moe").__file__
    with open(path) as f:
        source = f.read()
    code = source[source.index('"""', 3) + 3:]          # behind the docstring
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp"]
    assert "horovod_tpu" not in code
    assert 'default_matmul_precision("highest")' in source


# -- the parameter table, the job and its arithmetic ---------------------------

def test_the_parameter_table(job):
    """ISSUE 56's table, matrix by matrix, and the program's own count from
    the built model's leaves."""
    conv = HIDDEN * 3 * HIDDEN + HIDDEN * HIDDEN + 3 * HIDDEN
    attention = 2 * HIDDEN * HIDDEN + 2 * HIDDEN * 512 + 2 * 64
    dense = 3 * HIDDEN * 11776
    expert = 3 * HIDDEN * 1536
    router, norms = HIDDEN * 64, 2 * HIDDEN
    assert (conv, attention, dense, expert, 16 * expert, router) == (
        16_783_360, 10_485_888, 72_351_744, 9_437_184, 150_994_944, 131_072)
    dense_layer = conv + dense + norms
    routed_conv = conv + 16 * expert + router + norms
    routed_attention = attention + 16 * expert + router + norms
    assert (dense_layer, routed_conv, 3 * routed_conv, routed_attention) == (
        89_139_200, 167_913_472, 503_740_416, 161_616_000)
    embedding = 16384 * HIDDEN
    table = (dense_layer + 3 * routed_conv + routed_attention + embedding
             + HIDDEN)
    params, _, bias = jax.eval_shape(job.init_state, jax.random.key(0))
    assert set(params) == {"params"}
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(params))
    assert count == table == 788_052_096
    assert count * 14 == pytest.approx(11.03e9, rel=1e-3)
    assert "788,052,096 parameters = 11.03 GB" in job.config["reduced_why"][
        "num_hidden_layers"]
    # The choice bias is state beside the parameters, 64 entries a router.
    assert {k: v["moe"]["bias"].shape for k, v in bias.items()} == {
        f"layer_{i}": (64,) for i in (1, 2, 3, 4)}
    tree = params["params"]
    assert "lm_head" not in tree                        # the head is tied
    assert tree["tok_emb"]["embedding"].shape == (16384, HIDDEN)
    assert tree["layer_0"]["conv"]["in_proj"]["kernel"].shape == (
        HIDDEN, 3 * HIDDEN)
    assert tree["layer_0"]["conv"]["conv_w"].shape == (3, HIDDEN)
    assert tree["layer_0"]["conv"]["out_proj"]["kernel"].shape == (
        HIDDEN, HIDDEN)
    assert tree["layer_0"]["mlp"]["w_gate_up"]["kernel"].shape == (
        HIDDEN, 2 * 11776)
    assert tree["layer_1"]["attn"]["wq"]["kernel"].shape == (HIDDEN, HIDDEN)
    assert tree["layer_1"]["attn"]["wk"]["kernel"].shape == (HIDDEN, 512)
    assert tree["layer_1"]["attn"]["q_norm"]["scale"].shape == (64,)
    assert tree["layer_2"]["moe"]["w_gate_up"].shape == (16, HIDDEN, 3072)
    assert tree["layer_2"]["moe"]["w_down"].shape == (16, 1536, HIDDEN)
    assert tree["layer_2"]["moe"]["router"]["kernel"].shape == (HIDDEN, 64)
    assert "shared" not in tree["layer_2"]["moe"]
    assert [sorted(tree[f"layer_{i}"]) for i in range(5)] == [
        sorted((mixer, ffn, "norm_attn", "norm_mlp")) for mixer, ffn in (
            ("conv", "mlp"), ("attn", "moe"), ("conv", "moe"),
            ("conv", "moe"), ("conv", "moe"))]


def test_the_gated_filters_bytes_and_operations_by_hand():
    """B, C, z read and y written forward: 268 MB a layer at 2 x 8192 x
    2048; those and g read and three cotangents written backward: 470 MB."""
    tensor = TOKENS * HIDDEN * 2
    assert tensor == 67_108_864
    moved = arithmetic_lconv.gated_conv_bytes(batch=2, seq=8192,
                                              channels=HIDDEN)
    assert moved == {"forward": 4 * tensor, "backward": 7 * tensor}
    assert (round(moved["forward"] / 1e6), round(moved["backward"] / 1e6)) == (
        268, 470)
    # A token and a channel at 3 taps: 7 operations forward, 21 backward.
    assert arithmetic_lconv.gated_conv_flops(
        batch=1, seq=1, channels=1, taps=3) == 7 + 21
    assert arithmetic_lconv.gated_conv_flops(
        batch=2, seq=8192, channels=HIDDEN, taps=3) == 28 * TOKENS * HIDDEN
    assert arithmetic_lconv.conv_mixer_matmul_params(hidden=HIDDEN) == (
        12_582_912 + 4_194_304)


def test_kernel_work_of_the_cell(job):
    work = job.kernel_work_per_step()
    assert set(work) == {"flash", "lconv_conv", "moe_experts"}
    # Seven products a kept pair at 32 heads of 64, ONE layer of five.
    causal = 7 * 2 * 64 * 32 * 2 * arithmetic.causal_pairs(8192)
    assert work["flash"]["flops"] == causal
    assert work["flash"]["forward"]["flops"] * 7 == work["flash"]["flops"] * 2
    tensor = TOKENS * 64 * 2
    assert work["flash"]["bytes"] == 6 * (32 + 8) * tensor
    # The gated filters of four layers: bytes bound, 3.6 ms at 819 GB/s.
    assert work["lconv_conv"]["bytes"] == 4 * 11 * 67_108_864
    assert work["lconv_conv"]["flops"] == 4 * 28 * TOKENS * HIDDEN
    least, bound = arithmetic.roofline_seconds(
        work["lconv_conv"]["flops"], work["lconv_conv"]["bytes"],
        manifest.peaks("TPU v5 lite"))
    assert bound == "bytes" and least == pytest.approx(3.605e-3, rel=1e-3)
    # The held experts at the rows this chip computes: 4 x 16 / 64 of an
    # expert a token, 1,024 rows an expert, three matrices an expert.
    rows = arithmetic_moe.expert_rows(tokens=TOKENS, per_token=4, held=16,
                                      experts=64)
    assert rows == 16384 and rows / 16 == 1024
    assert work["moe_experts"]["flops"] == 4 * 3 * 2 * rows * 3 * HIDDEN * 1536
    assert work["moe_experts"]["bytes"] == 4 * 3 * 2 * (
        16 * 3 * HIDDEN * 1536 + rows * (2 * HIDDEN + 3 * 1536))


def test_flops_of_the_five_layers_by_hand(job):
    conv = 4 * HIDDEN * HIDDEN
    attention = HIDDEN * 64 * (2 * 32 + 2 * 8)
    routed = HIDDEN * 64 + 4 * 16 / 64 * 3 * HIDDEN * 1536
    weights = (4 * conv + attention + 3 * HIDDEN * 11776 + 4 * routed
               + HIDDEN * 16384)
    # The issue's reckoning: dense layer 89 M, three routed conv layers 26.4
    # M each, the routed attention layer 20 M, the head 34 M.
    assert conv + 3 * HIDDEN * 11776 == pytest.approx(89.1e6, rel=1e-3)
    assert conv + routed == pytest.approx(26.3e6, rel=5e-3)
    assert attention + routed == pytest.approx(20.1e6, rel=5e-3)
    assert weights == pytest.approx(221.9e6, rel=1e-3)
    scores = 2 * 2 * 32 * 64 * arithmetic.causal_pairs(8192)
    filters = 4 * 28 * 8192 * HIDDEN
    assert job.flops_per_unit() * 8192 == pytest.approx(
        3 * (2 * weights * 8192 + scores) + filters, rel=1e-12)
    # ~0.48 GFLOP a token forward, three times that to train, nothing run
    # again counted: 23.5 TFLOP a step of 16,384 tokens.
    assert job.flops_per_unit() / 3 == pytest.approx(0.477e9, rel=5e-3)
    assert job.flops_per_unit() * TOKENS == pytest.approx(23.45e12, rel=5e-3)


def test_job_builds_the_published_layers(job, cell):
    c = job.llama
    assert job.has_aux is True
    assert c.layer_types == tuple(HERE_TYPES) and c.num_layers == 5
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
            c.rope_theta, c.qk_norm, c.qk_norm_over, c.rms_eps) == (
                2048, 32, 8, 64, 1e6, True, "head", 1e-5)
    assert (c.conv_L_cache, c.conv_bias, c.tie_word_embeddings,
            c.first_dense_layers, c.intermediate_size) == (
                3, False, True, 1, 11776)
    assert (c.num_experts, c.experts_held, c.first_held_expert,
            c.experts_per_token, c.moe_intermediate_size, c.shared_experts,
            c.norm_topk_prob, c.routed_scaling_factor, c.balance_over,
            c.scoring_func, c.topk_method, c.mlp_hidden_act,
            c.router_bias_update_rate) == (
                64, 16, 0, 4, 1536, 0, True, 1.0, "batch", "sigmoid",
                "noaux_tc", "silu", 1e-3)
    assert [c.is_routed(i) for i in range(5)] == [False] + [True] * 4
    assert [c.is_conv(i) for i in range(5)] == [
        kind == "conv" for kind in HERE_TYPES]
    assert c.remat == cell["config"]["training"]["remat"]
    assert job.expected_first_loss() == pytest.approx(
        np.log(16384) + 0.5 + 1e-4)
    module = manifest.load_job("lconv_moe_lm")
    with pytest.raises(ValueError, match="LFM2's layers"):
        module.build({**cell["config"], "conv_bias": True},
                     cell["traffic"], 1)
    with pytest.raises(ValueError, match="LFM2's layers"):
        module.build({**cell["config"], "layer_types": HERE_TYPES[:4]},
                     cell["traffic"], 1)
    with pytest.raises(ValueError, match="master AdamW"):
        module.build({**cell["config"], "training": {
            **cell["config"]["training"], "optimizer": "sgd"}},
            cell["traffic"], 1)


# -- the readers of the new scopes ---------------------------------------------

STEP = "jit(hvd_train_step)/hvd.loss/"
FWD = STEP + "jvp(LlamaModel)/layer_2/hvd.block.attn/conv/"
REC = (STEP + "transpose(jvp(LlamaModel))/hvd.loss/jvp(LlamaModel)/"
       "checkpoint/rematted_computation/layer_2/hvd.block.attn/conv/")
BWD = STEP + "transpose(jvp(LlamaModel))/layer_2/hvd.block.attn/conv/"
FUSION = "%fusion.3 = bf16[2,8192,6144]{2,1,0} fusion(%a), kind=kLoop"
CALL = ('%short_conv.1 = bf16[2,8192,2048]{2,1,0} custom-call(%fusion.2), '
        'custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("op_name, kind", [
    (FWD + "hvd.lconv.proj/in_proj/dot_general", "proj"),
    (BWD + "hvd.lconv.proj/out_proj/dot_general", "proj"),
    (FWD + "hvd.lconv.conv/jit(_forward)/pallas_call", "conv"),
    (REC + "hvd.lconv.conv/jit(_forward)/pallas_call", "conv"),
    (BWD + "hvd.lconv.conv/jit(_backward)/reduce_sum", "conv"),
    (FWD + "hvd.lconv.conv/checkpoint/mul", "conv"),
    (FWD + "norm_attn/mul", None),
    (STEP + "jvp(LlamaModel)/layer_0/hvd.block.attn/mamba/hvd.ssd.conv/mul",
     None),
    (STEP + "jvp(LlamaModel)/layer_1/hvd.block.attn/attn/wq/dot_general",
     None),
])
def test_classify_by_the_new_scopes(op_name, kind):
    assert lconv_scopes.classify(op_name, names) == kind


def test_readers_on_hand_built_events(monkeypatch):
    step = "jit_hvd_train_step(1)"
    ops = [((FUSION, FWD + "hvd.lconv.proj/in_proj/dot_general"), 0.0, 2e-3),
           ((CALL, FWD + "hvd.lconv.conv/jit(_forward)/pallas_call"),
            2e-3, 3e-3),
           ((FUSION, FWD + "norm_attn/mul"), 3e-3, 4e-3),
           ((CALL, REC + "hvd.lconv.conv/jit(_forward)/pallas_call"),
            4e-3, 5e-3),
           ((CALL, BWD + "hvd.lconv.conv/jit(_backward)/pallas_call"),
            5e-3, 7e-3),
           ((FUSION, BWD + "hvd.lconv.proj/out_proj/dot_general"),
            7e-3, 10e-3)]
    events = {"devices": {0: {
        "ops": ops + [((n, o), a + 10e-3, b + 10e-3) for (n, o), a, b in ops],
        "modules": [(step, 0.0, 10e-3), (step, 10e-3, 20e-3)]}}}
    reduced = lconv_scopes.partition(events, names)
    assert reduced == pytest.approx({"proj": 5.0, "conv": 4.0,
                                     "conv_recomputed": 1.0})
    # A stack without a conv layer never enters the scopes.
    assert lconv_scopes.partition(
        {"devices": {0: {"ops": ops[2:3], "modules": [(step, 0.0, 10e-3)]}}},
        names) is None
    monkeypatch.setattr(lconv_scopes.scopes, "read_events",
                        lambda path: events)
    monkeypatch.setattr(lconv_scopes.trace, "find_xplane",
                        lambda trace_dir: __file__)
    lconv_scopes._reduce_file.cache_clear()
    work = {"lconv_conv": {"flops": 1e9, "bytes": 819e9 * 1e-3}}
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": work}}
    read = {name: manifest.load_reader(name)(ctx) for name in METRICS}
    assert read == pytest.approx({
        "lconv_proj_ms": 5.0, "lconv_conv_ms": 4.0,
        "lconv_conv_roofline": 25.0})
    for name in METRICS:
        assert manifest.load_reader(name)({**ctx, "trace": None}) is None
    assert manifest.load_reader("lconv_conv_roofline")(
        {**ctx, "job": {"kernel_work_per_step": {}}}) is None
    # A program without the scopes (the parent) gives no number, and does
    # not raise.
    for program in (argparse.Namespace(LOSS="hvd.loss",
                                       SSD_CONV="hvd.ssd.conv"), None):
        monkeypatch.setattr(lconv_scopes.scopes, "program_scopes",
                            lambda program=program: program)
        lconv_scopes._reduce_file.cache_clear()
        assert all(manifest.load_reader(name)(ctx) is None
                   for name in METRICS)
    lconv_scopes._reduce_file.cache_clear()


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
    assert {names.LOSS, names.LCONV_PROJ, names.LCONV_CONV, names.BLOCK_ATTN,
            names.BLOCK_FFN, names.HEAD, names.FLASH_FWD, names.FLASH_BWD,
            names.QK_NORM, names.MOE_ROUTE, names.REMATTED} <= held
    # The two are in the conv layers (0, 2, 3 and 4) alone, inside the
    # mixer's block.
    ours = [op_name for (_, op_name), _, _ in ops
            if lconv_scopes.classify(op_name, names)]
    assert {op.split("/layer_")[1][0] for op in ours} == {"0", "2", "3", "4"}
    assert all(names.BLOCK_ATTN in op and "/conv/" in op for op in ours)
    # The gated filter ran as Mosaic calls, under its scope: forward, run
    # again and backward.
    mosaic = [op_name for (text, op_name), _, _ in ops
              if scopes.trace.op_kind(text) == "mosaic"
              and not op_name.startswith(names.RAGGED_DOT_PREFIX)]
    filters = [op for op in mosaic if names.LCONV_CONV in op]
    assert filters and any(names.REMATTED in op for op in filters)
    assert any("transpose(" in op and names.REMATTED not in op
               for op in filters)
    assert all(names.LCONV_CONV in op or names.FLASH_FWD in op
               or names.FLASH_BWD in op for op in mosaic)
    assert os.path.getsize(RECORDED) < 700_000


def test_recorded_step_by_the_scopes_the_cell_reports(recorded, monkeypatch):
    events = scopes.read_events(recorded)
    mixer = lconv_scopes.partition(events, names)
    routed = moe_scopes.partition(events, names)
    assert mixer["proj"] > 0 and mixer["conv"] > 0
    assert 0 < mixer["conv_recomputed"] < mixer["conv"]
    assert all(routed[kind] > 0 for kind in ("route", "experts"))
    by_class = scopes.partition(events, names)
    assert by_class["flash"]["fwd"] > 0 and by_class["flash"]["bwd"] > 0
    assert mixer["proj"] + mixer["conv"] < (
        by_class["classes"]["forward"] + by_class["classes"]["backward"])
    monkeypatch.setattr(lconv_scopes.trace, "find_xplane",
                        lambda trace_dir: recorded)
    lconv_scopes._reduce_file.cache_clear()
    work = {"lconv_conv": {"flops": 1.0, "bytes": 819e9 * 1e-6}}
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": work}}
    for kind in ("proj", "conv"):
        assert manifest.load_reader(f"lconv_{kind}_ms")(ctx) == pytest.approx(
            mixer[kind])
    assert manifest.load_reader("lconv_conv_roofline")(ctx) == pytest.approx(
        100.0 * 1e-3 / mixer["conv"])
    lconv_scopes._reduce_file.cache_clear()
