"""The ``granite-4.0-h-micro`` configuration and its cell: the manifest's new
entries, the configuration's file against the catalog's row, the parameter
table from the built model's leaves, the job and
``benchmark/arithmetic_ssm_dense.py`` against hand counts, the two new readers
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

from benchmark import (arithmetic, arithmetic_ssd, arithmetic_ssm_dense,
                       manifest, scopes, ssd_dense_scopes, ssd_scopes)
from horovod_tpu.common import scopes as names

CELL = "granite-4.0-h-micro.train-s8k"
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
          "config.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = {"ssd_proj_ms": ("model", "program_span", "ms", "lower"),
           "ssd_gates_roofline": ("kernels", "device_trace", "%", "higher")}
JOINED = ("tokens_per_s_per_chip", "mfu", "flash_ms", "flash_roofline",
          "flash_fwd_ms", "flash_bwd_ms", "flash_fwd_roofline",
          "flash_bwd_roofline", "block_attn_ms", "block_ffn_ms", "head_ms",
          "ssd_conv_ms", "ssd_gates_ms", "ssd_scan_ms", "ssd_scan_roofline",
          "import_hvd_ms", "init_ms", "init_native_ms", "trace_attn_ms",
          "trace_ffn_ms", "trace_head_ms", "trace_optimizer_ms",
          "trace_kernels_ms", "trace_kernel_calls", "trace_loss_self_ms")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
REDUCED = {"num_hidden_layers": (10, 40), "layer_types": (PERIOD, None),
           "vocab_size": (12544, 100352)}
# Hidden 512; three layers (mamba, attention, mamba), each with a SwiGLU of
# 1024: Mamba-2 mixers of 16 heads of 64 with a state of 64 in ONE group of
# 1024 lanes (so the gates take the WIDE Mosaic pass, two pieces of 512) in
# chunks of 64, 8 query heads over 2 key-value heads of 64 at the published
# multipliers, a tied head over 1,024 ids, 1 x 512 tokens,
# ``layer_keep_attention``: traced on one TPU v5e chip by this harness
# (PR 60), cut by ``benchmark.xspace.trim`` to its first three steps and to
# the lines the reductions read; gzipped.  Named ``.xspace.gz`` as PERF.md's
# Open question 23 says.
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-ssm-dense-decoder-v5e.xspace.gz")
TOKENS = 8192
HIDDEN = 2048


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(CELL)


@pytest.fixture(scope="module")
def job(cell):
    return manifest.load_job("ssm_lm").build(cell["config"], cell["traffic"],
                                             1)


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
    assert config["reduced"] == list(REDUCED)
    for key, (here, there) in REDUCED.items():
        assert config[key] == here, key
        assert there is None or published[key] == there, key
    # Layers 0 to 9 of the published forty, in their order: one period.
    assert published["layer_types"] == PERIOD * 4
    assert config["deployment"]["layer_types_published"] == (
        published["layer_types"])
    # Every width and all four multipliers as published.
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["shared_intermediate_size"],
            config["intermediate_size"], config["mamba_n_heads"],
            config["mamba_d_head"], config["mamba_d_state"],
            config["mamba_n_groups"], config["mamba_d_conv"],
            config["mamba_chunk_size"], config["mamba_expand"],
            config["rms_norm_eps"], config["max_position_embeddings"],
            config["rope_theta"]) == (
                2048, 32, 8, 8192, 8192, 64, 64, 128, 1, 4, 256, 2, 1e-5,
                131072, 10000)
    assert (config["embedding_multiplier"], config["attention_multiplier"],
            config["residual_multiplier"], config["logits_scaling"]) == (
                12, 0.015625, 0.22, 8)
    assert config["tie_word_embeddings"] and config["mamba_conv_bias"]
    assert config["position_embedding_type"] == "nope"
    assert set(config["reduced_why"]) == set(REDUCED)
    deployment = config["deployment"]
    assert (deployment["chips_sharing_a_layer"],
            deployment["vocab_size_published"],
            deployment["num_hidden_layers_published"]) == (8, 100352, 40)
    assumed = config["assumed"]
    assert {"head_dim_why", "mamba_layer", "d_inner", "mlp",
            "multipliers", "no_position", "chunk", "initialisation",
            "training"} <= set(assumed)
    assert (config["head_dim"], assumed["d_inner"]) == (64, 4096)
    assert set(config) - set(published) - {"head_dim"} == {
        "source", "job", "reference", "reduced", "reduced_why",
        "deployment", "assumed", "training", "checks"}
    assert "2405.21060" in assumed["mamba_layer"]
    assert config["training"]["remat"] in ("layer", "layer_keep_attention")
    checks = config["checks"]
    assert checks["first_loss_is_ln_vocab_plus"] == 1 / 128
    limits = checks["reference"]
    assert limits["parameters"] == "initial" and len(limits["why"]) > 500


def test_the_manifests_new_entries(cell):
    listed = manifest.load()
    entry = next(c for c in listed["configs"]
                 if c["name"] == "granite-4.0-h-micro")
    assert entry["source"] == cell["config"]["source"] == SOURCE
    assert entry["reduced"] == cell["config"]["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/granite-4.0-h-micro.json"
    assert len(entry["why"]) <= 200
    # (By name, not by place: a later PR's entries come behind these.)
    workload = next(w for w in listed["workloads"] if w["name"] == CELL)
    assert workload == {"name": CELL, "config": "granite-4.0-h-micro",
                        "traffic": "train-s8k", "chips": 1,
                        "why": workload["why"]}
    assert "9 of 10 layers" in workload["why"]
    assert len(workload["why"]) <= 200
    assert cell["traffic"] == manifest.cell("ouro-2.6b.train-s8k")["traffic"]
    assert len(listed["configs"]) >= 12 and len(listed["workloads"]) >= 14
    assert sum(w["chips"] == 4 for w in listed["workloads"]) == 1
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "step_ms_p90", "peak_hbm_gb", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(METRICS) | set(JOINED[1:]) <= reported
    # Readers that find nothing to read in this cell: nothing is routed, no
    # head is normed, no window; and the two that cannot take it without an
    # edit (PERF.md's Open questions).
    assert not {"moe_route_ms", "moe_experts_ms", "moe_shared_ms",
                "qk_norm_ms", "window_attn_ms", "recompute_ms",
                "dense_roofline", "gdn_scan_ms", "sscan_scan_ms",
                "lconv_conv_ms"} & reported
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
    path = manifest.load_reference("granite_hybrid").__file__
    with open(path) as f:
        source = f.read()
    code = source[source.index('"""', 3) + 3:]          # behind the docstring
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp"]
    assert "horovod_tpu" not in code
    assert 'default_matmul_precision("highest")' in source
    module = manifest.load_reference("granite_hybrid")
    assert (module.TOKENS, module.QUERIES, module.ROWS) == (256, 128, 1024)


# -- the parameter table, the job and its arithmetic ---------------------------

def test_the_parameter_table(job):
    """ISSUE 60's table, matrix by matrix, and the program's own count from
    the built model's leaves."""
    w_in, w_out = HIDDEN * (4096 + 4352 + 64), 4096 * HIDDEN
    mixer = w_in + w_out + 4 * 4352 + 4352 + 3 * 64 + 4096
    mlp = 3 * HIDDEN * 8192
    attention = 2 * HIDDEN * HIDDEN + 2 * HIDDEN * 512
    assert (w_in, w_out, mixer, mlp, attention) == (
        17_432_576, 8_388_608, 25_847_232, 50_331_648, 10_485_760)
    mamba_layer = mixer + mlp + 2 * HIDDEN
    attention_layer = attention + mlp + 2 * HIDDEN
    assert (mamba_layer, attention_layer) == (76_182_976, 60_821_504)
    table = 9 * mamba_layer + attention_layer + 12544 * HIDDEN + HIDDEN
    params, _ = jax.eval_shape(job.init_state, jax.random.key(0))
    assert set(params) == {"params"}
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(params))
    assert count == table == 772_160_448
    assert count * 14 == pytest.approx(10.81e9, rel=1e-3)
    tree = params["params"]
    assert "lm_head" not in tree                    # the head is tied
    assert tree["tok_emb"]["embedding"].shape == (12544, HIDDEN)
    mamba = tree["layer_0"]["mamba"]
    assert mamba["in_proj"]["kernel"].shape == (HIDDEN, 8512)
    assert mamba["out_proj"]["kernel"].shape == (4096, HIDDEN)
    assert mamba["conv_w"].shape == (4, 4352)
    assert mamba["conv_b"].shape == (4352,)
    assert mamba["norm"].shape == (4096,)
    assert {mamba[n].shape for n in ("a_log", "d", "dt_bias")} == {(64,)}
    assert tree["layer_5"]["attn"]["wq"]["kernel"].shape == (HIDDEN, HIDDEN)
    assert tree["layer_5"]["attn"]["wk"]["kernel"].shape == (HIDDEN, 512)
    assert tree["layer_5"]["mlp"]["w_gate_up"]["kernel"].shape == (
        HIDDEN, 16384)
    assert all(sorted(tree[f"layer_{i}"]) == sorted(
        ("attn" if kind == "attention" else "mamba", "mlp", "norm_attn",
         "norm_mlp")) for i, kind in enumerate(PERIOD))


def test_the_gates_bytes_and_operations_by_hand():
    sizes = dict(batch=1, seq=TOKENS, channels=4096)
    tensor = TOKENS * 4096 * 2
    assert arithmetic_ssm_dense.gates_bytes(**sizes) == {
        "forward": 4 * tensor, "backward": 7 * tensor}
    assert 4 * tensor == 268_435_456 and 7 * tensor == 469_762_048
    assert arithmetic_ssm_dense.gates_flops(**sizes) == (
        TOKENS * 4096 * (11 + 28))
    assert arithmetic_ssm_dense.gates_bytes(batch=2, seq=4, channels=3,
                                            itemsize=4)["forward"] == 384


def test_kernel_work_of_the_cell(job):
    work = job.kernel_work_per_step()
    assert set(work) == {"flash", "ssd_scan", "ssd_gates"}
    # Seven products a kept pair at 32 heads of 64, ONE layer of ten.
    causal = 7 * 2 * 64 * 32 * arithmetic.causal_pairs(8192)
    assert work["flash"]["flops"] == causal
    assert work["flash"]["forward"]["flops"] * 7 == work["flash"]["flops"] * 2
    tensor = 8192 * 64 * 2
    assert work["flash"]["bytes"] == 6 * (32 + 8) * tensor
    # The scan: 32 chunks of 256 a sequence, C B^T once for all 64 heads,
    # nine layers.
    macs = arithmetic_ssd.chunk_scan_macs(head_dim=64, state=128,
                                          heads_a_group=64, chunk=256)
    assert macs == 256 * 256 * 128 / 64 + 256 * 256 * 64 + 2 * 256 * 64 * 128
    assert work["ssd_scan"]["flops"] == 9 * (3 * 2 * 64 * 32 * macs)
    assert work["ssd_scan"]["flops"] == pytest.approx(0.942e12, rel=1e-3)
    u = TOKENS * 64 * 64 * 2
    bc = TOKENS * 1 * 2 * 128 * 2
    dt = TOKENS * 64 * 4
    states = 64 * 32 * 64 * 128 * 4
    a_layer = 2 * (2 * u + bc + dt + states) + u + bc + dt
    assert work["ssd_scan"]["bytes"] == 9 * a_layer
    peaks = manifest.peaks("TPU v5 lite")
    least, bound = arithmetic.roofline_seconds(
        work["ssd_scan"]["flops"], work["ssd_scan"]["bytes"], peaks)
    assert bound == "bytes" and least == pytest.approx(5.37e-3, rel=2e-3)
    # The gates: eleven tensors of 8192 x 4096 in bf16 a layer.
    assert work["ssd_gates"]["bytes"] == 9 * 11 * TOKENS * 4096 * 2
    least, bound = arithmetic.roofline_seconds(
        work["ssd_gates"]["flops"], work["ssd_gates"]["bytes"], peaks)
    assert bound == "bytes" and least == pytest.approx(8.11e-3, rel=2e-3)


def test_flops_of_the_ten_layers_by_hand(job):
    mamba = HIDDEN * 8512 + 4096 * HIDDEN + 3 * HIDDEN * 8192
    attention = HIDDEN * 64 * (2 * 32 + 2 * 8) + 3 * HIDDEN * 8192
    assert arithmetic_ssm_dense.layer_matmul_params(
        hidden=HIDDEN, heads=32, kv_heads=8, head_dim=64, mamba_heads=64,
        mamba_head_dim=64, groups=1, state=128, ffn=8192) == {
            "mamba": mamba, "attention": attention}
    weights = 9 * mamba + attention + HIDDEN * 12544
    assert weights == 771_883_008
    scores = 2 * 2 * 32 * 64 * arithmetic.causal_pairs(8192)
    macs = arithmetic_ssd.chunk_scan_macs(head_dim=64, state=128,
                                          heads_a_group=64, chunk=256)
    scan = 9 * 3 * 2 * 64 * 32 * macs
    assert job.flops_per_unit() * TOKENS == pytest.approx(
        3 * (2 * weights * TOKENS + scores) + scan, rel=1e-12)
    assert job.flops_per_unit() == pytest.approx(4.85e9, rel=2e-3)
    assert job.flops_per_unit() * TOKENS == pytest.approx(39.7e12, rel=2e-3)


def test_job_builds_the_published_layers(job, cell):
    c = job.llama
    assert job.has_aux is False
    assert c.layer_types == tuple(PERIOD) and c.num_layers == 10
    assert c.hybrid_override_pattern is None
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
            c.rope_theta, c.intermediate_size, c.vocab_size,
            c.tie_word_embeddings) == (2048, 32, 8, 64, None, 8192, 12544,
                                       True)
    assert (c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size, c.n_groups,
            c.conv_kernel, c.chunk_size, c.mamba_inner) == (
                64, 64, 128, 1, 4, 256, 4096)
    assert (c.embedding_multiplier, c.attention_multiplier,
            c.residual_multiplier, c.logits_scaling) == (12, 1 / 64, 0.22, 8)
    assert c.num_experts == 1 and not any(map(c.is_routed, range(10)))
    assert all(c.rope_of(i) is None for i in range(10))
    assert c.remat == cell["config"]["training"]["remat"]
    assert job.expected_first_loss() == pytest.approx(
        np.log(12544) + 1 / 128)
    module = manifest.load_job("ssm_lm")
    for wrong in ({"mamba_conv_bias": False}, {"tie_word_embeddings": False},
                  {"position_embedding_type": "rope"},
                  {"num_local_experts": 8}, {"mamba_expand": 3},
                  {"layer_types": PERIOD[:9]},
                  {"layer_types": PERIOD[:9] + ["full_attention"]}):
        with pytest.raises(ValueError, match="Granite-4.0-H's dense layers"):
            module.build({**cell["config"], **wrong}, cell["traffic"], 1)
    with pytest.raises(ValueError, match="master AdamW"):
        module.build({**cell["config"], "training": {
            **cell["config"]["training"], "optimizer": "sgd"}},
            cell["traffic"], 1)


# -- the readers of the new scope ------------------------------------------------

STEP = "jit(hvd_train_step)/hvd.loss/"
FWD = STEP + "jvp(LlamaModel)/layer_2/hvd.block.attn/mamba/"
REC = (STEP + "transpose(jvp(LlamaModel))/hvd.loss/jvp(LlamaModel)/"
       "checkpoint/rematted_computation/layer_2/hvd.block.attn/mamba/")
BWD = STEP + "transpose(jvp(LlamaModel))/layer_2/hvd.block.attn/mamba/"
FUSION = "%fusion.3 = bf16[1,8192,8512]{2,1,0} fusion(%a), kind=kLoop"


@pytest.mark.parametrize("op_name, ours", [
    (FWD + "hvd.ssd.proj/in_proj/dot_general", True),
    (REC + "hvd.ssd.proj/in_proj/dot_general", True),
    (BWD + "hvd.ssd.proj/out_proj/transpose", True),
    (BWD + "transpose(jvp(hvd.ssd.proj))/in_proj/dot_general", True),
    (FWD + "hvd.ssd.gates/softplus", False),
    (FWD + "hvd.ssd.scan/while/body/dot_general", False),
    (STEP + "jvp(LlamaModel)/layer_5/hvd.block.attn/attn/wq/dot_general",
     False),
    (STEP + "jvp(LlamaModel)/layer_2/hvd.block.ffn/mlp/w_down/dot_general",
     False),
])
def test_classify_by_the_new_scope(op_name, ours):
    assert ssd_dense_scopes.is_projection(op_name, names) is ours


def test_readers_on_hand_built_events(monkeypatch):
    step = "jit_hvd_train_step(1)"
    ops = [((FUSION, FWD + "hvd.ssd.proj/in_proj/dot_general"), 0.0, 1e-3),
           ((FUSION, FWD + "hvd.ssd.scan/dot_general"), 1e-3, 4e-3),
           ((FUSION, FWD + "hvd.ssd.gates/mul"), 4e-3, 5e-3),
           ((FUSION, REC + "hvd.ssd.proj/in_proj/dot_general"), 5e-3, 6e-3),
           ((FUSION, BWD + "hvd.ssd.gates/mul"), 6e-3, 8e-3),
           ((FUSION, BWD + "hvd.ssd.proj/out_proj/dot_general"), 8e-3, 10e-3)]
    events = {"devices": {0: {
        "ops": ops + [((n, o), a + 10e-3, b + 10e-3) for (n, o), a, b in ops],
        "modules": [(step, 0.0, 10e-3), (step, 10e-3, 20e-3)]}}}
    assert ssd_dense_scopes.projections_ms(events, names) == pytest.approx(
        4.0)
    # A stack without a Mamba layer never enters the scope.
    assert ssd_dense_scopes.projections_ms(
        {"devices": {0: {"ops": ops[1:3], "modules": [(step, 0.0, 10e-3)]}}},
        names) is None
    for module in (ssd_dense_scopes, ssd_scopes):
        monkeypatch.setattr(module.scopes, "read_events",
                            lambda path: events)
        monkeypatch.setattr(module.trace, "find_xplane",
                            lambda trace_dir: __file__)
        module._reduce_file.cache_clear()
    work = {"ssd_gates": {"flops": 1e9, "bytes": 819e9 * 1.5e-3}}
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": work}}
    read = {name: manifest.load_reader(name)(ctx) for name in METRICS}
    assert read == pytest.approx({"ssd_proj_ms": 4.0,
                                  "ssd_gates_roofline": 50.0})
    for name in METRICS:
        assert manifest.load_reader(name)({**ctx, "trace": None}) is None
    # A job that states no such work (the Nemotron cell's) gives no share.
    assert manifest.load_reader("ssd_gates_roofline")(
        {**ctx, "job": {"kernel_work_per_step": {}}}) is None
    # A program without the scope (the parent) gives no time, and raises
    # nothing.
    for program in (argparse.Namespace(LOSS="hvd.loss",
                                       SSD_SCAN="hvd.ssd.scan"), None):
        monkeypatch.setattr(ssd_dense_scopes.scopes, "program_scopes",
                            lambda program=program: program)
        ssd_dense_scopes._reduce_file.cache_clear()
        assert manifest.load_reader("ssd_proj_ms")(ctx) is None
    for module in (ssd_dense_scopes, ssd_scopes):
        module._reduce_file.cache_clear()


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
    assert {names.LOSS, names.SSD_CONV, names.SSD_GATES, names.SSD_SCAN,
            names.SSD_PROJ, names.BLOCK_ATTN, names.BLOCK_FFN, names.HEAD,
            names.FLASH_FWD, names.FLASH_BWD, names.REMATTED} <= held
    # The projections are in the Mamba layers (0 and 2 of mamba, attention,
    # mamba) alone, inside the mixer's block, and they are matmuls.
    ours = [op_name for (_, op_name), _, _ in ops
            if ssd_dense_scopes.is_projection(op_name, names)]
    assert {op.split("/layer_")[1][0] for op in ours} == {"0", "2"}
    assert all(names.BLOCK_ATTN in op and "/mamba/" in op for op in ours)
    assert all("in_proj" in op or "out_proj" in op for op in ours)
    assert not any(ssd_scopes.classify(op, names) for op in ours)
    # Every layer is BOTH blocks: a mixer and a SwiGLU.
    blocks = {}
    for (_, op_name), _, _ in ops:
        if "/layer_" in op_name:
            blocks.setdefault(op_name.split("/layer_")[1][0], set()).update(
                block for block in (names.BLOCK_ATTN, names.BLOCK_FFN)
                if block in op_name)
    assert blocks == {layer: {names.BLOCK_ATTN, names.BLOCK_FFN}
                      for layer in "012"}
    # The Mosaic calls: the flash pair, the filter's and the gates' (the
    # wide pass: one norm group of 1024 lanes); the scan is XLA's.
    mosaic = {scopes.bare(part) for (text, op_name), _, _ in ops
              if scopes.trace.op_kind(text) == "mosaic"
              for part in scopes.components(op_name)}
    assert {names.FLASH_FWD, names.FLASH_BWD, names.SSD_CONV,
            names.SSD_GATES} <= mosaic and names.SSD_SCAN not in mosaic
    assert os.path.getsize(RECORDED) < 700_000


def test_recorded_step_by_the_scopes_the_cell_reports(recorded, monkeypatch):
    events = scopes.read_events(recorded)
    mamba = ssd_scopes.partition(events, names)
    proj = ssd_dense_scopes.projections_ms(events, names)
    assert all(mamba[kind] > 0 for kind in ("conv", "gates", "scan"))
    assert 0 < mamba["scan_recomputed"] < mamba["scan"]
    by_class = scopes.partition(events, names)
    assert by_class["flash"]["fwd"] > 0 and by_class["flash"]["bwd"] > 0
    assert 0 < proj and proj + sum(
        mamba[k] for k in ("conv", "gates", "scan")) < (
            by_class["classes"]["forward"] + by_class["classes"]["backward"])
    for module in (ssd_dense_scopes, ssd_scopes):
        monkeypatch.setattr(module.trace, "find_xplane",
                            lambda trace_dir: recorded)
        module._reduce_file.cache_clear()
    work = {"ssd_gates": {"flops": 1.0, "bytes": 819e9 * 1e-6}}
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": work}}
    assert manifest.load_reader("ssd_proj_ms")(ctx) == pytest.approx(proj)
    assert manifest.load_reader("ssd_gates_roofline")(ctx) == pytest.approx(
        100.0 * 1e-3 / mamba["gates"])
    for module in (ssd_dense_scopes, ssd_scopes):
        module._reduce_file.cache_clear()
