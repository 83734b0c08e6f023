"""The ``phi-4-mini-flash`` configuration and its cell: the manifest's new
entries, the configuration's file against the catalog's row, the parameter
table, the job and ``benchmark/arithmetic_sambay.py`` against brute-force and
hand counts, the six new readers on hand-built events and on a tiny step
traced on a v5e.  (``test_cell_traced_tiny`` traces the manifest's first and
last cells, so this cell's traced tiny run is there.)"""

import argparse
import gzip
import json
import os

import jax
import numpy as np
import pytest

from benchmark import (arithmetic, arithmetic_sambay, arithmetic_window,
                       manifest, sambay_scopes, scopes, window_scopes)
from horovod_tpu.common import scopes as names

CELL = "phi-4-mini-flash.train-s8k"
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
          "blob/main/config.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = {"sscan_conv_ms": ("model", "program_span", "ms", "lower"),
           "sscan_gates_ms": ("model", "program_span", "ms", "lower"),
           "sscan_scan_ms": ("kernels", "program_span", "ms", "lower"),
           "sscan_scan_roofline": ("kernels", "device_trace", "%", "higher"),
           "gmu_ms": ("model", "program_span", "ms", "lower"),
           "diff_attn_ms": ("model", "program_span", "ms", "lower")}
JOINED = ("tokens_per_s_per_chip", "mfu", "flash_ms", "flash_roofline",
          "flash_fwd_ms", "flash_bwd_ms", "flash_fwd_roofline",
          "flash_bwd_roofline", "window_attn_ms", "window_attn_roofline",
          "block_attn_ms", "block_ffn_ms", "head_ms", "import_hvd_ms",
          "init_ms", "init_native_ms", "trace_attn_ms", "trace_ffn_ms",
          "trace_head_ms", "trace_optimizer_ms", "trace_kernels_ms",
          "trace_kernel_calls", "trace_loss_self_ms")
REDUCED = {"num_hidden_layers": (8, 32), "vocab_size": (25008, 200064)}
# Hidden 128; the eight layers of the placement rule: scans over 256 channels
# of 16 state entries (so they take the Mosaic pair), differential attention
# of 4 query heads over 2 key-value heads of 64 under a window of 128, 1 x 512
# tokens, ``remat="layer"``: traced on one TPU v5e chip by this harness (PR
# 54), cut by ``benchmark.xspace.trim`` to its first three steps and to the
# lines the reductions read; gzipped.  Named ``.xspace.gz`` as PERF.md's Open
# question 23 says.
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-sambay-decoder-v5e.xspace.gz")
TOKENS = 8192


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(CELL)


@pytest.fixture(scope="module")
def job(cell):
    return manifest.load_job("sambay_lm").build(cell["config"],
                                                cell["traffic"], 1)


# -- the manifest and the configuration's file --------------------------------

def test_the_configuration_is_the_catalogs_row_but_for_its_two_cuts(cell):
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
        assert (config[key], published[key]) == (here, there), key
    # Every width as published, and the Mamba defaults under ``assumed``.
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["sliding_window"],
            config["mb_per_layer"], config["layer_norm_eps"],
            config["max_position_embeddings"]) == (
                2560, 40, 20, 64, 10240, 512, 2, 1e-5, 262144)
    assert config["tie_word_embeddings"] is True
    assumed = config["assumed"]
    assert (assumed["d_state"], assumed["d_conv"], assumed["expand"],
            assumed["dt_rank"]) == (16, 4, 2, 160)
    assert set(config["reduced_why"]) == set(REDUCED)
    assert config["vocab_size"] * 8 == 200064
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 8
    assert deployment["vocab_size_published"] == 200064
    assert deployment["num_hidden_layers_published"] == 32
    assert {"mamba_sizes_why", "mamba_layer", "gated_memory_unit",
            "attention_bias", "layer_norm", "head_dim", "window",
            "differential_attention", "no_positional_encoding",
            "initialisation", "training"} <= set(assumed)
    assert "2312.00752" in assumed["mamba_sizes_why"]
    assert "2507.06607" in assumed["gated_memory_unit"]
    assert "2410.05258" in assumed["differential_attention"]
    assert config["training"]["remat"] == "layer"
    limits = config["checks"]["reference"]
    assert limits["parameters"] == "initial" and len(limits["why"]) > 500


def test_the_manifests_new_entries(cell):
    listed = manifest.load()
    entry = next(c for c in listed["configs"]
                 if c["name"] == "phi-4-mini-flash")
    assert entry["source"] == cell["config"]["source"] == SOURCE
    assert entry["reduced"] == cell["config"]["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/phi-4-mini-flash.json"
    assert len(entry["why"]) <= 200
    # (By name, not by place: a later PR's entries come behind these.)
    workload = next(w for w in listed["workloads"] if w["name"] == CELL)
    assert workload == {"name": CELL, "config": "phi-4-mini-flash",
                        "traffic": "train-s8k", "chips": 1,
                        "why": workload["why"]}
    assert "3 : 2 : 1 : 1 : 1" in workload["why"]
    assert "9 : 8 : 1 : 7 : 7" in workload["why"]
    assert len(workload["why"]) <= 200
    assert cell["traffic"] == manifest.cell(
        "olmo-hybrid-7b.train-s8k")["traffic"]
    assert len(listed["configs"]) >= 10 and len(listed["workloads"]) >= 12
    assert sum(w["chips"] == 4 for w in listed["workloads"]) <= (
        len(listed["workloads"]) // 4)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "step_ms_p90", "peak_hbm_gb", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(METRICS) | set(JOINED[1:]) <= reported
    # Readers that find nothing to read in this cell.
    assert not {"recompute_ms", "dense_roofline", "mla_latent_ms",
                "sparse_index_ms", "gdn_scan_ms", "ssd_scan_ms",
                "attn_gate_ms", "qk_norm_ms", "moe_route_ms"} & reported
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


# -- the parameter table, the job and its arithmetic ---------------------------

def test_the_parameter_table(job):
    """ISSUE 54's table, mixer by mixer, and the program's own count."""
    from horovod_tpu.models import LlamaModel

    shapes = jax.eval_shape(
        lambda k: LlamaModel(job.llama).init(
            k, np.zeros((1, 8), np.int32)), jax.random.key(0))["params"]

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    swiglu = 2560 * 20480 + 10240 * 2560
    mamba = (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 + 5120 * 2560
             + 5120 * 16 + 4 * 5120 + 5120 + 5120)
    attention = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    unit = 2 * 2560 * 5120
    cross = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
    assert (swiglu, mamba, attention, unit, cross) == (
        78_643_200, 41_241_600, 19_668_864, 26_214_400, 13_112_704)
    mixers = [("mamba", mamba), ("attn", attention), ("mamba", mamba),
              ("attn", attention), ("mamba", mamba), ("attn", attention),
              ("gmu", unit), ("attn", cross)]
    for i, (name, size) in enumerate(mixers):
        layer = shapes[f"layer_{i}"]
        assert sorted(layer) == sorted([name, "mlp", "norm_attn",
                                        "norm_mlp"])
        assert count(layer[name]) == size and count(layer["mlp"]) == swiglu
        assert count(layer["norm_attn"]) == count(layer["norm_mlp"]) == 5120
    assert "wq" in shapes["layer_7"]["attn"]
    assert "wqkv" in shapes["layer_5"]["attn"]
    assert "lm_head" not in shapes                  # the head is tied
    assert count(shapes["tok_emb"]) == 25008 * 2560
    total = count(shapes)
    assert total == 915_311_616                     # 12.81 GB at 14 bytes
    # The published stack by the same leaves: the card's 3.8B.
    published = (32 * (swiglu + 10240) + 9 * mamba + 9 * attention
                 + 7 * unit + 7 * cross + 200064 * 2560 + 5120)
    assert 3.84e9 < published < 3.86e9


def _brute_force_scan_macs(seq, channels, states):
    macs = 0
    for _ in range(seq):
        for _ in range(channels):
            macs += 3 * states      # the state decayed, written and read
    return macs


def test_arithmetic_against_a_brute_force_count_at_a_tiny_size():
    shape = dict(batch=2, seq=24, channels=6, state=4)
    assert arithmetic_sambay.scan_flops(**shape) == (
        3 * 2 * 2 * _brute_force_scan_macs(24, 6, 4))
    tokens = 2 * 24
    assert arithmetic_sambay.scan_bytes(**shape) == (
        # forward: u, B, C, the step read; y and one block's states written
        tokens * 6 * 2 + tokens * 8 * 2 + tokens * 6 * 4 + tokens * 6 * 2
        + 2 * 6 * 4 * 4
        # backward: those and y's cotangent read, four gradients written
        + tokens * 6 * 2 * 2 + tokens * 8 * 2 + tokens * 6 * 4 + 2 * 6 * 4 * 4
        + tokens * 6 * 2 + tokens * 8 * 2 + tokens * 6 * 4)
    # Two maps a pair: the pairs a head keeps, times q k^T at D and p v at 2 D.
    kept = sum(1 for t in range(24) for s in range(24) if 0 <= t - s < 5)
    assert arithmetic_window.band_pairs(24, 5) == kept
    work = arithmetic_sambay.attention_work(
        batch=2, seq=24, heads=4, kv_heads=2, head_dim=8, window=5)
    assert work["forward"]["flops"] == 2 * 4 * kept * 2 * (8 + 16)
    assert work["backward"]["flops"] == 2 * 4 * kept * 2 * (3 * 8 + 2 * 16)
    assert work["forward"]["bytes"] == 2 * 24 * 8 * 2 * (3 * 4 + 3 * 2)
    assert work["backward"]["bytes"] == 2 * work["forward"]["bytes"]
    assert work["flops"] == (work["forward"]["flops"]
                             + work["backward"]["flops"])


def test_kernel_work_of_the_cell(job):
    work = job.kernel_work_per_step()
    assert set(work) == {"flash", "window_attn", "sscan"}
    full = arithmetic.causal_pairs(TOKENS)
    band = arithmetic_window.band_pairs(TOKENS, 512)
    assert band == 8192 * 512 - 512 * 511 // 2
    # Two windowed layers and two full ones (layer 5 and the cross layer 7),
    # 40 maps a layer, 64 + 128 multiply-adds a kept pair forward.
    assert work["flash"]["forward"]["flops"] == (
        2 * 40 * 192 * 2 * (band + full))
    assert work["window_attn"]["forward"]["flops"] == 2 * 40 * 192 * 2 * band
    assert work["flash"]["flops"] == pytest.approx(
        2 * 40 * 2 * (band + full) * (192 + 448))
    assert work["sscan"]["flops"] == 3 * (3 * 2 * 3 * TOKENS * 5120 * 16)
    assert work["sscan"]["bytes"] == 3 * arithmetic_sambay.scan_bytes(
        batch=1, seq=TOKENS, channels=5120, state=16)
    # The scan's work is the vector unit's: by the two peaks it is bound by
    # bytes, and its share of them cannot pass 100 %.
    peaks = manifest.peaks("TPU v5 lite")
    least_s, bound = arithmetic.roofline_seconds(
        work["sscan"]["flops"], work["sscan"]["bytes"], peaks)
    assert bound == "bytes" and 3e-3 < least_s < 4e-3


def test_flops_of_the_eight_layers_by_hand(job):
    weights = (8 * 3 * 2560 * 10240
               + 3 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
               + 2 * 2560 * 5120
               + 3 * 2560 * 64 * (2 * 40 + 2 * 20) + 2560 * 64 * 2 * 40
               + 2560 * 25008)
    band = arithmetic_window.band_pairs(TOKENS, 512)
    full = arithmetic.causal_pairs(TOKENS)
    attention = 2 * 40 * 192 * 2 * (band + full) / TOKENS
    scan = 3 * (3 * 2 * 3 * 5120 * 16)
    assert job.flops_per_unit() == pytest.approx(
        3 * (2 * weights + attention) + scan)
    # ISSUE 54's count: 16.1 TFLOP of matmul work a step forward.
    assert job.flops_per_unit() * TOKENS / 3 == pytest.approx(16.16e12,
                                                              rel=2e-3)


def test_job_builds_the_published_layers(job, cell):
    c = job.llama
    assert (c.mb_per_layer, c.sliding_window, c.tie_word_embeddings,
            c.layer_norm_eps, c.attention_kind, c.rope_theta) == (
                2, 512, True, 1e-5, "differential", None)
    assert (c.scan_inner, c.ssm_state_size, c.conv_kernel, c.dt_rank,
            c.head_dim, c.num_heads, c.num_kv_heads) == (
                5120, 16, 4, 160, 64, 40, 20)
    assert [c.mixer_of(i) for i in range(8)] == [
        "mamba", "attention", "mamba", "attention", "mamba", "attention",
        "gated_memory", "cross_attention"]
    assert [c.window_of(i) for i in (1, 3, 5, 7)] == [512, 512, None, None]
    assert c.remat == "layer" and job.has_aux is False
    assert (job.batch, job.seq, job.units_per_step) == (1, TOKENS, TOKENS)
    assert job.expected_first_loss() == pytest.approx(np.log(25008) + 0.5)
    wrong = {**cell["config"], "tie_word_embeddings": False}
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        manifest.load_job("sambay_lm").build(wrong, cell["traffic"], 1)


# -- the readers ----------------------------------------------------------------

STEP = "jit(hvd_train_step)/hvd.loss/"
FWD = STEP + "jvp(LlamaModel)/layer_4/hvd.block.attn/mamba/"
REC = (STEP + "transpose(jvp(LlamaModel))/checkpoint/rematted_computation/"
       "layer_4/hvd.block.attn/mamba/")
BWD = STEP + "transpose(jvp(LlamaModel))/layer_4/hvd.block.attn/mamba/"
FUSION = "%fusion.1 = bf16[1,512,256]{2,1,0} fusion(...)"
MOSAIC = ('%custom-call.7 = bf16[1,512,256]{2,1,0} custom-call(...), '
          'custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("op_name,kind", [
    (FWD + "hvd.sscan.conv/mul", "conv"),
    (REC + "hvd.sscan.conv/logistic", "conv"),
    (FWD + "hvd.sscan.gates/x_proj/dot_general", "gates"),
    (BWD + "transpose(jvp(hvd.sscan.gates))/softplus", "gates"),
    (FWD + "hvd.sscan.scan/pallas_call", "scan"),
    (BWD + "hvd.sscan.scan/reduce_sum", "scan"),
    (STEP + "jvp(LlamaModel)/layer_6/hvd.block.attn/gmu/hvd.gmu/"
     "out_proj/dot_general", "gmu"),
    (STEP + "jvp(LlamaModel)/layer_5/hvd.block.attn/attn/hvd.attn.diff/"
     "rsqrt", "diff"),
    (FWD + "in_proj/dot_general", None),
    (STEP + "jvp(LlamaModel)/layer_0/hvd.block.attn/mamba/hvd.ssd.scan/exp",
     None),
])
def test_classify_by_the_new_scopes(op_name, kind):
    assert sambay_scopes.classify(op_name, names) == kind


def test_readers_on_hand_built_events(monkeypatch):
    step = "jit_hvd_train_step(1)"
    gmu = STEP + "jvp(LlamaModel)/layer_6/hvd.block.attn/gmu/hvd.gmu/"
    diff = STEP + "jvp(LlamaModel)/layer_5/hvd.block.attn/attn/hvd.attn.diff/"
    ops = [((FUSION, FWD + "hvd.sscan.conv/mul"), 0.0, 1e-3),
           ((MOSAIC, FWD + "hvd.sscan.scan/pallas_call"), 1e-3, 4e-3),
           ((FUSION, FWD + "in_proj/dot_general"), 4e-3, 5e-3),
           ((FUSION, BWD + "hvd.sscan.scan/reduce_sum"), 5e-3, 6e-3),
           ((FUSION, BWD + "hvd.sscan.gates/mul"), 6e-3, 8e-3),
           ((FUSION, gmu + "out_proj/dot_general"), 8e-3, 8.5e-3),
           ((FUSION, diff + "rsqrt"), 8.5e-3, 10e-3)]
    events = {"devices": {0: {
        "ops": ops + [((n, o), a + 10e-3, b + 10e-3) for (n, o), a, b in ops],
        "modules": [(step, 0.0, 10e-3), (step, 10e-3, 20e-3)]}}}
    reduced = sambay_scopes.partition(events, names)
    assert reduced == pytest.approx({
        "conv": 1.0, "gates": 2.0, "scan": 4.0, "gmu": 0.5, "diff": 1.5,
        "scan_mosaic": 3.0})
    # A stack without these layers never enters the scopes.
    assert sambay_scopes.partition(
        {"devices": {0: {"ops": ops[2:3], "modules": [(step, 0.0, 10e-3)]}}},
        names) is None
    monkeypatch.setattr(sambay_scopes.scopes, "read_events",
                        lambda path: events)
    monkeypatch.setattr(sambay_scopes.trace, "find_xplane",
                        lambda trace_dir: __file__)
    sambay_scopes._reduce_file.cache_clear()
    work = {"sscan": {"flops": 1e9, "bytes": 819e9 * 1e-3}}
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": work}}
    read = {name: manifest.load_reader(name)(ctx) for name in METRICS}
    assert read == pytest.approx({
        "sscan_conv_ms": 1.0, "sscan_gates_ms": 2.0, "sscan_scan_ms": 4.0,
        "sscan_scan_roofline": 25.0, "gmu_ms": 0.5, "diff_attn_ms": 1.5})
    for name in METRICS:
        assert manifest.load_reader(name)({**ctx, "trace": None}) is None
    assert manifest.load_reader("sscan_scan_roofline")(
        {**ctx, "job": {"kernel_work_per_step": {}}}) is None
    # A program without the scopes (the parent) gives no number.
    for program in (argparse.Namespace(LOSS="hvd.loss",
                                       SSD_SCAN="hvd.ssd.scan"), None):
        monkeypatch.setattr(sambay_scopes.scopes, "program_scopes",
                            lambda program=program: program)
        sambay_scopes._reduce_file.cache_clear()
        assert all(manifest.load_reader(name)(ctx) is None
                   for name in METRICS)
    sambay_scopes._reduce_file.cache_clear()


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
    assert {names.LOSS, names.SSCAN_CONV, names.SSCAN_GATES,
            names.SSCAN_SCAN, names.GMU, names.ATTN_DIFF, names.ATTN_WINDOW,
            names.BLOCK_ATTN, names.BLOCK_FFN, names.HEAD, names.FLASH_FWD,
            names.FLASH_BWD, names.REMATTED} <= held
    # Each scope in the layers of its kind alone, inside the mixer's block.
    by_kind = {}
    for (_, op_name), _, _ in ops:
        kind = sambay_scopes.classify(op_name, names)
        if kind is not None:
            assert names.BLOCK_ATTN in op_name
            by_kind.setdefault(kind, set()).add(
                op_name.split("/layer_")[1][0])
    assert by_kind == {"conv": {"0", "2", "4"}, "gates": {"0", "2", "4"},
                       "scan": {"0", "2", "4"}, "gmu": {"6"},
                       "diff": {"1", "3", "5", "7"}}
    windowed = {op_name.split("/layer_")[1][0] for (_, op_name), _, _ in ops
                if window_scopes.classify(op_name, names) == "window"}
    assert windowed == {"1", "3"}
    # The scan is the Mosaic pair, the filter its pass, the attention the
    # flash pair: every Mosaic call of the step is under one of their scopes.
    mosaic = [op_name for (text, op_name), _, _ in ops
              if scopes.trace.op_kind(text) == "mosaic"]
    assert any(names.SSCAN_SCAN in op for op in mosaic)
    assert any(names.SSCAN_CONV in op for op in mosaic)
    assert all(any(scope in op for scope in (
        names.SSCAN_SCAN, names.SSCAN_CONV, names.FLASH_FWD, names.FLASH_BWD))
        for op in mosaic)
    assert os.path.getsize(RECORDED) < 700_000


def test_recorded_step_by_the_scopes_the_cell_reports(recorded, monkeypatch):
    events = scopes.read_events(recorded)
    ours = sambay_scopes.partition(events, names)
    assert all(ours[kind] > 0 for kind in sambay_scopes.KINDS)
    assert 0 < ours["scan_mosaic"] <= ours["scan"]
    window = window_scopes.partition(events, names)
    assert window["window"] > 0 and not window["gate"]
    by_class = scopes.partition(events, names)
    assert by_class["flash"]["fwd"] > 0 and by_class["flash"]["bwd"] > 0
    assert sum(ours[k] for k in sambay_scopes.KINDS) < (
        by_class["classes"]["forward"] + by_class["classes"]["backward"])
    monkeypatch.setattr(sambay_scopes.trace, "find_xplane",
                        lambda trace_dir: recorded)
    sambay_scopes._reduce_file.cache_clear()
    work = {"sscan": {"flops": 1.0, "bytes": 819e9 * 1e-6}}
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": work}}
    for kind, metric in (("conv", "sscan_conv_ms"), ("gates", "sscan_gates_ms"),
                         ("scan", "sscan_scan_ms"), ("gmu", "gmu_ms"),
                         ("diff", "diff_attn_ms")):
        assert manifest.load_reader(metric)(ctx) == pytest.approx(ours[kind])
    assert manifest.load_reader("sscan_scan_roofline")(ctx) == pytest.approx(
        100.0 * 1e-3 / ours["scan"])
    sambay_scopes._reduce_file.cache_clear()
