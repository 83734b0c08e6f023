"""The ``nemotron-3-nano-30b-a3b`` configuration and its cell: the manifest's
new entries, the configuration's file against the catalog's row, the parameter
table, the job and ``benchmark/arithmetic_ssd.py`` against brute-force and
hand counts, the four new readers on hand-built events and on a tiny step
traced on a v5e.  (``test_cell_traced_tiny`` traces the manifest's first and
last cells, so this cell's traced tiny run is there; the suite is too near its
time limit to keep the ``qwen3-next-80b-a3b`` cell's beside it.)"""

import argparse
import gzip
import itertools
import json
import os

import jax
import numpy as np
import pytest

from benchmark import (arithmetic, arithmetic_moe, arithmetic_ssd, manifest,
                       moe_scopes, scopes, ssd_scopes)
from horovod_tpu.common import scopes as names

CELL = "nemotron-3-nano-30b-a3b.train-s8k-b2"
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
          "blob/main/config.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = {"ssd_conv_ms": ("model", "program_span", "ms", "lower"),
           "ssd_gates_ms": ("model", "program_span", "ms", "lower"),
           "ssd_scan_ms": ("kernels", "program_span", "ms", "lower"),
           "ssd_scan_roofline": ("kernels", "device_trace", "%", "higher")}
JOINED = ("tokens_per_s_per_chip", "mfu", "flash_ms", "flash_roofline",
          "flash_fwd_ms", "flash_bwd_ms", "flash_fwd_roofline",
          "flash_bwd_roofline", "block_attn_ms", "block_ffn_ms", "head_ms",
          "moe_route_ms", "moe_experts_ms", "moe_experts_roofline",
          "moe_shared_ms")
REDUCED = {"num_hidden_layers": (9, 52),
           "hybrid_override_pattern": (
               "MEMEM*EME",
               "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"),
           "n_routed_experts": (8, 128), "vocab_size": (16384, 131072)}
# Hidden 256; five one-sublayer layers MEM*E: Mamba-2 layers of 4 heads of 64
# with a state of 128 and 2 groups in chunks of 128, an attention layer of 2
# query heads over one key-value head of 128 (so the flash calls go in
# place), 4 of 16 relu2 experts held, top-3, a shared expert, 1 x 512 tokens,
# ``layer_keep_attention``: traced on one TPU v5e chip by this harness
# (PR 50), cut by ``benchmark.xspace.trim`` to its first three steps and to
# the lines the reductions read; gzipped.  Named ``.xspace.gz`` as PERF.md's
# Open question 23 says.
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-ssm-moe-decoder-v5e.xspace.gz")
TOKENS = 2 * 8192


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(CELL)


@pytest.fixture(scope="module")
def job(cell):
    return manifest.load_job("ssm_moe_lm").build(cell["config"],
                                                 cell["traffic"], 1)


# -- the manifest and the configuration's file --------------------------------

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
    assert config["reduced"] == list(REDUCED)
    for key, (here, there) in REDUCED.items():
        assert (config[key], published[key]) == (here, there), key
    assert REDUCED["hybrid_override_pattern"][1].startswith(
        config["hybrid_override_pattern"])
    assert len(config["hybrid_override_pattern"]) == 9
    # Every width as published.
    assert (config["hidden_size"], config["mamba_num_heads"],
            config["mamba_head_dim"], config["ssm_state_size"],
            config["n_groups"], config["conv_kernel"], config["chunk_size"],
            config["expand"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["moe_shared_expert_intermediate_size"],
            config["n_shared_experts"], config["num_experts_per_tok"],
            config["routed_scaling_factor"], config["layer_norm_epsilon"],
            config["max_position_embeddings"], config["rope_theta"],
            config["partial_rotary_factor"]) == (
                2688, 64, 64, 128, 8, 4, 128, 2, 32, 2, 128, 1856, 1856,
                3712, 1, 6, 2.5, 1e-5, 262144, 10000, 1)
    assert config["mlp_hidden_act"] == "relu2" and config["use_conv_bias"]
    assert set(config["reduced_why"]) == set(REDUCED)
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 16
    assert deployment["num_experts_published"] == 128
    assert deployment["first_held_expert"] == 0
    assert deployment["vocab_size_published"] == 131072
    assert deployment["num_hidden_layers_published"] == 52
    assert deployment["hybrid_override_pattern_published"] == (
        REDUCED["hybrid_override_pattern"][1])
    assert {"d_inner", "aux_loss_alpha", "bias_update_rate", "mamba_layer",
            "no_positional_embedding", "router", "relu2", "chunk",
            "initialisation", "training"} <= set(config["assumed"])
    assert config["assumed"]["d_inner"] == 64 * 64
    assert "2405.21060" in config["assumed"]["mamba_layer"]
    assert "2504.03624" in config["assumed"]["no_positional_embedding"]
    assert config["training"]["remat"] in ("layer", "layer_keep_attention")
    limits = config["checks"]["reference"]
    assert limits["parameters"] == "initial" and len(limits["why"]) > 500


def test_the_manifests_new_entries(cell):
    listed = manifest.load()
    entry = next(c for c in listed["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["source"] == cell["config"]["source"] == SOURCE
    assert entry["reduced"] == cell["config"]["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/nemotron-3-nano-30b-a3b.json"
    assert len(entry["why"]) <= 200
    # (By name, not by place: a later PR's entries come behind these.)
    workload = next(w for w in listed["workloads"] if w["name"] == CELL)
    assert workload == {"name": CELL, "config": "nemotron-3-nano-30b-a3b",
                        "traffic": "train-s8k-b2", "chips": 1,
                        "why": workload["why"]}
    assert "768 rows" in workload["why"] and len(workload["why"]) <= 200
    assert cell["traffic"] == manifest.cell(
        "qwen3-next-80b-a3b.train-s8k-b2")["traffic"]
    assert len(listed["configs"]) >= 9 and len(listed["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in listed["workloads"]) <= (
        len(listed["workloads"]) // 4)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "step_ms_p90", "peak_hbm_gb", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(METRICS) | set(JOINED[1:]) <= reported
    # Readers that find nothing to read in this cell.
    assert not {"recompute_ms", "dense_roofline", "mla_latent_ms",
                "sparse_index_ms", "window_attn_ms", "gdn_scan_ms",
                "attn_gate_ms", "qk_norm_ms"} & reported
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
    """ISSUE 50's table, matrix by matrix, and the program's own count."""
    hidden = 2688
    mamba = (hidden * 10304 + 4096 * hidden + 4 * 6144 + 6144 + 3 * 64 + 4096
             + hidden)
    attention = 2 * hidden * 4096 + 2 * hidden * 256 + hidden
    routed = (8 * 2 * hidden * 1856 + 2 * hidden * 3712 + hidden * 128
              + hidden)
    assert (mamba, attention, routed) == (38_744_896, 23_399_040, 100_125_312)
    table = 4 * mamba + 4 * routed + attention + 2 * 16384 * hidden + hidden
    params, _, bias = jax.eval_shape(job.init_state, jax.random.key(0))
    assert set(params) == {"params"}
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(params))
    assert count == table == 666_962_944
    assert count * 14 == pytest.approx(9.338e9, rel=1e-3)
    # The choice bias is state beside the parameters, 128 entries a router.
    assert {k: v["moe"]["bias"].shape for k, v in bias.items()} == {
        f"layer_{i}": (128,) for i in (1, 3, 6, 8)}
    tree = params["params"]
    assert tree["layer_0"]["mamba"]["in_proj"]["kernel"].shape == (
        2688, 10304)
    assert tree["layer_0"]["mamba"]["conv_w"].shape == (4, 6144)
    assert tree["layer_0"]["mamba"]["conv_b"].shape == (6144,)
    assert tree["layer_0"]["mamba"]["norm"].shape == (4096,)
    assert tree["layer_5"]["attn"]["wq"]["kernel"].shape == (2688, 4096)
    assert tree["layer_5"]["attn"]["wk"]["kernel"].shape == (2688, 256)
    assert tree["layer_1"]["moe"]["w_up"].shape == (8, 2688, 1856)
    assert tree["layer_1"]["moe"]["w_down"].shape == (8, 1856, 2688)
    assert tree["layer_1"]["moe"]["shared"]["w_up"]["kernel"].shape == (
        2688, 3712)
    assert tree["layer_1"]["moe"]["router"]["kernel"].shape == (2688, 128)
    assert all(sorted(tree[f"layer_{i}"]) == sorted((kind, "norm"))
               for i, kind in enumerate(
                   ["mamba", "moe", "mamba", "moe", "mamba", "attn", "moe",
                    "mamba", "moe"]))


def _brute_force_scan_macs(seq, heads, groups, width, state, chunk):
    """Multiply-adds of the chunked algorithm, one at a time."""
    macs = 0
    for _ in range(-(-seq // chunk)):
        for _ in range(groups):                             # C B^T
            macs += sum(1 for _ in itertools.product(
                range(chunk), range(chunk), range(state)))
        for _ in range(heads):
            # the masked product, the state written, the state read
            macs += sum(1 for _ in itertools.product(
                range(chunk), range(chunk), range(width)))
            macs += 2 * sum(1 for _ in itertools.product(
                range(chunk), range(width), range(state)))
    return macs


def test_arithmetic_against_a_brute_force_count_at_a_tiny_size():
    sizes = dict(seq=8, heads=4, groups=2, head_dim=2, state=3, chunk=4)
    macs = _brute_force_scan_macs(8, 4, 2, 2, 3, 4)
    assert macs == 2 * (2 * 4 * 4 * 3 + 4 * (4 * 4 * 2 + 2 * 4 * 2 * 3))
    assert arithmetic_ssd.scan_flops(batch=1, **sizes) == 3 * 2 * macs
    assert arithmetic_ssd.scan_flops(batch=3, **sizes) == 3 * 3 * 2 * macs
    # A sequence that is no whole number of chunks pays for the last whole.
    assert arithmetic_ssd.scan_flops(batch=1, **{**sizes, "seq": 7}) == (
        3 * 2 * macs)
    # Bytes, array by array: u and y at the heads, B and C at the GROUPS,
    # dt float32 a head, the chunk states float32 once each way.
    u = 8 * 4 * 2 * 2
    bc = 8 * 2 * 2 * 3 * 2
    dt = 8 * 4 * 4
    states = 2 * 4 * 2 * 3 * 4
    forward = u + bc + dt + u + states          # read u B C dt; write y, S
    backward = forward + u + bc + dt            # and dy read, 4 grads written
    assert arithmetic_ssd.scan_bytes(batch=1, **sizes) == forward + backward


def test_kernel_work_of_the_cell(job):
    work = job.kernel_work_per_step()
    assert set(work) == {"flash", "ssd_scan", "moe_experts"}
    # Seven products a kept pair at 32 heads of 128, ONE layer of nine.
    causal = 7 * 2 * 128 * 32 * 2 * arithmetic.causal_pairs(8192)
    assert work["flash"]["flops"] == causal
    assert work["flash"]["forward"]["flops"] * 7 == work["flash"]["flops"] * 2
    tensor = 2 * 8192 * 128 * 2
    assert work["flash"]["bytes"] == 6 * (32 + 2) * tensor
    # The scan: 64 chunks of 128 a sequence, four layers.
    macs = arithmetic_ssd.chunk_scan_macs(head_dim=64, state=128,
                                          heads_a_group=8)
    assert macs == 128 * 128 * 128 / 8 + 128 * 128 * 64 + 2 * 128 * 64 * 128
    assert work["ssd_scan"]["flops"] == 4 * (3 * 2 * 2 * 64 * 64 * macs)
    u = TOKENS * 64 * 64 * 2
    bc = TOKENS * 8 * 2 * 128 * 2
    dt = TOKENS * 64 * 4
    states = 2 * 64 * 64 * 64 * 128 * 4
    a_layer = 2 * (2 * u + bc + dt + states) + u + bc + dt
    assert work["ssd_scan"]["bytes"] == 4 * a_layer
    least, bound = arithmetic.roofline_seconds(
        work["ssd_scan"]["flops"], work["ssd_scan"]["bytes"],
        manifest.peaks("TPU v5 lite"))
    assert bound == "bytes" and least == pytest.approx(6.944e-3, rel=1e-3)
    # The held experts at the rows this chip computes: 6 x 8 / 128 of an
    # expert a token, 768 rows an expert, TWO matrices an expert.
    rows = arithmetic_moe.expert_rows(tokens=TOKENS, per_token=6, held=8,
                                      experts=128)
    assert rows == 6144 and rows / 8 == 768
    assert work["moe_experts"]["flops"] == 4 * 3 * 2 * rows * 2 * 2688 * 1856
    assert work["moe_experts"]["bytes"] == 4 * 3 * 2 * (
        8 * 2 * 2688 * 1856 + rows * 2 * (2688 + 1856))


def test_flops_of_the_nine_sublayers_by_hand(job):
    hidden = 2688
    mamba = hidden * 10304 + 4096 * hidden
    attention = hidden * 128 * (2 * 32 + 2 * 2)
    routed = (hidden * 128 + 2 * hidden * 3712
              + 6 * 8 / 128 * 2 * hidden * 1856)
    weights = 4 * mamba + attention + 4 * routed + hidden * 16384
    assert weights == pytest.approx(318.43e6, rel=1e-3)
    scores = 2 * 2 * 32 * 128 * arithmetic.causal_pairs(8192)
    macs = arithmetic_ssd.chunk_scan_macs(head_dim=64, state=128,
                                          heads_a_group=8)
    scan = 4 * 3 * 2 * 64 * 64 * macs
    assert job.flops_per_unit() * 8192 == pytest.approx(
        3 * (2 * weights * 8192 + scores) + scan, rel=1e-12)
    assert job.flops_per_unit() * TOKENS == pytest.approx(35.1e12, rel=5e-3)


def test_job_builds_the_published_layers(job, cell):
    c = job.llama
    assert job.has_aux is True
    assert c.hybrid_override_pattern == "MEMEM*EME" and c.num_layers == 9
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
            c.rope_theta) == (2688, 32, 2, 128, None)
    assert (c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size, c.n_groups,
            c.conv_kernel, c.chunk_size, c.mamba_inner) == (
                64, 64, 128, 8, 4, 128, 4096)
    assert (c.num_experts, c.experts_held, c.experts_per_token,
            c.moe_intermediate_size, c.shared_experts,
            c.moe_shared_expert_intermediate_size, c.norm_topk_prob,
            c.routed_scaling_factor, c.balance_over, c.scoring_func,
            c.topk_method, c.mlp_hidden_act, c.router_bias_update_rate) == (
                128, 8, 6, 1856, 1, 3712, True, 2.5, "batch", "sigmoid",
                "noaux_tc", "relu2", 1e-3)
    assert [c.is_routed(i) for i in range(9)] == [
        kind == "E" for kind in "MEMEM*EME"]
    assert all(c.rope_of(i) is None for i in range(9))
    assert c.remat == cell["config"]["training"]["remat"]
    assert job.expected_first_loss() == pytest.approx(
        np.log(16384) + 0.5 + 1e-4)
    module = manifest.load_job("ssm_moe_lm")
    with pytest.raises(ValueError, match="Nemotron-H's layers"):
        module.build({**cell["config"], "use_conv_bias": False},
                     cell["traffic"], 1)
    with pytest.raises(ValueError, match="Nemotron-H's layers"):
        module.build({**cell["config"], "hybrid_override_pattern": "MEMEM*E"},
                     cell["traffic"], 1)
    with pytest.raises(ValueError, match="master AdamW"):
        module.build({**cell["config"], "training": {
            **cell["config"]["training"], "optimizer": "sgd"}},
            cell["traffic"], 1)


# -- the readers of the new scopes ---------------------------------------------

STEP = "jit(hvd_train_step)/hvd.loss/"
FWD = STEP + "jvp(LlamaModel)/layer_2/hvd.block.attn/mamba/"
REC = (STEP + "transpose(jvp(LlamaModel))/hvd.loss/jvp(LlamaModel)/"
       "checkpoint/rematted_computation/layer_2/hvd.block.attn/mamba/")
BWD = STEP + "transpose(jvp(LlamaModel))/layer_2/hvd.block.attn/mamba/"
FUSION = "%fusion.3 = bf16[2,8192,6144]{2,1,0} fusion(%a), kind=kLoop"


@pytest.mark.parametrize("op_name, kind", [
    (FWD + "hvd.ssd.conv/mul", "conv"),
    (REC + "hvd.ssd.conv/logistic", "conv"),
    (BWD + "hvd.ssd.conv/checkpoint/reduce_sum", "conv"),
    (FWD + "hvd.ssd.gates/softplus", "gates"),
    (BWD + "transpose(jvp(hvd.ssd.gates))/rsqrt", "gates"),
    (FWD + "hvd.ssd.scan/while/body/dot_general", "scan"),
    (REC + "hvd.ssd.scan/checkpoint/exp", "scan"),
    (FWD + "in_proj/dot_general", None),
    (STEP + "jvp(LlamaModel)/layer_0/hvd.block.attn/linear/hvd.gdn.scan/exp",
     None),
])
def test_classify_by_the_new_scopes(op_name, kind):
    assert ssd_scopes.classify(op_name, names) == kind


def test_readers_on_hand_built_events(monkeypatch):
    step = "jit_hvd_train_step(1)"
    ops = [((FUSION, FWD + "hvd.ssd.conv/mul"), 0.0, 1e-3),
           ((FUSION, FWD + "hvd.ssd.scan/dot_general"), 1e-3, 4e-3),
           ((FUSION, FWD + "in_proj/dot_general"), 4e-3, 5e-3),
           ((FUSION, REC + "hvd.ssd.scan/dot_general"), 5e-3, 6e-3),
           ((FUSION, BWD + "hvd.ssd.gates/mul"), 6e-3, 8e-3),
           ((FUSION, BWD + "hvd.ssd.scan/dot_general"), 8e-3, 10e-3)]
    events = {"devices": {0: {
        "ops": ops + [((n, o), a + 10e-3, b + 10e-3) for (n, o), a, b in ops],
        "modules": [(step, 0.0, 10e-3), (step, 10e-3, 20e-3)]}}}
    reduced = ssd_scopes.partition(events, names)
    assert reduced == pytest.approx({"conv": 1.0, "gates": 2.0, "scan": 6.0,
                                     "scan_recomputed": 1.0})
    # A stack without a Mamba layer never enters the scopes.
    assert ssd_scopes.partition(
        {"devices": {0: {"ops": ops[2:3], "modules": [(step, 0.0, 10e-3)]}}},
        names) is None
    monkeypatch.setattr(ssd_scopes.scopes, "read_events", lambda path: events)
    monkeypatch.setattr(ssd_scopes.trace, "find_xplane",
                        lambda trace_dir: __file__)
    ssd_scopes._reduce_file.cache_clear()
    work = {"ssd_scan": {"flops": 1e9, "bytes": 819e9 * 1.5e-3}}
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": work}}
    read = {name: manifest.load_reader(name)(ctx) for name in METRICS}
    assert read == pytest.approx({
        "ssd_conv_ms": 1.0, "ssd_gates_ms": 2.0, "ssd_scan_ms": 6.0,
        "ssd_scan_roofline": 25.0})
    for name in METRICS:
        assert manifest.load_reader(name)({**ctx, "trace": None}) is None
    assert manifest.load_reader("ssd_scan_roofline")(
        {**ctx, "job": {"kernel_work_per_step": {}}}) is None
    # A program without the scopes (the parent) gives no number.
    for program in (argparse.Namespace(LOSS="hvd.loss",
                                       GDN_SCAN="hvd.gdn.scan"), None):
        monkeypatch.setattr(ssd_scopes.scopes, "program_scopes",
                            lambda program=program: program)
        ssd_scopes._reduce_file.cache_clear()
        assert all(manifest.load_reader(name)(ctx) is None
                   for name in METRICS)
    ssd_scopes._reduce_file.cache_clear()


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
    assert {names.LOSS, names.SSD_CONV, names.SSD_GATES, names.SSD_SCAN,
            names.BLOCK_ATTN, names.BLOCK_FFN, names.HEAD, names.FLASH_FWD,
            names.FLASH_BWD, names.MOE_ROUTE, names.MOE_SHARED,
            names.AUX_ALLREDUCE, names.REMATTED} <= held | {
                names.AUX_ALLREDUCE}
    # The three are in the Mamba layers (0 and 2 of MEM*E) alone, inside
    # the mixer's block; a layer is ONE block: no layer is under both.
    ours = [op_name for (_, op_name), _, _ in ops
            if ssd_scopes.classify(op_name, names)]
    assert {op.split("/layer_")[1][0] for op in ours} == {"0", "2"}
    assert all(names.BLOCK_ATTN in op and "/mamba/" in op for op in ours)
    blocks = {}
    for (_, op_name), _, _ in ops:
        if "/layer_" in op_name:
            blocks.setdefault(op_name.split("/layer_")[1][0], set()).update(
                block for block in (names.BLOCK_ATTN, names.BLOCK_FFN)
                if block in op_name)
    assert blocks == {"0": {names.BLOCK_ATTN}, "1": {names.BLOCK_FFN},
                      "2": {names.BLOCK_ATTN}, "3": {names.BLOCK_ATTN},
                      "4": {names.BLOCK_FFN}}
    # No Mosaic call of the program's in a Mamba layer: the scan is XLA's
    # and the filter, with its bias, the plain body.
    mosaic = [op_name for (text, op_name), _, _ in ops
              if scopes.trace.op_kind(text) == "mosaic"
              and not op_name.startswith(names.RAGGED_DOT_PREFIX)]
    assert mosaic and all(names.FLASH_FWD in op or names.FLASH_BWD in op
                          for op in mosaic)
    assert os.path.getsize(RECORDED) < 700_000


def test_recorded_step_by_the_scopes_the_cell_reports(recorded, monkeypatch):
    events = scopes.read_events(recorded)
    mamba = ssd_scopes.partition(events, names)
    routed = moe_scopes.partition(events, names)
    assert all(mamba[kind] > 0 for kind in ("conv", "gates", "scan"))
    assert 0 < mamba["scan_recomputed"] < mamba["scan"]
    assert all(routed[kind] > 0 for kind in ("route", "experts", "shared"))
    by_class = scopes.partition(events, names)
    assert by_class["flash"]["fwd"] > 0 and by_class["flash"]["bwd"] > 0
    assert sum(mamba[k] for k in ("conv", "gates", "scan")) < (
        by_class["classes"]["forward"] + by_class["classes"]["backward"])
    monkeypatch.setattr(ssd_scopes.trace, "find_xplane",
                        lambda trace_dir: recorded)
    ssd_scopes._reduce_file.cache_clear()
    work = {"ssd_scan": {"flops": 1.0, "bytes": 819e9 * 1e-6}}
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": work}}
    for kind in ("conv", "gates", "scan"):
        assert manifest.load_reader(f"ssd_{kind}_ms")(ctx) == pytest.approx(
            mamba[kind])
    assert manifest.load_reader("ssd_scan_roofline")(ctx) == pytest.approx(
        100.0 * 1e-3 / mamba["scan"])
    ssd_scopes._reduce_file.cache_clear()
