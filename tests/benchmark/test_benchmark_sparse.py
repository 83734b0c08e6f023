"""The ``keye-vl-2.0-30b-a3b`` configuration and its cell: the manifest's new
entries, the configuration's file against the published config, the job and
its arithmetic against hand counts, the job against wrong versions of itself
through the comparison that decides ``correct``, the layers' own counters,
the readers of the new scopes on hand-built events and on a tiny step traced
on a v5e, and the traced tiny run that the ``deepseek-v2-lite`` cell had
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

from benchmark import (arithmetic, arithmetic_moe, arithmetic_sparse,
                       compare, manifest, run, scopes, sparse_scopes)
from horovod_tpu.common import scopes as names
from horovod_tpu.models import LlamaModel, llama
from horovod_tpu.ops import sparse_index
from horovod_tpu.ops.flash_attention import flash_attention_fn

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_sizes import TINY  # noqa: E402

CELL = "keye-vl-2.0-30b-a3b.train-s8k-b2"
# Hidden 256, 4 query heads on 2 key-value heads of 128, an indexer of 4
# heads of 64 that keeps 128 keys, two layers holding experts 4 to 7 of 16
# (3 choices a token), 2 x 512 tokens, ``layer_keep_selection``: traced on
# one TPU v5e chip by this harness (PR 34), cut by ``benchmark.xspace.trim``
# to its first three steps and to the lines the reductions read; gzipped.
# Named ``.xspace.gz`` as PERF.md's Open question 23 says: the accepted
# ``test_flash_passes_add_up_to_the_mosaic_time_of_every_recording`` takes
# every ``*.xplane.pb*`` and holds the flash passes to ALL Mosaic time, and
# this step's selection, indexer loss and grouped products are Mosaic too.
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-sparse-decoder-v5e.xspace.gz")

# https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json,
# the language model's keys.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
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
    return manifest.load_job("sparse_moe_lm").build(cell["config"],
                                                    cell["traffic"], 1)


# -- the manifest and the configuration's file --------------------------------

def test_the_configuration_is_the_published_one_but_for_its_three_cuts(cell):
    config = cell["config"]
    differ = {key for key, value in PUBLISHED.items()
              if config[key] != value}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "num_local_experts", "vocab_size"}
    assert (config["num_hidden_layers"], config["num_local_experts"],
            config["vocab_size"]) == (5, 16, 18992)
    assert set(config["reduced_why"]) == set(config["reduced"])
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 8
    assert deployment["first_held_expert"] == 0
    assert {"qk_norm", "index_rope", "index_key", "index_scale",
            "chunk_sizes", "aux_loss_alpha", "index_loss_lambda",
            "index_precision", "rope_layout", "training",
            "initialisation"} <= set(config["assumed"])
    assert config["training"]["remat"] == "layer_keep_selection"
    # The floors of a cut: four layers of the one kind, eight routed
    # experts, an eighth of the vocabulary; the router's width stays.
    assert config["num_hidden_layers"] >= 4
    assert config["num_local_experts"] >= 8
    assert config["num_experts"] == PUBLISHED["num_experts"]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]


def test_the_manifests_new_entries(cell):
    listed = manifest.load()
    entry = listed["configs"][-1]
    assert entry["name"] == "keye-vl-2.0-30b-a3b"
    assert entry["source"] == cell["config"]["source"]
    assert entry["reduced"] == cell["config"]["reduced"]
    assert listed["workloads"][-1] == {
        "name": CELL, "config": "keye-vl-2.0-30b-a3b",
        "traffic": "train-s8k-b2", "chips": 1,
        "why": listed["workloads"][-1]["why"]}
    assert cell["traffic"] == {"chips": 1, "mesh": {"data": 1},
                               "batch_per_chip": 2, "sequence": 8192,
                               "pool": 8, "sample_per_chip": 1}
    assert len(listed["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in listed["workloads"]) == 1
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "step_ms_p90", "peak_hbm_gb", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"mfu", "flash_ms", "flash_roofline", "flash_fwd_ms",
            "flash_bwd_ms", "flash_fwd_roofline", "flash_bwd_roofline",
            "moe_route_ms", "moe_experts_ms", "moe_experts_roofline",
            "sparse_index_ms", "sparse_select_ms", "index_select_ms",
            "index_select_roofline", "index_loss_ms",
            "index_loss_roofline"} <= reported
    # No shared expert and no latent: those readers would find nothing.
    assert not {"moe_shared_ms", "mla_latent_ms"} & reported
    new = listed["per_layer"][-6:]
    assert [m["name"] for m in new] == [
        "sparse_index_ms", "sparse_select_ms", "index_select_ms",
        "index_select_roofline", "index_loss_ms", "index_loss_roofline"]
    for metric in new:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "step_ms_p90"
        assert os.path.exists(manifest.metric_path(metric["name"]))
        assert metric["source"] == ("program_span" if metric["name"].
                                    startswith("sparse_") else "device_trace")
        assert (metric["unit"], metric["better"]) == (
            ("%", "higher") if metric["name"].endswith("_roofline")
            else ("ms", "lower"))


# -- the job and its arithmetic ------------------------------------------------

def test_arithmetic_against_hand_counts(job):
    assert arithmetic_sparse.selected_pairs(8192, 2048) == (
        2048 * 2049 // 2 + 6144 * 2048) == 14_681_088
    assert arithmetic_sparse.selected_pairs(1024, 2048) == (
        arithmetic.causal_pairs(1024))
    kept = 14_681_088 / arithmetic.causal_pairs(8192)
    assert kept == pytest.approx(0.4375, abs=2e-4)     # what a call that
    # executes every causal pair can read of its roofline at most
    assert arithmetic_sparse.attention_matmul_params(
        hidden=2048, heads=32, kv_heads=4, head_dim=128) == 18_874_368
    assert arithmetic_sparse.indexer_matmul_params(
        hidden=2048, index_heads=16, index_dim=64) == 2048 * (1024 + 64 + 16)
    assert arithmetic_sparse.index_loss_flops_per_pair(
        heads=32, head_dim=128, index_heads=16, index_dim=64) == 14336
    # One token of the cell, by hand: five layers' weights a token meets
    # (attention, the router, one expert's worth of the 8 x 16 / 128 choices
    # that land here), the head; the kept pairs' two products forward; the
    # indexer's projections (no input gradient), its scores over every
    # causal pair once, its loss on the kept ones.
    weights = 5 * (18_874_368 + 2048 * 128 + 3 * 2048 * 768) + 2048 * 18992
    per_token = 14_681_088 / 8192
    by_hand = (3 * (2 * weights + 5 * 32 * 4 * 128 * per_token)
               + 4 * 5 * 2048 * 1104
               + 5 * 2 * 1024 * arithmetic.causal_pairs(8192) / 8192
               + 5 * per_token * 14336)
    assert job.flops_per_unit() == pytest.approx(by_hand, rel=1e-12)


def test_kernel_work_counts_the_kept_pairs_at_grouped_query_bytes(job):
    work = job.kernel_work_per_step()
    kept = 2 * 14_681_088
    assert work["flash"]["forward"]["flops"] == 5 * 2 * 2 * 128 * 32 * kept
    assert work["flash"]["backward"]["flops"] == 5 * 5 * 2 * 128 * 32 * kept
    rows = 2 * 8192 * 128 * 2
    assert work["flash"]["forward"]["bytes"] == 5 * rows * (2 * 32 + 2 * 4)
    assert work["flash"]["backward"]["bytes"] == 5 * rows * (4 * 32 + 4 * 4)
    assert work["flash"]["flops"] == (work["flash"]["forward"]["flops"]
                                      + work["flash"]["backward"]["flops"])
    assert work["index_select"]["flops"] == (
        5 * 2 * 2 * 1024 * arithmetic.causal_pairs(8192))
    assert work["index_select"]["bytes"] == 5 * 2 * 8192 * (
        2 * 17 * 64 + 4 * 16 + 8192)
    assert work["index_loss"]["flops"] == 5 * kept * 14336
    # 8 x 16 / 128 = 1 choice a token lands here: 16384 rows a layer.
    assert work["moe_experts"]["flops"] == (
        5 * arithmetic_moe.expert_products_flops(
            rows=16384, hidden=2048, expert_ffn=768))
    peaks = manifest.peaks("TPU v5 lite")
    for call in ("index_select", "index_loss"):
        assert arithmetic.roofline_seconds(
            work[call]["flops"], work[call]["bytes"], peaks)[1] == "flops"


def test_job_builds_the_published_layers(job, cell):
    c = job.llama
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim) == (
        2048, 32, 4, 128)
    assert (c.index_heads, c.index_head_dim, c.index_topk) == (16, 64, 2048)
    assert (c.num_experts, c.experts_per_token, c.experts_held,
            c.moe_intermediate_size, c.shared_experts) == (128, 8, 16, 768, 0)
    assert c.norm_topk_prob and c.qk_norm and c.balance_over == "batch"
    assert c.attention_kind == "sparse" and c.rope_theta == 1e7
    assert c.remat == "layer_keep_selection"
    shapes = jax.eval_shape(job.init_state, jax.random.key(0))[0]
    count = sum(np.prod(leaf.shape) for leaf in jax.tree.leaves(shapes))
    assert count == 5 * (18_874_368 + 256 + 2048 * 1104 + 2 * 2048
                         + 2048 * 128 + 16 * 3 * 2048 * 768) + 2048 + (
                             2 * 18992 * 2048)
    assert count * 14 == pytest.approx(7.872e9, rel=1e-3)
    attn = shapes["params"]["layer_0"]["attn"]
    assert attn["wq"]["kernel"].shape == (2048, 4096)
    assert attn["wk"]["kernel"].shape == (2048, 512)
    assert attn["q_norm"]["scale"].shape == (128,)
    assert attn["index_wq"]["kernel"].shape == (2048, 1024)
    assert attn["index_wk"]["kernel"].shape == (2048, 64)
    assert attn["index_ww"]["kernel"].shape == (2048, 16)
    assert job.expected_first_loss() == pytest.approx(
        np.log(18992) + 0.5 + 0.001 + 0.124)
    with pytest.raises(ValueError, match="Keye-VL-2.0's decoder layers"):
        manifest.load_job("sparse_moe_lm").build(
            {**cell["config"], "attention_bias": True}, cell["traffic"], 1)


def test_counters_of_the_tiny_job():
    tiny, reference, config = _tiny_job()
    params, _ = jax.jit(tiny.init_state)(jax.random.key(0))
    batch = tiny.make_batch(jax.random.key(1))
    taken, selected, rows, dropped = jax.jit(tiny.counters)(params, batch)
    assert taken.shape == (2, 2, 256) and selected.shape == (2, 2, 256, 256)
    assert int(taken.max()) == 64 and dropped.tolist() == [0, 0]
    np.testing.assert_array_equal(selected.sum(-1), taken)
    assert rows.shape == (2, 4)
    # 4 of 16 experts held, 3 choices a token: about a quarter of them.
    assert 0 < int(rows[0].sum()) < 2 * 256 * 3
    # bf16 against the float32 reference: most of its keys, not all.
    wanted = jnp.stack(reference.selection(tiny.to_reference(params), batch,
                                           config))
    agreement = float(jnp.sum(wanted & (selected != 0)) / jnp.sum(wanted))
    assert 0.9 < agreement <= 1.0


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


def _patched(job, module, name, replacement):
    """The job's loss with ``module.name`` replaced while it is traced."""
    def loss_fn(params, batch):
        original = getattr(module, name)
        setattr(module, name, replacement(original))
        try:
            return type(job).loss_fn(job, params, batch)
        finally:
            setattr(module, name, original)
    return loss_fn


def _dense_attention(job):
    return _with_model(job, attention_kind="full")


def _half_the_keys(job):
    return _with_model(job, index_topk=job.llama.index_topk // 2)


def _gates_not_renormalised(job):
    return _with_model(job, norm_topk_prob=False)


def _no_qk_norm(job):
    return _with_model(job, qk_norm=False)


def _no_relu(job):
    def without(original):
        def tile_scores(qi_ref, k_blk, w):
            acc = 0.0
            for j in range(qi_ref.shape[0]):
                acc = acc + w[:, j:j + 1] * jax.lax.dot_general(
                    qi_ref[j], k_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return acc
        return tile_scores
    return _patched(job, sparse_index, "_tile_scores", without)


def _target_from_one_head(job):
    def one_head(original):
        def index_loss(q, k, lse, *rest, **options):
            heads = q.shape[2]
            return original(jnp.repeat(q[:, :, :1], heads, axis=2), k,
                            jnp.repeat(lse[:, :1], heads, axis=1), *rest,
                            **options)
        return index_loss
    return _patched(job, llama, "index_loss", one_head)


@pytest.mark.parametrize("defect, least", [
    (None, 0.0), (_dense_attention, 0.2), (_half_the_keys, 0.2),
    (_no_relu, 0.1), (_gates_not_renormalised, 0.2),
    (_target_from_one_head, 0.02), (_no_qk_norm, 0.2)])
def test_comparison_passes_the_job_and_fails_wrong_versions_of_it(defect,
                                                                  least):
    """In float32 at the tiny size, where the job as it is reads 1e-6 and
    every wrong version has to show: dense causal attention in place of the
    selection, half the keys, the ReLU left out of the index scores, gates
    not renormalised, the target from one head for the mean of all, QK-norm
    dropped.  (At the cell's size in bf16 the limits of the configuration's
    file decide; ``checks.reference.why`` says what they caught there.)"""
    job, reference, config = _tiny_job()
    job.llama = dataclasses.replace(job.llama, dtype=jnp.float32,
                                    logits_dtype=jnp.float32)
    job.model = LlamaModel(job.llama, attention_fn=flash_attention_fn)
    config = {**config, "checks": {**config["checks"], "reference": {
        "parameters": "initial", "loss_abs": 1e-4, "grad_rel": 1e-4}}}
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    params = LlamaModel(job.llama).init(jax.random.key(0),
                                        jnp.zeros((1, 8), jnp.int32))
    state = (params, None)
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
FWD = STEP + "jvp(LlamaModel)/layer_1/attn/"
BWD = STEP + "transpose(jvp(LlamaModel))/layer_1/attn/"
MOSAIC = ('%custom-call.7 = (s8[2,512,512]{2,1,0}) custom-call(%a), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.3 = bf16[2,512,256]{2,1,0} fusion(%a), kind=kLoop"


@pytest.mark.parametrize("text, op_name, kinds", [
    (MOSAIC, FWD + "hvd.sparse.select/pallas_call",
     ("select", "index_select")),
    (MOSAIC, FWD + "jvp(hvd.sparse.index)/pallas_call",
     ("index", "index_loss")),
    (FUSION, FWD + "hvd.sparse.index/index_wq/dot_general", ("index",)),
    (FUSION, BWD + "hvd.sparse.index/index_wq/transpose", ("index",)),
    (FUSION, FWD + "hvd.sparse.select/convert_element_type", ("select",)),
    (MOSAIC, FWD + "hvd.flash.fwd/pallas_call", ()),
    (FUSION, FWD + "wq/dot_general", ()),
    (MOSAIC, "ragged-dot-none", ()),
])
def test_classify_by_the_new_scopes(text, op_name, kinds):
    assert sparse_scopes.classify(text, op_name, names) == kinds


def test_partition_and_readers_on_hand_built_events(monkeypatch):
    step = "jit_hvd_train_step(1)"
    ops = [((MOSAIC, FWD + "hvd.sparse.select/pallas_call"), 0.0, 2e-3),
           ((FUSION, FWD + "hvd.sparse.index/index_wq/dot_general"),
            2e-3, 3e-3),
           ((MOSAIC, FWD + "jvp(hvd.sparse.index)/pallas_call"), 3e-3, 7e-3),
           ((FUSION, FWD + "wq/dot_general"), 7e-3, 9e-3)]
    events = {"devices": {0: {
        "ops": ops + [((n, o), a + 10e-3, b + 10e-3) for (n, o), a, b in ops],
        "modules": [(step, 0.0, 10e-3), (step, 10e-3, 20e-3)]}}}
    kinds = sparse_scopes.partition(events, names)
    assert kinds == pytest.approx({"index": 5.0, "select": 2.0,
                                   "index_loss": 4.0, "index_select": 2.0})
    assert sparse_scopes.partition(
        {"devices": {0: {"ops": ops[3:], "modules": [(step, 0.0, 10e-3)]}}},
        names) is None
    monkeypatch.setattr(sparse_scopes.scopes, "read_events",
                        lambda path: events)
    monkeypatch.setattr(sparse_scopes.trace, "find_xplane",
                        lambda trace_dir: __file__)
    sparse_scopes._reduce_file.cache_clear()
    work = {"flops": 197e9, "bytes": 1e6}            # 1 ms at the peak
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {"index_select": work,
                                            "index_loss": work}}}
    for metric, value in (("sparse_index_ms", 5.0), ("sparse_select_ms", 2.0),
                          ("index_loss_ms", 4.0), ("index_select_ms", 2.0),
                          ("index_loss_roofline", 25.0),
                          ("index_select_roofline", 50.0)):
        assert manifest.load_reader(metric)(ctx) == pytest.approx(value)
    ctx["job"]["kernel_work_per_step"] = {}
    assert manifest.load_reader("index_loss_roofline")(ctx) is None
    for metric in ("sparse_index_ms", "index_select_roofline"):
        assert manifest.load_reader(metric)({**ctx, "trace": None}) is None
    # A program without the scopes (the parent) gives no number.
    monkeypatch.setattr(sparse_scopes.scopes, "program_scopes",
                        lambda: argparse.Namespace(LOSS="hvd.loss"))
    sparse_scopes._reduce_file.cache_clear()
    assert manifest.load_reader("sparse_select_ms")(ctx) is None
    sparse_scopes._reduce_file.cache_clear()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_recorded_trace_holds_the_new_scopes_and_four_calls_a_layer(recorded):
    events = scopes.read_events(recorded)
    assert sorted(events["devices"]) == [0]
    ops = events["devices"][0]["ops"]
    held = {scopes.bare(part) for (_, op_name), _, _ in ops
            for part in scopes.components(op_name)}
    assert {names.LOSS, names.SPARSE_INDEX, names.SPARSE_SELECT,
            names.FLASH_FWD, names.FLASH_BWD, names.MOE_ROUTE,
            names.MOE_EXPERTS, names.REMATTED} <= held
    mosaic = [op_name for (text, op_name), _, _ in ops
              if scopes.trace.op_kind(text) == "mosaic"
              and not op_name.startswith(names.RAGGED_DOT_PREFIX)]
    # Nothing of the attention is run again by the recomputing backward.
    assert mosaic and not any(names.REMATTED in op for op in mosaic)
    per_scope = {scope: sum(scope in op for op in mosaic) for scope in (
        names.SPARSE_SELECT, names.FLASH_FWD, names.SPARSE_INDEX,
        names.FLASH_BWD)}
    assert len(set(per_scope.values())) == 1 and per_scope[names.FLASH_FWD]
    assert os.path.getsize(RECORDED) < 400_000


def test_recorded_step_by_the_new_scopes(recorded, monkeypatch):
    events = scopes.read_events(recorded)
    kinds = sparse_scopes.partition(events, names)
    assert all(kinds[kind] > 0.0 for kind in sparse_scopes.KINDS)
    assert kinds["index_select"] == pytest.approx(kinds["select"])
    assert kinds["index_loss"] < kinds["index"]        # the projections
    by_class = scopes.partition(events, names)
    assert kinds["index"] + kinds["select"] < (
        by_class["classes"]["forward"] + by_class["classes"]["backward"])
    assert by_class["flash"]["fwd"] > 0 and by_class["flash"]["bwd"] > 0
    monkeypatch.setattr(sparse_scopes.trace, "find_xplane",
                        lambda trace_dir: recorded)
    sparse_scopes._reduce_file.cache_clear()
    ctx = {"trace": {}, "peaks": manifest.peaks("TPU v5 lite"),
           "job": {"kernel_work_per_step": {}}}
    for metric, kind in (("sparse_index_ms", "index"),
                         ("sparse_select_ms", "select"),
                         ("index_loss_ms", "index_loss"),
                         ("index_select_ms", "index_select")):
        assert manifest.load_reader(metric)(ctx) == pytest.approx(kinds[kind])
    sparse_scopes._reduce_file.cache_clear()


# -- the deepseek cell's traced tiny run ----------------------------------------

def test_deepseek_cell_traced_tiny():
    """``test_cell_traced_tiny`` traces the manifest's first and last
    cells; this configuration's cell is the last now, so the
    ``deepseek-v2-lite`` cell's traced run is kept here."""
    workload = "deepseek-v2-lite.train-s4k"
    routed = manifest.cell(workload)
    args = argparse.Namespace(workload=workload, seed=2 ** 31 + 11,
                              seconds=1.0, trace=1)
    result = json.loads(json.dumps(run.run(
        args, start=time.perf_counter(),
        overrides=TINY[routed["config"]["job"]], allow_cpu=True)))
    assert result["correct"] is True, result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) <= {m["name"] for m in routed["per_layer"]}
    assert metrics["compiles_in_window"] == 0
    assert metrics["hbm_arguments_gb"] > 0
