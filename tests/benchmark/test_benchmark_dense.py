"""The ordinary blocks' reader (``benchmark/dense_scopes.py``) and its four
metrics: on a tiny dense decoder's step recorded on a v5e with the three
scopes in it and XLA's own stats kept, and on hand-built traces.  No test
here starts a traced run."""

import gzip
import os

import pytest

from benchmark import arithmetic, dense_scopes, manifest, scopes, xspace
from horovod_tpu.common import scopes as names

# Hidden 256, 2 heads of 128, FFN 512, vocabulary 1024, two layers, 2 x 256
# tokens: traced on one TPU v5e chip through ``benchmark/run.py`` (PR 36),
# cut by ``python -m benchmark.xspace <in> <out> 3`` to its first three
# steps and the lines the reductions read; gzipped.  Its name does not say
# ``.xplane.pb`` (``test_flash_passes_add_up_to_the_mosaic_time_of_every_
# recording`` takes every file so named).
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-dense-blocks-v5e.xspace.gz")
RECORDED_CONFIG = {"hidden_size": 256, "num_attention_heads": 2,
                   "num_key_value_heads": 2, "head_dim": 128,
                   "intermediate_size": 512, "vocab_size": 1024,
                   "num_hidden_layers": 2}
RECORDED_TOKENS = 2 * 256
DENSE_CELLS = ["ouro-2.6b.train-s8k", "ouro-2.6b.train-s2k",
               "ouro-2.6b.train-s2k-dp4", "ouro-2.6b-ut4.train-s8k"]
DECODER_CELLS = DENSE_CELLS + ["deepseek-v2-lite.train-s4k",
                               "keye-vl-2.0-30b-a3b.train-s8k-b2"]
METRICS = ("block_attn_ms", "block_ffn_ms", "head_ms", "dense_roofline")
PEAKS = manifest.peaks("TPU v5 lite")
V, B = xspace.VARINT, xspace.BYTES
STEP = "jit(hvd_train_step)/"
LOSS = STEP + "hvd.loss/"
FUSION = "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p), kind=kOutput"


# -- hand-built traces -------------------------------------------------------

def _write(path, ops, steps=((0, 100),)):
    """An ``.xplane.pb`` of one chip: ``ops`` are ``(HLO text, op_name or
    None, flops, bytes_accessed, hlo_category, start_us, duration_us)``,
    ``steps`` the executions of the one program, in microseconds."""
    stat_names = {1: "tf_op", 2: "flops", 3: "bytes_accessed",
                  4: "hlo_category"}

    def stat(key, kind, value):
        return (5, B, [(1, V, key), (kind, B if kind == 5 else V, value)])

    metadata, events = [], []
    for key, (text, op_name, flops, nbytes, category, start, duration) in (
            enumerate(ops, start=10)):
        stats = [stat(2, 3, flops), stat(3, 3, nbytes), stat(4, 5, category)]
        if op_name is not None:
            stats.append(stat(1, 5, op_name + ":"))
        metadata.append((4, B, [(1, V, key), (2, B, [
            (1, V, key), (2, B, text), (4, B, "")] + stats)]))
        events.append((4, B, [(1, V, key), (2, V, start * 10 ** 6),
                              (3, V, duration * 10 ** 6)]))
    metadata.append((4, B, [(1, V, 1), (2, B, [
        (1, V, 1), (2, B, "jit_hvd_train_step(1)")])]))
    plane = [(2, B, "/device:TPU:0"),
             (3, B, [(2, B, "XLA Ops"), (3, V, 0)] + events),
             (3, B, [(2, B, "XLA Modules"), (3, V, 0)] + [
                 (4, B, [(1, V, 1), (2, V, start * 10 ** 6),
                         (3, V, (end - start) * 10 ** 6)])
                 for start, end in steps])] + metadata + [
        (5, B, [(1, V, key), (2, B, [(1, V, key), (2, B, text)])])
        for key, text in stat_names.items()]
    path.write_bytes(xspace.encode([(1, B, plane)]))
    return str(path)


def _ctx(config=RECORDED_CONFIG, tokens=RECORDED_TOKENS, chips=1):
    return {"trace": {}, "chips": chips, "peaks": PEAKS,
            "cell": {"config": config},
            "job": {"units_per_step": tokens * chips}}


@pytest.fixture()
def read_from(monkeypatch):
    """Point the readers at a file, as a traced run's would be found."""
    def point(path):
        monkeypatch.setattr(dense_scopes.trace, "find_xplane",
                            lambda trace_dir: path)
        dense_scopes._reduced.clear()
    yield point
    dense_scopes._reduced.clear()


def test_a_container_counts_its_childrens_flops_once_and_none_of_its_own(
        tmp_path):
    """A ``while`` event carries its children's sums: the looped
    recording has one of 1,974 us with 1.88e10 ``flops``."""
    body = LOSS + "jvp(M)/hvd.loop.pass/while/body/layer_0/hvd.block.ffn/mlp/"
    loop = "%while.1 = (s32[], bf16[8,8]{1,0}) while(%t), body=%b"
    path = _write(tmp_path / "w.xplane.pb", [
        (loop, LOSS + "jvp(M)/hvd.loop.pass/while", 3000, 600,
         "while", 10, 50),
        (FUSION, body + "w_gate_up/dot_general", 1000, 200,
         "convolution fusion", 20, 10),
        (FUSION, body + "w_down/dot_general", 2000, 400,
         "convolution fusion", 40, 10)])
    reduced = dense_scopes.partition(dense_scopes.read_ops(path), names)
    ffn = reduced["table"][("ffn", "forward")]
    assert (ffn["flops"], ffn["bytes"], ffn["events"]) == (3000, 600, 2)
    assert ffn["seconds"] == pytest.approx(20e-6)
    # The loop's own 30 us are "other", with no FLOPs beside them.
    other = reduced["table"][("other", "forward")]
    assert other["seconds"] == pytest.approx(30e-6)
    assert other["flops"] == other["bytes"] == 0
    assert sum(c["flops"] for c in reduced["categories"].values()) == 3000
    assert reduced["categories"]["while"]["flops"] == 0
    assert reduced["stray_matmuls"] == 0


@pytest.mark.parametrize("op_name, text, want", [
    (LOSS + "jvp(M)/layer_0/hvd.block.attn/attn/wq/dot_general", FUSION,
     ("attn", "forward")),
    (LOSS + "transpose(jvp(M))/layer_0/hvd.block.ffn/norm_mlp/mul", FUSION,
     ("ffn", "backward")),
    (LOSS + "transpose(jvp(M))/layer_0/checkpoint/rematted_computation/"
     "layer_0/hvd.block.ffn/mlp/w_down/dot_general", FUSION,
     ("ffn", "recomputed")),
    (LOSS + "jvp(hvd.head)/reduce_max", FUSION, ("head", "forward")),
    (LOSS + "jvp(hvd.loop.exit)/while/body/closed_call/transpose(jvp("
     "LlamaModel.head))/hvd.head/lm_head/dot_general", FUSION,
     ("head", "backward")),
    (LOSS + "jvp(M)/tok_emb/take", FUSION, ("other", "forward")),
    ("ragged-dot-none", "%custom-call.1 = bf16[8,8]{1,0} custom-call(%p), "
     "custom_call_target=\"tpu_custom_call\"", ("ffn", "unnamed")),
    # The flash kernel's call is timed beside the blocks, in none.  Not
    # looked at: a collective, the optimizer, and what carries no tf_op
    # (asynchronous copies, some constants).
    (LOSS + "jvp(M)/layer_0/hvd.block.attn/attn/hvd.flash.fwd/pallas_call",
     "%custom-call.2 = bf16[8,8]{1,0} custom-call(%p), "
     "custom_call_target=\"tpu_custom_call\"", ("mosaic", None)),
    (LOSS + "transpose(jvp(M))/layer_0/hvd.block.ffn/psum",
     "%all-reduce.1 = bf16[8,8]{1,0} all-reduce(%p)", (None, None)),
    (STEP + "hvd.optimizer/mul", FUSION, (None, None)),
    ("", "%copy-done.1 = bf16[8,8]{1,0} copy-done(%p)", (None, None)),
])
def test_an_operation_is_in_one_block_or_other_or_left_out(op_name, text,
                                                           want):
    assert dense_scopes.classify(text, op_name, names) == want


def test_an_operation_with_no_tf_op_is_in_no_block(tmp_path):
    copy = "%copy-done.1 = bf16[8,8]{1,0} copy-done(%p)"
    path = _write(tmp_path / "n.xplane.pb", [
        (FUSION, LOSS + "jvp(M)/hvd.head/norm_f/mul", 10, 10,
         "loop fusion", 10, 10),
        (copy, None, 0, 128, "copy-done", 30, 10)])
    ops = dense_scopes.read_ops(path)[0]["ops"]
    assert [name[1] for name, _, _ in ops] == [
        LOSS + "jvp(M)/hvd.head/norm_f/mul", ""]
    reduced = dense_scopes.partition(dense_scopes.read_ops(path), names)
    assert set(reduced["table"]) == {("head", "forward")}
    assert reduced["categories"]["copy-done"]["seconds"] == pytest.approx(
        10e-6)
    assert (("copy-done bf16[8,8]", "-", "-") in reduced["families"])


def test_a_matmul_outside_every_block_takes_the_share_away(tmp_path,
                                                            read_from):
    inside = [
        (FUSION, LOSS + "jvp(M)/layer_0/hvd.block.attn/attn/wq/dot_general",
         100, 10, "convolution fusion", 10, 10),
        (FUSION, LOSS + "jvp(M)/layer_0/hvd.block.ffn/mlp/w_down/dot_general",
         100, 10, "convolution fusion", 30, 10),
        (FUSION, LOSS + "jvp(M)/LlamaModel.head/hvd.head/lm_head/dot_general",
         100, 10, "convolution fusion", 50, 10)]
    read_from(_write(tmp_path / "in.xplane.pb", inside))
    assert 0 < manifest.load_reader("dense_roofline")(_ctx())
    assert manifest.load_reader("block_attn_ms")(_ctx()) == pytest.approx(
        0.01)
    stray = (FUSION, LOSS + "jvp(M)/layer_0/mlp/w_up/dot_general", 100, 10,
             "convolution fusion", 70, 10)
    read_from(_write(tmp_path / "out.xplane.pb", inside + [stray]))
    assert manifest.load_reader("dense_roofline")(_ctx()) is None
    # The blocks' own times stand; "other" holds the stray product.
    assert manifest.load_reader("head_ms")(_ctx()) == pytest.approx(0.01)
    # One elementwise fusion outside the blocks takes nothing away.
    loose = (FUSION, LOSS + "jvp(M)/mul", 1, 10, "loop fusion", 70, 10)
    read_from(_write(tmp_path / "ok.xplane.pb", inside + [loose]))
    assert 0 < manifest.load_reader("dense_roofline")(_ctx())


def test_a_program_without_the_three_names_gives_no_number(
        tmp_path, read_from, monkeypatch, capsys):
    for metric in METRICS:
        assert manifest.load_reader(metric)({"trace": None}) is None
    ops = [(FUSION, LOSS + "jvp(M)/layer_0/attn/wq/dot_general", 100, 10,
            "convolution fusion", 10, 10),
           ("%custom-call.1 = bf16[8,8]{1,0} custom-call(%p), "
            "custom_call_target=\"tpu_custom_call\"", "ragged-dot-none",
            0, 0, "custom-call", 30, 10)]
    # The table has the names, the executable is older (a cache that
    # ignores metadata served it): XLA's ragged-dot calls alone, which are
    # told without the names, make no block.
    read_from(_write(tmp_path / "old.xplane.pb", ops))
    for metric in METRICS:
        assert manifest.load_reader(metric)(_ctx()) is None
    assert "older than the names" in capsys.readouterr().out

    class Parent:                    # the parent's table
        LOSS, RAGGED_DOT_PREFIX = names.LOSS, names.RAGGED_DOT_PREFIX
    monkeypatch.setattr(scopes, "program_scopes", lambda: Parent)
    named = [(FUSION, LOSS + "jvp(M)/layer_0/hvd.block.attn/attn/wq/"
              "dot_general", 100, 10, "convolution fusion", 10, 10)]
    read_from(_write(tmp_path / "parent.xplane.pb", named))
    for metric in METRICS:
        assert manifest.load_reader(metric)(_ctx()) is None
    # The tables that need no name still come (ResNet-50's by-category
    # line is read so).
    reduced = dense_scopes.partition(
        dense_scopes.read_ops(str(tmp_path / "parent.xplane.pb")), Parent)
    assert reduced["table"] == {}
    assert reduced["categories"]["convolution fusion"]["flops"] == 100


def test_the_update_and_whose_product_it_is(tmp_path):
    """A backward matmul whose result holds a weight's float32 copies
    carries that weight's optimizer update; and a product's FLOPs say
    whose it is whatever its root is called, split over the batch or not."""
    tokens = 8192
    cell = manifest.cell("ouro-2.6b.train-s2k")
    products = dense_scopes.whose_products(cell["config"], tokens)
    weights = {flops / 2 / tokens: block for flops, block in products.items()}
    assert weights[4_194_304] == weights[3 * 4_194_304] == "attn"
    assert weights[23_068_672] == weights[11_534_336] == "ffn"
    assert weights[100_663_296] == "head"
    update = ("%fusion.7 = (bf16[2048,5632]{1,0:T(8,128)(2,1)}, "
              "f32[2048,5632]{1,0:T(8,128)}, f32[2048,5632]{1,0:T(8,128)}, "
              "f32[2048,5632]{1,0:T(8,128)}) fusion(bf16[8192,2048]{1,0} "
              "%p, f32[2048,5632]{1,0} %m), kind=kOutput")
    assert dense_scopes.holds_an_update(update)
    assert not dense_scopes.holds_an_update(FUSION)
    assert not dense_scopes.holds_an_update(
        "%fusion.8 = (bf16[8192,2048]{1,0}, f32[8192]{0}, f32[8192]{0}) "
        "fusion(f32[4,4]{1,0} %a, f32[4,4]{1,0} %b), kind=kOutput")
    down = 2 * 11_534_336 * tokens
    bwd = LOSS + "transpose(jvp(M))/layer_0/"
    path = _write(tmp_path / "u.xplane.pb", [
        (update, bwd + "hvd.block.ffn/mlp/w_down/dot_general", down, 10 ** 8,
         "convolution fusion", 10, 10),
        # w_down's forward product, half the batch an event, fused with the
        # next layer's norm_attn: counted in attn, owned by ffn.
        (FUSION, LOSS + "jvp(M)/layer_1/hvd.block.attn/norm_attn/mul",
         down // 2 + 10 ** 6, 10, "convolution fusion", 30, 10),
        (FUSION, LOSS + "jvp(M)/layer_1/hvd.block.attn/attn/wq/dot_general",
         2 * 4_194_304 * tokens, 10, "convolution fusion", 50, 10)])
    reduced = dense_scopes.partition(dense_scopes.read_ops(path), names,
                                     products)
    assert reduced["updates"]["events"] == 1
    assert reduced["updates"]["seconds"] == pytest.approx(10e-6)
    assert reduced["foreign"] == {("attn", "ffn"): pytest.approx(10e-6)}


# -- the configuration's count ------------------------------------------------

def test_the_dense_count_is_the_arithmetics_and_the_issues():
    """27.68 TFLOP of dense products a step and chip: 140.49 ms at the
    peak, 37.67 projections + 77.70 FFN + 25.12 head; four times that
    looped."""
    config = manifest.cell("ouro-2.6b.train-s2k")["config"]
    work = dense_scopes.dense_work(config, 8192)
    layer = arithmetic.decoder_layer_matmul_params(2048, 16, 16, 128, 5632)
    assert layer == 51_380_224
    assert work["all"]["flops"] == 6.0 * 8192 * (9 * layer + 2048 * 49152)
    ms = {block: 1e3 * arithmetic.roofline_seconds(
        w["flops"], w["bytes"], PEAKS)[0] for block, w in work.items()}
    assert [round(ms[b], 2) for b in ("attn", "ffn", "head", "all")] == [
        37.67, 77.70, 25.12, 140.49]
    assert all(arithmetic.roofline_seconds(w["flops"], w["bytes"], PEAKS)[1]
               == "flops" for w in work.values())
    looped = dense_scopes.dense_work(
        manifest.cell("ouro-2.6b-ut4.train-s8k")["config"], 8192)
    assert looped["all"]["flops"] == 4 * work["all"]["flops"]
    # The routed cells' layers are no plain decoder's: no count.
    for cell in DECODER_CELLS[4:]:
        assert dense_scopes.dense_work(manifest.cell(cell)["config"],
                                       8192) is None


# -- the manifest -------------------------------------------------------------

def test_the_four_entries_are_in_the_manifest_as_the_issue_put_them():
    listed = {m["name"]: m for m in manifest.load()["per_layer"]}
    for metric in METRICS[:3]:
        assert listed[metric] == {
            "name": metric, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "model",
            "moves": "step_ms_p90", "workloads": DECODER_CELLS}
    assert listed["dense_roofline"] == {
        "name": "dense_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "model", "moves": "step_ms_p90",
        "workloads": DENSE_CELLS}
    assert [m["name"] for m in manifest.load()["per_layer"]][-4:] == list(
        METRICS)
    for metric in METRICS:
        assert os.path.exists(manifest.metric_path(metric))
    for cell in DECODER_CELLS:
        reported = {m["name"] for m in manifest.cell(cell)["per_layer"]}
        assert set(METRICS[:3]) <= reported
        assert ("dense_roofline" in reported) == (cell in DENSE_CELLS)
    assert not set(METRICS) & {
        m["name"] for m in manifest.cell("resnet50-v1.5.train-b256")[
            "per_layer"]}


# -- the recorded step --------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_recorded_trace_holds_the_three_scopes_and_xlas_own_stats(recorded):
    ops = dense_scopes.read_ops(recorded)[0]["ops"]
    assert len(dense_scopes.read_ops(recorded)) == 1
    held = [{scopes.bare(part) for part in scopes.components(name[1])}
            for name, _, _ in ops]
    everything = set().union(*held)
    assert {names.LOSS, names.BLOCK_ATTN, names.BLOCK_FFN, names.HEAD,
            names.FLASH_FWD, names.FLASH_BWD} <= everything
    for scopes_held in held:
        assert len(scopes_held & {names.BLOCK_ATTN, names.BLOCK_FFN,
                                  names.HEAD}) <= 1
        if scopes_held & {names.FLASH_FWD, names.FLASH_BWD}:
            assert names.BLOCK_ATTN in scopes_held
    # XLA's own numbers are on every event, and on the products they say
    # what the shapes say: 2 x 512 tokens x 256 x 256 and a little more.
    assert all(name[4] for name, _, _ in ops)
    products = [name for name, _, _ in ops
                if name[4] == dense_scopes.MATMUL_CATEGORY]
    assert products and all(name[2] > 0 and name[3] > 0 for name in products)
    wq = [name for name in products if name[1].endswith("/attn/wq/dot_general")
          and "transpose(" not in name[1]]
    assert wq and all(1.0 <= name[2] / (2 * 512 * 256 * 256) < 1.03
                      for name in wq)
    assert os.path.getsize(RECORDED) < 400_000


def test_recorded_step_by_block(recorded, read_from):
    """The blocks and "other" add up to forward + backward less the Mosaic
    calls, every matrix product is in a block, the share is one, and the
    readers give the partition's numbers."""
    reduced = dense_scopes.partition(
        dense_scopes.read_ops(recorded), names,
        dense_scopes.whose_products(RECORDED_CONFIG, RECORDED_TOKENS))
    assert reduced["steps"] == 3 and reduced["stray_matmuls"] == 0
    blocks = {block: dense_scopes.block_ms(reduced, block)
              for block in dense_scopes.BLOCKS + (dense_scopes.OTHER,)}
    assert all(ms > 0 for ms in blocks.values())
    accepted = scopes.partition(scopes.read_events(recorded), names)
    classes, flash = accepted["classes"], accepted["flash"]
    assert sum(blocks.values()) == pytest.approx(
        classes["forward"] + classes["backward"] - flash["fwd"]
        - flash["bwd"], rel=1e-9)
    assert reduced["mosaic_in_loss_s"] * 1e3 / 3 == pytest.approx(
        flash["fwd"] + flash["bwd"], rel=1e-9)
    assert reduced["ragged_s"] == 0
    assert {which for _, which in reduced["table"]} == {"forward", "backward"}
    # Every weight's update rides in its gradient product: 4 projections
    # and 2 FFN matrices a layer, and the head.
    assert reduced["updates"]["events"] == 3 * (2 * 6 + 1)
    # XLA's count of the products is the arithmetic's, a little more.
    counted = sum(cell["flops"] for (block, _), cell
                  in reduced["table"].items()) / 3
    needed = dense_scopes.dense_work(RECORDED_CONFIG, RECORDED_TOKENS)["all"]
    assert 1.0 <= counted / needed["flops"] < 1.05

    read_from(recorded)
    ctx = _ctx()
    for metric, block in zip(METRICS, dense_scopes.BLOCKS):
        assert manifest.load_reader(metric)(ctx) == pytest.approx(
            blocks[block])
    share = manifest.load_reader("dense_roofline")(ctx)
    # (At 512 tokens a step the weights' bytes bind, not the operations.)
    least_s, bound = arithmetic.roofline_seconds(
        needed["flops"], needed["bytes"], PEAKS)
    assert bound == "bytes"
    assert share == pytest.approx(100 * 1e3 * least_s / (
        blocks["attn"] + blocks["ffn"] + blocks["head"]))
    assert 0 < share <= 100
