"""The reduction from a trace to metrics, on a small recorded trace and on
hand-built events, and the analytic operation and byte counts against
hand-worked values."""

import os

import pytest

from benchmark import arithmetic, manifest, trace

# The tiny decoder (hidden 256, one layer, 2 x 256 tokens) traced on a TPU
# v5e by this harness (PR 23), cut to the lines the reduction reads and to
# its first six steps.  A host-bound run: the chip idles most of the time.
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "tiny-decoder-v5e.xplane.pb")
SPANS = ("dispatch", "wait_loss")


@pytest.fixture(scope="module")
def events():
    return trace.read_xplane(RECORDED, SPANS)


def test_recorded_trace_has_the_lines_the_reduction_reads(events):
    assert list(events["devices"]) == [0]
    device = events["devices"][0]
    assert len(device["modules"]) == 6
    assert len(device["ops"]) > 100 and device["async"]
    assert set(events["host"]) == set(SPANS)
    kinds = {trace.op_kind(name) for name, _, _ in device["ops"]}
    assert kinds == {"xla", "mosaic"}


def test_busy_is_the_union_and_idle_the_rest(events):
    device = events["devices"][0]
    reduced = trace.reduce_events(events)
    start = min(s for _, s, _ in device["modules"])
    end = max(e for _, _, e in device["modules"])
    assert reduced["steps"] == 6
    assert reduced["window_s"] == pytest.approx(end - start)
    # Brute force on a grid of nanoseconds: the union, not the sum.
    ops = trace.clip(device["ops"], start, end)
    covered = set()
    for _, s, e in ops:
        covered.update(range(round((s - start) * 1e9),
                             round((e - start) * 1e9)))
    assert reduced["busy_s"] == pytest.approx(len(covered) * 1e-9, rel=1e-3)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    idle = dict(reduced["breakdown"]["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert idle["dispatch"] > idle["other"] and idle["wait_loss"] > 0


def test_per_operation_sums(events):
    device = events["devices"][0]
    reduced = trace.reduce_events(events)
    start = min(s for _, s, _ in device["modules"])
    end = max(e for _, _, e in device["modules"])
    ops = trace.clip(device["ops"], start, end)
    mosaic = [(s, e) for name, s, e in ops if "tpu_custom_call" in name]
    # One layer: the flash kernel's calls, the same in each of six steps.
    assert mosaic and len(mosaic) % 6 == 0
    assert reduced["mosaic_ms_per_step"] == pytest.approx(
        sum(e - s for s, e in mosaic) / 6 * 1e3)
    # No operation of this program nests, so self times add up to the sum.
    assert (reduced["mosaic_ms_per_step"] + reduced["xla_ms_per_step"]
            ) == pytest.approx(sum(e - s for _, s, e in ops) / 6 * 1e3)
    names = [name for name, _ in reduced["breakdown"]["device_ops"]]
    assert "custom-call bf16[4,256,128]" in names and len(names) <= 10
    assert reduced["collective_ms_per_step"] == 0


def test_hlo_names_are_read():
    fusion = ("%fusion.12 = bf16[2048,5632]{1,0:T(8,128)(2,1)S(1)} fusion("
              "bf16[8192,2048]{1,0:T(8,128)(2,1)} %x), kind=kOutput")
    flash = ('%attn.3 = (bf16[16,8192,128]{2,1,0:T(8,128)(2,1)}, f32[16,8,'
             '8192]{2,1,0}) custom-call(bf16[16,8192,128] %q), '
             'custom_call_target="tpu_custom_call"')
    concat = ('%custom-call.2 = bf16[2048,2048]{1,0} custom-call(bf16[512,'
              '2048] %a), custom_call_target="ConcatBitcast"')
    reduce_ = ("%all-reduce-start.1 = f32[1024]{0} all-reduce-start(f32["
               "1024]{0} %g), replica_groups={{0,1,2,3}}")
    assert trace.opcode(fusion) == "fusion"
    assert trace.family(fusion) == "fusion bf16[2048,5632]"
    assert trace.family(flash) == "custom-call bf16[16,8192,128]"
    assert [trace.op_kind(n) for n in (fusion, flash, concat, reduce_)] == [
        "xla", "mosaic", "xla", "collective"]
    assert trace.op_kind("all-reduce.7") == "collective"
    assert trace.op_kind("custom-call.7") == "mosaic"


def test_exposed_collective_time_on_a_hand_built_overlap():
    """One chip, two steps of 10 s.  In each: compute 0-4, an asynchronous
    all-reduce 3-8 (the TensorCore waits in its -done 6-8), compute 4-6 and
    8-10.  The collective runs 5 s a step, 2 of them exposed."""
    ops, asyncs, modules = [], [], []
    for base in (0.0, 10.0):
        modules.append(("jit_step(1)", base, base + 10))
        ops += [("fusion.1", base, base + 4),
                ("all-reduce-start.1", base + 3, base + 3.000001),
                ("fusion.2", base + 4, base + 6),
                ("all-reduce-done.1", base + 6, base + 8),
                ("custom-call.1", base + 8, base + 10)]
        asyncs.append(("all-reduce-start.1", base + 3, base + 8))
    modules.append(("jit_other(2)", 20.0, 21.0))    # a shorter program
    host = {"wait_loss": [("wait_loss", 0.0, 20.0)]}
    reduced = trace.reduce_events(
        {"devices": {0: {"ops": ops, "async": asyncs, "modules": modules}},
         "host": host})
    assert reduced["steps"] == 2 and reduced["window_s"] == 20
    assert reduced["exposed_collective_ms_per_step"] == pytest.approx(2e3)
    assert reduced["xla_ms_per_step"] == pytest.approx(6e3)
    assert reduced["mosaic_ms_per_step"] == pytest.approx(2e3)
    # Waiting inside a collective is not idling: the device's idle share
    # is what the host leaves it without work.
    assert reduced["busy_s"] == pytest.approx(20)
    # Two chips are averaged: a second chip with no collective halves it.
    quiet = {"ops": [("fusion.1", 0.0, 10.0), ("fusion.1", 10.0, 20.0)],
             "async": [], "modules": modules[:2]}
    both = trace.reduce_events(
        {"devices": {0: {"ops": ops, "async": asyncs, "modules": modules},
                     1: quiet}, "host": host})
    assert both["chips"] == 2
    assert both["exposed_collective_ms_per_step"] == pytest.approx(1e3)


def test_interval_arithmetic_and_self_times():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [[0, 3], [5, 6]]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [[0, 2], [3, 5]]
    assert trace.overlap([(0, 10)], [(2, 3), (5, 12)]) == 6
    nested = [("while.1", 0, 10), ("fusion.1", 1, 4), ("fusion.2", 4, 9)]
    assert dict(trace.self_times(nested)) == {
        "while.1": 2, "fusion.1": 3, "fusion.2": 5}


def test_collectives_in_hlo_text():
    text = """
  %all-reduce.1 = f32[1024,256]{1,0} all-reduce(f32[1024,256]{1,0} %a), to_apply=%add
  %all-reduce-start.2 = (bf16[4096]{0}, bf16[4096]{0}) all-reduce-start(bf16[4096]{0} %b), to_apply=%add
  %all-reduce-done.2 = bf16[4096]{0} all-reduce-done((bf16[4096]{0}, bf16[4096]{0}) %all-reduce-start.2)
  %all-reduce.4 = (bf16[19922944]{0:T(1024)(128)(2,1)}, bf16[2048]{0:T(1024)(128)(2,1)S(1)}) all-reduce(%x, %y), channel_id=1
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %c), kind=kLoop
"""
    found = trace.collectives_in_hlo(text)
    assert found["ops"] == {"all-reduce": 3}
    assert found["bytes"] == (1024 * 256 * 4 + 4096 * 2
                              + (19922944 + 2048) * 2)


# -- analytic operations and bytes, against hand-worked values ---------------

def test_one_decoder_layer_by_hand():
    # q, k, v, o: 4 x 2048 x 2048; gate, up, down: 3 x 2048 x 5632.
    assert arithmetic.decoder_layer_matmul_params(
        2048, 16, 16, 128, 5632) == 4 * 2048 * 2048 + 3 * 2048 * 5632
    # One layer, no head (vocab 0), 4 positions: the mean query sees 2.5
    # keys; 2 products x 2 x 2048 operations a pair.
    per_token = arithmetic.decoder_train_flops_per_token(
        hidden=2048, layers=1, heads=16, kv_heads=16, head_dim=128, ffn=5632,
        vocab=0, seq=4)
    assert per_token == 3 * (2 * 51380224 + 2 * 2 * 2048 * 2.5)
    # GQA shrinks k and v only.
    assert arithmetic.decoder_layer_matmul_params(
        2048, 16, 4, 128, 5632) == (2 * 2048 * 2048 + 2 * 2048 * 512
                                    + 3 * 2048 * 5632)


def test_one_flash_call_by_hand():
    # One head of 128, 8192 positions: 8192 * 8193 / 2 = 33,558,528 pairs;
    # seven products of 2 * 128 operations a pair.
    assert arithmetic.causal_pairs(8192) == 33558528
    assert arithmetic.flash_step_flops(
        batch=1, seq=8192, heads=1, head_dim=128) == 7 * 256 * 33558528
    # Twelve tensors of 8192 x 128 bf16 cross HBM.
    assert arithmetic.flash_step_bytes(
        batch=1, seq=8192, heads=1, head_dim=128) == 12 * 8192 * 128 * 2
    peaks = manifest.peaks("TPU v5 lite")
    seconds, bound = arithmetic.roofline_seconds(
        arithmetic.flash_step_flops(batch=1, seq=8192, heads=16,
                                    head_dim=128),
        arithmetic.flash_step_bytes(batch=1, seq=8192, heads=16,
                                    head_dim=128), peaks)
    assert bound == "flops"
    assert seconds == pytest.approx(16 * 7 * 256 * 33558528 / 197e12)
    assert arithmetic.roofline_seconds(1.0, 1e6, peaks)[1] == "bytes"


SHAPES = [dict(batch=1, seq=8192, heads=16, head_dim=128),
          dict(batch=4, seq=2048, heads=16, head_dim=128),
          dict(batch=3, seq=7, heads=2, head_dim=64)]


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"s{s['seq']}")
def test_flash_passes_add_up_to_the_step_and_split_2_to_5_and_4_to_8(shape):
    """Forward: QK^T and PV, q k v read and o written.  Backward: five
    products, q k v o dO read and dq dk dv written.  The step's counts,
    which ``flash_roofline`` has read since PR 23, are their sums."""
    forward = arithmetic.flash_forward_flops(**shape)
    backward = arithmetic.flash_backward_flops(**shape)
    assert forward + backward == arithmetic.flash_step_flops(**shape)
    assert 5 * forward == 2 * backward > 0
    pairs = shape["batch"] * shape["heads"] * arithmetic.causal_pairs(
        shape["seq"])
    assert forward == 2 * 2 * shape["head_dim"] * pairs
    moved = arithmetic.flash_forward_bytes(**shape)
    moved_back = arithmetic.flash_backward_bytes(**shape)
    assert moved + moved_back == arithmetic.flash_step_bytes(**shape)
    assert 8 * moved == 4 * moved_back > 0
    tensor = (shape["batch"] * shape["seq"] * shape["heads"]
              * shape["head_dim"])
    assert moved == 4 * tensor * 2
    assert arithmetic.flash_backward_bytes(**shape, itemsize=4) == (
        8 * tensor * 4)


def test_each_flash_pass_is_bound_by_operations_at_the_cells_shapes():
    """At 8k and at 2k both passes are ``flops`` bound on a v5e, so a
    pass's share of its roofline is its share of the MXU's peak on the
    products counted: 12.56 and 31.40 ms a step of nine layers at 8k."""
    peaks = manifest.peaks("TPU v5 lite")
    for shape in SHAPES[:2]:
        for flops, nbytes in ((arithmetic.flash_forward_flops,
                               arithmetic.flash_forward_bytes),
                              (arithmetic.flash_backward_flops,
                               arithmetic.flash_backward_bytes)):
            assert arithmetic.roofline_seconds(
                flops(**shape), nbytes(**shape), peaks)[1] == "flops"
    least = [9e3 * arithmetic.roofline_seconds(
        flops(**SHAPES[0]), 1.0, peaks)[0]
        for flops in (arithmetic.flash_forward_flops,
                      arithmetic.flash_backward_flops)]
    assert least == pytest.approx([12.56, 31.40], abs=0.005)


def test_one_bottleneck_block_by_hand():
    # First block of stage 1: 56 x 56 x 64 in, 64 filters, stride 1, with
    # a projection: 1x1 64->64, 3x3 64->64, 1x1 64->256, 1x1 64->256.
    hw = 56 * 56
    assert arithmetic.bottleneck_macs(56, 64, 64, 1) == hw * (
        64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    # First block of stage 2: 56 x 56 x 256 in, 128 filters, stride 2 on
    # the 3x3 (v1.5): the 1x1 reduce runs at 56 x 56, the rest at 28 x 28.
    assert arithmetic.bottleneck_macs(56, 256, 128, 2) == (
        hw * 256 * 128 + 28 * 28 * (9 * 128 * 128 + 128 * 512 + 256 * 512))
    # The whole network: 4.09 GMACs forward, the figure v1.5 is quoted at.
    per_image = arithmetic.resnet_train_flops_per_image(image=224,
                                                        classes=1000)
    assert per_image / 6 == pytest.approx(4.09e9, rel=0.005)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        manifest.peaks("TPU v0 imaginary")
