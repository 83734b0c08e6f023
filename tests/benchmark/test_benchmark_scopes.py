"""The readers of the program's own scopes and compile log: the raw
``XSpace`` decoder on hand-built bytes, the partition of a step into
forward, backward, optimizer, packing, collective and unscoped on
hand-built events and on a small trace recorded on four v5e chips with its
stat metadata kept, and the metric readers on a CPU's context."""

import gzip
import os
import struct

import pytest

from benchmark import manifest, scopes, trace, xspace
from horovod_tpu.common import scopes as names

# A small decoder (hidden 256, 2 heads x 128, one layer, 2 x 256 tokens a
# chip, data=4) traced on four TPU v5e chips by this harness (PR 24), cut
# by ``python -m benchmark.xspace`` to its first three steps and to the
# lines the reductions read; gzipped (HLO texts repeat).
RECORDED = os.path.join(manifest.HERE, "testdata",
                        "small-decoder-dp4-v5e.xplane.pb.gz")
V, B = xspace.VARINT, xspace.BYTES


# -- the wire format ---------------------------------------------------------

def test_fields_and_encode_are_inverses():
    message = [(1, V, 0), (1, V, 300), (2, B, b"abc"), (3, xspace.FIXED64, 7),
               (4, xspace.FIXED32, 9), (16, V, 2 ** 63 + 5), (2, B, b"")]
    raw = xspace.encode(message)
    assert raw[:2] == b"\x08\x00" and raw[2:5] == b"\x08\xac\x02"
    assert xspace.fields(raw) == message
    nested = xspace.encode([(1, B, [(2, B, "name"), (3, V, 1)])])
    assert xspace.fields(xspace.fields(nested)[0][2]) == [
        (2, B, b"name"), (3, V, 1)]
    assert xspace.encode([(1, V, -1)]) == b"\x08" + b"\xff" * 9 + b"\x01"


@pytest.mark.parametrize("raw", [b"\x0a\x05ab", b"\x08\x80", b"\x0b\x00"])
def test_fields_refuses_what_is_no_message(raw):
    with pytest.raises(ValueError):
        xspace.fields(raw)


def _stat(meta_id, **value):
    (kind, v), = value.items()
    number, wire = {"double": (2, xspace.FIXED64), "uint": (3, V),
                    "int": (4, V), "text": (5, B), "ref": (7, V)}[kind]
    if kind == "double":
        v = int.from_bytes(struct.pack("<d", v), "little")
    return (number, wire, v), [(1, V, meta_id), (number, wire, v)]


def _entry(number, key, value):
    return (number, B, [(1, V, key), (2, B, value)])


def _plane(name, stat_names, metadata, lines):
    """metadata: {id: (name, display, [stat message])}; lines: {name:
    (timestamp_ns, [(metadata id, offset_ps, duration_ps, [stat])])}."""
    plane = [(2, B, name)]
    for line_name, (origin, events) in lines.items():
        plane.append((3, B, [(2, B, line_name), (3, V, origin)] + [
            (4, B, [(1, V, m), (2, V, offset), (3, V, duration)]
             + [(4, B, stat) for stat in stats])
            for m, offset, duration, stats in events]))
    for key, (text, display, stats) in metadata.items():
        plane.append(_entry(4, key, [(1, V, key), (2, B, text),
                                     (4, B, display)]
                            + [(5, B, stat) for stat in stats]))
    for key, text in stat_names.items():
        plane.append(_entry(5, key, [(1, V, key), (2, B, text)]))
    return (1, B, plane)


@pytest.fixture()
def hand_built(tmp_path):
    stat_names = {1: "tf_op", 2: "flops", 3: "device_offset_ps", 4: "share",
                  5: "hlo_category", 6: "loop fusion", 7: "source_stack"}
    op = "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop"
    metadata = {
        7: (op, "fusion.1", [
            _stat(1, text=b"jit(hvd_train_step)/hvd.optimizer/mul:")[1],
            _stat(2, uint=4096)[1], _stat(4, double=0.5)[1],
            _stat(5, ref=6)[1], _stat(7, text=b"a.py:1\nb.py:2")[1]]),
        8: ("jit_hvd_train_step(1)", "", []),
        9: ("%unused = f32[] constant(0)", "unused", []),
    }
    lines = {
        "XLA Ops": (1000, [
            (7, 5000, 2000, [_stat(3, int=-3)[1]]),
            (7, 2_000_000, 1000, []),
            (7, 9_000_000, 1000, [])]),
        "XLA Modules": (1000, [(8, 0, 1_000_000, []),
                               (8, 1_500_000, 1_000_000, []),
                               (8, 8_000_000, 2_000_000, [])]),
        "Steps": (1000, [(8, 0, 10, [])]),
    }
    host = _plane("/host:CPU", {}, {1: ("dispatch", "", []),
                                    2: ("other", "", [])},
                  {"python3": (1000, [(1, 100_000, 50_000, []),
                                      (2, 200_000, 50_000, []),
                                      (1, 9_000_000, 50_000, [])])})
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(xspace.encode([
        _plane("/device:TPU:0", stat_names, metadata, lines), host,
        (4, B, b"hostname")]))
    return str(path)


def test_read_planes_lays_metadata_stats_under_the_events(hand_built):
    device, host = xspace.read_planes(hand_built)
    assert device["name"] == "/device:TPU:0" and host["name"] == "/host:CPU"
    assert set(device["lines"]) == {"XLA Ops", "XLA Modules", "Steps"}
    first = device["lines"]["XLA Ops"][0]
    assert first["name"].startswith("%fusion.1 = bf16[8]")
    assert first["display_name"] == "fusion.1"
    assert first["start_s"] == pytest.approx(1000e-9 + 5000e-12, abs=1e-15)
    assert first["end_s"] - first["start_s"] == pytest.approx(2000e-12)
    assert first["stats"] == {
        "tf_op": "jit(hvd_train_step)/hvd.optimizer/mul:", "flops": 4096,
        "share": 0.5, "hlo_category": "loop fusion",
        "source_stack": "a.py:1\nb.py:2", "device_offset_ps": -3}
    assert "device_offset_ps" not in device["lines"]["XLA Ops"][1]["stats"]
    only = xspace.read_planes(hand_built, want_line="XLA Ops".__eq__)
    assert [set(p["lines"]) for p in only] == [{"XLA Ops"}, set()]


def test_the_raw_reader_agrees_with_jax_on_a_recorded_trace():
    """Names and times of the PR 23 recording, which JAX's ProfileData
    reads as well."""
    path = os.path.join(manifest.HERE, "testdata",
                        "tiny-decoder-v5e.xplane.pb")
    by_jax = trace.read_xplane(path)["devices"][0]
    device, = [p for p in xspace.read_planes(path)
               if trace.DEVICE_PLANE.match(p["name"])]
    for line, key in trace.LINES.items():
        raw = device["lines"][line]
        assert len(raw) == len(by_jax[key]) > 0
        for event, (name, start, end) in zip(raw, by_jax[key]):
            assert event["name"] == name
            assert event["start_s"] == pytest.approx(start, abs=1e-9)
            assert event["end_s"] == pytest.approx(end, abs=1e-9)


def test_trim_keeps_whole_steps_and_the_metadata_they_use(hand_built,
                                                          tmp_path):
    out = str(tmp_path / "cut.xplane.pb")
    xspace.trim(hand_built, out, 2, keep_lines={"XLA Ops", "XLA Modules"},
                module_line="XLA Modules", host_events=("dispatch",),
                drop_stats=("source_stack",))
    device, host = xspace.read_planes(out)
    assert set(device["lines"]) == {"XLA Ops", "XLA Modules"}
    assert len(device["lines"]["XLA Modules"]) == 2
    ops = device["lines"]["XLA Ops"]
    assert len(ops) == 2                     # the third ran in step three
    assert ops[0]["stats"]["tf_op"].endswith("hvd.optimizer/mul:")
    assert "source_stack" not in ops[0]["stats"]
    assert ops[0]["stats"]["hlo_category"] == "loop fusion"
    assert [e["name"] for e in host["lines"]["python3"]] == ["dispatch"]
    assert b"unused" not in open(out, "rb").read()
    with pytest.raises(ValueError):
        xspace.trim(hand_built, out, 2, keep_lines=set(),
                    module_line="No Such Line")


# -- one operation -----------------------------------------------------------

def test_components_split_outside_brackets_only():
    assert scopes.components("jit(f)/hvd.loss/transpose(jvp(a/b))/c/mul") \
        == ["jit(f)", "hvd.loss", "transpose(jvp(a/b))", "c", "mul"]
    assert scopes.components("") == [""]
    assert scopes.bare("transpose(jvp(LlamaModel))") == "LlamaModel"
    assert scopes.bare("jvp(hvd.flash.fwd)") == names.FLASH_FWD
    assert scopes.bare("jvp()") == "" and scopes.bare("mul") == "mul"


FUSION = "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop"
MOSAIC = ('%attn.3 = bf16[4,256,128]{2,1,0} custom-call(bf16[4,256,128] %q),'
          ' custom_call_target="tpu_custom_call"')
ALL_REDUCE = "%all-reduce.1 = bf16[64]{0} all-reduce(bf16[64]{0} %g)"
STEP = "jit(hvd_train_step)/"


@pytest.mark.parametrize("name, op_name, kind", [
    (FUSION, STEP + "hvd.loss/jvp(LlamaModel)/layer_0/mlp/mul", "forward"),
    (FUSION, STEP + "hvd.loss/jvp()/tanh", "forward"),
    (FUSION, STEP + "shard_map/hvd.loss/transpose(jvp(LlamaModel))/"
     "layer_0/mlp/w_up/dot_general", "backward"),
    (FUSION, STEP + "hvd.loss/transpose(jvp())/mul", "backward"),
    (FUSION, STEP + "hvd.loss/transpose(hvd.loss)/jvp(hvd.flash.dq)/while",
     "backward"),
    (FUSION, STEP + "transpose(jvp(hvd.loss))/mul", "backward"),
    (MOSAIC, STEP + "hvd.loss/jvp(LlamaModel)/attn/hvd.flash.fwd/"
     "pallas_call", "forward"),
    (MOSAIC, STEP + "hvd.loss/transpose(jvp(LlamaModel))/attn/"
     "hvd.flash.dkv/pallas_call", "backward"),
    # A user's module that is called "transpose" is no backward pass.
    (FUSION, STEP + "hvd.loss/jvp(Model)/transpose/mul", "forward"),
    (FUSION, STEP + "hvd.optimizer/mul", "optimizer"),
    (FUSION, STEP + "hvd.apply/add", "optimizer"),
    (FUSION, STEP + "hvd.fusion.pack/concatenate", "packing"),
    (FUSION, STEP + "hvd.fusion.unpack/slice", "packing"),
    # An update that XLA fused into the unpacking slice: its root's name.
    (FUSION, STEP + "hvd.fusion.unpack/hvd.optimizer/mul", "packing"),
    (ALL_REDUCE, STEP + "hvd.allreduce.data/psum", "collective"),
    (ALL_REDUCE, "", "collective"),
    # pmean's division: the collective layer's work, and no collective.
    (FUSION, STEP + "hvd.allreduce.data/div", "packing"),
    (FUSION, STEP + "hvd.aux_allreduce/hvd.allreduce.data/div", "packing"),
    (FUSION, STEP + "hvd.loss/jvp(M)/hvd.allreduce.data/div", "forward"),
    (FUSION, "", "unscoped"),
    (FUSION, STEP + "not.hvd.loss/mul", "unscoped"),
])
def test_classify(name, op_name, kind):
    assert scopes.classify(name, op_name, names) == kind


def test_flash_calls_and_collective_axes_by_scope():
    fwd = STEP + "hvd.loss/jvp(M)/attn/hvd.flash.fwd/pallas_call"
    assert scopes.flash_call(MOSAIC, fwd, names) == ("fwd", "fwd")
    assert scopes.flash_call(
        MOSAIC, STEP + "hvd.loss/transpose(hvd.loss)/jvp(hvd.flash.dq)/"
        "pallas_call", names) == ("bwd", "dq")
    # A backward call under a name this reader has never seen.
    assert scopes.flash_call(
        MOSAIC, STEP + "hvd.loss/transpose(jvp(M))/attn/hvd.flash.anything/"
        "pallas_call", names) == ("bwd", "anything")
    # Not the kernel's: another prefix, and the prefix without its dot.
    for other in ("other/pallas_call", "hvd.flashy.fwd/pallas_call",
                  "hvd.flash/pallas_call", "hvd.loss/pallas_call"):
        assert scopes.flash_call(MOSAIC, STEP + other, names) is None
    assert scopes.flash_call(FUSION, fwd, names) is None
    assert scopes.collective_axes(
        STEP + "hvd.allreduce.data+fsdp/psum", names) == "data+fsdp"
    assert scopes.collective_axes(STEP + "psum", names) is None


# -- the flash kernel by pass, on hand-built events --------------------------

BACKWARD = STEP + "hvd.loss/transpose(jvp(M))/layer_0/attn/"


def _flash_step(backward_calls, extra=()):
    """One step of 10 ms on one chip: a forward call of 1 ms, then the
    backward pass's Mosaic calls ``(scope, ms)`` back to back from 4 ms,
    then whatever ``extra`` ``(op_name, ms)`` holds from 8 ms."""
    ops = [((FUSION, STEP + "hvd.loss/jvp(M)/mul"), 0.0, _ms(2)),
           ((MOSAIC, STEP + "hvd.loss/jvp(M)/layer_0/attn/hvd.flash.fwd/"
             "pallas_call"), _ms(2), _ms(3))]
    at = 4.0
    for scope, ms in backward_calls:
        ops.append(((MOSAIC, BACKWARD + scope + "/pallas_call"),
                    _ms(at), _ms(at + ms)))
        at += ms
    at = 8.0
    for op_name, ms in extra:
        ops.append(((MOSAIC, STEP + op_name), _ms(at), _ms(at + ms)))
        at += ms
    return scopes.partition({"devices": {0: {
        "ops": ops, "modules": [("jit_hvd_train_step(1)", 0.0, _ms(10))]}}},
        names)


def test_a_backward_pass_of_one_call_reads_as_one_of_two():
    """However the program splits its backward pass, and whatever it names
    the calls, ``bwd`` is the time under the kernel's scopes but ``fwd``."""
    two = _flash_step([("hvd.flash.dq", 1.25), ("hvd.flash.dkv", 1.75)])
    one = _flash_step([("hvd.flash.anything", 3.0)])
    assert two["flash"] == pytest.approx({"fwd": 1.0, "bwd": 3.0})
    assert one["flash"] == pytest.approx(two["flash"])
    assert two["flash_scopes"] == pytest.approx(
        {"fwd": 1.0, "dq": 1.25, "dkv": 1.75})
    assert one["flash_scopes"] == pytest.approx(
        {"fwd": 1.0, "anything": 3.0})
    assert one["classes"] == pytest.approx(two["classes"])


def test_a_forward_call_repeated_in_the_backward_pass_is_forward():
    """A recomputation policy that does not keep the flash output runs the
    forward call again inside ``transpose(...)``: backward by class, and
    the kernel's forward pass still."""
    again = ("hvd.loss/transpose(jvp(M))/checkpoint/rematted_computation/"
             "layer_0/attn/hvd.flash.fwd/pallas_call")
    reduced = _flash_step([("hvd.flash.dq", 1.0), ("hvd.flash.dkv", 2.0)],
                          extra=[(again, 1.0)])
    assert reduced["flash"] == pytest.approx({"fwd": 2.0, "bwd": 3.0})
    assert reduced["classes"]["backward"] == pytest.approx(3.0 + 1.0)
    assert reduced["classes"]["forward"] == pytest.approx(2.0 + 1.0)


def test_a_mosaic_call_under_no_flash_scope_is_in_neither_pass():
    reduced = _flash_step(
        [("hvd.flash.dkv", 2.0)],
        extra=[("hvd.loss/transpose(jvp(M))/mlp/grouped_matmul/pallas_call",
                1.5), ("pallas_call", 0.25)])
    assert reduced["flash"] == pytest.approx({"fwd": 1.0, "bwd": 2.0})
    assert sum(reduced["flash_scopes"].values()) == pytest.approx(3.0)
    # The plain reduction's Mosaic time holds them all the same.
    assert reduced["classes"]["backward"] == pytest.approx(2.0 + 1.5)
    assert reduced["classes"]["unscoped"] == pytest.approx(0.25)


# -- the partition, on hand-built events -------------------------------------

def _ms(seconds):
    return seconds * 1e-3


def test_partition_on_hand_built_events():
    """Two steps of 10 ms on one chip.  A ``while`` of the backward pass
    spans two operations of its body; a fusion across the boundary counts
    where its root's name puts it; one operation is under no scope; a
    collective is one whatever its scope; an operation outside the steps
    is not counted."""
    ops = []
    for t in (0.0, _ms(10)):
        ops += [
            ((FUSION, STEP + "hvd.loss/jvp(M)/mul"), t, t + _ms(2)),
            ((MOSAIC, STEP + "hvd.loss/jvp(M)/hvd.flash.fwd/pallas_call"),
             t + _ms(2), t + _ms(3)),
            (("%while.1 = (s32[]) while((s32[]) %t), body=%b",
              STEP + "hvd.loss/transpose(jvp(M))/while"),
             t + _ms(3), t + _ms(6)),
            ((FUSION, STEP + "hvd.loss/transpose(jvp(M))/while/body/mul"),
             t + _ms(3), t + _ms(4)),
            ((MOSAIC, STEP + "hvd.loss/transpose(jvp(M))/while/body/"
              "hvd.flash.dkv/pallas_call"), t + _ms(4), t + _ms(5.5)),
            ((ALL_REDUCE, STEP + "hvd.allreduce.data/psum"),
             t + _ms(6), t + _ms(7)),
            # The weight-gradient matmul with its update fused in.
            ((FUSION, STEP + "hvd.loss/transpose(jvp(M))/dot_general"),
             t + _ms(7), t + _ms(8)),
            ((FUSION, STEP + "hvd.apply/add"), t + _ms(8), t + _ms(8.5)),
            ((FUSION, STEP + "hvd.fusion.unpack/slice"),
             t + _ms(8.5), t + _ms(8.75)),
            (("%copy.5 = bf16[8]{0} copy(bf16[8]{0} %p)", ""),
             t + _ms(9), t + _ms(9.25)),
        ]
    ops.append(((FUSION, STEP + "hvd.optimizer/mul"), _ms(25), _ms(26)))
    modules = [("jit_hvd_train_step(1)", 0.0, _ms(10)),
               ("jit_hvd_train_step(1)", _ms(10), _ms(20)),
               ("jit_other(2)", _ms(25), _ms(26))]
    events = {"devices": {0: {"ops": ops, "modules": modules},
                          1: {"ops": [], "modules": []}}}
    reduced = scopes.partition(events, names)
    assert reduced["classes"] == pytest.approx({
        "forward": 3.0, "backward": 0.5 + 1.0 + 1.5 + 1.0,
        "optimizer": 0.5, "packing": 0.25, "collective": 1.0,
        "unscoped": 0.25})
    assert reduced["flash"] == pytest.approx({"fwd": 1.0, "bwd": 1.5})
    assert reduced["flash_scopes"] == pytest.approx({"fwd": 1.0, "dkv": 1.5})
    assert reduced["collective_axes"] == pytest.approx({"data": 1.0})
    assert reduced["unscoped"] == [["copy bf16[8]", pytest.approx(0.25)]]
    # The same sum as the reduction that knows no scopes.
    plain = trace.reduce_events({"devices": {0: {
        "ops": [(name, s, e) for (name, _), s, e in ops],
        "modules": modules}}, "host": {}})
    assert sum(reduced["classes"].values()) == pytest.approx(
        plain["mosaic_ms_per_step"] + plain["xla_ms_per_step"]
        + plain["collective_ms_per_step"])
    assert sum(reduced["flash"].values()) == pytest.approx(
        plain["mosaic_ms_per_step"])


def test_a_program_without_scopes_gives_no_number():
    ops = [((FUSION, "jit(_sharded_step)/jvp(M)/mul"), 0.0, 1.0),
           ((ALL_REDUCE, "jit(_sharded_step)/psum"), 1.0, 2.0)]
    events = {"devices": {0: {"ops": ops, "modules": [("m", 0.0, 2.0)]}}}
    assert scopes.partition(events, names) is None
    assert scopes.partition({"devices": {}}, names) is None


# -- the recorded trace ------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_recorded_trace_holds_the_scopes(recorded):
    events = scopes.read_events(recorded)
    assert sorted(events["devices"]) == [0, 1, 2, 3]
    op_names = {op_name for device in events["devices"].values()
                for (_, op_name), _, _ in device["ops"]}
    assert all(n.startswith(f"jit({names.TRAIN_STEP_PROGRAM})/")
               for n in op_names if n)
    held = {scopes.bare(part) for n in op_names
            for part in scopes.components(n)}
    assert {names.LOSS, names.FUSION_PACK, names.FUSION_UNPACK,
            names.APPLY, names.FLASH_FWD,
            names.allreduce_scope("data")} <= held
    # And a backward pass of the flash kernel's, under whatever names.
    assert {scope for scope in held if scope.startswith("hvd.flash.")
            } - {names.FLASH_FWD}
    assert os.path.getsize(RECORDED) < 400_000


def _plain_reduction(events):
    """The reduction that knows no scopes, on the same events."""
    return trace.reduce_events({"host": {}, "devices": {
        n: {"ops": [(name, s, e) for (name, _), s, e in device["ops"]],
            "modules": device["modules"]}
        for n, device in events["devices"].items()}})


def test_recorded_partition_adds_up_to_the_plain_reduction(recorded):
    """Forward + backward + optimizer + packing + unscoped + collective is
    the existing reduction's mosaic + xla + collective, and the flash
    kernel's two passes its mosaic.  To a part in a million on the same
    events; to 0.2 % against ``trace.read_xplane``, because JAX's ProfileData cuts
    every start and duration to whole nanoseconds where the file has
    picoseconds, and this step's operations take a few nanoseconds each."""
    events = scopes.read_events(recorded)
    reduced = scopes.partition(events, names)
    classes = reduced["classes"]
    assert set(classes) == set(scopes.CLASSES)
    assert all(ms > 0 for ms in classes.values())
    same_events = _plain_reduction(events)
    for plain, rel in ((same_events, 1e-6),
                       (trace.reduce_trace(recorded), 2e-3)):
        assert plain["chips"] == 4 and plain["steps"] == 3
        assert sum(classes.values()) == pytest.approx(
            plain["mosaic_ms_per_step"] + plain["xla_ms_per_step"]
            + plain["collective_ms_per_step"], rel=rel)
        assert classes["collective"] == pytest.approx(
            plain["collective_ms_per_step"], rel=rel)
        assert sum(reduced["flash"].values()) == pytest.approx(
            plain["mosaic_ms_per_step"], rel=rel)
    assert set(reduced["flash"]) == {"fwd", "bwd"}
    by_scope = reduced["flash_scopes"]
    assert reduced["flash"]["fwd"] == pytest.approx(by_scope["fwd"])
    assert reduced["flash"]["bwd"] == pytest.approx(
        sum(ms for scope, ms in by_scope.items() if scope != "fwd"))
    assert reduced["flash"]["bwd"] > reduced["flash"]["fwd"] > 0
    assert set(reduced["collective_axes"]) == {"data"}
    assert reduced["collective_axes"]["data"] == pytest.approx(
        classes["collective"])
    assert classes["backward"] > classes["forward"]


# -- the readers -------------------------------------------------------------

NEW_TRACE_METRICS = ["forward_ms", "backward_ms", "optimizer_ms",
                     "fusion_pack_ms", "unscoped_ms", "flash_fwd_ms",
                     "flash_bwd_ms"]
FLASH_SHARES = ["flash_fwd_roofline", "flash_bwd_roofline"]
FLASH_METRICS = ["flash_ms", "flash_roofline", "flash_fwd_ms",
                 "flash_bwd_ms"] + FLASH_SHARES
NEW_LOG_METRICS = ["step_trace_ms", "step_lower_ms", "step_backend_ms"]


def _cells_whose_job_reports_flash_work(bench):
    found = []
    for workload in bench["workloads"]:
        cell = manifest.cell(workload["name"], bench)
        job = manifest.load_job(cell["config"]["job"]).build(
            cell["config"], cell["traffic"], cell["chips"])
        if "flash" in job.kernel_work_per_step():
            found.append(workload["name"])
    return found


def test_new_metrics_are_in_the_manifest_as_the_issue_put_them():
    bench = manifest.load()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_TRACE_METRICS:
        assert per_layer[name]["source"] == "program_span"
        assert per_layer[name]["moves"] == "step_ms_p90"
        assert per_layer[name]["unit"] == "ms"
    for name in FLASH_SHARES:
        assert per_layer[name]["source"] == "device_trace"
        assert per_layer[name]["moves"] == "step_ms_p90"
        assert (per_layer[name]["unit"], per_layer[name]["better"]) == (
            "%", "higher")
    for name in NEW_LOG_METRICS:
        assert per_layer[name]["source"] == "program_counter"
        assert per_layer[name]["moves"] == "setup_s"
        assert per_layer[name]["unit"] == "ms"
        assert per_layer[name]["layer"] == "entry and init"
        assert "workloads" not in per_layer[name]
    # Every metric of the flash kernel's is reported where there is flash
    # work to read, and nowhere else: the cells are derived, not counted.
    flash_cells = _cells_whose_job_reports_flash_work(bench)
    assert flash_cells and len(flash_cells) < len(bench["workloads"])
    assert [n for n in per_layer if "flash" in n] == FLASH_METRICS
    for name in FLASH_METRICS:
        assert per_layer[name]["workloads"] == flash_cells
        assert per_layer[name]["layer"] == "kernels"


@pytest.mark.parametrize("metric", NEW_TRACE_METRICS + FLASH_SHARES)
def test_trace_readers_give_nothing_without_a_device_trace(metric):
    ctx = {"trace": None, "job": {"kernel_work_per_step": {"flash": {}}}}
    assert manifest.load_reader(metric)(ctx) is None


@pytest.mark.parametrize("metric, kind", [
    ("forward_ms", "forward"), ("backward_ms", "backward"),
    ("optimizer_ms", "optimizer"), ("fusion_pack_ms", "packing"),
    ("unscoped_ms", "unscoped")])
def test_trace_readers_read_the_traced_run_once(monkeypatch, recorded,
                                                capsys, metric, kind):
    """The readers find the run's file themselves (``ctx`` holds no
    operation names), reduce it once and print the split."""
    monkeypatch.setattr(trace, "find_xplane", lambda trace_dir: recorded)
    ctx = {"trace": {"not": "read"},
           "job": {"kernel_work_per_step": {"flash": {}}}}
    expected = scopes.partition(scopes.read_events(recorded), names)
    scopes._reduce_file.cache_clear()
    assert manifest.load_reader(metric)(ctx) == pytest.approx(
        expected["classes"][kind])
    printed = capsys.readouterr().out
    assert "[benchmark] a step by the program's scopes" in printed
    assert "collectives by mesh axes, ms a step: data" in printed
    assert manifest.load_reader("flash_bwd_ms")(ctx) == pytest.approx(
        expected["flash"]["bwd"])
    no_kernel = {**ctx, "job": {"kernel_work_per_step": {}}}
    assert manifest.load_reader("flash_bwd_ms")(no_kernel) is None
    assert capsys.readouterr().out == ""     # reduced once, said once
    assert scopes._reduce_file.cache_info().misses == 1


def test_readers_give_nothing_for_a_program_without_scopes(monkeypatch,
                                                           recorded):
    monkeypatch.setattr(trace, "find_xplane", lambda trace_dir: recorded)
    monkeypatch.setattr(scopes, "program_scopes", lambda: None)
    scopes._reduce_file.cache_clear()
    ctx = {"trace": {"not": "read"},
           "job": {"kernel_work_per_step": {"flash": {}}}}
    assert manifest.load_reader("forward_ms")(ctx) is None
    assert manifest.load_reader("flash_fwd_ms")(ctx) is None
    scopes._reduce_file.cache_clear()


@pytest.mark.parametrize("which, work", [("fwd", "forward"),
                                         ("bwd", "backward")])
def test_flash_shares_are_the_pass_s_least_time_over_its_time(
        monkeypatch, recorded, capsys, which, work):
    """A pass's share is ``arithmetic.roofline_seconds`` of what the job
    says that pass needs over the pass's traced time, and no share without
    a table of peaks (a CPU run) or without the kernel."""
    monkeypatch.setattr(trace, "find_xplane", lambda trace_dir: recorded)
    peaks = manifest.peaks("TPU v5 lite")
    took_ms = scopes.partition(scopes.read_events(recorded),
                               names)["flash"][which]
    need_s = 0.25 * took_ms * 1e-3             # so the share reads 25 %
    job = {"kernel_work_per_step": {"flash": {
        "flops": 1.0, "bytes": 1.0,
        work: {"flops": need_s * peaks["bf16_flops_per_s"], "bytes": 8.0}}}}
    ctx = {"trace": {"not": "read"}, "peaks": peaks, "job": job}
    scopes._reduce_file.cache_clear()
    read = manifest.load_reader(f"flash_{which}_roofline")
    assert read(ctx) == pytest.approx(25.0)
    assert f"flash {which} roofline: flops bound" in capsys.readouterr().out
    assert read({**ctx, "peaks": None}) is None
    assert read({**ctx, "job": {"kernel_work_per_step": {}}}) is None
    scopes._reduce_file.cache_clear()


@pytest.mark.parametrize("recording", sorted(
    name for name in os.listdir(os.path.join(manifest.HERE, "testdata"))
    if ".xplane.pb" in name))
def test_flash_passes_add_up_to_the_mosaic_time_of_every_recording(
        recording, tmp_path):
    """``fwd`` + ``bwd`` is the plain reduction's Mosaic time on each trace
    recorded on the chip: no call of the kernel's falls between the
    passes.  The oldest recording (PR 23) is of a program without scopes
    and gives no split."""
    path = os.path.join(manifest.HERE, "testdata", recording)
    if recording.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            path = tmp_path / "recording.xplane.pb"
            path.write_bytes(f.read())
    events = scopes.read_events(str(path))
    reduced = scopes.partition(events, names)
    plain = _plain_reduction(events)
    assert plain["mosaic_ms_per_step"] > 0
    if reduced is None:
        assert recording.startswith("tiny-decoder-v5e")
        return
    flash = reduced["flash"]
    assert set(flash) == {"fwd", "bwd"}
    assert flash["fwd"] + flash["bwd"] == pytest.approx(
        plain["mosaic_ms_per_step"], rel=1e-6)
    assert sum(reduced["flash_scopes"].values()) == pytest.approx(
        plain["mosaic_ms_per_step"], rel=1e-6)


def test_compile_log_readers_sum_the_step_s_records(monkeypatch, capsys):
    import horovod_tpu.jax as hvd

    log = [{"program": "hvd_train_step", "event": "trace", "seconds": 1.5},
           {"program": "jit(hvd_train_step)", "event": "lower",
            "seconds": 0.25},
           {"program": "jit(hvd_train_step)", "event": "cache_hit",
            "seconds": None},
           {"program": "jit(hvd_train_step)", "event": "backend",
            "seconds": 2.0},
           {"program": "hvd_train_step", "event": "trace", "seconds": 5e-5}]
    other = {"program": "jit(make_state)", "event": "backend", "seconds": 9}
    quick = {"program": "jit(add)", "event": "backend", "seconds": 0.01}
    monkeypatch.setattr(
        hvd, "compile_log", lambda program=None:
        log if program == hvd.TRAIN_STEP_PROGRAM else [other, quick] + log)
    scopes._say_compile_log.cache_clear()
    assert manifest.load_reader("step_trace_ms")({}) == pytest.approx(
        1500.05)
    assert manifest.load_reader("step_lower_ms")({}) == pytest.approx(250.0)
    assert manifest.load_reader("step_backend_ms")({}) == pytest.approx(2e3)
    printed = capsys.readouterr().out
    assert printed.count("[benchmark] compile log") == 1
    assert "jit(make_state) backend 9" in printed and "add" not in printed
    assert "hvd_train_step trace 0.000050" in printed
    scopes._say_compile_log.cache_clear()
    # A program that keeps no log (the parent of PR 24): no number.
    monkeypatch.delattr(hvd, "compile_log")
    assert manifest.load_reader("step_trace_ms")({}) is None
