"""From a profiler trace (``.xplane.pb``) and a compiled step's HLO text to
the numbers the per-layer readers take.

Two halves.  ``read_xplane`` turns the file into plain tuples with nothing
but JAX's ``ProfileData``; everything after it is interval arithmetic on
those tuples, which the tests drive with hand-built events.

What a TPU trace holds (looked at by hand, PR 23): one plane a chip,
``/device:TPU:<n>``, with a line ``XLA Modules`` (one event an execution
of a jitted program), a line ``XLA Ops`` (one event an HLO operation of
the TensorCore, named by its whole HLO text: ``%fusion.12 = bf16[2048,
5632]{...} fusion(...), kind=kOutput, ...``; a Mosaic kernel is a
``custom-call`` whose text holds ``tpu_custom_call``) and a line ``Async
XLA Ops`` (one event from an asynchronous operation's ``-start`` to its
``-done``: copies, slices and collectives that run beside the
TensorCore); and a plane ``/host:CPU`` with a line a thread, on which
``jax.profiler.TraceAnnotation`` spans appear under their own names.  All
on one clock, in nanoseconds.

    python -m benchmark.trace <file.xplane.pb>     # what is in a trace
"""

from __future__ import annotations

import glob
import os
import re
import sys
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LINES = {"XLA Ops": "ops", "Async XLA Ops": "async", "XLA Modules": "modules"}
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
MOSAIC_TARGET = "tpu_custom_call"
# "%name = <result shape> opcode(": the opcode is the first lower-case word
# that a blank precedes and "(" follows (shapes hold "T(8,128)" and "S(1)").
_HLO = re.compile(r"^%?(\S+) = (.*?)\s([a-z][\w\-]*)\(")
_RESULT = re.compile(r"[a-z]\w*\[[\d,]*\]")


# -- intervals ---------------------------------------------------------------

def union(intervals) -> list:
    """Sorted, disjoint ``[start, end)`` covering the same points."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def length(intervals) -> float:
    return sum(end - start for start, end in intervals)


def subtract(intervals, holes) -> list:
    """The part of ``intervals`` that no interval of ``holes`` covers."""
    holes = union(holes)
    left = []
    for start, end in union(intervals):
        at = start
        for h_start, h_end in holes:
            if h_end <= at:
                continue
            if h_start >= end:
                break
            if h_start > at:
                left.append([at, h_start])
            at = max(at, h_end)
            if at >= end:
                break
        if at < end:
            left.append([at, end])
    return left


def overlap(intervals, others) -> float:
    return length(intervals) - length(subtract(intervals, others))


def clip(events, start: float, end: float) -> list:
    """Events ``(name, start, end)`` that lie wholly inside the window."""
    return [e for e in events if e[1] >= start and e[2] <= end]


def self_times(events) -> list:
    """``(name, self seconds)`` of events of one line: an event's time less
    that of the events nested inside it (a ``while`` spans its body)."""
    out, stack = [], []          # stack of [name, end, self]
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    out.extend((name, own) for name, _, own in stack)
    return out


def opcode(name: str) -> str:
    """The HLO opcode in an event's name; a bare name (``fusion.12``, as
    hand-built events and other backends give) is its own opcode without
    the running number."""
    text = _HLO.match(name)
    if text:
        return text.group(3)
    return re.sub(r"[.\-_]\d+$", "", name.lstrip("%"))


def op_kind(name: str) -> str:
    code = opcode(name)
    if COLLECTIVE.match(code):
        return "collective"
    if code == "custom-call" and (MOSAIC_TARGET in name
                                  or not _HLO.match(name)):
        return "mosaic"
    return "xla"


def family(name: str) -> str:
    """What the breakdown calls an operation: its opcode and the first
    array of its result, ``fusion bf16[8192,11264]``: the same for every
    layer's copy of it."""
    text = _HLO.match(name)
    if not text:
        return opcode(name)
    result = _RESULT.search(text.group(2))
    return text.group(3) + (" " + result.group(0) if result else "")


# -- the file ----------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str, host_spans=()) -> dict:
    """``{"devices": {n: {"ops": [...], "async": [...], "modules":
    [...]}}, "host": {span: [...]}}``, every event ``(name, start_s,
    end_s)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, defaultdict(list)
    for plane in data.planes:
        on_device = DEVICE_PLANE.match(plane.name)
        if on_device:
            lines = {key: [] for key in LINES.values()}
            for line in plane.lines:
                key = LINES.get(line.name)
                if key is None:
                    continue
                for event in line.events:
                    start = event.start_ns * 1e-9
                    lines[key].append(
                        (event.name, start,
                         start + event.duration_ns * 1e-9))
            devices[int(on_device.group(1))] = lines
        elif plane.name == HOST_PLANE and host_spans:
            for line in plane.lines:
                for event in line.events:
                    if event.name in host_spans:
                        start = event.start_ns * 1e-9
                        host[event.name].append(
                            (event.name, start,
                             start + event.duration_ns * 1e-9))
    return {"devices": devices, "host": dict(host)}


# -- the reduction -----------------------------------------------------------

def step_window(modules) -> tuple:
    """(start, end, executions) of the program that took most device time
    on the ``XLA Modules`` line: the train step.  The window runs from the
    first whole execution's start to the last one's end."""
    by_name = defaultdict(list)
    for name, start, end in modules:
        by_name[name].append((start, end))
    if not by_name:
        raise ValueError("no execution of any program in the trace")
    runs = max(by_name.values(), key=length)
    return min(s for s, _ in runs), max(e for _, e in runs), len(runs)


def reduce_device(ops, asyncs, modules, host) -> dict:
    """One chip's numbers over the window of whole steps it traced.  Busy
    is the TensorCore's: the union of the ``XLA Ops`` line.  A collective
    counts from its start to its end, wherever it shows: as one operation,
    as a ``-done`` the TensorCore waits in, or on the asynchronous line;
    it is exposed while no other operation runs on the TensorCore."""
    start, end, steps = step_window(modules)
    ops = clip(ops, start, end)
    if not ops:
        raise ValueError("no operation ran on the device inside the "
                         "traced steps")
    busy = union((s, e) for _, s, e in ops)
    by_kind = defaultdict(list)
    for name, s, e in ops:
        by_kind[op_kind(name)].append((s, e))
    by_kind["collective"] += [
        (max(s, start), min(e, end)) for name, s, e in asyncs
        if op_kind(name) == "collective" and e > start and s < end]
    per_kind = defaultdict(float)
    per_family = defaultdict(float)
    for name, own in self_times(ops):
        per_kind[op_kind(name)] += own
        per_family[family(name)] += own
    exposed = subtract(by_kind["collective"],
                       by_kind["xla"] + by_kind["mosaic"])
    gaps = subtract([[start, end]], busy)
    idle = {}
    for span, events in host.items():
        idle[span] = overlap(gaps, [(s, e) for _, s, e in events])
    idle["other"] = max(length(gaps) - sum(idle.values()), 0.0)
    return {
        "window_s": end - start, "busy_s": length(busy), "steps": steps,
        "mosaic_s": per_kind["mosaic"], "xla_s": per_kind["xla"],
        "collective_s": per_kind["collective"],
        "exposed_collective_s": length(exposed),
        "families": dict(per_family), "idle": idle,
    }


def reduce_events(events: dict) -> dict:
    """Average over the chips that ran operations; the breakdown sums."""
    chips = [reduce_device(d["ops"], d.get("async", []), d["modules"],
                           events["host"])
             for _, d in sorted(events["devices"].items())
             if d["ops"] and d["modules"]]
    if not chips:
        raise ValueError("the trace holds no device operation: did the "
                         "window run on a TPU?")
    n = len(chips)

    def mean(key):
        return sum(c[key] for c in chips) / n

    steps = mean("steps")
    families, idle = defaultdict(float), defaultdict(float)
    for chip in chips:
        for name, seconds in chip["families"].items():
            families[name] += seconds / n
        for name, seconds in chip["idle"].items():
            idle[name] += seconds / n

    def top(table):
        return [[name, seconds] for name, seconds in
                sorted(table.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "chips": n, "steps": steps,
        "window_s": mean("window_s"), "busy_s": mean("busy_s"),
        "mosaic_ms_per_step": mean("mosaic_s") / steps * 1e3,
        "xla_ms_per_step": mean("xla_s") / steps * 1e3,
        "collective_ms_per_step": mean("collective_s") / steps * 1e3,
        "exposed_collective_ms_per_step":
            mean("exposed_collective_s") / steps * 1e3,
        "breakdown": {"device_ops": top(families), "idle_gaps": top(idle)},
    }


def reduce_trace(path: str, host_spans=()) -> dict:
    return reduce_events(read_xplane(path, host_spans))


# -- the compiled step's text ------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "pred": 1, "s8": 1, "u8": 1, "s16": 2,
                "u16": 2}
# The result's shape is all between "= " and the opcode; a tuple's layouts
# hold brackets of their own, "{0:T(1024)(128)(2,1)}".
_COLLECTIVE_LINE = re.compile(
    r"^\s*%?\S+ = (.*?)\s(all-reduce|reduce-scatter|all-gather|"
    r"all-to-all|collective-permute)(-start|-done)?\(", re.M)


def _shape_bytes(shape: str) -> int:
    total = 0
    for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", shape):
        count = 1
        for d in dims.split(","):
            if d:
                count *= int(d)
        total += count * _DTYPE_BYTES.get(dtype, 4)
    return total


def collectives_in_hlo(text: str) -> dict:
    """Collective operations of an optimised HLO module and the bytes of
    their results (an all-reduce's result is its payload).  A synchronous
    operation counts by its own line; an asynchronous pair by its
    ``-done`` line, whose result is the payload alone (a ``-start``
    carries operands and scratch too)."""
    ops, nbytes = defaultdict(int), 0
    for shape, kind, phase in _COLLECTIVE_LINE.findall(text):
        if phase != "-done":
            ops[kind] += 1
        if phase != "-start":
            nbytes += _shape_bytes(shape)
    return {"ops": dict(ops), "bytes": nbytes}


def describe(path: str) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for event in events[:4]:
                print(f"    {event.name!r} start_ns={event.start_ns} "
                      f"duration_ns={event.duration_ns} "
                      f"stats={dict(list(event.stats)[:8])}")


if __name__ == "__main__":
    describe(sys.argv[1])
