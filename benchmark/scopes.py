"""Where a step's device time goes, told by the program's own names.

The program puts ``jax.named_scope``s around its forward and backward
pass, its optimizer, its gradient packing, its all-reduces and the calls
of its flash kernel (``horovod_tpu/common/scopes.py`` is the table),
and keeps a log of what JAX compiled (``hvd.compile_log()``).  This module
reads both for the per-layer readers of ``benchmark/metrics``: it opens the
traced run's own file, reduces it once and keeps the result for the other
readers; ``benchmark/trace.py`` does the interval arithmetic.

What a raw v5e trace holds for an operation (looked at by hand, PR 24):
each event of the ``XLA Ops`` line points to an event metadata whose name
is the whole HLO text, whose display name is the instruction's
(``fusion.1109``), and whose stats are ``hlo_category``, ``program_id``,
``flops``, ``bytes_accessed``, ``source``, ``source_stack``,
``shape_with_layout`` and ``tf_op``: the HLO metadata's ``op_name`` with a
colon behind it, ``jit(hvd_train_step)/hvd.loss/transpose(jvp(LlamaModel))/
layer_0/mlp/w_gate_up/dot_general:``.  Asynchronous copies and slices
(``slice-start``, ``copy-done``) and a few constants carry none.
``jax.profiler.ProfileData`` shows an event's own stats
(``device_offset_ps``, ``device_duration_ps``) and not its metadata's, so
the file is decoded by ``benchmark/xspace.py``.

Every operation falls into exactly one class:

* ``collective``  by its opcode, whatever scope it is under;
* ``packing``     under ``hvd.fusion.pack`` or ``hvd.fusion.unpack``;
* ``optimizer``   under ``hvd.optimizer`` or ``hvd.apply``;
* ``backward``    under ``hvd.loss`` and a ``transpose(...)`` component;
* ``forward``     under ``hvd.loss`` otherwise;
* ``packing``     again: under ``hvd.allreduce.<axes>`` and no collective,
  which is an averaging all-reduce's division of the fused buffer;
* ``unscoped``    the rest: what the scopes miss.

A fusion that XLA built across a boundary has one ``op_name``, its root's,
and counts where that puts it.

The flash kernel is read by pass, not by call: a Mosaic call under the
forward call's scope is ``fwd``, wherever it runs; one under any other
scope of the kernel's is ``bwd``, however many calls the backward pass is
made of and whatever the table names them.  A program without the scopes
(the parent of the PR that added them) gives no number, not a wrong one.
"""

from __future__ import annotations

import functools
import os
from collections import defaultdict

from benchmark import arithmetic, manifest, trace, xspace

OP_NAME_STAT = "tf_op"
CLASSES = ("forward", "backward", "optimizer", "packing", "collective",
           "unscoped")
FLASH_PASSES = {"fwd": "forward", "bwd": "backward"}   # a job's name for it
TRACE_DIR = os.path.join(manifest.ROOT, ".bench_trace")


def program_scopes():
    """The program's table of names, or None where it has none."""
    try:
        from horovod_tpu.common import scopes
    except ImportError:
        return None
    return scopes


# -- one operation -----------------------------------------------------------

def components(op_name: str) -> list:
    """``a/b(c/d)/e`` is three components: a slash inside brackets (JAX
    wraps what differentiation goes through, ``transpose(jvp(Model))``)
    does not split."""
    out, depth, at = [], 0, 0
    for i, char in enumerate(op_name):
        if char in "([":
            depth += 1
        elif char in ")]":
            depth -= 1
        elif char == "/" and depth == 0:
            out.append(op_name[at:i])
            at = i + 1
    out.append(op_name[at:])
    return out


def bare(component: str) -> str:
    """``Model`` of ``transpose(jvp(Model))``: JAX wraps the first scope
    entered inside a transformation, ``jvp(hvd.flash.fwd)`` where the
    model has no scope of its own around the call."""
    while component.endswith(")") and "(" in component:
        component = component[component.index("(") + 1:-1]
    return component


@functools.lru_cache(maxsize=None)
def _path(op_name: str) -> tuple:
    """(components, their bare names): a trace repeats a few thousand
    ``op_name``s in every step."""
    path = tuple(components(op_name))
    return path, tuple(bare(part) for part in path)


def classify(name: str, op_name: str, names) -> str:
    """The class of the operation whose HLO text is ``name`` and whose
    ``op_name`` path is ``op_name``; ``names`` is the program's table."""
    if trace.op_kind(name) == "collective":
        return "collective"
    path, scopes = _path(op_name)
    if names.FUSION_PACK in scopes or names.FUSION_UNPACK in scopes:
        return "packing"
    if names.OPTIMIZER in scopes or names.APPLY in scopes:
        return "optimizer"
    if names.LOSS in scopes:
        inside = path[scopes.index(names.LOSS):]
        if any(part.startswith("transpose(") for part in inside):
            return "backward"
        return "forward"
    if collective_axes(op_name, names) is not None:
        return "packing"
    return "unscoped"


def flash_call(name: str, op_name: str, names):
    """``(pass, scope)`` of a Mosaic call of the flash kernel's, or None:
    the pass is ``fwd`` under ``names.FLASH_FWD`` -- a forward call that a
    recomputation policy repeats inside ``transpose(...)`` is a forward
    call still -- and ``bwd`` under any other scope that shares its prefix
    (``hvd.flash.``); the scope is named by its last component (``dq``)."""
    if trace.op_kind(name) != "mosaic":
        return None
    prefix = names.FLASH_FWD[:names.FLASH_FWD.rindex(".") + 1]
    scopes = [s for s in _path(op_name)[1] if s.startswith(prefix)]
    if names.FLASH_FWD in scopes:
        return "fwd", names.FLASH_FWD[len(prefix):]
    if scopes:
        return "bwd", scopes[0][len(prefix):]
    return None


def collective_axes(op_name: str, names):
    """``data`` of ``.../hvd.allreduce.data/psum``; None under no such
    scope."""
    prefix = names.ALLREDUCE + "."
    for scope in _path(op_name)[1]:
        if scope.startswith(prefix):
            return scope[len(prefix):]
    return None


# -- the file ----------------------------------------------------------------

def read_events(path: str) -> dict:
    """``{"devices": {n: {"ops": [...], "modules": [...]}}}`` with every
    event ``(name, start_s, end_s)``; an operation's name is the pair
    (HLO text, ``op_name``), which the interval arithmetic carries
    through unopened."""
    wanted = {line for line, key in trace.LINES.items()
              if key in ("ops", "modules")}
    devices = {}
    for plane in xspace.read_planes(path, want_line=wanted.__contains__):
        on_device = trace.DEVICE_PLANE.match(plane["name"])
        if not on_device:
            continue
        lines = {"ops": [], "modules": []}
        for line, events in plane["lines"].items():
            for event in events:
                name = event["name"]
                if trace.LINES[line] == "ops":
                    op_name = event["stats"].get(OP_NAME_STAT) or ""
                    # "a/b/mul;a/b/broadcast": the first is the root's.
                    name = (name, op_name.rstrip(":").split(";")[0])
                lines[trace.LINES[line]].append(
                    (name, event["start_s"], event["end_s"]))
        devices[int(on_device.group(1))] = lines
    return {"devices": devices}


# -- the reduction -----------------------------------------------------------

def partition_device(ops, modules, names) -> dict:
    """One chip's self time on the ``XLA Ops`` line by class, by the flash
    kernel's pass and scope and by collective axes, in seconds over the
    window of the whole steps it traced (``trace.step_window``, as the
    other reduction)."""
    start, end, steps = trace.step_window(modules)
    by_class = dict.fromkeys(CLASSES, 0.0)
    flash, flash_scopes, axes, unscoped = (defaultdict(float)
                                           for _ in range(4))
    for (name, op_name), own in trace.self_times(
            trace.clip(ops, start, end)):
        kind = classify(name, op_name, names)
        by_class[kind] += own
        call = flash_call(name, op_name, names)
        if call:
            flash[call[0]] += own
            flash_scopes[call[1]] += own
        if kind == "collective":
            axes[collective_axes(op_name, names) or "unscoped"] += own
        elif kind == "unscoped":
            unscoped[trace.family(name)] += own
    return {"steps": steps, "classes": by_class, "flash": dict(flash),
            "flash_scopes": dict(flash_scopes),
            "collective_axes": dict(axes), "unscoped": dict(unscoped)}


def partition(events: dict, names) -> dict | None:
    """Milliseconds a step, averaged over the chips that ran operations:
    ``{"classes": {class: ms}, "flash": {"fwd": ms, "bwd": ms},
    "flash_scopes": {scope: ms}, "collective_axes": {axes: ms},
    "unscoped": [[family, ms], ...]}``; ``flash`` is for the metrics,
    ``flash_scopes`` (the same time by the scope's last component) for
    people.  None if no operation is under any scope of the program's: it
    has none."""
    chips = [partition_device(d["ops"], d["modules"], names)
             for _, d in sorted(events["devices"].items())
             if d["ops"] and d["modules"]]
    if not any(chip["classes"][kind] for chip in chips for kind in CLASSES
               if kind not in ("collective", "unscoped")):
        return None
    per_step = 1e3 / sum(chip["steps"] for chip in chips)

    def total(key):
        out = defaultdict(float)
        for chip in chips:
            for name, seconds in chip[key].items():
                out[name] += seconds * per_step
        return dict(out)

    unscoped = sorted(total("unscoped").items(), key=lambda kv: -kv[1])
    return {"classes": total("classes"), "flash": total("flash"),
            "flash_scopes": total("flash_scopes"),
            "collective_axes": total("collective_axes"),
            "unscoped": [list(kv) for kv in unscoped[:8]]}


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> dict | None:
    names = program_scopes()
    if names is None:
        return None
    reduced = partition(read_events(path), names)
    if reduced is None:
        say("no operation of the trace is under a scope of the program's")
        return None
    say("a step by the program's scopes, ms: " + ", ".join(
        f"{name} {ms:.3f}" for name, ms in reduced["classes"].items()))
    if reduced["flash"]:
        say("flash calls, ms a step: " + ", ".join(
            f"{scope} {ms:.3f}"
            for scope, ms in reduced["flash_scopes"].items())
            + "; by pass: " + ", ".join(
            f"{which} {ms:.3f}" for which, ms in reduced["flash"].items()))
    if reduced["collective_axes"]:
        say("collectives by mesh axes, ms a step: " + ", ".join(
            f"{axes} {ms:.3f}"
            for axes, ms in reduced["collective_axes"].items()))
    say("largest unscoped families, ms a step: " + ", ".join(
        f"{family} {ms:.3f}" for family, ms in reduced["unscoped"][:5]))
    return reduced


def say(message: str) -> None:
    print(f"[benchmark] {message}", flush=True)


# -- what the readers call ---------------------------------------------------

def traced(ctx) -> dict | None:
    """The traced window by scopes, reduced once a run; None without a
    device trace (a CPU run) or without scopes in the program."""
    if ctx["trace"] is None:
        return None
    path = trace.find_xplane(TRACE_DIR)
    return _reduce_file(path, os.path.getmtime(path))


def class_ms(ctx, kind: str):
    reduced = traced(ctx)
    return None if reduced is None else reduced["classes"][kind]


def flash_ms(ctx, which: str):
    """Device time a step of the flash kernel's ``fwd`` or ``bwd`` pass."""
    reduced = traced(ctx)
    if reduced is None or "flash" not in ctx["job"]["kernel_work_per_step"]:
        return None
    return reduced["flash"].get(which)


def flash_roofline(ctx, which: str):
    """The least time the chip could take for that pass of a step's flash
    calls (``arithmetic.roofline_seconds`` of what the job says the pass
    needs) over the time the trace shows the pass take, in per cent."""
    ms = flash_ms(ctx, which)
    if not ms or ctx["peaks"] is None:
        return None
    work = ctx["job"]["kernel_work_per_step"]["flash"][FLASH_PASSES[which]]
    least_s, bound = arithmetic.roofline_seconds(
        work["flops"], work["bytes"], ctx["peaks"])
    say(f"flash {which} roofline: {bound} bound, least "
        f"{least_s * 1e3:.3f} ms a step")
    return 100.0 * least_s * 1e3 / ms


@functools.lru_cache(maxsize=1)
def _say_compile_log() -> None:
    """Once a run: what took a tenth of a second or more, and the step."""
    import horovod_tpu.jax as hvd

    step = hvd.compile_log(hvd.TRAIN_STEP_PROGRAM)
    said = [r for r in hvd.compile_log()
            if (r["seconds"] or 0.0) >= 0.1 or r in step]
    say("compile log (program event seconds): " + "; ".join(
        f"{r['program']} {r['event']}"
        + ("" if r["seconds"] is None else f" {r['seconds']:.6f}")
        for r in said))


def step_compile_ms(event: str):
    """Milliseconds JAX reported for ``event`` (``trace``, ``lower``,
    ``backend``) of the train step, summed over the process so far: the
    step compiles in the set-up and nowhere else.  None where the program
    keeps no compile log."""
    import horovod_tpu.jax as hvd

    if not hasattr(hvd, "compile_log"):
        return None
    _say_compile_log()
    return 1e3 * sum(
        record["seconds"]
        for record in hvd.compile_log(hvd.TRAIN_STEP_PROGRAM)
        if record["event"] == event)
