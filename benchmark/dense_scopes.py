"""A step's ordinary work, told by the three scopes that name it
(``horovod_tpu/common/scopes.py``): a layer's mixer block
(``hvd.block.attn``: norm, projections, rotation, ``wo``, residual add), its
feed-forward block (``hvd.block.ffn``) and what turns the stack's output
into a loss (``hvd.head``: final norm, head, cross-entropy); forward,
recomputed and backward alike, LESS the Mosaic calls (the flash kernel's
and the indexer's have metrics of their own) but WITH the Mosaic calls
XLA:TPU makes of ``jax.lax.ragged_dot`` (``RAGGED_DOT_PREFIX``, as
``moe_scopes.classify`` tells them), which are the routed feed-forward's
products.  What is under ``hvd.loss`` and in no block is "other": the
embedding's lookup and its scatter-add, the rotary tables, a looped
model's exit distribution and its scan's own work, what XLA hoists.  A
fusion has one ``op_name``: were a product named by the NEXT block's norm
it would count there, and ``whose_products`` says how much does (on the
v5e none: XLA:TPU names a matmul fusion by its ``dot_general``, PR 36).

Read for ``benchmark/metrics/block_attn_ms``, ``block_ffn_ms``, ``head_ms``
and ``dense_roofline`` from the traced run's file, reduced once a run.
The names come from the program's table, and a program without the three
(the parent of the PR that added them), or a trace that shows none of them,
gives no number.

This reader opens the file through ``xspace.read_planes`` itself, because
it keeps what ``scopes.read_events`` drops: every ``XLA Ops`` event of a
v5e trace carries XLA's own ``flops``, ``bytes_accessed`` and
``hlo_category``.  They are for people (the ``[benchmark]`` lines: time,
FLOPs and achieved rates by block and pass, by category, by family; the
optimizer's update inside the gradient matmuls), never for a share of a
peak: ``bytes_accessed`` counts operands that live in VMEM (``S(1)`` in a
layout) as if they crossed HBM, and reads above the chip's 819 GB/s.  A
container (``while``, ``conditional``, ``call``) carries its children's
sums: FLOPs and bytes are counted beside an event's SELF time, and never a
container's.  ``dense_roofline`` takes its count from the configuration and
``benchmark/arithmetic.py`` alone, so recomputing cannot raise it.

    python -m benchmark.dense_scopes <file.xplane.pb>     # the tables
"""

from __future__ import annotations

import functools
import os
import re
import sys
import time
from collections import defaultdict

from benchmark import arithmetic, scopes, trace, xspace

BLOCKS = ("attn", "ffn", "head")
OTHER = "other"
PASSES = ("forward", "recomputed", "backward")
UNNAMED = "unnamed"       # XLA's ragged-dot calls: no scope, so no pass
MOSAIC = "mosaic"         # a Mosaic call of the loss: timed, in no block
CONTAINERS = ("while", "conditional", "call")
MATMUL_CATEGORY = "convolution fusion"
FAMILIES_SHOWN = 12
_F32 = re.compile(r"f32\[(\d+(?:,\d+)+)\]")


# -- one operation -----------------------------------------------------------

@functools.lru_cache(maxsize=None)
def classify(text: str, op_name: str, names) -> tuple:
    """``(block, pass)`` of the operation whose HLO text is ``text`` and
    whose ``op_name`` path is ``op_name``: the block is one of ``BLOCKS``
    or ``OTHER``, or ``MOSAIC`` for a Mosaic call of the program's under
    ``hvd.loss`` (which the blocks leave out); ``(None, None)`` for what
    this reader does not look at (a collective, what is not under
    ``hvd.loss``)."""
    if op_name.startswith(names.RAGGED_DOT_PREFIX):
        return "ffn", UNNAMED
    kind = trace.op_kind(text)
    path = scopes.components(op_name)
    held = [scopes.bare(part) for part in path]
    if kind == "collective" or names.LOSS not in held:
        return None, None
    if kind == "mosaic":
        return MOSAIC, None
    if names.BLOCK_ATTN in held:
        block = "attn"
    elif names.BLOCK_FFN in held:
        block = "ffn"
    elif names.HEAD in held:
        block = "head"
    else:
        block = OTHER
    if not any(part.startswith("transpose(")
               for part in path[held.index(names.LOSS):]):
        return block, "forward"
    return block, "recomputed" if names.REMATTED in held else "backward"


def holds_an_update(text: str) -> bool:
    """Whether the instruction's result is a tuple that holds two or more
    float32 arrays of one shape of rank two or more: a weight's master
    copy and its moments, so its optimizer update rides in this fusion."""
    result = text.partition(" = ")[2]
    if not result.startswith("("):
        return False
    depth = 0
    for at, char in enumerate(result):
        depth += (char == "(") - (char == ")")
        if depth == 0:
            break
    shapes = _F32.findall(result[:at])
    return any(shapes.count(shape) >= 2 for shape in set(shapes))


# -- the file ----------------------------------------------------------------

def _number(value) -> float:
    try:
        return float(value)
    except (TypeError, ValueError):
        return 0.0


def read_ops(path: str) -> dict:
    """``{chip: {"ops": [...], "modules": [...]}}`` with every event
    ``(name, start_s, end_s)``; an operation's name is ``(HLO text,
    op_name, flops, bytes_accessed, hlo_category)``, which the interval
    arithmetic carries through unopened.  An operation with no ``tf_op``
    (asynchronous copies, some constants) has the empty ``op_name``."""
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
                    stats = event["stats"]
                    op_name = stats.get(scopes.OP_NAME_STAT) or ""
                    name = (name, op_name.rstrip(":").split(";")[0],
                            _number(stats.get("flops")),
                            _number(stats.get("bytes_accessed")),
                            stats.get("hlo_category") or "")
                lines[trace.LINES[line]].append(
                    (name, event["start_s"], event["end_s"]))
        devices[int(on_device.group(1))] = lines
    return devices


# -- the reduction -----------------------------------------------------------

def _cell():
    return {"seconds": 0.0, "flops": 0.0, "bytes": 0.0, "events": 0}


def _add(cell: dict, own: float, flops: float, nbytes: float) -> None:
    cell["seconds"] += own
    cell["flops"] += flops
    cell["bytes"] += nbytes
    cell["events"] += 1


def partition(devices: dict, names, products: dict | None = None) -> dict:
    """The window of whole steps, over the chips that ran operations.
    ``names`` may lack the three scopes (then only ``categories`` and
    ``families`` mean something).  ``products`` maps the FLOPs of one
    matrix product a step to the block that owns it (``whose_products``;
    without it ``foreign`` is None).
    Tables hold sums over chips and steps, beside ``steps``."""
    has_blocks = names is not None and hasattr(names, "BLOCK_ATTN")
    table = defaultdict(_cell)           # (block, pass)
    categories = defaultdict(_cell)      # hlo_category
    families = defaultdict(_cell)        # (family, block, pass)
    updates = _cell()
    foreign = defaultdict(float)         # (counted in, owned by) -> seconds
    ragged = mosaic_in_loss = 0.0
    stray_matmuls = steps = 0
    for _, device in sorted(devices.items()):
        if not (device["ops"] and device["modules"]):
            continue
        start, end, executions = trace.step_window(device["modules"])
        steps += executions
        for (text, op_name, flops, nbytes, category), own in (
                trace.self_times(trace.clip(device["ops"], start, end))):
            if trace.opcode(text) in CONTAINERS:
                flops = nbytes = 0.0     # its children's sums, not its own
            _add(categories[category or "(none)"], own, flops, nbytes)
            block = which = None
            if has_blocks:
                block, which = classify(text, op_name, names)
            _add(families[(trace.family(text), block or "-", which or "-")],
                 own, flops, nbytes)
            if block == MOSAIC:
                mosaic_in_loss += own
            if block in (None, MOSAIC):
                continue
            _add(table[(block, which)], own, flops, nbytes)
            if which == UNNAMED:
                ragged += own
            if category != MATMUL_CATEGORY:
                continue
            if block == OTHER:
                stray_matmuls += 1
            if which == "backward" and holds_an_update(text):
                _add(updates, own, flops, nbytes)
            owner = _owner(flops, products)
            if owner and owner != block:
                foreign[(block, owner)] += own
    return {"steps": steps, "table": dict(table),
            "categories": dict(categories), "families": dict(families),
            "updates": updates,
            "foreign": dict(foreign) if products else None,
            "ragged_s": ragged, "mosaic_in_loss_s": mosaic_in_loss,
            "stray_matmuls": stray_matmuls}


def _owner(flops: float, products: dict | None):
    """The block whose product has ``flops`` (to 3 %: a fusion's
    elementwise work rides on the product's count), or None.  XLA may
    split a product over the batch: halves and quarters match too."""
    if not products or not flops:
        return None
    for parts in (1, 2, 4):
        nearest = min(products, key=lambda known: abs(known - parts * flops))
        if abs(nearest - parts * flops) <= 0.03 * nearest:
            return products[nearest]
    return None


def block_seconds(reduced: dict, block: str) -> float:
    return sum(cell["seconds"] for (b, _), cell in reduced["table"].items()
               if b == block)


def block_ms(reduced: dict, block: str) -> float:
    return block_seconds(reduced, block) * 1e3 / reduced["steps"]


# -- the lines for people ----------------------------------------------------

def _rates(cell: dict, steps: int) -> str:
    seconds = cell["seconds"]
    tflops = cell["flops"] / seconds / 1e12 if seconds else 0.0
    gbs = cell["bytes"] / seconds / 1e9 if seconds else 0.0
    return (f"{seconds * 1e3 / steps:.3f} ms, "
            f"{cell['flops'] / steps / 1e12:.4f} TFLOP, "
            f"{tflops:.1f} TFLOP/s, {gbs:.0f} GB/s")


def say_tables(reduced: dict, peaks: dict | None = None) -> None:
    """The ``[benchmark]`` lines: a step by block and pass, by XLA's
    category, the largest families, the update inside the gradient
    matmuls, and how much of a block is another block's product.  Rates
    are XLA's own ``flops`` and ``bytes_accessed`` over self time; the
    bytes count VMEM operands too, so no share of a peak is built on
    them."""
    steps, say = reduced["steps"], scopes.say
    if any(block_seconds(reduced, block) for block in BLOCKS):
        say("a step by block and pass (ms, XLA's TFLOP, achieved TFLOP/s, "
            "GB/s of bytes_accessed): " + "; ".join(
                f"{block} {which} {_rates(cell, steps)}"
                for (block, which), cell in sorted(
                    reduced["table"].items(),
                    key=lambda kv: ((BLOCKS + (OTHER,)).index(kv[0][0]),
                                    (PASSES + (UNNAMED,)).index(kv[0][1])))))
        blocks = {block: block_ms(reduced, block)
                  for block in BLOCKS + (OTHER,)}
        total = sum(blocks.values())
        ragged = reduced["ragged_s"] * 1e3 / steps
        say("blocks, ms a step: " + ", ".join(
            f"{block} {ms:.3f}" for block, ms in blocks.items())
            + f"; together {total:.3f} = forward + backward less the "
            f"Mosaic calls of the loss "
            f"({reduced['mosaic_in_loss_s'] * 1e3 / steps:.3f})"
            + (f" plus XLA's ragged-dot calls ({ragged:.3f}, in ffn, "
               f"under no scope)" if ragged else "")
            + f"; other is {100 * blocks[OTHER] / total:.2f} % of them")
    say("a step by hlo_category: " + "; ".join(
        f"{category} {_rates(cell, steps)}"
        for category, cell in sorted(reduced["categories"].items(),
                                     key=lambda kv: -kv[1]["seconds"])
        if cell["seconds"] * 1e3 / steps >= 0.01))
    largest = sorted(reduced["families"].items(),
                     key=lambda kv: -kv[1]["seconds"])[:FAMILIES_SHOWN]
    say(f"the {len(largest)} largest families (block, pass): "
        + "; ".join(
            f"{family} ({block}, {which}) {_rates(cell, steps)}, "
            f"{cell['events'] / steps:.1f} events"
            for (family, block, which), cell in largest))
    if reduced["table"]:
        update = reduced["updates"]
        say("the update inside the gradient matmuls (backward "
            f"'{MATMUL_CATEGORY}'s whose result holds float32 arrays of a "
            f"weight's shape): {update['events'] / steps:.1f} a step, "
            f"{update['seconds'] * 1e3 / steps:.3f} ms"
            + (f"; their flops at the peak "
               f"{update['flops'] / peaks['bf16_flops_per_s'] * 1e3 / steps:.3f}"
               f" ms, their bytes_accessed at the HBM's rate "
               f"{update['bytes'] / peaks['hbm_bytes_per_s'] * 1e3 / steps:.3f}"
               f" ms (a time beside a time: the bytes hold VMEM operands, "
               f"so no share is built on them)" if peaks else ""))
    if reduced["foreign"] is not None:
        say("matrix products counted in one block and owned by another "
            "(told by flops / 2 / tokens), ms a step: " + (", ".join(
                f"in {block} of {owner} {seconds * 1e3 / steps:.3f}"
                for (block, owner), seconds in sorted(
                    reduced["foreign"].items()))
                or "none, every product is counted in its own block"))


# -- what the configuration says the dense products need ---------------------

def dense_matrices(config: dict) -> dict | None:
    """``{block: [(rows in, columns out), ...]}`` of the weights a token
    is multiplied with in ONE pass of a plain decoder (the layers'
    together, the head once), from the configuration's published keys;
    None for a configuration whose layers are not that (experts, a
    latent, an indexer): ``benchmark/arithmetic_moe.py`` and
    ``arithmetic_sparse.py`` do not state their dense part alone."""
    if any("expert" in key or key in ("kv_lora_rank", "sa_config")
           for key in config):
        return None
    try:
        hidden, layers = config["hidden_size"], config["num_hidden_layers"]
        heads, kv_heads = (config["num_attention_heads"],
                           config["num_key_value_heads"])
        head_dim, ffn = config["head_dim"], config["intermediate_size"]
        vocab = config["vocab_size"]
    except KeyError:
        return None
    attn = [(hidden, heads * head_dim), (hidden, kv_heads * head_dim),
            (hidden, kv_heads * head_dim), (heads * head_dim, hidden)]
    ffn_ = [(hidden, 2 * ffn), (ffn, hidden)]
    assert (sum(i * o for i, o in attn + ffn_)
            == arithmetic.decoder_layer_matmul_params(
                hidden, heads, kv_heads, head_dim, ffn))
    return {"attn": attn * layers, "ffn": ffn_ * layers,
            "head": [(hidden, vocab)]}


def dense_work(config: dict, tokens: int) -> dict | None:
    """Operations and bytes a chip needs for a step's dense products:
    three products a weight (forward, the input's gradient, the weight's),
    ``6 x weights x tokens`` operations, each weight and each activation
    counted once a product; times the passes where the configuration
    loops.  By block and ``"all"``."""
    matrices = dense_matrices(config)
    if matrices is None:
        return None
    passes = config.get("total_ut_steps", 1)
    work = {}
    for block, shapes in matrices.items():
        weights = sum(i * o for i, o in shapes)
        touched = sum(i * o + tokens * (i + o) for i, o in shapes)
        work[block] = {"flops": 6.0 * weights * tokens * passes,
                       "bytes": 3.0 * 2 * touched * passes}
    work["all"] = {key: sum(w[key] for w in work.values())
                   for key in ("flops", "bytes")}
    return work


def whose_products(config: dict, tokens: int) -> dict | None:
    """``{flops of one product a step: the block that owns it}``: a
    fusion's ``flops / (2 x tokens)`` is a weight count, which says whose
    product it is whatever its root is called.  XLA may merge the q, k and
    v products of equal shape, so two and three of them are listed too."""
    matrices = dense_matrices(config)
    if matrices is None:
        return None
    known = {}
    for block, shapes in matrices.items():
        for i, o in set(shapes):
            for merged in ((1, 2, 3) if block == "attn" else (1,)):
                known.setdefault(2.0 * merged * i * o * tokens, block)
    return known


# -- what the readers call ---------------------------------------------------

def _tokens(ctx) -> int:
    return ctx["job"]["units_per_step"] // ctx["chips"]


def _reduce_file(path: str, ctx) -> dict | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "BLOCK_ATTN"):
        return None
    started = time.perf_counter()
    reduced = partition(read_ops(path), names, whose_products(
        ctx["cell"]["config"], _tokens(ctx)))
    # (XLA's ragged-dot calls are told without the names: they alone do
    # not show that the executable has them.)
    if not any(cell["seconds"] for (block, which), cell
               in reduced["table"].items()
               if block in BLOCKS and which != UNNAMED):
        scopes.say(f"no operation of the trace is under {names.BLOCK_ATTN}, "
                   f"{names.BLOCK_FFN} or {names.HEAD}: the executable is "
                   f"older than the names")
        return None
    say_tables(reduced, ctx["peaks"])
    scopes.say(f"the dense reader (one more decode of the trace) took "
               f"{time.perf_counter() - started:.3f} s")
    return reduced


_reduced: dict = {}      # {(path, its modification time): the reduction}


def traced(ctx) -> dict | None:
    """The traced window by blocks, reduced once a run; None without a
    device trace, or without the three scopes in the program or the
    trace."""
    if ctx["trace"] is None:
        return None
    path = trace.find_xplane(scopes.TRACE_DIR)
    key = (path, os.path.getmtime(path))
    if key not in _reduced:
        _reduced.clear()
        _reduced[key] = _reduce_file(path, ctx)
    return _reduced[key]


def scope_ms(ctx, block: str):
    reduced = traced(ctx)
    return None if reduced is None else block_ms(reduced, block) or None


def dense_roofline(ctx):
    """The least time the chip could take for the dense products the model
    needs in a step (``dense_work``: from the configuration, never from
    the trace) over ``block_attn_ms + block_ffn_ms + head_ms``, in per
    cent.  None, with a line saying why, where a matrix product of the
    loss lies in no block: the denominator would lack it."""
    reduced = traced(ctx)
    if reduced is None or ctx["peaks"] is None:
        return None
    work = dense_work(ctx["cell"]["config"], _tokens(ctx))
    if work is None:
        scopes.say("dense_roofline: the configuration's layers are no "
                   "plain decoder's; no count of its dense part alone")
        return None
    if reduced["stray_matmuls"]:
        scopes.say(f"dense_roofline: {reduced['stray_matmuls']} "
                   f"'{MATMUL_CATEGORY}' event(s) of the loss lie in no "
                   f"block, so the blocks lack matmul time: no share")
        return None
    least = {block: arithmetic.roofline_seconds(w["flops"], w["bytes"],
                                                ctx["peaks"])
             for block, w in work.items()}
    scopes.say("dense roofline: " + ", ".join(
        f"{block} least {s * 1e3:.3f} ms ({bound} bound) of "
        f"{block_ms(reduced, block):.3f}" for block, (s, bound)
        in least.items() if block != "all")
        + f"; all {least['all'][0] * 1e3:.3f} ms a step")
    return 100.0 * least["all"][0] * 1e3 / sum(
        block_ms(reduced, block) for block in BLOCKS)


if __name__ == "__main__":
    say_tables(partition(read_ops(sys.argv[1]), scopes.program_scopes()))
