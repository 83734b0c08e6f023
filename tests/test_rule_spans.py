"""Every differentiation rule of the program runs inside a span of its own
(``rule.<op>.fwd`` / ``rule.<op>.bwd``, ``common/scopes.py::rules``): the
registry against the sources, each rule differentiated at a tiny shape, and
the log's count of what it dropped.  Nothing here runs a kernel or reads a
clock: the spans are entered while JAX TRACES, so every case is traced
(``jax.make_jaxpr`` of ``jax.grad``) and never executed."""

import functools
import importlib
import os
import pkgutil
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu
import horovod_tpu.jax as hvd
import horovod_tpu.ops as ops_package
from horovod_tpu.common import compile_cache, scopes
from horovod_tpu.models import llama
from horovod_tpu.parallel import pipeline

for _module in pkgutil.iter_modules(ops_package.__path__):
    importlib.import_module("horovod_tpu.ops." + _module.name)

from horovod_tpu.ops import (flash_attention, gated_delta, gated_norm,  # noqa: E402,E501
                             grouped_matmul, hyper_connection, kda, losses,
                             rope, selective_scan, short_conv, sparse_index,
                             ssd)

PACKAGE = os.path.dirname(os.path.abspath(horovod_tpu.__file__))
F32 = jnp.float32


def _ones(*shape, dtype=F32):
    return jnp.full(shape, 0.5, dtype)


def _tables(seq, d):
    return llama.rope_freqs(d, seq, 1e4)


def _live_buffers_case():
    tokens, k, held, width = 8, 2, 2, 16
    rows = tokens * k
    order = jnp.arange(rows, dtype=jnp.int32)
    return (lambda x, w_gu, w_down, weights: llama._live_buffers(
        x, w_gu, w_down, weights, order, order,
        jnp.asarray([8, 16], jnp.int32), jnp.asarray([8, 8], jnp.int32),
        jnp.int32(rows), rows, k),
        (_ones(tokens, 16), _ones(held, 16, 2 * width),
         _ones(held, width, 16), _ones(tokens, k)))


def _exit_nll_case():
    def head(hidden, w):
        return hidden @ w

    targets = jnp.zeros((4,), jnp.int32)
    return (lambda consts, hidden, weights: losses._weighted_exit_nll(
        head, consts, hidden, weights, targets),
        ((_ones(8, 16),), _ones(2, 4, 8), _ones(2, 4)))


def _broadcast_case():
    hvd.init()
    mesh = hvd.data_parallel_mesh(jax.devices()[:2])
    return (jax.shard_map(
        lambda out, mask: pipeline._broadcast_from_last(out, mask, "data"),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False),
        (_ones(4), _ones(4)))


def _index_loss_case():
    b, s, h, d, n, di = 1, 128, 2, 64, 2, 32
    q_i, k_i, w = _ones(b, s, n, di), _ones(b, s, di), _ones(b, s, n)
    selected = jnp.tril(jnp.ones((b, s, s), jnp.int8))
    return (lambda q_i, k_i, w: sparse_index._index_loss(
        _ones(b, s, h, d), _ones(b, s, h, d), _ones(b, h, s), q_i, k_i, w,
        selected, _ones(b, s), d ** -0.5, di ** -0.5), (q_i, k_i, w))


def _streams_case():
    b, s, n, h = 1, 16, 2, 128
    maps = functools.partial(llama._hc_maps,
                             llama.LlamaConfig(hc_sinkhorn_iters=2), n)
    m = n * (n + 2)

    def both(x, phi, gain, bias, y):
        x, x_in, h_post, h_res = hyper_connection.streams(
            maps, x, phi, gain, bias)
        return hyper_connection.write(x, y + x_in, h_post, h_res)

    return both, (_ones(b, s, n, h), _ones(n * h, m), _ones(m), _ones(m),
                  _ones(b, s, h))


#: op -> () -> (a function of arrays, the arrays it is differentiated in).
#: Shapes are the smallest each rule takes; ``streams`` and ``write`` are
#: one case because ``write`` takes what ``streams`` returned.
CASES = {
    "_rows_of_tokens": lambda: (
        lambda x: llama._rows_of_tokens(
            x, jnp.arange(8, dtype=jnp.int32),
            jnp.arange(8, dtype=jnp.int32), 2), (_ones(4, 16),)),
    "_weighted_rows_to_tokens": lambda: (
        lambda rows, weights: llama._weighted_rows_to_tokens(
            rows, weights, jnp.arange(8, dtype=jnp.int32),
            jnp.arange(8, dtype=jnp.int32), 2),
        (_ones(8, 16), _ones(4, 2))),
    "_live_buffers": _live_buffers_case,
    "_flash": lambda: (
        lambda q: flash_attention.flash_attention(q, q, q, causal=True),
        (_ones(1, 128, 2, 64),)),
    "rotate_pairs": lambda: (
        lambda x: rope.rotate_pairs(x, *_tables(16, 128)),
        (_ones(1, 16, 256),)),
    "norm_rotate_pairs": lambda: (
        lambda x, scale: rope.norm_rotate_pairs(
            x, scale, *_tables(16, 128), 1e-6),
        (_ones(1, 16, 256), _ones(128))),
    "grouped_matmul._mosaic": lambda: (
        lambda rows, w: grouped_matmul._mosaic(
            rows, w, jnp.asarray([64, 64], jnp.int32)),
        (_ones(128, 64), _ones(2, 64, 72))),
    "short_conv": lambda: (
        lambda y, taps: short_conv.short_conv(y, taps, 1, None),
        (_ones(1, 16, 128), _ones(4, 128))),
    "_tril_inverse": lambda: (
        lambda a: gated_delta._tril_inverse(jnp.tril(a, -1), False),
        (_ones(2, 16, 16),)),
    "_rule": lambda: (
        lambda *a: gated_delta.gated_delta_rule(*a, in_place=False),
        (_ones(1, 64, 1, 16), _ones(1, 64, 1, 16), _ones(1, 64, 1, 16),
         -_ones(1, 64, 1), _ones(1, 64, 1))),
    "kda._rule": lambda: (       # the rule with a decay a channel (PR 69)
        lambda *a: kda.kda_rule(*a, in_place=False),
        (_ones(1, 64, 1, 16), _ones(1, 64, 1, 16), _ones(1, 64, 1, 16),
         -_ones(1, 64, 1, 16), _ones(1, 64, 1))),
    "kda.systems_call": lambda: (   # a slab's [n, B, H, C, d_k], interpreted
        kda.systems_call,
        (_ones(1, 1, 1, 8, 128), _ones(1, 1, 1, 8, 128),
         -_ones(1, 1, 1, 8, 128))),
    "walk_rows": lambda: (        # heads go in pairs, each whole lane tiles
        gated_delta.walk_rows,
        (_ones(1, 64, 256), _ones(1, 64, 256), _ones(1, 64, 256),
         -_ones(1, 2, 1, 64), _ones(1, 2, 1, 64))),
    "_heads_copied": lambda: (
        lambda rows: gated_delta._heads_copied(rows, 1, 2),
        (_ones(1, 16, 128),)),
    "skip_gate_norm": lambda: (
        lambda y, u, z, d, w: gated_norm.skip_gate_norm(
            y, u, z, d, w, 1, 1e-5),
        (_ones(1, 16, 256), _ones(1, 16, 256), _ones(1, 16, 256), _ones(2),
         _ones(256))),
    "norm_gate": lambda: (
        lambda o, z, w: gated_norm.norm_gate(o, z, w, 2, 1e-5),
        (_ones(1, 16, 256), _ones(1, 16, 256), _ones(128))),
    "selective_scan._mosaic": lambda: (
        lambda u, delta, a_log, b, c, d: selective_scan._mosaic(
            u, delta, a_log, b, c, d, True),
        (_ones(1, 128, 128), _ones(1, 128, 128), _ones(128, 16),
         _ones(1, 128, 16), _ones(1, 128, 16), _ones(128))),
    "scan_rows": lambda: (
        lambda x, dt, a_log: ssd.scan_rows(x, dt, a_log, 2, 1),
        (_ones(1, 128, 2 * 64 + 2 * 128), _ones(1, 128, 2), _ones(2))),
    "_index_loss": _index_loss_case,
    "streams": _streams_case,
    "write": _streams_case,
    "_nll": lambda: (
        lambda logits: losses._nll(logits, jnp.zeros((4,), jnp.int32)),
        (_ones(4, 8),)),
    "_weighted_exit_nll": _exit_nll_case,
    "_broadcast_from_last": _broadcast_case,
}


def _now():
    """This moment on the axis of the log's ``began``."""
    return time.perf_counter() - horovod_tpu.IMPORT_BEGAN


def test_every_defvjp_of_the_package_goes_through_the_registry():
    """A kernel added later cannot skip the decorator: the sources hold as
    many ``.defvjp(`` as the registry has pairs, each handed ``rules``'
    pair, and the cases below are the registry's."""
    calls = []
    for folder, _, files in os.walk(PACKAGE):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as source:
                    calls += re.findall(r"\.defvjp\((\*_scopes\.rules\()?",
                                        source.read())
    assert len(calls) == len(scopes.RULES) >= 22
    assert all(calls), "a defvjp that takes its rules bare"
    assert sorted(CASES) == sorted(scopes.RULES)
    for op, pair in scopes.RULES.items():
        assert all(callable(rule) and rule.__wrapped__ for rule in pair), op
    with pytest.raises(ValueError, match="both named"):
        scopes.rules("_flash", len, len)


@pytest.mark.parametrize("op", sorted(CASES))
def test_a_rule_differentiated_leaves_its_two_spans(op):
    """``jax.grad`` of the op at a tiny shape: one ``rule.<op>.fwd`` and
    one ``rule.<op>.bwd``, the forward first, with no flag; whatever scope
    or Mosaic bind a rule enters nests INSIDE its span."""
    jax.clear_caches()      # an inlined jit's Python runs once a shape
    f, args = CASES[op]()
    began = _now()
    jax.make_jaxpr(jax.grad(
        lambda *a: sum(jnp.sum(leaf.astype(F32))
                       for leaf in jax.tree.leaves(f(*a))),
        argnums=tuple(range(len(args)))))(*args)
    spans = [s for s in hvd.compile_spans() if s["began"] >= began]
    fwd, bwd = (f"{scopes.RULE}{op}.{half}" for half in ("fwd", "bwd"))
    mine = [s for s in spans if s["name"] in (fwd, bwd)]
    assert [s["name"] for s in mine] == [fwd, bwd], [
        s["path"] for s in spans]
    for span in mine:
        assert set(span) == {"name", "path", "began", "seconds",
                             "self_seconds"}
        assert 0 <= span["self_seconds"] <= span["seconds"]
    # Traced at the top of a thread: a rule's span is a root, or lies in
    # another rule's that called JAX back (``streams`` / ``write``: no).
    assert mine[0]["path"] == fwd and mine[1]["path"] == bwd
    assert mine[0]["began"] + mine[0]["seconds"] <= mine[1]["began"]
    for span in spans:
        if span["name"].startswith((scopes.MOSAIC, "hvd.")):
            assert span["path"].split("/")[0].startswith(scopes.RULE), (
                span["path"])


def test_a_rule_called_by_name_enters_no_span():
    """``_weighted_exit_nll_fwd`` calls ``_nll_bwd`` itself: the program's
    own call of a rule is the rule bare."""
    began = _now()
    logits = _ones(4, 8)
    targets = jnp.zeros((4,), jnp.int32)
    nll, kept = losses._nll_fwd(logits, targets)
    losses._nll_bwd(kept, nll)
    assert [s for s in hvd.compile_spans() if s["began"] >= began] == []
    fwd, bwd = scopes.RULES["_nll"]
    assert fwd.__wrapped__ is losses._nll_fwd
    assert bwd.__wrapped__ is losses._nll_bwd and bwd.__name__ == "_nll_bwd"


def test_the_log_counts_what_it_evicts():
    class Small(compile_cache.CompileLog):
        MAX_SPANS = 4
        MAX_RECORDS = 3

    log = Small()
    assert log.evicted() == {"records": 0, "spans": 0}
    for i in range(4):
        with log.span(f"s{i}"):
            pass
    assert log.evicted()["spans"] == 0
    for i in range(3):
        with log.span(f"t{i}"):
            pass
    log.add_span("from stamps", 1.0, 2.0)
    assert log.evicted() == {"records": 0, "spans": 4}
    assert [s["name"] for s in log.spans()] == ["from stamps", "t0", "t1",
                                                "t2"]
    for i in range(5):
        log._on_duration("/jax/core/compile/backend_compile_duration", 0.5,
                         fun_name=f"jit(f{i})")
    assert log.evicted() == {"records": 2, "spans": 4}
    assert [r["program"] for r in log.records()] == [
        "jit(f2)", "jit(f3)", "jit(f4)"]
    # A copy, and the process's own log through the package.
    log.evicted()["spans"] = 0
    assert log.evicted()["spans"] == 4
    assert set(hvd.compile_evicted()) == {"records", "spans"}
    assert "compile_evicted" in hvd.__all__


def test_a_stamp_is_the_innermost_open_spans_flag():
    log = compile_cache.CompileLog()
    log.stamp("nothing open")                  # no span: nothing kept
    with log.span("outer"):
        with log.span("inner"):
            log.stamp("here")
        log.stamp(scopes.FORWARD_SECONDS)
    inner, outer = sorted(log.spans(), key=lambda s: s["name"])
    assert 0 <= inner["here"] <= inner["seconds"]
    assert scopes.FORWARD_SECONDS not in inner and "here" not in outer
    assert inner["seconds"] <= outer[scopes.FORWARD_SECONDS] <= outer[
        "seconds"]


def test_a_layers_span_is_named_from_its_kind():
    log_began = _now()
    for args, name in (
            ((llama.SELF_ATTENTION, llama.DENSE), "layer.attention.dense"),
            ((llama.SELF_ATTENTION, llama.ROUTED, 4096),
             "layer.attention+window.routed"),
            ((llama.DELTA_RULE, llama.ROUTED),
             "layer.linear_attention.routed"),
            ((llama.MAMBA2, None), "layer.mamba2.none"),
            ((None, llama.ROUTED), "layer.none.routed"),
            (("resnet", "stage2"), "layer.resnet.stage2")):
        with scopes.layer_span(*args):
            pass
        last = [s for s in hvd.compile_spans() if s["began"] >= log_began][-1]
        assert last["name"] == last["path"] == name
        assert name.startswith(scopes.LAYER)
    assert set(llama.MIXERS) >= {llama.SELF_ATTENTION, llama.DELTA_RULE,
                                 llama.MAMBA2}
