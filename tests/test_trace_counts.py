"""``common/trace_counts.py``: the one table behind ``fallback_count``,
``layout_counts``, ``body_counts`` and ``update_counts``; and the one place
``models/llama.py`` reads "may this trace hold Mosaic calls"."""

import functools
import sys
import threading
import warnings

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

import horovod_tpu.jax as hvd
from horovod_tpu.common import trace_counts
from horovod_tpu.models import llama
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import short_conv


def test_kinds_and_reasons_are_counted_apart_and_a_reason_warns_once():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        trace_counts.note("test.a", "one way")
        trace_counts.note("test.a", "one way")
        trace_counts.note("test.a", "another", warn="kernel not used: ")
        trace_counts.note("test.a", "another", warn="kernel not used: ")
        trace_counts.note("test.b", "one way", warn="kernel not used: ")
    assert trace_counts.counts("test.a") == {"one way": 2, "another": 2}
    assert trace_counts.counts("test.b") == {"one way": 1}
    assert trace_counts.counts("test.c") == {}
    assert [str(w.message) for w in caught] == [
        "kernel not used: another", "kernel not used: one way"]
    assert all(w.category is RuntimeWarning for w in caught)
    # A kind that describes its last trace alone is replaced whole.
    trace_counts.note_last("test.a", {"alone": 3, "fused": 4})
    assert trace_counts.counts("test.a") == {"alone": 3, "fused": 4}
    assert trace_counts.counts("test.b") == {"one way": 1}


def test_the_four_views_keep_their_shapes():
    assert isinstance(fa.fallback_count(), int)
    layouts = fa.layout_counts()
    assert set(layouts) == {"in_place", "flat"}
    assert isinstance(layouts["in_place"], int)
    assert fa._IN_PLACE not in layouts["flat"]
    bodies = short_conv.body_counts()
    assert set(bodies) == {"fused", "plain"}
    assert isinstance(bodies["fused"], int)
    assert short_conv._FUSED not in bodies["plain"]
    assert list(hvd.update_counts()) == ["alone", "fused"]
    # What a view returns is the caller's own: the table is not reached.
    layouts["flat"]["mine"] = 1
    bodies["plain"]["mine"] = 1
    assert "mine" not in fa.layout_counts()["flat"]
    assert "mine" not in short_conv.body_counts()["plain"]
    # Each view moves with its own kind alone.
    before = (fa.fallback_count(), fa.layout_counts(),
              short_conv.body_counts(), hvd.update_counts())
    trace_counts.note(fa._FALLBACK, "a reason of this test's")
    assert fa.fallback_count() == before[0] + 1
    assert (fa.layout_counts(), short_conv.body_counts(),
            hvd.update_counts()) == before[1:]


def test_counts_from_many_threads_add_up():
    threads, each = 16, 2000
    start = threading.Barrier(threads)

    def work(i):
        start.wait()
        for _ in range(each):
            trace_counts.note("test.threads", i % 2)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=work, args=(i,))
                   for i in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert trace_counts.counts("test.threads") == {
        0: threads // 2 * each, 1: threads // 2 * each}


@pytest.mark.parametrize("name, attention_fn, in_place", [
    ("the flash seam", fa.flash_attention_fn, True),
    ("a functools.partial of it", functools.partial(
        fa.flash_attention_fn, segment_ids=jnp.zeros((1, 128), jnp.int32)),
     True),
    ("the model's own dense attention", llama.causal_attention, False)])
def test_the_model_reads_the_rule_once_and_every_mixer_gets_the_answer(
        name, attention_fn, in_place, monkeypatch):
    """``LlamaLayer`` asks ``_reads_in_place`` of the ``attention_fn`` it
    was given, once a layer, and hands the answer to the mixer it builds as
    the field ``in_place``: the linear layers and the attention layers of a
    hybrid stack hold the same one, and no mixer asks for itself."""
    config = llama.LlamaConfig(
        vocab_size=64, hidden_size=256, num_layers=4, num_heads=2,
        num_kv_heads=1, intermediate_size=128, max_seq_len=128,
        layer_types=("linear_attention", "full_attention") * 2,
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=24, linear_value_head_dim=48,
        linear_conv_kernel_dim=4)
    asked, given = [], []
    reads = llama._reads_in_place

    def counted(fn):
        asked.append(fn)
        return reads(fn)

    def watch(next_fun, args, kwargs, context):
        if (context.method_name == "__call__" and isinstance(
                context.module, (llama.GatedDeltaNet, llama.LlamaAttention))):
            given.append((type(context.module).__name__,
                          context.module.in_place))
        return next_fun(*args, **kwargs)

    monkeypatch.setattr(llama, "_reads_in_place", counted)
    model = llama.LlamaModel(config, attention_fn=attention_fn)
    tokens = jnp.zeros((1, 128), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)
    asked.clear()
    before = short_conv.body_counts()
    with nn.intercept_methods(watch):
        jax.eval_shape(model.apply, params, tokens)
    assert asked == [attention_fn] * config.num_layers, name
    assert given == [("GatedDeltaNet", in_place),
                     ("LlamaAttention", in_place)] * 2, name
    # And each op took the body the answer allows (two linear layers, three
    # convolutions each).
    after = short_conv.body_counts()
    assert after["fused"] - before["fused"] == (6 if in_place else 0)
