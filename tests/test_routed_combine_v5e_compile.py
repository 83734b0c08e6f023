"""The routed layer's rows going back to their tokens, compiled for a
described ``v5e:2x2`` (no chip attached) at ``deepseek-v2-lite.train-s4k``'s
shape: ``_rows_to_tokens`` alone, and one routed layer's forward and
backward pass under ``jax.checkpoint``.  Neither holds the gathered
``[T * K, H]`` rows nor their ``[T, K, H]`` float32 reshape, and the
temporaries are what the slot loop needs (PERF.md §6, PR 33).  And the
layer at ``laguna-s-2.1.train-s8k``'s shape, sixteen row buffers of which
one holds rows: its backward pass writes no zeros of a weight's shape
(PERF.md §6, PR 43).  The TPU
compiler is loaded inside a fixture (the on-chip-measurement guide says
why); the recipe is ``tests/test_flash_v5e_compile.py``'s."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.models import llama
from horovod_tpu.models.llama import LlamaConfig, RoutedExperts

# The cell: 4 rows of 4096 tokens, top-6 of 64 experts of 1408, 8 held, two
# shared, so a row buffer of twice an eighth of the 98,304 assignments.
BATCH, SEQUENCE, HIDDEN, K = 4, 4096, 2048, 6
TOKENS = BATCH * SEQUENCE
BUFFER = 24576
#: ``temp_size_in_bytes`` at the parent of PR 33, 0235081 (one gather of all
#: ``T * K`` rows, a ``[T, K, H]`` float32 reshape, a sum over K), compiled
#: with this installation.  The slot loop: 403,432,448 and 2,328,274,432.
GATHERED_TEMPORARIES = 1_476_524_032
GATHERED_LAYER_TEMPORARIES = 3_807_795_712

# The two arrays that must not exist, in any dtype or layout.
_GATHERED = re.compile(rf"\[{TOKENS * K},{HIDDEN}\]|\[{TOKENS},{K},{HIDDEN}\]")


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip, and no persistent cache (a deviceless
    executable cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as error:
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["gates", "liveness"])
def test_a_pass_is_k_gathers_and_one_accumulate(one_chip, weighted):
    rows = _shape((BUFFER, HIDDEN), jnp.bfloat16, one_chip)
    position = _shape((TOKENS * K,), jnp.int32, one_chip)
    arguments = [rows, position]
    if weighted:
        arguments.append(_shape((TOKENS, K), jnp.float32, one_chip))
    compiled = jax.jit(
        lambda rows, position, *weights: llama._rows_to_tokens(
            rows, position, K, *weights)).lower(*arguments).compile()
    text = compiled.as_text()
    assert not _GATHERED.search(text), _GATHERED.findall(text)
    gathers = re.findall(
        rf" = bf16\[{TOKENS},{HIDDEN}\][^\n]* fusion\([^\n]*/gather\"", text)
    assert len(gathers) == K, text
    # One fusion writes the float32 sum, and nothing else of that size is
    # written in float32 (no cast slot copy).
    assert len(re.findall(rf" = f32\[{TOKENS},{HIDDEN}\][^\n]* fusion\(",
                          text)) == 1, text
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries <= 0.45e9 < GATHERED_TEMPORARIES, temporaries


def test_one_routed_layer_forward_and_backward(one_chip):
    """``value_and_grad``, with respect to parameters and input, of the sum
    of squares of the checkpointed layer: the combine and the dispatch's
    transpose inside ``cond``, ``scan`` and ``jax.checkpoint``."""
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=HIDDEN, num_layers=1, num_heads=16,
        num_kv_heads=16, intermediate_size=10944, max_seq_len=SEQUENCE,
        num_experts=64, experts_per_token=K, held_experts=8,
        moe_intermediate_size=1408, shared_experts=2, norm_topk_prob=False,
        dtype=jnp.bfloat16)
    layer = RoutedExperts(cfg)
    assert llama._row_chunk(TOKENS * K, 8 / 64) == BUFFER
    x = _shape((BATCH, SEQUENCE, HIDDEN), jnp.bfloat16, one_chip)
    params = jax.tree.map(
        lambda s: _shape(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda x: layer.init(jax.random.key(0), x), x))

    def loss(params, x):
        y = jax.checkpoint(layer.apply)(params, x)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    text = compiled.as_text()
    assert not _GATHERED.search(text), sorted(set(_GATHERED.findall(text)))
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries <= 2.6e9 < GATHERED_LAYER_TEMPORARIES, temporaries


#: ``temp_size_in_bytes`` of the layer below at the parent of PR 43, f6fe42f
#: (a ``scan`` of ``cond`` s over the sixteen buffers under
#: ``jax.checkpoint``, whose transpose adds each buffer's weight gradients,
#: zeros for a buffer skipped, into accumulators), compiled with this
#: installation.  The backward pass over the live buffers alone: 930,774,528.
SCANNED_SIXTEEN_BUFFERS_TEMPORARIES = 1_500_559_872


def test_a_32nd_of_the_experts_writes_no_zeros_of_a_weights_shape(one_chip):
    """``laguna-s-2.1.train-s8k``'s routed layer: 8192 tokens x 10 choices
    of 256 experts of 1024 at hidden 3072, 8 held and one shared, so sixteen
    buffers of 5120 rows.  The parent's compiled backward pass held three
    ``broadcast`` s each of ``bf16[8,3072,2048]`` and ``bf16[8,1024,3072]``,
    two of them in the loop's body; none is left anywhere, loop or not: the
    first buffer's weight gradients start the sum."""
    tokens, hidden, width, k, held = 8192, 3072, 1024, 10, 8
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=hidden, num_layers=1, num_heads=48,
        num_kv_heads=8, intermediate_size=12288, max_seq_len=tokens,
        num_experts=256, experts_per_token=k, held_experts=held,
        moe_intermediate_size=width, shared_experts=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, dtype=jnp.bfloat16)
    layer = RoutedExperts(cfg)
    assert llama._row_chunk(tokens * k, held / 256) == 5120
    x = _shape((1, tokens, hidden), jnp.bfloat16, one_chip)
    params = jax.tree.map(
        lambda s: _shape(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda x: layer.init(jax.random.key(0), x), x))

    def loss(params, x):
        y = jax.checkpoint(layer.apply)(params, x)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    zeros = re.findall(
        rf"bf16\[{held},(?:{hidden},{2 * width}|{width},{hidden})\]"
        r"[^ ]* broadcast\(", compiled.as_text())
    assert not zeros, zeros
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries <= SCANNED_SIXTEEN_BUFFERS_TEMPORARIES, temporaries
