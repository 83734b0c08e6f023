"""The flash kernel compiled for a described ``v5e:2x2`` (no chip attached):
a forward and a backward pass are ONE Mosaic call each at the benchmark's
shapes, alone and inside a decoder's train step (where XLA puts operands of
the call into the call's own VMEM scope, which the compiler's default limit
does not hold at 8k), and the backward call holds a 16k row, all with the
limit the call computes from its shapes.  The TPU compiler is loaded inside
a fixture (the on-chip-measurement guide says why); the manner is
``tests/test_fusion_v5e_compile.py``'s."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.common import scopes
from horovod_tpu.ops import flash_attention as fa

_MOSAIC_CALL = re.compile(r' = .*custom_call_target="tpu_custom_call"')
SM_SCALE = 128 ** -0.5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as error:
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")


@pytest.fixture
def one_chip(topo, monkeypatch):
    """Kernels take their non-interpreted path, and nothing is read from or
    written to a persistent cache (a deviceless executable cannot be read
    back)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _mosaic_calls(compiled):
    return [line for line in compiled.as_text().splitlines()
            if _MOSAIC_CALL.search(line)]


def _no_square_array(compiled, s):
    """No array whose two trailing dimensions are both the sequence."""
    return not re.search(rf"\[(\d+,)*{s},{s}\]", compiled.as_text())


@pytest.mark.parametrize("bh, s, d", [(16, 8192, 128), (64, 2048, 128)])
def test_forward_and_backward_are_two_mosaic_calls_at_the_cells_shapes(
        one_chip, bh, s, d):
    """``[heads, S, D]`` of ``ouro-2.6b`` at 1 x 8192 and 4 x 2048 tokens."""
    x = jax.ShapeDtypeStruct((bh, s, d), jnp.bfloat16, sharding=one_chip)

    def grads(q, k, v, w):
        return jax.grad(lambda q, k, v: jnp.sum(
            fa._flash(q, k, v, True, SM_SCALE).astype(jnp.float32)
            * w.astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(x, x, x, x).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == 2, calls
    assert sum(scopes.FLASH_FWD in c for c in calls) == 1
    assert sum(scopes.FLASH_BWD in c for c in calls) == 1
    assert _no_square_array(compiled, s)


@pytest.mark.parametrize("sideband", [None, "bias", "seg"])
def test_backward_call_holds_a_16k_row_with_the_limit_it_computes(
        one_chip, sideband):
    """The backward call alone: the forward call refuses S = 16384 under
    the default limit today (K and V whole and double-buffered are 16 MiB),
    as the parent's did."""
    bh, s, d = 16, 16384, 128
    assert fa._bwd_vmem_limit(s, d, 512, 512, 2, sideband is not None) > (
        fa._DEFAULT_SCOPED_VMEM)
    x = jax.ShapeDtypeStruct((bh, s, d), jnp.bfloat16, sharding=one_chip)
    stat = jax.ShapeDtypeStruct((bh, 8, s), jnp.float32, sharding=one_chip)
    extra = {} if sideband is None else {sideband: jax.ShapeDtypeStruct(
        (1, 8, s), jnp.float32 if sideband == "bias" else jnp.int32,
        sharding=one_chip)}

    def backward(q, k, v, out, lse, do, extra):
        return fa._bwd_impl(True, SM_SCALE, (q, k, v, out, lse), do, **extra)

    compiled = jax.jit(backward).lower(x, x, x, x, stat, x, extra).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == 1 and scopes.FLASH_BWD in calls[0], calls
    assert _no_square_array(compiled, s)



@pytest.mark.parametrize("heads", [16, 32])       # of 128 and of 64
def test_backward_call_fits_inside_a_decoders_step_at_8k(one_chip, heads):
    """Two layers at ``ouro-2.6b``'s widths, one row of 8192 tokens: the
    step's backward calls used 19.8 MiB of scoped VMEM each where the call
    alone takes 12 (XLA keeps lse and delta there), and 16 is the default."""
    import flax.linen as nn

    from horovod_tpu.models.llama import LlamaConfig, LlamaModel

    config = LlamaConfig(
        vocab_size=49152, hidden_size=2048, num_layers=2, num_heads=heads,
        num_kv_heads=heads, intermediate_size=5632,
        max_seq_len=8192, dtype=jnp.bfloat16)
    model = LlamaModel(config, attention_fn=fa.flash_attention_fn)
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        nn.meta.unbox(jax.eval_shape(
            model.init, jax.random.key(0), jnp.zeros((1, 8192), jnp.int32))))

    def loss(params, tokens):
        logits = model.apply(params, tokens).astype(jnp.float32)
        return jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0])

    compiled = jax.jit(jax.grad(loss)).lower(params, tokens).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == 2 * config.num_layers
    assert sum(scopes.FLASH_BWD in call for call in calls) == config.num_layers
    used = [int(n) for call in calls if scopes.FLASH_BWD in call
            for n in re.findall(r'"used_scoped_memory_configs":\[\{[^}]*'
                                r'"size":"(\d+)"', call)]
    assert used and max(used) <= fa._bwd_vmem_limit(
        8192, 2048 // heads, 512, 512, 2, 0)


def test_forward_call_refuses_a_32k_row_as_before(one_chip):
    """K and V are whole rows in VMEM; walking them in blocks is not done."""
    x = jax.ShapeDtypeStruct((16, 32768, 128), jnp.bfloat16,
                             sharding=one_chip)
    with pytest.raises(Exception, match="vmem"):
        jax.jit(lambda q, k, v: fa._flash(q, k, v, True, SM_SCALE)).lower(
            x, x, x).compile()
