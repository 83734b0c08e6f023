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

from horovod_tpu.common import scopes, trace_counts
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import rope

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
    monkeypatch.setattr(rope, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash(q, k, v, scale, heads=1):
    """The two calls, causal and with no sideband: the output alone."""
    return fa._flash(q, k, v, None, None, None, True, scale, heads)[0]


def _mosaic_calls(compiled):
    return [line for line in compiled.as_text().splitlines()
            if _MOSAIC_CALL.search(line)]


def _used_scoped_vmem(calls):
    """Bytes of scoped VMEM the compiler gave each of these calls."""
    return [int(n) for call in calls for n in re.findall(
        r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', call)]


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
            _flash(q, k, v, SM_SCALE).astype(jnp.float32)
            * w.astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(x, x, x, x).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == 2, calls
    assert sum(scopes.FLASH_FWD in c for c in calls) == 1
    assert sum(scopes.FLASH_BWD in c for c in calls) == 1
    assert _no_square_array(compiled, s)
    # The forward call states no limit: the loop and the diagonal's pair
    # beside it fit the default.
    used = _used_scoped_vmem(c for c in calls if scopes.FLASH_FWD in c)
    assert used and max(used) <= fa._DEFAULT_SCOPED_VMEM, used


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
    """One layer at ``ouro-2.6b``'s widths, one row of 8192 tokens: the
    step's backward call used 19.8 MiB of scoped VMEM where the call alone
    takes 12 (XLA keeps lse and delta there), and 16 is the default."""
    import flax.linen as nn

    from horovod_tpu.models.llama import LlamaConfig, LlamaModel

    config = LlamaConfig(
        vocab_size=49152, hidden_size=2048, num_layers=1, num_heads=heads,
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
    # Beside the flash calls, heads of 128 are rotated by Mosaic passes of
    # their own (q and k, forward and backward); heads of 64 are not.
    assert sum(scopes.ROPE in call for call in calls) == (
        4 * config.num_layers if heads == 16 else 0)
    calls = [call for call in calls if scopes.ROPE not in call]
    assert len(calls) == 2 * config.num_layers
    assert sum(scopes.FLASH_BWD in call for call in calls) == config.num_layers
    used = _used_scoped_vmem(c for c in calls if scopes.FLASH_BWD in c)
    assert used and max(used) <= fa._bwd_vmem_limit(
        8192, 2048 // heads, 512, 512, 2, 0)


def test_forward_call_holds_a_32k_row_with_the_limit_it_computes(one_chip):
    """K and V are whole rows in VMEM, 16 MiB at 32768 x 128 and twice that
    with the pipeline's second copy: over the compiler's default, which
    refused the call until PR 46.  A call whose blocks pass the default
    states the limit it computes from them (as the backward call always
    has), and compiles with it; walking K and V in blocks is not done."""
    s, d = 32768, 128
    x = jax.ShapeDtypeStruct((16, s, d), jnp.bfloat16, sharding=one_chip)
    assert fa._fwd_vmem_limit(s, d, d, 512, 512, 2, masked=False) > (
        fa._DEFAULT_SCOPED_VMEM)
    compiled = jax.jit(lambda q, k, v: _flash(q, k, v, SM_SCALE)).lower(
        x, x, x).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == 1 and scopes.FLASH_FWD in calls[0], calls
    assert _no_square_array(compiled, s)


# -- a value width of its own: latent attention's 192-wide keys, 128-wide values

def _latent_shapes(one_chip, bh=64, s=4096):
    wide = jax.ShapeDtypeStruct((bh, s, 192), jnp.bfloat16, sharding=one_chip)
    narrow = jax.ShapeDtypeStruct((bh, s, 128), jnp.bfloat16,
                                  sharding=one_chip)
    return wide, narrow


def test_forward_and_backward_at_192_and_128_are_two_mosaic_calls(one_chip):
    """``[4 x 16 heads, 4096, 192 / 128]`` of ``deepseek-v2-lite`` at
    4 x 4096 tokens: the same two calls, q, k, dq and dk 192 wide and v, o,
    dO and dv 128 wide, nothing padded to a common width in HBM."""
    wide, narrow = _latent_shapes(one_chip)
    scale = 1.5896 * 192 ** -0.5

    def grads(q, k, v, w):
        return jax.grad(lambda q, k, v: jnp.sum(
            _flash(q, k, v, scale).astype(jnp.float32)
            * w.astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(wide, wide, narrow, narrow).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == 2, calls
    forward, = (c for c in calls if scopes.FLASH_FWD in c)
    backward, = (c for c in calls if scopes.FLASH_BWD in c)
    assert "bf16[64,4096,128]" in forward.split(" custom-call(")[0]
    result = backward.split(" custom-call(")[0]
    assert result.count("bf16[64,4096,192]") == 2       # dq, dk
    assert result.count("bf16[64,4096,128]") == 1       # dv
    assert _no_square_array(compiled, 4096)
    assert [d.shape for d in jax.eval_shape(grads, wide, wide, narrow,
                                            narrow)] == [
        (64, 4096, 192), (64, 4096, 192), (64, 4096, 128)]


def test_backward_limit_counts_both_widths_and_is_the_old_one_at_one_width():
    """The limit the backward call states: at ``d_v == d_qk`` the number it
    stated before it knew of two widths (30 MiB at [8192, 128], which
    ``test_backward_call_fits_inside_a_decoders_step_at_8k`` holds the
    compiled step to), and at 192 / 128 what its blocks at both widths
    need (VMEM pads 192 lanes to 256)."""
    assert fa._bwd_vmem_limit(8192, 128, 512, 512, 2, 0) == (
        fa._bwd_vmem_limit(8192, 128, 512, 512, 2, 0, d_v=128)) == 2 * (
        3 * 8192 * 128 * 2 + 4 * 512 * 128 * 2 + 2 * 8 * 8192 * 4
        + 8192 * 128 * 4 + 4 * 512 * 512 * 4)
    assert fa._bwd_vmem_limit(4096, 192, 512, 512, 2, 0, d_v=128) == 2 * (
        4096 * (2 * 256 + 128) * 2 + 2 * 512 * (256 + 128) * 2
        + 2 * 8 * 4096 * 4 + 4096 * 256 * 4 + 4 * 512 * 512 * 4)


def test_latent_decoders_step_compiles_with_its_calls_inside_their_limit(
        one_chip):
    """One routed layer at ``deepseek-v2-lite``'s widths (8 of 64 experts
    held; the dense layer that leads the published stack differs in its MLP
    alone, which holds no call, and is not compiled here), 1 x 4096 tokens,
    the layer recomputed with the flash output kept: a forward and a
    backward flash call a layer, the backward one inside the limit it
    states, and the routed layer's grouped products as XLA:TPU's own Mosaic
    calls (``ragged-dot``: two forward, the two again and the four gradient
    products backward, for the first row buffer and once more in the loop
    over those behind it, sixteen a routed layer; the layer's recomputation
    adds none, the walk keeps its inputs alone), which carry no scope of
    the program's."""
    import flax.linen as nn

    from horovod_tpu.models.llama import (LlamaConfig, LlamaModel,
                                          YarnScaling)
    from horovod_tpu.ops.losses import balance_loss

    config = LlamaConfig(
        vocab_size=12800, hidden_size=2048, num_layers=1, num_heads=16,
        num_kv_heads=16, intermediate_size=10944, max_seq_len=4096,
        rms_eps=1e-6, num_experts=64, experts_per_token=6, held_experts=8,
        moe_intermediate_size=1408, shared_experts=2, first_dense_layers=0,
        norm_topk_prob=False, attention_kind="latent", kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling=YarnScaling(40, 4096, 32, 1, 0.707, 0.707),
        remat="layer_keep_attention")
    model = LlamaModel(config, attention_fn=fa.flash_attention_fn)
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        nn.meta.unbox(jax.eval_shape(
            LlamaModel(config).init, jax.random.key(0),
            jnp.zeros((1, 8), jnp.int32))))

    def loss(params, tokens):
        logits, sown = model.apply(params, tokens, mutable=["losses"])
        logits = logits.astype(jnp.float32)
        return (jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0])
                + 0.001 * balance_loss(sown))

    compiled = jax.jit(jax.grad(loss)).lower(params, tokens).compile()
    calls = _mosaic_calls(compiled)
    flash = [c for c in calls if "hvd.flash." in c]
    assert sum(scopes.FLASH_FWD in c for c in flash) == config.num_layers
    assert sum(scopes.FLASH_BWD in c for c in flash) == config.num_layers
    used = _used_scoped_vmem(c for c in flash if scopes.FLASH_BWD in c)
    assert used and max(used) <= fa._bwd_vmem_limit(
        4096, 192, 512, 512, 2, 0, d_v=128)
    grouped = [c for c in calls if c not in flash]
    names = [re.search(r'op_name="([^"]*)"', c).group(1) for c in grouped]
    assert all(n.startswith(scopes.RAGGED_DOT_PREFIX) for n in names), names
    assert sum(n == "ragged-dot-none" for n in names) == 16
    # No score matrix: the only [.., 4096, 4096] is W_kvb's output, sixteen
    # heads of 128 + 128.
    assert not re.search(r"\[(\d+,)*16,4096,4096\]", compiled.as_text())


# -- q, k, v and o cross from the projections to the calls where they lie ------
#
# Through the seam the flash calls index heads in ``[B, S, H * D]`` and the
# rotation is a Mosaic pass on the same view, so between a projection's matmul
# and a call nothing is relaid: no copy or transpose of a tensor a head
# (``[B, H, S, D]`` in any order, a dimension split or not), no gather (the
# ``jnp`` rotation's strided slices) and no scatter (their transpose).

@pytest.mark.parametrize("batch, seq, heads, kv_heads", [
    (4, 2048, 16, 16), (1, 8192, 16, 16), (2, 8192, 32, 4)])
def test_calls_on_operands_in_place_are_two_and_nothing_is_relaid(
        one_chip, batch, seq, heads, kv_heads):
    """The two calls alone on ``[B, S, H * 128]`` operands (``_flash`` with
    its head count): ONE Mosaic call a pass, as on flat operands, and no
    tensor a head is copied, grouped heads' dk and dv included.  The forward
    call states no limit of its own (``test_forward_call_states_no_vmem_
    limit``), so that it compiles says it fits the default: what the
    compiled text calls its used scoped memory holds the operands XLA chose
    to keep in VMEM too, 70 MiB and more here."""
    q = jax.ShapeDtypeStruct((batch, seq, heads * 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((batch, seq, kv_heads * 128), jnp.bfloat16,
                             sharding=one_chip)

    def grads(q, k, v, w):
        return jax.grad(lambda q, k, v: jnp.sum(
            _flash(q, k, v, SM_SCALE, heads).astype(jnp.float32)
            * w.astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(q, k, k, q).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == 2, calls
    forward = next(c for c in calls if scopes.FLASH_FWD in c)
    assert sum(scopes.FLASH_BWD in c for c in calls) == 1
    assert _relaid_by_head(compiled, batch * seq,
                           (heads * 128, kv_heads * 128)) == []
    assert [g.shape for g in jax.eval_shape(grads, q, k, k, q)] == [
        q.shape, k.shape, k.shape]


def test_forward_call_states_no_vmem_limit():
    """In place as flat, the forward call leaves the scoped-VMEM limit to
    the compiler's default (a selection's call and the backward call state
    theirs)."""
    q = jnp.zeros((1, 8192, 16 * 128), jnp.bfloat16)

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    forward, = calls(jax.make_jaxpr(
        lambda q: fa._fwd(q, q, q, True, SM_SCALE, heads=16))(q).jaxpr)
    params = forward.params["compiler_params"]
    assert not params or all(
        getattr(p, "vmem_limit_bytes", None) is None for p in params.values())


def _instructions(compiled, *kinds):
    """``(kind, dims, line)`` of every instruction of these kinds, in the
    entry computation or in a fusion's."""
    found = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= \(?\w+\[([\d,]*)\]\S* (%s)\(" % "|".join(kinds),
                      line)
        if m:
            found.append((m.group(2), tuple(
                int(n) for n in m.group(1).split(",") if n), line))
    return found


def _relaid_by_head(compiled, tokens, widths):
    """Copies and transposes of an array a head: four dimensions or more and
    as many elements as q (or k), so ``[B, H, S, D]`` in any order, S split
    or not.  The calls' own operands have two or three."""
    import math

    return [line for _, dims, line in _instructions(
        compiled, "copy", "transpose")
        if len(dims) >= 4 and math.prod(dims) in {tokens * w for w in widths}]


def _under_attention(compiled, *kinds):
    return [line for _, _, line in _instructions(compiled, *kinds)
            if scopes.BLOCK_ATTN in line]


@pytest.mark.parametrize("batch, seq, heads, kv_heads, selection", [
    (4, 2048, 16, 16, False), (1, 8192, 16, 16, False),
    (2, 8192, 32, 4, True)], ids=["s2k", "s8k", "keye-s8k-b2"])
def test_attention_block_relays_nothing_between_projections_and_calls(
        one_chip, batch, seq, heads, kv_heads, selection):
    """One attention block, forward and backward, at ``ouro-2.6b``'s 4 x 2048
    and 1 x 8192 tokens and at ``keye-vl-2.0``'s 2 x 8192 with 32 query heads
    on 4 key-value heads and a selection: projections, rotation, the two
    flash calls, ``wo``."""
    import flax.linen as nn

    from horovod_tpu.models import llama
    from horovod_tpu.models.llama import (LlamaAttention, LlamaConfig,
                                          rope_freqs)

    d = 128
    config = LlamaConfig(
        vocab_size=512, hidden_size=2048, num_layers=1, num_heads=heads,
        num_kv_heads=kv_heads, attention_head_dim=d, intermediate_size=5632,
        max_seq_len=seq, dtype=jnp.bfloat16)

    def describe(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x = describe((batch, seq, 2048), jnp.bfloat16)
    selected = describe((batch, seq, seq) if selection else (1,), jnp.int8)
    params = jax.tree.map(
        lambda p: describe(p.shape, p.dtype), nn.meta.unbox(jax.eval_shape(
            lambda: LlamaAttention(config).init(
                jax.random.key(0), jnp.zeros((1, 128, 2048), jnp.bfloat16),
                *rope_freqs(d, 128, 1e6)))))

    def loss(params, x, weight, selected):
        attend = fa.flash_attention_fn
        if selection:
            def attend(q, k, v):
                return fa.flash_attention_fn(q, k, v, selected=selected)[0]
            # (A closure around the seam says what the seam says.)
            attend.in_place = fa.flash_attention_fn.in_place
        cos, sin = rope_freqs(d, seq, 1e6)
        with jax.named_scope(scopes.BLOCK_ATTN):
            out = LlamaAttention(
                config, attention_fn=attend,
                in_place=llama._reads_in_place(attend)).apply(
                    params, x, cos, sin)
        return jnp.sum(out.astype(jnp.float32) * weight.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x, x, selected).compile()
    assert _relaid_by_head(compiled, batch * seq,
                           (heads * d, kv_heads * d)) == []
    assert _under_attention(compiled, "gather", "scatter") == []
    calls = _mosaic_calls(compiled)
    assert sum(scopes.FLASH_FWD in c for c in calls) == 1
    assert sum(scopes.FLASH_BWD in c for c in calls) == 1
    assert sum(scopes.ROPE in c for c in calls) == 4 == len(calls) - 2
    assert all(scopes.BLOCK_ATTN in c for c in calls)
    # The calls take rows of tokens, heads side by side: q as the projection
    # wrote it, K and V at their own head count.
    forward = next(c for c in calls if scopes.FLASH_FWD in c)
    operands = forward.split(" custom-call(")[1]
    assert f"bf16[{batch * seq},{heads * d}]" in forward.split(
        " custom-call(")[0]
    assert operands.count(f"bf16[{batch * seq},{kv_heads * d}]") >= 2


def test_decoders_s2k_step_relays_nothing_in_its_attention_blocks(one_chip):
    """One layer at ``ouro-2.6b``'s widths, 4 x 2048 tokens, the whole
    forward and backward pass: in its attention block no tensor a head is
    copied or transposed and nothing gathers or scatters; a forward and a
    backward flash call a layer and four rotation passes, the forward calls
    inside the default scoped VMEM."""
    import flax.linen as nn

    from horovod_tpu.models.llama import LlamaConfig, LlamaModel

    config = LlamaConfig(
        vocab_size=49152, hidden_size=2048, num_layers=1, num_heads=16,
        num_kv_heads=16, intermediate_size=5632, max_seq_len=2048,
        dtype=jnp.bfloat16)
    model = LlamaModel(config, attention_fn=fa.flash_attention_fn)
    tokens = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        nn.meta.unbox(jax.eval_shape(
            model.init, jax.random.key(0), jnp.zeros((1, 128), jnp.int32))))

    def loss(params, tokens):
        logits = model.apply(params, tokens).astype(jnp.float32)
        return jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0])

    compiled = jax.jit(jax.grad(loss)).lower(params, tokens).compile()
    assert _relaid_by_head(compiled, 4 * 2048, (2048,)) == []
    assert _under_attention(compiled, "gather", "scatter") == []
    calls = _mosaic_calls(compiled)
    for scope, a_layer in ((scopes.FLASH_FWD, 1), (scopes.FLASH_BWD, 1),
                           (scopes.ROPE, 4)):
        assert sum(scope in c for c in calls) == a_layer * config.num_layers
    assert len(calls) == 6 * config.num_layers
    rotations = [c for c in calls if scopes.ROPE in c]
    assert all(scopes.BLOCK_ATTN in c and "hvd.flash" not in c
               for c in rotations)


def test_gspmd_step_with_the_models_own_attention_holds_no_mosaic_call(
        one_chip, topo):
    """``parallel/api.py::make_parallel_train_step`` over all four chips
    (data x fsdp x tensor, the partitioner placing the collectives) with the
    model's default dense attention, at a head width of 128 and a sequence
    the rotation's pass would take: a Mosaic call cannot be partitioned
    automatically, so nothing the model does on its own account may be one.
    The rotation is ``apply_rope``'s ``jnp`` form unless the ``attention_fn``
    the model was given reads its operands in place."""
    import flax.linen as nn
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.models.llama import (LlamaConfig, LlamaModel,
                                          rope_freqs)
    from horovod_tpu.parallel.api import (make_parallel_train_step,
                                          param_shardings)

    config = LlamaConfig(
        vocab_size=1024, hidden_size=512, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=1024, max_seq_len=64,
        dtype=jnp.bfloat16)
    assert config.head_dim == 128
    # (A shape the rotation's pass takes where it is asked to.)
    taken = trace_counts.counts(rope.BODY).get(rope.IN_PLACE, 0)
    jax.eval_shape(
        lambda x: rope.rotate(x, *rope_freqs(128, 64, 1e4), in_place=True),
        jax.ShapeDtypeStruct((8, 64, 4, 128), jnp.bfloat16))
    assert trace_counts.counts(rope.BODY)[rope.IN_PLACE] == taken + 1
    mesh = Mesh(np.array(topo.devices).reshape(1, 2, 2),
                ("data", "fsdp", "tensor"))
    model = LlamaModel(config)
    optimizer = optax.sgd(1e-2)
    params = nn.meta.unbox(jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 64), jnp.int32)))

    def placed(tree, shardings):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, shardings)

    params = placed(params, param_shardings(params, mesh))
    state = jax.eval_shape(optimizer.init, params)
    state = placed(state, jax.tree.map(
        lambda _: NamedSharding(mesh, P()), state))
    tokens = jax.ShapeDtypeStruct((8, 65), jnp.int32,
                                  sharding=NamedSharding(mesh, P("fsdp")))
    step = make_parallel_train_step(model, optimizer, mesh)
    compiled = step.lower(params, state, tokens).compile()
    assert _mosaic_calls(compiled) == []
    assert "all-reduce" in compiled.as_text()
