"""The ``nemotron-3-nano-30b-a3b.train-s8k-b2`` cell's layers compiled for a
described ``v5e:2x2`` (no chip attached), a LAYER at a time at the cell's
size, beside ``tests/test_qwen3_next_v5e_compile.py`` and in its manner: the
flash pair at 32 query heads over 2 key-value heads of 128 without a rotation;
a routed layer's grouped products (relu2: two matrices an expert); the
convolution over a Mamba-2 layer's 6,144 channels, which is the Mosaic pass
with its bias as without one, on an array of their own or where they lie in
``in_proj``'s output; and a Mamba-2 layer whole, forward and backward, whose
Mosaic calls are that filter's, the scan's (``ops/ssd.py``: the chunks walked
with the state in VMEM, u, B and C read where the filter left them) and the
gates' (``ops/gated_norm.py``: the skip, the gate and the grouped norm), one
call each way each, on operands in place.  The whole step at 2 x 8192 is
compiled by the builder's study and on the chip, not here (it takes a
minute)."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import manifest
from horovod_tpu.common import scopes
from horovod_tpu.models import llama
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import gated_norm, grouped_matmul, short_conv, ssd

CELL = "nemotron-3-nano-30b-a3b.train-s8k-b2"
_MOSAIC_CALL = re.compile(r' = .*custom_call_target="tpu_custom_call"')
B, S = 2, 8192


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
    """The kernels' non-interpreted bodies, and no persistent cache (a
    deviceless executable cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    for module in (fa, short_conv, grouped_matmul, gated_norm, ssd):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def config():
    cell = manifest.cell(CELL)
    job = manifest.load_job(cell["config"]["job"]).build(
        cell["config"], cell["traffic"], 1)
    return job.llama


def _mosaic_calls(text):
    return [line for line in text.splitlines() if _MOSAIC_CALL.search(line)]


def _layer_compiled(module, one_chip, hidden):
    """``module``'s forward and backward pass on ``bf16[2, 8192, hidden]``
    with parameters as it initialises them, compiled."""
    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    x = jax.ShapeDtypeStruct((B, S, hidden), jnp.bfloat16, sharding=one_chip)
    variables = jax.eval_shape(
        lambda k: module.init(k, jnp.zeros((1, 8, hidden), jnp.bfloat16)),
        jax.random.key(0))

    def grads(variables, x):
        return jax.grad(lambda p, x: jnp.sum(module.apply(
            {**variables, "params": p}, x).astype(jnp.float32)),
            argnums=(0, 1))(variables["params"], x)

    return jax.jit(grads).lower(jax.tree.map(sds, variables), x).compile()


def test_the_flash_pair_compiles_at_32_over_2_heads_of_128(one_chip, config):
    """Forward and backward through the seam at 2 x 8192 tokens, 32 query
    heads in groups of 16 over 2 key-value heads of 128, in place, no
    rotation before it: two Mosaic calls and no ``[S, S]`` array."""
    assert (config.num_heads, config.num_kv_heads, config.head_dim,
            config.rope_theta) == (32, 2, 128, None)

    def sds(n):
        return jax.ShapeDtypeStruct((B, S, n, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *x: jnp.sum(fa.flash_attention_fn(
            *x).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    before = fa.layout_counts()
    text = jax.jit(grads).lower(sds(32), sds(2), sds(2)).compile().as_text()
    assert fa.layout_counts()["in_place"] == before["in_place"] + 1
    calls = _mosaic_calls(text)
    assert len(calls) == 2
    assert sum(scopes.FLASH_FWD in c for c in calls) == 1
    assert sum(scopes.FLASH_BWD in c for c in calls) == 1
    assert not re.findall(rf"\w+\[(?:\d+,)*{S},{S}\]", text)


@pytest.mark.parametrize("in_place", [False, True],
                         ids=["a partitioned trace", "in place"])
def test_a_routed_layers_grouped_products(one_chip, config, in_place):
    """8 of 128 relu2 experts at 2 x 8192 tokens, 6 choices a token: 98,304
    assignments through buffers of 12,288 rows (``_live_buffers``: the first
    buffer and the loop's body), forward: two grouped products a buffer where
    a SwiGLU expert has two as well, over ``w_up [8, 2688, 1856]``, and no
    gate's split.  1856 is 14.5 lane tiles: in a trace that may be
    partitioned the four are ``ragged_dot``, in place they are the Mosaic
    grouped matmul (``ops/grouped_matmul.py``: ``gmm`` in blocks of 256 rows
    with the contracted width whole), and either way they read the matrices
    at the parameters' shapes: no padded copy is made.  LOWERED for the
    described chip, not compiled: XLA:TPU's own grouped kernels take 23 s to
    compile, which the suite has not."""
    module = llama.RoutedExperts(config, in_place=in_place)
    variables = jax.eval_shape(
        lambda k: module.init(k, jnp.zeros((1, 8, 2688), jnp.bfloat16)),
        jax.random.key(0))
    x = jax.ShapeDtypeStruct((B, S, 2688), jnp.bfloat16, sharding=one_chip)
    before = grouped_matmul.body_counts()
    llama._one_buffer.clear_cache()     # it keeps its traces by shape
    lowered = jax.jit(module.apply).lower(jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip),
        variables), x)
    text = lowered.as_text()
    after = grouped_matmul.body_counts()
    xla = [line for line in text.splitlines() if "ragged_dot" in line]
    mosaic = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert (len(xla), len(mosaic)) == ((0, 4) if in_place else (4, 0))
    if in_place:
        assert after["mosaic"] == before["mosaic"] + 4
        assert after["xla"] == before["xla"]
        # The program's own calls compile in seconds, and carry the scope
        # that ``moe_experts_ms`` reads.
        calls = _mosaic_calls(lowered.compile().as_text())
        assert len(calls) == 4
        assert all(scopes.MOE_EXPERTS in call for call in calls)
    else:
        assert after["mosaic"] == before["mosaic"]
        assert after["xla"][grouped_matmul.NOT_IN_PLACE] == before["xla"].get(
            grouped_matmul.NOT_IN_PLACE, 0) + 4
    for line in xla + mosaic:
        assert ("8x2688x1856xbf16" in line) != ("8x1856x2688xbf16" in line)
        assert "12288x2688xbf16" in line and "12288x1856xbf16" in line
    assert "8x2688x1856xbf16" in text and "8x2688x3712xbf16" not in text
    assert "stablehlo.pad" not in "".join(
        line for line in text.splitlines() if "x1856x" in line)
    assert "2688x3712xbf16" in text          # the shared expert's own width


@pytest.mark.parametrize("bias, first", [
    (False, None), (True, None), (True, 4096)],
    ids=["no bias", "bias", "bias, in in_proj's output"])
def test_the_filter_over_6144_channels(one_chip, bias, first):
    """``short_conv``'s Mosaic pass takes ``[2, 8192, 6144]`` (48 lane
    tiles, one head, no norm; blocks of 256 rows), with a Mamba-2 layer's
    bias as without one: one call each way, and with a bias its gradient
    ``f32[6144]`` among the results, summed from the backward call's
    ``[B, (K + 1) * 8, C]`` partial sums (``[B, K * 8, C]`` without).  And
    as the layer hands them over: channels 4096.. of ``in_proj``'s
    ``[2, 8192, 10304]``, read by element windows with no cut before the
    calls."""
    y = jax.ShapeDtypeStruct((B, S, 6144 if first is None else 10304),
                             jnp.bfloat16, sharding=one_chip)
    taps = jax.ShapeDtypeStruct((4, 6144), jnp.float32, sharding=one_chip)
    offset = jax.ShapeDtypeStruct((6144,), jnp.float32, sharding=one_chip)

    def grads(y, taps, offset):
        return jax.value_and_grad(lambda y, taps, offset: jnp.sum(
            short_conv.convolved(
                y, taps, 1, None, True, bias=offset if bias else None,
                first=first).astype(jnp.float32)),
            argnums=(0, 1, 2))(y, taps, offset)

    before = short_conv.body_counts()
    compiled = jax.jit(grads).lower(y, taps, offset).compile()
    after = short_conv.body_counts()
    assert after["fused"] == before["fused"] + 1
    assert after["plain"] == before["plain"]
    text = compiled.as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 2
    sums = f"f32[{B},{(4 + bias) * 8},6144]"
    assert sum(sums in call for call in calls) == 1, sums
    _, (d_y, _, d_offset) = compiled.out_info
    assert d_y.shape == y.shape
    assert d_offset.shape == (6144,) and d_offset.dtype == jnp.float32
    if first is not None:
        assert all(f"bf16[{B},{S},10304]" in call for call in calls)
        assert not re.search(rf" = bf16\[{B},{S},6144\]\S* slice\(", text)


_LAYER = {}     # the one compile of a Mamba-2 layer, for the tests that read it


@pytest.fixture
def mamba_layer(one_chip, config):
    """A ``Mamba2`` layer at 2 x 8192 tokens and the published widths
    (``inner`` 4096, 8 groups, ``in_proj`` 10304 wide), in place, forward and
    backward: its compiled text, its temporaries' bytes, and the two op
    modules' ``body_counts()`` before and after the trace.  Compiled by the
    first test that asks."""
    if not _LAYER:
        before = (short_conv.body_counts(), gated_norm.body_counts(),
                  ssd.body_counts())
        compiled = _layer_compiled(llama.Mamba2(config, in_place=True),
                                   one_chip, config.hidden_size)
        _LAYER.update(
            text=compiled.as_text(),
            temporaries=compiled.memory_analysis().temp_size_in_bytes,
            conv=(before[0], short_conv.body_counts()),
            gates=(before[1], gated_norm.body_counts()),
            scan=(before[2], ssd.body_counts()))
    return _LAYER


def test_a_mamba_layers_mosaic_calls_are_the_filters_and_the_gates(
        mamba_layer):
    """A ``Mamba2`` layer at 2 x 8192 tokens, forward and backward: six
    Mosaic calls, one each way under each of ``hvd.ssd.conv`` (the biased
    filter, ``short_conv``'s pass, where ``ssd_conv_ms`` reads them),
    ``hvd.ssd.scan`` (``ops/ssd.py``'s pair: the chunks walked with the state
    in VMEM) and ``hvd.ssd.gates`` (``gated_norm``'s).  Of the ``jnp`` scan
    nothing is left in the text: no ``while``, no ``reduce-window`` (the
    log-decays' ``cumsum`` was one), no array of a chunk's ``[128, 128]`` a
    slab (``f32[8,2,8,8,128,128]``, 67 MB each); and y goes from the scan's
    forward call to the gates' as the rows it is, with no transposing
    ``copy`` between the two."""
    text = mamba_layer["text"]
    before, after = mamba_layer["conv"]
    assert after["fused"] == before["fused"] + 1
    # (``_layer_compiled`` initialises on 8 rows, which no block divides.)
    assert {why for why, n in after["plain"].items()
            if n != before["plain"].get(why, 0)} == {short_conv._NO_ROW_BLOCK}
    before, after = mamba_layer["scan"]
    assert after["mosaic"] == before["mosaic"] + 2      # the init's 8 rows,
    assert after["plain"] == before["plain"]            # padded, and these
    calls = _mosaic_calls(text)
    assert len(calls) == 6
    for scope in (scopes.SSD_CONV, scopes.SSD_SCAN, scopes.SSD_GATES):
        assert sum(scope in call for call in calls) == 2, scope
    assert " while(" not in text and "reduce-window" not in text
    assert not re.search(r"f32\[\d+,2,8,8,128,128\]", text)
    # The scan's calls read u, B and C in the filter's result and write
    # rows; the forward call's y is the gates' operand itself.
    scan = [call for call in calls if scopes.SSD_SCAN in call]
    forward, = (call for call in scan if "jit(_forward)" in call)
    backward, = (call for call in scan if "jit(_backward)" in call)
    assert forward.count(f"bf16[{B},{S},6144]{{2,1,0}}") == 3
    assert backward.count(f"bf16[{B},{S},6144]{{2,1,0}}") == 3
    y = re.match(r"\s*(%[\w.\-]+) = ", forward).group(1)
    made = {name: line for line in text.splitlines()
            for name in re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = ", line)}
    gates, = (call for call in calls
              if scopes.SSD_GATES in call and "jit(_forward)" in call)
    operand = re.search(r"custom-call\((%[\w.\-]+)", gates).group(1)
    while " get-tuple-element(" in made[operand] or " bitcast(" in made[
            operand]:
        operand = re.search(r"(?:get-tuple-element|bitcast)\((%[\w.\-]+)",
                            made[operand]).group(1)
    assert operand == y, (operand, y)
    assert not re.search(rf" = bf16\[{B},{S},4096\]\S* copy\(", text)
    # The filter's calls read its channels in ``in_proj``'s output: no
    # ``bf16[2, 8192, 6144]`` cut of them, which as the backward call's
    # residual is 201 MB more.  (Temporaries: 1.483 GB, of which the
    # chunks' starting states that the scan's backward call reads are 268
    # MB; 1.493 with the ``jnp`` scan, 2.030 before the gates were a pass of
    # their own, 2.231 with the cut.)
    assert all(f"bf16[{B},{S},10304]" in call for call in calls
               if scopes.SSD_SCAN not in call)
    assert not re.search(rf" = bf16\[{B},{S},6144\]\S* slice\(", text)
    assert mamba_layer["temporaries"] < 1.49e9


def test_a_mamba_layers_gates_are_one_call_each_way_on_operands_in_place(
        mamba_layer):
    """Under ``hvd.ssd.gates`` exactly ``ops/gated_norm.py``'s two calls:
    forward ``(y, u, z, D, w) -> out``, backward ``(.., go) -> dy, du, dz``
    and the partial sums of dw and dD.  u is the filter's ``[2, 8192,
    6144]`` result and z ``in_proj``'s ``[2, 8192, 10304]`` output, the
    arrays themselves (a block of rows is their first 4096 lanes): no
    ``[2, 8192, 4096]`` cut of ``in_proj``'s output is made anywhere, no
    float32 view of the norm's groups (``f32[.., 8, 512]``: 13 ms of
    re-tiling a step before, PERF.md §5) and no float32 array of the
    activations' shape.  The calls fit the VMEM they ask for (the compile
    refuses one that does not), which is the module's stated limit."""
    text = mamba_layer["text"]
    before, after = mamba_layer["gates"]
    assert after["mosaic"] == before["mosaic"] + 1
    assert {why for why, n in after["plain"].items()
            if n != before["plain"].get(why, 0)} == {gated_norm.NO_ROW_BLOCK}
    calls = [call for call in _mosaic_calls(text) if scopes.SSD_GATES in call]
    assert len(calls) == 2
    wide = (f"bf16[{B},{S},4096]{{2,1,0}}, bf16[{B},{S},6144]{{2,1,0}}, "
            f"bf16[{B},{S},10304]{{2,1,0}}, ")
    activation = rf"bf16\[{B},{S},4096\]\S*"
    forward, = (call for call in calls if "jit(_forward)" in call)
    backward, = (call for call in calls if "jit(_backward)" in call)
    assert "transpose(" not in forward and "transpose(" in backward
    assert f"operand_layout_constraints={{{wide}f32[1,4096]" in forward
    assert re.match(rf"\s*%\S+ = {activation} custom-call\(", forward)
    assert (f"operand_layout_constraints={{{wide}bf16[{B},{S},4096]{{2,1,0}}, "
            "f32[1,4096]") in backward
    assert re.match(rf"\s*%\S+ = \({activation}, {activation}, {activation}, "
                    rf"f32\[{B},16,4096\]\S*\) custom-call\(", backward)
    sliced = re.findall(rf" = bf16\[{B},{S},4096\]\S* slice\((%[\w.\-]+)\)",
                        text)
    made = {name: line for line in text.splitlines()
            for name in re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = ", line)}
    assert all(f"bf16[{B},{S},10304]" not in made[name].split(" = ")[1].split(
        "(")[0] for name in sliced), sliced
    assert not re.search(r"f32\[[\d,]+,8,512\]", text)
    assert not re.search(rf"f32\[{B},{S},4096\]", text)
    for call in calls:
        stated = re.search(r'"scoped_memory_configs":\[\{"memory_space":"1",'
                           r'"offset":"0","size":"(\d+)"', call)
        assert int(stated.group(1)) == gated_norm._VMEM_LIMIT <= 2 ** 27
