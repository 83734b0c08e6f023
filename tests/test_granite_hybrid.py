"""Granite-4.0-H's layers (``models/llama.py`` with ``"mamba"`` and
``"attention"`` in ``layer_types``: a ``Mamba2`` mixer or grouped-query
attention without a position, each with a dense SwiGLU behind a norm of its
own, under the four multipliers and a tied head) against the plain reference
(``benchmark/reference/granite_hybrid.py``), on the CPU in float32 at a small
size with seeded random weights moved off their start; the wide-group form of
``ops/gated_norm.py``'s Mosaic pass, interpreted, against its ``jnp`` body and
compiled once for a described v5e; ``ops/ssd.py`` at chunks of 256 and one
group against the token-by-token recurrence."""

import copy
import dataclasses
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import manifest
from benchmark.reference import granite_hybrid as reference
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.models import llama
from horovod_tpu.ops import gated_norm, ssd
from tiny_sizes import TINY

CELL = "granite-4.0-h-micro.train-s8k"
KINDS = ["mamba", "attention", "mamba"]


@pytest.fixture(scope="module")
def tiny():
    """The tiny job at three layers (mamba, attention, mamba: both kinds,
    and a Mamba layer that reads what attention wrote) in float32 with the
    model's own dense attention, its parameters moved off their start (every
    scale differs from 1, the filter's bias from 0), a batch, and the
    configuration the reference reads."""
    cell = manifest.cell(CELL)
    over = TINY["ssm_lm"]
    config = {**cell["config"], **over["config"],
              "num_hidden_layers": len(KINDS), "layer_types": KINDS}
    job = manifest.load_job("ssm_lm").build(
        config, {**cell["traffic"], **over["traffic"]}, 1)
    job.llama = dataclasses.replace(job.llama, dtype=jnp.float32,
                                    logits_dtype=jnp.float32, remat="none")
    job.model = LlamaModel(job.llama)
    k_init, k_move, k_batch = jax.random.split(jax.random.key(60), 3)
    variables = job.model.init(k_init, jnp.zeros((1, 8), jnp.int32))
    leaves, tree = jax.tree.flatten(variables["params"])
    keys = jax.random.split(k_move, len(leaves))
    params = {"params": jax.tree.unflatten(tree, [
        leaf + 0.1 * (jnp.std(leaf) or 1.0) * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])}
    batch = job.make_batch(k_batch)
    with jax.default_matmul_precision("highest"):
        wanted = jax.jit(lambda p: reference.loss_and_grads(
            job.to_reference(p), batch, config))(params)
    return job, config, params, batch, wanted


def _distance(job, params, batch, wanted):
    """(|loss - reference loss|, the gradient's relative distance over all
    leaves, the worst leaf's) of the program on ``params`` from ``wanted``,
    the reference's loss and gradients."""
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(job.loss_fn))(params, batch)
    ref_loss, ref_grads = wanted
    off = jax.tree.map(lambda g, r: jnp.sum(jnp.square(g - r)),
                       job.to_reference(grads), ref_grads)
    size = jax.tree.map(lambda r: jnp.sum(jnp.square(r)), ref_grads)
    off, size = (np.asarray(jax.tree.leaves(t)) for t in (off, size))
    return (abs(float(loss - ref_loss)),
            float(np.sqrt(off.sum() / size.sum())),
            float(np.max(np.sqrt(off / (size + 1e-30)))))


def test_program_matches_reference_loss_and_every_gradient_leaf(tiny):
    """Both layer kinds, each with its SwiGLU, the four multipliers and the
    tied head: the loss and every leaf's gradient."""
    job, _, params, batch, wanted = tiny
    loss_off, grad_off, worst_leaf = _distance(job, params, batch, wanted)
    assert loss_off < 2e-5 and grad_off < 1e-4 and worst_leaf < 2e-3


def _copy_with(job, **changes):
    other = copy.copy(job)
    other.llama = dataclasses.replace(job.llama, **changes)
    other.model = LlamaModel(other.llama)
    return other


def _norm_then_gate(y, z, scale, groups, eps):
    y = y.astype(jnp.float32)
    grouped = y.reshape(*y.shape[:-1], groups, -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return (grouped.reshape(y.shape) * scale
            * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)


@pytest.mark.parametrize("variant", [
    "residual_multiplier_left_at_1", "softmax_scale_of_head_dim",
    "embedding_multiplier_left_out", "logits_scaling_left_out",
    "norm_in_two_groups", "norm_before_the_gate", "skip_left_out",
    "filter_bias_dropped"])
def test_a_wrong_version_fails_the_comparison(tiny, monkeypatch, variant):
    """Each departure from the published layer, alone, in the PROGRAM: on
    parameters moved off their start the LOSS is already outside what the
    job as it is keeps to (2e-5), so the forward pass alone is compiled.
    (``logits_scaling`` and a dropped filter bias are the two that the
    chip's comparison, at the INITIAL parameters, does not hold: the
    configuration's ``checks.reference.why``.)"""
    job, _, params, batch, wanted = tiny
    wrong = params
    body = gated_norm._gate_then_norm
    if variant == "residual_multiplier_left_at_1":
        job = _copy_with(job, residual_multiplier=1.0)
    elif variant == "softmax_scale_of_head_dim":
        job = _copy_with(job, attention_multiplier=None)
    elif variant == "embedding_multiplier_left_out":
        job = _copy_with(job, embedding_multiplier=1.0)
    elif variant == "logits_scaling_left_out":
        job = _copy_with(job, logits_scaling=1.0)
    elif variant == "norm_in_two_groups":
        monkeypatch.setattr(
            gated_norm, "_gate_then_norm",
            lambda y, z, scale, groups, eps: body(y, z, scale, 2, eps))
    elif variant == "norm_before_the_gate":
        monkeypatch.setattr(gated_norm, "_gate_then_norm", _norm_then_gate)
    elif variant == "skip_left_out":
        monkeypatch.setattr(gated_norm, "skipped", lambda y, u, d: y)
    elif variant == "filter_bias_dropped":
        wrong = {"params": {
            name: ({**layer, "mamba": {
                **layer["mamba"],
                "conv_b": jnp.zeros_like(layer["mamba"]["conv_b"])}}
                   if "mamba" in layer else layer)
            for name, layer in params["params"].items()}}
    try:
        loss = jax.jit(job.loss_fn)(wrong, batch)
    finally:
        monkeypatch.undo()
    assert abs(float(loss - wanted[0])) > 2e-4, (variant, float(loss))


# -- the share -----------------------------------------------------------------

def test_eight_vocabulary_slices_side_by_side_are_the_uncut_logits(tiny):
    """The cut's arithmetic (model-configs guide, section 4): each of eight
    chips holds an eighth of the tied embedding's rows and makes its own
    slice of the logits through ``LlamaModel.head``; side by side they are
    the UNCUT REFERENCE's logits for the same hidden states.  (What is over
    all slices, the softmax's normaliser, is the exchange the cell runs
    without.)"""
    job, config, _, _, _ = tiny
    hidden, slice_rows = job.llama.hidden_size, job.llama.vocab_size
    k_table, k_states = jax.random.split(jax.random.key(8))
    table = jax.random.normal(k_table, (8 * slice_rows, hidden)) * 0.05
    states = jax.random.normal(k_states, (2, 16, hidden))

    def share(first):
        rows = table[first:first + slice_rows]
        return job.model.apply(
            {"params": {"tok_emb": {"embedding": rows}}}, states,
            method=LlamaModel.head)

    with jax.default_matmul_precision("highest"):
        side_by_side = jnp.concatenate(
            [share(first) for first in range(0, 8 * slice_rows, slice_rows)],
            axis=-1)
        uncut = reference.head_logits(states, table, config)
    assert side_by_side.shape == (2, 16, 8 * slice_rows)
    np.testing.assert_allclose(side_by_side, uncut, atol=2e-6)
    # And the division is in them: without it they are 8 times as large.
    np.testing.assert_allclose(side_by_side * config["logits_scaling"],
                               states @ table.T, atol=2e-5)


# -- the gates' wide group -------------------------------------------------------

def _gates_operands(shape, dtype):
    b, s, c, heads = shape
    ks = jax.random.split(jax.random.key(60), 6)

    def normal(k, width):
        return jax.random.normal(k, (b, s, width), jnp.float32).astype(dtype)

    return (normal(ks[0], c), normal(ks[1], c + 256), normal(ks[2], c + 384),
            1.0 + 0.3 * jax.random.normal(ks[3], (heads,)),
            1.0 + 0.3 * jax.random.normal(ks[4], (c,)), normal(ks[5], c))


def _both_ways(f):
    """The result and the five gradients of ``f(y, u, z, d, w)``, jitted."""
    def run(y, u, z, d, w, go):
        out, vjp = jax.vjp(f, y, u, z, d, w)
        return (out, *vjp(go.astype(out.dtype)))
    return jax.jit(run)


@pytest.mark.parametrize("shape, groups, dtype", [
    ((1, 320, 4096, 64), 1, jnp.float32),
    ((1, 320, 4096, 64), 1, jnp.bfloat16),
    ((1, 64, 4096, 64), 8, jnp.float32),
    ((2, 96, 1280, 10), 2, jnp.float32),
], ids=["one group of 4096", "one group of 4096 in bf16",
        "8 groups of 512 as they were", "2 groups of 640 in pieces of 128"])
def test_the_wide_pass_interpreted_is_the_jnp_body(shape, groups, dtype):
    """One norm group over all 4096 lanes (Granite-4.0-H's), taken in two
    sweeps over pieces of 512 lanes, 320 rows in five blocks of 64: the
    result and all five gradients are the ``jnp`` body's float32 values.
    Groups of 512 lanes take the former body (``_kernels``); a group of 640
    goes in pieces of 128 lanes, 256 rows a step."""
    width = shape[2] // groups
    wide = width > gated_norm._GROUP_LANES
    assert (gated_norm._kernels(shape[2], groups)[0]
            is gated_norm._fwd_kernel_wide) == wide
    rows = gated_norm._pick_rows(shape[1])
    if wide:
        assert gated_norm._pieces(rows, width) == (
            (64, 512) if width == 4096 else (32, 128))
        assert shape[1] > rows                  # a block edge is crossed
    args = _gates_operands(shape, dtype)
    got = _both_ways(lambda *a: gated_norm.skip_gate_norm(
        *a, groups, 1e-5))(*args)
    want = _both_ways(lambda *a: gated_norm.gated_norm(
        *a, groups, 1e-5, False))(*(x.astype(jnp.float32) for x in args))
    for name, a, b in zip(("out", "dy", "du", "dz", "dd", "dw"), got, want):
        assert a.shape == b.shape, name
        a, b = (np.asarray(jnp.asarray(x, jnp.float32)) for x in (a, b))
        size = np.abs(b).max()
        if dtype == jnp.float32 or a.ndim == 1:
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * max(
                size, 1.0), err_msg=name)
        else:
            # A unit in the last place of a bfloat16 value is up to 2^-7.
            assert np.all(np.abs(a - b) <= 2.0 ** -7 * np.abs(b)
                          + 1e-6 * size), name


def test_the_rule_takes_a_wide_group():
    """A group wider than a step holds is a shape the pass TAKES: the rule
    gives the cell's shape no reason of its own (off the TPU, the last)."""
    assert gated_norm._why_not((1, 8192, 4096), 1, True) == gated_norm.NO_TPU
    assert gated_norm._why_not((1, 8192, 4096), 1, False) == (
        gated_norm.NOT_IN_PLACE)
    assert gated_norm._pieces(256, 4096) == (64, 512)
    assert gated_norm._pieces(256, 768) == (64, 384)
    assert gated_norm._pieces(256, 640) == (256, 128)
    assert gated_norm._pieces(16, 4096) == (16, 512)


def test_a_filter_window_off_the_lane_tile_takes_the_jnp_body():
    """A Mamba-2 layer whose filter runs over ``inner + 2 G N`` channels
    that are no whole lane tiles (1024 + 2 x 32) hands ``short_conv`` a
    window its Mosaic calls cannot take (the TPU's lowering refused it, my
    chip run, PR 60): the rule says so, by the reason it had."""
    from horovod_tpu.ops import short_conv

    assert short_conv._why_not((1, 512, 2192), (4, 1088), 1, first=1024) == (
        short_conv._NOT_AT_A_TILE)
    assert short_conv._why_not((1, 512, 2320), (4, 1152), 1,
                               first=1024) is None
    assert short_conv._why_not((1, 8192, 8512), (4, 4352), 1,
                               first=4096) is None        # the cell's


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
    """The kernel's non-interpreted body, and no persistent cache (a
    deviceless executable cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    from horovod_tpu.ops import short_conv

    for module in (gated_norm, short_conv, ssd):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_wide_pair_compiles_for_a_v5e_at_the_cells_shape(one_chip):
    """1 x 8192 rows of ONE group of 4096 lanes, u the first 4096 of the
    filter's 4352 channels and z of ``in_proj``'s 8512, forward and backward:
    two Mosaic calls, and nothing float32 of the activations' shape beside
    them."""
    def sds(width, dtype=jnp.bfloat16, rows=(1, 8192)):
        return jax.ShapeDtypeStruct((*rows, width), dtype, sharding=one_chip)

    def both(y, u, z, d, w, go):
        out, vjp = jax.vjp(lambda *a: gated_norm.skip_gate_norm(
            *a, 1, 1e-5), y, u, z, d, w)
        return (out, *vjp(go))

    text = jax.jit(both).lower(
        sds(4096), sds(4352), sds(8512), sds(64, jnp.float32, ()),
        sds(4096, jnp.float32, ()), sds(4096)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "f32[1,8192,4096]" not in text


def test_a_mamba_layer_compiles_for_a_v5e_at_the_cells_shape(one_chip):
    """The cell's ``Mamba2`` mixer at 1 x 8192 tokens and the published
    widths (``inner`` 4096 in ONE group, chunks of 256, ``in_proj`` 8512
    wide), in place, forward and backward: six Mosaic calls, one each way
    under each of ``hvd.ssd.conv``, ``hvd.ssd.scan`` and ``hvd.ssd.gates``;
    the scan's pair takes the shape (one group is eight steps of eight
    heads: B's and C's cotangents are summed inside the call) and leaves
    nothing of the ``jnp`` scan in the text -- no ``while``, no
    ``reduce-window``, no ``[256, 256]`` array a slab (``f32[8,1,1,64,256,
    256]``, 134 MB each)."""
    import re

    from horovod_tpu.common import scopes

    cell = manifest.cell(CELL)
    config = manifest.load_job(cell["config"]["job"]).build(
        cell["config"], cell["traffic"], 1).llama
    assert (config.mamba_num_heads, config.mamba_head_dim, config.n_groups,
            config.ssm_state_size, config.chunk_size) == (64, 64, 1, 128, 256)
    module = llama.Mamba2(config, in_place=True)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    x = jax.ShapeDtypeStruct((1, 8192, config.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    variables = jax.eval_shape(lambda k: module.init(k, jnp.zeros(
        (1, 256, config.hidden_size), jnp.bfloat16)), jax.random.key(0))

    def grads(variables, x):
        return jax.grad(lambda p, x: jnp.sum(module.apply(
            {**variables, "params": p}, x).astype(jnp.float32)),
            argnums=(0, 1))(variables["params"], x)

    before = ssd.body_counts()
    compiled = jax.jit(grads).lower(jax.tree.map(sds, variables), x).compile()
    after = ssd.body_counts()
    assert after["mosaic"] == before["mosaic"] + 1
    assert after["plain"] == before["plain"]
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 6
    for scope in (scopes.SSD_CONV, scopes.SSD_SCAN, scopes.SSD_GATES):
        assert sum(scope in call for call in calls) == 2, scope
    assert " while(" not in text and "reduce-window" not in text
    assert not re.search(r"f32\[[\d,]*256,256\]", text)
    scan = [call for call in calls if scopes.SSD_SCAN in call]
    assert all(call.count("bf16[1,8192,4352]{2,1,0}") == 3 for call in scan)
    # (1.6 GB is the cell's whole step's; a layer's own: 0.49.)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


# -- the scan at the published chunk ----------------------------------------------

def test_the_scan_at_chunks_of_256_and_one_group_is_the_recurrence(
        monkeypatch):
    """``ssd_scan`` at ``chunk=256`` with B and C shared by ALL heads (G =
    1) over 512 tokens, two chunks, against the token-by-token recurrence of
    the plain reference, forward and backward."""
    monkeypatch.setattr(reference, "TOKENS", 64)
    k = jax.random.split(jax.random.key(6), 6)
    batch, seq, heads, width, state = 1, 512, 4, 16, 32
    operands = (
        jax.random.normal(k[0], (batch, seq, heads, width)),
        jax.nn.softplus(jax.random.normal(k[1], (batch, seq, heads)) - 2),
        jnp.log(jax.random.uniform(k[2], (heads,), minval=1, maxval=16)),
        jax.random.normal(k[3], (batch, seq, 1, state)),
        jax.random.normal(k[4], (batch, seq, 1, state)),
        jax.random.normal(k[5], (heads,)))

    def plain(u, dt, a_log, b, c, d):
        b, c = (jnp.repeat(t, heads, axis=2) for t in (b, c))
        decay = jnp.exp(-jnp.exp(a_log) * dt)
        return reference.skip(reference.state_space_scan(u, dt, decay, b, c),
                              u, d)

    def both(scan):
        return jax.jit(lambda *a: (scan(*a), jax.grad(
            lambda *a: jnp.sum(jnp.sin(scan(*a))), argnums=range(6))(*a)))

    y, grads = both(lambda *a: ssd.ssd_scan(*a, chunk=256))(*operands)
    wanted, wanted_grads = both(plain)(*operands)
    np.testing.assert_allclose(y, wanted, atol=5e-4)
    for got, want in zip(grads, wanted_grads):
        np.testing.assert_allclose(got, want, atol=5e-4 * float(
            jnp.max(jnp.abs(want))))


# -- the stack and the config -----------------------------------------------------

def test_layers_by_type_two_norms_and_a_tied_head(tiny):
    job, _, params, _, _ = tiny
    layers = params["params"]
    assert {k: sorted(v) for k, v in layers.items() if k.startswith(
        "layer_")} == {
            "layer_0": ["mamba", "mlp", "norm_attn", "norm_mlp"],
            "layer_1": ["attn", "mlp", "norm_attn", "norm_mlp"],
            "layer_2": ["mamba", "mlp", "norm_attn", "norm_mlp"]}
    assert "lm_head" not in layers
    assert sorted(layers["layer_0"]["mamba"]) == [
        "a_log", "conv_b", "conv_w", "d", "dt_bias", "in_proj", "norm",
        "out_proj"]
    assert sorted(layers["layer_1"]["attn"]) == ["wk", "wo", "wq", "wv"]
    specs = job.llama.layers
    assert [(s.mixer, s.ffn, s.norms, s.type, s.rope) for s in specs] == [
        (llama.MAMBA2, llama.DENSE, llama.TWO_NORMS, "mamba", None),
        (llama.SELF_ATTENTION, llama.DENSE, llama.TWO_NORMS, "attention",
         None),
        (llama.MAMBA2, llama.DENSE, llama.TWO_NORMS, "mamba", None)]
    # A layer with two sublayers has no pattern character.
    assert [job.llama.kind_of(i) for i in range(3)] == [None] * 3


def test_config_knows_and_refuses_the_new_types():
    sizes = dict(mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16)
    stack = dataclasses.replace(LlamaConfig.tiny(), rope_theta=None,
                                layer_types=("mamba", "attention"), **sizes)
    assert [s.mixer for s in stack.layers] == [llama.MAMBA2,
                                               llama.SELF_ATTENTION]
    # The published TYPE "mamba" is Mamba-2; the MIXER "mamba" is Mamba-1's.
    assert llama.TYPE_MIXERS["mamba"] == llama.MAMBA2 != llama.SCAN
    assert not stack.layers_share and stack.mixer_of(0) is None
    # The size checks are a pattern's 'M' layer's, made once for both.
    for missing in sizes:
        with pytest.raises(ValueError, match='"mamba" layer'):
            dataclasses.replace(stack, **{missing: 0})
    with pytest.raises(ValueError, match="n_groups"):
        dataclasses.replace(stack, n_groups=3)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(stack, layer_types=("mamba", "mamba2"))
    with pytest.raises(ValueError, match="attention_multiplier"):
        LlamaConfig(attention_kind="latent", kv_lora_rank=8,
                    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                    attention_multiplier=0.1)
    # With a rotation stated the "attention" layers turn; "mamba" has none.
    turning = dataclasses.replace(stack, rope_theta=1e4)
    assert turning.rope_of(0) is not None and turning.rope_of(1) is not None
    assert LlamaConfig.tiny().attention_multiplier is None


@pytest.mark.parametrize("who", ["generation", "serve", "pipeline"])
@pytest.mark.parametrize("changes, says", [
    (dict(layer_types=("mamba", "attention"), mamba_num_heads=4,
          mamba_head_dim=16, ssm_state_size=16, rope_theta=None),
     r"Mamba-2 state-space layers \(layer_types holds 'mamba'\)"),
    (dict(residual_multiplier=0.22), "residual_multiplier"),
    (dict(attention_multiplier=0.015625), "attention_multiplier"),
    (dict(embedding_multiplier=12.0), "embedding_multiplier"),
    (dict(logits_scaling=8.0), "logits_scaling"),
], ids=["a mamba layer", "residual", "attention", "embedding", "logits"])
def test_the_other_paths_refuse_by_name(who, changes, says):
    from horovod_tpu.models.generation import prefill
    from horovod_tpu.parallel.pipeline import init_pipelined_llama

    stack = dataclasses.replace(LlamaConfig.tiny(), **changes)
    with pytest.raises(NotImplementedError, match=says):
        if who == "generation":
            prefill(stack, {}, jnp.zeros((1, 4), jnp.int32), cache_len=8)
        elif who == "serve":
            stack.refuse_new_kinds("the paged KV cache")
        else:
            init_pipelined_llama(stack, jax.random.key(0), 1)


# -- what the other stacks trace ---------------------------------------------------

# sha256 of the StableHLO text (no locations) lowered for the function below
# (JAX 0.9.0): a tiny Nemotron stack MEM*E under ``hybrid_override_pattern``,
# value and gradient.  PR 60 pinned its parent's (commit 68d333b: b49b8244..);
# PR 62 changed what a Mamba-2 layer traces on the CPU (``ops/ssd.py``: the
# log-decays' sums are a triangular product, no ``cumsum``; u, B and C are cut
# from the filter's result inside ``ssd_scan_rows``) and pinned its own.
PARENTS_TEXT = (
    "a2d788f45bf2ddd4152ae35b556c1d6ea7cb393f75d7086e4c3ff9935a3f280a")


def test_a_pattern_stack_with_identity_multipliers_lowers_as_it_did():
    """The four multipliers at their defaults multiply nothing and a
    ``"mamba"`` type changes no pattern layer: the lowered text of a tiny
    Nemotron stack is the parent's, character for character (the new scope
    ``hvd.ssd.proj`` lives in the locations, which the text leaves out)."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the parent's text was lowered by JAX 0.9.0")
    config = LlamaConfig(
        vocab_size=512, hidden_size=64, num_layers=5,
        hybrid_override_pattern="MEM*E", num_heads=4, num_kv_heads=2,
        attention_head_dim=32, intermediate_size=48, rope_theta=None,
        mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=32, n_groups=2,
        chunk_size=16, num_experts=8, experts_per_token=2, held_experts=4,
        first_held_expert=2, moe_intermediate_size=48, shared_experts=1,
        moe_shared_expert_intermediate_size=96, mlp_hidden_act="relu2",
        scoring_func="sigmoid", topk_method="noaux_tc",
        routed_scaling_factor=2.5, balance_over="batch",
        remat="layer_keep_attention")
    assert (config.embedding_multiplier, config.attention_multiplier,
            config.residual_multiplier, config.logits_scaling) == (
                1.0, None, 1.0, 1.0)
    model = LlamaModel(config)
    tokens = jnp.zeros((2, 64), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), tokens)

    def loss(params, state, tokens):
        logits, _ = model.apply({"params": params, **state}, tokens,
                                mutable=["losses", llama.ROUTER_STATE])
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    state = {k: v for k, v in variables.items() if k != "params"}
    text = jax.jit(jax.value_and_grad(loss)).lower(
        variables["params"], state, tokens).as_text()
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_TEXT
