"""The ``qwen3-next-80b-a3b.train-s8k-b2`` cell compiled for a described
``v5e:2x2`` (no chip attached), beside ``tests/test_flash_v5e_compile.py``
and in its manner: the flash kernel's two calls at the cell's heads (16 query
heads over 2 key-value heads of 256, in place), whose forward call states the
limit it computes because K and V of 256 lanes pass the compiler's default;
the forward call at heads of 128, which still states none; and the cell's
train step at 2 x 8192 tokens and one layer of each kind, with the
configuration's ``remat``, which holds the gated delta rule's solve as
``ops/gated_delta.py``'s Mosaic call (PR 47).  That the cell's depth fits
the chip is the chip's to say (``peak_hbm_gb``, every PR)."""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import horovod_tpu.jax as hvd
from benchmark import manifest
from horovod_tpu.common import scopes
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import gated_delta
from horovod_tpu.ops import gated_norm
from horovod_tpu.ops import rope
from horovod_tpu.ops import short_conv

CELL = "qwen3-next-80b-a3b.train-s8k-b2"
_MOSAIC_CALL = re.compile(r' = .*custom_call_target="tpu_custom_call"')
_USED = re.compile(r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"')
#: The depth the whole step is compiled at: one layer of each of the cell's
#: two kinds (its four are linear, linear, linear, full).
LAYERS = 2
B, S, HEADS, KV_HEADS, D = 2, 8192, 16, 2, 256


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
    """The five kernels' non-interpreted bodies, and no persistent cache
    (a deviceless executable cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    for module in (fa, rope, short_conv, gated_delta, gated_norm):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _mosaic_calls(text):
    return [line for line in text.splitlines() if _MOSAIC_CALL.search(line)]


def _stated_limits(fn, *args):
    """``vmem_limit_bytes`` of every ``pallas_call`` that ``fn`` traces to,
    None where a call states none."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    limits = []
    for call in calls(jax.make_jaxpr(fn)(*args).jaxpr):
        params = call.params["compiler_params"] or {}
        stated = [getattr(p, "vmem_limit_bytes", None)
                  for p in params.values()]
        limits.append(next((x for x in stated if x is not None), None))
    return limits


def test_the_two_calls_compile_at_256_inside_the_limits_they_state(
        one_chip):
    """Forward and backward through the seam at 2 x 8192 tokens, 16 query
    heads over 2 key-value heads of 256, in place: two Mosaic calls, each
    inside the limit it states, and no array with two sequence-long axes."""
    def sds(n):
        return jax.ShapeDtypeStruct((B, S, n, D), jnp.bfloat16,
                                    sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *x: jnp.sum(fa.flash_attention_fn(
            *x).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    forward_limit = fa._fwd_vmem_limit(S, D, D, 512, 512, 2, masked=False)
    backward_limit = fa._bwd_vmem_limit(S, D, 512, 512, 2, 0)
    assert forward_limit > fa._DEFAULT_SCOPED_VMEM
    assert forward_limit == pytest.approx(24.5 * 2 ** 20)
    assert backward_limit == pytest.approx(53.5e6, rel=0.01)
    before = fa.layout_counts()
    text = jax.jit(grads).lower(sds(HEADS), sds(KV_HEADS),
                                sds(KV_HEADS)).compile().as_text()
    assert fa.layout_counts()["in_place"] == before["in_place"] + 1
    calls = _mosaic_calls(text)
    forward, = (c for c in calls if scopes.FLASH_FWD in c)
    backward, = (c for c in calls if scopes.FLASH_BWD in c)
    assert len(calls) == 2
    assert int(_USED.search(forward).group(1)) <= forward_limit
    assert int(_USED.search(backward).group(1)) <= backward_limit
    # K and V whole and twice are the default already.
    assert int(_USED.search(forward).group(1)) > fa._DEFAULT_SCOPED_VMEM
    assert not re.findall(rf"\w+\[(?:\d+,)*{S},{S}\]", text)


@pytest.mark.parametrize("heads, kv_heads, dim, stated", [
    (16, 16, 128, False), (16, 2, 256, True)])
def test_forward_call_states_a_limit_at_256_and_none_at_128(heads, kv_heads,
                                                            dim, stated):
    """``test_flash_v5e_compile.py::test_forward_call_states_no_vmem_limit``
    beside the call it does not cover: at ``[8192, 16 x 128]`` the forward
    call leaves the scoped-VMEM limit to the compiler's default, as in every
    cell before this one; at ``[8192, 16 / 2 x 256]`` it states the one it
    computes."""
    q = jnp.zeros((1, S, heads * dim), jnp.bfloat16)
    k = jnp.zeros((1, S, kv_heads * dim), jnp.bfloat16)
    limit, = _stated_limits(
        lambda q, k: fa._fwd(q, k, k, True, dim ** -0.5, heads=heads), q, k)
    if stated:
        assert limit == fa._fwd_vmem_limit(S, dim, dim, 512, 512, 2,
                                           masked=False)
    else:
        assert limit is None


@pytest.mark.parametrize("lead", [(8, 2, 32), (8, 1, 30)],
                         ids=["the-cells-slab", "olmo-hybrids-slab"])
def test_the_solve_compiles_at_a_slabs_shape_and_states_no_limit(one_chip,
                                                                 lead):
    """``ops/gated_delta.py::_solve`` at a slab's ``[8, 2, 32, 64, 64]`` and
    at the other hybrid cell's ``[8, 1, 30, 64, 64]`` (240 matrices: two
    grid steps, the second with 112 of its 128 lanes): ONE Mosaic call
    between XLA's two transposes, no limit stated, half the default scoped
    VMEM used."""
    chunk = gated_delta.CHUNK
    a = jax.ShapeDtypeStruct((*lead, chunk, chunk), jnp.float32,
                             sharding=one_chip)

    def solve(a):
        return gated_delta._solve(a, interpret=False)

    assert _stated_limits(solve, a) == [None]
    text = jax.jit(solve).lower(a).compile().as_text()
    calls = [line for line in text.splitlines() if _MOSAIC_CALL.search(line)]
    assert len(calls) == 1
    assert int(_USED.search(calls[0])[1]) <= 8 * 2 ** 20
    count = math.prod(lead)
    assert f"f32[{chunk},{chunk},{count}]" in calls[0]


@pytest.mark.parametrize("heads", [HEADS, KV_HEADS], ids=["q", "k"])
def test_norm_and_rotation_are_one_call_a_pass_at_heads_of_256(one_chip,
                                                               heads):
    """``ops/rope.py::norm_rotate_pairs`` on ``bf16[2, 8192, heads * 256]``,
    a quarter of a head turning: a head is two lane tiles whose squares are
    added before the one reduction; ONE Mosaic call forward and ONE
    backward, no limit stated, the default scoped VMEM not filled, no
    float32 array of x's size around either."""
    from horovod_tpu.models.llama import rope_freqs
    from horovod_tpu.ops import rope

    x = jax.ShapeDtypeStruct((B, S, heads * D), jnp.bfloat16,
                             sharding=one_chip)
    scale = jax.ShapeDtypeStruct((D,), jnp.float32, sharding=one_chip)

    def both(x, scale, g):
        cos, sin = rope_freqs(D, S, 1e7, rotary_dim=D // 4)
        out, vjp = jax.vjp(lambda x, scale: rope.norm_rotate_pairs(
            x, 1.0 + scale, cos, sin, 1e-6), x, scale)
        return out, vjp(g)

    assert _stated_limits(both, x, scale, x) == [None, None]
    text = jax.jit(both).lower(x, scale, x).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 2 and all(scopes.ROPE in c for c in calls)
    assert max(int(_USED.search(c)[1]) for c in calls) < (
        fa._DEFAULT_SCOPED_VMEM)
    assert f"f32[{B},{S},{heads * D}]" not in text
    assert f"f32[{B},{S},{heads},{D}]" not in text


def test_the_cells_whole_step_fits_with_the_chosen_remat(topo, one_chip):
    """One linear and one full layer of the published widths (the cell's
    period has three linear before the full one) at 2 x 8192 tokens under
    the configuration's ``layer_keep_attention`` (at the cell's four layers
    ``layer`` compiled to the same bytes and ran the flash forward call
    twice; ``none`` to 8.54 GB of temporaries, 17.3 GB in all: no room).
    The full layer is two flash calls (the policy keeps the forward call's
    output) and six rotations; a linear layer's convolutions are nine
    Mosaic calls, its chunk systems' solves three (PR 47) and its output
    norm and gate three (PR 61: ``gated_norm.norm_gate`` at 32 heads of 128
    lanes, forward, again, backward; one trace a layer took the Mosaic pass
    and none the ``jnp`` body); the grouped products are XLA:TPU's own.  That the cell's four layers (8.76 GB of
    state, 4.03 GB of temporaries) fit the chip is no longer summed here:
    the chip's ``peak_hbm_gb`` in this cell says it in every PR, and
    ``tests/benchmark/test_benchmark_reference.py::
    test_whole_step_compiles_for_v5e_and_fits`` compiles a whole step."""
    cell = manifest.cell(CELL)
    assert cell["config"]["training"]["remat"] == "layer_keep_attention"
    # ``full_attention_interval`` is how this configuration states its
    # pattern: every second layer full gives (linear, full).
    config = {**cell["config"], "num_hidden_layers": LAYERS,
              "full_attention_interval": LAYERS}
    job = manifest.load_job(config["job"]).build(config, cell["traffic"], 1)
    linear = sum(map(job.llama.is_linear, range(job.llama.num_layers)))
    full = LAYERS - linear
    assert (job.llama.num_layers, linear, full) == (LAYERS, 1, 1)
    mesh = Mesh([topo.devices[0]], ("data",))
    replicated = NamedSharding(mesh, P())

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=replicated), tree)

    state = jax.eval_shape(job.init_state, jax.random.key(0))
    batch = jax.eval_shape(job.make_batch, jax.random.key(0))
    assert batch.shape == (2, 8193)
    step = hvd.make_train_step(job.loss_fn, job.optimizer, mesh)
    before = (fa.layout_counts(), short_conv.body_counts(),
              gated_delta.solve_counts(), gated_norm.body_counts(),
              gated_delta.walk_counts())
    compiled = step.lower(*described(state), described(batch)).compile()
    after = (fa.layout_counts(), short_conv.body_counts(),
             gated_delta.solve_counts(), gated_norm.body_counts(),
             gated_delta.walk_counts())
    assert after[4]["mosaic"] - before[4]["mosaic"] == linear
    assert after[4]["plain"] == before[4]["plain"]
    assert after[3]["mosaic"] - before[3]["mosaic"] == linear
    assert after[3]["plain"] == before[3]["plain"]
    assert after[2]["mosaic"] - before[2]["mosaic"] == linear
    assert after[2]["plain"] == before[2]["plain"]
    assert after[0]["in_place"] - before[0]["in_place"] == full
    assert after[0]["flat"] == before[0]["flat"]
    assert after[1]["fused"] - before[1]["fused"] == 3 * linear
    text = compiled.as_text()
    calls = _mosaic_calls(text)
    forward = [c for c in calls if scopes.FLASH_FWD in c]
    backward = [c for c in calls if scopes.FLASH_BWD in c]
    assert len(forward) == len(backward) == full
    assert not any(scopes.REMATTED in c for c in forward)
    # q and k normed and turned by one call each, forward, again, backward
    # (PR 48): no float32 array of their size, heads apart or together.
    assert sum(scopes.ROPE in c for c in calls) == 6 * full
    assert all(scopes.QK_NORM in c for c in calls if scopes.ROPE in c)
    for gone in ("f32[2,8192,16,256]", "f32[2,8192,2,256]"):
        assert gone not in text, gone
    assert sum(scopes.GDN_CONV in c for c in calls) == 9 * linear
    # Every chunk's system (8192 matrices, two heads' side by side on the
    # middle axis: 2 x 32 grid steps) by the solve's call, forward and again,
    # a linear layer (the backward call of the walk makes A's cotangent
    # itself: PR 64); each states no limit and takes half the default scoped
    # VMEM.
    solves = [c for c in calls if scopes.GDN_SOLVE in c]
    assert len(solves) == 2 * linear
    assert all(scopes.GDN_SCAN in c for c in solves)
    assert sum(scopes.REMATTED in c for c in solves) == linear
    assert "f32[64,128,4096]" in solves[0]
    assert max(int(_USED.search(c)[1]) for c in solves) <= 8 * 2 ** 20
    # The chunks' walk (PR 64): the systems' call and the forward call,
    # each forward and again, and ONE backward call, a linear layer; q, k, v
    # read and o, dq, dk, dv written as rows; no ``while`` is left under the
    # scope, and each call takes less than the default scoped VMEM.
    walks = [c for c in calls if scopes.GDN_SCAN in c
             and scopes.GDN_SOLVE not in c]
    assert len(walks) == 5 * linear
    assert sum(scopes.REMATTED in c for c in walks) == 2 * linear
    assert all("bf16[2,8192,4096]" in c for c in walks)
    assert max(int(_USED.search(c)[1]) for c in walks) < (
        fa._DEFAULT_SCOPED_VMEM)
    assert not [line for line in text.splitlines()
                if scopes.GDN_SCAN in line and " while(" in line]
    # The output norm and gate: forward, again, backward (do, dz and the
    # weight's partial sums), and no float32 array of the activations' shape
    # under their scope.
    gates = [c for c in calls if scopes.GDN_GATES in c]
    assert len(gates) == 3 * linear
    assert sum(scopes.REMATTED in c for c in gates) == linear
    assert sum(" = (" in c for c in gates) == linear
    assert not [line for line in text.splitlines()
                if scopes.GDN_GATES in line and " = f32[2,8192,4096]" in line]
    # o is read, and do written, as the rows the walk's calls write and
    # read: no transposing copy or reshape of the activations' size is left
    # under the scope.
    assert all("operand_layout_constraints={bf16[2,8192,4096]" in c
               for c in gates)
    assert not [line for line in text.splitlines()
                if scopes.GDN_GATES in line and " = bf16[2,8192,4096]" in line
                and any(op in line for op in (" reshape(", " copy(",
                                              " transpose("))]
    ours = [c for c in calls if scopes.RAGGED_DOT_PREFIX not in c]
    # The key heads' copies to their value heads, made in the rows (PR 64):
    # q's and k's forward, again, and their cotangents' sums.
    heads = [c for c in calls if scopes.GDN_HEADS in c]
    assert len(heads) == 6 * linear
    assert sum("bf16[2,8192,2048]{" in c.split(" custom-call(")[0]
               for c in heads) == 2 * linear
    assert len(ours) == (2 + 6) * full + (9 + 2 + 5 + 3 + 6) * linear
    assert scopes.RAGGED_DOT_PREFIX in text
    for scope in (scopes.GDN_HEADS, scopes.GDN_SCAN, scopes.GDN_GATES,
                  scopes.ATTN_GATE, scopes.MOE_SHARED, scopes.MOE_ROUTE):
        assert scope in text, scope
    assert not re.findall(rf"\w+\[(?:\d+,)*{S},{S}\]", text)
    memory = compiled.memory_analysis()
    print(f"arguments {memory.argument_size_in_bytes} + "
          f"temporaries {memory.temp_size_in_bytes}")
    # Read at these two layers (all four: 8.759 GB and 3.906 GB since PR 64).
    assert memory.argument_size_in_bytes == pytest.approx(4.879e9, abs=0.1e9)
    assert memory.temp_size_in_bytes == pytest.approx(3.434e9, abs=0.1e9)
