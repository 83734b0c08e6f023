"""Ring attention / Ulysses correctness vs single-device attention."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu.models.llama import causal_attention
from horovod_tpu.parallel.ring_attention import (
    ring_attention,
    ulysses_attention,
)


def _rand_qkv(B=2, S=32, H=8, Hkv=4, D=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    return q, k, v


def _shard_over_seq(fn, mesh):
    spec = P(None, "seq", None, None)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    ))


@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_ring_attention_matches_reference(n_devices, n_shards):
    mesh = hvd.build_mesh({"seq": n_shards},
                          devices=jax.devices()[:n_shards])
    q, k, v = _rand_qkv()
    expected = causal_attention(q, k, v)
    got = _shard_over_seq(
        functools.partial(ring_attention, axis_name="seq"), mesh)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_noncausal(n_devices):
    from horovod_tpu.models.bert import dot_product_attention

    mesh = hvd.build_mesh({"seq": 4}, devices=jax.devices()[:4])
    q, k, v = _rand_qkv(H=4, Hkv=4)
    expected = dot_product_attention(
        q.reshape(2, 32, 4, 16), k, v)
    got = _shard_over_seq(
        functools.partial(ring_attention, axis_name="seq", causal=False),
        mesh)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_matches_reference(n_devices):
    mesh = hvd.build_mesh({"seq": 4}, devices=jax.devices()[:4])
    q, k, v = _rand_qkv(H=8, Hkv=4)
    expected = causal_attention(q, k, v)
    got = _shard_over_seq(
        functools.partial(ulysses_attention, axis_name="seq"), mesh)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_grads_flow(n_devices):
    """jax.grad through the ring (ppermute transpose) matches dense grads."""
    mesh = hvd.build_mesh({"seq": 4}, devices=jax.devices()[:4])
    q, k, v = _rand_qkv(B=1, S=16, H=4, Hkv=2, D=8)

    def dense_loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v) ** 2)

    def ring_loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, axis_name="seq") ** 2)

    spec = P(None, "seq", None, None)
    sharded_grads = jax.jit(jax.shard_map(
        jax.grad(ring_loss, argnums=(0, 1, 2)), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=(spec, spec, spec),
        check_vma=False,
    ))(q, k, v)
    dense_grads = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for g1, g2 in zip(sharded_grads, dense_grads):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=5e-5, rtol=5e-5)


def test_llama_with_ring_attention_matches_dense(n_devices):
    """Full model equivalence: LlamaModel(attention_fn=ring) under
    shard_map equals the dense model."""
    from horovod_tpu.models import LlamaConfig, LlamaModel
    from horovod_tpu.parallel.ring_attention import make_ring_attention_fn

    import dataclasses

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32, logits_dtype=jnp.float32)
    mesh = hvd.build_mesh({"seq": 4}, devices=jax.devices()[:4])
    ids = jax.random.randint(jax.random.key(0), (2, 32), 0, cfg.vocab_size)

    dense = LlamaModel(cfg)
    params = dense.init(jax.random.key(1), ids)
    expected = dense.apply(params, ids)

    ring_model = LlamaModel(cfg, attention_fn=make_ring_attention_fn("seq"))

    def inner(params, ids_local):
        # RoPE positions must be global: offset by this shard's start.
        offset = jax.lax.axis_index("seq") * ids_local.shape[1]
        return ring_model.apply(params, ids_local, positions_offset=offset)

    sharded_fwd = jax.jit(jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    ))
    got = sharded_fwd(params, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-4, rtol=2e-4)


def test_ulysses_local_attention_is_flash(n_devices):
    """Flash-legal head dims (D % 64 == 0): the ulysses local attention
    runs the Pallas kernel — asserted structurally in the jaxpr — and
    matches the dense reference (round-3 VERDICT item 5: flash by
    default on shard_map paths)."""
    mesh = hvd.build_mesh({"seq": 2}, devices=jax.devices()[:2])
    q, k, v = _rand_qkv(B=1, S=128, H=4, Hkv=4, D=64, seed=5)
    fn = _shard_over_seq(
        functools.partial(ulysses_attention, axis_name="seq"), mesh)
    jaxpr = jax.make_jaxpr(fn)(q, k, v)
    assert "pallas_call" in str(jaxpr)
    from horovod_tpu.ops import flash_attention as fa
    before = fa.fallback_count()
    got = fn(q, k, v)
    assert fa.fallback_count() == before  # the kernel path, no fallback
    expected = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-2, rtol=2e-2)


def test_context_parallel_auto_selects_flash(n_devices):
    """make_context_parallel_train_step(attention="auto") picks the
    flash-backed ulysses path when heads divide the seq axis: the
    compiled step's jaxpr contains the Pallas call."""
    import dataclasses

    import optax

    from horovod_tpu.models.llama import LlamaConfig
    from horovod_tpu.parallel.seq import make_context_parallel_train_step

    cfg = dataclasses.replace(LlamaConfig.tiny(), hidden_size=256,
                              num_heads=4, num_kv_heads=2)
    assert cfg.head_dim == 64
    mesh = hvd.build_mesh({"seq": 2}, devices=jax.devices()[:2])
    step = make_context_parallel_train_step(cfg, optax.sgd(1e-2), mesh,
                                            donate=False)
    from horovod_tpu.models.llama import LlamaModel

    ids = jnp.zeros((2, 128), jnp.int32)
    params = LlamaModel(cfg).init(jax.random.key(0), ids)
    opt_state = optax.sgd(1e-2).init(params)
    jaxpr = jax.make_jaxpr(step)(params, opt_state, ids, ids)
    assert "pallas_call" in str(jaxpr)
    # and it runs
    params, opt_state, loss = step(params, opt_state, ids, ids)
    assert np.isfinite(float(loss))


def test_flash_ring_matches_dense(n_devices):
    """Flash-legal per-shard shapes: the ring's per-hop block attention
    runs the Pallas kernel with lse-merge across hops — values AND grads
    must match the dense reference (long-context path, no per-hop
    [B,H,S,S] score block)."""
    mesh = hvd.build_mesh({"seq": 2}, devices=jax.devices()[:2])
    q, k, v = _rand_qkv(B=1, S=256, H=4, Hkv=2, D=64, seed=11)
    fn = _shard_over_seq(
        functools.partial(ring_attention, axis_name="seq"), mesh)
    jaxpr = jax.make_jaxpr(fn)(q, k, v)
    assert "pallas_call" in str(jaxpr)
    got = fn(q, k, v)
    expected = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-4, rtol=2e-4)

    def loss(fn_):
        def f(q, k, v):
            return jnp.sum(fn_(q, k, v).astype(jnp.float32) ** 2)
        return f

    def sharded_loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    gd = jax.grad(loss(causal_attention), argnums=(0, 1, 2))(q, k, v)
    gf = jax.jit(jax.grad(sharded_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gd, gf, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=2e-3, rtol=2e-3,
            err_msg=f"d{name} mismatch (flash ring)")


def test_ring_xla_hop_fallback_counted(n_devices):
    """Losing the per-hop kernel (off-tile S_loc) must be VISIBLE:
    fallback_count moves and a single RuntimeWarning fires per reason
    (the telemetry contract the kernel-path tests assert the absence
    of)."""
    import warnings

    from horovod_tpu.common import trace_counts
    from horovod_tpu.ops import flash_attention as fa

    mesh = hvd.build_mesh({"seq": 2}, devices=jax.devices()[:2])
    q, k, v = _rand_qkv(B=1, S=128, H=2, Hkv=2, D=64, seed=14)  # S_loc=64
    fn = _shard_over_seq(
        functools.partial(ring_attention, axis_name="seq"), mesh)
    reason = "ring attention hop uses the XLA online-softmax path"
    with trace_counts._lock:        # forgotten: the warning is due again
        for key in [key for key in trace_counts._counts
                    if key[0] == fa._FALLBACK and reason in key[1]]:
            del trace_counts._counts[key]
    before = fa.fallback_count()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = fn(q, k, v)
    assert fa.fallback_count() > before, "XLA hop not counted"
    msgs = [w for w in caught if reason in str(w.message)]
    assert len(msgs) == 1, [str(w.message) for w in caught]
    expected = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_flash_ring_small_head_dim_keeps_kernel(n_devices):
    """Off-tile head dims (D=32) stay on the per-hop Pallas kernel via
    the lse wrapper's D-padding (zero dims change neither scores nor
    lse): no fallback counted, values and grads match dense."""
    from horovod_tpu.ops import flash_attention as fa

    mesh = hvd.build_mesh({"seq": 2}, devices=jax.devices()[:2])
    q, k, v = _rand_qkv(B=1, S=256, H=4, Hkv=2, D=32, seed=13)
    fn = _shard_over_seq(
        functools.partial(ring_attention, axis_name="seq"), mesh)
    jaxpr = jax.make_jaxpr(fn)(q, k, v)
    assert "pallas_call" in str(jaxpr)
    before = fa.fallback_count()
    got = fn(q, k, v)
    assert fa.fallback_count() == before, "XLA hop fallback fired"
    expected = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-4, rtol=2e-4)

    def sharded_loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v).astype(jnp.float32) ** 2)

    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    gf = jax.jit(jax.grad(sharded_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gd, gf, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=2e-3, rtol=2e-3,
            err_msg=f"d{name} mismatch (flash ring, padded D)")


def test_flash_ring_noncausal_matches_dense(n_devices):
    from horovod_tpu.models.bert import dot_product_attention

    mesh = hvd.build_mesh({"seq": 2}, devices=jax.devices()[:2])
    q, k, v = _rand_qkv(B=1, S=256, H=2, Hkv=2, D=64, seed=12)
    got = _shard_over_seq(
        functools.partial(ring_attention, axis_name="seq", causal=False),
        mesh)(q, k, v)
    expected = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-4, rtol=2e-4)
