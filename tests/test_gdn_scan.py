"""``ops/gated_delta.py``'s walk as one Mosaic call each way (``walk_rows``,
interpreted on the CPU at small shapes and chunks of 16) against the ``jnp``
walk AND the token-by-token recurrence of the plain reference
(``benchmark/reference/olmo_hybrid.py``) in float32, forward and every
gradient; the rule that chooses between the two bodies and its counter; and
what XLA prepares for a step."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid
from horovod_tpu.ops import gated_delta
from horovod_tpu.ops.gated_delta import gated_delta_rule

HIGHEST = jax.default_matmul_precision("highest")
NAMES = ("o", "d q", "d k", "d v", "d g", "d beta")


def _operands(batch, seq, heads, d_k, d_v, write=0.0):
    """q and k as the rule reads them (unit, q scaled), v, a log-decay, a
    beta in (0, 2) whose logit's centre ``write`` moves, and a cotangent
    that weighs every entry of o differently."""
    keys = jax.random.split(jax.random.key(13), 6)
    q = jax.random.normal(keys[0], (batch, seq, heads, d_k))
    k = jax.random.normal(keys[1], (batch, seq, heads, d_k))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d_k ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (batch, seq, heads, d_v))
    g = -jnp.exp(jax.random.normal(keys[3], (batch, seq, heads)) - 2.0)
    beta = 2 * jax.nn.sigmoid(
        2 * jax.random.normal(keys[4], (batch, seq, heads)) + write)
    return (q, k, v, g, beta), jax.random.normal(keys[5], v.shape)


def _token_by_token(q, k, v, g, beta):
    with mock.patch.object(olmo_hybrid, "TOKENS", q.shape[1]):
        return olmo_hybrid.delta_rule(q, k, v, jnp.exp(g), beta)[0]


def _lifted(monkeypatch, chunk=16):
    """The rule's last reason lifted (a TPU's answer, the calls interpreted)
    and chunks of ``chunk`` rows."""
    monkeypatch.setattr(gated_delta, "_why_not", lambda: None)
    monkeypatch.setattr(gated_delta, "CHUNK", chunk)


def _rel(got, want):
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _with_gradients(rule, operands, go):
    def run(*a):
        o, back = jax.vjp(rule, *a)
        return (o, *back(go.astype(o.dtype)))
    return jax.jit(run)(*operands)


def _padded_to_tiles(rule):
    """``rule`` on q and k with zero lanes up to whole lane tiles, v
    likewise, o cut back: what a caller whose heads are off the tile may
    do, and exact (a zero lane of k and q adds nothing to K K^T, Q K^T or
    the state; a zero lane of v gives a zero lane of o)."""
    def lanes(x):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, -x.shape[-1] % 128),))

    return lambda q, k, v, g, beta: rule(
        lanes(q), lanes(k), lanes(v), g, beta)[..., :v.shape[-1]]


@pytest.mark.parametrize(
    "batch,seq,heads,d_k,d_v,write,dtype", [
        (2, 64, 2, 128, 128, 0.0, jnp.float32),
        (2, 64, 2, 128, 128, 0.0, jnp.bfloat16),
        (1, 64, 2, 96, 192, 0.0, jnp.float32),
        (1, 40, 2, 128, 128, 0.0, jnp.float32),
        (256, 16, 2, 128, 128, 0.0, jnp.float32),
        (1, 64, 2, 128, 128, 3.0, jnp.float32),
        (1, 48, 12, 256, 128, 0.0, jnp.float32),
    ], ids=["heads of 128 and 128", "the same, bf16",
            "heads padded from 96 and 192", "no whole chunks",
            "one chunk alone, 256 batch rows", "beta over one",
            "keys of two lane tiles, twelve heads in two steps"])
def test_the_mosaic_walk_is_the_jnp_walk_and_the_recurrence(
        monkeypatch, batch, seq, heads, d_k, d_v, write, dtype):
    """``gated_delta_rule`` in place (``walk_rows``' calls, interpreted)
    against the same entry not in place (the ``jnp`` walk) AND against the
    token-by-token recurrence in float32: o and the gradients of q, k, v, g
    and beta.  In float32 the calls are the walk to rounding; in bf16 they
    are no further from the float32 recurrence than the walk is (a tenth
    and a half of room: the sums run in another order)."""
    _lifted(monkeypatch)
    operands, go = _operands(batch, seq, heads, d_k, d_v, write)
    if write:
        assert float(jnp.mean(operands[4] > 1)) > 0.8
    exact_operands = operands
    operands = tuple(x.astype(dtype) for x in operands[:3]) + operands[3:]

    def entry(in_place):
        rule = lambda *a: gated_delta_rule(*a, in_place=in_place)
        return _padded_to_tiles(rule) if d_k % 128 else rule

    before = gated_delta.walk_counts()
    with HIGHEST:
        mosaic = _with_gradients(entry(True), operands, go)
        after = gated_delta.walk_counts()
        plain = _with_gradients(
            lambda *a: gated_delta_rule(*a, in_place=False), operands, go)
        exact = _with_gradients(_token_by_token, exact_operands, go)
    assert after["mosaic"] == before["mosaic"] + 1
    assert after["plain"] == before["plain"]
    assert gated_delta.walk_counts()["plain"][gated_delta.NOT_IN_PLACE] == (
        before["plain"].get(gated_delta.NOT_IN_PLACE, 0) + 1)
    assert mosaic[0].shape == (batch, seq, heads, d_v)
    assert [x.dtype for x in mosaic] == [x.dtype for x in plain]
    for name, got, want, true in zip(NAMES, mosaic, plain, exact):
        assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))), name
        if dtype == jnp.float32:
            assert _rel(got, want) < 2e-4, name
            assert _rel(got, true) < 2e-4, name
        else:
            assert _rel(got, true) < 1.15 * _rel(want, true) + 1e-4, name
            assert _rel(got, true) < 2e-2, name


def test_the_rule_names_every_refusal_and_the_counter_counts_them(
        monkeypatch):
    """``gated_delta_rule`` notes each walk under its reason -- the caller's
    word, the backend, the heads' lanes -- and the ``jnp`` walk then gives
    the bits it always gave; the solve's counter keeps its own count."""
    assert gated_delta._why_no_walk(128, 128, 32) is None
    assert gated_delta._why_no_walk(128, 256, 30) is None
    for d_k, d_v in ((96, 192), (128, 192), (64, 128), (24, 48)):
        assert gated_delta._why_no_walk(d_k, d_v, 30) == (
            gated_delta.HEADS_OFF_THE_TILE), (d_k, d_v)
    assert gated_delta._why_no_walk(128, 128, 7) == gated_delta.HEADS_ODD
    assert (gated_delta._heads_a_step(32), gated_delta._heads_a_step(30),
            gated_delta._heads_a_step(14), gated_delta._heads_a_step(2)) == (
                8, 6, 2, 2)
    operands, _ = _operands(1, 32, 1, 24, 48)
    wanted = gated_delta_rule(*operands, in_place=False)

    def counted(in_place, why, solved_by_the_call=False):
        before = gated_delta.walk_counts(), gated_delta.solve_counts()
        o = gated_delta_rule(*operands, in_place=in_place)
        after = gated_delta.walk_counts(), gated_delta.solve_counts()
        assert after[0]["mosaic"] == before[0]["mosaic"]
        assert after[0]["plain"][why] == before[0]["plain"].get(why, 0) + 1
        assert after[1]["mosaic"] - before[1]["mosaic"] == solved_by_the_call
        return o

    np.testing.assert_array_equal(
        counted(False, gated_delta.NOT_IN_PLACE), wanted)
    np.testing.assert_array_equal(counted(True, gated_delta.NO_TPU), wanted)
    assert not gated_delta.walks_rows(128, 128, 2, True)    # a CPU's answer
    _lifted(monkeypatch)
    # Heads of 24 and 48 lanes: the systems by the solve's call, the chunks
    # by the ``jnp`` walk.
    np.testing.assert_allclose(
        counted(True, gated_delta.HEADS_OFF_THE_TILE, True),
        gated_delta_rule(*operands, in_place=False), atol=1e-5)
    assert not gated_delta.walks_rows(24, 48, 2, True)
    assert gated_delta.walks_rows(128, 128, 2, True)
    assert not gated_delta.walks_rows(128, 128, 3, True)
    assert not gated_delta.walks_rows(128, 128, 2, False)


@pytest.mark.parametrize("rows", [False, True], ids=["repeat", "lane tiles"])
def test_key_heads_are_copied_where_the_rule_reads_them(rows):
    """``key_heads_copied``: value head j reads key head ``j // times`` by
    either body, and a key head's gradient is its value heads' sum."""
    x = jax.random.normal(jax.random.key(2), (2, 32, 3, 128))
    weight = jax.random.normal(jax.random.key(3), (2, 32, 6, 128))
    got, back = jax.vjp(
        lambda x: gated_delta.key_heads_copied(x, 2, rows), x)
    np.testing.assert_array_equal(got, jnp.repeat(x, 2, axis=2))
    np.testing.assert_allclose(
        back(weight)[0], weight.reshape(2, 32, 3, 2, 128).sum(3), rtol=1e-6)


def test_what_xla_prepares_for_the_calls_is_what_the_steps_read():
    """``_quantities``: lane ``8 k + j`` of a block's columns is quantity k
    of its head j down the rows, bit for bit; ``across`` holds that head's
    gamma along the lanes and its e^{gamma_C} on every lane."""
    batch, heads, chunks, chunk, a_step = 2, 6, 3, 16, 3
    keys = jax.random.split(jax.random.key(5), 2)
    gamma = jnp.cumsum(-jax.nn.softplus(jax.random.normal(
        keys[0], (batch, heads, chunks, chunk))), axis=-1)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[1], gamma.shape))
    columns, across = gated_delta._quantities(gamma, beta, a_step)
    assert columns.shape == (batch, heads // a_step, chunks * chunk, 128)
    assert across.shape == (batch, chunks, heads // a_step, 16, 128)
    last = gamma[..., -1:]
    wanted = (gamma, jnp.exp(gamma), jnp.exp(last - gamma), beta,
              beta * jnp.exp(gamma))
    for k, want in enumerate(wanted):
        got = columns[..., 8 * k:8 * k + a_step]     # [B, blocks, S, a]
        np.testing.assert_array_equal(
            got.transpose(0, 1, 3, 2).reshape(gamma.shape), want)
        assert not columns[..., 8 * k + a_step:8 * (k + 1)].any()
    assert not columns[..., 40:].any()
    rows = across.transpose(0, 2, 3, 1, 4)       # [B, blocks, 16, N, 128]
    np.testing.assert_array_equal(
        rows[:, :, :a_step, :, :chunk].reshape(gamma.shape), gamma)
    np.testing.assert_array_equal(
        rows[:, :, 8:8 + a_step].reshape(batch, heads, chunks, 128),
        jnp.broadcast_to(jnp.exp(last), (batch, heads, chunks, 128)))
