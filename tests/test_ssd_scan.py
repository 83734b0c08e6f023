"""``ops/ssd.py``'s chunked state-space scan against the token-by-token
recurrence of the plain reference (``benchmark/reference/nemotron_h.py``),
forward and backward -- the ``jnp`` body, and the Mosaic pair interpreted --
its rule and its counter, the log-decays' triangular sums, and the filter's
bias in ``ops/short_conv.py``; on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as reference
from horovod_tpu.ops import short_conv, ssd


def _plain_scan(u, dt, a_log, b, c, d):
    heads, groups = u.shape[2], b.shape[2]
    b, c = (jnp.repeat(t, heads // groups, axis=2) for t in (b, c))
    decay = jnp.exp(-jnp.exp(a_log) * dt)
    return reference.state_space_scan(u, dt, decay, b, c) + d[:, None] * u


def _scan_operands(seq):
    k = jax.random.split(jax.random.key(7), 6)
    batch, heads, width, groups, state = 2, 4, 16, 2, 32
    return (jax.random.normal(k[0], (batch, seq, heads, width)),
            jax.nn.softplus(jax.random.normal(k[1], (batch, seq, heads)) - 2),
            jnp.log(jax.random.uniform(k[2], (heads,), minval=1, maxval=16)),
            jax.random.normal(k[3], (batch, seq, groups, state)),
            jax.random.normal(k[4], (batch, seq, groups, state)),
            jax.random.normal(k[5], (heads,)))


@pytest.mark.parametrize("seq,chunk", [(64, 16), (72, 16)])
def test_chunked_scan_against_token_by_token_forward_and_backward(
        monkeypatch, seq, chunk):
    """Chunks that divide the sequence and chunks that do not (the last is
    padded); the backward pass
    against autodiff of the plain scan."""
    monkeypatch.setattr(reference, "TOKENS", 8)
    operands = _scan_operands(seq)


    def both(scan):
        """y, and the gradients of a function of y that weighs every entry
        differently."""
        return jax.jit(lambda *a: (scan(*a), jax.grad(
            lambda *a: jnp.sum(jnp.sin(scan(*a))), argnums=range(6))(*a)))

    y, grads = both(lambda *a: ssd.ssd_scan(*a, chunk=chunk))(*operands)
    wanted, wanted_grads = both(_plain_scan)(*operands)
    assert y.shape == wanted.shape
    np.testing.assert_allclose(y, wanted, atol=2e-4)
    for got, want in zip(grads, wanted_grads):
        np.testing.assert_allclose(got, want, atol=2e-4 * float(
            jnp.max(jnp.abs(want))))


def test_states_are_the_ones_each_chunk_starts_from():
    u, dt, a_log, b, c, _ = _scan_operands(64)
    states = ssd.ssd_states(u, dt, a_log, b, c, chunk=16)
    assert states.shape == (4, 2, 4, 16, 32) and not states[0].any()
    # The state behind 16 tokens, token by token, head 3 of row 1.
    state = np.zeros((16, 32))
    for t in range(16):
        state = (np.exp(-np.exp(a_log[3]) * dt[1, t, 3]) * state
                 + dt[1, t, 3] * np.outer(u[1, t, 3], b[1, t, 1]))
    np.testing.assert_allclose(states[1, 1, 3], state, atol=1e-5)


# -- the Mosaic pair, interpreted ---------------------------------------------

def _rows_operands(batch, seq, heads, groups, dtype, width=64, state=128):
    """x ``[B, S, H P + 2 G N]`` as the filter leaves it, dt, a_log, and a
    cotangent that weighs every entry of y differently."""
    k = jax.random.split(jax.random.key(11), 5)
    x = jnp.concatenate([
        jax.random.normal(k[0], (batch, seq, heads * width)),
        0.3 * jax.random.normal(k[1], (batch, seq, 2 * groups * state))],
        axis=-1).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[2], (batch, seq, heads)) - 2)
    a_log = jnp.log(jax.random.uniform(k[3], (heads,), minval=1, maxval=16))
    return (x, dt, a_log), jax.random.normal(
        k[4], (batch, seq, heads * width)).astype(dtype)


def _cut(x, heads, groups, width=64, state=128):
    batch, seq, _ = x.shape
    inner, wide = heads * width, groups * state
    return (x[..., :inner].reshape(batch, seq, heads, width),
            x[..., inner:inner + wide].reshape(batch, seq, groups, state),
            x[..., inner + wide:].reshape(batch, seq, groups, state))


def _lifted(monkeypatch):
    """The rule's last reason lifted: a TPU's answer, the pair interpreted."""
    rule = ssd._why_not
    monkeypatch.setattr(ssd, "_why_not", lambda *a: (
        None if rule(*a) == ssd.NO_TPU else rule(*a)))


def _rel(got, want):
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("batch,seq,heads,groups,chunk,dtype", [
    (1, 256, 64, 8, 128, jnp.float32),
    (1, 256, 64, 8, 128, jnp.bfloat16),
    (1, 512, 8, 1, 256, jnp.float32),
    (1, 512, 8, 1, 256, jnp.bfloat16),
    (1, 200, 16, 1, 128, jnp.float32),
    (2, 256, 16, 2, 128, jnp.bfloat16),
    (1, 128, 8, 2, 128, jnp.float32),
], ids=["chunks 128, 8 groups of 8 heads, float32", "the same, bf16",
        "chunks 256, one group, float32", "the same, bf16",
        "no whole chunks, a group two steps", "two rows, bf16",
        "groups of four heads: a step of 256 lanes"])
def test_the_mosaic_pair_is_the_jnp_body_and_the_recurrence(
        monkeypatch, batch, seq, heads, groups, chunk, dtype):
    """``ssd_scan_rows`` in place (the Mosaic pair, interpreted) against the
    same entry not in place (the ``jnp`` body) AND against the token-by-token
    recurrence in float32: y and the gradients of x (u, B and C where the
    filter left them), dt and a_log.  In float32 the pair is the body to
    rounding; in bf16 it is no further from the float32 recurrence than the
    body is (a tenth and a half of room, the H numbers of d a_log half:
    the sums run in another order)."""
    monkeypatch.setattr(reference, "TOKENS", 8)
    _lifted(monkeypatch)
    operands, go = _rows_operands(batch, seq, heads, groups, dtype)

    def both(scan, go):
        def run(*a):
            y, back = jax.vjp(scan, *a)
            return (y, *back(go))
        return jax.jit(run)(*operands)

    def entry(in_place):
        return lambda x, dt, a_log: ssd.ssd_scan_rows(
            x, dt, a_log, heads, groups, 128, in_place, chunk=chunk)

    def token_by_token(x, dt, a_log):
        u, b, c = (t.astype(jnp.float32) for t in _cut(x, heads, groups))
        b, c = (jnp.repeat(t, heads // groups, axis=2) for t in (b, c))
        return reference.state_space_scan(
            u, dt, jnp.exp(-jnp.exp(a_log) * dt), b, c).reshape(
                batch, seq, -1)

    before = ssd.body_counts()
    mosaic = both(entry(True), go)
    after = ssd.body_counts()
    assert after["mosaic"] == before["mosaic"] + 1
    assert after["plain"] == before["plain"]
    plain = both(entry(False), go)
    assert ssd.body_counts()["plain"][ssd.NOT_IN_PLACE] == before[
        "plain"].get(ssd.NOT_IN_PLACE, 0) + 1
    exact = both(token_by_token, go.astype(jnp.float32))
    assert mosaic[0].shape == (batch, seq, heads * 64)
    assert mosaic[0].dtype == dtype and mosaic[1].dtype == dtype
    names = ("y", "d x", "d dt", "d a_log")
    if dtype == jnp.float32:
        for name, got, want, true in zip(names, mosaic, plain, exact):
            assert _rel(got, want) < 2e-4, name
            assert _rel(got, true) < 2e-4, name
        return
    inner, wide = heads * 64, groups * 128
    parts = {"y": [("y", slice(None))],
             "d x": [("d u", slice(0, inner)),
                     ("d B", slice(inner, inner + wide)),
                     ("d C", slice(inner + wide, None))],
             "d dt": [("d dt", slice(None))],
             "d a_log": [("d a_log", slice(None))]}
    for name, got, want, true in zip(names, mosaic, plain, exact):
        for part, lanes in parts[name]:
            ours, theirs = (_rel(t[..., lanes], true[..., lanes])
                            for t in (got, want))
            room = 1.5 if name == "d a_log" else 1.15
            assert ours < room * theirs + 1e-4, part
            assert ours < 6e-3, part


def test_the_rule_names_every_refusal_and_the_counter_counts_them():
    """``_why_not`` by shape and by the caller's word; ``ssd_scan_rows``
    notes each under its reason, and the ``jnp`` body runs."""
    cell = dict(heads=64, width=64, groups=8, state=128, chunk=128,
                in_place=True)
    refusals = [
        (dict(in_place=False), ssd.NOT_IN_PLACE),
        (dict(width=32), ssd.HEADS_OFF_THE_TILE),
        (dict(width=96), ssd.HEADS_OFF_THE_TILE),
        (dict(state=64), ssd.STATE_OFF_THE_TILE),
        (dict(heads=12, groups=4), ssd.NO_HEAD_BLOCK),
        (dict(heads=64, groups=7), ssd.NO_HEAD_BLOCK),
        (dict(chunk=64), ssd.CHUNK_OFF_THE_TILE),
        (dict(), ssd.NO_TPU),               # a CPU's answer
        (dict(groups=1, chunk=256), ssd.NO_TPU),
        (dict(width=128, heads=32), ssd.NO_TPU),
    ]
    for change, why in refusals:
        assert ssd._why_not(**{**cell, **change}) == why, change
    assert (ssd._heads_a_step(8, 64), ssd._heads_a_step(64, 64),
            ssd._heads_a_step(4, 128), ssd._heads_a_step(3, 64)) == (
                8, 8, 4, 0)
    for width, state, chunk, in_place, why in [
            (64, 128, 128, False, ssd.NOT_IN_PLACE),
            (64, 128, 128, True, ssd.NO_TPU),
            (32, 128, 128, True, ssd.HEADS_OFF_THE_TILE),
            (64, 64, 128, True, ssd.STATE_OFF_THE_TILE),
            (64, 128, 32, True, ssd.CHUNK_OFF_THE_TILE)]:
        (x, dt, a_log), _ = _rows_operands(1, 64, 8, 1, jnp.float32, width,
                                           state)
        before = ssd.body_counts()
        y = ssd.ssd_scan_rows(x, dt, a_log, 8, 1, state, in_place,
                              chunk=chunk)
        after = ssd.body_counts()
        assert after["mosaic"] == before["mosaic"]
        assert after["plain"][why] == before["plain"].get(why, 0) + 1
        u, b, c = _cut(x, 8, 1, width, state)
        np.testing.assert_array_equal(y, ssd.ssd_scan(
            u, dt, a_log, b, c, chunk=chunk).reshape(y.shape))


@pytest.mark.parametrize("reverse", [False, True])
def test_the_triangular_sums_are_cumsums_in_float32(reverse):
    """``_summed`` (the ``jnp`` body's, and ``_prepared``'s for the Mosaic
    pair) against ``jnp.cumsum`` in float64-free float32: log-decays of a
    chunk of 256 rows, to rounding; and ``_prepared``'s three bf16 pieces
    add up to the float32 value they were cut from."""
    x = -jax.nn.softplus(jax.random.normal(jax.random.key(5), (3, 7, 256)))
    want = (jnp.flip(jnp.cumsum(jnp.flip(x, -1), -1), -1) if reverse
            else jnp.cumsum(x, -1))
    got = jax.jit(lambda x: ssd._summed(x, reverse))(x)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-6)
    pieces = jax.jit(ssd._rounded_pieces)(want)
    assert all(piece.dtype == jnp.bfloat16 for piece in pieces)
    np.testing.assert_array_equal(
        sum(piece.astype(jnp.float32) for piece in pieces), want)


def test_what_xla_prepares_for_the_pair_is_what_the_steps_read():
    """``_prepared``: a block's rows of ``packed`` times ``_spreads`` put l,
    e^l, e^{l_Q - l}, D and D e^{l_Q - l} on the heads' lanes exactly, and
    ``across`` holds l and D of the block's heads along the sequence."""
    (_, dt, a_log), _ = _rows_operands(2, 256, 16, 2, jnp.float32)
    packed, across = ssd._prepared(dt, a_log, 8, 128)
    assert packed.shape == (2, 2, 128, 256) and packed.dtype == jnp.bfloat16
    assert across.shape == (2, 2, 16, 256)
    ell = jnp.cumsum((dt * -jnp.exp(a_log)).reshape(2, 2, 128, 16), axis=2)
    last = ell[:, :, -1:]
    wanted = [t.reshape(2, 256, 16) for t in (
        ell, jnp.exp(ell), jnp.exp(last - ell), dt.reshape(ell.shape),
        dt.reshape(ell.shape) * jnp.exp(last - ell))]
    spreads = ssd._spreads(8, 64)
    assert spreads.shape == (5, 128, 512)
    for k, want in enumerate(wanted):
        got = jnp.einsum("bhrs,rl->bshl", packed.astype(jnp.float32),
                         spreads[k].astype(jnp.float32))
        for head in range(16):
            lanes = got[:, :, head // 8, (head % 8) * 64:(head % 8 + 1) * 64]
            np.testing.assert_allclose(
                lanes, jnp.broadcast_to(want[..., head:head + 1],
                                        lanes.shape), rtol=3e-5, atol=3e-6)
    np.testing.assert_allclose(
        across[:, :, :8].reshape(2, 16, 256).transpose(0, 2, 1), wanted[0],
        rtol=3e-6, atol=1e-6)
    np.testing.assert_array_equal(
        across[:, :, 8:].reshape(2, 16, 256).transpose(0, 2, 1), dt)


# -- the filter's bias --------------------------------------------------------

@pytest.mark.parametrize("in_place", [False, True])
def test_filter_bias_is_added_before_the_silu_in_both_bodies(in_place):
    """The plain body where the caller does not read its operands in place,
    ``short_conv``'s Mosaic pass (interpreted here) where it does: the same
    values and the same gradient of the bias, and the counter says which."""
    k = jax.random.split(jax.random.key(3), 3)
    y = jax.random.normal(k[0], (2, 32, 256))
    taps = jax.random.uniform(k[1], (4, 256), minval=-0.5, maxval=0.5)
    bias = jax.random.normal(k[2], (256,))
    before = short_conv.body_counts()
    got = short_conv.convolved(y, taps, 1, None, in_place, bias=bias)
    wanted = jax.nn.silu(reference.short_convolution(y, taps, bias))
    np.testing.assert_allclose(got, wanted, atol=1e-5)
    after = short_conv.body_counts()
    if in_place:
        assert after["fused"] == before["fused"] + 1
        assert after["plain"] == before["plain"]
    else:
        assert after["fused"] == before["fused"]
        assert after["plain"][short_conv.NOT_IN_PLACE] == before["plain"].get(
            short_conv.NOT_IN_PLACE, 0) + 1
    g_bias = jax.grad(lambda b: jnp.sum(short_conv.convolved(
        y, taps, 1, None, in_place, bias=b) ** 2))(bias)
    w_bias = jax.grad(lambda b: jnp.sum(jax.nn.silu(
        reference.short_convolution(y, taps, b)) ** 2))(bias)
    np.testing.assert_allclose(g_bias, w_bias, rtol=1e-4, atol=1e-4)
