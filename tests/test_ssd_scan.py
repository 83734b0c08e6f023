"""``ops/ssd.py``'s chunked state-space scan against the token-by-token
recurrence of the plain reference (``benchmark/reference/nemotron_h.py``),
forward and backward, and the filter's bias in ``ops/short_conv.py``; on the
CPU in float32."""

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
