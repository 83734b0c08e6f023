"""``ops/hyper_connection.py``: a hyper-connected sublayer's passes over its
residual streams as Mosaic calls over rows of X, interpreted here, against
the ``jnp`` bodies of ``models/llama.py`` (``HyperConnection``'s maps,
``_hc_read``, ``_hc_write``), which every trace that may hold no Mosaic call
still runs: each call alone, the threaded pair ``streams`` / ``write`` around
a stand-in sublayer with every gradient, the rule and its counter, and a tiny
model whose layers take the calls against the same model where they do not.

In float32 the calls and the bodies agree to rounding.  In bf16 a call is
held to a unit in the last place of the ``jnp`` body's FLOAT32 values on the
same operands; Phi is then made of bf16 values, as the chip's MXU reads it at
default precision from either body."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from horovod_tpu.models import LlamaModel
from horovod_tpu.models import llama
from horovod_tpu.models.llama import HyperConnection
from horovod_tpu.ops import hyper_connection as hc
from horovod_tpu.ops.flash_attention import flash_attention_fn
from tiny_sizes import TINY          # tests/conftest.py put it on the path

CELL = "xing4.0-29b-a4b.train-s8k"
CONFIG = llama.LlamaConfig(hc_sinkhorn_iters=5)
# (B, S, n, H, rows of a block: None is the module's own choice, all of T).
SHAPES = {"4 streams of 128, one block": (1, 64, 4, 128, None),
          "4 streams of 256, three blocks": (2, 48, 4, 256, 32),
          "2 streams of 256, two blocks": (1, 64, 2, 256, 32),
          "2 streams of 128, one block": (2, 16, 2, 128, None)}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
_JITS = (hc._stats, hc._read, hc._write, hc._write_bwd, hc._read_bwd,
         hc._streams_bwd_call)


@pytest.fixture
def blocks_of(monkeypatch):
    """``blocks_of(rows)``: the calls walk blocks of that many rows (their
    jits' traces are dropped: the choice is no argument of theirs)."""
    def choose(rows):
        if rows is not None:
            monkeypatch.setattr(hc, "_rows_of", lambda _: rows)
        for f in _JITS:
            f.clear_cache()
    yield choose
    for f in _JITS:
        f.clear_cache()


@pytest.fixture
def lifted(monkeypatch):
    """The rule without its last reason: off the TPU the entries take the
    calls, interpreted (``tests/test_ssd_scan.py::_lifted``'s way)."""
    rule = hc._why_not
    monkeypatch.setattr(hc, "_why_not", lambda *a: (
        None if rule(*a) == hc.NO_TPU else rule(*a)))


def _f32(x):
    return np.asarray(jnp.asarray(x, jnp.float32))


def _maps(n):
    return functools.partial(llama._hc_maps, CONFIG, n)


def _operands(shape, dtype, seed=66):
    b, s, n, h, _ = shape
    t, m = b * s, n * (n + 2)
    ks = jax.random.split(jax.random.key(seed), 10)

    def normal(k, *dims):
        return jax.random.normal(k, dims, jnp.float32).astype(dtype)

    # (bf16 values for Phi and d raw, in either dtype: the module's
    # docstring, Precision.)
    phi = (jax.random.normal(ks[4], (n * h, m)) / np.sqrt(n * h)).astype(
        jnp.bfloat16).astype(jnp.float32)
    gain = 1 + 0.3 * jax.random.normal(ks[5], (m,))
    bias = 0.3 * jax.random.normal(ks[6], (m,))
    x = normal(ks[0], t, n * h) * jnp.repeat(jnp.arange(1, n + 1) / 2.0, h
                                             ).astype(dtype)
    flat = x.astype(jnp.float32)
    h_pre, h_post, h_res = _maps(n)(
        (flat @ phi).T, jnp.mean(flat * flat, axis=-1), gain, bias)
    return dict(
        x=x, g=normal(ks[1], t, n * h), y=normal(ks[2], t, h),
        gin=normal(ks[3], t, h), phi=phi, gain=gain, bias=bias, h_pre=h_pre,
        h_post=h_post, h_res=h_res,
        draw=(0.1 * jax.random.normal(ks[7], (t, m))).astype(
            jnp.bfloat16).astype(jnp.float32),
        dss=1e-3 * jax.random.normal(ks[8], (t,)))


def _close(name, got, want, dtype):
    """``got`` of a call in ``dtype`` against the ``jnp`` body's float32
    values ``want``: float32 arrays to rounding, arrays of ``dtype`` to a
    unit in the last place."""
    assert got.shape == want.shape, name
    a, b = _f32(got), _f32(want)
    size = max(float(np.abs(b).max()), 1e-30)
    if got.dtype == jnp.bfloat16:
        assert np.all(np.abs(a - b) <= 2.0 ** -7 * np.abs(b) + 2e-3 * size
                      ), (name, np.abs(a - b).max(), size)
    else:
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * max(size, 1.0),
                                   err_msg=name)


# -- the jnp bodies on rows, as ``models/llama.py`` has them --------------------

def _read_body(n, x, h_pre):
    t = x.shape[0]
    return llama._hc_read(x.reshape(1, t, n, -1), h_pre)[0]


def _write_body(n, x, y, h_post, h_res):
    t = x.shape[0]
    return llama._hc_write(x.reshape(1, t, n, -1), y[None], h_post,
                           h_res).reshape(x.shape)


# -- each call against its jnp body ---------------------------------------------

@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES)
@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES)
def test_each_call_gives_its_jnp_bodys_values(dtype, shape, blocks_of):
    blocks_of(shape[4])
    n = shape[2]
    m = n * (n + 2)
    o = _operands(shape, dtype)
    up = {k: v.astype(jnp.float32) for k, v in o.items()}
    assert hc._rows_of(o["x"]) == (shape[4] or shape[0] * shape[1])
    phi_t = hc._phi_rows(o["phi"], dtype)
    assert phi_t.shape == (-(-m // 16) * 16, n * shape[3])

    # F1: the product with Phi and a token's sum of squares.
    raw, ss = hc._stats(o["x"], phi_t, interpret=True)
    assert raw.dtype == ss.dtype == jnp.float32
    _close("raw", raw[:, :m], up["x"] @ o["phi"], dtype)
    assert not np.any(_f32(raw[:, m:]))
    _close("ss", ss, jnp.sum(up["x"] ** 2, axis=-1), dtype)

    # F2 and F3: the read and the write.
    _close("x_in", hc._read(o["x"], o["h_pre"], interpret=True),
           _read_body(n, up["x"], o["h_pre"]), dtype)
    mixes = (o["h_post"], o["h_res"])
    _close("written", hc._write(o["x"], o["y"], *mixes, interpret=True),
           _write_body(n, up["x"], up["y"], *mixes), dtype)

    # B3: the write's transpose, every cotangent from one call.
    want = jax.vjp(functools.partial(_write_body, n), up["x"], up["y"],
                   *mixes)[1](up["g"])
    got = hc._write_bwd(o["g"], o["x"], o["y"], *mixes, interpret=True)
    for name, a, b in zip(("dx_res", "dy", "dh_post", "dh_res"), got, want):
        _close(name, a, b, dtype)

    # B2a: the read's transpose on h_pre.
    _close("dh_pre", hc._read_bwd(o["x"], o["gin"], n=n, interpret=True),
           jax.vjp(functools.partial(_read_body, n), up["x"], o["h_pre"])[1](
               up["gin"])[1], dtype)

    # B2b: x's whole cotangent from the four that reach it, and dPhi.
    def readers(x, phi):
        return (x, _read_body(n, x, o["h_pre"]), x @ phi,
                jnp.sum(x * x, axis=-1))

    want = jax.vjp(readers, up["x"], o["phi"])[1](
        (up["g"], up["gin"], o["draw"], o["dss"]))
    pad = ((0, 0), (0, phi_t.shape[0] - m))
    dx, dphi = hc._streams_bwd_call(
        o["x"], o["g"], o["gin"], o["h_pre"], o["dss"],
        jnp.pad(o["draw"], pad), phi_t, interpret=True)
    _close("dx", dx, want[0], dtype)
    _close("dphi", dphi[:m].T, want[1], dtype)
    assert not np.any(_f32(dphi[m:]))


# -- the threaded pair around a stand-in sublayer --------------------------------

def _sublayer(body, n, x, w, phi, gain, bias):
    """Maps, read, ``y = tanh(x_in w)``, write, on ``x [1, T, n, H]``."""
    if body == "mosaic":
        x, x_in, h_post, h_res = hc.streams(_maps(n), x, phi, gain, bias)
        return hc.write(x, jnp.tanh(x_in @ w), h_post, h_res)
    flat = x.reshape(x.shape[1], -1).astype(jnp.float32)
    h_pre, h_post, h_res = _maps(n)(
        jnp.einsum("tk,km->mt", flat, phi), jnp.mean(flat * flat, axis=-1),
        gain, bias)
    return llama._hc_write(x, jnp.tanh(llama._hc_read(x, h_pre) @ w),
                           h_post, h_res)


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES)
@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES)
def test_the_threaded_pair_gives_the_jnp_sublayers_values_and_gradients(
        dtype, shape, blocks_of):
    """Forward and every gradient (X, the sublayer's weight, Phi, gains,
    biases).  X has three readers; the ``jnp`` sublayer's gradient of X is
    the sum autodiff makes of their three cotangents, and the pair's is the
    one B2b writes."""
    blocks_of(shape[4])
    b, s, n, h, _ = shape
    o = _operands(shape, dtype)
    w = (jax.random.normal(jax.random.key(3), (h, h)) / np.sqrt(h)).astype(
        dtype)
    x, go = (o[k].reshape(1, b * s, n, h) for k in ("x", "g"))

    def both_ways(body, *operands):
        out, back = jax.vjp(functools.partial(_sublayer, body, n), *operands)
        return (out, *back(go.astype(out.dtype)))

    got = jax.jit(functools.partial(both_ways, "mosaic"))(
        x, w, o["phi"], o["gain"], o["bias"])
    want = jax.jit(functools.partial(both_ways, "jnp"))(
        x.astype(jnp.float32), w.astype(jnp.float32), o["phi"], o["gain"],
        o["bias"])
    for name, a, b in zip(("out", "dx", "dw", "dphi", "dgain", "dbias"),
                          got, want):
        assert a.dtype == (dtype if name in ("out", "dx", "dw")
                           else jnp.float32), name
        if dtype == jnp.float32:
            _close(name, a, b, dtype)
        elif name in ("out", "dx"):
            # x_in and y are rounded to bf16 on the way, as the jnp body
            # rounds them in bf16: a few units in the last place of out.
            size = np.abs(_f32(b)).max()
            assert np.all(np.abs(_f32(a) - _f32(b)) <= 2.0 ** -5 * np.abs(
                _f32(b)) + 2e-2 * size), name
        else:
            assert np.linalg.norm(_f32(a) - _f32(b)) <= 2e-2 * np.linalg.norm(
                _f32(b)), name


def test_in_bf16_the_pair_is_as_near_float32_as_the_jnp_sublayer():
    shape = SHAPES["4 streams of 128, one block"]
    b, s, n, h, _ = shape
    o = _operands(shape, jnp.bfloat16)
    w = (jax.random.normal(jax.random.key(3), (h, h)) / np.sqrt(h))
    x, go = (o[k].reshape(1, b * s, n, h) for k in ("x", "g"))

    def both_ways(body, x, w):
        out, back = jax.vjp(lambda x: _sublayer(
            body, n, x, w, o["phi"], o["gain"], o["bias"]), x)
        return out, back(go.astype(out.dtype))[0]

    exact = jax.jit(functools.partial(both_ways, "jnp"))(
        x.astype(jnp.float32), w)
    off = {body: [float(np.linalg.norm(_f32(a) - _f32(e)))
                  for a, e in zip(jax.jit(functools.partial(both_ways, body))(
                      x, w.astype(jnp.bfloat16)), exact)]
           for body in ("mosaic", "jnp")}
    for k, name in enumerate(("out", "dx")):
        assert off["mosaic"][k] <= 1.05 * off["jnp"][k], (name, off)


# -- the rule and its counter ---------------------------------------------------

@pytest.mark.parametrize("shape, in_place, why", [
    ((1, 8192, 4, 3584), True, hc.NO_TPU),              # (on a TPU: None)
    ((2, 128, 4, 128), True, hc.NO_TPU),
    ((1, 8192, 4, 3584), False, hc.NOT_IN_PLACE),
    ((1, 8192, 4, 3600), True, hc.OFF_THE_LANE_TILE),
    ((1, 8192, 4 * 3584), True, hc.OFF_THE_LANE_TILE),       # no streams
    ((1, 8200, 4, 3584), True, hc.NO_ROW_BLOCK),
    ((3, 5, 2, 128), True, hc.NO_ROW_BLOCK),
])
def test_the_rule_reads_the_shape_and_the_callers_word(shape, in_place, why,
                                                       monkeypatch):
    assert hc._why_not(shape, in_place) == why
    monkeypatch.setattr(hc, "_interpret", lambda: False)
    assert hc._why_not(shape, in_place) == (
        None if why == hc.NO_TPU else why)


def test_the_cells_blocks_are_128_rows_of_all_four_streams():
    x = jax.ShapeDtypeStruct((8192, 4 * 3584), jnp.bfloat16)
    assert hc._rows_of(x) == 128 and hc._piece(3584) == 512
    assert hc._rows_of(jax.ShapeDtypeStruct(x.shape, jnp.float32)) == 64
    assert hc._rows_of(jax.ShapeDtypeStruct((96, 512), jnp.float32)) == 32
    assert hc._piece(4 * 3584) == 512 and hc._piece(384) == 384


@pytest.mark.parametrize("way", ["mosaic", hc.NOT_IN_PLACE, hc.NO_TPU])
def test_the_entry_counts_the_body_it_took(way, monkeypatch, request):
    """``HyperConnection(.., in_place)(x, read=True)`` is the sublayer's one
    entry: it returns x beside ``x_in`` and the two maps the write reads,
    counts its body once a trace, and off the rule's shapes is the ``jnp``
    body bit for bit."""
    if way == "mosaic":
        request.getfixturevalue("lifted")
    cfg = dataclasses.replace(CONFIG, hidden_size=128, hc_mult=4)
    x = _operands(SHAPES["4 streams of 128, one block"], jnp.float32)[
        "x"].reshape(1, 64, 4, 128)
    plain = HyperConnection(cfg)
    params = plain.init(jax.random.key(0), x)
    h_pre, h_post, h_res = plain.apply(params, x)
    before = hc.body_counts()
    got = jax.jit(lambda p, x: HyperConnection(
        cfg, in_place=way != hc.NOT_IN_PLACE).apply(p, x, read=True))(
            params, x)
    after = hc.body_counts()
    if way == "mosaic":
        assert after["mosaic"] == before["mosaic"] + 1
        assert after["plain"] == before["plain"]
    else:
        assert after["mosaic"] == before["mosaic"]
        assert after["plain"].get(way, 0) == before["plain"].get(way, 0) + 1
    want = (x, llama._hc_read(x, h_pre), h_post, h_res)
    for a, b in zip(got, want):
        if way == "mosaic":
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        else:
            np.testing.assert_array_equal(a, b)


# -- a tiny model whose layers take the calls ------------------------------------

def _tiny_job():
    cell = manifest.cell(CELL)
    job = manifest.load_job(cell["config"]["job"]).build(
        {**cell["config"], **TINY["hc_moe_lm"]["config"]},
        {**cell["traffic"], **TINY["hc_moe_lm"]["traffic"]}, 1)
    job.llama = dataclasses.replace(job.llama, dtype=jnp.float32,
                                    logits_dtype=jnp.float32)
    return job


@pytest.fixture(scope="module")
def tiny_model():
    """A dense and a routed layer under ``remat``, four streams; its loss and
    gradients where no layer takes the calls (off the TPU)."""
    job = _tiny_job()
    job.model = LlamaModel(job.llama, attention_fn=flash_attention_fn)
    assert job.llama.remat != "none" and job.llama.hc_mult == 4
    state = jax.jit(job.init_state)(jax.random.key(0))
    params = jax.tree.map(lambda p: p.astype(jnp.float32), state[0])
    tokens = job.make_batch(jax.random.key(1), 2)

    def step(*operands):        # (a new function a trace: jit keeps none)
        return jax.jit(lambda *a: jax.value_and_grad(
            job.loss_fn, has_aux=True)(*a))(*operands)

    with jax.default_matmul_precision("highest"):
        before = hc.body_counts()
        want = step(params, state[2], tokens)
        assert hc.body_counts()["mosaic"] == before["mosaic"]
    return job, step, params, state[2], tokens, want


def test_a_model_in_place_is_the_model_it_is_elsewhere(tiny_model, lifted):
    """Every sublayer of both layers takes the calls (counted), the loss and
    every gradient leaf are the ``jnp`` bodies' to float32 rounding."""
    _, step, params, bias, tokens, ((want_loss, _), want_grads) = tiny_model
    before = hc.body_counts()
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = step(params, bias, tokens)
    after = hc.body_counts()
    # Two layers of two sublayers, forward and again under ``remat``.
    assert after["mosaic"] - before["mosaic"] >= 4
    assert after["plain"] == before["plain"]
    assert float(loss) == pytest.approx(float(want_loss), abs=2e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-5 * max(float(jnp.abs(w).max()), 1.0),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("change", ["h_post halved", "H_res the identity"])
def test_a_write_wrapped_by_name_engages_on_the_calls(tiny_model, lifted,
                                                      monkeypatch, change):
    """``tools/xing4_wrong_versions.py`` makes wrong models by wrapping
    ``llama._hc_write`` BY NAME with its four operands and nothing else: the
    layer looks the name up at call time, and what says ``in_place`` reaches
    the write around the wrapper."""
    _, step, params, bias, tokens, ((want_loss, _), _) = tiny_model
    write = llama._hc_write

    def wrapped(x, y, h_post, h_res):
        if change == "h_post halved":
            return write(x, y, h_post / 2, h_res)
        return write(x, y, h_post, jnp.broadcast_to(
            jnp.eye(4)[:, :, None], h_res.shape))

    monkeypatch.setattr(llama, "_hc_write", wrapped)
    before = hc.body_counts()
    with jax.default_matmul_precision("highest"):
        (loss, _), _ = step(params, bias, tokens)
    assert hc.body_counts()["mosaic"] > before["mosaic"]
    assert abs(float(loss) - float(want_loss)) > 1e-3, change
