"""The decoder-hybrid-decoder stack (``models/llama.py`` with ``mb_per_layer``:
``Mamba1`` selective scans, differential attention under a window, in full and
across layers, ``GatedMemory`` units, LayerNorm, a tied head) against the
plain reference (``benchmark/reference/phi4_flash.py``), and
``ops/selective_scan.py`` against a token-by-token loop, on the CPU in float32
at a small size with seeded random weights moved off their start.  Eight
layers: the least depth at which the placement rule builds all five kinds.
One compile of the model's gradients and one of the reference's serve the
comparison; a wrong version compiles a forward pass alone."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.reference import phi4_flash as reference
from horovod_tpu.models import LlamaConfig, LlamaModel, llama
from horovod_tpu.ops import selective_scan as sscan
from tiny_sizes import TINY

CELL = "phi-4-mini-flash.train-s8k"
# 200 tokens: two chunks of the scan's jnp body, the second one ragged; a
# window of 32 keys; 128 queries a block of the reference's attention.
SEQ = 200


@pytest.fixture(scope="module")
def tiny():
    """The tiny job in float32 with the model's own dense attention, its
    parameters moved off their start (every bias, scale, skip and lambda
    differs from 0 or 1), a batch, the configuration the reference reads,
    and the reference's loss and gradients."""
    cell = manifest.cell(CELL)
    over = TINY["sambay_lm"]
    # (Eight layers: the harness's tiny size has four.)
    config = {**cell["config"], **over["config"], "num_hidden_layers": 8}
    job = manifest.load_job("sambay_lm").build(
        config, {**cell["traffic"], **over["traffic"], "sequence": SEQ}, 1)
    job.llama = dataclasses.replace(job.llama, dtype=jnp.float32,
                                    logits_dtype=jnp.float32, remat="none")
    job.model = LlamaModel(job.llama)
    k_init, k_move, k_batch = jax.random.split(jax.random.key(54), 3)
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
        got = jax.jit(jax.value_and_grad(job.loss_fn))(params, batch)
    return job, config, params, batch, wanted, got


def _distance(job, got, wanted):
    """(|loss - reference loss|, the gradient's relative distance over all
    leaves, the worst leaf's)."""
    off = jax.tree.map(lambda g, r: jnp.sum(jnp.square(g - r)),
                       job.to_reference(got[1]), wanted[1])
    size = jax.tree.map(lambda r: jnp.sum(jnp.square(r)), wanted[1])
    off, size = (np.asarray(jax.tree.leaves(t)) for t in (off, size))
    return (abs(float(got[0] - wanted[0])),
            float(np.sqrt(off.sum() / size.sum())),
            float(np.max(np.sqrt(off / (size + 1e-30)))))


def test_program_matches_reference_loss_and_every_gradient_leaf(tiny):
    """Float32 both sides: what is left is the order of float32 sums (the
    scan's chunks against a token loop, a tiled softmax), 1e-6 a leaf on
    most and 1e-4 on the leaves whose gradient nearly cancels."""
    job, _, params, _, wanted, got = tiny
    loss_off, grad_off, worst_leaf = _distance(job, got, wanted)
    assert loss_off < 2e-5 and grad_off < 1e-4 and worst_leaf < 2e-3, (
        loss_off, grad_off, worst_leaf)
    # No leaf of the comparison is empty or starts where it was initialised.
    flat = jax.tree.leaves(job.to_reference(params))
    assert len(flat) == len(jax.tree.leaves(wanted[1]))
    assert all(float(jnp.sum(jnp.abs(g))) > 0
               for g in jax.tree.leaves(wanted[1]))


# -- wrong versions, each caught by name -------------------------------------

def _rms_for_layer_norm(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * w["scale"] + w["bias"])


def _own_projections(monkeypatch):
    """The cross layers project THEIR OWN input with the sharing layer's
    key and value columns, in place of reading what that layer projected."""
    right = reference.attention_mixer

    def wrong(u, layer, config, index, window, kv=None):
        q_width = config["num_attention_heads"] * config["head_dim"]
        if kv is None:      # the weights travel with what the layer shares
            out, kv = right(u, layer, config, index, window)
            return out, (*kv, layer["wqkv"][:, q_width:],
                         layer["bqkv"][q_width:])
        own = u @ kv[2] + kv[3]
        return right(u, layer, config, index, window,
                     tuple(jnp.split(own, 2, axis=-1)))

    monkeypatch.setattr(reference, "attention_mixer", wrong)


WRONG = {
    "memory_after_the_gate": lambda m: m.setattr(
        reference, "memory_of", lambda y, z, skip: y * jax.nn.silu(z)),
    "memory_without_the_skip": lambda m: m.setattr(
        reference, "memory_of", lambda y, z, skip: y - skip),
    "one_minus_lambda_init_dropped": lambda m: m.setattr(
        reference, "out_factor", lambda index: 1.0),
    "subnorm_dropped": lambda m: m.setattr(
        reference, "pair_norm", lambda diff, scale, eps: diff * scale),
    "lambda_without_its_exp": lambda m: m.setattr(
        reference, "lambda_of", lambda layer, index: (
            jnp.sum(layer["lambda_q1"] * layer["lambda_k1"])
            - jnp.sum(layer["lambda_q2"] * layer["lambda_k2"])
            + reference.lambda_init(index))),
    "cross_layer_reads_its_own_projections": _own_projections,
    "rmsnorm_for_layernorm": lambda m: m.setattr(
        reference, "layer_norm", _rms_for_layer_norm),
}


@pytest.mark.parametrize("variant", [*WRONG, "window_one_key_wider"])
def test_a_wrong_version_fails_the_comparison(tiny, monkeypatch, variant):
    """Each departure from the layers as written down, alone, in the
    REFERENCE: on parameters moved off their start its loss is already
    outside what the program keeps to (2e-5), so the forward pass alone is
    compiled."""
    job, config, params, batch, wanted, _ = tiny
    if variant == "window_one_key_wider":
        config = {**config, "sliding_window": config["sliding_window"] + 1}
    else:
        WRONG[variant](monkeypatch)
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(lambda p: reference.loss(
            job.to_reference(p), batch, config))(params)
    monkeypatch.undo()
    assert abs(float(loss - wanted[0])) > 2e-4, (variant, float(loss))


def test_an_untied_head_is_another_model(tiny):
    """With ``lm_head = E^T`` an untied head gives the tied loss, and an
    embedding gradient that lacks the head's part: the tied gradient is the
    sum of the two."""
    job, _, params, batch, _, got = tiny
    untied = LlamaModel(dataclasses.replace(job.llama,
                                            tie_word_embeddings=False))
    table = params["params"]["tok_emb"]["embedding"]
    loose = {"params": {**params["params"],
                        "lm_head": {"kernel": table.T}}}
    from horovod_tpu.ops.losses import softmax_cross_entropy
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: softmax_cross_entropy(untied.apply(p, batch[:, :-1]),
                                            batch[:, 1:])))(loose)
    assert float(loss) == pytest.approx(float(got[0]), abs=1e-5)
    lookup = grads["params"]["tok_emb"]["embedding"]
    head = grads["params"]["lm_head"]["kernel"].T
    tied = got[1]["params"]["tok_emb"]["embedding"]
    np.testing.assert_allclose(lookup + head, tied, atol=2e-6)
    assert float(jnp.linalg.norm(lookup - tied)) > 0.1 * float(
        jnp.linalg.norm(tied))


# -- the wiring: who writes, who reads ----------------------------------------

def _grads(job, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(job.loss_fn))(params, batch)


def _norm_of_difference(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_the_readers_gradients_reach_their_writers(tiny, monkeypatch):
    """With the gated memory unit's reading cut (a ``stop_gradient`` on the
    memory) layer N / 2's ``a_log`` and ``x_proj`` get another gradient, and
    with the cross layer's cut the key and value columns of layer N / 2 +
    1's ``wqkv`` do; the query columns, which no later layer reads, keep
    theirs to rounding only in the second case."""
    job, _, params, batch, _, got = tiny
    half = job.llama.num_layers // 2
    unit, cross = llama.GatedMemory.__call__, (
        llama.DifferentialAttention.__call__)
    monkeypatch.setattr(
        llama.GatedMemory, "__call__", lambda self, x, memory: unit(
            self, x, jax.lax.stop_gradient(memory)))
    monkeypatch.setattr(
        llama.DifferentialAttention, "__call__",
        lambda self, x, cos, sin, kv=None: cross(
            self, x, cos, sin,
            None if kv is None else jax.lax.stop_gradient(kv)))
    _, cut = _grads(job, params, batch)
    monkeypatch.undo()
    whole_scan = got[1]["params"][f"layer_{half}"]["mamba"]
    cut_scan = cut["params"][f"layer_{half}"]["mamba"]
    assert _norm_of_difference(cut_scan["a_log"], whole_scan["a_log"]) > 0.05
    assert _norm_of_difference(cut_scan["x_proj"]["kernel"],
                               whole_scan["x_proj"]["kernel"]) > 0.05
    q_width = job.llama.num_heads * job.llama.head_dim
    whole_kv = got[1]["params"][f"layer_{half + 1}"]["attn"]["wqkv"]["kernel"]
    cut_kv = cut["params"][f"layer_{half + 1}"]["attn"]["wqkv"]["kernel"]
    assert _norm_of_difference(cut_kv[:, q_width:],
                               whole_kv[:, q_width:]) > 0.05
    # The layers in front of both writers see the cut only through them.
    assert _norm_of_difference(
        cut["params"]["layer_0"]["mamba"]["a_log"],
        got[1]["params"]["layer_0"]["mamba"]["a_log"]) > 1e-4


def test_recomputing_each_layer_changes_nothing_but_rounding(tiny):
    """``remat="layer"``: the shared tensors go through ``nn.remat`` as layer
    inputs and outputs, and every gradient, the writers' included, agrees."""
    job, _, params, batch, _, got = tiny
    other = manifest.load_job("sambay_lm").build(job.config, {
        "sequence": SEQ, "batch_per_chip": 2, "sample_per_chip": 1}, 1)
    other.llama = dataclasses.replace(job.llama, remat="layer")
    other.model = LlamaModel(other.llama)
    loss, grads = _grads(other, params, batch)
    assert float(loss) == pytest.approx(float(got[0]), abs=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(got[1])):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)


# -- differential attention alone ---------------------------------------------

@pytest.mark.parametrize("window", [None, 5])
def test_differential_attention_is_the_dense_two_softmax_formula(window):
    cfg = LlamaConfig(
        hidden_size=32, num_layers=4, num_heads=4, num_kv_heads=2,
        attention_head_dim=8, rope_theta=None, attention_kind="differential",
        mb_per_layer=2, ssm_state_size=8,
        sliding_window=window or 99, dtype=jnp.float32)
    index = 1 if window else 3          # a windowed layer, a full one
    module = llama.DifferentialAttention(cfg, index=index)
    x = jax.random.normal(jax.random.key(0), (2, 12, 32))
    lambda_init = 0.8 - 0.6 * np.exp(-0.3 * index)

    @jax.jit
    def both(x):
        """(the module's output, its k and v, the dense formula's)."""
        variables = module.init(jax.random.key(1), x, None, None)
        p = jax.tree.map(lambda leaf: leaf + 0.3 * jax.random.normal(
            jax.random.key(2), leaf.shape), variables["params"])
        out, (k, v) = module.apply({"params": p}, x, None, None)
        qkv = x @ p["wqkv"]["kernel"] + p["wqkv"]["bias"]
        q = qkv[..., :32].reshape(2, 12, 4, 8)
        k2, v2 = (qkv[..., 32:48].reshape(2, 12, 2, 8),
                  qkv[..., 48:].reshape(2, 12, 2, 8))
        back = jnp.arange(12)[:, None] - jnp.arange(12)[None, :]
        keep = (back >= 0) & (back < (window or 12))
        lam = (jnp.exp(p["lambda_q1"] @ p["lambda_k1"])
               - jnp.exp(p["lambda_q2"] @ p["lambda_k2"]) + lambda_init)
        # Two query pairs over ONE key-value pair: both read heads (0, 1).
        values = jnp.concatenate([v2[:, :, 0], v2[:, :, 1]], -1)
        pairs = []
        for j in range(2):
            maps = [jax.nn.softmax(jnp.where(keep, jnp.einsum(
                "bqd,bkd->bqk", q[:, :, 2 * j + i], k2[:, :, i])
                / np.sqrt(8), -jnp.inf), axis=-1) for i in range(2)]
            diff = jnp.einsum("bqk,bkd->bqd", maps[0] - lam * maps[1],
                              values)
            pairs.append(diff / jnp.sqrt(
                jnp.mean(diff ** 2, -1, keepdims=True) + cfg.rms_eps)
                * p["subln"] * (1 - lambda_init))
        wanted = (jnp.concatenate(pairs, -1) @ p["wo"]["kernel"]
                  + p["wo"]["bias"])
        return out, k, v, qkv, wanted

    with jax.default_matmul_precision("highest"):
        out, k, v, qkv, wanted = both(x)
    np.testing.assert_allclose(k, qkv[..., 32:48], atol=1e-6)
    np.testing.assert_allclose(v, qkv[..., 48:], atol=1e-6)
    np.testing.assert_allclose(out, wanted, atol=2e-5)


# -- the selective scan alone -------------------------------------------------

def _token_loop(u, delta, a_log, b, c, d, carry=jnp.float32):
    """The recurrence one token a step, the state carried in ``carry``."""
    a = -jnp.exp(a_log.astype(jnp.float32))

    def token(h, x):
        u, delta, b, c = (t.astype(jnp.float32) for t in x)
        h = (jnp.exp(delta[..., None] * a) * h.astype(jnp.float32)
             + (delta * u)[..., None] * b[:, None, :])
        return h.astype(carry), jnp.einsum("bcn,bn->bc", h, c) + d * u

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (u, delta, b, c))
    _, y = jax.lax.scan(token, jnp.zeros(
        (u.shape[0], u.shape[2], a.shape[1]), carry), xs)
    return jnp.moveaxis(y, 0, 1)


@pytest.fixture(scope="module")
def operands():
    batch, seq, channels, states = 2, 80, 256, 16
    keys = jax.random.split(jax.random.key(7), 7)
    a_log = (jnp.log(jnp.arange(1, states + 1, dtype=jnp.float32))
             + 0.1 * jax.random.normal(keys[2], (channels, states)))
    args = (jax.random.normal(keys[0], (batch, seq, channels)),
            jax.nn.softplus(jax.random.normal(keys[1],
                                              (batch, seq, channels)) - 2),
            a_log, jax.random.normal(keys[3], (batch, seq, states)),
            jax.random.normal(keys[4], (batch, seq, states)),
            1 + 0.1 * jax.random.normal(keys[5], (channels,)))
    weights = jax.random.normal(keys[6], (batch, seq, channels))

    def value_and_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda *xs: jnp.sum(fn(*xs).astype(jnp.float32) * weights),
            argnums=tuple(range(6))))

    return args, value_and_grads, value_and_grads(_token_loop)(*args)


def _apart(got, wanted):
    return [_norm_of_difference(g.astype(jnp.float32), w)
            for g, w in zip((got[0], *got[1]), (wanted[0], *wanted[1]))]


@pytest.mark.parametrize("body", ["chunks_of_32", "chunks_of_128", "mosaic"])
def test_scan_is_the_token_loop_with_all_six_gradients(operands, body):
    """Both bodies at 80 rows: two and a half chunks of 32, one ragged chunk
    of 128, five blocks of the Mosaic pair in interpret mode."""
    args, value_and_grads, wanted = operands
    fn = {"chunks_of_32": lambda *xs: sscan._plain(*xs, chunk=32),
          "chunks_of_128": sscan._plain,
          "mosaic": lambda *xs: sscan._mosaic(*xs, True)}[body]
    assert max(_apart(value_and_grads(fn)(*args), wanted)) < 2e-6


def test_bf16_operands_keep_a_float32_state():
    """u, B and C in bf16 over 1,024 tokens of slow decays (0.999 a token,
    so a state is the sum of a thousand writes): the state and the decays
    stay float32 in both bodies, and the result is the float32 loop's on
    the rounded operands to y's one rounding (2^-9 an entry); the same loop
    with a bf16 carry, its y NOT rounded, is several times further off."""
    batch, seq, channels, states = 1, 1024, 128, 16
    keys = jax.random.split(jax.random.key(8), 3)
    low = (jax.random.normal(keys[0], (batch, seq, channels), jnp.bfloat16),
           jnp.full((batch, seq, channels), 2e-3),
           jnp.full((channels, states), np.log(0.5), jnp.float32),
           1 + jax.random.normal(keys[1], (batch, seq, states), jnp.bfloat16),
           1 + jax.random.normal(keys[2], (batch, seq, states), jnp.bfloat16),
           jnp.ones((channels,)))
    wanted = jax.jit(_token_loop)(*low)
    for fn in (sscan._plain, lambda *xs: sscan._mosaic(*xs, True)):
        got = jax.jit(fn)(*low)
        assert got.dtype == jnp.bfloat16
        assert _norm_of_difference(got.astype(jnp.float32), wanted) < 2.5e-3
    assert _norm_of_difference(jax.jit(lambda *xs: _token_loop(
        *xs, carry=jnp.bfloat16))(*low), wanted) > 5e-3


def test_the_entry_takes_the_jnp_body_off_the_chip_and_says_why(operands):
    args, _, _ = operands
    before = sscan.body_counts()
    y = sscan.selective_scan(*args, in_place=True)      # a CPU: no TPU
    sscan.selective_scan(*args, in_place=False)
    sscan.selective_scan(*(x[..., :100] if x.ndim == 3 and x.shape[-1] == 256
                           else x[:100] for x in args), in_place=True)
    after = sscan.body_counts()
    assert after["mosaic"] == before["mosaic"]
    for reason in (sscan.NO_TPU, sscan.NOT_IN_PLACE, sscan._OFF_TILING):
        assert after["plain"][reason] == before["plain"].get(reason, 0) + 1
    np.testing.assert_allclose(y, _token_loop(*args), atol=2e-5)
    starts = sscan.selective_scan_states(*args[:5], chunk=32)
    assert starts.shape == (3, 2, 256, 16) and not starts[0].any()


# -- the config ----------------------------------------------------------------

STACK = dict(hidden_size=64, num_heads=4, num_kv_heads=2, rope_theta=None,
             mb_per_layer=2, sliding_window=16, ssm_state_size=16,
             attention_kind="differential")


@pytest.mark.parametrize("layers,counts", [(32, (9, 8, 1, 7, 7)),
                                           (8, (3, 2, 1, 1, 1))])
def test_placement_counts(layers, counts):
    cfg = LlamaConfig(num_layers=layers, **STACK)
    kinds = [(cfg.mixer_of(i), cfg.window_of(i) is not None)
             for i in range(layers)]
    assert (sum(k == llama.SCAN for k, _ in kinds),
            sum(k == llama.SELF_ATTENTION and w for k, w in kinds),
            sum(k == llama.SELF_ATTENTION and not w for k, w in kinds),
            sum(k == llama.MEMORY_GATE for k, _ in kinds),
            sum(k == llama.CROSS_ATTENTION for k, _ in kinds)) == counts
    # The reference's own rule places the same kinds.
    names = {llama.SCAN: "mamba", llama.MEMORY_GATE: "gmu",
             llama.CROSS_ATTENTION: "cross"}
    assert [reference.placement(i, layers) for i in range(layers)] == [
        names.get(k, "window" if w else "full") for k, w in kinds]
    assert kinds[layers // 2][0] == llama.SCAN
    assert kinds[layers // 2 + 1] == (llama.SELF_ATTENTION, False)
    assert LlamaConfig().mixer_of(0) is None


@pytest.mark.parametrize("changes,says", [
    (dict(num_layers=6), "num_layers % 4"),
    (dict(mb_per_layer=3), "mb_per_layer is 2"),
    (dict(sliding_window=None), "sliding_window"),
    (dict(ssm_state_size=0), "ssm_state_size"),
    (dict(rope_theta=10000.0), "it pairs adjacent heads"),
    (dict(num_heads=3), "it pairs adjacent heads"),
    (dict(attention_kind="twice"), "attention_kind"),
    (dict(mb_per_layer=0, sliding_window=None), "differential attention is"),
])
def test_config_refuses(changes, says):
    with pytest.raises(ValueError, match=says):
        LlamaConfig(**{**STACK, "num_layers": 8, **changes})


@pytest.mark.parametrize("changes,says", [
    (dict(), "decoder-hybrid-decoder stack"),
    (dict(), "with differential attention a decode step would attend twice"),
    (dict(mb_per_layer=0, sliding_window=None, attention_kind="full",
          rope_theta=1e4, layer_norm_eps=1e-5), "LayerNorm"),
    (dict(mb_per_layer=0, sliding_window=None, attention_kind="full",
          rope_theta=1e4, tie_word_embeddings=True), "tied to the embedding"),
])
def test_the_paths_with_a_layer_of_their_own_refuse_by_name(changes, says):
    config = LlamaConfig(**{**STACK, "num_layers": 8, **changes})
    with pytest.raises(NotImplementedError, match=says):
        config.refuse_new_kinds("generation")


def test_the_partitioned_step_gets_the_jnp_bodies(tiny):
    """``parallel/api.py``'s GSPMD step hands the model no ``attention_fn``
    that reads in place: every scan and every filter of its trace is the
    ``jnp`` body, by the one rule."""
    import optax

    from horovod_tpu.ops import short_conv
    from horovod_tpu.parallel.api import make_parallel_train_step
    from horovod_tpu.parallel.mesh import build_mesh

    job, _, params, batch, _, _ = tiny
    model = LlamaModel(dataclasses.replace(job.llama, num_layers=4))
    tokens = batch[:, :33]
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)
    optimizer = optax.sgd(1e-2)
    scans, filters = sscan.body_counts(), short_conv.body_counts()
    step = make_parallel_train_step(
        model, optimizer, build_mesh({"data": 1}, devices=jax.devices()[:1]),
        donate=False)
    # Traced and lowered, not compiled: the bodies are chosen in the trace.
    step.lower(params, jax.eval_shape(optimizer.init, params), tokens)
    after = sscan.body_counts()
    assert after["mosaic"] == scans["mosaic"]
    assert after["plain"][sscan.NOT_IN_PLACE] > scans["plain"].get(
        sscan.NOT_IN_PLACE, 0)
    assert short_conv.body_counts()["fused"] == filters["fused"]
