"""Learned sparse attention over grouped-query heads and the routed layer
of the ``keye-vl-2.0-30b-a3b`` configuration, at a small size on the CPU
(interpret-mode kernels): the selection against ``jax.lax.top_k`` and its
tie rule, the flash calls over a selection against dense ``jnp.where``
attention, the indexer's loss against autodiff, and the whole program
against ``benchmark/reference/keye_vl2.py`` -- in float32 to rounding, in
bf16 inside stated limits -- with the stop-gradients, the shares of the
experts and the refusals by name."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.reference import keye_vl2
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.models.llama import (RoutedExperts, causal_attention)
from horovod_tpu.ops import sparse_index
from horovod_tpu.ops.flash_attention import (flash_attention,
                                             flash_attention_fn,
                                             flash_attention_selected)
from horovod_tpu.ops.losses import (batch_balance_loss,
                                    sequence_balance_loss)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))
from tiny_sizes import TINY  # noqa: E402

CELL = "keye-vl-2.0-30b-a3b.train-s8k-b2"
HIGHEST = jax.default_matmul_precision("highest")


def _indexer(seed, batch, seq, heads=4, dim=64, dtype=jnp.float32):
    k_q, k_k, k_w = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k_q, (batch, seq, heads, dim), dtype),
            jax.random.normal(k_k, (batch, seq, dim), dtype),
            jax.random.normal(k_w, (batch, seq, heads), jnp.float32))


def _qkv(seed, batch, seq, heads, kv_heads, dim, dtype=jnp.float32):
    k_q, k_k, k_v = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k_q, (batch, seq, heads, dim), dtype),
            jax.random.normal(k_k, (batch, seq, kv_heads, dim), dtype),
            jax.random.normal(k_v, (batch, seq, kv_heads, dim), dtype))


def _dense_selected(q, k, v, selected):
    """Attention with whole ``[B, H, S, S]`` scores and ``jnp.where``."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(selected[:, None] != 0, scores, -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v),
            jax.nn.logsumexp(scores, -1))


# -- the selection -------------------------------------------------------------

@pytest.mark.parametrize("seq, topk", [(256, 64), (512, 128), (384, 512)])
def test_selection_kernel_is_top_k(seq, topk):
    q_i, k_i, w = _indexer(0, 2, seq)
    with HIGHEST:
        want = sparse_index.select_keys(q_i, k_i, w, topk, scale=0.1,
                                        dense=True)
        got = sparse_index.select_keys(q_i, k_i, w, topk, scale=0.1,
                                       dense=False)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_allclose(got[1], want[1], atol=2e-6)
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_array_equal(
        got[2][0], np.minimum(np.arange(seq) + 1, topk))
    assert got[0].dtype == jnp.int8
    # Nothing above the diagonal, and while t < topk every causal key.
    np.testing.assert_array_equal(np.triu(np.asarray(got[0][0]), 1), 0)
    early = min(topk, seq)
    np.testing.assert_array_equal(
        np.asarray(got[0][0])[:early, :early], np.tri(early, dtype=np.int8))


def test_ties_go_to_the_lower_position():
    """All-zero weights make every score 0.0 (some -0.0): ``top_k`` takes
    the lowest positions.  Keys repeated in groups of four tie in fours at
    every level, the threshold's among them."""
    seq, topk = 512, 98          # no multiple of four: a group is cut
    q_i, k_i, w = _indexer(1, 1, seq)
    with HIGHEST:
        selected, _, taken = sparse_index.select_keys(
            q_i, k_i, jnp.where(w > 0, 0.0, -0.0), topk, scale=0.1,
            dense=False)
        np.testing.assert_array_equal(
            np.asarray(selected[0, topk:, :topk]), 1)
        assert int(jnp.max(taken)) == topk
        assert int(jnp.sum(selected[0, topk:, topk:])) == 0
        repeated = jnp.repeat(k_i[:, ::4], 4, axis=1)
        want = sparse_index.select_keys(q_i, repeated, w, topk, scale=0.1,
                                        dense=True)
        got = sparse_index.select_keys(q_i, repeated, w, topk, scale=0.1,
                                       dense=False)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[2], want[2])
    # The tie rule did some work: a row's last group of four is cut.
    groups = np.asarray(got[0][0, -1]).reshape(-1, 4).sum(axis=1)
    assert set(np.unique(groups)) - {0, 4}


def test_reference_selects_as_the_program_does():
    """The reference reads its selection off ``top_k``'s values and
    positions without scattering them; with ties in fours it is ``top_k``'s
    own set (the dense path marks every returned position) and the
    kernel's."""
    q_i, k_i, w = _indexer(2, 2, 256)
    k_i = jnp.repeat(k_i[:, ::4], 4, axis=1)
    with HIGHEST:
        scores = sparse_index.index_scores(q_i, k_i, w)
        want = keye_vl2.selected_keys(scores[:, 128:], 128, 62)
        dense = sparse_index.select_keys(q_i, k_i, w, 62, scale=0.1,
                                         dense=True)[0]
        got = sparse_index.select_keys(q_i, k_i, w, 62, scale=0.1)[0]
    np.testing.assert_array_equal(dense[:, 128:] != 0, want)
    np.testing.assert_array_equal(got[:, 128:] != 0, want)
    assert int(jnp.min(jnp.sum(want, axis=-1))) == 62 == int(
        jnp.max(jnp.sum(want, axis=-1)))


# -- the flash calls over a selection ------------------------------------------

@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 2e-5),
                                          (jnp.bfloat16, 3e-2)])
def test_selected_flash_matches_dense_where_with_four_kv_heads(dtype, limit):
    """8 query heads over 4 key-value heads, two 512-blocks a side, 128 of
    a query's keys kept: forward, the row statistics, and all three
    gradients (dk and dv add up over a group's two heads)."""
    seq = 1024
    q, k, v = _qkv(3, 2, seq, 8, 4, 64, dtype)
    selected = sparse_index.select_keys(*_indexer(4, 2, seq), 128,
                                        scale=0.1)[0]
    weight = jax.random.normal(jax.random.key(5), q.shape, jnp.float32)

    def grads(attend):
        def loss(q, k, v):
            out = attend(q, k, v, selected)[0]
            return jnp.sum(out.astype(jnp.float32) * weight)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    with HIGHEST:
        out, lse = flash_attention_selected(q, k, v, selected)
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        want_out, want_lse = _dense_selected(*f32, selected)
        got = grads(flash_attention_selected)
        want = jax.grad(lambda q, k, v: jnp.sum(
            _dense_selected(q, k, v, selected)[0] * weight),
            argnums=(0, 1, 2))(*f32)
    np.testing.assert_allclose(out.astype(jnp.float32), want_out, atol=limit)
    np.testing.assert_allclose(lse, want_lse, atol=limit)
    for g, r, which in zip(got, want, "qkv"):
        assert g.dtype == dtype and g.shape == r.shape
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g.astype(jnp.float32), r,
                                   atol=limit * scale * 4,
                                   err_msg=f"d{which}")


def test_the_dense_seam_gives_what_the_kernel_gives():
    q, k, v = _qkv(6, 1, 256, 4, 2, 64)
    selected = sparse_index.select_keys(*_indexer(7, 1, 256), 32,
                                        scale=0.1)[0]
    with HIGHEST:
        dense_out, dense_lse = causal_attention(q, k, v, selected=selected)
        out, lse = flash_attention_fn(q, k, v, selected=selected)
    np.testing.assert_allclose(out, dense_out, atol=2e-5)
    np.testing.assert_allclose(lse, dense_lse, atol=2e-5)


def test_grouped_query_heads_are_not_repeated():
    """K and V reach the calls ``[B * Hkv, S, D]``: the index maps send a
    query head to its group's block."""
    from horovod_tpu.ops import flash_attention as fa

    q, k, v = _qkv(8, 2, 256, 8, 2, 64)
    qt, kt, vt = fa._flat_layout(q, k, v)
    assert qt.shape == (16, 256, 64) and kt.shape == vt.shape == (4, 256, 64)
    text = jax.jit(flash_attention).lower(q, k, v).as_text()
    assert "256x8x64" not in text.replace("2x256x8x64", "")   # q alone
    with HIGHEST:
        np.testing.assert_allclose(
            flash_attention(q, k, v), causal_attention(q, k, v), atol=2e-5)


# -- the indexer's loss --------------------------------------------------------

def test_index_loss_kernel_matches_autodiff():
    seq, topk = 512, 128
    q, k, _ = _qkv(9, 2, seq, 8, 2, 64)
    q_i, k_i, w = _indexer(10, 2, seq)
    scale = (4 * 64) ** -0.5
    with HIGHEST:
        selected, lse_i, _ = sparse_index.select_keys(q_i, k_i, w, topk,
                                                      scale=scale)
        lse = flash_attention_selected(q, k, k, selected)[1]

        def both(dense):
            return jax.value_and_grad(
                lambda q_i, k_i, w: sparse_index.index_loss(
                    q, k, lse, q_i, k_i, w, selected, lse_i,
                    sm_scale=64 ** -0.5, scale=scale, dense=dense),
                argnums=(0, 1, 2))(q_i, k_i, w)

        (want_loss, want), (loss, got) = both(True), both(False)
    assert 0.0 < float(want_loss)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=2e-6 * float(jnp.max(
            jnp.abs(r))) + 1e-9)
    # Neither the attention's q, k nor its lse gets a gradient through it.
    silent = jax.grad(lambda q, k, lse: sparse_index.index_loss(
        q, k, lse, q_i, k_i, w, selected, lse_i, sm_scale=0.125,
        scale=scale), argnums=(0, 1, 2))(q, k, lse)
    assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in silent)


# -- the model against the plain reference -------------------------------------

def _tiny(dtype=jnp.float32, **config_changes):
    cell = manifest.cell(CELL)
    tiny = TINY[cell["config"]["job"]]
    config = {**cell["config"], **tiny["config"], **config_changes}
    traffic = {**cell["traffic"], **tiny["traffic"]}
    job = manifest.load_job(config["job"]).build(config, traffic, 1)
    job.llama = dataclasses.replace(job.llama, dtype=dtype,
                                    logits_dtype=dtype)
    job.model = LlamaModel(job.llama, attention_fn=flash_attention_fn)
    params = LlamaModel(job.llama).init(jax.random.key(3),
                                        jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    return job, config, params, job.make_batch(jax.random.key(4), 1)


def _programs(job, config):
    """The job's (loss, gradients) and the reference's: a caller that asks
    about two sets of parameters compiles each once."""
    return (jax.jit(jax.value_and_grad(job.loss_fn)),
            jax.jit(lambda p, b: keye_vl2.loss_and_grads(p, b, config)))


def _distances(job, config, params, batch, programs=None):
    program, reference = programs or _programs(job, config)
    with HIGHEST:
        loss, grads = program(params, batch)
        want_loss, want = reference(job.to_reference(params), batch)
    off = jax.tree.map(
        lambda g, r: float(jnp.linalg.norm(g.astype(jnp.float32) - r)
                           / (jnp.linalg.norm(r) + 1e-30)),
        job.to_reference(grads), want)
    whole = float(jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32) - r)) for g, r in zip(
            jax.tree.leaves(job.to_reference(grads)), jax.tree.leaves(want)))
        / sum(jnp.sum(jnp.square(r)) for r in jax.tree.leaves(want))))
    return float(loss) - float(want_loss), whole, off


def test_program_is_the_reference_in_float32():
    job, config, params, batch = _tiny()
    loss_off, whole, by_leaf = _distances(job, config, params, batch)
    assert abs(loss_off) < 2e-5
    worst = max(jax.tree.leaves(by_leaf))
    assert whole < 1e-5 and worst < 2e-5, by_leaf
    with HIGHEST:
        taken, selected, rows, dropped = jax.jit(job.counters)(params, batch)
        wanted = jnp.stack(keye_vl2.selection(job.to_reference(params),
                                              batch, config))
    np.testing.assert_array_equal(selected != 0, wanted)
    topk = config["sa_config"]["topk"]
    assert int(jnp.max(taken)) == topk
    np.testing.assert_array_equal(
        taken[0, 0], np.minimum(np.arange(job.seq) + 1, topk))
    assert rows.shape == (2, 4) and int(jnp.sum(dropped)) == 0


def test_program_is_near_the_reference_in_bfloat16():
    """A random indexer's selection flips keys near the threshold in bf16
    and top-64 of 256 is coarse, so the gradients are up to a third apart
    at this size; with the selection pinned (an indexer of zeros takes the
    64 lowest positions in both) bf16 alone is left: under a tenth."""
    job, config, params, batch = _tiny(jnp.bfloat16)
    programs = _programs(job, config)
    loss_off, whole, _ = _distances(job, config, params, batch, programs)
    assert abs(loss_off) < 0.03 and whole < 0.35, (loss_off, whole)

    def zero_the_indexer(path, leaf):
        return leaf * 0 if any("index_w" in str(key) for key in path) else leaf

    pinned = jax.tree_util.tree_map_with_path(zero_the_indexer, params)
    loss_off, whole, _ = _distances(job, config, pinned, batch, programs)
    assert abs(loss_off) < 0.02 and whole < 0.1, (loss_off, whole)


def test_the_qk_norm_in_the_pass_is_the_modules_in_the_whole_program(
        dtype=jnp.float32, limit=1e-4):
    """At heads of 128 the flash seam norms and turns q and k in ONE Mosaic
    call each (``ops/rope.py::norm_rotate_pairs``, interpreted here); the
    model's own dense seam keeps the ``RMSNorm`` arithmetic and the jnp
    rotation.  The same parameters (``q_norm/scale``, ``k_norm/scale``
    ``[128]``, away from their start), the same loss and every gradient, the
    two scales' among them, in float32 to the kernels' own distance from
    dense attention (bf16 is ``tests/test_rope.py``'s to hold, a unit at a
    time).  One layer: the second would only run the same calls again."""
    from horovod_tpu.common import trace_counts
    from horovod_tpu.ops import rope

    job, config, params, batch = _tiny(dtype, head_dim=128,
                                       num_hidden_layers=1)
    attn = params["params"]["layer_0"]["attn"]
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (
        128,)
    assert attn["wq"]["kernel"].shape == (128, 4 * 128)

    def spread(path, leaf):
        name = jax.tree_util.keystr(path)
        if "q_norm" in name or "k_norm" in name:
            return leaf * (1 + 0.3 * jax.random.normal(
                jax.random.key(len(name)), leaf.shape)).astype(leaf.dtype)
        return leaf

    params = jax.tree_util.tree_map_with_path(spread, params)
    normed = trace_counts.counts(rope.BODY).get(rope.NORMED, 0)
    with HIGHEST:
        loss, grads = jax.jit(jax.value_and_grad(job.loss_fn))(params, batch)
        assert trace_counts.counts(rope.BODY)[rope.NORMED] > normed
        normed = trace_counts.counts(rope.BODY)[rope.NORMED]
        job.model = LlamaModel(job.llama)       # the dense seam: jnp bodies
        want_loss, want = jax.jit(jax.value_and_grad(job.loss_fn))(params,
                                                                   batch)
        assert trace_counts.counts(rope.BODY)[rope.NORMED] == normed
    assert abs(float(loss) - float(want_loss)) < limit
    found = jax.tree_util.tree_flatten_with_path(grads)[0]
    names = [jax.tree_util.keystr(path) for path, _ in found]
    assert sum("q_norm" in n or "k_norm" in n for n in names) == 2
    for (path, leaf), wanted in zip(found, jax.tree.leaves(want)):
        off = float(jnp.linalg.norm((leaf - wanted).astype(jnp.float32))
                    / (jnp.linalg.norm(wanted.astype(jnp.float32)) + 1e-30))
        assert off < limit, (jax.tree_util.keystr(path), off)


def test_the_stop_gradients_hold():
    """Under cross-entropy and the balance loss alone the indexer's three
    matrices get exactly nothing; under the indexer's loss alone every
    other leaf gets exactly nothing."""
    job, config, params, batch = _tiny()

    def norms(index_lambda, others):
        job.index_lambda = index_lambda
        job.alpha = others * 0.001
        loss_fn = job.loss_fn
        if not others:
            def loss_fn(params, batch):
                _, sown = job.model.apply(params, batch[:, :-1],
                                          mutable=["index_losses"])
                from horovod_tpu.ops.losses import indexer_loss
                return indexer_loss(sown)
        grads = jax.jit(jax.grad(loss_fn))(params, batch)
        flat = jax.tree_util.tree_flatten_with_path(grads)[0]
        return {jax.tree_util.keystr(path): float(jnp.max(jnp.abs(g)))
                for path, g in flat}

    without = norms(0.0, 1)
    alone = norms(1.0, 0)
    for name in without:
        if "index_w" in name:
            assert without[name] == 0.0 and alone[name] > 0.0, name
        else:
            assert without[name] > 0.0 and alone[name] == 0.0, name


def test_short_rows_are_plain_grouped_query_attention():
    """S <= topk: every causal key is in, so the layer is ``attention_kind
    = "full"`` with the same weights, to the last digit."""
    job, config, params, _ = _tiny(
        jnp.float32, sa_config={"indexer_head_dim": 64,
                                "indexer_num_heads": 2,
                                "indexer_num_kv_heads": 1, "topk": 256})
    tokens = jax.random.randint(jax.random.key(11), (2, 256), 0, 512)
    plain = LlamaModel(dataclasses.replace(job.llama, attention_kind="full"),
                       attention_fn=flash_attention_fn)
    without = jax.tree.map(lambda leaf: leaf, params)      # a copy to cut
    for layer in without["params"].values():
        for name in [n for n in layer.get("attn", {}) if "index_w" in n]:
            del layer["attn"][name]
    with HIGHEST:
        got = job.model.apply(params, tokens)
        want = plain.apply(without, tokens)
    np.testing.assert_array_equal(got, want)


# -- the routed layer: shares, gates, balance ----------------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """16 experts in 8 shares of 2, gates renormalised over a token's 3
    choices wherever they live, no shared expert: the shares' sum is the
    reference layer given all 16."""
    cfg = LlamaConfig(hidden_size=64, num_experts=16, experts_per_token=3,
                      held_experts=2, moe_intermediate_size=32,
                      intermediate_size=32, shared_experts=0,
                      norm_topk_prob=True, balance_over="batch",
                      dtype=jnp.float32)
    whole = dataclasses.replace(cfg, held_experts=0)
    x = jax.random.normal(jax.random.key(12), (2, 64, 64))
    params = RoutedExperts(whole).init(jax.random.key(13), x)
    p = params["params"]
    config = {"num_experts_per_tok": 3, "norm_topk_prob": True,
              "deployment": {"first_held_expert": 0}}
    layer = {"router": p["router"]["kernel"],
             "experts": {"w_gate": p["w_gate_up"][..., :32],
                         "w_up": p["w_gate_up"][..., 32:],
                         "w_down": p["w_down"]}}
    with HIGHEST:
        want, want_balance = keye_vl2.routed_experts(x, layer, config)
        total = 0.0
        for share in range(8):
            held = {"params": {"router": p["router"],
                               "w_gate_up": p["w_gate_up"][2 * share:][:2],
                               "w_down": p["w_down"][2 * share:][:2]}}
            y, sown = RoutedExperts(dataclasses.replace(
                cfg, first_held_expert=2 * share)).apply(
                    held, x, mutable=["losses", "moe_stats"])
            total = total + y
            np.testing.assert_allclose(sown["losses"]["balance"][0],
                                       want_balance, rtol=1e-5)
            assert int(sown["moe_stats"]["rows_dropped"][0]) == 0
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert "shared" not in p


def test_batch_balance_loss_against_a_hand_count():
    scores = jnp.array([[[0.7, 0.2, 0.1, 0.0], [0.1, 0.6, 0.2, 0.1]],
                        [[0.4, 0.3, 0.2, 0.1], [0.25, 0.25, 0.25, 0.25]]])
    chosen = jnp.array([[[0, 1], [1, 2]], [[0, 1], [0, 1]]])
    share = np.array([3, 4, 1, 0]) / 8
    mean = np.asarray(scores).reshape(4, 4).mean(axis=0)
    assert float(batch_balance_loss(scores, chosen)) == pytest.approx(
        4 * float(np.sum(share * mean)), rel=1e-6)
    assert float(batch_balance_loss(scores, chosen)) != pytest.approx(
        float(sequence_balance_loss(scores, chosen)), rel=1e-3)


# -- configuration and refusals -------------------------------------------------

def test_config_validates_the_new_kind():
    with pytest.raises(ValueError, match="index_heads"):
        LlamaConfig(attention_kind="sparse")
    with pytest.raises(ValueError, match="balance_over"):
        LlamaConfig(balance_over="layer")
    with pytest.raises(ValueError, match="'full', 'latent' or 'sparse'"):
        LlamaConfig(attention_kind="windowed")
    assert LlamaConfig(hidden_size=2048, num_heads=32,
                       attention_head_dim=128).head_dim == 128
    assert LlamaConfig(remat="layer_keep_selection").remat


@pytest.mark.parametrize("who", ["generation", "serve", "pipeline"])
def test_the_other_paths_refuse_sparse_attention_by_name(who):
    from horovod_tpu.models.generation import prefill
    from horovod_tpu.parallel.pipeline import init_pipelined_llama

    cfg = dataclasses.replace(_tiny()[0].llama, num_experts=1,
                              held_experts=0, first_held_expert=0)
    with pytest.raises(NotImplementedError,
                       match="learned sparse attention") as refusal:
        if who == "generation":
            prefill(cfg, {}, jnp.zeros((1, 4), jnp.int32), cache_len=8)
        elif who == "serve":
            cfg.refuse_new_kinds("the paged KV cache")
        else:
            init_pipelined_llama(cfg, jax.random.key(0), 1)
    assert "attention_kind='sparse'" in str(refusal.value)
