"""The three scopes that name the layer stack's ordinary work
(``hvd.block.attn``, ``hvd.block.ffn``, ``hvd.head``;
``horovod_tpu/common/scopes.py``): in the train step of every kind of
model the stack runs, each matrix product of the loss lies under exactly
one of them, no operation under two, and the older scopes nest where the
table says.  Tiny sizes, compiled for the CPU; the names are read from the
compiled step's ``op_name``s, as a trace shows them."""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

import horovod_tpu.jax as hvd
from benchmark import manifest
from benchmark.scopes import bare, components
from horovod_tpu.common import scopes
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.losses import softmax_cross_entropy
from tiny_sizes import TINY          # tests/conftest.py put it on the path

BLOCKS = (scopes.BLOCK_ATTN, scopes.BLOCK_FFN, scopes.HEAD)

# kind -> (the cell whose job builds it, or None; what else must nest where:
# inner scope -> the scope it lies under)
KINDS = {
    "dense": ("ouro-2.6b.train-s2k", {}),
    "looped": ("ouro-2.6b-ut4.train-s8k", {
        scopes.BLOCK_ATTN: scopes.LOOP_PASS,
        scopes.BLOCK_FFN: scopes.LOOP_PASS,
        scopes.HEAD: scopes.LOOP_EXIT,
        scopes.REMATTED: scopes.LOOP_PASS}),
    "latent_routed": ("deepseek-v2-lite.train-s4k", {
        scopes.MLA_LATENT: scopes.BLOCK_ATTN,
        scopes.MOE_ROUTE: scopes.BLOCK_FFN,
        scopes.MOE_EXPERTS: scopes.BLOCK_FFN,
        scopes.MOE_COMBINE: scopes.BLOCK_FFN,
        scopes.MOE_SHARED: scopes.BLOCK_FFN,
        scopes.REMATTED: scopes.LOSS}),
    "sparse_routed": ("keye-vl-2.0-30b-a3b.train-s8k-b2", {
        scopes.SPARSE_INDEX: scopes.BLOCK_ATTN,
        scopes.SPARSE_SELECT: scopes.BLOCK_ATTN,
        scopes.MOE_ROUTE: scopes.BLOCK_FFN,
        scopes.MOE_EXPERTS: scopes.BLOCK_FFN,
        scopes.MOE_COMBINE: scopes.BLOCK_FFN,
        scopes.REMATTED: scopes.LOSS}),
    "dense_remat": (None, {scopes.REMATTED: scopes.LOSS}),
}
EVERYWHERE = {scopes.FLASH_FWD: scopes.BLOCK_ATTN,
              scopes.FLASH_BWD: scopes.BLOCK_ATTN}


def _job(workload):
    """The cell's own job at the tests' tiny sizes: its ``loss_fn`` and
    optimizer are what the cell hands ``make_train_step``."""
    cell = manifest.cell(workload)
    tiny = TINY.get(cell["config"]["job"], TINY["decoder_lm"])
    job = manifest.load_job(cell["config"]["job"]).build(
        {**cell["config"], **tiny["config"]},
        {**cell["traffic"], **tiny["traffic"]}, 1)
    state = jax.eval_shape(job.init_state, jax.random.key(0))
    batch = jax.eval_shape(job.make_batch, jax.random.key(1))
    return job.loss_fn, job.optimizer, state, batch


def _dense_remat(**changes):
    cfg = dataclasses.replace(LlamaConfig.tiny(),
                              **{"remat": "layer", **changes})
    model = LlamaModel(cfg, attention_fn=flash_attention_fn)

    def loss_fn(params, batch):
        return softmax_cross_entropy(model.apply(params, batch[:, :-1]),
                                     batch[:, 1:])

    optimizer = optax.adamw(1e-3)
    params = jax.eval_shape(
        lambda: LlamaModel(cfg).init(jax.random.key(0),
                                     jnp.zeros((1, 8), jnp.int32)))
    state = (params, jax.eval_shape(optimizer.init, params))
    return loss_fn, optimizer, state, jax.ShapeDtypeStruct((2, 129),
                                                           jnp.int32)


def _op_names(kind, **changes):
    """Every ``op_name`` of the compiled tiny step, as ``(components,
    their bare names)``.  (A reduction's scalar body keeps a relative
    name: no ``jit(...)/``.)"""
    workload, _ = KINDS[kind]
    loss_fn, optimizer, state, batch = (_job(workload) if workload
                                        else _dense_remat(**changes))
    mesh = hvd.build_mesh({"data": 1}, devices=jax.devices()[:1])
    step = hvd.make_train_step(loss_fn, optimizer, mesh)
    assert type(step) is type(jax.jit(lambda: None))
    text = step.lower(*state, batch).compile().as_text()
    # (XLA joins the names of what it merged into one instruction with ";".)
    names = {name for joined in re.findall(r'op_name="(jit\([^"]*)"', text)
             for name in joined.split(";")}
    assert names
    return [(tuple(components(n)), tuple(bare(c) for c in components(n)))
            for n in sorted(names)]


@pytest.mark.parametrize("kind", list(KINDS))
def test_every_product_of_the_loss_is_in_exactly_one_block(kind):
    paths = _op_names(kind)
    in_loss = [(path, held) for path, held in paths if scopes.LOSS in held]
    products = [held for path, held in in_loss
                if path[-1] == "dot_general"]
    assert len(products) >= 12, products
    for held in products:
        assert sum(held.count(block) for block in BLOCKS) == 1, held
    # No operation under two blocks, and none under one block twice.
    for path, held in paths:
        assert sum(held.count(block) for block in BLOCKS) <= 1, path
    # Each block holds work of the forward and of the backward pass.
    for block in BLOCKS:
        mine = [path for path, held in in_loss if block in held]
        assert any("transpose(" in "/".join(path) for path in mine), block
        assert any("transpose(" not in "/".join(path) for path in mine), block
    # What is in no block is the embedding, the rotary tables, a looped
    # model's exit distribution and scan, the balance losses: no product.
    for path, held in in_loss:
        if not any(block in held for block in BLOCKS):
            assert path[-1] != "dot_general", path

    # The older scopes still appear, nested where the table says.
    everywhere = {scopes.LOSS, scopes.OPTIMIZER, scopes.APPLY}
    seen = {name for _, held in paths for name in held}
    assert everywhere <= seen, everywhere - seen
    for inner, outer in {**EVERYWHERE, **KINDS[kind][1]}.items():
        # (Under the loss: JAX names a few operations that a scan's
        # partial evaluation moved by the scan body alone.)
        under = [held for _, held in in_loss if inner in held]
        assert under, f"{inner} appears nowhere in the {kind} step"
        for held in under:
            assert outer in held[:held.index(inner)], (inner, outer, held)


@pytest.mark.parametrize("remat", ["none", "layer"])
def test_the_rotation_nests_in_the_attention_block_and_in_no_flash_scope(
        remat):
    """Heads of 128 are rotated by ``ops/rope.py``'s pass (the tiny sizes
    above have narrower heads and keep the ``jnp`` form): its operations
    carry ``hvd.rope`` inside ``hvd.block.attn``, forward, recomputed and
    backward, never inside ``hvd.flash.*``, and the flash calls never
    inside it."""
    paths = _op_names("dense_remat", attention_head_dim=128, remat=remat)
    rotation = [held for _, held in paths if scopes.ROPE in held]
    assert rotation
    for held in rotation:
        assert scopes.BLOCK_ATTN in held[:held.index(scopes.ROPE)], held
        assert scopes.LOSS in held[:held.index(scopes.BLOCK_ATTN)], held
        assert not {scopes.FLASH_FWD, scopes.FLASH_BWD} & set(held), held
        assert held.count(scopes.ROPE) == 1, held
    joined = ["/".join(path) for path, held in paths if scopes.ROPE in held]
    assert any("transpose(" in name for name in joined)
    assert any("transpose(" not in name for name in joined)
    assert any(scopes.REMATTED in name for name in joined) == (
        remat == "layer")
    # With the pass in place the block holds no strided slice's gather and
    # no scatter-add of its transpose.
    for path, held in paths:
        if scopes.BLOCK_ATTN in held:
            assert path[-1] not in ("gather", "scatter-add", "scatter_add"), (
                path)


def test_the_table_its_constants_and_all_agree():
    constants = {name: value for name, value in vars(scopes).items()
                 if name.isupper() and isinstance(value, str)}
    assert set(constants) | {
        "allreduce_scope", "scope", "span", "stamp", "layer_span", "rules",
        "RULES"} == set(scopes.__all__)
    assert len(set(constants.values())) == len(constants)
    # The docstring's table: a row starts with the name in double
    # backquotes; ``hvd.allreduce.<a>`` is the prefix's row.
    table = scopes.__doc__.split("=" * 20 + "  " + "=" * 52)[2]
    rows = {re.sub(r"\.<\w+>$", "", name)
            for name in re.findall(r"^``([\w.<>]+)``", table, re.M)}
    # ``INIT*`` name spans of the compile log alone (``hvd.init`` and its
    # parts), as ``MOSAIC_*`` and ``IMPORT*`` do: no scope, no row.
    named = {value for name, value in constants.items()
             if value.startswith("hvd.") and not name.endswith("_NAME")
             and not name.startswith("INIT")}
    assert rows == named, rows ^ named
    assert {scopes.BLOCK_ATTN, scopes.BLOCK_FFN, scopes.HEAD,
            scopes.ROPE} <= rows
    assert (scopes.BLOCK_ATTN, scopes.BLOCK_FFN, scopes.HEAD, scopes.ROPE) == (
        "hvd.block.attn", "hvd.block.ffn", "hvd.head", "hvd.rope")
    assert "ROPE" in scopes.__all__
    assert not scopes.ROPE.startswith(scopes.FLASH_FWD.rsplit(".", 1)[0])


def test_the_three_names_are_spelled_in_the_table_alone():
    """As a string of the code (documents and comments may say them): in no
    file of the program or of the benchmark but the table."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    table = os.path.join(repo, "horovod_tpu", "common", "scopes.py")
    spelled = re.compile("|".join(
        rf'["\'][^"\'\n]*{re.escape(name)}\b'
        for name in BLOCKS + (scopes.ROPE,)))
    entered = []
    for top in ("horovod_tpu", "benchmark"):
        for folder, _, files in os.walk(os.path.join(repo, top)):
            for name in files:
                path = os.path.join(folder, name)
                if not name.endswith(".py") or path == table:
                    continue
                with open(path) as f:
                    text = f.read()
                code = re.sub(r'""".*?"""|#[^\n]*', "", text, flags=re.S)
                assert not spelled.search(code), path
                entered += re.findall(
                    r"_?scopes\.scope\(\s*_?scopes\.(\w+)", text)
    assert {"BLOCK_ATTN", "BLOCK_FFN", "HEAD", "ROPE"} <= set(entered)
