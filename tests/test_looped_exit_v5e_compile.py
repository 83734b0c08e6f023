"""The looped cell's train step compiled for a described ``v5e:2x2`` (no chip
attached), at the cell's row length and vocabulary and a reduced depth:
each exit's head product runs once, its two gradient products beside it
in the same loop body, nothing of an exit under ``rematted_computation``,
and the step's temporaries no larger than with the checkpointed walk this
replaced (PERF.md §6, PR 31).  The TPU compiler is loaded inside a fixture
(the on-chip-measurement guide says why); the recipe is
``tests/test_flash_v5e_compile.py``'s."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.common import scopes

CELL = "ouro-2.6b-ut4.train-s8k"
LAYERS = 2
#: ``temp_size_in_bytes`` of this step (``LAYERS`` layers, the cell's other
#: sizes) with each exit under ``jax.checkpoint`` and its head made again
#: in the backward pass: the parent of PR 31, e691569, compiled with this
#: installation.  The change: 3,170,807,296.  (All 9 layers: 6,733,157,888,
#: the ledger's ``hbm_temporaries_gb`` of PR 30 to the digit, → 6,599,521,792.)
CHECKPOINTED_TEMPORARIES = 3_302_983_168

_PRODUCT = re.compile(r" = \w+\[([0-9,]*)\].* (?:convolution|dot)\(.*"
                      r'op_name="([^"]*/lm_head/dot_general)"')


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as error:
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")


@pytest.fixture(scope="module")
def looped_step(topo):
    """The compiled step.  Kernels take their non-interpreted path, and
    nothing is read from or written to a persistent cache (a deviceless
    executable cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    import horovod_tpu.jax as hvd
    from benchmark import manifest
    from horovod_tpu.ops import flash_attention

    patch = pytest.MonkeyPatch()
    patch.setattr(flash_attention, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    cell = manifest.cell(CELL)
    config = {**cell["config"], "num_hidden_layers": LAYERS,
              "layer_types": cell["config"]["layer_types"][:LAYERS]}
    job = manifest.load_job(config["job"]).build(config, cell["traffic"], 1)
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))

    def make(seed):
        k_state, k_batch = jax.random.split(jax.random.key(seed))
        return job.init_state(k_state), job.make_batch(k_batch)

    def placed(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    state, batch = jax.eval_shape(make, jnp.uint32(0))
    step = hvd.make_train_step(job.loss_fn, job.optimizer, mesh,
                               has_aux=job.has_aux)
    compiled = step.lower(*placed(state, P()),
                          placed(batch, P("data"))).compile()
    yield compiled, config, cell["traffic"]
    patch.undo()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_an_exits_head_runs_once_and_its_gradients_beside_it(looped_step):
    compiled, config, traffic = looped_step
    rows = traffic["batch_per_chip"] * traffic["sequence"]
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    products = [(tuple(int(d) for d in dims.split(",")), op_name)
                for dims, op_name in _PRODUCT.findall(compiled.as_text())]
    assert all(scopes.LOSS in n and scopes.LOOP_EXIT in n
               and "/while/body/" in n for _, n in products), products
    forward = [s for s, n in products if "transpose(" not in n]
    gradient = sorted(
        s for s, n in products
        if f"/jvp({scopes.LOOP_EXIT})/" in n
        and "/transpose(jvp(LlamaModel.head))/" in n)
    # One loop body, so each product of an exit appears once: the logits,
    # then the head's gradient and the hidden states'.
    assert forward == [(rows, vocab)], products
    assert gradient == [(hidden, vocab), (rows, hidden)], products
    assert len(products) == 3, products


def test_nothing_of_an_exits_head_or_loss_is_recomputed(looped_step):
    """What is still recomputed under the exits' scope is the norm and the
    gate that end a pass, elementwise over ``[rows, hidden]``."""
    compiled, config, _ = looped_step
    again = set(re.findall(
        rf'op_name="([^"]*/{scopes.REMATTED}/[^"]*)"', compiled.as_text()))
    assert any("/layer_0/" in n for n in again)
    exits = [n for n in again if scopes.LOOP_EXIT in n]
    assert exits
    for name in exits:
        assert "lm_head" not in name and "LlamaModel.head" not in name, name
        assert "/norm_f/" in name or "/exit_gate/" in name, name
    assert not re.search(rf"\[(\d+,)*{config['vocab_size']}\][^\n]*"
                         rf"{scopes.REMATTED}", compiled.as_text())


def test_the_walk_needs_no_more_memory_than_the_checkpointed_one(
        looped_step):
    compiled, _, _ = looped_step
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= CHECKPOINTED_TEMPORARIES)


def test_the_weight_gradient_product_reads_one_exits_hidden_states(
        looped_step):
    """The fusion that holds the head's weight-gradient product takes this
    exit's ``[1, rows, hidden]`` slice, not the stack of all exits' states
    (from which XLA reads a transposed layout, 1.5 ms an exit slower on the
    chip: what the ``optimization_barrier`` in the walk is for)."""
    compiled, config, traffic = looped_step
    rows = traffic["batch_per_chip"] * traffic["sequence"]
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    fused = [body for body in re.findall(
        r"\n%fused_computation[^\n]*\{\n(.*?)\n\}", compiled.as_text(), re.S)
        if re.search(rf" = bf16\[{hidden},{vocab}\][^\n]* convolution\(",
                     body)]
    assert len(fused) == 1
    states = re.findall(rf"bf16\[([0-9,]*{rows},{hidden})\][^\n]* parameter\(",
                        fused[0])
    assert states == [f"1,{rows},{hidden}"], states
