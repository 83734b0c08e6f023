"""Checkpoint-plane worker: deterministic training under save/restore.

Launched by tests/test_checkpoint.py via the supervised launcher
(``python -m horovod_tpu.run ...``).  Three scenarios:

* ``elastic`` — numpy SGD under ``run_elastic`` with the env-configured
  ``CheckpointWriter`` riding every commit.  Used for the full-fleet
  kill-and-resume gate (a fresh fleet must restore the newest manifest
  and still land on the closed form) and for the injected ``ckpt-kill``
  durability test (a rank SIGKILLed mid-shard-write must cost at most
  the failed attempt, never a torn checkpoint set).
* ``jax`` / ``torch`` — the frontend adapters (``jax_capture`` /
  ``jax_restore``, ``torch_capture`` / ``torch_restore``) driven
  through real sharded (and unsharded) optimizers.  ``CKPT_MODE=train``
  runs from scratch and checkpoints; ``CKPT_MODE=resume`` rebuilds the
  state from the newest manifest at the CURRENT world size — possibly
  different from the writer's — and trains to the same total step.

The gradients are integer-valued and IDENTICAL on every rank, so the
ring average is exact (integer partial sums, exact division) and the
whole trajectory is bitwise-identical at ANY world size: the final
``digest=`` printed by a resumed run must equal the uninterrupted
reference run's, which is exactly the resharding-restore contract
("equal world: bit-identical; resized: the same math").
"""

import hashlib
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horovod_tpu.common.basics import basics  # noqa: E402
from horovod_tpu.checkpoint import (  # noqa: E402
    CheckpointLoader, CheckpointWriter,
    jax_capture, jax_restore, torch_capture, torch_restore,
)
from horovod_tpu.elastic import ElasticState, run_elastic  # noqa: E402
from horovod_tpu.runtime import engine_or_none  # noqa: E402
from horovod_tpu.runtime.engine import HorovodInternalError  # noqa: E402

LR = 0.05
DIM = 8


def _env_int(name, default):
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


TOTAL = _env_int("CKPT_TOTAL_STEPS", 30)


def _int_grads(step: int, n: int) -> np.ndarray:
    """Rank-INDEPENDENT integer-valued fp32 gradients: every partial sum
    in the reduction is an exact small integer and the average divides
    out exactly, so the training trajectory does not depend on the world
    size or the reduction order — the bitwise cross-world anchor."""
    rng = np.random.default_rng(1000 + step)
    return rng.integers(-8, 9, n).astype(np.float32)


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a, np.float32)).tobytes())
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# elastic: run_elastic + env-configured writer (kill/resume scenarios)
# ---------------------------------------------------------------------------

_writer = None
_entry_step = None


def rank_target(rank: int) -> np.ndarray:
    return np.linspace(rank + 1.0, rank + 2.0, DIM)


def _train_elastic(state: ElasticState):
    global _writer, _entry_step
    eng = engine_or_none()
    if _writer is None:
        # Lazy: the writer must capture the POST-init rank identity.
        _writer = CheckpointWriter(meta={"scenario": "elastic"})
    if _entry_step is None:
        # First entry of this incarnation — after maybe_restore+sync, so
        # this records where the fleet actually resumed from.
        _entry_step = int(state.step)
    while state.step < TOTAL:
        grad = 2.0 * (state.w - rank_target(basics.rank()))
        if eng is not None:
            grad = eng.allreduce(grad, average=True, name="ckel.g")
        state.w = state.w - LR * grad
        state.step += 1
        state.commit()
        try:
            _writer.maybe_save(int(state.step), state, None)
        except HorovodInternalError:
            # A failed checkpoint ATTEMPT (peer died mid-write) is not a
            # training failure; the step path's own collective surfaces
            # the abort and run_elastic recovers.
            pass


def scenario_elastic():
    state = ElasticState(w=np.zeros(DIM, dtype=np.float64), step=0)
    run_elastic(_train_elastic, state)
    try:
        _writer.wait(timeout=60)
    except (HorovodInternalError, TimeoutError):
        pass
    size = basics.size()
    tbar = np.mean([rank_target(r) for r in range(size)], axis=0)
    expected = tbar * (1.0 - (1.0 - 2.0 * LR) ** TOTAL)
    assert np.allclose(state.w, expected, rtol=0, atol=1e-9), (
        state.w, expected)
    print(f"CKPT_ELASTIC_OK rank={basics.rank()} step={int(state.step)} "
          f"entry={_entry_step} last_commit={_writer.last_committed_step}",
          flush=True)
    _writer.close()
    basics.shutdown()


# ---------------------------------------------------------------------------
# jax: DistributedOptimizer(optax.adam) + jax_capture / jax_restore
# ---------------------------------------------------------------------------

def scenario_jax():
    # Force CPU BEFORE first jax use: N ranks cannot share a chip.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax.numpy as jnp
    import optax

    import horovod_tpu.jax as hvdj

    basics.init()
    rank = basics.rank()
    sharded = os.environ.get("CKPT_SHARDED", "1") != "0"
    mode = os.environ.get("CKPT_MODE", "train")
    directory = os.environ["HOROVOD_CHECKPOINT_DIR"]

    opt = hvdj.DistributedOptimizer(optax.adam(1e-2), sharded=sharded,
                                    name="ckj")
    params0 = {
        "w": jnp.asarray(np.linspace(-1, 1, 257, dtype=np.float32)),
        "b": jnp.asarray(np.linspace(0, 1, 31, dtype=np.float32)),
    }
    step, entry = 0, -1
    if mode == "resume":
        loader = CheckpointLoader(directory)
        try:
            params, opt_state, step = jax_restore(opt, params0, loader)
        finally:
            loader.close()
        entry = step
    else:
        params, opt_state = params0, opt.init(params0)

    writer = CheckpointWriter(meta={"model": "ckpt-test"})
    while step < TOTAL:
        step += 1
        g = _int_grads(step, 288)
        grads = {"b": jnp.asarray(g[:31]), "w": jnp.asarray(g[31:])}
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        st, sh = jax_capture(opt, params, opt_state, step)
        if writer.maybe_save(step, st, sh):
            # Deterministic commits for the test assertions (the async
            # latest-wins drop path has its own coverage).
            writer.wait(timeout=120)
    writer.close()
    print(f"CKPT_JAX_OK rank={rank} mode={mode} sharded={int(sharded)} "
          f"step={step} entry={entry} "
          f"digest={_digest(params['b'], params['w'])}", flush=True)
    basics.shutdown()


# ---------------------------------------------------------------------------
# torch: DistributedOptimizer(SGD+momentum) + torch_capture / torch_restore
# ---------------------------------------------------------------------------

def scenario_torch():
    os.environ["JAX_PLATFORMS"] = "cpu"  # in case anything pulls jax in
    import torch

    import horovod_tpu.torch as hvdt

    basics.init()
    rank = basics.rank()
    sharded = os.environ.get("CKPT_SHARDED", "1") != "0"
    mode = os.environ.get("CKPT_MODE", "train")
    directory = os.environ["HOROVOD_CHECKPOINT_DIR"]

    class Net(torch.nn.Module):
        def __init__(self):
            super().__init__()
            torch.manual_seed(7)
            self.w = torch.nn.Parameter(torch.randn(137, 3))
            self.b = torch.nn.Parameter(torch.randn(19))

    model = Net()
    base = torch.optim.SGD(model.parameters(), lr=LR, momentum=0.9)
    opt = hvdt.DistributedOptimizer(base, sharded=sharded)
    n = 137 * 3 + 19

    step, entry = 0, -1
    if mode == "resume":
        loader = CheckpointLoader(directory)
        try:
            step = torch_restore(opt, model, loader)
        finally:
            loader.close()
        entry = step

    writer = CheckpointWriter(meta={"model": "ckpt-test"})
    while step < TOTAL:
        step += 1
        g = _int_grads(step, n)
        model.w.grad = torch.from_numpy(
            g[:137 * 3].reshape(137, 3).copy())
        model.b.grad = torch.from_numpy(g[137 * 3:].copy())
        opt.step()
        st, sh = torch_capture(opt, model, step)
        if writer.maybe_save(step, st, sh):
            writer.wait(timeout=120)
    writer.close()
    print(f"CKPT_TORCH_OK rank={rank} mode={mode} sharded={int(sharded)} "
          f"step={step} entry={entry} "
          f"digest={_digest(model.w.detach().numpy(), model.b.detach().numpy())}",
          flush=True)
    basics.shutdown()


SCENARIOS = {
    "elastic": scenario_elastic,
    "jax": scenario_jax,
    "torch": scenario_torch,
}


if __name__ == "__main__":
    SCENARIOS[sys.argv[1] if len(sys.argv) > 1 else "elastic"]()
