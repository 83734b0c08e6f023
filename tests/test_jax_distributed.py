"""hvd.init(jax_distributed=True): the launcher identity bootstraps JAX's
own multi-process runtime so the jit/GSPMD path spans processes (the
pod-metadata role of ``jax.distributed.initialize``, driven from
HOROVOD_RANK/SIZE/COORDINATOR instead)."""

import os
import subprocess
import sys

import pytest

from tests.test_native_engine import _free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "jaxdist_worker.py")


#: Infra-flake signatures from JAX's multi-process runtime on a loaded
#: box: a missed coordination-service heartbeat / shutdown barrier
#: (one process tearing down slowly), or gloo's CPU-collective
#: transport aborting on a stale TCP pair ("op.preamble.length <=
#: op.nbytes" — a connection from a previous incarnation reaching a
#: reused port).  Both are runtime plumbing, not product failures, so
#: those exact signatures (and only those) are retried with fresh
#: ports.  Assertion failures never retry.
_COORD_FLAKE = (b"heartbeat timeout", b"Shutdown barrier has failed",
                b"Barrier failed because", b"gloo::EnforceNotMet",
                b"op.preamble.length",
                # Collateral on the surviving rank when its peer's
                # runtime died: the distributed client terminates the
                # process itself (a real product failure reproduces on
                # every attempt and still fails the test).
                b"JAX distributed service detected fatal errors",
                b"Failed to send RPC to coordination service",
                b"lost connection to the coordinator")


def _run_jaxdist(scenario, timeout=240, attempts=3):
    last = None
    for attempt in range(attempts):
        port = _free_port()
        jax_port = _free_port()  # explicit: the derived port+64 may be taken
        procs = []
        for rank in range(2):
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)  # worker sets its own 2-device flag
            env.update({
                "HOROVOD_RANK": str(rank),
                "HOROVOD_SIZE": "2",
                "HOROVOD_COORDINATOR": f"127.0.0.1:{port}",
                "HOROVOD_JAX_COORDINATOR": f"127.0.0.1:{jax_port}",
                "JAX_PLATFORMS": "cpu",
            })
            procs.append(subprocess.Popen(
                [sys.executable, WORKER, scenario],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            ))
        try:
            results = [p.communicate(timeout=timeout) for p in procs]
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.communicate()
        failed = [(rank, p.returncode, out, err)
                  for rank, (p, (out, err)) in enumerate(zip(procs, results))
                  if p.returncode != 0 or b"OK" not in out]
        if not failed:
            return results
        last = failed
        coord_flake = all(
            any(sig in err or sig in out for sig in _COORD_FLAKE)
            or b"OK" in out  # this rank finished; a peer's teardown died
            for _, _, out, err in failed)
        if not (coord_flake and attempt + 1 < attempts):
            break
        print(f"[jaxdist] runtime-plumbing flake on attempt "
              f"{attempt + 1}/{attempts} "
              f"(ranks {[r for r, _, _, _ in failed]}) — retrying with "
              f"fresh ports", flush=True)
    raise AssertionError("\n".join(
        f"rank {rank} failed (rc={rc}):\n"
        f"stdout: {out.decode()}\nstderr: {err.decode()}"
        for rank, rc, out, err in last))


def test_jax_distributed_bootstrap_two_processes():
    _run_jaxdist("bootstrap")


@pytest.mark.slow
def test_gspmd_train_step_two_processes_matches_single():
    """make_parallel_train_step across 2 processes x 2 devices (4-device
    data x fsdp mesh via jax.distributed): both ranks observe identical
    losses, and they match the SAME step run single-process on a 4-device
    mesh — multi-controller GSPMD is numerically the same program
    (round-3 VERDICT item 6)."""
    results = _run_jaxdist("gspmd_step")
    losses = []
    for out, _err in results:
        for line in out.decode().splitlines():
            if line.startswith("LOSSES "):
                losses.append([float(x) for x in line.split()[1:]])
    assert len(losses) == 2, results
    assert losses[0] == losses[1], losses

    # Single-process reference on 4 of this process's virtual devices —
    # the SAME program the workers ran (shared module, cannot drift).
    import jax
    import numpy as np

    from tests.gspmd_parity_case import run_tiny_gspmd_train

    ref = run_tiny_gspmd_train(mesh_devices=jax.devices()[:4])
    np.testing.assert_allclose(losses[0], ref, rtol=1e-5, atol=1e-6)


def test_hybrid_mesh_outer_axis_spans_processes():
    """build_mesh over 2 processes x 2 devices places the outer axis
    across processes and the inner axis within each process — the
    DCN-outer/ICI-inner CONTRACT the sharding rules assume.  (CPU devices
    report no slice, so parallel/mesh.py lays them out in enumeration
    order, which is process order: the test pins the contract; the
    multi-slice branch only runs on real multi-slice TPU topologies.)
    """
    _run_jaxdist("hybrid_mesh")
