"""Example smoke tests (reference CI runs sed-shrunk examples under
mpirun, .travis.yml:113-137; here each runs --smoke on the 8-device CPU
mesh, single process)."""

import os
import subprocess
import sys

import pytest


# Example smokes spawn a full training subprocess each (minutes apiece on the CI mesh);
# too heavy for the bounded tier-1 gate, covered by ci.sh's full run.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXAMPLES = [
    ("jax_mnist.py", []),
    ("flax_mnist.py", []),
    ("jax_mnist_estimator.py", []),
    ("flax_mnist_advanced.py", []),
    ("jax_imagenet_resnet50.py", []),
    ("jax_word2vec.py", []),
    ("torch_mnist.py", []),
    ("tf_mnist.py", []),
    ("keras_mnist.py", []),
    ("torch_imagenet_resnet50.py", []),
    ("torch_synthetic_benchmark.py", []),
    ("bert_pretraining_fsdp.py", []),
    ("llama_packed_pretraining.py", []),
    ("llama_training_5d.py", ["--strategy", "gspmd"]),
    ("llama_training_5d.py", ["--strategy", "seq"]),
    ("llama_training_5d.py", ["--strategy", "pipeline"]),
]


@pytest.mark.parametrize("script,extra", EXAMPLES,
                         ids=[f"{s}{'-' + e[1] if e else ''}"
                              for s, e in EXAMPLES])
def test_example_smoke(script, extra, tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    cmd = [sys.executable, os.path.join(REPO, "examples", script),
           "--smoke"] + extra
    if script in ("jax_imagenet_resnet50.py", "torch_imagenet_resnet50.py"):
        cmd += ["--checkpoint-dir", str(tmp_path / "ckpt")]
    p = subprocess.run(cmd, env=env, capture_output=True, timeout=420)
    assert p.returncode == 0, (
        f"{script} failed:\nstdout: {p.stdout.decode()[-2000:]}\n"
        f"stderr: {p.stderr.decode()[-3000:]}")
    assert b"done" in p.stdout


def test_resnet50_example_resumes(tmp_path):
    """Checkpoint/resume round trip (reference keras_imagenet_resnet50
    resume pattern)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    cmd = [sys.executable,
           os.path.join(REPO, "examples", "jax_imagenet_resnet50.py"),
           "--smoke", "--checkpoint-dir", str(tmp_path / "ckpt")]
    p1 = subprocess.run(cmd, env=env, capture_output=True, timeout=420)
    assert p1.returncode == 0, p1.stderr.decode()[-2000:]
    p2 = subprocess.run(cmd, env=env, capture_output=True, timeout=420)
    assert p2.returncode == 0, p2.stderr.decode()[-2000:]
    assert b"resuming from epoch" in p2.stdout


def _run_torch_example_world(script, n, extra, timeout=420):
    """Launch the example as an n-rank world over the engine's TCP
    rendezvous (the mpirun role)."""
    from tests.test_native_engine import _ensure_lib, _free_port

    _ensure_lib()
    port = _free_port()
    procs = []
    for rank in range(n):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "HOROVOD_RANK": str(rank),
            "HOROVOD_SIZE": str(n),
            "HOROVOD_COORDINATOR": f"127.0.0.1:{port}",
            "HOROVOD_CYCLE_TIME": "2",
        })
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(REPO, "examples", script),
             "--smoke"] + extra,
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    try:
        results = [p.communicate(timeout=timeout) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for rank, (p, (out, err)) in enumerate(zip(procs, results)):
        assert p.returncode == 0, (
            f"rank {rank} failed (rc={p.returncode}):\n"
            f"stdout: {out.decode()[-2000:]}\nstderr: {err.decode()[-3000:]}")
    return results


def test_torch_resnet50_example_resumes_two_process(tmp_path):
    """The torch ImageNet workload end-to-end at size 2: train + rank-0
    checkpoint, then a second 2-rank run discovers the checkpoint on
    rank 0, broadcasts the resume epoch, and restores state everywhere
    (reference pytorch_imagenet_resnet50.py:62-72,140-142)."""
    extra = ["--checkpoint-dir", str(tmp_path / "ckpt")]
    _run_torch_example_world("torch_imagenet_resnet50.py", 2, extra)
    results = _run_torch_example_world("torch_imagenet_resnet50.py", 2,
                                       extra)
    rank0_out = results[0][0]
    assert b"resuming from epoch" in rank0_out
