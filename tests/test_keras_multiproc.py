"""Multi-process Keras-3 frontend tests across the JAX backend (the
TPU-native flagship: jitted train step, allreduce via io_callback),
the TensorFlow backend (py_function path), and the torch backend
(eager host path).  Scenarios live in tests/keras_worker.py."""

import os

import pytest

from tests.test_native_engine import run_workers


# Each scenario spawns N keras+TF worker processes;
# too heavy for the bounded tier-1 gate, covered by ci.sh's full run.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "keras_worker.py")


def run_keras_workers(n, scenario, backend, timeout=300, extra_env=None,
                      expected_rc=None):
    env = {
        "KERAS_BACKEND": backend,
        "CUDA_VISIBLE_DEVICES": "-1",
    }
    if backend == "jax":
        env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    run_workers(n, scenario, timeout=timeout, worker=WORKER, extra_env=env,
                expected_rc=expected_rc)


@pytest.mark.parametrize("backend", ["jax", "tensorflow", "torch"])
def test_keras_fit_equalizes(backend):
    run_keras_workers(2, "fit", backend)


def test_keras_fit_equalizes_4rank():
    run_keras_workers(4, "fit", "jax")


@pytest.mark.parametrize("backend", ["tensorflow", "jax"])
def test_keras_batch0_loss_identical(backend):
    """Weights broadcast strictly before the first train step: batch-0
    losses match across ranks even with divergent init (reference
    callbacks_impl.py:20-30)."""
    run_keras_workers(2, "batch0", backend)


def test_keras_momentum_correction_jax():
    """Momentum correction is active (velocity-slot scaling) under the
    jitted JAX trainer — no warning, slots scaled by new_lr/old_lr."""
    run_keras_workers(2, "momentum", "jax")


@pytest.mark.parametrize("backend", ["jax", "tensorflow"])
def test_keras_worker_death_contained(backend):
    """A crashed peer surfaces a descriptive error on survivors instead
    of hanging the fit loop."""
    run_keras_workers(3, "death", backend, expected_rc={2: 31})


def test_keras_load_model_resume(tmp_path):
    run_keras_workers(2, "resume", "jax", extra_env={
        "HVD_TEST_CKPT": str(tmp_path / "model.keras")})


def test_keras_lr_warmup(tmp_path):
    run_keras_workers(2, "warmup", "jax")
