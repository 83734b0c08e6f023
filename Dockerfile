# Container recipe for horovod_tpu — role parity with the reference's
# Dockerfile (reference Dockerfile:1-60: CUDA base + TF/PyTorch/Keras +
# OpenMPI + horovod build), reshaped for the TPU stack: no MPI and no
# CUDA anywhere; jax provides the accelerator path and the native TCP
# engine is built from source with plain g++.
#
#   docker build -t horovod-tpu .                 # CPU/CI image
#   docker build --build-arg JAX_VARIANT=tpu -t horovod-tpu .   # TPU VM
#
# Verify the image the same way CI does (8-device virtual CPU mesh — no
# hardware needed):
#
#   docker run --rm horovod-tpu ./ci.sh
#
# On a TPU VM, run with host networking and the TPU runtime mounted as
# that platform documents; multi-host launches use the bundled
# `horovod-tpu-run` console script.

FROM python:3.12-slim-bookworm

RUN apt-get update && apt-get install -y --no-install-recommends \
        build-essential \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /opt/horovod_tpu

# Framework deps first (stable layer, cached across source edits).
# JAX_VARIANT=cpu (default) runs everywhere; =tpu pulls libtpu for TPU
# VMs.  torch is the CPU wheel by design: the torch frontend is a host
# data plane here (accelerator compute is JAX/XLA).
ARG JAX_VARIANT=cpu
RUN pip install --no-cache-dir \
        "jax[${JAX_VARIANT}]" flax optax orbax-checkpoint chex einops \
        ml_dtypes numpy pytest tensorflow-cpu \
    && pip install --no-cache-dir torch \
        --index-url https://download.pytorch.org/whl/cpu

# Source + editable install + native engine build (mirrors ci.sh).
COPY pyproject.toml setup.py README.md ci.sh bench_engine.py \
     __graft_entry__.py ./
COPY horovod_tpu ./horovod_tpu
COPY tests ./tests
COPY examples ./examples
RUN pip install --no-cache-dir -e . \
    && make -C horovod_tpu/cpp

CMD ["./ci.sh"]
