"""ImageNet ResNet-50 — the north-star workload (BASELINE.md).

Role parity with reference ``examples/keras_imagenet_resnet50.py``:
checkpoint/resume with broadcast of the resume epoch (ref :64-73),
restore + re-broadcast state on resume (:102-104), bf16 wire compression
flag (:34-35, 97 — fp16 there), warmup + staircase LR schedule
(:147-153), 1/N data sharding (:161-173), final allreduce of the eval
score (:176), rank-0-only checkpoints (:156-158).

Synthetic ImageNet (see examples/common.py); the benchmark's cell
``resnet50-v1.5.train-b256`` measures the same model's throughput.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training.train_state import TrainState

import horovod_tpu.flax as hvdk
import horovod_tpu.jax as hvd
from examples.common import example_args, shard_for_rank, synthetic_imagenet
from horovod_tpu.models import ResNet50


def main():
    args = example_args("ResNet-50 ImageNet (synthetic)", epochs=8,
                        batch_size=64, lr=0.0125,
                        checkpoint_dir="./checkpoints-resnet50",
                        compression="bf16", warmup_epochs=3)
    hvd.init()
    mesh = hvd.data_parallel_mesh()
    n = hvd.num_chips()

    image_size = 32 if args.smoke else 224
    n_train = 256 if args.smoke else 4096
    images, labels = synthetic_imagenet(n_train, image_size)
    images, labels = shard_for_rank((images, labels), hvd.rank(), hvd.size())
    val_images, val_labels = synthetic_imagenet(
        128 if args.smoke else 1024, image_size, seed=99)
    val_images, val_labels = shard_for_rank(
        (val_images, val_labels), hvd.rank(), hvd.size())

    model = ResNet50(dtype=jnp.bfloat16)
    variables = jax.jit(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, image_size, image_size, 3)),
        train=False))()

    compression = {"none": hvd.Compression.none,
                   "fp16": hvd.Compression.fp16,
                   "bf16": hvd.Compression.bf16}[args.compression]

    tx = optax.inject_hyperparams(optax.sgd)(
        learning_rate=args.lr * n, momentum=0.9, nesterov=True)
    opt = hvd.DistributedOptimizer(tx, compression=compression)

    def loss_fn(params, batch_stats, batch):
        x, y = batch
        logits, updates = model.apply(
            {"params": params, "batch_stats": batch_stats}, x,
            train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))
        return loss, updates["batch_stats"]

    dist_step = hvd.make_train_step(loss_fn, opt, mesh, has_aux=True,
                                    donate=False)

    class State(TrainState):
        batch_stats: dict = None

    state = State.create(apply_fn=model.apply, params=variables["params"],
                         tx=tx, batch_stats=variables["batch_stats"])

    def train_step(state, batch):
        params, opt_state, batch_stats, loss = dist_step(
            state.params, state.opt_state, state.batch_stats, batch)
        return state.replace(params=params, opt_state=opt_state,
                             batch_stats=batch_stats,
                             step=state.step + 1), {"loss": loss}

    # ---- resume (reference :64-73, :102-104) ----
    state, start_epoch = hvdk.restore_and_broadcast(args.checkpoint_dir,
                                                    state)
    if start_epoch and hvd.rank() == 0:
        print(f"resuming from epoch {start_epoch}", flush=True)

    batch = args.batch_size
    steps = max(len(images) // batch, 1)

    def data_fn(epoch):
        perm = np.random.default_rng(epoch).permutation(len(images))
        for i in range(steps):
            idx = perm[i * batch:(i + 1) * batch]
            idx = idx[: len(idx) - len(idx) % n] if len(idx) >= n else idx
            if len(idx) == 0:
                continue
            yield jnp.asarray(images[idx]), jnp.asarray(labels[idx])

    epochs = 1 if args.smoke else args.epochs

    class CheckpointCallback(hvdk.Callback):
        def on_epoch_end(self, epoch, state, logs):
            hvdk.save_checkpoint(args.checkpoint_dir, state, epoch)
            return state

    callbacks = [
        hvdk.BroadcastGlobalVariablesCallback(0),
        hvdk.MetricAverageCallback(),
        hvdk.LearningRateWarmupCallback(
            initial_lr=args.lr * n, warmup_epochs=args.warmup_epochs,
            steps_per_epoch=steps, verbose=hvd.rank() == 0),
        hvdk.LearningRateScheduleCallback(
            initial_lr=args.lr * n, start_epoch=args.warmup_epochs,
            multiplier=lambda e: 10.0 ** -(e // 30)),  # staircase /10 @30,60
        CheckpointCallback(),
    ]
    state = hvdk.fit(state, data_fn, epochs=epochs, train_step=train_step,
                     steps_per_epoch=steps, callbacks=callbacks,
                     initial_epoch=start_epoch)

    # ---- eval, score allreduced across processes (reference :176) ----
    @jax.jit
    def eval_step(state, x, y):
        logits = model.apply({"params": state.params,
                              "batch_stats": state.batch_stats}, x,
                             train=False)
        return jnp.mean(jnp.argmax(logits, -1) == y)

    accs = []
    for i in range(0, len(val_images) - batch + 1, batch):
        accs.append(float(eval_step(
            state, jnp.asarray(val_images[i:i + batch]),
            jnp.asarray(val_labels[i:i + batch]))))
    local = np.mean(accs) if accs else 0.0
    global_acc = hvd.allreduce(jnp.asarray(local), op=hvd.Average,
                               name="eval_acc")
    if hvd.rank() == 0:
        print(f"validation accuracy (all ranks): {float(global_acc):.4f}",
              flush=True)
    print("done", flush=True)


if __name__ == "__main__":
    main()
