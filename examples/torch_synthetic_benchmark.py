"""Synthetic throughput benchmark for the torch frontend.

Role parity with reference ``examples/pytorch_synthetic_benchmark.py``:
timed fwd+bwd+step loop over synthetic batches, img/sec per device and
total with ±1.96σ (ref :96-110); broadcast at start (:66-67); fp16
compression flag (:33, here bf16 too).  The torch path runs on host CPU
(the TPU benchmark is ``python3 -m benchmark.run``); its numbers measure the frontend + ring
collective overhead, not TPU compute.
"""

import os
import sys
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import torch

import horovod_tpu.torch as hvd
from examples.common import example_args


def main():
    args = example_args("torch synthetic benchmark", batch_size=8,
                        num_iters=3, num_batches_per_iter=4,
                        compression="none")
    hvd.init()
    torch.manual_seed(1)

    # A small convnet stands in for torchvision's resnet50 (no model hub
    # in an air-gapped environment; same measurement semantics).
    model = torch.nn.Sequential(
        torch.nn.Conv2d(3, 32, 3, stride=2, padding=1), torch.nn.ReLU(),
        torch.nn.Conv2d(32, 64, 3, stride=2, padding=1), torch.nn.ReLU(),
        torch.nn.AdaptiveAvgPool2d(1), torch.nn.Flatten(),
        torch.nn.Linear(64, 1000),
    )
    compression = {"none": hvd.Compression.none,
                   "fp16": hvd.Compression.fp16,
                   "bf16": hvd.Compression.bf16}[args.compression]
    optimizer = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.01 * hvd.size()),
        named_parameters=model.named_parameters(),
        compression=compression,
    )
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)

    size = 32 if args.smoke else 96
    data = torch.randn(args.batch_size, 3, size, size)
    target = torch.randint(0, 1000, (args.batch_size,))

    def benchmark_step():
        optimizer.zero_grad()
        loss = torch.nn.functional.cross_entropy(model(data), target)
        loss.backward()
        optimizer.step()

    benchmark_step()  # warmup
    img_secs = []
    iters = 1 if args.smoke else args.num_iters
    for _ in range(iters):
        t = timeit.timeit(benchmark_step, number=args.num_batches_per_iter)
        img_secs.append(args.batch_size * args.num_batches_per_iter / t)

    img_sec_mean = np.mean(img_secs)
    img_sec_conf = 1.96 * np.std(img_secs)
    if hvd.rank() == 0:
        print(f"Img/sec per device: {img_sec_mean:.1f} +-{img_sec_conf:.1f}")
        print(f"Total img/sec on {hvd.size()} device(s): "
              f"{hvd.size() * img_sec_mean:.1f} "
              f"+-{hvd.size() * img_sec_conf:.1f}")
    print("done", flush=True)


if __name__ == "__main__":
    main()
