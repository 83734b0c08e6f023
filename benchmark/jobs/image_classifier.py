"""Image-classifier training through the program's main path.

``models.resnet.ResNet`` of bottleneck blocks in bf16, momentum SGD under
``hvd.DistributedOptimizer``, batch-norm statistics carried as the
``has_aux`` state of ``hvd.make_train_step`` (averaged over the data axis:
cross-replica running statistics).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark import arithmetic
from horovod_tpu.models.resnet import BottleneckBlock, ResNet


def build(config: dict, traffic: dict, chips: int):
    return ImageClassifier(config, traffic, chips)


class ImageClassifier:
    unit = "images"
    has_aux = True

    def __init__(self, config: dict, traffic: dict, chips: int):
        training = config["training"]
        if (training["optimizer"], training["compute_dtype"]) != (
                "sgd", "bfloat16"):
            raise ValueError(f"this job trains under bf16 momentum SGD; "
                             f"asked for {training}")
        if (config["stride_on"], config["bottleneck_expansion"],
                config["zero_init_last_bn_scale"]) != ("conv3x3", 4, True):
            raise ValueError("models/resnet.py builds v1.5 bottlenecks "
                             "(stride on the 3x3, expansion 4, last "
                             "batch-norm scale zero)")
        self.config = config
        self.chips = chips
        self.image = config["image_size"]
        self.classes = config["num_classes"]
        self.batch = traffic["batch_per_chip"] * chips
        self.sample_rows = traffic["sample_per_chip"] * chips
        self.units_per_step = self.batch
        self.model = ResNet(stage_sizes=tuple(config["stage_sizes"]),
                            block_cls=BottleneckBlock,
                            num_classes=self.classes, width=config["width"],
                            dtype=jnp.bfloat16)
        self.optimizer = hvd.DistributedOptimizer(optax.sgd(
            training["learning_rate_per_chip"] * chips,
            momentum=training["momentum"]))

    # -- what the harness jits ------------------------------------------

    def init_state(self, key):
        """(params, opt_state, batch_stats), traced inside the harness's
        one set-up program."""
        variables = self.model.init(
            key, jnp.zeros((1, self.image, self.image, 3)), train=False)
        params = variables["params"]
        return params, self.optimizer.init(params), variables["batch_stats"]

    def make_batch(self, key, rows: int | None = None):
        k_images, k_labels = jax.random.split(key)
        rows = rows or self.batch
        images = jax.random.normal(
            k_images, (rows, self.image, self.image, 3), jnp.float32)
        labels = jax.random.randint(k_labels, (rows,), 0, self.classes,
                                    jnp.int32)
        return images, labels

    def loss_fn(self, params, batch_stats, batch):
        images, labels = batch
        logits, updates = self.model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
        return loss, updates["batch_stats"]

    # -- facts for the metric readers -----------------------------------

    def flops_per_unit(self) -> float:
        return arithmetic.resnet_train_flops_per_image(
            image=self.image, classes=self.classes,
            stage_sizes=tuple(self.config["stage_sizes"]),
            width=self.config["width"])

    def kernel_work_per_step(self) -> dict:
        return {}                              # no Mosaic call in this job

    # -- checks ---------------------------------------------------------

    def expected_first_loss(self) -> float:
        return (math.log(self.classes)
                + self.config["checks"]["first_loss_is_ln_classes_plus"])

    def to_reference(self, tree):
        """The program's parameter (or gradient) tree in the plain
        reference's layout: a re-naming, so gradients map alike."""

        def bn(node):
            return {"scale": node["scale"], "bias": node["bias"]}

        blocks = []
        for i in range(sum(self.config["stage_sizes"])):
            node = tree[f"BottleneckBlock_{i}"]
            block = {f"conv{j + 1}": node[f"Conv_{j}"]["kernel"]
                     for j in range(3)}
            block.update({f"bn{j + 1}": bn(node[f"BatchNorm_{j}"])
                          for j in range(3)})
            if "Conv_3" in node:
                block["proj"] = node["Conv_3"]["kernel"]
                block["bn_proj"] = bn(node["BatchNorm_3"])
            blocks.append(block)
        return {"stem": {"conv": tree["conv_init"]["kernel"],
                         "bn": bn(tree["bn_init"])},
                "blocks": blocks,
                "head": {"kernel": tree["head"]["kernel"],
                         "bias": tree["head"]["bias"]}}
