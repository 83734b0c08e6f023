"""Plain reference of the classifier the ``resnet50-v1.5`` cell trains.

Straightforward ``jax.numpy`` / ``lax.conv_general_dilated`` in float32 at
``highest`` precision, nothing imported from the program.  He et al.
(arXiv:1512.03385) with the v1.5 stride placement: a 7x7/2 stem, 3x3/2 max
pool, four stages of bottlenecks (1x1 reduce, 3x3 carrying the stage's
stride, 1x1 expand x4, a 1x1 projection on the shortcut where the shape
changes), global average pool, a dense classifier; batch norm in training
mode (statistics of the batch at hand, biased variance), mean
cross-entropy.  NHWC activations, HWIO kernels.

Parameters are a plain tree: ``stem`` (``conv``, ``bn``), ``blocks`` (a
list of ``conv1 bn1 conv2 bn2 conv3 bn3`` and, where the shape changes,
``proj bn_proj``), ``head`` (``kernel [C, classes]``, ``bias``); a ``bn``
is ``scale`` and ``bias``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def conv(x, kernel, stride=1, padding="SAME"):
    return lax.conv_general_dilated(
        x, kernel, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def batch_norm(x, bn, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * bn["scale"] + bn["bias"]


def bottleneck(x, block, stride, eps):
    y = jax.nn.relu(batch_norm(conv(x, block["conv1"]), block["bn1"], eps))
    y = jax.nn.relu(batch_norm(conv(y, block["conv2"], stride),
                               block["bn2"], eps))
    y = batch_norm(conv(y, block["conv3"]), block["bn3"], eps)
    if "proj" in block:
        x = batch_norm(conv(x, block["proj"], stride), block["bn_proj"], eps)
    return jax.nn.relu(x + y)


def logits(params, images, config):
    eps = config["batch_norm_eps"]
    x = conv(images, params["stem"]["conv"], 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(batch_norm(x, params["stem"]["bn"], eps))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    blocks = iter(params["blocks"])
    for stage, n_blocks in enumerate(config["stage_sizes"]):
        for i in range(n_blocks):
            stride = 2 if stage > 0 and i == 0 else 1
            x = bottleneck(x, next(blocks), stride, eps)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["head"]["kernel"] + params["head"]["bias"]


def loss(params, batch, config):
    images, labels = batch
    logp = jax.nn.log_softmax(logits(params, images, config))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def loss_and_grads(params, batch, config):
    """(loss, d loss / d params) in float32 at ``highest`` precision."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return jax.value_and_grad(loss)(params, batch, config)
