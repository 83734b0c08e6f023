"""The comparison that decides ``correct``: the program's loss and
gradient against the plain reference's, on the parameters the
configuration's file names (``checks.reference.parameters``).

The program's side is what the train step applies: ``loss_fn``
differentiated on each chip's rows of a seeded sample, then
``hvd.allreduce_gradients`` over the mesh's data axes, the call
``DistributedOptimizer.update`` makes.  The reference's side is the
gradient of the mean loss over the whole sample, in float32 at
``highest`` precision, on one logical device.  A sum where a mean belongs,
a shard left out, a wrong causal edge, a dropped block or a lower
precision than the configuration states puts the two further apart than
the limits in the configuration's file allow (``checks.reference``, with
their reason).  The distance between gradients is the L2 norm of their
difference over all parameters, relative to the reference's; the worst
single leaf is reported beside it and not judged (a leaf whose gradient
nearly cancels is mostly rounding).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd


def program_loss_and_grads(job, mesh, params, aux, sample):
    axes = mesh.axis_names

    def on_each_chip(params, aux, rows):
        if job.has_aux:
            (loss, _), grads = jax.value_and_grad(
                job.loss_fn, has_aux=True)(params, aux, rows)
        else:
            loss, grads = jax.value_and_grad(job.loss_fn)(params, rows)
        return (hvd.allreduce(loss, axis_name=axes),
                hvd.allreduce_gradients(grads, axis_name=axes))

    return jax.shard_map(
        on_each_chip, mesh=mesh, in_specs=(P(), P(), P(axes)),
        out_specs=(P(), P()), check_vma=False)(params, aux, sample)


def against_reference(job, reference, config, mesh, state, sample) -> dict:
    params = state[0]
    aux = state[2] if job.has_aux else ()

    def distances(params, aux, sample):
        loss, grads = program_loss_and_grads(job, mesh, params, aux, sample)
        ref_loss, ref_grads = reference.loss_and_grads(
            job.to_reference(params), sample, config)
        off = jax.tree.map(
            lambda g, r: jnp.sum(jnp.square(g.astype(jnp.float32) - r)),
            job.to_reference(grads), ref_grads)
        size = jax.tree.map(lambda r: jnp.sum(jnp.square(r)), ref_grads)
        off, size = jnp.stack(jax.tree.leaves(off)), jnp.stack(
            jax.tree.leaves(size))
        return (loss, ref_loss, jnp.sqrt(jnp.sum(off) / jnp.sum(size)),
                jnp.max(jnp.sqrt(off / (size + 1e-30))))

    loss, ref_loss, grad_rel, worst_leaf_rel = map(
        float, jax.jit(distances)(params, aux, sample))
    limits = config["checks"]["reference"]
    loss_err = abs(loss - ref_loss)
    return {
        "program_loss": loss, "reference_loss": ref_loss,
        "grad_rel_err": grad_rel, "grad_worst_leaf_rel_err": worst_leaf_rel,
        "reference_loss_close":
            math.isfinite(loss_err) and loss_err < limits["loss_abs"],
        "reference_grad_close":
            math.isfinite(grad_rel) and grad_rel < limits["grad_rel"],
    }
