"""Pipeline parallelism: GPipe-style microbatched stages over a mesh axis.

No reference equivalent (Horovod 0.15.1 is data-parallel only, SURVEY.md
§2.3); pipeline support is TPU-native new work.  Design:

* the layer stack is split into ``pipe`` contiguous stages; stage
  parameters live stacked with a leading stage dim sharded over the
  ``pipe`` mesh axis — each device materializes only its own stage (the
  memory win that motivates PP);
* inside ``shard_map`` the batch is cut into microbatches that flow
  through the stage ring via ``lax.ppermute`` — neighbor-only ICI
  transfers;
* every device runs the identical SPMD program (XLA requirement): during
  bubble steps stages compute on garbage that is masked out of the result;
* backward is plain ``jax.grad`` — ppermute's transpose reverses the ring,
  so autodiff yields the reverse-schedule pipeline automatically (GPipe
  semantics: all microbatch activations live until backward; wrap
  ``stage_fn`` in ``jax.checkpoint`` to trade FLOPs for memory).

The final broadcast-from-last-stage pins its own vjp
(``_broadcast_from_last``), so ``pipeline_apply`` differentiates
identically under ``check_vma=True`` AND ``check_vma=False`` (verified
against sequential-execution gradients in tests/test_pipeline.py).
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.ops.losses import softmax_cross_entropy

__all__ = [
    "pipeline_apply",
    "stack_pytrees",
    "unstack_pytree",
    "init_pipelined_llama",
    "make_pipelined_llama_train_step",
]


def stack_pytrees(trees: Sequence):
    """Stack a list of identical-structure pytrees along a new leading axis
    (layer params -> scannable/shardable stacked params)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def unstack_pytree(tree, n: int):
    return [jax.tree.map(lambda x: x[i], tree) for i in range(n)]


# Broadcast-from-last-stage with an EXPLICIT vjp.  The forward is the
# masked psum; the correct cotangent is simply the (replicated) output
# cotangent delivered to the last stage and zero elsewhere.  Under
# check_vma=False (the explicit-grad-psum builders) AD's own psum
# transpose would sum the replicas, inflating every stage gradient by
# the stage count — so the rule is pinned by hand.
from functools import partial as _partial  # noqa: E402


@_partial(jax.custom_vjp, nondiff_argnums=(2,))
def _broadcast_from_last(outputs, mask, axis_name):
    return lax.psum(outputs * mask, axis_name)


def _broadcast_from_last_fwd(outputs, mask, axis_name):
    return _broadcast_from_last(outputs, mask, axis_name), mask


def _broadcast_from_last_bwd(axis_name, mask, g):
    return (g * mask, jnp.zeros_like(mask))


_broadcast_from_last.defvjp(*_scopes.rules(
    "_broadcast_from_last", _broadcast_from_last_fwd,
    _broadcast_from_last_bwd))


def pipeline_apply(stage_fn: Callable, stage_params, x, *,
                   axis_name: str = "pipe", n_microbatches: int):
    """Run ``x`` through the pipeline.  Call inside ``shard_map`` with
    ``axis_name`` bound and ``stage_params`` sharded so each device holds
    its stage slice (leading dim 1, pre-squeezed by the in_spec).

    ``stage_fn(stage_params, x_mb) -> y_mb`` with matching shapes (the
    homogeneous-stage constraint standard pipelines share).
    ``x``: [B, ...] with B divisible by ``n_microbatches``.
    Returns [B, ...], identical on every pipe shard.
    """
    n_stages = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = n_microbatches
    B = x.shape[0]
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    micro = x.reshape((M, mb) + x.shape[1:])

    # Activations hop stage i -> i+1; the wrap edge only carries bubble
    # garbage, and a ring permute keeps the collective uniform.
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    state = jnp.zeros((mb,) + x.shape[1:], x.dtype)
    outputs = jnp.zeros((M, mb) + x.shape[1:], x.dtype)

    for t in range(M + n_stages - 1):
        feed = micro[min(t, M - 1)]
        inp = jnp.where(idx == 0, feed, state)
        out = stage_fn(stage_params, inp)
        j = t - (n_stages - 1)
        if 0 <= j < M:
            keep = jnp.where(idx == n_stages - 1, out, outputs[j])
            outputs = outputs.at[j].set(keep)
        state = lax.ppermute(out, axis_name, perm)

    # Everyone receives the final result (masked psum = broadcast from the
    # last stage) so loss/metrics can be computed replicated.
    mask = (idx == n_stages - 1).astype(outputs.dtype)
    outputs = _broadcast_from_last(outputs, mask, axis_name)
    return outputs.reshape((B,) + x.shape[1:])


# ---------------------------------------------------------------------------
# Pipelined Llama (the framework's PP training path)
# ---------------------------------------------------------------------------

def _refuse_looped(cfg) -> None:
    cfg.refuse_new_kinds("the pipelined step")
    if cfg.total_ut_steps > 1:
        raise NotImplementedError(
            f"the pipelined step sends a microbatch through the stages "
            f"once; total_ut_steps={cfg.total_ut_steps} would run one "
            f"pass silently")


def init_pipelined_llama(cfg, rng, n_stages: int):
    """Init Llama params in pipeline layout.

    Returns ``{"stages": <stacked layer params [n_stages, L/n_stages, ...]>,
    "rest": {tok_emb, norm_f, lm_head}}``.  Place ``stages`` leaves with
    ``NamedSharding(mesh, P("pipe"))`` so each device materializes one
    stage.
    """
    from horovod_tpu.models.llama import LlamaModel

    if cfg.num_layers % n_stages != 0:
        raise ValueError(
            f"{cfg.num_layers} layers not divisible into {n_stages} stages")
    _refuse_looped(cfg)
    model = LlamaModel(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(rng, ids)["params"]
    layers = [params[f"layer_{i}"] for i in range(cfg.num_layers)]
    staged = jax.tree.map(
        lambda a: a.reshape(
            (n_stages, cfg.num_layers // n_stages) + a.shape[1:]),
        stack_pytrees(layers))
    rest = {"tok_emb": params["tok_emb"], "norm_f": params["norm_f"],
            "lm_head": params["lm_head"]}
    return {"stages": staged, "rest": rest}


def make_pipelined_llama_train_step(cfg, optimizer, mesh, *,
                                    n_microbatches: int,
                                    pipe_axis: str = "pipe",
                                    donate: bool = True):
    """Jitted LM train step with the layer stack pipelined over
    ``pipe_axis`` and batch sharded over the data-like axes.

    Hybrid design: loss+grads run in ``shard_map`` (explicit microbatch
    ppermute pipeline, data-axis psum of gradients); the optimizer update
    runs at the GSPMD level so optimizer state inherits each parameter's
    sharding (stage-sharded for stage params) with no manual spec plumbing.

    ``step(params, opt_state, inputs, targets) ->
    (params, opt_state, loss)`` with ``params`` from
    :func:`init_pipelined_llama`.

    FSDP composition: wrapping the optimizer as
    ``DistributedOptimizer(inner, fsdp=True)`` on a mesh with a
    non-trivial ``fsdp`` axis shards the GSPMD-level OPTIMIZER STATE
    over that axis (each moment tensor constrained to 1/|fsdp| per
    device; XLA inserts the allgather/reducescatter halves around the
    update).  The batch already shards over the data-LIKE axes —
    ``data`` and ``fsdp`` both carry microbatches — so pipeline × fsdp
    × data coexist on one mesh: ``build_mesh({"pipe": P, "fsdp": F,
    "data": D})``.  This is the in-jit rung of the sharding ladder; the
    host-driven eager rung is ``runtime/fsdp.py`` (docs/zero.md).
    """
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models.llama import LlamaLayer, rope_freqs
    from horovod_tpu.parallel.mesh import AXIS_FSDP, data_axes

    from horovod_tpu.jax import DistributedOptimizer

    fsdp_axis = None
    if isinstance(optimizer, DistributedOptimizer):
        if getattr(optimizer, "_fsdp", False) \
                and AXIS_FSDP in mesh.axis_names \
                and mesh.shape[AXIS_FSDP] > 1:
            fsdp_axis = AXIS_FSDP
        # Gradients are already data-psum'd inside the shard_map below.
        optimizer = optimizer.inner

    _refuse_looped(cfg)
    batch_axes = tuple(data_axes(mesh)) or ()
    layer_mod = LlamaLayer(cfg)

    def stage_fn(stage_params, x):
        cos, sin = rope_freqs(cfg.head_dim, x.shape[1], cfg.rope_theta)

        def body(h, lp):
            return layer_mod.apply({"params": lp}, h, cos, sin), None

        out, _ = lax.scan(body, x, stage_params)
        return out

    def _local_loss(stages, rest, inputs, targets):
        emb = jnp.take(rest["tok_emb"]["embedding"], inputs,
                       axis=0).astype(cfg.dtype)
        h = pipeline_apply(stage_fn, stages, emb, axis_name=pipe_axis,
                           n_microbatches=n_microbatches)
        h32 = h.astype(jnp.float32)
        h32 = h32 * lax.rsqrt(
            jnp.mean(h32 * h32, axis=-1, keepdims=True) + cfg.rms_eps)
        h = (h32 * rest["norm_f"]["scale"]).astype(cfg.dtype)
        logits = (h @ rest["lm_head"]["kernel"]).astype(jnp.float32)
        # Local sum in lse form (no fp32 log-prob tensor).
        return softmax_cross_entropy(logits, targets, reduction="sum")

    def _grads(stages_sharded, rest, inputs, targets):
        stages = jax.tree.map(lambda a: a[0], stages_sharded)
        n_data = lax.axis_size(batch_axes) if batch_axes else 1
        denom = inputs.shape[0] * n_data * inputs.shape[1]
        loss_sum, grads = jax.value_and_grad(
            _local_loss, argnums=(0, 1))(stages, rest, inputs, targets)
        g_stages, g_rest = grads
        # Horovod pattern (check_vma=False + explicit grad psums — same
        # discipline as make_train_step and the seq builder, and identical
        # on both jax AD lines, where VMA-aware AD would instead insert
        # these reductions itself): each shard holds partial cotangents.
        # tok_emb feeds the pipeline INPUT, so its cotangent lives only on
        # the stage-0 shard — collect it with a psum over pipe.  norm_f /
        # lm_head act on the replicated broadcast OUTPUT, so every pipe
        # shard already holds their full cotangent — no pipe reduction.
        # Everything then reduces over the data axes it is invariant to.
        g_rest = dict(g_rest)
        g_rest["tok_emb"] = jax.tree.map(
            lambda a: lax.psum(a, pipe_axis), g_rest["tok_emb"])
        if batch_axes:
            loss_sum = lax.psum(loss_sum, batch_axes)
            g_stages = jax.tree.map(lambda a: lax.psum(a, batch_axes),
                                    g_stages)
            g_rest = jax.tree.map(lambda a: lax.psum(a, batch_axes),
                                  g_rest)
        g_stages = jax.tree.map(lambda a: a[None] / denom, g_stages)
        g_rest = jax.tree.map(lambda a: a / denom, g_rest)
        return loss_sum / denom, {"stages": g_stages, "rest": g_rest}

    stage_specs = P(pipe_axis)
    batch_spec = P(tuple(batch_axes) if batch_axes else None)

    def _fsdp_state_spec(shape, n_stages):
        """ZeRO spec for one optimizer-state leaf: stage-stacked moments
        keep their pipe dim, then the first remaining dim divisible by
        the fsdp axis shards over it (scalars and indivisible leaves
        stay replicated — counts, tiny norms)."""
        fsdp_size = mesh.shape[fsdp_axis]
        spec = [None] * len(shape)
        start = 0
        if shape and shape[0] == n_stages:
            spec[0] = pipe_axis
            start = 1
        for d in range(start, len(shape)):
            if shape[d] >= fsdp_size and shape[d] % fsdp_size == 0:
                spec[d] = fsdp_axis
                break
        return P(*spec)

    def _constrain_opt_state(opt_state, n_stages):
        def leaf(a):
            if not hasattr(a, "shape"):
                return a
            return lax.with_sharding_constraint(
                a, NamedSharding(mesh,
                                 _fsdp_state_spec(a.shape, n_stages)))
        return jax.tree.map(leaf, opt_state)

    def step(params, opt_state, inputs, targets):
        loss, grads = jax.shard_map(
            _grads, mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: stage_specs, params["stages"]),
                jax.tree.map(lambda _: P(), params["rest"]),
                batch_spec, batch_spec),
            out_specs=(
                P(),
                {"stages": jax.tree.map(lambda _: stage_specs,
                                        params["stages"]),
                 "rest": jax.tree.map(lambda _: P(), params["rest"])}),
            check_vma=False,
        )(params["stages"], params["rest"], inputs, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        if fsdp_axis is not None:
            n_stages = jax.tree.leaves(params["stages"])[0].shape[0]
            opt_state = _constrain_opt_state(opt_state, n_stages)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    donate_args = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_args)
