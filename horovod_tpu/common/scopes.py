"""The names the jit path gives its own work in an XLA trace.

Every ``jax.named_scope`` of ``horovod_tpu`` takes its name from this table,
and so do the documents and the benchmark's reader
(``benchmark/scopes.py``): a name is spelled here and nowhere else.  A scope
is compile-time metadata (it becomes part of each HLO operation's
``op_name``, which the profiler shows for every device operation), so it
costs nothing when the step runs.  Plain Python, no JAX.

An operation's ``op_name`` is a path, ``jit(hvd_train_step)/.../hvd.loss/
.../mul``.  JAX wraps the components that differentiation goes through:
what the forward pass runs sits under ``jvp(...)``, what the backward pass
runs under ``transpose(jvp(...))``.  So one scope around
``jax.value_and_grad`` splits forward from backward.

====================  ====================================================
scope                 what falls under it
====================  ====================================================
``hvd.loss``          ``jax.value_and_grad(loss_fn)`` in ``make_train_step``
``hvd.fusion.pack``   ravel + concatenate into a fused gradient buffer
``hvd.fusion.unpack`` slice + reshape out of it
``hvd.allreduce.<a>`` every traced all-reduce over mesh axes ``<a>``
                      (``hvd.allreduce.data``; several axes joined by ``+``)
``hvd.aux_allreduce`` the ``has_aux`` state's per-leaf all-reduces
``hvd.optimizer``     the wrapped optax transformation's ``update``
``hvd.apply``         ``optax.apply_updates``
``hvd.flash.fwd``     the flash kernel's forward Mosaic call
``hvd.flash.dq``      its backward call for dq
``hvd.flash.dkv``     its backward call for dk and dv
====================  ====================================================
"""

from __future__ import annotations

__all__ = [
    "LOSS", "FUSION_PACK", "FUSION_UNPACK", "ALLREDUCE", "AUX_ALLREDUCE",
    "OPTIMIZER", "APPLY", "FLASH_FWD", "FLASH_DQ", "FLASH_DKV",
    "TRAIN_STEP_PROGRAM", "allreduce_scope",
]

LOSS = "hvd.loss"
FUSION_PACK = "hvd.fusion.pack"
FUSION_UNPACK = "hvd.fusion.unpack"
ALLREDUCE = "hvd.allreduce"          # a prefix: allreduce_scope() completes it
AUX_ALLREDUCE = "hvd.aux_allreduce"
OPTIMIZER = "hvd.optimizer"
APPLY = "hvd.apply"
FLASH_FWD = "hvd.flash.fwd"
FLASH_DQ = "hvd.flash.dq"
FLASH_DKV = "hvd.flash.dkv"

#: The name JAX reports for the program ``make_train_step`` builds: in its
#: monitoring events (``hvd.compile_log()``: tracing under this name,
#: lowering and backend compilation under ``jit(hvd_train_step)``), as the
#: first component of every operation's ``op_name``, and on the profiler's
#: ``XLA Modules`` line (``jit_hvd_train_step(...)``).
TRAIN_STEP_PROGRAM = "hvd_train_step"


def allreduce_scope(axis_name) -> str:
    """``hvd.allreduce.data``; ``hvd.allreduce.data+fsdp`` for a tuple."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    return ALLREDUCE + "." + "+".join(str(a) for a in axes)
