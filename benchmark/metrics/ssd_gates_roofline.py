"""The least time the chip could take for the Mamba-2 layers' skips, gates
and norms in a step (``benchmark/arithmetic_ssm_dense.py``: y, v and z read
and the result written forward; those and the result's cotangent read and
three cotangents written backward; forward once and backward once, nothing
recomputed; the elementwise operations beside them) over ``ssd_gates_ms``.
The count is the algorithm's, from shapes: it reads the same whatever
implements the pass, and below 100 % by the forward pass that ``remat`` runs
again and by the steps' softplus, which the scope holds too."""

from benchmark import ssd_dense_scopes


def read(ctx):
    return ssd_dense_scopes.gates_roofline(ctx)
