"""Whether the limits of ``smallthinker-21b-a3b``'s comparison tell the job from wrong versions of it.

On the chip, at the cell's size (published widths, one sequence of 16,384 tokens), each named version of
``benchmark/jobs/prerouted_moe_lm.py``'s job, alone, goes through ``benchmark/compare.against_reference``
under the limits of the configuration's own file (``checks.reference``), on the seed's initial parameters
and sample as ``benchmark/run.py`` makes them:

    python3 tools/smallthinker_wrong_versions.py <seed> [version ...]

prints one JSON line a version: ``against_reference``'s readings and its two verdicts, the loss's distance,
the limits, and ``correct`` (both verdicts).  ``right`` is the job as it is and has to be correct; every
other version has to fail a limit.  ``tests/benchmark/test_benchmark_prerouted.py`` holds the same table at
a tiny size in float32 on the CPU.  A version costs about 100 s on a v5e (the reference is computed anew in
each, as the harness computes it)."""
import dataclasses, json, os, sys, time
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp, numpy as np
from horovod_tpu.models import LlamaModel, llama
from horovod_tpu.models.llama import LlamaConfig
from horovod_tpu.ops.flash_attention import flash_attention_fn

CELL = "smallthinker-21b-a3b.train-s16k"


def _with_model(job, **changes):
    """The job's loss through a model whose configuration differs by ``changes``."""
    wrong = LlamaModel(dataclasses.replace(job.llama, **changes), attention_fn=flash_attention_fn)
    return _patched(job, job, "model", lambda _: wrong)


def _patched(job, owner, name, value):
    """The job's loss while ``owner.name`` is ``value(what it was)``."""
    right = type(job).loss_fn

    def loss_fn(*args):     # (params, batch), or with the state beside them
        original = getattr(owner, name)
        setattr(owner, name, value(original))
        try:
            return right(job, *args)
        finally:
            setattr(owner, name, original)
    return loss_fn


def _but_in(layer, value):
    """A ``LlamaConfig`` method that answers ``value`` for ``layer`` alone."""
    return lambda method: lambda self, i: value if i == layer else method(self, i)


def _float8(job, dtype):
    """A precision below the stated one: the inputs of the forward pass's matmuls (the dense projections',
    the head's and the experts' grouped products') rounded to ``dtype`` with a power-of-two scale a tensor
    and a straight-through gradient; the flash kernel's own products and the float32 router stay."""
    @jax.custom_vjp
    def rounded(x):
        # Rows past a grouped product's last group are undefined on the chip, NaN at times: the program cuts
        # them off behind the second product, so here they are zeroed before the tensor's scale is taken.
        x32 = x.astype(jnp.float32)
        x32 = jnp.where(jnp.isfinite(x32), x32, 0.0)
        scale = jnp.exp2(jnp.ceil(jnp.log2(
            jnp.maximum(jnp.max(jnp.abs(x32)), 1e-30) / float(jnp.finfo(dtype).max))))
        return ((x32 / scale).astype(dtype).astype(jnp.float32) * scale).astype(x.dtype)
    rounded.defvjp(lambda x: (rounded(x), None), lambda _, g: (g,))
    right, compute = type(job).loss_fn, job.llama.dtype

    def over(dot):
        def wrapped(lhs, rhs, *args, **kwargs):
            if lhs.dtype == compute and lhs.ndim == 3:      # flax Dense's [B, S, H] x [H, F]
                lhs, rhs = rounded(lhs), rounded(rhs)
            return dot(lhs, rhs, *args, **kwargs)
        return wrapped

    def loss_fn(*args):
        was = jax.lax.dot_general, llama.grouped_matmul
        jax.lax.dot_general = over(was[0])
        llama.grouped_matmul = lambda rows, w, *rest: was[1](rounded(rows), rounded(w), *rest)
        try:
            return right(job, *args)
        finally:
            jax.lax.dot_general, llama.grouped_matmul = was
    return loss_fn


def versions(job) -> dict:
    """name -> a maker of the version's ``loss_fn``; ``right`` is the job's own.  ONE sliding layer is the
    middle one of those the job has (layer 2 of the cell's 0, 1, 1, 1)."""
    sliding = [i for i in range(job.llama.num_layers) if job.llama.window_of(i)]
    one = sliding[len(sliding) // 2]
    kinds = dict(job.llama.rope_parameters)
    turns = kinds["sliding_attention"]
    return {
        "right": lambda: type(job).loss_fn.__get__(job),
        "router_reads_post_attention_normed_state": lambda: _with_model(job, router_input="experts"),
        "window_ignored_in_one_sliding_layer": lambda: _patched(
            job, LlamaConfig, "window_of", _but_in(one, None)),
        "global_layer_rotated": lambda: _with_model(job, rope_parameters=tuple(
            (kind, turns) for kind in kinds)),
        "one_window_layer_not_rotated": lambda: _patched(
            job, LlamaConfig, "rope_of", _but_in(one, None)),
        "silu_for_relu": lambda: _with_model(job, mlp_hidden_act="silu"),
        "softmax_over_64_not_renormalised": lambda: _with_model(job, norm_topk_prob=False),
        "float8_e4m3": lambda: _float8(job, jnp.float8_e4m3fn),
        "float8_e5m2": lambda: _float8(job, jnp.float8_e5m2),
    }


def judge(job, reference, config, mesh, state, sample, name, table=None) -> dict:
    """``compare.against_reference``'s line for version ``name`` of the job, with ``correct``; ``table``:
    another job's ``versions`` (``tools/xing4_wrong_versions.py``)."""
    from benchmark import compare
    started = time.perf_counter()
    job.loss_fn = (table or versions)(job)[name]()
    try:
        found = compare.against_reference(job, reference, config, mesh, state, sample)
    finally:
        del job.loss_fn
    limits = config["checks"]["reference"]
    return {"version": name, **found,
            "loss_abs_err": abs(found["program_loss"] - found["reference_loss"]),
            "limits": {key: limits[key] for key in ("loss_abs", "grad_rel")},
            "correct": found["reference_loss_close"] and found["reference_grad_close"],
            "seconds": round(time.perf_counter() - started, 1)}


def main(argv=None) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P
    import horovod_tpu.jax as hvd
    from benchmark import manifest

    argv = argv or sys.argv[1:]
    if not argv:
        sys.exit(__doc__)
    seed, names = int(argv[0]), argv[1:]
    cell = manifest.cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    hvd.init()
    mesh = hvd.build_mesh(traffic["mesh"], devices=jax.devices()[:1])
    job = manifest.load_job(config["job"]).build(config, traffic, 1)
    reference = manifest.load_reference(config["reference"])

    def make(seed):     # benchmark/run.py's keys; the optimizer's state is not made
        k_state, k_sample, *_ = jax.random.split(jax.random.key(seed), 2 + traffic["pool"])
        return job.init_state(k_state)[0], job.make_batch(k_sample, job.sample_rows)

    params, sample = jax.jit(make, out_shardings=(
        NamedSharding(mesh, P()), NamedSharding(mesh, P(mesh.axis_names))))(np.uint32(seed % 2 ** 32))
    for name in names or list(versions(job)):
        jax.clear_caches()
        try:
            line = judge(job, reference, config, mesh, (params, None), sample, name)
        except Exception as error:      # one version's failure is a line, not the end of the table
            line = {"version": name, "error": repr(error)[:1500]}
        print(json.dumps({"seed": seed, **line}), flush=True)


if __name__ == "__main__":
    main()
