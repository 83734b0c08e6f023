"""Whether the limits of ``xing4.0-29b-a4b``'s comparison tell the job from wrong versions of it.

On the chip, at the cell's size (published widths, one sequence of 8,192 tokens in four streams), each named
version of ``benchmark/jobs/hc_moe_lm.py``'s job, alone, goes through ``benchmark/compare.against_reference``
under the limits of the configuration's own file (``checks.reference``), on the seed's initial parameters
and sample as ``benchmark/run.py`` makes them:

    python3 tools/xing4_wrong_versions.py <seed> [version ...]

prints one JSON line a version, as ``tools/smallthinker_wrong_versions.py`` does (whose patches it shares):
``against_reference``'s readings and its two verdicts, the loss's distance, the limits, and ``correct``.
``right`` is the job as it is and has to be correct; every other version has to fail a limit, or the
configuration's file says which cannot and what holds it.  ``tests/benchmark/test_benchmark_hc.py`` holds the
same table at a tiny size in float32 on the CPU."""
import json, os, sys
sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import jax, jax.numpy as jnp, numpy as np
from horovod_tpu.models import llama
from smallthinker_wrong_versions import _float8, _patched, _with_model, judge as _judge

CELL = "xing4.0-29b-a4b.train-s8k"


def _write(change):
    """``llama._hc_write`` handed ``change(h_post, h_res)`` in place of the two maps."""
    return lambda write: lambda x, y, h_post, h_res: write(x, y, *change(h_post, h_res))


def _identity(h_res):
    return jnp.broadcast_to(jnp.eye(h_res.shape[0], dtype=h_res.dtype)[:, :, None], h_res.shape)


def _no_query_norm(norm):
    """``llama.RMSNorm`` but for the one named ``q_norm``, which passes its input on (its scale stays a
    leaf, unread)."""
    return lambda *args, name=None, **kwargs: (
        (lambda x: x) if name == "q_norm" else norm(*args, name=name, **kwargs))


def versions(job) -> dict:
    """name -> a maker of the version's ``loss_fn``; ``right`` is the job's own."""
    return {
        "right": lambda: type(job).loss_fn.__get__(job),
        "one_sinkhorn_step": lambda: _with_model(job, hc_sinkhorn_iters=1),
        "h_post_without_its_2": lambda: _patched(
            job, llama, "_hc_write", _write(lambda h_post, h_res: (h_post / 2, h_res))),
        "h_res_the_identity": lambda: _patched(
            job, llama, "_hc_write", _write(lambda h_post, h_res: (h_post, _identity(h_res)))),
        "q_norm_left_out": lambda: _patched(job, llama, "RMSNorm", _no_query_norm),
        "gates_not_renormalised": lambda: _with_model(job, norm_topk_prob=False),
        "gates_without_their_2": lambda: _with_model(job, routed_scaling_factor=1.0),
        "float8_e4m3": lambda: _float8(job, jnp.float8_e4m3fn),
        "float8_e5m2": lambda: _float8(job, jnp.float8_e5m2),
    }


def judge(*args) -> dict:
    """``compare.against_reference``'s line for a version of the job, with ``correct``."""
    return _judge(*args, table=versions)


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
        params, _, bias = job.init_state(k_state)
        return params, bias, job.make_batch(k_sample, job.sample_rows)

    params, bias, sample = jax.jit(make, out_shardings=(
        NamedSharding(mesh, P()), NamedSharding(mesh, P()),
        NamedSharding(mesh, P(mesh.axis_names))))(np.uint32(seed % 2 ** 32))
    for name in names or list(versions(job)):
        jax.clear_caches()
        try:
            line = judge(job, reference, config, mesh, (params, None, bias), sample, name)
        except Exception as error:      # one version's failure is a line, not the end of the table
            line = {"version": name, "error": repr(error)[:1500]}
        print(json.dumps({"seed": seed, **line}), flush=True)


if __name__ == "__main__":
    main()
