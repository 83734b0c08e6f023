"""Whether the limits of ``kimi-linear-48b-a3b``'s comparison tell the job from wrong versions of it.

On the chip, at the cell's size (published widths, one sequence of 8,192 tokens), each named version of
``benchmark/jobs/kda_moe_lm.py``'s job, alone, goes through ``benchmark/compare.against_reference`` under the
limits of the configuration's own file (``checks.reference``), on the seed's initial parameters and sample as
``benchmark/run.py`` makes them:

    python3 tools/kimi_linear_wrong_versions.py <seed> [version ...]

prints one JSON line a version, as ``tools/smallthinker_wrong_versions.py`` does (whose patches it shares):
``against_reference``'s readings and its two verdicts, the loss's distance, the limits, and ``correct``.
``right`` is the job as it is and has to be correct; every other version has to fail a limit.
``tests/benchmark/test_benchmark_kda.py`` holds the same table at a tiny size in float32 on the CPU."""
import json, os, sys
sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import jax, jax.numpy as jnp, numpy as np
from horovod_tpu.models import llama
from horovod_tpu.ops import kda
from smallthinker_wrong_versions import _float8, _patched, _with_model, judge as _judge

CELL = "kimi-linear-48b-a3b.train-s8k-b2"


def _scalar_decay(rule):
    """The channels' log-decays replaced by their mean a head: the scalar rule (``ops/gated_delta.py``'s),
    through the same bodies."""
    return lambda q, k, v, g, beta: rule(
        q, k, v, jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape), beta)


def _decay_behind(rule):
    """``S_t = (S_{t-1} (I - beta k k^T) + beta v k^T) Diag(a_t)``: the decay BEHIND the delta step.  With
    ``R_t = S_t Diag(a_t)^-1`` that is the right rule under the decay of the step before, read by
    ``a_t (x) q_t``."""
    def wrong(q, k, v, g, beta):
        before = jnp.concatenate([jnp.zeros_like(g[:, :1]), g[:, :-1]], axis=1)
        return rule(q * jnp.exp(g).astype(q.dtype), k, v, before, beta)
    return wrong


def _silu_gate(norm_gated):
    return lambda *args, sigmoid=False, **kwargs: norm_gated(*args, **kwargs)


def _no_l2_norm(convolved):
    """``ops/short_conv.py::convolved`` with the L2 norm of q and k left out: the filter and its SiLU alone
    (what v gets), then q's scale."""
    return lambda y, taps, heads, scale, in_place: (
        convolved(y, taps, heads, None, in_place) if scale is None else
        (convolved(y, taps, heads, None, in_place) * scale).astype(y.dtype))


def versions(job) -> dict:
    """name -> a maker of the version's ``loss_fn``; ``right`` is the job's own."""
    return {
        "right": lambda: type(job).loss_fn.__get__(job),
        "scalar_decay_a_head": lambda: _patched(job, kda, "kda_rule", _scalar_decay),
        "decay_behind_the_delta_step": lambda: _patched(job, kda, "kda_rule", _decay_behind),
        "silu_for_the_sigmoid_output_gate": lambda: _patched(job, llama, "norm_gated", _silu_gate),
        "l2_norm_of_q_and_k_left_out": lambda: _patched(job, llama, "convolved", _no_l2_norm),
        "latent_lanes_rotated": lambda: _with_model(job, mla_use_nope=False),
        "gates_not_renormalised": lambda: _with_model(job, norm_topk_prob=False),
        "gates_without_their_2.446": lambda: _with_model(job, routed_scaling_factor=1.0),
        "float8_e4m3": lambda: _float8(job, jnp.float8_e4m3fn),
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
