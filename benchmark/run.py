"""Run one cell of ``BENCHMARK.json`` once, in one process that holds the
cell's chips.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up is five phases on the host clock, which add up to ``setup_s`` and
hold nothing the measured job does not need: imports; the backend coming
up, ``hvd.init()`` and the mesh; one jitted program that makes parameters,
optimizer state and the batch pool from the seed, already placed on the
mesh; the train step compiled ahead of time; a fixed number of blocked
warm-up steps.  Every program is served by JAX's persistent cache after a
cell's first run in a checkout.  Then the window: the loop keeps one step
in flight (dispatch step i+1, fetch the loss of step i, stamp the clock).
Peak memory is read when the window ends; the optimizer state is freed;
and only then are loss and gradient compared with the plain reference, on
the parameters the configuration names (the live ones, or the initial ones
made again from the seed).  The last line of standard output is the result.

Nothing here names a configuration, a traffic mix or a metric:
``benchmark/manifest.py`` finds their files by the names in the manifest.
"""

import time

T0 = time.perf_counter()       # the first line of the entry module that runs

import argparse                # noqa: E402
import json                    # noqa: E402
import math                    # noqa: E402
import os                      # noqa: E402
import shutil                  # noqa: E402
import statistics              # noqa: E402
import sys                     # noqa: E402

from benchmark import manifest  # noqa: E402

# The TPU runtime pins a host staging buffer when it comes up: 4 GiB by
# default, which on a host without transparent hugepages took 7 to 12 s of
# every run and all of the set-up's noise (PERF.md, set-up study).  These
# jobs move one scalar a step to the host, so a run pins 256 MiB instead.
# Read by libtpu when JAX first asks for its devices; a value already in
# the environment stands.
os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(256 * 1024 * 1024))

WARMUP_STEPS = 3               # blocked steps before the window, every run
TRACE_SECONDS = 4.0            # of the window, in a --trace 1 run
TRACE_DIR = os.path.join(manifest.ROOT, ".bench_trace")
DISPATCH, WAIT_LOSS = HOST_SPANS = ("dispatch", "wait_loss")


def say(message: str) -> None:
    print(f"[benchmark] {message}", flush=True)


class Phases:
    """Consecutive named stretches of the host clock, from ``T0``."""

    def __init__(self, start: float):
        self._last = start
        self.seconds: dict = {}

    def end(self, name: str) -> float:
        now = time.perf_counter()
        self.seconds[name] = now - self._last
        self._last = now
        return now

    def total(self) -> float:
        return sum(self.seconds.values())


class CompileEvents:
    """JAX's own monitoring events: requests to the persistent cache, its
    hits, and programs the backend compiled."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"
    COMPILED = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.requests = self.hits = self.compiled = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == self.REQUEST:
            self.requests += 1
        elif event == self.HIT:
            self.hits += 1

    def _on_duration(self, event, _seconds, **_):
        if event == self.COMPILED:
            self.compiled += 1

    def snapshot(self) -> tuple:
        return self.requests, self.hits, self.compiled


def parse_args(argv):
    parser = argparse.ArgumentParser(prog="python -m benchmark.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return parser.parse_args(argv)


def peak_bytes(devices) -> int:
    """Peak on the fullest chip.  The runtime books a program's
    temporaries as reserved, apart from the live buffers in use
    (PERF.md, finding 9 of PR 21)."""
    peaks = []
    for device in devices:
        stats = device.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return max(peaks)


def set_up(args, cell: dict, phases: Phases, allow_cpu: bool):
    """The five phases.  Returns what the window needs, as a namespace."""
    config, traffic, chips = cell["config"], cell["traffic"], cell["chips"]

    # -- import_s -------------------------------------------------------
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu.jax as hvd
    from benchmark import compare, trace  # noqa: F401  (paid for here)
    job_module = manifest.load_job(config["job"])
    reference = manifest.load_reference(config["reference"])
    imported = phases.end("import_s")

    # -- backend_s ------------------------------------------------------
    # Every program goes to the persistent cache, however quickly it
    # compiled: JAX's defaults leave out what compiles in under a second,
    # which then compiles again in every run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    events = CompileEvents(jax)
    devices = jax.devices()
    devices_s = time.perf_counter() - imported
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not allow_cpu:
        sys.exit(f"benchmark.run measures the chip and found platform "
                 f"{device['platform']!r} ({device['kind']}, "
                 f"{device['count']} device(s)); a CPU run gives no rate")
    if len(devices) < chips:
        sys.exit(f"workload {cell['name']} asks for {chips} chip(s) and JAX "
                 f"found {len(devices)}")
    peaks = manifest.peaks(device["kind"]) if on_tpu else None
    hvd.init()
    mesh = hvd.build_mesh(traffic["mesh"], devices=devices[:chips])
    if mesh.size != chips:
        sys.exit(f"mesh {dict(mesh.shape)} does not span {chips} chip(s)")
    phases.end("backend_s")
    say(f"backend_s: {devices_s:.3f} s until JAX had its devices (the TPU "
        f"runtime coming up), the rest hvd.init() and the mesh")

    # -- state_s: one program, placed on the mesh, from the seed --------
    job = job_module.build(config, traffic, chips)
    replicated = NamedSharding(mesh, P())
    batch_sharded = NamedSharding(mesh, P(mesh.axis_names))

    def make_state(seed):
        k_state, k_sample, *k_pool = jax.random.split(
            jax.random.key(seed), 2 + traffic["pool"])
        return (job.init_state(k_state),
                tuple(job.make_batch(k) for k in k_pool),
                job.make_batch(k_sample, job.sample_rows))

    make_state = jax.jit(
        make_state, out_shardings=(replicated, batch_sharded, batch_sharded))
    seed = np.uint32(args.seed % 2 ** 32)
    state, pool, sample = make_state(seed)
    jax.block_until_ready((state, pool, sample))
    phases.end("state_s")

    # -- compile_s: the step, ahead of time, alone on the clock ---------
    step = hvd.make_train_step(job.loss_fn, job.optimizer, mesh,
                               has_aux=job.has_aux)
    compiled = step.lower(*state, pool[0]).compile()
    phases.end("compile_s")

    # -- warmup_s: the same few blocked steps in every run --------------
    warmup_losses = []
    for i in range(WARMUP_STEPS):
        *state, loss = step(*state, pool[i % len(pool)])
        warmup_losses.append(float(loss))
    phases.end("warmup_s")
    requests, hits, _ = events.snapshot()
    say("set-up " + " ".join(f"{k}={v:.3f}" for k, v in
                             phases.seconds.items())
        + f" setup_s={phases.total():.3f}; persistent cache at "
        f"{jax.config.jax_compilation_cache_dir}: {requests} requests, "
        f"{hits} hits")
    if requests - hits:
        say(f"WARNING: {requests - hits} program(s) of the set-up were not "
            f"in the persistent cache (expected in a cell's first run in a "
            f"checkout, and in no other)")
    return argparse.Namespace(
        jax=jax, job=job, reference=reference, mesh=mesh, devices=devices,
        device=device, peaks=peaks, events=events, step=step,
        compiled=compiled, state=state, pool=pool, sample=sample,
        make_state=lambda: make_state(seed),
        warmup_losses=warmup_losses, cache_misses_in_setup=requests - hits)


def measure_window(args, up) -> argparse.Namespace:
    """Keep one step in flight for ``--seconds``: dispatch step i+1, fetch
    the loss of step i, stamp the clock.  A traced run has the profiler on
    for the window's first ``TRACE_SECONDS``."""
    jax, step, pool, state = up.jax, up.step, up.pool, up.state
    tracing = bool(args.trace)
    if tracing:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    span = jax.profiler.TraceAnnotation

    def compile_count():
        _, hits, compiled = up.events.snapshot()
        return step._cache_size() + hits + compiled

    compiles_before = compile_count()
    stamps, losses, dispatch_ms = [], [], []
    attempted = failed = clean_from = 0
    pending = None
    i = WARMUP_STEPS
    window_start = time.perf_counter()
    while True:
        now = time.perf_counter()
        if tracing and now - window_start >= min(TRACE_SECONDS,
                                                 args.seconds):
            if pending is not None:
                pending.block_until_ready()
            jax.profiler.stop_trace()
            tracing = False
            # Writing the trace out stalls the loop: a traced run takes
            # its rate and its intervals from the steps after the stall.
            clean_from = len(stamps) + 2
        if now - window_start >= args.seconds:
            break
        attempted += 1
        try:
            with span(DISPATCH):
                *state, loss = step(*state, pool[i % len(pool)])
        except Exception as error:      # the run goes on to report it
            say(f"step {attempted} raised {error!r}")
            failed += 1
            break
        dispatch_ms.append((time.perf_counter() - now) * 1e3)
        i += 1
        if pending is not None:
            with span(WAIT_LOSS):
                losses.append(float(pending))
            stamps.append(time.perf_counter())
        pending = loss
    if pending is not None:
        losses.append(float(pending))
        stamps.append(time.perf_counter())
    if tracing:
        jax.profiler.stop_trace()
    compiles_in_window = compile_count() - compiles_before
    memory_peak_bytes = peak_bytes(up.devices[:up.mesh.size])
    failed += sum(not math.isfinite(x) for x in losses)

    if len(stamps) - clean_from < 3:
        if len(stamps) < 3:
            sys.exit(f"the window of {args.seconds} s completed "
                     f"{len(stamps)} step(s): too short to measure")
        clean_from = 0
    stamps = stamps[clean_from:]
    intervals_ms = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    say(f"window: {attempted} steps dispatched, {len(losses)} completed, "
        f"{len(intervals_ms)} intervals between completions, median "
        f"{statistics.median(intervals_ms):.3f} ms, losses "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    up.state = state
    return argparse.Namespace(
        attempted=attempted, failed=failed, losses=losses,
        intervals_ms=intervals_ms, dispatch_ms=dispatch_ms,
        steps_per_s=(len(stamps) - 1) / (stamps[-1] - stamps[0]),
        compiles_in_window=compiles_in_window,
        memory_peak_bytes=memory_peak_bytes)


def check(cell: dict, up, window) -> dict:
    """What decides ``correct``.  Behind the window and the peak reading:
    the optimizer state is freed first, so the comparison is in neither.
    The configuration's file says on which parameters loss and gradient
    are compared with the reference: the ``live`` ones the window left, or
    the ``initial`` ones, made again from the seed by the set-up's own
    program once the live state is freed (there is no room for both)."""
    from benchmark import compare

    config, job = cell["config"], up.job
    leaves = up.jax.tree.leaves
    started = time.perf_counter()
    parameters = config["checks"]["reference"]["parameters"]
    if parameters == "initial":
        for leaf in leaves((up.state, up.pool, up.sample)):
            leaf.delete()
        up.state, up.pool, up.sample = up.make_state()
        for leaf in leaves(up.pool):
            leaf.delete()
    elif parameters != "live":
        raise ValueError(f"checks.reference.parameters is {parameters!r}: "
                         f"'initial' or 'live'")
    for leaf in leaves(up.state[1]):
        leaf.delete()
    checks = compare.against_reference(job, up.reference, config, up.mesh,
                                       up.state, up.sample)
    say(f"reference comparison on the {parameters} parameters took "
        f"{time.perf_counter() - started:.3f} s, behind the window")
    expected = job.expected_first_loss()
    tolerance = config["checks"]["first_loss_tolerance"]
    n_pool = len(up.pool)
    head, tail = window.losses[:n_pool], window.losses[-n_pool:]
    checks.update({
        "losses_finite": all(map(math.isfinite,
                                 up.warmup_losses + window.losses)),
        "no_compile_in_window": window.compiles_in_window == 0,
        "first_loss_as_expected":
            abs(up.warmup_losses[0] - expected) < tolerance,
        # Medians over one pass of the pool: AdamW without a warm-up on
        # random tokens spikes now and then, and one spike is not a rise.
        "loss_fell": (not config["checks"]["loss_must_fall"]
                      or statistics.median(tail) < statistics.median(head)),
    })
    say(f"checks: first loss {up.warmup_losses[0]:.4f} (expected "
        f"{expected:.4f} +- {tolerance}), median of the window's first "
        f"{len(head)} losses {statistics.median(head):.4f}, of its last "
        f"{statistics.median(tail):.4f}; " + json.dumps(checks))
    return checks


def run(args, *, start: float = T0, overrides: dict | None = None,
        allow_cpu: bool = False) -> dict:
    """Run the cell and return the result object.  ``overrides`` and
    ``allow_cpu`` are for the repository's tests, which run every cell at
    a tiny size on the CPU; the command line cannot reach them."""
    cell = manifest.cell(args.workload)
    for part, changes in (overrides or {}).items():
        cell[part] = {**cell[part], **changes}
    phases = Phases(start)
    up = set_up(args, cell, phases, allow_cpu)
    window = measure_window(args, up)

    # What the metric readers see (benchmark/metrics/<name>.py).
    job, chips = up.job, cell["chips"]
    ctx = {
        "cell": cell, "chips": chips, "peaks": up.peaks,
        "device": up.device, "phases": dict(phases.seconds),
        "setup_s": phases.total(),
        "cache_misses_in_setup": up.cache_misses_in_setup,
        "compiles_in_window": window.compiles_in_window,
        "intervals_ms": window.intervals_ms,
        "dispatch_ms": window.dispatch_ms,
        "units_per_s_per_chip":
            window.steps_per_s * job.units_per_step / chips,
        "memory_peak_bytes": window.memory_peak_bytes,
        "job": {"unit": job.unit, "units_per_step": job.units_per_step,
                "flops_per_unit": job.flops_per_unit(),
                "kernel_work_per_step": job.kernel_work_per_step()},
        "compiled": None, "trace": None,
    }
    if args.trace:
        from benchmark import trace

        memory = up.compiled.memory_analysis()
        ctx["compiled"] = {
            "argument_bytes": memory.argument_size_in_bytes,
            "temporary_bytes": memory.temp_size_in_bytes,
            "collectives": trace.collectives_in_hlo(up.compiled.as_text()),
        }
        if up.peaks is not None:          # a CPU trace has no device plane
            ctx["trace"] = trace.reduce_trace(trace.find_xplane(TRACE_DIR),
                                              host_spans=HOST_SPANS)
    del up.compiled
    checks = check(cell, up, window)

    wanted = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = {}
    for entry in wanted:
        value = manifest.load_reader(entry["name"])(ctx)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    result = {
        "correct": all(v for v in checks.values() if isinstance(v, bool)),
        "attempted": window.attempted, "failed": window.failed,
        "metrics": metrics,
        "device": {**up.device,
                   "memory_peak_bytes": window.memory_peak_bytes},
        "setup_s": ctx["setup_s"], "intervals": len(window.intervals_ms),
        "checks": checks,
    }
    if ctx["trace"] is not None:
        result["device"]["busy_s"] = ctx["trace"]["busy_s"]
        result["device"]["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = ctx["trace"]["breakdown"]
    return result


def main(argv=None) -> None:
    result = run(parse_args(argv))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
