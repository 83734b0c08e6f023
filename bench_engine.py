"""Host-engine data-plane benchmark: throughput of the TCP ring engine
under the torch and TF frontends at 2 and 4 ranks.

Role parity with the reference's benchmark methodology
(``examples/pytorch_synthetic_benchmark.py:96-110`` — timed fwd+bwd+step
loops, img/sec), applied to the part of THIS stack the chip's benchmark
(``benchmark/``) does not exercise: the native TCP engine serving the host frontends
(torch hooks, TF grouped allreduce).  The numbers are CPU-host numbers by
design — they track frontend + negotiation + ring-collective overhead,
so hot-path regressions (e.g. a fusion/batching break) become visible as
throughput drops.

The TF step loop runs twice per world size — negotiation response cache
ON (the default) and OFF (``HOROVOD_CACHE_CAPACITY=0``) — and reports
``control_round_trips_per_step`` alongside step time, so the control
plane's contribution is separable from the data plane's.

An allreduce size sweep (4 KB → 64 MB, 2 and 4 ranks) additionally
reports the data plane's bus bandwidth (NCCL convention:
``2(N-1)/N · bytes / wall``, wall from the native engine's own
``allreduce_ns`` counter so Python overhead is excluded) with the
multi-channel fan-out (``HOROVOD_NUM_CHANNELS=4``) and with the
single-channel legacy path (``..._1ch``), plus the small-allreduce
latency at 2 ranks on the single-channel path (the PR 2 control-plane
number, guarded against regression).

Prints ONE JSON line, e.g.::

    {"metric": "engine_data_plane", "torch_img_per_sec": {"2": ..,
     "4": ..}, "tf_img_per_sec": {"2": .., "4": ..},
     "tf_step_ms": {"2": .., "4": ..},
     "tf_step_ms_nocache": {"2": .., "4": ..},
     "control_round_trips_per_step": {"2": .., "4": ..},
     "control_round_trips_per_step_nocache": {"2": .., "4": ..},
     "allreduce_bus_bw_mb_s": {"2": {"4KB": .., ..}, "4": {..}},
     "allreduce_bus_bw_mb_s_1ch": {"2": {..}, "4": {..}},
     "allreduce_bus_bw_mb_s_shm": {"2": {..}, "4": {..}},
     "allreduce_small_latency_ms": {"2": ..},
     "allreduce_small_latency_ms_shm": {"2": ..},
     "algo_threshold_sweep": {"256B": {"star": .., "ring": ..}, ..},
     "allreduce_effective_bus_bw_mb_s_fp32": {"2": {..}, "4": {..}},
     "allreduce_effective_bus_bw_mb_s_fp16": {..},
     "allreduce_effective_bus_bw_mb_s_int8": {..},
     "wire_bytes_ratio_fp16": {"2": {..}, "4": {..}},
     "wire_bytes_ratio_int8": {"2": {..}, "4": {..}}}

The wire sweep (``HOROVOD_WIRE_DTYPE`` compression) reports EFFECTIVE
bus bandwidth — logical pre-compression bytes over wall time, since
``allreduce_bytes`` counts logical payload by design — plus the
deterministic per-rank ``data_bytes_tx`` ratio vs the fp32 wire, which
is what the ci compression gate judges (wall time on this loopback-
ceilinged box is noise; byte counters are exact).

The TCP-plane keys (``allreduce_bus_bw_mb_s``/``_1ch`` and
``allreduce_small_latency_ms``) pin ``HOROVOD_SHM_DISABLE=1`` so they
stay comparable with the pre-shm trajectory; the ``_shm`` variants
measure the default plane (shm flat ring + size-based algorithm
selection), and ``algo_threshold_sweep`` interleaves the star and ring
paths per payload size so the crossover is visible.

Use: ``python bench_engine.py`` prints the keys as one JSON line.

``python bench_engine.py --gate`` runs the CI data-plane gate instead:
one 4-rank worker set alternates channels=4 / channels=1 in-process
(shutdown + re-init between rounds, so slow machine drift hits both
configs equally) on 16 MB allreduces and fails loudly when the median
bandwidth ratio falls below the gate threshold.  ``--shm-gate`` is the
shm analogue: alternate shm on / off in-process on the small-allreduce
latency (2 ranks) and 16 MB bus bandwidth (4 ranks), judged as a
regression floor on the best interleaved round.
"""

from __future__ import annotations

import json
import os
import re
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# TF worker (run as: bench_engine.py --tf-worker)
# ---------------------------------------------------------------------------

def _tf_worker() -> None:
    """MNIST-shaped training step over DistributedGradientTape: every
    dense gradient rides the grouped single-cycle allreduce
    (``horovod_tpu/tf/mpi_ops.py``)."""
    import numpy as np
    import tensorflow as tf

    sys.path.insert(0, REPO)
    import horovod_tpu.tf as hvd

    hvd.init()
    tf.keras.utils.set_random_seed(1 + hvd.rank())
    model = tf.keras.Sequential([
        tf.keras.layers.Dense(128, activation="relu"),
        tf.keras.layers.Dense(10),
    ])
    model(tf.zeros([1, 784]))
    hvd.broadcast_variables(model.trainable_variables, root_rank=0)
    opt = tf.keras.optimizers.SGD(0.01 * hvd.size())
    batch = 32
    rng = np.random.default_rng(7 + hvd.rank())
    X = tf.constant(rng.standard_normal((batch, 784)), dtype=tf.float32)
    Y = tf.constant(rng.integers(0, 10, batch), dtype=tf.int64)

    def step():
        with hvd.DistributedGradientTape(tf.GradientTape()) as tape:
            logits = model(X)
            loss = tf.reduce_mean(
                tf.nn.sparse_softmax_cross_entropy_with_logits(
                    labels=Y, logits=logits))
        grads = tape.gradient(loss, model.trainable_variables)
        opt.apply_gradients(zip(grads, model.trainable_variables))

    for _ in range(3):
        step()
    from horovod_tpu.runtime import engine_or_none

    eng = engine_or_none()
    iters = int(os.environ.get("HOROVOD_SMOKE_STEPS", "30"))
    before = eng.stats() if eng is not None else {}
    t0 = time.perf_counter()
    for _ in range(iters):
        step()
    dt = time.perf_counter() - t0
    after = eng.stats() if eng is not None else {}
    rt_per_step = (after.get("control_round_trips", 0)
                   - before.get("control_round_trips", 0)) / iters
    # Priority-scheduling instrument: inversions per step over the
    # measured window (0 by construction with HOROVOD_PRIORITY_BANDS on;
    # the legacy arrival ordering's count under HOROVOD_PRIORITY_STAMP=1
    # is the motivation metric).
    inv_per_step = (after.get("priority_inversions", 0)
                    - before.get("priority_inversions", 0)) / iters
    if hvd.rank() == 0:
        print(f"TF_STEP_MS {dt / iters * 1e3:.3f} "
              f"TF_IMG_PER_SEC {batch * hvd.size() * iters / dt:.1f} "
              f"TF_RT_PER_STEP {rt_per_step:.2f} "
              f"TF_PRIO_INV_PER_STEP {inv_per_step:.3f}",
              flush=True)
    hvd.shutdown()


# ---------------------------------------------------------------------------
# allreduce sweep / latency / gate workers (numpy + native engine only)
# ---------------------------------------------------------------------------

def _engine_setup():
    sys.path.insert(0, REPO)
    import numpy as np  # noqa: F401

    from horovod_tpu.common.basics import basics
    from horovod_tpu.runtime.engine import get_engine

    basics.init()
    return basics, get_engine()


def _measure_bus_bw_mb_s(basics, eng, nbytes: int, iters: int) -> float:
    """Bus bandwidth over `iters` allreduces from the engine's own
    allreduce byte/wall counters (NCCL busbw convention), via the
    stats_delta helper the autotuner scores trials with."""
    import numpy as np

    n = max(1, nbytes // 4)
    x = np.ones(n, dtype=np.float32)
    eng.allreduce(x.copy(), name="sweep.warm")
    before = eng.stats()
    for i in range(iters):
        eng.synchronize(eng.enqueue_allreduce(x.copy(), name="sweep.t"))
    return eng.stats_delta(before)["allreduce_bus_bw_bytes_per_sec"] / 1e6


def _sweep_worker() -> None:
    basics, eng = _engine_setup()
    nbytes = int(os.environ["BENCH_SWEEP_BYTES"])
    iters = max(2, min(30, (32 << 20) // max(nbytes, 1)))
    bw = _measure_bus_bw_mb_s(basics, eng, nbytes, iters)
    if basics.rank() == 0:
        print(f"SWEEP_BUS_MB_S {bw:.1f}", flush=True)
    basics.shutdown()


def _fleet_worker() -> None:
    """Fleet-telemetry snapshot source for the BENCH json: a short
    4-rank workload with per-cycle TELEM, quiesced so the fleet table
    converges, then rank 0 prints the table (the soak trend artifacts
    of ROADMAP item 5 ride these `fleet_` keys)."""
    import json as _json
    import time as _time

    import numpy as np

    basics, eng = _engine_setup()
    x = np.ones(1 << 16, dtype=np.float32)
    for i in range(12):
        eng.allreduce(x.copy(), name=f"fleet.t{i % 3}")
    eng.allreduce(np.ones(4, dtype=np.float32), name="fleet.barrier")
    _time.sleep(1.0)  # idle cycles flush the final TELEM deltas
    if basics.rank() == 0:
        _time.sleep(0.3)
        print("FLEET_SNAPSHOT " + _json.dumps(basics.fleet_stats()),
              flush=True)
    else:
        _time.sleep(0.5)
    basics.shutdown()


def _rs_sweep_worker() -> None:
    """Reduce-scatter bus bandwidth ((N-1)/N · bytes / wall — half the
    allreduce numerator, matching the RS wire pattern) from the
    engine's deterministic reducescatter counters."""
    import numpy as np

    basics, eng = _engine_setup()
    nbytes = int(os.environ["BENCH_SWEEP_BYTES"])
    n = max(1, nbytes // 4)
    iters = max(2, min(30, (32 << 20) // max(nbytes, 1)))
    x = np.ones(n, dtype=np.float32)
    eng.reducescatter(x, name="rs.sweep.warm")
    before = eng.stats()
    for _ in range(iters):
        eng.synchronize(eng.enqueue_reducescatter(x, name="rs.sweep.t"))
    d = eng.stats_delta(before)
    if basics.rank() == 0:
        print(f"RS_SWEEP_BUS_MB_S "
              f"{d['reducescatter_bus_bw_bytes_per_sec'] / 1e6:.1f} "
              f"FALLBACKS {d['reducescatter_fallbacks']}", flush=True)
    basics.shutdown()


def _alltoall_sweep_worker() -> None:
    """Alltoall bus bandwidth ((N-1)/N · bytes / wall — each rank keeps
    its own block, so that's the fraction crossing the wire) from the
    engine's deterministic alltoall counters.  Equal splits: the sweep
    measures the transport, not the split negotiation (the variable-
    split cases are correctness-gated in the moe marker)."""
    import numpy as np

    basics, eng = _engine_setup()
    nbytes = int(os.environ["BENCH_SWEEP_BYTES"])
    size = basics.size()
    n = max(size, nbytes // 4 // size * size)  # divisible by the world
    iters = max(2, min(30, (32 << 20) // max(nbytes, 1)))
    x = np.ones(n, dtype=np.float32)
    eng.alltoall(x, name="a2a.sweep.warm")
    before = eng.stats()
    for _ in range(iters):
        eng.synchronize(eng.enqueue_alltoall(x, name="a2a.sweep.t"))
    d = eng.stats_delta(before)
    if basics.rank() == 0:
        print(f"A2A_SWEEP_BUS_MB_S "
              f"{d['alltoall_bus_bw_bytes_per_sec'] / 1e6:.1f}",
              flush=True)
    basics.shutdown()


def _sharded_bytes_worker() -> None:
    """Per-step wire accounting of the ZeRO sharded step vs the
    unsharded allreduce, on the deterministic byte counters: the
    gradient reduce-scatter (the gate metric, ~0.5x by construction)
    and the FULL step incl. the parameter allgather (~1.0x — the honest
    ZeRO number; memory, not bytes, is the lever)."""
    import numpy as np

    from horovod_tpu.runtime.sharded import FlatSharder

    basics, eng = _engine_setup()
    n = int(os.environ.get("BENCH_SHARDED_ELEMS", str(1 << 20)))
    sharder = FlatSharder(n, np.float32, name="bench.zero")
    g = np.ones(n, dtype=np.float32)
    # Warm both paths (wiring, fusion scratch).
    eng.allreduce(g.copy(), name="zb.warm")
    sharder.step(g, lambda s: s, average=True)
    steps = 4
    s0 = eng.stats()
    for _ in range(steps):
        eng.allreduce(g.copy(), average=True, name="zb.ar")
    ar_tx = eng.stats_delta(s0)["data_bytes_tx"]
    s1 = eng.stats()
    shard = None
    for _ in range(steps):
        shard = sharder.reduce_grads(g, average=True)
    rs_tx = eng.stats_delta(s1)["data_bytes_tx"]
    s2 = eng.stats()
    for _ in range(steps):
        sharder.gather_updates(shard)
    ag_tx = eng.stats_delta(s2)["data_bytes_tx"]
    if basics.rank() == 0:
        print(f"SHARDED_BYTES ar_tx {ar_tx} rs_tx {rs_tx} "
              f"ag_tx {ag_tx}", flush=True)
    basics.shutdown()


def _latency_worker() -> None:
    import numpy as np

    basics, eng = _engine_setup()
    x = np.ones(1, dtype=np.float32)
    for _ in range(5):
        eng.allreduce(x.copy(), name="lat.warm")
    iters = 100
    t0 = time.perf_counter()
    for _ in range(iters):
        eng.synchronize(eng.enqueue_allreduce(x.copy(), name="lat.t"))
    dt = time.perf_counter() - t0
    if basics.rank() == 0:
        print(f"LATENCY_MS {dt / iters * 1e3:.3f}", flush=True)
    basics.shutdown()


def _link_heal_bench_worker() -> None:
    """Busbw + heal-latency under a seeded flap schedule (the test's
    conn-reset fault kind, recurring): the run must complete with ZERO
    aborts while edges break and heal, and rank 0 reports the engine's
    link_heal percentiles next to the flap-loaded bus bandwidth."""
    import numpy as np

    basics, eng = _engine_setup()
    nbytes = int(os.environ.get("BENCH_SWEEP_BYTES", str(1 << 20)))
    n = max(1, nbytes // 4)
    x = np.ones(n, dtype=np.float32)
    eng.allreduce(x.copy(), name="link.warm")
    before = eng.stats()
    for _ in range(40):
        eng.synchronize(eng.enqueue_allreduce(x.copy(), name="link.t"))
    d = eng.stats_delta(before)
    st = eng.stats()
    assert eng.abort_reason() == "", eng.abort_reason()
    assert st["link_heal_failures"] == 0, st["link_heal_failures"]
    if basics.rank() == 0:
        print(f"LINK_BENCH BUS_MB_S "
              f"{d['allreduce_bus_bw_bytes_per_sec'] / 1e6:.1f} "
              f"HEAL_MS_P50 {st['link_heal_ns_p50'] / 1e6:.3f} "
              f"RECONNECTS {st['link_reconnects']}", flush=True)
    basics.shutdown()


def _gate_worker() -> None:
    """Alternate channels=4 / channels=1 IN-PROCESS (re-init between
    rounds) so machine drift hits both configs; print the per-round
    bandwidth pairs for the driver to judge."""
    basics, eng = _engine_setup()
    nbytes = 16 << 20
    rounds = int(os.environ.get("BENCH_GATE_ROUNDS", "3"))
    pairs = []
    for _ in range(rounds):
        os.environ["HOROVOD_NUM_CHANNELS"] = "4"
        basics.shutdown()
        basics.init()
        multi = _measure_bus_bw_mb_s(basics, eng, nbytes, 5)
        os.environ["HOROVOD_NUM_CHANNELS"] = "1"
        basics.shutdown()
        basics.init()
        single = _measure_bus_bw_mb_s(basics, eng, nbytes, 5)
        pairs.append((multi, single))
    if basics.rank() == 0:
        for multi, single in pairs:
            print(f"GATE_PAIR {multi:.1f} {single:.1f}", flush=True)
    basics.shutdown()


def _shm_gate_worker() -> None:
    """Alternate shm ON / shm OFF in-process (re-init between rounds, so
    ambient-load drift hits both transports): per round, the small-
    allreduce latency and/or the 16 MB bus bandwidth under each —
    BENCH_GATE_METRIC=lat|bw measures only the judged metric (the gate
    judges one per world size; measuring the other would double the
    wall time inside ci.sh's hard timeout).  The driver judges the
    pairs."""
    import numpy as np

    basics, eng = _engine_setup()
    metric = os.environ.get("BENCH_GATE_METRIC", "both")

    def lat_ms(iters=100):
        x = np.ones(1, dtype=np.float32)
        for _ in range(5):
            eng.allreduce(x.copy(), name="sg.w")
        t0 = time.perf_counter()
        for _ in range(iters):
            eng.synchronize(eng.enqueue_allreduce(x.copy(), name="sg.t"))
        return (time.perf_counter() - t0) / iters * 1e3

    def bw_mb_s():
        return _measure_bus_bw_mb_s(basics, eng, 16 << 20, 5)

    rounds = int(os.environ.get("BENCH_GATE_ROUNDS", "3"))
    pairs = []
    for _ in range(rounds):
        os.environ.pop("HOROVOD_SHM_DISABLE", None)
        basics.shutdown()
        basics.init()
        assert eng.stats()["config"]["shm_enabled"], "shm did not engage"
        s_lat = lat_ms() if metric != "bw" else 0.0
        s_bw = bw_mb_s() if metric != "lat" else 0.0
        os.environ["HOROVOD_SHM_DISABLE"] = "1"
        basics.shutdown()
        basics.init()
        t_lat = lat_ms() if metric != "bw" else 0.0
        t_bw = bw_mb_s() if metric != "lat" else 0.0
        pairs.append((s_lat, t_lat, s_bw, t_bw))
    if basics.rank() == 0:
        for s_lat, t_lat, s_bw, t_bw in pairs:
            print(f"SHM_GATE_PAIR lat {s_lat:.3f} {t_lat:.3f} "
                  f"bw {s_bw:.1f} {t_bw:.1f}", flush=True)
    basics.shutdown()


def _wire_sweep_worker() -> None:
    """One wire-dtype point of the compression sweep: EFFECTIVE bus
    bandwidth (logical pre-compression bytes over the engine's own wall
    counter — allreduce_bytes is logical by design, so the standard
    busbw computation already measures effectiveness) plus this rank's
    data_bytes_tx for the deterministic byte-ratio keys."""
    import numpy as np

    basics, eng = _engine_setup()
    nbytes = int(os.environ["BENCH_SWEEP_BYTES"])
    wd = os.environ.get("BENCH_WIRE_DTYPE", "fp32")
    iters = max(2, min(30, (32 << 20) // max(nbytes, 1)))
    n = max(1, nbytes // 4)
    x = np.ones(n, dtype=np.float32)
    eng.allreduce(x.copy(), name="wsweep.warm", wire_dtype=wd)
    before = eng.stats()
    for _ in range(iters):
        eng.synchronize(eng.enqueue_allreduce(x.copy(), name="wsweep.t",
                                              wire_dtype=wd))
    delta = eng.stats_delta(before)
    bw = delta["allreduce_bus_bw_bytes_per_sec"] / 1e6
    if basics.rank() == 0:
        print(f"WIRE_SWEEP_BUS_MB_S {bw:.1f} TX {delta['data_bytes_tx']}",
              flush=True)
    basics.shutdown()


def _wire_gate_worker() -> None:
    """CI compression-gate body: the DETERMINISTIC byte-counter ratio on
    a 16 MB fp32 allreduce — int8 wire vs fp32 wire data_bytes_tx — plus
    the counter sanity the gate asserts on.  Byte counters, not wall
    time: loopback is CPU-ceilinged and noisy (docs/performance.md), but
    the bytes a wire format moves are exact."""
    import numpy as np

    basics, eng = _engine_setup()
    n = (16 << 20) // 4
    x = np.ones(n, dtype=np.float32)
    s0 = eng.stats()
    out = eng.allreduce(x.copy(), name="wg.fp32")
    assert np.allclose(out, float(basics.size()))
    s1 = eng.stats()
    out = eng.allreduce(x.copy(), name="wg.int8", wire_dtype="int8")
    assert np.allclose(out, float(basics.size()), atol=1e-2)
    s2 = eng.stats()
    fp32_tx = s1["data_bytes_tx"] - s0["data_bytes_tx"]
    int8_tx = s2["data_bytes_tx"] - s1["data_bytes_tx"]
    assert s2["wire_int8_count"] - s1["wire_int8_count"] == 1, s2
    assert s2["compressed_bytes_tx"] > s1["compressed_bytes_tx"], s2
    if basics.rank() == 0:
        print(f"WIRE_GATE_TX fp32 {fp32_tx} int8 {int8_tx}", flush=True)
    basics.shutdown()


def _algo_sweep_worker() -> None:
    """Per-payload-size latency with the star path engaged (threshold
    above every size) vs disabled (pure ring), interleaved in-process:
    the table shows where the latency/bandwidth crossover actually sits
    on this host."""
    import numpy as np

    basics, eng = _engine_setup()
    sizes = [("256B", 256), ("4KB", 4 << 10), ("32KB", 32 << 10),
             ("256KB", 256 << 10)]

    def lat_ms(nbytes, iters=60):
        x = np.ones(max(1, nbytes // 4), dtype=np.float32)
        for _ in range(3):
            eng.allreduce(x.copy(), name="as.w")
        t0 = time.perf_counter()
        for _ in range(iters):
            eng.synchronize(eng.enqueue_allreduce(x.copy(), name="as.t"))
        return (time.perf_counter() - t0) / iters * 1e3

    rows = []
    for label, nbytes in sizes:
        os.environ["HOROVOD_ALGO_THRESHOLD"] = str(1 << 20)
        basics.shutdown()
        basics.init()
        star = lat_ms(nbytes)
        os.environ["HOROVOD_ALGO_THRESHOLD"] = "0"
        basics.shutdown()
        basics.init()
        ring = lat_ms(nbytes)
        rows.append((label, star, ring))
    if basics.rank() == 0:
        for label, star, ring in rows:
            print(f"ALGO_SWEEP {label} {star:.3f} {ring:.3f}", flush=True)
    basics.shutdown()


# ---------------------------------------------------------------------------
# autotune workers (online knob search; see docs/autotune.md)
# ---------------------------------------------------------------------------

def _converge_autotuner(basics, eng, step_bytes: int, max_steps: int = 5000):
    """Drive allreduce traffic until rank 0's tuner converges; the stop
    is broadcast-driven so every rank exits on the same step.  Returns
    rank 0's tuner (None elsewhere)."""
    import numpy as np

    from horovod_tpu.autotune import get_tuner

    tuner = get_tuner() if basics.rank() == 0 else None
    if basics.rank() == 0:
        assert tuner is not None, "HOROVOD_AUTOTUNE=1 did not start a tuner"
    x = np.ones(max(1, step_bytes // 4), dtype=np.float32)
    keep, steps = 1, 0
    while keep:
        eng.synchronize(eng.enqueue_allreduce(x.copy(), name="at.bench.t"))
        steps += 1
        if basics.rank() == 0:
            keep = 0 if (tuner.converged or steps >= max_steps) else 1
        flag = eng.broadcast(np.asarray([keep], dtype="int8"), root_rank=0,
                             name="at.bench.ctl")
        keep = int(flag[0])
    if basics.rank() == 0:
        assert tuner.converged, f"tuner did not converge in {steps} steps"
    return tuner


def _apply_config_all(basics, eng, cfg: dict, last_tt: int) -> int:
    """rank 0 queues a TUNE; EVERY rank waits for its own application
    (the frame lands on all ranks at the same cycle boundary), so the
    next measurement runs under the new config everywhere.  Returns the
    new tune_trials watermark."""
    if basics.rank() == 0:
        assert eng.autotune_set(
            chunk_bytes=cfg.get("chunk_bytes", 0),
            fusion_threshold=cfg.get("fusion_threshold", 0),
            cycle_time_ms=cfg.get("cycle_time_ms", 0),
            wave_width=cfg.get("wave_width", 0))
    deadline = time.time() + 20
    while eng.stats()["tune_trials"] <= last_tt:
        assert time.time() < deadline, "TUNE frame never applied"
        time.sleep(0.002)
    return eng.stats()["tune_trials"]


#: Static chunk-size grid the gate compares the committed config
#: against (the sweep dimension PR 4 measured the big busbw swings on).
_GATE_GRID = [256 << 10, 1 << 20, 4 << 20]


def _autotune_worker() -> None:
    """Bench body: converge the online search, then measure the committed
    config's 16 MB bus bandwidth (same methodology as the static sweep
    numbers it prints next to)."""
    import json as _json

    from horovod_tpu.autotune import stop_autotuner

    basics, eng = _engine_setup()
    tuner = _converge_autotuner(basics, eng, step_bytes=4 << 20)
    if basics.rank() == 0:
        # Freeze the regression watcher: an ambient-load dip during the
        # measurement could otherwise re-open the search and flip knobs
        # underneath it (the gate worker does the same).
        stop_autotuner()
    bw = _measure_bus_bw_mb_s(basics, eng, 16 << 20, 5)
    if basics.rank() == 0:
        print(f"AUTOTUNE_BUS_MB_S {bw:.1f} TRIALS {len(tuner.trace)} "
              f"CONFIG {_json.dumps(tuner.committed, sort_keys=True)}",
              flush=True)
    basics.shutdown()


def _autotune_gate_worker() -> None:
    """CI gate body: converge, stop the tuner (so the regression watcher
    cannot fight the measurement flips), then interleave rounds of the
    committed config against each static grid point — alternation means
    machine drift hits both sides equally, exactly like the data-plane
    gate."""
    import json as _json

    from horovod_tpu.autotune import stop_autotuner

    basics, eng = _engine_setup()
    tuner = _converge_autotuner(basics, eng, step_bytes=4 << 20)
    committed = dict(tuner.committed) if basics.rank() == 0 else None
    max_trials = int(os.environ.get("HOROVOD_AUTOTUNE_MAX_TRIALS", "32"))
    if basics.rank() == 0:
        assert len(tuner.trace) <= max_trials, (len(tuner.trace), max_trials)
        stop_autotuner()
    # Ship the committed config so every rank drives the same schedule.
    import numpy as np

    keys = ("chunk_bytes", "fusion_threshold", "cycle_time_ms",
            "wave_width")
    payload = np.zeros(len(keys), dtype=np.int64)
    if basics.rank() == 0:
        payload = np.asarray([committed.get(k, 0) for k in keys],
                             dtype=np.int64)
    got = eng.broadcast(payload, root_rank=0, name="at.gate.cfg")
    committed = {k: int(v) for k, v in zip(keys, got)}
    base = {k: int(v) for k, v in eng.stats()["config"].items()
            if k in keys}
    rounds = int(os.environ.get("BENCH_GATE_ROUNDS", "3"))
    nbytes = 16 << 20
    tt = eng.stats()["tune_trials"]
    for _ in range(rounds):
        # The committed config is sampled at BOTH ends of the round (the
        # statics sandwiched between): taking max-of-3 statics against a
        # single auto sample would bias the ratio down on a noisy box,
        # and a monotone drift (the box settling after the convergence
        # phase) would otherwise load entirely onto whichever side runs
        # first.
        tt = _apply_config_all(basics, eng, committed, tt)
        auto_bw = _measure_bus_bw_mb_s(basics, eng, nbytes, 4)
        static_bws = []
        for chunk in _GATE_GRID:
            tt = _apply_config_all(basics, eng, {**base,
                                                 "chunk_bytes": chunk}, tt)
            static_bws.append(_measure_bus_bw_mb_s(basics, eng, nbytes, 4))
        tt = _apply_config_all(basics, eng, committed, tt)
        auto_bw = max(auto_bw, _measure_bus_bw_mb_s(basics, eng, nbytes, 4))
        if basics.rank() == 0:
            print(f"AUTOGATE_ROUND auto={auto_bw:.1f} "
                  f"static_best={max(static_bws):.1f}", flush=True)
    if basics.rank() == 0:
        print(f"AUTOGATE_TRIALS {len(tuner.trace)} MAX {max_trials}",
              flush=True)
        print(f"AUTOGATE_CONFIG {_json.dumps(committed, sort_keys=True)}",
              flush=True)
    basics.shutdown()


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_ranks(n: int, argv: list, timeout: int = 240,
               extra_env: dict | None = None) -> str:
    """Run ``argv`` as n engine ranks; returns rank 0's stdout."""
    port = _free_port()
    procs = []
    for rank in range(n):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env.update({
            "HOROVOD_RANK": str(rank),
            "HOROVOD_SIZE": str(n),
            "HOROVOD_COORDINATOR": f"127.0.0.1:{port}",
            "CUDA_VISIBLE_DEVICES": "-1",
        })
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            argv, env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            outs.append((p.returncode, out.decode(), err.decode()))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for rank, (rc, out, err) in enumerate(outs):
        if rc != 0:
            raise RuntimeError(
                f"rank {rank} failed (rc={rc}):\n{out}\n{err}")
    return outs[0][1]


_TF_LINE = re.compile(r"TF_STEP_MS ([\d.]+) TF_IMG_PER_SEC ([\d.]+)"
                      r"(?: TF_RT_PER_STEP ([\d.]+))?"
                      r"(?: TF_PRIO_INV_PER_STEP ([\d.]+))?")


def main() -> None:
    result: dict = {"metric": "engine_data_plane"}
    torch_rates: dict = {}
    tf_rates: dict = {}
    tf_step_ms: dict = {}
    tf_step_ms_nocache: dict = {}
    rt_per_step: dict = {}
    rt_per_step_nocache: dict = {}
    for n in (2, 4):
        # No --smoke: it would force num_iters to 1, and these numbers
        # exist to catch regressions — keep the 3-sample mean the
        # example reports (its ±1.96σ methodology, ref :96-110).
        out = _run_ranks(n, [
            sys.executable,
            os.path.join(REPO, "examples", "torch_synthetic_benchmark.py"),
            "--batch-size", "16", "--num-iters", "3",
            "--num-batches-per-iter", "4",
        ])
        m = re.search(r"Total img/sec on \d+ device\(s\): ([\d.]+)", out)
        if m:
            torch_rates[str(n)] = float(m.group(1))

        # TF step loop, negotiation cache ON (default) and OFF — the
        # delta isolates the control plane's share of step time, and the
        # OFF run proves the legacy path still reproduces its numbers.
        for label, env, step_dict, rt_dict in (
                ("cache", {}, tf_step_ms, rt_per_step),
                ("nocache", {"HOROVOD_CACHE_CAPACITY": "0"},
                 tf_step_ms_nocache, rt_per_step_nocache)):
            out = _run_ranks(n, [sys.executable, os.path.abspath(__file__),
                                 "--tf-worker"], extra_env=env)
            m = _TF_LINE.search(out)
            if m:
                step_dict[str(n)] = float(m.group(1))
                if label == "cache":
                    tf_rates[str(n)] = float(m.group(2))
                if m.group(3) is not None:
                    rt_dict[str(n)] = float(m.group(3))
    result["torch_img_per_sec"] = torch_rates
    result["tf_img_per_sec"] = tf_rates
    result["tf_step_ms"] = tf_step_ms
    result["tf_step_ms_nocache"] = tf_step_ms_nocache
    result["control_round_trips_per_step"] = rt_per_step
    result["control_round_trips_per_step_nocache"] = rt_per_step_nocache

    # Priority scheduling: the SAME real-model loop with bands on
    # (engine_tf_step_ms_priority — judged as a regression floor in the
    # overlap gate) and, for the motivation metric, the legacy ordering
    # with stamping forced on so priority_inversions_per_step shows what
    # banding eliminates.
    tf_step_ms_priority: dict = {}
    inv_per_step: dict = {}
    for n in (2, 4):
        out = _run_ranks(n, [sys.executable, os.path.abspath(__file__),
                             "--tf-worker"],
                         extra_env={"HOROVOD_PRIORITY_BANDS": "1"})
        m = _TF_LINE.search(out)
        if m:
            tf_step_ms_priority[str(n)] = float(m.group(1))
        out = _run_ranks(n, [sys.executable, os.path.abspath(__file__),
                             "--tf-worker"],
                         extra_env={"HOROVOD_PRIORITY_STAMP": "1",
                                    "HOROVOD_FUSION_THRESHOLD": "0"})
        m = _TF_LINE.search(out)
        if m and m.group(4) is not None:
            inv_per_step[str(n)] = float(m.group(4))
    result["tf_step_ms_priority"] = tf_step_ms_priority
    result["priority_inversions_per_step"] = inv_per_step

    # Data-plane size sweep: bus bandwidth with the channel fan-out vs the
    # single-channel legacy path (both pinned to the TCP plane for
    # trajectory comparability) vs the default shm plane, 4 KB -> 64 MB
    # at 2 and 4 ranks.
    sweep: dict = {}
    sweep_1ch: dict = {}
    sweep_shm: dict = {}
    sizes = [("4KB", 4 << 10), ("64KB", 64 << 10), ("1MB", 1 << 20),
             ("16MB", 16 << 20), ("64MB", 64 << 20)]
    for n in (2, 4):
        for dest, env in ((sweep, {"HOROVOD_NUM_CHANNELS": "4",
                                   "HOROVOD_SHM_DISABLE": "1"}),
                          (sweep_1ch, {"HOROVOD_NUM_CHANNELS": "1",
                                       "HOROVOD_SHM_DISABLE": "1"}),
                          (sweep_shm, {"HOROVOD_NUM_CHANNELS": "4"})):
            per_size = dest.setdefault(str(n), {})
            for label, nbytes in sizes:
                out = _run_ranks(n, [sys.executable, os.path.abspath(__file__),
                                     "--sweep-worker"],
                                 extra_env={**env,
                                            "BENCH_SWEEP_BYTES": str(nbytes)})
                m = re.search(r"SWEEP_BUS_MB_S ([\d.]+)", out)
                if m:
                    per_size[label] = float(m.group(1))
    result["allreduce_bus_bw_mb_s"] = sweep
    result["allreduce_bus_bw_mb_s_1ch"] = sweep_1ch
    result["allreduce_bus_bw_mb_s_shm"] = sweep_shm

    # Reduce-scatter size sweep (the ZeRO gradient half) on the default
    # plane: RS bus bandwidth = (N-1)/N · bytes / wall — directly
    # comparable to the allreduce busbw above because both normalize to
    # per-link traffic.
    rs_sweep: dict = {}
    for n in (2, 4):
        per_size = rs_sweep.setdefault(str(n), {})
        for label, nbytes in sizes:
            out = _run_ranks(n, [sys.executable, os.path.abspath(__file__),
                                 "--rs-sweep-worker"],
                             extra_env={"BENCH_SWEEP_BYTES": str(nbytes)})
            m = re.search(r"RS_SWEEP_BUS_MB_S ([\d.]+)", out)
            if m:
                per_size[label] = float(m.group(1))
    result["reducescatter_bus_bw_mb_s"] = rs_sweep

    # Alltoall size sweep (the MoE dispatch/combine transport) on the
    # default plane and the single-channel TCP baseline: alltoall busbw
    # = (N-1)/N · bytes / wall, comparable to the RS busbw above.
    a2a_sweep: dict = {}
    a2a_sweep_1ch: dict = {}
    for n in (2, 4):
        for dest, env in ((a2a_sweep, {}),
                          (a2a_sweep_1ch, {"HOROVOD_NUM_CHANNELS": "1",
                                           "HOROVOD_SHM_DISABLE": "1"})):
            per_size = dest.setdefault(str(n), {})
            for label, nbytes in sizes:
                out = _run_ranks(n, [sys.executable, os.path.abspath(__file__),
                                     "--alltoall-sweep-worker"],
                                 extra_env={**env,
                                            "BENCH_SWEEP_BYTES": str(nbytes)})
                m = re.search(r"A2A_SWEEP_BUS_MB_S ([\d.]+)", out)
                if m:
                    per_size[label] = float(m.group(1))
    result["alltoall_bus_bw_mb_s"] = a2a_sweep
    result["alltoall_bus_bw_mb_s_1ch"] = a2a_sweep_1ch

    # ZeRO step wire accounting at 4 ranks, 4 MB flat model, on the
    # deterministic byte counters: grads_rs ~0.5 (the gated half),
    # full_step ~1.0 (RS + param allgather — the honest ZeRO total).
    out = _run_ranks(4, [sys.executable, os.path.abspath(__file__),
                         "--sharded-bytes-worker"])
    m = re.search(r"SHARDED_BYTES ar_tx (\d+) rs_tx (\d+) ag_tx (\d+)",
                  out)
    if m:
        ar_tx, rs_tx, ag_tx = (int(m.group(i)) for i in (1, 2, 3))
        result["sharded_step_bytes_ratio"] = {
            "grads_rs": round(rs_tx / max(1, ar_tx), 4),
            "full_step": round((rs_tx + ag_tx) / max(1, ar_tx), 4),
        }

    # ZeRO-3/FSDP residency + prefetch at 4 ranks, on the deterministic
    # counters: peak resident param bytes / total (the 1/N lever), and
    # the allgather-prefetch hit counters from the same run.
    fsdp_worker = os.path.join(REPO, "tests", "fsdp_worker.py")
    out = _run_ranks(4, [sys.executable, fsdp_worker, "mem"],
                     timeout=300,
                     extra_env={"HOROVOD_PRIORITY_BANDS": "1"})
    pairs = re.findall(r"FSDP_MEM rank=\d+ peak=(\d+) total=(\d+)", out)
    if pairs:
        result["fsdp_param_resident_peak_ratio"] = round(
            max(int(p) / max(1, int(t)) for p, t in pairs), 4)
    out = _run_ranks(2, [sys.executable, fsdp_worker, "overlap"],
                     timeout=300,
                     extra_env={"HOROVOD_PRIORITY_BANDS": "1"})
    m = re.search(r"FSDP_OVERLAP rank=\d+ on_ms=([\d.]+) "
                  r"off_ms=([\d.]+) inversions=(\d+) "
                  r"hits=(\d+) misses=(\d+)", out)
    if m:
        result["fsdp_forward_walk_ms_prefetch_on"] = float(m.group(1))
        result["fsdp_forward_walk_ms_prefetch_off"] = float(m.group(2))
        result["fsdp_ag_prefetch_hits"] = int(m.group(4))
        result["fsdp_ag_prefetch_misses"] = int(m.group(5))

    # Single-allreduce latency at 2 ranks: single-channel TCP (the PR 2
    # control-plane number; must not regress) and the default shm plane
    # (star path — the PR 6 gated metric).
    lat: dict = {}
    for key, env in (("allreduce_small_latency_ms",
                      {"HOROVOD_NUM_CHANNELS": "1",
                       "HOROVOD_SHM_DISABLE": "1"}),
                     ("allreduce_small_latency_ms_shm", {})):
        out = _run_ranks(2, [sys.executable, os.path.abspath(__file__),
                             "--latency-worker"], extra_env=env)
        m = re.search(r"LATENCY_MS ([\d.]+)", out)
        lat[key] = {"2": float(m.group(1))} if m else {}
    result["allreduce_small_latency_ms"] = lat["allreduce_small_latency_ms"]
    result["allreduce_small_latency_ms_shm"] = \
        lat["allreduce_small_latency_ms_shm"]

    # Link self-healing under a seeded flap schedule: two ranks shoot
    # their own data sockets every 7th/11th enqueue for the whole run
    # (the conn-reset fault kind, recurring), and the job must absorb
    # every break — the keys report the median transparent-reconnect
    # latency and the bus bandwidth the flapping plane still sustains,
    # next to the undisturbed sweep above.
    out = _run_ranks(4, [sys.executable, os.path.abspath(__file__),
                         "--link-heal-worker"],
                     extra_env={"HOROVOD_SHM_DISABLE": "1",
                                "HOROVOD_NUM_CHANNELS": "3",
                                "BENCH_SWEEP_BYTES": str(1 << 20),
                                "HOROVOD_FAULT_INJECT":
                                    "0:*:conn-reset:7,"
                                    "2:*:conn-reset:11:prev"})
    m = re.search(r"LINK_BENCH BUS_MB_S ([\d.]+) HEAL_MS_P50 ([\d.]+) "
                  r"RECONNECTS (\d+)", out)
    if m:
        result["allreduce_bus_bw_mb_s_flap"] = {"4": float(m.group(1))}
        result["link_heal_ms_p50"] = float(m.group(2))
        result["link_reconnects_flap"] = int(m.group(3))

    # Wire-dtype sweep (fp32/fp16/int8, 4 KB -> 64 MB, 2 and 4 ranks):
    # EFFECTIVE bus bandwidth per wire format, plus the deterministic
    # per-rank byte-counter ratio vs the fp32 wire — the gate metric
    # (wall time is loopback-noise; bytes are exact).
    wire_bw: dict = {w: {} for w in ("fp32", "fp16", "int8")}
    wire_tx: dict = {w: {} for w in ("fp32", "fp16", "int8")}
    for n in (2, 4):
        for wd in ("fp32", "fp16", "int8"):
            per_size = wire_bw[wd].setdefault(str(n), {})
            per_tx = wire_tx[wd].setdefault(str(n), {})
            for label, nbytes in sizes:
                out = _run_ranks(n, [sys.executable,
                                     os.path.abspath(__file__),
                                     "--wire-sweep-worker"],
                                 extra_env={
                                     "BENCH_SWEEP_BYTES": str(nbytes),
                                     "BENCH_WIRE_DTYPE": wd})
                m = re.search(r"WIRE_SWEEP_BUS_MB_S ([\d.]+) TX (\d+)",
                              out)
                if m:
                    per_size[label] = float(m.group(1))
                    per_tx[label] = int(m.group(2))
    for wd in ("fp32", "fp16", "int8"):
        result[f"allreduce_effective_bus_bw_mb_s_{wd}"] = wire_bw[wd]
        if wd == "fp32":
            continue
        ratios: dict = {}
        for n in ("2", "4"):
            ratios[n] = {
                label: round(wire_tx[wd][n][label]
                             / max(1, wire_tx["fp32"][n][label]), 4)
                for label in wire_tx[wd].get(n, {})
                if label in wire_tx["fp32"].get(n, {})
            }
        result[f"wire_bytes_ratio_{wd}"] = ratios

    # Algorithm-threshold sweep at 2 ranks: star vs ring latency per
    # payload size, interleaved in-process so drift hits both paths.
    algo_sweep: dict = {}
    out = _run_ranks(2, [sys.executable, os.path.abspath(__file__),
                         "--algo-sweep-worker"], timeout=300)
    for label, star, ring in re.findall(
            r"ALGO_SWEEP (\S+) ([\d.]+) ([\d.]+)", out):
        algo_sweep[label] = {"star": float(star), "ring": float(ring)}
    result["algo_threshold_sweep"] = algo_sweep

    # Online-autotuned 16 MB bus bandwidth next to the static numbers,
    # plus the config the search committed (docs/autotune.md).
    autotuned: dict = {}
    autotune_cfg: dict = {}
    for n in (2, 4):
        out = _run_ranks(n, [sys.executable, os.path.abspath(__file__),
                             "--autotune-worker"], timeout=300,
                         extra_env=_AUTOTUNE_ENV)
        m = re.search(
            r"AUTOTUNE_BUS_MB_S ([\d.]+) TRIALS (\d+) CONFIG (.*)", out)
        if m:
            autotuned[str(n)] = float(m.group(1))
            autotune_cfg[str(n)] = json.loads(m.group(3))
    result["allreduce_bus_bw_mb_s_autotuned"] = autotuned
    result["autotune_committed_config"] = autotune_cfg

    # Fleet-telemetry snapshot (docs/observability.md): the per-rank
    # counter table rank 0 aggregated over a short 4-rank run, flattened
    # under the `fleet_` prefix so nightly soak artifacts can trend the
    # fleet view next to the per-process numbers.
    try:
        out = _run_ranks(4, [sys.executable, os.path.abspath(__file__),
                             "--fleet-worker"],
                         extra_env={"HOROVOD_TELEMETRY_CYCLES": "1",
                                    "HOROVOD_CYCLE_TIME": "2"})
        m = re.search(r"FLEET_SNAPSHOT (.*)", out)
        if m:
            fleet = json.loads(m.group(1))
            result["fleet_ranks_reporting"] = fleet.get("ranks_reporting")
            result["fleet_quorum_lag_ns_p50"] = fleet.get(
                "quorum_lag_ns_p50")
            result["fleet_quorum_lag_ns_p99"] = fleet.get(
                "quorum_lag_ns_p99")
            result["fleet_slowest_rank"] = fleet.get("slowest", {}).get(
                "rank")
            for key, v in fleet.get("totals", {}).items():
                result[f"fleet_{key}"] = v
    except RuntimeError as exc:
        print(f"fleet snapshot skipped: {exc}", file=sys.stderr)

    # Big-world control-plane sweep (tests/scale harness): cycle latency,
    # coordinator control-cycle percentiles, rendezvous time and
    # steady-state negotiation bytes/cycle vs world size, hierarchical
    # coordination on.  HOROVOD_SKIP_SCALE_BENCH=1 skips (64 ranks).
    if os.environ.get("HOROVOD_SKIP_SCALE_BENCH") != "1":
        result["scale_sweep"] = _scale_sweep()
    print(json.dumps(result))


def _scale_sweep() -> dict:
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from scale.harness import run_world

    sweep: dict = {}
    for n, groups in ((4, 2), (16, 4), (64, 8)):
        r = run_world(n, groups=groups, steps=50, timeout=300)
        s = r["stats"] or {}
        sweep[str(n)] = {
            "cycle_latency_ms_p50": s.get("step_ms_p50"),
            "cycle_latency_ms_p99": s.get("step_ms_p99"),
            "coordinator_cycle_ms_p50":
                (s.get("coordinator_cycle_ns_p50") or 0) / 1e6,
            "coordinator_cycle_ms_p99":
                (s.get("coordinator_cycle_ns_p99") or 0) / 1e6,
            "rendezvous_ms": r["rendezvous_ms"],
            "negotiation_bytes_per_cycle":
                s.get("negotiation_bytes_per_cycle"),
            "hierarchical": s.get("hier"),
            "hosts": s.get("hosts"),
        }
    return sweep


def scale_gate() -> None:
    """CI big-world gate: 64 single-process engine ranks rendezvous and
    run 50 steady steps within the outer hard timeout (the hang
    detector), and hierarchical coordination cuts rank 0's steady-state
    negotiation bytes/cycle to <= HOROVOD_SCALE_GATE_RATIO (default 0.5)
    x the flat path.  Judged on DETERMINISTIC byte counters, never wall
    time — the PR 4/6 loopback-ceiling lesson: this box's wall numbers
    swing with ambient load, its byte counters do not."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from scale.harness import run_world

    threshold = float(os.environ.get("HOROVOD_SCALE_GATE_RATIO", "0.5"))
    hier = run_world(64, groups=8, steps=50, timeout=300)
    flat = run_world(64, groups=8, steps=50, hier=False, timeout=300)
    hs, fs = hier["stats"], flat["stats"]
    if not hs or not fs:
        print("SCALE GATE FAILED: missing rank-0 measurements")
        sys.exit(1)
    hb, fb = (hs["negotiation_bytes_per_cycle"],
              fs["negotiation_bytes_per_cycle"])
    ratio = hb / fb if fb > 0 else float("inf")
    print(f"scale gate: 64 ranks / 8 hosts — hier {hb:.0f} B/cycle vs "
          f"flat {fb:.0f} B/cycle (x{ratio:.3f}, threshold "
          f"x{threshold:.2f}); rendezvous {hier['rendezvous_ms']:.0f} ms "
          f"hier / {flat['rendezvous_ms']:.0f} ms flat; coordinator "
          f"cycle p50 {hs['coordinator_cycle_ns_p50'] / 1e6:.2f} ms / "
          f"p99 {hs['coordinator_cycle_ns_p99'] / 1e6:.2f} ms")
    failed = []
    if hs["hier"] != 1:
        failed.append("hierarchical coordination did not activate")
    if fs["hier"] != 0:
        failed.append("flat run unexpectedly hierarchical")
    if hs["cache_hits"] < 49 or fs["cache_hits"] < 49:
        failed.append("steady state did not ride the response cache")
    if ratio > threshold:
        failed.append(
            f"negotiation bytes/cycle ratio x{ratio:.3f} exceeds "
            f"x{threshold:.2f}")
    if failed:
        for f in failed:
            print(f"SCALE GATE FAILED: {f}")
        sys.exit(1)
    print("SCALE GATE PASSED")


#: Shared env for the autotune bench/gate runs: small fixed-bytes
#: windows so the full search converges in seconds of traffic, and a
#: pinned seed so the trial schedule is reproducible run to run.
_AUTOTUNE_ENV = {
    "HOROVOD_AUTOTUNE": "1",
    "HOROVOD_AUTOTUNE_SEED": "7",
    "HOROVOD_AUTOTUNE_WINDOW_BYTES": str(8 << 20),
    "HOROVOD_AUTOTUNE_TRIAL_TIMEOUT_SEC": "20",
}


def gate() -> None:
    """CI data-plane gate: channels=4 vs channels=1 on 16 MB 4-rank
    allreduce bus bandwidth (median of in-process alternating rounds),
    and pool liveness comes free — a deadlocked pool hangs the worker
    and the ci.sh timeout kills the run loudly.

    The default threshold is a REGRESSION FLOOR judged on the BEST of
    the interleaved rounds, not the multi-core speedup target: this CI
    box has 2 cores shared by 4 ranks, and its loopback is CPU-ceilinged
    at ~1.4 GB/s aggregate — measured, BOTH paths saturate it when the
    box is quiet (ratio ~1.0) and per-round ratios swing 0.7-2.4x with
    ambient load, while under contention the channeled path wins ~1.4x
    (stall smoothing).  Best-of still catches real data-plane breakage:
    a channel scheduling bug (e.g. serializing 4 channels on one driver)
    measured ~0.65 in EVERY round and fails it.  On hosts with >= 4
    cores per rank, set HOROVOD_GATE_RATIO=1.5 to assert the genuine
    link-parallelism win (there the rounds are stable)."""
    threshold = float(os.environ.get("HOROVOD_GATE_RATIO", "0.85"))
    # Pinned to the TCP plane: this gate was calibrated on it, and the
    # channels-vs-single comparison stays meaningful there; the shm
    # plane has its own gate (--shm-gate).
    out = _run_ranks(4, [sys.executable, os.path.abspath(__file__),
                         "--gate-worker"], timeout=420,
                     extra_env={"BENCH_GATE_ROUNDS": "4",
                                "HOROVOD_SHM_DISABLE": "1"})
    pairs = [(float(a), float(b)) for a, b in
             re.findall(r"GATE_PAIR ([\d.]+) ([\d.]+)", out)]
    if not pairs:
        print("DATA-PLANE GATE FAILED: no measurements produced")
        sys.exit(1)
    ratios = sorted(m / s for m, s in pairs if s > 0)
    if not ratios:
        print("DATA-PLANE GATE FAILED: no valid bandwidth measurements")
        sys.exit(1)
    median = ratios[len(ratios) // 2]
    best = ratios[-1]
    for m, s in pairs:
        ratio = f"x{m / s:.2f}" if s > 0 else "n/a"
        print(f"gate round: channels=4 {m:.0f} MB/s vs channels=1 "
              f"{s:.0f} MB/s ({ratio})")
    print(f"median ratio x{median:.2f}, best x{best:.2f}, "
          f"threshold x{threshold:.2f} (judged on best)")
    if best < threshold:
        print("DATA-PLANE GATE FAILED: multi-channel bus bandwidth did "
              "not clear the threshold in any round")
        sys.exit(1)
    print("DATA-PLANE GATE PASSED")


def shm_gate() -> None:
    """CI shm gate: shm ON vs OFF, interleaved in-process per round —
    small-allreduce latency at 2 ranks and 16 MB bus bandwidth at 4
    ranks.  Judged as a REGRESSION FLOOR on the best interleaved round
    (HOROVOD_SHM_GATE_RATIO, default 0.85), same convention as the
    data-plane gate: this box's loopback CPU ceiling makes single-round
    ratios swing with ambient load, while measured best-of rounds show
    shm ~2x ahead on both metrics (latency 0.8 vs 1.7 ms, 16 MB busbw
    ~1.0 vs ~0.5 GB/s under contention) — so a floor of 0.85 catches a
    broken shm path (those rounds measure 0.3-0.6x) without flaking on
    a quiet-box tie.  The bench JSON records both sides."""
    threshold = float(os.environ.get("HOROVOD_SHM_GATE_RATIO", "0.85"))
    failed = False
    for n, metric in ((2, "lat"), (4, "bw")):
        out = _run_ranks(n, [sys.executable, os.path.abspath(__file__),
                             "--shm-gate-worker"], timeout=420,
                         extra_env={"BENCH_GATE_ROUNDS": "3",
                                    "BENCH_GATE_METRIC": metric})
        pairs = [tuple(map(float, g)) for g in re.findall(
            r"SHM_GATE_PAIR lat ([\d.]+) ([\d.]+) bw ([\d.]+) ([\d.]+)",
            out)]
        if not pairs:
            print(f"SHM GATE FAILED at {n} ranks: no measurements "
                  f"produced\n{out}")
            sys.exit(1)
        ratios = []
        for s_lat, t_lat, s_bw, t_bw in pairs:
            if metric == "lat":
                # Latency: lower is better -> ratio = tcp / shm.
                ratio = t_lat / s_lat if s_lat > 0 else 0.0
                print(f"[{n} ranks] round: shm {s_lat:.3f} ms vs tcp "
                      f"{t_lat:.3f} ms (x{ratio:.2f})")
            else:
                ratio = s_bw / t_bw if t_bw > 0 else 0.0
                print(f"[{n} ranks] round: shm {s_bw:.0f} MB/s vs tcp "
                      f"{t_bw:.0f} MB/s (x{ratio:.2f})")
            ratios.append(ratio)
        best = max(ratios)
        print(f"[{n} ranks] best ratio x{best:.2f}, threshold "
              f"x{threshold:.2f} (judged on best)")
        if best < threshold:
            failed = True
    if failed:
        print("SHM GATE FAILED: the shm plane did not clear the "
              "regression floor in any round")
        sys.exit(1)
    print("SHM GATE PASSED")


def sharded_gate() -> None:
    """CI sharded (ZeRO-1) gate, three legs under ci.sh's hard timeout,
    all on DETERMINISTIC instruments (bitwise compares + byte
    counters — never wall time):

    1. bitwise sharded-vs-unsharded parity at 4 ranks: the
       sharded_worker numpy core asserts params bit-identical to the
       unsharded flat step after EVERY step, optimizer state ~1/N, and
       the per-step byte bounds rank-side;
    2. RS-vs-sliced-allreduce byte parity + the RS wire ratio at 4
       ranks (reducescatter_worker bytes scenario: tx in [0.40, 0.55]x
       the allreduce's);
    3. driver-side wire-bytes ratio: grads reduce-scatter tx <= 0.55x
       the unsharded allreduce tx on a 4 MB flat model (and the honest
       full-step total printed for the record — ZeRO trades no bytes
       for its 1/N memory, see docs/zero.md).
    """
    cap = float(os.environ.get("HOROVOD_SHARDED_GATE_RATIO", "0.55"))

    print("sharded gate 1/3: bitwise sharded-vs-unsharded parity @ 4")
    worker = os.path.join(REPO, "tests", "sharded_worker.py")
    _run_ranks(4, [sys.executable, worker, "numpy"], timeout=300)
    print("sharded parity OK")

    print("sharded gate 2/3: RS parity + wire ratio @ 4 ranks")
    rs_worker = os.path.join(REPO, "tests", "reducescatter_worker.py")
    _run_ranks(4, [sys.executable, rs_worker, "bytes"], timeout=300)
    print("RS byte ratio OK")

    print("sharded gate 3/3: step wire accounting @ 4 ranks")
    out = _run_ranks(4, [sys.executable, os.path.abspath(__file__),
                         "--sharded-bytes-worker"], timeout=300)
    m = re.search(r"SHARDED_BYTES ar_tx (\d+) rs_tx (\d+) ag_tx (\d+)",
                  out)
    if m is None:
        print("SHARDED GATE FAILED: no byte measurements produced")
        sys.exit(1)
    ar_tx, rs_tx, ag_tx = (int(m.group(i)) for i in (1, 2, 3))
    grads_ratio = rs_tx / max(1, ar_tx)
    full_ratio = (rs_tx + ag_tx) / max(1, ar_tx)
    print(f"data_bytes_tx: allreduce {ar_tx}, grads RS {rs_tx} "
          f"(x{grads_ratio:.3f}, cap {cap:.2f}), full sharded step "
          f"{rs_tx + ag_tx} (x{full_ratio:.3f} — the honest ZeRO "
          f"total; the lever is 1/N memory)")
    if grads_ratio > cap:
        print("SHARDED GATE FAILED: the gradient reduce-scatter did "
              "not halve the deterministic byte counter")
        sys.exit(1)
    print("SHARDED GATE PASSED")


def fsdp_gate() -> None:
    """CI ZeRO-3/FSDP gate, three legs under ci.sh's hard timeout:

    1. bitwise fsdp-vs-unsharded parity at 4 ranks (the fsdp_worker
       numpy core): per-unit RS -> shard update -> AG params bit-equal
       to the unsharded flat step after EVERY step, the grads-RS byte
       ratio in [0.40, 0.55]x the allreduce's on the ring path, and
       priority_inversions == 0 with bands on — all asserted
       rank-side;
    2. the deterministic residency ratio at 4 ranks over 16 near-equal
       units: fsdp_param_bytes_resident_peak / total_param_bytes <=
       0.45 (owned 1/N window + one gathered unit — never the full
       model; an unsharded plane sits at 1.0).  Byte counters, never
       RSS — RSS on this box is allocator- and import-noise;
    3. prefetch on vs off on the forward gather walk with real
       per-unit compute, PAIRED IN-PROCESS (two planes, prefetch 1 vs
       0, walked alternately in the same workers — the shm-gate trick,
       so scheduler placement and ambient drift hit both identically),
       best-of-round each, judged at prefetch-on >= 0.95x prefetch-off
       (the cross-process variant flaked: on this CPU-ceilinged
       loopback the engine thread competes with compute, and process
       placement alone swung walls ~20%), with priority_inversions ==
       0 on the banded run.

    HOROVOD_FSDP_GATE_MEM_RATIO / HOROVOD_FSDP_GATE_RATIO override the
    caps on capable hosts.
    """
    mem_cap = float(os.environ.get("HOROVOD_FSDP_GATE_MEM_RATIO", "0.45"))
    floor = float(os.environ.get("HOROVOD_FSDP_GATE_RATIO", "0.95"))
    worker = os.path.join(REPO, "tests", "fsdp_worker.py")

    print("fsdp gate 1/3: bitwise parity + RS wire ratio @ 4 ranks")
    _run_ranks(4, [sys.executable, worker, "numpy"], timeout=300,
               extra_env={"HOROVOD_PRIORITY_BANDS": "1"})
    print("fsdp parity OK (params bitwise == unsharded flat, every "
          "step; inversions == 0)")

    print("fsdp gate 2/3: deterministic peak-residency ratio @ 4 ranks")
    out = _run_ranks(4, [sys.executable, worker, "mem"], timeout=300,
                     extra_env={"HOROVOD_PRIORITY_BANDS": "1"})
    pairs = re.findall(r"FSDP_MEM rank=\d+ peak=(\d+) total=(\d+)", out)
    if not pairs:
        print("FSDP GATE FAILED: no residency measurements produced")
        sys.exit(1)
    ratio = max(int(p) / max(1, int(t)) for p, t in pairs)
    print(f"fsdp_param_bytes_resident_peak / total = x{ratio:.3f} "
          f"(cap {mem_cap:.2f}) — owned 1/N window + one gathered "
          f"unit, never the full model")
    if ratio > mem_cap:
        print("FSDP GATE FAILED: parameter residency did not shrink "
              "to ~1/N")
        sys.exit(1)

    print(f"fsdp gate 3/3: prefetch on/off, paired in-process, "
          f"floor {floor:.2f}")
    out = _run_ranks(2, [sys.executable, worker, "overlap"],
                     timeout=300,
                     extra_env={"HOROVOD_PRIORITY_BANDS": "1"})
    pairs = [m for line in out.splitlines()
             if (m := re.search(
                 r"FSDP_OVERLAP rank=\d+ on_ms=([\d.]+) "
                 r"off_ms=([\d.]+) inversions=(\d+) hits=\d+ "
                 r"misses=\d+ on_all=(\S+) off_all=(\S+)", line))]
    if not pairs:
        print("FSDP GATE FAILED: no overlap measurements produced")
        sys.exit(1)
    if any(int(m.group(3)) for m in pairs):
        print("FSDP GATE FAILED: the band-0 prefetch dispatched a "
              "priority inversion")
        sys.exit(1)
    # Best-of-interleaved, PAIRED: each round's on/off walks run
    # back-to-back on the same cores, so the per-round ratio isolates
    # the prefetch path from placement and ambient drift; the best
    # round is the protocol's verdict.  A broken prefetch (a blocking
    # wait re-serialized into every walk) drags EVERY round under the
    # floor; ambient spikes cannot manufacture a passing round.
    ratios = []
    for m in pairs:
        ons = [float(v) for v in m.group(4).split(",")]
        offs = [float(v) for v in m.group(5).split(",")]
        ratios += [off / on for on, off in zip(ons, offs)]
    best_ratio = max(ratios)
    on_ms = min(float(m.group(1)) for m in pairs)
    off_ms = min(float(m.group(2)) for m in pairs)
    print(f"forward walk: prefetch on best {on_ms:.3f} ms vs off "
          f"{off_ms:.3f} ms; paired off/on best {best_ratio:.3f} "
          f"over {len(ratios)} rounds (floor {floor:.2f})")
    if not (best_ratio >= floor):
        print("FSDP GATE FAILED: the prefetch-on walk regressed past "
              "the floor in every paired round")
        sys.exit(1)
    print("FSDP GATE PASSED")


def compression_gate() -> None:
    """CI wire-compression gate, three legs under ci.sh's hard timeout:

    1. fp32-wire bitwise parity at 4 ranks — HOROVOD_WIRE_DTYPE=fp32 and
       the per-tensor fp32 override must be BYTE-IDENTICAL to the
       default engine across the full dtype/op parity corpus (the
       native_worker wire_parity scenario asserts it rank-side);
    2. int8 wire byte ratio on a 16 MB fp32 allreduce:
       data_bytes_tx(int8) / data_bytes_tx(fp32) <= 0.30, judged on the
       DETERMINISTIC byte counters — never wall time, the loopback is
       CPU-ceilinged and ambient-load-noisy (docs/performance.md);
    3. the convergence worker at 2 ranks: int8 and top-k(1%)+error-
       feedback within their pinned loss bounds of the fp32 run, and
       top-k WITHOUT feedback measurably worse (asserted worker-side).
    """
    ratio_cap = float(os.environ.get("HOROVOD_WIRE_GATE_RATIO", "0.30"))
    worker = os.path.join(REPO, "tests", "native_worker.py")

    print("compression gate 1/3: fp32-wire bitwise parity at 4 ranks")
    _run_ranks(4, [sys.executable, worker, "wire_parity"], timeout=360)
    print("fp32 parity OK")

    print("compression gate 2/3: int8 byte ratio on 16 MB @ 4 ranks")
    out = _run_ranks(4, [sys.executable, os.path.abspath(__file__),
                         "--wire-gate-worker"], timeout=240)
    m = re.search(r"WIRE_GATE_TX fp32 (\d+) int8 (\d+)", out)
    if m is None:
        print("COMPRESSION GATE FAILED: no byte measurements produced")
        sys.exit(1)
    fp32_tx, int8_tx = int(m.group(1)), int(m.group(2))
    ratio = int8_tx / max(1, fp32_tx)
    print(f"data_bytes_tx: fp32 {fp32_tx} vs int8 {int8_tx} "
          f"(ratio {ratio:.3f}, cap {ratio_cap:.2f}, "
          f"cut x{fp32_tx / max(1, int8_tx):.2f})")
    if ratio > ratio_cap:
        print("COMPRESSION GATE FAILED: int8 wire did not cut the "
              "deterministic byte counter under the cap")
        sys.exit(1)

    print("compression gate 3/3: convergence worker at 2 ranks")
    conv = os.path.join(REPO, "tests", "compression_worker.py")
    out = _run_ranks(2, [sys.executable, conv], timeout=420)
    m = re.search(r"LOSSES (.*)", out)
    detail = m.group(1) if m else "bounds asserted worker-side"
    print(f"convergence OK ({detail})")
    print("COMPRESSION GATE PASSED")


def overlap_gate() -> None:
    """CI priority-scheduling / overlap gate, four legs under ci.sh's
    hard timeout:

    1. bands=0 vs bands=1 bitwise parity at 4 ranks (priority_worker
       bands_parity: ordering changes WHEN responses dispatch, never
       what they compute — fusion pinned off, since banding changes
       fusion GROUPING and grouping is a different deterministic fp
       order by design);
    2. a 2-rank REAL-MODEL loop (the tf bench worker, HOROVOD_SMOKE_STEPS)
       with bands on must dispatch with priority_inversions == 0 — the
       deterministic instrument, judged exactly, never wall time;
    3. best-of-interleaved engine_tf_step_ms: bands on vs off alternated
       in rounds (slow-box drift hits both configs equally), judged on a
       0.85 REGRESSION FLOOR — this box's loopback is CPU-ceilinged, so
       the floor guards against scheduling breakage rather than
       asserting a speedup (HOROVOD_OVERLAP_GATE_RATIO overrides);
    4. the wire-policy convergence worker at 2 ranks: the embedding-
       heavy model's policy run must cut the deterministic data_bytes_tx
       (<= 0.60x, the big leaf quartered) at fp32-parity convergence
       (asserted worker-side).
    """
    floor = float(os.environ.get("HOROVOD_OVERLAP_GATE_RATIO", "0.85"))
    prio_worker = os.path.join(REPO, "tests", "priority_worker.py")

    print("overlap gate 1/4: bands on/off bitwise parity at 4 ranks")
    _run_ranks(4, [sys.executable, prio_worker, "bands_parity"],
               timeout=300,
               extra_env={"HOROVOD_PRIORITY_BANDS": "1",
                          "HOROVOD_FUSION_THRESHOLD": "0"})
    print("bands parity OK")

    print("overlap gate 2/4: real-model inversions == 0 with bands on")
    out = _run_ranks(2, [sys.executable, os.path.abspath(__file__),
                         "--tf-worker"], timeout=300,
                     extra_env={"HOROVOD_PRIORITY_BANDS": "1",
                                "HOROVOD_SMOKE_STEPS":
                                    os.environ.get("HOROVOD_SMOKE_STEPS",
                                                   "50")})
    m = _TF_LINE.search(out)
    if m is None or m.group(4) is None:
        print("OVERLAP GATE FAILED: no inversions measurement produced")
        sys.exit(1)
    inv = float(m.group(4))
    print(f"priority_inversions_per_step = {inv:.3f} (bands on)")
    if inv != 0.0:
        print("OVERLAP GATE FAILED: banded ordering dispatched an "
              "inversion on the real-model loop")
        sys.exit(1)

    print("overlap gate 3/4: best-of-interleaved tf step time, "
          f"floor {floor:.2f}")
    best = {"on": float("inf"), "off": float("inf")}
    for _round in range(2):
        for label, env in (("on", {"HOROVOD_PRIORITY_BANDS": "1"}),
                           ("off", {})):
            out = _run_ranks(2, [sys.executable, os.path.abspath(__file__),
                                 "--tf-worker"], timeout=300,
                             extra_env=env)
            m = _TF_LINE.search(out)
            if m:
                best[label] = min(best[label], float(m.group(1)))
    print(f"engine_tf_step_ms best-of: bands on {best['on']:.3f} "
          f"vs off {best['off']:.3f} "
          f"(ratio off/on {best['off'] / best['on']:.3f})")
    if not (best["off"] / best["on"] >= floor):
        print("OVERLAP GATE FAILED: bands-on step time regressed past "
              "the floor")
        sys.exit(1)

    print("overlap gate 4/4: wire-policy bytes + convergence at 2 ranks")
    wp = os.path.join(REPO, "tests", "wire_policy_worker.py")
    out = _run_ranks(2, [sys.executable, wp], timeout=420,
                     extra_env={"HOROVOD_WIRE_POLICY": "1"})
    m = re.search(r"WIRE_POLICY (.*)", out)
    print(f"wire policy OK ({m.group(1) if m else 'asserted worker-side'})")
    print("OVERLAP GATE PASSED")


def autotune_gate() -> None:
    """CI autotune gate at 2 AND 4 ranks: the search must converge
    within HOROVOD_AUTOTUNE_MAX_TRIALS (the worker asserts it), and the
    committed config's 16 MB bus bandwidth must reach the gate ratio of
    the BEST static grid point, judged on the best of interleaved
    rounds — same regression-floor convention as the data-plane gate
    (this box's loopback is CPU-ceilinged and ambient-load-noisy; both
    sides usually tie at ~1.0, and the floor catches a search that
    commits a genuinely broken config).  HOROVOD_AUTOTUNE_GATE_RATIO
    overrides the 0.85 default on capable hosts."""
    threshold = float(os.environ.get("HOROVOD_AUTOTUNE_GATE_RATIO", "0.85"))
    env = {
        **_AUTOTUNE_ENV,
        # chunk + wave only: the full 4-knob schedule buys the gate
        # nothing but wall time (fusion/cycle barely move single-tensor
        # busbw), and the grid it is judged against is the chunk axis.
        "HOROVOD_AUTOTUNE_KNOBS": "chunk_bytes,wave_width",
        "BENCH_GATE_ROUNDS": "3",
    }
    failed = False
    for n in (2, 4):
        out = _run_ranks(n, [sys.executable, os.path.abspath(__file__),
                             "--autotune-gate-worker"], timeout=420,
                         extra_env=env)
        rounds = [(float(a), float(s)) for a, s in re.findall(
            r"AUTOGATE_ROUND auto=([\d.]+) static_best=([\d.]+)", out)]
        trials = re.search(r"AUTOGATE_TRIALS (\d+) MAX (\d+)", out)
        cfg = re.search(r"AUTOGATE_CONFIG (.*)", out)
        if not rounds or trials is None:
            print(f"AUTOTUNE GATE FAILED at {n} ranks: no measurements "
                  f"produced\n{out}")
            sys.exit(1)
        print(f"[{n} ranks] converged in {trials.group(1)} trials "
              f"(cap {trials.group(2)}); committed "
              f"{cfg.group(1) if cfg else '?'}")
        ratios = []
        for a, s in rounds:
            ratio = a / s if s > 0 else 0.0
            ratios.append(ratio)
            print(f"[{n} ranks] round: autotuned {a:.0f} MB/s vs "
                  f"best-static {s:.0f} MB/s (x{ratio:.2f})")
        best = max(ratios) if ratios else 0.0
        print(f"[{n} ranks] best ratio x{best:.2f}, "
              f"threshold x{threshold:.2f} (judged on best)")
        if best < threshold:
            failed = True
    if failed:
        print("AUTOTUNE GATE FAILED: the committed config did not reach "
              "the static-grid floor in any round")
        sys.exit(1)
    print("AUTOTUNE GATE PASSED")


if __name__ == "__main__":
    if "--tf-worker" in sys.argv:
        _tf_worker()
    elif "--sweep-worker" in sys.argv:
        _sweep_worker()
    elif "--latency-worker" in sys.argv:
        _latency_worker()
    elif "--gate-worker" in sys.argv:
        _gate_worker()
    elif "--shm-gate-worker" in sys.argv:
        _shm_gate_worker()
    elif "--algo-sweep-worker" in sys.argv:
        _algo_sweep_worker()
    elif "--wire-sweep-worker" in sys.argv:
        _wire_sweep_worker()
    elif "--wire-gate-worker" in sys.argv:
        _wire_gate_worker()
    elif "--fleet-worker" in sys.argv:
        _fleet_worker()
    elif "--link-heal-worker" in sys.argv:
        _link_heal_bench_worker()
    elif "--rs-sweep-worker" in sys.argv:
        _rs_sweep_worker()
    elif "--alltoall-sweep-worker" in sys.argv:
        _alltoall_sweep_worker()
    elif "--sharded-bytes-worker" in sys.argv:
        _sharded_bytes_worker()
    elif "--sharded-gate" in sys.argv:
        sharded_gate()
    elif "--fsdp-gate" in sys.argv:
        fsdp_gate()
    elif "--compression-gate" in sys.argv:
        compression_gate()
    elif "--shm-gate" in sys.argv:
        shm_gate()
    elif "--autotune-worker" in sys.argv:
        _autotune_worker()
    elif "--autotune-gate-worker" in sys.argv:
        _autotune_gate_worker()
    elif "--autotune-gate" in sys.argv:
        autotune_gate()
    elif "--overlap-gate" in sys.argv:
        overlap_gate()
    elif "--scale-gate" in sys.argv:
        scale_gate()
    elif "--gate" in sys.argv:
        gate()
    else:
        main()
