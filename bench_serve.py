"""Serving benchmark: open-loop Poisson load against the replica fleet.

Open-loop (the serving-literature convention): request arrival times
are drawn from a Poisson process and honored REGARDLESS of completions,
so the generator measures the system under load rather than pacing
itself to it.  Each request records submit → first-token (TTFT) and
submit → done latency from the client's side of the socket.

Prints ONE JSON line::

    {"metric": "serve", "tokens_per_sec": .., "req_latency_ms_p50": ..,
     "req_latency_ms_p99": .., "ttft_ms_p50": .., "ttft_ms_p99": ..,
     "batch_occupancy": .., "completed": .., "requests": ..,
     "replicas": 2, "requeued": .., "preemptions": ..,
     "kv_blocks_in_use_peak_seen": ..}

``python bench_serve.py --gate`` is the CI serve gate: a short Poisson
run (2 replicas) that FAILS loudly unless every request completes with
its full nonzero token count, continuous batching actually overlapped
(measured batch occupancy > 1), a LIVE WEIGHT PUSH lands mid-load
(every replica acks epoch 1 and every stream finishes self-consistent
under whichever epoch stamped its ``done`` — never dropped, never a
partial token count), shutdown is clean (router exit 0), and nothing
leaks — replica processes, the router's listen socket, and /dev/shm
are checked against their pre-run state.

``python bench_serve.py --prefix-gate`` is the CI prefix-cache +
fused-kernel gate.  The workload is the serving-literature chatbot
shape: every request shares a SYSTEM PROMPT (24 tokens = 6 full KV
blocks) ahead of its random user suffix, and the tail of the plan
repeats earlier requests verbatim.  Two fleets run per round —
fused+prefix ON vs both OFF — interleaved, best-of-2 per arm.  FAILS
unless: prefix hit rate >= 0.5 and prefill_tokens_saved > 0 on the ON
arm (and exactly 0 on the OFF arm), verbatim repeats stream
BIT-IDENTICAL tokens to their originals, every request completes,
occupancy > 1, no replica-process/socket/shm leaks, no KV blocks left
in use, and ON throughput >= 0.85x OFF (the fused path plus cache must
never cost real throughput; the artifact records both so the win is
visible where it exists).
"""

from __future__ import annotations

import glob
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))

BENCH_ENV = {
    "JAX_PLATFORMS": "cpu",
    "HOROVOD_SERVE_BLOCK_SIZE": "4",
    "HOROVOD_SERVE_MAX_MODEL_LEN": "64",
    "HOROVOD_SERVE_MAX_BATCH": "8",
}


def _percentile(values, q):
    if not values:
        return 0.0
    vs = sorted(values)
    idx = min(len(vs) - 1, max(0, int(round(q / 100.0 * (len(vs) - 1)))))
    return vs[idx]


def _replica_procs():
    """Pids currently running the replica module (leak detection)."""
    pids = set()
    for path in glob.glob("/proc/[0-9]*/cmdline"):
        try:
            with open(path, "rb") as f:
                cmd = f.read()
        except OSError:
            continue
        if b"horovod_tpu.serve.replica" in cmd:
            pids.add(int(path.split("/")[2]))
    return pids


def _start_fleet(replicas: int, env_extra=None):
    env = dict(os.environ)
    env.update(BENCH_ENV)
    env.update(env_extra or {})
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.run", "--serve",
         "--replicas", str(replicas), "--serve-port", "0"],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    port = None
    log = []
    deadline = time.time() + 300
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        log.append(line)
        m = re.search(r"SERVE_ROUTER_READY port=(\d+)", line)
        if m:
            port = int(m.group(1))
            break
    if port is None:
        proc.kill()
        raise RuntimeError("router never became ready:\n" + "".join(log))
    threading.Thread(target=lambda: [log.append(ln) for ln in
                                     iter(proc.stdout.readline, "")],
                     daemon=True).start()
    return proc, port, log


def run_load(port: int, *, requests: int, rate_hz: float, seed: int = 0,
             max_tokens_lo: int = 8, max_tokens_hi: int = 24,
             push_at: int = -1, system_prompt=None, dup_tail: int = 0):
    """Drive the Poisson open-loop load; returns per-request records and
    the aggregate dict.  ``push_at >= 0`` fires a live weight push
    (scaled params, epoch 1, lossless fp32 wire) right after that
    request index is submitted — from a background thread, so the
    Poisson clock stays honest.  ``system_prompt`` is a token list
    prepended to every prompt (the shared-prefix chatbot workload);
    ``dup_tail`` makes the last N requests repeat the first N verbatim
    (same prompt AND token budget), and the aggregate reports whether
    each repeat streamed bit-identical tokens (``dup_exact``)."""
    import numpy as np

    sys.path.insert(0, REPO)
    from horovod_tpu.serve.server import ServeClient

    push_acks = []
    if push_at >= 0:
        # Built BEFORE the clock starts: model init must not distort
        # the arrival process.
        from horovod_tpu.checkpoint import WeightPusher
        from horovod_tpu.serve.config import ServeConfig
        from horovod_tpu.serve.engine import ModelRunner
        import jax

        runner = ModelRunner(ServeConfig.from_env(BENCH_ENV))
        vars2 = jax.tree_util.tree_map(
            lambda a: (np.asarray(a, np.float32) * 1.25).astype(
                np.asarray(a).dtype)
            if np.issubdtype(np.asarray(a).dtype, np.floating)
            else np.asarray(a),
            runner.variables)

        def _push():
            pusher = WeightPusher("127.0.0.1", port, timeout=300)
            try:
                push_acks.append(pusher.push(vars2, epoch=1, wire="fp32"))
            finally:
                pusher.close()

    rng = np.random.default_rng(seed)
    head = list(system_prompt or [])
    plan = []
    t = 0.0
    for i in range(requests):
        t += float(rng.exponential(1.0 / rate_hz))
        prompt = head + rng.integers(0, 512,
                                     int(rng.integers(3, 12))).tolist()
        n = int(rng.integers(max_tokens_lo, max_tokens_hi + 1))
        if dup_tail and i >= requests - dup_tail:
            # Verbatim repeat of an early request: by now its prefix is
            # registered, so this is the cache-hit + bit-exactness probe.
            _, prompt, n = plan[i - (requests - dup_tail)]
        plan.append((t, prompt, n))

    cli = ServeClient("127.0.0.1", port, timeout=600)
    push_thread = None
    records = {}
    t0 = time.monotonic()
    for i, (due, prompt, n) in enumerate(plan):
        now = time.monotonic() - t0
        if now < due:
            time.sleep(due - now)
        rid = f"load{i}"
        records[rid] = {"submit": time.monotonic(), "n": n}
        cli.start_generate(rid, prompt, max_tokens=n)
        if i == push_at:
            push_thread = threading.Thread(target=_push, daemon=True)
            push_thread.start()
    for i in range(requests):
        rid = f"load{i}"
        evs = cli.collect(rid, timeout=600)
        rec = records[rid]
        rec["events"] = evs
        rec["ok"] = (evs[-1]["event"] == "done"
                     and len(evs[-1]["tokens"]) == rec["n"]
                     and rec["n"] > 0)
        rec["requeued"] = any(e["event"] == "requeued" for e in evs)
        rec["tokens"] = evs[-1].get("tokens", []) \
            if evs[-1]["event"] == "done" else []
    wall = time.monotonic() - t0

    # TTFT needs receive timestamps; approximate from the collect order
    # is wrong under concurrency, so ServeClient stamps each event.
    lat, ttft = [], []
    total_tokens = 0
    completed = 0
    requeued = 0
    for rec in records.values():
        if not rec["ok"]:
            continue
        completed += 1
        total_tokens += len(rec["tokens"])
        lat.append((rec["events"][-1]["_recv_ts"] - rec["submit"]) * 1e3)
        first = next(e for e in rec["events"] if e["event"] == "token")
        ttft.append((first["_recv_ts"] - rec["submit"]) * 1e3)
        requeued += int(rec["requeued"])
    stats = cli.stats()
    agg = {
        "metric": "serve",
        "requests": requests,
        "completed": completed,
        "tokens_per_sec": round(total_tokens / wall, 2),
        "req_latency_ms_p50": round(_percentile(lat, 50), 1),
        "req_latency_ms_p99": round(_percentile(lat, 99), 1),
        "ttft_ms_p50": round(_percentile(ttft, 50), 1),
        "ttft_ms_p99": round(_percentile(ttft, 99), 1),
        "requeued": requeued,
        "router": stats["router"],
        "batch_occupancy": max(
            (r.get("scheduler", {}).get("batch_occupancy", 0.0)
             for r in stats["replicas"]), default=0.0),
        "preemptions": sum(
            r.get("scheduler", {}).get("preemptions", 0)
            for r in stats["replicas"]),
        "kv_blocks_in_use_peak_seen": max(
            (r.get("scheduler", {}).get("kv_blocks_in_use", 0)
             for r in stats["replicas"]), default=0),
        "kv_blocks_in_use_final": sum(
            r.get("scheduler", {}).get("kv_blocks_in_use", 0)
            for r in stats["replicas"]),
    }
    scheds = [r.get("scheduler", {}) for r in stats["replicas"]]
    for key in ("prefix_hits", "prefix_misses", "prefix_evictions",
                "cow_forks", "fused_attn_steps", "prefill_tokens_saved"):
        agg[key] = sum(s.get(key, 0) for s in scheds)
    attempts = agg["prefix_hits"] + agg["prefix_misses"]
    agg["prefix_hit_rate"] = round(agg["prefix_hits"] / attempts, 3) \
        if attempts else 0.0
    if dup_tail:
        agg["dup_exact"] = all(
            records[f"load{requests - dup_tail + j}"]["tokens"]
            == records[f"load{j}"]["tokens"]
            for j in range(dup_tail))
    if push_at >= 0:
        if push_thread is not None:
            push_thread.join(timeout=300)
        agg["weight_pushes"] = stats["router"].get("weight_pushes", 0)
        agg["weight_push_acked"] = bool(
            push_acks and push_acks[0].get("replicas")
            and all(r.get("applied")
                    for r in push_acks[0]["replicas"]))
        agg["replica_weight_epochs"] = [
            r.get("scheduler", {}).get("weight_epoch")
            for r in stats["replicas"]]
        agg["stream_weight_epochs"] = sorted({
            rec["events"][-1].get("weight_epoch")
            for rec in records.values() if rec["ok"]})
    return cli, records, agg


def _main(replicas: int, requests: int, rate_hz: float) -> dict:
    proc, port, log = _start_fleet(replicas)
    cli, _, agg = run_load(port, requests=requests, rate_hz=rate_hz)
    agg["replicas"] = replicas
    cli.shutdown()
    rc = proc.wait(timeout=120)
    cli.close()
    agg["clean_shutdown"] = (rc == 0)
    return agg


def _gate() -> int:
    """CI serve gate — see module docstring for the contract."""
    shm_before = set(os.listdir("/dev/shm")) if os.path.isdir("/dev/shm") \
        else set()
    procs_before = _replica_procs()

    replicas, requests, rate = 2, 24, 6.0
    proc, port, log = _start_fleet(replicas)
    try:
        # push_at: mid-load, so a real set of streams is in flight when
        # the swap lands (the live-push self-consistency contract).
        cli, records, agg = run_load(port, requests=requests, rate_hz=rate,
                                     push_at=requests // 2)
    except Exception:
        proc.kill()
        sys.stdout.write("".join(log[-40:]))
        raise
    agg["replicas"] = replicas
    cli.shutdown()
    try:
        rc = proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()
        rc = -9
    cli.close()
    agg["clean_shutdown"] = (rc == 0)
    print(json.dumps(agg))

    failures = []
    if agg["completed"] != requests:
        failures.append(f"only {agg['completed']}/{requests} requests "
                        "completed with their full token count")
    if agg["batch_occupancy"] <= 1.0:
        failures.append("batch occupancy "
                        f"{agg['batch_occupancy']:.2f} <= 1.0: continuous "
                        "batching never overlapped")
    if agg["tokens_per_sec"] <= 0:
        failures.append("zero streamed tokens")
    if agg.get("weight_pushes") != 1 or not agg.get("weight_push_acked"):
        failures.append(
            f"live weight push did not land: pushes="
            f"{agg.get('weight_pushes')} acked="
            f"{agg.get('weight_push_acked')}")
    if agg.get("replica_weight_epochs") != [1] * replicas:
        failures.append(
            "replicas not all at the pushed weight epoch: "
            f"{agg.get('replica_weight_epochs')}")
    if not set(agg.get("stream_weight_epochs") or []) <= {0, 1}:
        failures.append(
            f"mixed-epoch streams: {agg.get('stream_weight_epochs')}")
    if rc != 0:
        failures.append(f"router exited {rc} (unclean shutdown)")
    # Leak checks: give stragglers a moment to be reaped.
    deadline = time.time() + 20
    while time.time() < deadline and _replica_procs() - procs_before:
        time.sleep(0.5)
    leaked_procs = _replica_procs() - procs_before
    if leaked_procs:
        failures.append(f"leaked replica processes: {sorted(leaked_procs)}")
    try:
        with socket.create_connection(("127.0.0.1", port), timeout=2):
            failures.append(f"router port {port} still accepting "
                            "connections")
    except OSError:
        pass
    shm_after = set(os.listdir("/dev/shm")) if os.path.isdir("/dev/shm") \
        else set()
    leaked_shm = shm_after - shm_before
    if leaked_shm:
        failures.append(f"leaked /dev/shm entries: {sorted(leaked_shm)}")

    if failures:
        for f in failures:
            print(f"SERVE GATE FAIL: {f}", file=sys.stderr)
        print("".join(log[-40:]), file=sys.stderr)
        return 1
    print(f"SERVE GATE OK: {requests} requests, "
          f"{agg['tokens_per_sec']} tok/s, occupancy "
          f"{agg['batch_occupancy']:.2f}, p99 "
          f"{agg['req_latency_ms_p99']:.0f} ms, clean shutdown")
    return 0


#: The shared system prompt of the prefix workload: 24 tokens = 6 FULL
#: KV blocks at the bench block size (4), so every warm request shares 6
#: blocks and COW-forks where its user suffix diverges.
SYSTEM_PROMPT = [7 * i % 512 for i in range(1, 25)]


def _prefix_run(env_extra, requests, rate):
    """One fleet round of the shared-system-prompt workload; returns the
    aggregate (with clean_shutdown folded in)."""
    proc, port, log = _start_fleet(2, env_extra=env_extra)
    try:
        cli, _, agg = run_load(port, requests=requests, rate_hz=rate,
                               system_prompt=SYSTEM_PROMPT, dup_tail=2)
    except Exception:
        proc.kill()
        sys.stdout.write("".join(log[-40:]))
        raise
    agg["replicas"] = 2
    cli.shutdown()
    try:
        rc = proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()
        rc = -9
    cli.close()
    agg["clean_shutdown"] = (rc == 0)
    agg["log_tail"] = "".join(log[-40:])
    return agg


def _prefix_gate() -> int:
    """CI prefix-cache + fused-kernel gate — see module docstring."""
    shm_before = set(os.listdir("/dev/shm")) if os.path.isdir("/dev/shm") \
        else set()
    procs_before = _replica_procs()

    requests, rate = 20, 8.0
    # Both arms pre-compile their whole program menu before READY
    # (HOROVOD_SERVE_WARMUP): the arms want different program sets
    # (suffix-prefill + fused decode vs gather decode), and without
    # warmup the measured window would mostly compare jit compile
    # counts, not steady-state serving throughput.
    arms = {
        "on": {"HOROVOD_SERVE_FUSED_ATTN": "1",
               "HOROVOD_SERVE_PREFIX_CACHE": "1",
               "HOROVOD_SERVE_WARMUP": "64"},
        "off": {"HOROVOD_SERVE_FUSED_ATTN": "0",
                "HOROVOD_SERVE_PREFIX_CACHE": "0",
                "HOROVOD_SERVE_WARMUP": "64"},
    }
    # Interleaved best-of-2 per arm: alternating runs share whatever
    # machine-noise drift exists instead of handing one arm a quiet box.
    runs = {"on": [], "off": []}
    for _round in range(2):
        for arm in ("off", "on"):
            runs[arm].append(_prefix_run(arms[arm], requests, rate))
    best = {arm: max(rs, key=lambda a: a["tokens_per_sec"])
            for arm, rs in runs.items()}
    on, off = best["on"], best["off"]

    out = {"metric": "serve_prefix", "requests": requests}
    for arm, agg in best.items():
        for key in ("tokens_per_sec", "ttft_ms_p50", "ttft_ms_p99",
                    "req_latency_ms_p99", "batch_occupancy", "completed",
                    "prefix_hit_rate", "prefill_tokens_saved",
                    "prefix_hits", "cow_forks", "fused_attn_steps",
                    "dup_exact", "clean_shutdown",
                    "kv_blocks_in_use_final"):
            out[f"{key}_{arm}"] = agg.get(key)
    out["throughput_ratio"] = round(
        on["tokens_per_sec"] / max(1e-9, off["tokens_per_sec"]), 3)
    print(json.dumps(out))

    failures = []
    for arm, agg in best.items():
        if agg["completed"] != requests:
            failures.append(
                f"[{arm}] only {agg['completed']}/{requests} requests "
                "completed with their full token count")
        if not agg["dup_exact"]:
            failures.append(
                f"[{arm}] verbatim repeat streamed DIFFERENT tokens "
                "than its original")
        if agg["batch_occupancy"] <= 1.0:
            failures.append(
                f"[{arm}] batch occupancy {agg['batch_occupancy']:.2f} "
                "<= 1.0: continuous batching never overlapped")
        if agg["kv_blocks_in_use_final"] != 0:
            failures.append(
                f"[{arm}] {agg['kv_blocks_in_use_final']} KV blocks "
                "still in use after all streams finished (leak)")
        if not agg["clean_shutdown"]:
            failures.append(f"[{arm}] unclean router shutdown")
    if on["prefix_hit_rate"] < 0.5:
        failures.append(
            f"prefix hit rate {on['prefix_hit_rate']} < 0.5 on the "
            "shared-system-prompt workload")
    if on["prefill_tokens_saved"] <= 0:
        failures.append("prefix cache saved zero prefill tokens")
    if on["fused_attn_steps"] <= 0:
        failures.append("fused kernel never ran on the ON arm")
    if off["prefix_hits"] != 0 or off["prefill_tokens_saved"] != 0:
        failures.append(
            "OFF arm touched the prefix cache: hits="
            f"{off['prefix_hits']} saved={off['prefill_tokens_saved']}")
    if on["tokens_per_sec"] < 0.85 * off["tokens_per_sec"]:
        failures.append(
            f"fused+prefix throughput {on['tokens_per_sec']} tok/s < "
            f"0.85x baseline {off['tokens_per_sec']} tok/s")
    deadline = time.time() + 20
    while time.time() < deadline and _replica_procs() - procs_before:
        time.sleep(0.5)
    leaked_procs = _replica_procs() - procs_before
    if leaked_procs:
        failures.append(f"leaked replica processes: {sorted(leaked_procs)}")
    shm_after = set(os.listdir("/dev/shm")) if os.path.isdir("/dev/shm") \
        else set()
    leaked_shm = shm_after - shm_before
    if leaked_shm:
        failures.append(f"leaked /dev/shm entries: {sorted(leaked_shm)}")

    if failures:
        for f in failures:
            print(f"SERVE PREFIX GATE FAIL: {f}", file=sys.stderr)
        for arm, agg in best.items():
            print(f"--- [{arm}] log tail ---\n" + agg.get("log_tail", ""),
                  file=sys.stderr)
        return 1
    print(f"SERVE PREFIX GATE OK: hit_rate={on['prefix_hit_rate']}, "
          f"saved={on['prefill_tokens_saved']} prefill tokens, "
          f"{on['tokens_per_sec']} tok/s on vs {off['tokens_per_sec']} "
          f"off (ratio {out['throughput_ratio']}), repeats bit-exact, "
          "no leaks")
    return 0


if __name__ == "__main__":
    if "--gate" in sys.argv:
        sys.exit(_gate())
    if "--prefix-gate" in sys.argv:
        sys.exit(_prefix_gate())
    out = _main(
        replicas=int(os.environ.get("HOROVOD_SERVE_BENCH_REPLICAS", "2")),
        requests=int(os.environ.get("HOROVOD_SERVE_BENCH_REQUESTS", "40")),
        rate_hz=float(os.environ.get("HOROVOD_SERVE_BENCH_RATE", "6")))
    print(json.dumps(out))
