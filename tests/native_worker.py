"""Worker body for multi-process native-engine tests.

The TPU-native analogue of the reference's ``mpirun -np 2 pytest`` strategy
(reference .travis.yml:104-111): N identical processes run the same
assertions simultaneously; here the launcher is plain ``subprocess`` + the
engine's own TCP rendezvous instead of mpirun.  Run as:

    python native_worker.py <scenario>

with identity in HOROVOD_RANK/HOROVOD_SIZE/HOROVOD_COORDINATOR env vars.
Deliberately jax-free: exercises the native engine + numpy only.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horovod_tpu.common.basics import basics  # noqa: E402
from horovod_tpu.runtime.engine import (  # noqa: E402
    HorovodInternalError,
    get_engine,
)


def scenario_allreduce(rank, size, eng):
    # Identity check: sum of per-rank constants (reference
    # test_tensorflow.py:56-85 does tensor*size with random tensors).
    x = np.full((32, 5), float(rank + 1), dtype=np.float32)
    out = eng.allreduce(x)
    expected = size * (size + 1) / 2.0
    assert np.allclose(out, expected), (out[0, 0], expected)
    # Average.
    out = eng.allreduce(x, average=True)
    assert np.allclose(out, expected / size)
    # int64 + float64.
    for dtype in (np.int64, np.float64):
        x = (np.arange(7) + rank).astype(dtype)
        out = eng.allreduce(x)
        exp = size * np.arange(7, dtype=np.float64) + size * (size - 1) / 2
        assert np.allclose(np.asarray(out, np.float64), exp), (dtype, out)


def scenario_fused(rank, size, eng):
    # Many small same-dtype tensors enqueued in one burst: the coordinator
    # fuses them into few ring collectives (reference fused test,
    # test_tensorflow.py:87-119).  Validates values per tensor.
    arrs = [np.full((n + 1, 3), float(rank + n), np.float32)
            for n in range(17)]
    handles = [eng.enqueue_allreduce(a, name=f"fused.{i}")
               for i, a in enumerate(arrs)]
    for n, h in enumerate(handles):
        out = eng.synchronize(h)
        expected = sum(r + n for r in range(size))
        assert np.allclose(out, expected), (n, out[0, 0], expected)
    # bf16 via jax's ml_dtypes if available.
    try:
        import ml_dtypes

        x = np.full((64,), 1.5, dtype=ml_dtypes.bfloat16) * (rank + 1)
        out = eng.allreduce(x)
        expected = 1.5 * size * (size + 1) / 2
        assert np.allclose(np.asarray(out, np.float32), expected, rtol=0.02)
    except ImportError:
        pass


def scenario_allgather(rank, size, eng):
    # Variable dim-0 per rank — the negotiated-shape path (reference
    # test_tensorflow.py:348-433, operations.cc:796-856).
    x = np.full((rank + 1, 4), float(rank), dtype=np.float32)
    out = eng.allgather(x)
    assert out.shape == (size * (size + 1) // 2, 4), out.shape
    off = 0
    for r in range(size):
        block = out[off:off + r + 1]
        assert np.all(block == float(r)), (r, block)
        off += r + 1


def scenario_reduce_ops(rank, size, eng):
    # MIN/MAX/PROD on the wire — an extension past the reference's SUM-only
    # protocol, matching the jit path's pmin/pmax/product surface.
    x = np.arange(6, dtype=np.float32) + 10 * rank
    assert np.allclose(eng.allreduce(x.copy(), red_op="min"),
                       np.arange(6, dtype=np.float32))
    assert np.allclose(eng.allreduce(x.copy(), red_op="max"),
                       np.arange(6) + 10.0 * (size - 1))
    y = np.full((4,), float(rank + 1), dtype=np.float32)
    import math
    assert np.allclose(eng.allreduce(y.copy(), red_op="prod"),
                       float(math.factorial(size)))
    # int64 min and bf16 max
    z = (np.arange(5) + rank).astype(np.int64)
    assert np.array_equal(eng.allreduce(z.copy(), red_op="min"),
                          np.arange(5, dtype=np.int64))
    # reducescatter with max
    rows = size * 2
    base = np.arange(rows * 2, dtype=np.float32).reshape(rows, 2)
    out = eng.reducescatter(base + rank, red_op="max")
    assert np.allclose(out, base[rank * 2:(rank + 1) * 2] + (size - 1)), out


def scenario_red_op_mismatch(rank, size, eng):
    # Ranks disagreeing on the reduction operator must get a typed error.
    try:
        eng.allreduce(np.zeros(4, np.float32), name="bad_op",
                      red_op="min" if rank == 0 else "max")
        if size == 1:
            return
    except HorovodInternalError as e:
        assert "Mismatched reduction operators" in str(e), str(e)
        return
    raise AssertionError("expected HorovodInternalError")


def scenario_reducescatter(rank, size, eng):
    # dim0 = size + 1 exercises the uneven split (rank 0 gets 2 rows).
    rows = size + 1
    base = np.arange(rows * 3, dtype=np.float32).reshape(rows, 3)
    x = base * (rank + 1)
    out = eng.reducescatter(x)
    factor = size * (size + 1) / 2.0
    my_rows = rows // size + (1 if rank < rows % size else 0)
    offset = sum(rows // size + (1 if r < rows % size else 0)
                 for r in range(rank))
    assert out.shape == (my_rows, 3), out.shape
    assert np.allclose(out, base[offset:offset + my_rows] * factor), out
    # Average parity with allreduce semantics.
    out = eng.reducescatter(x, average=True)
    assert np.allclose(out, base[offset:offset + my_rows] * factor / size)


def scenario_alltoall(rank, size, eng):
    # Block b of rank r carries value r*100 + b; after the exchange block s
    # of every rank must carry s*100 + rank.
    x = np.concatenate([
        np.full((2, 3), rank * 100 + b, dtype=np.float32)
        for b in range(size)
    ])
    out = eng.alltoall(x)
    assert out.shape == x.shape, (out.shape, x.shape)
    for s in range(size):
        block = out[2 * s:2 * (s + 1)]
        assert np.all(block == s * 100 + rank), (s, block)


def scenario_alltoall_indivisible(rank, size, eng):
    # dim0 not divisible by size -> negotiated typed error on every rank.
    x = np.zeros((size + 1, 2), dtype=np.float32)
    try:
        eng.alltoall(x, name="bad_split")
    except HorovodInternalError as e:
        assert "divisible" in str(e), str(e)
        return
    if size == 1:
        return  # single rank: 2 % 1 == 0, no error possible
    raise AssertionError("expected HorovodInternalError")


def _a2a_dtypes():
    dtypes = [np.float32, np.float64, np.int32, np.int64, np.uint8,
              np.int8, np.uint16, np.int16, np.float16, np.bool_]
    try:
        import ml_dtypes

        dtypes.append(ml_dtypes.bfloat16)
    except ImportError:
        pass
    return dtypes


def _a2a_case(src, size, dt, case):
    """Rank ``src``'s deterministic payload + split vector for parity
    case ``case`` — every rank recomputes every peer's payload locally,
    so the pairwise-sends reference needs no second data path.  Case 0:
    prime per-destination counts.  Case 1: a zero-heavy matrix with an
    all-zero ROW (rank 1 sends nothing) and an all-zero COLUMN (rank 0
    receives nothing) — the empty-block codec offsets.  Case 2: equal
    legacy splits."""
    primes = (1, 3, 7, 13, 61)
    if case == 0:
        sp = [primes[(src + d) % len(primes)] for d in range(size)]
    elif case == 1:
        sp = [0 if (src == 1 % size or d == 0) else 2 + ((src + d) % 3)
              for d in range(size)]
    else:
        sp = [2] * size
    rows = sum(sp)
    rng = np.random.default_rng(5000 + 17 * src + case)
    if np.dtype(dt).kind == "b":
        x = (rng.integers(0, 2, (rows, 3)) > 0)
    elif np.dtype(dt).kind in "fV" or np.dtype(dt).name == "bfloat16":
        x = rng.standard_normal((rows, 3)).astype(dt)
    else:
        x = rng.integers(0, 100, (rows, 3)).astype(dt)
    return np.ascontiguousarray(x), sp


def _a2a_expected(rank, size, dt, case):
    """The pairwise-sends reference: concatenate, in source-rank order,
    each source's block addressed to ``rank``."""
    blocks = []
    for s in range(size):
        xs, sp = _a2a_case(s, size, dt, case)
        off = sum(sp[:rank])
        blocks.append(xs[off:off + sp[rank]])
    return np.concatenate(blocks) if blocks else None


def scenario_alltoall_splits(rank, size, eng):
    # The variable-split tentpole contract, bitwise: for every wire
    # dtype and split geometry (prime counts, empty rows/columns, equal
    # legacy splits) the alltoall output must equal the pairwise-sends
    # reference BYTE FOR BYTE — alltoall moves payload verbatim, so each
    # rank rebuilds every peer's deterministic payload and compares.
    before = eng.stats()
    for case in range(3):
        for d_i, dt in enumerate(_a2a_dtypes()):
            x, sp = _a2a_case(rank, size, dt, case)
            out = eng.alltoall(x.copy(), name=f"a2a.c{case}.d{d_i}",
                               splits=None if case == 2 else sp)
            exp = _a2a_expected(rank, size, dt, case)
            assert out.shape == exp.shape, (case, dt, out.shape, exp.shape)
            assert out.tobytes() == exp.tobytes(), (
                f"case {case} dtype {np.dtype(dt).name}: alltoall != "
                "pairwise sends")
    after = eng.stats()
    assert after["alltoall_bytes"] > before["alltoall_bytes"], after
    assert after["alltoall_ns"] > before["alltoall_ns"], after
    # Split-vector validation is LOCAL and typed (bad geometry never
    # reaches the wire).
    x = np.zeros((4, 2), dtype=np.float32)
    for bad in ([3] * (size + 1), [-1] + [5 - size + 2] * (size - 1),
                [0] * size):
        try:
            eng.alltoall(x, splits=bad, name="a2a.bad")
        except ValueError:
            continue
        raise AssertionError(f"splits {bad} accepted for dim0=4")
    # Rank-dependent dim 0 is LEGAL with splits (that is the point);
    # rank-dependent trailing dims are a negotiated typed error.
    if size > 1:
        y = np.zeros((rank + 1, 2), dtype=np.float32)
        vr = [0] * size
        vr[rank] = rank + 1
        out = eng.alltoall(y, splits=vr, name="a2a.selfsend")
        assert out.shape == (rank + 1, 2), out.shape
        z = np.zeros((size, rank + 2), dtype=np.float32)
        try:
            eng.alltoall(z, name="a2a.mismatch")
            raise AssertionError("rank-dependent trailing dims accepted")
        except HorovodInternalError as e:
            assert "Mismatched" in str(e), str(e)


def scenario_alltoall_cached(rank, size, eng):
    # Steady-state variable-split loop: step 1 earns the cache slot
    # (splits are part of the signature), later steps replay the stored
    # size matrix via the slot bit — same hit-rate contract as the
    # allreduce steady loop.
    steps = 40
    sp = [(rank + d) % 3 + 1 for d in range(size)]
    exp_rows = sum((s + rank) % 3 + 1 for s in range(size))
    before = eng.stats()
    for i in range(steps):
        x = np.full((sum(sp), 2), float(rank + i), dtype=np.float32)
        out = eng.alltoall(x, name="a2a.steady", splits=sp)
        assert out.shape == (exp_rows, 2), out.shape
        off = 0
        for s in range(size):
            n = (s + rank) % 3 + 1
            assert np.all(out[off:off + n] == s + i), (i, s, out[off])
            off += n
    after = eng.stats()
    hits = after["cache_hits"] - before["cache_hits"]
    misses = after["cache_misses"] - before["cache_misses"]
    assert hits + misses == steps, (hits, misses)
    assert misses <= max(1, steps // 20), (
        f"alltoall cache hit rate {hits}/{steps}")
    # A DIFFERENT split vector under the same name must renegotiate
    # (signature mismatch), not replay the stale matrix.
    sp2 = [x + 1 for x in sp]
    x = np.full((sum(sp2), 2), 7.0, dtype=np.float32)
    out = eng.alltoall(x, name="a2a.steady", splits=sp2)
    assert out.shape[0] == sum((s + rank) % 3 + 2 for s in range(size))
    assert eng.stats()["cache_misses"] > after["cache_misses"]


def scenario_alltoall_wire(rank, size, eng):
    # Compressed wires on variable splits: fp32 wire is bitwise-verbatim
    # (checked against pairwise sends in alltoall_splits); lossy wires
    # must be DETERMINISTIC (repeat runs bitwise identical) and inside
    # each format's error envelope — including the rank's OWN block,
    # which round-trips the codec so output bytes never depend on which
    # rank data stayed on.
    rng = np.random.default_rng(6000 + rank)
    sp = [13 * ((rank + d) % 3) + 5 for d in range(size)]
    x = rng.standard_normal((sum(sp), 64)).astype(np.float32)
    exp_blocks = []
    for s in range(size):
        sps = [13 * ((s + d) % 3) + 5 for d in range(size)]
        rs = np.random.default_rng(6000 + s)
        xs = rs.standard_normal((sum(sps), 64)).astype(np.float32)
        off = sum(sps[:rank])
        exp_blocks.append(xs[off:off + sps[rank]])
    exp = np.concatenate(exp_blocks)
    scale = float(np.max(np.abs(exp))) + 1e-9
    s0 = eng.stats()
    for wd, tol in (("fp16", 2e-3), ("bf16", 2e-2), ("int8", 4e-2),
                    ("fp8", 1e-1)):
        a = eng.alltoall(x.copy(), name=f"a2aw.{wd}.a", splits=sp,
                         wire_dtype=wd)
        b = eng.alltoall(x.copy(), name=f"a2aw.{wd}.b", splits=sp,
                         wire_dtype=wd)
        assert a.tobytes() == b.tobytes(), (
            f"{wd}: alltoall repeat not deterministic")
        err = float(np.max(np.abs(a - exp))) / scale
        assert err < tol, (wd, err)
    s1 = eng.stats()
    if size > 1:
        assert s1["wire_fp16_count"] > s0["wire_fp16_count"], s1
        assert s1["wire_int8_count"] > s0["wire_int8_count"], s1
        assert s1["quantize_ns"] > s0["quantize_ns"], s1
    # Non-fp32 payloads ignore the advisory: int64 rides verbatim.
    z = np.arange(size * 4, dtype=np.int64).reshape(size * 2, 2) + rank
    out = eng.alltoall(z.copy(), name="a2aw.int64", wire_dtype="int8")
    for s in range(size):
        blk = out[2 * s:2 * s + 2]
        zs = np.arange(size * 4, dtype=np.int64).reshape(size * 2, 2) + s
        assert np.array_equal(blk, zs[2 * rank:2 * rank + 2]), (s, blk)


def scenario_alltoall_shm_tcp(rank, size, eng):
    # Transport neutrality for the variable-split path: the shm flat
    # ring run must be BIT-IDENTICAL to the pure-TCP multi-channel run —
    # same committed matrix, same block layout, only the bytes' route
    # changes.
    assert eng.stats()["config"]["shm_enabled"], "expected shm on"

    def run(tag):
        outs = []
        for case in range(2):
            for d_i, dt in enumerate(_a2a_dtypes()):
                x, sp = _a2a_case(rank, size, dt, case)
                outs.append(eng.alltoall(
                    x.copy(), name=f"a2a.{tag}.c{case}.d{d_i}",
                    splits=sp))
        return outs

    shm_out = run("shm")
    basics.shutdown()
    os.environ["HOROVOD_SHM_DISABLE"] = "1"
    basics.init()
    assert not eng.stats()["config"]["shm_enabled"]
    tcp_out = run("tcp")
    for i, (a, b) in enumerate(zip(shm_out, tcp_out)):
        assert a.shape == b.shape and a.dtype == b.dtype, (i, a.shape)
        assert a.tobytes() == b.tobytes(), (
            f"case {i}: shm alltoall differs from TCP")


def scenario_alltoall_death(rank, size, eng):
    # Fault containment mid-alltoall: the highest rank dies abruptly
    # after a warm-up exchange; every surviving rank's next alltoall
    # must abort with a DESCRIPTIVE error naming the disconnect, not
    # hang (the abort tests pin HOROVOD_LINK_RETRIES=0).
    sp = [rank + 1] * size
    x = np.full((sum(sp), 3), float(rank), dtype=np.float32)
    out = eng.alltoall(x, name="pre_death", splits=sp)
    assert out.shape[0] == sum(s + 1 for s in range(size)), out.shape
    if rank == size - 1:
        os._exit(31)  # crash without shutdown handshake
    try:
        eng.alltoall(x, name="post_death", splits=sp)
    except HorovodInternalError as e:
        msg = str(e)
        assert ("disconnected" in msg or "lost connection" in msg
                or "could not reach" in msg), msg
        return
    raise AssertionError("expected HorovodInternalError after peer death")


def scenario_alltoall_fault(rank, size, eng):
    # Deterministic conn-reset mid-alltoall (HOROVOD_FAULT_INJECT, link
    # retries pinned to 0 by the test): every surviving rank aborts with
    # the CULPRIT rank named; the injected rank sees its own fault.
    frank, fstep, fkind = os.environ["HOROVOD_FAULT_INJECT"].split(":")
    frank, fstep = int(frank), int(fstep)
    sp = [2 * d + 1 for d in range(size)]
    steps = fstep + 5
    try:
        for i in range(steps):
            x = np.full((sum(sp), 8), float(rank + i), dtype=np.float32)
            out = eng.alltoall(x, name=f"a2a.fault.{i}", splits=sp)
            assert out.shape[0] == size * (2 * rank + 1), out.shape
            assert np.all(out[:1] == i), (i, out[0, 0])
    except HorovodInternalError as e:
        msg = str(e)
        if rank == frank:
            assert "fault injection" in msg, msg
        else:
            assert f"rank {frank}" in msg, msg
        print(f"worker rank={rank} got expected abort: {msg}", flush=True)
        return
    raise AssertionError(
        f"rank {rank}: expected HorovodInternalError after injected "
        f"{fkind} on rank {frank}")


def scenario_broadcast(rank, size, eng):
    for root in range(size):
        x = np.arange(10, dtype=np.float32) * (rank + 1)
        out = eng.broadcast(x, root_rank=root)
        assert np.allclose(out, np.arange(10, dtype=np.float32) * (root + 1))


def scenario_shape_mismatch(rank, size, eng):
    # Rank-dependent shapes must produce a typed error on every rank
    # (reference negative tests, test_tensorflow.py:249-320).
    x = np.zeros((rank + 2,), dtype=np.float32)
    try:
        eng.allreduce(x, name="bad_shape")
    except HorovodInternalError as e:
        assert "Mismatched" in str(e), str(e)
        return
    raise AssertionError("expected HorovodInternalError")


def scenario_dtype_mismatch(rank, size, eng):
    x = np.zeros((4,), dtype=np.float32 if rank == 0 else np.float64)
    try:
        eng.allreduce(x, name="bad_dtype")
    except HorovodInternalError as e:
        assert "Mismatched data types" in str(e), str(e)
        return
    raise AssertionError("expected HorovodInternalError")


def scenario_root_mismatch(rank, size, eng):
    x = np.zeros((4,), dtype=np.float32)
    try:
        eng.broadcast(x, root_rank=rank % size, name="bad_root")
        if size == 1:
            return  # single rank cannot disagree with itself
    except HorovodInternalError as e:
        assert "root rank" in str(e), str(e)
        return
    raise AssertionError("expected HorovodInternalError")


def scenario_timeline(rank, size, eng):
    scenario_allreduce(rank, size, eng)
    scenario_broadcast(rank, size, eng)


def scenario_mixed_stress(rank, size, eng):
    # Randomized burst of MIXED collective types enqueued in one go —
    # identical order on every rank (same seed), so the coordinator must
    # interleave fusion-eligible allreduces with gathers/broadcasts and
    # deliver every result correctly.  Exercises the negotiation pipeline
    # the way a real framework does: many ops of different kinds in
    # flight at once.
    rng = np.random.default_rng(1234)  # SAME on all ranks
    ops = rng.choice(["allreduce", "broadcast", "allgather"], size=40)
    handles, checks = [], []
    for i, kind in enumerate(ops):
        n = int(rng.integers(1, 600))
        if kind == "allreduce":
            arr = np.full((n,), float(rank + i), np.float32)
            handles.append(eng.enqueue_allreduce(arr, name=f"mix.{i}"))
            checks.append(("ar", float(sum(r + i for r in range(size)))))
        elif kind == "broadcast":
            root = int(rng.integers(0, size))
            arr = np.full((n,), float(rank * 100 + i), np.float32)
            handles.append(eng.enqueue_broadcast(arr, root, name=f"mix.{i}"))
            checks.append(("bc", float(root * 100 + i)))
        else:
            arr = np.full((2, 3), float(rank + i), np.float32)
            handles.append(eng.enqueue_allgather(arr, name=f"mix.{i}"))
            checks.append(("ag", i))
    for h, (kind, expect) in zip(handles, checks):
        out = eng.synchronize(h)
        if kind == "ag":
            assert out.shape == (2 * size, 3)
            for r in range(size):
                assert np.all(out[2 * r:2 * r + 2] == r + expect), (r, out)
        else:
            assert np.allclose(out, expect), (kind, out.ravel()[0], expect)


def scenario_restart(rank, size, eng):
    # Full lifecycle twice: shutdown tears down the coordinator, rings, and
    # background thread; a second init() must rebuild them on the same
    # coordinator address and produce correct collectives again (the
    # checkpoint-restart pattern without exec-ing a new process).
    def dbg(msg):
        if os.environ.get("HOROVOD_TEST_DEBUG"):
            print(f"[r{rank}] {msg}", file=sys.stderr, flush=True)

    x = np.full((8,), float(rank + 1), dtype=np.float32)
    assert np.allclose(eng.allreduce(x), size * (size + 1) / 2.0)
    dbg("allreduce1 done")
    basics.shutdown()
    dbg("shutdown done")
    basics.init()
    dbg("reinit done")
    # Same cached ctypes wrapper; what restarts is the NATIVE core behind
    # it (coordinator, rings, background thread).
    y = np.full((8,), float(rank + 2), dtype=np.float32)
    out = eng.allreduce(y)
    expected = sum(r + 2 for r in range(size))
    assert np.allclose(out, expected), (out[0], expected)


def scenario_worker_death(rank, size, eng):
    # Fault containment: the highest rank dies abruptly mid-run; every
    # surviving rank must get a DESCRIPTIVE HorovodInternalError (naming a
    # disconnect/lost peer), not a hang or a generic abort (VERDICT round 1
    # "transport robustness"; reference containment intent,
    # operations.cc:315-517).
    x = np.full((8,), float(rank + 1), dtype=np.float32)
    out = eng.allreduce(x, name="pre_death")
    assert np.allclose(out, size * (size + 1) / 2.0)
    if rank == size - 1:
        os._exit(31)  # crash without shutdown handshake
    try:
        eng.allreduce(x, name="post_death")
    except HorovodInternalError as e:
        msg = str(e)
        assert ("disconnected" in msg or "lost connection" in msg
                or "could not reach" in msg), msg
        return
    raise AssertionError("expected HorovodInternalError after peer death")


def scenario_wedged_peer(rank, size, eng):
    # A peer that is ALIVE but has stopped cycling (its cycle time is
    # cranked to 20 s in main(), vs the survivors' 2 ms): the coordinator
    # must burn its control patience LOUDLY — a "still waiting on control
    # frame from rank k" warning per idle timeout (socket.cc
    # RecvAllPatient) — then abort descriptively instead of stalling
    # silently for the whole patience window.
    import time

    if rank == size - 1:
        time.sleep(8)   # outlive the survivors' abort, prove we never died
        os._exit(0)     # skip the shutdown handshake; coordinator is gone
    x = np.full((8,), float(rank + 1), dtype=np.float32)
    try:
        eng.allreduce(x, name="stalled")
    except HorovodInternalError as e:
        msg = str(e)
        assert ("lost connection" in msg or "could not reach" in msg
                or "disconnected" in msg), msg
        return
    raise AssertionError("expected an abort while a peer is wedged")


def scenario_fault_steps(rank, size, eng):
    # Deterministic fault injection (HOROVOD_FAULT_INJECT=rank:step:kind,
    # set by the test): every rank runs a fixed allreduce-per-step loop;
    # the engine itself fires the fault on the injected rank's step-th
    # enqueue.  EVERY surviving rank must get a HorovodInternalError
    # naming the culprit rank within the fault timeout — the scenario that
    # used to wedge the whole world inside a blocking collective.
    frank, fstep, fkind = os.environ["HOROVOD_FAULT_INJECT"].split(":")
    frank, fstep = int(frank), int(fstep)
    if rank == frank and fkind == "hang":
        # The wedged rank blocks forever inside Wait once its background
        # loop freezes; let SIGALRM's default action kill it (expected
        # rc -SIGALRM) — a Python handler would never run while the main
        # thread is parked in a C call.
        import signal

        signal.alarm(12)
    steps = fstep + 5
    try:
        for i in range(steps):
            x = np.full((64,), float(rank + i), dtype=np.float32)
            out = eng.allreduce(x, name=f"fault.step.{i}")
            assert np.allclose(out, sum(r + i for r in range(size))), (i, out)
    except HorovodInternalError as e:
        msg = str(e)
        if rank == frank:
            # drop-conn: our own injected abort.
            assert "fault injection" in msg, msg
        else:
            assert f"rank {frank}" in msg, msg
        print(f"worker rank={rank} got expected abort: {msg}", flush=True)
        return
    raise AssertionError(
        f"rank {rank}: expected HorovodInternalError after injected "
        f"{fkind} on rank {frank}")


def scenario_cache_steady(rank, size, eng):
    # Steady-state identical-tensor loop (the data-parallel training
    # shape): step 1 fully negotiates and earns a cache slot; every later
    # step negotiates as ONE slot bit and ONE coordinator round trip.
    # HOROVOD_SMOKE_STEPS overrides the step count (ci.sh's bounded
    # 50-step control-plane gate rides this scenario).
    steps = int(os.environ.get("HOROVOD_SMOKE_STEPS", "100"))
    expected = size * (size + 1) / 2.0
    before = eng.stats()
    for _ in range(steps):
        x = np.full((1024,), float(rank + 1), dtype=np.float32)
        out = eng.allreduce(x, name="steady.t")
        assert np.allclose(out, expected), out[0]
    after = eng.stats()
    hits = after["cache_hits"] - before["cache_hits"]
    misses = after["cache_misses"] - before["cache_misses"]
    assert hits + misses == steps, (hits, misses, steps)
    # Only the first sight of the signature may miss: >= 98% at the
    # default 100 steps, and never more than the warm-up miss + 2% churn.
    assert misses <= max(1, steps // 50), (
        f"cache hit rate {hits / float(steps):.3f} ({hits}/{steps})")
    # The ISSUE's steady-state bound: <= 1 coordinator round trip per
    # cycle/step (1.5 allows the rare idle heartbeat landing mid-loop).
    rts = after["control_round_trips"] - before["control_round_trips"]
    per_step = rts / float(steps)
    assert per_step <= 1.5, (
        f"{per_step:.2f} control round trips per step (want ~1)")
    # Steady-state control frames are a few dozen bytes (slot bitvector +
    # framing), nowhere near a serialized per-tensor Request stream.
    tx_per_step = (after["negotiation_bytes_tx"]
                   - before["negotiation_bytes_tx"]) / float(steps)
    if rank != 0:
        assert tx_per_step < 128, f"{tx_per_step:.0f} tx bytes/step"


def scenario_cache_invalidate(rank, size, eng):
    # Same tensor name renegotiated with a new shape, then a new dtype:
    # each change must evict the slot and renegotiate (never reuse the
    # stale layout), and hits must resume on the new signature.
    before = eng.stats()
    expected = size * (size + 1) / 2.0
    a = np.full((8,), float(rank + 1), dtype=np.float32)
    assert np.allclose(eng.allreduce(a, name="inv.t"), expected)   # miss
    assert np.allclose(eng.allreduce(a, name="inv.t"), expected)   # hit
    b = np.full((4, 2), float(rank + 1), dtype=np.float32)
    assert np.allclose(eng.allreduce(b, name="inv.t"), expected)   # evict
    assert np.allclose(eng.allreduce(b, name="inv.t"), expected)   # hit
    c = np.full((4, 2), float(rank + 1), dtype=np.float64)
    assert np.allclose(eng.allreduce(c, name="inv.t"), expected)   # evict
    after = eng.stats()
    assert after["cache_evictions"] - before["cache_evictions"] >= 2, (
        before, after)
    assert after["cache_hits"] - before["cache_hits"] >= 2, (before, after)
    assert after["cache_misses"] - before["cache_misses"] >= 3, (
        before, after)
    # A fused burst straight after the churn: the fusion buffer must pack
    # the NEW layouts (a stale cached response here would corrupt offsets).
    handles = [
        eng.enqueue_allreduce(
            np.full((16,), float(rank + i), dtype=np.float32),
            name=f"inv.fused.{i}")
        for i in range(8)
    ]
    for i, h in enumerate(handles):
        out = eng.synchronize(h)
        assert np.allclose(out, sum(r + i for r in range(size))), (i, out)


def scenario_cache_disabled(rank, size, eng):
    # HOROVOD_CACHE_CAPACITY=0 (pinned by the test): the pre-cache
    # negotiation path must stay fully intact — correct values, zero
    # cache activity.
    before = eng.stats()
    expected = size * (size + 1) / 2.0
    for _ in range(20):
        x = np.full((64,), float(rank + 1), dtype=np.float32)
        assert np.allclose(eng.allreduce(x, name="nc.t"), expected)
    after = eng.stats()
    assert after["cache_hits"] == before["cache_hits"], (before, after)
    assert after["cache_misses"] == before["cache_misses"], (before, after)
    assert after["cache_evictions"] == before["cache_evictions"]


def scenario_cache_restart(rank, size, eng):
    # Clean shutdown + re-Init must start from an EMPTY cache on every
    # rank: the first post-restart step of a previously cached tensor is
    # a full renegotiation (a stale slot id replayed into the new world
    # would execute the wrong response).
    expected = size * (size + 1) / 2.0
    for _ in range(3):
        x = np.full((8,), float(rank + 1), dtype=np.float32)
        assert np.allclose(eng.allreduce(x, name="cr.t"), expected)
    s1 = eng.stats()
    basics.shutdown()
    basics.init()
    x = np.full((8,), float(rank + 1), dtype=np.float32)
    assert np.allclose(eng.allreduce(x, name="cr.t"), expected)
    s2 = eng.stats()
    assert s2["cache_hits"] == s1["cache_hits"], "stale cache slot replayed"
    assert s2["cache_misses"] == s1["cache_misses"] + 1, (s1, s2)
    # ... and the new world's cache warms up again.
    assert np.allclose(eng.allreduce(x.copy(), name="cr.t"), expected)
    s3 = eng.stats()
    assert s3["cache_hits"] == s2["cache_hits"] + 1, (s2, s3)


def scenario_cache_fault_reinit(rank, size, eng):
    # Elastic abort path (PR 1) with a HOT cache: HOROVOD_FAULT_INJECT
    # drop-conn kills the world mid-steady-state; after the abort an
    # in-process shutdown + re-Init must start from an EMPTY cache on
    # every rank — recovery never replays stale slot ids — and the
    # recovered world must produce correct values and warm up again.
    expected = size * (size + 1) / 2.0
    try:
        for _ in range(8):
            x = np.full((16,), float(rank + 1), dtype=np.float32)
            out = eng.allreduce(x, name="cf.t")
            assert np.allclose(out, expected), out[0]
        raise AssertionError("expected an abort from the injected fault")
    except HorovodInternalError:
        pass
    basics.shutdown()
    basics.init()
    s1 = eng.stats()
    x = np.full((16,), float(rank + 1), dtype=np.float32)
    assert np.allclose(eng.allreduce(x, name="cf.t"), expected)
    s2 = eng.stats()
    assert s2["cache_hits"] == s1["cache_hits"], "stale cache slot replayed"
    assert s2["cache_misses"] == s1["cache_misses"] + 1, (s1, s2)
    for _ in range(3):
        assert np.allclose(eng.allreduce(x.copy(), name="cf.t"), expected)
    s3 = eng.stats()
    assert s3["cache_hits"] == s2["cache_hits"] + 3, (s2, s3)


def scenario_stale_epoch(rank, size, eng):
    # Structural stale-epoch rejection: HOROVOD_FAULT_INJECT=1:2:stale-epoch
    # makes rank 1 prefix one control frame with a duplicate stamped
    # epoch-1 (a dead incarnation's delayed message).  The coordinator must
    # DROP it — counting it in stats()["stale_epoch_msgs"] — and negotiate
    # from the genuine frame only, so every collective still produces
    # correct values and nothing desyncs.
    expected = size * (size + 1) / 2.0
    for i in range(6):
        x = np.full((16,), float(rank + 1), dtype=np.float32)
        out = eng.allreduce(x, name=f"se.{i}")
        assert np.allclose(out, expected), (i, out[0], expected)
    s = eng.stats()
    if rank == 0:
        assert s["stale_epoch_msgs"] == 1, s
    else:
        assert s["stale_epoch_msgs"] == 0, s
    assert eng.epoch() >= 1


def _parity_cases(rank, size):
    """Deterministic per-rank payloads covering every wire dtype, odd and
    prime element counts SMALLER than channels*size (empty channel slices
    and segments), plus buffers big enough to actually shard across the
    channel fan-out (>= kMinBytesPerChannel per channel)."""
    rng = np.random.default_rng(1000 + rank)
    cases = []
    dtypes = [np.float32, np.float64, np.int32, np.int64, np.uint8,
              np.int8, np.uint16, np.int16, np.float16]
    try:
        import ml_dtypes

        dtypes.append(ml_dtypes.bfloat16)
    except ImportError:
        pass
    # bfloat16 registers as a structured ('V') dtype in numpy, so "is
    # this a float" must go through the dtype NAME, not kind — with the
    # kind check alone the bf16 payloads silently degrade to small
    # integers that never round, and the parity test passes vacuously.
    def is_float(dt):
        return np.dtype(dt).kind == "f" or np.dtype(dt).name == "bfloat16"

    ops = ["sum", "min", "max"]
    for d, dt in enumerate(dtypes):
        for n in (1, 3, 7, 13, 61):
            if is_float(dt):
                arr = rng.standard_normal(n).astype(dt)
            else:
                arr = rng.integers(0, 7, n).astype(dt)
            cases.append((arr, ops[(d + n) % 3]))
    # prod stays in range on tiny values
    cases.append(((rng.integers(1, 3, 17)).astype(np.float32), "prod"))
    cases.append(((rng.integers(1, 3, 5)).astype(np.int64), "prod"))
    cases.append((rng.integers(0, 2, 97) > 0, "sum"))   # bool or
    cases.append((rng.integers(0, 2, 11) > 0, "min"))   # bool and
    # Large enough to engage real multi-channel sharding (fp32 4 MB ->
    # 4 channels; 16-bit floats 1 MB -> 2) and the chunk pipeline.
    cases.append((rng.standard_normal(1 << 20).astype(np.float32), "sum"))
    cases.append((rng.standard_normal(1 << 19).astype(np.float16), "sum"))
    try:
        import ml_dtypes

        cases.append(
            (rng.standard_normal(1 << 19).astype(ml_dtypes.bfloat16),
             "sum"))
    except ImportError:
        pass
    cases.append((rng.integers(0, 100, 200003).astype(np.int32), "sum"))
    return cases


def _parity_run(eng, cases, tag):
    outs = []
    for i, (arr, op) in enumerate(cases):
        outs.append(eng.allreduce(arr.copy(), name=f"par.{tag}.{i}",
                                  red_op=op))
    # Fused burst: same dtype back-to-back so the coordinator fuses them
    # into one ring collective over the shared fusion buffer.
    handles = [
        eng.enqueue_allreduce(
            np.asarray(cases[0][0], np.float32).copy() + i,
            name=f"par.{tag}.fused.{i}")
        for i in range(9)
    ]
    outs.extend(eng.synchronize(h) for h in handles)
    return outs


def scenario_channels_parity(rank, size, eng):
    # Bit-exactness of the multi-channel data plane: the run under the
    # test-set HOROVOD_NUM_CHANNELS (>1: streaming cascade, sharded rings)
    # must be BIT-IDENTICAL to channels=1 (the stepped legacy path) for
    # every dtype/op — channel shards slice within ring segments, so the
    # per-element reduction order is fan-out-independent by construction.
    cases = _parity_cases(rank, size)
    multi = _parity_run(eng, cases, "n")
    stats = eng.stats()
    assert stats["num_channels"] == int(
        os.environ.get("HOROVOD_NUM_CHANNELS", "0") or 0), stats
    basics.shutdown()
    os.environ["HOROVOD_NUM_CHANNELS"] = "1"
    basics.init()
    single = _parity_run(eng, cases, "1")
    assert eng.stats()["num_channels"] == 1
    for i, (m, s) in enumerate(zip(multi, single)):
        assert m.dtype == s.dtype and m.shape == s.shape, (i, m.shape)
        assert m.tobytes() == s.tobytes(), (
            f"case {i}: channels=N differs from channels=1 "
            f"(dtype {m.dtype})")
    # Spot-check against numpy for the order-independent ops (min/max are
    # bitwise order-free; integer sums are exact).  Every rank's payload
    # is deterministic, so each rank rebuilds all peers' inputs locally.
    peer_cases = [cases if r == rank else _parity_cases(r, size)
                  for r in range(size)]
    for i, (arr, op) in enumerate(cases):
        floatish = (np.dtype(arr.dtype).kind == "f"
                    or np.dtype(arr.dtype).name == "bfloat16")
        if op not in ("min", "max") and floatish:
            continue  # rounding-order-sensitive: parity covers these
        ref_in = [np.asarray(peer_cases[r][i][0]) for r in range(size)]
        if np.dtype(arr.dtype).kind == "b":
            # Wire semantics: sum/max = logical or, min/prod = logical and.
            stack = np.stack(ref_in)
            ref = stack.any(0) if op in ("sum", "max") else stack.all(0)
            assert np.array_equal(single[i], ref), (i, op)
            continue
        stack = np.stack([np.asarray(a, np.float64) for a in ref_in])
        ref = {"sum": stack.sum(0), "min": stack.min(0),
               "max": stack.max(0), "prod": stack.prod(0)}[op]
        got = np.asarray(single[i], np.float64)
        assert np.allclose(got, ref), (i, op, arr.dtype)


def scenario_channels_stats(rank, size, eng):
    # Data-plane counters: an 8 MB allreduce must move ~2(N-1)/N of its
    # payload per rank over the ring sockets, split wall time into
    # wire/reduce, and yield a positive derived bus bandwidth.
    before = eng.stats()
    n = (8 << 20) // 4
    x = np.ones(n, dtype=np.float32)
    out = eng.allreduce(x, name="dp.stats")
    assert np.allclose(out, float(size))
    after = eng.stats()
    nbytes = n * 4
    expect_wire = nbytes * 2 * (size - 1) / size
    dtx = after["data_bytes_tx"] - before["data_bytes_tx"]
    drx = after["data_bytes_rx"] - before["data_bytes_rx"]
    # Ring segment remainders make the exact figure off by < 1%.
    assert abs(dtx - expect_wire) < 0.02 * expect_wire + 4096, (
        dtx, expect_wire)
    assert abs(drx - expect_wire) < 0.02 * expect_wire + 4096, (
        drx, expect_wire)
    assert after["wire_ns"] > before["wire_ns"]
    assert after["reduce_ns"] > before["reduce_ns"]
    assert after["allreduce_bytes"] - before["allreduce_bytes"] == nbytes
    assert after["allreduce_ns"] > before["allreduce_ns"]
    assert after["allreduce_bus_bw_bytes_per_sec"] > 0
    want_ch = int(os.environ.get("HOROVOD_NUM_CHANNELS", "0") or 0)
    if want_ch:
        assert after["num_channels"] == want_ch, after


def scenario_shm_parity(rank, size, eng):
    # Transport neutrality: the shm flat ring (the default on a single
    # host) must be BIT-IDENTICAL to the pure-TCP plane
    # (HOROVOD_SHM_DISABLE=1) for every dtype/op — same vrank/rsize, same
    # segments, same fold order; only the bytes' route changes.  This
    # also covers the small-tensor star path: under the default
    # HOROVOD_ALGO_THRESHOLD the sub-32 KB cases take the star fold on
    # the shm run (the TCP run has no star edges), so identical bytes
    # prove the star emulates the ring's exact operand sequence.
    assert eng.stats()["config"]["shm_enabled"], "expected shm on"
    cases = _parity_cases(rank, size)
    before = eng.stats()
    shm_out = _parity_run(eng, cases, "shm")
    after = eng.stats()
    assert after["shm_bytes_tx"] > before["shm_bytes_tx"], after
    assert after["intra_host_bytes"] > before["intra_host_bytes"], after
    assert after["algo_small_count"] > before["algo_small_count"], after
    basics.shutdown()
    os.environ["HOROVOD_SHM_DISABLE"] = "1"
    basics.init()
    assert not eng.stats()["config"]["shm_enabled"]
    s0 = eng.stats()
    tcp_out = _parity_run(eng, cases, "tcp")
    s1 = eng.stats()
    assert s1["shm_bytes_tx"] == s0["shm_bytes_tx"], "TCP run used shm?"
    assert s1["algo_small_count"] == s0["algo_small_count"], s1
    for i, (m, s) in enumerate(zip(shm_out, tcp_out)):
        assert m.dtype == s.dtype and m.shape == s.shape, (i, m.shape)
        assert m.tobytes() == s.tobytes(), (
            f"case {i}: shm differs from TCP (dtype {m.dtype})")


def scenario_algo_parity(rank, size, eng):
    # Size-based algorithm selection is value-neutral: a run with the
    # star path engaged for everything it can reach (the harness sets
    # HOROVOD_ALGO_THRESHOLD=1 MB) is bit-identical to the same run with
    # it disabled (threshold 0 → pure ring).  Counters are process-
    # cumulative, so deltas prove which path actually ran.
    cases = _parity_cases(rank, size)
    b0 = eng.stats()
    star_out = _parity_run(eng, cases, "star")
    b1 = eng.stats()
    assert b1["algo_small_count"] > b0["algo_small_count"], b1
    basics.shutdown()
    os.environ["HOROVOD_ALGO_THRESHOLD"] = "0"
    basics.init()
    assert eng.stats()["config"]["algo_threshold"] == 0
    r0 = eng.stats()
    ring_out = _parity_run(eng, cases, "ring")
    r1 = eng.stats()
    assert r1["algo_small_count"] == r0["algo_small_count"], r1
    assert r1["algo_ring_count"] > r0["algo_ring_count"], r1
    for i, (a, b) in enumerate(zip(star_out, ring_out)):
        assert a.tobytes() == b.tobytes(), (
            f"case {i}: star path differs from ring (dtype {a.dtype})")


def scenario_shm_stats(rank, size, eng):
    # The shm/hierarchy counters: a 4 MB allreduce rides the shm ring
    # (ALGO_RING), a 256 B one takes the star (ALGO_SMALL, default 32 KB
    # threshold); shm bytes count into data bytes, and the committed
    # topology is one host spanning the world.
    before = eng.stats()
    n = (4 << 20) // 4
    big = eng.allreduce(np.ones(n, np.float32), name="shm.stats.big")
    assert np.allclose(big, float(size))
    small = eng.allreduce(np.ones(64, np.float32), name="shm.stats.small")
    assert np.allclose(small, float(size))
    after = eng.stats()
    assert after["topology"] == {"hosts": 1, "local_ranks": size}, after
    assert after["config"]["shm_enabled"] is True, after
    assert after["config"]["algo_threshold"] == 32 << 10, after
    d_shm_tx = after["shm_bytes_tx"] - before["shm_bytes_tx"]
    d_shm_rx = after["shm_bytes_rx"] - before["shm_bytes_rx"]
    d_data_tx = after["data_bytes_tx"] - before["data_bytes_tx"]
    assert d_shm_tx > 0 and d_shm_rx > 0, after
    assert d_shm_tx <= d_data_tx, (d_shm_tx, d_data_tx)
    d_intra = after["intra_host_bytes"] - before["intra_host_bytes"]
    assert d_intra == d_shm_tx + d_shm_rx, (d_intra, d_shm_tx, d_shm_rx)
    assert after["algo_ring_count"] - before["algo_ring_count"] >= 1, after
    assert after["algo_small_count"] - before["algo_small_count"] >= 1, \
        after


def scenario_hier_exact(rank, size, eng):
    # Two-level is a DIFFERENT (deterministic) reduction order than the
    # flat ring, so fp sums need not match it bitwise — but the topology
    # must be deterministic (identical bytes when the same collectives
    # repeat) and order-free ops (integer sums, min/max, bool) must equal
    # the numpy reference exactly.
    st = eng.stats()
    assert st["topology"]["hosts"] > 1, st
    cases = _parity_cases(rank, size)
    out1 = _parity_run(eng, cases, "h1")
    out2 = _parity_run(eng, cases, "h2")
    for i, (a, b) in enumerate(zip(out1, out2)):
        assert a.tobytes() == b.tobytes(), (
            f"case {i}: two-level not deterministic (dtype {a.dtype})")
    peer_cases = [cases if r == rank else _parity_cases(r, size)
                  for r in range(size)]
    for i, (arr, op) in enumerate(cases):
        floatish = (np.dtype(arr.dtype).kind == "f"
                    or np.dtype(arr.dtype).name == "bfloat16")
        if op not in ("min", "max") and floatish:
            # Rounding-order-sensitive: allclose only.
            stack = np.stack([np.asarray(peer_cases[r][i][0], np.float64)
                              for r in range(size)])
            ref = {"sum": stack.sum(0), "prod": stack.prod(0)}[op]
            assert np.allclose(np.asarray(out1[i], np.float64), ref,
                               rtol=5e-2, atol=1e-1), (i, op, arr.dtype)
            continue
        ref_in = [np.asarray(peer_cases[r][i][0]) for r in range(size)]
        if np.dtype(arr.dtype).kind == "b":
            stack = np.stack(ref_in)
            ref = stack.any(0) if op in ("sum", "max") else stack.all(0)
            assert np.array_equal(out1[i], ref), (i, op)
            continue
        stack = np.stack([np.asarray(a, np.float64) for a in ref_in])
        ref = {"sum": stack.sum(0), "min": stack.min(0),
               "max": stack.max(0), "prod": stack.prod(0)}[op]
        got = np.asarray(out1[i], np.float64)
        assert np.allclose(got, ref), (i, op, arr.dtype)
    assert eng.stats()["intra_host_bytes"] > 0


def scenario_wire_parity(rank, size, eng):
    # The fp32-wire default contract: HOROVOD_WIRE_DTYPE unset, =fp32,
    # and a per-tensor wire_dtype="fp32" override must all produce
    # BIT-IDENTICAL results (the wire field rides the control plane; the
    # data plane is untouched).  Runs the full parity corpus: every
    # dtype, sum/min/max/prod, prime counts, fused bursts, sharded MBs.
    cases = _parity_cases(rank, size)
    base = _parity_run(eng, cases, "wdef")
    s = eng.stats()
    assert s["config"]["wire_dtype"] == "fp32", s["config"]
    assert s["wire_fp16_count"] == 0 and s["wire_int8_count"] == 0, s
    assert s["compressed_bytes_tx"] == 0, s
    basics.shutdown()
    os.environ["HOROVOD_WIRE_DTYPE"] = "fp32"
    basics.init()
    explicit = _parity_run(eng, cases, "wfp32")
    # Per-tensor explicit override on top.
    outs3 = []
    for i, (arr, op) in enumerate(cases):
        h = eng.enqueue_allreduce(arr.copy(), name=f"wovr.{i}",
                                  red_op=op, wire_dtype="fp32")
        outs3.append(eng.synchronize(h))
    for i, (a, b) in enumerate(zip(base, explicit)):
        assert a.tobytes() == b.tobytes(), (
            f"case {i}: HOROVOD_WIRE_DTYPE=fp32 differs from default "
            f"(dtype {a.dtype})")
    for i, (a, c) in enumerate(zip(base, outs3)):
        assert a.tobytes() == c.tobytes(), (
            f"case {i}: wire_dtype='fp32' override differs from default")


def scenario_wire_values(rank, size, eng):
    # Compressed wires are value-lossy but bounded and DETERMINISTIC:
    # repeat runs must be bitwise identical, and results must sit within
    # each format's error envelope of the fp32 reference.
    rng = np.random.default_rng(4000 + rank)
    x = rng.standard_normal(1 << 18).astype(np.float32)
    ref = eng.allreduce(x.copy(), name="wv.ref")
    scale = float(np.max(np.abs(ref))) + 1e-9
    for wd, tol in (("fp16", 2e-3), ("bf16", 2e-2), ("int8", 4e-2),
                    ("fp8", 1e-1)):
        a = eng.allreduce(x.copy(), name=f"wv.{wd}.a", wire_dtype=wd)
        b = eng.allreduce(x.copy(), name=f"wv.{wd}.b", wire_dtype=wd)
        assert a.tobytes() == b.tobytes(), (
            f"{wd}: same-world repeat not deterministic")
        err = float(np.max(np.abs(a - ref))) / scale
        assert err < tol, (wd, err)
    # Non-finite propagation: a mixed-precision overflow element must
    # surface as NaNs in its quantized block on EVERY rank — never
    # silently zero the gradient out from under an overflow detector.
    bad = np.ones(1 << 12, dtype=np.float32)
    if rank == 0:
        bad[17] = np.inf
    h = eng.enqueue_allreduce(bad, name="wv.inf", red_op="sum",
                              wire_dtype="int8")
    out = eng.synchronize(h)
    assert np.isnan(out).any(), "overflow silently vanished on the wire"
    # non-fp32 payloads are never compressed even when the env asks:
    # int64 sums stay exact under a global int8 wire.
    z = (np.arange(257) + rank).astype(np.int64)
    h = eng.enqueue_allreduce(z.copy(), name="wv.int64", red_op="sum",
                              wire_dtype="int8")
    out = eng.synchronize(h)
    exp = size * np.arange(257, dtype=np.int64) + size * (size - 1) // 2
    assert np.array_equal(out, exp), out[:4]


def scenario_wire_stats(rank, size, eng):
    # Counter contract on a 16 MB fp32 allreduce: int8 must cut this
    # rank's data_bytes_tx >= 3.3x vs the fp32 wire (the wire payload is
    # ~1/4 + per-chunk scale headers), wire_bytes_saved/compressed_
    # bytes_tx/quantize_ns must move, per-mode counts must count, and
    # the effective busbw numerator (allreduce_bytes) must stay LOGICAL.
    n = (16 << 20) // 4
    x = np.ones(n, dtype=np.float32)
    s0 = eng.stats()
    out = eng.allreduce(x.copy(), name="ws.fp32")
    assert np.allclose(out, float(size))
    s1 = eng.stats()
    out = eng.allreduce(x.copy(), name="ws.int8", wire_dtype="int8")
    assert np.allclose(out, float(size), atol=1e-2)
    s2 = eng.stats()
    out = eng.allreduce(x.copy(), name="ws.fp16", wire_dtype="fp16")
    assert np.allclose(out, float(size), atol=1e-2)
    s3 = eng.stats()
    fp32_tx = s1["data_bytes_tx"] - s0["data_bytes_tx"]
    int8_tx = s2["data_bytes_tx"] - s1["data_bytes_tx"]
    fp16_tx = s3["data_bytes_tx"] - s2["data_bytes_tx"]
    assert fp32_tx > 0 and int8_tx > 0
    ratio8 = int8_tx / fp32_tx
    assert ratio8 <= 0.30, f"int8 wire ratio {ratio8:.3f} (want <= 0.30)"
    assert fp32_tx / int8_tx >= 3.3, (fp32_tx, int8_tx)
    assert 0.4 <= fp16_tx / fp32_tx <= 0.6, fp16_tx / fp32_tx
    # logical (pre-compression) bytes: identical for all three runs.
    assert s2["allreduce_bytes"] - s1["allreduce_bytes"] == n * 4, s2
    assert s3["allreduce_bytes"] - s2["allreduce_bytes"] == n * 4, s3
    assert s2["wire_bytes_saved"] > s1["wire_bytes_saved"], s2
    assert s2["compressed_bytes_tx"] > s1["compressed_bytes_tx"], s2
    assert s2["quantize_ns"] > s1["quantize_ns"], s2
    assert s1["compressed_bytes_tx"] == s0["compressed_bytes_tx"], s1
    assert s2["wire_int8_count"] - s1["wire_int8_count"] == 1, s2
    assert s3["wire_fp16_count"] - s2["wire_fp16_count"] == 1, s3
    assert s1["wire_int8_count"] == s0["wire_int8_count"], s1


def scenario_wire_mismatch(rank, size, eng):
    # Ranks disagreeing on the wire format must get the negotiated typed
    # error naming both formats — never a garbled ring.
    x = np.zeros(64, dtype=np.float32)
    try:
        h = eng.enqueue_allreduce(
            x, name="bad_wire",
            wire_dtype="int8" if rank == 0 else "fp32")
        eng.synchronize(h)
        if size == 1:
            return
    except HorovodInternalError as e:
        msg = str(e)
        assert "Mismatched wire dtypes" in msg, msg
        assert "int8" in msg and "fp32" in msg, msg
        return
    raise AssertionError("expected HorovodInternalError")


def scenario_wire_fused(rank, size, eng):
    # Fused bursts under a global compressed wire: same-wire responses
    # fuse and the whole batch reduces through one quantized ring; the
    # cache replays the committed wire on later steps (hits, not
    # renegotiation).
    assert os.environ.get("HOROVOD_WIRE_DTYPE") == "int8"
    assert eng.stats()["config"]["wire_dtype"] == "int8"
    for step in range(3):
        handles = [
            eng.enqueue_allreduce(
                np.full((4096,), float(rank + i), dtype=np.float32),
                name=f"wf.{i}")
            for i in range(8)
        ]
        # int8 absolute error bound: the fused block's max |value| is
        # size-1+7; each of the ~size quantization hops contributes up
        # to maxabs/127 — scale the tolerance accordingly.
        atol = (size + 6) / 127.0 * (size + 1) * 1.5
        for i, h in enumerate(handles):
            out = eng.synchronize(h)
            exp = sum(r + i for r in range(size))
            assert np.allclose(out, exp, atol=atol), (
                step, i, out[0], exp, atol)
    s = eng.stats()
    assert s["wire_int8_count"] > 0, s
    assert s["cache_hits"] > 0, s


def scenario_wire_tune(rank, size, eng):
    # The wire dtype as the 6th live-tunable knob: a TUNE frame flips the
    # default between cycles on EVERY rank; enqueues after it negotiate
    # (and execute) under the new wire; stats()["config"] tracks it.
    import time

    # Every baseline is read HERE, before the first collective: rank 0
    # cannot set the knob, and no rank can enqueue under the new wire (which
    # evicts the slot on every rank, through the coordinator's frame), until
    # that collective has had this rank's part.  Read after it, a baseline
    # on a rank a few ms late already holds what it is to be compared with.
    base = eng.stats()
    assert base["config"]["wire_dtype"] == "fp32"
    x = np.ones(1 << 16, dtype=np.float32)
    assert np.allclose(eng.allreduce(x.copy(), name="wt.t"), float(size))

    def wait_for_wire(name):
        # The value, not a count of TUNE frames above a late baseline.
        deadline = time.time() + 20
        while eng.stats()["config"]["wire_dtype"] != name:
            assert time.time() < deadline, "TUNE frame never applied"
            time.sleep(0.002)

    if rank == 0:
        assert eng.autotune_set(wire_dtype=3)  # int8
    wait_for_wire("int8")
    # Same name, new signature (wire changed): the slot evicts and the
    # collective renegotiates + executes under int8.
    out = eng.allreduce(x.copy(), name="wt.t")
    assert np.allclose(out, float(size), atol=1e-2)
    s1 = eng.stats()
    assert s1["wire_int8_count"] - base["wire_int8_count"] == 1, s1
    assert s1["cache_evictions"] > base["cache_evictions"], s1
    # ... and back to fp32: bitwise-identical to an untouched run.
    if rank == 0:
        assert eng.autotune_set(wire_dtype=0)
    wait_for_wire("fp32")
    assert eng.stats()["tune_trials"] >= base["tune_trials"] + 2
    out = eng.allreduce(x.copy(), name="wt.t")
    assert np.array_equal(out, np.full_like(x, float(size))), out[:4]


def scenario_wire_death(rank, size, eng):
    # Worker death MID-COMPRESSED-ALLREDUCE: the highest rank dies while
    # an int8-wire 8 MB allreduce is in flight; every survivor must get
    # the clean attributed abort (a dead peer EOFs every channel of the
    # quantized ring exactly like the uncompressed one).
    assert eng.stats()["config"]["wire_dtype"] == "int8"
    x = np.full((1 << 16,), float(rank + 1), dtype=np.float32)
    out = eng.allreduce(x, name="wd.pre")
    # int8 tolerance: ~maxabs/127 per quantization hop.
    assert np.allclose(out, size * (size + 1) / 2.0,
                       atol=0.1 * size * size), out[0]
    assert eng.stats()["wire_int8_count"] >= 1
    if rank == size - 1:
        os._exit(31)  # crash without shutdown handshake
    try:
        big = np.full(((8 << 20) // 4,), 1.0, dtype=np.float32)
        eng.allreduce(big, name="wd.mid")
        # One allreduce may complete from buffered data; the next cannot.
        eng.allreduce(big, name="wd.mid2")
    except HorovodInternalError as e:
        msg = str(e)
        assert ("disconnected" in msg or "lost connection" in msg
                or "could not reach" in msg or "closed" in msg), msg
        return
    raise AssertionError("expected HorovodInternalError after peer death")


def scenario_wire_sparse(rank, size, eng):
    # Top-k sparse allreduce with error feedback over the allgather
    # path: selection is deterministic, the mean of the selected entries
    # is exact, unsent mass accumulates in the residual and drains on
    # later steps; sparse_count tracks completions.
    from horovod_tpu.runtime import sparse

    n = 1000
    x = np.zeros(n, dtype=np.float32)
    x[7] = 10.0 + rank          # always the biggest entry
    x[1:4] = 0.25               # never in the top-1%
    s0 = eng.stats()
    out = sparse.sparse_allreduce_topk(x, name="sp.t", ratio=0.001,
                                       average=True)
    # k = 1: only index 7 ships; its mean is exact.
    exp7 = float(np.mean([10.0 + r for r in range(size)]))
    assert np.isclose(out[7], exp7), (out[7], exp7)
    assert np.all(out[1:4] == 0.0), out[1:4]
    assert sparse.residual_norm("sp.t") > 0.0
    assert eng.stats()["sparse_count"] - s0["sparse_count"] == 1
    # Second step with zero gradient: the residual (0.25s) is the whole
    # signal; top-1 selects one of them and ships it.
    out2 = sparse.sparse_allreduce_topk(np.zeros(n, np.float32),
                                       name="sp.t", ratio=0.001,
                                       average=True)
    assert np.sum(np.abs(out2)) > 0.0, "residual never drained"
    # No error feedback: the registry holds nothing for this name.
    sparse.sparse_allreduce_topk(x, name="sp.nef", ratio=0.001,
                                 error_feedback=False, average=True)
    assert sparse.residual_norm("sp.nef") == 0.0


def scenario_spin(rank, size, eng):
    # Keep allreducing until killed (the shm leak test SIGKILLs the job
    # mid-collective and then inspects /dev/shm); bounded so an un-killed
    # run still exits.
    deadline = __import__("time").monotonic() + 60
    i = 0
    while __import__("time").monotonic() < deadline:
        x = np.full((1 << 14,), float(rank + 1), dtype=np.float32)
        out = eng.allreduce(x, name=f"spin.{i % 8}")
        assert np.allclose(out, size * (size + 1) / 2.0)
        i += 1


def scenario_channels_big(rank, size, eng):
    # A few 8 MB allreduces: enough payload that every configured channel
    # carries a shard (timeline shows the per-channel RING_CH tracks).
    n = (8 << 20) // 4
    for i in range(3):
        x = np.full(n, float(rank + i), dtype=np.float32)
        out = eng.allreduce(x, name=f"dp.big.{i}")
        assert np.allclose(out, sum(r + i for r in range(size))), out[0]


SCENARIOS = {
    "allreduce": scenario_allreduce,
    "fused": scenario_fused,
    "allgather": scenario_allgather,
    "broadcast": scenario_broadcast,
    "reduce_ops": scenario_reduce_ops,
    "red_op_mismatch": scenario_red_op_mismatch,
    "reducescatter": scenario_reducescatter,
    "alltoall": scenario_alltoall,
    "alltoall_indivisible": scenario_alltoall_indivisible,
    "alltoall_splits": scenario_alltoall_splits,
    "alltoall_cached": scenario_alltoall_cached,
    "alltoall_wire": scenario_alltoall_wire,
    "alltoall_shm_tcp": scenario_alltoall_shm_tcp,
    "alltoall_death": scenario_alltoall_death,
    "alltoall_fault": scenario_alltoall_fault,
    "shape_mismatch": scenario_shape_mismatch,
    "dtype_mismatch": scenario_dtype_mismatch,
    "root_mismatch": scenario_root_mismatch,
    "timeline": scenario_timeline,
    "mixed_stress": scenario_mixed_stress,
    "restart": scenario_restart,
    "worker_death": scenario_worker_death,
    "wedged_peer": scenario_wedged_peer,
    "fault_steps": scenario_fault_steps,
    "cache_steady": scenario_cache_steady,
    "cache_invalidate": scenario_cache_invalidate,
    "cache_disabled": scenario_cache_disabled,
    "cache_restart": scenario_cache_restart,
    "cache_fault_reinit": scenario_cache_fault_reinit,
    "stale_epoch": scenario_stale_epoch,
    "channels_parity": scenario_channels_parity,
    "channels_stats": scenario_channels_stats,
    "channels_big": scenario_channels_big,
    "shm_parity": scenario_shm_parity,
    "algo_parity": scenario_algo_parity,
    "wire_parity": scenario_wire_parity,
    "wire_values": scenario_wire_values,
    "wire_stats": scenario_wire_stats,
    "wire_mismatch": scenario_wire_mismatch,
    "wire_fused": scenario_wire_fused,
    "wire_tune": scenario_wire_tune,
    "wire_death": scenario_wire_death,
    "wire_sparse": scenario_wire_sparse,
    "shm_stats": scenario_shm_stats,
    "hier_exact": scenario_hier_exact,
    "spin": scenario_spin,
    "all": None,
}


def scenario_subset(world_rank, _world_size, _eng_unused):
    # hvd.init(comm=[0, 2]) in a world of 3: members form their own
    # 2-rank communicator; the excluded rank becomes a world of one
    # (reference common/__init__.py:58-84, operations.cc:1469-1488).
    rank, size = basics.rank(), basics.size()
    eng = get_engine() if size > 1 else None
    if world_rank in (0, 2):
        assert size == 2, size
        assert rank == {0: 0, 2: 1}[world_rank], (world_rank, rank)
        x = np.full((16,), float(world_rank + 1), dtype=np.float32)
        out = eng.allreduce(x)
        assert np.allclose(out, 4.0), out  # 1 + 3: only members contribute
    else:
        assert size == 1 and rank == 0, (rank, size)
        assert basics.local_size() == 1
        # World of one: collectives really are identities.
        eng1 = get_engine()
        x = np.full((16,), 7.0, dtype=np.float32)
        assert np.array_equal(eng1.allreduce(x), x)


def main():
    scenario = sys.argv[1] if len(sys.argv) > 1 else "all"
    if scenario == "subset":
        world_rank = int(os.environ["HOROVOD_RANK"])
        basics.init(comm=[0, 2])
        scenario_subset(world_rank, int(os.environ["HOROVOD_SIZE"]), None)
        basics.shutdown()
        print(f"worker rank={world_rank} OK", flush=True)
        return
    if scenario == "wedged_peer":
        wr, ws = int(os.environ["HOROVOD_RANK"]), int(
            os.environ["HOROVOD_SIZE"])
        if wr == ws - 1:
            # Wedge THIS rank: its background loop wakes every 20 s, so
            # its control frames stop arriving at the coordinator.
            os.environ["HOROVOD_CYCLE_TIME"] = "20000"
    basics.init()
    rank, size = basics.rank(), basics.size()
    eng = get_engine()
    if scenario == "all":
        for name in ("allreduce", "fused", "allgather", "broadcast",
                     "reducescatter", "alltoall"):
            SCENARIOS[name](rank, size, eng)
    else:
        SCENARIOS[scenario](rank, size, eng)
    basics.shutdown()
    print(f"worker rank={rank} OK", flush=True)


if __name__ == "__main__":
    main()
