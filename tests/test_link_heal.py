"""Link self-healing tests (``linkheal`` marker).

The data plane's TCP channel cascades classify a mid-collective socket
failure as SUSPECT instead of fatal: the cascade parks at its exact
chunk/offset cursor, the edge re-establishes via a RESUME re-handshake
(bounded HOROVOD_LINK_RETRIES / HOROVOD_LINK_HEAL_TIMEOUT_MS), the sender
rewinds to the receiver's authoritative cursor, and the collective
completes BIT-IDENTICALLY with zero Python-visible disruption.  Exhaustion
escalates to the unchanged abort path with the same culprit attribution.

Every test pins HOROVOD_SHM_DISABLE=1: on a single host the flat ring
would otherwise run over shared-memory edges, which have no socket to
heal (by design — shm rings fail-fast exactly as before this feature).
The existing abort-path fault tests pin HOROVOD_LINK_RETRIES=0 so the
abort machinery keeps dedicated coverage.
"""

import os

import pytest

from tests.test_native_engine import run_workers

pytestmark = pytest.mark.linkheal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "link_heal_worker.py")

# Multichannel TCP data plane (the healing surface) + a tight failure-
# detection bound so an accidental regression to the abort path fails the
# test quickly instead of burning the default 120 s socket patience.
#
# Small socket buffers, so that a segment cannot sit in the kernel whole.
# The sender re-dials a broken edge only while it is still inside its send
# cursor (engine.cc's liveness probe stops at ``ss == nsteps``).  With the
# kernel's default buffers (4 MB) the 128-170 KB segments of these payloads
# fit whole: in a world of two, rank 0 could receive rank 1's whole first
# segment, reduce it and queue its LAST segment before rank 1's injected
# ``conn-reset:prev`` discarded those queued bytes; rank 0 then had nothing
# left to send, never saw the break, and rank 1 escalated after the heal
# budget ("gave up after 0 reconnect attempts", ~1 run in 3 on a loaded
# machine).  With 16 KB buffers the shooter cannot have pushed a whole
# segment when it fires, so its peer is still owed bytes and heals.
HEAL_ENV = {
    "HOROVOD_SHM_DISABLE": "1",
    "HOROVOD_NUM_CHANNELS": "3",
    "HOROVOD_LINK_RETRIES": "4",
    "HOROVOD_LINK_HEAL_TIMEOUT_MS": "8000",
    "HOROVOD_SOCKET_BUF_BYTES": "16384",
}


def heal_schedule(n):
    """One conn-reset per rank at distinct mid steps: odd ranks shoot the
    recv side of their prev edge (discarding buffered bytes — the genuine
    lost-data case the RESUME rewind must repair), even ranks the send
    side."""
    toks = []
    for r in range(n):
        side = ":prev" if r % 2 else ""
        toks.append(f"{r}:{3 + 2 * r}:conn-reset{side}")
    return ",".join(toks)


@pytest.mark.parametrize("n", [2, 4])
def test_heal_mid_allreduce_bitwise_parity(n):
    """One injected conn-reset per rank mid-cascade: every step completes
    with zero aborts, link_reconnects >= 1 on every rank, results equal
    the exact analytic sum AND are bit-identical to an undisturbed
    re-run of the same world."""
    run_workers(n, "heal_parity", worker=WORKER, timeout=180,
                extra_env={**HEAL_ENV,
                           "HOROVOD_FAULT_INJECT": heal_schedule(n)})


@pytest.mark.parametrize("n", [2, 4])
def test_heal_mid_alltoall_bitwise_parity(n):
    """One injected conn-reset per rank in an allreduce+alltoall loop:
    the cascade's RESUME rewind heals each shot edge, and the variable-
    split alltoalls riding the SAME healed per-channel sockets complete
    every step with zero aborts and output bytes equal to both the
    pairwise-sends reference and an undisturbed re-run — a healed edge
    may not slip a single alltoall payload byte."""
    run_workers(n, "heal_alltoall", worker=WORKER, timeout=180,
                extra_env={**HEAL_ENV,
                           "HOROVOD_FAULT_INJECT": heal_schedule(n)})


@pytest.mark.parametrize("n,wire", [(2, "int8"), (4, "fp16")])
def test_heal_compressed_wire_bitwise(n, wire):
    """Healing under compressed wires: the rewound byte stream is the
    same quantized stream, so the healed run stays bit-identical to the
    undisturbed re-run (compressed modes are deterministic per world)."""
    run_workers(n, "heal_parity", worker=WORKER, timeout=180,
                extra_env={**HEAL_ENV,
                           "HOROVOD_TEST_WIRE": wire,
                           "HOROVOD_FAULT_INJECT": heal_schedule(n)})


def test_heal_with_tiny_chunks_and_multi_driver():
    """Adversarial pipeline geometry: 8 KB chunks (hundreds of chunk
    credits per segment, so the parked cursor is mid-step almost surely)
    and channels split across pool drivers (the RESUME can land on a
    driver that does not own the channel — the heal inbox hand-off)."""
    run_workers(2, "heal_parity", worker=WORKER, timeout=180,
                extra_env={**HEAL_ENV,
                           "HOROVOD_NUM_CHANNELS": "4",
                           "HOROVOD_CHANNEL_DRIVERS": "4",
                           "HOROVOD_CHUNK_BYTES": "8192",
                           "HOROVOD_FAULT_INJECT": heal_schedule(2)})


def test_recv_stall_heals_without_reconnect():
    """A 400 ms one-shot drain stall on one channel is a TRANSIENT, not a
    failure: all steps complete, zero aborts, and zero reconnects —
    suspect classification must not flap a live link."""
    run_workers(2, "recv_stall", worker=WORKER, timeout=120,
                extra_env={**HEAL_ENV,
                           "HOROVOD_FAULT_INJECT": "1:4:recv-stall:400"})


def test_retries_exhausted_escalates_to_clean_abort(tmp_path):
    """HOROVOD_LINK_HEAL_TIMEOUT_MS=1 strangles healing: the injected
    conn-reset escalates to today's clean attributed abort within the
    fault bound — the receiver of the shot edge names the TRUE culprit
    (its ring-prev neighbor), and nobody hangs (subprocess timeout is the
    hang detector).  The flight dumps record the suspect/escalate trail,
    so the post-mortem can tell "flapped then died" from "died"."""
    run_workers(4, "heal_exhaust", worker=WORKER, timeout=120,
                extra_env={**HEAL_ENV,
                           "HOROVOD_LINK_HEAL_TIMEOUT_MS": "1",
                           "HOROVOD_FAULT_TIMEOUT_SEC": "6",
                           "HOROVOD_FLIGHT_RECORDER_DIR": str(tmp_path),
                           "HOROVOD_FAULT_INJECT": "1:4:conn-reset"})
    from horovod_tpu.monitor.postmortem import analyze, load_dumps

    dumps = load_dumps(str(tmp_path))
    if dumps:  # dumps ride the abort broadcast; at least rank 0 writes one
        result = analyze(dumps, world_size=4)
        assert result["link_events"], "no link events in the flight dumps"
        assert any(v["suspect"] >= 1 or v["escalate"] >= 1
                   for v in result["link_events"].values()), result


def test_link_retries_zero_is_todays_abort_path():
    """HOROVOD_LINK_RETRIES=0 restores the fail-fast engine bit-for-bit:
    the same conn-reset aborts immediately with the same attribution and
    zero heal activity (the counters stay provably zero)."""
    run_workers(4, "heal_exhaust", worker=WORKER, timeout=120,
                extra_env={**HEAL_ENV,
                           "HOROVOD_LINK_RETRIES": "0",
                           "HOROVOD_TEST_EXPECT_FAILURES": "0",
                           "HOROVOD_FAULT_TIMEOUT_SEC": "6",
                           "HOROVOD_FAULT_INJECT": "1:4:conn-reset"})


def test_heal_during_partial_commit_step():
    """Healing composes with backup-worker partial commits: rank 3 is
    permanently slow (ghost-ridden at k=1), rank 0 shoots a data socket
    mid-run, and every committed SUM still identifies a valid participant
    set (inputs are 2^rank, so the result IS the participant bitmask)."""
    run_workers(
        4, "partial_commit_heal", worker=WORKER, timeout=180,
        extra_env={**HEAL_ENV,
                   "HOROVOD_BACKUP_WORKERS": "1",
                   "HOROVOD_BACKUP_GRACE_MS": "30",
                   "HOROVOD_FAULT_INJECT":
                       "3:*:slow:120,0:4:conn-reset"})


@pytest.mark.slow
def test_seeded_flap_soak_zero_aborts():
    """60 steps under a recurring flap schedule (two ranks shoot their
    own sockets every 9th/13th enqueue, one of them the lossy recv side):
    zero aborts, every step exact, reconnects accumulate."""
    run_workers(
        4, "flap_soak", worker=WORKER, timeout=600,
        extra_env={**HEAL_ENV,
                   "HOROVOD_TEST_STEPS": "60",
                   "HOROVOD_FAULT_INJECT":
                       "0:*:conn-reset:9,2:*:conn-reset:13:prev"})
