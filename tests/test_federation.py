"""Federation tier (ISSUE 11): frame-codec fuzz, wire drills over real
TCP sockets, sequencing/idempotence, journal-backed receiver recovery,
chaos hooks, health invariants, and the 32-process conservation test
whose federated aggregate must be bit-identical to a single-process
oracle fed the same samples.

Wire drills run against a stub aggregator (interning + merge recording
only) so socket/sequencing behavior is tested without device dispatches;
the conservation and system-wiring tests use the real stack.
"""

import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from loghisto_tpu.federation import FederationConfig, wire
from loghisto_tpu.federation.emitter import FederationEmitter
from loghisto_tpu.federation.receiver import FederationReceiver
from loghisto_tpu.ops.codec import (
    FrameError,
    FrameTruncated,
    decode_frame,
    encode_frame,
    iter_frames,
)

from federation_emitter_worker import (  # tests/ is on sys.path (rootdir)
    CFG,
    SAMPLES_PER_PHASE,
    phase_names,
    phase_samples,
)

pytestmark = pytest.mark.federation

REPO_WORKER = __file__.replace(
    "test_federation.py", "federation_emitter_worker.py"
)


def _wait(cond, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() >= deadline:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.01)


class StubAgg:
    """Interning + merge recording without a device: `_id_for` assigns
    dense rows like the registry would, ``merge_packed`` keeps every
    merged array for inspection."""

    def __init__(self):
        self.rows = {}
        self.merged = []

    def _id_for(self, name, samples=1):
        return self.rows.setdefault(name, len(self.rows))

    def merge_packed(self, packed, wait=False):
        self.merged.append(np.array(packed))

    def merged_samples(self):
        return sum(int(m[:, 2].sum()) for m in self.merged)


def _delta_frame(emitter_id=7, seq=1, names=((0, "m.a"), (1, "m.b")),
                 rows=((0, 10, 3), (1, -4, 2))):
    payload = wire.encode_delta(
        emitter_id, seq, list(names),
        np.array(rows, dtype=np.int32).reshape(-1, 3),
    )
    return encode_frame(wire.KIND_DELTA, payload)


def _send_raw(port, data):
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        s.sendall(data)


# -- jax-free frontier: fast static proof ------------------------------- #
# The 32-subprocess drill at the bottom of this file remains the runtime
# oracle (its worker asserts "jax" not in sys.modules after importing the
# emitter); this static check gives every tier-1 run the same guarantee in
# milliseconds and names the offending import chain when it regresses.


def test_emitter_import_closure_is_statically_jax_free():
    from loghisto_tpu.analysis import import_lint

    findings = import_lint.frontier_findings()
    assert findings == [], "\n".join(f.render() for f in findings)
    # the PEP 562 lazy surfaces must stay lazy too — an eager re-export
    # would drag jax into the emitter's closure via the package __init__
    assert import_lint.lazy_surface_findings() == []


# -- frame codec fuzz (satellite: shared framing entry point) ----------- #


def test_frame_roundtrip_and_iteration():
    frames = [
        encode_frame(1, b"abc"),
        encode_frame(2, b""),
        encode_frame(200, bytes(range(256))),
    ]
    buf = b"".join(frames)
    out = list(iter_frames(buf))
    assert out == [(1, b"abc"), (2, b""), (200, bytes(range(256)))]


def test_frame_fuzz_every_truncation_raises_truncated():
    frame = _delta_frame()
    for cut in range(len(frame)):
        with pytest.raises(FrameTruncated):
            decode_frame(frame[:cut])


def test_frame_fuzz_every_bit_flip_fails_closed():
    """No single-bit corruption anywhere in a frame may decode to a
    payload — header flips fail structurally, payload flips fail CRC.
    A flip may also present as truncation (length-field flips); what it
    must never do is hand back bytes."""
    frame = _delta_frame()
    for i in range(len(frame)):
        for bit in range(8):
            bad = bytearray(frame)
            bad[i] ^= 1 << bit
            with pytest.raises((FrameError, FrameTruncated)):
                # oversized length flips truncate; buf is exactly one
                # frame, so any successful decode means corruption won
                decode_frame(bytes(bad))


def test_v1_frames_from_old_emitters_still_apply(rx):
    """Backward compat (ISSUE 12): a wire_version=1 emitter's frames
    keep applying through the v2 receiver — minus freshness/health —
    and the mixed-fleet stats mark them."""
    e = FederationEmitter(("127.0.0.1", rx.port), interval=0.2,
                          emitter_id=46, wire_version=1)
    e.record("fed.v1.lat", 1.0)
    e.flush()
    e._sender.start_sender("v1-compat")
    assert e.drain(10.0)
    _wait(lambda: rx.samples_merged == 1, what="v1 emitter merge")
    st = rx.stats()
    assert st["frames_v1"] == 1
    assert st["emitters"][f"{46:016x}"]["wire_v"] == 1
    assert st["freshness_samples"] == 0
    e.close(drain_timeout=1.0)


def test_delta_payload_structural_violations_raise_wireerror():
    good = wire.encode_delta(
        1, 1, [(0, "m")], np.array([[0, 0, 1]], dtype=np.int32)
    )
    with pytest.raises(wire.WireError):
        wire.decode_delta(good[:-1])  # row array cut short
    with pytest.raises(wire.WireError):
        wire.decode_delta(good + b"\x00")  # trailing garbage
    with pytest.raises(wire.WireError):
        wire.decode_delta(good[:4])  # shorter than the header


# -- wire drills over real sockets -------------------------------------- #


@pytest.fixture
def rx():
    agg = StubAgg()
    r = FederationReceiver(agg)
    r.start()
    yield r
    r.stop()


def test_frame_delivery_interns_and_merges(rx):
    _send_raw(rx.port, _delta_frame())
    _wait(lambda: rx.frames_received == 1, what="frame apply")
    assert rx.aggregator.rows == {"m.a": 0, "m.b": 1}
    assert rx.aggregator.merged_samples() == 5
    st = rx.stats()["emitters"][f"{7:016x}"]
    assert st["last_seq"] == 1 and st["samples"] == 5


def test_duplicate_frame_applied_once(rx):
    frame = _delta_frame(seq=1)
    _send_raw(rx.port, frame)
    _send_raw(rx.port, frame)  # at-least-once re-delivery
    _wait(lambda: rx.duplicate_frames == 1, what="duplicate detection")
    assert rx.frames_received == 1
    assert rx.aggregator.merged_samples() == 5  # not 10


def test_seq_gap_counted_and_late_frame_still_applies(rx):
    _send_raw(rx.port, _delta_frame(seq=1))
    _send_raw(rx.port, _delta_frame(
        seq=4, names=(), rows=((0, 2, 7),)))
    _wait(lambda: rx.frames_received == 2, what="both frames")
    assert rx.seq_gaps == 2  # frames 2 and 3 missing so far
    assert rx.aggregator.merged_samples() == 12
    # frame 3 arrives late (conn threads race: one connection per
    # frame): never applied before, so it merges and fills its gap
    _send_raw(rx.port, _delta_frame(seq=3, names=(), rows=((0, 0, 9),)))
    _wait(lambda: rx.frames_received == 3, what="late frame applies")
    assert rx.aggregator.merged_samples() == 21
    assert rx.seq_gaps == 1  # only frame 2 is still missing
    assert rx.duplicate_frames == 0
    # a RE-delivery of that same late frame is a true duplicate
    _send_raw(rx.port, _delta_frame(seq=3, names=(), rows=((0, 0, 9),)))
    _wait(lambda: rx.duplicate_frames == 1, what="exact-dup drop")
    assert rx.aggregator.merged_samples() == 21


def test_reordered_dict_frame_parks_rows_then_merges(rx):
    # one connection per frame means frame 2 (rows only) can overtake
    # frame 1 (the dictionary carrier) through racing conn threads: its
    # rows must PARK, not shed, and merge once frame 1 lands
    _send_raw(rx.port, _delta_frame(seq=2, names=(), rows=((0, 1, 4),)))
    _wait(lambda: rx.frames_received == 1, what="reordered frame")
    assert rx.aggregator.merged_samples() == 0
    assert rx.samples_shed == 0
    assert rx.samples_parked == 4
    assert rx.seq_gaps == 1
    _send_raw(rx.port, _delta_frame(seq=1))
    _wait(lambda: rx.aggregator.merged_samples() == 9, what="park resolve")
    assert rx.samples_shed == 0 and rx.samples_parked == 0
    assert rx.seq_gaps == 0  # the late frame filled its own gap


def test_emitter_crash_mid_frame_counts_error_merges_nothing(rx):
    frame = _delta_frame()
    _send_raw(rx.port, frame[: len(frame) // 2])  # crash mid-send
    _wait(lambda: rx.decode_errors == 1, what="torn-frame count")
    assert rx.frames_received == 0
    assert rx.aggregator.merged_samples() == 0
    _send_raw(rx.port, frame)  # the restarted emitter's next attempt
    _wait(lambda: rx.frames_received == 1, what="clean retry")
    assert rx.aggregator.merged_samples() == 5


def test_corrupt_frame_drops_connection_not_receiver(rx):
    frame = bytearray(_delta_frame())
    frame[-1] ^= 0xFF  # payload corruption: CRC fails
    _send_raw(rx.port, bytes(frame))
    _wait(lambda: rx.decode_errors == 1, what="decode error")
    _send_raw(rx.port, _delta_frame())  # receiver still accepts
    _wait(lambda: rx.frames_received == 1, what="post-corruption frame")


def test_unknown_local_id_rows_are_shed_and_counted(rx):
    # the dictionary frame for local id 9 died in a gap: its rows can't
    # be interned and must be shed (counted), not merged as garbage
    _send_raw(rx.port, _delta_frame(
        seq=1, names=((0, "m.known"),), rows=((0, 1, 2), (9, 1, 3))))
    _wait(lambda: rx.frames_received == 1, what="frame apply")
    assert rx.samples_shed == 3
    assert rx.aggregator.merged_samples() == 2


def test_dict_delta_applies_on_duplicate_frames(rx):
    # a re-delivered frame may be the only carrier of a name — the
    # dictionary applies idempotently even when the triples are dropped
    frame = _delta_frame(seq=1, names=((0, "m.late"),), rows=())
    _send_raw(rx.port, _delta_frame(seq=1, names=(), rows=()))
    _wait(lambda: rx.frames_received == 1, what="first frame")
    _send_raw(rx.port, frame)
    _wait(lambda: rx.duplicate_frames == 1, what="dup frame")
    assert "m.late" in rx.aggregator.rows


# -- emitter over live TCP ---------------------------------------------- #


def test_emitter_end_to_end_over_tcp(rx):
    e = FederationEmitter(("127.0.0.1", rx.port), interval=0.2,
                          emitter_id=42)
    e.start()
    for v in (1.0, 2.0, 3.0):
        e.record("fed.lat", v)
    e.record_batch(
        np.full(7, e.local_id("fed.sz"), dtype=np.int32),
        np.linspace(1, 7, 7, dtype=np.float32),
    )
    e.flush()
    assert e.drain(10.0)
    _wait(lambda: rx.samples_merged == 10, what="samples merged")
    assert e.samples_shipped == 10 and e.bytes_sent > 0
    assert {"fed.lat", "fed.sz"} <= set(rx.aggregator.rows)
    assert e.close()


def test_emitter_heartbeats_keep_lag_fresh(rx):
    e = FederationEmitter(("127.0.0.1", rx.port), interval=0.1,
                          emitter_id=43)
    e.start()
    _wait(lambda: rx.stats()["emitters"], what="first heartbeat")
    time.sleep(0.5)  # several idle intervals
    assert rx.max_emitter_lag_s() < 5.0
    assert rx.samples_merged == 0  # heartbeats carry no samples
    e.close()


def test_emitter_backlogs_through_receiver_downtime():
    agg = StubAgg()
    r = FederationReceiver(agg)
    r.start()
    port = r.port
    r.stop()  # receiver down before the emitter ever connects

    e = FederationEmitter(("127.0.0.1", port), interval=0.2,
                          emitter_id=44)
    e.record("fed.lat", 1.0)
    e.flush()
    assert not e.drain(0.3)  # undeliverable: held in the backlog
    assert e.send_failures > 0 and e.backlog_depth == 1

    r2 = FederationReceiver(agg, port=port)  # pod back on the same port
    r2.start()
    try:
        assert e.drain(10.0)
        _wait(lambda: r2.samples_merged == 1, what="backlog delivery")
    finally:
        e.close(drain_timeout=1.0)
        r2.stop()


# -- chaos hooks --------------------------------------------------------- #


def test_fed_send_fault_retries_from_backlog(rx):
    from loghisto_tpu.resilience import FaultInjector

    inj = FaultInjector().plan("fed.send", "raise", on_call=1)
    e = FederationEmitter(("127.0.0.1", rx.port), interval=0.2,
                          emitter_id=45, fault_injector=inj)
    e.record("fed.lat", 1.0)
    e.flush()
    assert e.drain(10.0)  # injected failure, then the retry lands
    assert e.send_failures == 1
    _wait(lambda: rx.samples_merged == 1, what="retried delivery")
    e.close(drain_timeout=1.0)


def test_fed_decode_fault_counts_and_drops_connection():
    from loghisto_tpu.resilience import FaultInjector

    agg = StubAgg()
    inj = FaultInjector().plan("fed.decode", "raise", on_call=1)
    r = FederationReceiver(agg, fault_injector=inj)
    r.start()
    try:
        _send_raw(r.port, _delta_frame(seq=1))
        _wait(lambda: r.decode_errors == 1, what="injected decode error")
        assert agg.merged_samples() == 0
        _send_raw(r.port, _delta_frame(seq=1))  # emitter re-delivers
        _wait(lambda: r.frames_received == 1, what="re-delivery")
        assert agg.merged_samples() == 5
    finally:
        r.stop()


def test_fed_accept_fault_restarts_supervised_accept_loop():
    from loghisto_tpu.resilience import FaultInjector, ThreadSupervisor

    agg = StubAgg()
    sup = ThreadSupervisor(base_backoff_s=0.01, max_backoff_s=0.05)
    inj = FaultInjector().plan("fed.accept", "raise", on_call=1)
    r = FederationReceiver(agg, supervisor=sup, fault_injector=inj)
    r.start()
    try:
        _send_raw(r.port, _delta_frame(seq=1))  # crashes the accept loop
        _wait(lambda: sup.total_restarts >= 1, what="supervised restart")
        # the loop came back: the emitter's retry gets through
        _send_raw(r.port, _delta_frame(seq=1))
        _wait(lambda: r.frames_received == 1, what="post-restart frame")
    finally:
        r.stop()


# -- journal-backed receiver recovery ------------------------------------ #


def test_receiver_restart_replays_journal_bit_identical(tmp_path):
    jpath = str(tmp_path / "fed.journal")
    agg1 = StubAgg()
    r1 = FederationReceiver(agg1, journal_path=jpath)
    r1.start()
    _send_raw(r1.port, _delta_frame(seq=1))
    _send_raw(r1.port, _delta_frame(seq=2, names=(),
                                    rows=((1, 3, 4),)))
    _wait(lambda: r1.frames_received == 2, what="both frames")
    r1.stop()  # pod crash: receiver + aggregator state both die

    agg2 = StubAgg()
    r2 = FederationReceiver(agg2, journal_path=jpath,
                            replay_on_start=True)
    r2.start()
    try:
        assert r2.frames_replayed == 2
        assert agg2.rows == agg1.rows
        assert agg2.merged_samples() == agg1.merged_samples() == 9
        # and the rebuilt seq state dedups live re-delivery
        _send_raw(r2.port, _delta_frame(seq=2, names=(),
                                        rows=((1, 3, 4),)))
        _wait(lambda: r2.duplicate_frames == 1, what="post-replay dedup")
        assert agg2.merged_samples() == 9
    finally:
        r2.stop()


def test_journal_replay_into_live_receiver_is_all_duplicates(tmp_path):
    jpath = str(tmp_path / "fed.journal")
    agg = StubAgg()
    r = FederationReceiver(agg, journal_path=jpath)
    r.start()
    try:
        _send_raw(r.port, _delta_frame(seq=1))
        _wait(lambda: r.frames_received == 1, what="frame")
        before = agg.merged_samples()
        assert r.replay_journal() == 1  # duplicate re-delivery at scale
        assert r.duplicate_frames == 1
        assert agg.merged_samples() == before
    finally:
        r.stop()


# -- health invariants --------------------------------------------------- #


def test_emitter_starvation_and_decode_error_invariants():
    from loghisto_tpu.obs.health import HealthWatchdog

    class _Com:
        fanout_intervals = 0
        bridge_evictions = 0
        intervals_committed = 0

    class _Agg:
        max_pending_samples = 0
        pending_samples = 0
        _xfer_queued_samples = 0
        _device_down_until = 0.0

    agg = StubAgg()
    r = FederationReceiver(agg, expected_emitters=2)
    r.start()
    try:
        wd = HealthWatchdog(_Com(), _Agg(), interval=0.1,
                            commit_path="fused", federation=r,
                            federation_starvation_intervals=3.0)
        wd.note_commit(1)
        assert wd.report().ok  # just started: inside the grace window

        r._started_t -= 60.0  # a minute of silence
        wd.note_commit(2)
        rep = wd.report()
        assert "emitter_starvation" in rep.reason_codes()

        _send_raw(r.port, _delta_frame(seq=1))
        _wait(lambda: r.frames_received == 1, what="frame")
        wd.note_commit(3)
        assert "emitter_starvation" not in wd.report().reason_codes()

        frame = bytearray(_delta_frame(seq=2))
        frame[-1] ^= 0xFF
        _send_raw(r.port, bytes(frame))
        _wait(lambda: r.decode_errors == 1, what="decode error")
        wd.note_commit(4)
        assert "fed_decode_errors" in wd.report().reason_codes()
    finally:
        r.stop()


# -- system wiring -------------------------------------------------------- #


def test_metric_system_federation_wiring(tmp_path):
    from loghisto_tpu.system import TPUMetricSystem

    ms = TPUMetricSystem(
        interval=0.5, sys_stats=False, num_metrics=64,
        federation=FederationConfig(
            journal_path=str(tmp_path / "fed.journal"),
            expected_emitters=1,
        ),
        observability=True,
    )
    ms.start()
    try:
        assert ms.federation.port > 0
        e = FederationEmitter(("127.0.0.1", ms.federation.port),
                              interval=0.2, emitter_id=99)
        for v in (1.0, 10.0, 100.0):
            e.record("fed.sys.lat", v)
        e.flush()
        assert e.drain(10.0)
        _wait(lambda: ms.federation.samples_merged == 3,
              what="system merge")
        e.close()
        ms.aggregator.wait_transfers()
        pms = ms.device_metrics(reset=False)
        assert pms.metrics["fed.sys.lat_count"] == 3.0
        dump = ms.debug_dump()
        assert dump["federation"]["frames_received"] >= 1
        with ms._gauge_lock:
            gauge_names = set(ms._gauge_funcs)
        assert "federation.ConnectedEmitters" in gauge_names
        assert "federation.FramesPerSec" in gauge_names
        assert f"federation.emitter.{99:016x}.LagS" in gauge_names
        # health carries the federation invariants end to end
        assert ms.health is not None
        assert "emitter_starvation" not in (
            ms.health.report().reason_codes()
        )
    finally:
        ms.stop()


# -- the conservation oracle: 32 processes, one pod, one crash ----------- #


def _drained_acc(agg):
    agg.wait_transfers()
    agg.flush(force=True)
    with agg._dev_lock:
        acc = np.asarray(agg._acc, dtype=np.int64)
        if agg._spill is not None:
            acc = acc + agg._spill
    return acc


def _rows_by_name(agg, names):
    acc = _drained_acc(agg)
    return {n: acc[agg.registry.id_for(n)].copy() for n in names}


def test_32_emitters_conserve_bit_identical(tmp_path):
    """32 emitter subprocesses, one aggregator pod, a mid-run pod crash
    recovered from the frame journal, then the whole journal re-delivered
    as duplicates — and the per-name accumulator rows still come out
    bit-identical to one process recording every sample locally."""
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    N, PHASES = 32, 2
    jpath = str(tmp_path / "fed.journal")
    agg = TPUAggregator(num_metrics=64, config=CFG, transport="sparse")
    r1 = FederationReceiver(agg, journal_path=jpath)
    r1.start()
    port = r1.port

    procs = [
        subprocess.Popen(
            [sys.executable, REPO_WORKER, str(port), str(i), str(PHASES)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for i in range(N)
    ]
    try:
        half = N * SAMPLES_PER_PHASE
        _wait(lambda: r1.samples_merged == half, timeout=240.0,
              what="phase-0 fan-in")

        # pod crash between phases: receiver AND aggregator state die;
        # the journal is the only survivor
        r1.stop()
        agg = TPUAggregator(num_metrics=64, config=CFG,
                            transport="sparse")
        r2 = FederationReceiver(agg, port=port, journal_path=jpath,
                                replay_on_start=True)
        r2.start()
        assert r2.samples_merged == half  # replay rebuilt phase 0

        for p in procs:
            p.stdin.write("go\n")
            p.stdin.flush()
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
            assert p.returncode == 0, out[-2000:]
            assert " OK " in out, out[-2000:]
        total = N * PHASES * SAMPLES_PER_PHASE
        _wait(lambda: r2.samples_merged == total, timeout=240.0,
              what="phase-1 fan-in")
        assert r2.samples_shed == 0 and r2.decode_errors == 0

        names = sorted({n for i in range(N) for n in phase_names(i)})
        fed_rows = _rows_by_name(agg, names)

        # duplicate chaos at scale: re-deliver every journaled frame
        # into the live receiver — all must dedup, state unchanged
        dups_before = r2.duplicate_frames
        r2.replay_journal()
        assert r2.duplicate_frames > dups_before
        fed_rows_after = _rows_by_name(agg, names)
        for n in names:
            assert np.array_equal(fed_rows[n], fed_rows_after[n])
        r2.stop()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    # the single-process oracle: identical samples, local record_batch
    oracle = TPUAggregator(num_metrics=64, config=CFG,
                           transport="sparse")
    for i in range(N):
        mids = np.array(
            [oracle.registry.id_for(n) for n in phase_names(i)],
            dtype=np.int32,
        )
        for phase in range(PHASES):
            k, values = phase_samples(i, phase)
            oracle.record_batch(mids[k], values)
    oracle_rows = _rows_by_name(oracle, names)

    assert sum(int(v.sum()) for v in fed_rows.values()) == total
    for n in names:
        assert np.array_equal(fed_rows[n], oracle_rows[n]), (
            f"row for {n!r} diverged from the oracle"
        )
