"""Chaos drills (ISSUE 10): scripted fault plans driven end-to-end
through the pipeline, crash-at-every-stage recovery with a bit-identical
oracle, breaker behavior under repeated device failures, wedged-worker
liveness, and supervised bridge restarts with /healthz transitions.

The crash/recovery tests use the direct committer stack (the
test_checkpoint.py idiom) so both the crashed run and its oracle commit
through identical code — the bit-identical assertion is then exact
dict equality over every device statistic, percentiles included."""

import datetime as dt
import threading
import time

import numpy as np
import pytest

from loghisto_tpu.commit import IntervalCommitter
from loghisto_tpu.config import MetricConfig
from loghisto_tpu.metrics import RawMetricSet
from loghisto_tpu.parallel.aggregator import TPUAggregator
from loghisto_tpu.resilience import (
    CircuitBreaker,
    FaultInjector,
    RecoveryManager,
    ThreadSupervisor,
)
from loghisto_tpu.utils import journal
from loghisto_tpu.window import TimeWheel

pytestmark = pytest.mark.chaos

CFG = MetricConfig(bucket_limit=64)


def _raw(i, hists, counters=None):
    return RawMetricSet(
        time=dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
        + dt.timedelta(seconds=i),
        counters=dict(counters or {}), rates={},
        histograms=hists, gauges={}, duration=1.0, seq=i,
    )


def _build(inj=None, breaker=None):
    agg = TPUAggregator(num_metrics=16, config=CFG)
    wheel = TimeWheel(num_metrics=16, config=CFG, interval=1.0,
                      tiers=((4, 2),), registry=agg.registry)
    com = IntervalCommitter(agg, wheel)
    com.fault_injector = inj
    com.breaker = breaker
    agg.fault_injector = inj
    agg.device_breaker = breaker
    com.warmup()
    return com, agg, wheel


def _snap(agg):
    """Every device statistic (counts, sums, percentiles) as one dict —
    exact equality over it IS the bit-identical oracle check."""
    return dict(sorted(agg.collect(reset=False).metrics.items()))


# -- crash at every stage: at most one interval lost, rest bit-identical -- #


@pytest.mark.parametrize("stage", [
    "after_checkpoint",        # kill right after a checkpoint landed
    "mid_journal_append",      # kill mid-append: torn final line
    "mid_checkpoint_rename",   # kill between fsync and rename
])
def test_crash_at_every_stage_loses_at_most_one_interval(tmp_path, stage):
    ck = str(tmp_path / "ck.npz")
    jl = str(tmp_path / "j.jsonl")
    raws = [
        _raw(i, {"lat": {i % 7: 10 + i}}, {"reqs": 100 * i})
        for i in range(1, 7)
    ]

    # ---- the doomed run: commit 6 intervals, checkpoint at seq 2 and
    # seq 4, journal every interval, then "crash" per the stage script
    com, agg, wheel = _build()
    rec = RecoveryManager(
        None, aggregator=agg, committer=com,
        checkpoint_path=ck, journal_path=jl,
        checkpoint_every_intervals=10_000,  # cadence driven by hand
    )
    tear = FaultInjector(seed=5).plan("journal.append", "truncate")
    lost = None
    with open(jl, "w") as f:
        for r in raws:
            com.commit(r)
            rec.on_commit(r)
            line = journal.dump_line(r) + "\n"
            if stage == "mid_journal_append" and r.seq == 6:
                # the crash tears the LAST append; that interval is the
                # one the guarantee allows losing
                line = tear.mangle("journal.append", line)
                lost = 6
            f.write(line)
            if r.seq == 2:
                assert rec.checkpoint_now()
            if r.seq == 4:
                if stage == "mid_checkpoint_rename":
                    # the crash lands between fsync and rename: the
                    # seq-2 checkpoint must survive untouched
                    rec.fault_injector = FaultInjector().plan(
                        "checkpoint.rename", "raise"
                    )
                    assert not rec.checkpoint_now()
                    assert rec.checkpoint_errors == 1
                    rec.fault_injector = None
                else:
                    assert rec.checkpoint_now()

    # ---- recovery into a fresh stack
    com2, agg2, wheel2 = _build()
    rec2 = RecoveryManager(
        None, aggregator=agg2, committer=com2,
        checkpoint_path=ck, journal_path=jl,
    )
    report = rec2.recover()

    expected_watermark = 2 if stage == "mid_checkpoint_rename" else 4
    assert report.watermark == expected_watermark
    assert report.checkpoint_found and report.journal_found
    assert report.skipped_intervals == expected_watermark
    survived = [r for r in raws if r.seq != lost]
    # at-most-one-interval-loss: everything except the torn line replays
    assert report.replayed_intervals == len(survived) - expected_watermark
    assert report.corrupt_lines == (1 if stage == "mid_journal_append"
                                    else 0)

    # ---- oracle: a pristine stack committing exactly the survivors
    com3, agg3, wheel3 = _build()
    for r in survived:
        com3.commit(r)
    assert _snap(agg2) == _snap(agg3)  # bit-identical, percentiles too

    # retention rebuilds from the journal suffix past the watermark
    # (the checkpoint snapshots lifetime aggregator state, not wheel
    # ring history — window completeness is bounded by the cadence)
    assert wheel2.intervals_pushed == report.replayed_intervals


def test_recover_advances_seq_counter_past_replay(tmp_path):
    # replayed seqs and freshly minted seqs must never collide: the
    # reaper's counter jumps past the recovered watermark
    import itertools

    jl = str(tmp_path / "j.jsonl")
    with open(jl, "w") as f:
        for r in [_raw(i, {"m": {1: 1}}) for i in (1, 2, 9)]:
            f.write(journal.dump_line(r) + "\n")

    class FakeMS:
        _interval_seq = itertools.count(1)

    ms = FakeMS()
    com, agg, wheel = _build()
    rec = RecoveryManager(ms, aggregator=agg, committer=com,
                          journal_path=jl)
    report = rec.recover()
    assert report.replayed_intervals == 3
    assert next(ms._interval_seq) == 10


def test_recover_without_artifacts_is_a_clean_noop(tmp_path):
    com, agg, wheel = _build()
    rec = RecoveryManager(
        None, aggregator=agg, committer=com,
        checkpoint_path=str(tmp_path / "never.npz"),
        journal_path=str(tmp_path / "never.jsonl"),
    )
    report = rec.recover()
    assert not report.checkpoint_found and not report.journal_found
    assert report.replayed_intervals == 0 and report.watermark is None


# -- scripted device failures: breaker opens, samples conserved ----------- #


def test_repeated_dispatch_failures_trip_breaker_and_pin_fanout():
    inj = FaultInjector()
    inj.plan("commit.dispatch", "raise", every=1, times=3)
    br = CircuitBreaker(threshold=3, window_s=30.0, open_s=60.0)
    com, agg, wheel = _build(inj=inj, breaker=br)
    agg.retry_cooldown = 0.0

    for i in (1, 2, 3):
        com.commit(_raw(i, {"m": {1: 5}}))
    assert inj.fires_at("commit.dispatch") == 3
    assert br.failures_total == 3
    assert br.state == "open" and br.opened_total == 1

    # breaker open: the next interval takes the pinned fan-out/spill
    # path — no further donated-carry dispatch attempt burns a rebuild
    mode = com.commit(_raw(4, {"m": {1: 5}}))
    assert mode == "fanout"
    assert inj.fires_at("commit.dispatch") == 3  # no new dispatch tried

    # count conservation across every injected failure + the pinned path
    out = agg.collect(reset=False).metrics
    assert out["m_count"] == 20.0


def test_breaker_halfopen_trial_recloses_through_commit():
    br = CircuitBreaker(threshold=1, window_s=30.0, open_s=0.01)
    inj = FaultInjector().plan("commit.dispatch", "raise", on_call=1)
    com, agg, wheel = _build(inj=inj, breaker=br)
    agg.retry_cooldown = 0.0

    com.commit(_raw(1, {"m": {1: 5}}))  # injected failure opens it
    assert br.state == "open"
    time.sleep(0.02)  # past open_s: next commit is the half-open trial
    mode = com.commit(_raw(2, {"m": {1: 5}}))
    assert mode == "fused"
    assert br.state == "closed"  # record_success closed the trial
    assert agg.collect(reset=False).metrics["m_count"] == 10.0


# -- wedged transfer worker: no deadlock, exact conservation -------------- #


def test_wedged_transfer_worker_backs_up_then_drains():
    inj = FaultInjector(wedge_timeout_s=30.0)
    inj.plan("agg.xfer_worker", "wedge", on_call=1)
    # raw transport: a bare flush() enqueues immediately (no preagg
    # watermark), so the wedge provably holds a queued item hostage
    agg = TPUAggregator(num_metrics=16, config=CFG, transport="raw")
    agg.fault_injector = inj
    mid = agg.registry.id_for("m")

    agg.record_batch(np.full(100, mid, np.int32),
                     np.ones(100, np.float32))
    agg.flush()  # enqueue-only; the worker wedges at its loop top
    deadline = time.monotonic() + 5.0
    while inj.wedged_now == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert inj.wedged_now == 1
    # the barrier times out instead of deadlocking
    assert not agg.wait_transfers(timeout=0.3)

    inj.release_wedges()
    assert agg.wait_transfers(timeout=10.0)
    assert agg.collect(reset=False).metrics["m_count"] == 100.0


def test_crashed_transfer_worker_respawns_on_next_enqueue():
    inj = FaultInjector()
    inj.plan("agg.xfer_worker", "raise", on_call=1)
    sup = ThreadSupervisor()
    agg = TPUAggregator(num_metrics=16, config=CFG, transport="raw")
    agg.fault_injector = inj
    agg.supervisor = sup
    mid = agg.registry.id_for("m")

    agg.record_batch(np.full(50, mid, np.int32), np.ones(50, np.float32))
    agg.flush()  # worker crashes at its loop top; the item stays queued
    deadline = time.monotonic() + 5.0
    while (agg._xfer_thread is not None and agg._xfer_thread.is_alive()
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert not agg._xfer_thread.is_alive()

    # next enqueue respawns the worker and counts it on the restart
    # ledger; the forced flush then drains BOTH items exactly
    agg.record_batch(np.full(50, mid, np.int32), np.ones(50, np.float32))
    agg.flush(force=True)
    assert sup.restarts_by_name.get("loghisto-tpu-xfer") == 1
    assert agg.collect(reset=False).metrics["m_count"] == 100.0


@pytest.mark.parametrize("action", ["raise", "wedge"])
def test_worker_lost_mid_fold_ahead_conserves_every_sample(action,
                                                            monkeypatch):
    # the worker takes item 0 and hands item 1's fold to its helper,
    # where it is held; the worker merges item 0 and is lost at its
    # next loop top while that fold is still in flight
    from loghisto_tpu import _native

    inj = FaultInjector(wedge_timeout_s=30.0)
    inj.plan("agg.xfer_worker", action, on_call=2)
    sup = ThreadSupervisor()
    agg = TPUAggregator(num_metrics=16, config=CFG, transport="sparse",
                        batch_size=256)
    agg.fault_injector = inj
    agg.supervisor = sup
    mid = agg.registry.id_for("m")
    started, release = threading.Event(), threading.Event()
    fold = _native.fold_packed

    def held_fold(ids, values, **kw):
        if threading.current_thread().name == "loghisto-tpu-fold":
            started.set()
            release.wait(30)
        return fold(ids, values, **kw)

    monkeypatch.setattr(_native, "fold_packed", held_fold)
    with agg._xfer_cv:
        for _ in range(2):
            agg.record_batch(np.full(256, mid, np.int32),
                             np.ones(256, np.float32))
    assert started.wait(10.0)
    worker, helper = agg._xfer_thread, agg._fold_thread
    deadline = time.monotonic() + 5.0
    while (worker.is_alive() if action == "raise"
           else inj.wedged_now == 0) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert inj.fires_at("agg.xfer_worker") == 1
    # item 1 is still queued and counted: the barrier does not pass
    assert not agg.wait_transfers(timeout=0.3)
    assert agg._xfer_queued_samples == 256

    release.set()
    if action == "wedge":
        inj.release_wedges()
    else:
        # the helper finishes the fold it holds, then stops with its
        # worker
        helper.join(timeout=10.0)
        assert not helper.is_alive()
    # the next enqueue respawns worker and helper together; the forced
    # flush merges the fold left on item 1, then item 2
    agg.record_batch(np.full(256, mid, np.int32), np.ones(256, np.float32))
    agg.flush(force=True)
    if action == "raise":
        assert sup.restarts_by_name.get("loghisto-tpu-xfer") == 1
        assert agg._xfer_thread is not worker
        assert agg._fold_thread is not helper
        assert agg._fold_thread.is_alive()
    else:
        assert agg._xfer_thread is worker and agg._fold_thread is helper
        assert not sup.restarts_by_name
    assert agg.collect(reset=False).metrics["m_count"] == 768.0
    assert agg.transport_stats()["folds_ahead"] >= 1
    assert agg._shed_samples == 0
    agg.close()


# -- scripted slow consumer / clock step ---------------------------------- #


def test_delay_fault_slows_but_never_corrupts():
    # wheel.push is the fan-out tier path (the fused program commits
    # tiers on device and never enters push_cells), so drive the wheel
    # directly — a scripted slow consumer must delay, never corrupt
    inj = FaultInjector()
    inj.plan("wheel.push", "delay", delay_s=0.01, every=1, times=3)
    wheel = TimeWheel(num_metrics=16, config=CFG, interval=1.0,
                      tiers=((4, 2),))
    wheel.fault_injector = inj
    for i in (1, 2, 3):
        wheel.push(_raw(i, {"m": {2: 7}}))
    assert inj.fires_at("wheel.push") == 3
    assert wheel.intervals_pushed == 3
    out = wheel.query("m", window=8).metrics
    assert out["m"]["count"] == 21


def test_backward_clock_step_cannot_stall_checkpoint_cadence(tmp_path):
    # the cadence counts committed intervals, not wall time, so an
    # injected backward clock step must not delay the next checkpoint
    inj = FaultInjector()
    inj.plan("recovery.tick", "clock_step", step_s=-3600.0)
    com, agg, wheel = _build()
    rec = RecoveryManager(
        None, aggregator=agg, committer=com,
        checkpoint_path=str(tmp_path / "ck.npz"),
        checkpoint_every_intervals=2, fault_injector=inj,
    )
    for i in (1, 2, 3, 4):
        r = _raw(i, {"m": {1: 1}})
        com.commit(r)
        rec.on_commit(r)
    assert inj.clock_offset() == -3600.0
    assert rec.checkpoints_taken == 2  # every 2 intervals, regardless


# -- supervised live pipeline: restart + health transitions --------------- #


def test_supervised_bridge_restart_and_health_transitions(tmp_path):
    """End-to-end drill on a live system: a scripted bridge crash is
    restarted by the supervisor, /healthz degrades with
    ``thread_restarted`` and returns to ok once the latch expires while
    commits keep flowing."""
    from loghisto_tpu.resilience import ResilienceConfig
    from loghisto_tpu.system import TPUMetricSystem

    inj = FaultInjector()
    inj.plan("commit.bridge", "raise", on_call=2)
    cfg = ResilienceConfig(
        restart_backoff_s=0.01, restart_backoff_cap_s=0.05,
        fault_injector=inj,
    )
    ms = TPUMetricSystem(
        interval=0.1, sys_stats=False, num_metrics=32,
        retention=((4, 1),), commit="fused", resilience=cfg,
        observability=True,
    )
    ms.start()
    try:
        ms.counter("reqs", 3)
        deadline = time.monotonic() + 30.0
        while (ms.supervisor.total_restarts == 0
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert ms.supervisor.total_restarts >= 1
        assert ms.supervisor.restarts_by_name.get("loghisto-commit") >= 1

        # degraded with the thread_restarted invariant latched
        rep = ms.health.report()
        assert "thread_restarted" in rep.reason_codes()
        assert rep.status in ("degraded", "stalled")

        # the restarted bridge keeps committing
        before = ms.committer.intervals_committed
        deadline = time.monotonic() + 30.0
        while (ms.committer.intervals_committed <= before
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert ms.committer.intervals_committed > before

        # latch expires (stall window) and the report returns to ok
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            rep = ms.health.report()
            if rep.ok:
                break
            time.sleep(0.1)
        assert rep.ok
        dump = ms.debug_dump()
        assert dump["resilience"]["thread_restarts"] == dict(
            ms.supervisor.restarts_by_name
        )
    finally:
        ms.stop()
