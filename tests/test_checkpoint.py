"""Checkpoint/resume round-trip tests."""

import numpy as np
import pytest

from loghisto_tpu import MetricSystem
from loghisto_tpu.config import MetricConfig
from loghisto_tpu.parallel.aggregator import TPUAggregator
from loghisto_tpu.utils import checkpoint

CFG = MetricConfig(bucket_limit=256)


def test_metric_system_roundtrip(tmp_path):
    ms = MetricSystem(interval=1e-6, sys_stats=False)
    ms.counter("reqs", 500)
    ms.histogram("lat", 100.0)
    ms.process_metrics(ms.collect_raw_metrics())  # folds lifetime state

    path = str(tmp_path / "snap.npz")
    checkpoint.save(path, metric_system=ms)

    fresh = MetricSystem(interval=1e-6, sys_stats=False)
    checkpoint.restore(path, metric_system=fresh)
    metrics = fresh.process_metrics(fresh.collect_raw_metrics()).metrics
    assert metrics["reqs"] == 500  # lifetime counter survived
    raw = fresh.collect_raw_metrics()
    fresh.histogram("lat", 100.0)
    raw = fresh.collect_raw_metrics()
    processed = fresh.process_metrics(raw)
    fresh._attach_aggregates(processed, raw)
    # lifetime agg includes the pre-restart sample
    assert processed.metrics["lat_agg_count"] == 2


def test_aggregator_roundtrip(tmp_path):
    agg = TPUAggregator(num_metrics=8, config=CFG)
    agg.record("m", 50.0)
    agg.record("m", 70.0)
    agg.collect()  # lifetime folded; interval reset
    agg.record("m", 90.0)
    agg.flush()

    path = str(tmp_path / "agg.npz")
    checkpoint.save(path, aggregator=agg)

    fresh = TPUAggregator(num_metrics=8, config=CFG)
    checkpoint.restore(path, aggregator=fresh)
    out = fresh.collect().metrics
    assert out["m_count"] == 1  # the unreaped interval sample survived
    assert out["m_agg_count"] == 3  # 2 lifetime + 1 restored interval


def test_restore_into_nonempty_registry_remaps_by_name(tmp_path):
    # The target already has a different name at the checkpoint's row 0:
    # restore must remap by name, not overwrite rows by id.
    # values within CFG's bucket range (limit 256 covers |v| <= ~11.9)
    agg = TPUAggregator(num_metrics=8, config=CFG)
    agg.record("m", 5.0)
    agg.flush()
    path = str(tmp_path / "agg.npz")
    checkpoint.save(path, aggregator=agg)

    target = TPUAggregator(num_metrics=8, config=CFG)
    target.record("x", 9.0)  # takes id 0 in the target registry
    target.flush()
    checkpoint.restore(path, aggregator=target)
    out = target.collect().metrics
    assert out["x_count"] == 1 and abs(out["x_avg"] / 9.0 - 1) < 0.01
    assert out["m_count"] == 1 and abs(out["m_avg"] / 5.0 - 1) < 0.01


def test_restore_rejects_shape_mismatch(tmp_path):
    agg = TPUAggregator(num_metrics=8, config=CFG)
    agg.record("m", 1.0)
    path = str(tmp_path / "agg.npz")
    checkpoint.save(path, aggregator=agg)
    other = TPUAggregator(num_metrics=4, config=CFG)
    with pytest.raises(ValueError):
        checkpoint.restore(path, aggregator=other)


def test_go_compat_roundtrip_survives_restart(tmp_path):
    # the review repro: restoring into a go_compat system then recording
    # must not TypeError on the uint64 mask, and wrapped sums stay exact
    ms = MetricSystem(
        interval=1e-6, sys_stats=False, config=MetricConfig(go_compat=True)
    )
    ms.histogram("neg", -1000.0)
    ms.process_metrics(ms.collect_raw_metrics())
    path = str(tmp_path / "gc.npz")
    checkpoint.save(path, metric_system=ms)

    fresh = MetricSystem(
        interval=1e-6, sys_stats=False, config=MetricConfig(go_compat=True)
    )
    checkpoint.restore(path, metric_system=fresh)
    fresh.histogram("neg", -1.0)
    raw = fresh.collect_raw_metrics()  # must not crash
    processed = fresh.process_metrics(raw)
    fresh._attach_aggregates(processed, raw)
    assert processed.metrics["neg_agg_count"] == 2
    # the wrapped huge sum round-tripped exactly through the u64 sidecar
    stored = fresh._histogram_agg_store["neg"][0]
    assert isinstance(stored, int) and stored > 1 << 60


def test_checkpoint_portable_across_ingest_paths(tmp_path):
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    src = TPUAggregator(num_metrics=8, config=CFG, ingest_path="sort")
    src.record("m", 5.0)
    path = str(tmp_path / "x.npz")
    checkpoint.save(path, aggregator=src)
    # restore into an aggregator running another kernel
    dst = TPUAggregator(num_metrics=8, config=CFG, ingest_path="fused")
    checkpoint.restore(path, aggregator=dst)
    assert dst.collect().metrics["m_count"] == 1


@pytest.mark.parametrize("path", ["sort", "fused", "pallas"])
def test_device_failure_rebuilds_accumulator(path):
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    agg = TPUAggregator(
        num_metrics=1 if path == "pallas" else 8, config=CFG,
        ingest_path=path,
    )
    agg.retry_cooldown = 0.0
    agg.registry.id_for("m")
    real = agg._ingest
    calls = [0]

    def flaky(acc, ids, values):
        calls[0] += 1
        if calls[0] == 1:
            raise RuntimeError("device gone")
        return real(acc, ids, values)

    agg._ingest = flaky
    import numpy as np

    agg.record_batch(
        np.zeros(10, dtype=np.int32), np.full(10, 5.0, dtype=np.float32)
    )
    agg.flush()  # fails; if the acc was lost it is rebuilt [M, B]
    out = agg.collect().metrics
    assert out["m_count"] == 10
    assert calls[0] == 2  # the failed dispatch, then the retry
    assert agg.ingest_path == path
    assert agg._acc.shape == (agg.num_metrics, CFG.num_buckets)


def test_atomic_write_leaves_no_tmp(tmp_path):
    ms = MetricSystem(interval=1e-6, sys_stats=False)
    ms.counter("c", 1)
    path = str(tmp_path / "snap.npz")
    checkpoint.save(path, metric_system=ms)
    checkpoint.save(path, metric_system=ms)  # overwrite is atomic
    leftovers = [p for p in tmp_path.iterdir() if p.suffix == ".tmp"]
    assert not leftovers


def test_checkpoint_preserves_spill(tmp_path):
    # a snapshot taken mid-spill must carry the host int64 fold: losing it
    # silently would drop every sample past spill_threshold
    import datetime

    from loghisto_tpu.metrics import RawMetricSet

    cfg = MetricConfig(bucket_limit=64)
    agg = TPUAggregator(num_metrics=2, config=cfg, batch_size=64)
    agg.registry.id_for("hot")
    big = (1 << 31) + 777  # forces the spill path in merge_raw
    raw = RawMetricSet(
        time=datetime.datetime.now(tz=datetime.timezone.utc),
        counters={}, rates={}, histograms={"hot": {10: big}}, gauges={},
    )
    agg.merge_raw(raw)
    assert agg._spill is not None

    path = str(tmp_path / "spill.npz")
    checkpoint.save(path, aggregator=agg)

    agg2 = TPUAggregator(num_metrics=2, config=cfg, batch_size=64)
    checkpoint.restore(path, aggregator=agg2)
    # counts too large for int32 land in the restored aggregator's spill
    assert agg2._spill is not None
    out = agg2.collect().metrics
    assert out["hot_count"] == float(big)


def test_checkpoint_small_restore_stays_on_device(tmp_path):
    cfg = MetricConfig(bucket_limit=64)
    agg = TPUAggregator(num_metrics=2, config=cfg, batch_size=64)
    agg.record("a", 0.5)
    agg.flush(force=True)
    path = str(tmp_path / "small.npz")
    checkpoint.save(path, aggregator=agg)
    agg2 = TPUAggregator(num_metrics=2, config=cfg, batch_size=64)
    checkpoint.restore(path, aggregator=agg2)
    assert agg2._spill is None  # int32-safe restores stay on device
    assert agg2.collect().metrics["a_count"] == 1.0


def test_successive_restores_route_to_spill(tmp_path):
    # restored counts never increment the spill trigger's interval
    # counter, so stacking several worker checkpoints must divert to the
    # int64 spill once the combined headroom approaches 2^31
    import datetime

    from loghisto_tpu.metrics import RawMetricSet

    cfg = MetricConfig(bucket_limit=64)
    agg = TPUAggregator(num_metrics=2, config=cfg, batch_size=64)
    agg.registry.id_for("hot")
    per_worker = 900_000_000  # ~0.9e9: one restore fits, two would wrap
    raw = RawMetricSet(
        time=datetime.datetime.now(tz=datetime.timezone.utc),
        counters={}, rates={}, histograms={"hot": {10: per_worker}},
        gauges={},
    )
    agg.merge_raw(raw)
    path = str(tmp_path / "worker.npz")
    checkpoint.save(path, aggregator=agg)

    target = TPUAggregator(num_metrics=2, config=cfg, batch_size=64)
    checkpoint.restore(path, aggregator=target)
    checkpoint.restore(path, aggregator=target)  # second worker merge
    out = target.collect().metrics
    assert out["hot_count"] == float(2 * per_worker)  # no int32 wrap


@pytest.mark.lifecycle
def test_lifecycle_roundtrip_generation_and_overflow(tmp_path):
    """ISSUE 4 satellite: a checkpoint taken after eviction carries the
    registry generation, the overflow series' folded state, the activity
    vector, and the churn counters — and a restore remaps all of them
    by name, with free-slot holes surviving as holes."""
    import datetime as dt

    from loghisto_tpu.commit import IntervalCommitter
    from loghisto_tpu.lifecycle import LifecycleConfig, LifecycleManager
    from loghisto_tpu.metrics import RawMetricSet
    from loghisto_tpu.window import TimeWheel

    cfg = MetricConfig(bucket_limit=64)

    def build():
        agg = TPUAggregator(num_metrics=16, config=cfg)
        wheel = TimeWheel(num_metrics=16, config=cfg, interval=1.0,
                          tiers=((4, 2),), registry=agg.registry)
        lc = LifecycleManager(
            agg, wheel,
            LifecycleConfig(check_every=1000,
                            auto_compact_fragmentation=0.0),
        )
        com = IntervalCommitter(agg, wheel, lifecycle=lc)
        com.warmup()
        return com, agg, wheel, lc

    def raw(i, hists):
        return RawMetricSet(
            time=dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
            + dt.timedelta(seconds=i),
            counters={}, rates={}, histograms=hists, gauges={},
            duration=1.0,
        )

    com, agg, wheel, lc = build()
    com.commit(raw(0, {"api.a": {1: 5}, "api.b": {2: 3}, "db.q": {0: 2}}))
    com.commit(raw(1, {"api.a": {1: 1}}))
    lc.evict_ids([agg.registry.lookup("api.b")])  # folds into _overflow.api
    gen = agg.registry.generation
    assert gen > 0 and lc.overflowed_samples == 3

    path = str(tmp_path / "lc.npz")
    checkpoint.save(path, aggregator=agg, lifecycle=lc)

    com2, agg2, wheel2, lc2 = build()
    # occupy id 0 with a DIFFERENT name so the restore must remap by name
    agg2._id_for("other")
    checkpoint.restore(path, aggregator=agg2, lifecycle=lc2)

    reg2 = agg2.registry
    assert reg2.generation >= gen  # caches from the old world stay dead
    assert lc2.evicted_series == 1 and lc2.overflowed_samples == 3
    assert lc2.evictions == 1 and lc2.compactions == 0
    assert reg2.lookup("api.b") is None  # the hole did not resurrect

    acc2 = np.asarray(agg2._acc)
    ovid = reg2.lookup("_overflow.api")
    assert ovid is not None and int(acc2[ovid].sum()) == 3
    # total conservation across save/restore: 5+3+2+1 samples
    assert int(acc2.sum()) == 11

    # the remapped activity vector keeps per-name recency: api.a was
    # touched at epoch 2, db.q only at epoch 1
    la2 = np.asarray(lc2._la)
    assert la2[reg2.lookup("api.a")] == 2
    assert la2[reg2.lookup("db.q")] == 1


@pytest.mark.anomaly
def test_anomaly_bank_roundtrip_remaps_by_name(tmp_path):
    """ISSUE 7: drift baselines survive a restart — the EWMA banks are
    checkpointed and restored through the same by-name row remap as the
    activity vector, so a fresh process with a permuted registry still
    scores each series against ITS OWN baseline."""
    import datetime as dt

    from loghisto_tpu.anomaly import AnomalyConfig, AnomalyManager
    from loghisto_tpu.commit import IntervalCommitter
    from loghisto_tpu.metrics import RawMetricSet
    from loghisto_tpu.window import TimeWheel

    cfg = MetricConfig(bucket_limit=64)

    def build():
        agg = TPUAggregator(num_metrics=16, config=cfg)
        wheel = TimeWheel(num_metrics=16, config=cfg, interval=1.0,
                          tiers=((4, 1),), registry=agg.registry)
        am = AnomalyManager(agg, wheel, AnomalyConfig(
            banks=2, bank_of=lambda t: t.hour, decay=0.9, min_samples=4,
        ))
        com = IntervalCommitter(agg, wheel, anomaly=am)
        com.warmup()
        return com, agg, am

    def raw(i, hists):
        return RawMetricSet(
            time=dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
            + dt.timedelta(seconds=i),
            counters={}, rates={}, histograms=hists, gauges={},
            duration=1.0,
        )

    com, agg, am = build()
    for i in range(4):
        com.commit(raw(i, {"api.a": {1: 10}, "api.b": {5: 10}}))
    mids = {n: agg.registry.lookup(n) for n in ("api.a", "api.b")}
    prof0 = np.asarray(am._prof)
    wsum0 = np.asarray(am._wsum)
    assert wsum0[0, mids["api.a"]] > 0
    scored = am.scored_intervals

    path = str(tmp_path / "an.npz")
    checkpoint.save(path, aggregator=agg, anomaly=am)

    com2, agg2, am2 = build()
    # occupy id 0 with a DIFFERENT name so the restore must remap by name
    agg2._id_for("other")
    checkpoint.restore(path, aggregator=agg2, anomaly=am2)
    assert am2.scored_intervals == scored

    reg2 = agg2.registry
    prof2 = np.asarray(am2._prof)
    wsum2 = np.asarray(am2._wsum)
    for n, old in mids.items():
        new = reg2.lookup(n)
        assert new is not None and new != old  # actually remapped
        assert (prof2[:, new] == prof0[:, old]).all()
        assert (wsum2[:, new] == wsum0[:, old]).all()
    # the interloper and every unnamed row came through cold
    assert (wsum2[:, reg2.lookup("other")] == 0).all()

    # restored baselines serve immediately: the same steady shape scores
    # ~0 drift on the first post-restore interval
    com2.commit(raw(10, {"api.a": {1: 10}, "api.b": {5: 10}}))
    s = am2.scores_for("api.a")
    assert s is not None and s["jsd"] < 1e-5

    # bank-count mismatch is a config error, not silent corruption
    am3 = AnomalyManager(
        TPUAggregator(num_metrics=16, config=cfg),
        TimeWheel(num_metrics=16, config=cfg, interval=1.0,
                  tiers=((4, 1),)),
        AnomalyConfig(banks=1, min_samples=4),
    )
    with pytest.raises(ValueError, match="banks"):
        am3.load_state({"prof": prof0, "wsum": wsum0})


# -- FORMAT_VERSION 2: seq watermark (ISSUE 10 satellite) ----------------- #


def test_v2_seq_watermark_roundtrip(tmp_path):
    agg = TPUAggregator(num_metrics=8, config=CFG)
    agg.record("m", 5.0)
    agg.flush()
    path = str(tmp_path / "wm.npz")
    checkpoint.save(path, aggregator=agg, seq_watermark=42)
    fresh = TPUAggregator(num_metrics=8, config=CFG)
    assert checkpoint.restore(path, aggregator=fresh) == 42
    assert fresh.collect().metrics["m_count"] == 1


def test_v2_without_watermark_restores_none(tmp_path):
    agg = TPUAggregator(num_metrics=8, config=CFG)
    agg.record("m", 5.0)
    path = str(tmp_path / "nowm.npz")
    checkpoint.save(path, aggregator=agg)
    assert checkpoint.restore(
        path, aggregator=TPUAggregator(num_metrics=8, config=CFG)
    ) is None


def test_v1_checkpoint_still_restores(tmp_path):
    # backward compatibility: a v1 snapshot (no seq_watermark key, old
    # version stamp) loads cleanly and reports watermark None
    import numpy as np

    agg = TPUAggregator(num_metrics=8, config=CFG)
    agg.record("m", 5.0)
    agg.flush()
    path = str(tmp_path / "v1.npz")
    checkpoint.save(path, aggregator=agg)
    data = dict(np.load(path, allow_pickle=True))
    data["version"] = np.int64(1)
    data.pop("seq_watermark", None)
    np.savez(path, **data)

    fresh = TPUAggregator(num_metrics=8, config=CFG)
    assert checkpoint.restore(path, aggregator=fresh) is None
    assert fresh.collect().metrics["m_count"] == 1


def test_future_version_rejected(tmp_path):
    import numpy as np

    agg = TPUAggregator(num_metrics=8, config=CFG)
    agg.record("m", 5.0)
    path = str(tmp_path / "fut.npz")
    checkpoint.save(path, aggregator=agg)
    data = dict(np.load(path, allow_pickle=True))
    data["version"] = np.int64(99)
    np.savez(path, **data)
    with pytest.raises(ValueError, match="version"):
        checkpoint.restore(
            path, aggregator=TPUAggregator(num_metrics=8, config=CFG)
        )


def test_injected_crash_mid_write_leaves_previous_snapshot(tmp_path):
    from loghisto_tpu.resilience import FaultInjector, InjectedFault

    agg = TPUAggregator(num_metrics=8, config=CFG)
    agg.record("m", 5.0)
    agg.flush()
    path = str(tmp_path / "crash.npz")
    checkpoint.save(path, aggregator=agg, seq_watermark=7)

    agg.record("m", 9.0)
    for site in ("checkpoint.write", "checkpoint.rename"):
        inj = FaultInjector().plan(site, "raise")
        with pytest.raises(InjectedFault):
            checkpoint.save(path, aggregator=agg, seq_watermark=8,
                            fault_injector=inj)
        # the previous snapshot is intact and no temp litter remains
        fresh = TPUAggregator(num_metrics=8, config=CFG)
        assert checkpoint.restore(path, aggregator=fresh) == 7
        assert fresh.collect().metrics["m_count"] == 1
        leftovers = [p for p in tmp_path.iterdir() if p.suffix == ".tmp"]
        assert not leftovers


# -- FORMAT_VERSION 3: paged storage portability (ISSUE 14) --------------- #


def _paged_agg(codec="auto", **kw):
    from loghisto_tpu.paging import PagedStoreConfig

    kw.setdefault(
        "paged_config", PagedStoreConfig(pool_pages=256, codec=codec)
    )
    return TPUAggregator(num_metrics=8, config=CFG, storage="paged", **kw)


@pytest.mark.paged
def test_v3_paged_save_restores_into_dense(tmp_path):
    # a paged save carries the canonical dense decode, so a DENSE
    # aggregator restores it with no knowledge of pages or codecs
    src = _paged_agg()
    src.record("m", 5.0)
    src.record("m", 7.0)
    src.flush(force=True)
    path = str(tmp_path / "p2d.npz")
    checkpoint.save(path, aggregator=src)
    with np.load(path) as data:
        assert int(data["version"]) == 3
        assert "pg_codec_names" in data  # the codec sidecar rode along

    dst = TPUAggregator(num_metrics=8, config=CFG)  # dense target
    checkpoint.restore(path, aggregator=dst)
    out = dst.collect().metrics
    assert out["m_count"] == 2
    assert abs(out["m_avg"] / 6.0 - 1) < 0.02


@pytest.mark.paged
def test_v3_dense_save_restores_into_paged(tmp_path):
    src = TPUAggregator(num_metrics=8, config=CFG)
    src.record("m", 5.0)
    src.flush(force=True)
    path = str(tmp_path / "d2p.npz")
    checkpoint.save(path, aggregator=src)

    dst = _paged_agg()
    checkpoint.restore(path, aggregator=dst)
    out = dst.collect().metrics
    assert out["m_count"] == 1
    assert abs(out["m_avg"] / 5.0 - 1) < 0.02


@pytest.mark.paged
def test_v3_paged_roundtrip_preserves_codec_choices(tmp_path):
    # the source pinned a compressed codec; the restore must re-pin it
    # BEFORE recommitting, not re-derive resolution from the delta
    src = _paged_agg(codec="loglinear")
    src.record("m", 5.0)
    src.record("m", 7.0)
    src.flush(force=True)
    mid = src.registry.lookup("m")
    assert src.paged.codec_names()[mid] == "loglinear"
    path = str(tmp_path / "p2p.npz")
    checkpoint.save(path, aggregator=src)

    dst = _paged_agg()  # auto would have picked dense for this row
    checkpoint.restore(path, aggregator=dst)
    new_id = dst.registry.lookup("m")
    assert dst.paged.codec_names()[new_id] == "loglinear"
    out = dst.collect().metrics
    assert out["m_count"] == 2


@pytest.mark.paged
def test_v2_file_restores_into_paged_without_codec_sidecar(tmp_path):
    # the FORMAT_VERSION bump path: a pre-bump (v2) snapshot has no
    # pg_codec_names — the paged restore assigns codecs from the delta
    # occupancy instead of failing on the missing key
    src = TPUAggregator(num_metrics=8, config=CFG)
    src.record("m", 5.0)
    src.flush(force=True)
    path = str(tmp_path / "v2p.npz")
    checkpoint.save(path, aggregator=src)
    data = dict(np.load(path, allow_pickle=True))
    data["version"] = np.int64(2)
    data.pop("pg_codec_names", None)
    np.savez(path, **data)

    dst = _paged_agg()
    checkpoint.restore(path, aggregator=dst)
    out = dst.collect().metrics
    assert out["m_count"] == 1


@pytest.mark.paged
def test_paged_successive_restores_route_to_store_spill(tmp_path):
    # the paged twin of test_successive_restores_route_to_spill:
    # restored counts never increment the interval counter, so the
    # second worker merge must take the store's exact host spill
    # instead of wrapping an int32 pool cell
    import datetime

    from loghisto_tpu.metrics import RawMetricSet

    src = TPUAggregator(num_metrics=8, config=CFG, batch_size=64)
    src.registry.id_for("hot")
    per_worker = 900_000_000
    raw = RawMetricSet(
        time=datetime.datetime.now(tz=datetime.timezone.utc),
        counters={}, rates={}, histograms={"hot": {10: per_worker}},
        gauges={},
    )
    src.merge_raw(raw)
    path = str(tmp_path / "pw.npz")
    checkpoint.save(path, aggregator=src)

    target = _paged_agg(batch_size=64)
    checkpoint.restore(path, aggregator=target)
    checkpoint.restore(path, aggregator=target)  # second worker merge
    assert len(target.paged._host_spill) > 0  # headroom check fired
    out = target.collect().metrics
    assert out["hot_count"] == float(2 * per_worker)  # no int32 wrap
