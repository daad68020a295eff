"""TPUAggregator runtime tests: direct firehose ingestion, the host-tier
bridge behind the subscription boundary, lifetime aggregates, gauges."""

import time

import numpy as np
import pytest

from loghisto_tpu import MetricSystem
from loghisto_tpu.config import MetricConfig
from loghisto_tpu.parallel.aggregator import TPUAggregator

CFG = MetricConfig(bucket_limit=512)


def test_record_and_collect_naming():
    agg = TPUAggregator(num_metrics=8, config=CFG)
    rng = np.random.default_rng(0)
    values = rng.lognormal(4, 1, 10_000).astype(np.float32)
    ids = np.full(len(values), agg.registry.id_for("latency"), dtype=np.int32)
    agg.record_batch(ids, values)
    out = agg.collect().metrics
    for suffix in ("count", "sum", "avg", "min", "50", "99", "max",
                   "agg_avg", "agg_count", "agg_sum"):
        assert f"latency_{suffix}" in out, suffix
    assert out["latency_count"] == 10_000
    true_p50 = float(np.quantile(values, 0.5))
    assert abs(out["latency_50"] / true_p50 - 1) < 0.011


def test_collect_resets_interval_but_keeps_lifetime():
    agg = TPUAggregator(num_metrics=4, config=CFG)
    agg.record("m", 10.0)
    first = agg.collect().metrics
    assert first["m_count"] == 1
    agg.record("m", 20.0)
    second = agg.collect().metrics
    assert second["m_count"] == 1  # interval reset
    assert second["m_agg_count"] == 2  # lifetime kept


def test_collect_without_reset():
    agg = TPUAggregator(num_metrics=4, config=CFG)
    agg.record("m", 10.0)
    agg.collect(reset=False)
    out = agg.collect(reset=False).metrics
    assert out["m_count"] == 1
    # peeking must not fold lifetime aggregates (no quadratic growth)
    assert out["m_agg_count"] == 1
    final = agg.collect(reset=True).metrics
    assert final["m_agg_count"] == 1


def test_empty_metrics_omitted():
    agg = TPUAggregator(num_metrics=4, config=CFG)
    agg.registry.id_for("never_recorded")
    agg.record("real", 5.0)
    out = agg.collect().metrics
    assert "real_count" in out
    assert "never_recorded_count" not in out


def test_attach_bridges_host_intervals_to_device():
    ms = MetricSystem(interval=0.05, sys_stats=False)
    # default bucket_limit (4096): 330000 lands at bucket 1271, which the
    # test's small 512-bucket config would clip to the edge bucket.
    agg = TPUAggregator(num_metrics=8, config=MetricConfig())
    agg.attach(ms)
    for v in (33.0, 59.0, 330000.0):
        ms.histogram("histogram1", v)
    ms.start()
    try:
        # generous deadline: the first collect() pays the stats-fn XLA
        # compile, which on a cold container can take tens of seconds
        deadline = time.time() + 90
        while time.time() < deadline:
            out = agg.collect(reset=False).metrics
            if out.get("histogram1_count") == 3:
                break
            time.sleep(0.05)
        assert out["histogram1_count"] == 3
        # the golden 331132 decompressed sum survives the device path
        # (float32 matvec: within float tolerance)
        assert abs(out["histogram1_sum"] / 331132.0 - 1) < 1e-4
    finally:
        agg.detach()
        ms.stop()


def test_bridge_resubscribes_after_eviction():
    """Strike-eviction (reaper closes a full channel, metrics.go:565-581)
    must not kill the bridge permanently: it re-subscribes on a fresh
    channel and later intervals still reach the device accumulator."""
    ms = MetricSystem(interval=0.05, sys_stats=False)
    agg = TPUAggregator(num_metrics=8, config=MetricConfig())
    agg.attach(ms)
    try:
        evicted_ch = agg._bridge_ch
        evicted_ch.close()  # what the reaper's eviction does
        deadline = time.time() + 10
        while time.time() < deadline:
            if agg._bridge_evictions >= 1 and agg._bridge_ch is not evicted_ch:
                break
            time.sleep(0.02)
        assert agg._bridge_evictions >= 1
        assert agg._bridge_ch is not evicted_ch
        ms.histogram("after_eviction", 7.0)
        ms.start()
        deadline = time.time() + 90
        out = {}
        while time.time() < deadline:
            out = agg.collect(reset=False).metrics
            if out.get("after_eviction_count") == 1:
                break
            time.sleep(0.05)
        assert out.get("after_eviction_count") == 1
    finally:
        agg.detach()
        ms.stop()


def test_device_gauges_registered():
    ms = MetricSystem(interval=0.05, sys_stats=False)
    agg = TPUAggregator(num_metrics=4, config=CFG)
    agg.register_device_gauges(ms)
    gauges = ms.collect_raw_metrics().gauges
    assert "tpu.HbmBytesInUse" in gauges
    assert "tpu.LastAggregationUs" in gauges


def test_registry_full():
    from loghisto_tpu.registry import RegistryFullError

    # default policy grows past capacity (the reference admits new names
    # forever, metrics.go:281-294); "error" restores the hard-fail
    agg = TPUAggregator(num_metrics=2, config=CFG)
    agg.record("a", 1.0)
    agg.record("b", 1.0)
    agg.record("c", 1.0)
    assert agg.num_metrics == 4

    strict = TPUAggregator(
        num_metrics=2, config=CFG, on_registry_full="error"
    )
    strict.record("a", 1.0)
    strict.record("b", 1.0)
    with pytest.raises(RegistryFullError):
        strict.record("c", 1.0)


@pytest.mark.parametrize("path", ["scatter", "sort", "fused"])
def test_ingest_paths_agree(path):
    agg = TPUAggregator(num_metrics=8, config=CFG, ingest_path=path)
    rng = np.random.default_rng(7)
    for i in range(8):
        agg.registry.id_for(f"m{i}")
    ids = rng.integers(0, 8, 4000).astype(np.int32)
    values = rng.lognormal(1, 0.7, 4000).astype(np.float32)
    agg.record_batch(ids, values)
    out = agg.collect().metrics
    ref = TPUAggregator(num_metrics=8, config=CFG)
    for i in range(8):
        ref.registry.id_for(f"m{i}")
    ref.record_batch(ids, values)
    want = ref.collect().metrics
    assert out.keys() == want.keys()
    for key in want:
        assert out[key] == pytest.approx(want[key], rel=1e-6), key


def test_ingest_path_validation():
    import jax

    from loghisto_tpu.parallel.mesh import make_mesh

    # exactly five values construct (pallas from a single-metric shape)
    for path in ("auto", "scatter", "sort", "fused"):
        TPUAggregator(num_metrics=8, config=CFG, ingest_path=path).close()
    TPUAggregator(num_metrics=1, config=CFG, ingest_path="pallas").close()
    # every other value is refused, with the five named
    expected = "'auto', 'scatter', 'sort', 'pallas', or 'fused'"
    for path in ("warp-drive", "matmul", "hybrid", "multirow", "fused_paged"):
        with pytest.raises(ValueError, match=expected):
            TPUAggregator(num_metrics=8, config=CFG, ingest_path=path)
    with pytest.raises(ValueError, match=expected):
        TPUAggregator(
            num_metrics=8, config=CFG, ingest_path="multirow",
            mesh=make_mesh(stream=4, metric=2, devices=jax.devices()[:8]),
        )


@pytest.mark.parametrize("path", ["sort", "fused", "pallas"])
def test_ingest_path_checkpoint_roundtrip(tmp_path, path):
    from loghisto_tpu.utils import checkpoint

    m = 1 if path == "pallas" else 8
    agg = TPUAggregator(num_metrics=m, config=CFG, ingest_path=path)
    agg.record("m", 5.0)
    agg.record_batch(
        np.zeros(3000, np.int32), np.full(3000, 7.0, np.float32)
    )
    file = str(tmp_path / "m.npz")
    checkpoint.save(file, aggregator=agg)
    fresh = TPUAggregator(num_metrics=m, config=CFG, ingest_path=path)
    checkpoint.restore(file, aggregator=fresh)
    # restored counts merge with what the restored aggregator ingests
    fresh.record("m", 5.0)
    assert fresh.collect().metrics["m_count"] == 3002
    agg.close()
    fresh.close()


def test_mesh_mode_matches_single_device():
    import jax

    from loghisto_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(stream=4, metric=2, devices=jax.devices()[:8])
    rng = np.random.default_rng(9)
    ids = rng.integers(0, 8, 50_000).astype(np.int32)
    values = rng.lognormal(1, 0.8, 50_000).astype(np.float32)

    single = TPUAggregator(num_metrics=8, config=CFG)
    sharded = TPUAggregator(num_metrics=8, config=CFG, mesh=mesh)
    for agg in (single, sharded):
        for i in range(8):
            agg.registry.id_for(f"m{i}")
        agg.record_batch(ids, values)
    a = single.collect().metrics
    b = sharded.collect().metrics
    assert a.keys() == b.keys()
    for key in a:
        assert abs(a[key] - b[key]) <= max(1e-4 * abs(a[key]), 1e-4), key


def test_mesh_mode_requires_divisible_metrics():
    import jax

    from loghisto_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(stream=2, metric=4, devices=jax.devices()[:8])
    with pytest.raises(ValueError):
        TPUAggregator(num_metrics=10, config=CFG, mesh=mesh)


def test_oversized_registry_rejected():
    from loghisto_tpu.registry import MetricRegistry

    with pytest.raises(ValueError):
        TPUAggregator(
            num_metrics=2, config=CFG, registry=MetricRegistry(capacity=10)
        )


def test_record_batch_shape_mismatch():
    agg = TPUAggregator(num_metrics=2, config=CFG)
    with pytest.raises(ValueError):
        agg.record_batch(np.array([0, 1]), np.array([1.0]))


def test_aggregator_rejects_malformed_percentile_labels():
    with pytest.raises(ValueError):
        TPUAggregator(
            num_metrics=4, config=CFG, percentiles={"%d_bad": 0.5}
        )


def test_preagg_transport_bit_parity_with_raw():
    """transport='preagg' (host compress+dedup, weighted scatter) must be
    bit-identical to transport='raw' (device compress) — the codec is the
    same formula in both tiers.

    Caveat the seeds here steer clear of: a value within ~1 f32 ulp of a
    bucket boundary can land one bucket apart between tiers (device
    compress evaluates log1p in f32, the C host tier in f64; measured
    ~2e-5 of lognormal samples).  Either placement is within the codec's
    1% contract and total counts are always conserved — see
    test_preagg_boundary_values_conserve_counts."""
    from loghisto_tpu import _native

    if not _native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(7)
    n = 40_000
    ids = rng.integers(0, 16, n).astype(np.int32)
    values = np.concatenate([
        rng.lognormal(4, 2, n - 3).astype(np.float32),
        np.array([0.0, -5.5, np.nan], dtype=np.float32),
    ])
    outs = {}
    for transport in ("raw", "preagg"):
        agg = TPUAggregator(
            num_metrics=16, config=CFG, transport=transport,
            batch_size=4096,
        )
        for name_id in range(16):
            agg.registry.id_for(f"m{name_id}")
        agg.record_batch(ids, values)
        agg.flush(force=True)
        outs[transport] = np.asarray(agg._acc)
    np.testing.assert_array_equal(outs["raw"], outs["preagg"])


def test_preagg_transport_exact_beyond_int16_ids():
    """Regression for the int64 [n, 2] wire format bug: under no-x64,
    JAX canonicalized the packed int64 (id << 16 | bucket) keys to
    int32, truncating every id >= 2^15.  The int32 [n, 3] format carries
    the id in its own column; a grown >32k-row registry must round-trip
    the preagg transport bit-exactly against the raw device path."""
    from loghisto_tpu import _native

    if not _native.available():
        pytest.skip("native library unavailable")
    num_metrics = 40_000  # ids span both sides of 2^15
    rng = np.random.default_rng(23)
    n = 60_000
    ids = rng.integers(0, num_metrics, n).astype(np.int32)
    # make sure the truncation zone is actually hit, densely
    ids[:1000] = rng.integers(1 << 15, num_metrics, 1000)
    values = rng.lognormal(4, 2, n).astype(np.float32)
    outs = {}
    for transport in ("raw", "preagg"):
        agg = TPUAggregator(
            num_metrics=num_metrics, config=CFG, transport=transport,
            batch_size=8192,
        )
        agg.record_batch(ids, values)
        agg.flush(force=True)
        outs[transport] = np.asarray(agg._acc)
    np.testing.assert_array_equal(outs["raw"], outs["preagg"])
    # every sample landed (nothing silently dropped by id truncation)
    assert int(outs["preagg"].sum()) == n


def test_preagg_boundary_values_conserve_counts():
    """Cross-tier contract on bucket-boundary values: raw (f32 device
    compress) and preagg (f64 host compress) may place a value within
    ~1 ulp of a boundary one bucket apart, but totals per metric are
    conserved exactly and any disagreement is confined to adjacent
    buckets."""
    from loghisto_tpu import _native

    if not _native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(1)
    n = 200_000
    ids = rng.integers(0, 32, n).astype(np.int32)
    values = rng.lognormal(4, 2, n).astype(np.float32)
    outs = {}
    for transport in ("raw", "preagg"):
        agg = TPUAggregator(
            num_metrics=32, config=CFG, transport=transport,
            batch_size=16384,
        )
        agg.record_batch(ids, values)
        agg.flush(force=True)
        outs[transport] = np.asarray(
            agg._acc, dtype=np.int64
        )
    a, b = outs["raw"], outs["preagg"]
    # per-metric totals exact — no sample lost or duplicated by tier
    np.testing.assert_array_equal(a.sum(axis=1), b.sum(axis=1))
    diff = a - b
    rows, cols = np.nonzero(diff)
    # any placement disagreement is a +1/-1 pair in adjacent buckets
    assert len(rows) <= max(4, n // 10_000), len(rows)
    for r in set(rows.tolist()):
        row = diff[r]
        nz = np.nonzero(row)[0]
        assert row.sum() == 0
        # each disagreement moves exactly one count, one bucket over:
        # +1/-1 pairs in adjacent buckets, nothing larger
        assert np.all(np.abs(row[nz]) == 1), row[nz]
        pos = nz[row[nz] > 0]
        neg = nz[row[nz] < 0]
        assert len(pos) == len(neg)
        assert np.all(np.abs(np.sort(pos) - np.sort(neg)) == 1)


def test_ship_packed_rejects_legacy_two_column_format():
    """The aggregator must refuse a [m, 2] packed array outright — under
    jit a 2-column array would not raise (static OOB gathers clamp), it
    would silently corrupt the histogram."""
    from loghisto_tpu import _native

    if not _native.available():
        pytest.skip("native library unavailable")
    agg = TPUAggregator(
        num_metrics=8, config=CFG, transport="preagg", batch_size=1024,
    )
    legacy = np.array([[1 << 16 | 32768, 5]], dtype=np.int64)
    with pytest.raises(ValueError, match=r"\[m, 3\]"):
        agg._ship_packed(legacy)
    wrong_dtype = np.array([[1, 0, 5]], dtype=np.int64)
    with pytest.raises(ValueError, match="int32"):
        agg._ship_packed(wrong_dtype)


def test_preagg_transport_spill_threshold_respected():
    """A preagg flush whose total would cross spill_threshold must fold
    into the exact host spill, same as the raw path's guarantee."""
    from loghisto_tpu import _native

    if not _native.available():
        pytest.skip("native library unavailable")
    agg = TPUAggregator(
        num_metrics=4, config=CFG, transport="preagg",
        batch_size=4096, spill_threshold=10_000,
    )
    agg.registry.id_for("hot")
    ids = np.zeros(20_000, dtype=np.int32)
    values = np.full(20_000, 42.0, dtype=np.float32)
    agg.record_batch(ids, values)
    agg.flush(force=True)
    assert agg._spill is not None
    assert agg._spill.sum() == 20_000
    out = agg.collect().metrics
    assert out["hot_count"] == 20_000


def test_partial_merge_failure_never_double_counts(monkeypatch):
    """A device failure mid-way through a multi-chunk cell merge must
    spill ONLY the unapplied remainder: total observed count == total
    ingested, never more (reproduces the r2 review's 12-in/32-out bug)."""
    from loghisto_tpu import _native
    from loghisto_tpu.parallel import aggregator as agg_mod

    if not _native.available():
        pytest.skip("native library unavailable")
    monkeypatch.setattr(agg_mod, "_MERGE_CHUNK", 4)
    agg = TPUAggregator(
        num_metrics=8, config=CFG, transport="preagg", batch_size=64,
    )
    for i in range(8):
        agg.registry.id_for(f"m{i}")
    calls = {"n": 0}
    real = agg._weighted_ingest

    def flaky(acc, ids, buckets, weights):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("injected device failure")
        return real(acc, ids, buckets, weights)

    agg._weighted_ingest = flaky
    # 12 samples across 12 distinct cells -> 3 chunks of 4
    ids = np.arange(12, dtype=np.int32) % 8
    values = (np.arange(12) * 10 + 1).astype(np.float32)
    agg.record_batch(ids, values)
    agg.flush(force=True)
    out = agg.collect().metrics
    total = sum(v for k, v in out.items()
                if k.endswith("_count") and not k.endswith("_agg_count"))
    assert total == 12, total


def test_preagg_cells_persist_until_interval_boundary():
    """Non-forced flushes fold into the host cell store (no device
    traffic); collect() ships and reports everything exactly."""
    from loghisto_tpu import _native

    if not _native.available():
        pytest.skip("native library unavailable")
    agg = TPUAggregator(
        num_metrics=8, config=CFG, transport="preagg", batch_size=128,
    )
    agg.registry.id_for("m")
    before = np.asarray(agg._acc).sum()
    for _ in range(5):  # crosses batch_size -> auto non-forced flushes
        agg.record_batch(
            np.zeros(100, dtype=np.int32),
            np.full(100, 3.0, dtype=np.float32),
        )
    assert len(agg._cell_store) >= 1
    assert np.asarray(agg._acc).sum() == before  # device untouched
    out = agg.collect().metrics
    assert out["m_count"] == 500
    assert len(agg._cell_store) == 0


def test_preagg_watermark_ships_mid_interval():
    from loghisto_tpu import _native

    if not _native.available():
        pytest.skip("native library unavailable")
    agg = TPUAggregator(
        num_metrics=8, config=CFG, transport="preagg", batch_size=64,
    )
    agg.max_host_cells = 16
    agg.registry.id_for("m")
    # 64 distinct values -> >16 unique cells; crossing batch_size flushes,
    # and the watermark forces a device ship despite force=False
    vals = (np.arange(64) * 7 + 1).astype(np.float32)
    agg.record_batch(np.zeros(64, dtype=np.int32), vals)
    assert len(agg._cell_store) == 0  # drained for shipping
    # the ship rides the transfer worker now; barrier before inspecting
    assert agg.wait_transfers(timeout=30.0)
    assert np.asarray(agg._acc).sum() == 64
    assert agg.collect().metrics["m_count"] == 64


def test_preagg_transport_with_mesh_matches_single_device():
    """The cell-store transport must compose with the sharded accumulator:
    the weighted merge runs SPMD and the result matches single-device."""
    import jax

    from loghisto_tpu import _native
    from loghisto_tpu.parallel.mesh import make_mesh

    if not _native.available():
        pytest.skip("native library unavailable")
    mesh = make_mesh(stream=4, metric=2, devices=jax.devices()[:8])
    rng = np.random.default_rng(11)
    ids = rng.integers(0, 8, 30_000).astype(np.int32)
    values = rng.lognormal(1, 0.8, 30_000).astype(np.float32)

    single = TPUAggregator(num_metrics=8, config=CFG, transport="preagg")
    sharded = TPUAggregator(
        num_metrics=8, config=CFG, mesh=mesh, transport="preagg"
    )
    for agg in (single, sharded):
        for i in range(8):
            agg.registry.id_for(f"m{i}")
        agg.record_batch(ids, values)
    want = single.collect().metrics
    got = sharded.collect().metrics
    assert got.keys() == want.keys()
    for key in want:
        assert got[key] == pytest.approx(want[key], rel=1e-6), key


def test_unregistered_row_lifetime_survives_reset():
    """Raw-id ingestion (no registered name) must keep its lifetime
    aggregates across collect(reset=True); the history surfaces once the
    row's name is registered (matching checkpoint identity mapping)."""
    agg = TPUAggregator(num_metrics=4, config=CFG)
    agg.record_batch(
        np.full(10, 2, dtype=np.int32),  # row 2, never registered
        np.full(10, 5.0, dtype=np.float32),
    )
    first = agg.collect().metrics   # nothing namable this interval
    assert not any(k.endswith("_agg_count") for k in first)
    agg.registry.id_for("a")  # rows 0,1 -> names a,b; row 2 -> c
    agg.registry.id_for("b")
    agg.registry.id_for("c")
    agg.record_batch(
        np.full(3, 2, dtype=np.int32), np.full(3, 5.0, dtype=np.float32)
    )
    out = agg.collect().metrics
    assert out["c_count"] == 3
    assert out["c_agg_count"] == 13  # 10 pre-registration + 3 after


def test_growth_and_spill_together_under_mesh():
    """VERDICT r2 item 5: registry growth — which re-shards the
    accumulator across the mesh metric axis — while the SAME interval is
    already past spill_threshold with a live int64 spill tensor.  The
    grow must pad the spill's rows in lockstep with the re-sharded
    accumulator (aggregator._grow_locked's spill branch), and collect()
    must still produce exact counts from spill + device + post-growth
    samples."""
    import jax

    from loghisto_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(stream=4, metric=2, devices=jax.devices()[:8])
    agg = TPUAggregator(
        num_metrics=4, config=CFG, mesh=mesh, batch_size=64,
        spill_threshold=500, max_metrics=32,
    )
    for i in range(4):
        agg.registry.id_for(f"m{i}")
    rng = np.random.default_rng(3)
    expected = np.zeros(20, dtype=np.int64)

    # 1) past spill_threshold within the interval: spill fold engages
    for _ in range(10):  # 640 samples > 500, flushed per 64-sample batch
        ids = rng.integers(0, 4, 64).astype(np.int32)
        expected[:4] += np.bincount(ids, minlength=4)[:4]
        agg.record_batch(ids, rng.lognormal(2, 1, 64).astype(np.float32))
    assert agg.wait_transfers(timeout=30.0)  # flushes ride the worker now
    assert agg._spill is not None, "spill never engaged"
    assert agg._spill.shape[0] == 4

    # 2) registry overflow with the spill LIVE: growth re-shards the
    #    accumulator over the mesh and must pad the spill identically
    for i in range(4, 20):
        agg.record(f"m{i}", float(i + 1))
        expected[i] += 1
    assert agg.num_metrics >= 20
    assert agg.num_metrics % 2 == 0, "mesh metric-axis divisibility lost"
    assert agg._spill is not None
    assert agg._spill.shape[0] == agg.num_metrics, "spill rows not grown"

    # 3) more samples landing on old AND new rows after the re-shard
    ids = rng.integers(0, 20, 64).astype(np.int32)
    expected += np.bincount(ids, minlength=20)
    agg.record_batch(ids, rng.lognormal(2, 1, 64).astype(np.float32))

    # 4) exact conservation through spill + re-shard + mesh collect
    out = agg.collect().metrics
    for i in range(20):
        assert out[f"m{i}_count"] == expected[i], f"m{i}"
    assert agg._spill is None  # interval closed, spill folded in
