"""Host->device ingest transport pipeline (r6): raw / preagg / sparse
bit-parity, the packed-triple split boundary, the staging ring, the
transfer worker's conservation guarantees, and the transport="auto"
density probe.

Seed discipline: exact-equality parity tests use the boundary-safe seed
pattern (seeds 7/23 with lognormal draws, pinned by the r2 preagg
tests) — the device codec evaluates log1p in f32, the host tiers in
f64, so an adversarial value within ~1 ulp of a bucket boundary may
legally land one bucket over (conservation still exact; see
test_preagg_boundary_values_conserve_counts)."""

import threading

import numpy as np
import pytest

from loghisto_tpu.config import MetricConfig
from loghisto_tpu.parallel.aggregator import IngestStagingRing, TPUAggregator

pytestmark = pytest.mark.ingest_transport

CFG = MetricConfig(bucket_limit=256)


def _drained_acc(agg):
    """Force-flush and return the dense accumulator (+ spill) as int64."""
    agg.flush(force=True)
    with agg._dev_lock:
        acc = np.asarray(agg._finalize_acc(agg._acc), dtype=np.int64)
        if agg._spill is not None:
            acc = acc + agg._spill
    return acc


def test_three_transport_bit_parity():
    """raw (device f32 compress), preagg (record-time host fold), and
    sparse (flush-time host fold) must produce bit-identical
    accumulators on boundary-safe input — including zero, negative, and
    NaN values."""
    rng = np.random.default_rng(7)
    n = 40_000
    ids = rng.integers(0, 16, n).astype(np.int32)
    values = np.concatenate([
        rng.lognormal(4, 2, n - 3).astype(np.float32),
        np.array([0.0, -5.5, np.nan], dtype=np.float32),
    ])
    outs = {}
    for transport in ("raw", "preagg", "sparse"):
        agg = TPUAggregator(
            num_metrics=16, config=CFG, transport=transport,
            batch_size=4096,
        )
        agg.record_batch(ids, values)
        outs[transport] = _drained_acc(agg)
        agg.close()
    np.testing.assert_array_equal(outs["raw"], outs["sparse"])
    np.testing.assert_array_equal(outs["raw"], outs["preagg"])
    assert int(outs["sparse"].sum()) == n


def test_sparse_parity_beyond_int16_ids():
    """Metric ids above 2^15 must round-trip the packed int32 [n, 3]
    wire exactly (the regression the 3-column format exists for)."""
    num_metrics = 40_000
    rng = np.random.default_rng(23)
    n = 60_000
    ids = rng.integers(0, num_metrics, n).astype(np.int32)
    ids[:1000] = rng.integers(1 << 15, num_metrics, 1000)
    values = rng.lognormal(4, 2, n).astype(np.float32)
    outs = {}
    for transport in ("raw", "sparse"):
        agg = TPUAggregator(
            num_metrics=num_metrics, config=CFG, transport=transport,
            batch_size=8192,
        )
        agg.record_batch(ids, values)
        outs[transport] = _drained_acc(agg)
        agg.close()
    np.testing.assert_array_equal(outs["raw"], outs["sparse"])
    assert int(outs["sparse"].sum()) == n


def test_packed_split_boundary_exact_past_2_30():
    """Counts at and beyond the 2^30 packed-count cap: pack_cells splits
    rows below the cap, and a shipped total past spill_threshold routes
    to the exact int64 host spill — no int32 cell can ever wrap."""
    from loghisto_tpu._native import PACKED_COUNT_CAP, pack_cells

    big = (1 << 31) + 5
    packed = pack_cells(
        np.array([3], dtype=np.int32),
        np.array([-2], dtype=np.int64),
        np.array([big], dtype=np.int64),
    )
    assert packed.dtype == np.int32
    assert packed[:, 2].max() <= PACKED_COUNT_CAP
    assert int(packed[:, 2].astype(np.int64).sum()) == big
    assert len(packed) == 3  # cap, cap, remainder

    agg = TPUAggregator(
        num_metrics=8, config=CFG, transport="sparse", batch_size=1024,
    )
    agg._ship_packed(packed)
    with agg._dev_lock:
        assert agg._spill is not None, "2^31-count merge must spill"
        assert int(agg._spill.sum()) == big
        # all three split rows merged into ONE cell, exactly
        assert int(agg._spill.max()) == big
    agg.close()


def test_conservation_under_concurrent_writers_during_flush():
    """Writer threads record while flushes (and the transfer worker) run
    concurrently; after the final force-flush every sample is accounted
    for: device + spill + still-buffered + shed == recorded."""
    agg = TPUAggregator(
        num_metrics=32, config=CFG, transport="sparse", batch_size=1024,
    )
    per_thread, batches = 1000, 20
    threads = 4

    def writer(seed):
        rng = np.random.default_rng(seed)
        for _ in range(batches):
            ids = rng.integers(0, 32, per_thread).astype(np.int32)
            vals = rng.lognormal(2, 1, per_thread).astype(np.float32)
            agg.record_batch(ids, vals)

    ts = [threading.Thread(target=writer, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    # flush storm concurrent with the writers
    for _ in range(10):
        agg.flush()
    for t in ts:
        t.join()
    total = int(_drained_acc(agg).sum())
    buffered = agg._buffered_samples()
    recorded = threads * batches * per_thread
    assert total + buffered + agg._shed_samples == recorded
    assert buffered == 0  # force-flush drained everything
    agg.close()


def test_close_mid_flush_conserves_counts():
    """Satellite (f): closing the aggregator while writers and flushes
    are in flight must drain the staging ring and queue fully — exact
    conservation, no dropped in-flight slots."""
    agg = TPUAggregator(
        num_metrics=16, config=CFG, batch_size=512,
    )
    stop = threading.Event()
    recorded = [0]

    def writer():
        rng = np.random.default_rng(99)
        while not stop.is_set():
            ids = rng.integers(0, 16, 300).astype(np.int32)
            agg.record_batch(
                ids, rng.lognormal(2, 1, 300).astype(np.float32)
            )
            recorded[0] += 300

    t = threading.Thread(target=writer)
    t.start()
    import time as _time

    _time.sleep(0.3)  # let flushes overlap the close
    agg.close()  # mid-flight: must drain, not drop
    stop.set()
    t.join()
    # writers kept recording after close's drain; final flush picks those
    # up (close leaves the aggregator usable — worker re-spawns lazily)
    total = int(_drained_acc(agg).sum())
    assert total + agg._buffered_samples() + agg._shed_samples \
        == recorded[0]
    agg.close()
    # close()'s phase two (ring.drain() under _dev_lock) must leave no
    # in-flight upload behind.  Checked once the writer has stopped: a
    # writer still recording re-spawns the worker, which stages anew.
    if agg._staging_ring is not None:
        assert all(s is None for s in agg._staging_ring._inflight)


def test_preagg_works_without_compiler(monkeypatch):
    """Satellite (e): transport='preagg' must work with NO native
    library — the ShardedCellStore degrades to the pure-NumPy backend
    and stays count-exact."""
    from loghisto_tpu import _native

    monkeypatch.setattr(_native, "available", lambda: False)
    agg = TPUAggregator(
        num_metrics=8, config=CFG, transport="preagg", batch_size=512,
    )
    assert agg._cell_store.backend == "numpy"
    agg.registry.id_for("m")
    rng = np.random.default_rng(7)
    vals = rng.lognormal(3, 1, 5000).astype(np.float32)
    agg.record_batch(np.zeros(5000, dtype=np.int32), vals)
    out = agg.collect().metrics
    assert out["m_count"] == 5000
    agg.close()


def test_sparse_numpy_fold_parity_with_native(monkeypatch):
    """The NumPy fold tier ships cells bit-identical to the native
    parallel drain (same f64 codec, same split rule) — the sparse
    transport works compiler-less."""
    from loghisto_tpu import _native

    rng = np.random.default_rng(7)
    n = 30_000
    ids = rng.integers(-2, 64, n).astype(np.int32)  # incl. dropped ids
    values = rng.lognormal(4, 2, n).astype(np.float32)
    via_numpy = _native.fold_packed_numpy(
        ids, values, bucket_limit=CFG.bucket_limit
    )
    if _native.available():
        via_native = _native.fold_packed_native(
            ids, values, bucket_limit=CFG.bucket_limit
        )
        # row order is tier-specific; compare as sorted cell sets
        np.testing.assert_array_equal(
            via_numpy[np.lexsort(via_numpy.T[::-1])],
            via_native[np.lexsort(via_native.T[::-1])],
        )
    # the transport end-to-end on the numpy tier
    monkeypatch.setattr(_native, "available", lambda: False)
    agg = TPUAggregator(
        num_metrics=64, config=CFG, transport="sparse", batch_size=4096,
    )
    agg.record_batch(ids, values)
    total = int(_drained_acc(agg).sum())
    assert total == int((ids >= 0).sum())  # negative ids dropped exactly
    agg.close()


def test_auto_probe_switches_to_sparse_on_skew():
    """transport='auto' starts raw; the worker probes the first large
    batch and a Zipf-skewed load crosses to the sparse transport."""
    rng = np.random.default_rng(5)
    n = 1 << 17
    ids = (rng.zipf(1.3, n) % 1024).astype(np.int32)
    values = rng.lognormal(2, 1, n).astype(np.float32)
    agg = TPUAggregator(
        num_metrics=1024, config=CFG, transport="auto", batch_size=1 << 16,
    )
    assert agg.transport == "raw"  # pre-probe default
    agg.record_batch(ids, values)
    agg.flush(force=True)
    assert agg.probe_density is not None
    assert agg.transport == "sparse"
    stats = agg.transport_stats()
    assert stats["transport"] == "sparse"
    assert int(_drained_acc(agg).sum()) == n
    agg.close()


def test_auto_probe_stays_raw_on_dense_load():
    """A load where nearly every sample is a unique cell (density ~1)
    must NOT pay the host fold: auto stays raw."""
    n = 1 << 16
    ids = np.arange(n, dtype=np.int32) % 4096
    # each id recurs with magnitudes decades apart -> distinct buckets
    values = np.geomspace(1.0, 1e12, n).astype(np.float32)
    agg = TPUAggregator(
        num_metrics=4096, config=CFG, transport="auto",
        batch_size=1 << 16,
    )
    agg.record_batch(ids, values)
    agg.flush(force=True)
    assert agg.probe_density is not None
    assert agg.probe_density > 0.5
    assert agg.transport == "raw"
    agg.close()


def test_auto_probe_folds_duplicates_across_the_whole_item():
    """Regression (r17 satellite): the probe must fold unique cells over
    the FULL item, not a prefix.  This load's first 64Ki samples are all
    distinct cells (a prefix probe reads density ~1.0 and stays raw —
    the PAGED_STORE_r14 misread), but the block repeats 4x across the
    item, so the true density is ~0.25 and auto must switch sparse."""
    base_n = 1 << 16
    base_ids = np.arange(base_n, dtype=np.int32) % 4096
    base_values = np.geomspace(1.0, 1e12, base_n).astype(np.float32)
    ids = np.tile(base_ids, 4)
    values = np.tile(base_values, 4)
    agg = TPUAggregator(
        num_metrics=4096, config=CFG, transport="auto",
        batch_size=len(ids),
    )
    agg.record_batch(ids, values)
    agg.flush(force=True)
    assert agg.probe_density is not None
    assert agg.probe_density <= 0.3  # a prefix probe would read ~1.0
    assert agg.transport == "sparse"
    assert int(_drained_acc(agg).sum()) == len(ids)
    agg.close()


def test_pallas_sparse_tier_matches_jnp_tier():
    """The Pallas per-cell-DMA tier (interpret mode off-TPU) is
    bit-identical to the XLA scatter tier, including dropped ids and
    bucket clipping."""
    import jax.numpy as jnp

    from loghisto_tpu.ops.sparse_ingest import (
        pallas_sparse_ingest, sparse_ingest_batch,
    )

    rng = np.random.default_rng(0)
    B, M, n = 128, 300, 700
    packed = np.stack([
        rng.integers(-2, M + 5, n),       # incl. negative + OOB rows
        rng.integers(-B - 5, B + 5, n),   # incl. clip-range buckets
        rng.integers(1, 1000, n),
    ], axis=1).astype(np.int32)
    acc0 = jnp.zeros((M, 2 * B + 1), jnp.int32)
    a = np.asarray(sparse_ingest_batch(acc0, jnp.asarray(packed), B))
    acc0 = jnp.zeros((M, 2 * B + 1), jnp.int32)
    b = np.asarray(pallas_sparse_ingest(acc0, jnp.asarray(packed), B))
    np.testing.assert_array_equal(a, b)
    valid = (packed[:, 0] >= 0) & (packed[:, 0] < M)
    assert int(a.sum()) == int(packed[valid, 2].sum())


def test_staging_ring_reuses_slots_exactly():
    """Depth-K ring: slots are reused after blocking on their previous
    upload, pad is id -1 beyond the chunk, and every staged chunk
    round-trips bit-exactly."""
    ring = IngestStagingRing(slot_samples=8, depth=2)
    for k in range(5):  # > depth: forces reuse
        n = 3 + (k % 4)
        ids = np.arange(n, dtype=np.int32) + 10 * k
        values = (np.arange(n) + 0.5).astype(np.float32) * (k + 1)
        ids_dev, values_dev = ring.stage(ids, values)
        got_ids = np.asarray(ids_dev)
        got_values = np.asarray(values_dev)
        np.testing.assert_array_equal(got_ids[:n], ids)
        np.testing.assert_array_equal(got_values[:n], values)
        assert np.all(got_ids[n:] == -1)  # pad id drops in every kernel
        assert np.all(got_values[n:] == 0.0)
    assert ring.uploads == 5
    assert ring.bytes_uploaded == 5 * 8 * 8  # 8 samples x (4+4) bytes
    with pytest.raises(ValueError):
        IngestStagingRing(slot_samples=8, depth=1)
    with pytest.raises(ValueError):
        ring.stage(
            np.zeros(9, dtype=np.int32), np.zeros(9, dtype=np.float32)
        )


def test_sparse_transport_failure_spills_exactly(monkeypatch):
    """A device failure during a sparse merge folds the packed cells
    into the exact host spill — never lost, never double-counted."""
    agg = TPUAggregator(
        num_metrics=8, config=CFG, transport="sparse", batch_size=512,
    )
    agg.registry.id_for("m")

    def boom(acc, packed):
        raise RuntimeError("injected device failure")

    monkeypatch.setattr(agg, "_packed_ingest", boom)
    agg.record_batch(
        np.zeros(1000, dtype=np.int32),
        np.full(1000, 7.0, dtype=np.float32),
    )
    agg.flush(force=True)
    with agg._dev_lock:
        assert agg._spill is not None
        assert int(agg._spill.sum()) == 1000
    out = agg.collect().metrics
    assert out["m_count"] == 1000
    agg.close()
