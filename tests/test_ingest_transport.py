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
import time

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
        acc = np.asarray(agg._acc, dtype=np.int64)
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


# ---------------------------------------------------------------------- #
# flat per-interval delta: the packed merge's single-device dense path
# ---------------------------------------------------------------------- #

FLAT_M, FLAT_BL = 24, 64
FLAT_B = 2 * FLAT_BL + 1


def _flat_chunks(case, rng, n=512, chunks=3):
    """int32 [n, 3] packed chunks exercising one edge of the merge."""
    out = []
    for _ in range(chunks):
        rows = rng.integers(0, FLAT_M, n)
        buckets = rng.integers(-FLAT_BL, FLAT_BL + 1, n)
        counts = rng.integers(1, 1000, n)
        if case == "pads":
            rows[rng.random(n) < 0.5] = -1
            counts[rows < 0] = 0
        elif case == "duplicates":
            pick = rng.integers(0, 5, n)
            rows = np.array([3, 3, 7, 23, 0])[pick]
            buckets = np.array([0, 0, -FLAT_BL, FLAT_BL, 5])[pick]
        elif case == "clipped":
            buckets = rng.choice(
                [-FLAT_BL, FLAT_BL, -FLAT_BL - 1, FLAT_BL + 1,
                 -(1 << 30), 1 << 30, -FLAT_BL - 4096], n)
        elif case == "edges":
            rows = rng.choice([0, FLAT_M - 1], n)
            buckets = rng.choice([-FLAT_BL, FLAT_BL], n)
        elif case == "out_of_range":
            rows = rng.choice([-1, -7, FLAT_M, FLAT_M + 1, (1 << 31) - 1,
                               FLAT_M - 1], n)
        else:  # mixed
            rows[::5] = -1
            rows[1::7] = FLAT_M - 1
            rows[2::11] = FLAT_M + 3
            buckets[::3] = FLAT_BL + 9
            buckets[1::13] = -FLAT_BL
        out.append(np.stack([rows, buckets, counts], 1).astype(np.int32))
    return out


@pytest.mark.parametrize(
    "case",
    ["pads", "duplicates", "clipped", "edges", "out_of_range", "mixed"],
)
def test_flat_delta_merge_and_settle_match_the_2d_scatter(case):
    """Chunks merged into the flat delta and settled once land exactly
    where the [M, B] scatter (sparse_ingest_batch) puts them: pads,
    duplicate cells, clipped buckets, the last row and column, rows out
    of range."""
    import jax.numpy as jnp

    from loghisto_tpu.ops.sparse_ingest import (
        make_flat_sparse_ingest_fn,
        settle_ingest_delta,
        sparse_ingest_batch,
    )

    rng = np.random.default_rng(
        ["pads", "duplicates", "clipped", "edges", "out_of_range",
         "mixed"].index(case) + 41
    )
    start = rng.integers(0, 1 << 20, (FLAT_M, FLAT_B)).astype(np.int32)
    ref = jnp.asarray(start)
    merge = make_flat_sparse_ingest_fn(FLAT_BL, FLAT_B)
    delta = jnp.zeros(FLAT_M * FLAT_B, dtype=jnp.int32)
    for packed in _flat_chunks(case, rng):
        ref = sparse_ingest_batch(ref, jnp.asarray(packed), FLAT_BL)
        delta = merge(delta, jnp.asarray(packed))
    got, zeroed = settle_ingest_delta(jnp.asarray(start), delta)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert not np.asarray(zeroed).any()
    assert zeroed.shape == (FLAT_M * FLAT_B,)


def test_flat_delta_readers_race_merges_without_loss():
    """Readers of the accumulator (collect with reset, `_acc` read-backs)
    race packed merges into the flat delta on the transfer worker, with
    a short switch interval: every merged sample is collected exactly
    once."""
    import sys

    agg = TPUAggregator(
        num_metrics=8, config=CFG, transport="sparse", batch_size=1024,
    )
    agg.registry.id_for("m")
    assert agg._flat
    rounds, cells = 60, 300
    collected = []
    done = threading.Event()

    def reader():
        while not done.is_set():
            collected.append(agg.collect(reset=True).metrics.get(
                "m_count", 0.0))
            np.asarray(agg._acc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t = threading.Thread(target=reader)
        t.start()
        rng = np.random.default_rng(3)
        total = 0
        for _ in range(rounds):
            packed = np.stack([
                np.zeros(cells), rng.integers(-300, 300, cells),
                rng.integers(1, 9, cells),
            ], 1).astype(np.int32)
            total += int(packed[:, 2].sum())
            agg.merge_packed(packed)
        assert agg.wait_transfers(timeout=60)
        done.set()
        t.join(timeout=60)
        assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    final = agg.collect(reset=True).metrics.get("m_count", 0.0)
    assert sum(collected) + final == total
    assert agg.flat_merges == rounds
    assert agg.delta_settles >= 1
    agg.close()


def test_flat_delta_conserves_through_every_reader(tmp_path):
    """Packed merges land in the flat delta; every reader of the
    accumulator settles it first and sees exact counts: read-back,
    collect, the fused commit, the spill fold, checkpoint save and
    restore, registry growth, lifecycle eviction and compaction."""
    import datetime as dt

    from loghisto_tpu.lifecycle import LifecycleConfig
    from loghisto_tpu.metrics import RawMetricSet
    from loghisto_tpu.system import TPUMetricSystem
    from loghisto_tpu.utils import checkpoint

    bl = 64
    b = 2 * bl + 1
    rng = np.random.default_rng(11)

    def build():
        return TPUMetricSystem(
            interval=3600.0, sys_stats=False,
            config=MetricConfig(bucket_limit=bl), num_metrics=8,
            retention=((4, 1),), commit="fused", transport="sparse",
            lifecycle=LifecycleConfig(check_every=10**6,
                                      auto_compact_fragmentation=0.0),
        )

    def merge(ms, want, names):
        agg = ms.aggregator
        ids = np.array([agg._id_for(n) for n in names])
        pick = rng.integers(0, len(names), 2000)
        buckets = rng.integers(-bl - 8, bl + 9, 2000)
        counts = rng.integers(1, 50, 2000)
        packed = np.stack([ids[pick], buckets, counts], 1).astype(np.int32)
        agg.merge_packed(packed, wait=True)
        for k, name in enumerate(names):
            row = want.setdefault(name, np.zeros(b, np.int64))
            sel = pick == k
            np.add.at(row, np.clip(buckets[sel], -bl, bl) + bl, counts[sel])
        assert agg._delta_samples > 0  # pending in the delta

    def exact(ms, want):
        agg = ms.aggregator
        with agg._dev_lock:
            acc = np.asarray(agg._acc, dtype=np.int64)
            assert agg._delta_samples == 0
            if agg._spill is not None:
                acc = acc + agg._spill
        for name, row in want.items():
            np.testing.assert_array_equal(
                acc[agg.registry.lookup(name)], row, err_msg=name)
        assert int(acc.sum()) == sum(int(r.sum()) for r in want.values())

    def settles(ms, read):
        before = ms.aggregator.delta_settles
        out = read()
        assert ms.aggregator.delta_settles > before
        return out

    ms = build()
    agg, lc = ms.aggregator, ms.lifecycle
    assert agg._flat
    names = [f"api.m{i}" for i in range(6)]
    want = {}
    try:
        merge(ms, want, names)
        acc = settles(ms, lambda: np.asarray(agg._acc))  # read-back
        assert int(acc.sum()) == sum(int(r.sum()) for r in want.values())
        exact(ms, want)

        merge(ms, want, names)
        got = settles(ms, lambda: ms.device_metrics(reset=False).metrics)
        for name in names:
            assert got[f"{name}_count"] == want[name].sum()
        exact(ms, want)

        merge(ms, want, names)
        raw = RawMetricSet(
            time=dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc),
            counters={}, rates={}, histograms={names[0]: {3: 7, -bl: 2}},
            gauges={}, duration=1.0,
        )
        settles(ms, lambda: ms.committer.commit(raw))
        assert ms.committer.fused_intervals == 1
        want[names[0]][3 + bl] += 7
        want[names[0]][0] += 2
        exact(ms, want)

        merge(ms, want, names)

        def spill_fold():
            with agg._dev_lock:
                agg._spill_fold_locked()
        settles(ms, spill_fold)
        assert agg._spill is not None
        exact(ms, want)

        merge(ms, want, names)
        path = str(tmp_path / "flat.npz")
        settles(ms, lambda: checkpoint.save(path, aggregator=agg))
        exact(ms, want)
        ms2 = build()
        try:
            want2 = {}
            merge(ms2, want2, names[:2])
            settles(ms2, lambda: checkpoint.restore(path,
                                                    aggregator=ms2.aggregator))
            for name, row in want.items():
                want2[name] = want2.get(name, 0) + row
            exact(ms2, want2)
        finally:
            ms2.stop()

        merge(ms, want, names)
        grown = [f"api.g{i}" for i in range(4)]
        settles(ms, lambda: [agg._id_for(n) for n in grown])
        assert agg.num_metrics == 16
        merge(ms, want, names + grown)
        exact(ms, want)

        merge(ms, want, names + grown)
        victim = names[1]
        settles(ms, lambda: lc.evict_ids([agg.registry.lookup(victim)]))
        want.setdefault("_overflow.api", np.zeros(b, np.int64))
        want["_overflow.api"] += want.pop(victim)
        exact(ms, want)

        live = [n for n in names + grown if n != victim]
        merge(ms, want, live)
        assert settles(ms, lc.compact)
        exact(ms, want)
        stats = agg.transport_stats()
        assert stats["flat_merges"] >= 9
        assert stats["delta_settles"] == agg.delta_settles >= 9
    finally:
        ms.stop()


# ---------------------------------------------------------------------- #
# fold-ahead: the worker's helper folds the next queued "fold" item while
# the worker merges the one before it
# ---------------------------------------------------------------------- #

FOLD_ROUTES = ["one_device", "mesh_2x2"]
FOLD_ITEM = 2048  # samples per queued item: one record_batch, one flush


def _fold_agg(route):
    from loghisto_tpu.parallel.mesh import make_mesh

    agg = TPUAggregator(
        num_metrics=16, config=CFG, transport="sparse",
        batch_size=FOLD_ITEM,
        mesh=make_mesh(2, 2) if route == "mesh_2x2" else None,
    )
    for i in range(16):
        agg.registry.id_for(f"m{i}")
    return agg


def _fold_item(rng, row=None):
    """One queued item's samples: every one on ``row`` when given."""
    ids = (
        np.full(FOLD_ITEM, row, np.int32) if row is not None
        else rng.integers(0, 16, FOLD_ITEM).astype(np.int32)
    )
    return ids, rng.lognormal(3, 1, FOLD_ITEM).astype(np.float32)


def _slow_merges(monkeypatch, agg, seconds, log=None):
    """Stretch every packed merge by ``seconds``; ``log`` gets
    ("merge", row) and ("merged", row) of the merge's first cell."""
    merge = agg._merge_packed_locked

    def slow(packed):
        row = int(packed[0, 0])
        if log is not None:
            log.append(("merge", row))
        time.sleep(seconds)
        merge(packed)
        if log is not None:
            log.append(("merged", row))

    monkeypatch.setattr(agg, "_merge_packed_locked", slow)


def _row_counts(agg):
    return _drained_acc(agg).sum(axis=1)


@pytest.mark.parametrize("route", FOLD_ROUTES)
@pytest.mark.parametrize("arrival", ["queued", "during_merge"])
def test_fold_ahead_starts_the_next_fold_before_the_merge_returns(
    route, arrival, monkeypatch
):
    """With every merge stretched, the fold of item k+1 starts, on the
    helper thread, before the merge of item k returns: when all four
    items were queued before the worker took the first, and when each
    arrives while the one before it merges.  The counts stay exact."""
    from loghisto_tpu import _native

    agg = _fold_agg(route)
    log, folds_on = [], {}
    fold = _native.fold_packed

    def logged_fold(ids, values, **kw):
        log.append(("fold", int(ids[0])))
        folds_on[int(ids[0])] = threading.current_thread().name
        return fold(ids, values, **kw)

    monkeypatch.setattr(_native, "fold_packed", logged_fold)
    _slow_merges(monkeypatch, agg, 0.2, log)
    rng = np.random.default_rng(5)
    items = 4
    if arrival == "queued":
        with agg._xfer_cv:  # all queued before the worker takes one
            for row in range(items):
                agg.record_batch(*_fold_item(rng, row))
    else:
        for row in range(items):
            deadline = time.monotonic() + 30
            while (row and ("merge", row - 1) not in log
                   and time.monotonic() < deadline):
                time.sleep(0.002)
            agg.record_batch(*_fold_item(rng, row))
    assert agg.wait_transfers(timeout=60)
    for k in range(items - 1):
        assert log.index(("fold", k + 1)) < log.index(("merged", k)), log
        assert folds_on[k + 1] == "loghisto-tpu-fold"
    assert agg.transport_stats()["folds_ahead"] == items - 1
    want = np.zeros(16, np.int64)
    want[:items] = FOLD_ITEM
    np.testing.assert_array_equal(_row_counts(agg), want)
    agg.close()


@pytest.mark.parametrize("route", FOLD_ROUTES)
@pytest.mark.parametrize("barrier", ["flush_force", "close_mid_flush",
                                     "collect_reset"])
def test_fold_ahead_keeps_counts_exact(route, barrier, monkeypatch):
    """Items folded ahead are merged exactly once through every barrier:
    a forced flush, close() while writers are still flushing, and
    collect(reset=True) between items, with a short switch interval."""
    import sys

    agg = _fold_agg(route)
    _slow_merges(monkeypatch, agg, 0.01)
    rng = np.random.default_rng(["flush_force", "close_mid_flush",
                                 "collect_reset"].index(barrier) + 61)
    chunks = [_fold_item(rng) for _ in range(12)]
    want = np.bincount(
        np.concatenate([ids for ids, _ in chunks]), minlength=16)
    collected = np.zeros(16, np.int64)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        if barrier == "flush_force":
            with agg._xfer_cv:
                for ids, values in chunks:
                    agg.record_batch(ids, values)
            agg.flush(force=True)
            assert agg._xfer_queued_samples == 0
        else:
            def writer(k):
                for ids, values in chunks[k::4]:
                    agg.record_batch(ids, values)

            ts = [threading.Thread(target=writer, args=(k,))
                  for k in range(4)]
            for t in ts:
                t.start()
            while any(t.is_alive() for t in ts):
                if barrier == "close_mid_flush":
                    agg.close()  # drains what was queued, then stops
                else:
                    out = agg.collect(reset=True).metrics
                    collected += np.array(
                        [out.get(f"m{i}_count", 0.0) for i in range(16)],
                        dtype=np.int64)
            for t in ts:
                t.join(timeout=60)
                assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    np.testing.assert_array_equal(_row_counts(agg) + collected, want)
    assert agg._buffered_samples() == 0 and agg._shed_samples == 0
    assert agg.transport_stats()["folds_ahead"] >= 1
    agg.close()


@pytest.mark.parametrize("route", FOLD_ROUTES)
def test_wait_transfers_times_out_while_a_fold_ahead_runs(
    route, monkeypatch
):
    """The item folded ahead stays queued: the transfer barrier does not
    pass while its fold is still running on the helper."""
    from loghisto_tpu import _native

    agg = _fold_agg(route)
    started, release = threading.Event(), threading.Event()
    fold = _native.fold_packed

    def held_fold(ids, values, **kw):
        if threading.current_thread().name == "loghisto-tpu-fold":
            started.set()
            release.wait(30)
        return fold(ids, values, **kw)

    monkeypatch.setattr(_native, "fold_packed", held_fold)
    rng = np.random.default_rng(9)
    with agg._xfer_cv:
        for row in (0, 1):
            agg.record_batch(*_fold_item(rng, row))
    assert started.wait(30)
    assert not agg.wait_transfers(timeout=0.3)
    assert agg._xfer_queued_samples > 0
    release.set()
    assert agg.wait_transfers(timeout=60)
    assert agg.transport_stats()["folds_ahead"] == 1
    want = np.zeros(16, np.int64)
    want[:2] = FOLD_ITEM
    np.testing.assert_array_equal(_row_counts(agg), want)
    agg.close()


# ---------------------------------------------------------------------- #
# the key-partitioned native fold: one row per distinct cell, and a
# context of the calling thread kept across folds
# ---------------------------------------------------------------------- #


def _fold_input(seed, n, rows=4096):
    """Zipf ids over ``rows`` with some negative (dropped) ids, and
    values of both signs, many beyond ``CFG.bucket_limit``, with a few
    non-finite ones, so the clamp and the codec's edges are exercised."""
    rng = np.random.default_rng(seed)
    ids = ((rng.zipf(1.3, n) - 1) % rows).astype(np.int32)
    ids[rng.random(n) < 0.01] = -1
    values = rng.lognormal(4, 2, n) * rng.choice([-1.0, 1.0], n)
    values[rng.integers(0, n, 16)] = np.inf
    values[rng.integers(0, n, 16)] = np.nan
    return ids, values.astype(np.float32)


def _assert_same_cells(native, numpy_tier):
    # one row per distinct cell on both tiers: equal lengths mean the
    # native fold emitted no cell twice
    assert len(native) == len(numpy_tier)
    np.testing.assert_array_equal(
        native[np.lexsort(native.T[::-1])],
        numpy_tier[np.lexsort(numpy_tier.T[::-1])],
    )


def test_partitioned_native_fold_matches_numpy_row_for_row():
    """At 2^20 samples the default thread count partitions the fold; it
    returns exactly the NumPy tier's rows: one per distinct cell."""
    from loghisto_tpu import _native

    if not _native.available():
        pytest.skip(f"native library unavailable: {_native.build_error()}")
    ids, values = _fold_input(7, 1 << 20)
    via_numpy = _native.fold_packed_numpy(ids, values, CFG.bucket_limit)
    via_native = _native.fold_packed_native(ids, values, CFG.bucket_limit)
    _assert_same_cells(via_native, via_numpy)
    buckets = via_numpy[:, 1]
    assert buckets.min() == -CFG.bucket_limit
    assert buckets.max() == CFG.bucket_limit
    assert int(via_native[:, 2].sum()) == int((ids >= 0).sum())


def test_native_fold_context_stays_exact_across_items_and_threads():
    """The tables kept between folds carry nothing over: a large item, a
    one-thread one, a few-thread one with far more rows, and a large one
    again each fold exactly; and two threads folding different items at
    once, each with its own context, stay exact every time."""
    from loghisto_tpu import _native

    if not _native.available():
        pytest.skip(f"native library unavailable: {_native.build_error()}")
    items = [
        _fold_input(11, 1 << 20),
        _fold_input(12, 5_000),
        _fold_input(13, 3 * (1 << 16) + 5, rows=1 << 20),
        _fold_input(14, 1 << 20),
    ]
    want = [_native.fold_packed_numpy(ids, values, CFG.bucket_limit)
            for ids, values in items]
    for (ids, values), cells in zip(items, want):
        _assert_same_cells(
            _native.fold_packed_native(ids, values, CFG.bucket_limit), cells)

    errors = []

    def fold_in_turn(order):
        try:
            for _ in range(3):
                for k in order:
                    got = _native.fold_packed_native(
                        *items[k], CFG.bucket_limit)
                    _assert_same_cells(got, want[k])
        except AssertionError as e:
            errors.append(e)

    threads = [threading.Thread(target=fold_in_turn, args=(order,))
               for order in ([0, 2, 1], [3, 1, 2])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors, errors[0]


@pytest.mark.parametrize("samples", [FOLD_ITEM, 1 << 18])
def test_transport_stats_count_the_fold_rows(samples):
    """``fold_rows`` counts the rows the sparse fold shipped: one
    record_batch folds into exactly its distinct cells.  The fold's table
    grows are counted beside it."""
    from loghisto_tpu import _native

    agg = TPUAggregator(
        num_metrics=16, config=CFG, transport="sparse", batch_size=samples,
    )
    rng = np.random.default_rng(17)
    ids = rng.integers(0, 16, samples).astype(np.int32)
    values = rng.lognormal(3, 1, samples).astype(np.float32)
    agg.record_batch(ids, values)
    assert agg.wait_transfers(timeout=60)
    stats = agg.transport_stats()
    assert stats["samples_shipped"] == samples
    assert stats["fold_rows"] == len(
        _native.fold_packed_numpy(ids, values, CFG.bucket_limit))
    assert stats["fold_table_grows"] >= 0
    agg.close()
