"""The packed (sparse transport) ingest on a ("stream", "metric") mesh:
each stream shard merges its own slice of every launch into a
per-(stream, metric) flat partial, and the settle before any read merges
the partials with one stream-axis all-reduce.  The interval commit
spreads its staged cells over every stream slice.

Small shapes on the 8 virtual CPU devices of tests/conftest.py: 64 rows,
bucket_limit 64."""

from __future__ import annotations

import datetime as dt

import jax
import numpy as np
import pytest

from loghisto_tpu.config import MetricConfig
from loghisto_tpu.metrics import RawMetricSet
from loghisto_tpu.parallel.mesh import STREAM_AXIS, make_mesh

M = 64
BL = 64
B = 2 * BL + 1
NAMES = [f"fire.{i:02d}" for i in range(56)]
ROUTE = "rpc.latency.0"
INTERVALS = 2
CHUNKS = 3          # record_batch calls an interval
CHUNK = 4096
SHAPES = [(2, 2), (4, 1), (1, 4)]


def _system(mesh):
    from loghisto_tpu.system import TPUMetricSystem

    ms = TPUMetricSystem(
        interval=3600.0, sys_stats=False, num_metrics=M,
        config=MetricConfig(bucket_limit=BL), retention=((4, 1),),
        commit="fused", transport="sparse", mesh=mesh,
    )
    for name in NAMES + [ROUTE]:
        ms.metric_id(name)
    return ms


def _bucket(values):
    """The reference codec: round(100 ln(1 + |v|)) with v's sign, clipped
    to the accumulator's span."""
    v = np.asarray(values, dtype=np.float64)
    mag = np.floor(100 * np.log1p(np.abs(v)) + 0.5)
    return np.clip(np.where(v < 0, -mag, mag), -BL, BL).astype(np.int64)


def _traffic(seed=5):
    """Per interval: Zipf(1.3) bulk chunks over the fire rows, and one
    per-call route histogram for the commit."""
    rng = np.random.default_rng(seed)
    out = []
    for k in range(INTERVALS):
        chunks = []
        for _ in range(CHUNKS):
            ids = (rng.zipf(1.3, CHUNK) - 1) % len(NAMES)
            vals = 1.0 + rng.lognormal(2.0, 1.0, CHUNK)
            chunks.append((ids.astype(np.int32), vals.astype(np.float32)))
        raw = RawMetricSet(
            time=dt.datetime(2026, 1, 1, 0, 0, k, tzinfo=dt.timezone.utc),
            counters={}, rates={}, gauges={}, duration=1.0,
            histograms={ROUTE: {int(b): int(c) for b, c in zip(
                rng.integers(-BL, BL + 1, 9), rng.integers(1, 30, 9))}},
        )
        out.append((chunks, raw))
    return out


def _reference(traffic, row_of):
    acc = np.zeros((M, B), np.int64)
    for chunks, raw in traffic:
        for ids, vals in chunks:
            np.add.at(acc, (np.array([row_of[NAMES[i]] for i in ids]),
                            _bucket(vals) + BL), 1)
        for b, c in raw.histograms[ROUTE].items():
            acc[row_of[ROUTE], b + BL] += c
    return acc


def _replicas_off(arr) -> int:
    """Elements in which copies of one shard differ across devices."""
    groups: dict = {}
    for sh in arr.addressable_shards:
        groups.setdefault(str(sh.index), []).append(np.asarray(sh.data))
    return sum(int(np.count_nonzero(o != g[0]))
               for g in groups.values() for o in g[1:])


def _drive(ms, traffic, before_commit=None):
    agg = ms.aggregator
    for chunks, raw in traffic:
        for ids, vals in chunks:
            ms.record_batch(ids, vals)
        agg.flush(force=True)
        assert agg.wait_transfers(timeout=120)
        if before_commit is not None:
            before_commit(agg)
        ms.committer.commit(raw)
    assert ms.committer.fused_intervals == INTERVALS


def _state(ms):
    agg = ms.aggregator
    acc = agg._acc
    rings = [np.asarray(t.ring) for t in ms.retention._tiers]
    return acc, np.asarray(acc).astype(np.int64), rings


@pytest.fixture(scope="module")
def one_device():
    traffic = _traffic()
    ms = _system(None)
    try:
        _drive(ms, traffic)
        _, acc, rings = _state(ms)
        row_of = {n: ms.metric_id(n) for n in NAMES + [ROUTE]}
    finally:
        ms.stop()
    return traffic, acc, rings, row_of


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_mesh_packed_ingest_matches_reference_and_one_device(shape,
                                                              one_device):
    """Two committed intervals of Zipf record_batch on the sparse
    transport: bucket counts bit-identical to the NumPy codec reference
    and to one device, the rings too, copies of every shard alike, and
    before each settle every stream slice of the partial holds samples."""
    traffic, acc1, rings1, row_of = one_device
    ms = _system(make_mesh(*shape))
    agg = ms.aggregator
    assert agg._flat
    assert agg._packed_ingest.__name__ == "sparse_ingest_flat_sharded"
    n_stream, n_metric = shape

    def blocks_hold_samples(agg):
        assert agg._delta_samples > 0
        part = np.asarray(agg._delta)
        assert part.shape == (n_stream, M * B)
        assert (part.sum(axis=1) > 0).all(), part.sum(axis=1)

    try:
        _drive(ms, traffic, before_commit=blocks_hold_samples)
        acc_dev, acc, rings = _state(ms)
        np.testing.assert_array_equal(acc, _reference(traffic, row_of))
        np.testing.assert_array_equal(acc, acc1)
        for got, want in zip(rings, rings1):
            np.testing.assert_array_equal(got, want)
        assert _replicas_off(acc_dev) == 0
        stats = agg.transport_stats()
        assert stats["flat_merges"] >= INTERVALS
        assert stats["delta_settles"] == INTERVALS
        assert stats["stream_allreduce_bytes"] == (
            INTERVALS * M // n_metric * B * 4)
    finally:
        ms.stop()


def test_settle_without_the_stream_exchange_is_wrong(monkeypatch,
                                                     one_device):
    """The control: with the settle's psum replaced by identity (and
    shard_map's replication check off, which would refuse that program),
    each stream copy of a shard adds only its own partial: the copies
    disagree and the counts read back are not the reference's, so the
    test above would fail."""
    import functools

    from loghisto_tpu.ops import sparse_ingest

    traffic, acc1, _, _ = one_device
    ms = _system(make_mesh(2, 2))
    agg = ms.aggregator
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)
    monkeypatch.setattr(sparse_ingest, "shard_map", functools.partial(
        jax.shard_map, check_vma=False))
    sparse_ingest.make_sharded_settle_fn.cache_clear()
    agg._select_packed_merge()
    try:
        for chunks, _ in traffic:
            for ids, vals in chunks:
                ms.record_batch(ids, vals)
        agg.flush(force=True)
        assert agg.wait_transfers(timeout=120)
        with agg._dev_lock:
            acc_dev = agg._dense_acc_locked()
            acc = np.asarray(acc_dev).astype(np.int64)
        assert agg.delta_settles == 1
        bulk = acc1.copy()
        bulk[ms.metric_id(ROUTE)] = 0
        assert _replicas_off(acc_dev) > 0
        assert acc.sum() < bulk.sum()
    finally:
        ms.stop()
        sparse_ingest.make_sharded_settle_fn.cache_clear()


def test_staged_cells_land_on_every_stream_slice():
    """A chunk of fewer cells than one slice holds is split into one
    contiguous run per stream slice; every slice's padding keeps the drop
    sentinel, and no cell is lost or repeated."""
    from loghisto_tpu.ops.commit import DROP_ID, CellStagingRing
    from loghisto_tpu.parallel.mesh import cell_sharding

    mesh = make_mesh(4, 2)
    ring = CellStagingRing(width=64, sharding=cell_sharding(mesh))
    n = 10
    ids = np.arange(100, 100 + n, dtype=np.int32)
    dev_ids, dev_idx, dev_w = ring.stage(ids, ids - 100, np.ones(n, np.int32))
    seen = []
    for sh in dev_ids.addressable_shards:
        got = np.asarray(sh.data)
        live = got[got != DROP_ID]
        assert len(live) > 0, sh.index
        assert (got[len(live):] == DROP_ID).all()
        if sh.device in mesh.devices[:, 0]:
            seen.append(live)
    assert len(seen) == mesh.shape[STREAM_AXIS]
    assert sorted(np.concatenate(seen).tolist()) == ids.tolist()
    host = np.asarray(dev_ids)
    np.testing.assert_array_equal(np.asarray(dev_idx)[host != DROP_ID],
                                  host[host != DROP_ID] - 100)
    assert np.asarray(dev_w).sum() == n


@pytest.mark.parametrize("slices", [1, 2, 4])
@pytest.mark.parametrize("n", [0, 1, 5, 16])
def test_stream_runs_lays_one_run_per_slice(slices, n):
    """``stream_runs`` puts ceil(n / slices) rows (fewer in the last
    slices) at the start of each slice, in order, and pads the rest: per
    column for 2-D rows, with a scalar for 1-D ones."""
    from loghisto_tpu.parallel.mesh import stream_runs

    width = 16
    rows = np.arange(3 * n, dtype=np.int32).reshape(n, 3) + 1
    got = stream_runs(width, rows, (-1, 0, 7), slices)
    flat = stream_runs(width, rows[:, 0], -5, slices)
    assert got.shape == (width, 3) and flat.shape == (width,)
    share = -(-n // slices)
    for s, (part, part1) in enumerate(zip(
            got.reshape(slices, width // slices, 3),
            flat.reshape(slices, width // slices))):
        run = rows[s * share:(s + 1) * share]
        np.testing.assert_array_equal(part[:len(run)], run)
        assert (part[len(run):] == (-1, 0, 7)).all()
        np.testing.assert_array_equal(part1[:len(run)], run[:, 0])
        assert (part1[len(run):] == -5).all()


def test_one_device_merge_compiles_to_the_same_programs():
    """Without a mesh the packed merge is the flat one-device program fed
    a host operand laid out as before (cells first, then id -1 pads), and
    the settle is ``settle_ingest_delta``."""
    import jax.numpy as jnp

    from loghisto_tpu.ops.sparse_ingest import (
        make_flat_sparse_ingest_fn, settle_ingest_delta,
    )
    from loghisto_tpu.parallel.aggregator import _MERGE_CHUNK, TPUAggregator

    agg = TPUAggregator(num_metrics=M, config=MetricConfig(bucket_limit=BL),
                        transport="sparse")
    try:
        assert agg._flat and agg._settle is settle_ingest_delta
        cells = np.array([[3, 5, 7], [9, -2, 1]], np.int32)
        pad = agg._merge_operand(cells)
        assert isinstance(pad, np.ndarray) and pad.shape == (_MERGE_CHUNK, 3)
        np.testing.assert_array_equal(pad[:2], cells)
        assert (pad[2:] == (-1, 0, 0)).all()
        delta = jnp.zeros(M * B, jnp.int32)
        ours = agg._packed_ingest.lower(delta, pad).as_text()
        before = make_flat_sparse_ingest_fn(BL, B).lower(delta, pad).as_text()
        assert ours == before
        assert agg._packed_ingest.__name__ == "sparse_ingest_flat"
    finally:
        agg.close()


def test_mesh_partial_settles_for_every_reader(tmp_path):
    """On a 2x2 mesh every reader settles the partial first and sees exact
    counts: growth (the row unit stays the metric axis), the spill fold,
    checkpoint save and restore; a failed merge that consumed the partial
    sheds exactly what the partial held."""
    from loghisto_tpu.utils import checkpoint

    rng = np.random.default_rng(17)
    ms = _system(make_mesh(2, 2))
    agg = ms.aggregator
    want = np.zeros((4 * M, B), np.int64)

    def merge(rows, fails=False):
        packed = np.stack([
            rng.choice(rows, 3000), rng.integers(-BL - 4, BL + 5, 3000),
            rng.integers(1, 40, 3000)], 1).astype(np.int32)
        agg.merge_packed(packed, wait=True)
        assert (agg._delta_samples > 0) != fails
        np.add.at(want, (packed[:, 0], np.clip(packed[:, 1], -BL, BL) + BL),
                  packed[:, 2])
        return packed

    def exact():
        with agg._dev_lock:
            acc = np.asarray(agg._acc, dtype=np.int64)
            assert agg._delta_samples == 0
            if agg._spill is not None:
                acc = acc + agg._spill
        np.testing.assert_array_equal(acc, want[:len(acc)])
        assert want[len(acc):].sum() == 0

    try:
        merge(np.arange(M))
        exact()
        merge(np.arange(M))
        with agg._dev_lock:
            assert agg._grow_locked(2 * M)
        assert agg.num_metrics == 2 * M
        assert agg._packed_ingest.__name__ == "sparse_ingest_flat_sharded"
        merge(np.arange(2 * M))
        exact()

        merge(np.arange(2 * M))
        with agg._dev_lock:
            agg._spill_fold_locked()
        assert agg._spill is not None
        exact()

        merge(np.arange(2 * M))
        path = str(tmp_path / "mesh.npz")
        checkpoint.save(path, aggregator=agg)
        exact()
        ms2 = _system(make_mesh(2, 2))
        try:
            with ms2.aggregator._dev_lock:
                assert ms2.aggregator._grow_locked(2 * M)
            checkpoint.restore(path, aggregator=ms2.aggregator)
            with ms2.aggregator._dev_lock:
                got = np.asarray(ms2.aggregator._acc, dtype=np.int64)
                if ms2.aggregator._spill is not None:
                    got = got + ms2.aggregator._spill
            assert got.sum() == want.sum()
        finally:
            ms2.stop()

        agg.collect(reset=True)
        want[:] = 0
        held = int(merge(np.arange(2 * M))[:, 2].sum())
        shed0 = agg._shed_samples

        def lost(delta, pad):
            delta.delete()
            raise RuntimeError("device lost")
        agg._packed_ingest = lost
        packed = merge(np.arange(2 * M), fails=True)
        assert agg._shed_samples - shed0 == held
        assert agg._delta is None and agg._delta_samples == 0
        want[:] = 0
        np.add.at(want, (packed[:, 0],
                         np.clip(packed[:, 1], -BL, BL) + BL), packed[:, 2])
        agg._device_down_until = 0.0
        exact()  # the failed launch's cells went to the exact host spill
    finally:
        ms.stop()
