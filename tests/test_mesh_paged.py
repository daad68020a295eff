"""r18 mesh-sharded paged storage: the page pool, the fused paged
committer, lifecycle, and v3 checkpoints all running on sharded
carries, pinned bit-identical to the single-device oracle.

Every parity assert here is exact (np.array_equal, not allclose): the
paged commit is an int32 scatter plus one stream-axis psum, both
order-free, so a sharded run that differs from single-device by even
one count is a translation/rebase bug, never float noise.  The mesh
shapes are every factorization of the conftest's 8 virtual CPU
devices — the same grid test_mesh.py pins for the dense path.
"""

import datetime as dt
import os
import tempfile

import jax
import numpy as np
import pytest

from loghisto_tpu.commit import IntervalCommitter
from loghisto_tpu.config import MetricConfig
from loghisto_tpu.lifecycle import LifecycleManager
from loghisto_tpu.lifecycle.policy import LifecycleConfig
from loghisto_tpu.metrics import RawMetricSet
from loghisto_tpu.paging import PagedStore, PagedStoreConfig
from loghisto_tpu.parallel.aggregator import TPUAggregator
from loghisto_tpu.parallel.mesh import make_mesh
from loghisto_tpu.utils import checkpoint
from loghisto_tpu.window import TimeWheel

pytestmark = pytest.mark.mesh_paged

MESH_SHAPES = [(8, 1), (4, 2), (2, 4), (1, 8)]
T0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
M, BL = 64, 128  # >= 257 buckets: clears the one-page minimum
CFG = MetricConfig(bucket_limit=BL)


def _packed(rng, n, m=M, bl=BL):
    out = np.empty((n, 3), np.int32)
    out[:, 0] = rng.integers(0, m, n)
    out[:, 1] = rng.integers(-bl, bl + 1, n)
    out[:, 2] = rng.integers(1, 50, n)
    return out


def _raw(i, hists):
    return RawMetricSet(
        time=T0 + dt.timedelta(seconds=i), counters={}, rates={},
        histograms=hists, gauges={}, duration=1.0,
    )


def _payloads(rng, intervals, series, bl=BL, draws=16):
    out = []
    for _ in range(intervals):
        hists = {}
        for j in range(series):
            b = rng.integers(-bl, bl, draws)
            c = rng.integers(1, 40, draws)
            h = {}
            for bb, cc in zip(b, c):
                h[int(bb)] = h.get(int(bb), 0) + int(cc)
            hists[f"h{j}"] = h
        out.append(hists)
    return out


# ---------------------------------------------------------------------- #
# store-level commit parity
# ---------------------------------------------------------------------- #


@pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
def test_sharded_commit_bit_identical_to_single(mesh_shape):
    assert jax.device_count() >= 8, "conftest must provide 8 CPU devices"
    packed = _packed(np.random.default_rng(0), 5000)

    ref = PagedStore(M, BL, config=PagedStoreConfig(pool_pages=256))
    applied_ref = ref.commit(packed)
    oracle = ref.decode_dense()

    s, t = mesh_shape
    pg = PagedStore(
        M, BL, config=PagedStoreConfig(pool_pages=256),
        mesh=make_mesh(stream=s, metric=t),
    )
    assert pg.commit(packed) == applied_ref
    np.testing.assert_array_equal(pg.decode_dense(), oracle)
    # per-shard occupancy surface the gauges/watchdog read: every shard
    # reports, fractions live in [0, 1), free pages complement occupancy
    occ = pg.shard_occupancy()
    assert len(occ) == t
    assert all(0.0 <= f < 1.0 for f in occ)
    assert pg.pool_saturation() == max(occ)


# ---------------------------------------------------------------------- #
# full committer pipeline: pool + retention rings, <= 2 dispatches
# ---------------------------------------------------------------------- #


def _run_committer(raws, mesh):
    agg = TPUAggregator(
        num_metrics=M, config=CFG, storage="paged",
        paged_config=PagedStoreConfig(pool_pages=256), mesh=mesh,
    )
    wheel = TimeWheel(
        num_metrics=M, config=CFG, interval=1.0, tiers=((8, 1), (4, 8)),
        registry=agg.registry, mesh=mesh,
    )
    com = IntervalCommitter(agg, wheel)
    com.warmup()
    for r in raws:
        com.commit(r)
    assert com.fanout_intervals == 0
    rings = [np.asarray(t.ring) for t in wheel._tiers]
    return agg.paged.decode_dense(), rings, com.last_dispatches


def test_committer_pipeline_parity_and_dispatch_budget():
    rng = np.random.default_rng(0)
    raws = [_raw(i, h) for i, h in enumerate(_payloads(rng, 4, M))]
    oracle, oracle_rings, d0 = _run_committer(raws, None)
    assert d0 <= 2
    for s, t in MESH_SHAPES:
        dec, rings, disp = _run_committer(raws, make_mesh(stream=s, metric=t))
        assert disp <= 2, (s, t, disp)
        np.testing.assert_array_equal(dec, oracle)
        for ring, want in zip(rings, oracle_rings):
            np.testing.assert_array_equal(ring, want)


# ---------------------------------------------------------------------- #
# lifecycle on paged sharded carries
# ---------------------------------------------------------------------- #


def _run_lifecycle(payloads, mesh):
    agg = TPUAggregator(
        num_metrics=M, config=CFG, storage="paged",
        paged_config=PagedStoreConfig(pool_pages=256), mesh=mesh,
    )
    wheel = TimeWheel(
        num_metrics=M, config=CFG, interval=1.0, tiers=((4, 2), (3, 4)),
        registry=agg.registry, mesh=mesh,
    )
    lc = LifecycleManager(agg, wheel, LifecycleConfig())
    com = IntervalCommitter(agg, wheel, lifecycle=lc)
    com.warmup()
    for i, h in enumerate(payloads[:3]):
        com.commit(_raw(i, h))
    vic = [agg.registry.lookup(f"h{j}") for j in range(4)]
    lc.evict_ids([v for v in vic if v is not None])
    lc.compact()
    for i, h in enumerate(payloads[3:]):
        com.commit(_raw(3 + i, h))
    assert com.fanout_intervals == 0
    return agg.paged.decode_dense(), [np.asarray(t.ring) for t in wheel._tiers]


def test_evict_and_compact_on_sharded_paged_matches_single():
    payloads = _payloads(np.random.default_rng(3), 6, 24, draws=8)
    oracle, oracle_rings = _run_lifecycle(payloads, None)
    for s, t in [(8, 1), (2, 4), (1, 8)]:
        dec, rings = _run_lifecycle(payloads, make_mesh(stream=s, metric=t))
        np.testing.assert_array_equal(dec, oracle)
        for ring, want in zip(rings, oracle_rings):
            np.testing.assert_array_equal(ring, want)


def test_grow_and_cross_shard_permutation_preserve_data_and_codecs():
    rng = np.random.default_rng(0)
    m = 32
    packed = _packed(rng, 3000, m=m)
    pg = PagedStore(
        m, BL, config=PagedStoreConfig(pool_pages=128),
        mesh=make_mesh(stream=2, metric=4),
    )
    pg.commit(packed)
    before = pg.decode_dense()
    codecs_before = pg.codec_names()

    pg.grow(64)
    after = pg.decode_dense()
    assert after.shape == (64, before.shape[1])
    np.testing.assert_array_equal(after[:m], before)
    assert pg.codec_names()[:m] == codecs_before

    # post-grow commits land, including into rows the grow created
    packed2 = packed.copy()
    packed2[:, 0] = rng.integers(0, 64, len(packed2))
    pg.commit(packed2)
    want = after.copy()
    np.add.at(
        want, (packed2[:, 0], np.clip(packed2[:, 1], -BL, BL) + BL),
        packed2[:, 2],
    )
    np.testing.assert_array_equal(pg.decode_dense(), want)

    # a full shuffle moves rows BETWEEN shard arenas: pages must be
    # re-homed into the destination shard, not just re-pointed
    perm = [int(p) for p in np.random.default_rng(1).permutation(64)]
    dense_before = pg.decode_dense()
    pg.apply_permutation(perm, 64)
    expect = dense_before[np.asarray(perm)]
    np.testing.assert_array_equal(pg.decode_dense(), expect)


# ---------------------------------------------------------------------- #
# v3 checkpoints are mesh-shape-portable
# ---------------------------------------------------------------------- #


def _make_agg(mesh, storage="paged"):
    kw = dict(num_metrics=M, config=CFG, storage=storage)
    if storage == "paged":
        kw["paged_config"] = PagedStoreConfig(pool_pages=256)
    return TPUAggregator(mesh=mesh, **kw)


def test_checkpoint_round_trips_across_mesh_shapes_and_storage():
    rng = np.random.default_rng(0)
    src = _make_agg(make_mesh(stream=2, metric=4))
    for j in range(32):
        src._id_for(f"h{j}")
    src.paged.commit(_packed(rng, 2000, m=32))
    want = src.paged.decode_dense()
    codecs = src.paged.codec_names()

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "ck.npz")
        checkpoint.save(p, aggregator=src)

        # 2x4 -> 1x8: pages re-assigned against the target's arenas
        tgt = _make_agg(make_mesh(stream=1, metric=8))
        checkpoint.restore(p, aggregator=tgt)
        np.testing.assert_array_equal(tgt.paged.decode_dense(), want)
        got = tgt.paged.codec_names()
        assert all(a == b for a, b in zip(got, codecs) if b is not None)

        # 1x8 -> single device
        p2 = os.path.join(d, "ck2.npz")
        checkpoint.save(p2, aggregator=tgt)
        tgt2 = _make_agg(None)
        checkpoint.restore(p2, aggregator=tgt2)
        np.testing.assert_array_equal(tgt2.paged.decode_dense(), want)

        # paged(mesh) -> dense(single): the same file restores a dense
        # accumulator exactly
        dn = _make_agg(None, storage="dense")
        checkpoint.restore(p, aggregator=dn)
        acc = np.asarray(dn._acc).astype(np.int64)
        if dn._spill is not None:
            acc += dn._spill
        np.testing.assert_array_equal(acc, want)

        # dense(single) -> paged(mesh): re-sharded on the way back in
        p3 = os.path.join(d, "ck3.npz")
        checkpoint.save(p3, aggregator=dn)
        pm = _make_agg(make_mesh(stream=2, metric=4))
        checkpoint.restore(p3, aggregator=pm)
        np.testing.assert_array_equal(pm.paged.decode_dense(), want)


# ---------------------------------------------------------------------- #
# pool-saturation watchdog invariant
# ---------------------------------------------------------------------- #


class _FakeCommitter:
    fanout_intervals = 0
    bridge_evictions = 0
    intervals_committed = 0


class _FakeAgg:
    max_pending_samples = 100
    pending_samples = 0
    _xfer_queued_samples = 0
    _device_down_until = 0.0

    def __init__(self, paged):
        self.paged = paged


def test_watchdog_pool_saturation_fires_and_clears_on_grow():
    from loghisto_tpu.obs.health import HealthWatchdog

    pg = PagedStore(
        M, BL, config=PagedStoreConfig(pool_pages=128),
        mesh=make_mesh(stream=2, metric=4),
    )
    pg.commit(_packed(np.random.default_rng(0), 5000))
    sat = pg.pool_saturation()
    assert 0.0 < sat < 1.0

    # threshold just above the live occupancy: healthy
    wd = HealthWatchdog(
        _FakeCommitter(), _FakeAgg(pg), interval=0.05,
        pool_saturation_fraction=min(sat + 0.01, 1.0),
    )
    wd.note_commit(1)
    assert "pool_saturation" not in wd.report().reason_codes()

    # threshold just below: degraded, naming the hottest shard
    wd = HealthWatchdog(
        _FakeCommitter(), _FakeAgg(pg), interval=0.05,
        pool_saturation_fraction=max(sat - 0.01, 0.0),
    )
    wd.note_commit(1)
    rep = wd.report()
    assert "pool_saturation" in rep.reason_codes()
    (reason,) = [r for r in rep.reasons if r["code"] == "pool_saturation"]
    hot = max(range(len(pg.shard_occupancy())),
              key=pg.shard_occupancy().__getitem__)
    assert f"shard {hot}" in reason["detail"]

    # live state, not an event latch: releasing rows frees their pages
    # and the very next report() sees the drop
    pg.release_rows(list(range(M)))
    assert pg.pool_saturation() < max(sat - 0.01, 0.0)
    assert "pool_saturation" not in wd.report().reason_codes()


def test_paging_gauges_registered_for_sharded_store():
    from loghisto_tpu.metrics import MetricSystem

    ms = MetricSystem(interval=0.05, sys_stats=False)
    agg = TPUAggregator(
        num_metrics=M, config=CFG, storage="paged",
        paged_config=PagedStoreConfig(pool_pages=256),
        mesh=make_mesh(stream=2, metric=4),
    )
    agg.paged.commit(_packed(np.random.default_rng(0), 2000))
    agg.register_device_gauges(ms)
    gauges = ms.collect_raw_metrics().gauges
    assert "paging.PoolSaturation" in gauges
    assert "paging.AllocatedPages" in gauges
    assert "paging.PageAllocRate" in gauges
    assert "paging.SpilledCells" in gauges
    assert "paging.ShardFreePagesMin" in gauges
    for k in range(agg.paged._n_shards):
        assert f"paging.Shard{k}Occupancy" in gauges
    assert gauges["paging.PoolSaturation"] == pytest.approx(
        agg.paged.pool_saturation()
    )


# ---------------------------------------------------------------------- #
# the capability table admits the sharded routes
# ---------------------------------------------------------------------- #


def test_resolve_full_path_admits_paged_routes_on_capable_mesh():
    from loghisto_tpu.ops import dispatch

    mesh = make_mesh(stream=2, metric=4)
    fp = dispatch.resolve_full_path(
        1 << 20, 8193, "tpu", batch_size=1 << 20, mesh=mesh
    )
    assert fp.storage == "paged"
    assert fp.ingest == "fused_paged"
    assert fp.transport == "raw"
    assert fp.commit == "fused"
    assert "storage:paged" not in fp.reasons
    assert "ingest:fused_paged" not in fp.reasons


# ---------------------------------------------------------------------- #
# static contracts for every sharded paged program (ISSUE 20): exactly
# one stream-axis psum, donated carries alias outputs, and no dense
# [M, B] tensor anywhere in the traced programs
# ---------------------------------------------------------------------- #


def test_sharded_paged_static_contracts():
    from loghisto_tpu.analysis.jaxpr_audit import assert_contract

    for name in (
        "sharded_paged_commit",
        "sharded_paged_fused_commit",
        "sharded_paged_fused_commit_snapshot",
        "sharded_fused_paged_ingest",
        "paged_commit_jnp",
        "paged_commit_pallas",
        "paged_query",
    ):
        assert_contract(name)
