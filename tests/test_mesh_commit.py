"""Mesh-sharded fused interval commit (the PR-8 tentpole): the one
donated-carry program per interval runs under ``shard_map`` on the
("stream", "metric") mesh — cell deltas psum over the stream axis ONCE,
then the acc fold, every tier's open-slot scatter, the activity stamp,
the EWMA bank update, and the commit-time CDF emission all execute
shard-local on metric-row-sharded carries.  Pins bit-identity against
the single-device fused path across rotation, registry growth,
lifecycle eviction/compaction, and drift scoring; the <= 2
dispatches / 1 upload budget; and mesh-shape-portable checkpoints."""

import datetime as dt

import numpy as np
import pytest

from loghisto_tpu.anomaly import AnomalyConfig, AnomalyManager
from loghisto_tpu.commit import IntervalCommitter
from loghisto_tpu.config import MetricConfig
from loghisto_tpu.lifecycle import LifecycleConfig, LifecycleManager
from loghisto_tpu.metrics import RawMetricSet
from loghisto_tpu.parallel.aggregator import TPUAggregator
from loghisto_tpu.parallel.mesh import METRIC_AXIS, make_mesh
from loghisto_tpu.window import TimeWheel

pytestmark = pytest.mark.mesh_commit

T0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)

MESH_SHAPES = [(2, 4), (4, 2)]


def _raw(i, histograms=None, rates=None, duration=1.0):
    return RawMetricSet(
        time=T0 + dt.timedelta(seconds=i), counters={},
        rates=dict(rates or {}), histograms=dict(histograms or {}),
        gauges={}, duration=duration,
    )


def _random_intervals(rng, n, names=6, cells_per=40):
    out = []
    for i in range(n):
        hists = {}
        for _ in range(int(rng.integers(0, names))):
            name = f"m{int(rng.integers(0, names))}"
            h = hists.setdefault(name, {})
            for _ in range(int(rng.integers(1, cells_per))):
                b = int(rng.integers(-900, 900))
                h[b] = h.get(b, 0) + int(rng.integers(1, 1000))
        out.append(_raw(i, hists, rates={"req": i % 3}))
    return out


def _build(mesh, num_metrics, tiers, chunk, lifecycle=None, anomaly=None,
           **agg_kw):
    """One fused pipeline (sharded when ``mesh`` is set)."""
    cfg = MetricConfig(bucket_limit=256)
    agg = TPUAggregator(num_metrics=num_metrics, config=cfg, mesh=mesh,
                        **agg_kw)
    wheel = TimeWheel(num_metrics=num_metrics, config=cfg, interval=1.0,
                      tiers=tiers, registry=agg.registry, mesh=mesh)
    lc = LifecycleManager(agg, wheel, lifecycle) if lifecycle else None
    am = AnomalyManager(agg, wheel, anomaly) if anomaly else None
    if lc is not None and am is not None:
        lc.anomaly = am
    kw = {} if chunk is None else {"chunk": chunk}
    committer = IntervalCommitter(agg, wheel, lifecycle=lc, anomaly=am, **kw)
    return committer, agg, wheel, lc, am


def _pair(mesh_shape, num_metrics=16, tiers=((3, 1), (2, 3)), chunk=16,
          lifecycle=None, anomaly=None, **agg_kw):
    """The same configuration twice: sharded over ``mesh_shape`` and on
    a single device, both on the FUSED path, fed identically."""
    mesh = make_mesh(stream=mesh_shape[0], metric=mesh_shape[1])
    sharded = _build(mesh, num_metrics, tiers, chunk,
                     lifecycle=lifecycle, anomaly=anomaly, **agg_kw)
    single = _build(None, num_metrics, tiers, chunk,
                    lifecycle=lifecycle, anomaly=anomaly, **agg_kw)
    return sharded, single


def _assert_carries_identical(sharded, single, check_lifecycle=False,
                              check_anomaly=False):
    committer, agg, wheel, lc, am = sharded
    rcommitter, ragg, rwheel, rlc, ram = single
    assert np.array_equal(np.asarray(agg._acc), np.asarray(ragg._acc))
    for t, rt in zip(wheel._tiers, rwheel._tiers):
        assert np.array_equal(np.asarray(t.ring), np.asarray(rt.ring))
        assert t.slot == rt.slot
        assert t.in_slot == rt.in_slot
        assert np.array_equal(t.written, rt.written)
    if check_lifecycle:
        assert np.array_equal(np.asarray(lc._la), np.asarray(rlc._la))
        assert agg.registry.names() == ragg.registry.names()
        assert lc.evicted_series == rlc.evicted_series
        assert lc.overflowed_samples == rlc.overflowed_samples
    if check_anomaly:
        assert np.array_equal(np.asarray(am._prof), np.asarray(ram._prof))
        assert np.array_equal(np.asarray(am._wsum), np.asarray(ram._wsum))


# ---------------------------------------------------------------------- #
# parity: sharded fused == single-device fused, bit for bit
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
def test_sharded_matches_single_device_across_rotation(mesh_shape):
    sharded, single = _pair(mesh_shape)
    rng = np.random.default_rng(7)
    for raw in _random_intervals(rng, 10):
        m1 = sharded[0].commit(raw)
        m2 = single[0].commit(raw)
        assert m1 == m2
    assert sharded[0].fused_intervals > 0
    _assert_carries_identical(sharded, single)


@pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
def test_sharded_matches_single_device_past_wheel_rows(mesh_shape):
    """Registry growth past the wheel's rows: the grown accumulator's
    metric-row shards no longer line up with the rings' shards, so the
    sharded program carries a second ring-width delta — identically to
    the single-device drop-off semantics."""
    sharded, single = _pair(mesh_shape, num_metrics=8, chunk=8,
                            max_metrics=32)
    for i in range(6):
        hists = {f"grow{j}": {j: 10 + j} for j in range(i + 4)}
        raw = _raw(i, hists)
        sharded[0].commit(raw)
        single[0].commit(raw)
    assert sharded[1].num_metrics > sharded[2].num_metrics  # grew
    _assert_carries_identical(sharded, single)


@pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
def test_sharded_eviction_and_compaction_parity(mesh_shape):
    """TTL eviction (host victim decisions + fold-evict program) and
    explicit slot compaction produce identical carries on sharded and
    single-device state — activity vector, overflow rows, registry."""
    cfg = LifecycleConfig(ttl_intervals=2, check_every=1,
                          auto_compact_fragmentation=0.0)
    sharded, single = _pair(mesh_shape, num_metrics=32, tiers=((4, 2),),
                            lifecycle=cfg)
    rng = np.random.default_rng(0)
    for i in range(8):
        h = {}
        for j in range(3):  # fresh names every interval -> churn
            counts = {int(b): int(c) for b, c in zip(
                rng.integers(-64, 64, 3), rng.integers(1, 20, 3))}
            h[f"api.u{i}_{j}.lat"] = counts
        h["api.steady"] = {0: 2}
        raw = _raw(i, h)
        sharded[0].commit(raw)
        single[0].commit(raw)
    assert sharded[3].evicted_series > 0
    _assert_carries_identical(sharded, single, check_lifecycle=True)
    # explicit compaction permutes live rows identically on both
    sharded[3].compact()
    single[3].compact()
    _assert_carries_identical(sharded, single, check_lifecycle=True)


@pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
def test_sharded_drift_scoring_parity(mesh_shape):
    """EWMA bank updates ride the sharded commit program and the fused
    divergence dispatch runs on sharded carries: banks and served
    scores match the single-device path."""
    acfg = AnomalyConfig(decay=0.8, min_samples=16)
    sharded, single = _pair(mesh_shape, tiers=((4, 1),), anomaly=acfg)
    unimodal = {90: 100, 100: 200, 110: 100}
    bimodal = {50: 120, 90: 40, 100: 160, 110: 40, 150: 120}
    for i in range(6):
        h = {"lat": unimodal if i < 4 else bimodal}
        sharded[0].commit(_raw(i, h))
        single[0].commit(_raw(i, h))
    am, ram = sharded[4], single[4]
    assert am.scored_intervals == ram.scored_intervals > 0
    _assert_carries_identical(sharded, single, check_anomaly=True)
    s, rs = am.scores_for("lat"), ram.scores_for("lat")
    assert s is not None and rs is not None
    for k in s:
        assert s[k] == pytest.approx(rs[k], rel=1e-6, abs=1e-7), k
    assert s["ks"] > 0.0  # the drift actually registered


# ---------------------------------------------------------------------- #
# the dispatch budget survives sharding
# ---------------------------------------------------------------------- #

def test_sharded_commit_keeps_dispatch_and_upload_budget():
    (committer, agg, wheel, _, _), _ = _pair((2, 4), num_metrics=16,
                                             chunk=None)
    committer.warmup()
    calls = {"fused": 0, "snap": 0}
    real_fused, real_snap = committer._fused, committer._fused_snap

    def counting_fused(*a, **kw):
        calls["fused"] += 1
        return real_fused(*a, **kw)

    def counting_snap(*a, **kw):
        calls["snap"] += 1
        return real_snap(*a, **kw)

    committer._fused = counting_fused
    committer._fused_snap = counting_snap
    for i in range(4):
        hists = {f"m{j}": {j - 2: 5 * (i + 1)} for j in range(8)}
        up0 = committer._staging.uploads
        assert committer.commit(_raw(i, hists)) == "fused"
        assert calls["fused"] + calls["snap"] <= 2, (
            "sharded interval exceeded 2 dispatches")
        assert calls["snap"] == 1
        assert committer._staging.uploads - up0 == 1
        calls["fused"] = calls["snap"] = 0


def test_sharded_chunk_must_split_over_stream_axis():
    mesh = make_mesh(stream=4, metric=2)
    cfg = MetricConfig(bucket_limit=256)
    agg = TPUAggregator(num_metrics=16, config=cfg, mesh=mesh)
    wheel = TimeWheel(num_metrics=16, config=cfg, interval=1.0,
                      tiers=((3, 1),), registry=agg.registry, mesh=mesh)
    with pytest.raises(ValueError, match="stream"):
        IntervalCommitter(agg, wheel, chunk=6)  # 6 % 4 != 0


# ---------------------------------------------------------------------- #
# checkpoint portability: save on one mesh shape, restore on another
# ---------------------------------------------------------------------- #

def test_checkpoint_roundtrip_across_mesh_shapes(tmp_path):
    from loghisto_tpu.utils import checkpoint

    lcfg = LifecycleConfig(ttl_intervals=8, check_every=4)
    acfg = AnomalyConfig(decay=0.8, min_samples=16)
    (committer, agg, wheel, lc, am), _ = (
        _pair((2, 4), num_metrics=16, tiers=((4, 1),),
              lifecycle=lcfg, anomaly=acfg))
    unimodal = {90: 100, 100: 200, 110: 100}
    for i in range(5):
        committer.commit(_raw(i, {"api.lat": unimodal, "api.rps": {0: 7}}))
    path = str(tmp_path / "mesh.npz")
    checkpoint.save(path, aggregator=agg, lifecycle=lc, anomaly=am)

    # restore onto a DIFFERENT mesh shape: row shards re-place through
    # each owner's canonical sharding helpers
    mesh18 = make_mesh(stream=1, metric=8)
    fresh, fagg, fwheel, flc, fam = _build(
        mesh18, 16, ((4, 1),), 16, lifecycle=lcfg, anomaly=acfg)
    checkpoint.restore(path, aggregator=fagg, lifecycle=flc, anomaly=fam)

    src = np.asarray(agg._acc)
    dst = np.asarray(fagg._acc)
    # restore remaps rows by NAME into the fresh registry
    for name in ("api.lat", "api.rps"):
        sid = agg.registry.lookup(name)
        did = fagg.registry.lookup(name)
        assert did is not None
        assert np.array_equal(src[sid], dst[did]), name
        assert np.array_equal(
            np.asarray(am._prof)[:, sid], np.asarray(fam._prof)[:, did])
        assert np.array_equal(
            np.asarray(am._wsum)[:, sid], np.asarray(fam._wsum)[:, did])
    # the restored carries landed on the 1x8 mesh's row sharding
    assert fagg._acc.sharding.mesh.shape[METRIC_AXIS] == 8
    # and the restored pipeline still commits fused
    assert fresh.commit(_raw(9, {"api.lat": unimodal})) == "fused"


# ---------------------------------------------------------------------- #
# system wiring: the two mesh ValueErrors are gone
# ---------------------------------------------------------------------- #

def test_system_mesh_lifecycle_anomaly_auto_resolves_fused():
    from loghisto_tpu.system import TPUMetricSystem

    mesh = make_mesh(stream=2, metric=4)
    ms = TPUMetricSystem(
        interval=0.05, sys_stats=False, num_metrics=16, mesh=mesh,
        retention=((8, 1), (4, 2)), commit="auto",
        lifecycle=LifecycleConfig(ttl_intervals=3, check_every=2),
        anomaly=AnomalyConfig(decay=0.8, min_samples=4),
    )
    try:
        assert ms.commit_path == "fused"
        assert ms.committer is not None
        assert ms.lifecycle is not None
        assert ms.anomaly is not None
        rng = np.random.default_rng(3)
        for i in range(4):
            h = {"api.lat": {int(b): 1 for b in rng.integers(-40, 40, 50)}}
            assert ms.committer.commit(_raw(i, h)) == "fused"
        q = ms.retention.query("api.lat", percentiles=(0.5, 0.99))
        assert q is not None and "api.lat" in q.metrics
    finally:
        ms.stop()
