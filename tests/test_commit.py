"""Fused interval-commit pipeline: bit-identical parity with the
per-consumer fan-out (aggregator bridge-merge + per-tier scatter),
the <= 2-dispatches / 1-upload-per-interval guarantee, spill routing,
dispatch policy, and TPUMetricSystem wiring."""

import datetime as dt
import time

import numpy as np
import pytest

from loghisto_tpu.commit import IntervalCommitter, commit_incompatibility
from loghisto_tpu.config import MetricConfig
from loghisto_tpu.metrics import RawMetricSet
from loghisto_tpu.ops import dispatch
from loghisto_tpu.ops.commit import COMMIT_CHUNK, DROP_ID, CellStagingRing
from loghisto_tpu.ops.dispatch import resolve_commit_path
from loghisto_tpu.parallel.aggregator import TPUAggregator
from loghisto_tpu.window import TimeWheel

pytestmark = pytest.mark.commit

T0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)


def _raw(i, histograms=None, rates=None, duration=1.0):
    return RawMetricSet(
        time=T0 + dt.timedelta(seconds=i), counters={},
        rates=dict(rates or {}), histograms=dict(histograms or {}),
        gauges={}, duration=duration,
    )


def _pair(num_metrics=8, tiers=((3, 1), (2, 3)), chunk=16, **agg_kw):
    """A fused (committer) and a fan-out (merge_raw + push) instance of
    the same configuration, fed identically by the tests."""
    cfg = MetricConfig(bucket_limit=1024)
    agg = TPUAggregator(num_metrics=num_metrics, config=cfg, **agg_kw)
    wheel = TimeWheel(num_metrics=num_metrics, config=cfg, interval=1.0,
                      tiers=tiers, registry=agg.registry)
    committer = IntervalCommitter(agg, wheel, chunk=chunk)
    ref_agg = TPUAggregator(num_metrics=num_metrics, config=cfg, **agg_kw)
    ref_wheel = TimeWheel(num_metrics=num_metrics, config=cfg, interval=1.0,
                          tiers=tiers, registry=ref_agg.registry)
    return committer, agg, wheel, ref_agg, ref_wheel


def _assert_state_identical(agg, wheel, ref_agg, ref_wheel):
    assert np.array_equal(np.asarray(agg._acc), np.asarray(ref_agg._acc))
    for t, rt in zip(wheel._tiers, ref_wheel._tiers):
        assert np.array_equal(np.asarray(t.ring), np.asarray(rt.ring))
        assert t.slot == rt.slot
        assert t.in_slot == rt.in_slot
        assert np.array_equal(t.written, rt.written)
        assert np.allclose(t.durations, rt.durations)
        assert t.rates == rt.rates


def _random_intervals(rng, n, names=6, cells_per=40):
    """Interval stream with empty intervals, hot/cold names, and weights
    spanning the int32 wire range."""
    out = []
    for i in range(n):
        hists = {}
        for _ in range(int(rng.integers(0, names))):
            name = f"m{int(rng.integers(0, names))}"
            h = hists.setdefault(name, {})
            for _ in range(int(rng.integers(1, cells_per))):
                b = int(rng.integers(-9000, 9000))  # clips at bucket_limit
                h[b] = h.get(b, 0) + int(rng.integers(1, 1000))
        out.append(_raw(i, hists, rates={"req": i % 3}))
    return out


# ---------------------------------------------------------------------- #
# parity: fused == fan-out, bit for bit
# ---------------------------------------------------------------------- #

def test_fused_matches_fanout_bit_identical_across_rotation():
    """10 intervals across both tiers' rotation boundaries with a chunk
    small enough to force multi-chunk commits and tail pad sentinels."""
    committer, agg, wheel, ref_agg, ref_wheel = _pair(chunk=16)
    rng = np.random.default_rng(7)
    for raw in _random_intervals(rng, 10):
        committer.commit(raw)
        ref_agg.merge_raw(raw)
        ref_wheel.push(raw)
    assert committer.fused_intervals > 0
    _assert_state_identical(agg, wheel, ref_agg, ref_wheel)


def test_fused_matches_fanout_with_registry_growth_past_wheel_rows():
    """Names past the wheel's row count land in the grown accumulator and
    drop off every ring — identically on both paths."""
    committer, agg, wheel, ref_agg, ref_wheel = _pair(
        num_metrics=2, chunk=8, max_metrics=16,
    )
    for i in range(6):
        hists = {f"grow{j}": {j: 10 + j} for j in range(i + 2)}
        raw = _raw(i, hists)
        committer.commit(raw)
        ref_agg.merge_raw(raw)
        ref_wheel.push(raw)
    assert agg.num_metrics > wheel.num_metrics  # growth actually happened
    _assert_state_identical(agg, wheel, ref_agg, ref_wheel)


def test_empty_intervals_rotate_slots_identically():
    committer, agg, wheel, ref_agg, ref_wheel = _pair()
    for i in range(7):
        raw = _raw(i, {"m": {0: 1}} if i == 0 else None, rates={"r": 1})
        committer.commit(raw)
        ref_agg.merge_raw(raw)
        ref_wheel.push(raw)
    _assert_state_identical(agg, wheel, ref_agg, ref_wheel)
    assert wheel.intervals_pushed == 7


if True:  # hypothesis when present, seeded fallback otherwise
    try:
        from hypothesis import given, settings, strategies as st
        HAVE_HYPOTHESIS = True
    except ImportError:
        HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**32 - 1), st.integers(2, 12))
    def test_parity_property(seed, n_intervals):
        committer, agg, wheel, ref_agg, ref_wheel = _pair(chunk=8)
        rng = np.random.default_rng(seed)
        for raw in _random_intervals(rng, n_intervals, names=4):
            committer.commit(raw)
            ref_agg.merge_raw(raw)
            ref_wheel.push(raw)
        _assert_state_identical(agg, wheel, ref_agg, ref_wheel)
else:  # pragma: no cover - hypothesis is present in the image
    def test_parity_property():
        for seed in range(5):
            committer, agg, wheel, ref_agg, ref_wheel = _pair(chunk=8)
            rng = np.random.default_rng(seed)
            for raw in _random_intervals(rng, 8, names=4):
                committer.commit(raw)
                ref_agg.merge_raw(raw)
                ref_wheel.push(raw)
            _assert_state_identical(agg, wheel, ref_agg, ref_wheel)


# ---------------------------------------------------------------------- #
# the dispatch-count guarantee (ISSUE acceptance: <= 2 dispatches and
# exactly one cell upload per committed interval with 3 tiers)
# ---------------------------------------------------------------------- #

def test_one_dispatch_one_upload_per_interval_with_three_tiers():
    cfg = MetricConfig(bucket_limit=256)  # default tier GEOMETRY, small rings
    agg = TPUAggregator(num_metrics=16, config=cfg)
    wheel = TimeWheel(num_metrics=16, config=cfg, interval=1.0,
                      tiers=((60, 1), (60, 60), (24, 3600)),
                      registry=agg.registry)
    committer = IntervalCommitter(agg, wheel)  # default COMMIT_CHUNK
    committer.warmup()

    calls = {"fused": 0, "snap": 0, "wheel_jit": 0}
    real_fused = committer._fused
    real_snap = committer._fused_snap

    def counting_fused(*a, **kw):
        calls["fused"] += 1
        return real_fused(*a, **kw)

    def counting_snap(*a, **kw):
        calls["snap"] += 1
        return real_snap(*a, **kw)

    committer._fused = counting_fused
    committer._fused_snap = counting_snap
    from loghisto_tpu.window import store as store_mod

    real_scatter = store_mod._scatter_cells_jit
    real_open = store_mod._open_slot_jit

    def counting_scatter(*a, **kw):
        calls["wheel_jit"] += 1
        return real_scatter(*a, **kw)

    def counting_open(*a, **kw):
        calls["wheel_jit"] += 1
        return real_open(*a, **kw)

    store_mod._scatter_cells_jit = counting_scatter
    store_mod._open_slot_jit = counting_open
    try:
        for i in range(5):
            hists = {f"m{j}": {j - 2: 5 * (i + 1)} for j in range(8)}
            up0 = committer._staging.uploads
            mode = committer.commit(_raw(i, hists))
            assert mode == "fused"
            dispatches = calls["fused"] + calls["snap"]
            assert dispatches <= 2, "interval exceeded 2 dispatches"
            # the final chunk always routes through the snapshot-emitting
            # variant: percentile queries are prepaid by the same program
            assert calls["snap"] == 1
            assert committer._staging.uploads - up0 == 1, (
                "interval uploaded cells more than once"
            )
            assert committer.last_dispatches <= 2
            assert committer.last_uploads == 1
            calls["fused"] = calls["snap"] = 0
        # the wheel's per-tier fan-out jits never ran: the fused program
        # paid every tier (and the aggregator) itself
        assert calls["wheel_jit"] == 0
    finally:
        store_mod._scatter_cells_jit = real_scatter
        store_mod._open_slot_jit = real_open


def test_fused_commit_static_contracts():
    # the runtime dispatch counter above proves the ≤2-dispatch budget
    # end-to-end; the static auditor (ISSUE 20) pins the same programs'
    # trace-level contracts — dispatch count, donation aliasing, int32
    # scatter discipline — for every fused-commit variant at once
    from loghisto_tpu.analysis.jaxpr_audit import assert_contract

    for name in (
        "fused_commit",
        "fused_commit_full",
        "fused_commit_snapshot",
        "fused_commit_snapshot_full",
        "paged_fused_commit",
        "paged_fused_commit_snapshot",
    ):
        assert_contract(name)


# ---------------------------------------------------------------------- #
# spill routing: the int32 envelope falls back to the exact fan-out
# ---------------------------------------------------------------------- #

def test_spill_threshold_routes_interval_to_fanout():
    committer, agg, wheel, ref_agg, ref_wheel = _pair()
    agg.spill_threshold = 100
    ref_agg.spill_threshold = 100
    raw = _raw(0, {"m": {0: 999}})
    assert committer.commit(raw) == "fanout"
    ref_agg.merge_raw(raw)
    ref_wheel.push(raw)
    assert agg._spilled_samples == ref_agg._spilled_samples > 0
    # the wheel still received the interval (its own int32 clip contract)
    _assert_state_identical(agg, wheel, ref_agg, ref_wheel)


def test_giant_cell_weight_routes_interval_to_fanout():
    committer, agg, wheel, ref_agg, ref_wheel = _pair()
    raw = _raw(0, {"m": {0: 1 << 31}})
    assert committer.commit(raw) == "fanout"
    ref_agg.merge_raw(raw)
    ref_wheel.push(raw)
    assert agg._spilled_samples > 0
    _assert_state_identical(agg, wheel, ref_agg, ref_wheel)


# ---------------------------------------------------------------------- #
# staging ring + fused program contracts
# ---------------------------------------------------------------------- #

def test_staging_ring_width_contract():
    ring = CellStagingRing(width=8)
    with pytest.raises(ValueError):
        ring.stage(np.zeros(9, np.int32), np.zeros(9, np.int32),
                   np.zeros(9, np.int32))
    ids = np.array([1, 2], dtype=np.int32)
    dev_ids, dev_idx, dev_w = ring.stage(ids, ids, ids)
    got = np.asarray(dev_ids)
    assert got[0] == 1 and got[1] == 2
    assert (got[2:] == DROP_ID).all()  # pad sentinel sheds in-program
    assert (np.asarray(dev_w)[2:] == 0).all()
    assert ring.uploads == 1
    assert ring.bytes_uploaded == 3 * 8 * 4


@pytest.mark.parametrize("ring_kind", ["cells", "triples", "ingest"])
def test_staged_upload_survives_later_stages(ring_kind):
    """A staged upload keeps its contents however many chunks are staged
    after it: no host buffer handed to device_put is refilled while the
    device array can still read it (jax 0.9's CPU device_put aliases
    the host buffer; on a TPU the H2D copy is asynchronous)."""
    from loghisto_tpu.ops.commit import PagedTripleRing
    from loghisto_tpu.parallel.aggregator import IngestStagingRing

    width = 1 << 12  # large enough for an aligned, aliasable buffer

    def chunk(k):
        return np.arange(3, dtype=np.int32) + 10 * k

    if ring_kind == "cells":
        ring = CellStagingRing(width=width)
        stage = lambda k: ring.stage(chunk(k), chunk(k), chunk(k))[0]
    elif ring_kind == "triples":
        ring = PagedTripleRing(width=width)
        stage = lambda k: ring.stage(np.stack([chunk(k)] * 3, axis=1))[:, 0]
    else:
        ring = IngestStagingRing(slot_samples=width, depth=2)
        stage = lambda k: ring.stage(chunk(k), chunk(k).astype(np.float32))[0]
    first = stage(0)
    for k in range(1, 6):
        stage(k)
    np.testing.assert_array_equal(np.asarray(first)[:3], chunk(0))


def test_warmup_is_a_numerical_noop():
    committer, agg, wheel, _, _ = _pair()
    committer.warmup()
    assert np.asarray(agg._acc).sum() == 0
    assert all(np.asarray(t.ring).sum() == 0 for t in wheel._tiers)
    assert all(t.slot == 0 and t.in_slot == 0 for t in wheel._tiers)


def test_commit_incompatibility_detects_split_registries():
    cfg = MetricConfig()
    agg = TPUAggregator(num_metrics=4, config=cfg)
    foreign = TimeWheel(num_metrics=4, config=cfg, interval=1.0,
                        tiers=((2, 1),))  # its own registry
    assert commit_incompatibility(agg, foreign) is not None
    with pytest.raises(ValueError):
        IntervalCommitter(agg, foreign)


# ---------------------------------------------------------------------- #
# dispatch policy
# ---------------------------------------------------------------------- #

def test_resolve_commit_path_policy(monkeypatch):
    assert resolve_commit_path("auto", "cpu") == "fused"
    # a capable sharded configuration resolves to the sharded fused path
    # (legacy bool callers mean "sharded and capable")
    assert resolve_commit_path("auto", "tpu", mesh=True) == "fused"
    assert resolve_commit_path("fanout", "tpu") == "fanout"
    assert resolve_commit_path("fused", "tpu", mesh=True) == "fused"
    with pytest.raises(ValueError):
        resolve_commit_path("warp", "tpu")
    monkeypatch.setattr(dispatch, "FUSED_COMMIT", False)
    assert resolve_commit_path("auto", "cpu") == "fanout"
    assert resolve_commit_path("fused", "cpu") == "fused"  # explicit opt-in


# ---------------------------------------------------------------------- #
# TPUMetricSystem wiring
# ---------------------------------------------------------------------- #

def _drain(ms, deadline_s=10.0):
    """Wait until the committer has seen at least one interval."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        if ms.committer.intervals_committed > 0:
            return
        time.sleep(0.05)
    raise AssertionError("committer saw no interval before the deadline")


def test_system_fused_replaces_both_bridges():
    from loghisto_tpu.system import TPUMetricSystem

    ms = TPUMetricSystem(interval=0.2, sys_stats=False, num_metrics=16,
                         retention=((4, 1), (3, 2)), commit="fused")
    try:
        assert ms.commit_path == "fused"
        assert ms.committer is not None
        assert ms.aggregator._attached is None  # single subscription
        assert ms.retention._thread is None
        ms.start()
        for _ in range(50):
            ms.histogram("lat", 42.0)
        _drain(ms)
        assert ms.committer.fused_intervals > 0
        # retention and device stats both paid by the one bridge
        assert np.asarray(ms.retention._tiers[0].ring).sum() > 0
    finally:
        ms.stop()
    assert ms.committer._thread is None
    ms.start()  # restartable, like the per-consumer bridges
    assert ms.committer._thread is not None
    ms.stop()


def test_system_fanout_keeps_per_consumer_bridges():
    from loghisto_tpu.system import TPUMetricSystem

    ms = TPUMetricSystem(interval=0.5, sys_stats=False, num_metrics=16,
                         retention=((4, 1),), commit="fanout")
    try:
        assert ms.commit_path == "fanout"
        assert ms.committer is None
        assert ms.aggregator._attached is not None
        assert ms.retention._thread is not None
    finally:
        ms.stop()


def test_system_explicit_fused_with_foreign_wheel_raises():
    from loghisto_tpu.system import TPUMetricSystem

    cfg = MetricConfig()
    foreign = TimeWheel(num_metrics=16, config=cfg, interval=0.5,
                        tiers=((4, 1),))
    with pytest.raises(ValueError):
        TPUMetricSystem(interval=0.5, sys_stats=False, num_metrics=16,
                        config=cfg, retention=foreign, commit="fused")
    # auto degrades to the fan-out instead of raising
    ms = TPUMetricSystem(interval=0.5, sys_stats=False, num_metrics=16,
                        config=cfg, retention=foreign, commit="auto")
    try:
        assert ms.commit_path == "fanout"
        assert ms.committer is None
    finally:
        ms.stop()


def test_system_without_retention_has_no_committer():
    from loghisto_tpu.system import TPUMetricSystem

    ms = TPUMetricSystem(interval=0.5, sys_stats=False, num_metrics=16)
    try:
        assert ms.committer is None
        assert ms.commit_path == "fanout"
        assert ms.aggregator._attached is not None
    finally:
        ms.stop()


def test_committer_gauges_registered():
    from loghisto_tpu.system import TPUMetricSystem

    ms = TPUMetricSystem(interval=0.2, sys_stats=False, num_metrics=16,
                         retention=((4, 1),), commit="fused")
    try:
        ms.start()
        for _ in range(20):
            ms.histogram("lat", 1.0)
        _drain(ms)
        with ms._gauge_lock:
            names = set(ms._gauge_funcs)
        for g in ("commit.DispatchesPerInterval", "commit.H2DBytesPerInterval",
                  "commit.CellUploadsPerInterval", "commit.FusedIntervals",
                  "commit.LatencyP50Us", "commit.LatencyP99Us"):
            assert g in names
        assert ms._gauge_funcs["commit.FusedIntervals"]() > 0
        assert ms._gauge_funcs["commit.DispatchesPerInterval"]() <= 2
    finally:
        ms.stop()
