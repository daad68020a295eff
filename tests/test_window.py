"""Windowed retention store (timewheel): merge correctness against
re-aggregation, tier downsampling count preservation (property), ring
wrap, pallas/jnp parity, mesh sharding, journal backfill."""

import datetime as dt

import jax
import numpy as np
import pytest

try:  # property test uses hypothesis when present, seeded random otherwise
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from loghisto_tpu.config import MetricConfig
from loghisto_tpu.metrics import RawMetricSet
from loghisto_tpu.ops.codec import compress_np, decompress_np
from loghisto_tpu.ops.stats import percentiles_sparse
from loghisto_tpu.ops.window import (
    resolve_merge_path,
    window_merge,
    window_merge_pallas,
)
from loghisto_tpu.window import TierSpec, TimeWheel

pytestmark = pytest.mark.window

T0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)


def _raw(i, histograms=None, rates=None, duration=1.0, precision=100):
    """RawMetricSet for interval i; histograms maps name -> value array
    (bucketed here) or ready {bucket: count} dicts."""
    hists = {}
    for name, v in (histograms or {}).items():
        if isinstance(v, dict):
            hists[name] = v
        else:
            ub, cnt = np.unique(compress_np(np.asarray(v, dtype=np.float64),
                                            precision), return_counts=True)
            hists[name] = {int(b): int(c) for b, c in zip(ub, cnt)}
    return RawMetricSet(
        time=T0 + dt.timedelta(seconds=i), counters={},
        rates=dict(rates or {}), histograms=hists, gauges={},
        duration=duration,
    )


# ---------------------------------------------------------------------- #
# acceptance: query over 60 intervals == re-aggregating the union
# ---------------------------------------------------------------------- #

def test_sixty_interval_window_matches_reaggregation():
    cfg = MetricConfig(bucket_limit=4096)
    wheel = TimeWheel(num_metrics=8, config=cfg, interval=1.0,
                      tiers=[TierSpec(60, 1)])
    rng = np.random.default_rng(42)
    all_vals = []
    for i in range(60):
        vals = rng.lognormal(8.0, 2.0, 200)
        all_vals.append(vals)
        wheel.push(_raw(i, {"lat": vals}))
    ps = (0.5, 0.9, 0.99, 0.999)
    res = wheel.query("lat", window=60.0, percentiles=ps)
    assert res.slots == 60 and res.covered_s == 60.0

    concat = np.concatenate(all_vals)
    entry = res.metrics["lat"]
    assert entry["count"] == len(concat)

    # exactness: the wheel's answer IS re-aggregation — same values
    # bucketed once, merged by addition, same percentile selection
    buckets = compress_np(concat, cfg.precision)
    ub, cnt = np.unique(buckets, return_counts=True)
    expect = percentiles_sparse(ub, cnt.astype(np.uint64),
                                np.asarray(ps), cfg.precision)
    got = np.array([entry["p50"], entry["p90"], entry["p99"], entry["p99.9"]])
    np.testing.assert_allclose(got, expect, rtol=1e-6)

    # bucket contract: within 1% of the true sample percentiles
    true = np.quantile(concat, ps)
    np.testing.assert_allclose(got, true, rtol=0.011)


def test_query_cost_is_one_device_program():
    """Query dispatch accounting, both engines: with snapshots the query
    never touches the full-recompute stats program (one sparse gather on
    the first query, ZERO dispatches on a repeat at the same epoch);
    with snapshots off, the recompute is one fused stats call — no
    per-interval device loop either way."""
    cfg = MetricConfig(bucket_limit=256)
    wheel = TimeWheel(num_metrics=4, config=cfg, tiers=[TierSpec(16, 1)])
    for i in range(16):
        wheel.push(_raw(i, {"m": [float(i + 1)] * 10}))
    stats_calls, gather_calls = [], []
    inner_stats = wheel._stats_fn
    inner_gather = wheel._query_fn
    wheel._stats_fn = lambda *a: (stats_calls.append(1), inner_stats(*a))[1]
    wheel._query_fn = lambda *a: (gather_calls.append(1), inner_gather(*a))[1]
    wheel.query("m", window=16.0)
    assert len(stats_calls) == 0 and len(gather_calls) == 1
    wheel.query("m", window=16.0)  # same epoch: host result cache
    assert len(stats_calls) == 0 and len(gather_calls) == 1

    plain = TimeWheel(num_metrics=4, config=cfg, tiers=[TierSpec(16, 1)],
                      snapshots=False)
    for i in range(16):
        plain.push(_raw(i, {"m": [float(i + 1)] * 10}))
    calls = []
    inner = plain._stats_fn
    plain._stats_fn = lambda *a: (calls.append(1), inner(*a))[1]
    plain.query("m", window=16.0)
    assert len(calls) == 1


# ---------------------------------------------------------------------- #
# property: tier downsampling preserves counts exactly
# ---------------------------------------------------------------------- #

def _downsample_property(interval_cells):
    cfg = MetricConfig(bucket_limit=64)
    wheel = TimeWheel(num_metrics=4, config=cfg,
                      tiers=[TierSpec(12, 1), TierSpec(4, 4)])
    total = 0
    for i, cells in enumerate(interval_cells):
        counts = {}
        for b, c in cells:
            counts[b] = counts.get(b, 0) + c
            total += c
        wheel.push(_raw(i, {"m": counts}))
    # both tiers retain every interval here (12 and 16 interval spans)
    fine = wheel.query("m", window=12.0, percentiles=(), tier=0)
    coarse = wheel.query("m", window=16.0, percentiles=(), tier=1)
    fine_count = fine.metrics.get("m", {}).get("count", 0)
    coarse_count = coarse.metrics.get("m", {}).get("count", 0)
    assert fine_count == coarse_count == total


if HAVE_HYPOTHESIS:
    @given(
        st.lists(
            st.lists(
                st.tuples(st.integers(-64, 64), st.integers(1, 1000)),
                min_size=0, max_size=5,
            ),
            min_size=1, max_size=12,
        )
    )
    @settings(max_examples=25, deadline=None)
    def test_downsampling_preserves_total_counts(interval_cells):
        _downsample_property(interval_cells)
else:
    @pytest.mark.parametrize("seed", range(12))
    def test_downsampling_preserves_total_counts(seed):
        rng = np.random.default_rng(seed)
        interval_cells = [
            [
                (int(rng.integers(-64, 65)), int(rng.integers(1, 1001)))
                for _ in range(int(rng.integers(0, 6)))
            ]
            for _ in range(int(rng.integers(1, 13)))
        ]
        _downsample_property(interval_cells)


def test_coarse_tier_slot_is_sum_of_fine_intervals():
    """Tier promotion is literally a bucket-tensor add: one coarse slot
    holds the exact sum of its res fine intervals."""
    cfg = MetricConfig(bucket_limit=32)
    wheel = TimeWheel(num_metrics=2, config=cfg,
                      tiers=[TierSpec(8, 1), TierSpec(2, 4)])
    for i in range(4):  # exactly one full coarse slot
        wheel.push(_raw(i, {"m": {i: 10 * (i + 1)}}))
    fine = np.asarray(window_merge(wheel._tiers[0].ring,
                                   np.ones(8, dtype=bool)))
    coarse_slot = np.asarray(wheel._tiers[1].ring[0])
    np.testing.assert_array_equal(fine, coarse_slot)


# ---------------------------------------------------------------------- #
# ring mechanics
# ---------------------------------------------------------------------- #

def test_ring_wrap_drops_oldest():
    cfg = MetricConfig(bucket_limit=32)
    wheel = TimeWheel(num_metrics=2, config=cfg, tiers=[TierSpec(4, 1)])
    for i in range(6):  # 6 intervals into 4 slots: 0 and 1 evicted
        wheel.push(_raw(i, {"m": {0: 1 << i}}))
    res = wheel.query("m", window=100.0, percentiles=())
    # only intervals 2..5 remain
    assert res.metrics["m"]["count"] == sum(1 << i for i in range(2, 6))
    assert res.slots == 4


def test_open_partial_slot_included_in_query():
    cfg = MetricConfig(bucket_limit=32)
    wheel = TimeWheel(num_metrics=2, config=cfg,
                      tiers=[TierSpec(4, 1), TierSpec(2, 4)])
    wheel.push(_raw(0, {"m": {5: 7}}))  # coarse slot still open (1/4)
    res = wheel.query("m", window=8.0, percentiles=(), tier=1)
    assert res.metrics["m"]["count"] == 7


def test_window_selects_finest_covering_tier():
    cfg = MetricConfig(bucket_limit=32)
    wheel = TimeWheel(num_metrics=2, config=cfg,
                      tiers=[TierSpec(4, 1), TierSpec(8, 4)])
    for i in range(2):
        wheel.push(_raw(i, {"m": {0: 1}}))
    assert wheel.query("m", window=3.0).tier == 0
    assert wheel.query("m", window=5.0).tier == 1   # beyond tier-0 span
    assert wheel.query("m", window=1e9).tier == 1   # clamps to coarsest


def test_query_pattern_and_empty_metrics_skipped():
    cfg = MetricConfig(bucket_limit=32)
    wheel = TimeWheel(num_metrics=4, config=cfg, tiers=[TierSpec(4, 1)])
    wheel.push(_raw(0, {"api.lat": {1: 5}, "db.lat": {1: 3}}))
    res = wheel.query("api.*", window=4.0, percentiles=())
    assert set(res.metrics) == {"api.lat"}
    assert wheel.query("nomatch*", window=4.0).metrics == {}


def test_registry_full_sheds_and_counts():
    cfg = MetricConfig(bucket_limit=32)
    wheel = TimeWheel(num_metrics=2, config=cfg, tiers=[TierSpec(4, 1)])
    wheel.push(_raw(0, {"a": {0: 1}, "b": {0: 2}, "c": {0: 40}}))
    assert wheel.shed_samples == 40
    assert wheel.query(window=4.0).metrics.keys() == {"a", "b"}


def test_counter_window_rate_uses_durations():
    cfg = MetricConfig(bucket_limit=32)
    wheel = TimeWheel(num_metrics=2, config=cfg, interval=1.0,
                      tiers=[TierSpec(8, 1)])
    # replayed history with 2s real intervals: 100 events per 2s = 50/s;
    # the slot walk is duration-driven, so "trailing 4s" is 2 slots
    for i in range(4):
        wheel.push(_raw(i, rates={"req": 100}, duration=2.0))
    total, covered = wheel.window_counter("req", 4.0)
    assert total == 200 and covered == 4.0
    assert wheel.window_rate("req", 4.0) == pytest.approx(50.0)
    assert wheel.window_rate("absent", 4.0) == 0.0


# ---------------------------------------------------------------------- #
# kernels: pallas/jnp parity, dispatch policy
# ---------------------------------------------------------------------- #

def test_pallas_merge_matches_jnp():
    rng = np.random.default_rng(0)
    ring = rng.integers(0, 1000, size=(5, 11, 65), dtype=np.int32)
    mask = np.array([1, 0, 1, 1, 0], dtype=np.int32)
    a = np.asarray(window_merge(ring, mask))
    b = np.asarray(window_merge_pallas(ring, mask, interpret=True))
    np.testing.assert_array_equal(a, b)
    # all-zero mask merges to zero
    z = np.asarray(window_merge_pallas(ring, np.zeros(5, np.int32),
                                       interpret=True))
    assert z.sum() == 0


def test_resolve_merge_path_policy():
    assert resolve_merge_path("auto", "cpu", mesh=False) == "jnp"
    # the Pallas tier's ring layout conversion does not fit a v5e at 10k
    assert resolve_merge_path("auto", "tpu", mesh=False) == "jnp"
    assert resolve_merge_path("pallas", "tpu", mesh=False) == "pallas"
    assert resolve_merge_path("auto", "tpu", mesh=True) == "jnp"
    assert resolve_merge_path("jnp", "tpu", mesh=False) == "jnp"
    with pytest.raises(ValueError):
        resolve_merge_path("pallas", "tpu", mesh=True)
    with pytest.raises(ValueError):
        resolve_merge_path("bogus", "cpu", mesh=False)


def test_mesh_sharded_query_matches_single_device():
    from loghisto_tpu.parallel.mesh import make_mesh

    cfg = MetricConfig(bucket_limit=128)
    mesh = make_mesh(stream=2, metric=4, devices=jax.devices()[:8])
    rng = np.random.default_rng(3)
    single = TimeWheel(num_metrics=8, config=cfg, tiers=[TierSpec(6, 1)])
    sharded = TimeWheel(num_metrics=8, config=cfg, tiers=[TierSpec(6, 1)],
                        mesh=mesh)
    for i in range(6):
        hists = {f"m{j}": rng.lognormal(5, 1, 50) for j in range(5)}
        raw = _raw(i, hists)
        single.push(raw)
        sharded.push(raw)
    a = single.query(window=6.0, percentiles=(0.5, 0.99))
    b = sharded.query(window=6.0, percentiles=(0.5, 0.99))
    assert a.metrics == b.metrics


# ---------------------------------------------------------------------- #
# journal backfill
# ---------------------------------------------------------------------- #

def test_backfill_from_journal_lines_carries_duration():
    from loghisto_tpu.utils.journal import dump_line, parse_line

    cfg = MetricConfig(bucket_limit=64)
    wheel = TimeWheel(num_metrics=2, config=cfg, interval=1.0,
                      tiers=[TierSpec(8, 1)])
    lines = [
        dump_line(_raw(i, {"m": {3: 10}}, rates={"req": 60}, duration=0.5))
        for i in range(4)
    ]
    n = wheel.backfill(parse_line(s) for s in lines)
    assert n == 4
    # 60 events per 0.5s interval -> 120/s, only via the journaled duration
    assert wheel.window_rate("req", 2.0) == pytest.approx(120.0)
    assert wheel.query("m", window=2.0).metrics["m"]["count"] == 40


def test_old_journal_line_without_interval_key_falls_back():
    import json

    from loghisto_tpu.utils.journal import dump_line, parse_line

    line = dump_line(_raw(0, {"m": {0: 1}}, rates={"req": 10},
                          duration=2.5))
    obj = json.loads(line)
    assert obj["interval"] == 2.5
    del obj["interval"]  # a pre-duration-era line
    raw = parse_line(json.dumps(obj))
    assert raw.duration is None
    wheel = TimeWheel(num_metrics=2, config=MetricConfig(bucket_limit=32),
                      interval=3.0, tiers=[TierSpec(4, 1)])
    wheel.push(raw)  # falls back to the wheel's configured interval
    assert wheel.window_counter("req", 3.0) == (10, 3.0)


# ---------------------------------------------------------------------- #
# construction validation & sizing
# ---------------------------------------------------------------------- #

def test_constructor_validation():
    cfg = MetricConfig(bucket_limit=32)
    with pytest.raises(ValueError):
        TimeWheel(config=cfg, tiers=[])
    with pytest.raises(ValueError):
        TimeWheel(config=cfg, tiers=[TierSpec(4, 2), TierSpec(4, 2)])
    with pytest.raises(ValueError):
        TimeWheel(config=cfg, tiers=[TierSpec(0, 1)])
    with pytest.raises(ValueError):
        TimeWheel(config=cfg, interval=0.0)
    with pytest.raises(ValueError):
        TimeWheel(config=cfg, tiers=[TierSpec(2, 1)]).query(
            percentiles=(1.5,))


def test_hbm_bytes_accounting():
    cfg = MetricConfig(bucket_limit=32)  # 65 buckets
    wheel = TimeWheel(num_metrics=4, config=cfg,
                      tiers=[TierSpec(3, 1), TierSpec(2, 3)])
    assert wheel.hbm_bytes() == (3 + 2) * 4 * 65 * 4
    assert wheel.tiers == (TierSpec(3, 1), TierSpec(2, 3))
