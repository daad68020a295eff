"""VERDICT r1 items 5/6/7: accumulator overflow spill, registry growth
past capacity, and automatic ingest-path dispatch."""

import datetime

import numpy as np
import pytest

from loghisto_tpu.config import MetricConfig
from loghisto_tpu.metrics import RawMetricSet
from loghisto_tpu.ops.dispatch import choose_ingest_path
from loghisto_tpu.parallel.aggregator import TPUAggregator
from loghisto_tpu.registry import MetricRegistry, RegistryFullError

CFG = MetricConfig(bucket_limit=64)


def raw_set(histograms):
    return RawMetricSet(
        time=datetime.datetime.now(tz=datetime.timezone.utc),
        counters={}, rates={}, histograms=histograms, gauges={},
    )


# ---------------------------------------------------------------- dispatch

@pytest.fixture
def baked_thresholds():
    """Pin the dispatch globals to the baked defaults: these tests assert
    the FALLBACK policy, which a committed capture-derived
    dispatch_thresholds.json legitimately overrides at import time
    (override behavior is covered by test_dispatch_thresholds.py)."""
    from loghisto_tpu.ops import dispatch

    saved = (dispatch.SORT_MIN_METRICS, dispatch.PALLAS_SINGLE_METRIC,
             dispatch.HIGH_CARDINALITY_KERNEL, dispatch.FUSED_INGEST,
             dispatch.FUSED_MIN_BATCH)
    dispatch.SORT_MIN_METRICS = 4096
    dispatch.PALLAS_SINGLE_METRIC = True
    dispatch.HIGH_CARDINALITY_KERNEL = "sort"
    dispatch.FUSED_INGEST = True
    dispatch.FUSED_MIN_BATCH = 1 << 17
    yield
    (dispatch.SORT_MIN_METRICS, dispatch.PALLAS_SINGLE_METRIC,
     dispatch.HIGH_CARDINALITY_KERNEL, dispatch.FUSED_INGEST,
     dispatch.FUSED_MIN_BATCH) = saved


def test_choose_ingest_path_table(baked_thresholds):
    # the baked thresholds: scatter through the low/mid range,
    # sort-dedup at high metric cardinality on TPU
    assert choose_ingest_path(1, 8193, "tpu") == "pallas"
    assert choose_ingest_path(128, 8193, "tpu") == "scatter"
    # r13: the fused sample->scatter kernel is the high-cardinality pick
    # on TPU; resolve degrades it to HIGH_CARDINALITY_KERNEL when
    # fused_ingest_incapability names a blocker
    assert choose_ingest_path(10_000, 8193, "tpu") == "fused"
    assert choose_ingest_path(1, 8193, "cpu") == "scatter"
    assert choose_ingest_path(10_000, 8193, "cpu") == "scatter"


def test_resolve_ingest_path_guards_sort_shape(baked_thresholds):
    from loghisto_tpu.ops.dispatch import resolve_ingest_path

    # auto on TPU at high cardinality picks the fused kernel when the
    # batch bound is known to amortize its preprocess; with the bound
    # unknown it degrades to sort (when the combined int32 cell key
    # fits), and falls back to scatter when that would wrap
    assert resolve_ingest_path(
        "auto", 10_000, 8193, "tpu", batch_size=1 << 20
    ) == "fused"
    assert resolve_ingest_path("auto", 10_000, 8193, "tpu") == "sort"
    assert resolve_ingest_path("auto", 300_000, 8193, "tpu") == "scatter"
    # an explicit unsupportable choice fails at selection time, not as a
    # silently corrupted accumulator inside the traced kernel
    with pytest.raises(ValueError):
        resolve_ingest_path("sort", 300_000, 8193, "tpu")
    # matmul's flat int32 cell index has the same wrap bound
    with pytest.raises(ValueError):
        resolve_ingest_path("matmul", 300_000, 8193, "tpu")
    assert resolve_ingest_path("hybrid", 300_000, 8193, "tpu") == "hybrid"
    # the aggregator guards against its GROWTH cap, not just num_metrics
    with pytest.raises(ValueError):
        resolve_ingest_path(
            "sort", 10_000, 8193, "tpu", guard_metrics=300_000
        )
    # hybrid's float32 hot-head exactness needs per-batch n < 2^24
    with pytest.raises(ValueError):
        resolve_ingest_path(
            "hybrid", 100, 8193, "tpu", batch_size=1 << 24
        )
    assert resolve_ingest_path(
        "hybrid", 100, 8193, "tpu", batch_size=1 << 20
    ) == "hybrid"
    # pallas: auto picks it at M=1 only when the growth cap pins M=1 AND
    # the batch bound is KNOWN to satisfy the float32-exactness
    # precondition (ADVICE r2: an unknown bound would otherwise defer the
    # 2^24 check to a trace-time raise inside the step)
    assert resolve_ingest_path(
        "auto", 1, 8193, "tpu", batch_size=1 << 20
    ) == "pallas"
    assert resolve_ingest_path("auto", 1, 8193, "tpu") == "scatter"
    assert resolve_ingest_path(
        "auto", 1, 8193, "tpu", guard_metrics=8, batch_size=1 << 20
    ) == "scatter"
    # auto must apply the same batch bound explicit pallas enforces —
    # never defer a precondition into the traced kernel
    assert resolve_ingest_path(
        "auto", 1, 8193, "tpu", batch_size=1 << 24
    ) == "scatter"
    # shard_map-embedded resolves never auto-pick pallas (pallas_call
    # inside shard_map is not hardware-validated; explicit opt-in only)
    assert resolve_ingest_path(
        "auto", 1, 8193, "tpu", batch_size=1 << 20, mesh=True
    ) == "scatter"
    # explicit pallas demands a [1, B] starting shape
    with pytest.raises(ValueError, match="single-metric"):
        resolve_ingest_path("pallas", 16, 8193, "tpu")


def test_aggregator_rejects_hybrid_oversized_batch_at_construction():
    with pytest.raises(ValueError):
        TPUAggregator(
            num_metrics=4, config=CFG, batch_size=1 << 24,
            ingest_path="hybrid",
        )


def test_auto_is_default_and_resolves():
    agg = TPUAggregator(num_metrics=4, config=CFG, batch_size=64)
    # CI runs on CPU, where auto must resolve to scatter
    assert agg.ingest_path == "scatter"


# ------------------------------------------------------------ registry grow

def test_registry_growth_past_capacity():
    agg = TPUAggregator(num_metrics=4, config=CFG, batch_size=8)
    for i in range(20):  # 5x the initial row space
        agg.record(f"m{i}", float(i + 1))
    assert agg.num_metrics >= 20
    assert agg._acc.shape[0] == agg.num_metrics
    out = agg.collect().metrics
    for i in range(20):
        assert out[f"m{i}_count"] == 1.0, f"m{i} lost in growth"


def test_growth_preserves_existing_counts():
    agg = TPUAggregator(num_metrics=2, config=CFG, batch_size=4)
    for _ in range(10):
        agg.record("a", 5.0)
    for i in range(6):  # forces two doublings mid-interval
        agg.record(f"new{i}", 1.0)
    out = agg.collect().metrics
    assert out["a_count"] == 10.0
    assert all(out[f"new{i}_count"] == 1.0 for i in range(6))


def test_growth_stops_at_max_then_sheds():
    agg = TPUAggregator(
        num_metrics=2, config=CFG, batch_size=4, max_metrics=4
    )
    for i in range(8):
        agg.record(f"m{i}", 1.0)  # m4..m7 exceed max_metrics
    assert agg.num_metrics == 4
    assert agg._registry_shed_samples == 4
    out = agg.collect().metrics
    for i in range(4):
        assert out[f"m{i}_count"] == 1.0
    for i in range(4, 8):
        assert f"m{i}_count" not in out
    # sustained operation: already-registered names still ingest fine
    agg.record("m0", 2.0)
    assert agg.collect().metrics["m0_count"] == 1.0


def test_error_policy_raises():
    agg = TPUAggregator(
        num_metrics=1, config=CFG, on_registry_full="error"
    )
    agg.record("a", 1.0)
    with pytest.raises(RegistryFullError):
        agg.record("b", 1.0)


def test_growth_under_mesh():
    from loghisto_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(stream=4, metric=2)
    agg = TPUAggregator(
        num_metrics=4, config=CFG, batch_size=8, mesh=mesh
    )
    for i in range(10):
        agg.record(f"m{i}", 3.0)
    assert agg.num_metrics % 2 == 0  # divisibility preserved
    out = agg.collect().metrics
    for i in range(10):
        assert out[f"m{i}_count"] == 1.0


# ------------------------------------------------------------ overflow spill

def test_spill_engages_and_counts_stay_exact():
    agg = TPUAggregator(
        num_metrics=2, config=CFG, batch_size=64, spill_threshold=500
    )
    ids = np.zeros(64, dtype=np.int32)
    # 0.5 sits inside bucket_limit=64's representable range (bucket 41)
    vals = np.full(64, 0.5, dtype=np.float32)
    agg.registry.id_for("hot")
    for _ in range(30):  # 1920 samples >> threshold 500
        agg.record_batch(ids, vals)
    agg.flush(force=True)
    assert agg._spill is not None, "spill never engaged"
    assert agg._spill.sum() + np.asarray(agg._acc).sum() == 1920
    out = agg.collect().metrics
    assert out["hot_count"] == 1920.0
    # percentiles of a single-value histogram collapse to its bucket rep
    # (|v| < 1: the codec's documented ~1.4% transition-zone error applies)
    assert abs(out["hot_50"] / 0.5 - 1) < 0.02
    # interval closed: spill cleared
    assert agg._spill is None
    assert agg.collect().metrics.get("hot_count") is None


def test_single_bucket_firehose_would_wrap_int32():
    # the adversarial case VERDICT r1 asks for: one (metric, bucket) cell
    # receiving more than 2^31 samples in one interval.  merge_raw routes
    # giant counts through the int64 spill, so the total stays exact where
    # the round-1 int32 accumulator would have silently wrapped.
    agg = TPUAggregator(num_metrics=2, config=CFG, batch_size=64)
    agg.registry.id_for("hot")
    big = (1 << 31) + 12345  # > int32 max, single bucket
    agg.merge_raw(raw_set({"hot": {10: big}}))
    out = agg.collect().metrics
    assert out["hot_count"] == float(big)


def test_spill_threshold_crossing_via_merge_raw():
    agg = TPUAggregator(
        num_metrics=2, config=CFG, batch_size=64, spill_threshold=1000
    )
    agg.registry.id_for("h")
    # several merges whose sum crosses the threshold
    for _ in range(5):
        agg.merge_raw(raw_set({"h": {3: 300}}))
    out = agg.collect().metrics
    assert out["h_count"] == 1500.0


def test_merge_raw_single_launch_padding():
    # power-of-two padding: 5000 entries must go through one launch
    # (shape 8192), not a chunked loop
    agg = TPUAggregator(num_metrics=8, config=CFG, batch_size=64)
    hist = {f"n{i % 8}": {} for i in range(8)}
    rng = np.random.default_rng(3)
    total = 0
    for i in range(5000):
        name = f"n{i % 8}"
        bucket = int(rng.integers(-60, 60))
        hist[name][bucket] = hist[name].get(bucket, 0) + 2
        total += 2
    agg.merge_raw(raw_set(hist))
    out = agg.collect().metrics
    assert sum(out[f"n{i}_count"] for i in range(8)) == total


def test_spill_validation():
    with pytest.raises(ValueError):
        TPUAggregator(num_metrics=2, config=CFG, spill_threshold=0)
    with pytest.raises(ValueError):
        TPUAggregator(num_metrics=2, config=CFG, spill_threshold=1 << 31)
    with pytest.raises(ValueError):
        TPUAggregator(num_metrics=4, config=CFG, max_metrics=2)
    with pytest.raises(ValueError):
        TPUAggregator(num_metrics=4, config=CFG, on_registry_full="lru")


def test_registry_grow_is_monotonic():
    r = MetricRegistry(capacity=2)
    r.grow(8)
    assert r.capacity == 8
    r.grow(4)  # never shrinks
    assert r.capacity == 8


def test_multirow_growth_respects_row_tile():
    # max_metrics=20 is off the rows_tile=8 grid: growth must stop at 16
    # (rounded down), never corrupt the kernel with a 20-row rebuild
    agg = TPUAggregator(
        num_metrics=8, config=CFG, ingest_path="multirow", max_metrics=20
    )
    for i in range(20):
        agg.record(f"m{i}", 1.0)
    assert agg.num_metrics == 16
    out = agg.collect().metrics
    assert sum(
        1 for k in out
        if k.endswith("_count") and not k.endswith("_agg_count")
    ) == 16
    assert agg._registry_shed_samples == 4
    # aggregator still healthy after the exhausted grow
    agg.record("m0", 2.0)
    assert agg.collect().metrics["m0_count"] == 1.0


def test_mesh_growth_rounds_to_metric_axis():
    from loghisto_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(stream=4, metric=2)
    agg = TPUAggregator(
        num_metrics=2, config=CFG, mesh=mesh, max_metrics=5
    )
    for i in range(8):
        agg.record(f"m{i}", 1.0)
    assert agg.num_metrics == 4  # 5 rounded down to the metric-axis grid
    assert agg._registry_shed_samples == 4


def test_batch_size_spill_headroom_validated():
    with pytest.raises(ValueError):
        TPUAggregator(
            num_metrics=2, config=CFG,
            batch_size=1 << 31, spill_threshold=1 << 30,
        )


def test_merge_raw_shed_counts_true_sample_weight():
    agg = TPUAggregator(
        num_metrics=1, config=CFG, max_metrics=1, batch_size=64
    )
    agg.record("kept", 1.0)
    agg.merge_raw(raw_set({"dropped": {5: 1_000_000}}))
    assert agg._registry_shed_samples == 1_000_000
    out = agg.collect().metrics
    assert out["kept_count"] == 1.0
    assert "dropped_count" not in out
