"""Fast-path ingest parity: every dense kernel the dispatcher can pick
(sort-dedup, the Pallas row kernel, the fused sample->scatter kernel)
must agree exactly with the scatter path on the one [M, B] int32
accumulator layout."""

import jax.numpy as jnp
import numpy as np
import pytest

from loghisto_tpu.config import MetricConfig
from loghisto_tpu.ops.ingest import ingest_batch
from loghisto_tpu.ops.dispatch import ingest_step_fn
from loghisto_tpu.ops.pallas_kernels import (
    SAMPLE_TILE,
    make_pallas_row_ingest,
    pallas_histogram_row,
)

CFG = MetricConfig(bucket_limit=512)


def _scatter_reference(ids, values, m):
    acc = jnp.zeros((m, CFG.num_buckets), dtype=jnp.int32)
    return np.asarray(ingest_batch(acc, ids, values, CFG.bucket_limit))


def test_pallas_row_matches_scatter():
    rng = np.random.default_rng(1)
    n = 2 * SAMPLE_TILE
    values = rng.lognormal(2, 1.5, n).astype(np.float32)
    values[::5] *= -1
    row = jnp.zeros(CFG.num_buckets, dtype=jnp.int32)
    got = np.asarray(
        pallas_histogram_row(row, values, CFG.bucket_limit, interpret=True)
    )
    want = _scatter_reference(
        np.zeros(n, dtype=np.int32), values, 1
    )[0]
    np.testing.assert_array_equal(got, want)


def test_pallas_row_accumulates_existing_counts():
    values = np.full(SAMPLE_TILE, 7.0, dtype=np.float32)
    row = jnp.zeros(CFG.num_buckets, dtype=jnp.int32)
    f = make_pallas_row_ingest(CFG.num_buckets, CFG.bucket_limit,
                               interpret=True)
    row = f(row, values)
    row = f(row, values)
    got = np.asarray(row)
    assert got.sum() == 2 * SAMPLE_TILE


def test_pallas_row_rejects_ragged_batch():
    row = jnp.zeros(CFG.num_buckets, dtype=jnp.int32)
    with pytest.raises(ValueError):
        pallas_histogram_row(
            row, np.ones(100, dtype=np.float32), CFG.bucket_limit,
            interpret=True,
        )


def test_pallas_row_nan_goes_to_zero_bucket():
    values = np.full(SAMPLE_TILE, np.nan, dtype=np.float32)
    row = jnp.zeros(CFG.num_buckets, dtype=jnp.int32)
    got = np.asarray(
        pallas_histogram_row(row, values, CFG.bucket_limit, interpret=True)
    )
    assert got[CFG.bucket_limit] == SAMPLE_TILE  # center bucket


def test_sort_ingest_matches_scatter():
    from loghisto_tpu.ops.ingest import make_ingest_fn
    from loghisto_tpu.ops.sort_ingest import make_sort_ingest_fn

    cfg = MetricConfig(bucket_limit=256)
    rng = np.random.default_rng(9)
    n, m = 1 << 14, 37
    ids = rng.integers(-2, m + 3, n).astype(np.int32)  # includes invalid
    values = rng.lognormal(3, 2, n).astype(np.float32)
    values[:64] = np.nan
    values[64:128] = 0.0
    values[128:256] *= -1
    scatter = make_ingest_fn(cfg.bucket_limit)
    sort_fn = make_sort_ingest_fn(cfg.bucket_limit)
    ref = np.asarray(
        scatter(jnp.zeros((m, cfg.num_buckets), jnp.int32), ids, values)
    )
    got = np.asarray(
        sort_fn(jnp.zeros((m, cfg.num_buckets), jnp.int32), ids, values)
    )
    np.testing.assert_array_equal(got, ref)


def test_pallas_row_batch_matches_scatter_with_invalid_ids():
    """The masked (ids, values) form of the row kernel drops non-zero
    ids and ragged-N padding, bit-identical to scatter on [1, B]."""
    from loghisto_tpu.ops.ingest import make_ingest_fn
    from loghisto_tpu.ops.pallas_kernels import pallas_row_ingest_batch

    cfg = MetricConfig(bucket_limit=256)
    rng = np.random.default_rng(3)
    n = 5000  # deliberately NOT a multiple of SAMPLE_TILE
    ids = rng.integers(-1, 3, n).astype(np.int32)  # mix of 0 and invalid
    values = rng.lognormal(3, 2, n).astype(np.float32)
    values[:32] = np.nan
    values[32:64] *= -1
    scatter = make_ingest_fn(cfg.bucket_limit)
    ref = np.asarray(
        scatter(jnp.zeros((1, cfg.num_buckets), jnp.int32), ids, values)
    )
    got = np.asarray(
        pallas_row_ingest_batch(
            jnp.zeros((1, cfg.num_buckets), jnp.int32), ids, values,
            cfg.bucket_limit,
        )
    )
    np.testing.assert_array_equal(got, ref)


def test_pallas_row_batch_rejects_multi_row_acc():
    from loghisto_tpu.ops.pallas_kernels import pallas_row_ingest_batch

    with pytest.raises(ValueError, match="single-metric"):
        pallas_row_ingest_batch(
            jnp.zeros((2, 513), jnp.int32),
            np.zeros(8, np.int32), np.ones(8, np.float32), 256,
        )


def test_pallas_aggregator_and_growth_swap():
    """Explicit pallas path works through the aggregator, and registry
    growth past one row swaps to a dense-family kernel without losing
    the accumulated row."""
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    agg = TPUAggregator(
        num_metrics=1, config=MetricConfig(bucket_limit=64),
        ingest_path="pallas", batch_size=512, max_metrics=4,
    )
    agg.registry.id_for("first")
    agg.record_batch(
        np.zeros(1000, np.int32), np.full(1000, 7.5, np.float32)
    )
    agg.flush()
    assert agg.ingest_path == "pallas"
    # second name triggers growth -> kernel family swap
    agg.record("second", 3.25)
    agg.flush()
    assert agg.num_metrics > 1
    assert agg.ingest_path != "pallas"
    out = agg.collect().metrics
    assert out["first_count"] == 1000
    assert out["second_count"] == 1


def test_sort_ingest_accumulates_and_zipf_hot_cell():
    from loghisto_tpu.ops.sort_ingest import make_sort_ingest_fn

    cfg = MetricConfig(bucket_limit=64)
    m = 8
    # adversarial duplicate concentration: all samples in ONE cell — the
    # exact workload where duplicate-index scatter serializes
    ids = np.zeros(4096, dtype=np.int32)
    values = np.full(4096, 2.5, dtype=np.float32)
    sort_fn = make_sort_ingest_fn(cfg.bucket_limit)
    acc = jnp.zeros((m, cfg.num_buckets), jnp.int32)
    acc = sort_fn(acc, ids, values)
    acc = sort_fn(acc, ids, values)
    acc = np.asarray(acc)
    assert acc.sum() == 8192
    assert (acc > 0).sum() == 1  # single populated cell


def test_sort_ingest_via_aggregator():
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    agg = TPUAggregator(
        num_metrics=8, config=MetricConfig(bucket_limit=64),
        ingest_path="sort", batch_size=512,
    )
    rng = np.random.default_rng(4)
    for i in range(8):
        agg.registry.id_for(f"m{i}")
    ids = rng.integers(0, 8, 4096).astype(np.int32)
    vals = rng.lognormal(1, 1, 4096).astype(np.float32)
    agg.record_batch(ids, vals)
    out = agg.collect().metrics
    assert sum(
        out[f"m{i}_count"] for i in range(8)
    ) == 4096


def test_sort_ingest_shape_validated_at_construction():
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    with pytest.raises(ValueError, match="combined int32 cell key"):
        TPUAggregator(
            num_metrics=1 << 18, config=MetricConfig(bucket_limit=4096),
            ingest_path="sort", max_metrics=1 << 18,
        )


# -- every dispatchable kernel on the uniform step contract ---------------- #


def _rows(path, m=8):
    """Accumulator rows for a case: the Pallas row kernel is
    single-metric; the fused kernel needs whole 8-row tiles."""
    return 1 if path == "pallas" else m


@pytest.mark.parametrize("path", ["scatter", "sort", "fused", "pallas"])
def test_step_single_cell_and_all_invalid(path):
    cfg = MetricConfig(bucket_limit=64)
    step = ingest_step_fn(path)
    zeros = jnp.zeros((_rows(path), cfg.num_buckets), jnp.int32)
    # every sample in one cell: one duplicate run spanning the whole batch
    acc = np.asarray(step(
        zeros, np.zeros(4096, np.int32), np.full(4096, 2.5, np.float32),
        cfg.bucket_limit,
    ))
    assert acc[0].sum() == 4096 and (acc > 0).sum() == 1
    # every sample invalid: nothing lands, nothing crashes
    acc2 = step(
        zeros, np.full(512, -1, np.int32), np.ones(512, np.float32),
        cfg.bucket_limit,
    )
    assert np.asarray(acc2).sum() == 0


@pytest.mark.parametrize("path", ["sort", "fused", "pallas"])
def test_step_drops_bad_ids(path):
    m = _rows(path)
    i32 = np.iinfo(np.int32)
    ids = np.array([0, -1, m, 99, i32.min, i32.max], dtype=np.int32)
    values = np.ones(len(ids), dtype=np.float32)
    acc = np.asarray(ingest_step_fn(path)(
        jnp.zeros((m, CFG.num_buckets), jnp.int32), ids, values,
        CFG.bucket_limit,
    ))
    assert acc.sum() == 1 and acc[0].sum() == 1


@pytest.mark.parametrize("path", ["sort", "fused", "pallas"])
def test_step_accumulates_onto_existing_counts(path):
    """A second batch lands on the counts the first left, bit-identical
    to two scatter steps; the second batch is ragged (not a whole number
    of sample tiles)."""
    rng = np.random.default_rng(11)
    m, n = _rows(path), 3 * SAMPLE_TILE
    ragged = n - SAMPLE_TILE // 3
    ids = rng.integers(0, m, n).astype(np.int32)
    values = rng.lognormal(2, 2, n).astype(np.float32)
    values[::9] *= -1
    step = ingest_step_fn(path)
    got = want = jnp.zeros((m, CFG.num_buckets), jnp.int32)
    for k in (n, ragged):
        got = step(got, ids[:k], values[:k], CFG.bucket_limit)
        want = ingest_batch(want, ids[:k], values[:k], CFG.bucket_limit)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.asarray(got).sum()) == n + ragged


@pytest.mark.parametrize("path", ["sort", "fused"])
def test_step_zipf_hot_rows_with_edge_ids_match_scatter(path):
    """A Zipf batch whose head rows take most samples, spliced with every
    droppable id and zero / NaN / negative values, lands bit-identical
    to scatter."""
    rng = np.random.default_rng(4)
    m, n = 64, 6000
    ids = ((rng.zipf(1.3, n) - 1) % m).astype(np.int32)
    ids[:8] = [-1, 2**29, m, m - 1, 0, 7, 8, 9]
    values = rng.lognormal(3, 2, n).astype(np.float32)
    values[-3:] = [0.0, np.nan, -7.5]
    zeros = jnp.zeros((m, CFG.num_buckets), jnp.int32)
    want = ingest_batch(zeros, ids, values, CFG.bucket_limit)
    got = ingest_step_fn(path)(zeros, ids, values, CFG.bucket_limit)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_row_batch_rejects_oversized_batch():
    """The float32 scratch is exact below 2^24 samples per call; a batch
    at the bound is refused while tracing, before anything runs."""
    import jax

    from loghisto_tpu.ops.pallas_kernels import pallas_row_ingest_batch

    with pytest.raises(ValueError, match="2\\^24"):
        jax.eval_shape(
            lambda a, i, v: pallas_row_ingest_batch(a, i, v, 512),
            jax.ShapeDtypeStruct((1, 1025), jnp.int32),
            jax.ShapeDtypeStruct((1 << 24,), jnp.int32),
            jax.ShapeDtypeStruct((1 << 24,), jnp.float32),
        )


def test_sort_path_keeps_its_kernel_through_growth():
    """Registry growth re-pads the [M, B] accumulator under the sort
    kernel without a kernel swap; the combined cell key was validated
    against the growth cap at construction."""
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    agg = TPUAggregator(
        num_metrics=4, config=MetricConfig(bucket_limit=64),
        ingest_path="sort", batch_size=512, max_metrics=16,
    )
    for i in range(12):
        agg.record(f"m{i}", 1.5 + i)  # a new name grows the rows
        agg.record_batch(
            np.full(99, agg.registry.id_for(f"m{i}"), np.int32),
            np.full(99, 1.5 + i, np.float32),
        )
    assert agg.num_metrics == 16
    assert agg.ingest_path == "sort"
    assert agg._acc.shape == (16, 129)
    out = agg.collect().metrics
    assert [out[f"m{i}_count"] for i in range(12)] == [100] * 12
    agg.close()
