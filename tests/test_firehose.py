"""Firehose config test: on-device generation -> aggregation -> export
replay (small shapes on CPU)."""

import io

import numpy as np

from loghisto_tpu.config import MetricConfig
from loghisto_tpu.firehose import make_firehose_step, run_firehose, zipf_cdf


def test_zipf_cdf_shape_and_skew():
    cdf = zipf_cdf(100)
    assert cdf.shape == (100,)
    assert cdf[-1] == 1.0
    assert cdf[0] > 1.0 / 100  # head is hot


def test_firehose_step_accumulates():
    import jax
    import jax.numpy as jnp

    cfg = MetricConfig(bucket_limit=1024)
    step = make_firehose_step(64, 4096, cfg)
    acc = jnp.zeros((64, cfg.num_buckets), dtype=jnp.int32)
    key = jax.random.key(1)
    acc, key = step(acc, key)
    acc, key = step(acc, key)
    got = np.asarray(acc)
    assert got.sum() == 2 * 4096
    # Zipf skew: metric 0 is hottest
    row_counts = got.sum(axis=1)
    assert row_counts[0] == row_counts.max()


def test_firehose_step_path_parity():
    """The dispatched accumulation kernels are interchangeable inside the
    generation loop: same key stream -> bit-identical accumulators."""
    import jax
    import jax.numpy as jnp

    cfg = MetricConfig(bucket_limit=512)
    accs = {}
    for path in ("scatter", "sort", "fused"):
        step = make_firehose_step(64, 2048, cfg, ingest_path=path)
        acc = jnp.zeros((64, cfg.num_buckets), dtype=jnp.int32)
        acc, _ = step(acc, jax.random.key(7))
        accs[path] = np.asarray(acc)
    np.testing.assert_array_equal(accs["scatter"], accs["sort"])
    np.testing.assert_array_equal(accs["scatter"], accs["fused"])


def test_firehose_step_single_metric_pallas_matches_scatter():
    """The single-metric deployment's row kernel inside the generation
    loop: same key stream -> the accumulator scatter builds."""
    import jax
    import jax.numpy as jnp

    cfg = MetricConfig(bucket_limit=512)
    accs = {}
    for path in ("scatter", "pallas"):
        step = make_firehose_step(1, 4096, cfg, ingest_path=path)
        acc = jnp.zeros((1, cfg.num_buckets), dtype=jnp.int32)
        for _ in range(2):
            acc, key = step(acc, jax.random.key(3))
        accs[path] = np.asarray(acc)
    np.testing.assert_array_equal(accs["scatter"], accs["pallas"])
    assert accs["pallas"].sum() == 2 * 4096


def test_run_firehose_end_to_end():
    out = io.StringIO()
    summary = run_firehose(
        num_metrics=64, batch=4096, seconds=0.6, interval=0.2,
        config=MetricConfig(bucket_limit=1024), out=out,
    )
    assert summary["total_samples"] > 0
    assert summary["intervals"] >= 1
    report = out.getvalue()
    assert "samples" in report
    assert "bytes serialized" in report


def test_run_firehose_mesh_mode():
    import io

    from loghisto_tpu.parallel.mesh import make_mesh

    out = io.StringIO()
    summary = run_firehose(
        num_metrics=64, batch=8192, seconds=0.5, interval=0.25,
        config=MetricConfig(bucket_limit=512),
        mesh=make_mesh(stream=4, metric=2), out=out,
    )
    assert summary["total_samples"] > 0
    assert "samples" in out.getvalue()


def test_mesh_firehose_step_conserves_counts():
    # every generated sample lands exactly once despite the redundant
    # per-metric-shard generation (same stream index -> same samples),
    # across multiple collective-free batches and the one-psum collect
    import jax
    import numpy as np

    from loghisto_tpu.firehose import make_mesh_firehose_interval_step
    from loghisto_tpu.parallel.mesh import make_mesh
    from loghisto_tpu.parallel import make_sharded_accumulator

    cfg = MetricConfig(bucket_limit=512)
    mesh = make_mesh(stream=4, metric=2)
    ingest, collect, make_partial = make_mesh_firehose_interval_step(
        mesh, 64, 8192, cfg
    )
    partial = make_partial()
    key = jax.random.key(7)
    partial, key = ingest(partial, key)
    partial, key = ingest(partial, key)
    acc = make_sharded_accumulator(mesh, 64, cfg.num_buckets)
    acc, partial = collect(acc, partial)
    assert int(np.asarray(acc).sum()) == 2 * 8192
    # returned partial is zeroed: a second interval starts clean
    partial, key = ingest(partial, key)
    acc, partial = collect(acc, partial)
    assert int(np.asarray(acc).sum()) == 3 * 8192


def test_firehose_int32_budget_closes_interval_early():
    """The int32-exactness guard: once an interval's dispatched samples
    reach the budget, the interval closes early (exact) instead of
    letting a hot cell wrap.  Budget shrunk so CI exercises the path."""
    import io

    out = io.StringIO()
    summary = run_firehose(
        num_metrics=16, batch=4096, seconds=1.2, interval=0.6,
        config=MetricConfig(bucket_limit=128), out=out,
        max_interval_samples=8192,
    )
    assert "int32 accumulator budget" in out.getvalue()
    # every reported interval stopped at (or under) the budget + 1 batch
    import re

    reports = re.findall(
        r"^interval \d+: ([\d,]+) samples", out.getvalue(), re.M
    )
    assert reports
    for count in reports:
        assert int(count.replace(",", "")) <= 8192 + 4096
    assert summary["intervals"] >= 1


def test_native_staging_aggregator_roundtrip():
    from loghisto_tpu import _native
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    if not _native.available():
        import pytest

        pytest.skip("native unavailable")
    agg = TPUAggregator(
        num_metrics=8, config=MetricConfig(bucket_limit=512),
        native_staging=True,
    )
    agg.registry.id_for("n")
    agg.record_batch(
        np.zeros(1000, dtype=np.int32),
        np.full(1000, 42.0, dtype=np.float32),
    )
    out = agg.collect().metrics
    assert out["n_count"] == 1000
