"""Distributed aggregation tests on the simulated 8-device CPU mesh —
the §5.8 communication backend the reference lacks (SURVEY.md §4: 'test
8-way mesh merges without a v5e-8')."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from loghisto_tpu.config import MetricConfig
from loghisto_tpu.ops.codec import compress_np
from loghisto_tpu.ops.stats import dense_stats
from loghisto_tpu.parallel.aggregator import (
    make_distributed_step,
    make_sharded_accumulator,
)
from loghisto_tpu.parallel.mesh import make_mesh

CFG = MetricConfig(bucket_limit=256)
PS = np.array([0.0, 0.5, 0.99, 1.0], dtype=np.float32)


def _single_device_reference(ids, values, m):
    acc = np.zeros((m, CFG.num_buckets), dtype=np.int32)
    buckets = np.clip(
        compress_np(values.astype(np.float32).astype(np.float64)),
        -CFG.bucket_limit, CFG.bucket_limit,
    )
    np.add.at(acc, (ids, buckets.astype(np.int64) + CFG.bucket_limit), 1)
    stats = dense_stats(jnp.asarray(acc), PS, CFG.bucket_limit)
    return acc, stats


@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2), (2, 4), (1, 8)])
def test_distributed_step_matches_single_device(mesh_shape):
    stream, metric = mesh_shape
    assert jax.device_count() >= 8, "conftest must provide 8 CPU devices"
    mesh = make_mesh(stream=stream, metric=metric)
    m, n = 16, 4096
    rng = np.random.default_rng(42)
    ids = rng.integers(0, m, n).astype(np.int32)
    values = rng.lognormal(3, 1, n).astype(np.float32)

    step = make_distributed_step(mesh, m, CFG.bucket_limit, PS)
    acc = make_sharded_accumulator(mesh, m, CFG.num_buckets)
    acc, stats = step(acc, jnp.asarray(ids), jnp.asarray(values))

    want_acc, want_stats = _single_device_reference(ids, values, m)
    np.testing.assert_array_equal(np.asarray(acc), want_acc)
    np.testing.assert_array_equal(
        np.asarray(stats["counts"]), np.asarray(want_stats["counts"])
    )
    np.testing.assert_allclose(
        np.asarray(stats["percentiles"]),
        np.asarray(want_stats["percentiles"]),
        rtol=1e-6,
    )


@pytest.mark.parametrize("path", ["sort"])
def test_distributed_step_dispatched_kernels_match_scatter(path):
    """The dispatched local-fold kernels are bit-identical to scatter
    inside shard_map — the mesh analog of the single-chip path parity
    (sort beats scatter on duplicate-heavy shards on TPU)."""
    mesh = make_mesh(stream=4, metric=2)
    m, n = 16, 4096
    rng = np.random.default_rng(7)
    # Zipf-ish duplicates: the regime the dispatched kernels exist for
    ids = (rng.zipf(1.5, n) % m).astype(np.int32)
    values = rng.lognormal(3, 1, n).astype(np.float32)

    base = make_distributed_step(
        mesh, m, CFG.bucket_limit, PS, ingest_path="scatter"
    )
    alt = make_distributed_step(
        mesh, m, CFG.bucket_limit, PS, ingest_path=path
    )
    acc0, _ = base(make_sharded_accumulator(mesh, m, CFG.num_buckets),
                   jnp.asarray(ids), jnp.asarray(values))
    acc1, _ = alt(make_sharded_accumulator(mesh, m, CFG.num_buckets),
                  jnp.asarray(ids), jnp.asarray(values))
    np.testing.assert_array_equal(np.asarray(acc0), np.asarray(acc1))


def test_mesh_firehose_dispatched_path_matches_scatter():
    from loghisto_tpu.firehose import make_mesh_firehose_interval_step

    mesh = make_mesh(stream=4, metric=2)
    cfg = MetricConfig(bucket_limit=128)
    accs = {}
    for path in ("scatter", "sort"):
        ingest, collect, make_partial = make_mesh_firehose_interval_step(
            mesh, 16, 1024, cfg, ingest_path=path
        )
        partial, _ = ingest(make_partial(), jax.random.key(5))
        acc = make_sharded_accumulator(mesh, 16, cfg.num_buckets)
        acc, _ = collect(acc, partial)
        accs[path] = np.asarray(acc)
    np.testing.assert_array_equal(accs["scatter"], accs["sort"])
    assert accs["scatter"].sum() == 1024


def test_distributed_step_accumulates_across_steps():
    mesh = make_mesh(stream=4, metric=2)
    m = 8
    step = make_distributed_step(mesh, m, CFG.bucket_limit, PS)
    acc = make_sharded_accumulator(mesh, m, CFG.num_buckets)
    ids = np.zeros(64, dtype=np.int32)
    values = np.full(64, 100.0, dtype=np.float32)
    acc, _ = step(acc, jnp.asarray(ids), jnp.asarray(values))
    acc, stats = step(acc, jnp.asarray(ids), jnp.asarray(values))
    assert int(np.asarray(stats["counts"])[0]) == 128


def test_distributed_step_requires_divisible_metrics():
    mesh = make_mesh(stream=2, metric=4)
    with pytest.raises(ValueError):
        make_distributed_step(mesh, 10, CFG.bucket_limit, PS)


def test_mesh_validation():
    with pytest.raises(ValueError):
        make_mesh(stream=7, metric=3)  # 21 > 8 devices
