"""Device ingest kernels: fused compress -> scatter-add into the dense
bucket tensor.

This is the TPU replacement for the reference's hot path
(MetricSystem.Histogram, metrics.go:273-295): where Go takes a RWMutex and
does a per-sample atomic add into a sparse map, here a whole batch of
``(metric_id, value)`` samples is compressed vectorized and scatter-added
into an ``int32[num_metrics, num_buckets]`` accumulator in one fused XLA
program.  Ordering never matters — log-bucket histograms are commutative —
which is exactly what makes the batch/device design legal.

The accumulator is donated, so steady-state ingest does not allocate.
Out-of-range metric ids are dropped (mode="drop"), mirroring how the
sparse tier simply cannot reference an unregistered name.

RETIRED as the TPU high-cardinality default (r13): this composition is
two device stages — compress materializes the bucket-index array in
HBM, then the scatter consumes it — and ``ops/fused_ingest.py`` now
does both in one Pallas dispatch with the codec on the VPU.  "auto"
prefers the fused kernel wherever ``fused_ingest_incapability`` allows
(ops/dispatch.py); what remains here is (a) the universal fallback for
CPU/GPU, small batches, and mesh-embedded folds, and (b) the semantic
oracle: ``fused_ingest_reference`` IS ``ingest_batch``, and the fused
kernel must match it bit-for-bit (tests/test_fused_ingest.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from loghisto_tpu.config import PRECISION
from loghisto_tpu.ops.codec import compress


def bucket_indices(
    values: jnp.ndarray, bucket_limit: int, precision: int = PRECISION
) -> jnp.ndarray:
    """values -> clipped dense bucket-axis indices in [0, 2*bucket_limit].
    (NaN pinning to bucket 0 happens inside compress.)"""
    buckets = compress(values, precision)
    return jnp.clip(buckets, -bucket_limit, bucket_limit) + bucket_limit


def sanitize_ids(ids: jnp.ndarray) -> jnp.ndarray:
    """Map negative metric ids to a large out-of-range value so that
    scatter mode="drop" actually drops them — JAX wraps negative indices
    (numpy semantics) *before* the bounds check, so a raw -1 would land in
    the last row instead of being dropped."""
    return jnp.where(ids < 0, jnp.int32(2**30), ids)


def ingest_batch(
    acc: jnp.ndarray,
    ids: jnp.ndarray,
    values: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
) -> jnp.ndarray:
    """Pure function: accumulate one (ids, values) batch into acc."""
    idx = bucket_indices(values, bucket_limit, precision)
    return acc.at[sanitize_ids(ids), idx].add(1, mode="drop")


def make_ingest_fn(bucket_limit: int, precision: int = PRECISION):
    """A jitted, donated-accumulator ingest step.

    Returns f(acc, ids, values) -> new_acc where acc is int32 [M, B],
    ids int32 [N], values float32 [N].  Donation makes steady-state
    ingestion allocation-free on device.
    """

    @functools.partial(jax.jit, donate_argnums=0)
    def scatter_ingest(acc, ids, values):
        return ingest_batch(acc, ids, values, bucket_limit, precision)

    return scatter_ingest


def make_weighted_ingest_fn(bucket_limit: int):
    """Like make_ingest_fn but takes pre-computed *codec* bucket indices
    plus integer weights — used when merging pre-bucketed host-tier
    histograms into the device accumulator (weight = bucket count).
    Bucket indices are clipped to the dense range inside the kernel."""

    @functools.partial(jax.jit, donate_argnums=0)
    def weighted_ingest(acc, ids, buckets, weights):
        idx = jnp.clip(buckets, -bucket_limit, bucket_limit) + bucket_limit
        return acc.at[sanitize_ids(ids), idx].add(weights, mode="drop")

    return weighted_ingest


def make_packed_ingest_fn(bucket_limit: int):
    """Weighted cell merge from ONE int32 [n, 3] array of
    (id, codec_bucket, count) columns — the cell store's packed drain
    (ingest.cpp lh_cells_drain_packed) converted host-side by
    aggregator._merge_packed_locked.  One host->device transfer per
    merge chunk instead of three parallel arrays.  int32 END TO END on
    purpose: this repo never enables jax_enable_x64, so an int64 wire
    array would be silently canonicalized to int32 — with the earlier
    (id << 16) key format that truncation corrupted every metric id
    >= 2^15 (registry growth takes the default 10k config to 80k rows).
    Padding rows use id -1, which sanitize_ids drops like every other
    kernel; callers route counts >= 2^30 to the exact host spill first,
    so the int32 count column cannot overflow."""

    @functools.partial(jax.jit, donate_argnums=0)
    def packed_ingest(acc, packed):
        # Trace-time contract check: shapes are static under jit, and a
        # 2-column array would NOT fail the [:, 2] read below (static
        # OOB gathers clamp) — it would silently misread columns.
        if packed.ndim != 2 or packed.shape[1] != 3:
            raise ValueError(
                f"packed must be [n, 3] (id, bucket, count); "
                f"got {packed.shape}"
            )
        ids = packed[:, 0]
        idx = jnp.clip(packed[:, 1], -bucket_limit, bucket_limit) + bucket_limit
        return acc.at[sanitize_ids(ids), idx].add(packed[:, 2], mode="drop")

    return packed_ingest


@functools.partial(jax.jit, donate_argnums=0)
def merge_accumulators(acc: jnp.ndarray, other: jnp.ndarray) -> jnp.ndarray:
    """Elementwise histogram merge — the fundamental mergeability property
    the whole distributed design rides on."""
    return acc + other
