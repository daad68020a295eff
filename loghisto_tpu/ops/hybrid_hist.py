"""Hybrid hot-row histogram accumulation: MXU matmul for the hot head,
scatter for the cold tail.

The two regimes it splits (device rates not measured on this chip
yet):

  * one-hot matmul (ops/matmul_hist.py) sustains hundreds of
    M samples/s but its MAC cost grows linearly with the covered row
    count — infeasible across all 10k rows;
  * scatter-add handles any cardinality but serializes on TPU at
    ~9M updates/s at 10k metrics.

Skewed workloads (the reference's natural regime: a handful of hot
timers plus a long tail; BASELINE.json's Zipf-1.3 config) let us split
the batch: samples whose row id is below ``hot_rows`` go through the
MXU one-hot matmul (factorized [T, hot*H] x [T, 128] like the multirow
kernel), the rest through the scatter.  With Zipf(1.3) ids, the top 128
rows absorb ~85% of samples, so the serialized scatter sees only the
tail.

The row-id-order hotness assumption is real but natural: the registry
assigns ids in first-touch order (loghisto_tpu/registry.py), and hot
metrics are touched first in steady-state workloads.  The kernel is
bit-identical to the scatter path for ANY id distribution — hotness
only affects speed, never results.

Reference anchor: this accelerates the same hot path as
MetricSystem.Histogram (metrics.go:273-295) at high metric cardinality.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from loghisto_tpu.config import PRECISION
from loghisto_tpu.ops.ingest import bucket_indices, sanitize_ids

LANES = 128


def ingest_batch_hybrid(
    acc: jnp.ndarray,
    ids: jnp.ndarray,
    values: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
    hot_rows: int = 128,
    sample_tile: int = 2048,
) -> jnp.ndarray:
    """Accumulate one (ids, values) batch into acc[M, B]; bit-identical
    to ops.ingest.ingest_batch, faster when low ids are hot."""
    m, b = acc.shape
    hot = min(hot_rows, m)
    h = (b + LANES - 1) // LANES
    n = values.shape[0]
    if n >= 1 << 24:
        raise ValueError(
            f"batch of {n} >= 2^24 could silently saturate the float32 "
            "hot-head accumulation; split the batch"
        )
    idx = bucket_indices(values, bucket_limit, precision)
    ids = sanitize_ids(ids)
    is_hot = ids < hot

    # --- hot head: factorized one-hot matmul over [hot, H*128] ---
    # column = row * H + idx // 128; cold samples get an out-of-range
    # column, whose one-hot row is all zeros (jax.nn.one_hot semantics)
    col = jnp.where(is_hot, ids * h + idx // LANES, hot * h)
    lane = idx % LANES

    def tile_hist(carry, xs):
        col_t, lane_t = xs
        onehot_col = jax.nn.one_hot(col_t, hot * h, dtype=jnp.bfloat16)
        onehot_lane = jax.nn.one_hot(lane_t, LANES, dtype=jnp.bfloat16)
        partial = jax.lax.dot_general(
            onehot_col, onehot_lane,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return carry + partial, None

    tiles = max(1, n // sample_tile)
    pad = tiles * sample_tile - n
    if pad < 0:  # n not divisible: one extra padded tile
        tiles += 1
        pad = tiles * sample_tile - n
    if pad:
        # padded entries point at the zero one-hot column
        col_p = jnp.concatenate([col, jnp.full(pad, hot * h, col.dtype)])
        lane_p = jnp.concatenate([lane, jnp.zeros(pad, lane.dtype)])
    else:
        col_p, lane_p = col, lane
    # seed the scan carry FROM the inputs (int32 * 0 is exactly zero, and
    # col is never NaN): a constant jnp.zeros carry is "unvarying" under
    # shard_map's varying-manual-axes typing while the body output is
    # varying, which rejects the scan — this kernel must stay usable
    # inside the mesh local fold without knowing the axis names
    zero_carry = jnp.zeros((hot * h, LANES), dtype=jnp.float32) + (
        col_p[0] * 0
    ).astype(jnp.float32)
    hot_hist, _ = jax.lax.scan(
        tile_hist,
        zero_carry,
        (col_p.reshape(tiles, sample_tile),
         lane_p.reshape(tiles, sample_tile)),
    )
    hot_hist = hot_hist.reshape(hot, h * LANES)[:, :b].astype(jnp.int32)
    acc = acc.at[:hot, :].add(hot_hist)

    # --- cold tail: scatter with hot ids dropped ---
    cold_ids = jnp.where(is_hot, jnp.int32(2**30), ids)
    return acc.at[cold_ids, idx].add(1, mode="drop")


def make_hybrid_ingest_fn(
    bucket_limit: int,
    precision: int = PRECISION,
    hot_rows: int = 128,
):
    """Jitted, donated-accumulator hybrid ingest with the standard
    f(acc, ids, values) -> acc contract."""

    @functools.partial(jax.jit, donate_argnums=0)
    def ingest(acc, ids, values):
        return ingest_batch_hybrid(
            acc, ids, values, bucket_limit, precision, hot_rows
        )

    return ingest
