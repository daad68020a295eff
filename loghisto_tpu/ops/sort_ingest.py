"""Sort-deduplicated ingest: an alternative device accumulation kernel
built for TPU scatter semantics.

The plain scatter path (ops/ingest.py) hands XLA a batch with many
DUPLICATE (metric, bucket) indices — a Zipf workload concentrates most of
a 4M-sample batch on a few hot cells, and duplicate-index scatter-adds
serialize on TPU.  This path restructures the batch so every scattered
index is unique:

  1. fuse compress -> combined cell key  (id * num_buckets + bucket)
  2. static-shape dedup via jnp.unique(size=N) — one XLA sort plus
     run-length counts, padding confined to the tail
  3. one scatter-add of (unique cell, count) pairs with
     unique_indices=True, indices_are_sorted=True — the conflict-free
     form XLA can fully vectorize (dropped tail entries park at distinct
     ascending out-of-bounds rows so both promises hold literally)

Bit-identical to the scatter path (tests/test_fast_paths.py);
ordering is irrelevant because bucket histograms are commutative.  The
combined key needs num_metrics * num_buckets < 2^31 - 2 (10k metrics x
8193 buckets ~= 8.2e7, three orders inside the bound; construction
validates it).

Selectable as TPUAggregator(ingest_path="sort"); "auto" prefers it at
high metric cardinality on TPU per the measured dispatch table
(ops/dispatch.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from loghisto_tpu.config import PRECISION
from loghisto_tpu.ops.ingest import bucket_indices


def sort_ingest_batch(
    acc: jnp.ndarray,
    ids: jnp.ndarray,
    values: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
) -> jnp.ndarray:
    """Pure function: accumulate one (ids, values) batch into acc via the
    sort-dedup formulation."""
    num_metrics, num_buckets = acc.shape
    n = ids.shape[0]
    # combined int32 cell keys; invalid ids (negative or >= num_metrics)
    # get the one-past-the-end key, the maximum, so they sort last
    bidx = bucket_indices(values, bucket_limit, precision)
    invalid_key = jnp.int32(num_metrics * num_buckets)
    valid = (ids >= 0) & (ids < num_metrics)
    key = jnp.where(valid, ids * num_buckets + bidx, invalid_key)
    # static-shape dedup: unique keys ascending, padding (fill =
    # invalid_key, the maximum) confined to the TAIL, counts 0 for pads
    ukeys, counts = jnp.unique(
        key, return_counts=True, size=n, fill_value=invalid_key
    )
    row = ukeys // num_buckets
    col = jnp.where(ukeys == invalid_key, 0, ukeys % num_buckets)
    # park every dropped entry at a DISTINCT ascending out-of-bounds row
    # (real rows stay below 2^30 because MAX_FLAT_CELLS bounds rows *
    # buckets), so both scatter promises hold literally: indices stay
    # sorted (the park rows exceed every real row and only occupy the
    # tail) and unique (each park row is distinct)
    park = jnp.int32(2**30) + jnp.arange(n, dtype=jnp.int32)
    row = jnp.where(ukeys == invalid_key, park, row)
    return acc.at[row, col].add(
        counts.astype(acc.dtype),
        mode="drop",
        unique_indices=True,
        indices_are_sorted=True,
    )


# one shy of the sort path's invalid_key sentinel, which must itself fit
MAX_FLAT_CELLS = 2**31 - 2


def validate_flat_cell_shape(num_metrics: int, num_buckets: int) -> None:
    """Raise if the sort path's combined int32 cell key (id * num_buckets
    + bucket) cannot represent this shape.  Called at
    CONSTRUCTION/selection — a raise inside the traced ingest would be
    swallowed by flush's shed-don't-block failure handling and look like
    a permanently down device instead of a config error."""
    if num_metrics * num_buckets >= MAX_FLAT_CELLS:
        raise ValueError(
            f"sort ingest needs num_metrics * num_buckets < 2^31 - 2 "
            f"for its combined int32 cell key; got "
            f"{num_metrics} x {num_buckets}"
        )


def make_sort_ingest_fn(bucket_limit: int, precision: int = PRECISION):
    """A jitted, donated-accumulator sort-dedup ingest step with the same
    f(acc, ids, values) -> new_acc contract as make_ingest_fn."""

    @functools.partial(jax.jit, donate_argnums=0)
    def sort_ingest(acc, ids, values):
        validate_flat_cell_shape(acc.shape[0], acc.shape[1])
        return sort_ingest_batch(acc, ids, values, bucket_limit, precision)

    return sort_ingest
