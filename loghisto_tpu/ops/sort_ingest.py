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

Bit-identical to the scatter/matmul paths (tests/test_fast_paths.py);
ordering is irrelevant because bucket histograms are commutative.  The
combined key needs num_metrics * num_buckets < 2^31 - 2 (10k metrics x
8193 buckets ~= 8.2e7, three orders inside the bound; construction
validates it).

Selectable as TPUAggregator(ingest_path="sort"); "auto" prefers it at
high metric cardinality on TPU per the measured dispatch table
(ops/dispatch.py).  sortscan_ingest_batch below is a leaner second
formulation of the same idea (one sort + one scan + one conflict-free
scatter instead of jnp.unique's generic bookkeeping), selectable as
ingest_path="sortscan" and measured side by side in
benchmarks/device_paths.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from loghisto_tpu.config import PRECISION
from loghisto_tpu.ops.ingest import bucket_indices


def _cell_keys(acc, ids, values, bucket_limit, precision):
    """Combined int32 cell keys (id * num_buckets + bucket) for one
    batch, shared by both dedup formulations.  Invalid ids (negative or
    >= num_metrics) get the one-past-the-end key — the maximum, so they
    sort last and scatter-drop.  Returns (key, invalid_key)."""
    num_metrics, num_buckets = acc.shape
    bidx = bucket_indices(values, bucket_limit, precision)
    invalid_key = jnp.int32(num_metrics * num_buckets)
    valid = (ids >= 0) & (ids < num_metrics)
    return jnp.where(valid, ids * num_buckets + bidx, invalid_key), invalid_key


def _park_rows(n: int) -> jnp.ndarray:
    """Distinct out-of-bounds rows for dropped scatter entries (real rows
    stay below 2^30 because MAX_FLAT_CELLS bounds rows * buckets)."""
    return jnp.int32(2**30) + jnp.arange(n, dtype=jnp.int32)


def sort_ingest_batch(
    acc: jnp.ndarray,
    ids: jnp.ndarray,
    values: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
) -> jnp.ndarray:
    """Pure function: accumulate one (ids, values) batch into acc via the
    sort-dedup formulation."""
    num_buckets = acc.shape[1]
    n = ids.shape[0]
    key, invalid_key = _cell_keys(acc, ids, values, bucket_limit, precision)
    # static-shape dedup: unique keys ascending, padding (fill =
    # invalid_key, the maximum) confined to the TAIL, counts 0 for pads
    ukeys, counts = jnp.unique(
        key, return_counts=True, size=n, fill_value=invalid_key
    )
    row = ukeys // num_buckets
    col = jnp.where(ukeys == invalid_key, 0, ukeys % num_buckets)
    # park every dropped entry at a DISTINCT ascending out-of-bounds row,
    # so both scatter promises hold literally: indices stay sorted (the
    # park rows exceed every real row and only occupy the tail) and
    # unique (each park row is distinct)
    row = jnp.where(ukeys == invalid_key, _park_rows(n), row)
    return acc.at[row, col].add(
        counts.astype(acc.dtype),
        mode="drop",
        unique_indices=True,
        indices_are_sorted=True,
    )


# one shy of the sort path's invalid_key sentinel, which must itself fit
MAX_FLAT_CELLS = 2**31 - 2


def validate_flat_cell_shape(
    num_metrics: int, num_buckets: int, path: str = "sort"
) -> None:
    """Raise if a combined int32 cell key (id * num_buckets + bucket)
    cannot represent this shape — shared bound for every kernel that
    flattens (row, bucket) into one int32 (sort's dedup key, matmul's
    flat cell index).  Called at CONSTRUCTION/selection — a raise inside
    the traced ingest would be swallowed by flush's shed-don't-block
    failure handling and look like a permanently down device instead of
    a config error."""
    if num_metrics * num_buckets >= MAX_FLAT_CELLS:
        raise ValueError(
            f"{path} ingest needs num_metrics * num_buckets < 2^31 - 2 "
            f"for its combined int32 cell key; got "
            f"{num_metrics} x {num_buckets}"
        )




def sortscan_ingest_batch(
    acc: jnp.ndarray,
    ids: jnp.ndarray,
    values: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
) -> jnp.ndarray:
    """Leaner sort-dedup: one sort + one associative scan + one
    conflict-free scatter.

    ``jnp.unique(size=n, return_counts=True)`` (the "sort" path) lowers
    to one sort plus ~40 scatter and ~16 gather ops for its general
    inverse/index bookkeeping, none of which this kernel needs: after
    sorting the combined cell keys, segment STARTS are adjacent-diff
    flags, and each start's count is the distance to the next start —
    computable with a single reverse min-scan over start positions.  The
    scatter then writes (row, col, count) at the starts only; non-starts
    and invalid keys park at distinct out-of-bounds rows, so
    unique_indices holds literally.  Unlike sort_ingest_batch the park
    rows interleave with real rows (starts sit wherever the sorted keys
    put them), so indices_are_sorted must NOT be promised here — the
    conflict-free guarantee is the one that unlocks vectorization.
    Bit-identical to every other ingest kernel."""
    num_buckets = acc.shape[1]
    n = ids.shape[0]
    key, invalid_key = _cell_keys(acc, ids, values, bucket_limit, precision)

    sk = jnp.sort(key)  # invalid keys are the maximum: they sort last
    idx = jnp.arange(n, dtype=jnp.int32)
    flags = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sk[1:] != sk[:-1]]
    )
    # next segment start strictly after i: reverse running-min of start
    # positions, shifted left by one (position n = "no further start")
    starts = jnp.where(flags, idx, jnp.int32(n))
    nxt = jax.lax.associative_scan(jnp.minimum, starts, reverse=True)
    nxt_after = jnp.concatenate([nxt[1:], jnp.full((1,), n, jnp.int32)])
    live = flags & (sk != invalid_key)
    cnt = jnp.where(live, nxt_after - idx, 0)
    row = jnp.where(live, sk // num_buckets, _park_rows(n))
    col = jnp.where(live, sk % num_buckets, 0)
    return acc.at[row, col].add(
        cnt.astype(acc.dtype),
        mode="drop",
        unique_indices=True,
    )


def make_sortscan_ingest_fn(bucket_limit: int, precision: int = PRECISION):
    """Jitted, donated-accumulator sortscan ingest with the standard
    f(acc, ids, values) -> new_acc contract."""

    @functools.partial(jax.jit, donate_argnums=0)
    def sortscan_ingest(acc, ids, values):
        validate_flat_cell_shape(acc.shape[0], acc.shape[1], "sortscan")
        return sortscan_ingest_batch(
            acc, ids, values, bucket_limit, precision
        )

    return sortscan_ingest


def make_sort_ingest_fn(bucket_limit: int, precision: int = PRECISION):
    """A jitted, donated-accumulator sort-dedup ingest step with the same
    f(acc, ids, values) -> new_acc contract as make_ingest_fn."""

    @functools.partial(jax.jit, donate_argnums=0)
    def sort_ingest(acc, ids, values):
        validate_flat_cell_shape(acc.shape[0], acc.shape[1], "sort")
        return sort_ingest_batch(acc, ids, values, bucket_limit, precision)

    return sort_ingest
