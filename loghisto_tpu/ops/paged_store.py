"""Paged bucket storage: device programs whose cost tracks *occupied*
pages, not dense capacity.

The dense accumulator spends ``M x B x 4`` bytes of HBM (and commit
H2D bytes proportional to the rows it touches) regardless of how many
buckets a metric ever populated — INTERVAL_COMMIT_r6 shows H2D
dominating the 10k-metric commit, and at the ROADMAP's 1M-live-row
target the dense tensor alone (1M x 8193 x 4 ~= 32.8 GB) exceeds a
chip's HBM outright.  Real latency/size distributions are SPARSE in
bucket space: a metric that only ever sees 1-50ms latencies occupies a
few hundred adjacent log buckets out of 8193.

The paged layout replaces the dense ``[M, B]`` tensor with

  * a **page pool** ``[P, page_size]`` int32 — fixed-size bucket pages,
    allocated on demand, slot 0 reserved as the permanently-zero page so
    unmapped reads decode to zeros without a mask gather;
  * a host-side **page table** ``[M, pages_per_row]`` int32 mapping each
    (row, page-of-storage-axis) to a pool slot, -1 = unmapped.

The committed wire stays the packed sparse-triple format (PR 6); the
host translate step (loghisto_tpu/paging.py) rewrites each
``(row, codec_bucket, count)`` cell into ``(slot, offset, count)``
against the page table — allocation and spill policy are host decisions
(the host already folds every batch to triples, so it sees exactly
which cells an interval touches) — and the device program here is a
pure weighted scatter into the pool: O(occupied cells) H2D, O(mapped
pages) HBM, no codec work, no dense row materialization.

Two commit tiers, bit-identical by construction (the Pallas tier reuses
the sparse-ingest per-cell (8, 128)-tile DMA round-trip with pool pages
as the rows — lane-aligned at the default 256, so no cell needs the
ragged-edge XLA scatter the 8193-wide dense rows do):

  * "jnp"    — XLA weighted scatter-add over the flat pool view;
  * "pallas" — per-cell DMA tile round-trip through a VMEM scratch
    (ops/sparse_ingest.py's kernel, parameterized by the pool shape).

Query serving gathers only a row's mapped pages and expands them
through the row's codec decode-LUT back onto the dense native bucket
axis — ``make_paged_query_fn`` then runs the exact
``snapshot_row_stats`` program of the dense snapshot engine, so a paged
query is bit-identical to a dense query over the same histogram for
identity-codec rows (tests/test_paged_store.py pins it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# Buckets per page.  256 int32 = 1 KiB per page, two full TPU vector
# lanes rows — page DMAs are lane-aligned, and at B=8193 a dense row is
# 33 pages, so one hot latency band (a few hundred buckets) costs 1-3
# pages instead of a 32 KiB dense row.  Mirrored (without importing
# jax) as ops/dispatch.PAGE_SIZE for the thresholds machinery.
PAGE_SIZE = 256

# Reserved pool slot: permanently zero, never allocated, never written.
# Page-table entries of -1 clamp onto it at gather time, so reading an
# unmapped page needs no mask pass — the zero page IS the mask.
ZERO_SLOT = 0

# Fixed commit-launch width: every paged commit pads its translated
# triples to a multiple of this, so ONE compiled executable serves
# every interval (the _MERGE_CHUNK idea from the dense bridge merge).
COMMIT_CHUNK = 1 << 14


def validate_pool_shape(pool_pages: int, page_size: int) -> None:
    """Construction-time guard: the flat pool index (slot * page_size +
    offset) must stay inside int32, and pages must keep the TPU lane
    alignment that makes the Pallas page DMA legal."""
    if page_size < 128 or page_size % 128:
        raise ValueError(
            f"page_size must be a positive multiple of 128 (TPU lane "
            f"alignment); got {page_size}"
        )
    if pool_pages < 2:
        raise ValueError(
            f"pool needs >= 2 pages (slot 0 is the reserved zero page); "
            f"got {pool_pages}"
        )
    if pool_pages * page_size >= 2**31 - 2:
        raise ValueError(
            f"pool of {pool_pages} x {page_size} buckets overflows the "
            "flat int32 cell index; shrink the pool or the page"
        )


def paged_scatter_batch(pool: jnp.ndarray, packed: jnp.ndarray) -> jnp.ndarray:
    """Pure jnp tier: weighted scatter-add of translated ``(slot,
    offset, count)`` triples into the page pool.  Padding rows use slot
    -1 and drop; slot 0 (the zero page) is refused by the translate
    step, never here (a traced guard would silently clamp)."""
    if packed.ndim != 2 or packed.shape[1] != 3:
        raise ValueError(
            f"packed must be [n, 3] (slot, offset, count); got {packed.shape}"
        )
    pages, page_size = pool.shape
    slots = packed[:, 0]
    offs = jnp.clip(packed[:, 1], 0, page_size - 1)
    valid = (slots > ZERO_SLOT) & (slots < pages)
    # invalid rows park past the largest flat index validate_pool_shape
    # admits (pool cells < 2^31 - 2); mode="drop" discards them
    flat_idx = jnp.where(valid, slots * page_size + offs, jnp.int32(2**31 - 2))
    flat = pool.reshape(-1).at[flat_idx].add(packed[:, 2], mode="drop")
    return flat.reshape(pages, page_size)


def pallas_paged_scatter(
    pool: jnp.ndarray,
    packed: jnp.ndarray,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pallas tier: same contract as paged_scatter_batch, executed as
    the sparse-ingest per-cell tile DMA round-trip with pool pages as
    the rows (serial grid => exact integer accumulation for duplicate
    cells)."""
    from loghisto_tpu.ops.sparse_ingest import pallas_cell_scatter

    if packed.ndim != 2 or packed.shape[1] != 3:
        raise ValueError(
            f"packed must be [n, 3] (slot, offset, count); got {packed.shape}"
        )
    page_size = pool.shape[1]
    slots = packed[:, 0]
    # the kernel bounds-guards rows to [0, pages); shift the zero page
    # out of range too so nothing can ever write it
    slots = jnp.where(slots <= ZERO_SLOT, jnp.int32(-1), slots)
    offs = jnp.clip(packed[:, 1], 0, page_size - 1)
    return pallas_cell_scatter(
        pool, slots, offs, packed[:, 2], interpret=interpret
    )


def make_paged_commit_fn(kernel: str = "jnp"):
    """Jitted, donated-pool commit step ``f(pool, packed) -> pool`` with
    pool int32 [P, page_size] and packed int32 [n, 3] translated
    triples.  One executable per (pool shape, padded triple length) —
    the host side pads to COMMIT_CHUNK multiples so the set of lengths
    stays tiny."""
    step = pallas_paged_scatter if kernel == "pallas" else paged_scatter_batch

    @functools.partial(jax.jit, donate_argnums=0)
    def commit(pool, packed):
        return step(pool, packed)

    return commit


def make_sharded_paged_commit_fn(mesh, shard_pages: int):
    """Mesh tier of the paged commit: ``f(pool, packed) -> pool`` with
    pool int32 [n_metric * shard_pages, page_size] laid out as one
    contiguous page arena per metric shard (shard k owns global slots
    [k*shard_pages, (k+1)*shard_pages), slot k*shard_pages being that
    shard's local zero page), and packed [n, 3] GLOBAL-slot triples
    split over the stream axis.

    Inside one shard_map each device keeps only the triples whose slot
    falls in its metric shard's arena (re-based to local slots — the
    local zero page and every foreign slot drop), scatters them into a
    zero local delta, and ONE psum over the stream axis merges the
    deltas.  Every triple is owned by exactly one metric shard and
    int32 adds commute, so the result is bit-identical to the
    single-device ``make_paged_commit_fn`` over the same pool — the
    PR-8 sharded-commit recipe applied to pages instead of rows.  The
    scatter body is the jnp tier (shard_map-local XLA scatter); the
    Pallas per-cell DMA tier stays single-device, matching
    resolve_compact_path's policy.

    Host-side contract: the padded triple count must divide by the
    stream axis size (COMMIT_CHUNK is a power of two, so any pow-2
    stream axis works; paging.py guards this at construction).
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from loghisto_tpu.parallel.mesh import METRIC_AXIS, STREAM_AXIS

    def _local(pool_local, packed):
        shard = jax.lax.axis_index(METRIC_AXIS)
        local = packed[:, 0] - shard * shard_pages
        own = (local > ZERO_SLOT) & (local < shard_pages)
        lp = jnp.stack(
            [jnp.where(own, local, jnp.int32(-1)), packed[:, 1], packed[:, 2]],
            axis=1,
        )
        delta = paged_scatter_batch(jnp.zeros_like(pool_local), lp)
        delta = jax.lax.psum(delta, STREAM_AXIS)
        return pool_local + delta

    sharded = shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(METRIC_AXIS, None), P(STREAM_AXIS, None)),
        out_specs=P(METRIC_AXIS, None),
    )

    @functools.partial(jax.jit, donate_argnums=0)
    def commit(pool, packed):
        return sharded(pool, packed)

    return commit


def gather_storage_rows(
    pool: jnp.ndarray, table_rows: jnp.ndarray, storage_buckets: int
) -> jnp.ndarray:
    """Reassemble dense STORAGE-axis rows from mapped pages: table_rows
    int32 [n, pages_per_row] (pool slots, -1 unmapped) -> int32
    [n, storage_buckets].  Unmapped entries clamp onto the reserved
    zero page, so no mask pass is needed — D2H and FLOP cost is
    O(n * pages_per_row * page_size), independent of M."""
    pages = pool[jnp.maximum(table_rows, ZERO_SLOT)]  # [n, ppr, page]
    n, ppr, page = pages.shape
    return pages.reshape(n, ppr * page)[:, :storage_buckets]


@functools.lru_cache(maxsize=None)
def make_paged_query_fn(bucket_limit: int, precision: int):
    """Jitted paged snapshot query ``f(pool, table_rows, dec_lut, ps) ->
    stats``: gather the requested rows' mapped pages, expand each
    storage bucket onto its representative native bucket through the
    codec decode-LUT (a scatter-add — decode LUTs are injective, so
    this is exact), and run the SAME snapshot_row_stats program as the
    dense query engine.  dec_lut is a traced int32 [S] operand, so all
    rows of one codec share one executable and neither the table values
    nor the LUT retrace."""
    from loghisto_tpu.ops.stats import snapshot_row_stats

    num_buckets = 2 * bucket_limit + 1

    @jax.jit
    def query(pool, table_rows, dec_lut, ps):
        storage = gather_storage_rows(pool, table_rows, dec_lut.shape[0])
        n = storage.shape[0]
        native = jnp.zeros((n, num_buckets), dtype=jnp.int32)
        native = native.at[:, dec_lut].add(storage)
        cdf = jnp.cumsum(native, axis=1, dtype=jnp.int32)
        counts = cdf[:, -1]
        from loghisto_tpu.ops.stats import (
            bucket_representatives, weighted_sums,
        )

        reps = bucket_representatives(bucket_limit, precision)
        sums = weighted_sums(native.astype(jnp.float32), reps)
        return snapshot_row_stats(
            cdf, counts, sums, ps, bucket_limit, precision
        )

    return query
