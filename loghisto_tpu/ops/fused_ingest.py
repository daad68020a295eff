"""Fused sample->scatter Pallas TPU ingest: raw values + metric ids to
dense [M, B] int32 accumulator in ONE device dispatch.

The other multi-metric paths split the work in two: a compress stage
that materializes a bucket-index array in HBM, then a scatter stage
that consumes it.  The circllhist observation
(PAPERS.md) is that log-linear bucket selection is pure bit/exponent
arithmetic — VPU work that belongs in the SAME kernel as the
accumulate, like SNIPPETS.md [2]'s histogram kernel which avoids
``searchsorted`` for exactly this reason.  This module fuses the whole
pipeline:

  1. (XLA preprocess, all static shapes, fused into the same jitted
     program) group samples by *metric row block* (rows_tile consecutive
     rows) with one sort, and lay the RAW values out so every
     SAMPLE_TILE-sized tile holds samples of exactly one block (a
     metric-tiled layout: each tile routes to one accumulator block),
     and no bucket index is ever computed here: the layout carries
     float32 values, not bucket ids.
  2. (Pallas kernel) grid over sample tiles routed by a
     scalar-prefetched ``tile_block`` map.  Each tile compresses its
     values on the VPU (``bucket_indices`` — the same codec function as
     the scatter path, so the contract can never diverge), forms the
     one-hots in VMEM, and accumulates a [rows_tile*H, 128] matmul on
     the MXU straight into the aliased accumulator block.

Compared to a metric-tiled kernel over precomputed bucket indices this
(a) moves the codec on-chip — the bucket-index array never exists in
HBM — and (b) needs no lane-padded accumulator layout: the acc block is
(rows_tile, B) with B equal to the array's own minor dim, which Mosaic
accepts (a block dim may equal the array dim instead of being
8/128-divisible), so the kernel aliases the product's [M, B]
accumulator directly and plugs into the uniform
``f(acc, ids, values, bucket_limit, precision)`` dispatch contract.

Exactness contract (same as every other path): per-tile f32 one-hot
accumulation is bounded by SAMPLE_TILE < 2^24 before the int32 cast;
cross-tile accumulation is integer; per-cell overflow at 2^31 is the
caller's spill bound.  Invalid ids (< 0 or >= M) take the filler row,
which the one-hot drops — bit-identical to sanitize_ids + mode="drop".

The jnp fallback for CPU/GPU is ``ops.ingest.ingest_batch`` itself —
re-exported as ``fused_ingest_reference`` — because that scatter
composition IS the semantics the kernel must reproduce bit-for-bit
(tests/test_fused_ingest.py pins the parity across denormals, negative
values, inf/NaN sanitization, row-boundary ids, and empty batches).

Direct-to-paged fusion (r17)
----------------------------

``fused_paged_ingest_batch`` extends the fusion all the way into the
paged backend (ops/paged_store.py): through r16, paged mode paid a
host fold (raw batch -> packed triples) plus a host page-table
translate before its pool commit dispatch — and combining the r13
kernel with paged storage would have materialized the dense [M, B]
accumulator only to re-encode and recommit it.  Here the whole
pipeline runs in ONE donated jitted program per batch:

  1. (XLA preprocess, same program) compress every raw value with the
     shared ``bucket_indices`` codec, gather the row's codec *encode*
     LUT (``enc_luts[row_codec[row], dense]`` — the circllhist
     log-linear / polytail layouts reduced to LUTs by
     loghisto_tpu/paging.py), gather the device page-table mirror to a
     flat pool cell (slot * page_size + offset), and fold duplicate
     cells with one sort + segment-sum — all static [N] shapes, no
     [M, B] tensor ever exists.  Invalid ids (and cells whose page the
     host declined) park on the sentinel flat index, sort to the end,
     and become the dropped filler cell; the reserved slot-0 zero page
     stays the unmapped-read mask and is never written.
  2. (Pallas kernel — the ONE pallas_call of the program) the folded
     (slot, offset, count) cells take the sparse-ingest per-cell DMA
     scatter with pool pages as the rows (``pallas_paged_scatter``):
     serial grid, int32 adds — exact cross-tile accumulation by
     construction.

The host half (PagedStore.prepare_batch) stays off the dispatch path:
codec assignment and page allocation for everything a batch touches
happen in one vectorized pass on the transfer worker BEFORE the upload,
so the page table never blocks the dispatch.  Bit-identity oracle: jnp
encode + ``paged_scatter_batch`` over per-sample triples
(tests/test_fused_paged.py pins it across all three codecs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from loghisto_tpu.config import PRECISION
from loghisto_tpu.ops.backend import default_interpret
from loghisto_tpu.ops.ingest import bucket_indices
from loghisto_tpu.ops.ingest import ingest_batch as fused_ingest_reference  # noqa: F401
from loghisto_tpu.ops.paged_store import (
    ZERO_SLOT,
    paged_scatter_batch,
    pallas_paged_scatter,
)
from loghisto_tpu.ops.pallas_kernels import LANES, SAMPLE_TILE

# Metric rows per accumulator block resident in VMEM.  8 matches the
# sublane tile, keeps the one-hot column space
# rows_tile*H narrow enough for VMEM at 8k buckets, and is what
# TPUAggregator._grow_row_unit preserves under registry growth.
ROWS_TILE = 8


def preprocess_values(
    ids: jnp.ndarray,
    values: jnp.ndarray,
    num_metrics: int,
    rows_tile: int = ROWS_TILE,
    sample_tile: int = SAMPLE_TILE,
):
    """Sort and block-pad one RAW batch (no bucketing happens here).

    Returns (layout_rows [G*T] int32, layout_vals [G*T] float32,
    tile_block [G] int32) with G = ceil(N/T) + n_blocks (static): every
    tile's samples belong to one metric block, filler entries carry
    row == rows_tile (dropped by the kernel's one-hot) and value 0.0.
    The searchsorted below routes TILES to blocks (an O(G) map over
    static shapes) — per-sample bucket selection stays on the VPU
    inside the kernel.
    """
    n = ids.shape[0]
    t = sample_tile
    n_blocks = num_metrics // rows_tile
    g = (n + t - 1) // t + n_blocks

    values = values.astype(jnp.float32)
    valid = (ids >= 0) & (ids < num_metrics)
    block = jnp.where(valid, ids // rows_tile, n_blocks - 1)
    row_in_block = jnp.where(
        valid, ids - block * rows_tile, rows_tile  # filler drops in one-hot
    )

    order = jnp.argsort(block)
    sorted_block = block[order]
    sorted_row = row_in_block[order]
    sorted_vals = values[order]

    counts = jnp.bincount(sorted_block, length=n_blocks)
    tiles_per_block = (counts + t - 1) // t
    start_tile = jnp.concatenate(
        [jnp.zeros(1, dtype=tiles_per_block.dtype),
         jnp.cumsum(tiles_per_block)[:-1]]
    )
    padded_start = start_tile * t
    sample_start = jnp.concatenate(
        [jnp.zeros(1, dtype=counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    rank = jnp.arange(n) - sample_start[sorted_block]
    dest = padded_start[sorted_block] + rank

    layout_rows = jnp.full(g * t, rows_tile, dtype=jnp.int32)
    layout_vals = jnp.zeros(g * t, dtype=jnp.float32)
    layout_rows = layout_rows.at[dest].set(sorted_row.astype(jnp.int32))
    layout_vals = layout_vals.at[dest].set(sorted_vals)

    tile_ids = jnp.arange(g)
    tile_block = (
        jnp.searchsorted(start_tile, tile_ids, side="right") - 1
    ).astype(jnp.int32)
    tile_block = jnp.clip(tile_block, 0, n_blocks - 1)
    return layout_rows, layout_vals, tile_block


def _kernel(tile_block_ref, rows_ref, vals_ref, acc_in_ref, acc_out_ref, *,
            rows_tile: int, h: int, num_buckets: int, bucket_limit: int,
            precision: int):
    i = pl.program_id(0)
    rows = rows_ref[0, :]
    v = vals_ref[0, :]
    # the fused step: codec on the VPU, inside the kernel — shared with
    # the scatter path so sign mirroring, NaN->bucket 0, and saturation
    # can never diverge (filler values are 0.0; their row drops them)
    bucket = bucket_indices(v, bucket_limit, precision)
    hi = bucket // LANES
    lo = bucket % LANES
    col = rows * h + hi  # filler rows land at >= rows_tile*h -> one-hot 0
    onehot_col = jax.nn.one_hot(col, rows_tile * h, dtype=jnp.bfloat16)
    onehot_lo = jax.nn.one_hot(lo, LANES, dtype=jnp.bfloat16)
    partial = jax.lax.dot_general(
        onehot_col, onehot_lo,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(rows_tile, h * LANES).astype(jnp.int32)[:, :num_buckets]

    # Consecutive tiles of one block keep the output block resident; the
    # aliased INPUT block may be re-fetched stale on revisits, so it is
    # only read on the block's first tile.
    first_visit = jnp.logical_or(
        i == 0, tile_block_ref[i] != tile_block_ref[jnp.maximum(i - 1, 0)]
    )

    @pl.when(first_visit)
    def _init():
        acc_out_ref[:] = acc_in_ref[:] + partial

    @pl.when(jnp.logical_not(first_visit))
    def _accumulate():
        acc_out_ref[:] = acc_out_ref[:] + partial


def fused_ingest_batch(
    acc: jnp.ndarray,
    ids: jnp.ndarray,
    values: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Uniform-contract fused step: acc int32 [M, B] (B = 2*bl+1,
    M % ROWS_TILE == 0), f(acc, ids, values) -> acc, ONE pallas_call and
    zero scatter dispatches (tests pin the jaxpr).  f64 values are cast
    to f32 at entry — the same canonicalization every other path gets.
    """
    if interpret is None:
        interpret = default_interpret()
    if acc.ndim != 2:
        raise ValueError(f"acc must be [M, B]; got shape {tuple(acc.shape)}")
    num_metrics, num_buckets = acc.shape
    if num_buckets != 2 * bucket_limit + 1:
        raise ValueError(
            f"acc has {num_buckets} buckets but bucket_limit={bucket_limit} "
            f"implies {2 * bucket_limit + 1}"
        )
    if num_metrics % ROWS_TILE:
        raise ValueError(
            f"fused ingest needs num_metrics % {ROWS_TILE} == 0; got "
            f"{num_metrics} (dispatch declines this shape before tracing)"
        )
    h = (num_buckets + LANES - 1) // LANES

    rows, vals, tile_block = preprocess_values(
        ids, values, num_metrics, ROWS_TILE
    )
    g = tile_block.shape[0]
    kernel = functools.partial(
        _kernel, rows_tile=ROWS_TILE, h=h, num_buckets=num_buckets,
        bucket_limit=bucket_limit, precision=precision,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g,),
        in_specs=[
            # lane-axis grid over [1, G*T] layouts (Mosaic rejects
            # block [1, T] on a [G, T] array)
            pl.BlockSpec((1, SAMPLE_TILE), lambda i, tb: (0, i)),
            pl.BlockSpec((1, SAMPLE_TILE), lambda i, tb: (0, i)),
            # acc block minor dim == array minor dim: legal without lane
            # padding, so the product accumulator aliases directly
            pl.BlockSpec((ROWS_TILE, num_buckets), lambda i, tb: (tb[i], 0)),
        ],
        out_specs=pl.BlockSpec(
            (ROWS_TILE, num_buckets), lambda i, tb: (tb[i], 0)
        ),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_metrics, num_buckets), jnp.int32),
        # flattened input index incl. the scalar-prefetch operand:
        # 0=tile_block, 1=rows, 2=vals, 3=acc
        input_output_aliases={3: 0},
        interpret=interpret,
    )(
        tile_block,
        rows.reshape(1, g * SAMPLE_TILE),
        vals.reshape(1, g * SAMPLE_TILE),
        acc,
    )


def make_fused_ingest_fn(
    bucket_limit: int,
    precision: int = PRECISION,
    interpret: bool | None = None,
):
    """Jitted, donated-accumulator fused step:
    f(acc [M, B], ids [N], values [N]) -> acc, one device dispatch."""

    @functools.partial(jax.jit, donate_argnums=0)
    def ingest(acc, ids, values):
        return fused_ingest_batch(
            acc, ids, values, bucket_limit, precision, interpret=interpret
        )

    return ingest


# Sentinel flat pool cell for samples that must drop (invalid id, row
# without a codec, page the host declined to map, zero-page hit).  One
# past the largest index validate_pool_shape admits, so the scatter's
# bounds guard discards it — the same "park past the end" idiom as
# paged_scatter_batch's mode="drop" filler.
_DROP_CELL = 2**31 - 2


def fused_paged_ingest_batch(
    pool: jnp.ndarray,
    ids: jnp.ndarray,
    values: jnp.ndarray,
    row_codec: jnp.ndarray,
    enc_luts: jnp.ndarray,
    page_table: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
    interpret: bool | None = None,
    kernel: str = "pallas",
) -> jnp.ndarray:
    """Direct-to-paged fused step: raw (ids, values) -> donated pool
    [P, page_size] int32 in ONE Pallas dispatch.

    ``kernel="jnp"`` swaps the final scatter for the XLA tier
    (``paged_scatter_batch``) — bit-identical by the paged-store parity
    pin, and legal inside shard_map where the per-cell DMA kernel is
    not (the resolve_compact_path policy: Pallas stays the
    single-device tier).

    The codec encode and page translate that paging.py performs on the
    host for the packed-commit path run here as three gathers on static
    [N] shapes; duplicate cells fold with one sort + segment-sum so the
    scatter sees each touched cell once per batch (per-cell DMA cost
    tracks UNIQUE cells, not samples).  Operands beyond the batch are
    the PagedStore device mirrors (``PagedStore.device_luts``):

      row_codec   int32 [M]        codec id per row (-1 = unassigned —
                                   those samples drop; the host assigns
                                   codecs in prepare_batch BEFORE the
                                   dispatch, so a -1 here means the host
                                   chose to spill the row)
      enc_luts    int32 [C, B]     per-codec dense->storage encode LUTs
      page_table  int32 [M, ppr]   pool slot per (row, storage page),
                                   -1 = unmapped (drops)

    Exactness: every count is an int32 add into the pool (the f32 path
    exists only inside bucket_indices, identical to every other tier);
    the segment fold is integer; ordering never matters.  Slot 0 (the
    reserved zero page) is excluded by the valid mask here AND shifted
    out of range by pallas_paged_scatter — double-guarded like the
    translate step.
    """
    pages, page_size = pool.shape
    if page_table.ndim != 2:
        raise ValueError(
            f"page_table must be [M, pages_per_row]; got {page_table.shape}"
        )
    if enc_luts.ndim != 2 or enc_luts.shape[1] != 2 * bucket_limit + 1:
        raise ValueError(
            f"enc_luts must be [codecs, {2 * bucket_limit + 1}]; got "
            f"{tuple(enc_luts.shape)}"
        )
    n = ids.shape[0]
    if n == 0:
        return pool
    num_metrics = page_table.shape[0]

    # -- XLA preprocess: compress -> encode -> translate -> fold, all
    #    static [N] shapes (no [M, B] array exists on this path) --
    dense = bucket_indices(values.astype(jnp.float32), bucket_limit, precision)
    valid = (ids >= 0) & (ids < num_metrics)
    row = jnp.where(valid, ids, 0).astype(jnp.int32)
    codec = row_codec[row]
    valid &= codec >= 0
    storage = enc_luts[jnp.maximum(codec, 0), dense]
    page_idx = storage // page_size
    offset = storage - page_idx * page_size
    slot = page_table[row, page_idx]
    valid &= slot > ZERO_SLOT
    flat = jnp.where(
        valid, slot * page_size + offset, jnp.int32(_DROP_CELL)
    )

    # fold duplicates: sort parks dropped samples at the end, then each
    # run of equal cells collapses to (cell, run length) on its first
    # position — everything else becomes a slot -1 filler triple
    sorted_flat = jnp.sort(flat)
    is_start = jnp.concatenate(
        [jnp.ones(1, dtype=bool), sorted_flat[1:] != sorted_flat[:-1]]
    )
    seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    seg_counts = jnp.zeros(n, dtype=jnp.int32).at[seg].add(1)
    keep = is_start & (sorted_flat != _DROP_CELL)
    slots = jnp.where(keep, sorted_flat // page_size, jnp.int32(-1))
    offs = jnp.where(keep, sorted_flat % page_size, 0)
    counts = jnp.where(keep, seg_counts[seg], 0)
    packed = jnp.stack([slots, offs, counts], axis=1).astype(jnp.int32)

    if kernel == "jnp":
        return paged_scatter_batch(pool, packed)
    # -- the ONE pallas_call of the program --
    return pallas_paged_scatter(pool, packed, interpret=interpret)


def make_fused_paged_ingest_fn(
    bucket_limit: int,
    precision: int = PRECISION,
    interpret: bool | None = None,
):
    """Jitted, donated-pool direct-to-paged step: f(pool [P, page_size],
    ids [N], values [N], row_codec [M], enc_luts [C, B],
    page_table [M, ppr]) -> pool.  One executable per (pool shape, batch
    length, table shape); the aggregator fixes the batch length to its
    staging chunk and PagedStore re-makes the fn on table growth."""

    @functools.partial(jax.jit, donate_argnums=0)
    def ingest(pool, ids, values, row_codec, enc_luts, page_table):
        return fused_paged_ingest_batch(
            pool, ids, values, row_codec, enc_luts, page_table,
            bucket_limit, precision, interpret=interpret,
        )

    return ingest


def make_sharded_fused_paged_ingest_fn(
    mesh,
    rows_per_shard: int,
    shard_pages: int,
    bucket_limit: int,
    precision: int = PRECISION,
):
    """Mesh tier of the direct-to-paged step — same operand contract as
    ``make_fused_paged_ingest_fn`` (pool, ids, values, row_codec,
    enc_luts, page_table) with the pool laid out as per-metric-shard
    page arenas and the batch split over the stream axis.

    Inside one shard_map each device (a) keeps the ids its metric shard
    owns (re-based to local rows; foreign ids take the dropped filler),
    (b) localizes its page-table slice's GLOBAL slots to arena-local
    slots (rows only ever map pages from their own shard's arena —
    PagedStore's allocation invariant — so this is a pure re-base; the
    defensive mask drops anything else), (c) runs the whole
    compress->encode->translate->fold->scatter body on its [N/n_stream]
    batch slice with the jnp scatter tier, and (d) merges deltas with
    ONE stream-axis psum.  int32 adds commute and every sample is owned
    by exactly one metric shard, so the result is bit-identical to the
    single-device fused ingest over the same batch.

    ids.shape[0] must divide by the stream axis (the capability table
    screens batch sizes); rows_per_shard bakes into the executable, so
    PagedStore drops its cached fn on grow().
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from loghisto_tpu.parallel.mesh import METRIC_AXIS, STREAM_AXIS

    def _local(pool_local, ids, values, row_codec_local, enc_luts, tbl_local):
        shard = jax.lax.axis_index(METRIC_AXIS)
        local_ids = ids - shard * rows_per_shard
        local_ids = jnp.where(
            (local_ids >= 0) & (local_ids < rows_per_shard),
            local_ids,
            jnp.int32(-1),
        )
        local_tbl = tbl_local - shard * shard_pages
        local_tbl = jnp.where(
            (tbl_local >= 0)
            & (local_tbl > ZERO_SLOT)
            & (local_tbl < shard_pages),
            local_tbl,
            jnp.int32(-1),
        )
        delta = fused_paged_ingest_batch(
            jnp.zeros_like(pool_local),
            local_ids,
            values,
            row_codec_local,
            enc_luts,
            local_tbl,
            bucket_limit,
            precision,
            kernel="jnp",
        )
        delta = jax.lax.psum(delta, STREAM_AXIS)
        return pool_local + delta

    sharded = shard_map(
        _local,
        mesh=mesh,
        in_specs=(
            P(METRIC_AXIS, None),
            P(STREAM_AXIS),
            P(STREAM_AXIS),
            P(METRIC_AXIS),
            P(),
            P(METRIC_AXIS, None),
        ),
        out_specs=P(METRIC_AXIS, None),
    )

    @functools.partial(jax.jit, donate_argnums=0)
    def ingest(pool, ids, values, row_codec, enc_luts, page_table):
        return sharded(pool, ids, values, row_codec, enc_luts, page_table)

    return ingest
