"""Sparse-delta device program (transport="sparse"): donated scatter-add
over packed int32 [n, 3] (id, codec_bucket, count) triples.

The raw transport ships every sample and pays a per-sample device
compress; the sparse transport folds the batch on host first (_native
``fold_packed`` — parallel C tier or pure NumPy) and ships only the
unique cells, so the device program is a WEIGHTED scatter over O(cells)
rows with no codec work at all.  For Zipf-shaped load the cell count is
a small fraction of the sample count, which moves both the wire bytes
and the device FLOPs from O(samples) to O(unique cells).

Two tiers, bit-identical by construction (tests/test_ingest_transport.py
pins the parity):

  * "jnp"    — XLA scatter-add, identical math to ops.ingest's
    make_packed_ingest_fn; works on every platform and is what "auto"
    dispatches today.
  * "pallas" — a TPU Pallas kernel that keeps the accumulator in HBM and
    round-trips the (8, 128) tile holding each cell through a VMEM
    scratch via explicit DMA.  Exact (integer adds, serial grid), but NOT yet
    hardware-ranked against the XLA scatter — it exists so a capture can
    rank it (benchmarks/device_paths.py pattern); "auto" will not pick
    it until a committed threshold table says so (ops/dispatch.py
    SPARSE_KERNEL).  Off-TPU it runs in interpret mode so CI exercises
    the same code path.

Padding rows use id -1, which ``sanitize_ids`` (jnp tier) or the
explicit bounds guard (Pallas tier) drops; callers route counts >= 2^30
to the exact host spill first, so the int32 count column cannot
overflow (the _native drain's split rule caps every wire row below
that).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from loghisto_tpu.ops.backend import default_interpret
from loghisto_tpu.ops.ingest import sanitize_ids

# Triples per Pallas grid step: small enough that the SMEM operand
# blocks stay trivial, large enough to amortize grid overhead.
TRIPLE_TILE = 256

# The accumulator tile one cell's DMA moves: the (sublane, lane) tiling
# of an int32 HBM array, the smallest region Mosaic will DMA.
ROW_BLOCK = 8
LANE_BLOCK = 128


def sparse_ingest_batch(
    acc: jnp.ndarray, packed: jnp.ndarray, bucket_limit: int
) -> jnp.ndarray:
    """Pure jnp tier: weighted scatter-add of packed triples into the
    dense accumulator (the math of ops.ingest.make_packed_ingest_fn)."""
    if packed.ndim != 2 or packed.shape[1] != 3:
        raise ValueError(
            f"packed must be [n, 3] (id, bucket, count); got {packed.shape}"
        )
    idx = jnp.clip(packed[:, 1], -bucket_limit, bucket_limit) + bucket_limit
    return acc.at[sanitize_ids(packed[:, 0]), idx].add(
        packed[:, 2], mode="drop"
    )


def _pallas_kernel(ids_ref, idx_ref, w_ref, acc_in_ref, acc_out_ref,
                   tile_ref, sem_in, sem_out):
    """One grid step: apply TRIPLE_TILE cells to the HBM accumulator.

    Per cell: DMA the (ROW_BLOCK, LANE_BLOCK) tile holding the cell
    HBM->VMEM, add the weight as a one-hot tile (Mosaic DMAs only whole
    (8, 128) tiles of an int32 HBM array and stores no single scalar to
    VMEM), DMA the tile back.  The TPU grid is sequential and each DMA
    pair completes before the next cell starts, so duplicate cells
    within or across tiles accumulate exactly — no atomics needed.
    Cells the wrapper routed elsewhere carry row -1 and are skipped.
    acc_in/acc_out alias (input_output_aliases), so all traffic goes
    through acc_out_ref and the input ref is only the donation anchor."""
    del acc_in_ref

    def body(j, carry):
        mid = ids_ref[0, j]

        @pl.when(mid >= 0)
        def _apply():
            col = idx_ref[0, j]
            base = pl.multiple_of(mid // ROW_BLOCK * ROW_BLOCK, ROW_BLOCK)
            lane_base = pl.multiple_of(
                col // LANE_BLOCK * LANE_BLOCK, LANE_BLOCK
            )
            window = acc_out_ref.at[
                pl.ds(base, ROW_BLOCK), pl.ds(lane_base, LANE_BLOCK)
            ]
            load = pltpu.make_async_copy(window, tile_ref, sem_in)
            load.start()
            load.wait()
            row = jax.lax.broadcasted_iota(jnp.int32, tile_ref.shape, 0)
            lane = jax.lax.broadcasted_iota(jnp.int32, tile_ref.shape, 1)
            hit = (row == mid - base) & (lane == col - lane_base)
            tile_ref[...] += jnp.where(hit, w_ref[0, j], 0)
            store = pltpu.make_async_copy(tile_ref, window, sem_out)
            store.start()
            store.wait()

        return carry

    jax.lax.fori_loop(0, ids_ref.shape[1], body, 0)


def pallas_cell_scatter(
    acc: jnp.ndarray,
    rows: jnp.ndarray,
    cols: jnp.ndarray,
    weights: jnp.ndarray,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """The one pallas_call behind both Pallas scatter tiers (dense
    sparse-ingest and the paged pool): add ``weights[k]`` at
    ``acc[rows[k], cols[k]]`` for every row in [0, M).  ``rows``,
    ``cols`` and ``weights`` are int32 [n]; cols must already be in
    [0, B).

    The kernel covers the whole (8, 128) tiles of ``acc``.  Cells in a
    ragged edge (the last M % 8 rows or B % 128 columns — the dense
    accumulator's odd 2*bucket_limit+1 axis leaves one such column) are
    added by one XLA scatter after it; integer adds commute, so the
    split is exact."""
    if interpret is None:
        interpret = default_interpret()
    num_rows, num_cols = acc.shape
    rows = rows.astype(jnp.int32)
    cols = cols.astype(jnp.int32)
    weights = weights.astype(jnp.int32)
    valid = (rows >= 0) & (rows < num_rows)
    tiled_rows = num_rows // ROW_BLOCK * ROW_BLOCK
    tiled_cols = num_cols // LANE_BLOCK * LANE_BLOCK
    in_tiles = valid & (rows < tiled_rows) & (cols < tiled_cols)

    n = rows.shape[0]
    g = max(1, (n + TRIPLE_TILE - 1) // TRIPLE_TILE)
    pad = g * TRIPLE_TILE - n

    # [g, 1, T] operands with a squeezed leading block dim: the block's
    # last two dims then equal the array's, which Mosaic requires of an
    # SMEM block that is not (8, 128)-aligned
    def tiles(a):
        return jnp.pad(a, (0, pad)).reshape(g, 1, TRIPLE_TILE)

    smem_spec = pl.BlockSpec(
        (None, 1, TRIPLE_TILE), lambda i: (i, 0, 0),
        memory_space=pltpu.SMEM,
    )
    if tiled_rows and tiled_cols:
        acc = pl.pallas_call(
            _pallas_kernel,
            grid=(g,),
            in_specs=[
                smem_spec,
                smem_spec,
                smem_spec,
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
            scratch_shapes=[
                pltpu.VMEM((ROW_BLOCK, LANE_BLOCK), jnp.int32),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
            ],
            input_output_aliases={3: 0},
            interpret=interpret,
        )(
            tiles(jnp.where(in_tiles, rows, -1)),
            tiles(cols),
            tiles(weights),
            acc,
        )
    if tiled_rows == num_rows and tiled_cols == num_cols:
        return acc
    edge = valid & ~in_tiles
    return acc.at[jnp.where(edge, rows, num_rows), cols].add(
        weights, mode="drop"
    )


def pallas_sparse_ingest(
    acc: jnp.ndarray,
    packed: jnp.ndarray,
    bucket_limit: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pallas tier: same contract as sparse_ingest_batch."""
    if packed.ndim != 2 or packed.shape[1] != 3:
        raise ValueError(
            f"packed must be [n, 3] (id, bucket, count); got {packed.shape}"
        )
    idx = jnp.clip(packed[:, 1], -bucket_limit, bucket_limit) + bucket_limit
    return pallas_cell_scatter(
        acc, packed[:, 0], idx, packed[:, 2], interpret=interpret
    )


def make_sparse_ingest_fn(bucket_limit: int, kernel: str = "auto"):
    """Jitted, donated-accumulator sparse merge step:
    ``f(acc, packed) -> acc`` with acc int32 [M, B] and packed int32
    [n, 3].  ``kernel`` picks the tier ("auto" follows the
    capture-overridable ops.dispatch.SPARSE_KERNEL switch)."""
    from loghisto_tpu.ops.dispatch import resolve_sparse_kernel

    kernel = resolve_sparse_kernel(kernel)
    step = (
        pallas_sparse_ingest if kernel == "pallas" else sparse_ingest_batch
    )

    @functools.partial(jax.jit, donate_argnums=0)
    def ingest(acc, packed):
        return step(acc, packed, bucket_limit)

    return ingest
