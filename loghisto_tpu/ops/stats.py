"""Histogram statistics: percentiles, sum/count/avg (reference layer L3 math).

The reference extracts each percentile by sorting the sparse bucket list and
walking the CDF — once per percentile per histogram per interval, with an
acknowledged TODO to batch them (metrics.go:406-418).  Here the scan is a
prefix sum + ``searchsorted`` computing *all* percentiles in one pass:

  * Bucket indices are monotonic in value (the codec is sign-mirrored and
    monotonic), so sorting by bucket index == sorting by representative value,
    and for the dense tensor the buckets are *already* sorted — no sort at all.
  * The reference's selection rule is "first representative where
    float64(cum)/float64(total) >= p" (metrics.go:411-414).  The host
    (NumPy) tier replicates the same float64 division before comparison so
    edge cases round identically (e.g. p=.99 over 10_000 samples must hit
    cum==9900 exactly).  The device tier keeps the cumsum exact in int32 and
    performs the division in float32 (TPUs have no fast float64): selection
    is bit-identical to the reference for per-metric interval counts up to
    2^24 and within one bucket (i.e. within the 1% accuracy contract)
    beyond; min (p=0) and max (p=1) are computed by exact populated-bucket
    selection at any count.

The jnp variants operate on a dense ``[num_metrics, num_buckets]`` count
tensor where bucket axis index b represents codec bucket ``b - bucket_limit``;
sums become a matvec against the representative values (MXU-friendly) and
percentile selection is a two-level hierarchical rank search: one pass of
128-lane block sums, a tiny block-level cumsum, then an in-block resolve —
every threshold served from a single pass over the data (no full-width
cumsum, which lowers as ~log2(B) whole-array passes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from loghisto_tpu.config import PRECISION
from loghisto_tpu.ops.codec import decompress, decompress_np


def percentiles_sparse(
    buckets: np.ndarray, counts: np.ndarray, ps: np.ndarray,
    precision: int = PRECISION,
) -> np.ndarray:
    """Percentiles from a sparse (bucket, count) histogram (host tier).

    Args:
      buckets: int bucket indices, any order, each count > 0.
      counts: occurrence counts per bucket.
      ps: quantiles in [0, 1] (caller validates; reference glog-and-drops
        out-of-range requests, metrics.go:378-385).

    Returns bucket representative values, one per p.  An empty histogram
    returns zeros for every p (consistent with dense_stats' empty-metric
    behavior; the reference never processes empty histograms because names
    only exist in its sparse map once a sample lands).
    """
    if len(np.asarray(buckets)) == 0:
        return np.zeros(len(np.asarray(ps)))
    order = np.argsort(buckets, kind="stable")
    values = decompress_np(np.asarray(buckets)[order], precision)
    cdf = np.cumsum(np.asarray(counts, dtype=np.uint64)[order])
    total = float(cdf[-1])
    # Same operation order as the reference: float(cum)/float(total) >= p.
    cdfn = cdf.astype(np.float64) / total
    idx = np.searchsorted(cdfn, np.asarray(ps, dtype=np.float64), side="left")
    idx = np.minimum(idx, len(values) - 1)
    return values[idx]


def summarize_sparse(
    buckets: np.ndarray, counts: np.ndarray, precision: int = PRECISION,
) -> tuple[float, int]:
    """(sum of representatives * counts, total count) — metrics.go:342-347."""
    values = decompress_np(np.asarray(buckets), precision)
    counts = np.asarray(counts, dtype=np.float64)
    return float(np.dot(values, counts)), int(counts.sum())


def bucket_representatives(
    bucket_limit: int, precision: int = PRECISION, dtype=jnp.float32
) -> jnp.ndarray:
    """Representative value of every dense-axis bucket: index b maps to codec
    bucket b - bucket_limit."""
    idx = jnp.arange(2 * bucket_limit + 1, dtype=jnp.int32) - bucket_limit
    return decompress(idx, precision).astype(dtype)


def sparse_cells_stats(
    rows: np.ndarray,
    dense_idx: np.ndarray,
    counts: np.ndarray,
    num_metrics: int,
    ps: np.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
) -> dict[str, np.ndarray]:
    """dense_stats_np over a sparse cell list: O(occupied cells) host
    work, never a dense ``[M, B]`` materialization — the collect() tier
    of the paged backend (loghisto_tpu/paging.py).

    Args:
      rows / dense_idx / counts: parallel cell arrays — metric row,
        dense-axis bucket index (codec bucket + bucket_limit), int64
        count.  Duplicate (row, bucket) cells are allowed and fold.

    Selection is identical to dense_stats_np (first bucket where
    float(cum)/float(total) >= p over int64-exact cumsums; endpoints
    are the first/last populated bucket), so percentiles of a sparse
    view are BIT-IDENTICAL to the dense oracle over the same histogram.
    Sums reduce in occupied-bucket order, which can differ from the
    dense matvec in the final float64 ulp.
    """
    rows = np.asarray(rows, dtype=np.int64)
    dense_idx = np.asarray(dense_idx, dtype=np.int64)
    cell_counts = np.asarray(counts, dtype=np.int64)
    ps = np.asarray(ps, dtype=np.float64)
    m, p_n = int(num_metrics), len(ps)
    out_counts = np.zeros(m, dtype=np.int64)
    out_sums = np.zeros(m, dtype=np.float64)
    out_pct = np.zeros((m, p_n), dtype=np.float64)
    if not len(rows):
        return {
            "counts": out_counts, "sums": out_sums, "percentiles": out_pct,
        }
    # fold duplicates and order cells by (row, bucket) in one pass
    order = np.lexsort((dense_idx, rows))
    rows, dense_idx, cell_counts = (
        rows[order], dense_idx[order], cell_counts[order]
    )
    keys = rows * (2 * bucket_limit + 2) + dense_idx
    uniq, inverse = np.unique(keys, return_inverse=True)
    folded = np.zeros(len(uniq), dtype=np.int64)
    np.add.at(folded, inverse, cell_counts)
    first = np.searchsorted(keys, uniq, side="left")
    rows, dense_idx, cell_counts = rows[first], dense_idx[first], folded

    reps = decompress_np(dense_idx - bucket_limit, precision)
    starts = np.searchsorted(rows, np.arange(m), side="left")
    ends = np.searchsorted(rows, np.arange(m), side="right")
    for r in range(m):
        lo, hi = starts[r], ends[r]
        if lo == hi:
            continue
        c = cell_counts[lo:hi]
        cdf = np.cumsum(c)
        total = cdf[-1]
        out_counts[r] = total
        out_sums[r] = np.dot(reps[lo:hi], c.astype(np.float64))
        cdfn = cdf.astype(np.float64) / float(total)
        pos = np.minimum(
            np.searchsorted(cdfn, ps, side="left"), hi - lo - 1
        )
        idx = np.where(ps <= 0, 0, np.where(ps >= 1, hi - lo - 1, pos))
        out_pct[r] = reps[lo:hi][idx]
    return {"counts": out_counts, "sums": out_sums, "percentiles": out_pct}


def dense_stats_np(
    acc: np.ndarray,
    ps: np.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
) -> dict[str, np.ndarray]:
    """Host (NumPy, int64) mirror of dense_stats for intervals whose
    counts exceed what the int32 device accumulator can hold — the
    overflow-spill path (SURVEY.md §7 hard part (b)).  Exact at any
    count < 2^53 (float64 integer exactness), same selection rule as
    percentiles_sparse: first bucket where float(cum)/float(total) >= p.
    """
    acc = np.asarray(acc, dtype=np.int64)
    ps = np.asarray(ps, dtype=np.float64)
    reps = decompress_np(
        np.arange(-bucket_limit, bucket_limit + 1, dtype=np.int64), precision
    )
    cdf = np.cumsum(acc, axis=1)
    counts = cdf[:, -1]
    sums = acc.astype(np.float64) @ reps
    m, b = acc.shape
    idx = np.zeros((m, len(ps)), dtype=np.int64)
    for row in range(m):
        total = counts[row]
        if total == 0:
            continue
        cdfn = cdf[row].astype(np.float64) / float(total)
        pos = np.minimum(np.searchsorted(cdfn, ps, side="left"), b - 1)
        populated = np.nonzero(acc[row])[0]
        lo, hi = populated[0], populated[-1]
        idx[row] = np.where(ps <= 0, lo, np.where(ps >= 1, hi, pos))
    pct = reps[idx]
    pct[counts == 0] = 0.0
    return {"counts": counts, "sums": sums, "percentiles": pct}


def weighted_sums(counts_f, reps):
    """Per-row sum of bucket representatives weighted by counts, a
    matvec on the MXU.  At full float32 precision: the TPU's default
    multiplies float32 operands in bfloat16, whose 8-bit mantissa
    cannot hold a bucket count above 256."""
    return jnp.matmul(counts_f, reps, precision=jax.lax.Precision.HIGHEST)


def _rank_threshold(k0, ps, total_f):
    """The smallest integer count k (as float32) with k / total >= p,
    searched in the +/-1 window around ``k0 = ceil(p * total)``.  The
    test is ``k >= p * total``: float32 division is not correctly
    rounded on a TPU (PR 21's chip run: 368,732 of 2^20 quotients
    differed from IEEE), and a quotient one ulp short at an exact tie
    moved the rank one sample up; a float32 product is exact-rounded,
    and at a tie it rounds to the integer itself."""
    cands = k0[:, :, None] + jnp.arange(-1.0, 2.0)  # [M, P, 3]
    ok = (cands >= ps[None, :, None] * total_f[:, :, None]) & (cands >= 1.0)
    best = jnp.min(jnp.where(ok, cands, jnp.inf), axis=2)
    return jnp.where(jnp.isfinite(best), best, k0)


def dense_stats(
    acc: jnp.ndarray,
    ps: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
) -> dict[str, jnp.ndarray]:
    """Full per-metric statistics from a dense [M, B] count tensor.

    Returns dict with:
      counts [M] int32 — per-metric total sample count (this interval; kept
        integer so counts above 2^24 stay exact)
      sums   [M]   — sum of bucket representatives weighted by counts
      percentiles [M, P] — representative value at each quantile in ps

    Percentile rule matches the reference exactly (see module docstring);
    empty metrics (count == 0) return 0 for all stats, mirroring the
    reference where empty histograms simply don't exist in the sparse map.
    """
    num_buckets = acc.shape[1]
    acc_f = acc.astype(jnp.float32)
    reps = bucket_representatives(bucket_limit, precision)
    sums = weighted_sums(acc_f, reps)
    # Hierarchical CDF: a full [M, B] cumsum lowers as ~log2(B) whole-
    # array passes (measured 0.9s of a 1.1s CPU stats call at 10k x 8193);
    # instead reduce to per-block sums in ONE pass (LANE-sized blocks — a
    # TPU vector register row), cumsum only the [M, B/LANE] block totals,
    # and resolve each rank threshold inside a single gathered block.
    # All integer arithmetic stays exact int32, same as the full cumsum.
    LANE = 128
    m = acc.shape[0]
    n_blocks = (num_buckets + LANE - 1) // LANE
    pad = n_blocks * LANE - num_buckets
    acc_pad = jnp.pad(acc, ((0, 0), (0, pad))) if pad else acc
    blocks = acc_pad.reshape(m, n_blocks, LANE)
    block_sums = blocks.sum(axis=2, dtype=jnp.int32)  # [M, nB]
    block_cdf = jnp.cumsum(block_sums, axis=1)  # [M, nB] — tiny
    counts = block_cdf[:, -1]

    ps = jnp.asarray(ps, dtype=jnp.float32)

    # Selection rule: first bucket with cdf/total >= p.  Instead
    # of materializing the [M, B] float CDF (a full extra array + division
    # per cell), derive the integer rank threshold k*[m, p] = the smallest
    # integer count satisfying the float division — an [M, P] computation —
    # and search the integer cumsum directly.  Monotonicity of k/total in
    # k makes the two formulations select identical buckets.
    # Exact below 2^24 (float32 integers are exact there, and the +/-1
    # window always brackets the crossover).  Above 2^24 float32 ulp
    # exceeds 1, so the window may contain no passing candidate; fall
    # back to k0 itself — within a few ulp of the true rank, i.e. a
    # relative rank error < 2^-22, far inside the within-one-bucket
    # contract.  Never use an out-of-int32 sentinel: its cast is
    # backend-defined.
    total_f = jnp.maximum(counts, 1).astype(jnp.float32)[:, None]  # [M,1]
    k0 = jnp.ceil(ps[None, :] * total_f)  # [M, P] first candidate
    k_star_f = _rank_threshold(k0, ps, total_f)
    # int32-representable float clamp BEFORE the cast (f32(2^31) itself
    # casts implementation-defined), then the exact integer clamp
    k_star_f = jnp.clip(k_star_f, 1.0, jnp.float32(2**31 - 256))
    k_star = jnp.minimum(
        k_star_f.astype(jnp.int32), jnp.maximum(counts, 1)[:, None]
    )

    # 0 < p < 1: first bucket whose integer cumsum reaches k*.  Two-level
    # search serving all P thresholds in one pass over the block totals
    # (metrics.go:408's TODO, answered at device scale):
    #   1. block level: j*[m,p] = count of blocks whose cumulative total
    #      is still below k* (vectorized count-below over [M, P, nB])
    #   2. lane level: gather block j* ([M, P, LANE] — tiny), cumsum its
    #      LANE lanes, count lanes below the residual threshold
    # Empty prefix buckets have cdf 0 < k*, so the hit lands on a
    # populated bucket — identical selection to a full-cumsum search.
    blk = jnp.sum(
        (block_cdf[:, None, :] < k_star[:, :, None]).astype(jnp.int32),
        axis=2,
    )
    blk = jnp.minimum(blk, n_blocks - 1)  # [M, P]
    # exclusive prefix before the selected block
    base = jnp.where(
        blk > 0,
        jnp.take_along_axis(block_cdf, jnp.maximum(blk - 1, 0), axis=1),
        0,
    )
    inner = jnp.take_along_axis(
        blocks, blk[:, :, None], axis=1
    )  # [M, P, LANE]
    inner_cdf = base[:, :, None] + jnp.cumsum(inner, axis=2)
    lane = jnp.sum(
        (inner_cdf < k_star[:, :, None]).astype(jnp.int32), axis=2
    )
    pos = jnp.minimum(blk * LANE + lane, num_buckets - 1)

    # Exact populated-bucket endpoints, immune to rounding, via the same
    # two-level structure: block_sums > 0 marks blocks with any count.
    # p == 0 / p == 1: the reference iterates only *populated* buckets, so
    # these mean first/last populated bucket — selected exactly.
    block_pop = block_sums > 0
    iota_b = jnp.arange(n_blocks, dtype=jnp.int32)[None, :]
    iota_l = jnp.arange(LANE, dtype=jnp.int32)[None, :]
    jb_min = jnp.argmax(block_pop, axis=1)  # first populated block
    jb_max = jnp.max(jnp.where(block_pop, iota_b, -1), axis=1)
    jb_max_c = jnp.maximum(jb_max, 0)
    first_blk = jnp.take_along_axis(
        blocks, jb_min[:, None, None], axis=1
    )[:, 0, :]
    last_blk = jnp.take_along_axis(
        blocks, jb_max_c[:, None, None], axis=1
    )[:, 0, :]
    idx_min = jb_min * LANE + jnp.argmax(first_blk > 0, axis=1)
    idx_max = jb_max_c * LANE + jnp.maximum(
        jnp.max(jnp.where(last_blk > 0, iota_l, -1), axis=1), 0
    )
    idx_max = jnp.minimum(idx_max, num_buckets - 1)

    idx = jnp.where(
        ps[None, :] <= 0,
        idx_min[:, None],
        jnp.where(ps[None, :] >= 1, idx_max[:, None], pos),
    )
    pct = reps[idx]
    nonempty = (counts > 0)[:, None]
    return {
        "counts": counts,
        "sums": sums,
        "percentiles": jnp.where(nonempty, pct, 0.0),
    }


# ---------------------------------------------------------------------- #
# Snapshot query engine: commit-time CDF + sparse row serving
# ---------------------------------------------------------------------- #
#
# dense_stats answers every metric at once, which is the right shape for
# the interval pipeline but the wrong one for serving: a scrape or a rule
# check re-pays the whole [M, B] scan per query.  The snapshot split
# moves the scan to COMMIT time: ``dense_cdf`` emits the exact int32
# bucket prefix sums (plus counts and the same f32 sums matvec) once per
# interval, and ``snapshot_row_stats`` turns a percentile query into a
# row gather + ``searchsorted`` over only the requested metrics.
#
# Selection parity: dense_stats picks "the number of buckets whose
# integer cumsum is < k*" (two-level block search).  For a nondecreasing
# CDF row, ``searchsorted(cdf, k*, side="left")`` returns exactly that
# count, and the endpoint rules collapse into the same primitive —
# first populated bucket == searchsorted(cdf, 1), last populated bucket
# == searchsorted(cdf, total) — so a snapshot query is bit-identical to
# dense_stats over the same histogram (tests/test_query_engine.py pins
# this), while reading back [n, P] floats instead of [M, P].


def dense_cdf(
    acc: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
) -> dict[str, jnp.ndarray]:
    """Commit-time snapshot payload for a dense [M, B] count tensor:

      cdf    int32 [M, B] — exact per-metric bucket prefix sums
      counts int32 [M]    — per-metric totals (cdf[:, -1])
      sums   f32   [M]    — the same representative matvec dense_stats
                            uses, precomputed so a query never touches
                            the full histogram
    """
    reps = bucket_representatives(bucket_limit, precision)
    cdf = jnp.cumsum(acc, axis=1, dtype=jnp.int32)
    return {
        "cdf": cdf,
        "counts": cdf[:, -1],
        "sums": weighted_sums(acc.astype(jnp.float32), reps),
    }


def snapshot_row_stats(
    cdf_rows: jnp.ndarray,
    counts: jnp.ndarray,
    sums: jnp.ndarray,
    ps: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
) -> dict[str, jnp.ndarray]:
    """Statistics for gathered snapshot rows: cdf_rows int32 [n, B],
    counts int32 [n], sums f32 [n], ps f32 [P] -> counts/sums pass
    through, percentiles [n, P].  Same k* derivation as dense_stats
    (identical float32 operation order), then one searchsorted per row.
    """
    num_buckets = cdf_rows.shape[1]
    reps = bucket_representatives(bucket_limit, precision)
    ps = jnp.asarray(ps, dtype=jnp.float32)
    total_f = jnp.maximum(counts, 1).astype(jnp.float32)[:, None]  # [n,1]
    k0 = jnp.ceil(ps[None, :] * total_f)  # [n, P]
    k_star_f = _rank_threshold(k0, ps, total_f)
    k_star_f = jnp.clip(k_star_f, 1.0, jnp.float32(2**31 - 256))
    total_i = jnp.maximum(counts, 1)[:, None]
    k_star = jnp.minimum(k_star_f.astype(jnp.int32), total_i)
    # endpoints through the same searchsorted: rank 1 hits the first
    # populated bucket, rank == total the last populated bucket
    k = jnp.where(
        ps[None, :] <= 0,
        jnp.ones_like(k_star),
        jnp.where(ps[None, :] >= 1, total_i, k_star),
    )
    pos = jax.vmap(
        lambda row, kk: jnp.searchsorted(row, kk, side="left")
    )(cdf_rows, k)
    pos = jnp.minimum(pos, num_buckets - 1)
    pct = reps[pos]
    nonempty = (counts > 0)[:, None]
    return {
        "counts": counts,
        "sums": sums,
        "percentiles": jnp.where(nonempty, pct, 0.0),
    }


@functools.lru_cache(maxsize=None)
def make_snapshot_query_fn(
    bucket_limit: int, precision: int = PRECISION, mesh=None
):
    """Jitted sparse snapshot query ``f(cdf, counts, sums, ids, ps) ->
    stats for rows ids``: ONE gather + searchsorted dispatch, D2H
    traffic O(len(ids) * len(ps)).  Cached per bucket geometry so every
    wheel/aggregator with the same codec shares one jit object (and its
    per-shape executable cache — the plan cache's backing store); ids
    and ps are traced operands, so neither their values nor the commit
    epoch ever retrace.

    With ``mesh`` (metric-row-sharded snapshot views) the gather
    partitions under GSPMD: each requested row ships from its owning
    shard — sparse cross-shard traffic proportional to the matched ids,
    never a full CDF replication — and the small ``[n, P]`` results are
    pinned replicated so the host readback is a local copy on every
    process."""
    jit_kwargs = {}
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        jit_kwargs["out_shardings"] = NamedSharding(mesh, PartitionSpec())

    @functools.partial(jax.jit, **jit_kwargs)
    def query(cdf, counts, sums, ids, ps):
        return snapshot_row_stats(
            cdf[ids], counts[ids], sums[ids], ps, bucket_limit, precision
        )

    return query


@functools.lru_cache(maxsize=None)
def make_group_query_fn(
    bucket_limit: int, precision: int = PRECISION, mesh=None
):
    """Jitted group_by rollup ``f(cdf, counts, sums, ids, gids, ps,
    num_groups=G) -> stats per group``: gather the matched snapshot
    rows, segment-sum them into per-group merged histograms, then run
    the same row-stats selection as the sparse query — ONE dispatch for
    the whole rollup (labels layer, ISSUE 16).

    Merging is EXACT, not approximate: log-bucket histograms merge by
    bucket-count addition, and a prefix sum is linear, so the sum of
    CDF rows IS the CDF of the merged histogram (int32 exact; a merged
    group's total must stay within int32, the same wire contract as a
    single wheel slot).  Percentiles of the merged CDF therefore match
    a host-side sparse merge oracle bit-for-bit for dense-codec rows
    (tests/test_labels.py pins this).

    ``num_groups`` is static (segment_sum needs a static segment
    count); callers pad it to a power of two — padding ids point at row
    0 and padding gids at a reserved dump segment that is sliced off
    after readback — so drifting group counts reuse one executable per
    (n_ids-bucket, groups-bucket, P) shape, exactly like the plan-cache
    discipline of the sparse query path.  Sharding note: under a mesh
    the gather ships only matched rows off their owning shards and the
    tiny per-group results land replicated, same as the sparse query.
    """
    jit_kwargs = {}
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        jit_kwargs["out_shardings"] = NamedSharding(mesh, PartitionSpec())

    @functools.partial(
        jax.jit, static_argnames=("num_groups",), **jit_kwargs
    )
    def group_query(cdf, counts, sums, ids, gids, ps, *, num_groups):
        gcdf = jax.ops.segment_sum(
            cdf[ids], gids, num_segments=num_groups
        )
        gcounts = jax.ops.segment_sum(
            counts[ids], gids, num_segments=num_groups
        )
        gsums = jax.ops.segment_sum(
            sums[ids], gids, num_segments=num_groups
        )
        return snapshot_row_stats(
            gcdf, gcounts, gsums, ps, bucket_limit, precision
        )

    return group_query
