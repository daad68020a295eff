"""Device programs for the metric lifecycle subsystem (ISSUE 4):
activity touch, evict-fold, and the gather-compact repack.

The paper's lossless-counting claim only survives name churn if series
can be RETIRED without losing their counts: log-bucket histograms merge
exactly by elementwise addition, so an evicted row folds into a
catch-all overflow row with zero information loss at the bucket level.
These kernels keep the whole lifecycle on-device over the same donated
carries the fused commit owns:

  * ``make_touch_fn`` — per-interval activity scatter for the fan-out
    path (the fused commit embeds the same update at zero extra
    dispatches; see ops/commit.py ``track_activity``).
  * ``make_fold_evict_fn`` — gather each victim row, scatter-add it
    into its overflow target, zero the victim, stamp ``last_active`` —
    one dispatch for the accumulator and every tier ring together.
  * ``make_compact_fn`` — repack every structure over a survivor
    permutation (``perm[new] = old`` row, DROP sentinel = empty) in one
    gather per structure; jnp ``take`` tier plus a Pallas
    scalar-prefetch tier where the permutation itself drives the block
    index_map, so each output row is read and written exactly once.

Out-of-range handling follows the house convention: DROP_ID pads
(ops/commit.py) vanish via ``mode="drop"`` scatters and zero-fill
gathers, so every program is shape-stable under jit — pad widths are
pow-2 bucketed by the callers to bound executable counts.

Mesh-sharded state (PR 8): these programs run unchanged on
metric-row-sharded carries.  Victim decisions stay host-side (the
manager gathers the activity vector, which is tiny), and the fold /
compact programs jit over the sharded arrays — the victim gathers and
permutation ``take``s address GLOBAL row ids, so GSPMD inserts the
cross-shard collectives where a victim's overflow target lives on a
different shard.  Only the per-interval hot path (the activity stamp
inside the fused commit) is hand-placed under ``shard_map``
(ops/commit.py); eviction and compaction are rare, so auto-partitioning
is the right trade there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from loghisto_tpu.ops.commit import DROP_ID
from loghisto_tpu.ops.backend import default_interpret


@functools.lru_cache(maxsize=None)
def make_touch_fn():
    """Jitted activity stamp for the fan-out commit path:
    ``touch(last_active, ids, epoch) -> last_active`` sets
    ``last_active[ids] = max(last_active[ids], epoch)`` with DROP_ID
    pads shedding.  The fused commit performs the identical update
    inside its own program; this standalone form exists for paths that
    cannot fuse (spill fallback, mesh fan-out)."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def touch(last_active, ids, epoch):
        return last_active.at[ids].max(epoch, mode="drop")

    return touch


@functools.lru_cache(maxsize=None)
def make_fold_evict_fn(num_tiers: int, with_acc: bool = True):
    """Build the evict-fold program for ``num_tiers`` retention tiers.

    ``with_acc=False`` is the paged-storage variant (r18): the lifetime
    accumulator lives in the page pool, whose fold is a host translate +
    pool commit (PagedStore.fold_rows_into) — so the device program
    folds only the tier rings and stamps the activity vector:
    ``fold(rings, last_active, victims, targets, epoch) -> (rings,
    last_active)``.  Victim-count accounting moves to the pool fold's
    exact host return value.

    With ``with_acc=True`` (dense):
    ``fold(acc, rings, last_active, victims, targets, epoch) ->
    (acc, rings, last_active, victim_counts)`` where

      acc         int32 [M, B]        — aggregator accumulator (donated)
      rings       tuple int32 [S,M_t,B] — tier rings (donated)
      last_active int32 [M]           — activity epochs (donated)
      victims     int32 [E]           — rows being evicted (DROP_ID pad)
      targets     int32 [E]           — overflow row for each victim
      epoch       int32 scalar        — stamped on the freed rows so a
                                        reused slot starts fresh

    Per structure: gather the victim rows (out-of-range -> zero), ONE
    scatter-add into the overflow targets (duplicate targets accumulate
    — integer scatter-adds are order-independent, so folding E victims
    is bit-identical to E sequential merges), then zero the victims.
    Victims whose id exceeds a ring's row space simply never had window
    state there; targets beyond it drop, which loses only *windowed*
    visibility of the overflow — the lifetime fold into ``acc`` is the
    lossless one.  ``victim_counts`` (int32 [E], bucket-sum per victim)
    feeds the lifecycle gauges; exact lifetime accounting is the host
    ``_agg`` fold in lifecycle/manager.py, which uses Python ints.

    Targets must never themselves be victims (the policy layer protects
    overflow names), so add-then-zero ordering is safe.
    """

    def _fold_rings(rings, last_active, victims, targets, epoch):
        new_rings = []
        for t in range(num_tiers):
            ring = rings[t]
            rrows = jnp.take(ring, victims, axis=1, mode="fill",
                             fill_value=0)
            ring = ring.at[:, targets].add(rrows, mode="drop")
            ring = ring.at[:, victims].set(0, mode="drop")
            new_rings.append(ring)
        last_active = last_active.at[victims].set(epoch, mode="drop")
        return tuple(new_rings), last_active

    if not with_acc:

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def fold_paged(rings, last_active, victims, targets, epoch):
            return _fold_rings(rings, last_active, victims, targets, epoch)

        return fold_paged

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def fold(acc, rings, last_active, victims, targets, epoch):
        rows = jnp.take(acc, victims, axis=0, mode="fill", fill_value=0)
        victim_counts = jnp.sum(rows, axis=1)
        acc = acc.at[targets].add(rows, mode="drop")
        acc = acc.at[victims].set(0, mode="drop")
        new_rings, last_active = _fold_rings(
            rings, last_active, victims, targets, epoch
        )
        return acc, new_rings, last_active, victim_counts

    return fold


# -- gather-compact ------------------------------------------------------ #


def _sanitize_perm(perm: jnp.ndarray, m: int) -> jnp.ndarray:
    """Map every out-of-range entry (DROP_ID pad OR explicit -1 hole) to
    the positive DROP sentinel: jnp's ``mode="fill"`` wraps negative
    indices BEFORE its bounds check, so a raw -1 would gather the last
    row instead of filling zero."""
    return jnp.where(
        (perm >= 0) & (perm < m), perm.astype(jnp.int32), DROP_ID
    )


def compact_rows(arr: jnp.ndarray, perm: jnp.ndarray) -> jnp.ndarray:
    """jnp tier of the row repack: ``out[new] = arr[perm[new]]``, zeros
    where ``perm[new]`` is out of range (DROP_ID = empty row).  One
    gather; XLA partitions it row-parallel under a metric-sharded
    mesh."""
    return jnp.take(
        arr, _sanitize_perm(perm, arr.shape[0]), axis=0,
        mode="fill", fill_value=0,
    )


# Output rows per compaction block: the int32 sublane tile.
COMPACT_ROWS = 8


def _compact_kernel(perm_ref, in_ref, out_ref):
    """Grid step (i, r): copy source row perm[8i + r] into row r of the
    resident output block i.  The input block is the (8, B) tile that
    holds the source row (a one-row block of a tiled array is not a
    legal block), and the row is picked and placed with sublane masks."""
    i = pl.program_id(0)
    r = pl.program_id(1)
    src = perm_ref[i * COMPACT_ROWS + r]
    sub = jax.lax.broadcasted_iota(jnp.int32, in_ref.shape, 0)
    row = jnp.sum(
        jnp.where(sub == src % COMPACT_ROWS, in_ref[...], 0),
        axis=0, keepdims=True,
    )
    # an empty row (negative perm) fetched block 0; it writes zeros
    row = jnp.where(src >= 0, row, 0)
    out_ref[...] = jnp.where(sub == r, row, out_ref[...])


def compact_rows_pallas(
    arr: jnp.ndarray,
    perm: jnp.ndarray,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pallas tier: the survivor permutation rides scalar prefetch and
    drives the input BlockSpec's index_map, so the repack is a gather
    hidden in block indexing.  The grid sweeps the 8 rows of each
    output block innermost; the output block stays resident across
    them, and consecutive rows from the same source tile (an
    order-keeping compaction) fetch that tile once.  Empty rows
    (negative / DROP sentinel) are zero."""
    if interpret is None:
        interpret = default_interpret()
    m, b = arr.shape
    n = perm.shape[0]
    n_pad = (n + COMPACT_ROWS - 1) // COMPACT_ROWS * COMPACT_ROWS
    # sanitize the sentinel into -1 so the kernel's sign test works for
    # both DROP_ID pads and explicit -1 holes
    perm32 = jnp.where(
        (perm >= 0) & (perm < m), perm.astype(jnp.int32), -1
    )
    perm32 = jnp.pad(perm32, (0, n_pad - n), constant_values=-1)

    def src_block(i, r, pr):
        return (jnp.maximum(pr[i * COMPACT_ROWS + r], 0) // COMPACT_ROWS, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // COMPACT_ROWS, COMPACT_ROWS),
        in_specs=[pl.BlockSpec((COMPACT_ROWS, b), src_block)],
        out_specs=pl.BlockSpec((COMPACT_ROWS, b), lambda i, r, pr: (i, 0)),
    )
    out = pl.pallas_call(
        _compact_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, b), arr.dtype),
        interpret=interpret,
    )(perm32, arr)
    return out if n_pad == n else out[:n]


def resolve_compact_path(path: str, platform: str, mesh: bool) -> str:
    """Dispatch policy for the repack, like the ingest dispatch:
    "auto" picks the Pallas tier only single-device on real TPU (Pallas
    under shard_map is off the table; interpret mode off-TPU is strictly
    slower than the jnp gather)."""
    if path not in ("auto", "jnp", "pallas"):
        raise ValueError(
            f"compact_path={path!r}: expected 'auto', 'jnp', or 'pallas'"
        )
    if path == "auto":
        return "pallas" if (platform == "tpu" and not mesh) else "jnp"
    if path == "pallas" and mesh:
        raise ValueError("compact_path='pallas' is single-device; use "
                         "jnp with a mesh")
    return path


@functools.lru_cache(maxsize=None)
def make_compact_fn(num_tiers: int, path: str = "jnp",
                    with_acc: bool = True):
    """Build the full-repack program: one donated-carry dispatch that
    reorders the accumulator, every tier ring, and the activity vector
    over the survivor permutation.

    ``with_acc=False`` is the paged-storage variant (r18): the pool
    repacks on host (PagedStore.apply_permutation permutes page-table
    ROWS — zero device data movement), so the device program handles
    only the rings and the activity vector:
    ``compact(rings, last_active, perm, epoch) -> (rings, last_active)``.

    ``compact(acc, rings, last_active, perm, epoch) ->
    (acc, rings, last_active)`` where ``perm`` is int32 [M] with
    ``perm[new] = old`` row (DROP sentinel = empty).  Shapes never
    change — compaction re-DENSIFIES rows toward the front so the
    registry free-list hands out low ids again; HBM stays bounded
    because rows are reused, not because arrays shrink mid-flight.
    Every output row is a pure copy of one input row (or zeros), so
    survivor histograms — and therefore every percentile derived from
    them — are bit-identical across the repack (tests/test_lifecycle.py
    pins this against a pre-compaction oracle).  Freed rows get
    ``last_active = epoch`` so reuse starts fresh.
    """

    def repack(arr2d, perm):
        if path == "pallas":
            return compact_rows_pallas(arr2d, perm)
        return compact_rows(arr2d, perm)

    def _compact_rings(rings, last_active, perm, epoch):
        new_rings = []
        for t in range(num_tiers):
            ring = rings[t]
            m_t = ring.shape[1]
            if path == "pallas":
                ring = jax.vmap(compact_rows_pallas,
                                in_axes=(0, None))(ring, perm[:m_t])
            else:
                ring = jnp.take(
                    ring, _sanitize_perm(perm[:m_t], m_t), axis=1,
                    mode="fill", fill_value=0,
                )
            new_rings.append(ring)
        la = jnp.take(
            last_active, _sanitize_perm(perm, last_active.shape[0]),
            axis=0, mode="fill", fill_value=0,
        )
        empty = (perm < 0) | (perm >= last_active.shape[0])
        last_active = jnp.where(empty, epoch, la)
        return tuple(new_rings), last_active

    if not with_acc:

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def compact_paged(rings, last_active, perm, epoch):
            return _compact_rings(rings, last_active, perm, epoch)

        return compact_paged

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def compact(acc, rings, last_active, perm, epoch):
        acc = repack(acc, perm)
        new_rings, last_active = _compact_rings(
            rings, last_active, perm, epoch
        )
        return acc, new_rings, last_active

    return compact


def pad_pow2_ids(ids, min_width: int = 8):
    """Pad a host id vector to the next pow-2 width with DROP_ID, so the
    evict/compact programs compile one executable per width bucket
    instead of one per victim count (same policy as
    QueryPlanCache.pad_ids)."""
    import numpy as np

    n = len(ids)
    width = max(min_width, 1 << max(0, (int(n) - 1).bit_length()))
    out = np.full(width, DROP_ID, dtype=np.int32)
    out[:n] = np.asarray(ids, dtype=np.int32)
    return out
