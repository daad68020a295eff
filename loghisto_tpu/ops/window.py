"""Fused window-merge + statistics kernels for the timewheel retention
store (window/store.py).

The log-bucket representation makes sliding windows almost free: interval
histograms merge *exactly* by elementwise addition (the same property the
mesh psum rides), so "p99 over the last W intervals" is ONE masked
reduction over the ring axis of a dense ``[slots, num_metrics,
num_buckets]`` tensor followed by the standard CDF scan of ops/stats.py —
no re-ingestion, no per-interval host loop, and a cost that depends on
the ring capacity, not the window length (which is what makes query
latency sublinear — effectively flat — in window size).

Two merge tiers:

  * ``window_merge`` — jnp masked ring-sum.  Works everywhere, and under
    a ("stream", "metric") mesh a metric-row-sharded ring partitions the
    reduction row-wise with zero collectives (the ring axis is local).
  * ``window_merge_pallas`` — metric-tiled Pallas kernel: grid over
    (metric tiles, ring slots) with the output block resident in VMEM
    across the slot sweep, so HBM traffic is ring-in + merged-out once —
    the bandwidth floor.  Single-device, TPU-targeted; interpret mode
    elsewhere so CI runs the same code path.

``window_stats`` composes either merge with ops/stats.py ``dense_stats``
into one jittable program: query(window) == one device dispatch.

Mesh-sharded state (PR 8): ``window_snapshot`` additionally runs
SHARD-LOCAL inside the sharded fused commit's ``shard_map`` program
(ops/commit.py) — the masked ring-sum and CDF scan are row-independent,
so each metric shard emits its own slice of the commit-time snapshot
payloads with zero collectives beyond the one cell-delta psum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from loghisto_tpu.config import PRECISION
from loghisto_tpu.ops.backend import default_interpret
from loghisto_tpu.ops.stats import dense_cdf, dense_stats

ROWS_TILE = 8  # int32 sublane tile


def window_merge(ring: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Merge the masked ring slots into one dense histogram.

    ring: int32 [slots, M, B]; mask: bool/int32 [slots].  Returns
    int32 [M, B] = sum over slots where mask is set — exact (histogram
    merges are elementwise adds).  One reduction over the ring axis;
    XLA partitions it row-parallel when the ring is metric-sharded.
    """
    keep = mask.astype(jnp.bool_)[:, None, None]
    return jnp.sum(jnp.where(keep, ring, 0), axis=0, dtype=jnp.int32)


def _merge_kernel(mask_ref, ring_ref, out_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(mask_ref[k] != 0)
    def _accumulate():
        out_ref[:] += ring_ref[0]


def window_merge_pallas(
    ring: jnp.ndarray,
    mask: jnp.ndarray,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pallas tier of window_merge: identical result, VMEM-resident
    output blocks.  The grid sweeps ring slots innermost per metric tile,
    so each [ROWS_TILE, B] output block is written to HBM exactly once
    however long the window is."""
    if interpret is None:
        interpret = default_interpret()
    slots, m, b = ring.shape
    m_pad = (m + ROWS_TILE - 1) // ROWS_TILE * ROWS_TILE
    if m_pad != m:
        ring = jnp.pad(ring, ((0, 0), (0, m_pad - m), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m_pad // ROWS_TILE, slots),
        in_specs=[
            # block last dim == the array dim (B is rarely 128-divisible:
            # 2*bucket_limit+1 is odd), which Mosaic accepts — see the
            # layout note in ops/pallas_kernels.py
            pl.BlockSpec((1, ROWS_TILE, b), lambda i, k, mk: (k, i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS_TILE, b), lambda i, k, mk: (i, 0)),
    )
    out = pl.pallas_call(
        _merge_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, b), jnp.int32),
        interpret=interpret,
    )(mask.astype(jnp.int32), ring)
    return out[:m]


def resolve_merge_path(path: str, platform: str, mesh: bool) -> str:
    """Shared dispatch policy for the window merge: "auto" is the jnp
    reduction everywhere.  The Pallas tier stays for explicit selection,
    single-device only (Pallas inside shard_map is off the table).  On
    a v5e it cannot serve the fused commit at 10k x 8193: the TPU's
    default layout of an int32 [S, M, B] ring puts the bucket axis
    major, the kernel needs it minor, and the layout-conversion copy of
    every ring it reads took 7.75 GB of HBM next to 7.9 GB of rings
    (PR 21's chip run: RESOURCE_EXHAUSTED loading the commit program)."""
    del platform
    if path not in ("auto", "jnp", "pallas"):
        raise ValueError(
            f"merge_path={path!r}: expected 'auto', 'jnp', or 'pallas'"
        )
    if path == "auto":
        return "jnp"
    if path == "pallas" and mesh:
        raise ValueError("merge_path='pallas' is single-device; use jnp "
                         "with a mesh")
    return path


def window_stats(
    ring: jnp.ndarray,
    mask: jnp.ndarray,
    ps: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
    merge_path: str = "jnp",
) -> dict[str, jnp.ndarray]:
    """Fused window query: masked ring merge + full CDF-scan statistics
    in one traceable program — counts [M], sums [M], percentiles [M, P]
    for every metric over the selected window."""
    if merge_path == "pallas":
        merged = window_merge_pallas(ring, mask)
    else:
        merged = window_merge(ring, mask)
    return dense_stats(merged, ps, bucket_limit, precision)


def make_window_stats_fn(
    bucket_limit: int,
    precision: int = PRECISION,
    merge_path: str = "jnp",
):
    """Jitted f(ring, mask, ps) -> stats, one executable per ring shape
    (one tier = one shape, so a wheel compiles one program per tier)."""
    # a named function, not a functools.partial: a partial's program is
    # called "_unknown" in a profiler trace
    def masked_window_stats(ring, mask, ps):
        return window_stats(ring, mask, ps, bucket_limit, precision,
                            merge_path)

    return jax.jit(masked_window_stats)


def window_snapshot(
    ring: jnp.ndarray,
    masks: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
    merge_path: str = "jnp",
) -> dict[str, jnp.ndarray]:
    """Commit-time snapshot payloads for a tier: merge each masked view
    of the ring and take its exact bucket CDF in one program.

    ring: int32 [slots, M, B]; masks: bool [V, slots] — one row per
    snapshot view (the full written span plus any pinned windows).
    Returns cdf int32 [V, M, B], counts int32 [V, M], sums f32 [V, M].

    Because cumsum is linear, the CDF of a merged window equals the sum
    of per-slot CDFs — merging first is just the cheaper order.  The
    per-view merge reuses the same window_merge the query path jits, so
    snapshot contents are bit-identical to a direct recompute over the
    identical mask (the parity contract tests/test_query_engine.py pins).
    """

    def one_view(mask):
        if merge_path == "pallas":
            merged = window_merge_pallas(ring, mask)
        else:
            merged = window_merge(ring, mask)
        return dense_cdf(merged, bucket_limit, precision)

    out = jax.vmap(one_view)(masks.astype(jnp.bool_))
    return out


def make_window_snapshot_fn(
    bucket_limit: int,
    precision: int = PRECISION,
    merge_path: str = "jnp",
):
    """Jitted f(ring, masks) -> snapshot payload dict.  One executable
    per (ring shape, view count); view counts only change when a new
    window is pinned, so steady state never retraces."""
    def masked_window_snapshot(ring, masks):
        return window_snapshot(ring, masks, bucket_limit, precision,
                               merge_path)

    return jax.jit(masked_window_snapshot)
