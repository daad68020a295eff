"""TPU aggregation engine: dense device accumulators, distributed merges,
and the TPUAggregator runtime that gates them behind the subscription
boundary.

North-star architecture (BASELINE.json): host callers keep using
``MetricSystem``; a TPUAggregator ships raw samples (or pre-bucketed
interval histograms) to the device, where

  * ingest is a fused compress -> scatter-add (ops/ingest.py),
  * cross-stream / cross-host merge is a ``psum`` over the mesh's stream
    axis — the elementwise-additive merge the log-bucket representation
    makes exact,
  * percentile extraction is the CDF scan of ops/stats.py, row-parallel
    over the metric axis.

The distributed step below runs under ``shard_map`` on a
("stream", "metric") mesh: sample shards enter per device, local dense
histograms are psum-merged across the stream axis, folded into the
metric-sharded accumulator, and per-metric statistics come back sharded by
metric rows.  This is the §5.7/§5.8 slot of SURVEY.md — the capability the
reference (a single-process Go library) does not have.
"""

from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Dict, Mapping, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from loghisto_tpu.config import DEFAULT_PERCENTILES, PRECISION, MetricConfig
from loghisto_tpu.metrics import MetricSystem, ProcessedMetricSet, RawMetricSet
from loghisto_tpu.channel import Channel, ChannelClosed
from loghisto_tpu.obs.spans import NULL_RECORDER
from loghisto_tpu.ops.ingest import (
    make_ingest_fn,
    make_weighted_ingest_fn,
    sanitize_ids,
)
from loghisto_tpu.ops.dispatch import resolve_ingest_path
from loghisto_tpu.ops.stats import dense_stats, dense_stats_np
from loghisto_tpu.parallel.mesh import METRIC_AXIS, STREAM_AXIS
from loghisto_tpu.registry import MetricRegistry, RegistryFullError

# Default registry-growth headroom: max_metrics = num_metrics * this when
# unspecified.  Shared with bench.py's path resolution so the benchmarked
# default kernel tracks the default-configured aggregator's exactly.
DEFAULT_GROWTH_FACTOR = 8

# Fixed launch width for weighted cell merges (bridge intervals, preagg
# flushes): one compiled executable serves every merge, and a 10k-metric
# interval is a handful of launches instead of the round-1 hundreds.
_MERGE_CHUNK = 1 << 16

# Minimum raw-item size the transport="auto" density probe runs on: the
# unique-cell ratio of a small batch says nothing about skew, and the
# probe itself (one host compress + unique over this prefix) must stay
# negligible next to shipping the batch.
_PROBE_SAMPLES = 1 << 16


class IngestStagingRing:
    """Bounded async H2D staging for the transfer worker — the
    CellStagingRing idea (ops/commit.py) applied to the raw
    (ids, values) wire.

    ``stage()`` copies a chunk into fresh host arrays, pads the tail
    with id -1 (every ingest kernel drops it), and issues the async
    ``device_put`` — which returns before the H2D copy completes, so the
    upload of chunk i overlaps the donated ingest dispatches still
    consuming chunk i-1.  A host buffer handed to ``device_put`` is
    never written again (the upload may alias it or read it late).
    ``depth`` bounds the uploads in flight: before the (depth+1)-th is
    issued, the oldest is ``block_until_ready``'d.  Depth 2 is the
    minimum for overlap; 3 keeps one filling, one in flight, one being
    consumed."""

    def __init__(self, slot_samples: int, depth: int = 3,
                 chunk_samples: Optional[int] = None):
        if depth < 2:
            raise ValueError(f"ring depth must be >= 2, got {depth}")
        if slot_samples < 1:
            raise ValueError(f"slot_samples must be >= 1, got {slot_samples}")
        self.slot_samples = int(slot_samples)
        # upload quantum: a partial chunk uploads only its prefix
        # rounded up to this (the dispatch loop consumes chunk_samples
        # slices) — a 1-batch item must not pay the full 8-batch slot on
        # the wire.  Default = whole slot.
        self.chunk_samples = int(chunk_samples or slot_samples)
        if not 1 <= self.chunk_samples <= self.slot_samples:
            raise ValueError(
                f"chunk_samples must be in [1, {self.slot_samples}]; "
                f"got {self.chunk_samples}"
            )
        self.depth = int(depth)
        self._inflight: list[Optional[tuple]] = [None] * depth
        self._next = 0
        self.uploads = 0
        self.bytes_uploaded = 0

    def stage(self, ids: np.ndarray, values: np.ndarray):
        """Copy one chunk (<= slot_samples) into fresh host arrays and
        start its async upload; returns the (ids, values) device
        arrays."""
        n = len(ids)
        if n > self.slot_samples:
            raise ValueError(f"chunk of {n} exceeds slot {self.slot_samples}")
        i = self._next
        self._next = (i + 1) % self.depth
        prev = self._inflight[i]
        if prev is not None:
            self._inflight[i] = None
            for arr in prev:
                try:
                    arr.block_until_ready()
                except Exception:
                    # the old transfer errored — its batch was already
                    # requeued/shed by the failure path; the slot is free
                    pass
        chunk = self.chunk_samples
        padded = min(self.slot_samples, -(-n // chunk) * chunk)
        host_ids = np.full(padded, -1, dtype=np.int32)
        host_ids[:n] = ids
        host_values = np.zeros(padded, dtype=np.float32)
        host_values[:n] = values
        ids_dev = jax.device_put(host_ids)
        values_dev = jax.device_put(host_values)
        self._inflight[i] = (ids_dev, values_dev)
        self.uploads += 1
        self.bytes_uploaded += padded * (
            host_ids.itemsize + host_values.itemsize
        )
        return ids_dev, values_dev

    def drain(self) -> None:
        """Block until EVERY in-flight async upload has completed (or
        surfaced its failure).  ``stage()`` only waits for the oldest
        upload, so with the r13 double-buffered dispatch loop up to
        ``depth`` uploads can still be in flight when the pipeline goes
        quiet — ``close()`` drains them all before the final interval
        commits.
        Failed transfers are swallowed like in ``stage()``: their batch
        was already requeued/shed by the failure path."""
        for i, prev in enumerate(self._inflight):
            if prev is None:
                continue
            self._inflight[i] = None
            for arr in prev:
                try:
                    arr.block_until_ready()
                except Exception:
                    pass


def local_histogram_fold(
    acc_local: jnp.ndarray,
    ids: jnp.ndarray,
    values: jnp.ndarray,
    rows_per_shard: int,
    bucket_limit: int,
    precision: int = PRECISION,
    ingest_path: str = "scatter",
) -> jnp.ndarray:
    """The sharded-ingest core, shared by every shard_map step: offset ids
    into this metric shard's row range (ids below it go negative, so
    sanitize before drop-mode scatter or they'd wrap to the last row),
    bucket the local sample shard, psum the dense histograms across the
    stream axis, and fold into the accumulator.  Must run inside
    shard_map on a ("stream", "metric") mesh.

    ``ingest_path`` names a CONCRETE per-batch kernel ("scatter", "sort",
    "hybrid", "matmul" — resolve "auto" outside the traced region): the
    duplicate-serialization economics that drive single-chip dispatch
    apply unchanged to the per-device local fold (a Zipf stream
    concentrates each shard's in-range samples on its hot rows), so the
    mesh path uses the same dispatched kernels.  Out-of-shard ids are
    sanitized far out of range, which every kernel drops."""
    from loghisto_tpu.ops.dispatch import ingest_step_fn

    shard = jax.lax.axis_index(METRIC_AXIS)
    local_ids = sanitize_ids(ids - shard * rows_per_shard)
    hist = ingest_step_fn(ingest_path)(
        jnp.zeros_like(acc_local), local_ids, values, bucket_limit,
        precision,
    )
    hist = jax.lax.psum(hist, STREAM_AXIS)
    return acc_local + hist


def make_distributed_step(
    mesh: Mesh,
    num_metrics: int,
    bucket_limit: int,
    percentile_values,
    precision: int = PRECISION,
    ingest_path: str = "auto",
    batch_size: int | None = None,
):
    """Build the jitted full aggregation step over a ("stream", "metric")
    mesh.

    Returns f(acc, ids, values) -> (new_acc, stats) where
      acc    int32 [num_metrics, num_buckets], sharded over metric rows
      ids    int32 [N], sharded over the stream axis
      values float32 [N], sharded over the stream axis
      stats  {"counts": [M] (metric-sharded), "sums": [M],
              "percentiles": [M, P]}

    Per device: bucket the local sample shard into a local dense histogram
    (dropping ids outside this device's metric rows), psum across the
    stream axis, fold into the accumulator, then extract statistics for
    the local metric rows.  All collectives are XLA-native and ride ICI.
    """
    n_metric = mesh.shape[METRIC_AXIS]
    if num_metrics % n_metric:
        raise ValueError(
            f"num_metrics={num_metrics} not divisible by metric axis "
            f"size {n_metric}"
        )
    rows_per_shard = num_metrics // n_metric
    ps = jnp.asarray(percentile_values, dtype=jnp.float32)
    # resolve dispatch OUTSIDE the traced region: choose on the global
    # metric count (duplicate-heaviness tracks global hotness), validate
    # on it too (stricter than the local shard shape, never looser).
    # mesh=True: auto must not pick pallas inside shard_map (ADVICE r2);
    # batch_size (the caller's per-step bound, when known) guards the
    # float32-exactness preconditions at selection time, not trace time.
    ingest_path = resolve_ingest_path(
        ingest_path, num_metrics,
        2 * bucket_limit + 1, mesh.devices.flat[0].platform,
        batch_size=batch_size, mesh=True,
    )

    def local_step(acc_local, ids, values):
        acc_local = local_histogram_fold(
            acc_local, ids, values, rows_per_shard, bucket_limit, precision,
            ingest_path=ingest_path,
        )
        stats = dense_stats(acc_local, ps, bucket_limit, precision)
        return acc_local, stats

    stats_specs = {
        "counts": P(METRIC_AXIS),
        "sums": P(METRIC_AXIS),
        "percentiles": P(METRIC_AXIS, None),
    }
    step = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(METRIC_AXIS, None), P(STREAM_AXIS), P(STREAM_AXIS)),
        out_specs=(P(METRIC_AXIS, None), stats_specs),
    )
    return jax.jit(step, donate_argnums=0)


def make_sharded_accumulator(
    mesh: Mesh, num_metrics: int, num_buckets: int
) -> jnp.ndarray:
    """Zero accumulator laid out metric-sharded, stream-replicated
    (the canonical acc layout from parallel.mesh, shared with the
    sharded fused commit and checkpoint restore).  global_put keeps
    the placement collective-free when the mesh spans real
    jax.distributed processes (a plain device_put onto a
    non-addressable sharding runs an assert-equal collective the CPU
    drill backend lacks)."""
    import numpy as np

    from loghisto_tpu.parallel.mesh import acc_sharding
    from loghisto_tpu.parallel.multihost import global_put

    return global_put(
        np.zeros((num_metrics, num_buckets), dtype=np.int32),
        acc_sharding(mesh),
    )


def make_interval_distributed_step(
    mesh: Mesh,
    num_metrics: int,
    bucket_limit: int,
    percentile_values,
    precision: int = PRECISION,
    ingest_path: str = "auto",
    batch_size: int | None = None,
):
    """Interval-amortized distributed aggregation (VERDICT r3 item 3).

    ``make_distributed_step`` psums the full dense [rows, buckets]
    histogram across the stream axis EVERY batch — MESH_SCALE_r3.json
    measured that collective at 7.8x a single-device step for pure
    stream sharding.  But histogram merges are associative: nothing
    requires the cross-stream reduction before the interval boundary.
    Here each device folds batches into its own (stream, metric) partial
    with ZERO collectives, and the stream-axis psum runs once per
    ``collect`` — with B batches/interval the collective amortizes to
    1/B of the per-batch design's volume.

    Returns (ingest, collect, make_partial):

      make_partial() -> int32 [n_stream, num_metrics, num_buckets],
          sharded P(stream, metric, None) — each device owns one
          [1, rows_per_shard, num_buckets] block, so the partial costs
          one accumulator's worth of HBM per device, not n_stream.
      ingest(partial, ids, values) -> partial
          Collective-free per-batch fold (donated partial; ids/values
          stream-sharded like the per-batch design).
      collect(acc, partial) -> (acc, fresh_partial, stats)
          One psum over the stream axis, fold into the metric-sharded
          accumulator, stats on the merged rows; returns a zeroed
          partial so the caller just rebinds both carries.  r13: the
          collective is issued ASYNC — ``collect.start(acc, partial) ->
          (acc, stats)`` exposes the raw program, whose outputs no
          longer include the fresh partial, so folding the next batch
          into an independent ``make_partial()`` overlaps the psum
          instead of serializing behind it.

    Overflow contract (same int32 budget as the per-batch design): the
    partials and the accumulator are int32, and the worst case
    concentrates every sample in one cell — callers must collect before
    an interval ingests 2^31 samples globally (at the 1e9/s north-star
    rate that is a 2-second interval).  TPUAggregator enforces this with
    its host int64 spill; raw step-factory callers own the bound, like
    run_firehose's early-close guard.
    """
    n_metric = mesh.shape[METRIC_AXIS]
    n_stream = mesh.shape[STREAM_AXIS]
    if num_metrics % n_metric:
        raise ValueError(
            f"num_metrics={num_metrics} not divisible by metric axis "
            f"size {n_metric}"
        )
    rows_per_shard = num_metrics // n_metric
    ps = jnp.asarray(percentile_values, dtype=jnp.float32)
    ingest_path = resolve_ingest_path(
        ingest_path, num_metrics,
        2 * bucket_limit + 1, mesh.devices.flat[0].platform,
        batch_size=batch_size, mesh=True,
    )

    def local_ingest(partial_local, ids, values):
        from loghisto_tpu.ops.dispatch import ingest_step_fn

        shard = jax.lax.axis_index(METRIC_AXIS)
        local_ids = sanitize_ids(ids - shard * rows_per_shard)
        folded = ingest_step_fn(ingest_path)(
            partial_local[0], local_ids, values, bucket_limit, precision
        )
        return folded[None]

    ingest = jax.jit(
        shard_map(
            local_ingest,
            mesh=mesh,
            in_specs=(
                P(STREAM_AXIS, METRIC_AXIS, None),
                P(STREAM_AXIS),
                P(STREAM_AXIS),
            ),
            out_specs=P(STREAM_AXIS, METRIC_AXIS, None),
        ),
        donate_argnums=0,
    )

    def local_collect(acc_local, partial_local):
        merged = jax.lax.psum(partial_local[0], STREAM_AXIS)
        acc_local = acc_local + merged
        stats = dense_stats(acc_local, ps, bucket_limit, precision)
        return acc_local, stats

    stats_specs = {
        "counts": P(METRIC_AXIS),
        "sums": P(METRIC_AXIS),
        "percentiles": P(METRIC_AXIS, None),
    }
    # The psum program no longer RETURNS the fresh partial (pre-r13 it
    # zeroed the donated one inside the same program): a fresh partial
    # that is an output of the collect would make the next interval's
    # first fold a data-dependent consumer of the collective, so XLA
    # would serialize batch folds behind the psum.  Allocating it
    # independently (make_partial below) breaks that edge — issuing
    # ``collect_start`` and immediately folding the next batch overlaps
    # the stream-axis collective with shard-local work.  Bit-identity is
    # untouched: the int32 psum is order-independent (PR-8 invariant)
    # and a zero partial is a zero partial wherever it comes from.
    collect_start = jax.jit(
        shard_map(
            local_collect,
            mesh=mesh,
            in_specs=(
                P(METRIC_AXIS, None),
                P(STREAM_AXIS, METRIC_AXIS, None),
            ),
            out_specs=(
                P(METRIC_AXIS, None),
                stats_specs,
            ),
        ),
        donate_argnums=(0, 1),
    )

    def make_partial() -> jnp.ndarray:
        sharding = NamedSharding(mesh, P(STREAM_AXIS, METRIC_AXIS, None))
        return jax.device_put(
            jnp.zeros(
                (n_stream, num_metrics, 2 * bucket_limit + 1),
                dtype=jnp.int32,
            ),
            sharding,
        )

    def collect(acc, partial):
        """Compat form of the interval collect: issue the async psum
        program (donates acc and partial) and hand back the pre-r13
        (acc, fresh_partial, stats) triple.  The returned arrays are
        un-fetched jax futures; callers that want the r13 overlap use
        ``collect.start(acc, partial) -> (acc, stats)`` directly, grab a
        fresh partial from make_partial(), and fold the next batch while
        the collective is still in flight."""
        acc, stats = collect_start(acc, partial)
        return acc, make_partial(), stats

    collect.start = collect_start

    return ingest, collect, make_partial


class TPUAggregator:
    """Device-tier metric engine (the reference has no equivalent; this is
    the TPU execution backend the north star adds behind the subscription
    boundary).

    Two ways in:
      * `record_batch(ids, values)` / `record(name, value)` — direct
        firehose ingestion; batches buffer on host and flush to the device
        as fused compress+scatter-add steps.
      * `attach(metric_system)` — subscribe to the host MetricSystem's raw
        broadcast and merge each interval's pre-bucketed histograms into
        the device accumulator (weighted scatter-add), so existing callers
        get device-side percentile extraction without code changes.

    `collect()` extracts all statistics on device (one CDF-scan program),
    resets the accumulator, folds lifetime aggregates on host (python ints
    — immune to int32 overflow across intervals), and returns a
    ProcessedMetricSet with the standard naming scheme.
    """

    def __init__(
        self,
        num_metrics: int = 1024,
        config: MetricConfig = MetricConfig(),
        percentiles: Mapping[str, float] = DEFAULT_PERCENTILES,
        registry: Optional[MetricRegistry] = None,
        batch_size: int = 1 << 16,
        mesh: Optional[Mesh] = None,
        native_staging: bool = False,
        ingest_path: str = "auto",
        on_registry_full: str = "grow",
        max_metrics: Optional[int] = None,
        spill_threshold: int = 1 << 30,
        transport: str = "auto",
        storage: str = "auto",
        paged_config=None,
    ):
        """When `mesh` is given (a ("stream","metric") mesh from
        parallel.mesh.make_mesh), the dense accumulator is laid out
        metric-row-sharded across the mesh and every jitted step runs
        SPMD — XLA partitions the scatter-adds and the CDF scan row-wise
        and inserts the collectives.  num_metrics must divide evenly by
        the metric-axis size.

        `native_staging=True` stages record_batch samples in the C++
        lock-striped buffer (loghisto_tpu._native) instead of Python
        lists — writers release the GIL in the C call, and overflow sheds
        with an exposed drop counter.  Requires the native library; falls
        back (with a log line) when unavailable.

        `ingest_path` selects the device accumulation kernel:
          * "auto"     — (default) pick the measured-fastest kernel for
            (num_metrics, num_buckets, platform) via ops/dispatch.py
          * "scatter"  — XLA scatter-add (works everywhere)
          * "matmul"   — one-hot MXU matmul (small metric counts)
          * "sort"     — sort-deduplicated conflict-free scatter
            (ops/sort_ingest.py; built for TPU scatter semantics)
          * "multirow" — metric-tiled Pallas kernel (sorted/block-padded;
            single-device only, TPU-targeted, interpret-mode elsewhere)
        All three are bit-identical (tests/test_fast_paths.py,
        tests/test_pallas_multirow.py); they differ only in speed per
        configuration — benchmarks/device_paths.py measures them.

        `on_registry_full` defines the name-cardinality policy when a new
        name arrives with the registry at capacity (the reference admits
        new names forever, metrics.go:281-294):
          * "grow"  — (default) double the accumulator's metric rows (and
            the registry capacity) up to `max_metrics` (default 8x the
            initial num_metrics; doubling preserves mesh divisibility).
            Past max_metrics, samples for unseen names are shed with a
            counter (`tpu.RegistryShedSamples` gauge) — the library-wide
            shed-don't-block degradation (SURVEY.md §5.3).
          * "error" — raise RegistryFullError (round-1 behavior).

        `spill_threshold` bounds int32 accumulator overflow (SURVEY.md §7
        hard part (b)): once a single interval has ingested this many
        samples (the worst case concentrates ALL of them in one cell),
        the device accumulator is folded into a host int64 spill tensor
        and reset, without closing the interval.  collect() merges the
        spill back in and computes that interval's statistics in exact
        int64 on host.  The default (2^30) can never wrap: 2^30 ingested
        samples + one further flush round cannot reach 2^31 in any cell.

        `transport` picks how flush() ships staged samples to the device:
          * "raw"    — ship (id, value) pairs; the device kernel does the
            compression (8 bytes/sample on the wire).
          * "preagg" — compress + dedup on host first (C++ hash, the
            same codec bit-for-bit) and ship unique (id, bucket, count)
            cells via the weighted scatter — the wire carries O(unique
            cells) instead of O(samples), which for Zipf-shaped load is
            orders of magnitude less.  This is the same
            local-aggregate-before-network design as the multi-host psum
            merge, applied to the host->device hop.
          * "sparse" — ship raw staging unchanged, but fold each FLUSH
            on host (parallel native tier, NumPy fallback) into packed
            (id, bucket, count) triples and merge them with the weighted
            scatter — the raw transport's zero record-time cost with the
            preagg transport's O(unique cells) wire.  The fold runs on
            the transfer worker thread, overlapped with device work.
          * "auto"   — (default) start on "raw"; the transfer worker
            probes the first large batch's unique-cell density and
            switches to "sparse" when the load is skewed enough to pay
            for the fold (ops/dispatch.py SPARSE_DENSITY_CROSSOVER,
            capture-overridable).  "preagg" is never auto-picked: its
            record-time fold taxes producer threads, which only wins
            when producers aren't the bottleneck — a property no
            flush-side probe can observe.

        `storage` picks the accumulator backend (r14):
          * "dense" — the donated [M, B] int32 tensor (every row pays
            full bucket capacity in HBM and commit bytes).
          * "paged" — page pool + per-row page table + per-metric
            variable-resolution codecs (loghisto_tpu/paging.py): HBM
            and commit H2D track OCCUPIED buckets.  Requires the
            sparse packed-triple transport (pinned automatically when
            transport="auto"; explicit "raw"/"preagg" raises) and a
            single device (no mesh).
          * "auto"  — (default) resolve_storage_path: paged at high
            metric cardinality (PAGED_MIN_METRICS rows) where the
            dense tensor's HBM cost bites, dense below it — the
            declining reason lands in `storage_reason`.
        `paged_config` takes a paging.PagedStoreConfig (pool size,
        codec policy, overflow row)."""
        self.config = config
        self.num_metrics = num_metrics
        # explicit None check: an empty registry is falsy (it has __len__),
        # so `registry or ...` would silently discard a caller's registry
        self.registry = (
            registry if registry is not None
            else MetricRegistry(capacity=num_metrics)
        )
        if self.registry.capacity > num_metrics:
            raise ValueError(
                f"registry capacity {self.registry.capacity} exceeds "
                f"num_metrics {num_metrics}: names beyond the accumulator "
                "rows could never be aggregated"
            )
        for label in percentiles:
            try:
                if not isinstance(label % "name", str):
                    raise TypeError("renders to non-string")
            except (TypeError, ValueError) as e:
                raise ValueError(
                    f"percentile label {label!r} is not a valid %-format "
                    f"template for a metric name: {e}"
                ) from None
        self.percentiles = dict(percentiles)
        self.batch_size = batch_size

        # Two-lock split so producers never stall on device work
        # (SURVEY.md §7 hard part (c)):
        #   _lock     — host staging state (_pending_*, _native_staged);
        #               held only for list appends/drains, never across a
        #               device call.
        #   _dev_lock — device state (_acc, _spill, _interval_ingested,
        #               growth); held across device dispatches.
        # Never nested: every method releases one before taking the other,
        # so lock-ordering deadlocks are impossible by construction.
        self._lock = threading.Lock()
        self._dev_lock = threading.Lock()
        self._pending_ids: list[np.ndarray] = []
        self._pending_values: list[np.ndarray] = []
        self._pending_count = 0

        self._native_buf = None
        self._native_staged = 0
        # Worker-side re-buffer for batches a device failure (or the
        # retry cooldown) bounced back: appended chronologically by the
        # single FIFO transfer worker, so everything here is OLDER than
        # everything in _pending_* — flush drains requeue-first and the
        # oldest-first shed policy stays honest.  Guarded by _lock.
        self._requeue_ids: list[np.ndarray] = []
        self._requeue_values: list[np.ndarray] = []
        self._requeue_count = 0
        # Transfer pipeline (r6 tentpole): flush() is enqueue-only; this
        # FIFO + condition pair feeds a single transfer worker thread
        # that stages slots, issues async device_puts, and runs the
        # donated dispatches — so producers never block on device work,
        # and the upload of chunk k+1 overlaps the dispatch of chunk k.
        self._xfer_cv = threading.Condition()
        self._xfer_queue: collections.deque = collections.deque()
        self._xfer_queued_samples = 0  # samples sitting in the queue
        self._xfer_active = False  # worker is mid-item
        self._xfer_thread: Optional[threading.Thread] = None
        self._xfer_stop = False
        self._staging_ring: Optional[IngestStagingRing] = None
        self.staging_depth = 3
        # wire accounting for bytes/sample reporting (bench satellite)
        self._xfer_uploads = 0
        self._xfer_bytes = 0
        self._xfer_samples_shipped = 0
        # host-side retry buffer bound when the device is unreachable
        self.max_pending_samples = 32 * batch_size
        self.retry_cooldown = 1.0  # seconds between device retry attempts
        self._shed_samples = 0
        # guards _shed_samples, which is incremented from both the staging
        # side (_bound_pending_locked, under _lock) and the device side
        # (_on_device_failure_locked, under _dev_lock)
        self._shed_lock = threading.Lock()
        self._device_down_until = 0.0
        self._interval_ingested = 0  # samples in the live accumulator
        # immutable (epoch, cdf/counts/sums) handle over the live
        # accumulator, published by the fused committer's snapshot
        # dispatch; None whenever the accumulator was reset, grown,
        # spilled, or rebuilt — readers must treat None as "recompute"
        self.stats_snapshot = None
        # resilience (ISSUE 10), installed by TPUMetricSystem: the
        # supervisor ledgers bridge/worker restarts, the breaker counts
        # device failures (ONE count point: _on_device_failure_locked),
        # the injector scripts chaos faults (None = one attribute test
        # per hook site)
        self.supervisor = None
        self.device_breaker = None
        self.fault_injector = None
        # observability (ISSUE 9): flush/drain spans; swapped for a real
        # ring by TPUMetricSystem(observability=...)
        self.obs_recorder = NULL_RECORDER

        if on_registry_full not in ("grow", "error"):
            raise ValueError(
                f"on_registry_full={on_registry_full!r}: expected 'grow' "
                "or 'error'"
            )
        self.on_registry_full = on_registry_full
        self.max_metrics = (
            int(max_metrics) if max_metrics is not None
            else num_metrics * DEFAULT_GROWTH_FACTOR
        )
        if self.max_metrics < num_metrics:
            raise ValueError(
                f"max_metrics {self.max_metrics} < num_metrics {num_metrics}"
            )
        if not 0 < spill_threshold <= 1 << 30:
            raise ValueError(
                "spill_threshold must be in (0, 2^30]: the overflow "
                "guarantee needs threshold + one ingest chunk < 2^31"
            )
        if spill_threshold + batch_size >= 1 << 31:
            raise ValueError(
                f"spill_threshold {spill_threshold} + batch_size "
                f"{batch_size} >= 2^31: a single chunk between spill "
                "checks could wrap an int32 cell"
            )
        self.spill_threshold = int(spill_threshold)
        if ingest_path in ("sort", "sortscan", "matmul", "hybrid", "pallas"):
            # validate explicit choices BEFORE the accumulator allocation
            # below — the combined-key bound failing after a multi-GB
            # jnp.zeros is a worse failure mode than a raise inside the
            # traced ingest, which flush's shed-don't-block handling would
            # mask as a down device (platform is irrelevant here)
            resolve_ingest_path(
                ingest_path, num_metrics, config.num_buckets, "any",
                guard_metrics=self.max_metrics, batch_size=batch_size,
            )
        # int64 host fold of pre-spill interval counts (canonical dense
        # layout); engaged only when an interval exceeds spill_threshold
        self._spill: Optional[np.ndarray] = None
        self._spilled_samples = 0  # this interval's spilled count
        self._registry_shed_samples = 0  # lifetime, past-max_metrics names
        if native_staging:
            from loghisto_tpu import _native

            if _native.available():
                # 16 shards x 4*batch_size x 12B ~= 48 MB at the default
                # batch_size; scale with the workload, don't floor at 1M
                self._native_buf = _native.NativeIngestBuffer(
                    num_shards=16,
                    capacity_per_shard=max(batch_size * 4, 1 << 16),
                )
            else:
                import logging

                logging.getLogger("loghisto_tpu").warning(
                    "native staging requested but unavailable (%s); using "
                    "Python staging", _native.build_error(),
                )

        if transport not in ("auto", "raw", "preagg", "sparse"):
            raise ValueError(
                f"transport={transport!r}: expected 'auto', 'raw', "
                "'preagg', or 'sparse'"
            )
        # "auto" (r6): start on raw and let the transfer worker probe
        # the first large batch's cell density — skewed load switches to
        # the sparse transport at runtime (ops.dispatch.choose_transport
        # / SPARSE_DENSITY_CROSSOVER).  "preagg" stays an explicit
        # opt-in: its record-time fold trades producer-thread CPU for
        # flush latency, a workload property no flush-side probe sees.
        # storage backend (r14/r17): resolved BEFORE the transport
        # rewrite below because the storage choice pins the transport —
        # paged with the direct-to-paged fused kernel (r17) keeps RAW
        # (compress/encode/translate all run on device), paged without
        # it pins sparse (the page-table translate rides the host fold).
        from loghisto_tpu.ops.dispatch import (
            fused_paged_incapability,
            resolve_storage_path,
        )

        backend = jax.default_backend()
        self.fused_paged_reason = fused_paged_incapability(
            num_metrics, config.num_buckets, batch_size=batch_size,
            mesh=mesh is not None, transport=transport, platform=backend,
            crossover=(ingest_path == "auto"), mesh_obj=mesh,
        )
        fused_paged_ok = (
            self.fused_paged_reason is None
            and ingest_path in ("auto", "fused")
        )
        self.storage, self.storage_reason = resolve_storage_path(
            storage, num_metrics, config.num_buckets,
            backend, mesh=mesh is not None,
            transport=transport, fused_ok=fused_paged_ok,
            mesh_obj=mesh,
        )
        self.paged = None
        self.fused_paged = self.storage == "paged" and fused_paged_ok
        if self.storage == "paged":
            # fused path ingests raw; host-fold fallback pins sparse
            # (auto pins either way; incompatible explicit transports
            # raised inside resolve_storage_path)
            transport = "raw" if self.fused_paged else "sparse"
        self._transport_auto = transport == "auto"
        self.probe_density: Optional[float] = None
        if transport == "auto":
            transport = "raw"
        self.transport = transport
        self._cell_store = None
        # watermark: ship cells to the device mid-interval once the host
        # store holds this many (bounds host memory at ~16B/cell)
        self.max_host_cells = 1 << 22
        if transport == "preagg":
            from loghisto_tpu import _native as _nat

            # Sharded + double-buffered (VERDICT r2 item 2): producers
            # fold into per-thread shards at record time (the C fold runs
            # with the GIL released, so writer threads aggregate in
            # parallel), and draining swaps buffers per shard so the
            # O(capacity) scan never blocks ingest.  backend="auto"
            # degrades to the pure-NumPy store when no compiler built the
            # native library — preagg no longer requires one (r6).
            self._cell_store = _nat.ShardedCellStore(
                config.bucket_limit, config.precision, backend="auto"
            )
            if self._native_buf is not None:
                import logging

                logging.getLogger("loghisto_tpu").info(
                    "preagg transport folds samples into the cell store "
                    "at record time; the native staging buffer is unused"
                )
                self._native_buf = None

        self.mesh = mesh
        if mesh is not None:
            n_metric = mesh.shape[METRIC_AXIS]
            if num_metrics % n_metric:
                raise ValueError(
                    f"num_metrics={num_metrics} not divisible by the mesh "
                    f"metric axis ({n_metric})"
                )
        if self.storage == "paged":
            from loghisto_tpu.paging import PagedStore, PagedStoreConfig

            if ingest_path == "multirow":
                raise ValueError(
                    "ingest_path='multirow' needs the dense lane-padded "
                    "accumulator; paged storage keeps none (every paged "
                    "commit rides the packed sparse-triple scatter)"
                )
            # r18: a mesh shards the store itself — per-shard page
            # arenas, shard-local translate/scatter inside one
            # shard_map (the capability table's relaxed "mesh shape:"
            # edges pre-screened the divisibility constraints)
            self.paged = PagedStore(
                num_metrics,
                config.bucket_limit,
                config.precision,
                config=paged_config or PagedStoreConfig(),
                mesh=mesh,
            )
            # no dense [M, B] tensor exists in paged mode — the pool +
            # page table ARE the accumulator.  Every _acc touch below is
            # behind a `self.paged is not None` branch.
            self._acc = None
            if self.fused_paged:
                ingest_path = "fused_paged"
            elif ingest_path == "fused":
                raise ValueError(
                    "ingest_path='fused' with paged storage needs the "
                    "direct-to-paged fused kernel: "
                    f"{self.fused_paged_reason}"
                )
        elif mesh is not None:
            self._acc = make_sharded_accumulator(
                mesh, num_metrics, config.num_buckets
            )
        else:
            self._acc = jnp.zeros(
                (num_metrics, config.num_buckets), dtype=jnp.int32
            )
        if ingest_path == "auto":
            platform = (
                mesh.devices.flat[0].platform
                if mesh is not None
                else jax.default_backend()
            )
            # shared guard policy: growth can take the row space to
            # max_metrics, so auto validates shapes against the cap and
            # must not pick a kernel the grown shape would invalidate
            ingest_path = resolve_ingest_path(
                "auto", num_metrics, config.num_buckets, platform,
                guard_metrics=self.max_metrics, batch_size=batch_size,
                mesh=mesh is not None,
            )
        # identity for dense-layout paths; multirow slices its lane padding
        self._finalize_acc = lambda a: a
        # per-path zero-accumulator factory (layout differs by path)
        self._make_acc = self._fresh_dense_acc
        if ingest_path == "scatter":
            self._ingest = make_ingest_fn(
                config.bucket_limit, config.precision
            )
        elif ingest_path == "matmul":
            from loghisto_tpu.ops.matmul_hist import make_matmul_ingest_fn

            self._ingest = make_matmul_ingest_fn(
                config.bucket_limit, config.precision
            )
        elif ingest_path == "hybrid":
            from loghisto_tpu.ops.hybrid_hist import make_hybrid_ingest_fn

            self._ingest = make_hybrid_ingest_fn(
                config.bucket_limit, config.precision
            )
        elif ingest_path == "sort":
            # shape already validated (pre-allocation, against max_metrics)
            from loghisto_tpu.ops.sort_ingest import make_sort_ingest_fn

            self._ingest = make_sort_ingest_fn(
                config.bucket_limit, config.precision
            )
        elif ingest_path == "sortscan":
            from loghisto_tpu.ops.sort_ingest import make_sortscan_ingest_fn

            self._ingest = make_sortscan_ingest_fn(
                config.bucket_limit, config.precision
            )
        elif ingest_path == "pallas":
            self._ingest = self._make_dense_step_fn("pallas")
        elif ingest_path == "fused":
            # explicit selection: surface the correctness blockers with
            # their reason strings at construction (auto resolved them
            # above); the crossover is the operator's call here
            from loghisto_tpu.ops.dispatch import fused_ingest_incapability

            reason = fused_ingest_incapability(
                num_metrics, batch_size=batch_size,
                mesh=mesh is not None, crossover=False,
            )
            if reason is not None:
                raise ValueError(f"ingest_path='fused': {reason}")
            self._ingest = self._make_dense_step_fn("fused")
        elif ingest_path == "fused_paged":
            # direct-to-paged fused kernel (r17): dispatches run through
            # PagedStore.ingest_raw inside _dispatch_slot_locked — the
            # donated pool is the accumulator, so there is no dense
            # f(acc, ids, values) step fn to build here
            self._ingest = None
        elif ingest_path == "multirow":
            if mesh is not None:
                raise ValueError(
                    "ingest_path='multirow' is single-device (its dense "
                    "layout is lane-padded); use scatter with a mesh"
                )
            from loghisto_tpu.ops.pallas_multirow import make_multirow_ingest

            init, multirow_ingest, self._finalize_acc = make_multirow_ingest(
                num_metrics, config.bucket_limit, config.precision
            )
            self._ingest = multirow_ingest
            # lane-padded accumulator layout; the weighted host-bridge
            # ingest still works (dense buckets are the leading columns)
            self._make_acc = init
            self._acc = init()
        else:
            raise ValueError(
                f"unknown ingest_path {ingest_path!r}: expected 'auto', "
                "'scatter', 'matmul', 'sort', 'sortscan', 'hybrid', "
                "'fused', or 'multirow'"
            )
        self.ingest_path = ingest_path
        self._weighted_ingest = make_weighted_ingest_fn(config.bucket_limit)
        # Packed [n, 3] merge step — built unconditionally (not just for
        # preagg) because transport="auto" can switch to sparse at
        # runtime after the density probe; the kernel tier follows the
        # capture-overridable SPARSE_KERNEL switch.  Compilation is lazy
        # (first packed merge), so raw-only aggregators never pay for it.
        from loghisto_tpu.ops.sparse_ingest import make_sparse_ingest_fn

        self._packed_ingest = make_sparse_ingest_fn(config.bucket_limit)
        self._stats_fn = jax.jit(
            functools.partial(
                dense_stats,
                bucket_limit=config.bucket_limit,
                precision=config.precision,
            )
        )
        # lifetime aggregates on host: name id -> [sum, count]
        self._agg_lock = threading.Lock()
        self._agg: Dict[int, list] = {}
        self._last_aggregation_us = 0.0

        self._attached: Optional[tuple[MetricSystem, threading.Thread]] = None
        self._bridge_ch: Optional[Channel] = None
        self._bridge_stop = threading.Event()
        # serializes the bridge's eviction re-subscribe against detach():
        # without it, detach racing an eviction could strand a freshly
        # subscribed reader-less channel on the MetricSystem
        self._bridge_lock = threading.Lock()
        self._bridge_evictions = 0

    # -- direct ingestion ---------------------------------------------- #

    def record(self, name: str, value: float) -> None:
        self.record_batch(
            np.array([self._id_for(name)], dtype=np.int32),
            np.array([value], dtype=np.float32),
        )

    def _id_for(self, name: str, samples: int = 1) -> int:
        """Row id for a name, applying the on_registry_full policy: grow
        the row space geometrically up to max_metrics, then shed (-1 —
        every ingest kernel drops it) with a counter.  `samples` is how
        many samples ride on this lookup (merge_raw passes a histogram's
        whole interval count), so the shed gauge reports true loss."""
        try:
            return self.registry.id_for(name)
        except RegistryFullError:
            if self.on_registry_full == "error":
                raise
        with self._dev_lock:
            try:
                return self.registry.id_for(name)  # a racer may have grown
            except RegistryFullError:
                pass
            if self._grow_locked():
                return self.registry.id_for(name)
            first = self._registry_shed_samples == 0
            self._registry_shed_samples += samples
            if first:
                import logging

                logging.getLogger("loghisto_tpu").warning(
                    "metric registry exhausted at max_metrics=%d; samples "
                    "for further new names are shed (tpu.RegistryShedSamples"
                    " counts them)", self.max_metrics,
                )
            return -1

    def _make_dense_step_fn(self, path: str):
        """Jitted donated-accumulator wrapper over any dense-layout
        dispatched kernel (all paths share the [*, B] accumulator, so
        growth can swap kernels without touching the data)."""
        from loghisto_tpu.ops.dispatch import ingest_step_fn

        step = ingest_step_fn(path)
        bl, prec = self.config.bucket_limit, self.config.precision

        @functools.partial(jax.jit, donate_argnums=0)
        def ingest(acc, ids, values):
            return step(acc, ids, values, bl, prec)

        return ingest

    def _grow_row_unit(self) -> int:
        """Row-count granularity growth must preserve: the mesh metric
        axis (shard divisibility) or the multirow kernel's row tile."""
        if self.mesh is not None:
            return self.mesh.shape[METRIC_AXIS]
        if self.ingest_path == "multirow":
            return 8  # make_multirow_ingest's rows_tile default
        if self.ingest_path == "fused":
            return 8  # fused_ingest.ROWS_TILE: M must stay tile-divisible
        return 1

    def _grow_locked(self, target: Optional[int] = None) -> bool:
        """Grow the metric-row space in place (caller holds _dev_lock): pad
        the accumulator (and spill) with zero rows, re-shard in mesh mode,
        rebuild the shape-specialized multirow kernel (caller holds
        _dev_lock — growth mutates device state).  Returns False when
        no growth is possible (max_metrics reached, or the divisibility
        unit leaves no room).  All fallible work happens BEFORE any state
        is committed, so a failed grow leaves the aggregator untouched.
        Geometric growth bounds jit recompiles at log2(max/initial)."""
        old_m = self.num_metrics
        unit = self._grow_row_unit()
        new_m = min(
            target if target is not None else old_m * 2, self.max_metrics
        )
        new_m -= new_m % unit  # clamp may land off-grid; round down
        if new_m <= old_m:
            return False
        if self.paged is not None:
            # paged growth is a host-side page-table extension: no device
            # tensor is reallocated, no kernel is rebuilt, no data moves.
            self.paged.grow(new_m)
            self.num_metrics = new_m
            self.stats_snapshot = None
            self.registry.grow(new_m)
            return True
        # -- fallible section: build everything in locals first --
        make_acc, ingest, finalize = (
            self._make_acc, self._ingest, self._finalize_acc
        )
        new_path = self.ingest_path
        if self.ingest_path == "multirow":
            from loghisto_tpu.ops.pallas_multirow import make_multirow_ingest

            make_acc, ingest, finalize = make_multirow_ingest(
                new_m, self.config.bucket_limit, self.config.precision
            )
        elif self.ingest_path == "pallas":
            # the single-row kernel cannot cover more rows; swap to the
            # auto-dispatched dense-family kernel for the grown shape
            # (same [*, B] layout, so the data moves unchanged)
            platform = (
                self.mesh.devices.flat[0].platform
                if self.mesh is not None
                else jax.default_backend()
            )
            new_path = resolve_ingest_path(
                "auto", new_m, self.config.num_buckets, platform,
                guard_metrics=self.max_metrics, batch_size=self.batch_size,
                mesh=self.mesh is not None,
            )
            ingest = self._make_dense_step_fn(new_path)
        acc_np = np.asarray(self._acc)
        grown = np.zeros((new_m, acc_np.shape[1]), dtype=acc_np.dtype)
        grown[:old_m] = acc_np
        if self.mesh is not None:
            new_acc = jax.device_put(
                grown, NamedSharding(self.mesh, P(METRIC_AXIS, None))
            )
        else:
            new_acc = jnp.asarray(grown)
        # -- commit --
        self._make_acc, self._ingest, self._finalize_acc = (
            make_acc, ingest, finalize
        )
        self.ingest_path = new_path
        self._acc = new_acc
        self.num_metrics = new_m
        self.stats_snapshot = None  # row space changed; handle is stale
        self.registry.grow(new_m)
        if self._spill is not None:
            spill = np.zeros(
                (new_m, self._spill.shape[1]), dtype=self._spill.dtype
            )
            spill[:old_m] = self._spill
            self._spill = spill
        return True

    def _spill_fold_locked(self) -> None:
        """Fold the device accumulator into the host int64 spill tensor and
        reset it, WITHOUT closing the interval (caller holds _dev_lock).
        Keeps
        every per-cell device count below spill_threshold + one flush
        round — the int32 overflow guarantee."""
        if self.paged is not None:
            # decode pool -> host spill dict inside the store (exact:
            # spill cells keep native dense indices), zero the pool
            self.paged.spill_pool()
            self._spilled_samples += self._interval_ingested
            self._interval_ingested = 0
            self.stats_snapshot = None
            return
        acc_np = np.asarray(self._finalize_acc(self._acc), dtype=np.int64)
        if self._spill is None:
            self._spill = acc_np
        else:
            self._spill += acc_np
        self._acc = self._fresh_acc()
        self._spilled_samples += self._interval_ingested
        self._interval_ingested = 0
        self.stats_snapshot = None  # acc folded out; handle is stale

    def record_batch(self, ids: np.ndarray, values: np.ndarray) -> None:
        """Buffer a batch of (metric_id, value) samples; flushes to device
        when the buffered count reaches batch_size."""
        ids = np.asarray(ids, dtype=np.int32)
        values = np.asarray(values, dtype=np.float32)
        if ids.shape != values.shape:
            raise ValueError("ids and values must have the same shape")
        if self._cell_store is not None:
            # preagg direct fold (VERDICT r2 item 2): samples are touched
            # ONCE — compressed + deduped into this thread's cell shard
            # right here, with the GIL released inside the C fold.  No
            # staging lists, no concatenate, no second pass at flush; the
            # device sees one packed ship per interval (or watermark).
            self._preagg_record(ids, values)
            return
        if self._native_buf is not None:
            accepted = self._native_buf.record_batch(
                ids, values.astype(np.float64)
            )
            # keep the documented auto-flush contract in the native path;
            # counted under the lock (an unsynchronized += can lose
            # updates and *miss* flushes) and only for accepted samples
            with self._lock:
                self._native_staged += accepted
                should_flush = self._native_staged >= self.batch_size
            if should_flush:
                self.flush()
            return
        with self._lock:
            self._pending_ids.append(ids)
            self._pending_values.append(values)
            self._pending_count += len(ids)
            # while the device is down (flush cooldown-gated), the buffer
            # must stay bounded
            self._bound_pending_locked()
            should_flush = self._pending_count >= self.batch_size
        if should_flush:
            self.flush()

    def _fresh_dense_acc(self) -> jnp.ndarray:
        if self.mesh is not None:
            return make_sharded_accumulator(
                self.mesh, self.num_metrics, self.config.num_buckets
            )
        return jnp.zeros(
            (self.num_metrics, self.config.num_buckets), dtype=jnp.int32
        )

    def _fresh_acc(self) -> jnp.ndarray:
        """Zero accumulator in THIS ingest path's layout (the multirow
        path is lane-padded; rebuilding the wrong shape after a device
        failure would permanently break ingestion)."""
        return self._make_acc()

    def _buffered_samples(self) -> int:
        """Samples currently buffered on host awaiting a device attempt
        (requeued failures + fresh pending).  Unsynchronized sum — a
        monitoring/test convenience, exact whenever the transfer queue
        is idle."""
        return self._requeue_count + self._pending_count

    @property
    def pending_samples(self) -> int:
        """Public monitoring alias for the host-buffered sample count —
        the health watchdog's ingest-backpressure signal (compared
        against ``max_pending_samples``)."""
        return self._buffered_samples()

    def _bound_pending_locked(self) -> None:
        """Enforce max_pending_samples over the WHOLE host buffer
        (requeue + pending) by shedding the OLDEST samples — the requeue
        lists hold strictly older content than _pending (single FIFO
        worker), so they shed first.  Partial arrays are sliced so no
        more than the overflow is dropped.  Caller holds self._lock."""
        overflow = (
            self._requeue_count + self._pending_count
            - self.max_pending_samples
        )
        for ids_list, values_list, count_attr in (
            (self._requeue_ids, self._requeue_values, "_requeue_count"),
            (self._pending_ids, self._pending_values, "_pending_count"),
        ):
            while overflow > 0 and ids_list:
                head = ids_list[0]
                if len(head) <= overflow:
                    ids_list.pop(0)
                    values_list.pop(0)
                    setattr(
                        self, count_attr,
                        getattr(self, count_attr) - len(head),
                    )
                    with self._shed_lock:
                        self._shed_samples += len(head)
                    overflow -= len(head)
                else:
                    ids_list[0] = head[overflow:]
                    values_list[0] = values_list[0][overflow:]
                    setattr(
                        self, count_attr,
                        getattr(self, count_attr) - overflow,
                    )
                    with self._shed_lock:
                        self._shed_samples += overflow
                    overflow = 0

    def flush(self, force: bool = False) -> None:
        """Hand buffered samples to the transfer pipeline.

        flush() is ENQUEUE-ONLY (r6 tentpole): it drains host staging
        under _lock, enqueues one transfer item, and returns — the
        transfer worker thread stages ring slots, issues the async
        device_puts, and runs the donated dispatches, so producers never
        block on device work and the upload of chunk k+1 overlaps the
        dispatch of chunk k.  ``force=True`` (collect / checkpoint /
        close) additionally WAITS until the whole queue has drained —
        after a forced flush, device state reflects every prior record.

        Device failures follow SURVEY.md §5.3 shed-don't-block: the
        worker re-buffers the unapplied remainder on host (bounded,
        oldest shed first) and retries are cooldown-gated so a down
        device costs one attempt per retry_cooldown, not one per
        record."""
        with self.obs_recorder.span("ingest.flush"):
            self._flush_impl(force)

    def _flush_impl(self, force: bool) -> None:
        if self._cell_store is not None:
            # preagg: samples were folded at record time; flushing means
            # shipping the deduped cells.  Mid-interval ships happen only
            # past the watermark (the wire carries each interval's unique
            # cells once); `force` (collect/checkpoint) always ships.
            if not force and len(self._cell_store) < self.max_host_cells:
                return
            packed = self._cell_store.drain_packed_all()
            if len(packed):
                self._enqueue_xfer(("packed", packed, None, 0, force))
            if force:
                self.wait_transfers()
            return
        if self._native_buf is not None:
            with self._lock:
                self._native_staged = 0
            nids, nvalues = self._native_buf.drain()
            if len(nids):
                with self._lock:
                    self._pending_ids.append(nids)
                    self._pending_values.append(nvalues.astype(np.float32))
                    self._pending_count += len(nids)
                    self._bound_pending_locked()
        with self._lock:
            if not self._requeue_count and not self._pending_count:
                ids = values = None
            elif (
                not force
                and time.monotonic() < self._device_down_until
            ):
                # _device_down_until is written under _dev_lock; this read
                # is a benign race (cooldown is a heuristic, not an
                # invariant)
                return  # device cooling down; keep buffering
            elif (
                not force
                and self._xfer_queued_samples >= self.max_pending_samples
            ):
                # transfer queue saturated (device slower than producers):
                # leave samples in the bounded host buffer, where the
                # oldest-first shed policy applies, instead of growing
                # the queue without bound
                return
            else:
                # requeue first: strictly older than anything in _pending
                ids = np.concatenate(self._requeue_ids + self._pending_ids)
                values = np.concatenate(
                    self._requeue_values + self._pending_values
                )
                self._requeue_ids, self._requeue_values = [], []
                self._requeue_count = 0
                self._pending_ids, self._pending_values = [], []
                self._pending_count = 0
        if ids is not None:
            kind = "fold" if self.transport == "sparse" else "raw"
            self._enqueue_xfer((kind, ids, values, len(ids), force))
        if not force:
            return
        self.wait_transfers()
        # An item already in flight when we drained may have failed
        # DURING the wait and requeued its samples — invisible to the
        # drain above, yet recorded strictly before this flush, so the
        # forced barrier owes them one forced (cooldown-bypassing)
        # attempt, exactly as the synchronous flush gave them.  One extra
        # pass only: if that attempt also fails, the device is down and
        # the samples stay buffered (same bounded-attempts contract as
        # the worker path).
        with self._lock:
            if not self._requeue_count and not self._pending_count:
                return
            ids = np.concatenate(self._requeue_ids + self._pending_ids)
            values = np.concatenate(
                self._requeue_values + self._pending_values
            )
            self._requeue_ids, self._requeue_values = [], []
            self._requeue_count = 0
            self._pending_ids, self._pending_values = [], []
            self._pending_count = 0
        kind = "fold" if self.transport == "sparse" else "raw"
        self._enqueue_xfer((kind, ids, values, len(ids), True))
        self.wait_transfers()

    def merge_packed(self, packed: np.ndarray, wait: bool = False) -> None:
        """Public packed-triple ingest: merge an int32 ``[n, 3]``
        (row_id, codec_bucket, count) cell array — already in THIS
        aggregator's row-id space — through the transfer worker's packed
        path (same device merge, spill guarantees, and wire accounting
        as the sparse transport's fold).  The federation receiver's
        drain; scatter-adds are order-independent, so interleaving with
        local ingest cannot change the aggregate.  ``wait`` blocks until
        the transfer queue drains (tests; production callers pipeline)."""
        packed = np.ascontiguousarray(packed, dtype=np.int32)
        if packed.ndim != 2 or packed.shape[1] != 3:
            raise ValueError(
                f"packed cell array must be [n, 3] (id, bucket, count); "
                f"got shape {packed.shape}"
            )
        if len(packed):
            self._enqueue_xfer(("packed", packed, None, 0, False))
        if wait:
            self.wait_transfers()

    # -- transfer pipeline ---------------------------------------------- #

    def _enqueue_xfer(self, item: tuple) -> None:
        """Append one (kind, a, b, n_samples, force) item to the transfer
        queue, lazily (re)spawning the worker thread."""
        with self._xfer_cv:
            if self._xfer_thread is None or not self._xfer_thread.is_alive():
                if (
                    self._xfer_thread is not None
                    and not self._xfer_stop
                    and self.supervisor is not None
                ):
                    # the worker died abnormally (a clean close() sets
                    # _xfer_stop first); the lazy respawn below is its
                    # restart — count it on the shared ledger so the
                    # thread_restarted invariant sees it
                    self.supervisor.note_external_restart(
                        "loghisto-tpu-xfer"
                    )
                self._xfer_stop = False
                self._xfer_thread = threading.Thread(
                    target=self._xfer_worker,
                    daemon=True,
                    name="loghisto-tpu-xfer",
                )
                self._xfer_thread.start()
            self._xfer_queue.append(item)
            self._xfer_queued_samples += item[3]
            self._xfer_cv.notify_all()

    def wait_transfers(self, timeout: Optional[float] = None) -> bool:
        """Block until the transfer queue is empty AND the worker is idle
        (every enqueued flush has reached the device, the spill, or the
        requeue buffer).  The synchronization barrier behind
        flush(force=True); tests and checkpointing rely on it.  Returns
        False on timeout."""
        deadline = (
            None if timeout is None else time.monotonic() + timeout
        )
        with self._xfer_cv:
            while self._xfer_queue or self._xfer_active:
                remaining = (
                    None if deadline is None
                    else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    return False
                self._xfer_cv.wait(remaining)
        return True

    def close(self) -> None:
        """Drain everything and stop the transfer worker, in two phases.
        flush(force) drains the host buffers and the transfer QUEUE —
        but NOT the staging ring: with the r13 double-buffered pipeline,
        up to ring-depth async uploads can still be in flight after the
        queue empties (stage() only waits for the slot it is about to
        reuse).  Phase two below drains those in-flight slots under
        _dev_lock (ring.drain()), restoring exact count conservation —
        nothing staged is dropped.  Then the worker is signalled down
        and joined.  The aggregator stays usable: a later flush lazily
        re-spawns the worker."""
        self.flush(force=True)
        # r13 double-buffering means up to ring-depth async uploads can
        # still be in flight after the queue drains (stage() only waits
        # for the slot it reuses, and a worker killed between items —
        # e.g. by an agg.xfer_worker chaos fault — leaves its staged
        # slot undispatched).  Drain them under _dev_lock so the final
        # interval commit can never race a live H2D copy.
        with self._dev_lock:
            ring = self._staging_ring
            if ring is not None:
                ring.drain()
        with self._xfer_cv:
            self._xfer_stop = True
            self._xfer_cv.notify_all()
            t = self._xfer_thread
        if t is not None:
            t.join(timeout=10.0)

    def _xfer_worker(self) -> None:
        while True:
            inj = self.fault_injector
            if inj is not None:
                # chaos hook BETWEEN items (no queue bookkeeping is in
                # flight here): a scripted crash kills the worker — the
                # next enqueue lazily respawns it, counted on the
                # supervisor ledger; a scripted wedge blocks it, backing
                # the queue up into the max_pending_samples shed bound
                inj.check("agg.xfer_worker")
            with self._xfer_cv:
                while not self._xfer_queue and not self._xfer_stop:
                    self._xfer_cv.wait()
                if not self._xfer_queue:  # stop requested, queue drained
                    self._xfer_active = False
                    self._xfer_cv.notify_all()
                    return
                item = self._xfer_queue.popleft()
                self._xfer_active = True
            try:
                with self.obs_recorder.span("ingest.drain"):
                    self._process_xfer_item(item)
            except Exception:  # pragma: no cover - defensive
                import logging

                logging.getLogger("loghisto_tpu").exception(
                    "transfer worker failed processing a %s item", item[0]
                )
            finally:
                with self._xfer_cv:
                    self._xfer_queued_samples -= item[3]
                    self._xfer_active = False
                    self._xfer_cv.notify_all()

    def _process_xfer_item(self, item: tuple) -> None:
        kind, a, b, n, force = item
        if kind == "packed":
            self._xfer_uploads += 1
            self._xfer_bytes += a.nbytes
            self._xfer_samples_shipped += int(a[:, 2].sum(dtype=np.int64))
            self._ship_packed(a)
            return
        # raw staging content ("raw" ships samples, "fold" packs first).
        # Cooldown gate runs HERE, per item: after a failure arms the
        # cooldown, queued non-forced items bounce straight to the
        # requeue buffer without a device attempt — one attempt per
        # cooldown window, in arrival order.
        if not force and time.monotonic() < self._device_down_until:
            self._requeue_raw(a, b)
            return
        if kind == "fold" or self._maybe_switch_sparse(a, b, n):
            self._process_fold(a, b, n)
            return
        self._process_raw(a, b, n)

    def _requeue_raw(self, ids: np.ndarray, values: np.ndarray) -> None:
        if not len(ids):
            return
        with self._lock:
            self._requeue_ids.append(ids)
            self._requeue_values.append(values)
            self._requeue_count += len(ids)
            self._bound_pending_locked()

    def _maybe_switch_sparse(
        self, ids: np.ndarray, values: np.ndarray, n: int
    ) -> bool:
        """transport="auto" density probe (runs once, on the worker, on
        the first raw item large enough to be representative): fold the
        WHOLE item to unique (row, bucket) cells with the host codec and
        switch to the sparse transport when the load is skewed past the
        crossover.  The fold must see the full item, not a prefix —
        PAGED_STORE_r14 measured the old 64Ki-prefix probe reading
        density 0.92 on a 100k-row skew because a prefix shorter than
        the interval cannot observe within-interval duplication (most
        prefix samples land on distinct cells even when every cell
        repeats hundreds of times across the batch).  Returns True when
        THIS item should already take the fold path."""
        if not self._transport_auto or self.probe_density is not None:
            return False
        if n < _PROBE_SAMPLES:
            return False
        from loghisto_tpu import _native
        from loghisto_tpu.ops import dispatch as _dispatch

        buckets = _native.compress_np_host(
            values, self.config.precision
        ).astype(np.int64)
        keep = ids >= 0
        kept = int(keep.sum())
        if not kept:
            return False
        keys = (ids[keep].astype(np.int64) << 16) | (
            buckets[keep] + 32768
        )
        self.probe_density = len(np.unique(keys)) / kept
        platform = (
            self.mesh.devices.flat[0].platform
            if self.mesh is not None
            else jax.default_backend()
        )
        chosen = _dispatch.choose_transport(
            platform, density=self.probe_density
        )
        if chosen != self.transport:
            import logging

            logging.getLogger("loghisto_tpu").info(
                "transport auto-probe: cell density %.3f <= crossover "
                "%.3f; switching to the sparse packed-triple transport",
                self.probe_density, _dispatch.SPARSE_DENSITY_CROSSOVER,
            )
            self.transport = chosen
        return self.transport == "sparse"

    def _process_fold(
        self, ids: np.ndarray, values: np.ndarray, n: int
    ) -> None:
        """Sparse transport: fold the raw batch into packed triples on
        this worker thread (GIL-released parallel native tier, NumPy
        fallback) and merge them via the packed scatter.  Failures past
        this point spill exactly (cells are finished aggregates — no
        retry queue needed)."""
        from loghisto_tpu import _native

        try:
            packed = _native.fold_packed(
                ids, values,
                bucket_limit=self.config.bucket_limit,
                precision=self.config.precision,
            )
        except MemoryError:
            # can't build the fold table: ship the batch raw instead of
            # losing it (same wire contract, just more bytes)
            self._process_raw(ids, values, n)
            return
        self._xfer_uploads += 1
        self._xfer_bytes += packed.nbytes
        self._xfer_samples_shipped += n
        self._ship_packed(packed)

    def _dispatch_slot_locked(self, slot: tuple) -> Optional[int]:
        """Consume one staged super-chunk (caller holds _dev_lock):
        wait for the slot's async upload, record its "ingest.upload"
        span (issue -> ready, i.e. the real H2D window — which overlaps
        the PREVIOUS slot's "ingest.dispatch" span when the pipeline is
        doing its job; benchmarks/fused_ingest_bench.py computes the
        overlap percentage from exactly these two span streams), then
        run the donated per-batch_size dispatches with the per-chunk
        spill check.  Returns the absolute sample offset where work
        failed, or None when the slot fully applied."""
        soff, send, ids_dev, values_dev, t_issue = slot
        bs = self.batch_size
        rec = self.obs_recorder
        try:
            ids_dev.block_until_ready()
            values_dev.block_until_ready()
        except Exception:
            self._on_device_failure_locked()
            return soff
        rec.record("ingest.upload", t_issue, time.perf_counter_ns())
        with rec.span("ingest.dispatch"):
            for off in range(soff, send, bs):
                lo = off - soff
                try:
                    inj = self.fault_injector
                    if inj is not None:
                        # chaos hook inside the per-chunk net: an
                        # injected device failure takes the organic
                        # recovery (cooldown + requeue remainder)
                        inj.check("agg.ingest")
                    if self.paged is not None:
                        # direct-to-paged (r17): ONE Pallas dispatch
                        # straight into the donated pool (the batch was
                        # page-prepared on the worker before staging)
                        self.paged.ingest_raw(
                            ids_dev[lo:lo + bs], values_dev[lo:lo + bs]
                        )
                    else:
                        self._acc = self._ingest(
                            self._acc,
                            ids_dev[lo:lo + bs],
                            values_dev[lo:lo + bs],
                        )
                    self._device_down_until = 0.0
                    self._interval_ingested += min(bs, send - off)
                    # int32 overflow guarantee: the check must run per
                    # chunk — a force-flush of a large host backlog
                    # could otherwise push a hot cell past 2^31
                    # (worst case all samples hit one cell; threshold
                    # + batch_size < 2^31 is validated at construction)
                    if self._interval_ingested >= self.spill_threshold:
                        self._spill_fold_locked()
                except Exception:
                    self._on_device_failure_locked()
                    return off
        return None

    def _process_raw(
        self, ids: np.ndarray, values: np.ndarray, n: int
    ) -> None:
        """Raw transport device loop (worker thread): a true
        double-buffered pipeline over the staging ring (r13).  Slot k+1
        is staged — its async ``device_put`` issued — BEFORE slot k's
        dispatches run, so the H2D copy of the next super-chunk proceeds
        while the donated ingest dispatches consume the current one; the
        per-slot "ingest.upload" / "ingest.dispatch" spans recorded by
        _dispatch_slot_locked prove the overlap.  Failures preserve
        exact sample conservation: everything before the failing offset
        was applied, everything from it on is requeued from the host
        arrays (which also covers a staged-but-undispatched next slot)."""
        if self.paged is not None and not self.fused_paged:
            # reached only through _process_fold's MemoryError fallback
            # (non-fused paged pins transport="sparse").  There is no
            # dense device loop to fall back to, and re-entering the
            # fold would repeat the failed allocation — compress on the
            # host and take the exact spill instead.  Rare by
            # construction; correctness over throughput.
            from loghisto_tpu._native import compress_np_host

            buckets = compress_np_host(
                values.astype(np.float64), self.config.precision
            )
            np.clip(
                buckets, -self.config.bucket_limit,
                self.config.bucket_limit, out=buckets,
            )
            with self._dev_lock:
                self._spill_add_cells_locked(
                    ids, buckets, np.ones(len(ids), dtype=np.int64)
                )
            self._xfer_samples_shipped += n
            return
        if self.paged is not None:
            # fused direct-to-paged (r17): assign codecs and map every
            # page this batch touches in one vectorized host pass on
            # THIS worker thread, BEFORE anything uploads — the
            # staged/dispatched loop below never consults the host page
            # table, so allocation can never block a dispatch.  ids come
            # back rewritten (saturation -> overflow row or -1 + exact
            # host spill), so a post-failure requeue of these arrays
            # stays count-exact: spilled counts were applied here
            # exactly once and their ids are already -1.
            with self._dev_lock:
                ids, _ = self.paged.prepare_batch(ids, values)
        bs = self.batch_size
        ring = self._staging_ring
        if ring is None or ring.slot_samples != 8 * bs:
            ring = self._staging_ring = IngestStagingRing(
                8 * bs, depth=self.staging_depth, chunk_samples=bs
            )
        super_bs = ring.slot_samples
        retry_off = None
        with self._dev_lock:
            pending: Optional[tuple] = None  # staged, not yet dispatched
            for soff in range(0, n, super_bs):
                send = min(soff + super_bs, n)
                t_issue = time.perf_counter_ns()
                try:
                    ids_dev, values_dev = ring.stage(
                        ids[soff:send], values[soff:send]
                    )
                    nxt = (soff, send, ids_dev, values_dev, t_issue)
                except Exception:
                    self._on_device_failure_locked()
                    nxt = None
                if pending is not None:
                    fail = self._dispatch_slot_locked(pending)
                    pending = None
                    if fail is not None:
                        retry_off = fail
                        break
                if nxt is None:
                    retry_off = soff
                    break
                pending = nxt
            if retry_off is None and pending is not None:
                retry_off = self._dispatch_slot_locked(pending)
        self._xfer_samples_shipped += (
            n if retry_off is None else retry_off
        )
        if retry_off is not None and retry_off < n:
            import logging

            # the traceback was already logged inside the except handler
            # (_on_device_failure_locked); this is just the retry notice
            logging.getLogger("loghisto_tpu").warning(
                "buffering %d samples for retry (cooldown %.1fs)",
                n - retry_off, self.retry_cooldown,
            )
            self._requeue_raw(ids[retry_off:n], values[retry_off:n])

    def transport_stats(self) -> dict:
        """Wire accounting for the active transport: uploads, bytes
        actually moved host->device (ring slots count their padded
        size — that IS what transfers), and samples those bytes carried.
        bench.py / benchmarks/h2d_bench.py derive bytes/sample from
        this."""
        ring = self._staging_ring
        return {
            "transport": self.transport,
            "probe_density": self.probe_density,
            "uploads": self._xfer_uploads + (ring.uploads if ring else 0),
            "bytes_uploaded": self._xfer_bytes
            + (ring.bytes_uploaded if ring else 0),
            "samples_shipped": self._xfer_samples_shipped,
        }

    def _preagg_record(self, ids: np.ndarray, values: np.ndarray) -> None:
        """Fold one batch into the calling thread's cell shard (the preagg
        hot path — native hash, the same codec bit-for-bit as the device
        kernel).  The device sees traffic only on force-flush (interval
        boundaries: collect/checkpoint) or past the max_host_cells
        watermark — so the wire carries each interval's UNIQUE cells
        once, however many samples they absorbed, and a thin host->device
        link no longer caps sample throughput.  On device failure the
        cells fold into the host int64 spill — they are already exact
        aggregates, so nothing needs a retry queue."""
        consumed = self._cell_store.add(ids, values)
        if consumed < len(ids):
            # shard table could not grow: the consumed prefix is folded
            # exactly once, so ship everything held (drained tables keep
            # their capacity, now at low load) and retry ONLY the
            # remainder — no double count
            self._ship_packed(self._cell_store.drain_packed_all())
            rest = self._cell_store.add(ids[consumed:], values[consumed:])
            if consumed + rest < len(ids):
                dropped = len(ids) - consumed - rest
                with self._shed_lock:
                    self._shed_samples += dropped
                import logging

                logging.getLogger("loghisto_tpu").error(
                    "cell store cannot grow even after draining; "
                    "shed %d samples", dropped,
                )
        if len(self._cell_store) >= self.max_host_cells:
            self.flush()

    def _ship_packed(self, packed: np.ndarray) -> None:
        """Merge drained packed cells into the device accumulator (one
        int32 [m, 3] (id, bucket, count) wire array; ingest.cpp
        lh_cells_drain_packed)."""
        if not len(packed):
            return
        # Hard guard on the wire contract BEFORE anything reaches the
        # kernel: a 2-column array would not raise under jit (static OOB
        # gathers clamp), it would silently misread keys as row ids —
        # the exact corruption the int32 [m, 3] format exists to prevent.
        if packed.ndim != 2 or packed.shape[1] != 3:
            raise ValueError(
                f"packed cell array must be [m, 3] (id, bucket, count); "
                f"got shape {packed.shape}"
            )
        if packed.dtype != np.int32:
            raise ValueError(
                f"packed cell array must be int32 (no-x64 JAX would "
                f"silently truncate int64); got {packed.dtype}"
            )
        with self._dev_lock:
            try:
                self._merge_packed_locked(packed)
            except Exception:
                # chunk-dispatch failures are handled (and partially
                # spilled) inside _merge_packed_locked; reaching here
                # means the merge failed BEFORE applying any cell (e.g.
                # the spill fold's device read) — spilling the full set
                # is exact, not a double count
                self._on_device_failure_locked()
                self._spill_add_packed_locked(packed)

    def _spill_add_packed_locked(self, packed: np.ndarray) -> None:
        from loghisto_tpu._native import unpack_cells

        uids, ubuckets, uweights = unpack_cells(packed)
        self._spill_add_cells_locked(
            uids, ubuckets.astype(np.int64), uweights
        )

    def _merge_packed_locked(self, packed: np.ndarray) -> None:
        """Packed twin of _merge_cells_locked: same spill guarantees and
        per-chunk accounting, one device transfer per chunk.  Caller
        holds _dev_lock."""
        n = len(packed)
        weights = packed[:, 2]
        total = int(weights.sum(dtype=np.int64))
        if (
            self._interval_ingested + total >= self.spill_threshold
            or (n and int(weights.max()) >= 1 << 30)
        ):
            self._spill_fold_locked()
            self._spill_add_packed_locked(packed)
            return
        if self.paged is not None:
            # the store translates (row, codec bucket, count) against the
            # page table and pads to COMMIT_CHUNK internally; cells that
            # can't get a page go to the store's exact host spill
            try:
                self._interval_ingested += self.paged.commit(packed)
            except Exception:
                self._on_device_failure_locked()
                self._spill_add_packed_locked(packed)
                return
            self._device_down_until = 0.0
            return
        for off in range(0, n, _MERGE_CHUNK):
            take = min(_MERGE_CHUNK, n - off)
            pad = np.empty((_MERGE_CHUNK, 3), dtype=np.int32)
            pad[:, 0] = -1  # negative id: dropped by sanitize_ids
            pad[:, 1] = 0
            pad[:, 2] = 0
            pad[:take] = packed[off:off + take]
            try:
                self._acc = self._packed_ingest(self._acc, pad)
            except Exception:
                self._on_device_failure_locked()
                self._spill_add_packed_locked(packed[off:])
                return
            # success-only reset, mirroring the raw flush loop
            self._device_down_until = 0.0
            self._interval_ingested += int(
                weights[off:off + take].sum(dtype=np.int64)
            )

    def _on_device_failure_locked(self) -> None:
        """Device-failure bookkeeping (caller holds _dev_lock, and must
        call from INSIDE the except handler so the traceback below is
        still live): log the failure, arm the retry cooldown, and recover
        the donated accumulator if the failed dispatch consumed it —
        continuing to use a deleted array would brick every later
        flush."""
        import logging

        logging.getLogger("loghisto_tpu").exception(
            "device ingest dispatch failed"
        )
        self._device_down_until = time.monotonic() + self.retry_cooldown
        if getattr(self._acc, "is_deleted", lambda: False)():
            logging.getLogger("loghisto_tpu").error(
                "device failure consumed the donated accumulator; %d "
                "already-ingested samples of this interval are lost",
                self._interval_ingested,
            )
            with self._shed_lock:
                self._shed_samples += self._interval_ingested
            self._interval_ingested = 0
            self._acc = self._fresh_acc()
        if self.paged is not None and self.paged.pool_deleted():
            logging.getLogger("loghisto_tpu").error(
                "device failure consumed the donated page pool; %d "
                "already-ingested samples of this interval are lost",
                self._interval_ingested,
            )
            with self._shed_lock:
                self._shed_samples += self._interval_ingested
            self._interval_ingested = 0
            self.paged.reset_pool()
        self.stats_snapshot = None
        if self.device_breaker is not None:
            # the SINGLE breaker count point per physical failure: the
            # committer's fused recovery, the bridge merge, and the
            # transfer worker all funnel through this handler, so the
            # consumer hooks fanning out from here must never count
            self.device_breaker.record_failure("aggregator")

    # -- host-tier bridge ----------------------------------------------- #

    def merge_raw(self, raw: RawMetricSet) -> None:
        """Merge one host-tier interval (sparse bucket maps) into the dense
        device accumulator via fixed-width weighted scatter launches.

        Cells are padded to _MERGE_CHUNK (dropped id -1) so ONE compiled
        executable — pre-warmed by _bridge_warmup — serves every merge;
        a typical interval is a single launch, a 10k-metric worst case a
        handful (the round-1 fixed-4096-chunk loop serialized ~hundreds
        under the ingest lock, VERDICT r1 item 9).

        Counts too large for the int32 device path (or intervals that
        would push a cell past the spill threshold) are folded directly
        into the int64 host spill instead — exact at any magnitude."""
        ids, bidx, weights = [], [], []
        for name, bucket_counts in raw.histograms.items():
            mid = self._id_for(name, samples=sum(bucket_counts.values()))
            if mid < 0:
                continue  # shed (already counted, with its true weight)
            for bucket, count in bucket_counts.items():
                ids.append(mid)
                bidx.append(bucket)  # codec bucket; clipped to range below
                weights.append(count)
        if not ids:
            return
        ids_np = np.asarray(ids, dtype=np.int32)
        bidx_np = np.asarray(bidx, dtype=np.int64)
        weights_np = np.asarray(weights, dtype=np.int64)
        with self._dev_lock:
            self._merge_cells_locked(ids_np, bidx_np, weights_np)

    def _spill_add_cells_locked(
        self,
        ids_np: np.ndarray,
        bidx_np: np.ndarray,
        weights_np: np.ndarray,
    ) -> None:
        """Add (id, codec bucket, weight) cells to the host int64 spill —
        exact at any magnitude.  Caller holds _dev_lock."""
        if self.paged is not None:
            # paged mode keeps its spill as a sparse host dict inside the
            # store (a dense [M, B] int64 tensor at 1M rows would defeat
            # the whole backend); same exactness contract
            keep = (ids_np >= 0) & (ids_np < self.num_metrics)
            dense_idx = (
                np.clip(
                    bidx_np[keep],
                    -self.config.bucket_limit,
                    self.config.bucket_limit,
                )
                + self.config.bucket_limit
            )
            self.paged.spill_cells(
                ids_np[keep].astype(np.int64), dense_idx, weights_np[keep]
            )
            self._spilled_samples += int(weights_np[keep].sum())
            return
        if self._spill is None:
            self._spill = np.zeros(
                (self.num_metrics, self.config.num_buckets), dtype=np.int64
            )
        keep = (ids_np >= 0) & (ids_np < self.num_metrics)
        dense_idx = (
            np.clip(
                bidx_np[keep],
                -self.config.bucket_limit,
                self.config.bucket_limit,
            )
            + self.config.bucket_limit
        )
        np.add.at(
            self._spill,
            (ids_np[keep].astype(np.int64), dense_idx),
            weights_np[keep],
        )
        self._spilled_samples += int(weights_np[keep].sum())

    def _merge_cells_locked(
        self,
        ids_np: np.ndarray,
        bidx_np: np.ndarray,
        weights_np: np.ndarray,
    ) -> None:
        """Merge weighted (id, codec bucket, count) cells into the device
        accumulator via ONE padded scatter launch, or the host spill when
        the int32 guarantee requires it.  Caller holds _dev_lock."""
        n = len(ids_np)
        total = int(weights_np.sum())
        if (
            self._interval_ingested + total >= self.spill_threshold
            or (n and int(weights_np.max()) >= 1 << 30)
        ):
            # giant merge: keep the int32 guarantee by applying it on
            # the host spill in exact int64
            self._spill_fold_locked()
            self._spill_add_cells_locked(ids_np, bidx_np, weights_np)
            return
        if self.paged is not None:
            # repack to the triple wire and ride the paged commit path.
            # int32 casts are safe here: the guard above bounds every
            # weight below 1 << 30 and ids/buckets are clipped in commit.
            packed = np.empty((n, 3), dtype=np.int32)
            packed[:, 0] = ids_np
            packed[:, 1] = np.clip(
                bidx_np, -self.config.bucket_limit, self.config.bucket_limit
            )
            packed[:, 2] = weights_np
            self._merge_packed_locked(packed)
            return
        # ONE fixed launch shape (not a power-of-two ladder): every merge
        # reuses the single executable _bridge_warmup pre-compiled, so no
        # interval — whatever its cell count — ever pays a cold XLA
        # compile mid-bridge.  Typical intervals fit one launch; a
        # 10k-metric worst case is a handful, not the round-1 hundreds.
        # Accounting is PER CHUNK and device failure is handled here:
        # chunks already applied stay counted in _interval_ingested (or
        # are shed with it if the failed dispatch consumed the donated
        # accumulator), and ONLY the unapplied remainder folds into the
        # exact host spill — no sample is ever lost or double-counted.
        for off in range(0, n, _MERGE_CHUNK):
            take = min(_MERGE_CHUNK, n - off)
            ids_pad = np.full(_MERGE_CHUNK, -1, dtype=np.int32)
            bidx_pad = np.zeros(_MERGE_CHUNK, dtype=np.int32)
            weights_pad = np.zeros(_MERGE_CHUNK, dtype=np.int32)
            ids_pad[:take] = ids_np[off:off + take]
            bidx_pad[:take] = bidx_np[off:off + take]
            weights_pad[:take] = weights_np[off:off + take]
            try:
                self._acc = self._weighted_ingest(
                    self._acc, ids_pad, bidx_pad, weights_pad
                )
            except Exception:
                self._on_device_failure_locked()
                self._spill_add_cells_locked(
                    ids_np[off:], bidx_np[off:], weights_np[off:]
                )
                return
            # success-only reset, mirroring the raw flush loop — a failed
            # chunk's cooldown must survive this merge returning normally
            self._device_down_until = 0.0
            self._interval_ingested += int(weights_np[off:off + take].sum())

    def _bridge_warmup(self) -> None:
        """Pre-compile the weighted-ingest executable at THE merge shape
        (all ids dropped — numerically a no-op).  _merge_cells_locked
        always launches exactly _MERGE_CHUNK-sized chunks, so this one
        compile covers every future merge: without it the bridge's FIRST
        merge_raw pays the cold XLA compile (tens of seconds) while the
        host reaper keeps ticking, fills the freshly subscribed channel,
        and strike-evicts it (metrics.go:565-581 semantics) before the
        bridge ever processes an interval."""
        if self.paged is not None:
            with self._dev_lock:
                self.paged.warmup()
                if self.fused_paged:
                    # one all-dropped compile at THE staging chunk shape
                    # — every fused dispatch launches exactly batch_size
                    # samples, so this covers all of them
                    self.paged.warmup_fused(self.batch_size)
            return
        ids = np.full(_MERGE_CHUNK, -1, dtype=np.int32)
        zeros = np.zeros(_MERGE_CHUNK, dtype=np.int32)
        with self._dev_lock:
            self._acc = self._weighted_ingest(self._acc, ids, zeros, zeros)

    def attach(self, ms: MetricSystem, channel_capacity: int = 8) -> None:
        """Subscribe to a MetricSystem's raw broadcast; every interval's
        histograms are merged into the device accumulator on a bridge
        thread (the subscription boundary of the north star).

        The bridge survives strike-eviction: if a long device stall fills
        the channel and the reaper closes it, queued intervals are still
        drained (Channel.get drains before raising), the stall's dropped
        intervals stay dropped (shed-don't-block), and the bridge
        re-subscribes on a fresh channel (`tpu.BridgeEvictions` counts
        occurrences) instead of dying silently."""
        if self._attached is not None:
            raise RuntimeError("already attached")
        self._bridge_warmup()
        stop = threading.Event()
        ch = Channel(channel_capacity)
        ms.subscribe_to_raw_metrics(ch)
        self._bridge_ch = ch
        self._bridge_stop = stop

        def bridge():
            nonlocal ch
            while not stop.is_set():
                try:
                    raw = ch.get()
                except ChannelClosed:
                    with self._bridge_lock:
                        # detach() sets stop BEFORE taking this lock, so
                        # checking under it guarantees we never subscribe
                        # a channel detach won't see
                        if stop.is_set():
                            return
                        self._bridge_evictions += 1
                        ch = Channel(channel_capacity)
                        ms.subscribe_to_raw_metrics(ch)
                        self._bridge_ch = ch
                    import logging

                    logging.getLogger("loghisto_tpu").warning(
                        "bridge channel was strike-evicted (device stall?);"
                        " re-subscribed (eviction #%d)",
                        self._bridge_evictions,
                    )
                    continue
                try:
                    self.merge_raw(raw)
                except Exception:  # pragma: no cover - defensive
                    import logging

                    logging.getLogger("loghisto_tpu").exception(
                        "device merge failed for interval %s", raw.time
                    )

        if self.supervisor is not None:
            # a crashed bridge restarts with capped backoff; the clean
            # stop-event return ends the thread for good
            t = self.supervisor.spawn(bridge, "loghisto-tpu-bridge")
        else:
            t = threading.Thread(
                target=bridge, daemon=True, name="loghisto-tpu-bridge"
            )
            t.start()
        self._attached = (ms, t)

    def detach(self) -> None:
        if self._attached is None:
            return
        ms, t = self._attached
        self._bridge_stop.set()
        with self._bridge_lock:
            ch = self._bridge_ch
            self._bridge_ch = None
        if ch is not None:
            ms.unsubscribe_from_raw_metrics(ch)
            ch.close()
        # a supervised handle also needs its restart loop stopped, or a
        # backoff nap could outlive the join below
        stop_fn = getattr(t, "stop", None)
        if stop_fn is not None:
            stop_fn()
        t.join(timeout=5.0)
        self._attached = None

    # -- collection ----------------------------------------------------- #

    def collect(self, reset: bool = True) -> ProcessedMetricSet:
        """Extract statistics for every registered metric on device and
        return them with the standard naming scheme."""
        self.flush(force=True)
        labels, ps = [], []
        for label, p in self.percentiles.items():
            if 0.0 <= p <= 1.0:
                labels.append(label)
                ps.append(p)
        t0 = time.perf_counter()
        # Only the snapshot/swap needs the ingest lock; the device stats
        # round-trip runs outside it so producers never stall on collection.
        # (With reset=False the accumulator keeps flowing, so it must be
        # copied under the lock — a later flush() would otherwise donate
        # the very buffer stats are reading.)
        if self.paged is not None:
            # the paged stats program runs the per-codec gathered
            # extraction inside the store (sparse_cells_stats —
            # percentiles are bit-identical to the dense selection), with
            # the store's exact host spill already folded in, so no dense
            # combine step exists on this branch
            with self._dev_lock:
                stats = self.paged.stats(
                    np.asarray(ps, dtype=np.float64), reset=reset
                )
                if reset:
                    self._interval_ingested = 0
                    self._spilled_samples = 0
                    self.stats_snapshot = None
        else:
            with self._dev_lock:
                acc = self._acc
                spill = self._spill
                if reset:
                    # zeros_like preserves the NamedSharding in mesh mode
                    self._acc = jnp.zeros_like(acc)
                    self._interval_ingested = 0
                    self._spill = None
                    self._spilled_samples = 0
                    self.stats_snapshot = None
                else:
                    acc = acc + 0  # defensive copy; donation-safe snapshot
                    spill = None if spill is None else spill.copy()
            from loghisto_tpu.utils.trace import maybe_capture

            if spill is not None:
                # overflow-spill interval: counts exceed int32 on device,
                # so the whole extraction runs in exact int64 on host
                combined = spill + np.asarray(
                    self._finalize_acc(acc), dtype=np.int64
                )
                stats = dense_stats_np(
                    combined,
                    np.asarray(ps, dtype=np.float64),
                    self.config.bucket_limit,
                    self.config.precision,
                )
            else:
                with maybe_capture("loghisto_collect"):
                    stats = self._stats_fn(
                        self._finalize_acc(acc),
                        np.asarray(ps, dtype=np.float32),
                    )
        counts = np.asarray(stats["counts"])
        sums = np.asarray(stats["sums"])
        pcts = np.asarray(stats["percentiles"])
        self._last_aggregation_us = (time.perf_counter() - t0) * 1e6

        names = self.registry.names()
        # a concurrent grow() may have registered names beyond the rows of
        # this snapshot; they belong to the next interval
        names = names[: len(counts)]
        metrics: Dict[str, float] = {}
        with self._agg_lock:
            if reset:
                agg_view = self._agg  # interval closes: fold for real
            else:
                # peek: report lifetime+current without mutating, so
                # repeated collect(reset=False) can never double-fold
                agg_view = {
                    mid: list(entry) for mid, entry in self._agg.items()
                }
            # Fold EVERY nonzero row into the lifetime store, named or
            # not: record_batch with raw unregistered ids is a supported
            # pattern (checkpoints identity-map such rows), so a reset
            # must not discard their history — it surfaces as soon as
            # the row's name is registered.  Reporting stays name-gated.
            for mid in np.nonzero(counts)[0]:
                mid = int(mid)
                count = int(counts[mid])
                total = float(sums[mid])
                if mid < len(names) and names[mid] is not None:
                    name = names[mid]
                    metrics[f"{name}_count"] = float(count)
                    metrics[f"{name}_sum"] = total
                    metrics[f"{name}_avg"] = total / count
                    for label, value in zip(labels, pcts[mid]):
                        metrics[label % name] = float(value)
                # int seed: go_compat accumulates exact integers like the
                # reference's uint64 store; float mode promotes naturally.
                entry = agg_view.setdefault(mid, [0, 0])
                if self.config.go_compat:
                    # same uint64 semantics as the host tier's store
                    from loghisto_tpu.metrics import _UINT64_MASK

                    entry[0] = (entry[0] + int(total)) & _UINT64_MASK
                else:
                    entry[0] += total
                entry[1] += count
            for mid, entry in agg_view.items():
                name = names[mid] if mid < len(names) else None
                if name is None or entry[1] <= 0:
                    continue
                if self.config.go_compat:
                    avg = float(int(entry[0]) // int(entry[1]))
                else:
                    avg = entry[0] / entry[1]
                metrics[f"{name}_agg_avg"] = avg
                metrics[f"{name}_agg_count"] = float(entry[1])
                metrics[f"{name}_agg_sum"] = float(entry[0])

        import datetime as _dt

        return ProcessedMetricSet(
            time=_dt.datetime.now(tz=_dt.timezone.utc), metrics=metrics
        )

    # -- gauges ---------------------------------------------------------- #

    def register_device_gauges(self, ms: MetricSystem) -> None:
        """Register TPU gauges on a MetricSystem: HBM use and the last
        device aggregation time (SURVEY.md §5.5)."""

        def hbm_bytes() -> float:
            try:
                stats = jax.devices()[0].memory_stats()
                return float((stats or {}).get("bytes_in_use", 0))
            except Exception:
                return 0.0

        ms.register_gauge_func("tpu.HbmBytesInUse", hbm_bytes)
        ms.register_gauge_func(
            "tpu.LastAggregationUs", lambda: self._last_aggregation_us
        )
        if self._native_buf is not None:
            ms.register_gauge_func(
                "tpu.StagingDropped",
                lambda: float(self._native_buf.dropped),
            )
        ms.register_gauge_func(
            "tpu.SamplesShed", lambda: float(self._shed_samples)
        )
        ms.register_gauge_func(
            "tpu.BridgeEvictions", lambda: float(self._bridge_evictions)
        )
        ms.register_gauge_func(
            "tpu.RegistryShedSamples",
            lambda: float(self._registry_shed_samples),
        )
        ms.register_gauge_func(
            "tpu.SpilledSamples", lambda: float(self._spilled_samples)
        )
        if self.paged is not None:
            ms.register_gauge_func(
                "tpu.PagedOccupiedPages",
                lambda: float(self.paged.occupied_pages),
            )
            ms.register_gauge_func(
                "tpu.PagedFreePages", lambda: float(self.paged.free_pages)
            )
            ms.register_gauge_func(
                "tpu.PagedHbmBytes", lambda: float(self.paged.hbm_bytes())
            )
            ms.register_gauge_func(
                "tpu.PagedSpilledCells",
                lambda: float(self.paged.spilled_cells),
            )
            ms.register_gauge_func(
                "tpu.PagedLastCommitH2DBytes",
                lambda: float(self.paged.last_h2d_bytes),
            )
            # paging.* family (ISSUE 18): the per-shard arena view the
            # /healthz pool_saturation invariant alerts on.  Saturation
            # is shard-local — one hot metric shard spills while the
            # pod-wide tpu.PagedFreePages still looks roomy
            ms.register_gauge_func(
                "paging.PoolSaturation",
                lambda: float(self.paged.pool_saturation()),
            )
            ms.register_gauge_func(
                "paging.ShardFreePagesMin",
                lambda: float(min(self.paged.shard_free_pages())),
            )
            for k in range(self.paged._n_shards):
                ms.register_gauge_func(
                    f"paging.Shard{k}Occupancy",
                    lambda k=k: float(self.paged.shard_occupancy()[k]),
                )
            ms.register_gauge_func(
                "paging.AllocatedPages",
                lambda: float(self.paged.allocated_pages),
            )

            def _alloc_rate(state={"n": 0, "t": None}):
                # pages/s since the previous scrape: cumulative counts
                # need dashboard-side deltas; the reaper cadence makes
                # this self-describing instead
                import time as _time

                now = _time.monotonic()
                n = int(self.paged.allocated_pages)
                last_n, last_t = state["n"], state["t"]
                state["n"], state["t"] = n, now
                if last_t is None or now <= last_t:
                    return 0.0
                return max(0.0, (n - last_n) / (now - last_t))

            ms.register_gauge_func("paging.PageAllocRate", _alloc_rate)
            ms.register_gauge_func(
                "paging.SpilledCells",
                lambda: float(self.paged.spilled_cells),
            )
