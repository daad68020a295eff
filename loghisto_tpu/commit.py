"""IntervalCommitter: the single subscription that pays every device
consumer of an interval with one fused dispatch.

Before this module, a committed interval with retention enabled fanned
out across two independent bridges: the TPUAggregator's bridge thread
merged the interval's histograms via its weighted scatter launch, and
the TimeWheel's bridge re-resolved the same names, rebuilt the same
cell arrays, and dispatched one scatter per tier — >= 4 device launches
and >= 4 uploads of the same data per interval, each behind its own
lock.

The committer replaces both bridges with ONE subscription behind the
raw boundary:

  1. the interval's sparse histograms are resolved to ``(ids, codec
     bucket, weight)`` cells ONCE (the aggregator's registry/growth/shed
     policy applies — the wheel shares the registry by construction);
  2. the cells are staged through fresh host arrays and an async H2D
     upload (``ops.commit.CellStagingRing``) so the next chunk/interval's
     transfer overlaps the in-flight commit dispatch;
  3. one jitted donated-carry program (``ops.commit.make_fused_commit_fn``)
     folds the cells into the aggregator accumulator AND every tier's
     open slot — slot indices and ring-wrap keep factors ride along as
     traced int32 operands, so tier rotation never recompiles.

A typical interval is therefore 1 dispatch + 1 upload, bounded at
ceil(cells / COMMIT_CHUNK) dispatches for pathological cardinality
(tests/test_commit.py pins the <= 2 dispatch guarantee and bit-identical
parity with the fan-out path).

Overflow contract: intervals that would break the aggregator's int32
guarantee (interval total past ``spill_threshold``, or any single cell
weight >= 2^30) take the aggregator's exact host-spill machinery and
the wheel's fan-out scatter for that interval — correctness first, the
fused program only ever runs inside the proven int32 envelope.

Lock ordering: the committer is the only code that holds the
aggregator's ``_dev_lock`` and the wheel's lock simultaneously, always
acquired in that order (device state, then wheel state); neither
subsystem ever takes them in reverse, so the pairing cannot deadlock.

Self-metrics: dispatches/interval, H2D bytes/interval, and a commit
latency histogram are exported as ``commit.*`` gauges through the
normal pipeline (``register_gauges``), plus a ``commit.LatencyUs``
histogram recorded into the attached MetricSystem each interval.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from loghisto_tpu.channel import ChannelClosed, ResilientSubscription
from loghisto_tpu.metrics import MetricSystem, RawMetricSet
from loghisto_tpu.obs.spans import NULL_RECORDER, LatencyHistogram
from loghisto_tpu.ops.commit import (
    COMMIT_CHUNK,
    CellStagingRing,
    PagedTripleRing,
    make_fused_commit_fn,
    make_fused_commit_snapshot_fn,
    make_paged_fused_commit_fn,
    make_paged_fused_commit_snapshot_fn,
    make_sharded_fused_commit_fn,
    make_sharded_fused_commit_snapshot_fn,
    make_sharded_paged_fused_commit_fn,
    make_sharded_paged_fused_commit_snapshot_fn,
)
from loghisto_tpu.parallel.mesh import (
    STREAM_AXIS,
    cell_sharding,
    sharded_zeros,
    triple_sharding,
)
from loghisto_tpu.window.snapshot import AccSnapshot
from loghisto_tpu.window.store import trailing_mask

logger = logging.getLogger("loghisto_tpu")


def commit_incompatibility(aggregator, wheel) -> Optional[str]:
    """Why this (aggregator, wheel) pair cannot share one fused commit
    program, or None when it can.  The fused program scatters ONE cell
    array into both carries, so the pair must agree on row ids (shared
    registry) and bucket geometry (bucket_limit/precision).

    r18: paged aggregators no longer refuse — the paged fused-commit
    family (``ops.commit.make_paged_fused_commit_fn``) carries the pool
    in the accumulator's place and scatters the interval's
    host-translated triples into it in the same dispatch as the tier
    rings; only the anomaly pairing (dense [M, B] interval-histogram
    carry) stays dense-only, checked in the constructor."""
    if aggregator.registry is not wheel.registry:
        return "aggregator and wheel use different registries"
    if aggregator.config.bucket_limit != wheel.config.bucket_limit:
        return (
            f"bucket_limit mismatch (aggregator "
            f"{aggregator.config.bucket_limit}, wheel "
            f"{wheel.config.bucket_limit})"
        )
    if aggregator.config.precision != wheel.config.precision:
        return (
            f"precision mismatch (aggregator {aggregator.config.precision},"
            f" wheel {wheel.config.precision})"
        )
    if getattr(aggregator, "mesh", None) is not getattr(wheel, "mesh", None):
        return (
            "aggregator and wheel are sharded over different meshes (the "
            "fused program's carries must share one row sharding)"
        )
    return None


class IntervalCommitter:
    """One-subscription interval commit for a (TPUAggregator, TimeWheel)
    pair — see the module docstring for the design.  ``chunk`` is the
    fixed commit launch width (tests shrink it to exercise multi-chunk
    intervals and pad sentinels)."""

    def __init__(
        self,
        aggregator,
        wheel,
        chunk: int = COMMIT_CHUNK,
        lifecycle=None,
        anomaly=None,
    ):
        reason = commit_incompatibility(aggregator, wheel)
        if reason is not None:
            raise ValueError(f"fused commit unavailable: {reason}")
        if anomaly is not None and not wheel.snapshots_enabled:
            raise ValueError(
                "drift engine requires commit-time snapshots: the EWMA "
                "bank update rides the final-chunk snapshot program and "
                "scoring consumes the published window CDFs"
            )
        self.aggregator = aggregator
        self.wheel = wheel
        self.chunk = int(chunk)
        # a LifecycleManager threads its donated last_active carry (and
        # a traced epoch) through the SAME fused programs — activity
        # tracking costs zero extra dispatches on the fused path
        self.lifecycle = lifecycle
        # an AnomalyManager likewise threads its donated interval
        # histogram + EWMA baseline banks; the bank decay step runs in
        # the final-chunk snapshot program — zero extra dispatches
        self.anomaly = anomaly
        track = lifecycle is not None
        track_b = anomaly is not None
        self.paged = getattr(aggregator, "paged", None)
        if anomaly is not None and self.paged is not None:
            raise ValueError(
                "drift engine requires the dense accumulator: the "
                "interval-histogram and EWMA baseline-bank carries are "
                "dense [M, B] tensors, which paged storage exists to "
                "avoid keeping"
            )
        self.mesh = getattr(aggregator, "mesh", None)
        staging_sharding = None
        trip_sharding = None
        tiers_n = len(wheel._tiers)
        if self.mesh is not None:
            # sharded fused path: identical operand protocol, but the
            # program runs under shard_map — staged cells arrive
            # stream-sharded and ONE psum per chunk merges the deltas
            # before the shard-local carry updates
            n_stream = self.mesh.shape[STREAM_AXIS]
            if self.chunk % n_stream:
                raise ValueError(
                    f"commit chunk {self.chunk} not divisible by the mesh "
                    f"stream axis ({n_stream}): staged cell chunks always "
                    "pad to the full width, which must split evenly"
                )
            if self.paged is not None:
                self._fused = make_sharded_paged_fused_commit_fn(
                    self.mesh, self.paged.shard_pages, tiers_n, track
                )
                self._fused_snap = make_sharded_paged_fused_commit_snapshot_fn(
                    self.mesh, self.paged.shard_pages, tiers_n,
                    wheel.config.bucket_limit, wheel.config.precision,
                    wheel.merge_path, track_activity=track,
                )
                trip_sharding = triple_sharding(self.mesh)
            else:
                self._fused = make_sharded_fused_commit_fn(
                    self.mesh, tiers_n, track, track_b
                )
                self._fused_snap = make_sharded_fused_commit_snapshot_fn(
                    self.mesh, tiers_n, wheel.config.bucket_limit,
                    wheel.config.precision, wheel.merge_path,
                    track_activity=track, track_baseline=track_b,
                )
            staging_sharding = cell_sharding(self.mesh)
        elif self.paged is not None:
            # paged pair (r18): the pool is the donated accumulator
            # carry; each chunk's cells also translate to (slot, offset,
            # count) triples on the host (under _dev_lock, so the page
            # table can allocate) and ride the same dispatch
            self._fused = make_paged_fused_commit_fn(tiers_n, track)
            self._fused_snap = make_paged_fused_commit_snapshot_fn(
                tiers_n, wheel.config.bucket_limit,
                wheel.config.precision, wheel.merge_path,
                track_activity=track,
            )
        else:
            self._fused = make_fused_commit_fn(tiers_n, track, track_b)
            # final-chunk variant: same fold + the query engine's snapshot
            # emission (per-tier window CDFs + the acc CDF) in ONE dispatch
            self._fused_snap = make_fused_commit_snapshot_fn(
                tiers_n, wheel.config.bucket_limit,
                wheel.config.precision, wheel.merge_path,
                track_activity=track, track_baseline=track_b,
            )
        self._staging = CellStagingRing(width=self.chunk,
                                        sharding=staging_sharding)
        self._triples = (
            PagedTripleRing(width=self.chunk,
                            sharding=trip_sharding)
            if self.paged is not None else None
        )
        # the one chunk whose translate ran but whose dispatch hasn't
        # succeeded yet — the failure handler's double-count guard
        self._trip_inflight = None

        # self-metrics (ISSUE 2): per-interval dispatch/H2D accounting.
        # The latency store IS one of the system's own log-bucketed
        # histograms (ISSUE 9 dogfooding): the LatencyP50Us/P99Us gauges
        # are served by the same codec + CDF walk as every user metric,
        # not an ad-hoc bounded host reservoir.
        self._metrics_lock = threading.Lock()
        self.intervals_committed = 0
        self.fused_intervals = 0
        self.fanout_intervals = 0  # spill or policy fan-outs
        self.last_dispatches = 0
        self.last_h2d_bytes = 0
        self.last_uploads = 0
        self._latency_hist = LatencyHistogram(wheel.config.precision)

        # observability (ISSUE 9): span ring + dogfooding + watchdog,
        # all installed by TPUMetricSystem(observability=...); the
        # defaults cost two no-op calls per site
        self.obs_recorder = NULL_RECORDER
        self.self_observer = None
        self.watchdog = None
        # fleet observability (ISSUE 12): the federation receiver's
        # note_publish — pending freshness samples complete the moment
        # the interval snapshot becomes queryable
        self.freshness_hook = None

        # resilience (ISSUE 10), installed by TPUMetricSystem
        # (resilience=...): the supervisor respawns a crashed bridge,
        # the breaker pins the fan-out/spill path after repeated device
        # failures, the injector scripts chaos faults (None = one
        # attribute test per site), and the recovery manager checkpoints
        # on the bridge cadence
        self.supervisor = None
        self.breaker = None
        self.fault_injector = None
        self.recovery = None

        self._ms: Optional[MetricSystem] = None
        self._sub: Optional[ResilientSubscription] = None
        self._thread: Optional[threading.Thread] = None

    # -- cell construction ---------------------------------------------- #

    def _cells_from_raw(self, raw: RawMetricSet):
        """Sparse interval histograms -> (ids int32, codec bucket int64,
        weight int64), resolved ONCE through the aggregator's registry
        policy (growth up to max_metrics, shed past it).  Shed samples
        are mirrored into the wheel's shed counter so both subsystems'
        gauges stay truthful with a single bridge."""
        agg = self.aggregator
        ids, bidx, weights = [], [], []
        shed = 0
        for name, bucket_counts in raw.histograms.items():
            mid = agg._id_for(name, samples=sum(bucket_counts.values()))
            if mid < 0:
                shed += sum(bucket_counts.values())
                continue
            for bucket, count in bucket_counts.items():
                ids.append(mid)
                bidx.append(bucket)
                weights.append(count)
        if shed:
            with self.wheel._lock:
                self.wheel.shed_samples += shed
        if not ids:
            return None
        return (
            np.asarray(ids, dtype=np.int32),
            np.asarray(bidx, dtype=np.int64),
            np.asarray(weights, dtype=np.int64),
        )

    def _dense_cells(self, cells):
        """(ids, codec bucket, int64 weight) -> the wheel's dense int32
        triplet, bit-for-bit the same conversion as
        TimeWheel._cells_from_raw (clip to the dense range; clip weights
        to the int32 wire contract)."""
        ids, bidx64, w64 = cells
        bl = self.wheel.config.bucket_limit
        idx = (np.clip(bidx64, -bl, bl) + bl).astype(np.int32)
        w32 = np.minimum(w64, np.int64(2**31 - 1)).astype(np.int32)
        return ids, idx, w32

    # -- the commit ----------------------------------------------------- #

    def commit(self, raw: RawMetricSet, duration: Optional[float] = None):
        """Land one interval on the aggregator AND every retention tier.
        Returns the path taken ("fused", "fanout", or "empty")."""
        rec = self.obs_recorder
        # adopt the reaper-minted interval sequence number: every span
        # recorded until the next commit attributes to this interval
        seq = rec.begin_interval(raw.seq)
        t0_ns = time.perf_counter_ns()
        t0 = time.perf_counter()
        wheel = self.wheel
        dur = (
            float(duration) if duration is not None
            else float(raw.duration) if raw.duration is not None
            else wheel.interval
        )
        up0 = self._staging.uploads
        b0 = self._staging.bytes_uploaded
        with rec.span("commit.cells", seq):
            cells = self._cells_from_raw(raw)
        if cells is None:
            # cell-less interval: slot rotation/durations still advance
            # (a reopened slot's clear is the only possible dispatch)
            wheel.push_cells(None, raw, dur)
            mode, dispatches = "empty", 0
        else:
            mode, dispatches = self._commit_cells(cells, raw, dur)
        if self.anomaly is not None:
            # score the freshly published snapshot BEFORE the hooks run,
            # so distribution_drift rules evaluate THIS interval's
            # scores, not last interval's — same bridge thread, so no
            # device state races with the commit that just landed
            self.anomaly.on_interval(raw)
        wheel.run_hooks(raw)
        if self.lifecycle is not None:
            # policy tick OUTSIDE every lock: eviction/compaction work
            # never extends the commit critical section, and sharing the
            # bridge thread means no interval's cells are in flight
            # while rows are folded or repacked
            self.lifecycle.on_interval()
        us = (time.perf_counter() - t0) * 1e6
        # the end-to-end span every stage span above nests inside
        rec.record("commit.e2e", t0_ns, time.perf_counter_ns(), seq)
        with self._metrics_lock:
            self.intervals_committed += 1
            if mode == "fused":
                self.fused_intervals += 1
            elif mode == "fanout":
                self.fanout_intervals += 1
            self.last_dispatches = dispatches
            self.last_uploads = self._staging.uploads - up0
            self.last_h2d_bytes = self._staging.bytes_uploaded - b0
        self._latency_hist.add(us)
        if self._ms is not None:
            # the commit latency histogram rides the normal pipeline,
            # so exporters/retention see it like any other metric
            try:
                self._ms.histogram("commit.LatencyUs", us)
            except Exception:  # pragma: no cover - defensive
                pass
        if self.watchdog is not None:
            self.watchdog.note_commit(seq)
        if self.freshness_hook is not None:
            # federated frames applied before this commit are now
            # queryable: close their record→queryable freshness samples
            try:
                self.freshness_hook(seq)
            except Exception:  # pragma: no cover - defensive
                pass
        if self.self_observer is not None:
            # dogfooding: this interval's closed spans re-enter through
            # the normal histogram() path as obs.<stage>.LatencyUs
            self.self_observer.on_interval(seq)
        if self.recovery is not None:
            # watermark + cadenced checkpoint ride the bridge thread,
            # never the ingest path (resilience/recovery.py)
            self.recovery.on_commit(raw)
        return mode

    def _commit_cells(self, cells, raw: RawMetricSet, dur: float):
        """Dispatch one interval's cells.  Returns (mode, dispatches)."""
        agg, wheel = self.aggregator, self.wheel
        ids, bidx64, w64 = cells
        total = int(w64.sum(dtype=np.int64))
        # an open breaker pins the fan-out/spill path: after repeated
        # device failures every fused attempt costs a donated-carry
        # rebuild, so stop attempting until the open window passes and a
        # half-open trial succeeds (resilience/recovery.py)
        pinned = self.breaker is not None and self.breaker.is_open()
        with agg._dev_lock:
            if (
                pinned
                or agg._interval_ingested + total >= agg.spill_threshold
                or int(w64.max()) >= 1 << 30
            ):
                # int32-overflow envelope exceeded: the aggregator side
                # takes its exact host-spill machinery; the tiers take
                # the fan-out scatter below (their own int32 clip
                # contract).  Rare by construction — the guarantee wins
                # over the dispatch count for this interval.
                agg._merge_cells_locked(ids, bidx64, w64)
                agg.stats_snapshot = None  # spill path; handle is stale
                if self.lifecycle is not None:
                    # spill intervals can't fuse the activity stamp;
                    # one tiny touch dispatch keeps TTLs truthful
                    self.lifecycle.touch_locked(ids)
                fused = False
            else:
                with wheel._lock:
                    dispatches = self._fused_dispatch_locked(
                        cells, raw, dur
                    )
                fused = True
        if fused:
            return "fused", dispatches
        dense = self._dense_cells(cells)
        wheel.push_cells(dense, raw, dur)
        # estimate: one weighted-scatter chunk ladder for the aggregator
        # plus one per tier (slot clears excluded)
        nchunks = -(-len(ids) // self.chunk)
        return "fanout", nchunks * (1 + len(wheel._tiers))

    def _post_close_masks(self, t, slot: int, dur: float, windows):
        """Snapshot view masks for one tier as they will read AFTER this
        interval's close-out, computed BEFORE the commit dispatches (the
        masks ride the fused program as operands).  Simulates
        ``_tier_close_locked``'s metadata fold on copies — written flag,
        duration accrual, slot rotation — and runs the same
        ``trailing_mask`` walk the live query path uses."""
        written = t.written.copy()
        durations = t.durations.copy()
        written[slot] = True
        durations[slot] += dur
        in_slot = t.in_slot + 1
        cur = slot
        if in_slot >= t.spec.res:
            cur = (slot + 1) % t.spec.slots
            in_slot = 0
        return np.stack([
            trailing_mask(written, durations, cur, in_slot,
                          t.spec.slots, w)
            for w in windows
        ])

    def _fused_dispatch_locked(self, cells, raw: RawMetricSet, dur: float):
        """The fused path.  Caller holds agg._dev_lock THEN wheel._lock
        (the committer's documented ordering).  Chunks the cells through
        the staging ring and the single fused program; first chunk
        carries the ring-wrap keep factors, later chunks keep
        everything; the FINAL chunk runs the snapshot-emitting variant,
        so the query engine's per-tier window CDFs and the aggregator's
        acc CDF cost zero extra dispatches.  Returns the dispatch
        count."""
        agg, wheel = self.aggregator, self.wheel
        ids, idx, w32 = self._dense_cells(cells)
        w64 = cells[2]
        tiers = wheel._tiers
        slots_host = [t.slot for t in tiers]
        keeps_host = [
            0 if wheel._tier_open_locked(t, s) else 1
            for t, s in zip(tiers, slots_host)
        ]
        slots = np.asarray(slots_host, dtype=np.int32)
        keeps = np.asarray(keeps_host, dtype=np.int32)
        ones = np.ones_like(keeps)
        wheel._note_interval_locked(raw.time, (ids, idx, w32))
        lc = self.lifecycle
        an = self.anomaly
        if lc is not None:
            la = lc.ensure_capacity_locked(agg.num_metrics)
            epoch = np.int32(wheel.intervals_pushed)
        if an is not None:
            ihist, banks = an.ensure_capacity_locked(agg.num_metrics)
            bank = an.bank_for(raw.time)
        emit = wheel.snapshots_enabled
        if emit:
            windows = wheel._view_windows_locked()
            masks = tuple(
                self._post_close_masks(t, s, dur, windows)
                for t, s in zip(tiers, slots_host)
            )
        n = len(ids)
        dispatches = 0
        applied = 0
        reset_tiers = ()
        payloads = acc_payload = None
        paged = self.paged
        bl = wheel.config.bucket_limit
        try:
            rec = self.obs_recorder
            inj = self.fault_injector
            for off in range(0, n, self.chunk):
                if inj is not None:
                    # chaos hook: a scripted device failure fires inside
                    # the try so _on_fused_failure_locked recovers it
                    # exactly like an organic dispatch failure
                    inj.check("commit.dispatch")
                take = min(self.chunk, n - off)
                with rec.span("commit.upload"):
                    dev_ids, dev_idx, dev_w = self._staging.stage(
                        ids[off:off + take],
                        idx[off:off + take],
                        w32[off:off + take],
                    )
                    if paged is not None:
                        # host translate against the page table (both
                        # locks held — allocation is safe), then stage
                        # the triples through their own overlap ring.
                        # Cells translate can't place (arena saturated,
                        # no overflow row) land in the exact host spill
                        # INSIDE translate; the in-flight record keeps
                        # the failure handler from re-spilling them.
                        pk = np.empty((take, 3), dtype=np.int32)
                        pk[:, 0] = ids[off:off + take]
                        pk[:, 1] = np.clip(
                            cells[1][off:off + take], -bl, bl
                        )
                        pk[:, 2] = w32[off:off + take]
                        trip, _, _ = paged.translate(pk)
                        self._trip_inflight = (trip, take)
                        dev_trip = self._triples.stage(trip)
                chunk_keeps = keeps if dispatches == 0 else ones
                final = emit and off + take >= n
                # operand ordering per make_fused_commit_fn /
                # make_fused_commit_snapshot_fn (and their paged twins):
                # carries first (acc-or-pool, rings, [la], [ihist],
                # [banks]), then cells, [then triples], then the traced
                # scalars ([epoch], [masks], [ifirst, bank, decay,
                # min_count])
                args = [
                    paged._pool if paged is not None else agg._acc,
                    tuple(t.ring for t in tiers),
                ]
                if lc is not None:
                    args.append(la)
                if an is not None:
                    args.append(ihist)
                    if final:
                        args.append(banks)
                args += [slots, chunk_keeps, dev_ids, dev_idx, dev_w]
                if paged is not None:
                    args.append(dev_trip)
                if lc is not None:
                    args.append(epoch)
                if final:
                    args.append(masks)
                if an is not None:
                    # 0 on the interval's FIRST chunk clears the
                    # previous interval's histogram; later chunks keep
                    # accumulating into it
                    args.append(np.int32(0 if dispatches == 0 else 1))
                    if final:
                        args += [bank, an.decay32, an.min_count32]
                with rec.span("commit.dispatch"):
                    out = iter(
                        (self._fused_snap if final else self._fused)(*args)
                    )
                if paged is not None:
                    paged._pool = next(out)
                else:
                    agg._acc = next(out)
                for t, r in zip(tiers, next(out)):
                    t.ring = r
                if lc is not None:
                    la = next(out)
                    lc.store_carry_locked(la)
                if an is not None:
                    ihist = next(out)
                    if final:
                        banks = next(out)
                    an.store_carry_locked(ihist, banks)
                if final:
                    payloads = next(out)
                    # the paged snapshot variant emits no acc payload —
                    # pool counts live behind per-row codecs, served by
                    # the paged query engine instead
                    acc_payload = next(out) if paged is None else None
                dispatches += 1
                applied = off + take
                self._trip_inflight = None
                agg._device_down_until = 0.0
                agg._interval_ingested += int(
                    w64[off:off + take].sum(dtype=np.int64)
                )
            if rec.enabled and dispatches:
                # only when observing: wait out the async dispatches so
                # the device-sync span carries the real device time
                # instead of it leaking into whoever touches the carries
                # next (a device failure here takes the normal recovery)
                with rec.span("commit.device_sync"):
                    jax.block_until_ready(
                        paged._pool if paged is not None else agg._acc
                    )
            if self.breaker is not None:
                # closes a half-open breaker after a successful trial;
                # failures are recorded in ONE place (the aggregator's
                # _on_device_failure_locked) so fan-out hooks can't
                # multi-count a single physical failure
                self.breaker.record_success()
        except Exception:
            payloads = acc_payload = None
            reset_tiers = self._on_fused_failure_locked(
                cells, applied
            )
        for t, s in zip(tiers, slots_host):
            if t in reset_tiers:
                continue  # recovery already re-zeroed its metadata
            wheel._tier_close_locked(t, s, raw.rates, dur)
        if payloads is not None and not reset_tiers:
            # the tier metadata now matches the simulated post-close
            # state the masks encoded; publish the lock-free handles
            with self.obs_recorder.span("commit.snapshot_publish"):
                wheel.publish_snapshot_locked(tuple(
                    wheel._tier_snapshot_locked(ti, windows, masks[ti],
                                                payloads[ti])
                    for ti in range(len(tiers))
                ))
                if acc_payload is not None:
                    agg.stats_snapshot = AccSnapshot(
                        epoch=wheel.intervals_pushed,
                        cdf=acc_payload["cdf"],
                        counts=acc_payload["counts"],
                        sums=acc_payload["sums"],
                    )
        return dispatches

    def _on_fused_failure_locked(self, cells, applied: int):
        """Device-failure recovery for the fused path (both locks held,
        called from inside the except handler).  The aggregator's
        handler recovers a consumed accumulator and arms the cooldown;
        consumed tier rings are rebuilt empty (retention history for
        that tier resets — logged); the UNAPPLIED cell remainder folds
        into the exact host spill, mirroring _merge_cells_locked's
        accounting so no sample is lost or double-counted on the
        aggregator side.  Returns the tiers whose state was reset."""
        agg, wheel = self.aggregator, self.wheel
        agg._on_device_failure_locked()  # also drops agg.stats_snapshot
        if self.lifecycle is not None:
            # the activity carry was donated into the failed dispatch;
            # rebuild it stamped "just active" (delays evictions only)
            self.lifecycle.on_device_failure_locked()
        if self.anomaly is not None:
            # likewise the interval histogram / baseline banks: rebuild
            # cold (drift detection restarts its EWMA warm-up — scores
            # stay floored until baselines re-establish, never wrong)
            self.anomaly.on_device_failure_locked()
        # the published wheel handle may describe rings this failure
        # consumed; queries fall back to locked recompute until the next
        # successful commit republishes
        wheel.invalidate_snapshot_locked()
        reset = []
        for t in wheel._tiers:
            if getattr(t.ring, "is_deleted", lambda: False)():
                t.ring = sharded_zeros(
                    (t.spec.slots, wheel.num_metrics,
                     wheel.config.num_buckets),
                    wheel._sharding,
                )
                t.written[:] = False
                t.durations[:] = 0.0
                t.rates = [dict() for _ in range(t.spec.slots)]
                t.slot = 0
                t.in_slot = 0
                reset.append(t)
        if reset:
            logger.error(
                "fused commit failure consumed %d tier ring(s); their "
                "retention history was reset", len(reset),
            )
        ids, bidx64, w64 = cells
        start = applied
        trip_inflight, self._trip_inflight = self._trip_inflight, None
        if self.paged is not None and trip_inflight is not None:
            # the failed chunk's translate already ran: its host-spill
            # portion was applied there, so only its DEVICE portion (the
            # translated triples) re-lands, via the page-table inverse —
            # spilling the chunk's cells would double-count
            trip, take_failed = trip_inflight
            self.paged.spill_triples(trip)
            start = applied + take_failed
        if start < len(ids):
            agg._spill_add_cells_locked(
                ids[start:], bidx64[start:], w64[start:]
            )
        return tuple(reset)

    # -- warmup / lifecycle --------------------------------------------- #

    def warmup(self) -> None:
        """Pre-compile the fused executable at THE commit shape (all
        pads — numerically a no-op), same rationale as the aggregator's
        _bridge_warmup: the first real interval must not pay the cold
        XLA compile while the reaper fills the freshly subscribed
        channel."""
        agg, wheel = self.aggregator, self.wheel
        lc = self.lifecycle
        an = self.anomaly
        empty = np.empty(0, dtype=np.int32)

        def run(fn, final):
            dev_ids, dev_idx, dev_w = self._staging.stage(
                empty, empty, empty
            )
            args = [
                self.paged._pool if self.paged is not None else agg._acc,
                tuple(t.ring for t in tiers),
            ]
            if lc is not None:
                args.append(la)
            if an is not None:
                args.append(ihist)
                if final:
                    args.append(banks)
            args += [slots, keeps, dev_ids, dev_idx, dev_w]
            if self.paged is not None:
                # all-pad triple chunk (slot -1 drops): warms the paged
                # program at THE fixed staging width
                args.append(
                    self._triples.stage(np.empty((0, 3), dtype=np.int32))
                )
            if lc is not None:
                args.append(epoch)
            if final:
                args.append(masks)
            if an is not None:
                # ifirst=1 with zero cells: the (all-zero) interval
                # histogram carries through unchanged, and zero counts
                # never clear the min_count bar — numerically a no-op
                args.append(np.int32(1))
                if final:
                    args += [an.bank_for(None), an.decay32,
                             an.min_count32]
            out = iter(fn(*args))
            if self.paged is not None:
                self.paged._pool = next(out)
            else:
                agg._acc = next(out)
            for t, r in zip(tiers, next(out)):
                t.ring = r
            if lc is not None:
                lc.store_carry_locked(next(out))
            if an is not None:
                ih = next(out)
                bk = next(out) if final else banks
                an.store_carry_locked(ih, bk)
                return ih, bk
            return None, None

        with agg._dev_lock:
            with wheel._lock:
                tiers = wheel._tiers
                slots = np.asarray([t.slot for t in tiers], dtype=np.int32)
                keeps = np.ones(len(tiers), dtype=np.int32)
                if lc is not None:
                    la = lc.ensure_capacity_locked(agg.num_metrics)
                    epoch = np.int32(wheel.intervals_pushed)
                if an is not None:
                    ihist, banks = an.ensure_capacity_locked(
                        agg.num_metrics
                    )
                ihist, banks = run(self._fused, final=False)
                if lc is not None:
                    la = lc.ensure_capacity_locked(agg.num_metrics)
                if wheel.snapshots_enabled:
                    # warm the final-chunk (snapshot-emitting) variant at
                    # the same shapes; all-False masks make the payloads
                    # numerically empty, so nothing is published
                    windows = wheel._view_windows_locked()
                    masks = tuple(
                        np.zeros((len(windows), t.spec.slots), dtype=bool)
                        for t in tiers
                    )
                    run(self._fused_snap, final=True)

    def attach(self, ms: MetricSystem, channel_capacity: int = 64) -> None:
        """Subscribe ONCE behind the raw boundary for both consumers —
        strike-eviction resilient, same recovery contract as the
        journal/exporters.

        The bridge is the system's only path from raw interval to
        queryable snapshot: an interval shed here permanently loses its
        histogram samples.  The channel is therefore deep enough to ride
        out multi-second scheduler stalls (64 intervals) and let the
        bridge catch up afterwards; sustained overload still sheds
        rather than blocking the reaper."""
        if self._thread is not None:
            raise RuntimeError("already attached")
        self.warmup()
        self._ms = ms
        self._sub = ResilientSubscription(
            ms.subscribe_to_raw_metrics,
            ms.unsubscribe_from_raw_metrics,
            channel_capacity,
        )
        sub = self._sub

        def bridge():
            while True:
                try:
                    raw = sub.get()
                except ChannelClosed:
                    return
                inj = self.fault_injector
                if inj is not None:
                    # chaos hook OUTSIDE the per-commit net: a scripted
                    # bridge crash escapes to the supervisor's restart
                    # loop (the per-commit except would swallow it)
                    inj.check("commit.bridge")
                try:
                    self.commit(raw)
                except Exception:  # pragma: no cover - defensive
                    logger.exception(
                        "fused interval commit failed for %s", raw.time
                    )

        if self.supervisor is not None:
            # crashed bridges restart with capped backoff; a clean
            # ChannelClosed return (detach) ends the thread for good
            self._thread = self.supervisor.spawn(bridge, "loghisto-commit")
        else:
            self._thread = threading.Thread(
                target=bridge, daemon=True, name="loghisto-commit"
            )
            self._thread.start()

    def detach(self) -> None:
        if self._sub is not None:
            self._sub.close()
            self._sub = None
        if self._thread is not None:
            # a supervised handle also needs its restart loop stopped —
            # otherwise a backoff nap could outlive the join below
            stop = getattr(self._thread, "stop", None)
            if stop is not None:
                stop()
            self._thread.join(timeout=5.0)
            self._thread = None

    # -- gauges ---------------------------------------------------------- #

    @property
    def bridge_evictions(self) -> int:
        return self._sub.evictions if self._sub is not None else 0

    def _latency_pct(self, q: float) -> float:
        # served from the system's own log-bucketed state (ISSUE 9):
        # same codec + CDF walk as any user histogram, full lifetime
        # history instead of a bounded reservoir
        return self._latency_hist.percentile(q)

    def register_gauges(self, ms: MetricSystem) -> None:
        """Export the commit-path self-metrics through the normal gauge
        pipeline: dispatches and H2D bytes per interval (the quantities
        the fused design exists to collapse), the fused/fan-out interval
        split, and the commit latency distribution."""
        ms.register_gauge_func(
            "commit.DispatchesPerInterval",
            lambda: float(self.last_dispatches),
        )
        ms.register_gauge_func(
            "commit.H2DBytesPerInterval",
            lambda: float(self.last_h2d_bytes),
        )
        ms.register_gauge_func(
            "commit.CellUploadsPerInterval",
            lambda: float(self.last_uploads),
        )
        ms.register_gauge_func(
            "commit.FusedIntervals", lambda: float(self.fused_intervals)
        )
        ms.register_gauge_func(
            "commit.FanoutIntervals", lambda: float(self.fanout_intervals)
        )
        ms.register_gauge_func(
            "commit.LatencyP50Us", lambda: self._latency_pct(50.0)
        )
        ms.register_gauge_func(
            "commit.LatencyP99Us", lambda: self._latency_pct(99.0)
        )
        ms.register_gauge_func(
            "commit.BridgeEvictions", lambda: float(self.bridge_evictions)
        )
