"""TimeWheel: device-resident windowed retention store.

The live stack aggregates one interval at a time and the data is gone
after broadcast; the wheel is the retention tier that makes "p99 over the
last 5 minutes" a device primitive.  It subscribes behind the existing
Raw/Processed boundary (attach(), same contract as TPUAggregator) and
keeps, per resolution tier, a device-resident ring of dense
``int32[slots, num_metrics, num_buckets]`` interval histograms plus
host-side per-slot counter-delta and duration vectors.

Multi-resolution tiers (default 60 slots x 1 interval, 60 x 1min,
24 x 1h in units of the base interval): every interval's bucket cells
scatter into each tier's open slot, so tier "promotion" IS a
bucket-tensor add — the log-bucket representation merges exactly under
addition, which is why downsampling loses nothing but slot-boundary
resolution (total counts are preserved bit-for-bit; the property test in
tests/test_window.py pins this).

``query(pattern, window, percentiles)`` picks the finest tier covering
the window and runs ONE fused device reduction over the ring axis
(ops/window.py) — no per-interval host loop, cost independent of window
length.  Under a ("stream", "metric") mesh the rings are laid out
metric-row-sharded and the reduction partitions row-wise with zero
collectives.

HBM budget: ``sum(tier.slots) * num_metrics * num_buckets * 4`` bytes
(``hbm_bytes()``); size ``bucket_limit``/tiers to the deployment — the
wheel takes its own MetricConfig so retention can run a narrower bucket
range than the live accumulator.
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import fnmatch
import logging
import math
import threading
from typing import Callable, Dict, Iterable, List, Mapping, Optional, \
    NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from loghisto_tpu.config import MetricConfig
from loghisto_tpu.channel import ChannelClosed, ResilientSubscription
from loghisto_tpu.labels.groupby import GroupStats, assign_groups, \
    equidepth_ranks
from loghisto_tpu.labels.selector import is_selector, parse_selector
from loghisto_tpu.metrics import MetricSystem, RawMetricSet
from loghisto_tpu.obs.spans import NULL_RECORDER
from loghisto_tpu.ops.stats import make_group_query_fn, \
    make_snapshot_query_fn
from loghisto_tpu.ops.window import (
    make_window_snapshot_fn,
    make_window_stats_fn,
    resolve_merge_path,
)
from loghisto_tpu.parallel.mesh import sharded_zeros
from loghisto_tpu.registry import MetricRegistry, RegistryFullError
from loghisto_tpu.window.snapshot import (
    QueryPlanCache,
    Snapshot,
    SnapshotView,
    TierSnapshot,
)

logger = logging.getLogger("loghisto_tpu")

# Fixed scatter launch width (same design as the aggregator's bridge
# merges): one compiled executable per tier serves every interval.
_CELL_CHUNK = 1 << 16

# drop sentinel: far out of row range, every scatter mode="drop" sheds it
_DROP_ID = np.int32(2**30)


class TierSpec(NamedTuple):
    """One retention tier: ``slots`` ring entries of ``res`` base
    intervals each (res=1 -> per-interval, res=60 at a 1s interval ->
    per-minute)."""

    slots: int
    res: int


DEFAULT_TIERS: tuple[TierSpec, ...] = (
    TierSpec(60, 1),      # e.g. 60 x 1s
    TierSpec(60, 60),     # 60 x 1m
    TierSpec(24, 3600),   # 24 x 1h
)

DEFAULT_QUERY_PERCENTILES: tuple[float, ...] = (0.5, 0.9, 0.99, 0.999)


def pct_key(q: float) -> str:
    """0.99 -> "p99", 0.999 -> "p99.9", 0.5 -> "p50"."""
    s = f"{q * 100:.4f}".rstrip("0").rstrip(".")
    return f"p{s}"


@dataclasses.dataclass
class WindowStats:
    """Result of one window query: per-metric stat dicts
    ({"count", "sum", "avg", "p50", ...}) plus what was actually
    covered (the wheel clamps to retained history)."""

    time: _dt.datetime
    window_s: float    # requested
    covered_s: float   # duration actually merged (sum of slot durations)
    tier: int          # tier index the query ran on
    slots: int         # ring slots merged
    metrics: Dict[str, Dict[str, float]]


class _Tier:
    """Host-side state for one resolution tier (device ring + per-slot
    metadata).  All mutation happens under the wheel's lock."""

    def __init__(self, spec: TierSpec, num_metrics: int, num_buckets: int,
                 sharding=None):
        self.spec = spec
        self.ring = sharded_zeros(
            (spec.slots, num_metrics, num_buckets), sharding
        )
        self.slot = 0            # open slot index
        self.in_slot = 0         # intervals landed in the open slot
        self.written = np.zeros(spec.slots, dtype=bool)
        self.durations = np.zeros(spec.slots, dtype=np.float64)
        self.rates: List[Dict[str, int]] = [dict() for _ in range(spec.slots)]

    def span_intervals(self) -> int:
        return self.spec.slots * self.spec.res


def _open_slot(ring, slot):
    """Zero a slot for reuse (ring wrap).  Donated so the wheel's
    steady-state never reallocates the ring."""
    return ring.at[slot].set(0)


_open_slot_jit = jax.jit(_open_slot, donate_argnums=0)


def _scatter_cells(ring, slot, ids, idx, weights):
    """Add weighted (row, dense bucket) cells into ring[slot] — the
    per-interval bucket-tensor add every tier shares."""
    return ring.at[slot, ids, idx].add(weights, mode="drop")


_scatter_cells_jit = jax.jit(_scatter_cells, donate_argnums=0)


def trailing_mask(
    written: np.ndarray,
    durations: np.ndarray,
    slot: int,
    in_slot: int,
    n_slots: int,
    window_s: float,
) -> np.ndarray:
    """Boolean mask over ring slots covering the trailing window: walk
    back from the open slot accumulating RECORDED slot durations until
    the window is covered.  Duration-driven (not nominal-interval-
    driven) so replayed history at a different cadence — e.g. a journal
    of 0.5s intervals backfilled into a 1s wheel — still answers "the
    trailing W seconds" correctly.

    Pure function of copy-in tier state so the fused committer can
    evaluate post-commit view masks BEFORE the commit dispatches (it
    simulates the close-out on scalars and calls this); the wheel's own
    ``_mask_locked`` is the same walk over live tier state."""
    mask = np.zeros(n_slots, dtype=bool)
    s = slot if in_slot > 0 else (slot - 1) % n_slots
    covered = 0.0
    for _ in range(n_slots):
        if not written[s] or mask[s]:
            break
        mask[s] = True
        covered += float(durations[s])
        if covered >= window_s - 1e-9:
            break
        s = (s - 1) % n_slots
    return mask


class TimeWheel:
    def __init__(
        self,
        num_metrics: int = 1024,
        config: MetricConfig = MetricConfig(),
        interval: float = 1.0,
        tiers: Sequence[TierSpec | tuple] = DEFAULT_TIERS,
        percentiles: Sequence[float] = DEFAULT_QUERY_PERCENTILES,
        registry: Optional[MetricRegistry] = None,
        mesh=None,
        merge_path: str = "auto",
        snapshots: bool = True,
    ):
        """``interval`` is the base interval in seconds (one push() per
        interval); ``tiers`` resolutions are in base intervals and must
        be strictly increasing.  With ``mesh`` (the aggregator's
        ("stream", "metric") mesh) rings are metric-row-sharded."""
        if interval <= 0:
            raise ValueError("interval must be positive seconds")
        self.interval = float(interval)
        self.config = config
        self.num_metrics = num_metrics
        self.registry = (
            registry if registry is not None
            else MetricRegistry(capacity=num_metrics)
        )
        if self.registry.capacity > num_metrics:
            raise ValueError(
                f"registry capacity {self.registry.capacity} exceeds the "
                f"wheel's num_metrics {num_metrics}"
            )
        tiers = tuple(TierSpec(*t) for t in tiers)
        if not tiers:
            raise ValueError("at least one retention tier is required")
        for t in tiers:
            if t.slots < 1 or t.res < 1:
                raise ValueError(f"invalid tier {t}: slots/res must be >= 1")
        if any(b.res <= a.res for a, b in zip(tiers, tiers[1:])):
            raise ValueError(
                f"tier resolutions must be strictly increasing, got "
                f"{[t.res for t in tiers]}"
            )
        self.percentiles = tuple(float(p) for p in percentiles)
        if any(not 0.0 <= p <= 1.0 for p in self.percentiles):
            raise ValueError("percentiles must be in [0, 1]")

        self.mesh = mesh
        sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from loghisto_tpu.parallel.mesh import METRIC_AXIS

            n_metric = mesh.shape[METRIC_AXIS]
            if num_metrics % n_metric:
                raise ValueError(
                    f"num_metrics={num_metrics} not divisible by the mesh "
                    f"metric axis ({n_metric})"
                )
            sharding = NamedSharding(mesh, P(None, METRIC_AXIS, None))
        platform = (
            mesh.devices.flat[0].platform if mesh is not None
            else jax.default_backend()
        )
        self.merge_path = resolve_merge_path(
            merge_path, platform, mesh is not None
        )
        self._stats_fn = make_window_stats_fn(
            config.bucket_limit, config.precision, self.merge_path
        )
        # snapshot query engine: commit-time CDF views + sparse serving.
        # ``snapshots=False`` is the kill switch back to per-query
        # recompute (benchmarks use it as the contender baseline).
        self.snapshots_enabled = bool(snapshots)
        self._snapshot_fn = make_window_snapshot_fn(
            config.bucket_limit, config.precision, self.merge_path
        )
        # under a mesh the snapshot views stay metric-row-sharded; the
        # query fn's gather then ships ONLY the requested rows from
        # their owning shard (replicated [n, P] results for local
        # host readback) — warm result-cache hits stay zero-dispatch
        self._query_fn = make_snapshot_query_fn(
            config.bucket_limit, config.precision, mesh
        )
        self._group_fn = make_group_query_fn(
            config.bucket_limit, config.precision, mesh
        )
        # label layer (ISSUE 16): installed by TPUMetricSystem (or any
        # owner sharing a LabelIndex over this wheel's registry); None
        # means selector-syntax queries raise and plain globs are the
        # only pattern language, exactly the pre-label behavior
        self.label_index = None
        self._snapshot: Optional[Snapshot] = None
        self._pinned: List[float] = []      # pinned window seconds
        self._max_pinned = 8
        self._glob_cache: Dict[str, tuple] = {}   # pattern -> (gen, matches)
        self._result_cache: Dict[tuple, tuple] = {}  # qkey -> (epoch, gen, ws)
        self.plan_cache = QueryPlanCache()
        self.query_snapshot_hits = 0     # queries served from a snapshot
        self.query_fallbacks = 0         # locked-recompute fallbacks
        self.query_result_cache_hits = 0  # zero-dispatch host-cache hits
        self.query_rows_fetched = 0      # sparse rows read back (padded)
        self.query_group_serves = 0      # group_by rollups served

        self._sharding = sharding
        self._tiers = [
            _Tier(t, num_metrics, config.num_buckets, sharding)
            for t in tiers
        ]
        # one lock covers ring refs AND their donation lifecycle: query
        # runs its device call under it so a concurrent push can never
        # donate the very ring a query is reading
        self._lock = threading.Lock()
        self.intervals_pushed = 0
        self.samples_retained = 0   # lifetime histogram samples landed
        self.shed_samples = 0       # registry-full sheds
        self._last_time: Optional[_dt.datetime] = None
        self._hooks: List[Callable[[RawMetricSet], None]] = []

        self._sub: Optional[ResilientSubscription] = None
        self._thread: Optional[threading.Thread] = None

        # observability (ISSUE 9): tier-push / hook / query-serve spans;
        # swapped for a real ring by TPUMetricSystem(observability=...)
        self.obs_recorder = NULL_RECORDER

        # resilience (ISSUE 10): supervised bridge + chaos hook site,
        # installed by TPUMetricSystem(resilience=...)
        self.supervisor = None
        self.fault_injector = None

    # -- sizing --------------------------------------------------------- #

    def hbm_bytes(self) -> int:
        """Device bytes the rings occupy (per replica when unsharded)."""
        return sum(
            t.spec.slots * self.num_metrics * self.config.num_buckets * 4
            for t in self._tiers
        )

    @property
    def tiers(self) -> tuple[TierSpec, ...]:
        return tuple(t.spec for t in self._tiers)

    # -- ingestion ------------------------------------------------------ #

    def _cells_from_raw(self, raw: RawMetricSet):
        """Sparse interval histograms -> (row, dense bucket, weight)
        int32 arrays, registry-resolved, sanitized for drop-mode
        scatter."""
        ids, bidx, weights = [], [], []
        for name, bucket_counts in raw.histograms.items():
            try:
                mid = self.registry.id_for(name)
            except RegistryFullError:
                n = sum(bucket_counts.values())
                first = self.shed_samples == 0
                self.shed_samples += n
                if first:
                    logger.warning(
                        "timewheel registry exhausted at %d names; samples "
                        "for further new names are shed (shed_samples "
                        "counts them)", self.registry.capacity,
                    )
                continue
            for bucket, count in bucket_counts.items():
                ids.append(mid)
                bidx.append(bucket)
                weights.append(count)
        if not ids:
            return None
        bl = self.config.bucket_limit
        ids_np = np.asarray(ids, dtype=np.int32)
        idx_np = (
            np.clip(np.asarray(bidx, dtype=np.int64), -bl, bl) + bl
        ).astype(np.int32)
        # int32 wire: counts above 2^31-1 in ONE sparse cell are outside
        # the wheel's contract (the live tier's spill handles them; a
        # retention slot holding >2e9 identical samples is clipped)
        weights_np = np.minimum(
            np.asarray(weights, dtype=np.int64), np.int64(2**31 - 1)
        ).astype(np.int32)
        return ids_np, idx_np, weights_np

    def push(self, raw: RawMetricSet, duration: Optional[float] = None) -> None:
        """Land one interval on every tier.  ``duration`` (seconds)
        defaults to the RawMetricSet's recorded duration (journal replays
        carry it) and then to the wheel's configured interval."""
        dur = (
            float(duration) if duration is not None
            else float(raw.duration) if raw.duration is not None
            else self.interval
        )
        self.push_cells(self._cells_from_raw(raw), raw, dur)
        self.run_hooks(raw)

    def push_cells(
        self, cells, raw: RawMetricSet, dur: float
    ) -> None:
        """Land pre-built interval cells (the ``_cells_from_raw``
        triplet, or None for a cell-less interval) on every tier.  The
        fused interval committer's fan-out fallback enters here so the
        cell arrays are built once per interval, not once per consumer;
        hooks are NOT run (the committer owns the interval tail — plain
        ``push`` runs them)."""
        inj = self.fault_injector
        if inj is not None:
            # chaos hook: a scripted tier-push failure exercises the
            # bridge's per-interval except net / supervisor restart
            inj.check("wheel.push")
        with self.obs_recorder.span("window.tier_push", raw.seq):
            with self._lock:
                self._note_interval_locked(raw.time, cells)
                for tier in self._tiers:
                    self._tier_push_locked(tier, cells, raw.rates, dur)
                self._refresh_snapshot_locked()

    def run_hooks(self, raw: RawMetricSet) -> None:
        """Fire the per-interval hooks (rule engine etc.) for ``raw`` —
        split out so the fused committer can run them after its own
        commit path."""
        with self.obs_recorder.span("window.hooks", raw.seq):
            for hook in list(self._hooks):
                try:
                    hook(raw)
                except Exception:
                    logger.exception("timewheel interval hook failed")

    def _note_interval_locked(self, time, cells) -> None:
        """Interval-level bookkeeping shared by push_cells and the fused
        committer (caller holds the wheel lock)."""
        self._last_time = time
        self.intervals_pushed += 1
        if cells is not None:
            self.samples_retained += int(cells[2].sum(dtype=np.int64))

    def _tier_open_locked(self, tier: _Tier, slot: int) -> bool:
        """Open ``tier``'s current slot for this interval: reset its
        metadata when this is the slot's first interval and report
        whether its previous ring life must be cleared (ring wrap).
        The caller owns the actual clear — the fan-out path dispatches
        ``_open_slot_jit``, the fused committer folds a keep-factor
        multiply into its single program."""
        needs_clear = False
        if tier.in_slot == 0:
            needs_clear = bool(tier.written[slot])
            tier.durations[slot] = 0.0
            tier.rates[slot] = {}
        return needs_clear

    def _tier_close_locked(self, tier: _Tier, slot: int, rates, dur: float):
        """Close out one interval on ``tier``: per-slot metadata fold and
        slot rotation — shared verbatim by the fan-out scatter path and
        the fused committer, so the two paths cannot drift."""
        tier.written[slot] = True
        tier.durations[slot] += dur
        slot_rates = tier.rates[slot]
        for name, delta in rates.items():
            slot_rates[name] = slot_rates.get(name, 0) + delta
        tier.in_slot += 1
        if tier.in_slot >= tier.spec.res:
            tier.slot = (slot + 1) % tier.spec.slots
            tier.in_slot = 0

    def _tier_push_locked(self, tier: _Tier, cells, rates, dur: float):
        slot = tier.slot
        if self._tier_open_locked(tier, slot):
            # opening the slot: clear its previous life (ring wrap)
            tier.ring = _open_slot_jit(tier.ring, np.int32(slot))
        if cells is not None:
            ids_np, idx_np, weights_np = cells
            n = len(ids_np)
            for off in range(0, n, _CELL_CHUNK):
                take = min(_CELL_CHUNK, n - off)
                ids_pad = np.full(_CELL_CHUNK, _DROP_ID, dtype=np.int32)
                idx_pad = np.zeros(_CELL_CHUNK, dtype=np.int32)
                w_pad = np.zeros(_CELL_CHUNK, dtype=np.int32)
                ids_pad[:take] = ids_np[off:off + take]
                idx_pad[:take] = idx_np[off:off + take]
                w_pad[:take] = weights_np[off:off + take]
                tier.ring = _scatter_cells_jit(
                    tier.ring, np.int32(slot), ids_pad, idx_pad, w_pad
                )
        self._tier_close_locked(tier, slot, rates, dur)

    def backfill(self, intervals: Iterable[RawMetricSet]) -> int:
        """Replay intervals (e.g. ``utils.journal.replay(path)``) into
        the wheel — offline reconstruction of the retention state.  Each
        interval's journaled duration drives the rate math; returns the
        number of intervals pushed."""
        n = 0
        for raw in intervals:
            self.push(raw)
            n += 1
        return n

    # -- snapshots ------------------------------------------------------ #

    def pin_window(self, window_s: float) -> None:
        """Ask the commit path to materialize a snapshot view for this
        trailing window (Prometheus scrape windows, rule windows).  The
        view appears at the NEXT interval commit; until then queries for
        it use the locked recompute fallback.  Pins are capped (first
        ``_max_pinned`` stick) — every uncovered window still answers
        correctly, just without the snapshot fast path."""
        with self._lock:
            self._pin_window_locked(float(window_s))

    def _pin_window_locked(self, w: float) -> None:
        if w <= 0 or not math.isfinite(w):
            return
        if any(abs(p - w) < 1e-9 for p in self._pinned):
            return
        if len(self._pinned) >= self._max_pinned:
            return
        self._pinned.append(w)

    def pinned_windows(self) -> tuple:
        return tuple(self._pinned)

    @property
    def snapshot(self) -> Optional[Snapshot]:
        """The latest immutable snapshot handle (or None before the
        first commit / after a failed fused dispatch).  Reading the
        attribute is atomic; the handle's arrays are never donated, so
        holders may query them without the store lock."""
        return self._snapshot

    def snapshot_age_intervals(self) -> Optional[int]:
        """Commits since the served snapshot's epoch (0 == fresh);
        None when no snapshot exists."""
        snap = self._snapshot
        if snap is None:
            return None
        return self.intervals_pushed - snap.epoch

    def _view_windows_locked(self) -> List[float]:
        """Windows materialized per snapshot: the full written span
        (inf sentinel) first, then the pinned windows."""
        return [np.inf] + list(self._pinned)

    def _refresh_snapshot_locked(self) -> None:
        """Recompute every tier's snapshot views from live ring state
        and publish a new handle (fan-out/push path; the fused committer
        folds the same emission into its single dispatch and publishes
        via ``publish_snapshot_locked``)."""
        if not self.snapshots_enabled:
            return
        windows = self._view_windows_locked()
        tiers = []
        for ti, t in enumerate(self._tiers):
            masks = np.stack([self._mask_locked(t, w) for w in windows])
            payload = self._snapshot_fn(t.ring, masks)
            tiers.append(self._tier_snapshot_locked(ti, windows, masks, payload))
        self.publish_snapshot_locked(tuple(tiers))

    def _tier_snapshot_locked(
        self, ti: int, windows, masks: np.ndarray, payload
    ) -> TierSnapshot:
        """Wrap one tier's snapshot payload (cdf/counts/sums stacked
        [V, ...]) into immutable views.  Caller holds the lock; tier
        metadata must already reflect the interval the payload covers."""
        t = self._tiers[ti]
        views = []
        for vi, w in enumerate(windows):
            mask = np.asarray(masks[vi], dtype=bool)
            views.append(SnapshotView(
                window_s=None if not math.isfinite(w) else float(w),
                mask=mask,
                covered_s=float(t.durations[mask].sum()),
                slots=int(mask.sum()),
                cdf=payload["cdf"][vi],
                counts=payload["counts"][vi],
                sums=payload["sums"][vi],
            ))
        return TierSnapshot(tier=ti, views=tuple(views))

    def publish_snapshot_locked(self, tiers: tuple) -> None:
        """Publish a new epoch-versioned handle (caller holds the lock
        and has already noted the interval)."""
        self._snapshot = Snapshot(
            epoch=self.intervals_pushed,
            time=self._last_time,
            interval=self.interval,
            tiers=tiers,
        )

    def invalidate_snapshot_locked(self) -> None:
        """Drop the published handle (fused-commit failure recovery:
        the rings were rebuilt, the snapshot may describe lost state).
        Queries fall back to locked recompute until the next commit."""
        self._snapshot = None

    def _resolve_glob(self, pattern: str):
        """Glob -> ((mid, name), ...) memoized per registry state.  The
        cache key is ``(structural_generation, high_water)``: while the
        structural generation is unchanged the registry behaved
        append-only, so an equal high-water means an unchanged match
        list and a grown one only needs the new tail scanned.  Eviction,
        free-slot reuse, and compaction bump the structural generation,
        which forces a full rescan here — a resolved id must never
        outlive the generation it was resolved under (a stale hit would
        serve an evicted row, or a reused row under its old name).
        Freed slots read as None and are skipped.  Rows beyond the
        wheel's metric capacity are filtered here once, not per
        query."""
        names = self.registry.names()
        rgen = getattr(self.registry, "generation", 0)
        hw = len(names)
        gen = (rgen, hw)
        ent = self._glob_cache.get(pattern)
        if ent is not None and ent[0] == gen:
            return gen, ent[1]
        if ent is not None and ent[0][0] == rgen and ent[0][1] < hw:
            matched = list(ent[1])
            start = ent[0][1]
        else:
            matched = []
            start = 0
        for mid in range(start, hw):
            name = names[mid]
            if name is None or mid >= self.num_metrics:
                continue
            if fnmatch.fnmatch(name, pattern):
                matched.append((mid, name))
        matches = tuple(matched)
        if len(self._glob_cache) >= 256 and pattern not in self._glob_cache:
            self._glob_cache.clear()
        self._glob_cache[pattern] = (gen, matches)
        return gen, matches

    def _resolve_matches(self, pattern: str):
        """Pattern -> (generation, ((mid, name), ...)) — the one seam
        where the two query languages meet.  Brace syntax
        (``base{k=v,...}``) routes to the label index's inverted-index
        resolution; anything else stays on the wheel's original fnmatch
        glob cache.  Both return the same (generation, matches) shape,
        so the snapshot result cache keys on either uniformly."""
        if is_selector(pattern):
            idx = self.label_index
            if idx is None:
                raise ValueError(
                    f"selector query {pattern!r} needs a LabelIndex "
                    "(TPUMetricSystem installs one; standalone wheels "
                    "set wheel.label_index = LabelIndex(wheel.registry))"
                )
            return idx.select(pattern, max_id=self.num_metrics)
        return self._resolve_glob(pattern)

    def _match_predicate(self, pattern: str):
        """Name-level match test for the locked recompute path (must
        agree with ``_resolve_matches`` row for row)."""
        if is_selector(pattern):
            return parse_selector(pattern).match_name
        return lambda name: fnmatch.fnmatch(name, pattern)

    def lifecycle_invalidated_locked(self) -> None:
        """Called (store lock held) after lifecycle eviction or
        compaction mutated ring rows in place: the published snapshot
        describes pre-eviction state, and every cached glob resolution /
        host result maps dead or remapped ids.  Drop all three — the
        next commit republishes; queries in between take the locked
        recompute path against the post-eviction rings."""
        self._glob_cache.clear()
        self._result_cache.clear()
        self.invalidate_snapshot_locked()

    # -- queries -------------------------------------------------------- #

    def _select_tier(self, needed_intervals: int) -> int:
        for i, tier in enumerate(self._tiers):
            if tier.span_intervals() >= needed_intervals:
                return i
        return len(self._tiers) - 1

    def _mask_locked(self, tier: _Tier, window_s: float) -> np.ndarray:
        """Trailing-window slot mask over live tier state (see
        ``trailing_mask`` for the walk semantics)."""
        return trailing_mask(
            tier.written, tier.durations, tier.slot, tier.in_slot,
            tier.spec.slots, window_s,
        )

    def query(
        self,
        pattern: str = "*",
        window: Optional[float] = None,
        percentiles: Optional[Sequence[float]] = None,
        tier: Optional[int] = None,
    ) -> WindowStats:
        """Sliding-window statistics for every metric matching
        ``pattern`` over the trailing ``window`` seconds.  ``pattern``
        is either a name glob (``http.*``) or, when a LabelIndex is
        installed, a label selector (``http.latency{route=/api,
        code=~5..}``) — both compile to the same sparse row-id serve
        path.

        Served from the latest commit-time snapshot when one covers the
        window (the full written span, or an exactly pinned window):
        cached glob resolution, ONE jitted gather+searchsorted dispatch
        over only the matched rows, sparse ``[n, P]`` readback — all
        without the store lock (the handle's arrays are never donated).
        Repeat queries at an unchanged epoch return the host-cached
        result with zero dispatch.  Windows no snapshot view covers fall
        back to the locked full recompute and auto-pin themselves so the
        next commit materializes them.  The open (partial) slot is
        included either way, so the window's trailing edge is live."""
        ps = tuple(
            float(p) for p in (
                percentiles if percentiles is not None else self.percentiles
            )
        )
        if any(not 0.0 <= p <= 1.0 for p in ps):
            raise ValueError("percentiles must be in [0, 1]")
        if window is None:
            window = self._tiers[-1].span_intervals() * self.interval
        window = float(window)
        needed = max(1, math.ceil(window / self.interval))
        ti = self._select_tier(needed) if tier is None else int(tier)
        if not 0 <= ti < len(self._tiers):
            raise ValueError(f"tier {ti} out of range")

        # query serving attributes to the latest landed interval (the
        # snapshot it reads is that commit's published handle)
        with self.obs_recorder.span("query.serve"):
            snap = self._snapshot  # atomic ref read; handle is immutable
            view = None
            if self.snapshots_enabled and snap is not None:
                view = snap.tiers[ti].view_for(window)
            if view is None:
                if self.snapshots_enabled:
                    self.pin_window(window)
                self.query_fallbacks += 1
                return self._query_recompute(pattern, window, ps, ti)
            return self._query_snapshot(pattern, window, ps, ti, snap, view)

    def _query_snapshot(
        self, pattern: str, window: float, ps: tuple, ti: int,
        snap: Snapshot, view: SnapshotView,
    ) -> WindowStats:
        """Lock-free snapshot serve: resolve the glob (cached), check
        the host result cache for this epoch, else run one sparse
        gather+searchsorted dispatch over the matched rows."""
        self.query_snapshot_hits += 1
        gen, matches = self._resolve_matches(pattern)
        qkey = (pattern, window, ps, ti)
        cached = self._result_cache.get(qkey)
        if (
            cached is not None
            and cached[0] == snap.epoch and cached[1] == gen
        ):
            self.query_result_cache_hits += 1
            return cached[2]
        keys = [pct_key(p) for p in ps]
        metrics: Dict[str, Dict[str, float]] = {}
        if matches:
            ids_np = np.fromiter(
                (mid for mid, _ in matches), dtype=np.int32,
                count=len(matches),
            )
            padded, nb = QueryPlanCache.pad_ids(ids_np)
            self.plan_cache.note(ti, nb, len(ps))
            with self.obs_recorder.span("query.gather"):
                out = self._query_fn(
                    view.cdf, view.counts, view.sums, padded,
                    np.asarray(ps, dtype=np.float32),
                )
                counts = np.asarray(out["counts"])
                sums = np.asarray(out["sums"])
                pcts = np.asarray(out["percentiles"])
            self.query_rows_fetched += nb
            for i, (mid, name) in enumerate(matches):
                count = int(counts[i])
                if count == 0:
                    continue
                entry = {
                    "count": float(count),
                    "sum": float(sums[i]),
                    "avg": float(sums[i]) / count,
                }
                for key, value in zip(keys, pcts[i]):
                    entry[key] = float(value)
                metrics[name] = entry
        ws = WindowStats(
            time=snap.time or _dt.datetime.now(tz=_dt.timezone.utc),
            window_s=window,
            covered_s=view.covered_s,
            tier=ti,
            slots=view.slots,
            metrics=metrics,
        )
        if len(self._result_cache) >= 128 and qkey not in self._result_cache:
            self._result_cache.clear()
        self._result_cache[qkey] = (snap.epoch, gen, ws)
        return ws

    def _query_recompute(
        self, pattern: str, window: float, ps: tuple, ti: int
    ) -> WindowStats:
        """Locked full recompute — the pre-snapshot path, kept for
        windows without a materialized view (and as the parity oracle in
        tests).  The device call stays under the lock: a concurrent push
        would otherwise donate the ring buffer out from under it."""
        t = self._tiers[ti]
        ps_arr = np.asarray(ps, dtype=np.float32)
        with self._lock:
            mask = self._mask_locked(t, window)
            covered = float(t.durations[mask].sum())
            ts = self._last_time or _dt.datetime.now(tz=_dt.timezone.utc)
            stats = self._stats_fn(t.ring, mask, ps_arr)
            counts = np.asarray(stats["counts"])
            sums = np.asarray(stats["sums"])
            pcts = np.asarray(stats["percentiles"])
        names = self.registry.names()
        keys = [pct_key(p) for p in ps]
        match = self._match_predicate(pattern)
        metrics: Dict[str, Dict[str, float]] = {}
        for mid, name in enumerate(names):
            if name is None:  # lifecycle-freed slot
                continue
            if mid >= len(counts) or not match(name):
                continue
            count = int(counts[mid])
            if count == 0:
                continue
            entry = {
                "count": float(count),
                "sum": float(sums[mid]),
                "avg": float(sums[mid]) / count,
            }
            for key, value in zip(keys, pcts[mid]):
                entry[key] = float(value)
            metrics[name] = entry
        return WindowStats(
            time=ts,
            window_s=window,
            covered_s=covered,
            tier=ti,
            slots=int(mask.sum()),
            metrics=metrics,
        )

    def query_group_by(
        self,
        selector: str,
        by: Sequence[str],
        window: Optional[float] = None,
        percentiles: Optional[Sequence[float]] = None,
        tier: Optional[int] = None,
        depth: Optional[int] = None,
    ) -> GroupStats:
        """Merge every row matching ``selector`` into one histogram per
        distinct value-tuple of the ``by`` label keys and answer
        count/sum/avg/percentiles per group — ON DEVICE, one jitted
        gather + segment-sum + rank search over the snapshot CDF rows
        (``ops.stats.make_group_query_fn``).  The merge is exact:
        log-bucket histograms merge by bucket addition and prefix sums
        are linear, so grouping introduces zero sketch error (the host
        oracle parity test pins bit-identity for dense rows).

        ``selector`` takes either query language (brace selector or
        plain glob); rows missing a ``by`` label group under "".
        ``depth=k`` additionally returns each group's equi-depth
        summary (the k-1 boundaries at ranks j/k) as ``edges`` —
        equi-depth bin edges ARE quantiles, so the summary rides the
        same dispatch.  Serving follows the sparse query path exactly:
        warm repeats at an unchanged (epoch, generation) are
        zero-dispatch host-cache hits; windows without a snapshot view
        fall back to a locked one-off view build and auto-pin."""
        by = tuple(str(k) for k in by)
        if not by:
            raise ValueError("group_by needs at least one label key")
        ps = tuple(
            float(p) for p in (
                percentiles if percentiles is not None else self.percentiles
            )
        )
        if any(not 0.0 <= p <= 1.0 for p in ps):
            raise ValueError("percentiles must be in [0, 1]")
        eps = equidepth_ranks(int(depth)) if depth is not None else ()
        if window is None:
            window = self._tiers[-1].span_intervals() * self.interval
        window = float(window)
        needed = max(1, math.ceil(window / self.interval))
        ti = self._select_tier(needed) if tier is None else int(tier)
        if not 0 <= ti < len(self._tiers):
            raise ValueError(f"tier {ti} out of range")

        with self.obs_recorder.span("query.serve"):
            snap = self._snapshot  # atomic ref read; handle is immutable
            view = None
            if self.snapshots_enabled and snap is not None:
                view = snap.tiers[ti].view_for(window)
            gen, matches = self._resolve_matches(selector)
            if view is not None:
                qkey = ("#group_by", selector, by, window, ps, ti, depth)
                cached = self._result_cache.get(qkey)
                if (
                    cached is not None
                    and cached[0] == snap.epoch and cached[1] == gen
                ):
                    self.query_result_cache_hits += 1
                    return cached[2]
                gs = self._group_rollup(
                    matches, by, ps, eps, ti,
                    view.cdf, view.counts, view.sums,
                    time=snap.time, window=window,
                    covered=view.covered_s, slots=view.slots,
                )
                if len(self._result_cache) >= 128 \
                        and qkey not in self._result_cache:
                    self._result_cache.clear()
                self._result_cache[qkey] = (snap.epoch, gen, gs)
                return gs
            # no materialized view: build a one-off CDF view for the
            # window under the lock (the snapshot program reads the live
            # ring), pin the window, and roll up outside the lock — the
            # payload arrays are fresh program outputs, never donated
            if self.snapshots_enabled:
                self.pin_window(window)
            self.query_fallbacks += 1
            t = self._tiers[ti]
            with self._lock:
                mask = self._mask_locked(t, window)
                covered = float(t.durations[mask].sum())
                slots = int(mask.sum())
                ts = self._last_time or _dt.datetime.now(
                    tz=_dt.timezone.utc
                )
                payload = self._snapshot_fn(t.ring, mask[None])
            return self._group_rollup(
                matches, by, ps, eps, ti,
                payload["cdf"][0], payload["counts"][0],
                payload["sums"][0],
                time=ts, window=window, covered=covered, slots=slots,
            )

    def _group_rollup(
        self, matches, by: tuple, ps: tuple, eps: tuple, ti: int,
        cdf, counts, sums, *, time, window: float, covered: float,
        slots: int,
    ) -> GroupStats:
        """Shared device rollup over one CDF view: pad ids to the plan
        grid (pow-2 rows, pow-2 segments, extra rows into a dump
        segment sliced off after readback) and run the group kernel."""
        self.query_group_serves += 1
        keys = [pct_key(p) for p in ps]
        groups: Dict[tuple, Dict[str, object]] = {}
        sizes: Dict[tuple, int] = {}
        if matches:
            gkeys, gids = assign_groups(matches, by)
            ng_real = len(gkeys)
            ids_np = np.fromiter(
                (mid for mid, _ in matches), dtype=np.int32,
                count=len(matches),
            )
            padded, nb = QueryPlanCache.pad_ids(ids_np)
            # pad rows land in segment ng_real (the dump group); the
            # static segment count rounds up to a power of two so
            # drifting group counts reuse one executable
            ng = 1 if ng_real < 1 else 1 << ng_real.bit_length()
            gids_pad = np.full(nb, ng_real, dtype=np.int32)
            gids_pad[: len(gids)] = gids
            all_ps = np.asarray(ps + eps, dtype=np.float32)
            self.plan_cache.note((ti, "group", ng), nb, len(all_ps))
            with self.obs_recorder.span("query.gather"):
                out = self._group_fn(
                    cdf, counts, sums, padded, gids_pad, all_ps,
                    num_groups=ng,
                )
                gcounts = np.asarray(out["counts"])
                gsums = np.asarray(out["sums"])
                gpcts = np.asarray(out["percentiles"])
            self.query_rows_fetched += nb
            gsizes = np.bincount(
                np.asarray(gids, dtype=np.int64), minlength=ng_real
            )
            for gi, gk in enumerate(gkeys):
                count = int(gcounts[gi])
                if count == 0:
                    continue
                entry: Dict[str, object] = {
                    "count": float(count),
                    "sum": float(gsums[gi]),
                    "avg": float(gsums[gi]) / count,
                }
                for key, value in zip(keys, gpcts[gi][: len(ps)]):
                    entry[key] = float(value)
                if eps:
                    entry["edges"] = [
                        float(v) for v in gpcts[gi][len(ps):]
                    ]
                groups[gk] = entry
                sizes[gk] = int(gsizes[gi])
        return GroupStats(
            time=time or _dt.datetime.now(tz=_dt.timezone.utc),
            window_s=window,
            covered_s=covered,
            tier=ti,
            slots=slots,
            by=by,
            groups=groups,
            sizes=sizes,
        )

    def window_counter(
        self, name: str, window: float, tier: Optional[int] = None
    ) -> tuple[int, float]:
        """(sum of counter deltas, covered seconds) for ``name`` over the
        trailing window — the burn-rate primitive.  Counter deltas live
        in host per-slot vectors (they are O(names), not O(buckets));
        the covered duration uses the journaled per-interval durations,
        so replayed history keeps its real rate denominators."""
        needed = max(1, math.ceil(window / self.interval))
        ti = self._select_tier(needed) if tier is None else int(tier)
        t = self._tiers[ti]
        with self._lock:
            mask = self._mask_locked(t, float(window))
            total = sum(
                t.rates[i].get(name, 0)
                for i in np.nonzero(mask)[0]
            )
            covered = float(t.durations[mask].sum())
        return int(total), covered

    def window_rate(self, name: str, window: float) -> float:
        """Counter rate (events/s) over the trailing window; 0 when the
        wheel has no covered history yet."""
        total, covered = self.window_counter(name, window)
        return total / covered if covered > 0 else 0.0

    def register_query_gauges(self, ms: MetricSystem) -> None:
        """Export the query engine's self-metrics through the normal
        gauge pipeline, alongside the committer's ``commit.*`` family:
        snapshot age (intervals behind; -1 before the first snapshot),
        plan-cache hits/misses, sparse rows fetched, and the
        snapshot-vs-fallback serve split."""
        def age() -> float:
            a = self.snapshot_age_intervals()
            return -1.0 if a is None else float(a)

        ms.register_gauge_func("commit.query_SnapshotAgeIntervals", age)
        ms.register_gauge_func(
            "commit.query_PlanCacheHits",
            lambda: float(self.plan_cache.hits),
        )
        ms.register_gauge_func(
            "commit.query_PlanCacheMisses",
            lambda: float(self.plan_cache.misses),
        )
        ms.register_gauge_func(
            "commit.query_SparseRowsFetched",
            lambda: float(self.query_rows_fetched),
        )
        ms.register_gauge_func(
            "commit.query_SnapshotServed",
            lambda: float(self.query_snapshot_hits),
        )
        ms.register_gauge_func(
            "commit.query_RecomputeFallbacks",
            lambda: float(self.query_fallbacks),
        )
        ms.register_gauge_func(
            "commit.query_ResultCacheHits",
            lambda: float(self.query_result_cache_hits),
        )
        ms.register_gauge_func(
            "commit.query_GroupByServed",
            lambda: float(self.query_group_serves),
        )

    # -- subscription bridge ------------------------------------------- #

    def add_interval_hook(self, fn: Callable[[RawMetricSet], None]) -> None:
        """Run ``fn(raw)`` after every pushed interval (rule-engine
        attachment point).  Hooks run on the pushing thread."""
        self._hooks.append(fn)

    def attach(self, ms: MetricSystem, channel_capacity: int = 16) -> None:
        """Subscribe behind the raw boundary: every broadcast interval
        lands on the wheel via a bridge thread.  Strike-eviction
        resilient (ResilientSubscription), same recovery contract as the
        journal/exporters."""
        if self._thread is not None:
            raise RuntimeError("already attached")
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
                try:
                    self.push(raw)
                except Exception:  # pragma: no cover - defensive
                    logger.exception(
                        "timewheel push failed for interval %s", raw.time
                    )

        if self.supervisor is not None:
            # a crashed bridge restarts with capped backoff; the clean
            # ChannelClosed return (detach) ends the thread for good
            self._thread = self.supervisor.spawn(
                bridge, "loghisto-timewheel"
            )
        else:
            self._thread = threading.Thread(
                target=bridge, daemon=True, name="loghisto-timewheel"
            )
            self._thread.start()

    def detach(self) -> None:
        if self._sub is not None:
            self._sub.close()
            self._sub = None
        if self._thread is not None:
            # stop a supervised handle's restart loop before joining
            stop = getattr(self._thread, "stop", None)
            if stop is not None:
                stop()
            self._thread.join(timeout=5.0)
            self._thread = None
