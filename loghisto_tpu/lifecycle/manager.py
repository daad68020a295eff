"""LifecycleManager: the runtime that owns the activity vector, runs
the eviction policies, and drives the device fold/compact programs.

Threading model: the manager piggybacks on the IntervalCommitter's
bridge thread — ``on_interval()`` runs after each committed interval
with NO locks held, so policy work never extends the commit critical
section.  Because commits and lifecycle actions share one thread, an
eviction can never race an in-flight cell scatter (the cells of
interval N are fully applied before the policies for interval N run).
Concurrent *registrations* (user threads calling ``_id_for``) are
tolerated: eviction only touches ids that were live when the policy
snapshot was taken, and compaction validates its permutation against
the registry under the registry's own lock, aborting cleanly if a
racer registered mid-build.

Lock ordering matches the committer's documented contract — the
aggregator's ``_dev_lock``, THEN the wheel's lock; the registry and
``_agg`` locks are leaves.  The activity vector (`int32 [M]`, device)
is guarded by ``_dev_lock`` like the accumulator it shadows.

Exactness contract: an eviction folds the victim's device buckets into
its overflow row by integer addition (order-independent, lossless) and
folds the host lifetime ``_agg`` / MetricSystem stores with Python
ints, so `sum(evicted counts) == overflow lifetime count` EXACTLY —
the acceptance criterion tests/test_lifecycle.py pins.  Compaction is
a pure row permutation: survivor histograms, and every percentile
derived from them, are bit-identical across a repack.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from loghisto_tpu.lifecycle.policy import LifecycleConfig, decide_victims
from loghisto_tpu.obs.spans import NULL_RECORDER
from loghisto_tpu.ops.commit import DROP_ID
from loghisto_tpu.ops.lifecycle import (
    make_compact_fn,
    make_fold_evict_fn,
    make_touch_fn,
    pad_pow2_ids,
    resolve_compact_path,
)
from loghisto_tpu.parallel.mesh import row_vector_sharding

logger = logging.getLogger("loghisto_tpu")


class LifecycleManager:
    """Lifecycle runtime for a (TPUAggregator, TimeWheel) pair.  Built
    by TPUMetricSystem when ``lifecycle=LifecycleConfig(...)`` is
    passed; standalone construction is supported for tests."""

    def __init__(
        self,
        aggregator,
        wheel,
        config: LifecycleConfig,
        metric_system=None,
    ):
        if wheel is None:
            raise ValueError(
                "lifecycle needs a retention wheel: activity tracking and"
                " eviction ride the fused interval commit"
            )
        # r18: paged aggregators are first-class.  The device programs
        # run in their with_acc=False form (rings + activity only) and
        # the pool folds/repacks through the PagedStore API — eviction
        # via fold_rows_into/drop_rows (count-exact host translate +
        # pool commit), compaction via apply_permutation (a host
        # page-table row permutation with zero device data movement).
        self._paged = getattr(aggregator, "paged", None) is not None
        self.aggregator = aggregator
        self.wheel = wheel
        self.config = config
        self.metric_system = metric_system
        num_tiers = len(wheel._tiers)
        self._fold = make_fold_evict_fn(num_tiers, with_acc=not self._paged)
        platform = jax.default_backend()
        self._compact = make_compact_fn(
            num_tiers,
            resolve_compact_path(
                config.compact_path, platform, aggregator.mesh is not None
            ),
            with_acc=not self._paged,
        )
        self._touch = make_touch_fn()

        # the drift engine's baseline banks live and die with the rows
        # this manager evicts/compacts; set by TPUMetricSystem wiring
        # (an AnomalyManager) so bank rows are zeroed with their victims
        # and permuted with their survivors
        self.anomaly = None

        # device activity vector; sized lazily to the accumulator's row
        # count (guarded by aggregator._dev_lock, like the accumulator).
        # Under a mesh the carry is metric-row-sharded like the
        # accumulator it shadows (the sharded fused commit requires it)
        self._sharding = (
            row_vector_sharding(aggregator.mesh)
            if aggregator.mesh is not None else None
        )
        self._la: Optional[jnp.ndarray] = None

        self._intervals_seen = 0
        self.evicted_series = 0       # lifetime victims
        self.overflowed_samples = 0   # device counts folded to overflow
        self.evictions = 0            # eviction batches
        self.compactions = 0
        self.last_compaction_us = 0.0
        self._compaction_us: deque = deque(maxlen=256)
        self._metrics_lock = threading.Lock()

        # observability (ISSUE 9): policy-tick spans; swapped for a real
        # ring by TPUMetricSystem(observability=...)
        self.obs_recorder = NULL_RECORDER

    # -- epoch / activity carry (callers hold agg._dev_lock) ------------- #

    @property
    def epoch(self) -> int:
        """Committed-interval count — the lifecycle clock.  Riding the
        wheel's counter (not a private one) means checkpoint restore and
        journal replay keep activity comparisons meaningful for free."""
        return self.wheel.intervals_pushed

    def _place(self, la: jnp.ndarray) -> jnp.ndarray:
        """Pin a rebuilt/grown carry to its mesh sharding (no-op when
        single-device).  Row growth under a mesh happens in metric-axis
        units (TPUAggregator._grow_row_unit), so the result always
        shards evenly."""
        if self._sharding is None:
            return la
        return jax.device_put(la, self._sharding)

    def ensure_capacity_locked(self, m: int) -> jnp.ndarray:
        """The activity carry, padded to ``m`` rows (new rows stamp the
        current epoch: a freshly grown row is as alive as a fresh
        registration)."""
        la = self._la
        if la is None:
            la = self._place(
                jnp.full((m,), np.int32(self.epoch), dtype=jnp.int32)
            )
        elif la.shape[0] < m:
            la = self._place(jnp.concatenate([
                la,
                jnp.full((m - la.shape[0],), np.int32(self.epoch),
                         dtype=jnp.int32),
            ]))
        self._la = la
        return la

    def store_carry_locked(self, la: jnp.ndarray) -> None:
        self._la = la

    def touch_locked(self, ids: np.ndarray) -> None:
        """Fan-out path activity stamp: one tiny scatter dispatch (the
        fused path embeds the same update at zero extra dispatches)."""
        if len(ids) == 0:
            return
        la = self.ensure_capacity_locked(self.aggregator.num_metrics)
        self._la = self._touch(
            la, pad_pow2_ids(ids), np.int32(self.epoch)
        )

    def on_device_failure_locked(self) -> None:
        """The fused dispatch died mid-donation: the carry may be
        consumed.  Rebuild it stamped at the current epoch — every
        series reads as just-active, which can only DELAY evictions,
        never cause a wrong one."""
        la = self._la
        if la is not None and getattr(la, "is_deleted", lambda: False)():
            self._la = self._place(jnp.full(
                (self.aggregator.num_metrics,), np.int32(self.epoch),
                dtype=jnp.int32,
            ))

    # -- the policy tick -------------------------------------------------- #

    def on_interval(self) -> None:
        """Called by the committer after each committed interval (its
        thread, no locks held).  Every ``check_every`` intervals: read
        the activity vector, run the policies, evict, and auto-compact
        if the row space fragmented past the configured threshold."""
        self._intervals_seen += 1
        if self._intervals_seen % self.config.check_every:
            return
        try:
            with self.obs_recorder.span("lifecycle.tick"):
                self.check()
        except Exception:  # pragma: no cover - defensive
            logger.exception("lifecycle policy check failed")

    def check(self) -> List[str]:
        """One policy pass.  Returns the evicted names."""
        with self.aggregator._dev_lock:
            la = self._la
            if la is None:
                return []
            last_active = np.asarray(la)
        victims = decide_victims(
            self.aggregator.registry.names(), last_active, self.epoch,
            self.config,
        )
        evicted = self.evict_ids(victims) if victims else []
        self._maybe_compact()
        return evicted

    def _maybe_compact(self) -> None:
        frac = self.config.auto_compact_fragmentation
        if frac <= 0:
            return
        reg = self.aggregator.registry
        free = reg.free_count()
        hw = len(reg)
        if free >= self.config.min_compact_rows and free > frac * hw:
            self.compact()

    # -- eviction --------------------------------------------------------- #

    def evict_ids(self, victims: List[int]) -> List[str]:
        """Retire the given live ids: device fold into their overflow
        rows, host lifetime folds, registry release, cache/snapshot
        invalidation.  Returns the evicted names."""
        agg, wheel, reg = self.aggregator, self.wheel, self.aggregator.registry
        pairs = []  # (victim id, name, overflow id or -1, overflow name)
        for mid in victims:
            name = reg.name_for(int(mid))
            if name is None or self.config.is_protected(name):
                continue
            oname = self.config.overflow_name(name)
            # registration BEFORE the device locks: _id_for may grow the
            # row space (it takes _dev_lock itself).  A freed slot can be
            # reused here — eviction zeroed its rows, so it starts clean.
            omid = agg._id_for(oname)
            pairs.append((int(mid), name, omid, oname))
        if not pairs:
            return []

        vids = np.asarray([p[0] for p in pairs], dtype=np.int32)
        # shed overflow targets (registry exhausted) become DROP: the
        # victim still zeroes; its lifetime total survives in the host
        # folds below, so nothing is silently lost
        tids = np.asarray(
            [p[2] if p[2] >= 0 else DROP_ID for p in pairs],
            dtype=np.int32,
        )
        vpad = pad_pow2_ids(vids)
        tpad = np.full(len(vpad), DROP_ID, dtype=np.int32)
        tpad[: len(tids)] = tids

        with agg._dev_lock:
            la = self.ensure_capacity_locked(agg.num_metrics)
            with wheel._lock:
                moved_total = 0
                if self._paged:
                    # pool fold first (host translate + pool commit —
                    # count-exact, returns the moved totals the dense
                    # path reads off vcounts), grouped by overflow
                    # target; shed targets (registry exhausted) drop
                    # their pool pages outright — the host lifetime
                    # folds below still preserve the totals
                    by_target: Dict[int, List[int]] = {}
                    shed: List[int] = []
                    for mid, _, omid, _ in pairs:
                        if omid >= 0:
                            by_target.setdefault(omid, []).append(mid)
                        else:
                            shed.append(mid)
                    for omid, vlist in by_target.items():
                        moved_total += agg.paged.fold_rows_into(
                            vlist, omid
                        )
                    if shed:
                        agg.paged.drop_rows(shed)
                    rings, la = self._fold(
                        tuple(t.ring for t in wheel._tiers),
                        la,
                        vpad,
                        tpad,
                        np.int32(self.epoch),
                    )
                    vcounts = np.zeros(len(vids), dtype=np.int64)
                else:
                    acc, rings, la, vcounts = self._fold(
                        agg._dense_acc_locked(),
                        tuple(t.ring for t in wheel._tiers),
                        la,
                        vpad,
                        tpad,
                        np.int32(self.epoch),
                    )
                    agg._acc = acc
                    vcounts = np.asarray(vcounts)[: len(vids)]
                for t, r in zip(wheel._tiers, rings):
                    t.ring = r
                self._la = la
                if self.anomaly is not None:
                    # zero the victims' drift baselines in the same
                    # critical section: the freed slots' next tenants
                    # must start cold, not inherit a dead shape
                    self.anomaly.on_evicted_locked(vpad)
                if agg._spill is not None:
                    for mid, _, omid, _ in pairs:
                        if mid < len(agg._spill):
                            if 0 <= omid < len(agg._spill):
                                agg._spill[omid] += agg._spill[mid]
                            agg._spill[mid] = 0
                # release the names INSIDE the critical section: a query
                # that starts after these locks drop sees the bumped
                # generation, the cleared caches, and no snapshot — it
                # can never resolve a dead id against live data
                reg.evict([p[0] for p in pairs])
                wheel.lifecycle_invalidated_locked()
            agg.stats_snapshot = None

        # host lifetime folds (leaf locks, exact integer arithmetic)
        with agg._agg_lock:
            for mid, _, omid, _ in pairs:
                entry = agg._agg.pop(mid, None)
                if entry is not None and omid >= 0:
                    dst = agg._agg.setdefault(omid, [0, 0])
                    dst[0] += entry[0]
                    dst[1] += entry[1]
        ms = self.metric_system
        if ms is not None:
            with ms._store_lock:
                for _, name, _, oname in pairs:
                    entry = ms._histogram_agg_store.pop(name, None)
                    if entry is not None:
                        dst = ms._histogram_agg_store.setdefault(
                            oname, [0, 0]
                        )
                        dst[0] += entry[0]
                        dst[1] += entry[1]
                    c = ms._counter_store.pop(name, None)
                    if c is not None:
                        ms._counter_store[oname] = (
                            ms._counter_store.get(oname, 0) + c
                        )

        with self._metrics_lock:
            self.evictions += 1
            self.evicted_series += len(pairs)
            self.overflowed_samples += (
                moved_total if self._paged else int(vcounts.sum())
            )
        return [p[1] for p in pairs]

    # -- compaction ------------------------------------------------------- #

    def compact(self) -> bool:
        """Repack live rows to a dense prefix: one donated gather per
        structure over the survivor permutation, then remap the
        registry and host aggregates.  Returns False when there was
        nothing to compact or a concurrent registration invalidated the
        permutation (the next tick retries)."""
        agg, wheel, reg = self.aggregator, self.wheel, self.aggregator.registry
        t0 = time.perf_counter()
        with agg._dev_lock:
            names = reg.names()
            live = [m for m, n in enumerate(names) if n is not None]
            m_rows = agg.num_metrics
            if len(live) == len(names):
                return False  # already dense
            perm = np.full(m_rows, DROP_ID, dtype=np.int32)
            perm[: len(live)] = live
            try:
                # host commit point FIRST: validates no registration
                # raced the permutation build.  If the device dispatch
                # below fails, the standard device-failure recovery
                # resets the consumed carries — ids stay consistent.
                reg.apply_permutation([int(p) for p in perm], m_rows)
            except ValueError as e:
                logger.warning("compaction aborted: %s", e)
                return False
            old_to_new = {old: new for new, old in enumerate(live)}
            la = self.ensure_capacity_locked(m_rows)
            with wheel._lock:
                try:
                    if self._paged:
                        # pool repack is a host page-table row
                        # permutation (zero device traffic); the
                        # DROP_ID pads become the -1 holes PagedStore
                        # expects.  Done after the registry commit
                        # point, before the ring repack, so a ring
                        # dispatch failure leaves registry + pool
                        # consistently permuted.
                        agg.paged.apply_permutation(
                            [
                                int(p) if 0 <= p < m_rows else -1
                                for p in perm
                            ],
                            m_rows,
                        )
                        rings, la = self._compact(
                            tuple(t.ring for t in wheel._tiers),
                            la,
                            perm,
                            np.int32(self.epoch),
                        )
                        jax.block_until_ready(la)
                    else:
                        acc, rings, la = self._compact(
                            agg._dense_acc_locked(),
                            tuple(t.ring for t in wheel._tiers),
                            la,
                            perm,
                            np.int32(self.epoch),
                        )
                        jax.block_until_ready(acc)
                except Exception:
                    logger.exception(
                        "compaction dispatch failed; recovering device "
                        "state"
                    )
                    agg._on_device_failure_locked()
                    self.on_device_failure_locked()
                    if self.anomaly is not None:
                        self.anomaly.on_device_failure_locked()
                    wheel.lifecycle_invalidated_locked()
                    return False
                if not self._paged:
                    agg._acc = acc
                for t, r in zip(wheel._tiers, rings):
                    t.ring = r
                self._la = la
                if self.anomaly is not None:
                    # baselines follow their rows through the repack
                    self.anomaly.apply_permutation_locked(perm)
                if agg._spill is not None:
                    spill = np.zeros_like(agg._spill)
                    nsrc = [s for s in live if s < len(agg._spill)]
                    spill[: len(nsrc)] = agg._spill[nsrc]
                    agg._spill = spill
                wheel.lifecycle_invalidated_locked()
            agg.stats_snapshot = None
        with agg._agg_lock:
            remapped: Dict[int, list] = {}
            for mid, entry in agg._agg.items():
                new = old_to_new.get(mid)
                if new is not None:
                    remapped[new] = entry
                else:
                    # unnamed raw-id rows (record_batch without names)
                    # have no post-compaction identity; their device
                    # rows were dropped by the repack too
                    logger.debug(
                        "compaction dropped unnamed row %d lifetime "
                        "aggregate", mid,
                    )
            agg._agg = remapped
        us = (time.perf_counter() - t0) * 1e6
        with self._metrics_lock:
            self.compactions += 1
            self.last_compaction_us = us
            self._compaction_us.append(us)
        ms = self.metric_system
        if ms is not None:
            try:
                ms.histogram("lifecycle.CompactionLatencyUs", us)
            except Exception:  # pragma: no cover - defensive
                pass
        return True

    # -- checkpoint ------------------------------------------------------- #

    def state_dict(self) -> dict:
        """Host-serializable lifecycle state for utils/checkpoint.py:
        the activity vector plus the lifetime counters.  The registry
        generation and overflow metric contents ride the normal
        name/accumulator payloads."""
        with self.aggregator._dev_lock:
            la = (
                np.asarray(self._la) if self._la is not None
                else np.zeros(0, dtype=np.int32)
            )
        with self._metrics_lock:
            return {
                "last_active": la,
                "evicted_series": self.evicted_series,
                "overflowed_samples": self.overflowed_samples,
                "evictions": self.evictions,
                "compactions": self.compactions,
            }

    def load_state(self, state: dict) -> None:
        # checkpoints carry host arrays, so restore re-shards onto THIS
        # manager's mesh layout — checkpoints stay mesh-shape-portable
        # (save on 2x4, restore on 1x8)
        la = np.asarray(state.get("last_active", []), dtype=np.int32)
        with self.aggregator._dev_lock:
            if len(la):
                self._la = self._place(jnp.asarray(la))
        with self._metrics_lock:
            self.evicted_series = int(state.get("evicted_series", 0))
            self.overflowed_samples = int(
                state.get("overflowed_samples", 0)
            )
            self.evictions = int(state.get("evictions", 0))
            self.compactions = int(state.get("compactions", 0))

    # -- gauges ----------------------------------------------------------- #

    def _compaction_p99(self) -> float:
        with self._metrics_lock:
            if not self._compaction_us:
                return 0.0
            return float(
                np.percentile(np.asarray(self._compaction_us), 99.0)
            )

    def register_gauges(self, ms) -> None:
        """Export the lifecycle self-metric family through the normal
        gauge pipeline (same shape as commit.* / tpu.*)."""
        reg = self.aggregator.registry
        ms.register_gauge_func(
            "lifecycle.ActiveSeries", lambda: float(reg.live_count())
        )
        ms.register_gauge_func(
            "lifecycle.FreeSlots", lambda: float(reg.free_count())
        )
        ms.register_gauge_func(
            "lifecycle.Generation", lambda: float(reg.generation)
        )
        ms.register_gauge_func(
            "lifecycle.EvictedSeries",
            lambda: float(self.evicted_series),
        )
        ms.register_gauge_func(
            "lifecycle.OverflowedSamples",
            lambda: float(self.overflowed_samples),
        )
        ms.register_gauge_func(
            "lifecycle.Evictions", lambda: float(self.evictions)
        )
        ms.register_gauge_func(
            "lifecycle.Compactions", lambda: float(self.compactions)
        )
        ms.register_gauge_func(
            "lifecycle.LastCompactionUs",
            lambda: float(self.last_compaction_us),
        )
        ms.register_gauge_func(
            "lifecycle.CompactionP99Us", self._compaction_p99
        )
        ms.register_gauge_func(
            "lifecycle.Occupancy",
            lambda: (
                float(reg.live_count()) / self.aggregator.num_metrics
                if self.aggregator.num_metrics else 0.0
            ),
        )
