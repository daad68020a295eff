"""TPUMetricSystem: the fully wired product in one object.

A drop-in MetricSystem whose aggregation also runs on device: it
constructs a TPUAggregator, attaches it behind the subscription boundary
(the north-star architecture — callers keep using counter/histogram/
start_timer unchanged), registers the TPU gauges, and exposes the
device-side statistics.

    ms = TPUMetricSystem(interval=1.0, num_metrics=10_000)
    ms.start()
    ms.histogram("rpc_latency", 1234.5)        # host path, as ever
    ms.record_batch(ids, values)               # firehose path, batched
    pms = ms.device_metrics()                  # percentiles computed on TPU

With ``retention=`` a TimeWheel subscribes alongside the aggregator,
keeping sliding-window history on device and powering the rule engine:

    ms = TPUMetricSystem(interval=1.0, retention=True)
    ms.start()
    ms.query_window("rpc_latency", window=300)          # p99 over 5m
    ms.add_rule(SloBurnRateRule("api_slo", "errors", "requests",
                                objective=0.999, long_window=3600,
                                short_window=300))
    ms.subscribe_to_alerts(ch)                          # Alert events
"""

from __future__ import annotations

from typing import Iterable, Mapping, Optional, Sequence

import numpy as np

from loghisto_tpu.channel import Channel
from loghisto_tpu.config import DEFAULT_PERCENTILES, MetricConfig
from loghisto_tpu.labels import LabelIndex
from loghisto_tpu.metrics import MetricSystem, ProcessedMetricSet, RawMetricSet
from loghisto_tpu.parallel.aggregator import TPUAggregator


class TPUMetricSystem(MetricSystem):
    def __init__(
        self,
        interval: float = 60.0,
        sys_stats: bool = True,
        config: MetricConfig = MetricConfig(),
        num_metrics: int = 1024,
        percentiles: Mapping[str, float] = DEFAULT_PERCENTILES,
        mesh=None,
        native_staging: bool = False,
        fast_ingest: bool = False,
        retention=None,
        commit: str = "auto",
        lifecycle=None,
        anomaly=None,
        transport: str = "auto",
        storage: str = "auto",
        paged_config=None,
        observability=None,
        resilience=None,
        federation=None,
    ):
        """``retention`` turns on the windowed retention tier:
        ``True`` builds a TimeWheel with the default 60x1 / 60x60 /
        24x3600 tiers, a sequence of ``(slots, res)`` pairs builds one
        with those tiers, and a ready ``TimeWheel`` instance is attached
        as-is (it must share this system's registry for consistent row
        ids).  The wheel subscribes behind the same raw boundary as the
        aggregator and shares its registry and mesh.

        ``commit`` picks the interval-commit pipeline when retention is
        on: "fused" runs ONE donated-carry program per interval for the
        aggregator fold plus every tier's open-slot scatter behind a
        single subscription (loghisto_tpu.commit.IntervalCommitter);
        "fanout" keeps the per-consumer bridges; "auto" (default)
        follows the capture-overridable switch in ops/dispatch.py.
        Sharded state (``mesh=``) runs the fused program under
        ``shard_map`` — capability-resolved, degrading to the fan-out
        only when the shape genuinely can't shard.  Without retention the
        aggregator is the only device consumer, so the fan-out IS one
        dispatch already and ``commit`` is moot.

        ``lifecycle`` takes a ``lifecycle.LifecycleConfig`` and turns on
        the metric lifecycle subsystem: per-interval activity tracking
        rides the fused commit (zero extra dispatches), TTL/idle and
        cardinality policies retire churned series into catch-all
        overflow metrics (count-exact), freed device rows are reused and
        periodically compacted, and a ``lifecycle.*`` gauge family
        reports the churn.  Requires retention + the fused commit path
        (the subsystem's clock and activity signal ARE the committed
        intervals).

        ``anomaly`` takes an ``anomaly.AnomalyConfig`` and turns on the
        distribution drift engine: per-metric EWMA baseline bucket
        profiles ride the fused commit (zero extra dispatches), one
        fused divergence dispatch per interval scores every metric's
        live window CDF against its baseline (KS / JSD / bucket-space
        EMD), ``DistributionDriftRule``s alert on the scores through
        the normal rule engine, and ``anomaly.<metric>.{ks,jsd,emd}``
        gauges ride every exporter.  Requires retention + the fused
        commit path, like ``lifecycle``.

        ``transport`` passes through to the TPUAggregator's host->device
        transport selection ("auto" / "raw" / "preagg" / "sparse"; see
        TPUAggregator.__init__).

        ``storage`` picks the accumulator backend ("auto" / "dense" /
        "paged"; PR 14): "paged" replaces the dense ``[M, B]`` device
        tensor with an occupancy-tracked page pool + host page table
        and per-metric variable-resolution codecs — HBM and commit H2D
        cost scale with occupied buckets, not capacity, which is what
        makes 1M live metric rows fit one chip.  "auto" follows
        ``ops.dispatch.resolve_storage_path`` (dense below the
        PAGED_MIN_METRICS crossover, or whenever a mesh / non-sparse
        transport rules paging out; ``aggregator.storage_reason`` says
        why).  ``paged_config`` takes a ``paging.PagedStoreConfig``
        (pool size, codec policy, overflow row).  Paged storage keeps
        no dense carry, so it composes with the fan-out commit, not the
        fused committer — ``commit="auto"`` degrades, explicit
        ``commit="fused"`` raises with the reason.

        ``observability`` takes an ``obs.ObsConfig`` (or ``True`` for
        the defaults) and turns on the self-observability subsystem
        (ISSUE 9): a lock-free span ring records interval-scoped stage
        timings across the whole pipeline, closed spans are re-ingested
        as ``obs.<stage>.LatencyUs`` histograms through the normal
        ingest path, a health watchdog exports ``health.*`` gauges and
        the Prometheus endpoint's ``/healthz`` JSON, and the span ring
        dumps as Perfetto-compatible Chrome trace JSON
        (``obs.dump_perfetto(ms.obs, path)``).  ``debug_dump()`` works
        with or without it.

        ``resilience`` takes a ``resilience.ResilienceConfig`` (or
        ``True`` for the defaults) and turns on the resilience subsystem
        (ISSUE 10): pipeline bridge threads (reaper, committer bridge,
        aggregator bridge, time-wheel bridge) restart with capped
        exponential backoff instead of silently dying; repeated device
        failures trip a circuit breaker that pins the fan-out/spill
        commit path; with ``checkpoint_path``/``journal_path`` set, the
        committer bridge checkpoints every N intervals (stamped with the
        interval seq watermark) and ``recover()`` restores + replays the
        journal past the watermark — at most one interval lost across a
        crash.  A ``fault_injector`` in the config scripts deterministic
        chaos faults through the pipeline's hook sites; left None, every
        hook is a single attribute test.

        ``federation`` takes a ``federation.FederationConfig`` (or
        ``True`` for the defaults) and turns this system into the
        aggregator pod of a federation tier (ISSUE 11): a TCP
        ``FederationReceiver`` listens on ``(host, port)`` (port 0 binds
        an ephemeral one, read back from ``ms.federation.port``) for
        framed packed-triple deltas from ``FederationEmitter``s running
        in other processes, interns their metric names through this
        system's registry, deduplicates frames by per-emitter sequence
        number, and drains the triples into the same staged ingest and
        fused commit local samples take — so the federated aggregate is
        bit-identical to a single process recording everything.  The
        accept/decode threads run supervised when ``resilience`` is on,
        ``federation.*`` gauges ride every exporter, and with
        ``observability`` the health report gains the
        ``emitter_starvation`` / ``fed_decode_errors`` invariants."""
        super().__init__(
            interval=interval, sys_stats=sys_stats, config=config,
            fast_ingest=fast_ingest,
        )

        # -- resilience (ISSUE 10), resolved FIRST so every component
        # below is constructed/attached already wired ------------------- #
        self.resilience = None
        self.fault_injector = None
        self.supervisor = None     # the reaper's start() picks this up
        self.device_breaker = None
        self.recovery = None
        self._recovered = False
        if resilience is not None and resilience is not False:
            from loghisto_tpu.resilience import (
                CircuitBreaker, ResilienceConfig, ThreadSupervisor,
            )

            rcfg = (
                ResilienceConfig() if resilience is True else resilience
            )
            self.resilience = rcfg
            self.fault_injector = rcfg.fault_injector
            if rcfg.supervise:
                self.supervisor = ThreadSupervisor(
                    base_backoff_s=rcfg.restart_backoff_s,
                    max_backoff_s=rcfg.restart_backoff_cap_s,
                )
            self.device_breaker = CircuitBreaker(
                threshold=rcfg.breaker_threshold,
                window_s=rcfg.breaker_window_s,
                open_s=rcfg.breaker_open_s,
            )

        self.aggregator = TPUAggregator(
            num_metrics=num_metrics,
            config=config,
            percentiles=percentiles,
            mesh=mesh,
            native_staging=native_staging,
            transport=transport,
            storage=storage,
            paged_config=paged_config,
        )
        self.aggregator.register_device_gauges(self)
        # label layer (ISSUE 16): one inverted index over the shared
        # registry serves selector queries and the labels.* gauges; the
        # retention wheel (below) routes brace-syntax patterns to it
        self.label_index = LabelIndex(self.aggregator.registry)
        self.label_index.register_gauges(self)
        if self.resilience is not None:
            # before attach: the bridge/xfer threads must spawn supervised
            self.aggregator.supervisor = self.supervisor
            self.aggregator.device_breaker = self.device_breaker
            self.aggregator.fault_injector = self.fault_injector

        self.retention = None
        self.rule_engine = None
        self.committer = None
        if retention is not None and retention is not False:
            from loghisto_tpu.window import (
                DEFAULT_TIERS, RuleEngine, TimeWheel,
            )

            if isinstance(retention, TimeWheel):
                self.retention = retention
            else:
                tiers = (
                    DEFAULT_TIERS if retention is True else retention
                )
                self.retention = TimeWheel(
                    num_metrics=num_metrics,
                    config=config,
                    interval=interval,
                    tiers=tiers,
                    registry=self.aggregator.registry,
                    mesh=mesh,
                )
            self.retention.label_index = self.label_index
            if self.resilience is not None:
                self.retention.supervisor = self.supervisor
                self.retention.fault_injector = self.fault_injector
            self.rule_engine = RuleEngine(self.retention)
            self.rule_engine.attach()
            # query-engine self-metrics (commit.query_* family): snapshot
            # age, plan-cache hits, sparse readback volume
            self.retention.register_query_gauges(self)

        import jax

        from loghisto_tpu.ops.dispatch import (
            mesh_commit_incapability, resolve_commit_path,
        )

        platform = (
            mesh.devices.flat[0].platform
            if mesh is not None
            else jax.default_backend()
        )
        self.commit_path = resolve_commit_path(
            commit, platform, mesh=mesh,
            num_metrics=self.aggregator.num_metrics,
        )
        self.lifecycle = None
        self.anomaly = None
        if lifecycle is not None and self.retention is None:
            raise ValueError(
                "lifecycle needs retention: construct with "
                "TPUMetricSystem(retention=True, lifecycle=...)"
            )
        if anomaly is not None and self.retention is None:
            raise ValueError(
                "the drift engine needs retention: construct with "
                "TPUMetricSystem(retention=True, anomaly=...)"
            )
        if self.commit_path == "fused" and self.retention is not None:
            from loghisto_tpu.commit import (
                IntervalCommitter, commit_incompatibility,
            )

            reason = commit_incompatibility(self.aggregator, self.retention)
            if reason is None:
                if lifecycle is not None:
                    from loghisto_tpu.lifecycle import LifecycleManager

                    self.lifecycle = LifecycleManager(
                        self.aggregator, self.retention, lifecycle,
                        metric_system=self,
                    )
                    self.lifecycle.register_gauges(self)
                if anomaly is not None:
                    from loghisto_tpu.anomaly import AnomalyManager

                    self.anomaly = AnomalyManager(
                        self.aggregator, self.retention, anomaly,
                        metric_system=self,
                    )
                    self.anomaly.register_gauges(self)
                    if self.lifecycle is not None:
                        # evictions zero bank rows, compaction permutes
                        # them — inside the lifecycle's own critical
                        # sections
                        self.lifecycle.anomaly = self.anomaly
                # ONE subscription pays both consumers: neither the
                # aggregator bridge nor the wheel bridge attaches
                self.committer = IntervalCommitter(
                    self.aggregator, self.retention,
                    lifecycle=self.lifecycle,
                    anomaly=self.anomaly,
                )
                if self.resilience is not None:
                    self.committer.supervisor = self.supervisor
                    self.committer.breaker = self.device_breaker
                    self.committer.fault_injector = self.fault_injector
                self.committer.attach(self)
                self.committer.register_gauges(self)
            elif commit == "fused":
                # the user explicitly demanded fused; an incompatible
                # pair must fail loudly, not silently fan out
                raise ValueError(f"fused commit unavailable: {reason}")
            else:
                self.commit_path = "fanout"
        else:
            if self.commit_path == "fused":
                # no retention: the aggregator is the only consumer, so
                # the "fan-out" is already a single dispatch per interval
                self.commit_path = "fanout"
        if self.committer is None:
            # mesh-sharded state takes the fused path too (the sharded
            # shard_map commit); only a genuine fan-out resolution —
            # explicit commit="fanout", the capture switch, or a shape
            # that can't shard — lacks the donated carries
            if lifecycle is not None:
                raise ValueError(
                    "lifecycle rides the fused interval commit; this "
                    f"configuration resolved commit={self.commit_path!r}"
                    " (the fan-out pipeline doesn't carry the activity "
                    "vector)"
                )
            if anomaly is not None:
                raise ValueError(
                    "the drift engine rides the fused interval commit; "
                    "this configuration resolved "
                    f"commit={self.commit_path!r} (the fan-out pipeline "
                    "doesn't carry the baseline banks)"
                )
            self.aggregator.attach(self)
            if self.retention is not None:
                self.retention.attach(self)

        if self.resilience is not None:
            from loghisto_tpu.resilience import (
                RecoveryManager, register_resilience_gauges,
            )

            rcfg = self.resilience
            if (rcfg.checkpoint_path is not None
                    or rcfg.journal_path is not None):
                self.recovery = RecoveryManager(
                    self,
                    aggregator=self.aggregator,
                    committer=self.committer,
                    lifecycle=self.lifecycle,
                    anomaly=self.anomaly,
                    checkpoint_path=rcfg.checkpoint_path,
                    journal_path=rcfg.journal_path,
                    checkpoint_every_intervals=(
                        rcfg.checkpoint_every_intervals
                    ),
                    fault_injector=self.fault_injector,
                )
                if self.committer is not None:
                    # the bridge thread drives the checkpoint cadence
                    self.committer.recovery = self.recovery
                elif self.retention is not None:
                    # fan-out path: the wheel's interval hook is the
                    # per-interval heartbeat instead
                    self.retention.add_interval_hook(
                        lambda raw, _rec=self.recovery: _rec.on_commit(raw)
                    )
            register_resilience_gauges(
                self,
                supervisor=self.supervisor,
                breaker=self.device_breaker,
                recovery=self.recovery,
                injector=self.fault_injector,
            )

        # -- federation tier (ISSUE 11) --------------------------------- #
        self.federation = None
        self.federation_config = None
        if federation is not None and federation is not False:
            from loghisto_tpu.federation import FederationConfig
            from loghisto_tpu.federation.receiver import FederationReceiver

            fcfg = (
                FederationConfig() if federation is True else federation
            )
            self.federation_config = fcfg
            self.federation = FederationReceiver(
                self.aggregator,
                host=fcfg.host,
                port=fcfg.port,
                journal_path=fcfg.journal_path,
                replay_on_start=fcfg.replay_on_start,
                expected_emitters=fcfg.expected_emitters,
                supervisor=self.supervisor,
                fault_injector=self.fault_injector,
            )
            self.federation.register_gauges(self)
            # fleet observability (ISSUE 12): freshness/rollup knobs
            self.federation.starvation_s = (
                fcfg.starvation_intervals * self.interval
            )
            self.federation.skew_tolerance_s = fcfg.skew_tolerance_s
            # record→queryable freshness completes at snapshot publish
            # when a commit path exists; headless/fanout systems fall
            # back to the wheel's interval hook; otherwise samples
            # complete at frame-apply time (has_publisher stays False)
            if self.committer is not None:
                self.committer.freshness_hook = self.federation.note_publish
                self.federation.has_publisher = True
            elif self.retention is not None:
                fed = self.federation
                self.retention.add_interval_hook(
                    lambda raw: fed.note_publish(getattr(raw, "seq", None))
                )
                self.federation.has_publisher = True

        # -- self-observability (ISSUE 9) ------------------------------- #
        self.obs = None            # the SpanRecorder (None when off)
        self.obs_config = None
        self.health = None         # the HealthWatchdog (None when off)
        self.self_observer = None
        self.commit_path_reason = (
            mesh_commit_incapability(
                mesh, num_metrics=self.aggregator.num_metrics
            )
            if mesh is not None and self.commit_path != "fused" else None
        )
        if observability is not None and observability is not False:
            from loghisto_tpu.obs import (
                HealthWatchdog, ObsConfig, SelfObserver, SpanRecorder,
            )

            cfg = ObsConfig() if observability is True else observability
            self.obs_config = cfg
            rec = SpanRecorder(cfg.capacity)
            self.obs = rec
            # ring saturation signal (ISSUE 12): dropped > 0 means the
            # ring wrapped faster than exporters drained it
            self.register_gauge_func(
                "obs.SpansDropped", lambda: float(rec.dropped)
            )
            # hand the ring to every instrumentation site
            self.obs_recorder = rec          # reaper broadcast span
            self.aggregator.obs_recorder = rec
            if self.retention is not None:
                self.retention.obs_recorder = rec
            if self.lifecycle is not None:
                self.lifecycle.obs_recorder = rec
            if self.anomaly is not None:
                self.anomaly.obs_recorder = rec
            if self.federation is not None:
                self.federation.obs_recorder = rec
            if self.committer is not None:
                self.committer.obs_recorder = rec
                if cfg.dogfood:
                    self.self_observer = SelfObserver(self, rec)
                    self.committer.self_observer = self.self_observer
            if cfg.health:
                self.health = HealthWatchdog(
                    self.committer, self.aggregator,
                    interval=self.interval,
                    stall_intervals=cfg.stall_intervals,
                    backpressure_fraction=cfg.backpressure_fraction,
                    commit_path=self.commit_path,
                    commit_path_reason=self.commit_path_reason,
                    wheel=self.retention,
                    supervisor=self.supervisor,
                    breaker=self.device_breaker,
                    recovery=self.recovery,
                    federation=self.federation,
                    federation_starvation_intervals=(
                        self.federation_config.starvation_intervals
                        if self.federation_config is not None else 3.0
                    ),
                    federation_skew_tolerance_s=(
                        self.federation_config.skew_tolerance_s
                        if self.federation_config is not None else 1.0
                    ),
                )
                if self.committer is not None:
                    self.committer.watchdog = self.health
                self.health.register_gauges(self)

    def debug_dump(self) -> dict:
        """One introspection snapshot of the whole pipeline: registry
        occupancy and free-list depth, the resolved commit path (with
        the mesh-incapability reason when it degraded), query/result
        cache hit counters, mesh layout, transfer/staging ring depths,
        span-ring state, and the current health report.  Pure reads —
        safe to call from any thread, any time."""
        agg = self.aggregator
        reg = agg.registry
        dump: dict = {
            "commit_path": self.commit_path,
            "commit_path_reason": self.commit_path_reason,
            "mesh": (
                {str(k): int(v) for k, v in agg.mesh.shape.items()}
                if agg.mesh is not None else None
            ),
            "registry": {
                "capacity": reg.capacity,
                "occupancy": len(reg),
                "free_count": reg.free_count(),
                "generation": reg.generation,
            },
            "rings": {
                "xfer_queued_samples": agg._xfer_queued_samples,
                "pending_samples": agg.pending_samples,
                "max_pending_samples": agg.max_pending_samples,
                "staging_depth": agg.staging_depth,
            },
            "transport": agg.transport_stats(),
        }
        wheel = self.retention
        if wheel is not None:
            dump["query"] = {
                "snapshot_hits": wheel.query_snapshot_hits,
                "fallbacks": wheel.query_fallbacks,
                "result_cache_hits": wheel.query_result_cache_hits,
                "rows_fetched": wheel.query_rows_fetched,
                "group_by_serves": wheel.query_group_serves,
                "plan_cache_hits": wheel.plan_cache.hits,
                "plan_cache_misses": wheel.plan_cache.misses,
                "snapshot_age_intervals": wheel.snapshot_age_intervals(),
            }
        # label layer: inverted-index size, selector-cache hit rate, and
        # live label cardinality per prefix — the operator's view of
        # which subsystem's label space is exploding (pair with the
        # lifecycle label_budgets and resolve_storage_path's crossover:
        # every label set is a registry row under the canonical
        # ``name;k1=v1`` encoding)
        li = self.label_index
        labels_dump = li.stats()
        labels_dump["cardinality_by_prefix"] = li.cardinality_by_prefix()
        dump["labels"] = labels_dump
        if self.committer is not None:
            dump["commit"] = {
                "intervals_committed": self.committer.intervals_committed,
                "fused_intervals": self.committer.fused_intervals,
                "fanout_intervals": self.committer.fanout_intervals,
            }
        dump["obs"] = {
            "enabled": self.obs is not None,
            "capacity": self.obs.capacity if self.obs else 0,
            "recorded": self.obs.recorded if self.obs else 0,
            "dropped": self.obs.dropped if self.obs else 0,
            "current_seq": self.obs.current_seq if self.obs else 0,
            "saturated": (
                bool(self.obs.recorded >= self.obs.capacity)
                if self.obs else False
            ),
        }
        if self.resilience is not None:
            dump["resilience"] = {
                "thread_restarts": (
                    dict(self.supervisor.restarts_by_name)
                    if self.supervisor is not None else {}
                ),
                "breaker_state": (
                    self.device_breaker.state
                    if self.device_breaker is not None else None
                ),
                "breaker_opened_total": (
                    self.device_breaker.opened_total
                    if self.device_breaker is not None else 0
                ),
                "checkpoints_taken": (
                    self.recovery.checkpoints_taken
                    if self.recovery is not None else 0
                ),
                "checkpoint_errors": (
                    self.recovery.checkpoint_errors
                    if self.recovery is not None else 0
                ),
                "last_checkpoint_seq": (
                    self.recovery.last_checkpoint_seq
                    if self.recovery is not None else None
                ),
                "recovery_in_progress": (
                    self.recovery.in_progress
                    if self.recovery is not None else False
                ),
                "faults_injected": (
                    self.fault_injector.faults_injected
                    if self.fault_injector is not None else 0
                ),
            }
        if self.federation is not None:
            dump["federation"] = self.federation.stats()
        dump["health"] = (
            self.health.report().as_dict() if self.health else None
        )
        return dump

    def record_batch(self, ids: np.ndarray, values: np.ndarray) -> None:
        """Batched firehose ingestion straight to the device accumulator
        (bypasses the host sparse tier; ids come from metric_id())."""
        self.aggregator.record_batch(ids, values)

    def metric_id(self, name: str) -> int:
        """Dense row id for `name` (registers on first use)."""
        return self.aggregator.registry.id_for(name)

    def device_metrics(self, reset: bool = True) -> ProcessedMetricSet:
        """Device-side statistics for everything aggregated so far."""
        return self.aggregator.collect(reset=reset)

    # ------------------------------------------------------------------ #
    # windowed retention & rules (requires retention=)
    # ------------------------------------------------------------------ #

    def _require_retention(self):
        if self.retention is None:
            raise RuntimeError(
                "windowed queries/rules need retention: construct with "
                "TPUMetricSystem(retention=True) (or tiers/a TimeWheel)"
            )
        return self.retention

    def query_window(
        self,
        pattern: str = "*",
        window: Optional[float] = None,
        percentiles: Optional[Sequence[float]] = None,
        tier: Optional[int] = None,
    ):
        """Sliding-window statistics over the retention wheel — served
        from the latest commit-time snapshot when one covers the window
        (one sparse gather dispatch, or zero when the epoch hasn't
        advanced); see TimeWheel.query."""
        return self._require_retention().query(
            pattern, window, percentiles, tier
        )

    def query(
        self,
        selector: str = "*",
        window: Optional[float] = None,
        percentiles: Optional[Sequence[float]] = None,
        tier: Optional[int] = None,
    ):
        """Selector-aware window query (ISSUE 16): ``selector`` is a
        label selector (``http.latency{route=/api,code=~5..}``) or a
        plain name glob — both resolve through the wheel's sparse
        row-id serve path.  Same serving guarantees as query_window
        (this method and query_window accept either syntax; query() is
        the labeled-era spelling)."""
        return self._require_retention().query(
            selector, window, percentiles, tier
        )

    def query_group_by(
        self,
        selector: str,
        by: Sequence[str],
        window: Optional[float] = None,
        percentiles: Optional[Sequence[float]] = None,
        tier: Optional[int] = None,
        depth: Optional[int] = None,
    ):
        """On-device group_by rollup: merge every row matching
        ``selector`` into one histogram per distinct value-tuple of the
        ``by`` label keys — one jitted gather + segment-sum dispatch,
        exact merges (see TimeWheel.query_group_by).  ``depth=k`` adds
        per-group equi-depth summaries (``edges``)."""
        return self._require_retention().query_group_by(
            selector, by, window=window, percentiles=percentiles,
            tier=tier, depth=depth,
        )

    def window_rate(self, name: str, window: float) -> float:
        """Counter rate (events/s) over the trailing window."""
        return self._require_retention().window_rate(name, window)

    def add_rule(self, rule):
        """Register an alerting rule (window.rules.*Rule), evaluated
        after every interval; its state gauges join this system's
        exporters immediately.  ``DistributionDriftRule``s are bound to
        this system's AnomalyManager automatically (requires
        ``anomaly=AnomalyConfig(...)``)."""
        self._require_retention()
        if getattr(rule, "kind", None) == "distribution_drift":
            if self.anomaly is None:
                raise ValueError(
                    "distribution_drift rules need the drift engine: "
                    "construct with TPUMetricSystem(retention=True, "
                    "anomaly=AnomalyConfig(...))"
                )
            rule.bind(self.anomaly)
        elif getattr(rule, "kind", None) == "freshness":
            if self.federation is None:
                raise ValueError(
                    "freshness rules read the federation receiver's "
                    "end-to-end latency ledger: construct with "
                    "TPUMetricSystem(federation=FederationConfig(...))"
                )
            rule.bind(self.federation)
        self.rule_engine.add(rule)
        self.rule_engine.register_gauges(self)
        return rule

    def subscribe_to_alerts(self, ch: Channel) -> None:
        self._require_retention()
        self.rule_engine.subscribe(ch)

    def unsubscribe_from_alerts(self, ch: Channel) -> None:
        if self.rule_engine is not None:
            self.rule_engine.unsubscribe(ch)

    def backfill_retention(self, intervals: Iterable[RawMetricSet]) -> int:
        """Replay journaled intervals (utils.journal.replay(path)) into
        the retention state — offline reconstruction of window state.
        On the fused commit path the replay runs through the interval
        committer (the system's single interval consumer), so lifecycle
        activity and drift baselines rebuild alongside the wheel and
        the aggregator sees the samples exactly as it would have live.
        Returns the number of intervals pushed."""
        self._require_retention()
        if self.committer is not None:
            n = 0
            for raw in intervals:
                self.committer.commit(raw)
                n += 1
            return n
        return self.retention.backfill(intervals)

    # ------------------------------------------------------------------ #

    def recover(self):
        """Restore the latest checkpoint and replay journaled intervals
        past its seq watermark (resilience.RecoveryManager.recover) —
        at most the one in-flight interval is lost across a crash.
        Returns the RecoveryReport.  Runs automatically on the first
        ``start()`` when ``ResilienceConfig.recover_on_start`` is set."""
        if self.recovery is None:
            raise RuntimeError(
                "crash recovery needs a checkpoint/journal path: "
                "construct with TPUMetricSystem(resilience="
                "ResilienceConfig(checkpoint_path=..., journal_path=...))"
            )
        self._recovered = True
        return self.recovery.recover()

    def start(self) -> None:
        # restartable like the base class: re-attach whichever commit
        # pipeline a previous stop() detached — the fused committer is
        # the single bridge when present, the per-consumer pair otherwise
        if self.committer is not None:
            if self.committer._thread is None:
                self.committer.attach(self)
        else:
            if self.aggregator._attached is None:
                self.aggregator.attach(self)
            if self.retention is not None and self.retention._thread is None:
                self.retention.attach(self)
        if self.recovery is not None:
            # recover BEFORE the reaper starts minting intervals: replay
            # runs through the normal commit path, then the seq counter
            # is advanced past the replayed watermark so live intervals
            # never collide with journaled ones
            if (self.resilience.recover_on_start
                    and not self._recovered):
                self._recovered = True
                self.recovery.recover()
            self.recovery.start()
        if self.federation is not None:
            # after recovery (a journal replay must land on restored
            # state), before the reaper: federated deltas are ordinary
            # staged ingest, safe as soon as the aggregator exists
            self.federation.start()
        super().start()

    def stop(self) -> None:
        if self.federation is not None:
            # first: stop accepting new deltas, then let the close()
            # below drain whatever already reached the transfer queue
            self.federation.stop()
        if self.committer is not None:
            self.committer.detach()
        else:
            self.aggregator.detach()
            if self.retention is not None:
                self.retention.detach()
        # drain the transfer pipeline fully (staging ring + queue) so a
        # shutdown never strands in-flight samples; the worker re-spawns
        # lazily if start() resumes ingestion
        self.aggregator.close()
        if self.recovery is not None:
            # after the bridges drained, before the reaper dies: the
            # final checkpoint captures every committed interval, so a
            # clean stop/start round trip replays nothing
            self.recovery.stop(final_checkpoint=True)
        super().stop()
