"""Self-observability subsystem (ISSUE 9): interval-scoped span
tracing, dogfooded latency histograms, pipeline health watchdog, and
Perfetto-compatible trace export.

The paper's dogfooding claim is that loghisto *is* its own profiling
tool — timers feed log-bucketed histograms accurate to arbitrary
percentiles.  This package closes the loop over the eight-stage
interval pipeline PRs 1-8 built:

  * ``spans``   — a lock-free fixed-capacity ring ``SpanRecorder``
    (Dapper-style spans keyed by an interval sequence number) that the
    committer, aggregator, wheel, drift/lifecycle managers, and query
    engine record into;
  * ``SelfObserver`` — re-ingests closed spans as
    ``obs.<stage>.LatencyUs`` histograms through the normal
    ``histogram()`` path (Monarch-style: the monitoring system reports
    through its own ingest), and serves the ``commit.LatencyP50Us`` /
    ``P99Us`` gauges from the system's own log-bucketed state;
  * ``health``  — a watchdog that turns pipeline invariants (commit
    liveness, ingest backpressure, transfer drain lag, fused→fanout
    degradation, strike evictions, device-failure cooldown) into a
    machine-readable ``HealthReport`` exported as ``health.*`` gauges
    and a ``/healthz`` JSON payload;
  * ``perfetto`` — dumps the span ring as Chrome ``trace_events`` JSON
    that opens in Perfetto (interval seq as flow ids).  The ring's
    clock is ``perf_counter_ns``; ``SpanRecorder.clock_mark()`` at both
    ends of a ``jax.profiler`` capture gives the offset that puts its
    spans on the device trace's clock.

Wired via ``TPUMetricSystem(observability=ObsConfig(...))``.
"""

from loghisto_tpu.obs.spans import (  # noqa: F401
    NULL_RECORDER,
    LatencyHistogram,
    ObsConfig,
    SelfObserver,
    Span,
    SpanRecorder,
)
from loghisto_tpu.obs.health import HealthReport, HealthWatchdog  # noqa: F401
from loghisto_tpu.obs.perfetto import (  # noqa: F401
    dump_perfetto,
    trace_events,
)

__all__ = [
    "ObsConfig",
    "Span",
    "SpanRecorder",
    "NULL_RECORDER",
    "LatencyHistogram",
    "SelfObserver",
    "HealthReport",
    "HealthWatchdog",
    "trace_events",
    "dump_perfetto",
]
