"""Interval-scoped span recording (the ISSUE 9 tentpole core).

``SpanRecorder`` is a fixed-capacity, preallocated, drop-oldest ring of
closed spans.  The hot path — ``record()`` — is two ``perf_counter_ns``
reads already taken by the caller plus one counter increment and one
slot store, no locks: under CPython the ``next()`` on the shared
``itertools.count`` and the single ``STORE_SUBSCR`` into the slot list
are each atomic bytecodes, so concurrent recorders from the committer
bridge, the transfer worker, the reaper, and query threads interleave
without coordination.  Capacity is a power of two so the slot index is
a mask, and the ring never allocates after construction — an old span
is overwritten in place (drop-oldest), never resized.

Every span carries the **interval sequence number** it attributes to.
The seq is minted once per interval by the reaper
(``MetricSystem.collect_raw_metrics`` stamps ``RawMetricSet.seq``) and
adopted by the committer at commit time (``begin_interval``); pipeline
work that runs off the committer thread (transfer drain, broadcast
fanout, query serving) attributes to ``current_seq`` — the latest
interval the pipeline landed.  Stage spans recorded during one commit
therefore nest inside that interval's end-to-end ``commit.e2e`` span
and decompose its latency exactly (pinned by tests/test_obs.py).

``SelfObserver`` is the dogfooding half: closed spans are re-ingested
as ``obs.<stage>.LatencyUs`` histograms through the system's normal
``histogram()`` path, and ``LatencyHistogram`` keeps the same samples
in the library's own log-bucket codec so percentile gauges
(``commit.LatencyP50Us``/``P99Us``) are served by the system itself —
no ad-hoc host-side latency lists.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np

from loghisto_tpu.config import PRECISION
from loghisto_tpu.ops.codec import compress_np, decompress_np


class Span(NamedTuple):
    """One closed span: a named pipeline stage, its wall-clock bounds
    (``perf_counter_ns``), the interval it attributes to, and the
    recording thread's name (the Perfetto track).  ``flow`` is an
    optional cross-process flow id (``wire.fed_flow_id``): spans that
    carry one are chained across emitter/receiver trace dumps by
    ``perfetto.merge_traces``."""

    stage: str
    start_ns: int
    end_ns: int
    seq: int
    thread: str
    flow: Optional[int] = None

    @property
    def duration_us(self) -> float:
        return (self.end_ns - self.start_ns) / 1e3


@dataclasses.dataclass
class ObsConfig:
    """Observability wiring for ``TPUMetricSystem(observability=...)``.

    ``capacity`` sizes the span ring (rounded up to a power of two);
    ``dogfood`` re-ingests closed spans as ``obs.*`` histograms through
    the normal pipeline; ``health`` attaches the watchdog and its
    ``health.*`` gauges; ``stall_intervals`` is the no-commit threshold
    (k in "no commit for > k×interval"); ``backpressure_fraction`` is
    the staging/transfer high-water fraction that counts as
    backpressure."""

    capacity: int = 4096
    dogfood: bool = True
    health: bool = True
    stall_intervals: float = 3.0
    backpressure_fraction: float = 0.8


class _SpanHandle:
    """Context-manager handle for one in-flight span.  Allocated per
    use — instrumentation sites on the microsecond-scale pipeline
    stages tolerate one small allocation; the O(ns) claim is about
    ``record()`` itself, which tests pin against a time budget."""

    __slots__ = ("_rec", "stage", "seq", "flow", "start_ns")

    def __init__(self, rec: "SpanRecorder", stage: str, seq: Optional[int],
                 flow: Optional[int] = None):
        self._rec = rec
        self.stage = stage
        self.seq = seq
        self.flow = flow

    def __enter__(self) -> "_SpanHandle":
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self._rec.record(
            self.stage, self.start_ns, time.perf_counter_ns(), self.seq,
            self.flow,
        )


class _NullHandle:
    """Reusable no-op span handle: disabled instrumentation costs two
    attribute loads and two no-op calls, nothing else."""

    __slots__ = ()

    def __enter__(self) -> "_NullHandle":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_HANDLE = _NullHandle()


class SpanRecorder:
    """Lock-free fixed-capacity span ring — see the module docstring."""

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        # round up to a power of two so the slot index is a mask
        cap = 1 << (int(capacity) - 1).bit_length()
        self.capacity = cap
        self._mask = cap - 1
        self._slots: list = [None] * cap
        self._counter = itertools.count()  # next() is atomic under GIL
        self._seq_counter = itertools.count(1)
        self.current_seq = 0  # latest interval the pipeline landed
        self.enabled = True

    # -- interval sequencing -------------------------------------------- #

    def begin_interval(self, seq: Optional[int] = None) -> int:
        """Adopt (or mint) the interval sequence number for the commit
        that is starting.  The committer passes ``raw.seq`` (stamped by
        the reaper at collection); a raw set without one (old journal
        lines, hand-built sets) gets a locally minted seq so every span
        still attributes to exactly one interval."""
        if seq is None:
            seq = next(self._seq_counter)
        self.current_seq = seq
        return seq

    # -- the hot path --------------------------------------------------- #

    def record(
        self,
        stage: str,
        start_ns: int,
        end_ns: int,
        seq: Optional[int] = None,
        flow: Optional[int] = None,
    ) -> None:
        """Store one closed span.  ~O(ns): one atomic counter increment,
        one tuple build, one masked slot store.  Drop-oldest by
        construction — slot ``i & mask`` is simply overwritten."""
        if not self.enabled:
            return
        i = next(self._counter)
        self._slots[i & self._mask] = Span(
            stage, start_ns, end_ns,
            self.current_seq if seq is None else seq,
            threading.current_thread().name,
            flow,
        )

    def span(self, stage: str, seq: Optional[int] = None,
             flow: Optional[int] = None):
        """Context manager that records ``stage`` on exit."""
        if not self.enabled:
            return _NULL_HANDLE
        return _SpanHandle(self, stage, seq, flow)

    def clock_mark(self, name: str = "obs.clock") -> None:
        """Record an empty span ``name`` inside a
        ``jax.profiler.TraceAnnotation`` of the same name.  The ring's
        spans are on ``perf_counter_ns``, a profiler trace on its own
        session-relative clock; the offset between the two is constant,
        so one mark at each end of a traced window puts every span of
        the window on the device trace's clock (the trace's annotation
        event and this span bracket the same instant)."""
        if not self.enabled:
            return
        # imported here, not at module top: federation emitters load
        # this module without jax
        import jax.profiler

        with jax.profiler.TraceAnnotation(name):
            with self.span(name):
                pass

    # -- readers (best-effort, rendezvous-free) ------------------------- #

    @property
    def recorded(self) -> int:
        """Lifetime spans recorded (monotonic; next() has not been
        called for this value yet)."""
        # itertools.count has no peek; derive from a throwaway... no:
        # that would consume a slot.  Count occupied + wraps instead is
        # racy; keep an O(capacity) scan-free estimate via the slots.
        return self._recorded_estimate()

    def _recorded_estimate(self) -> int:
        # The counter itself is the source of truth but peeking it would
        # consume an index; copy its repr instead (CPython exposes the
        # next value as count(n)).
        r = repr(self._counter)
        return int(r[r.index("(") + 1:-1])

    @property
    def dropped(self) -> int:
        """Spans overwritten before being read (lifetime)."""
        return max(0, self._recorded_estimate() - self.capacity)

    def spans(self) -> Tuple[Span, ...]:
        """A consistent-enough copy of the closed spans, oldest first.
        Concurrent records may overwrite slots mid-copy — fine for
        monitoring/export readers (each slot read is atomic)."""
        n = self._recorded_estimate()
        if n <= self.capacity:
            snap = self._slots[:n]
        else:
            head = n & self._mask
            snap = self._slots[head:] + self._slots[:head]
        return tuple(s for s in snap if s is not None)

    def spans_for(self, seq: int) -> Tuple[Span, ...]:
        return tuple(s for s in self.spans() if s.seq == seq)

    def clear(self) -> None:
        """Reset the ring (tests/benchmarks between phases)."""
        self._slots = [None] * self.capacity
        self._counter = itertools.count()


class _NullRecorder:
    """Disabled-recorder twin: every instrumentation site in the
    pipeline holds one of these by default, so un-configured systems
    pay two no-op calls per site and nothing more."""

    enabled = False
    capacity = 0
    current_seq = 0
    recorded = 0
    dropped = 0

    def begin_interval(self, seq: Optional[int] = None) -> int:
        return 0 if seq is None else seq

    def record(self, *a, **k) -> None:
        pass

    def span(self, stage: str, seq: Optional[int] = None,
             flow: Optional[int] = None):
        return _NULL_HANDLE

    def clock_mark(self, name: str = "obs.clock") -> None:
        pass

    def spans(self) -> Tuple[Span, ...]:
        return ()

    def spans_for(self, seq: int) -> Tuple[Span, ...]:
        return ()

    def clear(self) -> None:
        pass


NULL_RECORDER = _NullRecorder()


def percentile_sparse_host(
    buckets, counts, ps, precision: int = PRECISION
) -> np.ndarray:
    """Jax-free mirror of ``ops.stats.percentiles_sparse``.

    Byte-for-byte the same selection rule (stable argsort, uint64
    cumsum, ``float64(cum)/float64(total) >= p`` via a left-side
    searchsorted), but importable from processes that must never load
    jax — federation emitters compute their own stage p99s with this.
    Keep in lockstep with ops/stats.py; tests pin the two equal.
    """
    buckets = np.asarray(buckets)
    if len(buckets) == 0:
        return np.zeros(len(np.asarray(ps)))
    order = np.argsort(buckets, kind="stable")
    values = decompress_np(buckets[order], precision)
    cdf = np.cumsum(np.asarray(counts, dtype=np.uint64)[order])
    total = float(cdf[-1])
    cdfn = cdf.astype(np.float64) / total
    idx = np.searchsorted(cdfn, np.asarray(ps, dtype=np.float64), side="left")
    idx = np.minimum(idx, len(values) - 1)
    return values[idx]


class LatencyHistogram:
    """The system's own latency store: samples fold through the library
    log-bucket codec into sparse (bucket, count) state, and percentiles
    come from the same CDF walk every other histogram uses
    (``ops.stats.percentiles_sparse``) — accurate to the codec's
    relative-error bound at ANY percentile, unlike a bounded host deque
    that silently forgets history past its maxlen."""

    def __init__(self, precision: int = PRECISION):
        self.precision = precision
        self._lock = threading.Lock()
        self._buckets: Dict[int, int] = {}
        self.count = 0

    def add(self, value_us: float) -> None:
        b = int(compress_np(np.asarray([value_us]), self.precision)[0])
        with self._lock:
            self._buckets[b] = self._buckets.get(b, 0) + 1
            self.count += 1

    def percentile(self, q: float) -> float:
        """q in [0, 100] (gauge-call convention, matching the old
        ``np.percentile`` signature it replaces)."""
        with self._lock:
            if not self._buckets:
                return 0.0
            buckets = np.fromiter(self._buckets.keys(), dtype=np.int64)
            counts = np.fromiter(self._buckets.values(), dtype=np.int64)
        # imported here, not at module top: this module sits on the
        # base-package import path and federation emitters must load it
        # without pulling jax into their process
        from loghisto_tpu.ops.stats import percentiles_sparse

        return float(percentiles_sparse(
            buckets, counts, np.asarray([q / 100.0]), self.precision
        )[0])

    def percentile_host(self, q: float) -> float:
        """Same selection rule as ``percentile`` but via the jax-free
        mirror — safe to call from federation emitter processes."""
        with self._lock:
            if not self._buckets:
                return 0.0
            buckets = np.fromiter(self._buckets.keys(), dtype=np.int64)
            counts = np.fromiter(self._buckets.values(), dtype=np.int64)
        return float(percentile_sparse_host(
            buckets, counts, np.asarray([q / 100.0]), self.precision
        )[0])

    def count_above(self, value_us: float) -> int:
        """Samples whose bucket lies strictly above ``value_us``'s
        bucket — the numerator of an SLO "fraction over budget"."""
        b = int(compress_np(np.asarray([value_us]), self.precision)[0])
        with self._lock:
            return sum(c for k, c in self._buckets.items() if k > b)

    def snapshot(self) -> Tuple[np.ndarray, np.ndarray]:
        """(buckets, counts) copy for host-side oracles and rollups."""
        with self._lock:
            buckets = np.fromiter(
                self._buckets.keys(), dtype=np.int64, count=len(self._buckets)
            )
            counts = np.fromiter(
                self._buckets.values(), dtype=np.int64,
                count=len(self._buckets),
            )
        return buckets, counts


class SelfObserver:
    """Dogfooding bridge: after each committed interval the committer
    hands over that interval's closed spans; every span becomes one
    ``obs.<stage>.LatencyUs`` histogram sample through the NORMAL
    ``histogram()`` path (so exporters, retention tiers, and device
    aggregation see the pipeline's own latencies like any user metric),
    and ``commit.e2e`` samples additionally land in the
    ``LatencyHistogram`` behind the ``commit.LatencyP50Us``/``P99Us``
    gauges."""

    E2E_STAGE = "commit.e2e"

    def __init__(self, metric_system, recorder: SpanRecorder,
                 precision: int = PRECISION):
        self._ms = metric_system
        self._recorder = recorder
        self.commit_latency = LatencyHistogram(precision)
        self.reingested = 0

    def on_interval(self, seq: int) -> None:
        """Called by the committer (its bridge thread) after the
        interval's tail work — re-ingest the spans that attributed to
        ``seq``.  Exceptions never propagate into the commit path."""
        try:
            for span in self._recorder.spans_for(seq):
                us = span.duration_us
                if span.stage == self.E2E_STAGE:
                    self.commit_latency.add(us)
                self._ms.histogram(f"obs.{span.stage}.LatencyUs", us)
                self.reingested += 1
        except Exception:  # pragma: no cover - defensive
            import logging

            logging.getLogger("loghisto_tpu").exception(
                "self-observer re-ingest failed"
            )
