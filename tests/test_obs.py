"""Self-observability (ISSUE 9): span ring semantics, interval
attribution, the complete-nested-span-set acceptance pin, watchdog
stall/recovery, the /healthz payload contract, Perfetto export schema,
and debug_dump()."""

import json
import threading
import time
import urllib.request

import pytest

from loghisto_tpu.obs import (
    NULL_RECORDER,
    HealthWatchdog,
    LatencyHistogram,
    ObsConfig,
    SpanRecorder,
    dump_perfetto,
)

pytestmark = pytest.mark.obs


def _system(interval=0.1, **obs_kw):
    from loghisto_tpu.system import TPUMetricSystem

    return TPUMetricSystem(
        interval=interval, sys_stats=False, num_metrics=16,
        retention=((4, 1),), commit="fused",
        observability=ObsConfig(capacity=1024, **obs_kw),
    )


def _drain(ms, minimum=1, deadline=15.0):
    """Feed samples until the committer lands `minimum` intervals."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline:
        for _ in range(20):
            ms.histogram("lat", 42.0)
        if ms.committer.intervals_committed >= minimum:
            return
        time.sleep(0.02)
    raise AssertionError("committer saw no interval before the deadline")


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, json.loads(resp.read())


# -- ring semantics ------------------------------------------------------- #


def test_ring_wraps_drop_oldest():
    rec = SpanRecorder(capacity=8)
    for i in range(20):
        rec.record(f"s{i}", i, i + 1)
    assert rec.capacity == 8
    assert rec.recorded == 20
    assert rec.dropped == 12
    spans = rec.spans()
    assert len(spans) == 8
    # oldest-first, and exactly the newest 8 survive
    assert [s.stage for s in spans] == [f"s{i}" for i in range(12, 20)]


def test_capacity_rounds_to_power_of_two_and_never_reallocates():
    rec = SpanRecorder(capacity=5)
    assert rec.capacity == 8
    for i in range(100):
        rec.record("s", i, i + 1)
    assert len(rec._slots) == 8  # overwritten in place, never resized
    with pytest.raises(ValueError):
        SpanRecorder(capacity=0)


def test_record_stays_within_time_budget():
    # the O(ns) hot-path claim, pinned loosely enough for shared CI:
    # 50k records must average well under 20us each
    rec = SpanRecorder(capacity=256)
    t0 = time.perf_counter()
    for i in range(50_000):
        rec.record("s", i, i + 1)
    assert time.perf_counter() - t0 < 1.0


def test_interval_attribution_across_threads():
    rec = SpanRecorder(capacity=256)
    seq = rec.begin_interval(7)
    assert seq == 7

    def worker():
        for i in range(10):
            rec.record("w", i, i + 1)  # no explicit seq -> current_seq

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(rec.spans_for(7)) == 40
    # explicit seq wins over current_seq
    rec.record("x", 0, 1, seq=3)
    assert [s.stage for s in rec.spans_for(3)] == ["x"]
    # minted seqs keep incrementing when the caller has none
    assert rec.begin_interval() > 0
    assert rec.begin_interval(99) == 99
    assert rec.current_seq == 99


def test_null_recorder_is_inert():
    with NULL_RECORDER.span("commit.e2e"):
        pass
    NULL_RECORDER.record("s", 0, 1)
    assert NULL_RECORDER.spans() == ()
    assert NULL_RECORDER.begin_interval(5) == 5
    assert NULL_RECORDER.recorded == 0


def test_latency_histogram_percentiles_match_codec_error_bound():
    h = LatencyHistogram()
    for v in (100.0, 200.0, 300.0, 400.0, 1000.0):
        h.add(v)
    assert h.count == 5
    # log-bucket codec: answers within its relative-error envelope
    assert h.percentile(50.0) == pytest.approx(300.0, rel=0.05)
    assert h.percentile(100.0) == pytest.approx(1000.0, rel=0.05)
    assert LatencyHistogram().percentile(99.0) == 0.0


# -- the acceptance pin: complete nested span sets per interval ----------- #


def test_committed_intervals_yield_complete_nested_span_sets():
    ms = _system()
    try:
        ms.start()
        _drain(ms, minimum=3)
    finally:
        ms.stop()
    spans = ms.obs.spans()
    e2e = [s for s in spans if s.stage == "commit.e2e"]
    assert e2e, "no end-to-end commit spans recorded"
    by_seq = {}
    for s in spans:
        by_seq.setdefault(s.seq, []).append(s)
    full = 0
    for parent in e2e:
        stages = {s.stage for s in by_seq[parent.seq]}
        # every committed interval decomposes: the synchronous commit
        # stages are always present...
        assert "commit.cells" in stages
        assert "commit.snapshot_publish" in stages
        if {"commit.upload", "commit.dispatch",
                "commit.device_sync"} <= stages:
            full += 1
        # ...and every commit-stage span NESTS inside its interval's
        # end-to-end span (same thread, bounds contained)
        for s in by_seq[parent.seq]:
            if s.stage.startswith("commit.") and s is not parent:
                assert s.thread == parent.thread
                assert s.start_ns >= parent.start_ns
                assert s.end_ns <= parent.end_ns
    # intervals that shipped cells also show the upload/dispatch/sync legs
    assert full >= 1
    # each span attributes to exactly one interval, and the committer
    # adopted the reaper-minted seqs (strictly positive, increasing)
    assert all(s.seq > 0 for s in e2e)
    assert [s.seq for s in e2e] == sorted({s.seq for s in e2e})


def test_dogfooded_spans_reenter_the_pipeline():
    from loghisto_tpu.channel import Channel

    ms = _system()
    ch = Channel(capacity=64)
    try:
        ms.start()
        ms.subscribe_to_raw_metrics(ch)
        deadline = time.monotonic() + 15.0
        seen = set()
        while time.monotonic() < deadline:
            for _ in range(20):
                ms.histogram("lat", 42.0)
            try:
                raw = ch.get(timeout=0.2)
            except Exception:  # queue.Empty on a quiet interval
                continue
            seen.update(k for k in raw.histograms if k.startswith("obs."))
            if "obs.commit.e2e.LatencyUs" in seen:
                break
        assert "obs.commit.e2e.LatencyUs" in seen
        assert ms.self_observer.reingested > 0
        # the commit.LatencyP50Us gauge path is served by the library's
        # own log-bucket histogram now, not a host-side list
        assert ms.committer._latency_pct(50.0) > 0.0
    finally:
        ms.stop()


# -- watchdog ------------------------------------------------------------- #


class _FakeCommitter:
    fanout_intervals = 0
    bridge_evictions = 0
    intervals_committed = 0


class _FakeAgg:
    max_pending_samples = 100
    pending_samples = 0
    _xfer_queued_samples = 0
    _device_down_until = 0.0


def test_watchdog_unit_invariants():
    com, agg = _FakeCommitter(), _FakeAgg()
    wd = HealthWatchdog(com, agg, interval=0.05, stall_intervals=1.0)
    assert wd.report().ok  # armed but within the window
    time.sleep(0.12)
    rep = wd.report()
    assert rep.status == "stalled"
    assert rep.reason_codes() == ["no_commit"]
    wd.note_commit(9)
    rep = wd.report()
    assert rep.ok and rep.last_seq == 9

    agg.pending_samples = 90  # >= 0.8 * 100
    agg._xfer_queued_samples = 85
    agg._device_down_until = time.monotonic() + 5.0
    wd.note_commit(10)
    codes = wd.report().reason_codes()
    assert "ingest_backpressure" in codes
    assert "transfer_drain_lag" in codes
    assert "device_cooldown" in codes
    agg.pending_samples = agg._xfer_queued_samples = 0
    agg._device_down_until = 0.0

    # event latch: a fan-out fallback stays visible for one stall
    # window, then clears
    com.fanout_intervals = 1
    wd.note_commit(11)
    assert "fused_degraded" in wd.report().reason_codes()
    time.sleep(0.12)
    wd.note_commit(12)
    assert wd.report().ok


def test_watchdog_fanout_system_reports_construction_reason():
    wd = HealthWatchdog(
        _FakeCommitter(), _FakeAgg(), interval=0.05,
        commit_path="fanout", commit_path_reason="foreign wheel",
    )
    wd.note_commit(1)
    rep = wd.report()
    assert rep.status == "degraded"
    (reason,) = rep.reasons
    assert reason["code"] == "fused_degraded"
    assert "foreign wheel" in reason["detail"]


def test_watchdog_fires_on_induced_commit_stall_and_clears():
    ms = _system(stall_intervals=2.0)
    try:
        ms.start()
        _drain(ms)
        assert ms.health.report().ok
        # induce a commit stall: the bridge keeps consuming intervals
        # but commits nothing
        real_commit = ms.committer.commit
        ms.committer.commit = lambda raw: None
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            rep = ms.health.report()
            if rep.status == "stalled":
                break
            time.sleep(0.05)
        assert rep.status == "stalled"
        assert rep.reason_codes() == ["no_commit"]
        assert rep.last_commit_age_s > 2.0 * ms.interval
        # recovery: commits resume, the report clears within a cadence
        ms.committer.commit = real_commit
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            ms.histogram("lat", 1.0)
            rep = ms.health.report()
            if rep.ok:
                break
            time.sleep(0.05)
        assert rep.ok
    finally:
        ms.stop()


# -- /healthz ------------------------------------------------------------- #


def test_healthz_payload_contract_and_status_codes():
    from loghisto_tpu.prometheus import PrometheusEndpoint

    ms = _system()
    ep = PrometheusEndpoint(ms, port=0, host="127.0.0.1")
    try:
        ms.start()
        ep.start()
        _drain(ms)
        url = f"http://127.0.0.1:{ep.port}/healthz"
        status, doc = _get(url)
        assert status == 200
        assert doc["status"] in ("ok", "degraded")
        assert isinstance(doc["ok"], bool)
        assert isinstance(doc["reasons"], list)
        for r in doc["reasons"]:
            assert set(r) == {"code", "detail", "value"}
        assert doc["last_commit_age_s"] >= 0.0
        assert doc["last_seq"] >= 0
        assert doc["intervals_committed"] >= 1
        # stalled -> 503, so liveness probes fail without parsing JSON
        ms.health._last_commit_t = time.monotonic() - 999.0
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(url)
        assert e.value.code == 503
        doc = json.loads(e.value.read())
        assert doc["status"] == "stalled"
        assert doc["reasons"][0]["code"] == "no_commit"
    finally:
        ep.stop()
        ms.stop()


def test_healthz_without_watchdog_documents_itself():
    from loghisto_tpu.metrics import MetricSystem
    from loghisto_tpu.prometheus import PrometheusEndpoint

    ms = MetricSystem(interval=60.0, sys_stats=False)
    ep = PrometheusEndpoint(ms, port=0, host="127.0.0.1")
    try:
        ep.start()
        status, doc = _get(f"http://127.0.0.1:{ep.port}/healthz")
        assert status == 200
        assert doc["status"] == "unknown"
        assert doc["ok"] is True
        assert doc["reasons"][0]["code"] == "no_watchdog"
    finally:
        ep.stop()
        ms.stop()


def test_transfer_worker_stall_surfaces_in_healthz():
    from loghisto_tpu.prometheus import PrometheusEndpoint

    ms = _system()
    ep = PrometheusEndpoint(ms, port=0, host="127.0.0.1")
    release = threading.Event()
    try:
        ms.start()
        ep.start()
        _drain(ms)
        url = f"http://127.0.0.1:{ep.port}/healthz"
        # wedge the transfer worker: items enqueue (direct aggregator
        # ingest) but never drain
        agg = ms.aggregator
        agg._process_xfer_item = lambda item: release.wait(10.0)
        agg.max_pending_samples = 64
        for _ in range(100):
            agg.record("stall", 1.0)
        agg.flush()
        deadline = time.monotonic() + 10.0
        codes = []
        while time.monotonic() < deadline:
            _, doc = _get(url)
            codes = [r["code"] for r in doc["reasons"]]
            if "transfer_drain_lag" in codes:
                break
            for _ in range(50):
                agg.record("stall", 1.0)
            agg.flush()
            time.sleep(0.05)
        assert "transfer_drain_lag" in codes
        (reason,) = [
            r for r in doc["reasons"] if r["code"] == "transfer_drain_lag"
        ]
        assert reason["value"] >= 0.8 * 64
    finally:
        release.set()
        ep.stop()
        ms.stop()


def test_health_gauges_registered():
    ms = _system()
    try:
        with ms._gauge_lock:
            names = set(ms._gauge_funcs)
        for g in ("health.Status", "health.LastCommitAgeS",
                  "health.no_commit", "health.ingest_backpressure",
                  "health.transfer_drain_lag", "health.fused_degraded",
                  "health.subscriber_evictions", "health.device_cooldown"):
            assert g in names
        assert ms._gauge_funcs["health.Status"]() in (0.0, 1.0, 2.0)
    finally:
        ms.stop()


# -- Perfetto export ------------------------------------------------------ #


def test_perfetto_dump_schema(tmp_path):
    rec = SpanRecorder(capacity=64)
    rec.begin_interval(1)
    with rec.span("commit.e2e"):
        with rec.span("commit.cells"):
            pass
    rec.begin_interval(2)

    def off_thread():
        rec.record("ingest.drain", 10, 20)

    t = threading.Thread(target=off_thread, name="xfer-test")
    t.start()
    t.join()
    path = tmp_path / "trace.json"
    n = dump_perfetto(rec, str(path))
    doc = json.loads(path.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    # one named track per recording thread
    threads = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert "xfer-test" in threads
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {
        "commit.e2e", "commit.cells", "ingest.drain"
    }
    for e in xs:
        assert e["dur"] >= 0 and e["ts"] >= 0
        assert isinstance(e["args"]["seq"], int)
    # flow events chain each interval's spans: "s" opens a seq id,
    # "t" continues it
    flows = [e for e in events if e["ph"] in ("s", "t")]
    for seq in (1, 2):
        chain = [e for e in flows if e["id"] == seq]
        assert chain and chain[0]["ph"] == "s"
        assert all(e["ph"] == "t" for e in chain[1:])
        assert all(e["cat"] == "interval" for e in chain)
    assert n == len(events)


def test_debug_dump_keys():
    ms = _system()
    try:
        dump = ms.debug_dump()
        assert {
            "commit_path", "commit_path_reason", "mesh", "registry",
            "rings", "transport", "query", "commit", "obs", "health",
        } <= set(dump)
        assert dump["obs"]["enabled"] is True
        assert dump["obs"]["capacity"] == 1024
        assert dump["health"]["status"] in ("ok", "degraded", "stalled")
        assert dump["registry"]["capacity"] >= dump["registry"]["occupancy"]
        assert json.dumps(dump)  # JSON-serializable end to end
    finally:
        ms.stop()


def test_debug_dump_without_observability():
    from loghisto_tpu.system import TPUMetricSystem

    ms = TPUMetricSystem(interval=1.0, sys_stats=False, num_metrics=16)
    try:
        dump = ms.debug_dump()
        assert dump["obs"]["enabled"] is False
        assert dump["health"] is None
    finally:
        ms.stop()


# -- spans on the device trace's clock, and the spans inside the stages --- #


def _nests(child, parent):
    return (child.thread == parent.thread
            and parent.start_ns <= child.start_ns
            and child.end_ns <= parent.end_ns)


def test_clock_mark_maps_spans_onto_the_profiler_clock(tmp_path):
    """Two clock marks around a CPU profiler session put a span recorded
    inside a known TraceAnnotation within 100 us of that annotation on
    the trace's clock, and the two marks' readings agree as closely."""
    import sys

    import jax

    from benchmark import spanclock, xplane

    rec = SpanRecorder(capacity=64)
    switch = sys.getswitchinterval()
    # no thread switch between an annotation's start and its span's
    sys.setswitchinterval(1.0)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            rec.clock_mark()
            with jax.profiler.TraceAnnotation("known"):
                with rec.span("known"):
                    time.sleep(0.02)
            rec.clock_mark()
        finally:
            jax.profiler.stop_trace()
    finally:
        sys.setswitchinterval(switch)
    events = xplane.load_events(xplane.find_trace(str(tmp_path)))
    offset, readings = spanclock.span_offset_ns(events, rec.spans())
    assert len(readings) == 2
    assert abs(readings[1] - readings[0]) < 100_000
    (known,) = [e for e in events if e.name == "known"]
    (span,) = spanclock.on_trace_clock(
        [s for s in rec.spans() if s.stage == "known"], offset)
    assert abs(span.start_ns - known.start_ns) < 100_000
    assert abs(span.end_ns - known.end_ns) < 100_000


@pytest.mark.parametrize("recorder", ["disabled", "null"])
def test_disabled_recorder_marks_nothing(monkeypatch, recorder):
    import jax

    started = []

    class Annotation:
        def __init__(self, name):
            started.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    rec = NULL_RECORDER
    if recorder == "disabled":
        rec = SpanRecorder(capacity=8)
        rec.enabled = False
    rec.clock_mark()
    assert started == [] and rec.spans() == ()
    on = SpanRecorder(capacity=8)
    on.clock_mark()
    assert started == ["obs.clock"]
    assert [s.stage for s in on.spans()] == ["obs.clock"]


def _transport_spans(transport):
    import numpy as np

    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    agg = TPUAggregator(num_metrics=16, config=MetricConfig(bucket_limit=256),
                        transport=transport, batch_size=1024)
    agg.obs_recorder = SpanRecorder(capacity=256)
    rng = np.random.default_rng(3)
    for _ in range(3):
        agg.record_batch(rng.integers(0, 16, 5000).astype(np.int32),
                         rng.lognormal(4, 1, 5000).astype(np.float32))
        agg.flush(force=True)
    agg.close()
    return agg.obs_recorder.spans()


def test_sparse_drain_decomposes_into_fold_lock_wait_and_merge():
    spans = _transport_spans("sparse")
    drains = [s for s in spans if s.stage == "ingest.drain"]
    assert len(drains) == 3
    for d in drains:
        inner = [s for s in spans if s.stage in (
            "ingest.fold", "ingest.lock_wait", "ingest.merge")
            and _nests(s, d)]
        # one of each per item, one after the other
        ordered = sorted(inner, key=lambda s: s.start_ns)
        assert [s.stage for s in ordered] == [
            "ingest.fold", "ingest.lock_wait", "ingest.merge"]
        assert ordered[0].end_ns <= ordered[1].start_ns
        assert ordered[1].end_ns <= ordered[2].start_ns


def test_raw_drain_waits_for_the_lock_before_its_dispatches():
    spans = _transport_spans("raw")
    drains = [s for s in spans if s.stage == "ingest.drain"]
    assert len(drains) == 3
    for d in drains:
        (wait,) = [s for s in spans
                   if s.stage == "ingest.lock_wait" and _nests(s, d)]
        dispatches = [s for s in spans
                      if s.stage == "ingest.dispatch" and _nests(s, d)]
        assert dispatches
        assert all(wait.end_ns <= s.start_ns for s in dispatches)
    assert not [s for s in spans if s.stage in ("ingest.fold",
                                                "ingest.merge")]


@pytest.fixture(scope="module")
def served_spans():
    """A traced system that committed intervals and then answered a
    window query and a group-by, its spans taken after the stop."""
    ms = _system()
    try:
        ms.start()
        for _ in range(5):
            ms.histogram("http.lat", 5.0, labels={"route": "/a"})
            ms.histogram("http.lat", 9.0, labels={"route": "/b"})
        _drain(ms, minimum=3)
        ms.query_window("lat", window=None)
        ms.query_group_by("http.lat{}", by=["route"], window=None)
    finally:
        ms.stop()
    return ms.obs.spans()


def test_commit_lock_wait_nests_in_commit_e2e(served_spans):
    e2e = {s.seq: s for s in served_spans if s.stage == "commit.e2e"}
    waits = [s for s in served_spans if s.stage == "commit.lock_wait"]
    assert waits
    for w in waits:
        assert _nests(w, e2e[w.seq])
        # the wait ends before the interval's first upload starts
        ups = [s.start_ns for s in served_spans
               if s.stage == "commit.upload" and s.seq == w.seq]
        assert all(w.end_ns <= u for u in ups)


def test_reaper_collect_carries_the_interval_seq(served_spans):
    collected = {s.seq: s for s in served_spans
                 if s.stage == "reaper.collect"}
    e2e = [s for s in served_spans if s.stage == "commit.e2e"]
    assert e2e
    for s in e2e:
        c = collected[s.seq]
        assert c.thread != s.thread
        assert c.start_ns <= c.end_ns <= s.start_ns


def test_query_gather_nests_in_query_serve(served_spans):
    serves = [s for s in served_spans if s.stage == "query.serve"]
    gathers = [s for s in served_spans if s.stage == "query.gather"]
    # the window query's and the group-by's
    assert len(gathers) >= 2
    for g in gathers:
        assert any(_nests(g, s) for s in serves)


def test_backpressure_flushes_count_flushes_a_full_queue_refused():
    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    agg = TPUAggregator(num_metrics=16, config=MetricConfig(bucket_limit=64),
                        transport="raw", batch_size=256)
    release = threading.Event()
    agg._process_xfer_item = lambda item: release.wait(10.0)
    agg.max_pending_samples = 64
    try:
        for _ in range(100):
            agg.record("m", 1.0)
        agg.flush()  # enqueued: the worker takes it and wedges
        deadline = time.monotonic() + 10.0
        while agg._xfer_queue and time.monotonic() < deadline:
            time.sleep(0.005)
        assert agg._xfer_active and not agg._xfer_queue
        for _ in range(10):
            agg.record("m", 1.0)
        # one item may always wait behind the one in flight
        agg.flush()
        assert agg.transport_stats()["backpressure_flushes"] == 0
        assert agg._buffered_samples() == 0
        for _ in range(10):
            agg.record("m", 1.0)
        # the queue still counts the wedged item's 100 samples, at least
        # max_pending_samples, and holds a waiting item: the flush leaves
        # these 10 on the host
        agg.flush()
        assert agg.transport_stats()["backpressure_flushes"] == 1
        assert agg._buffered_samples() == 10
    finally:
        release.set()
        agg.wait_transfers(timeout=10.0)


def test_trace_capture_module_is_gone_and_collect_answers(tmp_path):
    """collect() runs no profiler session of its own: it answers inside
    one that is already running."""
    import importlib.util

    import jax

    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    assert importlib.util.find_spec("loghisto_tpu.utils.trace") is None
    agg = TPUAggregator(num_metrics=8, config=MetricConfig(bucket_limit=64))
    for v in (33.0, 59.0, 330000.0):
        agg.record("histogram1", v)
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = agg.collect().metrics
    finally:
        jax.profiler.stop_trace()
    agg.close()
    assert got["histogram1_count"] == 3.0


def test_device_programs_have_stable_names_in_their_layers():
    """Each jitted program on the main path has a name of its own, and
    the benchmark's programs.json still puts it in the same layer."""
    import os
    import re

    from loghisto_tpu.ops.sparse_ingest import settle_ingest_delta

    ms = _system()
    try:
        agg, wheel, com = ms.aggregator, ms.retention, ms.committer
        names = {
            agg._packed_ingest: "ingest",
            settle_ingest_delta: "ingest",
            agg._ingest: "ingest",
            com._fused: "commit",
            com._fused_snap: "commit",
            wheel._query_fn: "query",
            agg._stats_fn: "other",
            wheel._stats_fn: "other",
            wheel._snapshot_fn: "other",
        }
    finally:
        ms.stop()
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "metrics", "programs.json")
    with open(path) as f:
        layers = json.load(f)["layers"]
    seen = set()
    for fn, layer in names.items():
        name = fn.__name__
        got = next((k for k, pats in layers.items()
                    if any(re.search(p, name) for p in pats)), "other")
        assert got == layer, name
        seen.add(name)
    assert len(seen) == len(names)
