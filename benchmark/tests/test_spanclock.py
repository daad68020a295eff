"""The program's spans on the device trace's clock (``spanclock``), and
the per-layer readers of the transfer worker's and the commit's child
spans, on made-up spans, the recorded v5e trace and one CPU-size run."""

import os

import pytest

from benchmark import harness, spanclock, xplane
from benchmark.xplane import Event
from loghisto_tpu.obs import Span

from .test_runs import SCALE
from .test_xplane import LAYERS, RECORDED, made_up

MS = 1_000_000


def span(stage, start, end, seq=0, thread="t"):
    return Span(stage, int(start), int(end), seq, thread)


def test_offset_from_the_clock_marks():
    events = [Event("/host:CPU", "python", "obs.clock", 100.0, 110.0),
              Event("/host:CPU", "python", "other", 120.0, 130.0),
              Event("/host:CPU", "python", "obs.clock", 9_100.0, 9_105.0)]
    spans = [span("obs.clock", 5_000_102, 5_000_104),
             span("ingest.drain", 5_000_000, 5_009_000),
             span("obs.clock", 5_009_103, 5_009_104)]
    offset, readings = spanclock.span_offset_ns(events, spans)
    assert readings == [-5_000_002.0, -5_000_003.0]
    assert offset == -5_000_002.5
    (moved,) = spanclock.on_trace_clock(spans[1:2], offset)
    assert (moved.start_ns, moved.end_ns) == (-2.5, 8_997.5)
    assert moved.stage == "ingest.drain" and moved.thread == "t"
    # a mark on one clock only: no offset
    assert spanclock.span_offset_ns(events, spans[:2]) is None
    assert spanclock.span_offset_ns([], spans) is None


def test_idle_gaps_without_spans_are_xplanes():
    events = xplane.load_events(RECORDED)
    busy = xplane.reduce(events, LAYERS, 1.0)["busy_first"]
    assert (spanclock.idle_gaps(events, busy)
            == xplane.idle_gaps(events, busy))
    busy = xplane.reduce(made_up(), LAYERS, 0.02)["busy_first"]
    assert (spanclock.idle_gaps(made_up(), busy, spans=None)
            == xplane.idle_gaps(made_up(), busy))


def test_recorded_trace_gaps_named_by_the_innermost_covering_span():
    """On the recorded v5e trace: a span that covers the longest gap
    names it, over the enclosing span that covers it as fully; a span
    that covers under 5% of a gap leaves it its host-event name."""
    events = xplane.load_events(RECORDED)
    busy = xplane.reduce(events, LAYERS, 1.0)["busy_first"]
    by_host = xplane.idle_gaps(events, busy)
    longest = sorted(((e - s, s, e) for s, e in spanclock.gaps(busy)),
                     reverse=True)
    (g0, s0, e0), (g1, s1, e1) = longest[0], longest[1]
    spans = [span("ingest.drain", busy[0][0], busy[-1][1]),
             span("ingest.merge", s0, e0),
             span("ingest.fold", s1, s1 + 0.01 * g1)]
    got = spanclock.idle_gaps(events, busy, spans=spans[1:])
    assert got[0] == ["ingest.merge", pytest.approx(g0 / 1e9)]
    assert got[1] == by_host[1]
    got = spanclock.idle_gaps(events, busy, spans=spans)
    assert got[0][0] == "ingest.merge"
    assert all(row[0] == "ingest.drain" for row in got[1:])
    assert [row[1] for row in got] == [row[1] for row in by_host]
    assert spanclock.idle_covered_share(busy, spans) == pytest.approx(1.0)
    share = spanclock.idle_covered_share(busy, spans[1:2])
    idle = sum(e - s for s, e in spanclock.gaps(busy))
    assert share == pytest.approx(g0 / idle)
    assert spanclock.stage_cover(spans, s0, e0) == {
        "ingest.drain": 1.0, "ingest.merge": 1.0}
    assert spanclock.stage_cover(spans, s1, e1) == {
        "ingest.drain": 1.0, "ingest.fold": pytest.approx(0.01, abs=1e-3)}
    by_stage = spanclock.idle_by_stage(busy, spans)
    assert by_stage["ingest.drain"] == pytest.approx(1.0)
    assert by_stage["ingest.merge"] == pytest.approx(g0 / idle)


def test_drain_and_commit_decompose_into_their_children():
    spans = [span("ingest.drain", 0, 80), span("ingest.fold", 0, 30),
             span("ingest.lock_wait", 30, 40), span("ingest.merge", 40, 75),
             span("ingest.drain", 90, 120), span("ingest.fold", 90, 100),
             span("commit.e2e", 0, 10 * MS, seq=4, thread="c"),
             span("commit.lock_wait", 1 * MS, 2 * MS, seq=4, thread="c"),
             span("commit.dispatch", 2 * MS, 6 * MS, seq=4, thread="c"),
             span("commit.e2e", 20 * MS, 24 * MS, seq=5, thread="c"),
             span("commit.dispatch", 20 * MS, 22 * MS, seq=5, thread="c")]
    d = spanclock.drain_parts(spans, 0, 200)
    assert d == {"ingest.drain": 55.0, "ingest.fold": 20.0,
                 "ingest.lock_wait": 5.0, "ingest.merge": 17.5,
                 "self": 12.5}
    c = spanclock.commit_parts(spans)
    assert c == {"commit.e2e": 7.0, "commits": 2, "commit.dispatch": 3.0,
                 "commit.lock_wait": 0.5, "self": 3.5}
    assert spanclock.commit_parts(spans[:6]) == {}


def context(spans, t0=0, t1=1000):
    return harness.ReaderContext(
        setup_s=1.0, window_s=(t1 - t0) / 1e9, drained_s=1.0, streams={},
        freshness_ms=None, spans=spans, t0_ns=t0, t1_ns=t1, trace=None,
        samples=0, distinct_cells=0, device_kind="cpu", commits=0,
        queries=0, result_cache_hits=0)


def read(name, ctx):
    return harness.metric_reader(harness.ROOT, name).read(ctx)


def test_worker_child_shares_read_the_union_in_the_window():
    ctx = context([span("ingest.fold", -50, 100), span("ingest.fold", 50, 200),
                   span("ingest.lock_wait", 300, 310),
                   span("ingest.merge", 400, 500),
                   span("ingest.merge", 950, 1100),
                   span("ingest.drain", 0, 1000)])
    assert read("aggregator.fold_share", ctx) == pytest.approx(20.0)
    assert read("aggregator.lock_wait_share", ctx) == pytest.approx(1.0)
    assert read("aggregator.merge_share", ctx) == pytest.approx(15.0)


def test_commit_waits_read_per_interval():
    spans = [span("reaper.collect", 0, 2 * MS, seq=1, thread="reaper"),
             span("commit.e2e", 5 * MS, 40 * MS, seq=1, thread="c"),
             span("commit.lock_wait", 6 * MS, 9 * MS, seq=1, thread="c"),
             span("reaper.collect", 100 * MS, 101 * MS, seq=2,
                  thread="reaper"),
             span("commit.e2e", 103 * MS, 120 * MS, seq=2, thread="c"),
             span("commit.lock_wait", 104 * MS, 105 * MS, seq=2, thread="c"),
             # an interval collected before the window: no pair
             span("commit.e2e", 200 * MS, 210 * MS, seq=3, thread="c")]
    ctx = context(spans, 0, 1000 * MS)
    assert read("commit.lock_wait_ms", ctx) == pytest.approx(2.0)
    assert read("commit.wait_ms", ctx) == pytest.approx(4.0)


@pytest.mark.parametrize("name", [
    "aggregator.fold_share", "aggregator.lock_wait_share",
    "aggregator.merge_share", "commit.lock_wait_ms", "commit.wait_ms"])
def test_new_readers_find_nothing_in_a_program_without_the_spans(name):
    """The parent's program records only ``ingest.drain`` and the
    commit's own stages: each new reader returns None there."""
    ctx = context([span("ingest.drain", 0, 900),
                   span("commit.e2e", 0, 500, seq=1),
                   span("commit.dispatch", 10, 20, seq=1)])
    assert read(name, ctx) is None


def test_cpu_size_traced_run_reports_the_marks_and_every_reader():
    report, lines, diag = spanclock.traced_run(
        "rpc10k.firehose", 2**33 + 11, 2.5, require_tpu=False, scale=SCALE)
    assert report["correct"], lines
    a, b = report["clock_readings_ns"]
    assert abs(b - a) < 1e6
    m = report["metrics"]
    assert {"ingest_samples_per_s", "setup_s", "aggregator.fold_share",
            "aggregator.lock_wait_share", "aggregator.merge_share"} <= {
        k for k, v in m.items() if v is not None}
    d = report["drain"]
    assert d["ingest.drain"] == pytest.approx(m["aggregator.drain_share"])
    assert d["ingest.fold"] == pytest.approx(m["aggregator.fold_share"])
    assert report["counters"]["backpressure_flushes"] >= 0
    assert report["counters"]["h2d_bytes_per_sample"] > 0
    assert any(line.startswith("clock marks: offsets") for line in diag)
    # no device plane on the CPU: no gaps to name
    assert report["idle_gaps"] == [] and report["idle_gap_parts"] == []
    assert not os.path.exists(os.path.join(harness.ROOT, ".bench_traces",
                                           "rpc10k.firehose.8589934603"))
