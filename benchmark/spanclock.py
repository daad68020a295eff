"""Put the program's spans on a profiler trace's clock, and name the
device's idle gaps by them.

The program's spans (``loghisto_tpu.obs.SpanRecorder``) are timed on
``perf_counter_ns``; a ``jax.profiler`` trace keeps a clock of its own,
relative to its session.  ``SpanRecorder.clock_mark()`` records a span
inside a ``TraceAnnotation`` of the same name: the difference of the two
start times is the offset between the clocks, the same for every mark of
a session.  ``span_offset_ns`` reads it from the marks,
``on_trace_clock`` moves spans by it, and ``idle_gaps`` names each gap
by the span that covers most of it.

Run as a script, it runs one cell traced, with a mark right after the
profiler session starts and one right before it stops, and prints one
JSON line: both readings of the offset, the longest idle gaps named by
spans and by host events, the share of idle time spans cover, the
decomposition of ``ingest.drain`` and ``commit.e2e`` into their child
spans, the aggregator's transfer counters over the window, and every
reader of the cell (end-to-end and per-layer) on the traced run:

    python -m benchmark.spanclock --workload <cell> --seed <n> --seconds <s>

The harness places no marks itself yet, so the script wraps its
profiler calls to place them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types
from unittest import mock

from benchmark import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MARK = "obs.clock"
# a span names a gap only where it covers this share of it (the share
# below which xplane.idle_gaps too stops naming a gap by a host event)
MIN_COVER = 0.05
DRAIN, DRAIN_PARTS = "ingest.drain", ("ingest.fold", "ingest.lock_wait",
                                      "ingest.merge")
COMMIT = "commit.e2e"


def span_offset_ns(events, spans):
    """The offset that puts a ``perf_counter_ns`` time on the trace's
    clock, from the clock marks (only the ``obs.clock`` spans of
    ``spans`` are read): ``(offset, readings)``, each reading one mark's
    annotation start minus its span start, oldest first, and the offset
    their mean.  None where the trace and the spans hold no marks or a
    different number of them."""
    on_trace = sorted(e.start_ns for e in events
                      if e.name == MARK and e.plane.startswith("/host:"))
    recorded = sorted(s.start_ns for s in spans if s.stage == MARK)
    if not on_trace or len(on_trace) != len(recorded):
        return None
    readings = [t - s for t, s in zip(on_trace, recorded)]
    return sum(readings) / len(readings), readings


def on_trace_clock(spans, offset: float, lo: float = float("-inf"),
                   hi: float = float("inf")) -> list:
    """The spans that overlap [lo, hi) on the trace's clock, moved onto
    it."""
    out = []
    for s in spans:
        a, b = s.start_ns + offset, s.end_ns + offset
        if b >= lo and a < hi:
            out.append(s._replace(start_ns=a, end_ns=b))
    return out


def gaps(busy) -> list:
    """[start, end) of each gap between consecutive busy intervals."""
    return [[busy[i][1], busy[i + 1][0]] for i in range(len(busy) - 1)]


def covering_span(spans, start: float, end: float):
    """(span, covered ns) of the span that covers most of [start, end);
    of equal covers the shorter (innermost) one.  (None, 0.0) where no
    span overlaps it."""
    best, cover = None, 0.0
    for s in spans:
        c = min(end, s.end_ns) - max(start, s.start_ns)
        if c > cover or (best is not None and c == cover and
                         s.end_ns - s.start_ns < best.end_ns - best.start_ns):
            best, cover = s, c
    return best, cover


def idle_gaps(events, busy, top: int = 10, spans=None) -> list:
    """``xplane.idle_gaps``, with each gap named by the program span (on
    the trace's clock) that covers most of it where ``spans`` are given
    and one covers at least ``MIN_COVER`` of it; other gaps keep their
    host-event name.  With ``spans=None`` the output is
    ``xplane.idle_gaps``'s."""
    named = xplane.idle_gaps(events, busy, top)
    if not spans:
        return named
    longest = sorted(((e - s, s, e) for s, e in gaps(busy)),
                     reverse=True)[:top]
    for row, (g, s, e) in zip(named, longest):
        best, cover = covering_span(spans, s, e)
        if best is not None and cover >= MIN_COVER * g:
            row[0] = best.stage
    return named


def idle_covered_share(busy, spans):
    """Share of the idle time between busy intervals that some span
    covers; None where the device was never idle between two runs."""
    idle = [g for g in gaps(busy) if g[1] > g[0]]
    total = xplane.length(idle)
    if total <= 0:
        return None
    cover = xplane.union((s.start_ns, s.end_ns) for s in spans)
    uncovered = xplane.subtract(idle, cover)
    return (total - xplane.length(uncovered)) / total


def stage_cover(spans, start: float, end: float) -> dict:
    """Share of [start, end) that each stage's spans cover."""
    by: dict = {}
    for s in spans:
        if s.end_ns > start and s.start_ns < end:
            by.setdefault(s.stage, []).append(
                (max(s.start_ns, start), min(s.end_ns, end)))
    return {k: xplane.length(xplane.union(v)) / (end - start)
            for k, v in sorted(by.items())}


def idle_by_stage(busy, spans) -> dict:
    """Share of the idle time between busy intervals that each stage's
    spans cover (stages overlap, so the shares need not sum to 1)."""
    idle = [g for g in gaps(busy) if g[1] > g[0]]
    total = xplane.length(idle)
    out = {}
    for stage in sorted({s.stage for s in spans}):
        cover = xplane.union((s.start_ns, s.end_ns) for s in spans
                             if s.stage == stage)
        out[stage] = (total - xplane.length(xplane.subtract(idle, cover))
                      ) / total if total > 0 else 0.0
    return out


def union_of(spans, stage: str, t0: float, t1: float) -> list:
    """The union of ``stage``'s spans, clipped to [t0, t1)."""
    return xplane.union((max(s.start_ns, t0), min(s.end_ns, t1))
                        for s in spans if s.stage == stage
                        and s.end_ns > t0 and s.start_ns < t1)


def window_share(spans, stage: str, t0: float, t1: float):
    """Percent of [t0, t1) inside ``stage``'s spans; None where there
    are none."""
    got = union_of(spans, stage, t0, t1)
    if not got:
        return None
    return 100.0 * xplane.length(got) / (t1 - t0)


def drain_parts(spans, t0: float, t1: float) -> dict:
    """Percent of the window in ``ingest.drain`` and in each of its
    child spans, and ``self``: drain time outside all of them."""
    drain = union_of(spans, DRAIN, t0, t1)
    out = {DRAIN: 100.0 * xplane.length(drain) / (t1 - t0)}
    parts = []
    for stage in DRAIN_PARTS:
        u = union_of(spans, stage, t0, t1)
        out[stage] = 100.0 * xplane.length(u) / (t1 - t0)
        parts += u
    rest = xplane.subtract(drain, xplane.union(parts))
    out["self"] = 100.0 * xplane.length(rest) / (t1 - t0)
    return out


def commit_parts(spans) -> dict:
    """Per ``commit.e2e`` span: its mean milliseconds, the mean of each
    ``commit.*`` stage of the same interval, and ``self``, the mean
    left over (the stages follow one another on the committer thread)."""
    e2e = {s.seq: s for s in spans if s.stage == COMMIT}
    if not e2e:
        return {}
    sums: dict = {}
    for s in spans:
        p = e2e.get(s.seq)
        if (s.stage.startswith("commit.") and s.stage != COMMIT
                and p is not None and p.start_ns <= s.start_ns
                and s.end_ns <= p.end_ns):
            sums[s.stage] = sums.get(s.stage, 0) + (s.end_ns - s.start_ns)
    n = len(e2e)
    total = sum(p.end_ns - p.start_ns for p in e2e.values())
    out = {COMMIT: total / n / 1e6, "commits": n}
    out.update({k: v / n / 1e6 for k, v in sorted(sums.items())})
    out["self"] = (total - sum(sums.values())) / n / 1e6
    return out


def traced_run(workload: str, seed: int, seconds: float, root: str = ROOT,
               require_tpu: bool = True, scale=None) -> tuple:
    """Run one cell traced, with a clock mark at each end of the
    profiler session.  Returns (report, check lines, diagnostic lines)."""
    import jax

    from benchmark import harness

    got: dict = {}
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    load, reader = xplane.load_events, harness.metric_reader

    def start_marked(*a, **kw):
        start(*a, **kw)
        got["ms"].obs.clock_mark()
        got["stats0"] = got["ms"].aggregator.transport_stats()

    def stop_marked():
        got["stats1"] = got["ms"].aggregator.transport_stats()
        got["ms"].obs.clock_mark()
        stop()

    def load_kept(path, host=True):
        got["events"] = load(path, host)
        return got["events"]

    def reader_seen(r, name):
        mod = reader(r, name)

        def read(ctx):
            got["ctx"] = ctx
            return mod.read(ctx)
        return types.SimpleNamespace(read=read)

    with mock.patch.object(jax.profiler, "start_trace", start_marked), \
            mock.patch.object(jax.profiler, "stop_trace", stop_marked), \
            mock.patch.object(xplane, "load_events", load_kept), \
            mock.patch.object(harness, "metric_reader", reader_seen):
        result, checks, diag = harness.run(
            workload, seed, seconds, True, root=root,
            require_tpu=require_tpu, scale=scale,
            patch=lambda ms: got.__setitem__("ms", ms))
    ctx, events = got["ctx"], got["events"]
    spans = got["ms"].obs.spans()
    spec = harness.cell_spec(root, workload)
    metrics = {m["name"]: reader(root, m["name"]).read(ctx)
               for m in spec.end_to_end + spec.per_layer}
    s0, s1 = got["stats0"], got["stats1"]
    shipped = s1["samples_shipped"] - s0["samples_shipped"]
    report = {
        "workload": workload, "seed": seed, "correct": result["correct"],
        "device": result["device"], "metrics": metrics,
        "counters": {
            "h2d_bytes_per_sample": (
                (s1["bytes_uploaded"] - s0["bytes_uploaded"]) / shipped
                if shipped else None),
            "backpressure_flushes": (s1["backpressure_flushes"]
                                     - s0["backpressure_flushes"]),
        },
        "drain": drain_parts(ctx.spans, ctx.t0_ns, ctx.t1_ns),
        "commit": commit_parts(ctx.spans),
    }
    red = xplane.reduce(events, harness.program_layers(root), ctx.window_s)
    busy = red["busy_first"]
    report["idle_gaps_by_host"] = xplane.idle_gaps(events, busy)
    clock = span_offset_ns(events, spans)
    if clock is None:
        diag.append("clock marks: not found on both clocks")
    else:
        offset, readings = clock
        diag.append(f"clock marks: offsets {readings} ns, "
                    f"{readings[-1] - readings[0]:.0f} ns apart")
        report["clock_readings_ns"] = readings
        lo = busy[0][0] if busy else float("-inf")
        hi = busy[-1][1] if busy else float("inf")
        mapped = on_trace_clock(spans, offset, lo, hi)
        report["idle_gaps"] = idle_gaps(events, busy, spans=mapped)
        # where each of the longest gaps lies (seconds after the window
        # opened) and what each stage covers of it
        t0 = ctx.t0_ns + offset
        report["idle_gap_parts"] = [
            {"s": (e - s) / 1e9, "at_s": (s - t0) / 1e9,
             "cover": stage_cover(mapped, s, e)}
            for _, s, e in sorted(((e - s, s, e) for s, e in gaps(busy)),
                                  reverse=True)[:10]]
        report["idle_by_stage"] = idle_by_stage(busy, mapped)
        share = idle_covered_share(busy, mapped)
        report["idle_covered_share"] = share
        diag.append(f"share of device idle time a program span covers: "
                    f"{share}")
    return report, checks, diag


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from benchmark import run

    run.use_compile_cache()
    from benchmark import harness

    try:
        report, checks, diag = traced_run(args.workload, args.seed,
                                          args.seconds)
    except harness.NoChip as e:
        print(f"spanclock: {e}", file=sys.stderr)
        return 2
    for line in diag + checks:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
