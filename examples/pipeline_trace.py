"""Self-observability, end to end: the pipeline watching itself.

The scenario: a fused-commit metric system runs with
``observability=ObsConfig(...)``.  Every pipeline stage (cells build,
device upload, dispatch, snapshot publish, broadcast fan-out) records a
span attributed to its interval sequence number, the watchdog evaluates
pipeline invariants, and ``/healthz`` on the Prometheus endpoint serves
the verdict as machine-readable JSON.

Three acts:

  1. healthy   — traffic flows, spans accumulate, ``/healthz`` says ok
                 and the stage table decomposes the commit latency.
  2. stall     — the committer is wedged (commits stop landing while
                 intervals keep arriving).  Within one watchdog cadence
                 ``/healthz`` flips to HTTP 503 with the machine-readable
                 reason ``no_commit`` — an orchestrator liveness probe
                 fails without parsing anything.
  3. recovery  — the committer is restored; commits resume and the
                 report clears.  The whole run is then exported as a
                 Chrome/Perfetto ``trace_events`` JSON (one track per
                 thread, interval seqs as flow ids): load it at
                 https://ui.perfetto.dev.  Its clock is perf_counter_ns;
                 ``ms.obs.clock_mark()`` at both ends of a jax.profiler
                 capture puts these spans on the device trace's clock.

Runs anywhere (CPU backend)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import json
import tempfile
import time
import urllib.error
import urllib.request

import numpy as np

from loghisto_tpu import TPUMetricSystem
from loghisto_tpu.obs import ObsConfig, dump_perfetto
from loghisto_tpu.prometheus import PrometheusEndpoint

INTERVAL = 0.25

ms = TPUMetricSystem(
    interval=INTERVAL, sys_stats=False, num_metrics=64,
    retention=[(30, 1)], commit="fused",
    observability=ObsConfig(capacity=4096, stall_intervals=2.0),
)
ep = PrometheusEndpoint(ms, port=0, host="127.0.0.1")
ms.start()
ep.start()
url = f"http://127.0.0.1:{ep.port}/healthz"


def healthz():
    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:  # 503 still carries the report
        return e.code, json.loads(e.read())


def ingest(seconds):
    rng = np.random.default_rng(0)
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        for v in rng.exponential(50.0, 100):
            ms.histogram("api.latency", float(v) * 1000.0)
        time.sleep(0.01)


# -- act 1: healthy ------------------------------------------------------- #

ingest(4 * INTERVAL)
while ms.committer.intervals_committed < 2:
    time.sleep(0.05)
code, doc = healthz()
print(f"health: {doc['status']} (HTTP {code}), "
      f"{doc['intervals_committed']} intervals committed")

# -- act 2: induced stall ------------------------------------------------- #

print("\nwedging the committer (commits stop; intervals keep arriving)...")
real_commit = ms.committer.commit
ms.committer.commit = lambda raw: None
deadline = time.monotonic() + 20.0
while time.monotonic() < deadline:
    ingest(INTERVAL)
    code, doc = healthz()
    if doc["status"] == "stalled":
        break
reason = doc["reasons"][0]
print(f"health: {doc['status']} (HTTP {code})")
print(f"reason: {reason['code']} -- {reason['detail']}")

# -- act 3: recovery + trace export --------------------------------------- #

ms.committer.commit = real_commit
deadline = time.monotonic() + 20.0
while time.monotonic() < deadline:
    ingest(INTERVAL)
    code, doc = healthz()
    if doc["status"] == "ok":
        break
print(f"\nrecovered: {doc['status']} (HTTP {code})")

ms.stop()
ep.stop()

# the span ring decomposes the end-to-end commit latency per stage
by_stage = {}
for s in ms.obs.spans():
    by_stage.setdefault(s.stage, []).append(s.duration_us)
print("\nstage decomposition (from the pipeline's own span ring):")
for stage in sorted(by_stage):
    d = by_stage[stage]
    print(f"  {stage:<24} n={len(d):<4} p50={np.percentile(d, 50):9.1f}us "
          f"p99={np.percentile(d, 99):9.1f}us")

path = os.path.join(tempfile.mkdtemp(prefix="loghisto_trace_"),
                    "pipeline_trace.json")
n = dump_perfetto(ms.obs, path)
print(f"\nperfetto: {n} events -> {path}")
print("open at https://ui.perfetto.dev; interval seqs are flow ids, "
      "one track per pipeline thread")
print("to line it up with a jax.profiler capture, call ms.obs.clock_mark() "
      "right after start_trace and right before stop_trace")
