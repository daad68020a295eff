"""Observability receipts (the ISSUE 9 tentpole): what the span
recorder actually costs on the hot path, and what the pipeline's own
stage timings look like once it observes itself.

Two parts:

  * firehose contender — ``run_firehose`` with ``recorder=None`` vs a
    live ``SpanRecorder``: the recorder adds one ``perf_counter_ns``
    pair + one ring store per dispatch step, so throughput loss is the
    honest price of always-on observability.  The acceptance criterion
    is < 2% (``obs_overhead_pct``).  Contenders alternate rep by rep so
    host-speed drift (this shared host swings >2x between windows; see
    bench.py's ``cpu_calibration_mb_s``) hits both sides equally.
  * pipeline stage decomposition — a fused ``TPUMetricSystem`` with
    ``observability=ObsConfig(...)`` driven for a few seconds; per-stage
    p99s come straight from the span ring (the same data Perfetto
    renders), and ``pipeline_stage_p99_us`` is the end-to-end
    ``commit.e2e`` p99.

The roofline plausibility guard marks a throughput whose implied ingest
bandwidth (4 B/sample device-side) exceeds the platform cap as suspect
rather than reporting a faster-than-physics overhead number.

Usage: python benchmarks/obs_overhead.py [--reps 4] [--seconds 1.5]
       [--tpu] [--out OBS_OVERHEAD_r9.json]
Prints one JSON object (save as OBS_OVERHEAD_r*.json); importable as
``run(...)`` for tests/capture and for bench.py's ``obs_overhead_pct``
and ``pipeline_stage_p99_us`` headline fields.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import numpy as np

from bench import peak_bytes_per_s

NUM_METRICS = 1024
BATCH = 1 << 16
BUCKET_LIMIT = 1024


def _firehose_rate(seconds: float, recorder) -> float:
    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.firehose import run_firehose

    summary = run_firehose(
        num_metrics=NUM_METRICS, batch=BATCH, seconds=seconds,
        interval=max(seconds / 3.0, 0.2),
        config=MetricConfig(bucket_limit=BUCKET_LIMIT),
        out=io.StringIO(), recorder=recorder,
    )
    return float(summary["samples_per_s"])


def _pipeline_stages(seconds: float) -> dict:
    """Drive a fused self-observing system and read the stage p99s out
    of its own span ring."""
    from loghisto_tpu.obs import ObsConfig
    from loghisto_tpu.system import TPUMetricSystem

    ms = TPUMetricSystem(
        interval=0.1, sys_stats=False, num_metrics=64,
        retention=((8, 1),), commit="fused",
        observability=ObsConfig(capacity=8192),
    )
    try:
        ms.start()
        deadline = time.monotonic() + seconds
        rng = np.random.default_rng(0)
        while time.monotonic() < deadline:
            for v in rng.exponential(500.0, 200):
                ms.histogram("bench.lat", float(v))
            time.sleep(0.005)
        # let the last interval commit before reading the ring
        t0 = time.monotonic()
        while ms.committer.intervals_committed < 2 \
                and time.monotonic() - t0 < 5.0:
            time.sleep(0.02)
    finally:
        ms.stop()
    by_stage: dict = {}
    for s in ms.obs.spans():
        by_stage.setdefault(s.stage, []).append(s.duration_us)
    return {
        stage: {
            "count": len(d),
            "p50_us": round(float(np.percentile(d, 50)), 1),
            "p99_us": round(float(np.percentile(d, 99)), 1),
        }
        for stage, d in sorted(by_stage.items())
    }


def run(reps: int = 4, seconds: float = 1.5) -> dict:
    import jax

    from loghisto_tpu.obs import SpanRecorder

    platform = jax.devices()[0].platform
    cap = peak_bytes_per_s(jax.devices()[0].device_kind)

    # alternate the contenders so host-speed drift cancels
    off_rates, on_rates = [], []
    recorders = []
    for _ in range(reps):
        off_rates.append(_firehose_rate(seconds, None))
        rec = SpanRecorder(capacity=8192)
        on_rates.append(_firehose_rate(seconds, rec))
        recorders.append(rec)
    off_med = float(np.median(off_rates))
    on_med = float(np.median(on_rates))
    overhead_pct = (off_med - on_med) / max(off_med, 1e-9) * 100.0

    implied_bw = off_med * 4.0  # 4 B/sample reaches the device kernel
    suspect = implied_bw > cap
    if suspect:
        print(
            f"obs_overhead: implied ingest bandwidth {implied_bw:.3e} "
            f"B/s exceeds the {platform} roofline cap {cap:.3e}; "
            "marking suspect", file=sys.stderr,
        )

    spans_recorded = sum(r.recorded for r in recorders)
    stages = _pipeline_stages(max(seconds, 1.0) * 2.0)
    e2e = stages.get("commit.e2e", {})
    return {
        "metric": "span-recorder cost on the firehose + pipeline stage p99s",
        "platform": platform,
        "reps": reps,
        "seconds_per_rep": seconds,
        "num_metrics": NUM_METRICS,
        "batch": BATCH,
        "hbm_peak_bytes_per_s": cap,
        "firehose_samples_per_s_recorder_off": round(off_med, 1),
        "firehose_samples_per_s_recorder_on": round(on_med, 1),
        "obs_overhead_pct": round(overhead_pct, 2),
        "obs_overhead_budget_pct": 2.0,
        "spans_recorded": spans_recorded,
        "implied_ingest_bytes_per_s": round(implied_bw, 1),
        "suspect": suspect,
        "pipeline_stages": stages,
        "pipeline_stage_p99_us": e2e.get("p99_us"),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--reps", type=int, default=4)
    parser.add_argument("--seconds", type=float, default=1.5)
    parser.add_argument("--tpu", action="store_true",
                        help="keep the configured (TPU) platform instead "
                             "of forcing CPU")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    result = run(reps=args.reps, seconds=args.seconds)
    text = json.dumps(result, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
