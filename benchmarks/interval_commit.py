"""Fused vs fan-out interval-commit latency (the tentpole's receipts):
dispatches/interval, H2D bytes/interval, and per-interval commit
latency for both pipelines at 1 / 16 / 10k metric cardinalities.

The fan-out contender is the pre-existing pair of consumers fed the
same interval — TPUAggregator.merge_raw (bridge-merge scatter) plus
TimeWheel.push (one scatter per tier, plus slot clears) — each
re-resolving names and re-uploading cells.  The fused contender is
loghisto_tpu.commit.IntervalCommitter: one staged upload, one
donated-carry program for every consumer.

Commit latency is a host-blocking measure (block_until_ready on the
carries after each interval) so async dispatch cannot flatter either
side; the HBM-roofline plausibility guard from bench.py additionally
marks any implied cell bandwidth above the platform cap as suspect
rather than reporting it.

Usage: python benchmarks/interval_commit.py [--reps 30] [--tpu]
       [--out INTERVAL_COMMIT_r1.json]
Prints one JSON object (save as INTERVAL_COMMIT_r*.json); importable as
``run(...)`` for tests/capture.
"""

from __future__ import annotations

import argparse
import datetime as _dt
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import numpy as np

from bench import peak_bytes_per_s

# (label, num_metrics, bucket_limit, tiers): the 10k point shrinks the
# bucket space and tier depth so the rings fit comfortably everywhere —
# the contest is dispatch count and upload traffic, not ring HBM.
CONFIGS = [
    ("1", 1, 4096, ((60, 1), (60, 60), (24, 3600))),
    ("16", 16, 4096, ((60, 1), (60, 60), (24, 3600))),
    ("10000", 10_000, 256, ((8, 1), (4, 8))),
]


def _intervals(rng, n, num_metrics, bucket_limit, cells_per_metric=24):
    """Pre-built sparse interval payloads ({name: {bucket: count}}) —
    identical streams for both contenders."""
    t0 = _dt.datetime(2026, 1, 1, tzinfo=_dt.timezone.utc)
    names = [f"m{i}" for i in range(num_metrics)]
    out = []
    for i in range(n):
        hists = {}
        for name in names:
            b = rng.integers(-bucket_limit, bucket_limit, cells_per_metric)
            # weights sized so a full run stays inside the spill
            # threshold without a mid-run collect() reset (live traffic
            # gets that reset every collection interval)
            c = rng.integers(1, 100, cells_per_metric)
            h = {}
            for bb, cc in zip(b, c):
                h[int(bb)] = h.get(int(bb), 0) + int(cc)
            hists[name] = h
        out.append((t0 + _dt.timedelta(seconds=i), hists))
    return out


def _block(agg, wheel):
    agg._acc.block_until_ready()
    for t in wheel._tiers:
        t.ring.block_until_ready()


def run(reps: int = 30) -> dict:
    import jax

    from loghisto_tpu.commit import IntervalCommitter
    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.metrics import RawMetricSet
    from loghisto_tpu.parallel.aggregator import TPUAggregator
    from loghisto_tpu.window import TimeWheel
    from loghisto_tpu.window import store as store_mod

    platform = jax.devices()[0].platform
    result = {
        "metric": "interval commit latency, fused vs fan-out",
        "platform": platform,
        "reps": reps,
        "hbm_peak_bytes_per_s": peak_bytes_per_s(jax.devices()[0].device_kind),
        "configs": {},
    }
    for label, num_metrics, bucket_limit, tiers in CONFIGS:
        cfg = MetricConfig(bucket_limit=bucket_limit)
        rng = np.random.default_rng(0)
        stream = _intervals(rng, reps + 2, num_metrics, bucket_limit)

        def raw_of(entry):
            t, hists = entry
            return RawMetricSet(time=t, counters={}, rates={},
                                histograms=hists, gauges={}, duration=1.0)

        # -- fused ------------------------------------------------------ #
        agg = TPUAggregator(num_metrics=num_metrics, config=cfg)
        wheel = TimeWheel(num_metrics=num_metrics, config=cfg, interval=1.0,
                          tiers=tiers, registry=agg.registry)
        committer = IntervalCommitter(agg, wheel)
        committer.warmup()
        committer.commit(raw_of(stream[0]))  # warm name resolution
        _block(agg, wheel)
        fused_times, fused_dispatches, fused_bytes = [], [], []
        for entry in stream[2:]:
            raw = raw_of(entry)
            t1 = time.perf_counter()
            committer.commit(raw)
            _block(agg, wheel)
            fused_times.append(time.perf_counter() - t1)
            fused_dispatches.append(committer.last_dispatches)
            fused_bytes.append(committer.last_h2d_bytes)
        assert committer.fanout_intervals == 0

        # -- fan-out (the pre-existing per-consumer pipelines) ---------- #
        agg2 = TPUAggregator(num_metrics=num_metrics, config=cfg)
        wheel2 = TimeWheel(num_metrics=num_metrics, config=cfg, interval=1.0,
                           tiers=tiers, registry=agg2.registry)
        agg2._bridge_warmup()
        agg2.merge_raw(raw_of(stream[0]))
        wheel2.push(raw_of(stream[0]))
        _block(agg2, wheel2)

        # count the fan-out's device launches the same way the guard test
        # counts the fused path's: wrap the jitted entry points
        counts = {"n": 0}
        real_scatter = store_mod._scatter_cells_jit
        real_open = store_mod._open_slot_jit
        real_weighted = agg2._weighted_ingest

        def counting(fn):
            def wrapped(*a, **kw):
                counts["n"] += 1
                return fn(*a, **kw)
            return wrapped

        store_mod._scatter_cells_jit = counting(real_scatter)
        store_mod._open_slot_jit = counting(real_open)
        agg2._weighted_ingest = counting(real_weighted)
        fan_times, fan_dispatches = [], []
        try:
            for entry in stream[2:]:
                raw = raw_of(entry)
                counts["n"] = 0
                t1 = time.perf_counter()
                agg2.merge_raw(raw)
                wheel2.push(raw)
                _block(agg2, wheel2)
                fan_times.append(time.perf_counter() - t1)
                fan_dispatches.append(counts["n"])
        finally:
            store_mod._scatter_cells_jit = real_scatter
            store_mod._open_slot_jit = real_open
            agg2._weighted_ingest = real_weighted

        fused_med = float(np.median(fused_times))
        fan_med = float(np.median(fan_times))
        h2d_per_interval = int(np.median(fused_bytes))
        # plausibility: implied H2D bandwidth for the fused upload must
        # stay under the platform roofline, else the timing is broken
        implied_bw = h2d_per_interval / max(fused_med, 1e-9)
        cap = peak_bytes_per_s(jax.devices()[0].device_kind)
        suspect = implied_bw > cap
        if suspect:
            print(
                f"interval_commit: implied H2D {implied_bw:.3e} B/s exceeds "
                f"the {platform} roofline cap {cap:.3e}; withholding the "
                "speedup headline for this config", file=sys.stderr,
            )
        result["configs"][label] = {
            "num_metrics": num_metrics,
            "num_buckets": cfg.num_buckets,
            "tiers": [list(t) for t in tiers],
            "fused_commit_median_us": round(fused_med * 1e6, 1),
            "fanout_commit_median_us": round(fan_med * 1e6, 1),
            "fused_dispatches_per_interval": int(np.median(fused_dispatches)),
            "fanout_dispatches_per_interval": int(np.median(fan_dispatches)),
            "fused_h2d_bytes_per_interval": h2d_per_interval,
            "implied_h2d_bytes_per_s": round(implied_bw, 1),
            "suspect": suspect,
            "fanout_over_fused": (
                None if suspect else round(fan_med / max(fused_med, 1e-9), 2)
            ),
        }
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--reps", type=int, default=30)
    parser.add_argument("--tpu", action="store_true",
                        help="keep the configured (TPU) platform instead "
                             "of forcing CPU")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    result = run(reps=args.reps)
    text = json.dumps(result, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
