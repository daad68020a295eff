"""Metric lifecycle under name churn (the lifecycle tentpole's
receipts): commit latency, eviction/compaction cost, and the bounded-
memory claim at 1k / 16k / 100k cumulative names on a fixed live-series
budget.

Every interval brings a fresh per-user name population
(``api.u<id>.lat``), the cardinality-explosion workload a dense device
accumulator cannot survive without retirement.  The lifecycle config
TTLs idle series, folds them (count-exact) into ``_overflow.api``, and
auto-compacts the freed rows, so the device row space must stay at its
configured budget while cumulative names grow unbounded — the run
ASSERTS sample conservation (nothing lost to eviction) and reports
whether the row space actually stayed bounded.

The HBM-roofline plausibility guard from bench.py marks any compaction
timing whose implied repack bandwidth (read + write of the accumulator
and every ring) exceeds the platform cap as suspect, rather than
reporting physically impossible latencies.

Usage: python benchmarks/cardinality_churn.py [--tpu]
       [--configs 1000,16000] [--out CARDINALITY_CHURN_r8.json]
Prints one JSON object (save as CARDINALITY_CHURN_r*.json); importable
as ``run(...)`` for tests/capture and for bench.py's headline extras.
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

# (label, cumulative_names, live_budget_rows, bucket_limit, tiers)
# The big points shrink buckets and tier depth so the rings fit
# everywhere; the contest is churn handling, not ring HBM.  The 100k
# point is the acceptance grid: 100k cumulative names on a 16k live
# budget.
CONFIGS = [
    ("1000", 1_000, 256, 1024, ((8, 1), (4, 8))),
    ("16000", 16_000, 2_048, 256, ((8, 1), (4, 8))),
    ("100000", 100_000, 16_384, 64, ((4, 1),)),
]

INTERVALS = 40


def _stats_us(lat_s):
    return {
        "median_us": round(float(np.median(lat_s)) * 1e6, 1),
        "p99_us": round(float(np.percentile(lat_s, 99)) * 1e6, 1),
    }


def run(configs=None) -> dict:
    import jax

    from loghisto_tpu.commit import IntervalCommitter
    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.lifecycle import LifecycleConfig, LifecycleManager
    from loghisto_tpu.metrics import RawMetricSet
    from loghisto_tpu.parallel.aggregator import TPUAggregator
    from loghisto_tpu.window import TimeWheel

    platform = jax.devices()[0].platform
    cap = peak_bytes_per_s(jax.devices()[0].device_kind)
    wanted = set(configs) if configs else None
    result = {
        "metric": "interval commit + lifecycle cost under name churn",
        "platform": platform,
        "intervals": INTERVALS,
        "hbm_peak_bytes_per_s": cap,
        "configs": {},
    }
    for label, cumulative, rows, bucket_limit, tiers in CONFIGS:
        if wanted is not None and label not in wanted:
            continue
        churn = cumulative // INTERVALS
        cfg = MetricConfig(bucket_limit=bucket_limit)
        agg = TPUAggregator(num_metrics=rows, config=cfg)
        wheel = TimeWheel(num_metrics=rows, config=cfg, interval=1.0,
                          tiers=tiers, registry=agg.registry)
        # auto-compaction off: the repack is driven explicitly every 4
        # intervals below so every grid point yields compaction timings
        # (the auto trigger calls the same compact() path)
        lc = LifecycleManager(agg, wheel, LifecycleConfig(
            ttl_intervals=2,
            check_every=1,
            auto_compact_fragmentation=0.0,
        ))
        committer = IntervalCommitter(agg, wheel, lifecycle=lc)
        committer.warmup()

        rng = np.random.default_rng(0)
        t0 = _dt.datetime(2026, 1, 1, tzinfo=_dt.timezone.utc)
        total = 0
        peak_rows = agg.num_metrics
        commit_lat = []
        uid = 0
        for i in range(INTERVALS):
            hists = {}
            buckets = rng.integers(-bucket_limit, bucket_limit, churn)
            counts = rng.integers(1, 8, churn)
            for b, c in zip(buckets, counts):
                hists[f"api.u{uid}.lat"] = {int(b): int(c)}
                total += int(c)
                uid += 1
            hists["api.steady"] = {0: 10}
            total += 10
            raw = RawMetricSet(
                time=t0 + _dt.timedelta(seconds=i), counters={},
                rates={}, histograms=hists, gauges={}, duration=1.0,
            )
            t1 = time.perf_counter()
            committer.commit(raw)
            jax.block_until_ready(agg._acc)
            commit_lat.append(time.perf_counter() - t1)
            peak_rows = max(peak_rows, agg.num_metrics)
            if (i + 1) % 4 == 0:
                lc.compact()  # records its latency in lc._compaction_us

        # lossless retirement: every committed sample is still on device,
        # either in a live row or folded into the overflow row
        acc = np.asarray(
            agg._acc, dtype=np.int64
        )
        if agg._spill is not None:
            acc = acc + agg._spill
        assert int(acc.sum()) == total, (
            f"conservation broken: committed {total}, device holds "
            f"{int(acc.sum())}"
        )
        ovid = agg.registry.lookup("_overflow.api")
        overflow_count = int(acc[ovid].sum()) if ovid is not None else 0
        assert overflow_count == lc.overflowed_samples

        # bounded memory: the row space must never have grown past the
        # configured live budget — that IS the tentpole's claim
        bounded = peak_rows == rows
        hbm_bytes = (
            peak_rows * cfg.num_buckets * 4          # accumulator
            + wheel.hbm_bytes()                      # tier rings
            + peak_rows * 4                          # activity vector
        )

        comp_us = np.asarray(lc._compaction_us, dtype=np.float64)
        # plausibility: a repack reads + writes the accumulator and every
        # ring once; faster than the roofline means broken timing
        repack_bytes = 2 * (
            peak_rows * cfg.num_buckets * 4 + wheel.hbm_bytes()
        )
        suspect = False
        if len(comp_us):
            implied_bw = repack_bytes / max(
                float(np.median(comp_us)) / 1e6, 1e-9
            )
            suspect = implied_bw > cap
            if suspect:
                print(
                    f"cardinality_churn: implied compaction bandwidth "
                    f"{implied_bw:.3e} B/s exceeds the {platform} roofline"
                    f" cap {cap:.3e}; marking config {label} suspect",
                    file=sys.stderr,
                )
        result["configs"][label] = {
            "cumulative_names": cumulative,
            "live_budget_rows": rows,
            "churn_names_per_interval": churn,
            "num_buckets": cfg.num_buckets,
            "tiers": [list(t_) for t_ in tiers],
            "peak_device_rows": peak_rows,
            "bounded_by_live_budget": bounded,
            "peak_hbm_bytes": hbm_bytes,
            "live_series_final": agg.registry.live_count(),
            "evicted_series": lc.evicted_series,
            "eviction_batches": lc.evictions,
            "overflowed_samples": lc.overflowed_samples,
            "samples_committed": total,
            "compactions": lc.compactions,
            "commit_latency": _stats_us(commit_lat),
            "compaction_latency": (
                _stats_us(comp_us / 1e6) if len(comp_us) else None
            ),
            "repack_bytes_per_compaction": repack_bytes,
            "suspect": suspect,
        }
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--tpu", action="store_true",
                        help="keep the configured (TPU) platform instead "
                             "of forcing CPU")
    parser.add_argument("--configs", default=None,
                        help="comma-separated config labels (default all)")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    configs = args.configs.split(",") if args.configs else None
    result = run(configs=configs)
    text = json.dumps(result, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
