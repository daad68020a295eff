"""Snapshot query engine vs locked recompute (the query tentpole's
receipts): percentile-query latency at 1 / 16 / 10k metric
cardinalities, full-glob vs single-metric, warm-cached vs
fresh-dispatch, against the pre-change recompute baseline.

The baseline contender is a ``snapshots=False`` TimeWheel — queries
take the store lock and run the full masked merge + dense_stats over
every ring row (the pre-snapshot path, kept in-tree as
``_query_recompute``).  The snapshot contender is the same stream
committed through the fused IntervalCommitter, which publishes a
per-tier CDF snapshot at commit time; queries then cost one sparse
gather+searchsorted dispatch over only the matched rows
(fresh-dispatch), or zero dispatch when the epoch hasn't advanced
(warm-cached).

Latency is host-blocking end-to-end (WindowStats is host-side numpy,
so readback is inside the clock).  The HBM-roofline plausibility guard
from bench.py marks any recompute timing whose implied ring bandwidth
exceeds the platform cap as suspect rather than reporting a speedup
derived from broken timing.

The single-metric leg additionally asserts the sparse-readback
contract: one query fetches O(P) floats (1 padded row), not O(M*P).

Usage: python benchmarks/query_engine.py [--reps 30] [--tpu]
       [--out QUERY_ENGINE_r7.json]
Prints one JSON object (save as QUERY_ENGINE_r*.json); importable as
``run(...)`` for tests/capture and for bench.py's headline extras.
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

# (label, num_metrics, bucket_limit, tiers) — same grid as
# interval_commit.py: the 10k point shrinks buckets and tier depth so
# the rings fit everywhere; the contest is query dispatch and readback
# volume, not ring HBM.
CONFIGS = [
    ("1", 1, 4096, ((60, 1), (60, 60), (24, 3600))),
    ("16", 16, 4096, ((60, 1), (60, 60), (24, 3600))),
    ("10000", 10_000, 256, ((8, 1), (4, 8))),
]

WARM_INTERVALS = 6  # committed before any timing starts


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
            c = rng.integers(1, 100, cells_per_metric)
            h = {}
            for bb, cc in zip(b, c):
                h[int(bb)] = h.get(int(bb), 0) + int(cc)
            hists[name] = h
        out.append((t0 + _dt.timedelta(seconds=i), hists))
    return out


def _timed(fn, reps):
    lat = []
    for _ in range(reps):
        t1 = time.perf_counter()
        fn()
        lat.append(time.perf_counter() - t1)
    return lat


def _stats_us(lat):
    return {
        "median_us": round(float(np.median(lat)) * 1e6, 1),
        "p99_us": round(float(np.percentile(lat, 99)) * 1e6, 1),
    }


def run(reps: int = 30) -> dict:
    import jax

    from loghisto_tpu.commit import IntervalCommitter
    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.metrics import RawMetricSet
    from loghisto_tpu.parallel.aggregator import TPUAggregator
    from loghisto_tpu.window import TimeWheel

    platform = jax.devices()[0].platform
    cap = peak_bytes_per_s(jax.devices()[0].device_kind)
    result = {
        "metric": "windowed percentile-query latency, snapshot vs recompute",
        "platform": platform,
        "reps": reps,
        "hbm_peak_bytes_per_s": cap,
        "configs": {},
    }
    for label, num_metrics, bucket_limit, tiers in CONFIGS:
        cfg = MetricConfig(bucket_limit=bucket_limit)
        rng = np.random.default_rng(0)
        stream = _intervals(rng, WARM_INTERVALS, num_metrics, bucket_limit)

        def raw_of(entry):
            t, hists = entry
            return RawMetricSet(time=t, counters={}, rates={},
                                histograms=hists, gauges={}, duration=1.0)

        # -- snapshot contender: fused commits publish CDF snapshots --- #
        agg = TPUAggregator(num_metrics=num_metrics, config=cfg)
        wheel = TimeWheel(num_metrics=num_metrics, config=cfg, interval=1.0,
                          tiers=tiers, registry=agg.registry)
        committer = IntervalCommitter(agg, wheel)
        committer.warmup()
        for entry in stream:
            committer.commit(raw_of(entry))
        agg._acc.block_until_ready()
        assert committer.fanout_intervals == 0
        assert wheel.snapshot is not None
        epoch0 = wheel.snapshot.epoch

        # -- recompute baseline: the pre-snapshot locked path ----------- #
        agg2 = TPUAggregator(num_metrics=num_metrics, config=cfg)
        wheel2 = TimeWheel(num_metrics=num_metrics, config=cfg, interval=1.0,
                           tiers=tiers, registry=agg2.registry,
                           snapshots=False)
        for entry in stream:
            wheel2.push(raw_of(entry))

        # warm both query paths (glob cache, jit compiles) off the clock
        base_ws = wheel2.query("*")
        snap_ws = wheel.query("*")
        assert base_ws.metrics.keys() == snap_ws.metrics.keys()
        wheel.query("m0")
        wheel2.query("m0")

        recompute = _timed(lambda: wheel2.query("*"), reps)
        assert wheel2.query_snapshot_hits == 0

        # warm-cached: epoch unchanged -> host result-cache hit, zero
        # dispatch (this is what repeat scrapes within an interval pay)
        hits0 = wheel.query_result_cache_hits
        warm = _timed(lambda: wheel.query("*"), reps)
        assert wheel.query_result_cache_hits - hits0 == reps

        # fresh-dispatch: clearing the host result cache forces the one
        # sparse gather dispatch (what the first query after a commit
        # pays); the plan/glob caches stay warm, as they would live
        def fresh():
            wheel._result_cache.clear()
            wheel.query("*")
        dispatch = _timed(fresh, reps)

        # sparse single-metric leg + the O(P)-readback contract
        rows0 = wheel.query_rows_fetched

        def sparse():
            wheel._result_cache.clear()
            wheel.query("m0")
        sparse_lat = _timed(sparse, reps)
        rows_per_query = (wheel.query_rows_fetched - rows0) / reps
        assert rows_per_query < num_metrics or num_metrics == 1, (
            f"sparse query fetched {rows_per_query} rows/query at "
            f"{num_metrics} metrics — readback is O(M*P), not O(P)"
        )
        assert wheel.snapshot.epoch == epoch0  # nothing committed mid-run
        assert wheel.query_fallbacks == 0

        rec_med = float(np.median(recompute))
        rec_p99 = float(np.percentile(recompute, 99))
        warm_p99 = float(np.percentile(warm, 99))
        disp_p99 = float(np.percentile(dispatch, 99))

        # plausibility: the recompute merges every written ring slot, so
        # its implied ring bandwidth must stay under the platform
        # roofline — a faster-than-physics baseline means broken timing,
        # and a speedup against it would be garbage
        ti = base_ws.tier
        t = wheel2._tiers[ti]
        ring_bytes = (
            int(t.written.sum()) * num_metrics * cfg.num_buckets * 4
        )
        implied_bw = ring_bytes / max(rec_med, 1e-9)
        suspect = implied_bw > cap
        if suspect:
            print(
                f"query_engine: implied recompute bandwidth "
                f"{implied_bw:.3e} B/s exceeds the {platform} roofline cap "
                f"{cap:.3e}; withholding the speedup headline for config "
                f"{label}", file=sys.stderr,
            )
        result["configs"][label] = {
            "num_metrics": num_metrics,
            "num_buckets": cfg.num_buckets,
            "tiers": [list(t_) for t_ in tiers],
            "tier_queried": ti,
            "recompute_full_glob": _stats_us(recompute),
            "snapshot_warm_cached_full_glob": _stats_us(warm),
            "snapshot_dispatch_full_glob": _stats_us(dispatch),
            "snapshot_dispatch_one_metric": _stats_us(sparse_lat),
            "sparse_rows_per_one_metric_query": rows_per_query,
            "ring_bytes_merged_per_recompute": ring_bytes,
            "implied_recompute_bytes_per_s": round(implied_bw, 1),
            "suspect": suspect,
            "speedup_warm_cached": (
                None if suspect else round(rec_p99 / max(warm_p99, 1e-9), 1)
            ),
            "speedup_dispatch": (
                None if suspect else round(rec_p99 / max(disp_p99, 1e-9), 1)
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
