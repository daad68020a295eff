"""Drift-engine receipts (the ISSUE 7 tentpole): what the EWMA baseline
bank and the fused divergence pass actually cost, at 1 / 16 / 10k metric
rows.

Three contenders over identical interval streams:

  * baseline — the fused IntervalCommitter as shipped by the commit
    tentpole (no drift engine);
  * ewma     — AnomalyManager attached with scoring disabled
    (``check_every`` huge): the EWMA bank update rides the final-chunk
    donated program (``track_baseline``) at ZERO extra dispatches —
    this delta is the pure ride-along cost;
  * drift    — the full engine: EWMA ride-along plus ONE divergence
    dispatch per interval (KS + JSD + bucket EMD against the baseline
    bank).

Reported per config: commit latency for all three contenders (the EWMA
rides existing dispatches, so its delta is the fused program doing more
work, not more launches — the dispatch counters are asserted, not
trusted), the divergence-pass latency, and the scoring cost per row.

The HBM-roofline plausibility guard from bench.py marks any divergence
timing whose implied operand bandwidth (live CDFs + baseline bank in)
exceeds the platform cap as suspect rather than reporting a
faster-than-physics number.

Usage: python benchmarks/anomaly_bench.py [--reps 20] [--tpu]
       [--out ANOMALY_r9.json]
Prints one JSON object (save as ANOMALY_r*.json); importable as
``run(...)`` for tests/capture and for bench.py's ``drift_*`` headline
fields.
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

# (label, num_metrics, bucket_limit, tiers) — the query-engine grid: the
# 10k point shrinks buckets/tier depth so the rings fit everywhere; the
# contest here is the EWMA ride-along and the divergence dispatch.
CONFIGS = [
    ("1", 1, 4096, ((60, 1), (60, 60), (24, 3600))),
    ("16", 16, 4096, ((60, 1), (60, 60), (24, 3600))),
    ("10000", 10_000, 256, ((8, 1), (4, 8))),
]

WARM_INTERVALS = 4  # committed before any timing starts
BANKS = 2           # exercise the bank gather, not just bank 0


def _intervals(rng, n, num_metrics, bucket_limit, cells_per_metric=8):
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


def _stats_us(lat):
    return {
        "median_us": round(float(np.median(lat)) * 1e6, 1),
        "p99_us": round(float(np.percentile(lat, 99)) * 1e6, 1),
    }


def run(reps: int = 20, configs=None) -> dict:
    import jax

    from loghisto_tpu.anomaly import AnomalyConfig, AnomalyManager
    from loghisto_tpu.commit import IntervalCommitter
    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.metrics import RawMetricSet
    from loghisto_tpu.parallel.aggregator import TPUAggregator
    from loghisto_tpu.window import TimeWheel

    platform = jax.devices()[0].platform
    cap = peak_bytes_per_s(jax.devices()[0].device_kind)
    result = {
        "metric": "drift-engine cost: EWMA ride-along + divergence dispatch",
        "platform": platform,
        "reps": reps,
        "banks": BANKS,
        "hbm_peak_bytes_per_s": cap,
        "configs": {},
    }
    for label, num_metrics, bucket_limit, tiers in CONFIGS:
        if configs is not None and label not in configs:
            continue
        cfg = MetricConfig(bucket_limit=bucket_limit)
        rng = np.random.default_rng(0)
        stream = _intervals(rng, WARM_INTERVALS + reps, num_metrics,
                            bucket_limit)

        def raw_of(entry):
            t, hists = entry
            return RawMetricSet(time=t, counters={}, rates={},
                                histograms=hists, gauges={}, duration=1.0)

        def build(with_drift, check_every=1):
            agg = TPUAggregator(num_metrics=num_metrics, config=cfg)
            wheel = TimeWheel(num_metrics=num_metrics, config=cfg,
                              interval=1.0, tiers=tiers,
                              registry=agg.registry)
            am = None
            if with_drift:
                am = AnomalyManager(agg, wheel, AnomalyConfig(
                    banks=BANKS, bank_of=lambda t: t.second,
                    decay=0.95, min_samples=8,
                    check_every=check_every,
                ))
            com = IntervalCommitter(agg, wheel, anomaly=am)
            com.warmup()
            return com, agg, am

        def commit_lat(com, am):
            lat = []
            for k, entry in enumerate(stream):
                raw = raw_of(entry)
                if k < WARM_INTERVALS:
                    com.commit(raw)
                    continue
                t1 = time.perf_counter()
                com.commit(raw)
                lat.append(time.perf_counter() - t1)
                # the guarantee is structural, assert it every interval:
                # EWMA rides the commit (<= 2 launches), scoring adds 1
                assert com.last_dispatches <= 2
            return lat

        base_com, base_agg, _ = build(with_drift=False)
        base_lat = commit_lat(base_com, None)
        base_agg._acc.block_until_ready()

        # scoring disabled: the commit delta is the EWMA ride-along alone
        ewma_com, ewma_agg, ewma_am = build(with_drift=True,
                                            check_every=1 << 30)
        ewma_lat = commit_lat(ewma_com, ewma_am)
        ewma_agg._acc.block_until_ready()
        assert ewma_am.scored_intervals == 0

        com, agg, am = build(with_drift=True)
        drift_lat = commit_lat(com, am)
        agg._acc.block_until_ready()
        assert am.scored_intervals == WARM_INTERVALS + reps
        assert am.skipped_intervals == 0

        # the divergence pass in isolation (score_now = ONE dispatch +
        # host readback of 3*M floats; this is the engine's entire
        # per-interval device cost beyond the commit)
        now = stream[-1][0]
        score_lat = []
        for _ in range(reps):
            t1 = time.perf_counter()
            am.score_now(now)
            score_lat.append(time.perf_counter() - t1)

        score_med = float(np.median(score_lat))
        # plausibility: operands in (live view CDF + counts + the FULL
        # bank carries the gather reads) bound the pass from below
        b = cfg.num_buckets
        op_bytes = (
            num_metrics * b * 4        # view cdf  int32 [M, B]
            + num_metrics * 4          # counts    int32 [M]
            + BANKS * num_metrics * b * 4  # prof  f32 [K, M, B]
            + BANKS * num_metrics * 4      # wsum  f32 [K, M]
        )
        implied_bw = op_bytes / max(score_med, 1e-9)
        suspect = implied_bw > cap
        if suspect:
            print(
                f"anomaly_bench: implied divergence bandwidth "
                f"{implied_bw:.3e} B/s exceeds the {platform} roofline "
                f"cap {cap:.3e}; marking config {label} suspect",
                file=sys.stderr,
            )

        base_med = float(np.median(base_lat))
        ewma_med = float(np.median(ewma_lat))
        drift_med = float(np.median(drift_lat))
        result["configs"][label] = {
            "num_metrics": num_metrics,
            "num_buckets": b,
            "tiers": [list(t_) for t_ in tiers],
            "divergence_path": am.divergence_path,
            "commit_baseline": _stats_us(base_lat),
            "commit_ewma_only": _stats_us(ewma_lat),
            "commit_with_drift": _stats_us(drift_lat),
            "ewma_overhead_pct": round(
                (ewma_med / max(base_med, 1e-9) - 1.0) * 100.0, 1
            ),
            "commit_overhead_pct": round(
                (drift_med / max(base_med, 1e-9) - 1.0) * 100.0, 1
            ),
            "ewma_extra_dispatches": 0,  # asserted via last_dispatches
            "divergence_dispatches_per_interval": 1,
            "divergence_score": _stats_us(score_lat),
            "divergence_ns_per_row": round(
                score_med * 1e9 / num_metrics, 1
            ),
            "divergence_operand_bytes": op_bytes,
            "implied_divergence_bytes_per_s": round(implied_bw, 1),
            "suspect": suspect,
        }
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--reps", type=int, default=20)
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
