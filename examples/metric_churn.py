"""Metric lifecycle under name churn: per-user label cardinality on a
fixed HBM budget, end to end.

A synthetic API emits `api.<user>.latency` — a fresh user population
every interval, the classic cardinality explosion that would grow a
dense device accumulator without bound.  The lifecycle subsystem keeps
the device row space FIXED: idle per-user series TTL out, their counts
fold (exactly) into a per-prefix `_overflow.api` catch-all, freed rows
are reused and periodically compacted back to a dense prefix.

The intervals are synthetic and driven through the fused committer
directly, so the demo is deterministic and runs anywhere (CPU
backend)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import datetime as dt

import numpy as np

from loghisto_tpu import TPUMetricSystem
from loghisto_tpu.config import MetricConfig
from loghisto_tpu.lifecycle import LifecycleConfig
from loghisto_tpu.ops.codec import compress_np

cfg = MetricConfig(bucket_limit=1024)
NUM_ROWS = 256          # the whole device budget: rows never exceed this
USERS_PER_INTERVAL = 40  # fresh names per second — unbounded cumulative

ms = TPUMetricSystem(
    interval=1.0, sys_stats=False, config=cfg, num_metrics=NUM_ROWS,
    retention=[(30, 1), (10, 6)], commit="fused",
    lifecycle=LifecycleConfig(
        ttl_intervals=3,          # a user idle for 3s is retired
        max_live=200,             # hard cardinality ceiling under the rows
        prefix_budgets={"api.*": 180},
        check_every=2,
        auto_compact_fragmentation=0.25,
        min_compact_rows=16,
    ),
)


def synthetic_intervals(n=60, t0=dt.datetime(2026, 8, 5,
                                             tzinfo=dt.timezone.utc)):
    """One RawMetricSet per second; every interval brings a mostly-new
    user population plus one steady service-level series."""
    rng = np.random.default_rng(11)
    for i in range(n):
        hists = {}
        for u in range(USERS_PER_INTERVAL):
            uid = i * USERS_PER_INTERVAL + u  # fresh names forever
            lat_ms = rng.lognormal(np.log(50.0), 0.4, 25)
            buckets = compress_np(lat_ms, cfg.precision)
            ub, cnt = np.unique(buckets, return_counts=True)
            hists[f"api.u{uid}.latency"] = {
                int(b): int(c) for b, c in zip(ub, cnt)
            }
        hists["api.latency"] = {0: 100}  # steady, never evicted
        yield RawMetricSet(
            time=t0 + dt.timedelta(seconds=i), counters={}, gauges={},
            rates={}, histograms=hists, duration=1.0,
        )


from loghisto_tpu.metrics import RawMetricSet  # noqa: E402

total_samples = 0
cumulative_names = 1
for raw in synthetic_intervals():
    total_samples += sum(
        sum(h.values()) for h in raw.histograms.values()
    )
    cumulative_names += USERS_PER_INTERVAL
    ms.committer.commit(raw)

lc = ms.lifecycle
reg = ms.aggregator.registry
print("== churn summary ==")
print(f"  cumulative names ingested : {cumulative_names}")
print(f"  device rows (fixed budget): {ms.aggregator.num_metrics}")
print(f"  live series now           : {reg.live_count()}")
print(f"  evicted series            : {lc.evicted_series}")
print(f"  eviction batches          : {lc.evictions}")
print(f"  compactions               : {lc.compactions}")
print(f"  registry generation       : {reg.generation}")

# count-exact overflow: every evicted sample is still counted, in the
# per-prefix catch-all — nothing was lost to the churn
acc = np.asarray(ms.aggregator._acc)
ovid = reg.lookup("_overflow.api")
print("== lossless retirement ==")
print(f"  samples ingested          : {total_samples}")
print(f"  samples on device (total) : {int(acc.sum())}")
print(f"  held by _overflow.api     : {int(acc[ovid].sum())}"
      f" (== folded evicted counts {lc.overflowed_samples})")

# live + overflow series keep serving windowed percentiles as usual
res = ms.query_window("api.latency", window=10.0, percentiles=(0.99,))
entry = res.metrics["api.latency"]
print("== steady series still live ==")
print(f"  api.latency p99 over 10s  : {entry['p99']:.1f} "
      f"(count {entry['count']:.0f})")

print("== lifecycle gauges ==")
raw = ms.collect_raw_metrics()
for name in sorted(raw.gauges):
    if name.startswith("lifecycle."):
        print(f"  {name:32s} {raw.gauges[name]:.0f}")

ms.stop()
