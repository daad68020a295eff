"""Host-fed ingest benchmark (VERDICT r1 item 4): sustained samples/s
through the FULL host->device path — record_batch staging, the depth-K
ingest staging ring's async device_puts, device-side chunk slicing,
fused compress+scatter-add — unlike the firehose bench, whose samples
are generated on device and never cross PCIe/host memory.

r6 adds the transport dimension: --transport sparse ships flush-time
host-folded packed triples, --sweep measures raw/preagg/sparse in one
process and emits a comparison table (--out H2D_r6.json).  Every line
carries bytes/sample and effective wire MB/s from the aggregator's
transfer counters, and the samples/s figure is withheld (suspect=true)
when it exceeds the same HBM-roofline cap bench.py's headline uses.

Usage: python benchmarks/h2d_bench.py [--metrics 10000] [--seconds 5]
       [--batch 1048576] [--transport raw|preagg|sparse|auto]
       [--sweep] [--out H2D_r6.json] [--cpu]
Prints one JSON line (or one per transport plus a summary with --sweep).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))


def run(num_metrics: int, seconds: float, batch: int,
        transport: str = "auto") -> dict:
    import jax

    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    cfg = MetricConfig(bucket_limit=4096)
    agg = TPUAggregator(
        num_metrics=num_metrics,
        config=cfg,
        batch_size=batch,
        max_metrics=num_metrics,
        transport=transport,
    )
    rng = np.random.default_rng(0)
    # pre-generate a pool of host batches (shuffled reuse; generation must
    # not gate the measured path)
    pool = []
    for _ in range(8):
        raw = rng.zipf(1.3, size=batch)
        ids = ((raw - 1) % num_metrics).astype(np.int32)
        values = rng.lognormal(10.0, 2.0, batch).astype(np.float32)
        pool.append((ids, values))

    import jax.numpy as jnp

    def force_value():
        # a host VALUE fetch: it cannot complete before the work that
        # produced it, whatever the backend acks early.
        # Per-row device reduce (int32-safe: one interval's whole acc
        # holds < 2^31 samples by the spill guarantee), then an exact
        # int64 total on host; the wire carries one [M] vector.
        row_sums = np.asarray(
            jnp.sum(agg._finalize_acc(agg._acc), axis=1)
        )
        return int(row_sums.astype(np.int64).sum())

    # warmup: one full flush compiles the ingest executable
    agg.record_batch(*pool[0])
    agg.flush(force=True)
    warm_count = force_value()
    warm_stats = agg.transport_stats()

    sent = 0
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        # backpressure pacing: a producer that overruns the bounded
        # buffer measures the shed machinery (and, on small hosts,
        # starves the transfer worker of the very cores it needs) —
        # sustained throughput is the worker's drain rate with the
        # queue kept full, so yield while it's saturated
        if agg._xfer_queued_samples >= agg.max_pending_samples:
            time.sleep(0.0005)
            continue
        ids, values = pool[i % len(pool)]
        agg.record_batch(ids, values)  # auto-flushes at batch_size
        sent += len(ids)
        i += 1
    agg.flush(force=True)
    delivered_device = int(force_value())
    elapsed = time.perf_counter() - t0
    # sustained = samples that actually REACHED the accumulator; counting
    # shed samples would overstate throughput whenever the bounded host
    # buffer dropped under device cooldown
    delivered = sent - agg._shed_samples
    spilled = int(agg._spill.sum()) if agg._spill is not None else 0
    stats = agg.transport_stats()
    # warmup-batch traffic subtracted: the wire economics of the measured
    # window only
    wire_bytes = stats["bytes_uploaded"] - warm_stats["bytes_uploaded"]
    shipped = stats["samples_shipped"] - warm_stats["samples_shipped"]
    rate = delivered / elapsed

    from bench import plausibility_cap_samples_per_s

    cfg_bytes = num_metrics * cfg.num_buckets * 4
    platform = jax.devices()[0].platform
    cap = plausibility_cap_samples_per_s(jax.devices()[0].device_kind, cfg_bytes)
    suspect = rate > cap
    out = {
        "metric": "host-fed samples/sec/chip",
        # same contract as bench.py's headline: a physically impossible
        # rate is withheld, never laundered into a result line
        "value": None if suspect else round(rate, 1),
        "suspect": suspect,
        "measured_samples_per_s": round(rate, 1),
        "plausibility_cap_samples_per_s": round(cap, 1),
        "unit": "samples/s",
        "platform": platform,
        "transport": agg.transport,
        "probe_density": stats["probe_density"],
        # wire economics: what one delivered sample cost on the H2D link
        "bytes_per_sample": (
            round(wire_bytes / shipped, 3) if shipped else None
        ),
        "wire_mb_per_s": round(wire_bytes / elapsed / 1e6, 1),
        "num_metrics": num_metrics,
        "batch": batch,
        "seconds": round(elapsed, 2),
        "shed": agg._shed_samples,
        # device-side count: cross-checks that `delivered` samples truly
        # landed in the accumulator (+ any exact host spill; warmup
        # batch subtracted)
        "device_count": delivered_device + spilled - warm_count,
    }
    agg.close()
    return out


def sweep(num_metrics: int, seconds: float, batch: int) -> dict:
    """Measure every concrete transport on the identical load and report
    the comparison the auto-dispatch crossover is tuned from.  Each
    transport gets its own aggregator (fresh accumulator, fresh compile
    cache entry); the winner is picked on delivered samples/s among
    non-suspect lines."""
    table = {}
    for transport in ("raw", "preagg", "sparse"):
        table[transport] = run(
            num_metrics, seconds, batch, transport=transport
        )
    best = max(
        (t for t in table if not table[t]["suspect"]),
        key=lambda t: table[t]["measured_samples_per_s"],
        default=None,
    )
    return {
        "metric": "h2d transport sweep",
        "best_transport": best,
        "best_samples_per_s": (
            table[best]["measured_samples_per_s"] if best else None
        ),
        "num_metrics": num_metrics,
        "batch": batch,
        "seconds_per_transport": seconds,
        "transports": table,
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--metrics", type=int, default=10_000)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--batch", type=int, default=1 << 20)
    parser.add_argument("--transport", default="auto",
                        choices=("auto", "raw", "preagg", "sparse"))
    parser.add_argument("--sweep", action="store_true",
                        help="measure raw, preagg AND sparse; print the "
                             "comparison table")
    parser.add_argument("--out", default=None,
                        help="also write the result JSON to this path "
                             "(e.g. benchmarks/H2D_r6.json)")
    parser.add_argument("--cpu", action="store_true")
    args = parser.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if args.sweep:
        result = sweep(args.metrics, args.seconds, args.batch)
    else:
        result = run(args.metrics, args.seconds, args.batch,
                     transport=args.transport)
    print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
