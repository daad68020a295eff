"""r13 fused sample->scatter ingest characterization at the headline
shape (10k metrics x 8193 buckets): the single-dispatch Pallas kernel vs
the retired two-dispatch compress-then-scatter path, the batch-size
crossover that calibrates ``FUSED_MIN_BATCH``, and the double-buffered
upload/compute overlap measured from the aggregator's own
"ingest.upload" / "ingest.dispatch" span streams.

Roofline-guarded like bench.py: a samples/s above the platform's
HBM-RMW cap means the timing broke (async backend acking before
execution), so the headline is withheld — the raw measurement stays
inspectable next to ``suspect: true``.  On CPU the Pallas kernel runs in
interpret mode, which is orders of magnitude slower than compiled
Mosaic; the CPU numbers calibrate the PIPELINE (overlap pct, crossover
shape), not the kernel.  The per-chip headline only means something from
a chip run.

Usage: python benchmarks/fused_ingest_bench.py [--metrics 10000]
       [--bucket-limit 4096] [--batch 4194304] [--reps 3]
       [--crossover] [--out FILE]
Prints one JSON object (save as FUSED_INGEST_r*.json); importable as
``run(...)`` / ``run_overlap(...)`` for bench.py and tests.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import numpy as np


def _timed(step, acc, ids, values, reps: int) -> float:
    """Median per-batch seconds, value-fetch timed (a corner readback
    forces execution, so the timing cannot end before the work)."""
    acc = step(acc, ids, values)  # compile + warm
    np.asarray(acc[:1, :1])
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        acc = step(acc, ids, values)
        np.asarray(acc[:1, :1])
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def run(num_metrics: int = 10_000, bucket_limit: int = 4_096,
        batch: int = 1 << 22, reps: int = 3) -> dict:
    """Fused vs scatter per-batch ingest at one shape."""
    import jax
    import jax.numpy as jnp

    from bench import plausibility_cap_samples_per_s
    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.ops.fused_ingest import make_fused_ingest_fn
    from loghisto_tpu.ops.ingest import make_ingest_fn

    cfg = MetricConfig(bucket_limit=bucket_limit)
    platform = jax.devices()[0].platform
    rng = np.random.default_rng(0)
    ids = jnp.asarray(
        ((rng.zipf(1.3, batch) - 1) % num_metrics).astype(np.int32)
    )
    values = jnp.asarray(rng.lognormal(10.0, 2.0, batch).astype(np.float32))
    acc_bytes = num_metrics * cfg.num_buckets * 4
    cap = plausibility_cap_samples_per_s(jax.devices()[0].device_kind, acc_bytes)

    def zeros():
        return jnp.zeros((num_metrics, cfg.num_buckets), dtype=jnp.int32)

    scatter = make_ingest_fn(cfg.bucket_limit)
    fused = make_fused_ingest_fn(cfg.bucket_limit)

    t_scatter = _timed(scatter, zeros(), ids, values, reps)
    t_fused = _timed(fused, zeros(), ids, values, reps)

    def line(t):
        sps = batch / t
        suspect = sps > cap
        if suspect:
            print(
                f"fused_ingest_bench: {sps:.3e} samples/s exceeds the "
                f"{platform} roofline cap {cap:.3e}; withholding headline",
                file=sys.stderr,
            )
        return {
            "seconds_per_batch": round(t, 4),
            "samples_per_s": None if suspect else round(sps, 1),
            "measured_samples_per_s": round(sps, 1),
            "suspect": suspect,
        }

    return {
        "metric": "fused one-dispatch ingest vs retired two-dispatch "
                  "compress+scatter, samples/sec/chip",
        "platform": platform,
        "pallas_interpret": platform != "tpu",
        "num_metrics": num_metrics,
        "num_buckets": cfg.num_buckets,
        "batch": batch,
        "reps": reps,
        "roofline_cap_samples_per_s": cap,
        "scatter": line(t_scatter),
        "fused": line(t_fused),
        "fused_over_scatter": round(t_scatter / max(t_fused, 1e-9), 3),
    }


def run_crossover(num_metrics: int = 10_000, bucket_limit: int = 4_096,
                  batches=(1 << 14, 1 << 16, 1 << 17, 1 << 18, 1 << 20),
                  reps: int = 3) -> dict:
    """Where does the fused kernel's sort+layout preprocess amortize?
    Calibrates dispatch.FUSED_MIN_BATCH (captures override the baked
    constant via the thresholds file)."""
    import jax
    import jax.numpy as jnp

    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.ops.fused_ingest import make_fused_ingest_fn
    from loghisto_tpu.ops.ingest import make_ingest_fn

    cfg = MetricConfig(bucket_limit=bucket_limit)
    rng = np.random.default_rng(1)
    scatter = make_ingest_fn(cfg.bucket_limit)
    fused = make_fused_ingest_fn(cfg.bucket_limit)

    points = []
    crossover = None
    for batch in batches:
        ids = jnp.asarray(
            ((rng.zipf(1.3, batch) - 1) % num_metrics).astype(np.int32)
        )
        values = jnp.asarray(
            rng.lognormal(10.0, 2.0, batch).astype(np.float32)
        )
        z = jnp.zeros((num_metrics, cfg.num_buckets), dtype=jnp.int32)
        t_s = _timed(scatter, z, ids, values, reps)
        z = jnp.zeros((num_metrics, cfg.num_buckets), dtype=jnp.int32)
        t_f = _timed(fused, z, ids, values, reps)
        ratio = t_s / max(t_f, 1e-9)
        points.append({
            "batch": batch,
            "scatter_seconds": round(t_s, 5),
            "fused_seconds": round(t_f, 5),
            "fused_over_scatter": round(ratio, 3),
        })
        if crossover is None and ratio >= 1.0:
            crossover = batch
    return {
        "metric": "fused/scatter speedup vs batch size "
                  "(FUSED_MIN_BATCH calibration)",
        "platform": jax.devices()[0].platform,
        "num_metrics": num_metrics,
        "points": points,
        "measured_crossover_batch": crossover,
    }


def derive_fused_min_batch(crossover_result: dict) -> dict | None:
    """Map a measured crossover sweep (``run_crossover``'s output) to a
    platform-scoped thresholds-file update, or None when the sweep never
    found a crossover (the fused kernel never beat scatter at any swept
    batch — true of interpret-mode CPU runs, where writing a number
    would calibrate the TPU default from an untrustworthy measurement,
    the exact misread the r17 satellite exists to stop)."""
    batch = crossover_result.get("measured_crossover_batch")
    platform = crossover_result.get("platform")
    if not isinstance(batch, int) or isinstance(batch, bool) or batch < 1:
        return None
    if not isinstance(platform, str) or not platform:
        return None
    return {"fused_min_batch_by_platform": {platform: batch}}


def write_fused_min_batch(update: dict, path: str | None = None,
                          source: str | None = None) -> str:
    """Merge a ``derive_fused_min_batch`` update into the committed
    dispatch thresholds file (creating it if absent), preserving every
    other key — the same file analyze_capture.py --emit-thresholds
    owns, so a capture and this calibration coexist.  Returns the path
    written."""
    from loghisto_tpu.ops import dispatch

    if path is None:
        path = dispatch.THRESHOLDS_FILE
    table = {}
    try:
        with open(path) as f:
            loaded = json.load(f)
        if isinstance(loaded, dict):
            table = loaded
    except (OSError, ValueError):
        pass
    per_platform = dict(table.get("fused_min_batch_by_platform") or {})
    per_platform.update(update["fused_min_batch_by_platform"])
    table["fused_min_batch_by_platform"] = per_platform
    if source is not None:
        table["source"] = source
    with open(path, "w") as f:
        f.write(json.dumps(table, indent=1) + "\n")
    return path


def run_overlap(num_metrics: int = 4_096, bucket_limit: int = 512,
                batch: int = 1 << 15, rounds: int = 3,
                super_chunks_per_round: int = 4) -> dict:
    """Upload/compute overlap of the r13 double-buffered staging ring,
    measured from the aggregator's own span stream: slot k+1's
    "ingest.upload" window vs slot k's "ingest.dispatch" window.
    overlap_pct = (upload time hidden under a dispatch) / (total upload
    time).  Path-agnostic — the pipeline is the same machinery the fused
    kernel rides on TPU."""
    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.obs.spans import SpanRecorder
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    cfg = MetricConfig(bucket_limit=bucket_limit)
    agg = TPUAggregator(
        num_metrics=num_metrics, config=cfg, transport="raw",
        batch_size=batch,
    )
    rec = SpanRecorder(capacity=8192)
    agg.obs_recorder = rec
    rng = np.random.default_rng(2)
    # several 8-chunk super-slots per transfer item: the two-slot
    # pipeline (stage k+1 while dispatching k) lives INSIDE one
    # _process_raw walk, so each item must span multiple slots.  Rounds
    # are paced with wait_transfers — an unpaced producer trips the
    # shed-don't-block backpressure and drops samples, which would
    # silently shrink the span population being measured.
    n = 8 * batch * super_chunks_per_round
    total = 0
    for _ in range(rounds):
        ids = rng.integers(0, num_metrics, n).astype(np.int32)
        values = rng.lognormal(6.0, 2.0, n).astype(np.float32)
        agg.record_batch(ids, values)
        agg.flush()
        agg.wait_transfers(timeout=120.0)
        total += n
    shipped, shed = agg._xfer_samples_shipped, agg._shed_samples
    uploads = [s for s in rec.spans() if s.stage == "ingest.upload"]
    dispatches = [s for s in rec.spans() if s.stage == "ingest.dispatch"]
    agg.close()

    upload_ns = sum(s.end_ns - s.start_ns for s in uploads)
    hidden_ns = 0
    for u in uploads:
        for d in dispatches:
            lo = max(u.start_ns, d.start_ns)
            hi = min(u.end_ns, d.end_ns)
            if hi > lo:
                hidden_ns += hi - lo
    overlap_pct = 100.0 * hidden_ns / max(upload_ns, 1)
    return {
        "metric": "double-buffered upload/compute overlap "
                  "(span-ring attributed)",
        "num_metrics": num_metrics,
        "batch": batch,
        "samples": total,
        "samples_shipped": shipped,
        "samples_shed": shed,
        "upload_spans": len(uploads),
        "dispatch_spans": len(dispatches),
        "upload_ms_total": round(upload_ns / 1e6, 2),
        "upload_ms_hidden": round(hidden_ns / 1e6, 2),
        "ingest_overlap_pct": round(min(overlap_pct, 100.0), 1),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--metrics", type=int, default=10_000)
    parser.add_argument("--bucket-limit", type=int, default=4_096)
    parser.add_argument("--batch", type=int, default=1 << 22)
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument("--crossover", action="store_true",
                        help="include the FUSED_MIN_BATCH batch sweep")
    parser.add_argument("--tpu", action="store_true",
                        help="keep the configured (TPU) platform")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
        if (args.metrics, args.bucket_limit, args.batch) == (
            10_000, 4_096, 1 << 22
        ):
            # interpret-mode Pallas at the TPU headline shape takes
            # >5 min/dispatch on one core; shrink untouched defaults so
            # a bare CPU invocation terminates (pass shapes explicitly
            # to override)
            print(
                "fused_ingest_bench: CPU run — shrinking to 1024 metrics "
                "x 1025 buckets x 2^16 batch (interpret mode)",
                file=sys.stderr,
            )
            args.metrics, args.bucket_limit, args.batch = 1024, 512, 1 << 16
    result = run(num_metrics=args.metrics, bucket_limit=args.bucket_limit,
                 batch=args.batch, reps=args.reps)
    if args.crossover:
        result["crossover"] = run_crossover(
            num_metrics=args.metrics, bucket_limit=args.bucket_limit,
            reps=args.reps,
        )
    result["overlap"] = run_overlap()
    text = json.dumps(result, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
