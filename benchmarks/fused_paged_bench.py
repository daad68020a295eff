"""r17 direct-to-paged fused ingest characterization: the one-dispatch
compress -> log-bucket -> codec-encode -> page-translate -> scatter
route into the donated page pool vs the retired two-stage paged route
(host fold -> translate -> packed pool commit), the per-mesh-shape
roofline-fraction table, and the end-to-end interval budget (dispatches
per interval + staging-ring upload overlap) on the paged path.

Roofline-guarded like bench.py: samples/s above the platform's HBM-RMW
cap means the timing broke, so the headline is withheld with the raw
measurement left inspectable next to ``suspect: true``.  On CPU the
Pallas scatter tier runs in interpret mode — orders of magnitude slower
than compiled Mosaic — so CPU numbers calibrate the PIPELINE (dispatch
budget, overlap pct, route shape), not the kernel; the per-chip
roofline fraction only means something from a --tpu capture.

The mesh table is a RESOLUTION table, not a scaling sweep: since r18
the page pool shards across the ("stream","metric") mesh, so every
listed shape resolves ONTO the fused_paged route (the r17 rows showed
them declining off it; MESH_PAGED_r18.json has the sharded paged
scaling story, MESH_SCALE_r13 the sharded dense one).  A shape that
still declines — wrong axes, indivisible metric count — publishes the
capability table's own reason string instead of a fraction.

Usage: python benchmarks/fused_paged_bench.py [--metrics 4096]
       [--bucket-limit 512] [--batch 65536] [--reps 3] [--out FILE]
Prints one JSON object (save as FUSED_PAGED_r17.json); importable as
``run(...)`` / ``run_mesh_table(...)`` / ``run_interval_budget(...)``
for bench.py and tests.
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

# ISSUE 17's published shape grid: single device plus the v5e-8 slices.
MESH_SHAPES = ("single", "8x1", "4x2", "2x4", "1x8")


class _MeshShape:
    """Just the surface the capability edges inspect — lets the
    resolution table cover 8-chip shapes without 8 devices."""

    def __init__(self, stream: int, metric: int):
        self.axis_names = ("stream", "metric")
        self.shape = {"stream": stream, "metric": metric}


def _store(num_metrics: int, bucket_limit: int, pool_pages: int):
    from loghisto_tpu.paging import PagedStore, PagedStoreConfig

    return PagedStore(
        num_metrics, bucket_limit,
        config=PagedStoreConfig(pool_pages=pool_pages, page_size=128),
    )


def _force(store) -> None:
    np.asarray(store._pool[:1, :1])


def run(num_metrics: int = 4_096, bucket_limit: int = 512,
        batch: int = 1 << 16, reps: int = 3,
        pool_pages: int = 8_192) -> dict:
    """Fused one-dispatch paged ingest vs the retired two-stage route
    (host fold -> translate -> packed commit) at one shape."""
    import jax
    import jax.numpy as jnp

    from bench import plausibility_cap_samples_per_s
    from loghisto_tpu import _native

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(0)
    ids = ((rng.zipf(1.3, batch) - 1) % num_metrics).astype(np.int32)
    values = rng.lognormal(6.0, 2.0, batch).astype(np.float32)

    # fused path: host prep (codec assignment + page allocation, the
    # work the bridge thread overlaps with device dispatch) happens
    # once per batch content; the timed loop is the ONE device dispatch
    st = _store(num_metrics, bucket_limit, pool_pages)
    t0 = time.perf_counter()
    prep_ids, _ = st.prepare_batch(ids, values)
    host_prep_s = time.perf_counter() - t0
    ids_dev = jnp.asarray(prep_ids)
    values_dev = jnp.asarray(values)
    st.ingest_raw(ids_dev, values_dev)  # compile + warm
    _force(st)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        st.ingest_raw(ids_dev, values_dev)
        _force(st)
        times.append(time.perf_counter() - t0)
    t_fused = float(np.median(times))
    pool_bytes = st.hbm_bytes()

    # two-stage route the fusion retires: numpy fold to (row, bucket,
    # count) triples, host translate through the page table, packed
    # pool commit (the r14 machinery, one extra dispatch + full host
    # fold per batch)
    st2 = _store(num_metrics, bucket_limit, pool_pages)

    def two_stage():
        buckets = _native.compress_np_host(values, st2.precision)
        keep = (ids >= 0) & (ids < num_metrics)
        keys = (ids[keep].astype(np.int64) << 16) | (
            buckets[keep].astype(np.int64) + 32768
        )
        uniq, counts = np.unique(keys, return_counts=True)
        packed = np.empty((len(uniq), 3), dtype=np.int32)
        packed[:, 0] = uniq >> 16
        packed[:, 1] = (uniq & 0xFFFF) - 32768
        packed[:, 2] = counts
        st2.commit(packed)
        _force(st2)

    two_stage()  # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        two_stage()
        times.append(time.perf_counter() - t0)
    t_two = float(np.median(times))

    cap = plausibility_cap_samples_per_s(jax.devices()[0].device_kind, pool_bytes)
    sps = batch / t_fused
    suspect = sps > cap
    if suspect:
        print(
            f"fused_paged_bench: {sps:.3e} samples/s exceeds the "
            f"{platform} roofline cap {cap:.3e}; withholding headline",
            file=sys.stderr,
        )
    return {
        "metric": "direct-to-paged fused one-dispatch ingest vs retired "
                  "two-stage fold+translate+commit, samples/sec/chip",
        "platform": platform,
        "pallas_interpret": platform != "tpu",
        # artifact-level honesty flag: interpret-mode (non-TPU) numbers
        # characterize the pipeline shape, never the kernel — suspect
        # regardless of whether the roofline guard also tripped
        "suspect": bool(suspect or platform != "tpu"),
        "num_metrics": num_metrics,
        "num_buckets": 2 * bucket_limit + 1,
        "batch": batch,
        "reps": reps,
        "pool_hbm_bytes": pool_bytes,
        "roofline_cap_samples_per_s": cap,
        "fused": {
            "seconds_per_batch": round(t_fused, 4),
            "samples_per_s": None if suspect else round(sps, 1),
            "measured_samples_per_s": round(sps, 1),
            "roofline_fraction": round(min(sps / cap, 1.0), 4),
            "host_prep_seconds": round(host_prep_s, 4),
            "suspect": suspect,
        },
        "two_stage": {
            "seconds_per_batch": round(t_two, 4),
            "measured_samples_per_s": round(batch / t_two, 1),
        },
        "fused_over_two_stage": round(t_two / max(t_fused, 1e-9), 3),
    }


def run_mesh_table(num_metrics: int = 1 << 16, bucket_limit: int = 4_096,
                   batch: int = 1 << 20,
                   single_roofline_fraction: float | None = None) -> list:
    """Per-mesh-shape path resolution through resolve_full_path: which
    (transport, ingest, storage) route each shape actually takes, the
    capability reason when a shape declines the fused_paged route, and
    the measured single-device roofline fraction on the shape that runs
    it.  Resolution is pure table walking (no devices needed), which is
    the point: this documents WHAT runs where, with the same strings
    the explicit paths raise."""
    from loghisto_tpu.ops import dispatch

    rows = []
    for shape in MESH_SHAPES:
        if shape == "single":
            mesh = None
        else:
            stream, metric = (int(x) for x in shape.split("x"))
            mesh = _MeshShape(stream, metric)
        fp = dispatch.resolve_full_path(
            num_metrics, 2 * bucket_limit + 1, "tpu", batch_size=batch,
            mesh=mesh,
        )
        row = {
            "mesh": shape,
            "transport": fp.transport,
            "ingest": fp.ingest,
            "storage": fp.storage,
            "commit": fp.commit,
        }
        if fp.ingest == "fused_paged":
            # the measured fraction belongs to the shape it was measured
            # on; sharded shapes resolve the route (r18) but their
            # throughput story lives in MESH_PAGED_r18.json
            row["roofline_fraction"] = (
                single_roofline_fraction if shape == "single" else None
            )
        else:
            row["roofline_fraction"] = None
            row["declined"] = fp.reasons.get(
                "ingest:fused_paged", "fused_paged not resolved"
            )
        rows.append(row)
    return rows


def run_interval_budget(num_metrics: int = 4_096, bucket_limit: int = 512,
                        batch: int = 1 << 15, rounds: int = 2,
                        super_chunks_per_round: int = 4) -> dict:
    """End-to-end paged-path interval budget through the aggregator:
    device dispatches per interval (the acceptance bar is <= 2: the
    fused ingest dispatch, plus at most the interval's commit/readback)
    and the staging-ring upload/compute overlap — the r13 93% figure
    must survive composition with the paged pool (same ring, same
    span attribution, pool instead of dense accumulator)."""
    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.obs.spans import SpanRecorder
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    cfg = MetricConfig(bucket_limit=bucket_limit)
    agg = TPUAggregator(
        num_metrics=num_metrics, config=cfg, storage="paged",
        ingest_path="fused", batch_size=batch,
    )
    assert agg.fused_paged, agg.fused_paged_reason
    rec = SpanRecorder(capacity=8192)
    agg.obs_recorder = rec
    rng = np.random.default_rng(2)
    n = 8 * batch * super_chunks_per_round
    for _ in range(rounds):
        ids = rng.integers(0, num_metrics, n).astype(np.int32)
        values = rng.lognormal(6.0, 2.0, n).astype(np.float32)
        agg.record_batch(ids, values)
        agg.flush()
        agg.wait_transfers(timeout=300.0)
    fused_dispatches = agg.paged.fused_dispatches
    commits = agg.paged.commits
    batches = max(fused_dispatches, 1)
    uploads = [s for s in rec.spans() if s.stage == "ingest.upload"]
    dispatches = [s for s in rec.spans() if s.stage == "ingest.dispatch"]
    shipped, shed = agg._xfer_samples_shipped, agg._shed_samples
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
    import jax

    platform = jax.devices()[0].platform
    return {
        "metric": "paged-path interval budget + staging-ring overlap",
        "platform": platform,
        "suspect": platform != "tpu",
        "num_metrics": num_metrics,
        "batch": batch,
        "samples_shipped": shipped,
        "samples_shed": shed,
        "fused_dispatches": fused_dispatches,
        "pool_commits": commits,
        "dispatches_per_batch": round(
            (fused_dispatches + commits) / batches, 3
        ),
        "meets_two_dispatch_budget": (
            (fused_dispatches + commits) / batches <= 2.0
        ),
        "upload_spans": len(uploads),
        "dispatch_spans": len(dispatches),
        "upload_ms_total": round(upload_ns / 1e6, 2),
        "upload_ms_hidden": round(hidden_ns / 1e6, 2),
        "ingest_overlap_pct": round(min(overlap_pct, 100.0), 1),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--metrics", type=int, default=4_096)
    parser.add_argument("--bucket-limit", type=int, default=512)
    parser.add_argument("--batch", type=int, default=1 << 16)
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument("--tpu", action="store_true",
                        help="keep the configured (TPU) platform")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    result = run(num_metrics=args.metrics, bucket_limit=args.bucket_limit,
                 batch=args.batch, reps=args.reps)
    result["mesh_table"] = run_mesh_table(
        single_roofline_fraction=result["fused"]["roofline_fraction"]
        if not result["fused"]["suspect"] else None,
    )
    if args.tpu:
        result["interval_budget"] = run_interval_budget()
    else:
        # interpret-mode Pallas runs seconds per dispatch on one core;
        # the budget/overlap numbers are structural (dispatch counts,
        # span attribution), so a small population measures them fine
        result["interval_budget"] = run_interval_budget(
            num_metrics=1_024, batch=1 << 12, rounds=1,
            super_chunks_per_round=2,
        )
    text = json.dumps(result, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
