"""r18 mesh-sharded paged storage characterization: the fused interval
commit running ON the sharded page pool, per mesh shape, plus the
8M-live-row pod sizing the sharding exists to reach.

Three sections:

  * ``shapes`` — the identical interval stream committed through the
    paged fused committer at every mesh shape (single, 8x1, 4x2, 2x4,
    1x8): per-interval latency, dispatches/interval (the acceptance bar
    is <= 2), committed samples/s under bench.py's HBM-roofline guard,
    and a BIT-IDENTICAL parity check of the final pool decode against
    the single-device oracle (int32 scatter + one stream-axis psum is
    order-free, so any mismatch is a bug, not noise).  r17's table
    showed these shapes DECLINING off the paged route; these rows run
    it.
  * ``occupancy`` — measured pages/live-row on a real store at the HBM
    bucket resolution (codec mix included), the input to the sizing.
  * ``eight_million_rows`` — the 8-way-mesh pod config: 2^23 live rows
    split 8 ways over the metric axis, per-shard arena pages from the
    measured occupancy plus headroom, per-chip and pod HBM against the
    16 GiB v5e-class budget, and the dense-tensor footprint the paged
    substrate displaces.  Sizing arithmetic, not a timing — it is
    platform-independent and carries no throughput claim.

On the CI/CPU host the 8 "devices" are virtual
(--xla_force_host_platform_device_count=8) and time-slice one core, so
every absolute rate is marked suspect; the signal is dispatch counts,
parity, and the shape-to-shape ratio no longer degrading to a decline.

Usage: python benchmarks/mesh_paged.py [--metrics 1024]
       [--bucket-limit 512] [--reps 4] [--out FILE]
Prints one JSON object (save as MESH_PAGED_r18.json); importable as
``run_shapes(...)`` / ``run_sizing(...)`` for tests/capture.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

if "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np

# the published grid: single device plus every v5e-8 factorization
MESH_SHAPES = (None, (8, 1), (4, 2), (2, 4), (1, 8))


def _shape_key(shape) -> str:
    if shape is None:
        return "single"
    return f"stream{shape[0]}xmetric{shape[1]}"


def run_shapes(num_metrics: int = 1024, bucket_limit: int = 512,
               reps: int = 4, tiers=((8, 1), (4, 8)),
               pool_pages: int = 2048) -> dict:
    """The identical interval stream through the paged fused committer
    at every mesh shape, with pool-decode parity against single."""
    import jax

    from bench import peak_bytes_per_s
    from mesh_scale import _commit_intervals
    from loghisto_tpu.commit import IntervalCommitter
    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.metrics import RawMetricSet
    from loghisto_tpu.paging import PagedStoreConfig
    from loghisto_tpu.parallel.aggregator import TPUAggregator
    from loghisto_tpu.parallel.mesh import make_mesh
    from loghisto_tpu.window import TimeWheel

    platform = jax.devices()[0].platform
    cap = peak_bytes_per_s(jax.devices()[0].device_kind)
    cfg = MetricConfig(bucket_limit=bucket_limit)
    rng = np.random.default_rng(0)
    stream = _commit_intervals(rng, reps + 2, num_metrics, bucket_limit)
    samples_per_interval = sum(
        sum(h.values()) for h in stream[2][1].values()
    )

    def raw_of(entry):
        t, hists = entry
        return RawMetricSet(time=t, counters={}, rates={},
                            histograms=hists, gauges={}, duration=1.0)

    def timed(mesh):
        agg = TPUAggregator(
            num_metrics=num_metrics, config=cfg, storage="paged",
            paged_config=PagedStoreConfig(pool_pages=pool_pages),
            mesh=mesh,
        )
        wheel = TimeWheel(num_metrics=num_metrics, config=cfg,
                          interval=1.0, tiers=tiers,
                          registry=agg.registry, mesh=mesh)
        committer = IntervalCommitter(agg, wheel)
        committer.warmup()
        committer.commit(raw_of(stream[0]))  # warm name resolution
        agg.paged._pool.block_until_ready()
        times, dispatches = [], []
        for entry in stream[2:]:
            raw = raw_of(entry)
            t1 = time.perf_counter()
            committer.commit(raw)
            agg.paged._pool.block_until_ready()
            for t in wheel._tiers:
                t.ring.block_until_ready()
            times.append(time.perf_counter() - t1)
            dispatches.append(committer.last_dispatches)
        assert committer.fanout_intervals == 0
        decode = agg.paged.decode_dense(include_spill=True)
        return (float(np.median(times)), int(np.median(dispatches)),
                decode)

    result = {
        "metric": "fused interval commit on the mesh-sharded page pool, "
                  "per mesh shape",
        "platform": platform,
        # virtual CPU devices time-slice one core: absolute rates are
        # pipeline-shape calibration, not hardware numbers
        "suspect": platform != "tpu",
        "n_devices": len(jax.devices()),
        "num_metrics": num_metrics,
        "num_buckets": cfg.num_buckets,
        "pool_pages_per_shard": pool_pages,
        "tiers": [list(t) for t in tiers],
        "reps": reps,
        "samples_per_interval": samples_per_interval,
        "shapes": {},
    }

    oracle = None
    for shape in MESH_SHAPES:
        if shape is None:
            mesh = None
        else:
            stream_ax, metric_ax = shape
            if num_metrics % metric_ax:
                result["shapes"][_shape_key(shape)] = {
                    "declined": f"num_metrics {num_metrics} not divisible "
                                f"by {metric_ax}-way metric axis"
                }
                continue
            mesh = make_mesh(stream=stream_ax, metric=metric_ax)
        med, disp, decode = timed(mesh)
        if oracle is None:
            oracle = decode  # single runs first
        sps = samples_per_interval / max(med, 1e-9)
        suspect = platform != "tpu" or sps > cap / 8
        row = {
            "commit_median_us": round(med * 1e6, 1),
            "dispatches_per_interval": disp,
            "meets_two_dispatch_budget": disp <= 2,
            "samples_per_s": None if suspect else round(sps, 1),
            "measured_samples_per_s": round(sps, 1),
            "suspect": suspect,
            "pool_decode_bit_identical_to_single": bool(
                np.array_equal(decode, oracle)
            ),
        }
        result["shapes"][_shape_key(shape)] = row
    return result


def run_occupancy(rows: int = 16_384, bucket_limit: int = 4_096,
                  samples_per_row: int = 64) -> dict:
    """Measured pages per live row at the HBM bucket resolution, codec
    mix included — the empirical input to the 8M-row sizing."""
    from loghisto_tpu.paging import PagedStore, PagedStoreConfig

    st = PagedStore(
        rows, bucket_limit,
        config=PagedStoreConfig(pool_pages=rows * 8),
    )
    rng = np.random.default_rng(1)
    ids = np.repeat(np.arange(rows, dtype=np.int64), samples_per_row)
    # realistic row shape: each metric clusters around its own center
    # (a service's latency distribution), with a heavy tail — the mix
    # that exercises dense/loglinear/polytail codec choices without
    # every row smearing across the whole bucket axis
    centers = rng.integers(
        -bucket_limit // 2, bucket_limit // 2, rows
    )[ids]
    spread = rng.normal(0, bucket_limit / 24, len(ids))
    tail = rng.random(len(ids)) < 0.02
    spread[tail] *= 8.0
    buckets = np.clip(
        centers + spread, -bucket_limit, bucket_limit
    ).astype(np.int64)
    packed = np.empty((len(ids), 3), dtype=np.int32)
    packed[:, 0] = ids
    packed[:, 1] = buckets
    packed[:, 2] = 1
    st.commit(packed)
    live = rows
    pages_per_row = st.occupied_pages / live
    codec_counts: dict = {}
    for name in st.codec_names():
        if name is not None:
            codec_counts[name] = codec_counts.get(name, 0) + 1
    return {
        "rows": rows,
        "bucket_limit": bucket_limit,
        "samples_per_row": samples_per_row,
        "occupied_pages": st.occupied_pages,
        "pages_per_live_row": round(pages_per_row, 3),
        "codec_mix": codec_counts,
        "spilled_cells": st.spilled_cells,
    }


def run_sizing(occ: dict, n_shards: int = 8, page_size: int = 256,
               headroom: float = 1.25,
               hbm_budget_gib: float = 16.0) -> dict:
    """The 8M-live-row 8-way-mesh pod config from the measured
    occupancy.  Pure arithmetic — no throughput claim rides on it."""
    rows = 1 << 23  # 8,388,608
    rows_per_shard = rows // n_shards
    pages_per_row = occ["pages_per_live_row"]
    shard_pages = int(rows_per_shard * pages_per_row * headroom) + 1
    pool_bytes_per_shard = shard_pages * page_size * 4
    # host page table is pod-global (one per process), device pool is
    # the per-chip HBM cost
    bl = occ["bucket_limit"]
    dense_bytes_per_row = (2 * bl + 1) * 4
    dense_pod_gib = rows * dense_bytes_per_row / 2**30
    return {
        "live_rows": rows,
        "mesh": f"metric={n_shards} (8-way)",
        "rows_per_shard": rows_per_shard,
        "pages_per_live_row_measured": pages_per_row,
        "headroom": headroom,
        "shard_pool_pages": shard_pages,
        "pool_gib_per_chip": round(pool_bytes_per_shard / 2**30, 3),
        "pool_gib_pod": round(
            n_shards * pool_bytes_per_shard / 2**30, 3
        ),
        "hbm_budget_gib_per_chip": hbm_budget_gib,
        "fits_budget": pool_bytes_per_shard / 2**30 < hbm_budget_gib,
        "dense_equivalent_gib_pod": round(dense_pod_gib, 1),
        "paged_reduction_vs_dense": round(
            dense_pod_gib / max(
                n_shards * pool_bytes_per_shard / 2**30, 1e-9
            ), 1
        ),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--metrics", type=int, default=1024)
    parser.add_argument("--bucket-limit", type=int, default=512)
    parser.add_argument("--reps", type=int, default=4)
    parser.add_argument("--occupancy-rows", type=int, default=16_384)
    parser.add_argument("--tpu", action="store_true",
                        help="keep the configured (TPU) platform instead "
                             "of forcing virtual-CPU devices")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    result = run_shapes(num_metrics=args.metrics,
                        bucket_limit=args.bucket_limit, reps=args.reps)
    result["occupancy"] = run_occupancy(rows=args.occupancy_rows)
    result["eight_million_rows"] = run_sizing(result["occupancy"])
    text = json.dumps(result, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
