"""Distributed-step characterization at the headline shape (VERDICT r2
item 4): per-step cost of the full mesh aggregation step — local dense
fold + psum merge over the stream axis + metric-sharded accumulate +
stats — at 10k metrics x 8193 buckets with multi-million-sample batches,
against the single-device step on the same workload.

PR-8 adds the interval-commit contenders per mesh shape: the sharded
FUSED committer (one shard_map donated-carry program per interval —
cell deltas psum once over the stream axis, then acc fold + every
tier's open-slot scatter execute shard-local on metric-row-sharded
carries) against the FAN-OUT pipeline on the same sharded state
(bridge-merge + per-tier scatters, what "auto" used to force under a
mesh), plus the single-device fused baseline.  Interval-amortized:
per-interval commit latency, dispatches/interval, and committed
samples/s, with bench.py's HBM-roofline plausibility guard marking
physically impossible rates suspect instead of reporting them.

On the CI/CPU host the 8 "devices" are virtual
(--xla_force_host_platform_device_count=8) and time-slice one core, so
absolute samples/s is not a hardware number; the signal is the
mesh/single per-step ratio, which isolates the extra WORK the
distributed step adds (per-shard zero+fold, psum reduction, halo of
out-of-shard samples) from the kernel itself.  On a real multi-chip TPU
the same harness reports true weak scaling (run with --tpu).

Usage: python benchmarks/mesh_scale.py [--metrics 10000]
       [--bucket-limit 4096] [--batch 4194304] [--reps 3]
       [--commit-only] [--commit-metrics 1024] [--commit-reps 8]
       [--out FILE]
Prints one JSON object (save as MESH_SCALE_r*.json); importable as
``run(...)`` / ``run_commit(...)`` for tests/capture.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# must precede the jax import when run standalone
if "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np


def _timed_step(step, acc, ids, values, reps: int) -> tuple[float, object]:
    """Median per-step seconds, value-fetch timed (stats counts leave the
    device each rep, so the timing cannot end before the work)."""
    acc, stats = step(acc, ids, values)  # compile + warm
    np.asarray(stats["counts"])
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        acc, stats = step(acc, ids, values)
        np.asarray(stats["counts"])
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), acc


def run(num_metrics: int = 10_000, bucket_limit: int = 4_096,
        batch: int = 1 << 22, reps: int = 3,
        shapes: list[dict] | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.ops.dispatch import ingest_step_fn, resolve_ingest_path
    from loghisto_tpu.ops.stats import dense_stats
    from loghisto_tpu.parallel.aggregator import (
        make_distributed_step,
        make_sharded_accumulator,
    )
    from loghisto_tpu.parallel.mesh import make_mesh

    cfg = MetricConfig(bucket_limit=bucket_limit)
    devs = jax.devices()
    platform = devs[0].platform
    ps = np.array([0.0, 0.5, 0.99, 0.9999, 1.0], dtype=np.float32)

    rng = np.random.default_rng(0)
    raw = rng.zipf(1.3, size=batch)
    ids = jnp.asarray(((raw - 1) % num_metrics).astype(np.int32))
    values = jnp.asarray(
        rng.lognormal(10.0, 2.0, batch).astype(np.float32)
    )

    result = {
        "platform": platform,
        # virtual CPU "devices" time-slice one core: absolute rates are
        # not hardware numbers, only the mesh/single ratios are signal
        "suspect": platform != "tpu",
        "n_devices": len(devs),
        "num_metrics": num_metrics,
        "num_buckets": cfg.num_buckets,
        "batch": batch,
        "reps": reps,
        "steps": {},
    }

    # -- single-device reference step: dispatched kernel + stats --
    path = resolve_ingest_path(
        "auto", num_metrics, cfg.num_buckets, platform, batch_size=batch
    )
    kernel = ingest_step_fn(path)

    @jax.jit
    def single_step(acc, ids, values):
        acc = kernel(acc, ids, values, cfg.bucket_limit, cfg.precision)
        return acc, dense_stats(acc, ps, cfg.bucket_limit, cfg.precision)

    acc0 = jnp.zeros((num_metrics, cfg.num_buckets), dtype=jnp.int32)
    t_single, acc_out = _timed_step(single_step, acc0, ids, values, reps)
    del acc_out, acc0
    result["steps"]["single"] = {
        "ingest_path": path,
        "seconds_per_step": round(t_single, 4),
        "samples_per_s": round(batch / t_single, 1),
    }

    # -- mesh steps: sweep the dp(stream) x tp(metric) spectrum --
    n = len(devs)
    if shapes is None:
        shapes = []
        metric = 1
        while metric <= n:
            if n % metric == 0 and num_metrics % metric == 0:
                shapes.append({"stream": n // metric, "metric": metric})
            metric *= 2
    from loghisto_tpu.parallel.aggregator import (
        make_interval_distributed_step,
    )

    for shape in shapes:
        mesh = make_mesh(stream=shape["stream"], metric=shape["metric"])
        step = make_distributed_step(
            mesh, num_metrics, cfg.bucket_limit, ps, batch_size=batch
        )
        acc = make_sharded_accumulator(mesh, num_metrics, cfg.num_buckets)
        t_mesh, acc = _timed_step(step, acc, ids, values, reps)
        del acc
        key = f"stream{shape['stream']}xmetric{shape['metric']}"
        result["steps"][key] = {
            "seconds_per_step": round(t_mesh, 4),
            "samples_per_s": round(batch / t_mesh, 1),
            "vs_single": round(t_mesh / t_single, 3),
        }

        # -- interval-amortized path (VERDICT r3 item 3): collective-free
        # per-batch folds, ONE psum at collect.  Report the per-batch
        # ingest cost (the steady-state number the amortization buys) and
        # the once-per-interval collect cost separately.
        ingest, collect, make_partial = make_interval_distributed_step(
            mesh, num_metrics, cfg.bucket_limit, ps, batch_size=batch
        )
        partial = ingest(make_partial(), ids, values)  # compile + warm
        jax.block_until_ready(partial)
        t_in = []
        for _ in range(reps):
            t0 = time.perf_counter()
            partial = ingest(partial, ids, values)
            jax.block_until_ready(partial)
            t_in.append(time.perf_counter() - t0)
        t_ingest = float(np.median(t_in))
        acc = make_sharded_accumulator(mesh, num_metrics, cfg.num_buckets)
        acc, partial, stats = collect(acc, partial)  # compile + warm
        np.asarray(stats["counts"])
        t_col = []
        for _ in range(reps):
            partial = ingest(partial, ids, values)
            jax.block_until_ready(partial)
            t0 = time.perf_counter()
            acc, partial, stats = collect(acc, partial)
            np.asarray(stats["counts"])
            t_col.append(time.perf_counter() - t0)
        t_collect = float(np.median(t_col))
        del acc, partial, stats

        # -- r13 async stream psum: issue the collective via
        # collect.start (no fresh-partial output, so the next interval's
        # fold is not a data-dependent consumer), overlap the next
        # batch's shard-local fold, then fetch.  Compare against the
        # serial collect-then-ingest pair measured above.
        acc = make_sharded_accumulator(mesh, num_metrics, cfg.num_buckets)
        partial = ingest(make_partial(), ids, values)
        jax.block_until_ready(partial)
        acc, stats = collect.start(acc, partial)  # compile + warm
        np.asarray(stats["counts"])
        t_ov = []
        for _ in range(reps):
            partial = ingest(make_partial(), ids, values)
            jax.block_until_ready(partial)
            t0 = time.perf_counter()
            acc, stats = collect.start(acc, partial)
            nxt = ingest(make_partial(), ids, values)  # overlaps the psum
            np.asarray(stats["counts"])
            jax.block_until_ready(nxt)
            t_ov.append(time.perf_counter() - t0)
        t_overlap = float(np.median(t_ov))
        del acc, partial, nxt, stats
        t_serial_pair = t_collect + t_ingest

        result["steps"][key + "_interval"] = {
            "ingest_seconds_per_batch": round(t_ingest, 4),
            "collect_seconds": round(t_collect, 4),
            "ingest_samples_per_s": round(batch / t_ingest, 1),
            "ingest_vs_single": round(t_ingest / t_single, 3),
            # effective per-batch cost at 10 batches/interval
            "per_batch_at_10_vs_single": round(
                (t_ingest + t_collect / 10) / t_single, 3
            ),
            # collect + next batch, serial vs collective-overlapped
            "collect_plus_batch_serial_seconds": round(t_serial_pair, 4),
            "collect_plus_batch_overlap_seconds": round(t_overlap, 4),
            "async_psum_saving_pct": round(
                100.0 * (1.0 - t_overlap / max(t_serial_pair, 1e-9)), 1
            ),
        }
    return result


def _commit_intervals(rng, n, num_metrics, bucket_limit,
                      cells_per_metric=24):
    """Pre-built sparse interval payloads — identical streams for every
    contender (mirrors benchmarks/interval_commit.py)."""
    import datetime as _dt

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


def run_commit(num_metrics: int = 1024, bucket_limit: int = 512,
               reps: int = 8, tiers=((8, 1), (4, 8)),
               shapes: list[dict] | None = None) -> dict:
    """Fused-vs-fanout interval commit per mesh shape, interval-amortized.

    Every contender is fed the identical interval stream; latency is a
    host-blocking measure (block_until_ready on acc + every ring after
    each interval) so async dispatch cannot flatter either side.
    """
    import jax

    from bench import peak_bytes_per_s
    from loghisto_tpu.commit import IntervalCommitter
    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.metrics import RawMetricSet
    from loghisto_tpu.parallel.aggregator import TPUAggregator
    from loghisto_tpu.parallel.mesh import make_mesh
    from loghisto_tpu.window import TimeWheel
    from loghisto_tpu.window import store as store_mod

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

    def block(agg, wheel):
        agg._acc.block_until_ready()
        for t in wheel._tiers:
            t.ring.block_until_ready()

    def timed_fused(mesh):
        agg = TPUAggregator(num_metrics=num_metrics, config=cfg, mesh=mesh)
        wheel = TimeWheel(num_metrics=num_metrics, config=cfg, interval=1.0,
                          tiers=tiers, registry=agg.registry, mesh=mesh)
        committer = IntervalCommitter(agg, wheel)
        committer.warmup()
        committer.commit(raw_of(stream[0]))  # warm name resolution
        block(agg, wheel)
        times, dispatches = [], []
        for entry in stream[2:]:
            raw = raw_of(entry)
            t1 = time.perf_counter()
            committer.commit(raw)
            block(agg, wheel)
            times.append(time.perf_counter() - t1)
            dispatches.append(committer.last_dispatches)
        assert committer.fanout_intervals == 0
        return float(np.median(times)), int(np.median(dispatches))

    def timed_fanout(mesh):
        agg = TPUAggregator(num_metrics=num_metrics, config=cfg, mesh=mesh)
        wheel = TimeWheel(num_metrics=num_metrics, config=cfg, interval=1.0,
                          tiers=tiers, registry=agg.registry, mesh=mesh)
        agg._bridge_warmup()
        agg.merge_raw(raw_of(stream[0]))
        wheel.push(raw_of(stream[0]))
        block(agg, wheel)
        counts = {"n": 0}
        real_scatter = store_mod._scatter_cells_jit
        real_open = store_mod._open_slot_jit
        real_weighted = agg._weighted_ingest

        def counting(fn):
            def wrapped(*a, **kw):
                counts["n"] += 1
                return fn(*a, **kw)
            return wrapped

        store_mod._scatter_cells_jit = counting(real_scatter)
        store_mod._open_slot_jit = counting(real_open)
        agg._weighted_ingest = counting(real_weighted)
        times, dispatches = [], []
        try:
            for entry in stream[2:]:
                raw = raw_of(entry)
                counts["n"] = 0
                t1 = time.perf_counter()
                agg.merge_raw(raw)
                wheel.push(raw)
                block(agg, wheel)
                times.append(time.perf_counter() - t1)
                dispatches.append(counts["n"])
        finally:
            store_mod._scatter_cells_jit = real_scatter
            store_mod._open_slot_jit = real_open
            agg._weighted_ingest = real_weighted
        return float(np.median(times)), int(np.median(dispatches))

    n = len(jax.devices())
    if shapes is None:
        shapes = []
        metric = 1
        while metric <= n:
            if n % metric == 0 and num_metrics % metric == 0:
                shapes.append({"stream": n // metric, "metric": metric})
            metric *= 2

    result = {
        "metric": "mesh-sharded fused commit vs fan-out, per mesh shape",
        "platform": platform,
        # artifact-level flag mirroring the per-shape roofline guard:
        # on virtual CPU devices every absolute rate is suspect
        "suspect": platform != "tpu",
        "n_devices": n,
        "num_metrics": num_metrics,
        "num_buckets": cfg.num_buckets,
        "tiers": [list(t) for t in tiers],
        "reps": reps,
        "samples_per_interval": samples_per_interval,
        "hbm_peak_bytes_per_s": cap,
        "shapes": {},
    }

    def entry(fused, fanout, t_single_fused=None):
        fused_med, fused_disp = fused
        fan_med, fan_disp = fanout
        samples_per_s = samples_per_interval / max(fused_med, 1e-9)
        # roofline guard: every committed sample is at minimum one
        # int32 RMW (8 bytes); a rate above peak-bandwidth/8 means the
        # timing broke, not that the program is fast
        suspect = samples_per_s > cap / 8
        out = {
            "fused_commit_median_us": round(fused_med * 1e6, 1),
            "fanout_commit_median_us": round(fan_med * 1e6, 1),
            "fused_dispatches_per_interval": fused_disp,
            "fanout_dispatches_per_interval": fan_disp,
            "fused_samples_per_s": (
                None if suspect else round(samples_per_s, 1)
            ),
            "measured_samples_per_s": round(samples_per_s, 1),
            "suspect": suspect,
            "fanout_over_fused": (
                None if suspect
                else round(fan_med / max(fused_med, 1e-9), 2)
            ),
        }
        if suspect:
            print(
                f"mesh_scale: {samples_per_s:.3e} committed samples/s "
                f"exceeds the {platform} roofline cap {cap / 8:.3e}; "
                "withholding the headline for this shape",
                file=sys.stderr,
            )
        if t_single_fused is not None:
            out["fused_vs_single_device"] = round(
                fused_med / max(t_single_fused, 1e-9), 3
            )
        return out

    single_fused = timed_fused(None)
    result["shapes"]["single"] = entry(single_fused, timed_fanout(None))
    for shape in shapes:
        mesh = make_mesh(stream=shape["stream"], metric=shape["metric"])
        key = f"stream{shape['stream']}xmetric{shape['metric']}"
        result["shapes"][key] = entry(
            timed_fused(mesh), timed_fanout(mesh),
            t_single_fused=single_fused[0],
        )
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--metrics", type=int, default=10_000)
    parser.add_argument("--bucket-limit", type=int, default=4_096)
    parser.add_argument("--batch", type=int, default=1 << 22)
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument("--commit-only", action="store_true",
                        help="skip the distributed-step sweep and report "
                             "only the interval-commit contenders")
    parser.add_argument("--commit-metrics", type=int, default=1024)
    parser.add_argument("--commit-bucket-limit", type=int, default=512)
    parser.add_argument("--commit-reps", type=int, default=8)
    parser.add_argument("--tpu", action="store_true",
                        help="keep the configured (TPU) platform instead "
                             "of forcing virtual-CPU devices")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    result = {}
    if not args.commit_only:
        result = run(num_metrics=args.metrics,
                     bucket_limit=args.bucket_limit,
                     batch=args.batch, reps=args.reps)
    result["commit"] = run_commit(
        num_metrics=args.commit_metrics,
        bucket_limit=args.commit_bucket_limit,
        reps=args.commit_reps,
    )
    text = json.dumps(result, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
