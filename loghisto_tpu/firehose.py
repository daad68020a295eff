"""Synthetic latency firehose: on-device sample generation -> dense
aggregation -> per-interval export replay (BASELINE.json configs[4]:
"1B-sample/sec synthetic latency firehose -> OpenTSDB submitter replay").

Host->device transfer cannot carry 1B samples/s, so the firehose
generates samples *on device* inside the jitted step (Zipf-skewed metric
ids via inverse-CDF searchsorted, lognormal latencies), fuses generation
with compress+scatter-add, and only the per-interval statistics leave the
device.  Each interval's ProcessedMetricSet is serialized with the
OpenTSDB protocol and either written to a sink address or summarized to
stdout.

CLI: python -m loghisto_tpu.firehose --metrics 10000 --seconds 5
     [--sink host:port] [--batch 4194304]
"""

from __future__ import annotations

import datetime as _dt
import functools
import sys
import time
from typing import Optional

import numpy as np

from loghisto_tpu.config import DEFAULT_PERCENTILES, MetricConfig
from loghisto_tpu.metrics import ProcessedMetricSet
from loghisto_tpu.opentsdb import opentsdb_protocol


def zipf_cdf(num_metrics: int, s: float = 1.3) -> np.ndarray:
    weights = 1.0 / np.arange(1, num_metrics + 1, dtype=np.float64) ** s
    cdf = np.cumsum(weights)
    return (cdf / cdf[-1]).astype(np.float32)


def _make_sample_generator(
    num_metrics: int, mean: float, sigma: float
):
    """Shared synthetic workload: Zipf-skewed metric ids (inverse-CDF
    searchsorted) + lognormal latencies.  Used by both the single-device
    and the mesh firehose steps so the distributions can never diverge."""
    import jax
    import jax.numpy as jnp

    cdf = zipf_cdf(num_metrics)

    def generate(key, n: int):
        k1, k2 = jax.random.split(key)
        u = jax.random.uniform(k1, (n,), dtype=jnp.float32)
        ids = jnp.searchsorted(jnp.asarray(cdf), u).astype(jnp.int32)
        values = jnp.exp(
            mean + sigma * jax.random.normal(k2, (n,), dtype=jnp.float32)
        )
        return ids, values

    return generate


def make_firehose_step(
    num_metrics: int,
    batch: int,
    config: MetricConfig,
    mean: float = 10.0,
    sigma: float = 2.0,
    ingest_path: str = "auto",
):
    """Jitted (acc, key) -> (acc', key'): generate one batch on device and
    accumulate it.  Generation fuses into the ingest program, so HBM
    traffic is accumulator-only.  The accumulation kernel is the
    auto-dispatched one for this configuration (sort-dedup at high metric
    cardinality on TPU — the duplicate-heavy Zipf batches the firehose
    generates are exactly the regime where plain scatter serializes)."""
    import jax

    from loghisto_tpu.ops.dispatch import ingest_step_fn, resolve_ingest_path

    ingest_path = resolve_ingest_path(
        ingest_path, num_metrics, config.num_buckets,
        jax.default_backend(), batch_size=batch,
    )
    accumulate = ingest_step_fn(ingest_path)
    generate = _make_sample_generator(num_metrics, mean, sigma)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(acc, key):
        key, sub = jax.random.split(key)
        ids, values = generate(sub, batch)
        acc = accumulate(
            acc, ids, values, config.bucket_limit, config.precision
        )
        return acc, key

    return step


def make_mesh_firehose_interval_step(
    mesh,
    num_metrics: int,
    batch: int,
    config: MetricConfig,
    mean: float = 10.0,
    sigma: float = 2.0,
    ingest_path: str = "auto",
):
    """Interval-amortized distributed firehose (the firehose twin of
    aggregator.make_interval_distributed_step): each device generates its
    own sample shard (keys split per stream index) and folds it into its
    (stream, metric) partial block with ZERO collectives; the stream-axis
    psum — the BASELINE configs[2] '8-way psum merge' — runs once per
    collect, into the metric-sharded accumulator.

    Returns (ingest, collect, make_partial):
      ingest(partial, key) -> (partial, key)   collective-free batch
      collect(acc, partial) -> (acc, fresh_partial)  one psum/interval
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from loghisto_tpu.ops.dispatch import ingest_step_fn, resolve_ingest_path
    from loghisto_tpu.ops.ingest import sanitize_ids
    from jax import shard_map
    from loghisto_tpu.parallel.mesh import METRIC_AXIS, STREAM_AXIS

    n_stream = mesh.shape[STREAM_AXIS]
    n_metric = mesh.shape[METRIC_AXIS]
    if num_metrics % n_metric or batch % n_stream:
        raise ValueError("metrics/batch must divide the mesh axes")
    rows = num_metrics // n_metric
    local_batch = batch // n_stream
    ingest_path = resolve_ingest_path(
        ingest_path, num_metrics, config.num_buckets,
        mesh.devices.flat[0].platform, batch_size=local_batch, mesh=True,
    )
    generate = _make_sample_generator(num_metrics, mean, sigma)

    def local_ingest(partial_local, key):
        si = jax.lax.axis_index(STREAM_AXIS)
        mi = jax.lax.axis_index(METRIC_AXIS)
        ids, values = generate(jax.random.fold_in(key[0], si), local_batch)
        local_ids = sanitize_ids(ids - mi * rows)
        folded = ingest_step_fn(ingest_path)(
            partial_local[0], local_ids, values,
            config.bucket_limit, config.precision,
        )
        return folded[None]

    ingest_inner = shard_map(
        local_ingest, mesh=mesh,
        in_specs=(P(STREAM_AXIS, METRIC_AXIS, None), P()),
        out_specs=P(STREAM_AXIS, METRIC_AXIS, None),
    )

    @functools.partial(jax.jit, donate_argnums=0)
    def ingest(partial, key):
        key, sub = jax.random.split(key)
        return ingest_inner(partial, sub[None]), key

    def local_collect(acc_local, partial_local):
        merged = jax.lax.psum(partial_local[0], STREAM_AXIS)
        return acc_local + merged, jnp.zeros_like(partial_local)

    collect = jax.jit(
        shard_map(
            local_collect, mesh=mesh,
            in_specs=(
                P(METRIC_AXIS, None),
                P(STREAM_AXIS, METRIC_AXIS, None),
            ),
            out_specs=(
                P(METRIC_AXIS, None),
                P(STREAM_AXIS, METRIC_AXIS, None),
            ),
        ),
        donate_argnums=(0, 1),
    )

    def make_partial() -> jnp.ndarray:
        sharding = NamedSharding(mesh, P(STREAM_AXIS, METRIC_AXIS, None))
        return jax.device_put(
            jnp.zeros(
                (n_stream, num_metrics, config.num_buckets),
                dtype=jnp.int32,
            ),
            sharding,
        )

    return ingest, collect, make_partial


def run_firehose(
    num_metrics: int = 10_000,
    batch: int = 1 << 22,
    seconds: float = 5.0,
    interval: float = 1.0,
    sink: Optional[tuple[str, int]] = None,
    config: Optional[MetricConfig] = None,
    mesh=None,
    out=sys.stdout,
    max_inflight: int = 8,
    ingest_path: str = "auto",
    max_interval_samples: Optional[int] = None,
    recorder=None,
) -> dict:
    """Run the firehose; returns a summary dict (samples/s, intervals).
    With `mesh`, generation+aggregation run SPMD with psum merges.
    `max_interval_samples` overrides the int32-exactness early-close
    budget (default 2^31 - batch; see the guard below).  ``recorder``
    (an obs.SpanRecorder) records a span per dispatch step, per
    interval, and per export — the contender knob behind
    benchmarks/obs_overhead.py's < 2%% recorder-cost criterion."""
    import jax
    import jax.numpy as jnp

    from loghisto_tpu.obs.spans import NULL_RECORDER
    from loghisto_tpu.ops.stats import dense_stats

    rec = recorder if recorder is not None else NULL_RECORDER
    config = config or MetricConfig()
    ingest = collect = partial = None
    if mesh is not None:
        # interval-amortized SPMD: per-batch folds are collective-free;
        # the stream-axis psum runs once per interval at collect
        ingest, collect, make_partial = make_mesh_firehose_interval_step(
            mesh, num_metrics, batch, config, ingest_path=ingest_path
        )
    else:
        step = make_firehose_step(
            num_metrics, batch, config, ingest_path=ingest_path
        )
    stats_fn = jax.jit(
        functools.partial(
            dense_stats,
            bucket_limit=config.bucket_limit,
            precision=config.precision,
        )
    )
    labels, ps = zip(*(
        (label, p) for label, p in DEFAULT_PERCENTILES.items()
        if 0.0 <= p <= 1.0
    ))
    ps = np.asarray(ps, dtype=np.float32)

    if mesh is not None:
        from loghisto_tpu.parallel.aggregator import make_sharded_accumulator

        acc = make_sharded_accumulator(mesh, num_metrics, config.num_buckets)
        partial = make_partial()
        key = jax.random.key(0)
        partial, key = ingest(partial, key)  # compile both programs
        acc, partial = collect(acc, partial)
        jax.block_until_ready(acc)
        acc = jnp.zeros_like(acc)  # discard warm-up samples
    else:
        acc = jnp.zeros((num_metrics, config.num_buckets), dtype=jnp.int32)
        key = jax.random.key(0)
        acc, key = step(acc, key)  # compile
        jax.block_until_ready(acc)
        acc = jnp.zeros_like(acc)  # discard warm-up samples from interval 1

    # int32-exactness budget: the dense accumulator (and mesh partials)
    # are int32, and the worst case concentrates every sample of an
    # interval in one cell.  At TPU-scale rates (1e9/s) a >2s interval
    # would cross 2^31 — stop dispatching and close the interval early
    # instead of silently wrapping (TPUAggregator spills to host int64
    # for the same reason; the firehose's synthetic load just closes the
    # interval, which is exact).
    if max_interval_samples is None:
        max_interval_samples = (1 << 31) - batch

    total_samples = 0
    intervals = 0
    t_start = time.perf_counter()
    while time.perf_counter() - t_start < seconds:
        rec.begin_interval()
        t_int_ns = time.perf_counter_ns()
        t_int = time.perf_counter()
        interval_samples = 0
        inflight = 0
        while time.perf_counter() - t_int < interval:
            if interval_samples >= max_interval_samples:
                out.write(
                    "interval closing early: int32 accumulator budget "
                    f"({interval_samples:,} samples)\n"
                )
                break
            step_ns = time.perf_counter_ns()
            if mesh is not None:
                partial, key = ingest(partial, key)
            else:
                acc, key = step(acc, key)
            rec.record("firehose.step", step_ns, time.perf_counter_ns())
            interval_samples += batch
            # bound the async dispatch queue: without this, a dispatcher
            # that runs ahead of the device (or of a slow link) enqueues
            # thousands of steps inside one wall-clock interval and the
            # stats sync below then drains them for minutes — the
            # interval's sample count must reflect work the device kept
            # up with, not a backlog
            inflight += 1
            if inflight >= max_inflight:
                jax.block_until_ready(partial if mesh is not None else acc)
                inflight = 0
        if mesh is not None:
            acc, partial = collect(acc, partial)
        stats = stats_fn(acc, ps)
        counts = np.asarray(stats["counts"])
        pcts = np.asarray(stats["percentiles"])
        sums = np.asarray(stats["sums"])
        acc = jnp.zeros_like(acc)
        intervals += 1
        total_samples += interval_samples

        # serialize the hottest metrics for the export replay
        with rec.span("firehose.export"):
            metrics = {}
            hot = np.argsort(counts)[::-1][:16]
            for mid in hot:
                if counts[mid] == 0:
                    continue
                name = f"firehose_{mid}"
                metrics[f"{name}_count"] = float(counts[mid])
                metrics[f"{name}_sum"] = float(sums[mid])
                for label, value in zip(labels, pcts[mid]):
                    metrics[label % name] = float(value)
            pms = ProcessedMetricSet(
                time=_dt.datetime.now(tz=_dt.timezone.utc), metrics=metrics
            )
            payload = opentsdb_protocol(pms)
            if sink is not None:
                from loghisto_tpu.submitter import send_once

                err = send_once("tcp", sink, payload)
                status = "sent" if err is None else f"error: {err}"
            else:
                status = f"{len(payload)} bytes serialized"
        rec.record("firehose.interval", t_int_ns, time.perf_counter_ns())
        rate = interval_samples / (time.perf_counter() - t_int)
        out.write(
            f"interval {intervals}: {interval_samples:,} samples "
            f"({rate/1e6:.1f}M/s), export {status}\n"
        )
        out.flush()

    elapsed = time.perf_counter() - t_start
    summary = {
        "samples_per_s": total_samples / elapsed,
        "total_samples": total_samples,
        "intervals": intervals,
        "platform": jax.devices()[0].platform,
    }
    out.write(
        f"firehose: {summary['samples_per_s']/1e6:.1f}M samples/s over "
        f"{intervals} intervals on {summary['platform']}\n"
    )
    return summary


def main(argv=None) -> None:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--metrics", type=int, default=10_000)
    parser.add_argument("--batch", type=int, default=1 << 22)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--interval", type=float, default=1.0)
    parser.add_argument("--sink", default=None,
                        help="host:port OpenTSDB sink (optional)")
    parser.add_argument("--mesh", action="store_true",
                        help="run SPMD over all devices (psum merges)")
    parser.add_argument("--mesh-metric", type=int, default=1,
                        help="metric-axis size of the mesh")
    args = parser.parse_args(argv)
    sink = None
    if args.sink:
        host, port = args.sink.rsplit(":", 1)
        sink = (host, int(port))
    mesh = None
    if args.mesh:
        from loghisto_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(metric=args.mesh_metric)
    run_firehose(
        num_metrics=args.metrics, batch=args.batch, seconds=args.seconds,
        interval=args.interval, sink=sink, mesh=mesh,
    )


if __name__ == "__main__":
    main()
