"""Headline benchmark: histogram ingest+aggregation throughput at 10k
metrics on one chip (BASELINE.json: "histogram samples/sec/chip at 10k
metrics; p99 percentile-query latency").

Workload: batches of (metric_id, value) samples, Zipf-skewed across 10k
metric names (BASELINE.json configs[1]), pushed through the framework's
default (auto-dispatched) fused compress->accumulate ingest kernel into
the dense int32[10k, 8193] bucket tensor, with a full statistics
extraction (counts/sums/9 percentiles — the
PrintBenchmark percentile set) once per simulated interval.  Batches are
pre-staged on device: the measured path is the aggregation kernel, the
host->device transfer story is measured separately by the firehose bench
(future work, SURVEY.md §7 hard part (a)).

Baseline: the Go reference demonstrates ~2.017e7 samples/s/process through
its hot path (readme.md:27,34; BASELINE.md) — vs_baseline is against that.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""

from __future__ import annotations

import json
import time

import numpy as np

from loghisto_tpu.utils.compile_cache import enable_compile_cache

BASELINE_SAMPLES_PER_S = 2.017e7

# Plausibility guard: the bench must be INCAPABLE of reporting garbage.
# Every aggregated sample is at minimum a read-modify-write of one int32
# accumulator cell (8 bytes of memory traffic) plus its (id, value)
# operand reads (8 bytes) once the accumulator overflows VMEM — so
# samples/s is bounded by peak memory bandwidth over bytes/sample.  A
# measured rate above the cap is physically impossible and means the
# timing was broken (e.g. a backend acking before execution), NOT that
# the kernel is fast.  Keyed by ``jax.Device.device_kind``; a device
# kind that is not here is an error, not a default.
PEAK_BYTES_PER_S = {
    # Google Cloud documentation, "TPU v5e": 819 GB/s HBM per chip
    "TPU v5 lite": 819e9,
    # host DRAM: a generous ceiling (no CPU socket streams 400 GB/s)
    "cpu": 4e11,
}
_VMEM_BYTES = 128 * 1024 * 1024


def peak_bytes_per_s(device_kind: str) -> float:
    """Peak memory bandwidth of a device kind; unknown kinds raise."""
    if device_kind not in PEAK_BYTES_PER_S:
        raise ValueError(
            f"no peak memory bandwidth on record for device kind "
            f"{device_kind!r}; add it to bench.PEAK_BYTES_PER_S with its "
            "source"
        )
    return PEAK_BYTES_PER_S[device_kind]


def plausibility_cap_samples_per_s(device_kind: str, acc_bytes: int) -> float:
    """Upper bound on credible samples/s for this accumulator size."""
    # accumulator resident in VMEM/cache: only the RMW traffic is forced;
    # larger accumulators also stream operands through HBM
    bytes_per_sample = 8 if acc_bytes <= _VMEM_BYTES else 16
    return peak_bytes_per_s(device_kind) / bytes_per_sample


NUM_METRICS = 10_000
BUCKET_LIMIT = 4_096
BATCH = 1 << 22  # 4.2M samples per step
# Looped-interval mode (TPU): ROUNDS passes over DISTINCT_BATCHES
# pre-staged batches inside ONE jit dispatch, stats once at the end.
# Distinct batches stop XLA hoisting the compress as loop-invariant;
# the big loop makes device time dominate dispatch latency.
DISTINCT_BATCHES = 8
ROUNDS = 128  # 8 x 128 x 4.2M = 4.3G samples per timed dispatch


def _resolve_ingest_step(cfg, platform: str):
    """The pure per-batch accumulation function the framework would pick
    by default for this configuration (TPUAggregator(ingest_path="auto")
    resolves through the same table) — the headline measures what a user
    of the default path actually gets, not a hardwired kernel.  Override
    with LOGHISTO_BENCH_PATH=scatter|sort|hybrid for comparisons."""
    import os

    from loghisto_tpu.ops.dispatch import ingest_step_fn, resolve_ingest_path
    from loghisto_tpu.parallel.aggregator import DEFAULT_GROWTH_FACTOR

    # mirror the default TPUAggregator's resolve call exactly (its growth
    # cap, chunks of batch_size) so the benchmarked kernel can never
    # drift from the kernel the default-configured product picks
    path = resolve_ingest_path(
        os.environ.get("LOGHISTO_BENCH_PATH") or "auto",
        NUM_METRICS, cfg.num_buckets, platform,
        guard_metrics=NUM_METRICS * DEFAULT_GROWTH_FACTOR, batch_size=BATCH,
    )
    return path, ingest_step_fn(path)


def measure_headline(jax, jnp, cfg, ps, rounds: int | None = None) -> dict:
    """Device-resident headline: samples/s + stats-query latency."""
    import jax.numpy  # noqa: F401 (jnp passed in)

    from loghisto_tpu.ops.stats import dense_stats

    platform = jax.devices()[0].platform
    path, ingest_batch = _resolve_ingest_step(cfg, platform)

    # rounds=None -> adaptive: probe with one round, then size the real
    # measurement to ~20s of device time (capped at ROUNDS), so a slow
    # kernel (the serialized scatter runs ~9M/s at 10k metrics) cannot
    # make one dispatch outlive the 420s watchdog

    rng = np.random.default_rng(0)
    ids8 = jax.device_put(np.stack([
        zipf_ids(rng, BATCH, NUM_METRICS) for _ in range(DISTINCT_BATCHES)
    ]))
    values8 = jax.device_put(np.stack([
        rng.lognormal(10.0, 2.0, BATCH).astype(np.float32)
        for _ in range(DISTINCT_BATCHES)
    ]))

    stats = jax.jit(
        lambda acc: dense_stats(acc, ps, cfg.bucket_limit, cfg.precision)
    )

    def make_interval(n_rounds):
        @jax.jit
        def interval(acc, ids8, values8):
            def body(i, a):
                ids = jax.lax.dynamic_index_in_dim(
                    ids8, i % DISTINCT_BATCHES, keepdims=False
                )
                values = jax.lax.dynamic_index_in_dim(
                    values8, i % DISTINCT_BATCHES, keepdims=False
                )
                return ingest_batch(a, ids, values, cfg.bucket_limit,
                                    cfg.precision)
            acc = jax.lax.fori_loop(
                0, DISTINCT_BATCHES * n_rounds, body, acc
            )
            return acc, dense_stats(acc, ps, cfg.bucket_limit,
                                    cfg.precision)
        return interval

    # Timing ends at a host-side VALUE fetched from the result: fetching
    # the stats counts (40KB) cannot complete before the work that
    # produced them, whatever the backend acks early.
    def timed(n_rounds, acc):
        fn = make_interval(n_rounds)
        acc, s = fn(acc, ids8, values8)  # compile + warm
        np.asarray(s["counts"])
        t0 = time.perf_counter()
        acc, s = fn(acc, ids8, values8)
        counts_host = np.asarray(s["counts"])
        elapsed = time.perf_counter() - t0
        assert counts_host.sum() > 0
        return elapsed, acc

    acc = jnp.zeros((NUM_METRICS, cfg.num_buckets), dtype=jnp.int32)
    if rounds is None:
        probe_elapsed, acc = timed(1, acc)
        per_round = probe_elapsed  # upper bound (includes latency)
        rounds = max(1, min(ROUNDS, int(20.0 / per_round)))
    if rounds > 1:
        elapsed, acc = timed(rounds, acc)
    else:
        elapsed, acc = timed(1, acc)
        rounds = 1
    samples = DISTINCT_BATCHES * rounds * BATCH
    samples_per_s = samples / elapsed

    lat = []
    for _ in range(20):
        t1 = time.perf_counter()
        np.asarray(stats(acc)["counts"])  # value fetch, same reason
        lat.append(time.perf_counter() - t1)
    return {
        "samples_per_s": samples_per_s,
        "elapsed_s": elapsed,
        "samples": samples,
        "ingest_path": path,
        "percentile_query_p99_us": float(np.percentile(lat, 99) * 1e6),
        "percentile_query_median_us": float(np.median(lat) * 1e6),
    }


def zipf_ids(rng: np.random.Generator, n: int, m: int) -> np.ndarray:
    """Zipf-skewed metric ids in [0, m): a few hot metrics, long tail."""
    raw = rng.zipf(1.3, size=n)
    return ((raw - 1) % m).astype(np.int32)


def _cpu_calibration() -> float:
    """Fixed-workload host-speed index (MB/s of a NumPy reduction over a
    256 MB buffer).  This shared host's effective CPU speed swings >2x
    between rounds (round-5 measured the same bench at 36-80 M samples/s
    hours apart with identical code); CPU-fallback numbers are only
    comparable ACROSS rounds at similar calibration values."""
    buf = np.ones(1 << 25, dtype=np.float64)  # 256 MB
    t0 = time.perf_counter()
    s = 0.0
    for _ in range(4):
        s += float(buf.sum())
    dt = time.perf_counter() - t0
    assert s > 0
    return round(4 * buf.nbytes / dt / 1e6, 1)


def _start_watchdog(timeout_s: float = 420.0, on_timeout=None):
    """Fail loudly if device work wedges: after timeout_s without the ready flag, dump stacks to
    stderr and exit.  `on_timeout` (optional) runs first — used to salvage
    an already-computed result line before exiting; when it prints one,
    the exit code is 0 so the driver records the partial result."""
    import threading

    ready = threading.Event()

    def watch():
        if not ready.wait(timeout=timeout_s):
            import faulthandler
            import sys

            print(
                f"bench: device work exceeded {timeout_s}s; aborting",
                file=sys.stderr,
            )
            faulthandler.dump_traceback(file=sys.stderr)
            import os

            if on_timeout is not None:
                try:
                    on_timeout()
                    os._exit(0)
                except Exception:
                    pass
            os._exit(3)

    threading.Thread(target=watch, daemon=True).start()
    return ready


def _preflight_analyzer(timeout_s: float = 240.0) -> None:
    """Refuse to publish a BENCH artifact from a tree that fails its own
    static contract analyzer: a number measured on a program whose
    dispatch/donation/layout contracts are broken is not comparable to
    any other round's.  ``LOGHISTO_SKIP_PREFLIGHT=1`` is the escape
    hatch; analyzer *environment* failures (timeout, missing interpreter
    features) degrade to a warning rather than blocking the bench."""
    import os
    import subprocess
    import sys

    if os.environ.get("LOGHISTO_SKIP_PREFLIGHT"):
        print("bench: static-analysis preflight skipped via "
              "LOGHISTO_SKIP_PREFLIGHT", file=sys.stderr)
        return
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "loghisto_tpu.analysis"],
            capture_output=True, text=True, timeout=timeout_s,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
    except (subprocess.TimeoutExpired, OSError) as exc:
        print(f"bench: static-analysis preflight inconclusive ({exc}); "
              "continuing", file=sys.stderr)
        return
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise SystemExit(
            "bench: static contract analyzer failed — refusing to "
            "publish a BENCH artifact from a failing tree "
            "(set LOGHISTO_SKIP_PREFLIGHT=1 to override)"
        )


def main() -> None:
    import os
    import sys

    import jax

    # the analyzer child runs on the CPU before this process touches a
    # device: one process holds the chip at a time
    _preflight_analyzer()
    enable_compile_cache()
    ready = _start_watchdog()

    import jax.numpy as jnp

    from loghisto_tpu.config import MetricConfig

    cfg = MetricConfig(bucket_limit=BUCKET_LIMIT)
    ps = np.array(
        [0.0, 0.5, 0.75, 0.9, 0.95, 0.99, 0.999, 0.9999, 1.0],
        dtype=np.float32,
    )

    dev = jax.devices()[0]
    platform = dev.platform
    # refuse an unknown device before spending minutes measuring on it
    plausibility_cap_samples_per_s(dev.device_kind, 0)

    head = measure_headline(jax, jnp, cfg, ps)
    ready.set()  # device is alive and the workload ran; disarm watchdog
    samples_per_s = head["samples_per_s"]

    acc_bytes = NUM_METRICS * cfg.num_buckets * 4
    cap = plausibility_cap_samples_per_s(dev.device_kind, acc_bytes)
    suspect = samples_per_s > cap
    if suspect:
        print(
            f"bench: measured {samples_per_s:.3e} samples/s exceeds the "
            f"{platform} HBM-roofline cap {cap:.3e} for a {acc_bytes} byte "
            f"accumulator; refusing to report it as the headline",
            file=sys.stderr,
        )

    result = {
        "metric": "histogram samples/sec/chip at 10k metrics",
        # a physically impossible rate is withheld, not laundered: the
        # headline goes null, the raw measurement stays inspectable
        "value": None if suspect else round(samples_per_s, 1),
        "suspect": suspect,
        "measured_samples_per_s": round(samples_per_s, 1),
        "plausibility_cap_samples_per_s": round(cap, 1),
        "unit": "samples/s",
        "vs_baseline": (
            None if suspect
            else round(samples_per_s / BASELINE_SAMPLES_PER_S, 3)
        ),
        "percentile_query_p99_us": round(head["percentile_query_p99_us"], 1),
        "percentile_query_median_us": round(
            head["percentile_query_median_us"], 1
        ),
        "host_fed_samples_per_s": None,
        "ingest_path": head["ingest_path"],
        "platform": platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "batch": BATCH,
        "samples_per_interval": head["samples"],
        "num_metrics": NUM_METRICS,
        "num_buckets": cfg.num_buckets,
        # host-speed index for cross-round comparability of CPU numbers
        # (this shared host swings >2x; see _cpu_calibration)
        "cpu_calibration_mb_s": _cpu_calibration(),
    }

    # host-fed sustained rate through the full record_batch -> device
    # pipeline (samples cross host memory; the headline number above is
    # device-resident).  A second watchdog guards this stage: if the
    # device wedges mid-run, salvage the already-computed headline line
    # instead of hanging the driver with nothing printed.
    ready2 = _start_watchdog(300.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.h2d_bench import sweep as h2d_sweep

        # sweep all three concrete transports on the identical load and
        # report the best — which transport wins is box-dependent (host
        # fold speed vs PCIe width), so a fixed pick would pin the
        # number to one machine class
        h2d = h2d_sweep(num_metrics=NUM_METRICS, seconds=2.5, batch=1 << 20)
        best = h2d["best_transport"]
        if best is not None:
            line = h2d["transports"][best]
            result["host_fed_samples_per_s"] = line["value"]
            result["host_fed_transport"] = best
            result["host_fed_bytes_per_sample"] = line["bytes_per_sample"]
        result["host_fed_sweep"] = {
            t: {
                "samples_per_s": line["value"],
                "bytes_per_sample": line["bytes_per_sample"],
                "wire_mb_per_s": line["wire_mb_per_s"],
            }
            for t, line in h2d["transports"].items()
        }
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: host-fed stage failed: {e}", file=sys.stderr)
    ready2.set()

    # windowed query-engine latencies at the 10k point (snapshot-served
    # retention queries; benchmarks/query_engine.py has the full grid):
    # cold = first query after a commit (one sparse gather dispatch),
    # warm = repeat query at an unchanged epoch (host cache, zero
    # dispatch), sparse = one-metric query reading back O(P) floats.
    ready3 = _start_watchdog(300.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.query_engine import run as query_run

        q10k = query_run(reps=10)["configs"]["10000"]
        result["query_cold_full_glob_p99_us"] = (
            q10k["snapshot_dispatch_full_glob"]["p99_us"]
        )
        result["query_warm_full_glob_p99_us"] = (
            q10k["snapshot_warm_cached_full_glob"]["p99_us"]
        )
        result["query_sparse_one_metric_p99_us"] = (
            q10k["snapshot_dispatch_one_metric"]["p99_us"]
        )
        result["query_speedup_warm_cached"] = q10k["speedup_warm_cached"]
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: query-engine stage failed: {e}", file=sys.stderr)
    ready3.set()

    # lifecycle-under-churn headline (benchmarks/cardinality_churn.py has
    # the 1k/16k/100k grid): commit p99 while evicting/compacting, the
    # bounded-rows claim, and the repack cost at the 16k point.
    ready4 = _start_watchdog(300.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.cardinality_churn import run as churn_run

        c16k = churn_run(configs=["16000"])["configs"]["16000"]
        result["churn_commit_p99_us"] = c16k["commit_latency"]["p99_us"]
        result["churn_bounded_by_live_budget"] = (
            c16k["bounded_by_live_budget"]
        )
        result["churn_evicted_series"] = c16k["evicted_series"]
        result["churn_compaction_p99_us"] = (
            c16k["compaction_latency"]["p99_us"]
            if c16k["compaction_latency"] else None
        )
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: cardinality-churn stage failed: {e}", file=sys.stderr)
    ready4.set()

    # drift-engine headline at the 10k point (benchmarks/anomaly_bench.py
    # has the 1/16/10k grid): EWMA ride-along overhead on the fused
    # commit (zero extra dispatches) and the one divergence dispatch.
    ready5 = _start_watchdog(300.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.anomaly_bench import run as anomaly_run

        a10k = anomaly_run(reps=10, configs=["10000"])["configs"]["10000"]
        result["drift_ewma_overhead_pct"] = a10k["ewma_overhead_pct"]
        result["drift_ewma_extra_dispatches"] = (
            a10k["ewma_extra_dispatches"]
        )
        result["drift_score_p99_us"] = a10k["divergence_score"]["p99_us"]
        result["drift_score_ns_per_row"] = a10k["divergence_ns_per_row"]
        result["drift_score_suspect"] = a10k["suspect"]
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: anomaly stage failed: {e}", file=sys.stderr)
    ready5.set()

    # mesh-sharded fused commit headline (benchmarks/mesh_scale.py has
    # the full shape grid): sharded fused dispatches/interval and
    # committed samples/s vs the single-device fused path.  Runs in a
    # SUBPROCESS: the 8-virtual-device CPU mesh needs XLA_FLAGS set
    # before jax imports, which this process can no longer do.
    ready6 = _start_watchdog(360.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        import subprocess

        proc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmarks", "mesh_scale.py"),
             "--commit-only", "--commit-reps", "5"],
            capture_output=True, text=True, timeout=330.0,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"mesh_scale subprocess rc={proc.returncode}: "
                f"{proc.stderr[-500:]}"
            )
        shapes = json.loads(proc.stdout)["commit"]["shapes"]
        sharded = {
            k: v for k, v in shapes.items()
            if k != "single" and not v["suspect"]
        }
        if sharded:
            best_key = max(
                sharded, key=lambda k: sharded[k]["measured_samples_per_s"]
            )
            line = sharded[best_key]
            result["mesh_commit_shape"] = best_key
            result["mesh_commit_dispatches_per_interval"] = (
                line["fused_dispatches_per_interval"]
            )
            result["mesh_commit_samples_per_s"] = line["fused_samples_per_s"]
            result["mesh_commit_vs_single_device"] = (
                line["fused_vs_single_device"]
            )
            result["mesh_commit_fanout_over_fused"] = (
                line["fanout_over_fused"]
            )
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: mesh-commit stage failed: {e}", file=sys.stderr)
    ready6.set()

    # self-observability headline (benchmarks/obs_overhead.py has the
    # full stage table): span-recorder throughput cost on the firehose
    # (< 2% budget) and the pipeline's own end-to-end commit p99 as
    # read from its span ring.
    ready7 = _start_watchdog(300.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.obs_overhead import run as obs_run

        obs = obs_run(reps=3, seconds=1.0)
        result["obs_overhead_pct"] = obs["obs_overhead_pct"]
        result["obs_overhead_suspect"] = obs["suspect"]
        result["pipeline_stage_p99_us"] = obs["pipeline_stage_p99_us"]
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: obs-overhead stage failed: {e}", file=sys.stderr)
    ready7.set()

    # crash-recovery headline (benchmarks/recovery_bench.py has the
    # full durability table): wall time to restore a checkpoint and
    # replay the journal suffix through the real commit path, and the
    # commit-loop cost of the chaos hook points with no injector
    # attached (< 1% budget; measured via an attached-but-idle
    # injector, a strict upper bound on the disabled None check).
    ready8 = _start_watchdog(300.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.recovery_bench import run as recovery_run

        rcv = recovery_run(reps=3, intervals=32, commits=60)
        result["recovery_time_ms"] = rcv["recovery_time_ms"]
        result["faults_disabled_overhead_pct"] = (
            rcv["faults_disabled_overhead_pct"]
        )
        result["recovery_suspect"] = rcv["suspect"]
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: recovery stage failed: {e}", file=sys.stderr)
    ready8.set()

    # federation fan-in headline (benchmarks/federation_bench.py has
    # the 1/8/32-emitter x 1k/10k-metric grid): end-to-end samples/s
    # from many emitter frontends through TCP framing + seq dedup +
    # interning into the aggregator, and receiver-side wire cost per
    # sample.
    ready9 = _start_watchdog(300.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.federation_bench import run as federation_run

        fed = federation_run(
            emitter_counts=(8,), metric_counts=(10_000,),
            samples_per_cell=1 << 17,
        )
        result["federation_ingest_sps"] = fed["federation_ingest_sps"]
        result["federation_bytes_per_sample"] = (
            fed["federation_bytes_per_sample"]
        )
        result["federation_suspect"] = fed["suspect"]
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: federation stage failed: {e}", file=sys.stderr)
    ready9.set()

    # fleet-observability headline (benchmarks/fleet_obs_bench.py has
    # the per-round table): fan-in throughput cost of wire-v2 stamps +
    # health piggyback + receiver freshness/rollup accounting at 32
    # emitters (< 2% budget, roofline-guarded), and the end-to-end
    # record->queryable p99 from an interval-paced fleet.
    ready10 = _start_watchdog(300.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.fleet_obs_bench import run as fleet_obs_run

        fo = fleet_obs_run(samples_per_cell=1 << 18, repeats=3)
        result["fleet_obs_overhead_pct"] = fo["fleet_obs_overhead_pct"]
        result["fleet_freshness_p99_us"] = fo["fleet_freshness_p99_us"]
        result["fleet_obs_suspect"] = fo["suspect"]
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: fleet-obs stage failed: {e}", file=sys.stderr)
    ready10.set()

    # fused-ingest headline (benchmarks/fused_ingest_bench.py has the
    # crossover sweep and full shape): the r13 one-dispatch
    # sample->scatter kernel's samples/s, and the double-buffered
    # upload/compute overlap as attributed by the aggregator's own
    # ingest.upload/ingest.dispatch span streams.  On CPU the kernel is
    # interpret-mode (calibration only, orders slower than Mosaic), so
    # the shape shrinks to keep the stage bounded; a --tpu capture
    # reruns the bench at the 10k-metric headline shape.
    ready11 = _start_watchdog(300.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.fused_ingest_bench import run as fused_run
        from benchmarks.fused_ingest_bench import run_overlap

        if platform == "tpu":
            fu = fused_run(reps=3)
        else:
            fu = fused_run(num_metrics=1024, bucket_limit=512,
                           batch=1 << 16, reps=2)
        result["fused_ingest_sps"] = fu["fused"]["samples_per_s"]
        result["fused_ingest_suspect"] = fu["fused"]["suspect"]
        result["fused_ingest_interpret"] = fu["pallas_interpret"]
        result["fused_over_scatter"] = fu["fused_over_scatter"]
        ov = run_overlap(rounds=2)
        result["ingest_overlap_pct"] = ov["ingest_overlap_pct"]
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: fused-ingest stage failed: {e}", file=sys.stderr)
    ready11.set()

    # FUSED_MIN_BATCH calibration (r17 satellite): measure the fused
    # kernel's batch-size crossover on THIS platform and write it into
    # the committed dispatch thresholds file, platform-scoped — the
    # r13 CPU-interpret sweep must never set the TPU default.  A sweep
    # that finds no crossover (interpret-mode CPU: the fused kernel
    # never beats scatter) writes nothing; the baked fallback stands.
    ready11b = _start_watchdog(300.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.fused_ingest_bench import (
            derive_fused_min_batch, run_crossover, write_fused_min_batch,
        )

        if platform == "tpu":
            cx = run_crossover(reps=3)
        else:
            cx = run_crossover(num_metrics=1024, bucket_limit=512,
                               batches=(1 << 14, 1 << 16), reps=1)
        result["fused_min_batch_crossover"] = cx["measured_crossover_batch"]
        update = derive_fused_min_batch(cx)
        if update is not None:
            path = write_fused_min_batch(
                update, source=f"bench.py crossover sweep ({platform})"
            )
            result["fused_min_batch_written"] = path
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: fused-min-batch stage failed: {e}", file=sys.stderr)
    ready11b.set()

    # paged-storage headline (benchmarks/paged_store.py has the full
    # three-config wire comparison and the 1M-row HBM math): commit H2D
    # bytes per interval under the r14 paged backend at the largest wire
    # point, and live metric rows per GiB of pool+table HBM from measured
    # page occupancy.  Wire bytes come from transport accounting, not
    # wall clocks, so interpret-mode CPU runs report the same numbers a
    # TPU capture would; the row count shrinks off-TPU to bound runtime.
    ready12 = _start_watchdog(300.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.paged_store import run as paged_run

        if platform == "tpu":
            pg = paged_run(wire_rows=(10_000, 100_000))
        else:
            pg = paged_run(wire_rows=(25_000,), occupancy_rows=25_000)
        result["paged_h2d_bytes_per_interval"] = (
            pg["paged_h2d_bytes_per_interval"]
        )
        result["paged_h2d_reduction"] = pg["h2d_reduction"]
        result["max_live_rows_per_gib"] = pg["max_live_rows_per_gib"]
        result["paged_1m_rows_fit_one_chip"] = (
            pg["one_million_rows"]["fits_one_chip"]
        )
        result["paged_suspect"] = pg["suspect"]
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: paged-storage stage failed: {e}", file=sys.stderr)
    ready12.set()

    # direct-to-paged fused ingest headline (benchmarks/
    # fused_paged_bench.py has the mesh resolution table and the
    # two-stage comparison): the r17 one-dispatch
    # compress->encode->translate->scatter route's samples/s against the
    # pool's HBM-RMW roofline, and the paged-path interval dispatch
    # budget.  On CPU the Pallas scatter tier is interpret-mode
    # (seconds per dispatch), so the shape shrinks and the fraction
    # only calibrates the pipeline; a --tpu capture reruns the full
    # shape.
    ready12b = _start_watchdog(600.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.fused_paged_bench import run as fused_paged_run

        if platform == "tpu":
            fpd = fused_paged_run(num_metrics=1 << 16, bucket_limit=4096,
                                  batch=1 << 20, reps=3)
        else:
            fpd = fused_paged_run(num_metrics=1024, bucket_limit=512,
                                  batch=1 << 14, reps=2, pool_pages=4096)
        result["fused_paged_sps"] = fpd["fused"]["samples_per_s"]
        result["paged_roofline_fraction"] = (
            None if fpd["fused"]["suspect"]
            else fpd["fused"]["roofline_fraction"]
        )
        result["fused_paged_suspect"] = fpd["fused"]["suspect"]
        result["fused_paged_interpret"] = fpd["pallas_interpret"]
        result["fused_paged_over_two_stage"] = fpd["fused_over_two_stage"]
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: fused-paged stage failed: {e}", file=sys.stderr)
    ready12b.set()

    # label-serving headline (benchmarks/query_serving.py has the full
    # closed-loop table): sustained selector QPS and serve p99 under
    # live commits + label churn at the 10k-row shape, 8 query threads,
    # with the zero-stale-serve check folded into meets_slo.  Duration
    # shrinks off-TPU; a --tpu capture reruns the full grid.
    ready13 = _start_watchdog(300.0, on_timeout=lambda: print(
        json.dumps(result), flush=True
    ))
    try:
        from benchmarks.query_serving import run as serving_run

        qs = serving_run(duration=2.0 if platform == "tpu" else 1.0)
        result["query_serving_qps"] = qs["query_serving_qps"]
        result["query_serve_p99_us"] = qs["query_serve_p99_us"]
        result["query_serving_meets_slo"] = qs["meets_slo"]
    except Exception as e:  # never let the extra metric kill the bench
        print(f"bench: query-serving stage failed: {e}", file=sys.stderr)
    ready13.set()

    print(json.dumps(result))


if __name__ == "__main__":
    main()
