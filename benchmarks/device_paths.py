"""Compare the device ingest paths (scatter vs sort-dedup vs MXU matmul
vs Pallas row/multirow) across metric counts — the tuning harness for
picking per-config fast paths on real hardware.

Two measurement modes:
  * per-dispatch (``--steps N``): N jit calls, block at the end; the
    wall time includes N dispatch latencies, so small batches rank the
    dispatch overhead as much as the kernels.
  * looped (``--loop-iters K``, default on TPU): ONE jit dispatch whose
    ``fori_loop`` body generates a fresh batch on device (same
    generator as the firehose) and ingests it, K times.  Device time
    dominates the single dispatch latency, so the ranking measures the
    kernels.

Usage: python benchmarks/device_paths.py [--batch 1048576] [--steps 8]
       [--loop-iters 16384] [--cpu]
"""

from __future__ import annotations

import argparse
import time

import numpy as np

# runnable from anywhere: add the repo root to sys.path
import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))


def _force_value(arr) -> None:
    """End-of-timing barrier that cannot lie: fetch a host VALUE derived
    from the result: it cannot complete before the device finished."""
    import numpy as _np

    _np.asarray(arr.reshape(-1)[:8])


def bench_fn(fn, acc, args, steps):
    out = fn(acc, *args)  # compile
    _force_value(out if not isinstance(out, tuple) else out[0])
    acc = out if not isinstance(out, tuple) else out[0]
    t0 = time.perf_counter()
    for _ in range(steps):
        acc = fn(acc, *args)
    _force_value(acc)
    return time.perf_counter() - t0


def make_looped(pure_step, m, batch, iters, needs_ids=True):
    """ONE jit program: fori_loop generating a fresh batch per iteration
    (firehose generator — Zipf-ish ids, lognormal values) and ingesting
    it.  `pure_step(acc, ids, values) -> acc` must be jit-traceable."""
    import jax
    import jax.numpy as jnp

    from loghisto_tpu.firehose import _make_sample_generator

    generate = _make_sample_generator(m, 10.0, 2.0)

    @jax.jit
    def run(acc, key):
        def body(_, carry):
            acc, key = carry
            key, sub = jax.random.split(key)
            ids, values = generate(sub, batch)
            if needs_ids:
                acc = pure_step(acc, ids, values)
            else:
                acc = pure_step(acc, values)
            return acc, key
        acc, key = jax.lax.fori_loop(0, iters, body, (acc, key))
        return acc

    return run


def bench_looped_adaptive(make_run, make_acc, target_s=3.0,
                          probe_iters=16, max_iters=8192):
    """Two-phase looped measurement: probe with a small loop, then size
    the real loop to ~target_s of device time.  A fixed big loop faulted
    the device on the r2d capture — the single-row scatter's duplicate
    serialization made one 8.6G-sample dispatch exceed the device
    execution deadline.  Returns (dt, iters)."""
    import jax

    key = jax.random.key(0)
    run = make_run(probe_iters)
    out = run(make_acc(), key)  # compile
    _force_value(out)
    t0 = time.perf_counter()
    out = run(out, key)
    _force_value(out)
    dt0 = time.perf_counter() - t0
    per_iter = dt0 / probe_iters  # upper bound (includes dispatch latency)
    iters = max(probe_iters, min(max_iters, int(target_s / per_iter)))
    if iters <= probe_iters * 2:
        return dt0, probe_iters
    run = make_run(iters)
    out = run(make_acc(), key)  # compile
    _force_value(out)
    t0 = time.perf_counter()
    out = run(out, key)
    _force_value(out)
    return time.perf_counter() - t0, iters


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch", type=int, default=1 << 20)
    parser.add_argument("--steps", type=int, default=8)
    parser.add_argument("--loop-iters", type=int, default=None,
                        help="looped mode: fori_loop iterations per "
                             "measurement (defaults to 16384 on TPU, "
                             "off on CPU)")
    parser.add_argument("--per-dispatch", action="store_true",
                        help="force the per-dispatch mode even on TPU")
    parser.add_argument("--bucket-limit", type=int, default=4096)
    parser.add_argument("--budget-s", type=float, default=1200.0,
                        help="wall-clock budget for the whole table; "
                             "remaining measurements are skipped (the "
                             "r2e capture lost 20+ min to one "
                             "pathological sort measurement)")
    parser.add_argument("--cpu", action="store_true")
    args = parser.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.ops.pallas_kernels import SAMPLE_TILE

    cfg = MetricConfig(bucket_limit=args.bucket_limit)
    rng = np.random.default_rng(0)
    n = args.batch // SAMPLE_TILE * SAMPLE_TILE
    platform = jax.devices()[0].platform
    loop_iters = args.loop_iters
    if loop_iters is None and platform == "tpu" and not args.per_dispatch:
        loop_iters = 16384
    looped = bool(loop_iters)
    mode = f"looped x{loop_iters}" if looped else f"per-dispatch x{args.steps}"
    print(f"platform={platform} batch={n} mode={mode} "
          f"buckets={cfg.num_buckets}")
    print(f"{'M':>6} {'path':>10} {'samples/s':>14}")

    # each path runs isolated: one path's lowering failure must not lose
    # the rest of the table (the r2_a1 capture lost scatter/matmul/sort
    # data to a single Pallas lowering rejection)
    results = {"platform": platform, "batch": n,
               "num_buckets": cfg.num_buckets,
               "mode": mode, "rates": {}, "errors": {}}

    class DeviceDead(RuntimeError):
        pass

    t_table = time.perf_counter()

    def record(m, name, fn):
        import traceback

        if time.perf_counter() - t_table > args.budget_s:
            results["errors"][f"{name}@{m}"] = "skipped: table budget spent"
            print(f"{m:>6} {name:>10} {'SKIPPED (budget)':>16}", flush=True)
            return
        try:
            dt, total = fn()
            rate = total / dt
            results["rates"][f"{name}@{m}"] = rate
            print(f"{m:>6} {name:>10} {rate:>14.3e}", flush=True)
        except Exception as e:
            results["errors"][f"{name}@{m}"] = (
                traceback.format_exc(limit=3).strip().splitlines()[-1]
            )
            print(f"{m:>6} {name:>10} {'FAILED: ' + type(e).__name__:>14}",
                  flush=True)
            # a faulted device fails everything after it — abort the
            # table instead of producing 15 more identical errors
            try:
                jax.block_until_ready(jnp.zeros(8) + 1)
            except Exception:
                results["errors"]["<aborted>"] = "device fault; table aborted"
                raise DeviceDead from e

    def measure(m, name, pure_step, jitted, acc, fn_args,
                needs_ids=True, make_acc=None):
        if looped:
            def make_run(iters):
                return make_looped(pure_step, m, n, iters,
                                   needs_ids=needs_ids)

            if make_acc is None:
                make_acc = (
                    (lambda: jnp.zeros(cfg.num_buckets, dtype=jnp.int32))
                    if not needs_ids
                    else (lambda: jnp.zeros((m, cfg.num_buckets),
                                            dtype=jnp.int32))
                )
            def run_adaptive():
                dt, iters = bench_looped_adaptive(
                    make_run, make_acc, max_iters=loop_iters
                )
                return dt, n * iters

            record(m, name, run_adaptive)
        else:
            record(m, name, lambda: (
                bench_fn(jitted, acc, fn_args, args.steps),
                n * args.steps,
            ))

    try:
        _run_table(args, cfg, rng, n, platform, looped, measure, results)
    except DeviceDead:
        pass
    return results


def _run_table(args, cfg, rng, n, platform, looped, measure, results):
    import jax
    import jax.numpy as jnp

    from loghisto_tpu.ops.ingest import ingest_batch
    from loghisto_tpu.ops.matmul_hist import (
        ingest_batch_matmul,
        make_matmul_ingest_fn,
    )
    from loghisto_tpu.ops.pallas_kernels import (
        make_pallas_row_ingest,
        pallas_histogram_row,
    )
    from loghisto_tpu.ops.ingest import make_ingest_fn
    from loghisto_tpu.ops.sort_ingest import (
        make_sort_ingest_fn,
        make_sortscan_ingest_fn,
        sort_ingest_batch,
        sortscan_ingest_batch,
    )

    values = rng.lognormal(8, 2, n).astype(np.float32)
    # 10k first: it is the headline-relevant row, and the wall-clock
    # budget skips whatever remains — losing M=16 beats losing M=10000
    # (the r2e capture spent its budget before reaching high cardinality)
    for m in (10_000, 1, 256, 16):
        ids = rng.integers(0, m, n).astype(np.int32)
        acc = jnp.zeros((m, cfg.num_buckets), dtype=jnp.int32)
        measure(m, "scatter",
                lambda a, i, v: ingest_batch(a, i, v, cfg.bucket_limit),
                make_ingest_fn(cfg.bucket_limit), acc, (ids, values))

        acc = jnp.zeros((m, cfg.num_buckets), dtype=jnp.int32)
        measure(m, "sort",
                lambda a, i, v: sort_ingest_batch(
                    a, i, v, cfg.bucket_limit),
                make_sort_ingest_fn(cfg.bucket_limit), acc, (ids, values))

        acc = jnp.zeros((m, cfg.num_buckets), dtype=jnp.int32)
        measure(m, "sortscan",
                lambda a, i, v: sortscan_ingest_batch(
                    a, i, v, cfg.bucket_limit),
                make_sortscan_ingest_fn(cfg.bucket_limit), acc,
                (ids, values))

        if m * cfg.num_buckets <= 1 << 23:
            acc = jnp.zeros((m, cfg.num_buckets), dtype=jnp.int32)
            measure(m, "matmul",
                    lambda a, i, v: ingest_batch_matmul(
                        a, i, v, cfg.bucket_limit),
                    make_matmul_ingest_fn(cfg.bucket_limit), acc,
                    (ids, values))

        if m == 1:
            row = jnp.zeros(cfg.num_buckets, dtype=jnp.int32)
            measure(m, "pallas",
                    lambda a, v: pallas_histogram_row(
                        a, v, cfg.bucket_limit),
                    make_pallas_row_ingest(cfg.num_buckets,
                                           cfg.bucket_limit),
                    row, (values,), needs_ids=False)

            # the masked (ids, values) form auto-dispatch actually picks
            from loghisto_tpu.ops.pallas_kernels import (
                pallas_row_ingest_batch,
            )

            acc = jnp.zeros((1, cfg.num_buckets), dtype=jnp.int32)
            measure(m, "pallasb",
                    lambda a, i, v: pallas_row_ingest_batch(
                        a, i, v, cfg.bucket_limit),
                    jax.jit(lambda a, i, v: pallas_row_ingest_batch(
                        a, i, v, cfg.bucket_limit), donate_argnums=0),
                    acc, (ids, values))

        if m >= 256:
            from loghisto_tpu.ops.hybrid_hist import (
                ingest_batch_hybrid,
                make_hybrid_ingest_fn,
            )

            acc = jnp.zeros((m, cfg.num_buckets), dtype=jnp.int32)
            measure(m, "hybrid",
                    lambda a, i, v: ingest_batch_hybrid(
                        a, i, v, cfg.bucket_limit),
                    make_hybrid_ingest_fn(cfg.bucket_limit), acc,
                    (ids, values))

        if m >= 16 and platform == "tpu":
            # metric-tiled pallas path (interpret mode is far too slow off
            # TPU, and the pltpu lowering only targets TPU)
            from loghisto_tpu.ops.pallas_multirow import make_multirow_ingest

            try:
                init, mingest, _ = make_multirow_ingest(
                    m, cfg.bucket_limit, rows_tile=8
                )
                # the jitted ingest inlines when traced inside the loop;
                # its accumulator is LANE-PADDED — init(), not the dense
                # shape the other paths use
                measure(m, "multirow", mingest, mingest, init(),
                        (ids, values), make_acc=init)
            except Exception as e:
                results["errors"][f"multirow@{m}"] = repr(e)
                print(f"{m:>6} {'multirow':>10} {'FAILED':>14}")
    return results


if __name__ == "__main__":
    main()
