#!/usr/bin/env python3
"""Drive loghisto_tpu's main path once on a TPU and check what comes out.

The deployment is BASELINE.json configs[1]: a 10,000-metric
``TPUMetricSystem`` on a 1 s interval, Zipf(1.3)-skewed firehose
histogram writes through ``record_batch`` plus per-call ``counter`` and
``histogram`` writes, the fused interval commit into two retention tiers,
and queries of each interval's output and of a window spanning several
intervals.  Everything is checked against a plain NumPy reference built
here from the same seeded samples: counts exact, every published
percentile within 1% of the raw samples' quantile (readme.md:5's
contract).  The script also fails if the pipeline logged an error, shed a
sample, left the fused commit, ran a kernel interpreted, or lost its
native host tier — each of those can keep counts right while the device
did not do the work.

    python chip_smoke.py [--seed N]    one chip, the whole main path
    python chip_smoke.py --chips 4     a 2x2 ("stream", "metric") mesh run
                                       compared with a one-device run

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a TPU
the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import logging
import math
import os
import sys
import time

import numpy as np

# Published percentiles of the interval output (config.DEFAULT_PERCENTILES
# labels) and the window query's percentiles.
INTERVAL_PERCENTILES = {
    "min": 0.0, "50": 0.5, "75": 0.75, "90": 0.9, "95": 0.95, "99": 0.99,
    "99.9": 0.999, "99.99": 0.9999, "max": 1.0,
}
WINDOW_PERCENTILES = (0.5, 0.99, 0.9999)
TOLERANCE = 0.01
# Histograms the pipeline records about itself (the committer's latency
# is one of its own log-bucketed metrics): they own rows too, and their
# values are timings, so no reference or cross-run comparison reads them.
SELF_METRICS = ("commit.LatencyUs",)


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Workload:
    """Sizes of one run.  The defaults are the deployment's; tests and
    CPU rehearsals shrink them."""

    num_metrics: int = 10_000
    host_histograms: int = 8      # names written per call via histogram()
    host_counters: int = 8        # names written per call via counter()
    intervals: int = 4
    samples_per_interval: int = 3 << 20   # record_batch samples
    host_writes_per_interval: int = 2_000
    retention: tuple = ((16, 1), (8, 16))
    bucket_limit: int = 4096      # MetricConfig() default: 8,193 buckets

    @property
    def firehose_metrics(self) -> int:
        return (self.num_metrics - self.host_histograms
                - self.host_counters - len(SELF_METRICS))


# -- the plain reference ----------------------------------------------------- #


def codec(values: np.ndarray, bucket_limit: int) -> np.ndarray:
    """round(100 * ln(1 + |v|)) with the sign of v, clipped to the dense
    bucket range — the log-bucket codec, written out independently of
    the package."""
    v = np.asarray(values, dtype=np.float64)
    mag = np.floor(100.0 * np.log1p(np.abs(v)) + 0.5)
    return np.clip(np.where(v < 0, -mag, mag), -bucket_limit, bucket_limit)


def rank_quantiles(ids, values, num_ids, ps):
    """Per id, the sample at rank max(ceil(p * n), 1) of its sorted
    samples — ``np.quantile(..., method="inverted_cdf")``, the rank rule
    a log-bucket histogram answers with.  Returns (counts [num_ids],
    quantiles [num_ids, len(ps)], NaN where an id has no samples)."""
    order = np.lexsort((values, ids))
    ids_s = ids[order]
    vals_s = np.asarray(values, dtype=np.float64)[order]
    counts = np.bincount(ids_s, minlength=num_ids)
    starts = np.searchsorted(ids_s, np.arange(num_ids))
    out = np.full((num_ids, len(ps)), np.nan)
    have = counts > 0
    for j, p in enumerate(ps):
        rank = np.maximum(np.ceil(p * counts), 1).astype(np.int64)
        out[have, j] = vals_s[starts[have] + rank[have] - 1]
    return counts, out


def check_against_numpy(ids, values, num_ids, ps) -> None:
    """rank_quantiles agrees with np.quantile on the busiest ids."""
    counts, q = rank_quantiles(ids, values, num_ids, ps)
    for i in np.argsort(counts)[-5:]:
        if counts[i]:
            want = np.quantile(
                np.asarray(values[ids == i], dtype=np.float64), ps,
                method="inverted_cdf",
            )
            check(np.array_equal(want, q[i]), "rank rule != np.quantile")


def bucket_disagreement(acc, ids, values, bucket_limit) -> int:
    """Samples the device put in another bucket than the reference codec
    (device and reference round the log in float32 and float64, so a
    value within an ulp of a bucket boundary may land either side).
    Fails unless every row's total is exact and at most 1e-4 of the
    samples moved."""
    b = 2 * bucket_limit + 1
    col = (codec(values, bucket_limit) + bucket_limit).astype(np.int64)
    want = np.bincount(ids.astype(np.int64) * b + col,
                       minlength=acc.size).reshape(acc.shape)
    check(np.array_equal(acc.sum(axis=1), want.sum(axis=1)),
          "per-metric bucket totals differ from the reference")
    moved = int(np.abs(acc.astype(np.int64) - want).sum()) // 2
    check(moved <= 1e-4 * len(ids),
          f"{moved} of {len(ids)} samples in another bucket than the "
          "reference codec's")
    return moved


def within(got: float, want: float) -> bool:
    return abs(got - want) <= TOLERANCE * abs(want)


# -- the workload ------------------------------------------------------------ #


def interval_data(w: Workload, rng: np.random.Generator):
    """One interval of writes: firehose (ids, values) for record_batch,
    per-call histogram (name index, value) and counter (name index,
    amount) writes."""
    n = w.samples_per_interval
    ids = ((rng.zipf(1.3, size=n) - 1) % w.firehose_metrics).astype(np.int32)
    # latencies in microseconds: >= 1 so the codec's 0.5% bucket width
    # stays inside the 1% contract at every percentile, min included
    values = (1.0 + rng.lognormal(7.0, 1.0, size=n)).astype(np.float32)
    h = w.host_writes_per_interval
    hist_idx = rng.integers(0, w.host_histograms, size=h)
    hist_val = 1.0 + rng.lognormal(5.0, 0.8, size=h)
    ctr_idx = rng.integers(0, w.host_counters, size=h // 2)
    ctr_amt = rng.integers(1, 10, size=h // 2)
    return ids, values, hist_idx, hist_val, ctr_idx, ctr_amt


def metric_names(w: Workload):
    fire = [f"fire.{i:05d}" for i in range(w.firehose_metrics)]
    hist = [f"rpc.latency.{i}" for i in range(w.host_histograms)]
    ctr = [f"rpc.requests.{i}" for i in range(w.host_counters)]
    return fire, hist, ctr


class ErrorLog(logging.Handler):
    """Collects every record of the package's loggers at ERROR or above."""

    def __init__(self):
        super().__init__(logging.ERROR)
        self.records: list[logging.LogRecord] = []

    def emit(self, record):
        self.records.append(record)


def run_system(w: Workload, seed: int, mesh=None, resilience=None,
               snapshot_acc: bool = False) -> dict:
    """Build the system, drive ``w.intervals`` intervals through it and
    check every output against the reference.  Returns what the caller
    prints (and, with ``snapshot_acc``, each interval's accumulator)."""
    import queue

    import jax

    from loghisto_tpu import TPUMetricSystem
    from loghisto_tpu.channel import Channel
    from loghisto_tpu.config import MetricConfig

    errors = ErrorLog()
    pkg_log = logging.getLogger("loghisto_tpu")
    pkg_log.addHandler(errors)
    rng = np.random.default_rng(seed)
    fire, hist, ctr = metric_names(w)
    ms = TPUMetricSystem(
        interval=1.0,
        num_metrics=w.num_metrics,
        config=MetricConfig(bucket_limit=w.bucket_limit),
        retention=w.retention,
        mesh=mesh,
        resilience=resilience,
        sys_stats=False,
    )
    agg, wheel, committer = ms.aggregator, ms.retention, ms.committer
    check(committer is not None,
          f"fused commit not resolved (commit path {ms.commit_path})")
    # every name owns a row up front: the per-call names commit into the
    # same registry, and the firehose ids are these rows
    for name in fire + hist + ctr + list(SELF_METRICS):
        ms.metric_id(name)

    def feed(ids, values, chunk=1 << 18):
        """record_batch in chunks, as a firehose client honouring the
        aggregator's backpressure: a chunk goes in only when the host
        buffer has room for it (past max_pending_samples the aggregator
        sheds the oldest samples, by design)."""
        deadline = time.monotonic() + 300.0
        for lo in range(0, len(ids), chunk):
            n = min(chunk, len(ids) - lo)
            while agg.pending_samples + n > agg.max_pending_samples:
                check(time.monotonic() < deadline,
                      "the device path stopped draining the host buffer")
                time.sleep(0.001)
            ms.record_batch(ids[lo:lo + n], values[lo:lo + n])

    # warm-up, unchecked: compiles the ingest and statistics programs
    # (the commit program compiled in committer.attach) outside the
    # intervals, then resets the accumulator
    warm = np.random.default_rng(seed + 1)
    feed(*interval_data(
        dataclasses.replace(w, samples_per_interval=agg.batch_size), warm
    )[:2])
    ms.device_metrics(reset=True)

    ch = Channel(64)
    ms.subscribe_to_processed_metrics(ch)
    ms.start()
    seen = 0

    def next_set(timeout=60.0):
        nonlocal seen
        pset = ch.get(timeout=timeout)
        seen += 1
        return pset

    ref = {"fire": [], "hist": [], "ctr": []}
    device_sets, channel_sets, accs = [], [], []
    t_first = None
    try:
        next_set()  # the subscription is live from this tick on
        for k in range(w.intervals):
            ids, values, hidx, hval, cidx, camt = interval_data(w, rng)
            # sync to a fresh tick so this interval's writes land in one
            while True:
                try:
                    ch.get(block=False)
                    seen += 1
                except queue.Empty:
                    break
            next_set()
            t_first = t_first or time.monotonic()
            for i, v in zip(hidx.tolist(), hval.tolist()):
                ms.histogram(hist[i], v)
            for i, a in zip(cidx.tolist(), camt.tolist()):
                ms.counter(ctr[i], a)
            feed(ids, values)
            pset = next_set()  # the tick that closed the per-call writes
            deadline = time.monotonic() + 120.0
            while committer.intervals_committed < seen:
                check(time.monotonic() < deadline,
                      "interval commit did not keep up with the ticks")
                time.sleep(0.01)
            if snapshot_acc:
                agg.flush(force=True)
                with agg._dev_lock:
                    accs.append(np.asarray(agg._finalize_acc(agg._acc)))
            device_sets.append(ms.device_metrics(reset=True))
            channel_sets.append(pset)
            ref["fire"].append((ids, values))
            ref["hist"].append((hidx, hval))
            ref["ctr"].append((cidx, camt))
        elapsed_ticks = seen
        # spans every interval of the run: the fine tier if it reaches
        # back that far, the coarse one otherwise
        window = float(math.ceil(time.monotonic() - t_first) + 2)
        check(window <= max(s * r for s, r in w.retention),
              f"the run outlasted the retention ({window} s)")
        wq = ms.query_window(
            "rpc.latency.*", window=window,
            percentiles=list(WINDOW_PERCENTILES),
        )
    finally:
        ms.stop()
        pkg_log.removeHandler(errors)

    # -- honesty ------------------------------------------------------------
    check(len(agg.registry.names()) == w.num_metrics
          and agg.num_metrics == w.num_metrics,
          f"registry grew past {w.num_metrics} rows: "
          f"{agg.registry.names()[w.num_metrics:][:5]}")
    check(not errors.records, "package logged errors: " + "; ".join(
        r.getMessage() for r in errors.records[:5]))
    shed = (agg._shed_samples, agg._registry_shed_samples,
            wheel.shed_samples)
    check(shed == (0, 0, 0), f"shed samples (aggregator, registry, wheel) "
          f"= {shed}")
    check(committer.fused_intervals >= w.intervals,
          f"fused commit ran {committer.fused_intervals} of "
          f"{w.intervals} intervals")
    check(committer.fanout_intervals == 0,
          f"{committer.fanout_intervals} intervals left the fused commit")

    # -- the reference ------------------------------------------------------
    ps = list(INTERVAL_PERCENTILES.values())
    worst = 0.0
    worst_buckets = 0.0
    ctotals = np.zeros(w.host_counters)
    for k in range(w.intervals):
        ids, values = ref["fire"][k]
        hidx, hval = ref["hist"][k]
        cidx, camt = ref["ctr"][k]
        if k == 0:
            check_against_numpy(ids, values, w.firehose_metrics, ps)
        dev = device_sets[k].metrics
        out = channel_sets[k].metrics
        # firehose rows: device output only (record_batch is the device
        # path); counts exact, every percentile within 1%
        counts, q = rank_quantiles(ids, values, w.firehose_metrics, ps)
        sums = np.bincount(ids, weights=np.asarray(values, np.float64),
                           minlength=w.firehose_metrics)
        if accs:
            moved = bucket_disagreement(
                accs[k][:w.firehose_metrics], ids, values, w.bucket_limit
            )
            worst_buckets = max(worst_buckets, moved / len(ids))
        for i in np.nonzero(counts)[0]:
            name = fire[i]
            check(dev.get(f"{name}_count") == counts[i],
                  f"interval {k} {name}_count {dev.get(name + '_count')} "
                  f"!= {counts[i]}")
            check(within(dev[f"{name}_sum"], sums[i]),
                  f"interval {k} {name}_sum {dev[name + '_sum']} vs "
                  f"{sums[i]}")
            for j, label in enumerate(INTERVAL_PERCENTILES):
                got = dev[f"{name}_{label}"]
                check(within(got, q[i, j]),
                      f"interval {k} {name}_{label} {got} vs {q[i, j]}")
                worst = max(worst, abs(got - q[i, j]) / q[i, j])
        check(not any(f"{fire[i]}_count" in dev
                      for i in np.nonzero(counts == 0)[0]),
              "a firehose metric with no samples reported a count")
        # per-call histograms: in the interval's processed set (host
        # tier) and, committed by the fused commit, in the device output
        hcounts, hq = rank_quantiles(hidx, hval, w.host_histograms, ps)
        for i, name in enumerate(hist):
            for src, got_set in (("channel", out), ("device", dev)):
                check(got_set.get(f"{name}_count") == hcounts[i],
                      f"interval {k} {src} {name}_count")
                for j, label in enumerate(INTERVAL_PERCENTILES):
                    got = got_set[f"{name}_{label}"]
                    check(within(got, hq[i, j]),
                          f"interval {k} {src} {name}_{label} {got} vs "
                          f"{hq[i, j]}")
                    worst = max(worst, abs(got - hq[i, j]) / hq[i, j])
        # a counter reports its running total and its interval increment
        cdelta = np.bincount(cidx, weights=camt, minlength=w.host_counters)
        ctotals = ctotals + cdelta
        for i, name in enumerate(ctr):
            check(out.get(name, 0.0) == ctotals[i],
                  f"interval {k} counter {name} {out.get(name)} != "
                  f"{ctotals[i]}")
            check(out.get(f"{name}_rate", 0.0) == cdelta[i],
                  f"interval {k} {name}_rate {out.get(name + '_rate')} "
                  f"!= {cdelta[i]}")
    # the window query: every per-call histogram sample of the run
    hidx_all = np.concatenate([h[0] for h in ref["hist"]])
    hval_all = np.concatenate([h[1] for h in ref["hist"]])
    wcounts, wq_ref = rank_quantiles(
        hidx_all, hval_all, w.host_histograms, list(WINDOW_PERCENTILES)
    )
    for i, name in enumerate(hist):
        stats = wq.metrics[name]
        check(stats["count"] == wcounts[i],
              f"window {name} count {stats['count']} != {wcounts[i]}")
        for j, p in enumerate(WINDOW_PERCENTILES):
            got = stats[_window_key(p)]
            check(within(got, wq_ref[i, j]),
                  f"window {name} p{p} {got} vs {wq_ref[i, j]}")
            worst = max(worst, abs(got - wq_ref[i, j]) / wq_ref[i, j])

    dev0 = jax.devices()[0]
    mem = dev0.memory_stats() or {}
    # the device layouts the accumulator and a ring were given (major to
    # minor): every Pallas kernel reads them row-major
    layouts = {
        "acc": _major_to_minor(agg._acc),
        "ring0": _major_to_minor(wheel._tiers[0].ring),
    }
    return {
        "paths": {
            "transport": agg.transport,
            "ingest": agg.ingest_path,
            "storage": agg.storage,
            "commit": ms.commit_path,
            "window_merge": wheel.merge_path,
        },
        "metrics": w.num_metrics,
        "buckets": 2 * w.bucket_limit + 1,
        "intervals": w.intervals,
        "ticks": elapsed_ticks,
        "fused_intervals": committer.fused_intervals,
        "firehose_samples": w.intervals * w.samples_per_interval,
        "per_call_writes": w.intervals * (
            w.host_writes_per_interval + w.host_writes_per_interval // 2),
        "window_s": window,
        "worst_percentile_rel_err": worst,
        "bucket_disagreement": worst_buckets if accs else None,
        "retention_hbm_bytes": wheel.hbm_bytes(),
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "layouts": layouts,
        "errors_logged": len(errors.records),
        "shed_samples": sum(shed),
        "state": (_per_device_bytes(agg._acc, wheel) if mesh is not None
                  else None),
        "accs": accs,
    }


def _window_key(p: float) -> str:
    """WindowStats key of a percentile: 0.99 -> "p99", 0.9999 ->
    "p99.99"."""
    return "p" + f"{p * 100:g}"


def _major_to_minor(arr):
    layout = getattr(getattr(arr, "format", None), "layout", None)
    return getattr(layout, "major_to_minor", None)


def _per_device_bytes(acc, wheel) -> dict:
    """Bytes each device holds of the accumulator and the tier rings."""
    out: dict = {}
    for arr in [acc] + [t.ring for t in wheel._tiers]:
        for shard in arr.addressable_shards:
            key = str(shard.device.id)
            out[key] = out.get(key, 0) + shard.data.nbytes
    return out


# -- phases ------------------------------------------------------------------ #


def one_chip(seed: int, w: Workload = Workload()) -> dict:
    res = run_system(w, seed, snapshot_acc=True)
    for key in ("paths", "metrics", "buckets", "intervals", "ticks",
                "fused_intervals", "firehose_samples", "per_call_writes",
                "window_s", "worst_percentile_rel_err", "bucket_disagreement",
                "retention_hbm_bytes", "peak_bytes_in_use", "layouts",
                "errors_logged", "shed_samples"):
        print(f"{key}: {res[key]}", flush=True)
    return res


def four_chips(seed: int, w: Workload = Workload()) -> dict:
    """The same workload on a 2x2 mesh through TPUMetricSystem(mesh=...),
    against a one-device run of the same seed in this process: each
    interval's bucket counts bit-identical (an int32 psum is
    order-independent), both checked against the reference, and the
    state split across all four devices."""
    from loghisto_tpu.parallel.mesh import make_mesh

    import jax

    single = run_system(w, seed, snapshot_acc=True)
    single_accs = single.pop("accs")
    # the one-device run's state must leave device 0 before the mesh run
    # (its rings alone are 7.9 GB): release every large array it left
    gc.collect()
    left = [a for a in jax.live_arrays() if a.nbytes >= 1 << 26]
    print(f"one-device run left {sum(a.nbytes for a in left)} bytes in "
          f"{len(left)} large live arrays; released", flush=True)
    for a in left:
        a.delete()
    mesh = make_mesh(stream=2, metric=2)
    sharded = run_system(w, seed, mesh=mesh, snapshot_acc=True)
    rows = w.num_metrics - len(SELF_METRICS)  # self-metrics are timings
    for k, (a, b) in enumerate(zip(single_accs, sharded.pop("accs"))):
        check(a.shape == b.shape and np.array_equal(a[:rows], b[:rows]),
              f"interval {k}: mesh bucket counts differ from one device")
    state = sharded["state"]
    total = sum(state.values())
    check(len(state) == 4 and all(v > 0 for v in state.values()),
          f"state not on all four devices: {state}")
    check(max(state.values()) <= total // 2,
          f"state not split over the metric axis: {state}")
    for key in ("paths", "metrics", "buckets", "intervals",
                "fused_intervals", "firehose_samples",
                "worst_percentile_rel_err", "bucket_disagreement",
                "errors_logged",
                "shed_samples"):
        print(f"mesh {key}: {sharded[key]}", flush=True)
    print(f"one-device paths: {single['paths']}", flush=True)
    print(f"buckets bit-identical to one device: {len(single_accs)} "
          "intervals", flush=True)
    print(f"per-device state bytes: {state}", flush=True)
    return sharded


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing "
              "to drive", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 2

    from loghisto_tpu import _native
    from loghisto_tpu.ops.backend import default_interpret
    from loghisto_tpu.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    check("LOGHISTO_FORCE_INTERPRET" not in os.environ,
          "LOGHISTO_FORCE_INTERPRET is set")
    check(not default_interpret(), "kernels would run interpreted")
    check(_native.available(),
          f"native host tier not loaded: {_native.build_error()}")
    print(f"device: {dev.device_kind} x{len(devices)}  seed: {args.seed}  "
          f"compile cache: {cache}  native: "
          f"{os.path.basename(_native._LIB_PATH)}", flush=True)
    t0 = time.monotonic()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(f"seconds: {time.monotonic() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
