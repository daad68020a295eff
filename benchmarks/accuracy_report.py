"""Accuracy evidence: measured percentile error of every estimator across
distribution shapes, against exact np.quantile ground truth.

Usage: python benchmarks/accuracy_report.py  (writes markdown to stdout)
"""

from __future__ import annotations

import numpy as np

# runnable from anywhere: add the repo root to sys.path
import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

QS = np.array([0.5, 0.9, 0.99, 0.999, 0.9999], dtype=np.float32)
N = 200_000


def distributions(rng):
    yield "uniform(0,1000)", rng.uniform(0, 1000, N)
    yield "normal(100,15)", rng.normal(100, 15, N)
    yield "lognormal(5,2)", rng.lognormal(5, 2, N)
    yield "exponential(1e6)", rng.exponential(1e6, N)
    yield "pareto(a=1.5)x1e3", (rng.pareto(1.5, N) + 1) * 1e3
    yield "bimodal", np.concatenate(
        [rng.normal(10, 1, N // 2), rng.normal(1e4, 1e3, N // 2)]
    )


def main():
    import jax

    # accuracy is platform-independent; default to CPU unless the TPU is
    # explicitly requested
    if not _os.environ.get("LOGHISTO_REPORT_TPU"):
        jax.config.update("jax_platforms", "cpu")

    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.models import LogHistogram, moments, tdigest

    rng = np.random.default_rng(0)
    print("| distribution | estimator | " +
          " | ".join(f"p{q:g}" for q in QS) + " |")
    print("|---" * (len(QS) + 2) + "|")
    for label, data in distributions(rng):
        data = np.abs(data).astype(np.float32)  # latency-like
        truth = np.quantile(data, QS)

        # log-bucket histogram (the <=1% contract)
        h = LogHistogram.empty(MetricConfig(bucket_limit=4096))
        h = h.insert(data)
        hist_q = h.statistics(QS)["percentiles"]

        # t-digest (range-free)
        m, w = tdigest.empty()
        for chunk in np.array_split(data, 10):
            m, w = tdigest.insert(m, w, chunk)
        td_q = np.asarray(tdigest.quantile(m, w, QS))

        # moments (O(1) state)
        st = moments.empty()
        for chunk in np.array_split(data, 10):
            st = moments.insert(st, chunk)
        mo_q = np.asarray(moments.quantile(st, QS))

        for est, qvals in (
            ("loghist", hist_q), ("tdigest", td_q), ("moments", mo_q)
        ):
            errs = np.abs(qvals / np.maximum(truth, 1e-12) - 1)
            cells = " | ".join(f"{e:.2%}" for e in errs)
            print(f"| {label} | {est} | {cells} |")


if __name__ == "__main__":
    main()
