"""Time the host fold (``_native.fold_packed``) on firehose-sized items.

Each item is shaped like ``rpc-fleet-10k``'s ``fire`` group
(``benchmark/configs/rpc-fleet-10k.json``): 2^21 samples, Zipf(1.3) ids
folded onto 9,983 rows, 1 + lognormal(7, 1) values, ``bucket_limit``
4096.  The script folds a pool of items in turn, as the transfer worker
does, and prints one JSON line: ms per item (median and quartiles over
``--reps`` folds after ``--warm``), rows per item and the items' distinct
(row, bucket) cells, with the thread count the fold used.  It imports no
JAX, so it measures the host alone.

Usage: python benchmarks/fold_bench.py [--samples 2097152] [--reps 40]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FIRE_ROWS = 9_983
BUCKET_LIMIT = 4096


def make_items(samples: int, pool: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    items = []
    for _ in range(pool):
        ids = ((rng.zipf(1.3, samples) - 1) % FIRE_ROWS).astype(np.int32)
        values = (1.0 + rng.lognormal(7.0, 1.0, samples)).astype(np.float32)
        items.append((ids, values))
    return items


def distinct_cells(ids: np.ndarray, values: np.ndarray) -> int:
    from loghisto_tpu import _native

    return len(_native.fold_packed_numpy(ids, values, BUCKET_LIMIT))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--samples", type=int, default=1 << 21)
    parser.add_argument("--pool", type=int, default=4)
    parser.add_argument("--warm", type=int, default=8)
    parser.add_argument("--reps", type=int, default=40)
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args()

    from loghisto_tpu import _native

    if not _native.available():
        raise SystemExit(f"native library unavailable: {_native.build_error()}")
    items = make_items(args.samples, args.pool, args.seed)
    for k in range(args.warm):
        _native.fold_packed(*items[k % args.pool], bucket_limit=BUCKET_LIMIT)
    ms, rows = [], []
    for k in range(args.reps):
        ids, values = items[k % args.pool]
        t0 = time.perf_counter()
        packed = _native.fold_packed(ids, values, bucket_limit=BUCKET_LIMIT)
        ms.append((time.perf_counter() - t0) * 1e3)
        rows.append(len(packed))
    q1, med, q3 = statistics.quantiles(ms, n=4)
    print(json.dumps({
        "samples_per_item": args.samples,
        "threads": min(8, os.cpu_count() or 1),
        "cpu_count": os.cpu_count(),
        "fold_ms_median": round(med, 3),
        "fold_ms_q1": round(q1, 3),
        "fold_ms_q3": round(q3, 3),
        "rows_per_item": statistics.median(rows),
        "distinct_cells_per_item": statistics.median(
            distinct_cells(*item) for item in items),
        # null on a tree whose fold does not count its table grows
        "table_grows_last_fold": (
            _native.fold_table_grows()
            if hasattr(_native, "fold_table_grows") else None),
    }))


if __name__ == "__main__":
    main()
