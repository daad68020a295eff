"""Bit-parity validation of the Pallas ingest kernels vs the scatter path
ON REAL HARDWARE (non-interpret): run it on the chip, alone in its
process.

Prints PARITY OK / PARITY FAIL lines per kernel; exit code 0 iff all pass.
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(_os.path.abspath(__file__)))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.ops.ingest import make_ingest_fn
    from loghisto_tpu.ops.pallas_kernels import SAMPLE_TILE, make_pallas_row_ingest

    plat = jax.devices()[0].platform
    print(f"platform={plat} (interpret={'cpu' == plat})")

    import os

    cfg = MetricConfig(bucket_limit=4096)
    rng = np.random.default_rng(7)
    # full size on hardware; overridable so a CPU interpret-mode sanity
    # run finishes in seconds instead of tens of minutes
    n = int(os.environ.get("LOGHISTO_PARITY_N", 1 << 18))
    n = max(SAMPLE_TILE, n // SAMPLE_TILE * SAMPLE_TILE)
    # adversarial values: lognormal bulk + negatives + zeros + tiny + huge
    values = rng.lognormal(8, 4, n).astype(np.float32)
    values[: n // 8] *= -1.0
    values[n // 8 : n // 6] = 0.0
    values[n // 6 : n // 4] = rng.uniform(-0.6, 0.6, n // 4 - n // 6)
    values = np.ascontiguousarray(values)

    failures = 0

    # --- single-row pallas kernel vs scatter with all ids == 0 ---
    scatter = make_ingest_fn(cfg.bucket_limit)
    ids0 = np.zeros(n, dtype=np.int32)
    ref = scatter(jnp.zeros((1, cfg.num_buckets), jnp.int32), ids0, values)
    ref = np.asarray(ref)[0]
    row_fn = make_pallas_row_ingest(cfg.num_buckets, cfg.bucket_limit)
    got = np.asarray(row_fn(jnp.zeros(cfg.num_buckets, jnp.int32), values))
    if np.array_equal(ref, got):
        print(f"PARITY OK  pallas_row    n={n} sum={got.sum()}")
    else:
        bad = np.nonzero(ref != got)[0]
        print(f"PARITY FAIL pallas_row   {bad.size} cells differ, first={bad[:5]}")
        failures += 1

    # --- masked (ids, values) row form: ragged N + invalid-id drop ---
    from loghisto_tpu.ops.pallas_kernels import pallas_row_ingest_batch

    n_rag = n - SAMPLE_TILE // 2  # deliberately ragged
    ids_mix = rng.integers(-1, 3, n_rag).astype(np.int32)
    ref = np.asarray(scatter(
        jnp.zeros((1, cfg.num_buckets), jnp.int32), ids_mix,
        values[:n_rag],
    ))
    got = np.asarray(jax.jit(
        lambda a, i, v: pallas_row_ingest_batch(a, i, v, cfg.bucket_limit)
    )(jnp.zeros((1, cfg.num_buckets), jnp.int32), ids_mix, values[:n_rag]))
    if np.array_equal(ref, got):
        print(f"PARITY OK  pallas_masked n={n_rag} sum={got.sum()}")
    else:
        bad = np.nonzero(ref != got)
        print(f"PARITY FAIL pallas_masked {bad[0].size} cells differ")
        failures += 1

    # --- r13 fused sample->scatter kernel vs scatter oracle ---
    from loghisto_tpu.ops.fused_ingest import ROWS_TILE, make_fused_ingest_fn

    for m in (16, 1024, 10_000 // ROWS_TILE * ROWS_TILE):
        # ids straddle both droppable sides and every row-tile boundary
        ids = rng.integers(-2, m + 2, n).astype(np.int32)
        ids[:ROWS_TILE] = np.arange(ROWS_TILE)      # first tile, each row
        ids[ROWS_TILE:2 * ROWS_TILE] = m - 1        # last row
        ref = np.asarray(
            scatter(jnp.zeros((m, cfg.num_buckets), jnp.int32), ids, values)
        )
        fused = make_fused_ingest_fn(cfg.bucket_limit)
        got = np.asarray(
            fused(jnp.zeros((m, cfg.num_buckets), jnp.int32), ids, values)
        )
        if np.array_equal(ref, got):
            print(f"PARITY OK  fused m={m:<5} sum={got.sum()}")
        else:
            bad = np.nonzero(ref != got)
            print(f"PARITY FAIL fused m={m} {bad[0].size} cells differ")
            failures += 1

    # fused two-step accumulation through the donated alias
    m = 64
    ids = rng.integers(0, m, n).astype(np.int32)
    ref = scatter(jnp.zeros((m, cfg.num_buckets), jnp.int32), ids, values)
    ref = np.asarray(scatter(ref, ids[::-1].copy(), values))
    fused = make_fused_ingest_fn(cfg.bucket_limit)
    acc = fused(jnp.zeros((m, cfg.num_buckets), jnp.int32), ids, values)
    got = np.asarray(fused(acc, ids[::-1].copy(), values))
    if np.array_equal(ref, got):
        print(f"PARITY OK  fused-2step m={m} sum={got.sum()}")
    else:
        print("PARITY FAIL fused-2step")
        failures += 1

    # fused empty batch (grid degenerates to the single filler tile)
    got = np.asarray(fused(
        jnp.zeros((m, cfg.num_buckets), jnp.int32),
        np.zeros(0, np.int32), np.zeros(0, np.float32),
    ))
    if got.sum() == 0:
        print("PARITY OK  fused-empty")
    else:
        print("PARITY FAIL fused-empty")
        failures += 1

    print(f"pallas parity: {'ALL OK' if not failures else f'{failures} FAILURES'}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
