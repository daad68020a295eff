"""r14 paged bucket storage characterization: commit H2D bytes per
interval (dense default vs dense+explicit sparse transport vs paged)
and live-rows-per-GiB of HBM (the 1M-rows-per-chip budget math).

Two honest mechanisms, measured separately:

  * **Wire (H2D bytes/interval)** — the paged backend (on its r14
    host-fold route) PINS the packed sparse-triple transport, so every
    interval ships 12 bytes per *occupied cell*.  The dense default
    starts on the raw transport (8 bytes per *sample*); at the time of
    the r14 capture its one-shot density probe inspected only a
    64Ki-sample prefix, which at 100k+ live rows cannot see
    within-interval cell duplication (the prefix touches each cell at
    most ~once) — the probe read density ~0.9 and the dense default
    stayed raw for the whole run, shipping every duplicate sample.
    (r17 fixed that misread: the probe now folds unique cells over the
    WHOLE item, so a rerun of the 100k point switches the dense
    default to sparse and narrows the headline gap to roughly the
    explicitly-pinned line below.)  The dense aggregator CAN be pinned
    to the sparse transport explicitly; that line is reported too
    (wire parity with paged, up to commit padding), so the reduction
    is attributed to what the r14 storage resolver changes about the
    DEFAULT, not to hiding PR 6.
  * **HBM (live rows/GiB)** — dense spends ``B x 4`` bytes per row
    regardless of occupancy (8193 buckets -> 32 KiB/row, ~32.8k rows
    per GiB); the paged pool spends ~1 page per live sparse row plus
    132 B of page table.  Measured from a populated store's occupancy,
    then extrapolated to the 1M-row config against a simulated
    one-chip HBM budget.

Roofline-guarded like bench.py: measured commit samples/s above the
platform's HBM-RMW cap means broken timing, and the affected ratio is
reported with ``suspect: true`` instead of being laundered into a
headline.  Wire bytes come from the aggregators' own transport
accounting, not wall clocks, so they are timing-independent.

Usage: python benchmarks/paged_store.py [--out FILE]
Prints one JSON object (save as PAGED_STORE_r14.json); importable as
``run(...)`` for bench.py and tests.
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

# Wire measurements use a compact bucket axis: H2D bytes are
# bucket-count independent (raw ships 8 B/sample, triples 12 B/cell),
# and the 100k-row dense accumulator at the headline B=8193 would be
# 3.3 GB — pointless for a wire measurement.  HBM math uses the
# headline axis.
WIRE_BUCKET_LIMIT = 512
HBM_BUCKET_LIMIT = 4_096

SAMPLES_PER_ROW = 64   # ~1 sample/s per metric over a 60s interval
BUCKETS_PER_ROW = 4    # tight latency band: adjacent log buckets

# Simulated one-chip HBM budget for the 1M-row demo: 16 GiB (v5e-class
# chip), of which the accumulator may claim at most half — the rest is
# program workspace, staging, and the retention tiers.
HBM_BUDGET_GIB = 16.0
HBM_ACC_FRACTION = 0.5


def _sparse_band_workload(rng, m_rows: int):
    """(ids, values): every row gets SAMPLES_PER_ROW samples landing in
    BUCKETS_PER_ROW adjacent codec buckets (a narrow latency band) —
    the sparse-occupancy regime the paged backend targets."""
    base = rng.integers(0, 400, m_rows)
    ids = np.repeat(np.arange(m_rows, dtype=np.int32), SAMPLES_PER_ROW)
    buckets = (
        base.repeat(SAMPLES_PER_ROW)
        + rng.integers(0, BUCKETS_PER_ROW, len(ids))
    )
    # representative value of codec bucket k (k >= 0): e^(k/100) - 1
    # round-trips through compress() onto exactly bucket k
    values = np.expm1(buckets / 100.0).astype(np.float32)
    perm = rng.permutation(len(ids))
    return ids[perm], values[perm]


def _feed(agg, ids, values, chunk: int = 1 << 20) -> float:
    """Push the workload through record_batch + force-flush; returns
    elapsed seconds (host fold + upload + device commit)."""
    t0 = time.perf_counter()
    for off in range(0, len(ids), chunk):
        agg.record_batch(ids[off:off + chunk], values[off:off + chunk])
    agg.flush(force=True)
    return time.perf_counter() - t0


def _conserved_total(agg) -> int:
    if agg.paged is not None:
        _, _, counts = agg.paged.decode_cells(include_spill=True)
        return int(counts.sum())
    total = int(np.asarray(agg._finalize_acc(agg._acc), dtype=np.int64).sum())
    if agg._spill is not None:
        total += int(agg._spill.sum())
    return total


def measure_wire(m_rows: int, cap: float) -> dict:
    """One simulated interval at m_rows live metrics, three configs."""
    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.paging import PagedStoreConfig
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    cfg = MetricConfig(bucket_limit=WIRE_BUCKET_LIMIT)
    rng = np.random.default_rng(m_rows)
    ids, values = _sparse_band_workload(rng, m_rows)
    n = len(ids)
    # every row's pages fit comfortably; +1 for the reserved zero slot
    pool = 1 << max(12, (2 * m_rows - 1).bit_length())
    # one flush per reporting interval (the natural 60s-interval
    # deployment: data only needs to reach the device at commit), so the
    # sparse fold window IS the interval.  Identical for all three
    # configs — the raw wire ships 8 B/sample regardless of fold window,
    # so this choice cannot flatter the dense default's number.
    batch = 1 << max(16, (n - 1).bit_length())

    out = {"rows": m_rows, "samples_per_interval": n}
    for key, kw in (
        ("dense_default", dict(storage="dense")),     # transport="auto"
        ("dense_sparse", dict(storage="dense", transport="sparse")),
        ("paged", dict(storage="paged",
                       paged_config=PagedStoreConfig(pool_pages=pool))),
    ):
        agg = TPUAggregator(
            num_metrics=m_rows, config=cfg, batch_size=batch, **kw
        )
        try:
            elapsed = _feed(agg, ids, values)
            assert _conserved_total(agg) == n  # nothing shed or dropped
            if agg.paged is not None:
                h2d = agg.paged.h2d_bytes  # padded wire actually shipped
            else:
                h2d = agg.transport_stats()["bytes_uploaded"]
            sps = n / elapsed
            out[key] = {
                "transport": agg.transport,
                "probe_density": agg.transport_stats()["probe_density"],
                "h2d_bytes_per_interval": int(h2d),
                "h2d_bytes_per_sample": round(h2d / n, 2),
                "elapsed_s": round(elapsed, 3),
                "measured_samples_per_s": round(sps, 1),
                "suspect": sps > cap,
            }
            if agg.paged is not None:
                out[key]["occupied_pages"] = agg.paged.occupied_pages
                out[key]["storage_reason"] = agg.storage_reason
        finally:
            agg.close()
    out["paged_reduction_vs_dense_default"] = round(
        out["dense_default"]["h2d_bytes_per_interval"]
        / out["paged"]["h2d_bytes_per_interval"], 2
    )
    out["paged_vs_dense_sparse_wire"] = round(
        out["paged"]["h2d_bytes_per_interval"]
        / out["dense_sparse"]["h2d_bytes_per_interval"], 2
    )
    return out


def measure_hbm_occupancy(m_rows: int) -> dict:
    """Populate a paged store at the HEADLINE bucket axis with the same
    per-row band occupancy and read its real page consumption."""
    from loghisto_tpu.paging import PagedStore, PagedStoreConfig

    rng = np.random.default_rng(7 * m_rows)
    pool = 1 << max(12, (2 * m_rows - 1).bit_length())
    store = PagedStore(
        m_rows, HBM_BUCKET_LIMIT,
        config=PagedStoreConfig(pool_pages=pool),
    )
    base = rng.integers(0, 3500, m_rows)
    rows = np.repeat(np.arange(m_rows, dtype=np.int64), BUCKETS_PER_ROW)
    cb = (
        base.repeat(BUCKETS_PER_ROW)
        + np.tile(np.arange(BUCKETS_PER_ROW), m_rows)
    )
    packed = np.stack(
        [rows, cb, np.ones_like(rows)], axis=1
    ).astype(np.int32)
    store.commit(packed)
    assert store.spilled_cells == 0 and store.overflowed_cells == 0
    page_bytes = store.config.page_size * 4
    table_bytes_per_row = store.pages_per_row * 4
    pages_per_row = store.occupied_pages / m_rows
    bytes_per_live_row = pages_per_row * page_bytes + table_bytes_per_row
    dense_bytes_per_row = (2 * HBM_BUCKET_LIMIT + 1) * 4
    return {
        "rows": m_rows,
        "occupied_pages": store.occupied_pages,
        "pages_per_live_row": round(pages_per_row, 3),
        "bytes_per_live_row": round(bytes_per_live_row, 1),
        "dense_bytes_per_row": dense_bytes_per_row,
        "max_live_rows_per_gib": int((1 << 30) // bytes_per_live_row),
        "dense_max_live_rows_per_gib": (1 << 30) // dense_bytes_per_row,
        "hbm_reduction": round(dense_bytes_per_row / bytes_per_live_row, 1),
    }


def one_million_row_config(occ: dict) -> dict:
    """The ROADMAP target, sized from MEASURED per-row occupancy (25%
    pool headroom) against the simulated one-chip budget.  The 1M-row
    page table itself is constructed for real (host side) to prove the
    translate path holds at that M — only the pool size is extrapolated."""
    from loghisto_tpu.paging import PagedStore, PagedStoreConfig

    m = 1_000_000
    pages_needed = int(m * occ["pages_per_live_row"] * 1.25) + 1
    page_bytes = 256 * 4
    pool_bytes = pages_needed * page_bytes
    # real construction at M=1M (host table + a demo-size pool), plus a
    # 10k-row committed slice through the full translate/alloc path
    store = PagedStore(
        m, HBM_BUCKET_LIMIT, config=PagedStoreConfig(pool_pages=1 << 15)
    )
    table_bytes = store.page_table.nbytes
    rng = np.random.default_rng(1)
    rows = rng.choice(m, 10_000, replace=False).astype(np.int64)
    packed = np.stack([
        rows, rng.integers(0, 3500, len(rows)), np.ones(len(rows), np.int64)
    ], axis=1).astype(np.int32)
    applied = store.commit(packed)
    assert applied == len(rows)
    paged_gib = (pool_bytes + table_bytes) / (1 << 30)
    dense_gib = m * occ["dense_bytes_per_row"] / (1 << 30)
    budget_gib = HBM_BUDGET_GIB * HBM_ACC_FRACTION
    return {
        "rows": m,
        "pool_pages": pages_needed,
        "pool_gib": round(pool_bytes / (1 << 30), 2),
        "page_table_gib": round(table_bytes / (1 << 30), 2),
        "paged_hbm_gib": round(paged_gib, 2),
        "dense_hbm_gib": round(dense_gib, 2),
        "hbm_budget_gib": budget_gib,
        "fits_one_chip": paged_gib <= budget_gib,
        "dense_fits_one_chip": dense_gib <= budget_gib,
        "demonstrated_table_rows": m,
        "demonstrated_committed_rows": len(rows),
    }


def run(wire_rows=(10_000, 100_000), occupancy_rows: int = 100_000) -> dict:
    import jax

    from bench import plausibility_cap_samples_per_s

    platform = jax.devices()[0].platform
    cfg_bytes = 0
    result = {
        "metric": (
            "paged vs dense bucket storage: commit H2D bytes/interval "
            "and live metric rows per GiB of HBM"
        ),
        "platform": platform,
        "page_size": 256,
        "wire_bucket_limit": WIRE_BUCKET_LIMIT,
        "hbm_bucket_limit": HBM_BUCKET_LIMIT,
        "samples_per_row": SAMPLES_PER_ROW,
        "buckets_per_row": BUCKETS_PER_ROW,
        "configs": {},
    }
    suspect = False
    for m in wire_rows:
        cfg_bytes = m * (2 * WIRE_BUCKET_LIMIT + 1) * 4
        cap = plausibility_cap_samples_per_s(jax.devices()[0].device_kind, cfg_bytes)
        line = measure_wire(m, cap)
        line["roofline_cap_samples_per_s"] = cap
        result["configs"][str(m)] = line
        suspect = suspect or any(
            line[k]["suspect"]
            for k in ("dense_default", "dense_sparse", "paged")
        )

    occ = measure_hbm_occupancy(occupancy_rows)
    result["hbm_occupancy"] = occ
    result["one_million_rows"] = one_million_row_config(occ)

    # headline fields (bench.py lifts these verbatim)
    biggest = str(max(wire_rows))
    big = result["configs"][biggest]
    result["paged_h2d_bytes_per_interval"] = (
        big["paged"]["h2d_bytes_per_interval"]
    )
    result["dense_default_h2d_bytes_per_interval"] = (
        big["dense_default"]["h2d_bytes_per_interval"]
    )
    result["h2d_reduction_at_rows"] = int(biggest)
    result["h2d_reduction"] = big["paged_reduction_vs_dense_default"]
    result["max_live_rows_per_gib"] = occ["max_live_rows_per_gib"]
    result["dense_max_live_rows_per_gib"] = occ["dense_max_live_rows_per_gib"]
    result["suspect"] = suspect
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument(
        "--rows", type=int, nargs="*", default=[10_000, 100_000],
        help="live-row points for the wire measurement",
    )
    args = ap.parse_args()
    result = run(wire_rows=tuple(args.rows))
    text = json.dumps(result, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)


if __name__ == "__main__":
    main()
