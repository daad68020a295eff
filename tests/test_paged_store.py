"""Paged bucket storage (PR 14): page pool/table mechanics, the two
commit tiers' parity, variable-resolution codecs with the codec-parity
oracle, the lifecycle composition (pages return to the free pool), and
the aggregator's storage="paged" end-to-end path.

The load-bearing guarantees pinned here:

  * paged percentiles are BIT-IDENTICAL to the dense host oracle
    (dense_stats_np) for rows stored under the exact dense codec;
  * compressed-codec rows (loglinear / polytail) stay inside their
    codec's published max_rel_error bound vs the dense reference —
    measured, not assumed;
  * the reserved zero page is never written, whatever the commit tier;
  * eviction/repack returns pages to the free pool and conserves every
    count exactly.
"""

import math

import numpy as np
import pytest

from loghisto_tpu.config import PRECISION, MetricConfig
from loghisto_tpu.ops.paged_store import (
    PAGE_SIZE,
    ZERO_SLOT,
    gather_storage_rows,
    paged_scatter_batch,
    pallas_paged_scatter,
    validate_pool_shape,
)
from loghisto_tpu.ops.stats import dense_stats_np
from loghisto_tpu.paging import (
    PagedStore,
    PagedStoreConfig,
    dense_codec,
    loglinear_codec,
    polytail_codec,
)

pytestmark = pytest.mark.paged

BL = 512  # compact bucket axis keeps the CPU interpret runs quick
CFG = MetricConfig(bucket_limit=BL)
PS = np.array([0.0, 0.25, 0.5, 0.9, 0.99, 1.0])


def _sparse_rows(rng, m, cells_per_row, lo=-BL, hi=BL):
    """Synthetic occupied cells: (rows, dense_idx, counts) int64."""
    rows, idx, counts = [], [], []
    for r in range(m):
        cols = rng.choice(np.arange(lo + BL, hi + BL), size=cells_per_row,
                          replace=False)
        rows.extend([r] * cells_per_row)
        idx.extend(cols.tolist())
        counts.extend(rng.integers(1, 100, cells_per_row).tolist())
    return (np.array(rows, np.int64), np.array(idx, np.int64),
            np.array(counts, np.int64))


def _dense_of(store, m):
    acc = np.zeros((m, 2 * BL + 1), dtype=np.int64)
    return acc


# -- codecs ----------------------------------------------------------------- #

def test_dense_codec_is_identity():
    c = dense_codec(2 * BL + 1)
    assert c.max_halfwidth == 0
    assert c.max_rel_error(PRECISION) == 0.0
    assert np.array_equal(c.enc_lut, np.arange(2 * BL + 1))
    assert np.array_equal(c.dec_lut, np.arange(2 * BL + 1))


@pytest.mark.parametrize("codec_fn,kwargs", [
    (loglinear_codec, dict(factor=4)),
    (polytail_codec, dict(body_halfwidth=128, tail_rel_error=0.10,
                          precision=PRECISION)),
])
def test_compressed_codecs_bound_roundtrip_width(codec_fn, kwargs):
    c = codec_fn(BL, **kwargs)
    assert c.storage_buckets < 2 * BL + 1  # actually compresses
    # dec is injective: one representative native bucket per chunk
    assert len(np.unique(c.dec_lut)) == len(c.dec_lut)
    # round trip: every native bucket lands within max_halfwidth of its
    # chunk representative — this is what the value-space bound rides on
    rt = c.dec_lut[c.enc_lut]
    width = np.abs(rt - np.arange(2 * BL + 1))
    assert int(width.max()) <= c.max_halfwidth
    # the bound is tight enough to be meaningful
    assert c.max_rel_error(PRECISION) < 0.15


def test_polytail_respects_requested_error():
    c = polytail_codec(4096, 1024, 0.10, PRECISION)
    assert c.max_rel_error(PRECISION) <= 0.10 + 1e-12


# -- pool shape guards ------------------------------------------------------ #

def test_validate_pool_shape_guards():
    validate_pool_shape(64, PAGE_SIZE)
    with pytest.raises(ValueError, match="multiple of 128"):
        validate_pool_shape(64, 100)
    with pytest.raises(ValueError, match=">= 2 pages"):
        validate_pool_shape(1, PAGE_SIZE)
    with pytest.raises(ValueError, match="int32"):
        validate_pool_shape(2**23, 256)


# -- commit tier parity ----------------------------------------------------- #

def test_jnp_and_pallas_scatter_tiers_are_bit_identical():
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    pool = jnp.zeros((32, PAGE_SIZE), dtype=jnp.int32)
    n = 1000
    packed = np.stack([
        rng.integers(-1, 32, n),          # slots incl. invalid -1 and 0
        rng.integers(0, PAGE_SIZE, n),
        rng.integers(1, 50, n),
    ], axis=1).astype(np.int32)
    a = np.asarray(paged_scatter_batch(pool, jnp.asarray(packed)))
    b = np.asarray(pallas_paged_scatter(pool, jnp.asarray(packed)))
    assert np.array_equal(a, b)
    # the reserved zero page is never written by either tier
    assert not a[ZERO_SLOT].any()
    # duplicate-cell accumulation is exact (integer adds, serial kernel)
    assert int(a.sum()) == int(
        packed[(packed[:, 0] > 0) & (packed[:, 0] < 32), 2].sum()
    )


def test_gather_clamps_unmapped_onto_zero_page():
    import jax.numpy as jnp

    pool = jnp.zeros((4, PAGE_SIZE), dtype=jnp.int32).at[2, 7].set(99)
    table = jnp.asarray(np.array([[2, -1], [-1, -1]], np.int32))
    out = np.asarray(gather_storage_rows(pool, table, 2 * PAGE_SIZE))
    assert out[0, 7] == 99
    assert not out[1].any()           # fully unmapped row reads zeros
    assert not out[0, PAGE_SIZE:].any()  # unmapped page reads zeros


# -- store: exactness + codec-parity oracle --------------------------------- #

def test_dense_codec_rows_bit_identical_to_dense_oracle():
    rng = np.random.default_rng(5)
    m = 8
    store = PagedStore(m, BL, config=PagedStoreConfig(
        pool_pages=256, codec="dense"))
    rows, idx, counts = _sparse_rows(rng, m, 40)
    packed = np.stack([rows, idx - BL, counts], axis=1).astype(np.int32)
    store.commit(packed)
    acc = _dense_of(store, m)
    np.add.at(acc, (rows, idx), counts)
    ref = dense_stats_np(acc, PS, BL, PRECISION)
    got = store.stats(PS, reset=False)
    assert np.array_equal(np.asarray(got["counts"]), ref["counts"])
    assert np.array_equal(np.asarray(got["percentiles"]),
                          ref["percentiles"])  # BIT-identical
    np.testing.assert_allclose(got["sums"], ref["sums"], rtol=1e-12)


@pytest.mark.parametrize("codec", ["loglinear", "polytail"])
def test_codec_parity_oracle_bounds_percentile_error(codec):
    """The codec-parity oracle: every percentile served from a
    compressed row stays within the codec's published max_rel_error of
    the dense log-bucket reference, in VALUE space."""
    rng = np.random.default_rng(7)
    m = 6
    store = PagedStore(m, BL, config=PagedStoreConfig(
        pool_pages=512, codec=codec))
    rows, idx, counts = _sparse_rows(rng, m, 120)
    packed = np.stack([rows, idx - BL, counts], axis=1).astype(np.int32)
    store.commit(packed)
    acc = _dense_of(store, m)
    np.add.at(acc, (rows, idx), counts)
    ref = dense_stats_np(acc, PS, BL, PRECISION)
    got = store.stats(PS, reset=False)
    # counts and sums-of-counts are exact under ANY codec (integer adds)
    assert np.array_equal(np.asarray(got["counts"]), ref["counts"])
    cid = store._codec_ids[codec]
    bound = store._codecs[cid].max_rel_error(PRECISION)
    assert bound > 0.0
    rp = np.asarray(ref["percentiles"], dtype=np.float64)
    gp = np.asarray(got["percentiles"], dtype=np.float64)
    # the bound is |err| <= max_rel_error * (|v| + 1): log buckets are
    # spaced in ln(1 + |v|), so near zero the error is absolute-ish
    rel = np.abs(gp - rp) / (np.abs(rp) + 1.0)
    # +1/precision slack: representatives carry their own half-bucket
    # rounding on BOTH sides of the comparison
    slack = math.exp(1.0 / PRECISION) - 1.0
    assert float(rel.max()) <= bound + slack, (
        f"codec {codec}: worst rel err {rel.max():.4f} > bound {bound:.4f}"
    )


def test_auto_codec_picks_dense_for_narrow_rows_and_compresses_wide():
    store = PagedStore(4, BL, config=PagedStoreConfig(pool_pages=256))
    # row 0: a tight latency band -> dense pages
    narrow = np.stack([np.zeros(30), np.arange(30), np.ones(30)],
                      axis=1).astype(np.int32)
    store.commit(narrow)
    # row 1: occupied buckets spread across the whole axis -> compressed
    wide_idx = np.linspace(-BL, BL, 200).astype(np.int64)
    wide = np.stack([np.ones(200), wide_idx, np.ones(200)],
                    axis=1).astype(np.int32)
    store.commit(wide)
    names = store.codec_names()
    assert names[0] == "dense"
    assert names[1] in ("loglinear", "polytail")
    # compression means fewer pages than the dense row span would need
    dense_span = len(np.unique((wide_idx + BL) // store.config.page_size))
    mapped = int((store.page_table[1] >= 0).sum())
    assert mapped < dense_span


def test_counts_conserved_across_alloc_overflow_and_spill():
    """Saturate a tiny pool: everything that can't get a page must land
    in the overflow row (when configured) or the exact host spill —
    total count is conserved to the last sample either way."""
    rng = np.random.default_rng(11)
    m = 64
    # 7 usable pages, dense codec, rows span >1 page each -> saturates
    store = PagedStore(m, BL, config=PagedStoreConfig(
        pool_pages=8, codec="dense"))
    rows, idx, counts = _sparse_rows(rng, m, 12)
    packed = np.stack([rows, idx - BL, counts], axis=1).astype(np.int32)
    applied = store.commit(packed)
    assert applied == int(counts.sum())
    assert store.free_pages == 0
    assert store.spilled_cells > 0  # the pool genuinely saturated
    got = store.stats(PS, reset=False)
    assert int(np.asarray(got["counts"]).sum()) == int(counts.sum())

    # same load with an overflow row: unplaceable cells fold there
    store2 = PagedStore(m, BL, config=PagedStoreConfig(
        pool_pages=8, codec="dense", overflow_row=0))
    applied2 = store2.commit(packed)
    assert applied2 == int(counts.sum())
    assert store2.overflowed_cells > 0
    got2 = store2.stats(PS, reset=False)
    assert int(np.asarray(got2["counts"]).sum()) == int(counts.sum())


def test_stats_reset_clears_pool_and_spill():
    store = PagedStore(4, BL, config=PagedStoreConfig(pool_pages=64))
    packed = np.array([[0, 10, 5], [1, -3, 7]], np.int32)
    store.commit(packed)
    store.spill_cells(np.array([2]), np.array([BL]), np.array([9]))
    got = store.stats(PS, reset=True)
    assert int(np.asarray(got["counts"]).sum()) == 21
    again = store.stats(PS, reset=True)
    assert int(np.asarray(again["counts"]).sum()) == 0


def test_query_matches_stats_for_pool_resident_rows():
    rng = np.random.default_rng(13)
    m = 8
    store = PagedStore(m, BL, config=PagedStoreConfig(pool_pages=256))
    rows, idx, counts = _sparse_rows(rng, m, 60)
    packed = np.stack([rows, idx - BL, counts], axis=1).astype(np.int32)
    store.commit(packed)
    st = store.stats(PS, reset=False)
    q = store.query(np.arange(m), PS)
    assert np.array_equal(q["counts"], np.asarray(st["counts"]))
    # device query runs the f32 snapshot program; representative sums
    # agree to f32 precision, percentiles to the same bucket
    np.testing.assert_allclose(q["sums"], st["sums"], rtol=1e-5)
    np.testing.assert_allclose(q["percentiles"], st["percentiles"],
                               rtol=1e-5)


# -- lifecycle composition: pages return to the free pool ------------------- #

def test_release_rows_returns_pages_to_free_pool():
    store = PagedStore(8, BL, config=PagedStoreConfig(
        pool_pages=64, codec="dense"))
    packed = np.array([[0, 0, 3], [1, 300, 4], [2, -300, 5]], np.int32)
    store.commit(packed)
    before = store.free_pages
    # the release contract: the caller folds/zeroes victim pages first
    # (fold_rows_into does this internally; an eviction-without-fold
    # zeroes explicitly) — a freed page must come back clean
    store._zero_rows([0, 1])
    released = store.release_rows([0, 1])
    assert released > 0
    assert store.free_pages == before + released
    assert store.released_pages >= released
    # released rows read empty; survivor untouched
    got = store.stats(PS, reset=False)
    counts = np.asarray(got["counts"])
    assert counts[0] == 0 and counts[1] == 0 and counts[2] == 5
    # freed pages are immediately reusable
    store.commit(np.array([[5, 100, 2]], np.int32))
    assert np.asarray(store.stats(PS, reset=False)["counts"])[5] == 2


def test_fold_rows_into_is_count_exact_and_frees_pages():
    store = PagedStore(8, BL, config=PagedStoreConfig(
        pool_pages=64, codec="dense", overflow_row=7))
    packed = np.array(
        [[0, 5, 10], [1, -7, 20], [2, 9, 30]], np.int32
    )
    store.commit(packed)
    store.spill_cells(np.array([1]), np.array([BL + 2]), np.array([4]))
    free_before = store.free_pages
    moved = store.fold_rows_into([0, 1], target=7)
    assert moved == 10 + 20 + 4
    assert store.free_pages > free_before  # victim pages came back
    got = store.stats(PS, reset=False)
    counts = np.asarray(got["counts"])
    assert counts[0] == 0 and counts[1] == 0
    assert counts[7] == 34 and counts[2] == 30  # survivor untouched
    # total conserved through the fold
    assert int(counts.sum()) == 64


def test_apply_permutation_repacks_without_device_traffic():
    store = PagedStore(8, BL, config=PagedStoreConfig(
        pool_pages=64, codec="dense"))
    store.commit(np.array([[3, 11, 6], [6, -11, 8]], np.int32))
    store.spill_cells(np.array([6]), np.array([BL]), np.array([2]))
    h2d_before = store.h2d_bytes
    # survivors 3 and 6 compact to rows 0 and 1
    perm = [3, 6] + [i for i in range(8) if i not in (3, 6)]
    store.apply_permutation(perm, 8)
    assert store.h2d_bytes == h2d_before  # pure host table permute
    counts = np.asarray(store.stats(PS, reset=False)["counts"])
    assert counts[0] == 6 and counts[1] == 10
    assert counts[2:].sum() == 0


def test_grow_extends_table_without_touching_device_state():
    store = PagedStore(4, BL, config=PagedStoreConfig(pool_pages=64))
    store.commit(np.array([[0, 3, 5]], np.int32))
    h2d = store.h2d_bytes
    store.grow(16)
    assert store.num_metrics == 16
    assert store.page_table.shape[0] == 16
    assert store.h2d_bytes == h2d
    counts = np.asarray(store.stats(PS, reset=False)["counts"])
    assert counts[0] == 5 and len(counts) == 16


# -- aggregator integration ------------------------------------------------- #

def _mk_agg(storage, **kw):
    from loghisto_tpu.parallel.aggregator import TPUAggregator

    kw.setdefault("paged_config", PagedStoreConfig(pool_pages=512))
    return TPUAggregator(
        num_metrics=64, config=CFG, batch_size=256, storage=storage,
        percentiles={"p50_%s": 0.5, "p99_%s": 0.99}, **kw
    )


def test_aggregator_paged_end_to_end_matches_dense():
    rng = np.random.default_rng(17)
    ids = rng.integers(0, 8, 5000).astype(np.int32)
    vals = rng.lognormal(3.0, 1.0, 5000).astype(np.float32)
    paged, dense = _mk_agg("paged"), _mk_agg("dense")
    try:
        for agg in (paged, dense):
            for i in range(8):
                agg.registry.id_for(f"m{i}")
            agg.record_batch(ids, vals)
            agg.flush(force=True)
        assert paged.storage == "paged" and paged.paged is not None
        pm = paged.collect(reset=False).metrics
        dm = dense.collect(reset=False).metrics
        assert set(pm) == set(dm)
        for k in dm:
            # narrow per-metric bands get the exact dense codec here, so
            # full numeric parity — not just bounded error
            np.testing.assert_allclose(pm[k], dm[k], rtol=1e-6, err_msg=k)
    finally:
        paged.close()
        dense.close()


def test_aggregator_paged_giant_weight_takes_exact_spill():
    import datetime as dt

    from loghisto_tpu.metrics import RawMetricSet

    agg = _mk_agg("paged")
    try:
        agg.registry.id_for("g0")
        raw = RawMetricSet(
            time=dt.datetime.now(dt.timezone.utc), counters={}, rates={},
            gauges={}, histograms={"g0": {100: (1 << 31)}},
        )
        agg.merge_raw(raw)  # > int32: must spill, not wrap
        ms = agg.collect(reset=True)
        assert ms.metrics["g0_count"] == float(1 << 31)
    finally:
        agg.close()


def test_aggregator_paged_grow_is_host_side():
    agg = _mk_agg("paged", max_metrics=256)
    try:
        for i in range(100):  # past num_metrics=64: triggers growth
            agg.record(f"g{i}", float(i + 1))
        agg.flush(force=True)
        assert agg.num_metrics > 64
        assert agg.paged.num_metrics == agg.num_metrics
        ms = agg.collect(reset=False)
        assert ms.metrics["g99_count"] == 1.0
    finally:
        agg.close()


def test_storage_auto_degrades_below_crossover_with_reason():
    agg = _mk_agg("auto")
    try:
        assert agg.storage == "dense"
        assert "below crossover" in agg.storage_reason
        assert agg.paged is None
    finally:
        agg.close()


def test_paged_refuses_multirow_and_nonsparse_transports():
    with pytest.raises(ValueError, match="transport"):
        _mk_agg("paged", transport="raw")
    with pytest.raises(ValueError, match="transport"):
        _mk_agg("paged", transport="preagg")


def test_paged_joins_fused_commit_and_lifecycle_but_not_anomaly():
    # r18 retired the r14 refusals: a paged aggregator shares the fused
    # commit program (the pool rides in the accumulator's carry slot)
    # and LifecycleManager drives evict/compact/grow on it.  The one
    # pairing that stays dense-only is the drift engine, whose
    # interval-histogram carry IS a dense [M, B] tensor.
    from loghisto_tpu.anomaly import AnomalyConfig, AnomalyManager
    from loghisto_tpu.commit import IntervalCommitter, commit_incompatibility
    from loghisto_tpu.lifecycle import LifecycleConfig, LifecycleManager
    from loghisto_tpu.window import TimeWheel

    agg = _mk_agg("paged")
    try:
        wheel = TimeWheel(num_metrics=64, config=CFG, interval=1.0,
                          tiers=[(4, 1)], registry=agg.registry)
        assert commit_incompatibility(agg, wheel) is None
        lc = LifecycleManager(agg, wheel, LifecycleConfig())
        assert lc is not None
        an = AnomalyManager(agg, wheel, AnomalyConfig())
        with pytest.raises(ValueError, match="dense accumulator"):
            IntervalCommitter(agg, wheel, anomaly=an)
    finally:
        agg.close()


def test_system_level_storage_plumb():
    from loghisto_tpu.system import TPUMetricSystem

    ms = TPUMetricSystem(
        interval=60.0, sys_stats=False, config=CFG, num_metrics=64,
        storage="paged", paged_config=PagedStoreConfig(pool_pages=256),
    )
    try:
        assert ms.aggregator.storage == "paged"
        ms.record_batch(np.zeros(10, np.int32), np.ones(10, np.float32))
        ms.aggregator.flush(force=True)
        assert int(np.asarray(
            ms.aggregator.paged.stats(PS, reset=False)["counts"]
        ).sum()) == 10
    finally:
        ms.stop()
        ms.aggregator.close()


def test_zero_page_stays_zero_through_aggregator_traffic():
    rng = np.random.default_rng(23)
    agg = _mk_agg("paged")
    try:
        ids = rng.integers(0, 32, 3000).astype(np.int32)
        vals = rng.lognormal(1.0, 2.0, 3000).astype(np.float32)
        agg.record_batch(ids, vals)
        agg.flush(force=True)
        pool = np.asarray(agg.paged._pool)
        assert not pool[ZERO_SLOT].any()
    finally:
        agg.close()
