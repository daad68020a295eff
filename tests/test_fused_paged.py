"""Direct-to-paged fused ingest (PR 17): the one-dispatch compress ->
log-bucket -> codec-encode -> page-translate -> scatter-add program.

The load-bearing guarantees pinned here:

  * BIT-IDENTITY to the jnp encode + paged_scatter oracle across all
    three codecs (dense / loglinear / polytail) — per-sample triples
    through ``paged_scatter_batch`` are the semantics the fused program
    must reproduce exactly (integer adds are order-independent, so the
    sort + segment-fold cannot change any count);
  * the one-dispatch contract: the fused step's jaxpr holds exactly ONE
    pallas_call and ZERO [M, B]-shaped intermediates — the dense tensor
    whose elimination is the point of the fusion can never silently
    reappear in the traced program;
  * structural exactness: invalid ids and unmapped cells sort to the
    dropped filler, the reserved slot-0 zero page is never written, and
    int32 cross-tile accumulation is exact;
  * page-prepare accountability: pool saturation redirects to the
    overflow row or folds into the exact host spill BEFORE the upload —
    every count still lands somewhere accountable;
  * the aggregator end-to-end path: explicit ingest_path="fused" on a
    paged store activates the fused route (raw transport, no host
    fold), conserves every sample, and spends exactly one device
    dispatch per staged batch with zero packed pool commits.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from loghisto_tpu.config import MetricConfig
from loghisto_tpu.ops.fused_ingest import (
    fused_paged_ingest_batch,
    make_fused_paged_ingest_fn,
)
from loghisto_tpu.ops.ingest import bucket_indices
from loghisto_tpu.ops.paged_store import ZERO_SLOT, paged_scatter_batch
from loghisto_tpu.paging import PagedStore, PagedStoreConfig
from loghisto_tpu.parallel.aggregator import TPUAggregator

pytestmark = pytest.mark.ingest_paged

BL = 512
B = 2 * BL + 1
PREC = 10
M = 16
PAGE = 128
PS = np.array([0.25, 0.5, 0.9, 0.99])


def _store(codec="auto", pool_pages=64, overflow_row=None, m=M):
    return PagedStore(
        m, BL, precision=PREC,
        config=PagedStoreConfig(
            pool_pages=pool_pages, page_size=PAGE, codec=codec,
            overflow_row=overflow_row,
        ),
    )


def _batch(rng, n, m=M, lo=-2, scale=50.0):
    ids = rng.integers(lo, m + 2, size=n).astype(np.int32)
    vals = (rng.standard_normal(n) * scale).astype(np.float32)
    return ids, vals


def _oracle_pool(store, ids, vals):
    """Per-sample triples through the jnp encode + paged_scatter oracle:
    the semantics the fused program must reproduce bit-for-bit."""
    rc, enc, table = store.device_luts()
    pages, page = store._pool.shape
    dense = bucket_indices(jnp.asarray(vals), BL, PREC)
    ids_d = jnp.asarray(ids)
    valid = (ids_d >= 0) & (ids_d < store.num_metrics)
    row = jnp.where(valid, ids_d, 0)
    codec = rc[row]
    valid &= codec >= 0
    storage = enc[jnp.maximum(codec, 0), dense]
    slot = jnp.where(valid, table[row, storage // page], -1)
    packed = jnp.stack(
        [slot, storage % page, jnp.ones_like(slot)], axis=1
    ).astype(jnp.int32)
    return paged_scatter_batch(jnp.zeros((pages, page), jnp.int32), packed)


# -- bit-identity across all three codecs ---------------------------------- #


@pytest.mark.parametrize("codec", ["dense", "loglinear", "polytail"])
def test_fused_paged_matches_oracle_per_codec(codec):
    rng = np.random.default_rng(7)
    st = _store(codec=codec)
    ids, vals = _batch(rng, 8192)
    out_ids, spilled = st.prepare_batch(ids, vals)
    assert spilled == 0
    st.ingest_raw(jnp.asarray(out_ids), jnp.asarray(vals))
    expect = _oracle_pool(st, out_ids, vals)
    np.testing.assert_array_equal(np.asarray(st._pool), np.asarray(expect))


def test_fused_paged_mixed_codecs_in_one_batch():
    # rows pinned to three DIFFERENT codecs in one batch: the one-gather
    # enc_luts stack must route every sample through ITS row's LUT
    rng = np.random.default_rng(11)
    st = _store(codec="auto")
    for r in range(M):
        st.set_row_codec(r, ("dense", "loglinear", "polytail")[r % 3])
    ids = rng.integers(0, M, size=16384).astype(np.int32)
    vals = (rng.standard_normal(16384) * 1e4).astype(np.float32)
    out_ids, _ = st.prepare_batch(ids, vals)
    assert len(set(int(c) for c in st.row_codec)) == 3
    st.ingest_raw(jnp.asarray(out_ids), jnp.asarray(vals))
    expect = _oracle_pool(st, out_ids, vals)
    np.testing.assert_array_equal(np.asarray(st._pool), np.asarray(expect))


def test_fused_paged_duplicate_heavy_fold_is_exact():
    # every sample lands on a handful of cells: the sort + segment-fold
    # must produce the same integer totals as per-sample adds
    rng = np.random.default_rng(3)
    st = _store()
    ids = rng.integers(0, 2, size=4096).astype(np.int32)
    vals = np.full(4096, 7.5, dtype=np.float32)
    vals[::3] = -1.25
    out_ids, _ = st.prepare_batch(ids, vals)
    st.ingest_raw(jnp.asarray(out_ids), jnp.asarray(vals))
    expect = _oracle_pool(st, out_ids, vals)
    np.testing.assert_array_equal(np.asarray(st._pool), np.asarray(expect))
    assert int(np.asarray(st._pool).sum()) == 4096


def test_invalid_ids_drop_and_zero_page_stays_zero():
    rng = np.random.default_rng(5)
    st = _store()
    ids, vals = _batch(rng, 4096, lo=-4)
    n_valid = int(((ids >= 0) & (ids < M)).sum())
    out_ids, _ = st.prepare_batch(ids, vals)
    st.ingest_raw(jnp.asarray(out_ids), jnp.asarray(vals))
    pool = np.asarray(st._pool)
    assert int(pool.sum()) == n_valid
    assert not pool[ZERO_SLOT].any()


def test_empty_batch_returns_pool_unchanged():
    st = _store()
    pool_before = np.asarray(st._pool).copy()
    rc, enc, table = st.device_luts()
    out = fused_paged_ingest_batch(
        st._pool, jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.float32),
        rc, enc, table, BL, PREC,
    )
    np.testing.assert_array_equal(np.asarray(out), pool_before)


def test_warmup_fused_is_numeric_noop():
    st = _store()
    st.warmup_fused(1024)
    assert int(np.asarray(st._pool).sum()) == 0
    assert st.fused_dispatches == 0


# -- the one-dispatch contract --------------------------------------------- #
# Counting logic lives in loghisto_tpu.analysis.jaxpr_audit (ISSUE 20);
# this file keeps the pins but delegates the walking/counting.


def test_fused_paged_is_one_pallas_call_no_dense_intermediate():
    from loghisto_tpu.analysis.jaxpr_audit import (
        Contract, assert_contract, audit_callable,
    )

    # the registry entry pins the jitted factory program (donated pool,
    # 1 pallas_call, no dense [M, B]) on the registry's trace geometry
    assert_contract("fused_paged_ingest")

    # the whole paged-mode interval — compress, encode, translate, fold,
    # scatter — must trace to exactly ONE pallas_call, and no [M, B]
    # dense tensor may appear anywhere in the program (its elimination
    # is the point of the fusion); audited again on THIS store's shapes
    rng = np.random.default_rng(1)
    st = _store()
    ids, vals = _batch(rng, 4096)
    out_ids, _ = st.prepare_batch(ids, vals)
    rc, enc, table = st.device_luts()
    findings = audit_callable(
        lambda pool, i, v, r, e, t: fused_paged_ingest_batch(
            pool, i, v, r, e, t, BL, PREC
        ),
        (st._pool, jnp.asarray(out_ids), jnp.asarray(vals), rc, enc,
         table),
        Contract(dispatches=None, pallas_calls=1, donated=None,
                 stream_psums=0, forbidden_shapes=((M, B),)),
        name="fused_paged_ingest_batch",
    )
    assert not findings, "\n".join(f.render() for f in findings)


def test_make_fused_paged_ingest_fn_donates_and_accumulates():
    rng = np.random.default_rng(9)
    st = _store()
    ids, vals = _batch(rng, 2048, lo=0)
    out_ids, _ = st.prepare_batch(ids, vals)
    fn = make_fused_paged_ingest_fn(BL, PREC)
    luts = st.device_luts()
    pool = fn(st._pool, jnp.asarray(out_ids), jnp.asarray(vals), *luts)
    pool = fn(pool, jnp.asarray(out_ids), jnp.asarray(vals), *luts)
    n_valid = int(((out_ids >= 0) & (out_ids < M)).sum())
    assert int(np.asarray(pool).sum()) == 2 * n_valid


# -- page-prepare accountability ------------------------------------------- #


def test_prepare_batch_redirects_to_overflow_row_on_saturation():
    rng = np.random.default_rng(13)
    # dense rows need ceil(1025/128) = 9 pages; a 12-page pool (minus
    # zero page, minus the overflow row's reserved pages) saturates on
    # the second row
    st = _store(codec="dense", pool_pages=12, overflow_row=M - 1)
    ids = np.repeat(np.arange(4, dtype=np.int32), 512)
    vals = (rng.standard_normal(len(ids)) * 1e5).astype(np.float32)
    out_ids, spilled = st.prepare_batch(ids, vals)
    assert spilled == 0
    assert st.overflowed_cells > 0
    assert (out_ids == M - 1).any()
    st.ingest_raw(jnp.asarray(out_ids), jnp.asarray(vals))
    rows, _, counts = st.decode_cells()
    assert int(counts.sum()) == len(ids)  # every count conserved
    assert (rows == M - 1).any()  # some landed on the overflow row


def test_prepare_batch_spills_exactly_without_overflow_row():
    rng = np.random.default_rng(17)
    st = _store(codec="dense", pool_pages=12)
    ids = np.repeat(np.arange(6, dtype=np.int32), 512)
    vals = (rng.standard_normal(len(ids)) * 1e5).astype(np.float32)
    out_ids, spilled = st.prepare_batch(ids, vals)
    assert spilled > 0
    assert st.spilled_cells > 0
    assert (out_ids == -1).sum() == spilled
    st.ingest_raw(jnp.asarray(out_ids), jnp.asarray(vals))
    _, _, counts = st.decode_cells(include_spill=True)
    assert int(counts.sum()) == len(ids)  # pool + host spill conserve


def test_device_luts_cache_invalidates_on_host_mutation():
    rng = np.random.default_rng(19)
    st = _store()
    ids, vals = _batch(rng, 1024, lo=0)
    st.prepare_batch(ids, vals)
    luts_a = st.device_luts()
    assert st.device_luts() is luts_a  # clean -> cached, no re-upload
    st.grow(M + 8)
    luts_b = st.device_luts()
    assert luts_b is not luts_a
    assert luts_b[2].shape[0] == M + 8
    # releasing pages dirties the mirror too
    st.release_rows([0])
    assert st.device_luts() is not luts_b


# -- aggregator end-to-end -------------------------------------------------- #

CFG = MetricConfig(bucket_limit=BL)


def _fused_agg(**kw):
    kw.setdefault("paged_config", PagedStoreConfig(pool_pages=256))
    return TPUAggregator(
        num_metrics=M, config=CFG, storage="paged", ingest_path="fused",
        **kw,
    )


def test_aggregator_fused_paged_activates_with_raw_transport():
    agg = _fused_agg(batch_size=4096)
    try:
        assert agg.fused_paged
        assert agg.ingest_path == "fused_paged"
        assert agg.transport == "raw"
        assert agg._ingest is None  # the pool is the accumulator
    finally:
        agg.close()


def test_aggregator_auto_on_cpu_keeps_prior_paged_route():
    agg = TPUAggregator(
        num_metrics=M, config=CFG, storage="paged",
        paged_config=PagedStoreConfig(pool_pages=256),
    )
    try:
        assert not agg.fused_paged
        assert agg.transport == "sparse"
        assert "platform" in agg.fused_paged_reason
    finally:
        agg.close()


def test_aggregator_fused_paged_conserves_and_matches_dense():
    rng = np.random.default_rng(23)
    n = 20000
    ids = rng.integers(0, M, n).astype(np.int32)
    vals = (rng.standard_normal(n) * 3.0).astype(np.float32)
    agg = _fused_agg(batch_size=4096)
    try:
        agg.record_batch(ids, vals)
        agg.flush(force=True)
        got = agg.paged.decode_dense()
        assert int(got.sum()) == n
        assert agg.paged.fused_dispatches >= 1
        assert agg.paged.commits == 0  # no packed pool commit ever ran
    finally:
        agg.close()
    # narrow values keep every row on the exact dense codec; the fused
    # route must then be bit-identical to the dense aggregator over the
    # same stream (both compress with the same device codec)
    dense = TPUAggregator(num_metrics=M, config=CFG)
    try:
        dense.record_batch(ids, vals)
        dense.flush(force=True)
        with dense._dev_lock:
            ref = np.asarray(
                dense._acc, dtype=np.int64
            )
    finally:
        dense.close()
    np.testing.assert_array_equal(got, ref)


def test_aggregator_fused_paged_one_dispatch_per_batch():
    rng = np.random.default_rng(29)
    bs = 4096
    agg = _fused_agg(batch_size=bs)
    try:
        before = agg.paged.fused_dispatches
        ids = rng.integers(0, M, bs).astype(np.int32)
        vals = rng.standard_normal(bs).astype(np.float32)
        agg.record_batch(ids, vals)
        agg.flush(force=True)
        # one staged batch -> exactly ONE device dispatch, and the
        # interval needed zero packed commits: the <= 2-dispatch
        # interval budget holds with room to spare
        assert agg.paged.fused_dispatches - before == 1
        assert agg.paged.commits == 0
    finally:
        agg.close()


def test_aggregator_explicit_fused_raises_when_incapable():
    # sparse transport leaves the one-dispatch path nothing to fuse;
    # the explicit selection surfaces the capability reason
    with pytest.raises(ValueError, match="RAW"):
        TPUAggregator(
            num_metrics=M, config=CFG, storage="paged",
            ingest_path="fused", transport="sparse",
            paged_config=PagedStoreConfig(pool_pages=256),
        )


def test_aggregator_fused_paged_growth_keeps_ingesting():
    rng = np.random.default_rng(31)
    agg = _fused_agg(batch_size=4096, max_metrics=4 * M)
    try:
        # names beyond the initial row space force registry growth; the
        # fused path must keep ingesting through the page-table extension
        for i in range(3 * M):
            agg.record(f"grow.{i}", float(i % 7))
        agg.flush(force=True)
        rows, _, counts = agg.paged.decode_cells()
        assert int(counts.sum()) == 3 * M
    finally:
        agg.close()
