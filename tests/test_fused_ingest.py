"""ISSUE 13 (ingest_fused tier): the fused sample->scatter Pallas kernel.

Pins, against the jnp scatter oracle (``ops.ingest.ingest_batch``, the
semantics the kernel must reproduce bit-for-bit):

  * parity over adversarial values — denormals, negatives, inf/NaN
    sanitization, zeros — and ids at every row-tile boundary, plus the
    empty batch;
  * parity through the sparse-triple formulation and the sharded-mesh
    interval path on the SAME sample stream;
  * the one-dispatch contract: the fused step's jaxpr holds exactly one
    pallas_call and ZERO scatter primitives (the retired path's
    signature), so the fusion cannot silently regress to two stages;
  * the dispatch reason strings naming why fused ingest was declined
    (mesh shape, dtype, batch too small) and the matching
    resolve_commit_path behavior;
  * the r13 staging-ring drain: close() racing in-flight double-buffered
    uploads drains every slot before the final interval commits (driven
    with the agg.xfer_worker fault hook).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from loghisto_tpu.config import PRECISION, MetricConfig
from loghisto_tpu.ops import dispatch
from loghisto_tpu.ops.fused_ingest import (
    ROWS_TILE,
    fused_ingest_batch,
    fused_ingest_reference,
)
from loghisto_tpu.ops.ingest import ingest_batch
from loghisto_tpu.parallel.aggregator import IngestStagingRing, TPUAggregator
from loghisto_tpu.resilience import FaultInjector

pytestmark = pytest.mark.ingest_fused

BL = 64
B = 2 * BL + 1
M = 32
CFG = MetricConfig(bucket_limit=BL)


def _zeros():
    return jnp.zeros((M, B), dtype=jnp.int32)


def _adversarial(n, seed=0):
    """The pallas_parity.py adversarial recipe plus explicit specials:
    heavy-tailed magnitudes, a negative band, exact zeros, a
    sub-resolution band, then denormals / inf / -inf / NaN spliced in."""
    rng = np.random.default_rng(seed)
    v = rng.lognormal(8, 4, n).astype(np.float32)
    v[: n // 8] *= -1
    v[n // 8: n // 6] = 0.0
    v[n // 6: n // 4] = rng.uniform(-0.6, 0.6, n // 4 - n // 6)
    v[0] = np.float32(1e-40)       # positive denormal
    v[1] = np.float32(-1e-40)      # negative denormal
    v[2] = np.inf                  # saturates to +bucket_limit
    v[3] = -np.inf                 # saturates to -bucket_limit
    v[4] = np.nan                  # codec pins NaN to bucket 0
    v[5] = np.float32(3.4e38)
    ids = rng.integers(-3, M + 3, n).astype(np.int32)  # incl. droppable
    return ids, v


def _assert_parity(ids, values):
    got = fused_ingest_batch(
        _zeros(), jnp.asarray(ids), jnp.asarray(values), BL
    )
    want = ingest_batch(
        _zeros(), jnp.asarray(ids), jnp.asarray(values), BL
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    return np.asarray(got)


# -- parity vs the jnp oracle --------------------------------------------- #


def test_parity_adversarial_values():
    ids, values = _adversarial(6000)
    acc = _assert_parity(ids, values)
    # the oracle is also the re-exported reference — same object
    assert fused_ingest_reference is ingest_batch
    # in-range samples all landed (out-of-range ids dropped)
    assert acc.sum() == int(((ids >= 0) & (ids < M)).sum())


def test_parity_ids_at_row_tile_boundaries():
    # every edge the block/row decomposition can get wrong: first and
    # last row of a tile, first and last metric row, both droppable
    # sides, and the sanitize sentinel value itself
    edge_ids = np.array(
        [0, ROWS_TILE - 1, ROWS_TILE, 2 * ROWS_TILE - 1, M - ROWS_TILE,
         M - 1, -1, -2, M, M + 1, 2 ** 30, np.iinfo(np.int32).max],
        dtype=np.int32,
    )
    ids = np.tile(edge_ids, 50)
    rng = np.random.default_rng(3)
    values = rng.lognormal(2, 3, len(ids)).astype(np.float32)
    acc = _assert_parity(ids, values)
    assert acc.sum() == 50 * int(((edge_ids >= 0) & (edge_ids < M)).sum())


def test_parity_empty_batch():
    acc = _assert_parity(
        np.zeros(0, np.int32), np.zeros(0, np.float32)
    )
    assert acc.sum() == 0


def test_parity_f64_values_cast_like_every_other_path():
    ids = np.arange(20, dtype=np.int32) % M
    values = np.linspace(-1e6, 1e6, 20).astype(np.float64)
    _assert_parity(ids, values)  # asarray canonicalizes both paths alike


def test_parity_sparse_triple_config():
    # the sparse transport's packed [n, 3] formulation of the SAME batch
    # must land the identical accumulator (weight-1 triples, codec
    # buckets computed host-side like the _native fold does)
    from loghisto_tpu.ops.codec import compress
    from loghisto_tpu.ops.sparse_ingest import sparse_ingest_batch

    ids, values = _adversarial(4000, seed=11)
    buckets = np.asarray(compress(jnp.asarray(values), PRECISION))
    packed = np.stack(
        [ids, buckets.astype(np.int32), np.ones(len(ids), np.int32)],
        axis=1,
    )
    via_sparse = sparse_ingest_batch(_zeros(), jnp.asarray(packed), BL)
    via_fused = fused_ingest_batch(
        _zeros(), jnp.asarray(ids), jnp.asarray(values), BL
    )
    np.testing.assert_array_equal(
        np.asarray(via_fused), np.asarray(via_sparse)
    )


def test_parity_sharded_mesh_config():
    # the sharded interval path (fused declines mesh steps — its local
    # fold stays on the dispatched kernel) must still agree exactly with
    # a single-device fused fold over the same stream, and the r13 async
    # collect split (collect.start + independent make_partial) must be
    # bit-identical to the compat collect
    from loghisto_tpu.parallel.aggregator import (
        make_interval_distributed_step,
        make_sharded_accumulator,
    )
    from loghisto_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(stream=4, metric=2)
    num_metrics = 64
    ps = np.array([0.0, 0.5, 1.0], dtype=np.float32)
    batch = 1 << 12
    ingest, collect, make_partial = make_interval_distributed_step(
        mesh, num_metrics, BL, ps, batch_size=batch
    )
    rng = np.random.default_rng(17)
    batches = [
        (
            ((rng.zipf(1.3, batch) - 1) % num_metrics).astype(np.int32),
            rng.lognormal(8, 3, batch).astype(np.float32),
        )
        for _ in range(3)
    ]

    # compat collect
    partial = make_partial()
    for ids, values in batches[:2]:
        partial = ingest(partial, jnp.asarray(ids), jnp.asarray(values))
    acc = make_sharded_accumulator(mesh, num_metrics, B)
    acc, partial, _ = collect(acc, partial)
    # async form: issue the psum, fold the NEXT batch into the fresh
    # partial while the collective is (logically) in flight
    acc2 = make_sharded_accumulator(mesh, num_metrics, B)
    partial2 = make_partial()
    for ids, values in batches[:2]:
        partial2 = ingest(partial2, jnp.asarray(ids), jnp.asarray(values))
    inflight = collect.start(acc2, partial2)
    partial2 = make_partial()
    partial2 = ingest(
        partial2, jnp.asarray(batches[2][0]), jnp.asarray(batches[2][1])
    )
    acc2, _ = inflight
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(acc2))

    # and the sharded result equals a single-device fused fold
    single = jnp.zeros((num_metrics, B), dtype=jnp.int32)
    for ids, values in batches[:2]:
        single = fused_ingest_batch(
            single, jnp.asarray(ids), jnp.asarray(values), BL
        )
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(single))


# -- the one-dispatch contract -------------------------------------------- #
# Counting logic lives in loghisto_tpu.analysis.jaxpr_audit (ISSUE 20);
# this file keeps the pins but delegates the walking/counting.


def test_fused_step_is_one_dispatch_no_scatter():
    from loghisto_tpu.analysis.jaxpr_audit import (
        Contract, assert_contract, audit_callable, jaxpr_primitives,
    )

    # the registry entry pins the jitted factory program (1 pallas_call,
    # donated acc, 1 dispatch)
    assert_contract("fused_ingest")

    # The preprocess legitimately scatters into the small [G*T] layout
    # arrays (that IS the sort+layout stage).  What must never reappear
    # is a scatter writing the [M, B] accumulator — the retired
    # two-dispatch path's signature — and the bucket work must live in
    # exactly ONE pallas_call.  Audited here on THIS test's shapes.
    acc = _zeros()
    ids = jnp.zeros(4096, jnp.int32)
    values = jnp.zeros(4096, jnp.float32)
    findings = audit_callable(
        lambda a, i, v: fused_ingest_batch(a, i, v, BL),
        (acc, ids, values),
        Contract(dispatches=None, pallas_calls=1, donated=None,
                 stream_psums=0),
        name="fused_ingest_batch",
    )
    assert not findings, "\n".join(f.render() for f in findings)
    prims = jaxpr_primitives(jax.make_jaxpr(
        lambda a, i, v: fused_ingest_batch(a, i, v, BL)
    )(acc, ids, values))
    acc_scatters = [
        name for name, shapes in prims
        if name.startswith("scatter") and (M, B) in shapes
    ]
    assert not acc_scatters, (
        f"fused step regressed to accumulator scatters: {acc_scatters}"
    )
    # sanity: the retired compress->scatter composition DOES carry the
    # accumulator-scatter signature this guard looks for
    closed_ref = jax.make_jaxpr(
        lambda a, i, v: ingest_batch(a, i, v, BL)
    )(acc, ids, values)
    assert any(
        name.startswith("scatter") and (M, B) in shapes
        for name, shapes in jaxpr_primitives(closed_ref)
    )


# -- declined-reason regression (satellite 3) ------------------------------ #


class _MeshStub:
    def __init__(self, axis_names, shape):
        self.axis_names = axis_names
        self.shape = shape


@pytest.fixture
def baked_fused_thresholds():
    saved = (dispatch.FUSED_INGEST, dispatch.FUSED_MIN_BATCH,
             dispatch.SORT_MIN_METRICS)
    dispatch.FUSED_INGEST = True
    dispatch.FUSED_MIN_BATCH = 1 << 17
    dispatch.SORT_MIN_METRICS = 4096
    yield
    (dispatch.FUSED_INGEST, dispatch.FUSED_MIN_BATCH,
     dispatch.SORT_MIN_METRICS) = saved


def test_declined_reasons_name_the_blocker(baked_fused_thresholds):
    # mesh-embedded step
    reason = dispatch.fused_ingest_incapability(
        10_000, batch_size=1 << 20, mesh=True
    )
    assert reason is not None and "mesh shape" in reason
    # row-tile divisibility is reported as a mesh/shape blocker
    reason = dispatch.fused_ingest_incapability(10_001, batch_size=1 << 20)
    assert reason is not None and "mesh shape" in reason
    assert "10001" in reason
    # dtype
    reason = dispatch.fused_ingest_incapability(
        10_000, batch_size=1 << 20, acc_dtype="float32"
    )
    assert reason is not None and "dtype" in reason
    # batch too small, and batch unknown
    reason = dispatch.fused_ingest_incapability(10_000, batch_size=1 << 10)
    assert reason is not None and "batch too small" in reason
    assert str(1 << 10) in reason
    reason = dispatch.fused_ingest_incapability(10_000)
    assert reason is not None and "batch too small" in reason
    # capable config
    assert dispatch.fused_ingest_incapability(
        10_000, batch_size=1 << 20
    ) is None


def test_resolve_surfaces_reasons(baked_fused_thresholds):
    # auto degrades silently to the pre-r13 winner on a blocker...
    assert dispatch.resolve_ingest_path(
        "auto", 10_000, 8193, "tpu", batch_size=1 << 20, mesh=True
    ) == "sort"
    assert dispatch.resolve_ingest_path(
        "auto", 10_000, 8193, "tpu", batch_size=1 << 10
    ) == "sort"
    # ...and picks fused when capable
    assert dispatch.resolve_ingest_path(
        "auto", 10_000, 8193, "tpu", batch_size=1 << 20
    ) == "fused"
    # explicit selection raises WITH the reason string (correctness
    # blockers only — the crossover is the operator's call)
    with pytest.raises(ValueError, match="mesh shape"):
        dispatch.resolve_ingest_path(
            "fused", 10_000, 8193, "tpu", batch_size=1 << 20, mesh=True
        )
    with pytest.raises(ValueError, match="10001"):
        dispatch.resolve_ingest_path("fused", 10_001, 8193, "tpu")
    assert dispatch.resolve_ingest_path(
        "fused", 10_000, 8193, "tpu", batch_size=1 << 10
    ) == "fused"
    # the commit-path resolver keeps naming ITS mesh blockers the same
    # way (shared reason-string convention)
    bad_mesh = _MeshStub(("x", "y"), {"x": 2, "y": 4})
    with pytest.raises(ValueError, match=r"\('x', 'y'\)"):
        dispatch.resolve_commit_path("fused", "tpu", mesh=bad_mesh)


def test_aggregator_explicit_fused_raises_with_reason():
    with pytest.raises(ValueError, match="mesh shape"):
        TPUAggregator(num_metrics=M + 1, config=CFG, ingest_path="fused")


# -- fused path end-to-end through the aggregator -------------------------- #


def test_aggregator_fused_end_to_end_matches_scatter():
    rng = np.random.default_rng(23)
    n = 3000
    ids = rng.integers(0, M, n).astype(np.int32)
    values = rng.lognormal(5, 2, n).astype(np.float32)

    accs = {}
    for path in ("fused", "scatter"):
        agg = TPUAggregator(
            num_metrics=M, config=CFG, ingest_path=path, transport="raw"
        )
        mid = agg.registry.id_for("m0")
        assert mid == 0
        agg.record_batch(ids, values)
        agg.flush(force=True)
        accs[path] = np.asarray(agg._acc)
        agg.close()
    np.testing.assert_array_equal(accs["fused"], accs["scatter"])
    assert accs["fused"].sum() == n


# -- staging-ring drain (satellite 4) -------------------------------------- #


def test_ring_drain_clears_every_inflight_slot():
    ring = IngestStagingRing(64, depth=3, chunk_samples=16)
    for k in range(2):  # two slots in flight, third never staged
        ring.stage(
            np.full(40, k, np.int32), np.ones(40, np.float32)
        )
    assert sum(s is not None for s in ring._inflight) == 2
    ring.drain()
    assert all(s is None for s in ring._inflight)
    ring.drain()  # idempotent


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_close_racing_inflight_upload_drains_both_slots():
    """A worker killed by the agg.xfer_worker fault hook between items
    leaves the double-buffered ring with in-flight uploads (and a queued
    item).  close() must drain BOTH slots before the final interval
    commits — and conserve every recorded sample exactly."""
    inj = FaultInjector()
    inj.plan("agg.xfer_worker", "raise", on_call=2)
    agg = TPUAggregator(
        num_metrics=16, config=CFG, transport="raw", batch_size=32
    )
    agg.fault_injector = inj
    mid = agg.registry.id_for("m")

    # first flush: the worker processes the item (staging ring slots now
    # hold in-flight device arrays), then dies at the loop top
    n1 = 8 * 32 * 2 + 17  # two full super-chunks + a ragged tail
    agg.record_batch(np.full(n1, mid, np.int32), np.ones(n1, np.float32))
    agg.flush()
    deadline = __import__("time").monotonic() + 5.0
    while (agg._xfer_thread is not None and agg._xfer_thread.is_alive()
           and __import__("time").monotonic() < deadline):
        __import__("time").sleep(0.01)
    assert not agg._xfer_thread.is_alive()
    ring = agg._staging_ring
    assert ring is not None
    assert any(s is not None for s in ring._inflight)

    # second batch sits queued behind the dead worker until close()'s
    # forced flush respawns it
    n2 = 100
    agg.record_batch(np.full(n2, mid, np.int32), np.ones(n2, np.float32))
    agg.close()
    assert all(s is None for s in agg._staging_ring._inflight)
    assert agg.collect(reset=False).metrics["m_count"] == float(n1 + n2)
