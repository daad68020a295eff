"""Property-based tests (hypothesis): the numeric contracts hold for ALL
inputs, not just the golden values — codec round-trip accuracy, percentile
ordering/monotonicity, merge associativity."""

import numpy as np
from hypothesis import given, settings, strategies as st

from loghisto_tpu.config import INT16_BUCKET_LIMIT
from loghisto_tpu.ops.codec import (
    compress_np,
    compress_scalar,
    decompress_np,
    decompress_scalar,
)
from loghisto_tpu.ops.stats import percentiles_sparse

finite_values = st.floats(
    min_value=-1e100, max_value=1e100,
    allow_nan=False, allow_infinity=False,
)


@given(finite_values)
@settings(max_examples=300, deadline=None)
def test_codec_roundtrip_contract(v):
    rt = decompress_scalar(compress_scalar(v))
    if abs(v) >= 1.01:
        # the 1%-relative contract only holds for |v| >~ 1 (codec.py docstring)
        assert abs(rt / v - 1) <= 0.01
    elif abs(v) >= 0.51:
        # transition zone: worst-case error ~0.005*(1+|v|) (up to ~1.3%
        # relative near 0.51, still within half a bucket width absolute)
        assert abs(rt - v) <= 0.0101 * (1 + abs(v))
    else:
        # documented low-precision zone: absolute error stays tiny
        assert abs(rt - v) <= 0.01


@given(finite_values)
@settings(max_examples=200, deadline=None)
def test_codec_sign_and_monotonicity_local(v):
    b = compress_scalar(v)
    assert (b > 0) == (v >= 0.005 and b != 0) or b == 0 or (v < 0) == (b < 0)
    # monotone: a strictly larger magnitude never gets a smaller bucket
    if 0 <= v < 1e99:
        assert compress_scalar(v * 1.5 + 0.1) >= b


@given(st.lists(finite_values, min_size=1, max_size=200))
@settings(max_examples=100, deadline=None)
def test_scalar_vector_codec_agree(values):
    arr = np.array(values, dtype=np.float64)
    got = compress_np(arr)
    want = np.array([compress_scalar(float(v)) for v in arr], dtype=np.int16)
    np.testing.assert_array_equal(got, want)


@given(
    st.dictionaries(
        st.integers(-INT16_BUCKET_LIMIT, INT16_BUCKET_LIMIT),
        st.integers(1, 10_000),
        min_size=1, max_size=50,
    ),
    st.lists(st.floats(0.0, 1.0), min_size=1, max_size=10),
)
@settings(max_examples=100, deadline=None)
def test_percentiles_are_monotone_and_within_range(bucket_counts, ps):
    buckets = np.fromiter(bucket_counts.keys(), dtype=np.int64)
    counts = np.fromiter(bucket_counts.values(), dtype=np.uint64)
    ps_sorted = np.sort(np.array(ps))
    out = percentiles_sparse(buckets, counts, ps_sorted)
    # monotone in p
    assert (np.diff(out) >= -1e-12).all()
    # every output is an existing bucket representative (exact: both sides
    # come from the same decompress on the same integers)
    reps = set(decompress_np(buckets).tolist())
    for v in out:
        assert float(v) in reps
    # p=0 -> min representative, p=1 -> max representative
    if ps_sorted[0] == 0.0:
        assert out[0] == decompress_np(buckets).min()
    if ps_sorted[-1] == 1.0:
        assert out[-1] == decompress_np(buckets).max()


@given(
    st.lists(st.floats(-1e6, 1e6, allow_nan=False), min_size=1, max_size=100),
    st.lists(st.floats(-1e6, 1e6, allow_nan=False), min_size=1, max_size=100),
)
@settings(max_examples=50, deadline=None)
def test_histogram_merge_is_order_free(a, b):
    """Bucketing a+b together equals bucketing separately and summing the
    sparse maps — the property every psum merge in the framework rides."""
    from collections import Counter

    ca = Counter(compress_np(np.array(a)).tolist())
    cb = Counter(compress_np(np.array(b)).tolist())
    cab = Counter(compress_np(np.array(a + b)).tolist())
    assert ca + cb == cab


@given(
    st.dictionaries(
        st.integers(-500, 500), st.integers(1, 100_000),
        min_size=1, max_size=30,
    ),
    st.lists(st.floats(0.0, 1.0), min_size=1, max_size=6),
)
@settings(max_examples=60, deadline=None)
def test_dense_and_sparse_tiers_agree(bucket_counts, ps):
    """The device tier's dense CDF scan and the host tier's sparse scan
    must select identical bucket representatives for any histogram."""
    import jax.numpy as jnp

    from loghisto_tpu.ops.stats import dense_stats

    limit = 512
    buckets = np.fromiter(bucket_counts.keys(), dtype=np.int64)
    counts = np.fromiter(bucket_counts.values(), dtype=np.uint64)
    ps_arr = np.sort(np.array(ps, dtype=np.float64))

    sparse = percentiles_sparse(buckets, counts, ps_arr)

    acc = np.zeros((1, 2 * limit + 1), dtype=np.int32)
    acc[0, buckets + limit] = counts
    dense = np.asarray(
        dense_stats(jnp.asarray(acc), ps_arr, limit)["percentiles"][0]
    )
    # float32 representatives vs float64: compare within float32 eps
    np.testing.assert_allclose(dense, sparse, rtol=1e-5)


@given(
    st.lists(
        st.tuples(st.integers(-3, 12), st.floats(-1e6, 1e6, allow_nan=False)),
        min_size=1, max_size=300,
    )
)
@settings(max_examples=60, deadline=None)
def test_sort_ingest_always_matches_scatter(samples):
    import jax.numpy as jnp

    from loghisto_tpu.ops.ingest import ingest_batch
    from loghisto_tpu.ops.sort_ingest import sort_ingest_batch

    m, bl = 8, 32
    ids = np.array([s[0] for s in samples], dtype=np.int32)
    values = np.array([s[1] for s in samples], dtype=np.float32)
    acc = jnp.zeros((m, 2 * bl + 1), dtype=jnp.int32)
    ref = np.asarray(ingest_batch(acc, ids, values, bl))
    got = np.asarray(sort_ingest_batch(acc, ids, values, bl))
    np.testing.assert_array_equal(got, ref)
    # and accumulated onto the first batch's counts, same contract
    np.testing.assert_array_equal(
        np.asarray(sort_ingest_batch(jnp.asarray(got), ids, values, bl)),
        2 * ref,
    )


@given(
    st.lists(
        st.tuples(st.integers(0, 6), st.integers(-200, 200),
                  st.integers(1, 5000)),
        min_size=1, max_size=100,
    ),
    st.lists(st.floats(0.0, 1.0), min_size=1, max_size=6),
)
@settings(max_examples=60, deadline=None)
def test_hierarchical_dense_stats_matches_int64_oracle(entries, ps):
    """The device tier's two-level rank search must select the same
    buckets as the exact int64 host oracle (dense_stats_np) for any
    histogram — including block-boundary and single-bucket cases."""
    import jax.numpy as jnp

    from loghisto_tpu.ops.stats import dense_stats, dense_stats_np

    m, bl = 7, 256
    acc = np.zeros((m, 2 * bl + 1), dtype=np.int32)
    for mid, bucket, count in entries:
        acc[mid, np.clip(bucket, -bl, bl) + bl] += count
    ps_arr = np.asarray(sorted(set(ps)), dtype=np.float32)
    got = dense_stats(jnp.asarray(acc), ps_arr, bl)
    want = dense_stats_np(acc, ps_arr.astype(np.float64), bl)
    np.testing.assert_array_equal(np.asarray(got["counts"]), want["counts"])
    np.testing.assert_allclose(
        np.asarray(got["percentiles"]), want["percentiles"], rtol=2e-6
    )


@given(
    st.lists(  # batches of (id, value) pairs; ids beyond m or negative
        st.lists(  # must be dropped identically by both designs
            st.tuples(st.integers(-3, 24), st.floats(-1e6, 1e6,
                                                     allow_nan=False)),
            min_size=1, max_size=200,
        ),
        min_size=1, max_size=4,
    ),
)
@settings(max_examples=40, deadline=None)
def test_interval_mesh_matches_single_device_for_any_stream(batches):
    """Property: for ANY batch sequence (out-of-range ids included), the
    interval-amortized mesh design accumulates bit-identically to a
    single-device fold of the same stream — the sharding offsets, psum
    deferral, partial zeroing, and drop handling introduce no cases.
    Fixed shapes so the mesh program compiles once per session."""
    import jax
    import jax.numpy as jnp

    from loghisto_tpu.ops.ingest import ingest_batch
    from loghisto_tpu.parallel.aggregator import (
        make_interval_distributed_step,
        make_sharded_accumulator,
    )
    from loghisto_tpu.parallel.mesh import make_mesh

    m, bl, batch_n = 16, 64, 256
    if "step" not in _interval_cache:
        mesh = make_mesh(stream=2, metric=2)
        _interval_cache["step"] = make_interval_distributed_step(
            mesh, m, bl, np.array([0.5, 1.0], dtype=np.float32),
            batch_size=batch_n,
        )
        _interval_cache["mesh"] = mesh
    ingest, collect, make_partial = _interval_cache["step"]
    mesh = _interval_cache["mesh"]

    partial = make_partial()
    single = jnp.zeros((m, 2 * bl + 1), dtype=jnp.int32)
    for pairs in batches:
        ids = np.full(batch_n, -1, dtype=np.int32)  # pad rows dropped
        values = np.zeros(batch_n, dtype=np.float32)
        for i, (mid, v) in enumerate(pairs):
            ids[i] = mid
            values[i] = np.float32(v)
        partial = ingest(partial, jnp.asarray(ids), jnp.asarray(values))
        single = ingest_batch(single, jnp.asarray(ids),
                              jnp.asarray(values), bl)
    acc = make_sharded_accumulator(mesh, m, 2 * bl + 1)
    acc, partial, _stats = collect(acc, partial)
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(single))


_interval_cache: dict = {}
