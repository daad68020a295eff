"""Compile the main path's device programs for a TPU v5e that is described,
not attached: the chip's own compiler refuses what interpret mode accepts
(unaligned blocks, scalar VMEM stores, primitives with no Mosaic lowering).

Widths are the BASELINE configs[1] deployment: 10,000 metrics x 8,193
buckets (bucket_limit 4096) int32, batches of 2^20 samples.  Nothing runs;
each case only lowers and compiles.  The topology is described inside a
module fixture (one process may hold the TPU library at a time, so never
at import), and the persistent compilation cache is off while it is up: a
compile for a described chip is written to the cache but cannot be read
back without one.
"""

from __future__ import annotations

import os

import pytest

M = 10_000
BUCKET_LIMIT = 4096
B = 2 * BUCKET_LIMIT + 1
N = 1 << 20


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        from jax.experimental import topologies

        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype="int32"):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *args):
    import jax

    return jax.jit(fn).lower(*args).compile()


def _kernel_cases():
    from loghisto_tpu.ops import (
        anomaly, fused_ingest, lifecycle, paged_store, pallas_kernels,
        sparse_ingest, window,
    )

    bl = BUCKET_LIMIT
    return {
        "fused_ingest": (
            lambda a, i, v: fused_ingest.fused_ingest_batch(
                a, i, v, bl, interpret=False),
            [(M, B), (N,), ((N,), "float32")],
        ),
        "window_merge": (
            lambda r, m: window.window_merge_pallas(r, m, interpret=False),
            [(8, M, B), (8,)],
        ),
        "row_ingest": (
            lambda a, i, v: pallas_kernels.pallas_row_ingest_batch(
                a, i, v, bl, interpret=False),
            [(1, B), (N,), ((N,), "float32")],
        ),
        "sparse_ingest": (
            lambda a, p: sparse_ingest.pallas_sparse_ingest(
                a, p, bl, interpret=False),
            [(M, B), (1 << 16, 3)],
        ),
        "paged_scatter": (
            lambda pool, p: paged_store.pallas_paged_scatter(
                pool, p, interpret=False),
            [(1 << 16, paged_store.PAGE_SIZE), (1 << 14, 3)],
        ),
        "compact_rows": (
            lambda a, p: lifecycle.compact_rows_pallas(
                a, p, interpret=False),
            [(M, B), (M,)],
        ),
        "divergence": (
            lambda c, n, pmf, cdf: anomaly.divergence_pallas(
                c, n, pmf, cdf, interpret=False),
            [(M, B), (M,), ((M, B), "float32"), ((M, B), "float32")],
        ),
    }


@pytest.mark.parametrize(
    "name",
    ["fused_ingest", "window_merge", "row_ingest",
     "sparse_ingest", "paged_scatter", "compact_rows", "divergence"],
)
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [
        _shape(one_chip, *s) if isinstance(s[0], tuple)
        else _shape(one_chip, s)
        for s in shapes
    ]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("path", ["scatter", "sort"])
def test_xla_ingest_step_compiles_for_v5e(one_chip, path):
    """The raw-transport ingest steps that are plain XLA (no Pallas
    call), at the deployment's widths, donating the [M, B] accumulator
    in place: the dense raw path the aggregator runs before its density
    probe moves it to the sparse transport."""
    import jax

    from loghisto_tpu.ops.dispatch import ingest_step_fn

    step = ingest_step_fn(path)
    compiled = jax.jit(
        lambda a, i, v: step(a, i, v, BUCKET_LIMIT), donate_argnums=0
    ).lower(
        _shape(one_chip, (M, B)), _shape(one_chip, (N,)),
        _shape(one_chip, (N,), "float32"),
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    # the accumulator's buffer (tile-padded on the chip) is updated in
    # place, not copied
    alias = compiled.memory_analysis().alias_size_in_bytes
    assert alias >= M * B * 4, alias


def test_fused_commit_program_compiles_for_v5e(one_chip):
    """The single-device fused commit (final-chunk snapshot variant, the
    one every interval ends with) at chip_smoke.py's retention: tiers of
    16 and 8 slots, the window merge auto picks on TPU.  It must fit one
    chip's 16 GB (with the Pallas merge it needs 17.9 GB)."""
    from loghisto_tpu.ops.commit import (
        COMMIT_CHUNK, make_fused_commit_snapshot_fn,
    )
    from loghisto_tpu.ops.window import resolve_merge_path

    slots = (16, 8)
    fn = make_fused_commit_snapshot_fn(
        len(slots), BUCKET_LIMIT,
        merge_path=resolve_merge_path("auto", "tpu", mesh=False),
    )
    s = one_chip
    args = (
        _shape(s, (M, B)),
        tuple(_shape(s, (k, M, B)) for k in slots),
        _shape(s, (len(slots),)),
        _shape(s, (len(slots),)),
        _shape(s, (COMMIT_CHUNK,)),
        _shape(s, (COMMIT_CHUNK,)),
        _shape(s, (COMMIT_CHUNK,)),
        tuple(_shape(s, (1, k), "bool") for k in slots),
    )
    mem = fn.lower(*args).compile().memory_analysis()
    resident = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    )
    assert resident < 16e9, resident


def test_sharded_commit_merges_once_per_chunk_on_v5e_2x2(topo):
    """The sharded fused commit on a 2x2 ("stream", "metric") mesh: the
    jaxpr audit counts one stream merge per chunk (one ``lax.psum``
    call, traced as one ``psum_invariant`` per operand); the chip's
    compiler must emit it as one all-reduce."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from loghisto_tpu.ops.commit import (
        COMMIT_CHUNK, make_sharded_fused_commit_fn,
    )
    from loghisto_tpu.parallel.mesh import (
        acc_sharding, cell_sharding, make_mesh, ring_sharding,
    )

    mesh = make_mesh(stream=2, metric=2, devices=topo.devices)
    slots = (16, 8)
    fn = make_sharded_fused_commit_fn(mesh, len(slots))
    rep = NamedSharding(mesh, P())
    args = (
        _shape(acc_sharding(mesh), (M, B)),
        tuple(_shape(ring_sharding(mesh), (k, M, B)) for k in slots),
        _shape(rep, (len(slots),)),
        _shape(rep, (len(slots),)),
        *(_shape(cell_sharding(mesh), (COMMIT_CHUNK,)) for _ in range(3)),
    )
    hlo = fn.lower(*args).compile().as_text()
    all_reduces = re.findall(r"\sall-reduce(?:-start)?\(", hlo)
    assert len(all_reduces) == 1, all_reduces


def test_flat_sparse_merge_and_settle_compile_for_v5e(one_chip):
    """The packed merge into the flat per-interval delta at THE merge
    width (65,536 triples): no reshape or copy of any operand as large
    as the accumulator, and under 64 MB of temp (the scatter into the
    [M, B] accumulator relayouts it through 327.9 MB).  The settle that
    adds the delta in once an interval holds at most one relayout of
    it (a row-major delta would transpose through three)."""
    import math
    import re

    from loghisto_tpu.ops.sparse_ingest import (
        make_flat_sparse_ingest_fn, settle_ingest_delta,
    )

    flat = _shape(one_chip, (M * B,))
    merge = make_flat_sparse_ingest_fn(BUCKET_LIMIT, B).lower(
        flat, _shape(one_chip, (1 << 16, 3))).compile()
    moved = re.findall(r"= s32\[([\d,]*)\]\S* (?:reshape|copy)\(",
                       merge.as_text())
    dims = [math.prod(int(d) for d in m.split(",") if d) for m in moved]
    assert all(n < M * B for n in dims), moved
    assert merge.memory_analysis().temp_size_in_bytes < 64 << 20

    settle = settle_ingest_delta.lower(
        _shape(one_chip, (M, B)), flat).compile()
    assert settle.memory_analysis().temp_size_in_bytes < 2 * M * B * 4


def test_mesh_flat_merge_and_settle_compile_for_v5e_2x2(topo):
    """The mesh's packed merge and settle at 10k x 8193 on a 2x2
    ("stream", "metric") mesh, a 65,536-triple slice a stream shard (the
    aggregator's launch width): the merge runs no collective and copies or
    relayouts no operand as large as a metric shard's rows; the settle
    merges the stream partials with exactly one all-reduce."""
    import math
    import re

    from jax.sharding import NamedSharding

    from loghisto_tpu.ops.sparse_ingest import (
        make_sharded_flat_sparse_ingest_fn, make_sharded_settle_fn,
        DELTA_SPEC,
    )
    from loghisto_tpu.parallel.mesh import (
        acc_sharding, make_mesh, triple_sharding,
    )

    mesh = make_mesh(stream=2, metric=2, devices=topo.devices)
    shard = M // 2 * B
    partial = _shape(NamedSharding(mesh, DELTA_SPEC), (2, M * B))
    merge = make_sharded_flat_sparse_ingest_fn(
        mesh, BUCKET_LIMIT, B, M // 2).lower(
        partial, _shape(triple_sharding(mesh), (2 << 16, 3))).compile()
    hlo = merge.as_text()
    assert not re.search(
        r"all-reduce|all-gather|collective-permute|all-to-all", hlo)
    moved = re.findall(r"= s32\[([\d,]*)\]\S* (?:reshape|copy)\(", hlo)
    dims = [math.prod(int(d) for d in m.split(",") if d) for m in moved]
    assert all(n < shard for n in dims), moved
    assert merge.memory_analysis().temp_size_in_bytes < 64 << 20

    settle = make_sharded_settle_fn(mesh).lower(
        _shape(acc_sharding(mesh), (M, B)), partial).compile()
    all_reduces = re.findall(r"\sall-reduce(?:-start)?\(",
                             settle.as_text())
    assert len(all_reduces) == 1, all_reduces
