"""Worker process for the REAL multi-process jax.distributed test
(tests/test_multihost.py::test_two_process_distributed_step).

Each of the two processes owns 4 virtual CPU devices (8 global), builds
the global ("stream", "metric") mesh, feeds its LOCAL sample shard via
make_global_arrays, runs the shard_map distributed step, and checks the
globally-merged counts — proving initialize/global_mesh/make_global_arrays
compose across real process boundaries (VERDICT r1 item 8 / SURVEY §5.8).

Usage: python multihost_worker.py <coordinator_port> <process_id>
Prints "WORKER <pid> OK <total>" on success.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax

# a multi-process CPU worker: never the chip, whatever the environment says
jax.config.update("jax_platforms", "cpu")

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> int:
    port, pid = int(sys.argv[1]), int(sys.argv[2])
    from loghisto_tpu.parallel import multihost

    multihost.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=2,
        process_id=pid,
    )
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()
    assert jax.local_device_count() == 4

    from loghisto_tpu.config import MetricConfig
    from loghisto_tpu.parallel import (
        make_distributed_step,
        make_sharded_accumulator,
    )
    from loghisto_tpu.parallel.multihost import (
        global_mesh,
        local_sample_shard,
        make_global_arrays,
    )

    cfg = MetricConfig(bucket_limit=128)
    mesh = global_mesh(metric=2)
    m, global_batch = 8, 4096
    start, size = local_sample_shard(global_batch)
    assert size == global_batch // 2
    # deterministic global stream: every process derives the same global
    # arrays, slices out its own shard
    rng = np.random.default_rng(0)
    all_ids = rng.integers(0, m, global_batch).astype(np.int32)
    all_values = rng.lognormal(2, 1, global_batch).astype(np.float32)
    gids, gvalues = make_global_arrays(
        mesh, all_ids[start:start + size], all_values[start:start + size]
    )
    step = make_distributed_step(
        mesh, m, cfg.bucket_limit, np.array([0.5, 1.0], dtype=np.float32)
    )
    acc = make_sharded_accumulator(mesh, m, cfg.num_buckets)
    acc, stats = step(acc, gids, gvalues)
    # counts are metric-sharded; each process sees its addressable shards —
    # fetch what is local and all-check the global total via a psum-free
    # host path: every process recomputes the expected per-metric counts
    counts = np.asarray(
        jax.experimental.multihost_utils.process_allgather(
            stats["counts"], tiled=True
        )
    )
    expected = np.bincount(all_ids, minlength=m)
    np.testing.assert_array_equal(counts, expected)
    total = int(counts.sum())
    assert total == global_batch

    # interval-amortized design across the same real process boundary:
    # two collective-free ingests, one psum at collect (VERDICT r3
    # item 3's path must hold multihost, not just single-process)
    from loghisto_tpu.parallel import make_interval_distributed_step

    ingest, collect, make_partial = make_interval_distributed_step(
        mesh, m, cfg.bucket_limit, np.array([0.5, 1.0], dtype=np.float32)
    )
    partial = ingest(make_partial(), gids, gvalues)
    partial = ingest(partial, gids, gvalues)
    acc2 = make_sharded_accumulator(mesh, m, cfg.num_buckets)
    acc2, partial, stats2 = collect(acc2, partial)
    counts2 = np.asarray(
        jax.experimental.multihost_utils.process_allgather(
            stats2["counts"], tiled=True
        )
    )
    np.testing.assert_array_equal(counts2, 2 * expected)

    # paged sharded-commit drill (ISSUE 18): the page-pool substrate
    # spans the same real process boundary.  Every process derives the
    # SAME global packed delta, so the host-side translate step (page
    # table, free lists, codec choices) agrees across processes without
    # coordination; the device scatter + stream psum run inside one
    # shard_map over the global mesh, and decode funnels through
    # multihost.host_gather because the pool is only partially
    # addressable from either process.
    from loghisto_tpu.paging import PagedStore, PagedStoreConfig

    pg = PagedStore(
        m, cfg.bucket_limit, cfg.precision,
        config=PagedStoreConfig(pool_pages=64), mesh=mesh,
    )
    buckets = rng.integers(
        -cfg.bucket_limit, cfg.bucket_limit + 1, global_batch
    ).astype(np.int32)
    packed = np.empty((global_batch, 3), dtype=np.int32)
    packed[:, 0] = all_ids
    packed[:, 1] = buckets
    packed[:, 2] = 1
    applied = pg.commit(packed)
    assert applied == global_batch, applied
    dense = pg.decode_dense(include_spill=True)
    want = np.zeros((m, cfg.num_buckets), dtype=np.int64)
    np.add.at(want, (all_ids, buckets + cfg.bucket_limit), 1)
    np.testing.assert_array_equal(dense, want)
    print(f"WORKER {pid} PAGED OK", flush=True)

    jax.distributed.shutdown()
    print(f"WORKER {pid} OK {total}", flush=True)
    return 0


if __name__ == "__main__":
    import jax.experimental.multihost_utils  # noqa: F401  (import check)

    raise SystemExit(main())
