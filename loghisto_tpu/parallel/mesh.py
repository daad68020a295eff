"""Device mesh construction for distributed histogram aggregation.

The reference has no distributed surface at all (SURVEY.md §2 census); this
module supplies the communication backbone the TPU design adds: a named
2-axis mesh

    ("stream", "metric")

where the *stream* axis shards the sample firehose (data parallelism — each
device buckets its own shard of samples, valid because histograms are
order-free and mergeable) and the *metric* axis shards the dense
``[num_metrics, num_buckets]`` accumulator rows (tensor parallelism — for
10k+ metric configs whose dense tensor shouldn't be replicated).  Merges
ride ``psum`` over the stream axis (ICI within a slice, DCN across
slices); percentile extraction then runs row-parallel on the metric axis.

``TPUAggregator``'s packed ingest on a mesh keeps one flat partial per
(stream, metric) device: each launch's triples split over the stream
axis (``triple_sharding``), each device merges its slice collective-free,
and the settle before any read merges the partials with ONE stream psum.
The interval commit's cells split over the stream axis the same way
(``cell_sharding``); ``stream_runs`` lays both out, one contiguous run
of the rows per stream slice.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

STREAM_AXIS = "stream"
METRIC_AXIS = "metric"

# -- canonical carry shardings ---------------------------------------------- #
# Every device carry in the sharded commit pipeline uses one of these
# four layouts; the committer, the lifecycle/anomaly managers, and the
# checkpoint restore all build placements through them so the layouts
# cannot drift apart.

def row_vector_sharding(mesh: Mesh) -> NamedSharding:
    """int32 [M] carries (the lifecycle activity vector)."""
    return NamedSharding(mesh, PartitionSpec(METRIC_AXIS))


def acc_sharding(mesh: Mesh) -> NamedSharding:
    """[M, B] carries (accumulator, interval histogram)."""
    return NamedSharding(mesh, PartitionSpec(METRIC_AXIS, None))


def ring_sharding(mesh: Mesh) -> NamedSharding:
    """[S, M, B] / [K, M, B] carries (tier rings, baseline profiles)."""
    return NamedSharding(mesh, PartitionSpec(None, METRIC_AXIS, None))


def bank_weight_sharding(mesh: Mesh) -> NamedSharding:
    """f32 [K, M] carries (baseline bank weight mass)."""
    return NamedSharding(mesh, PartitionSpec(None, METRIC_AXIS))


def cell_sharding(mesh: Mesh) -> NamedSharding:
    """Staged interval cell chunks [N]: split over the stream axis so
    each device scatters its slice and ONE psum merges the deltas."""
    return NamedSharding(mesh, PartitionSpec(STREAM_AXIS))


def pool_sharding(mesh: Mesh) -> NamedSharding:
    """int32 [total_pages, page_size] page pools: each metric shard owns
    a contiguous arena of pool rows (its own zero page at the arena
    base), so the paged scatter runs shard-local under shard_map."""
    return NamedSharding(mesh, PartitionSpec(METRIC_AXIS, None))


def triple_sharding(mesh: Mesh) -> NamedSharding:
    """Translated commit triples [N, 3]: split over the stream axis
    like cell chunks — each device scatters its slice into a local pool
    delta and ONE psum merges them (int32 ⇒ order-independent)."""
    return NamedSharding(mesh, PartitionSpec(STREAM_AXIS, None))


def stream_runs(width: int, head: np.ndarray, pad,
                slices: int = 1) -> np.ndarray:
    """A new int32 host array of ``width`` rows for an upload split over
    ``slices`` stream shards: ``head`` cut into ``slices`` contiguous
    runs, one at the start of each of the ``slices`` equal slices of the
    width, every slice's tail filled with ``pad`` (a scalar, or one value
    per column of a 2-D ``head``).  With one slice it is ``head`` then
    ``pad``.  Staged uploads never reuse a host buffer: ``jax.device_put``
    may alias one (the CPU backend does) or still be reading it after it
    returns (an async H2D copy), so a buffer handed to it is never
    written again."""
    out = np.empty((width,) + head.shape[1:], dtype=np.int32)
    parts = out.reshape((slices, width // slices) + head.shape[1:])
    share = -(-len(head) // slices)
    for s in range(slices):
        run = head[s * share:(s + 1) * share]
        parts[s, :len(run)] = run
        tail = parts[s, len(run):]
        if np.ndim(pad):
            # a column at a time: a broadcast row fill is several times
            # slower on the host
            for col, value in enumerate(pad):
                tail[:, col] = value
        else:
            tail[...] = pad
    return out


def sharded_zeros(shape, sharding=None) -> jax.Array:
    """int32 zeros created in place under ``sharding`` (each device
    makes its own shards).  ``jax.device_put`` of a ``jnp.zeros`` would
    first build the whole array on one device — at 10k x 8193 a
    16-slot ring is 5.2 GB there before it is split."""
    import jax.numpy as jnp

    if sharding is None:
        return jnp.zeros(shape, dtype=jnp.int32)
    return jax.jit(
        lambda: jnp.zeros(shape, dtype=jnp.int32), out_shardings=sharding
    )()


def make_mesh(
    stream: Optional[int] = None,
    metric: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ("stream", "metric") mesh.

    Defaults to all local devices on the stream axis — the right default
    for the firehose workload, where ingest bandwidth is the bottleneck.
    """
    devices = list(devices if devices is not None else jax.devices())
    if stream is None:
        if len(devices) % metric:
            raise ValueError(
                f"{len(devices)} devices not divisible by metric={metric}"
            )
        stream = len(devices) // metric
    n = stream * metric
    if n > len(devices):
        raise ValueError(
            f"mesh {stream}x{metric} needs {n} devices, have {len(devices)}"
        )
    grid = np.asarray(devices[:n]).reshape(stream, metric)
    return Mesh(grid, (STREAM_AXIS, METRIC_AXIS))
