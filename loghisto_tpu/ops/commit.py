"""Fused interval commit: one donated-carry program for the aggregator
fold plus every retention tier's open-slot scatter.

The paper's core claim is that log-bucket histograms merge by elementwise
addition, so every device consumer of an interval is payable with ONE
pass over the interval's sparse bucket cells.  Before this module each
committed interval fanned out into ~5+ separate dispatches — the
aggregator bridge's weighted scatter (parallel/aggregator.py) plus one
``_scatter_cells_jit`` launch per TimeWheel tier (window/store.py), each
behind its own lock and each re-uploading the same host-built cell
arrays.  ``make_fused_commit_fn`` collapses that to a single jitted
program over a donated carry pytree ``(aggregator_acc, ring_0..N)``:

  * the cell arrays ``(ids, idx, weights)`` are uploaded once,
  * the aggregator fold and every tier's open-slot scatter (plus the
    slot clear on ring wrap) execute in the same XLA program,
  * per-tier slot indices and keep factors arrive as TRACED int32
    operands (the jnp analog of Pallas scalar prefetch), so tier
    rotation across intervals never recompiles — one executable serves
    every interval for the lifetime of the shapes.

``CellStagingRing`` is the async H2D front end: its ``stage()`` pads a
chunk into fresh host arrays, issues ``jax.device_put`` and returns
immediately, so interval N+1's cell transfer overlaps interval N's
commit dispatch (the same overlap design as the aggregator's raw flush
path, extended to the bridge).  A host buffer handed to ``device_put``
is never written again: the upload may alias it or read it late.

The orchestration (locks, spill policy, tier metadata) lives in
``loghisto_tpu.commit.IntervalCommitter``; this module stays pure
jax/numpy so it is importable and testable without the runtime classes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import PartitionSpec as P

from loghisto_tpu.config import PRECISION
from loghisto_tpu.ops.ingest import sanitize_ids
from loghisto_tpu.ops.paged_store import paged_scatter_batch
from loghisto_tpu.ops.stats import dense_cdf
from loghisto_tpu.ops.window import window_snapshot
from loghisto_tpu.parallel.mesh import METRIC_AXIS, STREAM_AXIS, stream_runs

# Fixed commit launch width, matching the aggregator bridge's merge
# chunk: one compiled executable serves every interval; a typical
# interval is one launch, a 10k-metric worst case a handful.
COMMIT_CHUNK = 1 << 16

# Drop sentinel for pad (and shed) cells: far out of every row range, so
# each scatter's mode="drop" sheds it — same design as sanitize_ids and
# the wheel's _DROP_ID.
DROP_ID = np.int32(2**30)


def _open_slot_slab(ring, slot, keep):
    """The open slot's [M, B] slab, times its keep factor (0 clears it
    on ring wrap)."""
    return jax.lax.dynamic_index_in_dim(ring, slot, keepdims=False) * keep


def _fold_open_slot(ring, slot, keep, ids, idx, weights):
    """``ring[slot] = ring[slot] * keep`` then ``ring[slot, ids, idx] +=
    weights``, on the one [M, B] slab.  A scatter straight into the
    [S, M, B] ring makes XLA's TPU backend copy the whole ring to a
    linear layout (S x 328 MB of temporary HBM at 10k x 8193); a slab
    costs one slab."""
    slab = _open_slot_slab(ring, slot, keep)
    slab = slab.at[ids, idx].add(weights, mode="drop")
    return jax.lax.dynamic_update_index_in_dim(ring, slab, slot, 0)


def _add_open_slot(ring, slot, keep, delta):
    """``ring[slot] = ring[slot] * keep + delta`` for a dense [M, B]
    delta (the sharded programs' merged interval cells)."""
    slab = _open_slot_slab(ring, slot, keep) + delta
    return jax.lax.dynamic_update_index_in_dim(ring, slab, slot, 0)


@functools.lru_cache(maxsize=None)
def make_fused_commit_fn(
    num_tiers: int,
    track_activity: bool = False,
    track_baseline: bool = False,
):
    """Build the fused commit program for ``num_tiers`` retention tiers.
    Cached per (tier count, activity flag, baseline flag): the jitted
    program is shape-polymorphic, so every committer with the same
    signature shares one jit object (and its per-shape executable
    cache) instead of recompiling.

    Returns ``commit(acc, rings, slots, keeps, ids, idx, weights) ->
    (acc, rings)`` where

      acc     int32 [M, B]            — aggregator accumulator (donated)
      rings   tuple of int32 [S_t, M_t, B] — tier rings (donated)
      slots   int32 [T]               — each tier's open slot (traced,
                                        so rotation never recompiles)
      keeps   int32 [T]               — 0 to clear the open slot first
                                        (ring wrap), 1 to keep it
      ids     int32 [N]               — metric rows; DROP_ID pads/sheds
      idx     int32 [N]               — dense bucket column in [0, B)
      weights int32 [N]               — per-cell counts (0 on pads)

    All consumers add the SAME cells: the aggregator fold is
    ``acc[ids, idx] += weights`` and each tier's open-slot scatter is
    ``ring[slot, ids, idx] += weights`` after multiplying the slot by
    its keep factor (x1 = no-op, x0 = the ring-wrap clear, fused into
    the same program instead of a separate ``_open_slot_jit`` launch).
    Integer scatter-adds are order-independent, so the result is
    bit-identical to the fan-out path (tests/test_commit.py pins this).

    Out-of-range rows drop: the accumulator may have grown past a ring's
    row count (registry growth), in which case those cells land in the
    accumulator and fall off every ring — the same semantics the
    separate paths had.

    With ``track_activity`` the signature gains a donated int32 [M]
    ``last_active`` carry and a traced int32 ``epoch`` — inserted after
    ``rings`` and after ``weights`` respectively — and the program
    additionally stamps ``last_active[ids] = max(., epoch)`` over the
    interval's touched rows.  Same cells, same dispatch: the lifecycle
    subsystem's activity vector costs zero extra launches, the
    identical fusion economics as the snapshot variant's commit-time
    CDFs.

    With ``track_baseline`` the signature further gains a donated int32
    [M, B] ``ihist`` carry (after ``last_active``) and a trailing
    traced int32 ``ifirst``: the program folds the SAME cells into the
    interval histogram after multiplying it by ``ifirst`` (0 on an
    interval's first chunk — clearing the previous interval — 1 on
    later chunks).  The completed ``ihist`` feeds the drift engine's
    EWMA baseline update in the final-chunk snapshot variant; like the
    activity stamp, it rides the commit dispatch for free.

    Full ordering with both flags:
    ``commit(acc, rings, last_active, ihist, slots, keeps, ids, idx,
    weights, epoch, ifirst) -> (acc, rings, last_active, ihist)``.
    """
    donate = tuple(range(2 + int(track_activity) + int(track_baseline)))

    @functools.partial(jax.jit, donate_argnums=donate)
    def fused_commit(*args):
        it = iter(args)
        acc = next(it)
        rings = next(it)
        last_active = next(it) if track_activity else None
        ihist = next(it) if track_baseline else None
        slots = next(it)
        keeps = next(it)
        ids = next(it)
        idx = next(it)
        weights = next(it)
        epoch = next(it) if track_activity else None
        ifirst = next(it) if track_baseline else None

        acc = acc.at[ids, idx].add(weights, mode="drop")
        new_rings = []
        for t in range(num_tiers):
            ring = rings[t]
            ring = _fold_open_slot(ring, slots[t], keeps[t], ids, idx,
                                   weights)
            new_rings.append(ring)
        out = [acc, tuple(new_rings)]
        if track_activity:
            out.append(last_active.at[ids].max(epoch, mode="drop"))
        if track_baseline:
            ihist = ihist * ifirst
            out.append(ihist.at[ids, idx].add(weights, mode="drop"))
        return tuple(out)

    return fused_commit


@functools.lru_cache(maxsize=None)
def make_fused_commit_snapshot_fn(
    num_tiers: int,
    bucket_limit: int,
    precision: int = PRECISION,
    merge_path: str = "jnp",
    track_activity: bool = False,
    track_baseline: bool = False,
):
    """The fused commit program's FINAL-chunk variant: same donated-carry
    fold as ``make_fused_commit_fn`` plus, in the SAME dispatch, the
    query engine's snapshot emission — per tier, the CDF/counts/sums of
    every materialized window view over the post-commit ring, and the
    aggregator accumulator's own CDF payload.

    Extra operand ``masks``: a tuple of bool ``[V, S_t]`` arrays, one per
    tier — the post-interval trailing-window slot masks (full span first,
    then pinned windows), computed host-side by simulating the slot
    close-out BEFORE dispatch.  Masks are traced, so slot rotation never
    recompiles; only a changed view count V (a new pinned window — rare)
    retraces.

    Returns ``(acc, rings, tier_payloads, acc_payload)`` where payload
    dicts carry cdf/counts/sums as in ``ops.window.window_snapshot`` /
    ``ops.stats.dense_cdf``.  The payload outputs are fresh (never
    donated), which is what lets the store publish them as a lock-free
    immutable handle while later commits keep donating the carries.

    ``track_activity`` threads the lifecycle's donated ``last_active``
    carry and traced ``epoch`` through exactly as in
    ``make_fused_commit_fn`` — the final chunk of an interval then pays
    the scatter fold, every snapshot payload, AND the activity stamp in
    one dispatch.

    ``track_baseline`` threads the drift engine's carries: the donated
    int32 [M, B] ``ihist`` interval histogram (as in
    ``make_fused_commit_fn``), a donated ``banks = (prof f32 [K, M, B],
    wsum f32 [K, M])`` EWMA baseline-bank pytree, and trailing traced
    scalars ``ifirst, bank, decay, min_count``.  Because this is the
    interval's FINAL chunk, the completed interval histogram decays
    into baseline bank ``bank`` here (``ops.anomaly.ewma_bank_update``;
    rows under ``min_count`` skip the update) — the whole EWMA baseline
    maintenance rides the commit dispatch, zero extra launches.

    Full ordering with both flags:
    ``commit(acc, rings, last_active, ihist, banks, slots, keeps, ids,
    idx, weights, epoch, masks, ifirst, bank, decay, min_count) ->
    (acc, rings, last_active, ihist, banks, tier_payloads,
    acc_payload)``.
    """
    if track_baseline:
        # Deferred: ops.anomaly -> ops.lifecycle -> ops.commit cycle.
        from loghisto_tpu.ops.anomaly import ewma_bank_update
    donate = tuple(range(2 + int(track_activity) + 2 * int(track_baseline)))

    @functools.partial(jax.jit, donate_argnums=donate)
    def fused_commit_snapshot(*args):
        it = iter(args)
        acc = next(it)
        rings = next(it)
        last_active = next(it) if track_activity else None
        ihist = next(it) if track_baseline else None
        banks = next(it) if track_baseline else None
        slots = next(it)
        keeps = next(it)
        ids = next(it)
        idx = next(it)
        weights = next(it)
        epoch = next(it) if track_activity else None
        masks = next(it)
        if track_baseline:
            ifirst = next(it)
            bank = next(it)
            decay = next(it)
            min_count = next(it)

        acc = acc.at[ids, idx].add(weights, mode="drop")
        new_rings = []
        payloads = []
        for t in range(num_tiers):
            ring = rings[t]
            ring = _fold_open_slot(ring, slots[t], keeps[t], ids, idx,
                                   weights)
            new_rings.append(ring)
            payloads.append(
                window_snapshot(ring, masks[t], bucket_limit, precision,
                                merge_path)
            )
        out = [acc, tuple(new_rings)]
        if track_activity:
            out.append(last_active.at[ids].max(epoch, mode="drop"))
        if track_baseline:
            ihist = ihist * ifirst
            ihist = ihist.at[ids, idx].add(weights, mode="drop")
            out.append(ihist)
            out.append(ewma_bank_update(banks, ihist, bank, decay,
                                        min_count))
        acc_payload = dense_cdf(acc, bucket_limit, precision)
        out.extend((tuple(payloads), acc_payload))
        return tuple(out)

    return fused_commit_snapshot


def _shard_local_deltas(acc, rings, ids, idx, weights, track_activity):
    """Shard-local body shared by the sharded commit factories: scatter
    THIS stream-shard's cell slice into dense per-shard deltas, then run
    ONE ``psum`` over the stream axis so every downstream consumer
    (accumulator fold, tier scatters, activity stamp, interval
    histogram) works shard-local on the merged interval delta.

    Rows are translated to shard-local coordinates with the aggregator's
    proven idiom (parallel/aggregator.py): ``ids - shard * local_rows``,
    sanitized BEFORE the drop-mode scatter because JAX wraps negative
    indices ahead of the bounds check.  Cells owned by other shards land
    out of the local range and drop — exactly the single-device
    ``mode="drop"`` semantics, applied per shard.

    When a ring's row count differs from the accumulator's (registry
    growth past the wheel's fixed rows), shard k of the ring covers
    DIFFERENT global rows than shard k of the accumulator, so a second
    delta is built at the ring width; all deltas (and the activity
    touch-marker vector) merge in a single ``psum`` call, keeping the
    collective count at one per dispatch.

    Returns ``(acc_delta, {ring_rows: ring_delta}, touched_or_None)``.
    """
    shard = jax.lax.axis_index(METRIC_AXIS)
    acc_rows = acc.shape[0]
    acc_ids = sanitize_ids(ids - shard * acc_rows)
    parts = {
        "acc": jnp.zeros_like(acc).at[acc_ids, idx].add(weights,
                                                        mode="drop")
    }
    ring_rows = sorted({r.shape[1] for r in rings} - {acc_rows})
    for rows in ring_rows:
        rids = sanitize_ids(ids - shard * rows)
        parts[f"ring{rows}"] = (
            jnp.zeros((rows, acc.shape[1]), acc.dtype)
            .at[rids, idx].add(weights, mode="drop")
        )
    if track_activity:
        # the single-device path stamps every in-range id, even at
        # weight 0, so "delta != 0" is NOT a faithful activity signal;
        # a psum'd touch-marker vector is exactly equivalent
        parts["touched"] = (
            jnp.zeros((acc_rows,), jnp.int32)
            .at[acc_ids].max(1, mode="drop")
        )
    parts = jax.lax.psum(parts, STREAM_AXIS)
    return (
        parts["acc"],
        {rows: parts[f"ring{rows}"] for rows in ring_rows},
        parts.get("touched"),
    )


def _sharded_commit_specs(track_activity, track_baseline):
    """(carry in/out specs, carry count) shared by both sharded
    factories — the donated-carry prefix of the operand list."""
    specs = [P(METRIC_AXIS, None), P(None, METRIC_AXIS, None)]
    if track_activity:
        specs.append(P(METRIC_AXIS))
    if track_baseline:
        specs.append(P(METRIC_AXIS, None))
    return specs


@functools.lru_cache(maxsize=None)
def make_sharded_fused_commit_fn(
    mesh,
    num_tiers: int,
    track_activity: bool = False,
    track_baseline: bool = False,
):
    """``make_fused_commit_fn`` for metric-row-sharded carries under the
    ("stream", "metric") mesh: identical operand ordering, donation
    ranges, and results (integer scatter-adds and the int32 psum are
    order-independent, so the output is bit-identical to the
    single-device fused path — tests/test_mesh_commit.py pins this).

    The staged cell chunk arrives stream-sharded (``P(STREAM_AXIS)``,
    see ``CellStagingRing``): each device scatters its slice into dense
    shard-local deltas, ONE ``psum`` over the stream axis merges them,
    and the accumulator fold, every tier's open-slot scatter, the
    activity stamp, and the interval-histogram fold then execute
    shard-local on the ``P(METRIC_AXIS)``-rowed carries — one collective
    and one dispatch per chunk, preserving the <= 2-dispatches/interval
    budget.  Cached per (mesh, tiers, flags); shape-polymorphic like the
    single-device factory (per-shard row counts come from local operand
    shapes), so registry growth never needs a new cache entry."""
    donate = tuple(range(2 + int(track_activity) + int(track_baseline)))

    def sharded_fused_commit(*args):
        it = iter(args)
        acc = next(it)
        rings = next(it)
        last_active = next(it) if track_activity else None
        ihist = next(it) if track_baseline else None
        slots = next(it)
        keeps = next(it)
        ids = next(it)
        idx = next(it)
        weights = next(it)
        epoch = next(it) if track_activity else None
        ifirst = next(it) if track_baseline else None

        delta, ring_deltas, touched = _shard_local_deltas(
            acc, rings, ids, idx, weights, track_activity
        )
        acc = acc + delta
        new_rings = []
        for t in range(num_tiers):
            ring = rings[t]
            rd = ring_deltas.get(ring.shape[1], delta)
            ring = _add_open_slot(ring, slots[t], keeps[t], rd)
            new_rings.append(ring)
        out = [acc, tuple(new_rings)]
        if track_activity:
            out.append(jnp.where(touched > 0,
                                 jnp.maximum(last_active, epoch),
                                 last_active))
        if track_baseline:
            out.append(ihist * ifirst + delta)
        return tuple(out)

    carry_specs = _sharded_commit_specs(track_activity, track_baseline)
    in_specs = tuple(carry_specs) + (
        P(), P(), P(STREAM_AXIS), P(STREAM_AXIS), P(STREAM_AXIS),
    )
    if track_activity:
        in_specs += (P(),)      # epoch
    if track_baseline:
        in_specs += (P(),)      # ifirst
    return jax.jit(
        shard_map(
            sharded_fused_commit, mesh=mesh,
            in_specs=in_specs, out_specs=tuple(carry_specs),
        ),
        donate_argnums=donate,
    )


@functools.lru_cache(maxsize=None)
def make_sharded_fused_commit_snapshot_fn(
    mesh,
    num_tiers: int,
    bucket_limit: int,
    precision: int = PRECISION,
    merge_path: str = "jnp",
    track_activity: bool = False,
    track_baseline: bool = False,
):
    """``make_fused_commit_snapshot_fn`` under the mesh: the sharded
    fold of ``make_sharded_fused_commit_fn`` plus, in the SAME dispatch,
    the EWMA baseline-bank decay and the snapshot emission — all
    shard-local after the single stream psum, because every emitted
    quantity (masked slot merge, row cumsum CDF, per-row sums matvec,
    per-row EWMA decay) is row-independent.  Payload outputs keep the
    metric-row sharding, so the published snapshot handle serves sparse
    per-row gathers from the owning shard without replicating the CDF
    tensors."""
    if track_baseline:
        # Deferred: ops.anomaly -> ops.lifecycle -> ops.commit cycle.
        from loghisto_tpu.ops.anomaly import ewma_bank_update
    donate = tuple(range(2 + int(track_activity) + 2 * int(track_baseline)))

    def sharded_fused_commit_snapshot(*args):
        it = iter(args)
        acc = next(it)
        rings = next(it)
        last_active = next(it) if track_activity else None
        ihist = next(it) if track_baseline else None
        banks = next(it) if track_baseline else None
        slots = next(it)
        keeps = next(it)
        ids = next(it)
        idx = next(it)
        weights = next(it)
        epoch = next(it) if track_activity else None
        masks = next(it)
        if track_baseline:
            ifirst = next(it)
            bank = next(it)
            decay = next(it)
            min_count = next(it)

        delta, ring_deltas, touched = _shard_local_deltas(
            acc, rings, ids, idx, weights, track_activity
        )
        acc = acc + delta
        new_rings = []
        payloads = []
        for t in range(num_tiers):
            ring = rings[t]
            rd = ring_deltas.get(ring.shape[1], delta)
            ring = _add_open_slot(ring, slots[t], keeps[t], rd)
            new_rings.append(ring)
            payloads.append(
                window_snapshot(ring, masks[t], bucket_limit, precision,
                                merge_path)
            )
        out = [acc, tuple(new_rings)]
        if track_activity:
            out.append(jnp.where(touched > 0,
                                 jnp.maximum(last_active, epoch),
                                 last_active))
        if track_baseline:
            ihist = ihist * ifirst + delta
            out.append(ihist)
            out.append(ewma_bank_update(banks, ihist, bank, decay,
                                        min_count))
        acc_payload = dense_cdf(acc, bucket_limit, precision)
        out.extend((tuple(payloads), acc_payload))
        return tuple(out)

    carry_specs = _sharded_commit_specs(track_activity, track_baseline)
    bank_specs = (P(None, METRIC_AXIS, None), P(None, METRIC_AXIS))
    in_specs = tuple(carry_specs)
    if track_baseline:
        in_specs += (bank_specs,)
    in_specs += (P(), P(), P(STREAM_AXIS), P(STREAM_AXIS), P(STREAM_AXIS))
    if track_activity:
        in_specs += (P(),)      # epoch
    in_specs += (P(),)          # masks (prefix broadcast over the tuple)
    if track_baseline:
        in_specs += (P(), P(), P(), P())  # ifirst, bank, decay, min_count
    tier_payload_spec = {
        "cdf": P(None, METRIC_AXIS, None),
        "counts": P(None, METRIC_AXIS),
        "sums": P(None, METRIC_AXIS),
    }
    acc_payload_spec = {
        "cdf": P(METRIC_AXIS, None),
        "counts": P(METRIC_AXIS),
        "sums": P(METRIC_AXIS),
    }
    out_specs = tuple(carry_specs)
    if track_baseline:
        out_specs += (bank_specs,)
    out_specs += (
        tuple(dict(tier_payload_spec) for _ in range(num_tiers)),
        acc_payload_spec,
    )
    return jax.jit(
        shard_map(
            sharded_fused_commit_snapshot, mesh=mesh,
            in_specs=in_specs, out_specs=out_specs,
        ),
        donate_argnums=donate,
    )


@functools.lru_cache(maxsize=None)
def make_paged_fused_commit_fn(num_tiers: int, track_activity: bool = False):
    """The fused commit program for a PAGED aggregator (r18): the pool
    replaces the dense accumulator carry, and the interval's cells ride
    the dispatch twice — as dense ``(ids, idx, weights)`` for every
    tier's open-slot scatter (tier rings stay dense), and as
    host-translated ``(slot, offset, count)`` triples for the pool
    scatter (``paged_scatter_batch``; translation against the page
    table is a host decision, exactly as in ``PagedStore.commit``).

    Returns ``commit(pool, rings, [last_active], slots, keeps, ids,
    idx, weights, triples, [epoch]) -> (pool, rings, [last_active])``
    with the same donation, drop-sentinel, and traced-scalar contracts
    as ``make_fused_commit_fn`` — one dispatch still pays the
    aggregator fold, every tier, and the activity stamp."""
    donate = tuple(range(2 + int(track_activity)))

    @functools.partial(jax.jit, donate_argnums=donate)
    def paged_fused_commit(*args):
        it = iter(args)
        pool = next(it)
        rings = next(it)
        last_active = next(it) if track_activity else None
        slots = next(it)
        keeps = next(it)
        ids = next(it)
        idx = next(it)
        weights = next(it)
        triples = next(it)
        epoch = next(it) if track_activity else None

        pool = paged_scatter_batch(pool, triples)
        new_rings = []
        for t in range(num_tiers):
            ring = rings[t]
            ring = _fold_open_slot(ring, slots[t], keeps[t], ids, idx,
                                   weights)
            new_rings.append(ring)
        out = [pool, tuple(new_rings)]
        if track_activity:
            out.append(last_active.at[ids].max(epoch, mode="drop"))
        return tuple(out)

    return paged_fused_commit


@functools.lru_cache(maxsize=None)
def make_paged_fused_commit_snapshot_fn(
    num_tiers: int,
    bucket_limit: int,
    precision: int = PRECISION,
    merge_path: str = "jnp",
    track_activity: bool = False,
):
    """Final-chunk variant of ``make_paged_fused_commit_fn``: the same
    fold plus every tier's window-snapshot emission.  Unlike the dense
    variant there is NO acc payload output — the pool's counts live
    behind per-row codecs, so accumulator stats are served by the paged
    query/stats engine (``PagedStore.query``), not a dense CDF ridden
    on the commit.  Ordering: ``commit(pool, rings, [last_active],
    slots, keeps, ids, idx, weights, triples, [epoch], masks) ->
    (pool, rings, [last_active], tier_payloads)``."""
    donate = tuple(range(2 + int(track_activity)))

    @functools.partial(jax.jit, donate_argnums=donate)
    def paged_fused_commit_snapshot(*args):
        it = iter(args)
        pool = next(it)
        rings = next(it)
        last_active = next(it) if track_activity else None
        slots = next(it)
        keeps = next(it)
        ids = next(it)
        idx = next(it)
        weights = next(it)
        triples = next(it)
        epoch = next(it) if track_activity else None
        masks = next(it)

        pool = paged_scatter_batch(pool, triples)
        new_rings = []
        payloads = []
        for t in range(num_tiers):
            ring = rings[t]
            ring = _fold_open_slot(ring, slots[t], keeps[t], ids, idx,
                                   weights)
            new_rings.append(ring)
            payloads.append(
                window_snapshot(ring, masks[t], bucket_limit, precision,
                                merge_path)
            )
        out = [pool, tuple(new_rings)]
        if track_activity:
            out.append(last_active.at[ids].max(epoch, mode="drop"))
        out.append(tuple(payloads))
        return tuple(out)

    return paged_fused_commit_snapshot


def _paged_shard_local_deltas(
    pool, rings, last_active, ids, idx, weights, triples, shard_pages,
    track_activity,
):
    """Shard-local body shared by the sharded PAGED commit factories.
    The staged triples carry GLOBAL pool slots; re-basing by ``shard *
    shard_pages`` puts this shard's own arena at [1, shard_pages) (its
    local zero page at 0) and every other shard's triples out of range,
    so ``paged_scatter_batch``'s validity mask implements ownership for
    free.  Ring/activity deltas re-use the dense sharded idiom; ONE
    ``psum`` over the stream axis merges every part."""
    shard = jax.lax.axis_index(METRIC_AXIS)
    local = jnp.stack(
        [triples[:, 0] - shard * shard_pages, triples[:, 1], triples[:, 2]],
        axis=1,
    )
    parts = {"pool": paged_scatter_batch(jnp.zeros_like(pool), local)}
    for rows in sorted({r.shape[1] for r in rings}):
        rids = sanitize_ids(ids - shard * rows)
        parts[f"ring{rows}"] = (
            jnp.zeros((rows, rings[0].shape[2]), rings[0].dtype)
            .at[rids, idx].add(weights, mode="drop")
        )
    if track_activity:
        la_rows = last_active.shape[0]
        lids = sanitize_ids(ids - shard * la_rows)
        parts["touched"] = (
            jnp.zeros((la_rows,), jnp.int32).at[lids].max(1, mode="drop")
        )
    return jax.lax.psum(parts, STREAM_AXIS)


def _sharded_paged_commit_specs(track_activity):
    """Donated-carry prefix specs for the sharded paged factories:
    (pool arenas over metric, tier ring rows over metric, [activity
    rows over metric])."""
    specs = [P(METRIC_AXIS, None), P(None, METRIC_AXIS, None)]
    if track_activity:
        specs.append(P(METRIC_AXIS))
    return specs


@functools.lru_cache(maxsize=None)
def make_sharded_paged_fused_commit_fn(
    mesh, shard_pages: int, num_tiers: int, track_activity: bool = False
):
    """``make_paged_fused_commit_fn`` under the ("stream", "metric")
    mesh: identical operand ordering and results (int32 scatter-adds
    and the single stream psum are order-independent, so the committed
    pool is bit-identical to the single-device paged fused path).  The
    pool carry splits per metric-shard arena (``P(METRIC_AXIS, None)``,
    each shard's zero page at its arena base), staged cells and triples
    arrive stream-sharded, and everything downstream of the one psum is
    shard-local — one collective, one dispatch per chunk."""
    donate = tuple(range(2 + int(track_activity)))

    def sharded_paged_fused_commit(*args):
        it = iter(args)
        pool = next(it)
        rings = next(it)
        last_active = next(it) if track_activity else None
        slots = next(it)
        keeps = next(it)
        ids = next(it)
        idx = next(it)
        weights = next(it)
        triples = next(it)
        epoch = next(it) if track_activity else None

        parts = _paged_shard_local_deltas(
            pool, rings, last_active, ids, idx, weights, triples,
            shard_pages, track_activity,
        )
        pool = pool + parts["pool"]
        new_rings = []
        for t in range(num_tiers):
            ring = rings[t]
            rd = parts[f"ring{ring.shape[1]}"]
            ring = _add_open_slot(ring, slots[t], keeps[t], rd)
            new_rings.append(ring)
        out = [pool, tuple(new_rings)]
        if track_activity:
            out.append(jnp.where(parts["touched"] > 0,
                                 jnp.maximum(last_active, epoch),
                                 last_active))
        return tuple(out)

    carry_specs = _sharded_paged_commit_specs(track_activity)
    in_specs = tuple(carry_specs) + (
        P(), P(), P(STREAM_AXIS), P(STREAM_AXIS), P(STREAM_AXIS),
        P(STREAM_AXIS, None),
    )
    if track_activity:
        in_specs += (P(),)      # epoch
    return jax.jit(
        shard_map(
            sharded_paged_fused_commit, mesh=mesh,
            in_specs=in_specs, out_specs=tuple(carry_specs),
        ),
        donate_argnums=donate,
    )


@functools.lru_cache(maxsize=None)
def make_sharded_paged_fused_commit_snapshot_fn(
    mesh,
    shard_pages: int,
    num_tiers: int,
    bucket_limit: int,
    precision: int = PRECISION,
    merge_path: str = "jnp",
    track_activity: bool = False,
):
    """``make_paged_fused_commit_snapshot_fn`` under the mesh: the
    sharded paged fold plus shard-local snapshot emission, payload
    outputs metric-row-sharded like the dense sharded variant."""
    donate = tuple(range(2 + int(track_activity)))

    def sharded_paged_fused_commit_snapshot(*args):
        it = iter(args)
        pool = next(it)
        rings = next(it)
        last_active = next(it) if track_activity else None
        slots = next(it)
        keeps = next(it)
        ids = next(it)
        idx = next(it)
        weights = next(it)
        triples = next(it)
        epoch = next(it) if track_activity else None
        masks = next(it)

        parts = _paged_shard_local_deltas(
            pool, rings, last_active, ids, idx, weights, triples,
            shard_pages, track_activity,
        )
        pool = pool + parts["pool"]
        new_rings = []
        payloads = []
        for t in range(num_tiers):
            ring = rings[t]
            rd = parts[f"ring{ring.shape[1]}"]
            ring = _add_open_slot(ring, slots[t], keeps[t], rd)
            new_rings.append(ring)
            payloads.append(
                window_snapshot(ring, masks[t], bucket_limit, precision,
                                merge_path)
            )
        out = [pool, tuple(new_rings)]
        if track_activity:
            out.append(jnp.where(parts["touched"] > 0,
                                 jnp.maximum(last_active, epoch),
                                 last_active))
        out.append(tuple(payloads))
        return tuple(out)

    carry_specs = _sharded_paged_commit_specs(track_activity)
    in_specs = tuple(carry_specs) + (
        P(), P(), P(STREAM_AXIS), P(STREAM_AXIS), P(STREAM_AXIS),
        P(STREAM_AXIS, None),
    )
    if track_activity:
        in_specs += (P(),)      # epoch
    in_specs += (P(),)          # masks (prefix broadcast over the tuple)
    tier_payload_spec = {
        "cdf": P(None, METRIC_AXIS, None),
        "counts": P(None, METRIC_AXIS),
        "sums": P(None, METRIC_AXIS),
    }
    out_specs = tuple(carry_specs) + (
        tuple(dict(tier_payload_spec) for _ in range(num_tiers)),
    )
    return jax.jit(
        shard_map(
            sharded_paged_fused_commit_snapshot, mesh=mesh,
            in_specs=in_specs, out_specs=out_specs,
        ),
        donate_argnums=donate,
    )


class CellStagingRing:
    """H2D staging for interval cell arrays.

    ``stage()`` pads one chunk to the fixed commit width with drop
    sentinels in freshly allocated host arrays and issues an async
    ``jax.device_put`` — the transfer of the NEXT chunk/interval
    overlaps the commit dispatch of the previous one, because
    ``device_put`` and the jitted commit both return before the device
    work completes.  Upload accounting (``uploads``, ``bytes_uploaded``)
    feeds the committer's H2D-bytes-per-interval gauge.
    """

    def __init__(self, width: int = COMMIT_CHUNK, sharding=None):
        self.width = width
        # under a mesh the cell chunk uploads stream-sharded (each
        # device receives its slice of the staged pad arrays); the
        # sharded commit programs consume it as P(STREAM_AXIS) operands.
        # The cells are split into one run per slice, so every stream
        # shard scatters a share of them, however few there are
        self.sharding = sharding
        self.uploads = 0          # lifetime stage() calls
        self.bytes_uploaded = 0   # lifetime H2D bytes issued

    def stage(self, ids: np.ndarray, idx: np.ndarray, weights: np.ndarray):
        """Pad one cell chunk (len <= width) and start its async upload;
        returns the device arrays."""
        n = len(ids)
        if n > self.width:
            raise ValueError(f"chunk of {n} cells exceeds staging width "
                             f"{self.width}")
        slices = (1 if self.sharding is None
                  else self.sharding.mesh.shape[STREAM_AXIS])
        host = (
            stream_runs(self.width, ids, DROP_ID, slices),
            stream_runs(self.width, idx, 0, slices),
            stream_runs(self.width, weights, 0, slices),
        )
        dev = (
            jax.device_put(host, self.sharding)
            if self.sharding is not None
            else jax.device_put(host)
        )
        self.uploads += 1
        self.bytes_uploaded += 3 * self.width * 4
        return dev


class PagedTripleRing:
    """``CellStagingRing``'s twin for the paged committer's translated
    ``(slot, offset, count)`` triples: same fresh-buffer contract, same
    fixed width (the commit chunk, so one executable serves every
    interval), pad row ``(-1, 0, 0)`` (``paged_scatter_batch`` drops
    slot -1).  Under a mesh the upload splits over the stream axis
    (``triple_sharding``), matching the sharded paged commit's
    ``P(STREAM_AXIS, None)`` operand spec."""

    def __init__(self, width: int = COMMIT_CHUNK, sharding=None):
        self.width = width
        self.sharding = sharding
        self.uploads = 0
        self.bytes_uploaded = 0

    def stage(self, triples: np.ndarray):
        """Pad one translated triple chunk (len <= width) and start its
        async upload; returns the device array."""
        n = len(triples)
        if n > self.width:
            raise ValueError(f"chunk of {n} triples exceeds staging "
                             f"width {self.width}")
        buf = stream_runs(self.width, triples, (-1, 0, 0))
        if self.sharding is not None:
            # collective-free across real jax.distributed processes
            # (every process stages the identical translated chunk)
            from loghisto_tpu.parallel.multihost import global_put

            dev = global_put(buf, self.sharding)
        else:
            dev = jax.device_put(buf)
        self.uploads += 1
        self.bytes_uploaded += buf.nbytes
        return dev
