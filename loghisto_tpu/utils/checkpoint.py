"""Checkpoint / resume for metric state (SURVEY.md §5.4).

The reference has no persistence: its lifetime stores die with the process
(metrics.go:111-126).  Long-running TPU aggregation wants better — the
dense bucket tensor plus lifetime scalars fully determine the statistics,
and both serialize trivially.

Format: a single .npz with JSON-encoded name tables, written atomically
(temp file + rename) so a crash mid-write can't corrupt the last good
snapshot.  Covers the host MetricSystem (lifetime counter store +
histogram aggregate store) and the TPUAggregator (dense accumulator,
registry names, lifetime aggregates).  Interval caches are deliberately
NOT persisted: in-flight samples of a crashed interval follow the
shed-don't-block philosophy of the reference.
"""

from __future__ import annotations

import json
import os
import tempfile
from typing import Optional

import numpy as np

from loghisto_tpu.metrics import MetricSystem

# v2: optional interval-seq watermark rides the payload so crash
# recovery can replay ONLY journal intervals past the snapshotted state
# (resilience/recovery.py).  v1 files load fine — watermark None.
# v3: paged aggregators (PR 14) snapshot portably — `agg_acc` carries
# the canonical dense decode of the page pool + host spill (so any
# storage mode restores any save), and `pg_codec_names` records each
# row's codec choice so a paged restore re-pins resolutions instead of
# re-deriving them from the first post-restore interval.  v1/v2 files
# load fine — codecs None.  The same two legs make v3 files
# MESH-SHAPE-portable (PR 18): decode_dense gathers the sharded pool
# to one host tensor on save, and restore replays through the target
# store's own translate/commit, which assigns pages against the
# target mesh's per-shard arenas — a 2x4 save restores onto 1x8, an
# unsharded store, or a dense aggregator, codec choices intact.
FORMAT_VERSION = 3


def save(
    path: str,
    metric_system: Optional[MetricSystem] = None,
    aggregator=None,
    lifecycle=None,
    anomaly=None,
    seq_watermark: Optional[int] = None,
    fault_injector=None,
) -> None:
    """Atomically snapshot lifetime state to `path` (.npz).

    ``seq_watermark`` stamps the snapshot with the last committed
    interval seq folded into this state; ``fault_injector`` exposes the
    two crash-window hook sites ("checkpoint.write" before the payload
    lands, "checkpoint.rename" after fsync but before the atomic
    rename) for the chaos harness.

    ``lifecycle`` (a lifecycle.LifecycleManager) additionally persists
    the activity vector, the lifetime churn counters, and the registry
    generation.  Overflow metric state needs no special handling — the
    catch-all series are ordinary named rows, so they ride the
    accumulator / lifetime-aggregate payloads like any other metric
    (tests/test_checkpoint.py round-trips this).

    ``anomaly`` (an anomaly.AnomalyManager) persists the EWMA baseline
    banks (profile + weight mass) so drift detection resumes warm
    after a restart instead of re-learning every baseline; rows are
    remapped by NAME on restore like every other per-row payload."""
    payload = {"version": np.int64(FORMAT_VERSION)}
    if seq_watermark is not None:
        payload["seq_watermark"] = np.int64(seq_watermark)

    if metric_system is not None:
        with metric_system._store_lock:
            counters = dict(metric_system._counter_store)
            agg = {
                name: (entry[0], entry[1])
                for name, entry in metric_system._histogram_agg_store.items()
            }
        payload["ms_counter_names"] = _names_arr(counters.keys())
        payload["ms_counter_values"] = np.array(
            list(counters.values()), dtype=np.uint64
        )
        payload["ms_agg_names"] = _names_arr(agg.keys())
        payload["ms_agg_sums"] = np.array(
            [v[0] for v in agg.values()], dtype=np.float64
        )
        payload["ms_agg_counts"] = np.array(
            [v[1] for v in agg.values()], dtype=np.uint64
        )
        if agg and all(isinstance(v[0], int) for v in agg.values()):
            # go_compat sums are exact uint64s that float64 would clip
            # above 2^53; keep the exact form alongside
            payload["ms_agg_sums_u64"] = np.array(
                [v[0] & 0xFFFFFFFFFFFFFFFF for v in agg.values()],
                dtype=np.uint64,
            )

    if aggregator is not None:
        # force: the preagg transport holds cells in a host store between
        # interval boundaries, and a cooling-down device gates non-forced
        # raw flushes — either way a plain flush() could silently omit
        # staged samples from the snapshot
        aggregator.flush(force=True)
        with aggregator._dev_lock:
            if getattr(aggregator, "paged", None) is not None:
                # canonical dense decode of pool + host spill: the
                # snapshot is storage-portable (a dense aggregator
                # restores a paged save and vice versa); codec choices
                # ride alongside so a paged restore re-pins resolutions
                acc = aggregator.paged.decode_dense(include_spill=True)
                payload["pg_codec_names"] = _names_arr(
                    aggregator.paged.codec_names()
                )
            else:
                # canonical dense layout: every ingest_path shares it, so
                # snapshots stay portable across them
                acc = np.asarray(aggregator._dense_acc_locked())
                # a spilled interval keeps part of its counts in the
                # host int64 fold — snapshotting only the device tensor
                # would silently lose them; the combined snapshot is
                # int64
                if aggregator._spill is not None:
                    acc = acc.astype(np.int64) + aggregator._spill
        with aggregator._agg_lock:
            agg_items = sorted(aggregator._agg.items())
        payload["agg_acc"] = acc
        # freed lifecycle slots serialize as JSON null and restore as
        # holes (their rows are zero — eviction folds then clears them)
        payload["agg_names"] = _names_arr(aggregator.registry.names())
        payload["agg_registry_generation"] = np.int64(
            getattr(aggregator.registry, "generation", 0)
        )
        payload["agg_ids"] = np.array([k for k, _ in agg_items], dtype=np.int64)
        payload["agg_sums"] = np.array(
            [v[0] for _, v in agg_items], dtype=np.float64
        )
        payload["agg_counts"] = np.array(
            [v[1] for _, v in agg_items], dtype=np.uint64
        )

    if lifecycle is not None:
        st = lifecycle.state_dict()
        payload["lc_last_active"] = st["last_active"]
        payload["lc_counters"] = np.array(
            [
                st["evicted_series"],
                st["overflowed_samples"],
                st["evictions"],
                st["compactions"],
            ],
            dtype=np.int64,
        )

    if anomaly is not None:
        st = anomaly.state_dict()
        payload["an_prof"] = st["prof"]
        payload["an_wsum"] = st["wsum"]
        payload["an_counters"] = np.array(
            [st["scored_intervals"]], dtype=np.int64
        )

    directory = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=directory, suffix=".tmp")
    try:
        if fault_injector is not None:
            fault_injector.check("checkpoint.write")
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, **payload)
            f.flush()
            os.fsync(f.fileno())  # data durable before the rename
        if fault_injector is not None:
            fault_injector.check("checkpoint.rename")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def restore(
    path: str,
    metric_system: Optional[MetricSystem] = None,
    aggregator=None,
    lifecycle=None,
    anomaly=None,
) -> Optional[int]:
    """Restore lifetime state saved by save().  Loads into the provided
    objects (merging over their current lifetime state).  With
    ``lifecycle``, the saved activity vector is remapped through the
    same by-name row mapping as the accumulator and the churn counters
    are restored; the target registry's generation is advanced to at
    least the saved one, so caches keyed on (generation, length) from a
    pre-restore world can never serve post-restore ids.

    Returns the interval-seq watermark the snapshot was stamped with
    (v2), or None for v1 files / unstamped saves — existing callers
    ignore the return value, recovery replay keys on it."""
    with np.load(path, allow_pickle=False) as data:
        version = int(data["version"])
        if version > FORMAT_VERSION:
            raise ValueError(f"unsupported checkpoint version {version}")
        seq_watermark = (
            int(data["seq_watermark"]) if "seq_watermark" in data else None
        )

        if metric_system is not None and "ms_counter_names" in data:
            names = _arr_names(data["ms_counter_names"])
            values = data["ms_counter_values"]
            agg_names = _arr_names(data["ms_agg_names"])
            sums = data["ms_agg_sums"]
            counts = data["ms_agg_counts"]
            # go_compat stores need INT sums (the uint64 mask would
            # TypeError on floats); prefer the exact u64 sidecar
            go_compat = metric_system.config.go_compat
            if go_compat and "ms_agg_sums_u64" in data:
                sums = data["ms_agg_sums_u64"]
            with metric_system._store_lock:
                for name, value in zip(names, values):
                    metric_system._counter_store[name] = int(value)
                for name, s, c in zip(agg_names, sums, counts):
                    metric_system._histogram_agg_store[name] = [
                        int(s) if go_compat else float(s), int(c)
                    ]

        if aggregator is not None and "agg_acc" in data:
            import jax.numpy as jnp

            acc = data["agg_acc"]
            # snapshots carry the canonical dense layout regardless of the
            # saving aggregator's ingest_path; the target may have MORE
            # rows than the snapshot (on_registry_full="grow")
            if (
                acc.shape[1] != aggregator.config.num_buckets
                or acc.shape[0] > aggregator.num_metrics
            ):
                raise ValueError(
                    f"checkpoint accumulator shape {acc.shape} does not "
                    "fit the aggregator's configuration "
                    f"({aggregator.num_metrics}, "
                    f"{aggregator.config.num_buckets})"
                )
            # Remap by NAME, not by row id: the target registry may already
            # hold other names at the checkpoint's ids.  Saved rows are
            # added into the target's rows for their re-registered ids.
            # Registration goes through the aggregator's _id_for so the
            # on_registry_full="grow" policy applies to restores exactly as
            # it does to live ingestion; a shed name (-1, past max_metrics)
            # drops that row with a warning rather than aborting mid-way.
            saved_names = _arr_names(data["agg_names"])
            row_map = []
            for saved_id, name in enumerate(saved_names):
                if name is None:
                    # lifecycle-freed slot: its row was folded into an
                    # overflow metric and zeroed before the save
                    continue
                new_id = aggregator._id_for(name)
                if new_id < 0:
                    import logging

                    logging.getLogger("loghisto_tpu").warning(
                        "restore: metric %r shed (registry at max_metrics)",
                        name,
                    )
                    continue
                row_map.append((saved_id, new_id))
            # Rows populated via record_batch with raw ids that were never
            # registered carry no name; map them identity (same row id) so
            # their counts survive the round trip — but ONLY when that row
            # is not claimed by a named metric (in the target registry or
            # by the named remap above): merging an unnamed row into a
            # named metric would silently corrupt its histogram.
            named_rows = {saved_id for saved_id, _ in row_map}
            named_targets = {new_id for _, new_id in row_map}
            target_named_rows = len(aggregator.registry)
            for saved_id in range(acc.shape[0]):
                if saved_id in named_rows or not acc[saved_id].any():
                    continue
                if saved_id in named_targets or saved_id < target_named_rows:
                    import logging

                    logging.getLogger("loghisto_tpu").warning(
                        "restore: dropping unnamed checkpoint row %d — its "
                        "row id is owned by a named metric in the target; "
                        "register names before saving to keep such rows",
                        saved_id,
                    )
                    continue
                row_map.append((saved_id, saved_id))
            remapped = np.zeros(
                (aggregator.num_metrics, acc.shape[1]), dtype=acc.dtype
            )
            for saved_id, new_id in row_map:
                remapped[new_id] += acc[saved_id]
            if getattr(aggregator, "paged", None) is not None:
                pg = aggregator.paged
                with aggregator._dev_lock:
                    # re-pin the saved codec choices first (by the same
                    # by-name row map), so the recommit below encodes
                    # each row at its saved resolution instead of
                    # re-deriving from this one delta's occupancy
                    if "pg_codec_names" in data:
                        saved_codecs = _arr_names(data["pg_codec_names"])
                        for saved_id, new_id in row_map:
                            if (
                                saved_id < len(saved_codecs)
                                and saved_codecs[saved_id] is not None
                            ):
                                pg.set_row_codec(
                                    new_id, saved_codecs[saved_id]
                                )
                    rows, cols = np.nonzero(remapped)
                    weights = remapped[rows, cols].astype(np.int64)
                    # same headroom rule as the dense branch: restored
                    # counts never increment _interval_ingested, so big
                    # deltas take the store's exact host spill
                    live_max = pg.max_cell()
                    if (
                        int(weights.max(initial=0))
                        + live_max
                        + aggregator.spill_threshold
                        + aggregator.batch_size
                    ) >= 2**31:
                        pg.spill_cells(
                            rows.astype(np.int64),
                            cols.astype(np.int64),
                            weights,
                        )
                    else:
                        packed = np.empty((len(rows), 3), dtype=np.int32)
                        packed[:, 0] = rows
                        packed[:, 1] = (
                            cols.astype(np.int64)
                            - aggregator.config.bucket_limit
                        )
                        packed[:, 2] = weights
                        pg.commit(packed)
            else:
                with aggregator._dev_lock:
                    _restore_dense_delta(aggregator, remapped)
            id_remap = dict(row_map)
            with aggregator._agg_lock:
                agg_compat = aggregator.config.go_compat
                for mid, s, c in zip(
                    data["agg_ids"], data["agg_sums"], data["agg_counts"]
                ):
                    new_id = id_remap.get(int(mid))
                    if new_id is None:
                        continue
                    entry = aggregator._agg.setdefault(new_id, [0, 0])
                    # int sums under go_compat (the uint64 mask applied at
                    # collect would TypeError on floats)
                    entry[0] += int(s) if agg_compat else float(s)
                    entry[1] += int(c)
            if "agg_registry_generation" in data:
                saved_gen = int(data["agg_registry_generation"])
                reg = aggregator.registry
                with reg._lock:
                    reg._generation = max(reg._generation, saved_gen)
            if lifecycle is not None and "lc_last_active" in data:
                saved_la = np.asarray(
                    data["lc_last_active"], dtype=np.int32
                )
                la = np.zeros(aggregator.num_metrics, dtype=np.int32)
                for saved_id, new_id in id_remap.items():
                    if saved_id < len(saved_la) and new_id < len(la):
                        la[new_id] = saved_la[saved_id]
                counters = data["lc_counters"]
                lifecycle.load_state({
                    "last_active": la,
                    "evicted_series": int(counters[0]),
                    "overflowed_samples": int(counters[1]),
                    "evictions": int(counters[2]),
                    "compactions": int(counters[3]),
                })
            if anomaly is not None and "an_prof" in data:
                # bank rows remap through the same by-name id map as
                # the accumulator — a baseline never lands on a row its
                # name doesn't own in the target registry
                saved_prof = np.asarray(data["an_prof"], dtype=np.float32)
                saved_wsum = np.asarray(data["an_wsum"], dtype=np.float32)
                k, ms_rows, b = saved_prof.shape
                m = aggregator.num_metrics
                prof = np.zeros((k, m, b), dtype=np.float32)
                wsum = np.zeros((k, m), dtype=np.float32)
                for saved_id, new_id in id_remap.items():
                    if saved_id < ms_rows and new_id < m:
                        prof[:, new_id] = saved_prof[:, saved_id]
                        wsum[:, new_id] = saved_wsum[:, saved_id]
                counters = data["an_counters"]
                anomaly.load_state({
                    "prof": prof,
                    "wsum": wsum,
                    "scored_intervals": int(counters[0]),
                })
    return seq_watermark


def _restore_dense_delta(aggregator, remapped: np.ndarray) -> None:
    """Merge a remapped canonical-dense delta into a dense aggregator
    (caller holds _dev_lock).  int64 snapshots (taken mid-spill) or
    counts too large for the int32 device tensor merge into the host
    spill instead — collect() folds spill + device exactly.  The live
    accumulator's hottest cell joins the headroom check: restored
    counts never increment _interval_ingested, so successive restores
    (merging several worker checkpoints) would otherwise stack toward
    2^31 unseen by the spill trigger."""
    import jax.numpy as jnp

    live = aggregator._dense_acc_locked()
    live_max = int(jnp.max(live))
    if (
        int(remapped.max(initial=0))
        + live_max
        + aggregator.spill_threshold
        + aggregator.batch_size
    ) >= 2**31:
        if aggregator._spill is None:
            aggregator._spill = remapped.astype(np.int64)
        else:
            aggregator._spill += remapped.astype(np.int64)
    else:
        # re-shard the host rows onto the live accumulator's layout
        # first: checkpoints save gathered host arrays, so a snapshot
        # taken on one mesh shape restores onto any other (or none)
        delta = jnp.asarray(remapped.astype(np.int32))
        live_sharding = getattr(live, "sharding", None)
        if (
            getattr(aggregator, "mesh", None) is not None
            and live_sharding is not None
        ):
            import jax

            delta = jax.device_put(delta, live_sharding)
        aggregator._acc = live + delta


def _names_arr(names) -> np.ndarray:
    return np.frombuffer(
        json.dumps(list(names)).encode(), dtype=np.uint8
    ).copy()


def _arr_names(arr: np.ndarray) -> list[str]:
    return json.loads(arr.tobytes().decode())
