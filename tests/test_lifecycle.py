"""Metric lifecycle subsystem (the PR-4 tentpole): TTL eviction,
device slot compaction, cardinality control under name churn.  Pins the
registry free-list/generation semantics, zero-extra-dispatch activity
tracking on the fused commit, count-exact overflow folding, bit-identical
survivor percentiles across compaction (oracle = pre-compaction
snapshot, including ring rotation and the open slot), cache/snapshot
invalidation (a query after eviction never serves a dead id), and the
threaded register/evict/query race."""

import datetime as dt
import threading

import numpy as np
import pytest

from loghisto_tpu.commit import IntervalCommitter
from loghisto_tpu.config import MetricConfig
from loghisto_tpu.lifecycle import (
    LifecycleConfig,
    LifecycleManager,
    decide_victims,
    default_overflow_name,
)
from loghisto_tpu.metrics import RawMetricSet
from loghisto_tpu.ops.commit import DROP_ID
from loghisto_tpu.ops.lifecycle import (
    compact_rows,
    compact_rows_pallas,
    make_fold_evict_fn,
    pad_pow2_ids,
)
from loghisto_tpu.parallel.aggregator import TPUAggregator
from loghisto_tpu.registry import MetricRegistry
from loghisto_tpu.window import TimeWheel

pytestmark = pytest.mark.lifecycle

T0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)


def _raw(i, histograms=None, duration=1.0):
    return RawMetricSet(
        time=T0 + dt.timedelta(seconds=i), counters={}, rates={},
        histograms=dict(histograms or {}), gauges={}, duration=duration,
    )


def _pair(
    num_metrics=32,
    bucket_limit=64,
    tiers=((4, 2), (3, 4)),
    config=None,
):
    cfg = MetricConfig(bucket_limit=bucket_limit)
    agg = TPUAggregator(num_metrics=num_metrics, config=cfg)
    wheel = TimeWheel(num_metrics=num_metrics, config=cfg, interval=1.0,
                      tiers=tiers, registry=agg.registry)
    lc = LifecycleManager(agg, wheel, config or LifecycleConfig())
    committer = IntervalCommitter(agg, wheel, lifecycle=lc)
    committer.warmup()
    return committer, agg, wheel, lc


# ---------------------------------------------------------------------- #
# registry: free-list, generation, grow preservation, permutation
# ---------------------------------------------------------------------- #

def test_registry_evict_free_list_reuse():
    r = MetricRegistry(8)
    ids = [r.id_for(n) for n in ("a", "b", "c")]
    assert ids == [0, 1, 2]
    assert r.generation == 0 and r.live_count() == 3

    assert r.evict([1]) == ["b"]
    assert r.generation == 1
    assert r.free_count() == 1 and r.live_count() == 2
    assert r.name_for(1) is None and r.lookup("b") is None
    assert r.names()[1] is None

    # reuse takes the freed slot before growing, and bumps generation
    assert r.id_for("d") == 1
    assert r.generation == 2 and r.free_count() == 0
    # a pure append does NOT bump generation (append-only fast path)
    assert r.id_for("e") == 3
    assert r.generation == 2

    # double-evict / out-of-range ids are ignored
    assert r.evict([99, 1]) == ["d"]
    assert r.evict([1]) == []


def test_registry_grow_preserves_free_list_and_generation():
    r = MetricRegistry(4)
    for n in ("a", "b", "c", "d"):
        r.id_for(n)
    r.evict([1, 2])
    gen, free = r.generation, r.free_count()
    r.grow(16)
    assert r.capacity == 16
    assert r.generation == gen and r.free_count() == free
    # freed slots still reused before the grown tail
    assert r.id_for("x") in (1, 2)


def test_registry_apply_permutation():
    r = MetricRegistry(8)
    for n in ("a", "b", "c", "d"):
        r.id_for(n)
    r.evict([0, 2])
    # live: b@1, d@3 -> dense prefix
    perm = [1, 3] + [int(DROP_ID)] * 6
    gen = r.generation
    r.apply_permutation(perm, 8)
    assert r.generation == gen + 1
    assert r.lookup("b") == 0 and r.lookup("d") == 1
    assert len(r) == 2 and r.free_count() == 0
    # dropping a live id is rejected
    with pytest.raises(ValueError):
        r.apply_permutation([0] + [int(DROP_ID)] * 7)
    # duplicating a row is rejected
    with pytest.raises(ValueError):
        r.apply_permutation([0, 0, 1] + [int(DROP_ID)] * 5)


# ---------------------------------------------------------------------- #
# policy: victim selection is pure and composable
# ---------------------------------------------------------------------- #

def test_policy_ttl_and_protection():
    cfg = LifecycleConfig(ttl_intervals=3, protect=("keep.*",))
    names = ["a", "keep.me", "_overflow.a", None, "b"]
    la = [0, 0, 0, 0, 9]
    # epoch 10: a idle 10 > 3 -> victim; keep.me protected; overflow
    # protected; hole skipped; b idle 1 -> alive
    assert decide_victims(names, la, 10, cfg) == [0]


def test_policy_budgets_evict_least_recently_active():
    cfg = LifecycleConfig(max_live=3,
                          prefix_budgets={"api.*": 2})
    names = ["api.a", "api.b", "api.c", "db.a", "db.b"]
    la = [5, 1, 9, 2, 8]
    victims = decide_victims(names, la, 10, cfg)
    # api over budget by 1 -> api.b (la=1); then global 5-1=4 live > 3
    # -> evict next least-active survivor db.a (la=2)
    assert victims == [1, 3]


def test_policy_ids_beyond_activity_vector_never_victims():
    cfg = LifecycleConfig(ttl_intervals=1)
    assert decide_victims(["a", "b"], [0], 10, cfg) == [0]


def test_default_overflow_name():
    assert default_overflow_name("api.u1.lat") == "_overflow.api"
    assert default_overflow_name("plain") == "_overflow.plain"


# ---------------------------------------------------------------------- #
# activity tracking rides the fused commit at zero extra dispatches
# ---------------------------------------------------------------------- #

def test_fused_commit_tracks_activity():
    committer, agg, wheel, lc = _pair()
    committer.commit(_raw(0, {"a": {1: 2}, "b": {0: 1}}))
    committer.commit(_raw(1, {"a": {2: 3}}))
    committer.commit(_raw(2, {"c": {0: 1}}))
    la = np.asarray(lc._la)
    reg = agg.registry
    assert la[reg.lookup("a")] == 2  # last touched at epoch 2
    assert la[reg.lookup("b")] == 1
    assert la[reg.lookup("c")] == 3
    # zero EXTRA dispatches: single-chunk interval stays 1 dispatch
    assert committer.last_dispatches == 1


def test_fold_evict_kernel_exactness():
    fold = make_fold_evict_fn(1)
    import jax.numpy as jnp

    acc = jnp.asarray(np.arange(6 * 5, dtype=np.int32).reshape(6, 5))
    ring = jnp.asarray(
        np.arange(2 * 6 * 5, dtype=np.int32).reshape(2, 6, 5)
    )
    acc0, ring0 = np.asarray(acc).copy(), np.asarray(ring).copy()
    victims = pad_pow2_ids([1, 4])
    targets = np.full(len(victims), DROP_ID, dtype=np.int32)
    targets[:2] = [5, 5]
    acc2, rings2, la2, vc = fold(
        acc, (ring,), jnp.zeros(6, dtype=jnp.int32), victims, targets,
        np.int32(7),
    )
    acc2 = np.asarray(acc2)
    assert (acc2[5] == acc0[5] + acc0[1] + acc0[4]).all()
    assert (acc2[1] == 0).all() and (acc2[4] == 0).all()
    assert (acc2[[0, 2, 3]] == acc0[[0, 2, 3]]).all()
    r2 = np.asarray(rings2[0])
    assert (r2[:, 5] == ring0[:, 5] + ring0[:, 1] + ring0[:, 4]).all()
    assert (r2[:, 1] == 0).all()
    assert list(np.asarray(vc)[:2]) == [acc0[1].sum(), acc0[4].sum()]
    assert np.asarray(la2)[1] == 7 and np.asarray(la2)[4] == 7


def test_compact_rows_pallas_matches_jnp():
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    arr = jnp.asarray(rng.integers(0, 100, (16, 13)).astype(np.int32))
    perm = np.array(
        [7, 0, 15, -1, DROP_ID, 3, 9, 1] + [DROP_ID] * 8, dtype=np.int32
    )
    a = np.asarray(compact_rows(arr, jnp.asarray(perm)))
    b = np.asarray(compact_rows_pallas(arr, jnp.asarray(perm)))
    assert (a == b).all()


# ---------------------------------------------------------------------- #
# eviction: count-exact overflow folding, lossless totals
# ---------------------------------------------------------------------- #

def test_ttl_eviction_folds_count_exact_overflow():
    cfg = LifecycleConfig(ttl_intervals=2, check_every=1,
                          auto_compact_fragmentation=0.0)
    committer, agg, wheel, lc = _pair(config=cfg)
    rng = np.random.default_rng(0)
    total = 0
    for i in range(8):
        h = {}
        for j in range(4):  # fresh names every interval -> churn
            counts = {int(b): int(c) for b, c in zip(
                rng.integers(-64, 64, 3), rng.integers(1, 20, 3)
            )}
            h[f"api.u{i}_{j}.lat"] = counts
        h["api.steady"] = {0: 2}
        committer.commit(_raw(i, h))
        total += sum(sum(c.values()) for c in h.values())
    reg = agg.registry
    assert lc.evicted_series > 0 and lc.evictions > 0
    assert reg.lookup("api.steady") is not None
    ovid = reg.lookup("_overflow.api")
    assert ovid is not None

    # count-exact: the overflow row holds EXACTLY the evicted device
    # samples, and live rows + overflow == every sample ever ingested
    acc = np.asarray(agg._acc)
    assert int(acc[ovid].sum()) == lc.overflowed_samples
    assert int(acc.sum()) == total

    # the overflow series reports through the normal collection path
    pm = agg.collect(reset=False)
    assert pm.metrics.get("_overflow.api_count", 0) > 0

    # HBM boundedness: cumulative names far exceed live rows, but the
    # accumulator never grew past its configured row budget
    assert agg.num_metrics == 32
    assert reg.live_count() <= 32


def test_eviction_respects_prefix_budget():
    cfg = LifecycleConfig(prefix_budgets={"api.*": 2}, check_every=1,
                          auto_compact_fragmentation=0.0)
    committer, agg, wheel, lc = _pair(config=cfg)
    h = {f"api.u{j}": {1: 1} for j in range(5)}
    h["db.q"] = {0: 1}
    committer.commit(_raw(0, h))
    committer.commit(_raw(1, {"db.q": {0: 1}}))  # tick runs policies
    reg = agg.registry
    live_api = [n for n in reg.names()
                if n and n.startswith("api.") and not
                n.startswith("_overflow")]
    assert len(live_api) == 2
    assert reg.lookup("db.q") is not None  # other prefixes untouched


# ---------------------------------------------------------------------- #
# compaction: bit-identical survivors, ring rotation + open slot
# ---------------------------------------------------------------------- #

def test_compaction_bit_identical_percentiles():
    cfg = LifecycleConfig(check_every=1000,  # manual control only
                          auto_compact_fragmentation=0.0)
    committer, agg, wheel, lc = _pair(config=cfg)
    rng = np.random.default_rng(1)
    names = [f"m{j}" for j in range(10)]
    # 9 intervals over a (4 slots, res 2) tier: the ring has WRAPPED
    # (slot 0 reopened and cleared) and the open slot is mid-fill — the
    # hard layout for a repack
    for i in range(9):
        h = {}
        for name in names:
            h[name] = {int(b): int(c) for b, c in zip(
                rng.integers(-64, 64, 6), rng.integers(1, 30, 6)
            )}
        committer.commit(_raw(i, h))
    t = wheel._tiers[0]
    assert t.written.all() and t.in_slot == 1  # wrapped + open slot

    victims = [agg.registry.lookup(n) for n in names[::3]]
    survivors = [n for j, n in enumerate(names) if j % 3 != 0]
    lc.evict_ids(victims)

    ps = (0.5, 0.99, 0.9999)
    oracle = {}
    for w in (4.0, 10.0):
        res = wheel.query("*", window=w, percentiles=ps)
        oracle[w] = {k: dict(v) for k, v in res.metrics.items()}
        for n in survivors:
            assert n in oracle[w]

    assert lc.compact() is True
    assert agg.registry.generation > 0
    # survivors repacked to the dense prefix
    assert sorted(
        m for m, n in enumerate(agg.registry.names()) if n is not None
    ) == list(range(agg.registry.live_count()))

    for w, want in oracle.items():
        got = wheel.query("*", window=w, percentiles=ps)
        assert set(got.metrics) == set(want)
        for name, entry in got.metrics.items():
            assert entry == want[name], name  # bit-exact, not approx

    # the wheel keeps committing cleanly on the repacked rings
    committer.commit(_raw(99, {"m1": {0: 1}}))
    assert lc.compact() is False  # already dense -> no-op


def test_compaction_reuses_low_ids_first():
    cfg = LifecycleConfig(check_every=1000,
                          auto_compact_fragmentation=0.0)
    committer, agg, wheel, lc = _pair(config=cfg)
    committer.commit(_raw(0, {f"n{j}": {0: 1} for j in range(6)}))
    lc.evict_ids([agg.registry.lookup("n2"), agg.registry.lookup("n4")])
    # before compaction the free-list serves the holes
    assert agg.registry.id_for("fresh1") in (2, 4)
    lc.compact()
    # after compaction ids are dense; new names extend the prefix
    assert agg.registry.id_for("fresh2") == agg.registry.live_count() - 1


# ---------------------------------------------------------------------- #
# invalidation: a query after eviction never serves a dead id
# ---------------------------------------------------------------------- #

def test_query_after_eviction_never_serves_dead_id():
    cfg = LifecycleConfig(check_every=1000,
                          auto_compact_fragmentation=0.0)
    committer, agg, wheel, lc = _pair(config=cfg)
    committer.commit(_raw(0, {"api.a": {1: 5}, "api.b": {2: 3}}))
    committer.commit(_raw(1, {"api.a": {1: 5}, "api.b": {2: 3}}))

    # warm both caches at the pre-eviction generation
    res = wheel.query("api.*", window=4.0)
    assert set(res.metrics) == {"api.a", "api.b"}
    res2 = wheel.query("api.*", window=4.0)
    assert set(res2.metrics) == {"api.a", "api.b"}  # cached serve

    lc.evict_ids([agg.registry.lookup("api.b")])

    # the registered name must be gone even though the cached glob/result
    # entries and snapshot predate the eviction
    res3 = wheel.query("api.*", window=4.0)
    assert "api.b" not in res3.metrics
    assert "api.a" in res3.metrics
    for name in res3.metrics:
        assert agg.registry.lookup(name) is not None

    # the reused slot must NOT resurrect the evicted tenant's data under
    # the new name in fresh windows
    committer.commit(_raw(2, {"api.c": {3: 1}}))
    assert agg.registry.lookup("api.c") == 1  # reused api.b's slot
    res4 = wheel.query("api.c", window=1.0)
    assert res4.metrics.get("api.c", {}).get("count") == 1.0


def test_snapshot_epoch_invalidated_on_eviction():
    cfg = LifecycleConfig(check_every=1000,
                          auto_compact_fragmentation=0.0)
    committer, agg, wheel, lc = _pair(config=cfg)
    committer.commit(_raw(0, {"a": {1: 5}, "b": {1: 5}}))
    assert wheel.snapshot is not None
    lc.evict_ids([agg.registry.lookup("b")])
    assert wheel.snapshot is None  # republished only by the next commit
    assert agg.stats_snapshot is None
    committer.commit(_raw(1, {"a": {1: 5}}))
    assert wheel.snapshot is not None


# ---------------------------------------------------------------------- #
# threaded churn: register/evict/query race
# ---------------------------------------------------------------------- #

def test_threaded_churn_register_evict_query():
    cfg = LifecycleConfig(ttl_intervals=2, check_every=1,
                          auto_compact_fragmentation=0.3,
                          min_compact_rows=4)
    committer, agg, wheel, lc = _pair(num_metrics=64, config=cfg)
    stop = threading.Event()
    errors = []

    def querier():
        while not stop.is_set():
            try:
                res = wheel.query("api.*", window=8.0)
                for name in res.metrics:
                    # served names must be live at SOME nearby instant;
                    # the hard guarantee is no crash and no stale-cache
                    # id resolution (checked via count sanity)
                    assert res.metrics[name]["count"] > 0
            except Exception as e:  # pragma: no cover - failure path
                errors.append(e)
                return

    def registrar():
        # bounded: past max_metrics further names would be shed, which
        # would (correctly) break the conservation assertion below
        for k in range(120):
            if stop.is_set():
                return
            try:
                agg._id_for(f"api.reg{k}.lat")
            except Exception as e:  # pragma: no cover - failure path
                errors.append(e)
                return

    threads = [threading.Thread(target=querier),
               threading.Thread(target=registrar)]
    for th in threads:
        th.start()
    try:
        for i in range(20):
            h = {f"api.w{i}_{j}.lat": {1: 2} for j in range(4)}
            h["api.steady"] = {0: 1}
            committer.commit(_raw(i, h))
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=10.0)
    assert not errors, errors
    assert lc.evicted_series > 0
    # lossless under the race: committed samples all remain (live rows +
    # overflow rows), none duplicated or lost by fold/compact
    acc = np.asarray(agg._acc)
    assert int(acc.sum()) == 20 * (4 * 2 + 1)


# ---------------------------------------------------------------------- #
# wiring: TPUMetricSystem facade + gauges
# ---------------------------------------------------------------------- #

def test_system_wiring_and_gauges():
    from loghisto_tpu.system import TPUMetricSystem

    ms = TPUMetricSystem(
        interval=0.05, sys_stats=False, num_metrics=32,
        retention=((8, 1),), commit="fused",
        lifecycle=LifecycleConfig(ttl_intervals=3, check_every=2),
    )
    try:
        assert ms.lifecycle is not None
        assert ms.committer is not None
        assert ms.committer.lifecycle is ms.lifecycle
        with ms._gauge_lock:
            gauge_names = set(ms._gauge_funcs)
        for g in ("lifecycle.ActiveSeries", "lifecycle.FreeSlots",
                  "lifecycle.EvictedSeries", "lifecycle.Occupancy",
                  "lifecycle.OverflowedSamples", "lifecycle.Generation",
                  "lifecycle.CompactionP99Us"):
            assert g in gauge_names, g
    finally:
        ms.stop()


def test_system_lifecycle_requires_retention():
    from loghisto_tpu.system import TPUMetricSystem

    with pytest.raises(ValueError, match="retention"):
        TPUMetricSystem(sys_stats=False,
                        lifecycle=LifecycleConfig(ttl_intervals=1))


def test_prometheus_staleness_after_eviction():
    """Evicted series stop being exported: the windowed exposition only
    serves names resolvable in the current generation, and the host
    lifetime stores forget the victim (its totals live on under the
    overflow name)."""
    from loghisto_tpu.prometheus import windowed_exposition

    cfg = LifecycleConfig(check_every=1000,
                          auto_compact_fragmentation=0.0)
    committer, agg, wheel, lc = _pair(config=cfg)
    committer.commit(_raw(0, {"api.a": {1: 5}, "api.b": {2: 3}}))
    text = windowed_exposition(wheel, windows=(4.0,)).decode()
    assert "api_b" in text
    lc.evict_ids([agg.registry.lookup("api.b")])
    text = windowed_exposition(wheel, windows=(4.0,)).decode()
    assert "api_b" not in text
    assert "api_a" in text
