"""Label/tag data model (PR 16): canonical ``name;k=v`` encoding over
the flat registry, selector parsing/matching, the generation-keyed
inverted index (tail scans, rebuilds, selector-cache invalidation under
churn), labeled-vs-flat storage parity (dense + paged + checkpoint —
the label layer must be invisible below the name), on-device group_by
rollups pinned bucket-identical to the float64 host merge oracle,
label-cardinality lifecycle budgets with count-exact overflow, labeled
exporter wire pins, and the federation permutation round trip."""

import datetime as dt

import numpy as np
import pytest

from loghisto_tpu.commit import IntervalCommitter
from loghisto_tpu.config import MetricConfig
from loghisto_tpu.labels import (
    LabelError,
    LabelIndex,
    LabelSet,
    base_of,
    canonical_name,
    is_labeled,
    is_selector,
    labels_of,
    parse_canonical,
    parse_selector,
    split_processed,
)
from loghisto_tpu.labels.groupby import (
    equidepth_ranks,
    group_key_for,
    merge_groups_host,
)
from loghisto_tpu.labels.selector import SelectorError
from loghisto_tpu.lifecycle import LifecycleConfig, LifecycleManager
from loghisto_tpu.lifecycle.policy import decide_victims, default_overflow_name
from loghisto_tpu.metrics import ProcessedMetricSet, RawMetricSet
from loghisto_tpu.parallel.aggregator import TPUAggregator
from loghisto_tpu.registry import MetricRegistry
from loghisto_tpu.window import TimeWheel

pytestmark = pytest.mark.labels

T0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
CFG = MetricConfig(bucket_limit=64)
CANON = "http.latency;code=500;route=/api"


def _raw(i, histograms=None, rates=None, duration=1.0):
    return RawMetricSet(
        time=T0 + dt.timedelta(seconds=i), counters={},
        rates=dict(rates or {}), histograms=dict(histograms or {}),
        gauges={}, duration=duration,
    )


def _pair(num_metrics=16, tiers=((8, 1), (4, 4)), lifecycle_config=None):
    agg = TPUAggregator(num_metrics=num_metrics, config=CFG)
    wheel = TimeWheel(num_metrics=num_metrics, config=CFG, interval=1.0,
                      tiers=tiers, registry=agg.registry)
    wheel.label_index = LabelIndex(agg.registry)
    lc = None
    if lifecycle_config is not None:
        lc = LifecycleManager(agg, wheel, lifecycle_config)
    committer = IntervalCommitter(agg, wheel, lifecycle=lc)
    committer.warmup()
    return committer, agg, wheel, lc


# ---------------------------------------------------------------------- #
# model: canonical encoding
# ---------------------------------------------------------------------- #

def test_canonical_name_is_permutation_invariant():
    a = canonical_name("http.latency", {"route": "/api", "code": "500"})
    b = canonical_name("http.latency", {"code": "500", "route": "/api"})
    assert a == b == CANON


def test_canonical_name_empty_labels_is_flat():
    assert canonical_name("m", None) == "m"
    assert canonical_name("m", {}) == "m"
    assert not is_labeled("m") and is_labeled(CANON)


def test_canonical_grammar_rejections():
    with pytest.raises(LabelError):
        canonical_name("m;x", {"k": "v"})        # ';' in base
    with pytest.raises(LabelError):
        canonical_name("m{", {"k": "v"})         # selector char in base
    with pytest.raises(LabelError):
        canonical_name("m", {"9bad": "v"})       # key grammar
    with pytest.raises(LabelError):
        canonical_name("m", {"k": "a;b"})        # structural value char
    with pytest.raises(LabelError):
        canonical_name("m", {"k": "a b"})        # whitespace value


def test_parse_canonical_round_trip_and_tolerance():
    assert parse_canonical(CANON) == (
        "http.latency", (("code", "500"), ("route", "/api")),
    )
    assert base_of(CANON) == "http.latency"
    assert labels_of(CANON) == {"code": "500", "route": "/api"}
    assert parse_canonical("flat") == ("flat", ())
    # foreign ';' names that aren't canonical pairs stay queryable flat
    assert parse_canonical("weird;notapair") == ("weird;notapair", ())
    assert labels_of("weird;=v") == {}


def test_label_set_interning():
    s1 = LabelSet({"b": "2", "a": "1"})
    s2 = LabelSet({"a": "1", "b": "2"})
    assert s1 == s2 and hash(s1) == hash(s2)
    assert s1.apply("m") == "m;a=1;b=2"
    assert s1.as_dict() == {"a": "1", "b": "2"}
    assert LabelSet().encode() == ""


def test_split_processed_undoes_suffix_after_label_tail():
    assert split_processed(CANON + "_99") == (
        "http.latency", (("code", "500"), ("route", "/api")), "_99",
    )
    assert split_processed(CANON + "_count")[2] == "_count"
    assert split_processed(CANON + "_agg_count")[2] == "_agg_count"
    # the full processed-suffix family must split — a missing entry
    # leaks an unsplit canonical tail onto every exporter wire
    for s in ("_sum", "_avg", "_min", "_max", "_rate", "_99.99"):
        assert split_processed(CANON + s)[2] == s, s
    assert split_processed(CANON) == (
        "http.latency", (("code", "500"), ("route", "/api")), "",
    )
    assert split_processed("flat_count") is None  # no label tail


# ---------------------------------------------------------------------- #
# selector: parsing + matching
# ---------------------------------------------------------------------- #

def test_selector_ops_match_semantics():
    sel = parse_selector("http.latency{route=/api,code=~5..}")
    assert sel.match_name(CANON)
    assert not sel.match_name("http.latency;code=200;route=/api")
    assert not sel.match_name("http.latency")  # missing labels read ""
    neg = parse_selector("http.latency{code!=500}")
    assert not neg.match_name(CANON)
    assert neg.match_name("http.latency")       # absent label is "" != 500
    nre = parse_selector("http.latency{code!~5..}")
    assert not nre.match_name(CANON)
    assert nre.match_name("http.latency;code=200;route=/api")


def test_selector_quoted_values_and_base_glob():
    sel = parse_selector('http.latency{route="/a,b"}')
    assert sel.match_name("http.latency;route=/a,b")
    glob = parse_selector("http.*{route=/api}")
    assert glob.base_is_glob
    assert glob.match_name("http.bytes;route=/api")
    assert not glob.match_name("db.q;route=/api")
    assert is_selector("m{k=v}") and not is_selector("m*")


def test_selector_parse_errors():
    for bad in ("m{", "{k=v}", "m{=v}", "m{k=v", "m;x{k=v}"):
        with pytest.raises(SelectorError):
            parse_selector(bad)


# ---------------------------------------------------------------------- #
# inverted index: postings, tail scans, churn invalidation
# ---------------------------------------------------------------------- #

def _seed_registry():
    r = MetricRegistry(16)
    for n in ("http.latency", CANON,
              "http.latency;code=200;route=/api", "db.q"):
        r.id_for(n)
    return r


def test_index_select_and_postings():
    idx = LabelIndex(_seed_registry())
    gen, rows = idx.select("http.latency{code=500}")
    assert [n for _, n in rows] == [CANON]
    # empty matcher list selects every row of the base, flat included
    assert len(idx.select("http.latency{}")[1]) == 3
    # glob base unions postings across bases
    assert len(idx.select("*{route=/api}")[1]) == 2
    st = idx.stats()
    assert st["labeled_rows"] == 2 and st["rebuilds"] == 1


def test_index_append_is_tail_scan_not_rebuild():
    r = _seed_registry()
    idx = LabelIndex(r)
    idx.select("http.latency{}")
    r.id_for("http.latency;code=503;route=/api")  # pure append
    gen, rows = idx.select("http.latency{code=~5..}")
    assert len(rows) == 2
    st = idx.stats()
    assert st["tail_scans"] == 1 and st["rebuilds"] == 1


def test_index_selector_cache_hits_and_flush_on_generation():
    r = _seed_registry()
    idx = LabelIndex(r)
    idx.select("http.latency{code=500}")
    idx.select("http.latency{code=500}")
    assert idx.stats()["selector_cache_hits"] == 1
    r.evict([r.lookup(CANON)])  # generation bump
    gen, rows = idx.select("http.latency{code=500}")
    assert rows == ()
    assert idx.stats()["rebuilds"] == 2


def test_index_never_serves_stale_ids_after_slot_reuse():
    r = _seed_registry()
    idx = LabelIndex(r)
    victim = r.lookup(CANON)
    assert idx.select("http.latency{code=500}")[1][0][0] == victim
    r.evict([victim])
    # freed slot reused under an unrelated labeled name
    assert r.id_for("db.q;shard=3") == victim
    assert idx.select("http.latency{code=500}")[1] == ()
    gen, rows = idx.select("db.q{shard=3}")
    assert rows == ((victim, "db.q;shard=3"),)


@pytest.mark.parametrize("churn", ["evict", "compact", "grow"])
def test_wheel_selector_queries_stay_correct_under_churn(churn):
    cfg = LifecycleConfig(check_every=1000,
                          auto_compact_fragmentation=0.0)
    committer, agg, wheel, lc = _pair(lifecycle_config=cfg)
    names = [f"http.latency;code={c};route=/api" for c in (200, 500, 503)]
    for i in range(3):
        committer.commit(_raw(i, {n: {j: 2} for j, n in enumerate(names)}))
    sel = "http.latency{code=~5..}"
    assert set(wheel.query(sel, window=16.0).metrics) == set(names[1:])

    if churn == "evict":
        lc.evict_ids([agg.registry.lookup(names[1])])
        expect = {names[2]}
    elif churn == "compact":
        lc.evict_ids([agg.registry.lookup(names[0])])
        assert lc.compact()  # permutation: every id may move
        expect = {names[1], names[2]}
    else:  # grow: new labeled row appended after the first query
        expect = {names[1], names[2],
                  "http.latency;code=599;route=/api"}
    h = {n: {1: 1} for n in expect}
    committer.commit(_raw(3, h))
    res = wheel.query(sel, window=16.0)
    assert set(res.metrics) == expect
    # recompute oracle agrees row for row after the churn
    ref = wheel._query_recompute(sel, 16.0, tuple(wheel.percentiles),
                                 res.tier)
    assert res.metrics == ref.metrics


# ---------------------------------------------------------------------- #
# labeled-vs-flat storage parity: dense, paged, checkpoint
# ---------------------------------------------------------------------- #

def _mk_agg(storage="dense"):
    from loghisto_tpu.paging import PagedStoreConfig

    # paged storage needs a bucket axis of at least one 256-bucket page
    return TPUAggregator(
        num_metrics=64, config=MetricConfig(bucket_limit=256),
        batch_size=256, storage=storage,
        paged_config=PagedStoreConfig(pool_pages=512),
        percentiles={"p50_%s": 0.5, "p99_%s": 0.99},
    )


def _drive(agg, name):
    rng = np.random.default_rng(7)
    for v in rng.lognormal(1.0, 0.5, 500):
        agg.record(name, float(v))
    agg.flush()
    return agg.collect(reset=False).metrics


@pytest.mark.parametrize("storage", ["dense", "paged"])
def test_labeled_row_is_bit_identical_to_flat_row(storage):
    """The label layer lives entirely above the registry: the same
    samples under a labeled name and a flat name take the exact same
    device path and yield the exact same numbers."""
    labeled = _drive(_mk_agg(storage), CANON)
    flat = _drive(_mk_agg(storage), "http.latency")
    assert labeled  # the canonical name actually reported
    for key, value in flat.items():
        assert key.count("http.latency") == 1
        lk = key.replace("http.latency", CANON)
        assert labeled[lk] == value, key


def test_labeled_names_survive_checkpoint(tmp_path):
    from loghisto_tpu.utils import checkpoint

    agg = _mk_agg()
    before = _drive(agg, CANON)
    path = str(tmp_path / "labeled.npz")
    checkpoint.save(path, aggregator=agg)
    fresh = _mk_agg()
    checkpoint.restore(path, aggregator=fresh)
    after = fresh.collect(reset=False).metrics
    for key, value in before.items():
        if key.startswith(CANON):
            assert after[key] == value
    assert fresh.registry.lookup(CANON) is not None


# ---------------------------------------------------------------------- #
# group_by: device rollup vs float64 host merge oracle
# ---------------------------------------------------------------------- #

def _commit_labeled(committer, intervals=5, seed=3):
    """Commit labeled traffic; returns the merged per-name histograms
    (the oracle's input) covering every committed interval."""
    rng = np.random.default_rng(seed)
    names = [
        "http.latency",                            # flat row: route ""
        "http.latency;code=200;route=/api",
        "http.latency;code=500;route=/api",
        "http.latency;code=200;route=/web",
        "http.latency;code=503;route=/web",
    ]
    merged = {n: {} for n in names}
    for i in range(intervals):
        h = {}
        for n in names:
            buckets = {}
            for b, c in zip(rng.integers(-64, 64, 10),
                            rng.integers(1, 40, 10)):
                buckets[int(b)] = buckets.get(int(b), 0) + int(c)
            h[n] = buckets
            for b, c in buckets.items():
                merged[n][b] = merged[n].get(b, 0) + c
        committer.commit(_raw(i, h))
    return merged


def _rep_table():
    from loghisto_tpu.ops.stats import bucket_representatives

    return np.asarray(
        bucket_representatives(CFG.bucket_limit, CFG.precision)
    )


def _bucket_of(reps, v):
    """Nearest-representative bucket id: adjacent log buckets are ~1%
    apart while in-jit vs eager rep tables differ by at most one f32
    ulp, so the mapping is unambiguous."""
    return int(np.argmin(np.abs(reps - np.float64(v))))


def test_group_by_matches_host_merge_oracle():
    committer, agg, wheel, _ = _pair()
    merged = _commit_labeled(committer)
    ps = (0.5, 0.9, 0.99)
    gs = wheel.query_group_by("http.latency{}", by=["route"],
                              window=1e9, percentiles=ps)
    reps = _rep_table()
    oracle = merge_groups_host(
        merged, ["route"], ps, CFG.precision,
        value_of=lambda b: reps[np.asarray(b) + CFG.bucket_limit],
    )
    assert set(gs.groups) == set(oracle) == {("",), ("/api",), ("/web",)}
    for gk, ref in oracle.items():
        got = gs.groups[gk]
        assert got["count"] == ref["count"]          # int-exact merge
        assert got["sum"] == pytest.approx(ref["sum"], rel=1e-5)
        for p in ps:
            key = f"p{f'{p * 100:.4f}'.rstrip('0').rstrip('.')}"
            assert _bucket_of(reps, got[key]) == _bucket_of(
                reps, ref[key]
            ), (gk, key)
    assert gs.sizes == {("",): 1, ("/api",): 2, ("/web",): 2}
    assert gs.by == ("route",)


def test_group_by_two_keys_and_selector_filter():
    committer, agg, wheel, _ = _pair()
    merged = _commit_labeled(committer)
    gs = wheel.query_group_by("http.latency{code=~[25]0[03]}",
                              by=["route", "code"], window=1e9,
                              percentiles=(0.5,))
    labeled = {n: h for n, h in merged.items() if ";" in n}
    oracle = merge_groups_host(labeled, ["route", "code"], (0.5,),
                               CFG.precision)
    assert set(gs.groups) == set(oracle)
    for gk, ref in oracle.items():
        assert gs.groups[gk]["count"] == ref["count"]


def test_group_by_equidepth_edges_are_quantiles():
    committer, agg, wheel, _ = _pair()
    _commit_labeled(committer)
    depth = 4
    gs = wheel.query_group_by("http.latency{}", by=["route"],
                              window=1e9, percentiles=(), depth=depth)
    ref = wheel.query_group_by("http.latency{}", by=["route"],
                               window=1e9,
                               percentiles=equidepth_ranks(depth))
    for gk, entry in gs.groups.items():
        edges = entry["edges"]
        assert len(edges) == depth - 1
        expect = [ref.groups[gk][k] for k in ("p25", "p50", "p75")]
        assert edges == expect  # same ranks, same dispatch arithmetic


def test_group_by_warm_repeat_is_zero_dispatch():
    committer, agg, wheel, _ = _pair()
    _commit_labeled(committer)
    args = dict(by=["route"], window=1e9, percentiles=(0.5,))
    first = wheel.query_group_by("http.latency{}", **args)
    serves = wheel.query_group_serves
    hits = wheel.query_result_cache_hits
    again = wheel.query_group_by("http.latency{}", **args)
    assert wheel.query_group_serves == serves      # no new rollup
    assert wheel.query_result_cache_hits == hits + 1
    assert again is first
    # commit invalidates: the next serve recomputes
    committer.commit(_raw(99, {"http.latency": {0: 1}}))
    wheel.query_group_by("http.latency{}", **args)
    assert wheel.query_group_serves == serves + 1


def test_group_by_unpinned_window_falls_back_then_snapshots():
    committer, agg, wheel, _ = _pair()
    _commit_labeled(committer, intervals=3)
    fb = wheel.query_fallbacks
    gs = wheel.query_group_by("http.latency{}", by=["code"], window=2.0,
                              percentiles=(0.5,))
    assert wheel.query_fallbacks == fb + 1 and gs.groups
    committer.commit(_raw(50, {"http.latency": {0: 1}}))  # pin took
    wheel.query_group_by("http.latency{}", by=["code"], window=2.0,
                         percentiles=(0.5,))
    assert wheel.query_fallbacks == fb + 1


def test_selector_query_parity_with_recompute_oracle():
    committer, agg, wheel, _ = _pair()
    _commit_labeled(committer)
    ps = (0.0, 0.5, 0.99, 1.0)
    got = wheel.query("http.latency{route=/api}", window=1e9,
                      percentiles=ps)
    ref = wheel._query_recompute("http.latency{route=/api}", 1e9, ps,
                                 got.tier)
    assert got.metrics == ref.metrics  # exact float equality
    assert set(got.metrics) == {
        "http.latency;code=200;route=/api",
        "http.latency;code=500;route=/api",
    }


# ---------------------------------------------------------------------- #
# lifecycle: label-cardinality budgets, count-exact overflow
# ---------------------------------------------------------------------- #

def test_default_overflow_name_strips_label_tail():
    assert default_overflow_name(CANON) == "_overflow.http"
    assert default_overflow_name("api.u1.lat") == "_overflow.api"


def test_decide_victims_label_budget_flat_rows_exempt():
    names = ["http.lat",                       # flat: exempt
             "http.lat;u=1", "http.lat;u=2", "http.lat;u=3",
             "http.bytes;u=1",                 # other base: own budget
             "db.q;u=1"]                       # base not matched
    la = [0, 1, 2, 3, 0, 0]
    cfg = LifecycleConfig(label_budgets={"http.*": 2})
    # LRU label set of the over-budget base only
    assert decide_victims(names, la, 10, cfg) == [1]
    cfg = LifecycleConfig(label_budgets={"http.lat": 0})
    assert decide_victims(names, la, 10, cfg) == [1, 2, 3]


def test_label_budget_eviction_folds_count_exact_overflow():
    cfg = LifecycleConfig(label_budgets={"http.latency": 2},
                          check_every=1, auto_compact_fragmentation=0.0)
    committer, agg, wheel, lc = _pair(num_metrics=32,
                                      lifecycle_config=cfg)
    total = 0
    for i in range(6):
        h = {"http.latency": {0: 3},
             f"http.latency;route=/r{i}": {int(i) - 2: 5}}
        committer.commit(_raw(i, h))
        total += 8
    reg = agg.registry
    live_labeled = [n for n in reg.names()
                    if n and n.startswith("http.latency;")]
    assert len(live_labeled) == 2
    assert reg.lookup("http.latency") is not None  # flat row exempt
    ovid = reg.lookup("_overflow.http")
    assert ovid is not None and lc.evicted_series > 0
    acc = np.asarray(agg._acc)
    assert int(acc[ovid].sum()) == lc.overflowed_samples
    assert int(acc.sum()) == total  # nothing lost, nothing doubled


# ---------------------------------------------------------------------- #
# metric-system frontend: labeled calls, cached handles
# ---------------------------------------------------------------------- #

def test_frontend_labeled_calls_land_on_canonical_row():
    from loghisto_tpu.metrics import MetricSystem

    ms = MetricSystem(interval=1e6, sys_stats=False)
    ms.histogram("http.latency", 3.0,
                 labels={"route": "/api", "code": "500"})
    ms.histogram("http.latency", 4.0,
                 labels={"code": "500", "route": "/api"})  # permuted
    ms.counter("hits", 2, labels={"route": "/api"})
    raw = ms.collect_raw_metrics()
    assert list(raw.histograms) == [CANON]
    assert sum(raw.histograms[CANON].values()) == 2
    assert raw.counters["hits;route=/api"] == 2


def test_frontend_handles_cached_per_label_set():
    from loghisto_tpu.metrics import MetricSystem

    ms = MetricSystem(interval=1e6, sys_stats=False)
    r1 = ms.recorder("http.latency", labels={"route": "/a", "code": "1"})
    r2 = ms.recorder("http.latency", labels={"code": "1", "route": "/a"})
    r3 = ms.recorder("http.latency", labels={"route": "/b"})
    assert r1 is r2 and r1 is not r3
    c1 = ms.counter_handle("hits", labels={"route": "/a"})
    assert c1 is ms.counter_handle("hits", labels={"route": "/a"})
    t1 = ms.timer("step", labels={"phase": "fwd"})
    assert t1 is ms.timer("step", labels={"phase": "fwd"})
    r1.record(1.0)
    c1.add(3)
    raw = ms.collect_raw_metrics()
    assert "http.latency;code=1;route=/a" in raw.histograms
    assert raw.counters["hits;route=/a"] == 3


# ---------------------------------------------------------------------- #
# exporters: pinned labeled wire formats
# ---------------------------------------------------------------------- #

def _pms(metrics):
    return ProcessedMetricSet(time=T0, metrics=dict(metrics))


def test_prometheus_labeled_exposition_pinned():
    from loghisto_tpu.prometheus import prometheus_exposition

    out = prometheus_exposition(_pms({
        CANON + "_99": 12.5,
        CANON + "_count": 7.0,
        "http.latency_99": 3.5,
        "http.latency_count": 2.0,
        "hits;route=/api_rate": 4.0,
    })).decode()
    lines = out.splitlines()
    assert "# TYPE http_latency summary" in lines
    assert ('http_latency{code="500",route="/api",quantile="0.99"} '
            "12.5") in lines
    assert 'http_latency{quantile="0.99"} 3.5' in lines
    assert 'http_latency_count{code="500",route="/api"} 7.0' in lines
    assert 'hits_rate{route="/api"} 4.0' in lines
    assert lines.count("# TYPE http_latency summary") == 1


def test_prometheus_label_value_escaping():
    from loghisto_tpu.prometheus import prometheus_exposition

    # foreign (non-canonical-grammar) values parsed tolerantly must be
    # escaped per the exposition format, never emitted raw
    out = prometheus_exposition(
        _pms({'m;k=a"b\\c': 1.0})
    ).decode()
    assert 'm{k="a\\"b\\\\c"} 1.0' in out.splitlines()


def test_graphite_labeled_tags_flag_and_legacy_bytes():
    from loghisto_tpu.graphite import graphite_protocol

    pms = _pms({CANON + "_99": 12.5, "flat.m": 1.0})
    legacy = graphite_protocol(pms, hostname="h").decode()
    # flag off: labeled names ride the path verbatim (legacy bytes)
    assert ("cockroach.h.http.latency;code=500;route=/api.99 "
            "12.500000 1767225600\n") in legacy
    tagged = graphite_protocol(pms, hostname="h",
                               labeled_tags=True).decode()
    assert ("cockroach.h.http.latency.99;code=500;route=/api "
            "12.500000 1767225600\n") in tagged
    # flat lines identical under either flag
    assert "cockroach.h.flat.m 1.000000 1767225600\n" in legacy
    assert "cockroach.h.flat.m 1.000000 1767225600\n" in tagged


def test_opentsdb_labeled_tags_flag_pinned():
    from loghisto_tpu.opentsdb import opentsdb_protocol

    pms = _pms({CANON + "_count": 7.0, "flat.m": 1.0})
    legacy = opentsdb_protocol(pms, hostname="h").decode()
    assert ("put http.latency;code=500;route=/api_count 1767225600 "
            "7.000000 host=h\n") in legacy
    tagged = opentsdb_protocol(pms, hostname="h",
                               labeled_tags=True).decode()
    assert ("put http.latency_count 1767225600 7.000000 "
            "host=h code=500 route=/api\n") in tagged
    assert "put flat.m 1767225600 1.000000 host=h\n" in tagged


# ---------------------------------------------------------------------- #
# federation: canonicalize at record time, permutations don't split
# ---------------------------------------------------------------------- #

def test_emitter_canonicalizes_permutations_to_one_dictionary_row():
    from loghisto_tpu.federation.emitter import FederationEmitter

    e = FederationEmitter(("127.0.0.1", 1), emitter_id=5)
    e.record("http.latency", 1.0, labels={"route": "/api", "code": "500"})
    e.record("http.latency", 2.0, labels={"code": "500", "route": "/api"})
    assert e._names == {CANON: 0}           # ONE local id
    assert e._names_unsent == [(0, CANON)]  # ONE dictionary-delta row


def test_labeled_federation_round_trip_serves_selectors():
    import time

    from loghisto_tpu.federation.emitter import FederationEmitter
    from loghisto_tpu.federation.receiver import FederationReceiver

    agg = TPUAggregator(num_metrics=16, config=CFG)
    rx = FederationReceiver(agg)
    rx.start()
    try:
        e = FederationEmitter(("127.0.0.1", rx.port), emitter_id=9,
                              config=CFG)
        e.record("http.latency", 3.0,
                 labels={"route": "/api", "code": "500"})
        e.record("http.latency", 4.0,
                 labels={"code": "500", "route": "/api"})
        e.record("http.latency", 5.0, labels={"route": "/web",
                                              "code": "200"})
        e.flush()
        e._sender.start_sender("labels-rt")
        assert e.drain(10.0)
        deadline = time.monotonic() + 30.0
        while rx.samples_merged < 3:
            assert time.monotonic() < deadline, "merge timed out"
            time.sleep(0.01)
        e.close(drain_timeout=1.0)
        reg = agg.registry
        labeled = [n for n in reg.names()
                   if n and n.startswith("http.latency;")]
        assert sorted(labeled) == [
            "http.latency;code=200;route=/web", CANON,
        ]  # permutations merged into ONE row
        idx = LabelIndex(reg)
        gen, rows = idx.select("http.latency{code=500}")
        assert [n for _, n in rows] == [CANON]
        agg.flush()
        out = agg.collect(reset=False).metrics
        assert out[CANON + "_count"] == 2
    finally:
        rx.stop()


# ---------------------------------------------------------------------- #
# system wiring: index installed, gauges, debug dump
# ---------------------------------------------------------------------- #

def test_group_key_for_missing_label_reads_empty():
    assert group_key_for(CANON, ["route", "zone"]) == ("/api", "")
    assert group_key_for("flat", ["route"]) == ("",)
