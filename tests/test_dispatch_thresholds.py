"""VERDICT r2 item 7: the dispatch policy's thresholds are data-driven —
a capture-derived JSON next to ops/dispatch.py overrides the baked
constants, and benchmarks/analyze_capture.py derives that JSON from a
hardware ranking table."""

import json

import pytest

from loghisto_tpu.ops import dispatch


@pytest.fixture
def restore_dispatch_globals():
    saved = (
        dispatch.SORT_MIN_METRICS,
        dispatch.PALLAS_SINGLE_METRIC,
        dispatch.SPARSE_DENSITY_CROSSOVER,
        dispatch.SPARSE_KERNEL,
        dispatch.FUSED_INGEST,
        dispatch.FUSED_MIN_BATCH,
        dispatch.FUSED_MIN_BATCH_BY_PLATFORM,
        dispatch.FUSED_PAGED,
        dispatch.PAGED_STORAGE,
        dispatch.PAGED_MIN_METRICS,
        dispatch.THRESHOLDS_FILE,
        dispatch.THRESHOLDS_SOURCE,
    )
    yield
    (
        dispatch.SORT_MIN_METRICS,
        dispatch.PALLAS_SINGLE_METRIC,
        dispatch.SPARSE_DENSITY_CROSSOVER,
        dispatch.SPARSE_KERNEL,
        dispatch.FUSED_INGEST,
        dispatch.FUSED_MIN_BATCH,
        dispatch.FUSED_MIN_BATCH_BY_PLATFORM,
        dispatch.FUSED_PAGED,
        dispatch.PAGED_STORAGE,
        dispatch.PAGED_MIN_METRICS,
        dispatch.THRESHOLDS_FILE,
        dispatch.THRESHOLDS_SOURCE,
    ) = saved


def test_thresholds_file_overrides_baked_constants(
    tmp_path, restore_dispatch_globals
):
    table = {
        "source": "TPU_CAPTURE_test",
        "sort_min_metrics": 512,
        "pallas_single_metric": False,
        # a capture that ranks the fused kernel slower pins it off — the
        # sort assertions below depend on that (otherwise choose
        # returns "fused" at >= sort_min_metrics on TPU)
        "fused_ingest": False,
    }
    path = tmp_path / "dispatch_thresholds.json"
    path.write_text(json.dumps(table))
    dispatch.THRESHOLDS_FILE = str(path)
    dispatch._load_thresholds()
    assert dispatch.SORT_MIN_METRICS == 512
    assert dispatch.FUSED_INGEST is False
    assert dispatch.THRESHOLDS_SOURCE == "TPU_CAPTURE_test"
    # the policy immediately reflects the overrides
    assert dispatch.choose_ingest_path(1, 8193, "tpu") == "scatter"
    assert dispatch.choose_ingest_path(600, 8193, "tpu") == "sort"
    assert dispatch.choose_ingest_path(256, 8193, "tpu") == "scatter"
    # auto resolve validates the sort pick below the overridden crossover
    # (falls back to scatter past the int32 cell-key wrap)
    assert dispatch.resolve_ingest_path(
        "auto", 600, 8193, "tpu"
    ) == "sort"
    assert dispatch.resolve_ingest_path(
        "auto", 300_000, 8193, "tpu"
    ) == "scatter"


def test_malformed_or_missing_thresholds_file_is_ignored(
    tmp_path, restore_dispatch_globals
):
    before = (dispatch.SORT_MIN_METRICS, dispatch.PALLAS_SINGLE_METRIC)
    dispatch.THRESHOLDS_FILE = str(tmp_path / "missing.json")
    dispatch._load_thresholds()
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    dispatch.THRESHOLDS_FILE = str(bad)
    dispatch._load_thresholds()
    # wrong types must not poison the policy either
    wrong = tmp_path / "wrong.json"
    wrong.write_text(json.dumps({
        "sort_min_metrics": "many", "pallas_single_metric": "yes",
    }))
    dispatch.THRESHOLDS_FILE = str(wrong)
    dispatch._load_thresholds()
    assert (dispatch.SORT_MIN_METRICS,
            dispatch.PALLAS_SINGLE_METRIC) == before


def test_transport_crossover_overrides(tmp_path, restore_dispatch_globals):
    """The r6 transport entries ride the same committed-JSON machinery:
    sparse_density_crossover retunes choose_transport, sparse_kernel
    retunes resolve_sparse_kernel."""
    table = {
        "source": "TPU_CAPTURE_test",
        "sparse_density_crossover": 0.25,
        "sparse_kernel": "pallas",
    }
    path = tmp_path / "dispatch_thresholds.json"
    path.write_text(json.dumps(table))
    dispatch.THRESHOLDS_FILE = str(path)
    dispatch._load_thresholds()
    assert dispatch.SPARSE_DENSITY_CROSSOVER == 0.25
    assert dispatch.SPARSE_KERNEL == "pallas"
    assert dispatch.THRESHOLDS_SOURCE == "TPU_CAPTURE_test"
    # the policy reflects the override immediately
    assert dispatch.choose_transport("cpu", density=0.2) == "sparse"
    assert dispatch.choose_transport("cpu", density=0.3) == "raw"
    assert dispatch.resolve_sparse_kernel("auto") == "pallas"


def test_transport_crossover_garbage_degrades_to_raw(
    tmp_path, restore_dispatch_globals
):
    """A missing or garbage thresholds file must never crash transport
    selection — the baked crossover stands and undecided (no-probe)
    batches ship raw."""
    before = (dispatch.SPARSE_DENSITY_CROSSOVER, dispatch.SPARSE_KERNEL)
    dispatch.THRESHOLDS_FILE = str(tmp_path / "missing.json")
    dispatch._load_thresholds()
    for garbage in (
        "{not json",
        json.dumps({"sparse_density_crossover": "half",
                    "sparse_kernel": "quantum"}),
        json.dumps({"sparse_density_crossover": 7.5}),   # out of [0, 1]
        json.dumps({"sparse_density_crossover": True}),  # bool is not a ratio
        json.dumps([1, 2, 3]),
    ):
        bad = tmp_path / "bad.json"
        bad.write_text(garbage)
        dispatch.THRESHOLDS_FILE = str(bad)
        dispatch._load_thresholds()
        assert (
            dispatch.SPARSE_DENSITY_CROSSOVER, dispatch.SPARSE_KERNEL
        ) == before
        # selection still works and defaults conservatively
        assert dispatch.choose_transport("cpu") == "raw"
        assert dispatch.choose_transport("tpu", density=None) == "raw"
        assert dispatch.resolve_sparse_kernel("auto") == "jnp"


def test_choose_transport_policy():
    # no probe yet -> raw (zero host-fold risk); skewed probe -> sparse;
    # dense probe -> raw; preagg never auto-picked at any density
    assert dispatch.choose_transport("tpu") == "raw"
    crossover = dispatch.SPARSE_DENSITY_CROSSOVER
    assert dispatch.choose_transport("tpu", density=crossover) == "sparse"
    assert dispatch.choose_transport(
        "tpu", density=min(1.0, crossover + 0.01)
    ) == "raw"
    assert dispatch.choose_transport("cpu", density=0.0) == "sparse"
    assert dispatch.choose_transport("tpu", density=0.0, native_ok=False) \
        == "raw"
    with pytest.raises(ValueError):
        dispatch.resolve_sparse_kernel("quantum")


def _derive(winners_table):
    from benchmarks.analyze_capture import derive_thresholds

    rates = {}
    for m, ranked in winners_table.items():
        for i, name in enumerate(ranked):
            rates[f"{name}@{m}"] = 100.0 - i  # descending = ranked order
    table = {"platform": "tpu", "num_buckets": 8193, "batch": 1 << 20,
             "mode": "looped", "rates": rates}
    winners = {m: ranked[0] for m, ranked in winners_table.items()}
    return derive_thresholds("TPU_CAPTURE_test", table, winners)


def test_derive_thresholds_from_r2_shaped_table():
    # the r2 capture's shape: pallas at M=1, scatter mid, sort at 10k
    t = _derive({
        1: ["pallasb", "sort", "scatter"],
        16: ["scatter", "sort"],
        256: ["scatter", "sort"],
        10_000: ["sort", "scatter"],
    })
    assert t["pallas_single_metric"] is True
    # geometric midpoint of the 256..10000 bracket
    assert 256 < t["sort_min_metrics"] < 10_000
    assert t["sort_min_metrics"] == round((256 * 10_000) ** 0.5)


def test_derive_thresholds_sort_never_wins():
    t = _derive({1: ["scatter"], 16: ["scatter"], 10_000: ["scatter"]})
    assert t["pallas_single_metric"] is False
    assert t["sort_min_metrics"] >= 1 << 30  # effectively disabled


def test_derive_thresholds_non_monotone_disables_sort():
    # sort wins at M=16 but LOSES at the top of the measured range: a
    # threshold would dispatch sort where the capture shows scatter
    # winning, so the derived table disables the sort region instead
    t = _derive({
        16: ["sort", "scatter"],
        256: ["scatter", "sort"],
        10_000: ["scatter", "sort"],
    })
    assert t["sort_min_metrics"] >= 1 << 30


def test_derive_thresholds_non_tpu_refused():
    from benchmarks.analyze_capture import derive_thresholds

    assert derive_thresholds(
        "d", {"platform": "cpu"}, {16: "scatter"}
    ) is None


# ---------------------------------------------------------------------- #
# capability-based mesh commit resolution: a sharded configuration only
# degrades off the fused path for a reason it can articulate
# ---------------------------------------------------------------------- #

class _MeshStub:
    """Just the surface mesh_commit_incapability inspects."""

    def __init__(self, axis_names, shape):
        self.axis_names = axis_names
        self.shape = shape


def test_mesh_commit_incapability_accepts_commit_layout():
    mesh = _MeshStub(("stream", "metric"), {"stream": 2, "metric": 4})
    assert dispatch.mesh_commit_incapability(None) is None
    assert dispatch.mesh_commit_incapability(mesh) is None
    assert dispatch.mesh_commit_incapability(mesh, num_metrics=16) is None


def test_mesh_commit_incapability_names_wrong_axis_layout():
    mesh = _MeshStub(("x", "y"), {"x": 4, "y": 2})
    reason = dispatch.mesh_commit_incapability(mesh)
    assert reason is not None
    assert "('x', 'y')" in reason and "'stream'" in reason
    assert "'metric'" in reason


def test_mesh_commit_incapability_names_indivisible_rows():
    mesh = _MeshStub(("stream", "metric"), {"stream": 2, "metric": 3})
    reason = dispatch.mesh_commit_incapability(mesh, num_metrics=16)
    assert reason is not None
    assert "num_metrics=16" in reason and "3-way" in reason


def test_resolve_commit_path_capable_mesh_stays_fused():
    mesh = _MeshStub(("stream", "metric"), {"stream": 2, "metric": 4})
    assert dispatch.resolve_commit_path(
        "auto", "cpu", mesh=mesh, num_metrics=16) == "fused"
    assert dispatch.resolve_commit_path(
        "fused", "cpu", mesh=mesh, num_metrics=16) == "fused"
    # fanout remains an explicit opt-out, never second-guessed
    assert dispatch.resolve_commit_path(
        "fanout", "cpu", mesh=mesh, num_metrics=16) == "fanout"


def test_resolve_commit_path_auto_degrades_with_reason():
    mesh = _MeshStub(("stream", "metric"), {"stream": 2, "metric": 3})
    assert dispatch.resolve_commit_path(
        "auto", "cpu", mesh=mesh, num_metrics=16) == "fanout"


def test_resolve_commit_path_explicit_fused_raises_the_reason():
    mesh = _MeshStub(("x", "y"), {"x": 4, "y": 2})
    with pytest.raises(ValueError, match=r"\('x', 'y'\)"):
        dispatch.resolve_commit_path("fused", "cpu", mesh=mesh)
    bad_rows = _MeshStub(("stream", "metric"), {"stream": 2, "metric": 3})
    with pytest.raises(ValueError, match="num_metrics=16"):
        dispatch.resolve_commit_path(
            "fused", "cpu", mesh=bad_rows, num_metrics=16)


# -- paged storage resolution (r14) ------------------------------------- #

def test_paged_storage_incapability_reason_strings():
    # r18: a mesh per se is admitted (per-shard arenas); only shapes
    # the arenas cannot take decline, and they still win over every
    # other reason
    assert dispatch.paged_storage_incapability(1 << 20, mesh=True) is None
    bad = _MeshStub(("stream", "metric"), {"stream": 2, "metric": 3})
    reason = dispatch.paged_storage_incapability(
        1 << 20, mesh=True, mesh_obj=bad, transport="raw"
    )
    assert reason is not None and "mesh shape" in reason
    # non-sparse transports ship whole batches, no host fold to translate
    reason = dispatch.paged_storage_incapability(1 << 20, transport="raw")
    assert reason is not None and "transport" in reason
    reason = dispatch.paged_storage_incapability(1 << 20, transport="preagg")
    assert reason is not None and "transport" in reason
    # a bucket axis narrower than one page can't amortize paging
    reason = dispatch.paged_storage_incapability(
        1 << 20, num_buckets=dispatch.PAGE_SIZE - 1
    )
    assert reason is not None and "bucket axis" in reason
    # below the crossover the dense accumulator wins; the reason names
    # the benchmark that set the bound
    reason = dispatch.paged_storage_incapability(
        dispatch.PAGED_MIN_METRICS - 1
    )
    assert reason is not None and "below crossover" in reason
    assert "PAGED_STORE_r14" in reason
    # a capable shape has no reason
    assert dispatch.paged_storage_incapability(
        dispatch.PAGED_MIN_METRICS
    ) is None
    # explicit selection skips the crossover check only
    assert dispatch.paged_storage_incapability(
        8, crossover=False
    ) is None


def test_resolve_storage_path_auto_degrades_with_reason():
    storage, reason = dispatch.resolve_storage_path(
        "auto", 8, 8193, "cpu"
    )
    assert storage == "dense"
    assert reason is not None and "below crossover" in reason
    # r18: a shardable mesh no longer degrades; an unshardable SHAPE does
    storage, reason = dispatch.resolve_storage_path(
        "auto", 1 << 20, 8193, "cpu", mesh=True
    )
    assert storage == "paged" and reason is None
    storage, reason = dispatch.resolve_storage_path(
        "auto", 1 << 20, 8193, "cpu", mesh=True,
        mesh_obj=_MeshStub(("stream", "metric"),
                           {"stream": 2, "metric": 3}),
    )
    assert storage == "dense" and "mesh shape" in reason
    storage, reason = dispatch.resolve_storage_path(
        "auto", 1 << 20, 8193, "cpu"
    )
    assert storage == "paged" and reason is None
    # dense stays an explicit opt-out, never second-guessed
    storage, reason = dispatch.resolve_storage_path(
        "dense", 1 << 20, 8193, "cpu"
    )
    assert storage == "dense" and reason is None


def test_resolve_storage_path_explicit_paged_raises_the_reason():
    # explicit paged skips the crossover (operator's call, like fused)...
    storage, reason = dispatch.resolve_storage_path("paged", 8, 8193, "cpu")
    assert storage == "paged" and reason is None
    # ...but correctness blockers raise with the same reason string auto
    # degrades on
    with pytest.raises(ValueError, match="mesh shape"):
        dispatch.resolve_storage_path(
            "paged", 1 << 20, 8193, "cpu", mesh=True,
            mesh_obj=_MeshStub(("stream", "metric"),
                               {"stream": 2, "metric": 3}),
        )
    with pytest.raises(ValueError, match="transport"):
        dispatch.resolve_storage_path("paged", 1 << 20, 8193, "cpu",
                                      transport="raw")
    with pytest.raises(ValueError, match="bucket axis"):
        dispatch.resolve_storage_path("paged", 1 << 20, 100, "cpu")
    with pytest.raises(ValueError, match="unknown storage"):
        dispatch.resolve_storage_path("quantum", 1 << 20, 8193, "cpu")


def test_paged_threshold_overrides(tmp_path, restore_dispatch_globals):
    """The r14 storage entries ride the same committed-JSON machinery:
    paged_storage pins the backend off, paged_min_metrics retunes the
    crossover."""
    path = tmp_path / "dispatch_thresholds.json"
    path.write_text(json.dumps({
        "source": "TPU_CAPTURE_test",
        "paged_storage": False,
        "paged_min_metrics": 1 << 10,
    }))
    dispatch.THRESHOLDS_FILE = str(path)
    dispatch._load_thresholds()
    assert dispatch.PAGED_STORAGE is False
    assert dispatch.PAGED_MIN_METRICS == 1 << 10
    # the kill switch is a policy default, not a capability blocker
    # (same semantic as FUSED_INGEST): auto degrades with a reason,
    # explicit selection still resolves
    storage, reason = dispatch.resolve_storage_path(
        "auto", 1 << 20, 8193, "cpu"
    )
    assert storage == "dense" and "threshold table" in reason
    assert dispatch.resolve_storage_path(
        "paged", 1 << 20, 8193, "cpu"
    ) == ("paged", None)
    # retuned crossover applies
    path.write_text(json.dumps({
        "paged_storage": True, "paged_min_metrics": 1 << 10,
    }))
    dispatch._load_thresholds()
    assert dispatch.resolve_storage_path(
        "auto", 1 << 12, 8193, "cpu"
    )[0] == "paged"
    # wrong types must not poison the policy (bool is not an int count)
    path.write_text(json.dumps({
        "paged_storage": "sideways", "paged_min_metrics": True,
    }))
    dispatch._load_thresholds()
    assert dispatch.PAGED_STORAGE is True
    assert dispatch.PAGED_MIN_METRICS == 1 << 10


# -- FUSED_MIN_BATCH calibration (r17 satellite) ------------------------ #

def test_fused_min_batch_platform_override(tmp_path, restore_dispatch_globals):
    """The per-platform crossover table rides the same committed-JSON
    machinery; the running platform's entry wins, everything else falls
    back to the baked FUSED_MIN_BATCH."""
    path = tmp_path / "dispatch_thresholds.json"
    path.write_text(json.dumps({
        "source": "bench.py crossover sweep (tpu)",
        "fused_min_batch_by_platform": {"tpu": 1 << 15, "cpu": True},
    }))
    dispatch.THRESHOLDS_FILE = str(path)
    dispatch._load_thresholds()
    # bool entries are filtered at load (bool is an int subclass)
    assert dispatch.FUSED_MIN_BATCH_BY_PLATFORM == {"tpu": 1 << 15}
    assert dispatch.fused_min_batch_for("tpu") == 1 << 15
    assert dispatch.fused_min_batch_for("cpu") == dispatch.FUSED_MIN_BATCH
    assert dispatch.fused_min_batch_for(None) == dispatch.FUSED_MIN_BATCH


def test_fused_paged_kill_switch(tmp_path, restore_dispatch_globals):
    """fused_paged rides the threshold table like its siblings: the
    switch is policy (auto declines with the table's source named), and
    explicit selection overrides it via crossover=False."""
    path = tmp_path / "dispatch_thresholds.json"
    path.write_text(json.dumps({
        "source": "TPU_CAPTURE_test", "fused_paged": False,
    }))
    dispatch.THRESHOLDS_FILE = str(path)
    dispatch._load_thresholds()
    assert dispatch.FUSED_PAGED is False
    reason = dispatch.fused_paged_incapability(
        1 << 20, num_buckets=8193, batch_size=1 << 20, transport="raw",
        platform="tpu",
    )
    assert reason is not None and "TPU_CAPTURE_test" in reason
    assert dispatch.fused_paged_incapability(
        1 << 20, num_buckets=8193, transport="raw", crossover=False,
    ) is None


def test_derive_and_write_fused_min_batch(tmp_path, restore_dispatch_globals):
    """bench.py's calibration stage: a measured crossover becomes a
    platform-scoped entry merged into the thresholds file (other keys
    preserved); a sweep with no crossover writes nothing."""
    from benchmarks.fused_ingest_bench import (
        derive_fused_min_batch, write_fused_min_batch,
    )

    assert derive_fused_min_batch(
        {"platform": "cpu", "measured_crossover_batch": None}
    ) is None
    assert derive_fused_min_batch(
        {"platform": "", "measured_crossover_batch": 1 << 16}
    ) is None
    update = derive_fused_min_batch(
        {"platform": "tpu", "measured_crossover_batch": 1 << 16}
    )
    assert update == {"fused_min_batch_by_platform": {"tpu": 1 << 16}}

    path = tmp_path / "dispatch_thresholds.json"
    path.write_text(json.dumps({
        "source": "TPU_CAPTURE_test", "sort_min_metrics": 512,
        "fused_min_batch_by_platform": {"cpu": 1 << 18},
    }))
    write_fused_min_batch(update, path=str(path), source="bench sweep")
    table = json.loads(path.read_text())
    # merged, not clobbered: the capture's other entries survive
    assert table["sort_min_metrics"] == 512
    assert table["fused_min_batch_by_platform"] == {
        "cpu": 1 << 18, "tpu": 1 << 16,
    }
    assert table["source"] == "bench sweep"
    dispatch.THRESHOLDS_FILE = str(path)
    dispatch._load_thresholds()
    assert dispatch.fused_min_batch_for("tpu") == 1 << 16
    assert dispatch.fused_min_batch_for("cpu") == 1 << 18
    # creating the file from nothing works too
    fresh = tmp_path / "fresh.json"
    write_fused_min_batch(update, path=str(fresh))
    assert json.loads(fresh.read_text())[
        "fused_min_batch_by_platform"] == {"tpu": 1 << 16}
