"""Automatic ingest/storage/commit path selection (VERDICT r1 item 6,
unified capability table r17).

Six bit-identical device accumulation kernels exist (scatter / sort-dedup
scatter / scan-based sort-dedup ("sortscan") / one-hot MXU matmul /
Pallas row / Pallas multirow, plus the hot-row hybrid); they differ
only in speed per (num_metrics, num_buckets, platform) configuration.
``TPUAggregator(ingest_path="auto")`` — the default — calls
``choose_ingest_path`` at construction (platform is known then; this is
NOT a trace-time probe).

The baked thresholds rank the kernels per metric count (Pallas row at
M=1, scatter through the mid range, sort-dedup / fused at 10k).  The
ranking they were drawn from was not taken on a directly attached chip,
and no kernel rate is measured on this chip yet; benchmarks/
device_paths.py run on the chip, then analyze_capture.py, retunes them
through the committed thresholds file.  The reasoning stands until
then: duplicate-heavy scatters serialize on TPU, so sort-dedup should
win back the lead at high metric cardinality where Zipf batches
concentrate on hot rows.  On CPU
the scatter path wins everywhere measured (BENCH_r01 table), so auto ==
scatter there.

Capability table (r17)
----------------------

Through r16 this module grew three independent contender ladders —
``fused_ingest_incapability`` (ingest), ``paged_storage_incapability``
(storage), and ``mesh_commit_incapability`` (commit) — each a
copy-pasted walk of if-return-reason checks.  The r17 direct-to-paged
fused kernel would have been a fourth.  They are now rows of ONE
``CAPABILITY_TABLE``: each (axis, contender) maps to an ordered tuple
of edges, each edge a named check returning its human-readable reason
string (or None), with policy edges (amortization crossovers — things
an explicit selection is allowed to override) flagged so
``crossover=False`` skips exactly those.  The public
``*_incapability`` functions are thin views over the table — every
pre-r17 reason string survives verbatim (tests pin them) — and
``resolve_full_path`` walks the single ``DEGRADATION_ORDER`` to
resolve a complete (transport, ingest, storage, commit) path with the
per-edge reasons of everything it declined along the way.
"""

from __future__ import annotations

import dataclasses
import json as _json
import os as _os
from typing import Callable, Dict, NamedTuple, Optional, Tuple

# Measured crossover (device_paths.json): sort-dedup overtakes plain
# scatter between M=256 and M=10000; the conservative switch point keeps
# scatter through the mid range it dominates.  Baked FALLBACK — a
# committed capture-derived table (below) overrides it.
SORT_MIN_METRICS = 4096

# Whether auto picks the fused Pallas row kernel at M=1 on TPU.  The
# masked pallas_row_ingest_batch form auto dispatches has not been
# ranked on the chip — this default is an extrapolation until a capture
# ranks "pallasb" (analyze_capture.py flags the comparison).
PALLAS_SINGLE_METRIC = True

# Which sort-dedup formulation auto uses at high cardinality: "sort"
# (jnp.unique-based) or "sortscan" (sort + reverse min-scan, 3x on CPU,
# awaiting a hardware ranking).  Capture-overridable like the rest.
HIGH_CARDINALITY_KERNEL = "sort"

# Whether auto considers the r13 fused sample->scatter Pallas kernel
# (ops/fused_ingest.py: codec on the VPU inside the kernel, one
# dispatch, no HBM bucket-index array) at high metric cardinality on
# TPU.  It replaces the sort-dedup pick where capable; when
# fused_ingest_incapability names a blocker (mesh-embedded step, row
# tile, dtype, batch too small/unknown) auto degrades to the pre-r13
# winner.  Capture-overridable.
FUSED_INGEST = True

# Whether auto considers the r17 direct-to-paged fused kernel
# (ops/fused_ingest.fused_paged_ingest_batch: compress -> log-bucket ->
# codec-encode -> page-translate -> scatter-add straight into the
# donated page pool, ONE dispatch per batch, no dense [M, B] tensor and
# no host fold on the hot path).  Only meaningful when storage resolves
# to "paged"; when fused_paged_incapability names a blocker the paged
# path degrades to the pre-r17 two-stage route (host fold + translate +
# packed pool commit).  Capture-overridable.
FUSED_PAGED = True

# Minimum batch the fused kernel's XLA sort+layout preprocess amortizes
# over: below this the plain scatter's per-sample random access is
# cheaper than sorting the batch and padding block segments to
# SAMPLE_TILE boundaries.  Baked FALLBACK from the r13 CPU-host
# calibration sweep (benchmarks/fused_ingest_bench.py, FUSED_INGEST_r13
# "crossover" section); a hardware capture retunes it via the committed
# JSON like every other threshold.
FUSED_MIN_BATCH = 1 << 17

# Per-platform measured crossover overrides for FUSED_MIN_BATCH
# (r17 satellite): the r13 CPU-interpret sweep is NOT trustworthy for
# the TPU default, so calibration writes a platform-scoped entry
# ("fused_min_batch_by_platform": {"cpu": ..., "tpu": ...}) and the
# capability check consults the running platform's entry, falling back
# to the baked FUSED_MIN_BATCH when the platform was never measured.
FUSED_MIN_BATCH_BY_PLATFORM: Dict[str, int] = {}

# Metric rows per fused-kernel accumulator block; mirrored from
# fused_ingest.ROWS_TILE without importing jax (this module must stay
# importable without jax — analyze_capture.py depends on that).
FUSED_ROWS_TILE = 8

# Dense one-hot matmul materializes an [N, B] one-hot per tile; the r2
# table shows it never beating scatter on hardware at >=16 metrics, and
# losing to the Pallas row kernel at M=1 — it remains available for
# explicit selection but auto no longer picks it.
MATMUL_MAX_CELLS = 1 << 21

# Whether auto commits intervals through the fused single-dispatch
# program (ops/commit.py: aggregator fold + all retention tiers in one
# donated-carry launch) instead of the per-consumer fan-out.  The fused
# program is pure XLA scatter composition — bit-identical to the fan-out
# by construction (tests/test_commit.py) — so it defaults on; a hardware
# capture that ever ranks the fan-out faster flips this via the same
# committed-JSON machinery as the ingest thresholds.
FUSED_COMMIT = True

# Host->device transport crossover (r6): "auto" transport folds each
# raw flush on host and measures cell density = unique_cells / samples.
# At or below this crossover the batch is skewed enough that shipping
# packed [n,3] triples (transport="sparse", 12B/cell) beats shipping
# every sample (8B/sample) — both on wire bytes and on device work
# (weighted scatter over cells vs per-sample compress+scatter).  Above
# it the fold overhead isn't paid back and raw stays.  0.5 is the
# conservative break-even from the wire-bytes ratio alone
# (12*density < 8 => density < 2/3, minus fold-cost margin); a capture
# retunes it via the committed-JSON table like every other threshold.
SPARSE_DENSITY_CROSSOVER = 0.5

# Which device tier the sparse transport's packed-triple scatter uses:
# "jnp" (XLA weighted scatter-add) or "pallas" (per-cell DMA row
# round-trip, ops/sparse_ingest.py).  The Pallas tier is bit-identical
# but not yet hardware-ranked, so auto stays on jnp until a capture
# flips this.
SPARSE_KERNEL = "jnp"

# Whether storage="auto" considers the r14 paged bucket backend
# (ops/paged_store.py + loghisto_tpu/paging.py): the dense [M, B]
# accumulator replaced by a page pool + page table so HBM and commit
# H2D track OCCUPIED buckets.  Auto only switches at high metric
# cardinality — below the crossover the dense tensor fits HBM trivially
# and its donated in-place commit beats the translate step's host work.
PAGED_STORAGE = True

# Metric-row crossover for storage="auto": the dense accumulator at
# M=2^16 x B=8193 x 4B is ~2.1 GiB of HBM and the page pool wins
# outright on sparse occupancy (PAGED_STORE_r14); below it dense wins
# on simplicity.  Baked FALLBACK, capture-overridable like the rest.
PAGED_MIN_METRICS = 1 << 16

# Buckets per pool page; mirrored from ops/paged_store.PAGE_SIZE
# without importing jax (this module must stay importable without jax).
PAGE_SIZE = 256

# Fixed paged-commit launch width; mirrored from
# ops/paged_store.COMMIT_CHUNK without importing jax.  The mesh edges
# below check the stream axis divides it (the sharded paged commit
# splits the padded triple wire over the stream axis).
PAGED_COMMIT_CHUNK = 1 << 14

# Capture-derived threshold table (VERDICT r2 item 7): refreshing the
# dispatch policy after a hardware capture is a committed JSON (emitted
# by ``benchmarks/analyze_capture.py --emit-thresholds``), not a code
# edit.  Lives next to this module; absent or unreadable -> the baked
# constants above stand.  Stdlib-only so the module stays importable
# without jax (analyze_capture.py depends on that).
THRESHOLDS_FILE = _os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "dispatch_thresholds.json"
)
THRESHOLDS_SOURCE = "baked-in defaults"


def _load_thresholds() -> None:
    global SORT_MIN_METRICS, PALLAS_SINGLE_METRIC, THRESHOLDS_SOURCE
    global HIGH_CARDINALITY_KERNEL, FUSED_COMMIT
    global SPARSE_DENSITY_CROSSOVER, SPARSE_KERNEL
    global FUSED_INGEST, FUSED_MIN_BATCH, FUSED_MIN_BATCH_BY_PLATFORM
    global PAGED_STORAGE, PAGED_MIN_METRICS, FUSED_PAGED
    try:
        with open(THRESHOLDS_FILE) as f:
            table = _json.load(f)
    except (OSError, ValueError):
        return
    if not isinstance(table, dict):
        return
    applied = False
    smm = table.get("sort_min_metrics")
    if isinstance(smm, int) and smm > 1:
        SORT_MIN_METRICS = smm
        applied = True
    psm = table.get("pallas_single_metric")
    if isinstance(psm, bool):
        PALLAS_SINGLE_METRIC = psm
        applied = True
    hck = table.get("high_cardinality_kernel")
    if hck in ("sort", "sortscan"):
        HIGH_CARDINALITY_KERNEL = hck
        applied = True
    fc = table.get("fused_commit")
    if isinstance(fc, bool):
        FUSED_COMMIT = fc
        applied = True
    sdc = table.get("sparse_density_crossover")
    # bool is an int subclass; a stray true/false must not become 1.0/0.0
    if (
        isinstance(sdc, (int, float))
        and not isinstance(sdc, bool)
        and 0.0 <= sdc <= 1.0
    ):
        SPARSE_DENSITY_CROSSOVER = float(sdc)
        applied = True
    sk = table.get("sparse_kernel")
    if sk in ("jnp", "pallas"):
        SPARSE_KERNEL = sk
        applied = True
    fi = table.get("fused_ingest")
    if isinstance(fi, bool):
        FUSED_INGEST = fi
        applied = True
    fmb = table.get("fused_min_batch")
    if isinstance(fmb, int) and not isinstance(fmb, bool) and fmb >= 1:
        FUSED_MIN_BATCH = fmb
        applied = True
    fmbp = table.get("fused_min_batch_by_platform")
    if isinstance(fmbp, dict):
        clean = {
            str(k): v
            for k, v in fmbp.items()
            if isinstance(v, int) and not isinstance(v, bool) and v >= 1
        }
        if clean:
            FUSED_MIN_BATCH_BY_PLATFORM = clean
            applied = True
    fp = table.get("fused_paged")
    if isinstance(fp, bool):
        FUSED_PAGED = fp
        applied = True
    pst = table.get("paged_storage")
    if isinstance(pst, bool):
        PAGED_STORAGE = pst
        applied = True
    pmm = table.get("paged_min_metrics")
    if isinstance(pmm, int) and not isinstance(pmm, bool) and pmm > 1:
        PAGED_MIN_METRICS = pmm
        applied = True
    if applied:  # never cite a table that contributed nothing
        THRESHOLDS_SOURCE = str(table.get("source", THRESHOLDS_FILE))


_load_thresholds()


def fused_min_batch_for(platform: Optional[str]) -> int:
    """The effective fused-kernel batch crossover for a platform: the
    calibrated per-platform entry when a measured sweep wrote one
    (bench.py's calibration stage / a hardware capture), else the baked
    FUSED_MIN_BATCH fallback.  ``platform=None`` (callers that never
    learned the backend) always gets the fallback."""
    if platform is not None:
        v = FUSED_MIN_BATCH_BY_PLATFORM.get(platform)
        if isinstance(v, int) and not isinstance(v, bool) and v >= 1:
            return v
    return FUSED_MIN_BATCH


# -- the capability table -------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class PathContext:
    """Everything a capability edge may inspect — one context shape for
    every axis, so edges compose across contenders (the fused_paged row
    reuses the fused-ingest and paged-storage edges verbatim)."""

    num_metrics: int = 0
    num_buckets: Optional[int] = None
    platform: Optional[str] = None
    batch_size: Optional[int] = None
    mesh: bool = False
    mesh_obj: object = None  # the Mesh, when the caller has one
    transport: str = "sparse"
    acc_dtype: str = "int32"
    fused_ok: bool = False  # a capable fused_paged path relaxes edges


class CapabilityEdge(NamedTuple):
    """One named check of one contender.  ``policy=True`` marks
    performance policy (amortization crossovers, platform preferences)
    that ``crossover=False`` — an explicit operator selection — may
    override; ``policy=False`` edges are correctness and always apply.
    ``check(ctx)`` returns the human-readable reason string (what the
    operator sees in the auto-degrade log or the explicit-path raise)
    or None when the edge passes."""

    name: str
    policy: bool
    check: Callable[[PathContext], Optional[str]]


# -- ingest:fused edges (r13 strings, preserved verbatim) --


def _ck_fused_mesh(ctx: PathContext) -> Optional[str]:
    if ctx.mesh:
        return (
            "mesh shape: the fused kernel does not run inside a "
            "shard_map-embedded step (pallas_call under shard_map is not "
            "hardware-validated; the sharded path keeps its dispatched "
            "local fold)"
        )
    return None


def _ck_fused_rows_tile(ctx: PathContext) -> Optional[str]:
    if ctx.num_metrics % FUSED_ROWS_TILE:
        return (
            f"mesh shape: num_metrics={ctx.num_metrics} does not divide by "
            f"the fused kernel's {FUSED_ROWS_TILE}-row metric tile"
        )
    return None


def _ck_fused_dtype(ctx: PathContext) -> Optional[str]:
    if ctx.acc_dtype != "int32":
        return (
            f"dtype: accumulator dtype {ctx.acc_dtype} is not int32 — the "
            "fused kernel's per-tile f32 one-hot accumulation is "
            "integer-exact only against the int32 dense layout"
        )
    return None


def _ck_fused_batch(ctx: PathContext) -> Optional[str]:
    min_batch = fused_min_batch_for(ctx.platform)
    if ctx.batch_size is None:
        return (
            "batch too small: batch size unknown, cannot prove the "
            f"sort+layout preprocess amortizes (needs >= {min_batch} "
            "samples/batch)"
        )
    if ctx.batch_size < min_batch:
        return (
            f"batch too small: {ctx.batch_size} samples/batch does not "
            "amortize the fused kernel's sort+layout preprocess "
            f"(measured crossover {min_batch})"
        )
    return None


# -- storage:paged edges (r14 strings, preserved verbatim) --


def _ck_paged_mesh(ctx: PathContext) -> Optional[str]:
    # r18: the page pool is no longer a single-device arena — each
    # metric shard owns its own page arena and the paged commit runs
    # shard-local inside one shard_map (ops/paged_store.
    # make_sharded_paged_commit_fn).  The edge now declines only the
    # mesh SHAPES the sharded arenas genuinely cannot take.
    if not ctx.mesh:
        return None
    mesh = ctx.mesh_obj
    if mesh is None:
        # bool-only callers carry no shape to inspect: admitted here;
        # the same shape edges re-run wherever the Mesh is in hand
        # (resolve_full_path, PagedStore's constructor backstop)
        return None
    from loghisto_tpu.parallel.mesh import METRIC_AXIS, STREAM_AXIS

    axes = tuple(getattr(mesh, "axis_names", ()))
    if STREAM_AXIS not in axes or METRIC_AXIS not in axes:
        return (
            f"mesh shape: mesh axes {axes!r} are not the "
            f"('{STREAM_AXIS}', '{METRIC_AXIS}') layout the per-shard "
            "page arenas partition over"
        )
    n_metric = mesh.shape[METRIC_AXIS]
    if ctx.num_metrics and ctx.num_metrics % n_metric:
        return (
            f"mesh shape: num_metrics={ctx.num_metrics} rows don't "
            f"shard evenly over the {n_metric}-way metric axis, so the "
            "page arenas cannot split per shard"
        )
    n_stream = mesh.shape[STREAM_AXIS]
    if PAGED_COMMIT_CHUNK % n_stream:
        return (
            f"mesh shape: the {PAGED_COMMIT_CHUNK}-triple paged commit "
            f"chunk does not split over the {n_stream}-way stream axis"
        )
    return None


def _ck_paged_transport(ctx: PathContext) -> Optional[str]:
    allowed = ("sparse", "auto", "raw") if ctx.fused_ok else ("sparse", "auto")
    if ctx.transport not in allowed:
        return (
            f"transport: paged storage commits through the packed "
            f"[n,3] sparse-triple fold (transport='sparse'); "
            f"transport={ctx.transport!r} ships whole batches with no host "
            "fold, so there is no translate step to route cells through "
            "the page table"
        )
    return None


def _ck_paged_bucket_axis(ctx: PathContext) -> Optional[str]:
    if ctx.num_buckets is not None and ctx.num_buckets < PAGE_SIZE:
        return (
            f"bucket axis: num_buckets={ctx.num_buckets} is smaller than "
            f"one {PAGE_SIZE}-bucket page — the dense row is already "
            "cheaper than any page table"
        )
    return None


def _ck_paged_crossover(ctx: PathContext) -> Optional[str]:
    if ctx.num_metrics < PAGED_MIN_METRICS:
        return (
            f"below crossover: {ctx.num_metrics} metric rows — the dense "
            f"accumulator fits HBM trivially below {PAGED_MIN_METRICS} "
            "rows and its donated in-place commit wins (PAGED_STORE_r14)"
        )
    return None


# -- commit:fused edges (mesh strings, preserved verbatim) --


def _ck_commit_axes(ctx: PathContext) -> Optional[str]:
    mesh = ctx.mesh_obj
    if mesh is None:
        return None
    from loghisto_tpu.parallel.mesh import METRIC_AXIS, STREAM_AXIS

    axes = tuple(getattr(mesh, "axis_names", ()))
    if STREAM_AXIS not in axes or METRIC_AXIS not in axes:
        return (
            f"mesh axes {axes!r} are not the ('{STREAM_AXIS}', "
            f"'{METRIC_AXIS}') commit layout"
        )
    return None


def _ck_commit_rows(ctx: PathContext) -> Optional[str]:
    mesh = ctx.mesh_obj
    if mesh is None:
        return None
    from loghisto_tpu.parallel.mesh import METRIC_AXIS, STREAM_AXIS

    axes = tuple(getattr(mesh, "axis_names", ()))
    if STREAM_AXIS not in axes or METRIC_AXIS not in axes:
        return None  # the axes edge already declined
    n_metric = mesh.shape[METRIC_AXIS]
    if ctx.num_metrics and ctx.num_metrics % n_metric:
        return (
            f"num_metrics={ctx.num_metrics} rows don't shard evenly over "
            f"the {n_metric}-way metric axis"
        )
    return None


# -- ingest:fused_paged edges (r17) --


def _ck_fused_paged_switch(ctx: PathContext) -> Optional[str]:
    if not FUSED_PAGED:
        return (
            "disabled: fused_paged is off in the threshold table "
            f"({THRESHOLDS_SOURCE})"
        )
    return None


def _ck_fused_paged_transport(ctx: PathContext) -> Optional[str]:
    if ctx.transport not in ("raw", "auto"):
        return (
            "transport: the direct-to-paged fused kernel ingests RAW "
            "samples (compress, codec-encode, and page-translate all "
            f"happen on device in one dispatch); transport="
            f"{ctx.transport!r} folds cells on host first, leaving the "
            "one-dispatch path nothing to fuse — the folded route keeps "
            "the translate + packed pool commit"
        )
    return None


def _ck_fused_paged_mesh(ctx: PathContext) -> Optional[str]:
    # Unlike the dense fused kernel (pallas_call under shard_map is not
    # hardware-validated — _ck_fused_mesh stands), the sharded
    # direct-to-paged step runs its scatter on the jnp tier inside
    # shard_map (ops/fused_ingest.make_sharded_fused_paged_ingest_fn),
    # so a mesh only declines on batch split shape.
    if not ctx.mesh:
        return None
    mesh = ctx.mesh_obj
    if mesh is None:
        return None
    from loghisto_tpu.parallel.mesh import STREAM_AXIS

    axes = tuple(getattr(mesh, "axis_names", ()))
    if STREAM_AXIS not in axes:
        return None  # the pool_mesh edge names the axis-layout reason
    n_stream = mesh.shape[STREAM_AXIS]
    if ctx.batch_size is not None and ctx.batch_size % n_stream:
        return (
            f"mesh shape: batch_size={ctx.batch_size} samples don't "
            f"split over the {n_stream}-way stream axis for the "
            "shard_map-embedded direct-to-paged step"
        )
    return None


def _ck_fused_paged_platform(ctx: PathContext) -> Optional[str]:
    if ctx.platform is not None and ctx.platform != "tpu":
        return (
            f"platform: {ctx.platform} — auto only picks the direct-to-"
            "paged fused kernel on TPU (the interpret-mode Pallas tier is "
            "parity-only; explicit selection remains the opt-in)"
        )
    return None


# The table: (axis, contender) -> ordered edges.  The fused_paged row is
# COMPOSED from the fused-ingest and paged-storage edges plus its own —
# the refactor's point: a new contender is a new row, not a fourth
# copy-pasted ladder.  Note what it does NOT inherit: the rows_tile and
# dtype edges (the paged kernel is per-sample gather + per-cell DMA —
# no ROWS_TILE accumulator blocks, and the pool is int32 by
# construction), and the sparse-transport edge (it exists to ingest raw
# batches directly).
CAPABILITY_TABLE: Dict[Tuple[str, str], Tuple[CapabilityEdge, ...]] = {
    ("ingest", "fused"): (
        CapabilityEdge("mesh", False, _ck_fused_mesh),
        CapabilityEdge("rows_tile", False, _ck_fused_rows_tile),
        CapabilityEdge("dtype", False, _ck_fused_dtype),
        CapabilityEdge("batch", True, _ck_fused_batch),
    ),
    ("storage", "paged"): (
        CapabilityEdge("mesh", False, _ck_paged_mesh),
        CapabilityEdge("transport", False, _ck_paged_transport),
        CapabilityEdge("bucket_axis", False, _ck_paged_bucket_axis),
        CapabilityEdge("crossover", True, _ck_paged_crossover),
    ),
    ("commit", "fused"): (
        CapabilityEdge("mesh_axes", False, _ck_commit_axes),
        CapabilityEdge("rows", False, _ck_commit_rows),
    ),
    ("ingest", "fused_paged"): (
        CapabilityEdge("switch", True, _ck_fused_paged_switch),
        CapabilityEdge("mesh", False, _ck_fused_paged_mesh),
        CapabilityEdge("pool_mesh", False, _ck_paged_mesh),
        CapabilityEdge("bucket_axis", False, _ck_paged_bucket_axis),
        CapabilityEdge("transport", False, _ck_fused_paged_transport),
        CapabilityEdge("platform", True, _ck_fused_paged_platform),
        CapabilityEdge("batch", True, _ck_fused_batch),
    ),
}

# The single degradation order per axis — the ladder every "auto"
# resolution walks, most-capable contender first.  (The ingest ladder's
# sort entry is HIGH_CARDINALITY_KERNEL at resolve time; "scatter" is
# the unconditional floor on every axis where it appears.)
DEGRADATION_ORDER: Dict[str, Tuple[str, ...]] = {
    "ingest": ("fused_paged", "fused", "sort", "scatter"),
    "storage": ("paged", "dense"),
    "commit": ("fused", "fanout"),
    "transport": ("sparse", "raw"),
}


def incapability(
    axis: str,
    contender: str,
    ctx: PathContext,
    include_policy: bool = True,
) -> Optional[Tuple[str, str]]:
    """Walk one table row: the first failing edge as ``(edge_name,
    reason)``, or None when the contender is capable.  This is the ONE
    reason-string walk behind every ``*_incapability`` view —
    ``include_policy=False`` is what the explicit-selection
    ``crossover=False`` contract maps onto."""
    for edge in CAPABILITY_TABLE[(axis, contender)]:
        if edge.policy and not include_policy:
            continue
        reason = edge.check(ctx)
        if reason is not None:
            return edge.name, reason
    return None


# -- public incapability views (pre-r17 signatures, table-backed) ----- #


def fused_ingest_incapability(
    num_metrics: int,
    batch_size: int | None = None,
    mesh: bool = False,
    acc_dtype: str = "int32",
    crossover: bool = True,
    platform: str | None = None,
) -> str | None:
    """Why a configuration genuinely cannot (or should not) run the r13
    fused sample->scatter kernel, as a human-readable reason string — or
    None when it can.  Mirrors ``mesh_commit_incapability``'s shape:
    "auto" degrades silently on a reason, an EXPLICIT
    ``ingest_path="fused"`` surfaces the same string in its raise, so
    the operator always learns WHY fused ingest was declined.

    ``crossover=False`` skips the amortization checks (batch unknown /
    batch too small) — those are performance policy, not correctness,
    and an explicit selection is allowed to eat the preprocess cost.
    ``platform``, when known, selects the calibrated per-platform batch
    crossover (fused_min_batch_for)."""
    ctx = PathContext(
        num_metrics=num_metrics, batch_size=batch_size, mesh=mesh,
        acc_dtype=acc_dtype, platform=platform,
    )
    hit = incapability("ingest", "fused", ctx, include_policy=crossover)
    return None if hit is None else hit[1]


def fused_paged_incapability(
    num_metrics: int,
    num_buckets: int | None = None,
    batch_size: int | None = None,
    mesh: bool = False,
    transport: str = "auto",
    platform: str | None = None,
    crossover: bool = True,
    mesh_obj=None,
) -> str | None:
    """Why a configuration cannot (or should not) take the r17
    direct-to-paged fused ingest — the one-dispatch compress -> encode
    -> page-translate -> pool-scatter kernel.  Same contract as its
    siblings: auto degrades (to the host-fold translate + packed pool
    commit) with the reason, an explicit ``ingest_path="fused"`` on a
    paged store raises it; ``crossover=False`` skips the policy edges
    (platform preference, batch amortization, threshold switch).
    ``mesh_obj`` (the Mesh, when in hand) lets the r18 mesh edges check
    the actual shard shape instead of blanket-declining."""
    ctx = PathContext(
        num_metrics=num_metrics, num_buckets=num_buckets,
        batch_size=batch_size, mesh=mesh, transport=transport,
        platform=platform, mesh_obj=mesh_obj,
    )
    hit = incapability("ingest", "fused_paged", ctx, include_policy=crossover)
    return None if hit is None else hit[1]


def paged_storage_incapability(
    num_metrics: int,
    num_buckets: int | None = None,
    mesh: bool = False,
    transport: str = "sparse",
    crossover: bool = True,
    fused_ok: bool = False,
    mesh_obj=None,
) -> str | None:
    """Why a configuration genuinely cannot (or should not) run the r14
    paged bucket backend, as a human-readable reason string — or None
    when it can.  Same contract as ``fused_ingest_incapability``:
    storage="auto" degrades silently on a reason, an EXPLICIT
    ``storage="paged"`` surfaces the same string in its raise.

    ``crossover=False`` skips the metric-cardinality check — that is
    capacity policy, not correctness, and an explicit selection is
    allowed to page a small deployment (the tests do).  ``fused_ok=True``
    (the r17 direct-to-paged fused kernel is capable) relaxes the
    transport edge: raw batches then ingest straight into the pool with
    no host fold, so "raw" no longer disqualifies paged storage."""
    ctx = PathContext(
        num_metrics=num_metrics, num_buckets=num_buckets, mesh=mesh,
        transport=transport, fused_ok=fused_ok, mesh_obj=mesh_obj,
    )
    hit = incapability("storage", "paged", ctx, include_policy=crossover)
    return None if hit is None else hit[1]


def mesh_commit_incapability(mesh, num_metrics=None) -> str | None:
    """Why a sharded configuration genuinely cannot run the fused
    commit under ``shard_map``, as a human-readable reason string — or
    None when it can (including ``mesh=None``: single-device state is
    always capable).  The checks mirror what the sharded program
    actually requires:

      * the mesh must carry the ("stream", "metric") commit layout —
        the program psums cell deltas over the stream axis and keeps
        every carry metric-row-sharded;
      * ``num_metrics`` (when known) must split evenly over the metric
        axis, or the carries cannot take their ``P(metric)`` row
        sharding at all.
    """
    ctx = PathContext(
        num_metrics=num_metrics or 0, mesh=mesh is not None, mesh_obj=mesh
    )
    hit = incapability("commit", "fused", ctx)
    return None if hit is None else hit[1]


# -- resolution ------------------------------------------------------- #


def choose_ingest_path(
    num_metrics: int, num_buckets: int, platform: str
) -> str:
    """Pick the measured-fastest ingest kernel for a configuration.

    The Pallas multirow kernel stays opt-in: it was never the fastest
    in the ranking the thresholds come from, so "auto" does not select
    it.  The Pallas row
    kernel (winner at M=1) participates via its masked
    pallas_row_ingest_batch form, which has the standard (ids, values)
    contract (see PALLAS_SINGLE_METRIC note on the extrapolation).  At
    high cardinality on TPU the r13 fused sample->scatter kernel is the
    preferred pick (one dispatch, codec on-chip); resolve_ingest_path
    degrades it to HIGH_CARDINALITY_KERNEL when
    ``fused_ingest_incapability`` names a blocker.
    """
    if platform == "tpu" and num_metrics == 1 and PALLAS_SINGLE_METRIC:
        # the fused Pallas row kernel wins the single-metric config
        # outright (r2 hardware table); its masked (ids, values) form
        # makes it contract-compatible with the other paths
        return "pallas"
    if platform == "tpu" and num_metrics >= SORT_MIN_METRICS:
        if FUSED_INGEST:
            return "fused"
        return HIGH_CARDINALITY_KERNEL
    return "scatter"


def resolve_ingest_path(
    path: str,
    num_metrics: int,
    num_buckets: int,
    platform: str,
    guard_metrics: int | None = None,
    batch_size: int | None = None,
    mesh: bool = False,
) -> str:
    """Resolve "auto" and enforce per-path shape preconditions — THE
    dispatch-guard policy, shared by TPUAggregator, the firehose, and the
    bench so the benchmarked default can never drift from the product
    default.  Auto never picks a kernel the shape invalidates (falls back
    to scatter), while an EXPLICIT choice the shape cannot support raises
    here — at selection time — instead of silently corrupting histograms
    inside the traced kernel (the sort and matmul paths' combined int32
    cell keys wrap negative past 2^31 cells).

    ``guard_metrics`` is the row count to validate shapes against when it
    exceeds ``num_metrics`` — TPUAggregator passes its growth cap
    (max_metrics) so auto cannot pick a kernel that registry growth would
    later invalidate.  ``batch_size``, when known, guards hybrid's
    float32 hot-head exactness bound (per-batch counts < 2^24); auto
    refuses to pick "pallas" when the bound is UNKNOWN (batch_size=None)
    — the precondition would otherwise surface as a trace-time raise
    inside a shard_map step (ADVICE r2).  ``mesh=True`` marks a
    shard_map-embedded resolve: auto additionally skips "pallas" there
    (pallas_call inside shard_map is not hardware-validated; explicit
    selection remains available as the opt-in)."""
    from loghisto_tpu.ops.sort_ingest import validate_flat_cell_shape

    guard = max(num_metrics, guard_metrics or 0)
    batch_too_big = batch_size is not None and batch_size >= 1 << 24
    if path == "auto":
        # auto never raises for a precondition: it just doesn't pick the
        # kernel the shape/batch would invalidate
        path = choose_ingest_path(num_metrics, num_buckets, platform)
        if path == "fused" and fused_ingest_incapability(
            guard, batch_size=batch_size, mesh=mesh, platform=platform
        ) is not None:
            # degrade to the pre-r13 high-cardinality winner, which then
            # takes its own shape validation below
            path = HIGH_CARDINALITY_KERNEL
        if path in ("sort", "sortscan"):
            try:
                validate_flat_cell_shape(guard, num_buckets, path)
            except ValueError:
                path = "scatter"
        elif path == "pallas" and (
            guard != 1 or batch_size is None or batch_too_big or mesh
        ):
            # registry growth can widen the row space past the
            # single-row kernel; auto must not pick it unless the cap
            # pins M=1 (explicit "pallas" instead swaps kernels on grow),
            # the batch bound is known to satisfy the float32-exactness
            # precondition, and the step is not shard_map-embedded
            path = "scatter"
        return path
    if path == "fused":
        # explicit selection: correctness blockers raise with the reason
        # string; the crossover (a perf policy) is the operator's call
        reason = fused_ingest_incapability(
            guard, batch_size=batch_size, mesh=mesh, crossover=False
        )
        if reason is not None:
            raise ValueError(f"fused ingest unavailable: {reason}")
    if path in ("sort", "sortscan", "matmul"):
        validate_flat_cell_shape(guard, num_buckets, path)
    elif path in ("hybrid", "pallas") and batch_too_big:
        raise ValueError(
            f"{path} ingest batches must stay < 2^24 samples (float32 "
            f"accumulation exactness); got batch_size={batch_size}"
        )
    if path == "pallas" and num_metrics != 1:
        raise ValueError(
            "ingest_path='pallas' is the single-metric row kernel; got "
            f"num_metrics={num_metrics} (growth past 1 row swaps kernels "
            "automatically, but the starting shape must be [1, B])"
        )
    return path


def resolve_sparse_kernel(kernel: str) -> str:
    """Resolve the sparse transport's device tier ("auto" follows the
    capture-overridable SPARSE_KERNEL switch)."""
    if kernel == "auto":
        return SPARSE_KERNEL
    if kernel not in ("jnp", "pallas"):
        raise ValueError(
            f"unknown sparse kernel {kernel!r}: expected 'auto', 'jnp', "
            "or 'pallas'"
        )
    return kernel


def choose_transport(
    platform: str, density: float | None = None, native_ok: bool = True
) -> str:
    """Pick the host->device transport for transport="auto".

    ``density`` is the measured unique-cell / samples ratio of a probe
    flush (None before any probe has run).  The policy: start on "raw"
    (zero host fold cost, always correct), and switch to "sparse" once a
    probe shows the load is skewed enough that shipping packed triples
    wins (density <= SPARSE_DENSITY_CROSSOVER).  "preagg" is never
    auto-picked: it trades flush latency for record()-time fold work,
    which only pays off when the *recording* threads are the bottleneck
    — a workload property no flush-side probe can see — so it stays an
    explicit opt-in.  ``native_ok=False`` (no compiler AND numpy tier
    unavailable — today never, the numpy tier always exists) pins raw.
    """
    del platform  # crossover is wire/fold-cost driven, not device-driven
    if not native_ok:
        return "raw"
    if density is not None and density <= SPARSE_DENSITY_CROSSOVER:
        return "sparse"
    return "raw"


def resolve_storage_path(
    storage: str,
    num_metrics: int,
    num_buckets: int,
    platform: str,
    mesh: bool = False,
    transport: str = "sparse",
    fused_ok: bool = False,
    mesh_obj=None,
) -> tuple[str, str | None]:
    """Resolve the accumulator storage backend: "dense" (the donated
    [M, B] tensor) or "paged" (page pool + page table + per-row codecs,
    r14).  Mirrors ``resolve_commit_path``: "auto" degrades to dense
    with the reason (returned, so TPUAggregator can surface it as
    ``storage_reason``), an explicit "paged" a capability blocker
    invalidates raises the same string, and unknown names raise.

    Returns ``(resolved, reason)`` — reason is None unless auto
    declined paged.

    ``fused_ok=True`` marks a capable r17 direct-to-paged fused ingest:
    the transport edge then admits "raw" (see
    ``paged_storage_incapability``).

    Labeled metrics (ISSUE 16): ``num_metrics`` counts REGISTRY ROWS,
    and under the canonical label encoding every distinct label set of
    a base name (``http.latency;code=500;route=/api``) is its own row —
    so label cardinality, not base-name count, is what drives this
    crossover.  A service with 50 base names and 10k live label sets is
    a 10k-row deployment and typically wants paged storage; see
    ``TPUMetricSystem.debug_dump()["labels"]["cardinality_by_prefix"]``
    for the live per-prefix label population.
    """
    del platform  # both backends run on every platform (interpret tier)
    if storage == "auto":
        if not PAGED_STORAGE:
            return "dense", "paged storage disabled by threshold table"
        reason = paged_storage_incapability(
            num_metrics, num_buckets, mesh=mesh, transport=transport,
            fused_ok=fused_ok, mesh_obj=mesh_obj,
        )
        if reason is not None:
            return "dense", reason
        return "paged", None
    if storage not in ("dense", "paged"):
        raise ValueError(
            f"unknown storage {storage!r}: expected 'auto', 'dense', or "
            "'paged'"
        )
    if storage == "paged":
        reason = paged_storage_incapability(
            num_metrics, num_buckets, mesh=mesh, transport=transport,
            crossover=False, fused_ok=fused_ok, mesh_obj=mesh_obj,
        )
        if reason is not None:
            raise ValueError(f"paged storage unavailable: {reason}")
    return storage, None


def resolve_commit_path(
    path: str, platform: str, mesh=None, num_metrics: int | None = None
) -> str:
    """Resolve the interval-commit path: "fused" (one donated-carry
    program for the aggregator fold + every retention tier,
    ops/commit.py) or "fanout" (the per-consumer bridge-merge +
    per-tier-scatter launches).  "auto" follows the capture-overridable
    FUSED_COMMIT switch — the same threshold machinery as the ingest
    kernels, so a hardware capture retunes this with a committed JSON,
    not a code edit.

    ``mesh`` takes the ("stream", "metric") mesh object when the state
    is sharded (or None).  Resolution is capability-based, not a
    blanket downgrade: sharded state runs the fused path under
    ``shard_map`` unless ``mesh_commit_incapability`` reports a shape
    that genuinely cannot shard (wrong axis layout, rows not divisible
    by the metric axis) — "auto" then degrades to the fan-out, and an
    explicit "fused" raises with the reason string.  A legacy boolean
    ``mesh=True`` (no mesh object to inspect) is treated as a capable
    sharded configuration.

    ``num_metrics`` here too counts registry rows under the canonical
    label encoding (one row per live label set, see
    loghisto_tpu/labels/model.py) — a labeled deployment's divisibility
    and sizing checks run against label cardinality, not base names."""
    mesh_obj = None if isinstance(mesh, bool) or mesh is None else mesh
    reason = mesh_commit_incapability(mesh_obj, num_metrics)
    if path == "auto":
        if reason is not None:
            return "fanout"
        return "fused" if FUSED_COMMIT else "fanout"
    if path not in ("fused", "fanout"):
        raise ValueError(
            f"unknown commit path {path!r}: expected 'auto', 'fused', or "
            "'fanout'"
        )
    if path == "fused" and reason is not None:
        raise ValueError(f"fused commit unavailable on this mesh: {reason}")
    return path


class FullPath(NamedTuple):
    """One resolved end-to-end dispatch: which wire the samples ride
    (transport), which kernel consumes them (ingest), which layout
    accumulates them (storage), and which program closes the interval
    (commit) — plus every reason the walk declined a more-capable
    contender, keyed "axis:contender"."""

    transport: str
    ingest: str
    storage: str
    commit: str
    reasons: Dict[str, str]


def resolve_full_path(
    num_metrics: int,
    num_buckets: int,
    platform: str,
    ingest: str = "auto",
    storage: str = "auto",
    transport: str = "auto",
    commit: str = "auto",
    batch_size: int | None = None,
    mesh=None,
    guard_metrics: int | None = None,
    density: float | None = None,
) -> FullPath:
    """THE composed resolver (r17): one walk of the capability table's
    degradation orders that answers all four axes together, because the
    axes are NOT independent — paged storage without the fused kernel
    pins the sparse transport (the translate step rides the host fold),
    while a capable fused_paged contender inverts that (raw samples
    ingest straight into the pool and the host fold disappears).  The
    per-edge reasons of every declined contender come back in
    ``reasons`` so callers (TPUAggregator's ``storage_reason`` /
    ``fused_paged_reason``, the bench's path table) surface WHY, with
    the same strings the explicit paths raise."""
    reasons: Dict[str, str] = {}
    mesh_flag = mesh is not None and mesh is not False
    mesh_obj = None if isinstance(mesh, bool) or mesh is None else mesh

    # 1. the fused_paged contender's capability gates BOTH the storage
    #    transport edge and the ingest ladder's top rung
    fp_reason = fused_paged_incapability(
        num_metrics, num_buckets, batch_size=batch_size, mesh=mesh_flag,
        transport=transport, platform=platform,
        crossover=(ingest == "auto"), mesh_obj=mesh_obj,
    )
    fused_ok = fp_reason is None and ingest in ("auto", "fused")
    if fp_reason is not None:
        reasons["ingest:fused_paged"] = fp_reason

    # 2. storage (may raise on explicit-invalid, same as before)
    storage_res, s_reason = resolve_storage_path(
        storage, num_metrics, num_buckets, platform, mesh=mesh_flag,
        transport=transport, fused_ok=fused_ok, mesh_obj=mesh_obj,
    )
    if s_reason is not None:
        reasons["storage:paged"] = s_reason

    # 3. ingest + transport, jointly
    if storage_res == "paged" and fused_ok:
        if ingest == "fused" and fp_reason is not None:
            raise ValueError(f"fused paged ingest unavailable: {fp_reason}")
        ingest_res = "fused_paged"
        transport_res = "raw"  # the batch IS the wire; no host fold
    elif storage_res == "paged":
        if ingest == "fused" and fp_reason is not None:
            raise ValueError(f"fused paged ingest unavailable: {fp_reason}")
        # pre-r17 paged route: host fold -> translate -> packed commit;
        # no per-sample ingest kernel runs at all
        ingest_res = "packed"
        transport_res = "sparse"
    else:
        ingest_res = resolve_ingest_path(
            ingest, num_metrics, num_buckets, platform,
            guard_metrics=guard_metrics, batch_size=batch_size,
            mesh=mesh_flag,
        )
        if transport == "auto":
            transport_res = choose_transport(platform, density=density)
        else:
            transport_res = transport

    # 4. commit
    commit_reason = mesh_commit_incapability(mesh_obj, num_metrics)
    if commit_reason is not None:
        reasons["commit:fused"] = commit_reason
    commit_res = resolve_commit_path(
        commit, platform, mesh=mesh if mesh_obj is not None else mesh_flag,
        num_metrics=num_metrics,
    )
    return FullPath(transport_res, ingest_res, storage_res, commit_res,
                    reasons)


def ingest_step_fn(path: str):
    """The pure per-batch accumulation function for a named path, with the
    uniform ``f(acc, ids, values, bucket_limit, precision) -> acc``
    contract (scatter / sort / sortscan / hybrid / matmul / pallas — the
    paths whose dense accumulator layout is interchangeable; pallas
    additionally requires acc shape [1, B]).  Used wherever a traced step
    needs the dispatched kernel inline (firehose generation loop, bench
    interval loop) rather than the TPUAggregator's jitted wrappers.
    The r17 "fused_paged" contender is NOT here: its accumulator is the
    page pool + LUT operands, a different contract
    (ops/fused_ingest.fused_paged_ingest_batch)."""
    if path == "sort":
        from loghisto_tpu.ops.sort_ingest import sort_ingest_batch

        return sort_ingest_batch
    if path == "sortscan":
        from loghisto_tpu.ops.sort_ingest import sortscan_ingest_batch

        return sortscan_ingest_batch
    if path == "hybrid":
        from loghisto_tpu.ops.hybrid_hist import ingest_batch_hybrid

        return ingest_batch_hybrid
    if path == "matmul":
        from loghisto_tpu.ops.matmul_hist import ingest_batch_matmul

        return ingest_batch_matmul
    if path == "pallas":
        from loghisto_tpu.ops.pallas_kernels import pallas_row_ingest_batch

        return pallas_row_ingest_batch
    if path == "fused":
        from loghisto_tpu.ops.fused_ingest import fused_ingest_batch

        return fused_ingest_batch
    if path != "scatter":
        raise ValueError(
            f"no pure step form for ingest_path {path!r}: expected "
            "'scatter', 'sort', 'sortscan', 'hybrid', 'matmul', "
            "'pallas', or 'fused'"
        )
    from loghisto_tpu.ops.ingest import ingest_batch

    return ingest_batch
