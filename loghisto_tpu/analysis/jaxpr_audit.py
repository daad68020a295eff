"""jaxpr contract auditor: one declarative registry mapping every
compiled program factory to its contract, checked on CPU abstract
traces (``jax.make_jaxpr`` — nothing executes, no TPU required).

A ``Contract`` pins, per program:

  * ``dispatches``      — exact top-level program-launch count (pjit /
                          pallas_call eqns in the traced jaxpr; nested
                          pjits inline at compile time and don't count)
  * ``pallas_calls``    — exact pallas_call count anywhere in the tree
  * ``donated``         — exact donated-invar count on the program eqn,
                          each of which must alias an output with the
                          same shape+dtype (a donated carry whose update
                          silently stopped being returned — "dropped
                          donation" — fails here)
  * ``stream_psums``    — exact count of stream-axis psums (sharded
                          programs pin exactly one; single-device pin 0)
  * ``int32_scatter_shapes`` — carry shapes whose scatter-add updates
                          must stay int32 (cross-tile accumulation is
                          bit-exact only because integer adds commute)
  * ``forbidden_shapes``— intermediate shapes that must NOT appear as
                          any eqn output (paged routes pin the dense
                          [M, B] and the shard-local [M/s, B] shapes)

plus two global rules: no host-callback primitive may appear inside
any audited program, and stream psums on int carries must be int32.

``assert_contract(name)`` is the public entry point the per-test
guards delegate to; ``audit_all()`` feeds the CLI gate.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Callable, Sequence

from loghisto_tpu.analysis import Finding, relpath

STREAM_AXIS_NAME = "stream"

# f32 in-tile partial sums are exact only while a tile's total count
# stays under 2^24 (the float32 integer-exactness bound); the Pallas
# sample tile is the largest per-tile population one kernel invocation
# can accumulate before the int32 cross-tile fold takes over.
F32_EXACT_BOUND = 1 << 24


@dataclasses.dataclass(frozen=True)
class Contract:
    """Static contract for one compiled program.  ``None`` disables a
    check (used by ad-hoc ``audit_callable`` traces of un-jitted
    functions, where there is no program eqn to count)."""

    dispatches: int | None = 1
    pallas_calls: int | None = 0
    donated: int | None = 0
    stream_psums: int | None = 0
    int32_scatter_shapes: tuple = ()
    forbidden_shapes: tuple = ()
    description: str = ""


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    name: str
    factory: str                 # dotted factory path, for the docs table
    build: Callable              # () -> (traceable_fn, args tuple)
    contract: Contract


# ---------------------------------------------------------------------- #
# jaxpr walking
# ---------------------------------------------------------------------- #


def _user_line(eqn) -> tuple:
    """(file, line) of the user code that emitted ``eqn``."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    return (frame.file_name, frame.start_line) if frame else (None, id(eqn))


def _sub_jaxprs(params):
    """Yield every sub-jaxpr hiding in an eqn's params.  pjit/scan/cond
    carry ClosedJaxpr values (``.jaxpr`` attribute); shard_map and
    pallas_call carry raw Jaxprs (``.eqns`` directly); cond carries a
    tuple of branches."""
    for value in params.values():
        items = value if isinstance(value, (list, tuple)) else (value,)
        for item in items:
            inner = getattr(item, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner
            elif hasattr(item, "eqns"):
                yield item


def iter_eqns(jaxpr):
    """Depth-first over every eqn in a (Closed)Jaxpr and all sub-jaxprs."""
    if hasattr(jaxpr, "jaxpr"):      # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def jaxpr_primitives(closed) -> list:
    """(primitive name, output shapes) for every eqn, recursively —
    the shape the scattered per-test guards used to compute locally."""
    return [
        (eqn.primitive.name, [tuple(v.aval.shape) for v in eqn.outvars])
        for eqn in iter_eqns(closed)
    ]


def _aval_sig(var):
    aval = var.aval
    return (tuple(aval.shape), getattr(aval, "dtype", None))


# ---------------------------------------------------------------------- #
# the audit
# ---------------------------------------------------------------------- #

_PROGRAM_EQNS = ("pjit", "jit", "xla_call", "pallas_call")


def audit_jaxpr(closed, contract: Contract, name: str,
                path: str = "", line: int = 0) -> list[Finding]:
    """Check one traced program against its contract.  Returns findings
    (empty = contract holds)."""

    def finding(detail, reason):
        return Finding("jaxpr", path, line, name, detail, reason)

    out: list[Finding] = []
    top = closed.jaxpr if hasattr(closed, "jaxpr") else closed

    # -- dispatch budget: every top-level eqn is a device launch --
    if contract.dispatches is not None:
        launches = [e for e in top.eqns
                    if e.primitive.name in _PROGRAM_EQNS]
        stragglers = [e for e in top.eqns
                      if e.primitive.name not in _PROGRAM_EQNS]
        if len(launches) != contract.dispatches:
            out.append(finding(
                "dispatch-count",
                f"contract pins {contract.dispatches} dispatch(es), "
                f"trace has {len(launches)} top-level program eqns "
                f"({[e.primitive.name for e in launches]})",
            ))
        if stragglers:
            out.append(finding(
                "eager-top-level-eqn",
                "ops outside the jitted program would run eagerly "
                f"op-by-op at runtime: "
                f"{sorted({e.primitive.name for e in stragglers})}",
            ))

    all_eqns = list(iter_eqns(closed))

    # -- exact pallas_call census --
    if contract.pallas_calls is not None:
        n_pallas = sum(
            1 for e in all_eqns if e.primitive.name == "pallas_call"
        )
        if n_pallas != contract.pallas_calls:
            out.append(finding(
                "pallas-count",
                f"contract pins exactly {contract.pallas_calls} "
                f"pallas_call(s), trace has {n_pallas}",
            ))

    # -- donation: declared count, and every donated invar must alias
    #    an output (shape+dtype) or XLA silently drops the donation --
    if contract.donated is not None:
        donated_total = 0
        for eqn in top.eqns:
            flags = eqn.params.get("donated_invars")
            if not flags:
                continue
            sigs = [_aval_sig(var)
                    for var, is_donated in zip(eqn.invars, flags)
                    if is_donated]
            donated_total += len(sigs)
            outs = [_aval_sig(v) for v in eqn.outvars]
            for sig in sigs:
                if sig in outs:
                    outs.remove(sig)   # each output absorbs one donation
                else:
                    out.append(finding(
                        "donation-alias",
                        f"donated operand {sig[0]}:{sig[1]} has no "
                        "matching output aval — XLA drops the donation "
                        "silently and the carry double-buffers",
                    ))
        if donated_total != contract.donated:
            out.append(finding(
                "donation-count",
                f"contract pins {contract.donated} donated carr"
                f"{'y' if contract.donated == 1 else 'ies'}, program "
                f"donates {donated_total}",
            ))

    # -- exactly-one stream psum in sharded programs (0 elsewhere) --
    psums = [e for e in all_eqns if e.primitive.name.startswith("psum")
             and STREAM_AXIS_NAME in tuple(e.params.get("axes", ()))]
    if contract.stream_psums is not None:
        # one merge = one ``lax.psum`` call: jax >= 0.7 traces a psum of
        # a pytree as one ``psum_invariant`` eqn per operand, all
        # carrying the call's source line
        merges = len({_user_line(e) for e in psums})
        if merges != contract.stream_psums:
            out.append(finding(
                "psum-count",
                f"contract pins exactly {contract.stream_psums} "
                f"stream-axis psum(s), trace has {merges}",
            ))
        for eqn in psums:
            for var in eqn.outvars:
                shape, dtype = _aval_sig(var)
                if dtype is not None and dtype.kind == "i" \
                        and str(dtype) != "int32":
                    out.append(finding(
                        "psum-dtype",
                        f"stream psum output {shape} is {dtype}; "
                        "cross-device accumulation must be int32 for "
                        "bit-identity with the single-device path",
                    ))

    # -- int32 cross-tile accumulation on the declared carry shapes --
    for eqn in all_eqns:
        if not eqn.primitive.name.startswith("scatter"):
            continue
        for var in eqn.outvars:
            shape, dtype = _aval_sig(var)
            if shape in contract.int32_scatter_shapes \
                    and str(dtype) != "int32":
                out.append(finding(
                    "scatter-dtype",
                    f"scatter-add into carry shape {shape} is {dtype}; "
                    "the accumulation contract requires int32 (integer "
                    "adds commute, float adds do not)",
                ))

    # -- forbidden intermediates (dense [M, B] in paged routes) --
    if contract.forbidden_shapes:
        hit: set = set()
        for eqn in all_eqns:
            for var in eqn.outvars:
                shape = tuple(var.aval.shape)
                if shape in contract.forbidden_shapes and shape not in hit:
                    hit.add(shape)
                    out.append(finding(
                        "forbidden-shape",
                        f"forbidden dense intermediate {shape} "
                        f"materialized by `{eqn.primitive.name}` — the "
                        "paged route must never build an [M, B] tensor",
                    ))

    # -- no host round-trips inside an audited program --
    callbacks = sorted({
        e.primitive.name for e in all_eqns
        if "callback" in e.primitive.name
    })
    if callbacks:
        out.append(finding(
            "host-callback",
            f"host callback primitive(s) {callbacks} inside the "
            "program — every audited program must be a pure device "
            "launch",
        ))
    return out


def audit_callable(fn, args, contract: Contract, name: str = "<adhoc>",
                   **kwargs) -> list[Finding]:
    """Trace ``fn(*args, **kwargs)`` and audit the jaxpr — for ad-hoc
    guards over shapes the registry doesn't carry."""
    import jax

    closed = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)
    path, line = _callable_origin(fn)
    return audit_jaxpr(closed, contract, name, path, line)


def _callable_origin(fn) -> tuple[str, int]:
    try:
        target = inspect.unwrap(fn)
        code = getattr(target, "__code__", None)
        if code is None and hasattr(target, "__wrapped__"):
            code = target.__wrapped__.__code__
        if code is not None:
            return relpath(code.co_filename), code.co_firstlineno
    except Exception:
        pass
    return "loghisto_tpu/analysis/jaxpr_audit.py", 0


# ---------------------------------------------------------------------- #
# trace geometry
# ---------------------------------------------------------------------- #
#
# Shapes are chosen so every contracted quantity is unambiguous:
#   dense rows M=32 (ROWS_TILE-aligned), buckets B=129 (bucket_limit 64),
#   tier rings (slots 3, rows 32/16), batch N=256 (divides the stream
#   axis), mesh 4x2 (needs the 8 forced host devices).
#   Paged rows PM=40 and the shard-local PM/2=20 collide with NO other
#   dimension in the trace, so forbidding (40, 129) / (20, 129) pins
#   "no dense [M, B] on the paged route" without false positives.

BL = 64
B = 2 * BL + 1            # 129
M = 32
N = 256
TIERS = 2
RING_ROWS = (32, 16)
SLOTS = 3
VIEWS = 1
PM = 40                   # paged metric rows
PPR = 2                   # page-table pages per row
POOL_PAGES = 48
PAGE = 256                # ops.paged_store.PAGE_SIZE
BANKS = 2
MESH_SHAPE = (4, 2)       # (stream, metric)

_DENSE_CARRIES = ((M, B), (SLOTS, RING_ROWS[0], B), (SLOTS, RING_ROWS[1], B))
_POOL_CARRY = ((POOL_PAGES, PAGE),)
_NO_DENSE_MB = ((PM, B), (PM // MESH_SHAPE[1], B))


def _required_devices() -> int:
    return MESH_SHAPE[0] * MESH_SHAPE[1]


class AuditEnvironmentError(RuntimeError):
    pass


@functools.lru_cache(maxsize=1)
def _mesh():
    import jax

    need = _required_devices()
    if len(jax.devices()) < need:
        raise AuditEnvironmentError(
            f"jaxpr audit needs {need} devices for the mesh contracts; "
            f"have {len(jax.devices())}.  Run on CPU with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} (the "
            "analysis CLI and tests/conftest.py both set this)."
        )
    from loghisto_tpu.parallel.mesh import make_mesh

    return make_mesh(*MESH_SHAPE)


def _z(shape, dtype="int32"):
    import jax.numpy as jnp

    return jnp.zeros(shape, dtype=dtype)


def _scalar(value=0, dtype="int32"):
    import jax.numpy as jnp

    return jnp.asarray(value, dtype=dtype)


def _dense_carries():
    return (
        _z((M, B)),
        tuple(_z((SLOTS, rows, B)) for rows in RING_ROWS),
    )


def _cells():
    return _z((N,)), _z((N,)), _z((N,))       # ids, idx, weights


def _tier_scalars():
    return _z((TIERS,)), _z((TIERS,))          # slots, keeps


def _masks():
    return tuple(_z((VIEWS, SLOTS), dtype="bool") for _ in range(TIERS))


def _paged_carries():
    return (
        _z((POOL_PAGES, PAGE)),
        tuple(_z((SLOTS, rows, B)) for rows in (24, 16)),
    )


def _paged_ring_shapes():
    return ((SLOTS, 24, B), (SLOTS, 16, B))


def _triples():
    return _z((N, 3))


def _paged_luts():
    return _z((PM,)), _z((3, B)), _z((PM, PPR))  # row_codec, enc_luts, table


# ---------------------------------------------------------------------- #
# the registry
# ---------------------------------------------------------------------- #


def _spec(name, factory, build, **contract_kwargs):
    return ProgramSpec(name, factory, build, Contract(**contract_kwargs))


def _build_fused_commit():
    from loghisto_tpu.ops.commit import make_fused_commit_fn

    fn = make_fused_commit_fn(TIERS)
    acc, rings = _dense_carries()
    slots, keeps = _tier_scalars()
    return fn, (acc, rings, slots, keeps, *_cells())


def _build_fused_commit_full():
    from loghisto_tpu.ops.commit import make_fused_commit_fn

    fn = make_fused_commit_fn(TIERS, track_activity=True,
                              track_baseline=True)
    acc, rings = _dense_carries()
    slots, keeps = _tier_scalars()
    return fn, (acc, rings, _z((M,)), _z((M, B)), slots, keeps,
                *_cells(), _scalar(1), _scalar(1))


def _build_fused_commit_snapshot():
    from loghisto_tpu.ops.commit import make_fused_commit_snapshot_fn

    fn = make_fused_commit_snapshot_fn(TIERS, BL)
    acc, rings = _dense_carries()
    slots, keeps = _tier_scalars()
    return fn, (acc, rings, slots, keeps, *_cells(), _masks())


def _build_fused_commit_snapshot_full():
    from loghisto_tpu.ops.commit import make_fused_commit_snapshot_fn

    fn = make_fused_commit_snapshot_fn(
        TIERS, BL, track_activity=True, track_baseline=True
    )
    acc, rings = _dense_carries()
    slots, keeps = _tier_scalars()
    banks = (_z((BANKS, M, B), "float32"), _z((BANKS, M), "float32"))
    return fn, (acc, rings, _z((M,)), _z((M, B)), banks, slots, keeps,
                *_cells(), _scalar(1), _masks(), _scalar(1), _scalar(0),
                _scalar(0.5, "float32"), _scalar(10))


def _build_sharded_fused_commit():
    from loghisto_tpu.ops.commit import make_sharded_fused_commit_fn

    fn = make_sharded_fused_commit_fn(_mesh(), TIERS)
    acc, rings = _dense_carries()
    slots, keeps = _tier_scalars()
    return fn, (acc, rings, slots, keeps, *_cells())


def _build_sharded_fused_commit_snapshot():
    from loghisto_tpu.ops.commit import (
        make_sharded_fused_commit_snapshot_fn,
    )

    fn = make_sharded_fused_commit_snapshot_fn(_mesh(), TIERS, BL)
    acc, rings = _dense_carries()
    slots, keeps = _tier_scalars()
    return fn, (acc, rings, slots, keeps, *_cells(), _masks())


def _build_paged_fused_commit():
    from loghisto_tpu.ops.commit import make_paged_fused_commit_fn

    fn = make_paged_fused_commit_fn(TIERS)
    pool, rings = _paged_carries()
    slots, keeps = _tier_scalars()
    return fn, (pool, rings, slots, keeps, *_cells(), _triples())


def _build_paged_fused_commit_snapshot():
    from loghisto_tpu.ops.commit import make_paged_fused_commit_snapshot_fn

    fn = make_paged_fused_commit_snapshot_fn(TIERS, BL)
    pool, rings = _paged_carries()
    slots, keeps = _tier_scalars()
    return fn, (pool, rings, slots, keeps, *_cells(), _triples(),
                _masks())


def _build_sharded_paged_fused_commit():
    from loghisto_tpu.ops.commit import make_sharded_paged_fused_commit_fn

    fn = make_sharded_paged_fused_commit_fn(
        _mesh(), POOL_PAGES // MESH_SHAPE[1], TIERS
    )
    pool, rings = _paged_carries()
    slots, keeps = _tier_scalars()
    return fn, (pool, rings, slots, keeps, *_cells(), _triples())


def _build_sharded_paged_fused_commit_snapshot():
    from loghisto_tpu.ops.commit import (
        make_sharded_paged_fused_commit_snapshot_fn,
    )

    fn = make_sharded_paged_fused_commit_snapshot_fn(
        _mesh(), POOL_PAGES // MESH_SHAPE[1], TIERS, BL
    )
    pool, rings = _paged_carries()
    slots, keeps = _tier_scalars()
    return fn, (pool, rings, slots, keeps, *_cells(), _triples(),
                _masks())


def _build_fused_ingest():
    from loghisto_tpu.ops.fused_ingest import make_fused_ingest_fn

    fn = make_fused_ingest_fn(BL)
    return fn, (_z((M, B)), _z((N,)), _z((N,), "float32"))


def _build_fused_paged_ingest():
    from loghisto_tpu.ops.fused_ingest import make_fused_paged_ingest_fn

    fn = make_fused_paged_ingest_fn(BL)
    return fn, (_z((POOL_PAGES, PAGE)), _z((N,)), _z((N,), "float32"),
                *_paged_luts())


def _build_sharded_fused_paged_ingest():
    from loghisto_tpu.ops.fused_ingest import (
        make_sharded_fused_paged_ingest_fn,
    )

    fn = make_sharded_fused_paged_ingest_fn(
        _mesh(), PM // MESH_SHAPE[1], POOL_PAGES // MESH_SHAPE[1], BL
    )
    return fn, (_z((POOL_PAGES, PAGE)), _z((N,)), _z((N,), "float32"),
                *_paged_luts())


def _build_sparse_ingest(kernel):
    from loghisto_tpu.ops.sparse_ingest import make_sparse_ingest_fn

    fn = make_sparse_ingest_fn(BL, kernel=kernel)
    return fn, (_z((M, B)), _z((N, 3)))


def _build_flat_sparse_ingest():
    from loghisto_tpu.ops.sparse_ingest import make_flat_sparse_ingest_fn

    fn = make_flat_sparse_ingest_fn(BL, B)
    return fn, (_z((M * B,)), _z((N, 3)))


def _build_settle_ingest_delta():
    from loghisto_tpu.ops.sparse_ingest import settle_ingest_delta

    return settle_ingest_delta, (_z((M, B)), _z((M * B,)))


def _build_flat_sparse_ingest_sharded():
    from loghisto_tpu.ops.sparse_ingest import (
        make_sharded_flat_sparse_ingest_fn,
    )

    fn = make_sharded_flat_sparse_ingest_fn(
        _mesh(), BL, B, M // MESH_SHAPE[1]
    )
    return fn, (_z((MESH_SHAPE[0], M * B)), _z((N, 3)))


def _build_settle_ingest_delta_sharded():
    from loghisto_tpu.ops.sparse_ingest import make_sharded_settle_fn

    return make_sharded_settle_fn(_mesh()), (
        _z((M, B)), _z((MESH_SHAPE[0], M * B))
    )


def _build_paged_commit(kernel):
    from loghisto_tpu.ops.paged_store import make_paged_commit_fn

    fn = make_paged_commit_fn(kernel)
    return fn, (_z((POOL_PAGES, PAGE)), _z((N, 3)))


def _build_sharded_paged_commit():
    from loghisto_tpu.ops.paged_store import make_sharded_paged_commit_fn

    fn = make_sharded_paged_commit_fn(_mesh(), POOL_PAGES // MESH_SHAPE[1])
    return fn, (_z((POOL_PAGES, PAGE)), _z((N, 3)))


def _build_paged_query():
    from loghisto_tpu.config import PRECISION
    from loghisto_tpu.ops.paged_store import make_paged_query_fn

    fn = make_paged_query_fn(BL, PRECISION)
    # 5 requested rows, identity codec: dec_lut [B] storage buckets
    return fn, (_z((POOL_PAGES, PAGE)), _z((5, PPR)), _z((B,)),
                _z((3,), "float32"))


def _build_snapshot_query():
    from loghisto_tpu.ops.stats import make_snapshot_query_fn

    fn = make_snapshot_query_fn(BL)
    return fn, (_z((M, B)), _z((M,)), _z((M,), "float32"), _z((8,)),
                _z((3,), "float32"))


def _build_group_query():
    from loghisto_tpu.ops.stats import make_group_query_fn

    fn = make_group_query_fn(BL)
    args = (_z((M, B)), _z((M,)), _z((M,), "float32"), _z((8,)),
            _z((8,)), _z((3,), "float32"))
    return (lambda *a: fn(*a, num_groups=4)), args


def _build_fold_evict():
    from loghisto_tpu.ops.lifecycle import make_fold_evict_fn

    fn = make_fold_evict_fn(TIERS)
    acc, rings = _dense_carries()
    return fn, (acc, rings, _z((M,)), _z((4,)), _z((4,)), _scalar(1))


def _build_fold_evict_paged():
    from loghisto_tpu.ops.lifecycle import make_fold_evict_fn

    fn = make_fold_evict_fn(TIERS, with_acc=False)
    _, rings = _paged_carries()
    return fn, (rings, _z((PM,)), _z((4,)), _z((4,)), _scalar(1))


def _build_compact():
    from loghisto_tpu.ops.lifecycle import make_compact_fn

    fn = make_compact_fn(TIERS)
    acc, rings = _dense_carries()
    return fn, (acc, rings, _z((M,)), _z((M,)), _scalar(1))


def _build_divergence():
    from loghisto_tpu.ops.anomaly import make_divergence_fn

    fn = make_divergence_fn("jnp")
    return fn, (_z((M, B)), _z((M,)), _z((BANKS, M, B), "float32"),
                _z((BANKS, M), "float32"), _scalar(0), _scalar(10))


def _build_bank_evict():
    from loghisto_tpu.ops.anomaly import make_bank_evict_fn

    fn = make_bank_evict_fn()
    return fn, (_z((BANKS, M, B), "float32"), _z((BANKS, M), "float32"),
                _z((M, B)), _z((4,)))


def _build_bank_compact():
    from loghisto_tpu.ops.anomaly import make_bank_compact_fn

    fn = make_bank_compact_fn()
    return fn, (_z((BANKS, M, B), "float32"), _z((BANKS, M), "float32"),
                _z((M, B)), _z((M,)))


PROGRAMS: tuple[ProgramSpec, ...] = (
    # -- fused commit, dense carries ---------------------------------- #
    _spec("fused_commit", "ops.commit.make_fused_commit_fn",
          _build_fused_commit,
          donated=3, int32_scatter_shapes=_DENSE_CARRIES,
          description="chunk commit: acc fold + every tier's open-slot "
                      "scatter, one dispatch"),
    _spec("fused_commit_full", "ops.commit.make_fused_commit_fn[act,base]",
          _build_fused_commit_full,
          donated=5, int32_scatter_shapes=_DENSE_CARRIES,
          description="commit + activity stamp + interval histogram, "
                      "same dispatch"),
    _spec("fused_commit_snapshot",
          "ops.commit.make_fused_commit_snapshot_fn",
          _build_fused_commit_snapshot,
          donated=3, int32_scatter_shapes=_DENSE_CARRIES,
          description="final-chunk commit + snapshot payload emission"),
    _spec("fused_commit_snapshot_full",
          "ops.commit.make_fused_commit_snapshot_fn[act,base]",
          _build_fused_commit_snapshot_full,
          donated=7, int32_scatter_shapes=_DENSE_CARRIES,
          description="final chunk + activity + EWMA bank decay, one "
                      "dispatch"),
    _spec("sharded_fused_commit",
          "ops.commit.make_sharded_fused_commit_fn",
          _build_sharded_fused_commit,
          donated=3, stream_psums=1,
          description="mesh commit: shard-local scatters, ONE stream "
                      "psum"),
    _spec("sharded_fused_commit_snapshot",
          "ops.commit.make_sharded_fused_commit_snapshot_fn",
          _build_sharded_fused_commit_snapshot,
          donated=3, stream_psums=1,
          description="mesh final-chunk commit + shard-local snapshot"),
    # -- fused commit, paged pool carries ----------------------------- #
    _spec("paged_fused_commit", "ops.commit.make_paged_fused_commit_fn",
          _build_paged_fused_commit,
          donated=3, forbidden_shapes=_NO_DENSE_MB,
          int32_scatter_shapes=_POOL_CARRY,
          description="pool scatter + dense tier rings, one dispatch"),
    _spec("paged_fused_commit_snapshot",
          "ops.commit.make_paged_fused_commit_snapshot_fn",
          _build_paged_fused_commit_snapshot,
          donated=3, forbidden_shapes=_NO_DENSE_MB,
          int32_scatter_shapes=_POOL_CARRY,
          description="paged final-chunk commit + tier snapshots"),
    _spec("sharded_paged_fused_commit",
          "ops.commit.make_sharded_paged_fused_commit_fn",
          _build_sharded_paged_fused_commit,
          donated=3, stream_psums=1, forbidden_shapes=_NO_DENSE_MB,
          description="per-shard page arenas, ONE stream psum"),
    _spec("sharded_paged_fused_commit_snapshot",
          "ops.commit.make_sharded_paged_fused_commit_snapshot_fn",
          _build_sharded_paged_fused_commit_snapshot,
          donated=3, stream_psums=1, forbidden_shapes=_NO_DENSE_MB,
          description="sharded paged final chunk + snapshots"),
    # -- ingest ------------------------------------------------------- #
    _spec("fused_ingest", "ops.fused_ingest.make_fused_ingest_fn",
          _build_fused_ingest,
          donated=1, pallas_calls=1, int32_scatter_shapes=(),
          description="compress->bucket->scatter in ONE pallas_call; "
                      "no per-sample [M, B] scatter"),
    _spec("fused_paged_ingest",
          "ops.fused_ingest.make_fused_paged_ingest_fn",
          _build_fused_paged_ingest,
          donated=1, pallas_calls=1, forbidden_shapes=_NO_DENSE_MB,
          description="compress->encode->translate->scatter straight "
                      "into the donated pool"),
    _spec("sharded_fused_paged_ingest",
          "ops.fused_ingest.make_sharded_fused_paged_ingest_fn",
          _build_sharded_fused_paged_ingest,
          donated=1, stream_psums=1, forbidden_shapes=_NO_DENSE_MB,
          description="mesh direct-to-paged ingest (jnp scatter tier), "
                      "ONE stream psum"),
    _spec("sparse_ingest_jnp", "ops.sparse_ingest.make_sparse_ingest_fn",
          functools.partial(_build_sparse_ingest, "jnp"),
          donated=1, int32_scatter_shapes=((M, B),),
          description="packed [n,3] sparse merge, XLA scatter tier"),
    _spec("sparse_ingest_pallas",
          "ops.sparse_ingest.make_sparse_ingest_fn[pallas]",
          functools.partial(_build_sparse_ingest, "pallas"),
          donated=1, pallas_calls=1,
          description="packed [n,3] sparse merge, per-cell DMA kernel"),
    _spec("sparse_ingest_flat",
          "ops.sparse_ingest.make_flat_sparse_ingest_fn",
          _build_flat_sparse_ingest,
          donated=1, int32_scatter_shapes=((M * B,),),
          forbidden_shapes=((M, B), (B, M)),
          description="packed [n,3] merge into the flat delta; no [M, B] "
                      "operand, so no relayout of it"),
    _spec("settle_ingest_delta", "ops.sparse_ingest.settle_ingest_delta",
          _build_settle_ingest_delta, donated=2,
          description="flat delta added into the accumulator, returned "
                      "zeroed"),
    _spec("sparse_ingest_flat_sharded",
          "ops.sparse_ingest.make_sharded_flat_sparse_ingest_fn",
          _build_flat_sparse_ingest_sharded,
          donated=1, stream_psums=0,
          int32_scatter_shapes=((M // MESH_SHAPE[1] * B,),),
          forbidden_shapes=((M // MESH_SHAPE[1], B),
                            (B, M // MESH_SHAPE[1])),
          description="mesh flat merge: each device scatters its stream "
                      "slice into its own partial; no collective"),
    _spec("sharded_settle_ingest_delta",
          "ops.sparse_ingest.make_sharded_settle_fn",
          _build_settle_ingest_delta_sharded,
          donated=2, stream_psums=1,
          description="mesh partials merged by ONE stream psum, added "
                      "into the accumulator shards, returned zeroed"),
    # -- paged storage ------------------------------------------------ #
    _spec("paged_commit_jnp", "ops.paged_store.make_paged_commit_fn",
          functools.partial(_build_paged_commit, "jnp"),
          donated=1, forbidden_shapes=_NO_DENSE_MB,
          int32_scatter_shapes=_POOL_CARRY,
          description="translated-triple pool commit, XLA scatter"),
    _spec("paged_commit_pallas",
          "ops.paged_store.make_paged_commit_fn[pallas]",
          functools.partial(_build_paged_commit, "pallas"),
          donated=1, pallas_calls=1, forbidden_shapes=_NO_DENSE_MB,
          description="translated-triple pool commit, per-cell DMA"),
    _spec("sharded_paged_commit",
          "ops.paged_store.make_sharded_paged_commit_fn",
          _build_sharded_paged_commit,
          donated=1, stream_psums=1, forbidden_shapes=_NO_DENSE_MB,
          description="arena-local triple scatter, ONE stream psum"),
    _spec("paged_query", "ops.paged_store.make_paged_query_fn",
          _build_paged_query,
          donated=0, forbidden_shapes=_NO_DENSE_MB,
          description="page gather + codec decode + row stats; dense "
                      "only in the requested [n, B] rows, never [M, B]"),
    # -- query engine ------------------------------------------------- #
    _spec("snapshot_query", "ops.stats.make_snapshot_query_fn",
          _build_snapshot_query,
          donated=0,
          description="sparse row gather + percentile selection, never "
                      "donated (lock-free snapshot handles)"),
    _spec("group_query", "ops.stats.make_group_query_fn",
          _build_group_query,
          donated=0,
          description="segment-sum rollup + row stats, one dispatch"),
    # -- lifecycle ---------------------------------------------------- #
    _spec("fold_evict", "ops.lifecycle.make_fold_evict_fn",
          _build_fold_evict,
          donated=4, int32_scatter_shapes=_DENSE_CARRIES,
          description="victim fold into overflow rows + zero + stamp"),
    _spec("fold_evict_paged", "ops.lifecycle.make_fold_evict_fn[paged]",
          _build_fold_evict_paged,
          donated=3,
          description="ring-only fold (pool fold is a host translate)"),
    _spec("compact", "ops.lifecycle.make_compact_fn",
          _build_compact,
          donated=4,
          description="survivor-permutation repack of every carry"),
    # -- drift engine ------------------------------------------------- #
    _spec("divergence", "ops.anomaly.make_divergence_fn",
          _build_divergence,
          donated=0,
          description="KS/JSD/EMD vs the EWMA bank; operands are "
                      "snapshot handles, never donated"),
    _spec("bank_evict", "ops.anomaly.make_bank_evict_fn",
          _build_bank_evict,
          donated=3,
          description="zero victims' baselines + interval rows"),
    _spec("bank_compact", "ops.anomaly.make_bank_compact_fn",
          _build_bank_compact,
          donated=3,
          description="survivor permutation over the bank carries"),
)

_BY_NAME = {spec.name: spec for spec in PROGRAMS}


def program_names() -> tuple:
    return tuple(spec.name for spec in PROGRAMS)


def get_spec(name: str) -> ProgramSpec:
    try:
        return _BY_NAME[name]
    except KeyError:
        raise KeyError(
            f"unknown audited program {name!r}; registered: "
            f"{', '.join(sorted(_BY_NAME))}"
        ) from None


@functools.lru_cache(maxsize=None)
def _trace(name: str):
    """Trace the registered program on CPU abstract shapes.  Cached —
    the per-test delegations and the CLI share one trace per program."""
    import jax

    spec = get_spec(name)
    fn, args = spec.build()
    closed = jax.make_jaxpr(fn)(*args)
    path, line = _callable_origin(fn)
    return closed, path, line


def audit_program(name: str) -> list[Finding]:
    spec = get_spec(name)
    closed, path, line = _trace(name)
    return audit_jaxpr(closed, spec.contract, name, path, line)


def audit_spec(spec: ProgramSpec) -> list[Finding]:
    """Audit an out-of-registry ProgramSpec (fixture programs, ad-hoc
    guards over store-specific shapes)."""
    import jax

    fn, args = spec.build()
    closed = jax.make_jaxpr(fn)(*args)
    path, line = _callable_origin(fn)
    return audit_jaxpr(closed, spec.contract, spec.name, path, line)


def assert_contract(name: str) -> None:
    """The per-test entry point: raise AssertionError listing every
    violated contract clause for ``name``."""
    findings = audit_program(name)
    if findings:
        raise AssertionError(
            f"static contract violated for program {name!r}:\n"
            + "\n".join("  " + f.render() for f in findings)
        )


def constant_findings() -> list[Finding]:
    """Static dtype-rule constants: the Pallas in-tile f32 partial sums
    are exact only while a tile's population stays under 2^24."""
    from loghisto_tpu.ops import pallas_kernels

    out: list[Finding] = []
    if pallas_kernels.SAMPLE_TILE >= F32_EXACT_BOUND:
        out.append(Finding(
            "jaxpr", "loghisto_tpu/ops/pallas_kernels.py", 40,
            "SAMPLE_TILE", "f32-tile-bound",
            f"SAMPLE_TILE={pallas_kernels.SAMPLE_TILE} >= 2^24 breaks "
            "the f32 in-tile exactness bound",
        ))
    return out


def audit_all(names: Sequence[str] | None = None) -> list[Finding]:
    """Audit every registered program (the CLI gate's jaxpr pass)."""
    out: list[Finding] = []
    for name in (names or program_names()):
        out.extend(audit_program(name))
    if names is None:
        out.extend(constant_findings())
    return out
