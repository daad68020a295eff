"""Device programs for the distribution drift engine (ISSUE 7):
EWMA baseline-bank maintenance and fused divergence scoring.

The paper's log-bucket histograms keep the ENTIRE distribution losslessly
— yet scalar rules throw that away at the last step.  These kernels put
the distribution back into alerting:

  * ``ewma_bank_update`` — the baseline side.  Per metric row, a bank of
    EWMA-decayed bucket *profiles* (normalized histograms) tracks "what
    this metric's distribution usually looks like"; configurable banks
    (e.g. one global + per-hour banks) absorb seasonality.  The update
    runs INSIDE the fused commit's donated-carry program
    (ops/commit.py ``track_baseline``) over the interval histogram the
    commit is already scattering — zero extra dispatches, the identical
    fusion economics as the lifecycle's activity stamp.
  * ``make_divergence_fn`` — the scoring side.  ONE fused dispatch per
    interval compares each live window CDF (the commit-time snapshot
    payload the query engine already materializes for free) against its
    baseline bank: Kolmogorov–Smirnov distance, Jensen–Shannon
    divergence (base-2, bounded [0, 1]), and bucket-space earth-mover's
    distance.  A jnp tier and a Pallas tier share one row-math helper,
    so the two are bit-identical (tests/test_anomaly.py pins this).
  * ``make_bank_evict_fn`` / ``make_bank_compact_fn`` — lifecycle
    integration: evicted rows zero their baselines (a reused slot must
    start cold, never inherit the dead series' shape) and compaction
    applies the same survivor permutation as every other carry.

Mesh-sharded state (PR 8): ``ewma_bank_update`` is row-elementwise, so
the sharded fused commit calls it shard-local inside its ``shard_map``
program on metric-row-sharded banks — same-order float ops per row,
hence bit-identical to the single-device path.  The divergence scorer
and the bank evict/compact programs jit over the sharded carries and
let GSPMD place the (row-parallel) math; scores read back replicated.

Divergence definitions, all in dense bucket space (axis index b = codec
bucket b - bucket_limit; log buckets make one step ~= precision% in
value space):

  ks  = max_b |F_live(b) - F_base(b)|            in [0, 1]
  emd = sum_b |F_live(b) - F_base(b)|            bucket-index units
  jsd = JS divergence of the pmfs, log base 2    in [0, 1]

Rows below the min-sample floor (live count < min_samples) or without an
established baseline (bank weight == 0) score exactly 0 — noise and
cold starts must not page.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from loghisto_tpu.ops.lifecycle import _sanitize_perm
from loghisto_tpu.ops.backend import default_interpret

ROWS_TILE = 8  # f32/int32 sublane tile, same as the window merge


# ---------------------------------------------------------------------- #
# baseline bank maintenance (runs inside the fused commit program)
# ---------------------------------------------------------------------- #


def ewma_bank_update(banks, ihist, bank, decay, min_count):
    """One EWMA step of the active baseline bank from a completed
    interval histogram.  Pure traceable math — ops/commit.py inlines it
    into the final-chunk fused program, so it costs zero dispatches.

      banks     (prof f32 [K, M, B], wsum f32 [K, M]) — donated carries
      ihist     int32 [M, B] — the interval's merged histogram
      bank      traced int32 scalar — active bank index (time-of-day
                selection happens host-side)
      decay     traced f32 scalar — EWMA retain factor in [0, 1)
      min_count traced int32 scalar — rows with fewer interval samples
                keep their baseline untouched (a quiet interval must not
                wash the profile toward zero)

    ``prof`` rows are EWMA mixes of per-interval *pmfs* and ``wsum`` is
    the matching EWMA weight mass (``decay*w + (1-decay)`` whenever the
    row updates), so ``prof/wsum`` is always a bias-corrected pmf — a
    young baseline after one update compares exactly, not attenuated by
    the EWMA warm-up.
    """
    prof, wsum = banks
    counts = jnp.sum(ihist, axis=1)                       # int32 [M]
    upd = counts >= min_count                             # bool  [M]
    tot = jnp.maximum(counts, 1).astype(jnp.float32)[:, None]
    pmf = ihist.astype(jnp.float32) / tot                 # [M, B]
    old_p = prof[bank]
    old_w = wsum[bank]
    gain = jnp.float32(1.0) - decay
    new_p = jnp.where(upd[:, None], decay * old_p + gain * pmf, old_p)
    new_w = jnp.where(upd, decay * old_w + gain, old_w)
    return prof.at[bank].set(new_p), wsum.at[bank].set(new_w)


# ---------------------------------------------------------------------- #
# divergence scoring
# ---------------------------------------------------------------------- #


def _baseline(prof, w):
    """Bias-corrected baseline pmf and its CDF, f32 [R, B] each.  w == 0
    rows are masked by the caller; the epsilon only keeps the division
    finite for them."""
    base_pmf = prof / jnp.maximum(w, jnp.float32(1e-30))[:, None]
    return base_pmf, jnp.cumsum(base_pmf, axis=1)


def _row_scores(cdf, counts, base_pmf, base_cdf):
    """Raw per-row divergence scores (no floor mask): cdf int32 [R, B],
    counts int32 [R], base_pmf/base_cdf f32 [R, B] -> (ks, jsd, emd),
    each f32 [R].  Row-independent elementwise math + axis-1 reductions
    ONLY — this is what makes the jnp and Pallas tiers bit-identical
    (the Pallas kernel applies the same function per 8-row tile)."""
    total = jnp.maximum(counts, 1).astype(jnp.float32)[:, None]
    live_cdf = cdf.astype(jnp.float32) / total
    # exact integer bin counts first, divide after — differencing the
    # float CDF would lose low-order bits
    bins = cdf - jnp.concatenate(
        [jnp.zeros_like(cdf[:, :1]), cdf[:, :-1]], axis=1
    )
    live_pmf = bins.astype(jnp.float32) / total
    diff = jnp.abs(live_cdf - base_cdf)
    ks = jnp.max(diff, axis=1)
    emd = jnp.sum(diff, axis=1)
    mid = jnp.float32(0.5) * (live_pmf + base_pmf)

    def kl_to_mid(p):
        # 0*log(0) := 0; where p > 0, mid >= p/2 > 0 so the ratio is
        # finite — the unselected lanes' NaNs are discarded by where
        return jnp.sum(
            jnp.where(p > 0, p * jnp.log2(p / mid), jnp.float32(0.0)),
            axis=1,
        )

    jsd = jnp.float32(0.5) * (kl_to_mid(live_pmf) + kl_to_mid(base_pmf))
    return ks, jsd, emd


def _div_kernel(cdf_ref, cnt_ref, pmf_ref, bcdf_ref,
                ks_ref, jsd_ref, emd_ref):
    ks, jsd, emd = _row_scores(
        cdf_ref[...], cnt_ref[...][:, 0], pmf_ref[...], bcdf_ref[...]
    )
    ks_ref[...] = ks[:, None]
    jsd_ref[...] = jsd[:, None]
    emd_ref[...] = emd[:, None]


def divergence_pallas(cdf, counts, base_pmf, base_cdf, interpret=None):
    """Pallas tier of the raw divergence scores: grid over metric tiles,
    each [ROWS_TILE, B] live/baseline block resident in VMEM while its
    three scores reduce.  The baseline's prefix sum is not in the kernel
    (Pallas has no TPU lowering of cumsum): the caller computes it with
    the same ``_baseline`` the jnp tier runs.  Row padding is
    score-neutral (padded rows are sliced off) and the per-row math is
    the SAME function the jnp tier runs, so results are bit-identical."""
    if interpret is None:
        interpret = default_interpret()
    m, b = cdf.shape
    m_pad = (m + ROWS_TILE - 1) // ROWS_TILE * ROWS_TILE
    if m_pad != m:
        gap = m_pad - m
        cdf = jnp.pad(cdf, ((0, gap), (0, 0)))
        counts = jnp.pad(counts, (0, gap))
        base_pmf = jnp.pad(base_pmf, ((0, gap), (0, 0)))
        base_cdf = jnp.pad(base_cdf, ((0, gap), (0, 0)))
    grid = (m_pad // ROWS_TILE,)
    row_spec = pl.BlockSpec((ROWS_TILE, b), lambda i: (i, 0))
    col_spec = pl.BlockSpec((ROWS_TILE, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        _div_kernel,
        grid=grid,
        in_specs=[row_spec, col_spec, row_spec, row_spec],
        out_specs=(col_spec, col_spec, col_spec),
        out_shape=tuple(
            jax.ShapeDtypeStruct((m_pad, 1), jnp.float32) for _ in range(3)
        ),
        interpret=interpret,
    )(cdf, counts[:, None], base_pmf, base_cdf)
    return tuple(o[:m, 0] for o in out)


def resolve_divergence_path(path: str, platform: str, mesh: bool) -> str:
    """Dispatch policy for the divergence tier, like the ingest
    dispatch: "auto" picks Pallas only single-device on real TPU (Pallas under shard_map is off the table; interpret mode off-TPU
    is strictly slower than the jnp form)."""
    if path not in ("auto", "jnp", "pallas"):
        raise ValueError(
            f"divergence_path={path!r}: expected 'auto', 'jnp', or "
            "'pallas'"
        )
    if path == "auto":
        return "pallas" if (platform == "tpu" and not mesh) else "jnp"
    if path == "pallas" and mesh:
        raise ValueError("divergence_path='pallas' is single-device; use "
                         "jnp with a mesh")
    return path


def divergence_scores(cdf, counts, prof, wsum, bank, min_samples,
                      path: str = "jnp"):
    """Full scoring pass: live window CDF vs the active baseline bank.

      cdf         int32 [M, B] — snapshot view CDF (commit-time payload)
      counts      int32 [M]    — snapshot view totals
      prof/wsum   f32 [K, Mb, B] / f32 [K, Mb] — the baseline bank
      bank        traced int32 scalar — bank to compare against
      min_samples traced int32 scalar — the min-sample floor

    Returns {"ks", "jsd", "emd"}: f32 [M] each, exactly 0 for rows below
    the floor or without an established baseline (wsum == 0 — including
    every row past the bank's high-water when the accumulator grew).
    The bank gather, both tiers' row math, and the floor mask all trace
    into ONE jitted program: one device dispatch per scoring pass.
    """
    m = cdf.shape[0]
    bprof = prof[bank]
    bw = wsum[bank]
    mb = bprof.shape[0]
    if mb < m:
        # the accumulator/wheel grew past the bank (rare, between carry
        # growth points): new rows have no baseline — masked below
        bprof = jnp.pad(bprof, ((0, m - mb), (0, 0)))
        bw = jnp.pad(bw, (0, m - mb))
    else:
        bprof = bprof[:m]
        bw = bw[:m]
    # materialized for both tiers (the Pallas tier has to take them as
    # operands), so XLA cannot fuse them into the jnp tier's scores
    # differently from what the kernel sees
    base_pmf, base_cdf = jax.lax.optimization_barrier(_baseline(bprof, bw))
    if path == "pallas":
        ks, jsd, emd = divergence_pallas(cdf, counts, base_pmf, base_cdf)
    else:
        ks, jsd, emd = _row_scores(cdf, counts, base_pmf, base_cdf)
    valid = (counts >= min_samples) & (bw > 0)
    zero = jnp.float32(0.0)
    return {
        "ks": jnp.where(valid, ks, zero),
        "jsd": jnp.where(valid, jsd, zero),
        "emd": jnp.where(valid, emd, zero),
    }


@functools.lru_cache(maxsize=None)
def make_divergence_fn(path: str = "jnp"):
    """Jitted ``div(cdf, counts, prof, wsum, bank, min_samples) ->
    {"ks","jsd","emd"}`` — the drift engine's single per-interval
    dispatch.  Cached per path; bank and min_samples are traced, so bank
    rotation (time-of-day) never recompiles.  Snapshot payloads are
    never donated (they back the lock-free query handles), so neither
    are the operands here."""

    @jax.jit
    def div(cdf, counts, prof, wsum, bank, min_samples):
        return divergence_scores(
            cdf, counts, prof, wsum, bank, min_samples, path
        )

    return div


# ---------------------------------------------------------------------- #
# lifecycle integration: bank eviction + compaction
# ---------------------------------------------------------------------- #


@functools.lru_cache(maxsize=None)
def make_bank_evict_fn():
    """``evict(prof, wsum, ihist, victims) -> (prof, wsum, ihist)``:
    zero the victims' baselines and interval-histogram rows in one
    donated dispatch (DROP_ID pads shed).  A freed row's next tenant
    must build its baseline from scratch — leaking the dead series'
    shape would score the newcomer against a stranger's history."""

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def evict(prof, wsum, ihist, victims):
        prof = prof.at[:, victims].set(0.0, mode="drop")
        wsum = wsum.at[:, victims].set(0.0, mode="drop")
        ihist = ihist.at[victims].set(0, mode="drop")
        return prof, wsum, ihist

    return evict


@functools.lru_cache(maxsize=None)
def make_bank_compact_fn():
    """``compact(prof, wsum, ihist, perm) -> (prof, wsum, ihist)``:
    apply the lifecycle's survivor permutation (``perm[new] = old``,
    DROP sentinel = empty -> zeros) to every bank carry — the same
    one-gather-per-structure repack as ops.lifecycle.make_compact_fn,
    so baselines follow their rows and freed rows come back cold."""

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def compact(prof, wsum, ihist, perm):
        mb = prof.shape[1]
        sp = _sanitize_perm(perm[:mb], mb)
        prof = jnp.take(prof, sp, axis=1, mode="fill", fill_value=0)
        wsum = jnp.take(wsum, sp, axis=1, mode="fill", fill_value=0)
        mi = ihist.shape[0]
        ihist = jnp.take(
            ihist, _sanitize_perm(perm[:mi], mi), axis=0,
            mode="fill", fill_value=0,
        )
        return prof, wsum, ihist

    return compact
