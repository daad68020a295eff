"""Pallas TPU kernel: fused compress -> one-hot -> MXU-accumulate
histogram for a single metric row.

An XLA one-hot matmul would materialize the two one-hot matrices
[N, H] and [N, 128] in HBM between fusion boundaries; this kernel keeps
everything on-chip: each grid step loads one sample tile into VMEM,
compresses it on the VPU, forms the one-hots in registers/VMEM, runs the
[H, T] x [T, 128] matmul on the MXU into a float32 VMEM scratch
accumulator, and only on the last step adds the scratch into the int32
output row.  HBM traffic is exactly `N * 4` bytes in + `B * 4` bytes out —
the information-theoretic minimum for this op.

This is the hot-op kernel for the reference's headline single-metric
benchmark (readme.md:27: ~20M samples/s/process in Go; the MXU sustains
~2 samples/cycle at 8k buckets).  The multi-metric scatter path stays on
XLA (see ops/ingest.py); per-metric-tile generalization is future work.

Runs in interpret mode off-TPU so CI exercises the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from loghisto_tpu.config import PRECISION
from loghisto_tpu.ops.backend import default_interpret
from loghisto_tpu.ops.ingest import bucket_indices

LANES = 128
SAMPLE_TILE = 2048
# float32 scratch accumulation is exact only below 2^24 per cell; bound the
# whole call so no cell can saturate silently.
MAX_SAMPLES_PER_CALL = 1 << 24


def _hist_kernel(values_ref, acc_ref, out_ref, scratch_ref, *,
                 bucket_limit: int, precision: int, h: int):
    """One grid step: accumulate one sample tile into the VMEM scratch."""
    i = pl.program_id(0)
    n_steps = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        scratch_ref[:] = jnp.zeros_like(scratch_ref)

    v = values_ref[0, :]  # [T] float32
    # fused codec (VPU): shared with the scatter path so the contract
    # (sign mirroring, NaN->bucket 0, saturation) can never diverge
    bucket = bucket_indices(v, bucket_limit, precision)

    hi = bucket // LANES  # [T] in [0, h)
    lo = bucket % LANES

    # one-hots in VMEM; iota comparisons are VPU-native
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], h), 1)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], LANES), 1)
    onehot_hi = (hi[:, None] == hi_iota).astype(jnp.bfloat16)  # [T, H]
    onehot_lo = (lo[:, None] == lo_iota).astype(jnp.bfloat16)  # [T, 128]

    # [H, T] x [T, 128] on the MXU, exact f32 integer accumulation
    partial = jax.lax.dot_general(
        onehot_hi, onehot_lo,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    scratch_ref[:] += partial

    @pl.when(i == n_steps - 1)
    def _finalize():
        out_ref[:] = acc_ref[:] + scratch_ref[:].astype(jnp.int32)


def pallas_histogram_row(
    acc_row: jnp.ndarray,
    values: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Accumulate `values` into a single dense histogram row.

    acc_row: int32 [num_buckets]; values: float32 [N] with N a multiple of
    SAMPLE_TILE (for ragged N or an (ids, values) contract use
    pallas_row_ingest_batch below, whose mask drops padding and non-zero
    ids).  Returns the updated row.
    """
    if interpret is None:
        interpret = default_interpret()
    b = acc_row.shape[0]
    h = (b + LANES - 1) // LANES
    b_pad = h * LANES
    n = values.shape[0]
    if n % SAMPLE_TILE:
        raise ValueError(f"N={n} must be a multiple of {SAMPLE_TILE}")
    if n >= MAX_SAMPLES_PER_CALL:
        raise ValueError(
            f"N={n} >= 2^24: the float32 scratch would silently saturate; "
            "split the batch across calls"
        )
    g = n // SAMPLE_TILE

    acc2d = jnp.zeros((h, LANES), dtype=jnp.int32)
    acc2d = acc2d.reshape(-1).at[:b].set(acc_row).reshape(h, LANES)
    # Mosaic requires each of a block's last two dims to be 8/128-divisible
    # OR equal to the array dim — so grid the LANE axis of a [1, N] layout
    # (block [1, T]: dim -2 equals the array's 1, dim -1 is 128-divisible);
    # a [g, T] layout with block [1, T] is rejected on hardware.
    values2d = values.reshape(1, n)

    kernel = functools.partial(
        _hist_kernel, bucket_limit=bucket_limit, precision=precision, h=h
    )
    out = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, SAMPLE_TILE), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((h, LANES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((h, LANES), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((h, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((h, LANES), jnp.float32)],
        interpret=interpret,
    )(values2d, acc2d)
    return out.reshape(-1)[:b]


def make_pallas_row_ingest(
    num_buckets: int,
    bucket_limit: int,
    precision: int = PRECISION,
    interpret: bool | None = None,
):
    """Jitted single-row ingest: f(acc_row, values[N]) -> acc_row."""

    @functools.partial(jax.jit, donate_argnums=0)
    def ingest(acc_row, values):
        return pallas_histogram_row(
            acc_row, values, bucket_limit, precision, interpret=interpret
        )

    return ingest


def _hist_kernel_masked(values_ref, mask_ref, acc_ref, out_ref, scratch_ref,
                        *, bucket_limit: int, precision: int, h: int):
    """Masked variant of _hist_kernel: samples whose mask is 0 contribute
    nothing (their one-hot row is zeroed) — this is what gives the row
    kernel a drop semantics for invalid ids and arbitrary-N padding."""
    i = pl.program_id(0)
    n_steps = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        scratch_ref[:] = jnp.zeros_like(scratch_ref)

    v = values_ref[0, :]
    bucket = bucket_indices(v, bucket_limit, precision)
    # a masked sample's hi index is h, which matches no iota column, so
    # its one-hot row is zero (Mosaic cannot reshape a [T] bool vector
    # to [T, 1], so the mask is folded into the int32 index instead)
    hi = jnp.where(mask_ref[0, :] != 0, bucket // LANES, h)
    lo = bucket % LANES
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], h), 1)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], LANES), 1)
    onehot_hi = (hi[:, None] == hi_iota).astype(jnp.bfloat16)
    onehot_lo = (lo[:, None] == lo_iota).astype(jnp.bfloat16)
    partial = jax.lax.dot_general(
        onehot_hi, onehot_lo,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    scratch_ref[:] += partial

    @pl.when(i == n_steps - 1)
    def _finalize():
        out_ref[:] = acc_ref[:] + scratch_ref[:].astype(jnp.int32)


def pallas_row_ingest_batch(
    acc: jnp.ndarray,
    ids: jnp.ndarray,
    values: jnp.ndarray,
    bucket_limit: int,
    precision: int = PRECISION,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Uniform-contract form of the row kernel: acc int32 [1, B],
    f(acc, ids, values) -> acc, bit-identical to the scatter path for a
    single-metric accumulator (samples with ids != 0 are dropped via the
    mask; ragged N is padded with masked-out samples).  This is what
    lets ``ingest_path="auto"``/"pallas" reach the measured-fastest M=1
    kernel through the same dispatch table as every other path."""
    if interpret is None:
        interpret = default_interpret()
    if acc.ndim != 2 or acc.shape[0] != 1:
        raise ValueError(
            f"pallas row path needs a single-metric [1, B] accumulator; "
            f"got shape {tuple(acc.shape)}"
        )
    b = acc.shape[1]
    h = (b + LANES - 1) // LANES
    n = values.shape[0]
    mask = (ids == 0).astype(jnp.int32)
    pad = (-n) % SAMPLE_TILE
    if n + pad >= MAX_SAMPLES_PER_CALL:
        raise ValueError(
            f"N={n} >= 2^24: the float32 scratch would silently saturate; "
            "split the batch across calls"
        )
    if pad:
        values = jnp.concatenate(
            [values, jnp.zeros(pad, values.dtype)]
        )
        mask = jnp.concatenate([mask, jnp.zeros(pad, mask.dtype)])
    g = (n + pad) // SAMPLE_TILE

    acc2d = jnp.zeros((h, LANES), dtype=jnp.int32)
    acc2d = acc2d.reshape(-1).at[:b].set(acc[0]).reshape(h, LANES)
    kernel = functools.partial(
        _hist_kernel_masked, bucket_limit=bucket_limit,
        precision=precision, h=h,
    )
    out = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, SAMPLE_TILE), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, SAMPLE_TILE), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((h, LANES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((h, LANES), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((h, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((h, LANES), jnp.float32)],
        interpret=interpret,
    )(values.reshape(1, -1), mask.reshape(1, -1), acc2d)
    return out.reshape(-1)[:b][None, :]
