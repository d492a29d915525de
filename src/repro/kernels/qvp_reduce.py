"""Pallas TPU kernel: masked azimuthal-mean reduction for QVPs (§5.1).

The QVP hot loop reduces a (time, azimuth, range) moment block to a
(time, range) profile under a NaN + quality mask.  On TPU the natural
layout streams (bt, A, br) tiles HBM→VMEM; azimuth is reduced inside
VMEM in one pass, so every profile value sums its azimuths in the same
order as :func:`repro.kernels.ref.qvp_reduce`.

Grid: ``(cdiv(T, bt), cdiv(R, br))``.  The output is written as
``(T, 1, R)`` so its block's last two dimensions, ``(1, br)``, satisfy
Mosaic's (8, 128) rule for any ``bt``: time needs no padding.  Tiles come
from :func:`repro.kernels._tiling.tile` under one VMEM budget: the range
tile is the whole axis when one time row of both inputs fits, else a
multiple of 128; the time tile takes as many rows as then fit.  At full
VCP-212 geometry (A=720, R=1192) and the default budget that is one
scan × 256 gates per step: 2 inputs × 2 buffers × 720·256·4 B ≈ 2.9 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tiling import LANE, SUBLANE, VMEM_BUDGET, round_up, tile


def _qvp_kernel(field_ref, quality_ref, out_ref, *, quality_min: float,
                min_valid_fraction: float, n_az: int):
    f = field_ref[...]            # (bt, A, br) float32
    q = quality_ref[...]
    valid = jnp.isfinite(f) & jnp.isfinite(q) & (q >= quality_min)
    x = jnp.where(valid, f, 0.0)
    count = jnp.sum(valid.astype(jnp.float32), axis=1, keepdims=True)
    total = jnp.sum(x, axis=1, keepdims=True)                # (bt, 1, br)
    mean = total / jnp.maximum(count, 1.0)
    out_ref[...] = jnp.where(
        count >= min_valid_fraction * n_az, mean, jnp.nan
    )


@functools.partial(
    jax.jit,
    static_argnames=("quality_min", "min_valid_fraction", "vmem_budget",
                     "interpret"),
)
def qvp_reduce_pallas(
    field: jax.Array,                     # (T, A, R) float32
    quality: jax.Array,                   # (T, A, R) float32
    *,
    quality_min: float = 0.85,
    min_valid_fraction: float = 0.1,
    vmem_budget: int = VMEM_BUDGET,
    interpret: bool = False,
) -> jax.Array:
    """Pallas QVP reduction kernel (quality-masked azimuthal mean)."""
    T, A, R = field.shape
    # bytes per range gate and time row: 2 inputs x 2 buffers of A
    # azimuths, plus the double-buffered (1, br) output row
    per_gate = (2 * 2 * round_up(A, SUBLANE) + 2 * SUBLANE) * 4
    br = tile(R, LANE, per_gate, vmem_budget)
    bt = tile(T, 1, per_gate * round_up(br, LANE), vmem_budget)
    out = pl.pallas_call(
        functools.partial(
            _qvp_kernel,
            quality_min=quality_min,
            min_valid_fraction=min_valid_fraction,
            n_az=A,
        ),
        out_shape=jax.ShapeDtypeStruct((T, 1, R), jnp.float32),
        grid=(pl.cdiv(T, bt), pl.cdiv(R, br)),
        in_specs=[
            pl.BlockSpec((bt, A, br), lambda i, j: (i, 0, j)),
            pl.BlockSpec((bt, A, br), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((bt, 1, br), lambda i, j: (i, 0, j)),
        interpret=interpret,
    )(field.astype(jnp.float32), quality.astype(jnp.float32))
    return out.reshape(T, R)
