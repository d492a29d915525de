"""Pallas TPU kernel: fused Marshall–Palmer Z–R + time integration (§5.3).

QPE accumulation is elementwise transcendental work (10^x, x^(1/b)) plus a
time reduction — memory-bound on the archive read, so the kernel fuses the
unit conversion and the accumulation into a single pass over each chunk:
nothing but the final (azimuth, range) accumulation field ever leaves VMEM.

Grid: ``(cdiv(A, ba), cdiv(R, br), Tp/bt)`` — the time axis is the
innermost (sequential) grid dimension, revisiting the output block, which
is the canonical TPU accumulation pattern (zero at t==0, add thereafter).
The scan weights ``dt_s`` travel as a ``(T, 1, 1)`` array, whose
``(1, 1)`` block tail equals the array's own and so passes Mosaic's
(8, 128) rule for any time tile.

Tiles come from :func:`repro.kernels._tiling.tile` under one VMEM budget.
The time tile is the whole window whenever it fits at the smallest
spatial tile — then every gate sums its scans in one ``jnp.sum``, the
order :func:`repro.kernels.ref.zr_accum` uses; a longer window is padded
to a multiple of its tile with NaN dBZ and zero weight (no rain).  Range
is then the whole axis or a multiple of 128, azimuth a multiple of 8.  At
full VCP-212 geometry (12 scans × 720 × 1192) each step holds
12 × 40 × 1192 gates: 2 buffers × 12·40·1280·4 B ≈ 4.9 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tiling import LANE, SUBLANE, VMEM_BUDGET, round_up, tile


def _zr_kernel(dbz_ref, dt_ref, out_ref, *, a: float, b: float,
               dbz_min: float, dbz_max: float):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dbz = dbz_ref[...]                      # (bt, ba, br)
    w = dt_ref[...] / 3600.0                # (bt, 1, 1)
    dbz_c = jnp.clip(dbz, dbz_min, dbz_max)
    z_lin = jnp.power(10.0, dbz_c / 10.0)
    rate = jnp.power(z_lin / a, 1.0 / b)
    rate = jnp.where(jnp.isfinite(dbz) & (dbz >= dbz_min), rate, 0.0)
    out_ref[...] += jnp.sum(rate * w, axis=0)


@functools.partial(
    jax.jit,
    static_argnames=("a", "b", "dbz_min", "dbz_max", "vmem_budget",
                     "interpret"),
)
def zr_accum_pallas(
    dbz: jax.Array,                # (T, A, R) float32
    dt_s: jax.Array,               # (T,) seconds
    *,
    a: float = 200.0,
    b: float = 1.6,
    dbz_min: float = 5.0,
    dbz_max: float = 53.0,
    vmem_budget: int = VMEM_BUDGET,
    interpret: bool = False,
) -> jax.Array:
    """Pallas Z–R accumulation kernel."""
    T, A, R = dbz.shape
    row = SUBLANE * LANE * 4        # one (8, 128) f32 tile
    # the weight block costs one tile per scan whatever the spatial tile
    budget = vmem_budget - 2 * 2 * row        # minus the smallest output
    bt = tile(T, 1, 2 * 2 * row, budget)      # dbz + dt, 2 buffers each
    Tp = round_up(T, bt)
    if Tp != T:
        dbz = jnp.pad(dbz, ((0, Tp - T), (0, 0), (0, 0)),
                      constant_values=jnp.nan)       # NaN -> rate 0
        dt_s = jnp.pad(dt_s, (0, Tp - T))            # dt 0 -> no weight
    budget = vmem_budget - 2 * bt * row       # minus the weight blocks
    # bytes per range gate (at 8 azimuths) and per azimuth row (at br
    # gates): bt dbz rows + 1 output row, double-buffered
    br = tile(R, LANE, 2 * (bt + 1) * SUBLANE * 4, budget)
    ba = tile(A, SUBLANE, 2 * (bt + 1) * round_up(br, LANE) * 4, budget)
    out = pl.pallas_call(
        functools.partial(_zr_kernel, a=a, b=b, dbz_min=dbz_min,
                          dbz_max=dbz_max),
        out_shape=jax.ShapeDtypeStruct((A, R), jnp.float32),
        grid=(pl.cdiv(A, ba), pl.cdiv(R, br), Tp // bt),
        in_specs=[
            pl.BlockSpec((bt, ba, br), lambda i, j, t: (t, i, j)),
            pl.BlockSpec((bt, 1, 1), lambda i, j, t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((ba, br), lambda i, j, t: (i, j)),
        interpret=interpret,
    )(dbz.astype(jnp.float32), dt_s.astype(jnp.float32).reshape(Tp, 1, 1))
    return out
