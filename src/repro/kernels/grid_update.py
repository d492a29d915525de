"""Pallas TPU kernel: scatter-update of incremental gridded products.

When a live feed appends one scan, the cached gate->cell maps localize
which Cartesian cells the new sweep touches; the incremental product
machinery (:mod:`repro.radar.incremental`) computes fresh values for
exactly those cells as a compact ``(time, touched)`` block and patches
them into the full ``(time, cells)`` state instead of a full regrid.

TPU has no efficient scatter, so the patch is phrased as its inverse
gather: each output cell reads its update column through a precomputed
``pos`` map (``-1`` marks untouched cells, which pass their state
through bitwise).  The gather runs in XLA, outside the kernel (one
``jnp.take`` over the update axis, which may be far larger than vector
memory); the kernel does the combine — `set`/`add`/NaN-aware `max` —
on aligned ``(bt, bc)`` tiles of state, gathered values and ``pos``,
mirroring :func:`repro.kernels.ref.grid_update` operation-for-operation
so interpret mode matches the oracle bitwise.

Grid: ``(cdiv(T, bt), cdiv(C, bc))`` with tiles from
:func:`repro.kernels._tiling.tile` under one VMEM budget: cells take the
whole axis or a multiple of 128, time the whole axis or a multiple of 8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tiling import LANE, SUBLANE, VMEM_BUDGET, round_up, tile

_OPS = ("set", "add", "max")


def _grid_update_kernel(state_ref, vals_ref, pos_ref, out_ref, *, op):
    s = state_ref[...]                      # (bt, bc) float32
    vals = vals_ref[...]                    # (bt, bc) gathered updates
    touched = pos_ref[...] >= 0             # (1, bc)
    if op == "set":
        new = vals
    elif op == "add":
        new = s + vals
    else:
        new = jnp.fmax(s, vals)
    out_ref[...] = jnp.where(touched, new, s)


@functools.partial(jax.jit,
                   static_argnames=("op", "vmem_budget", "interpret"))
def grid_update_pallas(
    state: jax.Array,                      # (T, C) float32 product state
    upd: jax.Array,                        # (T, M) float32 update block
    pos: jax.Array,                        # (C,) int32 into [0, M), -1 = keep
    *,
    op: str = "set",
    vmem_budget: int = VMEM_BUDGET,
    interpret: bool = False,
) -> jax.Array:
    """Gather in XLA, then the Pallas combine patching touched cells."""
    if op not in _OPS:
        raise ValueError(f"unknown grid_update op {op!r} (set|add|max)")
    T, C = state.shape
    M = upd.shape[1]
    if T == 0 or C == 0 or M == 0:
        # nothing to patch (or nothing to patch into): the state is the
        # answer, same as the oracle, without tiling a zero-extent grid
        return state.astype(jnp.float32)
    pos = pos.astype(jnp.int32)
    vals = jnp.take(upd.astype(jnp.float32), jnp.where(pos >= 0, pos, 0),
                    axis=1)                                  # (T, C)
    # bytes per cell at <= 8 time rows (state, values, output and pos
    # sublane tiles, double-buffered), then per time row at bc cells
    bc = tile(C, LANE, 2 * 4 * SUBLANE * 4, vmem_budget)
    lanes = round_up(bc, LANE)
    bt = tile(T, SUBLANE, 2 * 3 * lanes * 4,
              vmem_budget - 2 * SUBLANE * lanes * 4)
    return pl.pallas_call(
        functools.partial(_grid_update_kernel, op=op),
        out_shape=jax.ShapeDtypeStruct((T, C), jnp.float32),
        grid=(pl.cdiv(T, bt), pl.cdiv(C, bc)),
        in_specs=[
            pl.BlockSpec((bt, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bt, bc), lambda i, j: (i, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bc), lambda i, j: (i, j)),
        interpret=interpret,
    )(state.astype(jnp.float32), vals, pos.reshape(1, C))
