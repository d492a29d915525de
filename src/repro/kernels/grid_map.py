"""Pallas TPU kernel: masked weighted regrid for polar->Cartesian gridding.

The gridding hot loop turns a (time, azimuth, range) moment block into a
(time, cells) Cartesian product through a precomputed gate map: for each
output cell, at most ``k`` contributing gates (flat indices into the
flattened gate axis) with their weights (``repro.radar.grid.GridMapping``
builds the map once per site geometry x grid and caches it).

Split: the gather runs in XLA, outside the kernel — one ``jnp.take`` over
the flat gate axis, neighbour-major, so the gathered block is
``(k, T, C)`` with cells on the lanes.  TPU vector memory cannot hold an
arbitrary gate axis (a 14-cut full-geometry CAPPI stack is 48 MB per
scan) and Mosaic has no general in-kernel gather, while XLA's gather
reads HBM directly.  The kernel then does the masked weighted mean over
the ``k`` neighbours on aligned ``(k, bt, bc)`` tiles: a reduction over
the leading axis, i.e. plain vector adds.  A NaN gate drops out of its
cell's mean instead of poisoning it, and the per-cell math mirrors
:func:`repro.kernels.ref.grid_map` operation-for-operation so interpret
mode matches the oracle bitwise.

Grid: ``(cdiv(T, bt), cdiv(C, bc))`` with tiles from
:func:`repro.kernels._tiling.tile` under one VMEM budget: cells take the
whole axis or a multiple of 128, time the whole axis or a multiple of 8.
The weights travel as ``(k, 1, C)`` so their block tail ``(1, bc)`` is
legal for any ``bc``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tiling import LANE, SUBLANE, VMEM_BUDGET, round_up, tile


def _grid_map_kernel(vals_ref, w_ref, out_ref):
    vals = vals_ref[...]                    # (k, bt, bc) gathered gates
    w = w_ref[...]                          # (k, 1, bc) float32
    valid = jnp.isfinite(vals) & (w > 0.0)
    wv = jnp.where(valid, w, 0.0)
    num = jnp.sum(jnp.where(valid, vals, 0.0) * wv, axis=0)
    den = jnp.sum(wv, axis=0)
    out_ref[...] = jnp.where(den > 0.0, num / jnp.maximum(den, 1e-12),
                             jnp.nan)


@functools.partial(jax.jit, static_argnames=("vmem_budget", "interpret"))
def grid_map_pallas(
    field: jax.Array,                      # (T, G) float32, G = az*range
    gate_idx: jax.Array,                   # (C, k) int32 into [0, G)
    weights: jax.Array,                    # (C, k) float32, <= 0 = no gate
    *,
    vmem_budget: int = VMEM_BUDGET,
    interpret: bool = False,
) -> jax.Array:
    """Gather in XLA, then the Pallas masked weighted mean per cell."""
    T, _G = field.shape
    C, k = gate_idx.shape
    if T == 0 or C == 0:
        # degenerate axes (an empty planner window): same answer as the
        # oracle, without tiling a zero-extent grid
        return jnp.full((T, C), jnp.nan, jnp.float32)
    vals = jnp.take(field.astype(jnp.float32),
                    gate_idx.T.reshape(-1).astype(jnp.int32), axis=1)
    vals = vals.reshape(T, k, C).transpose(1, 0, 2)          # (k, T, C)
    w = weights.astype(jnp.float32).T.reshape(k, 1, C)
    # bytes per cell at <= 8 time rows (k gathered + k weight + 1 output
    # sublane tiles, double-buffered), then per time row at bc cells
    bc = tile(C, LANE, 2 * (2 * k + 1) * SUBLANE * 4, vmem_budget)
    lanes = round_up(bc, LANE)
    bt = tile(T, SUBLANE, 2 * (k + 1) * lanes * 4,
              vmem_budget - 2 * k * SUBLANE * lanes * 4)
    return pl.pallas_call(
        _grid_map_kernel,
        out_shape=jax.ShapeDtypeStruct((T, C), jnp.float32),
        grid=(pl.cdiv(T, bt), pl.cdiv(C, bc)),
        in_specs=[
            pl.BlockSpec((k, bt, bc), lambda i, j: (0, i, j)),
            pl.BlockSpec((k, 1, bc), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bc), lambda i, j: (i, j)),
        interpret=interpret,
    )(vals, w)
