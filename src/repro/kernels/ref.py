"""Pure-jnp oracles for every Pallas kernel.

These are the semantics of record: each kernel in this package must match
its oracle under ``interpret=True`` — the radar kernels bitwise, against
the oracle compiled by ``jax.jit`` (see ``tests/test_kernels.py``).  They
are also the CPU execution path: with ``mode="auto"`` the ops dispatch
here on a CPU backend and to the kernels on a TPU.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# qvp_reduce: masked azimuthal mean (paper §5.1)
# ---------------------------------------------------------------------------

def qvp_reduce(
    field: jax.Array,           # (time, azimuth, range)
    quality: Optional[jax.Array] = None,   # same shape, e.g. RHOHV
    *,
    quality_min: float = 0.85,
    min_valid_fraction: float = 0.1,
) -> jax.Array:
    """Azimuthal mean with NaN + quality masking -> (time, range).

    A gate contributes when it is finite and its quality metric passes
    ``quality_min``.  Rows (time, range) with fewer than
    ``min_valid_fraction`` valid azimuths are NaN (Ryzhkov et al. 2016).
    """
    valid = jnp.isfinite(field)
    if quality is not None:
        valid &= jnp.isfinite(quality) & (quality >= quality_min)
    x = jnp.where(valid, field, 0.0).astype(jnp.float32)
    count = jnp.sum(valid, axis=1).astype(jnp.float32)
    total = jnp.sum(x, axis=1)
    n_az = field.shape[1]
    mean = total / jnp.maximum(count, 1.0)
    return jnp.where(count >= min_valid_fraction * n_az, mean, jnp.nan)


# ---------------------------------------------------------------------------
# grid_map: polar -> Cartesian gather-regrid (repro.radar.grid)
# ---------------------------------------------------------------------------

def grid_map(
    field: jax.Array,           # (time, gates) — flattened (az, range) axis
    gate_idx: jax.Array,        # (cells, k) int32 flat gate indices
    weights: jax.Array,         # (cells, k) float32, <= 0 means "no gate"
) -> jax.Array:
    """Masked weighted gather: polar gates -> Cartesian cells, (time, cells).

    Each output cell is the weight-normalized mean of its (at most) k
    contributing gates, skipping non-finite gate values and non-positive
    weights; a cell with no valid contribution is NaN (outside the radar's
    reach, or every neighbour missing).  ``weights`` of exactly 1 with
    ``k == 1`` is nearest-neighbour; inverse-distance weights give IDW.
    The (cells, k) map is precomputed once per (site geometry, grid) by
    :class:`repro.radar.grid.GridMapping` and reused across scans.
    """
    f = field.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    vals = jnp.take(f, gate_idx.reshape(-1).astype(jnp.int32),
                    axis=1).reshape(f.shape[0], *gate_idx.shape)
    valid = jnp.isfinite(vals) & (w > 0.0)[None, :, :]
    wv = jnp.where(valid, w[None, :, :], 0.0)
    num = jnp.sum(jnp.where(valid, vals, 0.0) * wv, axis=-1)
    den = jnp.sum(wv, axis=-1)
    return jnp.where(den > 0.0, num / jnp.maximum(den, 1e-12), jnp.nan)


# ---------------------------------------------------------------------------
# zr_accum: Marshall–Palmer Z–R + time integration (paper §5.3)
# ---------------------------------------------------------------------------

def zr_accum(
    dbz: jax.Array,             # (time, azimuth, range)
    dt_s: jax.Array,            # (time,) integration weight per scan, seconds
    *,
    a: float = 200.0,
    b: float = 1.6,
    dbz_min: float = 5.0,
    dbz_max: float = 53.0,      # hail cap, standard practice
) -> jax.Array:
    """Accumulated precipitation in mm -> (azimuth, range).

    R = (10^(dBZ/10) / a)^(1/b)  [mm/h];  accum = sum_t R_t * dt_t / 3600.
    """
    dbz_c = jnp.clip(dbz, dbz_min, dbz_max)
    z_lin = jnp.power(10.0, dbz_c / 10.0)
    rate = jnp.power(z_lin / a, 1.0 / b)                    # mm/h
    rate = jnp.where(jnp.isfinite(dbz) & (dbz >= dbz_min), rate, 0.0)
    w = (dt_s / 3600.0).astype(jnp.float32)[:, None, None]
    return jnp.sum(rate * w, axis=0).astype(jnp.float32)


# ---------------------------------------------------------------------------
# flash_attention: causal/full GQA attention
# ---------------------------------------------------------------------------

def flash_attention(
    q: jax.Array,               # (B, Hq, Sq, D)
    k: jax.Array,               # (B, Hkv, Skv, D)
    v: jax.Array,               # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference attention with GQA head grouping.

    For decode (Sq < Skv) the query block is aligned to the *end* of the
    key sequence, i.e. query i attends to keys <= Skv - Sq + i.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)
    ) * scale
    if causal:
        q_pos = jnp.arange(Sq)[:, None] + (Skv - Sq)
        k_pos = jnp.arange(Skv)[None, :]
        logits = jnp.where(q_pos >= k_pos, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vv.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# mamba2_scan: SSD selective-state-space recurrence
# ---------------------------------------------------------------------------

def mamba2_scan(
    x: jax.Array,               # (B, L, H, P)
    dt: jax.Array,              # (B, L, H)   positive (already softplus'd)
    A: jax.Array,               # (H,)        negative
    Bmat: jax.Array,            # (B, L, N)   input projection (ngroups=1)
    Cmat: jax.Array,            # (B, L, N)   output projection
    *,
    h0: Optional[jax.Array] = None,   # (B, H, P, N) initial state
) -> Tuple[jax.Array, jax.Array]:
    """Sequential oracle for the Mamba2/SSD recurrence.

        h_t = exp(A * dt_t) * h_{t-1} + dt_t * x_t  B_t^T
        y_t = h_t C_t + 0  (skip connection handled by the caller)

    Returns (y  (B, L, H, P), final state (B, H, P, N)).
    """
    Bsz, L, H, P = x.shape
    N = Bmat.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), dtype=jnp.float32)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp           # (B,H,P) (B,H) (B,N) (B,N)
        decay = jnp.exp(A[None, :] * dt_t)  # (B,H)
        upd = (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        h = h * decay[..., None, None] + upd
        y_t = jnp.einsum("bhpn,bn->bhp", h, c_t)
        return h, y_t

    xs = (
        jnp.moveaxis(x, 1, 0).astype(jnp.float32),
        jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
        jnp.moveaxis(Bmat, 1, 0).astype(jnp.float32),
        jnp.moveaxis(Cmat, 1, 0).astype(jnp.float32),
    )
    h_final, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 1)              # (B, L, H, P)
    return y.astype(x.dtype), h_final


# ---------------------------------------------------------------------------
# grid_update: incremental scatter-update of gridded product state
# ---------------------------------------------------------------------------

def grid_update(
    state: jax.Array,           # (time, cells) current product state
    upd: jax.Array,             # (time, touched) freshly computed values
    pos: jax.Array,             # (cells,) int32: index into upd, -1 = keep
    *,
    op: str = "set",
) -> jax.Array:
    """Patch only the touched cells of a gridded product, (time, cells).

    The incremental-product primitive: ``pos`` maps every grid cell to
    its column in the compact update block (``-1`` for cells the new
    data does not reach, which keep their state bitwise).  ``op`` is how
    a touched cell combines with its update: ``"set"`` replaces,
    ``"add"`` accumulates (QPE), ``"max"`` is the NaN-aware composite
    maximum (column-max / mosaic).  With ``upd`` empty along cells the
    state is returned unchanged.
    """
    if op not in ("set", "add", "max"):
        raise ValueError(f"unknown grid_update op {op!r} (set|add|max)")
    s = state.astype(jnp.float32)
    if upd.shape[1] == 0 or s.shape[0] == 0 or s.shape[1] == 0:
        return s
    u = upd.astype(jnp.float32)
    p = pos.astype(jnp.int32)
    touched = p >= 0
    safe = jnp.where(touched, p, 0)
    vals = jnp.take(u, safe, axis=1)        # (time, cells)
    if op == "set":
        new = vals
    elif op == "add":
        new = s + vals
    else:
        new = jnp.fmax(s, vals)
    return jnp.where(touched[None, :], new, s)
