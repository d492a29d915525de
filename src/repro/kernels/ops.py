"""Jit'd public wrappers: dispatch kernels on TPU, oracles on CPU.

``mode`` semantics:
  * ``"auto"``   — Pallas kernel on TPU, pure-jnp reference elsewhere
  * ``"kernel"`` — force the Pallas kernel (interpret=True off-TPU, which
                   is how the CPU CI validates kernel semantics)
  * ``"ref"``    — force the reference implementation

:func:`to_host` is how product code calls a kernel and takes its result
back to the host, timed as the spans ``dispatch.call`` and
``dispatch.wait``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import ref
from .flash_attention import flash_attention_pallas
from .grid_map import grid_map_pallas
from .grid_update import grid_update_pallas
from .mamba2_scan import mamba2_scan_pallas
from .qvp_reduce import qvp_reduce_pallas
from .zr_accum import zr_accum_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(mode: str) -> Tuple[bool, bool]:
    """-> (use_kernel, interpret)"""
    if mode == "ref":
        return False, False
    if mode == "kernel":
        return True, not _on_tpu()
    if mode == "auto":
        return _on_tpu(), False
    raise ValueError(f"unknown mode {mode!r}")


def to_host(fn: Callable[..., Any], *args: Any, **kwargs: Any) -> np.ndarray:
    """Call the kernel wrapper ``fn`` and return its result on the host.

    The call (argument conversion, staging the host arrays, whose bytes
    it counts, for their copy to the device, and the enqueue) is the span
    ``dispatch.call``; blocking until the result is on the host (copies
    still in flight, the kernel, the copy back) is ``dispatch.wait``.
    Both carry the kernel's name."""
    kernel = fn.__name__
    host = sum(a.nbytes for a in args if isinstance(a, np.ndarray))
    with obs.span("dispatch.call", nbytes=host, kernel=kernel):
        result = fn(*args, **kwargs)
    with obs.span("dispatch.wait", kernel=kernel):
        return np.asarray(result)


def qvp_reduce(
    field: jax.Array,
    quality: Optional[jax.Array] = None,
    *,
    quality_min: float = 0.85,
    min_valid_fraction: float = 0.1,
    mode: str = "auto",
) -> jax.Array:
    """Quality-masked azimuthal QVP reduction (kernel or reference)."""
    use_kernel, interpret = _resolve(mode)
    if not use_kernel:
        return ref.qvp_reduce(field, quality, quality_min=quality_min,
                              min_valid_fraction=min_valid_fraction)
    if quality is None:
        # quality := field with an always-pass threshold keeps one kernel
        quality, quality_min = field, -jnp.inf
    return qvp_reduce_pallas(field, quality, quality_min=float(quality_min),
                             min_valid_fraction=min_valid_fraction,
                             interpret=interpret)


def grid_map(
    field: jax.Array,          # (time, gates) flattened polar block
    gate_idx: jax.Array,       # (cells, k) int32
    weights: jax.Array,        # (cells, k) float32
    *,
    mode: str = "auto",
) -> jax.Array:
    """Polar-to-grid gather-accumulate (kernel or reference)."""
    use_kernel, interpret = _resolve(mode)
    if not use_kernel:
        return ref.grid_map(field, gate_idx, weights)
    return grid_map_pallas(field, gate_idx, weights, interpret=interpret)


def grid_update(
    state: jax.Array,          # (time, cells) current product state
    upd: jax.Array,            # (time, touched) compact update block
    pos: jax.Array,            # (cells,) int32, -1 = untouched
    *,
    op: str = "set",
    mode: str = "auto",
) -> jax.Array:
    """Incremental scatter-update of a gridded product (kernel or ref)."""
    use_kernel, interpret = _resolve(mode)
    if not use_kernel:
        return ref.grid_update(state, upd, pos, op=op)
    return grid_update_pallas(state, upd, pos, op=op, interpret=interpret)


def zr_accum(
    dbz: jax.Array,
    dt_s: jax.Array,
    *,
    a: float = 200.0,
    b: float = 1.6,
    dbz_min: float = 5.0,
    dbz_max: float = 53.0,
    mode: str = "auto",
) -> jax.Array:
    """Z–R rainfall accumulation (kernel or reference)."""
    use_kernel, interpret = _resolve(mode)
    if not use_kernel:
        return ref.zr_accum(dbz, dt_s, a=a, b=b, dbz_min=dbz_min,
                            dbz_max=dbz_max)
    return zr_accum_pallas(dbz, dt_s, a=a, b=b, dbz_min=dbz_min,
                           dbz_max=dbz_max, interpret=interpret)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    mode: str = "auto",
) -> jax.Array:
    """Flash attention (kernel or reference)."""
    use_kernel, interpret = _resolve(mode)
    if not use_kernel:
        return ref.flash_attention(q, k, v, causal=causal, scale=scale)
    return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                  interpret=interpret)


def mamba2_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bmat: jax.Array,
    Cmat: jax.Array,
    *,
    h0: Optional[jax.Array] = None,
    mode: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Mamba-2 selective scan (kernel or reference)."""
    use_kernel, interpret = _resolve(mode)
    if not use_kernel or h0 is not None:
        # the kernel path assumes zero initial state (training/prefill);
        # stateful decode goes through the exact recurrence instead
        return ref.mamba2_scan(x, dt, A, Bmat, Cmat, h0=h0)
    return mamba2_scan_pallas(x, dt, A, Bmat, Cmat, interpret=interpret)
