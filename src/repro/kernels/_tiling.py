"""Shape-derived tiles for the radar kernels, under one VMEM budget.

Every radar kernel streams blocks HBM->VMEM through the Pallas pipeline,
which double-buffers each operand.  Mosaic accepts a block whose last
two dimensions are multiples of (8, 128) or equal to the array's own
extent; a leading dimension is free.  VMEM holds a block in (8, 128)
tiles, so a ``(1, 1192)`` row costs ``8 x 1280`` words, not 1192.

The kernels pick their tiles with :func:`tile` against
:data:`VMEM_BUDGET`: the whole axis when it fits, else the largest
aligned tile that does.  Grids use ``pl.cdiv``, so the last block may be
ragged — Mosaic masks its out-of-bounds writes and the radar kernels
never mix values across the ragged axis, except ``zr_accum``'s time
axis, which it pads instead.

The budget stays well under the 16 MiB scoped-VMEM default of a TPU
v5e core (no kernel raises ``vmem_limit_bytes``): the rest holds the
kernel body's temporaries, which are block-sized.
"""

from __future__ import annotations

# bytes of double-buffered pipeline blocks per grid step
VMEM_BUDGET = 6 * 1024 * 1024

SUBLANE = 8
LANE = 128


def round_up(n: int, m: int) -> int:
    """``n`` rounded up to a multiple of ``m``."""
    return -(-n // m) * m


def tile(n: int, align: int, unit_bytes: int, budget: int) -> int:
    """Largest tile along an axis of extent ``n`` costing ``unit_bytes``
    per (``align``-padded) element: ``n`` itself when it fits
    ``budget``, else the largest multiple of ``align`` that does (never
    less than one ``align``)."""
    if round_up(n, align) * unit_bytes <= budget:
        return n
    return min(n, max(align, budget // unit_bytes // align * align))
