"""Least work of ``zr_accum_pallas``: Marshall-Palmer Z-R, integrated.

Arguments: ``dbz`` (T, A, R) float32 and ``dt_s`` (T,).  Per gate: a
clip (2), two divides, two powers (one exp; one log and one exp), the
finite and threshold tests, a select, the weight multiply and the
accumulating add: 12 operations.  Bytes: every gate and weight read
once, the (A, R) accumulation written once.
"""

PROGRAM = "zr_accum_pallas"


def cost(shapes, kwargs):
    """-> (operations, bytes) of one call."""
    (t, a, r), _dt = shapes[0], shapes[1]
    return 12 * t * a * r, 4 * (t * a * r + t + a * r)
