"""Least work of ``qvp_reduce_pallas``: the masked azimuthal mean.

Arguments: ``field`` (T, A, R) float32 and ``quality`` of the same shape.
Without a quality moment the product path passes the field again with an
always-passing threshold (``quality_min=-inf``); the work then reads the
field once.  Arithmetic: a sum and a count per gate, a divide and a test
per output value.
"""

PROGRAM = "qvp_reduce_pallas"


def cost(shapes, kwargs):
    """-> (operations, bytes) of one call."""
    (t, a, r), _quality = shapes[0], shapes[1]
    gates = t * a * r
    inputs = 1 if kwargs.get("quality_min") == float("-inf") else 2
    return 2 * gates + 2 * t * r, 4 * (inputs * gates + t * r)
