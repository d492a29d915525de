"""The program's own span table (``repro.obs``), as the per-layer
readers see it: the window's part.

Per-layer metrics are read in traced runs, and the profiler session
spans the window and nothing else, so the part of the table recorded
while a session ran (``obs.snapshot(traced=True)``) is the window's: it
leaves out the archive build and the warm-up.  A harness that hands
readers the window's part itself (``ctx.spans``) is read instead.  A
program without ``repro.obs`` has no table, and every reader then
returns None.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def table(ctx) -> Dict[str, Dict[str, Any]]:
    spans = getattr(ctx, "spans", None)
    if spans is not None:
        return spans
    try:
        from repro import obs
    except ImportError:
        return {}
    return obs.snapshot(traced=True)


def ms_per_product(ctx, name: str) -> Optional[float]:
    """Milliseconds of the span ``name`` per product computed in the
    window; None without products or without the span."""
    row = table(ctx).get(name)
    if row is None or not ctx.computed:
        return None
    return 1e3 * row["s"] / ctx.computed
