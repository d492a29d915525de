"""Spans and counters along the archive's request path.

Every layer a product request crosses times itself here:

* :func:`span` is a context manager.  It adds ``(1, seconds, nbytes)``
  to a process-wide table under its name and, while a profiler session
  runs, puts a ``repro.<name>`` event on the profiler's host plane, on
  the same clock as the device's operations.  Spans on one thread nest.
* :func:`record` adds to the table without a span, for work that starts
  on one thread and ends on another (a request waiting for a worker).
* :func:`annotate` adds arguments to the innermost span open on this
  thread once they are known (the route of a request being handled).
* :func:`snapshot` reads the table: ``{name: {"n", "s", "bytes"}}``,
  or only what was recorded while a profiler session ran, which lines
  up with the trace.

The table is always on and has no settings; the trace is on only while a
profiler session runs.  This module imports nothing outside the standard
library: it traces through ``jax.profiler`` once the process has loaded
it (no session can run before), so the store neither needs JAX nor pays
for importing it, and without JAX the table still counts.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Any, Dict, List

__all__ = ["annotate", "record", "snapshot", "span"]

PREFIX = "repro."

_lock = threading.Lock()
_table: Dict[str, List[Any]] = {}
_open = threading.local()


def _annotation() -> Any:
    """``jax.profiler.TraceAnnotation`` once the process has loaded JAX's
    profiler (no session can run before), else None."""
    return getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)


def record(name: str, seconds: float, nbytes: int = 0) -> None:
    """Add one occurrence of ``name`` taking ``seconds`` to the table."""
    annotation = _annotation()
    traced = annotation is not None and annotation.is_enabled()
    with _lock:
        row = _table.get(name)
        if row is None:
            row = _table[name] = [0, 0.0, 0, 0, 0.0, 0]
        row[0] += 1
        row[1] += seconds
        row[2] += nbytes
        if traced:
            row[3] += 1
            row[4] += seconds
            row[5] += nbytes


def snapshot(traced: bool = False) -> Dict[str, Dict[str, Any]]:
    """Every name's count, summed seconds and summed bytes so far.

    With ``traced``, only what was recorded while a profiler session ran
    (summed over sessions), so that the numbers line up with a trace."""
    k = 3 if traced else 0
    with _lock:
        return {name: {"n": row[k], "s": row[k + 1], "bytes": row[k + 2]}
                for name, row in _table.items() if row[k]}


def annotate(**args: Any) -> None:
    """Attach ``args`` to the innermost span open on this thread.

    A no-op when none is open, or when no profiler session runs."""
    top = getattr(_open, "span", None)
    if top is not None and top._trace is not None:
        top._trace.set_metadata(**args)


class span:
    """A timed block: ``with span(name, nbytes=0, **args) as s:``.

    It adds to the table under ``name`` and, while a profiler session
    runs, is traced as ``repro.<name>`` carrying ``args``.  Bytes known
    only inside the block (a GET's payload) are set there as
    ``s.nbytes``."""

    __slots__ = ("_name", "nbytes", "_trace", "_outer", "_t0")

    def __init__(self, name: str, nbytes: int = 0, **args: Any) -> None:
        self._name = name
        self.nbytes = int(nbytes)
        annotation = _annotation()
        self._trace = (None if annotation is None
                       else annotation(PREFIX + name, **args))

    def __enter__(self) -> "span":
        self._outer = getattr(_open, "span", None)
        _open.span = self
        if self._trace is not None:
            self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        seconds = time.perf_counter() - self._t0
        if self._trace is not None:
            self._trace.__exit__(*exc)
        _open.span = self._outer
        record(self._name, seconds, self.nbytes)
