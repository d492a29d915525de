"""Instrumented synchronization layer (the ``REPRO_TSAN`` runtime).

``repro.store`` / ``repro.catalog`` / ``repro.etl`` route their
synchronization through this module instead of using :mod:`threading`
directly:

* :func:`new_lock` / :func:`new_rlock` replace ``threading.Lock()`` /
  ``threading.RLock()`` at the call sites that guard hot shared state,
* :func:`wrap_pool` wraps ``ThreadPoolExecutor`` instances so ``submit``
  / ``map`` / ``result`` carry fork/join happens-before edges,
* :func:`note_read` / :func:`note_write` annotate accesses to the hot
  mutable attributes (``Session`` caches, staged transaction state),
* :func:`atomic_read` / :func:`atomic_update` mark the object store's
  atomic primitives (put, get, compare-and-swap) as release/acquire
  pairs per key.

**Zero cost when disabled** (the default): ``new_lock`` returns a plain
``threading.Lock``, ``wrap_pool`` returns its argument, and every note is
behind a single ``rt.enabled`` attribute check.  Set ``REPRO_TSAN=1`` to
enable tracing process-wide (the test suite's sanitizer mode), or use
``rt.scoped()`` for a scoped detector (the schedule explorer and the
agreement report do this so intentionally-seeded races never leak into
the suite-wide report).

The runtime feeds two consumers: the vector-clock
:class:`~repro.analysis.dynamic.detector.RaceDetector` (always, while
enabled) and — when a :class:`~repro.analysis.dynamic.scheduler.Explorer`
is active — the cooperative scheduler, which turns every instrumentation
point into a serialization/yield point.
"""

from __future__ import annotations

import os
import sys
import threading
from concurrent.futures import Future
from contextlib import contextmanager
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

from .detector import RaceDetector

_SERIAL_LOCK = threading.Lock()
_SERIAL = 0


def _next_serial() -> int:
    global _SERIAL
    with _SERIAL_LOCK:
        _SERIAL += 1
        return _SERIAL


def _short_stack(skip: int = 2, depth: int = 4) -> Tuple[str, ...]:
    """Up to ``depth`` frames of ``file:line in fn``, cheapest possible."""
    frames: List[str] = []
    try:
        f = sys._getframe(skip)
    except ValueError:
        return ()
    while f is not None and len(frames) < depth:
        code = f.f_code
        name = os.path.basename(code.co_filename)
        if name not in ("runtime.py", "scheduler.py", "detector.py"):
            frames.append(f"{name}:{f.f_lineno} in {code.co_name}")
        f = f.f_back
    return tuple(frames)


class Runtime:
    """Process-global tracing state.  One instance, ``rt``, module-level."""

    def __init__(self) -> None:
        self.enabled = False
        self.detector = RaceDetector()
        self.scheduler = None  # set by scheduler.Explorer while exploring
        self._scope_stack: List[Tuple[bool, RaceDetector, Any]] = []

    # -- enable / disable / scoping -------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def scoped(self) -> "_Scope":
        """Context manager: fresh detector (and clean scheduler slot),
        tracing force-enabled inside, everything restored on exit.
        Returns the scope object; its ``detector`` holds what was seen."""
        return _Scope(self)

    # -- race reporting --------------------------------------------------
    def races(self):
        return list(self.detector.races)

    def report_doc(self) -> Dict[str, Any]:
        return self.detector.report_doc()

    def write_report(self, path) -> None:
        import json

        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.report_doc(), f, indent=2, sort_keys=True)
            f.write("\n")


class _Scope:
    def __init__(self, rt_: Runtime) -> None:
        self.rt = rt_
        self.detector: Optional[RaceDetector] = None

    def __enter__(self) -> "_Scope":
        rt_ = self.rt
        rt_._scope_stack.append((rt_.enabled, rt_.detector, rt_.scheduler))
        rt_.detector = RaceDetector()
        rt_.scheduler = None
        rt_.enabled = True
        self.detector = rt_.detector
        return self

    def __exit__(self, *exc) -> None:
        rt_ = self.rt
        rt_.enabled, rt_.detector, rt_.scheduler = rt_._scope_stack.pop()


rt = Runtime()


# -- traced locks -----------------------------------------------------------

class TracedLock:
    """Traced drop-in replacement for ``threading.Lock``.

    Reports acquire/release to the
    detector and, under an active schedule explorer, becomes a
    *cooperative* lock (manual owner state, scheduler-arbitrated) so the
    explorer fully controls interleaving."""

    _reentrant = False

    def __init__(self, name: str) -> None:
        self.name = name
        self._lock = threading.RLock() if self._reentrant else threading.Lock()
        # cooperative state (only consulted while a scheduler is active)
        self._coop_owner: Optional[int] = None
        self._coop_depth = 0

    def _sched(self):
        sch = rt.scheduler
        if sch is not None and sch.manages_current():
            return sch
        return None

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        sch = self._sched()
        if sch is not None:
            return sch.coop_acquire(self, blocking)
        ok = self._lock.acquire(blocking, timeout)
        if ok and rt.enabled:
            rt.detector.on_acquire(self.name)
        return ok

    def release(self) -> None:
        sch = self._sched()
        if sch is not None:
            sch.coop_release(self)
            return
        if rt.enabled:
            rt.detector.on_release(self.name)
        self._lock.release()

    def locked(self) -> bool:
        if rt.scheduler is not None and self._coop_owner is not None:
            return True
        return self._lock.locked()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()

    def __repr__(self) -> str:
        return f"<TracedLock {self.name!r}>"


class TracedRLock(TracedLock):
    """Reentrant variant of :class:`TracedLock`."""
    _reentrant = True

    def __init__(self, name: str) -> None:
        super().__init__(name)
        self._local = threading.local()

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        sch = self._sched()
        if sch is not None:
            return sch.coop_acquire(self, blocking)
        ok = self._lock.acquire(blocking, timeout)
        if ok and rt.enabled:
            depth = getattr(self._local, "depth", 0)
            self._local.depth = depth + 1
            if depth == 0:  # outermost acquisition only
                rt.detector.on_acquire(self.name)
        return ok

    def release(self) -> None:
        sch = self._sched()
        if sch is not None:
            sch.coop_release(self)
            return
        if rt.enabled:
            depth = getattr(self._local, "depth", 1) - 1
            self._local.depth = depth
            if depth == 0:
                rt.detector.on_release(self.name)
        self._lock.release()


def new_lock(name: str):
    """Lock factory for a named guard.

    A mutex for ``name`` — plain ``threading.Lock`` when tracing is
    off (zero cost), a :class:`TracedLock` when on.  The name should be
    the guard's identity as the static ``lock-discipline`` pass sees it,
    e.g. ``"Session._cache_lock"`` — the agreement report joins on it."""
    if not rt.enabled:
        return threading.Lock()
    return TracedLock(name)


def new_rlock(name: str):
    """Reentrant counterpart of :func:`new_lock`."""
    if not rt.enabled:
        return threading.RLock()
    return TracedRLock(name)


# -- traced pools -----------------------------------------------------------

class TracedFuture(Future):
    """Future subclass applying the task-end -> ``result()`` join edge.

    A real ``concurrent.futures.Future``, so ``as_completed`` / ``wait``
    keep working."""

    def __init__(self) -> None:
        super().__init__()
        self._tsan_end = None  # end-of-task clock packet

    def _tsan_join(self) -> None:
        pkt = self._tsan_end
        if pkt is not None and rt.enabled:
            rt.detector.join(pkt)

    def _tsan_wait(self, fn, timeout):
        sch = rt.scheduler
        if sch is not None and sch.manages_current():
            with sch.external("future.result"):
                return fn(timeout)
        return fn(timeout)

    def result(self, timeout: Optional[float] = None):
        try:
            return self._tsan_wait(super().result, timeout)
        finally:
            self._tsan_join()

    def exception(self, timeout: Optional[float] = None):
        try:
            return self._tsan_wait(super().exception, timeout)
        finally:
            self._tsan_join()


class TracedPool:
    """Executor wrapper adding fork/join edges.

    Under an active explorer it also registers the worker threads with
    the scheduler."""

    def __init__(self, inner) -> None:
        self._inner = inner

    def submit(self, fn, *args, **kwargs) -> Future:
        if not rt.enabled:
            return self._inner.submit(fn, *args, **kwargs)
        packet = rt.detector.fork()
        tf = TracedFuture()

        def task():
            sch = rt.scheduler
            managed = sch is not None and sch.task_enter()
            try:
                rt.detector.join(packet)
                return fn(*args, **kwargs)
            finally:
                tf._tsan_end = rt.detector.fork()
                if managed:
                    sch.task_leave()

        inner_f = self._inner.submit(task)

        def done(f):
            if f.cancelled():
                tf.cancel()
                return
            exc = f.exception()
            if exc is not None:
                tf.set_exception(exc)
            else:
                tf.set_result(f.result())

        inner_f.add_done_callback(done)
        return tf

    def map(self, fn, *iterables, timeout: Optional[float] = None,
            chunksize: int = 1) -> Iterable:
        if not rt.enabled:
            return self._inner.map(fn, *iterables, timeout=timeout,
                                   chunksize=chunksize)
        futures = [self.submit(fn, *args) for args in zip(*iterables)]

        def results():
            for f in futures:
                yield f.result(timeout)

        return results()

    def shutdown(self, wait: bool = True, **kwargs) -> None:
        sch = rt.scheduler
        if wait and sch is not None and sch.manages_current():
            with sch.external("pool.shutdown"):
                self._inner.shutdown(wait=wait, **kwargs)
            return
        self._inner.shutdown(wait=wait, **kwargs)

    def __enter__(self) -> "TracedPool":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(wait=True)

    def __getattr__(self, name: str):
        return getattr(self._inner, name)


def wrap_pool(pool):
    """Route an executor's ``submit``/``map`` through the tracing layer.

    Returns ``pool`` untouched when tracing is off."""
    if not rt.enabled or isinstance(pool, TracedPool):
        return pool
    return TracedPool(pool)


@contextmanager
def external_wait(desc: str) -> Iterator[None]:
    """Mark a wait for another thread that the explorer cannot arbitrate.

    While it waits (for an event a pool task sets), a managed thread
    hands control away.  A no-op when no explorer runs."""
    sch = rt.scheduler if rt.enabled else None
    if sch is None:
        yield
        return
    with sch.external(desc):
        yield


# -- access notes -----------------------------------------------------------

def _obj_loc(obj, attr: str) -> str:
    serial = getattr(obj, "_tsan_serial", None)
    if serial is None:
        serial = _next_serial()
        try:
            object.__setattr__(obj, "_tsan_serial", serial)
        except (AttributeError, TypeError):
            serial = id(obj)
    return f"{type(obj).__name__}#{serial}.{attr}"


def note_read(obj, attr: str, owner: str = "") -> None:
    """Record a read of shared state ``obj.attr``.

    ``owner`` is the
    class-level aggregation key the agreement report joins on, e.g.
    ``"Session"`` — pass the class that *defines* the attribute (a
    ``Transaction`` is still ``"Session"`` for ``_chunk_cache``)."""
    if not rt.enabled:
        return
    sch = rt.scheduler
    if sch is not None:
        sch.yield_point(f"read {attr}")
    rt.detector.on_access(
        _obj_loc(obj, attr), write=False, stack=_short_stack(),
        owner=f"{owner}.{attr}" if owner else "",
    )


def note_write(obj, attr: str, owner: str = "") -> None:
    """Record a write of shared state ``obj.attr``."""
    if not rt.enabled:
        return
    sch = rt.scheduler
    if sch is not None:
        sch.yield_point(f"write {attr}")
    rt.detector.on_access(
        _obj_loc(obj, attr), write=True, stack=_short_stack(),
        owner=f"{owner}.{attr}" if owner else "",
    )


# -- object-store atomic hooks ----------------------------------------------

def schedule_point(desc: str) -> None:
    """A pure scheduling decision point (no detector event).

    Placed at
    the *entry* of read-modify-write primitives so the explorer can
    interleave a competitor between a caller's read and its swap."""
    if not rt.enabled:
        return
    sch = rt.scheduler
    if sch is not None:
        sch.yield_point(desc)


def atomic_read(key: str) -> None:
    """A get (or failed CAS) of an object-store key: acquire side."""
    if not rt.enabled:
        return
    sch = rt.scheduler
    if sch is not None:
        sch.yield_point(f"store get {key}")
    rt.detector.atomic_acquire(key)


def atomic_update(key: str) -> None:
    """A put / successful CAS / delete of a key: release side."""
    if not rt.enabled:
        return
    sch = rt.scheduler
    if sch is not None:
        sch.yield_point(f"store put {key}")
    rt.detector.atomic_release(key)


# environment opt-in: REPRO_TSAN=1 enables tracing for the whole process
if os.environ.get("REPRO_TSAN") == "1":
    rt.enable()


__all__ = [
    "Runtime", "TracedFuture", "TracedLock", "TracedPool", "TracedRLock",
    "atomic_read", "atomic_update", "external_wait", "new_lock", "new_rlock",
    "note_read", "note_write", "rt", "schedule_point", "wrap_pool",
]
