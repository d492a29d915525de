"""Harness-side spans and counters around the program's layers.

The program has no spans of its own, so the harness wraps the calls it
can reach from outside and adds nothing to the program's code:

- ``ArchiveService.product`` and ``.compute_product`` (per instance) and
  ``repro.serve.http.encode_product``: a ``TraceAnnotation`` each, and a
  timer on ``compute_product``;
- every ``repro.kernels.ops.<name>_pallas`` the product path dispatches
  to: a ``TraceAnnotation``, the argument shapes (for ``cost/``) and the
  bytes of host arrays handed to it (host-to-device copies);
- ``Catalog.open_session`` (per instance): the sessions, for their
  ``cache_stats()["chunk_fetches"]``;
- JAX's monitoring events: backend compiles and persistent-cache hits.

Recording is switched on for the measured window only; the wrappers cost
a few microseconds per call whether or not a trace is being taken.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

import jax
import numpy as np
from jax.profiler import TraceAnnotation

SPAN_PREFIX = "chipbench."


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (fired on whichever thread compiles)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.compile_s, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += duration_secs
                self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"compile_s": self.compile_s, "compiles": self.compiles,
                    "cache_hits": self.hits, "cache_misses": self.misses}


class Probes:
    """All the harness's wrappers, installed on one service and catalog."""

    def __init__(self, service, catalog) -> None:
        from repro.kernels import ops
        from repro.serve import http

        self.meter = CompileMeter()
        self.recording = False
        self._lock = threading.Lock()
        self.kernel_calls: List[Dict[str, Any]] = []
        self.h2d_bytes = 0
        self.products = 0
        self.computed = 0
        self.compute_s = 0.0
        self.sessions: List[Any] = []
        self._patched: List[Any] = []

        for name in [n for n in dir(ops) if n.endswith("_pallas")]:
            self._patch(ops, name, self._kernel(name[: -len("_pallas")],
                                                getattr(ops, name)))
        encode = http.encode_product

        def encode_product(result):
            with TraceAnnotation(SPAN_PREFIX + "encode_product"):
                return encode(result)
        self._patch(http, "encode_product", encode_product)

        product, compute = service.product, service.compute_product

        def product_(kind, params, tenant="public"):
            if self.recording:
                with self._lock:
                    self.products += 1
            with TraceAnnotation(SPAN_PREFIX + "product"):
                return product(kind, params, tenant)

        def compute_product_(kind, clean, tenant="public"):
            t0 = time.perf_counter()
            try:
                with TraceAnnotation(SPAN_PREFIX + "compute_product"):
                    return compute(kind, clean, tenant)
            finally:
                if self.recording:
                    with self._lock:
                        self.computed += 1
                        self.compute_s += time.perf_counter() - t0
        self._patch(service, "product", product_)
        self._patch(service, "compute_product", compute_product_)

        open_session = catalog.open_session

        def open_session_(*args, **kwargs):
            session = open_session(*args, **kwargs)
            with self._lock:
                self.sessions.append(session)
            return session
        self._patch(catalog, "open_session", open_session_)

    def _patch(self, obj, name: str, value) -> None:
        self._patched.append((obj, name, obj.__dict__.get(name)))
        setattr(obj, name, value)

    def close(self) -> None:
        """Put back everything :meth:`__init__` replaced."""
        for obj, name, old in reversed(self._patched):
            if old is None:
                delattr(obj, name)
            else:
                setattr(obj, name, old)
        self._patched.clear()

    def _kernel(self, name: str, fn):
        span = f"{SPAN_PREFIX}kernel.{name}"

        def wrapped(*args, **kwargs):
            if self.recording:
                host = sum(a.nbytes for a in args if isinstance(a, np.ndarray))
                call = {"kernel": name,
                        "shapes": [tuple(np.shape(a)) for a in args],
                        "kwargs": {k: v for k, v in kwargs.items()
                                   if k != "interpret"}}
                with self._lock:
                    self.h2d_bytes += host
                    self.kernel_calls.append(call)
            with TraceAnnotation(span):
                return fn(*args, **kwargs)
        wrapped.__wrapped__ = fn
        return wrapped

    def chunk_fetches(self) -> int:
        with self._lock:
            sessions = list(self.sessions)
        return sum(int(s.cache_stats()["chunk_fetches"]) for s in sessions)


__all__ = ["CompileMeter", "Probes", "SPAN_PREFIX"]
