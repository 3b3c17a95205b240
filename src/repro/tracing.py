"""Host spans on the profiler's clock.

`span(name, **stats)` is a `jax.profiler.TraceAnnotation` named
``"dxpta." + name``. With a profiler running, its event lands in the same
trace as the device ops, on the same clock, so every stretch of device idle
time can be put down to the program layer the host was in; the keyword
stats (and any `set_metadata(**stats)` on the entered span, for numbers
known only later) become the event's stats. With no profiler running a
span costs about a microsecond. Spans nest on the calling thread: the
caller's own span is a request's identity.

Layers and their spans (benchmark readers match on these names):

  extraction      extract   (core.extract.workload_for)
  service         service.query, service.reprice   (serve.dse_service)
  search driver   search, search.descend, search.bounds, search.refine
                  (core.search, core.factorized)
  kernel wrappers launch (stat ``lanes``), launch.wait  (kernels.ops)

Besides the spans, `gemm_lane_tally()` counts the lanes x GEMM rows of
every launch made inside it (`count_gemm_lanes`): a search reports its
tally as `n_gemm_lanes`.

Importing this module does not import JAX. Until something else has, no
profiler can be running, so `span` returns a shared no-op context.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import sys
import threading

PREFIX = "dxpta."


class _NullSpan:
    """What `span` gives while JAX was never imported."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        pass


_NULL = _NullSpan()
_annotation = None
_tallies = contextvars.ContextVar("dxpta_gemm_lane_tallies", default=())


def span(name: str, **stats):
    """A context manager: the profiler span ``"dxpta." + name``."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return _NULL
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(PREFIX + name, **stats)


def traced(name: str):
    """Decorator: run the whole function inside `span(name)`."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


class GemmLaneTally:
    """Lanes x GEMM rows launched inside one `gemm_lane_tally()`."""

    def __init__(self):
        self.n = 0
        self._lock = threading.Lock()

    def add(self, n: int) -> None:
        with self._lock:
            self.n += int(n)


@contextlib.contextmanager
def gemm_lane_tally():
    """Count the lanes x GEMM rows of every launch made in this context,
    and in threads started with a copy of it, until the block exits.
    Tallies nest: a launch counts in every open one."""
    tally = GemmLaneTally()
    token = _tallies.set(_tallies.get() + (tally,))
    try:
        yield tally
    finally:
        _tallies.reset(token)


def count_gemm_lanes(n: int) -> None:
    """Add a launch's lanes x GEMM rows to every open tally."""
    for tally in _tallies.get():
        tally.add(n)
