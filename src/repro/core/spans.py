"""Host spans in the JAX profiler's own trace.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation``: inside a
profiler session it records a host event named ``name`` with ``stats``
attached, on the clock of the device planes; outside one it records
nothing and costs about a microsecond.  There is no switch to turn spans
off.

``wait(what, counts)`` wraps a place where the host blocks on the device:
it adds the block's seconds to ``counts["host_blocked_s"]`` and records a
``serving.wait`` span with stat ``what``, so the counter and the span
measure the same waits at the same sites.

Every garbage collection is recorded as a ``serving.gc`` span (stat
``generation``) through one ``gc.callbacks`` hook, installed once per
process on import.
"""
from __future__ import annotations

import gc
import time

from jax.profiler import TraceAnnotation

BLOCKED = "host_blocked_s"


def span(name: str, **stats) -> TraceAnnotation:
    return TraceAnnotation(name, **stats)


class wait:
    """Time a host block into ``counts[BLOCKED]`` under a
    ``serving.wait`` span."""

    __slots__ = ("_counts", "_span", "_t0")

    def __init__(self, what: str, counts: dict):
        self._counts = counts
        self._span = TraceAnnotation("serving.wait", what=what)

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._counts[BLOCKED] += time.perf_counter() - self._t0
        return self._span.__exit__(*exc)


_gc_span = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if phase == "start":
        _gc_span = TraceAnnotation("serving.gc",
                                   generation=info["generation"])
        _gc_span.__enter__()
    elif _gc_span is not None:
        _gc_span.__exit__(None, None, None)
        _gc_span = None


_on_gc.serving_gc = True
if not any(getattr(cb, "serving_gc", False) for cb in gc.callbacks):
    gc.callbacks.append(_on_gc)
