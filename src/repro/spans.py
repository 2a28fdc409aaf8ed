"""Host spans on the profiler's clock, with counters kept in memory.

``with span(counters, "engine.decode", tier=16):`` adds the block's
duration on ``time.perf_counter`` (the clock of ``Request.submitted_s``
and its siblings) to ``counters["engine.decode"] = {"count", "seconds"}``
and marks the block as a ``jax.profiler.TraceAnnotation``: while a trace
is being taken, the span shows on a host line of the same trace as the
device's operations, with ``meta`` beside its name.  With no trace
active the annotation costs about a microsecond, so spans stay on.

The counter is picked by ``name`` when the block ends, so a block that
turns out to be a rejected attempt can set ``name`` and be counted apart
(the trace keeps the name it was entered with).
"""
from __future__ import annotations

import time

import jax


class span:
    __slots__ = ("name", "_counters", "_ann", "_t0")

    def __init__(self, counters: dict, name: str, **meta):
        self.name = name
        self._counters = counters
        self._ann = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        c = self._counters.get(self.name)
        if c is None:
            c = self._counters[self.name] = {"count": 0, "seconds": 0.0}
        c["count"] += 1
        c["seconds"] += dt
        return False
