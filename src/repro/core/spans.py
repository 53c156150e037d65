"""Profiler spans at the program's layer boundaries.

``with span("evaluate"):`` opens a ``jax.profiler.TraceAnnotation``
named ``fifo.evaluate``, so a profiler trace shows what the host was
doing on the same clock as the device's ops.  It does so only when jax
is already imported: the numpy-only worker processes never import jax
because of a span.  Given a stats object and one of its fields, the span
also adds its ``perf_counter`` duration to that field.

Spans cost a few microseconds whether or not a trace is being recorded;
they are on in every run and open once per call, never per row.
"""

from __future__ import annotations

import sys
import time

PREFIX = "fifo."


class span:
    """Context manager: the ``fifo.<name>`` span, timed into
    ``stats.<field>`` when ``stats`` is given."""

    __slots__ = ("name", "stats", "field", "_ann", "_t0")

    def __init__(self, name: str, stats=None, field: str = None):
        self.name, self.stats, self.field = name, stats, field
        self._ann = None

    def __enter__(self):
        jax = sys.modules.get("jax")
        if jax is not None:
            self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.stats is not None:
            setattr(self.stats, self.field, getattr(self.stats, self.field)
                    + time.perf_counter() - self._t0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        return False
