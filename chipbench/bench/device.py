"""The device a run measures, and the compiles it counts.

A measured run needs a TPU: anything else is an error, never a fallback.
Only the harness's explicit rehearsal option accepts the CPU, and a
rehearsal prints no metric.
"""

from __future__ import annotations

import time
from typing import List


class NoChip(Exception):
    """The run found no TPU, or fewer chips than its cell asks for."""


def check(chips: int, rehearse: bool = False) -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX sees."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not (rehearse and platform == "cpu"):
        raise NoChip(f"found platform {platform!r} with {len(devs)} "
                     f"device(s); a measured run needs a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} devices, found {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(n: int) -> int:
    """Peak bytes in use on the fullest of the first ``n`` devices (0
    where the backend keeps no such statistic, as the CPU does)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n]]
    return int(max(peaks))


class CompileCounter:
    """Times of every program compiled, or loaded from the persistent
    cache, in this process (JAX's monitoring events)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == self.COMPILE:
            self.times.append(time.perf_counter())

    def _event(self, event, **kwargs):
        if event == self.CACHE_HIT:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)
