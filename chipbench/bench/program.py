"""What the program measures itself, read for the per-layer metrics.

The program keeps counters on its own stats objects (``BatchStats`` of
the evaluator, ``HeteroStats`` of the cross-design dispatcher; their
per-window deltas reach ``run.counters``) and opens ``fifo.<layer>``
profiler spans at its layer boundaries.  The readers here return None
where a run holds nothing to read, as with a program older than the
counter.  :func:`program_idle_gaps` charges the window's idle gaps to
the program's spans, as ``bench.trace.reduce`` charges them to the
harness's ``chipbench.`` spans.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench import trace

PROGRAM_PREFIX = "fifo."
OUTSIDE = "outside fifo spans"


def ratio(run, num: str, den: str, scale: float = 1.0) -> Optional[float]:
    """``scale * counters[num] / counters[den]``."""
    n, d = run.counters.get(num), run.counters.get(den)
    if n is None or not d:
        return None
    return scale * n / d


def ns_per_tile_iter(run, kernel: str = "fifo_eval_raw") -> Optional[float]:
    """The kernel's device time per vreg-tile iteration it counted."""
    tiles = run.counters.get("raw_tile_iters")
    if run.trace is None or not tiles:
        return None
    return 1e9 * run.trace.kernel_s.get(kernel, 0.0) / tiles


def charge(gaps, spans) -> Dict[str, float]:
    """Idle seconds of ``gaps`` by the innermost program span around
    each, with ``trace._charge``; other spans are left out."""
    out = trace._charge(gaps, [s for s in spans
                               if s[0].startswith(PROGRAM_PREFIX)])
    if "outside chipbench spans" in out:
        out[OUTSIDE] = out.pop("outside chipbench spans")
    return out


def program_idle_gaps(path: str, n_devices: int, top: int = 10
                      ) -> List[Tuple[str, float]]:
    """The idle gaps of the trace at ``path`` (those ``trace.reduce``
    finds: no device of the first ``n_devices`` ran an op) charged to
    the program's spans, largest first."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    spans, busy, window = [], [], None
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            idx = int(plane.name[len(trace.DEVICE_PREFIX):].split()[0])
            if idx < n_devices:
                busy += [(e.start_ns, e.end_ns) for line in plane.lines
                         if line.name == trace.OPS_LINE
                         for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace.WINDOW_SPAN:
                        window = (e.start_ns, e.end_ns)
                    elif e.name.startswith(PROGRAM_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
    if window is None:
        raise ValueError(f"no {trace.WINDOW_SPAN} span in {path}")
    lo, hi = window
    gaps = trace._gaps(trace.union(trace._clip(busy, lo, hi)), lo, hi)
    out = sorted(charge(gaps, spans).items(), key=lambda kv: -kv[1])
    return [(name, ns / 1e9) for name, ns in out[:top]]
