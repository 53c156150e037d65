"""From a profiler trace to device busy time, kernel time and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
:func:`reduce` reads it with JAX's own ``ProfileData``:

* busy: per device, the union of the intervals in which an XLA op ran
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to
  the window; the mean over the devices used is ``busy_s``;
* window: the ``chipbench.window`` host span, which the harness opens
  around the measured window;
* kernel time: the summed device durations of the ops named after a
  kernel (``fifo_eval_raw``, ``fifo_eval_condensed``: the name the kernel
  gives its ``pallas_call``), over all devices;
* top ops: device time by op name, the HLO instance number dropped;
* idle gaps: the stretches of the window in which no device ran an op,
  each charged to the innermost ``chipbench.`` host span around its
  midpoint, summed by span name.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # mean over the devices used
    busy_by_device: List[float]
    kernel_s: Dict[str, float]          # summed over devices
    top_ops: List[Tuple[str, float]]    # device ops by total seconds
    idle_gaps: List[Tuple[str, float]]  # host span -> idle seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(paths)}")
    return paths[0]


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Sorted, merged ``[start, end]`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def _gaps(busy: List[List[float]], lo: float, hi: float):
    t = lo
    for s, e in busy:
        if s > t:
            yield t, s
        t = max(t, e)
    if hi > t:
        yield t, hi


def op_name(event_name: str) -> str:
    """An XLA op's name without its HLO text and instance number:
    ``"%fifo_eval_raw.1 = f32[8,128] custom-call(...)"`` -> ``"fifo_eval_raw"``."""
    token = event_name.split(" = ", 1)[0].strip().lstrip("%")
    head, _, tail = token.rpartition(".")
    return head if head and tail.isdigit() else token


def _charge(gaps, spans) -> Dict[str, float]:
    """Sum each gap's length under the innermost span around its midpoint.
    The harness's spans come from one thread, so they nest: a stack of
    the open spans, swept in time order, finds the innermost."""
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    out: Dict[str, float] = {}
    stack: list = []
    j = 0
    for s, e in gaps:
        t = (s + e) / 2
        while j < len(spans) and spans[j][1] <= t:
            while stack and stack[-1][2] < spans[j][1]:
                stack.pop()
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        who = stack[-1][0] if stack else "outside chipbench spans"
        out[who] = out.get(who, 0.0) + (e - s)
    return out


def reduce(path: str, n_devices: int,
           kernels: Iterable[str] = ("fifo_eval_raw", "fifo_eval_condensed"),
           top: int = 10) -> Reduction:
    """Reduce the trace at ``path`` (an ``.xplane.pb`` file) over the
    first ``n_devices`` TPU devices."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    devices: Dict[int, list] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            if idx < n_devices:
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        devices[idx] = [(e.name, e.start_ns, e.end_ns)
                                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, "
                         f"found {len(windows)}")
    lo, hi = windows[0]
    busy_by_device, per_op, kernel_ns = [], {}, {k: 0.0 for k in kernels}
    all_busy = []
    for idx in range(n_devices):
        evs = [(n, s, e) for n, s, e in devices.get(idx, ()) if e > lo
               and s < hi]
        busy = union(_clip([(s, e) for _, s, e in evs], lo, hi))
        all_busy += busy
        busy_by_device.append(sum(e - s for s, e in busy) / 1e9)
        for name, s, e in evs:
            op = op_name(name)
            per_op[op] = per_op.get(op, 0.0) + (e - s)
            if op in kernel_ns:
                kernel_ns[op] += e - s
    gaps = _charge(_gaps(union(all_busy), lo, hi), spans)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy_by_device) / n_devices,
        busy_by_device=busy_by_device,
        kernel_s={k: v / 1e9 for k, v in kernel_ns.items()},
        top_ops=[(n, v / 1e9) for n, v in ops],
        idle_gaps=[(n, v / 1e9) for n, v in idle])

