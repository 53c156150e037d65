"""The plain reference: what a FIFO-sizing answer must be.

Independent of the program under test.  A design comes from the
benchmark's own configuration files as a list of stream arrays and
stages (Stream-HLS loaders, stores and pipelined loop nests, written
from the published kernels by ``chipbench/tools/stage_lists.py``); this
module turns each stage into
its sequence of FIFO operations and simulates the design at one depth
vector.  A stage that is none of the nine built in here is a builder
found by file: ``"stage": "pna.scatter"`` is ``scatter(fifos, rec)`` of
``chipbench/stages/pna.py`` (:func:`bench.spec.stage`), so a
data-dependent design, whose tasks' op counts follow its input data,
arrives as new files only.  The timing contract is the FIFOAdvisor
paper's:

* op ``i`` of a task completes no earlier than ``t[i-1] + delay[i]``;
* the k-th read of FIFO ``f`` completes no earlier than
  ``t(write_k) + rd_lat(f)``: 1 for a shift-register FIFO (depth <= 2 or
  depth * width <= 1024 bits), 2 for a BRAM-backed one;
* the j-th write to FIFO ``f`` of depth ``d`` completes no earlier than
  ``t(read_{j-d}) + 1``;
* a task ends at its last op plus its trailing delay; the design's
  latency is the latest task end; a design deadlocks when unfinished
  tasks remain and none can progress.

BRAM is Algorithm 1 of the paper (BRAM18K aspect ratios, shift
registers free), and the frontier is the set of Pareto-optimal
(latency, BRAM) points of the feasible rows.  ``round_to`` lets the
control run the same simulation with every completion time rounded to a
lower precision (bfloat16), which must fail the comparison.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bench.spec import BENCH, stage as find_stage

READ, WRITE = 0, 1

#: BRAM18K (depth, width) aspect ratios, widest first
BRAM18K = ((1024, 18), (2048, 9), (4096, 4), (8192, 2), (16384, 1))
SRL_BITS = 1024
SRL_DEPTH = 2


class Design:
    """A design's FIFO operations: ``tasks[t]`` is a list of
    ``(kind, fifo, delay)`` triples and ``trailing[t]`` the delay after
    its last op; ``widths[f]`` is FIFO f's element width in bits.
    ``bench`` is the directory whose ``stages/`` holds the stage files."""

    def __init__(self, spec: dict, bench: str = BENCH):
        self.name = spec["name"]
        self.fifos: Dict[str, List[int]] = {}
        self.widths: List[int] = []
        for s in spec["streams"]:
            base = len(self.widths)
            self.fifos[s["name"]] = list(range(base, base + s["lanes"]))
            self.widths += [int(s["width"])] * s["lanes"]
        self.tasks: List[List[Tuple[int, int, int]]] = []
        self.trailing: List[int] = []
        builders = dict(_STAGES)
        for rec in spec["tasks"]:
            if rec["stage"] not in builders:
                builders[rec["stage"]] = find_stage(bench, rec["stage"])
            ops, trailing = builders[rec["stage"]](self.fifos, rec)
            self.tasks.append(ops)
            self.trailing.append(trailing)

    @property
    def n_fifos(self) -> int:
        return len(self.widths)

    @property
    def n_events(self) -> int:
        return sum(len(ops) for ops in self.tasks)


class _Ops:
    """Collects one task's ops, folding delays into the next op."""

    def __init__(self):
        self.ops: List[Tuple[int, int, int]] = []
        self.pending = 0

    def delay(self, cycles: int):
        self.pending += int(cycles)

    def read(self, fifo: int):
        self.ops.append((READ, fifo, self.pending))
        self.pending = 0

    def write(self, fifo: int):
        self.ops.append((WRITE, fifo, self.pending))
        self.pending = 0

    def done(self):
        return self.ops, self.pending


#: the op collector, for stage files
Ops = _Ops


def _producer(fifos, r):
    o, out = _Ops(), fifos[r["out"]]
    o.delay(r.get("start_delay", 0))
    for i in range(r["count"]):
        o.delay(r["ii"])
        o.write(out[i % len(out)])
    return o.done()


def _sink(fifos, r):
    o, inp = _Ops(), fifos[r["inp"]]
    for i in range(r["count"]):
        o.delay(r["ii"])
        o.read(inp[i % len(inp)])
    return o.done()


def _map(fifos, r):
    o, inp, out = _Ops(), fifos[r["inp"]], fifos[r["out"]]
    for i in range(r["count"]):
        o.delay(r["ii"])
        o.read(inp[i % len(inp)])
        o.delay(r.get("extra_delay", 0))
        o.write(out[i % len(out)])
    return o.done()


def _fork(fifos, r):
    o, inp = _Ops(), fifos[r["inp"]]
    a, b = fifos[r["out_a"]], fifos[r["out_b"]]
    for i in range(r["count"]):
        o.delay(r["ii"])
        o.read(inp[i % len(inp)])
        o.write(a[i % len(a)])
        o.write(b[i % len(b)])
    return o.done()


def _join(fifos, r):
    o, a, b = _Ops(), fifos[r["in_a"]], fifos[r["in_b"]]
    out = fifos[r["out"]]
    for i in range(r["count"]):
        o.delay(r["ii"])
        o.read(a[i % len(a)])
        o.read(b[i % len(b)])
        o.write(out[i % len(out)])
    return o.done()


def _matvec(fifos, r):
    o, inp, out = _Ops(), fifos[r["inp"]], fifos[r["out"]]
    cols = r["cols"]
    for row in range(r["rows"]):
        if row == 0 or not r["reuse_input"]:
            for c in range(cols):
                o.delay(r["ii"])
                o.read(inp[c % len(inp)])
        else:
            o.delay(max(1, cols // 4))
        o.delay(r["row_overhead"])
        o.write(out[row % len(out)])
    return o.done()


def _row_products(o, a, out, m, k, n, ii, overhead):
    for row in range(m):
        for c in range(k):
            o.delay(ii)
            o.read(a[(row * k + c) % len(a)])
        o.delay(overhead)
        for j in range(n):
            o.delay(ii)
            o.write(out[(row * n + j) % len(out)])


def _matmul(fifos, r):
    o = _Ops()
    _row_products(o, fifos[r["inp"]], fifos[r["out"]], r["m"], r["k"],
                  r["n"], r["ii"], r["row_overhead"])
    return o.done()


def _conv(fifos, r):
    o, inp, out = _Ops(), fifos[r["inp"]], fifos[r["out"]]
    for i in range(r["length"]):
        o.delay(r["ii"])
        o.read(inp[i % len(inp)])
        o.write(out[i % len(out)])
    return o.done()


def _buffered_matmul(fifos, r):
    o, b_in = _Ops(), fifos[r["b_in"]]
    k, n = r["k"], r["n"]
    if r["b_col_order"]:
        order = [i * n + j for j in range(n) for i in range(k)]
    else:
        order = range(k * n)
    for flat in order:
        o.delay(r["ii"])
        o.read(b_in[flat % len(b_in)])
    _row_products(o, fifos[r["a_in"]], fifos[r["out"]], r["m"], k, n,
                  r["ii"], r["row_overhead"])
    return o.done()


_STAGES: Dict[str, Callable] = {
    "producer": _producer, "sink": _sink, "map": _map, "fork": _fork,
    "join": _join, "matvec": _matvec, "matmul": _matmul, "conv": _conv,
    "buffered_matmul": _buffered_matmul,
}


def is_srl(depth: int, width: int) -> bool:
    return depth <= SRL_DEPTH or depth * width <= SRL_BITS


def bram(depths: Sequence[int], widths: Sequence[int]) -> int:
    """Algorithm 1 of the paper, summed over the FIFOs."""
    total = 0
    for depth, width in zip(depths, widths):
        if is_srl(depth, width):
            continue
        w = width
        for d_i, w_i in BRAM18K:
            total += (w // w_i) * -(-depth // d_i)
            w %= w_i
            if w > 0 and depth <= d_i:
                total += 1
                w = 0
    return total


def simulate(design: Design, depths: Sequence[int],
             round_to: Optional[Callable[[float], float]] = None
             ) -> Tuple[int, bool]:
    """``(latency, deadlocked)`` of one depth vector; latency is -1 on
    deadlock.  ``round_to`` rounds every completion time (the control)."""
    depths = [int(d) for d in depths]
    rd_lat = [1 if is_srl(d, w) else 2
              for d, w in zip(depths, design.widths)]
    wt: List[list] = [[] for _ in depths]
    rt: List[list] = [[] for _ in depths]
    pos = [0] * len(design.tasks)
    now = [0] * len(design.tasks)
    progress = True
    while progress:
        progress = False
        for t, ops in enumerate(design.tasks):
            i, cur = pos[t], now[t]
            while i < len(ops):
                kind, f, delay = ops[i]
                ready = cur + delay
                if kind == READ:
                    k = len(rt[f])
                    if k >= len(wt[f]):
                        break
                    cur = max(ready, wt[f][k] + rd_lat[f])
                    if round_to is not None:
                        cur = round_to(cur)
                    rt[f].append(cur)
                else:
                    j, d = len(wt[f]), depths[f]
                    if j >= d:
                        if len(rt[f]) <= j - d:
                            break
                        cur = max(ready, rt[f][j - d] + 1)
                    else:
                        cur = ready
                    if round_to is not None:
                        cur = round_to(cur)
                    wt[f].append(cur)
                i += 1
            if i != pos[t]:
                pos[t], now[t] = i, cur
                progress = True
    if any(p < len(ops) for p, ops in zip(pos, design.tasks)):
        return -1, True
    ends = [now[t] + design.trailing[t] for t in range(len(design.tasks))]
    latency = max(ends) if ends else 0
    if round_to is not None:
        latency = round_to(latency)
    return int(latency), False


def answer(design: Design, depths: Sequence[int],
           round_to=None) -> Tuple[int, int, bool]:
    """``(latency, bram, deadlocked)`` of one depth vector."""
    lat, dead = simulate(design, depths, round_to)
    return lat, bram(depths, design.widths), dead


def frontier(latency: Sequence[int], brams: Sequence[int],
             dead: Sequence[bool]) -> List[Tuple[int, int]]:
    """The distinct Pareto-optimal (latency, BRAM) points of the feasible
    rows, minimizing both, sorted by latency."""
    pts = sorted({(int(l), int(b)) for l, b, d in zip(latency, brams, dead)
                  if not d})
    out: List[Tuple[int, int]] = []
    best = None
    for lat, b in pts:
        if best is None or b < best:
            out.append((lat, b))
            best = b
    return out


def bfloat16(x: float) -> float:
    """``x`` rounded to the nearest bfloat16 (ties to even)."""
    import ml_dtypes
    import numpy as np
    return float(np.float32(x).astype(ml_dtypes.bfloat16))
