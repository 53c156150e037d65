"""The benchmark's designs as stage lists for the plain reference
(``chipbench/bench/reference.py``), written from the published kernels.

    python3 chipbench/tools/stage_lists.py            # compare with the files
    python3 chipbench/tools/stage_lists.py --write    # rewrite the files

Each design is a Stream-HLS dataflow graph of the FIFOAdvisor paper's
suite (arXiv:2510.20981, Table II, after Basalama & Cong, FPGA'24): the
PolyBench kernel or DNN block lowered to loaders, pipelined loop nests
and stores that talk through stream arrays.  The structure follows the
kernel (``C = alpha*A@B + beta*C`` is a loader per operand, one matmul,
one combine, one store); the trip counts are the scaled ones listed under
each configuration's ``reduced``.  Nothing here imports the program: the
stage records are this table, not a recording of the program's own
design builders.  ``chipbench/tests/test_reference.py`` holds the files
equal to this table, and the reference built from them equal to the
program's trace.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
DESIGNS = os.path.join(os.path.dirname(HERE), "configs", "designs")

#: loop-nest timing of every stage: initiation interval 1, and two
#: cycles of row overhead between a row's reads and its writes
II, ROW_OVERHEAD = 1, 2


class _Graph:
    def __init__(self, name: str):
        self.spec = {"name": name, "streams": [], "tasks": []}

    def streams(self, name: str, lanes: int) -> str:
        self.spec["streams"].append({"name": name, "lanes": lanes,
                                     "width": 32})
        return name

    def _task(self, stage: str, name: str, **params):
        self.spec["tasks"].append({"stage": stage, "name": name, **params})

    def producer(self, name, out, count):
        self._task("producer", name, out=out, count=count, ii=II,
                   start_delay=0)

    def sink(self, name, inp, count):
        self._task("sink", name, inp=inp, count=count, ii=II)

    def map(self, name, inp, out, count, extra_delay=0):
        self._task("map", name, inp=inp, out=out, count=count, ii=II,
                   extra_delay=extra_delay)

    def fork(self, name, inp, out_a, out_b, count):
        self._task("fork", name, inp=inp, out_a=out_a, out_b=out_b,
                   count=count, ii=II)

    def join(self, name, in_a, in_b, out, count):
        self._task("join", name, in_a=in_a, in_b=in_b, out=out, count=count,
                   ii=II)

    def matvec(self, name, inp, out, rows, cols):
        self._task("matvec", name, inp=inp, out=out, rows=rows, cols=cols,
                   ii=II, row_overhead=ROW_OVERHEAD, reuse_input=True)

    def matmul(self, name, inp, out, m, k, n):
        self._task("matmul", name, inp=inp, out=out, m=m, k=k, n=n, ii=II,
                   row_overhead=ROW_OVERHEAD)

    def conv(self, name, inp, out, length):
        self._task("conv", name, inp=inp, out=out, length=length, ii=II)

    def buffered_matmul(self, name, a_in, b_in, out, m, k, n, b_col_order):
        self._task("buffered_matmul", name, a_in=a_in, b_in=b_in, out=out,
                   m=m, k=k, n=n, ii=II, row_overhead=ROW_OVERHEAD,
                   b_col_order=b_col_order)


def gemm(m=32, k=32, n=32, lanes=8):
    g = _Graph("gemm")
    a, c_in = g.streams("a", lanes), g.streams("c_in", lanes)
    ab, c_out = g.streams("ab", lanes), g.streams("c_out", lanes)
    g.producer("load_a", a, m * k)
    g.producer("load_c", c_in, m * n)
    g.matmul("mm", a, ab, m, k, n)
    g.join("scale_add", ab, c_in, c_out, m * n)
    g.sink("store_c", c_out, m * n)
    return g


def atax(m=96, n=96, lanes=2):
    g = _Graph("atax")
    x, tmp, y = (g.streams(s, lanes) for s in ("x", "tmp", "y"))
    g.producer("load_x", x, n)
    g.matvec("ax", x, tmp, m, n)
    g.matvec("aty", tmp, y, n, m)
    g.sink("store_y", y, n)
    return g


def mvt(n=96, lanes=2):
    g = _Graph("mvt")
    y1, y2, t1, t2, x1i, x2i, x1o, x2o = (g.streams(s, lanes) for s in (
        "y1", "y2", "t1", "t2", "x1_in", "x2_in", "x1_out", "x2_out"))
    for name, out in (("load_y1", y1), ("load_y2", y2), ("load_x1", x1i),
                      ("load_x2", x2i)):
        g.producer(name, out, n)
    g.matvec("a_y1", y1, t1, n, n)
    g.matvec("at_y2", y2, t2, n, n)
    g.join("add_x1", x1i, t1, x1o, n)
    g.join("add_x2", x2i, t2, x2o, n)
    g.sink("store_x1", x1o, n)
    g.sink("store_x2", x2o, n)
    return g


def gesummv(n=96, lanes=2):
    g = _Graph("gesummv")
    x, xa, xb, ta, tb, y = (g.streams(s, lanes) for s in (
        "x", "xa", "xb", "ta", "tb", "y"))
    g.producer("load_x", x, n)
    g.fork("dup_x", x, xa, xb, n)
    g.matvec("a_x", xa, ta, n, n)
    g.matvec("b_x", xb, tb, n, n)
    g.join("sum", ta, tb, y, n)
    g.sink("store_y", y, n)
    return g


def feedforward(seq=32, dim=16, hidden=64, lanes=8):
    """y = x + W2 relu(W1 x)"""
    g = _Graph("FeedForward")
    x, skip, main, h, hr, o, y = (g.streams(s, lanes) for s in (
        "x", "skip", "main", "h", "hr", "o", "y"))
    g.producer("load_x", x, seq * dim)
    g.fork("fork", x, skip, main, seq * dim)
    g.matmul("w1", main, h, seq, dim, hidden)
    g.map("relu", h, hr, seq * hidden)
    g.matmul("w2", hr, o, seq, hidden, dim)
    g.join("residual", skip, o, y, seq * dim)
    g.sink("store", y, seq * dim)
    return g


def autoencoder(seq=24, dims=(32, 16, 8, 16, 32), lanes=4):
    g = _Graph("Autoencoder")
    cur = g.streams("x", lanes)
    g.producer("load", cur, seq * dims[0])
    for i in range(len(dims) - 1):
        out = g.streams(f"z{i}", lanes)
        g.matmul(f"fc{i}", cur, out, seq, dims[i], dims[i + 1])
        cur = out
        if i < len(dims) - 2:
            cur = g.streams(f"a{i}", lanes)
            g.map(f"relu{i}", out, cur, seq * dims[i + 1])
    g.sink("store", cur, seq * dims[-1])
    return g


def residual_block(length=768, lanes=4):
    """conv -> relu -> conv beside a skip path, then add and relu"""
    g = _Graph("ResidualBlock")
    x, skip, main, c1, r1, c2, y, yr = (g.streams(s, lanes) for s in (
        "x", "skip", "main", "c1", "r1", "c2", "y", "yr"))
    g.producer("load", x, length)
    g.fork("fork", x, skip, main, length)
    g.conv("conv1", main, c1, length)
    g.map("relu1", c1, r1, length, extra_delay=1)
    g.conv("conv2", r1, c2, length)
    g.join("residual", skip, c2, y, length)
    g.map("relu2", y, yr, length)
    g.sink("store", yr, length)
    return g


def kmm_seq(name, dims, lanes=4):
    """a chain of len(dims) - 2 matmuls over an (m0 x dims[1]) input"""
    g = _Graph(name)
    m0 = dims[0]
    cur = g.streams("x0", lanes)
    g.producer("load_x0", cur, m0 * dims[1])
    for s in range(1, len(dims) - 1):
        out = g.streams(f"x{s}", lanes)
        g.matmul(f"mm{s}", cur, out, m0, dims[s], dims[s + 1])
        cur = out
    g.sink("store", cur, m0 * dims[-1])
    return g


def kmm_tree(name, n_leaves, t, lanes=4, relu=False, b_col_order=True):
    """a balanced reduction tree of 2 * n_leaves - 1 (t x t) matmuls: the
    leaves stream their operand in, each node buffers its right operand
    and streams its left (column-major reads of the right one where
    ``b_col_order``), with a ReLU after every node below the root"""
    g = _Graph(name)
    level: List[str] = []
    for i in range(n_leaves):
        src, out = g.streams(f"in{i}", lanes), g.streams(f"l0_{i}", lanes)
        g.producer(f"load{i}", src, t * t)
        g.matmul(f"leaf{i}", src, out, t, t, t)
        level.append(out)
    lvl = 1
    while len(level) > 1:
        nxt = []
        for j in range(0, len(level), 2):
            out = g.streams(f"l{lvl}_{j // 2}", lanes)
            g.buffered_matmul(f"node{lvl}_{j // 2}", level[j], level[j + 1],
                              out, t, t, t, b_col_order)
            if relu and len(level) > 2:
                act = g.streams(f"lr{lvl}_{j // 2}", lanes)
                g.map(f"relu{lvl}_{j // 2}", out, act, t * t)
                out = act
            nxt.append(out)
        level = nxt
        lvl += 1
    g.sink("store", level[0], t * t)
    return g


SUITE = {
    "gemm": gemm, "atax": atax, "mvt": mvt, "gesummv": gesummv,
    "FeedForward": feedforward, "Autoencoder": autoencoder,
    "ResidualBlock": residual_block,
    "k15mmseq": lambda: kmm_seq("k15mmseq", [16] * 16),
    "k7mmtree_balanced": lambda: kmm_tree("k7mmtree_balanced", 4, 24,
                                          b_col_order=False),
    "k15mmtree": lambda: kmm_tree("k15mmtree", 8, 24),
    "k15mmtree_relu": lambda: kmm_tree("k15mmtree_relu", 8, 24, relu=True),
}


def stage_list(name: str) -> Dict:
    return SUITE[name]().spec


def dumps(spec: Dict) -> str:
    """One stream or stage to a line, as the files are laid out; a
    data-dependent design's ``program`` entry on a line after its name."""
    def block(key):
        rows = ",\n".join("  " + json.dumps(x) for x in spec[key])
        return f' "{key}": [\n{rows}\n ]'
    program = (f' "program": {json.dumps(spec["program"])},\n'
               if "program" in spec else "")
    return (f'{{\n "name": {json.dumps(spec["name"])},\n{program}'
            f'{block("streams")},\n{block("tasks")}\n}}\n')


def main(argv) -> int:
    stale = []
    for name in sorted(SUITE):
        path = os.path.join(DESIGNS, f"{name}.json")
        text = dumps(stage_list(name))
        if "--write" in argv:
            with open(path, "w") as f:
                f.write(text)
            continue
        try:
            with open(path) as f:
                if f.read() != text:
                    stale.append(name)
        except FileNotFoundError:
            stale.append(name)
    for name in stale:
        print(f"{name}: the file differs from the table", file=sys.stderr)
    return 1 if stale else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
