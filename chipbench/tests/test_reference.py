"""The plain reference against the program, on the CPU.

The design files must be the benchmark's own table of the published
kernels (``tools/stage_lists.py``).  The reference must describe the
same designs as the program (its op streams equal the program's trace,
task by task) and give the same answers as the program's exact numpy
worklist on seeded rows, deadlocks included; its bfloat16 control must
not.
"""

import glob
import json
import os

import numpy as np
import pytest

from bench import reference as ref
from tools import stage_lists

DESIGNS = sorted(glob.glob(os.path.join(
    os.path.dirname(__file__), "..", "configs", "designs", "*.json")))


def _spec(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", DESIGNS, ids=os.path.basename)
def test_files_follow_the_table(path):
    with open(path) as f:
        text = f.read()
    name = json.loads(text)["name"]
    assert text == stage_lists.dumps(stage_lists.stage_list(name))


@pytest.mark.parametrize("path", DESIGNS, ids=os.path.basename)
def test_ops_match_program_trace(path):
    from repro.core.tracer import collect_trace
    from repro.designs import make_design
    spec = _spec(path)
    design = ref.Design(spec)
    program = make_design(spec["name"])
    assert design.widths == program.widths()
    trace = collect_trace(program)
    assert len(trace.tasks) == len(design.tasks)
    for tt, ops, trailing in zip(trace.tasks, design.tasks,
                                 design.trailing):
        kinds, fifos, delays = zip(*ops) if ops else ((), (), ())
        assert list(tt.kinds) == list(kinds)
        assert list(tt.fifos) == list(fifos)
        assert list(tt.deltas) == list(delays)
        assert tt.end_delay == trailing


def _rows(g, n, seed):
    """Half inside the upper bounds' hot region, half uniform (deadlocks)."""
    rng = np.random.default_rng(seed)
    u = np.asarray(g.upper_bounds, dtype=np.int64)
    hot = np.maximum(2, (u * rng.uniform(0.5, 1.0, (n // 2, u.size)))
                     .astype(np.int64))
    wide = rng.integers(1, u + 1, size=(n - n // 2, u.size))
    return np.concatenate([hot, wide])


@pytest.mark.parametrize("path", DESIGNS, ids=os.path.basename)
def test_answers_match_program(path):
    from repro.core import build_simgraph
    from repro.core.config import EvalConfig
    from repro.core.simulate import BatchedEvaluator
    from repro.designs import make_design
    spec = _spec(path)
    design = ref.Design(spec)
    g = build_simgraph(make_design(spec["name"]))
    rows = np.concatenate([_rows(g, 6, seed=7),
                           np.full((1, g.n_fifos), 2, dtype=np.int64)])
    lat, bram, dead = BatchedEvaluator(
        g, EvalConfig(backend="numpy")).evaluate(rows)
    got = [ref.answer(design, r) for r in rows]
    assert [a[0] for a in got] == lat.tolist()
    assert [a[1] for a in got] == bram.tolist()
    assert [a[2] for a in got] == dead.tolist()
    assert not all(dead)
    if spec["name"] == "k15mmtree_relu":
        assert dead[-1]       # the paper's Baseline-Min deadlock


def test_bfloat16_control_fails():
    spec = _spec([p for p in DESIGNS if p.endswith("gemm.json")][0])
    design = ref.Design(spec)
    depths = [64] * design.n_fifos
    exact = ref.answer(design, depths)
    control = ref.answer(design, depths, round_to=ref.bfloat16)
    assert not exact[2] and control[0] != exact[0]


def test_frontier():
    pts = ref.frontier([5, 3, 3, 7, -1, 3], [1, 4, 2, 0, 0, 2],
                       [False, False, False, False, True, False])
    assert pts == [(3, 2), (5, 1), (7, 0)]


def test_bram_algorithm_1():
    # 32-bit FIFOs: depth 32 is 1,024 bits, a shift register; depth 33
    # takes one 1Kx18 for 18 bits and, as the 14 bits left fit 1K deep,
    # one more: 2
    assert ref.bram([32], [32]) == 0
    assert ref.bram([2], [4096]) == 0
    assert ref.bram([33], [32]) == 2
    assert ref.bram([4096, 33], [9, 32]) == 2 + 2
