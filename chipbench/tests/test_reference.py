"""The plain reference against the program, on the CPU.

A design file without a ``program`` entry is a Stream-HLS kernel: it
must be the benchmark's own table of the published kernels
(``tools/stage_lists.py``) and describe ``make_design(name)``.  A file
with one (a data-dependent design, whose stages are found by file under
``stages/``) must be its writer's output for the entry's arguments
(``tools/pna_design.py``) and describe the design that the entry's call
builds.  The reference must describe the same designs as the program
(its op streams equal the program's trace, task by task) and give the
same answers as the program's exact numpy worklist on seeded rows,
deadlocks included; its bfloat16 control must not.
"""

import glob
import json
import os

import numpy as np
import pytest

from bench import reference as ref
from stages import pna
from tools import pna_design, stage_lists

DESIGNS = sorted(glob.glob(os.path.join(
    os.path.dirname(__file__), "..", "configs", "designs", "*.json")))
#: what writes a design file with a ``program`` entry, by its call
WRITERS = {pna_design.CALL: pna_design.text_of}
#: PNA graphs beyond the design files' (nodes, edges, lanes, seed); the
#: last is the Cora citation graph's size, 133,192 events
PNA_GRAPHS = [(50, 300, 2, 3), (200, 1000, 8, 12345),
              (97, 97, 1, 3000000019), (2708, 10556, 4, 7)]


def _spec(path):
    if isinstance(path, tuple):
        return pna_design.design("flowgnn_pna", *path)
    with open(path) as f:
        return json.load(f)


def _id(path):
    if isinstance(path, tuple):
        return "pna-" + "-".join(map(str, path))
    return os.path.basename(path)


def _program(spec):
    """The program's design that ``spec`` describes."""
    import repro.designs
    if "program" in spec:
        call = spec["program"]
        return getattr(repro.designs, call["call"])(**call["args"])
    return repro.designs.make_design(spec["name"])


@pytest.mark.parametrize("path", DESIGNS, ids=os.path.basename)
def test_files_follow_the_table(path):
    with open(path) as f:
        text = f.read()
    spec = json.loads(text)
    if "program" in spec:
        assert text == WRITERS[spec["program"]["call"]](spec)
    else:
        assert text == stage_lists.dumps(stage_lists.stage_list(
            spec["name"]))


@pytest.mark.parametrize("path", DESIGNS + PNA_GRAPHS, ids=_id)
def test_ops_match_program_trace(path):
    from repro.core.tracer import collect_trace
    spec = _spec(path)
    design = ref.Design(spec)
    program = _program(spec)
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


def _bound_rows(g, n, seed):
    """Each FIFO at its upper bound, or with chance 1/4 drawn uniformly
    below it: a data-dependent design deadlocks on nearly every row
    ``_rows`` draws, and runs on some of these."""
    rng = np.random.default_rng(seed)
    u = np.asarray(g.upper_bounds, dtype=np.int64)
    drawn = rng.integers(1, u + 1, size=(n, u.size))
    return np.where(rng.uniform(size=(n, u.size)) < 0.75, u, drawn)


@pytest.mark.parametrize("path", DESIGNS, ids=os.path.basename)
def test_answers_match_program(path):
    from repro.core import build_simgraph
    from repro.core.config import EvalConfig
    from repro.core.simulate import BatchedEvaluator
    spec = _spec(path)
    design = ref.Design(spec)
    g = build_simgraph(_program(spec))
    rows = [_rows(g, 6, seed=7), np.full((1, g.n_fifos), 2, dtype=np.int64)]
    if "program" in spec:
        rows.append(_bound_rows(g, 8, seed=7))
    rows = np.concatenate(rows)
    lat, bram, dead = BatchedEvaluator(
        g, EvalConfig(backend="numpy")).evaluate(rows)
    got = [ref.answer(design, r) for r in rows]
    assert [a[0] for a in got] == lat.tolist()
    assert [a[1] for a in got] == bram.tolist()
    assert [a[2] for a in got] == dead.tolist()
    assert not all(dead)
    if spec["name"] == "k15mmtree_relu":
        assert dead[-1]       # the paper's Baseline-Min deadlock
    if "program" in spec:
        assert any(dead)


@pytest.mark.parametrize("name", ["gemm", "flowgnn_pna"])
def test_bfloat16_control_fails(name):
    spec = _spec([p for p in DESIGNS if p.endswith(f"/{name}.json")][0])
    design = ref.Design(spec)
    depths = [64] * design.n_fifos
    exact = ref.answer(design, depths)
    control = ref.answer(design, depths, round_to=ref.bfloat16)
    assert not exact[2] and control[0] != exact[0]


@pytest.mark.parametrize("nodes, edges, seed", [
    (64, 256, 7), (50, 300, 3), (1000, 5000, 3000000019),
    (2708, 10556, 7)])
def test_pna_graph_matches_program(nodes, edges, seed):
    from repro.designs.ddcf import _random_graph
    assert pna.graph(nodes, edges, seed) == _random_graph(nodes, edges,
                                                          seed)


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
