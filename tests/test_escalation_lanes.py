"""Escalated rows solved on worklist lanes: exact, in row order, and no
lane outlives its evaluator."""

import gc
import multiprocessing as mp
import os
import pickle

import numpy as np
import pytest

from repro.core import FifoAdvisor
from repro.core.backends import dispatch
from repro.core.backends.base import DEADLOCK, UNRESOLVED, EvalBackend
from repro.core.backends.dispatch import (LANES, DispatchPolicy,
                                          EscalationLanes)
from repro.core.backends.worklist import WorklistBackend
from repro.core.bram import design_bram_np
from repro.core.campaign.pool import WorkerPool
from repro.core.config import EvalConfig
from repro.core.faults import Fault, FaultPlan
from repro.core.simgraph import build_simgraph
from repro.core.simulate import BatchStats, BatchedEvaluator
from repro.designs import make_design
from repro.designs.ddcf import flowgnn_pna, mult_by_2


class Undecided(EvalBackend):
    """A backend that leaves every row UNRESOLVED, so the policy
    escalates the whole batch."""

    name = "undecided"

    def prepare(self, g):
        self.g = g

    def evaluate(self, depth_matrix):
        m = np.atleast_2d(np.asarray(depth_matrix, dtype=np.int64))
        c = m.shape[0]
        return (np.zeros(c, np.int64),
                design_bram_np(m, np.asarray(self.g.widths)),
                np.full(c, UNRESOLVED, np.int8))


def _policy(g, lanes=None):
    wl = WorklistBackend()
    wl.prepare(g)
    backend = Undecided()
    backend.prepare(g)
    return DispatchPolicy(wl, lanes=lanes), backend


def _inline(g, m):
    """What the policy returned before lanes: the worklist on all rows."""
    wl = WorklistBackend()
    wl.prepare(g)
    lat, bram, status = wl.evaluate(m)
    dead = status == DEADLOCK
    return np.where(dead, -1, lat), bram, dead


def _assert_same(got, want):
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        assert np.array_equal(a, b)


@pytest.fixture(scope="module")
def relu_rows():
    """64 grouped_random rows of k15mmtree_relu, most of them deadlocked."""
    adv = FifoAdvisor(make_design("k15mmtree_relu"),
                      EvalConfig(backend="numpy"))
    m = adv.run("grouped_random", budget=64, seed=70000).result.configs
    return adv.graph, np.unique(np.asarray(m, dtype=np.int64), axis=0)


def test_lanes_match_the_inline_worklist(relu_rows):
    g, m = relu_rows
    want = _inline(g, m)
    assert 0 < want[2].sum() < m.shape[0]      # deadlocked and live rows
    policy, backend = _policy(g)
    stats = BatchStats()
    try:
        _assert_same(policy.dispatch(backend, m, stats), want)
        lanes = min(dispatch.escalation_lanes(), m.shape[0] - 1)
        assert LANES.pool.n_workers == lanes
        parent = -(-m.shape[0] // (lanes + 1))
        assert stats.worklist_lane_rows == m.shape[0] - parent
        assert stats.n_fallbacks == m.shape[0]
        # fewer rows than lanes: at most one lane a row
        stats = BatchStats()
        _assert_same(policy.dispatch(backend, m[:3], stats),
                     tuple(a[:3] for a in want))
        assert stats.worklist_lane_rows == 2
    finally:
        policy.close()
    assert LANES.pool is None


def test_lanes_grow_with_the_rows_to_solve(relu_rows, monkeypatch):
    """An 8-row escalation starts 7 lanes, not all the host's; a larger
    one adds lanes, and a smaller one later stops none."""
    monkeypatch.setattr(dispatch, "escalation_lanes", lambda: 12)
    g, m = relu_rows
    want = _inline(g, m)
    lanes = EscalationLanes()
    policy, backend = _policy(g, lanes)
    try:
        for rows, n_workers in ((8, 7), (3, 7), (10, 9), (64, 12)):
            _assert_same(policy.dispatch(backend, m[:rows]),
                         tuple(a[:rows] for a in want))
            assert lanes.pool.n_workers == n_workers
            assert sum(p.is_alive() for p in lanes.pool._procs) == n_workers
    finally:
        policy.close()
    assert lanes.pool is None


def test_one_escalated_row_stays_inline(relu_rows):
    g, m = relu_rows
    policy, backend = _policy(g)
    stats = BatchStats()
    _assert_same(policy.dispatch(backend, m[:1], stats),
                 tuple(a[:1] for a in _inline(g, m[:1])))
    assert stats.n_fallbacks == 1
    assert stats.worklist_lane_rows == 0
    assert policy._lane_key is None


def test_busy_lanes_leave_the_rows_to_the_caller(relu_rows):
    """A thread that finds the lanes solving another thread's rows solves
    its own inline, with the same answers."""
    g, m = relu_rows
    lanes = EscalationLanes()
    policy, backend = _policy(g, lanes)
    stats = BatchStats()
    with lanes._busy:
        _assert_same(policy.dispatch(backend, m[:16], stats),
                     tuple(a[:16] for a in _inline(g, m[:16])))
    assert stats.worklist_lane_rows == 0
    assert lanes.pool is None
    policy.close()


def test_escalation_lanes_follow_the_usable_cpus():
    want = (len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity")
            else os.cpu_count())
    assert dispatch.escalation_lanes() == want - 1


def test_spawn_lanes_are_shipped_the_arrays_of_a_graph():
    g = build_simgraph(flowgnn_pna(24, 60, 2, 3))
    with WorkerPool(1, start_method="spawn", graphs={"g": g}) as pool:
        shipped = pickle.loads(pickle.dumps(pool._ship(0, "g")))
        assert pool._ship(0, "g") is None      # once a lane
    assert shipped.design is None and g.design is not None
    assert shipped.unbounded_latency == g.unbounded_latency
    for name in ("kind", "fifo", "delta", "read_evt_flat", "widths",
                 "upper_bounds", "max_occupancy"):
        assert np.array_equal(getattr(shipped, name), getattr(g, name))


def test_spawn_lanes_solve_a_design_outside_the_registry(monkeypatch):
    """flowgnn_pna at other than its default size: a lane that rebuilt
    the design by name would solve another graph, or none."""
    monkeypatch.setattr("repro.core.campaign.pool.pick_start_method",
                        lambda: "spawn")
    monkeypatch.setattr(dispatch, "escalation_lanes", lambda: 3)
    g = build_simgraph(flowgnn_pna(40, 120, 2, 11))
    u = np.asarray(g.upper_bounds, dtype=np.int64)
    rng = np.random.default_rng(5)
    m = np.unique(np.stack([np.maximum(2, (u * rng.uniform(0, 1, u.size))
                                       .astype(np.int64))
                            for _ in range(12)] + [u]), axis=0)
    want = _inline(g, m)
    assert 0 < want[2].sum() < m.shape[0]
    lanes = EscalationLanes()
    policy, backend = _policy(g, lanes)
    try:
        _assert_same(policy.dispatch(backend, m), want)
        assert lanes.pool.start_method == "spawn"
    finally:
        policy.close()


def test_a_lane_killed_mid_batch_changes_no_answer(relu_rows, monkeypatch):
    monkeypatch.setattr(dispatch, "escalation_lanes", lambda: 3)
    g, m = relu_rows
    plan = FaultPlan([Fault("crash_worker", at=1, lane=1)])
    lanes = EscalationLanes(faults=plan)
    policy, backend = _policy(g, lanes)
    try:
        want = _inline(g, m)
        # lane 1 dies before its second job, the second batch's chunk
        for rows in (slice(0, 32), slice(32, None)):
            _assert_same(policy.dispatch(backend, m[rows]),
                         tuple(a[rows] for a in want))
        assert plan.all_fired
        assert lanes.pool.stats["respawns"] == 1
    finally:
        policy.close()


def test_a_failed_escalation_leaves_no_answer_behind(relu_rows, monkeypatch):
    """The caller's own chunk raises after the lanes took theirs: the
    lanes stop with their answers unread, and the next escalation, on
    fresh lanes, gets its own answers."""
    monkeypatch.setattr(dispatch, "escalation_lanes", lambda: 3)
    g, m = relu_rows
    want = _inline(g, m)
    lanes = EscalationLanes()
    policy, backend = _policy(g, lanes)
    solve = policy.worklist.evaluate
    armed = []

    def fails_once(rows):
        if armed and armed.pop():
            raise RuntimeError("caller's chunk failed")
        return solve(rows)

    monkeypatch.setattr(policy.worklist, "evaluate", fails_once)
    try:
        policy.dispatch(backend, m[:4])                   # lanes started
        procs = list(lanes.pool._procs)
        armed.append(True)
        with pytest.raises(RuntimeError, match="caller's chunk"):
            policy.dispatch(backend, m[:32])
        assert lanes.pool is None
        assert not any(p.is_alive() for p in procs)
        # same row count as the failed batch, other rows
        _assert_same(policy.dispatch(backend, m[32:64]),
                     tuple(a[32:64] for a in want))
    finally:
        policy.close()


def _escalating_evaluator():
    """A jax-backend evaluator whose mult_by_2 rows run out of
    iterations and escalate to its lanes, with the numpy worklist's
    answers: deadlocked below a depth of 47, live from it on."""
    g = build_simgraph(mult_by_2(48))
    ev = BatchedEvaluator(g, EvalConfig(backend="jax", max_iters=4))
    m = np.array([[d, 1] for d in (*range(2, 10), 47, 48, 60)],
                 dtype=np.int64)
    got = ev.evaluate(m)
    _assert_same(got, BatchedEvaluator(
        g, EvalConfig(backend="numpy")).evaluate(m))
    assert 0 < got[2].sum() < m.shape[0]
    assert ev.stats.worklist_lane_rows > 0
    return ev


@pytest.mark.parametrize("how", ["close", "collected"])
def test_no_lane_outlives_its_evaluator(how):
    ev = _escalating_evaluator()
    procs = list(LANES.pool._procs)
    assert procs and all(p.is_alive() for p in procs)
    if how == "close":
        ev.close()
    else:
        del ev
        gc.collect()
    assert not any(p.is_alive() for p in procs)
    assert not set(procs) & set(mp.active_children())


def test_evaluators_share_the_process_lanes():
    """Every evaluator escalates on the same lanes, so the process holds
    no more lane processes than the CPUs it may use, less one; they
    stop when the last evaluator leaves."""
    before = set(mp.active_children())
    evs = [_escalating_evaluator() for _ in range(3)]
    pool = LANES.pool
    lanes = set(mp.active_children()) - before
    assert set(pool._procs) == lanes
    assert 0 < len(lanes) <= dispatch.escalation_lanes()
    evs.pop().close()
    del evs[0]
    gc.collect()
    assert LANES.pool is pool and all(p.is_alive() for p in lanes)
    evs.pop().close()
    assert LANES.pool is None
    assert not any(p.is_alive() for p in lanes)


def test_spawn_lanes_do_not_rerun_an_unguarded_script(tmp_path):
    """A script without a ``__main__`` guard starts lanes: spawn would run
    it again in each lane, which would start lanes of its own."""
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / "unguarded.py"
    script.write_text(f"""
import sys
sys.path.insert(0, {os.path.abspath(src)!r})
import numpy as np
from repro.core.campaign.pool import WorkerPool
from repro.core.simgraph import build_simgraph
from repro.designs.ddcf import mult_by_2
print("script body", flush=True)
g = build_simgraph(mult_by_2(8))
with WorkerPool(2, start_method="spawn", graphs={{"g": g}}) as pool:
    out = pool.run_jobs([(w, "g", np.array([[7, 1], [2, 1]]), None)
                         for w in range(2)])
print("dead", [r[2].tolist() for r in out], flush=True)
""")
    res = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert res.stdout.count("script body") == 1
    assert "dead [[False, True], [False, True]]" in res.stdout
