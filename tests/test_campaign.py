"""Campaign engine: stepwise equivalence, scheduling, checkpoint/resume.

The load-bearing invariants:

* the stepwise ``propose()/observe()`` protocol driven by an external
  scheduler produces byte-identical histories to the blocking ``run()``
  for EVERY registered optimizer;
* a campaign (any routing mode) produces byte-identical per-task
  frontiers to the sequential ``FifoAdvisor.run()`` loop;
* killing a campaign mid-run and resuming from its checkpoint reproduces
  byte-identical frontiers and hypervolumes to an uninterrupted run
  (the seeded RNG state round-trips through the checkpoint — replay
  verifies the bit-state and raises on drift);
* the cross-design hetero dispatch agrees exactly with the per-design
  worklist.
"""

import numpy as np
import pytest

from repro.core import FifoAdvisor
from repro.core.campaign import (Campaign, CampaignSpec, CheckpointMismatch,
                                 load_checkpoint)
from repro.core.optimizers import OPTIMIZERS
from repro.designs import make_design

DESIGN = "gemm"
BUDGET = 80


@pytest.mark.parametrize("opt", sorted(OPTIMIZERS))
def test_stepwise_equals_blocking_run(opt):
    """Scheduler-style stepping == legacy blocking run, per optimizer."""
    d = make_design(DESIGN)
    adv_a = FifoAdvisor(d)
    blocking = adv_a.run(opt, budget=BUDGET, seed=3)

    adv_b = FifoAdvisor(d)
    ctx = adv_b.make_context(seed=3)
    stepper = OPTIMIZERS[opt](ctx, budget=BUDGET)
    while True:
        req = stepper.propose()
        if req is None:
            break
        # the campaign scheduler's routing: cache lookup, evaluate the
        # misses, record history/budget, observe
        lat, bram, dead, miss = ctx.cache.lookup(req.depths)
        rows = np.flatnonzero(miss)
        if rows.size:
            if req.base is not None and adv_b.evaluator.prefer_incremental:
                l, b, dd = adv_b.evaluator.evaluate_incremental(
                    req.base[rows], req.depths[rows])
            else:
                l, b, dd = adv_b.evaluator.evaluate(req.depths[rows])
            lat[rows], bram[rows], dead[rows] = l, b, dd
            ctx.cache.insert(req.depths[rows], l, b, dd)
        ctx.record(req.depths, lat, bram, dead, rows.size)
        stepper.observe(lat, bram, dead)
    stepwise = ctx.result(opt, 0.0)

    assert np.array_equal(blocking.result.configs, stepwise.configs)
    assert np.array_equal(blocking.result.latency, stepwise.latency)
    assert np.array_equal(blocking.result.bram, stepwise.bram)
    assert np.array_equal(blocking.result.deadlock, stepwise.deadlock)
    assert blocking.result.n_evals == stepwise.n_evals
    assert np.array_equal(blocking.frontier_points, stepwise.frontier()[0])


def _spec(**kw):
    base = dict(designs=("gemm", "FeedForward"),
                optimizers=("grouped_sa", "grouped_random"),
                budget=60, seed=0, workers=0)
    base.update(kw)
    return CampaignSpec(**base)


def test_campaign_matches_sequential_loop():
    store = Campaign(_spec()).run()
    for d in ("gemm", "FeedForward"):
        adv = FifoAdvisor(make_design(d))
        for o in ("grouped_sa", "grouped_random"):
            ref = adv.run(o, budget=60, seed=0)
            dse = store[f"{d}:{o}:s0"]
            assert np.array_equal(dse.frontier_points, ref.frontier_points)
            assert dse.hypervolume() == ref.hypervolume()
            assert np.array_equal(dse.result.configs, ref.result.configs)


def test_campaign_pool_matches_inline():
    import multiprocessing as mp

    spec = _spec(designs=("gemm",), budget=40)
    inline = Campaign(spec).run()
    pooled = Campaign(_spec(designs=("gemm",), budget=40,
                            workers=1)).run()
    for k in inline.keys():
        assert np.array_equal(pooled[k].frontier_points,
                              inline[k].frontier_points)
        assert np.array_equal(pooled[k].result.latency,
                              inline[k].result.latency)
    # run() closes the pool on exit; no worker may outlive it
    assert mp.active_children() == []


def test_checkpoint_resume_byte_identical(tmp_path):
    """Kill mid-run; resume must equal the uninterrupted run exactly."""
    spec = _spec(checkpoint_every=2)
    uninterrupted = Campaign(spec).run()

    path = str(tmp_path / "camp.npz")
    camp = Campaign(spec, checkpoint_path=path)
    camp.run(max_rounds=3)          # simulated kill
    assert not camp.finished

    resumed = Campaign.resume(path)
    # replay restored some finished work and the mid-flight generators
    store = resumed.run()
    assert resumed.finished
    for k in uninterrupted.keys():
        a, b = store[k], uninterrupted[k]
        assert np.array_equal(a.frontier_points, b.frontier_points)
        assert a.hypervolume() == b.hypervolume()
        assert np.array_equal(a.result.configs, b.result.configs)
        assert np.array_equal(a.result.latency, b.result.latency)
        assert a.result.n_evals == b.result.n_evals


def test_checkpoint_rng_state_roundtrip(tmp_path):
    """The checkpointed RNG bit-state must match the replayed one."""
    path = str(tmp_path / "camp.npz")
    camp = Campaign(_spec(designs=("gemm",), checkpoint_every=1),
                    checkpoint_path=path)
    camp.run(max_rounds=2)
    data = load_checkpoint(path)
    states = [t["rng_state"] for t in data["tasks"]]
    assert all(s["bit_generator"] == "PCG64" for s in states)
    resumed = Campaign.resume(path)     # raises CheckpointMismatch on drift
    for task, saved in zip(resumed.tasks, states):
        assert task.ctx.rng.bit_generator.state == saved


def test_checkpoint_tamper_detected(tmp_path):
    path = str(tmp_path / "camp.npz")
    camp = Campaign(_spec(designs=("gemm",), checkpoint_every=1),
                    checkpoint_path=path)
    camp.run(max_rounds=2)
    data = np.load(path, allow_pickle=False)
    arrays = {k: data[k].copy() for k in data.files}
    arrays["t0_configs"][0, 0] += 1      # corrupt the recorded history
    np.savez_compressed(path, **arrays)
    with pytest.raises(CheckpointMismatch):
        Campaign.resume(path)


def test_hetero_dispatch_matches_worklist():
    from repro.core.backends import DEADLOCK, HeteroDispatcher
    from repro.core.simgraph import build_simgraph
    from repro.core.tracer import collect_trace
    from repro.designs.ddcf import flowgnn_pna, mult_by_2

    designs = {"m2": mult_by_2(24), "pna": flowgnn_pna(n_nodes=12,
                                                       n_edges=30)}
    graphs = {k: build_simgraph(d, collect_trace(d))
              for k, d in designs.items()}
    disp = HeteroDispatcher(graphs, max_iters=64)
    rng = np.random.default_rng(11)
    items = []
    for k, g in graphs.items():
        u = g.upper_bounds
        m = np.concatenate([
            np.maximum(u, 2)[None, :],
            np.full((1, g.n_fifos), 2),
            np.maximum(2, (u * rng.uniform(0.1, 1.0, (6, g.n_fifos))
                           ).astype(np.int64))])
        items.append((k, m))
    for (k, m), (lat, bram, dead) in zip(items, disp.dispatch(items)):
        wlat, wbram, wstatus = disp.worklists[k].evaluate(m)
        wdead = wstatus == DEADLOCK
        assert np.array_equal(dead, wdead)
        assert np.array_equal(lat, np.where(wdead, -1, wlat))
        assert np.array_equal(bram, wbram)


def test_hetero_kernel_matches_reference():
    """The TPU path of the cross-design dispatch — the raw Pallas kernel
    on per-row tables and per-row bounds (interpreted here) — equals the
    vmapped jnp reference the CPU runs, row for row, deadlocks included."""
    from repro.core.backends import DEADLOCK, HeteroDispatcher
    from repro.core.backends.operands import stack_hetero
    from repro.core.simgraph import build_simgraph
    from repro.core.tracer import collect_trace
    from repro.designs.ddcf import flowgnn_pna, mult_by_2
    from repro.kernels.fifo_eval.ops import make_hetero_batched_eval

    designs = {"m2": mult_by_2(24), "pna": flowgnn_pna(n_nodes=12,
                                                       n_edges=30)}
    graphs = {k: build_simgraph(d, collect_trace(d))
              for k, d in designs.items()}
    disp = HeteroDispatcher(graphs, max_iters=64)
    rng = np.random.default_rng(5)
    entries = [(disp._ext[k], np.concatenate([
        np.maximum(g.upper_bounds, 2)[None, :],
        np.full((1, g.n_fifos), 2),
        np.maximum(2, (g.upper_bounds * rng.uniform(
            0.1, 1.0, (3, g.n_fifos))).astype(np.int64))]))
        for k, g in graphs.items()]
    batch = stack_hetero(entries)          # 10 rows: a partial row block
    ref = make_hetero_batched_eval(64, use_ref=True)(batch)
    got = make_hetero_batched_eval(64, use_ref=False)(batch)
    for a, b in zip(got, ref):
        assert np.array_equal(a, b)
    assert (ref[2] == DEADLOCK).any() and (ref[2] != DEADLOCK).any()


def test_result_store_summary_roundtrip(tmp_path):
    store = Campaign(_spec(designs=("gemm",), budget=40)).run()
    out = store.summary()
    assert out["n_tasks"] == 2
    assert set(out["tasks"]) == {"gemm:grouped_sa:s0",
                                 "gemm:grouped_random:s0"}
    for entry in out["tasks"].values():
        assert entry["hypervolume"] > 0
        assert entry["frontier"]
    path = store.save_json(str(tmp_path / "store.json"))
    import json
    with open(path) as f:
        assert json.load(f)["n_tasks"] == 2
