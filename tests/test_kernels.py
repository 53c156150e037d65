"""Pallas kernel validation: the kernel (interpreted on CPU) vs pure-jnp ref vs the
numpy worklist, swept over designs (event counts straddling the 128-lane
padding boundary), batch sizes, and FIFO widths (which flip the SRL/BRAM
read-latency path).  Results are integer-exact, so equality — not
allclose — is asserted.
"""

import numpy as np
import pytest

from repro.core.design import Design
from repro.core.simgraph import build_simgraph
from repro.core.config import EvalConfig
from repro.core.simulate import BatchedEvaluator, evaluate_np
from repro.designs.builder import map_stage, producer, sink, streams
from repro.designs.ddcf import mult_by_2
from repro.kernels.fifo_eval.ops import make_batched_eval


def tiny_chain(count=10, lanes=1, width=32):
    d = Design("tiny")
    a = streams(d, "a", lanes, width=width)
    b = streams(d, "b", lanes, width=width)
    producer(d, "p", a, [1.0] * count)
    map_stage(d, "m", a, b, count, ii=2, extra_delay=1)
    sink(d, "s", b, count)
    return d


DESIGNS = [
    ("tiny_sub128", lambda: tiny_chain(count=8)),          # E < 128 (pad)
    ("tiny_odd", lambda: tiny_chain(count=23, lanes=2)),   # E % 128 != 0
    ("wide64", lambda: tiny_chain(count=40, width=64)),    # BRAM rd-lat
    ("mult_by_2", lambda: mult_by_2(24)),                  # deadlocks
]


@pytest.mark.parametrize("name,factory", DESIGNS)
@pytest.mark.parametrize("batch", [1, 5, 8])
def test_kernel_matches_ref_and_worklist(name, factory, batch):
    d = factory()
    g = build_simgraph(d)
    rng = np.random.default_rng(hash(name) % 2**32)
    u = g.upper_bounds
    cfgs = np.stack([u, np.full(g.n_fifos, 2)] +
                    [rng.integers(2, np.maximum(3, u + 1))
                     for _ in range(max(batch - 2, 0))])[:batch]

    ev = BatchedEvaluator(g, EvalConfig(backend="numpy", max_iters=64))
    pallas_call = make_batched_eval(ev, max_iters=128)
    ref_call = make_batched_eval(ev, use_ref=True, max_iters=128)

    lat_p, bram_p, st_p, _, _ = pallas_call(cfgs)
    lat_r, bram_r, st_r, _, _ = ref_call(cfgs)
    np.testing.assert_array_equal(np.asarray(st_p), np.asarray(st_r))
    np.testing.assert_array_equal(np.asarray(bram_p), np.asarray(bram_r))
    np.testing.assert_allclose(np.asarray(lat_p), np.asarray(lat_r))

    for i in range(cfgs.shape[0]):
        lat_np, dead_np = evaluate_np(g, cfgs[i])
        if st_p[i] == 1:                      # DEADLOCK
            assert dead_np
        elif st_p[i] == 0:                    # CONVERGED
            assert not dead_np
            assert int(round(float(lat_p[i]))) == lat_np


def test_full_evaluator_pallas_backend_end_to_end():
    d = mult_by_2(24)
    g = build_simgraph(d)
    ev_np = BatchedEvaluator(g, EvalConfig(backend="numpy", max_iters=64))
    ev_pl = BatchedEvaluator(
        g, EvalConfig(backend="pallas", max_iters=128))
    rng = np.random.default_rng(3)
    cfgs = np.stack([rng.integers(2, 30, size=2) for _ in range(12)])
    a = ev_np.evaluate(cfgs)
    b = ev_pl.evaluate(cfgs)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("batch", [1, 3, 5, 8, 12])
def test_condensed_kernel_ragged_batches_exact(batch):
    """The fused condensed kernel pads ragged batches to its row block
    internally (shrinking the block for small escalation buckets); every
    batch size must reproduce the row-at-a-time results exactly."""
    from repro.core.condense import condense_auto
    from repro.designs import make_design
    from repro.kernels.fifo_eval.ops import make_condensed_eval

    g = build_simgraph(make_design("gemm"))
    cg = condense_auto(g)[0]
    fused = make_condensed_eval(cg, max_iters=64)
    assert fused is not None
    rng = np.random.default_rng(11)
    u = np.asarray(g.upper_bounds, dtype=np.int64)
    cfgs = np.stack([np.maximum(2, (u * rng.uniform(0.4, 1.0, g.n_fifos))
                                .astype(int)) for _ in range(12)])
    cfgs = cfgs[:batch].astype(np.int32)
    got = [np.asarray(x) for x in fused(cfgs)]
    for i in range(batch):
        solo = [np.asarray(x) for x in fused(cfgs[i:i + 1])]
        for a, b in zip(got, solo):
            np.testing.assert_array_equal(a[i:i + 1], b,
                                          err_msg=f"row {i} of {batch}")


def test_pallas_cascade_end_to_end_matches_numpy():
    """BatchedEvaluator(backend='pallas') with the auto cascade (fused
    aggressive rung + scan safe rung + raw backstop) equals the numpy
    ground truth on a deadlock-heavy design."""
    from repro.designs import make_design
    g = build_simgraph(make_design("gemm"))
    rng = np.random.default_rng(7)
    u = np.asarray(g.upper_bounds, dtype=np.int64)
    cfgs = np.stack([np.ones_like(u), u] +
                    [rng.integers(1, u + 1) for _ in range(6)])
    a = BatchedEvaluator(
        g, EvalConfig(backend="numpy", max_iters=64)).evaluate(cfgs)
    b = BatchedEvaluator(
        g, EvalConfig(backend="pallas", max_iters=64)).evaluate(cfgs)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_kernel_iteration_cap_reports_unresolved_not_wrong():
    """With a tiny iteration cap the kernel must mark rows UNRESOLVED
    (status 2) rather than return a wrong latency as CONVERGED."""
    d = mult_by_2(32)
    g = build_simgraph(d)
    ev = BatchedEvaluator(g, EvalConfig(backend="numpy", max_iters=64))
    call = make_batched_eval(ev, max_iters=2)
    cfgs = np.array([[40, 2], [2, 2]])
    lat, _, st, _, _ = call(cfgs)
    for i in range(2):
        if st[i] == 0:
            lat_np, dead_np = evaluate_np(g, cfgs[i])
            assert not dead_np and int(round(float(lat[i]))) == lat_np
        else:
            assert st[i] in (1, 2)


def test_kernel_iteration_lane_is_the_block_max_of_the_reference():
    """Lane 3 of the raw kernel's output is its 8-row block's Jacobi
    iteration count: the largest of the jnp reference's per-row counts
    over that block, on a batch mixing converged, deadlocked and capped
    rows (mult_by_2(24) deadlocks below depth 23 on x)."""
    g = build_simgraph(mult_by_2(24))
    max_iters = 40
    u = np.asarray(g.upper_bounds)
    rng = np.random.default_rng(0)
    pool = np.stack([u] + [rng.integers(2, np.maximum(3, u + 1))
                           for _ in range(40)])
    ref_call = make_batched_eval(g, use_ref=True, max_iters=max_iters)
    pool_status = np.asarray(ref_call(pool)[2])
    conv, dead, capped = (pool[pool_status == s] for s in (0, 1, 2))
    assert len(conv) >= 3 and len(dead) >= 8 and len(capped) >= 2
    # block 0 holds no capped row, so its count stays below the cap;
    # block 1 holds one; the last block is ragged
    cfgs = np.concatenate([conv[:2], dead[:6], capped[:1], conv[2:3],
                           dead[6:8], capped[1:2]])
    _, _, st_r, it_r, _ = ref_call(cfgs)
    _, _, st_p, it_p, _ = make_batched_eval(g, max_iters=max_iters)(cfgs)
    np.testing.assert_array_equal(np.asarray(st_p), np.asarray(st_r))
    it_p, it_r = np.asarray(it_p), np.asarray(it_r)
    for b in range(0, cfgs.shape[0], 8):
        assert set(it_p[b:b + 8]) == {it_r[b:b + 8].max()}, b
    assert it_p[0] < max_iters == it_p[8]


def _gathers(src, idx):
    """The walk and the replayed schedule of one (8, N) block on the
    Pallas interpreter: ``(walked, replayed, fit)``; ``replayed`` is
    zeros where the schedule did not fit its slots."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from repro.kernels.fifo_eval import fifo_eval as fe

    def kernel(src_ref, idx_ref, walk_ref, replay_ref, fit_ref, sched_ref):
        fe._gather(src_ref, idx_ref, walk_ref)
        fit = fe._schedule(src_ref.shape[0], src_ref.shape[1] // fe.LANES,
                           idx_ref, sched_ref)
        replay_ref[...] = jnp.zeros(replay_ref.shape, jnp.float32)

        @pl.when(fit)
        def _():
            fe._replay(src_ref, idx_ref, replay_ref, sched_ref)
        fit_ref[...] = jnp.full(fit_ref.shape, fit.astype(jnp.int32))

    n = idx.shape[1]
    walked, replayed, fit = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((8, n), jnp.float32)] * 2
        + [jax.ShapeDtypeStruct((8, fe.LANES), jnp.int32)],
        scratch_shapes=[fe.schedule_scratch(n)],
        interpret=True,
    )(jnp.asarray(src), jnp.asarray(idx))
    return np.asarray(walked), np.asarray(replayed), bool(fit[0, 0])


@pytest.mark.parametrize("table, slack", [
    ("shared", None), ("per_row", None),
    ("per_row", 0),          # the widest chunk fills its slots exactly
    ("per_row", -1),         # ... and has one source more than its slots
])
def test_replayed_gather_equals_the_walk(monkeypatch, table, slack):
    """The schedule found once and replayed gives the walk's gather bit
    for bit (on every f32 pattern, NaNs included) for a shared (1, E) and
    a per-row (8, E) index table; where an output chunk names more
    source chunks than its slots the schedule reports that it does not
    fit, and the walk still gathers exactly."""
    from repro.kernels.fifo_eval import fifo_eval as fe
    rng = np.random.default_rng(5)
    e = 6 * fe.LANES
    src = rng.integers(0, 2**32, size=(8, e), dtype=np.uint64).astype(
        np.uint32).view(np.float32)
    # each output chunk reads a few source chunks near its own, as the
    # trace-ordered tables do
    near = np.arange(e) // fe.LANES
    rows = 1 if table == "shared" else 8
    chunk = np.clip(near + rng.integers(-2, 3, size=(rows, e)), 0, 5)
    idx = (chunk * fe.LANES
           + rng.integers(0, fe.LANES, size=(rows, e))).astype(np.int32)
    widest = max(len(np.unique(c)) for c in
                 np.split(chunk, e // fe.LANES, axis=1))
    assert 1 < widest < fe.GATHER_K
    if slack is not None:
        monkeypatch.setattr(fe, "GATHER_K", widest + slack)
    walked, replayed, fit = _gathers(src, idx)
    want = np.take_along_axis(src, np.broadcast_to(idx, (8, e)), axis=1)
    np.testing.assert_array_equal(walked.view(np.uint32),
                                  want.view(np.uint32))
    assert fit == (slack != -1)
    if fit:
        np.testing.assert_array_equal(replayed.view(np.uint32),
                                      want.view(np.uint32))


@pytest.mark.parametrize("design, gather_k, replays", [
    ("k15mmtree_relu", None, True),
    ("atax", None, True),
    ("atax", 1, False),      # every block walks its gathers
])
def test_raw_kernel_replay_matches_reference(monkeypatch, design, gather_k,
                                             replays):
    """The raw kernel on rows with random depths returns the jnp
    reference's latency and status and, per 8-row block, the largest of
    its iteration counts, whether its blocks replay their gather
    schedules or (with too few slots) walk them; the replayed lane says
    which."""
    from repro.designs import make_design
    from repro.kernels.fifo_eval import fifo_eval as fe
    if gather_k is not None:
        monkeypatch.setattr(fe, "GATHER_K", gather_k)
    g = build_simgraph(make_design(design))
    u = np.asarray(g.upper_bounds)
    rng = np.random.default_rng(2)
    cfgs = np.stack([u] + [rng.integers(2, np.maximum(3, u + 1))
                           for _ in range(8)]).astype(np.int32)
    lat_r, _, st_r, it_r, rep_r = make_batched_eval(
        g, use_ref=True, max_iters=256)(cfgs)
    lat_p, _, st_p, it_p, rep_p = make_batched_eval(
        g, max_iters=256)(cfgs)
    np.testing.assert_array_equal(np.asarray(lat_p), np.asarray(lat_r))
    np.testing.assert_array_equal(np.asarray(st_p), np.asarray(st_r))
    for b in range(0, cfgs.shape[0], 8):
        assert set(it_p[b:b + 8]) == {it_r[b:b + 8].max()}, b
    assert set(np.asarray(rep_p)) == {replays}
    assert not np.asarray(rep_r).any()
