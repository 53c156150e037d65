"""The program's own spans and counters: ``fifo.*`` profiler spans at
the layer boundaries, the raw kernel's iteration counters and the
worklist time on ``BatchStats``, and the cross-design phases on
``HeteroStats``."""

import glob
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.config import EvalConfig
from repro.core.simgraph import build_simgraph
from repro.core.simulate import BatchedEvaluator
from repro.designs.ddcf import mult_by_2

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.parametrize("max_iters, escalates", [(40, True), (256, False)])
def test_batch_stats_count_the_raw_launch(max_iters, escalates):
    """After one evaluate on the raw kernel, the iteration counters hold
    the batch's lane 3 and the worklist time is positive exactly when
    rows escalated."""
    from repro.kernels.fifo_eval.fifo_eval import LANES, ROWS
    from repro.kernels.fifo_eval.ops import make_batched_eval
    g = build_simgraph(mult_by_2(24))
    u = np.asarray(g.upper_bounds)
    rng = np.random.default_rng(1)
    cfgs = np.unique(np.stack([u] + [rng.integers(2, np.maximum(3, u + 1))
                                     for _ in range(11)]), axis=0)
    ev = BatchedEvaluator(g, EvalConfig(backend="pallas", condense=None,
                                        max_iters=max_iters))
    ev.evaluate(cfgs)
    _, _, status, iters, _ = make_batched_eval(g, max_iters=max_iters)(
        cfgs)
    status, iters = np.asarray(status), np.asarray(iters)
    e_pad = ev._impl.ops.e_pad
    s = ev.stats
    assert s.raw_rows == cfgs.shape[0]
    assert s.raw_row_iters == iters.sum()
    assert s.raw_tile_iters == iters[::ROWS].sum() * (e_pad // LANES)
    assert s.raw_gather_fallbacks == 0
    assert s.n_fallbacks == (status == 2).sum()
    assert (s.n_fallbacks > 0) == escalates
    assert (s.worklist_s > 0) == escalates


@pytest.mark.parametrize("gather_k, walks", [(None, False), (1, True)])
def test_batch_stats_count_rows_that_walk_their_gathers(monkeypatch,
                                                        gather_k, walks):
    """``raw_gather_fallbacks`` counts the real rows whose block walked
    its gathers: none where the schedules fit their slots, every row
    where no output chunk of atax's several may keep more than one
    source chunk."""
    from repro.designs import make_design
    from repro.kernels.fifo_eval import fifo_eval as fe
    if gather_k is not None:
        monkeypatch.setattr(fe, "GATHER_K", gather_k)
    g = build_simgraph(make_design("atax"))
    u = np.asarray(g.upper_bounds)
    cfgs = np.stack([u, np.maximum(2, u // 2), np.full_like(u, 2)])
    ev = BatchedEvaluator(g, EvalConfig(backend="pallas", condense=None,
                                        max_iters=64))
    ev.evaluate(cfgs)
    assert ev.stats.raw_rows == 3
    assert ev.stats.raw_gather_fallbacks == (3 if walks else 0)


def test_hetero_stats_time_each_dispatch():
    from repro.core.backends import HeteroDispatcher
    from repro.designs import make_design
    graphs = {"m2": build_simgraph(mult_by_2(12)),
              "gemm": build_simgraph(make_design("gemm"))}
    hd = HeteroDispatcher(graphs, max_iters=64)
    items = [(k, np.asarray(g.upper_bounds)[None, :])
             for k, g in graphs.items()]
    seen = [(0.0, 0.0)]
    for _ in range(2):
        hd.dispatch(items)
        prep, wait = hd.stats.prep_s, hd.stats.wait_s
        assert prep > seen[-1][0] and wait > seen[-1][1]
        seen.append((prep, wait))
    assert hd.stats.n_dispatches == 2


def _spans(path):
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("fifo."):
                        out.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    return out


def test_profiler_trace_nests_the_program_spans(tmp_path):
    """A CPU profiler trace of one search holds ``fifo.search``, with
    ``fifo.evaluate`` inside it and ``fifo.wait`` inside that."""
    import jax
    from repro.core import FifoAdvisor
    adv = FifoAdvisor(mult_by_2(12), EvalConfig(backend="jax"))
    adv.run("grouped_sa", budget=16, seed=0)      # compile outside
    with jax.profiler.trace(str(tmp_path)):
        adv.run("grouped_sa", budget=16, seed=1)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = _spans(path)

    def inside(inner, outer):
        return [(s, e) for s, e in spans[inner]
                if any(a <= s and e <= b for a, b in spans[outer])]
    (search,) = spans["fifo.search"]
    assert spans["fifo.evaluate"] == inside("fifo.evaluate", "fifo.search")
    assert inside("fifo.wait", "fifo.evaluate")
    assert {"fifo.optimizer", "fifo.cache", "fifo.raw"} <= set(spans)


def test_dispatch_import_leaves_jax_out():
    """The numpy-only worker processes import the dispatch layer and its
    spans without importing jax."""
    code = ("import sys, repro.core.backends.dispatch, repro.core.spans; "
            "sys.exit('jax' in sys.modules)")
    env = dict(os.environ, PYTHONPATH=SRC)
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=120).returncode == 0


def test_span_adds_its_time_when_the_body_raises():
    from types import SimpleNamespace
    from repro.core.spans import span
    stats = SimpleNamespace(t=0.0)
    with pytest.raises(KeyError):
        with span("x", stats, "t"):
            {}["missing"]
    assert stats.t > 0
