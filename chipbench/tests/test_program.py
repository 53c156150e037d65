"""The readers of the program's own counters, and the idle gaps charged
to its ``fifo.`` spans, on synthetic spans and on the recorded trace."""

import os
from types import SimpleNamespace

import pytest

from bench import program, trace
from bench.spec import Benchmark

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "trace.xplane.pb")


def test_gaps_go_to_the_innermost_program_span():
    spans = [("chipbench.search", 0, 100), ("fifo.search", 1, 99),
             ("fifo.evaluate", 10, 50), ("fifo.worklist", 20, 40),
             ("chipbench.worklist", 21, 39), ("fifo.optimizer", 60, 70)]
    gaps = [(12, 14), (22, 30), (62, 66), (80, 82), (99.5, 100)]  # in order
    assert program.charge(gaps, spans) == {
        "fifo.worklist": 8, "fifo.evaluate": 2, "fifo.optimizer": 4,
        "fifo.search": 2, program.OUTSIDE: 0.5}


def test_recorded_trace_reduces_as_before():
    """The harness's reduction of the recorded trace is what it was
    before the program had spans; that trace holds no ``fifo.`` span,
    so all of its idle time is outside them."""
    red = trace.reduce(FIXTURE, n_devices=1)
    assert red.window_s == pytest.approx(0.054873209)
    assert red.busy_s == pytest.approx(0.005318553)
    assert red.kernel_s == pytest.approx(
        {"fifo_eval_raw": 0.002193006, "fifo_eval_condensed": 0.000232995})
    assert [n for n, _ in red.idle_gaps] == [
        "chipbench.pause", "chipbench.rung.aggressive", "chipbench.raw"]
    assert [v for _, v in red.idle_gaps] == pytest.approx(
        [0.045448592, 0.004106018, 4.6e-08])
    gaps = program.program_idle_gaps(FIXTURE, n_devices=1)
    assert [n for n, _ in gaps] == [program.OUTSIDE]
    assert gaps[0][1] == pytest.approx(red.window_s - red.busy_s)


def _run(counters, kernel_s=None):
    red = (None if kernel_s is None else
           SimpleNamespace(kernel_s={"fifo_eval_raw": kernel_s}))
    return SimpleNamespace(counters=counters, trace=red)


NEW = ["raw_iters_per_row.random", "raw_iters_per_row.sa",
       "raw_ns_per_tile_iter.random", "raw_ns_per_tile_iter.sa",
       "worklist_ms_per_row.random", "hetero_prep_ms_per_dispatch.mesh4",
       "hetero_wait_ms_per_dispatch.mesh4"]


@pytest.mark.parametrize("metric", NEW)
def test_new_readers_read_nothing_from_an_older_program(metric):
    """Counters the program did not keep yet read as None, not an
    error, so a traced run of an older program leaves the metric out."""
    old = {"n_fallbacks": 5, "hetero_n_dispatches": 3, "searches": 1}
    assert Benchmark().reader(metric)(_run(old, kernel_s=1.0)) is None


def test_new_readers_arithmetic():
    bm = Benchmark()
    c = {"raw_rows": 16, "raw_row_iters": 4000, "raw_tile_iters": 2 * 10**6,
         "worklist_s": 0.3, "n_fallbacks": 30, "hetero_prep_s": 1.5,
         "hetero_wait_s": 0.25, "hetero_n_dispatches": 50}
    run = _run(c, kernel_s=0.01)
    assert bm.reader("raw_iters_per_row.sa")(run) == 250
    assert bm.reader("raw_ns_per_tile_iter.random")(run) == \
        pytest.approx(5.0)
    assert bm.reader("worklist_ms_per_row.random")(run) == \
        pytest.approx(10.0)
    assert bm.reader("hetero_prep_ms_per_dispatch.mesh4")(run) == \
        pytest.approx(30.0)
    assert bm.reader("hetero_wait_ms_per_dispatch.mesh4")(run) == \
        pytest.approx(5.0)
    assert bm.reader("raw_ns_per_tile_iter.sa")(_run(c)) is None
