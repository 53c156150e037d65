"""The readers of the raw kernel's replayed gather schedules."""

from types import SimpleNamespace

import pytest

from bench.spec import Benchmark

METRICS = ["raw_replay_share.random", "raw_replay_share.sa"]


@pytest.mark.parametrize("metric", METRICS)
def test_replay_share_reads_nothing_from_an_older_program(metric):
    run = SimpleNamespace(counters={"raw_rows": 16, "n_fallbacks": 5})
    assert Benchmark().reader(metric)(run) is None


@pytest.mark.parametrize("metric", METRICS)
def test_replay_share_arithmetic(metric):
    read = Benchmark().reader(metric)
    run = SimpleNamespace(counters={"raw_rows": 64,
                                    "raw_gather_fallbacks": 16})
    assert read(run) == pytest.approx(75.0)
    run.counters["raw_gather_fallbacks"] = 0
    assert read(run) == 100.0
    run.counters["raw_rows"] = 0
    assert read(run) is None
