"""The reader of the rows the worklist lanes solved."""

from types import SimpleNamespace

import pytest

from bench.spec import Benchmark

METRIC = "worklist_lane_share.random"


def test_lane_share_reads_nothing_from_an_older_program():
    run = SimpleNamespace(counters={"n_fallbacks": 120, "worklist_s": 1.1})
    assert Benchmark().reader(METRIC)(run) is None


def test_lane_share_arithmetic():
    read = Benchmark().reader(METRIC)
    run = SimpleNamespace(counters={"n_fallbacks": 120,
                                    "worklist_lane_rows": 110})
    assert read(run) == pytest.approx(100.0 * 110 / 120)
    run.counters["worklist_lane_rows"] = 0
    assert read(run) == 0.0
    run.counters["n_fallbacks"] = 0
    assert read(run) is None
