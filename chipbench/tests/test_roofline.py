"""Work and byte counters against hand counts, and the peak table."""

import numpy as np
import pytest

from bench import instrument, roofline


def _pipe(n: int, width: int = 32):
    """load -> fifo x -> store, ``n`` items: 2n events, one FIFO."""
    from repro.core.design import Design
    d = Design("pipe")
    d.fifo("x", width=width)

    def load(ctx):
        for i in range(n):
            yield ctx.delay(1)
            yield ctx.write("x", i)

    def store(ctx):
        for _ in range(n):
            yield ctx.delay(1)
            yield ctx.read("x")
    d.add_task("load", load)
    d.add_task("store", store)
    return d


def test_hand_counts_raw_dispatch():
    from repro.core import FifoAdvisor
    from repro.core.config import EvalConfig
    adv = FifoAdvisor(_pipe(5), EvalConfig(backend="pallas", condense=None))
    assert adv.graph.n_events == 10 and adv.graph.n_fifos == 1
    tallies = instrument.Tallies()
    instrument.instrument_evaluator(adv.evaluator, tallies)
    adv.evaluator.evaluate(np.array([[2], [3], [4]]))
    raw = tallies[instrument.RAW]
    # 10 events pad to one 128-lane vector; six tables of 128 words
    # once, and per row one depth word in and a 128-word result out
    assert (raw.dispatches, raw.rows) == (1, 3)
    assert raw.bytes == 6 * 128 * 4 + 3 * (1 * 4 + 128 * 4)
    # per row: an add and a max for each of 10 events and 10 cross edges
    assert raw.work == 3 * 2 * (10 + 10)


def test_hand_counts_cross_design():
    t = roofline.KernelTally()
    t.add(4, 300, 300, 7, per_row_tables=True)
    t.add(2, 100, 300, 7, per_row_tables=True, new_dispatch=False)
    assert (t.dispatches, t.rows) == (1, 6)
    # 300 events pad to 384 lanes; every row reads its own six tables
    assert t.bytes == 6 * (7 * 4 + 128 * 4 + 6 * 384 * 4)
    assert t.work == 4 * 4 * 300 + 2 * 4 * 100


def test_padded_rows_are_not_counted():
    m = np.array([[1, 2], [3, 4], [5, 6], [5, 6], [5, 6]])
    assert instrument.real_rows(m) == 3
    assert instrument.real_rows(m[:1]) == 1


def test_share_of_hbm_bound():
    t = roofline.KernelTally(dispatches=1, rows=1, bytes=int(819e9))
    assert roofline.share(t, 2.0, "TPU v5 lite") == pytest.approx(50.0)
    assert roofline.share(t, 0.0, "TPU v5 lite") is None


def test_unknown_device_kind_raises():
    t = roofline.KernelTally(dispatches=1, rows=1, bytes=1)
    with pytest.raises(KeyError, match="TPU v99"):
        roofline.share(t, 1.0, "TPU v99")
