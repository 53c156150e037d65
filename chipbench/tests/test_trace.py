"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``): two rounds of a fused condensed batch, a raw
batch and a 20 ms host pause, inside one ``chipbench.window`` span."""

import os

import pytest

from bench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "trace.xplane.pb")


def _events():
    """Device op intervals and host spans, read independently of
    ``bench.trace``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(FIXTURE)
    ops, spans = [], {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((e.name, e.start_ns, e.end_ns))
                if e.name.startswith("chipbench."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns))
    return ops, spans


@pytest.fixture(scope="module")
def red():
    return trace.reduce(FIXTURE, n_devices=1)


def test_window_is_the_span(red):
    _, spans = _events()
    (lo, hi), = spans["chipbench.window"]
    assert red.window_s == pytest.approx((hi - lo) / 1e9)


def test_busy_is_the_union_of_op_intervals(red):
    ops, spans = _events()
    (lo, hi), = spans["chipbench.window"]
    # sweep over interval end points, counting open ops
    points = sorted([(max(s, lo), 1) for _, s, e in ops if e > lo and s < hi]
                    + [(min(e, hi), -1) for _, s, e in ops
                       if e > lo and s < hi])
    busy, open_, t0 = 0.0, 0, None
    for t, d in points:
        if open_ == 0 and d == 1:
            t0 = t
        open_ += d
        if open_ == 0:
            busy += t - t0
    assert red.busy_s == pytest.approx(busy / 1e9)
    assert 0 < red.busy_s < red.window_s
    assert red.busy_by_device == [red.busy_s]


def test_kernel_time_by_op_name(red):
    ops, _ = _events()
    for kernel in ("fifo_eval_raw", "fifo_eval_condensed"):
        mine = [e - s for name, s, e in ops
                if name.startswith(f"%{kernel}.") or
                name.startswith(f"%{kernel} ")]
        assert len(mine) == 2
        assert red.kernel_s[kernel] == pytest.approx(sum(mine) / 1e9)
    assert red.top_ops[0][1] >= red.top_ops[-1][1]
    assert {"fifo_eval_raw", "fifo_eval_condensed"} <= \
        {name for name, _ in red.top_ops}


def test_idle_gaps_charged_to_host_spans(red):
    gaps = dict(red.idle_gaps)
    # the two 20 ms host pauses leave the device idle under their span
    assert 0.04 <= gaps["chipbench.pause"] < 0.06
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)


def test_union_and_op_name():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
    assert trace.op_name("%fifo_eval_raw.12 = f32[8,128] custom-call(x)") \
        == "fifo_eval_raw"
    assert trace.op_name("%copy.3 = f32[8] copy(%fifo_eval_raw.1)") == "copy"
