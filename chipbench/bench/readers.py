"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Each reader takes the run's view (``window_s``, ``counters``,
``tallies``, ``trace``, ``device_kind``, ``compiles_in_window``) and
returns a number, or None when the run holds nothing to read.  Shares
are percentages.
"""

from __future__ import annotations

from typing import Optional

from bench import roofline


def idle_share(run) -> Optional[float]:
    """Share of the traced window in which no op ran on the device,
    the mean over the devices used."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share


def compiles_in_window(run) -> float:
    """Programs compiled, or loaded from the persistent cache, inside
    the window."""
    return run.compiles_in_window


def _solved_rows(run) -> int:
    c = run.counters
    return c["n_configs"] - c["n_dedup"]


def rung_share(run, counter: str) -> Optional[float]:
    """``counter`` rows over the distinct rows the evaluator solved."""
    rows = _solved_rows(run)
    return 100.0 * run.counters[counter] / rows if rows else None


def kernel_roofline(run, kernel: str) -> Optional[float]:
    """The kernel's share of the HBM roofline (``bench.roofline``)."""
    if run.trace is None:
        return None
    return roofline.share(run.tallies.get(kernel, roofline.KernelTally()),
                          run.trace.kernel_s.get(kernel, 0.0),
                          run.device_kind)


def per_search(run, value: float) -> Optional[float]:
    n = run.counters.get("searches", 0)
    return value / n if n else None


def host_s_per_search(run) -> Optional[float]:
    """Window time outside the evaluator's calls, per search."""
    return per_search(run, run.window_s - run.counters["wall_s"])


def dispatches_per_search(run) -> Optional[float]:
    return per_search(run, sum(run.counters["dispatches"].values()))


def rows_per_dispatch(run) -> Optional[float]:
    c = run.counters
    n = c["hetero_n_dispatches"]
    return c["hetero_n_rows"] / n if n else None


def h2d_mb_per_dispatch(run) -> Optional[float]:
    """Host bytes stacked for the device per cross-design dispatch: the
    padded rows times one row's depth row and own tables."""
    c = run.counters
    n = c["hetero_n_dispatches"]
    if not n:
        return None
    rows = c["hetero_n_rows"] + c["hetero_n_pad_rows"]
    return rows * c["row_bytes"] / n / 1e6
