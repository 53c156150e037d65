"""Host spans and kernel tallies, added from outside the program.

The harness never edits a program file.  It wraps methods of the objects
it holds (an evaluator, its rungs, a cross-design dispatcher) so that
each call

* opens a ``jax.profiler.TraceAnnotation`` named ``chipbench.<layer>``,
  which the trace reduction uses to say what the host was doing while
  the device idled, and
* adds to a :class:`~bench.roofline.KernelTally` the rows, bytes and work
  of each kernel dispatch, counted from shapes.

Annotations cost a few microseconds a call whether or not a trace is
being recorded; they are on in every run, so that traced and untraced
runs execute the same code.
"""

from __future__ import annotations

import functools
from typing import Dict

import numpy as np

from bench.roofline import KernelTally

RAW = "fifo_eval_raw"
CONDENSED = "fifo_eval_condensed"


def span(name: str, fn):
    import jax

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation("chipbench." + name):
            return fn(*args, **kwargs)
    return wrapped


def wrap(obj, attr: str, name: str, before=None):
    """Replace ``obj.attr`` by a spanned call; ``before(*args)`` runs
    first (to tally what the call dispatches)."""
    fn = getattr(obj, attr)
    spanned = span(name, fn)
    if before is None:
        setattr(obj, attr, spanned)
        return

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        before(*args, **kwargs)
        return spanned(*args, **kwargs)
    setattr(obj, attr, wrapped)


def real_rows(m: np.ndarray) -> int:
    """Rows of a bucket-padded batch of unique rows: padding repeats the
    last real row, so trailing copies of the final row are not real."""
    m = np.asarray(m)
    n = m.shape[0]
    while n > 1 and np.array_equal(m[n - 2], m[-1]):
        n -= 1
    return n


class Tallies(dict):
    """Kernel name -> :class:`KernelTally`."""

    def __missing__(self, key):
        self[key] = KernelTally()
        return self[key]


def instrument_evaluator(ev, tallies: Tallies) -> None:
    """Spans over a ``BatchedEvaluator``'s layers and tallies of its
    kernel dispatches: fused rungs run ``fifo_eval_condensed``, other
    rungs and the raw backstop run ``fifo_eval_raw``."""
    e_raw, n_fifos = ev.g.n_events, ev.g.n_fifos
    wrap(ev, "evaluate", "evaluate")
    if ev._cascade is not None:
        wrap(ev._cascade, "evaluate", "cascade")
    for cg, impl in ev.condensation:
        def tally(kernel, events, padded):
            def count(m, *a, **k):
                rows = real_rows(m) if padded else np.asarray(m).shape[0]
                tallies[kernel].add(rows, e_raw, events, n_fifos)
            return count
        if impl.fused_certificate:
            wrap(impl, "evaluate_certified", f"rung.{cg.tag}",
                 tally(CONDENSED, cg.n_events, True))
        else:
            wrap(impl, "evaluate_with_times", f"rung.{cg.tag}",
                 tally(RAW, cg.n_events, impl.wants_bucketing))
    if ev._impl is not ev._worklist:
        wrap(ev._impl, "evaluate", "raw",
             lambda m, *a, **k: tallies[RAW].add(
                 real_rows(m) if ev._impl.wants_bucketing
                 else np.asarray(m).shape[0], e_raw, e_raw, n_fifos))
    wrap(ev._worklist, "evaluate", "worklist")


def instrument_hetero(hd, tallies: Tallies, raw_events: Dict[str, int]
                      ) -> None:
    """Spans and tallies over a ``HeteroDispatcher``: every row carries
    its own tables at the envelope's width."""
    import repro.core.backends.operands as operands

    def count(items):
        for i, (key, m) in enumerate(items):
            tallies[RAW].add(np.atleast_2d(m).shape[0], raw_events[key],
                             hd.e_pad, hd.f_max, per_row_tables=True,
                             new_dispatch=i == 0)
    wrap(hd, "dispatch", "hetero.dispatch", count)
    wrap(hd, "_call", "hetero.device")
    for wl in hd.worklists.values():
        if not getattr(wl, "_chipbench", False):
            wrap(wl, "evaluate", "worklist")
            wl._chipbench = True
    if not getattr(operands.stack_hetero, "_chipbench", False):
        operands.stack_hetero = span("hetero.stack", operands.stack_hetero)
        operands.stack_hetero._chipbench = True
