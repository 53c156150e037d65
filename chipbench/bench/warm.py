"""Warm-up: compile, before the window, every program it can reach."""

from __future__ import annotations

import numpy as np


def evaluator(ev, max_rows: int, row: np.ndarray) -> None:
    """Every program a ``BatchedEvaluator`` batch of up to ``max_rows``
    unique rows can reach: each rung's and the raw kernel's batch shapes,
    per bucket where the path pads to buckets, else per row count.  Each
    program runs once on copies of ``row``; its other shapes are compiled
    ahead of time, which puts them in the same caches a call looks in."""
    import jax
    import jax.numpy as jnp
    policy = ev.dispatch
    top = policy.bucket_size(max_rows) or max_rows
    buckets = [b for b in policy.buckets if b <= top]
    every = list(range(1, max_rows + 1))
    n_fifos = ev.g.n_fifos

    def tile(c):
        return np.repeat(np.asarray(row, dtype=np.int64)[None, :], c, 0)

    def ahead(call, sizes):
        for c in sizes:
            call.run.lower(jax.ShapeDtypeStruct((c, n_fifos),
                                                jnp.int32)).compile()
    for _, impl in ev.condensation:
        sizes = buckets if (impl.fused_certificate
                            or impl.wants_bucketing) else every
        if impl.fused_certificate:
            impl.evaluate_certified(tile(sizes[0]))
            ahead(impl._fused, sizes[1:])
        else:
            impl.evaluate_with_times(tile(sizes[0]))
            ahead(impl._call_times, sizes[1:])
    sizes = buckets if ev._impl.wants_bucketing else every
    ev._impl.evaluate(tile(sizes[0]))
    ahead(ev._impl._call, sizes[1:])


def hetero(hd, max_rows: int, row: np.ndarray, design: str) -> None:
    """The cross-design program for every bucket a round of up to
    ``max_rows`` rows can reach, on copies of ``design``'s ``row``
    stacked once and repeated on the device."""
    import jax.numpy as jnp
    from repro.core.backends.operands import stack_hetero
    one = stack_hetero([(hd._ext[design], np.asarray(row)[None, :])])
    k = hd.shard_multiple
    top = next((b for b in hd.buckets if b >= max_rows), max_rows)
    for b in sorted({-(-b // k) * k for b in hd.buckets if b <= top}):
        hd._call({key: jnp.repeat(jnp.asarray(v), b, axis=0)
                  for key, v in one.items()})


def stacked_row_bytes(hd, row: np.ndarray, design: str) -> int:
    """Host bytes one row adds to a cross-design batch: its depth row
    and its own event tables at the envelope's width."""
    from repro.core.backends.operands import stack_hetero
    one = stack_hetero([(hd._ext[design], np.asarray(row)[None, :])])
    return int(sum(v.nbytes for v in one.values()))
