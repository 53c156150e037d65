"""Real rows per cross-design dispatch (HeteroStats n_rows / n_dispatches)."""

from bench.readers import rows_per_dispatch as read  # noqa: F401
