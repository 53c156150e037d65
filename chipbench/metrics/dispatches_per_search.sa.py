"""Device dispatches (every DISPATCH_COUNTS kind) per grouped_sa search."""

from bench.readers import dispatches_per_search as read  # noqa: F401
