"""MB of per-row tables and depth rows stacked for the device per
cross-design dispatch, padded rows included."""

from bench.readers import h2d_mb_per_dispatch as read  # noqa: F401
