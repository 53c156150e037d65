"""Seconds per grouped_sa search spent outside the evaluator's calls
(window minus BatchStats.wall_s): the optimizer and advisor layer."""

from bench.readers import host_s_per_search as read  # noqa: F401
