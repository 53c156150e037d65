"""Device idle share of the traced window in k15mmtree_relu.sa."""

from bench.readers import idle_share as read  # noqa: F401
