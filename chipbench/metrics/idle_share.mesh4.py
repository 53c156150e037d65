"""Device idle share of the traced window in campaign.fast10.mesh4, the mean
over the four devices."""

from bench.readers import idle_share as read  # noqa: F401
