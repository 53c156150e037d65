"""Programs compiled or loaded inside the window of campaign.fast10.mesh4."""

from bench.readers import compiles_in_window as read  # noqa: F401
