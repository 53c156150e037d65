"""Programs compiled or loaded inside the window of k15mmtree_relu.random."""

from bench.readers import compiles_in_window as read  # noqa: F401
