"""Device ns of fifo_eval_raw per f32 vreg tile stepped once in
k15mmtree_relu.random (BatchStats raw_tile_iters: each 8-row block's
iterations times E_pad / 128)."""

from bench.program import ns_per_tile_iter as read  # noqa: F401
