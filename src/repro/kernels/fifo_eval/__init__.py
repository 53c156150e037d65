"""Batched FIFO-configuration latency evaluation (Pallas TPU kernel).

``fifo_eval.py``  raw pl.pallas_call kernel (BlockSpec VMEM tiling, one
                  grid program per 8-row block of configurations).
``condensed.py``  fused condensed kernel: fixpoint + exactness certificate.
``ops.py``        jit'd wrapper: SimGraph -> padded event tensors -> kernel.
``ref.py``        pure-jnp oracle with identical semantics.
"""

from repro.kernels.fifo_eval.fifo_eval import fifo_eval_pallas
from repro.kernels.fifo_eval.ops import make_batched_eval
from repro.kernels.fifo_eval.ref import fifo_eval_ref

__all__ = ["fifo_eval_pallas", "fifo_eval_ref", "make_batched_eval"]
