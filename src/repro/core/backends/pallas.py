"""Pallas-kernel backend: condensation-native evaluation behind the
shared operand/dispatch machinery (Mosaic-compiled on a TPU, interpreted
on the CPU — :func:`repro.kernels.fifo_eval.fifo_eval.interpret_for`).

Two kernels back this registry entry, selected by what ``prepare`` is
given (the rung cascade spawns one backend per rung via
``EvalBackend.spawn()`` and prepares it on that rung's graph):

* a **CondensedGraph** selects the fused mega-kernel
  (:mod:`repro.kernels.fifo_eval.condensed`): row-blocked condensed
  tiles through VMEM, fixpoint + exactness certificate in ONE launch,
  ``evaluate_certified`` exposed to the cascade so accepted/escalated
  rows never ship event times to the host;
* a raw **SimGraph** keeps the 8-row-block Hillis-Steele kernel
  (:mod:`repro.kernels.fifo_eval.fifo_eval`) as the backstop engine.
"""

from __future__ import annotations

from repro.core.backends.base import register_backend
from repro.core.backends.fixpoint import _ScanBackend


@register_backend
class PallasBackend(_ScanBackend):
    """The :mod:`repro.kernels.fifo_eval` kernels (see module docstring).

    Raw graphs launch one grid program per 8-row block, so bucket
    padding buys little there — bucketing is disabled.  The fused
    condensed path buckets anyway (inside the cascade): its row-blocked
    grid is batch-shaped, so jit-cache reuse pays exactly like the scan
    backends.
    """

    name = "pallas"
    use_ref = False
    wants_bucketing = False
