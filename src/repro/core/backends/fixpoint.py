"""Batched fixpoint backends: jit/vmap max-plus scan and the Pallas kernel.

Both compute event times as the least fixpoint of a monotone max-plus map;
each Jacobi step is

    cross-edge gathers (data edges + depth-dependent back-pressure)
    -> segmented max-plus *associative scan* along each task's ops

vmapped over a batch of candidate depth vectors and jit-compiled.  A true
deadlock is a positive cycle: iterates grow strictly, provably never
converging; rows are flagged DEADLOCK as soon as any time exceeds the
design's schedule upper bound, and anything still unresolved at the
iteration cap is reported UNRESOLVED for the dispatch policy to escalate to
the worklist arbiter.

The two backends share all operand preparation
(:mod:`repro.core.backends.operands`) and the whole jit wrapper
(:func:`repro.kernels.fifo_eval.ops.make_batched_eval`); they differ only
in the inner fixpoint implementation:

``FixpointBackend``  ``lax.associative_scan`` + ``lax.while_loop`` in stock
                     jnp (the TPU-native formulation, DESIGN.md §6)
``PallasBackend``    the hand-rolled Hillis-Steele kernel in
                     :mod:`repro.kernels.fifo_eval` (Mosaic-compiled on a
                     TPU, interpreted on the CPU)

Numeric domain: times are exact in float32 while below 2**24; the façade
asserts the design's schedule upper bound stays below ~1.5e7 cycles.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.core.simgraph import SimGraph

from repro.core.backends.base import EvalBackend, register_backend
from repro.core.backends.operands import get_operands

#: minimum condensation ratio for a kernel backend to fuse the
#: certificate into the evaluation launch (aggressive rungs run 25-150x;
#: the 2-3x safe rung keeps the scan path + host verifier)
FUSED_MIN_COMPRESSION = 8.0


class _ScanBackend(EvalBackend):
    """Common wrapper: shared operands + one jitted batched callable."""

    use_ref = True
    wants_bucketing = True
    #: a jax.sharding.Mesh to shard the config-row axis over (None = solo
    #: jit on the default device); set by the MeshBackend subclass
    mesh = None
    #: the Pallas raw kernel's iteration and replayed lanes for each row
    #: of the last ``evaluate`` / ``evaluate_with_times`` batch, and that
    #: launch's vreg-tile iterations (see :meth:`_note_iters`); None on
    #: the jnp reference, which runs no kernel blocks
    last_iters = None
    last_replayed = None
    last_tile_iters = 0

    @property
    def shard_multiple(self) -> int:
        """Row counts must be a multiple of this (the mesh size)."""
        return int(self.mesh.devices.size) if self.mesh is not None else 1

    def _pad_shards(self, m: np.ndarray) -> Tuple[np.ndarray, int]:
        """Pad rows (repeating the last) to a shard multiple; returns the
        padded matrix and the real row count to slice results back to."""
        c = m.shape[0]
        k = self.shard_multiple
        if k > 1 and c % k:
            m = np.concatenate([m, np.repeat(m[-1:], k - c % k, axis=0)])
        return m, c

    def prepare(self, g: SimGraph):
        from repro.kernels.fifo_eval.ops import (make_batched_eval,
                                                 make_condensed_eval)
        self.g = g
        self.ops = get_operands(g)
        self._call = make_batched_eval(
            g, use_ref=self.use_ref, max_iters=self.max_iters,
            mesh=self.mesh)
        self._call_times = None
        # kernel-backed backends prepared on a CondensedGraph fuse the
        # exactness certificate into the evaluation launch (the rung
        # cascade then never ships event times to the host); the jnp
        # scan reference keeps the host verifier as the cross-check.
        # Fusion only pays on high-compression rungs where the condensed
        # tiles are narrow — low-compression rungs (the 2-3x safe rung)
        # stream nearly raw-width tiles per row block, so they stay on
        # the scan path where the host verifier's cost is bounded by the
        # few escalated rows that reach them.
        self._fused = None
        if not self.use_ref:
            from repro.core.condense import CondensedGraph
            if (isinstance(g, CondensedGraph)
                    and g.compression >= FUSED_MIN_COMPRESSION):
                self._fused = make_condensed_eval(
                    g, max_iters=self.max_iters, mesh=self.mesh)
        return self.ops

    @property
    def fused_certificate(self) -> bool:
        return getattr(self, "_fused", None) is not None

    def evaluate_certified(self, depth_matrix: np.ndarray
                           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                      np.ndarray]:
        """(C, F) depths -> (latency i64, bram i64, status i8, cert bool)
        in ONE device dispatch: the kernel evaluates the condensed
        fixpoint and checks every folded cross constraint in the same
        launch (``verify_rows`` semantics — cert is True only on
        CONVERGED rows whose expansion is provably the raw least
        fixpoint).  Only valid when :attr:`fused_certificate`."""
        m = np.atleast_2d(np.asarray(depth_matrix, dtype=np.int32))
        m, c = self._pad_shards(m)
        lat, bram, status, cert = self._fused(m)
        lat = np.asarray(np.rint(lat[:c]), dtype=np.int64)
        bram = np.asarray(bram[:c], dtype=np.int64)
        return (lat, bram, np.asarray(status[:c], dtype=np.int8),
                np.asarray(cert[:c], dtype=bool))

    def _note_iters(self, iters: np.ndarray, replayed: np.ndarray,
                    c: int) -> None:
        """Keep lanes 3 and 4 of a raw-kernel launch: every row of an
        8-row block carries the block's Jacobi iterations and whether it
        replayed its gather schedule, and each iteration of a block
        steps one f32 vreg tile per 128 events."""
        if self.use_ref:
            return
        from repro.kernels.fifo_eval.fifo_eval import LANES, ROWS
        iters = np.asarray(iters, dtype=np.int64)
        self.last_iters = iters[:c]
        self.last_replayed = np.asarray(replayed, dtype=bool)[:c]
        # under a mesh each device blocks its own row shard
        blocks = sum(int(s[::ROWS].sum())
                     for s in np.split(iters, self.shard_multiple))
        self.last_tile_iters = blocks * (self.ops.e_pad // LANES)

    def evaluate(self, depth_matrix: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        m = np.atleast_2d(np.asarray(depth_matrix, dtype=np.int32))
        m, c = self._pad_shards(m)
        lat, bram, status, iters, replayed = self._call(m)
        self._note_iters(iters, replayed, c)
        lat = np.asarray(np.rint(lat[:c]), dtype=np.int64)
        bram = np.asarray(bram[:c], dtype=np.int64)
        return lat, bram, np.asarray(status[:c], dtype=np.int8)

    def evaluate_with_times(self, depth_matrix: np.ndarray
                            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                       np.ndarray]:
        """Like :meth:`evaluate`, also returning the (C, E_pad) final
        event times (int64) — the condensation certificate's input."""
        if self._call_times is None:
            from repro.kernels.fifo_eval.ops import make_batched_eval
            self._call_times = make_batched_eval(
                self.g, use_ref=self.use_ref, max_iters=self.max_iters,
                with_times=True, mesh=self.mesh)
        m = np.atleast_2d(np.asarray(depth_matrix, dtype=np.int32))
        m, c = self._pad_shards(m)
        lat, bram, status, iters, replayed, times = self._call_times(m)
        self._note_iters(iters, replayed, c)
        lat = np.asarray(np.rint(lat[:c]), dtype=np.int64)
        bram = np.asarray(bram[:c], dtype=np.int64)
        times = np.asarray(np.rint(times[:c]), dtype=np.int64)
        return lat, bram, np.asarray(status[:c], dtype=np.int8), times


@register_backend
class FixpointBackend(_ScanBackend):
    """jit(vmap) Jacobi + segmented-scan fixpoint in stock jnp."""

    name = "fixpoint"
    aliases = ("jax",)
    use_ref = True
