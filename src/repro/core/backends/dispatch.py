"""Tiered dispatch policy: bucketing, jit-cache reuse, and escalation.

Owns the three batch-shaping concerns that used to be tangled into
``BatchedEvaluator``:

1. **Bucketing** — backends whose compiled callable specializes on the
   batch dimension (``wants_bucketing``) receive batches padded up to a
   small fixed set of sizes, so the jit cache holds at most
   ``len(BUCKETS)`` entries per graph instead of one per distinct C.
   Padding repeats the final row; pad results are sliced off.
2. **Status resolution** — DEADLOCK rows become infeasible (-1 latency);
   CONVERGED rows pass through.
3. **Escalation** — UNRESOLVED rows (the iteration cap fired before the
   fixpoint converged: deadlocks never converge by construction, and rare
   feasible rows converge slowly) are re-solved exactly by the worklist
   arbiter, counted in ``stats.n_fallbacks``, timed in
   ``stats.worklist_s``.

Each layer opens a ``fifo.<name>`` profiler span (:mod:`repro.core.spans`)
once per call: ``raw``, ``worklist``, ``rung.<tag>``, and the
cross-design phases ``hetero.stack``, ``.pad``, ``.h2d``, ``.wait`` and
``.scatter``.  A raw-kernel launch's iteration lane is counted into
``stats.raw_rows``, ``raw_row_iters`` and ``raw_tile_iters``, its rows
whose block walked its gathers instead of replaying their schedule into
``raw_gather_fallbacks``.

:class:`RungCascade` owns the condensation escalation ladder (moved here
from ``BatchedEvaluator``): route each row through the most aggressive
admissible rung, accept rows whose exactness certificate passes (or whose
relaxed solve already proves deadlock), and fall through rung by rung to
the raw dispatch backstop.  Kernel-backed rung evaluators certify
on-device (``fused_certificate``); the rest return event times for the
host-side ``condense.verify_rows``.

:class:`HeteroDispatcher` extends the same concerns across *designs*: it
packs rows from many SimGraphs into one lane-aligned hetero batch (shared
E*/F*/R* envelope, one jit cache for the whole campaign instead of one
per graph), with per-design worklist escalation.  jax is imported lazily
so this module stays importable in numpy-only worker processes.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.backends.base import (CONVERGED, DEADLOCK, F32_EXACT_LIMIT,
                                      EvalBackend, UNRESOLVED)
from repro.core.backends.worklist import WorklistBackend
from repro.core.simgraph import SimGraph
from repro.core.spans import span

BUCKETS = (1, 8, 32, 128, 512, 2048)


def _count_iters(stats, backend: EvalBackend, rows: int) -> None:
    """Add the iteration and replayed lanes of ``backend``'s last
    raw-kernel launch, whose first ``rows`` rows are real, to ``stats``."""
    iters = getattr(backend, "last_iters", None)
    if stats is None or iters is None:
        return
    stats.raw_rows += rows
    stats.raw_row_iters += int(iters[:rows].sum())
    stats.raw_tile_iters += backend.last_tile_iters
    stats.raw_gather_fallbacks += int(
        (~backend.last_replayed[:rows]).sum())


class DispatchPolicy:
    """Routes depth batches through a backend and resolves every row.

    ``shard_multiple`` (the backend's device-mesh size; 1 = unsharded)
    rounds every padded batch up to a shard multiple so the sharded
    evaluators split rows evenly across devices without growing their
    jit cache beyond the bucketed shape set.
    """

    def __init__(self, worklist: WorklistBackend,
                 buckets: Tuple[int, ...] = BUCKETS,
                 shard_multiple: int = 1):
        self.worklist = worklist
        self.buckets = tuple(buckets)
        self.shard_multiple = max(1, int(shard_multiple))

    def bucket_size(self, c: int) -> Optional[int]:
        return next((b for b in self.buckets if b >= c), None)

    def pad_batch(self, m: np.ndarray) -> np.ndarray:
        """Pad C up to the covering bucket (rounded to a shard multiple)
        by repeating the last row."""
        c = m.shape[0]
        bucket = self.bucket_size(c)
        target = c if bucket is None else bucket
        k = self.shard_multiple
        target = -(-target // k) * k
        if target == c:
            return m
        pad = np.repeat(m[-1:], target - c, axis=0)
        return np.concatenate([m, pad], axis=0)

    def dispatch(self, backend: EvalBackend, depth_matrix: np.ndarray,
                 stats=None) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(C, F) depths -> (latency int64, bram int64, deadlock bool)."""
        m = np.atleast_2d(np.asarray(depth_matrix))
        C = m.shape[0]
        batch = self.pad_batch(m) if backend.wants_bucketing else m
        with span("raw"):
            lat, bram, status = backend.evaluate(batch)
        _count_iters(stats, backend, C)
        lat, bram, status = lat[:C], bram[:C], status[:C]

        dead = status == DEADLOCK
        unresolved = np.flatnonzero(status == UNRESOLVED)
        if unresolved.size:
            with span("worklist", stats, "worklist_s"):
                wl_lat, _, wl_status = self.worklist.evaluate(m[unresolved])
            lat[unresolved] = wl_lat
            dead[unresolved] = wl_status == DEADLOCK
            if stats is not None:
                stats.n_fallbacks += int(unresolved.size)
        lat = np.where(dead, -1, lat)
        return lat, bram, dead


class RungCascade:
    """The condensation escalation ladder over certified rungs.

    ``rungs`` is the ordered ``[(CondensedGraph, prepared backend), ...]``
    list (most aggressive first); ``policy`` the shared
    :class:`DispatchPolicy`; ``primary`` the raw-graph backend used as
    the unconditional backstop.  Per rung, rows inside the rung's
    routing box are evaluated on the condensed stream and accepted when

    * the relaxed solve proves DEADLOCK (sound: the condensed fixpoint
      is a lower bound of the raw one), or
    * the row CONVERGED and its exactness certificate passes.

    Certification runs one of two ways:

    * **fused** — kernel-backed rung evaluators
      (``backend.fused_certificate``) evaluate and certify in ONE device
      program via ``evaluate_certified``; the event-time matrix never
      reaches the host, so a fully-certifying batch costs exactly one
      dispatch (asserted by the device-residency regression tests);
    * **host** — scan/worklist evaluators return per-anchor times
      (``evaluate_with_times``) and ``condense.verify_rows`` checks the
      folded cross constraints on the host.

    Everything still pending after the last rung goes to the raw
    dispatch backstop (bucketing + UNRESOLVED worklist escalation).
    """

    def __init__(self, rungs, policy: DispatchPolicy,
                 primary: EvalBackend):
        self.rungs = list(rungs)
        self.policy = policy
        self.primary = primary

    def evaluate(self, m: np.ndarray, stats=None
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """Unique (C, F) rows -> exact ``(latency i64, deadlock bool)``
        with -1 latency on deadlocked rows."""
        from repro.core.condense import verify_rows
        m = np.asarray(m, dtype=np.int64)
        C = m.shape[0]
        lat = np.zeros(C, dtype=np.int64)
        dead = np.zeros(C, dtype=bool)
        pending = np.ones(C, dtype=bool)
        for cg, impl in self.rungs:
            sel = np.flatnonzero(pending & cg.in_box(m))
            if not sel.size:
                continue
            rows = m[sel]
            fused = impl.fused_certificate
            if impl.wants_bucketing or fused:
                # the fused kernel path buckets too: its jit cache is
                # keyed on the padded batch shape like any scan backend
                batch = self.policy.pad_batch(rows)
            else:
                batch = rows
            with span("rung." + cg.tag):
                if fused:
                    rlat, _, rstatus, ok = impl.evaluate_certified(batch)
                    rlat = rlat[: sel.size]
                    rstatus = rstatus[: sel.size]
                    ok = ok[: sel.size]
                    dl = rstatus == DEADLOCK   # sound: relaxed system stalls
                else:
                    rlat, _, rstatus, times = impl.evaluate_with_times(batch)
                    _count_iters(stats, impl, sel.size)
                    rlat = rlat[: sel.size]
                    rstatus = rstatus[: sel.size]
                    times = times[: sel.size, : cg.n_events]
                    dl = rstatus == DEADLOCK
                    ok = np.zeros(sel.size, dtype=bool)
                    conv = rstatus == CONVERGED
                    if conv.any():
                        ci = np.flatnonzero(conv)
                        ok[ci] = verify_rows(cg, rows[ci], times[ci])
            acc = dl | ok
            if stats is not None:
                stats.n_cond_fail += int(sel.size - acc.sum())
            if acc.any():
                idx = sel[acc]
                lat[idx] = np.where(dl[acc], -1, rlat[acc])
                dead[idx] = dl[acc]
                pending[idx] = False
                if stats is not None:
                    stats.n_condensed += int(acc.sum())
            if not pending.any():
                break
        rem = np.flatnonzero(pending)
        if rem.size:
            rlat, _, rdead = self.policy.dispatch(
                self.primary, m[rem], stats)
            lat[rem] = rlat
            dead[rem] = rdead
        return lat, dead


@dataclasses.dataclass
class HeteroStats:
    n_dispatches: int = 0
    n_rows: int = 0          # real rows evaluated
    n_pad_rows: int = 0      # bucket-padding overhead rows
    n_fallbacks: int = 0     # UNRESOLVED rows escalated to a worklist
    wall_s: float = 0.0
    prep_s: float = 0.0      # stack, pad and send, until the launch is queued
    wait_s: float = 0.0      # from the launch until results are on the host


class HeteroDispatcher:
    """One vectorized dispatch for rows spanning MANY designs.

    Built once per campaign from every participating
    :class:`~repro.core.simgraph.SimGraph`: computes the shared
    ``(E*, F*, R*)`` envelope, re-pads each design's operands to it, and
    compiles ONE jitted fixpoint whose cache is keyed only on the bucketed
    total row count — where per-design dispatch would compile
    ``len(BUCKETS)`` variants per graph, a campaign compiles
    ``len(buckets)`` variants total.  UNRESOLVED rows are escalated to the
    owning design's worklist arbiter, exactly like
    :class:`DispatchPolicy`.
    """

    #: finer-grained than BUCKETS: cross-design batches vary more in size
    BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)

    def __init__(self, graphs: Dict[str, SimGraph],
                 worklists: Optional[Dict[str, WorklistBackend]] = None,
                 max_iters: int = 64,
                 buckets: Sequence[int] = BUCKETS,
                 mesh=None, shards: Optional[int] = None):
        from repro.kernels.fifo_eval.ops import make_hetero_batched_eval
        self.max_iters = int(max_iters)
        self.e_pad = 0
        self.f_max = 0
        self.r_max = 0
        self._base: Dict[str, object] = {}   # per-design raw operands
        self._ext: Dict[str, object] = {}    # envelope-padded operands
        self.worklists: Dict[str, WorklistBackend] = {}
        # design-parallel sharding: rows are stacked design-major, so
        # partitioning the packed batch over the mesh's devices spreads
        # whole-design blocks across the fleet (2-D campaign meshes put
        # contiguous designs on contiguous device groups)
        if mesh is None and shards is not None:
            from repro.launch.mesh import make_eval_mesh
            mesh = make_eval_mesh(shards)
        self.mesh = mesh
        self.shard_multiple = (int(mesh.devices.size)
                               if mesh is not None else 1)
        self.stats = HeteroStats()
        # the call adds its send to stats.prep_s and its wait to wait_s
        self._call = make_hetero_batched_eval(max_iters, mesh=mesh,
                                              stats=self.stats)
        self.buckets = tuple(buckets)
        worklists = worklists or {}
        if graphs:
            # pre-compute the shared envelope so registering N designs
            # pads each exactly once (growth re-pads would be O(N^2))
            from repro.core.backends.operands import get_operands
            opses = [get_operands(g) for g in graphs.values()]
            self.e_pad = max(o.e_pad for o in opses)
            self.f_max = max(o.n_fifos for o in opses)
            self.r_max = max(o.n_flat_reads for o in opses)
        for k, g in graphs.items():
            self.add_design(k, g, worklists.get(k))

    def add_design(self, key: str, graph: SimGraph,
                   worklist: Optional[WorklistBackend] = None) -> None:
        """Register a design after construction (idempotent per key).

        The advisory service traces designs lazily — the first session on
        a new design lands mid-campaign — so the shared envelope must be
        able to grow.  If the new design fits the current ``(E*, F*, R*)``
        envelope, only its own operands are padded; if it exceeds it,
        every registered design is re-padded from its raw operands (the
        jitted evaluator is shape-polymorphic via its cache, so growth
        costs one recompile on the next dispatch, nothing else).
        """
        if key in self._ext:
            return
        from repro.core.backends.operands import (extend_operands,
                                                  get_operands)
        # same guard as BatchedEvaluator: the f32 fixpoint is only
        # exact while times stay below 2**24
        if graph.latency_upper_bound() > F32_EXACT_LIMIT:
            raise ValueError(
                f"design {key!r}: schedule bound exceeds the "
                "float32-exact domain; split the design or reduce "
                "trip counts")
        ops = get_operands(graph)
        self._base[key] = ops
        grew = (ops.e_pad > self.e_pad or ops.n_fifos > self.f_max
                or ops.n_flat_reads > self.r_max)
        self.e_pad = max(self.e_pad, ops.e_pad)
        self.f_max = max(self.f_max, ops.n_fifos)
        self.r_max = max(self.r_max, ops.n_flat_reads)
        if grew:
            self._ext = {k: extend_operands(o, self.e_pad, self.f_max,
                                            self.r_max)
                         for k, o in self._base.items()}
        else:
            self._ext[key] = extend_operands(ops, self.e_pad, self.f_max,
                                             self.r_max)
        if worklist is None:
            worklist = WorklistBackend(max_iters=self.max_iters)
            worklist.prepare(graph)
        self.worklists[key] = worklist

    def _pad_rows(self, batch: dict, c: int) -> Tuple[dict, int]:
        bucket = next((b for b in self.buckets if b >= c), None)
        target = c if bucket is None else bucket
        k = self.shard_multiple
        target = -(-target // k) * k           # sharded: even device split
        if target == c:
            return batch, c
        pad = target - c
        return {k_: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                for k_, v in batch.items()}, target

    def dispatch(self, items: List[Tuple[str, np.ndarray]]
                 ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """``[(design_key, (c_i, F_i) depths), ...]`` -> per-item results.

        Every returned triple is exact ``(latency i64, bram i64,
        deadlock bool)`` with -1 latency on deadlocked rows.
        """
        from repro.core.backends.operands import stack_hetero
        t_start = time.perf_counter()
        mats = [np.atleast_2d(np.asarray(m, dtype=np.int64))
                for _, m in items]
        with span("hetero.stack", self.stats, "prep_s"):
            batch = stack_hetero(
                [(self._ext[k], m) for (k, _), m in zip(items, mats)])
        C = batch["depths"].shape[0]
        with span("hetero.pad", self.stats, "prep_s"):
            padded, c_padded = self._pad_rows(batch, C)
        lat, bram, status = self._call(padded)
        lat, bram, status = lat[:C], bram[:C], status[:C]

        out = []
        row0 = 0
        with span("hetero.scatter"):
            for (key, _), m in zip(items, mats):
                c = m.shape[0]
                sl = slice(row0, row0 + c)
                row0 += c
                lat_i, bram_i = lat[sl].copy(), bram[sl].copy()
                dead_i = status[sl] == DEADLOCK
                unresolved = np.flatnonzero(status[sl] == UNRESOLVED)
                if unresolved.size:
                    with span("worklist"):
                        wl_lat, _, wl_status = self.worklists[key].evaluate(
                            m[unresolved])
                    lat_i[unresolved] = wl_lat
                    dead_i[unresolved] = wl_status == DEADLOCK
                    self.stats.n_fallbacks += int(unresolved.size)
                lat_i = np.where(dead_i, -1, lat_i)
                out.append((lat_i, bram_i, dead_i))
        self.stats.n_dispatches += 1
        self.stats.n_rows += C
        self.stats.n_pad_rows += c_padded - C
        self.stats.wall_s += time.perf_counter() - t_start
        return out
