"""Trace-based incremental FIFO-latency evaluation (the LightningSim core).

This module is the stable public façade over the evaluation-backend
subsystem in :mod:`repro.core.backends`:

``evaluate_np``
    Kahn-worklist longest-path solve, one config at a time.  Readable
    reference; also the arbiter for the (rare) configs the batched path
    cannot classify within its iteration cap.

``BatchedEvaluator``
    Thin façade over the backend registry.  ``backend=`` selects

    ``"numpy"`` (alias ``"worklist"``, default) — the event-driven
        worklist; mirrors the paper's CPU tool and is the fastest option on
        this container (O(E) exact, ~10 ms at E=26k).  Also provides the
        *incremental* fast path: ``evaluate_incremental`` re-solves only
        the task segments coupled to the changed FIFOs.
    ``"jax"`` (alias ``"fixpoint"``) — jit(vmap) Jacobi + segmented-scan
        fixpoint; the TPU-native formulation (DESIGN.md §6).
    ``"pallas"`` — the ``kernels/fifo_eval`` kernels (Mosaic-compiled on a
        TPU, interpreted on the CPU).

    Batch bucketing, jit-cache reuse, and tiered UNRESOLVED-row escalation
    to the worklist live in :class:`repro.core.backends.DispatchPolicy`.
    All backends are exact and cross-validated in ``tests/test_backends``.

Numeric domain: times are exact in float32 while below 2**24; we assert the
design's schedule upper bound stays below ~1.5e7 cycles at build time.
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Optional, Tuple

import numpy as np

from repro.core.backends import (BIG, BUCKETS, CONVERGED, DEADLOCK,
                                 F32_EXACT_LIMIT, UNRESOLVED, DispatchPolicy,
                                 RungCascade, WorklistBackend, evaluate_np,
                                 get_backend)
from repro.core.backends.worklist import WorklistState
from repro.core.bram import design_bram_np
from repro.core.config import EvalConfig, resolve_config
from repro.core.simgraph import SimGraph
from repro.core.spans import span

__all__ = [
    "BIG", "CONVERGED", "DEADLOCK", "F32_EXACT_LIMIT", "UNRESOLVED",
    "BatchStats", "BatchedEvaluator", "bram_count_jnp", "evaluate_np",
]


def __getattr__(name):
    # re-exported lazily so the numpy worklist path never imports jax
    if name == "bram_count_jnp":
        from repro.core.backends.operands import bram_count_jnp
        return bram_count_jnp
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass
class BatchStats:
    n_calls: int = 0
    n_configs: int = 0
    n_fallbacks: int = 0
    n_incremental: int = 0
    n_dedup: int = 0          # duplicate in-batch rows solved once
    n_condensed: int = 0      # rows resolved on a condensed rung
    n_cond_fail: int = 0      # rung attempts whose certificate failed
    wall_s: float = 0.0
    worklist_s: float = 0.0   # escalating UNRESOLVED rows to the worklist
    worklist_lane_rows: int = 0  # of those rows, solved on worklist lanes
    raw_rows: int = 0         # real rows of fifo_eval_raw launches
    raw_row_iters: int = 0    # their Jacobi iterations (the kernel's lane 3)
    raw_tile_iters: int = 0   # 8-row block iterations x E_pad / 128 vregs
    raw_gather_fallbacks: int = 0  # their rows that walked, not replayed


#: historical BatchedEvaluator default (the advisor default is 256)
_EVALUATOR_DEFAULT = EvalConfig(max_iters=64)


class BatchedEvaluator:
    """Incremental trace-based evaluation over candidate depth matrices.

    ``config`` is the shared :class:`~repro.core.config.EvalConfig`
    (backend, iteration cap, condensation, sharding).  Runtime objects
    stay explicit keywords: ``rungs`` is a prebuilt
    :class:`~repro.core.condense.CondensedGraph` (or list) to use
    verbatim on any backend — the snapshot-restore and test hook —
    and ``mesh`` an explicit :class:`jax.sharding.Mesh`.  The legacy
    keyword spellings (``backend=``, ``max_iters=``, ``condense=``,
    ``shards=``, ``use_pallas=``) are deprecated shims.
    """

    BUCKETS = BUCKETS

    #: how many solved worklist states to keep for incremental re-solves
    STATE_CACHE_CAP = 128

    def __init__(self, g: SimGraph, config: Optional[EvalConfig] = None,
                 *, rungs=None, mesh=None, **legacy):
        if config is not None and not isinstance(config, EvalConfig):
            # pre-EvalConfig signature: second positional was max_iters
            import warnings
            warnings.warn(
                "BatchedEvaluator(g, max_iters) positional form is "
                "deprecated; pass config=EvalConfig(max_iters=...)",
                DeprecationWarning, stacklevel=2)
            config, legacy = None, dict(legacy, max_iters=int(config))
        if "condense" in legacy and not isinstance(
                legacy["condense"], (str, type(None))):
            # prebuilt CondensedGraph rungs used to ride the condense=
            # kwarg; they are runtime objects, so they move to rungs=
            import warnings
            warnings.warn(
                "BatchedEvaluator(condense=<rungs>) is deprecated; pass "
                "prebuilt CondensedGraphs via rungs=", DeprecationWarning,
                stacklevel=2)
            rungs = legacy.pop("condense")
        config = resolve_config(config, legacy, "BatchedEvaluator",
                                default=_EVALUATOR_DEFAULT)
        if g.latency_upper_bound() > F32_EXACT_LIMIT:
            raise ValueError(
                "design schedule bound exceeds float32-exact domain; "
                "split the design or reduce trip counts")
        self.g = g
        self.max_iters = config.max_iters
        self.stats = BatchStats()
        backend, shards = config.backend, config.shards
        # an explicit mesh/shard count selects the sharded scan backend
        # (docs/mesh.md); "auto" calibration also races it when the
        # process sees more than one device
        if (mesh is not None or shards is not None) \
                and backend not in ("mesh", "sharded"):
            backend = "mesh"
        self._mesh, self._shards = mesh, shards
        self.calibration = None
        if backend == "auto":
            backend = self._calibrate()
        self.backend = backend
        self.config = config.replace(backend=backend)
        if backend in ("mesh", "sharded"):
            from repro.core.backends.mesh import MeshBackend
            self._impl = MeshBackend(max_iters=self.max_iters,
                                     mesh=mesh, shards=shards)
        else:
            self._impl = get_backend(backend)(max_iters=self.max_iters)
        self._impl.prepare(g)
        if isinstance(self._impl, WorklistBackend):
            self._worklist = self._impl
        else:
            self._worklist = WorklistBackend(max_iters=self.max_iters)
            self._worklist.prepare(g)
        self.use_pallas = self._impl.name == "pallas"
        self.dispatch = DispatchPolicy(
            self._worklist,
            shard_multiple=getattr(self._impl, "shard_multiple", 1))
        self._states: "OrderedDict[bytes, WorklistState]" = OrderedDict()
        self.condensation = self._build_cascade(
            config.condense if rungs is None else rungs)
        self._cascade = RungCascade(self.condensation, self.dispatch,
                                    self._impl) if self.condensation \
            else None

    # ------------------------------------------------------- condensation
    def _build_cascade(self, condense):
        """Condense once per evaluator: ``"auto"`` builds (and caches on
        the graph) the default rung cascade; an explicit CondensedGraph
        or list (the ``rungs=`` argument) uses those rungs verbatim;
        None disables condensation.

        The per-row worklist's cost is bound by wake-wave count rather
        than event count, so it skips ``aggressive`` rungs — they only
        pay on the batched scan backends whose per-iteration cost is
        proportional to E_pad.
        """
        if condense is None:
            return []
        scan = not isinstance(self._impl, WorklistBackend)
        if condense == "auto":
            # the per-row worklist's cost is bound by wake-wave count
            # (set by the back-pressure dynamics), not event count, so
            # auto-condensation is a wash there and stays scan-only;
            # pass explicit CondensedGraphs to force it anywhere
            if not scan:
                return []
            cgs = getattr(self.g, "_cascade_cache", None)
            if cgs is None:
                from repro.core.condense import condense_auto
                cgs = condense_auto(self.g)
                self.g._cascade_cache = cgs
            # aggressive first: per-iteration cost is proportional to
            # E_pad, and folding the back-pressure anchors away also
            # slashes the Jacobi iteration count
            by_tag = {cg.tag: cg for cg in cgs}
            cgs = [by_tag[t] for t in ("aggressive", "safe") if t in by_tag]
        else:
            cgs = list(condense) if isinstance(condense, (list, tuple)) \
                else [condense]
        rungs = []
        for cg in cgs:
            impl = self._impl.spawn()   # keeps mesh/config of the primary
            impl.prepare(cg)
            rungs.append((cg, impl))
        return rungs

    def _calibrate(self) -> str:
        """One-shot per-design backend calibration (``backend="auto"``).

        Times every calibration candidate (the numpy worklist, plus the
        jax fixpoint when importable, plus the fused Pallas kernel when
        the design condenses, plus the sharded mesh backend when the
        process sees more than one device) through the SAME evaluation
        path production uses — a full ``BatchedEvaluator`` including
        each backend's condensation cascade, on a DSE-representative
        16-row batch — and picks the fastest.  The probe timings are
        kept in ``self.calibration`` for the runtime report.
        """
        import importlib.util

        candidates = ["numpy"]
        if importlib.util.find_spec("jax") is not None:
            candidates.append("jax")
            import jax
            if jax.device_count() > 1:
                # sharding only *can* pay with a real multi-device mesh;
                # the probe decides whether it actually does here
                candidates.append("mesh")
            # the condensation-native kernel evaluates AND certifies the
            # hot rungs in one device launch — it only *can* win where a
            # cascade exists, so probe it exactly there (raw streams
            # would just time the interpret-mode kernel at full E_pad)
            cgs = getattr(self.g, "_cascade_cache", None)
            if cgs is None:
                from repro.core.condense import condense_auto
                cgs = condense_auto(self.g)
                self.g._cascade_cache = cgs
            if cgs:
                candidates.append("pallas")
        u = np.asarray(self.g.upper_bounds, dtype=np.int64)
        rng = np.random.default_rng(0)
        probe = np.stack([np.maximum(
            2, (u * rng.uniform(0.5, 1.0, u.size)).astype(np.int64))
            for _ in range(16)])
        timings = {}
        for name in candidates:
            ev = BatchedEvaluator(self.g, EvalConfig(
                backend=name, max_iters=self.max_iters))
            ev.evaluate(probe)                # warm (jit compile)
            t0 = time.perf_counter()
            ev.evaluate(probe)
            timings[name] = time.perf_counter() - t0
        chosen = min(timings, key=timings.get)
        self.calibration = {"chosen": chosen, "probe_s": timings}
        return chosen

    # ------------------------------------------------------------------
    def evaluate(self, depth_matrix: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(C, F) int depths -> (latency int64, bram int64, deadlock bool).

        Routes through the dispatch policy: bucket-padded jit reuse for the
        batched backends, exact worklist escalation for UNRESOLVED rows,
        and -1 latency on deadlocked rows.  Duplicate rows within the
        batch are solved once and scattered back (exact, order-preserving;
        DSE batches repeat rows constantly — annealing chains initialize
        at the same corner, frontier refiners revisit the same configs).
        """
        depth_matrix = np.atleast_2d(np.asarray(depth_matrix))
        t_start = time.perf_counter()
        C = depth_matrix.shape[0]
        with span("evaluate"):
            uniq, inverse = np.unique(depth_matrix, axis=0,
                                      return_inverse=True)
            if uniq.shape[0] < C:
                lat, bram, dead = self._eval_rows(uniq)
                lat, bram, dead = (lat[inverse], bram[inverse],
                                   dead[inverse])
                self.stats.n_dedup += C - uniq.shape[0]
            else:
                lat, bram, dead = self._eval_rows(depth_matrix)
        self.stats.n_calls += 1
        self.stats.n_configs += C
        self.stats.wall_s += time.perf_counter() - t_start
        return lat, bram, dead

    def _eval_rows(self, m: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Unique rows -> exact results: condensation cascade first (each
        accepted row carries a passed exactness certificate or a sound
        deadlock verdict), raw dispatch as the unconditional backstop.
        The escalation logic lives in
        :class:`repro.core.backends.RungCascade`; kernel-backed rungs
        certify on-device, the rest through the host verifier."""
        if self._cascade is None:
            return self.dispatch.dispatch(self._impl, m, self.stats)
        m = np.asarray(m, dtype=np.int64)
        lat, dead = self._cascade.evaluate(m, self.stats)
        bram = design_bram_np(m, np.asarray(self.g.widths))
        return lat, bram, dead

    # ------------------------------------------------ incremental fast path
    @property
    def prefer_incremental(self) -> bool:
        """Whether single-FIFO-move searches should use the delta path.

        The incremental worklist always *works*, but only clearly wins when
        the primary backend is the worklist itself; batched backends may
        amortize better on real accelerators.
        """
        return self._impl is self._worklist

    def _state_for(self, depths: np.ndarray) -> WorklistState:
        key = depths.tobytes()
        st = self._states.get(key)
        if st is None:
            st = self._worklist.solve(depths)
            self._remember(key, st)
        else:
            self._states.move_to_end(key)
        return st

    def _remember(self, key: bytes, st: WorklistState):
        self._states[key] = st
        self._states.move_to_end(key)
        while len(self._states) > self.STATE_CACHE_CAP:
            self._states.popitem(last=False)

    def evaluate_incremental(self, base_depths: Optional[np.ndarray],
                             depth_matrix: np.ndarray
                             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Incremental (latency, bram, deadlock) against base config(s).

        ``base_depths`` is one (F,) base row, a (C, F) per-row base matrix,
        or None (full solves, states cached for future deltas).  Each row is
        re-solved only over the task segments transitively coupled to the
        FIFOs that differ from its base — the LightningSim primitive.
        """
        m = np.atleast_2d(np.asarray(depth_matrix, dtype=np.int64))
        C = m.shape[0]
        base = None
        if base_depths is not None:
            base = np.atleast_2d(np.asarray(base_depths, dtype=np.int64))
            if base.shape[0] == 1 and C > 1:
                base = np.broadcast_to(base, m.shape)
        t_start = time.perf_counter()
        lat = np.zeros(C, dtype=np.int64)
        dead = np.zeros(C, dtype=bool)
        for i in range(C):
            if base is None:
                st = self._state_for(m[i])
            else:
                base_st = self._state_for(base[i])
                st = self._worklist.solve_delta(base_st, m[i])
                self._remember(m[i].tobytes(), st)
            lat[i] = st.latency
            dead[i] = st.deadlocked
        bram = design_bram_np(m, np.asarray(self.g.widths))
        self.stats.n_calls += 1
        self.stats.n_configs += C
        self.stats.n_incremental += C
        self.stats.wall_s += time.perf_counter() - t_start
        return lat, bram, dead

    @property
    def incr_stats(self):
        return self._worklist.incr_stats

    def close(self) -> None:
        """Leave the process's worklist lanes, if this evaluator used
        them (collecting it does the same); the last to leave stops
        them."""
        self.dispatch.close()

    def condensation_info(self) -> list:
        """Per-rung condensation summary for reports: tag, raw/condensed
        event counts, and the compression ratio."""
        return [{"tag": cg.tag,
                 "events_raw": cg.n_raw_events,
                 "events_condensed": cg.n_events,
                 "compression": round(cg.compression, 2)}
                for cg, _ in self.condensation]

    # convenience -------------------------------------------------------
    def evaluate_one(self, depths: np.ndarray) -> Tuple[int, int, bool]:
        lat, bram, dead = self.evaluate(np.asarray(depths)[None, :])
        return int(lat[0]), int(bram[0]), bool(dead[0])

    def bram_only(self, depth_matrix: np.ndarray) -> np.ndarray:
        return design_bram_np(np.asarray(depth_matrix, dtype=np.int64),
                              np.asarray(self.g.widths))
