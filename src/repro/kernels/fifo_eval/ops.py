"""jit'd wrapper around the fifo_eval fixpoint implementations.

Consumes the shared lane-aligned event tensors from
:mod:`repro.core.backends.operands` (built once per graph) and exposes a
callable ``(C, F) int depths -> (latency, bram, status)``.  The
depth-dependent per-config operands (read latencies, back-pressure gather
indices) come from the shared :func:`~repro.core.backends.operands
.depth_operands`; only the heavy fixpoint differs between inners:

``use_ref=False``  the Pallas kernel (:mod:`repro.kernels.fifo_eval
                   .fifo_eval`): Mosaic-compiled on a TPU, interpreted
                   on the CPU (:func:`~repro.kernels.fifo_eval.fifo_eval
                   .kernel_interpret`)
``use_ref=True``   the pure-jnp oracle (:mod:`repro.kernels.fifo_eval.ref`),
                   which is also the ``fixpoint`` backend's implementation

Besides the results, the closure returns each row's Jacobi iteration
count (lane 3 of the kernel's output row: on the Pallas kernel, the
count of the row's 8-row block; on the oracle, the row's own) and
whether the row's block replayed its gather schedule (lane 4; never on
the oracle, which has none).  Every ``call`` waits for the device inside
a ``fifo.wait`` span.

Tests diff the two against each other and against the numpy worklist.
Each returned ``call`` carries its jitted program as ``call.run`` so a
caller can lower one dispatch (``call.run.lower(depths)``) and inspect
what the device runs.
"""

from __future__ import annotations

import functools
from collections import Counter
from typing import Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.backends.base import CONVERGED, DEADLOCK, UNRESOLVED
from repro.core.backends.operands import (bram_count_jnp, cert_row_operands,
                                          depth_operands, get_cert_tables,
                                          get_operands)
from repro.core.bram import (BRAM_READ_LATENCY, SRL_BITS, SRL_DEPTH,
                             SRL_READ_LATENCY)
from repro.core.simgraph import SimGraph
from repro.core.spans import span
from repro.kernels.fifo_eval.fifo_eval import (fifo_eval_pallas,
                                               kernel_interpret,
                                               kernel_platform)
from repro.kernels.fifo_eval.ref import fifo_eval_ref, fifo_eval_ref_hetero

#: device dispatches per wrapper kind ("batched" / "hetero" /
#: "condensed").  The cascade device-residency regression tests assert
#: that a fully-certifying batch costs exactly ONE "condensed" dispatch
#: and never touches the host verifier.
DISPATCH_COUNTS: Counter = Counter()

#: the batched program returns each row's status in the low bits of an
#: int32, then the replayed bit, then the row's iteration count, so a
#: launch brings back no more arrays than it did before either was kept
_STATUS_BITS = 2
_REPLAYED = 1 << _STATUS_BITS
_ITER_SHIFT = _STATUS_BITS + 1


def _shard_over_rows(run: Callable, mesh) -> Callable:
    """Wrap an un-jitted row-batch fixpoint in ``shard_map`` over ``mesh``.

    Every input and output is partitioned along its leading (config-row)
    axis across ALL mesh axes jointly, so a 1-D ``("eval",)`` mesh splits
    a batch into per-device row shards and a 2-D ``("design", "eval")``
    campaign mesh splits design-major row blocks onto contiguous device
    groups.  Rows are independent (one fixpoint per candidate config), so
    sharding is pure row partitioning — bit-identical to the solo path.
    ``check_vma=False`` because nothing here relies on replication (no
    collectives at all).  The caller must pad the row count to a
    multiple of the mesh size.
    """
    from jax.sharding import PartitionSpec
    spec = PartitionSpec(tuple(mesh.axis_names))
    return jax.shard_map(run, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)


def _make_run(ops, inner, max_iters: int, with_times: bool) -> Callable:
    """The un-jitted batched fixpoint body shared by the solo jit path
    and the shard_map-wrapped mesh path."""

    def run(depths):                     # (C, F) int32
        rd_lat_e, bp_idx, bp_valid, bp_base, structural = depth_operands(
            ops, depths)
        out, times = inner(ops.delta, ops.seg_start, ops.is_read,
                           ops.has_data, ops.data_idx, ops.end_bonus,
                           rd_lat_e, bp_idx, bp_valid, bp_base,
                           max_iters=max_iters, bound=ops.bound)
        lat = jnp.maximum(out[:, 0], ops.taskless_lat)
        conv = out[:, 1] > 0
        over = out[:, 2] > 0
        status = jnp.where(
            structural | over, DEADLOCK,
            jnp.where(conv, CONVERGED, UNRESOLVED)).astype(jnp.int32)
        code = status | (out[:, 3].astype(jnp.int32) << _ITER_SHIFT)
        if out.shape[1] > 4:                 # the kernel's replayed lane
            code = code | jnp.where(out[:, 4] > 0, _REPLAYED, 0)
        bram = jnp.sum(bram_count_jnp(depths.astype(jnp.int32),
                                      ops.widths[None, :]),
                       axis=1).astype(jnp.int32)
        if with_times:
            return lat, bram, code, times
        return lat, bram, code

    return run


def make_batched_eval(ev_or_graph, use_ref: bool = False,
                      max_iters: int = None,
                      with_times: bool = False,
                      mesh=None) -> Callable:
    """Build the batched evaluation closure for a SimGraph.

    Accepts either a :class:`~repro.core.simgraph.SimGraph` (raw or
    condensed — the condensation offsets ride the shared operands) or
    any object with ``.g`` / ``.max_iters`` (e.g. a ``BatchedEvaluator``).
    The closure returns ``(lat, bram, status, iters, replayed)``,
    ``iters`` being each row's Jacobi iteration count and ``replayed``
    whether its block replayed the gather schedule (False on the
    oracle); with ``with_times`` also ``t``,
    the (C, E_pad) final event-time matrix the condensation certificate
    checks, which is otherwise dead-code-eliminated inside the jit.

    ``mesh`` (a :class:`jax.sharding.Mesh`) shards the config-row axis
    across its devices via ``shard_map`` — see
    :mod:`repro.core.backends.mesh`; the row count must then be a
    multiple of the mesh size (``MeshBackend`` pads).
    """
    g: SimGraph = getattr(ev_or_graph, "g", ev_or_graph)
    if max_iters is None:
        max_iters = getattr(ev_or_graph, "max_iters", 64)
    max_iters = int(max_iters)
    ops = get_operands(g)

    inner = fifo_eval_ref if use_ref else functools.partial(
        fifo_eval_pallas, interpret=kernel_interpret(mesh),
        with_times=with_times)

    run = _make_run(ops, inner, max_iters, with_times)
    if mesh is not None:
        run = _shard_over_rows(run, mesh)
    run = jax.jit(run)

    def call(depth_matrix: np.ndarray
             ) -> Tuple[np.ndarray, ...]:
        DISPATCH_COUNTS["batched"] += 1
        out = run(jnp.asarray(depth_matrix, dtype=jnp.int32))
        with span("wait"):
            lat, bram, code, *times = jax.device_get(out)
        status = (code & ((1 << _STATUS_BITS) - 1)).astype(np.int8)
        return (lat, bram, status, code >> _ITER_SHIFT,
                (code & _REPLAYED) > 0, *times)

    call.run = run
    return call


def make_condensed_eval(cg, max_iters: int = 64,
                        with_times: bool = False,
                        mesh=None, block: int = None
                        ) -> Optional[Callable]:
    """Build the FUSED condensed evaluation closure for a CondensedGraph.

    One kernel launch per batch evaluates the condensed fixpoint AND the
    exactness certificate (:mod:`repro.kernels.fifo_eval.condensed`),
    returning ``call(depths) -> (lat, bram, status, cert)`` — ``cert``
    is the per-row pass/fail mask with ``verify_rows`` semantics, True
    only on CONVERGED rows, so the rung cascade accepts/escalates rows
    without the event-time matrix ever leaving the device.  Returns None
    when the graph has no expressible certificate tables (the caller
    falls back to the host verifier).

    ``mesh`` shards the config-row axis like :func:`make_batched_eval`;
    the batch is padded to the kernel's row-block size internally (per
    shard under a mesh), so callers only pad to the shard multiple.
    """
    from repro.kernels.fifo_eval.condensed import (fifo_eval_condensed,
                                                   pick_block)
    ops = get_operands(cg)
    ct = get_cert_tables(cg)
    if ct is None:
        return None
    if block is None:
        block = pick_block(ops.e_pad, ct.v_pad)
    max_iters = int(max_iters)
    interpret = kernel_interpret(mesh)

    def run(depths):                     # (C, F) int32, C % shards == 0
        c = depths.shape[0]
        # shrink the row block to the (static) batch size: escalation
        # rungs see small bucketed batches (8 rows), and padding those up
        # to the full-batch block would re-evaluate the rung 4x over
        b = min(block, max(8, 1 << (c - 1).bit_length()))
        pad = -c % b
        if pad:
            depths = jnp.concatenate(
                [depths,
                 jnp.broadcast_to(depths[-1:], (pad, depths.shape[1]))])
        rd_lat_e, bp_idx, bp_valid, bp_base, structural = depth_operands(
            ops, depths)
        csrc, cdst, cthr, cval = cert_row_operands(ops, ct, depths)
        out, times = fifo_eval_condensed(
            ops.delta, ops.seg_start, ops.is_read, ops.has_data,
            ops.data_idx, ops.end_bonus, rd_lat_e, bp_idx, bp_valid,
            bp_base, csrc, cdst, cthr, cval, max_iters=max_iters,
            bound=ops.bound, block=b, interpret=interpret,
            with_times=with_times)
        lat = jnp.maximum(out[:, 0], ops.taskless_lat)
        conv = out[:, 1] > 0
        over = out[:, 2] > 0
        status = jnp.where(
            structural | over, DEADLOCK,
            jnp.where(conv, CONVERGED, UNRESOLVED)).astype(jnp.int8)
        # kernel cert = conv & ~over & no violated slot; a structurally
        # deadlocked row must additionally never certify
        cert = (out[:, 4] > 0) & (status == CONVERGED)
        bram = jnp.sum(bram_count_jnp(depths.astype(jnp.int32),
                                      ops.widths[None, :]),
                       axis=1).astype(jnp.int32)
        res = (lat[:c], bram[:c], status[:c], cert[:c])
        if with_times:
            res = res + (times[:c],)
        return res

    if mesh is not None:
        run = _shard_over_rows(run, mesh)
    run = jax.jit(run)

    def call(depth_matrix: np.ndarray) -> Tuple[np.ndarray, ...]:
        DISPATCH_COUNTS["condensed"] += 1
        out = run(jnp.asarray(depth_matrix, dtype=jnp.int32))
        with span("wait"):
            return jax.device_get(out)

    call.run = run
    return call


def make_hetero_batched_eval(max_iters: int = 64, mesh=None,
                             use_ref: Optional[bool] = None,
                             stats=None) -> Callable:
    """Build the CROSS-DESIGN batched evaluation closure.

    Consumes the stacked per-row batch dict produced by
    :func:`repro.core.backends.operands.stack_hetero` — every row carries
    its own (padded) event tables, so one dispatch can mix rows from many
    SimGraphs.  The depth-dependent operand computation mirrors
    :func:`~repro.core.backends.operands.depth_operands` with per-row
    gathers (``take_along_axis`` instead of closed-over tables); the two
    are cross-validated in ``tests/test_campaign.py``.

    The fixpoint runs in the raw Pallas kernel with per-row tables where
    the kernels compile (a TPU) and in the vmapped jnp reference where
    they would be interpreted (the CPU); ``use_ref`` forces one or the
    other.  The reference's per-row gathers inside a vmapped while loop
    take the TPU compiler minutes per batch shape, the kernel seconds.

    Returns ``call(batch) -> (latency i64, bram i64, status i8)``; the
    jit cache is keyed on the batch shape, so callers should bucket the
    total row count (see ``HeteroDispatcher``).  Given ``stats`` (a
    ``HeteroStats``), each call adds the host's time until the launch is
    enqueued (``fifo.hetero.h2d``) to ``stats.prep_s`` and its wait for
    the results (``fifo.hetero.wait``) to ``stats.wait_s``.

    ``mesh`` shards the packed row batch over the mesh's devices — since
    every row carries its own event tables, the stacked batch is sharded
    leaf-by-leaf along rows with zero replication or collectives.  Rows
    are stacked design-major, so on a 2-D ``("design", "eval")`` campaign
    mesh contiguous design blocks land on contiguous device groups.  The
    (bucketed) row count must be a multiple of the mesh size.
    """
    if use_ref is None:
        use_ref = kernel_platform(mesh) != "tpu"
    interpret = False if use_ref else kernel_interpret(mesh)

    def run(b):
        d = b["depths"].astype(jnp.int32)              # (C, F*)
        w = b["widths"].astype(jnp.int32)              # (C, F*)
        is_bram = ~((d <= SRL_DEPTH) | (d * w <= SRL_BITS))
        rd_lat_f = jnp.where(is_bram, float(BRAM_READ_LATENCY),
                             float(SRL_READ_LATENCY))
        fifo = b["fifo"].astype(jnp.int32)             # (C, E*)
        rd_lat_e = jnp.take_along_axis(rd_lat_f, fifo, axis=1)
        d_e = jnp.take_along_axis(d, fifo, axis=1)
        bp_pos = b["rank"].astype(jnp.int32) - d_e
        is_write = b["is_write"]
        overrun = is_write & (bp_pos >= b["evt_n_reads"])
        structural = jnp.any(overrun, axis=1)          # (C,)
        bp_valid = (is_write & (bp_pos >= 0) & ~overrun
                    ).astype(jnp.float32)
        flat = jnp.clip(b["evt_read_base"] + bp_pos, 0,
                        b["n_flat_reads"][:, None] - 1)
        bp_idx = jnp.take_along_axis(
            b["read_evt_flat"].astype(jnp.int32), flat, axis=1)
        tables = (b["delta"], b["seg_start"], b["is_read"], b["has_data"],
                  b["data_idx"].astype(jnp.int32), b["end_bonus"],
                  rd_lat_e, bp_idx, bp_valid)
        if use_ref:
            out = fifo_eval_ref_hetero(*tables, b["bound"],
                                       max_iters=max_iters)
        else:
            bp_base = jnp.ones((1, rd_lat_e.shape[1]), jnp.float32)
            out, _ = fifo_eval_pallas(*tables, bp_base, max_iters=max_iters,
                                      bound=b["bound"], interpret=interpret)
        lat = jnp.maximum(out[:, 0], b["taskless"])
        conv = out[:, 1] > 0
        over = out[:, 2] > 0
        status = jnp.where(
            structural | over, DEADLOCK,
            jnp.where(conv, CONVERGED, UNRESOLVED)).astype(jnp.int8)
        bram = jnp.sum(bram_count_jnp(d, w), axis=1).astype(jnp.int32)
        return lat, bram, status

    if mesh is not None:
        run = _shard_over_rows(run, mesh)
    run = jax.jit(run)

    def call(batch: dict) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        DISPATCH_COUNTS["hetero"] += 1
        with span("hetero.h2d", stats, "prep_s"):
            out = run({k: jnp.asarray(v) for k, v in batch.items()})
        with span("hetero.wait", stats, "wait_s"):
            lat, bram, status = jax.device_get(out)
        lat = np.asarray(np.rint(lat), dtype=np.int64)
        return lat, np.asarray(bram, dtype=np.int64), np.asarray(status)

    call.run = run
    return call
