"""Shared operand preparation for every evaluation backend.

Every backend consumes the same packed :class:`repro.core.simgraph.SimGraph`
but needs it massaged into padded, lane-aligned tensors (the fixpoint scan
and the Pallas kernel both want 128-lane event vectors).  Historically that
padding logic was duplicated between ``core/simulate.py`` and
``kernels/fifo_eval/ops.py``; this module is now the single source of truth:

``GraphOperands``
    The depth-INDEPENDENT operands: event tensors padded to a 128-lane
    multiple, segment-start / read masks, data-edge gather indices, the
    per-event ``end_bonus`` (task end delay at each task's last event), and
    the flattened read-event table for back-pressure gathers.  Built exactly
    once per graph (cached on the graph object) and shared by the fixpoint
    and Pallas backends — and by any future accelerator backend.

``depth_operands``
    The depth-DEPENDENT operands for a batch of candidate configurations:
    per-event read latencies, back-pressure gather indices/masks, and the
    structural-deadlock flag.  Pure jnp, traceable under jit/vmap, shared
    verbatim by the fixpoint scan, the jnp reference oracle, and the Pallas
    kernel wrapper.

``HeteroOperands`` / ``extend_operands`` / ``stack_hetero``
    The hetero-batch packer: one design's operands re-padded to a
    campaign-wide ``(E*, F*, R*)`` envelope (numpy, built once per design
    per campaign), and the per-round stacking of rows from *different*
    designs into one lane-aligned cross-design batch for the fixpoint
    backend (``repro.kernels.fifo_eval.ops.make_hetero_batched_eval``).
    Unlike :class:`GraphOperands`, every per-event table is materialized
    per row so a single vmapped dispatch can mix graphs.

Padding contract (identical to the Pallas kernel's expectations): events are
padded to ``E_pad`` (a multiple of 128, minimum 128); the first padded event
opens a fresh segment (``seg_start[E] = 1``) so the pad chain can never leak
times into real events; padded events carry ``delta = 0``, no data edge, no
back-pressure edge, and ``end_bonus = NEG`` so they contribute nothing to
the latency reduction.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

import jax.numpy as jnp

from repro.core.backends.jaxcfg import configure_jax
from repro.core.bram import BRAM18K_CONFIGS, SRL_BITS, SRL_DEPTH

# arm the persistent compilation cache before any backend's first jit
# trace — this module is the first jax import on every backend path
configure_jax()
from repro.core.design import READ, WRITE
from repro.core.simgraph import SimGraph

LANES = 128
NEG = np.float32(-1e9)


def bram_count_jnp(depths: jnp.ndarray, widths: jnp.ndarray) -> jnp.ndarray:
    """Algorithm 1, jnp-vectorized (mirrors bram.bram_count_np)."""
    d = depths.astype(jnp.int32)
    w0 = jnp.broadcast_to(widths.astype(jnp.int32), d.shape)
    n = jnp.zeros_like(d)
    w = w0
    for d_i, w_i in BRAM18K_CONFIGS:
        n = n + (w // w_i) * (-(-d // d_i))
        w = w % w_i
        fits = (w > 0) & (d <= d_i)
        n = n + fits.astype(jnp.int32)
        w = jnp.where(fits, 0, w)
    srl = (d <= SRL_DEPTH) | (d * w0 <= SRL_BITS)
    return jnp.where(srl, 0, n)


@dataclasses.dataclass(frozen=True)
class GraphOperands:
    """Depth-independent, lane-aligned event tensors for one SimGraph."""

    n_events: int            # E, real events
    e_pad: int               # E padded to a LANES multiple (>= LANES)
    n_fifos: int
    n_flat_reads: int        # R, length of the padded read_evt_flat table
    bound: float             # schedule upper bound (deadlock threshold)
    taskless_lat: float      # latency floor from tasks with no FIFO events
    # (1, E_pad) f32 — shaped for the Pallas kernel's shared operands
    delta: jnp.ndarray
    seg_start: jnp.ndarray
    is_read: jnp.ndarray
    has_data: jnp.ndarray
    end_bonus: jnp.ndarray
    # (1, E_pad) i32
    data_idx: jnp.ndarray
    # (E_pad,) per-event tables for the depth-dependent gathers
    fifo: jnp.ndarray        # i32 fifo of each event
    rank: jnp.ndarray        # i32 per-fifo op rank
    is_write: jnp.ndarray    # bool
    evt_read_base: jnp.ndarray   # i32 read_base[fifo[e]]
    evt_n_reads: jnp.ndarray     # i32 n_reads[fifo[e]]
    # (F,) / (R,)
    widths: jnp.ndarray      # i32
    read_evt_flat: jnp.ndarray   # i32
    # condensation offsets (all-zero on a raw SimGraph): the delta-chain
    # offset of a data source / back-pressure partner relative to its
    # covering anchor (see repro.core.condense)
    data_off: jnp.ndarray        # (E_pad,) f32
    read_off_flat: jnp.ndarray   # (R,) f32


def _pad_to(a: np.ndarray, n: int, fill, dtype) -> np.ndarray:
    out = np.full(n, fill, dtype=dtype)
    out[: len(a)] = a
    return out


def build_operands(g: SimGraph) -> GraphOperands:
    """Build the padded event tensors for ``g`` (use :func:`get_operands`)."""
    E = g.n_events
    e_pad = max(LANES, -(-max(E, 1) // LANES) * LANES)
    real = np.arange(e_pad) < E

    kind = _pad_to(g.kind, e_pad, READ, np.int32)   # pad kind is irrelevant
    fifo = _pad_to(g.fifo, e_pad, 0, np.int64)
    delta = _pad_to(g.delta, e_pad, 0, np.float32)
    seg_start = _pad_to(g.seg_start, e_pad, 0, np.float32)
    if E < e_pad:
        seg_start[E] = 1.0                          # isolate the pad chain
    rank = _pad_to(g.rank, e_pad, 0, np.int64)
    data_src = _pad_to(g.data_src, e_pad, -1, np.int64)

    is_read = ((kind == READ) & real).astype(np.float32)
    is_write = (kind == WRITE) & real
    has_data = ((data_src >= 0) & (is_read > 0)).astype(np.float32)
    data_idx = np.clip(data_src, 0, e_pad - 1).astype(np.int32)

    end_bonus = np.full(e_pad, float(NEG), dtype=np.float32)
    taskless_lat = 0.0
    for t in range(g.n_tasks):
        le = int(g.last_evt[t])
        if le >= 0:
            end_bonus[le] = float(g.end_delay[t])
        else:
            taskless_lat = max(taskless_lat, float(g.end_delay[t]))

    R = max(int(g.n_reads.sum()), 1)
    read_evt_flat = np.zeros(R, dtype=np.int64)
    read_evt_flat[: len(g.read_evt_flat)] = g.read_evt_flat

    # condensation offsets (zeros on a raw SimGraph)
    data_off_src = getattr(g, "data_off", None)
    data_off = np.zeros(e_pad, dtype=np.float32)
    if data_off_src is not None:
        data_off[:E] = data_off_src
    read_off_src = getattr(g, "read_off_flat", None)
    read_off_flat = np.zeros(R, dtype=np.float32)
    if read_off_src is not None:
        read_off_flat[: len(read_off_src)] = read_off_src

    return GraphOperands(
        n_events=E,
        e_pad=e_pad,
        n_fifos=g.n_fifos,
        n_flat_reads=R,
        bound=float(g.latency_upper_bound()),
        taskless_lat=taskless_lat,
        delta=jnp.asarray(delta)[None, :],
        seg_start=jnp.asarray(seg_start)[None, :],
        is_read=jnp.asarray(is_read)[None, :],
        has_data=jnp.asarray(has_data)[None, :],
        end_bonus=jnp.asarray(end_bonus)[None, :],
        data_idx=jnp.asarray(data_idx)[None, :],
        fifo=jnp.asarray(fifo, dtype=jnp.int32),
        rank=jnp.asarray(rank, dtype=jnp.int32),
        is_write=jnp.asarray(is_write),
        evt_read_base=jnp.asarray(g.read_base.astype(np.int64)[fifo],
                                  dtype=jnp.int32),
        evt_n_reads=jnp.asarray(g.n_reads.astype(np.int64)[fifo],
                                dtype=jnp.int32),
        widths=jnp.asarray(g.widths, dtype=jnp.int32),
        read_evt_flat=jnp.asarray(read_evt_flat, dtype=jnp.int32),
        data_off=jnp.asarray(data_off),
        read_off_flat=jnp.asarray(read_off_flat),
    )


def get_operands(g: SimGraph) -> GraphOperands:
    """Cached :class:`GraphOperands` for ``g`` (built once per graph)."""
    cached = getattr(g, "_operands_cache", None)
    if cached is None:
        cached = build_operands(g)
        g._operands_cache = cached
    return cached


@dataclasses.dataclass(frozen=True)
class HeteroOperands:
    """One design's event tables re-padded to a shared hetero envelope.

    All arrays are numpy (the per-round stacking is a host-side gather;
    the stacked batch is shipped to the device once per dispatch).  The
    extension region ``[own e_pad, E*)`` follows the standard padding
    contract: it opens a fresh segment, carries no edges, zero delta, and
    ``end_bonus = NEG``, so it can never leak times into real events.
    Padded FIFO columns get width 1 (with depth padded to 2 they are SRL
    by construction, contributing zero BRAM), and padded read-table slots
    are never gathered because ``evt_n_reads`` masks them out.
    """

    e_pad: int               # shared E* (lane-aligned)
    n_fifos_max: int         # shared F*
    n_flat_reads_max: int    # shared R*
    n_fifos: int             # this design's real F
    n_flat_reads: int        # this design's real R
    bound: float
    taskless_lat: float
    # (E*,) event tables
    delta: np.ndarray        # f32
    seg_start: np.ndarray    # f32
    is_read: np.ndarray      # f32
    has_data: np.ndarray     # f32
    end_bonus: np.ndarray    # f32
    data_idx: np.ndarray     # i32
    fifo: np.ndarray         # i32
    rank: np.ndarray         # i32
    is_write: np.ndarray     # bool
    evt_read_base: np.ndarray    # i32
    evt_n_reads: np.ndarray      # i32
    # (F*,) / (R*,)
    widths: np.ndarray       # i32
    read_evt_flat: np.ndarray    # i32


def _extend(a: np.ndarray, n: int, fill) -> np.ndarray:
    out = np.full(n, fill, dtype=a.dtype)
    out[: len(a)] = a
    return out


def extend_operands(ops: GraphOperands, e_pad: int, f_max: int,
                    r_max: int) -> HeteroOperands:
    """Re-pad one design's :class:`GraphOperands` to a shared envelope."""
    assert e_pad % LANES == 0 and e_pad >= ops.e_pad
    assert f_max >= ops.n_fifos and r_max >= ops.n_flat_reads
    seg_start = _extend(np.asarray(ops.seg_start)[0], e_pad, 0.0)
    if e_pad > ops.e_pad:
        seg_start[ops.e_pad] = 1.0     # isolate the extension chain
    return HeteroOperands(
        e_pad=e_pad,
        n_fifos_max=f_max,
        n_flat_reads_max=r_max,
        n_fifos=ops.n_fifos,
        n_flat_reads=ops.n_flat_reads,
        bound=ops.bound,
        taskless_lat=ops.taskless_lat,
        delta=_extend(np.asarray(ops.delta)[0], e_pad, 0.0),
        seg_start=seg_start,
        is_read=_extend(np.asarray(ops.is_read)[0], e_pad, 0.0),
        has_data=_extend(np.asarray(ops.has_data)[0], e_pad, 0.0),
        end_bonus=_extend(np.asarray(ops.end_bonus)[0], e_pad, float(NEG)),
        data_idx=_extend(np.asarray(ops.data_idx)[0], e_pad, 0),
        fifo=_extend(np.asarray(ops.fifo), e_pad, 0),
        rank=_extend(np.asarray(ops.rank), e_pad, 0),
        is_write=_extend(np.asarray(ops.is_write), e_pad, False),
        evt_read_base=_extend(np.asarray(ops.evt_read_base), e_pad, 0),
        evt_n_reads=_extend(np.asarray(ops.evt_n_reads), e_pad, 0),
        widths=_extend(np.asarray(ops.widths), f_max, 1),
        read_evt_flat=_extend(np.asarray(ops.read_evt_flat), r_max, 0),
    )


#: fields of :class:`HeteroOperands` broadcast per row by the stacker
_HETERO_ROW_FIELDS = ("delta", "seg_start", "is_read", "has_data",
                      "end_bonus", "data_idx", "fifo", "rank", "is_write",
                      "evt_read_base", "evt_n_reads", "widths",
                      "read_evt_flat")


def stack_hetero(entries) -> dict:
    """Stack ``[(HeteroOperands, (c_i, F_i) depths), ...]`` into one batch.

    Returns the dict of (C, ...) arrays consumed by
    ``make_hetero_batched_eval``; rows from different designs are simply
    concatenated — every row carries its own event tables, bound, and
    latency floor.  Depth rows are padded to F* with depth 2 (zero-BRAM
    SRL columns that no event references).
    """
    entries = [(h, np.atleast_2d(np.asarray(m, dtype=np.int64)))
               for h, m in entries]
    batch = {}
    for name in _HETERO_ROW_FIELDS:
        batch[name] = np.concatenate([
            np.broadcast_to(getattr(h, name),
                            (m.shape[0],) + getattr(h, name).shape)
            for h, m in entries], axis=0)
    batch["bound"] = np.concatenate(
        [np.full(m.shape[0], h.bound, dtype=np.float32)
         for h, m in entries])
    batch["taskless"] = np.concatenate(
        [np.full(m.shape[0], h.taskless_lat, dtype=np.float32)
         for h, m in entries])
    batch["n_flat_reads"] = np.concatenate(
        [np.full(m.shape[0], h.n_flat_reads, dtype=np.int32)
         for h, m in entries])
    depths = []
    for h, m in entries:
        pad = np.full((m.shape[0], h.n_fifos_max), 2, dtype=np.int64)
        pad[:, : m.shape[1]] = m
        depths.append(pad)
    batch["depths"] = np.concatenate(depths, axis=0)
    return batch


def depth_operands(ops: GraphOperands, depths: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                              jnp.ndarray, jnp.ndarray]:
    """Depth-dependent per-config operands (jnp, jit/vmap traceable).

    depths: (C, F) integer depth matrix.  Returns

    - ``rd_lat_e``  (C, E_pad) f32: read latency at each event's fifo
      (1 cycle SRL, 2 cycles BRAM — depends on the candidate depth) plus
      the condensation data-source offset (zero on raw graphs),
    - ``bp_idx``    (C, E_pad) i32: back-pressure gather index — write j of
      fifo f waits on read event ``j - d_f`` (its covering anchor on a
      condensed graph),
    - ``bp_valid``  (C, E_pad) f32: mask of writes with an active
      back-pressure edge,
    - ``bp_base``   (C, E_pad) f32: additive term of the back-pressure
      edge — 1.0 on raw graphs, 1.0 + covering-anchor offset on
      condensed ones,
    - ``structural`` (C,) bool: config deadlocks structurally (a write's
      back-pressure partner read does not exist).
    """
    depths = depths.astype(jnp.int32)
    is_bram = ~((depths <= SRL_DEPTH) | (depths * ops.widths <= SRL_BITS))
    rd_lat_f = 1.0 + is_bram.astype(jnp.float32)          # (C, F)
    rd_lat_e = rd_lat_f[:, ops.fifo] + ops.data_off[None, :]

    bp_pos = ops.rank[None, :] - depths[:, ops.fifo]      # (C, E_pad)
    overrun = ops.is_write[None, :] & (bp_pos >= ops.evt_n_reads[None, :])
    structural = jnp.any(overrun, axis=1)                 # (C,)
    bp_valid = (ops.is_write[None, :] & (bp_pos >= 0) & ~overrun
                ).astype(jnp.float32)
    flat = jnp.clip(ops.evt_read_base[None, :] + bp_pos, 0,
                    ops.n_flat_reads - 1)
    bp_idx = ops.read_evt_flat[flat]                      # (C, E_pad)
    bp_base = ops.read_off_flat[flat] + 1.0               # (C, E_pad)
    return rd_lat_e, bp_idx, bp_valid, bp_base, structural


# --------------------------------------------------------------------------
# fused exactness-certificate tables (condensed graphs only)
# --------------------------------------------------------------------------
#
# ``repro.core.condense.verify_rows`` checks, per depth row, every folded
# event's dropped cross constraint against the *expanded* raw-space times
# ``t_hat[e] = t_cond[cond_of[e]] + off_of[e]``.  Every one of those
# checks only ever compares two expanded times plus a per-row integer, so
# it rewrites into CONDENSED anchor space as a flat list of slots
#
#     violated  iff  valid and  t_cond[src] - t_cond[dst] > thr
#
# * folded read r (raw data source s):  src = cond_of[s],
#   dst = cond_of[r], thr = (off_of[r] - off_of[s]) - rd_lat[row, fifo_r]
#   — the read-latency term is the only depth-dependent part;
# * folded write w at rank j of fifo f with depth d:  active iff j >= d;
#   its partner read slot is ``pos = read_base[f] + j - d`` whose
#   condensed anchor/offset are exactly ``read_evt_flat[pos]`` /
#   ``read_off_flat[pos]`` (GraphOperands already carries both), so
#   src = read_evt_flat[pos], dst = cond_of[w],
#   thr = off_of[w] - read_off_flat[pos] - 1;  a write whose partner
#   read does not exist (``j - d >= n_reads[f]``) is a structural
#   deadlock at that row and is encoded as a forced-fail slot
#   (src = dst = 0, thr = -1: ``t - t > -1`` always fires).
#
# All quantities are integers below the f32-exact limit (the evaluator
# façade asserts the schedule bound < 2**24), so evaluating the slots in
# float32 *inside the kernel* is bit-for-bit equal to the int64 host
# check — the kernel can certify its own fixpoint in the same launch.


@dataclasses.dataclass(frozen=True)
class CertTables:
    """Depth-independent certificate slots for one CondensedGraph.

    Slots are padded to ``v_pad`` (a LANES multiple) with ``valid = 0``;
    the depth-dependent parts (read latencies, write activation and
    partner gathers) are filled per row by :func:`cert_row_operands`.
    """

    n_read: int              # folded-read slot count
    n_write: int             # folded-write slot count
    v_pad: int               # total slots padded to a LANES multiple
    # folded reads: static anchors, depth-dependent threshold
    r_src: jnp.ndarray       # (Nr,) i32 cond_of[data_src]
    r_dst: jnp.ndarray       # (Nr,) i32 cond_of[read]
    r_base: jnp.ndarray      # (Nr,) f32 off_of[read] - off_of[data_src]
    r_fifo: jnp.ndarray      # (Nr,) i32
    # folded writes: depth-dependent partner anchor AND threshold
    w_dst: jnp.ndarray       # (Nw,) i32 cond_of[write]
    w_dst_off: jnp.ndarray   # (Nw,) f32 off_of[write]
    w_fifo: jnp.ndarray      # (Nw,) i32
    w_rank: jnp.ndarray      # (Nw,) i32
    w_read_base: jnp.ndarray     # (Nw,) i32 read_base[fifo]
    w_n_reads: jnp.ndarray       # (Nw,) i32 n_reads[fifo]


def build_cert_tables(cg) -> Optional[CertTables]:
    """Certificate slots for a CondensedGraph (use :func:`get_cert_tables`).

    Returns None when the graph's folded tables cannot be expressed as
    gather slots (a folded read without a data source would index
    ``t_hat[:, -1]`` on the host — numpy wraps where jnp clips, so such
    graphs keep the host verifier).
    """
    vr_src = np.asarray(cg.vr_src, dtype=np.int64)
    if vr_src.size and (vr_src < 0).any():
        return None
    cond_of = np.asarray(cg.cond_of, dtype=np.int64)
    off_of = np.asarray(cg.off_of, dtype=np.float32)
    vr_idx = np.asarray(cg.vr_idx, dtype=np.int64)
    vw_idx = np.asarray(cg.vw_idx, dtype=np.int64)
    vw_fifo = np.asarray(cg.vw_fifo, dtype=np.int64)
    n_read, n_write = vr_idx.size, vw_idx.size
    v_pad = max(LANES, -(-max(n_read + n_write, 1) // LANES) * LANES)
    g = cg.raw
    return CertTables(
        n_read=n_read,
        n_write=n_write,
        v_pad=v_pad,
        r_src=jnp.asarray(cond_of[vr_src], dtype=jnp.int32),
        r_dst=jnp.asarray(cond_of[vr_idx], dtype=jnp.int32),
        r_base=jnp.asarray(off_of[vr_idx] - off_of[vr_src],
                           dtype=jnp.float32),
        r_fifo=jnp.asarray(cg.vr_fifo, dtype=jnp.int32),
        w_dst=jnp.asarray(cond_of[vw_idx], dtype=jnp.int32),
        w_dst_off=jnp.asarray(off_of[vw_idx], dtype=jnp.float32),
        w_fifo=jnp.asarray(vw_fifo, dtype=jnp.int32),
        w_rank=jnp.asarray(cg.vw_rank, dtype=jnp.int32),
        w_read_base=jnp.asarray(g.read_base[vw_fifo], dtype=jnp.int32),
        w_n_reads=jnp.asarray(g.n_reads[vw_fifo], dtype=jnp.int32),
    )


_CERT_MISS = object()


def get_cert_tables(cg) -> Optional[CertTables]:
    """Cached :class:`CertTables` for ``cg`` (None = host verify only)."""
    cached = getattr(cg, "_cert_tables_cache", _CERT_MISS)
    if cached is _CERT_MISS:
        cached = build_cert_tables(cg)
        cg._cert_tables_cache = cached
    return cached


def cert_row_operands(ops: GraphOperands, ct: CertTables,
                      depths: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                 jnp.ndarray]:
    """Per-row certificate slots (jnp, jit traceable).

    depths: (C, F) int.  Returns ``(src i32, dst i32, thr f32, valid
    f32)``, each (C, v_pad): slot ``v`` of row ``c`` is violated iff
    ``valid > 0`` and ``t[src] - t[dst] > thr`` at that row's condensed
    fixpoint — exactly the constraint ``verify_rows`` checks in raw
    index space.
    """
    depths = depths.astype(jnp.int32)
    C = depths.shape[0]
    srcs, dsts, thrs, vals = [], [], [], []
    if ct.n_read:
        is_bram = ~((depths <= SRL_DEPTH) | (depths * ops.widths <= SRL_BITS))
        rd_lat_f = 1.0 + is_bram.astype(jnp.float32)          # (C, F)
        srcs.append(jnp.broadcast_to(ct.r_src[None, :], (C, ct.n_read)))
        dsts.append(jnp.broadcast_to(ct.r_dst[None, :], (C, ct.n_read)))
        thrs.append(ct.r_base[None, :] - rd_lat_f[:, ct.r_fifo])
        vals.append(jnp.ones((C, ct.n_read), dtype=jnp.float32))
    if ct.n_write:
        d = depths[:, ct.w_fifo]                              # (C, Nw)
        j = ct.w_rank[None, :]
        act = j >= d
        overrun = act & (j - d >= ct.w_n_reads[None, :])
        pos = jnp.clip(ct.w_read_base[None, :] + j - d, 0,
                       ops.n_flat_reads - 1)
        src = jnp.where(overrun, 0, ops.read_evt_flat[pos])
        dst = jnp.where(overrun, 0,
                        jnp.broadcast_to(ct.w_dst[None, :], d.shape))
        thr = jnp.where(overrun, jnp.float32(-1.0),
                        ct.w_dst_off[None, :]
                        - ops.read_off_flat[pos] - 1.0)
        srcs.append(src)
        dsts.append(dst)
        thrs.append(thr)
        vals.append(act.astype(jnp.float32))
    n = ct.n_read + ct.n_write
    pad = ct.v_pad - n
    if pad:
        srcs.append(jnp.zeros((C, pad), dtype=jnp.int32))
        dsts.append(jnp.zeros((C, pad), dtype=jnp.int32))
        thrs.append(jnp.zeros((C, pad), dtype=jnp.float32))
        vals.append(jnp.zeros((C, pad), dtype=jnp.float32))
    return (jnp.concatenate(srcs, axis=1).astype(jnp.int32),
            jnp.concatenate(dsts, axis=1).astype(jnp.int32),
            jnp.concatenate(thrs, axis=1),
            jnp.concatenate(vals, axis=1))
