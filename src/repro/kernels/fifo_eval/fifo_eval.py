"""Pallas TPU kernel: batched FIFO-configuration latency evaluation.

Each grid program evaluates a block of ``ROWS`` candidate configurations
(one f32 sublane tile); all per-event state lives in VMEM as (ROWS, E)
float32/int32 tiles (E padded to a multiple of 128 lanes).  Each Jacobi
iteration is

    cross-edge gathers (data + back-pressure)  ->  VPU max/where ops
    ->  segmented max-plus scan via STATIC Hillis-Steele doubling
        (ceil(log2 E) roll+mask+combine vector steps, fully unrolled)

The outer ``lax.while_loop`` stops when every row of the block has
converged, exceeded the design's schedule upper bound (deadlock), or hit
the iteration cap; finished rows are frozen while the rest keep stepping,
so each row's result equals its own solo fixpoint.

Gathers: Mosaic gathers lanes only within one 128-lane vreg
(``tpu.dynamic_gather``), so each 128-lane output chunk is assembled
from the DISTINCT 128-lane source chunks its indices reference — one
in-vreg lane gather plus a select per (output chunk, source chunk) pair.
Pure data movement: exact for any f32 value.  Trace-ordered dataflow
keeps the distinct-source count per chunk small (a chunk of reads draws
from the few writer segments feeding it).  The pairs never change within
a launch (data-edge indices are fixed per graph, back-pressure indices
per row), so :func:`_schedule` finds them once per grid program, before
the Jacobi loop, and records each output chunk's sources, smallest
first, in an SMEM table of ``GATHER_K`` (64) slots a chunk; every
iteration :func:`_replay` loads exactly those chunks, with no cross-lane
reduction in the loop.  A block in which some output chunk of either
in-loop table names more source chunks than it has slots walks them
anew each iteration (:func:`_gather`) for the whole launch, and says so
in lane 4.

The kernels compile with Mosaic on a TPU and run in the Pallas
interpreter on the CPU (tests); :func:`interpret_for` is the single rule.

Layout of the per-config output row (float32, 128 lanes):
    [0] latency   [1] converged (0/1)   [2] over-bound (0/1)   [3] iters
    [4] replayed (0/1: the block's gathers replayed their schedule)
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = np.float32(-1e9)   # numpy scalar: must not become a captured tracer
OUT_LANES = 128
LANES = 128
_LANE_BITS = 7           # log2(LANES)
#: configurations per raw-kernel grid program: one f32 sublane tile
ROWS = 8
#: scoped-VMEM limit handed to Mosaic (a TPU v5e core has 128 MiB of
#: VMEM; the default scoped limit of 16 MiB is too small for the raw
#: kernel at 33k events or the condensed kernel's certificate tiles)
VMEM_LIMIT = 64 * 2**20
#: source-chunk slots a gather schedule keeps per 128-lane output chunk.
#: k15mmtree_relu's output chunks read at most 5 source chunks through
#: its data edges and 17 through the back-pressure edges of the 8-row
#: blocks its grouped_random and grouped_sa searches send; 8 rows of
#: uniformly random depths reach 36, and a cross-design block that
#: straddles two designs 65.
GATHER_K = 64
#: SMEM words one gather schedule may take: a kernel keeps two, and a
#: TPU v5e core has 1 MiB of SMEM
_SCHEDULE_WORDS = 2**16


def interpret_for(platform: str) -> bool:
    """Whether the Pallas kernels run interpreted on ``platform``:
    compiled by Mosaic on ``"tpu"``, interpreted on ``"cpu"`` (tests);
    any other platform has no kernel path and raises."""
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise ValueError(
        f"the fifo_eval Pallas kernels run on 'tpu' (compiled) or 'cpu' "
        f"(interpreted), not on {platform!r}")


def kernel_platform(mesh=None) -> str:
    """Platform of the device the kernels will run on: the first device
    of ``mesh`` when given, else JAX's default device."""
    dev = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    return dev.platform


def kernel_interpret(mesh=None) -> bool:
    """:func:`interpret_for` the device the kernels will run on."""
    return interpret_for(kernel_platform(mesh))


def _num_scan_steps(e_pad: int) -> int:
    steps = 0
    while (1 << steps) < e_pad:
        steps += 1
    return steps


def _shift(x, sh: int, fill):
    """``x`` shifted ``sh`` lanes toward higher indices, ``fill`` entering
    at lane 0 (a static roll plus a mask)."""
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane >= sh, pltpu.roll(x, sh, 1), fill)


def _seg_scan(a, m, n_steps: int):
    """Inclusive max-plus scan, Hillis-Steele doubling (static shifts)."""
    for s in range(n_steps):
        sh = 1 << s
        m = jnp.maximum(_shift(m, sh, NEG) + a, m)
        a = _shift(a, sh, 0.0) + a
    return a, m


def _chunk_index(idx_ref, o, rows: int, n_src: int):
    """Column slice of output chunk ``o`` and its indices split into
    (source chunk, lane within it), each (rows, LANES)."""
    col = pl.ds(pl.multiple_of(o * LANES, LANES), LANES)
    idx = jnp.broadcast_to(idx_ref[:, col], (rows, LANES))
    hi = jnp.minimum(lax.shift_right_logical(idx, _LANE_BITS), n_src - 1)
    return col, hi, idx & (LANES - 1)


def _load_from(src_ref, s, lo):
    """Lanes ``lo`` of 128-lane source chunk ``s`` (a scalar)."""
    src = src_ref[:, pl.ds(pl.multiple_of(s * LANES, LANES), LANES)]
    return jnp.take_along_axis(src, lo, axis=1, mode="promise_in_bounds")


def _gather(src_ref, idx_ref, dst_ref):
    """``dst[r, j] = src[r, idx[r, j]]`` for every row of the block.

    ``idx_ref`` is (1, N) (shared by all rows) or (rows, N); every index
    must lie in ``[0, src width)``.  Each 128-lane output chunk walks
    the distinct source chunks its indices name, smallest first, finding
    each with a lane reduction.
    """
    rows, n_src = src_ref.shape[0], src_ref.shape[1] // LANES
    n_out = dst_ref.shape[1] // LANES

    def out_chunk(o, carry):
        col, hi, lo = _chunk_index(idx_ref, o, rows, n_src)

        def pending(state):
            # each pass retires >= 1 source chunk: n_src passes at most
            return (state[0] < n_src) & (jnp.max(state[2]) > 0)

        def src_chunk(state):
            k, got, left = state
            s = jnp.min(jnp.where(left > 0, hi, n_src))
            hit = (left > 0) & (hi == s)
            return (k + 1, jnp.where(hit, _load_from(src_ref, s, lo), got),
                    jnp.where(hit, 0, left))

        _, got, _ = lax.while_loop(
            pending, src_chunk,
            (jnp.int32(0), jnp.zeros((rows, LANES), jnp.float32),
             jnp.ones((rows, LANES), jnp.int32)))
        dst_ref[:, col] = got
        return carry

    lax.fori_loop(0, n_out, out_chunk, 0)


def _slots(n_out: int) -> int:
    """Source-chunk slots per output chunk of a schedule over ``n_out``
    output chunks: ``GATHER_K``, fewer where the table would pass
    ``_SCHEDULE_WORDS`` (graphs of more than about 250k events)."""
    return max(1, min(GATHER_K, _SCHEDULE_WORDS // n_out - 1))


def schedule_scratch(e_pad: int):
    """SMEM scratch for one gather schedule over ``e_pad`` output
    events: per 128-lane output chunk, its source-chunk count and then
    :func:`_slots` source-chunk slots."""
    n_out = e_pad // LANES
    return pltpu.SMEM((n_out * (_slots(n_out) + 1),), jnp.int32)


def _schedule(rows: int, n_src: int, idx_ref, sched_ref):
    """Record in ``sched_ref`` (:func:`schedule_scratch`) the distinct
    source chunks that each output chunk of ``idx_ref`` reads, smallest
    first, as :func:`_gather` walks them; returns whether every chunk
    fit in its slots.  The last slot of a chunk that does not fit is
    overwritten, and such a schedule must not be replayed."""
    n_out = idx_ref.shape[1] // LANES
    slots = _slots(n_out)
    stride = slots + 1

    def out_chunk(o, widest):
        _, hi, _ = _chunk_index(idx_ref, o, rows, n_src)

        def pending(state):
            return (state[0] < n_src) & (jnp.max(state[1]) > 0)

        def src_chunk(state):
            k, left = state
            s = jnp.min(jnp.where(left > 0, hi, n_src))
            sched_ref[o * stride + 1 + jnp.minimum(k, slots - 1)] = s
            return k + 1, jnp.where(hi == s, 0, left)

        k, _ = lax.while_loop(pending, src_chunk,
                              (jnp.int32(0),
                               jnp.ones((rows, LANES), jnp.int32)))
        sched_ref[o * stride] = k
        return jnp.maximum(widest, k)

    return lax.fori_loop(0, n_out, out_chunk, jnp.int32(0)) <= slots


def _replay(src_ref, idx_ref, dst_ref, sched_ref):
    """:func:`_gather` from the schedule :func:`_schedule` recorded for
    ``idx_ref``: each output chunk loads the source chunks named in its
    SMEM row, with no lane reduction.  Bit-identical to the walk, since
    every lane's source chunk is among them."""
    rows, n_src = src_ref.shape[0], src_ref.shape[1] // LANES
    n_out = dst_ref.shape[1] // LANES
    stride = _slots(n_out) + 1

    def out_chunk(o, carry):
        col, hi, lo = _chunk_index(idx_ref, o, rows, n_src)

        def src_chunk(k, got):
            s = sched_ref[o * stride + 1 + k]
            return jnp.where(hi == s, _load_from(src_ref, s, lo), got)

        dst_ref[:, col] = lax.fori_loop(
            0, sched_ref[o * stride], src_chunk,
            jnp.zeros((rows, LANES), jnp.float32))
        return carry

    lax.fori_loop(0, n_out, out_chunk, 0)


def _fixpoint(delta_ref, segst_ref, isread_ref, hasdata_ref, didx_ref,
              rdlat_ref, bpidx_ref, bpval_ref, bpbase_ref,
              t_ref, td_ref, tb_ref, dsched_ref, bsched_ref, *,
              max_iters: int, bound: float):
    """Row-frozen Jacobi fixpoint of one row block, left in ``t_ref``.

    Operand refs are (1, E) when shared by the block's rows, else (rows,
    E); ``bound`` is a float or a (rows, 1) array of per-row bounds;
    ``td_ref`` / ``tb_ref`` are (rows, E) scratch for the gathered data
    and back-pressure sources, ``dsched_ref`` / ``bsched_ref`` their
    gather schedules (:func:`schedule_scratch`).  Returns ``(iters,
    conv, over, replayed)`` with the flags as (rows, 1) f32 0/1 columns
    and ``replayed`` a scalar: whether the gathers replayed their
    schedules rather than walking every iteration.
    """
    rows, e_pad = t_ref.shape
    n_steps = _num_scan_steps(e_pad)
    replayed = (_schedule(rows, e_pad // LANES, didx_ref, dsched_ref)
                & _schedule(rows, e_pad // LANES, bpidx_ref, bsched_ref))

    def step():
        @pl.when(replayed)
        def _():
            _replay(t_ref, didx_ref, td_ref, dsched_ref)   # data edges
            _replay(t_ref, bpidx_ref, tb_ref, bsched_ref)  # bp edges

        @pl.when(jnp.logical_not(replayed))
        def _():
            _gather(t_ref, didx_ref, td_ref)
            _gather(t_ref, bpidx_ref, tb_ref)

        bd = jnp.where(hasdata_ref[...] > 0, td_ref[...] + rdlat_ref[...],
                       NEG)
        bb = jnp.where(bpval_ref[...] > 0, tb_ref[...] + bpbase_ref[...],
                       NEG)
        b = jnp.where(isread_ref[...] > 0, bd, bb)
        segst, delta = segst_ref[...], delta_ref[...]
        m = jnp.where(segst > 0, jnp.maximum(b, delta), b)
        a, m = _seg_scan(jnp.where(segst > 0, NEG, delta), m, n_steps)
        return jnp.maximum(a, m)

    def cond(state):
        it, conv, over = state
        active = jnp.max((1.0 - conv) * (1.0 - over)) > 0
        return (it == 0) | ((it < max_iters) & active)

    def body(state):
        # per-row freezing: finished rows (converged or past the bound)
        # keep their times and flags while active rows step
        it, conv, over = state
        t = t_ref[...]
        active = (conv == 0) & (over == 0)            # (rows, 1)
        t2 = jnp.where(active, step(), t)
        same = jnp.min(jnp.where(t2 == t, 1.0, 0.0), axis=1, keepdims=True)
        conv = jnp.where(active & (same > 0), 1.0, conv)
        peak = jnp.max(t2, axis=1, keepdims=True)
        over = jnp.where(active & (peak > bound), 1.0, over)
        t_ref[...] = t2
        return it + 1, conv, over

    t_ref[...] = jnp.zeros((rows, e_pad), jnp.float32)
    flags = jnp.zeros((rows, 1), jnp.float32)
    iters, conv, over = lax.while_loop(cond, body,
                                       (jnp.int32(0), flags, flags))
    return iters, conv, over, replayed


def _result_rows(*cols):
    """(rows, OUT_LANES) f32 block with ``cols[k]`` (each (rows, 1) or a
    scalar) in lane k and zeros elsewhere."""
    rows = next(c.shape[0] for c in cols if jnp.ndim(c) == 2)
    lane = lax.broadcasted_iota(jnp.int32, (rows, OUT_LANES), 1)
    out = jnp.zeros((rows, OUT_LANES), jnp.float32)
    for k, c in enumerate(cols):
        out = jnp.where(lane == k, jnp.asarray(c, jnp.float32), out)
    return out


def _fifo_eval_kernel(*refs, max_iters: int, bound, with_times: bool):
    """Refs: the ten operands of :func:`fifo_eval_pallas`, each (1, E)
    shared or (ROWS, E) per-config; when ``bound`` is None the per-row
    deadlock bounds, each repeated along a (ROWS, LANES) row; the outputs
    (result rows, then with_times the final event times); three (ROWS, E)
    f32 scratch tiles and the two gather schedules."""
    (delta_ref, segst_ref, isread_ref, hasdata_ref, didx_ref, endb_ref,
     rdlat_ref, bpidx_ref, bpval_ref, bpbase_ref) = refs[:10]
    rest = refs[10:]
    if bound is None:
        # a lane reduction, like the peak it is compared with: Mosaic
        # cannot broadcast a loaded (ROWS, 1) column across lanes
        bound = jnp.max(rest[0][...], axis=1, keepdims=True)
        rest = rest[1:]
    out_ref = rest[0]
    t_ref, td_ref, tb_ref, dsched_ref, bsched_ref = rest[-5:]
    iters, conv, over, replayed = _fixpoint(
        delta_ref, segst_ref, isread_ref, hasdata_ref, didx_ref,
        rdlat_ref, bpidx_ref, bpval_ref, bpbase_ref, t_ref, td_ref, tb_ref,
        dsched_ref, bsched_ref, max_iters=max_iters, bound=bound)
    t = t_ref[...]
    latency = jnp.max(t + endb_ref[...], axis=1, keepdims=True)
    out_ref[...] = _result_rows(latency, conv, over, iters,
                                replayed.astype(jnp.int32))
    if with_times:
        rest[1][...] = t


def fifo_eval_pallas(
    delta: jnp.ndarray, segst: jnp.ndarray, is_read: jnp.ndarray,
    has_data: jnp.ndarray, data_idx: jnp.ndarray, end_bonus: jnp.ndarray,
    rd_lat: jnp.ndarray, bp_idx: jnp.ndarray, bp_valid: jnp.ndarray,
    bp_base: jnp.ndarray, *, max_iters: int, bound,
    interpret: bool, with_times: bool = False,
):
    """Launch the kernel.

    Per-config operands are (C, E); E must be a multiple of 128.  The
    event tables (``delta`` .. ``end_bonus``) and ``bp_base`` are either
    (1, E), shared by every row of one graph, or (C, E), one table per
    row (cross-design batches mixing graphs padded to one E).  ``bound``
    is the deadlock threshold: a float for one graph, or a (C,) array of
    per-row bounds.  The batch is padded to a ``ROWS`` multiple by
    repeating its last row.  Returns (C, OUT_LANES) float32 result rows,
    plus the final (C, E) event times when ``with_times`` (the
    condensation certificate needs them; the extra output is skipped
    otherwise).  ``interpret`` comes from :func:`kernel_interpret`.
    """
    C, e_pad = rd_lat.shape
    assert e_pad % LANES == 0, "pad events to a lane multiple"
    pad = -C % ROWS
    n = C + pad

    def per_row(x):
        if pad:
            x = jnp.concatenate(
                [x, jnp.broadcast_to(x[-1:], (pad,) + x.shape[1:])])
        return x

    shared = pl.BlockSpec((1, e_pad), lambda i: (0, 0))
    percfg = pl.BlockSpec((ROWS, e_pad), lambda i: (i, 0))
    args, in_specs = [], []
    for x in (delta, segst, is_read, has_data, data_idx, end_bonus,
              rd_lat, bp_idx, bp_valid, bp_base):
        # a one-row operand is the same for every row (at C == 1 the pad
        # rows repeat it anyway)
        one = x.shape[0] == 1
        args.append(x if one else per_row(x))
        in_specs.append(shared if one else percfg)
    static_bound = None
    if jnp.ndim(bound) == 0:
        static_bound = float(bound)
    else:
        args.append(per_row(jnp.broadcast_to(
            jnp.asarray(bound, jnp.float32)[:, None], (C, LANES))))
        in_specs.append(pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)))
    kernel = functools.partial(_fifo_eval_kernel, max_iters=max_iters,
                               bound=static_bound, with_times=with_times)
    out_specs = [pl.BlockSpec((ROWS, OUT_LANES), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((n, OUT_LANES), jnp.float32)]
    if with_times:
        out_specs.append(percfg)
        out_shape.append(jax.ShapeDtypeStruct((n, e_pad), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=(n // ROWS,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=([pltpu.VMEM((ROWS, e_pad), jnp.float32)] * 3
                        + [schedule_scratch(e_pad)] * 2),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="fifo_eval_raw",
    )(*args)
    rows = out[0][:C]
    return rows, (out[1][:C] if with_times else None)
