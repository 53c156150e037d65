"""Pallas TPU kernel: condensation-native fused evaluation + certificate.

The raw kernel (:mod:`repro.kernels.fifo_eval.fifo_eval`) runs 8-row
blocks at raw width (E = 8k-33k events).  Post-condensation the hot rungs
run at Ec = 128-3328 anchors (10-110x compression), and the exactness
certificate (``condense.verify_rows``) used to run on the HOST: every
batch paid a device->host transfer of the (C, E_pad) event-time matrix
plus an O(C x E_raw) int64 expansion just to decide which rows to accept.

This kernel owns the whole rung on-device:

* **condensed tiles** — each grid program evaluates a BLOCK of
  configurations over the rank-dense condensed stream: per-config
  operands arrive as (BLOCK, Ec_pad) tiles and certificate slots as
  (BLOCK, V_pad) tiles; Pallas's BlockSpec pipeline streams consecutive
  tiles through VMEM, double-buffering the HBM copies against compute.
* **per-row fixpoint** — the raw kernel's Jacobi + segmented
  Hillis-Steele scan (shared code), batched over the block with per-row
  freezing: converged / over-bound rows stop updating while the rest of
  the block keeps stepping.
* **fused certificate** — after the fixpoint, the dropped cross
  constraints of every folded event are checked as flat gather slots
  (``t[src] - t[dst] > thr``, see
  :func:`repro.core.backends.operands.cert_row_operands`) and the
  pass/fail verdict is emitted as output lane [4].  Times never leave
  the device; a fully-certifying batch costs exactly one dispatch.

Integer times are exact in float32 below 2**24 (asserted at evaluator
construction), so the in-kernel f32 certificate is bit-for-bit equal to
the int64 host verifier — property-tested in
``tests/test_condensed_kernel.py``.

Compiled by Mosaic on a TPU, interpreted on the CPU (tests); the
certificate's slot gathers use the raw kernel's exact chunked gather.

Layout of the per-config output row (float32, 128 lanes):
    [0] latency  [1] converged  [2] over-bound  [3] iters  [4] certified
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fifo_eval.fifo_eval import (LANES, OUT_LANES, VMEM_LIMIT,
                                               _fixpoint, _gather,
                                               _result_rows,
                                               schedule_scratch)

#: default configurations per grid program.  Condensed tiles are narrow
#: (Ec_pad is 128-512 where raw graphs run 8k-13k events), so a block of
#: rows keeps the 8x128 vector registers busy and amortizes the per-grid
#: step overhead.
BLOCK = 32

#: working-set budget for picking a block size: half the scoped VMEM
#: limit, leaving the rest to Mosaic's own temporaries
_VMEM_BUDGET = VMEM_LIMIT // 2


def _vmem_bytes(e_pad: int, v_pad: int, block: int) -> int:
    """Working-set estimate of one grid program: double-buffered
    per-config (4) and certificate (4) tiles, the times output, gather
    scratch (3 event + 2 slot tiles), scan and certificate temporaries,
    plus the shared (1, E) rows, each padded to an 8-sublane tile and
    double-buffered."""
    return 4 * block * (19 * e_pad + 14 * v_pad) + 4 * 8 * 12 * e_pad


def pick_block(e_pad: int, v_pad: int, block: int = BLOCK) -> int:
    """Largest power-of-two block <= ``block`` whose working set fits
    the VMEM budget (never below the 8-sublane f32 min tile)."""
    while block > 8 and _vmem_bytes(e_pad, v_pad, block) > _VMEM_BUDGET:
        block //= 2
    return block


def _condensed_kernel(
    # shared (1, E) operands
    delta_ref, segst_ref, isread_ref, hasdata_ref, didx_ref, endb_ref,
    # per-config (BLOCK, E) operands
    rdlat_ref, bpidx_ref, bpval_ref, bpbase_ref,
    # per-config (BLOCK, V) certificate slots
    csrc_ref, cdst_ref, cthr_ref, cval_ref,
    # outputs (result rows, then with_times the final event times), then
    # scratch: three (BLOCK, E) and two (BLOCK, V) f32 tiles and the two
    # gather schedules
    *refs,
    max_iters: int, bound: float, with_times: bool,
):
    out_ref = refs[0]
    t_ref, td_ref, tb_ref, ts_ref, tq_ref, dsched_ref, bsched_ref = refs[-7:]
    iters, conv, over, _ = _fixpoint(
        delta_ref, segst_ref, isread_ref, hasdata_ref, didx_ref,
        rdlat_ref, bpidx_ref, bpval_ref, bpbase_ref, t_ref, td_ref, tb_ref,
        dsched_ref, bsched_ref, max_iters=max_iters, bound=bound)

    # fused exactness certificate: slot v of row c is violated iff
    # valid and t[src] - t[dst] > thr (all-integer f32, exact < 2**24)
    _gather(t_ref, csrc_ref, ts_ref)
    _gather(t_ref, cdst_ref, tq_ref)
    viol = (cval_ref[...] > 0) & (ts_ref[...] - tq_ref[...] > cthr_ref[...])
    any_viol = jnp.max(jnp.where(viol, 1.0, 0.0), axis=1, keepdims=True)
    cert = jnp.where((conv > 0) & (over == 0) & (any_viol == 0), 1.0, 0.0)

    t = t_ref[...]
    latency = jnp.max(t + endb_ref[...], axis=1, keepdims=True)
    out_ref[...] = _result_rows(latency, conv, over, iters, cert)
    if with_times:
        refs[1][...] = t


def fifo_eval_condensed(
    delta: jnp.ndarray, segst: jnp.ndarray, is_read: jnp.ndarray,
    has_data: jnp.ndarray, data_idx: jnp.ndarray, end_bonus: jnp.ndarray,
    rd_lat: jnp.ndarray, bp_idx: jnp.ndarray, bp_valid: jnp.ndarray,
    bp_base: jnp.ndarray, cert_src: jnp.ndarray, cert_dst: jnp.ndarray,
    cert_thr: jnp.ndarray, cert_valid: jnp.ndarray, *,
    max_iters: int, bound: float, interpret: bool, block: int = BLOCK,
    with_times: bool = False,
):
    """Launch the fused kernel.

    Shared operands are (1, E); per-config operands (C, E); certificate
    slots (C, V).  E and V must be multiples of 128 and C a multiple of
    ``block`` (the wrapper in ``kernels/fifo_eval/ops.py`` pads).
    Returns (C, OUT_LANES) f32 result rows ([4] = certificate verdict),
    plus the final (C, E) event times when ``with_times``.
    ``interpret`` comes from
    :func:`repro.kernels.fifo_eval.fifo_eval.kernel_interpret`.
    """
    C, e_pad = rd_lat.shape
    v_pad = cert_src.shape[1]
    assert e_pad % LANES == 0 and v_pad % LANES == 0, \
        "pad events and certificate slots to a lane multiple"
    assert C % block == 0 and block % 8 == 0, \
        "pad the config batch to a block multiple of 8 rows"
    kernel = functools.partial(
        _condensed_kernel, max_iters=max_iters, bound=bound,
        with_times=with_times)
    shared = pl.BlockSpec((1, e_pad), lambda i: (0, 0))
    percfg = pl.BlockSpec((block, e_pad), lambda i: (i, 0))
    certsp = pl.BlockSpec((block, v_pad), lambda i: (i, 0))
    out_specs = [pl.BlockSpec((block, OUT_LANES), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((C, OUT_LANES), jnp.float32)]
    if with_times:
        out_specs.append(percfg)
        out_shape.append(jax.ShapeDtypeStruct((C, e_pad), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=(C // block,),
        in_specs=[shared] * 6 + [percfg] * 4 + [certsp] * 4,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=([pltpu.VMEM((block, e_pad), jnp.float32)] * 3
                        + [pltpu.VMEM((block, v_pad), jnp.float32)] * 2
                        + [schedule_scratch(e_pad)] * 2),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="fifo_eval_condensed",
    )(delta, segst, is_read, has_data, data_idx, end_bonus,
      rd_lat, bp_idx, bp_valid, bp_base,
      cert_src, cert_dst, cert_thr, cert_valid)
    return out[0], (out[1] if with_times else None)
