"""Compile the Pallas kernels for a described TPU v5e chip.

Mosaic refuses what the Pallas interpreter accepts (unaligned blocks,
cross-vreg gathers, too much VMEM), so these tests compile the kernels of
the device path with ``interpret=False`` at the shapes of the suite's real
graphs.  No chip is needed: the TPU compiler is given a described
``v5e:2x2`` topology and the operands as ``ShapeDtypeStruct``.  Nothing
runs, so results are covered by the interpreter tests and the on-chip
smoke run (``chip_smoke.py``), not here.

The topology is described only inside the module-scoped fixture below: a
process that describes it loads the TPU library and holds it until it
exits, so it must happen in the test that needs it, never at import.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import build_simgraph
from repro.core.backends.operands import get_cert_tables, get_operands
from repro.core.condense import condense_auto
from repro.designs import make_design
from repro.kernels.fifo_eval import ops as ops_mod
from repro.kernels.fifo_eval.condensed import fifo_eval_condensed, pick_block
from repro.kernels.fifo_eval.fifo_eval import ROWS, fifo_eval_pallas


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _rungs(name):
    g = build_simgraph(make_design(name))
    return g, {cg.tag: cg for cg in condense_auto(g)}


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(sharding, ops, rows, v_pad=None):
    """ShapeDtypeStructs for the kernels' operands at ``ops``' widths."""
    f32, i32 = jnp.float32, jnp.int32
    e = ops.e_pad
    shared = [_spec(sharding, (1, e), f32)] * 4 + [
        _spec(sharding, (1, e), i32), _spec(sharding, (1, e), f32)]
    percfg = [_spec(sharding, (rows, e), f32), _spec(sharding, (rows, e), i32),
              _spec(sharding, (rows, e), f32), _spec(sharding, (rows, e), f32)]
    cert = []
    if v_pad is not None:
        cert = [_spec(sharding, (rows, v_pad), i32)] * 2 + [
            _spec(sharding, (rows, v_pad), f32)] * 2
    return shared + percfg + cert


def _compile(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("design", ["gemm", "k15mmtree_relu"])
def test_condensed_kernel_compiles_at_aggressive_rung(one_chip, design):
    """The fused kernel at the aggressive rung's (E, V) and block."""
    _, rungs = _rungs(design)
    cg = rungs["aggressive"]
    ops, ct = get_operands(cg), get_cert_tables(cg)
    block = pick_block(ops.e_pad, ct.v_pad)

    def fn(*a):
        return fifo_eval_condensed(*a, max_iters=256, bound=ops.bound,
                                   block=block, interpret=False)[0]
    _compile(fn, _kernel_args(one_chip, ops, 2 * block, ct.v_pad))


def test_raw_kernel_compiles_at_k15mmtree_relu(one_chip):
    """The raw kernel at the suite's largest raw graph (33,408 events),
    with the event-time output the safe rung's host verifier reads."""
    g, _ = _rungs("k15mmtree_relu")
    ops = get_operands(g)
    assert ops.e_pad >= 33408

    def fn(*a):
        return fifo_eval_pallas(*a, max_iters=256, bound=ops.bound,
                                interpret=False, with_times=True)
    _compile(fn, _kernel_args(one_chip, ops, 2 * ROWS))


def test_condensed_batch_program_compiles(one_chip, monkeypatch):
    """One whole ``make_condensed_eval`` dispatch (depth operands,
    certificate slots, kernel, status) as the TPU would run it."""
    monkeypatch.setattr(ops_mod, "kernel_interpret", lambda mesh=None: False)
    g, rungs = _rungs("gemm")
    call = ops_mod.make_condensed_eval(rungs["aggressive"], max_iters=256)
    assert call is not None
    depths = _spec(one_chip, (32, g.n_fifos), jnp.int32)
    compiled = _compile(call.run, [depths])
    assert np.prod(compiled.out_info[0].shape) == 32


def test_hetero_batch_program_compiles(one_chip, monkeypatch):
    """One cross-design dispatch (``make_hetero_batched_eval``: per-row
    tables and bounds, the raw kernel) for gemm and k15mmtree_relu padded
    to one envelope of 33,408 events."""
    from repro.core.backends.dispatch import HeteroDispatcher
    from repro.core.backends.operands import stack_hetero
    monkeypatch.setattr(ops_mod, "kernel_interpret", lambda mesh=None: False)
    monkeypatch.setattr(ops_mod, "kernel_platform", lambda mesh=None: "tpu")
    graphs = {k: _rungs(k)[0] for k in ("gemm", "k15mmtree_relu")}
    hd = HeteroDispatcher(graphs)
    assert hd.e_pad >= 33408
    batch = stack_hetero([(hd._ext[k], np.full((8, g.n_fifos), 4))
                          for k, g in graphs.items()])
    call = ops_mod.make_hetero_batched_eval(64)
    compiled = _compile(call.run, [{k: _spec(one_chip, v.shape, v.dtype)
                                    for k, v in batch.items()}])
    assert np.prod(compiled.out_info[0].shape) == 16
