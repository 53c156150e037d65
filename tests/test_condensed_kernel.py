"""Kernel-grade differential layer for the fused condensed Pallas kernel.

The fused kernel (:mod:`repro.kernels.fifo_eval.condensed`) evaluates the
condensed fixpoint AND the exactness certificate in one launch; its
output mask decides — on device — which rows the rung cascade accepts.
A wrong mask is silently wrong *results*, so this module pins it to the
host ground truth at the bit level:

* the kernel's certificate mask equals ``condense.verify_rows`` on every
  committed fuzz-corpus design and on fresh generator seeds
  (hypothesis-shim driven), at every condensation rung,
* rows that deadlock in the raw graph can NEVER certify,
* rows failing the aggressive rung produce identical final results
  through the cascade as forcing the safe rung / raw backstop directly,
* a fully-certifying batch is device-resident: exactly one dispatch and
  the host verifier provably never runs,
* the interpret flag follows the platform (``interpret_for``): the
  kernel is interpreted here on the CPU and compiled on a TPU, where
  ``chip_smoke.py`` runs the same identities.

Integer-exactness makes every assertion ``assert_array_equal`` — never
allclose.
"""

import glob
import importlib
import json
import os

import numpy as np
import pytest
from hypothesis import given, settings
import hypothesis.strategies as st

jax = pytest.importorskip("jax")

import repro.core.backends.worklist as wl
from repro.core import build_simgraph
from repro.core.backends.base import CONVERGED, DEADLOCK
from repro.core.condense import condense_auto, verify_rows
from repro.core.config import EvalConfig
from repro.core.simulate import BatchedEvaluator
from repro.designs import make_design, mult_by_2
from repro.designs.generate import (DesignSpec, build_design,
                                    generate_design)
from repro.kernels.fifo_eval.fifo_eval import interpret_for, kernel_interpret
from repro.kernels.fifo_eval.ops import (DISPATCH_COUNTS,
                                         make_condensed_eval)

CORPUS_DIR = os.path.join(os.path.dirname(__file__), "fuzz_corpus")

# the `condense` *module* (the function re-export in repro.core shadows
# it on attribute access; needed to monkeypatch verify_rows below)
condense_mod = importlib.import_module("repro.core.condense")


def _probe_rows(g, n_random=4, seed=0):
    """all-1 / all-2 / upper-bound corners plus random rows in [1, u]."""
    rng = np.random.default_rng(seed)
    u = np.asarray(g.upper_bounds, dtype=np.int64)
    rows = [np.ones_like(u), np.full_like(u, 2), u.copy()]
    for _ in range(n_random):
        rows.append(rng.integers(1, u + 1))
    return np.stack(rows).astype(np.int32)


def _hot_rows(g, C, seed=0):
    """Feasible-leaning rows (the cascade's in-box hot path)."""
    rng = np.random.default_rng(seed)
    u = np.asarray(g.upper_bounds, dtype=np.int64)
    return np.stack([np.maximum(
        2, (u * rng.uniform(0.5, 1.0, g.n_fifos)).astype(int))
        for _ in range(C)]).astype(np.int32)


def _assert_kernel_cert_matches_verify_rows(g, rows):
    """For every rung with expressible certificate tables: the kernel's
    on-device mask == CONVERGED & host ``verify_rows``, bit for bit."""
    n_checked = 0
    for cg in condense_auto(g):
        fused = make_condensed_eval(cg, max_iters=64, with_times=True)
        if fused is None:
            continue                  # no cert tables -> host verifier
        lat, bram, status, cert, times = (np.asarray(x)
                                          for x in fused(rows))
        t_int = np.asarray(np.rint(times), dtype=np.int64)
        expected = np.zeros(rows.shape[0], dtype=bool)
        conv = status == CONVERGED
        if conv.any():
            expected[conv] = verify_rows(cg, rows[conv].astype(np.int64),
                                         t_int[conv])
        np.testing.assert_array_equal(np.asarray(cert, bool), expected)
        # certified rows really are the raw least fixpoint
        for i in np.flatnonzero(cert):
            raw = wl.solve(g, rows[i].astype(np.int64))
            assert not raw.deadlocked
            assert int(lat[i]) == raw.latency
        n_checked += 1
    return n_checked


# ------------------------------------------------ mask == verify_rows
def test_kernel_cert_equals_verify_rows_on_corpus():
    """Every committed fuzz-corpus design, every rung: the fused mask is
    bit-identical to the host certificate."""
    paths = sorted(glob.glob(os.path.join(CORPUS_DIR, "*.json")))
    assert paths, "tests/fuzz_corpus/*.json missing"
    n_rungs = 0
    for path in paths:
        with open(path) as f:
            spec = DesignSpec.from_json(json.load(f)["spec"])
        g = build_simgraph(build_design(spec).design)
        n_rungs += _assert_kernel_cert_matches_verify_rows(
            g, _probe_rows(g, n_random=3))
    # at least one corpus design must actually exercise the kernel path
    assert n_rungs > 0


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=3000))
def test_kernel_cert_equals_verify_rows_fresh_seeds(seed):
    """Fresh generator seeds (hypothesis-shim driven): same bit-for-bit
    mask identity on arbitrary quick designs."""
    gen = generate_design(seed, quick=True)
    g = build_simgraph(gen.design)
    _assert_kernel_cert_matches_verify_rows(
        g, _probe_rows(g, n_random=2, seed=seed))


def test_kernel_cert_matches_on_benchmark_designs():
    """The paper's benchmark designs (the rungs auto-calibration races)
    hold the same identity on the differential row set + hot rows."""
    for name in ["gemm", "FeedForward"]:
        g = build_simgraph(make_design(name))
        rows = np.concatenate([_probe_rows(g, n_random=2),
                               _hot_rows(g, 6, seed=1)])
        assert _assert_kernel_cert_matches_verify_rows(g, rows) > 0


# ----------------------------------------------- deadlock soundness
@pytest.mark.parametrize("factory", [
    lambda: mult_by_2(24),
    lambda: make_design("k15mmtree"),
])
def test_deadlocked_rows_never_certify(factory):
    """A row that deadlocks in the RAW graph can never leave the kernel
    with a certificate: either the condensed solve deadlocks too (status
    DEADLOCK, cert forced off) or the certificate check fails."""
    g = build_simgraph(factory())
    rows = _probe_rows(g, n_random=4, seed=2)
    raw_dead = np.array([wl.solve(g, r.astype(np.int64)).deadlocked
                         for r in rows])
    assert raw_dead.any(), "probe rows must include deadlocks"
    for cg in condense_auto(g):
        fused = make_condensed_eval(cg, max_iters=64)
        if fused is None:
            continue
        _, _, status, cert = (np.asarray(x) for x in fused(rows))
        assert not (np.asarray(cert, bool) & raw_dead).any()
        # and DEADLOCK status always implies no certificate
        assert not (np.asarray(cert, bool)
                    & (status == DEADLOCK)).any()


# ------------------------------------------- cascade escalation paths
def test_cascade_escalation_identical_to_forced_rungs():
    """Rows that fail the aggressive rung must come out of the full
    cascade exactly as if the safe rung / raw backstop were forced
    directly — and everything equals the numpy ground truth."""
    g = build_simgraph(make_design("FeedForward"))
    rows = np.concatenate([_probe_rows(g, n_random=3),
                           _hot_rows(g, 8, seed=3)])
    rungs = condense_auto(g)
    assert len(rungs) >= 2
    ref = BatchedEvaluator(
        g, EvalConfig(backend="numpy", max_iters=64,
                      condense=None)).evaluate(rows)
    full = BatchedEvaluator(
        g, EvalConfig(backend="pallas", max_iters=64))
    got_full = full.evaluate(rows)
    # the aggressive rung must actually reject some probe rows, or the
    # escalation path under test is vacuous
    assert full.stats.n_cond_fail > 0
    for forced_rungs in ([rungs[-1]], []):      # safe only, raw only
        ev = BatchedEvaluator(
            g, EvalConfig(backend="pallas", max_iters=64),
            rungs=forced_rungs)
        got = ev.evaluate(rows)
        for a, b, c in zip(ref, got_full, got):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)


# ------------------------------------------------- device residency
def test_fully_certifying_batch_is_device_resident(monkeypatch):
    """When every row certifies on the aggressive rung, the whole batch
    is ONE fused dispatch: no scan/batched dispatches, and the host
    verifier provably never runs (it is patched to raise)."""
    g = build_simgraph(make_design("gemm"))
    rows = _hot_rows(g, 8, seed=0)
    expected = BatchedEvaluator(
        g, EvalConfig(backend="numpy", max_iters=64,
                      condense=None)).evaluate(rows)
    ev = BatchedEvaluator(g, EvalConfig(backend="pallas", max_iters=64))
    assert any(impl.fused_certificate for _, impl in ev._cascade.rungs)
    ev.evaluate(rows)                 # warm-up: jit compile + caches

    def _boom(*a, **k):
        raise AssertionError("host verify_rows ran on the fused path")
    monkeypatch.setattr(condense_mod, "verify_rows", _boom)
    DISPATCH_COUNTS.clear()
    got = ev.evaluate(rows)
    assert dict(DISPATCH_COUNTS) == {"condensed": 1}, (
        f"expected one fused dispatch, got {dict(DISPATCH_COUNTS)}")
    for a, b in zip(expected, got):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------- interpret flag
@pytest.mark.parametrize("interpret", [True, False])
def test_kernel_runs_under_interpret_flag(interpret):
    """The kernel identity on the platform whose kernels run with this
    flag: interpreted on the CPU, compiled on a TPU (where
    ``chip_smoke.py`` also runs it; ``tests/test_tpu_compile.py``
    compiles it for a described chip)."""
    platform = "cpu" if interpret else "tpu"
    if jax.devices()[0].platform != platform:
        pytest.skip(f"runs on a {platform} device")
    assert kernel_interpret() is interpret
    g = build_simgraph(make_design("gemm"))
    _assert_kernel_cert_matches_verify_rows(g, _probe_rows(g, n_random=3))


def test_interpret_rule_follows_platform():
    """Interpreted on the CPU, compiled on a TPU, and no kernel path is
    guessed for any other platform."""
    assert interpret_for("cpu") is True
    assert interpret_for("tpu") is False
    with pytest.raises(ValueError, match="gpu"):
        interpret_for("gpu")
