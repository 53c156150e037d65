"""Device-mesh sharded evaluation: bit-identity, padding, and topology.

The sharded backend (``repro.core.backends.mesh``) must be
indistinguishable from the solo evaluators in everything but wall-clock:
identical latencies, BRAM, and deadlock verdicts across the worklist,
fixpoint, and Pallas backends on fuzz-corpus designs; exact under ragged
batches whose row count is not a shard multiple; and campaign/hetero
dispatch with a mesh must reproduce sequential frontiers bit for bit.

This module arms a 4-device host-platform CPU mesh at import (i.e. at
pytest collection, before any test computes through jax).  When the
environment already initialized jax on fewer devices — e.g. running this
file after a jax-touching REPL — the multi-device tests skip instead of
crashing; the CI mesh job runs the file under an 8-device XLA_FLAGS
anyway.
"""

import glob
import json
import os
from pathlib import Path

import numpy as np
import pytest

from repro.core.config import EvalConfig
from repro.launch.mesh import (device_grid, ensure_host_platform_devices,
                               make_campaign_mesh, make_eval_mesh)

# must happen at import time, before jax's backends initialize
ensure_host_platform_devices(4)

jax = pytest.importorskip("jax")

CORPUS_DIR = os.path.join(os.path.dirname(__file__), "fuzz_corpus")


def _need_devices(n: int):
    if jax.device_count() < n:
        pytest.skip(f"needs >= {n} devices "
                    f"(jax initialized with {jax.device_count()})")


def _corpus_graphs():
    from repro.core import build_simgraph
    from repro.designs.generate import DesignSpec, build_design
    graphs = []
    for path in sorted(glob.glob(os.path.join(CORPUS_DIR, "*.json"))):
        with open(path) as f:
            spec = DesignSpec.from_json(json.load(f)["spec"])
        gen = build_design(spec)
        graphs.append((os.path.basename(path), build_simgraph(gen.design)))
    assert graphs, "tests/fuzz_corpus/*.json missing"
    return graphs


def _configs(g, C, seed=0, lo=0.1):
    """Depth batch spanning feasible AND deadlock-prone corners."""
    rng = np.random.default_rng(seed)
    u = np.asarray(g.upper_bounds, dtype=np.int64)
    rows = [u, np.ones_like(u)]
    rows += [np.maximum(1, (u * rng.uniform(lo, 1.0, u.size))
                        .astype(np.int64)) for _ in range(C - 2)]
    return np.stack(rows[:C])


# ------------------------------------------------------------- identity
def test_sharded_matches_every_solo_backend_on_corpus():
    """mesh == worklist == fixpoint == pallas (latency, BRAM, deadlock)
    on every committed fuzz-corpus design."""
    _need_devices(4)
    from repro.core.simulate import BatchedEvaluator
    for name, g in _corpus_graphs():
        cfgs = _configs(g, 10, seed=hash(name) % 1000)
        ref = BatchedEvaluator(
            g, EvalConfig(backend="numpy", max_iters=128)).evaluate(cfgs)
        for backend, kw in [("jax", {}), ("pallas", {}),
                            ("mesh", {"shards": 4}),
                            ("mesh", {"shards": 2})]:
            got = BatchedEvaluator(
                g, EvalConfig(backend=backend, max_iters=128, **kw)
            ).evaluate(cfgs)
            for a, b in zip(ref, got):
                np.testing.assert_array_equal(
                    a, b, err_msg=f"{name}:{backend}:{kw}")


def test_deadlock_verdicts_identical_across_shard_counts():
    """mult_by_2(n) deadlocks iff depth(x) < n - 1; the sharded path
    must agree on both sides of the boundary at every shard count."""
    _need_devices(4)
    from repro.core import build_simgraph
    from repro.core.simulate import BatchedEvaluator
    from repro.designs.ddcf import mult_by_2
    g = build_simgraph(mult_by_2(16))
    cfgs = np.array([[14, 2], [15, 2], [16, 2], [2, 2], [13, 3]])
    expect_dead = np.array([True, False, False, True, True])
    for shards in (1, 2, 4):
        lat, _, dead = BatchedEvaluator(
            g, EvalConfig(backend="mesh", max_iters=64,
                          shards=shards)).evaluate(cfgs)
        np.testing.assert_array_equal(dead, expect_dead,
                                      err_msg=f"shards={shards}")
        assert (lat[dead] == -1).all()


def test_ragged_batches_pad_to_shard_multiples_exactly():
    """Row counts that are not shard multiples (including C=1 and C above
    a bucket boundary) are padded, evaluated, and sliced back exactly."""
    _need_devices(4)
    from repro.core import build_simgraph
    from repro.core.simulate import BatchedEvaluator
    from repro.designs import make_design
    g = build_simgraph(make_design("gemm"))
    solo = BatchedEvaluator(g, EvalConfig(backend="jax", max_iters=64))
    mesh = BatchedEvaluator(
        g, EvalConfig(backend="mesh", max_iters=64, shards=4))
    assert mesh.dispatch.shard_multiple == 4
    all_cfgs = _configs(g, 13, seed=7)
    for C in (1, 3, 5, 13):
        cfgs = all_cfgs[:C]
        ref = solo.evaluate(cfgs)
        got = mesh.evaluate(cfgs)
        for a, b in zip(ref, got):
            assert a.shape[0] == C
            np.testing.assert_array_equal(a, b, err_msg=f"C={C}")


def test_pallas_inner_kernel_shards_identically():
    """MeshBackend(inner="pallas") wraps the Pallas kernel in the same
    row partitioning and returns the solo kernel's raw triples verbatim
    — statuses included (UNRESOLVED rows stay UNRESOLVED)."""
    _need_devices(2)
    from repro.core import build_simgraph
    from repro.core.backends.mesh import MeshBackend
    from repro.core.backends.pallas import PallasBackend
    from repro.designs.ddcf import mult_by_2
    g = build_simgraph(mult_by_2(24))
    cfgs = _configs(g, 6, seed=3)
    solo = PallasBackend()
    solo.prepare(g)
    ref = solo.evaluate(cfgs)
    impl = MeshBackend(shards=2, inner="pallas")
    impl.prepare(g)
    got = impl.evaluate(cfgs)   # 6 rows: already a multiple of 2 shards
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)


def test_fused_condensed_kernel_shards_identically():
    """MeshBackend(inner="pallas") prepared on a high-compression rung
    exposes the FUSED kernel (``fused_certificate``) sharded over the
    mesh; ``evaluate_certified`` — latency, BRAM, status, AND the
    on-device certificate mask — is bit-identical to the solo kernel
    across shard counts, ragged batches included."""
    _need_devices(4)
    from repro.core import build_simgraph
    from repro.core.backends.mesh import MeshBackend
    from repro.core.backends.pallas import PallasBackend
    from repro.core.condense import condense_auto
    from repro.designs import make_design
    g = build_simgraph(make_design("gemm"))
    cg = condense_auto(g)[0]          # the aggressive rung
    solo = PallasBackend()
    solo.prepare(cg)
    assert solo.fused_certificate
    cfgs = _configs(g, 9, seed=5, lo=0.4)
    ref = solo.evaluate_certified(cfgs)
    assert np.asarray(ref[3]).any(), "batch must certify some rows"
    for shards in (2, 4):
        impl = MeshBackend(shards=shards, inner="pallas")
        impl.prepare(cg)
        assert impl.fused_certificate
        for C in (1, 4, 9):           # ragged vs shard multiple
            got = impl.evaluate_certified(cfgs[:C])
            for a, b in zip((r[:C] for r in ref), got):
                np.testing.assert_array_equal(
                    a, b, err_msg=f"shards={shards} C={C}")


# ------------------------------------------------- campaign and service
def test_campaign_with_shards_matches_sequential():
    """Hetero campaign on a mesh reproduces per-task sequential
    frontiers and hypervolumes bit for bit."""
    _need_devices(4)
    from repro.core.advisor import FifoAdvisor
    from repro.core.campaign import Campaign, CampaignSpec
    from repro.designs import make_design
    spec = dict(designs=("gemm", "FeedForward"),
                optimizers=("grouped_random",), budget=30, seed=0)
    store = Campaign(CampaignSpec(**spec, hetero=True,
                                  eval=EvalConfig(shards=4))).run()
    for key in store.keys():
        dse = store[key]
        design, opt, _ = key.split(":")
        solo = FifoAdvisor(make_design(design)).run(
            optimizer=opt, budget=30, seed=0)
        pts, _ = solo.result.frontier()
        np.testing.assert_array_equal(dse.frontier_points, pts,
                                      err_msg=key)


def test_hetero_dispatcher_with_mesh_matches_per_design_worklists():
    """Sharded cross-design hetero dispatch == per-design worklists."""
    _need_devices(4)
    from repro.core import build_simgraph
    from repro.core.backends.dispatch import HeteroDispatcher
    from repro.core.simulate import BatchedEvaluator
    from repro.designs import make_design
    from repro.designs.ddcf import mult_by_2
    designs = {"m24": mult_by_2(24), "gemm": make_design("gemm")}
    graphs = {k: build_simgraph(d) for k, d in designs.items()}
    hd = HeteroDispatcher(graphs, shards=4)
    assert hd.shard_multiple == 4
    items = [(k, _configs(g, 5, seed=i))
             for i, (k, g) in enumerate(graphs.items())]
    results = hd.dispatch(items)
    for (k, cfgs), (lat, bram, dead) in zip(items, results):
        ref = BatchedEvaluator(
            graphs[k], EvalConfig(backend="numpy", max_iters=64)).evaluate(cfgs)
        np.testing.assert_array_equal(lat, ref[0], err_msg=k)
        np.testing.assert_array_equal(bram, ref[1], err_msg=k)
        np.testing.assert_array_equal(dead, ref[2], err_msg=k)


# ----------------------------------------------------- topology + wiring
def test_device_grid_factorizations():
    assert device_grid(1) == (1, 1)
    assert device_grid(8) == (2, 4)
    assert device_grid(16) == (4, 4)
    assert device_grid(7) == (1, 7)
    with pytest.raises(ValueError):
        device_grid(0)


def test_mesh_constructors_validate_device_count():
    """Requesting more shards than devices fails with a clear error
    naming the remedy, not a deep jax crash."""
    n = jax.device_count()
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_eval_mesh(n + 1)
    with pytest.raises(ValueError, match=f"needs {(n + 1) * 2} devices"):
        make_campaign_mesh(design_shards=2, eval_shards=n + 1)
    mesh = make_eval_mesh(None)
    assert mesh.axis_names == ("eval",)
    assert int(mesh.devices.size) == n


def test_spawn_preserves_mesh_and_calibration_lists_mesh():
    """spawn() clones (for condensation rungs) keep the device mesh, and
    auto-calibration races the mesh backend only on multi-device hosts."""
    _need_devices(2)
    from repro.core import build_simgraph
    from repro.core.backends.mesh import MeshBackend
    from repro.core.simulate import BatchedEvaluator
    from repro.designs.ddcf import mult_by_2
    impl = MeshBackend(shards=2)
    clone = impl.spawn()
    assert clone.mesh is impl.mesh and clone.inner == impl.inner
    g = build_simgraph(mult_by_2(24))
    ev = BatchedEvaluator(g, EvalConfig(backend="auto", max_iters=64))
    assert "mesh" in ev.calibration["probe_s"]
    assert ev.backend == min(ev.calibration["probe_s"],
                             key=ev.calibration["probe_s"].get)


def _first_jit_in_child(env):
    """Run one backend jit in a fresh process (jax binds its cache
    directory at the process's first compile — exactly the wiring,
    operands import -> configure_jax, these tests guard); returns the
    cache directory the child's jax config names."""
    import subprocess
    import sys
    env = dict(env, PYTHONPATH=os.pathsep.join(sys.path), JAX_PLATFORMS="cpu")
    code = (
        "import jax, numpy as np\n"
        "from repro.core import EvalConfig, build_simgraph\n"
        "from repro.core.simulate import BatchedEvaluator\n"
        "from repro.designs.ddcf import mult_by_2\n"
        "g = build_simgraph(mult_by_2(8))\n"
        "ev = BatchedEvaluator(g, EvalConfig(backend='jax', max_iters=64))\n"
        "ev.evaluate(np.stack([g.upper_bounds] * 2))\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True)
    return out.stdout.strip().splitlines()[-1]


def _has_entries(path):
    return os.path.isdir(path) and any("cache" in name
                                       for name in os.listdir(path))


def test_jit_cache_env_unset_is_inert():
    """Without JAX_COMPILATION_CACHE_DIR the cache stays inert outside
    the checkout: it goes to the fixed in-checkout directory
    ``<repo>/.jax_cache`` (never a temp, PID or time-derived path, and
    git-ignored), and the first backend jit writes entries there."""
    from repro.core.backends import jaxcfg
    env = {k: v for k, v in os.environ.items() if k != jaxcfg.ENV_VAR}
    assert jaxcfg.DEFAULT_DIR.parent == Path(__file__).resolve().parents[1]
    assert _first_jit_in_child(env) == str(jaxcfg.DEFAULT_DIR)
    assert _has_entries(jaxcfg.DEFAULT_DIR), \
        "backend jit wrote no persistent cache entries"


def test_jit_cache_env_populates_cache_dir(tmp_path):
    """JAX_COMPILATION_CACHE_DIR=dir is left to jax: the first backend
    jit writes its persistent cache entries into dir, and no other
    directory is set in code."""
    from repro.core.backends import jaxcfg
    cache_dir = tmp_path / "jitcache"
    env = dict(os.environ, **{jaxcfg.ENV_VAR: str(cache_dir)})
    assert _first_jit_in_child(env) == str(cache_dir)
    assert _has_entries(cache_dir), \
        "backend jit wrote no persistent cache entries"
