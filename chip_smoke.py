"""Smoke run of the FIFO advisor's device path on a TPU.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py              # one chip: advisor + service phases
    python chip_smoke.py --chips 4    # four chips: the sharded path only

One process, no child processes.  Phases, each printing its own lines:

* device  — the platform must be ``tpu`` (never falls back to the CPU);
* advisor — gemm, FeedForward, k15mmseq and k15mmtree_relu through
  ``FifoAdvisor(..., EvalConfig(backend="pallas"))``: a seeded batch of
  1,024 depth rows and ``grouped_sa`` at budget 400, both bit-identical
  to ``backend="numpy"``; the fused condensed kernel must be built and
  dispatched, and the dispatched programs must hold a compiled Mosaic
  kernel (``tpu_custom_call``);
* service — the advisory service on the Pallas backend, with and without
  cross-design ``hetero`` packing: 3 sessions over 2 designs, each equal
  to a solo ``FifoAdvisor.run()`` with the same seed; the cross-design
  dispatch must hold a compiled Mosaic kernel too;
* ``--chips 4`` — instead of the two phases above: ``EvalConfig(shards=4)``
  evaluation (gemm, FeedForward) and a 2-design, 2-optimizer ``hetero``
  campaign with ``shards=4``, each bit-identical to the one-chip result
  computed in this process, with rows on all four devices.

Times printed are smoke timings from the host clock around calls whose
results are fetched to the host (which waits for the device), not
metrics.  Any failed check exits non-zero; on success the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

DESIGNS = ("gemm", "FeedForward", "k15mmseq", "k15mmtree_relu")
N_ROWS = 1024
SA_BUDGET = 400
SERVICE_SESSIONS = (("gemm", "grouped_sa", 0), ("gemm", "grouped_random", 1),
                    ("FeedForward", "grouped_sa", 2))
SERVICE_BUDGET = 200
#: the four-chip cross-design campaign
CAMPAIGN = dict(designs=("gemm", "FeedForward"),
                optimizers=("grouped_sa", "grouped_random"), budget=200,
                seed=0)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def same(a, b, what: str) -> None:
    import numpy as np
    check(np.array_equal(np.asarray(a), np.asarray(b)), f"{what} differs")


def same_dse(got, ref, what: str) -> None:
    for field in ("configs", "latency", "bram", "deadlock"):
        same(getattr(got.result, field), getattr(ref.result, field),
             f"{what}: {field}")
    same(got.frontier_points, ref.frontier_points, f"{what}: frontier")


def smoke_rows(g, n: int, seed: int = 0):
    """Seeded depth rows: 7/8 inside the condensation box (the search's
    hot region, where the fused kernel certifies), 1/8 uniform over
    ``[1, upper]`` (deadlocks, the raw kernel and the worklist)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    u = np.asarray(g.upper_bounds, dtype=np.int64)
    n_box = n - n // 8
    hot = np.maximum(2, (u * rng.uniform(0.5, 1.0, (n_box, u.size)))
                     .astype(np.int64))
    wide = rng.integers(1, u + 1, size=(n - n_box, u.size))
    return np.concatenate([hot, wide])


def device_phase(chips: int):
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise SmokeFailure(
            f"found platform {platform!r} with {len(devs)} device(s); "
            f"this smoke run needs a TPU")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} TPU devices, found {len(devs)}")
    kind = devs[0].device_kind
    print(f"[device] platform=tpu kind={kind} count={len(devs)}", flush=True)
    return {"platform": platform, "kind": kind, "count": len(devs)}


def _compile_counter():
    import jax
    count = [0]

    def listener(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listener)
    return count


def _lowers_to_mosaic(call, n_rows: int, n_fifos: int) -> bool:
    import jax
    import jax.numpy as jnp
    spec = jax.ShapeDtypeStruct((n_rows, n_fifos), jnp.int32)
    return "tpu_custom_call" in call.run.lower(spec).as_text()


def _hetero_batch(hd, designs, n_rows: int) -> dict:
    """A cross-design batch for ``hd``: ``n_rows`` seeded rows of each
    named design."""
    from repro.core import build_simgraph
    from repro.core.backends.operands import stack_hetero
    from repro.designs import make_design
    return stack_hetero([
        (hd._ext[k], smoke_rows(build_simgraph(make_design(k)), n_rows))
        for k in designs])


def _hetero_lowers_to_mosaic(hd, designs) -> bool:
    batch = _hetero_batch(hd, designs, 8)
    return "tpu_custom_call" in hd._call.run.lower(batch).as_text()


def advisor_phase(compiles) -> None:
    from repro.core import FifoAdvisor
    from repro.core.config import EvalConfig
    from repro.designs import make_design
    from repro.kernels.fifo_eval.fifo_eval import kernel_interpret
    from repro.kernels.fifo_eval.ops import DISPATCH_COUNTS

    check(kernel_interpret() is False, "kernels would run interpreted")
    for name in DESIGNS:
        DISPATCH_COUNTS.clear()
        c0 = compiles[0]
        t0 = time.perf_counter()
        adv = FifoAdvisor(make_design(name),
                          config=EvalConfig(backend="pallas"))
        ref = FifoAdvisor(make_design(name),
                          config=EvalConfig(backend="numpy"))
        ev = adv.evaluator
        fused = [impl for _, impl in ev.condensation if impl.fused_certificate]
        check(bool(fused), f"{name}: no fused condensed kernel was built")
        F = adv.graph.n_fifos
        check(_lowers_to_mosaic(fused[0]._fused, 32, F),
              f"{name}: fused dispatch holds no tpu_custom_call")
        check(_lowers_to_mosaic(ev._impl._call, 8, F),
              f"{name}: raw dispatch holds no tpu_custom_call")

        rows = smoke_rows(adv.graph, N_ROWS)
        t1 = time.perf_counter()
        got = ev.evaluate(rows)
        t_rows = time.perf_counter() - t1
        want = ref.evaluator.evaluate(rows)
        for what, a, b in zip(("latency", "bram", "deadlock"), got, want):
            same(a, b, f"{name}: {N_ROWS} rows: {what}")

        t1 = time.perf_counter()
        dse = adv.run("grouped_sa", budget=SA_BUDGET, seed=0)
        t_sa = time.perf_counter() - t1
        same_dse(dse, ref.run("grouped_sa", budget=SA_BUDGET, seed=0),
                 f"{name}: grouped_sa")
        check(DISPATCH_COUNTS["condensed"] >= 1,
              f"{name}: the fused kernel was never dispatched")
        st = ev.stats
        print(f"[advisor] {name}: identical to numpy "
              f"(rows={N_ROWS}, sa_budget={SA_BUDGET}, "
              f"frontier={len(dse.frontier_points)}); "
              f"condensed_rows={st.n_condensed} cert_fail={st.n_cond_fail} "
              f"worklist_rows={st.n_fallbacks} "
              f"dispatches={dict(DISPATCH_COUNTS)} "
              f"compiles={compiles[0] - c0}; smoke timing (not a metric): "
              f"rows {t_rows:.3f}s, sa {t_sa:.3f}s, "
              f"design total {time.perf_counter() - t0:.3f}s", flush=True)


def service_phase() -> None:
    from repro.core import FifoAdvisor
    from repro.core.config import EvalConfig
    from repro.core.service import AdvisorClient
    from repro.designs import make_design
    from repro.kernels.fifo_eval.ops import DISPATCH_COUNTS

    solo = {(d, o, s): FifoAdvisor(make_design(d)).run(
        o, budget=SERVICE_BUDGET, seed=s) for d, o, s in SERVICE_SESSIONS}
    designs = sorted({d for d, _, _ in SERVICE_SESSIONS})
    for hetero in (False, True):
        DISPATCH_COUNTS.clear()
        t0 = time.perf_counter()
        client = AdvisorClient(config=EvalConfig(backend="pallas"),
                               hetero=hetero)
        handles = [client.open(d, optimizer=o, budget=SERVICE_BUDGET, seed=s)
                   for d, o, s in SERVICE_SESSIONS]
        rounds = client.drive()
        for h, key in zip(handles, SERVICE_SESSIONS):
            same_dse(h.result(), solo[key],
                     f"service hetero={hetero} {':'.join(map(str, key))}")
        elapsed = time.perf_counter() - t0
        note = ""
        if hetero:
            hd = client.service.batcher.router.hetero
            check(DISPATCH_COUNTS["hetero"] >= 1,
                  "service: no cross-design dispatch ran")
            check(_hetero_lowers_to_mosaic(hd, designs),
                  "service: cross-design dispatch holds no tpu_custom_call")
            note = (f", {hd.stats.n_dispatches} cross-design dispatches of "
                    f"{hd.stats.n_rows} rows at E*={hd.e_pad}")
        client.service.close()
        print(f"[service] hetero={hetero}: {len(handles)} sessions over "
              f"{len(designs)} designs identical to solo runs ({rounds} "
              f"rounds{note}); smoke timing (not a metric): {elapsed:.3f}s",
              flush=True)


def _devices_with_rows(out) -> set:
    """Devices holding a non-empty row shard of a sharded output."""
    return {sh.device for sh in out.addressable_shards if sh.data.shape[0]}


def mesh_phase(shards: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import FifoAdvisor
    from repro.core.campaign import Campaign, CampaignSpec
    from repro.core.config import EvalConfig
    from repro.designs import make_design

    want = set(jax.devices()[:shards])
    for name in ("gemm", "FeedForward"):
        t0 = time.perf_counter()
        adv = FifoAdvisor(make_design(name), config=EvalConfig(shards=shards))
        one = FifoAdvisor(make_design(name), config=EvalConfig(backend="jax"))
        rows = smoke_rows(adv.graph, N_ROWS)
        for what, a, b in zip(("latency", "bram", "deadlock"),
                              adv.evaluator.evaluate(rows),
                              one.evaluator.evaluate(rows)):
            same(a, b, f"{name}: shards={shards} rows: {what}")
        same_dse(adv.run("grouped_sa", budget=SA_BUDGET, seed=0),
                 one.run("grouped_sa", budget=SA_BUDGET, seed=0),
                 f"{name}: shards={shards} grouped_sa")
        out = adv.evaluator._impl._call.run(
            jnp.asarray(rows[: 16 * shards], dtype=jnp.int32))
        check(_devices_with_rows(out[0]) == want,
              f"{name}: sharded rows are not on all {shards} devices")
        print(f"[mesh] {name}: shards={shards} identical to one chip, rows "
              f"on devices {sorted(d.id for d in want)}; smoke timing (not a "
              f"metric): {time.perf_counter() - t0:.3f}s", flush=True)

    t0 = time.perf_counter()
    sharded = Campaign(CampaignSpec(**CAMPAIGN, hetero=True,
                                    eval=EvalConfig(shards=shards)))
    store = sharded.run()
    t_sharded = time.perf_counter() - t0
    one = Campaign(CampaignSpec(**CAMPAIGN, hetero=True)).run()
    check(sorted(store.keys()) == sorted(one.keys()),
          "campaign task sets differ")
    for key in store.keys():
        same_dse(store[key], one[key], f"campaign {key}")
        check(store[key].hypervolume() == one[key].hypervolume(),
              f"campaign {key}: hypervolume differs")
    hd = sharded.hetero
    check(_hetero_lowers_to_mosaic(hd, CAMPAIGN["designs"]),
          "sharded cross-design dispatch holds no tpu_custom_call")
    batch = _hetero_batch(hd, CAMPAIGN["designs"], 8 * shards)
    out = hd._call.run({k: jnp.asarray(v) for k, v in batch.items()})
    check(_devices_with_rows(out[0]) == want,
          f"hetero rows are not on all {shards} devices")
    print(f"[mesh] hetero campaign shards={shards}: {len(store.keys())} "
          f"tasks identical to one chip ({hd.stats.n_dispatches} "
          f"cross-design dispatches of {hd.stats.n_rows} rows at "
          f"E*={hd.e_pad}), hetero rows on devices "
          f"{sorted(d.id for d in want)}; smoke timing (not a metric): "
          f"sharded campaign {t_sharded:.3f}s, both "
          f"{time.perf_counter() - t0:.3f}s", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded four-chip path")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: FAIL: no repro package under {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    try:
        device = device_phase(args.chips)
        if args.chips == 1:
            compiles = _compile_counter()
            advisor_phase(compiles)
            service_phase()
        else:
            mesh_phase(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
