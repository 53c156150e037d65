"""Run every benchmark; print one ``name,seconds,derived`` CSV line each.

  PYTHONPATH=src python -m benchmarks.run              # fast budgets
  FULL=1 PYTHONPATH=src python -m benchmarks.run       # paper budgets
  PYTHONPATH=src python -m benchmarks.run --only mesh  # just one
  PYTHONPATH=src python -m benchmarks.run --list       # show names

``--only`` may be repeated (or comma-separated) to run a subset in the
canonical order; unknown names fail fast with the available list.
"""

from __future__ import annotations

import argparse
import sys
import time


def _accuracy() -> str:
    from benchmarks import accuracy
    acc = accuracy.run()
    return f"all_exact={acc['all_exact']}"


def _improvement() -> str:
    from benchmarks import improvement
    gsa = improvement.run()["summary"].get("grouped_sa", {})
    return (f"grouped_sa_lat_vs_max={gsa.get('geomean_lat_vs_max'):.4f};"
            f"bram_red={gsa.get('mean_bram_red'):.3f};"
            f"undeadlocked={gsa.get('undeadlocked')}")


def _runtime() -> str:
    from benchmarks import runtime
    g = runtime.run()["summary"]["grouped_sa"]
    return (f"grouped_sa_vs_des={g['geomean_speedup_vs_des']:.1f}x;"
            f"vs_rtl_slow={g['geomean_speedup_vs_rtl_slow']:.0f}x")


def _pareto_fronts() -> str:
    from benchmarks import pareto_fronts
    return f"designs={len(pareto_fronts.run())}"


def _convergence() -> str:
    from benchmarks import convergence
    cv = convergence.run()
    return f"final_grouped_sa={cv['curves']['grouped_sa']['final']}"


def _case_study() -> str:
    from benchmarks import case_study
    cs = case_study.run()
    return f"msg_depths={cs['min_feasible_msg_depth_by_graph']}"


def _batched_eval() -> str:
    from benchmarks import batched_eval
    be = batched_eval.run()
    return f"gemm_numpy_us_per_cfg={be['gemm']['numpy']['us_per_config']}"


def _campaign() -> str:
    from benchmarks import campaign
    cp = campaign.run()
    return (f"speedup_vs_seq={cp['campaign_speedup']:.2f}x;"
            f"identical_frontiers={cp['identical_frontiers']}")


def _service() -> str:
    from benchmarks import service
    sv = service.run()
    return (f"speedup_vs_solo={sv['service_speedup']:.2f}x;"
            f"identical_frontiers={sv['identical_frontiers']}")


def _condense() -> str:
    from benchmarks import condense
    cd = condense.run()
    return (f"scan_speedup={cd['geomean_speedup_scan']:.2f}x;"
            f"ratio={cd['geomean_condensation_ratio']:.1f}x;"
            f"identical={cd['identical_all']}")


def _mesh() -> str:
    from benchmarks import mesh
    ms = mesh.run()
    if "skipped" in ms:
        return f"skipped: {ms['skipped']}"
    return (f"speedup_8v1={ms['geomean_speedup_8v1']:.2f}x;"
            f"cores={ms['usable_cores']};"
            f"identical={ms['identical_all']}")


def _cache_lookup() -> str:
    from benchmarks import cache_lookup
    cl = cache_lookup.run()
    return f"c1024_speedup={cl['batch'][-1]['speedup']:.2f}x"


def _fuzz() -> str:
    from benchmarks import fuzz
    fz = fuzz.run()
    return (f"zero_mismatches={fz['differential']['zero_mismatches']};"
            f"cert_speedup={fz['cert_geomean_speedup']:.2f}x")


def _bounds() -> str:
    from benchmarks import bounds
    bd = bounds.run()
    return (f"probe_reduction={bd['probe_reduction_geomean']:.2f}x;"
            f"identical={bd['identical_depths_all']};"
            f"bracket={bd['bracket_all']}")


def _load() -> str:
    from benchmarks import load
    ld = load.run()
    s, o = ld["steady"], ld["overload"]
    return (f"p99_s={s['p99_s']};throughput={s['throughput_per_s']}/s;"
            f"shed={o['rejected']};cap_respected={o['cap_respected']}")


def _chaos() -> str:
    from benchmarks import chaos
    ch = chaos.run()
    pc, sf = ch["pool_crash"], ch["service_faults"]
    return (f"pool_identical={pc['identical_frontiers']};"
            f"respawns={pc['respawns']};"
            f"quarantine_exact="
            f"{ch['snapshot_corruption']['quarantined_only_damaged']};"
            f"resume_identical={ch['kill_resume']['identical_frontiers']};"
            f"timeout_isolated={sf['peer_identical']}")


def _pruning() -> str:
    from benchmarks import pruning
    k = pruning.run()["k15mmtree"]
    return (f"k15mmtree_random_dead:{k['random_raw']['dead']}->"
            f"{k['random_pruned']['dead']}")


#: canonical order — ``--only`` subsets preserve it
STEPS = [
    ("accuracy", _accuracy),
    ("improvement", _improvement),
    ("runtime", _runtime),
    ("pareto_fronts", _pareto_fronts),
    ("convergence", _convergence),
    ("case_study", _case_study),
    ("batched_eval", _batched_eval),
    ("campaign", _campaign),
    ("service", _service),
    ("condense", _condense),
    ("mesh", _mesh),
    ("cache_lookup", _cache_lookup),
    ("load", _load),
    ("fuzz", _fuzz),
    ("bounds", _bounds),
    ("chaos", _chaos),
    ("pruning", _pruning),
]


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m benchmarks.run",
        description="Run the benchmark suite (QUICK=1 / FULL=1 envs "
                    "select budgets).")
    p.add_argument("--only", action="append", default=None,
                   metavar="NAME",
                   help="run only this benchmark (repeatable, or "
                        "comma-separated); order stays canonical")
    p.add_argument("--list", action="store_true",
                   help="print benchmark names and exit")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    names = [n for n, _ in STEPS]
    if args.list:
        print("\n".join(names))
        return 0
    selected = None
    if args.only:
        selected = [n.strip() for arg in args.only
                    for n in arg.split(",") if n.strip()]
        unknown = sorted(set(selected) - set(names))
        if unknown:
            print(f"unknown benchmark(s): {', '.join(unknown)}; "
                  f"available: {', '.join(names)}", file=sys.stderr)
            return 2
    print("name,seconds,derived")
    for name, fn in STEPS:
        if selected is not None and name not in selected:
            continue
        t0 = time.perf_counter()
        derived = fn()
        print(f"{name},{time.perf_counter() - t0:.2f},{derived}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
