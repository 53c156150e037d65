"""A later cell arrives as new files and entries only.

The test copies the benchmark into a temporary checkout, adds a
configuration, a traffic mix and a per-layer metric as files of their
own plus their entries in ``BENCHMARK.json``, edits no file that was
there, and runs the new cell through the harness (rehearsal size, CPU).
"""

import io
import json
import os
import shutil

from bench import runner
from bench.spec import BENCH, ROOT, Benchmark


def test_new_cell_from_new_files_only(tmp_path):
    bench = tmp_path / "chipbench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}

    (bench / "configs" / "advisor.atax.json").write_text(json.dumps({
        "deployment": {"kind": "advisor", "design": "atax",
                       "eval": {"backend": "pallas"}}}))
    (bench / "traffic" / "tiny_random.json").write_text(json.dumps({
        "kind": "searches", "optimizer": "grouped_random", "budget": 8,
        "max_rows": 8,
        "check": {"n_results": 1, "n_rows": 4, "min_rows": 2}}))
    (bench / "metrics" / "searches.tiny.py").write_text(
        "def read(run):\n    return run.counters['searches']\n")
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "advisor.atax", "source": "test",
                          "file": "chipbench/configs/advisor.atax.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "atax.tiny", "config": "advisor.atax",
                            "traffic": "tiny_random", "chips": 1,
                            "why": "test"})
    bm["per_layer"].append({"name": "searches.tiny", "unit": "count",
                            "better": "higher", "source": "program_counter",
                            "layer": "optimizer and advisor",
                            "moves": "configs_per_s",
                            "workloads": ["atax.tiny"]})
    bm["end_to_end"][0]["workloads"].append("atax.tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    changed = [p for p, data in before.items()
               if p.name != "BENCHMARK.json" and p.read_bytes() != data]
    assert changed == []

    out, err = io.StringIO(), io.StringIO()
    rc = runner.run("atax.tiny", seed=3, seconds=1.0, traced=True,
                    rehearse=True, bm=Benchmark(str(tmp_path), str(bench)),
                    out=out, err=err)
    assert rc == 0
    result = json.loads(out.getvalue().splitlines()[-1])
    assert result["correct"] is True
    assert "searches.tiny" in result["computed"]
