"""A later cell, or a design with stages of its own, arrives as new
files and entries only.

The tests copy the benchmark into a temporary checkout and edit no file
that was there.  One adds a configuration, a traffic mix and a per-layer
metric as files of their own plus their entries in ``BENCHMARK.json``,
and runs the new cell through the harness (rehearsal size, CPU).  The
other adds a stage builder and a design file that uses it, and
simulates the design with the plain reference.
"""

import io
import json
import os
import shutil

import pytest

from bench import reference, runner
from bench.spec import BENCH, ROOT, Benchmark, SpecError


def _checkout(tmp_path):
    """A copy of the benchmark, and its files' bytes."""
    bench = tmp_path / "chipbench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return bench, {p: p.read_bytes() for p in tmp_path.rglob("*")
                   if p.is_file()}


def test_new_cell_from_new_files_only(tmp_path):
    bench, before = _checkout(tmp_path)

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


RELAY = """from bench.reference import Ops


def relay(fifos, r):
    o, inp, out = Ops(), fifos[r["inp"]], fifos[r["out"]]
    for _ in range(r["count"]):
        o.read(inp[0])
        o.delay(r["hold"])
        o.write(out[0])
    return o.done()
"""


def test_new_stage_from_new_files_only(tmp_path):
    bench, before = _checkout(tmp_path)
    (bench / "stages" / "toy.py").write_text(RELAY)
    (bench / "configs" / "designs" / "toy.json").write_text(json.dumps({
        "name": "toy",
        "streams": [{"name": s, "lanes": 1, "width": 32} for s in "ab"],
        "tasks": [
            {"stage": "producer", "name": "load", "out": "a", "count": 4,
             "ii": 1},
            {"stage": "toy.relay", "name": "relay", "inp": "a", "out": "b",
             "count": 4, "hold": 3},
            {"stage": "sink", "name": "store", "inp": "b", "count": 4,
             "ii": 1}]}))
    assert [p for p, data in before.items() if p.read_bytes() != data] == []

    bm = Benchmark(str(tmp_path), str(bench))
    design = reference.Design(bm.design("toy"), bm.bench)
    assert design.tasks[1] == [(reference.READ, 0, 0),
                               (reference.WRITE, 1, 3)] * 4
    # depth 2: a is written at 1, 2, 3, 6 and read at 2, 5, 8, 11; b is
    # written at 5, 8, 11, 14 and read at 6, 9, 12, 15
    assert reference.answer(design, [2, 2]) == (15, 0, False)
    with pytest.raises(SpecError):
        reference.Design(bm.design("toy"))    # not in the checkout's own
