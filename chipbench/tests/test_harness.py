"""The harness refuses to measure what it cannot: no chip, or no program."""

import json
import os
import shutil
import subprocess
import sys

from bench.spec import BENCH, ROOT, Benchmark

RUN = ["chipbench/run.py", "--workload", "k15mmtree_relu.sa", "--seed",
       "3000000001", "--seconds", "1", "--trace", "0"]


def _run(cwd, argv=RUN):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + argv, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_no_program_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "chipbench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_benchmark_names_resolve():
    bm = Benchmark()
    for w in bm.data["workloads"]:
        cfg = bm.config(w)
        mix = bm.mix(w["traffic"])
        bm.generator(mix["kind"])
        assert cfg["chips"] == w["chips"]
        names = {m["name"] for m in bm.end_to_end(w["name"])}
        assert "setup_s" in names and len(names) >= 2
        layer = bm.per_layer(w["name"])
        assert layer
        for m in layer:
            assert m["moves"] in names
            assert callable(bm.reader(m["name"]))
        designs = cfg["deployment"].get("designs",
                                        [cfg["deployment"].get("design")])
        for d in designs + mix.get("designs", []):
            assert bm.design(d)["name"] == d
    assert len(json.dumps(bm.data)) < 64 * 1024
