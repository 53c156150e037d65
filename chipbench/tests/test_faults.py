"""A broken timed path must come out ``correct: false``.

For each cell and each fault it can have (``faults.py``), a run at
rehearsal size on the CPU with the fault planted under the program's
device calls; every one must report ``correct`` false.  Alongside, the
same cells unbroken must report true, and the control (the reference in
bfloat16 in the program's place) must fail the comparison.
"""

import json
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

CASES = [
    ("k15mmtree_relu.random", "altered"), ("k15mmtree_relu.random", "half"),
    ("k15mmtree_relu.sa", "altered"), ("k15mmtree_relu.sa", "half"),
    ("campaign.fast10.mesh4", "altered"), ("campaign.fast10.mesh4", "half"),
    ("campaign.fast10.mesh4", "no_exchange"),
]


def _env(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if cell.startswith("campaign"):
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    return env


def _last_line(argv, cell):
    p = subprocess.run([sys.executable] + argv, cwd=ROOT, env=_env(cell),
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def results():
    def run(cell, fault):
        return _last_line([os.path.join(HERE, "faults.py"), fault, cell,
                           "5"], cell)
    with ThreadPoolExecutor(max_workers=3) as pool:
        futures = {case: pool.submit(run, *case) for case in CASES}
        return {case: f.result() for case, f in futures.items()}


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(results, cell, fault):
    result = results[(cell, fault)]
    assert result["correct"] is False, result["comparison"]


@pytest.mark.parametrize("cell", sorted({c for c, _ in CASES}))
def test_control_fails_and_program_passes(cell):
    line = _last_line([os.path.join(ROOT, "chipbench", "control.py"),
                       "--workload", cell, "--seeds", "7",
                       "--seconds", "2", "--rehearse"], cell)
    assert line["program_correct"] is True, line
    assert line["control_correct"] is False, line
    assert line["control"]["rows_mismatched"] > 0
