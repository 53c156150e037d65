"""Faults planted under the timed path, for ``test_faults.py``.

    python3 chipbench/tests/faults.py <fault> <workload> <seed> [<root>]

plants ``<fault>`` in the program's device calls, then runs the cell
through the harness at its rehearsal size on the CPU (skipping the look
for a chip) and prints the harness's result line.  Faults:

* ``altered``: every latency a device call produces is one cycle late;
* ``half``: a device call evaluates the first half of its rows and
  hands the second half copies of those answers;
* ``no_exchange``: a sharded cross-design call keeps only the first
  device's rows and repeats them in place of the other devices'.
"""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE),
                os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]


def _rows(batch):
    return (batch["depths"] if isinstance(batch, dict) else batch).shape[0]


def _take(batch, idx):
    if isinstance(batch, dict):
        return {k: np.asarray(v)[idx] for k, v in batch.items()}
    return np.asarray(batch)[idx]


def altered(call, sharded):
    def bad(batch):
        out = list(call(batch))
        out[0] = np.asarray(out[0]) + 1
        return tuple(out)
    return bad


def half(call, sharded):
    def bad(batch):
        c = _rows(batch)
        k = -(-c // 2)
        if sharded:
            k = -(-k // sharded) * sharded
        out = call(_take(batch, np.arange(min(k, c))))
        idx = np.arange(c) % min(k, c)
        return tuple(np.asarray(o)[idx] for o in out)
    return bad


def no_exchange(call, sharded):
    if not sharded:
        return call

    def bad(batch):
        out = call(batch)
        c = _rows(batch)
        idx = np.arange(c) % max(1, c // sharded)
        return tuple(np.asarray(o)[idx] for o in out)
    return bad


def plant(fault):
    """Wrap every device call the program builds from now on."""
    from repro.kernels.fifo_eval import ops
    for name in ("make_batched_eval", "make_condensed_eval",
                 "make_hetero_batched_eval"):
        make = getattr(ops, name)

        def planted(*args, _make=make, **kwargs):
            call = _make(*args, **kwargs)
            if call is None:
                return None
            mesh = kwargs.get("mesh")
            sharded = int(mesh.devices.size) if mesh is not None else 0
            bad = fault(call, sharded)
            bad.run = call.run
            return bad
        setattr(ops, name, planted)


if __name__ == "__main__":
    fault, workload, seed = sys.argv[1], sys.argv[2], int(sys.argv[3])
    plant({"altered": altered, "half": half,
           "no_exchange": no_exchange}[fault])
    from bench import runner
    from bench.spec import Benchmark
    bm = Benchmark(*sys.argv[4:5])
    sys.exit(runner.run(workload, seed, 2.0, traced=False, rehearse=True,
                        bm=bm))
