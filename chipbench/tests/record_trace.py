"""Record the small trace that ``test_trace.py`` reduces.

Run on a machine with a TPU, from the root of a checkout:

    python3 chipbench/tests/record_trace.py

It evaluates a few batches of gemm on the Pallas backend (the fused
condensed kernel and the raw kernel) inside a ``chipbench.window`` span,
with a host-only pause under a span of its own, and writes the
profiler's ``.xplane.pb`` to ``chipbench/tests/data/trace.xplane.pb``.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "chipbench"), os.path.join(ROOT, "src")]


def main():
    import jax
    import numpy as np
    from repro.core import FifoAdvisor
    from repro.core.config import EvalConfig
    from repro.designs import make_design

    assert jax.devices()[0].platform == "tpu", "record on a TPU"
    adv = FifoAdvisor(make_design("gemm"), EvalConfig(backend="pallas"))
    ev = adv.evaluator
    fused = [impl for _, impl in ev.condensation if impl.fused_certificate]
    rows = np.repeat(adv.baseline_max.depths[None, :], 8, axis=0)
    fused[0].evaluate_certified(rows)
    ev._impl.evaluate(rows)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("chipbench.rung.aggressive"):
                fused[0].evaluate_certified(rows)
            with jax.profiler.TraceAnnotation("chipbench.raw"):
                ev._impl.evaluate(rows)
            with jax.profiler.TraceAnnotation("chipbench.pause"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    os.makedirs(os.path.join(HERE, "data"), exist_ok=True)
    shutil.copy(path, os.path.join(HERE, "data", "trace.xplane.pb"))
    print("recorded", os.path.getsize(path), "bytes")
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
