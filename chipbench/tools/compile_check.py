"""Compile each cell's device programs for a described TPU v5e 2x2, with
no chip attached, at the cells' real shapes:

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 chipbench/tools/compile_check.py

* k15mmtree_relu: the raw kernel at 128 and 1 rows, the fused condensed
  rung at buckets 8 and 128, the safe rung's kernel at 128 rows;
* the 10 FAST designs' cross-design program at envelope width, one chip,
  1,024 rows;
* the same program sharded over the four described chips at 2,048 rows.

Each line prints the program's bytes on one device and whether it holds
the Mosaic kernel.  A refusal by the TPU compiler raises.  Nothing runs,
so nothing here is a measurement.
"""

from __future__ import annotations

import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core import build_simgraph
    from repro.core.backends.dispatch import HeteroDispatcher
    from repro.core.backends.operands import stack_hetero
    from repro.core.condense import condense_auto
    from repro.designs import make_design
    from repro.designs.streamhls import FAST_DESIGNS
    from repro.kernels.fifo_eval import ops
    jax.config.update("jax_enable_compilation_cache", False)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a one-device mesh makes the program's factories see a TPU (they take
    # their platform from the mesh) where jax.devices() is the CPU
    solo = Mesh(np.asarray(topo.devices[:1]), ("eval",))
    one = NamedSharding(solo, PartitionSpec("eval"))
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("eval",))

    def report(what, run, *specs):
        t0 = time.perf_counter()
        compiled = run.lower(*specs).compile()
        mem = compiled.memory_analysis()
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        mosaic = "tpu_custom_call" in compiled.as_text()
        print(f"{what}: {used / 2**20:.1f} MiB per device, Mosaic kernel "
              f"{mosaic}, compiled in {time.perf_counter() - t0:.1f} s",
              flush=True)

    g = build_simgraph(make_design("k15mmtree_relu"))
    F = g.n_fifos

    def depths(c, sharding=one):
        return jax.ShapeDtypeStruct((c, F), jnp.int32, sharding=sharding)
    raw = ops.make_batched_eval(g, max_iters=256, mesh=solo)
    for c in (128, 1):
        report(f"k15mmtree_relu raw kernel, {c} rows", raw.run, depths(c))
    for cg in condense_auto(g):
        fused = ops.make_condensed_eval(cg, max_iters=256, mesh=solo)
        if cg.compression >= 8 and fused is not None:
            for c in (8, 128):
                report(f"k15mmtree_relu {cg.tag} rung fused, {c} rows",
                       fused.run, depths(c))
        else:
            timed = ops.make_batched_eval(cg, max_iters=256,
                                          with_times=True, mesh=solo)
            report(f"k15mmtree_relu {cg.tag} rung, 128 rows", timed.run,
                   depths(128))

    graphs = {d: build_simgraph(make_design(d)) for d in FAST_DESIGNS}
    hd = HeteroDispatcher(graphs, max_iters=256)
    row = stack_hetero([(hd._ext["k15mmtree"],
                         np.asarray(graphs["k15mmtree"].upper_bounds)[None])])

    def batch(c, sharding):
        return {k: jax.ShapeDtypeStruct((c,) + v.shape[1:], v.dtype,
                                        sharding=sharding)
                for k, v in row.items()}
    report(f"cross-design, E*={hd.e_pad}, 1 chip, 1024 rows",
           ops.make_hetero_batched_eval(256, mesh=solo).run,
           batch(1024, one))
    sharded = ops.make_hetero_batched_eval(256, mesh=mesh)
    report(f"cross-design, E*={hd.e_pad}, 4 chips, 2048 rows", sharded.run,
           batch(2048, NamedSharding(mesh, PartitionSpec("eval"))))


if __name__ == "__main__":
    main()
