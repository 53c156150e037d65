"""FlowGNN PNA design files for the plain reference
(``chipbench/bench/reference.py``), whose stages are
``chipbench/stages/pna.py``'s.

    python3 chipbench/tools/pna_design.py             # compare the files
    python3 chipbench/tools/pna_design.py --new NAME N_NODES N_EDGES LANES SEED

The first holds every design file whose ``program`` entry calls
``flowgnn_pna`` to what its entry's arguments give here; ``--new``
writes ``configs/designs/NAME.json``.  A file's ``program``
entry is the program's call that builds the same design, which
``chipbench/tests/test_reference.py`` holds the reference to.

The layer's streams, in the order the design declares them: the edge
stream (a 64-bit (source, destination) pair), the node loader's feature
and self-feature streams (256 bits), one in-degree stream per aggregator
(16 bits), one array of ``lanes`` message streams per aggregator (32
bits, lane ``v % lanes`` for destination v), one aggregate stream per
aggregator and the output stream (32 bits).  The aggregators are PNA's
mean, max and standard deviation; std keeps running moments, so it costs
3 cycles a message and 4 to finish a node, mean and max 1 and 1.  The
combine's per-node update takes 6 cycles.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))
DESIGNS = os.path.join(os.path.dirname(HERE), "configs", "designs")
sys.path.insert(0, HERE)

from stage_lists import dumps  # noqa: E402

CALL = "flowgnn_pna"
#: aggregator: (cycles a message, cycles to finish a node)
AGGREGATORS = {"mean": (1, 1), "max": (1, 1), "std": (3, 4)}
UPDATE_DELAY = 6


def design(name: str, n_nodes: int, n_edges: int, lanes: int, seed: int
           ) -> Dict:
    graph = {"n_nodes": n_nodes, "n_edges": n_edges, "seed": seed}
    aggs = list(AGGREGATORS)
    streams = [("edges_q", 1, 64), ("feat_q", 1, 256), ("skip_q", 1, 256)]
    streams += [(f"deg_{a}", 1, 16) for a in aggs]
    streams += [(f"msg_{a}", lanes, 32) for a in aggs]
    streams += [(f"agg_{a}", 1, 32) for a in aggs] + [("out_q", 1, 32)]
    tasks = [
        {"stage": "producer", "name": "edge_loader", "out": "edges_q",
         "count": n_edges, "ii": 1, "start_delay": 0},
        {"stage": "pna.node_loader", "name": "node_loader", "graph": graph,
         "skip": "skip_q", "feat": "feat_q",
         "deg": [f"deg_{a}" for a in aggs], "ii": 1},
        {"stage": "pna.scatter", "name": "scatter", "graph": graph,
         "edges": "edges_q", "feat": "feat_q",
         "msg": [f"msg_{a}" for a in aggs], "ii": 1, "send_delay": 1}]
    tasks += [{"stage": "pna.aggregate", "name": f"agg_{a}", "graph": graph,
               "deg": f"deg_{a}", "msg": f"msg_{a}", "out": f"agg_{a}",
               "ii": 1, "per_msg": per_msg, "epilogue": epilogue}
              for a, (per_msg, epilogue) in AGGREGATORS.items()]
    tasks += [
        {"stage": "pna.combine", "name": "combine", "skip": "skip_q",
         "aggs": [f"agg_{a}" for a in aggs], "out": "out_q",
         "count": n_nodes, "update_delay": UPDATE_DELAY},
        {"stage": "sink", "name": "store", "inp": "out_q", "count": n_nodes,
         "ii": 1}]
    return {"name": name,
            "program": {"call": CALL, "args": {
                "n_nodes": n_nodes, "n_edges": n_edges, "lanes": lanes,
                "seed": seed}},
            "streams": [{"name": s, "lanes": n, "width": w}
                        for s, n, w in streams],
            "tasks": tasks}


def text_of(spec: Dict) -> str:
    """What a design file with ``spec``'s name and ``program`` entry
    holds."""
    return dumps(design(spec["name"], **spec["program"]["args"]))


def main(argv) -> int:
    if argv[:1] == ["--new"]:
        name, *sizes = argv[1:]
        with open(os.path.join(DESIGNS, f"{name}.json"), "w") as f:
            f.write(dumps(design(name, *map(int, sizes))))
        return 0
    stale = []
    for path in sorted(glob.glob(os.path.join(DESIGNS, "*.json"))):
        with open(path) as f:
            have = f.read()
        spec = json.loads(have)
        if (spec.get("program", {}).get("call") == CALL
                and have != text_of(spec)):
            stale.append(spec["name"])
    for name in stale:
        print(f"{name}: the file differs from the tool", file=sys.stderr)
    return 1 if stale else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
