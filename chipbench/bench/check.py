"""The comparison that decides ``correct``.

After the window closes, a sample drawn from the seed of the searches
(or campaign tasks) that finished in it is held against the
plain reference (:mod:`bench.reference`):

* every row of each sampled search's frontier, and a seeded sample of
  its other evaluated rows, must carry the reference's latency, BRAM and
  deadlock verdict exactly (``rows_mismatched``, limit 0);
* each sampled search's frontier must be the Pareto set of the rows it
  reports (``frontiers_mismatched``, limit 0);
* enough rows must have been checked (``rows_checked``, at least the
  cell's ``min_rows``).

The rows a search reports passed through every layer the window drove:
the condensed rungs and their certificate, the raw kernel, bucket
padding, worklist escalation, the evaluation cache, and, on the
cross-design path, envelope padding, per-row tables and the sharded
gather.

With ``control=True`` the reference computed in bfloat16 is put in the
program's place; it has to fail.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench import reference


def _sample_rows(rng, res, n_other: int) -> List[int]:
    """The first row of each frontier point, then ``n_other`` rows drawn
    from the other distinct configurations."""
    first_of: Dict[tuple, int] = {}
    for i, row in enumerate(map(tuple, res.configs)):
        first_of.setdefault(row, i)
    todo = {tuple(map(int, p)) for p in res.frontier()[0]}
    front = []
    for i in first_of.values():
        point = (int(res.latency[i]), int(res.bram[i]))
        if not res.deadlock[i] and point in todo:
            todo.discard(point)
            front.append(i)
    chosen = set(front)
    others = [i for i in first_of.values() if i not in chosen]
    take = rng.permutation(len(others))[:n_other]
    return front + [others[k] for k in sorted(take)]


def compare(answers: Sequence[Tuple[str, object]], designs: Dict[str, dict],
            seed: int, n_results: int, n_rows: int, min_rows: int,
            control: bool = False, bench: str = reference.BENCH
            ) -> Dict[str, dict]:
    """``answers`` are ``(design name, DseResult)`` in completion order;
    ``designs`` maps each design name to its stage list, whose file
    stages are found under ``bench``.  Returns each number compared with
    its limit."""
    rng = np.random.default_rng([abs(int(seed)), 0x5EED])
    built: Dict[str, reference.Design] = {}
    picks = sorted(rng.permutation(len(answers))[:n_results])
    per = max(1, n_rows // max(len(picks), 1))
    checked = mismatched = frontiers = 0
    for k in picks:
        name, dse = answers[k]
        res = dse.result
        if name not in built:
            built[name] = reference.Design(designs[name], bench)
        design = built[name]
        rows = _sample_rows(rng, res, per)
        for i in rows:
            want = reference.answer(design, res.configs[i])
            if control:
                got = reference.answer(design, res.configs[i],
                                       round_to=reference.bfloat16)
            else:
                got = (int(res.latency[i]), int(res.bram[i]),
                       bool(res.deadlock[i]))
            mismatched += got != want
            checked += 1
        program = sorted((int(a), int(b)) for a, b in dse.frontier_points)
        frontiers += program != reference.frontier(
            res.latency, res.bram, res.deadlock)
    return {"rows_mismatched": {"value": int(mismatched), "limit": 0},
            "frontiers_mismatched": {"value": int(frontiers), "limit": 0},
            "rows_checked": {"value": int(checked), "min": int(min_rows)}}


def passed(comparison: Dict[str, dict]) -> bool:
    for item in comparison.values():
        if "limit" in item and item["value"] > item["limit"]:
            return False
        if "min" in item and item["value"] < item["min"]:
            return False
    return True
