"""Find a cell's parts by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; each lives in files of its own.

* configuration: the ``file`` that ``BENCHMARK.json`` gives it, under
  ``chipbench/configs/``; design stage lists in ``configs/designs/``;
* stage builder: a stage list's ``"stage": "<module>.<builder>"`` that is
  not one of the reference's built-in stages is
  ``chipbench/stages/<module>.py``'s ``<builder>(fifos, rec)``;
* traffic mix: ``chipbench/traffic/<mix>.json``, driven by the generator
  ``chipbench/traffic/<kind>.py`` that the mix's ``kind`` names;
* per-layer metric: ``chipbench/metrics/<metric>.py``, whose ``read(run)``
  returns the value or None when the run holds nothing to read.

A later cell, mix, metric or stage builder is added as new files and
entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class SpecError(Exception):
    """A cell, configuration, mix or metric that cannot be found."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def stage(bench: str, name: str) -> Callable:
    """The stage builder ``name`` (``<module>.<builder>``) from
    ``<bench>/stages/<module>.py``."""
    module, _, builder = name.rpartition(".")
    if not module:
        raise SpecError(f"no stage {name!r}: built-in stages are "
                        "plain names, file stages <module>.<builder>")
    mod = _load_module(os.path.join(bench, "stages", f"{module}.py"),
                       "chipbench_stages_" + module.replace(".", "_"))
    try:
        return getattr(mod, builder)
    except AttributeError:
        raise SpecError(f"no stage builder {builder!r} in "
                        f"chipbench/stages/{module}.py")


class Benchmark:
    """``BENCHMARK.json`` with lookups by name; ``root`` is the checkout
    and ``bench`` the directory that holds configs, traffic, metrics and
    stages (both overridable, which is how the tests add a throwaway mix
    or stage)."""

    def __init__(self, root: str = ROOT, bench: str = BENCH):
        self.root, self.bench = root, bench
        self.data = _load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        for c in self.data["configs"]:
            if c["name"] == cell["config"]:
                return _load_json(os.path.join(self.root, c["file"]))
        raise SpecError(f"no config {cell['config']!r} in BENCHMARK.json")

    def design(self, name: str) -> dict:
        return _load_json(os.path.join(self.bench, "configs", "designs",
                                       f"{name}.json"))

    def mix(self, name: str) -> dict:
        return _load_json(os.path.join(self.bench, "traffic",
                                       f"{name}.json"))

    def generator(self, kind: str):
        return _load_module(os.path.join(self.bench, "traffic", f"{kind}.py"),
                            f"chipbench_traffic_{kind}")

    def end_to_end(self, cell: str):
        return [m for m in self.data["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str):
        """Per-layer metrics read in ``cell``: those that list it, and
        those that list no cells and move an end-to-end metric of it."""
        moved = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.data["per_layer"]
                if cell in m.get("workloads", ())
                or ("workloads" not in m and m["moves"] in moved)]

    def reader(self, metric: str) -> Callable[[object], Optional[float]]:
        path = os.path.join(self.bench, "metrics", f"{metric}.py")
        return _load_module(path, "chipbench_metric_" +
                            metric.replace(".", "_")).read
