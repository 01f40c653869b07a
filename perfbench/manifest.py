"""BENCHMARK.json and the files it names, found by name.

A cell is ``workloads[i]``: a configuration (``configs/<config>.json``) under
a traffic mix (``traffic/<traffic>.json``).  The traffic file's ``kind``
selects ``runners/<kind>.py``; a metric named ``m`` is described by
``metrics/<m>.json`` and computed by ``reducers/<reducer>.py``.  Nothing is
registered anywhere: a later PR adds files and manifest entries only.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


class Manifest:
    """``BENCHMARK.json`` plus the directory its data files live in.

    ``root`` holds ``BENCHMARK.json``; ``data`` holds ``configs/``,
    ``traffic/``, ``metrics/``, ``reducers/`` and ``runners/`` (by default
    this package's directory, a temporary one in the tests that show a cell
    is defined by files alone)."""

    def __init__(self, root: str = ROOT, data: Optional[str] = None):
        self.root = root
        self.data = data or HERE
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))

    # ------------------------------------------------------------- lookup
    def workload(self, name: str) -> Dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.doc["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")

    def config_entry(self, name: str) -> Dict[str, Any]:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict[str, Any]:
        return load_json(os.path.join(self.root, self.config_entry(name)["file"]))

    def traffic(self, name: str) -> Dict[str, Any]:
        return load_json(os.path.join(self.data, "traffic", f"{name}.json"))

    def metric_file(self, name: str) -> Dict[str, Any]:
        return load_json(os.path.join(self.data, "metrics", f"{name}.json"))

    def _module(self, kind: str, name: str):
        """``<data>/<kind>/<name>.py`` — the package's own by import, a
        foreign data directory's by path."""
        path = os.path.join(self.data, kind, f"{name}.py")
        if self.data == HERE or not os.path.exists(path):
            return importlib.import_module(f"perfbench.{kind}.{name}")
        spec = importlib.util.spec_from_file_location(
            f"perfbench_ext.{kind}.{name}", path
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def runner(self, kind: str):
        return self._module("runners", kind)

    def reducer(self, name: str):
        return self._module("reducers", name)

    # ------------------------------------------------------------ metrics
    def metrics_for(self, workload: str, group: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
        that list it under ``workloads``, or list nothing."""
        out = []
        for m in self.doc[group]:
            cells = m.get("workloads")
            if cells is None or workload in cells:
                out.append(m)
        return out
