"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration (``bench/configs/<config>.json``, via the
``file`` of its entry) and a traffic mix (``bench/traffic/<traffic>.json``),
whose ``kind`` and erasure ``model`` name generator modules
``bench/traffic/<name>.py``; every metric is a reader
``bench/metrics/<metric>.py`` whose ``read(run)`` returns the number, or
None where the run holds nothing to read.  Adding a configuration, a mix,
a generator or a metric is adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

__all__ = ["ROOT", "Bench", "load"]

ROOT = Path(__file__).resolve().parents[2]


class Bench:
    def __init__(self, doc: dict, root: Path):
        self.doc, self.root = doc, root

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r}; have "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r}")

    def traffic(self, name: str) -> dict:
        return json.loads((self.root / "bench" / "traffic" / f"{name}.json").read_text())

    def metrics(self, cell: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
        those that list it under ``workloads``, or list none."""
        return [m for m in self.doc[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        return self._module("metrics", metric)

    def generator(self, name: str):
        """The module ``bench/traffic/<name>.py`` named by a mix."""
        return self._module("traffic", name)

    def _module(self, kind: str, name: str):
        path = self.root / "bench" / kind / f"{name}.py"
        if not path.is_file():
            raise KeyError(f"no {kind} module {name!r} at {path}")
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def load(root: Path = ROOT) -> Bench:
    return Bench(json.loads((root / "BENCHMARK.json").read_text()), root)
