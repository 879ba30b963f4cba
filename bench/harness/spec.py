"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration (``bench/configs/<config>.json``, via the
``file`` of its entry) and a traffic mix (``bench/traffic/<traffic>.json``),
whose ``kind`` and erasure ``model`` name generator modules
``bench/traffic/<name>.py``; every metric is a reader
``bench/metrics/<metric>.py`` whose ``read(run)`` returns the number, or
None where the run holds nothing to read.

Every configuration names its plain reference under ``"reference"``: the
module ``bench/references/<reference>.py``, which decides ``correct``
(``harness/check.py``).  There is no default, and the name is not taken
from ``family``: one family can need several references, as the program's
``dense`` family holds both SwiGLU models (``references/dense.py``) and
the squared-ReLU Nemotron-4, which that reference refuses.  A reference
module

* provides ``logit_gaps(cfg, seed, prompts, served, s_pad, control=False)``:
  for each request, the gap of every served token (the reference's best
  logit at its position minus the served token's), teacher-forced over the
  prompt and its served tokens padded to ``s_pad``; with ``control``, also
  the gap of the token the control (the reference one precision step
  below the configuration) puts first, else None in its place;
* provides ``last_logits(cfg, seed, seqs, s_pad)``: ``[R, vocab]`` logits
  after each whole sequence, for the tests at small sizes;
* draws its weights from ``harness.traffic.seed_key(seed)``, the one seed
  rule, and imports nothing from ``src/``.

A family's layers are measured by reader files as well.  A reader of a
layer inside the step reads the program's ``jax.named_scope``s through
``readers.scope_ms(run, program, scope)``: the device time of the
operations whose ``op_name`` holds the scope, from the trace and the
compiled steps' maps (``Run.op_scopes``).  A reader of the host reads the
program's spans (``engine.*``, kept in ``Run.trace.host`` beside the
bench's ``bench.*``) through ``readers.idle_under_ms`` or the trace, and a
reader of a count reads ``Run.counters``: every public ``int`` attribute
of the engine at the window's start and end.  A family's operation and
byte counts, which a kernel's share of its roofline needs, are a new
module under ``bench/harness/`` named for the family, as ``cost.py`` holds
the dense family's.  No harness file needs an edit.

Adding a configuration, its reference, a mix, a generator or a metric is
adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import sys
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

    def reference(self, cfg: dict):
        """The module ``bench/references/<name>.py`` that the configuration
        names under ``reference``."""
        name = cfg.get("reference")
        if not name:
            raise KeyError(
                f"configuration {cfg.get('name')!r} names no \"reference\": give it "
                "the name of a module in bench/references/")
        return self._module("references", name)

    def generator(self, name: str):
        """The module ``bench/traffic/<name>.py`` named by a mix."""
        return self._module("traffic", name)

    def _module(self, kind: str, name: str):
        """Loaded once per file and process: a reference's jitted programs
        then compile once however many checks a process makes."""
        path = self.root / "bench" / kind / f"{name}.py"
        if not path.is_file():
            raise KeyError(f"no {kind} module {name!r} at {path}")
        mod_name = f"bench_{kind}_{name}"
        mod = sys.modules.get(mod_name)
        if mod is not None and mod.__file__ == str(path):
            return mod
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[mod_name]
            raise
        return mod


def load(root: Path = ROOT) -> Bench:
    return Bench(json.loads((root / "BENCHMARK.json").read_text()), root)
