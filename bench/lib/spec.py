"""Finds every piece of a cell by the name that ``BENCHMARK.json`` gives it.

A cell is a workload entry: a configuration (``configs/<name>.json`` with its
plain reference ``configs/<name>.py`` beside it), a traffic mix
(``traffic/<name>.json``) and the limits of its correctness check
(``limits/<workload>.json``).  A per-layer metric is a reader in
``metrics/<name>.py`` (or of the quantity it names, :func:`quantity`); a
count of operations or bytes is ``counts/<name>.py``.
Adding a cell, a configuration or a metric adds files and entries here and
edits none.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def quantity(name: str) -> str:
    """What a metric measures: its name up to the first dot.  The rest
    names a class of cells that reports the quantity under a bound of its
    own (``samples_per_s.host`` for the host-bound cells)."""
    return name.split(".", 1)[0]


class SpecError(ValueError):
    """A cell, file or entry that the benchmark cannot find or accept."""


def load_json(path: Path) -> Any:
    path = Path(path)
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by its path, once per process."""
    path = Path(path).resolve()
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    name = "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix("").parts)
    if name in sys.modules and getattr(sys.modules[name], "__file__", None) == str(path):
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the directory of files it names."""

    def __init__(self, root: Path = ROOT, bench_dir: Path = BENCH_DIR):
        self.root = Path(root)
        self.dir = Path(bench_dir)
        self.doc = load_json(self.root / "BENCHMARK.json")

    def _entry(self, key: str, name: str) -> Dict[str, Any]:
        hits = [e for e in self.doc[key] if e["name"] == name]
        if len(hits) != 1:
            raise SpecError(f"{key}: no single entry named {name!r}")
        return hits[0]

    def workload(self, name: str) -> Dict[str, Any]:
        return self._entry("workloads", name)

    def config_entry(self, name: str) -> Dict[str, Any]:
        return self._entry("configs", name)

    def config(self, name: str) -> Dict[str, Any]:
        return load_json(self.root / self.config_entry(name)["file"])

    def reference(self, name: str) -> ModuleType:
        """The configuration's plain reference, beside its file."""
        return load_module((self.root / self.config_entry(name)["file"]).with_suffix(".py"))

    def traffic(self, name: str) -> Dict[str, Any]:
        return load_json(self.dir / "traffic" / f"{name}.json")

    def limits(self, workload: str) -> Dict[str, float]:
        return load_json(self.dir / "limits" / f"{workload}.json")

    def count(self, name: str) -> ModuleType:
        return load_module(self.dir / "counts" / f"{name}.py")

    def metric_reader(self, name: str) -> ModuleType:
        """``metrics/<name>.py``; a metric named ``<quantity>.<class>``
        without a file of its own is read by ``metrics/<quantity>.py``."""
        own = self.dir / "metrics" / f"{name}.py"
        return load_module(own if own.is_file() else
                           self.dir / "metrics" / f"{quantity(name)}.py")

    def end_to_end(self, workload: str) -> List[Dict[str, Any]]:
        return [m for m in self.doc["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> List[Dict[str, Any]]:
        return [m for m in self.doc["per_layer"]
                if workload in m.get("workloads", [workload])]
