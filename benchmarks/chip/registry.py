"""Finds everything of a cell by the names in ``BENCHMARK.json``.

A configuration ``<c>`` is ``configs/<c>.json`` (its sizes, source and
host settings) with its generator ``configs/<c>.py``; a traffic mix
``<m>`` is ``traffic/<m>.json``; a per-layer metric ``<n>`` is
``metrics/<n>.py``; the plain reference of unit ``<u>`` of configuration
``<c>`` is ``reference/<c>/<u>.py``. Adding a cell, a metric or a
reference is adding files: nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_module(path: Path, name: str):
    if str(HERE) not in sys.path:
        sys.path.insert(0, str(HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Registry:
    def __init__(self, base: Path = HERE, benchmark: dict | None = None):
        self.base = Path(base)
        if benchmark is None:
            benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
        self.benchmark = benchmark
        self._modules: dict[Path, object] = {}

    def _module(self, rel: str):
        path = self.base / rel
        if path not in self._modules:
            tag = rel.replace("/", "_").replace(".", "_")
            self._modules[path] = load_module(path, f"chipbench_{tag}")
        return self._modules[path]

    def _entry(self, key: str, name: str) -> dict:
        for entry in self.benchmark[key]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")

    # -- by name ----------------------------------------------------------
    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        self._entry("configs", name)
        return json.loads((self.base / "configs" / f"{name}.json")
                          .read_text())

    def generator(self, name: str):
        return self._module(f"configs/{self.config(name)['generator']}")

    def traffic(self, name: str) -> dict:
        return json.loads((self.base / "traffic" / f"{name}.json")
                          .read_text())

    def metric(self, name: str):
        return self._module(f"metrics/{name}.py")

    def reference(self, config: str, unit: str):
        return self._module(f"reference/{config}/{unit}.py")

    # -- which metrics a cell reports -------------------------------------
    def _applies(self, metric: dict, cell: str) -> bool:
        return cell in metric.get("workloads", [cell])

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.benchmark["end_to_end"]
                if self._applies(m, cell)]

    def per_layer(self, cell: str) -> list[dict]:
        reported = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.benchmark["per_layer"]
                if m["moves"] in reported and self._applies(m, cell)]
