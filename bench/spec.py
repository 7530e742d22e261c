"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own, found from ``BENCHMARK.json`` at the checkout's root:

* a configuration: the JSON file named by its ``file`` entry;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* the generator a configuration names: ``bench/generators/<name>.py``, whose
  ``generate(...)`` makes the operand (``bench/operand.py``);
* the operation a traffic mix names: ``bench/operations/<name>.py``, whose
  ``operands(op)`` and ``program(...)`` give the product's host operands and
  the system under test (``bench/operations/square.py``);
* a metric: ``bench/metrics/<name>.py``, whose ``read(ctx)`` returns the
  metric's value, or None where the run has nothing to read it from.

A cell, configuration, generator, traffic mix, operation or metric is added
by adding its files and its entry in ``BENCHMARK.json``; no file of the
harness changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = "bench"


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    reader: object  # module with read(ctx)
    layer: str = ""
    moves: str = ""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: object  # module with generate(...)
    operation: object  # module with operands(op) and program(...)
    end_to_end: tuple  # Metric, in BENCHMARK.json order
    per_layer: tuple


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


class Benchmark:
    def __init__(self, root):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._modules: dict = {}

    def _module(self, kind: str, name: str):
        """``bench/<kind>/<name>.py`` under the root, loaded once."""
        key = (kind, name)
        if key not in self._modules:
            path = self.root / BENCH_DIR / kind / f"{name}.py"
            if not path.is_file():
                raise FileNotFoundError(f"{kind}: no file {path} for {name!r}")
            mod_spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{len(self._modules)}", path)
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def _metric(self, entry: dict) -> Metric:
        return Metric(
            name=entry["name"], unit=entry["unit"], better=entry["better"],
            source=entry["source"],
            reader=self._module("metrics", entry["name"]),
            layer=entry.get("layer", ""), moves=entry.get("moves", ""),
        )

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        path = self.root / BENCH_DIR / "traffic" / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"traffic mix {name!r} has no file {path}")
        return json.loads(path.read_text())

    def cell_names(self) -> list:
        return [w["name"] for w in self.spec["workloads"]]

    def cell(self, name: str) -> Cell:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                break
        else:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {self.cell_names()})")
        config, traffic = self.config(w["config"]), self.traffic(w["traffic"])
        return Cell(
            name=name, chips=int(w["chips"]), config=config, traffic=traffic,
            generator=self._module("generators", config["generator"]),
            operation=self._module("operations", traffic["operation"]),
            end_to_end=tuple(self._metric(m) for m in self.spec["end_to_end"]
                             if _applies(m, name)),
            per_layer=tuple(self._metric(m) for m in self.spec["per_layer"]
                            if _applies(m, name)),
        )
