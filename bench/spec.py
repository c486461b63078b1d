"""The benchmark's declaration, ``BENCHMARK.json``, and the files it names.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric sits in a file of its own, found by its name:

    bench/configs/<config>.json      sizes of the model as it is run
    bench/families/<family>.py       the program's configuration of a family
                                     (with ``program.py``, the only files
                                     that import the program)
    bench/reference/<family>.py      the plain float32 reference of a family,
                                     its counts and its paged kernel
    bench/traffic/<traffic>.json     parameters of a traffic mix
    bench/limits/<cell>.json         the limits of the comparison that
                                     decides ``correct`` in a cell
    bench/metrics/<metric>.py        the reader of a per-layer metric

so a new family, configuration, mix, cell or metric is new files plus new
entries in ``BENCHMARK.json``, and no existing file changes.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files and metrics it uses."""
    root: Path
    name: str
    config_name: str
    traffic_name: str
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]

    def _json(self, kind: str, name: str) -> dict:
        return json.loads((self.root / "bench" / kind / f"{name}.json")
                          .read_text())

    @property
    def config(self) -> dict:
        return self._json("configs", self.config_name)

    @property
    def traffic(self) -> dict:
        return self._json("traffic", self.traffic_name)

    @property
    def limits(self) -> dict:
        return self._json("limits", self.name)

    def reference(self) -> ModuleType:
        return load_module(self.root / "bench" / "reference"
                           / f"{self.config['family']}.py")

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py")


@functools.lru_cache(maxsize=None)
def load_module(path: Path) -> ModuleType:
    """Import one file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``; KeyError if absent."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entries: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}; "
                       f"known: {sorted(entries)}")
    w = entries[name]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(root=root, name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                end_to_end=e2e, per_layer=per_layer)
