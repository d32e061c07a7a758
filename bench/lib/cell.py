"""Find a cell's files by the names in BENCHMARK.json.

A cell (`workloads` entry) names a configuration and a traffic mix; each
lives in a file of its own:

    bench/configs/<config>.json      sizes, deployment, engine/trainer knobs
    bench/traffic/<workload>.json    generator kind and its parameters
    bench/metrics/<metric>.py        one reader per per-layer metric

A configuration names its weight maker and its plain reference, modules
under bench/lib/ (`module`).
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    def mesh_shape(self) -> tuple[int, int] | None:
        """(data, model) of the serving mesh that the configuration's
        `engine.mesh` names, None where it names none. ValueError unless
        data x model is the cell's chips."""
        mesh = self.config.get("engine", {}).get("mesh")
        if mesh is None:
            return None
        shape = (mesh.get("data"), mesh.get("model"))
        if (set(mesh) != {"data", "model"}
                or not all(isinstance(n, int) and n > 0 for n in shape)
                or shape[0] * shape[1] != self.chips):
            raise ValueError(
                f"cell {self.name}: engine.mesh {mesh} must be "
                f'{{"data": D, "model": M}} with D x M = chips '
                f"({self.chips})")
        return shape

    def reader(self, metric: str):
        """The `read(run)` function of bench/metrics/<metric>.py."""
        path = BENCH_DIR / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def module(name: str):
    """bench/lib/<name>.py, as a configuration names it."""
    return importlib.import_module(f"bench.lib.{name}")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load(root: Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{workload}.json").read_text())
    return Cell(root=root, name=workload, chips=w["chips"], config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, workload)])
