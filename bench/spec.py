"""Finding a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  A
configuration is the JSON file that its entry names; a traffic mix is
``<bench>/traffic/<name>.json``; the arrival process and the pattern pool
that a mix names are ``<bench>/arrivals/<kind>.py`` and
``<bench>/pools/<kind>.py``; each metric is ``<bench>/metrics/<name>.py``,
a module with ``read(run) -> float | None``.  A later cell adds files and a
``workloads`` entry, and edits none of these.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    bench_dir: pathlib.Path
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]

    def module(self, group: str, name: str):
        return load_module(self.bench_dir / group / f"{name}.py")


def load_module(path: pathlib.Path):
    """Import one plug-in file by its path (names may hold '.' or '-')."""
    tag = hashlib.sha1(str(path).encode()).hexdigest()[:12]
    spec = importlib.util.spec_from_file_location(f"_bench_{tag}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: pathlib.Path | None = None) -> Cell:
    """The cell ``name`` of ``spec_path`` (the repository's
    ``BENCHMARK.json`` by default), with its configuration and traffic."""
    spec_path = pathlib.Path(spec_path or ROOT / "BENCHMARK.json")
    root = spec_path.parent
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    bench_dir = root / spec["paths"][0]
    traffic = json.loads((bench_dir / "traffic" / f"{cell['traffic']}.json").read_text())
    return Cell(
        name=name,
        chips=int(cell["chips"]),
        config=config,
        traffic=traffic,
        bench_dir=bench_dir,
        end_to_end=tuple(Metric(m["name"], m["unit"])
                         for m in spec["end_to_end"] if _reports(m, name)),
        per_layer=tuple(Metric(m["name"], m["unit"])
                        for m in spec["per_layer"] if _reports(m, name)),
    )
