"""Finds a cell's pieces by the names in ``BENCHMARK.json``: the
configuration file and its architecture (``bench/arch/<model_type>.py``),
the traffic file (``bench/traffic/<traffic>.json``) and the metrics that
apply to the cell."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from bench import arch

ROOT = Path(__file__).resolve().parent.parent
TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def model_dims(c: dict) -> dict:
    """Model dimensions from a configuration file, by the module of its
    ``model_type`` (``bench/arch``), with ``model_type`` among them."""
    dims = dict(arch.load(c["model_type"]).dims(c),
                model_type=c["model_type"])
    try:
        hash(tuple(sorted(dims.items())))
    except TypeError:
        raise TypeError(f"{c['model_type']} dims must all be hashable "
                        f"(scalars, or tuples per layer): {dims}") from None
    return dims


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    dims: dict
    traffic_name: str
    traffic: dict
    end_to_end: list           # metric entries that apply to this cell
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, root: Path = ROOT, bench_file: str = "BENCHMARK.json"
         ) -> Cell:
    """The cell ``name`` of ``bench_file`` (a file in the form of
    ``BENCHMARK.json``, relative to ``root``)."""
    bench = json.loads((root / bench_file).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    from bench import traffic
    spec = traffic.load(TRAFFIC_DIR / f"{w['traffic']}.json")
    return Cell(name, int(w["chips"]), w["config"], config,
                model_dims(config), w["traffic"], spec,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def run_seconds(root: Path = ROOT) -> int:
    return int(json.loads((root / "BENCHMARK.json").read_text())["run_seconds"])
