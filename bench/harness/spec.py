"""Find a cell and everything it names, by name.

`BENCHMARK.json` lists the cells; a cell names a configuration (its
file is given there) and a traffic mix (`bench/traffic/<name>.json`).
The traffic names its plain reference (`bench/references/<name>.py`),
the cell's correctness limits are `bench/limits/<cell>.json`, and each
per-layer metric is read by `bench/metrics/<name>.py`. Adding a cell,
a configuration, a traffic mix or a metric adds files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    limits: dict          # {number: limit} for the correctness check
    end_to_end: tuple     # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _checked(name: str, what: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"bad {what} name {name!r}")
    return name


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell `name` with its configuration, traffic, limits and the
    metrics it reports. Raises KeyError for an unknown cell."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: "
                       f"{', '.join(sorted(cells))}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic"
                         / f"{_checked(w['traffic'], 'traffic')}.json")
    limits = _load_json(root / "bench" / "limits" / f"{name}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits["limits"],
        end_to_end=tuple(m for m in spec["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _applies(m, name)))


def _module(kind: str, name: str):
    path = BENCH / kind / f"{_checked(name, kind)}.py"
    mod = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                 path)
    out = importlib.util.module_from_spec(mod)
    mod.loader.exec_module(out)
    return out


def metric_reader(name: str):
    """`read(records) -> float | None` of the per-layer metric `name`."""
    return _module("metrics", name).read


def reference(name: str):
    """The plain reference module `name` (see `bench/references`)."""
    return _module("references", name)
