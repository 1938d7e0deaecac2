"""What one cell is, found by name: the ``workloads`` entry of
``BENCHMARK.json``, its configuration file and its traffic file.

Nothing here knows a cell, a configuration or a traffic mix by name: a
later change adds ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` and a ``workloads`` entry, and edits no
file that is already there."""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class CellError(ValueError):
    """A cell, configuration, traffic mix or metric cannot be found."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict         # bench/configs/<config>.json, as run
    traffic: dict        # bench/traffic/<traffic>.json
    end_to_end: tuple    # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` under ``root`` defines it."""
    bench_file = root / "BENCHMARK.json"
    if not bench_file.is_file():
        raise CellError(f"no {bench_file}")
    spec = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in {bench_file}; "
                        f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic_file = root / "bench" / "traffic" / f"{w['traffic']}.json"
    if not traffic_file.is_file():
        raise CellError(f"no traffic file {traffic_file}")
    traffic = json.loads(traffic_file.read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=tuple(m for m in spec["end_to_end"]
                                 if _reports(m, name)),
                per_layer=tuple(m for m in spec["per_layer"]
                                if _reports(m, name)))
