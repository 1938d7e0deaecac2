"""What one cell is, found by name: the ``workloads`` entry of
``BENCHMARK.json``, its configuration file, its traffic file and the
modules its configuration names.

Nothing here or in ``run.py`` knows a cell, a configuration, a traffic
mix or a model family by name.  A configuration file names its plain
reference (``"reference"``: ``bench/reference/<reference>.py``, which
exposes ``Reference``) and the count of its decode step's work
(``"work"``: ``bench/work/<work>.py``, which exposes ``decode_step``);
both are found by name, as a per-layer metric's reader is, under the
root the cell is loaded from, imported once by ``load_cell``, and every
configuration names both.  What such a module imports itself
(``from work import head``, ``from reference.nf4 import ...``) resolves
through ``sys.path`` to the ``bench/`` of the running harness, which in
a checkout is the same root.  So a later change adds
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``, the
modules its configuration names where no module there serves it, and a
``workloads`` entry, and edits no file that is already there."""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class CellError(ValueError):
    """A cell, configuration, traffic mix, named module or metric cannot
    be found."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict         # bench/configs/<config>.json, as run
    traffic: dict        # bench/traffic/<traffic>.json
    end_to_end: tuple    # BENCHMARK.json metric entries this cell reports
    per_layer: tuple
    Reference: type      # of bench/reference/<config["reference"]>.py
    decode_step: object  # of bench/work/<config["work"]>.py


#: configuration key that names a module of bench/<key>/, and what the
#: module exposes
NAMED_MODULES = {"reference": "Reference", "work": "decode_step"}


def named_modules(config: dict, root: Path = ROOT) -> dict[str, Path]:
    """The file of each module ``config`` names, under ``root``."""
    files = {}
    for key in NAMED_MODULES:
        if key not in config:
            raise CellError(f"config {config['name']!r} names no {key!r} "
                            f"module")
        path = root / "bench" / key / f"{config[key]}.py"
        if not path.is_file():
            raise CellError(f"no {key} module {path}, named by config "
                            f"{config['name']!r}")
        files[key] = path
    return files


def load_module(path: Path):
    """Import the file ``path`` as a module of its own."""
    name = f"bench_{path.parent.name}_{path.stem}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def named_objects(config: dict, root: Path = ROOT) -> dict[str, object]:
    """What each module ``config`` names exposes, by that name, each
    module imported once."""
    objects = {}
    for key, path in named_modules(config, root).items():
        attr = NAMED_MODULES[key]
        obj = getattr(load_module(path), attr, None)
        if not callable(obj):
            raise CellError(f"{key} module {path}, named by config "
                            f"{config['name']!r}, exposes no {attr}")
        objects[attr] = obj
    return objects


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
                                if _reports(m, name)),
                **named_objects(config, root))
