"""What one run is told: ``BENCHMARK.json``, the cell it names, and the
cell's configuration, traffic and limit files.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     sizes as run, with their source
    bench/families/<family>.py      what the harness knows of a model
                                    family (the config's "family"): its
                                    published keys, plain reference,
                                    weight fan-in and work counts
    bench/traffic/<traffic>.json    parameters for a generator module
                                    named in the file (bench/traffic/)
    bench/limits/<cell>.json        the limits that decide ``correct``
    bench/metrics/<metric>.py       one reader per metric
"""

from __future__ import annotations

import dataclasses
import functools
import json
import sys
from pathlib import Path
from typing import Optional

#: the checkout's root (this file is bench/harness/spec.py)
ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"

#: the CPU rehearsal's serving settings (its widths are the family's)
REHEARSAL_SERVE = {"slots": 4, "max_len": 256}


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict          # bench/configs/<config>.json
    family: object        # bench/families/<family>.py, loaded
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/limits/<cell>.json
    end_to_end: list      # metric entries this cell reports at --trace 0
    per_layer: list       # metric entries this cell reports at --trace 1
    run_seconds: int


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Read ``BENCHMARK.json`` and the files of the cell ``name``;
    raises ``KeyError`` for a name it does not list."""
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    conf_entry = configs[w["config"]]
    bench = root / "bench"
    config = _read_json(root / conf_entry["file"])
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], traffic_name=w["traffic"],
        config=config, family=family(config, root),
        traffic=_read_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(bench / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
        run_seconds=int(spec["run_seconds"]))


def family(config: dict, root: Path = ROOT):
    """The family file that ``config["family"]`` names; raises
    ``FileNotFoundError`` naming the path where there is none."""
    path = root / "bench" / "families" / f"{config['family']}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"configuration {config.get('name')!r} is of family "
            f"{config['family']!r}, but {path} does not exist")
    return _load_family(path)


@functools.lru_cache(maxsize=None)
def _load_family(path: Path):
    return load_module(path, f"bench_family_{path.stem}")


def sizes(config: dict, rehearse: bool = False, root: Path = ROOT) -> dict:
    """The configuration's published-name sizes as run (the rehearsal
    swaps in its family's tiny widths)."""
    out = dict(config)
    if rehearse:
        out.update(family(config, root).REHEARSAL_SIZES)
    return out


def serve_settings(config: dict, rehearse: bool = False) -> dict:
    out = dict(config["serve"])
    if rehearse:
        out.update(REHEARSAL_SERVE)
    return out


def model_config(config: dict, rehearse: bool = False, root: Path = ROOT):
    """The program's ``ModelConfig`` for a configuration file, by its
    family: every size comes from the file, nothing from the program's
    registry."""
    name = config["name"] + ("-rehearsal" if rehearse else "")
    return family(config, root).model_config(sizes(config, rehearse, root),
                                             name)


def metric_module_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "metrics" / f"{name}.py"


def traffic_module_path(generator: str, root: Path = ROOT) -> Path:
    return root / "bench" / "traffic" / f"{generator}.py"


def load_module(path: Path, modname: Optional[str] = None):
    """Import one reader or generator file by path."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        modname or f"bench_{path.stem.replace('-', '_').replace('.', '_')}",
        path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod
